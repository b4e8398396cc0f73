"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once through the public entry points, at the
full width of the models the repo is measured on, and checks what comes out:

- **Gate.** Print jax version, platform, ``device_kind``, device count, the
  resolved compile-cache dir. No TPU -> message and non-zero exit; no result
  line. Pallas must compile (``_interpret()`` False).
- **Leg A — trainer, full width.** ResNet-50 bf16, 224x224x3, 1000 classes,
  batch 128: ``init -> warmup -> fit_on_device -> fit() -> output()``.
- **Leg B — trainer on the kernel route.** char-RNN 2x512 bf16, B=64, T=256,
  adam: which variant ``lstm_seq`` / ``softmax_xent`` / ``optimizer`` resolved
  to, and parity with a twin trained under ``set_mode("reference")``.
- **Leg C — server.** ``InferenceService`` + ``POST /serving/predict`` with
  mixed row counts + one ``/serving/rnn`` session; donated request buffers;
  hot-swap from a net that keeps training.
- **Leg D — every Pallas kernel, compiled, f32 and bf16**, at the shapes legs
  A/B feed them, each against its XLA reference.
- **Leg E — four chips** (only when ``len(jax.devices()) >= 4``; otherwise
  reported as *not run*, never as passed): data-parallel and data x fsdp
  ``ParallelWrapper.fit_on_device``.
- **Leg F — the hybrid state-space / attention / expert blocks at the
  published widths** (``benchmarks/configs/nemotron3_nano_30b_a3b``, one
  sequence of 8192 positions): one Mamba-2, one grouped-query attention and
  one expert block and the head's loss, each as the engine runs it (bfloat16
  compute over float32 masters) against the float32 plain reference: forward
  outputs, every parameter's gradient and the input's. Then a control: the
  Mamba block once more with the scan's decays and state in bfloat16, which
  has to FAIL its tolerance, or the comparison would pass lower precision.

- **Leg G — the latent attention, gated feed-forward, gated expert and
  hyper-connection blocks at the published widths**
  (``benchmarks/configs/xing4_29b_a4b``, one sequence of 8192 positions, 4
  heads and 8 experts held, a 4-stream residual): each as the engine runs it
  against the float32 plain reference, like leg F. Two controls that have to
  FAIL: the attention block with its rotary angles rounded to bfloat16, and
  the hyper-connection block with its maps' projection and Sinkhorn in
  bfloat16.

- **Leg H — the Kimi Delta Attention block, the position-free latent
  attention block and the expert block at the published widths**
  (``benchmarks/configs/kimi_linear_48b_a3b``, one sequence of 8192
  positions): each as the engine runs it, in bfloat16 over float32 masters,
  and the delta-rule block in float32 too, against the float32 plain
  reference (the delta rule one position at a time), like leg F; the expert
  block's input is rounded once for both sides, so both route alike. And the
  recurrence by itself (``ops/kda.py kda_recurrence`` on the operands the
  block hands it: bfloat16 ``q``, ``k``, ``v``, float32 decays and steps)
  against the delta rule one position at a time, as the cell holds it before
  it times a step. A control that has to FAIL: the recurrence with its
  running sums, solved system and carried state rounded to bfloat16.

Legs are plain functions taking sizes: ``tests/test_chip_smoke.py`` calls them
tiny on the CPU (interpret-mode kernels); ``__main__`` runs them at full width
and REQUIRES the chip. Run every leg: ``python chip_smoke.py``; while
debugging: ``python chip_smoke.py --legs D,B``. A failed leg names itself and
the process exits non-zero. Last stdout line on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One process, no children, no platform override in code: a chip belongs to one
process at a time.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time
import traceback
import urllib.request

import numpy as np

LEGS = ("A", "B", "C", "D", "E", "F", "G", "H")


class LegFailure(AssertionError):
    """A leg's check did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise LegFailure(message)


# --------------------------------------------------------------- monitoring
class _Monitors:
    """jax.monitoring listeners (they cannot be unregistered, so one set per
    process): every backend compile request with its seconds, and the
    persistent compilation cache's hits and misses."""

    def __init__(self):
        from jax import monitoring

        self.backend_compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, seconds, **_kw):
        if name.endswith("backend_compile_duration"):
            self.backend_compiles += 1
            self.compile_seconds += float(seconds)

    def _on_event(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"backend_compiles": self.backend_compiles,
                "compile_seconds": round(self.compile_seconds, 3),
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}


_MONITORS = None


def monitors() -> _Monitors:
    global _MONITORS
    if _MONITORS is None:
        _MONITORS = _Monitors()
    return _MONITORS


@contextlib.contextmanager
def counting():
    """Compile activity inside the block: a dict, filled when it ends."""
    before, out = monitors().snapshot(), {}
    try:
        yield out
    finally:
        after = monitors().snapshot()
        out.update({k: round(after[k] - before[k], 3) for k in after})


def _manager():
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    return get_compile_manager()


def _admission_state() -> dict:
    """Every AOT executable the manager admitted must carry a static-cost
    record, and no admission-time analysis may have failed."""
    cm = _manager()
    stats = cm.stats()
    return {"aot_entries": len(cm.memory_records()),
            "cost_records": len(cm.cost_records()),
            "admission_errors": stats["admission_errors"],
            "compiles_total": stats["compiles_total"]}


def _check_admission(leg: str) -> dict:
    st = _admission_state()
    check(st["admission_errors"] == 0,
          f"leg {leg}: {st['admission_errors']} admission check(s) raised "
          "(see the flight recorder's admission_error events)")
    check(st["cost_records"] == st["aot_entries"] and st["aot_entries"] > 0,
          f"leg {leg}: {st['cost_records']} cost records for "
          f"{st['aot_entries']} AOT executables — the admission check did "
          "not produce a record for each")
    return st


def _one_hot(rng, classes: int, shape) -> np.ndarray:
    return np.eye(classes, dtype=np.float32)[rng.integers(0, classes, shape)]


def _tree_np(tree):
    import jax

    return [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(tree)]


# --------------------------------------------------------------------- gate
def device_info() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def gate(require_tpu: bool = True) -> dict:
    """Say what we run on; refuse to go on without the chip."""
    import jax

    from deeplearning4j_tpu.analysis.cost_model import roofline_params
    from deeplearning4j_tpu.ops import kernel_select as ks
    from deeplearning4j_tpu.ops.pallas_kernels import _interpret
    from deeplearning4j_tpu.runtime import native_available
    from deeplearning4j_tpu.runtime.compile_manager import (
        CACHE_DIR_ENV, resolve_persistent_cache)
    from deeplearning4j_tpu.tune import store as tuned

    monitors()
    info = device_info()
    cache_dir = resolve_persistent_cache()
    print(f"jax {jax.__version__}  platform={info['platform']}  "
          f"device_kind={info['kind']!r}  devices={info['count']}")
    print(f"compile cache: {cache_dir} "
          f"({CACHE_DIR_ENV} {'set' if os.environ.get(CACHE_DIR_ENV) else 'unset'})")
    if require_tpu and info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (platform is {info['platform']!r}); "
              "this script measures nothing on a CPU", file=sys.stderr)
        raise SystemExit(4)  # not 2/3: the chip tool uses those itself
    if require_tpu:
        check(not _interpret(), "Pallas kernels would run in interpret mode")
    rl = roofline_params()  # an unknown TPU device_kind raises here
    print(f"peaks: {rl['device_kind']!r} assumed={rl['assumed']} "
          f"{rl['peak_flops'] / 1e12:.0f} TFLOP/s {rl['hbm_gbps']:.0f} GB/s "
          f"HBM {rl['ici_gbps']:.0f} GB/s ICI")
    if require_tpu:
        check(not rl["assumed"] and rl["device_kind"] == info["kind"],
              "roofline_params() did not resolve the attached device's row")
    cal = ks.stats()["calibration"]
    print(f"kernel calibration: entries={cal['entries']} path={cal['path']}")
    check(cal["entries"] == 0,
          f"stale {cal['path']} would steer kernel selection; remove it")
    check(not os.path.exists(tuned.tuned_path()),
          f"{tuned.tuned_path()} exists; the smoke must not depend on it")
    print(f"native data loader: "
          f"{'built (g++)' if native_available() else 'python fallback'}")
    return info


def probe_dispatch(n: int = 8192, chain: int = 8, reps: int = 30) -> dict:
    """Informational, no claim: does ``block_until_ready`` wait for the
    device, and what does one dispatch cost? A matmul chain of known FLOPs is
    timed three ways (enqueue only / + block_until_ready / + host fetch of a
    scalar); a trivial program is timed round-trip."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def work(x):
        for _ in range(chain):
            x = (x @ x) * (1.0 / n)
        return x, x[0, 0].astype(jnp.float32)

    x = jnp.ones((n, n), jnp.bfloat16)
    jax.block_until_ready(work(x))
    t0 = time.perf_counter()
    out = work(x)
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready(out)
    block_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(np.asarray(work(x)[1]))
    fetch_s = time.perf_counter() - t0

    tiny = jax.jit(lambda a: a + 1.0)
    a = jnp.zeros((), jnp.float32)
    jax.block_until_ready(tiny(a))
    trips = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(tiny(a))
        trips.append(time.perf_counter() - t0)
    flops = chain * 2.0 * n ** 3
    res = {
        "matmul_chain_tflop": round(flops / 1e12, 3),
        "enqueue_ms": round(1e3 * enqueue_s, 3),
        "block_until_ready_ms": round(1e3 * block_s, 3),
        "host_fetch_ms": round(1e3 * fetch_s, 3),
        "achieved_tflops_by_block": round(flops / block_s / 1e12, 1),
        "block_until_ready_synchronizes": bool(block_s >= 0.8 * fetch_s),
        "tiny_dispatch_roundtrip_ms_median": round(
            1e3 * float(np.median(trips)), 4),
    }
    print("probe_dispatch:", json.dumps(res))
    return res


# -------------------------------------------------------------------- leg A
def leg_a_trainer(conf, image: int, classes: int, batch: int,
                  staged_steps: int = 3, batch_steps: int = 2) -> dict:
    """Trainer through the public staged path, the donated per-batch step
    and the inference fast path, for a ComputationGraph conf."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph

    cm = _manager()
    t_leg = time.perf_counter()
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(2, batch, image, image, 3)).astype(np.float32)
    ys = _one_hot(rng, classes, (2, batch))
    probe_leaf = np.asarray(jax.tree_util.tree_leaves(net.params)[0],
                            np.float32).copy()

    with counting() as warm:
        net.warmup(xs, ys, steps=staged_steps)
    print(f"  warmup: {warm}")

    # staged path: nothing may compile after warmup
    c0 = cm.stats()["compiles_total"]
    with counting() as first:
        t0 = time.perf_counter()
        losses = net.fit_on_device(xs, ys, steps=staged_steps)
        first_s = time.perf_counter() - t0
    with counting() as steady:
        t0 = time.perf_counter()
        losses2 = net.fit_on_device(xs, ys, steps=staged_steps)
        steady_s = time.perf_counter() - t0
    check(cm.stats()["compiles_total"] == c0,
          "fit_on_device compiled after warmup (compile manager)")
    check(steady["backend_compiles"] == 0,
          f"steady fit_on_device hit the backend compiler: {steady}")
    check(np.all(np.isfinite(losses)) and np.all(np.isfinite(losses2)),
          f"non-finite staged losses {losses} {losses2}")
    check(losses.shape == (staged_steps,), f"losses shape {losses.shape}")
    print(f"  fit_on_device x{staged_steps}: losses {np.round(losses, 4)} "
          f"then {np.round(losses2, 4)}; first dispatch {first_s:.3f}s "
          f"{first}, steady {steady_s:.3f}s "
          f"({1e3 * steady_s / staged_steps:.1f} ms/step incl. fetch)")

    # per-batch fit(): the donated _build_train_step program
    with counting() as step_compile:
        net.fit((xs[0], ys[0]))
    with counting() as again:
        for i in range(batch_steps):
            net.fit((xs[i % 2], ys[i % 2]))
    loss_b = net.score()
    check(np.isfinite(loss_b), f"non-finite per-batch loss {loss_b}")
    check(again["backend_compiles"] == 0,
          f"per-batch fit() recompiled on a seen shape: {again}")
    print(f"  fit() per-batch: first {step_compile}, "
          f"{batch_steps} more steps loss {loss_b:.4f} {again}")

    after = np.asarray(jax.tree_util.tree_leaves(net.params)[0], np.float32)
    check(np.all(np.isfinite(after)) and not np.array_equal(after, probe_leaf),
          "params did not change (or went non-finite) under training")

    # inference fast path; the SAME device array twice (request buffers are
    # donated — the caller's array must survive)
    x_dev = jnp.asarray(xs[0])
    out1 = net.output(x_dev)
    with counting() as repeat:
        out2 = net.output(x_dev)
    check(repeat["backend_compiles"] == 0,
          "output() recompiled on a seen shape")
    check(out1.shape == (batch, classes), f"output shape {out1.shape}")
    o1 = np.asarray(out1, np.float32)
    check(np.all(np.isfinite(o1))
          and np.array_equal(o1, np.asarray(out2, np.float32)),
          "output() not finite / not repeatable on the same device array")
    check(np.allclose(o1.sum(-1), 1.0, atol=2e-2), "softmax rows do not sum to 1")
    np.asarray(x_dev)  # raises if the request buffer was donated away

    adm = _check_admission("A")
    print(f"  admission: {adm}")
    return {"losses": [float(l) for l in losses],
            "warmup": warm, "steady_ms_per_step_with_fetch":
                round(1e3 * steady_s / staged_steps, 2),
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# -------------------------------------------------------------------- leg B
_LEG_B_SITES = ("lstm_seq", "softmax_xent", "optimizer")
_REFERENCE_VARIANTS = {"reference", "xla"}  # every site's XLA path


def _char_rnn_net(vocab, hidden, layers, dtype):
    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.models.char_rnn import char_rnn

    conf = char_rnn(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    dtype=dtype)
    conf.backprop_type = "standard"  # fit_on_device trains full sequences
    return MultiLayerNetwork(conf).init()


def _char_batches(vocab, batch, seq, slots=2, seed=0):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, vocab, size=(slots, batch, seq + 1))
    eye = np.eye(vocab, dtype=np.float32)
    return eye[idx[:, :, :-1]], eye[idx[:, :, 1:]]


def _update_cosines(p0, p_a, p_b):
    """Per-leaf cosine between the two nets' total updates (p - p0). Adam
    normalizes gradients, so low-precision noise flips a few near-zero
    elements by a whole step; a wrong kernel decorrelates the update."""
    cos = []
    for a0, a, b in zip(p0, p_a, p_b):
        da, db = (a - a0).ravel(), (b - a0).ravel()
        cos.append(float(da @ db / (np.linalg.norm(da) * np.linalg.norm(db)
                                    + 1e-30)))
    return cos


def leg_b_kernel_route(vocab: int = 96, hidden: int = 512, layers: int = 2,
                       batch: int = 64, seq: int = 256, steps: int = 3,
                       dtype: str = "bfloat16", require_fused: bool = True,
                       loss_rtol: float = 3e-2, min_cosine: float = 0.9):
    """char-RNN through ``fit_on_device`` with kernels chosen by the default
    selection, then a twin under ``set_mode("reference")``. Returns
    ``(info, trained_net, (xs, ys))`` — leg C serves the trained net and
    trains it on with the same staged batches (same executable)."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    t_leg = time.perf_counter()
    ks.reset()  # selections cache per shape key: start the log clean
    xs, ys = _char_batches(vocab, batch, seq)
    net = _char_rnn_net(vocab, hidden, layers, dtype)
    p0 = _tree_np(net.params)
    with counting() as warm:
        net.warmup(xs, ys, steps=steps)
    t0 = time.perf_counter()
    losses = net.fit_on_device(xs, ys, steps=steps)
    run_s = time.perf_counter() - t0
    check(np.all(np.isfinite(losses)), f"non-finite losses {losses}")

    chosen = {}
    for rec in ks.selection_log():
        if rec["site"] in _LEG_B_SITES:
            chosen.setdefault(rec["site"], []).append(rec)
            print(f"  kernel_select {rec['site']}: {rec['variant']} "
                  f"(reason={rec['reason']}, mode={rec['mode']}"
                  + (f", infeasible={rec['infeasible']}"
                     if rec.get("infeasible") else "") + f") ctx={rec['ctx']}")
    for site in _LEG_B_SITES:
        check(site in chosen, f"site {site} was never consulted")
        for rec in chosen[site]:
            check(rec["reason"] != "fallback" and not rec.get("infeasible"),
                  f"site {site} gave way to {rec['variant']}: fused variant(s) "
                  f"{rec.get('infeasible')} infeasible at {rec['ctx']}")
            if require_fused:
                check(rec["variant"] not in _REFERENCE_VARIANTS,
                      f"site {site} resolved to {rec['variant']} "
                      f"({rec['reason']}), expected a fused Pallas variant")

    ks.set_mode("reference")
    try:
        twin = _char_rnn_net(vocab, hidden, layers, dtype)
        losses_ref = twin.fit_on_device(xs, ys, steps=steps)
    finally:
        ks.set_mode(None)
    ref_variants = {r["variant"] for r in ks.selection_log()
                    if r["mode"] == "reference"}
    check(ref_variants <= _REFERENCE_VARIANTS,
          f"reference twin ran fused kernels: {ref_variants}")
    check(np.allclose(losses, losses_ref, rtol=loss_rtol),
          f"loss trajectories diverge: {losses} vs reference {losses_ref}")
    p_a, p_b = _tree_np(net.params), _tree_np(twin.params)
    cos = _update_cosines(p0, p_a, p_b)
    max_abs = max(float(np.max(np.abs(a - b))) for a, b in zip(p_a, p_b))
    print(f"  losses {np.round(losses, 4)} vs reference "
          f"{np.round(losses_ref, 4)}; update cosine per leaf min "
          f"{min(cos):.4f}; max |param diff| {max_abs:.2e}; "
          f"{steps} steps {run_s:.3f}s; warmup {warm}")
    check(min(cos) >= min_cosine,
          f"param updates decorrelate from the reference twin: {cos}")
    adm = _check_admission("B")
    info = {"variants": {s: sorted({r["variant"] for r in chosen[s]})
                         for s in _LEG_B_SITES},
            "reasons": {s: sorted({r["reason"] for r in chosen[s]})
                        for s in _LEG_B_SITES},
            "losses": [float(l) for l in losses],
            "min_update_cosine": round(min(cos), 4), "warmup": warm,
            "admission": adm,
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}
    return info, net, (xs, ys)


# -------------------------------------------------------------------- leg C
def _post(port: int, path: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def leg_c_server(trainer, batches, steps: int = 3, seq: int = 32,
                 row_cap: int = 8, request_rows=(1, 3, 8, 5, 2),
                 atol: float = 2e-2) -> dict:
    """Serve a clone of ``trainer`` (a recurrent MultiLayerNetwork) over
    HTTP while the trainer keeps training on donated buffers. ``batches``
    and ``steps`` are what the trainer was staged with (same executable)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
    from deeplearning4j_tpu.serving import (InferenceService, reset_services,
                                            set_service)
    from deeplearning4j_tpu.ui.server import UIServer

    cm = _manager()
    t_leg = time.perf_counter()
    rng = np.random.default_rng(1)
    xs, ys = batches
    vocab = int(xs.shape[-1])

    def request(rows):
        return _one_hot(rng, vocab, (rows, seq))

    def predict(x):
        return np.asarray(_post(ui.port, "/serving/predict", {
            "model": "smoke", "features": x.tolist()})["output"], np.float32)

    def rnn(op, **fields):
        return _post(ui.port, "/serving/rnn",
                     {"model": "smoke", "op": op, **fields})

    served = trainer.clone()
    svc = InferenceService(max_batch=row_cap)
    set_service(svc)
    ui = UIServer(port=0)
    try:
        svc.register("smoke", served)
        with counting() as warm:
            warmed = svc.warmup("smoke", request(1))
        print(f"  warmup: {warmed} row buckets <= {row_cap}: {warm}")
        # the decode stream's own program (slot batch x 1 step)
        sid = rnn("open")["session"]
        frame = request(1)[0, 0]
        first = rnn("step", session=sid, features=frame.tolist())["output"]

        c0 = cm.stats()["compiles_total"]
        with counting() as live:
            for rows in request_rows:
                x = request(rows)
                got = predict(x)
                want = np.asarray(served.output(x), np.float32)
                check(got.shape == (rows, seq, vocab),
                      f"served shape {got.shape}")
                check(np.all(np.isfinite(got))
                      and np.allclose(got, want, atol=1e-6),
                      f"/serving/predict rows={rows} differs from net.output: "
                      f"max {np.max(np.abs(got - want)):.3e}")
            second = rnn("step", session=sid,
                         features=frame.tolist())["output"]
            rnn("close", session=sid)
        check(cm.stats()["compiles_total"] == c0
              and live["backend_compiles"] == 0,
              f"serving compiled after warmup: {live}")
        # the session alone on a twin must see the same two steps
        solo = trainer.clone()
        want1 = np.asarray(solo.rnn_time_step(frame[None]), np.float32)[0]
        want2 = np.asarray(solo.rnn_time_step(frame[None]), np.float32)[0]
        check(np.allclose(first, want1, atol=atol)
              and np.allclose(second, want2, atol=atol),
              "/serving/rnn session differs from rnn_time_step run alone: "
              f"{np.max(np.abs(np.asarray(first) - want1)):.3e} "
              f"{np.max(np.abs(np.asarray(second) - want2)):.3e}")
        print(f"  {len(request_rows)} predict requests rows={request_rows} "
              f"+ 1 rnn session match net.output / rnn_time_step; {live}")

        # donated request buffers: the caller's device array must survive
        x_dev = jnp.asarray(request(4))
        o1 = np.asarray(served.output(x_dev), np.float32)
        o2 = np.asarray(served.output(x_dev), np.float32)
        check(np.array_equal(o1, o2), "output(x) twice on one device array differs")
        x_host = np.asarray(x_dev)  # raises if donation deleted it

        # the trainer keeps training (its buffers are donated); the clone
        # must keep serving, then take the new params by hot swap
        trainer.fit_on_device(xs, ys, steps=steps)
        before = np.asarray(served.output(x_host), np.float32)
        check(np.array_equal(before, o1),
              "serving changed although only the trainer stepped")
        snap = CheckpointStore.snapshot(trainer)
        c0 = cm.stats()["compiles_total"]
        svc.hot_swap("smoke", params=snap.params, state=snap.state, version=1)
        trainer.fit_on_device(xs, ys, steps=steps)  # donates what it holds
        after = predict(x_host)
        check(np.all(np.isfinite(after)) and not np.array_equal(after, before),
              "hot swap did not change what is served")
        check(cm.stats()["compiles_total"] == c0,
              "hot swap (or serving after it) compiled")
        print("  donated request buffer survived; hot swap served new params "
              "with zero compiles while the trainer stepped on")
        stats = svc.stats()["models"]["smoke"]
    finally:
        ui.stop()
        reset_services()
    adm = _check_admission("C")
    return {"buckets_warmed": warmed, "swaps": stats["swaps_total"],
            "requests": stats["requests_total"],
            "admission": adm,
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# -------------------------------------------------------------------- leg D
def _err(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b) / (np.abs(b) + 1.0)))


def _err_norm(a, b) -> float:
    """Norm-wise error for gradients: sums over many terms cancel, so an
    element near zero carries the noise of the whole sum."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1.0))


def _tol(dtype, long_sequence: bool = False) -> float:
    """bf16 carries ~3 significant digits; over hundreds of recurrent steps
    the rounding compounds, so the full-length bf16 LSTM check is a
    compile-and-sanity check and the short one the numerics check."""
    import jax.numpy as jnp

    if jnp.dtype(dtype) != jnp.bfloat16:
        return 3e-3
    return 2e-1 if long_sequence else 8e-2


def _named(name: str, fn):
    """Run one sub-check; a failure carries its name (one leg-D line can
    hide several kernels)."""
    try:
        return fn()
    except Exception as e:
        e.add_note(f"in sub-check {name}")
        raise


def _kd_lstm(T, B, H, dtype):
    """Whole-sequence fused LSTM (plain + masked, fwd + every grad) and the
    per-step fused cell vs autodiff through lax.scan of the same math."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(5)
    r = lambda *sh, s=0.3: jnp.asarray(rng.normal(size=sh) * s, dtype)  # noqa: E731
    zx, h0, c0 = r(T, B, 4 * H), r(B, H), r(B, H)
    RW = r(H, 4 * H, s=0.05)
    pF, pI, pO = r(H, s=0.1), r(H, s=0.1), r(H, s=0.1)
    mask = jnp.asarray((rng.random((T, B, 1)) > 0.25), dtype)
    f32 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)

    def scan_ref(zx, h0, c0, RW, pF, pI, pO, m=None):
        def step(carry, inp):
            h, c = carry
            z, mt = inp
            h2, c2, *_ = pk._cell_math(z, h, c, RW, pF, pI, pO,
                                       jnp.tanh, jax.nn.sigmoid)
            h2, c2 = mt * h2 + (1 - mt) * h, mt * c2 + (1 - mt) * c
            return (h2, c2), h2
        mm = jnp.ones((zx.shape[0], 1, 1), zx.dtype) if m is None else m
        (hT, cT), ys = jax.lax.scan(step, (h0, c0), (zx, mm))
        return ys, hT, cT

    def loss_of(fn):
        def loss(*a):
            ys, hT, cT = fn(*a)
            return (jnp.sum(ys.astype(jnp.float32) ** 2)
                    + jnp.sum(hT.astype(jnp.float32))
                    + jnp.sum(jnp.tanh(cT.astype(jnp.float32))))
        return loss

    args = (zx, h0, c0, RW, pF, pI, pO)
    out = {}
    plain = lambda *a: pk.fused_lstm_sequence(*a, "tanh", "sigmoid")  # noqa: E731
    masked = lambda *a: pk.fused_lstm_sequence_masked(  # noqa: E731
        a[0], mask, *a[1:], "tanh", "sigmoid")
    for name, fused, ref in (
            ("seq", plain, lambda *a: scan_ref(*f32(a))),
            ("seq_masked", masked,
             lambda *a: scan_ref(*f32(a), m=mask.astype(jnp.float32)))):
        out[f"{name}_fwd"] = max(map(
            _err, _named(f"{name}_fwd", lambda: jax.jit(fused)(*args)),
            ref(*args)))
        g = _named(f"{name}_grad", lambda: jax.jit(jax.grad(
            loss_of(fused), argnums=tuple(range(7))))(*args))
        gr = jax.grad(loss_of(ref), argnums=tuple(range(7)))(*args)
        out[f"{name}_grad"] = max(map(_err_norm, g, gr))
    cell = lambda z, h, c: pk.fused_lstm_cell(z, h, c, RW, pF, pI, pO)  # noqa: E731
    cref = lambda z, h, c: pk._cell_math(  # noqa: E731
        *f32((z, h, c, RW, pF, pI, pO)), jnp.tanh, jax.nn.sigmoid)[:2]
    out["cell_fwd"] = max(map(
        _err, _named("cell_fwd", lambda: jax.jit(cell)(zx[0], h0, c0)),
        cref(zx[0], h0, c0)))
    gl = lambda fn: jax.grad(lambda *a: jnp.sum(  # noqa: E731
        fn(*a)[0].astype(jnp.float32) ** 2), argnums=(0, 1, 2))
    out["cell_grad"] = max(map(
        _err_norm,
        _named("cell_grad", lambda: jax.jit(gl(cell))(zx[0], h0, c0)),
        gl(cref)(zx[0], h0, c0)))
    return out


def _kd_sxent(N, C, dtype):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_kernels import fused_softmax_xent

    rng = np.random.default_rng(N + C)
    x = jnp.asarray(rng.normal(size=(N, C)) * 2.0, dtype)
    lab = jnp.asarray(_one_hot(rng, C, (N,)), dtype)
    w = jnp.asarray(rng.random(N) + 0.5, jnp.float32)

    def ref(x, lab):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.sum(lab.astype(jnp.float32) * logp, axis=-1)

    out = {"fwd": _err(jax.jit(fused_softmax_xent)(x, lab), ref(x, lab))}
    g = jax.jit(jax.grad(lambda x, l: jnp.sum(fused_softmax_xent(x, l) * w),
                         argnums=(0, 1)))(x, lab)
    gr = jax.grad(lambda x, l: jnp.sum(ref(x, l) * w), argnums=(0, 1))(x, lab)
    out["grad"] = max(map(_err_norm, g, gr))
    return out


def _kd_adam(shape, dtype):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_kernels import fused_adam_update

    rng = np.random.default_rng(int(np.prod(shape)))
    g = jnp.asarray(rng.normal(size=shape), dtype)
    m = jnp.asarray(rng.normal(size=shape) * 0.1, dtype)
    v = jnp.asarray(rng.random(shape) * 0.1, dtype)
    b1, b2, eps, lr, t = 0.9, 0.999, 1e-8, 1e-3, 3
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    u, m2, v2 = jax.jit(lambda g, m, v: fused_adam_update(
        g, m, v, jnp.float32(lr), jnp.float32(bc1), jnp.float32(bc2),
        b1, b2, eps))(g, m, v)
    gf, mf, vf = (np.asarray(a, np.float32) for a in (g, m, v))
    m_ref = b1 * mf + (1 - b1) * gf
    v_ref = b2 * vf + (1 - b2) * gf * gf
    u_ref = -lr * (m_ref / bc1) / (np.sqrt(v_ref / bc2) + eps)
    check(u.shape == tuple(shape) and u.dtype == g.dtype,
          f"adam update shape/dtype {u.shape} {u.dtype}")
    # the update is O(lr): compare it relative to lr, moments as they are
    return {"update": _err(np.asarray(u, np.float32) / lr, u_ref / lr),
            "m": _err(m2, m_ref), "v": _err(v2, v_ref)}


def _flash_reference_by_head(q, k, v, causal, kmask):
    """The XLA attention in float32, one query head at a time (all 32 heads
    of the cell's shape at once would materialise 8.6 GB of float32 scores):
    the output and the gradients of ``sum(out ** 2)``, a shared key/value
    head's summed over its query heads."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.ring_attention import attention

    H, Hkv = q.shape[1], k.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))

    def head(i):
        take = lambda a, j: jax.lax.dynamic_slice_in_dim(a, j, 1, axis=1)  # noqa: E731
        out, vjp = jax.vjp(
            lambda *a: attention(*a, causal=causal, key_mask=kmask),
            take(q, i), take(k, i // (H // Hkv)), take(v, i // (H // Hkv)))
        return out[:, 0], tuple(g[:, 0] for g in vjp(2 * out))

    out, (dq, dk, dv) = jax.lax.map(head, jnp.arange(H))   # [H, B, T, D]
    shared = lambda g: jnp.moveaxis(  # noqa: E731
        g.reshape(Hkv, H // Hkv, *g.shape[1:]).sum(axis=1), 0, 1)
    return jnp.moveaxis(out, 0, 1), (jnp.moveaxis(dq, 0, 1), shared(dk),
                                     shared(dv))


def _kd_flash(B, H, T, D, dtype, kv_heads=None, causal_only=False):
    """The flash kernels, forward and gradients, against the XLA reference:
    with a random key mask, causal and not; ``causal_only`` is the hybrid
    cell's call (causal, no key mask, ``kv_heads`` shared heads)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(B, kv_heads or H, T, D)), dtype)
            for _ in range(2))
    kmask = None if causal_only else jnp.asarray(rng.random((B, T)) > 0.2)
    out = {}
    # f32 matmul precision: with the MXU's default bf16 multiply flash-vs-XLA
    # causal grads differ ~2% from arithmetic alone, masking logic bugs
    with jax.default_matmul_precision("float32"):
        for causal in (True,) if causal_only else (False, True):
            fl = lambda q, k, v: flash_attention(  # noqa: E731
                q, k, v, causal=causal, key_mask=kmask)
            gl = jax.grad(lambda *a: jnp.sum(  # noqa: E731
                fl(*a).astype(jnp.float32) ** 2), argnums=(0, 1, 2))
            ref, ref_grads = jax.jit(functools.partial(
                _flash_reference_by_head, causal=causal, kmask=kmask))(q, k, v)
            out[f"fwd_causal={causal}"] = _err(jax.jit(fl)(q, k, v), ref)
            out[f"grad_causal={causal}"] = max(map(
                _err_norm, jax.jit(gl)(q, k, v), ref_grads))
    return out


def _kd_flash_cell(B, H, Hkv, T, D, dtype):
    return _kd_flash(B, H, T, D, dtype, kv_heads=Hkv, causal_only=True)


def _kd_lrn(shape, dtype):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=shape), dtype)
    k, n, alpha, beta = 2.0, 5, 1e-4, 0.75

    def ref(x):
        x = x.astype(jnp.float32)
        return x * (k + alpha * pk._window_sum(x * x, n)) ** -beta

    fused = lambda x: pk.fused_lrn(x, k, n, alpha, beta)  # noqa: E731
    gl = lambda fn: jax.grad(lambda x: jnp.sum(  # noqa: E731
        fn(x).astype(jnp.float32) ** 2))
    return {"fwd": _err(jax.jit(fused)(x), ref(x)),
            "grad": _err_norm(jax.jit(gl(fused))(x), gl(ref)(x))}


def _kd_hyper(N, n, D, dtype):
    """The six kernels of the hyper-connected residual (the maps' projection,
    the normed read, the write; forward and every gradient) through their
    layer objects, against the same objects' jax.numpy (the site's
    ``reference`` variant) in float32."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import hyper_connections as hc
    from deeplearning4j_tpu.ops import kernel_select as ks

    f32 = jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(N + n + D), 5)
    x = jax.random.normal(keys[0], (1, N, n * D), f32).astype(dtype)
    y = jax.random.normal(keys[1], (1, N, D), f32).astype(dtype)
    maps = jax.random.uniform(keys[2], (1, N, n * (n + 2)), f32)
    gamma = 1.0 + 0.1 * jax.random.normal(keys[3], (D,), f32)
    layer = hc.HyperConnectionMapsLayer(n_streams=n)
    start = layer.init_params(keys[4], InputType.recurrent(n * D, N))
    # gates of order one: the maps depend on the token
    a, b = jnp.asarray([0.5, -0.5, 0.25], f32), start["b"].astype(f32)
    read = hc.HyperConnectionVertex(op="read", n_streams=n, norm_eps=1e-6)
    write = hc.HyperConnectionVertex(op="write", n_streams=n)
    pieces = {
        "maps": (lambda x, P: layer.apply({"P": P, "a": a, "b": b}, x, {})[0],
                 (x, start["P"].astype(f32))),
        "read": (lambda x, m, g: read.apply({"gamma": g}, [x, m], {})[0],
                 (x, maps, gamma)),
        "write": (lambda x, m, y: write.apply({}, [x, m, y], {})[0],
                  (x, maps, y)),
    }
    out = {}
    for name, (fn, args) in pieces.items():
        argnums = tuple(range(len(args)))

        def both(variant, args, fn=fn, argnums=argnums):
            ks.set_site_override("hyper_connection", variant)
            try:
                # a jit of its own a variant: the site is asked while tracing
                value = jax.jit(lambda *a: fn(*a))(*args)
                grads = jax.jit(jax.grad(lambda *a: jnp.sum(
                    fn(*a).astype(f32) ** 2), argnums=argnums))(*args)
                return jax.block_until_ready((value, grads))
            finally:
                ks.set_site_override("hyper_connection", None)

        got, got_g = _named(f"hyper_{name}", lambda: both("fused", args))
        want, want_g = both("reference", jax.tree_util.tree_map(
            lambda t: t.astype(f32), args))
        out[f"{name}_fwd"] = _err(got, want)
        out[f"{name}_grad"] = max(map(_err_norm, got_g, want_g))
    ran = {r["ctx"]["op"] for r in ks.selection_log()
           if r["site"] == "hyper_connection" and r["variant"] == "fused"
           and r["ctx"]["N"] == N and r["ctx"]["itemsize"]
           == jnp.dtype(dtype).itemsize}
    check(ran == set(pieces), f"hyper-connection kernels ran for {ran} only")
    return out


def leg_d_kernels(lstm=(256, 64, 512), lstm_small=(32, 16, 128),
                  sxent=((16384, 96), (128, 1000), (256, 10)),
                  adam=((512, 2048), (2048,), (96,), (7, 9)),
                  flash=(2, 4, 256, 64), flash_cell=(1, 32, 2, 8192, 128),
                  lrn=(4, 14, 14, 64), hyper=(8192, 4, 3584)) -> dict:
    """Every Pallas kernel compiled (interpret mode only off-TPU), f32 and
    bf16, against its XLA reference. Runs every check before failing, so one
    chip call shows every kernel Mosaic rejects."""
    import jax.numpy as jnp

    t_leg = time.perf_counter()
    plan = []
    for dt in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dt).name
        shape = lstm if dt == jnp.bfloat16 else lstm_small
        plan.append((f"lstm{shape}/{name}", _kd_lstm, (*shape, dt)))
        if dt == jnp.bfloat16 and lstm_small != lstm:
            plan.append((f"lstm{lstm_small}/{name}", _kd_lstm,
                         (*lstm_small, dt)))
        plan += [(f"softmax_xent{s}/{name}", _kd_sxent, (*s, dt))
                 for s in sxent]
        plan += [(f"adam{s}/{name}", _kd_adam, (s, dt)) for s in adam]
        plan.append((f"flash{flash}/{name}", _kd_flash, (*flash, dt)))
        if dt == jnp.bfloat16:   # nemotron3_nano_train_1chip's own call
            plan.append((f"flash_cell{flash_cell}/{name}", _kd_flash_cell,
                         (*flash_cell, dt)))
        plan.append((f"lrn{lrn}/{name}", _kd_lrn, (lrn, dt)))
        # xing4_train_1chip's streams: [8192, 4 x 3584]
        plan.append((f"hyper{hyper}/{name}", _kd_hyper, (*hyper, dt)))
    failed, worst = [], {}
    for label, fn, args in plan:
        tol = _tol(args[-1], long_sequence=fn is _kd_lstm and args[0] >= 128)
        try:
            errs = fn(*args)
        except Exception as e:  # noqa: BLE001 - report every kernel, then fail
            msg = f"{getattr(e, '__notes__', '')} {type(e).__name__}: {e}"
            print(f"  FAIL {label}: {msg[:1500]}")
            failed.append(label)
            continue
        bad = {k: float(f"{v:.2e}") for k, v in errs.items()
               if not (np.isfinite(v) and v <= tol)}
        worst[label] = float(f"{max(errs.values()):.2e}")
        if bad:
            print(f"  FAIL {label}: over tol {tol}: {bad}")
            failed.append(label)
        else:
            print(f"  ok   {label}: max rel err {worst[label]} (tol {tol})")
    check(not failed, f"{len(failed)}/{len(plan)} kernel checks failed: {failed}")
    return {"checks": len(plan), "worst": max(worst.values()),
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# -------------------------------------------------------------------- leg E
def _device_ids(tree) -> set:
    import jax

    return {s.device.id for leaf in jax.tree_util.tree_leaves(tree)
            for s in leaf.addressable_shards}


def leg_e_four_chips(vocab: int = 96, hidden: int = 512, layers: int = 2,
                     batch: int = 64, seq: int = 256, steps: int = 3,
                     dtype: str = "bfloat16", dp_rtol: float = 3e-2,
                     fsdp_rtol: float = 1e-1) -> dict:
    """The leg B model on four devices: plain data parallelism, then
    data x fsdp with bf16 param storage, each against a one-device twin."""
    import jax

    from deeplearning4j_tpu.parallel import (MeshLayout, ParallelWrapper,
                                             make_mesh)

    t_leg = time.perf_counter()
    xs, ys = _char_batches(vocab, batch, seq)
    single = _char_rnn_net(vocab, hidden, layers, dtype)
    want = single.fit_on_device(xs, ys, steps=steps)
    out = {"one_device_losses": [float(l) for l in want]}

    for name, make, rtol in (
            ("dp4", lambda n: ParallelWrapper(n, mesh=make_mesh(4)), dp_rtol),
            ("dp2xfsdp2", lambda n: ParallelWrapper(n, layout=MeshLayout(
                data=2, fsdp=2, params_dtype="bfloat16")), fsdp_rtol)):
        net = _char_rnn_net(vocab, hidden, layers, dtype)
        wrapper = make(net)
        got = wrapper.fit_on_device(xs, ys, steps=steps)
        ids = _device_ids(net.params)
        check(len(ids) == 4, f"{name}: params live on devices {sorted(ids)}")
        shard = wrapper.layout.staged_batch_sharding().shard_shape(xs.shape)
        check(wrapper.workers == 4 and shard[1] * 4 == batch,
              f"{name}: batch not split 4 ways: shard {shard} of {xs.shape}")
        if name != "dp4":
            leaves = jax.tree_util.tree_leaves(net.params)
            check(any(l.addressable_shards[0].data.shape != l.shape
                      for l in leaves), f"{name}: no param leaf is sharded")
        check(np.all(np.isfinite(got)) and np.allclose(got, want, rtol=rtol),
              f"{name}: losses {got} vs one-device twin {want}")
        print(f"  {name}: losses {np.round(got, 4)} vs one device "
              f"{np.round(want, 4)}; param devices {sorted(ids)}; "
              f"batch shard {shard}")
        out[name] = [float(l) for l in got]
    # GSPMD cannot partition a Mosaic kernel: on the mesh every site must
    # have said so and taken its XLA path (the one-device twin did not)
    from deeplearning4j_tpu.ops import kernel_select as ks

    on_mesh = [r for r in ks.selection_log() if r["ctx"].get("partitioned")]
    check(on_mesh and all(r["variant"] in _REFERENCE_VARIANTS
                          for r in on_mesh),
          f"a partitioned program selected a Mosaic kernel: {on_mesh}")
    out["on_mesh_selection"] = sorted(
        {(r["site"], r["variant"], r["reason"]) for r in on_mesh})
    print(f"  selections inside partitioned programs: "
          f"{out['on_mesh_selection']}")
    _check_admission("E")
    out["leg_seconds"] = round(time.perf_counter() - t_leg, 1)
    return out


# -------------------------------------------------------------------- leg F
# Tolerances of leg F at the published widths, on the relative L2 distance
# between the engine's bfloat16 result and the float32 plain reference (worst
# over a block's output and gradients), each about three times what the v5e
# read (my chip runs, PR 30, two runs alike; PERF.md section 6):
#   M    0.00933 (d_A_log; output 0.00514)   the control, the scan's decays
#        and state in bfloat16, read 1.47 (d_A_log; output 0.0177): fails
#   A    0.00626 (d_in; output 0.00473)
#   E    0.00451 (d_Ws_down; output 0.00446, d_in 0.0044; before rows outside
#        every group were selected away, d_in read 9.66)
#   head 0.00166 (d_in; the loss itself 1.0e-6)
LEG_F_TOLERANCES = {"M": 0.03, "A": 0.02, "E": 0.015, "head": 0.005}


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


class _BlockCheck:
    """Legs F and G: a block as the engine runs it (``dtype`` compute over
    float32 masters) against its plain reference. ``both(fn)`` gives the
    output, the parameters' gradient and the input's for the cotangent ``w``;
    ``errors`` their relative L2 distances; ``held`` prints a block's line
    and holds its worst distance to the block's tolerance."""

    def __init__(self, tolerances: dict, dtype: str, w):
        self.tolerances, self.dtype, self.w = tolerances, dtype, w
        self.results = {}

    def both(self, fn):
        import jax

        def run(p, x):
            out, pull = jax.vjp(fn, p, x)
            return (out,) + pull(self.w)
        return jax.jit(run)

    def layer_block(self, layer, plain, sizes, key, it):
        """``(params, program, reference)`` of one layer: the program casts
        the float32 masters as the engine does and runs ``layer.apply``."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.multilayer import _cast_layer_params

        cdt = jnp.dtype(self.dtype)
        params = _f32(layer.init_params(key, it))
        state = layer.init_state(it) if hasattr(layer, "init_state") else {}

        def program(p, x):
            out, _ = layer.apply(_cast_layer_params(self.dtype, layer, p),
                                 x.astype(cdt), state, train=True)
            return out.astype(jnp.float32)

        def reference(p, x):
            with jax.default_matmul_precision("highest"):
                return plain(p, x, sizes)

        return params, self.both(program), self.both(reference)

    @staticmethod
    def errors(kind, got, want) -> dict:
        import jax
        import jax.numpy as jnp

        (o, gp, gx), (o_r, gp_r, gx_r) = got, want
        errs = {"out": _rel_l2(o, o_r), "d_in": _rel_l2(gx, gx_r)}
        flat, _ = jax.tree_util.tree_flatten_with_path(gp_r)
        got_flat = dict(jax.tree_util.tree_flatten_with_path(gp)[0])
        for path, g in flat:
            if float(jnp.max(jnp.abs(g))) > 0.0:   # e_bias only selects
                name = "_".join(str(getattr(k, "key", k)) for k in path)
                errs["d_" + name] = _rel_l2(got_flat[path], g)
        return errs

    def held(self, kind, errs, label=None) -> bool:
        label = label or kind
        worst = max(errs.values())
        tol = self.tolerances[kind]
        line = {k: float(f"{v:.3g}") for k, v in errs.items()}
        ok = bool(np.isfinite(worst) and worst <= tol)
        print(f"  {'ok  ' if ok else 'OVER'} {label}: worst {worst:.3g} "
              f"(tol {tol:g}) {json.dumps(line)}", flush=True)
        self.results[label] = float(f"{worst:.3g}")
        return ok


def _bench_config(name: str):
    from benchmarks.harness.discovery import load_json, load_module

    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "configs", name)
    return load_module(base + ".py"), load_json(base + ".json")


def _hybrid_config():
    return _bench_config("nemotron3_nano_30b_a3b")


@contextlib.contextmanager
def _scan_state_in(dtype):
    """The scan's decays, running sums and state in ``dtype`` (``None``: as
    the program has them, float32), through the jax.numpy variant: the
    control's lower precision, never the program's."""
    if dtype is None:
        yield
        return
    from deeplearning4j_tpu.ops import kernel_select as ks
    from deeplearning4j_tpu.ops import ssd_scan as ssd

    was = ssd._state_dtype
    ssd._state_dtype = lambda dt: np.dtype(dtype)
    try:
        with ks.forced_mode("reference"):
            yield
    finally:
        ssd._state_dtype = was


def leg_f_hybrid_blocks(sizes: dict | None = None, seq_len: int = 8192,
                        batch: int = 1, dtype: str = "bfloat16",
                        tolerances: dict | None = None, kinds="MAEH",
                        control: str | None = "bfloat16",
                        seed: int = 0) -> dict:
    """One block of each kind and the head's loss, as ``ComputationGraph``
    runs them (``dtype`` compute over float32 masters, through the engine's
    own cast), against the plain reference: the relative L2 distance of the
    forward output, of every parameter's gradient and of the input's, the
    worst of a kind held under its tolerance. ``control``: the Mamba block
    again with its scan state in that dtype, which must not pass."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.nemotron_h import nemotron_h_conf
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.ops import kernel_select as ks

    t_leg = time.perf_counter()
    cfg, published = _hybrid_config()
    sizes = dict(published, **(sizes or {}))
    tolerances = dict(LEG_F_TOLERANCES, **(tolerances or {}))
    conf = nemotron_h_conf("M*E", dtype=dtype, **cfg.builder_kwargs(sizes))
    layers = {"M": conf.vertices["b0M_mixer"].layer,
              "A": conf.vertices["b1A_mixer"].layer,
              "E": conf.vertices["b2E_mixer"].layer,
              "H": conf.vertices["head"].layer}
    plain = {"M": cfg.reference_mamba, "A": cfg.reference_attention,
             "E": cfg.reference_experts}
    width = sizes["hidden_size"]
    it = InputType.recurrent(width, seq_len)
    cdt = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    # a block's input is a normed residual stream: unit scale, rounded to the
    # compute dtype once, so both sides route and attend over the same values
    x = jax.random.normal(keys[0], (batch, seq_len, width), jnp.float32)
    x = x.astype(cdt).astype(jnp.float32)
    w = jax.random.normal(keys[1], (batch, seq_len, width), jnp.float32)

    check_blocks = _BlockCheck(tolerances, dtype, w)
    held, compare = check_blocks.held, check_blocks.errors
    results, failed = check_blocks.results, []

    def block(kind):
        return check_blocks.layer_block(
            layers[kind], plain[kind], sizes, keys[2 + "MAE".index(kind)], it)

    for kind in (k for k in "MAE" if k in kinds):
        params, program, reference = block(kind)
        want = jax.block_until_ready(reference(params, x))
        got = jax.block_until_ready(program(params, x))
        if not held(kind, compare(kind, got, want)):
            failed.append(kind)
        if kind == "M" and control:
            with _scan_state_in(control):
                lower = jax.block_until_ready(block("M")[1](params, x))
            if held("M", compare("M", lower, want),
                    f"M with the scan state in {control} (control)"):
                failed.append("control: a lower-precision scan passed")
        del params, program, reference, want, got

    if "H" in kinds:
        head = layers["H"]
        hp = _f32(head.init_params(keys[8], it))
        ids = jax.random.randint(keys[9], (batch, seq_len), 0,
                                 sizes["vocab_size"])

        def program(p, h):   # as ComputationGraph._loss hands them over
            return head.compute_loss(p, h, ids, None, train=True)

        def reference(p, h):
            with jax.default_matmul_precision("highest"):
                return jnp.mean(cfg.reference_token_losses(p["W"], h, ids))

        vg = lambda fn: jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))  # noqa: E731
        (l, (gp, gh)), (l_r, (gp_r, gh_r)) = vg(program)(hp, x), \
            vg(reference)(hp, x)
        errs = {"loss": abs(float(l) - float(l_r)) / abs(float(l_r)),
                "d_W": _rel_l2(gp["W"], gp_r["W"]), "d_in": _rel_l2(gh, gh_r)}
        if not held("head", errs):
            failed.append("head")

    sites = {r["site"]: r["variant"] for r in ks.selection_log()
             if r.get("mode") != "reference"}
    print(f"  selection: {json.dumps(sites)}")
    check(not failed, f"leg F: over tolerance or control passed: {failed}")
    return {"worst": results, "selection": sites,
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# ---- leg G: the latent attention / gated / hyper-connection blocks ---------
# Worst distances read on the v5e at the published widths (my chip run, PR
# 34, first call), bfloat16 against the float32 plain reference:
#   A 0.00783 (d_q_norm; output 0.00593)     D 0.0047 (d_in; output 0.00423)
#   E 0.00504 (d_in; output 0.00414)         H 0.00244 (d_in; output 0.00166)
#   H with the maps' projection and Sinkhorn in bfloat16 (the control):
#   0.00953 (d_maps_a; d_maps_P 0.00403, d_in 0.00385): H's tolerance lies
#   between the two, twice the sound reading and half the control's.
LEG_G_TOLERANCES = {"A": 0.02, "D": 0.015, "E": 0.015, "H": 0.005}


@contextlib.contextmanager
def _replaced(module, name: str, make):
    """``module.<name>`` (a function through which a block takes its float32
    part) replaced by ``make(the function)`` for a control's lower
    precision, never the program's."""
    was = getattr(module, name)
    setattr(module, name, make(was))
    try:
        yield
    finally:
        setattr(module, name, was)


class _HyperConnectedIdentity:
    """One hyper-connected sublayer's own arithmetic as a block: ``X`` ->
    maps -> the normed read ``h`` -> ``X' = H_res X + H_post^T h`` (the
    sublayer ``F`` is the identity, so the read, its norm and the write all
    carry gradient). Parameters ``{"maps": ..., "pre": ...}``."""

    def __init__(self, conf, dtype):
        self.maps = conf.vertices["b0H_maps"].layer
        self.pre, self.post = (conf.vertices[f"b0H_{k}"] for k in ("pre",
                                                                   "post"))
        self.dtype = dtype

    def init_params(self, key, it):
        return {"maps": self.maps.init_params(key, it),
                "pre": self.pre.init_params(key, it, it)}

    def apply(self, params, x):
        from deeplearning4j_tpu.nn.multilayer import (_cast_layer_params,
                                                      _cast_params)

        maps, _ = self.maps.apply(
            _cast_layer_params(self.dtype, self.maps, params["maps"]), x, {})
        h, _ = self.pre.apply(_cast_params(self.dtype, params["pre"]),
                              [x, maps], {})
        return self.post.apply({}, [x, maps, h], {})[0]


def leg_g_latent_blocks(sizes: dict | None = None, seq_len: int = 8192,
                        batch: int = 1, dtype: str = "bfloat16",
                        tolerances: dict | None = None, kinds="ADEH",
                        control: str | None = "bfloat16",
                        seed: int = 0) -> dict:
    """One latent attention (``A``), one gated dense feed-forward (``D``),
    one gated expert layer (``E``) and one hyper-connected sublayer's own
    arithmetic (``H``) as ``ComputationGraph`` runs them, against the plain
    reference, as leg F does. ``control`` (``bfloat16``): ``A`` again with
    the rotary angles rounded to bfloat16 and ``H`` again with the maps'
    projection and the Sinkhorn in it; both must not pass. The angles are
    rounded with ``jax.lax.reduce_precision``: a cast to bfloat16 and back
    between elementwise operations proves nothing on the chip, where the
    compiler keeps the excess precision inside a fusion (the block read
    0.00781 against 0.00783 so; my chip run, PR 34)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.xing4 import xing4_conf
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import attention as att
    from deeplearning4j_tpu.nn.layers import hyper_connections as hc
    from deeplearning4j_tpu.ops import kernel_select as ks

    t_leg = time.perf_counter()
    cfg, published = _bench_config("xing4_29b_a4b")
    sizes = dict(published, **(sizes or {}))
    tolerances = dict(LEG_G_TOLERANCES, **(tolerances or {}))
    conf = xing4_conf(dtype=dtype, **dict(cfg.builder_kwargs(sizes),
                                          n_dense=1, n_expert=1))
    layers = {"A": conf.vertices["b0A_mixer"].layer,
              "D": conf.vertices["b1D_mixer"].layer,
              "E": conf.vertices["b3E_mixer"].layer}
    plain = {"A": cfg.reference_attention, "D": cfg.reference_dense,
             "E": cfg.reference_experts}
    width, n = sizes["hidden_size"], sizes["hc_mult"]
    it = InputType.recurrent(width, seq_len)
    cdt = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(seed), 16)
    # a block's input is a normed hidden state: unit scale, rounded to the
    # compute dtype once, so both sides route and attend over the same values
    x = jax.random.normal(keys[0], (batch, seq_len, width), jnp.float32)
    x = x.astype(cdt).astype(jnp.float32)
    w = jax.random.normal(keys[1], (batch, seq_len, width), jnp.float32)
    check_blocks = _BlockCheck(tolerances, dtype, w)
    held, compare = check_blocks.held, check_blocks.errors
    failed = []

    for kind in (k for k in "ADE" if k in kinds):
        params, program, reference = check_blocks.layer_block(
            layers[kind], plain[kind], sizes, keys[2 + "ADE".index(kind)], it)
        want = jax.block_until_ready(reference(params, x))
        got = jax.block_until_ready(program(params, x))
        if not held(kind, compare(kind, got, want)):
            failed.append(kind)
        if kind == "A" and control == "bfloat16":
            rounded = lambda exact: lambda t, f: jax.lax.reduce_precision(  # noqa: E731
                exact(t, f), exponent_bits=8, mantissa_bits=7)
            with _replaced(att, "_rotary_angles", rounded):
                lower = jax.block_until_ready(check_blocks.layer_block(
                    layers["A"], plain["A"], sizes, keys[2], it)[1](params, x))
            if held("A", compare("A", lower, want),
                    "A with the rotary angles in bfloat16 (control)"):
                failed.append("control: lower-precision rotary angles passed")
        del params, program, reference, want, got

    if "H" in kinds:
        wide = InputType.recurrent(n * width, seq_len)
        block = _HyperConnectedIdentity(conf, dtype)
        hp = _f32(block.init_params(keys[8], wide))
        # streams that differ, and maps that depend on the token: gates of
        # order one instead of the start's 0.01
        hp["maps"]["a"] = jnp.asarray([0.5, -0.5, 0.25], jnp.float32)
        X = jax.random.normal(keys[9], (batch, seq_len, n * width),
                              jnp.float32).astype(cdt).astype(jnp.float32)
        wide_checks = _BlockCheck(tolerances, dtype, jax.random.normal(
            keys[10], X.shape, jnp.float32))
        wide_checks.results = check_blocks.results

        def program(p, X):
            return block.apply(p, X.astype(cdt)).astype(jnp.float32)

        def reference(p, X):
            with jax.default_matmul_precision("highest"):
                streams = X.reshape(X.shape[:-1] + (n, width))
                pre, post, res = cfg.reference_maps(p["maps"], streams, sizes)
                h = cfg._rmsnorm(jnp.einsum("bts,btsd->btd", pre, streams),
                                 p["pre"]["gamma"], sizes["rms_norm_eps"])
                out = (jnp.einsum("btij,btjd->btid", res, streams)
                       + post[..., None] * h[..., None, :])
                return out.reshape(X.shape)

        want = jax.block_until_ready(wide_checks.both(reference)(hp, X))
        got = jax.block_until_ready(wide_checks.both(program)(hp, X))
        if not wide_checks.held("H", compare("H", got, want)):
            failed.append("H")
        if control:
            with _replaced(hc, "_sinkhorn_dtype",
                           lambda _: lambda dt: np.dtype(control)):
                lower = jax.block_until_ready(
                    wide_checks.both(program)(hp, X))
            if wide_checks.held("H", compare("H", lower, want),
                                f"H with the maps in {control} (control)"):
                failed.append("control: lower-precision maps passed")

    sites = {r["site"]: r["variant"] for r in ks.selection_log()
             if r.get("mode") != "reference"}
    print(f"  selection: {json.dumps(sites)}")
    if "H" in kinds and jax.default_backend() == "tpu":
        check(sites.get("hyper_connection") == "fused",
              "leg G: the hyper-connection block ran without its kernels")
    check(not failed, f"leg G: over tolerance or control passed: {failed}")
    return {"worst": check_blocks.results, "selection": sites,
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# Relative L2 distances of leg H's blocks from the plain reference (my chip
# runs, PR 36; the readings, seed by seed, are in PERF.md's findings of that
# PR). ``R``, the recurrence by itself, takes the limits the cell holds it to
# (the configuration's ``RECURRENCE_RTOL*``, one a result's dtype), each
# between its sound readings and the control's, which is held there: a
# block's other products, at one bfloat16 pass each, read as much as the
# control adds (``K``), the recurrence alone does not.
LEG_H_TOLERANCES = {"K": 0.026, "A": 0.02, "E": 0.015}
LEG_H_CONTROL = "R with the recurrence's state in bfloat16 (control)"


def _really_rounded(exact):
    """``exact``'s result rounded to bfloat16's 8 bits of mantissa (``jax.
    lax.reduce_precision``: the compiler cannot skip it inside a fusion)."""
    import jax

    return lambda a: jax.lax.reduce_precision(exact(a), exponent_bits=8,
                                              mantissa_bits=7)


def _each_held(errs: dict, label: str, limit, results: dict) -> bool:
    """``errs`` ``{name: (distance, its limit)}`` printed as a block's line
    and held, each distance to its own limit (to ``limit`` where a caller
    gives one for all)."""
    if limit is not None:
        errs = {k: (d, limit) for k, (d, _) in errs.items()}
    ok = all(d <= l for d, l in errs.values())     # a NaN holds nothing
    line = {k: [float(f"{d:.3g}"), l] for k, (d, l) in errs.items()}
    print(f"  {'ok  ' if ok else 'OVER'} {label}: [distance, limit] "
          f"{json.dumps(line)}", flush=True)
    results[label] = float(f"{max(d for d, _ in errs.values()):.3g}")
    return ok


def _recurrence_operands(cfg, sizes, params, x, dtype: str):
    """What the plain reference's delta-rule block hands its recurrence for
    ``params`` and ``x``: ``q``, ``k`` and ``v`` rounded to ``dtype``, as a
    layer computing in it hands them over, ``g`` and ``beta`` float32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def operands(p, a):
        with jax.default_matmul_precision("highest"):
            q, k, v, g, beta = cfg.delta_rule_operands(p, a, sizes)
        cast = lambda t: t.astype(jnp.dtype(dtype))  # noqa: E731
        return cast(q), cast(k), cast(v), g, beta

    return operands(params, x)


def leg_h_kimi_blocks(sizes: dict | None = None, seq_len: int = 8192,
                      batch: int = 1, dtypes=("float32", "bfloat16"),
                      tolerances: dict | None = None, kinds="KAER",
                      control: bool = True, seed: int = 0,
                      a_dtypes=("bfloat16",)) -> dict:
    """One Kimi Delta Attention block (``K``), one latent attention block
    without positions (``A``) and one expert block (``E``: a sigmoid router
    256 wide, 8 a token, the 8 experts held and the shared one) as
    ``ComputationGraph`` runs them, ``K`` in each of ``dtypes`` over float32
    masters, against the plain reference: outputs, every parameter's
    gradient and the input's, as leg F. ``A`` and ``E`` run in ``a_dtypes``,
    bfloat16 alone at 8192 positions: in float32 the flash kernels' strips do
    not fit their budget (``PERF.md`` section 7 (b)) and the XLA path's
    ``[32, 8192, 8192]`` scores are 8 GB. With its input rounded once for both
    sides ``E`` routes alike on both, which the whole cell's comparison cannot
    arrange, so the router's and the routed experts' gradients are held here.
    ``R``: the recurrence by itself, as kernel selection resolves it at these
    shapes, on the operands the ``K`` block hands it in the last of
    ``dtypes``, against the delta rule one position at a time: ``o`` and the
    gradient of every operand (the configuration's ``recurrence_distances``,
    what the cell runs on its own first delta-rule sublayer before it times a
    step; ``tolerances["R"]``, if given, stands in for its limits).
    ``control``: ``R`` once more with the recurrence's running sums, solved
    system and carried state really rounded to bfloat16 (``ops/kda.py
    _round_state``), which must not pass."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.kimi_linear import kimi_linear_conf
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.ops import kda
    from deeplearning4j_tpu.ops import kernel_select as ks

    t_leg = time.perf_counter()
    cfg, published = _bench_config("kimi_linear_48b_a3b")
    sizes = dict(published, **(sizes or {}))
    tolerances = dict(LEG_H_TOLERANCES, **(tolerances or {}))
    width = sizes["hidden_size"]
    it = InputType.recurrent(width, seq_len)
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    plain = {"K": cfg.reference_delta_attention,
             "A": cfg.reference_latent_attention,
             "E": cfg.reference_experts}
    # a block's input is a normed hidden state: unit scale, rounded to
    # bfloat16 once, so every side works on the same values
    x = jax.random.normal(keys[0], (batch, seq_len, width), jnp.float32)
    x = x.astype(jnp.bfloat16).astype(jnp.float32)
    w = jax.random.normal(keys[1], (batch, seq_len, width), jnp.float32)
    results, failed, want = {}, [], {}
    for dtype in dtypes:
        conf = kimi_linear_conf(dtype=dtype, **dict(
            cfg.builder_kwargs(sizes), mixers="KA", n_dense=0))
        layers = {"K": conf.vertices["b0K_mixer"].layer,
                  "A": conf.vertices["b2A_mixer"].layer,
                  "E": conf.vertices["b1E_mixer"].layer}
        blocks = _BlockCheck(tolerances, dtype, w)
        blocks.results = results
        for kind in (k for k in "KAE" if k in kinds):
            if kind in "AE" and dtype not in a_dtypes:
                continue
            params, program, reference = blocks.layer_block(
                layers[kind], plain[kind], sizes, keys[2 + "KAE".index(kind)],
                it)
            if kind not in want:    # the same seeded weights in every dtype
                want[kind] = jax.block_until_ready(reference(params, x))
            t0 = time.perf_counter()
            got = jax.block_until_ready(program(params, x))
            label = f"{kind} in {dtype}"
            if not blocks.held(kind, blocks.errors(kind, got, want[kind]),
                               label):
                failed.append(label)
            print(f"  {label}: forward + backward with its compile in "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
            del params, program, reference, got
    if "R" in kinds:
        t0 = time.perf_counter()
        label = f"R in {dtypes[-1]}"
        operands = _recurrence_operands(
            cfg, sizes, _f32(layers["K"].init_params(keys[2], it)), x,
            dtypes[-1])
        errs, alone = cfg.recurrence_distances(operands, sizes)
        if not _each_held(errs, label, tolerances.get("R"), results):
            failed.append(label)
        if control:
            with _replaced(kda, "_round_state", _really_rounded):
                errs, _ = cfg.recurrence_distances(operands, sizes, alone)
            if _each_held(errs, LEG_H_CONTROL, tolerances.get("R"), results):
                failed.append("control: a lower-precision state passed")
        print(f"  R: the recurrence alone, its reference and its control in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    sites = {r["site"]: r["variant"] for r in ks.selection_log()
             if r.get("mode") != "reference"}
    print(f"  selection: {json.dumps(sites)}")
    check("kda_recurrence" in sites or not set("KR") & set(kinds),
          "leg H: the delta rule left no kda_recurrence selection")
    check(not failed, f"leg H: over tolerance or control passed: {failed}")
    return {"worst": results, "selection": sites,
            "leg_seconds": round(time.perf_counter() - t_leg, 1)}


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma list out of A,B,C,D,E,F,G,H (default: all)")
    legs = [l.strip().upper() for l in ap.parse_args(argv).legs.split(",")
            if l.strip()]
    unknown = [l for l in legs if l not in LEGS]
    if unknown:
        ap.error(f"unknown legs {unknown}")
    t_start = time.perf_counter()
    info = gate(require_tpu=True)
    import jax

    from deeplearning4j_tpu.models.resnet import resnet50_conf

    probe_dispatch()
    results, failed, state = {}, [], {}

    def run(leg, fn):
        if leg not in legs:
            return
        print(f"== leg {leg}")
        try:
            with counting() as compiled:
                results[leg] = fn()
        except Exception:  # noqa: BLE001 - name the leg, run the rest, exit 1
            traceback.print_exc()
            failed.append(leg)
            print(f"== leg {leg} FAILED")
            return
        print(f"== leg {leg} passed: {json.dumps(results[leg])} "
              f"compile={json.dumps(compiled)}")

    def leg_b():
        info_b, state["net"], state["batches"] = leg_b_kernel_route()
        return info_b

    def leg_c():
        if "net" not in state:  # B skipped or failed: train a net for C alone
            state["net"] = _char_rnn_net(96, 512, 2, "bfloat16")
            state["batches"] = _char_batches(96, 64, 256)
            state["net"].fit_on_device(*state["batches"], steps=3)
        return leg_c_server(state["net"], state["batches"], steps=3)

    run("D", leg_d_kernels)
    run("A", lambda: leg_a_trainer(resnet50_conf(dtype="bfloat16"), 224, 1000,
                                   128))
    run("B", leg_b)
    run("C", leg_c)
    if "E" in legs and len(jax.devices()) < 4:
        print(f"== leg E not run: {len(jax.devices())} device(s), needs 4")
        results["E"] = "not run"
    else:
        run("E", leg_e_four_chips)
    run("F", leg_f_hybrid_blocks)
    run("G", leg_g_latent_blocks)
    run("H", leg_h_kimi_blocks)

    print(f"compile totals: {json.dumps(monitors().snapshot())}")
    print(f"compile manager: {json.dumps(_admission_state())}")
    passed = [l for l in legs if l in results and results[l] != "not run"]
    print(f"legs passed: {passed}  not run: "
          f"{[l for l in LEGS if l not in passed and l not in failed]}  "
          f"failed: {failed}  wall {time.perf_counter() - t_start:.1f}s")
    if failed:
        print(f"chip_smoke: FAILED legs {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
