"""Benchmark modes: one process, one JSON line, on the device it names.

Headline (BASELINE.json north-star): **ResNet-50 ImageNet-shape training
throughput, images/sec/chip**, bf16, batch 128, one chip. Batches are staged
on-device before timing (MLPerf convention) so the number measures the
training step. ``BENCH_MODEL`` selects another mode (``charrnn``,
``word2vec``, ``attention``, ``ragged``, ``serve``, ``online``, ``shard``,
``pipeline``, ``fleet``, ``history``, ``autotune``).

Contract:

- One process. A chip belongs to one process at a time, so nothing here
  spawns a child that needs it.
- A run needs a TPU: when ``jax.devices()[0].platform`` is not ``tpu`` the
  process says so on stderr and exits non-zero — it never falls back to a
  CPU number. ``BENCH_FORCE_CPU=1`` is the one EXPLICIT CPU request
  (``scripts/check.sh`` uses it for the host-side modes: serve, online,
  shard, pipeline, autotune, fleet, history, and the MLP default); any
  other mode under it is refused.
- Every printed line carries ``platform``, ``device_kind`` and
  ``device_count`` as jax reports them, so a CPU count can never be read as
  a device number.
- A failing mode raises: the traceback and a non-zero exit are the report.
- The persistent compilation cache sits where the compile manager's
  resolver puts it (``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``).

The reference publishes no numbers, so ``vs_baseline`` is the ratio to the
FIRST recorded value of the same metric (``BENCH_SELF.json``).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO_DIR = os.path.dirname(os.path.abspath(__file__))
SELF_BASELINE_PATH = os.environ.get(
    "BENCH_SELF_PATH", os.path.join(REPO_DIR, "BENCH_SELF.json")
)


def _telemetry_block(step_times_s, mfu_pct=None, extra_gauges=None) -> dict:
    """Per-mode results routed through the telemetry registry, then emitted
    as the machine-comparable "telemetry" block in the BENCH_* artifact:
    the step-time histogram summary comes from a real registry Histogram
    (same bucketing the /metrics endpoint scrapes), MFU from a Gauge —
    so the perf trajectory and the live scrape speak one schema."""
    from deeplearning4j_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    hist = reg.histogram("bench_step_time_seconds",
                         "per-step wall time of the timed runs")
    for t in step_times_s:
        hist.observe(float(t))
    if mfu_pct is not None:
        reg.gauge("bench_mfu_pct", "XLA-cost-analysis MFU").set(mfu_pct)
    for name, value in (extra_gauges or {}).items():
        reg.gauge(name).set(value)
    snap = reg.snapshot()
    block = {"step_time_seconds": snap["bench_step_time_seconds"]["values"][0]}
    block["step_time_seconds"].pop("labels", None)
    for name in snap:
        if snap[name]["type"] == "gauge":
            block[name] = snap[name]["values"][0]["value"]
    return block


def _memory_block(net=None, example=None) -> dict:
    """Per-mode HBM accounting for the BENCH_* artifact: executable bytes
    from the compile cache's XLA memory_analysis records, live device
    stats, and — when a net is at hand — the projected peak vs the live
    peak plus the top-3 layer consumers (telemetry/memory.py). Defensive:
    a broken collector yields an {"error": ...} block, never a lost metric
    line."""
    try:
        from deeplearning4j_tpu.runtime.compile_manager import (
            get_compile_manager,
        )
        from deeplearning4j_tpu.telemetry import memory as tmem

        block: dict = {
            "executables": get_compile_manager().stats()["memory"],
            "devices": tmem.device_memory_stats(),
        }
        live_peaks = [d.get("peak_bytes_in_use") for d in block["devices"]
                      if d.get("peak_bytes_in_use")]
        block["live_peak_bytes"] = max(live_peaks) if live_peaks else None
        if net is not None:
            rep = tmem.memory_report(net, example)
            block["projected_peak_bytes"] = \
                rep["totals"]["projected_peak_bytes"]
            block["top_layers"] = rep["top_consumers"]
        return block
    except Exception as e:  # noqa: BLE001 - the metric line must survive
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def _kernels_block(extra: dict | None = None) -> dict:
    """Per-mode kernel-selection view for the BENCH_* artifact: which
    variant every fusable site resolved to this run (ops.kernel_select),
    plus any measured auto-vs-reference ratio the mode computed. Defensive
    like the other collectors."""
    try:
        from deeplearning4j_tpu.ops import kernel_select as ks

        block = ks.stats()
        block.pop("recent", None)  # the per-mode artifact wants the summary
        if extra:
            block.update(extra)
        return block
    except Exception as e:  # noqa: BLE001 - the metric line must survive
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def _static_cost_block(net, example, measured_step_s=None, *,
                       calibration_key=None) -> dict:
    """Per-mode ``static_cost`` block: the roofline model's predicted
    FLOPs/bytes/step and — when a measured step time is at hand — the
    predicted-vs-measured ratio, so BENCH_*.json tracks model-vs-reality
    drift round over round (ratio drifting from its historical band means
    either the model or the machine changed). Defensive like
    :func:`_memory_block`: collector failures emit {"error": ...}."""
    try:
        rep = net.analyze_ir(example)
        cost = rep["static_cost"]
        rl = cost["roofline"]
        block = {
            "flops_per_step": cost["flops"],
            "hbm_bytes_per_step": cost["hbm_bytes"],
            "arithmetic_intensity": round(cost["arithmetic_intensity"], 4),
            "predicted_step_seconds": rl["predicted_step_seconds"],
            "bound": rl["bound"],
            "roofline": {"peak_flops": rl["peak_flops"],
                         "hbm_gbps": rl["hbm_gbps"],
                         "ridge_flops_per_byte":
                             round(rl["ridge_flops_per_byte"], 2)},
            "findings": sorted(f.rule_id for f in rep["findings"]),
        }
        if measured_step_s:
            block["measured_step_seconds"] = float(measured_step_s)
            block["predicted_vs_measured"] = round(
                rl["predicted_step_seconds"] / float(measured_step_s), 6)
            if calibration_key:
                # calibration loop: the measured ratio tightens the cost
                # model's un-fused byte counts for future kernel selections
                # (KERNEL_CALIBRATION.json — ops.kernel_select). TPU-class
                # backends only: a CPU-fallback ratio compares a TPU
                # roofline against CPU wall time and would poison the store.
                import jax

                if jax.default_backend() == "tpu":
                    from deeplearning4j_tpu.ops import kernel_select as ks

                    block["calibration_recorded"] = ks.update_calibration(
                        calibration_key, block["predicted_vs_measured"])
        return block
    except Exception as e:  # noqa: BLE001 - the metric line must survive
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def bench_resnet50(batch: int = 128, steps: int = 120) -> dict:
    """ResNet-50 training throughput + step breakdown + XLA-reported MFU.

    Measured through the on-device multi-step loop (fit_on_device's
    ``_build_multi_step``: a fori_loop of the train step, ONE dispatch for
    all timed steps), so the host is out of the timed region. The sync
    point is a host fetch of the per-step loss array, which cannot complete
    before the loop has executed (``chip_smoke.py``'s ``probe_dispatch``
    records what ``block_until_ready`` and one dispatch cost on the chip).
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import profiler
    from deeplearning4j_tpu.models.resnet import resnet50_conf
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph

    timer = profiler.StepTimer()
    with timer.phase("build"):
        conf = resnet50_conf(dtype="bfloat16")
        if os.environ.get("BENCH_REMAT") == "1":
            conf.remat = True  # per-vertex jax.checkpoint: HBM for FLOPs —
            #                    the lever for the memory-bound batch sizes
        if os.environ.get("BENCH_PARAMS_BF16") == "1":
            conf.params_dtype = "bfloat16"  # carry bf16 weights in the scan
            #   (the round-5 trace's weight-copy-bound lever); own metric key
        net = ComputationGraph(conf).init()
        # step/batch counts are device scalars since the compile-manager
        # rework — one executable per staged SHAPE, however many steps
        multi = net._build_multi_step(steps)
        n1 = jnp.asarray(steps, jnp.int32)
        k1 = jnp.asarray(1, jnp.int32)

    with timer.phase("data"):
        rng = np.random.default_rng(0)
        xs = jax.device_put(
            jnp.asarray(rng.normal(size=(1, batch, 224, 224, 3)), jnp.float32)
        )
        ys = jax.device_put(
            jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, (1, batch))])
        )
        key = jax.random.PRNGKey(0)

    p, o, s = net.params, net.opt_state, net.state
    with timer.phase("compile"):  # compile (or disk-cache hit) + full warmup run
        p, o, s, key, losses = multi(p, o, s, key, n1, k1, [xs], [ys],
                                     None, None)
        warm = np.asarray(losses)
    assert np.all(np.isfinite(warm)), "non-finite warmup losses"

    with timer.phase("step"):
        t0 = time.perf_counter()
        p, o, s, key, losses = multi(p, o, s, key, n1, k1, [xs], [ys],
                                     None, None)
        losses = np.asarray(losses)  # host fetch = sync
        dt = time.perf_counter() - t0
    assert np.all(np.isfinite(losses)), "non-finite losses"

    # FLOPs AFTER the timed run, from the loop program's own lowering (a
    # cache hit — it was just compiled above), so nothing compiles between
    # warmup and timing. XLA cost analysis counts the loop body ONCE (same
    # figure for 1 and 60 steps), so the result IS per-step flops — the
    # >100% MFU guard self-corrects if a future XLA counts the unrolled loop.
    flops_per_step = profiler.compiled_flops(multi, p, o, s, key, n1, k1,
                                             [xs], [ys], None, None)

    step_s = dt / steps
    metric = "resnet50_imagenet_train_images_per_sec_per_chip"
    if conf.remat:
        metric += "_remat"  # different program: own key in the baseline store
    if conf.params_dtype == "bfloat16":
        metric += "_bf16params"
    result = {
        "metric": metric,
        "value": round(steps * batch / dt, 1),
        "unit": "images/sec/chip",
        "timed_steps": steps,
        "breakdown": timer.breakdown(),
    }
    result["breakdown"]["step"]["mean_ms"] = round(1000 * step_s, 3)
    if flops_per_step:
        if profiler.mfu(flops_per_step, step_s) > 100.0:
            flops_per_step /= steps  # cost analysis counted the whole loop
        result["flops_per_step"] = flops_per_step
        result["mfu_pct"] = round(profiler.mfu(flops_per_step, step_s), 1)
    result["telemetry"] = _telemetry_block(
        [step_s], mfu_pct=result.get("mfu_pct"),
        extra_gauges={"bench_images_per_sec": result["value"]})
    result["memory"] = _memory_block(net, batch)
    result["static_cost"] = _static_cost_block(net, batch, step_s,
                                               calibration_key="resnet50")
    result["kernels"] = _kernels_block()
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:  # optional deep dive: xplane trace of one scanned run
        with profiler.trace(trace_dir):
            p, o, s, key, losses = multi(p, o, s, key, n1, k1, [xs], [ys],
                                         None, None)
            np.asarray(losses)
        result["trace_dir"] = trace_dir
    return result


def bench_char_rnn(batch: int = 64, seq: int = 256, vocab: int = 96,
                   steps: int = 30) -> dict:
    """GravesLSTM char-RNN training throughput (BASELINE config #3): the
    recurrence-as-lax.scan path, chars/sec. Select with BENCH_MODEL=charrnn.
    Same on-device multi-step + host-fetch-sync methodology as
    :func:`bench_resnet50`."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.models.char_rnn import char_rnn

    conf = char_rnn(vocab_size=vocab, hidden_size=512, num_layers=2,
                    dtype="bfloat16")
    conf.backprop_type = "standard"  # time the full-sequence jitted step
    if os.environ.get("BENCH_PARAMS_BF16") == "1":
        conf.params_dtype = "bfloat16"  # bf16 weight carry (own metric key)
    net = MultiLayerNetwork(conf).init()
    multi = net._build_multi_step(steps)  # steps/batches ride as device scalars
    n1 = jnp.asarray(steps, jnp.int32)
    k1 = jnp.asarray(1, jnp.int32)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, vocab, size=(batch, seq + 1))
    xs = jax.device_put(
        jnp.asarray(np.eye(vocab, dtype=np.float32)[idx[None, :, :-1]])
    )
    ys = jax.device_put(
        jnp.asarray(np.eye(vocab, dtype=np.float32)[idx[None, :, 1:]])
    )
    key = jax.random.PRNGKey(0)
    p, o, s = net.params, net.opt_state, net.state
    p, o, s, key, losses = multi(p, o, s, key, n1, k1, xs, ys,
                                 None, None)  # warmup
    assert np.all(np.isfinite(np.asarray(losses))), "non-finite warmup losses"
    # median of 3 timed scans: at ~5ms/step this row showed a 3.1-4.2M
    # chars/sec band across processes in round 5 (undiagnosed, ROADMAP S3),
    # and the repeats are nearly free on an already-compiled program
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        p, o, s, key, losses = multi(p, o, s, key, n1, k1, xs, ys,
                                     None, None)
        losses = np.asarray(losses)  # host fetch = sync
        times.append(time.perf_counter() - t0)
        assert np.all(np.isfinite(losses)), "non-finite losses"
    dt = sorted(times)[1]
    # per-step FLOPs from the already-compiled scan program (cache hit —
    # same rules as bench_resnet50: nothing compiles between warmup and the
    # timed run; cost analysis counts the scan body once = per-step)
    from deeplearning4j_tpu import profiler

    flops_per_step = profiler.compiled_flops(
        multi, p, o, s, key, n1, k1, xs, ys, None, None)
    step_s = dt / steps
    result = {
        "metric": ("char_rnn_train_chars_per_sec"
                   + ("_bf16params" if conf.params_dtype == "bfloat16"
                      else "")),
        "value": round(steps * batch * seq / dt, 1),
        "unit": "chars/sec",
        "timed_steps": steps,
        "step_ms": round(1000 * step_s, 3),
        "run_step_ms": [round(1000 * t / steps, 3) for t in times],
    }
    if flops_per_step:
        # Deterministic whole-program-vs-per-body disambiguation: a >100%
        # threshold cannot catch loop-unrolled counting when true per-step
        # MFU is below 100/steps percent (plausible for a memory-bound bf16
        # scan). Lower the SAME program at steps=1 and compare — a ratio of
        # ~steps means cost analysis counted every scan iteration. Compiled
        # AFTER the timed region, so the measurement is undisturbed.
        flops_1 = profiler.compiled_flops(
            net._build_multi_step(1), p, o, s, key,
            jnp.asarray(1, jnp.int32), k1, xs, ys, None, None)
        if flops_1 and flops_per_step / flops_1 > steps / 2:
            flops_per_step /= steps
        elif not flops_1 and profiler.mfu(flops_per_step, step_s) > 100.0:
            flops_per_step /= steps  # backend hides cost analysis: heuristic
        result["flops_per_step"] = flops_per_step
        result["mfu_pct"] = round(profiler.mfu(flops_per_step, step_s), 1)
    result["telemetry"] = _telemetry_block(
        [t / steps for t in times], mfu_pct=result.get("mfu_pct"),
        extra_gauges={"bench_chars_per_sec": result["value"]})
    result["memory"] = _memory_block(net, np.zeros((batch, seq, vocab),
                                                   np.float32))
    result["static_cost"] = _static_cost_block(
        net, np.zeros((batch, seq, vocab), np.float32), step_s,
        calibration_key="charrnn")
    # Kernel-selection A/B (ISSUE 6 acceptance): re-run the same config with
    # every site pinned to the XLA reference path and report the measured
    # auto-vs-reference chars/sec ratio next to the variants auto picked.
    # One compile + one timed scan — cheap next to the main median-of-3.
    kernels_extra = {}
    try:
        from deeplearning4j_tpu.ops import kernel_select as ks

        compare = (os.environ.get("BENCH_KERNELS_COMPARE", "1") == "1"
                   and ks.mode() == "auto"
                   and (jax.default_backend() == "tpu"
                        or os.environ.get("BENCH_KERNELS_COMPARE") == "1"))
        if compare:
            with ks.forced_mode("reference"):
                net_r = MultiLayerNetwork(conf).init()
                multi_r = net_r._build_multi_step(steps)
                pr, orr, sr = net_r.params, net_r.opt_state, net_r.state
                pr, orr, sr, key, losses_r = multi_r(
                    pr, orr, sr, key, n1, k1, xs, ys, None, None)  # warmup
                np.asarray(losses_r)
                t0 = time.perf_counter()
                pr, orr, sr, key, losses_r = multi_r(
                    pr, orr, sr, key, n1, k1, xs, ys, None, None)
                np.asarray(losses_r)  # host fetch = sync
                dt_ref = time.perf_counter() - t0
            kernels_extra = {
                "reference_chars_per_sec": round(steps * batch * seq / dt_ref, 1),
                "auto_vs_reference": round(dt_ref / dt, 3),
            }
    except Exception as e:  # noqa: BLE001 - the metric line must survive
        kernels_extra = {"compare_error": f"{type(e).__name__}: {e}"[:300]}
    result["kernels"] = _kernels_block(kernels_extra)
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:  # xplane capture AFTER the timed region (same as resnet)
        with profiler.trace(trace_dir):
            p, o, s, key, losses = multi(p, o, s, key, n1, k1, xs, ys,
                                         None, None)
            np.asarray(losses)
        result["trace_dir"] = trace_dir
    return result


def _real_text_sequences(min_words: int = 40000):
    """Real English tokenized sentences from the Python stdlib's own module
    documentation — a genuine natural-language corpus that needs no egress
    (same no-download standard as the digits/iris/pangram rows)."""
    import importlib
    import re

    mods = ("json", "os", "collections", "itertools", "functools", "logging",
            "threading", "subprocess", "pathlib", "statistics", "random",
            "textwrap", "datetime", "decimal", "fractions", "pickle", "copy",
            "heapq", "bisect", "enum", "typing", "inspect", "ast", "argparse",
            "configparser", "csv", "sqlite3", "gzip", "tarfile", "zipfile",
            "hashlib", "uuid", "base64", "difflib", "doctest", "pdb",
            "socket", "selectors", "email", "calendar", "gettext", "locale",
            "shutil", "tempfile", "glob", "fnmatch", "codecs", "unicodedata",
            "string", "struct", "queue", "sched", "pprint", "reprlib")
    sents = []
    words = 0
    for m in mods:
        try:
            doc = importlib.import_module(m).__doc__ or ""
        except ImportError:
            continue
        for raw in re.split(r"[.!?;\n]+", doc):
            toks = re.findall(r"[a-z][a-z']+", raw.lower())
            if len(toks) >= 4:
                sents.append(toks)
                words += len(toks)
    if not sents:  # e.g. PYTHONOPTIMIZE=2 strips every __doc__
        raise RuntimeError("stdlib docstring corpus unavailable "
                           "(running with docstrings stripped?)")
    base = list(sents)
    while words < min_words:  # cycle the real text up to the target size
        sents.extend(base)
        words += sum(len(s) for s in base)
    return sents


def bench_word2vec(layer_size: int = 128, negative: int = 5,
                   batch_size: int = 4096) -> dict:
    """Embedding-engine throughput: batched skip-gram negative-sampling
    device kernel over a real corpus (reference hot loop:
    SkipGram.java:150 learnSequence, SequenceVectors.java:193-313 fit —
    the reference's second hot path after the NN tier; it trains
    pair-at-a-time on CPU threads, this framework batches examples into one
    jitted MXU step). words/sec counts corpus words consumed, the
    reference's own words-per-second convention; pairs/sec counts the
    (center, context) training examples the kernel actually processed."""
    from deeplearning4j_tpu.nlp.word2vec import Word2Vec

    sents = _real_text_sequences()
    n_words = sum(len(s) for s in sents)
    w2v = Word2Vec(layer_size=layer_size, window=5, negative=negative,
                   use_hs=False, min_word_frequency=2, batch_size=batch_size,
                   seed=7)
    w2v.fit(sents)  # builds vocab + compiles the NEG kernel (warmup epoch)
    n_pairs = 0
    n_calls = 0
    orig = w2v._device_step

    def counting(src, src_mask, tgt, lr):
        nonlocal n_pairs, n_calls
        n_pairs += len(tgt)
        n_calls += 1
        return orig(src, src_mask, tgt, lr)

    w2v._device_step = counting
    t0 = time.perf_counter()
    w2v.fit(sents)  # steady state: every program cached
    dt = time.perf_counter() - t0  # _sync_tables host fetch = the sync point
    w2v._device_step = orig
    vec = w2v.get_word_vector("the")
    assert vec is not None and np.all(np.isfinite(vec))
    return {
        "metric": "word2vec_skipgram_neg_words_per_sec",
        "value": round(n_words / dt, 1),
        "unit": "words/sec",
        "pairs_per_sec": round(n_pairs / dt, 1),
        "corpus_words": n_words,
        "vocab_size": w2v.vocab.num_words(),
        "layer_size": layer_size,
        "negative": negative,
        # mean device-kernel dispatch time stands in for step time here
        "telemetry": _telemetry_block(
            [dt / max(n_calls, 1)],
            extra_gauges={"bench_words_per_sec": round(n_words / dt, 1),
                          "bench_pairs_per_sec": round(n_pairs / dt, 1)}),
        "memory": _memory_block(),  # no layered net: cache + live stats only
    }


def bench_attention(batch: int = 4, heads: int = 8, seq: int = 4096,
                    dim: int = 64, steps: int = 20) -> dict:
    """Long-context attention throughput: the flash kernel vs the XLA
    attention path, fwd+bwd, causal, bf16, one-dispatch scan (same
    methodology as the other rows). The long-context tier (SURVEY §5.7) is
    a first-class subsystem; this gives it a measured number the way
    word2vec got one for the embedding tier. tokens/sec counts query
    positions processed per second (batch*seq per iteration)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.flash_attention import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention as attention_xla

    rng = np.random.default_rng(0)
    shape = (batch, heads, seq, dim)
    mk = lambda: jax.device_put(  # noqa: E731
        jnp.asarray(rng.normal(size=shape) * 0.3, jnp.bfloat16))
    q0, k0, v0 = mk(), mk(), mk()

    def timed(fn_name, attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))

        def body(carry, _):
            q, k, v = carry
            dq, dk, dv = g(q, k, v)
            # chain iterations through the grads so the scan can't elide
            # or reorder the N attention steps
            lr = jnp.bfloat16(1e-6)
            return (q - lr * dq.astype(q.dtype), k - lr * dk.astype(k.dtype),
                    v - lr * dv.astype(v.dtype)), None

        run = jax.jit(lambda q, k, v: jax.lax.scan(
            body, (q, k, v), None, length=steps)[0])
        out = run(q0, k0, v0)  # compile + warmup
        np.asarray(out[0])
        t0 = time.perf_counter()
        out = run(q0, k0, v0)
        res = np.asarray(out[0])  # host fetch = sync
        dt = time.perf_counter() - t0
        assert np.all(np.isfinite(res.astype(np.float32))), fn_name
        return dt

    dt_flash = timed("flash", lambda q, k, v: flash_attention(
        q, k, v, causal=True))
    dt_xla = timed("xla", lambda q, k, v: attention_xla(q, k, v, causal=True))
    tokens = steps * batch * seq
    # record what the selection layer resolves for this exact shape, so the
    # artifact shows the auto pick next to the measured flash-vs-xla ratio
    try:
        from deeplearning4j_tpu.ops import select_attention_variant

        auto_pick = select_attention_variant(batch, heads, seq, dim,
                                             2, causal=True)  # bf16 inputs
    except Exception:  # noqa: BLE001
        auto_pick = None
    return {
        "metric": "flash_attention_train_tokens_per_sec",
        "value": round(tokens / dt_flash, 1),
        "unit": "tokens/sec",
        "xla_tokens_per_sec": round(tokens / dt_xla, 1),
        "flash_vs_xla": round(dt_xla / dt_flash, 2),
        "shape": {"batch": batch, "heads": heads, "seq": seq, "dim": dim},
        "timed_steps": steps,
        "step_ms": round(1000 * dt_flash / steps, 3),
        "telemetry": _telemetry_block(
            [dt_flash / steps],
            extra_gauges={"bench_tokens_per_sec": round(tokens / dt_flash, 1)}),
        "memory": _memory_block(),  # raw-kernel mode: cache + live stats only
        # raw-kernel A/B already measures flash vs xla directly; the block
        # records what auto WOULD pick for this shape alongside
        "kernels": _kernels_block({
            "flash_vs_xla_measured": round(dt_xla / dt_flash, 2),
            "auto_pick": auto_pick}),
    }


def bench_mlp_mnist(batch: int = 512, steps: int = 50, warmup: int = 5) -> dict:
    import jax

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.datasets.iterators import DataSet

    conf = MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=1024, activation="relu"),
            DenseLayer(n_out=1024, activation="relu"),
            OutputLayer(n_out=10, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(784),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
        dtype="bfloat16",
        seed=42,
    )
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    ds = DataSet(
        rng.normal(size=(batch, 784)).astype(np.float32),
        np.eye(10, dtype=np.float32)[rng.integers(0, 10, size=batch)],
    )
    from deeplearning4j_tpu.telemetry import MetricsRegistry, Telemetry

    # full telemetry spine on the fallback too: the jitted step carries the
    # device metrics vector, fetched ONCE after the timed loop (K=steps)
    reg = MetricsRegistry()
    net.set_telemetry(Telemetry(registry=reg, fetch_every=steps + warmup))
    net._train_step = net._build_train_step()
    for _ in range(warmup):
        net._fit_batch(ds)
    jax.block_until_ready(net.params)
    t0 = time.perf_counter()
    for _ in range(steps):
        net._fit_batch(ds)
    jax.block_until_ready(net.params)
    dt = time.perf_counter() - t0
    net.telemetry.flush()
    grad_norm = reg.get("dl4jtpu_train_grad_norm")
    result = {
        "metric": "mlp_mnist_train_samples_per_sec",
        "value": round(steps * batch / dt, 1),
        "unit": "samples/sec",
        "telemetry": _telemetry_block(
            [dt / steps],
            extra_gauges={"bench_samples_per_sec": round(steps * batch / dt, 1),
                          "bench_last_grad_norm": round(grad_norm.value, 6)}),
        "memory": _memory_block(net, batch),
        "static_cost": _static_cost_block(net, batch, dt / steps,
                                          calibration_key="mlp"),
        "kernels": _kernels_block(),
    }
    return result


def bench_autotune(budget_s: float = None) -> dict:
    """Closed-loop autopilot A/B (ISSUE 12 acceptance): a short
    fit-objective search on the bench MLP through the real tuner
    (roofline-pruned successive halving, compile-pinned trials), then the
    default and the winning config re-measured at EQUAL fidelity. Reports
    tuned/default as the gated ratio — the loop only stays green while the
    autopilot returns configs at least as fast as the hand-picked
    defaults. Select with BENCH_MODEL=autotune."""
    import tempfile

    from deeplearning4j_tpu.tune.search import MlpFitWorkload, run_autotune

    if budget_s is None:
        budget_s = float(os.environ.get("BENCH_AUTOTUNE_BUDGET_S", "75"))
    workload = MlpFitWorkload()
    store_path = os.environ.get("DL4JTPU_TUNED_PATH") or os.path.join(
        tempfile.mkdtemp(prefix="dl4jtpu_tuned_"), "TUNED.json")
    space = {"train_batch": (32, 256, 512), "stage_window": (2, 4, 8),
             "telemetry_fetch_every": (10, 50)}
    search = run_autotune(
        model="mlp", objective="fit", budget_s=budget_s, space=space,
        workload=workload, store_path=store_path, fidelities=(1, 2))
    # equal-fidelity A/B: the search's own rungs ran at mixed fidelity, so
    # the headline ratio re-measures both configs back to back
    fid = int(os.environ.get("BENCH_AUTOTUNE_AB_FIDELITY", "2"))
    default_sps = workload.measure(search.default.config, fid)["value"]
    tuned_sps = workload.measure(search.best.config, fid)["value"]
    measured = [t for t in search.trials if t.measured is not None]
    return {
        "metric": "autotune_tuned_over_default_ratio",
        "value": round(tuned_sps / default_sps, 4),
        "unit": "x",
        "default_samples_per_sec": round(default_sps, 1),
        "tuned_samples_per_sec": round(tuned_sps, 1),
        "best_config": search.best.config,
        "trials_measured": len(measured),
        "trials_pruned_by_prior": len(search.pruned),
        "compiles_in_timed_regions": sum(
            t.compiles_measured for t in measured),
        "env_ok": search.env_ok,
        "tuned_store": search.store_path,
        "tuned_key": search.key,
        "search_elapsed_s": round(search.elapsed_s, 1),
        "memory": _memory_block(),
    }


def bench_ragged(batch: int = 512, tail: int = 196, full_batches: int = 10,
                 stage: int = 4, epochs: int = 4, hidden: int = 1024) -> dict:
    """Ragged-epoch throughput (ISSUE 3 acceptance): every epoch ends in a
    trailing partial batch. Without bucketing that tail (and, historically,
    any shape change) forced per-batch dispatch and fresh XLA programs; with
    the bucketed stager + compile manager the whole epoch runs staged with a
    bounded executable set. Reports samples/sec WITH and WITHOUT bucketing,
    the staged-step fraction, and the compile counters
    (``dl4jtpu_compiles_total`` + compile-seconds) so BENCH_*.json tracks the
    recompile trajectory round over round. Select with BENCH_MODEL=ragged."""
    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.datasets.iterators import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    def make_net(seed=42):
        conf = MultiLayerConfiguration(
            layers=[
                DenseLayer(n_out=hidden, activation="relu"),
                DenseLayer(n_out=hidden, activation="relu"),
                OutputLayer(n_out=10, activation="softmax", loss="mcxent"),
            ],
            input_type=InputType.feed_forward(784),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
            dtype="bfloat16",
            seed=seed,
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)

    def mk(rows):
        return DataSet(
            rng.normal(size=(rows, 784)).astype(np.float32),
            np.eye(10, dtype=np.float32)[rng.integers(0, 10, rows)],
        )

    batches = [mk(batch) for _ in range(full_batches)] + [mk(tail)]
    n_samples = full_batches * batch + tail
    cm = get_compile_manager()

    def timed_fit(bucketing: bool):
        import jax

        net = make_net()
        it = ListDataSetIterator(list(batches))
        net.fit(it, epochs=1, stage_on_device=stage,
                bucketing=bucketing)  # warmup epoch: pays the compiles
        jax.block_until_ready(net.params)
        compiles_before = cm.compiles.value
        t0 = time.perf_counter()
        net.fit(it, epochs=epochs, stage_on_device=stage,
                bucketing=bucketing)
        jax.block_until_ready(net.params)
        dt = time.perf_counter() - t0
        return {
            "samples_per_sec": round(epochs * n_samples / dt, 1),
            "staged_fraction": round(net.staged_steps_total / net.iteration, 4),
            "warm_epoch_compiles": cm.compiles.value - compiles_before,
            "seconds": round(dt, 4),
        }

    bucketed = timed_fit(True)
    fallback = timed_fit(False)
    cm_stats = cm.stats()
    result = {
        "metric": "ragged_epoch_bucketed_train_samples_per_sec",
        "value": bucketed["samples_per_sec"],
        "unit": "samples/sec",
        "bucketed": bucketed,
        "unbucketed": fallback,
        "bucketing_speedup": round(
            bucketed["samples_per_sec"] / max(fallback["samples_per_sec"], 1e-9), 3),
        "shape": {"batch": batch, "tail": tail, "full_batches": full_batches,
                  "stage": stage, "epochs": epochs, "hidden": hidden},
    }
    result["telemetry"] = _telemetry_block(
        [bucketed["seconds"] / max(epochs * (full_batches + 1), 1)],
        extra_gauges={
            "bench_samples_per_sec": bucketed["samples_per_sec"],
            "bench_staged_fraction": bucketed["staged_fraction"],
            "bench_compiles_total": cm_stats["compiles_total"],
            "bench_compile_seconds_sum": cm_stats["compile_seconds"]["sum"],
        })
    result["telemetry"]["compile"] = cm_stats
    result["memory"] = _memory_block(make_net(), batch)
    result["static_cost"] = _static_cost_block(
        make_net(), batch,
        bucketed["seconds"] / max(epochs * (full_batches + 1), 1),
        calibration_key="ragged")
    result["kernels"] = _kernels_block()
    return result


def bench_serve(feature_dim: int = 256, hidden: int = 512, classes: int = 10,
                levels=(1, 4, 16), requests_per_client: int = 30,
                max_rows: int = 8, max_delay_ms: float = 2.0,
                max_batch: int = 64) -> dict:
    """Serving throughput under offered load (ISSUE 7 acceptance): an
    in-process :class:`serving.InferenceService` fronts an MLP, client
    threads fire mixed-size requests (1..max_rows rows) that the dynamic
    micro-batcher coalesces into pow2-bucket dispatches. Sweeps offered
    load (concurrent clients), reports the best samples/sec with exact
    p50/p99 request latency per level, and pins the recompile story: after
    ``warmup()`` the whole sweep must run at ZERO warm compiles (the count
    is in the artifact either way). Select with BENCH_MODEL=serve."""
    import threading

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
    from deeplearning4j_tpu.serving import InferenceService
    from deeplearning4j_tpu.telemetry import MetricsRegistry

    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=hidden, activation="relu"),
            DenseLayer(n_out=hidden, activation="relu"),
            OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(feature_dim),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
        seed=7,
    )).init()
    svc = InferenceService(registry=MetricsRegistry(),
                           max_delay_ms=max_delay_ms, max_batch=max_batch)
    svc.register("bench", net)
    svc.warmup("bench", np.zeros((1, feature_dim), np.float32))
    cm = get_compile_manager()
    rng = np.random.default_rng(0)
    shapes = [rng.normal(size=(1 + int(r), feature_dim)).astype(np.float32)
              for r in rng.integers(0, max_rows, size=64)]

    def run_level(clients: int) -> dict:
        for e in svc._models.values():
            e.latencies.clear()
        compiles_before = cm.compiles.value
        rows_served = [0] * clients

        def client(ci: int):
            for i in range(requests_per_client):
                x = shapes[(ci * requests_per_client + i) % len(shapes)]
                out = svc.predict("bench", x, timeout_s=60)
                rows_served[ci] += int(np.asarray(out).shape[0])

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        stats = svc.stats()["models"]["bench"]
        return {
            "clients": clients,
            "samples_per_sec": round(sum(rows_served) / dt, 1),
            "requests_per_sec": round(clients * requests_per_client / dt, 1),
            "p50_ms": round(1000 * (stats["latency_seconds"]["p50"] or 0), 3),
            "p99_ms": round(1000 * (stats["latency_seconds"]["p99"] or 0), 3),
            "mean_batch_fill_ratio": stats["mean_batch_fill_ratio"],
            "warm_compiles": cm.compiles.value - compiles_before,
            "seconds": round(dt, 4),
        }

    sweep = [run_level(c) for c in levels]
    best = max(sweep, key=lambda r: r["samples_per_sec"])
    final_stats = svc.stats()["models"]["bench"]
    svc.stop()
    result = {
        "metric": "serve_offered_load_samples_per_sec",
        "value": best["samples_per_sec"],
        "unit": "samples/sec",
        "best_level": best,
        "sweep": {str(r["clients"]): r for r in sweep},
        "warm_compiles_total": sum(r["warm_compiles"] for r in sweep),
        "shape": {"feature_dim": feature_dim, "hidden": hidden,
                  "classes": classes, "max_rows": max_rows,
                  "max_delay_ms": max_delay_ms, "max_batch": max_batch,
                  "requests_per_client": requests_per_client},
    }
    result["telemetry"] = _telemetry_block(
        [best["seconds"] / max(best["clients"] * requests_per_client, 1)],
        extra_gauges={
            "bench_samples_per_sec": best["samples_per_sec"],
            "bench_serve_p99_ms": best["p99_ms"],
            "bench_serve_batch_fill": final_stats["mean_batch_fill_ratio"] or 0.0,
            "bench_compiles_total": cm.stats()["compiles_total"],
        })
    result["telemetry"]["compile"] = cm.stats()
    result["memory"] = _memory_block()
    result["kernels"] = _kernels_block()
    return result


def bench_online(feature_dim: int = 32, hidden: int = 64, classes: int = 8,
                 batch: int = 32, stage: int = 4, records: int = 6144,
                 warm_records: int = 1024) -> dict:
    """Sustained-ingest online-learning throughput (ISSUE 10 acceptance):
    an :class:`runtime.online.OnlineTrainer` drains a producer-fed
    ``QueueSource`` into staged ``fit_on_device`` windows, with a versioned
    checkpoint + live hot-swap into an :class:`serving.InferenceService`
    fired MID-RUN. Reports records/sec over the post-warmup phase, pins the
    recompile story (steady-state ingest must admit zero new programs) and
    records whether the swap changed served predictions without a restart.
    Select with BENCH_MODEL=online."""
    import tempfile
    import threading

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
    from deeplearning4j_tpu.runtime.online import OnlineTrainer
    from deeplearning4j_tpu.serving import InferenceService
    from deeplearning4j_tpu.streaming import QueueSource
    from deeplearning4j_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=hidden, activation="relu"),
            OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(feature_dim),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
        seed=11,
    )).init()
    store = CheckpointStore(tempfile.mkdtemp(prefix="dl4jtpu_bench_ckpt_"),
                            retain=3, registry=reg)
    svc = InferenceService(registry=reg, max_delay_ms=0.5)
    source = QueueSource(maxsize=16384)
    trainer = OnlineTrainer(net, source, batch=batch, stage=stage,
                            linger=0.05, name="bench-online",
                            checkpoint_store=store,
                            checkpoint_every_steps=0,  # swaps are explicit
                            service=svc, serve_as="bench-live",
                            registry=reg)
    rng = np.random.default_rng(3)
    true_w = rng.normal(size=(feature_dim, classes))
    eye = np.eye(classes, dtype=np.float32)

    def produce(n: int) -> None:
        for _ in range(n):
            x = rng.normal(size=feature_dim).astype(np.float32)
            source.put(x, eye[int(np.argmax(x @ true_w))])

    def wait_until(pred, deadline_s: float = 120.0) -> bool:
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.02)
        return False

    trainer.start()
    cm = get_compile_manager()
    probe = rng.normal(size=(4, feature_dim)).astype(np.float32)
    try:
        # warm phase: the window programs AND the serving buckets compile
        # here — everything after the mark must be a cache hit
        produce(warm_records)
        warmed = wait_until(
            lambda: trainer.stats()["records_total"] >= warm_records)
        svc.warmup("bench-live", probe[:1])
        served_before = np.asarray(svc.predict("bench-live", probe,
                                               timeout_s=60))
        compiles_before = cm.compiles.value
        # timed phase, with a checkpoint + hot-swap fired mid-run
        feeder = threading.Thread(target=produce, args=(records,),
                                  daemon=True)
        t0 = time.perf_counter()
        feeder.start()
        wait_until(lambda: trainer.stats()["records_total"]
                   >= warm_records + records // 2)
        swap_version = trainer.checkpoint_now(swap=True)
        done = wait_until(lambda: trainer.stats()["records_total"]
                          >= warm_records + records)
        dt = time.perf_counter() - t0
        feeder.join(timeout=10)
        served_after = np.asarray(svc.predict("bench-live", probe,
                                              timeout_s=60))
        warm_compiles = cm.compiles.value - compiles_before
        stats = trainer.stats()
    finally:
        trainer.stop(checkpoint=False)
        svc.stop()
    value = round(records / dt, 1) if done else 0.0
    result = {
        "metric": "online_ingest_samples_per_sec",
        "value": value,
        "unit": "records/sec",
        "records": records,
        "seconds": round(dt, 4),
        "completed": bool(done and warmed),
        "warm_compiles": warm_compiles,
        "swap": {
            "version": int(swap_version),
            "served_changed": bool(
                np.abs(served_after - served_before).max() > 0),
            "swaps_total": stats["swaps_total"],
        },
        "windows_total": stats["windows_total"],
        "steps_total": stats["steps_total"],
        "checkpoint_versions": [
            v["version"] for v in (stats["checkpoints"] or
                                   {"versions": []})["versions"]],
        "shape": {"feature_dim": feature_dim, "hidden": hidden,
                  "classes": classes, "batch": batch, "stage": stage},
    }
    result["telemetry"] = _telemetry_block(
        [dt / max(stats["steps_total"], 1)],
        extra_gauges={
            "bench_samples_per_sec": value,
            "bench_online_windows": stats["windows_total"],
            "bench_compiles_total": cm.stats()["compiles_total"],
        })
    result["telemetry"]["compile"] = cm.stats()
    result["memory"] = _memory_block()
    result["kernels"] = _kernels_block()
    return result


def bench_fleet(feature_dim: int = 16, classes: int = 8,
                clients: int = 8, requests_per_client: int = 40,
                max_rows: int = 8, worker_counts=(1, 2)) -> dict:
    """Multi-process fleet throughput under offered load (ISSUE 13
    acceptance): a :class:`fleet.FleetRouter` spawns N forced-CPU worker
    processes that warm-boot from a shared checkpoint store's bundle,
    client threads fire mixed-size requests through the router's
    least-outstanding picker. Runs the SAME offered load against every
    count in ``worker_counts`` and reports the scale-out ratio (last vs
    first) — meaningful only on a multi-core host, so the check.sh gate
    enforces the >=1.5x floor only when ``os.cpu_count() >= 4`` (the
    ratio is in the artifact either way, labeled with the core count).
    Warm boot is pinned too: every worker must report
    ``compiles_since_ready == 0`` after serving. Select with
    BENCH_MODEL=fleet."""
    import shutil
    import tempfile
    import threading

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.fleet import FleetRouter, build_bundle, save_bundle
    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore

    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=32, activation="relu"),
            OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(feature_dim),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7,
    )).init()
    work = tempfile.mkdtemp(prefix="dl4jtpu-bench-fleet-")
    store_dir = os.path.join(work, "store")
    store = CheckpointStore(store_dir)
    store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, feature_dim), np.float32), argmax=True,
        max_batch=max_rows))
    rng = np.random.default_rng(0)
    shapes = [rng.normal(size=(1 + int(r), feature_dim)).astype(np.float32)
              for r in rng.integers(0, max_rows, size=64)]

    def run_level(n_workers: int) -> dict:
        router = FleetRouter(
            store_dir, workers=n_workers, poll_s=0.5,
            shed_outstanding=4096, respawn=False,
            worker_args={"max_delay_ms": 0, "max_batch": max_rows})
        router.start()
        rows_served = [0] * clients
        errors = []

        def client(ci: int):
            for i in range(requests_per_client):
                x = shapes[(ci * requests_per_client + i) % len(shapes)]
                status, body, _ = router.route_predict(
                    {"features": x.tolist()})
                if status == 200:
                    rows_served[ci] += len(body["output"])
                else:
                    errors.append((status, body))

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        # one final health poll per worker: a short level can finish before
        # the supervisor's first poll_s tick, which would leave the latency
        # rings empty and the compile counters unset
        for handle in router.workers:
            router._check_worker(handle)
        stats = router.stats()
        worker_compiles = [w["compiles_since_ready"]
                           for w in stats["workers"]]
        router.stop()
        return {
            "workers": n_workers,
            "samples_per_sec": round(sum(rows_served) / dt, 1),
            "requests_per_sec": round(
                clients * requests_per_client / dt, 1),
            "p50_ms": round(
                1000 * (stats["latency_seconds"]["p50"] or 0), 3),
            "p99_ms": round(
                1000 * (stats["latency_seconds"]["p99"] or 0), 3),
            "errors": len(errors),
            "warm_compiles": worker_compiles,
            "seconds": round(dt, 4),
        }

    try:
        sweep = [run_level(n) for n in worker_counts]
    finally:
        shutil.rmtree(work, ignore_errors=True)
    best = max(sweep, key=lambda r: r["samples_per_sec"])
    scale_out = (sweep[-1]["samples_per_sec"]
                 / max(sweep[0]["samples_per_sec"], 1e-9))
    result = {
        "metric": "fleet_offered_load_samples_per_sec",
        "value": best["samples_per_sec"],
        "unit": "samples/sec",
        "best_level": best,
        "sweep": {str(r["workers"]): r for r in sweep},
        "scale_out_ratio": round(scale_out, 3),
        "cpu_count": os.cpu_count(),
        "warm_compiles_total": sum(
            sum(r["warm_compiles"]) for r in sweep
            if None not in r["warm_compiles"]),
        "errors_total": sum(r["errors"] for r in sweep),
        "shape": {"feature_dim": feature_dim, "classes": classes,
                  "clients": clients, "max_rows": max_rows,
                  "requests_per_client": requests_per_client,
                  "worker_counts": list(worker_counts)},
    }
    result["telemetry"] = _telemetry_block(
        [best["seconds"] / max(clients * requests_per_client, 1)],
        extra_gauges={
            "bench_samples_per_sec": best["samples_per_sec"],
            "bench_fleet_scale_out_ratio": result["scale_out_ratio"],
            "bench_fleet_p99_ms": best["p99_ms"],
        })
    result["memory"] = _memory_block()
    return result


def bench_history(feature_dim: int = 16, classes: int = 8,
                  clients: int = 4, requests_per_client: int = 40,
                  max_rows: int = 8, rounds: int = 5,
                  workers: int = 2) -> dict:
    """History-plane overhead (ISSUE 19 acceptance): ONE warm-booted
    2-worker fleet with the scrape loop + process sampler live, the SAME
    offered load run in interleaved trials with history ingestion
    toggled off/on (``set_history_enabled`` pauses the router scrape,
    the process sampler and every worker's sampler). The gated metric is
    history-ON throughput; ``overhead_ratio`` (median on / median off)
    must stay within 3% of disabled — check.sh enforces the 1.03
    ceiling. Select with BENCH_MODEL=history."""
    import shutil
    import statistics
    import tempfile
    import threading

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.fleet import FleetRouter, build_bundle, save_bundle
    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore

    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=32, activation="relu"),
            OutputLayer(n_out=classes, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(feature_dim),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7,
    )).init()
    work = tempfile.mkdtemp(prefix="dl4jtpu-bench-history-")
    store_dir = os.path.join(work, "store")
    store = CheckpointStore(store_dir)
    store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, feature_dim), np.float32), argmax=True,
        max_batch=max_rows))
    rng = np.random.default_rng(0)
    shapes = [rng.normal(size=(1 + int(r), feature_dim)).astype(np.float32)
              for r in rng.integers(0, max_rows, size=64)]

    def trial(router) -> float:
        rows_served = [0] * clients
        errors = []

        def client(ci: int):
            for i in range(requests_per_client):
                x = shapes[(ci * requests_per_client + i) % len(shapes)]
                status, body, _ = router.route_predict(
                    {"features": x.tolist()})
                if status == 200:
                    rows_served[ci] += len(body["output"])
                else:
                    errors.append(status)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"{len(errors)} failed requests: "
                               f"{sorted(set(errors))}")
        return sum(rows_served) / dt

    router = FleetRouter(
        store_dir, workers=workers, poll_s=0.5, scrape_s=0.5,
        history=True, shed_outstanding=4096, respawn=False,
        worker_args={"max_delay_ms": 0, "max_batch": max_rows})
    router.start()
    off, on = [], []
    try:
        trial(router)  # warm both workers' compiled paths
        for _ in range(rounds):  # interleaved so drift hits both arms
            router.set_history_enabled(False)
            off.append(trial(router))
            router.set_history_enabled(True)
            on.append(trial(router))
        router.scrape_once()  # the artifact carries a live sensor proof
        history_stats = router.history.stats()
        sensor_series = sorted(
            n for n in router.history.series_names()
            if n.startswith(("fleet.", "worker.")))
        stats = router.stats()
        worker_compiles = [w["compiles_since_ready"]
                           for w in stats["workers"]]
    finally:
        router.stop()
        shutil.rmtree(work, ignore_errors=True)
    m_off = statistics.median(off)
    m_on = statistics.median(on)
    result = {
        "metric": "history_on_samples_per_sec",
        "value": round(m_on, 1),
        "unit": "samples/sec",
        "overhead_ratio": round(m_off / max(m_on, 1e-9), 4),
        "samples_per_sec_off": round(m_off, 1),
        "trials_off": [round(v, 1) for v in off],
        "trials_on": [round(v, 1) for v in on],
        "history_series": history_stats["series"],
        "history_samples_total": history_stats["samples_total"],
        "history_bytes": history_stats["bytes"],
        "history_byte_budget": history_stats["byte_budget"],
        "sensor_series": sensor_series,
        "warm_compiles": worker_compiles,
        "shape": {"feature_dim": feature_dim, "classes": classes,
                  "clients": clients, "max_rows": max_rows,
                  "requests_per_client": requests_per_client,
                  "rounds": rounds, "workers": workers},
    }
    result["telemetry"] = _telemetry_block(
        [1.0 / max(m_on, 1e-9)],
        extra_gauges={
            "bench_samples_per_sec": result["value"],
            "bench_history_overhead_ratio": result["overhead_ratio"],
        })
    result["memory"] = _memory_block()
    return result


def bench_shard(batch: int = 256, hidden: int = 2048, feature_dim: int = 784,
                classes: int = 10, steps: int = 12, groups: int = 2) -> dict:
    """Sharding-layout throughput + per-device HBM (ISSUE 8 acceptance):
    the SAME model trained replicated (pure dp), fsdp-sharded, and
    fsdp+bf16-storage through :class:`parallel.MeshLayout`, all on one
    mesh family. Reports samples/sec per variant, the per-device HBM of
    each variant's staged executable (the PR 4 ``memory_analysis`` records
    — fsdp+bf16 must land well under the replicated f32 footprint), and a
    DT207-style collective census of the compiled per-step program
    (all-gather/reduce-scatter pairs are GSPMD's fsdp signature). Select
    with BENCH_MODEL=shard; needs a multi-device backend (the CPU fallback
    forces a 4-device virtual mesh).

    ISSUE 15 grows two tensor-parallel variants on an attention net:
    ``tp_generic`` (shape-heuristic specs — pays the DT305 per-step
    activation collectives) vs ``tp_headaware`` (``roles=True`` — QKV
    column-parallel, out row-parallel, ONE all-reduce per block). The
    head-aware samples/sec rides the metric line as an ``aux_metrics``
    entry so BENCH_BASELINE.json anchors it independently."""
    import jax

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
    from deeplearning4j_tpu.parallel import MeshLayout, ParallelWrapper
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"BENCH_MODEL=shard needs a multi-device mesh, have {n_dev}")
    ways = 4 if n_dev >= 4 else n_dev

    def make_net(seed=42):
        return MultiLayerNetwork(MultiLayerConfiguration(
            layers=[
                DenseLayer(n_out=hidden, activation="relu"),
                DenseLayer(n_out=hidden, activation="relu"),
                OutputLayer(n_out=classes, activation="softmax",
                            loss="mcxent"),
            ],
            input_type=InputType.feed_forward(feature_dim),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
            seed=seed,
        )).init()

    a_batch, a_t, a_feat, a_d, a_heads, a_classes = 32, 32, 64, 128, 4, 16

    def make_attn_net(seed=42):
        return MultiLayerNetwork(MultiLayerConfiguration(
            layers=[
                SelfAttentionLayer(n_out=a_d, n_heads=a_heads,
                                   activation="identity"),
                RnnOutputLayer(n_in=a_d, n_out=a_classes,
                               activation="softmax", loss="mcxent"),
            ],
            input_type=InputType.recurrent(a_feat),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
            seed=seed,
        )).init()

    rng = np.random.default_rng(0)
    xs = rng.normal(size=(groups, batch, feature_dim)).astype(np.float32)
    ys = np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, (groups, batch))]
    axs = rng.normal(size=(groups, a_batch, a_t, a_feat)).astype(np.float32)
    ays = np.eye(a_classes, dtype=np.float32)[
        rng.integers(0, a_classes, (groups, a_batch, a_t))]
    cm = get_compile_manager()

    def census(net, layout, x, y, t=None):
        """Measured vs predicted collective census (ISSUE 9). Measured:
        collective ops parsed out of the compiled per-step program's
        post-SPMD HLO (kind, mesh axes from replica groups, per-device
        payload bytes). Predicted: the static sharding-flow pass over the
        SAME step's jaxpr — no devices touched. ``match`` holds them to
        parity (same major kinds/axes, byte totals within 1.5x) — the
        ground truth that keeps the static pass honest. Compiled AFTER the
        timed region; failures degrade to an error note."""
        from deeplearning4j_tpu.analysis.shard_flow import (
            check_network_shard_flow, compare_census, hlo_collective_census)

        try:
            x_d = layout.put(x, layout.input_sharding(x))
            y_d = layout.put(y, layout.input_sharding(y))
            step = net._build_train_step()
            hlo = step.lower(net.params, net.opt_state, net.state, x_d, y_d,
                             net._rng, None, None).compile().as_text()
            measured = hlo_collective_census(hlo, layout)
            flow = check_network_shard_flow(net, x.shape[0], layout,
                                            timesteps_probe=t)
            predicted = flow["census"]
            return {
                "measured": measured,
                "predicted": predicted,
                "predicted_comm_bytes_per_step": flow["comm_bytes_per_step"],
                "findings": [f.rule_id for f in flow["findings"]],
                "match": compare_census(predicted, measured),
            }
        except Exception as e:  # noqa: BLE001 - the metric line must survive
            return {"error": f"{type(e).__name__}: {e}"[:200]}

    def run_variant(label, layout, factory=make_net, data=None, t=None):
        vx, vy = data if data is not None else (xs, ys)
        net = factory()
        wrapper = ParallelWrapper(net, layout=layout)
        wrapper.fit_on_device(vx, vy, steps=steps)  # warmup: pays compiles
        before_mem = set(cm.memory_records())
        compiles_before = cm.compiles.value
        t0 = time.perf_counter()
        losses = wrapper.fit_on_device(vx, vy, steps=steps)
        dt = time.perf_counter() - t0  # losses host fetch = the sync point
        assert np.all(np.isfinite(losses)), f"non-finite {label} losses"
        # the staged executable's XLA memory record (post-SPMD = per-device)
        new_mem = [rec for k, rec in cm.memory_records().items()
                   if k not in before_mem]
        hbm = None
        for rec in new_mem:  # warm run admits nothing new; read the live set
            if rec.get("available"):
                hbm = int(rec["total_bytes"])
        if hbm is None:
            for k, rec in cm.memory_records().items():
                if rec.get("kind", "").endswith("multi_step") \
                        and rec.get("available"):
                    hbm = int(rec["total_bytes"])
        return {
            "samples_per_sec": round(steps * vx.shape[1] / dt, 1),
            "per_device_hbm_bytes": hbm,
            "warm_compiles": cm.compiles.value - compiles_before,
            "seconds": round(dt, 4),
            "layout": layout.describe(),
            "collectives": census(net, layout, vx[0], vy[0], t=t),
        }

    dp_ways = max(ways // 2, 1)
    variants = {
        "replicated_f32": run_variant(
            "replicated_f32", MeshLayout(data=ways, fsdp=1)),
        "fsdp": run_variant("fsdp", MeshLayout(data=1, fsdp=ways)),
        "fsdp_bf16": run_variant(
            "fsdp_bf16", MeshLayout(data=1, fsdp=ways,
                                    params_dtype="bfloat16")),
        # ISSUE 15: same attention net, same dp×tp mesh — the only delta is
        # the layer-roles registry. Generic tp pays the DT305 activation
        # collectives; head-aware tp pays ONE all-reduce per block.
        "tp_generic": run_variant(
            "tp_generic", MeshLayout(data=dp_ways, tp=2),
            factory=make_attn_net, data=(axs, ays), t=a_t),
        "tp_headaware": run_variant(
            "tp_headaware", MeshLayout(data=dp_ways, tp=2, roles=True),
            factory=make_attn_net, data=(axs, ays), t=a_t),
    }
    rep_hbm = variants["replicated_f32"]["per_device_hbm_bytes"]
    fb_hbm = variants["fsdp_bf16"]["per_device_hbm_bytes"]
    tp_gen = variants["tp_generic"]["samples_per_sec"]
    tp_head = variants["tp_headaware"]["samples_per_sec"]
    result = {
        "metric": "shard_fsdp_train_samples_per_sec",
        "value": variants["fsdp_bf16"]["samples_per_sec"],
        "unit": "samples/sec",
        "variants": variants,
        "hbm_fsdp_bf16_vs_replicated": (
            round(fb_hbm / rep_hbm, 4) if rep_hbm and fb_hbm else None),
        "tp_headaware_vs_generic": (
            round(tp_head / tp_gen, 4) if tp_gen else None),
        # gated independently against its BENCH_BASELINE.json anchor
        "aux_metrics": {
            "shard_tp_headaware_train_samples_per_sec": tp_head,
        },
        "shape": {"batch": batch, "hidden": hidden, "steps": steps,
                  "groups": groups, "ways": ways, "devices": n_dev,
                  "attn": {"batch": a_batch, "t": a_t, "d": a_d,
                           "heads": a_heads}},
    }
    result["telemetry"] = _telemetry_block(
        [variants["fsdp_bf16"]["seconds"] / steps],
        extra_gauges={
            "bench_samples_per_sec": result["value"],
            "bench_hbm_ratio": result["hbm_fsdp_bf16_vs_replicated"] or 0.0,
        })
    result["telemetry"]["compile"] = cm.stats()
    result["memory"] = _memory_block(make_net(), batch)
    result["kernels"] = _kernels_block()
    return result


def bench_pipeline(batch_mb: int = 256, hidden: int = 512,
                   feature_dim: int = 128, classes: int = 10,
                   depth: int = 4, steps: int = 4) -> dict:
    """Pipeline-axis throughput (ISSUE 18 acceptance): the SAME dense stack
    trained unpiped (pure dp over the whole mesh) vs piped
    (``MeshLayout(pipe=2)`` × dp, 1F1B micro-batch interleaving through
    :class:`parallel.PipelinedTrainer`). Reports samples/sec for both, and
    measures the schedule bubble empirically: with the micro-batch SIZE held
    fixed, step time is affine in the micro-batch COUNT —
    ``T(M) = a·M + b`` where the intercept ``b`` is the (P-1) warmup/drain
    ticks no amount of work amortises. ``measured_bubble = b/T(M1)`` is held
    to 1.5x of the roofline's ``(P-1)/(M1+P-1)`` term (the ground truth that
    keeps the cost model's pipeline branch honest). warm_compiles is
    asserted ZERO: after ``warm_up`` every fit step must reuse the one
    AOT-admitted executable. Select with BENCH_MODEL=pipeline; needs a
    multi-device backend (the CPU fallback forces a 4-device virtual mesh).
    """
    import jax

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.parallel import (
        MeshLayout, ParallelWrapper, PipelinedTrainer)
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"BENCH_MODEL=pipeline needs a multi-device mesh, have {n_dev}")
    pipe = 2
    dp = max(n_dev // pipe, 1) if n_dev >= 4 else 1

    def make_net(seed=42):
        return MultiLayerNetwork(MultiLayerConfiguration(
            layers=[DenseLayer(n_out=hidden, activation="relu")
                    for _ in range(depth)]
            + [OutputLayer(n_out=classes, activation="softmax",
                           loss="mcxent")],
            input_type=InputType.feed_forward(feature_dim),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
            seed=seed,
        )).init()

    # micro-batch size stays FIXED across the two piped runs; the batch
    # grows with M so the per-tick cost is identical and T(M) is affine
    m1, m2 = 2, 8
    rng = np.random.default_rng(0)

    def data_for(m):
        b = m * batch_mb
        x = rng.normal(size=(b, feature_dim)).astype(np.float32)
        y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, b)]
        return x, y

    x1, y1 = data_for(m1)
    x2, y2 = data_for(m2)
    cm = get_compile_manager()

    def timed_fit(fit, n, repeats=3):
        """Min-of-repeats per-step seconds (CPU timing noise guard)."""
        best = None
        for _ in range(repeats):
            t0 = time.perf_counter()
            losses = fit(n)
            dt = time.perf_counter() - t0
            assert np.all(np.isfinite(np.asarray(losses))), \
                "non-finite pipeline bench losses"
            best = dt if best is None else min(best, dt)
        return best / n

    # ---- piped: pipe x dp mesh, two micro-batch counts -----------------
    layout = MeshLayout(data=dp, pipe=pipe)
    runs = {}
    for m, x, y in ((m1, x1, y1), (m2, x2, y2)):
        tr = PipelinedTrainer(make_net(), layout, microbatches=m)
        tr.warm_up(x, y)
        compiles_before = cm.compiles.value
        sec = timed_fit(lambda n: tr.fit(x, y, steps=n), steps)
        warm = cm.compiles.value - compiles_before
        assert warm == 0, (
            f"pipelined fit admitted {warm} compiles after warm_up; the "
            "1F1B step must reuse its one AOT executable")
        runs[m] = {"trainer": tr, "sec_per_step": sec,
                   "samples_per_sec": round(m * batch_mb / sec, 1),
                   "warm_compiles": int(warm)}

    # affine fit T(M) = a*M + b: the intercept is the bubble's time share
    t1, t2 = runs[m1]["sec_per_step"], runs[m2]["sec_per_step"]
    a = (t2 - t1) / (m2 - m1)
    measured_bubble = max((t1 - m1 * a) / t1, 0.0)
    rl = runs[m1]["trainer"].roofline(x1, y1)["roofline"]
    predicted_bubble = rl["bubble_fraction"]
    bubble_ratio = (measured_bubble / predicted_bubble
                    if predicted_bubble else None)
    bubble_ok = (bubble_ratio is not None
                 and 1 / 1.5 <= bubble_ratio <= 1.5)
    # the acceptance bound that keeps apply_roofline's pipeline branch
    # honest — per-tick work (micro-batch size) must dominate the
    # M-independent optimizer/grad-reduce tail for the intercept to BE the
    # bubble, which the default shape guarantees
    assert bubble_ok, (
        f"measured bubble {measured_bubble:.4f} vs roofline prediction "
        f"{predicted_bubble:.4f} (ratio {bubble_ratio}) outside 1.5x")

    # ---- unpiped reference: the whole mesh as data parallelism ---------
    net_ref = make_net()
    wrapper = ParallelWrapper(net_ref, layout=MeshLayout(data=n_dev))
    vx, vy = x2[None], y2[None]
    wrapper.fit_on_device(vx, vy, steps=steps)  # warmup: pays compiles
    unpiped_sec = timed_fit(
        lambda n: wrapper.fit_on_device(vx, vy, steps=n), steps)
    unpiped_sps = round(m2 * batch_mb / unpiped_sec, 1)

    piped_sps = runs[m2]["samples_per_sec"]
    result = {
        "metric": "pipeline_train_samples_per_sec",
        "value": piped_sps,
        "unit": "samples/sec",
        "unpiped_samples_per_sec": unpiped_sps,
        "piped_vs_unpiped": round(piped_sps / unpiped_sps, 4)
        if unpiped_sps else None,
        "bubble": {
            "measured": round(measured_bubble, 4),
            "predicted": round(predicted_bubble, 4),
            "ratio": round(bubble_ratio, 4) if bubble_ratio else None,
            "within_1p5x": bool(bubble_ok),
            "sec_per_step": {str(m1): round(t1, 5), str(m2): round(t2, 5)},
        },
        "runs": {str(m): {k: v for k, v in r.items() if k != "trainer"}
                 for m, r in runs.items()},
        "plan": runs[m2]["trainer"].plan.describe(),
        "layout": layout.describe(),
        "shape": {"batch_mb": batch_mb, "hidden": hidden, "depth": depth,
                  "steps": steps, "pipe": pipe, "dp": dp, "devices": n_dev},
    }
    result["telemetry"] = _telemetry_block(
        [runs[m2]["sec_per_step"]],
        extra_gauges={
            "bench_samples_per_sec": result["value"],
            "bench_pipeline_bubble_measured": result["bubble"]["measured"],
        })
    result["telemetry"]["compile"] = cm.stats()
    result["kernels"] = _kernels_block()
    return result


def _load_baselines() -> dict:
    """Parse BENCH_SELF.json defensively: any malformed content reads as {}."""
    try:
        with open(SELF_BASELINE_PATH) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _with_self_baseline(result: dict) -> dict:
    """vs_baseline = value / first-ever recorded value for this metric.
    Also maintains a "_latest" map (most recent value per metric)."""
    baselines = _load_baselines()
    base = baselines.get(result["metric"])
    if not isinstance(base, (int, float)) or not base:
        # absent OR corrupted (non-numeric/zero): this run becomes the anchor
        baselines[result["metric"]] = result["value"]
        base = result["value"]
    latest = baselines.get("_latest")
    if not isinstance(latest, dict):
        latest = {}
        baselines["_latest"] = latest
    latest[result["metric"]] = result["value"]
    try:
        # atomic replace: a run killed mid-write must not leave a truncated
        # stats file that wipes every baseline on the next read
        tmp = SELF_BASELINE_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(baselines, f)
        os.replace(tmp, SELF_BASELINE_PATH)
    except OSError:
        pass
    result["vs_baseline"] = round(result["value"] / base, 3) if base else 1.0
    # Regression flag: a >10% drop vs the metric's own anchor is surfaced
    # loudly in the artifact rather than silently recorded — the round-4
    # CPU-fallback line shipped at vs_baseline 0.728 and nobody noticed.
    if result["vs_baseline"] < 0.9:
        result["regression"] = (
            f"value {result['value']} is {round(100 * (1 - result['vs_baseline']), 1)}% "
            f"below this metric's anchor {base}; investigate or re-anchor"
        )
    return result


def _force_cpu() -> None:
    from __graft_entry__ import _force_cpu_mesh

    # shard/pipeline modes measure multi-device layout placement and need a
    # virtual 4-device mesh; every other mode stays single-device
    _force_cpu_mesh(4 if os.environ.get("BENCH_MODEL") in ("shard", "pipeline")
                    else 1)


def _ienv(name: str, default: int) -> int:
    raw = os.environ.get(name)
    return default if raw is None else int(raw)


def _bench_resnet50_env() -> dict:
    """``BENCH_BATCH`` (default 128), or ``BENCH_SWEEP="64,128,256"``: run
    each batch and report the best (per-batch img/s in "sweep"); one OOM
    batch must not void the batches that DID measure, so its error rides
    "sweep_errors"."""
    sizes = [int(x) for x in os.environ.get("BENCH_SWEEP", "").split(",")
             if x.strip()]
    if not sizes:
        return bench_resnet50(batch=_ienv("BENCH_BATCH", 128))
    results, errors = [], {}
    for bs in sizes:
        try:
            r = bench_resnet50(batch=bs)
        except Exception as e:  # noqa: BLE001 - recorded per batch below
            errors[str(bs)] = f"{type(e).__name__}: {e}"[:300]
            continue
        r["batch"] = bs
        results.append(r)
    if not results:
        raise RuntimeError(f"every sweep batch failed: {errors}")
    result = max(results, key=lambda r: r["value"])
    result["sweep"] = {str(r["batch"]): r["value"] for r in results}
    if errors:
        result["sweep_errors"] = errors
    return result


def _bench_char_rnn_env() -> dict:
    cfg = {"batch": _ienv("BENCH_BATCH", 64), "seq": _ienv("BENCH_SEQ", 256),
           "steps": _ienv("BENCH_STEPS", 30)}
    result = bench_char_rnn(**cfg)
    result["config"] = cfg
    if cfg != {"batch": 64, "seq": 256, "steps": 30}:
        # non-default shapes get their own metric key so the baseline store
        # never compares different problem sizes
        result["metric"] += f"_b{cfg['batch']}xs{cfg['seq']}xn{cfg['steps']}"
    return result


def _bench_attention_env() -> dict:
    result = bench_attention(seq=_ienv("BENCH_SEQ", 4096))
    if result["shape"]["seq"] != 4096:
        result["metric"] += f"_s{result['shape']['seq']}"
    return result


# BENCH_MODEL -> runner. HOST_MODES measure the host-side stack (serving,
# ingest, layout machinery on a virtual mesh, router, sampler, tuned/default
# ratio), so they alone may run under the explicit BENCH_FORCE_CPU=1.
MODES = {
    "resnet50": _bench_resnet50_env,
    "charrnn": _bench_char_rnn_env,
    "word2vec": bench_word2vec,
    "attention": _bench_attention_env,
    "ragged": lambda: bench_ragged(batch=_ienv("BENCH_BATCH", 512),
                                   stage=_ienv("BENCH_STAGE", 4)),
    "serve": lambda: bench_serve(max_rows=_ienv("BENCH_SERVE_ROWS", 8),
                                 max_batch=_ienv("BENCH_SERVE_BATCH", 64)),
    "online": lambda: bench_online(batch=_ienv("BENCH_BATCH", 32),
                                   stage=_ienv("BENCH_STAGE", 4),
                                   records=_ienv("BENCH_RECORDS", 6144)),
    "shard": lambda: bench_shard(batch=_ienv("BENCH_BATCH", 256),
                                 steps=_ienv("BENCH_STEPS", 12)),
    "pipeline": lambda: bench_pipeline(steps=_ienv("BENCH_STEPS", 8)),
    "fleet": lambda: bench_fleet(clients=_ienv("BENCH_CLIENTS", 8)),
    "history": lambda: bench_history(clients=_ienv("BENCH_CLIENTS", 4)),
    "autotune": bench_autotune,
    "mlp": bench_mlp_mnist,
}
HOST_MODES = ("serve", "online", "shard", "pipeline", "fleet", "history",
              "autotune", "mlp")


def main() -> int:
    force_cpu = bool(os.environ.get("BENCH_FORCE_CPU"))
    mode = os.environ.get("BENCH_MODEL") or ("mlp" if force_cpu
                                             else "resnet50")
    if mode not in MODES:
        print(f"bench: unknown BENCH_MODEL {mode!r}; known: "
              f"{', '.join(sorted(MODES))}", file=sys.stderr)
        return 2
    if force_cpu:
        if mode not in HOST_MODES:
            print(f"bench: mode {mode!r} measures the device and does not "
                  f"run under BENCH_FORCE_CPU; host-side modes: "
                  f"{', '.join(HOST_MODES)}", file=sys.stderr)
            return 2
        _force_cpu()
    import jax

    from deeplearning4j_tpu.runtime.compile_manager import (
        resolve_persistent_cache)

    dev = jax.devices()[0]
    if not force_cpu and dev.platform != "tpu":
        print(f"bench: no TPU found (platform is {dev.platform!r}); no metric "
              "is printed from another device. BENCH_FORCE_CPU=1 requests "
              "the host-side modes explicitly.", file=sys.stderr)
        return 4
    resolve_persistent_cache()
    result = MODES[mode]()
    result.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=len(jax.devices()))
    print(json.dumps(_with_self_baseline(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
