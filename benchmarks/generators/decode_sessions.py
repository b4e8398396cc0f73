"""The general generator of streaming-decode traffic: closed-loop clients
that each open a session on ``InferenceService``'s decoder, stream tokens
greedily (the next input is the one-hot of the argmax of the last output),
close it and open the next.

Parameters (the mix's data file, overridden by the cell's):

- ``clients``: closed-loop client threads, zero think time;
- ``session_tokens``: ``{"median", "sigma", "min", "max", "strata"}`` of the
  lognormal session length. Every seed offers the same ``strata`` lengths
  (the distribution's quantiles at (k + 0.5) / strata, clipped) in a
  low-discrepancy order: see ``session_lengths``;
- ``replay_sessions`` / ``replay_tokens``: how many recorded sessions, and how
  many of their first tokens, are replayed alone after the window;
- ``replay_clone_atol``: tolerance of the replay against a ``clone()`` driven
  through ``rnn_time_step`` (the same arithmetic: what it catches is a slot
  that held another session's state);
- ``replay_reference_atol``: tolerance against the configuration's plain
  reference (float32: what it catches is wrong or coarser arithmetic);
- ``step_timeout_s``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

MODEL = "bench"


class _Client:
    """One closed-loop client's plan (from the seed) and what it recorded."""

    def __init__(self, index: int, seed: int, lengths: np.ndarray, vocab: int):
        self.lengths = lengths
        self.first_tokens = np.random.default_rng([seed, index]).integers(
            0, vocab, len(lengths))
        self.latencies: list[float] = []
        self.open_seconds: list[float] = []
        self.between_seconds = 0.0   # result in hand -> next step sent
        self.sent = 0
        self.failed = 0
        self.refused = 0
        self.recorded: list[tuple[list, list]] = []  # (tokens, outputs)
        self.first_send = self.last_recv = None


def _serve_loop(ctx, st: dict, client: _Client, deadline: float) -> None:
    dec, eye, p = st["decoder"], st["eye"], ctx.params
    keep, keep_tokens = int(p["replay_sessions"]), int(p["replay_tokens"])
    timeout, traced = float(p["step_timeout_s"]), ctx.trace
    now = time.perf_counter
    k = 0
    while now() < deadline:
        i = k % len(client.lengths)  # a window never gets this far
        length, tok = int(client.lengths[i]), int(client.first_tokens[i])
        k += 1
        t0 = now()
        try:
            with ctx.spans.span("session_open"):
                sid = dec.open()
        except RuntimeError:
            client.refused += 1
            continue
        have = now()
        client.open_seconds.append(have - t0)
        record = ([], []) if len(client.recorded) < keep else None
        if record is not None:
            client.recorded.append(record)
        for j in range(length):
            sent = now()
            if sent >= deadline:
                break
            client.between_seconds += sent - have
            if client.first_send is None:
                client.first_send = sent
            client.sent += 1
            try:
                if traced:
                    with ctx.spans.span("client_step"):
                        out = dec.step(sid, eye[tok], timeout_s=timeout)
                else:
                    out = dec.step(sid, eye[tok], timeout_s=timeout)
            except Exception as e:  # noqa: BLE001 - a failed step is counted
                client.failed += 1
                ctx.log(f"client step failed: {type(e).__name__}: {e}")
                break
            have = now()
            client.latencies.append(have - sent)
            client.last_recv = have
            if record is not None and j < keep_tokens:
                record[0].append(tok)
                record[1].append(np.array(out, np.float32))
            tok = int(np.argmax(out))
        dec.close(sid)


def session_lengths(spec: dict, clients: int, seed: int) -> list:
    """Per client its session lengths, in the order it plays them.

    The lengths are the lognormal's ``strata`` quantiles, clipped. Client
    ``c`` holds every ``clients``-th of them, so each client's share spans
    the distribution; it plays them in van der Corput (bit-reversed) order,
    so that any dozen consecutive sessions are spread over short and long.
    The seed decides which share a client holds and where in its cycle it
    starts. A window thus sees nearly the same mix, and nearly the same
    number of opens, whatever the seed: the run-to-run spread of
    ``serve_ops_per_s`` is the server's, not the draw's."""
    from statistics import NormalDist

    n = int(spec["strata"])
    z = np.array([NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)])
    lengths = np.clip(np.rint(np.exp(np.log(spec["median"])
                                     + spec["sigma"] * z)),
                      spec["min"], spec["max"]).astype(int)
    per = n // clients
    bits = max(1, (per - 1).bit_length())
    order = sorted(range(per),
                   key=lambda k: int(format(k, f"0{bits}b")[::-1], 2))
    rng = np.random.default_rng([seed, 1234])
    share = rng.permutation(clients)
    return [np.roll(lengths[share[c]::clients][:per][order],
                    -int(rng.integers(per))) for c in range(clients)]


def _run_clients(ctx, st: dict, seconds: float, seed: int) -> list:
    vocab = int(ctx.sizes["vocab_size"])
    n = int(ctx.params["clients"])
    clients = [_Client(i, seed, lengths, vocab) for i, lengths in enumerate(
        session_lengths(ctx.params["session_tokens"], n, seed))]
    deadline = time.perf_counter() + seconds
    threads = [threading.Thread(target=_serve_loop,
                                args=(ctx, st, c, deadline), daemon=True)
               for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return clients


def _service_counters(st: dict) -> dict:
    m = st["service"].stats()["models"][MODEL]
    return {k: m[k] for k in ("rows_total", "batches_total", "requests_total",
                              "mean_batch_fill_ratio")} | {
        "shed_total": m["admission"]["shed_total"]}


def setup(ctx) -> dict:
    from deeplearning4j_tpu.serving import InferenceService

    cfg = ctx.cell.config_module()
    p, sizes = ctx.params, ctx.sizes
    t0 = time.perf_counter()
    net = cfg.build(sizes, ctx.seed)
    st = {"cfg": cfg, "net": net,
          "eye": np.eye(int(sizes["vocab_size"]), dtype=np.float32)}
    st["service"] = InferenceService()
    st["service"].register(MODEL, net)
    st["decoder"] = st["service"].decoder(MODEL)
    ctx.log(f"net, service and decoder ({st['decoder'].capacity} slots) in "
            f"{time.perf_counter() - t0:.2f}s")
    # warm-up: the slot-batch x 1-step program and the open/reset path, with
    # the real client loop on another seed, for a moment
    t0 = time.perf_counter()
    warm = _run_clients(ctx, st, float(p.get("warmup_seconds", 0.5)),
                        ctx.seed + 1_000_003)
    ctx.log(f"warm-up: {sum(c.sent for c in warm)} steps in "
            f"{time.perf_counter() - t0:.2f}s")
    st["counters_before"] = _service_counters(st)
    return st


def run(ctx, st: dict, seconds: float) -> dict:
    """Latency is client side, from the step being sent to the result in the
    client's hands (closed loop: a step is due when it is sent). Operations
    per second are completed steps over the time from the first step sent to
    the last result received."""
    from benchmarks.harness.stats import percentile, samples_beyond

    clients = _run_clients(ctx, st, seconds, ctx.seed)
    after = _service_counters(st)
    lat = np.concatenate([np.asarray(c.latencies) for c in clients])
    opens = np.concatenate([np.asarray(c.open_seconds) for c in clients])
    span = max(c.last_recv for c in clients if c.last_recv) \
        - min(c.first_send for c in clients if c.first_send)
    sent = sum(c.sent for c in clients)
    failed = sum(c.failed + c.refused for c in clients)
    late_share = sum(c.between_seconds for c in clients) / (len(clients) * span)
    ctx.log(f"{len(lat)} latency samples ({samples_beyond(len(lat), 95.0)} "
            f"beyond p95, {samples_beyond(len(lat), 99.0)} beyond p99), "
            f"{len(opens)} session opens, {sent} steps sent in {span:.3f}s")
    ctx.log(f"late generator: clients spent {100 * late_share:.2f}% of the "
            "window between a result arriving and the next step being sent")
    return {
        "end_to_end": {
            "serve_ops_per_s": len(lat) / span,
            "serve_p50_ms": 1e3 * percentile(lat, 50.0),
            "serve_p95_ms": 1e3 * percentile(lat, 95.0),
        },
        "attempted": sent,
        "failed": failed,
        "elapsed_s": span,
        "latencies_s": lat,
        "open_seconds": opens,
        "client_late_share": late_share,
        "recorded": [r for c in clients for r in c.recorded],
        "program": {"service": {k: after[k] - st["counters_before"][k]
                                for k in ("rows_total", "batches_total",
                                          "requests_total", "shed_total")}},
    }


def verify(ctx, st: dict, result: dict) -> dict:
    """A seeded sample of the recorded sessions, replayed alone: on a
    ``clone()`` through ``rnn_time_step`` at the decoder's own slot-batch
    shape (no new program), and through the plain reference in one call of
    one shape (``replay_sessions`` x ``replay_tokens``, shorter sessions
    padded: the model is causal, so what follows a session's end cannot
    reach its outputs)."""
    p = ctx.params
    keep, keep_tokens = int(p["replay_sessions"]), int(p["replay_tokens"])
    rng = np.random.default_rng([ctx.seed, 77])
    recorded = [r for r in result["recorded"] if r[0]]
    pick = [recorded[i] for i in rng.permutation(len(recorded))[:keep]]
    # each number compared beside its limit: held as value <= limit
    compared = {
        "steps_unanswered": (result["failed"], 0),
        "sessions_wanted_for_replay": (0 if pick else 1, 0),
        "requests_shed": (result["program"]["service"]["shed_total"], 0)}
    if not pick:
        return compared
    cap, eye = st["decoder"].capacity, st["eye"]
    solo = st["net"].clone()
    solo.rnn_clear_previous_state()
    steps = max(len(t) for t, _ in pick)
    worst_clone = worst_ref = 0.0
    for j in range(steps):
        x = np.zeros((cap, 1, eye.shape[0]), np.float32)
        mask = np.zeros((cap, 1), np.float32)
        for row, (toks, _) in enumerate(pick):
            if j < len(toks):
                x[row, 0], mask[row, 0] = eye[toks[j]], 1.0
        out = np.asarray(solo.rnn_time_step(x, features_mask=mask), np.float32)
        out = out[:, 0] if out.ndim == 3 else out
        for row, (toks, outs) in enumerate(pick):
            if j < len(toks):
                worst_clone = max(worst_clone,
                                  float(np.max(np.abs(out[row] - outs[j]))))
    x = np.zeros((keep, keep_tokens, eye.shape[0]), np.float32)
    for row, (toks, _) in enumerate(pick):
        x[row, :len(toks)] = eye[np.asarray(toks)]
    ref = np.asarray(st["cfg"].reference_probs(st["net"].params, x), np.float32)
    for row, (toks, outs) in enumerate(pick):
        worst_ref = max(worst_ref, float(np.max(np.abs(
            ref[row, :len(toks)] - np.stack(outs)))))
    clone_atol = float(p["replay_clone_atol"])
    ref_atol = float(p["replay_reference_atol"])
    ctx.log(f"replay of {len(pick)} sessions x <= {steps} tokens: max |diff| "
            f"{worst_clone:.3e} vs clone().rnn_time_step (atol {clone_atol}), "
            f"{worst_ref:.3e} vs the plain reference (atol {ref_atol})")
    compared["replay_off_clone"] = (worst_clone, clone_atol)
    compared["replay_off_plain_reference"] = (worst_ref, ref_atol)
    return compared


def close(ctx, st: dict) -> None:
    if "service" in st:
        st["service"].stop()
    st.clear()
