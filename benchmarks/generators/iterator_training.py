"""The general generator of host-fed training traffic: ``net.fit(iterator)``
as a user calls it, over pre-decoded batches held in host memory.

Set-up builds the configuration's net, takes ``host_pool_batches`` batches
from the configuration's own ``make_batches`` and brings them to the host
once, computes the plain reference's loss on batch 0, and warms up with
``net.fit(<the first unit of the pool>, stage_on_device=S)`` twice: the first
pass compiles and its losses are the net's first, the second is timed and
gives one step's duration. A unit is one staged window (``stage_on_device``
batches) or, per batch, ``warmup_batches``.

The measured window is ONE ``net.fit(it, epochs=1, stage_on_device=S)`` over
``CyclingPool``: a ``DataSetIterator`` of the benchmark's own that hands out
the pool's batches by reference (no copy), round and round, and ends itself.
It says ``prefetch_supported``, so ``fit`` wraps it in its
``AsyncDataSetIterator`` (queue 8) as it does any user's iterator, and the
pool is walked on that producer thread. It ends once the time left is under
what is already handed out and not yet trained plus one more window (staged)
or batch (per batch), at the warm-up's step duration. A staged run hands out
whole windows only (a ragged tail would be a new shape, and a compile inside
the window), and at least one window or batch is handed out. After ``fit``
returns, the last loss is fetched, which is the synchronisation.

Closed loop, one client: the training thread pulls as fast as it trains.

Parameters (the mix's data file, overridden by the cell's):
``batch_per_chip``, ``host_pool_batches``, ``stage_on_device`` (0 or 1: per
batch; stated, so that no ``TUNED.json`` decides), ``warmup_batches``, and the
tolerances ``staged_training.verify`` reads (``first_loss_rtol``,
``reference_rtol``).

``verify`` compares what ``staged_training.verify`` compares (through that
function: non-finite losses, the first loss against ln(classes) and against
the plain reference, the mean loss of the last unit under the first's) and
``batches_handed_out_not_trained``: what the iterator handed out less what
``net.iteration`` moved by, which has to be 0 (nothing dropped, the epoch not
ended early).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness.discovery import load_module

# the program's counters at the boundaries of ``fit(iterator)``
# (docs/observability.md); a program without them gives none, and the
# per-layer metrics that read them are left out of the line
COUNTERS = ("dl4jtpu_iterator_gets_total", "dl4jtpu_iterator_produce_seconds",
            "dl4jtpu_iterator_queue_full_seconds",
            "dl4jtpu_fit_host_bytes_total")


class KeepLosses:
    """A listener that keeps each step's loss as ``fit`` hands it over: a
    device scalar on the per-batch path (no ``float()``: that would sync
    every step), a host number on the staged one."""

    supports_staged = True  # reads (iteration, score) alone

    def __init__(self):
        self.losses = []

    def iteration_done(self, model, iteration, score):
        self.losses.append(score)

    def take(self) -> np.ndarray:
        out = np.asarray([float(s) for s in self.losses], np.float64)
        self.losses = []
        return out


class CyclingPool:
    """The pool's batches by reference, round and round; see the module
    docstring for when it ends. ``limit``: end after so many batches instead
    (the warm-up). Walked on ``fit``'s prefetch thread."""

    prefetch_supported = True

    def __init__(self, pool, *, unit, limit=None, seconds=None, step_s=None,
                 trained=None):
        self.pool, self.unit, self.limit = pool, int(unit), limit
        self.seconds, self.step_s, self.trained = seconds, step_s, trained
        self.handed_out = 0
        self.t0 = None  # the caller starts the clock
        self.times = []  # when each batch was handed out

    def reset(self):
        pass

    def batch_size(self):
        return self.pool[0].num_examples()

    def _over(self) -> bool:
        if self.limit is not None:
            return self.handed_out >= self.limit
        if self.handed_out == 0:
            return False
        in_flight = self.handed_out - self.trained()
        left = self.seconds - (time.perf_counter() - self.t0)
        return left < (in_flight + self.unit) * self.step_s

    def __iter__(self):
        while self.handed_out % self.unit or not self._over():
            item = self.pool[self.handed_out % len(self.pool)]
            self.handed_out += 1
            self.times.append(time.perf_counter())
            yield item

    def pace(self, top: int = 3) -> str:
        """The median interval between two hand-outs and the longest ones
        with the batch each ended at. The full queue holds the producer to
        the training thread's pace, so a stall of the run shows here, in an
        untraced run too."""
        gaps = np.diff(np.asarray(self.times[self.unit:]))  # past the ramp
        if not len(gaps):
            return "too few hand-outs for a pace"
        longest = np.argsort(gaps)[::-1][:top]
        return (f"median {1e3 * float(np.median(gaps)):.2f} ms between "
                f"hand-outs; longest " + ", ".join(
                    f"{1e3 * float(gaps[i]):.1f} ms before batch "
                    f"{int(i) + self.unit + 1}" for i in longest))


def program_counts() -> dict:
    """``{"family{labels}": value}`` of ``COUNTERS`` in the program's default
    registry, as far as it has them; a histogram as its ``_sum`` and
    ``_count``."""
    try:
        from deeplearning4j_tpu.telemetry import get_registry
    except ImportError:
        return {}
    snap = get_registry().snapshot()
    out = {}
    for name in COUNTERS:
        for row in snap.get(name, {"values": []})["values"]:
            labels = "{%s}" % ",".join(
                f"{k}={v}" for k, v in sorted(row["labels"].items()))
            if "value" in row:
                out[name + labels] = row["value"]
            else:
                out[name + "_sum" + labels] = row["sum"]
                out[name + "_count" + labels] = row["count"]
    return out


def counts_delta(after: dict, before: dict) -> dict:
    """What moved over the window (a row that first appears in it counts
    from 0)."""
    return {k: v - before.get(k, 0) for k, v in after.items()}


def setup(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.datasets.iterators import DataSet

    cfg = ctx.cell.config_module()
    p, sizes = ctx.params, ctx.sizes
    batch, pool_n = int(p["batch_per_chip"]), int(p["host_pool_batches"])
    stage = int(p["stage_on_device"])
    unit = stage if stage > 1 else int(p["warmup_batches"])
    if pool_n < unit and stage > 1:
        raise ValueError(f"a pool of {pool_n} batches is under one window "
                         f"of {stage}")
    t0 = time.perf_counter()
    net = cfg.build(sizes, ctx.seed)
    xs, ys = cfg.make_batches(sizes, dict(p, slots=pool_n), ctx.seed, batch)
    xs, ys = np.asarray(xs), np.asarray(ys)  # the one copy: device to host
    pool = [DataSet(x, y) for x, y in zip(xs, ys)]
    ctx.log(f"net and a host pool of {pool_n} batches {tuple(xs.shape[1:])} "
            f"({(xs.nbytes + ys.nbytes) / 1e9:.3f} GB) in "
            f"{time.perf_counter() - t0:.2f}s")

    st = {"cfg": cfg, "net": net, "pool": pool, "stage": stage, "unit": unit,
          "samples_per_step": batch * cfg.samples_per_example(sizes, p),
          "wrapped": False, "keep": KeepLosses(),
          "staged": load_module(ctx.cell.path("generators",
                                              "staged_training.py"))}
    t0 = time.perf_counter()
    st["reference_loss"] = cfg.reference_loss(net.params, net.state,
                                              xs[0], ys[0], sizes)
    ctx.log(f"plain reference loss {st['reference_loss']:.5f} in "
            f"{time.perf_counter() - t0:.2f}s")
    net.set_listeners(st["keep"])

    def one_unit() -> tuple:
        t0 = time.perf_counter()
        net.fit(CyclingPool(pool, unit=unit, limit=unit), epochs=1,
                stage_on_device=stage)
        net.score()  # the last loss, fetched: the synchronisation
        return time.perf_counter() - t0, st["keep"].take()

    # warm-up: every shape the window uses; the first pass's losses are the
    # net's first (the first of them is the loss at the seeded weights)
    with ctx.spans.span("warmup"):
        cold_s, st["first_losses"] = one_unit()
        warm_s, second = one_unit()
    st["step_s"] = warm_s / unit
    ctx.log(f"warm-up: {unit} batches through fit(stage_on_device={stage}) "
            f"in {cold_s:.2f}s, again in {warm_s:.3f}s "
            f"({1e3 * st['step_s']:.2f} ms a step); losses "
            f"{st['first_losses'][:3]} .. {second[-1]:.5f}")
    jax.block_until_ready(net.params)
    return st


def run(ctx, st: dict, seconds: float) -> dict:
    """One ``net.fit`` over the cycling pool, then the fetch of the last
    loss. Throughput is the batches trained (``net.iteration``'s delta) times
    the batch over the wall time from the call of ``fit`` to that fetch. The
    kept losses stay where they are (on the device, per batch) until
    ``verify``: fetching some hundred scalars here would be device-idle time
    of the benchmark's own inside the traced window."""
    net, spans = st["net"], ctx.spans
    before = net.iteration
    it = CyclingPool(st["pool"], unit=max(st["stage"], 1), seconds=seconds,
                     step_s=st["step_s"],
                     trained=lambda: net.iteration - before)
    counts = program_counts()
    it.t0 = time.perf_counter()
    with spans.span("fit"):
        net.fit(it, epochs=1, stage_on_device=st["stage"])
    with spans.span("last_loss"):
        net.score()
    elapsed = time.perf_counter() - it.t0
    counts = counts_delta(program_counts(), counts)
    trained = net.iteration - before
    per_chip = trained * st["samples_per_step"] / elapsed / ctx.cell.chips
    ctx.log(f"{it.handed_out} batches handed out, {trained} trained in "
            f"{elapsed:.4f}s; {it.pace()}")
    ctx.log(f"the program's counters over the window: {counts}")
    from benchmarks.harness.gate import share_of_peak

    flops = st["cfg"].model_flops_per_sample(ctx.sizes)
    ctx.log(f"model FLOPs utilisation "
            f"{share_of_peak(flops, per_chip, ctx.peaks):.2f}% "
            f"({flops / 1e6:.2f} MFLOP a sample from the shapes)")
    return {
        "end_to_end": {"train_samples_per_s_per_chip": per_chip},
        "attempted": trained,
        "elapsed_s": elapsed,
        "handed_out": it.handed_out,
        "program": dict(st["staged"].counters(), fit_iterator=counts),
    }


def verify(ctx, st: dict, result: dict) -> dict:
    """Brings the window's losses to the host (and so completes ``result``:
    ``failed``, and ``losses`` with the last unit's last, which is what
    ``staged_training.verify`` reads), then compares."""
    losses = st["keep"].take()
    result["failed"] = int(np.sum(~np.isfinite(losses)))
    result["losses"] = [losses[:-st["unit"]], losses[-st["unit"]:]]
    ctx.log(f"loss {losses[0]:.4f} -> {losses[-1]:.4f} over {len(losses)} "
            f"steps")
    compared = st["staged"].verify(ctx, st, result)
    compared["batches_handed_out_not_trained"] = (
        abs(result["handed_out"] - result["attempted"]), 0)
    return compared


def close(ctx, st: dict) -> None:
    net = st.get("net")
    if net is not None:
        net.set_listeners()
    st.clear()
