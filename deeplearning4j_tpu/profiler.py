"""Profiling: jax.profiler traces, step-time breakdown, MFU estimation.

The reference has three narrow measurement mechanisms (SURVEY.md §5.1):
PerformanceListener samples/sec (optimize/listeners/PerformanceListener.java),
Spark per-phase timing events (spark/stats/StatsUtils.java), and the
StatsListener memory sections. This module is their TPU-native superset and
the single instrumentation path shared by ``bench.py``, the training-master
phase stats, and the UI system page (VERDICT round-2 task 7):

- :func:`trace` — capture a ``jax.profiler`` trace (TensorBoard/xplane) around
  any block; the deep-dive tool the reference never had.
- :class:`StepTimer` — named-phase wall-clock accounting (data / step /
  host-sync), the analog of ``ParameterAveragingTrainingMasterStats``'s
  per-phase event records, usable standalone or via :class:`ProfilingListener`.
- :func:`compiled_flops` / :func:`mfu` — model FLOPs from XLA's own cost
  analysis and the resulting MXU utilisation, so "TPU-first" is a measured
  number rather than a slogan.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List, Optional

from .optimize.listeners import TrainingListener
from .telemetry.spans import span

# env override of the attached chip's peak bf16 TFLOP/s for MFU math; unset,
# the peak comes from the one table (analysis.cost_model.DEVICE_PEAKS)
PEAK_BF16_TFLOPS_ENV = "DL4J_TPU_PEAK_BF16_TFLOPS"


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Capture a jax.profiler trace into ``logdir`` (view with TensorBoard).

    Usage::

        with profiler.trace("/tmp/trace"):
            train_step(...)
            jax.block_until_ready(params)

    Always block on the traced computation inside the context: XLA dispatch is
    async and an un-synced trace records only the enqueue.
    """
    import jax

    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir, create_perfetto_link=create_perfetto_link):
        yield


class StepTimer:
    """Named-phase wall-clock accounting for a training loop.

    Phases are arbitrary strings; the conventional trio mirrors what the
    reference's Spark stats tracked per worker (fit time, data-loading time,
    sync time — ParameterAveragingTrainingWorkerStats):

    - ``"data"``   host-side batch fetch/convert
    - ``"step"``   jitted train-step dispatch (async under jit)
    - ``"sync"``   block_until_ready / device barrier

    ``with timer.phase("data"): ...`` or ``timer.tick("data")`` /
    ``timer.tock()`` for loop-structured code.

    ``component``: ``phase()`` is also the span ``dl4j.<component>.<name>``,
    so every phase's seconds and count are scrapeable at ``/metrics`` under
    ``dl4jtpu_span_seconds{name="dl4j.<component>.<name>"}`` alongside the
    breakdown() dict the UI/bench already consume.
    """

    def __init__(self, component: str = "") -> None:
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._open: Optional[tuple] = None
        self._component = component

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase; it is also the span ``dl4j.<component>.<name>``
        (telemetry.spans), so it lies on the device trace's clock whenever
        a profiler capture is running."""
        parts = ("dl4j", self._component, name)
        with span(".".join(p for p in parts if p)):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def tick(self, name: str) -> None:
        self.tock()
        self._open = (name, time.perf_counter())

    def tock(self) -> None:
        if self._open is not None:
            name, t0 = self._open
            self.add(name, time.perf_counter() - t0)
            self._open = None

    def add(self, name: str, seconds: float) -> None:
        self.totals[name] = self.totals.get(name, 0.0) + seconds
        self.counts[name] = self.counts.get(name, 0) + 1

    def breakdown(self) -> Dict[str, dict]:
        """{phase: {total_s, count, mean_ms}} — JSON-ready."""
        out = {}
        for name, total in self.totals.items():
            n = self.counts.get(name, 1)
            out[name] = {
                "total_s": round(total, 4),
                "count": n,
                "mean_ms": round(1000.0 * total / n, 3),
            }
        return out

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()
        self._open = None


def compiled_flops(jitted_fn, *args, **kwargs) -> Optional[float]:
    """FLOPs per call of a jitted function, from XLA's own cost analysis.

    Returns None when the backend doesn't expose cost analysis. Lowering does
    not execute the computation, so donated-buffer signatures are safe.
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
        analysis = compiled.cost_analysis()  # a dict, or None if unsupported
        flops = (analysis or {}).get("flops")
        return float(flops) if flops else None
    except Exception:
        return None


def mfu(flops_per_step: float, step_time_s: float,
        peak_tflops: Optional[float] = None) -> float:
    """Model FLOPs utilisation in percent of the chip's peak: the
    ``peak_tflops`` argument, else ``DL4J_TPU_PEAK_BF16_TFLOPS``, else the
    peaks-table row of the attached device (an unknown TPU kind raises)."""
    if peak_tflops is None:
        raw = os.environ.get(PEAK_BF16_TFLOPS_ENV)
        if raw:
            peak_tflops = float(raw)
        else:
            from .analysis.cost_model import device_peaks  # noqa: PLC0415

            peak_tflops = device_peaks()["peak_flops"] / 1e12
    if step_time_s <= 0 or peak_tflops <= 0:
        return 0.0
    return 100.0 * (flops_per_step / step_time_s) / (peak_tflops * 1e12)


class ProfilingListener(TrainingListener):
    """Capture a jax.profiler trace for iterations [start, start+duration).

    Attach like any listener; the trace starts when ``iteration_done`` first
    sees ``iteration >= start`` and stops ``duration`` iterations later. The
    reference's closest analog was restarting training under an external
    profiler; here capture is scoped to steady-state steps (skipping compile).
    """

    def __init__(self, logdir: str, start: int = 3, duration: int = 5):
        self.logdir = logdir
        self.start = start
        self.duration = max(1, duration)
        self._active = False
        self._stop_at = None

    def iteration_done(self, model, iteration, score):
        import jax

        if not self._active and self._stop_at is None and iteration >= self.start:
            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._stop_at = iteration + self.duration
        elif self._active and iteration >= self._stop_at:
            jax.block_until_ready(score)
            self.stop()

    def stop(self) -> None:
        """Finalize an in-flight trace; safe to call repeatedly."""
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False

    def on_epoch_end(self, model, epoch: int) -> None:
        # Training may end before start+duration iterations — an unfinalized
        # trace is unreadable and blocks any later start_trace in-process.
        self.stop()

    def __del__(self):  # pragma: no cover - last resort
        try:
            self.stop()
        except Exception:
            pass


def device_memory_stats() -> List[dict]:
    """PJRT per-device memory stats. Compatibility wrapper: the single
    implementation now lives in :mod:`telemetry.memory` (where it also
    feeds the registry gauges and the flight recorder's watermark trail);
    :class:`SystemInfoSampler` and the UI StatsListener read through here
    unchanged."""
    from .telemetry.memory import device_memory_stats as _impl

    return _impl()


class SystemInfoSampler:
    """Host memory / device memory snapshots for the UI system page.

    Reference: BaseStatsListener's memory/GC sections (SURVEY.md §5.5). JVM GC
    has no analog; device-memory stats come from PJRT when available.
    """

    @staticmethod
    def sample() -> dict:
        info: dict = {"timestamp": time.time()}
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        info["host_rss_mb"] = round(int(line.split()[1]) / 1024.0, 1)
                    elif line.startswith("VmHWM:"):
                        info["host_peak_rss_mb"] = round(int(line.split()[1]) / 1024.0, 1)
        except OSError:
            pass
        try:
            import jax

            devs = jax.devices()
            info["device_count"] = len(devs)
            info["device_platform"] = devs[0].platform if devs else "none"
            stats = device_memory_stats()
            if stats:
                info["device_memory"] = stats
        except Exception:
            pass
        return info
