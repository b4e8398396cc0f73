"""What the run stands on: the compile cache, the TPU gate, the compile
monitors, the device as JAX reports it, and the peaks row it is divided by.

``Monitors`` and ``device_info`` are copied from ``chip_smoke.py`` (PR 22),
where they were proven on the chip; the benchmark keeps its own copy so that
no later PR to the program can move the yardstick.
"""

from __future__ import annotations

import json
import os
import sys

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
NO_TPU_EXIT = 4  # not 2/3: the chip tool uses those itself


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` when set, else
    at the fixed ``<checkout>/.jax_cache`` (the path is part of the cache's
    key). The program's own resolver picks the same directory; the variable is
    set here so that it has to. The floors under which jax does not cache a
    program are lowered in this process only, so the decode step (compiles in
    well under a second) is served from the cache on the second run too."""
    cache_dir = os.environ.setdefault(
        CACHE_DIR_ENV, os.path.join(repo_root(), ".jax_cache"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class Monitors:
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
                "compile_seconds": self.compile_seconds,
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": self.cache_misses}


_MONITORS = None


def monitors() -> Monitors:
    global _MONITORS
    if _MONITORS is None:
        _MONITORS = Monitors()
    return _MONITORS


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or exit non-zero with no result line:
    a number from a CPU run is never a device metric."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform!r}; nothing is measured "
              "on another platform", file=sys.stderr)
        raise SystemExit(NO_TPU_EXIT)
    return devices[:chips]


def memory_held_bytes(devices) -> int:
    """Bytes held right now on the fullest chip: ``bytes_in_use`` (live
    arrays: weights, staged data, state) plus ``bytes_reserved`` (what the
    v5e's runtime sets aside for the loaded programs' own buffers:
    ResNet-50's step at batch 128 has 4.44 GB there and 0.35 GB in use, and
    the compiler's analysis of that program says 4.85 GB; PR 23). Both are
    read at one moment, so the sum is what the chip holds then. The lifetime
    ``peak_*`` counters are not used: they include set-up transients (a data
    generator's temporaries) and fell at different moments."""
    def held(d):
        ms = d.memory_stats() or {}
        return ms.get("bytes_in_use", 0) + ms.get("bytes_reserved", 0)

    return int(max(held(d) for d in devices))


def peaks_row(device_kind: str) -> dict:
    """The published peaks of ``device_kind``. A device that is not in the
    table is an error, not a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks row for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def share_of_peak(flops_per_sample: float, samples_per_s_per_chip: float,
                  peaks: dict) -> float:
    """Per cent of one chip's bf16 peak that ``samples_per_s_per_chip`` is at
    ``flops_per_sample`` model FLOPs a sample: what the per-layer metric
    ``train_step_mfu`` reports and what a training generator logs in every
    run, so that the two cannot drift."""
    return 100.0 * flops_per_sample * samples_per_s_per_chip \
        / peaks["bf16_flops_per_s"]
