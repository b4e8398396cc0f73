"""Recompile-elimination compile manager: one executable per abstract shape.

The staged fit path (``fit_on_device``'s multi-step loop) used to bake the
step count and staged-batch count into the traced program: every distinct
``(steps, num_batches, masks, telemetry)`` tuple silently paid a fresh XLA
compile — seconds of dead device time per shape, and a ragged data stream
produces many shapes. This module is the other half of the fix
(``datasets/bucketing.py`` canonicalizes the *data* shapes):

- **Canonical keys.** Executables are cached by the *abstract* signature of
  their arguments (shape/dtype/pytree structure — ``signature()``), never by
  Python values. Step and batch counts are passed as device ``int32`` scalars
  (the jitted loop is a ``lax.fori_loop`` with a traced trip count), so
  changing ``steps`` or the number of real staged batches reuses ONE
  executable.
- **AOT compile, measured.** Programs go through ``jax.jit(...).lower()
  .compile()`` explicitly, so every compile is a visible, timed event:
  ``dl4jtpu_compiles_total`` and the ``dl4jtpu_compile_seconds`` histogram
  land in the PR 2 telemetry registry next to the step metrics they explain.
- **Bounded.** The cache is an LRU with a hard entry bound and an eviction
  counter (``dl4jtpu_compile_cache_evictions_total``) — a long-running job
  cycling through shapes can no longer leak executables the way the old
  per-net ``_multi_step_cache`` dicts did.
- **Compile-ahead.** ``aot(..., execute=False)`` / the networks' ``warmup``
  methods compile before the first optimizer step, moving compile latency
  out of the training-time critical path.
- **Persistent cache.** ``resolve_persistent_cache()`` (run by
  ``get_compile_manager()``) keeps jax's on-disk compilation cache at
  ``JAX_COMPILATION_CACHE_DIR`` when that is set and at ``<repo>/.jax_cache``
  otherwise, so a process restart pays disk-cache hits, not recompiles.

Host-side only: nothing here touches device buffers; the manager stores the
compiled callables and the telemetry counters that describe them.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Optional, Tuple

from ..telemetry.spans import span

__all__ = [
    "CompileManager",
    "get_compile_manager",
    "resolve_persistent_cache",
    "persistent_cache_dir",
    "signature",
    "next_pow2",
]

# jax's OWN env name for its persistent compilation cache directory: when set,
# jax honours it by itself and this package sets no directory in code
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# where the cache lives when the env var is unset: a FIXED path inside the
# checkout (the path is part of the cache key, so a temp name never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# env knob: "0" disables the DT2xx IR scan + static cost model run at
# admission time (see docs/static_analysis.md)
IR_CHECKS_ENV = "DL4JTPU_IR_CHECKS"

# compile times span ~0.1s (tiny CPU programs) to minutes (ResNet-50 on a
# cold cache) — wider than the step-time default buckets
COMPILE_TIME_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                       60.0, 120.0, 300.0)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1). The step/window bucket function:
    padding loop bounds and staged-window sizes to powers of two keeps the
    set of compiled programs logarithmic in the sizes actually seen."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def _sharding_sig(x: Any):
    """A leaf's mesh placement, iff it is explicitly mesh-sharded. Local
    (single-device / uncommitted / shell) leaves all collapse to None so
    the pre-sharding cache keys are byte-identical — but two programs whose
    arguments live on different meshes (or under different PartitionSpecs)
    must NOT share an executable: an AOT program is compiled FOR its input
    shardings, and serving a replicated-params executable to an
    fsdp-sharded net (or vice versa) would fail at dispatch."""
    sh = getattr(x, "sharding", None)
    if sh is None or type(sh).__name__ != "NamedSharding":
        return None
    mesh = sh.mesh
    if mesh.devices.size <= 1:
        return None
    spec = tuple(sh.spec)
    while spec and spec[-1] is None:
        spec = spec[:-1]  # P(None,) ≡ P(): GSPMD round-trips trim the spec
    return ("mesh", tuple((str(a), int(s)) for a, s in mesh.shape.items()),
            tuple(int(d.id) for d in mesh.devices.flat), str(spec))


def _leaf_sig(x: Any):
    """One leaf's contribution to a canonical key. Arrays reduce to
    (shape, dtype, weak_type, mesh-sharding-or-None) — exactly what decides
    whether an AOT executable can be reused; everything else must be
    hashable."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("arr", tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)), _sharding_sig(x))
    return x


def signature(*parts) -> Tuple:
    """Canonical cache key from arbitrary parts (hashables and/or pytrees of
    arrays — ``jax.ShapeDtypeStruct``s count as arrays, so warmup and live
    calls produce identical keys)."""
    import jax  # noqa: PLC0415 - keep module import light

    flat, treedef = jax.tree_util.tree_flatten(parts)
    return (tuple(_leaf_sig(l) for l in flat), str(treedef))


def resolve_persistent_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it — nothing is set
    here, so a cache placed from outside is never overridden. Unset: the
    cache goes to the fixed ``<repo>/.jax_cache``. Idempotent; every entry
    point reaches it through :func:`get_compile_manager`."""
    import jax  # noqa: PLC0415

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return persistent_cache_dir()


def persistent_cache_dir() -> Optional[str]:
    """The directory jax's persistent compilation cache is ACTIVELY using,
    or None when unset. Warm-boot bundles record it (fleet/artifacts.py) so
    a fresh worker on the same host finds the same compiled programs."""
    import jax  # noqa: PLC0415

    return jax.config.jax_compilation_cache_dir or None


class CompileManager:
    """Process-wide LRU of compiled/jitted programs, telemetry-instrumented.

    Two entry kinds share one LRU:

    - ``aot(key, build, args)``: ``build()`` returns a *jitted* callable; the
      manager ``lower(*args).compile()``s it once per canonical key and
      returns the compiled executable (counted + timed as a compile event).
    - ``callable(key, build)``: ``build()`` returns a callable (typically a
      ``jax.jit`` wrapper whose shapes vary per call, e.g. the per-batch
      train step); the manager only deduplicates and bounds it.

    Keys should start with a per-owner token (``new_token()``) so retiring an
    owner (``drop_token``) evicts its entries eagerly instead of waiting for
    LRU pressure.
    """

    def __init__(self, max_entries: int = 64, registry=None):
        if int(max_entries) < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = int(max_entries)
        self._lock = threading.RLock()
        self._entries: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._memory: "OrderedDict[Tuple, dict]" = OrderedDict()
        self._costs: "OrderedDict[Tuple, dict]" = OrderedDict()
        self._token_counter = 0
        self._admission_errors = 0
        if registry is None:
            from ..telemetry import get_registry  # noqa: PLC0415

            registry = get_registry()
        self.compiles = registry.counter(
            "dl4jtpu_compiles_total",
            "XLA programs compiled through the compile manager")
        self.compile_time = registry.histogram(
            "dl4jtpu_compile_seconds",
            "wall time of manager-issued lower().compile() calls",
            buckets=COMPILE_TIME_BUCKETS)
        self.cache_hits = registry.counter(
            "dl4jtpu_compile_cache_hits_total",
            "executable lookups served from the in-process cache")
        self.evictions = registry.counter(
            "dl4jtpu_compile_cache_evictions_total",
            "executables dropped by the LRU bound or owner retirement")
        self.cache_size = registry.gauge(
            "dl4jtpu_compile_cache_size",
            "executables currently held by the compile manager")
        # static HBM accounting from XLA itself: every admitted AOT
        # executable's memory_analysis() lands here, kind = byte category
        self.hbm_bytes = registry.gauge(
            "dl4jtpu_executable_hbm_bytes",
            "bytes of live cached executables by XLA memory_analysis "
            "category (argument/output/temp/generated_code)",
            labelnames=("kind",))
        self.hbm_total = registry.gauge(
            "dl4jtpu_executable_hbm_total_bytes",
            "cache-wide total HBM footprint of live cached executables")
        from ..analysis.ir_checks import ir_findings_family  # noqa: PLC0415
        self.ir_findings = ir_findings_family(registry)

    # -------------------------------------------------------- observability
    @staticmethod
    def _flight():
        """The process flight recorder; compiles/evictions are rare, so the
        lazy import costs nothing on the hot lookup path."""
        from ..telemetry.flight_recorder import get_flight_recorder  # noqa: PLC0415

        return get_flight_recorder()

    def _admission_failed(self, key, stage: str, exc: BaseException) -> None:
        """An admission-time analysis raised: compilation goes on, but the
        failure is COUNTED (``stats()['admission_errors']``, the
        ``dl4jtpu_ir_findings_total{rule="admission_error"}`` series) and
        rung into the flight recorder with its cause — a broken analysis
        must never again read as "no cost record, no findings"."""
        with self._lock:
            self._admission_errors += 1
        self.ir_findings.labels(rule="admission_error").inc()
        try:
            self._flight().record(
                "admission_error", entry=self._key_kind(key), stage=stage,
                error=f"{type(exc).__name__}: {exc}"[:300])
        except Exception:  # the flight ring is best-effort; the count is not
            pass

    @staticmethod
    def _key_kind(key) -> str:
        """Human label of a cache key: the entry-kind string that follows
        the owner token (e.g. ``mln_multi_step``)."""
        if isinstance(key, tuple):
            for part in key:
                if isinstance(part, str):
                    return part
        return "aot"

    def _refresh_memory_gauges(self) -> None:
        with self._lock:
            records = list(self._memory.values())
        totals = {"argument": 0, "output": 0, "temp": 0, "generated_code": 0}
        grand = 0
        for rec in records:
            if not rec.get("available"):
                continue
            for kind in totals:
                totals[kind] += int(rec.get(f"{kind}_bytes", 0))
            grand += int(rec.get("total_bytes", 0))
        for kind, v in totals.items():
            self.hbm_bytes.labels(kind=kind).set(v)
        self.hbm_total.set(grand)

    def memory_records(self) -> dict:
        """{key label: memory_analysis record} for every live AOT entry."""
        with self._lock:
            return {f"{self._key_kind(k)}#{i}": dict(rec)
                    for i, (k, rec) in enumerate(self._memory.items())}

    def program_texts(self) -> list:
        """The optimized HLO text of every live AOT executable, rendered on
        demand (nothing is kept: the executables are). Each instruction's
        ``metadata={op_name=...}`` carries the ``jax.named_scope`` path of
        its layer, which the device trace's events do not: a trace reader
        joins the two on the instruction's text."""
        with self._lock:
            entries = list(self._entries.values())
        return [e.as_text() for e in entries if hasattr(e, "as_text")]

    def cost_records(self) -> dict:
        """{key label: static_cost report} for every live AOT entry — the
        roofline twin of :meth:`memory_records` (same labeling scheme)."""
        with self._lock:
            return {f"{self._key_kind(k)}#{i}": dict(rec)
                    for i, (k, rec) in enumerate(self._costs.items())}

    def _cost_summary(self) -> dict:
        """Compact static-cost view for ``stats()``: per-entry FLOPs don't
        sum meaningfully across different programs, so expose the count and
        the most recently admitted report's headline numbers."""
        with self._lock:
            records = list(self._costs.values())
        out = {"entries_with_cost": len(records)}
        if records:
            last = records[-1]
            rl = last.get("roofline", {})
            out["last"] = {
                "kind": last.get("kind"),
                "flops": last.get("flops"),
                "hbm_bytes": last.get("hbm_bytes"),
                "arithmetic_intensity": last.get("arithmetic_intensity"),
                "predicted_step_seconds": rl.get("predicted_step_seconds"),
                "bound": rl.get("bound"),
            }
        return out

    def _memory_summary(self) -> dict:
        with self._lock:
            records = list(self._memory.values())
        out = {"measured_entries": 0, "unavailable_entries": 0,
               "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
               "generated_code_bytes": 0, "total_bytes": 0}
        for rec in records:
            if rec.get("available"):
                out["measured_entries"] += 1
                for kind in ("argument", "output", "temp", "generated_code",
                             "total"):
                    out[f"{kind}_bytes"] += int(rec.get(f"{kind}_bytes", 0))
            else:
                out["unavailable_entries"] += 1
        return out

    # ------------------------------------------------------------- tokens
    def new_token(self) -> Tuple[str, int]:
        """Fresh owner token; prefix cache keys with it so ``drop_token``
        can retire every executable built for one network generation."""
        with self._lock:
            self._token_counter += 1
            return ("cm-token", self._token_counter)

    def drop_token(self, token) -> int:
        """Evict every entry whose key starts with ``token``; returns the
        count. Called by the networks on re-init (new optimizer closure =
        stale executables)."""
        if token is None:
            return 0
        with self._lock:
            stale = [k for k in self._entries
                     if isinstance(k, tuple) and k and k[0] == token]
            for k in stale:
                del self._entries[k]
                self._memory.pop(k, None)
                self._costs.pop(k, None)
            if stale:
                self.evictions.inc(len(stale))
            self.cache_size.set(len(self._entries))
        if stale:
            self._refresh_memory_gauges()
            try:
                self._flight().record("eviction", cause="drop_token",
                                      count=len(stale))
            except Exception:  # observability must not break retirement
                pass
        return len(stale)

    # -------------------------------------------------------------- cache
    def _get(self, key):
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.cache_hits.inc()
            return entry

    def _put(self, key, value, memory: Optional[dict] = None,
             cost: Optional[dict] = None):
        evicted = 0
        with self._lock:
            # a racing compile of the same key: keep the first, count ours
            # as the loser (both compiles already happened and were counted)
            existing = self._entries.get(key)
            if existing is not None:
                self._entries.move_to_end(key)
                return existing
            self._entries[key] = value
            if memory is not None:
                self._memory[key] = memory
            if cost is not None:
                self._costs[key] = cost
            while len(self._entries) > self.max_entries:
                old_key, _ = self._entries.popitem(last=False)
                self._memory.pop(old_key, None)
                self._costs.pop(old_key, None)
                self.evictions.inc()
                evicted += 1
            self.cache_size.set(len(self._entries))
        if memory is not None or evicted:
            self._refresh_memory_gauges()
        if evicted:
            try:
                self._flight().record("eviction", cause="lru", count=evicted)
            except Exception:
                pass
        return value

    def _check_arg_shardings(self, key, args) -> None:
        """DT008 at admission (next to the DT2xx IR scan): an executable
        about to be compiled with mesh-sharded in/out structs gets every
        declared NamedSharding checked against the computation's mesh —
        axis membership, duplicate axes, shape divisibility, and
        cross-mesh mixing (stale params from a retired layout next to a
        fresh batch sharding fail lower() with a raw device error; the
        finding names the leaf first). Findings land in
        ``dl4jtpu_ir_findings_total{rule="DT008"}`` + a flight event and
        never block the compile — ``validate_shardings`` used to be
        manual-call-only."""
        import jax  # noqa: PLC0415

        meshes = []
        for leaf in jax.tree_util.tree_leaves(args):
            sh = getattr(leaf, "sharding", None)
            if type(sh).__name__ == "NamedSharding" and sh.mesh.devices.size > 1:
                if not any(sh.mesh is m or sh.mesh == m for m in meshes):
                    meshes.append(sh.mesh)
        if not meshes:
            return
        from jax.sharding import PartitionSpec  # noqa: PLC0415

        from ..analysis.graph_checks import check_partition_specs  # noqa: PLC0415

        def spec_of(leaf):
            sh = getattr(leaf, "sharding", None)
            if type(sh).__name__ == "NamedSharding":
                return sh  # keeps its own mesh: cross-mesh mixing is checked
            return PartitionSpec()  # local leaf: trivially applicable

        shardings = jax.tree_util.tree_map(spec_of, args)
        findings = check_partition_specs(
            shardings, meshes[0], args,
            source=f"<aot:{self._key_kind(key)}>")
        if not findings:
            return
        for f in findings:
            self.ir_findings.labels(rule=f.rule_id).inc()
        try:
            from ..analysis.ir_checks import record_findings  # noqa: PLC0415

            record_findings(findings, registry=False, flight=self._flight())
        except Exception:
            pass

    def _compile_and_admit(self, key, jitted, args):
        """AOT-compile ``jitted`` against ``args`` (counted + timed), take
        its memory record, and run the admission analysis. Returns
        ``(compiled, seconds, memory_record, cost_or_None)``."""
        kind = self._key_kind(key)
        t0 = time.perf_counter()
        with span("dl4j.cm.lower", kind=kind):  # trace + lowering
            lowered = jitted.lower(*args)
        with span("dl4j.cm.compile", kind=kind):  # backend compile or
            compiled = lowered.compile()          # persistent-cache load
        seconds = time.perf_counter() - t0
        self.compile_time.observe(seconds)
        self.compiles.inc()
        with span("dl4j.cm.admission", kind=kind):
            record, cost = self._admit(key, jitted, compiled, args)
        return compiled, seconds, record, cost

    def _admit(self, key, jitted, compiled, args):
        """The memory record and the admission analysis of one freshly
        compiled executable: ``(memory_record, cost_or_None)``."""
        # static HBM accounting from the compiler itself — every admitted
        # executable carries a memory_analysis record (or an explicit
        # "unavailable on this backend" flag), see telemetry/memory.py
        from ..telemetry.memory import executable_memory  # noqa: PLC0415

        record = executable_memory(compiled)
        record["kind"] = self._key_kind(key)
        # DT2xx IR scan + static roofline cost at admission: re-traces the
        # program host-side (dwarfed by the XLA compile it just paid);
        # findings land in dl4jtpu_ir_findings_total{rule} + the flight
        # recorder, the cost report next to the memory record in stats().
        # Programs admitted with mesh-sharded args additionally get the
        # DT3xx sharding-flow pass (predicted collective census + the
        # DL4JTPU_ICI_GBPS communication roofline term) inside the same
        # admission_check call.
        # Disable with DL4JTPU_IR_CHECKS=0; analysis must never break
        # compilation, so a failure degrades to cost=None — and is counted.
        cost = None
        if os.environ.get(IR_CHECKS_ENV, "1") != "0":
            try:
                from ..analysis.ir_checks import (  # noqa: PLC0415
                    admission_check, record_findings)

                findings, cost = admission_check(
                    jitted, compiled, args, kind=self._key_kind(key))
                cost["kind"] = self._key_kind(key)
                for stage, err in cost.get("analysis_errors", {}).items():
                    self._admission_failed(key, stage, RuntimeError(err))
                for f in findings:
                    self.ir_findings.labels(rule=f.rule_id).inc()
                if findings:
                    # counter handled above (the manager may own a private
                    # registry); record_findings only rings the flight ring
                    record_findings(findings, registry=False,
                                    flight=self._flight())
            except Exception as e:
                cost = None
                self._admission_failed(key, "admission_check", e)
        return record, cost

    def aot(self, key: Tuple, build: Callable[[], Any], args) -> Any:
        """Compiled executable for ``key``; on miss, ``build()`` must return
        a jitted callable which is AOT-lowered against ``args`` (concrete
        arrays or ``ShapeDtypeStruct``s) and compiled — the compile is
        counted and timed. The returned executable accepts exactly the
        signature of ``args``."""
        entry = self._get(key)
        if entry is not None:
            return entry
        if os.environ.get(IR_CHECKS_ENV, "1") != "0":
            try:  # analysis must never break compilation
                with span("dl4j.cm.admission", kind=self._key_kind(key)):
                    self._check_arg_shardings(key, args)
            except Exception as e:
                self._admission_failed(key, "arg_shardings", e)
        # kernel-selection hook: variants are resolved by ops.kernel_select
        # DURING the trace below (cost-model-guided, cached per shape key);
        # snapshot the log so selections first made for THIS admission land
        # on its cost record and compile event
        from ..ops import kernel_select as _ks  # noqa: PLC0415

        ks_mark = len(_ks.selection_log())
        # a program whose arguments are mesh-sharded is partitioned by
        # GSPMD, which cannot split a Mosaic kernel: tell the selection, for
        # the trace AND for admission's host-side re-trace of it
        import jax  # noqa: PLC0415

        partitioned = any(_sharding_sig(leaf) is not None
                          for leaf in jax.tree_util.tree_leaves(args))
        with _ks.partitioned_program(partitioned):
            compiled, seconds, record, cost = self._compile_and_admit(
                key, build(), args)
        # selections newly resolved while tracing/admitting this program
        kernels_here = [
            {"site": r["site"], "variant": r["variant"], "reason": r["reason"]}
            for r in _ks.selection_log()[ks_mark:]]
        if kernels_here and cost is not None:
            cost["kernels"] = kernels_here
        try:
            self._flight().record(
                "compile", entry=record["kind"], seconds=round(seconds, 6),
                hbm_total_bytes=record.get("total_bytes"),
                static_flops=(cost or {}).get("flops"),
                predicted_step_seconds=(cost or {}).get(
                    "roofline", {}).get("predicted_step_seconds"),
                # sharding-flow predicted per-step ICI volume (only present
                # when the program was admitted with mesh-sharded args)
                predicted_comm_bytes=(cost or {}).get(
                    "shard_flow", {}).get("comm_bytes_per_step"),
                kernel_selections=len(kernels_here))
        except Exception:
            pass
        return self._put(key, compiled, memory=record, cost=cost)

    def callable(self, key: Tuple, build: Callable[[], Any]) -> Any:
        """Deduplicated callable for ``key`` (no AOT compile here — the
        callable is typically ``jax.jit``-wrapped and compiles lazily per
        shape)."""
        entry = self._get(key)
        if entry is not None:
            return entry
        return self._put(key, build())

    # -------------------------------------------------------------- stats
    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        """Host-side snapshot for bench artifacts / debugging."""
        with self._lock:
            size = len(self._entries)
        # kernel-selection view next to the cost/memory records it explains;
        # selection lives in ops.kernel_select, the manager just exposes it
        try:
            from ..ops import kernel_select as _ks  # noqa: PLC0415

            kernels = _ks.stats()
        except Exception:
            kernels = {"error": "kernel_select unavailable"}
        return {
            "entries": size,
            "max_entries": self.max_entries,
            "compiles_total": self.compiles.value,
            "admission_errors": self._admission_errors,
            "cache_hits_total": self.cache_hits.value,
            "evictions_total": self.evictions.value,
            "compile_seconds": self.compile_time.summary(),
            "memory": self._memory_summary(),
            "static_cost": self._cost_summary(),
            "kernels": kernels,
        }


_GLOBAL: Optional[CompileManager] = None
_GLOBAL_LOCK = threading.Lock()


def get_compile_manager() -> CompileManager:
    """The process-wide manager (both network classes and the bench share
    it). First call also places the persistent compilation cache
    (:func:`resolve_persistent_cache`)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            resolve_persistent_cache()
            _GLOBAL = CompileManager()
        return _GLOBAL
