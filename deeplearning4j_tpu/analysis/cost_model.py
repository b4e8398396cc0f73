"""Static roofline cost model: FLOPs/bytes/intensity of a jaxpr, no devices.

Walks the eqns of a ``jax.make_jaxpr`` trace with a per-primitive cost table
and emits a ``static_cost`` report — total FLOPs, HBM bytes touched,
arithmetic intensity, and a predicted step time from a configurable roofline
(Williams et al., "Roofline: an insightful visual performance model"). The
whole pass is host-side shape algebra: no compile, no dispatch, no profiler
run — cheap enough to gate CI on.

Counting conventions (deliberately simple, deliberately stated):

- ``dot_general``: exact ``2 * batch * M * N * K``; ``conv_general_dilated``:
  exact ``2 * out_elements * kernel_spatial * C_in / feature_groups``. These
  two dominate real models and are bit-exact against the closed forms
  (tests/test_ir_cost.py holds them to equality).
- reductions count one FLOP per input element; every other arithmetic eqn
  counts one FLOP per output element (a transcendental is 1 FLOP — the MXU
  doesn't run it anyway, the VPU cost model is not the bottleneck we chase).
- pure data movement (reshape/transpose/slice/broadcast/convert/...) is
  0 FLOPs but still moves bytes.
- bytes per eqn = operand bytes + result bytes. No fusion modeling: XLA will
  beat this number, so arithmetic intensity is a *lower bound* and the
  predicted step time an *upper bound* — the right polarity for a gate.
- ``scan`` multiplies its body by the static trip count; ``while`` (dynamic
  trip count) counts ONE iteration and sets ``dynamic_loop`` — per-step cost
  is what the report means, and the staged ``fori_loop`` runs one optimizer
  step per iteration.
- ``cond`` takes the most expensive branch (upper bound again).

Collectives (``psum``/``all_gather``/``ppermute``/...) are tallied
separately — count and payload bytes per step — feeding the DT207 check.

Roofline: the peaks come from :data:`DEVICE_PEAKS`, the one table of
published per-chip peaks keyed by jax's ``device_kind`` (see
:func:`device_peaks`); ``DL4JTPU_PEAK_FLOPS`` (peak FLOP/s),
``DL4JTPU_HBM_GBPS`` (HBM GB/s) and ``DL4JTPU_ICI_GBPS`` (interconnect GB/s
per chip) override single entries. The interconnect term makes
``predicted_step_seconds`` cover compute-, memory- AND communication-bound
steps: the per-step collective
bytes (the jaxpr census here, plus the sharding-flow predicted census when
a layout is analyzed — see ``analysis/shard_flow.py``) divide by the ICI
bandwidth, and ``bound`` reports which of the three ceilings wins.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "PEAK_FLOPS_ENV",
    "HBM_GBPS_ENV",
    "ICI_GBPS_ENV",
    "DEVICE_PEAKS",
    "device_peaks",
    "roofline_params",
    "apply_roofline",
    "jaxpr_cost",
    "static_cost",
    "subjaxprs",
]

PEAK_FLOPS_ENV = "DL4JTPU_PEAK_FLOPS"
HBM_GBPS_ENV = "DL4JTPU_HBM_GBPS"
ICI_GBPS_ENV = "DL4JTPU_ICI_GBPS"

# THE peaks table: published per-chip peaks keyed by ``device_kind`` exactly
# as jax reports it. Every roofline bound, kernel auto-score and MFU figure
# in the package reads this one table. A TPU whose kind is not listed is an
# error, never a default — add its row with its source.
DEVICE_PEAKS = {
    "TPU v5 lite": {  # v5e; device_kind string as the chip reports it
        "peak_flops": 1.97e14,  # bf16 MXU
        "hbm_gbps": 819.0,
        "ici_gbps": 200.0,  # 1,600 Gbit/s chip-to-chip
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                  'bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s interconnect',
    },
}
# the chip this repo is measured on: off-chip analysis (static lint, the CPU
# tests) models THIS row and says so (``assumed: True``)
ASSUMED_DEVICE_KIND = "TPU v5 lite"

# pure data movement: 0 FLOPs, bytes only
_ZERO_FLOP = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "convert_element_type", "bitcast_convert_type", "copy", "rev", "iota",
    "stop_gradient", "gather", "scatter", "select_n", "split",
    "device_put",
})

# one FLOP per INPUT element (tree reductions)
_REDUCE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cumprod", "cummax", "cummin",
})

# cross-device data movement, tallied separately for DT207
_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "psum_scatter", "pbroadcast",
})

# jaxpr primitive -> census kind: the DT207 census keys (kind, axes) the
# same way the measured post-SPMD census and the sharding-flow predicted
# census do (analysis/shard_flow.py)
_COLLECTIVE_KINDS = {
    "psum": "all_reduce", "pmax": "all_reduce", "pmin": "all_reduce",
    "pmean": "all_reduce", "pbroadcast": "all_reduce",
    "all_gather": "all_gather", "all_to_all": "all_to_all",
    "ppermute": "collective_permute",
    "reduce_scatter": "reduce_scatter", "psum_scatter": "reduce_scatter",
}


def device_peaks() -> dict:
    """The :data:`DEVICE_PEAKS` row for the attached device. On a TPU
    backend the row is looked up by ``jax.devices()[0].device_kind`` and an
    unknown kind raises; anywhere else the result is the
    :data:`ASSUMED_DEVICE_KIND` row marked ``assumed: True`` — a modeled
    target, not the machine the process runs on."""
    import jax  # noqa: PLC0415

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return {**DEVICE_PEAKS[ASSUMED_DEVICE_KIND],
                "device_kind": ASSUMED_DEVICE_KIND, "assumed": True}
    row = DEVICE_PEAKS.get(dev.device_kind)
    if row is None:
        raise KeyError(
            f"no peaks for device_kind {dev.device_kind!r}: add its "
            f"published peaks (with their source) to "
            f"analysis.cost_model.DEVICE_PEAKS; known: {sorted(DEVICE_PEAKS)}")
    return {**row, "device_kind": dev.device_kind, "assumed": False}


def roofline_params() -> dict:
    """The roofline in force: peak FLOP/s, HBM GB/s, ICI GB/s and the ridge
    point (FLOPs/byte above which a kernel is compute-bound), from
    :func:`device_peaks` with the env overrides applied, plus which
    ``device_kind`` row was used and whether it was ``assumed``."""
    def _env_float(name: str, default: float) -> float:
        raw = os.environ.get(name)
        if raw:
            try:
                return float(raw)
            except ValueError:
                pass
        return default

    row = device_peaks()
    peak = _env_float(PEAK_FLOPS_ENV, row["peak_flops"])
    gbps = _env_float(HBM_GBPS_ENV, row["hbm_gbps"])
    ici = _env_float(ICI_GBPS_ENV, row["ici_gbps"])
    return {
        "peak_flops": peak,
        "hbm_gbps": gbps,
        "ici_gbps": ici,
        "ridge_flops_per_byte": peak / (gbps * 1e9),
        "device_kind": row["device_kind"],
        "assumed": row["assumed"],
    }


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0  # abstract tokens / effects
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    try:
        itemsize = int(np.dtype(dtype).itemsize)
    except TypeError:
        # extended dtypes (PRNG key<fry> etc.): negligible, count the
        # elements at 4 bytes rather than crashing the whole report
        itemsize = int(getattr(dtype, "itemsize", 4) or 4)
    return n * itemsize


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _dot_general_flops(eqn) -> int:
    """Exact 2*batch*M*N*K from the dimension numbers."""
    (lhs_c, rhs_c), (lhs_b, _rhs_b) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = 1
    for d in lhs_b:
        batch *= int(lhs[d])
    k = 1
    for d in lhs_c:
        k *= int(lhs[d])
    m = 1
    for i, d in enumerate(lhs):
        if i not in lhs_c and i not in lhs_b:
            m *= int(d)
    n = 1
    rhs_b = eqn.params["dimension_numbers"][1][1]
    for i, d in enumerate(rhs):
        if i not in rhs_c and i not in rhs_b:
            n *= int(d)
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    """Exact 2 * out_elements * kernel_spatial * C_in / feature_groups."""
    dn = eqn.params["dimension_numbers"]
    rhs_spec = dn.rhs_spec  # (out_chan, in_chan, *spatial)
    kernel = eqn.invars[1].aval.shape
    c_in = int(kernel[rhs_spec[1]])  # the kernel dim is already C_in/groups
    spatial = 1
    for d in rhs_spec[2:]:
        spatial *= int(kernel[d])
    out_elems = _aval_elems(eqn.outvars[0].aval)
    return 2 * out_elems * spatial * c_in  # c_in is already per-group


def subjaxprs(eqn) -> List[Tuple[Any, int]]:
    """(closed_jaxpr, multiplier) pairs nested inside one eqn.

    ``scan`` multiplies by its static trip count; ``while`` counts one
    iteration (dynamic trip count — the caller flags it); ``cond`` returns
    every branch (the cost walker takes the max). The generic fallback scans
    params for jaxpr-shaped values so new wrapper primitives (remat, custom
    derivatives, pjit) keep being walked without a registry update.
    """
    from jax.extend import core  # noqa: PLC0415

    def closed(j):
        if isinstance(j, core.ClosedJaxpr):
            return j
        if isinstance(j, core.Jaxpr):
            return core.ClosedJaxpr(j, ())
        return None

    name = eqn.primitive.name
    if name == "scan":
        body = closed(eqn.params["jaxpr"])
        return [(body, int(eqn.params.get("length", 1)))] if body else []
    if name == "while":
        out = []
        for key in ("cond_jaxpr", "body_jaxpr"):
            j = closed(eqn.params.get(key))
            if j is not None:
                out.append((j, 1))
        return out
    if name == "cond":
        return [(b, 1) for b in map(closed, eqn.params.get("branches", ()))
                if b is not None]
    out = []
    for v in eqn.params.values():
        j = closed(v)
        if j is not None:
            out.append((j, 1))
        elif isinstance(v, (tuple, list)):
            out.extend((closed(x), 1) for x in v if closed(x) is not None)
    return out


def _eqn_cost(eqn) -> Tuple[int, int]:
    """(flops, bytes) of one leaf eqn (no nested jaxpr)."""
    name = eqn.primitive.name
    in_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
    out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
    if name == "dot_general":
        flops = _dot_general_flops(eqn)
    elif name == "conv_general_dilated":
        flops = _conv_flops(eqn)
    elif name in _ZERO_FLOP:
        flops = 0
    elif name in _REDUCE or name.startswith("reduce_"):
        flops = sum(_aval_elems(v.aval) for v in eqn.invars)
    else:
        flops = sum(_aval_elems(v.aval) for v in eqn.outvars)
    return flops, in_bytes + out_bytes


def jaxpr_cost(closed_jaxpr) -> dict:
    """Cost report of a (closed) jaxpr: FLOPs, HBM bytes, per-primitive
    breakdown, collective tally, roofline projection. Pure host arithmetic.
    """
    acc = {
        "flops": 0, "hbm_bytes": 0, "eqns": 0, "dynamic_loop": False,
        "by_primitive": {},
        "collectives": {"count": 0, "bytes": 0, "by_primitive": {},
                        "census": {}},
    }

    def walk(closed, mult: int) -> Tuple[int, int]:
        flops_here = 0
        bytes_here = 0
        for eqn in closed.jaxpr.eqns:
            name = eqn.primitive.name
            nested = subjaxprs(eqn)
            if name == "while":
                acc["dynamic_loop"] = True
            if nested:
                if name == "cond":
                    best = (0, 0)
                    for sub, m in nested:
                        best = max(best, walk(sub, mult * m))
                    f, b = best
                else:
                    f = b = 0
                    for sub, m in nested:
                        sf, sb = walk(sub, mult * m)
                        f += sf
                        b += sb
                flops_here += f
                bytes_here += b
                continue
            f, b = _eqn_cost(eqn)
            f *= mult
            b *= mult
            flops_here += f
            bytes_here += b
            acc["eqns"] += mult
            row = acc["by_primitive"].setdefault(
                name, {"count": 0, "flops": 0, "bytes": 0})
            row["count"] += mult
            row["flops"] += f
            row["bytes"] += b
            if name in _COLLECTIVES:
                payload = mult * sum(_aval_bytes(v.aval) for v in eqn.invars)
                acc["collectives"]["count"] += mult
                acc["collectives"]["bytes"] += payload
                # mesh-axis labels: psum/all_gather/... carry the named axes
                # they span, so the jaxpr census keys exactly like the
                # measured post-SPMD census ((kind, axes) — see
                # analysis/shard_flow.hlo_collective_census)
                axes = eqn.params.get("axes") or eqn.params.get(
                    "axis_name") or ()
                if not isinstance(axes, (tuple, list)):
                    axes = (axes,)
                axes = tuple(sorted(str(a) for a in axes))
                crow = acc["collectives"]["by_primitive"].setdefault(
                    name, {"count": 0, "bytes": 0, "axes": []})
                crow["count"] += mult
                crow["bytes"] += payload
                for a in axes:
                    if a not in crow["axes"]:
                        crow["axes"].append(a)
                cens = acc["collectives"]["census"].setdefault(
                    (_COLLECTIVE_KINDS.get(name, name), axes),
                    {"count": 0, "bytes": 0})
                cens["count"] += mult
                cens["bytes"] += payload
        return flops_here, bytes_here

    flops, nbytes = walk(closed_jaxpr, 1)
    acc["flops"] = int(flops)
    acc["hbm_bytes"] = int(nbytes)
    acc["arithmetic_intensity"] = (
        flops / nbytes if nbytes else 0.0)
    # census rows in list form (tuple keys don't survive JSON)
    acc["collectives"]["census"] = [
        {"kind": k, "axes": list(axes), "count": row["count"],
         "bytes": row["bytes"]}
        for (k, axes), row in sorted(acc["collectives"]["census"].items())]
    apply_roofline(acc, comm_bytes=acc["collectives"]["bytes"])
    return acc


def apply_roofline(cost: dict, *, comm_bytes: Optional[int] = None,
                   pipeline: Optional[dict] = None) -> dict:
    """(Re)compute ``cost["roofline"]`` from its flops/bytes and a per-step
    communication volume. ``comm_bytes`` defaults to the jaxpr-level
    collective tally; the sharding-flow pass calls this again with its
    predicted census total, so ``predicted_step_seconds`` covers the
    communication-bound regime and ``bound`` can come back
    ``"communication"``.

    ``pipeline={"stages": P, "microbatches": M}`` models a pipelined step:
    compute/memory work divides across the P stages, and the interleaved
    schedule idles a bubble fraction ``(P-1)/(M+P-1)`` of every tick window
    — the predicted seconds inflate by ``1/(1-bubble)``. Communication
    (the per-microbatch stage handoffs are already in ``comm_bytes``) rides
    the same schedule, so it inflates too."""
    flops = cost.get("flops", 0)
    nbytes = cost.get("hbm_bytes", 0)
    if comm_bytes is None:
        comm_bytes = int(cost.get("collectives", {}).get("bytes", 0))
    rl = roofline_params()
    compute_s = flops / rl["peak_flops"] if rl["peak_flops"] else 0.0
    memory_s = (nbytes / (rl["hbm_gbps"] * 1e9)) if rl["hbm_gbps"] else 0.0
    comm_s = (comm_bytes / (rl["ici_gbps"] * 1e9)) if rl["ici_gbps"] else 0.0
    if pipeline:
        p = max(int(pipeline.get("stages", 1)), 1)
        m = max(int(pipeline.get("microbatches", 1)), 1)
        bubble = (p - 1) / (m + p - 1)
        rl["pipeline_stages"] = p
        rl["pipeline_microbatches"] = m
        rl["bubble_fraction"] = bubble
        compute_s /= p
        memory_s /= p
        rl["predicted_step_seconds"] = (
            max(compute_s, memory_s, comm_s) / (1.0 - bubble))
    else:
        rl["predicted_step_seconds"] = max(compute_s, memory_s, comm_s)
    rl["compute_seconds"] = compute_s
    rl["memory_seconds"] = memory_s
    rl["communication_seconds"] = comm_s
    rl["communication_bytes"] = int(comm_bytes)
    if comm_s > max(compute_s, memory_s):
        rl["bound"] = "communication"
    else:
        rl["bound"] = ("compute" if cost.get("arithmetic_intensity", 0.0)
                       >= rl["ridge_flops_per_byte"] else "memory")
    cost["roofline"] = rl
    return cost


def static_cost(fn, *example_args, **make_jaxpr_kw) -> dict:
    """Trace ``fn`` at ``example_args`` (arrays or ``ShapeDtypeStruct``
    shells — nothing executes) and cost the resulting jaxpr. ``fn`` may be
    ``jax.jit``-wrapped; the walker recurses through the pjit eqn."""
    import jax  # noqa: PLC0415

    closed = jax.make_jaxpr(fn, **make_jaxpr_kw)(*example_args)
    return jaxpr_cost(closed)
