"""Cost-model-guided kernel auto-selection — the routing layer over the
Pallas helper tier.

The reference hand-routed every hot path to the fastest native kernel it had
(LSTMHelpers/CudnnConvolutionHelper discovery, SURVEY.md §2.3). This module
is the TPU-native generalization: every *fusable site* (LSTM sequence,
attention, LRN, softmax+cross-entropy, the optimizer update) registers its
kernel variants here with a per-variant static cost estimate, and at trace
time the PR 5 roofline (:mod:`..analysis.cost_model`) scores the variants
for the concrete shapes and picks the winner. Layers stop hardcoding
``DL4J_TPU_PALLAS`` dispatch logic; a future kernel becomes a drop-in win by
registering one more variant.

How a selection resolves, in precedence order:

1. **forced** — the call site's legacy knobs (``DL4J_TPU_PALLAS``,
   ``set_helpers_enabled``, an explicit ``attention_impl=``) still win, so
   every pre-existing escape hatch keeps its exact meaning.
2. **per-site override** — ``set_site_override("lstm_seq", "reference")`` or
   the env form ``DL4JTPU_KERNELS=lstm_seq=reference,attention=flash``: the
   pragma-style escape hatch for one site without touching the others.
3. **mode** — ``DL4JTPU_KERNELS=auto|reference|fused`` (default ``auto``).
   ``reference`` pins every site to the XLA path, ``fused`` to the preferred
   fused variant (still subject to hard feasibility: VMEM fit, supported
   activations), ``auto`` scores.
4. **auto scoring** — each feasible variant's (FLOPs, HBM bytes, fixed
   launch overhead) estimate becomes a predicted time
   ``max(flops/peak, bytes/bw) + overhead`` on the configured roofline
   (``DL4JTPU_PEAK_FLOPS``/``DL4JTPU_HBM_GBPS``); minimum wins, fused
   breaking ties. Fused Pallas variants only *compete* when the process runs
   on a TPU backend (or :func:`set_force_available` is on — tests/CI score
   them in interpret mode), mirroring the helper tier's TPU-auto default.

Byte estimates for the XLA reference variants use the cost model's deliberate
un-fused counting (a known upper bound — PR 5 limits note). The bench feeds
its measured ``predicted_vs_measured`` ratio back through
:func:`update_calibration`; the persisted factor (``KERNEL_CALIBRATION.json``)
discounts exactly those un-fused byte counts, so the model tightens round
over round instead of staying a static guess.

Every selection is observable end to end: a
``dl4jtpu_kernel_selected_total{site,variant}`` counter in the PR 2 registry,
a ``kernel_select`` event in the PR 4 flight recorder, and a ``kernels``
block in ``CompileManager.stats()`` / ``/api/ircost`` / the BENCH_* artifact.
Selections are cached per (site, shape key, config), so the same shapes
always resolve to the same variant and are logged exactly once — pinned by
tests/test_kernel_select.py.

Host-side only: nothing here touches device buffers; selection runs during
tracing (zero dispatches) and is pure shape algebra plus the roofline.
"""

from __future__ import annotations

import functools
import json
import os
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "KERNELS_ENV",
    "CALIBRATION_PATH_ENV",
    "FLASH_MIN_SEQ_ENV",
    "Variant",
    "Site",
    "register_site",
    "select",
    "mode",
    "set_mode",
    "forced_mode",
    "set_site_override",
    "set_force_available",
    "force_available",
    "partitioned_program",
    "scoped_for_layout",
    "calibration_factor",
    "update_calibration",
    "selection_log",
    "stats",
    "reset",
]

# env knob: auto | reference | fused, optionally mixed with per-site
# overrides ("auto,lstm_seq=reference") — see docs/performance.md
KERNELS_ENV = "DL4JTPU_KERNELS"
# env knob: where the fusion-discount calibration JSON lives (default:
# KERNEL_CALIBRATION.json next to this package's repo root)
CALIBRATION_PATH_ENV = "DL4JTPU_KERNEL_CALIBRATION"
# env knob: sequence-length threshold below which auto mode keeps the XLA
# attention path even when flash is feasible (launch overhead + small [T,T]
# scores make the fused kernel a wash at short context)
FLASH_MIN_SEQ_ENV = "DL4JTPU_FLASH_MIN_SEQ"
DEFAULT_FLASH_MIN_SEQ = 256

_MODES = ("auto", "reference", "fused")

# calibration discount floor: never trust a measured ratio enough to claim
# XLA fuses >95% of the modeled traffic away
_CAL_MIN, _CAL_MAX = 0.05, 1.0


@dataclass(frozen=True)
class Variant:
    """One selectable kernel implementation at a site.

    ``available`` is HARD feasibility (VMEM fit, supported activations) —
    consulted for every resolution path including forced. ``auto_gate`` is
    soft policy (e.g. the flash min-seq threshold) consulted only by auto
    scoring. ``cost`` returns (flops, hbm_bytes, overhead_seconds) for the
    ctx; ``unfused_bytes`` marks estimates produced by the cost model's
    un-fused counting, which the measured calibration factor discounts.
    ``detail`` returns what else the selection record says of the variant at
    this ctx (the seq-fused LSTM's ``time_block``).
    """

    name: str
    fused: bool
    cost: Callable[[dict], Tuple[float, float, float]]
    available: Callable[[dict], bool] = lambda ctx: True
    auto_gate: Callable[[dict], bool] = lambda ctx: True
    unfused_bytes: bool = False
    detail: Callable[[dict], dict] = lambda ctx: {}


@dataclass
class Site:
    name: str
    reference: str
    preferred_fused: str
    variants: Dict[str, Variant] = field(default_factory=dict)


_SITES: Dict[str, Site] = {}
_LOCK = threading.RLock()
_CACHE: Dict[Tuple, dict] = {}
_LOG: List[dict] = []
_FORCE_AVAILABLE = False
_MODE_OVERRIDE: Optional[str] = None
_SITE_OVERRIDES: Dict[str, str] = {}
_CAL_CACHE: Optional[Tuple[float, dict, float]] = None  # (mtime, data, factor)
_TRACE = threading.local()  # .partitioned: see partitioned_program


def register_site(site: Site) -> None:
    with _LOCK:
        _SITES[site.name] = site


def _parse_env() -> Tuple[str, Dict[str, str]]:
    """``DL4JTPU_KERNELS`` grammar: comma-separated tokens; a bare token is
    the global mode, ``site=variant`` a per-site override."""
    raw = os.environ.get(KERNELS_ENV, "")
    env_mode = "auto"
    overrides: Dict[str, str] = {}
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if "=" in tok:
            site, _, variant = tok.partition("=")
            overrides[site.strip()] = variant.strip()
        elif tok in _MODES:
            env_mode = tok
    return env_mode, overrides


def mode() -> str:
    """The effective global mode (programmatic override > env > auto)."""
    if _MODE_OVERRIDE is not None:
        return _MODE_OVERRIDE
    return _parse_env()[0]


def set_mode(m: Optional[str]) -> None:
    """Programmatic mode override (None restores env/auto resolution)."""
    global _MODE_OVERRIDE
    if m is not None and m not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {m!r}")
    _MODE_OVERRIDE = m


@contextmanager
def forced_mode(m: str):
    """Scoped :func:`set_mode` — the bench's auto-vs-reference A/B uses it."""
    prev = _MODE_OVERRIDE
    set_mode(m)
    try:
        yield
    finally:
        set_mode(prev)


def set_site_override(site: str, variant: Optional[str]) -> None:
    """Pin one site to one variant (None clears) — the per-site pragma
    escape hatch; env-form overrides ride ``DL4JTPU_KERNELS=site=variant``."""
    with _LOCK:
        if variant is None:
            _SITE_OVERRIDES.pop(site, None)
        else:
            _SITE_OVERRIDES[site] = variant


def _site_override(site: str) -> Optional[str]:
    ov = _SITE_OVERRIDES.get(site)
    if ov is not None:
        return ov
    return _parse_env()[1].get(site)


def set_force_available(flag: bool) -> None:
    """Let fused variants compete in auto scoring off-TPU (interpret mode).
    CI's kernel-selection self-scan and the parity tests run under this —
    production auto mode only scores fused kernels on a real TPU backend."""
    global _FORCE_AVAILABLE
    _FORCE_AVAILABLE = bool(flag)


def force_available() -> bool:
    return _FORCE_AVAILABLE


def _fused_competes() -> bool:
    if _FORCE_AVAILABLE:
        return True
    try:
        import jax  # noqa: PLC0415 - keep module import light

        return jax.default_backend() == "tpu"
    except Exception:
        return False


@contextmanager
def partitioned_program(flag: bool = True):
    """Scope for TRACING a program that GSPMD will partition over a
    multi-device mesh. Mosaic kernels cannot be partitioned automatically
    ("Mosaic kernels cannot be automatically partitioned. Please wrap the
    call in a shard_map" — the lowering error every fused site raised on a
    four-chip v5e host), so inside the scope every fused variant is
    infeasible: sites resolve to their XLA reference with reason
    ``fallback`` and ``partitioned: True`` in the record's ctx. A false
    ``flag`` is a no-op, so an outer scope is never cleared by an inner one.
    The compile manager opens the scope from an AOT program's argument
    shardings; jit-compiled steps get it from their net's layout
    (:func:`scoped_for_layout`)."""
    prev = getattr(_TRACE, "partitioned", False)
    _TRACE.partitioned = prev or bool(flag)
    try:
        yield
    finally:
        _TRACE.partitioned = prev


def scoped_for_layout(fn, layout):
    """``fn`` wrapped so that it traces inside :func:`partitioned_program`
    when ``layout`` (a MeshLayout or None) spans more than one device;
    ``fn`` itself otherwise."""
    mesh = getattr(layout, "mesh", None)
    if mesh is None or mesh.devices.size <= 1:
        return fn

    @functools.wraps(fn)
    def traced_partitioned(*args, **kwargs):
        with partitioned_program():
            return fn(*args, **kwargs)

    return traced_partitioned


def flash_min_seq() -> int:
    try:
        return int(os.environ.get(FLASH_MIN_SEQ_ENV, DEFAULT_FLASH_MIN_SEQ))
    except ValueError:
        return DEFAULT_FLASH_MIN_SEQ


# ------------------------------------------------------------- calibration
def _calibration_path() -> str:
    explicit = os.environ.get(CALIBRATION_PATH_ENV)
    if explicit:
        return explicit
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(pkg_root, "KERNEL_CALIBRATION.json")


def _load_calibration() -> Tuple[dict, float]:
    """(raw data, discount factor). Cached by file mtime; a missing or
    malformed file means factor 1.0 (trust the un-fused counts as-is)."""
    global _CAL_CACHE
    path = _calibration_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}, 1.0
    with _LOCK:
        if _CAL_CACHE is not None and _CAL_CACHE[0] == mtime:
            return _CAL_CACHE[1], _CAL_CACHE[2]
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            data = {}
    except (OSError, json.JSONDecodeError):
        data = {}
    ratios = [v for k, v in data.items()
              if isinstance(v, (int, float)) and v > 0]
    if ratios:
        # geometric mean of predicted/measured across modes; >1 means the
        # un-fused byte counts over-predicted, so discount by its inverse
        import math

        g = math.exp(sum(math.log(r) for r in ratios) / len(ratios))
        factor = min(_CAL_MAX, max(_CAL_MIN, 1.0 / g)) if g > 1.0 else 1.0
    else:
        factor = 1.0
    with _LOCK:
        _CAL_CACHE = (mtime, data, factor)
    return data, factor


def calibration_factor() -> float:
    """Multiplier applied to un-fused byte estimates during auto scoring."""
    return _load_calibration()[1]


def calibration_snapshot() -> Tuple[str, dict]:
    """(path, raw ratios) of the active calibration file — what warm-boot
    bundles embed so a fresh fleet worker scores kernels with the same
    measured discounts as the process that built the bundle."""
    return _calibration_path(), dict(_load_calibration()[0])


def site_overrides() -> dict:
    """The pinned site→variant map (both set_site_override and
    ``DL4JTPU_KERNELS`` env form), for warm-boot bundle capture."""
    with _LOCK:
        pinned = dict(_SITE_OVERRIDES)
    env_form = _parse_env()[1]
    return {**env_form, **pinned}


def update_calibration(key: str, predicted_vs_measured: float) -> bool:
    """Persist one bench mode's predicted/measured step-time ratio — the
    feedback half of the calibration loop (bench.py calls this from its
    ``static_cost`` block). Returns True when written."""
    try:
        ratio = float(predicted_vs_measured)
    except (TypeError, ValueError):
        return False
    if not (ratio > 0):
        return False
    path = _calibration_path()
    data, _ = _load_calibration()
    data = dict(data)
    data[str(key)] = round(ratio, 6)
    try:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return False
    global _CAL_CACHE
    with _LOCK:
        _CAL_CACHE = None  # next read re-derives the factor
    return True


# --------------------------------------------------------------- selection
def _predicted_seconds(v: Variant, ctx: dict, cal: float) -> float:
    from ..analysis.cost_model import roofline_params  # noqa: PLC0415

    flops, nbytes, overhead = v.cost(ctx)
    if v.unfused_bytes:
        nbytes *= cal
    rl = roofline_params()
    compute_s = flops / rl["peak_flops"] if rl["peak_flops"] else 0.0
    memory_s = nbytes / (rl["hbm_gbps"] * 1e9) if rl["hbm_gbps"] else 0.0
    return max(compute_s, memory_s) + overhead


def _observe(record: dict) -> None:
    """Counter + flight-recorder event for one NEW (site, key) selection.
    Observability must never break the traced path that asked."""
    try:
        from ..telemetry import get_registry  # noqa: PLC0415

        get_registry().counter(
            "dl4jtpu_kernel_selected_total",
            "kernel-variant selections by site (one per distinct shape key)",
            labelnames=("site", "variant"),
        ).labels(site=record["site"], variant=record["variant"]).inc()
    except Exception:
        pass
    try:
        from ..telemetry.flight_recorder import get_flight_recorder  # noqa: PLC0415

        get_flight_recorder().record(
            "kernel_select", site=record["site"], variant=record["variant"],
            reason=record["reason"], ctx=dict(record["ctx"]),
            predicted_s=record.get("predicted_s"))
    except Exception:
        pass


def select(site_name: str, ctx: dict, forced: Optional[str] = None) -> str:
    """Resolve the variant for ``site_name`` at the concrete ``ctx`` shapes.

    ``forced`` carries a call site's legacy knob (highest precedence); it is
    still subject to the variant's hard feasibility check and falls back to
    the reference variant when infeasible. Resolutions are cached per
    (site, ctx, config) — deterministic, and logged/counted exactly once.

    No give-way is quiet: a fused variant whose hard feasibility check
    rejected this ctx (VMEM budget, unsupported activations) is listed under
    the record's ``infeasible`` key, and when the site ends on its reference
    variant where a fused one was asked for or would have competed, the
    record's reason is ``"fallback"``.
    """
    site = _SITES[site_name]
    partitioned = getattr(_TRACE, "partitioned", False)
    if partitioned:
        ctx = {**ctx, "partitioned": True}
    m = mode()
    ov = _site_override(site_name)
    cal = calibration_factor()
    key = (site_name, tuple(sorted(ctx.items())), forced, m, ov,
           _FORCE_AVAILABLE, round(cal, 4))
    with _LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            return hit["variant"]

    def available(v: Variant) -> bool:
        return not (partitioned and v.fused) and v.available(ctx)

    def feasible(name: Optional[str]) -> Optional[str]:
        v = site.variants.get(name or "")
        return v.name if v is not None and available(v) else None

    infeasible = [v.name for v in site.variants.values()
                  if v.fused and not available(v)]
    fused_ok = _fused_competes()
    choice: Optional[str] = None
    reason = "auto"
    predicted: Optional[dict] = None
    if forced is not None:
        choice = feasible(forced)
        reason = "forced"
    if choice is None and ov is not None:
        choice = feasible(ov)
        if choice is not None:
            reason = "override"
    if choice is None and m == "reference":
        choice, reason = site.reference, "mode"
    if choice is None and m == "fused":
        choice = feasible(site.preferred_fused) or next(
            (feasible(n) for n, v in site.variants.items()
             if v.fused and feasible(n)), None)
        reason = "mode"
    if choice is None:
        candidates = [
            v for v in site.variants.values()
            if available(v) and v.auto_gate(ctx)
            and (fused_ok or not v.fused)
        ]
        if not candidates:
            choice, reason = site.reference, "fallback"
        else:
            predicted = {v.name: _predicted_seconds(v, ctx, cal)
                         for v in candidates}
            # minimum predicted time; fused breaks ties (it is the variant
            # whose byte estimate we actually trust)
            choice = min(
                candidates,
                key=lambda v: (predicted[v.name], 0 if v.fused else 1),
            ).name
            reason = "auto"
    if choice not in site.variants:
        choice = site.reference
    asked_reference = site.reference in (forced, ov) or m == "reference"
    fused_in_play = (forced in infeasible or ov in infeasible
                     or m == "fused" or fused_ok)
    if choice == site.reference and infeasible and fused_in_play \
            and not asked_reference:
        reason = "fallback"

    record = {"site": site_name, "variant": choice, "reason": reason,
              "ctx": dict(ctx), "mode": m, **site.variants[choice].detail(ctx)}
    if infeasible:
        record["infeasible"] = infeasible
    if predicted is not None:
        record["predicted_s"] = {k: float(f"{v:.3e}")
                                 for k, v in predicted.items()}
    with _LOCK:
        # racing first-selection: keep the winner, log once
        hit = _CACHE.get(key)
        if hit is not None:
            return hit["variant"]
        _CACHE[key] = record
        _LOG.append(record)
    _observe(record)
    return choice


# ------------------------------------------------------------- introspection
def selection_log() -> List[dict]:
    with _LOCK:
        return list(_LOG)


def stats(last: int = 32) -> dict:
    """Snapshot for ``cm.stats()['kernels']`` / ``/api/ircost`` / bench."""
    with _LOCK:
        log = list(_LOG)
    by_site: Dict[str, Dict[str, int]] = {}
    for rec in log:
        row = by_site.setdefault(rec["site"], {})
        row[rec["variant"]] = row.get(rec["variant"], 0) + 1
    data, factor = _load_calibration()
    return {
        "mode": mode(),
        "force_available": _FORCE_AVAILABLE,
        "sites": sorted(_SITES),
        "selections_total": len(log),
        "by_site": by_site,
        "recent": log[-last:],
        "calibration": {"factor": round(factor, 4), "entries": len(data),
                        "path": _calibration_path()},
    }


def reset() -> None:
    """Test hook: clear cached selections, the log, and every override."""
    global _FORCE_AVAILABLE, _MODE_OVERRIDE, _CAL_CACHE
    with _LOCK:
        _CACHE.clear()
        _LOG.clear()
        _SITE_OVERRIDES.clear()
        _FORCE_AVAILABLE = False
        _MODE_OVERRIDE = None
        _CAL_CACHE = None


# ---------------------------------------------------------------------------
# Site registrations. Cost closed forms are deliberately simple RANKERS, not
# simulators (same philosophy as the PR 5 cost model): FLOPs are identical
# across variants of a site, byte counts model the HBM streams each variant
# actually moves (un-fused counting for the XLA reference paths — flagged so
# calibration discounts them), and overhead models fixed kernel-launch cost.
# tests/test_kernel_select.py pins the rankings the ISSUE demands.
# ---------------------------------------------------------------------------

_LAUNCH_S = 5e-6  # one pallas_call dispatch


def _lstm_flops(ctx) -> float:
    T, B, H = ctx["T"], ctx["B"], ctx["H"]
    # fwd recurrent matmul + bwd dzx@RW.T + dRW accumulation, plus gate math
    return 24.0 * T * B * H * H + 60.0 * T * B * H


def _lstm_seqfused_cost(ctx):
    T, B, H, itemsize = ctx["T"], ctx["B"], ctx["H"], ctx["itemsize"]
    # fwd: zx in + y out + 5 residual streams; bwd: dy + 5 residuals +
    # shifted c/h re-reads + dzx out; RW resident once per pass
    nbytes = itemsize * (2.0 * T * B * 4 * H + 14.0 * T * B * H
                         + 3.0 * H * 4 * H)
    return _lstm_flops(ctx), nbytes, 2 * _LAUNCH_S


def _lstm_fusedcell_cost(ctx):
    T, B, H, itemsize = ctx["T"], ctx["B"], ctx["H"], ctx["itemsize"]
    # per-step pallas_call: 7 residual arrays spill to HBM fwd AND re-load
    # bwd (the measured reason XLA's scan beats it — ops/__init__ docstring)
    nbytes = itemsize * T * (4.0 * B * 4 * H + 28.0 * B * H
                             + 4.0 * H * 4 * H)
    return _lstm_flops(ctx), nbytes, 2 * ctx["T"] * _LAUNCH_S


def _lstm_reference_cost(ctx):
    T, B, H, itemsize = ctx["T"], ctx["B"], ctx["H"], ctx["itemsize"]
    # un-fused counting of the scan body: every gate/cell intermediate is a
    # materialized [B,H] (or [B,4H]) round trip, fwd + ~2x bwd
    nbytes = itemsize * T * 66.0 * B * H
    return _lstm_flops(ctx), nbytes, 0.0


def _seq_fits_ctx(ctx) -> bool:
    from .pallas_kernels import _seq_fits  # noqa: PLC0415

    return bool(ctx["acts_ok"]) and _seq_fits(ctx["B"], ctx["H"],
                                              ctx["itemsize"])


def _seq_detail(ctx) -> dict:
    """Time steps a grid step the seq kernels take at these shapes; 1 means
    the blocking did not engage."""
    from .pallas_kernels import _seq_time_block  # noqa: PLC0415

    return {"time_block": _seq_time_block(ctx["T"], ctx["B"], ctx["H"],
                                          ctx["itemsize"])}


def _cell_fits_ctx(ctx) -> bool:
    from . import _cell_fits  # noqa: PLC0415

    return bool(ctx["acts_ok"]) and _cell_fits(ctx["B"], ctx["H"],
                                               ctx["itemsize"])


register_site(Site(
    name="lstm_seq",
    reference="reference",
    preferred_fused="seqfused",
    variants={
        "seqfused": Variant("seqfused", fused=True,
                            cost=_lstm_seqfused_cost,
                            available=_seq_fits_ctx, detail=_seq_detail),
        "fusedcell": Variant("fusedcell", fused=True,
                             cost=_lstm_fusedcell_cost,
                             available=_cell_fits_ctx),
        "reference": Variant("reference", fused=False,
                             cost=_lstm_reference_cost, unfused_bytes=True),
    },
))


def _attn_dims(ctx):
    return ctx["B"] * ctx["heads"], ctx["T"], ctx["D"], ctx["itemsize"]


# the compute dtypes a net can state (nn/conf: float32, bfloat16; float64
# under x64), by the itemsize the sites are asked with
_OPERAND_NAME = {2: "bfloat16", 4: "float32", 8: "float64"}


def _strip_width(ctx) -> int:
    """What the flash kernels size their tiles and their VMEM budget by:
    ``D`` for a plain call, the lane-padded strips of a call whose score and
    value products differ (``d_qk``, ``d_v``, ``d_rope`` in the ctx)."""
    from .flash_attention import strip_width  # noqa: PLC0415

    rope = ctx.get("d_rope", 0)
    return strip_width(ctx.get("d_qk", ctx["D"]) - rope,
                       ctx.get("d_v", ctx["D"]), rope)


def _flash_detail(ctx) -> dict:
    """The tiles the flash kernels take at these shapes, the dtype their
    products multiply in (the operands' own), and the share of the full
    square's tiles their loops visit: 1.0 without ``causal``, ``(n + 1) /
    2n`` with it for ``n`` tiles a side."""
    from .flash_attention import default_blocks, tiles_walked_share  # noqa: PLC0415

    block_q, block_k = default_blocks(ctx["T"], _strip_width(ctx),
                                      ctx["itemsize"])
    return {"block_q": block_q, "block_k": block_k,
            "mxu_operand": _OPERAND_NAME[ctx["itemsize"]],
            "tiles_walked_share": tiles_walked_share(
                ctx["T"], block_q, block_k, bool(ctx.get("causal")))}


def _attn_flash_cost(ctx):
    bh, t, d, itemsize = _attn_dims(ctx)
    # online-softmax recompute in the two backward passes costs extra FLOPs
    # but HBM traffic stays O(T*D) streams; under causal the loops leave out
    # the tiles above the diagonal
    flops = 14.0 * bh * t * t * d * _flash_detail(ctx)["tiles_walked_share"]
    nbytes = itemsize * 12.0 * bh * t * d + 8.0 * bh * t
    return flops, nbytes, 3 * _LAUNCH_S


def _attn_xla_cost(ctx):
    bh, t, d, itemsize = _attn_dims(ctx)
    flops = 10.0 * bh * t * t * d
    # the [T,T] score/prob/dprob/dscore tensors materialize in HBM
    nbytes = itemsize * (8.0 * bh * t * t + 8.0 * bh * t * d)
    return flops, nbytes, 0.0


def _flash_fits_ctx(ctx) -> bool:
    """Hard feasibility: the per-head K+V strip must fit the kernel's VMEM
    budget. Past it ``flash_attention`` itself runs the XLA path, so the
    site must say ``xla`` — never report a kernel that did not run."""
    from .flash_attention import _KV_VMEM_BUDGET_BYTES  # noqa: PLC0415

    return (2 * ctx["T"] * _strip_width(ctx) * ctx["itemsize"]
            <= _KV_VMEM_BUDGET_BYTES)


def _flash_auto_gate(ctx) -> bool:
    return ctx["T"] >= flash_min_seq()


register_site(Site(
    name="attention",
    reference="xla",
    preferred_fused="flash",
    variants={
        # the min-seq threshold is auto-mode policy only, so an explicit
        # attention_impl="flash" keeps meaning flash wherever it fits VMEM
        "flash": Variant("flash", fused=True, cost=_attn_flash_cost,
                         available=_flash_fits_ctx,
                         auto_gate=_flash_auto_gate, detail=_flash_detail),
        "xla": Variant("xla", fused=False, cost=_attn_xla_cost,
                       unfused_bytes=True),
    },
))


def _lrn_fused_cost(ctx):
    rows, C, n, itemsize = ctx["rows"], ctx["C"], ctx["n"], ctx["itemsize"]
    flops = (2.0 * n + 8.0) * rows * C
    # fwd: x in, y+d out; bwd: x, d, g in, dx out
    return flops, itemsize * 7.0 * rows * C, 2 * _LAUNCH_S


def _lrn_reference_cost(ctx):
    rows, C, n, itemsize = ctx["rows"], ctx["C"], ctx["n"], ctx["itemsize"]
    flops = (2.0 * n + 8.0) * rows * C
    # un-fused window sum: n shifted slices materialize fwd and again in the
    # adjoint, plus the pow/mul chain
    return flops, itemsize * (4.0 * n + 6.0) * rows * C, 0.0


register_site(Site(
    name="lrn",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_lrn_fused_cost),
        "reference": Variant("reference", fused=False,
                             cost=_lrn_reference_cost, unfused_bytes=True),
    },
))


def _sxent_fused_cost(ctx):
    N, C, itemsize = ctx["N"], ctx["C"], ctx["itemsize"]
    flops = 10.0 * N * C
    # fwd: preout+labels in, per-row loss out; bwd: preout+labels+g in,
    # dpre+dlabels out
    return flops, itemsize * 7.0 * N * C, 2 * _LAUNCH_S


def _sxent_reference_cost(ctx):
    N, C, itemsize = ctx["N"], ctx["C"], ctx["itemsize"]
    flops = 10.0 * N * C
    # un-fused: max/exp/sum/log/mul materialize between HBM round trips,
    # fwd + bwd softmax recompute
    return flops, itemsize * 12.0 * N * C, 0.0


register_site(Site(
    name="softmax_xent",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_sxent_fused_cost),
        "reference": Variant("reference", fused=False,
                             cost=_sxent_reference_cost, unfused_bytes=True),
    },
))


def _ssd_flops(ctx) -> float:
    """Forward products of the chunked algorithm (a group's ``C B^T`` scores
    once, then a head's ``M x``, ``C S`` and the state's update), three times
    for forward + backward."""
    B, T, H, P, G, N, L = (ctx[k] for k in ("B", "T", "H", "P", "G", "N",
                                            "chunk"))
    fwd = B * (T / L) * (G * 2.0 * L * L * N
                         + H * (2.0 * L * L * P + 4.0 * L * N * P))
    return 3.0 * fwd


def _ssd_streams(ctx) -> float:
    B, T, H, P, G, N = (ctx[k] for k in ("B", "T", "H", "P", "G", "N"))
    return B * T * (H * P + 2.0 * G * N)


def _ssd_fused_cost(ctx):
    # forward: x B C in, y out; backward: those and dy in, dx dB dC out; the
    # chunk-start states (float32) written once and read once
    states = 4.0 * 2.0 * ctx["B"] * (ctx["T"] / ctx["chunk"]) \
        * ctx["H"] * ctx["P"] * ctx["N"]
    nbytes = ctx["itemsize"] * 5.0 * _ssd_streams(ctx) + states
    return _ssd_flops(ctx), nbytes, 2 * _LAUNCH_S


def _ssd_reference_cost(ctx):
    # un-fused counting: the [L, L] scores, decay mask and their product a
    # head are materialized in float32, forward and again in the adjoint
    B, T, H, L = ctx["B"], ctx["T"], ctx["H"], ctx["chunk"]
    nbytes = ctx["itemsize"] * 10.0 * _ssd_streams(ctx) \
        + 4.0 * 12.0 * B * T * H * L
    return _ssd_flops(ctx), nbytes, 0.0


def _ssd_fits_ctx(ctx) -> bool:
    from .ssd_scan import ssd_fits, ssd_layout_ok  # noqa: PLC0415

    hpg = ctx["H"] // ctx["G"]
    return ssd_layout_ok(ctx["chunk"], ctx["P"], ctx["N"], hpg) and ssd_fits(
        ctx["chunk"], ctx["P"], ctx["N"], hpg, ctx["itemsize"])


register_site(Site(
    name="ssd_scan",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_ssd_fused_cost,
                         available=_ssd_fits_ctx,
                         detail=lambda ctx: {"chunk": ctx["chunk"]}),
        "reference": Variant("reference", fused=False,
                             cost=_ssd_reference_cost, unfused_bytes=True,
                             detail=lambda ctx: {"chunk": ctx["chunk"]}),
    },
))


_RAGGED_DOT_MXU_SHARE = 0.07   # XLA's grouped product on the v5e (PERF.md, PR 30)


def _gmm_flops(ctx) -> float:
    # forward, dlhs and drhs over the rows the buffer is sized for four times
    return 3.0 * 2.0 * (ctx["M"] / 4.0) * ctx["K"] * ctx["N"]


def _gmm_bytes(ctx) -> float:
    return ctx["itemsize"] * 3.0 * (ctx["E"] * ctx["K"] * ctx["N"]
                                    + (ctx["M"] / 4.0) * (ctx["K"] + ctx["N"]))


def _gmm_fused_cost(ctx):
    return _gmm_flops(ctx), _gmm_bytes(ctx), 3 * _LAUNCH_S


def _gmm_reference_cost(ctx):
    # measured, not modelled: 6.4 us a row at the published widths
    return _gmm_flops(ctx) / _RAGGED_DOT_MXU_SHARE, _gmm_bytes(ctx), 0.0


def _gmm_fits_ctx(ctx) -> bool:
    from .grouped_matmul import gmm_fits, gmm_layout_ok  # noqa: PLC0415

    return gmm_layout_ok(ctx["M"], ctx["K"], ctx["N"]) and gmm_fits(
        ctx["K"], ctx["N"], ctx["itemsize"])


def _gmm_detail(ctx) -> dict:
    from .grouped_matmul import ROW_TILE  # noqa: PLC0415

    return {"row_tile": ROW_TILE}


register_site(Site(
    name="grouped_matmul",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_gmm_fused_cost,
                         available=_gmm_fits_ctx, detail=_gmm_detail),
        "reference": Variant("reference", fused=False,
                             cost=_gmm_reference_cost,
                             detail=lambda ctx: {"row_tile": 1}),
    },
))


# reads and writes of the streams X [N, n * D] (the unit) a piece makes,
# forward + backward, when each kernel passes over X once: the read's result
# and the sublayer's output are a quarter of a unit at four streams
_HC_PASSES = {"maps": 1.0 + 2.0, "read": 1.25 + 2.25, "write": 2.25 + 3.5}


def _hc_cost(ctx, trips: float):
    elems = float(ctx["N"]) * ctx["n"] * ctx["D"]
    # a multiply-add a term: 24 columns, n streams, n * n + n map entries
    terms = {"maps": ctx["n"] * (2 + ctx["n"]), "read": 1,
             "write": ctx["n"] + 1}[ctx["op"]]
    return (6.0 * terms * elems,
            trips * _HC_PASSES[ctx["op"]] * ctx["itemsize"] * elems)


def _hc_fused_cost(ctx):
    return (*_hc_cost(ctx, 1.0), 2 * _LAUNCH_S)


def _hc_reference_cost(ctx):
    # measured on the compiled program, not modelled: XLA's fusions move
    # 7.0 GB a sublayer where one pass a piece moves 3.6 (PERF.md, PR 35)
    return (*_hc_cost(ctx, 2.0), 0.0)


def _hc_row_tile(ctx):
    from .hyper_connections import hc_row_tile  # noqa: PLC0415

    return hc_row_tile(ctx["op"], ctx["n"], ctx["D"], ctx["itemsize"])


register_site(Site(
    name="hyper_connection",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_hc_fused_cost,
                         available=lambda ctx: _hc_row_tile(ctx) is not None,
                         detail=lambda ctx: {"row_tile": _hc_row_tile(ctx)}),
        "reference": Variant("reference", fused=False,
                             cost=_hc_reference_cost),
    },
))


def _kda_flops(ctx) -> float:
    """The chunk equations' operations (two score matrices and the triangular
    system over half a chunk on average, three products with the ``K x V``
    state), three times for forward + backward."""
    B, T, H, K, V, C = (ctx[k] for k in ("B", "T", "H", "K", "V", "chunk"))
    a_token = C / 2.0 * (2 * K + 2 * V) + 3.0 * K * V
    return 3.0 * 2.0 * B * T * H * a_token


def _kda_reference_cost(ctx):
    """With no loop over the chunks the jax.numpy form also builds a chunk's
    map (``M`` [K, K], ``Z`` [K, V]) and composes the maps in the two sweeps
    of an associative scan (``[K, K] x [K, K + V]`` a chunk and sweep); every
    operand in float32, read and written about twice a pass by XLA's
    fusions."""
    B, T, H, K, V, C = (ctx[k] for k in ("B", "T", "H", "K", "V", "chunk"))
    scan = K * (K + V) * (1.0 + 2.0 * K / C)
    streams = B * T * H * (3.0 * K + 2.0 * V + 1.0)
    return (_kda_flops(ctx) + 3.0 * 2.0 * B * T * H * scan,
            4.0 * 6.0 * streams, 0.0)


def _kda_fused_cost(ctx):
    # the same operations without the maps; forward, and twice more with the
    # cotangents: v and o at the item size, q, k and the sums of the
    # log-decays in float32, a chunk's two [C, C] matrices; the states that
    # enter the chunks (float32) written once and read once
    B, T, H, K, V, C = (ctx[k] for k in ("B", "T", "H", "K", "V", "chunk"))
    streams = 3.0 * (ctx["itemsize"] * 2.0 * V + 4.0 * 3.0 * K + 4.0 * 2.0 * C)
    states = 4.0 * 2.0 * K * V / C
    return (_kda_flops(ctx), B * T * H * (streams + states), 2 * _LAUNCH_S)


def _kda_fits_ctx(ctx) -> bool:
    from .kda import kda_fits, kda_layout_ok  # noqa: PLC0415

    # float64 (the gradient checks) keeps the jax.numpy: the kernels' state
    # and products are float32
    return ctx["itemsize"] <= 4 and kda_layout_ok(
        ctx["chunk"], ctx["K"], ctx["V"]) and kda_fits(
        ctx["chunk"], ctx["K"], ctx["V"], ctx["itemsize"])


register_site(Site(
    name="kda_recurrence",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_kda_fused_cost,
                         available=_kda_fits_ctx,
                         detail=lambda ctx: {"chunk": ctx["chunk"]}),
        "reference": Variant("reference", fused=False,
                             cost=_kda_reference_cost, unfused_bytes=True,
                             detail=lambda ctx: {"chunk": ctx["chunk"]}),
    },
))


def _opt_fused_cost(ctx):
    n, itemsize = ctx["n_elems"], ctx["itemsize"]
    # read g/m/v, write u/m/v in one pass per leaf
    return 12.0 * n, itemsize * 7.0 * n, ctx.get("n_leaves", 1) * _LAUNCH_S


def _opt_reference_cost(ctx):
    n, itemsize = ctx["n_elems"], ctx["itemsize"]
    # un-fused optax chain: moment updates, bias corrections, sqrt, scale —
    # each a materialized tree-wide intermediate
    return 12.0 * n, itemsize * 14.0 * n, 0.0


# On the v5e a staged program that holds the in-place Adam kernel beside the
# gated delta rule in its jax.numpy form and an expert layer does not return
# from its first step: the host waits on the losses for good, on any seed,
# though the kernel alone is bit-exact there and the program compiles
# (PERF.md section 7 (c)). With optax's update the same program runs. Until
# the cause is found the kernel is infeasible where the caller says what it
# stands beside (``beside_reference``: a net says so of its own layers when
# it builds its updater, ``nn.engine.adam_kernel_gives_way_beside``), and the
# record shows it (``fallback``, ``infeasible``).
register_site(Site(
    name="optimizer",
    reference="reference",
    preferred_fused="fused",
    variants={
        "fused": Variant("fused", fused=True, cost=_opt_fused_cost,
                         available=lambda ctx: ctx.get("updater") == "adam"
                         and not ctx.get("beside_reference")),
        "reference": Variant("reference", fused=False,
                             cost=_opt_reference_cost, unfused_bytes=True),
    },
))
