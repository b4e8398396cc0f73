"""Op dispatch: Pallas helpers on TPU, pure-XLA math elsewhere.

Mirrors the reference's helper discovery (ConvolutionLayer.java:69-79 loads
CudnnConvolutionHelper reflectively and falls back to builtin math): here the
"helper" is a Pallas kernel, enabled when running on TPU (or forced via the
``DL4J_TPU_PALLAS`` env var: "1" forces on — interpret mode off-TPU, for
testing — and "0" forces off).

Since the kernel-selection rework, *which* implementation runs at each
fusable site is decided by :mod:`.kernel_select`: the ``select_*_variant``
wrappers below translate this module's legacy knobs (``DL4J_TPU_PALLAS``,
``set_helpers_enabled``) into a ``forced`` choice — preserving their exact
historical meaning — and otherwise let the PR 5 roofline score the variants
for the concrete shapes (``DL4JTPU_KERNELS=auto|reference|fused``).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from . import kernel_select
from .pallas_kernels import (
    _ACT,
    _cell_math,
    _window_sum,
    fused_adam_update,
    fused_lrn,
    fused_lstm_cell,
    fused_softmax_xent,
    fused_softmax_xent_ids,
    supported_lstm_activations,
)
from .flash_attention import flash_attention

_FORCED: Optional[bool] = None  # set_helpers_enabled override

# keep every fused-cell buffer comfortably inside ~16MB VMEM
_CELL_VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def set_helpers_enabled(enabled: Optional[bool]) -> None:
    """Force pallas helpers on/off (None = auto). Auto = TPU backend only."""
    global _FORCED
    _FORCED = enabled


def helpers_enabled() -> bool:
    if _FORCED is not None:
        return _FORCED
    env = os.environ.get("DL4J_TPU_PALLAS")
    if env == "0":
        return False
    if env == "1":
        return True
    return jax.default_backend() == "tpu"


def _cell_fits(B: int, H: int, itemsize: int) -> bool:
    # zx[B,4H] + 7×[B,H] + RW[H,4H] residuals/outputs
    return (B * 4 * H + 7 * B * H + H * 4 * H) * itemsize < _CELL_VMEM_BUDGET_BYTES


def lstm_helper_enabled() -> bool:
    """The fused LSTM cell is opt-in only: measured on v5e, XLA's fused
    scan-body beats the per-step pallas_call at every VMEM-fitting shape
    (e.g. B=128,H=256: 3.3ms vs 4.5ms/grad-step), because the custom VJP
    must spill 7 residual arrays per step that XLA instead rematerializes.
    Kept for parity with the reference's helper tier and as the base for
    future multi-step fusion; force with set_helpers_enabled(True) or
    DL4J_TPU_PALLAS=1."""
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("DL4J_TPU_PALLAS") == "1"


def lstm_sequence_enabled() -> bool:
    """The time-fused whole-sequence kernel (fused_lstm_sequence): grid over
    blocks of time steps with h/c carried in VMEM scratch — the multi-step
    fusion the cell docstring anticipates.

    DEFAULT ON for TPU (measured, v5e char-RNN bench B=64 H=512 T=256:
    3.10M chars/sec median seq-fused vs 1,489,072 scan — 2.1x; probe steps
    charrnn/charrnn_seqfused, round 5). ``DL4J_TPU_PALLAS=seq`` still
    forces it on off-TPU (interpret mode, tests); "0"/"1" select the scan
    or per-step-cell paths instead; unset means TPU-auto like
    helpers_enabled. ``set_helpers_enabled(False)`` disables it like every
    other Pallas helper — the programmatic kill-switch covers the
    default-on kernel too. Shapes the VMEM guard rejects fall back to the
    scan path at call sites (sequence_fits)."""
    if _FORCED is not None:
        return _FORCED
    env = os.environ.get("DL4J_TPU_PALLAS")
    if env == "seq":
        return True
    if env in ("0", "1"):  # explicit other-path selection
        return False
    return jax.default_backend() == "tpu"


def sequence_fits(B: int, H: int, itemsize: int) -> bool:
    from .pallas_kernels import _seq_fits  # noqa: PLC0415

    return _seq_fits(B, H, itemsize)


def lstm_cell(zx, h_prev, c_prev, RW, pF, pI, pO,
              act_name: str = "tanh", gate_name: str = "sigmoid"):
    """One LSTM step (h, c). Pallas-fused when available, XLA otherwise."""
    B, H = c_prev.shape
    if (
        lstm_helper_enabled()
        and supported_lstm_activations(act_name, gate_name)
        and _cell_fits(B, H, zx.dtype.itemsize)
    ):
        return fused_lstm_cell(zx, h_prev, c_prev, RW, pF, pI, pO,
                               act_name, gate_name)
    act = _ACT.get(act_name)
    gate = _ACT.get(gate_name)
    if act is not None and gate is not None:
        h, c, *_ = _cell_math(zx, h_prev, c_prev, RW, pF, pI, pO,
                              act[0], gate[0])
        return h, c
    raise ValueError(f"Unknown LSTM activations ({act_name}, {gate_name})")


def lrn(x, k: float = 2.0, n: int = 5, alpha: float = 1e-4, beta: float = 0.75):
    """Cross-channel LRN over the trailing axis. The variant (fused Pallas
    pass vs unrolled XLA window sum) is picked by the ``lrn`` selection
    site; legacy ``set_helpers_enabled``/``DL4J_TPU_PALLAS`` forcing wins."""
    C = x.shape[-1]
    rows = max(x.size // max(C, 1), 1)
    if select_lrn_variant(rows, C, n, x.dtype.itemsize) == "fused":
        return fused_lrn(x, k, n, alpha, beta)
    d = k + alpha * _window_sum(x * x, n)
    return x * d**-beta


def softmax_xent_rows(labels2d, preout2d):
    """Per-row softmax cross-entropy for 2D [N, C] logits/labels — fused
    Pallas pass or the numerically-identical unfused XLA form, per the
    ``softmax_xent`` selection site (losses.mcxent routes here)."""
    N, C = preout2d.shape
    if select_softmax_xent_variant(N, C, preout2d.dtype.itemsize) == "fused":
        return fused_softmax_xent(preout2d, labels2d)
    import jax.numpy as jnp  # noqa: PLC0415

    # match the fused kernel's >=f32 compute contract (_sxent_compute_dt):
    # log_softmax subtracts the row max, but in bf16/f16 the log-sum-exp and
    # the label-weighted reduction still lose mantissa. The fused kernel
    # returns per-row losses in the promoted dtype; mirror that here.
    cdt = jnp.promote_types(preout2d.dtype, jnp.float32)
    logp = jax.nn.log_softmax(preout2d.astype(cdt), axis=-1)
    return -jnp.sum(labels2d.astype(cdt) * logp, axis=-1)


def softmax_xent_rows_ids(ids1d, preout2d):
    """Per-row softmax cross-entropy of [N, C] logits against [N] integer
    class ids: the ``softmax_xent`` site's choice, as
    :func:`softmax_xent_rows` for one-hot labels, and no [N, C] label array
    on either path."""
    N, C = preout2d.shape
    if select_softmax_xent_variant(N, C, preout2d.dtype.itemsize) == "fused":
        return fused_softmax_xent_ids(preout2d, ids1d)
    import jax.numpy as jnp  # noqa: PLC0415

    cdt = jnp.promote_types(preout2d.dtype, jnp.float32)
    logp = jax.nn.log_softmax(preout2d.astype(cdt), axis=-1)
    return -jnp.take_along_axis(
        logp, ids1d.astype(jnp.int32)[:, None], axis=-1)[:, 0]


# ------------------------------------------------------ selection wrappers
# Each wrapper maps this module's legacy forcing knobs onto kernel_select's
# ``forced`` argument (exact historical semantics), then lets the roofline
# decide. All are host-side, run at trace time, and are cached/logged by
# kernel_select — same shapes always resolve identically.


def select_lstm_variant(T: int, B: int, H: int, itemsize: int,
                        acts_ok: bool, masked: bool = False) -> str:
    """'seqfused' | 'fusedcell' | 'reference' for one LSTM direction."""
    forced = None
    env = os.environ.get("DL4J_TPU_PALLAS")
    if _FORCED is False:
        forced = "reference"
    elif _FORCED is True:
        forced = "seqfused"
    elif env == "0":
        forced = "reference"
    elif env == "seq":
        forced = "seqfused"
    elif env == "1":
        forced = "fusedcell"
    ctx = {"T": int(T), "B": int(B), "H": int(H), "itemsize": int(itemsize),
           "acts_ok": bool(acts_ok), "masked": bool(masked)}
    return kernel_select.select("lstm_seq", ctx, forced=forced)


def select_attention_variant(B: int, heads: int, T: int, D: int,
                             itemsize: int, impl: str = "auto",
                             causal: bool = False, kv_heads: int = 0,
                             d_v: int = 0, d_rope: int = 0) -> str:
    """'flash' | 'xla' for a local attention call; an explicit
    ``attention_impl`` ("flash"/"xla") is the per-site escape hatch. ``D`` is
    the width of the score product; ``d_v`` (0: ``D``) the value product's
    and ``d_rope`` the part of ``D`` that is a rotary key every head shares
    (latent attention), recorded only where they say something."""
    forced = impl if impl in ("flash", "xla") else None
    if _FORCED is False:
        forced = "xla"
    ctx = {"B": int(B), "heads": int(heads), "T": int(T), "D": int(D),
           "itemsize": int(itemsize), "causal": bool(causal)}
    if kv_heads and kv_heads != heads:   # grouped-query heads only
        ctx["kv_heads"] = int(kv_heads)
    if d_rope or (d_v and d_v != D):     # products of two sizes only
        ctx.update(d_qk=int(D), d_v=int(d_v or D), d_rope=int(d_rope),
                   rope_shared_key=bool(d_rope))
    return kernel_select.select("attention", ctx, forced=forced)


def select_lrn_variant(rows: int, C: int, n: int, itemsize: int) -> str:
    forced = None
    env = os.environ.get("DL4J_TPU_PALLAS")
    if _FORCED is False:
        forced = "reference"
    elif _FORCED is True:
        forced = "fused"
    elif env == "0":
        forced = "reference"
    elif env == "1":
        forced = "fused"
    ctx = {"rows": int(rows), "C": int(C), "n": int(n),
           "itemsize": int(itemsize)}
    return kernel_select.select("lrn", ctx, forced=forced)


def select_softmax_xent_variant(N: int, C: int, itemsize: int) -> str:
    forced = "reference" if _FORCED is False else None
    ctx = {"N": int(N), "C": int(C), "itemsize": int(itemsize)}
    return kernel_select.select("softmax_xent", ctx, forced=forced)


def select_ssd_scan_variant(B: int, T: int, H: int, P: int, G: int, N: int,
                            chunk: int, itemsize: int) -> str:
    """'fused' | 'reference' for one chunked state-space scan."""
    forced = "reference" if _FORCED is False else None
    ctx = {"B": int(B), "T": int(T), "H": int(H), "P": int(P), "G": int(G),
           "N": int(N), "chunk": int(chunk), "itemsize": int(itemsize)}
    return kernel_select.select("ssd_scan", ctx, forced=forced)


def select_grouped_matmul_variant(M: int, K: int, N: int, E: int,
                                  itemsize: int) -> str:
    """'fused' | 'reference' for the grouped products of ``M`` buffered rows
    with ``E`` matrices [K, N] (and [N, K] back)."""
    forced = "reference" if _FORCED is False else None
    ctx = {"M": int(M), "K": int(K), "N": int(N), "E": int(E),
           "itemsize": int(itemsize)}
    return kernel_select.select("grouped_matmul", ctx, forced=forced)


def select_hyper_connection_variant(op: str, N: int, n: int, D: int,
                                    itemsize: int) -> str:
    """'fused' | 'reference' for one piece (``op``: 'maps', 'read', 'write')
    of a hyper-connected residual of ``n`` streams ``D`` wide over ``N``
    tokens."""
    forced = "reference" if _FORCED is False else None
    ctx = {"N": int(N), "n": int(n), "D": int(D), "op": str(op),
           "itemsize": int(itemsize)}
    return kernel_select.select("hyper_connection", ctx, forced=forced)


def select_kda_variant(B: int, T: int, H: int, K: int, V: int, chunk: int,
                       itemsize: int) -> str:
    """'fused' | 'reference' for one chunked gated delta rule (Kimi Delta
    Attention's recurrence)."""
    forced = "reference" if _FORCED is False else None
    ctx = {"B": int(B), "T": int(T), "H": int(H), "K": int(K), "V": int(V),
           "chunk": int(chunk), "itemsize": int(itemsize)}
    return kernel_select.select("kda_recurrence", ctx, forced=forced)


def select_optimizer_variant(n_elems: int, itemsize: int, updater: str,
                             n_leaves: int = 1,
                             beside: Optional[str] = None) -> str:
    """``beside``: what in the caller's program the fused variant gives way
    beside (``kernel_select``'s ``optimizer`` site says why), or None."""
    forced = "reference" if _FORCED is False else None
    ctx = {"n_elems": int(n_elems), "itemsize": int(itemsize),
           "updater": str(updater), "n_leaves": int(n_leaves)}
    if beside:
        ctx["beside_reference"] = str(beside)
    return kernel_select.select("optimizer", ctx, forced=forced)


__all__ = [
    "flash_attention",
    "fused_adam_update",
    "fused_lrn",
    "fused_lstm_cell",
    "fused_softmax_xent",
    "fused_softmax_xent_ids",
    "helpers_enabled",
    "kernel_select",
    "lrn",
    "lstm_cell",
    "lstm_helper_enabled",
    "select_attention_variant",
    "select_grouped_matmul_variant",
    "select_hyper_connection_variant",
    "select_kda_variant",
    "select_lrn_variant",
    "select_lstm_variant",
    "select_optimizer_variant",
    "select_softmax_xent_variant",
    "select_ssd_scan_variant",
    "set_helpers_enabled",
    "softmax_xent_rows",
    "softmax_xent_rows_ids",
    "supported_lstm_activations",
]
