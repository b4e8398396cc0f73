"""Pallas TPU kernels — the architectural slot of the reference's cuDNN helper
tier (SURVEY.md §2.3: CudnnConvolutionHelper etc.).

On TPU, XLA already *is* the fast path for conv/BN/pooling, so unlike the
reference there is no helper needed for those. What earns hand-written kernels
here is what XLA fuses poorly (SURVEY.md §7):

- the LSTM recurrent cell: the h_{t-1}@RW matmul + 4 gate nonlinearities +
  peephole/cell update chain, executed T times under ``lax.scan``. One fused
  VMEM kernel per step keeps every intermediate on-chip (the reference's hot
  loop, LSTMHelpers.java:159-179).
- cross-channel LRN: windowed sum-of-squares + pow, a bandwidth-bound chain
  (CudnnLocalResponseNormalizationHelper's slot).

Both ops carry a custom VJP whose backward is also a fused kernel, mirroring
the reference pattern of helpers implementing both activate and
backpropGradient. Everything falls back to pure-XLA math off-TPU or for
unsupported activations — the same "helper absent → builtin math" fallback as
ConvolutionLayer.java:69-79's reflective loading.

Kernels run compiled on TPU; ``interpret=True`` (CPU tests) exercises
identical code paths.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..analysis.annotations import jit_entry

# gate/activation catalog usable inside kernels, with value-derivatives
# (derivative expressed in terms of the *activated* value, so the backward
# kernel needs no pre-activation residuals)
def _sigmoid_kernel(x):
    """sigmoid(x) = (tanh(x/2)+1)/2 — used ONLY inside Pallas kernel bodies.

    jax.nn.sigmoid (lax.logistic) trips a Mosaic bf16 lowering bug inside
    Pallas TPU kernels ('vector.broadcast' f32 scalar into a bf16 vector,
    verification error); the tanh form lowers cleanly at every dtype and is
    mathematically identical. The XLA scan path keeps lax.logistic: the
    tanh form underflows to exactly 0/1 for saturated gates where
    lax.logistic preserves tiny values — a relative-precision loss the
    float64 finite-difference gradchecks can resolve."""
    return 0.5 * (jnp.tanh(0.5 * x) + 1.0)


_ACT = {
    "tanh": (jnp.tanh, lambda y: 1.0 - y * y),
    "sigmoid": (jax.nn.sigmoid, lambda y: y * (1.0 - y)),
    "hardsigmoid": (
        lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0),
        lambda y: jnp.where((y > 0.0) & (y < 1.0), 0.2, 0.0),
    ),
    "relu": (jax.nn.relu, lambda y: (y > 0.0).astype(y.dtype)),
    "identity": (lambda x: x, lambda y: jnp.ones_like(y)),
}

# kernel-side table: identical except for the Mosaic-safe sigmoid
_ACT_KERNEL = dict(_ACT)
_ACT_KERNEL["sigmoid"] = (_sigmoid_kernel, _ACT["sigmoid"][1])


def _acc_dtype(dt):
    """Matmul accumulator dtype: ≥f32 always (Mosaic rejects a bf16 acc —
    'Expected matmul acc to be 32-bit'), but never BELOW the input dtype
    (f32 accumulation under the float64 gradcheck suites would truncate)."""
    return jnp.float32 if jnp.dtype(dt).itemsize < 4 else dt


def _rows(*vectors):
    """Peephole vectors enter and leave the kernels as (1, H) rows: Mosaic
    lays 1-D bf16 vectors out in 256-element packed tiles, and an (H,)
    operand with H=128 fails to lower ("offset not aligned to sublanes" —
    seen on the v5e at bf16 B=16 H=128 while H=512 compiled). A 2-D row
    broadcasts against [B, H] in any dtype."""
    return tuple(v.reshape(1, -1) for v in vectors)


def supported_lstm_activations(act: str, gate: str) -> bool:
    return act in _ACT and gate in _ACT


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# fused LSTM cell
# ---------------------------------------------------------------------------


def _cell_math(zx, h_prev, c_prev, RW, pF, pI, pO, act, gate):
    """Shared gate math (column order [a, f, o, i] — LSTMHelpers parity)."""
    H = c_prev.shape[-1]
    # Mosaic requires a 32-bit matmul accumulator (bf16 acc is rejected at
    # verification); accumulate f32 and cast back to the compute dtype
    z = zx + jnp.dot(h_prev, RW,
                     preferred_element_type=_acc_dtype(zx.dtype)).astype(zx.dtype)
    a = act(z[..., :H])
    f = gate(z[..., H : 2 * H] + c_prev * pF)
    i = gate(z[..., 3 * H :] + c_prev * pI)
    c = f * c_prev + i * a
    o = gate(z[..., 2 * H : 3 * H] + c * pO)
    cact = act(c)
    h = o * cact
    return h, c, a, f, o, i, cact


@jit_entry
def _fwd_kernel(act, gate, zx_ref, h_ref, c_ref, rw_ref, pf_ref, pi_ref,
                po_ref, h_out, c_out, a_out, f_out, o_out, i_out, cact_out):
    h, c, a, f, o, i, cact = _cell_math(
        zx_ref[:], h_ref[:], c_ref[:], rw_ref[:],
        pf_ref[:], pi_ref[:], po_ref[:], act, gate,
    )
    h_out[:], c_out[:] = h, c
    a_out[:], f_out[:], o_out[:], i_out[:], cact_out[:] = a, f, o, i, cact


@jit_entry
def _bwd_kernel(dact, dgate, a_ref, f_ref, o_ref, i_ref, cact_ref, cprev_ref,
                c_ref, hprev_ref, rw_ref, pf_ref, pi_ref, po_ref,
                dh_ref, dc_ref,
                dzx_out, dhprev_out, dcprev_out, drw_out, dpf_out, dpi_out,
                dpo_out):
    a, f, o, i = a_ref[:], f_ref[:], o_ref[:], i_ref[:]
    cact, c_prev, c = cact_ref[:], cprev_ref[:], c_ref[:]
    dh, dc = dh_ref[:], dc_ref[:]
    pF, pI, pO = pf_ref[:], pi_ref[:], po_ref[:]

    do = dh * cact * dgate(o)
    dc_tot = dc + dh * o * dact(cact) + do * pO
    df = dc_tot * c_prev * dgate(f)
    di = dc_tot * a * dgate(i)
    da = dc_tot * i * dact(a)
    dzx = jnp.concatenate([da, df, do, di], axis=-1)
    dcprev_out[:] = dc_tot * f + df * pF + di * pI
    dzx_out[:] = dzx
    dhprev_out[:] = jnp.dot(
        dzx, rw_ref[:].T, preferred_element_type=_acc_dtype(dzx.dtype)
    ).astype(dzx.dtype)
    drw_out[:] = jnp.dot(
        hprev_ref[:].T, dzx, preferred_element_type=_acc_dtype(dzx.dtype)
    ).astype(dzx.dtype)
    dpf_out[:] = jnp.sum(df * c_prev, axis=0, keepdims=True)
    dpi_out[:] = jnp.sum(di * c_prev, axis=0, keepdims=True)
    dpo_out[:] = jnp.sum(do * c, axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def fused_lstm_cell(zx, h_prev, c_prev, RW, pF, pI, pO,
                    act_name: str = "tanh", gate_name: str = "sigmoid"):
    """One LSTM step, fused in VMEM. Returns (h, c).

    ``zx`` is the precomputed input projection x_t@W + b for this step
    ([B, 4H]); the kernel performs the recurrent matmul and every gate op
    without round-tripping intermediates through HBM.
    """
    h, c, *_ = _cell_fwd_impl(zx, h_prev, c_prev, RW, pF, pI, pO,
                              act_name, gate_name)
    return h, c


def _cell_fwd_impl(zx, h_prev, c_prev, RW, pF, pI, pO, act_name, gate_name):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    act, _ = _ACT_KERNEL[act_name]
    gate, _ = _ACT_KERNEL[gate_name]
    B, H = c_prev.shape
    dt = zx.dtype
    shapes = [jax.ShapeDtypeStruct((B, H), dt)] * 7
    kernel = functools.partial(_fwd_kernel, act, gate)
    return pl.pallas_call(
        kernel,
        out_shape=tuple(shapes),
        interpret=_interpret(),
        name="lstm_cell_fwd",
    )(zx, h_prev, c_prev, RW, *_rows(pF, pI, pO))


def _cell_fwd(zx, h_prev, c_prev, RW, pF, pI, pO, act_name, gate_name):
    h, c, a, f, o, i, cact = _cell_fwd_impl(
        zx, h_prev, c_prev, RW, pF, pI, pO, act_name, gate_name
    )
    residuals = (a, f, o, i, cact, c_prev, c, h_prev, RW, pF, pI, pO)
    return (h, c), residuals


def _cell_bwd(act_name, gate_name, residuals, grads):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    a, f, o, i, cact, c_prev, c, h_prev, RW, pF, pI, pO = residuals
    dh, dc = grads
    _, dact = _ACT_KERNEL[act_name]
    _, dgate = _ACT_KERNEL[gate_name]
    B, H = c_prev.shape
    dt = dh.dtype
    out_shape = (
        jax.ShapeDtypeStruct((B, 4 * H), dt),   # dzx
        jax.ShapeDtypeStruct((B, H), dt),       # dh_prev
        jax.ShapeDtypeStruct((B, H), dt),       # dc_prev
        jax.ShapeDtypeStruct((H, 4 * H), dt),   # dRW
        jax.ShapeDtypeStruct((1, H), dt),       # dpF
        jax.ShapeDtypeStruct((1, H), dt),       # dpI
        jax.ShapeDtypeStruct((1, H), dt),       # dpO
    )
    kernel = functools.partial(_bwd_kernel, dact, dgate)
    *grads, dpF, dpI, dpO = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        interpret=_interpret(),
        name="lstm_cell_bwd",
    )(a, f, o, i, cact, c_prev, c, h_prev, RW, *_rows(pF, pI, pO), dh, dc)
    return (*grads, dpF[0], dpI[0], dpO[0])


fused_lstm_cell.defvjp(_cell_fwd, _cell_bwd)


# ---------------------------------------------------------------------------
# LRN
# ---------------------------------------------------------------------------


def _window_sum(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """sum over channel window W(c) = [c - n//2, c + n - 1 - n//2]."""
    half = n // 2
    C = x.shape[-1]
    padded = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    acc = jnp.zeros_like(x)
    for j in range(n):
        acc = acc + jax.lax.slice_in_dim(padded, j, j + C, axis=-1)
    return acc


def _window_sum_adjoint(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """Adjoint of _window_sum: channel c receives from every j with
    c ∈ W(j), i.e. the window offsets flip sign. Identical to _window_sum
    for odd n (symmetric window); shifted by one for even n."""
    lo = n - 1 - n // 2  # pad so offset range becomes [-(n-1-half), half]
    hi = n // 2
    C = x.shape[-1]
    padded = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(lo, hi)])
    acc = jnp.zeros_like(x)
    for j in range(n):
        acc = acc + jax.lax.slice_in_dim(padded, j, j + C, axis=-1)
    return acc


@jit_entry
def _lrn_fwd_kernel(k, n, alpha, beta, x_ref, y_ref, d_ref):
    x = x_ref[:]
    d = k + alpha * _window_sum(x * x, n)
    d_ref[:] = d
    y_ref[:] = x * d**-beta


@jit_entry
def _lrn_bwd_kernel(k, n, alpha, beta, x_ref, d_ref, g_ref, dx_ref):
    x, d, g = x_ref[:], d_ref[:], g_ref[:]
    # dx_c = g_c d_c^-b - 2ab x_c * Σ_{j: c∈W(j)} g_j x_j d_j^{-b-1}
    dx_ref[:] = g * d**-beta - 2.0 * alpha * beta * x * _window_sum_adjoint(
        g * x * d ** (-beta - 1.0), n
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def fused_lrn(x, k: float = 2.0, n: int = 5, alpha: float = 1e-4,
              beta: float = 0.75):
    """Cross-channel LRN on the trailing axis, one fused VMEM pass."""
    y, _ = _lrn_fwd_impl(x, k, n, alpha, beta)
    return y


def _as2d(x):
    return x.reshape(-1, x.shape[-1])


# rows per grid step: keeps each VMEM block ≲1MB for typical channel counts
_LRN_TILE_ROWS = 1024


def _lrn_specs(rows: int, C: int, n_arrays: int):
    """Row-tiled grid so arbitrarily large activations never exceed VMEM.
    The channel (window) axis stays whole inside each block."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    tile = min(_LRN_TILE_ROWS, rows)
    grid = (pl.cdiv(rows, tile),)
    spec = pl.BlockSpec((tile, C), lambda i: (i, 0))
    return grid, [spec] * n_arrays, spec


def _lrn_fwd_impl(x, k, n, alpha, beta):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    x2 = _as2d(x)
    grid, in_specs, out_spec = _lrn_specs(x2.shape[0], x2.shape[1], 1)
    kernel = functools.partial(_lrn_fwd_kernel, k, n, alpha, beta)
    y, d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct(x2.shape, x2.dtype),) * 2,
        interpret=_interpret(),
        name="lrn_fwd",
    )(x2)
    return y.reshape(x.shape), d


def _lrn_fwd(x, k, n, alpha, beta):
    y, d = _lrn_fwd_impl(x, k, n, alpha, beta)
    return y, (x, d)


def _lrn_bwd(k, n, alpha, beta, residuals, g):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    x, d = residuals
    x2, g2 = _as2d(x), _as2d(g)
    grid, in_specs, out_spec = _lrn_specs(x2.shape[0], x2.shape[1], 3)
    kernel = functools.partial(_lrn_bwd_kernel, k, n, alpha, beta)
    dx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=_interpret(),
        name="lrn_bwd",
    )(x2, d, g2)
    return (dx.reshape(x.shape),)


fused_lrn.defvjp(_lrn_fwd, _lrn_bwd)


# ---------------------------------------------------------------------------
# time-fused LSTM sequence — the cuDNN "fused LSTM" analog
# ---------------------------------------------------------------------------
#
# The per-step fused cell above loses to XLA's scan on TPU because its custom
# VJP spills 7 residual arrays to HBM every step. These kernels fuse the WHOLE
# time loop instead, a block of ``Tc`` time steps a grid step: the grid
# ``(T // Tc,)`` executes sequentially on TPU, every streamed operand is a
# ``(Tc, B, ·)`` block, the recurrence runs as an unrolled loop over the block,
# h/c (dh/dc) live in VMEM scratch across grid steps, RW stays VMEM-resident,
# and only the 5 residual tensors cuDNN also reserves (gate activations + cell
# state) stream out. What the recurrence does not order is done once a block:
# the backward's ``dRW += h_prev^T @ dzx`` contracts over the block's ``Tc*B``
# rows (at B=64 one step half fills the MXU's contraction depth, and the
# [H, 4H] f32 accumulator would go through VMEM every step), the peephole sums
# are reduced over rows once a block, and the constant-index outputs (h_T/c_T;
# dh0/dc0/dRW/dp*) are written on the last grid step only. h_{t-1}/c_{t-1} are
# the block's own ys/c rows shifted by one; the row before the block comes from
# a one-step block of ys/c at ``t0 - 1`` (``h0``/``c0`` at ``t0 == 0``).
# ``Tc == 1`` is the one-step-a-grid-step kernel this grew from.
#
# VMEM (``_seq_footprint`` reckons the backward, the larger of the two): the
# streamed blocks are double-buffered — 2 x Tc x (7 BH + 4 BH) elements of dy,
# a, f, o, i, c, ys in and dzx out — the constant-index operands (RW, dRW, the
# [B, H] states) are single-buffered (``pl.Buffered(1)``), and the f32 [H, 4H]
# accumulator plus the block matmul's f32 product sit beside them: 26 MiB at
# Tc=8 for B=64 H=512 bf16 (43 MiB at f32), over the compiler's 16 MiB
# default, so every call states its ``vmem_limit_bytes`` from the same
# reckoning. The budget is the smaller of ``_SEQ_VMEM_BUDGET_BYTES`` and half
# the platform's VMEM (128 MiB a core on the v5e): ``_seq_time_block`` takes the
# largest block under it, ``_seq_fits`` (kernel selection's hard check) gives
# way to the scan where not even one step fits.

_SEQ_VMEM_BUDGET_BYTES = 64 * 1024 * 1024
# Tc*B >= 128 fills the MXU's contraction depth; beyond that a longer block
# only spreads the grid step's fixed cost thinner. Measured on the v5e (B=64
# T=256 H=512 bf16, PERF.md PR 27): the backward reads 0.76 ms an event at a
# block of 1, 0.52 at 2, 0.50 at 4, 0.49 at 8, 0.50 at 16 and 0.62 at 32, and
# the unrolled body's compile time doubles with the block
_SEQ_MAX_TIME_BLOCK = 8
_SEQ_MIN_VMEM_LIMIT_BYTES = 16 * 1024 * 1024  # the compiler's own default


def _seq_vmem_budget() -> int:
    budget = _SEQ_VMEM_BUDGET_BYTES
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

        budget = min(budget, pltpu.get_tpu_info().vmem_capacity_bytes // 2)
    return budget


def _seq_footprint(Tc: int, B: int, H: int, itemsize: int) -> int:
    """VMEM bytes of the BACKWARD kernel at a block of ``Tc`` steps; the
    forward (10 streamed [B, H] a step, no accumulator) is strictly smaller.
    An upper bound: it counts every value the body names as if VMEM held it,
    and the v5e's compiler takes this kernel under 0.6 of it (B=64 H=512 bf16,
    a block of 8: accepted at a 15.8 MiB limit, refused at 13.2)."""
    row, mat = B * H, H * 4 * H
    streamed = (Tc * 11 * row          # dy a f o i c ys in, dzx [B, 4H] out
                + Tc * B * 128         # mask, one lane tile a row
                + 2 * row) * itemsize  # ys/c at t0 - 1
    constant = (2 * mat + 6 * row) * itemsize   # RW, dRW; dhT dcT h0 c0 dh0 dc0
    scratch = mat * 4 + 2 * row * itemsize      # f32 dRW accumulator; dh/dc
    # values the body holds: the block matmul's f32 product, its [Tc*B, H]
    # lhs, a step's f32 gate block and matmul results, three peephole partials
    working = mat * 4 + Tc * row * itemsize + (3 * 4 * row + 3 * row) * 4
    return 2 * streamed + constant + scratch + working


def _seq_fits(B: int, H: int, itemsize: int) -> bool:
    return _seq_footprint(1, B, H, itemsize) <= _seq_vmem_budget()


def _seq_time_block(T: int, B: int, H: int, itemsize: int) -> int:
    """Time steps a grid step of the seq kernels: the largest divisor of ``T``
    not above ``_SEQ_MAX_TIME_BLOCK`` whose footprint fits the VMEM budget; 1
    where none does, or where ``B`` rows are not whole sublane tiles (the block
    matmul collapses ``(Tc, B)`` into rows, free only on tile boundaries)."""
    if B % max(32 // itemsize, 1):
        return 1
    budget = _seq_vmem_budget()
    for tc in range(min(T, _SEQ_MAX_TIME_BLOCK), 1, -1):
        if T % tc == 0 and _seq_footprint(tc, B, H, itemsize) <= budget:
            return tc
    return 1


def _seq_compiler_params(Tc, B, H, itemsize):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=max(_seq_footprint(Tc, B, H, itemsize),
                             _SEQ_MIN_VMEM_LIMIT_BYTES))


def _seq_specs(Tc, B):
    """BlockSpec makers shared by the seq kernels: a ``(Tc, B, w)`` block of a
    streamed operand under ``index_map``, and a whole constant-index operand
    (fetched once, so single-buffered)."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    def block(w, index_map, steps=Tc):
        return pl.BlockSpec((steps, B, w), index_map)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda k: (0,) * len(shape),
                            pipeline_mode=pl.Buffered(1))

    return block, whole


@jit_entry
def _seq_fwd_kernel(act, gate, Tc, masked, residuals, *refs):
    """Forward of ``Tc`` steps a grid step. ``masked`` adds the [Tc, B, 1]
    mask operand (masked steps hold h/c); ``residuals`` adds the five gate
    outputs the backward reads (the lean primal emits ys/hT/cT only)."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    it = iter(refs)
    zx_ref = next(it)
    m_ref = next(it) if masked else None  # static — dl4jtpu: ignore[DT104]
    h0_ref, c0_ref, rw_ref, pf_ref, pi_ref, po_ref, y_out = (
        next(it) for _ in range(7))
    res_out = [next(it) for _ in range(5 if residuals else 0)]  # a f o i c
    hT_out, cT_out, h_scr, c_scr = it
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    pF, pI, pO = pf_ref[:], pi_ref[:], po_ref[:]
    h, c = h_scr[:], c_scr[:]
    for j in range(Tc):
        h_new, c_new, a, f, o, i, _cact = _cell_math(
            zx_ref[j], h, c, rw_ref[:], pF, pI, pO, act, gate)
        if masked:  # static via partial — dl4jtpu: ignore[DT104]
            m = m_ref[j]
            h_new = m * h_new + (1.0 - m) * h
            c_new = m * c_new + (1.0 - m) * c
        y_out[j] = h_new
        for ref, v in zip(res_out, (a, f, o, i, c_new)):
            ref[j] = v
        h, c = h_new, c_new
    h_scr[:], c_scr[:] = h, c

    @pl.when(k == pl.num_programs(0) - 1)
    def _last():
        hT_out[:], cT_out[:] = h, c


def _seq_fwd_call(zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name,
                  residuals: bool):
    """ys, [a, f, o, i, c,] hT, cT of the whole sequence in one kernel."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    act, _ = _ACT_KERNEL[act_name]
    gate, _ = _ACT_KERNEL[gate_name]
    T, B, H4 = zx.shape
    H = H4 // 4
    dt = zx.dtype
    Tc = _seq_time_block(T, B, H, dt.itemsize)
    block, whole = _seq_specs(Tc, B)
    fwd = lambda k: (k, 0, 0)  # noqa: E731
    masked = mask is not None
    n_seq = 6 if residuals else 1   # ys, then a f o i c
    return pl.pallas_call(
        functools.partial(_seq_fwd_kernel, act, gate, Tc, masked, residuals),
        grid=(T // Tc,),
        in_specs=[block(H4, fwd), *([block(1, fwd)] if masked else []),
                  whole(B, H), whole(B, H), whole(H, H4),
                  whole(1, H), whole(1, H), whole(1, H)],
        out_specs=(*[block(H, fwd)] * n_seq, whole(B, H), whole(B, H)),
        out_shape=(*[jax.ShapeDtypeStruct((T, B, H), dt)] * n_seq,
                   jax.ShapeDtypeStruct((B, H), dt),     # hT
                   jax.ShapeDtypeStruct((B, H), dt)),    # cT
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)],
        compiler_params=_seq_compiler_params(Tc, B, H, dt.itemsize),
        interpret=_interpret(),
        name=("lstm_seq_lean" if not residuals
              else "lstm_seq_masked_fwd" if masked else "lstm_seq_fwd"),
    )(zx, *([mask.astype(dt)] if masked else []), h0, c0, RW,
      *_rows(pF, pI, pO))


@jit_entry
def _seq_bwd_kernel(act, dact, dgate, Tc, masked, *refs):
    """Backward of ``Tc`` steps a grid step, blocks in reverse time order
    (grid step k covers t0 = (n-1-k)*Tc .. t0+Tc-1, walked last step first).
    Masked steps pass dh/dc straight through to t-1."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    it = iter(refs)
    dy_ref, dhT_ref, dcT_ref = (next(it) for _ in range(3))
    m_ref = next(it) if masked else None  # static — dl4jtpu: ignore[DT104]
    (a_ref, f_ref, o_ref, i_ref, c_ref, y_ref, cb_ref, hb_ref, rw_ref,
     pf_ref, pi_ref, po_ref, h0_ref, c0_ref,
     dzx_out, dh0_out, dc0_out, drw_out, dpf_out, dpi_out, dpo_out,
     dh_scr, dc_scr, drw_scr, dpf_scr, dpi_scr, dpo_scr) = it
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]
        drw_scr[:] = jnp.zeros(drw_scr.shape, drw_scr.dtype)
        dpf_scr[:] = jnp.zeros(dpf_scr.shape, dpf_scr.dtype)
        dpi_scr[:] = jnp.zeros(dpi_scr.shape, dpi_scr.dtype)
        dpo_scr[:] = jnp.zeros(dpo_scr.shape, dpo_scr.dtype)

    first = k == pl.num_programs(0) - 1   # t0 == 0: the initial state
    c_before = jnp.where(first, c0_ref[:], cb_ref[0])
    h_before = jnp.where(first, h0_ref[:], hb_ref[0])
    pF, pI, pO = pf_ref[:], pi_ref[:], po_ref[:]
    B, H = c_before.shape
    f32 = drw_scr.dtype
    dh_next, dc_next = dh_scr[:], dc_scr[:]
    dpf = dpi = dpo = jnp.zeros((B, H), f32)
    for j in reversed(range(Tc)):
        a, f, o, i = a_ref[j], f_ref[j], o_ref[j], i_ref[j]
        c_prev = c_ref[j - 1] if j else c_before
        # c_t (pre-mask) recomputed from the gates (VPU-cheap), not stored
        c = f * c_prev + i * a
        cact = act(c)
        dh = dh_t = dy_ref[j] + dh_next
        dc = dc_t = dc_next
        if masked:  # static via partial — dl4jtpu: ignore[DT104]
            m = m_ref[j]
            dh, dc = m * dh_t, m * dc_t   # gradient into the cell outputs
        do = dh * cact * dgate(o)
        dc_tot = dc + dh * o * dact(cact) + do * pO
        df = dc_tot * c_prev * dgate(f)
        di = dc_tot * a * dgate(i)
        da = dc_tot * i * dact(a)
        dzx = jnp.concatenate([da, df, do, di], axis=-1)
        dzx_out[j] = dzx
        # the one matmul on the sequential path
        dh_next = jnp.dot(
            dzx, rw_ref[:].T, preferred_element_type=_acc_dtype(dzx.dtype)
        ).astype(dzx.dtype)
        dc_next = dc_tot * f + df * pF + di * pI
        if masked:  # static via partial — dl4jtpu: ignore[DT104]
            dh_next = dh_next + (1.0 - m) * dh_t
            dc_next = dc_next + (1.0 - m) * dc_t
        dpf += (df * c_prev).astype(f32)
        dpi += (di * c_prev).astype(f32)
        dpo += (do * c).astype(f32)
    dh_scr[:], dc_scr[:] = dh_next, dc_next

    # once a block: nothing at t-1 reads these. dRW contracts over Tc*B rows
    h_prev = h_before
    if Tc > 1:  # static via partial — dl4jtpu: ignore[DT104]
        h_prev = jnp.concatenate(
            [h_before, y_ref[:Tc - 1].reshape((Tc - 1) * B, H)], axis=0)
    drw_scr[:] += jnp.dot(h_prev.T, dzx_out[:].reshape(Tc * B, 4 * H),
                          preferred_element_type=f32)
    dpf_scr[:] += jnp.sum(dpf, axis=0, keepdims=True)
    dpi_scr[:] += jnp.sum(dpi, axis=0, keepdims=True)
    dpo_scr[:] += jnp.sum(dpo, axis=0, keepdims=True)

    @pl.when(first)
    def _last():
        dt = dzx_out.dtype
        dh0_out[:] = dh_next
        dc0_out[:] = dc_next
        drw_out[:] = drw_scr[:].astype(dt)
        dpf_out[:] = dpf_scr[:].astype(dt)
        dpi_out[:] = dpi_scr[:].astype(dt)
        dpo_out[:] = dpo_scr[:].astype(dt)


def _seq_bwd_call(act_name, gate_name, mask, residuals, grads):
    """dzx, dh0, dc0, dRW, dpF, dpI, dpO of the whole sequence in one kernel."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    ys, a, f, o, i, c, h0, c0, RW, pF, pI, pO = residuals
    dys, dhT, dcT = grads
    act, dact = _ACT_KERNEL[act_name]
    _, dgate = _ACT_KERNEL[gate_name]
    T, B, H = ys.shape
    dt = ys.dtype
    Tc = _seq_time_block(T, B, H, dt.itemsize)
    n = T // Tc
    block, whole = _seq_specs(Tc, B)
    rev = lambda k: (n - 1 - k, 0, 0)   # noqa: E731
    # the step before the block: element t0 - 1 of a one-step block, clamped
    # at 0 (t0 == 0 substitutes the initial state inside the kernel)
    before = lambda k: (jnp.maximum((n - 1 - k) * Tc - 1, 0), 0, 0)  # noqa: E731
    masked = mask is not None
    dzx, dh0, dc0, dRW, dpF, dpI, dpO = pl.pallas_call(
        functools.partial(_seq_bwd_kernel, act, dact, dgate, Tc, masked),
        grid=(n,),
        in_specs=[
            block(H, rev), whole(B, H), whole(B, H),       # dys dhT dcT
            *([block(1, rev)] if masked else []),
            *[block(H, rev)] * 6,                          # a f o i c ys
            block(H, before, 1), block(H, before, 1),      # c, ys at t0 - 1
            whole(H, 4 * H), whole(1, H), whole(1, H), whole(1, H),
            whole(B, H), whole(B, H),                      # h0 c0
        ],
        out_specs=(block(4 * H, rev), whole(B, H), whole(B, H),
                   whole(H, 4 * H), whole(1, H), whole(1, H), whole(1, H)),
        out_shape=(
            jax.ShapeDtypeStruct((T, B, 4 * H), dt),  # dzx
            jax.ShapeDtypeStruct((B, H), dt),         # dh0
            jax.ShapeDtypeStruct((B, H), dt),         # dc0
            jax.ShapeDtypeStruct((H, 4 * H), dt),     # dRW
            *[jax.ShapeDtypeStruct((1, H), dt)] * 3,  # dpF dpI dpO
        ),
        scratch_shapes=[
            pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt),
            pltpu.VMEM((H, 4 * H), jnp.float32),
            *[pltpu.VMEM((1, H), jnp.float32)] * 3,
        ],
        compiler_params=_seq_compiler_params(Tc, B, H, dt.itemsize),
        interpret=_interpret(),
        name="lstm_seq_masked_bwd" if masked else "lstm_seq_bwd",
    )(dys, dhT, dcT, *([mask.astype(dt)] if masked else []),
      a, f, o, i, c, ys, c, ys, RW, *_rows(pF, pI, pO), h0, c0)
    return dzx, dh0, dc0, dRW, dpF[0], dpI[0], dpO[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def fused_lstm_sequence(zx, h0, c0, RW, pF, pI, pO,
                        act_name: str = "tanh", gate_name: str = "sigmoid"):
    """Whole-sequence fused LSTM: ``zx`` [T, B, 4H] (precomputed x@W + b),
    returns (ys [T, B, H], h_T, c_T). Unmasked, forward-direction.

    The primal (inference) path runs the LEAN kernel that emits only
    ys/hT/cT; the five gate residuals stream to HBM only under jax.grad
    (the VJP's forward rule) where the backward actually consumes them."""
    return _seq_fwd_call(zx, None, h0, c0, RW, pF, pI, pO,
                         act_name, gate_name, residuals=False)


def _seq_fwd(zx, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    ys, a, f, o, i, c, hT, cT = _seq_fwd_call(
        zx, None, h0, c0, RW, pF, pI, pO, act_name, gate_name, residuals=True)
    return (ys, hT, cT), (ys, a, f, o, i, c, h0, c0, RW, pF, pI, pO)


def _seq_bwd(act_name, gate_name, residuals, grads):
    return _seq_bwd_call(act_name, gate_name, None, residuals, grads)


fused_lstm_sequence.defvjp(_seq_fwd, _seq_bwd)


# -- masked variant: padded/bucketed sequences ride the fused loop too ------
#
# Masked steps carry h/c through unchanged (h_t = m·h̃ + (1−m)·h_{t-1} — the
# scan path's semantics exactly): the same two kernel bodies under their
# static ``masked`` flag. The backward recomputes the pre-mask cell state
# c̃ = f·c_prev + i·a from the stored gates, so the residual set stays the
# same five tensors plus the [T, B, 1] mask.


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def fused_lstm_sequence_masked(zx, mask, h0, c0, RW, pF, pI, pO,
                               act_name: str = "tanh",
                               gate_name: str = "sigmoid"):
    """Masked whole-sequence fused LSTM: ``mask`` [T, B, 1]; masked steps
    hold h/c (scan-path semantics). Returns (ys, h_T, c_T). The primal runs
    the lean (no-residual) kernel; see fused_lstm_sequence."""
    return _seq_fwd_call(zx, mask, h0, c0, RW, pF, pI, pO,
                         act_name, gate_name, residuals=False)


def _seq_masked_fwd(zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    ys, a, f, o, i, c, hT, cT = _seq_fwd_call(
        zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name, residuals=True)
    return (ys, hT, cT), (mask, (ys, a, f, o, i, c, h0, c0, RW, pF, pI, pO))


def _seq_masked_bwd(act_name, gate_name, residuals, grads):
    mask, residuals = residuals
    dzx, *rest = _seq_bwd_call(act_name, gate_name, mask, residuals, grads)
    return (dzx, None, *rest)


fused_lstm_sequence_masked.defvjp(_seq_masked_fwd, _seq_masked_bwd)


# ---------------------------------------------------------------------------
# fused softmax + cross-entropy — the loss-head hot path
# ---------------------------------------------------------------------------
#
# The reference fuses LossMCXENT with softmax numerically (losses.py keeps
# that); this kernel fuses it PHYSICALLY: one VMEM pass computes the per-row
# loss from logits+labels without materializing max/exp/sum/logp between HBM
# round trips, and the backward rebuilds the softmax in-tile to emit
# d(logits) and d(labels) in a single fused pass. Selected by the
# "softmax_xent" kernel_select site where the roofline says the loss head is
# bandwidth-bound (it always is — pure elementwise/reduce chains).

_SXENT_TILE_ROWS = 1024
# VMEM the backward's pipelined blocks may take: 2 in + 2 out (tile, C)
# blocks, double-buffered, modeled at f32 with C padded to the 128-lane
# tile — half of the 16 MiB default scoped limit, the rest is left to the
# in-kernel f32 temporaries
_SXENT_BLOCK_BUDGET_BYTES = 8 * 1024 * 1024


def _sxent_tile(rows: int, C: int) -> int:
    """Rows per grid step, sized from C so a wide head (ImageNet's 1000
    classes) fits VMEM where a fixed 1024-row tile would need ~32 MB. A
    tile below ``rows`` stays a multiple of 16 (bf16 sublane packing)."""
    lanes = -(-C // 128) * 128
    fit = _SXENT_BLOCK_BUDGET_BYTES // (8 * 4 * lanes)
    tile = max(16, min(_SXENT_TILE_ROWS, fit // 16 * 16))
    return min(tile, rows)


def _sxent_specs(rows: int, C: int):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    tile = _sxent_tile(rows, C)
    grid = (pl.cdiv(rows, tile),)
    mat = pl.BlockSpec((tile, C), lambda i: (i, 0))
    col = pl.BlockSpec((tile, 1), lambda i: (i, 0))
    return grid, mat, col


def _sxent_compute_dt(dt):
    # bf16/f16 logits get f32 softmax math (exp/log at data precision loses
    # the loss's small differences); f32/f64 stay at their own precision
    return jnp.promote_types(dt, jnp.float32)


@jit_entry
def _sxent_fwd_kernel(x_ref, l_ref, loss_ref):
    cdt = _sxent_compute_dt(x_ref.dtype)
    x = x_ref[:].astype(cdt)
    lab = l_ref[:].astype(cdt)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
    loss_ref[:] = (-jnp.sum(lab * (x - lse), axis=-1, keepdims=True)
                   ).astype(loss_ref.dtype)


@jit_entry
def _sxent_bwd_kernel(x_ref, l_ref, g_ref, dx_ref, dl_ref):
    cdt = _sxent_compute_dt(x_ref.dtype)
    x = x_ref[:].astype(cdt)
    lab = l_ref[:].astype(cdt)
    g = g_ref[:].astype(cdt)  # [R, 1]
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    s = jnp.sum(ex, axis=-1, keepdims=True)
    p = ex / s
    logp = x - (jnp.log(s) + m)
    # d/dx_j of -Σ_c lab_c·logp_c = p_j·Σ_c lab_c − lab_j  (general labels,
    # reduces to p − lab for one-hot)
    lab_sum = jnp.sum(lab, axis=-1, keepdims=True)
    dx_ref[:] = ((p * lab_sum - lab) * g).astype(dx_ref.dtype)
    dl_ref[:] = (-logp * g).astype(dl_ref.dtype)


@jax.custom_vjp
def fused_softmax_xent(preout, labels):
    """Per-row -Σ labels·log_softmax(preout) for 2D [N, C] inputs, one fused
    VMEM pass. Returns [N] row losses (mask/mean stay at the caller, exactly
    like losses._apply_mask over the unfused form)."""
    return _sxent_fwd_impl(preout, labels)


def _sxent_fwd_impl(preout, labels):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, C = preout.shape
    grid, mat, col = _sxent_specs(N, C)
    out = pl.pallas_call(
        _sxent_fwd_kernel,
        grid=grid,
        in_specs=[mat, mat],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((N, 1), _sxent_compute_dt(preout.dtype)),
        interpret=_interpret(),
        name="softmax_xent_fwd",
    )(preout, labels)
    return out[:, 0]


def _sxent_fwd(preout, labels):
    return _sxent_fwd_impl(preout, labels), (preout, labels)


def _sxent_bwd(residuals, g):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    preout, labels = residuals
    N, C = preout.shape
    grid, mat, col = _sxent_specs(N, C)
    g2 = g.reshape(N, 1).astype(_sxent_compute_dt(preout.dtype))
    dx, dl = pl.pallas_call(
        _sxent_bwd_kernel,
        grid=grid,
        in_specs=[mat, mat, col],
        out_specs=(mat, mat),
        out_shape=(jax.ShapeDtypeStruct((N, C), preout.dtype),
                   jax.ShapeDtypeStruct((N, C), labels.dtype)),
        interpret=_interpret(),
        name="softmax_xent_bwd",
    )(preout, labels, g2)
    return dx, dl


fused_softmax_xent.defvjp(_sxent_fwd, _sxent_bwd)


# -- integer labels: the same pass without a one-hot [N, C] label array ------
#
# A language model's head has 16k-260k classes; its labels are class ids. The
# kernels below are the two above with the label block replaced by an [R, 1]
# column of ids, compared against a lane iota in the tile. ``_sxent_tile``
# sizes their blocks as it does the one-hot pair's (fewer [R, C] blocks are
# live here, so its reckoning is an upper bound).


@jit_entry
def _sxent_ids_fwd_kernel(x_ref, id_ref, loss_ref):
    cdt = _sxent_compute_dt(x_ref.dtype)
    x = x_ref[:].astype(cdt)
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == id_ref[:]
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True)) + m
    picked = jnp.sum(jnp.where(hit, x, 0.0), axis=-1, keepdims=True)
    loss_ref[:] = (lse - picked).astype(loss_ref.dtype)


@jit_entry
def _sxent_ids_bwd_kernel(x_ref, id_ref, g_ref, dx_ref):
    cdt = _sxent_compute_dt(x_ref.dtype)
    x = x_ref[:].astype(cdt)
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == id_ref[:]
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    p = ex / jnp.sum(ex, axis=-1, keepdims=True)
    dx_ref[:] = ((p - jnp.where(hit, 1.0, 0.0)) * g_ref[:].astype(cdt)
                 ).astype(dx_ref.dtype)


@jax.custom_vjp
def fused_softmax_xent_ids(preout, ids):
    """Per-row ``-log_softmax(preout)[ids]`` for [N, C] logits and [N] integer
    class ids, one fused VMEM pass; [N] row losses in the promoted dtype."""
    return _sxent_ids_fwd_impl(preout, ids)


def _sxent_ids_fwd_impl(preout, ids):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, C = preout.shape
    grid, mat, col = _sxent_specs(N, C)
    out = pl.pallas_call(
        _sxent_ids_fwd_kernel,
        grid=grid,
        in_specs=[mat, col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((N, 1), _sxent_compute_dt(preout.dtype)),
        interpret=_interpret(),
        name="softmax_xent_ids_fwd",
    )(preout, ids.reshape(N, 1).astype(jnp.int32))
    return out[:, 0]


def _sxent_ids_fwd(preout, ids):
    return _sxent_ids_fwd_impl(preout, ids), (preout, ids)


def _sxent_ids_bwd(residuals, g):
    import numpy as np  # noqa: PLC0415
    from jax.experimental import pallas as pl  # noqa: PLC0415

    preout, ids = residuals
    N, C = preout.shape
    grid, mat, col = _sxent_specs(N, C)
    dx = pl.pallas_call(
        _sxent_ids_bwd_kernel,
        grid=grid,
        in_specs=[mat, col, col],
        out_specs=mat,
        out_shape=jax.ShapeDtypeStruct((N, C), preout.dtype),
        interpret=_interpret(),
        name="softmax_xent_ids_bwd",
    )(preout, ids.reshape(N, 1).astype(jnp.int32),
      g.reshape(N, 1).astype(_sxent_compute_dt(preout.dtype)))
    return dx, np.zeros(ids.shape, jax.dtypes.float0)   # ids carry no gradient


fused_softmax_xent_ids.defvjp(_sxent_ids_fwd, _sxent_ids_bwd)


# ---------------------------------------------------------------------------
# fused Adam update — the optimizer-step hot path
# ---------------------------------------------------------------------------
#
# The optax chain materializes every intermediate of the moment/bias-correct/
# scale pipeline as a tree-wide HBM round trip; per parameter leaf this
# kernel reads (g, m, v) and writes (update, m, v) once — the bandwidth
# floor of the math. A leaf tiled as it lies gets each result where the
# operand it replaces lay (``input_output_aliases``: m' on m, v' on v, the
# update on the gradient): a loop that carries the moments gets them back in
# the buffers they came in, where fresh results cost a copy of each into the
# carry, 5.3 GB a step of the hybrid model (PERF.md, PR 33). Selected by the
# "optimizer" kernel_select site (the update is elementwise, i.e. always
# below the roofline ridge). Not differentiated: optimizer updates sit
# outside jax.grad by construction.

_ADAM_LANES = 128
# A block of at most 1 MiB: the call's three operands and three results,
# double-buffered, are then 12 MiB of the 16 MiB the compiler gives a kernel
# by default, so no call states a limit of its own and none depends on where
# XLA keeps its operands. (At 2 MiB, the 4096 lane rows this kernel began
# with, a call of two or more blocks asks for 24 MiB: the char-RNN's 4 MiB
# leaves compiled only because XLA held them in VMEM, and inside the 9-block
# hybrid program a [2688, 256] leaf was refused; PERF.md, PR 30.)
_ADAM_BLOCK_BYTES = 1 << 20


@jit_entry
def _adam_kernel(b1, b2, eps, g_ref, m_ref, v_ref, sc_ref,
                 u_out, m_out, v_out):
    # math at the scalars' dtype (>= f32): Mosaic has no bf16 scalar
    # arithmetic ("failed to legalize arith.subf (bf16, bf16)" on the v5e),
    # and the optimizer update is the f32 island of a bf16 step anyway
    cdt = sc_ref.dtype
    g = g_ref[:].astype(cdt)
    lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2]  # bcK = 1 - bK**t
    m = b1 * m_ref[:].astype(cdt) + (1.0 - b1) * g
    v = b2 * v_ref[:].astype(cdt) + (1.0 - b2) * g * g
    u_out[:] = (-lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)).astype(u_out.dtype)
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v.astype(v_out.dtype)


# Up to here a leaf is flattened into lane rows, as this kernel always did:
# XLA holds leaves of this size in VMEM, where the relayout that flattening
# asks for costs next to nothing (the char-RNN's largest are 4 MiB; tiled as
# they lie that cell read 0.6% lower, PERF.md, PR 30). Above it the relayout
# is an HBM copy of each operand and result: 29 ms of a 524 ms step of the
# hybrid model when its lane-tiled leaves were flattened (PERF.md, PR 30).
# A flattened leaf's operands are XLA's own temporaries and its results stay
# untied, as they always were: tied, the char-RNN read 1.7% lower (``p + u``
# left its fusion and ran as a pass of its own; PERF.md, PR 33).
_ADAM_FLATTEN_BYTES = 4 << 20


def _adam_view(shape, itemsize: int):
    """How a leaf of ``shape`` is handed to the kernel as ``[rows, cols]``:
    ``(swap, rows, cols, tile)``. Flattened into lane rows up to
    ``_ADAM_FLATTEN_BYTES`` and where nothing else can be done (one axis, a
    last axis short of a lane tile, rows that are no whole sublane tiles). A
    longer matrix is taken as it lies, its leading axes merged, so that no
    element changes its tile and XLA moves nothing. (The TPU keeps a matrix
    whose last axis is no whole number of lane tiles and whose last but one
    is, [2688, 1856], with the two swapped: ``swap`` views it so.)"""
    n = 1
    for d in shape:
        n *= d
    lane_rows = _ADAM_BLOCK_BYTES // (_ADAM_LANES * itemsize)
    flat = (False, None, _ADAM_LANES if n >= _ADAM_LANES else max(n, 1),
            lane_rows)
    if len(shape) < 2 or n * itemsize <= _ADAM_FLATTEN_BYTES:
        return flat
    swap = shape[-1] % _ADAM_LANES != 0 and shape[-2] % _ADAM_LANES == 0
    cols, sublanes = (shape[-2], shape[-1]) if swap else (shape[-1], shape[-2])
    if cols < _ADAM_LANES or (len(shape) > 2 and sublanes % 8):
        return flat
    lanes = -(-cols // _ADAM_LANES) * _ADAM_LANES
    return swap, n // cols, cols, max(
        8, _ADAM_BLOCK_BYTES // (lanes * itemsize) // 8 * 8)


def fused_adam_update(g, m, v, lr, bc1, bc2,
                      b1: float, b2: float, eps: float):
    """One fused Adam step for one parameter leaf, in place: returns
    ``(update, new_m, new_v)`` with ``update = -lr·m̂/(√v̂+eps)`` using
    exactly optax's ``scale_by_adam`` bias corrections (``bc1``/``bc2`` are
    the traced ``1 - βᵢ**t`` scalars, ``lr`` the schedule's value). Any leaf
    shape, viewed as :func:`_adam_view` says and row-tiled in blocks of at
    most ``_ADAM_BLOCK_BYTES``; a flattened view is lane-padded, its padded
    slots compute a zero update and are sliced off. A leaf tiled as it lies
    has each result aliased onto the operand it replaces (``g``, ``m``,
    ``v``): a caller that donates them, as a loop's carry does, gets them
    back where they lay, and XLA copies an operand first where its caller
    still reads it."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    shape, dt = g.shape, g.dtype
    n = g.size
    sdt = jnp.promote_types(dt, jnp.float32)
    swap, rows, cols, tile = _adam_view(shape, sdt.itemsize)
    if swap:
        g, m, v = (jnp.swapaxes(a, -1, -2) for a in (g, m, v))
    lies = g.shape
    in_place = {} if rows is None else {0: 0, 1: 1, 2: 2}
    pad = 0 if rows is not None else (-n) % cols
    rows = rows if rows is not None else (n + pad) // cols

    def flat(a):
        a = a.astype(dt)
        if pad:
            a = jnp.concatenate([a.reshape(-1), jnp.zeros((pad,), dt)])
        return a.reshape(rows, cols)

    # traced scalars ride one (3,) array in SMEM — Mosaic reads scalars
    # from scalar memory, not from a VMEM block: lr, 1-b1^t, 1-b2^t (kept at
    # >=f32 — f64 under the x64 test env so parity against optax holds)
    scalars = jnp.stack([jnp.asarray(lr), jnp.asarray(bc1),
                         jnp.asarray(bc2)]).astype(sdt)
    tile = min(tile, rows)
    grid = (pl.cdiv(rows, tile),)
    mat = pl.BlockSpec((tile, cols), lambda i: (i, 0))
    sc = pl.BlockSpec(memory_space=pltpu.SMEM)
    u2, m2, v2 = pl.pallas_call(
        functools.partial(_adam_kernel, float(b1), float(b2), float(eps)),
        grid=grid,
        in_specs=[mat, mat, mat, sc],
        out_specs=(mat, mat, mat),
        out_shape=(jax.ShapeDtypeStruct((rows, cols), dt),) * 3,
        input_output_aliases=in_place,
        interpret=_interpret(),
        name="adam_update",
    )(flat(g), flat(m), flat(v), scalars)

    def unflat(a):
        a = a.reshape(-1)[:n].reshape(lies)
        return jnp.swapaxes(a, -1, -2) if swap else a

    return unflat(u2), unflat(m2), unflat(v2)
