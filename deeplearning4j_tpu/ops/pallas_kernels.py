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
# VJP spills 7 residual arrays to HBM every step. This kernel fuses the WHOLE
# time loop instead: grid=(T,) executes sequentially on TPU, h/c live in VMEM
# scratch across grid steps, RW stays VMEM-resident, and only the 5 residual
# tensors cuDNN also reserves (gate activations + cell state) stream out —
# c_{t-1}/h_{t-1} are re-read in the backward via shifted block indices
# rather than stored twice. Select with DL4J_TPU_PALLAS=seq (measured winner
# becomes the default).

_SEQ_VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def _seq_fits(B: int, H: int, itemsize: int) -> bool:
    # Model the BACKWARD kernel — its footprint dominates: RW plus the f32
    # (H, 4H) dRW accumulator are resident, dh/dc carries in scratch, and
    # per-step it streams dy + 5 residuals + c_prev/h_prev + dzx blocks
    # (double-buffered). The forward (RW + 2 carries + 7 streamed blocks)
    # is strictly smaller.
    resident = (H * 4 * H * itemsize      # RW
                + H * 4 * H * 4           # f32 dRW accumulator
                + 2 * B * H * itemsize    # dh/dc carries
                + 3 * H * 4)              # peephole accumulators
    streamed = 2 * (8 * B * H + B * 4 * H) * itemsize
    return resident + streamed < _SEQ_VMEM_BUDGET_BYTES


@jit_entry
def _seq_fwd_kernel(act, gate,
                    zx_ref, h0_ref, c0_ref, rw_ref, pf_ref, pi_ref, po_ref,
                    y_out, a_out, f_out, o_out, i_out, c_out, hT_out, cT_out,
                    h_scr, c_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h, c, a, f, o, i, _cact = _cell_math(
        zx_ref[0], h_scr[:], c_scr[:], rw_ref[:],
        pf_ref[:], pi_ref[:], po_ref[:], act, gate,
    )
    y_out[0], a_out[0], f_out[0], o_out[0], i_out[0], c_out[0] = h, a, f, o, i, c
    h_scr[:], c_scr[:] = h, c
    # constant-index outputs: written every step, the last write is h_T/c_T
    hT_out[:], cT_out[:] = h, c


@jit_entry
def _seq_bwd_kernel(act, dact, dgate, T,
                    dy_ref, dhT_ref, dcT_ref,
                    a_ref, f_ref, o_ref, i_ref, cprev_ref, hprev_ref,
                    rw_ref, pf_ref, pi_ref, po_ref, h0_ref, c0_ref,
                    dzx_out, dh0_out, dc0_out, drw_out, dpf_out, dpi_out,
                    dpo_out,
                    dh_scr, dc_scr, drw_scr, dpf_scr, dpi_scr, dpo_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    k = pl.program_id(0)          # reverse-time grid: time t = T-1-k

    @pl.when(k == 0)
    def _init():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]
        drw_scr[:] = jnp.zeros(drw_scr.shape, drw_scr.dtype)
        dpf_scr[:] = jnp.zeros(dpf_scr.shape, dpf_scr.dtype)
        dpi_scr[:] = jnp.zeros(dpi_scr.shape, dpi_scr.dtype)
        dpo_scr[:] = jnp.zeros(dpo_scr.shape, dpo_scr.dtype)

    a, f, o, i = a_ref[0], f_ref[0], o_ref[0], i_ref[0]
    first = k == T - 1            # t == 0: previous state is the initial one
    c_prev = jnp.where(first, c0_ref[:], cprev_ref[0])
    h_prev = jnp.where(first, h0_ref[:], hprev_ref[0])
    # c_t recomputed from the gates (VPU-cheap) — only the prev-indexed c
    # stream is read, saving a T×B×H HBM stream (same as the masked kernel)
    c = f * c_prev + i * a
    cact = act(c)                 # recomputed, not stored
    pF, pI, pO = pf_ref[:], pi_ref[:], po_ref[:]

    dh = dy_ref[0] + dh_scr[:]
    dc = dc_scr[:]
    do = dh * cact * dgate(o)
    dc_tot = dc + dh * o * dact(cact) + do * pO
    df = dc_tot * c_prev * dgate(f)
    di = dc_tot * a * dgate(i)
    da = dc_tot * i * dact(a)
    dzx = jnp.concatenate([da, df, do, di], axis=-1)
    dzx_out[0] = dzx
    dh_scr[:] = jnp.dot(
        dzx, rw_ref[:].T, preferred_element_type=_acc_dtype(dzx.dtype)
    ).astype(dzx.dtype)
    dc_scr[:] = dc_tot * f + df * pF + di * pI
    f32 = drw_scr.dtype
    drw_scr[:] += jnp.dot(h_prev.T, dzx, preferred_element_type=f32)
    dpf_scr[:] += jnp.sum(df * c_prev, axis=0, dtype=f32, keepdims=True)
    dpi_scr[:] += jnp.sum(di * c_prev, axis=0, dtype=f32, keepdims=True)
    dpo_scr[:] += jnp.sum(do * c, axis=0, dtype=f32, keepdims=True)
    # constant-index outputs: last (t==0) write carries the full sums
    dt = dzx.dtype
    dh0_out[:] = dh_scr[:]
    dc0_out[:] = dc_scr[:]
    drw_out[:] = drw_scr[:].astype(dt)
    dpf_out[:] = dpf_scr[:].astype(dt)
    dpi_out[:] = dpi_scr[:].astype(dt)
    dpo_out[:] = dpo_scr[:].astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def fused_lstm_sequence(zx, h0, c0, RW, pF, pI, pO,
                        act_name: str = "tanh", gate_name: str = "sigmoid"):
    """Whole-sequence fused LSTM: ``zx`` [T, B, 4H] (precomputed x@W + b),
    returns (ys [T, B, H], h_T, c_T). Unmasked, forward-direction.

    The primal (inference) path runs a LEAN kernel that emits only
    ys/hT/cT; the five gate residuals stream to HBM only under jax.grad
    (the VJP's forward rule) where the backward actually consumes them."""
    return _seq_lean_impl(zx, None, h0, c0, RW, pF, pI, pO,
                          act_name, gate_name)


@jit_entry
def _seq_lean_kernel(act, gate, masked, *refs):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    if masked:  # static via partial — dl4jtpu: ignore[DT104]
        (zx_ref, m_ref, h0_ref, c0_ref, rw_ref, pf_ref, pi_ref, po_ref,
         y_out, hT_out, cT_out, h_scr, c_scr) = refs
    else:
        (zx_ref, h0_ref, c0_ref, rw_ref, pf_ref, pi_ref, po_ref,
         y_out, hT_out, cT_out, h_scr, c_scr) = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h_prev, c_prev = h_scr[:], c_scr[:]
    h, c, *_ = _cell_math(zx_ref[0], h_prev, c_prev, rw_ref[:],
                          pf_ref[:], pi_ref[:], po_ref[:], act, gate)
    if masked:  # static via partial — dl4jtpu: ignore[DT104]
        m = m_ref[0]
        h = m * h + (1.0 - m) * h_prev
        c = m * c + (1.0 - m) * c_prev
    y_out[0] = h
    h_scr[:], c_scr[:] = h, c
    hT_out[:], cT_out[:] = h, c


def _seq_lean_impl(zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    act, _ = _ACT_KERNEL[act_name]
    gate, _ = _ACT_KERNEL[gate_name]
    T, B, H4 = zx.shape
    H = H4 // 4
    dt = zx.dtype
    step = lambda t: (t, 0, 0)  # noqa: E731
    const = lambda t: (0, 0)    # noqa: E731
    in_specs = [pl.BlockSpec((1, B, H4), step)]
    args = [zx]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, B, 1), step))
        args.append(mask.astype(dt))
    in_specs += [
        pl.BlockSpec((B, H), const),
        pl.BlockSpec((B, H), const),
        pl.BlockSpec((H, H4), const),
        pl.BlockSpec((1, H), lambda t: (0, 0)),
        pl.BlockSpec((1, H), lambda t: (0, 0)),
        pl.BlockSpec((1, H), lambda t: (0, 0)),
    ]
    args += [h0, c0, RW, *_rows(pF, pI, pO)]
    return pl.pallas_call(
        functools.partial(_seq_lean_kernel, act, gate, mask is not None),
        grid=(T,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, B, H), step),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((T, B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
            jax.ShapeDtypeStruct((B, H), dt),
        ),
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)],
        interpret=_interpret(),
        name="lstm_seq_lean",
    )(*args)


def _seq_fwd_impl(zx, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    act, _ = _ACT_KERNEL[act_name]
    gate, _ = _ACT_KERNEL[gate_name]
    T, B, H4 = zx.shape
    H = H4 // 4
    dt = zx.dtype
    step = lambda t: (t, 0, 0)  # noqa: E731
    const3 = lambda t: (0, 0)   # noqa: E731
    seq_spec = lambda w: pl.BlockSpec((1, B, w), step)  # noqa: E731
    out_shape = (
        jax.ShapeDtypeStruct((T, B, H), dt),  # ys
        *[jax.ShapeDtypeStruct((T, B, H), dt) for _ in range(5)],  # a f o i c
        jax.ShapeDtypeStruct((B, H), dt),     # hT
        jax.ShapeDtypeStruct((B, H), dt),     # cT
    )
    return pl.pallas_call(
        functools.partial(_seq_fwd_kernel, act, gate),
        grid=(T,),
        in_specs=[
            seq_spec(H4),
            pl.BlockSpec((B, H), const3),
            pl.BlockSpec((B, H), const3),
            pl.BlockSpec((H, H4), const3),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
        ],
        out_specs=(
            seq_spec(H), seq_spec(H), seq_spec(H), seq_spec(H), seq_spec(H),
            seq_spec(H),
            pl.BlockSpec((B, H), const3),
            pl.BlockSpec((B, H), const3),
        ),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)],
        interpret=_interpret(),
        name="lstm_seq_fwd",
    )(zx, h0, c0, RW, *_rows(pF, pI, pO))


def _seq_fwd(zx, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    ys, a, f, o, i, c, hT, cT = _seq_fwd_impl(
        zx, h0, c0, RW, pF, pI, pO, act_name, gate_name
    )
    residuals = (ys, a, f, o, i, c, h0, c0, RW, pF, pI, pO)
    return (ys, hT, cT), residuals


def _seq_bwd(act_name, gate_name, residuals, grads):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    ys, a, f, o, i, c, h0, c0, RW, pF, pI, pO = residuals
    dys, dhT, dcT = grads
    act, dact = _ACT_KERNEL[act_name]
    _, dgate = _ACT_KERNEL[gate_name]
    T, B, H = ys.shape
    dt = ys.dtype
    rev = lambda k: (T - 1 - k, 0, 0)   # noqa: E731
    # previous-step state: block t-1, clamped at 0 (t==0 substitutes the
    # initial state inside the kernel)
    prev = lambda k: (jnp.maximum(T - 2 - k, 0), 0, 0)  # noqa: E731
    const = lambda k: (0, 0)            # noqa: E731
    seq = lambda ix: pl.BlockSpec((1, B, H), ix)  # noqa: E731
    out_shape = (
        jax.ShapeDtypeStruct((T, B, 4 * H), dt),  # dzx
        jax.ShapeDtypeStruct((B, H), dt),         # dh0
        jax.ShapeDtypeStruct((B, H), dt),         # dc0
        jax.ShapeDtypeStruct((H, 4 * H), dt),     # dRW
        jax.ShapeDtypeStruct((1, H), dt),         # dpF
        jax.ShapeDtypeStruct((1, H), dt),         # dpI
        jax.ShapeDtypeStruct((1, H), dt),         # dpO
    )
    dzx, dh0, dc0, dRW, dpF, dpI, dpO = pl.pallas_call(
        functools.partial(_seq_bwd_kernel, act, dact, dgate, T),
        grid=(T,),
        in_specs=[
            seq(rev),                       # dys
            pl.BlockSpec((B, H), const),    # dhT
            pl.BlockSpec((B, H), const),    # dcT
            seq(rev), seq(rev), seq(rev), seq(rev),  # a f o i
            seq(prev),                      # c_{t-1} (from c)
            seq(prev),                      # h_{t-1} (from ys)
            pl.BlockSpec((H, 4 * H), const),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((B, H), const),    # h0
            pl.BlockSpec((B, H), const),    # c0
        ],
        out_specs=(
            pl.BlockSpec((1, B, 4 * H), rev),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((H, 4 * H), const),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt),
            pltpu.VMEM((H, 4 * H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32), pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
        ],
        interpret=_interpret(),
        name="lstm_seq_bwd",
    )(dys, dhT, dcT, a, f, o, i, c, ys, RW, *_rows(pF, pI, pO), h0, c0)
    return dzx, dh0, dc0, dRW, dpF[0], dpI[0], dpO[0]


fused_lstm_sequence.defvjp(_seq_fwd, _seq_bwd)


# -- masked variant: padded/bucketed sequences ride the fused loop too ------
#
# Masked steps carry h/c through unchanged (h_t = m·h̃ + (1−m)·h_{t-1} — the
# scan path's semantics exactly). The backward recomputes the pre-mask cell
# state c̃ = f·c_prev + i·a from the stored gates, so the residual set stays
# the same five tensors plus the [T, B, 1] mask.


@jit_entry
def _seq_fwd_kernel_masked(act, gate,
                           zx_ref, m_ref, h0_ref, c0_ref, rw_ref, pf_ref,
                           pi_ref, po_ref,
                           y_out, a_out, f_out, o_out, i_out, c_out,
                           hT_out, cT_out, h_scr, c_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    h_prev, c_prev = h_scr[:], c_scr[:]
    h_tilde, c_tilde, a, f, o, i, _cact = _cell_math(
        zx_ref[0], h_prev, c_prev, rw_ref[:],
        pf_ref[:], pi_ref[:], po_ref[:], act, gate,
    )
    m = m_ref[0]
    h = m * h_tilde + (1.0 - m) * h_prev
    c = m * c_tilde + (1.0 - m) * c_prev
    y_out[0], a_out[0], f_out[0], o_out[0], i_out[0], c_out[0] = h, a, f, o, i, c
    h_scr[:], c_scr[:] = h, c
    hT_out[:], cT_out[:] = h, c


@jit_entry
def _seq_bwd_kernel_masked(act, dact, dgate, T,
                           dy_ref, dhT_ref, dcT_ref, m_ref,
                           a_ref, f_ref, o_ref, i_ref, cprev_ref,
                           hprev_ref, rw_ref, pf_ref, pi_ref, po_ref,
                           h0_ref, c0_ref,
                           dzx_out, dh0_out, dc0_out, drw_out, dpf_out,
                           dpi_out, dpo_out,
                           dh_scr, dc_scr, drw_scr, dpf_scr, dpi_scr, dpo_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]
        drw_scr[:] = jnp.zeros(drw_scr.shape, drw_scr.dtype)
        dpf_scr[:] = jnp.zeros(dpf_scr.shape, dpf_scr.dtype)
        dpi_scr[:] = jnp.zeros(dpi_scr.shape, dpi_scr.dtype)
        dpo_scr[:] = jnp.zeros(dpo_scr.shape, dpo_scr.dtype)

    a, f, o, i = a_ref[0], f_ref[0], o_ref[0], i_ref[0]
    first = k == T - 1
    c_prev = jnp.where(first, c0_ref[:], cprev_ref[0])
    h_prev = jnp.where(first, h0_ref[:], hprev_ref[0])
    m = m_ref[0]
    c_tilde = f * c_prev + i * a        # pre-mask cell state, recomputed
    cact = act(c_tilde)
    pF, pI, pO = pf_ref[:], pi_ref[:], po_ref[:]

    dh_t = dy_ref[0] + dh_scr[:]
    dc_t = dc_scr[:]
    dh = m * dh_t                        # gradient into the cell outputs
    dc = m * dc_t
    do = dh * cact * dgate(o)
    dc_tot = dc + dh * o * dact(cact) + do * pO
    df = dc_tot * c_prev * dgate(f)
    di = dc_tot * a * dgate(i)
    da = dc_tot * i * dact(a)
    dzx = jnp.concatenate([da, df, do, di], axis=-1)
    dzx_out[0] = dzx
    # carry-through paths: masked steps pass dh/dc straight to t-1
    dh_scr[:] = (jnp.dot(dzx, rw_ref[:].T,
                         preferred_element_type=_acc_dtype(dzx.dtype)
                         ).astype(dzx.dtype)
                 + (1.0 - m) * dh_t)
    dc_scr[:] = dc_tot * f + df * pF + di * pI + (1.0 - m) * dc_t
    f32 = drw_scr.dtype
    drw_scr[:] += jnp.dot(h_prev.T, dzx, preferred_element_type=f32)
    dpf_scr[:] += jnp.sum(df * c_prev, axis=0, dtype=f32, keepdims=True)
    dpi_scr[:] += jnp.sum(di * c_prev, axis=0, dtype=f32, keepdims=True)
    dpo_scr[:] += jnp.sum(do * c_tilde, axis=0, dtype=f32, keepdims=True)
    dt = dzx.dtype
    dh0_out[:] = dh_scr[:]
    dc0_out[:] = dc_scr[:]
    drw_out[:] = drw_scr[:].astype(dt)
    dpf_out[:] = dpf_scr[:].astype(dt)
    dpi_out[:] = dpi_scr[:].astype(dt)
    dpo_out[:] = dpo_scr[:].astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def fused_lstm_sequence_masked(zx, mask, h0, c0, RW, pF, pI, pO,
                               act_name: str = "tanh",
                               gate_name: str = "sigmoid"):
    """Masked whole-sequence fused LSTM: ``mask`` [T, B, 1]; masked steps
    hold h/c (scan-path semantics). Returns (ys, h_T, c_T). The primal runs
    the lean (no-residual) kernel; see fused_lstm_sequence."""
    return _seq_lean_impl(zx, mask, h0, c0, RW, pF, pI, pO,
                          act_name, gate_name)


def _seq_masked_fwd_impl(zx, mask, h0, c0, RW, pF, pI, pO, act_name,
                         gate_name):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    act, _ = _ACT_KERNEL[act_name]
    gate, _ = _ACT_KERNEL[gate_name]
    T, B, H4 = zx.shape
    H = H4 // 4
    dt = zx.dtype
    step = lambda t: (t, 0, 0)  # noqa: E731
    const = lambda t: (0, 0)    # noqa: E731
    seq_spec = lambda w: pl.BlockSpec((1, B, w), step)  # noqa: E731
    out_shape = (
        jax.ShapeDtypeStruct((T, B, H), dt),
        *[jax.ShapeDtypeStruct((T, B, H), dt) for _ in range(5)],
        jax.ShapeDtypeStruct((B, H), dt),
        jax.ShapeDtypeStruct((B, H), dt),
    )
    return pl.pallas_call(
        functools.partial(_seq_fwd_kernel_masked, act, gate),
        grid=(T,),
        in_specs=[
            seq_spec(H4),
            pl.BlockSpec((1, B, 1), step),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((H, H4), const),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
            pl.BlockSpec((1, H), lambda t: (0, 0)),
        ],
        out_specs=(
            seq_spec(H), seq_spec(H), seq_spec(H), seq_spec(H), seq_spec(H),
            seq_spec(H),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
        ),
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt)],
        interpret=_interpret(),
        name="lstm_seq_masked_fwd",
    )(zx, mask.astype(dt), h0, c0, RW, *_rows(pF, pI, pO))


def _seq_masked_fwd(zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name):
    ys, a, f, o, i, c, hT, cT = _seq_masked_fwd_impl(
        zx, mask, h0, c0, RW, pF, pI, pO, act_name, gate_name
    )
    residuals = (ys, a, f, o, i, c, mask, h0, c0, RW, pF, pI, pO)
    return (ys, hT, cT), residuals


def _seq_masked_bwd(act_name, gate_name, residuals, grads):
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    ys, a, f, o, i, c, mask, h0, c0, RW, pF, pI, pO = residuals
    dys, dhT, dcT = grads
    act, dact = _ACT_KERNEL[act_name]
    _, dgate = _ACT_KERNEL[gate_name]
    T, B, H = ys.shape
    dt = ys.dtype
    rev = lambda k: (T - 1 - k, 0, 0)   # noqa: E731
    prev = lambda k: (jnp.maximum(T - 2 - k, 0), 0, 0)  # noqa: E731
    const = lambda k: (0, 0)            # noqa: E731
    seq = lambda ix: pl.BlockSpec((1, B, H), ix)  # noqa: E731
    out_shape = (
        jax.ShapeDtypeStruct((T, B, 4 * H), dt),
        jax.ShapeDtypeStruct((B, H), dt),
        jax.ShapeDtypeStruct((B, H), dt),
        jax.ShapeDtypeStruct((H, 4 * H), dt),
        jax.ShapeDtypeStruct((1, H), dt),
        jax.ShapeDtypeStruct((1, H), dt),
        jax.ShapeDtypeStruct((1, H), dt),
    )
    dzx, dh0, dc0, dRW, dpF, dpI, dpO = pl.pallas_call(
        functools.partial(_seq_bwd_kernel_masked, act, dact, dgate, T),
        grid=(T,),
        in_specs=[
            seq(rev),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((1, B, 1), rev),
            seq(rev), seq(rev), seq(rev), seq(rev),
            # the kernel recomputes c_tilde from the gates, so only the
            # prev-indexed c stream is read (one T×B×H HBM stream saved)
            seq(prev),
            seq(prev),
            pl.BlockSpec((H, 4 * H), const),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
        ],
        out_specs=(
            pl.BlockSpec((1, B, 4 * H), rev),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((B, H), const),
            pl.BlockSpec((H, 4 * H), const),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
            pl.BlockSpec((1, H), lambda k: (0, 0)),
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((B, H), dt), pltpu.VMEM((B, H), dt),
            pltpu.VMEM((H, 4 * H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32), pltpu.VMEM((1, H), jnp.float32),
            pltpu.VMEM((1, H), jnp.float32),
        ],
        interpret=_interpret(),
        name="lstm_seq_masked_bwd",
    )(dys, dhT, dcT, mask.astype(dt), a, f, o, i, c, ys,
      RW, *_rows(pF, pI, pO), h0, c0)
    return dzx, None, dh0, dc0, dRW, dpF[0], dpI[0], dpO[0]


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


# ---------------------------------------------------------------------------
# fused Adam update — the optimizer-step hot path
# ---------------------------------------------------------------------------
#
# The optax chain materializes every intermediate of the moment/bias-correct/
# scale pipeline as a tree-wide HBM round trip; per parameter leaf this
# kernel reads (g, m, v) and writes (update, m, v) once — the bandwidth
# floor of the math. Selected by the "optimizer" kernel_select site (the
# update is elementwise, i.e. always below the roofline ridge). Not
# differentiated: optimizer updates sit outside jax.grad by construction.

_ADAM_LANES = 128
_ADAM_TILE_ROWS = 4096


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


def fused_adam_update(g, m, v, lr, bc1, bc2,
                      b1: float, b2: float, eps: float):
    """One fused Adam step for one parameter leaf: returns
    ``(update, new_m, new_v)`` with ``update = -lr·m̂/(√v̂+eps)`` using
    exactly optax's ``scale_by_adam`` bias corrections (``bc1``/``bc2`` are
    the traced ``1 - βᵢ**t`` scalars, ``lr`` the schedule's value). Any leaf
    shape: the view is flattened, lane-padded, and row-tiled; padded slots
    compute a zero update and are sliced off."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    shape, dt = g.shape, g.dtype
    n = g.size
    cols = _ADAM_LANES if n >= _ADAM_LANES else max(n, 1)
    pad = (-n) % cols
    rows = (n + pad) // cols

    def flat(a):
        a = a.reshape(-1).astype(dt)
        if pad:
            a = jnp.concatenate([a, jnp.zeros((pad,), dt)])
        return a.reshape(rows, cols)

    # traced scalars ride one (3,) array in SMEM — Mosaic reads scalars
    # from scalar memory, not from a VMEM block: lr, 1-b1^t, 1-b2^t (kept at
    # >=f32 — f64 under the x64 test env so parity against optax holds)
    sdt = jnp.promote_types(dt, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr), jnp.asarray(bc1),
                         jnp.asarray(bc2)]).astype(sdt)
    tile = min(_ADAM_TILE_ROWS, rows)
    grid = (pl.cdiv(rows, tile),)
    mat = pl.BlockSpec((tile, cols), lambda i: (i, 0))
    sc = pl.BlockSpec(memory_space=pltpu.SMEM)
    u2, m2, v2 = pl.pallas_call(
        functools.partial(_adam_kernel, float(b1), float(b2), float(eps)),
        grid=grid,
        in_specs=[mat, mat, mat, sc],
        out_specs=(mat, mat, mat),
        out_shape=(jax.ShapeDtypeStruct((rows, cols), dt),) * 3,
        interpret=_interpret(),
        name="adam_update",
    )(flat(g), flat(m), flat(v), scalars)

    def unflat(a):
        return a.reshape(-1)[:n].reshape(shape)

    return unflat(u2), unflat(m2), unflat(v2)
