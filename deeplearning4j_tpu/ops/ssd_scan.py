"""The chunked state-space scan of Mamba-2 (the "SSD" form) — the successor of
``fused_lstm_sequence``: a linear recurrence over time whose state is a
matrix a head, computed a chunk of positions at a time so that nearly all of
it is matrix products.

For every head ``h`` of ``P`` channels (reading group ``h // (H // G)`` of the
``G`` groups of ``B``/``C`` projections, ``N`` wide) the state ``S`` [P, N]
follows

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t B_t^T,      y_t = S_t C_t

from a zero state (the ``D * x`` skip is the caller's: it is elementwise).
Over a chunk of ``L`` positions, with ``cum_t`` the running sum of ``dt * A``
inside the chunk:

    y_t   = sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s  +  exp(cum_t) S_prev C_t
    S_end = exp(cum_L) S_prev + sum_s exp(cum_L - cum_s) dt_s x_s B_s^T

Two variants, one site (``ssd_scan`` in :mod:`.kernel_select`):

- ``reference`` — :func:`ssd_scan_reference`: the chunked form in plain
  ``jax.numpy`` (scores under the decay mask, each chunk's end state, the
  state carried across chunks by ``lax.scan``), differentiated by autodiff.
- ``fused`` — :func:`ssd_scan_fused`: the same as two Mosaic kernels under
  one ``custom_vjp``, ``ssd_scan_fwd`` and ``ssd_scan_bwd``. The grid is
  (batch, group, chunk): batch and group are parallel axes, the chunk axis is
  sequential and carries the group's states in VMEM scratch (float32), as
  ``lstm_seq_*`` carries h/c. The ``C B^T`` scores are computed once a group
  and shared by its heads; heads are taken ``k`` at a time (``k * P`` = 128
  lanes at the published P = 64) so that no operand is sliced across a lane
  tile. The forward saves the state at each chunk's start; the backward walks
  the chunks last to first from those, carrying the state's gradient, and
  never holds a state for every position. Decays, cumulative sums and states
  are float32 (never below the input's own precision); matrix products take
  the input dtype with a float32 accumulator.

``T`` that is not a whole number of chunks is padded (``dt = 0``: the state
passes through unchanged) and the padding's outputs are cut off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..analysis.annotations import jit_entry
from .pallas_kernels import (_SEQ_MIN_VMEM_LIMIT_BYTES, _acc_dtype,
                             _interpret, _seq_vmem_budget)

_LANES = 128


def _state_dtype(dt):
    return jnp.promote_types(dt, jnp.float32)


def _pad_time(chunk, x, dt, Bm, Cm):
    """Pad ``T`` up to whole chunks: ``dt = 0`` keeps the state, ``x = 0``."""
    T = x.shape[1]
    pad = (-T) % chunk
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        x, dt, Bm, Cm = widen(x), widen(dt), widen(Bm), widen(Cm)
    return T, x, dt, Bm, Cm


# --------------------------------------------------------- reference variant
def ssd_scan_reference(x, dt, A, Bm, Cm, chunk: int):
    """``y`` [B, T, H, P] of ``x`` [B, T, H, P], ``dt`` [B, T, H] (after the
    softplus), ``A`` [H] (negative), ``Bm``/``Cm`` [B, T, G, N]: the chunked
    form in plain jax.numpy, the state carried across chunks by a scan."""
    T, x, dt, Bm, Cm = _pad_time(chunk, x, dt, Bm, Cm)
    Bsz, Tp, H, P = x.shape
    G, N = Bm.shape[2:]
    L, nc, rep = chunk, Tp // chunk, H // G
    f = _state_dtype(x.dtype)
    xr = x.reshape(Bsz, nc, L, H, P)
    dtr = dt.astype(f).reshape(Bsz, nc, L, H)
    cum = jnp.cumsum(dtr * A.astype(f), axis=2)            # [B, nc, L, H]
    Br = jnp.repeat(Bm.reshape(Bsz, nc, L, G, N), rep, axis=3)
    Cr = jnp.repeat(Cm.reshape(Bsz, nc, L, G, N), rep, axis=3)
    # inside a chunk: scores under the decay mask
    scores = jnp.einsum("bclhn,bcshn->bchls", Cr, Br,
                        preferred_element_type=f)
    cum_h = jnp.moveaxis(cum, 3, 2)                         # [B, nc, H, L]
    tri = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(tri, cum_h[..., :, None] - cum_h[..., None, :],
                              -jnp.inf))
    m = (scores * decay * jnp.moveaxis(dtr, 3, 2)[..., None, :]).astype(x.dtype)
    y_intra = jnp.einsum("bchls,bcshp->bclhp", m, xr,
                         preferred_element_type=f)
    # each chunk's own contribution to its end state, and its whole decay
    w = jnp.exp(cum[:, :, -1:, :] - cum) * dtr               # [B, nc, L, H]
    xw = (xr.astype(f) * w[..., None]).astype(x.dtype)
    local = jnp.einsum("bclhp,bclhn->bchpn", xw, Br,
                       preferred_element_type=f)
    whole = jnp.exp(cum[:, :, -1, :])                        # [B, nc, H]

    def carry(S, inp):
        loc, dec = inp
        return dec[..., None, None] * S + loc, S             # emits S_prev

    S0 = jnp.zeros((Bsz, H, P, N), f)
    _, S_prev = jax.lax.scan(
        carry, S0, (jnp.moveaxis(local, 1, 0), jnp.moveaxis(whole, 1, 0)))
    S_prev = jnp.moveaxis(S_prev, 0, 1)                      # [B, nc, H, P, N]
    y_inter = jnp.einsum("bclhn,bchpn->bclhp", Cr, S_prev.astype(x.dtype),
                         preferred_element_type=f) * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).astype(x.dtype).reshape(Bsz, Tp, H, P)
    return y[:, :T]


# ------------------------------------------------------------- fused variant
def _heads_a_pack(P: int, heads_per_group: int) -> int:
    """Heads taken together so that their channels fill a lane tile: the
    largest divisor of the group's heads with ``k * P <= 128`` (2 at P=64)."""
    k = max(1, min(_LANES // P if P <= _LANES else 1, heads_per_group))
    while heads_per_group % k:
        k -= 1
    return k


def ssd_footprint(L: int, P: int, N: int, heads_per_group: int,
                  itemsize: int) -> int:
    """VMEM bytes of the backward kernel (the larger of the two) a grid step:
    the streamed blocks double-buffered, the state's gradient in scratch, and
    the [L, L] float32 values the body holds for one head."""
    wide = heads_per_group * P
    lanes = max(_LANES, heads_per_group)
    streamed = (3 * L * wide + 4 * L * N) * itemsize       # x dy dx; B C dB dC
    streamed += 4 * (2 * L * lanes + 2 * 8 * L) * 4        # dt/cum in, grads out
    state = N * wide * 4                                    # one group's states
    working = 8 * L * L * 4 + 6 * L * max(wide, N) * 4
    return 2 * (streamed + state) + state + working


def ssd_fits(L: int, P: int, N: int, heads_per_group: int,
             itemsize: int) -> bool:
    return ssd_footprint(L, P, N, heads_per_group, itemsize) \
        <= _seq_vmem_budget()


def ssd_layout_ok(L: int, P: int, N: int, heads_per_group: int) -> bool:
    """What Mosaic's tiling asks of the shapes (interpret mode asks nothing):
    whole lane tiles for a pack of heads, the state width and the chunk."""
    k = _heads_a_pack(P, heads_per_group)
    return (k * P) % _LANES == 0 and N % _LANES == 0 and L % _LANES == 0 \
        and heads_per_group <= 8


def _compiler_params(L, P, N, hpg, itemsize):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(ssd_footprint(L, P, N, hpg, itemsize),
                             _SEQ_MIN_VMEM_LIMIT_BYTES))


def _dot(a, b, contract, acc):
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=acc)


def _head_terms(j, h, L, tri, lane_head, cuc, cur, dtc, dtr):
    """The decays of head ``h`` (the ``j``-th of its pack) in a chunk: its
    lane mask in the pack, the [L, L] decay without and with ``dt_s``, and
    the columns ``exp(cum_t)``, ``exp(cum_L - cum_s)``, ``dt_s`` and
    ``exp(cum_L)``."""
    cu_c, cu_r = cuc[:, h:h + 1], cur[h:h + 1, :]
    dt_c, dt_r = dtc[:, h:h + 1], dtr[h:h + 1, :]
    decay = jnp.exp(jnp.where(tri, cu_c - cu_r, -jnp.inf))
    cu_l = cu_c[L - 1:L, :]
    return (lane_head == j, decay, decay * dt_r, jnp.exp(cu_c),
            jnp.exp(cu_l - cu_c), dt_c, jnp.exp(cu_l))


@jit_entry
def _ssd_fwd_kernel(L, P, hpg, k, x_ref, b_ref, c_ref, dtc_ref, cuc_ref,
                    dtr_ref, cur_ref, y_ref, st_ref, s_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    Bm, Cm = b_ref[0], c_ref[0]                              # [L, N]
    cdt, f = Bm.dtype, s_scr.dtype
    acc = _acc_dtype(cdt)
    scores = _dot(Cm, Bm, ((1,), (1,)), acc).astype(f)       # C B^T, a group's
    tri = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, k * P), 1) // P
    cuc, cur, dtc, dtr = cuc_ref[0, 0], cur_ref[0, 0], dtc_ref[0, 0], dtr_ref[0, 0]
    st_ref[0, 0, 0] = s_scr[...]                             # S at the chunk's start
    for pk in range(hpg // k):
        lanes = slice(pk * k * P, (pk + 1) * k * P)
        xp = x_ref[0, :, lanes]                              # [L, kP]
        Sp = s_scr[pk]                                       # [N, kP]
        yp = ea_m = w_m = jnp.zeros((L, k * P), f)
        dl_m = jnp.zeros((1, k * P), f)
        for j in range(k):
            sel, _, lam, ea, el, dt_c, dl = _head_terms(
                j, pk * k + j, L, tri, lane_head, cuc, cur, dtc, dtr)
            m = (scores * lam).astype(cdt)
            yp = jnp.where(sel, _dot(m, xp, ((1,), (0,)), acc).astype(f), yp)
            ea_m = jnp.where(sel, ea, ea_m)
            w_m = jnp.where(sel, el * dt_c, w_m)
            dl_m = jnp.where(sel, dl, dl_m)
        y_inter = _dot(Cm, Sp.astype(cdt), ((1,), (0,)), acc).astype(f) * ea_m
        y_ref[0, :, lanes] = (yp + y_inter).astype(y_ref.dtype)
        xw = (xp.astype(f) * w_m).astype(cdt)
        s_scr[pk] = dl_m * Sp + _dot(Bm, xw, ((0,), (0,)), acc).astype(f)


@jit_entry
def _ssd_bwd_kernel(L, P, hpg, k, x_ref, b_ref, c_ref, dtc_ref, cuc_ref,
                    dtr_ref, cur_ref, st_ref, dy_ref, dx_ref, db_ref, dc_ref,
                    dcuc_ref, dcur_ref, ddtc_ref, ddtr_ref, ds_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    Bm, Cm = b_ref[0], c_ref[0]
    cdt, f = Bm.dtype, ds_scr.dtype
    acc = _acc_dtype(cdt)
    scores = _dot(Cm, Bm, ((1,), (1,)), acc).astype(f)
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    tri = row >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, k * P), 1) // P
    col_head = jax.lax.broadcasted_iota(jnp.int32, (1, hpg), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (hpg, 1), 0)
    cuc, cur, dtc, dtr = cuc_ref[0, 0], cur_ref[0, 0], dtc_ref[0, 0], dtr_ref[0, 0]
    n = Bm.shape[1]
    d_scores = jnp.zeros((L, L), f)
    dC = dB = jnp.zeros((L, n), f)
    dcu_c = ddt_c = jnp.zeros((L, hpg), f)
    dcu_r = ddt_r = jnp.zeros((hpg, L), f)
    zero = jnp.zeros((), f)
    for pk in range(hpg // k):
        lanes = slice(pk * k * P, (pk + 1) * k * P)
        xp, dyp = x_ref[0, :, lanes], dy_ref[0, :, lanes]    # [L, kP]
        Sp, dSp = st_ref[0, 0, 0, pk], ds_scr[pk]            # [N, kP]
        xf, dyf = xp.astype(f), dyp.astype(f)
        BdS = _dot(Bm, dSp.astype(cdt), ((1,), (0,)), acc).astype(f)
        CS = _dot(Cm, Sp.astype(cdt), ((1,), (0,)), acc).astype(f)
        dxp = ea_m = w_m = jnp.zeros((L, k * P), f)
        dl_m = jnp.zeros((1, k * P), f)
        for j in range(k):
            h = pk * k + j
            sel, decay, lam, ea, el, dt_c, dl = _head_terms(
                j, h, L, tri, lane_head, cuc, cur, dtc, dtr)
            m = (scores * lam).astype(cdt)
            dm = _dot(jnp.where(sel, dyp, jnp.zeros((), cdt)), xp,
                      ((1,), (1,)), acc).astype(f)           # dy_h x_h^T
            dxp = jnp.where(
                sel, _dot(m, dyp, ((0,), (0,)), acc).astype(f), dxp)
            d_scores = d_scores + dm * lam
            e_nodt = dm * scores * decay
            e = e_nodt * dtr[h:h + 1, :]
            # what the state's update and the carried state give the decays
            q = jnp.sum(jnp.where(sel, xf * BdS, zero), axis=1, keepdims=True)
            w = el * dt_c
            carried = jnp.sum(jnp.where(sel, dSp * Sp, zero), axis=1,
                              keepdims=True)
            dcu_l = (jnp.sum(w * q, axis=0, keepdims=True)
                     + dl * jnp.sum(carried, axis=0, keepdims=True))
            col = (jnp.sum(e, axis=1, keepdims=True)
                   + jnp.sum(jnp.where(sel, dyf * CS, zero), axis=1,
                             keepdims=True) * ea
                   - w * q + jnp.where(last_row, dcu_l, zero))
            dcu_c = jnp.where(col_head == h, col, dcu_c)
            ddt_c = jnp.where(col_head == h, el * q, ddt_c)
            dcu_r = jnp.where(row_head == h,
                              -jnp.sum(e, axis=0, keepdims=True), dcu_r)
            ddt_r = jnp.where(row_head == h,
                              jnp.sum(e_nodt, axis=0, keepdims=True), ddt_r)
            ea_m = jnp.where(sel, ea, ea_m)
            w_m = jnp.where(sel, w, w_m)
            dl_m = jnp.where(sel, dl, dl_m)
        dx_ref[0, :, lanes] = (dxp + BdS * w_m).astype(dx_ref.dtype)
        dye = (dyf * ea_m).astype(cdt)
        dC = dC + _dot(dye, Sp.astype(cdt), ((1,), (1,)), acc).astype(f)
        dB = dB + _dot((xf * w_m).astype(cdt), dSp.astype(cdt),
                       ((1,), (1,)), acc).astype(f)
        ds_scr[pk] = dl_m * dSp + _dot(Cm, dye, ((0,), (0,)), acc).astype(f)
    dsc = d_scores.astype(cdt)
    dc_ref[0] = (dC + _dot(dsc, Bm, ((1,), (0,)), acc).astype(f)
                 ).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot(dsc, Cm, ((0,), (0,)), acc).astype(f)
                 ).astype(db_ref.dtype)
    dcuc_ref[0, 0], ddtc_ref[0, 0] = dcu_c, ddt_c
    dcur_ref[0, 0], ddtr_ref[0, 0] = dcu_r, ddt_r


def _decay_operands(dt, A, G, L):
    """``dt`` and the in-chunk running sum of ``dt * A`` in the two forms the
    kernels read: time down the sublanes ([B, G, T, hpg]) and time along the
    lanes ([B, G, hpg, T])."""
    Bsz, T, H = dt.shape
    f = dt.dtype
    cum = jnp.cumsum((dt * A.astype(f)).reshape(Bsz, T // L, L, H),
                     axis=2).reshape(Bsz, T, H)

    def cols(a):
        return jnp.moveaxis(a.reshape(Bsz, T, G, H // G), 2, 1)

    def rows(a):
        return jnp.swapaxes(cols(a), 2, 3)

    return cols(dt), cols(cum), rows(dt), rows(cum)


def _specs(Bsz, T, H, P, G, N, L, reverse):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    nc, hpg = T // L, H // G
    k = _heads_a_pack(P, hpg)
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    wide = pl.BlockSpec((1, L, hpg * P), lambda b, g, c: (b, at(c), g))
    proj = pl.BlockSpec((1, L, N), lambda b, g, c: (b, at(c), g))
    col = pl.BlockSpec((1, 1, L, hpg), lambda b, g, c: (b, g, at(c), 0))
    row = pl.BlockSpec((1, 1, hpg, L), lambda b, g, c: (b, g, 0, at(c)))
    state = pl.BlockSpec((1, 1, 1, hpg // k, N, k * P),
                         lambda b, g, c: (b, g, at(c), 0, 0, 0))
    return (Bsz, G, nc), k, wide, proj, col, row, state


def _ssd_fwd_call(x, Bm, Cm, dt, A, L, P, G):
    """``y`` and the states at the chunks' starts, of flat operands: ``x``
    [B, T, H*P], ``Bm``/``Cm`` [B, T, G*N], ``dt`` [B, T, H] in the state's
    dtype."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    Bsz, T, H = dt.shape
    N, hpg = Bm.shape[2] // G, H // G
    grid, k, wide, proj, col, row, state = _specs(Bsz, T, H, P, G, N, L, False)
    interpret = _interpret()
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, L, P, hpg, k),
        grid=grid,
        in_specs=[wide, proj, proj, col, col, row, row],
        out_specs=(wide, state),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((Bsz, G, T // L, hpg // k, N, k * P),
                                        dt.dtype)),
        scratch_shapes=[pltpu.VMEM((hpg // k, N, k * P), dt.dtype)],
        compiler_params=None if interpret else _compiler_params(
            L, P, N, hpg, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x, Bm, Cm, *_decay_operands(dt, A, G, L))


def _ssd_bwd_call(x, Bm, Cm, dt, A, states, dy, L, P, G):
    """Gradients of the flat operands and of ``dt`` and ``A``."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    Bsz, T, H = dt.shape
    N, hpg, f = Bm.shape[2] // G, H // G, dt.dtype
    grid, k, wide, proj, col, row, state = _specs(Bsz, T, H, P, G, N, L, True)
    interpret = _interpret()
    cols = jax.ShapeDtypeStruct((Bsz, G, T, hpg), f)
    rows = jax.ShapeDtypeStruct((Bsz, G, hpg, T), f)
    dx, dB, dC, dcu_c, dcu_r, ddt_c, ddt_r = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, L, P, hpg, k),
        grid=grid,
        in_specs=[wide, proj, proj, col, col, row, row, state, wide],
        out_specs=(wide, proj, proj, col, row, col, row),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(Bm.shape, Bm.dtype),
                   jax.ShapeDtypeStruct(Cm.shape, Cm.dtype),
                   cols, rows, cols, rows),
        scratch_shapes=[pltpu.VMEM((hpg // k, N, k * P), f)],
        compiler_params=None if interpret else _compiler_params(
            L, P, N, hpg, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x, Bm, Cm, *_decay_operands(dt, A, G, L), states, dy)

    def flat(c, r):   # both forms back to [B, T, H]
        return (jnp.moveaxis(c, 1, 2)
                + jnp.moveaxis(jnp.swapaxes(r, 2, 3), 1, 2)).reshape(Bsz, T, H)

    dcum = flat(dcu_c, dcu_r).reshape(Bsz, T // L, L, H)
    # cum is the running sum inside a chunk: its gradient runs back the same way
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2).reshape(Bsz, T, H)
    ddt = flat(ddt_c, ddt_r) + da * A.astype(f)
    dA = jnp.sum(da * dt, axis=(0, 1)).astype(A.dtype)
    return dx, dB, dC, ddt, dA


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd_core(x, Bm, Cm, dt, A, L, P, G):
    return _ssd_fwd_call(x, Bm, Cm, dt, A, L, P, G)[0]


def _ssd_core_fwd(x, Bm, Cm, dt, A, L, P, G):
    y, states = _ssd_fwd_call(x, Bm, Cm, dt, A, L, P, G)
    return y, (x, Bm, Cm, dt, A, states)


def _ssd_core_bwd(L, P, G, residuals, dy):
    x, Bm, Cm, dt, A, states = residuals
    return _ssd_bwd_call(x, Bm, Cm, dt, A, states, dy, L, P, G)


_ssd_core.defvjp(_ssd_core_fwd, _ssd_core_bwd)


def ssd_scan_fused(x, dt, A, Bm, Cm, chunk: int):
    """Same contract as :func:`ssd_scan_reference`, as the two Mosaic kernels
    (interpret mode off the TPU)."""
    T, x, dt, Bm, Cm = _pad_time(chunk, x, dt, Bm, Cm)
    Bsz, Tp, H, P = x.shape
    G, N = Bm.shape[2:]
    f = _state_dtype(x.dtype)
    y = _ssd_core(x.reshape(Bsz, Tp, H * P), Bm.reshape(Bsz, Tp, G * N),
                  Cm.reshape(Bsz, Tp, G * N), dt.astype(f), A.astype(f),
                  chunk, P, G)
    return y.reshape(Bsz, Tp, H, P)[:, :T]


def ssd_scan(x, dt, A, Bm, Cm, chunk: int):
    """The scan by the variant the ``ssd_scan`` selection site resolves for
    these shapes."""
    from . import select_ssd_scan_variant  # noqa: PLC0415

    Bsz, T, H, P = x.shape
    G, N = Bm.shape[2:]
    variant = select_ssd_scan_variant(Bsz, T, H, P, G, N, chunk,
                                      x.dtype.itemsize)
    fn = ssd_scan_fused if variant == "fused" else ssd_scan_reference
    return fn(x, dt, A, Bm, Cm, chunk)
