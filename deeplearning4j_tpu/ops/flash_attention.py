"""Flash attention as a Pallas TPU kernel — blockwise online-softmax with
O(T) memory and a fused custom-VJP backward.

The architectural slot: the reference's cuDNN tier existed to win the hot-op
fight (SURVEY.md §2.3); on TPU the one attention shape XLA does NOT handle
optimally is long-sequence softmax attention, whose naive form materializes
the [T, T] score matrix in HBM. This kernel computes attention in [block_q x
block_k] VMEM tiles with the online-softmax recurrence (running row max m and
denominator l), so HBM traffic is O(T·D) instead of O(T^2):

    m'  = max(m, rowmax(s))
    acc = acc * e^(m - m') + e^(s - m') @ v
    l   = l  * e^(m - m') + rowsum(e^(s - m'))

The backward follows the standard flash recipe: save only (out, lse); rebuild
p = e^(s - lse) per tile and accumulate dq over k-tiles (one kernel) and
dk/dv over q-tiles (a second kernel, which works on the transposed tile
``k q^T`` so that no product needs a transpose and ``lse``/``delta`` broadcast
as the rows they are stored as).

The tile loops. Each kernel loops over the tiles of the other axis inside one
grid program. Under ``causal`` the loop visits only the tiles at or under the
diagonal (:func:`causal_key_tiles`, :func:`causal_query_tiles`): a tile wholly
above it adds exactly 0. The visited tiles come in two runs of one loop body:
tiles wholly under the diagonal take no causal mask at all, tiles the
diagonal crosses take the ``rows >= cols`` select. Without ``causal`` every
tile is walked. :func:`tiles_walked_share` counts what the loops visit.

Precision. The products take ``q``, ``k``, ``v`` and ``do`` in the dtype they
arrive in (bfloat16 tiles go to the MXU as bfloat16) and accumulate in
float32; ``p`` and ``ds`` are cast to that dtype for the products they enter.
Scores, ``m``, ``l``, ``lse``, ``delta``, the exponentials, the rescaling and
every accumulator are float32. float32 inputs multiply in float32.

VMEM note: scores/probabilities are tiled, but each grid program stages the
full per-head K/V [T, D] strip in VMEM (the k-loop runs inside the kernel,
not the grid; the dk/dv kernel stages Q and dO so), so per-program VMEM is
O(T·D), double-buffered. A budget guard in :func:`flash_attention` falls back
to the XLA path beyond ~8 MB of K+V per head — beyond that length, ring
attention (sequence parallelism) is the intended tool anyway.
:func:`default_blocks` sizes the tiles so that the float32 ``[block_q,
block_k]`` temporaries fit beside those strips in the compiler's default
scoped VMEM: no call states a limit.

Sizes of the products. The score product runs over ``q``/``k``'s trailing
axis and the value product over ``v``'s, and the two need not agree
(``d_qk != d_v``). A second, rotary part of the scores may ride beside the
first: ``q_rope`` [B, H, T, Dr] against ``k_rope`` [B, Hr, T, Dr] with ``Hr``
dividing ``H`` (latent attention: one rotary key for every head, ``Hr`` 1,
read in place through the block index like a shared key/value head), so that
``s = (q k^T + q_rope k_rope^T) * scale``: two products that each start on a
lane boundary instead of one over a width that is no multiple of the lanes.
A call without a rotary part and with ``d_qk == d_v`` lowers to the program
it lowered to before these existed.

Used by SelfAttentionLayer via ``attention_impl="flash"``; interpret mode
(CPU) runs identical code for tests. Causal masking and key padding masks are
applied inside the tiles. Inputs [B, H, T, D], same contract as
``parallel.ring_attention.attention`` (which remains the XLA reference path).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_kernels import _interpret

_NEG_INF = -1e30
_KV_VMEM_BUDGET_BYTES = 8 * 1024 * 1024
_LANES = 128
# the compiler's default scoped VMEM on the chips this runs on; the tiles are
# sized to stay inside it (see default_blocks)
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def causal_key_tiles(qi0, block_q: int, block_k: int):
    """``(full, end)`` for the query tile whose first row is ``qi0``: key
    tiles ``[0, full)`` lie wholly at or under the causal diagonal (``rows >=
    cols`` everywhere: no mask), ``[full, end)`` are crossed by it, and tiles
    from ``end`` on lie wholly above it and are never visited. ``qi0`` is a
    Python int or a traced int32."""
    return (qi0 + 1) // block_k, (qi0 + block_q + block_k - 1) // block_k


def causal_query_tiles(kj0, block_q: int, block_k: int):
    """``(start, full)`` for the key tile whose first column is ``kj0``:
    query tiles ``[0, start)`` lie wholly above the causal diagonal and are
    never visited, ``[start, full)`` are crossed by it, and tiles from
    ``full`` on lie wholly at or under it (no mask)."""
    return kj0 // block_q, (kj0 + block_k + block_q - 2) // block_q


def tiles_walked_share(t: int, block_q: int, block_k: int,
                       causal: bool) -> float:
    """Tiles the three kernels' loops visit over the tiles of the full
    square, for ``t`` positions padded to the blocks' common multiple as the
    call pads them: 1.0 without ``causal``, ``(n + 1) / 2n`` with it for
    ``n`` equal tiles a side."""
    if not causal:
        return 1.0
    lcm = math.lcm(block_q, block_k)
    t = -(-t // lcm) * lcm
    nq, nk = t // block_q, t // block_k
    by_query = sum(causal_key_tiles(i * block_q, block_q, block_k)[1]
                   for i in range(nq))          # flash_fwd and flash_bwd_dq
    by_key = sum(nq - causal_query_tiles(j * block_k, block_q, block_k)[0]
                 for j in range(nk))            # flash_bwd_dkv
    return (2 * by_query + by_key) / (3.0 * nq * nk)


def default_blocks(t: int, d: int, itemsize: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` from the shapes. A loop iteration should be MXU
    work and not loop overhead, so the tile is the largest power-of-two
    multiple of the lane width that (a) pads ``t`` no further than 128-wide
    tiles would, and (b) leaves the float32 ``[block_q, block_k]`` temporaries
    of an iteration (scores, probabilities and their gradients: five of
    them, and two casts for the MXU) room beside the double-buffered
    ``[t, d]`` strips in the scoped VMEM. A sequence shorter than a lane tile
    is one tile."""
    if t <= _LANES:
        return max(t, 1), max(t, 1)
    padded = -(-t // _LANES) * _LANES
    strips = 2 * 2 * padded * d * itemsize
    block = _LANES
    while (padded % (2 * block) == 0
           and strips + (2 * block) ** 2 * (5 * 4 + 2 * itemsize)
           <= _SCOPED_VMEM_BYTES):
        block *= 2
    return block, block


def _dot(a, b, contract=(1, 0)):
    """``a @ b`` on the operands as they are, accumulated in float32 (never
    below the operands' own dtype). A product of 16-bit operands is exact in
    float32 whatever ``jax.default_matmul_precision`` says, and Mosaic
    refuses a float32 contract precision on them: they state the default."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=None if a.dtype.itemsize >= 4 else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.promote_types(a.dtype, jnp.float32))


def _dot_nt(a, b):
    """``a @ b.T`` contracting the last axes directly: no transpose."""
    return _dot(a, b, contract=(1, 1))


def _tile_start(i, block: int):
    """First row of tile ``i``; a loop's index carries the alignment the
    dynamic slice needs, a lone tile's is the literal 0."""
    return 0 if isinstance(i, int) else pl.multiple_of(i * block, block)


def _rows_minus_cols(rows: int, cols: int):
    """``row index - column index`` of a ``[rows, cols]`` tile: the causal
    select keeps where this is at least ``first column - first row``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _scores(a, b, scale: float, keep, under, rope=None):
    """The score tile ``a b^T * scale`` in float32 (``rope``: a second pair
    whose product is added before the scale), with ``_NEG_INF`` where the
    key mask ``keep`` or the causal select ``under`` (boolean arrays that
    broadcast against the tile; ``under`` is ``None`` off the diagonal) says
    no."""
    s = _dot_nt(a, b)
    if rope is not None:
        s = s + _dot_nt(*rope)
    s = jnp.where(keep, s * scale, _NEG_INF)
    if under is not None:
        s = jnp.where(under, s, _NEG_INF)
    return s


def _two_runs(body, init, n_tiles: int, runs, diagonal_first: bool):
    """The tile loop over an axis of ``n_tiles``. ``runs`` is ``None``
    without ``causal`` (one run over every tile, no causal mask) or the
    traced ``(first, split, last)``: two runs of the same body split at
    ``split``, the run on the diagonal's side with the mask. A lone tile is
    no loop (the diagonal crosses it), so its slices are static whatever its
    size."""
    if n_tiles == 1:
        return body(runs is not None)(0, init)
    if runs is None:
        return jax.lax.fori_loop(0, n_tiles, body(False), init)
    first, split, last = runs
    carry = jax.lax.fori_loop(first, split, body(diagonal_first), init)
    return jax.lax.fori_loop(split, last, body(not diagonal_first), carry)


def _split_refs(refs, rope: bool):
    """A kernel's refs as ``(q, k, v, mask, q_rope, k_rope, the rest)``:
    the rotary pair follows the mask where the call has one (``None``s
    otherwise), then come the kernel's further inputs and its outputs."""
    n = 6 if rope else 4
    return (*refs[:4], *(refs[4:6] if rope else (None, None)), refs[n:])


def _fwd_kernel(block_k: int, causal: bool, scale: float, rope: bool, *refs):
    """One q-tile vs the k-tiles at or under its diagonal. Refs: q [1,Bq,D];
    k [1,T,D]; v [1,T,Dv]; mask [1,1,T]; with ``rope`` also q_rope [1,Bq,Dr]
    and k_rope [1,T,Dr]; out o [1,Bq,Dv], lse [1,1,Bq]. (Mask/lse ride a
    unit middle axis: TPU lowering requires each block's last two dims to
    divide (8, 128) or equal the array dims — a [1, T] block on a [BH, T]
    array violates the sublane rule, a [1, 1, T] block on [BH, 1, T] does
    not.)"""
    q_ref, k_ref, v_ref, mask_ref, qr_ref, kr_ref, (o_ref, lse_ref) = \
        _split_refs(refs, rope)
    q = q_ref[0]  # [Bq, D]
    qr = qr_ref[0] if rope else None
    bq = q.shape[0]
    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    qi0 = pl.program_id(1) * bq

    def body(on_diagonal):
        def step(j, carry):
            acc, m, l = carry
            k0 = _tile_start(j, block_k)
            k = k_ref[0, pl.dslice(k0, block_k), :]
            v = v_ref[0, pl.dslice(k0, block_k), :]
            s = _scores(  # [Bq, Bk]
                q, k, scale, mask_ref[0, :, pl.dslice(k0, block_k)] > 0,
                _rows_minus_cols(bq, block_k) >= k0 - qi0 if on_diagonal
                else None,
                (qr, kr_ref[0, pl.dslice(k0, block_k), :]) if rope else None)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            # Rows with NO valid key yet have m_new == _NEG_INF; exp(s -
            # m_new) would then be exp(0) = 1 at every masked position (the
            # reference guards this with m_safe + explicit zeroing —
            # ring_attention.py). Subtracting 0 instead keeps exp(-1e30) == 0
            # for those rows.
            m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
            alpha = jnp.exp(jnp.where(m <= _NEG_INF / 2, m_safe, m) - m_safe)
            p = jnp.exp(s - m_safe)
            acc = acc * alpha + _dot(p.astype(v.dtype), v)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            return acc, m_new, l
        return step

    acc0 = jnp.zeros((bq, v_ref.shape[-1]), acc_t)
    m0 = jnp.full((bq, 1), _NEG_INF, acc_t)
    l0 = jnp.zeros((bq, 1), acc_t)
    runs = (0, *causal_key_tiles(qi0, bq, block_k)) if causal else None
    acc, m, l = _two_runs(body, (acc0, m0, l0), k_ref.shape[1] // block_k,
                          runs, diagonal_first=False)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    # Fully-masked rows (l == 0): out = 0, and lse = 0 (finite) so the
    # backward's exp(s - lse) = exp(-1e30) = 0 instead of exp(0) = 1.
    m_fin = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    lse = jnp.where(l > 0, m_fin + jnp.log(l_safe), 0.0)
    lse_ref[0, 0] = lse[:, 0].astype(lse_ref.dtype)


def _dq_kernel(block_k: int, causal: bool, scale: float, rope: bool, *refs):
    """dq (and dq_rope) for one q-tile: loop over the k-tiles at or under
    its diagonal (flash backward, dq pass). Refs: q, k, v, mask, [q_rope,
    k_rope,] do, lse, delta; out dq [, dq_rope]."""
    q_ref, k_ref, v_ref, mask_ref, qr_ref, kr_ref, \
        (do_ref, lse_ref, delta_ref, *outs) = _split_refs(refs, rope)
    q = q_ref[0]
    qr = qr_ref[0] if rope else None
    do = do_ref[0]
    bq = q.shape[0]
    acc_t = jnp.promote_types(q.dtype, jnp.float32)
    lse = lse_ref[0, 0].astype(acc_t)[:, None]      # [Bq, 1]
    delta = delta_ref[0, 0].astype(acc_t)[:, None]  # rowsum(do * o)
    qi0 = pl.program_id(1) * bq

    def body(on_diagonal):
        def step(j, carry):
            k0 = _tile_start(j, block_k)
            k = k_ref[0, pl.dslice(k0, block_k), :]
            v = v_ref[0, pl.dslice(k0, block_k), :]
            kr = kr_ref[0, pl.dslice(k0, block_k), :] if rope else None
            s = _scores(
                q, k, scale, mask_ref[0, :, pl.dslice(k0, block_k)] > 0,
                _rows_minus_cols(bq, block_k) >= k0 - qi0 if on_diagonal
                else None, (qr, kr) if rope else None)
            p = jnp.exp(s - lse)  # [Bq, Bk]
            dp = _dot_nt(do, v)  # [Bq, Bk]
            ds = (p * (dp - delta)).astype(k.dtype)
            if rope:
                return carry[0] + _dot(ds, k), carry[1] + _dot(ds, kr)
            return carry + _dot(ds, k)
        return step

    runs = (0, *causal_key_tiles(qi0, bq, block_k)) if causal else None
    zero = jnp.zeros(q.shape, acc_t)
    init = (zero, jnp.zeros(qr.shape, acc_t)) if rope else zero
    got = _two_runs(body, init, k_ref.shape[1] // block_k, runs,
                    diagonal_first=False)
    for ref, dq in zip(outs, got if rope else (got,)):
        ref[0] = (dq * scale).astype(ref.dtype)


def _dkv_kernel(block_q: int, causal: bool, scale: float, rope: bool, *refs):
    """dk/dv for one k-tile: loop over the q-tiles at or under its diagonal
    (flash backward, dk/dv pass), on the transposed tile ``k q^T`` [Bk, Bq].
    Refs: q/do [1,T,D*]; k/v tile [1,Bk,D*]; mask tile [1,1,Bk] (unit middle
    axis — see _fwd_kernel); [q_rope [1,T,Dr], k_rope tile [1,Bk,Dr];]
    lse/delta [1,1,T]; out dk, dv [, dk_rope: this query head's part]."""
    q_ref, k_ref, v_ref, mask_ref, qr_ref, kr_ref, \
        (do_ref, lse_ref, delta_ref, *outs) = _split_refs(refs, rope)
    k = k_ref[0]
    v = v_ref[0]
    kr = kr_ref[0] if rope else None
    bk = k.shape[0]
    acc_t = jnp.promote_types(k.dtype, jnp.float32)
    kj0 = pl.program_id(1) * bk
    keep = mask_ref[0, 0][:, None] > 0  # [Bk, 1]

    def body(on_diagonal):
        def step(i, carry):
            q0 = _tile_start(i, block_q)
            q = q_ref[0, pl.dslice(q0, block_q), :]
            do = do_ref[0, pl.dslice(q0, block_q), :]
            qr = qr_ref[0, pl.dslice(q0, block_q), :] if rope else None
            lse = lse_ref[0, :, pl.dslice(q0, block_q)].astype(acc_t)
            delta = delta_ref[0, :, pl.dslice(q0, block_q)].astype(acc_t)
            st = _scores(  # [Bk, Bq]: keys down, queries across
                k, q, scale, keep,
                _rows_minus_cols(bk, block_q) <= q0 - kj0 if on_diagonal
                else None, (kr, qr) if rope else None)
            pt = jnp.exp(st - lse)
            dv = carry[1] + _dot(pt.astype(do.dtype), do)
            dpt = _dot_nt(v, do)
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dk = carry[0] + _dot(dst, q)
            if rope:
                return dk, dv, carry[2] + _dot(dst, qr)
            return dk, dv
        return step

    nq = q_ref.shape[1] // block_q
    runs = (*causal_query_tiles(kj0, block_q, bk), nq) if causal else None
    zero = jnp.zeros(k.shape, acc_t)
    init = (zero, zero if v.shape == k.shape else jnp.zeros(v.shape, acc_t))
    if rope:
        init += (jnp.zeros(kr.shape, acc_t),)
    got = _two_runs(body, init, nq, runs, diagonal_first=True)
    outs[0][0] = (got[0] * scale).astype(outs[0].dtype)
    outs[1][0] = got[1].astype(outs[1].dtype)
    if rope:
        outs[2][0] = (got[2] * scale).astype(outs[2].dtype)


def _pad_to(x, axis: int, mult: int):
    t = x.shape[axis]
    pad = (-t) % mult
    if pad == 0:
        return x, t
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), t


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash_core(q, k, v, mask, q_rope, k_rope, causal, scale, block_q,
                block_k):
    """``q`` [B*H, T, D] against ``k`` [B*Hkv, T, D] and ``v`` [B*Hkv, T,
    Dv]: query head ``i`` reads key/value head ``i // (H // Hkv)`` through
    the block index, so a shared head is never copied. ``q_rope`` [B*H, T,
    Dr] / ``k_rope`` [B*Hr, T, Dr] (or both ``None``) add the rotary part of
    the scores, the rotary key read the same way."""
    out, _ = _flash_fwd(q, k, v, mask, q_rope, k_rope, causal, scale,
                        block_q, block_k)
    return out


def _specs(bh, t, q, k, v, rope, block_q, block_k, by_query: bool):
    """The in_specs of ``q, k, v, mask[, q_rope, k_rope]`` for a kernel whose
    grid runs over query tiles (``by_query``: whole key strips) or over key
    tiles (whole query strips)."""
    grp = bh // k.shape[0]     # query heads a key/value head
    dq, dv = q.shape[-1], v.shape[-1]
    if by_query:
        mine = lambda d: pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))  # noqa: E731
        strip = lambda d, g: pl.BlockSpec(  # noqa: E731
            (1, t, d), lambda b, i: (b // g, 0, 0))
        specs = [mine(dq), strip(dq, grp), strip(dv, grp),
                 pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0))]
        if rope is not None:
            specs += [mine(rope[0].shape[-1]),
                      strip(rope[1].shape[-1], bh // rope[1].shape[0])]
        return specs
    tile = lambda d, g: pl.BlockSpec(  # noqa: E731
        (1, block_k, d), lambda b, j: (b // g, j, 0))
    strip = lambda d: pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0))  # noqa: E731
    specs = [strip(dq), tile(dq, grp), tile(dv, grp),
             pl.BlockSpec((1, 1, block_k), lambda b, j: (b, 0, j))]
    if rope is not None:
        specs += [strip(rope[0].shape[-1]),
                  tile(rope[1].shape[-1], bh // rope[1].shape[0])]
    return specs


def _flash_call(q, k, v, mask, q_rope, k_rope, causal, scale, block_q,
                block_k):
    bh, t, _ = q.shape
    dv = v.shape[-1]
    rope = None if q_rope is None else (q_rope, k_rope)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_k, causal, scale,
                          rope is not None),
        grid=(bh, t // block_q),
        in_specs=_specs(bh, t, q, k, v, rope, block_q, block_k, True),
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v, mask, *(rope or ()))


def _flash_fwd(q, k, v, mask, q_rope, k_rope, causal, scale, block_q,
               block_k):
    out, lse = _flash_call(q, k, v, mask, q_rope, k_rope, causal, scale,
                           block_q, block_k)
    return out, (q, k, v, mask, q_rope, k_rope, out, lse)


def _shared_sum(d, heads: int, like):
    """The gradient of a head that ``d.shape[0] // heads`` query heads share:
    the sum over them."""
    grp = d.shape[0] // heads
    if grp == 1:
        return d
    return d.reshape((heads, grp) + d.shape[1:]).sum(axis=1).astype(like.dtype)


def _flash_bwd(causal, scale, block_q, block_k, residuals, g):
    q, k, v, mask, q_rope, k_rope, out, lse = residuals
    bh, t, d = q.shape
    dv_ = v.shape[-1]
    rope = None if q_rope is None else (q_rope, k_rope)
    dr = rope[0].shape[-1] if rope else 0
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    row_q = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
    dq_specs = [pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))]
    dq_shapes = [jax.ShapeDtypeStruct((bh, t, d), q.dtype)]
    if rope:
        dq_specs.append(pl.BlockSpec((1, block_q, dr), lambda b, i: (b, i, 0)))
        dq_shapes.append(jax.ShapeDtypeStruct((bh, t, dr), q_rope.dtype))
    dqs = pl.pallas_call(
        functools.partial(_dq_kernel, block_k, causal, scale, bool(rope)),
        grid=(bh, t // block_q),
        in_specs=_specs(bh, t, q, k, v, rope, block_q, block_k, True) + [
            pl.BlockSpec((1, block_q, dv_), lambda b, i: (b, i, 0)),
            row_q, row_q,
        ],
        out_specs=dq_specs if rope else dq_specs[0],
        out_shape=dq_shapes if rope else dq_shapes[0],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, mask, *(rope or ()), g, lse, delta)
    dq, dq_rope = dqs if rope else (dqs, None)
    row_t = pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0))
    tile = lambda w: pl.BlockSpec((1, block_k, w), lambda b, j: (b, j, 0))  # noqa: E731
    dkv_specs = [tile(d), tile(dv_)]
    dkv_shapes = [jax.ShapeDtypeStruct((bh, t, d), k.dtype),
                  jax.ShapeDtypeStruct((bh, t, dv_), v.dtype)]
    if rope:
        dkv_specs.append(tile(dr))
        dkv_shapes.append(jax.ShapeDtypeStruct((bh, t, dr), k_rope.dtype))
    dk, dv, *dk_rope = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q, causal, scale, bool(rope)),
        grid=(bh, t // block_k),
        in_specs=_specs(bh, t, q, k, v, rope, block_q, block_k, False) + [
            pl.BlockSpec((1, t, dv_), lambda b, j: (b, 0, 0)),
            row_t, row_t,
        ],
        out_specs=dkv_specs,
        out_shape=dkv_shapes,
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, mask, *(rope or ()), g, lse, delta)
    # a shared head's gradient is the sum over its query heads
    dk, dv = _shared_sum(dk, k.shape[0], k), _shared_sum(dv, v.shape[0], v)
    if rope:
        return (dq, dk, dv, None, dq_rope,
                _shared_sum(dk_rope[0], k_rope.shape[0], k_rope))
    return dq, dk, dv, None, None, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def _lanes(d: int) -> int:
    return -(-d // _LANES) * _LANES


def strip_width(d_qk: int, d_v: int, d_rope: int = 0) -> int:
    """The width :func:`default_blocks` sizes the tiles for: what one of the
    two whole-sequence strips a kernel stages takes in VMEM, in elements a
    position. A plain call (``d_qk == d_v``, no rotary part) stages two
    strips of ``d``; otherwise the strips (the score part, the rotary part
    and the values, each padded to whole lane tiles) are counted two by
    two."""
    if not d_rope and d_qk == d_v:
        return d_qk
    return (_lanes(d_qk) + _lanes(d_v) + (_lanes(d_rope) if d_rope else 0)) // 2


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, key_mask=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    q_rope=None, k_rope=None):
    """Blockwise flash attention. q: [B, H, T, D]; k: [B, Hkv, T, D]; v: [B,
    Hkv, T, Dv] (``Dv`` need not be ``D``) with ``Hkv`` dividing ``H``
    (grouped-query heads: query head ``i`` reads key/value head ``i // (H //
    Hkv)`` in place); key_mask: [B, T] (1 = real key). With ``Hkv == H`` the
    contract of ``ring_attention.attention``. ``q_rope`` [B, H, T, Dr] and
    ``k_rope`` [B, Hr, T, Dr] (``Hr`` dividing ``H``; 1: one rotary key for
    every head) add ``q_rope k_rope^T`` to the scores; ``scale`` then
    defaults to ``(D + Dr) ** -0.5``. Returns [B, H, T, Dv].

    T is padded internally to a block multiple (padded keys masked out,
    padded query rows sliced off), so any sequence length works; the tiles
    come from :func:`default_blocks` unless given, and shrink automatically
    for short sequences.
    """
    b, h, t, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    if h % hkv:
        raise ValueError(f"{h} query heads do not share {hkv} key/value heads")
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    dr = 0 if q_rope is None else q_rope.shape[-1]
    if dr and h % k_rope.shape[1]:
        raise ValueError(f"{h} query heads do not share {k_rope.shape[1]} "
                         "rotary keys")
    scale = float(scale if scale is not None else (d + dr) ** -0.5)
    width = strip_width(d, dv, dr)
    # K+V strip per grid program must fit VMEM (see module docstring);
    # past the budget the XLA reference path is used instead — same
    # measured-default fallback philosophy as ops/__init__'s LSTM helper.
    if 2 * t * width * q.dtype.itemsize > _KV_VMEM_BUDGET_BYTES:
        from ..parallel.ring_attention import attention as _xla_attention

        if hkv != h:
            k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
        if dr:
            q = jnp.concatenate([q, q_rope], axis=-1)
            k = jnp.concatenate(
                [k, jnp.repeat(k_rope, h // k_rope.shape[1], axis=1)], axis=-1)
        return _xla_attention(q, k, v, causal=causal, scale=scale,
                              key_mask=key_mask)
    auto_q, auto_k = default_blocks(t, width, q.dtype.itemsize)
    block_q = min(block_q or auto_q, max(t, 1))
    block_k = min(block_k or auto_k, max(t, 1))

    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * hkv, t, d)
    vf = v.reshape(b * hkv, t, dv)
    if key_mask is None:
        mask = jnp.ones((b, t), jnp.float32)
    else:
        mask = key_mask.astype(jnp.float32)
    maskf = jnp.repeat(mask[:, None, :], h, axis=1).reshape(b * h, 1, t)

    # one pad straight to the lcm: q must reach a block_k multiple for the
    # dkv q-loop and k a block_q multiple for the dq k-loop; zero mask
    # padding == masked out
    lcm = math.lcm(block_q, block_k)
    qf, t_real = _pad_to(qf, 1, lcm)
    kf, _ = _pad_to(kf, 1, lcm)
    vf, _ = _pad_to(vf, 1, lcm)
    maskf, _ = _pad_to(maskf, 2, lcm)
    qrf = krf = None
    if dr:
        qrf, _ = _pad_to(q_rope.reshape(b * h, t, dr), 1, lcm)
        krf, _ = _pad_to(k_rope.reshape(-1, t, dr), 1, lcm)

    out = _flash_core(qf, kf, vf, maskf, qrf, krf, causal, scale, block_q,
                      block_k)
    return out[:, :t_real, :].reshape(b, h, t_real, dv)
