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
dk/dv over q-tiles (a second kernel).

VMEM note: scores/probabilities are tiled, but each grid program stages the
full per-head K/V [T, D] strip in VMEM (the k-loop runs inside the kernel,
not the grid), so per-program VMEM is O(T·D). A budget guard in
:func:`flash_attention` falls back to the XLA path beyond ~8 MB of K+V per
head — beyond that length, ring attention (sequence parallelism) is the
intended tool anyway. Grid-tiled K/V streaming is the upgrade path.

Used by SelfAttentionLayer via ``attention_impl="flash"``; interpret mode
(CPU) runs identical code for tests. Causal masking and key padding masks are
applied inside the tiles. Inputs [B, H, T, D], same contract as
``parallel.ring_attention.attention`` (which remains the XLA reference path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_kernels import _interpret

_NEG_INF = -1e30
_KV_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _fwd_kernel(block_k: int, causal: bool, scale: float,
                q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref):
    """One q-tile vs all k-tiles. Refs: q [1,Bq,D]; k/v [1,T,D]; mask
    [1,1,T]; out o [1,Bq,D], lse [1,1,Bq]. (Mask/lse ride a unit middle axis:
    TPU lowering requires each block's last two dims to divide (8, 128) or
    equal the array dims — a [1, T] block on a [BH, T] array violates the
    sublane rule, a [1, 1, T] block on [BH, 1, T] does not.)"""
    q = q_ref[0].astype(jnp.float32)  # [Bq, D]
    bq, d = q.shape
    t = k_ref.shape[1]
    qi0 = pl.program_id(1) * bq

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * scale  # [Bq, Bk]
        kmask = mask_ref[0, 0, pl.dslice(j * block_k, block_k)]  # [Bk]
        s = jnp.where(kmask[None, :] > 0, s, _NEG_INF)
        if causal:
            rows = qi0 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # Rows with NO valid key yet have m_new == _NEG_INF; exp(s - m_new)
        # would then be exp(0) = 1 at every masked position (the reference
        # guards this with m_safe + explicit zeroing — ring_attention.py).
        # Subtracting 0 instead keeps exp(-1e30) == 0 for those rows.
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        alpha = jnp.exp(jnp.where(m <= _NEG_INF / 2, m_safe, m) - m_safe)
        p = jnp.exp(s - m_safe[:, None])
        acc = acc * alpha[:, None] + p @ v
        l = l * alpha + p.sum(axis=-1)
        return acc, m_new, l

    nk = t // block_k
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # Fully-masked rows (l == 0): out = 0, and lse = 0 (finite) so the
    # backward's exp(s - lse) = exp(-1e30) = 0 instead of exp(0) = 1.
    m_fin = jnp.where(m <= _NEG_INF / 2, 0.0, m)
    lse = jnp.where(l > 0, m_fin + jnp.log(l_safe), 0.0)
    lse_ref[0, 0] = lse.astype(lse_ref.dtype)


def _dq_kernel(block_k: int, causal: bool, scale: float,
               q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               dq_ref):
    """dq for one q-tile: loop over k-tiles (flash backward, dq pass)."""
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = delta_ref[0, 0].astype(jnp.float32)  # rowsum(do * o)
    bq, d = q.shape
    t = k_ref.shape[1]
    qi0 = pl.program_id(1) * bq

    def body(j, dq):
        k = k_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k.T) * scale
        kmask = mask_ref[0, 0, pl.dslice(j * block_k, block_k)]
        s = jnp.where(kmask[None, :] > 0, s, _NEG_INF)
        if causal:
            rows = qi0 + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])  # [Bq, Bk]
        dp = do @ v.T  # [Bq, Bk]
        ds = p * (dp - delta[:, None])
        return dq + (ds @ k) * scale

    dq = jax.lax.fori_loop(0, t // block_k, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(block_q: int, causal: bool, scale: float,
                q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref):
    """dk/dv for one k-tile: loop over q-tiles (flash backward, dk/dv pass).
    Refs: k/v tile [1,Bk,D]; q/do [1,T,D]; lse/delta [1,1,T]; mask tile
    [1,1,Bk] (unit middle axis — see _fwd_kernel)."""
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    tq = q_ref.shape[1]
    kj0 = pl.program_id(1) * bk
    kmask = mask_ref[0, 0]  # [Bk]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q)].astype(jnp.float32)
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q)].astype(jnp.float32)
        s = (q @ k.T) * scale  # [Bq, Bk]
        s = jnp.where(kmask[None, :] > 0, s, _NEG_INF)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            cols = kj0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv = dv + p.T @ do
        dp = do @ v.T
        ds = p * (dp - delta[:, None])
        dk = dk + (ds.T @ q) * scale
        return dk, dv

    zero = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, tq // block_q, body, (zero, zero))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pad_to(x, axis: int, mult: int):
    t = x.shape[axis]
    pad = (-t) % mult
    if pad == 0:
        return x, t
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), t


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, mask, causal, scale, block_q, block_k):
    """``q`` [B*H, T, D] against ``k``/``v`` [B*Hkv, T, D]: query head ``i``
    reads key/value head ``i // (H // Hkv)`` through the block index, so a
    shared head is never copied."""
    out, _ = _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k)
    return out


def _flash_call(q, k, v, mask, causal, scale, block_q, block_k):
    bh, t, d = q.shape
    grp = bh // k.shape[0]     # query heads a key/value head
    grid = (bh, t // block_q)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, block_k, causal, scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b // grp, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b // grp, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, t), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v, mask)


def _flash_fwd(q, k, v, mask, causal, scale, block_q, block_k):
    out, lse = _flash_call(q, k, v, mask, causal, scale, block_q, block_k)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, residuals, g):
    q, k, v, mask, out, lse = residuals
    bh, t, d = q.shape
    grp = bh // k.shape[0]
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[:, None, :]
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_k, causal, scale),
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b // grp, 0, 0)),
            pl.BlockSpec((1, t, d), lambda b, i: (b // grp, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, mask, g, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q, causal, scale),
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b // grp, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b // grp, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, j: (b, 0, j)),
            pl.BlockSpec((1, t, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, t), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, mask, g, lse, delta)
    if grp > 1:   # a shared head's gradient is the sum over its query heads
        dk = dk.reshape(bh // grp, grp, t, d).sum(axis=1).astype(k.dtype)
        dv = dv.reshape(bh // grp, grp, t, d).sum(axis=1).astype(v.dtype)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, key_mask=None,
                    block_q: int = 128, block_k: int = 128):
    """Blockwise flash attention. q: [B, H, T, D]; k/v: [B, Hkv, T, D] with
    ``Hkv`` dividing ``H`` (grouped-query heads: query head ``i`` reads
    key/value head ``i // (H // Hkv)`` in place); key_mask: [B, T] (1 = real
    key). With ``Hkv == H`` the contract of ``ring_attention.attention``.

    T is padded internally to a block multiple (padded keys masked out,
    padded query rows sliced off), so any sequence length works; block sizes
    shrink automatically for short sequences.
    """
    b, h, t, d = q.shape
    hkv = k.shape[1]
    if h % hkv:
        raise ValueError(f"{h} query heads do not share {hkv} key/value heads")
    scale = float(scale if scale is not None else d ** -0.5)
    # K+V strip per grid program must fit VMEM (see module docstring);
    # past the budget the XLA reference path is used instead — same
    # measured-default fallback philosophy as ops/__init__'s LSTM helper.
    if 2 * t * d * q.dtype.itemsize > _KV_VMEM_BUDGET_BYTES:
        from ..parallel.ring_attention import attention as _xla_attention

        if hkv != h:
            k, v = (jnp.repeat(a, h // hkv, axis=1) for a in (k, v))
        return _xla_attention(q, k, v, causal=causal, scale=scale,
                              key_mask=key_mask)
    block_q = min(block_q, max(t, 1))
    block_k = min(block_k, max(t, 1))

    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * hkv, t, d)
    vf = v.reshape(b * hkv, t, d)
    if key_mask is None:
        mask = jnp.ones((b, t), jnp.float32)
    else:
        mask = key_mask.astype(jnp.float32)
    maskf = jnp.repeat(mask[:, None, :], h, axis=1).reshape(b * h, 1, t)

    # one pad straight to the lcm: q must reach a block_k multiple for the
    # dkv q-loop and k a block_q multiple for the dq k-loop; zero mask
    # padding == masked out
    import math

    lcm = math.lcm(block_q, block_k)
    qf, t_real = _pad_to(qf, 1, lcm)
    kf, _ = _pad_to(kf, 1, lcm)
    vf, _ = _pad_to(vf, 1, lcm)
    maskf, _ = _pad_to(maskf, 2, lcm)

    out = _flash_core(qf, kf, vf, maskf, causal, scale, block_q, block_k)
    return out[:, :t_real, :].reshape(b, h, t_real, d)
