"""Ring attention — sequence/context parallelism over the device mesh.

The reference has NO long-context machinery (SURVEY.md §5.7: TBPTT + masking
only, no attention of any kind in 2016). This module is the framework's
first-class long-context tier, built the TPU way (prompt requirement): Q/K/V
live sharded over a ``seq`` mesh axis; each device computes attention of its
query shard against every key/value shard while K/V blocks rotate around the
ICI ring via ``lax.ppermute``. Accumulation uses the online-softmax
(flash-attention) recurrence so nothing materializes beyond one [Tq_local,
Tk_local] score block per step — sequence length scales with the number of
devices at constant per-device memory.

Layout: [batch, heads, time, head_dim], time sharded. Collectives ride ICI
(mesh axis order puts ``seq`` innermost) — the design recipe of the scaling
book: pick a mesh, annotate shardings, let XLA overlap the ppermute with the
block matmuls.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _block_accumulate(q, k, v, m, l, o, scale, causal, q_off, k_off,
                      kmask=None):
    """One online-softmax accumulation of a K/V block into (m, l, o).

    q [B,H,Tq,D]; k,v [B,H,Tk,D]; m,l [B,H,Tq]; o [B,H,Tq,D].
    ``q_off``/``k_off`` are the blocks' global time offsets for causal masks;
    ``kmask`` [B,Tk] marks valid (1) vs padded (0) keys — padded keys get
    score -inf (NOT zero: zero would keep softmax mass exp(0)).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    valid = None
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        qi = q_off + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        ki = k_off + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        valid = (qi >= ki)[None, None]
    if kmask is not None:
        km = kmask[:, None, None, :].astype(bool)
        valid = km if valid is None else jnp.logical_and(valid, km)
    if valid is not None:
        s = jnp.where(valid, s, _NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # guard fully-masked rows (m_new == -inf): exp(-inf - -inf) would be NaN
    m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(jnp.where(m <= _NEG_INF, _NEG_INF, m - m_safe))
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, o_new


def attention(q, k, v, causal: bool = False, scale: Optional[float] = None,
              key_mask=None):
    """Single-device softmax attention (the ring's local/reference case).
    ``key_mask`` [B,T]: 1 = real key, 0 = padding (excluded via -inf score)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    B, H, Tq, D = q.shape
    m = jnp.full((B, H, Tq), _NEG_INF, q.dtype)
    l = jnp.zeros((B, H, Tq), q.dtype)
    o = jnp.zeros((B, H, Tq, v.shape[-1]), q.dtype)
    m, l, o = _block_accumulate(q, k, v, m, l, o, scale, causal, 0, 0, key_mask)
    return o / jnp.maximum(l, 1e-30)[..., None]


def _batch_entry(mesh, batch_axes):
    """The PartitionSpec batch-dim entry for the live batch axes (size-1
    axes trimmed): shard_map treats every mesh axis as manual, so a batch
    axis left out of the in_specs would force GSPMD to all-gather the
    activations over it at the region boundary."""
    live = tuple(a for a in (batch_axes or ()) if mesh.shape.get(a, 1) > 1)
    return live if live else None


def ring_attention(q, k, v, mesh, seq_axis: str = "seq",
                   causal: bool = False, scale: Optional[float] = None,
                   key_mask=None, batch_axes=()):
    """Sequence-parallel attention: time axis sharded over ``seq_axis``.

    Full q/k/v are passed in [B,H,T,D]; shard_map splits T over the mesh
    axis and the K/V shards circulate the ring (P-1 ppermute hops); the
    ``key_mask`` [B,T] shard (padding exclusion) travels with its K block.
    ``batch_axes`` names the mesh axes the batch dim is sharded over
    (kept sharded inside the region). The result equals :func:`attention`
    on the gathered arrays.
    """
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    from jax import shard_map  # noqa: PLC0415

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n_shards = mesh.shape[seq_axis]
    batch = _batch_entry(mesh, batch_axes)
    spec = P(batch, None, seq_axis, None)
    mspec = P(batch, seq_axis)

    local = functools.partial(
        _ring_local, n_shards=n_shards, seq_axis=seq_axis,
        causal=causal, scale=scale,
    )
    if key_mask is None:
        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )(q, k, v)
    return shard_map(
        functools.partial(local, masked=True), mesh=mesh,
        in_specs=(spec, spec, spec, mspec), out_specs=spec,
    )(q, k, v, key_mask)


def _ring_local(q, k, v, kmask=None, *, n_shards, seq_axis, causal, scale,
                masked: bool = False):
    idx = lax.axis_index(seq_axis)
    B, H, Tq, D = q.shape
    m = jnp.full((B, H, Tq), _NEG_INF, q.dtype)
    l = jnp.zeros((B, H, Tq), q.dtype)
    o = jnp.zeros((B, H, Tq, D), q.dtype)
    q_off = idx * Tq
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    k_cur, v_cur, km_cur = k, v, kmask
    for step in range(n_shards):
        src = (idx - step) % n_shards  # origin device of the current K/V block
        m, l, o = _block_accumulate(
            q, k_cur, v_cur, m, l, o, scale, causal, q_off, src * Tq, km_cur
        )
        if step + 1 < n_shards:
            # rotate K/V (and their mask) one hop around the ICI ring
            k_cur = lax.ppermute(k_cur, seq_axis, perm)
            v_cur = lax.ppermute(v_cur, seq_axis, perm)
            if km_cur is not None:
                km_cur = lax.ppermute(km_cur, seq_axis, perm)
    return o / jnp.maximum(l, 1e-30)[..., None]


def all_to_all_attention(q, k, v, mesh, seq_axis: str = "seq",
                         causal: bool = False, scale: Optional[float] = None,
                         key_mask=None, batch_axes=()):
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all swaps the
    sharded axis from time to heads, computes full-sequence attention locally
    per head group, and swaps back. Complements ring attention: better when
    heads ≥ devices and the full sequence fits per device."""
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    from jax import shard_map  # noqa: PLC0415

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"heads ({q.shape[1]}) must divide mesh axis ({n})")
    batch = _batch_entry(mesh, batch_axes)
    spec = P(batch, None, seq_axis, None)
    mspec = P(batch, seq_axis)

    def local(q, k, v, kmask=None):
        # [B, H, T/n, D] -> all_to_all -> [B, H/n, T, D]
        def swap_in(x):
            return lax.all_to_all(x, seq_axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        def swap_out(x):
            return lax.all_to_all(x, seq_axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        if kmask is not None:
            # heads axis is fully replicated in the mask; gather time shards
            kmask = lax.all_gather(kmask, seq_axis, axis=1, tiled=True)
        out = attention(swap_in(q), swap_in(k), swap_in(v),
                        causal=causal, scale=scale, key_mask=kmask)
        return swap_out(out)

    if key_mask is None:
        return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec, mspec),
                     out_specs=spec)(q, k, v, key_mask)
