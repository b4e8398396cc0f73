"""Grouped matrix products over rows sorted by group — what a layer of routed
experts computes: every row of ``lhs`` [M, K] is multiplied with the matrix of
its own group out of ``rhs`` [E, K, N].

Two variants, one site (``grouped_matmul`` in :mod:`.kernel_select`):

- ``reference`` — ``jax.lax.ragged_dot`` over the groups' sizes, XLA's own
  grouped product, differentiated by autodiff.
- ``fused`` — :func:`grouped_matmul_fused`: three Mosaic kernels under one
  ``custom_vjp``, ``grouped_matmul_fwd`` (``lhs @ rhs[g]``),
  ``grouped_matmul_dlhs`` (the same body on ``dout`` with ``rhs[g]``
  transposed) and ``grouped_matmul_drhs`` (``lhs^T @ dout`` summed a group).
  Rows come in tiles of :data:`ROW_TILE` and **a tile belongs to one group**:
  the caller lays each group's rows out from a tile's start and pads the
  group to whole tiles with zero rows (:func:`aligned_layout`), so the
  kernels need no masks inside a tile. ``tile_group`` [M / ROW_TILE] names
  each tile's group and ``n_tiles`` how many tiles are in use; both ride in
  scalar memory and steer the block indices: a group's matrix is fetched
  once for all its consecutive tiles, a tile past the last one in use
  fetches nothing, computes nothing and is written as zeros. Every group
  owns at least one tile, so ``drhs`` writes every group's block (zeros for
  a group without rows). Products take the operands' dtype with a float32
  accumulator (never below the operands' own precision).

The time of all three follows the tiles in use, that is the rows that landed,
as ``ragged_dot``'s does, at the MXU's rate instead of XLA's 7% of it
(PERF.md, PR 30).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..analysis.annotations import jit_entry
from .pallas_kernels import (_SEQ_MIN_VMEM_LIMIT_BYTES, _acc_dtype,
                             _interpret, _seq_vmem_budget)

ROW_TILE = 256     # rows a tile: two passes of the v5e's 128-row MXU tiles
_LANES = 128
_SPLIT = 1024      # the widest slice of a split axis of the drhs block


def aligned_layout(sizes, align: int, slots: int):
    """Where the rows of groups of ``sizes`` [E] lie when every group starts
    on a multiple of ``align`` and owns at least ``align`` slots. Returns
    ``(group, index, valid, padded)``: each of the ``slots`` slots' group,
    its row's index among the rows sorted by group, whether it holds a row,
    and the groups' padded sizes (their sum is what the layout needs; slots
    past it hold nothing). ``align`` 1 with no empty group is the sorted
    order itself."""
    sizes = sizes.astype(jnp.int32)
    padded = jnp.maximum(1, -(-sizes // align)) * align
    ends = jnp.cumsum(padded)
    slot = jnp.arange(slots, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(ends, slot, side="right"),
                        sizes.shape[0] - 1).astype(jnp.int32)
    within = slot - (ends - padded)[group]
    valid = (within < sizes[group]) & (slot < ends[-1])
    index = jnp.where(valid, (jnp.cumsum(sizes) - sizes)[group] + within, 0)
    return group, index, valid, padded


# ------------------------------------------------------------- fused variant
def _split(n: int) -> int:
    """The widest slice of an axis of ``n`` that is whole lane tiles, divides
    it and is at most ``_SPLIT``; ``n`` itself where there is none."""
    if n % _LANES:
        return n
    best = n if n <= _SPLIT else _LANES
    for t in range(_LANES, min(n, _SPLIT) + 1, _LANES):
        if n % t == 0:
            best = t
    return best


def _drhs_blocks(K: int, N: int):
    """(tk, tn) of the drhs kernel's output block: one axis split, the one
    that can be (a lane-tiled ``K`` first: it is the lhs block's last)."""
    tk = _split(K)
    return (tk, N) if tk < K else (K, _split(N))


def gmm_footprint(K: int, N: int, itemsize: int, tm: int = ROW_TILE) -> int:
    """VMEM bytes a grid step of the largest of the three kernels: blocks
    double-buffered, the float32 accumulator and as much for the body."""
    fwd = 2 * (tm * K + K * N) * itemsize + 4 * tm * max(K, N) * 4
    tk, tn = _drhs_blocks(K, N)
    drhs = 2 * (tm * tk + tm * tn + tk * tn) * itemsize + 2 * tk * tn * 4
    return max(fwd, drhs)


def gmm_fits(K: int, N: int, itemsize: int) -> bool:
    return gmm_footprint(K, N, itemsize) <= _seq_vmem_budget()


def gmm_layout_ok(M: int, K: int, N: int) -> bool:
    """What Mosaic's tiling asks (interpret mode asks nothing): whole row
    tiles, and widths that are whole sublane tiles of a 16-bit operand."""
    return M % ROW_TILE == 0 and K % 16 == 0 and N % 16 == 0


def _params(K, N, itemsize, semantics):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(gmm_footprint(K, N, itemsize),
                             _SEQ_MIN_VMEM_LIMIT_BYTES))


@jit_entry
def _gmm_kernel(transpose_rhs, tg_ref, nt_ref, lhs_ref, rhs_ref, out_ref):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    i = pl.program_id(0)

    @pl.when(i < nt_ref[0])
    def _product():
        contract = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], (contract, ((), ())),
            preferred_element_type=_acc_dtype(lhs_ref.dtype)
        ).astype(out_ref.dtype)

    @pl.when(i >= nt_ref[0])
    def _unused():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _gmm_call(lhs, rhs, tile_group, n_tiles, transpose_rhs, out_dtype):
    """``lhs`` [M, K] a tile at a time times its group's ``rhs[g]`` ([K, N],
    or [N, K] read transposed)."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    M, K = lhs.shape
    E, R0, R1 = rhs.shape
    N = R0 if transpose_rhs else R1
    tm = ROW_TILE
    interpret = _interpret()

    def used(i, nt):        # a tile past the last stays on the last's blocks
        return jnp.minimum(i, nt[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M // tm,),
        in_specs=[
            pl.BlockSpec((tm, K), lambda i, tg, nt: (used(i, nt), 0)),
            pl.BlockSpec((1, R0, R1),
                         lambda i, tg, nt: (tg[used(i, nt)], 0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, N), lambda i, tg, nt: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=None if interpret else _params(
            R0, R1, lhs.dtype.itemsize, ("arbitrary",)),
        interpret=interpret,
        name="grouped_matmul_dlhs" if transpose_rhs else "grouped_matmul_fwd",
    )(tile_group, n_tiles, lhs, rhs)


@jit_entry
def _drhs_kernel(tg_ref, nt_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    i, nt = pl.program_id(2), nt_ref[0]
    last = pl.num_programs(2) - 1
    g = tg_ref[jnp.minimum(i, nt - 1)]
    before = tg_ref[jnp.maximum(jnp.minimum(i, nt - 1) - 1, 0)]
    after = tg_ref[jnp.minimum(jnp.minimum(i, nt - 1) + 1, last)]
    in_use = i < nt

    @pl.when(in_use & ((i == 0) | (before != g)))
    def _first_of_group():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(in_use)
    def _product():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=acc_ref.dtype)

    @pl.when(in_use & ((i == nt - 1) | (after != g)))
    def _last_of_group():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _drhs_call(lhs, dout, tile_group, n_tiles, E, out_dtype):
    """``sum over a group's tiles of lhs_tile^T @ dout_tile`` -> [E, K, N]."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    M, K = lhs.shape
    N = dout.shape[1]
    tm = ROW_TILE
    tk, tn = _drhs_blocks(K, N)
    interpret = _interpret()

    def used(i, nt):
        return jnp.minimum(i, nt[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // tk, N // tn, M // tm),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda a, b, i, tg, nt: (used(i, nt), a)),
            pl.BlockSpec((tm, tn), lambda a, b, i, tg, nt: (used(i, nt), b)),
        ],
        out_specs=pl.BlockSpec(
            (1, tk, tn), lambda a, b, i, tg, nt: (tg[used(i, nt)], a, b)),
        scratch_shapes=[pltpu.VMEM((tk, tn), _acc_dtype(lhs.dtype))],
    )
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, K, N), out_dtype),
        compiler_params=None if interpret else _params(
            K, N, lhs.dtype.itemsize, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul_drhs",
    )(tile_group, n_tiles, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_core(lhs, rhs, tile_group, n_tiles, out_dtype):
    return _gmm_call(lhs, rhs, tile_group, n_tiles, False, out_dtype)


def _gmm_core_fwd(lhs, rhs, tile_group, n_tiles, out_dtype):
    out = _gmm_call(lhs, rhs, tile_group, n_tiles, False, out_dtype)
    return out, (lhs, rhs, tile_group, n_tiles)


def _gmm_core_bwd(out_dtype, residuals, dout):
    import numpy as np  # noqa: PLC0415

    lhs, rhs, tile_group, n_tiles = residuals
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, tile_group, n_tiles, True, lhs.dtype)
    drhs = _drhs_call(lhs, dout, tile_group, n_tiles, rhs.shape[0], rhs.dtype)
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return dlhs, drhs, none(tile_group), none(n_tiles)


_gmm_core.defvjp(_gmm_core_fwd, _gmm_core_bwd)


def grouped_matmul_fused(lhs, rhs, group, padded, out_dtype=None):
    """``lhs`` [M, K] in :func:`aligned_layout`'s order at ``ROW_TILE``
    (``group`` [M] and ``padded`` [E] as it returned them) times each row's
    ``rhs[group]`` -> [M, N]; rows past the layout are zeros."""
    tile_group = group[::ROW_TILE]
    n_tiles = (jnp.sum(padded) // ROW_TILE).astype(jnp.int32).reshape(1)
    return _gmm_core(lhs, rhs.astype(lhs.dtype), tile_group, n_tiles,
                     jnp.dtype(out_dtype or lhs.dtype))


# --------------------------------------------------------- reference variant
def grouped_matmul_reference(lhs, rhs, group, padded, out_dtype=None):
    """Same contract through ``jax.lax.ragged_dot`` at any alignment. A
    grouped product leaves rows that belong to no group as it finds them,
    forward and transposed (on the v5e: whatever the buffer held), so both
    ends select them away."""
    in_layout = (jnp.arange(lhs.shape[0]) < jnp.sum(padded))[:, None]
    out = jax.lax.ragged_dot(
        jnp.where(in_layout, lhs, 0), rhs.astype(lhs.dtype), padded,
        preferred_element_type=jnp.dtype(out_dtype or lhs.dtype))
    return jnp.where(in_layout, out, 0)


def grouped_matmul(lhs, rhs, group, padded, variant: str, out_dtype=None):
    fn = grouped_matmul_fused if variant == "fused" \
        else grouped_matmul_reference
    return fn(lhs, rhs, group, padded, out_dtype)
