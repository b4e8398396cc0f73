"""The heavy pieces of a hyper-connected residual (``nn/layers/
hyper_connections.py``), each in one pass over the streams ``X`` [N, n * D]:
the maps' projection with the row's mean square, the (normed) read and the
write, forward and backward.

Two variants, one site (``hyper_connection`` in :mod:`.kernel_select`):

- ``reference`` — the jax.numpy code of the layer itself, differentiated by
  autodiff: XLA makes about twice the trips to HBM that the mathematics
  needs (7.0 GB a sublayer at [8192, 14336] bfloat16; PERF.md, PR 34).
- ``fused`` — three ``custom_vjp`` functions over six Mosaic kernels,
  ``hc_maps_fwd`` / ``hc_maps_bwd`` (:func:`hc_project`), ``hc_read_fwd`` /
  ``hc_read_bwd`` (:func:`hc_read`) and ``hc_write_fwd`` / ``hc_write_bwd``
  (:func:`hc_write`). The grid runs over tiles of 128 token rows with all
  ``n * D`` features of a tile in VMEM; stream ``s`` is the lane slice
  ``[s * D, (s + 1) * D)``. The elementwise kernels walk a tile in blocks of
  one sublane tile of rows, a lane tile at a time, so that what a step of
  the walk holds fits the vector registers. Every residual of a
  ``custom_vjp`` is an input of its function: under the graph's per-vertex
  ``jax.checkpoint`` the re-run forward of the read and of the write is
  dead code.

Whatever is a few numbers a token (the projection's result, the maps, their
cotangents) enters and leaves the kernels with the tokens along the lanes,
[128, N] blocks of [128, 128], as the Sinkhorn normalisation takes them: the
kernels turn a block in VMEM. Handed over as [N, 24], such an operand made
the compiler lay the Sinkhorn's arrays out tokens-major, 16 of 128 lanes in
use: 8 ms a step (PERF.md, PR 35).

The products of the projection are the ``highest`` float32 products they
always were. Where ``X`` is bfloat16 its float32 copy has no low bits, so
``highest`` (three bfloat16 pieces an operand, the six largest cross
products) comes to: the float32 operand split into its three bfloat16 pieces
(``reduce_precision``: a cast there and back is not a rounding the compiler
keeps) laid side by side, and one bfloat16 pass with a float32 accumulator.
Sums, norms and dot products over ``D`` are float32 as in the reference.

``X`` has three readers a sublayer, so three cotangents. :func:`hc_project_
handing_on` and :func:`hc_read_handing_on` also return ``x`` itself: a caller
that reads ``x`` from there afterwards (``ComputationGraph`` does, for a
vertex that ``hands_input_on``) brings the later readers' cotangent back with
the output's, and the backward kernel takes it as one more operand, tied to
its result (``input_output_aliases``), and adds onto it: the write's, then
the read's, then the projection's, in one buffer and no pass of their own.

A token count that is no whole number of tiles is padded with zero rows (a
copy: no cell pays it) and the padding's rows are cut off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..analysis.annotations import jit_entry
from .pallas_kernels import (_SEQ_MIN_VMEM_LIMIT_BYTES, _interpret,
                             _seq_vmem_budget)

_LANES = 128
_ROW_TILE = _LANES             # tokens a grid step: a lane tile of them
_MS_ROW = _LANES - 1           # where hc_maps_fwd leaves the tokens' mean squares
_VMEM_MARGIN_BYTES = 4 << 20   # the compiler's own scratch


def _f(dt):
    return jnp.promote_types(dt, jnp.float32)


def _row_block(itemsize: int) -> int:
    """Rows of one sublane tile of the streams' dtype: what a step of a
    kernel's walk over its row tile takes."""
    return max(8, 32 // itemsize)


def _pieces(itemsize: int) -> int:
    """bfloat16 pieces a float32 operand of the projection is split into: 3
    beside bfloat16 streams, 1 (the operand itself, multiplied at ``highest``)
    beside float32 ones."""
    return 3 if itemsize == 2 else 1


def n_maps(n: int) -> int:
    return n * (2 + n)


def _bwd_heights(n: int, itemsize: int):
    """``(KA, R)`` of ``hc_maps_bwd``: the contraction of its ``dX`` product
    and the rows of its ``dP^T`` accumulator, whole sublane tiles."""
    m, pieces = n_maps(n), _pieces(itemsize)
    whole = lambda k: -(-k // (32 // itemsize)) * (32 // itemsize)  # noqa: E731
    return whole(6 * m if pieces == 3 else m), whole(pieces * m)


def hc_footprint(op: str, n: int, D: int, itemsize: int) -> int:
    """VMEM bytes a grid step of the larger kernel of ``op`` (its backward):
    the blocks double-buffered, the scratch, and the float32 values the body
    holds for one stream."""
    tile, part = _ROW_TILE * n * D * itemsize, _ROW_TILE * D * itemsize
    ka, r = _bwd_heights(n, itemsize)
    if op == "maps":       # X, seen, dX; the constant operands; three products
        need = 6 * tile + 2 * ka * n * D * itemsize + 2 * r * n * D * 4 \
            + 3 * _ROW_TILE * D * 4
    elif op == "read":     # X, seen, dX; dh; the row block's scratch and dgamma
        need = 6 * tile + 2 * part + 6 * _row_block(itemsize) * D * 4
    else:                  # dX', X, dX; y, dy
        need = 6 * tile + 4 * part
    return need + _VMEM_MARGIN_BYTES


def hc_layout_ok(n: int, D: int, itemsize: int) -> bool:
    """What the kernels ask of the shapes: streams that are whole lane
    tiles, bfloat16 or float32, and the projection's pieces beside the mean
    square in one lane tile."""
    return D % _LANES == 0 and itemsize in (2, 4) \
        and _pieces(itemsize) * n_maps(n) < _LANES


def hc_row_tile(op: str, n: int, D: int, itemsize: int):
    """Token rows a grid step of ``op``'s kernels; ``None`` where the shapes
    are not theirs or their footprint does not fit the VMEM budget."""
    if hc_layout_ok(n, D, itemsize) \
            and hc_footprint(op, n, D, itemsize) <= _seq_vmem_budget():
        return _ROW_TILE
    return None


def _over_tiles(op, x, n, semantics) -> dict:
    """What every ``pallas_call`` over the token tiles of ``x`` [N, n * D]
    states: the grid, interpret mode off the TPU, and the VMEM limit its
    ``op`` reckons for itself."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    interpret = _interpret()
    limit = max(hc_footprint(op, n, x.shape[1] // n, x.dtype.itemsize),
                _SEQ_MIN_VMEM_LIMIT_BYTES)
    return dict(
        grid=(x.shape[0] // _ROW_TILE,), interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=(semantics,), vmem_limit_bytes=limit))


def _rows(width):
    """The block of one tile's token rows of an [N, width] array."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    return pl.BlockSpec((_ROW_TILE, width), lambda i: (i, 0))


def _lanes_of(height):
    """The block of one tile's tokens of a [height, N] array: tokens along
    the lanes."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    return pl.BlockSpec((height, _ROW_TILE), lambda i: (0, i))


def _whole(shape):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    return pl.BlockSpec(shape, lambda i: (0, 0))


def _scratch(dtype, *shape):
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.VMEM(shape, dtype)


def _padded(*arrays):
    """The [N, .] arrays (``None`` stays ``None``) with zero rows up to whole
    tiles of tokens."""
    pad = (-arrays[0].shape[0]) % _ROW_TILE
    if not pad:
        return arrays
    return tuple(a if a is None else jnp.pad(a, ((0, pad), (0, 0)))
                 for a in arrays)


def _along_lanes(a, height=_LANES):
    """``a`` [N, k] as [height, N]: the tokens along the lanes, zero rows
    below."""
    return jnp.pad(a.T, ((0, height - a.shape[1]), (0, 0)))


def _if_seen(seen, item) -> list:
    """``[item]`` (a block spec, an operand) for a backward kernel that takes
    ``seen``, the cotangent of ``x`` so far; ``[]`` for one that starts it."""
    return [] if seen is None else [item]


def _onto_seen(seen, operand: int) -> dict:
    """The new cotangent (result 0) written where ``seen`` lay."""
    return {} if seen is None else {operand: 0}


def _lanes(col, rb):
    """A [rb, 1] column of per-row scalars along a lane tile."""
    return jnp.broadcast_to(col, (rb, _LANES))


def _row_sum(partial):
    return jnp.sum(partial, axis=1, keepdims=True)


def _columns(cols, rb, dtype):
    """[rb, 128] with column ``k`` the [rb, 1] ``cols[k]``, zeros elsewhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rb, _LANES), 1)
    out = jnp.zeros((rb, _LANES), dtype)
    for k, col in cols.items():
        out = jnp.where(lane == k, col, out)
    return out


def _walk(rb, block):
    """``block(rows)`` for every row block of a tile."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    def step(r, carry):
        block(pl.ds(pl.multiple_of(r * rb, rb), rb))
        return carry

    jax.lax.fori_loop(0, _ROW_TILE // rb, step, 0)


# ------------------------------------------------------------ the projection
def _split(a, pieces: int):
    """The bfloat16 pieces of float32 ``a``, largest first: their sum is
    ``a`` to its last bit at three. Really rounded: the compiler keeps the
    excess precision of a cast there and back."""
    out = []
    for _ in range(pieces):
        p = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        out.append(p.astype(jnp.bfloat16))
        a = a - p
    return out


def _precision(itemsize: int):
    return None if itemsize == 2 else jax.lax.Precision.HIGHEST


@jit_entry
def _maps_fwd_kernel(rb, precision, x_ref, p_ref, out_ref):
    tm, W = x_ref.shape
    f = out_ref.dtype
    out_ref[...] = jax.lax.dot_general(
        p_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=f, precision=precision)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rb, tm), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rb, tm), 0)

    def block(rows):
        ssq = jnp.zeros((rb, _LANES), f)
        for c in range(W // _LANES):
            v = x_ref[rows, c * _LANES:(c + 1) * _LANES].astype(f)
            ssq = ssq + v * v
        # the block's mean squares from a column to their lanes of the row
        at = jnp.where(lane == row + rows.start, _row_sum(ssq) / W, 0.0)
        out_ref[_MS_ROW:, :] += jnp.sum(at, axis=0, keepdims=True)

    _walk(rb, block)


@functools.partial(jax.jit, static_argnames=("n",))
def _maps_fwd_call(x, p_rows, *, n):
    """[128, N] float32, tokens along the lanes: the rows of ``p_rows`` [128,
    W] times ``x^T``, and the tokens' mean squares in row ``_MS_ROW`` (a zero
    row of ``p_rows``)."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, W = x.shape
    itemsize = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_maps_fwd_kernel, _row_block(itemsize),
                          _precision(itemsize)),
        name="hc_maps_fwd",
        in_specs=[_rows(W), _whole(p_rows.shape)],
        out_specs=_lanes_of(_LANES),
        out_shape=jax.ShapeDtypeStruct((_LANES, N), _f(x.dtype)),
        **_over_tiles("maps", x, n, "parallel"),
    )(x, p_rows)


@jit_entry
def _maps_bwd_kernel(n, precision, x_ref, a_ref, b_ref, a2_ref, c_ref, *refs):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    seen_ref, dx_ref, dpt_ref = refs if len(refs) == 3 else (None, *refs)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dpt_ref[...] = jnp.zeros(dpt_ref.shape, dpt_ref.dtype)

    f = dpt_ref.dtype
    tm, W = x_ref.shape
    D = W // n
    a, a2 = a_ref[...], a2_ref[...]
    # the tokens' scalars from their lanes of a row to a column
    on_diagonal = (jax.lax.broadcasted_iota(jnp.int32, (tm, tm), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (tm, tm), 1))
    c = _row_sum(jnp.where(on_diagonal, c_ref[...], 0.0))
    for s in range(n):
        lanes = slice(s * D, (s + 1) * D)
        xs = x_ref[:, lanes]
        dx = jax.lax.dot_general(
            a, b_ref[:, lanes], (((0,), (0,)), ((), ())),
            preferred_element_type=f, precision=precision) + xs.astype(f) * c
        if seen_ref is not None:
            dx = dx + seen_ref[:, lanes].astype(f)
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        dpt_ref[:, lanes] += jnp.dot(a2, xs, preferred_element_type=f,
                                     precision=precision)


@functools.partial(jax.jit, static_argnames=("n",))
def _maps_bwd_call(x, a, b, a2, c, seen, *, n):
    """``dX = a^T b + x c^T`` (onto ``seen``, the cotangent of ``x`` so far,
    where there is one) rounded to ``x``'s dtype, and ``a2 x`` [R, W] summed
    over the tiles; ``a`` [KA, N], ``a2`` [R, N] and ``c`` [1, N] have the
    tokens along the lanes."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    W = x.shape[1]
    itemsize = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_maps_bwd_kernel, n, _precision(itemsize)),
        name="hc_maps_bwd",
        in_specs=[_rows(W), _lanes_of(a.shape[0]), _whole(b.shape),
                  _lanes_of(a2.shape[0]), _lanes_of(1),
                  *_if_seen(seen, _rows(W))],
        out_specs=(_rows(W), _whole((a2.shape[0], W))),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((a2.shape[0], W), _f(x.dtype))),
        input_output_aliases=_onto_seen(seen, 5),
        **_over_tiles("maps", x, n, "arbitrary"),
    )(x, a, b, a2, c, *_if_seen(seen, seen))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hc_project(x, P, n: int):
    """``((x P)^T, mean(x^2)^T)`` of the streams ``x`` [N, n * D] and the
    maps' projection ``P`` [n * D, n (2 + n)] (float32, or ``x``'s own wider
    dtype): [n (2 + n), N] and [1, N], float32, tokens along the lanes as the
    Sinkhorn normalisation takes them, the product at ``highest`` precision."""
    return _project_fwd(x, P, n)[0]


def _project_fwd(x, P, n):
    N = x.shape[0]
    m, pieces = P.shape[1], _pieces(x.dtype.itemsize)
    rows = P.astype(_f(x.dtype)).T
    if pieces > 1:
        rows = jnp.concatenate(_split(rows, pieces), axis=0)
    rows = jnp.pad(rows, ((0, _LANES - pieces * m), (0, 0)))
    out = _maps_fwd_call(*_padded(x), rows.astype(x.dtype), n=n)[:, :N]
    xp = sum(out[k * m:(k + 1) * m] for k in range(pieces))
    return (xp, out[_MS_ROW:]), (x, P)


def _project_bwd(n, residuals, cts, seen=None):
    x, P = residuals
    d_xp, d_ms = cts                         # [n (2 + n), N] and [1, N]
    N, W = x.shape
    m, itemsize = P.shape[1], x.dtype.itemsize
    f = _f(x.dtype)
    d_xp, PT = d_xp.astype(f), P.astype(f).T
    pieces = _pieces(itemsize)
    if pieces == 3:
        # highest: the six largest cross products of the operands' pieces
        (dh, dm, dl), (ph, pm, pl_) = _split(d_xp, 3), _split(PT, 3)
        a = jnp.concatenate([dh, dh, dm, dh, dl, dm], axis=0)
        b = jnp.concatenate([ph, pm, ph, pl_, ph, pm], axis=0)
        a2 = jnp.concatenate([dh, dm, dl], axis=0)
    else:
        a, b, a2 = d_xp, PT, d_xp
    ka, r = _bwd_heights(n, itemsize)
    # whole sublane tiles of rows, whole tiles of tokens along the lanes
    tall = lambda t, rows, wide=0: jnp.pad(  # noqa: E731
        t, ((0, rows - t.shape[0]), (0, wide)))
    more = (-N) % _ROW_TILE
    c = d_ms.astype(f) * (2.0 / W)          # the mean square's own gradient
    xr, seen = _padded(x, seen)
    dx, dpt = _maps_bwd_call(xr, tall(a, ka, more), tall(b, ka),
                             tall(a2, r, more), tall(c, 1, more), seen, n=n)
    dP = sum(dpt[k * m:(k + 1) * m] for k in range(pieces)).T
    return dx[:N], dP.astype(P.dtype)


hc_project.defvjp(_project_fwd, _project_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def hc_project_handing_on(x, P, n: int):
    """:func:`hc_project` and ``x`` itself: a caller that takes ``x`` from
    here for its later reads brings their cotangent back with the
    product's, and the backward kernel adds onto it where it lies."""
    return (*hc_project(x, P, n), x)


def _project_on_fwd(x, P, n):
    out, residuals = _project_fwd(x, P, n)
    return (*out, x), residuals


def _project_on_bwd(n, residuals, cts):
    *cts, seen = cts
    return _project_bwd(n, residuals, cts, seen.astype(residuals[0].dtype))


hc_project_handing_on.defvjp(_project_on_fwd, _project_on_bwd)


# ------------------------------------------------------------------ the read
def _pre_norm_sum(n, D, rows, hb, x_ref, h_scr, f):
    """The un-normed read of one row block into ``h_scr`` and its sum of
    squares a lane."""
    ssq = jnp.zeros(hb[0].shape, f)
    for c in range(D // _LANES):
        lanes = slice(c * _LANES, (c + 1) * _LANES)
        h = sum(hb[s] * x_ref[rows, s * D + c * _LANES:
                              s * D + (c + 1) * _LANES].astype(f)
                for s in range(n))
        h_scr[:, lanes] = h
        ssq = ssq + h * h
    return ssq


@jit_entry
def _read_fwd_kernel(n, eps, rb, x_ref, maps_ref, g_ref, out_ref, h_scr,
                     m_scr):
    D = out_ref.shape[1]
    f = h_scr.dtype
    m_scr[...] = maps_ref[...].T             # a token's maps along a row

    def block(rows):
        m = m_scr[rows, :]
        hb = [_lanes(m[:, s:s + 1], rb) for s in range(n)]
        ssq = _pre_norm_sum(n, D, rows, hb, x_ref, h_scr, f)
        scale = _lanes(jax.lax.rsqrt(_row_sum(ssq) / D + eps), rb) \
            if eps > 0 else None
        for c in range(D // _LANES):
            lanes = slice(c * _LANES, (c + 1) * _LANES)
            h = h_scr[:, lanes]
            if eps > 0:
                h = h * scale * g_ref[:, lanes].astype(f)
            out_ref[rows, lanes] = h.astype(out_ref.dtype)

    _walk(rb, block)


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _read_fwd_call(x, maps, gamma, *, n, eps):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, W = x.shape
    D, rb, f = W // n, _row_block(x.dtype.itemsize), _f(x.dtype)
    return pl.pallas_call(
        functools.partial(_read_fwd_kernel, n, eps, rb),
        name="hc_read_fwd",
        in_specs=[_rows(W), _lanes_of(_LANES), _whole((1, D))],
        out_specs=_rows(D),
        out_shape=jax.ShapeDtypeStruct((N, D), x.dtype),
        scratch_shapes=[_scratch(f, rb, D), _scratch(f, _ROW_TILE, _LANES)],
        **_over_tiles("read", x, n, "parallel"),
    )(x, maps, gamma)


@jit_entry
def _read_bwd_kernel(n, eps, rb, x_ref, maps_ref, g_ref, dh_ref, *refs):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    seen_ref, dx_ref, dmaps_ref, dg_ref, h_scr, u_scr, m_scr, dm_scr = \
        refs if len(refs) == 8 else (None, *refs)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[...] = jnp.zeros(dg_ref.shape, dg_ref.dtype)

    D = dh_ref.shape[1]
    f = h_scr.dtype
    chunks = [slice(c * _LANES, (c + 1) * _LANES) for c in range(D // _LANES)]
    m_scr[...] = maps_ref[...].T

    def block(rows):
        m = m_scr[rows, :]
        hb = [_lanes(m[:, s:s + 1], rb) for s in range(n)]
        if eps > 0:
            # the forward's un-normed sum and scale again, from the inputs
            ssq = _pre_norm_sum(n, D, rows, hb, x_ref, h_scr, f)
            scale = _lanes(jax.lax.rsqrt(_row_sum(ssq) / D + eps), rb)
            dot = jnp.zeros((rb, _LANES), f)
            for lanes in chunks:
                hn = h_scr[:, lanes] * scale
                g = dh_ref[rows, lanes].astype(f)
                dg_ref[:, lanes] += g * hn
                u = g * g_ref[:, lanes].astype(f)
                dot = dot + u * hn
                h_scr[:, lanes], u_scr[:, lanes] = hn, u
            mean = _lanes(_row_sum(dot) / D, rb)
        acc = [jnp.zeros((rb, _LANES), f) for _ in range(n)]
        for c, lanes in enumerate(chunks):
            if eps > 0:
                d = scale * (u_scr[:, lanes] - h_scr[:, lanes] * mean)
            else:
                d = dh_ref[rows, lanes].astype(f)
            for s in range(n):
                at = slice(s * D + c * _LANES, s * D + (c + 1) * _LANES)
                dx = hb[s] * d
                if seen_ref is not None:
                    dx = dx + seen_ref[rows, at].astype(f)
                dx_ref[rows, at] = dx.astype(dx_ref.dtype)
                acc[s] = acc[s] + d * x_ref[rows, at].astype(f)
        dm_scr[rows, :] = _columns({s: _row_sum(acc[s]) for s in range(n)},
                                   rb, f)

    _walk(rb, block)
    dmaps_ref[...] = dm_scr[...].T


@functools.partial(jax.jit, static_argnames=("n", "eps"))
def _read_bwd_call(x, maps, gamma, dh, seen, *, n, eps):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, W = x.shape
    D, rb, f = W // n, _row_block(x.dtype.itemsize), _f(x.dtype)
    return pl.pallas_call(
        functools.partial(_read_bwd_kernel, n, eps, rb),
        name="hc_read_bwd",
        in_specs=[_rows(W), _lanes_of(_LANES), _whole((1, D)), _rows(D),
                  *_if_seen(seen, _rows(W))],
        out_specs=(_rows(W), _lanes_of(_LANES), _whole((rb, D))),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((_LANES, N), f),
                   jax.ShapeDtypeStruct((rb, D), f)),
        scratch_shapes=[_scratch(f, rb, D), _scratch(f, rb, D),
                        _scratch(f, _ROW_TILE, _LANES),
                        _scratch(f, _ROW_TILE, _LANES)],
        input_output_aliases=_onto_seen(seen, 4),
        **_over_tiles("read", x, n, "arbitrary"),
    )(x, maps, gamma, dh, *_if_seen(seen, seen))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def hc_read(x, maps, gamma, n: int, eps: float):
    """``sum_s maps[:, s] x_s`` [N, D] of the streams ``x`` [N, n * D] and the
    float32 maps [N, n (2 + n)]; with ``eps`` > 0 its RMS norm over ``D``
    times ``gamma`` [D]. Sums in float32, the result in ``x``'s dtype."""
    return _read_fwd(x, maps, gamma, n, eps)[0]


def _gamma_row(gamma, x, n):
    """``gamma`` as the [1, D] row the kernels read; ones where the read
    takes no norm (``gamma`` is None and nothing reads the row)."""
    if gamma is None:
        return jnp.ones((1, x.shape[1] // n), x.dtype)
    return gamma.reshape(1, -1)


def _read_fwd(x, maps, gamma, n, eps):
    N = x.shape[0]
    xr, mr = _padded(x, maps.astype(_f(x.dtype)))
    out = _read_fwd_call(xr, _along_lanes(mr), _gamma_row(gamma, x, n),
                         n=n, eps=eps)
    return out[:N], (x, maps, gamma)


def _read_bwd(n, eps, residuals, dh, seen=None):
    x, maps, gamma = residuals
    N, m = maps.shape
    xr, mr, dhr, seen = _padded(x, maps.astype(_f(x.dtype)),
                                dh.astype(x.dtype), seen)
    dx, dmaps, dg = _read_bwd_call(xr, _along_lanes(mr),
                                   _gamma_row(gamma, x, n), dhr, seen,
                                   n=n, eps=eps)
    dgamma = None if gamma is None else \
        jnp.sum(dg, axis=0).reshape(gamma.shape).astype(gamma.dtype)
    return dx[:N], dmaps[:m, :N].T.astype(maps.dtype), dgamma


hc_read.defvjp(_read_fwd, _read_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def hc_read_handing_on(x, maps, gamma, n: int, eps: float):
    """:func:`hc_read` and ``x`` itself, as :func:`hc_project_handing_on`."""
    return hc_read(x, maps, gamma, n, eps), x


def _read_on_fwd(x, maps, gamma, n, eps):
    out, residuals = _read_fwd(x, maps, gamma, n, eps)
    return (out, x), residuals


def _read_on_bwd(n, eps, residuals, cts):
    dh, seen = cts
    return _read_bwd(n, eps, residuals, dh, seen.astype(residuals[0].dtype))


hc_read_handing_on.defvjp(_read_on_fwd, _read_on_bwd)


# ----------------------------------------------------------------- the write
def _write_maps(m, n, rb):
    """``(H_post[i], H_res[i][j])`` of a row block's maps, each along a
    lane tile."""
    col = lambda k: _lanes(m[:, k:k + 1], rb)  # noqa: E731
    return ([col(n + i) for i in range(n)],
            [[col(2 * n + i * n + j) for j in range(n)] for i in range(n)])


@jit_entry
def _write_fwd_kernel(n, rb, x_ref, maps_ref, y_ref, out_ref, m_scr):
    D = y_ref.shape[1]
    f = m_scr.dtype
    m_scr[...] = maps_ref[...].T

    def block(rows):
        post, res = _write_maps(m_scr[rows, :], n, rb)
        for c in range(D // _LANES):
            at = [slice(s * D + c * _LANES, s * D + (c + 1) * _LANES)
                  for s in range(n)]
            xs = [x_ref[rows, at[j]].astype(f) for j in range(n)]
            y = y_ref[rows, c * _LANES:(c + 1) * _LANES].astype(f)
            for i in range(n):
                mixed = sum(res[i][j] * xs[j] for j in range(n))
                out_ref[rows, at[i]] = (mixed + post[i] * y
                                        ).astype(out_ref.dtype)

    _walk(rb, block)


@functools.partial(jax.jit, static_argnames=("n",))
def _write_fwd_call(x, maps, y, *, n):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    W = x.shape[1]
    return pl.pallas_call(
        functools.partial(_write_fwd_kernel, n, _row_block(x.dtype.itemsize)),
        name="hc_write_fwd",
        in_specs=[_rows(W), _lanes_of(_LANES), _rows(W // n)],
        out_specs=_rows(W),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[_scratch(_f(x.dtype), _ROW_TILE, _LANES)],
        **_over_tiles("write", x, n, "parallel"),
    )(x, maps, y)


@jit_entry
def _write_bwd_kernel(n, rb, x_ref, maps_ref, y_ref, dout_ref, dx_ref,
                      dy_ref, dmaps_ref, m_scr, dm_scr):
    D = y_ref.shape[1]
    f = m_scr.dtype
    m_scr[...] = maps_ref[...].T

    def block(rows):
        post, res = _write_maps(m_scr[rows, :], n, rb)
        zero = jnp.zeros((rb, _LANES), f)
        d_post = [zero] * n
        d_res = [[zero] * n for _ in range(n)]
        for c in range(D // _LANES):
            at = [slice(s * D + c * _LANES, s * D + (c + 1) * _LANES)
                  for s in range(n)]
            xs = [x_ref[rows, at[j]].astype(f) for j in range(n)]
            ds = [dout_ref[rows, at[i]].astype(f) for i in range(n)]
            y = y_ref[rows, c * _LANES:(c + 1) * _LANES].astype(f)
            for j in range(n):
                dx_ref[rows, at[j]] = sum(
                    res[i][j] * ds[i] for i in range(n)).astype(dx_ref.dtype)
            dy_ref[rows, c * _LANES:(c + 1) * _LANES] = sum(
                post[i] * ds[i] for i in range(n)).astype(dy_ref.dtype)
            for i in range(n):
                d_post[i] = d_post[i] + ds[i] * y
                for j in range(n):
                    d_res[i][j] = d_res[i][j] + ds[i] * xs[j]
        cols = {n + i: _row_sum(d_post[i]) for i in range(n)}
        cols.update({2 * n + i * n + j: _row_sum(d_res[i][j])
                     for i in range(n) for j in range(n)})
        dm_scr[rows, :] = _columns(cols, rb, f)

    _walk(rb, block)
    dmaps_ref[...] = dm_scr[...].T


@functools.partial(jax.jit, static_argnames=("n",))
def _write_bwd_call(x, maps, y, dout, *, n):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    N, W = x.shape
    f = _f(x.dtype)
    return pl.pallas_call(
        functools.partial(_write_bwd_kernel, n, _row_block(x.dtype.itemsize)),
        name="hc_write_bwd",
        in_specs=[_rows(W), _lanes_of(_LANES), _rows(W // n), _rows(W)],
        out_specs=(_rows(W), _rows(W // n), _lanes_of(_LANES)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((_LANES, N), f)),
        scratch_shapes=[_scratch(f, _ROW_TILE, _LANES),
                        _scratch(f, _ROW_TILE, _LANES)],
        **_over_tiles("write", x, n, "parallel"),
    )(x, maps, y, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def hc_write(x, maps, y, n: int):
    """``x'`` [N, n * D] with ``x'_i = sum_j H_res[i, j] x_j + H_post[i] y``
    of the streams ``x``, the float32 maps [N, n (2 + n)] and the sublayer's
    output ``y`` [N, D]. Sums in float32, rounded stream by stream."""
    return _write_fwd(x, maps, y, n)[0]


def _write_fwd(x, maps, y, n):
    N = x.shape[0]
    xr, mr, yr = _padded(x, maps.astype(_f(x.dtype)), y.astype(x.dtype))
    return _write_fwd_call(xr, _along_lanes(mr), yr, n=n)[:N], (x, maps, y)


def _write_bwd(n, residuals, dout):
    x, maps, y = residuals
    N, m = maps.shape
    xr, mr, yr, dr = _padded(x, maps.astype(_f(x.dtype)), y.astype(x.dtype),
                             dout.astype(x.dtype))
    dx, dy, dmaps = _write_bwd_call(xr, _along_lanes(mr), yr, dr, n=n)
    return (dx[:N], dmaps[:m, :N].T.astype(maps.dtype),
            dy[:N].astype(y.dtype))


hc_write.defvjp(_write_fwd, _write_bwd)
