"""Kimi Delta Attention's recurrence: the gated delta rule with a decay a
channel, a linear attention whose state is a matrix a head that is read back
into its own update.

For every head, with ``q_t``, ``k_t`` [K], ``v_t`` [V], a log-decay ``g_t``
[K] (``<= 0``; ``alpha_t = exp(g_t)``) and a step ``beta_t`` in (0, 1), the
state ``S`` [K, V] follows, from zero,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_reference` is that, one position at a time (``lax.scan``, float32):
what the tests hold everything else to. :func:`kda_chunked` is what a program
runs: chunks of ``C`` positions, nearly all of it matrix products. With ``G_r``
the running sum of ``g`` inside a chunk and ``S_0`` the state that enters it,

    A_ri = beta_r <k_r * exp(G_r - G_i), k_i>  (i < r);   P_ri likewise with
           q_r and i <= r
    (I + A) U~ = Diag(beta) (V - (K * exp(G)) S_0)
    o_r = S_0^T (q_r * exp(G_r)) + sum_{i<=r} P_ri u~_i
    S_C = Diag(exp(G_C)) S_0 + sum_i (k_i * exp(G_C - G_i)) u~_i^T

``(I + A)^-1 = T`` is taken once a chunk: sub-blocks of ``SUB`` rows by
substitution (``SUB`` rounds of a small product over every sub-block of
every chunk at once), merged in pairs up to the chunk by block products
(:func:`_inverse_of_unit_lower`). Then ``U~ = U - W
S_0`` with ``U = T Diag(beta) V`` and ``W = T Diag(beta) (K * exp(G))``, so a
chunk maps the state that enters it to the one that leaves it by ``S_C = M
S_0 + Z``, ``M = Diag(exp(G_C)) - K_end^T W`` [K, K], ``Z = K_end^T U`` [K,
V], ``K_end`` the rows ``k_i * exp(G_C - G_i)``. Such maps compose, so the
states that enter every chunk come from one ``lax.associative_scan`` over the
chunks (log2 of their number of rounds of ``[K, K]`` products, every ``M`` a
contraction), and every chunk's ``U~`` and ``o`` from batched products over
all chunks at once: the program has no loop over chunks, and over rows only
the ``SUB`` unrolled rounds of the substitution.

**No exponent that is formed is positive.** Written as ``(K * exp(G)) (K /
exp(G))^T`` the second factor overflows float32 once a chunk's summed decay
passes -88. Here a chunk is cut into sub-blocks of ``SUB`` rows. Between two
sub-blocks the scores are a product of ``k_r * exp(G_r - G_ref)`` with ``k_i *
exp(G_ref - G_i)``, ``G_ref`` the running sum just before the later
sub-block's first row: ``i`` lies before it and ``r`` at or after it, so both
differences are ``<= 0``. Inside a sub-block the differences ``G_r - G_i`` are
formed one pair at a time (``SUB x SUB x K``, summed over ``K`` in one
fusion). A factor that underflows to 0 stands for a product that is smaller
still.

Everything on the way to the state is float32 (never below the input's own
precision): the decays, their running sums, the scores, ``T``, the maps and
the states, and every product between them at ``highest`` precision (six
bfloat16 passes on the TPU's MXU; one pass, the default for float32 operands
there, is good to 2^-8 only). ``o`` comes back in ``v``'s dtype.
Differentiated by autodiff; the scores are under ``jax.checkpoint`` so that
no ``SUB x SUB x K`` array is kept for the backward pass.

``T`` that is not a whole number of chunks is padded (``g = 0``, ``beta =
0``: the state passes through unchanged) and the padding's outputs are cut
off, as :mod:`.ssd_scan` does.

One selection site, ``kda_recurrence`` (:mod:`.kernel_select`), two variants;
every call is in ``selection_log()`` with its chunk and shapes, and runs under
``jax.named_scope("kda_recurrence")``.

- ``reference`` — :func:`kda_chunked`, the above in jax.numpy: what
  ``mode == "reference"``, a mesh, the CPU and shapes the kernels do not tile
  run, and what the tests hold the kernels to.
- ``fused`` — :func:`kda_fused`: the same chunk equations with **a loop over
  the chunks that carries the state**, as two Mosaic kernels under one
  ``custom_vjp``, ``kda_fwd`` and ``kda_bwd`` (the pattern of
  :mod:`.ssd_scan`). The grid is (batch, head, chunk), the chunk axis
  sequential; the head's float32 state lives in VMEM scratch from chunk to
  chunk, so ``M``, ``Z`` and the associative scan are not in the program.
  ``v`` and ``o`` enter and leave as the layer has them, ``[B, T, H * V]``
  in its dtype (a head is a 128-lane column block, a chunk ``C`` sublanes:
  no transpose on either side). ``q``, ``k`` and the running sums ``G`` come
  a chunk and head at a time (``[B, H, N, C, K]`` float32), the one copy the
  scores read too: while the scores stay jax.numpy that layout has to exist,
  a second, flat one for the kernels cost 25 ms of copies a step (747
  against 722 ms busy), and two cotangents of a bfloat16 ``q`` that meet
  only after each was rounded read 2.4e-3 off where one rounding reads
  1.66e-3 (my chip runs, PR 37). The query's ``scale`` goes onto ``P`` and,
  in float32, onto ``q`` inside the kernels (as a bfloat16 product with
  ``q`` it cost 1e-3 on every result). Every product with
  the state is in the kernels (``W S``, ``(q e^G) S``, ``P u~``, ``K_end^T
  u~``, the state's decay), and so are ``W``, ``U``, ``q e^G`` and ``K_end``
  themselves. The scores ``A``, ``P`` and ``T = (I + beta A)^-1`` stay
  jax.numpy, batched over all chunks (a chunk's 16 rounds of substitution
  are a chain of small dependent products: one chunk at a time they wait on
  each other, 4,096 chunks at a time they do not). The forward saves the
  state that enters each chunk (``[B, H, N, V, K]`` float32); the backward
  walks the chunks last to first from those carrying the state's cotangent,
  and returns the cotangent of ``beta A`` as ``-T^T dT T^T``, so autodiff
  never sees the substitution. Products in the kernels are float32 at
  ``highest`` (Mosaic's ``fp32`` contract precision), as the reference's.
  Available where ``K`` and ``V`` are whole 128-lane tiles, the chunk is
  ``SUB`` times a power of two and the footprint fits the VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..analysis.annotations import jit_entry
from .pallas_kernels import (_SEQ_MIN_VMEM_LIMIT_BYTES, _interpret,
                             _seq_vmem_budget)

_LANES = 128
SUB = 16    # rows of a sub-block: the pairs formed one at a time

_HIGHEST = jax.lax.Precision.HIGHEST


def _state_dtype(dt):
    return jnp.promote_types(dt, jnp.float32)


def _round_state(a):
    """What is done to the decays' running sums, the solved ``T`` and the
    states that enter the chunks before each is used. Nothing; a function of its own so
    that a lower-precision control can replace it with one that really rounds
    (``jax.lax.reduce_precision``: a cast there and back inside a fusion is
    something the TPU compiler may skip)."""
    return a


def _mm(a, b, spec: str):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=a.dtype)


# ------------------------------------------------------------------ reference
def kda_reference(q, k, v, g, beta, scale: float = 1.0):
    """``o`` [B, T, H, V] of ``q``, ``k`` [B, T, H, K], ``v`` [B, T, H, V],
    ``g`` [B, T, H, K] (log-decay) and ``beta`` [B, T, H]: the recurrence one
    position at a time, in float32 (float64 for float64 inputs)."""
    f = _state_dtype(q.dtype)
    q, k, v, g, beta = (a.astype(f) for a in (q, k, v, g, beta))
    Bsz, T, H, K = k.shape

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp                       # [B, H, .]
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S,
                                               precision=_HIGHEST))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t * scale, S,
                             precision=_HIGHEST)

    S0 = jnp.zeros((Bsz, H, K, v.shape[-1]), f)
    _, o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


# -------------------------------------------------------------------- chunked
def _sub_block(chunk: int) -> int:
    """``SUB`` where the chunk is ``SUB`` times a power of two; else the
    chunk is its own one sub-block."""
    n = chunk // SUB
    return SUB if chunk % SUB == 0 and n & (n - 1) == 0 else chunk


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _scores(q, k, G, c: int):
    """``(A, P)`` [.., C, C] of one chunk's ``q``, ``k``, ``G`` [.., C, K]:
    ``<k_r * exp(G_r - G_i), k_i>`` for ``i < r`` and ``<q_r * exp(G_r -
    G_i), k_i>`` for ``i <= r``, zero elsewhere; sub-blocks of ``c`` rows."""
    C, K = k.shape[-2:]
    s, lead = C // c, k.shape[:-2]
    qs, ks, Gs = (a.reshape(lead + (s, c, K)) for a in (q, k, G))
    # inside a sub-block: every pair's own difference
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    d = Gs[..., :, None, :] - Gs[..., None, :, :]           # [.., s, c, c, K]
    at_or_before = (col <= row)[..., None]
    e = jnp.where(at_or_before, jnp.exp(jnp.where(at_or_before, d, 0.0)), 0.0)
    kk = ks[..., None, :, :] * e
    A_in = jnp.where(col < row, jnp.sum(ks[..., :, None, :] * kk, -1), 0.0)
    P_in = jnp.sum(qs[..., :, None, :] * kk, -1)            # [.., s, c, c]
    if s == 1:
        return A_in[..., 0, :, :], P_in[..., 0, :, :]
    # between sub-blocks: both sides decayed to the later one's first row
    G_ref = jnp.concatenate([jnp.zeros_like(Gs[..., :1, 0, :]),
                             Gs[..., :-1, c - 1, :]], axis=-2)   # [.., s, K]
    to_ref = jnp.exp(Gs - G_ref[..., None, :])
    before = (jnp.arange(C)[None, :] < (jnp.arange(s) * c)[:, None])[..., None]
    from_ref = jnp.where(before, jnp.exp(jnp.where(
        before, G_ref[..., :, None, :] - G[..., None, :, :], 0.0)), 0.0)
    k_out = k[..., None, :, :] * from_ref                    # [.., s, C, K]
    own = jnp.eye(s, dtype=k.dtype)[:, None, :, None]        # [s, 1, s, 1]

    def whole(off, inside):
        full = off.reshape(lead + (s, c, s, c)) + inside[..., None, :] * own
        return full.reshape(lead + (C, C))

    return (whole(_mm(ks * to_ref, k_out, "...sck,...sjk->...scj"), A_in),
            whole(_mm(qs * to_ref, k_out, "...sck,...sjk->...scj"), P_in))


def _inverse_of_unit_lower(A, c: int):
    """``(I + A)^-1`` of strictly lower triangular ``A`` [.., C, C]. The
    diagonal blocks of ``c`` rows by substitution, a row at a time (row ``r``
    of the inverse is ``e_r - sum_{j<r} A_rj row_j``: what the recurrence
    itself does, so it stays sound where keys that follow each other are
    nearly alike and ``beta`` is near 1); then pairs of blocks are merged,
    ``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, until one
    block is the chunk (``C / c`` is a power of two). The power series ``(I -
    A)(I + A^2)(I + A^4)...`` is no way to do it: its terms grow like
    binomial coefficients before they cancel, and at 64 rows with aligned
    keys it returned NaN (``tests/test_kimi_linear.py``)."""
    C = A.shape[-1]
    s, lead = C // c, A.shape[:-2]
    if s & (s - 1):
        raise ValueError(f"a chunk of {C} rows in sub-blocks of {c}: their "
                         "number has to be a power of two")
    blocks = A.reshape(lead + (s, c, s, c))
    own = jnp.stack([blocks[..., i, :, i, :] for i in range(s)], axis=-3)
    eye = jnp.eye(c, dtype=A.dtype)
    rows = [jnp.broadcast_to(eye[0], own.shape[:-2] + (c,))]
    for r in range(1, c):
        done = jnp.stack(rows, axis=-2)                      # [.., r, c]
        rows.append(eye[r] - jnp.einsum(
            "...j,...jk->...k", own[..., r, :r], done, precision=_HIGHEST))
    inv = jnp.stack(rows, axis=-2)                           # [.., s, c, c]
    width = c
    while width < C:
        n = C // (2 * width)
        # the lower-left block of every pair of diagonal blocks
        pairs = A.reshape(lead + (n, 2, width, n, 2, width))
        R = jnp.stack([pairs[..., i, 1, :, i, 0, :] for i in range(n)],
                      axis=-3)                                # [.., n, w, w]
        P, Q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        low = -_mm(Q, _mm(R, P, "...ij,...jk->...ik"), "...ij,...jk->...ik")
        top = jnp.concatenate([P, jnp.zeros_like(P)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([low, Q], axis=-1)],
                              axis=-2)                        # [.., n, 2w, 2w]
        width *= 2
    return inv[..., 0, :, :]


def _pad_time(chunk, q, k, v, g, beta):
    """Pad ``T`` up to whole chunks: ``g = 0`` and ``beta = 0`` keep the
    state."""
    T = q.shape[1]
    pad = (-T) % chunk
    if pad:
        widen = lambda a: jnp.pad(  # noqa: E731
            a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    return T, q, k, v, g, beta


def kda_chunked(q, k, v, g, beta, chunk: int = 64, scale: float = 1.0):
    """Same contract as :func:`kda_reference`, a chunk of ``chunk`` positions
    at a time (module docstring); ``o`` in ``v``'s dtype."""
    out_dtype = v.dtype
    f = _state_dtype(q.dtype)
    T, q, k, v, g, beta = _pad_time(chunk, q, k, v, g, beta)
    Bsz, Tp, H, K = k.shape
    V, C, N = v.shape[-1], chunk, Tp // chunk

    def chunks(a):      # [B, T, H, ...] -> [B, H, N, C, ...]
        a = a.astype(f).reshape((Bsz, N, C) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q * scale, k, v, g, beta))
    G = _round_state(jnp.cumsum(g, axis=3))                  # [B, H, N, C, K]
    A, P = _scores(q, k, G, _sub_block(C))
    inv = _round_state(_inverse_of_unit_lower(beta[..., None] * A,
                                              _sub_block(C)))
    decayed = jnp.exp(G)
    W = _mm(inv, beta[..., None] * k * decayed, "...ij,...jk->...ik")
    U = _mm(inv, beta[..., None] * v, "...ij,...jv->...iv")
    G_end = G[..., -1:, :]
    k_end = k * jnp.exp(G_end - G)
    # a chunk maps the state that enters it to the one that leaves it:
    # S' = M S + Z with M = Diag(exp(G_C)) - k_end^T W and Z = k_end^T U
    M = (jnp.exp(G_end[..., 0, :])[..., None] * jnp.eye(K, dtype=f)
         - _mm(k_end, W, "...ck,...cj->...kj"))
    Z = _mm(k_end, U, "...ck,...cv->...kv")

    def then(first, second):      # the map of two runs of chunks, in order
        (M1, Z1), (M2, Z2) = first, second
        return (_mm(M2, M1, "...ij,...jk->...ik"),
                _mm(M2, Z1, "...ij,...jv->...iv") + Z2)

    _, after = jax.lax.associative_scan(then, (M, Z), axis=2)
    S = _round_state(jnp.concatenate(              # the state that enters
        [jnp.zeros_like(after[:, :, :1]), after[:, :, :-1]], axis=2))
    u = U - _mm(W, S, "...ck,...kv->...cv")
    o = _mm(q * decayed, S, "...ck,...kv->...cv") \
        + _mm(P, u, "...cj,...jv->...cv")            # [B, H, N, C, V]
    o = jnp.moveaxis(o, 1, 3).reshape(Bsz, Tp, H, V)
    return o[:, :T].astype(out_dtype)


# ---------------------------------------------------------------------- fused
# The walk over the chunks as two Mosaic kernels under one ``custom_vjp``. A
# grid step is one chunk of one head; the chunk axis is the sequential one
# and the head's state crosses it in VMEM scratch, transposed ([V, K]) so
# that the chunk's whole decay ``exp(G_C)`` [1, K] scales it along the lanes
# and every product with it is one of the three forms the MXU takes as they
# are (``a b``, ``a b^T``, ``a^T b``).
def _dot(a, b, contract):
    """A float32 product at ``highest``: Mosaic's ``fp32`` contract precision,
    which reads as XLA's six bfloat16 passes do (7e-8 off a float64 product
    where three passes read 4e-6: my chip run, PR 37)."""
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


_AB, _ABT, _ATB = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _chunk_terms(scale, q_ref, k_ref, v_ref, g_ref, b_ref, t_ref):
    """What both kernels make of a chunk's blocks before the state comes in,
    float32: ``scale * q * exp(G)``, ``K_end``, ``exp(G_C)`` [1, K], ``W``
    and ``U`` with the factors they are made of."""
    C = g_ref.shape[0]
    f = jnp.float32
    q, k, v, G = (r[...].astype(f) for r in (q_ref, k_ref, v_ref, g_ref))
    q = q * scale
    eye = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))
    # beta comes as a row [1, C]; down the rows it is the diagonal's sums
    beta = jnp.sum(jnp.where(eye, b_ref[...], 0.0), axis=1, keepdims=True)
    G_end = G[C - 1:C, :]
    decayed, to_end = jnp.exp(G), jnp.exp(G_end - G)
    kd, vb = beta * (k * decayed), beta * v
    T = t_ref[...]
    return dict(q=q, k=k, v=v, eye=eye, beta=beta, decayed=decayed,
                to_end=to_end, qd=q * decayed, k_end=k * to_end,
                dec=jnp.exp(G_end), kd=kd, vb=vb, T=T,
                W=_dot(T, kd, _AB), U=_dot(T, vb, _AB))


@jit_entry
def _kda_fwd_kernel(scale, q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, p_ref,
                    o_ref, st_ref, s_scr):
    from jax.experimental import pallas as pl  # noqa: PLC0415

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros(s_scr.shape, s_scr.dtype)

    c = _chunk_terms(scale, q_ref, k_ref, v_ref, g_ref, b_ref, t_ref)
    St = s_scr[...]                                          # S^T [V, K]
    st_ref[...] = St                                         # as it enters
    u = c["U"] - _dot(c["W"], St, _ABT)                      # U - W S
    o = _dot(c["qd"], St, _ABT) + _dot(p_ref[...], u, _AB)
    o_ref[...] = o.astype(o_ref.dtype)
    s_scr[...] = c["dec"] * St + _dot(u, c["k_end"], _ATB)


@jit_entry
def _kda_bwd_kernel(scale, q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, p_ref,
                    st_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                    da_ref, dp_ref, ds_scr):
    """One chunk of the walk back, last chunk first: ``ds_scr`` holds the
    cotangent of the state that leaves the chunk (``dS^T`` [V, K]). ``da_ref``
    takes the cotangent of ``beta A``, the matrix ``T`` is the inverse of
    ``I +``: ``-T^T dT T^T``, so nothing differentiates the substitution."""
    from jax.experimental import pallas as pl  # noqa: PLC0415

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros(ds_scr.shape, ds_scr.dtype)

    c = _chunk_terms(scale, q_ref, k_ref, v_ref, g_ref, b_ref, t_ref)
    C = g_ref.shape[0]
    St, dSt, do = st_ref[...], ds_scr[...], do_ref[...].astype(jnp.float32)
    W, T, P, k_end, dec = c["W"], c["T"], p_ref[...], c["k_end"], c["dec"]
    u = c["U"] - _dot(W, St, _ABT)                           # as the forward
    du = _dot(P, do, _ATB) + _dot(k_end, dSt, _ABT)          # [C, V]
    dp_ref[...] = _dot(do, u, _ABT)
    dqd = _dot(do, St, _AB)                                  # of q * exp(G)
    dke = _dot(u, dSt, _AB)                                  # of K_end
    dW = -_dot(du, St, _AB)
    ds_scr[...] = (dec * dSt + _dot(do, c["qd"], _ATB) - _dot(du, W, _ATB))
    dT = _dot(dW, c["kd"], _ABT) + _dot(du, c["vb"], _ABT)   # [C, C]
    da_ref[...] = -_dot(_dot(T, dT, _ATB), T, _ABT)
    dkd, dvb = _dot(T, dW, _ATB), _dot(T, du, _ATB)
    dq_ref[...] = (dqd * (c["decayed"] * scale)).astype(dq_ref.dtype)
    dk_ref[...] = (dke * c["to_end"] + dkd * (c["beta"] * c["decayed"])
                   ).astype(dk_ref.dtype)
    dv_ref[...] = (dvb * c["beta"]).astype(dv_ref.dtype)
    # G_C is the chunk's last row of G: what K_end and the state's decay
    # give it lands there
    ended = dke * k_end
    last = (jnp.sum(ended, axis=0, keepdims=True)
            + jnp.sum(dSt * St, axis=0, keepdims=True) * dec)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1
    dg_ref[...] = (dqd * c["qd"] - ended + dkd * c["kd"]
                   + jnp.where(last_row, last, 0.0))
    dbeta = (jnp.sum(dkd * (c["k"] * c["decayed"]), axis=1, keepdims=True)
             + jnp.sum(dvb * c["v"], axis=1, keepdims=True))     # [C, 1]
    db_ref[...] = jnp.sum(jnp.where(c["eye"], dbeta, 0.0), axis=0,
                          keepdims=True)


def kda_footprint(chunk: int, K: int, V: int, itemsize: int) -> int:
    """VMEM bytes of the backward kernel (the larger of the two) a grid step:
    the streamed blocks double-buffered (``q``, ``k``, ``G`` and their
    cotangents float32, ``v``, ``do`` and ``dv`` at the item size, two ``[C,
    C]`` matrices in and two out, ``beta`` a tile each way, the state), the
    state's cotangent in scratch, and the float32 values the body holds."""
    streamed = 4 * (6 * chunk * K + 4 * chunk * chunk + 2 * 8 * _LANES
                    + V * K) + itemsize * 3 * chunk * V
    working = 4 * (16 * chunk * max(K, V) + 4 * chunk * chunk + 3 * V * K)
    return 2 * streamed + 4 * V * K + working


def kda_fits(chunk: int, K: int, V: int, itemsize: int) -> bool:
    return kda_footprint(chunk, K, V, itemsize) <= _seq_vmem_budget()


def kda_layout_ok(chunk: int, K: int, V: int) -> bool:
    """What the kernels tile: keys and values in whole 128-lane tiles, a
    chunk in sub-blocks of ``SUB`` rows, a power of two of them."""
    return K % _LANES == 0 and V % _LANES == 0 and _sub_block(chunk) == SUB


def _kda_call(kernel, reverse, operands, outs):
    """``pallas_call`` of one of the two kernels over the grid (batch, head,
    chunk), the backward's (``reverse``) last chunk first. An operand or
    result is ``[B, H, N, r, c]`` (a block ``(r, c)`` at head and chunk) or,
    ``v``, ``o`` and their cotangents, ``[B, T, H * V]`` as the layer has
    them (a block ``(C, V)``: a head is whole lane tiles, a chunk ``C``
    sublanes, and nothing is transposed for them). ``outs``: the arrays each
    result is shaped and typed like; ``operands[:5]`` are ``q``, ``k``,
    ``v``, ``G`` and ``beta`` [B, H, N, 1, C]."""
    from jax.experimental import pallas as pl  # noqa: PLC0415
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    k, v = operands[1], operands[2]
    Bsz, H, N, C, K = k.shape
    V = v.shape[2] // H
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)

    def spec(shape):
        if len(shape) == 3:
            return pl.BlockSpec((None, C, V), lambda b, h, n: (b, at(n), h))
        return pl.BlockSpec((None, None, None) + tuple(shape[3:]),
                            lambda b, h, n: (b, h, at(n), 0, 0))

    interpret = _interpret()
    return pl.pallas_call(
        kernel,
        grid=(Bsz, H, N),
        in_specs=[spec(a.shape) for a in operands],
        out_specs=tuple(spec(a.shape) for a in outs),
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in outs),
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(kda_footprint(C, K, V, v.dtype.itemsize),
                                 _SEQ_MIN_VMEM_LIMIT_BYTES)),
        interpret=interpret,
        name="kda_bwd" if reverse else "kda_fwd",
    )(*operands)


def _kda_fwd_call(q, k, v, G, beta, A, P, scale):
    Bsz, H, N, C, K = k.shape
    T = _round_state(_inverse_of_unit_lower(A, _sub_block(C)))
    states = jax.ShapeDtypeStruct((Bsz, H, N, v.shape[2] // H, K),
                                  jnp.float32)
    o, states = _kda_call(functools.partial(_kda_fwd_kernel, scale), False,
                          (q, k, v, G, beta, T, P), (v, states))
    return o, (q, k, v, G, beta, T, P, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kda_walk(q, k, v, G, beta, A, P, scale):
    """``o`` [B, T, H * V] of a chunk's ``q`` (``scale`` it is yet to take:
    a float32 factor, so that no bfloat16 product rounds it), ``k`` and
    running sums ``G`` of the log-decays [B, H, N, C, K], ``beta`` [B, H, N,
    1, C], ``beta A`` and ``P`` [B, H, N, C, C], all float32, and ``v`` [B,
    T, H * V] in the layer's dtype. ``(I + beta A)^-1`` is taken here, by
    substitution and merges over all chunks at once; the rest is the
    kernels' walk over the chunks."""
    return _kda_fwd_call(q, k, v, G, beta, A, P, scale)[0]


def _kda_walk_bwd(scale, residuals, do):
    *operands, states = residuals
    return _kda_call(functools.partial(_kda_bwd_kernel, scale), True,
                     (*operands, _round_state(states), do), operands)


_kda_walk.defvjp(_kda_fwd_call, _kda_walk_bwd)


def kda_fused(q, k, v, g, beta, chunk: int = 64, scale: float = 1.0):
    """Same contract as :func:`kda_chunked`, the state carried over the
    chunks by the kernels ``kda_fwd`` / ``kda_bwd`` (interpret mode off the
    TPU): no chunk maps and no scan over them. The scores and the inverse
    stay jax.numpy, over all chunks at once. Float32 throughout, whatever
    the inputs' dtype."""
    f = jnp.float32
    T, q, k, v, g, beta = _pad_time(chunk, q, k, v, g, beta)
    Bsz, Tp, H, K = k.shape
    V, C, N = v.shape[-1], chunk, Tp // chunk

    def chunks(a):      # [B, T, H, ...] -> [B, H, N, C, ...]
        a = a.astype(f).reshape((Bsz, N, C) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    # q, k and the running sums reach the scores and the kernels, both from
    # the one float32 copy a chunk and head: their two cotangents are added
    # there, before the one rounding to the inputs' dtype and not after two
    q, k, beta = chunks(q), chunks(k), chunks(beta)
    # the running sums as a product with a triangle of ones, every addend at
    # its full 24 bits: XLA's own cumsum (a reduce-window) took 12 ms a
    # sublayer and step here, this takes under two (my chip runs, PR 37)
    G = _round_state(_mm(jnp.tril(jnp.ones((C, C), f)), chunks(g),
                         "ij,...jk->...ik"))                 # [B, H, N, C, K]
    # P is linear in q: the query's scale goes onto its [C, C], not onto q
    A, P = _scores(q, k, G, _sub_block(C))
    o = _kda_walk(q, k, v.reshape(Bsz, Tp, H * V), G, beta[..., None, :],
                  beta[..., None] * A, P * scale, scale)
    return o.reshape(Bsz, Tp, H, V)[:, :T]


def kda_recurrence(q, k, v, g, beta, chunk: int = 64, scale: float = 1.0):
    """The recurrence by the variant the ``kda_recurrence`` selection site
    resolves for these shapes."""
    from . import select_kda_variant  # noqa: PLC0415

    Bsz, T, H, K = k.shape
    variant = select_kda_variant(Bsz, T, H, K, v.shape[-1], chunk,
                                 v.dtype.itemsize)
    fn = kda_fused if variant == "fused" else kda_chunked
    with jax.named_scope("kda_recurrence"):
        return fn(q, k, v, g, beta, chunk, scale)
