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

One selection site, ``kda_recurrence`` (:mod:`.kernel_select`), with the one
variant there is (``reference``: this file's jax.numpy); every call is in
``selection_log()`` with its chunk and shapes, and runs under
``jax.named_scope("kda_recurrence")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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


def kda_recurrence(q, k, v, g, beta, chunk: int = 64, scale: float = 1.0):
    """The recurrence by the variant the ``kda_recurrence`` selection site
    resolves for these shapes (one so far: :func:`kda_chunked`)."""
    from . import select_kda_variant  # noqa: PLC0415

    Bsz, T, H, K = k.shape
    select_kda_variant(Bsz, T, H, K, v.shape[-1], chunk, v.dtype.itemsize)
    with jax.named_scope("kda_recurrence"):
        return kda_chunked(q, k, v, g, beta, chunk, scale)
