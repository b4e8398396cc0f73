"""Manifold-constrained hyper-connections: a residual that is ``n`` streams
wide, read and written through learned, token-dependent maps (mHC,
arXiv:2512.24880, on top of hyper-connections, arXiv:2409.19606).

The stream ``X`` of one token is ``[n, D]``; in the graph it travels as
``[B, T, n * D]``, stream ``s`` in the features ``[s * D, (s + 1) * D)``.
Around every sublayer ``F`` (an attention, a feed-forward):

    x'      = rmsnorm(vec(X))                       over all n * D, no weight
    Hpre~   = a_pre  (x' P_pre)  + b_pre            [n]
    Hpost~  = a_post (x' P_post) + b_post           [n]
    Hres~   = a_res  mat(x' P_res) + b_res          [n, n]
    H_pre   = sigmoid(Hpre~);  H_post = 2 sigmoid(Hpost~)
    H_res   = sinkhorn(clip(Hres~, clamp_min, clamp_max))
    X'      = H_res X + H_post^T F(norm(H_pre X))

``sinkhorn``: ``M = exp(.)``, then ``iters`` times every column divided by
its sum ``+ eps``, then every row by its sum ``+ eps``: a matrix near the
doubly stochastic ones, so that the streams' mean passes a layer unchanged.

Three graph pieces, so that a device trace tells their time apart:
:class:`HyperConnectionMapsLayer` (the parameters; ``X`` -> the ``n + n +
n * n`` maps of every token, float32), and :class:`HyperConnectionVertex`
with ``op`` ``read`` (``X``, maps -> ``norm(H_pre X)``: the sublayer's
pre-norm, with its learned scale, is taken where the streams are read, so
that the un-normed sum is nowhere kept for the backward pass), ``write``
(``X``, maps, ``y`` -> ``X'``), and ``expand`` / ``collapse`` (the embedding
copied into the ``n`` streams; the streams summed before the final norm).
Each is written to read ``X`` once. The projection, ``read`` and ``write``
run as the jax.numpy below or as the Mosaic kernels of
``ops/hyper_connections.py``, as the ``hyper_connection`` selection site says
for their shapes.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import hyper_connections as hc_kernels
from ...ops import select_hyper_connection_variant
from ..conf.inputs import InputType
from ..graph.vertices import BaseVertex, register_vertex
from .base import BaseLayer, Params, register_layer


def _sinkhorn_dtype(dtype):
    """The dtype the maps, the exponential and the normalisations are
    computed in: float32 (never below the activations' own). A function so
    that a lower-precision control can replace it."""
    return jnp.promote_types(dtype, jnp.float32)


def sinkhorn(logits, iters: int, eps: float):
    """``logits`` [n, n, N] (a matrix a trailing index: tokens lie along the
    lanes) -> ``exp`` of them with columns, then rows, normalised ``iters``
    times, each sum ``+ eps`` in the denominator."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)   # columns
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows
    return m


def _fused(op: str, x, n: int) -> bool:
    """Whether the ``hyper_connection`` site takes the kernels for piece
    ``op`` of the streams ``x`` [.., n * D]."""
    return select_hyper_connection_variant(
        op, x.size // x.shape[-1], n, x.shape[-1] // n,
        x.dtype.itemsize) == "fused"


def split_maps(maps, n: int):
    """``(H_pre [.., n], H_post [.., n], H_res [.., n, n])`` of the maps as
    :class:`HyperConnectionMapsLayer` lays them out."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:].reshape(maps.shape[:-1] + (n, n)))


@register_layer
@dataclass
class HyperConnectionMapsLayer(BaseLayer):
    """``X`` [B, T, n * D] -> the maps of every token [B, T, n + n + n * n]:
    ``H_pre`` (n), ``H_post`` (n), ``H_res`` (n x n, row-major: ``X'_i`` takes
    ``H_res[i, j] X_j``), in float32 whatever the compute dtype.

    The norm of ``vec(X)`` has no weight, so ``x' P = (X P) * rsqrt(mean(X^2)
    + eps)``: the product runs on ``X`` as it arrives and the normalised copy
    is never written. Starts: ``P_*`` normal at ``init_std``; ``a_*`` at
    ``gate_init``; ``b_pre`` reads stream ``read_stream`` (+``bias_init`` there,
    -``bias_init`` elsewhere); ``b_post`` 0 (every stream written with 1);
    ``b_res`` ``bias_init`` on the diagonal and 0 off it (near the identity
    after the normalisations)."""

    n_streams: int = 4
    sinkhorn_iters: int = 20
    eps: float = 1e-6             # the Sinkhorn denominators'
    clamp_min: float = -30.0
    clamp_max: float = 30.0
    norm_eps: float = 1e-6
    read_stream: int = 0
    init_std: float = 0.02
    gate_init: float = 0.01
    bias_init: float = 4.0

    FLOAT32_PARAMS = ("P", "a", "b")

    @property
    def is_recurrent(self) -> bool:
        return False

    @property
    def n_maps(self) -> int:
        return self.n_streams * (2 + self.n_streams)

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_maps, input_type.timesteps)
        return InputType.feed_forward(self.n_maps)

    def init_params(self, key, input_type) -> Params:
        n = self.n_streams
        if input_type.size % n:
            raise ValueError(f"{input_type.size} features are not {n} streams")
        dt = jnp.result_type(float)
        b_pre = jnp.where(jnp.arange(n) == self.read_stream % n,
                          self.bias_init, -self.bias_init)
        b = jnp.concatenate([b_pre, jnp.zeros((n,)),
                             (self.bias_init * jnp.eye(n)).reshape(-1)])
        return {
            # columns: P_pre (n) | P_post (n) | P_res (n * n)
            "P": self.init_std * jax.random.normal(
                key, (input_type.size, self.n_maps), dt),
            "a": jnp.full((3,), self.gate_init, dt),     # pre, post, res
            "b": b.astype(dt),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return self._maps(params, x, False)[0], state

    def apply_handing_on(self, params, x, state, *, train=False, rng=None,
                         mask=None):
        """``((maps, x), state)``: ``BaseVertex.hands_input_on``."""
        return self._maps(params, x, True), state

    def _maps(self, params, x, hand_on: bool):
        """``(maps, x)``; with ``hand_on`` the ``x`` whose cotangent the
        projection's backward kernel adds onto."""
        n = self.n_streams
        f = _sinkhorn_dtype(x.dtype)
        lead = x.shape[:-1]
        tokens = x.reshape(-1, x.shape[-1])
        with jax.named_scope("project"):
            # [maps, N] and [1, N]: tokens along the lanes from here on
            if _fused("maps", x, n):
                project = hc_kernels.hc_project_handing_on if hand_on \
                    else hc_kernels.hc_project
                xp, ms, *on = project(tokens, params["P"].astype(f), n)
                xp, ms = xp.astype(f), ms.astype(f)
                x = on[0].reshape(x.shape) if on else x
            else:
                ms = jnp.mean(jnp.square(tokens.astype(f)), axis=-1,
                              keepdims=True).T
                xp = jnp.dot(tokens.astype(f), params["P"].astype(f),
                             precision=jax.lax.Precision.HIGHEST).T
            gate = params["a"].astype(f)[
                np.repeat(np.arange(3), [n, n, n * n])]
            raw = xp * jax.lax.rsqrt(ms + self.norm_eps) * gate[:, None] \
                + params["b"].astype(f)[:, None]
        with jax.named_scope("sinkhorn"):
            res = sinkhorn(
                jnp.clip(raw[2 * n:], self.clamp_min, self.clamp_max)
                .reshape(n, n, -1), self.sinkhorn_iters, self.eps)
        maps = jnp.concatenate([jax.nn.sigmoid(raw[:n]),
                                2.0 * jax.nn.sigmoid(raw[n:2 * n]),
                                res.reshape(n * n, -1)], axis=0).T
        return maps.reshape(lead + (self.n_maps,)), x


@register_vertex
@dataclass
class HyperConnectionVertex(BaseVertex):
    """The parameter-free pieces of a hyper-connected residual of
    ``n_streams`` streams (module docstring), by ``op``:

    - ``expand``: ``x`` [.., D] -> ``X`` [.., n * D], ``x`` copied into
      every stream;
    - ``read``: ``X``, maps -> ``sum_s H_pre[s] X_s`` [.., D]; with
      ``norm_eps`` > 0 the RMS norm of it times the learned ``gamma`` (the
      vertex's one parameter);
    - ``write``: ``X``, maps, ``y`` -> ``X'`` [.., n * D] with ``X'_i = sum_j
      H_res[i, j] X_j + H_post[i] y``;
    - ``collapse``: ``X`` -> ``sum_s X_s`` [.., D].

    The sums are taken in float32 and handed on in ``X``'s dtype."""

    op: str = "read"
    n_streams: int = 4
    norm_eps: float = 0.0      # read only; > 0: RMS norm with a learned scale

    @property
    def has_params(self) -> bool:
        return self.op == "read" and self.norm_eps > 0

    def init_params(self, key, *input_types: InputType) -> Params:
        if not self.has_params:
            return {}
        return {"gamma": jnp.ones((input_types[0].size // self.n_streams,),
                                  jnp.result_type(float))}

    def _resized(self, t: InputType, size: int) -> InputType:
        if t.kind == "rnn":
            return InputType.recurrent(size, t.timesteps)
        return InputType.feed_forward(size)

    def get_output_type(self, *input_types: InputType) -> InputType:
        n, t = self.n_streams, input_types[0]
        want = {"expand": 1, "read": 2, "write": 3, "collapse": 1}
        if self.op not in want:
            raise ValueError(f"Unknown HyperConnectionVertex op '{self.op}'")
        if len(input_types) != want[self.op]:
            raise ValueError(f"HyperConnectionVertex '{self.op}' takes "
                             f"{want[self.op]} inputs, got {len(input_types)}")
        if self.op == "expand":
            return self._resized(t, t.size * n)
        if t.size % n:
            raise ValueError(f"{t.size} features are not {n} streams")
        if self.op == "write":
            return t
        return self._resized(t, t.size // n)

    def _apply_kernels(self, params, inputs, hand_on: bool = False):
        """``read`` or ``write`` through ``ops/hyper_connections.py``; with
        ``hand_on`` the read's ``(output, x)``."""
        n, x = self.n_streams, inputs[0]
        tokens = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
        shaped = lambda a: a.reshape(x.shape[:-1] + a.shape[-1:])  # noqa: E731
        if self.op == "write":
            return shaped(hc_kernels.hc_write(tokens(x), tokens(inputs[1]),
                                              tokens(inputs[2]), n))
        read = hc_kernels.hc_read_handing_on if hand_on else hc_kernels.hc_read
        out = read(tokens(x), tokens(inputs[1]),
                   params["gamma"] if self.has_params else None, n,
                   float(self.norm_eps) if self.has_params else 0.0)
        return (shaped(out[0]), shaped(out[1])) if hand_on else shaped(out)

    @property
    def hands_input_on(self) -> bool:
        return self.op == "read"

    def apply_handing_on(self, params, inputs, state, *, train=False,
                         rng=None, masks=None):
        """The read's ``((output, X), state)``: ``X`` handed on, so that the
        write's cotangent of it comes back through the read's backward
        kernel, which adds onto it."""
        if _fused("read", inputs[0], self.n_streams):
            return self._apply_kernels(params, inputs, hand_on=True), state
        out, state = self.apply(params, inputs, state, train=train, rng=rng,
                                masks=masks)
        return (out, inputs[0]), state

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        n, x = self.n_streams, inputs[0]
        if self.op == "expand":
            return jnp.tile(x, (1,) * (x.ndim - 1) + (n,)), state
        if self.op in ("read", "write") and _fused(self.op, x, n):
            return self._apply_kernels(params, inputs), state
        f = jnp.promote_types(x.dtype, jnp.float32)
        d = x.shape[-1] // n
        # a stream is a slice of the features (whole lane tiles at a width
        # that is a multiple of 128): no [.., n, D] array, whose rows of n
        # would be padded to a sublane tile
        streams = [x[..., s * d:(s + 1) * d].astype(f) for s in range(n)]
        if self.op == "collapse":
            return sum(streams[1:], start=streams[0]).astype(x.dtype), state
        maps = inputs[1].astype(f)
        col = lambda k: maps[..., k:k + 1]      # one map of every token  # noqa: E731
        if self.op == "read":
            h = sum(col(s) * streams[s] for s in range(n))
            if self.has_params:
                h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1,
                                               keepdims=True) + self.norm_eps)
                h = h * params["gamma"].astype(f)
            return h.astype(x.dtype), state
        y = inputs[2].astype(f)
        # rounded stream by stream: the backward pass then takes the
        # cotangent's slices as they are, and no float32 copy of all the
        # streams is written
        out = [(sum(col(2 * n + i * n + j) * streams[j] for j in range(n))
                + col(n + i) * y).astype(x.dtype) for i in range(n)]
        return jnp.concatenate(out, axis=-1), state
