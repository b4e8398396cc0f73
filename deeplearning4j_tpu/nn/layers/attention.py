"""Attention layers — the long-context tier's nn surface.

No counterpart exists in the reference (2016: SURVEY.md §5.7 — sequence
handling is TBPTT + masking only); these layers extend the framework beyond
parity per the long-context-first design requirement. The math lives in
:mod:`deeplearning4j_tpu.parallel.ring_attention`; a layer switches between
the local kernel and ring/all-to-all sequence parallelism purely by the mesh
context the trainer establishes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from .base import BaseLayer, Params, register_layer, maybe_dropout


@register_layer
@dataclass
class LayerNormLayer(BaseLayer):
    """Per-feature LayerNorm over the trailing axis (transformer building
    block; the reference's closest relative is BatchNormalization)."""

    eps: float = 1e-5

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    @property
    def is_recurrent(self) -> bool:
        return False  # shape-agnostic; works on [B,F] and [B,T,F]

    def init_params(self, key, input_type) -> Params:
        # normalization runs over the TRAILING axis, so gamma/beta size by it:
        # features for ff/rnn, channels for NHWC conv activations
        if input_type.kind in ("ff", "rnn"):
            n = input_type.size
        elif input_type.kind == "cnn":
            n = input_type.channels
        else:
            n = input_type.flat_size()
        dt = jnp.result_type(float)
        return {"gamma": jnp.ones((n,), dt), "beta": jnp.zeros((n,), dt)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        mean = x.mean(axis=-1, keepdims=True)
        var = x.var(axis=-1, keepdims=True)
        xhat = (x - mean) * jax.lax.rsqrt(var + self.eps)
        return self._activate(xhat * params["gamma"] + params["beta"]), state


@register_layer
@dataclass
class SelfAttentionLayer(BaseLayer):
    """Multi-head self-attention over [B,T,F] sequences.

    ``sequence_parallel`` selects the mesh execution when the trainer has
    installed one via :func:`set_attention_mesh`: "ring" (K/V circulate the
    ICI ring — arbitrarily long sequences) or "all_to_all" (Ulysses-style
    head swap). With no mesh installed the local fused kernel runs.

    Head layouts. ``n_kv_heads`` (0: as many as ``n_heads``) key/value heads
    are shared by ``n_heads // n_kv_heads`` query heads each (grouped-query
    attention; 1 is multi-query); ``head_dim`` (0: ``n_out // n_heads``)
    frees the head size from ``n_out``. Every layout reaches the flash
    kernel, which reads a shared key/value head in place (one K/V strip per
    group, no repeated copy) and whose limit is the K/V strip's VMEM; the XLA
    path and the two mesh paths repeat the shared heads first. The defaults
    keep the parameter shapes of a plain multi-head layer.
    """

    n_out: int = 0
    n_heads: int = 4
    n_kv_heads: int = 0   # 0: n_heads (no sharing)
    head_dim: int = 0     # 0: n_out // n_heads
    has_bias: bool = True  # the output projection's bias
    causal: bool = False
    sequence_parallel: str = "ring"  # ring | all_to_all
    # local-kernel choice: "auto" (cost-model-guided — ops.kernel_select
    # scores the variants on the roofline, flash above the
    # DL4JTPU_FLASH_MIN_SEQ threshold when it is memory-bound), "xla"
    # (compiler-fused, materializes [T,T] scores) or "flash" (Pallas
    # blockwise online-softmax, O(T) memory — ops/flash_attention.py).
    # The explicit values are the per-site escape hatch.
    attention_impl: str = "auto"

    # parallel.roles registry (MeshLayout(roles=True)): QKV column-parallel
    # (each tp device computes whole heads), out-projection row-parallel —
    # the Megatron pattern; the block pays ONE all-reduce instead of
    # per-site activation gathers (DT305).
    PARAM_ROLES = {"Wq": "attention_qkv", "Wk": "attention_qkv",
                   "Wv": "attention_qkv", "Wo": "attention_out",
                   "bo": "attention_out"}

    @property
    def is_recurrent(self) -> bool:
        return True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        d = self.n_out
        if not self.head_dim and d % self.n_heads:
            raise ValueError(f"n_out {d} not divisible by n_heads {self.n_heads}")
        H, Hkv, D = self._heads()
        if H % Hkv:
            raise ValueError(f"n_heads {H} not divisible by n_kv_heads {Hkv}")
        kq, kk, kv, ko = jax.random.split(key, 4)
        p = {
            "Wq": self._init_weight(kq, (n_in, H * D), n_in, H * D),
            "Wk": self._init_weight(kk, (n_in, Hkv * D), n_in, Hkv * D),
            "Wv": self._init_weight(kv, (n_in, Hkv * D), n_in, Hkv * D),
            "Wo": self._init_weight(ko, (H * D, d), H * D, d),
        }
        if self.has_bias:
            p["bo"] = self._init_bias((d,))
        return p

    def _heads(self):
        """(query heads, key/value heads, head size)."""
        return (self.n_heads, self.n_kv_heads or self.n_heads,
                self.head_dim or self.n_out // self.n_heads)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from ...parallel.ring_attention import (  # noqa: PLC0415
            all_to_all_attention,
            attention,
            ring_attention,
        )

        B, T, _unused = x.shape
        H, Hkv, D = self._heads()

        def split(w, heads):
            return (x @ w).reshape(B, T, heads, D).transpose(0, 2, 1, 3)

        q = split(params["Wq"], H)
        k, v = split(params["Wk"], Hkv), split(params["Wv"], Hkv)

        def whole(kv):   # every path but flash reads one K/V head a query head
            return kv if Hkv == H else jnp.repeat(kv, H // Hkv, axis=1)
        # padded keys are excluded with -inf scores inside the kernel
        key_mask = None if mask is None else mask.astype(x.dtype)

        mesh_ctx = get_attention_mesh()
        if mesh_ctx is None:
            from ... import ops as _ops  # noqa: PLC0415

            variant = _ops.select_attention_variant(
                B, H, T, D, x.dtype.itemsize, impl=self.attention_impl,
                causal=self.causal, kv_heads=Hkv)
            if variant == "flash":
                from ...ops.flash_attention import flash_attention  # noqa: PLC0415

                out = flash_attention(q, k, v, causal=self.causal,
                                      key_mask=key_mask)
            else:
                out = attention(q, whole(k), whole(v), causal=self.causal,
                                key_mask=key_mask)
        else:
            mesh, axis, batch_axes = mesh_ctx
            fn = (ring_attention if self.sequence_parallel == "ring"
                  else all_to_all_attention)
            out = fn(q, whole(k), whole(v), mesh, seq_axis=axis,
                     causal=self.causal, key_mask=key_mask,
                     batch_axes=batch_axes)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        out = out @ params["Wo"]
        if self.has_bias:
            out = out + params["bo"]
        out = maybe_dropout(out, self.dropout, train, rng)
        return self._activate(out), state


# ---------------------------------------------------------- rotary embeddings
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1``, and 1 for no stretch."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """The ``dim // 2`` rotary frequencies, float32. ``factor`` 1: the plain
    ``base ** (-2j / dim)``. Otherwise YaRN's blend: a frequency that turns
    more than ``beta_fast`` times over the ``original`` positions is kept, one
    that turns fewer than ``beta_slow`` times is divided by ``factor``, and a
    linear ramp over the pair index joins the two (the correction dims are
    where a pair turns exactly ``beta`` times: ``dim * ln(original / (2 pi
    beta)) / (2 ln base)``, floored and ceiled, kept inside ``[0, dim-1]``)."""
    plain = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 1:
        return plain

    def turns(beta):
        return dim * math.log(original / (beta * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotary_angles(t: int, inv_freq):
    """``position * frequency`` [t, dim/2], float32. A function of its own
    so that a lower-precision control can replace it (with one that really
    rounds: ``jax.lax.reduce_precision``; a cast there and back inside a
    fusion is something the compiler may skip)."""
    return jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]


def apply_rotary(x, inv_freq, magnitude: float = 1.0):
    """Rotate ``x`` [B, T, heads, dim] by its position along ``T``. The
    lanes arrive interleaved (pair ``j`` is lanes ``2j``, ``2j + 1``): they
    are de-interleaved to ``[evens, odds]`` and rotated as halves, ``x cos +
    rotate_half(x) sin`` with ``rotate_half([a, b]) = [-b, a]``. Angles and
    rotation in float32 (never below ``x``'s own dtype); the result is in
    ``x``'s dtype and in
    the de-interleaved order (queries and keys alike, so their product is
    the interleaved one's)."""
    f = jnp.promote_types(x.dtype, jnp.float32)
    angles = _rotary_angles(x.shape[1], inv_freq).astype(f)  # [T, dim/2]
    cos = (jnp.cos(angles) * magnitude)[None, :, None, :]
    sin = (jnp.sin(angles) * magnitude)[None, :, None, :]
    xf = x.astype(f)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@register_layer
@dataclass
class LatentAttentionLayer(BaseLayer):
    """Multi-head latent attention over [B, T, F]: queries and keys/values
    through low ranks with an RMS norm on each latent, a per-head part of the
    scores that takes no position (``nope_dim``) beside a rotary part
    (``rope_dim``) whose key is one head shared by every query head, values
    of their own size (``v_dim``), no bias:

        c_q  = rmsnorm(x W_qa) * q_norm                        [q_rank]
        [q_nope_i, q_rope_i] = c_q W_qb                        per head i
        [c_kv, k_rope] = x W_kva;  c_kv = rmsnorm(c_kv) * kv_norm
        [k_nope_i, v_i] = c_kv W_kvb                           per head i
        o_i = softmax_causal(([q_nope_i, rope(q_rope_i)]
                              . [k_nope_i, rope(k_rope)]) * s) v_i
        out = concat_i(o_i) W_o

    with ``s = (nope_dim + rope_dim) ** -0.5 * m^2``, ``m =
    yarn_mscale(rope_factor, rope_mscale_all_dim)`` (1 when that is 0 or the
    context is not stretched). The rotary frequencies are
    :func:`yarn_inv_freq`'s, the cosines and sines scaled by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.

    ``q_rank=None``: the queries take no low rank and no norm, ``[q_nope_i,
    q_rope_i] = x W_q``. ``rotary=False``: no position enters (a stack whose
    other mixers carry the order): the ``rope_dim`` lanes stay what they are,
    one key part shared by every head, and ``s = (nope_dim + rope_dim) **
    -0.5``.

    ``heads_held_first`` / ``heads_held_count`` (0: all ``n_heads``) name the
    heads whose ``W_qb``, ``W_kvb`` and ``W_o`` slices live here (a
    tensor-parallel rank's share); ``W_qa``, ``W_kva`` and the two norms are
    alike on every rank. The output is the held heads' partial sum: over
    every share it is the whole layer's.

    On the chip the scores run through the flash kernels (site ``attention``
    of kernel selection) as two products, ``q_nope k_nope^T + q_rope
    k_rope^T``, the rotary key read in place by every head; the XLA path
    concatenates the parts and repeats the rotary key.
    """

    n_out: int = 0
    n_heads: int = 32
    heads_held_first: int = 0
    heads_held_count: int = 0     # 0: all n_heads
    q_rank: Optional[int] = 768   # None: one W_q, no query latent
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    eps: float = 1e-6
    rotary: bool = True           # False: the rope_dim lanes are not rotated
    rope_theta: float = 10000.0
    rope_factor: float = 1.0      # > 1: YaRN over rope_original_positions
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    causal: bool = True
    attention_impl: str = "auto"   # as SelfAttentionLayer's
    init_std: float = 0.02
    rescale_layers: int = 0        # > 0: W_o at init_std / sqrt(it)

    PARAM_ROLES = {"W_qb": "attention_qkv", "W_q": "attention_qkv",
                   "W_kvb": "attention_qkv", "W_o": "attention_out"}

    @property
    def is_recurrent(self) -> bool:
        return True

    @property
    def held(self):
        """(first, count) of the heads whose weights are here."""
        return (self.heads_held_first, self.heads_held_count or self.n_heads)

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) \
            if self.rope_mscale_all_dim and self.rotary else 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    @property
    def rotary_magnitude(self) -> float:
        return (yarn_mscale(self.rope_factor, self.rope_mscale)
                / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        first, count = self.held
        if not 0 <= first <= first + count <= self.n_heads:
            raise ValueError(f"heads held [{first}, {first + count}) are not "
                             f"among the {self.n_heads}")
        if self.rope_dim % 2:
            raise ValueError(f"rope_dim {self.rope_dim} is odd: rotary "
                             "lanes come in pairs")
        dt = jnp.result_type(float)
        ks = jax.random.split(key, 5)
        normal = jax.random.normal
        out_std = self.init_std / math.sqrt(self.rescale_layers or 1)
        qk = self.nope_dim + self.rope_dim
        if self.q_rank is None:
            queries = {"W_q": self.init_std * normal(ks[0], (n_in, count * qk),
                                                     dt)}
        else:
            queries = {
                "W_qa": self.init_std * normal(ks[0], (n_in, self.q_rank), dt),
                "q_norm": jnp.ones((self.q_rank,), dt),
                "W_qb": self.init_std * normal(
                    ks[1], (self.q_rank, count * qk), dt)}
        return {
            **queries,
            "W_kva": self.init_std * normal(
                ks[2], (n_in, self.kv_rank + self.rope_dim), dt),
            "kv_norm": jnp.ones((self.kv_rank,), dt),
            "W_kvb": self.init_std * normal(
                ks[3], (self.kv_rank, count * (self.nope_dim + self.v_dim)), dt),
            "W_o": out_std * normal(ks[4], (count * self.v_dim, self.n_out), dt),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from ...parallel.ring_attention import attention  # noqa: PLC0415
        from .state_space import rms_norm  # noqa: PLC0415

        if get_attention_mesh() is not None:
            raise NotImplementedError(
                "LatentAttentionLayer has no sequence-parallel path: clear "
                "set_attention_mesh or shard its heads (heads_held)")
        B, T, _unused = x.shape
        H = self.held[1]
        dn, dr, dv = self.nope_dim, self.rope_dim, self.v_dim
        x = maybe_dropout(x, self.dropout, train, rng)
        with jax.named_scope("q_proj"):
            if self.q_rank is None:
                q = x @ params["W_q"]
            else:
                c_q = rms_norm(x @ params["W_qa"], params["q_norm"], self.eps)
                q = c_q @ params["W_qb"]
            q = q.reshape(B, T, H, dn + dr)
        with jax.named_scope("kv_proj"):
            kva = x @ params["W_kva"]
            c_kv = rms_norm(kva[..., :self.kv_rank], params["kv_norm"],
                            self.eps)
            kv = (c_kv @ params["W_kvb"]).reshape(B, T, H, dn + dv)
        if self.rotary:
            with jax.named_scope("rotary"):
                inv_freq = yarn_inv_freq(
                    dr, self.rope_theta, self.rope_factor,
                    self.rope_original_positions, self.rope_beta_fast,
                    self.rope_beta_slow)
                q_rope = apply_rotary(q[..., dn:], inv_freq,
                                      self.rotary_magnitude)
                k_rope = apply_rotary(kva[..., None, self.kv_rank:], inv_freq,
                                      self.rotary_magnitude)  # [B, T, 1, dr]
        else:
            q_rope, k_rope = q[..., dn:], kva[..., None, self.kv_rank:]
        heads_first = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        q_nope, q_rope = heads_first(q[..., :dn]), heads_first(q_rope)
        k_nope, v = heads_first(kv[..., :dn]), heads_first(kv[..., dn:])
        k_rope = heads_first(k_rope)
        key_mask = None if mask is None else mask.astype(x.dtype)

        from ... import ops as _ops  # noqa: PLC0415

        variant = _ops.select_attention_variant(
            B, H, T, dn + dr, x.dtype.itemsize, impl=self.attention_impl,
            causal=self.causal, d_v=dv, d_rope=dr)
        with jax.named_scope("scores"):
            if variant == "flash":
                from ...ops.flash_attention import flash_attention  # noqa: PLC0415

                out = flash_attention(q_nope, k_nope, v, causal=self.causal,
                                      scale=self.softmax_scale,
                                      key_mask=key_mask, q_rope=q_rope,
                                      k_rope=k_rope)
            else:
                out = attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1),
                    jnp.concatenate([k_nope, jnp.repeat(k_rope, H, axis=1)],
                                    axis=-1),
                    v, causal=self.causal, scale=self.softmax_scale,
                    key_mask=key_mask)
        with jax.named_scope("out_proj"):
            out = heads_first(out).reshape(B, T, H * dv) @ params["W_o"]
        return self._activate(out), state


_ATTENTION_MESH: Optional[tuple] = None


def set_attention_mesh(mesh, seq_axis: str = "seq", nets=(),
                       batch_axes=()) -> None:
    """Install (or clear, with None) the mesh attention layers execute on —
    call BEFORE the first fit/output: the choice is captured at jit trace
    time. ``batch_axes`` names the mesh axes the batch dim is sharded over
    so the shard_map kernels keep it sharded inside the region. Pass
    already-traced models via ``nets`` to drop their cached programs so the
    new mesh takes effect."""
    global _ATTENTION_MESH
    _ATTENTION_MESH = (None if mesh is None
                       else (mesh, seq_axis, tuple(batch_axes or ())))
    for net in nets:
        for attr in ("_train_step", "_eval_forward", "_tbptt_step", "_rnn_step_fn",
                     "_grad_stats_step"):
            if hasattr(net, attr):
                setattr(net, attr, None)


def get_attention_mesh():
    return _ATTENTION_MESH
