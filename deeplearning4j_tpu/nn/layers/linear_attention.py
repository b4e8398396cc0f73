"""Linear attention: the Kimi Delta Attention mixer (arXiv:2510.26692), a
gated delta rule whose state, a matrix a head, decays by a factor a channel.

No counterpart exists in the reference (2016). ``KimiDeltaAttentionLayer`` is
the mixer of one block (the residual and the block's pre-norm are the
graph's, as ``models/kimi_linear.py`` builds them). With ``H`` heads of ``D``
channels, over ``x`` [B, T, F], no bias but the output gate's:

    q = l2norm(silu(conv(x W_q)));  k = l2norm(silu(conv(x W_k)))    a head
    v = silu(conv(x W_v))                 conv: causal, depthwise, K taps
    g = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias)   [D] a head, <= 0
    beta = sigmoid(x W_beta)                              one a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t D^-0.5
    out = (rmsnorm_D(o) * norm_w * sigmoid((x W_ga) W_gb + b_g)) W_o

The recurrence is :func:`deeplearning4j_tpu.ops.kda.kda_recurrence` (site
``kda_recurrence`` of kernel selection; chunks of ``chunk`` positions). The
decays, ``beta``, the l2 norms and everything inside the recurrence are
float32; the projections take the compute dtype, as every layer's. The
convolutions are :func:`..state_space.causal_depthwise_conv`, as
``Mamba2Layer``'s, without a bias.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from .base import BaseLayer, Params, maybe_dropout, register_layer
from .state_space import causal_depthwise_conv, rms_norm


def l2_normalize(x, eps: float):
    """``x / sqrt(sum(x^2) + eps)`` over the trailing axis, in >= float32."""
    f = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(f)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
                              + eps)


@register_layer
@dataclass
class KimiDeltaAttentionLayer(BaseLayer):
    """The Kimi Delta Attention mixer over [B, T, F] sequences (module
    docstring)."""

    n_out: int = 0              # the model's hidden size (in and out)
    n_heads: int = 32
    head_dim: int = 128         # of q, k and v alike
    conv_kernel: int = 4
    gate_rank: int = 128        # of the decay gate and the output gate
    chunk: int = 64
    eps: float = 1e-5           # of the output norm
    l2_eps: float = 1e-6        # of the norms of q and k
    decay_min: float = 1.0      # A = exp(A_log) drawn uniform in [min, max]
    decay_max: float = 16.0
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    init_std: float = 0.02
    rescale_layers: int = 0     # > 0: W_o at init_std / sqrt(it)

    PARAM_ROLES = {"W_q": "attention_qkv", "W_k": "attention_qkv",
                   "W_v": "attention_qkv", "W_o": "attention_out"}
    # a bfloat16 A_log or dt_bias moves every decay by up to 0.4% a position
    FLOAT32_PARAMS = ("A_log", "dt_bias")

    @property
    def is_recurrent(self) -> bool:
        return True

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        if n_in != self.n_out:
            raise ValueError(f"KimiDeltaAttentionLayer keeps the width: n_in "
                             f"{n_in} != n_out {self.n_out}")
        dt = jnp.result_type(float)
        ks = jax.random.split(key, 14)
        d_in, H, R = self.d_inner, self.n_heads, self.gate_rank
        std = self.init_std
        normal = lambda k, shape: std * jax.random.normal(k, shape, dt)  # noqa: E731
        bound = 1.0 / math.sqrt(self.conv_kernel)
        taps = lambda tap_key: jax.random.uniform(  # noqa: E731
            tap_key, (self.conv_kernel, d_in), dt, -bound, bound)
        # the step drawn log-uniform in [min, max], then softplus inverted
        step = jnp.exp(jax.random.uniform(ks[12], (d_in,), dt)
                       * (math.log(self.time_step_max)
                          - math.log(self.time_step_min))
                       + math.log(self.time_step_min))
        step = jnp.maximum(step, self.time_step_floor)
        return {
            "W_q": normal(ks[0], (n_in, d_in)),
            "W_k": normal(ks[1], (n_in, d_in)),
            "W_v": normal(ks[2], (n_in, d_in)),
            "conv_q": taps(ks[3]), "conv_k": taps(ks[4]), "conv_v": taps(ks[5]),
            "W_fa": normal(ks[6], (n_in, R)),
            "W_fb": normal(ks[7], (R, d_in)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(
                ks[13], (H,), dt, self.decay_min, self.decay_max)),
            "W_beta": normal(ks[8], (n_in, H)),
            "W_ga": normal(ks[9], (n_in, R)),
            "W_gb": normal(ks[10], (R, d_in)),
            "b_g": jnp.zeros((d_in,), dt),
            "norm_w": jnp.ones((self.head_dim,), dt),
            "W_o": std / math.sqrt(self.rescale_layers or 1)
            * jax.random.normal(ks[11], (d_in, self.n_out), dt),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from ...ops.kda import kda_recurrence  # noqa: PLC0415

        Bsz, T, _ = x.shape
        H, D = self.n_heads, self.head_dim
        heads = lambda a: a.reshape(Bsz, T, H, D)  # noqa: E731
        f = jnp.promote_types(x.dtype, jnp.float32)
        x = maybe_dropout(x, self.dropout, train, rng)
        with jax.named_scope("proj"):
            q, k, v = (x @ params[w] for w in ("W_q", "W_k", "W_v"))
        with jax.named_scope("conv1d"):
            q, k, v = (jax.nn.silu(causal_depthwise_conv(a, params[w]))
                       for a, w in ((q, "conv_q"), (k, "conv_k"),
                                    (v, "conv_v")))
            q = l2_normalize(heads(q), self.l2_eps)
            k = l2_normalize(heads(k), self.l2_eps)
        with jax.named_scope("gates"):
            raw = (x @ params["W_fa"]) @ params["W_fb"]
            g = -jnp.exp(params["A_log"].astype(f))[:, None] * heads(
                jax.nn.softplus(raw.astype(f) + params["dt_bias"].astype(f)))
            beta = jax.nn.sigmoid((x @ params["W_beta"]).astype(f))
            gate = (x @ params["W_ga"]) @ params["W_gb"] + params["b_g"]
            if mask is not None:   # a padded position leaves the state alone
                keep = mask.astype(f)[..., None]
                g, beta = g * keep[..., None], beta * keep
        o = kda_recurrence(q, k, heads(v), g, beta, self.chunk,
                           scale=D ** -0.5)
        with jax.named_scope("out_norm"):
            o = rms_norm(o, params["norm_w"], self.eps)
            o = (o * jax.nn.sigmoid(heads(gate))).reshape(Bsz, T, H * D)
        with jax.named_scope("out_proj"):
            out = o @ params["W_o"]
        return self._activate(out), state
