"""State-space layers: the Mamba-2 mixer and the RMS norm it is built with.

No counterpart exists in the reference (2016); like attention and the expert
layers, these extend the framework to the hybrid state-space models of today.
``Mamba2Layer`` is the mixer of one block (the residual and the block's
pre-norm are the graph's, as ``models/nemotron_h.py`` builds them):

    z, xBC, dt = split(x @ W_in)                      in_proj, no bias
    xBC = silu(causal_depthwise_conv(xBC) + conv_b)   K taps
    x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
    y = rmsnorm_grouped(y * silu(z)) * norm_w         norm after the gate
    out = y @ W_out

The recurrence is :func:`deeplearning4j_tpu.ops.ssd_scan.ssd_scan` (site
``ssd_scan`` of kernel selection: the chunked form in plain jax.numpy, or
the Mosaic kernels ``ssd_scan_fwd`` / ``ssd_scan_bwd``). The convolution is
``conv_kernel`` shifted multiply-adds over the time axis (4 taps: cheaper to
read than a ``conv_general_dilated`` call and fused by XLA into one pass).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from .base import BaseLayer, Params, register_layer, maybe_dropout


def rms_norm(x, weight, eps: float, groups: int = 1):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the trailing axis, or over
    each of ``groups`` equal slices of it; statistics in >= float32."""
    f = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(f)
    shape = xf.shape
    if groups > 1:
        xf = xf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                            + eps)
    return (xf.reshape(shape) * weight.astype(f)).astype(x.dtype)


@register_layer
@dataclass
class RMSNormLayer(BaseLayer):
    """RMS norm over the trailing axis with a learned scale, no bias and no
    mean subtraction; ``groups`` > 1 normalises equal slices separately."""

    eps: float = 1e-5
    groups: int = 1

    PARAM_ROLES = {"gamma": "generic"}

    @property
    def is_recurrent(self) -> bool:
        return False  # shape-agnostic; works on [B,F] and [B,T,F]

    def init_params(self, key, input_type) -> Params:
        n = input_type.size if input_type.kind in ("ff", "rnn") \
            else input_type.flat_size()
        if n % self.groups:
            raise ValueError(f"{n} features do not split into {self.groups} "
                             "norm groups")
        return {"gamma": jnp.ones((n,), jnp.result_type(float))}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        y = rms_norm(x, params["gamma"], self.eps, self.groups)
        return self._activate(y), state


def causal_depthwise_conv(x, w, b=None):
    """``y[t] = b + sum_j w[j] * x[t - (K-1) + j]`` over ``x`` [B, T, C] with
    zeros before the sequence's start: ``K`` shifted multiply-adds (no ``b``:
    no bias)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = b
    for j in range(K):
        tap = xp[:, j:j + T, :] * w[j]
        y = tap if y is None else y + tap
    return y


@register_layer
@dataclass
class Mamba2Layer(BaseLayer):
    """The Mamba-2 mixer over [B, T, F] sequences (module docstring)."""

    n_out: int = 0              # the model's hidden size (in and out)
    n_heads: int = 64
    head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    init_std: float = 0.02
    # > 0: W_out drawn at init_std / sqrt(rescale_layers), as a pre-norm
    # residual stack of that many layers rescales its output projections
    rescale_layers: int = 0

    PARAM_ROLES = {"W_in": "ffn_up", "W_out": "ffn_down"}
    # the step sizes and decays are float32 whatever the compute dtype: a
    # bfloat16 A_log moves every decay by up to 0.4% a position
    FLOAT32_PARAMS = ("A_log", "dt_bias", "D")

    @property
    def is_recurrent(self) -> bool:
        return True

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        if n_in != self.n_out:
            raise ValueError(f"Mamba2Layer keeps the width: n_in {n_in} != "
                             f"n_out {self.n_out}")
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads do not split into "
                             f"{self.n_groups} groups")
        dt = jnp.result_type(float)
        k_in, k_conv, k_dt, k_out = jax.random.split(key, 4)
        d_in, H = self.d_inner, self.n_heads
        proj = d_in + self.conv_dim + H                   # z | xBC | dt
        out_std = self.init_std / math.sqrt(self.rescale_layers or 1)
        # dt drawn log-uniform in [min, max], then softplus inverted
        step = jnp.exp(jax.random.uniform(k_dt, (H,), dt)
                       * (math.log(self.time_step_max)
                          - math.log(self.time_step_min))
                       + math.log(self.time_step_min))
        step = jnp.maximum(step, self.time_step_floor)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "W_in": self.init_std * jax.random.normal(k_in, (n_in, proj), dt),
            "conv_w": jax.random.uniform(k_conv, (self.conv_kernel,
                                                  self.conv_dim), dt,
                                         -bound, bound),
            "conv_b": jnp.zeros((self.conv_dim,), dt),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jnp.arange(1, H + 1, dtype=dt)),
            "D": jnp.ones((H,), dt),
            "norm_w": jnp.ones((d_in,), dt),
            "W_out": out_std * jax.random.normal(k_out, (d_in, self.n_out), dt),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from ...ops.ssd_scan import ssd_scan  # noqa: PLC0415

        Bsz, T, _ = x.shape
        H, P, G, N = (self.n_heads, self.head_dim, self.n_groups,
                      self.state_size)
        d_in = self.d_inner
        f = jnp.promote_types(x.dtype, jnp.float32)
        x = maybe_dropout(x, self.dropout, train, rng)
        with jax.named_scope("in_proj"):
            zxbcdt = x @ params["W_in"]
            z = zxbcdt[..., :d_in]
            xbc = zxbcdt[..., d_in:d_in + self.conv_dim]
            dt = zxbcdt[..., d_in + self.conv_dim:]
        with jax.named_scope("conv1d"):
            xbc = jax.nn.silu(causal_depthwise_conv(
                xbc, params["conv_w"], params["conv_b"]))
        xs = xbc[..., :d_in].reshape(Bsz, T, H, P)
        Bm = xbc[..., d_in:d_in + G * N].reshape(Bsz, T, G, N)
        Cm = xbc[..., d_in + G * N:].reshape(Bsz, T, G, N)
        with jax.named_scope("ssd_scan"):
            step = jax.nn.softplus(dt.astype(f) + params["dt_bias"].astype(f))
            if mask is not None:   # a padded position leaves the state alone
                step = step * mask.astype(f)[..., None]
            A = -jnp.exp(params["A_log"].astype(f))
            y = ssd_scan(xs, step, A, Bm, Cm, self.chunk_size)
            y = (y + xs * params["D"].astype(f)[:, None]).astype(xs.dtype)
        with jax.named_scope("gated_norm"):
            y = y.reshape(Bsz, T, d_in)
            y = rms_norm(y * jax.nn.silu(z), params["norm_w"], self.eps, G)
        with jax.named_scope("out_proj"):
            out = y @ params["W_out"]
        return self._activate(out), state
