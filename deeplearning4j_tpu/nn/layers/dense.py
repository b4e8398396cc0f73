"""Dense / feed-forward layers + output layers.

Reference parity: nn/conf/layers/DenseLayer + nn/layers/feedforward/dense,
nn/conf/layers/OutputLayer + nn/layers/OutputLayer, ActivationLayer,
DropoutLayer, LossLayer, EmbeddingLayer
(see SURVEY.md §2.1 "Layer SPI + impls").

Matmuls are the MXU path: ``x @ W`` lowers to a single XLA dot that tiles onto
the systolic array; bias-add and activation fuse into it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from ..losses import get_loss
from .base import BaseLayer, Params, State, register_layer, maybe_dropout


@register_layer
@dataclass
class DenseLayer(BaseLayer):
    """Fully connected: y = act(xW + b). Reference: conf/layers/DenseLayer.java."""

    n_in: int = 0  # inferred from input type when 0
    n_out: int = 0
    has_bias: bool = True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def infer_n_in(self, input_type: InputType) -> int:
        return self.n_in or input_type.flat_size()

    def init_params(self, key: jax.Array, input_type: InputType) -> Params:
        n_in = self.infer_n_in(input_type)
        wkey, _ = jax.random.split(key)
        p = {"W": self._init_weight(wkey, (n_in, self.n_out), n_in, self.n_out)}
        if self.has_bias:
            p["b"] = self._init_bias((self.n_out,))
        return p

    def pre_output(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        z = x @ params["W"]
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = maybe_dropout(x, self.dropout, train, rng)
        return self._activate(self.pre_output(params, x)), state


@register_layer
@dataclass
class GatedFeedForwardLayer(BaseLayer):
    """A gated feed-forward block over the trailing axis, no bias:
    ``(act(x @ W_gate) * (x @ W_up)) @ W_down`` with ``hidden`` columns in
    between and ``n_out`` (the input's width when 0) out. ``activation``
    is the gate's (``silu``: SwiGLU)."""

    n_out: int = 0
    hidden: int = 0
    activation: str = "silu"
    init_std: float = 0.02
    rescale_layers: int = 0       # > 0: W_down at init_std / sqrt(it)

    PARAM_ROLES = {"W_gate": "ffn_up", "W_up": "ffn_up", "W_down": "ffn_down"}

    @property
    def is_recurrent(self) -> bool:
        return False  # shape-agnostic over leading dims

    def get_output_type(self, input_type: InputType) -> InputType:
        n = self.n_out or input_type.size
        if input_type.kind == "rnn":
            return InputType.recurrent(n, input_type.timesteps)
        return InputType.feed_forward(n)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        if not self.hidden:
            raise ValueError("GatedFeedForwardLayer needs its hidden width")
        dt = jnp.result_type(float)
        kg, ku, kd = jax.random.split(key, 3)
        down = self.init_std / (self.rescale_layers or 1) ** 0.5
        return {
            "W_gate": self.init_std * jax.random.normal(
                kg, (n_in, self.hidden), dt),
            "W_up": self.init_std * jax.random.normal(
                ku, (n_in, self.hidden), dt),
            "W_down": down * jax.random.normal(
                kd, (self.hidden, self.n_out or n_in), dt),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = maybe_dropout(x, self.dropout, train, rng)
        hid = self._activate(x @ params["W_gate"]) * (x @ params["W_up"])
        return hid @ params["W_down"], state


@register_layer
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head. Reference: conf/layers/OutputLayer.java.

    The training loss is computed from the *pre-activation* output so fused
    softmax-xent / sigmoid-xent paths stay numerically stable (losses.py).
    """

    loss: str = "mcxent"

    # parallel.roles: logits gather back whole (row-parallel W, replicated
    # bias) so the loss softmax runs without cross-device reduces.
    PARAM_ROLES = {"W": "ffn_down", "b": "ffn_down"}

    @property
    def is_output_layer(self) -> bool:
        return True

    def compute_loss(
        self,
        params: Params,
        x: jnp.ndarray,
        labels: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        x = maybe_dropout(x, self.dropout, train, rng)
        preout = self.pre_output(params, x)
        return get_loss(self.loss)(labels, preout, self.activation, mask)


@register_layer
@dataclass
class LossLayer(BaseLayer):
    """Loss head without params (reference: conf/layers/LossLayer.java)."""

    loss: str = "mcxent"

    @property
    def has_params(self) -> bool:
        return False

    @property
    def is_output_layer(self) -> bool:
        return True

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return self._activate(x), state

    def compute_loss(self, params, x, labels, mask=None, *, train=False, rng=None):
        return get_loss(self.loss)(labels, x, self.activation, mask)


@register_layer
@dataclass
class ActivationLayer(BaseLayer):
    """Pure activation (reference: conf/layers/ActivationLayer.java)."""

    @property
    def has_params(self) -> bool:
        return False

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return self._activate(x), state


@register_layer
@dataclass
class DropoutLayer(BaseLayer):
    """Standalone dropout (reference: conf/layers/DropoutLayer.java)."""

    @property
    def has_params(self) -> bool:
        return False

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        return maybe_dropout(x, self.dropout, train, rng), state


@register_layer
@dataclass
class EmbeddingLayer(BaseLayer):
    """Index -> row lookup (reference: nn/layers/feedforward/embedding/EmbeddingLayer.java).

    Input: int indices [batch] or [batch, 1]; output [batch, n_out]. On TPU the
    lookup is a one-hot matmul for small vocabularies (MXU-friendly) and a
    gather for large ones; XLA picks the lowering from ``jnp.take``.
    """

    n_in: int = 0  # vocab size
    n_out: int = 0
    has_bias: bool = True

    # parallel.roles: the table replicates over tp (vocab rows over fsdp
    # when divisible) — lookups never pay a per-token gather.
    PARAM_ROLES = {"W": "embedding"}

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type) -> Params:
        n_in = self.n_in or input_type.flat_size()
        wkey, _ = jax.random.split(key)
        p = {"W": self._init_weight(wkey, (n_in, self.n_out), n_in, self.n_out)}
        if self.has_bias:
            p["b"] = self._init_bias((self.n_out,))
        return p

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        z = jnp.take(params["W"], idx, axis=0)
        if self.has_bias:
            z = z + params["b"]
        # dropout on the looked-up rows (indices can't be dropped meaningfully)
        z = maybe_dropout(z, self.dropout, train, rng)
        return self._activate(z), state
