"""Recurrent layers: GravesLSTM (+peepholes), bidirectional, RNN output head.

TPU-native reimagining of the reference's recurrent tier
(nn/layers/recurrent/LSTMHelpers.java — fwd time-loop :159-179, gate layout
:62-64; GravesLSTM.java; GravesBidirectionalLSTM.java sum-combine :224-228;
RnnOutputLayer.java). The reference runs a hand-written per-timestep gemm loop
with hand-derived backprop (LSTMHelpers.backpropGradientHelper:260). Here:

- The input projection ``x @ W`` for ALL timesteps is ONE big [B*T, 4H] matmul
  (MXU-friendly), hoisted out of the recurrence.
- The recurrence itself is ``lax.scan`` over time — XLA compiles it to a single
  fused while-loop on device; ``jax.grad`` differentiates through it, so the
  500-line hand-written LSTM backprop does not exist.
- Data layout is [batch, time, features] (the reference is [batch, features,
  time]); scan runs time-major internally via a transpose XLA folds away.

Reference gate semantics preserved exactly (LSTMHelpers.activateHelper):
order [a (block input, layer activation), f (forget), o (output), i (input-mod
gate)]; peepholes: f and i see ``c_{t-1}`` (wFF, wGG), o sees ``c_t`` (wOO);
``c_t = f*c_{t-1} + i*a``; ``h_t = o * act(c_t)``; gates use ``gate_activation``
(sigmoid / hardsigmoid).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..conf.inputs import InputType
from ..activations import get_activation
from ..losses import get_loss
from .base import BaseLayer, Params, State, register_layer, maybe_dropout
from .dense import DenseLayer

RecurrentState = Dict[str, jnp.ndarray]


def _lstm_scan(
    params_prefix: str,
    params: Params,
    x: jnp.ndarray,  # [B, T, n_in]
    h0: jnp.ndarray,  # [B, H]
    c0: jnp.ndarray,  # [B, H]
    act,
    gate,
    mask: Optional[jnp.ndarray],  # [B, T] or None
    reverse: bool = False,
    act_name: Optional[str] = None,
    gate_name: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run one LSTM direction. Returns (y [B,T,H], h_T, c_T).

    ``params_prefix`` selects the direction's weights ("" or "bwd_").
    Masked steps (mask==0) carry h/c through unchanged — the streaming-state
    equivalent of the reference's maskArray muliColumnVector handling.
    """
    p = params_prefix
    W, RW, b = params[p + "W"], params[p + "RW"], params[p + "b"]
    pF, pI, pO = params[p + "pF"], params[p + "pI"], params[p + "pO"]
    H = RW.shape[0]

    # One big MXU matmul for every timestep's input projection, computed
    # DIRECTLY time-major: transposing x first moves [T,B,n_in] bytes where
    # transposing the projection would move [T,B,4H] — on the round-5
    # char-RNN trace the two materialized [256,64,2048] projection
    # transposes (fwd + VJP) were ~48% of the step's synchronous device
    # windows, dwarfing the recurrent kernel itself.
    x_t = jnp.swapaxes(x, 0, 1)  # [T, B, n_in]
    xw_t = x_t @ W + b  # [T, B, 4H] time-major for scan/kernel
    from ... import ops as _ops0  # noqa: PLC0415
    from ...nn.activations import is_builtin as _is_builtin  # noqa: PLC0415

    # Variant routing is cost-model-guided (ops.kernel_select site
    # "lstm_seq"): the PR 5 roofline scores seqfused / fusedcell / the lax
    # scan for these concrete shapes at trace time; DL4J_TPU_PALLAS and
    # set_helpers_enabled keep their exact legacy forcing semantics.
    acts_ok = (
        act_name is not None and gate_name is not None
        and _ops0.supported_lstm_activations(act_name.lower(), gate_name.lower())
        and _is_builtin(act_name) and _is_builtin(gate_name)
    )
    variant = _ops0.select_lstm_variant(
        xw_t.shape[0], x.shape[0], H, xw_t.dtype.itemsize, acts_ok,
        masked=mask is not None)
    if variant == "seqfused":
        # whole-loop fusion: h/c carries live in VMEM across the time grid
        # (see ops/pallas_kernels.fused_lstm_sequence).
        # A reverse scan is the forward kernel on time-flipped input; padded
        # batches go through the masked variant (held h/c, scan semantics).
        from ...ops.pallas_kernels import (  # noqa: PLC0415
            fused_lstm_sequence,
            fused_lstm_sequence_masked,
        )

        zx_seq = jnp.flip(xw_t, 0) if reverse else xw_t
        if mask is None:
            ys, h_f, c_f = fused_lstm_sequence(
                zx_seq, h0, c0, RW, pF, pI, pO,
                act_name.lower(), gate_name.lower()
            )
        else:
            m_seq = jnp.swapaxes(mask.astype(xw_t.dtype), 0, 1)[..., None]
            if reverse:
                m_seq = jnp.flip(m_seq, 0)
            ys, h_f, c_f = fused_lstm_sequence_masked(
                zx_seq, m_seq, h0, c0, RW, pF, pI, pO,
                act_name.lower(), gate_name.lower()
            )
        if reverse:
            ys = jnp.flip(ys, 0)
        return jnp.swapaxes(ys, 0, 1), h_f, c_f
    if mask is not None:
        mask_t = jnp.swapaxes(mask.astype(xw_t.dtype), 0, 1)[..., None]  # [T, B, 1]
    else:
        mask_t = jnp.ones((xw_t.shape[0], 1, 1), xw_t.dtype)

    # Scan path. "fusedcell" routes each step through the per-step Pallas
    # kernel (the cuDNN-helper slot, SURVEY.md §2.3); "reference" runs the
    # same math inline via the layer's activation callables and lets XLA
    # fuse the scan body.
    from ...ops.pallas_kernels import _cell_math, fused_lstm_cell  # noqa: PLC0415

    act_key = (act_name or "").lower()
    gate_key = (gate_name or "").lower()
    use_helper = variant == "fusedcell"

    def step(carry, inp):
        h_prev, c_prev = carry
        zx, m = inp
        if use_helper:
            h, c = fused_lstm_cell(zx, h_prev, c_prev, RW, pF, pI, pO,
                                   act_key, gate_key)
        else:
            h, c, *_ = _cell_math(zx, h_prev, c_prev, RW, pF, pI, pO, act, gate)
        h = m * h + (1.0 - m) * h_prev
        c = m * c + (1.0 - m) * c_prev
        return (h, c), h

    (h_f, c_f), ys = lax.scan(step, (h0, c0), (xw_t, mask_t), reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), h_f, c_f  # back to [B, T, H]


@register_layer
@dataclass
class GravesLSTM(BaseLayer):
    """LSTM with peephole connections (reference: nn/conf/layers/GravesLSTM.java,
    nn/layers/recurrent/GravesLSTM.java + LSTMHelpers.java).

    Param pytree (replaces the reference's packed [H, 4H+3] recurrent matrix,
    LSTMHelpers.java:62-64): "W" [n_in,4H], "RW" [n_out,4H], "b" [4H],
    peepholes "pF"/"pI"/"pO" each [H]. Gate column order [a, f, o, i] matches
    the reference's [wi(block), wf, wo, wg(input-mod)].
    """

    n_in: int = 0
    n_out: int = 0
    forget_gate_bias_init: float = 1.0  # reference: GravesLSTM.Builder.forgetGateBiasInit
    gate_activation: str = "sigmoid"
    activation: str = "tanh"

    # parallel.roles registry (MeshLayout(roles=True)): the i/f/g/o gate
    # blocks stay device-local — W goes row-parallel (tp shards the hoisted
    # x@W rows, ONE all-reduce outside the scan), RW/b/peepholes replicate
    # over tp, so the scan body pays zero per-step collectives. Bidirectional
    # bwd_* params follow these via the roles.role_of prefix rule.
    PARAM_ROLES = {"W": "lstm_gates", "RW": "lstm_gates", "b": "lstm_gates",
                   "pF": "lstm_gates", "pI": "lstm_gates", "pO": "lstm_gates"}

    @property
    def is_recurrent(self) -> bool:
        return True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def infer_n_in(self, input_type: InputType) -> int:
        return self.n_in or input_type.size

    def _direction_params(self, key, n_in: int, dtype, prefix: str = "") -> Params:
        H = self.n_out
        kw, kr = jax.random.split(key)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate slice of the bias (columns [H, 2H)) starts at forget_gate_bias_init
        b = b.at[H : 2 * H].set(self.forget_gate_bias_init)
        return {
            prefix + "W": self._init_weight(kw, (n_in, 4 * H), n_in, H, dtype=dtype),
            prefix + "RW": self._init_weight(kr, (H, 4 * H), H, H, dtype=dtype),
            prefix + "b": b,
            prefix + "pF": jnp.zeros((H,), dtype),
            prefix + "pI": jnp.zeros((H,), dtype),
            prefix + "pO": jnp.zeros((H,), dtype),
        }

    def init_params(self, key: jax.Array, input_type: InputType) -> Params:
        dtype = jnp.result_type(float)
        return self._direction_params(key, self.infer_n_in(input_type), dtype)

    # ---- recurrent-state API (streaming rnnTimeStep + TBPTT) ----
    def init_recurrent_state(self, batch: int, dtype=None) -> RecurrentState:
        dtype = dtype or jnp.result_type(float)
        H = self.n_out
        return {"h": jnp.zeros((batch, H), dtype), "c": jnp.zeros((batch, H), dtype)}

    def apply_seq(
        self,
        params: Params,
        x: jnp.ndarray,
        rstate: RecurrentState,
        *,
        mask: Optional[jnp.ndarray] = None,
        train: bool = False,
        rng: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, RecurrentState]:
        x = maybe_dropout(x, self.dropout, train, rng)
        act = get_activation(self.activation)
        gate = get_activation(self.gate_activation)
        h0 = rstate["h"].astype(x.dtype)
        c0 = rstate["c"].astype(x.dtype)
        y, h, c = _lstm_scan("", params, x, h0, c0, act, gate, mask,
                             act_name=self.activation, gate_name=self.gate_activation)
        return y, {"h": h, "c": c}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        rstate = self.init_recurrent_state(x.shape[0], x.dtype)
        y, _ = self.apply_seq(params, x, rstate, mask=mask, train=train, rng=rng)
        return y, state


@register_layer
@dataclass
class GravesBidirectionalLSTM(GravesLSTM):
    """Bidirectional peephole LSTM; directions are SUMMED (reference:
    GravesBidirectionalLSTM.java:224-228 "sum outputs" — output size stays
    n_out). Like the reference, TBPTT/streaming state is unsupported
    (LSTMHelpers.java:41-43 note)."""

    def init_params(self, key: jax.Array, input_type: InputType) -> Params:
        dtype = jnp.result_type(float)
        kf, kb = jax.random.split(key)
        n_in = self.infer_n_in(input_type)
        p = self._direction_params(kf, n_in, dtype)
        p.update(self._direction_params(kb, n_in, dtype, prefix="bwd_"))
        return p

    def apply_seq(self, params, x, rstate, *, mask=None, train=False, rng=None):
        raise NotImplementedError(
            "Bidirectional LSTM has no streaming/TBPTT state (reference parity: "
            "LSTMHelpers.java:41-43)"
        )

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = maybe_dropout(x, self.dropout, train, rng)
        act = get_activation(self.activation)
        gate = get_activation(self.gate_activation)
        B, H = x.shape[0], self.n_out
        zeros = jnp.zeros((B, H), x.dtype)
        y_f, _, _ = _lstm_scan("", params, x, zeros, zeros, act, gate, mask,
                               act_name=self.activation, gate_name=self.gate_activation)
        y_b, _, _ = _lstm_scan("bwd_", params, x, zeros, zeros, act, gate, mask, reverse=True,
                               act_name=self.activation, gate_name=self.gate_activation)
        return y_f + y_b, state


@register_layer
@dataclass
class RnnOutputLayer(DenseLayer):
    """Per-timestep dense + loss head (reference: nn/conf/layers/RnnOutputLayer.java,
    nn/layers/recurrent/RnnOutputLayer.java). 3D [B,T,C] activations; the loss
    flattens time into batch exactly as the reference reshapes to 2d, with the
    [B,T] label mask flattened alongside."""

    loss: str = "mcxent"

    # parallel.roles: logits gather back whole (row-parallel W, replicated
    # bias) so the softmax-xent loss runs without cross-device reduces.
    PARAM_ROLES = {"W": "ffn_down", "b": "ffn_down"}

    @property
    def is_output_layer(self) -> bool:
        return True

    @property
    def is_recurrent(self) -> bool:
        return True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def infer_n_in(self, input_type: InputType) -> int:
        return self.n_in or input_type.size

    def pre_output(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        z = x @ params["W"]  # [B, T, C] — keep time, unlike DenseLayer's flatten
        if self.has_bias:
            z = z + params["b"]
        return z

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        x = maybe_dropout(x, self.dropout, train, rng)
        return self._activate(self.pre_output(params, x)), state

    def compute_loss(self, params, x, labels, mask=None, *, train=False, rng=None):
        x = maybe_dropout(x, self.dropout, train, rng)
        preout = self.pre_output(params, x)  # [B, T, C]
        C = preout.shape[-1]
        preout2d = preout.reshape(-1, C)
        labels2d = jnp.asarray(labels)
        # integer class ids [B, T] flatten to [B*T]; one-hot rows to [B*T, C]
        ids = (jnp.issubdtype(labels2d.dtype, jnp.integer)
               and labels2d.ndim == preout.ndim - 1)
        labels2d = labels2d.reshape(-1) if ids else labels2d.reshape(-1, C)
        mask1d = None if mask is None else jnp.asarray(mask).reshape(-1)
        return get_loss(self.loss)(labels2d, preout2d, self.activation, mask1d)


@register_layer
@dataclass
class RnnEmbeddingLayer(BaseLayer):
    """Sequence token embedding: int [B,T] -> [B,T,n_out]. The reference routes
    sequence embeddings through EmbeddingLayer + preprocessors; a dedicated
    sequence variant is the TPU-idiomatic shape (gather lowered by XLA)."""

    n_in: int = 0  # vocab
    n_out: int = 0

    # parallel.roles: the table replicates over tp (vocab rows over fsdp
    # when divisible) — token lookups never pay a per-token gather.
    PARAM_ROLES = {"W": "embedding"}

    @property
    def is_recurrent(self) -> bool:
        return True

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type) -> Params:
        n_in = self.n_in or input_type.size
        return {"W": self._init_weight(key, (n_in, self.n_out), n_in, self.n_out)}

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        z = jnp.take(params["W"], idx, axis=0)
        z = maybe_dropout(z, self.dropout, train, rng)
        return self._activate(z), state


@register_layer
@dataclass
class LastTimeStepLayer(BaseLayer):
    """[B,T,F] -> [B,F] at the last *unmasked* step (reference: graph vertex
    LastTimeStepVertex — provided as a layer too for sequential nets)."""

    @property
    def has_params(self) -> bool:
        return False

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(input_type.size)

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        if mask is None:
            return x[:, -1, :], state
        # last *nonzero* index per row (handles non-contiguous masks, matching
        # the reference's LastTimeStepVertex scan for the final set step)
        T = x.shape[1]
        idx = jnp.arange(T)
        last = jnp.max(jnp.where(mask > 0, idx, -1), axis=1)  # [B]
        last = jnp.maximum(last, 0).astype(jnp.int32)
        return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0, :], state
