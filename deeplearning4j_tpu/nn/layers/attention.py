"""Attention layers — the long-context tier's nn surface.

No counterpart exists in the reference (2016: SURVEY.md §5.7 — sequence
handling is TBPTT + masking only); these layers extend the framework beyond
parity per the long-context-first design requirement. The math lives in
:mod:`deeplearning4j_tpu.parallel.ring_attention`; a layer switches between
the local kernel and ring/all-to-all sequence parallelism purely by the mesh
context the trainer establishes.
"""

from __future__ import annotations

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
