"""Kimi-Linear style hybrids as ComputationGraph configs: pre-norm residual
blocks whose mixer is Kimi Delta Attention (``K``: a gated delta rule with a
decay a channel, ``nn/layers/linear_attention.py``) in most layers and latent
attention without positions (``A``: MLA, no query rank, no rotary embedding)
in the others, each followed by a gated feed-forward (``D``, dense, in the
leading layers; ``E``, sigmoid-routed gated experts with a shared expert,
after them), between a token embedding and an untied softmax head.

Every sublayer is ``x = x + F(rmsnorm(x))``, built from the vertex set a user
has (``RMSNormLayer`` -> the sublayer's layer -> ``ElementWiseVertex`` add, as
``models/nemotron_h.py`` builds its blocks). No position enters anywhere: the
order of the tokens is carried by the delta rule's recurrence and its short
convolutions. A layer is two sublayers, mixer then feed-forward. Vertices are
named ``b<i><kind>_norm`` / ``_mixer`` / ``_add`` with ``i`` counting
sublayers from 0 and ``kind`` one of ``K``, ``A``, ``D``, ``E``: a device
trace's scopes tell them apart by that letter. The builder takes the layers'
mixers, the sizes and this chip's share (experts, vocabulary rows) as
arguments:

    kimi_linear_conf("KKKAK", n_dense=1, experts_held=(0, 8),
                     vocab_size=20480)
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.conf.computation_graph import ComputationGraphConfiguration
from ..nn.conf.inputs import InputType
from ..nn.graph.vertices import ElementWiseVertex
from ..nn.layers.attention import LatentAttentionLayer
from ..nn.layers.dense import GatedFeedForwardLayer
from ..nn.layers.linear_attention import KimiDeltaAttentionLayer
from ..nn.layers.moe import DroplessExpertsLayer
from ..nn.layers.recurrent import RnnEmbeddingLayer, RnnOutputLayer
from ..nn.layers.state_space import RMSNormLayer
from ..nn.updaters import UpdaterConfig

MIXERS = ("K", "A")


def sublayer_kinds(mixers: str, n_dense: int) -> str:
    """One letter a sublayer, in order: a layer's mixer, then ``D`` in the
    ``n_dense`` leading layers and ``E`` after them."""
    return "".join(m + ("D" if i < n_dense else "E")
                   for i, m in enumerate(mixers))


def kimi_linear_conf(
    mixers: str = "KKKAK",
    n_dense: int = 1,
    *,
    hidden_size: int = 2304,
    vocab_size: int = 163840,
    seq_len: Optional[int] = None,
    # Kimi Delta Attention
    num_heads: int = 32,
    head_dim: int = 128,
    short_conv_kernel_size: int = 4,
    chunk: int = 64,
    # latent attention
    num_attention_heads: int = 32,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    # feed-forwards
    intermediate_size: int = 9216,
    num_experts: int = 256,
    num_experts_per_token: int = 8,
    moe_intermediate_size: int = 1024,
    num_shared_experts: int = 1,
    routed_scaling_factor: float = 2.446,
    moe_renormalize: bool = True,
    experts_held: Optional[Tuple[int, int]] = None,   # (first, count); None: all
    # the stack
    rms_norm_eps: float = 1e-5,
    init_std: float = 0.02,
    rescale_layers: int = 0,    # the whole model's depth, for the rescaled projections
    dtype: str = "float32",
    remat: bool = False,
    updater: Optional[UpdaterConfig] = None,
    seed: int = 12345,
) -> ComputationGraphConfiguration:
    """The graph of ``mixers`` (one character a layer: ``K`` or ``A``), the
    first ``n_dense`` layers with a dense feed-forward and the others with
    experts. Input: integer token ids ``[B, T]``; labels: the next ids ``[B,
    T]`` (integer labels reach the softmax cross-entropy without a one-hot)."""
    unknown = set(mixers) - set(MIXERS)
    if unknown or not mixers:
        raise ValueError(f"mixers {mixers!r}: a layer's is 'K' or 'A' "
                         f"(unknown: {sorted(unknown)})")
    if not 0 <= n_dense <= len(mixers):
        raise ValueError(f"{n_dense} dense layers of {len(mixers)}")
    e_first, e_count = experts_held or (0, num_experts)
    normal = {"weight_init": "distribution",
              "distribution": {"type": "normal", "std": init_std}}
    b = (
        ComputationGraphConfiguration.builder()
        .add_inputs("ids")
        .set_input_types(InputType.recurrent(1, seq_len))
        .seed(seed)
        .dtype(dtype)
        .remat(remat)
        .updater(updater or UpdaterConfig(updater="adam", learning_rate=1e-4))
    )
    b.add_layer("embed", RnnEmbeddingLayer(n_in=vocab_size, n_out=hidden_size,
                                           **normal), "ids")
    t = "embed"
    for i, kind in enumerate(sublayer_kinds(mixers, n_dense)):
        if kind == "K":
            layer = KimiDeltaAttentionLayer(
                n_out=hidden_size, n_heads=num_heads, head_dim=head_dim,
                conv_kernel=short_conv_kernel_size, gate_rank=head_dim,
                chunk=chunk, eps=rms_norm_eps, init_std=init_std,
                rescale_layers=rescale_layers)
        elif kind == "A":
            layer = LatentAttentionLayer(
                n_out=hidden_size, n_heads=num_attention_heads, q_rank=None,
                kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
                rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
                eps=rms_norm_eps, rotary=False, causal=True,
                init_std=init_std, rescale_layers=rescale_layers)
        elif kind == "D":
            layer = GatedFeedForwardLayer(
                n_out=hidden_size, hidden=intermediate_size,
                activation="silu", init_std=init_std,
                rescale_layers=rescale_layers)
        else:
            layer = DroplessExpertsLayer(
                n_out=hidden_size, n_experts=num_experts,
                top_k=num_experts_per_token, hidden=moe_intermediate_size,
                shared_hidden=num_shared_experts * moe_intermediate_size,
                experts_held_first=e_first, experts_held_count=e_count,
                routed_scaling=routed_scaling_factor,
                norm_topk_prob=moe_renormalize, expert_activation="silu",
                gated=True, init_std=init_std, rescale_layers=rescale_layers)
        name = f"b{i}{kind}"
        b.add_layer(f"{name}_norm", RMSNormLayer(eps=rms_norm_eps), t)
        b.add_layer(f"{name}_mixer", layer, f"{name}_norm")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"),
                     f"{name}_mixer", t)
        t = f"{name}_add"
    b.add_layer("norm_f", RMSNormLayer(eps=rms_norm_eps), t)
    b.add_layer("head", RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                       loss="mcxent", has_bias=False,
                                       **normal), "norm_f")
    b.set_outputs("head")
    return b.build()
