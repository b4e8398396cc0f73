"""Nemotron-H style hybrids as ComputationGraph configs: a stack of pre-norm
residual blocks whose mixer is a Mamba-2 layer (``M``), grouped-query
attention (``*``) or sigmoid-routed experts with a shared expert (``E``),
between a token embedding and an untied softmax head.

Every block is ``x = x + mixer(rmsnorm(x))``, built from the vertex set a
user has (``RMSNormLayer`` -> the mixer's layer -> ``ElementWiseVertex`` add,
as ``models/resnet.py`` builds its residuals). No linear layer has a bias;
the attention layers carry no positional embedding (position comes from the
Mamba blocks, as in the ``nemotron_h`` model code). The builder takes the
pattern string, the sizes, the experts held here and the vocabulary slice as
arguments, so one chip's share of a wider deployment is a call away:

    nemotron_h_conf("MEMEMEM*E", experts_held=(0, 8), vocab_size=16384)

Vertices are named ``b<i><kind>_norm`` / ``_mixer`` / ``_add`` with ``kind``
one of ``M``, ``A`` (attention), ``E``: the device trace's scopes tell the
blocks apart by that letter.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.conf.computation_graph import ComputationGraphConfiguration
from ..nn.conf.inputs import InputType
from ..nn.graph.vertices import ElementWiseVertex
from ..nn.layers.attention import SelfAttentionLayer
from ..nn.layers.moe import DroplessExpertsLayer
from ..nn.layers.recurrent import RnnEmbeddingLayer, RnnOutputLayer
from ..nn.layers.state_space import Mamba2Layer, RMSNormLayer
from ..nn.updaters import UpdaterConfig

BLOCK_KINDS = {"M": "M", "*": "A", "E": "E"}


def nemotron_h_conf(
    pattern: str = "MEMEMEM*E",
    *,
    hidden_size: int = 2688,
    vocab_size: int = 131072,
    seq_len: Optional[int] = None,
    # Mamba-2
    mamba_num_heads: int = 64,
    mamba_head_dim: int = 64,
    n_groups: int = 8,
    ssm_state_size: int = 128,
    conv_kernel: int = 4,
    chunk_size: int = 128,
    time_step_min: float = 0.001,
    time_step_max: float = 0.1,
    time_step_floor: float = 1e-4,
    # attention
    num_attention_heads: int = 32,
    num_key_value_heads: int = 2,
    head_dim: int = 128,
    # experts
    n_routed_experts: int = 128,
    num_experts_per_tok: int = 6,
    moe_intermediate_size: int = 1856,
    moe_shared_expert_intermediate_size: int = 3712,
    routed_scaling_factor: float = 2.5,
    norm_topk_prob: bool = True,
    experts_held: Optional[Tuple[int, int]] = None,   # (first, count); None: all
    # the stack
    norm_eps: float = 1e-5,
    init_std: float = 0.02,
    rescale_layers: int = 0,    # the whole model's depth, for the rescaled projections
    dtype: str = "float32",
    remat: bool = False,
    updater: Optional[UpdaterConfig] = None,
    seed: int = 12345,
) -> ComputationGraphConfiguration:
    """The graph of ``pattern`` (one character a block: ``M``, ``E``, ``*``).
    Input: integer token ids ``[B, T]``; labels: the next ids ``[B, T]``
    (integer labels reach the softmax cross-entropy without a one-hot)."""
    unknown = set(pattern) - set(BLOCK_KINDS)
    if unknown or not pattern:
        raise ValueError(f"pattern {pattern!r}: blocks are 'M', 'E' and '*'"
                         f" (unknown: {sorted(unknown)})")
    first, count = experts_held or (0, n_routed_experts)
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
    for i, ch in enumerate(pattern):
        name = f"b{i}{BLOCK_KINDS[ch]}"
        b.add_layer(f"{name}_norm", RMSNormLayer(eps=norm_eps), t)
        if ch == "M":
            mixer = Mamba2Layer(
                n_out=hidden_size, n_heads=mamba_num_heads,
                head_dim=mamba_head_dim, n_groups=n_groups,
                state_size=ssm_state_size, conv_kernel=conv_kernel,
                chunk_size=chunk_size, eps=norm_eps,
                time_step_min=time_step_min, time_step_max=time_step_max,
                time_step_floor=time_step_floor, init_std=init_std,
                rescale_layers=rescale_layers)
        elif ch == "*":
            mixer = SelfAttentionLayer(
                n_out=hidden_size, n_heads=num_attention_heads,
                n_kv_heads=num_key_value_heads, head_dim=head_dim,
                causal=True, has_bias=False, **normal)
        else:
            mixer = DroplessExpertsLayer(
                n_out=hidden_size, n_experts=n_routed_experts,
                top_k=num_experts_per_tok, hidden=moe_intermediate_size,
                shared_hidden=moe_shared_expert_intermediate_size,
                experts_held_first=first, experts_held_count=count,
                routed_scaling=routed_scaling_factor,
                norm_topk_prob=norm_topk_prob, expert_activation="relu2",
                init_std=init_std, rescale_layers=rescale_layers)
        b.add_layer(f"{name}_mixer", mixer, f"{name}_norm")
        b.add_vertex(f"{name}_add", ElementWiseVertex(op="add"),
                     f"{name}_mixer", t)
        t = f"{name}_add"
    b.add_layer("norm_f", RMSNormLayer(eps=norm_eps), t)
    b.add_layer("head", RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                       loss="mcxent", has_bias=False,
                                       **normal), "norm_f")
    b.set_outputs("head")
    return b.build()
