"""Xing4.0-style stacks as ComputationGraph configs: latent attention (MLA,
YaRN rotary embeddings) and gated feed-forwards (dense in the leading
layers, sigmoid-routed gated experts with a shared expert after them) on a
residual that is ``hc_mult`` streams wide, every sublayer read and written
through manifold-constrained hyper-connections, between a token embedding
and an untied softmax head.

No sublayer is ``x + F(norm(x))``: with ``X`` the streams of a token,

    maps = H_maps(X);  X' = H_res X + H_post^T F(norm(H_pre X))

(``nn/layers/hyper_connections.py``). A layer is two sublayers, attention
then feed-forward. Vertices are named ``b<i><kind>_*`` with ``i`` counting
sublayers from 0 and ``kind`` the sublayer's: ``A`` (attention), ``D`` (dense
feed-forward), ``E`` (experts) for ``_mixer``, and ``H`` for the
hyper-connection pieces around it (``b<i>H_maps``; ``b<i>H_pre``, which
reads the streams and takes the sublayer's pre-norm, ``gamma`` its
parameter; ``b<i>H_post``): a device trace's scopes tell them apart by that
letter. Four vertices a sublayer: under ``remat`` each keeps its inputs, so
a sublayer keeps the streams, the maps, the normed input and the mixer's
output, and nothing else.
The builder takes the depth, the sizes and this chip's share (heads,
experts, vocabulary rows) as arguments:

    xing4_conf(n_dense=1, n_expert=4, heads_held=(0, 4),
               experts_held=(0, 8), vocab_size=16384)
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..nn.conf.computation_graph import ComputationGraphConfiguration
from ..nn.conf.inputs import InputType
from ..nn.layers.attention import LatentAttentionLayer
from ..nn.layers.dense import GatedFeedForwardLayer
from ..nn.layers.hyper_connections import (HyperConnectionMapsLayer,
                                           HyperConnectionVertex)
from ..nn.layers.moe import DroplessExpertsLayer
from ..nn.layers.recurrent import RnnEmbeddingLayer, RnnOutputLayer
from ..nn.layers.state_space import RMSNormLayer
from ..nn.updaters import UpdaterConfig


def sublayer_kinds(n_dense: int, n_expert: int) -> str:
    """One letter a sublayer, in order: ``AD`` a dense layer, ``AE`` an
    expert layer."""
    return "AD" * n_dense + "AE" * n_expert


def xing4_conf(
    n_dense: int = 1,
    n_expert: int = 4,
    *,
    hidden_size: int = 3584,
    vocab_size: int = 131072,
    seq_len: Optional[int] = None,
    # latent attention
    num_attention_heads: int = 32,
    heads_held: Optional[Tuple[int, int]] = None,     # (first, count); None: all
    q_lora_rank: int = 768,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    rope_theta: float = 10000.0,
    rope_scaling: Optional[dict] = None,    # the published group; None: plain
    # feed-forwards
    intermediate_size: int = 9216,
    n_routed_experts: int = 64,
    num_experts_per_tok: int = 4,
    moe_intermediate_size: int = 1024,
    n_shared_experts: int = 1,
    routed_scaling_factor: float = 2.0,
    norm_topk_prob: bool = True,
    experts_held: Optional[Tuple[int, int]] = None,   # (first, count); None: all
    # hyper-connections
    hc_mult: int = 4,
    hc_sinkhorn_iters: int = 20,
    hc_eps: float = 1e-6,
    mhc_h_res_clamp_min: float = -30.0,
    mhc_h_res_clamp_max: float = 30.0,
    # the stack
    rms_norm_eps: float = 1e-6,
    init_std: float = 0.02,
    rescale_layers: int = 0,    # the whole model's depth, for the rescaled projections
    dtype: str = "float32",
    remat: bool = False,
    updater: Optional[UpdaterConfig] = None,
    seed: int = 12345,
) -> ComputationGraphConfiguration:
    """The graph of ``n_dense`` dense layers then ``n_expert`` expert
    layers. Input: integer token ids ``[B, T]``; labels: the next ids ``[B,
    T]`` (integer labels reach the softmax cross-entropy without a one-hot)."""
    if n_dense < 0 or n_expert < 0 or n_dense + n_expert == 0:
        raise ValueError(f"{n_dense} dense and {n_expert} expert layers: "
                         "a stack has at least one layer")
    h_first, h_count = heads_held or (0, num_attention_heads)
    e_first, e_count = experts_held or (0, n_routed_experts)
    yarn = dict(rope_scaling or {})
    if yarn and yarn.get("type", "yarn") != "yarn":
        raise ValueError(f"rope_scaling type {yarn['type']!r}: only 'yarn'")
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
    b.add_vertex("streams", HyperConnectionVertex(op="expand",
                                                  n_streams=hc_mult), "embed")
    t = "streams"
    for i, kind in enumerate(sublayer_kinds(n_dense, n_expert)):
        if kind == "A":
            mixer = LatentAttentionLayer(
                n_out=hidden_size, n_heads=num_attention_heads,
                heads_held_first=h_first, heads_held_count=h_count,
                q_rank=q_lora_rank, kv_rank=kv_lora_rank,
                nope_dim=qk_nope_head_dim, rope_dim=qk_rope_head_dim,
                v_dim=v_head_dim, eps=rms_norm_eps, rope_theta=rope_theta,
                rope_factor=yarn.get("factor", 1.0),
                rope_original_positions=yarn.get(
                    "original_max_position_embeddings", 4096),
                rope_beta_fast=yarn.get("beta_fast", 32.0),
                rope_beta_slow=yarn.get("beta_slow", 1.0),
                rope_mscale=yarn.get("mscale", 1.0),
                rope_mscale_all_dim=yarn.get("mscale_all_dim", 0.0),
                causal=True, init_std=init_std, rescale_layers=rescale_layers)
        elif kind == "D":
            mixer = GatedFeedForwardLayer(
                n_out=hidden_size, hidden=intermediate_size,
                activation="silu", init_std=init_std,
                rescale_layers=rescale_layers)
        else:
            mixer = DroplessExpertsLayer(
                n_out=hidden_size, n_experts=n_routed_experts,
                top_k=num_experts_per_tok, hidden=moe_intermediate_size,
                shared_hidden=n_shared_experts * moe_intermediate_size,
                experts_held_first=e_first, experts_held_count=e_count,
                routed_scaling=routed_scaling_factor,
                norm_topk_prob=norm_topk_prob, expert_activation="silu",
                gated=True, init_std=init_std, rescale_layers=rescale_layers)
        hc, name = f"b{i}H", f"b{i}{kind}"
        b.add_layer(f"{hc}_maps", HyperConnectionMapsLayer(
            n_streams=hc_mult, sinkhorn_iters=hc_sinkhorn_iters, eps=hc_eps,
            clamp_min=mhc_h_res_clamp_min, clamp_max=mhc_h_res_clamp_max,
            norm_eps=rms_norm_eps, read_stream=i, init_std=init_std), t)
        b.add_vertex(f"{hc}_pre", HyperConnectionVertex(
            op="read", n_streams=hc_mult, norm_eps=rms_norm_eps),
            t, f"{hc}_maps")
        b.add_layer(f"{name}_mixer", mixer, f"{hc}_pre")
        b.add_vertex(f"{hc}_post", HyperConnectionVertex(
            op="write", n_streams=hc_mult), t, f"{hc}_maps", f"{name}_mixer")
        t = f"{hc}_post"
    b.add_vertex("streams_sum", HyperConnectionVertex(
        op="collapse", n_streams=hc_mult), t)
    b.add_layer("norm_f", RMSNormLayer(eps=rms_norm_eps), "streams_sum")
    b.add_layer("head", RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                       loss="mcxent", has_bias=False,
                                       **normal), "norm_f")
    b.set_outputs("head")
    return b.build()
