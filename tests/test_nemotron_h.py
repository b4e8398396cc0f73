"""The hybrid state-space / attention / expert model (``models/nemotron_h.py``)
and the layers it is built from, each against the plain reference of
``benchmarks/configs/nemotron3_nano_30b_a3b.py`` (float32, the Mamba
recurrence position by position) at the tiny preset sizes, seeded weights."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.discovery import load_json, load_module  # noqa: E402
from deeplearning4j_tpu import ComputationGraph  # noqa: E402
from deeplearning4j_tpu.nn.conf.computation_graph import \
    ComputationGraphConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer  # noqa: E402
from deeplearning4j_tpu.nn.layers.base import layer_from_dict  # noqa: E402
from deeplearning4j_tpu.nn.layers.moe import (DroplessExpertsLayer,  # noqa: E402
                                              MixtureOfExpertsLayer,
                                              expert_row_counts)
from deeplearning4j_tpu.nn.layers.state_space import (Mamba2Layer,  # noqa: E402
                                                      RMSNormLayer)
from deeplearning4j_tpu.ops import kernel_select as ks  # noqa: E402
from deeplearning4j_tpu.ops import ssd_scan as ssd  # noqa: E402

CONFIG = "nemotron3_nano_30b_a3b"
REF = load_module(os.path.join(REPO, "benchmarks", "configs", CONFIG + ".py"))
PUBLISHED = load_json(os.path.join(REPO, "benchmarks", "configs",
                                   CONFIG + ".json"))
TINY = dict(PUBLISHED, dtype="float32", **load_json(os.path.join(
    REPO, "tests", "benchmark_harness", "presets", "configs",
    CONFIG + ".json"))["sizes"])


@pytest.fixture(autouse=True)
def _fresh_selection():
    ks.reset()
    yield
    ks.reset()


def f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-6)
    assert a.shape == b.shape
    assert float(np.max(np.abs(a - b))) <= tol * scale, \
        (float(np.max(np.abs(a - b))), scale)


def reference(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


# ------------------------------------------------------------------ Mamba-2
def mamba_layer(**kw):
    return Mamba2Layer(
        n_out=TINY["hidden_size"], n_heads=TINY["mamba_num_heads"],
        head_dim=TINY["mamba_head_dim"], n_groups=TINY["n_groups"],
        state_size=TINY["ssm_state_size"], chunk_size=TINY["chunk_size"],
        rescale_layers=52, **kw)


@pytest.mark.parametrize("variant", ["reference", "mosaic_interpret"])
@pytest.mark.parametrize("batch,T", [
    (1, 8),      # one chunk
    (2, 24),     # several chunks, batch > 1
    (2, 21),     # not whole chunks: padded with dt = 0, the padding cut off
])
def test_mamba2_layer_and_all_its_gradients_match_the_plain_reference(
        variant, batch, T, monkeypatch):
    if variant == "mosaic_interpret":   # the kernels, whatever the shapes
        monkeypatch.setattr(ssd, "ssd_scan", ssd.ssd_scan_fused)
    layer = mamba_layer()
    it = InputType.recurrent(TINY["hidden_size"], T)
    params = layer.init_params(jax.random.PRNGKey(3), it)
    # every parameter away from its initial constant, so no gradient is
    # compared at a special point
    keys = jax.random.split(jax.random.PRNGKey(4), len(params))
    params = {k: v + 0.1 * jax.random.normal(kk, v.shape, v.dtype)
              for (k, v), kk in zip(sorted(params.items()), keys)}
    x = jax.random.normal(jax.random.PRNGKey(5), (batch, T, it.size))
    w = jax.random.normal(jax.random.PRNGKey(6), (batch, T, it.size))

    def program(p, x):
        return jnp.sum(layer.apply(p, x, {})[0] * w)

    def plain(p, x):
        return jnp.sum(REF.reference_mamba(p, x, TINY) * w.astype(jnp.float32))

    close(layer.apply(params, x, {})[0],
          reference(REF.reference_mamba, f32(params), f32(x), TINY))
    got = jax.grad(program, argnums=(0, 1))(params, x)
    want = reference(jax.grad(plain, argnums=(0, 1)), f32(params), f32(x))
    for name in params:
        close(got[0][name], want[0][name], 5e-4)
    close(got[1], want[1], 5e-4)


def test_scan_variants_agree_and_the_padding_is_cut_off():
    r = np.random.default_rng(0)
    B, T, H, P, G, N, L = 2, 19, 4, 8, 2, 16, 8
    x = jnp.asarray(r.normal(size=(B, T, H, P)))
    dt = jnp.asarray(np.log1p(np.exp(r.normal(size=(B, T, H)) - 1)))
    A = -jnp.asarray(np.exp(0.5 * r.normal(size=(H,))))
    Bm, Cm = (jnp.asarray(r.normal(size=(B, T, G, N))) for _ in range(2))
    a = ssd.ssd_scan_reference(x, dt, A, Bm, Cm, L)
    b = ssd.ssd_scan_fused(x, dt, A, Bm, Cm, L)
    assert a.shape == b.shape == x.shape
    close(a, b, 1e-9)
    # what comes before a position decides it: a longer sequence agrees on
    # the shorter one's positions
    longer = ssd.ssd_scan_fused(*(jnp.concatenate([v, v], axis=1)
                                  for v in (x, dt)), A,
                                *(jnp.concatenate([v, v], axis=1)
                                  for v in (Bm, Cm)), L)
    close(longer[:, :T], b, 1e-9)


def test_ssd_scan_site_auto_picks_the_kernels_at_the_published_shapes():
    s = PUBLISHED
    shapes = (1, 8192, s["mamba_num_heads"], s["mamba_head_dim"],
              s["n_groups"], s["ssm_state_size"], s["chunk_size"], 2)
    from deeplearning4j_tpu import ops

    assert ops.select_ssd_scan_variant(*shapes) == "reference"  # the CPU
    ks.reset()
    ks.set_force_available(True)
    assert ops.select_ssd_scan_variant(*shapes) == "fused"
    rec = ks.selection_log()[-1]
    assert rec["site"] == "ssd_scan" and rec["reason"] == "auto"
    assert rec["chunk"] == 128 and "infeasible" not in rec
    # a program GSPMD will partition: never quietly
    with ks.partitioned_program():
        assert ops.select_ssd_scan_variant(*shapes) == "reference"
    rec = ks.selection_log()[-1]
    assert rec["reason"] == "fallback" and rec["infeasible"] == ["fused"]
    assert rec["ctx"]["partitioned"] is True and rec["chunk"] == 128
    # shapes Mosaic's tiling does not take (the tiny preset's) give way too
    assert ops.select_ssd_scan_variant(2, 16, 2, 16, 2, 16, 8, 4) \
        == "reference"
    assert ks.selection_log()[-1]["reason"] == "fallback"


def test_ssd_footprint_is_inside_the_seq_kernels_vmem_budget():
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    fp = ssd.ssd_footprint(128, 64, 128, 8, 2)
    assert 1 << 20 < fp < pk._SEQ_VMEM_BUDGET_BYTES
    assert ssd.ssd_fits(128, 64, 128, 8, 2)
    assert not ssd.ssd_fits(2048, 64, 128, 8, 4)   # [L, L] values a head
    assert ssd.ssd_layout_ok(128, 64, 128, 8)
    assert not ssd.ssd_layout_ok(8, 16, 16, 1)


# ---------------------------------------------------------------- attention
@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_grouped_query_attention_matches_the_plain_reference(impl, kv_heads):
    sizes = dict(TINY, num_key_value_heads=kv_heads)
    layer = SelfAttentionLayer(
        n_out=TINY["hidden_size"], n_heads=4, n_kv_heads=kv_heads,
        head_dim=16, causal=True, has_bias=False, attention_impl=impl)
    it = InputType.recurrent(TINY["hidden_size"], 20)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    assert set(params) == {"Wq", "Wk", "Wv", "Wo"}
    assert params["Wk"].shape == (64, kv_heads * 16)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 64))
    w = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 64))
    close(layer.apply(params, x, {})[0],
          reference(REF.reference_attention, f32(params), f32(x), sizes), 1e-4)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x, {})[0] * w),
                   argnums=(0, 1))(params, x)
    want = reference(jax.grad(
        lambda p, x: jnp.sum(REF.reference_attention(p, x, sizes)
                             * w.astype(jnp.float32)), argnums=(0, 1)),
        f32(params), f32(x))
    for name in params:
        close(got[0][name], want[0][name], 5e-4)
    close(got[1], want[1], 5e-4)
    variants = {r["variant"] for r in ks.selection_log()
                if r["site"] == "attention"}
    assert variants == {impl}


def test_attention_defaults_keep_the_multi_head_shapes():
    layer = SelfAttentionLayer(n_out=32, n_heads=4)
    p = layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(32, 5))
    assert {k: v.shape for k, v in p.items()} == {
        "Wq": (32, 32), "Wk": (32, 32), "Wv": (32, 32), "Wo": (32, 32),
        "bo": (32,)}
    d = layer.to_dict()
    assert d["n_kv_heads"] == 0 and d["head_dim"] == 0 and d["has_bias"]
    assert layer_from_dict(json.loads(json.dumps(d))) == layer


# ------------------------------------------------------------------ experts
def experts_layer(first=2, count=2, shared=64, **kw):
    return DroplessExpertsLayer(
        n_out=64, n_experts=8, top_k=2, hidden=32, shared_hidden=shared,
        experts_held_first=first, experts_held_count=count,
        routed_scaling=2.5, rescale_layers=52, **kw)


def experts_sizes(first=2, count=2):
    return dict(TINY, experts_held_first=first, n_routed_experts=count)


def experts_case(case, params, x):
    """Inputs and router weights that force a routing case."""
    if case == "ties":          # equal scores: the lower index wins, in both
        params = dict(params, Wr=jnp.zeros_like(params["Wr"]))
    elif case == "no_rows_for_one":   # expert 3 (held) is nobody's pick
        params = dict(params, e_bias=params["e_bias"].at[3].set(-10.0))
    elif case == "all_on_one":        # expert 2 (held) is everybody's pick
        params = dict(params, e_bias=params["e_bias"].at[2].set(10.0))
    elif case == "none_here":         # every pick lands on another chip
        params = dict(params, e_bias=params["e_bias"].at[jnp.array([2, 3])]
                      .set(-10.0))
    return params, x


def grouped_products(variant):
    """The expert layer's grouped products as ``ragged_dot`` or as the Mosaic
    kernels in interpret mode, whatever the shapes."""
    if variant == "mosaic_interpret":
        ks.set_force_available(True)
        ks.set_site_override("grouped_matmul", "fused")


def grouped_variants():
    return {r["variant"] for r in ks.selection_log()
            if r["site"] == "grouped_matmul"}


@pytest.mark.parametrize("variant", ["reference", "mosaic_interpret"])
@pytest.mark.parametrize("case", ["random", "ties", "no_rows_for_one",
                                  "all_on_one", "none_here"])
def test_dropless_experts_match_the_plain_reference(case, variant):
    grouped_products(variant)
    layer = experts_layer()
    it = InputType.recurrent(64, 12)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))
    params, x = experts_case(case, params, x)
    state = layer.init_state(it)
    out, new_state = layer.apply(params, x, state)
    sizes = experts_sizes()
    close(out, reference(REF.reference_experts, f32(params), f32(x), sizes))
    rows, fullest, tokens, dropped = (int(v) for v in new_state["counters"])
    assert tokens == 24 and dropped == 0
    chosen, _ = layer.route(params, x.reshape(-1, 64))
    counts = np.asarray(expert_row_counts(chosen, 8))
    assert rows == counts[2:4].sum() and fullest == counts[2:4].max()
    assert counts.sum() == 24 * 2
    if case == "ties":
        assert counts.tolist() == [24, 24, 0, 0, 0, 0, 0, 0] and rows == 0
    if case == "no_rows_for_one":
        assert counts[3] == 0
    if case == "all_on_one":
        assert counts[2] == 24 and fullest == 24
    if case == "none_here":
        assert rows == 0
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x, state)[0] * w),
                   argnums=(0, 1))(params, x)
    want = reference(jax.grad(
        lambda p, x: jnp.sum(REF.reference_experts(p, x, sizes)
                             * w.astype(jnp.float32)), argnums=(0, 1)),
        f32(params), f32(x))
    for name in params:
        close(got[0][name], want[0][name], 5e-4)
    close(got[1], want[1], 5e-4)
    assert grouped_variants() == {
        "fused" if variant == "mosaic_interpret" else "reference"}


@pytest.mark.parametrize("variant", ["reference", "mosaic_interpret"])
def test_more_rows_than_the_usual_buffer_take_the_larger_one(variant):
    """128 experts, 8 held: the row buffer is sized for four times the even
    share; with every token sent to a held expert the layer computes them
    all (no capacity, nothing dropped)."""
    grouped_products(variant)
    layer = DroplessExpertsLayer(n_out=16, n_experts=128, top_k=6, hidden=8,
                                 experts_held_first=8, experts_held_count=8)
    it = InputType.recurrent(16, 2048)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    params["e_bias"] = params["e_bias"].at[8:14].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 2048, 16))
    out, st = jax.jit(lambda p, x: layer.apply(p, x, layer.init_state(it)))(
        params, x)
    assert [int(v) for v in st["counters"]] == [2048 * 6, 2048, 2048, 0]
    sizes = dict(TINY, experts_held_first=8, n_routed_experts=8,
                 num_experts_per_tok=6, routed_scaling_factor=1.0)
    close(out, reference(REF.reference_experts, f32(params), f32(x), sizes))


def test_aligned_layout_gives_every_group_whole_tiles_and_keeps_the_order():
    from deeplearning4j_tpu.ops.grouped_matmul import aligned_layout

    sizes = jnp.asarray([5, 0, 9, 8], jnp.int32)
    group, index, valid, padded = (np.asarray(a) for a in
                                   aligned_layout(sizes, 8, 64))
    assert padded.tolist() == [8, 8, 16, 8]        # an empty group owns a tile
    assert group[::8].tolist() == [0, 1, 2, 2, 3, 3, 3, 3]
    assert index[valid].tolist() == list(range(22))  # the sorted order kept
    assert valid.reshape(8, 8).sum(axis=1).tolist() == [5, 0, 8, 1, 8, 0, 0, 0]
    # alignment 1: the sorted order itself, but for the empty group's slot
    group, index, valid, padded = (np.asarray(a) for a in
                                   aligned_layout(sizes, 1, 32))
    assert padded.tolist() == [5, 1, 9, 8] and valid.sum() == 22
    assert index[valid].tolist() == list(range(22))


def test_grouped_matmul_site_auto_picks_the_kernels_at_the_published_shapes():
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.ops import grouped_matmul as gm

    s = PUBLISHED
    shapes = (14336, s["hidden_size"], s["moe_intermediate_size"], 8, 2)
    assert ops.select_grouped_matmul_variant(*shapes) == "reference"  # the CPU
    assert ks.selection_log()[-1]["row_tile"] == 1
    ks.reset()
    ks.set_force_available(True)
    assert ops.select_grouped_matmul_variant(*shapes) == "fused"
    rec = ks.selection_log()[-1]
    assert rec["site"] == "grouped_matmul" and rec["reason"] == "auto"
    assert rec["row_tile"] == gm.ROW_TILE and "infeasible" not in rec
    with ks.partitioned_program():   # GSPMD will partition: never quietly
        assert ops.select_grouped_matmul_variant(*shapes) == "reference"
    rec = ks.selection_log()[-1]
    assert rec["reason"] == "fallback" and rec["infeasible"] == ["fused"]
    # a buffer that is not whole row tiles gives way too
    assert ops.select_grouped_matmul_variant(100, 64, 32, 2, 4) == "reference"
    assert ks.selection_log()[-1]["reason"] == "fallback"
    # the kernels' own reckoning is inside the seq kernels' VMEM budget
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    assert gm.gmm_footprint(2688, 1856, 2) <= pk._seq_vmem_budget()
    assert gm.gmm_footprint(1856, 2688, 4) <= pk._seq_vmem_budget()


def test_the_shares_routed_parts_and_the_shared_expert_once_make_the_layer():
    """One test ties the share to the model: four shares of 2 experts (the
    preset's sixteen of 8), each computing its own experts' part for the
    tokens routed to them, plus what every chip computes alike counted once,
    add up to the uncut reference's expert layer."""
    whole = experts_layer(first=0, count=8)
    it = InputType.recurrent(64, 12)
    params = whole.init_params(jax.random.PRNGKey(7), it)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, 64))
    tokens = x.reshape(-1, 64)
    total = whole.shared(params, tokens)
    rows = 0
    for share in range(4):
        layer = experts_layer(first=2 * share, count=2)
        mine = dict(params, W_up=params["W_up"][2 * share:2 * share + 2],
                    W_down=params["W_down"][2 * share:2 * share + 2])
        part, counters = layer.routed(mine, tokens)
        total = total + part
        rows += int(counters[0])
    assert rows == 24 * 2          # every pick lands on exactly one share
    uncut = reference(REF.reference_experts, f32(params), f32(x),
                      experts_sizes(first=0, count=8))
    close(total.reshape(x.shape), uncut)
    close(whole.apply(params, x, whole.init_state(it))[0], uncut)


def test_load_balance_stats_and_the_counters_share_one_counting_function():
    idx = jnp.array([[0, 3], [3, 5], [7, 3]])
    assert expert_row_counts(idx, 8).tolist() == [1, 0, 0, 3, 0, 1, 0, 1]
    assert expert_row_counts(idx - 3, 2).tolist() == [3, 0]   # a held share
    old = MixtureOfExpertsLayer(n_out=8, n_experts=4, top_k=2)
    p = old.init_params(jax.random.PRNGKey(0), InputType.recurrent(8, 6))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 8))
    stats = old.load_balance_stats(p, x)
    assert float(jnp.sum(stats["expert_fraction"])) == pytest.approx(2.0)
    new = experts_layer()
    p = new.init_params(jax.random.PRNGKey(0), InputType.recurrent(64, 6))
    stats = new.load_balance_stats(p, jax.random.normal(
        jax.random.PRNGKey(1), (2, 6, 64)))
    assert float(jnp.sum(stats["expert_fraction"])) == pytest.approx(2.0)
    assert stats["dropped_tokens"] == 0


# --------------------------------------------------------------------- loss
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_integer_labels_give_the_one_hot_loss_and_gradient(fused, masked):
    from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer

    if fused:
        ks.set_force_available(True)
        ks.set_site_override("softmax_xent", "fused")
    layer = RnnOutputLayer(n_out=40, activation="softmax", loss="mcxent",
                           has_bias=False)
    it = InputType.recurrent(24, 6)
    params = layer.init_params(jax.random.PRNGKey(0), it)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 24))
    ids = jax.random.randint(jax.random.PRNGKey(2), (3, 6), 0, 40)
    hot = jax.nn.one_hot(ids, 40, dtype=x.dtype)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (3, 6)) > 0.3).astype(
        x.dtype) if masked else None
    a, ga = jax.value_and_grad(
        lambda p: layer.compute_loss(p, x, ids, mask))(params)
    b, gb = jax.value_and_grad(
        lambda p: layer.compute_loss(p, x, hot, mask))(params)
    assert float(a) == pytest.approx(float(b), rel=1e-10)
    close(ga["W"], gb["W"], 1e-9)
    assert {r["variant"] for r in ks.selection_log()
            if r["site"] == "softmax_xent"} == {"fused" if fused
                                                else "reference"}


def test_integer_labels_with_a_loss_that_is_not_softmax():
    from deeplearning4j_tpu.nn import losses

    x = jax.random.normal(jax.random.PRNGKey(1), (5, 7))
    ids = jnp.array([0, 6, 3, 3, 1])
    hot = jax.nn.one_hot(ids, 7, dtype=x.dtype)
    for act in ("softmax", "sigmoid"):
        assert float(losses.mcxent(ids, x, act)) == pytest.approx(
            float(losses.mcxent(hot, x, act)), rel=1e-9)


# ---------------------------------------------------------------- the model
def tiny_net(seed=3, **kw):
    return REF.build(dict(TINY, **kw), seed)


def tiny_batches(slots=3, batch=2, seed=0):
    return REF.make_batches(TINY, {"slots": slots, "seq_len": 16}, seed, batch)


def test_the_builder_takes_pattern_sizes_share_and_slice_as_arguments():
    from deeplearning4j_tpu.models.nemotron_h import nemotron_h_conf

    net = tiny_net()
    conf = net.conf
    assert conf.remat and conf.dtype == "float32"
    kinds = [n[:-len("_mixer")] for n in conf.vertices if n.endswith("_mixer")]
    assert kinds == ["b0M", "b1E", "b2M", "b3E", "b4M", "b5E", "b6M", "b7A",
                     "b8E"]
    experts = conf.vertices["b1E_mixer"].layer
    assert experts.held == (0, 2) and experts.n_experts == 8
    assert net.params["b1E_mixer"]["W_up"].shape == (2, 64, 32)
    assert net.params["b1E_mixer"]["Wr"].shape == (64, 8)
    assert net.params["embed"]["W"].shape == (64, 64)
    assert net.params["head"]["W"].shape == (64, 64)
    assert "b" not in net.params["head"] and "bo" not in net.params["b7A_mixer"]
    # the rescaled projections: 0.02 / sqrt(52)
    assert float(jnp.std(net.params["b0M_mixer"]["W_out"])) == pytest.approx(
        0.02 / 52 ** 0.5, rel=0.1)
    assert float(jnp.std(net.params["b0M_mixer"]["W_in"])) == pytest.approx(
        0.02, rel=0.1)
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    with pytest.raises(ValueError, match="blocks are"):
        nemotron_h_conf("MXE")
    # the published sizes count what ISSUE 30 counts
    met = REF.params_a_token_meets(PUBLISHED)
    assert met["M"] == pytest.approx(38.7e6, rel=2e-3)
    assert met["*"] == pytest.approx(23.4e6, rel=2e-3)
    assert REF.model_flops_per_sample(PUBLISHED) == pytest.approx(
        2.153e9, rel=2e-3)


def test_layers_declare_roles_types_and_round_trip():
    from deeplearning4j_tpu.parallel.roles import roles_for

    for layer in (mamba_layer(), experts_layer(), RMSNormLayer(groups=2)):
        assert layer_from_dict(json.loads(json.dumps(layer.to_dict()))) == layer
        out = layer.get_output_type(InputType.recurrent(64, 9))
        assert (out.kind, out.size, out.timesteps) == ("rnn", 64, 9)
    assert roles_for(mamba_layer()) == {"W_in": "ffn_up", "W_out": "ffn_down"}
    assert roles_for(experts_layer())["Ws_down"] == "ffn_down"
    assert roles_for(RMSNormLayer()) == {"gamma": "generic"}
    norm = RMSNormLayer(groups=2)
    p = norm.init_params(jax.random.PRNGKey(0), InputType.recurrent(8, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8))
    y = norm.apply(p, x, {})[0]
    want = x.reshape(2, 3, 2, 4)
    want = want / jnp.sqrt(jnp.mean(want ** 2, -1, keepdims=True) + 1e-5)
    close(y, want.reshape(2, 3, 8), 1e-6)
    with pytest.raises(ValueError, match="norm groups"):
        RMSNormLayer(groups=3).init_params(jax.random.PRNGKey(0),
                                           InputType.recurrent(8, 3))


def test_whole_model_loss_and_first_update_match_the_plain_reference():
    net = tiny_net()
    xs, ys = tiny_batches()
    ref_loss = REF.reference_loss(net.params, net.state, xs[0], ys[0], TINY)
    loss = float(net.loss_fn(net.params, [xs[0]], [ys[0]], train=True))
    assert loss == pytest.approx(ref_loss, rel=2e-5)
    assert abs(loss - REF.expected_first_loss(TINY)) < 0.1 * loss

    def plain(p):
        h = REF.reference_hidden(p, xs[0], TINY)
        return jnp.mean(REF.reference_token_losses(
            jnp.asarray(p["head"]["W"], jnp.float32), h, ys[0]))

    want = reference(jax.grad(plain), f32(dict(net.params)))
    got = jax.grad(lambda p: net.loss_fn(p, [xs[0]], [ys[0]], train=True))(
        net.params)
    for vertex, leaves in want.items():
        for name, g in leaves.items():
            if name == "e_bias":          # selects only: no gradient
                assert float(jnp.max(jnp.abs(got[vertex][name]))) == 0.0
                continue
            close(got[vertex][name], g, 2e-3)
    # the first update is Adam's: every parameter with a gradient moves by
    # the learning rate, against the gradient's sign (lr * g / (|g| + 1e-8):
    # a gradient of 1e-4 or more is within 1e-4 of a whole step)
    before = jax.tree_util.tree_map(np.asarray, net.params)
    net.fit_on_device(xs, ys, steps=1)
    lr = TINY["learning_rate"]
    for vertex, leaves in want.items():
        for name, g in leaves.items():
            step = np.asarray(net.params[vertex][name]) - before[vertex][name]
            big = np.abs(np.asarray(g)) > 1e-4
            if name == "e_bias" or not big.any():
                continue
            np.testing.assert_allclose(step[big], -lr * np.sign(
                np.asarray(g))[big], rtol=1e-3)


def test_fit_on_device_equals_fit_step_by_step_and_publishes_the_counters():
    from deeplearning4j_tpu import MultiDataSet
    from deeplearning4j_tpu.telemetry import get_registry
    from deeplearning4j_tpu.telemetry.device import LAYER_COUNTER_FAMILY

    def counted():
        fam = get_registry().snapshot().get(LAYER_COUNTER_FAMILY,
                                            {"values": []})
        return {(r["labels"]["layer"], r["labels"]["counter"]): r["value"]
                for r in fam["values"]}

    xs, ys = tiny_batches()
    staged, stepped = tiny_net(), tiny_net()
    before = counted()
    losses = staged.fit_on_device(xs, ys, steps=3)
    after = counted()
    one_by_one = []
    for i in range(3):
        stepped.fit(MultiDataSet([np.asarray(xs[i])], [np.asarray(ys[i])]),
                    stage_on_device=0)
        one_by_one.append(float(stepped._last_loss))
    np.testing.assert_allclose(losses, one_by_one, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(staged.params),
                    jax.tree_util.tree_leaves(stepped.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    added = {k: after[k] - before.get(k, 0.0) for k in after}
    for layer in ("b1E_mixer", "b3E_mixer", "b5E_mixer", "b8E_mixer"):
        assert added[(layer, "tokens")] == 3 * 2 * 16
        assert added[(layer, "rows_dropped")] == 0
        assert 0 < added[(layer, "rows_fullest")] <= added[(layer, "rows_held")]
        # the dispatch's sums are what the state holds after it
        assert int(staged.state[layer]["counters"][0]) \
            == added[(layer, "rows_held")]
    # a second dispatch counts from zero again
    staged.fit_on_device(xs, ys, steps=1)
    assert int(staged.state["b1E_mixer"]["counters"][2]) == 2 * 16


def test_reference_gradients_block_by_block_equal_the_whole_differentiated():
    """The plain reference's gradient is computed a block at a time (to fit
    beside the net on the chip): the same numbers as differentiating its
    loss in one piece."""
    net = tiny_net()
    xs, ys = tiny_batches()
    vertices = ["b0M_mixer", "b0M_norm", "b1E_mixer", "b7A_mixer", "norm_f",
                "head"]
    loss, got = REF.reference_gradients(net.params, xs[0], ys[0], TINY,
                                        vertices)

    def whole(p):
        with jax.default_matmul_precision("highest"):
            h = REF.reference_hidden(p, xs[0], TINY)
            return jnp.mean(REF.reference_token_losses(
                jnp.asarray(p["head"]["W"], jnp.float32), h, ys[0]))

    want_loss, want = jax.value_and_grad(whole)(f32(dict(net.params)))
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert loss == pytest.approx(
        REF.reference_loss(net.params, net.state, xs[0], ys[0], TINY),
        rel=1e-6)
    assert list(got) == vertices
    for v in vertices:
        assert set(got[v]) == set(net.params[v])
        for name, g in got[v].items():
            close(g, want[v][name], 1e-5)
