"""The latent-attention / hyper-connection / gated-expert model
(``models/xing4.py``) and the layers it is built from, each against the plain
reference of ``benchmarks/configs/xing4_29b_a4b.py`` (float32, full scores,
a loop over the experts, the Sinkhorn as a loop over matrices) at the tiny
preset sizes, seeded weights; the flash kernels with score and value products
of two sizes and a shared rotary key in interpret mode; and the shares of a
layer (heads, experts) adding up to the uncut layer."""

import hashlib
import importlib
import json
import os
import re
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
from deeplearning4j_tpu.models.xing4 import sublayer_kinds, xing4_conf  # noqa: E402
from deeplearning4j_tpu.nn.conf.computation_graph import \
    ComputationGraphConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.graph.vertices import vertex_from_dict  # noqa: E402
from deeplearning4j_tpu.nn.layers import attention as att  # noqa: E402
from deeplearning4j_tpu.nn.layers import hyper_connections as hc  # noqa: E402
from deeplearning4j_tpu.nn.layers.base import layer_from_dict  # noqa: E402
from deeplearning4j_tpu.nn.layers.dense import GatedFeedForwardLayer  # noqa: E402
from deeplearning4j_tpu.nn.layers.moe import DroplessExpertsLayer  # noqa: E402
from deeplearning4j_tpu.ops import kernel_select as ks  # noqa: E402
from deeplearning4j_tpu.parallel.ring_attention import attention as xla_attention  # noqa: E402

fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
CONFIG = "xing4_29b_a4b"
REF = load_module(os.path.join(REPO, "benchmarks", "configs", CONFIG + ".py"))
PUBLISHED = load_json(os.path.join(REPO, "benchmarks", "configs",
                                   CONFIG + ".json"))
TINY = dict(PUBLISHED, dtype="float32", **load_json(os.path.join(
    REPO, "tests", "benchmark_harness", "presets", "configs",
    CONFIG + ".json"))["sizes"])
D = TINY["hidden_size"]


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


def layer_and_gradients_match(layer, ref_fn, sizes, x, key=1, tol=5e-4,
                              no_gradient=()):
    """``layer`` over ``x`` against ``ref_fn(params, x, sizes)``: the output
    and the gradient of a random projection of it, for every parameter and
    the input."""
    it = InputType.recurrent(x.shape[-1], x.shape[1])
    params = layer.init_params(jax.random.PRNGKey(key), it)
    state = layer.init_state(it)
    out, _ = layer.apply(params, x, state)
    close(out, reference(ref_fn, f32(params), f32(x), sizes))
    w = jax.random.normal(jax.random.PRNGKey(3), out.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x, state)[0] * w),
                   argnums=(0, 1))(params, x)
    want = reference(jax.grad(
        lambda p, x: jnp.sum(ref_fn(p, x, sizes) * w.astype(jnp.float32)),
        argnums=(0, 1)), f32(params), f32(x))
    for name in params:
        if name in no_gradient:
            assert float(jnp.max(jnp.abs(got[0][name]))) == 0.0
        else:
            close(got[0][name], want[0][name], tol)
    close(got[1], want[1], tol)
    return params, out


# --------------------------------------------------------- latent attention
def latent_layer(first=0, count=None, **kw):
    rs = TINY["rope_scaling"]
    return att.LatentAttentionLayer(
        n_out=D, n_heads=TINY["published"]["num_attention_heads"],
        heads_held_first=first,
        heads_held_count=TINY["num_attention_heads"] if count is None
        else count,
        q_rank=TINY["q_lora_rank"], kv_rank=TINY["kv_lora_rank"],
        nope_dim=TINY["qk_nope_head_dim"], rope_dim=TINY["qk_rope_head_dim"],
        v_dim=TINY["v_head_dim"], eps=TINY["rms_norm_eps"],
        rope_theta=TINY["rope_theta"], rope_factor=rs["factor"],
        rope_original_positions=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"], rope_mscale_all_dim=rs["mscale_all_dim"],
        rescale_layers=40, **kw)


def flash_everywhere(impl):
    """``flash``: the kernels in interpret mode whatever the shapes."""
    if impl == "flash":
        ks.set_force_available(True)


@pytest.mark.parametrize("impl", ["xla", "flash"])
@pytest.mark.parametrize("batch,T,count", [
    (2, 16, 2),    # twice the rotary base of 8: the YaRN blend is live
    (1, 24, 4),    # every head, three times the base
    (2, 5, 1),     # inside the base, one head held
])
def test_latent_attention_matches_the_plain_reference(impl, batch, T, count):
    flash_everywhere(impl)
    x = jax.random.normal(jax.random.PRNGKey(2), (batch, T, D))
    layer_and_gradients_match(latent_layer(count=count, attention_impl=impl),
                              REF.reference_attention, TINY, x)
    log = [r for r in ks.selection_log() if r["site"] == "attention"]
    assert {r["variant"] for r in log} == {impl}
    ctx = log[0]["ctx"]
    assert (ctx["d_qk"], ctx["d_v"], ctx["d_rope"], ctx["rope_shared_key"]) \
        == (12, 8, 4, True) and ctx["D"] == 12
    if impl == "flash":
        assert log[0]["tiles_walked_share"] == 1.0    # one tile a side


def test_yarn_frequencies_blend_past_the_rotary_base():
    """The published rotary group: pairs that turn more than 32 times over
    the 4096 positions keep their frequency, pairs that turn less than once
    are divided by 64, a ramp in between; the softmax scale carries
    ``mscale^2`` = 1.4159^2; the reference computes the same."""
    rs = PUBLISHED["rope_scaling"]
    got = np.asarray(att.yarn_inv_freq(
        64, 10000.0, rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"]))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 64, rtol=1e-6)
    inside = (got < plain * 0.999) & (got > plain / 64 * 1.001)
    assert 5 <= inside.sum() <= 16
    want, magnitude, scale = REF.yarn_frequencies(PUBLISHED)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert magnitude == 1.0
    layer = att.LatentAttentionLayer(rope_factor=64.0, rope_mscale_all_dim=1.0)
    assert att.yarn_mscale(64, 1) == pytest.approx(1.4159, abs=1e-4)
    assert layer.softmax_scale == pytest.approx(scale)
    assert scale == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    assert layer.rotary_magnitude == 1.0
    # no stretch: the plain frequencies and the plain scale
    np.testing.assert_allclose(
        np.asarray(att.yarn_inv_freq(64, 10000.0, 1.0, 4096, 32, 1)), plain,
        rtol=1e-6)
    assert att.LatentAttentionLayer().softmax_scale == 192 ** -0.5


def test_the_yarn_blend_changes_the_layer_past_the_base_only_there():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, D))
    blended, plain = latent_layer(), latent_layer()
    plain.rope_factor = 1.0
    it = InputType.recurrent(D, 16)
    params = blended.init_params(jax.random.PRNGKey(1), it)
    a = blended.apply(params, x, {})[0]
    b = plain.apply(params, x, {})[0]
    assert float(jnp.max(jnp.abs(a - b))) > 1e-4 * float(jnp.max(jnp.abs(a)))
    # position 0 attends to itself alone, whatever the angles
    close(a[:, 0], b[:, 0], 1e-6)


def test_rotary_is_a_rotation_of_the_interleaved_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 8))
    inv = att.yarn_inv_freq(8, 10000.0, 1.0, 4096, 32, 1)
    y = att.apply_rotary(x, inv)
    # norms of every pair are kept; position 0 is only de-interleaved
    pairs = lambda a: a[..., 0::2] ** 2 + a[..., 1::2] ** 2  # noqa: E731
    halves = lambda a: a[..., :4] ** 2 + a[..., 4:] ** 2  # noqa: E731
    close(halves(y), pairs(x), 1e-5)
    close(y[:, 0], jnp.concatenate([x[:, 0, :, 0::2], x[:, 0, :, 1::2]], -1),
          1e-6)
    # the same scores as the reference's rotation in place
    want = reference(REF._rotate, x, np.asarray(inv), 1.0)
    close(jnp.einsum("bthd,bshd->bhts", y, y),
          jnp.einsum("bthd,bshd->bhts", want, want), 1e-5)


def test_the_head_shares_partial_sums_make_the_uncut_layer():
    """One test ties the share to the model: four shares of one head each,
    given their slices of ``W_qb``, ``W_kvb`` and ``W_o`` and everything else
    alike, add up to what the reference gives with all four heads."""
    whole = latent_layer(count=4)
    it = InputType.recurrent(D, 16)
    params = whole.init_params(jax.random.PRNGKey(1), it)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, D))
    qk = TINY["qk_nope_head_dim"] + TINY["qk_rope_head_dim"]
    kv = TINY["qk_nope_head_dim"] + TINY["v_head_dim"]
    dv = TINY["v_head_dim"]
    total = 0.0
    for h in range(4):
        mine = dict(params, W_qb=params["W_qb"][:, h * qk:(h + 1) * qk],
                    W_kvb=params["W_kvb"][:, h * kv:(h + 1) * kv],
                    W_o=params["W_o"][h * dv:(h + 1) * dv])
        total = total + latent_layer(first=h, count=1).apply(mine, x, {})[0]
    close(total, reference(REF.reference_attention, f32(params), f32(x),
                           TINY))
    with pytest.raises(ValueError, match="heads held"):
        latent_layer(first=3, count=2).init_params(jax.random.PRNGKey(0), it)


# -------------------------------------------------------------- flash kernels
def latent_qkv(b, h, t, dn, dr, dv, dtype, seed=0, hr=1):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    return (mk(b, h, t, dn), mk(b, h, t, dn), mk(b, h, t, dv),
            mk(b, h, t, dr), mk(b, hr, t, dr))


def latent_reference(q, k, v, qr, kr, **kw):
    q, k, v, qr, kr = (a.astype(jnp.float32) for a in (q, k, v, qr, kr))
    qq = jnp.concatenate([q, qr], -1)
    kk = jnp.concatenate([k, jnp.repeat(kr, q.shape[1] // kr.shape[1], 1)], -1)
    return xla_attention(qq, kk, v, scale=qq.shape[-1] ** -0.5, **kw)


FLASH_TOL = {"float32": (1e-5, 1e-4), "bfloat16": (2e-2, 5e-2)}
# causal, T, block_q, block_k, heads, dn, dr, dv
LATENT_SHAPES = [
    (True, 64, 32, 32, 2, 128, 64, 128),    # the published head: 192 / 128
    (True, 40, 16, 8, 3, 16, 8, 24),        # unequal blocks, T padded
    (False, 48, 16, 16, 2, 16, 8, 8),       # every tile walked
    (True, 20, None, None, 2, 16, 8, 16),   # one tile
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal,t,bq,bk,h,dn,dr,dv", LATENT_SHAPES)
def test_flash_kernels_with_a_shared_rotary_key_forward_and_backward(
        causal, t, bq, bk, h, dn, dr, dv, dtype):
    """``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` with scores over
    ``dn + dr`` (the rotary key one head for all) and values over ``dv``,
    against the XLA path on the concatenated, repeated arrays."""
    args = latent_qkv(1, h, t, dn, dr, dv, jnp.dtype(dtype))

    def flash(q, k, v, qr, kr):
        return fa.flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk, q_rope=qr, k_rope=kr)

    out = flash(*args)
    assert out.shape == (1, h, t, dv) and out.dtype == jnp.dtype(dtype)
    ref = latent_reference(*args, causal=causal)
    tol, gtol = FLASH_TOL[dtype]
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)
    w = jnp.asarray(np.random.default_rng(1).normal(size=ref.shape),
                    jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32) * w),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(latent_reference(*a, causal=causal)
                                       * w), argnums=(0, 1, 2, 3, 4))(*args)
    for g, r, a in zip(got, want, args):
        assert g.shape == a.shape and g.dtype == a.dtype
        scale = float(jnp.max(jnp.abs(r.astype(jnp.float32))))
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=gtol, atol=gtol * scale)


@pytest.mark.parametrize("d_qk,d_v,hkv", [(24, 8, 2), (8, 24, 1), (192, 128, 4)])
def test_flash_kernels_with_score_and_value_products_of_two_sizes(d_qk, d_v,
                                                                  hkv):
    rng = np.random.default_rng(0)
    mk = lambda h, d: jnp.asarray(rng.normal(size=(1, h, 32, d)), jnp.float32)  # noqa: E731
    q, k, v = mk(4, d_qk), mk(hkv, d_qk), mk(hkv, d_v)
    rep = lambda a: jnp.repeat(a, 4 // hkv, axis=1)  # noqa: E731
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=16, block_k=16)
    plain = lambda q, k, v: xla_attention(q, rep(k), rep(v), causal=True)  # noqa: E731
    close(flash(q, k, v), plain(q, k, v), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        close(g, r, 1e-4)


# the traced program of a plain call (``d_qk == d_v``, no rotary part) at the
# hybrid cell's shape and at a small float32 one: the text of the jaxpr with
# the three kernels' bodies in it, hashed on the commit before the kernels
# took two sizes (bc4c03b). An edit of the kernels that changes what a plain
# call lowers to changes these.
PLAIN_PROGRAMS = [
    ((1, 32, 2, 8192, 128, "bfloat16", True),
     "7e87da3c42919dab080a2f9abdc2419a1955cdc2b70cc3ea9bd28717f444a6a4"),
    ((2, 4, 4, 200, 64, "float32", False),
     "d36b0fee3bad239f0078f3dab72ce5c0a2d2a8df7f777e05c017bc6d44c5ca7a"),
]


@pytest.mark.parametrize("shape,digest", PLAIN_PROGRAMS)
def test_a_plain_call_traces_to_the_program_it_traced_to_before(shape, digest):
    b, h, hkv, t, d, dtype, causal = shape
    q = jax.ShapeDtypeStruct((b, h, t, d), jnp.dtype(dtype))
    k = jax.ShapeDtypeStruct((b, hkv, t, d), jnp.dtype(dtype))

    def both_ways(q, k, v):
        return jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal), q, k, v)[1](q)

    with jax.enable_x64(False):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(both_ways)(q, k, k)))
    assert hashlib.sha256(text.encode()).hexdigest() == digest
    assert fa.strip_width(d, d) == d
    assert fa.default_blocks(t, fa.strip_width(d, d), q.dtype.itemsize) \
        == fa.default_blocks(t, d, q.dtype.itemsize)


def test_attention_site_records_the_two_sizes_only_where_they_differ():
    from deeplearning4j_tpu import ops

    ks.set_force_available(True)
    assert ops.select_attention_variant(1, 32, 8192, 128, 2, causal=True,
                                        kv_heads=2) == "flash"
    assert ops.select_attention_variant(1, 4, 8192, 192, 2, causal=True,
                                        d_v=128, d_rope=64) == "flash"
    hybrid, latent = [r for r in ks.selection_log() if r["site"] == "attention"]
    assert hybrid["ctx"] == {"B": 1, "heads": 32, "T": 8192, "D": 128,
                             "itemsize": 2, "causal": True, "kv_heads": 2}
    assert (hybrid["block_q"], hybrid["block_k"]) == (512, 512)
    assert hybrid["tiles_walked_share"] == 0.53125
    assert latent["ctx"] == {"B": 1, "heads": 4, "T": 8192, "D": 192,
                             "itemsize": 2, "causal": True, "d_qk": 192,
                             "d_v": 128, "d_rope": 64,
                             "rope_shared_key": True}
    # three strips of 128 lanes instead of two: 256-wide tiles, 32 a side
    assert (latent["block_q"], latent["block_k"]) == (256, 256)
    assert latent["tiles_walked_share"] == 33 / 64
    assert fa.strip_width(128, 128, 64) == 192
    # float32 strips of this call do not fit the kernels' VMEM: the XLA path
    assert ops.select_attention_variant(1, 4, 8192, 192, 4, causal=True,
                                        d_v=128, d_rope=64) == "xla"


# ------------------------------------------------------------- feed-forwards
def experts_layer(first=2, count=2, **kw):
    return DroplessExpertsLayer(
        n_out=D, n_experts=8, top_k=2, hidden=16, shared_hidden=16,
        experts_held_first=first, experts_held_count=count,
        routed_scaling=2.0, expert_activation="silu", gated=True,
        rescale_layers=40, **kw)


def experts_sizes(first=2, count=2):
    return dict(TINY, experts_held_first=first, n_routed_experts=count)


@pytest.mark.parametrize("variant", ["reference", "mosaic_interpret"])
def test_gated_experts_match_the_plain_reference(variant):
    if variant == "mosaic_interpret":
        ks.set_force_available(True)
        ks.set_site_override("grouped_matmul", "fused")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, D))
    params, _ = layer_and_gradients_match(
        experts_layer(), REF.reference_experts, experts_sizes(), x,
        no_gradient=("e_bias",))
    assert set(params) == {"Wr", "e_bias", "W_gate", "W_up", "W_down",
                           "Ws_gate", "Ws_up", "Ws_down"}
    assert params["W_gate"].shape == params["W_up"].shape == (2, D, 16)
    assert {r["variant"] for r in ks.selection_log()
            if r["site"] == "grouped_matmul"} == {
        "fused" if variant == "mosaic_interpret" else "reference"}


def test_gated_experts_keep_the_layers_counters():
    layer = experts_layer()
    it = InputType.recurrent(D, 12)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, D))
    _, state = layer.apply(params, x, layer.init_state(it))
    rows, fullest, tokens, dropped = (int(v) for v in state["counters"])
    assert layer.COUNTERS == ("rows_held", "rows_fullest", "tokens",
                              "rows_dropped")
    assert tokens == 24 and dropped == 0 and 0 < fullest <= rows <= 48


def test_an_ungated_layer_has_no_gate_and_the_parameters_it_had():
    layer = DroplessExpertsLayer(n_out=D, n_experts=8, top_k=2, hidden=16,
                                 shared_hidden=16)
    gated = DroplessExpertsLayer(n_out=D, n_experts=8, top_k=2, hidden=16,
                                 shared_hidden=16, gated=True)
    it = InputType.recurrent(D, 4)
    plain = layer.init_params(jax.random.PRNGKey(1), it)
    both = gated.init_params(jax.random.PRNGKey(1), it)
    assert set(both) - set(plain) == {"W_gate", "Ws_gate"}
    for name in plain:       # the same draws, gated or not
        np.testing.assert_array_equal(np.asarray(plain[name]),
                                      np.asarray(both[name]))


def test_the_expert_shares_and_the_shared_expert_once_make_the_layer():
    """Four shares of 2 experts, each with its slices of the three stacks,
    and the shared expert counted once, add up to the uncut layer."""
    whole = experts_layer(first=0, count=8)
    it = InputType.recurrent(D, 12)
    params = whole.init_params(jax.random.PRNGKey(1), it)
    tokens = jax.random.normal(jax.random.PRNGKey(2), (24, D))
    total = whole.shared(params, tokens)
    rows = 0
    for share in range(4):
        cut = slice(2 * share, 2 * share + 2)
        mine = dict(params, W_gate=params["W_gate"][cut],
                    W_up=params["W_up"][cut], W_down=params["W_down"][cut])
        y, counters = experts_layer(first=2 * share, count=2).routed(
            mine, tokens)
        total, rows = total + y, rows + int(counters[0])
    assert rows == 24 * 2          # every pick lands on exactly one share
    close(total, reference(REF.reference_experts, f32(params), f32(tokens),
                           experts_sizes(0, 8)))


def test_gated_dense_feed_forward_matches_the_plain_reference():
    layer = GatedFeedForwardLayer(n_out=D, hidden=TINY["intermediate_size"],
                                  rescale_layers=40)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, D))
    params, out = layer_and_gradients_match(layer, REF.reference_dense, TINY, x)
    assert out.shape == x.shape
    assert params["W_gate"].shape == params["W_up"].shape == (D, 48)
    assert float(jnp.std(params["W_down"])) < 0.5 * float(jnp.std(params["W_up"]))
    with pytest.raises(ValueError, match="hidden"):
        GatedFeedForwardLayer().init_params(jax.random.PRNGKey(0),
                                            InputType.recurrent(D, 3))


# ---------------------------------------------------------- hyper-connections
def maps_layer(n=4, **kw):
    return hc.HyperConnectionMapsLayer(n_streams=n, **kw)


def maps_sizes(n=4):
    return dict(TINY, hc_mult=n)


@pytest.mark.parametrize("n,read", [(4, 0), (2, 1), (4, 6)])
def test_hyper_connection_maps_match_the_plain_reference(n, read):
    layer = maps_layer(n, read_stream=read)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 5, n * 8))
    # weights that make the maps depend on the token
    it = InputType.recurrent(n * 8, 5)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    params = dict(params, a=jnp.asarray([0.7, -0.4, 0.9]))

    def ref(p, x, sizes):
        pre, post, res = REF.reference_maps(
            p, x.reshape(x.shape[:-1] + (n, -1)), sizes)
        return jnp.concatenate(
            [pre, post, res.reshape(res.shape[:-2] + (-1,))], axis=-1)

    out = layer.apply(params, x, {})[0]
    assert out.shape == (2, 5, n * (n + 2)) and layer.n_maps == n * (n + 2)
    close(out, reference(ref, f32(params), f32(x), maps_sizes(n)), 1e-5)
    w = jax.random.normal(jax.random.PRNGKey(3), out.shape)
    got = jax.grad(lambda p, x: jnp.sum(layer.apply(p, x, {})[0] * w),
                   argnums=(0, 1))(params, x)
    want = reference(jax.grad(lambda p, x: jnp.sum(ref(p, x, maps_sizes(n)) * w),
                              argnums=(0, 1)), f32(params), f32(x))
    for name in params:
        close(got[0][name], want[0][name], 5e-4)
    close(got[1], want[1], 5e-4)
    # the start: one stream read, all written with 1, near the identity
    start = layer.init_params(jax.random.PRNGKey(1), it)
    pre, post, res = hc.split_maps(layer.apply(start, x, {})[0], n)
    assert int(jnp.argmax(jnp.mean(pre, (0, 1)))) == read % n
    assert float(jnp.min(jnp.max(pre, -1))) > 0.9
    close(post, jnp.ones_like(post), 0.2)
    assert float(jnp.min(jnp.diagonal(res, axis1=-2, axis2=-1))) > 0.8


@pytest.mark.parametrize("case", ["start", "spread", "steep_gates"])
def test_the_stream_map_is_doubly_stochastic_and_the_others_are_bounded(case):
    """After 20 iterations every row and column of ``H_res`` sums to 1
    within 1e-4 (at the start, and with raw entries a unit apart around no
    diagonal: a matrix near a permutation takes more), ``H_pre`` lies in
    (0, 1) and ``H_post`` in (0, 2) however steep their gates."""
    layer = maps_layer(4)
    it = InputType.recurrent(32, 6)
    params = layer.init_params(jax.random.PRNGKey(4), it)
    if case != "start":
        gates = [40.0, 40.0, 1.0] if case == "steep_gates" else [1.0, 1.0, 1.0]
        params = dict(
            params, a=jnp.asarray(gates), b=jnp.zeros_like(params["b"]),
            P=jax.random.normal(jax.random.PRNGKey(5), (32, 24)) * 0.2)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 6, 32))
    pre, post, res = hc.split_maps(layer.apply(params, x, {})[0], 4)
    np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=1e-4)
    assert float(res.min()) >= 0.0
    if case != "start":      # the maps differ from token to token
        assert float(jnp.std(res[..., 0, 0])) > 0.01
    assert 0.0 <= float(pre.min()) and float(pre.max()) <= 1.0
    assert 0.0 <= float(post.min()) and float(post.max()) <= 2.0
    if case == "steep_gates":
        assert float(pre.max()) > 0.999 and float(post.min()) < 0.001
    else:
        assert 0.0 < float(pre.min()) and float(post.max()) < 2.0


def test_the_clip_at_thirty_is_hit_by_a_planted_input():
    """A stream map whose raw entries reach +-200 is clipped to +-30 before
    the exponential: the result is finite, equals the result of entries
    already at the clip, and differs from an unclipped layer's."""
    layer = maps_layer(2)
    it = InputType.recurrent(8, 1)
    params = layer.init_params(jax.random.PRNGKey(0), it)
    planted = jnp.asarray([0.0, 0.0, 0.0, 0.0, 200.0, -200.0, 10.0, -40.0])
    at_clip = jnp.asarray([0.0, 0.0, 0.0, 0.0, 30.0, -30.0, 10.0, -30.0])
    x = jnp.ones((1, 1, 8))
    run = lambda lay, b: hc.split_maps(  # noqa: E731
        lay.apply(dict(params, a=jnp.zeros(3), b=b), x, {})[0], 2)[2]
    res = run(layer, planted)
    assert bool(jnp.all(jnp.isfinite(res)))
    np.testing.assert_array_equal(np.asarray(res), np.asarray(run(layer, at_clip)))
    wide = maps_layer(2, clamp_min=-80.0, clamp_max=80.0)
    assert float(jnp.max(jnp.abs(run(wide, planted) - res))) > 0.0
    # the gradient stops at a clipped entry and passes an unclipped one
    g = jax.grad(lambda b: jnp.sum(run(layer, b) * jnp.asarray([[1.0, 2.0],
                                                                [3.0, 5.0]])))(
        planted)
    assert float(g[4]) == 0.0 and float(g[5]) == 0.0 and float(g[7]) == 0.0
    assert float(jnp.abs(g[6])) > 0.0


def test_hyper_connection_vertices_read_write_expand_and_collapse():
    n, d = 4, 8
    X = jax.random.normal(jax.random.PRNGKey(0), (2, 3, n * d))
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 3, d))
    maps = jax.random.uniform(jax.random.PRNGKey(2), (2, 3, n * (n + 2)))
    pre, post, res = hc.split_maps(maps, n)
    streams = X.reshape(2, 3, n, d)
    vertex = lambda op: hc.HyperConnectionVertex(op=op, n_streams=n)  # noqa: E731
    rnn = lambda size: InputType.recurrent(size, 3)  # noqa: E731
    read = vertex("read").apply({}, [X, maps], {})[0]
    close(read, jnp.einsum("bts,btsd->btd", pre, streams), 1e-6)
    write = vertex("write").apply({}, [X, maps, y], {})[0]
    want = (jnp.einsum("btij,btjd->btid", res, streams)
            + post[..., None] * y[..., None, :])
    close(write, want.reshape(2, 3, n * d), 1e-6)
    normed = hc.HyperConnectionVertex(op="read", n_streams=n, norm_eps=1e-6)
    gamma = normed.init_params(jax.random.PRNGKey(0), rnn(n * d), rnn(24))
    assert normed.has_params and not vertex("read").has_params
    assert gamma["gamma"].shape == (d,) and vertex("write").init_params(
        jax.random.PRNGKey(0), rnn(n * d), rnn(24), rnn(d)) == {}
    gamma = {"gamma": 1.0 + jnp.arange(d) / d}
    close(normed.apply(gamma, [X, maps], {})[0],
          REF._rmsnorm(read, gamma["gamma"], 1e-6), 1e-6)
    wide = vertex("expand").apply({}, [y], {})[0]
    close(wide.reshape(2, 3, n, d), jnp.broadcast_to(y[:, :, None], (2, 3, n, d)),
          1e-7)
    close(vertex("collapse").apply({}, [X], {})[0], streams.sum(2), 1e-6)
    assert vertex("expand").get_output_type(rnn(d)).size == n * d
    assert vertex("read").get_output_type(rnn(n * d), rnn(24)).size == d
    assert vertex("write").get_output_type(rnn(n * d), rnn(24), rnn(d)).size \
        == n * d
    assert vertex("collapse").get_output_type(rnn(n * d)).size == d
    with pytest.raises(ValueError, match="takes 3 inputs"):
        vertex("write").get_output_type(rnn(n * d), rnn(24))
    with pytest.raises(ValueError, match="Unknown"):
        vertex("mix").get_output_type(rnn(n * d))
    # bfloat16 streams: float32 sums, bfloat16 out
    out = vertex("write").apply({}, [X.astype(jnp.bfloat16), maps,
                                     y.astype(jnp.bfloat16)], {})[0]
    assert out.dtype == jnp.bfloat16
    close(out.astype(jnp.float32), want.reshape(2, 3, n * d), 2e-2)


def test_new_layers_and_vertices_declare_types_roles_and_round_trip():
    from deeplearning4j_tpu.parallel.roles import roles_for

    for layer in (latent_layer(), experts_layer(), maps_layer(read_stream=3),
                  GatedFeedForwardLayer(n_out=D, hidden=48)):
        assert layer_from_dict(json.loads(json.dumps(layer.to_dict()))) == layer
    v = hc.HyperConnectionVertex(op="write", n_streams=2)
    assert vertex_from_dict(json.loads(json.dumps(v.to_dict()))) == v
    assert roles_for(latent_layer())["W_o"] == "attention_out"
    assert roles_for(GatedFeedForwardLayer())["W_gate"] == "ffn_up"
    assert roles_for(experts_layer())["Ws_gate"] == "ffn_up"
    assert maps_layer().FLOAT32_PARAMS == ("P", "a", "b")
    out = latent_layer().get_output_type(InputType.recurrent(D, 9))
    assert (out.kind, out.size, out.timesteps) == ("rnn", D, 9)


# ------------------------------------------------------------ the whole model
def tiny_net(seed=7, **over):
    return REF.build(dict(TINY, **over), seed)


def tiny_batches(slots=3, batch=2):
    return REF.make_batches(TINY, {"slots": slots, "seq_len": 16}, 3, batch)


def test_the_builder_takes_depth_sizes_and_the_shares_as_arguments():
    assert sublayer_kinds(1, 4) == "ADAEAEAEAE"
    conf = xing4_conf(1, 2, hidden_size=16, vocab_size=32,
                      num_attention_heads=4, heads_held=(2, 2),
                      q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=4,
                      qk_rope_head_dim=2, v_head_dim=4,
                      rope_scaling=PUBLISHED["rope_scaling"],
                      intermediate_size=24, n_routed_experts=8,
                      experts_held=(4, 2), moe_intermediate_size=8,
                      hc_mult=2, dtype="bfloat16", remat=True)
    names = [n for n in conf.vertices if n.startswith("b")]
    assert names[:5] == ["b0H_maps", "b0H_pre", "b0A_mixer", "b0H_post",
                         "b1H_maps"]
    assert [n for n in names if n.endswith("_mixer")] == [
        "b0A_mixer", "b1D_mixer", "b2A_mixer", "b3E_mixer", "b4A_mixer",
        "b5E_mixer"]
    # the expert blocks' reader takes b<i>E_ and no hyper-connection piece
    expert = re.compile(r"^b\d+E_")
    assert [n for n in names if expert.match(n)] == ["b3E_mixer",
                                                     "b5E_mixer"]
    mla = conf.vertices["b2A_mixer"].layer
    assert mla.held == (2, 2) and mla.rope_factor == 64 and mla.n_heads == 4
    moe = conf.vertices["b3E_mixer"].layer
    assert moe.held == (4, 2) and moe.gated and moe.top_k == 4
    assert conf.vertices["b3H_maps"].layer.read_stream == 3
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    net = ComputationGraph(conf).init()
    assert net.params["b3E_mixer"]["W_gate"].shape == (2, 16, 8)
    assert net.params["b0H_maps"]["P"].shape == (32, 8)
    assert set(net.params["b0H_pre"]) == {"gamma"}
    assert net.params["embed"]["W"].shape == (32, 16)
    with pytest.raises(ValueError, match="at least one layer"):
        xing4_conf(0, 0)
    with pytest.raises(ValueError, match="only 'yarn'"):
        xing4_conf(1, 1, rope_scaling={"type": "linear", "factor": 2})


def test_the_configuration_file_holds_every_published_width():
    c = PUBLISHED
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"]) \
        == (3584, 768, 512)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]) \
        == (128, 64, 128)
    assert (c["intermediate_size"], c["moe_intermediate_size"]) == (9216, 1024)
    assert (c["router_width"], c["num_experts_per_tok"]) == (64, 4)
    assert (c["hc_mult"], c["hc_sinkhorn_iters"]) == (4, 20)
    assert c["rope_scaling"]["factor"] == 64
    assert c["rope_scaling"]["original_max_position_embeddings"] == 4096
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(e for e in manifest["configs"] if e["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(c["published"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "num_attention_heads", "vocab_size", "num_nextn_predict_layers"])
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):      # every other key as the catalog has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert c["published"][key] == value
            else:
                assert c[key] == value, key
    # 656M parameters: what the cell's sizing says
    kw = REF.builder_kwargs(c)
    assert (kw["n_dense"], kw["n_expert"], kw["heads_held"],
            kw["experts_held"]) == (1, 4, (0, 4), (0, 8))
    met = REF.params_a_token_meets(c)
    assert met["A"] == pytest.approx(7.77e6, rel=2e-3)
    assert met["D"] == 3 * 3584 * 9216 and met["head"] == 3584 * 16384
    assert REF.model_flops_per_sample(c) == pytest.approx(1.76e9, rel=0.03)


WHOLE = ["b0H_maps", "b0H_pre", "b0A_mixer", "b1D_mixer", "b2H_maps",
         "b3E_mixer", "b8A_mixer", "b9H_maps", "b9E_mixer", "norm_f", "head",
         "embed"]


@pytest.fixture(scope="module")
def whole_model():
    ks.reset()
    net = tiny_net()
    xs, ys = tiny_batches()

    def plain(p):
        h = REF.reference_hidden(p, xs[0], TINY)
        return jnp.mean(REF.reference_token_losses(
            jnp.asarray(p["head"]["W"], jnp.float32), h, ys[0]))

    want = reference(jax.grad(plain), f32(dict(net.params)))
    got = jax.grad(lambda p: net.loss_fn(p, [xs[0]], [ys[0]], train=True))(
        net.params)
    return net, xs, ys, got, want


def test_whole_model_loss_matches_the_plain_reference(whole_model):
    net, xs, ys, _, _ = whole_model
    ref_loss = REF.reference_loss(net.params, net.state, xs[0], ys[0], TINY)
    loss = float(net.loss_fn(net.params, [xs[0]], [ys[0]], train=True))
    assert loss == pytest.approx(ref_loss, rel=2e-5)
    assert abs(loss - REF.expected_first_loss(TINY)) < 0.1 * loss


@pytest.mark.parametrize("vertex", WHOLE)
def test_whole_model_gradients_match_the_plain_reference(whole_model, vertex):
    _, _, _, got, want = whole_model
    for name, g in want[vertex].items():
        if name == "e_bias":          # selects only: no gradient
            assert float(jnp.max(jnp.abs(got[vertex][name]))) == 0.0
        else:
            close(got[vertex][name], g, 2e-3)


def test_fit_on_device_trains_remats_and_publishes_the_counters():
    from deeplearning4j_tpu.telemetry import get_registry
    from deeplearning4j_tpu.telemetry.device import LAYER_COUNTER_FAMILY

    def counted():
        fam = get_registry().snapshot().get(LAYER_COUNTER_FAMILY,
                                            {"values": []})
        return {(r["labels"]["layer"], r["labels"]["counter"]): r["value"]
                for r in fam["values"]}

    xs, ys = tiny_batches()
    plain, remat = tiny_net(remat=False), tiny_net(remat=True)
    before = counted()
    a = plain.fit_on_device(xs, ys, steps=3)
    after = counted()
    b = remat.fit_on_device(xs, ys, steps=3)
    np.testing.assert_allclose(a, b, rtol=1e-5)     # remat changes no number
    for x, y in zip(jax.tree_util.tree_leaves(plain.params),
                    jax.tree_util.tree_leaves(remat.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-6)
    added = {k: after[k] - before.get(k, 0.0) for k in after}
    for layer in ("b3E_mixer", "b5E_mixer", "b7E_mixer", "b9E_mixer"):
        assert added[(layer, "tokens")] == 3 * 2 * 16
        assert added[(layer, "rows_dropped")] == 0
        assert 0 < added[(layer, "rows_fullest")] <= added[(layer, "rows_held")]


def test_reference_gradients_sublayer_by_sublayer_equal_the_whole():
    """The plain reference's gradient is computed a sublayer at a time (to
    fit beside the net on the chip): the same numbers as differentiating its
    loss in one piece."""
    net = tiny_net()
    xs, ys = tiny_batches()
    vertices = ["b0A_mixer", "b0H_maps", "b1D_mixer", "b3E_mixer", "norm_f",
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
    assert list(got) == vertices
    assert set(got["b0H_maps"]) == {"P"}    # gates and offsets: left out
    for v in vertices:
        for name, g in got[v].items():
            close(g, want[v][name], 1e-5)
