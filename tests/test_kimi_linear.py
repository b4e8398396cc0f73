"""The Kimi Delta Attention / position-free latent attention / gated-expert
model (``models/kimi_linear.py``) and what it is built from: the chunked
gated delta rule (``ops/kda.py``) against the recurrence one position at a
time, also where a chunk's summed log-decay is far under float32's exponent
range; the layers and the whole model against the plain reference of
``benchmarks/configs/kimi_linear_48b_a3b.py`` at the tiny preset sizes,
seeded weights; the 32 shares of an expert layer adding up to the uncut
layer; and the layers this model shares with the two other drawn
configurations tracing to what they traced to before."""

import hashlib
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
from deeplearning4j_tpu.models.kimi_linear import (kimi_linear_conf,  # noqa: E402
                                                   sublayer_kinds)
from deeplearning4j_tpu.nn.conf.computation_graph import \
    ComputationGraphConfiguration  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers.attention import LatentAttentionLayer  # noqa: E402
from deeplearning4j_tpu.nn.layers.base import layer_from_dict  # noqa: E402
from deeplearning4j_tpu.nn.layers.linear_attention import \
    KimiDeltaAttentionLayer  # noqa: E402
from deeplearning4j_tpu.nn.layers.moe import DroplessExpertsLayer  # noqa: E402
from deeplearning4j_tpu.nn.layers.state_space import (Mamba2Layer,  # noqa: E402
                                                      causal_depthwise_conv)
from deeplearning4j_tpu.ops import kda  # noqa: E402
from deeplearning4j_tpu.ops import kernel_select as ks  # noqa: E402

CONFIG = "kimi_linear_48b_a3b"
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


# ------------------------------------------------------------ the recurrence
def delta_inputs(T, decay, beta_shift=0.0, B=2, H=2, K=8, V=8, seed=0,
                 dtype=jnp.float64):
    """Unit ``q`` and ``k``, ``g = -decay * (0.5 + exp(normal))`` a channel,
    ``beta = sigmoid(normal + beta_shift)``."""
    ks_ = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks_[0], (B, T, H, K), dtype))
    k = unit(jax.random.normal(ks_[1], (B, T, H, K), dtype))
    v = jax.random.normal(ks_[2], (B, T, H, V), dtype)
    g = -decay * (0.5 + jnp.exp(jax.random.normal(ks_[3], (B, T, H, K),
                                                  dtype)))
    beta = jax.nn.sigmoid(jax.random.normal(ks_[4], (B, T, H), dtype)
                          + beta_shift)
    return q, k, v, g, beta


# mild decays; decays of at least -30 a position and channel, so that even a
# chunk of 4 sums to under -120 (the overflow trap: exp(+120) is no float32),
# with beta near 1
DECAYS = {"mild": (0.1, 0.0), "strong": (60.0, 6.0)}


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("case", sorted(DECAYS))
def test_chunked_delta_rule_matches_the_recurrence(case, chunk, dtype):
    decay, shift = DECAYS[case]
    T = 70                        # no multiple of 4, 16 or 64: a padded tail
    args = delta_inputs(T, decay, shift, dtype=jnp.dtype(dtype))
    if case == "strong":
        per_chunk = jnp.sum(args[3].reshape(2, T, -1)[:, :chunk], 1)
        assert float(jnp.max(per_chunk)) < -100.0
        assert float(jnp.mean(args[4])) > 0.95
    tol = 1e-10 if dtype == "float64" else 5e-5
    want = kda.kda_reference(*args, scale=0.5)
    got = kda.kda_chunked(*args, chunk=chunk, scale=0.5)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, want, tol)
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape, want.dtype)
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4))(
        *args) for fn in (
            lambda *a: kda.kda_reference(*a, scale=0.5),
            lambda *a: kda.kda_chunked(*a, chunk=chunk, scale=0.5))]
    for want_g, got_g in zip(*grads):       # q, k, v, g, beta
        assert bool(jnp.all(jnp.isfinite(got_g)))
        close(got_g, want_g, 20 * tol)


def test_no_exponent_that_the_chunked_form_takes_is_positive(monkeypatch):
    """Every ``exp`` of the chunk equations is of a difference ``<= 0``:
    under decays of -400 a chunk the largest argument seen is 0."""
    seen = []
    real = jnp.exp

    def watched(a):      # traced under jax.checkpoint: read when it runs
        jax.debug.callback(lambda m: seen.append(float(m)), jnp.max(a))
        return real(a)

    args = delta_inputs(64, 60.0, 6.0, dtype=jnp.float32)
    monkeypatch.setattr(kda.jnp, "exp", watched)    # jax.numpy's own
    out = kda.kda_chunked(*args, chunk=32)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert len(seen) >= 5 and max(seen) <= 0.0


@pytest.mark.parametrize("C,c", [(16, 16), (64, 16), (4, 4), (32, 8)])
def test_substitution_and_merges_invert_a_unit_lower_triangular_matrix(C, c):
    A = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, C, C)), -1)
    inv = kda._inverse_of_unit_lower(A, c)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("bij,bjk->bik", jnp.eye(C) + A, inv)),
        np.broadcast_to(np.eye(C), (3, C, C)), atol=1e-6)
    with pytest.raises(ValueError, match="power of two"):
        kda._inverse_of_unit_lower(jnp.zeros((48, 48)), 16)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("align", [0.5, 1.0])
def test_keys_that_are_nearly_alike_do_not_break_the_chunked_form(align, chunk):
    """What training makes of the keys: those that follow each other nearly
    alike, ``beta`` near 1, hardly any decay, so ``A``'s entries are near 1.
    (A power series for ``(I + A)^-1`` returned NaN here at 64 rows, and the
    whole model's loss went NaN within twenty steps on the chip.)"""
    ks_ = jax.random.split(jax.random.PRNGKey(0), 6)
    shape, f = (1, 256, 2, 16), jnp.float32
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    base = jax.random.normal(ks_[5], (1, 1, 2, 16), f)
    k = unit(align * base + (1 - align) * jax.random.normal(ks_[1], shape, f))
    q = unit(align * base + (1 - align) * jax.random.normal(ks_[0], shape, f))
    v = jax.random.normal(ks_[2], shape, f)
    g = -0.001 * jnp.exp(jax.random.normal(ks_[3], shape, f))
    beta = jax.nn.sigmoid(jax.random.normal(ks_[4], shape[:3], f) + 5.0)
    want = kda.kda_reference(q, k, v, g, beta)
    got = kda.kda_chunked(q, k, v, g, beta, chunk=chunk)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, want, 2e-4)
    grads = [jax.grad(lambda k: jnp.sum(jnp.sin(fn(q, k, v, g, beta))))(k)
             for fn in (kda.kda_reference,
                        lambda *a: kda.kda_chunked(*a, chunk=chunk))]
    close(grads[1], grads[0], 2e-3)


def test_the_site_records_the_chunk_and_the_shapes_once():
    args = delta_inputs(20, 0.1, dtype=jnp.float32)
    jax.jit(lambda *a: kda.kda_recurrence(*a, chunk=8))(*args)
    jax.jit(lambda *a: kda.kda_recurrence(*a, chunk=8))(*args)
    (rec,) = [r for r in ks.selection_log() if r["site"] == "kda_recurrence"]
    assert rec["variant"] == "reference" and rec["chunk"] == 8
    assert rec["scores"] == "jnp"
    assert rec["ctx"] == {"B": 2, "T": 20, "H": 2, "K": 8, "V": 8,
                          "chunk": 8, "itemsize": 4}
    # its jax.numpy under every mode: the kernels do not tile K = 8
    for mode in ("fused", "reference"):
        with ks.forced_mode(mode):
            assert ks.select("kda_recurrence", rec["ctx"]) == "reference"


# ----------------------------------------------- the kernels (interpret mode)
def fused_inputs(case, dtype):
    """``(q, k, v, g, beta)`` at the widths the kernels tile (K = V = 128)
    for one case of the recurrence; ``q``, ``k``, ``v`` in ``dtype``, ``g``
    and ``beta`` float32, as a layer hands them over."""
    T, B, H, decay, shift = {
        "several chunks": (192, 1, 2, 0.1, 0.0),
        "one chunk": (64, 1, 1, 0.1, 0.0),
        "a padded tail": (100, 1, 1, 0.1, 0.0),
        "strong decays": (128, 1, 1, 3.0, 4.0),
        "no decay": (128, 1, 1, 0.0, 0.0),
        "aligned keys": (84, 1, 1, 0.001, 5.0),
        "a masked position": (128, 1, 1, 0.1, 0.0),
        "two sequences": (128, 2, 1, 0.1, 0.0),
    }[case]
    q, k, v, g, beta = delta_inputs(T, decay, shift, B=B, H=H, K=128, V=128,
                                    dtype=jnp.float32)
    if case == "aligned keys":      # 20 updates along nearly one direction
        base = k[:, :1]
        unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
        near = unit(base + 0.05 * k)
        k = k.at[:, 64:84].set(near[:, 64:84])
        q = q.at[:, 64:84].set(unit(base + 0.05 * q)[:, 64:84])
    if case == "a masked position":  # as the layer masks: g = 0, beta = 0
        keep = jnp.ones((T,), jnp.float32).at[jnp.array([5, 63, 64, 100])].set(0.0)
        g, beta = g * keep[None, :, None, None], beta * keep[None, :, None]
    cast = lambda a: a.astype(jnp.dtype(dtype))  # noqa: E731
    return cast(q), cast(k), cast(v), g, beta


FUSED_CASES = ["several chunks", "one chunk", "a padded tail",
               "strong decays", "no decay", "aligned keys",
               "a masked position", "two sequences"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", FUSED_CASES)
def test_the_kernels_match_the_recurrence(case, dtype):
    """``kda_fwd`` / ``kda_bwd`` in interpret mode against the recurrence one
    position at a time: ``o`` and the cotangent of every operand."""
    args = fused_inputs(case, dtype)
    if case == "strong decays":     # a chunk's summed log-decay past -88
        assert float(jnp.max(jnp.sum(args[3][:, :64], 1))) < -88.0
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, jnp.float32)
    w = w.astype(args[2].dtype).astype(jnp.float32)   # once, for both sides

    def both(fn, a):
        out, pull = jax.vjp(lambda *b: fn(*b, scale=0.5), *a)
        return (out,) + pull(w.astype(out.dtype))

    want = both(kda.kda_reference, tuple(a.astype(jnp.float32) for a in args))
    got = both(lambda *a, scale: kda.kda_fused(*a, chunk=64, scale=scale),
               args)
    assert got[0].dtype == args[2].dtype and got[4].dtype == jnp.float32
    for name, a, b in zip(("o", "d_q", "d_k", "d_v", "d_g", "d_beta"),
                          got, want):
        # float32 results to rounding; one that comes back in bfloat16 to
        # what rounding it costs (2^-9 of the largest value)
        tol = 6e-3 if a.dtype == jnp.bfloat16 else 1e-4
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))), name
        close(a.astype(jnp.float32), b, tol)
    if case == "a masked position":     # the state passes it unchanged
        q, k, v, g, beta = args
        gone = kda.kda_fused(q, k, v.at[:, 5].set(100.0), g, beta, chunk=64)
        kept = kda.kda_fused(q, k, v, g, beta, chunk=64)
        close(gone[:, 6:].astype(jnp.float32), kept[:, 6:].astype(jnp.float32),
              1e-6)


def scores_inputs(case, dtype, chunk=64):
    """``(q, k, G, beta)`` of one case as :func:`kda.kda_fused` hands them to
    the scores: float32 chunks ``[B, H, N, C, K]``, ``G`` the running sums
    of the log-decays, ``beta`` a row ``[B, H, N, 1, C]``."""
    q, k, v, g, beta = fused_inputs(case, dtype)
    _, q, k, v, g, beta = kda._pad_time(chunk, q, k, v, g, beta)
    Bsz, Tp, H, _ = k.shape

    def chunks(a):
        a = a.astype(jnp.float32).reshape((Bsz, Tp // chunk, chunk)
                                          + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    return (chunks(q), chunks(k), jnp.cumsum(chunks(g), axis=3),
            chunks(beta)[..., None, :])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", FUSED_CASES)
def test_the_score_kernels_match_the_scores_and_their_gradient(case, dtype):
    """``kda_scores_fwd`` / ``kda_scores_bwd`` in interpret mode against
    ``_scores`` (``beta`` on ``A``'s rows, ``scale`` on ``P``) and its
    autodiff: ``beta A``, ``scale P`` and the cotangents of ``q``, ``k``,
    ``G`` and ``beta``, float32 all through (``q`` and ``k`` rounded to
    ``dtype`` first, as the layer hands them over)."""
    args = scores_inputs(case, dtype)
    if case == "strong decays":     # a chunk's summed log-decay past -88
        assert float(jnp.min(args[2][..., -1, :])) < -88.0
    C = args[0].shape[3]

    def plain(q, k, G, beta):
        A, P = kda._scores(q, k, G, kda.SUB)
        return jnp.swapaxes(beta, -1, -2) * A, 0.5 * P

    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    w = tuple(jax.random.normal(key, args[0].shape[:3] + (C, C),
                                jnp.float32) for key in keys)

    def both(fn):
        out, pull = jax.vjp(fn, *args)
        return out + pull(w)

    want = both(plain)
    got = both(lambda *a: kda.kda_scores(*a, 0.5))
    for name, a, b in zip(("beta A", "scale P", "d_q", "d_k", "d_G",
                           "d_beta"), got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        close(a, b, 1e-5)


def test_the_site_takes_the_kernels_where_they_tile():
    """``fused`` at the published head (K = V = 128, chunks of 64) when fused
    variants compete, the jax.numpy at the tests' small shapes, off a lane
    tile and under ``mode == "reference"``; the record carries what
    ``kda_recurrence_roofline.shapes_of`` reads."""
    published = {"B": 1, "T": 8192, "H": 32, "K": 128, "V": 128, "chunk": 64,
                 "itemsize": 2}
    assert ks.select("kda_recurrence", published) == "reference"   # the CPU
    ks.set_force_available(True)
    try:
        assert ks.select("kda_recurrence", published) == "fused"
        for off in ({"K": 8, "V": 8}, {"K": 64}, {"V": 192}, {"chunk": 4},
                    {"chunk": 48}, {"chunk": 96}, {"itemsize": 8}):
            assert ks.select("kda_recurrence",
                             dict(published, **off)) == "reference", off
        with ks.forced_mode("reference"):
            assert ks.select("kda_recurrence", published) == "reference"
        with ks.partitioned_program():
            assert ks.select("kda_recurrence", published) == "reference"
    finally:
        ks.set_force_available(False)
    ks.reset()
    # one chunk is too little for the cost model: asked for, the kernels run
    args = fused_inputs("one chunk", "bfloat16")
    with ks.forced_mode("fused"):
        out = jax.jit(lambda *a: kda.kda_recurrence(*a, chunk=64))(*args)
    close(out.astype(jnp.float32),
          kda.kda_chunked(*args, chunk=64).astype(jnp.float32), 1e-2)
    (rec,) = [r for r in ks.selection_log() if r["site"] == "kda_recurrence"]
    assert rec["variant"] == "fused" and rec["chunk"] == 64
    assert rec["scores"] == "kernel"
    assert rec["ctx"] == {"B": 1, "T": 64, "H": 1, "K": 128, "V": 128,
                          "chunk": 64, "itemsize": 2}


def test_the_fused_program_has_no_scan_over_chunk_maps():
    """No ``[K, K] x [K, K]`` product (the chunk maps ``M`` composed by
    ``lax.associative_scan``) is in the ``fused`` program's text, forward or
    backward; the jax.numpy form, the control, has them. ``V`` is 256 so that
    a product with the state is no such shape."""
    q, k, _, g, beta = delta_inputs(256, 0.1, B=1, H=1, K=128, V=128,
                                    dtype=jnp.float32)
    v = jnp.ones((1, 256, 1, 256), jnp.float32)
    square = re.compile(r"dot_general.*\(tensor<(?:\d+x)*128x128xf32>, "
                        r"tensor<(?:\d+x)*128x128xf32>\)")

    def text(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a, chunk=64)), argnums=(0, 1, 2, 3, 4))
        ).lower(q, k, v, g, beta).as_text()

    assert square.search(text(kda.kda_chunked))
    fused = text(kda.kda_fused)
    assert not square.search(fused) and "dot_general" in fused


def test_the_recurrence_runs_under_its_named_scope():
    args = delta_inputs(16, 0.1, dtype=jnp.float32)
    text = jax.jit(lambda *a: kda.kda_recurrence(*a, chunk=8)).lower(
        *args).as_text(debug_info=True)
    assert "kda_recurrence" in text


# ---------------------------------------------------------------- the layers
def layer_and_gradients_match(layer, ref_fn, sizes, x, key=1, tol=5e-4):
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
        close(got[0][name], want[0][name], tol)
    close(got[1], want[1], tol)
    return params, out


def delta_layer(**kw):
    c = TINY["linear_attn_config"]
    return KimiDeltaAttentionLayer(**{**dict(
        n_out=D, n_heads=c["num_heads"], head_dim=c["head_dim"],
        conv_kernel=c["short_conv_kernel_size"], gate_rank=c["head_dim"],
        chunk=TINY["kda_chunk"], eps=TINY["rms_norm_eps"],
        rescale_layers=27), **kw})


@pytest.mark.parametrize("batch,T,chunk", [(2, 16, 4), (1, 22, 4), (2, 22, 16)])
def test_delta_attention_layer_matches_the_plain_reference(batch, T, chunk):
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, T, D))
    params, _ = layer_and_gradients_match(delta_layer(chunk=chunk),
                                          REF.reference_delta_attention,
                                          TINY, x)
    c = TINY["linear_attn_config"]
    inner = c["num_heads"] * c["head_dim"]
    assert {k: v.shape for k, v in params.items()} == {
        "W_q": (D, inner), "W_k": (D, inner), "W_v": (D, inner),
        "conv_q": (4, inner), "conv_k": (4, inner), "conv_v": (4, inner),
        "W_fa": (D, c["head_dim"]), "W_fb": (c["head_dim"], inner),
        "dt_bias": (inner,), "A_log": (c["num_heads"],),
        "W_beta": (D, c["num_heads"]), "W_ga": (D, c["head_dim"]),
        "W_gb": (c["head_dim"], inner), "b_g": (inner,),
        "norm_w": (c["head_dim"],), "W_o": (inner, D)}


def test_delta_attention_starts_with_the_decays_the_file_says():
    """``A`` uniform in [1, 16], the step log-uniform in [1e-3, 0.1]: the
    log-decay of a position starts between -1.6 and -0.001 a channel."""
    it = InputType.recurrent(D, 8)
    p = delta_layer(n_heads=32, head_dim=64, n_out=D).init_params(
        jax.random.PRNGKey(5), it)
    A = np.exp(np.asarray(p["A_log"]))
    step = np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert 1.0 <= A.min() < 4.0 and 12.0 < A.max() <= 16.0
    assert 1e-3 <= step.min() < 2e-3 and 0.05 < step.max() <= 0.1
    assert float(np.max(np.abs(p["b_g"]))) == 0.0
    assert KimiDeltaAttentionLayer.FLOAT32_PARAMS == ("A_log", "dt_bias")


def test_a_masked_position_leaves_the_state_as_it_found_it():
    """Positions 3 and 4 masked: what follows reads the state position 2
    left (decayed by nothing, corrected by nothing)."""
    layer = delta_layer()
    it = InputType.recurrent(D, 8)
    params = layer.init_params(jax.random.PRNGKey(1), it)
    # no convolution across the gap: one tap, on the position itself
    params = dict(params, **{n: jnp.zeros_like(params[n]).at[-1].set(1.0)
                             for n in ("conv_q", "conv_k", "conv_v")})
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, D))
    mask = jnp.asarray([[1, 1, 1, 0, 0, 1, 1, 1]], jnp.float32)
    masked, _ = layer.apply(params, x, {}, mask=mask)
    cut, _ = layer.apply(params, jnp.delete(x, jnp.asarray([3, 4]), axis=1),
                         {})
    close(jnp.delete(masked, jnp.asarray([3, 4]), axis=1), cut, 1e-6)


def latent_layer(**kw):
    return LatentAttentionLayer(**{**dict(
        n_out=D, n_heads=TINY["num_attention_heads"], q_rank=None,
        kv_rank=TINY["kv_lora_rank"], nope_dim=TINY["qk_nope_head_dim"],
        rope_dim=TINY["qk_rope_head_dim"], v_dim=TINY["v_head_dim"],
        eps=TINY["rms_norm_eps"], rotary=False, rescale_layers=27), **kw})


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_latent_attention_without_positions_matches_the_plain_reference(impl):
    if impl == "flash":          # the kernels in interpret mode
        ks.set_force_available(True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, D))
    params, _ = layer_and_gradients_match(
        latent_layer(attention_impl=impl), REF.reference_latent_attention,
        TINY, x)
    assert set(params) == {"W_q", "W_kva", "kv_norm", "W_kvb", "W_o"}
    heads = TINY["num_attention_heads"]
    assert params["W_q"].shape == (D, heads * (8 + 4))
    variants = {r["variant"] for r in ks.selection_log()
                if r["site"] == "attention"}
    assert variants == {impl}


def test_without_rotary_no_position_enters_the_layer():
    """Keys and values at earlier positions in another order: a causal
    layer's last output does not move (with the rotary embedding it does)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, D))
    swapped = x[:, jnp.asarray([2, 0, 1, 4, 3, 5])]
    it = InputType.recurrent(D, 6)
    for rotary, same in ((False, True), (True, False)):
        layer = latent_layer(rotary=rotary)
        params = layer.init_params(jax.random.PRNGKey(1), it)
        a = layer.apply(params, x, {})[0][:, -1]
        b = layer.apply(params, swapped, {})[0][:, -1]
        assert bool(np.allclose(a, b, rtol=1e-9, atol=1e-12)) is same
    assert latent_layer().softmax_scale == pytest.approx(12 ** -0.5)


# the traced program (forward and backward, float32, the tiny presets' shapes)
# of the layers this PR edits and the two other drawn configurations run:
# the text of the jaxpr, hashed on the parent commit (bc04d9b). An edit that
# changes what xing4's latent attention (a query rank, rotary on) or the
# Mamba-2 mixer (the convolution with its bias) lowers to changes these.
def xing4_latent_layer():
    rs = {"factor": 4, "original_max_position_embeddings": 8}
    return LatentAttentionLayer(
        n_out=32, n_heads=4, heads_held_first=0, heads_held_count=2,
        q_rank=16, kv_rank=12, nope_dim=8, rope_dim=4, v_dim=8, eps=1e-6,
        rope_theta=10000, rope_factor=rs["factor"],
        rope_original_positions=rs["original_max_position_embeddings"],
        rope_beta_fast=32, rope_beta_slow=1, rope_mscale=1,
        rope_mscale_all_dim=1, rescale_layers=40)


UNCHANGED_PROGRAMS = [
    ("xing4_latent", xing4_latent_layer,
     "9d069734f17eef763a9c0d1e4afc82f1f6bbb13d8c2047051282c6f4bb767413"),
    ("mamba2", lambda: Mamba2Layer(n_out=32, n_heads=4, head_dim=8,
                                   n_groups=2, state_size=8, conv_kernel=4,
                                   chunk_size=8),
     "1369a65074f313f6edc3aff514890cd6d7a4a6b0c8669c6fdf0f2be2e2fc8a42"),
]


@pytest.mark.parametrize("name,make,digest", UNCHANGED_PROGRAMS)
def test_a_shared_layer_traces_to_the_program_it_traced_to_before(
        name, make, digest):
    layer = make()
    with jax.enable_x64(False):
        it = InputType.recurrent(32, 16)
        params = jax.eval_shape(
            lambda: layer.init_params(jax.random.PRNGKey(1), it))
        x = jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)

        def both_ways(p, x):
            out, pull = jax.vjp(lambda p, x: layer.apply(p, x, {})[0], p, x)
            return out, pull(out)

        text = re.sub(r"0x[0-9a-f]+", "0x",
                      str(jax.make_jaxpr(both_ways)(params, x)))
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_the_convolution_takes_no_bias_or_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    b = jax.random.normal(jax.random.PRNGKey(2), (6,))
    close(causal_depthwise_conv(x, w), REF._short_conv(x, w), 1e-12)
    close(causal_depthwise_conv(x, w, b), REF._short_conv(x, w) + b, 1e-12)


def test_the_32_shares_of_an_expert_layer_make_the_uncut_layer():
    """Top-8 of 64 experts in 32 shares of 2, each with its slices of the
    three stacks, and the shared expert counted once, add up to the uncut
    layer (the deployment's split at a narrower router)."""
    sizes = dict(TINY, router_width=64, num_experts=64, experts_held_first=0,
                 num_experts_per_token=8)

    def layer(first, count):
        return DroplessExpertsLayer(
            n_out=D, n_experts=64, top_k=8, hidden=16, shared_hidden=16,
            experts_held_first=first, experts_held_count=count,
            routed_scaling=TINY["routed_scaling_factor"], norm_topk_prob=True,
            expert_activation="silu", gated=True, rescale_layers=27)

    whole = layer(0, 64)
    params = whole.init_params(jax.random.PRNGKey(1),
                               InputType.recurrent(D, 12))
    tokens = jax.random.normal(jax.random.PRNGKey(2), (24, D))
    total, rows = whole.shared(params, tokens), 0
    for share in range(32):
        cut = slice(2 * share, 2 * share + 2)
        mine = dict(params, W_gate=params["W_gate"][cut],
                    W_up=params["W_up"][cut], W_down=params["W_down"][cut])
        y, counters = layer(2 * share, 2).routed(mine, tokens)
        total, rows = total + y, rows + int(counters[0])
    assert rows == 24 * 8          # every pick lands on exactly one share
    close(total, reference(REF.reference_experts, f32(params), f32(tokens),
                           sizes))


def test_new_layers_declare_types_roles_and_round_trip():
    from deeplearning4j_tpu.parallel.roles import roles_for

    for layer in (delta_layer(), latent_layer()):
        assert layer_from_dict(json.loads(json.dumps(layer.to_dict()))) == layer
    assert roles_for(delta_layer())["W_o"] == "attention_out"
    assert roles_for(delta_layer())["W_k"] == "attention_qkv"
    assert roles_for(latent_layer())["W_q"] == "attention_qkv"
    out = delta_layer().get_output_type(InputType.recurrent(D, 9))
    assert (out.kind, out.size, out.timesteps) == ("rnn", D, 9)
    with pytest.raises(ValueError, match="keeps the width"):
        delta_layer().init_params(jax.random.PRNGKey(0),
                                  InputType.recurrent(D + 1, 4))


# ------------------------------------------------------------ the whole model
def tiny_net(seed=7, **over):
    return REF.build(dict(TINY, **over), seed)


def tiny_batches(slots=3, batch=2):
    return REF.make_batches(TINY, {"slots": slots, "seq_len": 16}, 3, batch)


def test_the_builder_takes_the_mixers_sizes_and_the_share_as_arguments():
    assert sublayer_kinds("KKKAK", 1) == "KDKEKEAEKE"
    conf = kimi_linear_conf("KAK", 1, hidden_size=16, vocab_size=32,
                            num_heads=2, head_dim=4, chunk=4,
                            num_attention_heads=2, kv_lora_rank=8,
                            qk_nope_head_dim=4, qk_rope_head_dim=2,
                            v_head_dim=4, intermediate_size=24,
                            num_experts=8, num_experts_per_token=2,
                            experts_held=(4, 2), moe_intermediate_size=8,
                            dtype="bfloat16", remat=True)
    names = [n for n in conf.vertices if n.startswith("b")]
    assert names[:4] == ["b0K_norm", "b0K_mixer", "b0K_add", "b1D_norm"]
    assert [n for n in names if n.endswith("_mixer")] == [
        "b0K_mixer", "b1D_mixer", "b2A_mixer", "b3E_mixer", "b4K_mixer",
        "b5E_mixer"]
    # the expert blocks' reader takes b<i>E_ and the new reader b<i>K_, and
    # neither another kind's vertex
    for letter, want in (("E", ["b3E", "b5E"]), ("K", ["b0K", "b4K"])):
        kind = re.compile(rf"^b\d+{letter}_")
        assert sorted({n[:3] for n in names if kind.match(n)}) == want
    mla = conf.vertices["b2A_mixer"].layer
    assert mla.q_rank is None and not mla.rotary and mla.held == (0, 2)
    kda_layer = conf.vertices["b4K_mixer"].layer
    assert (kda_layer.n_heads, kda_layer.head_dim, kda_layer.chunk,
            kda_layer.gate_rank) == (2, 4, 4, 4)
    moe = conf.vertices["b3E_mixer"].layer
    assert moe.held == (4, 2) and moe.gated and moe.top_k == 2
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    net = ComputationGraph(conf).init()
    assert net.params["b3E_mixer"]["W_gate"].shape == (2, 16, 8)
    assert net.params["b2A_mixer"]["W_q"].shape == (16, 2 * 6)
    assert net.params["embed"]["W"].shape == (32, 16)
    with pytest.raises(ValueError, match="'K' or 'A'"):
        kimi_linear_conf("KM")
    with pytest.raises(ValueError, match="dense layers"):
        kimi_linear_conf("KA", 3)


def test_the_configuration_file_holds_every_published_width():
    c = PUBLISHED
    assert (c["hidden_size"], c["kv_lora_rank"], c["q_lora_rank"]) \
        == (2304, 512, None)
    assert (c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]) \
        == (128, 64, 128)
    assert (c["intermediate_size"], c["moe_intermediate_size"]) == (9216, 1024)
    assert (c["router_width"], c["num_experts_per_token"]) == (256, 8)
    kda_sizes = c["linear_attn_config"]
    assert (kda_sizes["num_heads"], kda_sizes["head_dim"],
            kda_sizes["short_conv_kernel_size"]) == (32, 128, 4)
    # the five layers run are the published first five
    assert [("K" if i in kda_sizes["kda_layers"] else "A")
            for i in range(1, 6)] == list(c["mixers_run"])
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entry = next(e for e in manifest["configs"] if e["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(c["published"]) == sorted([
        "num_hidden_layers", "num_experts", "vocab_size"])
    assert entry["source"] == c["source"] and len(c["source"]) <= 200
    for key in ("deployment", "assumed", "published"):
        assert c[key]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):      # every other key as the catalog has it
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert c["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key in entry["reduced"]:
                assert c["published"][key] == value
            else:
                assert c[key] == value, key
    # 602.45M parameters: the program's own count, from the shapes
    kw = REF.builder_kwargs(c)
    assert (kw["mixers"], kw["n_dense"], kw["experts_held"],
            kw["num_experts"]) == ("KKKAK", 1, (0, 8), 256)
    conf = kimi_linear_conf(**kw)
    shapes = jax.eval_shape(lambda: ComputationGraph(conf).init().params)
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 602_450_816
    met = REF.params_a_token_meets(c)
    assert met["K"] == pytest.approx(39.5e6, rel=2e-3)
    assert met["A"] == pytest.approx(29.1e6, rel=2e-3)
    assert met["D"] == 3 * 2304 * 9216 and met["head"] == 2304 * 20480
    assert met["E"] == pytest.approx(9.44e6, rel=2e-3)
    assert REF.model_flops_per_sample(c) == pytest.approx(2.32e9, rel=0.01)


WHOLE = ["b0K_norm", "b0K_mixer", "b1D_mixer", "b2K_mixer", "b3E_mixer",
         "b6A_mixer", "b7E_mixer", "b8K_mixer", "b9E_mixer", "norm_f", "head",
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
    sites = {r["site"]: r["variant"] for r in ks.selection_log()}
    assert sites["kda_recurrence"] == "reference"


def test_reference_gradients_sublayer_by_sublayer_equal_the_whole():
    """The plain reference's gradient is computed a sublayer at a time (to
    fit beside the net on the chip): the same numbers as differentiating its
    loss in one piece, with the parameters of a few numbers left out."""
    net = tiny_net()
    xs, ys = tiny_batches()
    vertices = ["b0K_mixer", "b6A_mixer", "b1D_mixer", "b3E_mixer", "norm_f",
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
    assert set(net.params["b0K_mixer"]) - set(got["b0K_mixer"]) \
        == set(REF.NOT_SAMPLED)
    # of the expert sublayer: the shared expert, not what the router chooses
    assert set(got["b3E_mixer"]) == {"Ws_gate", "Ws_up", "Ws_down", "e_bias"}
    assert set(got["b1D_mixer"]) == {"W_gate", "W_up", "W_down"}
    for v in vertices:
        for name, g in got[v].items():
            close(g, want[v][name], 1e-5)


def test_reference_gradients_hold_the_recurrence_alone(monkeypatch, capsys):
    """On its way the plain reference runs the program's recurrence on the
    first delta-rule sublayer's operands against its own delta rule: the
    readings go to standard error, and a recurrence whose running sums,
    solved system and states are rounded to bfloat16 stops the run (at these
    few positions it is off by 2e-3; the limit here lies under that, the
    cell's under what 8192 positions read)."""
    net = tiny_net()
    xs, ys = tiny_batches()
    monkeypatch.setattr(REF, "RECURRENCE_RTOL", 1e-4)
    REF.reference_gradients(net.params, xs[0], ys[0], TINY, ["norm_f"])
    said = capsys.readouterr().err
    assert "the recurrence of b0K alone off the delta rule: out " in said
    assert said.count("(limit 0.0001)") == 6 and "d_g " in said
    monkeypatch.setattr(
        kda, "_round_state",
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7))
    with pytest.raises(RuntimeError, match="recurrence is off the delta rule"):
        REF.reference_gradients(net.params, xs[0], ys[0], TINY, ["norm_f"])


def test_the_delta_rule_in_segments_is_the_delta_rule():
    """The plain reference's scan in checkpointed segments (with a padded
    last one) against the program's position-by-position recurrence."""
    q, k, v, g, beta = delta_inputs(21, 0.3, dtype=jnp.float32)
    want = kda.kda_reference(q, k, v, g, beta)
    close(REF.delta_rule(q, k, v, g, beta, segment=8), want, 1e-5)


def test_the_adam_kernel_gives_way_beside_the_jax_numpy_recurrence():
    """Where fused kernels compete (a TPU; here: forced), the in-place Adam
    kernel is infeasible in a net that has a delta-rule layer and an expert
    layer (the staged program does not return on the v5e, ``PERF.md`` section
    7 (c)), and the record says so; a net without such a layer keeps the
    kernel. The net reads it off its own layers when it builds its updater,
    so a step that is traced again, in whatever order, resolves the same."""
    ks.set_force_available(True)
    ks.set_mode("fused")    # tiny leaves: scoring alone would keep optax
    xs, ys = tiny_batches(slots=2)
    net = tiny_net()
    net.fit_on_device(xs, ys, steps=1)
    (rec,) = [r for r in ks.selection_log() if r["site"] == "optimizer"]
    assert (rec["variant"], rec["reason"]) == ("reference", "fallback")
    assert rec["infeasible"] == ["fused"]
    assert rec["ctx"]["beside_reference"] == "kda_recurrence"
    # the updater alone, traced with no layer's selection before it
    ks.reset()
    ks.set_force_available(True)
    ks.set_mode("fused")
    jax.eval_shape(lambda g, o, p: net._tx.update(g, o, p), net.params,
                   net.opt_state, net.params)
    (rec,) = [r for r in ks.selection_log() if r["site"] == "optimizer"]
    assert rec["variant"] == "reference"
    assert rec["ctx"]["beside_reference"] == "kda_recurrence"
    ks.reset()
    ks.set_force_available(True)
    ks.set_mode("fused")
    plain = kimi_linear_conf("A", 1, hidden_size=16, vocab_size=32,
                             num_attention_heads=2, kv_lora_rank=8,
                             qk_nope_head_dim=4, qk_rope_head_dim=2,
                             v_head_dim=4, intermediate_size=24)
    ComputationGraph(plain).init().fit_on_device(
        xs[:, :, :8] % 32, ys[:, :, :8] % 32, steps=1)
    (rec,) = [r for r in ks.selection_log() if r["site"] == "optimizer"]
    assert rec["variant"] == "fused" and "beside_reference" not in rec["ctx"]
    ks.set_mode(None)
