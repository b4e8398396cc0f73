"""Flash-attention Pallas kernel vs the XLA reference implementation.

Interpret mode (CPU) runs the identical kernel code; numerics are compared
against parallel.ring_attention.attention (itself gradient-checked)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.flash_attention import (causal_key_tiles,
                                                    causal_query_tiles,
                                                    default_blocks,
                                                    flash_attention,
                                                    tiles_walked_share)
from deeplearning4j_tpu.parallel.ring_attention import attention as _attention


def _qkv(b=2, h=2, t=16, d=8, seed=0, hkv=None, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda heads: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, heads, t, d)), dtype)
    return mk(h), mk(hkv or h), mk(hkv or h)


def attention(q, k, v, **kw):
    """The XLA reference in float32, a shared key/value head repeated for
    its query heads (the flash kernels read it in place)."""
    grp = q.shape[1] // k.shape[1]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    return _attention(q, jnp.repeat(k, grp, axis=1), jnp.repeat(v, grp, axis=1),
                      **kw)


# causal, T, block_q, block_k, query heads, key/value heads: the bounds of
# the tile loops under every shape of tile, T a multiple of neither block,
# and the hybrid cell's sixteen query heads a key/value head
SHAPES = [
    (False, 16, 8, 8, 2, 2),
    (True, 16, 8, 8, 2, 2),
    (True, 200, 64, 128, 2, 2),
    (True, 200, 128, 64, 2, 2),
    (False, 200, 64, 128, 2, 2),
    (True, 72, 8, 24, 2, 2),
    (True, 48, 16, 16, 16, 1),
    (True, 100, None, None, 4, 2),     # the default: one tile of 100
    (True, 300, None, None, 2, 1),     # the default: 128-wide, padded to 384
]
# float32 inputs multiply in float32 as before; bfloat16 inputs go to the
# MXU as they are and ``p``/``ds`` are rounded to bfloat16 for their
# products: 2^-8 a rounding, a few of them in a row
TOLERANCE = {"float32": (1e-5, 1e-4), "bfloat16": (2e-2, 4e-2)}


class TestForward:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal,t,block_q,block_k,h,hkv", SHAPES)
    def test_matches_reference(self, causal, t, block_q, block_k, h, hkv,
                               dtype):
        q, k, v = _qkv(t=t, h=h, hkv=hkv, dtype=jnp.dtype(dtype))
        ref = attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k)
        assert out.dtype == q.dtype and out.shape == q.shape
        tol = TOLERANCE[dtype][0]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=tol, atol=tol)

    def test_key_mask(self):
        q, k, v = _qkv(t=12)
        mask = jnp.asarray(np.tile([1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0], (2, 1)),
                           jnp.float32)
        ref = attention(q, k, v, key_mask=mask)
        out = flash_attention(q, k, v, key_mask=mask, block_q=4, block_k=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_non_divisible_lengths(self):
        """T not a multiple of the block: internal padding + slice."""
        q, k, v = _qkv(t=13)
        ref = attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=4)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_blocks_larger_than_t(self):
        q, k, v = _qkv(t=6)
        ref = attention(q, k, v)
        out = flash_attention(q, k, v, block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def _dot_operand_dtypes(jaxpr):
    """The operand dtypes of every ``dot_general`` under ``jaxpr``, the
    kernels' bodies and their loops included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(tuple(str(v.aval.dtype) for v in eqn.invars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)    # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    found += _dot_operand_dtypes(sub)
    return found


class TestBackward:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal,t,block_q,block_k,h,hkv", SHAPES)
    def test_grads_match_reference(self, causal, t, block_q, block_k, h, hkv,
                                   dtype):
        q, k, v = _qkv(t=t, d=4, h=h, hkv=hkv, dtype=jnp.dtype(dtype))

        def loss_ref(q, k, v):
            return jnp.sum(attention(q, k, v, causal=causal) ** 2)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, block_q=block_q,
                block_k=block_k).astype(jnp.float32) ** 2)

        # the reference's gradient at the same (rounded) inputs, in float32
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
            *(a.astype(jnp.float32) for a in (q, k, v)))
        g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        tol = TOLERANCE[dtype][1]
        for a, b, name in zip(g_fl, g_ref, "qkv"):
            assert a.dtype == q.dtype
            # float32 is held element by element, as ever; a bfloat16
            # gradient is rounded to 2^-8 of its own size, so its absolute
            # term is a share of the largest element
            scale = 1.0 if dtype == "float32" else max(
                1.0, float(np.abs(np.asarray(b)).max()))
            np.testing.assert_allclose(
                np.asarray(a, np.float32) / scale, np.asarray(b) / scale,
                rtol=tol, atol=tol, err_msg=f"d{name} mismatch")

    @pytest.mark.parametrize("dtype,operands", [
        ("bfloat16", {("bfloat16", "bfloat16")}),
        ("float32", {("float32", "float32")}),
    ])
    def test_products_take_the_operands_as_they_arrive(self, dtype, operands):
        """Nine products in the three kernels, none on operands cast up: a
        bfloat16 call hands the MXU bfloat16, a float32 call float32."""
        q, k, v = _qkv(t=32, d=8, dtype=jnp.dtype(dtype))
        grad = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=8, block_k=16).astype(
                jnp.float32) ** 2), argnums=(0, 1, 2))
        dots = _dot_operand_dtypes(jax.make_jaxpr(grad)(q, k, v).jaxpr)
        # the causal loops trace their body twice (on and off the diagonal)
        assert len(dots) == 2 * (2 + 3 + 4)
        assert set(dots) == operands

    def test_grads_with_mask_and_padding(self):
        q, k, v = _qkv(t=10, d=4)
        mask = jnp.asarray(np.tile([1] * 7 + [0] * 3, (2, 1)), jnp.float32)

        def loss_ref(q, k, v):
            return jnp.sum(attention(q, k, v, key_mask=mask) ** 2)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, key_mask=mask,
                                           block_q=4, block_k=4) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_jit_and_value_grad(self):
        q, k, v = _qkv(t=8, d=4)
        f = jax.jit(lambda q, k, v: jnp.mean(
            flash_attention(q, k, v, causal=True, block_q=4, block_k=4)))
        val, grads = jax.value_and_grad(f)(q, k, v)
        assert np.isfinite(float(val))
        assert np.isfinite(np.asarray(grads).sum())


class TestLayerIntegration:
    def test_self_attention_layer_flash_impl_trains(self):
        """attention_impl='flash' produces the same model math as 'xla' and
        trains end-to-end."""
        import numpy as np

        from deeplearning4j_tpu import (
            InputType, MultiLayerConfiguration, MultiLayerNetwork, UpdaterConfig,
        )
        from deeplearning4j_tpu.datasets.iterators import DataSet
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
        from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer

        def build(impl):
            conf = MultiLayerConfiguration(
                layers=[SelfAttentionLayer(n_out=16, n_heads=4, causal=True,
                                           attention_impl=impl),
                        RnnOutputLayer(n_out=5, activation="softmax",
                                       loss="mcxent")],
                input_type=InputType.recurrent(8, 12),
                updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
                seed=0,
            )
            return MultiLayerNetwork(conf).init()

        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 12, 8)).astype(np.float32)
        y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, size=(4, 12))]

        net_x, net_f = build("xla"), build("flash")
        np.testing.assert_allclose(np.asarray(net_f.output(x)),
                                   np.asarray(net_x.output(x)),
                                   rtol=1e-5, atol=1e-5)
        net_f.fit(DataSet(x, y))
        net_x.fit(DataSet(x, y))
        assert np.isfinite(float(net_f._last_loss))
        np.testing.assert_allclose(float(net_f._last_loss),
                                   float(net_x._last_loss), rtol=1e-4)


class TestFullyMaskedRows:
    """Round-3 review finding: fully-masked rows must output 0 (not mean-of-V)
    and leak no gradient — matching the reference's m_safe guard."""

    @pytest.mark.parametrize("block_q,block_k", [(4, 4), (2, 4), (4, 2)])
    def test_causal_with_leading_padding(self, block_q, block_k):
        q, k, v = _qkv(t=8, d=4)
        mask = jnp.asarray(np.tile([0, 0, 1, 1, 1, 1, 1, 1], (2, 1)), jnp.float32)
        ref = attention(q, k, v, causal=True, key_mask=mask)
        out = flash_attention(q, k, v, causal=True, key_mask=mask,
                              block_q=block_q, block_k=block_k)
        # rows 0-1 see only masked keys under the causal triangle -> zeros
        assert not np.asarray(out[:, :, :2, :]).any()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("block_q,block_k", [(4, 4), (2, 4), (4, 2)])
    def test_grads_with_leading_padding(self, block_q, block_k):
        q, k, v = _qkv(t=8, d=4)
        mask = jnp.asarray(np.tile([0, 0, 1, 1, 1, 1, 1, 1], (2, 1)), jnp.float32)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v) ** 2)

        g_ref = jax.grad(loss(lambda q, k, v: attention(
            q, k, v, causal=True, key_mask=mask)), argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, key_mask=mask, block_q=block_q,
            block_k=block_k)), argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(g_fl, g_ref, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{n}")
        # no phantom gradient through masked keys
        assert not np.asarray(g_fl[1][:, :, :2, :]).any()

    @pytest.mark.parametrize("causal,block_q,block_k", [
        (False, 4, 4), (True, 4, 4), (True, 2, 4), (True, 4, 2)])
    def test_all_padding_example_in_batch(self, causal, block_q, block_k):
        q, k, v = _qkv(t=8, d=4)
        mask = jnp.asarray(np.stack([[0] * 8, [1] * 8]), jnp.float32)
        kw = dict(causal=causal, key_mask=mask)
        ref = attention(q, k, v, **kw)
        out = flash_attention(q, k, v, block_q=block_q, block_k=block_k, **kw)
        assert not np.asarray(out[0]).any()  # all-padding example -> zeros
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g_ref = jax.grad(lambda k: jnp.sum(attention(q, k, v, **kw) ** 2))(k)
        g_fl = jax.grad(lambda k: jnp.sum(flash_attention(
            q, k, v, block_q=block_q, block_k=block_k, **kw) ** 2))(k)
        np.testing.assert_allclose(np.asarray(g_fl), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_vmem_budget_falls_back_to_xla(self):
        import importlib

        # ops/__init__ re-exports the function under the submodule's name,
        # shadowing attribute access — resolve the module via importlib
        mod = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
        q, k, v = _qkv(t=16, d=8)
        old = mod._KV_VMEM_BUDGET_BYTES
        try:
            mod._KV_VMEM_BUDGET_BYTES = 1  # force the guard
            out = mod.flash_attention(q, k, v, causal=True)
        finally:
            mod._KV_VMEM_BUDGET_BYTES = old
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestTileBounds:
    """The function the kernels take their loop bounds from, against the
    mask it replaces: a tile it leaves out has ``rows >= cols`` false
    everywhere, a tile it passes without a mask has it true everywhere."""

    @pytest.mark.parametrize("t,block_q,block_k", [
        (64, 8, 8), (96, 8, 24), (96, 24, 8), (128, 64, 128), (128, 128, 64),
        (120, 40, 24), (8, 8, 8)])
    def test_every_tile_pair_is_classed_as_the_mask_would(self, t, block_q,
                                                           block_k):
        nq, nk = t // block_q, t // block_k
        rows, cols = np.arange(t)[:, None], np.arange(t)[None, :]
        keep = rows >= cols

        def tile(i, j):
            return keep[i * block_q:(i + 1) * block_q,
                        j * block_k:(j + 1) * block_k]

        by_query = by_key = 0
        for i in range(nq):
            full, end = causal_key_tiles(i * block_q, block_q, block_k)
            assert 0 <= full <= end <= nk
            by_query += end
            for j in range(nk):
                if j >= end:
                    assert not tile(i, j).any()
                elif j < full:
                    assert tile(i, j).all()
                else:   # the diagonal crosses it: the mask is needed
                    assert tile(i, j).any() and not tile(i, j).all()
        for j in range(nk):
            start, full = causal_query_tiles(j * block_k, block_q, block_k)
            assert 0 <= start <= full <= nq
            by_key += nq - start
            for i in range(nq):
                if i < start:
                    assert not tile(i, j).any()
                elif i >= full:
                    assert tile(i, j).all()
                else:
                    assert tile(i, j).any() and not tile(i, j).all()
        # both loops visit the same tile pairs, and the counter counts them
        assert by_query == by_key
        assert tiles_walked_share(t, block_q, block_k, True) == pytest.approx(
            by_query / (nq * nk))
        assert tiles_walked_share(t, block_q, block_k, False) == 1.0

    def test_share_and_default_tiles_at_the_hybrid_cells_shape(self):
        # T = 8192, head size 128, bfloat16: (n + 1) / 2n for n tiles a side
        assert tiles_walked_share(8192, 128, 128, True) == pytest.approx(
            65 / 128)
        block_q, block_k = default_blocks(8192, 128, 2)
        assert block_q == block_k and 8192 % block_q == 0
        n = 8192 // block_q
        assert tiles_walked_share(8192, block_q, block_k, True) \
            == pytest.approx((n + 1) / (2 * n))
        # a length short of the blocks' multiple is counted as it is padded
        assert tiles_walked_share(13, 8, 4, True) == tiles_walked_share(
            16, 8, 4, True)
        # a short sequence is one tile; tiles never pad further than
        # 128-wide ones would
        assert default_blocks(100, 64, 2) == (100, 100)
        for t in (129, 300, 600, 1000, 4096):
            block = default_blocks(t, 64, 2)[0]
            assert block % 128 == 0 and -(-t // block) * block \
                == -(-t // 128) * 128
