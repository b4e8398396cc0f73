"""The seq-fused LSTM kernels compiled by the TPU's own compiler for a v5e
that is described, not attached: what interpret mode cannot show (Mosaic's
tiling rules, the VMEM a kernel may use against the ``vmem_limit_bytes`` the
kernels reckon for themselves). Nothing runs, so nothing here is a time.

All such compiles live in this one file and describe the topology inside a
fixture: only the worker that is given the file loads the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("T,B,H,dtype,masked,block", [
    (256, 64, 512, "bfloat16", False, 8),   # charrnn_train_1chip's layers
    (256, 64, 512, "bfloat16", True, 8),    # a padded batch of them
    (256, 64, 512, "float32", False, 8),    # chip_smoke's f32: 43 MiB of VMEM
    (50, 32, 256, "bfloat16", False, 5),    # a TBPTT segment
    (251, 64, 512, "bfloat16", False, 1),   # prime T: one step a grid step
])
def test_seq_kernels_compile_for_the_v5e_within_their_own_vmem_limit(
        one_chip, monkeypatch, T, B, H, dtype, masked, block):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert pk._seq_time_block(T, B, H, dt.itemsize) == block
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s(T, B, 4 * H), s(B, H), s(B, H), s(H, 4 * H), s(H), s(H), s(H))
    mask = s(T, B, 1)

    def fused(mask, *a):
        if masked:
            return pk.fused_lstm_sequence_masked(a[0], mask, *a[1:],
                                                 "tanh", "sigmoid")
        return pk.fused_lstm_sequence(*a, "tanh", "sigmoid")

    def loss(mask, *a):
        ys, hT, cT = fused(mask, *a)
        return (jnp.sum(ys.astype(jnp.float32) ** 2)
                + jnp.sum(hT.astype(jnp.float32))
                + jnp.sum(jnp.tanh(cT.astype(jnp.float32))))

    grad = jax.jit(jax.grad(loss, argnums=tuple(range(1, 8))))
    # the suite runs under x64 (conftest), the chip's programs do not: a
    # 64-bit block index is nothing Mosaic lowers
    with jax.enable_x64(False):
        text = grad.lower(mask, *args).compile().as_text()
        lean = jax.jit(fused).lower(mask, *args).compile().as_text()
    prefix = "lstm_seq_masked_" if masked else "lstm_seq_"
    assert text.count("tpu_custom_call") == 2
    assert prefix + "fwd" in text and prefix + "bwd" in text
    assert lean.count("tpu_custom_call") == 1 and "lstm_seq_lean" in lean
