"""Pallas helper-tier tests: fused kernels (interpret mode on CPU) must match
pure-XLA math in value AND gradient — the same role the reference's
CuDNNGradientChecks played for its cuDNN helpers (SURVEY.md §4.1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import ops
from deeplearning4j_tpu.ops.pallas_kernels import (
    _ACT,
    _cell_math,
    _window_sum,
    fused_lrn,
    fused_lstm_cell,
)


@pytest.fixture(autouse=True)
def _no_selection_outlives_its_test():
    """Tests here force kernels through DL4J_TPU_PALLAS; the selection log is
    the process's, and the benchmark's rehearsals count its fused sites."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    yield
    ks.reset()


def _cell_inputs(seed=0, B=4, H=8):
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.normal(size=s) * 0.5, jnp.float32)  # noqa: E731
    return (r(B, 4 * H), r(B, H), r(B, H), r(H, 4 * H), r(H), r(H), r(H))


@pytest.mark.parametrize("act,gate", [("tanh", "sigmoid"), ("tanh", "hardsigmoid")])
def test_fused_lstm_cell_forward_matches_xla(act, gate):
    args = _cell_inputs()
    h_p, c_p = fused_lstm_cell(*args, act, gate)
    h_x, c_x, *_ = _cell_math(*args, _ACT[act][0], _ACT[gate][0])
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_x), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_x), atol=1e-6)


def test_fused_lstm_cell_gradients_match_autodiff():
    args = _cell_inputs(seed=1)

    def loss_fused(*a):
        h, c = fused_lstm_cell(*a, "tanh", "sigmoid")
        return jnp.sum(h * h) + jnp.sum(jnp.sin(c))

    def loss_xla(*a):
        h, c, *_ = _cell_math(*a, _ACT["tanh"][0], _ACT["sigmoid"][0])
        return jnp.sum(h * h) + jnp.sum(jnp.sin(c))

    g_fused = jax.grad(loss_fused, argnums=tuple(range(7)))(*args)
    g_xla = jax.grad(loss_xla, argnums=tuple(range(7)))(*args)
    for gf, gx, name in zip(g_fused, g_xla,
                            ["zx", "h_prev", "c_prev", "RW", "pF", "pI", "pO"]):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gx), atol=1e-5, err_msg=f"grad {name}"
        )


# T -> the time block the seq kernels take at B=8 (whole f32 sublane tiles):
# a prime T above the cap leaves one step a grid step, 24 runs three blocks
# of 8 (the row before a block comes from the one-step boundary stream), 8 is
# one block covering all of T, 12 two blocks under the cap
_SEQ_T_AND_BLOCK = [(17, 1), (24, 8), (8, 8), (12, 6)]
_SEQ_B, _SEQ_H = 8, 16


def _seq_inputs(seed=0, T=6, B=_SEQ_B, H=_SEQ_H):
    """h0/c0 are non-zero: the first block's boundary row is the initial
    state, every later block's is the step before it."""
    rng = np.random.default_rng(seed)
    r = lambda *s: jnp.asarray(rng.normal(size=s) * 0.4, jnp.float32)  # noqa: E731
    return (r(T, B, 4 * H), r(B, H), r(B, H), r(H, 4 * H),
            r(H) * 0.2, r(H) * 0.2, r(H) * 0.2)


def _seq_mask(seed, T, B=_SEQ_B):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.random((T, B, 1)) > 0.3).astype(np.float32))


def _seq_ref(zx, h0, c0, RW, pF, pI, pO, act="tanh", gate="sigmoid",
             mask=None):
    a_fn, g_fn = _ACT[act][0], _ACT[gate][0]
    if mask is None:
        mask = jnp.ones((zx.shape[0], 1, 1), zx.dtype)

    def step(carry, inp):
        z, m = inp
        h, c = carry
        h2, c2, *_ = _cell_math(z, h, c, RW, pF, pI, pO, a_fn, g_fn)
        h2, c2 = m * h2 + (1 - m) * h, m * c2 + (1 - m) * c  # masked: hold
        return (h2, c2), h2

    (hT, cT), ys = jax.lax.scan(step, (h0, c0), (zx, mask))
    return ys, hT, cT


def _seq_fused(mask):
    from deeplearning4j_tpu.ops.pallas_kernels import (
        fused_lstm_sequence,
        fused_lstm_sequence_masked,
    )

    if mask is None:
        return fused_lstm_sequence
    return lambda zx, *rest: fused_lstm_sequence_masked(zx, mask, *rest)


@pytest.mark.parametrize("T,block", _SEQ_T_AND_BLOCK)
def test_seq_time_block_of_the_test_shapes(T, block):
    from deeplearning4j_tpu.ops.pallas_kernels import _seq_time_block

    assert _seq_time_block(T, _SEQ_B, _SEQ_H, 4) == block


@pytest.mark.parametrize("T,B,H,itemsize", [
    (256, 64, 512, 2), (256, 64, 512, 4), (50, 32, 256, 2), (16, 16, 128, 2),
    (16, 16, 128, 4), (100, 128, 1024, 2), (251, 64, 512, 2), (6, 4, 8, 4),
    (256, 64, 2048, 4), (64, 8, 128, 2), (48, 256, 1024, 4),
])
def test_seq_time_block_divides_T_fits_vmem_and_fills_the_mxu(T, B, H, itemsize):
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    tc = pk._seq_time_block(T, B, H, itemsize)
    budget = pk._seq_vmem_budget()
    cap = pk._SEQ_MAX_TIME_BLOCK
    assert 1 <= tc <= cap and T % tc == 0
    fitting = [d for d in range(1, min(T, cap) + 1) if T % d == 0
               and pk._seq_footprint(d, B, H, itemsize) <= budget]
    if B % (32 // itemsize):
        assert tc == 1     # rows short of a sublane tile: no block matmul
    elif fitting:
        assert tc == max(fitting)
        # the MXU's contraction is filled whenever a divisor allows
        assert tc * B >= 128 or not any(d * B >= 128 for d in fitting)
        # a wider item never gets a longer block
        assert pk._seq_time_block(T, B, H, 2 * itemsize) <= tc
    else:
        assert tc == 1 and not pk._seq_fits(B, H, itemsize)


def test_seq_time_block_shrinks_with_the_vmem_budget(monkeypatch):
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    T, B, H = 256, 64, 512
    assert pk._seq_time_block(T, B, H, 2) == pk._SEQ_MAX_TIME_BLOCK == 8
    seen = []
    for mib in (64, 32, 24, 17, 8):
        monkeypatch.setattr(pk, "_SEQ_VMEM_BUDGET_BYTES", mib << 20)
        tc = pk._seq_time_block(T, B, H, 2)
        assert tc == 1 or pk._seq_footprint(tc, B, H, 2) <= mib << 20
        seen.append(tc)
    assert seen == [8, 8, 4, 1, 1]
    # where not even one step fits the block is 1 and selection gives way
    assert seen[-1] == 1 and not pk._seq_fits(B, H, 2)
    # f32 doubles every streamed block: a shorter block under a budget that
    # holds 8 bf16 steps but not 8 f32 ones
    monkeypatch.setattr(pk, "_SEQ_VMEM_BUDGET_BYTES", 32 << 20)
    assert pk._seq_time_block(T, B, H, 4) == 4 < pk._seq_time_block(T, B, H, 2)


@pytest.mark.parametrize("T,block", _SEQ_T_AND_BLOCK)
@pytest.mark.parametrize("act,gate", [("tanh", "sigmoid"), ("tanh", "hardsigmoid")])
@pytest.mark.parametrize("masked", [False, True])
def test_fused_lstm_sequence_forward_matches_scan(act, gate, masked, T, block):
    args = _seq_inputs(seed=3, T=T)
    mask = _seq_mask(2, T) if masked else None
    got = _seq_fused(mask)(*args, act, gate)
    want = _seq_ref(*args, act=act, gate=gate, mask=mask)
    for g, w, name in zip(got, want, ["ys", "hT", "cT"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6,
                                   err_msg=name)


@pytest.mark.parametrize("T,block", _SEQ_T_AND_BLOCK)
@pytest.mark.parametrize("masked", [False, True])
def test_fused_lstm_sequence_gradients_match_autodiff(masked, T, block):
    """The whole-loop custom VJP (reverse-time blocks, VMEM carries, the
    block's own rows shifted by one for c_{t-1}/h_{t-1}, dRW and the
    peephole sums once a block) against autodiff-through-scan, all seven
    cotangents."""
    args = _seq_inputs(seed=4, T=T)
    mask = _seq_mask(5, T) if masked else None
    fused = _seq_fused(mask)

    def loss_of(fn):
        def loss(*a):
            ys, hT, cT = fn(*a)
            return jnp.sum(ys * ys) + jnp.sum(hT) + 0.5 * jnp.sum(jnp.sin(cT))
        return loss

    gk = jax.grad(loss_of(lambda *a: fused(*a, "tanh", "sigmoid")),
                  argnums=tuple(range(7)))(*args)
    gr = jax.grad(loss_of(lambda *a: _seq_ref(*a, mask=mask)),
                  argnums=tuple(range(7)))(*args)
    for a, b, name in zip(gk, gr, ["zx", "h0", "c0", "RW", "pF", "pI", "pO"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=f"grad {name}")


@pytest.mark.parametrize("T,block", _SEQ_T_AND_BLOCK[:3])
@pytest.mark.parametrize("kind", ["plain", "masked", "bidirectional"])
def test_fused_lstm_sequence_layer_end_to_end(monkeypatch, kind, T, block):
    """DL4J_TPU_PALLAS=seq routes GravesLSTM (padded batches through the
    masked kernels) and both directions of GravesBidirectionalLSTM
    (reverse = the forward kernel on time-flipped input) through the
    sequence kernels; 3 adam steps must match the scan path bit-close."""
    from deeplearning4j_tpu import (
        GravesBidirectionalLSTM,
        GravesLSTM,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        RnnOutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.datasets.iterators import DataSet
    from deeplearning4j_tpu.ops import kernel_select as ks

    layer = GravesBidirectionalLSTM if kind == "bidirectional" else GravesLSTM

    def make():
        conf = MultiLayerConfiguration(
            layers=[layer(n_out=16, activation="tanh"),
                    RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent")],
            input_type=InputType.recurrent(7),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
            seed=3,
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.normal(size=(_SEQ_B, T, 7)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (_SEQ_B, T))]
    data = (x, y)
    if kind == "masked":
        fm = np.ones((_SEQ_B, T), np.float32)
        fm[1, T - 5:] = 0.0
        fm[3, T // 2:] = 0.0
        data = DataSet(x, y, fm, fm)
    ks.reset()
    monkeypatch.setenv("DL4J_TPU_PALLAS", "seq")
    seq = make()
    for _ in range(3):
        seq.fit(data)
    chosen = [r for r in ks.selection_log() if r["site"] == "lstm_seq"]
    assert chosen and all(r["variant"] == "seqfused"
                          and r["time_block"] == block for r in chosen)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ref = make()
    for _ in range(3):
        ref.fit(data)
    for a, b in zip(jax.tree_util.tree_leaves(seq.params),
                    jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("T", [9, 24])   # B=4: one step a grid step; B=8: 8
def test_fused_lstm_sequence_inside_fit_on_device(monkeypatch, T):
    """The charrnn bench path: the sequence kernel nested inside the
    one-dispatch lax.scan training loop (stacked 2-layer char-RNN) must
    match the scan path — this is exactly what the benchmark's
    charrnn_train_1chip cell runs on hardware."""
    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.models.char_rnn import char_rnn

    def make():
        conf = char_rnn(vocab_size=12, hidden_size=16, num_layers=2)
        conf.backprop_type = "standard"
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    idx = rng.integers(0, 12, size=(4 if T == 9 else _SEQ_B, T + 1))
    xs = np.eye(12, dtype=np.float32)[idx[None, :, :-1]]
    ys = np.eye(12, dtype=np.float32)[idx[None, :, 1:]]
    monkeypatch.setenv("DL4J_TPU_PALLAS", "seq")
    seq_losses = make().fit_on_device(xs, ys, steps=3)
    monkeypatch.setenv("DL4J_TPU_PALLAS", "0")
    ref_losses = make().fit_on_device(xs, ys, steps=3)
    np.testing.assert_allclose(seq_losses, ref_losses, atol=1e-5)


def test_fused_lstm_cell_under_scan_trains():
    """The fused cell must compose with lax.scan + jit + grad (the real
    training topology)."""
    args = _cell_inputs(seed=2)
    zx, h0, c0, RW, pF, pI, pO = args
    T = 5
    zxs = jnp.stack([zx * (t + 1) / T for t in range(T)])

    @jax.jit
    def loss(RW, pF, pI, pO):
        def step(carry, z):
            h, c = fused_lstm_cell(z, carry[0], carry[1], RW, pF, pI, pO,
                                   "tanh", "sigmoid")
            return (h, c), h

        (_, _), ys = jax.lax.scan(step, (h0, c0), zxs)
        return jnp.mean(ys**2)

    g = jax.grad(loss)(RW, pF, pI, pO)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).sum()) > 0


def test_fused_lrn_matches_xla_value_and_grad():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 3, 3, 8)), jnp.float32)
    k, n, alpha, beta = 2.0, 5, 1e-4, 0.75

    def xla_lrn(x):
        d = k + alpha * _window_sum(x * x, n)
        return x * d**-beta

    np.testing.assert_allclose(
        np.asarray(fused_lrn(x, k, n, alpha, beta)), np.asarray(xla_lrn(x)),
        atol=1e-6,
    )
    g_p = jax.grad(lambda v: jnp.sum(jnp.cos(fused_lrn(v, k, n, alpha, beta))))(x)
    g_x = jax.grad(lambda v: jnp.sum(jnp.cos(xla_lrn(v))))(x)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x), atol=1e-5)


def test_dispatch_fallback_off_tpu_and_force_on():
    """Auto mode on CPU uses XLA math; forcing helpers on routes through
    pallas interpret — results identical either way."""
    args = _cell_inputs(seed=4)
    assert jax.default_backend() != "tpu"
    assert not ops.helpers_enabled()
    h_auto, c_auto = ops.lstm_cell(*args, "tanh", "sigmoid")
    try:
        ops.set_helpers_enabled(True)
        assert ops.helpers_enabled()
        h_forced, c_forced = ops.lstm_cell(*args, "tanh", "sigmoid")
    finally:
        ops.set_helpers_enabled(None)
    np.testing.assert_allclose(np.asarray(h_auto), np.asarray(h_forced), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_auto), np.asarray(c_forced), atol=1e-6)


def test_lstm_layer_end_to_end_with_helpers_forced():
    """A GravesLSTM network trains identically (numerics within tolerance)
    with the helper tier forced on."""
    from deeplearning4j_tpu import (
        GravesLSTM,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        RnnOutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.datasets.iterators import DataSet

    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 6, 3)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=(4, 6))]

    def build():
        conf = MultiLayerConfiguration(
            layers=[
                GravesLSTM(n_out=8),
                RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent"),
            ],
            input_type=InputType.recurrent(3, 6),
            updater=UpdaterConfig(updater="sgd", learning_rate=0.1),
            seed=0,
        )
        return MultiLayerNetwork(conf).init()

    net_plain = build()
    net_plain.fit(DataSet(x, y))
    out_plain = np.asarray(net_plain.output(x))

    try:
        ops.set_helpers_enabled(True)
        net_helper = build()
        net_helper.fit(DataSet(x, y))
        out_helper = np.asarray(net_helper.output(x))
    finally:
        ops.set_helpers_enabled(None)
    np.testing.assert_allclose(out_plain, out_helper, atol=1e-5)


@pytest.mark.parametrize("n", [2, 4, 5])
def test_fused_lrn_grad_even_and_odd_windows(n):
    """Even n makes the window asymmetric; the backward must use the adjoint
    (flipped) window, not the forward one."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    k, alpha, beta = 2.0, 1e-2, 0.75

    def xla_lrn(v):
        d = k + alpha * _window_sum(v * v, n)
        return v * d**-beta

    g_p = jax.grad(lambda v: jnp.sum(jnp.sin(fused_lrn(v, k, n, alpha, beta))))(x)
    g_x = jax.grad(lambda v: jnp.sum(jnp.sin(xla_lrn(v))))(x)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_x), atol=1e-5)


def test_rnn_time_step_streaming_under_seq_kernel(monkeypatch):
    """Streaming inference under the TPU-default dispatch: rnn_time_step's
    carried h/c state through the seq-kernel path must match the scan
    path step for step (single-step calls AND a multi-step warmup chunk)."""
    from deeplearning4j_tpu import (
        GravesLSTM,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        RnnOutputLayer,
        UpdaterConfig,
    )

    def make():
        conf = MultiLayerConfiguration(
            layers=[GravesLSTM(n_out=12, activation="tanh"),
                    RnnOutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent")],
            input_type=InputType.recurrent(6),
            updater=UpdaterConfig(updater="sgd", learning_rate=0.05),
            seed=9,
        )
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(4)
    warm = rng.normal(size=(3, 8, 6)).astype(np.float32)   # [B, T, F] chunk
    steps = [rng.normal(size=(3, 6)).astype(np.float32) for _ in range(4)]

    outs = {}
    for mode in ("0", "seq"):
        monkeypatch.setenv("DL4J_TPU_PALLAS", mode)
        net = make()
        chunk = np.asarray(net.rnn_time_step(warm), np.float32)
        singles = [np.asarray(net.rnn_time_step(s), np.float32)
                   for s in steps]
        outs[mode] = (chunk, singles)
    np.testing.assert_allclose(outs["0"][0], outs["seq"][0],
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(outs["0"][1], outs["seq"][1]):
        # the carried h/c crossed the kernel boundary identically
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape", [
    (512, 2048),      # 4 MiB: flattened into lane rows, four blocks
    (1100, 1000),     # longer: tiled as it lies
    (1100, 1024),     # the same with a last axis of whole lane tiles
    (8, 512, 320),    # longer, last but one whole lane tiles: axes swapped
    (3, 700, 500),    # rows no whole sublane tiles: flattened after all
    (100, 33),        # short: flattened and lane-padded
])
def test_fused_adam_update_is_adams_step_in_every_view_of_a_leaf(shape):
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    key = jax.random.PRNGKey(len(shape) + shape[-1])
    g, m, v = (jax.random.normal(k, shape, jnp.float32)
               for k in jax.random.split(key, 3))
    v = jnp.square(v)
    lr, b1, b2, eps, t = 1e-3, 0.9, 0.999, 1e-8, 3
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    u, m2, v2 = pk.fused_adam_update(g, m, v, lr, bc1, bc2, b1, b2, eps)
    want_m = b1 * m + (1 - b1) * g
    want_v = b2 * v + (1 - b2) * g * g
    want_u = -lr * (want_m / bc1) / (jnp.sqrt(want_v / bc2) + eps)
    assert u.shape == m2.shape == v2.shape == shape
    np.testing.assert_allclose(np.asarray(m2), np.asarray(want_m), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(want_v), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(u), np.asarray(want_u), rtol=1e-4,
                               atol=1e-8)


def test_adam_views_keep_six_double_buffered_blocks_inside_the_default():
    """One rule for every leaf: blocks of at most 1 MiB, so no call states
    a VMEM limit; a leaf of up to 4 MiB is flattened, a longer matrix is
    tiled as it lies."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    def view(shape):
        swap, rows, cols, tile = pk._adam_view(shape, 4)
        lanes = -(-cols // pk._ADAM_LANES) * pk._ADAM_LANES
        assert 12 * tile * lanes * 4 <= 12 * pk._ADAM_BLOCK_BYTES < 16 << 20
        return swap, rows, cols

    # charrnn_2x512's leaves (4 MiB at most), and what cannot be tiled as it
    # lies: lane rows, as ever
    for shape in [(512, 2048), (96, 2048), (512, 96), (2048,), (512,),
                  (2688, 256), (4, 6144), (3, 700, 500), (2000000,)]:
        assert view(shape) == (False, None, 128), shape
    assert view((96,)) == (False, None, 96)
    # a longer matrix: as it lies, swapped where the TPU keeps it so
    assert view((16384, 2688)) == (False, 16384, 2688)
    assert view((2688, 16384)) == (False, 2688, 16384)
    assert view((8, 1856, 2688)) == (False, 8 * 1856, 2688)
    assert view((8, 2688, 1856)) == (True, 8 * 1856, 2688)
    assert view((2688, 10304)) == (True, 10304, 2688)
    assert view((1100, 1000)) == (False, 1100, 1000)
    assert view((8, 512, 320)) == (True, 8 * 320, 512)


# the views ``_adam_view`` gives the benchmark's leaves, at sizes a CPU test
# can afford: with ``_ADAM_FLATTEN_BYTES`` lowered to 1 KiB a small leaf takes
# the view of the large leaf it stands for
ADAM_LEAVES = [
    ((2, 128, 72), (True, 144, 128)),    # [8, 2688, 1856]: axes swapped
    ((128, 200), (True, 200, 128)),      # [2688, 10304]: swapped, two axes
    ((2, 72, 128), (False, 144, 128)),   # [8, 1856, 2688]: as it lies
    ((136, 256), (False, 136, 256)),     # [2688, 16384]: as it lies
    ((2688,), (False, None, 128)),       # a bias or a norm's weight: lane rows
    ((64, 96), (False, None, 128)),      # short rows, as the char-RNN's
                                         # leaves are under 4 MiB: lane rows
    ((96, 33), (False, None, 128)),      # no multiple of 128: lane-padded
]


def _adam_leaf(shape, monkeypatch):
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_ADAM_FLATTEN_BYTES", 1 << 10)
    key = jax.random.PRNGKey(sum(shape))
    return pk, {"w": jax.random.normal(key, shape, jnp.float32)}


def _grads(params, i):
    return jax.tree_util.tree_map(lambda a: jnp.sin(a * (i + 1.5)), params)


@pytest.mark.parametrize("shape,view", ADAM_LEAVES)
def test_in_place_adam_is_optax_adam_over_three_steps(shape, view,
                                                      monkeypatch):
    """The kernel whose results lie on its operands against the same kernel
    with the aliases taken off (as it was before PR 33), bit for bit, and
    against ``optax.adam``: update, parameter and both moments, three steps
    of a jit that donates what it carries."""
    import optax
    from jax.experimental import pallas as pl

    pk, params = _adam_leaf(shape, monkeypatch)
    assert pk._adam_view(shape, 4)[:3] == view
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8
    # tied where the leaf is tiled as it lies; a flattened leaf's call is
    # declared as it always was
    w = params["w"]
    (call,) = [e for e in jax.make_jaxpr(lambda g, m, v: pk.fused_adam_update(
        g, m, v, lr, 0.1, 0.001, b1, b2, eps))(w, w, w).eqns
        if e.primitive.name == "pallas_call"]
    assert call.params["input_output_aliases"] == (
        () if view[1] is None else ((0, 0), (1, 1), (2, 2)))

    def steps():
        def step(p, m, v, t, g):
            u, m, v = pk.fused_adam_update(g, m, v, lr, 1 - b1 ** t,
                                           1 - b2 ** t, b1, b2, eps)
            return optax.apply_updates(p, u), m, v, u

        step = jax.jit(step, donate_argnums=(0, 1, 2))
        p = params["w"] + 0.0   # the jit donates its own copy
        m, v = jnp.zeros_like(p), jnp.zeros_like(p)
        for i in range(3):
            g = _grads({"w": p}, i)["w"]
            p, m, v, u = step(p, m, v, jnp.float32(i + 1), g)
        return [np.asarray(a) for a in (p, m, v, u)]

    got = steps()
    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, input_output_aliases=None, **kw: real(*a, **kw))
    for a, b in zip(got, steps()):
        assert a.shape == shape and a.dtype == np.float32
        np.testing.assert_array_equal(a, b)

    ref = optax.adam(lr, b1=b1, b2=b2, eps=eps)
    want_p, want_state = params, ref.init(params)
    for i in range(3):
        u, want_state = ref.update(_grads(want_p, i), want_state, want_p)
        want_p = optax.apply_updates(want_p, u)
    # float32 against optax's own order of operations: an ulp or two
    for a, want, atol in ((got[0], want_p, 1e-6), (got[3], u, 1e-6),
                          (got[1], want_state[0].mu, 1e-6),
                          (got[2], want_state[0].nu, 1e-8)):
        np.testing.assert_allclose(a, np.asarray(want["w"]), rtol=1e-5,
                                   atol=atol)


@pytest.mark.parametrize("shape,view", ADAM_LEAVES)
def test_aliased_adam_leaves_the_callers_arrays_unharmed(shape, view,
                                                         monkeypatch):
    """A leaf tiled as it lies has every result aliased onto an operand; a
    jit that does not donate them must hand back fresh arrays and leave the
    caller's as they were, in every view."""
    pk, params = _adam_leaf(shape, monkeypatch)
    g, m = params["w"], params["w"] * 0.5
    v = jnp.square(m)
    kept = [np.array(a) for a in (g, m, v)]

    @jax.jit
    def step(g, m, v):
        return pk.fused_adam_update(g, m, v, 1e-2, 0.1, 0.001, 0.9, 0.999,
                                    1e-8)

    results = jax.block_until_ready(step(g, m, v))
    for was, now, result in zip(kept, (g, m, v), results):
        np.testing.assert_array_equal(was, np.asarray(now))
        assert not np.array_equal(np.asarray(result), was)


@pytest.mark.parametrize("route", ["reference", "fused"])
def test_adam_state_tree_and_checkpoint_are_optax_adams(route, tmp_path,
                                                        monkeypatch):
    """The in-place updater keeps optax's optimizer-state tree, and a net
    trained through it saves and restores moments and parameters as any
    other and goes on training from them."""
    import optax

    from deeplearning4j_tpu import (DenseLayer, InputType,
                                    MultiLayerConfiguration,
                                    MultiLayerNetwork, OutputLayer,
                                    UpdaterConfig, restore_model, write_model)
    from deeplearning4j_tpu.ops import kernel_select as ks
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_ADAM_FLATTEN_BYTES", 1 << 10)
    ks.reset()
    if route == "fused":
        ks.set_force_available(True)
        ks.set_site_override("optimizer", "fused")
    conf = MultiLayerConfiguration(
        layers=[DenseLayer(n_out=128, activation="tanh"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(136),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2), seed=5)
    net = MultiLayerNetwork(conf).init()
    want = optax.chain(optax.identity(), optax.adam(
        optax.constant_schedule(1e-2))).init(net.params)
    tree = jax.tree_util.tree_structure
    assert tree(net.opt_state) == tree(want)
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(3, 8, 136)), jnp.float32)
    ys = jax.nn.one_hot(jnp.asarray(rng.integers(0, 3, (3, 8))), 3)
    losses = net.fit_on_device(xs, ys)
    assert np.isfinite(losses).all()
    sites = [r for r in ks.selection_log() if r["site"] == "optimizer"]
    assert sites and sites[-1]["variant"] == route
    assert tree(net.opt_state) == tree(want)
    path = str(tmp_path / "net.zip")
    write_model(net, path)
    back = restore_model(path)
    assert tree(back.opt_state) == tree(net.opt_state)
    for a, b in zip(jax.tree_util.tree_leaves((net.params, net.opt_state)),
                    jax.tree_util.tree_leaves((back.params, back.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and it goes on training from there as the net it was saved from does
    np.testing.assert_array_equal(back.fit_on_device(xs, ys),
                                  net.fit_on_device(xs, ys))
