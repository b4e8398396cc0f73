"""Kernel-selection tests (ISSUE 6): cost-model-guided variant routing.

Covers the selection core (modes, overrides, determinism, calibration),
fused-vs-reference parity — forward AND gradient — for every selectable
site on CPU interpret mode, the observability plumbing (counter, flight
recorder, compile-manager stats, /api/ircost), and the bench regression
gate script.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.ops import kernel_select as ks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_selection_state(tmp_path, monkeypatch):
    """Every test starts with an empty selection cache and a throwaway
    calibration store (the repo-root JSON must never be touched by tests)."""
    monkeypatch.setenv(ks.CALIBRATION_PATH_ENV,
                       str(tmp_path / "calibration.json"))
    monkeypatch.delenv(ks.KERNELS_ENV, raising=False)
    monkeypatch.delenv("DL4J_TPU_PALLAS", raising=False)
    ks.reset()
    yield
    ks.reset()


def _charrnn_ctx(**kw):
    ctx = {"T": 256, "B": 64, "H": 512, "itemsize": 2, "acts_ok": True,
           "masked": False}
    ctx.update(kw)
    return ctx


def _attn_ctx(T, **kw):
    ctx = {"B": 4, "heads": 8, "T": T, "D": 64, "itemsize": 2,
           "causal": True}
    ctx.update(kw)
    return ctx


class TestSelectionCore:
    def test_auto_on_cpu_is_reference(self):
        # fused Pallas variants only compete on a TPU-class backend
        assert ks.select("lstm_seq", _charrnn_ctx()) == "reference"
        assert ks.select("softmax_xent",
                         {"N": 4096, "C": 96, "itemsize": 4}) == "reference"

    def test_auto_with_availability_picks_seqfused_for_charrnn(self):
        # the ISSUE acceptance shape: B=64 H=512 T=256 bf16 is memory-bound
        # (DT206) and the whole-sequence kernel moves ~3x fewer bytes
        ks.set_force_available(True)
        assert ks.select("lstm_seq", _charrnn_ctx()) == "seqfused"

    def test_seqfused_unfit_shape_falls_back(self):
        ks.set_force_available(True)
        # H huge: the VMEM guard rejects the fused sequence AND cell kernels
        ctx = _charrnn_ctx(H=8192, itemsize=4)
        assert ks.select("lstm_seq", ctx) == "reference"

    def test_a_give_way_is_never_quiet(self):
        ks.set_force_available(True)
        ctx = _charrnn_ctx(H=8192, itemsize=4)
        assert ks.select("lstm_seq", ctx) == "reference"
        rec = ks.selection_log()[-1]
        assert rec["reason"] == "fallback"
        assert rec["infeasible"] == ["seqfused", "fusedcell"]
        # a fitting shape records no give-way
        ks.select("lstm_seq", _charrnn_ctx())
        assert "infeasible" not in ks.selection_log()[-1]
        # flash past its K+V VMEM budget: the site says xla (the path that
        # runs), also when flash was asked for by name
        big = _attn_ctx(1 << 17)
        assert ks.select("attention", big, forced="flash") == "xla"
        rec = ks.selection_log()[-1]
        assert rec["reason"] == "fallback" and rec["infeasible"] == ["flash"]

    def test_partitioned_program_keeps_mosaic_kernels_out(self):
        ks.set_force_available(True)
        ctx = {"N": 16384, "C": 96, "itemsize": 4}
        assert ks.select("softmax_xent", ctx) == "fused"
        with ks.partitioned_program():
            assert ks.select("softmax_xent", ctx) == "reference"
            assert ks.select("lstm_seq", _charrnn_ctx(),
                             forced="seqfused") == "reference"
            with ks.partitioned_program(False):  # inner no-op, outer holds
                assert ks.select("optimizer", {
                    "n_elems": 1 << 20, "itemsize": 4, "updater": "adam",
                    "n_leaves": 4}) == "reference"
        for rec in ks.selection_log()[1:]:
            assert rec["ctx"]["partitioned"] is True
            assert rec["infeasible"] and rec["reason"] == "fallback"
        # the scope is over; the unpartitioned selection is its own cache key
        assert ks.select("softmax_xent", ctx) == "fused"

    def test_scoped_for_layout_only_wraps_multi_device_layouts(self):
        from deeplearning4j_tpu.parallel import MeshLayout

        seen = []
        probe = lambda: seen.append(  # noqa: E731
            ks.select("softmax_xent", {"N": 64, "C": 8, "itemsize": 4}))
        ks.set_mode("fused")
        assert ks.scoped_for_layout(probe, None) is probe
        assert ks.scoped_for_layout(probe, MeshLayout(data=1)) is probe
        ks.scoped_for_layout(probe, MeshLayout(data=2, fsdp=2))()
        probe()
        assert seen == ["reference", "fused"]

    def test_unsupported_activations_always_reference(self):
        ks.set_force_available(True)
        assert ks.select("lstm_seq",
                         _charrnn_ctx(acts_ok=False)) == "reference"

    def test_attention_seq_threshold(self):
        ks.set_force_available(True)
        assert ks.select("attention", _attn_ctx(4096)) == "flash"
        # below DL4JTPU_FLASH_MIN_SEQ auto keeps the XLA path
        assert ks.select("attention", _attn_ctx(64)) == "xla"

    def test_mode_env_reference(self, monkeypatch):
        monkeypatch.setenv(ks.KERNELS_ENV, "reference")
        ks.set_force_available(True)
        assert ks.select("lstm_seq", _charrnn_ctx()) == "reference"
        assert ks.select("attention", _attn_ctx(4096)) == "xla"

    def test_mode_env_fused(self, monkeypatch):
        monkeypatch.setenv(ks.KERNELS_ENV, "fused")
        # fused mode pins the preferred fused variant even off-TPU (the
        # interpret-mode testing path), still subject to hard feasibility
        assert ks.select("lstm_seq", _charrnn_ctx()) == "seqfused"
        assert ks.select("lstm_seq",
                         _charrnn_ctx(acts_ok=False)) == "reference"

    def test_per_site_env_override(self, monkeypatch):
        monkeypatch.setenv(ks.KERNELS_ENV, "fused,lstm_seq=reference")
        assert ks.select("lstm_seq", _charrnn_ctx()) == "reference"
        assert ks.select("softmax_xent",
                         {"N": 4096, "C": 96, "itemsize": 4}) == "fused"

    def test_programmatic_site_override(self):
        ks.set_force_available(True)
        ks.set_site_override("attention", "xla")
        assert ks.select("attention", _attn_ctx(4096)) == "xla"
        ks.set_site_override("attention", None)
        assert ks.select("attention", _attn_ctx(4096)) == "flash"

    def test_forced_wins_over_mode(self, monkeypatch):
        monkeypatch.setenv(ks.KERNELS_ENV, "fused")
        assert ks.select("lstm_seq", _charrnn_ctx(),
                         forced="reference") == "reference"

    def test_optimizer_site_requires_adam(self):
        ks.set_force_available(True)
        ks.set_mode("fused")
        ctx = {"n_elems": 1 << 20, "itemsize": 4, "updater": "sgd",
               "n_leaves": 4}
        assert ks.select("optimizer", ctx) == "reference"
        ctx = dict(ctx, updater="adam")
        assert ks.select("optimizer", ctx) == "fused"

    def test_determinism_and_logged_once(self):
        ks.set_force_available(True)
        first = ks.select("lstm_seq", _charrnn_ctx())
        for _ in range(5):
            assert ks.select("lstm_seq", _charrnn_ctx()) == first
        log = [r for r in ks.selection_log() if r["site"] == "lstm_seq"]
        assert len(log) == 1  # cached: same shapes resolve AND log once
        # a different shape is a new decision
        ks.select("lstm_seq", _charrnn_ctx(T=128))
        log = [r for r in ks.selection_log() if r["site"] == "lstm_seq"]
        assert len(log) == 2

    def test_stats_shape(self):
        ks.set_force_available(True)
        ks.select("lrn", {"rows": 1 << 16, "C": 64, "n": 5, "itemsize": 4})
        st = ks.stats()
        assert st["selections_total"] >= 1
        assert "lrn" in st["by_site"]
        assert set(st["by_site"]["lrn"]) <= {"fused", "reference"}
        assert "calibration" in st and "factor" in st["calibration"]

    @pytest.mark.parametrize("ctx,block", [
        (dict(), 8),                  # T=256 B=64 H=512 bf16: the cap
        (dict(T=50), 5),              # a TBPTT segment
        (dict(T=251), 1),             # prime T: the blocking does not engage
        (dict(B=4, itemsize=4), 1),   # rows short of a sublane tile
    ])
    def test_lstm_seq_record_carries_its_time_block(self, ctx, block):
        from deeplearning4j_tpu.ops.pallas_kernels import _seq_time_block

        ks.set_force_available(True)
        ctx = _charrnn_ctx(**ctx)
        assert ks.select("lstm_seq", ctx, forced="seqfused") == "seqfused"
        rec = ks.selection_log()[-1]
        assert rec["time_block"] == block == _seq_time_block(
            ctx["T"], ctx["B"], ctx["H"], ctx["itemsize"])
        assert ks.stats()["recent"][-1]["time_block"] == block

    def test_time_block_is_the_seqfused_variant_s_alone(self):
        ks.set_force_available(True)
        ks.select("lstm_seq", _charrnn_ctx(), forced="reference")
        ks.select("softmax_xent", {"N": 1 << 14, "C": 96, "itemsize": 4})
        assert all("time_block" not in r for r in ks.selection_log())

    @pytest.mark.parametrize("ctx,share,operand", [
        # nemotron3_nano_train_1chip's attention block: 16 tiles a side
        (dict(B=1, heads=32, T=8192, D=128, kv_heads=2), 17 / 32, "bfloat16"),
        (dict(B=1, heads=32, T=8192, D=128, causal=False), 1.0, "bfloat16"),
        (dict(itemsize=4), 9 / 16, "float32"),   # T=4096, D=64: 8 a side
        (dict(T=300), 2 / 3, "bfloat16"),        # 128-wide, padded to 384
    ])
    def test_attention_record_says_its_tiles_and_what_they_walk(
            self, ctx, share, operand):
        from deeplearning4j_tpu.ops.flash_attention import (
            default_blocks, tiles_walked_share)

        ks.set_force_available(True)
        ctx = _attn_ctx(ctx.pop("T", 4096), **ctx)
        assert ks.select("attention", ctx) == "flash"
        rec = ks.selection_log()[-1]
        assert rec["reason"] == "auto"
        blocks = default_blocks(ctx["T"], ctx["D"], ctx["itemsize"])
        assert (rec["block_q"], rec["block_k"]) == blocks
        assert rec["mxu_operand"] == operand
        # from the function the kernels take their loop bounds from
        assert rec["tiles_walked_share"] == pytest.approx(share) \
            == tiles_walked_share(ctx["T"], *blocks, ctx["causal"])
        assert ks.stats()["recent"][-1]["tiles_walked_share"] \
            == rec["tiles_walked_share"]

    def test_tiles_are_the_flash_variant_s_alone(self):
        ks.set_force_available(True)
        assert ks.select("attention", _attn_ctx(64)) == "xla"
        assert not {"block_q", "block_k", "mxu_operand",
                    "tiles_walked_share"} & set(ks.selection_log()[-1])

    def test_flash_cost_follows_the_tiles_the_kernel_walks(self):
        from deeplearning4j_tpu.ops.kernel_select import (_attn_flash_cost,
                                                          _attn_xla_cost)

        causal, full = _attn_ctx(8192, D=128), _attn_ctx(8192, D=128,
                                                         causal=False)
        flops, nbytes, overhead = _attn_flash_cost(full)
        assert flops == 14.0 * 32 * 8192 * 8192 * 128
        c_flops, c_bytes, c_overhead = _attn_flash_cost(causal)
        # 17 of 32 tiles a side-pair at 512-wide tiles; bytes as they were
        assert c_flops == pytest.approx(flops * 17 / 32)
        assert (c_bytes, c_overhead) == (nbytes, overhead)
        # the XLA path computes the whole square and masks it
        assert _attn_xla_cost(causal) == _attn_xla_cost(full)


class TestCalibration:
    def test_update_and_factor(self):
        # predicted 4x slower than measured -> discount un-fused bytes 4x
        assert ks.update_calibration("charrnn", 4.0)
        assert ks.calibration_factor() == pytest.approx(0.25, rel=1e-6)
        data = json.loads(open(os.environ[ks.CALIBRATION_PATH_ENV]).read())
        assert data["charrnn"] == 4.0

    def test_under_prediction_never_inflates(self):
        # measured slower than predicted (CPU-ish ratio) must NOT discount
        assert ks.update_calibration("mlp", 0.01)
        assert ks.calibration_factor() == 1.0

    def test_factor_floor(self):
        ks.update_calibration("x", 1e9)
        assert ks.calibration_factor() == pytest.approx(0.05)

    def test_discount_can_flip_a_selection(self):
        ks.set_force_available(True)
        rows = {"rows": 1 << 16, "C": 64, "n": 5, "itemsize": 4}
        assert ks.select("lrn", rows) == "fused"
        # a huge measured discount says XLA fuses the reference path far
        # better than counted -> reference wins on the roofline
        ks.update_calibration("measured", 1e9)
        assert ks.select("lrn", rows) == "reference"

    def test_malformed_file_reads_as_empty(self):
        with open(os.environ[ks.CALIBRATION_PATH_ENV], "w") as f:
            f.write("not json{")
        assert ks.calibration_factor() == 1.0


class TestFusedSoftmaxXentParity:
    def _ref_rows(self, x, lab):
        return -(lab * jax.nn.log_softmax(x, axis=-1)).sum(-1)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
    def test_forward_and_gradients(self, rng, dtype):
        from deeplearning4j_tpu.ops.pallas_kernels import fused_softmax_xent

        x = jnp.asarray(rng.normal(size=(9, 17)), dtype)
        lab = jnp.asarray(
            np.eye(17)[rng.integers(0, 17, 9)] * 0.9 + 0.005, dtype)
        tol = 1e-6 if dtype == jnp.float32 else 1e-12
        np.testing.assert_allclose(fused_softmax_xent(x, lab),
                                   self._ref_rows(x, lab), atol=tol)
        gf = jax.grad(lambda a, b: fused_softmax_xent(a, b).sum(),
                      argnums=(0, 1))(x, lab)
        gr = jax.grad(lambda a, b: self._ref_rows(a, b).sum(),
                      argnums=(0, 1))(x, lab)
        np.testing.assert_allclose(gf[0], gr[0], atol=tol)
        np.testing.assert_allclose(gf[1], gr[1], atol=tol)

    def test_loss_registry_routing_matches_reference(self, rng):
        from deeplearning4j_tpu.nn.losses import get_loss

        x = jnp.asarray(rng.normal(size=(12, 7)), jnp.float32)
        lab = jnp.asarray(np.eye(7, dtype=np.float32)[
            rng.integers(0, 7, 12)])
        mask = jnp.asarray((rng.random(12) > 0.3).astype(np.float32))
        ref = get_loss("mcxent")(lab, x, "softmax", mask)
        ks.set_mode("fused")
        ks.set_force_available(True)
        fused = get_loss("mcxent")(lab, x, "softmax", mask)
        np.testing.assert_allclose(fused, ref, atol=1e-6)


class TestFusedAdamParity:
    def _tree(self, rng):
        return {"W": jnp.asarray(rng.normal(size=(13, 29))),
                "b": jnp.asarray(rng.normal(size=(29,)))}

    def _run(self, fused: bool, rng, **cfg):
        from deeplearning4j_tpu.nn.updaters import UpdaterConfig

        ks.reset()
        if fused:
            ks.set_mode("fused")
            ks.set_force_available(True)
        params = self._tree(rng)
        tx = UpdaterConfig(updater="adam", learning_rate=1e-2, **cfg).build()
        state = tx.init(params)

        @jax.jit
        def step(p, s, g):
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s

        for i in range(6):
            g = jax.tree_util.tree_map(
                lambda a: 0.05 * (i + 1) * jnp.ones_like(a), params)
            params, state = step(params, state, g)
        ks.reset()
        return params, state

    def test_trajectory_matches_optax(self):
        r = np.random.default_rng(3)
        p_ref, s_ref = self._run(False, np.random.default_rng(3))
        p_fused, s_fused = self._run(True, r)
        for k in p_ref:
            np.testing.assert_allclose(p_fused[k], p_ref[k], atol=1e-9)
        assert (jax.tree_util.tree_structure(s_ref)
                == jax.tree_util.tree_structure(s_fused))

    def test_trajectory_matches_with_schedule(self):
        kw = dict(lr_policy="step", lr_policy_decay_rate=0.5,
                  lr_policy_steps=2)
        p_ref, _ = self._run(False, np.random.default_rng(4), **kw)
        p_fused, _ = self._run(True, np.random.default_rng(4), **kw)
        for k in p_ref:
            np.testing.assert_allclose(p_fused[k], p_ref[k], atol=1e-9)


class TestSelectionDrivenNetParity:
    """Whole-net loss+gradient parity: the same config under forced fused
    routing must match the reference path for every touched site."""

    def _lstm_net(self):
        from deeplearning4j_tpu import (GravesLSTM, InputType,
                                        MultiLayerConfiguration,
                                        MultiLayerNetwork, UpdaterConfig)
        from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer

        conf = MultiLayerConfiguration(
            layers=[GravesLSTM(n_out=16),
                    RnnOutputLayer(n_out=5, activation="softmax",
                                   loss="mcxent")],
            input_type=InputType.recurrent(6),
            updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
            seed=11)
        return MultiLayerNetwork(conf).init()

    def test_lstm_softmax_xent_adam_sites(self, rng):
        xs = jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.float32)
        ys = jnp.asarray(np.eye(5, dtype=np.float32)[
            rng.integers(0, 5, (2, 8))])

        def loss_and_grad():
            net = self._lstm_net()
            val = net.loss_fn(net.params, xs, ys, train=False)
            grads = jax.grad(net.loss_fn)(net.params, xs, ys, train=False)
            return val, grads

        ref_val, ref_grads = loss_and_grad()
        ks.set_mode("fused")
        ks.set_force_available(True)
        fused_val, fused_grads = loss_and_grad()
        np.testing.assert_allclose(fused_val, ref_val, rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(fused_grads),
                        jax.tree_util.tree_leaves(ref_grads)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
        sites = {r["site"]: r["variant"] for r in ks.selection_log()
                 if r["variant"] != "reference"}
        assert sites.get("lstm_seq") == "seqfused"
        assert sites.get("softmax_xent") == "fused"

    def test_lrn_layer_parity(self, rng):
        from deeplearning4j_tpu.nn.layers.normalization import (
            LocalResponseNormalization)

        layer = LocalResponseNormalization()
        x = jnp.asarray(rng.normal(size=(2, 3, 3, 16)), jnp.float32)

        def val(v):
            y, _ = layer.apply({}, v, {})
            return jnp.sum(y ** 2)

        ref_y, ref_g = val(x), jax.grad(val)(x)
        ks.set_mode("fused")
        ks.set_force_available(True)
        np.testing.assert_allclose(val(x), ref_y, rtol=1e-5)
        np.testing.assert_allclose(jax.grad(val)(x), ref_g,
                                   rtol=1e-4, atol=1e-6)
        assert {r["site"] for r in ks.selection_log()} >= {"lrn"}

    def test_attention_layer_parity(self, rng):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

        layer = SelfAttentionLayer(n_out=16, n_heads=2, causal=True)
        assert layer.attention_impl == "auto"
        params = layer.init_params(jax.random.PRNGKey(0),
                                   InputType.recurrent(16, 12))
        x = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)

        def val(p):
            y, _ = layer.apply(p, x, {})
            return jnp.sum(y ** 2)

        ref_y, ref_g = val(params), jax.grad(val)(params)
        ks.set_mode("fused")
        ks.set_force_available(True)
        fused_y, fused_g = val(params), jax.grad(val)(params)
        np.testing.assert_allclose(fused_y, ref_y, rtol=1e-4)
        for a, b in zip(jax.tree_util.tree_leaves(fused_g),
                        jax.tree_util.tree_leaves(ref_g)):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
        assert {r["site"]: r["variant"] for r in ks.selection_log()
                }.get("attention") == "flash"

    def test_legacy_pallas_env_still_forces(self, monkeypatch, rng):
        # DL4J_TPU_PALLAS=seq keeps its historical meaning through the
        # selection layer (forced, logged with reason "forced")
        monkeypatch.setenv("DL4J_TPU_PALLAS", "seq")
        net = self._lstm_net()
        xs = jnp.asarray(rng.normal(size=(2, 8, 6)), jnp.float32)
        ys = jnp.asarray(np.eye(5, dtype=np.float32)[
            rng.integers(0, 5, (2, 8))])
        float(net.loss_fn(net.params, xs, ys))
        recs = [r for r in ks.selection_log() if r["site"] == "lstm_seq"]
        assert recs and recs[0]["variant"] == "seqfused"
        assert recs[0]["reason"] == "forced"


class TestObservability:
    def test_counter_and_flight_event(self):
        from deeplearning4j_tpu.telemetry import get_registry
        from deeplearning4j_tpu.telemetry.flight_recorder import (
            get_flight_recorder)

        ks.set_force_available(True)
        ks.select("softmax_xent", {"N": 1 << 14, "C": 96, "itemsize": 4})
        fam = get_registry().get("dl4jtpu_kernel_selected_total")
        assert fam is not None
        counts = {key: child.value for key, child in fam._items()}
        assert any(k[0] == "softmax_xent" for k in counts)
        kinds = [e for e in get_flight_recorder().snapshot(256)["events"]
                 if e["kind"] == "kernel_select"]
        assert kinds and kinds[-1]["site"] == "softmax_xent"

    def test_compile_manager_stats_kernels_block(self):
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        cm = CompileManager(max_entries=4, registry=MetricsRegistry())
        st = cm.stats()
        assert "kernels" in st and "by_site" in st["kernels"]

    def test_admission_captures_new_selections(self):
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        ks.set_mode("fused")
        ks.set_force_available(True)
        cm = CompileManager(max_entries=4, registry=MetricsRegistry())

        def build():
            from deeplearning4j_tpu.ops import softmax_xent_rows

            return jax.jit(lambda x, l: softmax_xent_rows(l, x).sum())

        x = jnp.ones((256, 32), jnp.float32)
        lab = jnp.ones((256, 32), jnp.float32) / 32
        cm.aot(("t", "sxent"), build, (x, lab))
        recs = cm.cost_records()
        (rec,) = recs.values()
        kernels = rec.get("kernels", [])
        assert any(k["site"] == "softmax_xent" and k["variant"] == "fused"
                   for k in kernels)

    def test_api_ircost_kernels_block(self):
        import urllib.request

        from deeplearning4j_tpu.ui.server import UIServer

        ks.set_force_available(True)
        ks.select("lrn", {"rows": 4096, "C": 32, "n": 5, "itemsize": 4})
        server = UIServer(port=0)
        try:
            body = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/api/ircost",
                timeout=10).read())
            assert "kernels" in body
            assert body["kernels"]["selections_total"] >= 1
        finally:
            server.stop()


class TestBenchGate:
    def _gate(self):
        spec = importlib.util.spec_from_file_location(
            "bench_gate", os.path.join(REPO, "scripts", "bench_gate.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _result(self, value, metric="mlp_mnist_train_samples_per_sec"):
        return {"metric": metric, "value": value, "unit": "samples/sec"}

    def test_within_band_passes(self):
        g = self._gate()
        ok, msgs, new = g.gate([self._result(7000)], {
            "mlp_mnist_train_samples_per_sec": 7888}, 0.75, False)
        assert ok and new["mlp_mnist_train_samples_per_sec"] == 7888

    def test_regression_fails(self):
        # the r03->r04 drop (7888 -> 5508, 0.70x) must be caught
        g = self._gate()
        ok, msgs, _ = g.gate([self._result(5508)], {
            "mlp_mnist_train_samples_per_sec": 7888}, 0.75, False)
        assert not ok
        assert any("FAIL" in m for m in msgs)

    def test_missing_baseline_anchors(self):
        g = self._gate()
        ok, msgs, new = g.gate([self._result(5000)], {}, 0.75, False)
        assert ok and new["mlp_mnist_train_samples_per_sec"] == 5000

    def test_refresh_moves_baseline(self):
        g = self._gate()
        ok, _, new = g.gate([self._result(9000)], {
            "mlp_mnist_train_samples_per_sec": 7888}, 0.75, True)
        assert ok and new["mlp_mnist_train_samples_per_sec"] == 9000

    def test_bench_error_fails(self):
        g = self._gate()
        ok, msgs, _ = g.gate([{"metric": "bench_error", "value": 0.0,
                               "unit": "error"}], {}, 0.75, False)
        assert not ok

    def test_cli_end_to_end(self, tmp_path):
        g = self._gate()
        res = tmp_path / "r.json"
        res.write_text(json.dumps(self._result(5132.6)) + "\n")
        base = tmp_path / "base.json"
        base.write_text(json.dumps(
            {"mlp_mnist_train_samples_per_sec": 5000.0}))
        assert g.main([str(res), "--baseline", str(base)]) == 0
        res.write_text(json.dumps(self._result(1000.0)) + "\n")
        assert g.main([str(res), "--baseline", str(base)]) == 1
        # repo baseline file exists and every entry is gate-parseable
        # (bare number or {"value": x, "tolerance": t} override form)
        repo_base = g.load_baselines(os.path.join(REPO,
                                                  "BENCH_BASELINE.json"))
        assert repo_base and all(
            isinstance(g.baseline_value(v), (int, float))
            and 0 < g.baseline_tolerance(v, 0.75) <= 1
            for v in repo_base.values())
