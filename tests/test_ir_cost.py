"""dl4jtpu-irlint: DT2xx IR rules + static roofline cost model (ISSUE 5).

Covers the acceptance criteria:
- ``net.analyze_ir(batch)`` returns findings + a cost report on BOTH net
  classes with ZERO device dispatches (counting-tracer proof: every real
  execution funnels through ``pxla.ExecuteReplicated.__call__``).
- the cost model's dense/conv FLOPs match closed-form analytic values
  exactly;
- the DT202 donation audit catches a deliberately-broken donation while
  the normal ``fit_on_device`` path stays clean;
- findings are merged/deduplicated/stable-sorted across passes;
- the compile manager runs the scan at admission (counters, flight events,
  cost records next to the memory records);
- CLI ``--ir`` and ``conf.analyze(ir=True)`` share the JSON/exit-code
  semantics of the other passes.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (
    ComputationGraph,
    ComputationGraphConfiguration,
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.analysis import (
    RULES,
    audit_donation,
    check_jaxpr_ir,
    check_network_ir,
    check_padding_waste,
    jaxpr_cost,
    merge_findings,
    roofline_params,
    static_cost,
)
from deeplearning4j_tpu.analysis.cli import main as cli_main
from deeplearning4j_tpu.analysis.findings import Finding
from deeplearning4j_tpu.datasets.bucketing import BucketedStager
from deeplearning4j_tpu.datasets.iterators import DataSet, ListDataSetIterator
from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
from deeplearning4j_tpu.telemetry import get_registry


def _mln(n_in=64, hidden=128, n_out=8, updater="adam"):
    return MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=hidden, activation="relu"),
                OutputLayer(n_out=n_out, activation="softmax",
                            loss="mcxent")],
        input_type=InputType.feed_forward(n_in),
        updater=UpdaterConfig(updater=updater, learning_rate=1e-3)))


def _graph(n_in=32, hidden=64, n_out=8):
    conf = (ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .add_layer("h", DenseLayer(n_out=hidden, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=n_out, activation="softmax",
                                          loss="mcxent"), "h")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(n_in))
            .build())
    return ComputationGraph(conf)


def _rules_hit(findings):
    return {f.rule_id for f in findings}


class TestCostModelGroundTruth:
    """Satellite: counted FLOPs match closed-form analytic values exactly."""

    def test_dense_matmul_flops_exact(self):
        B, I, O = 32, 64, 128
        cost = static_cost(
            lambda x, w: x @ w,
            jax.ShapeDtypeStruct((B, I), jnp.float32),
            jax.ShapeDtypeStruct((I, O), jnp.float32))
        assert cost["flops"] == 2 * B * I * O

    def test_dense_layer_with_bias_flops_exact(self):
        B, I, O = 16, 48, 96
        cost = static_cost(
            lambda x, w, b: x @ w + b,
            jax.ShapeDtypeStruct((B, I), jnp.float32),
            jax.ShapeDtypeStruct((I, O), jnp.float32),
            jax.ShapeDtypeStruct((O,), jnp.float32))
        # dot + one add per output element (the broadcast itself is free)
        assert cost["flops"] == 2 * B * I * O + B * O

    def test_conv_flops_exact(self):
        B, H, W, Cin, Cout, K = 4, 16, 16, 8, 32, 3

        def conv(x, k):
            return jax.lax.conv_general_dilated(
                x, k, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        cost = static_cost(
            conv,
            jax.ShapeDtypeStruct((B, H, W, Cin), jnp.float32),
            jax.ShapeDtypeStruct((K, K, Cin, Cout), jnp.float32))
        assert cost["flops"] == 2 * B * H * W * Cout * K * K * Cin

    def test_train_step_flops_match_closed_form_floor(self):
        # full fwd+bwd of the MLP: first layer pays fwd + dL/dW (inputs are
        # not differentiated), the head pays fwd + dL/dW + dL/dh; the
        # counted total sits between that floor and floor + elementwise
        B, I, H, O = 64, 784, 256, 10
        net = _mln(n_in=I, hidden=H, n_out=O, updater="sgd").init()
        cost = net.analyze_ir(B)["static_cost"]
        floor = 2 * (2 * B * I * H) + 3 * (2 * B * H * O)
        assert floor <= cost["flops"] <= floor * 1.1

    def test_scan_multiplies_body_by_length(self):
        L, B = 10, 4

        def scanned(c0, xs):
            def body(c, x):
                return c + x @ jnp.ones((8, 8), jnp.float32), None
            return jax.lax.scan(body, c0, xs)

        cost = static_cost(
            scanned,
            jax.ShapeDtypeStruct((B, 8), jnp.float32),
            jax.ShapeDtypeStruct((L, B, 8), jnp.float32))
        assert cost["flops"] >= L * 2 * B * 8 * 8

    def test_roofline_report_shape(self):
        cost = static_cost(lambda x: (x * 2).sum(),
                           jax.ShapeDtypeStruct((128, 128), jnp.float32))
        rl = cost["roofline"]
        assert rl["predicted_step_seconds"] > 0
        assert rl["bound"] in ("compute", "memory")
        assert rl["ridge_flops_per_byte"] == pytest.approx(
            rl["peak_flops"] / (rl["hbm_gbps"] * 1e9))
        assert cost["arithmetic_intensity"] == pytest.approx(
            cost["flops"] / cost["hbm_bytes"])

    def test_peaks_table_off_chip_is_the_assumed_v5e_row(self):
        from deeplearning4j_tpu.analysis.cost_model import (DEVICE_PEAKS,
                                                             device_peaks)

        row = device_peaks()
        assert row["assumed"] is True and row["device_kind"] == "TPU v5 lite"
        assert row["peak_flops"] == 1.97e14 and row["hbm_gbps"] == 819.0
        assert row["ici_gbps"] == 200.0
        assert all(r["source"] for r in DEVICE_PEAKS.values())
        rl = roofline_params()
        assert rl["assumed"] is True and rl["peak_flops"] == 1.97e14

    def test_peaks_table_on_a_tpu_is_keyed_by_device_kind(self, monkeypatch):
        import jax

        from deeplearning4j_tpu import profiler
        from deeplearning4j_tpu.analysis import cost_model

        class FakeTpu:
            platform = "tpu"

            def __init__(self, kind):
                self.device_kind = kind

        monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu("TPU v5 lite")])
        row = cost_model.device_peaks()
        assert row["assumed"] is False and row["device_kind"] == "TPU v5 lite"
        assert profiler.mfu(1.97e14, 1.0) == pytest.approx(100.0)
        monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu("TPU v9")])
        with pytest.raises(KeyError, match="no peaks for device_kind 'TPU v9'"):
            cost_model.roofline_params()
        with pytest.raises(KeyError, match="TPU v9"):
            profiler.mfu(1e12, 1.0)

    def test_roofline_env_knobs(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_PEAK_FLOPS", "1e12")
        monkeypatch.setenv("DL4JTPU_HBM_GBPS", "100")
        rl = roofline_params()
        assert rl["peak_flops"] == 1e12
        assert rl["hbm_gbps"] == 100.0
        assert rl["ridge_flops_per_byte"] == pytest.approx(10.0)


class TestAnalyzeIr:
    def test_mln_report_structure_and_clean(self):
        net = _mln().init()
        rep = net.analyze_ir(32)
        assert set(rep) == {"findings", "static_cost", "numerics"}
        assert all(isinstance(f, Finding) for f in rep["findings"])
        # the repo's own step must be clean at warning level (DT206
        # "memory-bound" is info by design for tiny CPU-probe nets)
        assert not [f for f in rep["findings"] if f.severity != "info"]
        assert rep["static_cost"]["flops"] > 0
        assert rep["static_cost"]["hbm_bytes"] > 0

    def test_graph_report_structure_and_clean(self):
        net = _graph().init()
        rep = net.analyze_ir(16)
        assert not [f for f in rep["findings"] if f.severity != "info"]
        assert rep["static_cost"]["flops"] > 0

    def test_zero_device_dispatches_counting_tracer(self, monkeypatch):
        """Acceptance: analyze_ir is pure trace/eval_shape. Every real
        execution (eager or jit) funnels through
        ExecuteReplicated.__call__; analyze_ir must never reach it."""
        from jax._src.interpreters import pxla

        mln = _mln().init()
        graph = _graph().init()
        calls = []

        def boom(self, *a, **kw):
            calls.append(1)
            raise AssertionError("device dispatch during analyze_ir")

        monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", boom)
        rep = mln.analyze_ir(32)
        rep_g = graph.analyze_ir(16)
        assert calls == []
        assert rep["static_cost"]["flops"] > 0
        assert rep_g["static_cost"]["flops"] > 0

    def test_ignore_suppresses_rules(self):
        net = _mln().init()
        rep = net.analyze_ir(32, ignore=("DT206",))
        assert "DT206" not in _rules_hit(rep["findings"])

    def test_recurrent_net_traces_with_probe(self):
        from deeplearning4j_tpu import GravesLSTM, RnnOutputLayer

        net = MultiLayerNetwork(MultiLayerConfiguration(
            layers=[GravesLSTM(n_out=16, activation="tanh"),
                    RnnOutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent")],
            input_type=InputType.recurrent(8, timesteps=None),
            updater=UpdaterConfig(updater="sgd", learning_rate=0.1))).init()
        rep = net.analyze_ir(4)
        assert rep["static_cost"]["flops"] > 0


class TestDt200Promotion:
    def test_tensor_promotion_fires(self):
        closed = jax.make_jaxpr(lambda x: x * np.float64(2.0))(
            jax.ShapeDtypeStruct((32, 64), jnp.float32))
        assert "DT200" in _rules_hit(check_jaxpr_ir(closed))

    def test_astype_promotion_fires(self):
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64).sum())(
            jax.ShapeDtypeStruct((16, 16), jnp.float32))
        assert "DT200" in _rules_hit(check_jaxpr_ir(closed))

    def test_scalar_x64_bookkeeping_not_flagged(self):
        # optax-style scalar bias correction under x64: scalar f64 math is
        # free on the scalar core — must not drown the report
        def f(x, count):
            corr = 1.0 - jnp.asarray(0.9, jnp.float64) ** count
            return x / corr.astype(x.dtype)

        closed = jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((8, 8), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32))
        assert "DT200" not in _rules_hit(check_jaxpr_ir(closed))

    def test_all_f64_program_not_flagged(self):
        # an intentionally-f64 pipeline has no promotion POINT
        closed = jax.make_jaxpr(lambda x: (x * 2.0).sum())(
            jax.ShapeDtypeStruct((16,), jnp.float64))
        assert "DT200" not in _rules_hit(check_jaxpr_ir(closed))


class TestDt201Callbacks:
    def test_debug_print_fires(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
        assert "DT201" in _rules_hit(check_jaxpr_ir(closed))

    def test_pure_callback_fires(self):
        def f(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct((4,), np.float32), x)
            return y + 1

        closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), jnp.float32))
        assert "DT201" in _rules_hit(check_jaxpr_ir(closed))

    def test_clean_step_has_no_callbacks(self):
        closed = jax.make_jaxpr(lambda x: jnp.tanh(x).sum())(
            jax.ShapeDtypeStruct((4,), jnp.float32))
        assert "DT201" not in _rules_hit(check_jaxpr_ir(closed))


class TestDt202Donation:
    """Acceptance: a deliberately-broken donation is caught; the normal
    fit_on_device path stays clean."""

    def test_broken_donation_caught(self):
        fn = lambda a, b: (a * 2.0, b.sum())  # noqa: E731
        findings = audit_donation(
            fn,
            (jax.ShapeDtypeStruct((8, 8), jnp.float32),
             jax.ShapeDtypeStruct((16,), jnp.float32)),
            donate_argnums=(0, 1))
        assert [f.rule_id for f in findings] == ["DT202"]
        assert "1 of 2" in findings[0].message

    def test_matching_donation_clean(self):
        fn = lambda a, b: (a * 2.0, b * 3.0)  # noqa: E731
        assert audit_donation(
            fn,
            (jax.ShapeDtypeStruct((8, 8), jnp.float32),
             jax.ShapeDtypeStruct((16,), jnp.float32)),
            donate_argnums=(0, 1)) == []

    def test_no_donation_requested_is_noop(self):
        fn = lambda a: a.sum()  # noqa: E731
        assert audit_donation(
            fn, (jax.ShapeDtypeStruct((8,), jnp.float32),),
            donate_argnums=()) == []

    def test_normal_train_step_donation_clean_on_both_classes(self):
        # the real step returns new params/opt/state with identical
        # shapes/dtypes, so the (0, 1, 2) donation the TPU path requests
        # fully aliases — analyze_ir audits that contract on any backend
        for net in (_mln().init(), _graph().init()):
            rep = net.analyze_ir(16)
            assert "DT202" not in _rules_hit(rep["findings"])

    def test_dropped_donation_in_step_shaped_fixture(self):
        # a step that "updates" params but returns them flattened: every
        # donated buffer loses its matching output — the bug class DT202
        # exists for (dropped donation = double-buffered params)
        def step(params, opt_state, x):
            loss = (x @ params["w"]).sum() + opt_state["m"].sum()
            flat = jnp.concatenate([params["w"].ravel(),
                                    opt_state["m"].ravel()])
            return flat, loss

        args = ({"w": jax.ShapeDtypeStruct((8, 4), jnp.float32)},
                {"m": jax.ShapeDtypeStruct((8, 4), jnp.float32)},
                jax.ShapeDtypeStruct((2, 8), jnp.float32))
        findings = audit_donation(step, args, donate_argnums=(0, 1))
        assert [f.rule_id for f in findings] == ["DT202"]
        assert "2 of 2" in findings[0].message


class TestDt203Blowup:
    def test_big_broadcast_fires(self):
        closed = jax.make_jaxpr(
            lambda s: jnp.broadcast_to(s, (4096, 4096)) + 0.5)(
            jax.ShapeDtypeStruct((4096,), jnp.float32))
        assert "DT203" in _rules_hit(check_jaxpr_ir(closed))

    def test_small_bias_broadcast_not_flagged(self):
        closed = jax.make_jaxpr(lambda x, b: x + b)(
            jax.ShapeDtypeStruct((128, 128), jnp.float32),
            jax.ShapeDtypeStruct((128,), jnp.float32))
        assert "DT203" not in _rules_hit(check_jaxpr_ir(closed))


class TestDt204DynamicIndices:
    def test_traced_indices_fire(self):
        closed = jax.make_jaxpr(lambda x, i: x[i])(
            jax.ShapeDtypeStruct((100, 8), jnp.float32),
            jax.ShapeDtypeStruct((16,), jnp.int32))
        assert "DT204" in _rules_hit(check_jaxpr_ir(closed))

    def test_constant_indices_clean(self):
        idx = np.arange(16)
        closed = jax.make_jaxpr(lambda x: x[idx])(
            jax.ShapeDtypeStruct((100, 8), jnp.float32))
        assert "DT204" not in _rules_hit(check_jaxpr_ir(closed))

    # -- PR 6 regression fixtures: constness must survive the nested-jaxpr
    # boundary (the PR 5 known limit — a baked np index array threaded into
    # a scanned/sub-jaxpr used to read as a traced gather index)

    def test_baked_indices_into_scan_clean(self):
        idx = np.array([0, 2, 1, 3])

        def f(x):
            def body(carry, row):
                return carry + row[idx].sum(), None

            return jax.lax.scan(body, 0.0, x)[0]

        closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((5, 4), jnp.float32))
        assert "DT204" not in _rules_hit(check_jaxpr_ir(closed))

    def test_baked_indices_as_subjaxpr_argument_clean(self):
        idx = jnp.asarray(np.array([1, 0, 3]))

        def f(x):
            return jax.jit(lambda a, j: a[j].sum())(x, idx)

        closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((7,), jnp.float32))
        assert "DT204" not in _rules_hit(check_jaxpr_ir(closed))

    def test_traced_indices_inside_scan_still_fire(self):
        def f(x, js):
            def body(c, j):
                return c + x[j].sum(), None

            return jax.lax.scan(body, 0.0, js)[0]

        closed = jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((7,), jnp.float32),
            jax.ShapeDtypeStruct((4, 2), jnp.int32))
        assert "DT204" in _rules_hit(check_jaxpr_ir(closed))


class TestDt205PaddingWaste:
    def test_stager_accumulates_padding_stats(self):
        stager = BucketedStager(4)
        batches = [DataSet(np.zeros((b, 8), np.float32),
                           np.zeros((b, 4), np.float32))
                   for b in (32, 32, 2)]

        def normalize(ds):
            return ([np.asarray(ds.features)], [np.asarray(ds.labels)],
                    [None], [None])

        list(stager.plan(batches, normalize))
        stats = stager.padding_stats()
        assert stats["windows"] == 1
        assert stats["batches"] == 3
        # 66 real rows staged as 3 slots x 32 rows
        assert stats["padding_fraction"] == pytest.approx(1 - 66 / 96)

    def test_threshold_gates_finding(self):
        stats = {"windows": 2, "batches": 6, "real_bytes": 50,
                 "staged_bytes": 100, "padding_fraction": 0.5}
        assert [f.rule_id for f in check_padding_waste(stats)] == ["DT205"]
        assert check_padding_waste(stats, threshold=0.6) == []
        assert check_padding_waste({"windows": 0}) == []
        assert check_padding_waste(None) == []

    def test_fit_epoch_hook_increments_counter(self):
        fam = get_registry().counter(
            "dl4jtpu_ir_findings_total",
            "IR-lint (DT2xx) findings from admission/preflight/epoch scans",
            labelnames=("rule",))
        before = fam.labels(rule="DT205").value
        net = _mln(n_in=8, hidden=16, n_out=4, updater="sgd").init()
        rng = np.random.default_rng(0)
        batches = [DataSet(rng.normal(size=(b, 8)).astype(np.float32),
                           np.eye(4, dtype=np.float32)[
                               rng.integers(0, 4, b)])
                   for b in (32, 32, 2)]
        net.fit(ListDataSetIterator(batches), stage_on_device=4)
        assert fam.labels(rule="DT205").value >= before + 1


class TestDt206Dt207:
    def test_memory_bound_info(self):
        closed = jax.make_jaxpr(lambda x: x + 1.0)(
            jax.ShapeDtypeStruct((64,), jnp.float32))
        f = [f for f in check_jaxpr_ir(closed) if f.rule_id == "DT206"]
        assert f and f[0].severity == "info"

    def test_compute_bound_no_dt206(self, monkeypatch):
        # drop the modeled peak so a matmul crosses the ridge
        monkeypatch.setenv("DL4JTPU_PEAK_FLOPS", "1e9")
        closed = jax.make_jaxpr(lambda x: x @ x)(
            jax.ShapeDtypeStruct((512, 512), jnp.float32))
        assert "DT206" not in _rules_hit(check_jaxpr_ir(closed))

    def test_collectives_counted_and_flagged(self):
        closed = jax.make_jaxpr(lambda x: jax.lax.psum(x, "i"),
                                axis_env=[("i", 8)])(
            jax.ShapeDtypeStruct((32,), jnp.float32))
        cost = jaxpr_cost(closed)
        assert cost["collectives"]["count"] == 1
        assert cost["collectives"]["bytes"] == 32 * 4
        f = [f for f in check_jaxpr_ir(closed, cost=cost)
             if f.rule_id == "DT207"]
        assert f and f[0].severity == "info"


class TestCompileManagerAdmission:
    def test_aot_admission_records_cost_and_counters(self):
        cm = get_compile_manager()
        net = _mln(n_in=8, hidden=16, n_out=4, updater="sgd").init()
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(2, 8, 8)).astype(np.float32)
        ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, 8))]
        net.fit_on_device(xs, ys, steps=3)
        stats = cm.stats()["static_cost"]
        assert stats["entries_with_cost"] >= 1
        assert stats["last"]["flops"] > 0
        assert stats["last"]["bound"] in ("compute", "memory")
        records = cm.cost_records()
        assert any(k.startswith("mln_multi_step") for k in records)
        # the per-entry report sits NEXT to the PR 4 memory record
        assert set(cm.memory_records()) >= set(records)
        # no DT202 on the normal path (CPU requests no donation; the
        # analyze_ir audit of the TPU contract is checked elsewhere)
        fam = get_registry().get("dl4jtpu_ir_findings_total")
        assert fam is not None
        dt202 = [c.value for k, c in fam._items() if k == ("DT202",)]
        assert not dt202 or dt202[0] == 0

    def test_admission_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_IR_CHECKS", "0")
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        cm = CompileManager(registry=MetricsRegistry())
        fn = cm.aot(("t", "k"), lambda: jax.jit(lambda x: x * 2),
                    (jnp.ones((4,)),))
        assert np.allclose(fn(jnp.ones((4,))), 2.0)
        assert cm.stats()["static_cost"]["entries_with_cost"] == 0

    def test_eviction_retires_cost_records(self):
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        cm = CompileManager(max_entries=1, registry=MetricsRegistry())
        cm.aot(("t", "a"), lambda: jax.jit(lambda x: x * 2),
               (jnp.ones((4,)),))
        cm.aot(("t", "b"), lambda: jax.jit(lambda x: x * 3),
               (jnp.ones((4,)),))
        assert len(cm.cost_records()) <= 1


class TestMergeAndCli:
    def test_merge_dedupes_and_stable_sorts(self):
        a = Finding("DT206", "info", "msg", file="z.json", context="c")
        b = Finding("DT206", "info", "msg", file="z.json", context="c")
        c = Finding("DT200", "warning", "other", file="a.json", context="c")
        merged = merge_findings([a, c], [b])
        assert len(merged) == 2
        assert [f.rule_id for f in merged] == ["DT200", "DT206"]
        # repeated merging is idempotent and order-stable
        assert merge_findings(merged, merged) == merged

    def test_conf_analyze_ir_flag_and_repeatability(self):
        conf = _mln().conf
        once = conf.analyze(ir=True)
        twice = conf.analyze(ir=True)
        assert [f.to_dict() for f in once] == [f.to_dict() for f in twice]
        assert "DT206" in _rules_hit(once)
        assert conf.analyze(ir=True, ignore=("DT206",)) == []

    def test_graph_conf_analyze_ir_flag(self):
        conf = _graph().conf
        assert "DT206" in _rules_hit(conf.analyze(ir=True))

    def _write_conf(self, tmp_path, name="net.json"):
        conf = _mln(n_in=128, hidden=128, n_out=8).conf
        p = tmp_path / name
        p.write_text(conf.to_json())
        return str(p)

    def test_cli_ir_json_report(self, tmp_path, capsys):
        path = self._write_conf(tmp_path)
        rc = cli_main(["--ir", "--json", "--fail-on", "warning", path])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0  # DT206 is info — below the warning threshold
        assert out["files_analyzed"] == 1
        assert {f["rule_id"] for f in out["findings"]} == {"DT206"}
        assert len(out["static_cost"]) == 1
        cost = out["static_cost"][0]
        assert cost["source"] == path
        assert cost["flops"] > 0
        assert cost["roofline"]["predicted_step_seconds"] > 0

    def test_cli_ir_exit_code_semantics(self, tmp_path, capsys):
        path = self._write_conf(tmp_path)
        assert cli_main(["--ir", "--fail-on", "info", path]) == 1
        capsys.readouterr()
        assert cli_main(["--ir", "--fail-on", "never", path]) == 0
        capsys.readouterr()

    def test_cli_same_config_twice_dedupes(self, tmp_path, capsys):
        path = self._write_conf(tmp_path)
        cli_main(["--ir", "--json", "--fail-on", "never", path, path])
        out = json.loads(capsys.readouterr().out)
        assert out["files_analyzed"] == 2
        # the bugfix: repeated passes cannot emit the same finding twice
        dicts = [json.dumps(f, sort_keys=True) for f in out["findings"]]
        assert len(dicts) == len(set(dicts))
        assert {f["rule_id"] for f in out["findings"]} == {"DT206"}

    def test_cli_ignore_flag(self, tmp_path, capsys):
        path = self._write_conf(tmp_path)
        rc = cli_main(["--ir", "--json", "--fail-on", "info",
                       "--ignore", "DT206", path])
        out = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert out["findings"] == []

    def test_cli_ignore_unknown_rule_rejected(self, capsys):
        assert cli_main(["--ignore", "DT999", "foo.py"]) == 2

    def test_cli_list_rules_includes_ir_scope(self, capsys):
        cli_main(["--list-rules"])
        out = capsys.readouterr().out
        for rid in ("DT200", "DT202", "DT207"):
            assert rid in out


class TestPreflightFolding:
    def test_preflight_report_carries_ir_section(self):
        net = _mln().init()
        rep = net.preflight(16)
        assert "ir" in rep
        assert rep["ir"]["static_cost"]["flops"] > 0
        assert {f["rule_id"] for f in rep["ir"]["findings"]} <= set(RULES)


class TestRuleCatalog:
    def test_every_ir_rule_has_a_fixture_in_this_file(self):
        """Every shipped DT2xx rule is exercised above; a new IR rule must
        bring a fixture (mirrors test_analysis' per-scope guarantees).
        The DT3xx sharding-flow family has its per-rule firing + clean
        fixtures in tests/test_shard_flow.py."""
        ir_rules = {rid for rid, r in RULES.items() if r.scope == "ir"}
        assert ir_rules == {"DT200", "DT201", "DT202", "DT203", "DT204",
                            "DT205", "DT206", "DT207",
                            "DT300", "DT301", "DT302", "DT303", "DT304",
                            "DT305", "DT306"}

    def test_ir_rules_registered_with_hints(self):
        for rid, rule in RULES.items():
            if rule.scope == "ir":
                assert rule.hint and rule.description
