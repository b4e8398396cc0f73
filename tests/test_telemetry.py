"""Telemetry spine (ISSUE 2): registry semantics, Prometheus exposition,
span→Chrome-trace round-trip, watchdog anomalies, and — the acceptance
core — a counting-tracer proof that the K-step fetch adds zero extra host
syncs to ``fit_on_device`` (the jitted step compiles once and device
metrics are fetched at most ceil(steps/K) times)."""

import json
import math
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.telemetry import (
    NAN_LOSS,
    MetricsRegistry,
    Telemetry,
    Watchdog,
    get_recorder,
    get_registry,
    span,
)
from deeplearning4j_tpu.telemetry import device as tdevice


def _two_layer_net(seed: int = 7) -> MultiLayerNetwork:
    conf = MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=16, activation="relu"),
            OutputLayer(n_out=4, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=0.1),
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _staged_data(num_batches: int = 3, batch: int = 10, seed: int = 0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(num_batches, batch, 8)).astype(np.float32)
    ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (num_batches, batch))]
    return xs, ys


# --------------------------------------------------------------------------
# registry semantics
# --------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricsRegistry()
        c = r.counter("steps_total", "steps")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError):
            c.inc(-1)  # counters are monotone
        g = r.gauge("loss", "loss")
        g.set(2.5)
        g.dec(0.5)
        assert g.value == 2.0
        h = r.histogram("t", "times", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        s = h.summary()
        assert s["count"] == 3 and s["min"] == 0.05 and s["max"] == 5.0
        assert s["buckets"]["0.1"] == 1 and s["buckets"]["1"] == 2
        assert s["buckets"]["+Inf"] == 3

    def test_idempotent_registration_and_type_conflict(self):
        r = MetricsRegistry()
        a = r.counter("x_total", "x")
        b = r.counter("x_total", "different help is fine")
        assert a is b
        with pytest.raises(ValueError):
            r.gauge("x_total")  # same name, different type
        with pytest.raises(ValueError):
            r.counter("x_total", labelnames=("kind",))  # labelset conflict

    def test_labels(self):
        r = MetricsRegistry()
        c = r.counter("req_total", "requests", labelnames=("route",))
        c.labels(route="train").inc(2)
        c.labels(route="serve").inc()
        assert c.labels(route="train").value == 2
        with pytest.raises(ValueError):
            c.labels(wrong="x")
        with pytest.raises(ValueError):
            c.inc()  # labelled family needs .labels()

    def test_invalid_names_rejected(self):
        r = MetricsRegistry()
        with pytest.raises(ValueError):
            r.counter("9starts_with_digit")
        with pytest.raises(ValueError):
            r.histogram("h", labelnames=("le",))  # reserved

    def test_snapshot_shape(self):
        r = MetricsRegistry()
        r.counter("a_total", "a").inc()
        r.histogram("b_seconds", "b").observe(0.2)
        snap = r.snapshot()
        assert snap["a_total"]["type"] == "counter"
        assert snap["a_total"]["values"][0]["value"] == 1
        row = snap["b_seconds"]["values"][0]
        assert {"count", "sum", "mean", "min", "max", "buckets"} <= set(row)
        json.dumps(snap)  # JSON-ready end to end


class TestPrometheusExposition:
    def test_text_format(self):
        r = MetricsRegistry()
        r.counter("steps_total", "optimizer steps").inc(3)
        r.gauge("loss", "last loss").set(1.25)
        h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.7)
        c = r.counter("req_total", "requests", labelnames=("route", "code"))
        c.labels(route="train", code="200").inc()
        text = r.prometheus_text()
        assert "# HELP steps_total optimizer steps" in text
        assert "# TYPE steps_total counter" in text
        assert "steps_total 3" in text
        assert "loss 1.25" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text
        assert 'req_total{route="train",code="200"} 1' in text
        assert text.endswith("\n")

    def test_label_value_escaping(self):
        r = MetricsRegistry()
        c = r.counter("e_total", "esc", labelnames=("name",))
        c.labels(name='a"b\\c\nd').inc()
        text = r.prometheus_text()
        assert 'name="a\\"b\\\\c\\nd"' in text


# --------------------------------------------------------------------------
# spans
# --------------------------------------------------------------------------
def _own_events(prefix):
    """Events of the process-wide recorder whose names start with
    ``prefix`` (each test uses a prefix of its own)."""
    return [e for e in get_recorder().events if e["name"].startswith(prefix)]


def _span_family():
    return get_registry().get("dl4jtpu_span_seconds")


class TestSpans:
    def test_chrome_trace_round_trip(self, tmp_path):
        with span("rt.outer", step=1):
            with span("rt.inner"):
                pass
        path = get_recorder().export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as fh:
            doc = json.load(fh)
        events = [e for e in doc["traceEvents"] if e["name"].startswith("rt.")]
        assert [e["name"] for e in events] == ["rt.inner", "rt.outer"]  # close order
        for e in events:
            assert e["ph"] == "X" and e["dur"] >= 0 and e["pid"] > 0
        inner, outer = events
        # ts and dur are one clock (perf_counter): the inner span nests
        # inside the outer's [ts, ts+dur] window exactly
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert outer["args"]["step"] == 1
        # the record: the enclosing span's name, and the root's identifier
        assert inner["args"]["parent"] == "rt.outer"
        assert outer["args"]["parent"] is None
        assert inner["args"]["dispatch"] == outer["args"]["dispatch"]

    def test_span_registry_histogram(self):
        """The default registry's dl4jtpu_span_seconds{name} is the store of
        seconds and count by name."""
        before = _span_family().labels(name="hist.phase_x").summary() \
            if _span_family() is not None else {"count": 0, "sum": 0.0}
        with span("hist.phase_x") as sp:
            pass
        after = _span_family().labels(name="hist.phase_x").summary()
        assert after["count"] == before["count"] + 1
        assert after["sum"] - before["sum"] == pytest.approx(sp.duration_s,
                                                             abs=1e-8)

    def test_explicit_start_stop_and_misuse(self):
        s = span("manual.one")
        s.start()
        assert s.stop() >= 0
        with pytest.raises(RuntimeError):
            s.stop()  # double stop
        assert len(_own_events("manual.")) == 1

    def test_roots_get_distinct_dispatch_ids_per_thread_stack(self):
        """Two roots never share an identifier; a span opened on another
        thread is a root of its own, whatever is open here."""
        import threading

        seen = {}

        def other():
            with span("ids.thread") as sp:
                seen["thread"] = (sp.parent, sp.dispatch)

        with span("ids.a") as a:
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        with span("ids.b") as b:
            with span("ids.child") as child:
                pass
        assert seen["thread"][0] is None
        assert len({a.dispatch, b.dispatch, seen["thread"][1]}) == 3
        assert (child.parent, child.dispatch) == ("ids.b", b.dispatch)

    def test_span_wraps_device_work_in_profiler_trace(self, tmp_path):
        """Host spans enter jax.profiler.TraceAnnotation: under an active
        profiler capture the span name lands in the xplane, aligning host
        spans with XLA slices in one timeline."""
        import os

        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu import profiler

        logdir = str(tmp_path / "tr")
        f = jax.jit(lambda a: a @ a)
        a = jnp.ones((64, 64))
        f(a)  # compile outside the capture
        with profiler.trace(logdir):
            with span("telemetry_step_span"):
                np.asarray(f(a))
        found = [os.path.join(d, fn) for d, _, fs in os.walk(logdir)
                 for fn in fs]
        assert found, "no trace written"


# --------------------------------------------------------------------------
# watchdog
# --------------------------------------------------------------------------
class TestWatchdog:
    def test_nan_loss_event(self):
        events = []
        wd = Watchdog(sinks=[events.append], registry=MetricsRegistry())
        wd.observe(iteration=3, loss=float("nan"), grad_norm=1.0)
        assert [e.kind for e in events] == [NAN_LOSS]
        assert events[0].iteration == 3

    def test_nonfinite_flag_fires_even_with_finite_loss(self):
        wd = Watchdog(sinks=[], registry=MetricsRegistry())
        wd.observe(iteration=1, loss=0.5, grad_norm=1.0, nonfinite=1.0)
        assert [e.kind for e in wd.events] == [NAN_LOSS]

    def test_exploding_grad_norm(self):
        reg = MetricsRegistry()
        wd = Watchdog(sinks=[], grad_norm_limit=10.0, registry=reg)
        wd.observe(iteration=1, loss=0.5, grad_norm=5.0)
        wd.observe(iteration=2, loss=0.5, grad_norm=50.0)
        kinds = [e.kind for e in wd.events]
        assert kinds == ["exploding-grad-norm"]
        fam = reg.get("dl4jtpu_anomalies_total")
        assert fam.labels(kind="exploding-grad-norm").value == 1

    def test_stalled_step_time_rolling_median(self):
        wd = Watchdog(sinks=[], stall_factor=5.0, stall_warmup_steps=3,
                      registry=MetricsRegistry())
        for i in range(4):
            wd.observe(iteration=i, loss=0.5, grad_norm=1.0, step_time_s=0.01)
        wd.observe(iteration=9, loss=0.5, grad_norm=1.0, step_time_s=1.0)
        assert [e.kind for e in wd.events] == ["stalled-step-time"]
        # the stall did not poison the baseline
        wd.observe(iteration=10, loss=0.5, grad_norm=1.0, step_time_s=0.01)
        assert len(wd.events) == 1

    def test_broken_sink_does_not_raise(self):
        def boom(event):
            raise RuntimeError("sink down")

        wd = Watchdog(sinks=[boom], registry=MetricsRegistry())
        wd.observe(iteration=1, loss=float("inf"), grad_norm=1.0)
        assert len(wd.events) == 1

    def test_watchdog_fires_on_injected_nan_training(self):
        """End to end: NaN features -> NaN loss inside the jitted scan ->
        flagged by the device vector -> watchdog event at fetch time."""
        events = []
        reg = MetricsRegistry()
        wd = Watchdog(sinks=[events.append], registry=reg)
        tel = Telemetry(registry=reg, fetch_every=4, watchdog=wd)
        net = _two_layer_net().set_telemetry(tel)
        xs, ys = _staged_data()
        xs[1, 0, 0] = np.nan  # poison one staged batch
        net.fit_on_device(xs, ys, steps=3)
        assert any(e.kind == NAN_LOSS for e in events)
        assert reg.get("dl4jtpu_train_nonfinite_steps_total").value >= 1


# --------------------------------------------------------------------------
# the acceptance core: telemetry on the fit paths
# --------------------------------------------------------------------------
class TestTelemetryFitOnDevice:
    def test_exposes_metrics_via_snapshot_and_prometheus(self):
        reg = MetricsRegistry()
        tel = Telemetry(registry=reg, fetch_every=4)
        net = _two_layer_net().set_telemetry(tel)
        xs, ys = _staged_data()
        losses = net.fit_on_device(xs, ys, steps=6)
        snap = reg.snapshot()
        assert snap["dl4jtpu_train_steps_total"]["values"][0]["value"] == 6
        loss_gauge = snap["dl4jtpu_train_loss"]["values"][0]["value"]
        assert loss_gauge == pytest.approx(float(losses[-1]), rel=1e-5)
        assert snap["dl4jtpu_train_grad_norm"]["values"][0]["value"] > 0
        st = snap["dl4jtpu_train_step_time_seconds"]["values"][0]
        assert st["count"] == 6 and st["sum"] > 0
        text = reg.prometheus_text()
        assert "dl4jtpu_train_steps_total 6" in text
        assert "dl4jtpu_train_loss " in text
        assert "dl4jtpu_train_step_time_seconds_bucket" in text
        assert "dl4jtpu_train_grad_norm " in text

    def test_metrics_scrape_over_ui_server(self):
        """ISSUE 2 acceptance: the same run's metrics come back over
        ui/server.py GET /metrics (Prometheus) and /api/telemetry (JSON)."""
        from deeplearning4j_tpu.ui.server import UIServer

        reg = MetricsRegistry()
        net = _two_layer_net().set_telemetry(Telemetry(registry=reg,
                                                       fetch_every=4))
        xs, ys = _staged_data()
        net.fit_on_device(xs, ys, steps=6)
        server = UIServer(port=0, registry=reg)
        try:
            base = f"http://127.0.0.1:{server.port}"
            body = urllib.request.urlopen(base + "/metrics").read().decode()
            assert "dl4jtpu_train_steps_total 6" in body
            assert "dl4jtpu_train_loss " in body
            assert "dl4jtpu_train_step_time_seconds_bucket" in body
            assert "dl4jtpu_train_grad_norm " in body
            doc = json.loads(
                urllib.request.urlopen(base + "/api/telemetry").read())
            assert doc["metrics"]["dl4jtpu_train_steps_total"][
                "values"][0]["value"] == 6
            assert "system" in doc and doc["system"]["device_count"] >= 1
        finally:
            server.stop()

    def test_counting_tracer_single_compile_bounded_fetches(self, monkeypatch):
        """ISSUE 2 acceptance: with telemetry enabled, fit_on_device's step
        is compiled once (the trace hook inside step_stats fires at trace
        time only) and device metrics are fetched at most ceil(steps/K)
        times — no per-step host sync."""
        traces = []
        monkeypatch.setattr(tdevice, "_TRACE_HOOK",
                            lambda: traces.append(1))
        fetch_calls = []
        real_fetch = Telemetry._fetch
        monkeypatch.setattr(
            Telemetry, "_fetch",
            staticmethod(lambda a: (fetch_calls.append(1), real_fetch(a))[1]),
        )
        K, steps = 2, 6
        tel = Telemetry(registry=MetricsRegistry(), fetch_every=K)
        net = _two_layer_net().set_telemetry(tel)
        xs, ys = _staged_data()
        net.fit_on_device(xs, ys, steps=steps)
        # lax.scan may trace its body a bounded number of times while
        # building ONE program — but never once per step
        first_traces = len(traces)
        assert 1 <= first_traces < steps
        assert len(fetch_calls) == 1  # one stacked fetch for the window
        assert len(fetch_calls) <= math.ceil(steps / K)
        # a second same-shape run reuses the compiled program: zero retraces
        net.fit_on_device(xs, ys, steps=steps)
        assert len(traces) == first_traces
        assert len(fetch_calls) == 2
        assert tel.fetch_count == 2
        assert tel.steps.value == 2 * steps

    def test_per_batch_fit_fetches_every_k_steps(self, monkeypatch):
        traces = []
        monkeypatch.setattr(tdevice, "_TRACE_HOOK",
                            lambda: traces.append(1))
        K, iterations = 3, 7
        tel = Telemetry(registry=MetricsRegistry(), fetch_every=K)
        net = _two_layer_net().set_telemetry(tel)
        xs, ys = _staged_data(num_batches=1)
        net.fit((xs[0], ys[0]), epochs=iterations)  # one batch per epoch
        assert len(traces) == 1  # per-batch jitted step compiled once
        # ceil(7/3): two K-full flushes + the end-of-fit drain
        assert tel.fetch_count == math.ceil(iterations / K)
        assert tel.steps.value == iterations

    def test_staged_and_per_batch_agree_with_untelemetered_run(self):
        """The telemetry variant of the step must not change numerics."""
        xs, ys = _staged_data()
        plain = _two_layer_net()
        base = plain.fit_on_device(xs, ys, steps=5)
        instrumented = _two_layer_net().set_telemetry(
            Telemetry(registry=MetricsRegistry(), fetch_every=2))
        got = instrumented.fit_on_device(xs, ys, steps=5)
        np.testing.assert_allclose(np.asarray(base), np.asarray(got),
                                   rtol=1e-6)

    def test_computation_graph_fit_on_device_telemetry(self):
        from deeplearning4j_tpu import (
            ComputationGraph,
            ComputationGraphConfiguration,
        )

        conf = (
            ComputationGraphConfiguration.builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(8))
            .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
            .add_layer("out",
                       OutputLayer(n_out=4, activation="softmax",
                                   loss="mcxent"), "d")
            .set_outputs("out")
            .build()
        )
        reg = MetricsRegistry()
        g = ComputationGraph(conf).init().set_telemetry(
            Telemetry(registry=reg, fetch_every=4))
        xs, ys = _staged_data()
        g.fit_on_device(xs, ys, steps=4)
        snap = reg.snapshot()
        assert snap["dl4jtpu_train_steps_total"]["values"][0]["value"] == 4
        assert snap["dl4jtpu_train_grad_norm"]["values"][0]["value"] > 0


# --------------------------------------------------------------------------
# listener / bench integration
# --------------------------------------------------------------------------
class TestIntegrations:
    def test_score_listener_records_into_registry(self):
        from deeplearning4j_tpu import ScoreIterationListener

        reg = MetricsRegistry()
        net = _two_layer_net()
        net.set_listeners(ScoreIterationListener(print_every=2, registry=reg))
        xs, ys = _staged_data(num_batches=1)
        net.fit((xs[0], ys[0]), epochs=4)
        assert reg.get("dl4jtpu_score_reports_total").value == 2
        assert reg.get("dl4jtpu_score").value == pytest.approx(net.score())

    def test_step_timer_records_into_registry(self):
        from deeplearning4j_tpu.profiler import StepTimer

        from deeplearning4j_tpu.telemetry import get_registry

        def steps():
            # a phase is the span dl4j.<component>.<phase>: the one store
            fam = get_registry().histogram(
                "dl4jtpu_span_seconds", "host span durations",
                labelnames=("name",))
            return fam.labels(name="dl4j.unit.step").count

        before = steps()
        t = StepTimer(component="unit")
        with t.phase("data"):
            pass
        with t.phase("step"):
            pass
        with t.phase("step"):
            pass
        assert steps() - before == 2
        assert t.breakdown()["step"]["count"] == 2  # dict API intact

    def test_streaming_pipeline_counters(self):
        from deeplearning4j_tpu.streaming.pipeline import (
            QueueSource,
            Route,
            StreamingPipeline,
        )

        class CollectRoute(Route):
            def __init__(self):
                self.batches = []

            def on_batch(self, features, labels):
                self.batches.append((features, labels))

        reg = MetricsRegistry()
        src = QueueSource()
        route = CollectRoute()
        with StreamingPipeline(src, [route], batch=4, linger=0.05,
                               registry=reg):
            for i in range(8):
                src.put(np.full((3,), float(i)))
            import time as _time

            deadline = _time.monotonic() + 5
            while (reg.get("dl4jtpu_streaming_records_total").value < 8
                   and _time.monotonic() < deadline):
                _time.sleep(0.01)
        assert reg.get("dl4jtpu_streaming_records_total").value == 8
        assert reg.get("dl4jtpu_streaming_batches_total").value >= 2

    def test_param_server_counters(self):
        from deeplearning4j_tpu.parallel.param_server import (
            ParameterServer,
            ParameterServerClient,
        )

        reg = MetricsRegistry()
        with ParameterServer(np.zeros(4, np.float32), learning_rate=0.5,
                             registry=reg) as srv:
            client = ParameterServerClient(srv.host, srv.port)
            client.push_gradient(np.ones(4, np.float32))
            out = client.pull_params()
            client.close()
        np.testing.assert_allclose(out, -0.5 * np.ones(4))
        assert reg.get("dl4jtpu_param_server_pushes_total").value == 1
        assert reg.get("dl4jtpu_param_server_pulls_total").value == 1
        assert reg.get("dl4jtpu_param_server_updates").value == 1

    def test_bench_telemetry_block_schema(self):
        import bench

        block = bench._telemetry_block([0.01, 0.02], mfu_pct=12.5,
                                       extra_gauges={"bench_x": 3.0})
        assert block["step_time_seconds"]["count"] == 2
        assert block["step_time_seconds"]["mean"] == pytest.approx(0.015)
        assert block["bench_mfu_pct"] == 12.5
        assert block["bench_x"] == 3.0
        json.dumps(block)

    def test_global_registry_is_shared(self):
        assert get_registry() is get_registry()
