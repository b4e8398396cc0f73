"""Serving front-end (ISSUE 7): micro-batcher, service, decode, HTTP.

Pins the serving acceptance criteria:

- concurrent requests COALESCE (batches < requests) under the latency
  budget and the row cap is a hard ceiling (the compiled-bucket bound);
- coalesced + masked-pad output is bit-exact vs per-request unbatched
  ``output()``;
- zero warm-request compiles under mixed request shapes after
  ``warmup()`` (compile-manager counter + backend_compile ground truth);
- continuous-batching RNN decode: interleaved sessions in one slot batch
  reproduce each session's solo trajectory exactly (the
  ``rnn_time_step`` mask-holds-state contract);
- ``dl4jtpu_serve_*`` metrics + ``/api/serving`` + the HTTP endpoints.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTM, RnnOutputLayer
from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
from deeplearning4j_tpu.serving import (
    DecodeServer,
    InferenceService,
    MicroBatcher,
    get_service,
    set_service,
)
from deeplearning4j_tpu.telemetry import MetricsRegistry


def _f32(net):
    f32 = jax.tree_util.tree_map(
        lambda a: a.astype(np.float32)
        if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
        net.params)
    return net.init(params=f32)


def _mlp(n_in=5, seed=7):
    return _f32(MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(n_in),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed)).init())


def _rnn(n_in=6, seed=3):
    return _f32(MultiLayerNetwork(MultiLayerConfiguration(
        layers=[GravesLSTM(n_out=10),
                RnnOutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.recurrent(n_in),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed)).init())


class TestMicroBatcher:
    def test_coalesces_concurrent_requests(self):
        calls = []

        def dispatch(feats):
            calls.append(int(feats.shape[0]))
            return feats * 2.0

        mb = MicroBatcher(dispatch, max_delay_ms=50, max_batch=64)
        try:
            futs = [mb.submit(np.full((2, 3), float(i), np.float32))
                    for i in range(6)]
            outs = [f.result(timeout=10) for f in futs]
            for i, out in enumerate(outs):
                np.testing.assert_array_equal(out, np.full((2, 3), 2.0 * i))
            assert len(calls) < 6, calls  # coalesced
            assert sum(calls) == 12
        finally:
            mb.stop()

    def test_row_cap_is_a_hard_ceiling(self):
        calls = []

        def dispatch(feats):
            calls.append(int(feats.shape[0]))
            return feats

        mb = MicroBatcher(dispatch, max_delay_ms=50, max_batch=8)
        try:
            futs = [mb.submit(np.zeros((3, 2), np.float32))
                    for _ in range(5)]
            for f in futs:
                f.result(timeout=10)
            assert max(calls) <= 8, calls
            assert sum(calls) == 15
        finally:
            mb.stop()

    def test_mixed_shapes_never_mix_in_one_dispatch(self):
        shapes = []

        def dispatch(feats):
            shapes.append(feats.shape[1:])
            return feats

        mb = MicroBatcher(dispatch, max_delay_ms=30, max_batch=64)
        try:
            futs = [mb.submit(np.zeros((1, d), np.float32))
                    for d in (3, 4, 3, 4, 3)]
            for f in futs:
                f.result(timeout=10)
            assert set(shapes) == {(3,), (4,)}
        finally:
            mb.stop()

    def test_dispatch_error_rejects_only_that_batch(self):
        def dispatch(feats):
            if feats.shape[0] == 1:
                raise RuntimeError("boom")
            return feats

        mb = MicroBatcher(dispatch, max_delay_ms=0, max_batch=64)
        try:
            bad = mb.submit(np.zeros((1, 2), np.float32))
            with pytest.raises(RuntimeError, match="boom"):
                bad.result(timeout=10)
            ok = mb.submit(np.zeros((2, 2), np.float32))
            assert ok.result(timeout=10).shape == (2, 2)
        finally:
            mb.stop()


class TestInferenceService:
    def test_coalesced_output_matches_unbatched(self, rng, monkeypatch):
        net = _mlp()
        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=20)
        try:
            svc.register("m", net)
            xs = [rng.normal(size=(1 + i % 3, 5)).astype(np.float32)
                  for i in range(10)]
            results = {}

            def fire(i):
                results[i] = svc.predict("m", xs[i], timeout_s=30)

            threads = [threading.Thread(target=fire, args=(i,))
                       for i in range(len(xs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            monkeypatch.setenv("DL4JTPU_INFER", "legacy")
            for i, x in enumerate(xs):
                ref = np.asarray(net.output(x))
                # coalesced bucket vs unbatched: two programs, equal to
                # f32's last ulp or two (XLA:CPU gemv vs gemm)
                np.testing.assert_allclose(np.asarray(results[i]), ref,
                                           rtol=5e-7, atol=0)
        finally:
            svc.stop()

    def test_zero_warm_compiles_after_warmup(self, rng):
        net = _mlp(seed=13)
        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=5,
                               max_batch=16)
        cm = get_compile_manager()
        try:
            svc.register("m", net)
            svc.warmup("m", np.zeros((1, 5), np.float32), argmax=True)
            before = cm.compiles.value
            threads = [
                threading.Thread(
                    target=lambda i=i: svc.predict(
                        "m", rng.normal(size=(1 + i % 5, 5))
                        .astype(np.float32), argmax=bool(i % 2)))
                for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert cm.compiles.value - before == 0
        finally:
            svc.stop()

    def test_metrics_and_stats(self, rng):
        reg = MetricsRegistry()
        svc = InferenceService(registry=reg, max_delay_ms=1)
        try:
            svc.register("m", _mlp(seed=17))
            for _ in range(4):
                svc.predict("m", rng.normal(size=(2, 5)).astype(np.float32))
            stats = svc.stats()["models"]["m"]
            assert stats["requests_total"] == 4
            assert stats["rows_total"] == 8
            assert stats["latency_seconds"]["p50"] is not None
            assert stats["latency_seconds"]["p99"] is not None
            assert 0 < stats["mean_batch_fill_ratio"] <= 1.0
            assert reg.get("dl4jtpu_serve_requests_total") is not None
            val = reg.get("dl4jtpu_serve_requests_total").labels(
                model="m").value
            assert val == 4
            assert reg.get("dl4jtpu_serve_latency_seconds").labels(
                model="m").count == 4
        finally:
            svc.stop()

    def test_serve_dispatch_flight_events(self, rng):
        from deeplearning4j_tpu.telemetry.flight_recorder import (
            get_flight_recorder,
        )

        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=1)
        try:
            svc.register("m", _mlp(seed=19))
            svc.predict("m", rng.normal(size=(2, 5)).astype(np.float32))
            events = get_flight_recorder().snapshot(512)["events"]
            serve = [e for e in events if e["kind"] == "serve_dispatch"]
            assert serve and serve[-1]["model"] == "m"
            assert serve[-1]["rows"] >= 2
        finally:
            svc.stop()

    def test_argmax_requests_coalesce_through_the_batcher(self, rng):
        """ISSUE 10 satellite: fused-argmax requests dispatched DIRECT
        before; now they coalesce on their own batcher (never mixed with
        logits requests) and still return int-only, bit-exact classes."""
        net = _mlp(seed=23)
        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=25)
        try:
            svc.register("m", net)
            svc.warmup("m", np.zeros((1, 5), np.float32), argmax=True)
            xs = [rng.normal(size=(2, 5)).astype(np.float32)
                  for _ in range(8)]
            outs = [None] * len(xs)

            def client(i):
                outs[i] = np.asarray(svc.predict("m", xs[i], argmax=True))

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(xs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = svc.stats()["models"]["m"]
            # coalesced: strictly fewer dispatches than requests
            assert stats["batches_total"] < len(xs)
            assert stats["last_dispatch"]["kind"] == "argmax"
            assert stats["last_dispatch"]["requests"] >= 2
            from deeplearning4j_tpu.runtime import inference as _inf

            for x, out in zip(xs, outs):
                assert np.issubdtype(out.dtype, np.integer)
                np.testing.assert_array_equal(
                    out, _inf.mln_output(net, x, argmax=True))
        finally:
            svc.stop()

    def test_request_rows_histogram_feeds_max_batch_tuning(self, rng):
        reg = MetricsRegistry()
        svc = InferenceService(registry=reg, max_delay_ms=1)
        try:
            svc.register("m", _mlp(seed=29))
            for rows in (1, 2, 2, 5):
                svc.predict("m", rng.normal(size=(rows, 5)).astype(
                    np.float32))
            svc.predict("m", rng.normal(size=(3, 5)).astype(np.float32),
                        argmax=True)
            fam = reg.get("dl4jtpu_serve_request_rows")
            child = fam.labels(model="m")
            assert child.count == 5  # argmax requests are size-classed too
            assert child.summary()["sum"] == 1 + 2 + 2 + 5 + 3
        finally:
            svc.stop()

    def test_hot_swap_flips_params_without_recompiling(self, rng):
        """ISSUE 10: the train→serve handoff — a params-pointer flip behind
        the service lock changes served predictions, keeps executables."""
        net_a, net_b = _mlp(seed=31), _mlp(seed=37)
        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=1)
        try:
            svc.register("m", net_a)
            svc.warmup("m", np.zeros((1, 5), np.float32))
            x = rng.normal(size=(3, 5)).astype(np.float32)
            out_a = np.asarray(svc.predict("m", x))
            cm = get_compile_manager()
            before = cm.compiles.value
            svc.hot_swap("m", net=net_b, version=7)
            out_b = np.asarray(svc.predict("m", x))
            assert cm.compiles.value - before == 0
            assert np.abs(out_b - out_a).max() > 0
            from deeplearning4j_tpu.runtime import inference as _inf

            np.testing.assert_array_equal(out_b, _inf.mln_output(net_b, x))
            stats = svc.stats()["models"]["m"]
            assert stats["version"] == 7 and stats["swaps_total"] == 1
            from deeplearning4j_tpu.telemetry.flight_recorder import (
                get_flight_recorder,
            )

            events = [e for e in get_flight_recorder().events
                      if e["kind"] == "serve_swap"]
            assert events and events[-1]["version"] == 7
        finally:
            svc.stop()

    def test_multi_model_tenancy_shares_the_lru(self, rng):
        cm = get_compile_manager()
        svc = InferenceService(registry=MetricsRegistry(), max_delay_ms=1)
        try:
            svc.register("a", _mlp(seed=23))
            svc.register("b", _mlp(n_in=9, seed=29))
            svc.predict("a", rng.normal(size=(2, 5)).astype(np.float32))
            svc.predict("b", rng.normal(size=(2, 9)).astype(np.float32))
            kinds = [cm._key_kind(k) for k in cm._entries]
            assert kinds.count("mln_infer") >= 2
        finally:
            svc.stop()

    def test_unknown_model_raises(self):
        svc = InferenceService(registry=MetricsRegistry())
        try:
            with pytest.raises(KeyError):
                svc.predict("nope", np.zeros((1, 2), np.float32))
        finally:
            svc.stop()


class TestContinuousDecode:
    def test_interleaved_sessions_match_solo_runs(self, rng, monkeypatch):
        """Two sessions decoding through ONE slot batch must reproduce each
        session's solo trajectory exactly — the continuous-batching
        acceptance (rnn_time_step state continuity across coalesced decode
        batches)."""
        net = _rnn(seed=31)
        dec = DecodeServer(net, capacity=4, max_delay_ms=30)
        try:
            s1, s2 = dec.open(), dec.open()
            steps1 = [rng.normal(size=(6,)).astype(np.float32)
                      for _ in range(4)]
            steps2 = [rng.normal(size=(6,)).astype(np.float32)
                      for _ in range(4)]
            outs1, outs2 = [], []

            def run(sid, steps, sink):
                for s in steps:
                    sink.append(np.asarray(dec.step(sid, s, timeout_s=30)))

            t1 = threading.Thread(target=run, args=(s1, steps1, outs1))
            t2 = threading.Thread(target=run, args=(s2, steps2, outs2))
            t1.start(); t2.start(); t1.join(); t2.join()
        finally:
            dec.stop()
        # solo references: one net per session, batch 1, legacy stream
        monkeypatch.setenv("DL4JTPU_INFER", "legacy")
        for steps, outs in ((steps1, outs1), (steps2, outs2)):
            solo = MultiLayerNetwork(net.conf).init(params=net.params)
            solo.rnn_clear_previous_state()
            for s, got in zip(steps, outs):
                ref = np.asarray(solo.rnn_time_step(s[None, :]))[0]
                np.testing.assert_allclose(got, ref, rtol=0, atol=1e-6)

    def test_slot_reuse_resets_state(self, rng):
        net = _rnn(seed=37)
        dec = DecodeServer(net, capacity=2, max_delay_ms=0)
        try:
            x = rng.normal(size=(6,)).astype(np.float32)
            s1 = dec.open()
            first = np.asarray(dec.step(s1, x, timeout_s=30))
            np.asarray(dec.step(s1, x, timeout_s=30))  # state advances
            dec.close(s1)
            s2 = dec.open()  # same slot, fresh state
            again = np.asarray(dec.step(s2, x, timeout_s=30))
            np.testing.assert_allclose(again, first, rtol=0, atol=1e-6)
        finally:
            dec.stop()

    def test_capacity_exhaustion_raises(self):
        net = _rnn(seed=41)
        dec = DecodeServer(net, capacity=1, max_delay_ms=0)
        try:
            dec.open()
            with pytest.raises(RuntimeError, match="slots"):
                dec.open()
        finally:
            dec.stop()


class TestServingHTTP:
    @pytest.fixture
    def served(self, rng):
        from deeplearning4j_tpu.ui.server import UIServer

        svc = InferenceService(max_delay_ms=5)
        set_service(svc)
        svc.register("mlp", _mlp(seed=43))
        svc.register("rnn", _rnn(seed=47))
        server = UIServer(port=0)
        try:
            yield f"http://127.0.0.1:{server.port}", svc
        finally:
            server.stop()
            svc.stop()
            set_service(None)

    @staticmethod
    def _post(base, path, payload):
        req = urllib.request.Request(
            base + path, json.dumps(payload).encode(),
            {"Content-Type": "application/json"})
        return json.loads(urllib.request.urlopen(req, timeout=30).read())

    def test_predict_endpoint(self, served, rng):
        base, _ = served
        x = rng.normal(size=(3, 5)).astype(np.float32)
        out = self._post(base, "/serving/predict",
                         {"model": "mlp", "features": x.tolist()})
        assert np.asarray(out["output"]).shape == (3, 3)
        cls = self._post(base, "/serving/predict",
                         {"model": "mlp", "features": x.tolist(),
                          "argmax": True})
        assert np.asarray(cls["classes"]).shape == (3,)

    def test_predict_unknown_model_404(self, served):
        base, _ = served
        with pytest.raises(urllib.error.HTTPError) as exc:
            self._post(base, "/serving/predict",
                       {"model": "nope", "features": [[0.0]]})
        assert exc.value.code == 404

    def test_predict_malformed_400(self, served):
        base, _ = served
        with pytest.raises(urllib.error.HTTPError) as exc:
            self._post(base, "/serving/predict", {"model": "mlp"})
        assert exc.value.code == 400

    def test_rnn_session_endpoints(self, served, rng):
        base, _ = served
        opened = self._post(base, "/serving/rnn",
                            {"model": "rnn", "op": "open"})
        sid = opened["session"]
        out = self._post(base, "/serving/rnn",
                         {"model": "rnn", "session": sid,
                          "features": rng.normal(size=(6,)).tolist()})
        assert len(out["output"]) == 4
        closed = self._post(base, "/serving/rnn",
                            {"model": "rnn", "op": "close", "session": sid})
        assert closed["closed"] == sid

    def test_api_serving_and_metrics(self, served, rng):
        base, svc = served
        svc.predict("mlp", rng.normal(size=(2, 5)).astype(np.float32))
        stats = json.loads(urllib.request.urlopen(
            base + "/api/serving", timeout=10).read())
        assert "mlp" in stats["models"]
        assert stats["models"]["mlp"]["requests_total"] >= 1
        assert "compile_cache" in stats
        metrics = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        assert "dl4jtpu_serve_requests_total" in metrics
        assert "dl4jtpu_serve_batch_fill_ratio" in metrics
