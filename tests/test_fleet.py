"""Fleet subsystem tests (ISSUE 13).

Fast tier: warm-boot bundle roundtrip/schema, bundle install into a
sandboxed tuned/calibration state, the named-service registry bugfix,
per-model admission knobs (queue-depth + latency-budget shed), the shared
forced-CPU env recipe, batcher/service drain semantics, and the
checkpoint-store bus helpers.

Slow tier (real OS processes, same recipe as test_multiprocess): a fresh
worker serves its first request with ZERO backend compiles when a bundle
exists (jax.monitoring counter-pinned inside the worker), rolling-rollout
bit-exactness (every response during the roll equals exactly the v1 or v2
reference, never a torn mix), worker-kill respawn + 429 shedding under
overload, and drain completing in-flight requests. check.sh's fleet
self-scan re-proves the same contract in CI.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (DenseLayer, InputType,
                                MultiLayerConfiguration, MultiLayerNetwork,
                                OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.fleet import (FleetRouter, build_bundle,
                                      bundle_filename, install_bundle,
                                      load_bundle, save_bundle)
from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
from deeplearning4j_tpu.serving import (AdmissionError, InferenceService,
                                        MicroBatcher, ServiceDraining,
                                        get_service, reset_services,
                                        service_names, set_service)
from deeplearning4j_tpu.tune.knobs import scoped_env
from deeplearning4j_tpu.utils.subproc import forced_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_net(n_in=8, n_out=4, seed=7):
    return MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=n_out, activation="softmax",
                            loss="mcxent")],
        input_type=InputType.feed_forward(n_in),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=seed)).init()


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _post(url, payload, timeout=60):
    req = urllib.request.Request(url, json.dumps(payload).encode(),
                                 {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------------------
# warm-boot bundle (fast)
# ---------------------------------------------------------------------------
class TestWarmBootBundle:
    def test_roundtrip_and_schema(self, tmp_path):
        net = _toy_net()
        store = CheckpointStore(str(tmp_path / "store"))
        store.save(net)
        bundle = build_bundle(net, example=np.zeros((1, 8), np.float32),
                              argmax=True, max_batch=8)
        assert bundle["bundle_version"] == 1
        assert bundle["warmup"]["buckets"] == [1, 2, 4, 8]
        assert bundle["warmup"]["example_shape"] == [8]
        assert bundle["warmup"]["argmax"] is True
        assert bundle["signature"] and bundle["backend"] and (
            bundle["topology"])
        path = save_bundle(store, bundle)
        assert os.path.basename(path) == bundle_filename(
            bundle["signature"], bundle["backend"], bundle["topology"])
        # sidecar is invisible to the version scan
        assert store.latest_version() == 1
        loaded = load_bundle(store)
        assert loaded == bundle
        assert load_bundle(store, net) == bundle
        assert load_bundle(store, signature="nope") is None

    def test_example_derived_from_feed_forward_conf(self, tmp_path):
        bundle = build_bundle(_toy_net(n_in=12), max_batch=4)
        assert bundle["warmup"]["example_shape"] == [12]
        assert bundle["warmup"]["example_dtype"] == "float32"

    def test_install_applies_tuned_and_calibration(self, tmp_path):
        from deeplearning4j_tpu.ops import kernel_select as ks
        from deeplearning4j_tpu.tune import store as tuned_store

        net = _toy_net()
        src_tuned = tmp_path / "src-TUNED.json"
        dst_tuned = tmp_path / "dst-TUNED.json"
        dst_cal = tmp_path / "dst-KERNEL_CALIBRATION.json"
        with scoped_env(DL4JTPU_TUNED_PATH=str(src_tuned)):
            key = tuned_store.key_for(net)
            tuned_store.TunedStore().put(
                key, {"serve_max_batch": 16, "serve_max_queue_depth": 32},
                objective="serve")
            bundle = build_bundle(net, example=np.zeros((1, 8), np.float32))
        assert bundle["tuned"]["key"] == key
        assert bundle["tuned"]["entry"]["config"]["serve_max_batch"] == 16
        bundle["kernel"]["calibration"] = {"mlp": 1.25}
        with scoped_env(DL4JTPU_TUNED_PATH=str(dst_tuned),
                        DL4JTPU_KERNEL_CALIBRATION=str(dst_cal)):
            report = install_bundle(bundle)
            assert report["tuned"] is True
            assert report["calibration"] is True
            entry = tuned_store.TunedStore().get(key)
            assert entry["config"]["serve_max_queue_depth"] == 32
            assert json.load(open(dst_cal)) == {"mlp": 1.25}
            # an EXISTING calibration file is never clobbered
            report2 = install_bundle(
                {**bundle,
                 "kernel": {**bundle["kernel"],
                            "calibration": {"mlp": 9.0}}})
            assert report2["calibration"] is False
            assert ks.calibration_snapshot()[1] == {"mlp": 1.25}

    def test_stale_bundle_tolerated(self, tmp_path):
        store = CheckpointStore(str(tmp_path / "store"))
        # unknown knobs in the tuned slice must not poison install
        report = install_bundle({
            "bundle_version": 1,
            "tuned": {"key": "k", "entry": {"config": {"no_such_knob": 1}}},
            "warmup": {"buckets": [1]}})
        assert report["tuned"] is False
        # future-schema bundles are skipped by load
        with open(store.artifact_path("warmboot-x.cpu.d1.json"), "w") as f:
            json.dump({"bundle_version": 99, "signature": "x"}, f)
        assert load_bundle(store) is None


# ---------------------------------------------------------------------------
# named service registry (fast) — the get_service singleton bugfix
# ---------------------------------------------------------------------------
class TestServiceRegistry:
    def test_named_services_are_isolated(self):
        reset_services()
        try:
            default = get_service()
            edge = get_service("edge")
            assert default is not edge
            assert get_service() is default
            assert get_service("edge") is edge
            assert service_names() == ["default", "edge"]
            net = _toy_net()
            edge.register("m", net)
            assert edge.models() == ["m"]
            assert default.models() == []  # no cross-contamination
        finally:
            reset_services()

    def test_set_and_reset(self):
        reset_services()
        try:
            svc = InferenceService(max_delay_ms=0.0)
            set_service(svc, "mine")
            assert get_service("mine") is svc
            set_service(None, "mine")
            assert get_service("mine") is not svc
            before = get_service()
            reset_services()
            assert service_names() == []
            assert get_service() is not before
        finally:
            reset_services()


# ---------------------------------------------------------------------------
# per-model admission knobs (fast)
# ---------------------------------------------------------------------------
class TestAdmission:
    def test_per_model_batcher_knobs_override_service(self):
        svc = InferenceService(max_delay_ms=5.0, max_batch=64)
        try:
            svc.register("a", _toy_net())
            svc.register("b", _toy_net(seed=8), max_delay_ms=0.0,
                         max_batch=8)
            stats = svc.stats()["models"]
            assert stats["a"]["batcher"]["max_batch"] == 64
            assert stats["b"]["batcher"]["max_batch"] == 8
            assert stats["b"]["batcher"]["max_delay_ms"] == 0.0
        finally:
            svc.stop()

    def test_queue_depth_shed(self):
        svc = InferenceService(max_delay_ms=0.0)
        try:
            svc.register("m", _toy_net(), max_queue_depth=1)
            entry = svc._entry("m")
            assert entry.max_queue_depth == 1
            # make the queue LOOK saturated without racing the dispatcher
            entry.batcher.queue_depth = lambda: 5
            with pytest.raises(AdmissionError) as ei:
                svc.predict("m", np.zeros((1, 8), np.float32))
            assert ei.value.reason == "queue_depth"
            assert ei.value.retry_after_s >= 0.05
            assert svc.stats()["models"]["m"]["admission"]["shed_total"] == 1
        finally:
            svc.stop()

    def test_latency_budget_shed(self):
        svc = InferenceService(max_delay_ms=0.0)
        try:
            svc.register("m", _toy_net(), latency_budget_ms=10.0)
            entry = svc._entry("m")
            entry.latencies.extend([0.5] * 64)  # p99 far over 10ms
            with pytest.raises(AdmissionError) as ei:
                svc.predict("m", np.zeros((1, 8), np.float32))
            assert ei.value.reason == "latency_budget"
        finally:
            svc.stop()

    def test_env_default_applies_when_no_per_model_arg(self):
        with scoped_env(DL4JTPU_SERVE_MAX_QUEUE="7",
                        DL4JTPU_SERVE_LATENCY_BUDGET_MS="125"):
            svc = InferenceService(max_delay_ms=0.0)
            try:
                svc.register("m", _toy_net())
                adm = svc.stats()["models"]["m"]["admission"]
                assert adm["max_queue_depth"] == 7
                assert adm["latency_budget_ms"] == 125.0
            finally:
                svc.stop()

    def test_zero_disables(self):
        svc = InferenceService(max_delay_ms=0.0)
        try:
            svc.register("m", _toy_net(), max_queue_depth=0,
                         latency_budget_ms=0.0)
            adm = svc.stats()["models"]["m"]["admission"]
            assert adm["max_queue_depth"] is None
            assert adm["latency_budget_ms"] is None
        finally:
            svc.stop()

    def test_knob_registry_contexts(self):
        from deeplearning4j_tpu.tune.knobs import get_knob

        for name in ("serve_max_queue_depth", "serve_latency_budget_ms"):
            assert get_knob(name).contexts == ("serve",)


# ---------------------------------------------------------------------------
# drain semantics (fast)
# ---------------------------------------------------------------------------
class TestDrain:
    def test_batcher_drain_waits_for_in_flight(self):
        release = threading.Event()
        dispatched = threading.Event()

        def slow_dispatch(feats):
            dispatched.set()
            release.wait(5)
            return feats

        b = MicroBatcher(slow_dispatch, max_delay_ms=0.0, max_batch=4)
        try:
            fut = b.submit(np.zeros((1, 2), np.float32))
            assert dispatched.wait(5)
            assert b.in_flight() == 1
            assert b.drain(timeout_s=0.2) is False  # still in flight
            release.set()
            assert b.drain(timeout_s=5.0) is True
            assert fut.result(timeout=5) is not None
        finally:
            b.stop()

    def test_service_drain_completes_in_flight_then_refuses(self):
        # a generous latency budget keeps the requests QUEUED (waiting for
        # company) while drain starts — genuinely in flight, not racing
        svc = InferenceService(max_delay_ms=200.0, max_batch=64)
        try:
            svc.register("m", _toy_net())
            results = []
            threads = [threading.Thread(
                target=lambda: results.append(
                    svc.predict("m", np.random.rand(1, 8).astype(
                        np.float32)))) for _ in range(4)]
            for t in threads:
                t.start()
            entry = svc._entry("m")
            deadline = time.monotonic() + 5
            while (entry.batcher.pending() < 4
                   and time.monotonic() < deadline):
                time.sleep(0.002)
            assert entry.batcher.pending() == 4  # all admitted, unresolved
            assert svc.drain(timeout_s=10.0) is True
            for t in threads:
                t.join(timeout=10)
            assert len(results) == 4  # every in-flight request finished
            with pytest.raises(ServiceDraining):
                svc.predict("m", np.zeros((1, 8), np.float32))
        finally:
            svc.stop()


# ---------------------------------------------------------------------------
# shared forced-CPU env recipe (fast)
# ---------------------------------------------------------------------------
class TestForcedCpuEnv:
    def test_recipe(self):
        base = {"XLA_FLAGS": "--foo=1 --xla_force_host_platform_device_count=8",
                "JAX_NUM_PROCESSES": "4", "KEEP": "me"}
        env = forced_cpu_env(2, base=base)
        assert env["JAX_PLATFORMS"] == "cpu"
        # device count REWRITTEN (not appended), unrelated flags kept
        assert env["XLA_FLAGS"] == (
            "--foo=1 --xla_force_host_platform_device_count=2")
        assert "JAX_NUM_PROCESSES" not in env
        assert env["KEEP"] == "me"
        assert base["JAX_NUM_PROCESSES"] == "4"  # input not mutated

    def test_appends_when_absent(self):
        env = forced_cpu_env(3, base={})
        assert env["XLA_FLAGS"] == (
            "--xla_force_host_platform_device_count=3")


# ---------------------------------------------------------------------------
# checkpoint-store bus helpers (fast)
# ---------------------------------------------------------------------------
class TestStoreBus:
    def test_latest_version_and_artifact_path(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        assert store.latest_version() == 0
        store.save(_toy_net())
        assert store.latest_version() == 1
        sidecar = store.artifact_path("warmboot-a.cpu.d1.json")
        assert os.path.dirname(sidecar) == str(tmp_path)
        with pytest.raises(ValueError):
            store.artifact_path("model-v00000002.zip")

    def test_wait_for_version(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        assert store.wait_for_version(1, timeout_s=0.2, poll_s=0.05) is None
        net = _toy_net()

        def publish():
            time.sleep(0.2)
            store.save(net)

        t = threading.Thread(target=publish)
        t.start()
        info = store.wait_for_version(1, timeout_s=10.0, poll_s=0.05)
        t.join()
        assert info is not None and info.version == 1


class TestUiEndpoint:
    def test_api_fleet_lists_registered_routers(self):
        from deeplearning4j_tpu.fleet import get_fleet_routers
        from deeplearning4j_tpu.ui.server import UIServer

        assert get_fleet_routers() == []
        ui = UIServer(port=0)
        try:
            d = _get(f"http://127.0.0.1:{ui.port}/api/fleet")
            assert d == {"routers": []}
        finally:
            ui.stop()


# ---------------------------------------------------------------------------
# subprocess integration (slow): the real-OS-process fleet
# ---------------------------------------------------------------------------
def _seed_store(tmp_path, versions=1):
    """Store + bundle + the net used to build them."""
    net = _toy_net()
    store = CheckpointStore(str(tmp_path / "store"))
    store.save(net)
    for _ in range(versions - 1):
        store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, 8), np.float32), argmax=True,
        max_batch=8))
    return store, net


# ---------------------------------------------------------------------------
# resilience integration (fast, ISSUE 14): hung-worker detection, staggered
# respawn backoff, corrupt-latest worker boot
# ---------------------------------------------------------------------------


class TestHungWorkerDetection:
    def test_frozen_healthz_is_hung_not_crash(self, tmp_path):
        """A worker that accepts TCP but never answers /healthz is a live
        wedged process: the health Deadline must expire, classify it as
        "hung" (not "crash"/"unhealthy") and reap it so the respawn can
        rebind the port."""
        import socket

        from deeplearning4j_tpu.telemetry import MetricsRegistry

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        sock.listen(8)  # handshake completes in-kernel; nothing ever reads
        port = sock.getsockname()[1]
        dummy = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"])
        router = FleetRouter(str(tmp_path), workers=1, respawn=False,
                             health_timeout_s=0.5,
                             registry=MetricsRegistry())
        handle = router.workers[0]
        handle.proc = dummy
        handle.port = port
        handle.alive = True
        handle.ready = True
        try:
            router._check_worker(handle)
            assert handle.down_reason == "hung", handle.down_reason
            assert not handle.ready
            assert dummy.wait(timeout=10) is not None  # reaped, port freed
            assert router.health_deadline.stats()["expired_total"] >= 1
        finally:
            if dummy.poll() is None:
                dummy.kill()
                dummy.wait(timeout=10)
            sock.close()


class TestRespawnBackoffStagger:
    def test_simultaneous_deaths_backoff_staggered(self, tmp_path):
        """Regression for the thundering-herd respawn: simultaneous worker
        deaths must schedule DIFFERENT backoffs (jitter keyed per worker
        id), and the stagger must be deterministic run to run."""
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        router = FleetRouter(str(tmp_path), workers=3, respawn=False,
                             backoff_base_s=0.5, backoff_cap_s=10.0,
                             registry=MetricsRegistry())
        for handle in router.workers:
            router._backoff(handle)
        waits = [h.backoff_s for h in router.workers]
        assert len(set(waits)) == len(waits), waits
        # attempt 1 with jitter=0.5: base <= wait <= 1.5*base
        assert all(0.5 <= w <= 0.75 for w in waits), waits
        router2 = FleetRouter(str(tmp_path), workers=3, respawn=False,
                              backoff_base_s=0.5, backoff_cap_s=10.0,
                              registry=MetricsRegistry())
        for handle in router2.workers:
            router2._backoff(handle)
        assert [h.backoff_s for h in router2.workers] == waits


class TestWorkerBootIntegrity:
    def test_boot_quarantines_corrupt_latest_serves_previous(self, tmp_path):
        """In-process half of the corrupt-latest acceptance: a cold worker
        boot over a store whose newest version is torn must quarantine it,
        serve the previous good version, and swap forward as soon as a
        good NEWER version lands."""
        from deeplearning4j_tpu.fleet.worker import FleetWorker
        from deeplearning4j_tpu.testing.chaos import truncate_file

        store, net = _seed_store(tmp_path, versions=2)
        truncate_file(store.path(2), keep_frac=0.4)
        worker = FleetWorker(str(tmp_path / "store"), max_delay_ms=0,
                             max_batch=8, use_bundle=False)
        try:
            worker.boot()
            assert worker.ready and worker.version == 1
            assert os.path.exists(store.path(2) + ".quarantine")
            out = worker.predict_payload(
                {"features": np.zeros((2, 8), np.float32).tolist()})
            assert len(out["output"]) == 2
            # the quarantined id stays claimed; the next good save is v3
            # and the worker swaps to it with no restart
            v3 = store.save(net).version
            assert v3 == 3
            assert worker.swap_to() == 3
            assert worker.version == 3
        finally:
            worker.shutdown()
            if worker.service is not None:
                worker.service.stop()
            set_service(None, f"fleet-worker:{worker.model}")


@pytest.mark.slow
class TestFleetSubprocess:
    def test_warm_boot_zero_compiles(self, tmp_path):
        """A fresh worker process with a bundle answers its FIRST request
        with zero backend compiles — the in-worker jax.monitoring counter
        (armed before warmup, snapshotted at ready) is the proof."""
        _seed_store(tmp_path)
        env = forced_cpu_env(1)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.fleet.worker",
             "--store", str(tmp_path / "store"), "--max-delay-ms", "0",
             "--max-batch", "8"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            assert line.startswith("FLEET_WORKER_READY"), (
                line, proc.stderr.read())
            port = int(dict(kv.split("=") for kv in line.split()[1:])["port"])
            base = f"http://127.0.0.1:{port}"
            first = _post(base + "/predict",
                          {"features": np.random.rand(3, 8).tolist()})
            assert len(first["output"]) == 3
            health = _get(base + "/healthz")
            assert health["bundle_installed"] is True
            assert health["warmed_buckets"] == 4  # 1,2,4,8
            assert health["compiles_since_ready"] == 0, health
        finally:
            proc.terminate()
            proc.wait(timeout=15)

    @pytest.fixture()
    def fleet(self, tmp_path):
        store, net = _seed_store(tmp_path)
        router = FleetRouter(
            str(tmp_path / "store"), workers=2, poll_s=0.2,
            shed_outstanding=4,
            worker_args={"max_delay_ms": 0, "max_batch": 8,
                         "max_queue_depth": 2}).start()
        try:
            yield router, store
        finally:
            router.stop()

    def test_rolling_rollout_bit_exact(self, fleet):
        router, store = fleet
        base = f"http://127.0.0.1:{router.port}"
        probe = np.linspace(-1, 1, 8, dtype=np.float32).reshape(1, 8)
        ref1 = np.asarray(_post(base + "/predict",
                                {"features": probe.tolist()})["output"],
                          np.float32)
        sampled, errors, stop = [], [], threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    out = _post(base + "/predict",
                                {"features": probe.tolist()})
                    sampled.append(np.asarray(out["output"], np.float32))
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(2)]
        for t in threads:
            t.start()
        # publish v2 with DIFFERENT params -> supervisor rolls the fleet
        import jax

        loader = store.restore(1)
        loader.params = jax.tree_util.tree_map(
            lambda p: p * np.float32(0.5), loader.params)
        store.save(loader)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            stats = router.stats()
            if (stats["rollouts"] >= 1 and all(
                    w["version"] == 2 for w in stats["workers"]
                    if w["ready"])):
                break
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:3]  # no failed requests during the roll
        stats = router.stats()
        assert stats["rollouts"] == 1
        assert all(w["version"] == 2 for w in stats["workers"])
        # zero recompiles: hot_swap is a pointer flip
        assert all(w["compiles_since_ready"] == 0
                   for w in stats["workers"] if w["ready"])
        ref2 = np.asarray(_post(base + "/predict",
                                {"features": probe.tolist()})["output"],
                          np.float32)
        assert not np.array_equal(ref1, ref2)  # the versions DO differ
        torn = [s for s in sampled
                if not (np.array_equal(s, ref1) or np.array_equal(s, ref2))]
        assert sampled and not torn, (len(torn), len(sampled))

    def test_kill_respawn_and_shed(self, fleet):
        router, _store = fleet
        base = f"http://127.0.0.1:{router.port}"
        victim = router.workers[0]
        os.kill(victim.proc.pid, signal.SIGKILL)
        # overload the survivor: more concurrent load than
        # shed_outstanding(4)+queue(2) admits -> at least one 429 with
        # Retry-After while requests on the healthy worker still succeed
        codes = []
        lock = threading.Lock()

        def client():
            try:
                _post(base + "/predict",
                      {"features": np.random.rand(8, 8).tolist()})
                with lock:
                    codes.append(200)
            except urllib.error.HTTPError as e:
                with lock:
                    codes.append(e.code)
                if e.code == 429:
                    assert e.headers.get("Retry-After") is not None
            except Exception:  # noqa: BLE001 - transient failover window
                with lock:
                    codes.append(-1)

        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and 429 not in codes:
            threads = [threading.Thread(target=client) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        assert 200 in codes
        assert 429 in codes, sorted(set(codes))
        # the killed worker comes back warm, at the served version
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            snap = router.stats()["workers"][0]
            if snap["ready"] and snap["respawns"] >= 1:
                break
            time.sleep(0.2)
        assert snap["ready"] and snap["respawns"] >= 1, snap
        out = _post(base + "/predict",
                    {"features": np.zeros((1, 8)).tolist()})
        assert out["version"] == 1

    def test_drain_completes_in_flight(self, fleet):
        router, _store = fleet
        base = f"http://127.0.0.1:{router.port}"
        results, errors = [], []

        def client():
            try:
                results.append(_post(
                    base + "/predict",
                    {"features": np.random.rand(2, 8).tolist()}))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # let them enter the pipeline
        assert router.drain(timeout_s=30) is True
        for t in threads:
            t.join(timeout=60)
        assert len(results) + len(errors) == 6
        assert not errors, errors[:3]  # in-flight requests all landed
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base + "/predict",
                  {"features": np.zeros((1, 8)).tolist()})
        assert ei.value.code == 503
