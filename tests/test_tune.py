"""Autopilot tests (ISSUE 12): knob registry + scoped env hygiene, the
successive-halving engine against deterministic synthetic objectives,
prior pruning, the TUNED.json store, and the startup auto-apply hooks
(tuned values fill unset knobs; explicit user settings always win).

Every test that touches the store monkeypatches ``DL4JTPU_TUNED_PATH``
into tmp_path — nothing here may read or write the user's cache dir.
"""

import json
import os

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
from deeplearning4j_tpu.serving import InferenceService
from deeplearning4j_tpu.serving.batcher import MAX_BATCH_ENV, MAX_DELAY_ENV
from deeplearning4j_tpu.telemetry import MetricsRegistry, Telemetry, get_registry
from deeplearning4j_tpu.tune import (
    EnvScope,
    TunedStore,
    all_knobs,
    get_knob,
    run_autotune,
    scoped_env,
    successive_halving,
)
from deeplearning4j_tpu.tune.knobs import KERNEL_SITES, apply_config, validate_config
from deeplearning4j_tpu.tune import store as tuned_store

FEATURES, CLASSES = 16, 4


def _net(seed=11, dtype="float32"):
    conf = MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=24, activation="relu"),
            OutputLayer(n_out=CLASSES, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(FEATURES),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3),
        dtype=dtype,
        seed=seed,
    )
    return MultiLayerNetwork(conf)


def _applied_count(context: str) -> float:
    counter = get_registry().counter(
        "dl4jtpu_tuned_config_applied_total",
        "tuned-config knobs auto-applied at startup, by context",
        labelnames=("context",))
    return counter.labels(context=context).value


@pytest.fixture()
def tuned_file(tmp_path, monkeypatch):
    path = str(tmp_path / "TUNED.json")
    monkeypatch.setenv(tuned_store.TUNED_PATH_ENV, path)
    return path


# ------------------------------------------------------------ knob registry
class TestKnobRegistry:
    def test_registry_covers_the_tuned_surfaces(self):
        names = {k.name for k in all_knobs()}
        expected = {
            "train_batch", "stage_window", "bucket_boundaries",
            "telemetry_fetch_every", "precision_params_dtype", "donation",
            "serve_max_delay_ms", "serve_max_batch", "decode_slots",
            "flash_min_seq",
        } | {f"kernel_{s}" for s in KERNEL_SITES}
        assert expected <= names

    def test_every_knob_is_well_formed(self):
        for k in all_knobs():
            assert k.default in k.domain, k.name
            assert k.kind in ("env", "call"), k.name
            if k.kind == "env":
                assert k.env and k.env.startswith("DL4JTPU_"), k.name
            assert k.cost_hint in (
                "compute", "memory", "latency", "host", "neutral"), k.name

    def test_unknown_knob_is_loud(self):
        with pytest.raises(KeyError, match="no_such_knob"):
            get_knob("no_such_knob")
        with pytest.raises(KeyError):
            validate_config({"stage_window": 4, "no_such_knob": 1})


# ---------------------------------------------------------------- env scope
class TestEnvScope:
    def test_restores_unset_and_overwritten_vars(self, monkeypatch):
        monkeypatch.delenv("DL4JTPU_TUNE_T1", raising=False)
        monkeypatch.setenv("DL4JTPU_TUNE_T2", "orig")
        with scoped_env(DL4JTPU_TUNE_T1="a", DL4JTPU_TUNE_T2="b") as scope:
            assert os.environ["DL4JTPU_TUNE_T1"] == "a"
            assert os.environ["DL4JTPU_TUNE_T2"] == "b"
            scope.set("DL4JTPU_TUNE_T2", "c")  # nested write, same var
        assert "DL4JTPU_TUNE_T1" not in os.environ
        assert os.environ["DL4JTPU_TUNE_T2"] == "orig"  # first write wins

    def test_restores_on_exception(self, monkeypatch):
        monkeypatch.delenv("DL4JTPU_TUNE_T3", raising=False)
        with pytest.raises(RuntimeError):
            with scoped_env(DL4JTPU_TUNE_T3="x"):
                raise RuntimeError("trial crashed")
        assert "DL4JTPU_TUNE_T3" not in os.environ

    def test_none_unsets_for_the_scope(self, monkeypatch):
        monkeypatch.setenv("DL4JTPU_TUNE_T4", "keepme")
        with scoped_env(DL4JTPU_TUNE_T4=None):
            assert "DL4JTPU_TUNE_T4" not in os.environ
        assert os.environ["DL4JTPU_TUNE_T4"] == "keepme"

    def test_apply_config_composes_kernels_and_gates(self, monkeypatch):
        monkeypatch.delenv("DL4JTPU_KERNELS", raising=False)
        monkeypatch.delenv("DL4JTPU_DONATE", raising=False)
        config = {
            "kernel_attention": "reference", "kernel_lrn": "fused",
            "kernel_optimizer": "auto",   # auto = no override, not listed
            "donation": False,
            "stage_window": 8,            # call-kind: returned, not set
        }
        with EnvScope() as scope:
            residue = apply_config(config, scope)
            assert residue == {"stage_window": 8}
            assert (os.environ["DL4JTPU_KERNELS"]
                    == "attention=reference,lrn=fused")
            assert os.environ["DL4JTPU_DONATE"] == "0"
        assert "DL4JTPU_KERNELS" not in os.environ
        assert "DL4JTPU_DONATE" not in os.environ


# ------------------------------------------------------- search engine
class TestSuccessiveHalving:
    def test_finds_known_optimum_deterministically(self):
        # synthetic bowl: best at stage_window=8, train_batch=512
        def score(c):
            return 100.0 - (c["stage_window"] - 8) ** 2 \
                - abs(c["train_batch"] - 512) / 64.0

        candidates = [{"stage_window": w, "train_batch": b}
                      for w in (2, 4, 8, 16) for b in (128, 512)]
        calls = []

        def measure(config, fidelity):
            calls.append((dict(config), fidelity))
            return score(config)

        best, trials = successive_halving(
            candidates, measure, rungs=3, keep=0.5, fidelities=(1, 2, 4))
        assert best.config == {"stage_window": 8, "train_batch": 512}
        assert best.measured == pytest.approx(100.0)
        assert best.rung == 2
        # halving really halves: rung 0 measures all 8, rung 1 at most 4
        assert sum(1 for _, f in calls if f == 1) == 8
        assert sum(1 for _, f in calls if f == 2) <= 4
        # deterministic: same inputs, same winner
        best2, _ = successive_halving(
            candidates, lambda c, f: score(c), rungs=3, keep=0.5,
            fidelities=(1, 2, 4))
        assert best2.config == best.config

    def test_prior_prunes_predicted_bad_without_measuring(self):
        candidates = [{"train_batch": 512}, {"train_batch": 32},
                      {"train_batch": 256}]
        measured = []

        def measure(config, fidelity):
            measured.append(config["train_batch"])
            return float(config["train_batch"])

        # prior: batch 32 predicted >2x worse than the incumbent 512
        best, trials = successive_halving(
            candidates, measure,
            prior=lambda c: float(c["train_batch"]),
            prune_factor=2.0, rungs=1)
        assert 32 not in measured
        assert {t.config["train_batch"] for t in trials if t.pruned} == {32}
        pruned = [t for t in trials if t.pruned][0]
        assert pruned.measured is None and pruned.rung == -1
        assert best.config["train_batch"] == 512

    def test_incumbent_is_measured_even_past_deadline(self):
        import time

        candidates = [{"stage_window": 4}, {"stage_window": 8}]
        measured = []

        def measure(config, fidelity):
            measured.append(config["stage_window"])
            return 1.0

        best, trials = successive_halving(
            candidates, measure, rungs=2,
            deadline=time.monotonic() - 1.0)  # already expired
        assert measured == [4]  # incumbent only
        assert best.config == {"stage_window": 4}

    def test_rich_measure_dict_fills_trial_evidence(self):
        def measure(config, fidelity):
            return {"value": 5.0, "p99_ms": 1.25, "compiles": 0,
                    "telemetry": {"warm_compiles": 2}}

        best, _ = successive_halving([{"stage_window": 4}], measure, rungs=1)
        assert best.measured == 5.0
        assert best.p99_ms == 1.25
        assert best.compiles_measured == 0
        assert best.telemetry == {"warm_compiles": 2}


class _SyntheticWorkload:
    """In-memory workload for run_autotune plumbing tests: a known optimum,
    a prior that dooms one candidate, and an env knob trialed per config to
    prove the search restores os.environ bit-identically."""

    objective = "fit"
    metric = "synthetic_score"

    def __init__(self, net):
        self._net = net

    def default_config(self):
        return {"stage_window": 4}

    def space(self):
        return {"stage_window": (2, 4, 8), "train_batch": (32, 512)}

    def key(self):
        return tuned_store.key_for(self._net)

    def prior(self, config):
        # predicted objective: batch 32 looks >2x worse than the incumbent
        return 0.1 if config.get("train_batch", 512) == 32 else 1.0

    def measure(self, config, fidelity):
        with EnvScope() as scope:
            apply_config({"donation": False}, scope)
            assert os.environ["DL4JTPU_DONATE"] == "0"
            return 10.0 + config["stage_window"]


class TestRunAutotune:
    def test_search_persists_winner_and_keeps_env_clean(self, tuned_file):
        net = _net()
        env_before = dict(os.environ)
        result = run_autotune(workload=_SyntheticWorkload(net),
                              budget_s=30.0, rungs=2, fidelities=(1, 2))
        assert dict(os.environ) == env_before
        assert result.env_ok
        assert result.best.config["stage_window"] == 8
        assert result.best.config["train_batch"] == 512
        assert result.best.measured == pytest.approx(18.0)
        # prior pruned every train_batch=32 candidate before measurement
        assert result.pruned and all(
            t.config["train_batch"] == 32 for t in result.pruned)
        # the winner landed in TUNED.json under the model's key
        assert result.store_path == tuned_file
        entry = TunedStore(tuned_file).get(tuned_store.key_for(net))
        assert entry["config"]["stage_window"] == 8
        assert entry["metric"] == "synthetic_score"
        assert entry["value"] == pytest.approx(18.0)

    def test_unknown_workload_is_loud(self):
        with pytest.raises(ValueError, match="no workload"):
            run_autotune(model="transformer", objective="fit")


# ------------------------------------------------------------- tuned store
class TestTunedStore:
    def test_roundtrip_and_merge(self, tuned_file):
        store = TunedStore(tuned_file)
        key = "abc123def456/cpu/d8"
        store.put(key, {"stage_window": 8}, objective="fit",
                  metric="train_samples_per_sec", value=6000.0, trials=5)
        # a serve-objective tune of the same model merges, not replaces
        store.put(key, {"serve_max_batch": 128}, objective="serve")
        entry = TunedStore(tuned_file).get(key)
        assert entry["config"] == {"stage_window": 8, "serve_max_batch": 128}
        assert entry["value"] == 6000.0
        raw = json.load(open(tuned_file))
        assert raw["version"] == 1 and key in raw["configs"]

    def test_malformed_file_reads_as_empty(self, tuned_file):
        with open(tuned_file, "w") as f:
            f.write("{not json")
        store = TunedStore(tuned_file)
        assert store.get("any/key/here") is None
        store.put("k/cpu/d1", {"stage_window": 2})  # and is recoverable
        assert store.get("k/cpu/d1")["config"] == {"stage_window": 2}

    def test_put_rejects_unknown_knobs(self, tuned_file):
        with pytest.raises(KeyError):
            TunedStore(tuned_file).put("k/cpu/d1", {"bogus_knob": 1})

    def test_key_is_stable_per_architecture(self, tuned_file):
        a, b = _net(seed=1), _net(seed=1)
        assert tuned_store.key_for(a) == tuned_store.key_for(b)
        sig, backend, topo = tuned_store.key_for(a).split("/")
        assert len(sig) == 12
        assert backend == "cpu"


# -------------------------------------------------------------- auto-apply
class TestAutoApply:
    def test_no_entry_is_a_noop(self, tuned_file):
        assert tuned_store.auto_apply(_net(), "fit") == {}

    def test_register_applies_tuned_batcher_knobs(self, tuned_file,
                                                  monkeypatch):
        monkeypatch.delenv(MAX_DELAY_ENV, raising=False)
        monkeypatch.delenv(MAX_BATCH_ENV, raising=False)
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net),
            {"serve_max_delay_ms": 0.5, "serve_max_batch": 32},
            objective="serve")
        before = _applied_count("serve")
        service = InferenceService(registry=MetricsRegistry())
        try:
            service.register("m", net)
            st = service.stats()["models"]["m"]["batcher"]
            assert st["max_delay_ms"] == pytest.approx(0.5)
            assert st["max_batch"] == 32
            assert _applied_count("serve") == before + 2
        finally:
            service.unregister("m")

    def test_explicit_ctor_arg_beats_tuned(self, tuned_file, monkeypatch):
        monkeypatch.delenv(MAX_DELAY_ENV, raising=False)
        monkeypatch.delenv(MAX_BATCH_ENV, raising=False)
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net),
            {"serve_max_delay_ms": 0.5, "serve_max_batch": 32},
            objective="serve")
        service = InferenceService(registry=MetricsRegistry(),
                                   max_delay_ms=5.0)  # user said 5ms
        try:
            service.register("m", net)
            st = service.stats()["models"]["m"]["batcher"]
            assert st["max_delay_ms"] == pytest.approx(5.0)  # user wins
            assert st["max_batch"] == 32                     # tuned fills
        finally:
            service.unregister("m")

    def test_user_env_setting_beats_tuned(self, tuned_file, monkeypatch):
        monkeypatch.setenv(MAX_DELAY_ENV, "3.0")
        monkeypatch.delenv(MAX_BATCH_ENV, raising=False)
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net), {"serve_max_delay_ms": 0.5},
            objective="serve")
        service = InferenceService(registry=MetricsRegistry())
        try:
            service.register("m", net)
            st = service.stats()["models"]["m"]["batcher"]
            assert st["max_delay_ms"] == pytest.approx(3.0)
        finally:
            service.unregister("m")

    def test_fit_applies_stage_window_and_telemetry_cadence(self, tuned_file):
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net),
            {"stage_window": 2, "telemetry_fetch_every": 25},
            objective="fit")
        net.set_telemetry(Telemetry(registry=MetricsRegistry()))
        applied = tuned_store.auto_apply(net, "fit")
        assert applied == {"stage_window": 2, "telemetry_fetch_every": 25}
        assert net.telemetry.fetch_every == 25

    def test_explicit_telemetry_cadence_is_not_retargeted(self, tuned_file):
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net), {"telemetry_fetch_every": 25},
            objective="fit")
        net.set_telemetry(Telemetry(registry=MetricsRegistry(),
                                    fetch_every=7))  # user chose 7
        applied = tuned_store.auto_apply(net, "fit")
        assert "telemetry_fetch_every" not in applied
        assert net.telemetry.fetch_every == 7

    def test_explicit_list_masks_knobs(self, tuned_file):
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net), {"stage_window": 2}, objective="fit")
        applied = tuned_store.auto_apply(net, "fit",
                                         explicit=("stage_window",))
        assert applied == {}

    def test_fit_uses_tuned_stage_window(self, tuned_file):
        """End-to-end: a TUNED entry changes how fit stages batches, and
        the applied counter + staged-steps metric prove it."""
        net = _net()
        TunedStore(tuned_file).put(
            tuned_store.key_for(net), {"stage_window": 2}, objective="fit")
        before = _applied_count("fit")
        rng = np.random.default_rng(0)
        xs = rng.normal(size=(32, FEATURES)).astype(np.float32)
        ys = np.eye(CLASSES, dtype=np.float32)[
            rng.integers(0, CLASSES, size=32)]
        net.fit((xs, ys), epochs=1)
        assert _applied_count("fit") >= before + 1


# ------------------------------------------------- real workload (tiny MLP)
@pytest.mark.slow
def test_mlp_fit_workload_end_to_end(tuned_file):
    """A real (but tiny) search: measured trials through the staged
    warmup/fit_on_device path, zero compiles in timed regions, env
    bit-identical, winner persisted."""
    from deeplearning4j_tpu.tune.search import MlpFitWorkload

    wl = MlpFitWorkload(hidden=32, features=FEATURES, classes=CLASSES)
    env_before = dict(os.environ)
    result = run_autotune(
        workload=wl, budget_s=90.0, rungs=1, fidelities=(1,),
        space={"train_batch": (16, 64), "stage_window": (2,)})
    assert dict(os.environ) == env_before
    assert result.best.measured is not None and result.best.measured > 0
    assert all(t.compiles_measured == 0 for t in result.trials
               if t.measured is not None)
    entry = TunedStore(tuned_file).get(wl.key())
    assert entry is not None and "train_batch" in entry["config"]
