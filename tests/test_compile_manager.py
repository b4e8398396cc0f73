"""Recompile elimination (ISSUE 3): the staged fit path compiles once per
canonical abstract shape, not once per (steps, batches, tail) tuple.

The acceptance core: after a warmup dispatch, changing the step count, the
number of real staged batches, and the trailing-tail size causes ZERO new
XLA compiles — proven two ways: the compile manager's own counter (every
staged program goes through an explicit, counted ``lower().compile()``) and
``jax.monitoring``'s backend_compile events (the ground truth the manager
cannot fake). Same counting style as PR 2's no-extra-syncs test.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.telemetry import MetricsRegistry
from deeplearning4j_tpu.runtime.compile_manager import (
    CompileManager,
    get_compile_manager,
    next_pow2,
    signature,
)


def _net(seed=7):
    conf = MultiLayerConfiguration(
        layers=[
            DenseLayer(n_out=16, activation="relu"),
            OutputLayer(n_out=3, activation="softmax", loss="mcxent"),
        ],
        input_type=InputType.feed_forward(5),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-2),
        seed=seed,
    )
    return MultiLayerNetwork(conf).init()


def _staged(k=4, b=8, f=5, c=3, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(k, b, f)).astype(np.float32)
    ys = np.eye(c, dtype=np.float32)[rng.integers(0, c, size=(k, b))]
    return xs, ys


class _BackendCompileCounter:
    """Ground-truth XLA compile counter via jax.monitoring: listeners cannot
    be unregistered on this jax, so one process-wide instance is armed per
    measurement window."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.armed = False
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, *a, **kw):
        if self.armed and "backend_compile" in name:
            self.count += 1

    def window(self):
        self.armed = True
        self.count = 0
        return self

    def stop(self) -> int:
        self.armed = False
        return self.count


_COUNTER = None


def _compile_counter():
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _BackendCompileCounter()
    return _COUNTER


# --------------------------------------------------------------------------
# unit behavior
# --------------------------------------------------------------------------
class TestPrimitives:
    def test_next_pow2(self):
        assert [next_pow2(n) for n in (0, 1, 2, 3, 4, 5, 9, 64, 65)] == [
            1, 1, 2, 4, 4, 8, 16, 64, 128]

    def test_signature_canonicalizes_values_not_shapes(self):
        a = jnp.zeros((3, 4), jnp.float32)
        b = jnp.ones((3, 4), jnp.float32)
        assert signature(a) == signature(b)  # values don't matter
        assert signature(a) != signature(a.astype(jnp.float64))
        assert signature(a) != signature(jnp.zeros((4, 3), jnp.float32))
        # structs and concrete arrays produce the same key (warmup contract)
        assert signature(a) == signature(
            jax.ShapeDtypeStruct((3, 4), jnp.float32))
        # pytree structure (incl. None-ness of masks) is part of the key
        assert signature((a, None)) != signature((a, b))

    def test_lru_bound_and_eviction_counter(self):
        cm = CompileManager(max_entries=2, registry=MetricsRegistry())
        for i in range(4):
            cm.callable(("k", i), lambda i=i: i)
        assert len(cm) == 2
        assert cm.evictions.value == 2
        # oldest evicted, newest retained
        assert cm.callable(("k", 3), lambda: "rebuilt") == 3
        assert cm.cache_hits.value == 1

    def test_drop_token_retires_owner_entries(self):
        cm = CompileManager(registry=MetricsRegistry())
        t1, t2 = cm.new_token(), cm.new_token()
        cm.callable((t1, "a"), lambda: 1)
        cm.callable((t1, "b"), lambda: 2)
        cm.callable((t2, "a"), lambda: 3)
        assert cm.drop_token(t1) == 2
        assert len(cm) == 1
        assert cm.callable((t2, "a"), lambda: "stale?") == 3

    def test_aot_counts_and_times_compiles(self):
        cm = CompileManager(registry=MetricsRegistry())

        def build():
            return jax.jit(lambda x: x * 2)

        x = jnp.ones((4,), jnp.float32)
        fn = cm.aot(("p",), build, (x,))
        assert cm.compiles.value == 1
        assert cm.compile_time.summary()["count"] == 1
        np.testing.assert_allclose(np.asarray(fn(x)), 2.0)
        assert cm.aot(("p",), build, (x,)) is fn  # cache hit, no new compile
        assert cm.compiles.value == 1

    def test_net_reinit_drops_its_executables(self):
        cm = get_compile_manager()
        net = _net()
        xs, ys = _staged(k=2)
        net.fit_on_device(xs, ys)
        token = net._cm_token
        assert any(k[0] == token for k in list(cm._entries))
        before = cm.evictions.value
        net.init(force=True)
        assert cm.evictions.value > before  # token entries retired eagerly
        assert not any(k[0] == token for k in list(cm._entries))


# --------------------------------------------------------------------------
# the acceptance core: varying steps / batch counts / tails do not recompile
# --------------------------------------------------------------------------
class TestRecompileElimination:
    def test_steps_and_tail_changes_reuse_one_executable(self):
        cm = get_compile_manager()
        counter = _compile_counter()
        net = _net()
        xs, ys = _staged(k=4)

        net.fit_on_device(xs, ys, steps=4)  # warmup: the one real compile
        c0 = cm.compiles.value
        counter.window()
        # changing the step count, cycling past K, running a partial window
        # (fewer real batches than staged slots), and training the "tail"
        # (real_batches < K) are all device-scalar changes — zero compiles
        net.fit_on_device(xs, ys, steps=2)
        net.fit_on_device(xs, ys, steps=3)
        net.fit_on_device(xs, ys, steps=1, real_batches=1)
        net.fit_on_device(xs, ys, steps=3, real_batches=3)
        assert counter.stop() == 0
        assert cm.compiles.value == c0
        assert net.staged_steps_total == 4 + 2 + 3 + 1 + 3

    def test_losses_match_old_per_shape_semantics(self):
        """The dynamic-steps executable returns exactly ``steps`` losses and
        the same values the per-batch path produces (i % real_batches
        cycling)."""
        from deeplearning4j_tpu.datasets.iterators import DataSet

        xs, ys = _staged(k=2)
        seq = _net()
        seq._train_step = seq._build_train_step()
        seq_losses = []
        for i in range(5):
            seq._fit_batch(DataSet(xs[i % 2], ys[i % 2]))
            seq_losses.append(float(seq._last_loss))
        dev = _net()
        losses = dev.fit_on_device(xs, ys, steps=5)
        assert losses.shape == (5,)
        np.testing.assert_allclose(losses, seq_losses, atol=1e-6, rtol=1e-5)

    def test_warmup_compiles_ahead(self):
        cm = get_compile_manager()
        net = _net()
        xs, ys = _staged(k=3)
        before = cm.compiles.value
        net.warmup(jax.ShapeDtypeStruct(xs.shape, xs.dtype),
                   jax.ShapeDtypeStruct(ys.shape, ys.dtype))
        assert cm.compiles.value == before + 1
        counter = _compile_counter().window()
        net.fit_on_device(xs, ys, steps=3)
        net.fit_on_device(xs, ys, steps=2, real_batches=2)
        assert counter.stop() == 0
        assert cm.compiles.value == before + 1

    def test_graph_warmup_and_reuse(self):
        from deeplearning4j_tpu.nn.conf.computation_graph import (
            ComputationGraphConfiguration,
        )
        from deeplearning4j_tpu.nn.graph.computation_graph import (
            ComputationGraph,
        )

        conf = (
            ComputationGraphConfiguration.builder()
            .seed(9)
            .updater(UpdaterConfig(updater="adam", learning_rate=1e-2))
            .add_inputs("in")
            .add_layer("h", DenseLayer(n_out=12, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "h")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build()
        )
        net = ComputationGraph(conf).init()
        xs, ys = _staged(k=3)
        cm = get_compile_manager()
        net.warmup(jax.ShapeDtypeStruct(xs.shape, xs.dtype),
                   jax.ShapeDtypeStruct(ys.shape, ys.dtype))
        before = cm.compiles.value
        counter = _compile_counter().window()
        net.fit_on_device(xs, ys, steps=3)
        net.fit_on_device(xs, ys, steps=2, real_batches=2)
        assert counter.stop() == 0
        assert cm.compiles.value == before

    def test_distinct_shapes_do_compile(self):
        """The cache keys on abstract shapes — a genuinely new batch shape
        is a new program (sanity check that reuse isn't vacuous)."""
        cm = get_compile_manager()
        net = _net()
        xs, ys = _staged(k=2, b=8)
        net.fit_on_device(xs, ys)
        before = cm.compiles.value
        xs2, ys2 = _staged(k=2, b=16)
        net.fit_on_device(xs2, ys2)
        assert cm.compiles.value == before + 1


class TestPersistentCacheResolver:
    """One resolver places jax's persistent cache: where
    JAX_COMPILATION_CACHE_DIR says when it is set (and then nothing is set
    in code), else the fixed <repo>/.jax_cache."""

    def test_env_set_means_no_directory_is_set_in_code(self, tmp_path,
                                                       monkeypatch):
        from deeplearning4j_tpu.runtime import compile_manager as cmod

        monkeypatch.setenv(cmod.CACHE_DIR_ENV, str(tmp_path))
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda name, value: updates.append(name))
        cmod.resolve_persistent_cache()
        assert updates == []

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    @classmethod
    def _resolve_in_fresh_process(cls, cwd, **env_overrides):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=cls.REPO, **env_overrides)
        code = ("from deeplearning4j_tpu.runtime import "
                "resolve_persistent_cache as r; print(r())")
        return subprocess.run(
            [sys.executable, "-c", code], cwd=cwd, env=env, text=True,
            capture_output=True, timeout=120, check=True,
        ).stdout.strip().splitlines()[-1]

    def test_unset_resolves_to_the_fixed_checkout_path(self, monkeypatch):
        from deeplearning4j_tpu.runtime import compile_manager as cmod

        monkeypatch.delenv(cmod.CACHE_DIR_ENV, raising=False)
        prev = jax.config.jax_compilation_cache_dir
        try:
            got = cmod.resolve_persistent_cache()
        finally:
            jax.config.update("jax_compilation_cache_dir", prev)
        assert got == os.path.join(self.REPO, ".jax_cache") \
            == cmod.DEFAULT_CACHE_DIR

    def test_two_processes_resolve_the_same_directory(self):
        outs = [self._resolve_in_fresh_process(cwd)
                for cwd in (self.REPO, os.path.join(self.REPO, "tests"))]
        assert outs[0] == outs[1] == os.path.join(self.REPO, ".jax_cache")

    def test_env_placed_cache_is_honoured_by_a_fresh_process(self, tmp_path):
        out = self._resolve_in_fresh_process(
            self.REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert out == str(tmp_path)


class TestAdmissionFailureIsCounted:
    def test_a_raising_admission_check_is_counted_not_silent(self,
                                                             monkeypatch):
        from deeplearning4j_tpu.analysis import ir_checks
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        def boom(*a, **k):
            raise AttributeError("module 'jax.core' has no attribute 'X'")

        monkeypatch.setattr(ir_checks, "admission_check", boom)
        cm = CompileManager(registry=MetricsRegistry())
        fn = cm.aot(("t", "probe"), lambda: jax.jit(lambda x: x + 1),
                    (jnp.ones((4,)),))
        assert float(fn(jnp.ones((4,)))[0]) == 2.0  # compilation went on
        stats = cm.stats()
        assert stats["admission_errors"] == 1
        assert stats["static_cost"]["entries_with_cost"] == 0
        assert cm.ir_findings.labels(rule="admission_error").value == 1

    def test_a_working_admission_check_counts_nothing(self):
        from deeplearning4j_tpu.runtime.compile_manager import CompileManager
        from deeplearning4j_tpu.telemetry import MetricsRegistry

        cm = CompileManager(registry=MetricsRegistry())
        cm.aot(("t", "probe"), lambda: jax.jit(lambda x: x @ x),
               (jnp.ones((8, 8)),))
        stats = cm.stats()
        assert stats["admission_errors"] == 0
        assert stats["static_cost"]["entries_with_cost"] == 1
