"""DT4xx runtime-guard lint: every shipped rule fires on a seeded
violation and stays silent on its clean twin; pragmas suppress; the CLI
``--concurrency`` mode routes exit codes; scans are deterministic and
deduplicated.

Fixture map (ISSUE 16 acceptance):
- DT400: container appended from a spawned thread AND a public method
  with no common lock / clean twin guards both with the same lock
- DT401: ``time.sleep`` inside ``with self._lock`` / clean twin sleeps
  after releasing
- DT402: two locks nested A->B on one path and B->A on another / clean
  twin keeps one global order
- DT403: ``os.environ[...] =`` / clean twin only reads
- DT404: bare ``time.sleep`` / clean twin paces on a Deadline
- DT405: ``jax.config.update`` on a thread target / clean twin updates
  at import time (before threads exist)
- DT406: one metric name declared with two label sets, an unregistered
  flight-event kind / clean twin declares once and records a registered
  kind
"""

import textwrap

import pytest

from deeplearning4j_tpu.analysis import RULES
from deeplearning4j_tpu.analysis.cli import main as cli_main
from deeplearning4j_tpu.analysis.concurrency import check_concurrency_source
from deeplearning4j_tpu.analysis.runtime_checks import (
    TelemetrySchema,
    check_runtime_paths,
    check_runtime_source,
)


def _src(s: str) -> str:
    return textwrap.dedent(s).lstrip()


def _ids(findings):
    return {f.rule_id for f in findings}


# --------------------------------------------------------------- fixtures
# each rule id maps to (firing source, clean twin); both twins go through
# check_runtime_source so a fixture cannot fire a *different* DT4xx rule
# without the clean-twin assertion catching it.

_FIRING = {
    "DT400": _src("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._run)
                self._thread.start()

            def _run(self):
                self.items.append(1)

            def add(self, x):
                self.items.append(x)
        """),
    "DT401": _src("""
        import threading
        import urllib.request

        class Prober:
            def __init__(self):
                self._lock = threading.Lock()
                self.results = []

            def probe(self, url):
                with self._lock:
                    body = urllib.request.urlopen(url).read()
                    self.results.append(body)
        """),
    "DT402": _src("""
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.n = 0

            def forward(self):
                with self._a:
                    with self._b:
                        self.n += 1

            def backward(self):
                with self._b:
                    with self._a:
                        self.n += 1
        """),
    "DT403": _src("""
        import os

        def poison(flag):
            os.environ["JAX_PLATFORMS"] = flag
        """),
    "DT404": _src("""
        import time

        def nap():
            time.sleep(0.5)
        """),
    "DT405": _src("""
        import threading
        import jax

        class Reloader:
            def __init__(self):
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._flip)
                self._thread.start()

            def _flip(self):
                jax.config.update("jax_enable_x64", True)
        """),
    "DT406": _src("""
        from deeplearning4j_tpu.telemetry import get_registry

        reg = get_registry()
        a = reg.counter("dl4jtpu_fixture_total", "h", labelnames=("a",))
        b = reg.counter("dl4jtpu_fixture_total", "h", labelnames=("b",))

        def note(recorder):
            recorder.record("dt406_fixture_unregistered_kind")
        """),
}

_CLEAN = {
    "DT400": _src("""
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._run)
                self._thread.start()

            def _run(self):
                with self._lock:
                    self.items.append(1)

            def add(self, x):
                with self._lock:
                    self.items.append(x)
        """),
    "DT401": _src("""
        import threading
        import urllib.request

        class Prober:
            def __init__(self):
                self._lock = threading.Lock()
                self.results = []

            def probe(self, url):
                body = urllib.request.urlopen(url).read()
                with self._lock:
                    self.results.append(body)
        """),
    "DT402": _src("""
        import threading

        class Pair:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.n = 0

            def forward(self):
                with self._a:
                    with self._b:
                        self.n += 1

            def backward(self):
                with self._a:
                    with self._b:
                        self.n += 1
        """),
    "DT403": _src("""
        import os

        def read(flag):
            return os.environ.get(flag, "")
        """),
    "DT404": _src("""
        from deeplearning4j_tpu.runtime.resilience import Deadline

        def nap(stop=None):
            Deadline(0.5).pace(0.5, stop=stop)
        """),
    "DT405": _src("""
        import threading
        import jax

        jax.config.update("jax_enable_x64", False)

        class Reloader:
            def __init__(self):
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._work)
                self._thread.start()

            def _work(self):
                return jax.numpy.zeros(())
        """),
    "DT406": _src("""
        from deeplearning4j_tpu.telemetry import get_registry

        reg = get_registry()
        a = reg.counter("dl4jtpu_fixture_total", "h", labelnames=("a",))

        def note(recorder):
            recorder.record("step")
        """),
}


class TestRuntimeRules:
    @pytest.mark.parametrize("rule_id", sorted(_FIRING))
    def test_rule_fires(self, rule_id):
        findings = check_runtime_source(_FIRING[rule_id], f"{rule_id}.py")
        assert rule_id in _ids(findings), findings
        for f in findings:
            assert f.line > 0 and f.file == f"{rule_id}.py"

    @pytest.mark.parametrize("rule_id", sorted(_CLEAN))
    def test_clean_twin_is_silent(self, rule_id):
        findings = check_runtime_source(_CLEAN[rule_id], f"{rule_id}.py")
        assert rule_id not in _ids(findings), findings

    def test_every_shipped_runtime_rule_has_fixtures(self):
        runtime_rules = {r for r, rule in RULES.items()
                        if rule.scope == "runtime"}
        assert runtime_rules == set(_FIRING) == set(_CLEAN)
        assert runtime_rules == {"DT400", "DT401", "DT402", "DT403",
                                 "DT404", "DT405", "DT406"}


class TestPragmas:
    def test_line_pragma_suppresses(self):
        src = 'import time\ntime.sleep(1)  # dl4jtpu: ignore[DT404]\n'
        assert check_runtime_source(src, "p.py") == []

    def test_line_pragma_is_rule_specific(self):
        src = 'import time\ntime.sleep(1)  # dl4jtpu: ignore[DT403]\n'
        assert "DT404" in _ids(check_runtime_source(src, "p.py"))

    def test_skip_file_suppresses(self):
        src = '# dl4jtpu: skip-file\nimport time\ntime.sleep(1)\n'
        assert check_runtime_source(src, "p.py") == []

    def test_concurrency_pragma_suppresses(self):
        # DT402 anchors each finding on the INNER acquisition (where the
        # ordering edge is recorded); pragma both inner withs
        src = _src("""
            import threading

            class Pair:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.n = 0

                def forward(self):
                    with self._a:
                        with self._b:  # dl4jtpu: ignore[DT402]
                            self.n += 1

                def backward(self):
                    with self._b:
                        with self._a:  # dl4jtpu: ignore[DT402]
                            self.n += 1
            """)
        all_ids = [f.rule_id for f in
                   check_concurrency_source(src, "p.py")]
        assert "DT402" not in all_ids


class TestSchemaAggregation:
    def test_one_schema_across_files_catches_cross_file_drift(self):
        # declared per-file the two label sets never collide; one shared
        # schema across both files must still see the conflict
        one = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'a = get_registry().counter("dl4jtpu_split_total", "h",\n'
               '                           labelnames=("x",))\n')
        two = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'b = get_registry().counter("dl4jtpu_split_total", "h",\n'
               '                           labelnames=("y",))\n')
        schema = TelemetrySchema()
        findings = []
        findings += check_runtime_source(one, "one.py", schema=schema)
        findings += check_runtime_source(two, "two.py", schema=schema)
        findings += schema.findings()
        assert "DT406" in _ids(findings), findings

    def test_registered_kind_stays_clean(self):
        src = ('def note(recorder):\n'
               '    recorder.record("online_rollback")\n')
        assert check_runtime_source(src, "k.py") == []

    def test_tracing_and_slo_kinds_are_registered(self):
        # the tracing/SLO subsystem's event kinds went through the same
        # single-owner registration as every other family — emitting them
        # must not trip the unregistered-kind arm of DT406
        src = ('def note(recorder):\n'
               '    recorder.record("trace_upgrade")\n'
               '    recorder.record("slo_burn")\n'
               '    recorder.record("fleet_rollout")\n'
               '    recorder.record("fleet_respawn")\n')
        assert check_runtime_source(src, "k.py") == []

    def test_unregistered_trace_kind_fires(self):
        src = ('def note(recorder):\n'
               '    recorder.record("trace_upgrade_v2_unregistered")\n')
        assert "DT406" in _ids(check_runtime_source(src, "k.py"))

    def test_slo_family_cross_file_conflict_fires(self):
        # two modules each claiming dl4jtpu_slo_burn_rate with different
        # label sets — the shared schema must flag the second owner
        one = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'g = get_registry().gauge("dl4jtpu_slo_burn_rate", "h",\n'
               '        labelnames=("model", "objective"))\n')
        two = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'g = get_registry().gauge("dl4jtpu_slo_burn_rate", "h",\n'
               '        labelnames=("model",))\n')
        schema = TelemetrySchema()
        findings = []
        findings += check_runtime_source(one, "one.py", schema=schema)
        findings += check_runtime_source(two, "two.py", schema=schema)
        findings += schema.findings()
        assert "DT406" in _ids(findings), findings

    def test_history_kinds_are_registered(self):
        # the metric-history plane registers its annotation kind through
        # the same single-owner path as tracing/SLO; splicing flight
        # events into the timeline must not trip DT406
        src = ('def note(recorder):\n'
               '    recorder.record("history_annotation")\n')
        assert check_runtime_source(src, "k.py") == []

    def test_unregistered_history_kind_fires(self):
        src = ('def note(recorder):\n'
               '    recorder.record("history_annotation_v2_bogus")\n')
        assert "DT406" in _ids(check_runtime_source(src, "k.py"))

    def test_history_family_cross_file_conflict_fires(self):
        # two modules each claiming dl4jtpu_history_samples_total with
        # different label sets — the shared schema flags the drift
        one = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'c = get_registry().counter(\n'
               '        "dl4jtpu_history_samples_total", "h",\n'
               '        labelnames=("kind",))\n')
        two = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'c = get_registry().counter(\n'
               '        "dl4jtpu_history_samples_total", "h",\n'
               '        labelnames=("kind", "worker"))\n')
        schema = TelemetrySchema()
        findings = []
        findings += check_runtime_source(one, "one.py", schema=schema)
        findings += check_runtime_source(two, "two.py", schema=schema)
        findings += schema.findings()
        assert "DT406" in _ids(findings), findings

    def test_forecast_family_kind_conflict_fires(self):
        # same forecast gauge re-declared as a counter elsewhere
        one = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'g = get_registry().gauge(\n'
               '        "dl4jtpu_forecast_offered_load", "h",\n'
               '        labelnames=("model", "horizon"))\n')
        two = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'c = get_registry().counter(\n'
               '        "dl4jtpu_forecast_offered_load", "h",\n'
               '        labelnames=("model", "horizon"))\n')
        schema = TelemetrySchema()
        findings = []
        findings += check_runtime_source(one, "one.py", schema=schema)
        findings += check_runtime_source(two, "two.py", schema=schema)
        findings += schema.findings()
        assert "DT406" in _ids(findings), findings

    def test_history_clean_twin_single_owner(self):
        # the shipped pattern: one module owns the history families and
        # records only registered kinds — no findings
        src = ('from deeplearning4j_tpu.telemetry import get_registry\n'
               'samples = get_registry().counter(\n'
               '        "dl4jtpu_history_samples_total", "h",\n'
               '        labelnames=("kind",))\n'
               'bytes_g = get_registry().gauge(\n'
               '        "dl4jtpu_history_bytes", "h")\n'
               'fc = get_registry().gauge(\n'
               '        "dl4jtpu_forecast_queue_depth", "h",\n'
               '        labelnames=("model", "horizon"))\n'
               'def splice(recorder):\n'
               '    recorder.record("history_annotation")\n')
        assert check_runtime_source(src, "clean.py") == []

    def test_shipped_history_modules_stay_clean(self):
        # the real telemetry/history.py (and everything else the DT4xx
        # self-scan covers) must stay at zero findings
        from deeplearning4j_tpu.analysis.runtime_checks import (
            check_runtime_package,
        )
        findings = check_runtime_package()
        assert findings == [], [
            (f.rule_id, f.file, f.line, f.message) for f in findings
        ]


class TestDeterminism:
    def test_same_source_scans_identically(self):
        a = check_runtime_source(_FIRING["DT400"], "same.py")
        b = check_runtime_source(_FIRING["DT400"], "same.py")
        assert a == b and a

    def test_duplicate_paths_dedupe(self, tmp_path):
        p = tmp_path / "dup.py"
        p.write_text(_FIRING["DT404"])
        once = check_runtime_paths([str(p)])
        twice = check_runtime_paths([str(p), str(p)])
        assert once == twice and once


class TestCli:
    def test_firing_file_fails_at_warning(self, tmp_path):
        p = tmp_path / "racy.py"
        p.write_text(_FIRING["DT400"])
        assert cli_main([str(p), "--concurrency",
                         "--fail-on", "warning"]) == 1

    def test_clean_file_passes(self, tmp_path):
        p = tmp_path / "fine.py"
        p.write_text(_CLEAN["DT400"])
        assert cli_main([str(p), "--concurrency",
                         "--fail-on", "warning"]) == 0

    def test_fail_on_never_always_passes(self, tmp_path):
        p = tmp_path / "racy.py"
        p.write_text(_FIRING["DT401"])
        assert cli_main([str(p), "--concurrency",
                         "--fail-on", "never"]) == 0

    def test_ignore_filters_rule(self, tmp_path):
        p = tmp_path / "sleepy.py"
        p.write_text(_FIRING["DT404"])
        assert cli_main([str(p), "--concurrency", "--ignore", "DT404",
                         "--fail-on", "warning"]) == 0
