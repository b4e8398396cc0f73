"""Driver-facing entry points, each in a subprocess with a plain CPU
environment: the multi-device dryrun completes on the virtual mesh, and
bench.py prints a metric only from the device it names — the explicit CPU
request works, and a device mode without a TPU exits non-zero with no line.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("BENCH_FORCE_CPU", None)
    env.update(extra)
    return env


def test_dryrun_multichip_on_the_virtual_mesh():
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8); print('DRYRUN_OK')",
        ],
        cwd=REPO,
        env=_cpu_env(),
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, f"stderr tail: {proc.stderr[-2000:]}"
    assert "DRYRUN_OK" in proc.stdout


def test_bench_explicit_cpu_request_prints_one_line_naming_the_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        # BENCH_SELF_PATH: keep the test from latching a pytest-load value
        # into the repo-root self-baseline.
        env=_cpu_env(BENCH_FORCE_CPU="1",
                     BENCH_SELF_PATH=str(tmp_path / "self.json")),
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert proc.returncode == 0, f"stderr tail: {proc.stderr[-2000:]}"
    lines = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    assert len(lines) == 1, f"expected exactly one JSON line, stdout: {proc.stdout[-2000:]}"
    result = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(result)
    assert result["metric"] == "mlp_mnist_train_samples_per_sec"
    assert result["value"] > 0
    assert result["platform"] == "cpu" and result["device_count"] >= 1
    assert result["device_kind"]


def test_bench_without_a_tpu_exits_nonzero_and_prints_no_metric(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=_cpu_env(BENCH_SELF_PATH=str(tmp_path / "self.json")),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU found (platform is 'cpu')" in proc.stderr
    assert "{" not in proc.stdout
    assert not (tmp_path / "self.json").exists()


def test_bench_refuses_a_device_mode_under_the_cpu_request(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=REPO,
        env=_cpu_env(BENCH_FORCE_CPU="1", BENCH_MODEL="charrnn",
                     BENCH_SELF_PATH=str(tmp_path / "self.json")),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "does not run under BENCH_FORCE_CPU" in proc.stderr
    assert "{" not in proc.stdout
