"""chip_smoke.py's legs, run tiny on the CPU (interpret-mode kernels, the
8-device virtual mesh), and its refusal to pass without a chip."""

import os
import subprocess
import sys

import pytest

import chip_smoke
from deeplearning4j_tpu.models.resnet import resnet18_conf
from deeplearning4j_tpu.ops import kernel_select as ks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_RNN = dict(vocab=12, hidden=128, layers=2, batch=8, seq=16)


@pytest.fixture(autouse=True)
def _clean_selection(monkeypatch, tmp_path):
    monkeypatch.setenv("DL4JTPU_KERNEL_CALIBRATION", str(tmp_path / "cal.json"))
    monkeypatch.setenv("DL4JTPU_TUNED_PATH", str(tmp_path / "TUNED.json"))
    ks.reset()
    yield
    ks.reset()


def test_gate_reports_an_assumed_target_off_chip(capsys):
    info = chip_smoke.gate(require_tpu=False)
    assert info["platform"] == "cpu"
    out = capsys.readouterr().out
    assert "assumed=True" in out and "entries=0" in out


def test_probe_dispatch_tiny():
    res = chip_smoke.probe_dispatch(n=64, chain=2, reps=3)
    assert res["block_until_ready_ms"] > 0
    assert res["tiny_dispatch_roundtrip_ms_median"] > 0


def test_leg_a_resnet18_tiny():
    conf = resnet18_conf(dtype="bfloat16", image_size=(32, 32), num_classes=10)
    res = chip_smoke.leg_a_trainer(conf, image=32, classes=10, batch=8)
    assert len(res["losses"]) == 3


def test_legs_b_and_c_tiny(monkeypatch):
    # off-chip the fused variants only run when asked for; mode "fused"
    # routes all three sites through the interpret-mode kernels
    monkeypatch.setenv("DL4JTPU_KERNELS", "fused")
    info, net, batches = chip_smoke.leg_b_kernel_route(**TINY_RNN)
    assert info["variants"] == {"lstm_seq": ["seqfused"],
                                "softmax_xent": ["fused"],
                                "optimizer": ["fused"]}
    assert info["min_update_cosine"] > 0.99
    res = chip_smoke.leg_c_server(net, batches, seq=8, row_cap=4,
                                  request_rows=(1, 3, 4, 2))
    assert res["buckets_warmed"] == 3 and res["swaps"] == 1


def test_leg_b_fails_on_a_give_way(monkeypatch):
    # a VMEM guard that rejects the leg's shape must not pass quietly
    monkeypatch.setenv("DL4JTPU_KERNELS", "fused")
    monkeypatch.setattr(
        "deeplearning4j_tpu.ops.pallas_kernels._SEQ_VMEM_BUDGET_BYTES", 1)
    with pytest.raises(chip_smoke.LegFailure, match="lstm_seq gave way"):
        chip_smoke.leg_b_kernel_route(**TINY_RNN)


def test_leg_d_kernels_tiny():
    res = chip_smoke.leg_d_kernels(
        lstm=(8, 8, 128), lstm_small=(8, 8, 128),
        sxent=((64, 96), (16, 1000), (32, 10)),
        adam=((16, 128), (256,), (96,), (7, 9)),
        flash=(1, 2, 32, 16), flash_cell=(1, 4, 2, 48, 16),
        lrn=(2, 4, 4, 16), hyper=(40, 2, 128))
    assert res["checks"] == 23


def test_leg_e_on_the_virtual_mesh():
    res = chip_smoke.leg_e_four_chips(**TINY_RNN)
    assert len(res["dp4"]) == len(res["dp2xfsdp2"]) == 3


def _tiny_hybrid_sizes():
    import json

    with open(os.path.join(REPO, "tests", "benchmark_harness", "presets",
                           "configs", "nemotron3_nano_30b_a3b.json")) as f:
        return json.load(f)["sizes"]


@pytest.mark.parametrize("mode", ["auto", "fused"])
def test_leg_f_hybrid_blocks_tiny(monkeypatch, mode):
    # float32 on the CPU, so the tolerances are rounding's; "fused" takes
    # the flash and loss kernels in interpret mode (the scan kernels' layout
    # needs the published widths: tests/test_nemotron_h.py calls them)
    monkeypatch.setenv("DL4JTPU_KERNELS", mode)
    tol = dict.fromkeys(("M", "A", "E", "head"), 1e-4)
    res = chip_smoke.leg_f_hybrid_blocks(
        _tiny_hybrid_sizes(), seq_len=24, batch=2, dtype="float32",
        tolerances=tol)
    assert set(res["worst"]) == {
        "M", "A", "E", "head", "M with the scan state in bfloat16 (control)"}
    assert res["worst"]["M with the scan state in bfloat16 (control)"] > 1e-3
    if mode == "fused":
        assert res["selection"]["attention"] == "flash"
        assert res["selection"]["softmax_xent"] == "fused"


def test_leg_f_fails_when_the_lower_precision_control_passes():
    tol = dict.fromkeys(("M", "A", "E", "head"), 0.5)
    with pytest.raises(chip_smoke.LegFailure, match="control"):
        chip_smoke.leg_f_hybrid_blocks(
            _tiny_hybrid_sizes(), seq_len=16, batch=1, dtype="float32",
            tolerances=tol, kinds="M")


def _tiny_latent_sizes():
    import json

    with open(os.path.join(REPO, "tests", "benchmark_harness", "presets",
                           "configs", "xing4_29b_a4b.json")) as f:
        return json.load(f)["sizes"]


G_CONTROLS = ("A with the rotary angles in bfloat16 (control)",
              "H with the maps in bfloat16 (control)")


@pytest.mark.parametrize("mode", ["auto", "fused"])
def test_leg_g_latent_blocks_tiny(monkeypatch, mode):
    # float32 on the CPU, so the tolerances are rounding's; "fused" takes
    # the flash kernels (two sizes of product, a shared rotary key) and the
    # grouped products in interpret mode
    monkeypatch.setenv("DL4JTPU_KERNELS", mode)
    # 24 positions and weights of 0.02: rounding the angles moves the
    # attention block by 3e-5 only, so its tolerance here is 1e-5
    tol = dict(dict.fromkeys(("D", "E", "H"), 2e-4), A=1e-5)
    res = chip_smoke.leg_g_latent_blocks(
        _tiny_latent_sizes(), seq_len=24, batch=2, dtype="float32",
        tolerances=tol)
    assert set(res["worst"]) == {"A", "D", "E", "H", *G_CONTROLS}
    assert res["worst"][G_CONTROLS[0]] > 10 * res["worst"]["A"]
    assert res["worst"][G_CONTROLS[1]] > 1e-3
    if mode == "fused":
        assert res["selection"]["attention"] == "flash"


def test_leg_g_fails_when_a_lower_precision_control_passes():
    tol = dict.fromkeys(("A", "D", "E", "H"), 0.9)
    with pytest.raises(chip_smoke.LegFailure, match="control"):
        chip_smoke.leg_g_latent_blocks(
            _tiny_latent_sizes(), seq_len=16, batch=1, dtype="float32",
            tolerances=tol, kinds="H")


def _tiny_kimi_sizes():
    import json

    with open(os.path.join(REPO, "tests", "benchmark_harness", "presets",
                           "configs", "kimi_linear_48b_a3b.json")) as f:
        return json.load(f)["sizes"]


@pytest.mark.parametrize("mode", ["auto", "fused"])
def test_leg_h_kimi_blocks_tiny(monkeypatch, mode):
    # float32 on the CPU, so the tolerances are rounding's; "fused" takes the
    # flash kernels (no query rank, an un-rotated shared key part) in
    # interpret mode; the recurrence has its jax.numpy under every mode
    monkeypatch.setenv("DL4JTPU_KERNELS", mode)
    res = chip_smoke.leg_h_kimi_blocks(
        _tiny_kimi_sizes(), seq_len=22, batch=2, dtypes=("float32",),
        a_dtypes=("float32",),
        tolerances={"K": 2e-4, "A": 2e-4, "E": 2e-4, "R": 2e-4})
    control = chip_smoke.LEG_H_CONTROL
    assert set(res["worst"]) == {"K in float32", "A in float32",
                                 "E in float32", "R in float32", control}
    assert res["worst"][control] > 100 * res["worst"]["R in float32"]
    assert res["selection"]["kda_recurrence"] == "reference"
    if mode == "fused":
        assert res["selection"]["attention"] == "flash"


def test_leg_h_fails_when_the_lower_precision_control_passes():
    with pytest.raises(chip_smoke.LegFailure, match="control"):
        chip_smoke.leg_h_kimi_blocks(
            _tiny_kimi_sizes(), seq_len=16, batch=1, dtypes=("float32",),
            tolerances={"K": 0.9, "A": 0.9, "R": 0.9}, kinds="KR")


def test_main_refuses_to_pass_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          capture_output=True, text=True, env=env, cwd=REPO,
                          timeout=300)
    assert proc.returncode not in (0, 2, 3)
    assert "no TPU found (platform is 'cpu')" in proc.stderr
    assert '"ok"' not in proc.stdout
