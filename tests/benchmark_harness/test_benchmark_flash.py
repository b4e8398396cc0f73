"""The two readers that came with PR 31: the flash attention kernels' share
of their roofline (device trace) and the share of the score square's tiles
their loops visit (the program's selection log). Synthetic traces: the
kernels' names are what the v5e's trace carries (``%flash_bwd_dkv.7``), the
times are made up."""

import os
import types

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# nemotron3_nano_train_1chip's attention block: B T H Hkv D itemsize causal
PUBLISHED = (1, 8192, 32, 2, 128, 2, True)
CALL = ('%{name} = bf16[32,8192,128]{{2,1,0}} custom-call(bf16[32,8192,128]'
        '{{2,1,0}} %a), custom_call_target="tpu_custom_call"')
ROOFLINE, SHARE = "flash_attention_roofline", "flash_tiles_walked_share"


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


class Cell:
    params = {"batch_per_chip": 1, "seq_len": 8192}
    sizes = {"num_attention_heads": 32, "num_key_value_heads": 2,
             "head_dim": 128, "dtype": "bfloat16"}


def flash(mode="auto", causal=True, **more):
    return {"site": "attention", "variant": "flash", "mode": mode,
            "ctx": {"causal": causal}, **more}


def run_with(log, trace=None):
    program = {} if log is None else {"selection_log": log}
    return types.SimpleNamespace(result={"program": program}, trace=trace,
                                 cell=Cell, peaks=V5E)


def window(*ops, end=10_000_000_000):
    return tr.TraceData([tr.DeviceTrace("d", ops=list(ops))], spans=[],
                        window=(0, end))


def kernel(name, start, end):
    return tr.Op(start, end, CALL.format(name=name), "pallas")


def test_operations_and_bytes_are_those_of_the_mathematics():
    roof = metric(ROOFLINE)
    B, T, H, Hkv, D, item, _ = PUBLISHED
    entries = T * (T + 1) // 2
    query, shared, rows = H * T * D * item, Hkv * T * D * item, H * T * 4
    flops, moved = roof.flops_and_bytes("flash_fwd", *PUBLISHED)
    # q k^T and p v over the causal triangle: 0.55 TFLOP, 2.8 ms at the peak
    assert flops == 2 * 2 * H * D * entries
    assert flops / 197e12 == pytest.approx(2.79e-3, rel=1e-2)
    assert moved == 2 * query + 2 * shared + rows          # q o, k v, lse
    flops_dq, moved_dq = roof.flops_and_bytes("flash_bwd_dq", *PUBLISHED)
    assert flops_dq == 3 * 2 * H * D * entries
    assert moved_dq == 3 * query + 2 * shared + 2 * rows   # q do dq, k v
    flops_dkv, moved_dkv = roof.flops_and_bytes("flash_bwd_dkv", *PUBLISHED)
    assert flops_dkv == 4 * 2 * H * D * entries
    assert moved_dkv == 2 * query + 4 * shared + 2 * rows  # q do, k v dk dv
    # without causal the whole square counts
    full = roof.flops_and_bytes("flash_fwd", *PUBLISHED[:-1], False)
    assert full == (2 * 2.0 * H * D * T * T, moved)
    # every kernel is bound by its products here, not its bytes: a step's
    # four calls (the forward twice under remat) take 15.4 ms at least
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in roof.PRODUCTS}
    assert least["flash_fwd"] == pytest.approx(flops / 197e12)
    assert least["flash_fwd"] > moved / 819e9
    assert 2 * least["flash_fwd"] + least["flash_bwd_dq"] \
        + least["flash_bwd_dkv"] == pytest.approx(15.4e-3, rel=1e-2)


def test_share_of_the_roofline_from_a_trace_worked_out_by_hand():
    roof = metric(ROOFLINE)
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in roof.PRODUCTS}
    # the parent's step as the ledger has it: 50 ms a forward, 41 and 56
    ms = 1_000_000
    ops = [tr.Op(0, 5 * ms, "%fusion.1 = f32[] fusion()", "mxu"),
           kernel("flash_fwd.3", 10 * ms, 60 * ms),
           kernel("flash_fwd.4", 100 * ms, 150 * ms),
           kernel("flash_bwd_dq.5", 200 * ms, 241 * ms),
           kernel("flash_bwd_dkv.7", 300 * ms, 356 * ms),
           kernel("ssd_scan_fwd.9", 400 * ms, 401 * ms)]
    want = 100.0 * (2 * least["flash_fwd"] + least["flash_bwd_dq"]
                    + least["flash_bwd_dkv"]) / 0.197
    run = run_with([flash()], window(*ops))
    assert roof.read(run) == pytest.approx(want)
    assert want == pytest.approx(7.8, abs=0.05)
    # an event outside the window is not counted
    late = run_with([flash()], window(*ops, end=250 * ms))
    assert roof.read(late) == pytest.approx(
        100.0 * (2 * least["flash_fwd"] + least["flash_bwd_dq"]) / 0.141)
    # the same calls without causal have twice the entries to multiply
    assert roof.read(run_with([flash(causal=False)], window(*ops))) \
        == pytest.approx(want * 2 * 8192 / 8193)


def test_no_event_no_selection_no_trace_read_nothing_never_zero():
    roof = metric(ROOFLINE)
    other = tr.Op(0, 1000, "%fusion.1 = f32[] fusion()", "mxu")
    assert roof.read(run_with([flash()], window(other))) is None
    assert roof.read(run_with([flash()], None)) is None
    # the XLA path was selected (the CPU, a mesh): no flash call to describe
    xla = {"site": "attention", "variant": "xla", "mode": "auto",
           "ctx": {"causal": True}}
    op = kernel("flash_fwd.1", 0, 50_000_000)
    assert roof.read(run_with([xla], window(op))) is None
    assert roof.read(run_with(None, window(op))) is None
    # the reference-mode twin's records are left out
    assert roof.read(run_with([flash(mode="reference")], window(op))) is None


@pytest.mark.parametrize("kernel_name", ["flash_fwd", "flash_bwd_dq",
                                         "flash_bwd_dkv"])
def test_no_real_duration_reads_over_100(kernel_name):
    """An event cannot take less than its products at the MXU's peak: at
    that duration the share is 100, and longer only lowers it."""
    roof = metric(ROOFLINE)
    least_ns = roof.least_seconds(kernel_name, PUBLISHED, V5E) * 1e9
    for slower in (1.0, 1.5, 12.8):
        # whole nanoseconds, as a trace has them: never short of the least
        op = kernel(kernel_name + ".2", 0, int(-(-least_ns * slower // 1)))
        got = roof.read(run_with([flash()], window(op)))
        assert got == pytest.approx(100.0 / slower, rel=1e-6)
        assert got <= 100.0


@pytest.mark.parametrize("log,value", [
    # the hybrid cell: 512-wide tiles, 16 a side
    ([flash(tiles_walked_share=17 / 32, block_q=512, block_k=512),
      {"site": "optimizer", "variant": "fused", "mode": "auto"}], 53.125),
    # shapes that differ: the largest share
    ([flash(tiles_walked_share=0.5078125), flash(tiles_walked_share=0.5625)],
     56.25),
    ([flash(causal=False, tiles_walked_share=1.0)], 100.0),
    # a program from before the bound chose flash and says no share: every
    # tile was walked
    ([flash()], 100.0),
    # the XLA path materialises the whole square
    ([{"site": "attention", "variant": "xla", "mode": "auto"}], 100.0),
    # the reference-mode twin's records are left out; no attention site; no
    # log at all
    ([flash(mode="reference", tiles_walked_share=0.5)], None),
    ([{"site": "optimizer", "variant": "reference", "mode": "auto"}], None),
    ([], None),
    (None, None),
])
def test_tiles_walked_share_is_the_attention_selections_own(log, value):
    assert metric(SHARE).read(run_with(log)) == value


def test_the_entries_list_the_cell_that_runs_the_kernels():
    per_layer = load_json(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]
    names = [m["name"] for m in per_layer]
    scan = next(m for m in per_layer if m["name"] == "ssd_scan_roofline")
    assert per_layer[names.index(SHARE)] == {
        "name": SHARE, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": scan["layer"],
        "moves": scan["moves"], "workloads": ["nemotron3_nano_train_1chip"]}
    # the roofline's entry waits for a PR that may edit the cell's preset
    # (fixtures/flash_attention_roofline_entry.json says why): once it is in
    # the manifest it is this one
    waiting = load_json(os.path.join(
        REPO, "tests", "benchmark_harness", "fixtures",
        "flash_attention_roofline_entry.json"))["per_layer"]
    assert waiting == [{
        "name": ROOFLINE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": scan["layer"],
        "moves": scan["moves"], "workloads": scan["workloads"]}]
    if ROOFLINE in names:
        assert per_layer[names.index(ROOFLINE)] == waiting[0]


def test_the_traced_rehearsal_carries_the_tiles_share():
    """On the CPU ``auto`` takes the XLA attention path, which computes the
    whole square: 100."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    ks.reset()  # the log is the process's: earlier tests' selections go
    cell = tiny_cell("nemotron3_nano_train_1chip")
    assert SHARE in {m["name"] for m in cell.per_layer}
    line = rehearse(cell, trace=True, seconds=0.5)
    assert line["correct"] is True
    assert line["metrics"][SHARE] == {"value": 100.0, "unit": "%"}
    assert ROOFLINE not in line["metrics"]
