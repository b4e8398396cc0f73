"""The reader that came with the hyper-connection kernels (PR 35): the
``hc_*`` events' share of their roofline, by bytes. Synthetic traces: the
kernels' names are what the v5e's trace carries (``%hc_write_bwd.77``), the
times are made up."""

import os
import types

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# xing4_train_1chip's streams: N n D itemsize
PUBLISHED = (8192, 4, 3584, 2)
CALL = ('%{name} = bf16[8192,14336]{{1,0}} custom-call(bf16[8192,14336]'
        '{{1,0}} %a), custom_call_target="tpu_custom_call"')
ROOFLINE = "hyper_connection_roofline"
CELL = "xing4_train_1chip"
KERNELS = ("hc_maps_fwd", "hc_maps_bwd", "hc_read_fwd", "hc_read_bwd",
           "hc_write_fwd", "hc_write_bwd")


def metric(name=ROOFLINE):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


def piece(op="write", mode="auto", variant="fused", **ctx):
    N, n, D, itemsize = PUBLISHED
    return {"site": "hyper_connection", "variant": variant, "mode": mode,
            "ctx": dict({"N": N, "n": n, "D": D, "op": op,
                         "itemsize": itemsize}, **ctx), "row_tile": 128}


PIECES = [piece("maps"), piece("read"), piece("write")]


def run_with(log, trace=None):
    program = {} if log is None else {"selection_log": log}
    return types.SimpleNamespace(result={"program": program}, trace=trace,
                                 peaks=V5E)


def window(*ops, end=10_000_000_000):
    return tr.TraceData([tr.DeviceTrace("d", ops=list(ops))], spans=[],
                        window=(0, end))


def kernel(name, start, end):
    return tr.Op(start, end, CALL.format(name=name), "pallas")


def test_operations_and_bytes_are_those_of_the_mathematics():
    roof = metric()
    N, n, D, item = PUBLISHED
    unit, part = N * n * D * item, N * D * item     # 235 MB and 59 MB
    maps, proj = N * 25 * 4, n * D * 24 * 4
    moved = {k: roof.flops_and_bytes(k, *PUBLISHED)[1] for k in KERNELS}
    assert moved == {
        "hc_maps_fwd": unit + maps + proj,
        "hc_maps_bwd": 3 * unit + maps + 2 * proj,     # X, seen; dX
        "hc_read_fwd": unit + part + maps,
        "hc_read_bwd": 3 * unit + part + 2 * maps,     # X, seen; dX
        "hc_write_fwd": 2 * unit + part + maps,
        "hc_write_bwd": 3 * unit + 2 * part + 2 * maps}
    # a sublayer's kernels (the projection's forward twice under remat) move
    # 3.6 GB: half of what XLA's fusions moved (7.0; PERF.md, PR 34)
    assert sum(moved.values()) + moved["hc_maps_fwd"] \
        == pytest.approx(3.61e9, rel=5e-3)
    # every kernel is bound by its bytes: 4.4 ms a sublayer at the HBM peak
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in KERNELS}
    for k in KERNELS:
        flops = roof.flops_and_bytes(k, *PUBLISHED)[0]
        assert least[k] == pytest.approx(moved[k] / 819e9)
        assert least[k] > 3 * flops / 197e12
    assert sum(least.values()) + least["hc_maps_fwd"] \
        == pytest.approx(4.41e-3, rel=5e-3)
    # the projection's product: 24 columns and the mean square, a feature
    assert roof.flops_and_bytes("hc_maps_fwd", *PUBLISHED)[0] \
        == 2.0 * 25 * N * n * D


def test_share_of_the_roofline_from_a_trace_worked_out_by_hand():
    roof = metric()
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in KERNELS}
    ms = 1_000_000
    ops = [tr.Op(0, 5 * ms, "%fusion.1 = f32[] fusion()", "mxu"),
           kernel("hc_maps_fwd.3", 10 * ms, 11 * ms),
           kernel("hc_maps_fwd.4", 100 * ms, 101 * ms),
           kernel("hc_read_bwd.5", 200 * ms, 202 * ms),
           kernel("hc_write_bwd.77", 300 * ms, 303 * ms),
           kernel("flash_fwd.9", 400 * ms, 401 * ms)]
    want = 100.0 * (2 * least["hc_maps_fwd"] + least["hc_read_bwd"]
                    + least["hc_write_bwd"]) / 0.007
    run = run_with(PIECES, window(*ops))
    assert roof.read(run) == pytest.approx(want)
    assert 34.0 < want < 38.0
    # an event outside the window is not counted
    late = run_with(PIECES, window(*ops, end=250 * ms))
    assert roof.read(late) == pytest.approx(
        100.0 * (2 * least["hc_maps_fwd"] + least["hc_read_bwd"]) / 0.004)


def test_no_event_no_selection_no_trace_read_nothing_never_zero():
    roof = metric()
    other = tr.Op(0, 1000, "%fusion.1 = f32[] fusion()", "mxu")
    op = kernel("hc_write_fwd.1", 0, 50_000_000)
    assert roof.read(run_with(PIECES, window(other))) is None
    assert roof.read(run_with(PIECES, None)) is None
    assert roof.read(run_with(None, window(op))) is None
    assert roof.read(run_with([], window(op))) is None
    # the jax.numpy variant, a reference-mode twin, and two shapes in one
    # run are not this reader's to describe
    assert roof.read(run_with([piece(variant="reference")], window(op))) \
        is None
    assert roof.read(run_with([piece(mode="reference")], window(op))) is None
    assert roof.read(run_with(PIECES + [piece(N=4096)], window(op))) is None
    # the parent's program has no such site and no such kernel
    flash = {"site": "attention", "variant": "flash", "mode": "auto",
             "ctx": {"causal": True}}
    assert roof.read(run_with([flash], window(other))) is None


@pytest.mark.parametrize("kernel_name", KERNELS)
def test_a_planted_event_at_its_least_time_reads_100_never_over(kernel_name):
    roof = metric()
    least_ns = roof.least_seconds(kernel_name, PUBLISHED, V5E) * 1e9
    for slower in (1.0, 1.5, 12.8):
        # whole nanoseconds, as a trace has them: never short of the least
        op = kernel(kernel_name + ".2", 0, int(-(-least_ns * slower // 1)))
        got = roof.read(run_with(PIECES, window(op)))
        assert got == pytest.approx(100.0 / slower, rel=1e-5)
        assert got <= 100.0


def test_the_entry_waits_beside_the_test_and_the_rehearsal_reads_nothing():
    """The manifest entry is kept in a fixture until a ``benchmark`` PR may
    list it for the cell (the fixture says why); once it is in the manifest
    it is this one. On the CPU ``auto`` takes the jax.numpy variant: the
    reader reads nothing from the rehearsal's run."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    per_layer = manifest["per_layer"]
    names = [m["name"] for m in per_layer]
    sibling = next(m for m in per_layer if m["name"] == "latent_flash_roofline")
    waiting = load_json(os.path.join(
        REPO, "tests", "benchmark_harness", "fixtures",
        ROOFLINE + "_entry.json"))["per_layer"]
    assert waiting == [{
        "name": ROOFLINE, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": sibling["layer"],
        "moves": sibling["moves"], "workloads": [CELL]}]
    if ROOFLINE in names:
        assert per_layer[names.index(ROOFLINE)] == waiting[0]
    ks.reset()  # the log is the process's: earlier tests' selections go
    cell = tiny_cell(CELL)
    line = rehearse(cell, trace=True, seconds=0.5)
    assert line["correct"] is True
    assert ROOFLINE not in line["metrics"]
    pieces = {r["ctx"]["op"]: r["variant"] for r in ks.selection_log()
              if r["site"] == "hyper_connection"}
    assert pieces == {"maps": "reference", "read": "reference",
                      "write": "reference"}
