"""The trace reduction, on a small trace recorded on a v5e (PR 23: three
dispatches of a 512x512 bf16 matmul+transpose, each followed by a host fetch
under ``bench.loss_fetch``) and on synthetic interval sets."""

import os

import pytest

from bench_presets import REPO  # noqa: F401  (puts the repo on sys.path)
from benchmarks.harness import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def recorded():
    return tr.load(FIXTURE)


def test_recorded_trace_has_one_tpu_device_and_the_harness_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    names = [s.name for s in recorded.spans]
    assert names.count("window") == 1
    assert names.count("dispatch") == 3 and names.count("loss_fetch") == 3
    win = next(s for s in recorded.spans if s.name == "window")
    assert recorded.window == (win.start, win.end)


def test_recorded_trace_busy_union_and_idle_share(recorded):
    ops = recorded.devices[0].ops
    assert len(ops) == 18  # no while/conditional/call umbrellas in this one
    lo, hi = recorded.window
    inside = [o for o in ops if lo <= o.start and o.end <= hi]
    assert len(inside) == 15  # the first dispatch's 3 ops ran before the window
    summed = sum(o.end - o.start for o in inside) / 1e9
    # ops of one program run back to back without overlap: union == sum
    assert recorded.busy_s() == pytest.approx(summed, rel=1e-9)
    assert recorded.busy_s() == pytest.approx(9.14e-6, rel=0.02)
    assert recorded.window_s() == pytest.approx(0.1192, rel=0.01)
    assert recorded.idle_share() == pytest.approx(
        1 - recorded.busy_s() / recorded.window_s())
    assert 0.999 < recorded.idle_share() < 1.0


def test_recorded_trace_buckets(recorded):
    secs = recorded.bucket_seconds()
    assert set(secs) == {"mxu", "copy"}
    assert secs["mxu"] == pytest.approx(4.908e-6, rel=0.01)  # 3 x the matmul fusion
    assert recorded.bucket_share("mxu") + recorded.bucket_share("copy") \
        == pytest.approx(1.0)
    assert recorded.bucket_share("pallas") == 0.0
    assert recorded.collective_seconds() == (0.0, 0.0)


def test_recorded_trace_gap_attribution_and_breakdown(recorded):
    by_span = recorded.gap_seconds_by_span()
    # nearly all idle time falls while the host waits for a result
    assert max(by_span, key=by_span.get) == "loss_fetch"
    assert sum(by_span.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s(), rel=1e-6)
    between = recorded.gaps_between("dispatch")
    assert len(between) == 2 and all(g > 0 for g in between)
    bd = recorded.breakdown()
    assert bd["device_ops"][0][0] == "mxu:convolution_add_fusion"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0] == "loss_fetch"


@pytest.mark.parametrize("name,opcode,bucket", [
    ("%copy-done = bf16[512,512]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16[512,512]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) %copy-start)",
     "copy-done", "copy"),
    ("%while.3 = (s32[]{:T(128)}, f32[512,2048]{1,0:T(8,128)S(1)}) while((s32[]{:T(128)}, f32[512,2048]{1,0}) %tuple), body=%b",
     "while", "other"),
    ('%jvp__.20 = f32[16384,1]{1,0:T(8,128)S(1)} custom-call(f32[16384,96]{1,0} %a), custom_call_target="tpu_custom_call"',
     "custom-call", "pallas"),
    ('%custom-call.8 = bf16[512,2048]{1,0} custom-call(bf16[128,2048]{1,0} %s), custom_call_target="ConcatBitcast"',
     "custom-call", "other"),
    ("%fusion.91 = bf16[256,64,512]{2,1,0:T(8,128)(2,1)} fusion(bf16[256,64,2048]{2,1,0} %p), kind=kOutput, calls=%fc",
     "fusion", "mxu"),
    ("%multiply_reduce_fusion.3 = (bf16[64]{0}, bf16[64]{0}) fusion(bf16[128,56,56,64]{0,3,2,1} %x), kind=kInput, calls=%fc",
     "fusion", "reduce"),
    ("%add_add_fusion.45 = bf16[128,56,56,256]{3,0,2,1} fusion(bf16[128,56,56,256]{3,0,2,1} %a), kind=kLoop, calls=%fc",
     "fusion", "elementwise"),
    ("%all-reduce-start.1 = f32[2048,1000]{1,0} all-reduce-start(f32[2048,1000]{1,0} %g), replica_groups={{0,1,2,3}}",
     "all-reduce-start", "collective"),
    ("%all-gather.2 = f32[8]{0} all-gather(f32[2]{0} %g), dimensions={0}",
     "all-gather", "collective"),
    ("%slice-done.1 = f32[128,2048]{1,0} async-done(((f32[512,2048]{1,0}), f32[128,2048]{1,0}, s32[]) %slice-start.1)",
     "async-done", "copy"),
    ("%convolution.4 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %a, bf16[8,8]{1,0} %b), dim_labels=bf_io->bf",
     "convolution", "mxu"),
])
def test_opcode_and_bucket_of_hlo_event_names(name, opcode, bucket):
    assert tr.opcode_of(name) == opcode
    assert tr.bucket_of(name) == bucket


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (4, 4)]) == [(0, 3), (5, 8)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.subtract([(0, 4)], []) == [(0, 4)]


def _op(a, b, bucket, name="x"):
    return tr.Op(a, b, name, bucket)


def test_exposed_collective_is_what_no_compute_covers():
    # device 0: collective 10..30, compute 0..20 -> 10 exposed of 20 running
    # device 1: collective 10..20 fully under compute 0..40 -> 0 exposed
    d0 = tr.DeviceTrace("d0", ops=[_op(0, 20, "mxu"), _op(10, 30, "collective")])
    d1 = tr.DeviceTrace("d1", ops=[_op(0, 40, "mxu")],
                        async_ops=[_op(10, 20, "collective")])
    td = tr.TraceData([d0, d1], spans=[], window=(0, 40))
    running, exposed = td.collective_seconds()
    assert running == pytest.approx((20 + 10) / 2 / 1e9)
    assert exposed == pytest.approx((10 + 0) / 2 / 1e9)
    # busy is the union per device, averaged: (30 + 40) / 2
    assert td.busy_s() == pytest.approx(35 / 1e9)
    assert td.idle_share() == pytest.approx(1 - 35 / 40)


def test_gaps_are_attributed_to_the_innermost_span_and_window_clips():
    dev = tr.DeviceTrace("d", ops=[_op(-5, 10, "mxu"), _op(30, 40, "copy"),
                                   _op(90, 120, "mxu")])
    spans = [tr.HostSpan("window", 0, 100), tr.HostSpan("dispatch", 0, 50),
             tr.HostSpan("stage", 12, 28), tr.HostSpan("dispatch", 50, 100)]
    td = tr.TraceData([dev], spans=spans, window=(0, 100))
    assert td.gaps() == [(10, 30), (40, 90)]
    assert td.busy_s() == pytest.approx((10 + 10 + 10) / 1e9)
    assert td.gap_seconds_by_span() == {"stage": pytest.approx(20e-9),
                                        "dispatch": pytest.approx(50e-9)}
    # between the middles of the two dispatch spans (25..75): idle 25..30, 40..75
    assert td.gaps_between("dispatch") == [pytest.approx(40e-9)]
    assert td.span_at(500) == "outside_spans"


def test_load_refuses_a_directory_without_a_trace(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.load(str(tmp_path))
