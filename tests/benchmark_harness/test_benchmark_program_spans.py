"""The readers of what the program itself records (PR 26): its ``dl4j.*``
spans on the device trace's clock (``harness/program_spans.py``), kernel
names and layer scopes (``harness/scopes.py``), and the per-layer metrics
built on them.

The recorded trace is ``fixture_charrnn/``: a one-layer 128-wide char-RNN
(B=16, T=8, Mosaic kernels forced at that size) trained on a v5e by three
one-step ``fit_on_device`` dispatches under ``bench.window`` /
``bench.dispatch``, with the ``dl4j_scopes.json`` that the same run's join
against the compiled program's text left beside it. It has a directory of its
own because ``trace.load`` reads every ``.xplane.pb`` under the directory it
is given, and ``fixtures/`` is read whole by the PR 23 tests. The file was cut
to 140 KB after recording: the HLO protos of ``/host:metadata`` and the host
threads other than the Python one are taken out (no reader opens them)."""

import math
import os

import pytest

from bench_presets import (REPO, manifest_with_serving_cell,  # noqa: F401
                           rehearse, tiny_cell)
from benchmarks.harness import program_spans as ps
from benchmarks.harness import scopes as sc
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixture_charrnn")
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
FIT = ["dl4j.fit.prepare", "dl4j.fit.launch", "dl4j.fit.fetch",
       "dl4j.fit.listeners"]
PR23_METRICS = [
    "dispatch_gap_ms", "compile_s", "compiles_in_window",
    "persistent_cache_hits", "fused_sites", "pallas_time_share",
    "copy_time_share", "conv_time_share", "collective_exposed_share",
    "collective_time_share", "device_idle_share", "peak_hbm_gb"]
NEW_METRICS = {
    "idle_in_put_ms", "idle_in_launch_ms", "idle_in_fetch_ms", "net_init_s",
    "package_import_s", "cm_lower_s", "cm_load_or_compile_s",
    "cm_admission_s", "lstm_seq_time_share", "lstm_seq_roofline",
    "scope_attributed_share"}


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


class Run:
    """What a reader gets, as far as these readers look."""

    def __init__(self, trace, cell=None):
        self.trace, self.cell = trace, cell


@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


@pytest.fixture(scope="module")
def recorded_spans():
    return ps.load(RECORDED)


@pytest.fixture(scope="module")
def recorded_scopes():
    return sc.Scopes.read(RECORDED)


# ------------------------------------------------------------ program spans
def test_recorded_trace_holds_each_dispatch_with_its_four_children(
        recorded, recorded_spans):
    assert os.path.getsize(os.path.join(
        RECORDED, "tiny_charrnn_v5e.xplane.pb")) < 200 * 1024
    names = [s.name for s in recorded_spans]
    assert names == (["dl4j.fit.dispatch"] + FIT) * 3  # sorted by start
    harness = [s for s in recorded.spans if s.name == "dispatch"]
    roots = [s for s in recorded_spans if s.name == ps.DISPATCH]
    for outer, root in zip(harness, roots):
        # one clock: the program's span lies inside the harness's around it
        assert outer.start <= root.start and root.end <= outer.end
    for root, i in zip(roots, range(0, 15, 5)):
        for child in recorded_spans[i + 1:i + 5]:
            assert root.start <= child.start and child.end <= root.end


def test_recorded_idle_splits_by_span_and_adds_up_to_the_dispatch_gap(
        recorded, recorded_spans):
    parts = {}
    for key, names in (("launch", FIT[:2]), ("fetch", FIT[2:3]),
                       ("listeners", FIT[3:]), ("dispatch", [ps.DISPATCH])):
        idle, n = ps.idle_inside(recorded, recorded_spans, set(names))
        assert n == 3
        parts[key] = idle / n
    assert parts["launch"] > 0 and parts["fetch"] > 0
    # children never overlap: together they are what falls in the dispatch
    assert parts["launch"] + parts["fetch"] + parts["listeners"] \
        <= parts["dispatch"] + 1e-6
    # a 3 ms dispatch: the root's own code between its children is 8% of it
    assert parts["launch"] + parts["fetch"] + parts["listeners"] \
        >= 0.85 * parts["dispatch"]
    gap_ms = 1e3 * sum(recorded.gaps_between("dispatch")) / 2
    assert 0.7 <= (parts["launch"] + parts["fetch"]) / 1e6 / gap_ms <= 1.1


def test_idle_parts_of_a_synthetic_steady_run_add_up_to_dispatch_gap_ms():
    """Three identical dispatches of 100: put 0-10, prepare 10-14, launch
    14-16, the device busy 18-90, fetch 16-93, listeners 93-94, and 6 in the
    harness's own loop. A boundary's gap is 28: 3 after the last op in
    fetch, 1 in listeners, 6 outside, 10 in the put, 6 in prepare+launch, 2
    in fetch before the first op."""
    ops, spans, bench = [], [], [tr.HostSpan("window", 0, 300)]
    for k in range(3):
        t = 100 * k
        ops.append(tr.Op(t + 18, t + 90, "%fusion.1 = f32[] fusion()", "mxu"))
        bench.append(tr.HostSpan("dispatch", t, t + 94))
        spans += [
            ps.ProgramSpan("dl4j.parallel_wrapper.data", t, t + 10),
            ps.ProgramSpan("dl4j.parallel_wrapper.step", t + 10, t + 94),
            ps.ProgramSpan("dl4j.fit.dispatch", t + 10, t + 94),
            ps.ProgramSpan("dl4j.fit.prepare", t + 10, t + 14),
            ps.ProgramSpan("dl4j.fit.launch", t + 14, t + 16),
            ps.ProgramSpan("dl4j.fit.fetch", t + 16, t + 93),
            ps.ProgramSpan("dl4j.fit.listeners", t + 93, t + 94)]
    td = tr.TraceData([tr.DeviceTrace("d", ops=ops)], spans=bench,
                      window=(0, 300))

    def per_dispatch(*names):
        idle, n = ps.idle_inside(td, spans, set(names))
        assert n == 3
        return idle / n

    put = per_dispatch("dl4j.parallel_wrapper.data")
    launch = per_dispatch("dl4j.fit.prepare", "dl4j.fit.launch")
    fetch = per_dispatch("dl4j.fit.fetch")
    listeners = per_dispatch("dl4j.fit.listeners")
    assert (put, launch, fetch, listeners) == (10, 6, 5, 1)
    (gap_a, gap_b) = td.gaps_between("dispatch")
    assert gap_a == gap_b == pytest.approx(28e-9)
    outside = 6  # the harness's loop, under no span of the program
    assert put + launch + fetch + listeners + outside == pytest.approx(28)
    # a window with one dispatch still gives a number (no boundary does)
    one = tr.TraceData([tr.DeviceTrace("d", ops=ops[:1])], spans=bench[:2],
                       window=(0, 100))
    assert one.gaps_between("dispatch") == []
    assert ps.idle_inside(one, spans, {"dl4j.fit.fetch"}) == (5, 1)
    # the line's breakdown names the innermost span of the program over each
    # idle stretch; what lies under none of them keeps the harness's name
    # (its loop between two dispatches); the parts are the window's idle time
    by_span = td.gap_seconds_by_program_span(spans)
    assert by_span == {
        "dl4j.parallel_wrapper.data": pytest.approx(30e-9),
        "dl4j.fit.prepare": pytest.approx(12e-9),
        "dl4j.fit.launch": pytest.approx(6e-9),
        "dl4j.fit.fetch": pytest.approx(15e-9),
        "dl4j.fit.listeners": pytest.approx(3e-9),
        "outside_spans": pytest.approx(18e-9)}
    assert sum(by_span.values()) == pytest.approx(td.window_s() - td.busy_s())
    gaps = td.breakdown(program_spans=spans)["idle_gaps"]
    assert gaps[0] == ["dl4j.parallel_wrapper.data", pytest.approx(30e-9)]
    assert "dispatch" not in [name for name, _ in gaps]
    # a program without spans (a parent from before PR 26): as it was
    assert td.gap_seconds_by_program_span(()) == td.gap_seconds_by_span()
    assert td.breakdown()["idle_gaps"][0][0] == "dispatch"


def test_innermost_gives_each_moment_to_the_span_that_started_last():
    S = ps.ProgramSpan
    nested = [S("outer", 0, 100), S("a", 10, 40), S("a.x", 20, 30),
              S("b", 40, 90)]
    assert tr.innermost(nested) == {
        "outer": [(0, 10), (90, 100)], "a": [(10, 20), (30, 40)],
        "a.x": [(20, 30)], "b": [(40, 90)]}
    # another thread's span that straddles an end: the later start has it
    assert tr.innermost([S("main", 0, 50), S("other", 30, 80),
                         S("main", 60, 70)]) == {
        "main": [(0, 30), (60, 70)], "other": [(30, 60), (70, 80)]}
    assert tr.innermost([]) == {}


def test_a_trace_without_the_programs_spans_reads_as_nothing_not_zero():
    """The PR 23 fixture was recorded before the program had spans: the
    readers leave their metric out there, as they do on a parent commit."""
    old = os.path.join(HERE, "fixtures")
    assert ps.load(old) == ()
    idle, dispatches = ps.idle_inside(tr.load(old), (), {"dl4j.fit.fetch"})
    assert (idle, dispatches) == (0, 0)
    assert ps.span_seconds("dl4j.no.such.span") in (None, 0.0)


def test_span_seconds_reads_the_programs_histogram():
    from deeplearning4j_tpu.telemetry.spans import span

    before = ps.span_seconds("dl4j.test.reader") or 0.0
    with span("dl4j.test.reader") as s:
        pass
    assert ps.span_seconds("dl4j.test.reader") - before \
        == pytest.approx(s.duration_s, abs=1e-8)
    assert ps.span_seconds("dl4j.test.never_ran") == 0.0


# ------------------------------------------------------------------- scopes
@pytest.mark.parametrize("event,kernel", [
    ('%lstm_seq_bwd.12 = (bf16[8,16,512]{2,1,0}) custom-call(bf16[8,16,128]{2,1,0} %a), custom_call_target="tpu_custom_call"',
     "lstm_seq_bwd"),
    ('%adam_update = (f32[1,96]{1,0}) custom-call(f32[1,96]{1,0} %g), custom_call_target="tpu_custom_call"',
     "adam_update"),
    # called outside any named scope, the instruction is named for the
    # transform around the kernel too
    ('%transpose_jvp_lstm_seq_bwd__.1 = (bf16[8,16,512]{2,1,0}) custom-call(bf16[8,16,128]{2,1,0} %a), custom_call_target="tpu_custom_call"',
     "lstm_seq_bwd"),
    ('%jvp_lstm_seq_fwd_.1 = (bf16[8,16,128]{2,1,0}) custom-call(bf16[8,16,512]{2,1,0} %a), custom_call_target="tpu_custom_call"',
     "lstm_seq_fwd"),
    ('%custom-call.8 = bf16[512,2048]{1,0} custom-call(bf16[128,2048]{1,0} %s), custom_call_target="ConcatBitcast"',
     None),
    ("%fusion.91 = bf16[256,64,512]{2,1,0} fusion(bf16[256,64,2048]{2,1,0} %p), kind=kOutput, calls=%fc",
     None),
])
def test_kernel_name_of_an_event(event, kernel):
    assert sc.kernel_name(event) == kernel


@pytest.mark.parametrize("op_name,scopes,backward", [
    ("jit(dl4j_mln_staged)/while/body/jvp(layer0)/dot_general",
     ("layer0",), False),
    ("jit(dl4j_mln_staged)/while/body/transpose(jvp(layer1))/while/body/mul",
     ("layer1",), True),
    ("jit(dl4j_mln_staged)/while/body/transpose(jvp(loss))/layer2/jit(log_softmax)/add_any",
     ("loss", "layer2"), True),
    ("jit(dl4j_graph_staged)/while/body/closed_call/optimizer_update/sub",
     ("optimizer_update",), False),
    ("jit(dl4j_graph_staged)/while/body/transpose(jvp(s0_b1_a_conv))/jvp(s0_b1_a_conv)/checkpoint/rematted_computation/conv_general_dilated",
     ("s0_b1_a_conv", "s0_b1_a_conv"), True),
    ("jit(dl4j_mln_staged)/while/body/jvp(layer0)/lstm_seq_fwd/pallas_call",
     ("layer0", "lstm_seq_fwd"), False),
    # merged operations: the first name stands for all
    ("jit(f)/while/body/transpose(jvp(loss))/mul;transpose(jvp(loss))/broadcast_in_dim",
     ("loss",), True),
    # no scope of the program
    ("jit(dl4j_mln_staged)/while/body/jvp()/convert_element_type", (), False),
    ("jit(dl4j_mln_staged)/while/body/dynamic_slice", (), False),
    ("jit(dl4j_mln_staged)/while/body/cond/branch_1_fun/add", (), False),
    ("jit(dl4j_mln_staged)/while/body/jit(_threefry_split)/MultiLayerNetwork._build_multi_step.<locals>.dl4j_mln_staged.<locals>.body/add",
     (), False),
    ("", (), False),
])
def test_scope_path_of_an_op_name(op_name, scopes, backward):
    assert sc.scope_path(op_name) == (scopes, backward)


PROGRAM_A = """HloModule jit_dl4j_mln_staged, is_scheduled=true

%fused_computation.1 (p: f32[8,8]) -> f32[8,8] {
  ROOT %tanh.1 = f32[8,8]{1,0} tanh(f32[8,8]{1,0} %p), metadata={op_name="jit(dl4j_mln_staged)/while/body/jvp(layer0)/tanh" stack_frame_id=3}
}

ENTRY %main {
  %copy.38 = f32[8,8]{1,0} copy(f32[8,8]{0,1} %params_0), metadata={op_name="jit(dl4j_mln_staged)/while/body/transpose(jvp(loss))/layer2/add_any" stack_frame_id=87}
  %fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %copy.38), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(dl4j_mln_staged)/while/body/jvp(layer0)/tanh" stack_frame_id=3}
  ROOT %lstm_seq_fwd.2 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(dl4j_mln_staged)/while/body/jvp(layer0)/lstm_seq_fwd/pallas_call"}, backend_config={}
}
"""
PROGRAM_B = """HloModule jit_dl4j_mln_staged, is_scheduled=true

ENTRY %main {
  %copy.38 = f32[4,4]{1,0} copy(f32[4,4]{0,1} %params_1)
}
"""


def test_an_event_finds_its_instruction_by_its_own_text():
    """Two programs of one name (a net and its reference-mode twin) both
    have a ``%copy.38``: the event's text tells them apart."""
    ops = [tr.Op(0, 10, "%copy.38 = f32[8,8]{1,0} copy(f32[8,8]{0,1} %params_0)", "copy"),
           tr.Op(10, 30, "%fusion.1 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %copy.38), kind=kLoop, calls=%fused_computation.1", "elementwise"),
           tr.Op(30, 90, '%lstm_seq_fwd.2 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %fusion.1), custom_call_target="tpu_custom_call"', "pallas"),
           tr.Op(90, 100, "%copy-done.3 = f32[8,8]{1,0} copy-done(f32[8,8]{1,0} %copy-start.3)", "copy")]
    td = tr.TraceData([tr.DeviceTrace("d", ops=ops)], spans=[],
                      window=(0, 100))
    for texts in ([PROGRAM_B, PROGRAM_A], [PROGRAM_A, PROGRAM_B]):
        joined = sc.Scopes.join(td, texts)
        assert joined.of(ops[0]) == (None, ("loss", "layer2"), True)
        assert joined.of(ops[1]) == (None, ("layer0",), False)
        assert joined.of(ops[2]) == ("lstm_seq_fwd", ("layer0",), False)
        assert joined.of(ops[3]) == (None, (), False)  # not in any program
        assert "tanh.1" not in joined.op_names  # only what the trace ran
    assert sc.attributed_share(td, joined) == pytest.approx(0.9)
    rows = sc.table(td, joined, steps=2)
    assert rows[0] == ("layer0", "fwd", "lstm_seq_fwd",
                       pytest.approx(1e3 * 60e-9 / 2), pytest.approx(0.6))
    assert [r[0] for r in rows] == ["layer0", "layer0", "loss/layer2",
                                    sc.UNSCOPED]


def test_scopes_are_written_beside_the_trace_and_read_back(tmp_path):
    joined = sc.Scopes({"fusion.1": "jit(f)/jvp(layer0)/tanh"})
    joined.write(str(tmp_path))
    assert sc.Scopes.read(str(tmp_path)).op_names == joined.op_names
    assert load_json(str(tmp_path / sc.SCOPES_FILE)) == joined.op_names


def test_recorded_trace_by_scope_names_every_mosaic_kernel(
        recorded, recorded_scopes):
    kernels = {}
    for op in recorded.devices[0].ops:
        kernel, scopes, backward = recorded_scopes.of(op)
        if kernel:
            kernels[kernel] = (scopes, backward)
    assert kernels == {
        "lstm_seq_fwd": (("layer0",), False),
        "lstm_seq_bwd": (("layer0",), True),
        "softmax_xent_fwd": (("loss", "layer1"), False),
        "softmax_xent_bwd": (("loss", "layer1"), True),
        "adam_update": (("optimizer_update",), False),
    }
    share = sc.attributed_share(recorded, recorded_scopes)
    assert 0.5 < share < 1.0  # a 73 us step: the unscoped copies weigh more
    rows = sc.table(recorded, recorded_scopes, steps=3)
    assert len(rows) == 15
    assert sum(r[4] for r in rows) <= 1.0 + 1e-9
    assert ("layer0", "bwd", "lstm_seq_bwd") in [r[:3] for r in rows]
    # the breakdown the harness prints names the kernels too
    labels = [k for k, _ in recorded.breakdown()["device_ops"]]
    assert "pallas:lstm_seq_bwd" in labels and "pallas:adam_update" in labels
    assert not [k for k in labels if "jvp" in k]


def test_recorded_idle_gaps_name_the_programs_spans(recorded, recorded_spans):
    gaps = dict(recorded.breakdown(program_spans=recorded_spans)["idle_gaps"])
    assert set(FIT[:3]) <= set(gaps) and "dispatch" in gaps
    assert ps.DISPATCH in gaps    # the root's own code between its children
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s(), rel=1e-6)
    # most of the idle time of a 3 ms dispatch lies under the program's own
    # spans; without them all of it read ``dispatch``
    under = sum(v for k, v in gaps.items() if k.startswith("dl4j."))
    assert under > 0.85 * sum(gaps.values()) - gaps.get("outside_spans", 0.0)
    assert dict(recorded.breakdown()["idle_gaps"]).keys() \
        <= {"dispatch", "outside_spans"}


def test_the_command_prints_the_window_by_scope(tmp_path, monkeypatch, capsys):
    import shutil

    from benchmarks.harness import main

    cell_dir = tmp_path / "trace" / "charrnn_train_1chip"
    shutil.copytree(RECORDED, cell_dir)
    monkeypatch.setattr(main, "trace_dir", lambda cell: str(cell_dir))
    assert sc.cli(["charrnn_train_1chip"]) == 0
    out = capsys.readouterr().out
    assert "3 dispatches, 1152 steps" in out  # the cell's 384 a dispatch
    assert "lstm_seq_bwd" in out and "loss/layer1" in out
    monkeypatch.setattr(main, "trace_dir", lambda cell: str(tmp_path / "no"))
    assert sc.cli(["charrnn_train_1chip"]) == 1   # no traced run to read
    assert sc.cli(["no_such_cell"]) == 2
    assert "no_such_cell" in capsys.readouterr().err


# -------------------------------------------------------- the lstm kernels
def test_lstm_seq_operations_and_bytes_come_from_the_shapes():
    roof = metric("lstm_seq_roofline")
    B, T, H = 64, 256, 512
    flops, moved = roof.flops_and_bytes("lstm_seq_fwd", B, T, H, 2)
    assert flops == 2 * B * H * 4 * H * T == pytest.approx(34.36e9, rel=1e-3)
    # zx + RW + ys and five residuals + h0 c0 hT cT + peepholes, bfloat16
    assert moved == 2 * (T * B * 4 * H + H * 4 * H + 6 * T * B * H
                         + 4 * B * H + 3 * H)
    bflops, bmoved = roof.flops_and_bytes("lstm_seq_bwd", B, T, H, 2)
    assert bflops == 2 * flops
    assert bmoved == 2 * (7 * T * B * H + T * B * 4 * H + 2 * H * 4 * H
                          + 6 * B * H + 6 * H)
    lean = roof.flops_and_bytes("lstm_seq_lean", B, T, H, 2)
    assert lean[0] == flops and lean[1] == moved - 2 * 5 * T * B * H
    masked = roof.flops_and_bytes("lstm_seq_masked_bwd", B, T, H, 2)
    assert masked == (bflops, bmoved + 2 * T * B)
    # at these sizes the forward is bound by memory, the backward by the MXU
    assert roof.least_seconds("lstm_seq_fwd", B, T, H, 2, V5E) \
        == pytest.approx(moved / 819e9)
    assert roof.least_seconds("lstm_seq_bwd", B, T, H, 2, V5E) \
        == pytest.approx(bflops / 197e12)


def test_recorded_lstm_kernels_share_of_time_and_of_their_roofline(recorded):
    share = metric("lstm_seq_time_share").read(Run(recorded))
    ops = [op for op in recorded.devices[0].ops
           if sc.kernel_name(op.name) in ("lstm_seq_fwd", "lstm_seq_bwd")]
    assert len(ops) == 2 * 3  # one layer, forward and backward, three steps
    spent = sum(op.end - op.start for op in ops) / 1e9
    assert share == pytest.approx(100 * spent / recorded.busy_s())
    roof = metric("lstm_seq_roofline")
    got = roof.share(recorded, 16, 8, 128, 2, V5E)
    least = 3 * (roof.least_seconds("lstm_seq_fwd", 16, 8, 128, 2, V5E)
                 + roof.least_seconds("lstm_seq_bwd", 16, 8, 128, 2, V5E))
    assert got == pytest.approx(least / spent)
    assert 0.0 < got < 1.0
    # no such kernel in the window: 0.0 of the busy time, as
    # pallas_time_share reads, and no share of a roofline at all
    old = tr.load(os.path.join(HERE, "fixtures"))
    assert metric("lstm_seq_time_share").read(Run(old)) == 0.0
    assert roof.share(old, 16, 8, 128, 2, V5E) is None
    assert roof.read(Run(old)) is None
    # a Mosaic call without a name (a program from before ``name=``) cannot
    # be told from the others: nothing is reported, not 0
    unnamed = tr.Op(0, 10, '%jvp__.20 = f32[16384,1]{1,0} custom-call(f32[16384,96]{1,0} %a), custom_call_target="tpu_custom_call"', "pallas")
    parent = tr.TraceData([tr.DeviceTrace("d", ops=[unnamed])], spans=[],
                          window=(0, 10))
    assert sc.kernel_name(unnamed.name) == ""
    assert metric("lstm_seq_time_share").read(Run(parent)) is None
    assert roof.read(Run(parent)) is None


# ------------------------------------------------------------ CPU rehearsal
@pytest.mark.parametrize("name", ["resnet50_train_1chip", "resnet50_train_dp4",
                                  "charrnn_train_1chip"])
def test_the_traced_rehearsal_reads_every_new_metric_the_cell_lists(
        name, tmp_path):
    cell = tiny_cell(name, manifest_path=manifest_with_serving_cell(
        str(tmp_path)))
    listed = {m["name"] for m in cell.per_layer} & NEW_METRICS
    assert ("idle_in_put_ms" in listed) == (name == "resnet50_train_dp4")
    assert ("lstm_seq_roofline" in listed) == (name == "charrnn_train_1chip")
    assert len(listed) >= 8
    line = rehearse(cell, trace=True, seconds=1.0)
    assert line["correct"] is True
    # the CPU takes the XLA path: no Mosaic kernel in the window, so the
    # kernels' share of the busy time is 0 and their roofline says nothing
    silent = listed & {"lstm_seq_roofline"}
    assert not silent & set(line["metrics"])
    for metric_name in sorted(listed - silent):
        value = line["metrics"][metric_name]["value"]  # a number, never absent
        assert math.isfinite(value) and value >= 0.0, metric_name
    m = line["metrics"]
    # set-up ran through the spans: a net was initialised, a program compiled
    assert m["net_init_s"]["value"] > 0 and m["cm_lower_s"]["value"] > 0
    assert m["package_import_s"]["value"] > 0
    if "lstm_seq_time_share" in listed:
        assert m["lstm_seq_time_share"]["value"] == 0.0
    assert 0.0 <= m["scope_attributed_share"]["value"] <= 100.0
    # idle under the program's spans is idle of the window
    idle_ms = sum(m[k]["value"] for k in listed if k.startswith("idle_in_"))
    assert idle_ms <= 1e3 * line["device"]["window_s"]


def test_every_new_entry_lists_its_cells_and_has_its_reader(manifest_path):
    manifest = load_json(manifest_path)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    assert NEW_METRICS <= set(entries)
    cells = {w["name"] for w in manifest["workloads"]}
    for name in NEW_METRICS:
        entry = entries[name]
        # without the list an entry is declared for every later cell
        assert set(entry["workloads"]) <= cells and entry["workloads"]
        assert os.path.isfile(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py"))
    # the head is pinned: what PR 23 declared stands first, in its order,
    # and PR 26's eleven follow it. The tail is free: every later PR appends
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[:12] == PR23_METRICS
    assert set(names[12:23]) == NEW_METRICS
    assert names[23:25] == ["lstm_seq_time_block", "train_step_mfu"]
    assert len(set(names)) == len(names)
