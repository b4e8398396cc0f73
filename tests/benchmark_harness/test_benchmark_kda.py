"""The readers that came with ``kimi_linear_train_1chip`` (PR 36): the Kimi
Delta Attention sublayers' share of the busy time by scope and kernel name,
and the recurrence's share of its roofline, read from whatever implements it
(the scope ``kda_recurrence``, kernels named ``kda_*``). Synthetic traces:
the scopes are what ``harness/scopes.py`` joins in from the program's text,
a kernel's name what the v5e's trace carries (``%kda_fwd.3``), the times are
made up."""

import os
import types

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import scopes as sc
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# kimi_linear_train_1chip's recurrence: B T H K V chunk itemsize
PUBLISHED = (1, 8192, 32, 128, 128, 64, 2)
CALL = ('%{name} = bf16[8192,4096]{{1,0}} custom-call(bf16[8192,4096]'
        '{{1,0}} %a), custom_call_target="tpu_custom_call"')
CELL = "kimi_linear_train_1chip"
SHARE, ROOFLINE = "kda_time_share", "kda_recurrence_roofline"
BODY = "jit(dl4j_graph_staged)/while/body/"
MS = 1_000_000


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


def selection(mode="auto", **ctx):
    B, T, H, K, V, chunk, itemsize = PUBLISHED
    return {"site": "kda_recurrence", "variant": "reference", "mode": mode,
            "chunk": chunk,
            "ctx": dict(dict(B=B, T=T, H=H, K=K, V=V, chunk=chunk,
                             itemsize=itemsize), **ctx)}


def run_with(log, trace=None, steps=4):
    program = {} if log is None else {"selection_log": log}
    return types.SimpleNamespace(
        result={"program": program, "attempted": steps}, trace=trace,
        peaks=V5E, trace_dir=None)


def window(*ops, end=10_000 * MS, device="d"):
    return tr.TraceData([tr.DeviceTrace(device, ops=list(ops))], spans=[],
                        window=(0, end))


def fusion(i, start, end, bucket="mxu"):
    return tr.Op(start, end, f"%fusion.{i} = f32[] fusion()", bucket)


def kernel(name, start, end):
    return tr.Op(start, end, CALL.format(name=name), "pallas")


def joined_as(monkeypatch, module, op_names):
    """``scopes.of_run`` answers with these scopes (the join itself is
    ``test_benchmark_program_spans.py``'s subject)."""
    joined = sc.Scopes(op_names)
    monkeypatch.setattr(module.scopes, "of_run", lambda run: joined)
    return joined


# two KDA sublayers' recurrences (forward, the rematted forward, backward),
# a projection of the first, an expert block, an attention block
SCOPES = {
    "fusion.1": BODY + "b0K_mixer/kda_recurrence/dot_general",
    "fusion.2": BODY + "b0K_mixer/proj/dot_general",
    "fusion.3": BODY + "transpose(jvp(b0K_mixer))/transpose(jvp("
                       "kda_recurrence))/while/body/dot_general",
    "fusion.4": BODY + "checkpoint/rematted_computation/b2K_mixer/"
                       "kda_recurrence/checkpoint/mul",
    "fusion.5": BODY + "b3E_mixer/experts/mul",
    "fusion.6": BODY + "b6A_mixer/scores/dot_general",
    "fusion.7": BODY + "b2K_norm/mul",
}
OPS = [fusion(1, 0, 40 * MS), fusion(2, 40 * MS, 50 * MS),
       fusion(3, 50 * MS, 130 * MS), fusion(4, 130 * MS, 170 * MS),
       fusion(5, 170 * MS, 180 * MS), fusion(6, 180 * MS, 195 * MS),
       fusion(7, 195 * MS, 200 * MS)]


def test_operations_and_bytes_are_those_of_the_mathematics():
    roof = metric(ROOFLINE)
    B, T, H, K, V, C, item = PUBLISHED
    (f_fwd, b_fwd), (f_bwd, b_bwd) = roof.flops_and_bytes(*PUBLISHED)
    # a position and head: half a chunk of columns for two scores, the
    # system and the output's own part, and three products with the state
    assert f_fwd == 2.0 * T * H * (32 * (2 * 128 + 2 * 128) + 3 * 128 * 128)
    # q k v o at two bytes, the float32 log-decays (128 a head) and beta
    assert b_fwd == T * H * (2 * 4 * 128 + 4 * 129)
    assert (f_bwd, b_bwd) == (2 * f_fwd, 2 * b_fwd)
    # both passes are bound by their bytes: 0.49 and 0.99 ms a sublayer
    least = roof.least_seconds(PUBLISHED, V5E)
    assert least == pytest.approx(3 * b_fwd / 819e9)
    assert least == pytest.approx(1.479e-3, rel=1e-3)
    assert f_fwd / 197e12 < b_fwd / 819e9
    # a longer chunk costs operations, no bytes
    longer = roof.flops_and_bytes(B, T, H, K, V, 128, item)
    assert longer[0][0] > f_fwd and longer[0][1] == b_fwd


def test_share_of_the_roofline_from_a_trace_worked_out_by_hand(monkeypatch):
    roof = metric(ROOFLINE)
    joined = joined_as(monkeypatch, roof, SCOPES)
    trace = window(*OPS)
    # 40 + 80 + 40 ms under the scope, two KDA vertices
    assert roof.traced(trace, joined) == (pytest.approx(0.160), 2)
    least = roof.least_seconds(PUBLISHED, V5E)
    got = roof.read(run_with([selection()], trace, steps=4))
    assert got == pytest.approx(100.0 * 4 * 2 * least / 0.160)
    assert 7.0 < got < 8.0
    # a kernel named kda_* counts by its name, scope or none, and a window
    # that ends earlier takes what lies inside it
    with_kernel = window(*OPS, kernel("kda_bwd_dqk.3", 300 * MS, 310 * MS))
    assert roof.traced(with_kernel, joined)[0] == pytest.approx(0.170)
    assert roof.traced(window(*OPS, end=100 * MS), joined)[0] \
        == pytest.approx(0.090)
    # another kernel's event and another block's scope are not its
    other = window(*OPS, kernel("flash_fwd.2", 300 * MS, 310 * MS))
    assert roof.traced(other, joined)[0] == pytest.approx(0.160)


def test_a_swapped_implementation_moves_the_number_without_an_edit(monkeypatch):
    """Kernels ``kda_fwd`` / ``kda_bwd`` at their least time, one call a
    sublayer and pass under the vertex's scope: 100, and never over."""
    roof = metric(ROOFLINE)
    (f_fwd, b_fwd), _ = roof.flops_and_bytes(*PUBLISHED)
    fwd_ns = -(-b_fwd / 819e9 * 1e9 // 1)       # whole nanoseconds, rounded up
    for slower in (1, 3):
        ops = [kernel("kda_fwd.1", 0, int(slower * fwd_ns)),
               kernel("kda_bwd.2", 10 * MS,
                      10 * MS + int(slower * 2 * fwd_ns))]
        joined_as(monkeypatch, roof, {
            "kda_fwd.1": BODY + "b0K_mixer/kda_recurrence/kda_fwd",
            "kda_bwd.2": BODY + "transpose(jvp(b0K_mixer))/kda_recurrence/"
                                "kda_bwd"})
        got = roof.read(run_with([selection()], window(*ops), steps=1))
        assert got == pytest.approx(100.0 / slower, rel=1e-5)
        assert got <= 100.0


def test_nothing_to_read_reads_nothing_never_zero(monkeypatch):
    roof = metric(ROOFLINE)
    joined_as(monkeypatch, roof, SCOPES)
    trace = window(*OPS)
    assert roof.read(run_with([selection()], trace)) > 0
    assert roof.read(run_with([selection()], None)) is None
    # a program from before the site (the parent commit): no selection
    assert roof.read(run_with([], trace)) is None
    assert roof.read(run_with(None, trace)) is None
    assert roof.read(run_with([selection(mode="reference")], trace)) is None
    # two shapes would not describe the events
    assert roof.read(run_with([selection(), selection(T=4096)], trace)) is None
    # no operation of the recurrence in the window
    assert roof.read(run_with([selection()], window(OPS[1], OPS[4]))) is None
    assert roof.read(run_with([selection()], trace, steps=0)) is None
    # a trace without a device plane (the tests' CPU rehearsal)
    assert roof.read(run_with([selection()], window(
        *OPS, device=roof.NO_DEVICE_PLANE))) is None
    # a program that does not offer its text
    monkeypatch.setattr(roof.scopes, "of_run", lambda run: None)
    assert roof.read(run_with([selection()], trace)) is None


def test_time_share_reads_the_k_vertices_and_kernels_by_name(monkeypatch):
    share = metric(SHARE)
    joined_as(monkeypatch, share, SCOPES)
    # b0K and b2K: 40 + 10 + 80 + 40 + 5 of 200 ms
    assert share.read(run_with(None, window(*OPS, end=200 * MS))) \
        == pytest.approx(100.0 * 175 / 200)
    with_kernel = window(*OPS, kernel("kda_fwd.9", 200 * MS, 225 * MS),
                         end=225 * MS)
    assert share.read(run_with(None, with_kernel)) \
        == pytest.approx(100.0 * 200 / 225)
    # neither an expert block's nor an attention block's vertex is taken,
    # and the expert blocks' reader takes no K vertex
    assert not share.KDA_BLOCK.match("b3E_mixer")
    assert not share.KDA_BLOCK.match("b6A_mixer")
    experts = metric("expert_blocks_time_share")
    assert not experts.EXPERT_BLOCK.match("b0K_mixer")
    # 0.0 is a reading where no operation is either; nothing without text
    joined_as(monkeypatch, share, {})
    assert share.read(run_with(None, window(*OPS))) == 0.0
    monkeypatch.setattr(share.scopes, "of_run", lambda run: None)
    assert share.read(run_with(None, window(*OPS))) is None


def test_the_entries_list_the_cell_and_the_preset_the_silent_rooflines():
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in (SHARE, ROOFLINE):
        assert CELL in entries[name]["workloads"]
        assert entries[name]["moves"] == "train_samples_per_s_per_chip"
        assert (entries[name]["unit"], entries[name]["source"]) \
            == ("%", "device_trace")
    assert entries[ROOFLINE]["layer"] == entries["grouped_matmul_roofline"][
        "layer"] == "Pallas kernels"
    assert entries[SHARE]["layer"] \
        == entries["expert_blocks_time_share"]["layer"]
    preset = load_json(os.path.join(
        REPO, "tests", "benchmark_harness", "presets", "cells", CELL + ".json"))
    assert {ROOFLINE, "grouped_matmul_roofline", "dispatch_gap_ms"} \
        == set(preset["reads_nothing_on_cpu"])
    # every metric both other drawn cells are on, and the two new
    both = {m["name"] for m in manifest["per_layer"]
            if {"nemotron3_nano_train_1chip", "xing4_train_1chip"}
            <= set(m.get("workloads", []))}
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    assert mine == both | {SHARE, ROOFLINE}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "train_staged_gradcheck")
    params = load_json(os.path.join(REPO, "benchmarks", "workloads",
                                    CELL + ".json"))["params"]
    assert (params["batch_per_chip"], params["seq_len"], params["slots"],
            params["steps_per_dispatch"]) == (1, 8192, 8, 4)


def test_the_traced_rehearsal_reads_the_time_share_and_no_roofline():
    """On the CPU the recurrence's jax.numpy runs under its scopes, so the
    share reads above 0; a roofline is read from a device plane only."""
    from deeplearning4j_tpu.ops import kernel_select as ks

    ks.reset()  # the log is the process's: earlier tests' selections go
    line = rehearse(tiny_cell(CELL), trace=True, seconds=1.0)
    assert line["correct"] is True
    assert 0 < line["metrics"][SHARE]["value"] < 100
    assert ROOFLINE not in line["metrics"]
    assert 0 < line["metrics"]["moe_rows_per_token"]["value"] <= 2
    (rec,) = [r for r in ks.selection_log() if r["site"] == "kda_recurrence"]
    assert (rec["variant"], rec["chunk"], rec["ctx"]["T"]) \
        == ("reference", 4, 16)
