"""The readers that came with ``xing4_train_1chip`` (PR 34): the flash
kernels' share of their roofline where the score and value products differ
in size and one rotary key serves every head, and the latent attention
sublayers' and the hyper-connection pieces' shares of the busy time by
scope. Synthetic traces: the kernels' names are what the v5e's trace carries
(``%flash_bwd_dkv.7``), the times are made up."""

import os
import types

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import scopes as sc
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# xing4_train_1chip's attention: B T H d_qk d_rope d_v itemsize causal
PUBLISHED = (1, 8192, 4, 192, 64, 128, 2, True)
CALL = ('%{name} = bf16[4,8192,128]{{2,1,0}} custom-call(bf16[4,8192,128]'
        '{{2,1,0}} %a), custom_call_target="tpu_custom_call"')
ROOFLINE = "latent_flash_roofline"
NEW = (ROOFLINE, "latent_attention_time_share", "hyper_connection_time_share")
CELL = "xing4_train_1chip"


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


class Cell:
    params = {"batch_per_chip": 1, "seq_len": 8192}
    sizes = {"num_attention_heads": 4, "dtype": "bfloat16"}


def flash(mode="auto", causal=True, latent=True, **more):
    ctx = {"causal": causal, "D": 192}
    if latent:
        ctx.update(d_qk=192, d_v=128, d_rope=64, rope_shared_key=True)
    return {"site": "attention", "variant": "flash", "mode": mode,
            "ctx": ctx, **more}


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
    B, T, H, d_qk, d_rope, d_v, item, _ = PUBLISHED
    entries = T * (T + 1) // 2
    q, k_nope, v = (H * T * d * item for d in (d_qk, d_qk - d_rope, d_v))
    k_rope, rows = T * d_rope * item, H * T * 4    # one rotary key for all
    flops, moved = roof.flops_and_bytes("flash_fwd", *PUBLISHED)
    # one product over 192 and one over 128 over the causal triangle
    assert flops == 2 * H * entries * (192 + 128)
    assert moved == q + k_nope + k_rope + v + v + rows        # .. o, lse
    flops_dq, moved_dq = roof.flops_and_bytes("flash_bwd_dq", *PUBLISHED)
    assert flops_dq == 2 * H * entries * (2 * 192 + 128)
    assert moved_dq == q + k_nope + k_rope + v + v + 2 * rows + q
    flops_dkv, moved_dkv = roof.flops_and_bytes("flash_bwd_dkv", *PUBLISHED)
    assert flops_dkv == 2 * H * entries * (2 * 192 + 2 * 128)
    assert moved_dkv == (q + k_nope + k_rope + v + v + 2 * rows
                         + k_nope + k_rope + v)
    # without causal the whole square counts
    full = roof.flops_and_bytes("flash_fwd", *PUBLISHED[:-1], False)
    assert full == (2.0 * H * T * T * 320, moved)
    # every kernel is bound by its products at this shape: a step's four
    # calls a layer (the forward twice under remat) take 2.4 ms at least
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in roof.PRODUCTS}
    assert least["flash_fwd"] == pytest.approx(flops / 197e12)
    assert least["flash_fwd"] > moved / 819e9
    assert 2 * least["flash_fwd"] + least["flash_bwd_dq"] \
        + least["flash_bwd_dkv"] == pytest.approx(2.44e-3, rel=1e-2)


def test_share_of_the_roofline_from_a_trace_worked_out_by_hand():
    roof = metric(ROOFLINE)
    least = {k: roof.least_seconds(k, PUBLISHED, V5E) for k in roof.PRODUCTS}
    ms = 1_000_000
    ops = [tr.Op(0, 5 * ms, "%fusion.1 = f32[] fusion()", "mxu"),
           kernel("flash_fwd.3", 10 * ms, 11 * ms),
           kernel("flash_fwd.4", 100 * ms, 101 * ms),
           kernel("flash_bwd_dq.5", 200 * ms, 202 * ms),
           kernel("flash_bwd_dkv.7", 300 * ms, 303 * ms),
           kernel("grouped_matmul_fwd.9", 400 * ms, 401 * ms)]
    want = 100.0 * (2 * least["flash_fwd"] + least["flash_bwd_dq"]
                    + least["flash_bwd_dkv"]) / 0.007
    run = run_with([flash()], window(*ops))
    assert roof.read(run) == pytest.approx(want)
    assert 30.0 < want < 36.0
    # an event outside the window is not counted
    late = run_with([flash()], window(*ops, end=250 * ms))
    assert roof.read(late) == pytest.approx(
        100.0 * (2 * least["flash_fwd"] + least["flash_bwd_dq"]) / 0.004)
    # the same calls without causal have twice the entries to multiply
    assert roof.read(run_with([flash(causal=False)], window(*ops))) \
        == pytest.approx(want * 2 * 8192 / 8193)


def test_no_event_no_latent_selection_no_trace_read_nothing_never_zero():
    roof = metric(ROOFLINE)
    other = tr.Op(0, 1000, "%fusion.1 = f32[] fusion()", "mxu")
    op = kernel("flash_fwd.1", 0, 50_000_000)
    assert roof.read(run_with([flash()], window(other))) is None
    assert roof.read(run_with([flash()], None)) is None
    # a plain call's selection (one size of product: the hybrid cell's, a
    # program from before this PR) is not this reader's to describe
    assert roof.read(run_with([flash(latent=False)], window(op))) is None
    xla = dict(flash(), variant="xla")
    assert roof.read(run_with([xla], window(op))) is None
    assert roof.read(run_with(None, window(op))) is None
    assert roof.read(run_with([flash(mode="reference")], window(op))) is None


@pytest.mark.parametrize("kernel_name", ["flash_fwd", "flash_bwd_dq",
                                         "flash_bwd_dkv"])
def test_a_planted_event_at_its_least_time_reads_100_never_over(kernel_name):
    roof = metric(ROOFLINE)
    least_ns = roof.least_seconds(kernel_name, PUBLISHED, V5E) * 1e9
    for slower in (1.0, 1.5, 12.8):
        # whole nanoseconds, as a trace has them: never short of the least
        op = kernel(kernel_name + ".2", 0, int(-(-least_ns * slower // 1)))
        got = roof.read(run_with([flash()], window(op)))
        assert got == pytest.approx(100.0 / slower, rel=1e-5)
        assert got <= 100.0


def test_time_shares_read_the_vertex_scopes_of_a_and_h_pieces():
    attention = metric("latent_attention_time_share")
    hyper = metric("hyper_connection_time_share")
    ops = [tr.Op(0, 400, "%fusion.1 = f32[] fusion()", "mxu"),
           tr.Op(400, 700, "%fusion.2 = f32[] fusion()", "mxu"),
           tr.Op(700, 900, "%fusion.3 = f32[] fusion()", "elementwise"),
           tr.Op(900, 1000, "%copy.4 = f32[] copy()", "copy"),
           tr.Op(1000, 1200, "%fusion.5 = f32[] fusion()", "mxu")]
    body = "jit(dl4j_graph_staged)/while/body/"
    joined = sc.Scopes({
        "fusion.1": body + "b0A_mixer/q_proj/dot_general",
        "fusion.2": body + "transpose(jvp(b2H_post))/mul",
        "fusion.3": body + "b3H_maps/sinkhorn/div",
        "copy.4": body + "transpose(jvp(b4A_mixer))/scores/transpose",
        "fusion.5": body + "b3E_mixer/experts/mul"})
    trace = window(*ops, end=1200)
    assert attention.share(trace, joined, attention.ATTENTION_BLOCK,
                           attention.KERNELS) == pytest.approx(500 / 1200)
    assert attention.share(trace, joined, hyper.HYPER_CONNECTION) \
        == pytest.approx(500 / 1200)
    # neither takes the other's vertices, nor an expert block's
    assert not hyper.HYPER_CONNECTION.match("b3E_mixer")
    assert not attention.ATTENTION_BLOCK.match("b3H_pre")
    # the flash kernels count for attention by their names, scope or none
    flash_op = kernel("flash_bwd_dq.2", 1200, 1800)
    trace = window(*ops, flash_op, end=1800)
    assert attention.share(trace, joined, attention.ATTENTION_BLOCK,
                           attention.KERNELS) == pytest.approx(1100 / 1800)
    assert attention.share(trace, joined, hyper.HYPER_CONNECTION) \
        == pytest.approx(500 / 1800)
    assert attention.share(trace, sc.Scopes({}), hyper.HYPER_CONNECTION) == 0.0


def test_the_entries_list_the_cell_and_the_preset_the_silent_roofline():
    manifest = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_samples_per_s_per_chip"
        assert entries[name]["unit"] == "%"
    assert entries[ROOFLINE]["layer"] == entries["grouped_matmul_roofline"][
        "layer"] == "Pallas kernels"
    assert entries["hyper_connection_time_share"]["layer"] \
        == entries["expert_blocks_time_share"]["layer"]
    preset = load_json(os.path.join(
        REPO, "tests", "benchmark_harness", "presets", "cells", CELL + ".json"))
    assert {ROOFLINE, "grouped_matmul_roofline"} \
        <= set(preset["reads_nothing_on_cpu"])
    # every metric the hybrid cell lists but the scan's, and the three new;
    # less the two whose own tests hold their lists to the cells they had
    # (test_benchmark_flash.py, test_benchmark_updater.py: a benchmark PR's
    # to loosen, PERF.md section 7)
    hybrid = {m["name"] for m in manifest["per_layer"]
              if "nemotron3_nano_train_1chip" in m.get("workloads", [])}
    mine = {m["name"] for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])}
    waiting = {"flash_tiles_walked_share", "updater_in_place_share"}
    assert mine == {m for m in hybrid if not m.startswith("ssd_scan_")} \
        - waiting | set(NEW)


def test_the_traced_rehearsal_reads_the_two_time_shares():
    """On the CPU the XLA attention path and ``ragged_dot`` run: the scopes
    are there, so both shares read above 0; the roofline reads nothing."""
    line = rehearse(tiny_cell(CELL), trace=True, seconds=1.0)
    assert line["correct"] is True
    assert line["metrics"]["latent_attention_time_share"]["value"] > 0
    assert line["metrics"]["hyper_connection_time_share"]["value"] > 0
    assert ROOFLINE not in line["metrics"]
    assert 0 < line["metrics"]["moe_rows_per_token"]["value"] <= 2
