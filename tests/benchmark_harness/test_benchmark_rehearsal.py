"""Each traffic mix and configuration, rehearsed end to end on the CPU at a
tiny preset that lives in the tests only: the generator's set-up, window and
checks, the per-layer readers, the final line. No time or rate read here
means anything; what is asserted is control flow and counts."""

import os

import pytest

from bench_presets import (ADDED_CELL, ADDED_METRIC, ADDED_ROOFLINE, REPO,
                           manifest_with_a_later_prs_additions,
                           manifest_with_serving_cell, reads_nothing_on_cpu,
                           rehearse)
from bench_presets import tiny_cell as _tiny_cell
from benchmarks.harness.discovery import load_json, load_module, resolve_cell

DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
# every cell the manifest has, and the two that only the tests add: the
# decode cell, and the cell of a PR that brings a configuration with a
# kernel's roofline. A PR that appends a cell (and adds its two presets) has
# it rehearsed here as that last one is
CELLS = [w["name"] for w in load_json(os.path.join(REPO, "BENCHMARK.json"))[
    "workloads"]] + ["charrnn_decode_c8", ADDED_CELL]


def manifest_that_has(name, tmp_path) -> str:
    if name == ADDED_CELL:
        return manifest_with_a_later_prs_additions(tmp_path)
    return manifest_with_serving_cell(str(tmp_path))


@pytest.fixture
def tiny_cell(tmp_path):
    """A cell of the manifest, or one that only the tests add to it, at its
    tiny preset."""
    return lambda name: _tiny_cell(
        name, manifest_path=manifest_that_has(name, tmp_path))


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end_run(name, tiny_cell):
    cell = tiny_cell(name)
    line = rehearse(cell)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["count"] == cell.chips
    assert "breakdown" not in line
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("name", CELLS)
def test_cell_traced_run(name, tiny_cell, tmp_path):
    cell = tiny_cell(name)
    line = rehearse(cell, trace=True, seconds=1.0)
    assert line["correct"] is True
    declared = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) <= declared
    # a reader that finds nothing to read returns nothing and its metric is
    # left out of the line (a kernel's roofline where the CPU takes the XLA
    # path; a boundary where a loaded CPU fits one dispatch): the cell's
    # preset names those, and every other metric it lists is read
    silent = reads_nothing_on_cpu(name, manifest_that_has(name, tmp_path))
    assert silent <= declared
    assert declared - set(line["metrics"]) <= silent
    rooflines = {m for m in declared if m.endswith("_roofline")}
    assert rooflines <= silent and not rooflines & set(line["metrics"])
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    bd = line["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    if name == "charrnn_decode_c8":
        assert 1 <= line["metrics"]["decode_tick_rows_mean"]["value"] <= 8
        assert line["metrics"]["decode_ticks_per_s"]["value"] > 0
    if "fused_sites" in declared:
        assert line["metrics"]["fused_sites"]["value"] == 0  # CPU: XLA paths
    if "train_step_mfu" in declared:
        assert 0 < line["metrics"]["train_step_mfu"]["value"] < 100
    if name == "resnet50_train_dp4":
        assert line["metrics"]["collective_time_share"]["value"] > 0
    if name == ADDED_CELL:
        assert line["metrics"][ADDED_METRIC]["value"] >= 1
        assert ADDED_ROOFLINE in declared


@pytest.mark.parametrize("name,param,value", [
    # no float matches to 0 relative error
    ("charrnn_train_1chip", "reference_rtol", 0.0),
    # one tolerance per twin step; on the CPU the twin runs the same XLA
    # path and differs by exactly 0, which only a negative tolerance refuses
    ("charrnn_train_1chip", "twin_rtol", [1.0, 1.0, -1.0]),
    ("charrnn_decode_c8", "replay_reference_atol", 0.0),
])
def test_a_difference_beyond_a_tolerance_makes_the_run_incorrect(
        name, param, value, tiny_cell, capfd):
    cell = tiny_cell(name)
    cell.params[param] = value
    line = rehearse(cell)
    assert line["correct"] is False
    # the number at fault is in the line beside its limit, and in the last
    # lines of standard error, which is all the driver's record keeps
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert len(over) == 1 and list(line)[-1] == "compared"
    err = capfd.readouterr().err.splitlines()
    assert err[-1].startswith("correct False: FAILED [")
    (at_fault,) = over
    assert [l for l in err[-1 - len(line["compared"]):-1]
            if l.startswith(f"compared {at_fault} ")]


def test_a_traced_run_writes_where_its_caller_says_and_only_there(
        tiny_cell, tmp_path):
    """The command's runs trace into ``<checkout>/.bench_out/trace/<cell>``
    (``scopes`` reads there); every other caller hands in a root of its own,
    so two rehearsals of one cell on two test workers share no directory
    (they used to delete each other's files) and tier-1 leaves nothing in
    the checkout."""
    import time

    import jax

    from bench_presets import FAKE_PEAKS
    from benchmarks.harness import main, scopes, trace

    cell = tiny_cell("charrnn_train_1chip")
    default = os.path.join(REPO, ".bench_out", "trace", cell.name)
    assert main.trace_dir(cell) == default
    assert main.trace_dir(cell, str(tmp_path)) == str(tmp_path / cell.name)
    checkout_had = os.path.isdir(os.path.join(REPO, ".bench_out"))
    stale = tmp_path / cell.name / "left_by_an_earlier_run.xplane.pb"
    stale.parent.mkdir()
    stale.write_bytes(b"not a trace")
    main.run_cell(cell, seed=5, seconds=0.3, trace=True,
                  devices=jax.devices()[:1], t0=time.perf_counter(),
                  peaks=FAKE_PEAKS, cpu_rehearsal=True,
                  trace_root=str(tmp_path))
    assert not stale.exists()     # a run empties its own directory first
    assert len(trace.find_xplane_files(str(tmp_path / cell.name))) == 1
    # the scopes the traced run joined lie beside its trace
    assert os.path.isfile(tmp_path / cell.name / scopes.SCOPES_FILE)
    assert os.path.isdir(os.path.join(REPO, ".bench_out")) == checkout_had


def test_a_traced_run_without_a_device_plane_is_refused(tiny_cell):
    """Host events never stand in for the device: only the tests' rehearsal
    flag lets the CPU backend's trace be read."""
    cell = tiny_cell("charrnn_train_1chip")
    with pytest.raises(ValueError, match="no device plane"):
        rehearse(cell, trace=True, seconds=0.3, cpu_rehearsal=False)


@pytest.mark.parametrize("config,flops", [
    ("resnet50", 6 * 3.857973248e9),     # 3.86 G multiply-adds forward
    ("charrnn_2x512", 20.348928e6),      # 2*3*(4H(I+H) + 4H(2H) + H*V)
])
def test_model_flops_come_from_the_shapes(config, flops):
    cell_sizes = load_json(f"{_bench()}/configs/{config}.json")
    mod = load_module(f"{_bench()}/configs/{config}.py")
    assert mod.model_flops_per_sample(cell_sizes) == pytest.approx(flops)


def _bench():
    return resolve_cell("charrnn_train_1chip").bench_dir


@pytest.mark.parametrize("name,rate,share", [
    # 23.15 GFLOP an image at PR 23's 2,886 images/s; 20.35 MFLOP a character
    # at PR 27's 6.07M characters/s, over the v5e's 197 TFLOP/s
    ("resnet50_train_1chip", 2886.0, 33.91),
    ("resnet50_train_dp4", 2698.9, 31.71),
    ("charrnn_train_1chip", 6.0738e6, 62.74),
])
def test_train_step_mfu_is_model_flops_times_throughput_over_the_peak(
        name, rate, share):
    import types

    from benchmarks.harness import gate

    cell = resolve_cell(name)
    assert "train_step_mfu" in {m["name"] for m in cell.per_layer}
    read = cell.metric_reader("train_step_mfu")
    peaks = gate.peaks_row("TPU v5 lite")

    def run(end_to_end):
        return types.SimpleNamespace(cell=cell, peaks=peaks,
                                     result={"end_to_end": end_to_end})

    rate_key = "train_samples_per_s_per_chip"
    assert read(run({rate_key: rate})) == pytest.approx(share, abs=0.01)
    flops = cell.config_module().model_flops_per_sample(cell.sizes)
    assert gate.share_of_peak(flops, rate, peaks) \
        == read(run({rate_key: rate}))
    # over the peak: a wrong FLOP count is raised, never reported or clipped
    with pytest.raises(ValueError, match=r"train_step_mfu reads [12]\d\d\.\d%"):
        read(run({rate_key: 3.2 * rate}))
    # a cell without the rate (a serving one) has nothing to read
    assert read(run({"serve_ops_per_s": 1.0})) is None


def test_memory_held_is_in_use_plus_reserved_on_the_fullest_chip():
    from benchmarks.harness import gate

    class Dev:
        def __init__(self, **ms):
            self._ms = ms

        def memory_stats(self):
            return self._ms or None

    a = Dev(bytes_in_use=5, bytes_reserved=7, peak_bytes_in_use=900,
            peak_bytes_reserved=900)
    b = Dev(bytes_in_use=20, bytes_reserved=1)
    assert gate.memory_held_bytes([a, b]) == 21   # lifetime peaks play no part
    assert gate.memory_held_bytes([Dev()]) == 0   # a backend without counters


def test_session_lengths_are_one_multiset_in_another_order_per_seed():
    mod = load_module(f"{_bench()}/generators/decode_sessions.py")
    spec = load_json(f"{_bench()}/traffic/decode_sessions_closed.json")[
        "params"]["session_tokens"]
    a = mod.session_lengths(spec, 8, seed=1)
    b = mod.session_lengths(spec, 8, seed=2)
    flat = lambda per_client: sorted(int(x) for c in per_client for x in c)  # noqa: E731
    assert flat(a) == flat(b) and len(flat(a)) == spec["strata"]
    assert [list(c) for c in a] != [list(c) for c in b]
    assert min(flat(a)) >= spec["min"] and max(flat(a)) <= spec["max"]
    middle = flat(a)[len(flat(a)) // 2]
    assert abs(middle - spec["median"]) <= 2
    assert all(len(c) == spec["strata"] // 8 for c in a)
    # low discrepancy: any 8 consecutive sessions of a client hold short and
    # long ones, so a window's mix hardly depends on the seed
    for c in a:
        for i in range(len(c)):
            run = [int(c[(i + j) % len(c)]) for j in range(8)]
            assert min(run) < spec["median"] < max(run)
