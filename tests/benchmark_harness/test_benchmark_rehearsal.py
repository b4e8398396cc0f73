"""Each traffic mix and configuration, rehearsed end to end on the CPU at a
tiny preset that lives in the tests only: the generator's set-up, window and
checks, the per-layer readers, the final line. No time or rate read here
means anything; what is asserted is control flow and counts."""

import pytest

from bench_presets import manifest_with_serving_cell, rehearse
from bench_presets import tiny_cell as _tiny_cell
from benchmarks.harness.discovery import load_json, load_module, resolve_cell

DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture
def tiny_cell(tmp_path):
    """A cell of the manifest, or the decode cell that only the tests add to
    it, at its tiny preset."""
    return lambda name: _tiny_cell(
        name, manifest_path=manifest_with_serving_cell(str(tmp_path)))


@pytest.mark.parametrize("name", ["resnet50_train_1chip", "resnet50_train_dp4",
                                  "charrnn_train_1chip", "charrnn_decode_c8"])
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


@pytest.mark.parametrize("name", ["resnet50_train_dp4", "charrnn_train_1chip",
                                  "charrnn_decode_c8"])
def test_cell_traced_run(name, tiny_cell):
    cell = tiny_cell(name)
    line = rehearse(cell, trace=True, seconds=1.0)
    assert line["correct"] is True
    declared = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) <= declared
    # a loaded CPU may fit one dispatch in the window: no boundary to read
    assert declared - set(line["metrics"]) <= {"dispatch_gap_ms"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["device"]["busy_s"] > 0
    assert line["device"]["window_s"] >= line["device"]["busy_s"]
    bd = line["breakdown"]
    assert 1 <= len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    if name == "charrnn_decode_c8":
        assert 1 <= line["metrics"]["decode_tick_rows_mean"]["value"] <= 8
        assert line["metrics"]["decode_ticks_per_s"]["value"] > 0
    else:
        assert line["metrics"]["fused_sites"]["value"] == 0  # CPU: XLA paths
    if name == "resnet50_train_dp4":
        assert line["metrics"]["collective_time_share"]["value"] > 0


@pytest.mark.parametrize("name,param,value", [
    # no float matches to 0 relative error
    ("charrnn_train_1chip", "reference_rtol", 0.0),
    # one tolerance per twin step; on the CPU the twin runs the same XLA
    # path and differs by exactly 0, which only a negative tolerance refuses
    ("charrnn_train_1chip", "twin_rtol", [1.0, 1.0, -1.0]),
    ("charrnn_decode_c8", "replay_reference_atol", 0.0),
])
def test_a_difference_beyond_a_tolerance_makes_the_run_incorrect(
        name, param, value, tiny_cell):
    cell = tiny_cell(name)
    cell.params[param] = value
    assert rehearse(cell)["correct"] is False


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
