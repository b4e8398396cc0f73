"""The cells of ``fit(iterator)`` (PR 38) and the readers of what the program
records there: each reader on hand-built spans, gaps and counter deltas (and
``None`` on a program without them), the manifest's entries, the generator's
iterator, and rehearsals whose planted faults (the program drops the last
window it was handed, or trains none of the window) ``verify`` catches,
however slow the host.

``resnet50_fit_iterator`` is in the manifest. Its staged sibling is not: its
rate spread 1.6% over five seeds on the v5e, and its entries wait in
``fixtures/fit_iterator_staged_cell_entries.json``. Its files are here, and
the tests below rehearse it through a copy of the manifest with those
entries added, as a later PR would add them."""

import collections
import json
import os
import time

import pytest

from bench_presets import REPO, rehearse, tiny_cell
from benchmarks.harness import fit_iterator_spans as fis
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_json, load_module

PER_BATCH, STAGED = "resnet50_fit_iterator", "resnet50_fit_iterator_staged20"
LAYER = "input pipeline and the fit(iterator) loops"
STAGED_ENTRIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "fixtures",
                              "fit_iterator_staged_cell_entries.json")
# the new metrics and the cells each reads in, once the staged one is in too
NEW = {"iterator_wait_share": [PER_BATCH, STAGED],
       "idle_in_next_batch_share": [PER_BATCH, STAGED],
       "idle_in_h2d_share": [PER_BATCH, STAGED],
       "prefetch_ready_share": [PER_BATCH, STAGED],
       "input_gb_per_s": [PER_BATCH, STAGED],
       "idle_in_stack_share": [STAGED]}


def manifest_with_the_staged_cell(tmp_dir) -> str:
    """A copy of ``BENCHMARK.json`` under ``tmp_dir`` with the staged cell's
    entries added as the fixture says: the cell and its own metric appended,
    its name appended to the ``workloads`` of the metrics it reports. The
    benchmark's files are reached through a link beside it."""
    m = load_json(os.path.join(REPO, "BENCHMARK.json"))
    extra = load_json(STAGED_ENTRIES)
    for section in ("workloads", "end_to_end", "per_layer"):
        m[section] = m[section] + extra[section]
    for entry in m["end_to_end"] + m["per_layer"]:
        if entry["name"] in extra["appended_to"]:
            entry["workloads"].append(STAGED)
    link = os.path.join(str(tmp_dir), "benchmarks")
    if not os.path.exists(link):
        os.symlink(os.path.join(REPO, "benchmarks"), link)
    path = os.path.join(str(tmp_dir), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


@pytest.fixture
def cell(tmp_path):
    """Either cell at its tiny preset."""
    return lambda name: tiny_cell(
        name, manifest_path=manifest_with_the_staged_cell(tmp_path))


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


def generator():
    return load_module(os.path.join(REPO, "benchmarks", "generators",
                                    "iterator_training.py"))


class Run:
    def __init__(self, trace=None, result=None, trace_dir=None):
        self.trace, self.result, self.trace_dir = trace, result, trace_dir


# ------------------------------------------------------- a hand-built window
def staged_window():
    """Two cycles of 100 in a traced window of 200. A cycle: four waits of 2
    (0-8), the stack 8-38, the put 38-40, the dispatch 40-100 with prepare
    40-42, launch 42-44, fetch 44-99, listeners 99-100. The device is busy
    44-98 and idle for the other 46: 8 under the waits, 30 under the stack,
    2 under the put, 6 inside the dispatch."""
    ops, spans = [], [ps.ProgramSpan("dl4j.fit.epoch", 0, 200)]
    for k in range(2):
        t = 100 * k
        ops.append(tr.Op(t + 44, t + 98, "%fusion.1 = f32[] fusion()", "mxu"))
        spans += [ps.ProgramSpan("dl4j.fit.next_batch", t + 2 * i, t + 2 * i + 2)
                  for i in range(4)]
        spans += [
            ps.ProgramSpan("dl4j.fit.stack", t + 8, t + 38),
            ps.ProgramSpan("dl4j.fit.put", t + 38, t + 40),
            ps.ProgramSpan("dl4j.fit.dispatch", t + 40, t + 100),
            ps.ProgramSpan("dl4j.fit.prepare", t + 40, t + 42),
            ps.ProgramSpan("dl4j.fit.launch", t + 42, t + 44),
            ps.ProgramSpan("dl4j.fit.fetch", t + 44, t + 99),
            ps.ProgramSpan("dl4j.fit.listeners", t + 99, t + 100)]
    trace = tr.TraceData([tr.DeviceTrace("d", ops=ops)],
                         spans=[tr.HostSpan("window", 0, 200)], window=(0, 200))
    return trace, sorted(spans, key=lambda s: s.start)


def test_shares_of_a_hand_built_staged_window_add_up_to_its_idle_share():
    trace, spans = staged_window()
    assert fis.span_share(trace, spans, ["dl4j.fit.next_batch"]) == 8.0
    wait = fis.idle_share(trace, spans, ["dl4j.fit.next_batch"])
    stack = fis.idle_share(trace, spans, ["dl4j.fit.stack"])
    h2d = fis.idle_share(trace, spans, ["dl4j.fit.put", "dl4j.fit.step"])
    assert (wait, stack, h2d) == (8.0, 30.0, 2.0)
    # with the dispatch's own spans (4 of prepare + launch, 1 of fetch after
    # the last op, 1 of listeners) that is all the device idled
    rest = fis.idle_share(trace, spans, ["dl4j.fit.dispatch"])
    assert rest == 6.0
    assert wait + stack + h2d + rest == pytest.approx(100 * trace.idle_share())
    # ... and the breakdown gives each stretch to its innermost span
    gaps = trace.gap_seconds_by_program_span(spans)
    assert {k: round(v * 1e9) for k, v in gaps.items()} == {
        "dl4j.fit.next_batch": 16, "dl4j.fit.stack": 60, "dl4j.fit.put": 4,
        "dl4j.fit.prepare": 4, "dl4j.fit.launch": 4, "dl4j.fit.fetch": 2,
        "dl4j.fit.listeners": 2}


def test_the_per_batch_steps_idle_is_read_under_h2d_and_clipped_to_the_window():
    spans = [ps.ProgramSpan("dl4j.fit.epoch", -50, 250),
             ps.ProgramSpan("dl4j.fit.next_batch", -10, 10),  # half outside
             ps.ProgramSpan("dl4j.fit.step", 10, 60),
             ps.ProgramSpan("dl4j.fit.listeners", 60, 61),
             ps.ProgramSpan("dl4j.fit.step", 100, 120)]
    ops = [tr.Op(40, 110, "%fusion.1 = f32[] fusion()", "mxu")]
    trace = tr.TraceData([tr.DeviceTrace("d", ops=ops)], spans=[],
                         window=(0, 200))
    assert fis.span_share(trace, spans, ["dl4j.fit.next_batch"]) == 5.0
    assert fis.idle_share(trace, spans, ["dl4j.fit.next_batch"]) == 5.0
    # idle inside the steps: 10-40 of the first, 110-120 of the second
    assert fis.idle_share(trace, spans, ["dl4j.fit.put",
                                         "dl4j.fit.step"]) == 20.0
    assert fis.idle_share(trace, spans, ["dl4j.fit.stack"]) == 0.0  # a reading


def test_a_program_without_the_spans_reads_nothing():
    trace, spans = staged_window()
    parent = [s for s in spans if s.name in (
        "dl4j.fit.dispatch", "dl4j.fit.prepare", "dl4j.fit.launch",
        "dl4j.fit.fetch", "dl4j.fit.listeners")]   # what PR 37's fit leaves
    assert fis.span_share(trace, parent, ["dl4j.fit.next_batch"]) is None
    assert fis.idle_share(trace, parent, ["dl4j.fit.stack"]) is None
    # an epoch that closed before the window began is no epoch of the window
    early = parent + [ps.ProgramSpan("dl4j.fit.epoch", -30, -10)]
    assert fis.idle_share(trace, early, ["dl4j.fit.stack"]) is None
    # and an untraced run has no trace to read
    for name in ("iterator_wait_share", "idle_in_next_batch_share",
                 "idle_in_stack_share", "idle_in_h2d_share"):
        assert metric(name).read(Run(trace=None)) is None


def test_the_counter_readers_take_the_windows_deltas():
    counts = {"dl4jtpu_iterator_gets_total{state=ready}": 30.0,
              "dl4jtpu_iterator_gets_total{state=empty}": 10.0,
              "dl4jtpu_fit_host_bytes_total{path=staged}": 3.0e9,
              "dl4jtpu_fit_host_bytes_total{path=per_batch}": 1.0e9,
              "dl4jtpu_iterator_produce_seconds_count": 40.0}
    run = Run(result={"elapsed_s": 2.0, "program": {"fit_iterator": counts}})
    assert metric("prefetch_ready_share").read(run) == 75.0
    assert metric("input_gb_per_s").read(run) == 2.0
    # a program without the counters (the parent), or no get at all
    for result in ({"elapsed_s": 2.0, "program": {"fit_iterator": {}}},
                   {"elapsed_s": 2.0, "program": {}}, {"elapsed_s": 2.0}):
        assert metric("prefetch_ready_share").read(Run(result=result)) is None
        assert metric("input_gb_per_s").read(Run(result=result)) is None
    none_got = {"dl4jtpu_iterator_gets_total{state=ready}": 0.0,
                "dl4jtpu_iterator_gets_total{state=empty}": 0.0}
    assert metric("prefetch_ready_share").read(Run(result={
        "elapsed_s": 1.0, "program": {"fit_iterator": none_got}})) is None


def test_program_counts_are_flat_and_a_delta_counts_a_new_row_from_zero():
    gen = generator()
    before = {"a{state=ready}": 5.0, "h_sum{}": 1.5, "h_count{}": 3}
    after = {"a{state=ready}": 9.0, "a{state=empty}": 2.0, "h_sum{}": 2.0,
             "h_count{}": 7}
    assert gen.counts_delta(after, before) == {
        "a{state=ready}": 4.0, "a{state=empty}": 2.0, "h_sum{}": 0.5,
        "h_count{}": 4}
    # the program's registry, as far as it has the families
    for key in gen.program_counts():
        assert key.split("{")[0].removesuffix("_sum").removesuffix(
            "_count") in gen.COUNTERS


# ------------------------------------------------------------- the manifest
def test_the_manifest_lists_the_new_metrics_for_the_per_batch_cell():
    m = load_json(os.path.join(REPO, "BENCHMARK.json"))
    entries = {e["name"]: e for e in m["per_layer"]}
    in_manifest = [n for n in NEW if n != "idle_in_stack_share"]
    for name in in_manifest:
        e = entries[name]
        assert (e["layer"], e["moves"], e["workloads"]) == (
            LAYER, "train_samples_per_s_per_chip", [PER_BATCH]), name
        assert e["unit"] == ("GB/s" if name == "input_gb_per_s" else "%")
    # appended: what was there keeps its place
    assert [e["name"] for e in m["per_layer"]][-len(in_manifest):] \
        == in_manifest
    assert m["workloads"][-1] == {
        "name": PER_BATCH, "config": "resnet50",
        "traffic": "train_fit_iterator", "chips": 1,
        "why": m["workloads"][-1]["why"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert e2e["train_samples_per_s_per_chip"]["workloads"][-1] == PER_BATCH
    # no dispatch on the per-batch path, and no program text of its step
    for name in ("idle_in_launch_ms", "idle_in_fetch_ms", "dispatch_gap_ms",
                 "scope_attributed_share", "cm_lower_s"):
        assert PER_BATCH not in entries[name]["workloads"]
    # the staged cell and the metric only it reads wait in the fixture
    assert STAGED not in {w["name"] for w in m["workloads"]}
    assert "idle_in_stack_share" not in entries


def test_the_staged_cells_entries_are_addable_as_the_fixture_has_them(
        tmp_path):
    extra = load_json(STAGED_ENTRIES)
    assert [w["name"] for w in extra["workloads"]] == [STAGED]
    assert extra["workloads"][0]["traffic"] == "train_fit_iterator_staged20"
    assert [(e["name"], e["layer"], e["workloads"])
            for e in extra["per_layer"]] == [
        ("idle_in_stack_share", LAYER, [STAGED])]
    m = load_json(manifest_with_the_staged_cell(tmp_path))
    entries = {e["name"]: e for e in m["end_to_end"] + m["per_layer"]}
    assert set(extra["appended_to"]) <= set(entries)
    for name, cells in NEW.items():
        assert entries[name]["workloads"] == cells, name
    for name in ("idle_in_launch_ms", "idle_in_fetch_ms"):  # a dispatch's
        assert entries[name]["workloads"][-1] == STAGED
    assert STAGED not in entries["dispatch_gap_ms"]["workloads"]
    # a pair of configuration and mix appears once
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_the_two_mixes_differ_in_the_stated_window_alone():
    bench = os.path.join(REPO, "benchmarks")
    a = load_json(os.path.join(bench, "traffic", "train_fit_iterator.json"))
    b = load_json(os.path.join(bench, "traffic",
                               "train_fit_iterator_staged20.json"))
    assert a["generator"] == b["generator"] == "iterator_training"
    assert a["params"]["stage_on_device"] == 0    # stated: no TUNED.json decides
    assert b["params"]["stage_on_device"] == 20
    assert {k: v for k, v in a["params"].items() if k != "stage_on_device"} \
        == {k: v for k, v in b["params"].items() if k != "stage_on_device"}
    cells = [load_json(os.path.join(bench, "workloads", c + ".json"))["params"]
             for c in (PER_BATCH, STAGED)]
    assert cells[0] == {"batch_per_chip": 128, "host_pool_batches": 24}
    # a staged cycle is 2.7 s on the v5e, and a traced window of the mix's
    # (ISSUE 38's) 4 s trains one window: 12 until the stack is off the
    # training thread, and the fixture says the cell waits for that
    assert a["params"]["trace_seconds"] == 4
    assert cells[1] == dict(cells[0], trace_seconds=12)
    assert "trace_seconds is ISSUE 38's 4 again" in load_json(
        STAGED_ENTRIES)["what"]


# ------------------------------------------------------------- the iterator
def test_the_pool_is_cycled_by_reference_and_ends_on_a_whole_window():
    gen = generator()
    pool = [object() for _ in range(6)]
    warm = gen.CyclingPool(pool, unit=4, limit=4)
    assert list(warm) == pool[:4] and warm.handed_out == 4
    assert warm.prefetch_supported     # so fit wraps it as it does a user's

    trained = [0]
    it = gen.CyclingPool(pool, unit=4, seconds=100.0, step_s=1.0,
                         trained=lambda: trained[0])
    it.t0 = time.perf_counter()
    walk = iter(it)
    first = [next(walk) for _ in range(8)]
    assert first == pool + pool[:2]                 # round and round, no copy
    # 8 handed out, none trained: 8 in flight + a window more is 12 s of 100
    it.seconds = 11.0
    assert list(walk) == [] and it.handed_out == 8  # ended on a window's edge
    # inside a window it goes on to the edge whatever the clock says
    late = gen.CyclingPool(pool, unit=4, seconds=0.0, step_s=1.0,
                           trained=lambda: 0)
    late.t0 = time.perf_counter() - 5.0
    assert len(list(late)) == 4                     # at least one window
    # per batch (a unit of 1) it ends at any batch
    per_batch = gen.CyclingPool(pool, unit=1, seconds=0.0, step_s=1.0,
                                trained=lambda: 0)
    per_batch.t0 = time.perf_counter()
    assert len(list(per_batch)) == 1
    # when each batch was handed out: a stall shows in an untraced run's log
    assert len(it.times) == it.handed_out == 8
    it.times = [0.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 1.9]   # the ramp: 4
    assert it.pace(top=1) == ("median 100.00 ms between hand-outs; longest "
                              "1300.0 ms before batch 7")   # ordinals from 0
    assert per_batch.pace() == "too few hand-outs for a pace"


def test_a_timed_epoch_hands_out_two_units_however_slow_the_step():
    """``least``: the clock ends no timed epoch before so many batches, so a
    slow host's window still holds two units; past it the clock decides."""
    gen = generator()
    pool = [object() for _ in range(6)]
    for unit, least in ((4, 8), (1, 8), (1, 2)):
        slow = gen.CyclingPool(pool, unit=unit, seconds=0.5, step_s=10.0,
                               trained=lambda: 0, least=least)
        slow.t0 = time.perf_counter() - 5.0          # the time is long past
        assert len(list(slow)) == least == slow.handed_out
    # on a fast host the clock still ends the epoch past the least
    fast = gen.CyclingPool(pool, unit=4, seconds=100.0, step_s=1.0,
                           trained=lambda: 0, least=8)
    fast.t0 = time.perf_counter()
    assert len(list(fast)) == 96      # 96 in flight + a window > 99 s left


# ------------------------------------------------------------ the rehearsal
@pytest.mark.parametrize("name", [PER_BATCH, STAGED])
def test_a_rehearsal_ends_correct_with_every_batch_handed_out_trained(
        name, cell):
    line = rehearse(cell(name), seconds=0.5)
    assert line["correct"] is True
    assert line["compared"]["batches_handed_out_not_trained"] == {
        "value": 0.0, "limit": 0.0}
    assert line["attempted"] >= 4 and line["failed"] == 0
    if name == STAGED:
        assert line["attempted"] % 4 == 0    # whole windows of the preset's 4


@pytest.mark.parametrize("name", [PER_BATCH, STAGED])
def test_a_traced_rehearsal_reads_the_new_metrics(name, cell):
    declared = {m["name"] for m in cell(name).per_layer}
    line = rehearse(cell(name), trace=True, seconds=0.8)
    assert set(line["metrics"]) == declared   # every reader finds its data
    assert line["correct"] is True
    got = line["metrics"]
    for metric_name, cells in NEW.items():
        assert (metric_name in got) == (name in cells), metric_name
    for share in ("iterator_wait_share", "idle_in_next_batch_share",
                  "idle_in_h2d_share", "prefetch_ready_share"):
        assert 0.0 <= got[share]["value"] <= 100.0
    assert got["idle_in_next_batch_share"]["value"] \
        <= got["iterator_wait_share"]["value"] + 1e-9
    assert got["input_gb_per_s"]["value"] > 0
    named = {k for k, _ in line["breakdown"]["idle_gaps"]}
    expect = ({"dl4j.fit.stack", "dl4j.fit.put", "dl4j.fit.fetch"}
              if name == STAGED else {"dl4j.fit.step"})
    assert expect <= named and "dl4j.fit.next_batch" in named


def test_a_program_that_drops_the_last_window_it_was_handed_is_not_correct(
        monkeypatch, cell):
    """The planted fault: ``fit``'s prefetch iterator loses the last four
    batches of the measured epoch (the warm-up's, which says ``limit``, are
    left alone). The iterator counted them as handed out, the net never
    trained them, and nothing else notices: the losses are fine."""
    from deeplearning4j_tpu.datasets import iterators

    whole = iterators.AsyncDataSetIterator.__iter__

    def lossy(self):
        if getattr(self.base, "limit", 0) is not None:
            yield from whole(self)
            return
        held = collections.deque()
        for item in whole(self):
            held.append(item)
            if len(held) > 4:
                yield held.popleft()

    monkeypatch.setattr(iterators.AsyncDataSetIterator, "__iter__", lossy)
    line = rehearse(cell(STAGED), seconds=0.5)
    assert line["correct"] is False
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert over == {"batches_handed_out_not_trained"}
    assert line["compared"]["batches_handed_out_not_trained"]["value"] == 4


def test_a_program_that_trains_none_of_the_window_gives_a_line_not_correct(
        monkeypatch, cell):
    """The planted fault at its extreme: ``fit``'s prefetch iterator walks
    the measured epoch and hands ``fit`` none of it. ``verify`` gives a line
    (no exception on the empty loss list) that says so: every batch handed
    out not trained, the fall read as 1 (none), and every number finite."""
    from deeplearning4j_tpu.datasets import iterators

    whole = iterators.AsyncDataSetIterator.__iter__
    handed_out = []

    def withholds_all(self):
        if getattr(self.base, "limit", 0) is not None:
            yield from whole(self)
            return
        for _ in whole(self):
            pass
        handed_out.append(self.base.handed_out)
        yield from ()

    monkeypatch.setattr(iterators.AsyncDataSetIterator, "__iter__",
                        withholds_all)
    line = rehearse(cell(STAGED), seconds=0.5)
    json.loads(json.dumps(line, allow_nan=False))   # no NaN, no infinity
    assert line["correct"] is False
    assert (line["attempted"], line["failed"]) == (0, 0)
    compared = line["compared"]
    assert compared["batches_handed_out_not_trained"]["value"] \
        == handed_out[0] >= 8                 # two windows of 4 at the least
    over = {k for k, c in compared.items() if c["value"] > c["limit"]}
    assert over == {"batches_handed_out_not_trained",
                    "last_over_first_mean_loss"}
    assert compared["last_over_first_mean_loss"]["value"] == 1.0
