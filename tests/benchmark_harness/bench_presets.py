"""Tiny presets for rehearsing the benchmark's cells on the CPU. They live in
the tests only: a cell is never measured at these sizes."""

import copy
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness.discovery import load_json, resolve_cell  # noqa: E402
from benchmarks.harness.main import run_cell  # noqa: E402

SERVING_ENTRIES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "fixtures", "serving_cell_entries.json")

FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
              "hbm_bytes": 1e9, "source": "tests only"}
TINY_SIZES = {
    "resnet50": dict(blocks=[1, 1], stage_widths=[8, 16], stem_channels=8,
                     image_size=32, classes=10),
    "charrnn_2x512": dict(rnn_size=16, vocab_size=12, classes=12),
}
TINY_PARAMS = {
    "resnet50_train_1chip": dict(batch_per_chip=4, steps_per_dispatch=4,
                                 trace_seconds=1),
    "resnet50_train_dp4": dict(batch_per_chip=2, steps_per_dispatch=4,
                               trace_seconds=1),
    "charrnn_train_1chip": dict(batch_per_chip=4, seq_len=8, slots=4,
                                steps_per_dispatch=4, trace_seconds=1),
    "charrnn_decode_c8": dict(
        session_tokens={"median": 8, "sigma": 0.5, "min": 2, "max": 16,
                        "strata": 64},
        # outputs near 1/12, where one bfloat16 step is 4.9e-4 (the mix's
        # tolerance is sized for 1/96)
        replay_reference_atol=2e-3,
        warmup_seconds=0.2, trace_seconds=1),
}


def manifest_with_serving_cell(tmp_dir) -> str:
    """A copy of ``BENCHMARK.json`` in ``tmp_dir`` with the decode cell's
    entries added, as a later PR would add them (the benchmark itself has no
    serving cell: ``fixtures/serving_cell_entries.json`` says why). The
    benchmark's files are reached through a link beside it."""
    m = load_json(os.path.join(REPO, "BENCHMARK.json"))
    extra = load_json(SERVING_ENTRIES)
    for section in ("workloads", "end_to_end", "per_layer"):
        m[section] = m[section] + extra[section]
    link = os.path.join(tmp_dir, "benchmarks")
    if not os.path.exists(link):
        os.symlink(os.path.join(REPO, "benchmarks"), link)
    path = os.path.join(tmp_dir, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def tiny_cell(name, **kw):
    cell = copy.deepcopy(resolve_cell(name, **kw))
    cell.sizes.update(TINY_SIZES[cell.config])
    cell.params.update(TINY_PARAMS.get(name, {}))
    return cell


def rehearse(cell, *, trace=False, seconds=0.5, seed=5, cpu_rehearsal=True):
    """The cell's whole run on the CPU backend's first ``chips`` devices;
    returns the parsed final line."""
    import jax

    line = run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                    devices=jax.devices()[:cell.chips],
                    t0=time.perf_counter(), peaks=FAKE_PEAKS,
                    cpu_rehearsal=cpu_rehearsal)
    return json.loads(line)
