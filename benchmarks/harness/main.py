"""One run of one cell: set-up, the measured window, the final line."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from . import gate
from .discovery import BenchmarkError, Cell, resolve_cell
from .spans import WINDOW, Spans

DEFAULT_TRACE_SECONDS = 3.0


def seconds_since_process_start(t0: float) -> float:
    """Interpreter start-up that ran before ``run.py`` took ``t0``, from the
    kernel's own record of when the process began (0 where /proc is absent)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        before = uptime - start_ticks / os.sysconf("SC_CLK_TCK") \
            - (time.perf_counter() - t0)
        return max(0.0, before)
    except (OSError, ValueError, IndexError):
        return 0.0


@dataclass
class Context:
    """What a generator gets: the cell, the seed, the devices, the spans."""

    cell: Cell
    seed: int
    devices: list
    trace: bool
    peaks: dict             # the device's row of harness/peaks.json
    spans: Spans = field(default_factory=Spans)

    @property
    def params(self) -> dict:
        return self.cell.params

    @property
    def sizes(self) -> dict:
        return self.cell.sizes

    def log(self, *parts) -> None:
        """An earlier line of stdout (never the last one)."""
        print("#", *parts, flush=True)


@dataclass
class Run:
    """What a per-layer metric's reader gets."""

    cell: Cell
    result: dict            # the generator's: end_to_end, counters, samples
    trace: object | None    # harness.trace.TraceData of the traced window
    setup_compile: dict     # Monitors delta over set-up
    window_compile: dict    # Monitors delta over the measured window
    memory_peak_bytes: int  # see run_cell
    spans: Spans
    trace_dir: str          # where the traced window's files lie
    peaks: dict             # the device's row of harness/peaks.json


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def final_line(*, correct, attempted, failed, metrics, device, compared,
               breakdown=None):
    """The contract's keys; ``compared`` (each number ``correct`` was decided
    from, beside its limit) is the harness's own key and comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {name: {"value": float(value), "limit": float(limit)}
                        for name, (value, limit) in compared.items()}
    return json.dumps(line)


def metric_values(entries, values: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the entries that have a value."""
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in entries if values.get(m["name"]) is not None}


def trace_dir(cell: Cell, root: str | None = None) -> str:
    """Where a traced run of ``cell`` leaves its files: under ``root``, or
    under the checkout's ``.bench_out/trace`` (the command's own runs, which
    ``python3 -m benchmarks.harness.scopes <cell>`` reads afterwards)."""
    root = root or os.path.join(gate.repo_root(), ".bench_out", "trace")
    return os.path.join(root, cell.name)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             t0: float, lead: float = 0.0, peaks: dict | None = None,
             cpu_rehearsal: bool = False,
             trace_root: str | None = None) -> str:
    """Set-up, window, checks and metrics of one cell on ``devices``; returns
    the final line. ``main`` gates on the TPU first; the tests call this at
    a tiny preset on the CPU, with ``cpu_rehearsal`` (and their own
    ``peaks``), which alone lets a trace without a device plane be read.

    A traced run empties ``trace_dir(cell, trace_root)`` and writes there.
    Two runs that trace into one directory at once delete each other's
    files, so every caller but the command itself hands in a ``trace_root``
    of its own (the tests: a fresh temporary directory per call).

    ``memory_peak_bytes`` is the larger of what the fullest chip held when
    the window began and when it ended, the cell's arrays still alive (see
    ``gate.memory_held_bytes``). What comes and goes inside a dispatch
    between the two readings is not in it."""
    monitors = gate.monitors()
    at_start = monitors.snapshot()
    info = gate.device_info(devices)
    peaks = peaks or gate.peaks_row(info["kind"])  # unknown kind: an error
    ctx = Context(cell=cell, seed=seed, devices=devices, trace=trace,
                  peaks=peaks)
    ctx.log(f"cell {cell.name} seed {seed} device {info}")

    gen = cell.generator_module()
    state = gen.setup(ctx)
    try:
        setup_compile = gate.delta(monitors.snapshot(), at_start)
        if trace:
            from . import trace as tr

            seconds = min(seconds, float(
                cell.params.get("trace_seconds", DEFAULT_TRACE_SECONDS)))
            tracing = trace_dir(cell, trace_root)
            shutil.rmtree(tracing, ignore_errors=True)
            tr.start(tracing)
        held = [gate.memory_held_bytes(devices)]
        setup_s = lead + time.perf_counter() - t0
        t_w0 = time.perf_counter()
        try:
            with ctx.spans.span(WINDOW):
                result = gen.run(ctx, state, seconds)
            window_s = time.perf_counter() - t_w0
        finally:
            if trace:
                tr.stop()
        held.append(gate.memory_held_bytes(devices))
        ctx.log(f"memory_stats of device 0 after the window: "
                f"{devices[0].memory_stats()}")
        window_compile = gate.delta(monitors.snapshot(), at_start)
        window_compile = gate.delta(window_compile, setup_compile)
        trace_data = tr.load(tracing, max_devices=len(devices),
                             allow_cpu_backend=cpu_rehearsal) if trace else None
        compared = gen.verify(ctx, state, result)
    finally:
        gen.close(ctx, state)

    # what decides ``correct``: every number the generator compared and the
    # window's compiles, each held as value <= limit (a NaN holds nothing)
    compared["compiles_in_window"] = (window_compile["backend_compiles"], 0)
    over = [name for name, (value, limit) in compared.items()
            if not value <= limit]
    mem = max(held)
    ctx.log(f"held on the fullest chip: {held[0]} bytes before the window, "
            f"{held[1]} after")
    device = dict(info, memory_peak_bytes=mem)
    ctx.log(f"setup_s {setup_s:.3f} window_s {window_s:.3f} compile in set-up "
            f"{setup_compile} in window {window_compile}")

    breakdown = None
    if trace:
        from . import program_spans

        run = Run(cell=cell, result=result, trace=trace_data,
                  setup_compile=setup_compile, window_compile=window_compile,
                  memory_peak_bytes=mem, spans=ctx.spans, trace_dir=tracing,
                  peaks=peaks)
        values = {}
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"])(run)
            if value is not None:
                values[m["name"]] = value
        metrics = metric_values(cell.per_layer, values)
        device["busy_s"] = trace_data.busy_s()
        device["window_s"] = trace_data.window_s()
        breakdown = trace_data.breakdown(
            program_spans=program_spans.of_run(run))
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        metrics = metric_values(cell.end_to_end, values)
    # the last lines on standard error: what was compared, beside its limit
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value:.6g} limit {limit:.6g}", file=sys.stderr)
    print(f"correct {not over}" + (f": FAILED {over}" if over else ""),
          file=sys.stderr, flush=True)
    return final_line(correct=not over,
                      attempted=result["attempted"], failed=result["failed"],
                      metrics=metrics, device=device, breakdown=breakdown,
                      compared=compared)


def main(argv, t0: float) -> int:
    args = parse_args(argv)
    lead = seconds_since_process_start(t0)
    if not os.path.isdir(os.path.join(gate.repo_root(), "deeplearning4j_tpu")):
        print("benchmark: the program (deeplearning4j_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 5
    try:
        cell = resolve_cell(args.workload)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    t_a = time.perf_counter()
    cache_dir = gate.place_compile_cache()   # imports jax
    t_b = time.perf_counter()
    devices = gate.require_tpu(cell.chips)   # attaches the chip(s)
    print(f"# compile cache {cache_dir}; start-up: interpreter and harness "
          f"{lead + t_a - t0:.2f}s, import jax {t_b - t_a:.2f}s, attach "
          f"devices {time.perf_counter() - t_b:.2f}s", flush=True)
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices, t0=t0, lead=lead)
    print(line, flush=True)
    return 0
