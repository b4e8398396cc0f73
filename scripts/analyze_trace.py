"""Summarize an xplane trace captured by profiler.trace / BENCH_TRACE_DIR.

A trace-backed breakdown of a training step: what fraction of device time
is convolution vs BN-style elementwise vs copies/transposes, and whether any
f32 leaks appear in the hot ops. This reads the .xplane.pb files jax.profiler writes (via
jax.profiler.ProfileData — no TensorBoard needed), buckets device-plane
events by op kind, and prints a ranked table plus bucket totals.

Usage:
  python scripts/analyze_trace.py /tmp/dl4j_tpu_trace [--top 25] [--json OUT]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict

from jax.profiler import ProfileData

# op-name → bucket. Order matters: first match wins.
_BUCKETS = [
    ("conv", re.compile(r"conv", re.I)),
    ("matmul", re.compile(r"dot|gemm|matmul", re.I)),
    ("allreduce", re.compile(r"all-reduce|all-gather|reduce-scatter|collective", re.I)),
    ("copy", re.compile(r"copy|transpose|bitcast|reshape", re.I)),
    ("reduce", re.compile(r"reduce", re.I)),
    ("scatter_gather", re.compile(r"scatter|gather|dynamic-slice|dynamic-update", re.I)),
    ("elementwise", re.compile(
        r"fusion|add|mul|sub|div|max|min|exp|log|tanh|rsqrt|select|compare|convert", re.I)),
    ("infeed_outfeed", re.compile(r"infeed|outfeed|host", re.I)),
]


def bucket_of(name: str) -> str:
    for label, pat in _BUCKETS:
        if pat.search(name):
            return label
    return "other"


def find_xplane_files(trace_dir: str):
    return sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )


def analyze(trace_dir: str):
    files = find_xplane_files(trace_dir)
    if not files:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    op_time = defaultdict(float)      # ns, synchronous op executions
    async_time = defaultdict(float)   # ns, async spans (overlap compute)
    plane_names = []

    def eat(plane) -> None:
        plane_names.append(plane.name)
        # TPU device planes carry several lines: "XLA Ops" holds the real
        # per-op execution windows; "Async XLA Ops" holds copy-start/done
        # style spans that OVERLAP compute (summing them into the op total
        # double-counts and drowns the compute signal — the round-5 trace
        # read 63% "copy" before this split); "Steps"/"XLA Modules" are
        # umbrella events spanning the whole program.
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" in lines:
            for event in lines["XLA Ops"].events:
                # control-flow umbrellas span their whole body; the body ops
                # are separately present on this line
                root = event.name.split(" =")[0]
                if re.match(r"%?(while|conditional|call)\b", root.lstrip("%")):
                    continue
                op_time[event.name] += event.duration_ns
            if "Async XLA Ops" in lines:
                for event in lines["Async XLA Ops"].events:
                    async_time[event.name] += event.duration_ns
            return
        for line in plane.lines:  # CPU fallback plane: flat lines
            for event in line.events:
                # host python trace markers + XLA:CPU executor machinery
                if (event.name.startswith("$")
                        or event.name.startswith("ThunkExecutor")):
                    continue
                op_time[event.name] += event.duration_ns

    datas = [ProfileData.from_file(p) for p in files]
    for data in datas:
        for plane in data.planes:
            # device planes: "/device:TPU:0" or "TPU:0"-style; host
            # python/thread planes are bookkeeping
            if "TPU" in plane.name or "device" in plane.name.lower():
                eat(plane)
    if not op_time:
        # CPU backend traces put XLA ops on the "/host:CPU" plane
        for data in datas:
            for plane in data.planes:
                if plane.name == "/host:CPU":
                    eat(plane)
    if not op_time:
        raise SystemExit(
            f"no device-plane events in {files} (host-only trace?) — "
            "was the trace captured around device execution?"
        )
    total = sum(op_time.values())
    buckets = defaultdict(float)
    for name, t in op_time.items():
        buckets[bucket_of(name)] += t
    f32_suspects = {
        n: t for n, t in op_time.items()
        if re.search(r"f32|float32", n) and not re.search(r"reduce|convert", n)
    }
    async_total = sum(async_time.values())
    async_buckets = defaultdict(float)
    for name, t in async_time.items():
        async_buckets[bucket_of(name)] += t
    return {
        "trace_dir": trace_dir,
        "planes": sorted(set(plane_names)),
        "total_device_ns": total,
        "async_span_ns": async_total,
        "async_buckets_pct_of_op_total": {
            k: round(100.0 * v / total, 2)
            for k, v in sorted(async_buckets.items(), key=lambda kv: -kv[1])
        },
        "buckets_pct": {
            k: round(100.0 * v / total, 2)
            for k, v in sorted(buckets.items(), key=lambda kv: -kv[1])
        },
        "top_ops": [
            {"name": n, "pct": round(100.0 * t / total, 2)}
            for n, t in sorted(op_time.items(), key=lambda kv: -kv[1])
        ],
        "f32_suspects_pct": {
            n: round(100.0 * t / total, 2)
            for n, t in sorted(f32_suspects.items(), key=lambda kv: -kv[1])[:10]
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    report = analyze(args.trace_dir)
    print(f"device planes: {report['planes']}")
    print(f"total device time: {report['total_device_ns'] / 1e6:.2f} ms")
    print("\nbuckets (XLA Ops — synchronous execution windows):")
    for k, pct in report["buckets_pct"].items():
        print(f"  {k:>16}: {pct:6.2f}%")
    if report.get("async_span_ns"):
        print(f"\nasync spans (overlap compute; {report['async_span_ns'] / 1e6:.2f} ms"
              " total, as % of op total):")
        for k, pct in report["async_buckets_pct_of_op_total"].items():
            print(f"  {k:>16}: {pct:6.2f}%")
    print(f"\ntop {args.top} ops:")
    for op in report["top_ops"][: args.top]:
        print(f"  {op['pct']:6.2f}%  {op['name']}")
    if report["f32_suspects_pct"]:
        print("\nf32-named hot ops (possible precision leaks):")
        for n, pct in report["f32_suspects_pct"].items():
            print(f"  {pct:6.2f}%  {n}")
    if args.json:
        trimmed = dict(report, top_ops=report["top_ops"][: args.top])
        with open(args.json, "w") as f:
            json.dump(trimmed, f, indent=2)
        print(f"\nwrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
