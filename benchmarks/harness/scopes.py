"""Which kernel and which layer a device operation belongs to.

What the v5e's trace carries, and what it does not (one look, PR 26): an
"XLA Ops" event is named by its HLO instruction's text and has no stat but
its offset and duration. A Mosaic kernel's ``pallas_call(name=...)`` arrives
as the instruction's own name (``%lstm_seq_bwd.12``), so the event says which
kernel it is. The ``jax.named_scope`` path of a layer does not arrive at all:
it lives in ``metadata={op_name="jit(dl4j_mln_staged)/while/body/
transpose(jvp(layer0))/dot_general"}`` of the compiled program's text, which
the trace leaves out. So the scope is joined in from the program: the
compile manager renders its executables' text
(``CompileManager.program_texts``), and an event finds its instruction there
by its own text (two programs may both have a ``%copy.38``; the text before
``metadata=`` tells them apart).

The join needs the process that compiled the programs. A traced run
therefore leaves ``dl4j_scopes.json`` ({instruction label: op_name}, for the
operations of the window) beside its trace, and

    python3 -m benchmarks.harness.scopes <cell>

prints the window by scope from the two files for an operator: ms a step,
share of busy time, bucket.
"""

from __future__ import annotations

import functools
import json
import os
import re
import sys
from dataclasses import dataclass

from . import main
from . import trace as tr

SCOPES_FILE = "dl4j_scopes.json"
UNSCOPED = "(no scope)"
# name-stack entries that jax itself pushes; whatever else stands between
# the program's ``jit(...)`` and the primitive is a scope of the program
JAX_STRUCTURE = {
    "while", "body", "cond", "checkpoint", "rematted_computation",
    "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "remat", "pjit",
}
_BRANCH = re.compile(r"^branch_\d+_fun$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")  # jvp(x), transpose(jvp(x)), jit(f)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SANITIZED_WRAPPER = re.compile(r"^(?:transpose_|jvp_|vmap_)+")


@functools.lru_cache(maxsize=None)  # a step's ops repeat every step
def kernel_name(event_name: str) -> str | None:
    """The ``name=`` of the ``pallas_call`` behind a Mosaic custom call, from
    its instruction's name: ``%lstm_seq_bwd.12`` is ``lstm_seq_bwd``. Called
    outside any named scope, jax names the instruction for the transform
    too (``%transpose_jvp_lstm_seq_bwd__.1``), which is taken off."""
    if tr.bucket_of(event_name) != "pallas":
        return None
    label = re.sub(r"[.\d]+$", "", tr.op_label(event_name))
    stripped = _SANITIZED_WRAPPER.sub("", label)
    return stripped.rstrip("_") if stripped != label else label


@functools.lru_cache(maxsize=None)
def scope_path(op_name: str) -> tuple:
    """``(scopes, backward)`` of one ``op_name``: the program's own scopes
    in order (``("loss", "layer2")``), jax's wrappers and structure taken
    off, and whether the operation is of the backward pass. XLA joins the
    names of operations it merged with ``;``: the first stands for all."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]  # the last is the primitive
    scopes, backward = [], False
    for part in parts:
        while (m := _WRAPPED.match(part)) is not None:
            if m.group(1) in ("jit", "pjit"):
                part = ""  # a jitted function's name is not a scope
                break
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
        if part and part not in JAX_STRUCTURE and not _BRANCH.match(part) \
                and "<locals>" not in part:  # XLA names some ops it builds
            scopes.append(part)              # itself for a Python function
    return tuple(scopes), backward


def instructions(text: str):
    """``(label, instruction text, op_name)`` of every instruction of one
    program's text; ``op_name`` is "" where the compiler kept none."""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        body = line.strip().removeprefix("ROOT ")
        found = _OP_NAME.search(body)
        yield m.group(1), body, found.group(1) if found else ""


def program_texts() -> list | None:
    """The compiled programs' text from the program's compile manager, or
    None from a program that does not offer it."""
    try:
        from deeplearning4j_tpu.runtime.compile_manager import \
            get_compile_manager

        return get_compile_manager().program_texts()
    except (ImportError, AttributeError):
        return None


@dataclass
class Scopes:
    """``{instruction label: op_name}`` for the operations of one window."""

    op_names: dict

    @classmethod
    def join(cls, trace, texts) -> "Scopes":
        """Find each traced operation's instruction in ``texts``."""
        index: dict = {}
        for text in texts:
            for label, body, op_name in instructions(text):
                index.setdefault(label, []).append((body, op_name))
        op_names = {}
        for dev in trace.devices:
            for op in dev.ops:
                label = tr.op_label(op.name)
                if label in op_names or label not in index:
                    continue
                candidates = index[label]
                exact = [name for body, name in candidates
                         if body.startswith(op.name)]
                op_names[label] = exact[0] if exact else candidates[0][1]
        return cls(op_names)

    @classmethod
    def read(cls, directory: str) -> "Scopes":
        with open(os.path.join(directory, SCOPES_FILE)) as f:
            return cls(json.load(f))

    def write(self, directory: str) -> None:
        with open(os.path.join(directory, SCOPES_FILE), "w") as f:
            json.dump(self.op_names, f)

    def of(self, op) -> tuple:
        """``(kernel name or None, scopes, backward)`` of one device op."""
        scopes, backward = scope_path(
            self.op_names.get(tr.op_label(op.name), ""))
        kernel = kernel_name(op.name)
        if kernel and scopes and scopes[-1] == kernel:
            scopes = scopes[:-1]  # ``name=`` is on the name stack too
        return kernel, scopes, backward


def of_run(run) -> Scopes | None:
    """The window's scopes, joined in this process and left beside the trace
    for the command below. None where the program offers no text."""
    texts = program_texts()
    if run.trace is None or texts is None:
        return None
    scopes = Scopes.join(run.trace, texts)
    scopes.write(run.trace_dir)
    return scopes


def attributed_share(trace, scopes: Scopes) -> float:
    """Share of device-busy time in operations that carry a scope of the
    program or a kernel's name; mean over devices."""
    named = 0.0
    for dev in trace.devices:
        iv = []
        for op in dev.ops:
            kernel, path, _ = scopes.of(op)
            if kernel or path:
                iv.append((op.start, op.end))
        named += tr.total(tr.union(tr.clip(iv, *trace.window)))
    busy = trace.busy_s() * 1e9 * len(trace.devices)
    return named / busy if busy else 0.0


def table(trace, scopes: Scopes, steps: int, top: int = 15) -> list:
    """The window by scope: rows ``(scope, pass, kernel or bucket, ms a
    step, share of busy)``, heaviest first; mean over devices."""
    per: dict = {}
    lo, hi = trace.window
    for dev in trace.devices:
        for op in dev.ops:
            if not lo <= op.start < hi:
                continue
            kernel, path, backward = scopes.of(op)
            key = ("/".join(path) or UNSCOPED, "bwd" if backward else "fwd",
                   kernel or op.bucket)
            per[key] = per.get(key, 0.0) + (op.end - op.start)
    n = len(trace.devices) * 1e9
    busy = trace.busy_s()
    rows = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    return [(*key, 1e3 * ns / n / max(steps, 1), ns / n / busy if busy else 0.0)
            for key, ns in rows]


def cli(argv) -> int:
    from .discovery import BenchmarkError, resolve_cell

    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    try:
        cell = resolve_cell(argv[0])
    except BenchmarkError as e:
        print(f"scopes: {e}", file=sys.stderr)
        return 2
    directory = main.trace_dir(cell)
    try:
        trace = tr.load(directory)
        scopes = Scopes.read(directory)
    except (FileNotFoundError, ValueError) as e:
        print(f"scopes: no traced run of {cell.name} to read ({e}); run "
              f"benchmarks/run.py --workload {cell.name} --trace 1 first",
              file=sys.stderr)
        return 1
    dispatches = sum(1 for s in trace.spans if s.name == "dispatch")
    steps = dispatches * int(cell.params["steps_per_dispatch"])
    print(f"{cell.name}: window {trace.window_s():.3f} s, busy "
          f"{trace.busy_s():.3f} s, {dispatches} dispatches, {steps} steps; "
          f"{100 * attributed_share(trace, scopes):.1f}% of busy time "
          f"carries a scope or a kernel's name")
    print(f"{'scope':<40} {'pass':<4} {'kernel/bucket':<20} "
          f"{'ms/step':>9} {'% busy':>7}")
    for scope, direction, what, ms, share in table(trace, scopes, steps):
        print(f"{scope[:40]:<40} {direction:<4} {what[:20]:<20} "
              f"{ms:>9.4f} {100 * share:>7.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(cli(sys.argv[1:]))
