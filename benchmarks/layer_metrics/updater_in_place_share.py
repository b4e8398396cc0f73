"""Share of the fused Adam kernel's results that it writes where the operand
they replace lies, by bytes, in percent, in the compiled programs of this
run: over every ``adam_update`` Mosaic custom call, the bytes of the results
that ``output_to_operand_aliasing`` ties to an operand (the new moments on
the old, the update on the gradient) over the bytes of all its results. A result that is tied needs no buffer of its own,
and a loop that carries it gets it back in the buffer it came in: XLA adds no
copy of it into the carry (5.31 GB a step of ``nemotron3_nano_train_1chip``
before PR 33). The kernel ties the results of a leaf it tiles as it lies and
leaves a flattened leaf's untied. 0.0 where no result is tied (the char-RNN:
every leaf flattened) and where the programs hold no such call (the CPU, a
mesh, an updater other than Adam: optax ran); nothing
from a program that does not offer its text. Source: the compiled programs'
text, ``CompileManager.program_texts()`` through ``harness/scopes.py``."""

import re

from benchmarks.harness import scopes

KERNEL = "adam_update"
ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2}
_RESULT = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_TIED = re.compile(r"output_to_operand_aliasing=\{(.*?\))\}")
_TIED_RESULT = re.compile(r"\{(\d*)\}:")


def result_bytes(body: str) -> list:
    """Bytes of each result of one instruction, from its text: what stands
    between ``=`` and the opcode is one shape or a tuple of them."""
    shapes = body.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    sizes = []
    for dtype, dims in _RESULT.findall(shapes):
        n = ITEMSIZE[dtype]
        for d in filter(None, dims.split(",")):
            n *= int(d)
        sizes.append(n)
    return sizes


def tied_and_all_bytes(texts) -> tuple:
    """``(bytes of the tied results, bytes of all results)`` over every
    ``adam_update`` call of the programs' texts."""
    tied = total = 0
    for text in texts:
        for label, body, _ in scopes.instructions(text):
            if not label.startswith(KERNEL) or \
                    'custom_call_target="tpu_custom_call"' not in body:
                continue
            sizes = result_bytes(body)
            total += sum(sizes)
            found = _TIED.search(body)
            if found:
                # ``{1}: (1, {})``: result 1 of the tuple; ``{}`` the only one
                tied += sum(sizes[int(i or 0)]
                            for i in _TIED_RESULT.findall(found.group(1)))
    return tied, total


def read(run):
    texts = scopes.program_texts()
    if texts is None:
        return None
    tied, total = tied_and_all_bytes(texts)
    return 100.0 * tied / total if total else 0.0
