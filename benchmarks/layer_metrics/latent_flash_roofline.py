"""The flash attention kernels of a latent attention layer against their
roofline: the sum over the traced ``flash_*`` events (``flash_fwd``,
``flash_bwd_dq``, ``flash_bwd_dkv``) of the least time each could take, over
the sum of their traced durations. Nothing where no such event is in the
window (the CPU takes the XLA path: a share of a roofline is never 0) or
where the run's ``attention`` selections are not of a call whose score and
value products differ. Source: device trace; ``causal``, ``d_qk``, ``d_v``
and the shared rotary key are the run's own ``attention`` selection's.

One event is one attention layer's whole batch: ``BH`` heads held, ``T``
positions. The scores run over ``d_qk`` (the per-head part and the rotary
part together: 128 + 64) and the values over ``d_v`` (128). Operations are
those of the mathematics, whatever the kernel pads or splits: a product over
the score matrix is ``2 * BH * d * n`` with ``n = T (T + 1) / 2`` entries
under ``causal`` and ``T^2`` otherwise;

- ``flash_fwd``: one product over ``d_qk`` (``q k^T``), one over ``d_v``
  (``p v``). It reads ``q``, the per-head keys, the rotary key and ``v`` and
  writes ``o`` and the float32 ``lse``.
- ``flash_bwd_dq``: two over ``d_qk`` (``q k^T``, ``ds k``), one over
  ``d_v`` (``do v^T``). It reads what ``flash_fwd`` reads and ``do``, ``lse``,
  ``delta``, and writes ``dq``.
- ``flash_bwd_dkv``: two over ``d_qk`` (``q k^T``, ``ds^T q``), two over
  ``d_v`` (``do v^T``, ``p^T do``). It reads what ``flash_bwd_dq`` reads and
  writes ``dk`` and ``dv``.

The rotary key, and its gradient, count once for all heads. The least time of
an event is the larger of its FLOPs over the chip's bf16 peak and its bytes
over the HBM peak (``run.peaks``), so no real duration reads over 100.
"""

from benchmarks.layer_metrics.flash_attention_roofline import (ITEMSIZE,
                                                               kernel_ops)

# products over (d_qk, d_v)
PRODUCTS = {"flash_fwd": (1, 1), "flash_bwd_dq": (2, 1),
            "flash_bwd_dkv": (2, 2)}


def flops_and_bytes(kernel: str, B, T, H, d_qk, d_rope, d_v, itemsize,
                    causal):
    """Operations and bytes one call of ``kernel`` needs, from its shapes."""
    entries = T * (T + 1) / 2 if causal else T * T
    over_qk, over_v = PRODUCTS[kernel]
    flops = 2.0 * B * H * entries * (over_qk * d_qk + over_v * d_v)
    head = B * H * T * itemsize            # one lane of a per-head array
    q, k_nope, v = head * d_qk, head * (d_qk - d_rope), head * d_v
    k_rope = B * T * d_rope * itemsize     # once for all heads
    rows = B * H * T * 4                   # lse, delta: float32
    if kernel == "flash_fwd":
        moved = q + k_nope + k_rope + v + v + rows               # .. o, lse
    elif kernel == "flash_bwd_dq":
        moved = q + k_nope + k_rope + v + v + 2 * rows + q       # .. do; dq
    else:
        moved = (q + k_nope + k_rope + v + v + 2 * rows
                 + k_nope + k_rope + v)                          # dk, dv
    return float(flops), float(moved)


def least_seconds(kernel, shapes, peaks) -> float:
    flops, moved = flops_and_bytes(kernel, *shapes)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def share(trace, shapes, peaks):
    """Least over traced seconds of the window's ``flash_*`` events; ``None``
    where the window has none."""
    lo, hi = trace.window
    least = traced = 0.0
    for dev in trace.devices:
        for op, kernel in kernel_ops(dev):
            if lo <= op.start < hi and kernel in PRODUCTS:
                least += least_seconds(kernel, shapes, peaks)
                traced += (op.end - op.start) / 1e9
    return least / traced if traced else None


def latent_call(run):
    """``(d_qk, d_rope, d_v, causal)`` of the run's ``flash`` selections
    whose products differ in size; ``None`` where the run made none, or they
    differ among themselves (one set of shapes would not describe them)."""
    log = run.result.get("program", {}).get("selection_log") or []
    said = set()
    for rec in log:
        ctx = rec.get("ctx", {})
        if rec["site"] == "attention" and rec["variant"] == "flash" \
                and rec.get("mode") != "reference" and "d_qk" in ctx:
            said.add((int(ctx["d_qk"]), int(ctx.get("d_rope", 0)),
                      int(ctx["d_v"]), bool(ctx.get("causal"))))
    return said.pop() if len(said) == 1 else None


def read(run):
    if run.trace is None:
        return None
    call = latent_call(run)
    if call is None:
        return None
    d_qk, d_rope, d_v, causal = call
    p, s = run.cell.params, run.cell.sizes
    shapes = (int(p["batch_per_chip"]), int(p["seq_len"]),
              int(s["num_attention_heads"]), d_qk, d_rope, d_v,
              ITEMSIZE[s["dtype"]], causal)
    got = share(run.trace, shapes, run.peaks)
    return None if got is None else 100.0 * got
