"""The general generator of training traffic: seeded batches staged in device
memory, ``fit_on_device`` dispatches back to back for the window.

Parameters (the mix's data file, overridden by the cell's):

- ``batch_per_chip``, ``slots``, ``steps_per_dispatch`` and whatever the
  configuration's ``make_batches`` reads (``seq_len``);
- ``wrapper``: ``"none"`` (the net's own ``fit_on_device``) or
  ``"data_parallel"`` (``ParallelWrapper(net, mesh=make_mesh(chips))``, fed
  host arrays as its ``np.asarray`` expects);
- ``reference_twin_steps``: steps of a twin trained under
  ``kernel_select.forced_mode("reference")`` on the same seed and batches
  (0: no twin);
- tolerances: ``first_loss_rtol`` (against ln(classes)), ``reference_rtol``
  (first loss against the plain reference), ``twin_rtol`` (one number, or one
  per twin step: rounding grows with every update). The mix's file gives
  the measurement behind each.

What a run returns is in ``run``'s docstring; ``verify`` returns what decides
``correct``: each number compared, beside its limit.
"""

from __future__ import annotations

import time

import numpy as np


def setup(ctx) -> dict:
    import jax

    cfg = ctx.cell.config_module()
    p, sizes, chips = ctx.params, ctx.sizes, ctx.cell.chips
    wrapped = p.get("wrapper", "none") == "data_parallel"
    global_batch = int(p["batch_per_chip"]) * (chips if wrapped else 1)
    steps = int(p["steps_per_dispatch"])
    t0 = time.perf_counter()
    net = cfg.build(sizes, ctx.seed)
    xs, ys = cfg.make_batches(sizes, p, ctx.seed, global_batch)
    jax.block_until_ready(xs)
    ctx.log(f"net and {tuple(xs.shape)} staged batches in "
            f"{time.perf_counter() - t0:.2f}s")

    st = {"cfg": cfg, "net": net, "steps": steps, "global_batch": global_batch,
          "samples_per_step": global_batch * cfg.samples_per_example(sizes, p),
          "wrapped": wrapped}
    t0 = time.perf_counter()
    st["reference_loss"] = cfg.reference_loss(net.params, net.state,
                                              xs[0], ys[0], sizes)
    ctx.log(f"plain reference loss {st['reference_loss']:.5f} in "
            f"{time.perf_counter() - t0:.2f}s")

    twin_steps = int(p.get("reference_twin_steps", 0))
    if twin_steps:
        from deeplearning4j_tpu.ops import kernel_select as ks

        t0 = time.perf_counter()
        with ks.forced_mode("reference"):
            twin = cfg.build(sizes, ctx.seed)
            st["twin_losses"] = np.asarray(
                twin.fit_on_device(xs, ys, steps=twin_steps), np.float64)
        del twin
        ctx.log(f"reference-mode twin losses {st['twin_losses']} in "
                f"{time.perf_counter() - t0:.2f}s")

    if wrapped:
        from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

        st["wrapper"] = ParallelWrapper(net, mesh=make_mesh(chips))
        xs, ys = np.asarray(xs), np.asarray(ys)
        fit = st["wrapper"].fit_on_device
    else:
        fit = net.fit_on_device
    st["fit"] = lambda: fit(xs, ys, steps=steps)

    # warm-up: the one shape the window uses, once; its losses are the
    # net's first (the first of them is the loss at the seeded weights)
    t0 = time.perf_counter()
    with ctx.spans.span("warmup"):
        st["first_losses"] = np.asarray(st["fit"](), np.float64)
    ctx.log(f"warm-up dispatch of {steps} steps in "
            f"{time.perf_counter() - t0:.2f}s; losses "
            f"{st['first_losses'][:3]} .. {st['first_losses'][-1]:.5f}")
    return st


def run(ctx, st: dict, seconds: float) -> dict:
    """Dispatch back to back; a dispatch is started only while it is expected
    to end inside ``seconds``, and at least one is made. Throughput is the
    samples of the completed dispatches over the wall time from the first
    dispatch's start to the last one's loss fetch (``fit_on_device`` returns
    host losses, which is the synchronisation)."""
    fit, spans = st["fit"], ctx.spans
    losses, ends = [], []
    t_first = time.perf_counter()
    last = 0.0
    while not ends or (ends[-1] - t_first) + last <= seconds:
        t0 = time.perf_counter()
        with spans.span("dispatch"):
            losses.append(np.asarray(fit(), np.float64))
        ends.append(time.perf_counter())
        last = ends[-1] - t0
    elapsed = ends[-1] - t_first
    steps = sum(len(l) for l in losses)
    all_losses = np.concatenate(losses)
    chips = ctx.cell.chips
    per_chip = steps * st["samples_per_step"] / elapsed / chips
    ctx.log(f"{len(losses)} dispatches, {steps} steps in {elapsed:.4f}s; "
            f"loss {all_losses[0]:.4f} -> {all_losses[-1]:.4f}")
    # the per-layer metric train_step_mfu reads the traced run through the
    # same function; this line is in every run
    from benchmarks.harness.gate import share_of_peak

    flops = st["cfg"].model_flops_per_sample(ctx.sizes)
    peak = ctx.peaks["bf16_flops_per_s"]
    ctx.log(f"model FLOPs utilisation "
            f"{share_of_peak(flops, per_chip, ctx.peaks):.2f}% "
            f"({flops / 1e6:.2f} MFLOP a sample from the shapes, over "
            f"{peak / 1e12:.0f} TFLOP/s a chip)")
    return {
        "end_to_end": {"train_samples_per_s_per_chip": per_chip},
        "attempted": steps,
        "failed": int(np.sum(~np.isfinite(all_losses))),
        "dispatches": len(losses),
        "elapsed_s": elapsed,
        "losses": losses,
        "program": counters(),
    }


# a mean loss that did not fall is a fault: a step that returns its state
# unchanged reads exactly 1 on the same staged batches, so 1 itself is over
BELOW_ONE = float(np.nextafter(1.0, 0.0))


def verify(ctx, st: dict, result: dict) -> dict:
    """Updates happen and do not blow up, and the arithmetic is the
    configuration's: each number compared beside its limit, held as ``value
    <= limit`` by the harness (the module docstring has each tolerance)."""
    p = ctx.params
    first = st["first_losses"]
    last = result["losses"][-1]
    expect = st["cfg"].expected_first_loss(ctx.sizes)
    ref = st["reference_loss"]
    compared = {
        "nonfinite_losses": (
            result["failed"] + int(np.sum(~np.isfinite(first))), 0),
        "first_loss_off_ln_classes": (abs(first[0] - expect) / expect,
                                      float(p["first_loss_rtol"])),
        "first_loss_off_plain_reference": (abs(first[0] - ref) / abs(ref),
                                           float(p["reference_rtol"])),
        "last_over_first_mean_loss": (
            float(np.mean(last)) / float(np.mean(first)), BELOW_ONE),
    }
    ctx.log(f"first loss {first[0]:.5f} (ln classes {expect:.4f}, plain "
            f"reference {ref:.5f}); mean loss first "
            f"dispatch {np.mean(first):.4f}, last {np.mean(last):.4f}")
    if "twin_losses" in st:
        twin = st["twin_losses"]
        rtol = np.broadcast_to(np.asarray(p["twin_rtol"], np.float64),
                               twin.shape)
        off = np.abs(first[:len(twin)] - twin) / np.abs(twin)
        ctx.log(f"reference-mode twin: relative difference per step {off} "
                f"(rtol {rtol})")
        for k, (o, r) in enumerate(zip(off, rtol), start=1):
            compared[f"twin_off_step{k}"] = (float(o), float(r))
    if st["wrapped"]:
        compared.update(_placement(ctx, st))
    return compared


def _placement(ctx, st: dict) -> dict:
    """Parameter shards on every chip, and a replicated leaf equal on all."""
    import jax

    leaves = jax.tree_util.tree_leaves(st["net"].params)
    ids = {s.device.id for leaf in leaves for s in leaf.addressable_shards}
    leaf = max(leaves, key=lambda a: a.size)
    copies = [np.asarray(s.data) for s in leaf.addressable_shards]
    same = all(c.shape == leaf.shape and np.array_equal(c, copies[0])
               for c in copies)
    ctx.log(f"parameter shards on device ids {sorted(ids)}; largest leaf "
            f"{leaf.shape} equal on {len(copies)} devices: {same}")
    return {"chips_without_params": (ctx.cell.chips - len(ids), 0),
            "replicas_that_differ": (
                0 if same and len(copies) == ctx.cell.chips else 1, 0)}


def counters() -> dict:
    """The program's own counters, read after the window."""
    from deeplearning4j_tpu.ops import kernel_select as ks
    from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager

    return {"selection_log": ks.selection_log(),
            "compile_manager": get_compile_manager().stats()}


def close(ctx, st: dict) -> None:
    st.clear()
