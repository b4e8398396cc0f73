"""Staged training of a net too large for a twin, held to the plain
reference by its first gradient: ``staged_training``'s window, dispatches and
limits (that file's ``run`` and ``verify`` are used as they are), with one
comparison more, made on the timed net through the timed program.

Before anything else runs, the configuration's ``reference_gradients``
gives the float32 plain reference's loss on the first staged batch and its
gradient for the parameters of ``gradient_vertices``, at the seeded weights.
Then the net takes **one** step through ``fit_on_device`` (the staged
program the window dispatches: the step count is a device scalar, so this is
no other executable). From a fresh Adam state the first moment after one step
is ``(1 - beta1) * g``, so the gradient the program computed, in float32 as
the updater got it, is read from the net's own optimizer state. Compared:
for every sampled parameter ``|g - g_ref| / |g_ref|`` (L2), the worst of them
under ``gradient_rtol``. A parameter whose reference gradient is zero (a
selection bias) is left out; a step that leaves its state unchanged reads 1.
The warm-up dispatch follows, so the net's first losses are the single
step's and the warm-up's.

Parameters beyond ``staged_training``'s: ``gradient_vertices`` (the layers
whose parameters are sampled), ``gradient_rtol``. No twin and no wrapper:
``reference_twin_steps`` 0 and ``wrapper`` ``none`` are all this takes.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.generators import staged_training as base

run, close, counters = base.run, base.close, base.counters


def first_moments(net):
    """``(mu, beta1)`` of the net's Adam state."""
    import jax
    import optax

    adam = [s for s in jax.tree_util.tree_leaves(
        net.opt_state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    if len(adam) != 1:
        raise ValueError("the first gradient is read from Adam's first "
                         f"moment: the net has {len(adam)} Adam states")
    return adam[0].mu, float(net.conf.updater.beta1)


def gradient_distances(mu, beta1: float, reference: dict) -> dict:
    """``{"vertex/parameter": |g - g_ref| / |g_ref|}`` with ``g = mu / (1 -
    beta1)``, for every parameter whose reference gradient is not zero."""
    out = {}
    for vertex, grads in reference.items():
        for name, g_ref in grads.items():
            g_ref = np.asarray(g_ref, np.float64)
            norm = float(np.linalg.norm(g_ref))
            if norm == 0.0:
                continue
            g = np.asarray(mu[vertex][name], np.float64) / (1.0 - beta1)
            out[f"{vertex}/{name}"] = float(np.linalg.norm(g - g_ref)) / norm
    return out


def setup(ctx) -> dict:
    import jax

    p, sizes = ctx.params, ctx.sizes
    if int(p.get("reference_twin_steps", 0)) or \
            p.get("wrapper", "none") != "none":
        raise ValueError("staged_training_gradcheck runs one net on its own "
                         "fit_on_device: no twin, no wrapper")
    cfg = ctx.cell.config_module()
    batch, steps = int(p["batch_per_chip"]), int(p["steps_per_dispatch"])
    t0 = time.perf_counter()
    net = cfg.build(sizes, ctx.seed)
    xs, ys = cfg.make_batches(sizes, p, ctx.seed, batch)
    jax.block_until_ready(xs)
    ctx.log(f"net and {tuple(xs.shape)} staged batches in "
            f"{time.perf_counter() - t0:.2f}s")
    st = {"cfg": cfg, "net": net, "steps": steps, "global_batch": batch,
          "samples_per_step": batch * cfg.samples_per_example(sizes, p),
          "wrapped": False}

    t0 = time.perf_counter()
    st["reference_loss"], reference = cfg.reference_gradients(
        net.params, xs[0], ys[0], sizes, list(p["gradient_vertices"]))
    reference = jax.tree_util.tree_map(np.asarray, reference)  # off the chip
    ctx.log(f"plain reference loss {st['reference_loss']:.5f} and its "
            f"gradient for {len(reference)} layers in "
            f"{time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    with ctx.spans.span("first_step"):
        first = np.asarray(net.fit_on_device(xs, ys, steps=1), np.float64)
    mu, beta1 = first_moments(net)
    st["gradient_distances"] = gradient_distances(mu, beta1, reference)
    del reference, mu
    ctx.log(f"first step (compiles the staged program) in "
            f"{time.perf_counter() - t0:.2f}s; loss {first[0]:.5f}; first "
            f"gradient off the plain reference: " + ", ".join(
                f"{k} {v:.3g}" for k, v in st["gradient_distances"].items()))

    st["fit"] = lambda: net.fit_on_device(xs, ys, steps=steps)
    t0 = time.perf_counter()
    with ctx.spans.span("warmup"):
        warm = np.asarray(st["fit"](), np.float64)
    st["first_losses"] = np.concatenate([first, warm])
    ctx.log(f"warm-up dispatch of {steps} steps in "
            f"{time.perf_counter() - t0:.2f}s; losses {warm}")
    return st


def verify(ctx, st: dict, result: dict) -> dict:
    compared = base.verify(ctx, st, result)
    distances = st["gradient_distances"]
    worst = max(distances, key=distances.get) if distances else None
    ctx.log(f"first gradient off the plain reference: worst {worst}")
    compared["first_gradient_off_plain_reference"] = (
        distances[worst] if worst else float("nan"),
        float(ctx.params["gradient_rtol"]))
    return compared
