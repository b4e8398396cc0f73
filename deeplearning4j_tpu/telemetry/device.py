"""Device-side step accumulators: the jnp vector carried out of the jit step.

The instrumentation contract that keeps telemetry off the dispatch critical
path: everything per-step is computed INSIDE the jitted program as a tiny
``[NUM_SLOTS]`` float32 vector (loss, global grad norm, non-finite flag) and
returned alongside the step outputs. The host appends these device scalars to
a buffer without reading them — fetching (the only host sync) happens once
every K steps in :class:`telemetry.session.Telemetry`, or once per staged
``fit_on_device`` dispatch where the scan stacks them to ``[steps, NUM_SLOTS]``.

``step_stats`` is pure jnp and works both traced (inside ``jax.jit``) and
eager (on the grad-stats path, where the step already returns gradients) —
eager jnp ops dispatch async and still never block the host.
"""

from __future__ import annotations

# Slot layout of the per-step metrics vector.
LOSS = 0
GRAD_NORM = 1
NONFINITE = 2
NUM_SLOTS = 3

# Test seam: a callable invoked at TRACE time from inside step_stats. Because
# Python in a traced body runs only while XLA traces it, counting calls here
# counts compilations — the "counting tracer" the telemetry tests use to
# prove the instrumented step compiles once, not per iteration.
_TRACE_HOOK = None


def step_stats(loss, grads=None):
    """Build the per-step metrics vector (float32 ``[NUM_SLOTS]``).

    ``loss``: scalar. ``grads``: gradient pytree (or None when the step has
    no gradient view — grad norm reports 0). The non-finite flag is 1.0 when
    the loss or any gradient leaf contains NaN/Inf.
    """
    import jax
    import jax.numpy as jnp

    if _TRACE_HOOK is not None:
        _TRACE_HOOK()
    loss32 = jnp.asarray(loss, jnp.float32)
    finite = jnp.isfinite(loss32)
    if grads is not None:
        leaves = [l for l in jax.tree_util.tree_leaves(grads)
                  if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)]
        if leaves:
            sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
            gnorm = jnp.sqrt(sq)
            finite = jnp.logical_and(finite, jnp.isfinite(gnorm))
        else:
            gnorm = jnp.zeros((), jnp.float32)
    else:
        gnorm = jnp.zeros((), jnp.float32)
    nonfinite = 1.0 - finite.astype(jnp.float32)
    return jnp.stack([loss32, gnorm, nonfinite])


# --------------------------------------------------------------------------
# Layer counters: what a layer counts on the device inside the step.
#
# A layer that counts (the dropless expert layer: rows that landed on the
# experts it holds) keeps an int32 vector under ``state["counters"]``, named
# entry by entry by its class's ``COUNTERS``, and adds to it every step. The
# staged program zeroes the vectors when a dispatch begins, so after it they
# hold the dispatch's sums; ``fit_on_device`` fetches them beside the losses
# (inside ``dl4j.fit.fetch``: the program has ended, nothing waits) and adds
# them to ``dl4jtpu_layer_counter_total{layer,counter}`` of the default
# registry. No step reads them back, and the per-step ``fit`` path leaves
# them in ``net.state`` unpublished.
LAYER_COUNTERS = "counters"
LAYER_COUNTER_FAMILY = "dl4jtpu_layer_counter_total"


def zero_layer_counters(layer_state):
    """``layer_state`` (one layer's state dict) with its counters at zero."""
    if not isinstance(layer_state, dict) or LAYER_COUNTERS not in layer_state:
        return layer_state
    import jax.numpy as jnp

    return {**layer_state, LAYER_COUNTERS:
            jnp.zeros_like(layer_state[LAYER_COUNTERS])}


def publish_layer_counters(named_layers) -> None:
    """Fetch the counters of ``(name, layer, layer_state)`` triples and add
    them to the default registry; a layer without ``COUNTERS`` is passed
    over."""
    import numpy as np

    family = None
    for name, layer, layer_state in named_layers:
        names = getattr(layer, "COUNTERS", None)
        if not names or not isinstance(layer_state, dict) \
                or LAYER_COUNTERS not in layer_state:
            continue
        if family is None:
            from . import get_registry

            family = get_registry().counter(
                LAYER_COUNTER_FAMILY,
                "what layers count on the device, summed per dispatch",
                labelnames=("layer", "counter"))
        values = np.asarray(layer_state[LAYER_COUNTERS])
        for counter, value in zip(names, values):
            family.labels(layer=name, counter=counter).inc(float(value))
