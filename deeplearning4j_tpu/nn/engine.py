"""The training engine under both net classes.

``MultiLayerNetwork`` and ``ComputationGraph`` inherit :class:`TrainingEngine`:
the jitted train step, the on-device multi-step loop, TBPTT, the ``fit``
loops and the compile-manager plumbing exist once, here. A front-end keeps
its forward pass, its ``_loss``, its parameter tree and a handful of hooks
that say how ITS batches look (one array for the sequential class; lists of
arrays and masks by input name for the graph). A staged batch is a pytree:
the engine indexes, checks and places whatever the front-end staged with
``jax.tree_util`` and never asks which class is calling.

- The reference's Solver/ConvexOptimizer/StepFunction tier (optimize/solvers/*)
  collapses into ONE pure jitted ``train_step``: value_and_grad → optax update →
  apply_updates (:func:`apply_step`). XLA traces it once and fuses the whole
  step (forward, backward, updater) into a single device program — the per-op
  dispatch boundary that dominated the reference's hot loop does not exist.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..telemetry import device as _tdev
from ..telemetry.registry import get_registry
from ..telemetry.spans import identified, span
from .updaters import (optimizer_update, scaled_loss, unscale_grads,
                       unscale_loss)


def apply_step(loss_of, tx, loss_scale, params, opt_state):
    """One optimizer step's arithmetic, the only copy: scale the loss,
    ``value_and_grad``, unscale loss and gradients, the updater under the
    scope ``optimizer_update`` (what ``scope_attributed_share`` reads the
    updater's operations by). ``loss_of(params) -> (loss, aux)``. Returns
    ``(loss, aux, grads, updates, new_opt_state, new_params)``."""

    def scaled(p):
        loss, aux = loss_of(p)
        return scaled_loss(loss, loss_scale), aux

    (loss, aux), grads = jax.value_and_grad(scaled, has_aux=True)(params)
    loss = unscale_loss(loss, loss_scale)
    grads = unscale_grads(grads, loss_scale)
    with jax.named_scope("optimizer_update"):
        updates, new_opt, new_params = optimizer_update(
            tx, grads, opt_state, params)
    return loss, aux, grads, updates, new_opt, new_params


def adam_kernel_gives_way_beside(layers) -> Optional[str]:
    """What among ``layers`` the in-place Adam kernel gives way beside, or
    None: ``"kda_recurrence"`` for a net with a Kimi Delta Attention layer
    and a dropless expert layer, whose staged program with that kernel did
    not return from its first step on the v5e while the recurrence ran in
    jax.numpy, the one form it had then (``ops.kernel_select``'s
    ``optimizer`` site; ``PERF.md`` section 7 (c)). With the recurrence's own
    kernels (``ops/kda.py``, PR 37) the gate stands as it stood: letting the
    Adam kernel back in is a change, and a pair of runs, of its own. Read
    from the net's own layers when it builds its updater, so it holds however
    and how often a step is traced."""
    from .layers.linear_attention import KimiDeltaAttentionLayer  # noqa: PLC0415
    from .layers.moe import DroplessExpertsLayer  # noqa: PLC0415

    kinds = {type(layer) for layer in layers}
    if {KimiDeltaAttentionLayer, DroplessExpertsLayer} <= kinds:
        return "kda_recurrence"
    return None


def _staged_dim0(arr) -> int:
    """Leading (staged-batch) dim of an array or ShapeDtypeStruct."""
    shape = getattr(arr, "shape", None)
    if shape is None:
        shape = np.shape(arr)
    return int(shape[0])


def _check_staged_counts(num_batches: int, named_arrays) -> None:
    """Shared fit_on_device guard: dynamic_index_in_dim CLAMPS out-of-range
    indices, so a staged-batch-count mismatch would silently train features i
    against labels min(i, K-1) — refuse loudly instead."""
    for name, arr in named_arrays:
        if arr is not None and _staged_dim0(arr) != num_batches:
            raise ValueError(
                f"{name} stages {_staged_dim0(arr)} batches, "
                f"expected {num_batches}"
            )


def _named_slots(xs, ys, features_masks, labels_masks):
    """``(name, array)`` of everything staged, named as the caller passed it:
    a bare array by its argument, a list's entries by kind and position."""
    for tree, arg, kind in ((xs, "xs", "input array"),
                            (ys, "ys", "label array"),
                            (features_masks, "features_masks", "features mask"),
                            (labels_masks, "labels_masks", "labels mask")):
        if isinstance(tree, (list, tuple)):
            yield from ((f"{kind} {i}", a) for i, a in enumerate(tree))
        else:
            yield arg, tree


def _shell(a):
    """Shape/dtype shell of an array (warmup needs no data)."""
    if a is None or isinstance(a, jax.ShapeDtypeStruct):
        return a
    a = np.asarray(a) if not hasattr(a, "dtype") else a
    return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)


def _host_bytes(tree) -> int:
    """Bytes of the host (numpy) arrays in ``tree``: what a jitted call
    handed them has to bring to the device before it can run."""
    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree)
               if isinstance(a, np.ndarray))


class _BatchWaits:
    """One epoch's iterator as ``fit`` consumes it: every wait for the next
    item is a ``dl4j.fit.next_batch`` span on the consuming thread (for an
    :class:`AsyncDataSetIterator` the wait on its queue; for a plain
    iterator the production itself), the end of the stream included.
    ``batch`` is the ordinal of the item waited for; ``window`` the staged
    window it will fill, which the staged loop moves on (None on the
    per-batch path)."""

    def __init__(self, it):
        self._it = iter(it)
        self.batches = 0
        self.window: Optional[int] = None

    def __iter__(self):
        return self

    def __next__(self):
        ids = {"batch": self.batches}
        if self.window is not None:
            ids["window"] = self.window
        with identified(**ids), span("dl4j.fit.next_batch"):
            item = next(self._it)
        self.batches += 1
        return item


def _zero_counters(state):
    """``state`` (a tuple by layer or a dict by vertex) with every counting
    layer's counters at zero, entries in their own order; the state itself,
    operation for operation, where no layer counts."""
    zero = _tdev.zero_layer_counters
    if isinstance(state, dict):
        return {n: zero(s) for n, s in state.items()}
    return tuple(zero(s) for s in state)


def _donate(*argnums):
    from ..tune.knobs import donation_enabled

    return (argnums if jax.default_backend() != "cpu" and donation_enabled()
            else ())


class TrainingEngine:
    """Training state and loops of a net over ``conf``. A front-end sets
    ``_KIND`` (``"mln"`` / ``"graph"``: the span argument ``net=``, the
    compile-manager kinds and the jitted functions' names are formed from
    it) and defines ``init``, ``_loss`` and the hooks at the end of this
    class."""

    _KIND: str

    def __init__(self, conf):
        self.conf = conf
        self.params: Any = None
        self.state: Any = None
        self.opt_state: Any = None
        self.iteration: int = 0
        self.epoch: int = 0
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.seed)
        self._tx: Optional[optax.GradientTransformation] = None
        self._train_step = None
        self._tbptt_step = None
        self._eval_forward = None
        self._last_loss = None
        self._rnn_state = None  # streaming rnnTimeStep state, one entry per layer/vertex
        self._rnn_step_fn = None
        self._grad_stats_step = None
        self._last_grads = None  # populated when a listener needs_gradients
        self._last_updates = None
        self.telemetry = None  # telemetry.Telemetry session (set_telemetry)
        self._telemetry_step = None
        self._cm_token = None  # compile-manager owner token (one per init())
        self.staged_steps_total = 0  # optimizer steps run via fit_on_device
        self._host_bytes_counters: dict = {}  # by path, looked up once

    def _invalidate_compiled(self) -> None:
        """Retire every executable built for the previous generation (the
        optimizer closure changed) and start a fresh compile-manager token;
        the manager evicts the stale entries eagerly instead of leaking them
        until LRU pressure."""
        from ..runtime.compile_manager import get_compile_manager

        cm = get_compile_manager()
        if self._cm_token is not None:
            cm.drop_token(self._cm_token)
        self._cm_token = cm.new_token()
        self._train_step = None
        self._tbptt_step = None
        self._eval_forward = None
        self._rnn_state = None
        self._rnn_step_fn = None
        self._grad_stats_step = None
        self._telemetry_step = None

    def _build_tx(self) -> optax.GradientTransformation:
        """The updater of ``conf``, told what among this net's layers its
        kernel gives way beside (:func:`adam_kernel_gives_way_beside`)."""
        return self.conf.updater.build(beside=adam_kernel_gives_way_beside(
            layer for _, layer, _ in self._layer_states()))

    def _kernel_scoped(self, fn):
        """``fn`` traced with kernel selection told whether GSPMD will
        partition the program: a net living on a multi-device layout cannot
        run Mosaic kernels outside a shard_map (ops.kernel_select
        .partitioned_program). AOT programs get the same scope from the
        compile manager, by their argument shardings."""
        from ..ops import kernel_select

        return kernel_select.scoped_for_layout(
            fn, getattr(self, "_mesh_layout", None))

    def _named(self, fn, what: str):
        """``fn`` under the name the traces, the lowered programs and
        ``docs/observability.md`` know it by: ``dl4j_<kind>_<what>``."""
        fn.__name__ = fn.__qualname__ = f"dl4j_{self._KIND}_{what}"
        return fn

    def _step_callable(self, variant: str = "plain"):
        """The per-batch jitted step, deduplicated through the process-wide
        compile manager (one LRU holds every executable of every net, so
        long-running jobs stay bounded)."""
        from ..runtime.compile_manager import get_compile_manager

        flags = {"grad_stats": {"with_grad_stats": True},
                 "telemetry": {"with_telemetry": True}}.get(variant, {})
        return get_compile_manager().callable(
            (self._cm_token, f"{self._KIND}_train_step", variant),
            lambda: self._build_train_step(**flags))

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    def set_telemetry(self, telemetry):
        """Attach a :class:`telemetry.Telemetry` session to the fit paths.

        With a session attached the jitted step additionally returns the
        device-side metrics vector (loss, grad norm, non-finite flag —
        telemetry.device.step_stats); the session fetches it every K steps,
        so instrumentation adds zero per-step host syncs. Pass None to
        detach."""
        self.telemetry = telemetry
        self._telemetry_step = None  # force rebuild with/without the vector
        return self

    def _wants_grad_stats(self) -> bool:
        """True when some listener will consume gradient/update stats on the
        iteration about to run — off-frequency iterations keep the donated
        fast path (StatsListener(frequency=50) costs the instrumented step
        on 1 of 50 steps, not all 50)."""
        nxt = self.iteration + 1
        return any(
            getattr(lst, "needs_gradients", False)
            and nxt % max(1, getattr(lst, "frequency", 1)) == 0
            for lst in self.listeners
        )

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))

    def memory_report(self, batch_or_struct=None) -> dict:
        """Per-layer (per-vertex) HBM attribution (param/grad/optimizer/
        activation bytes) at a batch size or example shape (a list of shapes
        for a multi-input graph) — pure ``jax.eval_shape``, nothing
        allocates. See :func:`deeplearning4j_tpu.telemetry.memory_report`."""
        from ..telemetry.memory import memory_report

        return memory_report(self, batch_or_struct)

    def preflight(self, batch_or_struct=None, **kw) -> dict:
        """Will this net + batch fit in HBM? Raises
        :class:`~deeplearning4j_tpu.telemetry.MemoryPreflightError` naming
        the biggest consumers BEFORE fit/warmup pays a doomed compile;
        returns the annotated memory report (including the DT2xx IR scan +
        static cost model) when it fits."""
        from ..telemetry.memory import preflight

        return preflight(self, batch_or_struct, **kw)

    def analyze_ir(self, batch_or_struct=None, **kw) -> dict:
        """DT2xx IR lint + static roofline cost model over this net's real
        train step — ``jax.make_jaxpr`` over ShapeDtypeStruct shells, zero
        device dispatches. Returns ``{"findings": [...], "static_cost":
        {...}}``; suppress rules with ``ignore=("DT204", ...)``. With
        ``layout=MeshLayout(...)`` the DT3xx sharding-flow pass joins in:
        the report gains ``"shard_flow"`` (predicted collective census,
        per-step ICI bytes) and the roofline covers communication-bound.
        See docs/static_analysis.md (DT2xx/DT3xx), docs/performance.md
        (roofline) and docs/distributed.md (predicting your collectives).
        """
        from ..analysis.ir_checks import check_network_ir

        return check_network_ir(self, batch_or_struct, **kw)

    # ------------------------------------------------------------- train step
    def _build_train_step(self, with_grad_stats: bool = False,
                          with_telemetry: bool = False):
        """Jitted step. ``with_grad_stats`` additionally returns the gradient
        and update pytrees so StatsListener can histogram them (reference:
        BaseStatsListener.java:419-437 collects parameters, gradients AND
        per-iteration updates). Kept off the default path: returning them
        defeats buffer reuse XLA would otherwise apply. ``with_telemetry``
        returns only the small device-side metrics vector instead
        (telemetry.device.step_stats) — the grad norm is reduced INSIDE the
        step, so the full gradient pytree never leaves the program."""
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)

        def train_step(params, opt_state, state, x, y, rng,
                       labels_mask, features_mask):
            # a counting layer's state holds the last step's counts here
            state = _zero_counters(state)

            def loss_of(p):
                loss, new_state, _ = self._loss(
                    p, state, x, y, rng, True, labels_mask, features_mask
                )
                return loss, new_state

            loss, new_state, grads, updates, new_opt, new_params = apply_step(
                loss_of, tx, ls, params, opt_state)
            if with_grad_stats:
                return new_params, new_opt, new_state, loss, grads, updates
            if with_telemetry:
                return (new_params, new_opt, new_state, loss,
                        _tdev.step_stats(loss, grads))
            return new_params, new_opt, new_state, loss

        return jax.jit(self._kernel_scoped(self._named(train_step, "train_step")),
                       donate_argnums=_donate(0, 1, 2))

    # ------------------------------------------------- on-device multi-step
    def _build_multi_step(self, steps_cap: int, with_masks: bool = False,
                          with_telemetry: bool = False):
        """ONE device dispatch for a whole window of optimizer steps: a
        ``lax.fori_loop`` of the train step over batches staged in HBM
        (every staged array stacked ``[K, B, ...]``), cycling
        ``i % n_batches``. ``xmasks``/``ymasks``: the staged features and
        labels masks (None, or None entries, allowed) — the bucketed
        stager's padded batches flow through here.

        The reference's fit loop dispatches per minibatch
        (MultiLayerNetwork.fit:917) — on TPU that pays a host dispatch per
        step (~0.6 ms round trip measured on the v5e, PERF.md), which a
        short step cannot hide. The loop keeps everything on-chip; per-step
        RNG uses the same split chain as sequential ``_fit_batch``, so
        results are bit-identical to per-step dispatch.

        Recompile elimination: the step count and the real staged-batch
        count are DEVICE scalars (``n_steps``/``n_batches``), not trace-time
        constants — changing either reuses one executable. Only ``steps_cap``
        (the static per-step-output buffer size, a power-of-two bucket) and
        the staged array shapes are baked into the program.

        Sharded nets additionally pin the OUTPUT placements to the layout's
        declared specs: unconstrained, GSPMD is free to return updated
        params at whatever sharding propagation favors — under
        ``MeshLayout(zero_stage=1)`` the fsdp-sharded moments pulled the
        (declared-replicated) params out fsdp-sharded, so the next dispatch
        saw new input shardings and paid one extra compile.
        """
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)
        constrain = self._staged_out_constraint()

        def staged(params, opt_state, state, rng, n_steps, n_batches,
                   xs, ys, xmasks, ymasks):
            losses0 = jnp.zeros((steps_cap,), jnp.float32)
            mvecs0 = (jnp.zeros((steps_cap, _tdev.NUM_SLOTS), jnp.float32)
                      if with_telemetry else None)
            # what layers count, they count from the dispatch's start
            state = _zero_counters(state)

            def body(i, carry):
                params, opt, st, rng, losses, mvecs = carry
                rng, step_key = jax.random.split(rng)
                idx = i % n_batches

                def pick(staged_tree):
                    # slot idx of whatever was staged (None: empty subtree)
                    return jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, idx, 0, keepdims=False), staged_tree)

                x, y = pick(xs), pick(ys)
                fm, lm = self._loss_masks(
                    pick(xmasks if with_masks else None),
                    pick(ymasks if with_masks else None))

                def loss_of(p):
                    loss, new_state, _ = self._loss(
                        p, st, x, y, step_key, True, lm, fm)
                    return loss, new_state

                loss, new_state, grads, _, new_opt, new_params = apply_step(
                    loss_of, tx, ls, params, opt)
                losses = jax.lax.dynamic_update_index_in_dim(
                    losses, loss.astype(jnp.float32), i, 0)
                if with_telemetry:
                    # per-step metrics vector written into the window buffer —
                    # the host fetches [steps, NUM_SLOTS] once, after dispatch
                    mvecs = jax.lax.dynamic_update_index_in_dim(
                        mvecs, _tdev.step_stats(loss, grads), i, 0)
                return (new_params, new_opt, new_state, rng, losses, mvecs)

            (params, opt_state, state, rng, losses, mvecs) = jax.lax.fori_loop(
                0, n_steps, body,
                (params, opt_state, state, rng, losses0, mvecs0))
            if constrain is not None:
                params, opt_state = constrain(params, opt_state)
            if with_telemetry:
                return params, opt_state, state, rng, losses, mvecs
            return params, opt_state, state, rng, losses

        return jax.jit(self._named(staged, "staged"),
                       donate_argnums=_donate(0, 1, 2, 3))

    def _staged_out_constraint(self):
        """Output-sharding pin for the staged step of a layout-applied net:
        updated params/opt-state leave the program at the layout's DECLARED
        specs (``with_sharding_constraint``), so the next dispatch's input
        signature is a fixed point — zero warm compiles even where GSPMD's
        own propagation would prefer a different placement (ZeRO-1)."""
        layout = getattr(self, "_mesh_layout", None)
        if layout is None or layout.mesh is None \
                or layout.mesh.devices.size <= 1:
            return None
        p_sh = layout.param_shardings(self.params)
        o_sh = layout.opt_shardings(self.opt_state)

        def constrain(params, opt_state):
            return (jax.lax.with_sharding_constraint(params, p_sh),
                    jax.lax.with_sharding_constraint(opt_state, o_sh))

        return constrain

    def _staged_executable(self, steps_cap: int, with_masks: bool,
                           with_telemetry: bool, args):
        """AOT-compiled multi-step executable from the process-wide compile
        manager, keyed by the canonical abstract signature of ``args``."""
        from ..runtime.compile_manager import get_compile_manager, signature

        cm = get_compile_manager()
        # token stays the key's FIRST element (drop_token matches on it)
        key = (self._cm_token, f"{self._KIND}_multi_step",
               signature(steps_cap, with_masks, with_telemetry, args))
        return cm.aot(
            key,
            lambda: self._build_multi_step(steps_cap, with_masks,
                                           with_telemetry),
            args,
        )

    def _staged_args(self, xs, ys, steps, features_masks, labels_masks,
                     real_batches):
        """Shared fit_on_device/warmup plumbing over what the front-end
        staged: validate, canonicalize scalars, and return
        ``(steps_cap, with_masks, n_steps, args)``."""
        from ..runtime.compile_manager import next_pow2

        num_slots = _staged_dim0(jax.tree_util.tree_leaves(xs)[0])
        if num_slots == 0:
            raise ValueError("fit_on_device needs at least one staged batch")
        _check_staged_counts(
            num_slots, _named_slots(xs, ys, features_masks, labels_masks))
        n_real = num_slots if real_batches is None else int(real_batches)
        if not 1 <= n_real <= num_slots:
            raise ValueError(
                f"real_batches={n_real} outside [1, {num_slots}]")
        n_steps = int(steps) if steps is not None else n_real
        # static loop/buffer bound: the staged window size, or the pow2
        # bucket when cycling past it — so nearby step counts share programs
        steps_cap = num_slots if n_steps <= num_slots else next_pow2(n_steps)
        with_masks = features_masks is not None or labels_masks is not None
        args = (self.params, self.opt_state, self.state, self._rng,
                jnp.asarray(n_steps, jnp.int32),
                jnp.asarray(n_real, jnp.int32),
                xs, ys, features_masks, labels_masks)
        return steps_cap, with_masks, n_steps, args

    def warmup(self, features, labels, steps: Optional[int] = None,
               features_masks=None, labels_masks=None,
               real_batches: Optional[int] = None):
        """Compile-ahead: build the staged executable for this window shape
        WITHOUT running a step, so the first training dispatch pays zero
        compile latency. Arrays may be real data or ``jax.ShapeDtypeStruct``
        shells — only shapes/dtypes matter. The compile lands in the same
        cache (and telemetry counters) fit_on_device uses."""
        self.init()
        from ..tune import store as _tuned

        _tuned.auto_apply(self, "warmup")  # tuned telemetry cadence etc.
        xs, ys, fm, lm = self._stage(features, labels, features_masks,
                                     labels_masks, _shell)
        steps_cap, with_masks, _, args = self._staged_args(
            xs, ys, steps, fm, lm, real_batches)
        self._staged_executable(steps_cap, with_masks,
                                self.telemetry is not None, args)
        return self

    def fit_on_device(self, features, labels, steps: Optional[int] = None,
                      features_masks=None, labels_masks=None,
                      real_batches: Optional[int] = None) -> np.ndarray:
        """Run a whole training loop in ONE device dispatch (TPU-native fit).

        ``features``/``labels``: stacked batches ``[K, B, ...]`` staged in
        HBM (a sequential net's ``xs``/``ys``); a graph takes lists, one
        entry per network input/output, or a single array where it has one.
        Step i trains on batch ``i % real_batches``. ``features_masks``/
        ``labels_masks``: stacked masks, per input/output for a graph (None
        entries allowed). ``real_batches`` (default K) marks how many
        leading slots hold real data — trailing slots may be dummy padding
        from the bucketed stager and are never indexed. ``steps`` defaults
        to one pass over the real batches. Returns the per-step losses as a
        host array. Gradient-stats listeners and TBPTT are not served by
        this path (use :meth:`fit`); ``iteration_done`` fires per step
        afterwards with the device-computed losses.
        """
        self.init()
        if self.conf.backprop_type == "tbptt":
            raise ValueError("fit_on_device does not support TBPTT; use fit()")
        with span("dl4j.fit.dispatch", net=self._KIND) as dispatch:
            with span("dl4j.fit.prepare"):
                xs, ys, fm, lm = self._stage(features, labels, features_masks,
                                             labels_masks, jnp.asarray)
                tel = self.telemetry
                steps_cap, with_masks, n_steps, args = self._staged_args(
                    xs, ys, steps, fm, lm, real_batches)
                fn = self._staged_executable(steps_cap, with_masks,
                                             tel is not None, args)
            slots, batch = (
                int(d) for d in jax.tree_util.tree_leaves(xs)[0].shape[:2])
            dispatch.args.update(steps=int(n_steps), slots=slots, batch=batch)
            t0 = time.perf_counter()
            with span("dl4j.fit.launch"):
                out = fn(*args)
            mvecs = None
            if tel is not None:
                (self.params, self.opt_state, self.state, self._rng,
                 losses, mvecs) = out
            else:
                self.params, self.opt_state, self.state, self._rng, losses = out
            # host fetch = the sync point; the tail of the buffer (beyond
            # n_steps) is sliced off HOST-side — a device-side slice would
            # compile a tiny program per distinct step count. The loop
            # stacked per-step metrics; ONE more (already-computed) fetch
            # brings the whole window — never a per-step sync
            with span("dl4j.fit.fetch"):
                losses = np.asarray(losses)[:n_steps]
                if mvecs is not None:
                    mvecs = np.asarray(mvecs)[:n_steps]
                # the dispatch's layer counters into the default registry
                # (telemetry/device.py); nothing without a counting layer
                _tdev.publish_layer_counters(self._layer_states())
            elapsed = time.perf_counter() - t0
            if tel is not None:
                if tel.flight is not None:
                    # ring the dispatch BEFORE on_staged reads the metrics —
                    # an anomaly found there auto-dumps, and the bundle
                    # should already show what was dispatched
                    tel.flight.record(
                        "staged_dispatch", net=self._KIND, steps=int(n_steps),
                        slots=slots, batch=batch, seconds=round(elapsed, 6))
                tel.on_staged(self.iteration + 1, mvecs,
                              per_step_time_s=elapsed / max(len(losses), 1))
            self.last_batch_size = batch
            self.staged_steps_total += len(losses)
            # replayed callbacks arrive in a tight host loop; wall-clock
            # deltas between them measure nothing, so publish the dispatch's
            # even per-step share for throughput listeners
            # (PerformanceListener)
            self.staged_step_time = elapsed / max(len(losses), 1)
            with span("dl4j.fit.listeners"):
                try:
                    for loss in losses:
                        self.iteration += 1
                        self._last_loss = loss
                        for lst in self.listeners:
                            lst.iteration_done(self, self.iteration, loss)
                finally:
                    self.staged_step_time = None
        return losses

    def fit(self, data, epochs: int = 1,
            stage_on_device: Optional[int] = None,
            bucketing: bool = True):
        """Train (reference: MultiLayerNetwork.fit(DataSetIterator):917,
        ComputationGraph.fit(MultiDataSet):743).

        ``data``: (x, y) tuple, a DataSet (a MultiDataSet for a graph), or
        an iterator of them. Iterators are auto-wrapped in async prefetch
        (reference :920-924) unless already async.

        ``stage_on_device`` left unset auto-applies a matching TUNED.json
        staging window when the autopilot has tuned this model (tune/store.py)
        and otherwise trains per-batch; an explicit value — including 0 —
        always wins.

        ``stage_on_device=K`` (TPU fast path): buffer K batches, stack them
        in HBM, and run the whole window as ONE dispatch via
        :meth:`fit_on_device`, double-buffered. What the double buffer
        covers is the transfer: window i+1's ``device_put`` is enqueued
        before window i is launched, so it runs beside window i's compute.
        What it does not cover is the stack: window i+1 is copied together
        from its K batches on this thread (``np.stack``, the span
        ``dl4j.fit.stack``) BEFORE window i is launched, and the launch
        returns only with window i's losses, so the device has nothing
        queued while the host stacks. With ``bucketing`` (default)
        ragged batches stay on the staged path: trailing partial batches pad
        up with masked zero rows, variable sequence lengths pad to
        power-of-two time buckets, and a trailing partial window runs with a
        device-scalar step count — all numerically equivalent on the real
        elements (see datasets/bucketing.py; dropout draws differ in shape,
        and models with BatchNormalization skip row padding because batch
        statistics couple examples). ``bucketing=False`` restores the strict
        legacy contract: only full uniform groups stage (bit-identical RNG
        chain), everything ragged trains per-batch. Gradient-stats listeners
        and TBPTT disable staging since the on-device loop can't serve them.

        Every epoch is one ``dl4j.fit.epoch`` span on the calling thread,
        with the iterator waits (``dl4j.fit.next_batch``), the windows'
        ``stack`` / ``put`` / ``dispatch`` or the batches' ``step`` /
        ``listeners`` under it (docs/observability.md has the table).
        """
        from ..datasets.iterators import AsyncDataSetIterator, as_iterator

        self.init()
        if self._train_step is None:
            self._train_step = self._step_callable()
        from ..tune import store as _tuned

        tuned = _tuned.auto_apply(
            self, "fit",
            explicit=() if stage_on_device is None else ("stage_window",))
        if stage_on_device is None:
            stage_on_device = int(tuned.get("stage_window", 0))
        stage = int(stage_on_device)
        if stage > 1 and (
            self.conf.backprop_type == "tbptt"
            or any(not getattr(lst, "supports_staged", False)
                   for lst in self.listeners)
        ):
            stage = 0  # TBPTT needs per-batch segmenting; listeners must
            #            OPT IN to staging (iteration_done replays after the
            #            scan, so per-iteration model state is unavailable —
            #            see IterationListener.supports_staged)

        for _ in range(epochs):
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self, self.epoch)
            it = as_iterator(data)
            if hasattr(it, "reset"):
                it.reset()  # reference resets the iterator each epoch (fit:917)
            if getattr(it, "prefetch_supported", False):
                it = AsyncDataSetIterator(it)
            with span("dl4j.fit.epoch", net=self._KIND, epoch=self.epoch,
                      stage=stage) as epoch:
                waits = _BatchWaits(it)
                if stage > 1:
                    waits.window = 0
                    self._fit_epoch_staged(waits, stage, bucketing)
                else:
                    for batch, ds in enumerate(waits):
                        with identified(batch=batch):
                            self._fit_batch(ds)
                epoch.args.update(batches=waits.batches,
                                  windows=waits.window or 0)
            self.epoch += 1
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self, self.epoch)
        if self.telemetry is not None:
            self.telemetry.flush()  # drain a partial K-window at fit end
        return self

    def _count_host_bytes(self, path: str, nbytes: int) -> None:
        """``dl4jtpu_fit_host_bytes_total{path}`` of the default registry
        (``staged``: a window's ``device_put``; ``per_batch``: a jitted
        step's host arguments), looked up once a net and path: a batch of a
        small net is a few hundred microseconds of host work."""
        counter = self._host_bytes_counters.get(path)
        if counter is None:
            counter = self._host_bytes_counters[path] = get_registry().counter(
                "dl4jtpu_fit_host_bytes_total",
                "host bytes fit() handed to the device (a window's "
                "device_put, a step's host arguments)",
                labelnames=("path",)).labels(path=path)
        counter.inc(nbytes)

    def _fit_epoch_staged(self, it, stage: int, bucketing: bool = True) -> None:
        """Stage windows of ``stage`` batches per fit_on_device dispatch via
        the bucketed planner (datasets/bucketing.py), double-buffered:
        window i+1 is host-stacked (``dl4j.fit.stack``) and its
        ``jax.device_put`` enqueued (``dl4j.fit.put``, async) before window i
        is dispatched, so its H2D transfer overlaps window i's compute; the
        stack itself does not (``fit``'s docstring). Unstageable batches
        train through the ordinary per-batch step, in stream order. ``it``:
        the epoch's :class:`_BatchWaits`."""
        from ..datasets.bucketing import BucketedStager

        stager = BucketedStager(stage, bucketing=bucketing,
                                pad_examples=self._pad_examples_ok())

        def normalize(ds):
            feats, labs, fmasks, lmasks = self._batch_lists(ds)
            return ([np.asarray(f) for f in feats],
                    [np.asarray(l) for l in labs], fmasks, lmasks)

        def to_device(win):
            # async: overlaps the pending dispatch
            nbytes = win.nbytes()
            with identified(window=win.ordinal), span("dl4j.fit.put",
                                                      bytes=nbytes):
                (win.features, win.labels, win.features_masks,
                 win.labels_masks) = jax.tree_util.tree_map(
                    jax.device_put, (win.features, win.labels,
                                     win.features_masks, win.labels_masks))
            self._count_host_bytes("staged", nbytes)
            return win

        def dispatch(win):
            xs, ys, fm, lm = self._from_lists(
                win.features, win.labels, win.features_masks,
                win.labels_masks)
            # fit_on_device's spans are this window's without knowing of it
            with identified(window=win.ordinal):
                self.fit_on_device(xs, ys, steps=win.n_real,
                                   features_masks=fm, labels_masks=lm,
                                   real_batches=win.n_real)

        pending = None
        trained = 0  # the plan keeps stream order: ordinals count what came out
        for kind, payload in stager.plan(it, normalize):
            if kind == "window":
                it.window = payload.ordinal + 1  # what the next waits fill
                trained += payload.n_real
                staged = to_device(payload)
                if pending is not None:
                    dispatch(pending)
                pending = staged
            else:
                if pending is not None:
                    dispatch(pending)
                    pending = None
                with identified(batch=trained):
                    self._fit_batch(payload)
                trained += 1
        if pending is not None:
            dispatch(pending)
        self._check_padding_waste(stager)

    def _check_padding_waste(self, stager) -> None:
        """DT205 epoch hook: compare the stager's bucket shapes against the
        real batch statistics it just staged; findings land in
        dl4jtpu_ir_findings_total{rule} + the flight recorder. Advisory —
        never interrupts training."""
        try:
            from ..analysis.ir_checks import (check_padding_waste,
                                              record_findings)

            findings = check_padding_waste(
                stager.padding_stats(),
                source=f"<{type(self).__name__} epoch {self.epoch}>")
            registry = (self.telemetry.registry
                        if self.telemetry is not None else None)
            record_findings(findings, registry=registry)
        except Exception:  # observability must never break fit
            pass

    def _fit_batch(self, ds) -> None:
        x, y, fm, lm = self._from_lists(*self._batch_lists(ds))
        fm, lm = self._loss_masks(fm, lm)
        leaves = jax.tree_util.tree_leaves(x)
        self.last_batch_size = int(leaves[0].shape[0])
        # host-side reference (no copy), kept ONLY while a listener needs it:
        # ConvolutionalIterationListener re-runs the forward on this batch
        # (reference: Model.setInput/input()). Unconditional retention would
        # pin one full batch per net for the net's lifetime.
        if any(getattr(lst, "needs_input", False) for lst in self.listeners):
            self._last_input = x
        else:
            self._last_input = None
        if self.conf.backprop_type == "tbptt" and any(
                np.ndim(f) == 3 for f in leaves):
            self._fit_tbptt(x, y, fm, lm)
            return
        self._rng, step_key = jax.random.split(self._rng)
        step_args = (x, y, step_key, lm, fm)
        tel = self.telemetry
        mvec = None
        nbytes = _host_bytes(step_args)
        # the jitted step's call: the enqueue, and the implicit transfer of
        # whatever it was handed as host arrays (never a sync)
        with span("dl4j.fit.step", bytes=nbytes):
            if self._wants_grad_stats():
                if self._grad_stats_step is None:
                    self._grad_stats_step = self._step_callable("grad_stats")
                (self.params, self.opt_state, self.state, loss,
                 self._last_grads, self._last_updates) = self._grad_stats_step(
                    self.params, self.opt_state, self.state, *step_args)
                if tel is not None:
                    # grads already left the program for StatsListener; reduce
                    # them eagerly (async dispatch, still no host sync)
                    mvec = _tdev.step_stats(loss, self._last_grads)
            elif tel is not None:
                if self._telemetry_step is None:
                    self._telemetry_step = self._step_callable("telemetry")
                (self.params, self.opt_state, self.state, loss, mvec) = \
                    self._telemetry_step(
                        self.params, self.opt_state, self.state, *step_args)
            else:
                (self.params, self.opt_state, self.state,
                 loss) = self._train_step(
                    self.params, self.opt_state, self.state, *step_args)
        self._count_host_bytes("per_batch", nbytes)
        self._last_loss = loss
        self.iteration += 1
        if tel is not None and mvec is not None:
            tel.on_step(self.iteration, mvec)
        if self.listeners:  # a span only where there is something to time
            with span("dl4j.fit.listeners"):
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration, loss)
        # listeners have copied what they need; don't pin ~2x model size of
        # gradient+update buffers in HBM until the next instrumented step
        self._last_grads = None
        self._last_updates = None

    # ---------------------------------------------------------------- TBPTT
    def _build_tbptt_step(self):
        """One param update per time segment, recurrent state carried across
        segments with gradients stopped (reference: doTruncatedBPTT:1080;
        tbptt_back_length < fwd_length truncates the backward window like
        tbpttBackwardLength does)."""
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)
        back_len = int(self.conf.tbptt_back_length or 0)

        def cut(masks, sl):
            return jax.tree_util.tree_map(lambda m: m[:, sl], masks)

        def tbptt_step(params, opt_state, state, rnn, x, y, rng,
                       labels_mask, features_mask):
            state = _zero_counters(state)
            seg_len = next(a.shape[1] for a in jax.tree_util.tree_leaves(x)
                           if a.ndim == 3)
            k = seg_len if back_len <= 0 else min(back_len, seg_len)
            if k < seg_len:
                # tbptt_back_length < fwd_length: the first seg_len-k steps
                # evolve hidden state (and BN stats) but contribute no
                # gradient — the reference's backward loop caps at
                # tbpttBackwardLength (LSTMHelpers.backpropGradientHelper),
                # discarding epsilons from earlier outputs entirely.
                pre, post = slice(None, seg_len - k), slice(seg_len - k, None)
                pre_rng, rng = jax.random.split(rng)
                state_in, rnn_in = jax.lax.stop_gradient(self._warm_state(
                    params, self._time_slice(x, pre), state, pre_rng,
                    cut(features_mask, pre), rnn))
                x_g, y_g = self._time_slice(x, post), self._time_slice(y, post)
                lm_g, fm_g = cut(labels_mask, post), cut(features_mask, post)
            else:
                x_g, y_g, lm_g, fm_g = x, y, labels_mask, features_mask
                state_in, rnn_in = state, rnn

            def loss_of(p):
                loss, new_state, new_rnn = self._loss(
                    p, state_in, x_g, y_g, rng, True, lm_g, fm_g, rnn_state=rnn_in
                )
                return loss, (new_state, new_rnn)

            loss, (new_state, new_rnn), _, _, new_opt, new_params = apply_step(
                loss_of, tx, ls, params, opt_state)
            # Segment boundary IS the gradient-truncation boundary: the returned
            # h/c re-enter the next jit call as constants (reference:
            # MultiLayerNetwork.doTruncatedBPTT:1080 rnnUpdateStateWithTBPTTState).
            new_rnn = jax.lax.stop_gradient(new_rnn)
            return new_params, new_opt, new_state, new_rnn, loss

        return jax.jit(self._kernel_scoped(self._named(tbptt_step, "tbptt_step")))

    def _fit_tbptt(self, x, y, features_mask, labels_mask) -> None:
        """Truncated BPTT over time segments (reference: doTruncatedBPTT:1080).

        The sequence is split into ``tbptt_fwd_length`` chunks; one param update
        per chunk; LSTM h/c carry across chunks with gradients stopped. A
        trailing partial chunk trains too (the reference processes it) — XLA
        compiles the step once more for the tail shape. ``tbptt_back_length <
        tbptt_fwd_length`` truncates the backward window inside each chunk
        (reference: tbpttBackwardLength in LSTMHelpers.backpropGradientHelper).
        """
        if self._tbptt_step is None:
            self._tbptt_step = self._build_tbptt_step()
        # TBPTT uses its own jitted step without grad-stats instrumentation;
        # drop any stale grads so StatsListener never histograms a previous
        # non-TBPTT batch's gradients under this iteration's label.
        self._last_grads = None
        self._last_updates = None
        x, y = jax.tree_util.tree_map(np.asarray, (x, y))
        leaves = jax.tree_util.tree_leaves((x, y))
        seq_lens = {a.shape[1] for a in leaves if a.ndim == 3}
        if len(seq_lens) != 1:
            raise ValueError(
                f"TBPTT requires one shared sequence length; got {sorted(seq_lens)}"
            )
        T, L = seq_lens.pop(), self.conf.tbptt_fwd_length
        rnn = self._init_rnn_states(leaves[0].shape[0])
        for t0 in range(0, T, L):
            seg = slice(t0, t0 + min(L, T - t0))
            lm, fm = jax.tree_util.tree_map(
                lambda m: np.asarray(m)[:, seg], (labels_mask, features_mask))
            self._rng, step_key = jax.random.split(self._rng)
            step_args = (self._time_slice(x, seg), self._time_slice(y, seg),
                         step_key, lm, fm)
            nbytes = _host_bytes(step_args)
            with span("dl4j.fit.step", bytes=nbytes, segment=t0 // L):
                (self.params, self.opt_state, self.state, rnn,
                 loss) = self._tbptt_step(
                    self.params, self.opt_state, self.state, rnn, *step_args)
            self._count_host_bytes("per_batch", nbytes)
            self._last_loss = loss
            self.iteration += 1
            if self.telemetry is not None:
                # TBPTT's step returns no gradient view; record loss +
                # finiteness (grad norm reads 0 on this path)
                self.telemetry.on_step(self.iteration, _tdev.step_stats(loss))
            if self.listeners:
                with span("dl4j.fit.listeners", segment=t0 // L):
                    for lst in self.listeners:
                        lst.iteration_done(self, self.iteration, loss)

    # ------------------------------------------------------------------ misc
    def clone(self):
        other = type(self)(type(self.conf).from_dict(self.conf.to_dict()))
        if self.params is not None:
            # real copies, not shared buffers: the train steps donate
            # params/opt-state/state on accelerators, so a clone that
            # aliased them would read "Array has been deleted" after the
            # original's next step (early stopping's best-model saver)
            other.init(params=jax.tree_util.tree_map(jnp.copy, self.params))
            other.state = jax.tree_util.tree_map(jnp.copy, self.state)
            other.opt_state = jax.tree_util.tree_map(jnp.copy, self.opt_state)
            other.iteration = self.iteration
        return other

    # ------------------------------------------- what a front-end defines
    def _loss(self, params, state, x, y, rng, train, labels_mask=None,
              features_mask=None, rnn_state=None):
        """``(loss + regularization, new_state, new_rnn)`` of one batch in
        the front-end's own form."""
        raise NotImplementedError

    def _stage(self, features, labels, features_masks, labels_masks, leaf):
        """The caller's ``fit_on_device`` arguments as the staged pytrees
        ``(xs, ys, features_masks, labels_masks)``, ``leaf`` applied to
        every array (``jnp.asarray``, or a shell for warmup)."""
        raise NotImplementedError

    def _loss_masks(self, features_masks, labels_masks):
        """One batch's masks in their staged form as ``_loss`` takes them:
        ``(features_mask, labels_mask)``."""
        raise NotImplementedError

    def _batch_lists(self, ds):
        """A dataset as the bucketed stager's four lists: features, labels,
        features masks, labels masks."""
        raise NotImplementedError

    def _from_lists(self, features, labels, features_masks, labels_masks):
        """Those four lists back in the form ``fit_on_device`` takes."""
        raise NotImplementedError

    def _layer_states(self):
        """``(name, layer, layer_state)`` of every layer, for the layer
        counters (telemetry/device.py)."""
        raise NotImplementedError

    def _pad_examples_ok(self) -> bool:
        """Row padding is exact only for per-example models; batch statistics
        (BatchNormalization) couple rows, so such models keep exact batch
        sizes (window padding with dummy slots stays on — never executed)."""
        raise NotImplementedError

    def _init_rnn_states(self, batch: int):
        """Streaming state per layer/vertex ({} for stateless ones)."""
        raise NotImplementedError

    def _warm_state(self, params, x, state, rng, features_mask, rnn):
        """``(new_state, new_rnn)`` after a train-mode forward over ``x``:
        the TBPTT steps before the backward window."""
        raise NotImplementedError

    def _time_slice(self, arrays, sl):
        """Features or labels cut to the time steps ``sl``."""
        raise NotImplementedError
