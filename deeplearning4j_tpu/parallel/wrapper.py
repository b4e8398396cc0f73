"""ParallelWrapper: multi-device data-parallel training on one mesh.

Reference: deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:44
(fit loop :141-247, parameter averaging :170-235, updater-state averaging
:198-224). The reference spawns N replica threads pinned to devices, dispatches
minibatches round-robin, and every ``averaging_frequency`` iterations barriers
and calls ``Nd4j.averageAndPropagate``.

TPU-native design — the thread/queue machinery does not exist:

- ``averaging_frequency == 1`` (sync mode, the modern strictly-better default,
  SURVEY.md §5.8): params live replicated on the mesh, the global batch is
  sharded over the "data" axis, and the net's OWN jitted train step runs
  SPMD — XLA inserts the gradient all-reduce (psum) over ICI. Per-step
  all-reduce ≡ averaging every iteration, with none of the reference's barrier
  or propagate steps.

- ``averaging_frequency > 1`` (parameter-averaging parity mode): each device
  holds an INDEPENDENT replica (params stacked on a leading replica axis,
  sharded over "data"); ``jax.vmap`` of the train step over that axis runs all
  replicas in parallel with zero communication — the exact semantics of the
  reference's free-running threads — and a jitted averaging program (mean over
  the replica axis = all-reduce, broadcast back = all-gather) replaces
  ``Nd4j.averageAndPropagate``. Updater state averaging matches
  ``averageUpdaters`` (ParallelWrapper.java:198-224).

Every sharding this wrapper places comes from ONE authority — the
:class:`~deeplearning4j_tpu.parallel.layout.MeshLayout` (dp×fsdp×tp layout
rules + precision policy, docs/distributed.md); the wrapper is a thin
training strategy over it.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kernel_select
from .layout import MeshLayout
from .mesh import make_mesh, global_put, global_put_local


def _stack_tree(tree, n: int):
    return jax.tree_util.tree_map(lambda a: jnp.stack([a] * n), tree)


def _mean_tree(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.mean(a, axis=0) if jnp.issubdtype(a.dtype, jnp.floating)
        else a[0],
        tree,
    )


class ParallelWrapper:
    """Data-parallel trainer over a device mesh (reference API:
    ParallelWrapper.Builder → workers/averagingFrequency/averageUpdaters/
    reportScoreAfterAveraging, ParallelWrapper.java:44)."""

    def __init__(
        self,
        net,
        workers: Optional[int] = None,
        averaging_frequency: int = 1,
        average_updaters: bool = True,
        report_score_after_averaging: bool = True,
        prefetch_buffer: int = 2,
        mesh=None,
        model_axis: Optional[str] = None,
        expert_axis: Optional[str] = None,
        data_is_local: bool = False,
        layout: Optional[MeshLayout] = None,
    ):
        self.net = net
        # ONE sharding authority: every batch/param/opt-state sharding this
        # wrapper uses comes from a MeshLayout (parallel/layout.py). Pass
        # ``layout=`` for the canonical dp×fsdp×tp mesh; the legacy
        # mesh/model_axis/expert_axis arguments wrap into a layout too
        # (model_axis plays the tp role), so both paths share the rule set.
        if layout is not None:
            if mesh is not None or model_axis or expert_axis:
                raise ValueError(
                    "pass either layout= or mesh=/model_axis=/expert_axis=, "
                    "not both — the layout already owns the mesh and axes")
            self.layout = layout
        else:
            m = mesh if mesh is not None else make_mesh(workers)
            # dp×tp: batch shards over "data", params over model_axis (GSPMD
            # inserts the tensor-parallel collectives); dp×ep: MoE
            # expert-stacked weights shard over expert_axis — from_mesh
            # raises on an axis name absent from the mesh (typo = loud)
            self.layout = MeshLayout.from_mesh(m, model_axis, expert_axis)
        self.mesh = self.layout.mesh
        self.model_axis = self.layout._tp_axis
        self.expert_axis = self.layout._expert_axis
        if averaging_frequency > 1 and (
                self.layout._tp_axis or self.layout._expert_axis
                or self.layout._fsdp_axis):
            raise ValueError(
                "fsdp/tensor/expert parallelism requires sync mode "
                "(averaging_frequency=1); periodic replica averaging stacks "
                "independent UNSHARDED replicas and would silently drop the "
                "declared param sharding"
            )
        self._data_axes = self.layout.batch_axes
        self.workers = int(self.layout.batch_factor)
        # data_is_local: each PROCESS feeds only its shard of the global
        # batch (per-host input pipelines, SURVEY.md §7(d)); default is the
        # broadcast pattern (every process holds the full batch). Sync mode
        # only — periodic mode stacks per-replica batches globally.
        self.data_is_local = data_is_local
        if data_is_local and averaging_frequency > 1:
            raise ValueError("data_is_local requires sync mode "
                             "(averaging_frequency=1)")
        if data_is_local:
            # every process must address an equal, non-zero share of the
            # mesh: a mesh over a device subset leaves some process with
            # zero addressable shards (and another with extra), which
            # mis-assembles the global batch instead of failing loudly
            pidx = jax.process_index()
            local_devs = sum(1 for d in self.mesh.devices.flat
                             if d.process_index == pidx)
            total = int(np.prod(self.mesh.devices.shape))
            if local_devs == 0 or local_devs * jax.process_count() != total:
                raise ValueError(
                    f"data_is_local needs every process to address an equal "
                    f"share of the mesh; process {pidx} addresses "
                    f"{local_devs}/{total} devices"
                )
            if self.workers % jax.process_count() != 0:
                # group_size = workers // process_count must tile the data
                # sharding exactly (e.g. data=4 over 3 processes cannot)
                raise ValueError(
                    f"data_is_local needs the {self.workers}-way data "
                    f"sharding to divide evenly over "
                    f"{jax.process_count()} processes"
                )
            # NOTE: per-host pipelines must feed IDENTICAL step counts on
            # every host — a host with more full groups enters a collective
            # the others never join and the cluster hangs (inherent to SPMD;
            # pad or truncate per-host data to equal length).
        self.averaging_frequency = int(averaging_frequency)
        self.average_updaters = average_updaters
        self.report_score_after_averaging = report_score_after_averaging
        self.prefetch_buffer = prefetch_buffer
        self.iteration = 0
        self._replica = None  # (params, opt_state, state) stacked, periodic mode
        self._vstep = None
        self._avg_fn = None
        self._sync_ready = False
        # Shared instrumentation path (profiler.StepTimer): the same
        # data/step/average phases feed the TrainingMaster's phase stats, the
        # StatsListener records (UI system page), the bench breakdown AND,
        # as the spans dl4j.parallel_wrapper.<phase>, the telemetry registry
        # (dl4jtpu_span_seconds at /metrics) —
        # reference: ParameterAveragingTrainingWorkerStats per-phase events.
        from ..profiler import StepTimer  # noqa: PLC0415

        self.timer = StepTimer(component="parallel_wrapper")
        net._phase_timer = self.timer

    # ------------------------------------------------------------- sync mode
    def _setup_sync(self):
        net = self.net
        # layout.apply: precision policy + params/opt-state sharded by the
        # rule set (moments follow their param's spec; training state is
        # preserved, not reset), state replicated, net stamped so the
        # serving fast path discovers the placement
        self.layout.apply(net)
        # the rng key rides every staged dispatch and comes back
        # mesh-replicated; placing it up front keeps the FIRST dispatch's
        # cache signature identical to every later one (zero warm compiles)
        net._rng = self.layout.put(net._rng, self.layout.replicated())
        if net._train_step is None:
            net._train_step = net._build_train_step()
        self._sync_ready = True

    def _batch_sharding(self):
        """Batch-dim sharding over every batch (data×fsdp) mesh axis."""
        return self.layout.batch_sharding()

    def _fit_sync(self, global_ds) -> None:
        """One SPMD step on a globally-sharded batch; grads psum over ICI."""
        net = self.net
        shard = self._batch_sharding()
        put = global_put_local if self.data_is_local else global_put
        with self.timer.phase("data"):
            x = put(np.asarray(global_ds.features), shard)
            y = put(np.asarray(global_ds.labels), shard)
            net._rng, step_key = jax.random.split(net._rng)
            lm_ = getattr(global_ds, "labels_mask", None)
            fm_ = getattr(global_ds, "features_mask", None)
            lm = None if lm_ is None else put(np.asarray(lm_), shard)
            fm = None if fm_ is None else put(np.asarray(fm_), shard)
        tel = getattr(net, "telemetry", None)
        with self.timer.phase("step"):
            if tel is not None:
                # telemetry-instrumented SPMD step: the metrics vector is
                # reduced on-mesh (grad-norm psums ride ICI with the grads)
                if net._telemetry_step is None:
                    net._telemetry_step = net._build_train_step(
                        with_telemetry=True)
                (net.params, net.opt_state, net.state, loss, mvec) = \
                    net._telemetry_step(
                        net.params, net.opt_state, net.state, x, y, step_key,
                        lm, fm,
                    )
            else:
                net.params, net.opt_state, net.state, loss = net._train_step(
                    net.params, net.opt_state, net.state, x, y, step_key, lm, fm
                )
        net._last_loss = loss
        net.iteration += 1
        self.iteration += 1
        if tel is not None:
            tel.on_step(net.iteration, mvec)
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration, loss)

    def fit_on_device(self, xs, ys, steps: Optional[int] = None,
                      features_masks=None, labels_masks=None):
        """Whole training loop in ONE dispatch, in either wrapper mode.

        Sync mode (``averaging_frequency=1``): ``xs``/``ys`` are K global
        batches ``[K, B_global, ...]`` staged sharded over the data axes
        (batch dim is axis 1); lax.scan of the SPMD train step — gradient
        psums ride ICI *inside* the scan, with zero host round-trips between
        steps.

        Periodic mode (``averaging_frequency=F > 1``): ``xs``/``ys`` are K
        replica-stacked groups ``[K, workers, batch, ...]`` (the same shape
        each sequential ``_fit_periodic`` step consumes); the scan runs every
        replica's independent step per tick and folds the
        averageAndPropagate mean/broadcast in via ``lax.cond`` on the same
        ``iteration % F`` schedule — Spark-parity parameter averaging with
        the host out of the loop entirely.

        Both paths match sequential :meth:`fit` numerics exactly (same RNG
        chains). Multi-process: every process calls this with the same K and
        steps; under ``data_is_local`` each passes only its per-process share
        of each global batch.
        """
        if self.averaging_frequency > 1:
            return self._fit_on_device_periodic(xs, ys, steps,
                                                features_masks, labels_masks)
        if not self._sync_ready:
            self._setup_sync()
        net = self.net
        shard = self.layout.staged_batch_sharding()
        put = global_put_local if self.data_is_local else global_put
        try:
            with self.timer.phase("data"):
                xs = put(np.asarray(xs), shard)
                ys = put(np.asarray(ys), shard)
                fm = None if features_masks is None else put(np.asarray(features_masks), shard)
                lm = None if labels_masks is None else put(np.asarray(labels_masks), shard)
            with self.timer.phase("step"):
                losses = net.fit_on_device(xs, ys, steps=steps,
                                           features_masks=fm, labels_masks=lm)
        finally:
            # same stale-breakdown guard as fit(): a later plain net.fit must
            # not report this wrapper's frozen phase timings
            if getattr(net, "_phase_timer", None) is self.timer:
                net._phase_timer = None
        self.iteration += len(losses)
        return losses

    def _build_periodic_multi_step(self, num_steps: int, num_groups: int,
                                   start_iter: int):
        """lax.scan over the vmapped per-replica step with the averaging
        fold-in: tick i runs every replica's independent step, then
        ``lax.cond((start_iter + i + 1) % F == 0)`` applies the
        averageAndPropagate mean/broadcast — the exact schedule sequential
        ``_fit_periodic`` follows, so numerics match per-step dispatch."""
        one_step, average = self._one_step, self._avg_pure
        n, F = self.workers, self.averaging_frequency

        def run(replica, rng, xs, ys, xmasks, ymasks):
            def body(carry, i):
                (params, opt, state), rng = carry
                rng, k = jax.random.split(rng)
                keys = jax.random.split(k, n)
                idx = i % num_groups
                x = jax.lax.dynamic_index_in_dim(xs, idx, 0, keepdims=False)
                y = jax.lax.dynamic_index_in_dim(ys, idx, 0, keepdims=False)
                fm = (jax.lax.dynamic_index_in_dim(xmasks, idx, 0, keepdims=False)
                      if xmasks is not None else None)
                lm = (jax.lax.dynamic_index_in_dim(ymasks, idx, 0, keepdims=False)
                      if ymasks is not None else None)
                params, opt, state, losses = jax.vmap(one_step)(
                    params, opt, state, x, y, keys, lm, fm
                )
                params, opt, state = jax.lax.cond(
                    (start_iter + i + 1) % F == 0,
                    lambda t: average(*t),
                    lambda t: t,
                    (params, opt, state),
                )
                return ((params, opt, state), rng), jnp.mean(losses)

            (replica, rng), losses = jax.lax.scan(
                body, (replica, rng), jnp.arange(num_steps)
            )
            return replica, rng, losses

        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        return jax.jit(kernel_select.scoped_for_layout(run, self.layout),
                       donate_argnums=donate)

    def _fit_on_device_periodic(self, xs, ys, steps, features_masks, labels_masks):
        if self._replica is None:
            self._setup_periodic()
        net = self.net
        xs = np.asarray(xs)
        ys = np.asarray(ys)
        num_groups = int(xs.shape[0])
        if num_groups == 0:
            raise ValueError("fit_on_device needs at least one staged group")
        if int(xs.shape[1]) != self.workers:
            raise ValueError(
                f"periodic fit_on_device groups must stack one batch per "
                f"replica: got axis-1 size {int(xs.shape[1])}, "
                f"workers={self.workers}"
            )
        from ..nn.engine import _check_staged_counts  # noqa: PLC0415

        _check_staged_counts(num_groups, (("ys", ys),
                                          ("features_masks", features_masks),
                                          ("labels_masks", labels_masks)))
        n_steps = int(steps) if steps is not None else num_groups
        if n_steps <= 0:  # match the sync path: no-op, no dispatch
            return np.zeros((0,), np.float32)
        # the averaging schedule is phase-dependent: bake the entry
        # iteration's offset into the compiled program (and its cache key)
        phase = self.iteration % self.averaging_frequency
        if getattr(self, "_periodic_multi_cache", None) is None:
            self._periodic_multi_cache = {}
        cache_key = (n_steps, num_groups, phase,
                     features_masks is not None, labels_masks is not None)
        fn = self._periodic_multi_cache.get(cache_key)
        if fn is None:
            fn = self._build_periodic_multi_step(n_steps, num_groups, phase)
            self._periodic_multi_cache[cache_key] = fn
        # groups [K, workers, batch, ...]: replica axis is 1
        group_shard = self.layout.staged_batch_sharding()
        try:
            with self.timer.phase("data"):
                xs = global_put(xs, group_shard)
                ys = global_put(ys, group_shard)
                fm = (None if features_masks is None
                      else global_put(np.asarray(features_masks), group_shard))
                lm = (None if labels_masks is None
                      else global_put(np.asarray(labels_masks), group_shard))
            with self.timer.phase("step"):
                # the scan body splits the carried rng exactly as sequential
                # _fit_periodic splits net._rng each step — seed the carry
                # with net._rng itself and write back the final carry so a
                # later sequential step continues the same chain
                self._replica, net._rng, losses = fn(
                    self._replica, net._rng, xs, ys, fm, lm
                )
                losses = np.asarray(losses)  # host fetch = sync
        finally:
            if getattr(net, "_phase_timer", None) is self.timer:
                net._phase_timer = None
        # replay the sequential per-step bookkeeping so listeners observe
        # iteration/score in lockstep (reference IterationListener contract):
        # score updates at averaging boundaries when
        # report_score_after_averaging, else every step — then the callback
        F = self.averaging_frequency
        for j, loss in enumerate(losses):
            self.iteration += 1
            net.iteration += 1
            at_boundary = (phase + j + 1) % F == 0
            if (at_boundary and self.report_score_after_averaging) or (
                    not self.report_score_after_averaging):
                net._last_loss = loss
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration, loss)
        # propagate trained weights into the wrapped net, exactly as fit()
        # does at the end of its epochs (net.output/save must see them)
        self._finalize_periodic()
        return losses

    # --------------------------------------------------------- periodic mode
    def _setup_periodic(self):
        net = self.net
        net.init()
        n = self.workers
        self._replica = (
            _stack_tree(net.params, n),
            _stack_tree(net.opt_state, n),
            _stack_tree(net.state, n),
        )
        # leading replica axis over the batch devices; the layout REFUSES
        # this placement for tp/expert layouts (stacked replicas would
        # silently drop the declared param sharding — the constructor
        # guards the same combination)
        shard0 = self.layout.replica_sharding()
        self._replica = jax.tree_util.tree_map(
            lambda a: global_put(a, shard0), self._replica)

        tx = net._tx
        ls = getattr(net.conf, "loss_scale", None)

        def one_step(params, opt_state, state, x, y, rng, labels_mask, features_mask):
            from ..nn.engine import apply_step  # noqa: PLC0415

            def loss_of(p):
                loss, new_state, _ = net._loss(
                    p, state, x, y, rng, True, labels_mask, features_mask
                )
                return loss, new_state

            loss, new_state, _, _, new_opt, new_params = apply_step(
                loss_of, tx, ls, params, opt_state)
            return new_params, new_opt, new_state, loss

        # vmap over the replica axis: every replica steps independently in one
        # XLA program; sharding over "data" keeps each on its own device.
        self._one_step = one_step  # pure, un-jitted: reused by the scanned loop
        # replicas are stacked and sharded over the data axes: a partitioned
        # program, where Mosaic kernels cannot run (ops.kernel_select)
        self._vstep = jax.jit(kernel_select.scoped_for_layout(
            jax.vmap(one_step), self.layout))

        avg_upd = self.average_updaters

        def average(params, opt_state, state):
            """averageAndPropagate: mean over replicas, broadcast back."""
            p = _stack_tree(_mean_tree(params), n)
            o = _stack_tree(_mean_tree(opt_state), n) if avg_upd else opt_state
            s = _stack_tree(_mean_tree(state), n)
            return p, o, s

        self._avg_pure = average  # pure, un-jitted: reused by the scanned loop
        self._avg_fn = jax.jit(average)
        self._periodic_multi_cache = None  # closures above changed

    def _fit_periodic(self, stacked_ds) -> None:
        """stacked_ds features/labels: [workers, batch, ...] — one independent
        step per replica (round-robin dispatch parity, ParallelWrapper.java:141-151)."""
        net = self.net
        params, opt_state, state = self._replica
        net._rng, k = jax.random.split(net._rng)
        keys = jax.random.split(k, self.workers)
        shard0 = self.layout.replica_sharding()
        with self.timer.phase("data"):
            x = global_put(np.asarray(stacked_ds.features), shard0)
            y = global_put(np.asarray(stacked_ds.labels), shard0)
            # Masks ride the replica axis too — each replica's loss must see
            # its own masks exactly as its net.fit would (round-1 weak #4:
            # periodic mode silently computed unmasked loss). None passes
            # through vmap as an empty pytree.
            lm = global_put(getattr(stacked_ds, "labels_mask", None), shard0)
            fm = global_put(getattr(stacked_ds, "features_mask", None), shard0)
        with self.timer.phase("step"):
            params, opt_state, state, losses = self._vstep(
                params, opt_state, state, x, y, keys, lm, fm
            )
        self.iteration += 1
        net.iteration += 1
        if self.iteration % self.averaging_frequency == 0:
            with self.timer.phase("average"):
                params, opt_state, state = self._avg_fn(params, opt_state, state)
            if self.report_score_after_averaging:
                net._last_loss = jnp.mean(losses)
        if not self.report_score_after_averaging:
            net._last_loss = jnp.mean(losses)
        self._replica = (params, opt_state, state)
        for lst in net.listeners:
            lst.iteration_done(net, net.iteration, jnp.mean(losses))

    def _finalize_periodic(self):
        """Propagate averaged replica params back into the wrapped net."""
        if self._replica is None:
            return
        params, opt_state, state = self._avg_fn(*self._replica)
        net = self.net
        net.params = _mean_tree(params)
        net.opt_state = _mean_tree(opt_state)
        net.state = _mean_tree(state)

    # ------------------------------------------------------------------- fit
    def fit(self, data, epochs: int = 1) -> "ParallelWrapper":
        """Reference: ParallelWrapper.fit(DataSetIterator):317. Minibatches are
        pulled through async prefetch and grouped ``workers`` at a time."""
        sync = self.averaging_frequency <= 1
        if sync and not self._sync_ready:
            self._setup_sync()
        if not sync and self._replica is None:
            self._setup_periodic()
        try:
            self._fit_epochs(data, epochs, sync)
        finally:
            # Detach even on mid-fit failure: a later plain net.fit must not
            # report this wrapper's frozen breakdown as the new run's timings.
            if getattr(self.net, "_phase_timer", None) is self.timer:
                self.net._phase_timer = None
            if getattr(self.net, "telemetry", None) is not None:
                self.net.telemetry.flush()  # drain a partial K-window
        return self

    def _fit_epochs(self, data, epochs: int, sync: bool) -> None:
        from ..datasets.iterators import as_iterator, AsyncDataSetIterator

        for _ in range(epochs):
            it = as_iterator(data)
            if hasattr(it, "reset"):
                it.reset()
            if getattr(it, "prefetch_supported", False):
                it = AsyncDataSetIterator(it, queue_size=self.prefetch_buffer)
            group_size = self.workers
            if self.data_is_local:
                group_size = self.workers // jax.process_count()
            group: List[Any] = []
            for ds in it:
                group.append(ds)
                if len(group) < group_size:
                    continue
                if sync:
                    self._fit_sync(_concat_group(group))
                else:
                    self._fit_periodic(_stack_group(group))
                group = []
            if group:
                # Trailing partial group. Sync mode can still shard it as one
                # global batch when the example count divides the data axes;
                # otherwise (and always in periodic mode, which needs exactly
                # one batch per replica) it is dropped — warn instead of the
                # silent drop that made small iterators train zero steps.
                import warnings  # noqa: PLC0415

                partial = _concat_group(group)
                if self.data_is_local:
                    # A trailing partial cannot train here: each process
                    # decides locally, and a process entering the collective
                    # step alone (or with a different local size) hangs or
                    # mis-assembles the global batch. Dropping it locally is
                    # only safe when every host drops the same way — hosts
                    # MUST feed identical full-group counts (see the
                    # constructor note); this warning may print on a
                    # different host than the one that then hangs.
                    warnings.warn(
                        "ParallelWrapper(data_is_local=True) dropped a "
                        f"trailing partial group of {len(group)} local "
                        "minibatch(es); ALL hosts must feed identical step "
                        "counts or the cluster deadlocks",
                        stacklevel=2,
                    )
                elif sync and partial.num_examples() % self.workers == 0:
                    if partial.num_examples() != self.workers * (
                        group[0].num_examples()
                    ) and self.iteration > len(group):
                        warnings.warn(
                            "ParallelWrapper: trailing partial group trains at "
                            f"a new global batch shape ({partial.num_examples()} "
                            "examples) — XLA compiles the train step a second "
                            "time for this shape",
                            stacklevel=2,
                        )
                    self._fit_sync(partial)
                elif sync:
                    warnings.warn(
                        "ParallelWrapper dropped a trailing partial group: its "
                        f"{partial.num_examples()} examples do not divide the "
                        f"{self.workers}-way data sharding; pad the final "
                        "minibatches or size the epoch accordingly",
                        stacklevel=2,
                    )
                else:
                    warnings.warn(
                        f"ParallelWrapper dropped a trailing partial group of "
                        f"{len(group)} minibatch(es) (periodic mode needs "
                        f"exactly {self.workers}, one per replica)",
                        stacklevel=2,
                    )
        if not sync:
            self._finalize_periodic()

    def average_model(self):
        """Current averaged model params (periodic mode) or the net's params."""
        if self._replica is not None:
            return _mean_tree(self._replica[0])
        return self.net.params


def _concat_group(group):
    from ..datasets.iterators import DataSet

    return DataSet(
        np.concatenate([np.asarray(d.features) for d in group]),
        np.concatenate([np.asarray(d.labels) for d in group]),
        _cat_masks([getattr(d, "features_mask", None) for d in group]),
        _cat_masks([getattr(d, "labels_mask", None) for d in group]),
    )


def _stack_group(group):
    from ..datasets.iterators import DataSet

    return DataSet(
        np.stack([np.asarray(d.features) for d in group]),
        np.stack([np.asarray(d.labels) for d in group]),
        _merge_masks([getattr(d, "features_mask", None) for d in group], np.stack),
        _merge_masks([getattr(d, "labels_mask", None) for d in group], np.stack),
    )


def _merge_masks(masks, combine):
    if all(m is None for m in masks):
        return None
    if any(m is None for m in masks):
        raise ValueError("mixed masked/unmasked minibatches in one group")
    return combine([np.asarray(m) for m in masks])


def _cat_masks(masks):
    return _merge_masks(masks, np.concatenate)
