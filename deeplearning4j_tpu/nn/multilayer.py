"""MultiLayerNetwork: sequential model with a jit-compiled train step.

TPU-native equivalent of the reference's ``MultiLayerNetwork``
(nn/multilayer/MultiLayerNetwork.java — init():382, fit(DataSetIterator):917,
backprop():988, feedForward:652, output:1505; call stack SURVEY.md §3.1).

Architecture differences, by design:
- The reference's Solver/ConvexOptimizer/StepFunction tier (optimize/solvers/*)
  collapses into ONE pure jitted ``train_step``: value_and_grad → optax update →
  apply_updates. XLA traces it once and fuses the whole step (forward, backward,
  updater) into a single device program — the per-op dispatch boundary that
  dominated the reference's hot loop does not exist.
- Flattened param vector + gradient views (initGradientsView:470) → param
  pytree ``(dict_per_layer, ...)``.
- ``backpropGradient`` per layer → ``jax.grad`` end to end.
- Mutable layer state (BN running stats, RNN streaming state) is an explicit
  state pytree threaded through ``apply``, never hidden mutation.
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..telemetry.spans import span
from .conf.multi_layer import MultiLayerConfiguration
from .conf.inputs import InputType
from .updaters import (optimizer_update, scaled_loss, unscale_grads,
                       unscale_loss)


def _cast_params(conf_dtype: str, params):
    """Mixed precision: master params stay f32; bf16 compute keeps the MXU fed.

    The inverse combination is the bf16-storage/f32-compute precision
    policy (parallel/layout.py): ``params_dtype="bfloat16"`` under a
    float32 compute dtype stores/communicates bf16 leaves but upcasts them
    here, per step, so the forward/backward math (and the loss/psum
    accumulation downstream) runs in f32. Gradients transpose back through
    the cast and land in bf16 — half the all-reduce bytes."""
    if conf_dtype == "bfloat16":
        return jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16) if jnp.issubdtype(a.dtype, jnp.floating) else a,
            params,
        )
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if getattr(a, "dtype", None) == jnp.bfloat16 else a, params)


def _cast_layer_params(conf_dtype: str, layer, params):
    """One layer's params for compute: :func:`_cast_params`, less what the
    layer declares under ``FLOAT32_PARAMS`` (a state-space layer's decay
    parameters, a router's weights), which reach it as the master holds
    them and not rounded to bfloat16 on the way. ``ComputationGraph`` casts
    through here; a ``MultiLayerNetwork`` rounds every leaf alike."""
    cast = _cast_params(conf_dtype, params)
    keep = getattr(layer, "FLOAT32_PARAMS", ())
    if conf_dtype != "bfloat16" or not keep:
        return cast
    return {**cast, **{k: params[k] for k in keep if k in params}}


def _carry_params_dtype(conf, params):
    """Apply conf.params_dtype to freshly-initialized params (the round-5
    weight-copy lever): "bfloat16" carries params in the compute dtype;
    None/"float32" keeps the f32 master convention. Shared by
    MultiLayerNetwork.init and ComputationGraph.init."""
    pd = getattr(conf, "params_dtype", None)
    if pd in (None, "float32"):
        return params
    if pd != "bfloat16":
        raise ValueError(
            f"params_dtype={pd!r} is not supported (use None, 'float32', "
            "or 'bfloat16')"
        )
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)


def _cast_input(conf_dtype: str, params, x):
    """Align one input array with the compute dtype of (already-cast) params."""
    if conf_dtype == "bfloat16":
        x = jnp.asarray(x)
        return x.astype(jnp.bfloat16) if jnp.issubdtype(x.dtype, jnp.floating) else x
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        leaf = jax.tree_util.tree_leaves(params)
        if leaf:
            x = jnp.asarray(x).astype(leaf[0].dtype)
    return x


def _compute_cast(conf_dtype: str, params, x):
    """Cast params and one input for compute (see _cast_params/_cast_input)."""
    params = _cast_params(conf_dtype, params)
    return params, _cast_input(conf_dtype, params, x)


def _format_summary_table(rows, total: int) -> str:
    """Fixed-width table + totals footer, shared by both summary() methods."""
    widths = [max(len(r[c]) for r in rows) for c in range(len(rows[0]))]
    lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths)).rstrip()
             for r in rows]
    lines.insert(1, "-" * max(len(l) for l in lines))
    lines.append(f"Total params: {total:,}")
    return "\n".join(lines)


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


class MultiLayerNetwork:
    """Sequential network over a :class:`MultiLayerConfiguration`."""

    def __init__(self, conf: MultiLayerConfiguration):
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
        self._rnn_state = None  # streaming rnnTimeStep state, one entry per layer
        self._rnn_step_fn = None
        self._grad_stats_step = None
        self._last_grads = None  # populated when a listener needs_gradients
        self._last_updates = None
        self.telemetry = None  # telemetry.Telemetry session (set_telemetry)
        self._telemetry_step = None
        self._cm_token = None  # compile-manager owner token (one per init())
        self.staged_steps_total = 0  # optimizer steps run via fit_on_device

    # ------------------------------------------------------------------ init
    def init(self, params=None, force: bool = False) -> "MultiLayerNetwork":
        """Initialize params/state/updater (reference: MultiLayerNetwork.init():382)."""
        if self.params is not None and not force and params is None:
            return self
        with span("dl4j.net.init", net="mln"):
            input_types = self.conf.layer_input_types()
            key = jax.random.PRNGKey(self.conf.seed)
            keys = jax.random.split(key, len(self.conf.layers))
            if params is None:
                params = tuple(
                    layer.init_params(k, it)
                    for layer, k, it in zip(self.conf.layers, keys, input_types)
                )
            params = _carry_params_dtype(self.conf, params)
            self.params = params
            self.state = tuple(
                layer.init_state(it)
                for layer, it in zip(self.conf.layers, input_types)
            )
            self._tx = self.conf.updater.build()
            self.opt_state = self._tx.init(self.params)
            self.iteration = 0
            self._invalidate_compiled()
        return self

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

    def _kernel_scoped(self, fn):
        """``fn`` traced with kernel selection told whether GSPMD will
        partition the program: a net living on a multi-device layout cannot
        run Mosaic kernels outside a shard_map (ops.kernel_select
        .partitioned_program). AOT programs get the same scope from the
        compile manager, by their argument shardings."""
        from ..ops import kernel_select

        return kernel_select.scoped_for_layout(
            fn, getattr(self, "_mesh_layout", None))

    def _step_callable(self, variant: str = "plain"):
        """The per-batch jitted step, deduplicated through the process-wide
        compile manager (one LRU holds every executable of every net, so
        long-running jobs stay bounded)."""
        from ..runtime.compile_manager import get_compile_manager

        flags = {"grad_stats": {"with_grad_stats": True},
                 "telemetry": {"with_telemetry": True}}.get(variant, {})
        return get_compile_manager().callable(
            (self._cm_token, "mln_train_step", variant),
            lambda: self._build_train_step(**flags))

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    def set_telemetry(self, telemetry) -> "MultiLayerNetwork":
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
        """Per-layer HBM attribution (param/grad/optimizer/activation bytes)
        at a batch size or example shape — pure ``jax.eval_shape``, nothing
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

    def summary(self) -> str:
        """Layer table: name, in/out types, param count (reference:
        MultiLayerNetwork.summary())."""
        self.init()
        its = self.conf.layer_input_types()
        rows = [("idx", "layer", "in", "out", "params")]
        total = 0
        for i, (layer, it) in enumerate(zip(self.conf.layers, its)):
            n = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(self.params[i]))
            total += n
            rows.append((str(i), type(layer).__name__, str(it),
                         str(layer.get_output_type(it)), f"{n:,}"))
        return _format_summary_table(rows, total)

    # ------------------------------------------------------- functional core
    def _forward(
        self, params, x, state, train: bool, rng, *,
        upto: Optional[int] = None, features_mask=None, rnn_state=None,
    ):
        """Forward pass through layers [0, upto). Returns (x, new_state, new_rnn).

        ``features_mask`` ([batch, time] for padded sequences) reaches every
        layer's ``apply`` (reference: Layer.setMaskArray / feedForward masking).
        ``rnn_state`` (tuple per layer, {} for non-recurrent) threads LSTM h/c
        across TBPTT segments / rnnTimeStep calls (reference:
        MultiLayerNetwork.rnnActivateUsingStoredState).
        """
        layers = self.conf.layers
        n = len(layers) if upto is None else upto
        params, x = _compute_cast(self.conf.dtype, params, x)
        rngs = (
            jax.random.split(rng, len(layers)) if rng is not None else [None] * len(layers)
        )
        new_state = list(state)
        new_rnn = list(rnn_state) if rnn_state is not None else None
        for i in range(n):
            with jax.named_scope(self.layer_scope(i)):
                pre = self.conf.preprocessors.get(i)
                if pre is not None:
                    x = pre.apply(x)
                if new_rnn is not None and new_rnn[i]:
                    x, new_rnn[i] = layers[i].apply_seq(
                        params[i], x, new_rnn[i], mask=features_mask,
                        train=train, rng=rngs[i]
                    )
                elif train and self.conf.remat:
                    # per-layer rematerialization (jax.checkpoint): keep only
                    # layer-boundary activations for the backward pass and
                    # recompute each layer's internals — HBM for FLOPs, the
                    # standard TPU trade at memory-bound batch sizes
                    layer = layers[i]

                    def _ck(p_, x_, st_, rng_, m_, _layer=layer):
                        return _layer.apply(p_, x_, st_, train=True, rng=rng_,
                                            mask=m_)

                    x, new_state[i] = jax.checkpoint(_ck)(
                        params[i], x, state[i], rngs[i], features_mask
                    )
                else:
                    x, new_state[i] = layers[i].apply(
                        params[i], x, state[i], train=train, rng=rngs[i],
                        mask=features_mask
                    )
        return x, tuple(new_state), (tuple(new_rnn) if new_rnn is not None else None)

    def layer_scope(self, i: int) -> str:
        """The ``jax.named_scope`` of layer ``i``'s operations in every
        compiled program: its configured name, else ``layer<i>``."""
        return self.conf.layers[i].name or f"layer{i}"

    def _loss(self, params, state, x, y, rng, train: bool, labels_mask=None,
              features_mask=None, rnn_state=None):
        """Loss + regularization (reference: computeGradientAndScore + calcL1/L2)."""
        layers = self.conf.layers
        out_idx = len(layers) - 1
        fwd_rng, out_rng = (
            jax.random.split(rng) if rng is not None else (None, None)
        )
        h, new_state, new_rnn = self._forward(
            params, x, state, train, fwd_rng, upto=out_idx, features_mask=features_mask,
            rnn_state=rnn_state,
        )
        out_layer = layers[out_idx]
        if not hasattr(out_layer, "compute_loss"):
            raise ValueError(f"Last layer {type(out_layer).__name__} is not an output layer")
        with jax.named_scope("loss"):
            with jax.named_scope(self.layer_scope(out_idx)):
                pre = self.conf.preprocessors.get(out_idx)
                if pre is not None:
                    h = pre.apply(h)
                h32 = h.astype(jnp.float32) if h.dtype == jnp.bfloat16 else h
                cast_p = params[out_idx]
                if self.conf.dtype == "bfloat16":
                    cast_p = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.float32), cast_p)
                loss = out_layer.compute_loss(cast_p, h32, y, labels_mask,
                                              train=train, rng=out_rng)
            reg = sum(
                (layer.regularization_loss(params[i]) for i, layer in enumerate(layers)),
                start=jnp.asarray(0.0),
            )
        return loss + reg, new_state, new_rnn

    def loss_fn(self, params, x, y, *, train: bool = False, state=None, rng=None,
                labels_mask=None, features_mask=None):
        """Pure scalar loss of params — the gradient-check entry point."""
        st = state if state is not None else self.state
        val, _, _ = self._loss(params, st, x, y, rng, train, labels_mask, features_mask)
        return val

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

        def dl4j_mln_train_step(params, opt_state, state, x, y, rng,
                                labels_mask, features_mask):
            def loss_of(p):
                loss, new_state, _ = self._loss(
                    p, state, x, y, rng, True, labels_mask, features_mask
                )
                return scaled_loss(loss, ls), new_state

            (loss, new_state), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            loss = unscale_loss(loss, ls)
            grads = unscale_grads(grads, ls)
            with jax.named_scope("optimizer_update"):
                updates, new_opt, new_params = optimizer_update(
                    tx, grads, opt_state, params)
            if with_grad_stats:
                return new_params, new_opt, new_state, loss, grads, updates
            if with_telemetry:
                from ..telemetry import device as _tdev  # noqa: PLC0415

                return (new_params, new_opt, new_state, loss,
                        _tdev.step_stats(loss, grads))
            return new_params, new_opt, new_state, loss

        from ..tune.knobs import donation_enabled

        donate = ((0, 1, 2) if jax.default_backend() != "cpu"
                  and donation_enabled() else ())
        return jax.jit(self._kernel_scoped(dl4j_mln_train_step),
                       donate_argnums=donate)

    # ------------------------------------------------- on-device multi-step
    def _build_multi_step(self, steps_cap: int, with_masks: bool = False,
                          with_telemetry: bool = False):
        """ONE device dispatch for a whole window of optimizer steps: a
        ``lax.fori_loop`` of the train step over batches staged in HBM
        (stacked ``[K, B, ...]``), cycling ``i % n_batches``.

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

        def dl4j_mln_staged(params, opt_state, state, rng, n_steps, n_batches,
                            xs, ys, xmasks, ymasks):
            from ..telemetry import device as _tdev  # noqa: PLC0415

            losses0 = jnp.zeros((steps_cap,), jnp.float32)
            mvecs0 = (jnp.zeros((steps_cap, _tdev.NUM_SLOTS), jnp.float32)
                      if with_telemetry else None)

            def body(i, carry):
                params, opt, st, rng, losses, mvecs = carry
                rng, step_key = jax.random.split(rng)
                idx = i % n_batches
                x = jax.lax.dynamic_index_in_dim(xs, idx, 0, keepdims=False)
                y = jax.lax.dynamic_index_in_dim(ys, idx, 0, keepdims=False)
                fm = (
                    jax.lax.dynamic_index_in_dim(xmasks, idx, 0, keepdims=False)
                    if with_masks and xmasks is not None else None
                )
                lm = (
                    jax.lax.dynamic_index_in_dim(ymasks, idx, 0, keepdims=False)
                    if with_masks and ymasks is not None else None
                )

                def loss_of(p):
                    loss, new_state, _ = self._loss(p, st, x, y, step_key, True, lm, fm)
                    return scaled_loss(loss, ls), new_state

                (loss, new_state), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
                loss = unscale_loss(loss, ls)
                grads = unscale_grads(grads, ls)
                with jax.named_scope("optimizer_update"):
                    updates, new_opt, new_params = optimizer_update(
                        tx, grads, opt, params)
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

        from ..tune.knobs import donation_enabled

        donate = ((0, 1, 2, 3) if jax.default_backend() != "cpu"
                  and donation_enabled() else ())
        return jax.jit(dl4j_mln_staged, donate_argnums=donate)

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
        key = (self._cm_token, "mln_multi_step",
               signature(steps_cap, with_masks, with_telemetry, args))
        return cm.aot(
            key,
            lambda: self._build_multi_step(steps_cap, with_masks,
                                           with_telemetry),
            args,
        )

    def _staged_args(self, xs, ys, steps, features_masks, labels_masks,
                     real_batches):
        """Shared fit_on_device/warmup plumbing: validate, canonicalize
        scalars, and return ``(steps_cap, with_masks, n_steps, args)``."""
        from ..runtime.compile_manager import next_pow2

        num_slots = int(xs.shape[0])
        if num_slots == 0:
            raise ValueError("fit_on_device needs at least one staged batch")
        _check_staged_counts(num_slots, (("ys", ys),
                                         ("features_masks", features_masks),
                                         ("labels_masks", labels_masks)))
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

    def warmup(self, xs, ys, steps: Optional[int] = None,
               features_masks=None, labels_masks=None,
               real_batches: Optional[int] = None) -> "MultiLayerNetwork":
        """Compile-ahead: build the staged executable for this window shape
        WITHOUT running a step, so the first training dispatch pays zero
        compile latency. Arrays may be real data or ``jax.ShapeDtypeStruct``
        shells — only shapes/dtypes matter. The compile lands in the same
        cache (and telemetry counters) fit_on_device uses."""
        self.init()
        from ..tune import store as _tuned

        _tuned.auto_apply(self, "warmup")  # tuned telemetry cadence etc.
        def _shell(a):
            if a is None or isinstance(a, jax.ShapeDtypeStruct):
                return a
            a = np.asarray(a) if not hasattr(a, "dtype") else a
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        steps_cap, with_masks, _, args = self._staged_args(
            _shell(xs), _shell(ys), steps, _shell(features_masks),
            _shell(labels_masks), real_batches)
        self._staged_executable(steps_cap, with_masks,
                                self.telemetry is not None, args)
        return self

    def fit_on_device(self, xs, ys, steps: Optional[int] = None,
                      features_masks=None, labels_masks=None,
                      real_batches: Optional[int] = None) -> np.ndarray:
        """Run a whole training loop in ONE device dispatch (TPU-native fit).

        ``xs``/``ys``: stacked batches ``[K, B, ...]`` staged in HBM; step i
        trains on batch ``i % real_batches``. ``real_batches`` (default K)
        marks how many leading slots hold real data — trailing slots may be
        dummy padding from the bucketed stager and are never indexed.
        ``steps`` defaults to one pass over the real batches. Returns the
        per-step losses as a host array. Gradient-stats listeners are not
        served by this path (use :meth:`fit`); ``iteration_done`` fires per
        step afterwards with the device-computed losses.
        """
        self.init()
        if self.conf.backprop_type == "tbptt":
            raise ValueError("fit_on_device does not support TBPTT; use fit()")
        with span("dl4j.fit.dispatch", net="mln") as dispatch:
            with span("dl4j.fit.prepare"):
                xs = jnp.asarray(xs)
                ys = jnp.asarray(ys)
                fm = (None if features_masks is None
                      else jnp.asarray(features_masks))
                lm = None if labels_masks is None else jnp.asarray(labels_masks)
                tel = self.telemetry
                steps_cap, with_masks, n_steps, args = self._staged_args(
                    xs, ys, steps, fm, lm, real_batches)
                fn = self._staged_executable(steps_cap, with_masks,
                                             tel is not None, args)
            slots, batch = int(xs.shape[0]), int(xs.shape[1])
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
            elapsed = time.perf_counter() - t0
            if tel is not None:
                if tel.flight is not None:
                    # ring the dispatch BEFORE on_staged reads the metrics —
                    # an anomaly found there auto-dumps, and the bundle
                    # should already show what was dispatched
                    tel.flight.record(
                        "staged_dispatch", net="mln", steps=int(n_steps),
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
            bucketing: bool = True) -> "MultiLayerNetwork":
        """Train (reference: MultiLayerNetwork.fit(DataSetIterator):917).

        ``data``: (x, y) tuple, a DataSet, or a DataSetIterator. Iterators are
        auto-wrapped in async prefetch (reference :920-924) unless already async.

        ``stage_on_device`` left unset auto-applies a matching TUNED.json
        staging window when the autopilot has tuned this model (tune/store.py)
        and otherwise trains per-batch; an explicit value — including 0 —
        always wins.

        ``stage_on_device=K`` (TPU fast path): buffer K batches, stack them
        in HBM, and run the whole window as ONE dispatch via
        :meth:`fit_on_device`, double-buffered (window i+1's host→device
        transfer overlaps window i's compute). With ``bucketing`` (default)
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
        """
        from ..datasets.iterators import DataSet, AsyncDataSetIterator, as_iterator

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

        for ep in range(epochs):
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self, self.epoch)
            it = as_iterator(data)
            if hasattr(it, "reset"):
                it.reset()  # reference resets the iterator each epoch (fit:917)
            if getattr(it, "prefetch_supported", False):
                it = AsyncDataSetIterator(it)
            if stage > 1:
                self._fit_epoch_staged(it, stage, bucketing)
            else:
                for ds in it:
                    self._fit_batch(ds)
            self.epoch += 1
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self, self.epoch)
        if self.telemetry is not None:
            self.telemetry.flush()  # drain a partial K-window at fit end
        return self

    def _pad_examples_ok(self) -> bool:
        """Row padding is exact only for per-example models; batch statistics
        (BatchNormalization) couple rows, so such models keep exact batch
        sizes (window padding with dummy slots stays on — never executed)."""
        from .layers.normalization import BatchNormalization

        return not any(isinstance(l, BatchNormalization)
                       for l in self.conf.layers)

    def _fit_epoch_staged(self, it, stage: int, bucketing: bool = True) -> None:
        """Stage windows of ``stage`` batches per fit_on_device dispatch via
        the bucketed planner (datasets/bucketing.py), double-buffered: while
        window i executes on device, window i+1 is host-stacked and
        ``jax.device_put`` (async) so its H2D transfer overlaps compute.
        Unstageable batches train through the ordinary per-batch step, in
        stream order."""
        from ..datasets.bucketing import BucketedStager

        stager = BucketedStager(stage, bucketing=bucketing,
                                pad_examples=self._pad_examples_ok())

        def normalize(ds):
            return ([np.asarray(ds.features)], [np.asarray(ds.labels)],
                    [getattr(ds, "features_mask", None)],
                    [getattr(ds, "labels_mask", None)])

        def to_device(win):
            put = jax.device_put  # async: overlaps the pending dispatch
            win.features = [put(a) for a in win.features]
            win.labels = [put(a) for a in win.labels]
            if win.features_masks is not None:
                win.features_masks = [None if m is None else put(m)
                                      for m in win.features_masks]
            if win.labels_masks is not None:
                win.labels_masks = [None if m is None else put(m)
                                    for m in win.labels_masks]
            return win

        def dispatch(win):
            self.fit_on_device(
                win.features[0], win.labels[0], steps=win.n_real,
                features_masks=(None if win.features_masks is None
                                else win.features_masks[0]),
                labels_masks=(None if win.labels_masks is None
                              else win.labels_masks[0]),
                real_batches=win.n_real,
            )

        pending = None
        for kind, payload in stager.plan(it, normalize):
            if kind == "window":
                staged = to_device(payload)
                if pending is not None:
                    dispatch(pending)
                pending = staged
            else:
                if pending is not None:
                    dispatch(pending)
                    pending = None
                self._fit_batch(payload)
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
        self.last_batch_size = int(ds.features.shape[0])
        # host-side reference (no copy), kept ONLY while a listener needs it:
        # ConvolutionalIterationListener re-runs the forward on this batch
        # (reference: Model.setInput/input()). Unconditional retention would
        # pin one full batch per net for the net's lifetime.
        if any(getattr(lst, "needs_input", False) for lst in self.listeners):
            self._last_input = ds.features
        else:
            self._last_input = None
        if (
            self.conf.backprop_type == "tbptt"
            and np.ndim(ds.features) == 3
        ):
            self._fit_tbptt(ds)
            return
        self._rng, step_key = jax.random.split(self._rng)
        tel = self.telemetry
        mvec = None
        if self._wants_grad_stats():
            if self._grad_stats_step is None:
                self._grad_stats_step = self._step_callable("grad_stats")
            (self.params, self.opt_state, self.state, loss,
             self._last_grads, self._last_updates) = self._grad_stats_step(
                self.params, self.opt_state, self.state, ds.features, ds.labels,
                step_key,
                getattr(ds, "labels_mask", None), getattr(ds, "features_mask", None),
            )
            if tel is not None:
                # grads already left the program for StatsListener; reduce
                # them eagerly (async dispatch, still no host sync)
                from ..telemetry import device as _tdev  # noqa: PLC0415

                mvec = _tdev.step_stats(loss, self._last_grads)
        elif tel is not None:
            if self._telemetry_step is None:
                self._telemetry_step = self._step_callable("telemetry")
            (self.params, self.opt_state, self.state, loss, mvec) = \
                self._telemetry_step(
                    self.params, self.opt_state, self.state, ds.features,
                    ds.labels, step_key,
                    getattr(ds, "labels_mask", None),
                    getattr(ds, "features_mask", None),
                )
        else:
            self.params, self.opt_state, self.state, loss = self._train_step(
                self.params, self.opt_state, self.state, ds.features, ds.labels,
                step_key,
                getattr(ds, "labels_mask", None), getattr(ds, "features_mask", None),
            )
        self._last_loss = loss
        self.iteration += 1
        if tel is not None and mvec is not None:
            tel.on_step(self.iteration, mvec)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, loss)
        # listeners have copied what they need; don't pin ~2x model size of
        # gradient+update buffers in HBM until the next instrumented step
        self._last_grads = None
        self._last_updates = None

    # ---------------------------------------------------------------- TBPTT
    def _init_rnn_states(self, batch: int):
        """Per-layer streaming state tuple ({} for stateless layers)."""
        return tuple(
            layer.init_recurrent_state(batch)
            if hasattr(layer, "init_recurrent_state") and layer.is_recurrent
            else {}
            for layer in self.conf.layers
        )

    def _build_tbptt_step(self):
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)
        back_len = int(self.conf.tbptt_back_length or 0)

        def dl4j_mln_tbptt_step(params, opt_state, state, rnn, x, y, rng,
                                labels_mask, features_mask):
            seg_len = x.shape[1]
            k = seg_len if back_len <= 0 else min(back_len, seg_len)
            if k < seg_len:
                # tbptt_back_length < fwd_length: the first seg_len-k steps
                # evolve hidden state (and BN stats) but contribute no
                # gradient — the reference's backward loop caps at
                # tbpttBackwardLength (LSTMHelpers.backpropGradientHelper),
                # discarding epsilons from earlier outputs entirely.
                split = seg_len - k
                pre_rng, rng = jax.random.split(rng)
                fm_pre = None if features_mask is None else features_mask[:, :split]
                _, state_in, rnn_in = jax.lax.stop_gradient(
                    self._forward(
                        params, x[:, :split], state, True, pre_rng,
                        upto=len(self.conf.layers) - 1,
                        features_mask=fm_pre, rnn_state=rnn,
                    )
                )
                x_g, y_g = x[:, split:], y[:, split:]
                lm_g = None if labels_mask is None else labels_mask[:, split:]
                fm_g = None if features_mask is None else features_mask[:, split:]
            else:
                x_g, y_g, lm_g, fm_g = x, y, labels_mask, features_mask
                state_in, rnn_in = state, rnn

            def loss_of(p):
                loss, new_state, new_rnn = self._loss(
                    p, state_in, x_g, y_g, rng, True, lm_g, fm_g, rnn_state=rnn_in
                )
                return scaled_loss(loss, ls), (new_state, new_rnn)

            (loss, (new_state, new_rnn)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            loss = unscale_loss(loss, ls)
            grads = unscale_grads(grads, ls)
            updates, new_opt, new_params = optimizer_update(
                tx, grads, opt_state, params)
            # Segment boundary IS the gradient-truncation boundary: the returned
            # h/c re-enter the next jit call as constants (reference:
            # MultiLayerNetwork.doTruncatedBPTT:1080 rnnUpdateStateWithTBPTTState).
            new_rnn = jax.lax.stop_gradient(new_rnn)
            return new_params, new_opt, new_state, new_rnn, loss

        return jax.jit(self._kernel_scoped(dl4j_mln_tbptt_step))

    def _fit_tbptt(self, ds) -> None:
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
        x, y = np.asarray(ds.features), np.asarray(ds.labels)
        fmask = getattr(ds, "features_mask", None)
        lmask = getattr(ds, "labels_mask", None)
        T, L = x.shape[1], self.conf.tbptt_fwd_length
        rnn = self._init_rnn_states(x.shape[0])
        for t0 in range(0, T, L):
            seg = slice(t0, t0 + min(L, T - t0))
            self._rng, step_key = jax.random.split(self._rng)
            (self.params, self.opt_state, self.state, rnn, loss) = self._tbptt_step(
                self.params, self.opt_state, self.state, rnn,
                x[:, seg], y[:, seg], step_key,
                None if lmask is None else lmask[:, seg],
                None if fmask is None else fmask[:, seg],
            )
            self._last_loss = loss
            self.iteration += 1
            if self.telemetry is not None:
                # TBPTT's step returns no gradient view; record loss +
                # finiteness (grad norm reads 0 on this path)
                from ..telemetry import device as _tdev  # noqa: PLC0415

                self.telemetry.on_step(self.iteration, _tdev.step_stats(loss))
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, loss)

    # ------------------------------------------------------------- streaming
    def rnn_time_step(self, x, features_mask=None):
        """Stateful streaming inference (reference: MultiLayerNetwork.rnnTimeStep:2163).

        ``x``: [batch, features] (one step) or [batch, time, features]. LSTM
        h/c persist across calls until :meth:`rnn_clear_previous_state`.

        XLA shape note: single-step 2-D input is normalized to [B, 1, F] so
        streaming always reuses ONE traced program; multi-step calls compile
        once per distinct (batch, T). For variable-length streaming, bucket T
        — pad to a few fixed lengths (``datasets.iterators.pad_to_bucket``)
        and pass ``features_mask`` ([batch, time]): masked steps hold LSTM
        h/c, so the streaming state after the call is exactly the state
        after the sequence's REAL steps, and only len(buckets) programs ever
        compile.

        Fast path (default): routed through ``runtime/inference.py`` — the
        time axis pow2-buckets with an auto-synthesized mask, the program is
        AOT-admitted via the compile manager, and the RNN state + input
        buffers are donated on accelerators. ``DL4JTPU_INFER=legacy``
        restores the per-net ``jax.jit`` dispatch below.
        """
        from ..runtime import inference as _inf

        if _inf.fast_path_enabled():
            return _inf.mln_rnn_step(self, x, features_mask=features_mask)
        self.init()
        x = jnp.asarray(x)
        single_step = x.ndim == 2
        if single_step:
            x = x[:, None, :]
        if features_mask is not None:
            features_mask = jnp.asarray(features_mask)
        if self._rnn_state is None or (
            jax.tree_util.tree_leaves(self._rnn_state)
            and jax.tree_util.tree_leaves(self._rnn_state)[0].shape[0] != x.shape[0]
        ):
            self._rnn_state = self._init_rnn_states(x.shape[0])
        if self._rnn_step_fn is None:
            self._rnn_step_fn = jax.jit(
                lambda params, state, rnn, x, mask: self._forward(
                    params, x, state, False, None, features_mask=mask,
                    rnn_state=rnn,
                )[::2]  # (out, new_rnn) — per-token dispatch stays on device
            )
        out, self._rnn_state = self._rnn_step_fn(
            self.params, self.state, self._rnn_state, x, features_mask
        )
        if single_step and out.ndim == 3:
            out = out[:, 0, :]
        return out

    def rnn_clear_previous_state(self) -> None:
        """Reference: MultiLayerNetwork.rnnClearPreviousState."""
        self._rnn_state = None

    def rnn_get_previous_state(self, layer_idx: int):
        """Reference: MultiLayerNetwork.rnnGetPreviousState."""
        if self._rnn_state is None:
            return None
        st = self._rnn_state[layer_idx]
        return st if st else None

    def rnn_set_previous_state(self, layer_idx: int, state_dict) -> None:
        """Reference: MultiLayerNetwork.rnnSetPreviousState."""
        if self._rnn_state is None:
            raise ValueError("No streaming state; call rnn_time_step first")
        st = list(self._rnn_state)
        st[layer_idx] = state_dict
        self._rnn_state = tuple(st)

    # --------------------------------------------------------------- pretrain
    def pretrain(self, data, epochs: int = 1) -> "MultiLayerNetwork":
        """Layerwise unsupervised pretraining of AE/RBM/VAE layers
        (reference: MultiLayerNetwork.pretrain, MultiLayerNetwork.java:932-945:
        each pretrainable layer trains on the frozen activations of the stack
        below it)."""
        self.init()
        for i, layer in enumerate(self.conf.layers):
            if getattr(layer, "is_pretrain_layer", False):
                self.pretrain_layer(i, data, epochs)
        return self

    def pretrain_layer(self, layer_idx: int, data, epochs: int = 1) -> None:
        """Reference: MultiLayerNetwork.pretrainLayer."""
        from ..datasets.iterators import as_iterator
        import optax as _optax

        self.init()
        layer = self.conf.layers[layer_idx]
        if not getattr(layer, "is_pretrain_layer", False):
            raise ValueError(f"layer {layer_idx} ({type(layer).__name__}) is not pretrainable")
        tx = self.conf.updater.build()
        opt_state = tx.init(self.params[layer_idx])

        def step(lp, opt, params_all, state, x, rng):
            h, _, _ = self._forward(params_all, x, state, False, None, upto=layer_idx)
            if h.ndim > 2:
                h = h.reshape(h.shape[0], -1)

            def loss_of(p):
                return layer.pretrain_loss(p, h, rng)

            loss, grads = jax.value_and_grad(loss_of)(lp)
            _, new_opt, new_lp = optimizer_update(tx, grads, opt, lp)
            return new_lp, new_opt, loss

        jstep = jax.jit(step)
        lp = self.params[layer_idx]
        for _ in range(epochs):
            it = as_iterator(data)
            if hasattr(it, "reset"):
                it.reset()
            for ds in it:
                self._rng, k = jax.random.split(self._rng)
                lp, opt_state, loss = jstep(
                    lp, opt_state, self.params, self.state, ds.features, k
                )
                self._last_loss = loss
        params = list(self.params)
        params[layer_idx] = lp
        self.params = tuple(params)
        # params object replaced: retire the generation's executables so the
        # next fit builds fresh ones (and the manager doesn't serve stale fns)
        self._invalidate_compiled()

    # -------------------------------------------------------------- inference
    def output(self, x, train: bool = False, features_mask=None):
        """Inference output (reference: MultiLayerNetwork.output:1505).

        Served by the AOT-bucketed inference fast path
        (``runtime/inference.py``): input dtype canonicalized at the
        boundary, rows/time padded to pow2 buckets with exact masked
        padding, executable admitted through the process-wide compile
        manager, result returned as a host array with the padding sliced
        off. ``DL4JTPU_INFER=legacy`` restores the per-net ``jax.jit``
        dispatch (device-array return)."""
        from ..runtime import inference as _inf

        self.init()
        if _inf.fast_path_enabled():
            return _inf.mln_output(self, x, features_mask=features_mask)
        if self._eval_forward is None:
            self._eval_forward = jax.jit(
                lambda params, state, x, fm: self._forward(
                    params, x, state, False, None, features_mask=fm
                )[0]
            )  # _forward returns (out, state, rnn); [0] unchanged
        return self._eval_forward(self.params, self.state, jnp.asarray(x), features_mask)

    def predict(self, x) -> np.ndarray:
        """Class indices (reference: MultiLayerNetwork.predict). The argmax
        is fused into the compiled inference executable — only int32 class
        indices cross the device boundary, never the full logits."""
        from ..runtime import inference as _inf

        if _inf.fast_path_enabled():
            return np.asarray(_inf.mln_output(self, x, argmax=True))
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def feed_forward(self, x, train: bool = False) -> List[jnp.ndarray]:
        """All layer activations (reference: feedForward:652)."""
        from ..runtime.inference import canonicalize_input

        self.init()
        acts = []
        # boundary canonicalization: f64/host-dtype inputs would otherwise
        # re-trace per dtype and promote every downstream op (DT200)
        cur = jnp.asarray(canonicalize_input(x, self.conf.dtype, self.params))
        params, cur = _compute_cast(self.conf.dtype, self.params, cur)
        for i, layer in enumerate(self.conf.layers):
            pre = self.conf.preprocessors.get(i)
            if pre is not None:
                cur = pre.apply(cur)
            cur, _ = layer.apply(params[i], cur, self.state[i], train=train, rng=None)
            acts.append(cur)
        return acts

    def score(self, dataset=None) -> float:
        """Loss on a dataset, or last training loss (reference: score())."""
        if dataset is None:
            return float(self._last_loss) if self._last_loss is not None else float("nan")
        self.init()
        val = self.loss_fn(self.params, dataset.features, dataset.labels)
        return float(val)

    def evaluate(self, data, top_n: int = 1):
        """Classification evaluation over an iterator (reference: MultiLayerNetwork.evaluate;
        top_n matches the reference's evaluate(iter, topN) top-N accuracy)."""
        from ..eval.evaluation import Evaluation
        from ..datasets.iterators import as_iterator

        ev = Evaluation(top_n=top_n)
        for ds in as_iterator(data):
            out = self.output(ds.features, features_mask=getattr(ds, "features_mask", None))
            # metadata (when the iterator collects it) flows into Prediction
            # records (reference: evaluate -> Evaluation metadata overload).
            # Time-series outputs flatten to B*T rows — per-example metadata
            # no longer aligns, so attribution is skipped for 3-D outputs.
            meta = getattr(ds, "example_metadata", None)
            if np.ndim(out) == 3:
                meta = None
            ev.eval(ds.labels, out, record_metadata=meta)
        return ev

    # ------------------------------------------------------------------ misc
    def clone(self) -> "MultiLayerNetwork":
        import copy

        other = MultiLayerNetwork(
            MultiLayerConfiguration.from_dict(self.conf.to_dict())
        )
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
