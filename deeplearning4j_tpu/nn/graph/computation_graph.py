"""ComputationGraph: DAG model with a jit-compiled train step.

Reference parity: nn/graph/ComputationGraph.java — init():286,
fit(MultiDataSet):743, feed-forward loop :1051-1060, backprop loop :1184-1205,
rnnTimeStep:1801 (call stack SURVEY.md §3.2).

TPU-native design: the topological forward is traced once into a single XLA
program; ``jax.grad`` replaces the reverse-topological doBackward/epsilon
accumulation entirely (epsilon fan-in "+=" is exactly what autodiff does for
shared subexpressions). Multi-output losses sum, as in the reference's score
aggregation across output layers.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...telemetry.spans import span
from ..multilayer import (
    _carry_params_dtype,
    _cast_input,
    _cast_layer_params,
    _format_summary_table,
)
from ..updaters import (optimizer_update, scaled_loss, unscale_grads,
                        unscale_loss)
from .vertices import LayerVertex


class ComputationGraph:
    """DAG network over a :class:`ComputationGraphConfiguration`."""

    def __init__(self, conf: "ComputationGraphConfiguration"):  # noqa: F821
        self.conf = conf
        self.params: Any = None
        self.state: Any = None
        self.opt_state: Any = None
        self.iteration: int = 0
        self.epoch: int = 0
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.seed)
        self._tx = None
        self._train_step = None
        self._eval_forward = None
        self._last_loss = None
        self._topo = conf.topological_order()
        self._rnn_state = None  # streaming rnnTimeStep state, one entry per vertex
        self._rnn_step_fn = None
        self._tbptt_step = None
        self._grad_stats_step = None
        self._last_grads = None  # populated when a listener needs_gradients
        self._last_updates = None
        self.telemetry = None  # telemetry.Telemetry session (set_telemetry)
        self._telemetry_step = None
        self._cm_token = None  # compile-manager owner token (one per init())
        self.staged_steps_total = 0  # optimizer steps run via fit_on_device

    # ------------------------------------------------------------------ init
    def init(self, params=None, force: bool = False) -> "ComputationGraph":
        if self.params is not None and not force and params is None:
            return self
        with span("dl4j.net.init", net="graph"):
            vit = self.conf.vertex_input_types()
            key = jax.random.PRNGKey(self.conf.seed)
            keys = jax.random.split(key, max(len(self._topo), 1))
            if params is None:
                params = {
                    name: self.conf.vertices[name].init_params(k, *vit[name])
                    for name, k in zip(self._topo, keys)
                }
            params = _carry_params_dtype(self.conf, params)
            self.params = params
            self.state = {
                name: self.conf.vertices[name].init_state(*vit[name])
                for name in self._topo
            }
            self._tx = self.conf.updater.build()
            self.opt_state = self._tx.init(self.params)
            self.iteration = 0
            self._invalidate_compiled()
        return self

    def _invalidate_compiled(self) -> None:
        """See MultiLayerNetwork._invalidate_compiled: retire this
        generation's executables from the compile manager and null the
        per-instance step handles (they close over self._tx)."""
        from ...runtime.compile_manager import get_compile_manager

        cm = get_compile_manager()
        if self._cm_token is not None:
            cm.drop_token(self._cm_token)
        self._cm_token = cm.new_token()
        self._train_step = None
        self._eval_forward = None
        self._tbptt_step = None
        self._rnn_step_fn = None
        self._rnn_state = None
        self._grad_stats_step = None
        self._telemetry_step = None

    def _kernel_scoped(self, fn):
        """``fn`` traced with kernel selection told whether GSPMD will
        partition the program: a net living on a multi-device layout cannot
        run Mosaic kernels outside a shard_map (ops.kernel_select
        .partitioned_program). AOT programs get the same scope from the
        compile manager, by their argument shardings."""
        from ...ops import kernel_select

        return kernel_select.scoped_for_layout(
            fn, getattr(self, "_mesh_layout", None))

    def _step_callable(self, variant: str = "plain"):
        """Per-batch jitted step via the process-wide compile manager (one
        bounded LRU across every net — see MultiLayerNetwork._step_callable)."""
        from ...runtime.compile_manager import get_compile_manager

        flags = {"grad_stats": {"with_grad_stats": True},
                 "telemetry": {"with_telemetry": True}}.get(variant, {})
        return get_compile_manager().callable(
            (self._cm_token, "graph_train_step", variant),
            lambda: self._build_train_step(**flags))

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def set_telemetry(self, telemetry) -> "ComputationGraph":
        """Attach a :class:`telemetry.Telemetry` session — see
        MultiLayerNetwork.set_telemetry (same K-step-fetch contract)."""
        self.telemetry = telemetry
        self._telemetry_step = None
        return self

    def _wants_grad_stats(self) -> bool:
        """See MultiLayerNetwork._wants_grad_stats — instrumented step only on
        iterations a listener will actually report."""
        nxt = self.iteration + 1
        return any(
            getattr(lst, "needs_gradients", False)
            and nxt % max(1, getattr(lst, "frequency", 1)) == 0
            for lst in self.listeners
        )

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params))

    def memory_report(self, batch_or_struct=None) -> dict:
        """Per-vertex HBM attribution at a batch size or example shapes
        (a list for multi-input graphs) — pure ``jax.eval_shape``. See
        :func:`deeplearning4j_tpu.telemetry.memory_report`."""
        from ...telemetry.memory import memory_report

        return memory_report(self, batch_or_struct)

    def preflight(self, batch_or_struct=None, **kw) -> dict:
        """Will this graph + batch fit in HBM? Raises
        :class:`~deeplearning4j_tpu.telemetry.MemoryPreflightError` naming
        the biggest consumers before any dispatch; returns the annotated
        memory report when it fits."""
        from ...telemetry.memory import preflight

        return preflight(self, batch_or_struct, **kw)

    def analyze_ir(self, batch_or_struct=None, **kw) -> dict:
        """DT2xx IR lint + static roofline cost model over this graph's real
        train step — ``jax.make_jaxpr`` over ShapeDtypeStruct shells, zero
        device dispatches. Returns ``{"findings": [...], "static_cost":
        {...}}``; suppress rules with ``ignore=("DT204", ...)``. With
        ``layout=MeshLayout(...)`` the DT3xx sharding-flow pass joins in
        (predicted collective census + communication roofline). See
        docs/static_analysis.md (DT2xx/DT3xx) and docs/distributed.md.
        """
        from ...analysis.ir_checks import check_network_ir

        return check_network_ir(self, batch_or_struct, **kw)

    def summary(self) -> str:
        """Vertex table in topological order: name, type, inputs, out type,
        param count (reference: ComputationGraph.summary())."""
        self.init()
        vit = self.conf.vertex_input_types()
        rows = [("vertex", "type", "inputs", "out", "params")]
        total = 0
        for name in self._topo:
            vertex = self.conf.vertices[name]
            n = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(self.params[name]))
            total += n
            out_t = vertex.get_output_type(*vit[name])
            vtype = (type(vertex.layer).__name__
                     if isinstance(vertex, LayerVertex) and vertex.layer is not None
                     else type(vertex).__name__)
            rows.append((name, vtype,
                         ",".join(self.conf.vertex_inputs[name]),
                         str(out_t), f"{n:,}"))
        return _format_summary_table(rows, total)

    # ------------------------------------------------------- functional core
    def _activations(self, params, inputs, state, train, rng, masks, rnn_state=None):
        """Run the topological forward; returns (acts, new_state, new_rnn).

        ``inputs``: list of arrays aligned with conf.network_inputs.
        ``masks``: dict network-input-name -> [b, t] mask (or None).
        ``rnn_state``: dict vertex-name -> recurrent h/c ({} for stateless),
        threading LSTM state across TBPTT segments / rnnTimeStep calls
        (reference: ComputationGraph.rnnActivateUsingStoredState).
        (reference: ComputationGraph feed-forward loop :1051-1060)
        """
        conf = self.conf
        params = {name: _cast_layer_params(
            conf.dtype, getattr(conf.vertices.get(name), "layer", None), p)
            for name, p in params.items()}
        cast = [_cast_input(conf.dtype, params, x) for x in inputs]
        acts: Dict[str, jnp.ndarray] = dict(zip(conf.network_inputs, cast))
        if masks is None:
            masks = {}
        # single-mask convenience: layers deep in the graph receive it as the
        # feature mask (the common one-recurrent-path case)
        feat_mask = None
        non_null = [m for m in masks.values() if m is not None]
        if len(non_null) == 1:
            feat_mask = non_null[0]
        vmasks = dict(masks)
        vmasks["features"] = feat_mask
        rngs = (
            jax.random.split(rng, len(self._topo)) if rng is not None
            else [None] * len(self._topo)
        )
        new_state = dict(state)
        new_rnn = dict(rnn_state) if rnn_state is not None else None
        for name, r in zip(self._topo, rngs):
            vertex = conf.vertices[name]
            ins = [acts[src] for src in conf.vertex_inputs[name]]
            # every operation of a vertex carries its configured name
            with jax.named_scope(name):
                if new_rnn is not None and new_rnn.get(name):
                    acts[name], new_rnn[name] = vertex.apply_seq(
                        params[name], ins, new_rnn[name], train=train, rng=r,
                        masks=vmasks
                    )
                elif train and conf.remat:
                    # per-vertex jax.checkpoint: keep only vertex-boundary
                    # activations for backward (see
                    # MultiLayerConfiguration.remat)
                    def _ck(p_, ins_, st_, r_, m_, _v=vertex):
                        return _v.apply(p_, ins_, st_, train=True, rng=r_,
                                        masks=m_)

                    acts[name], new_state[name] = jax.checkpoint(_ck)(
                        params[name], ins, state[name], r, vmasks
                    )
                else:
                    acts[name], new_state[name] = vertex.apply(
                        params[name], ins, state[name], train=train, rng=r,
                        masks=vmasks
                    )
        return acts, new_state, new_rnn

    def _forward(self, params, inputs, state, train, rng, masks=None, rnn_state=None):
        acts, new_state, new_rnn = self._activations(
            params, inputs, state, train, rng, masks, rnn_state
        )
        return [acts[o] for o in self.conf.network_outputs], new_state, new_rnn

    def _loss(self, params, state, inputs, labels, rng, train,
              labels_masks=None, masks=None, rnn_state=None):
        """Sum of output-layer losses + regularization
        (reference: ComputationGraph.computeGradientAndScore score accumulation)."""
        conf = self.conf
        acts_rng, out_rng = (
            jax.random.split(rng) if rng is not None else (None, None)
        )
        # forward over all non-output vertices; output-layer vertices consume
        # their input activations via compute_loss (pre-activation path for
        # fused stable softmax-xent, as in MultiLayerNetwork._loss)
        acts, new_state, new_rnn = self._activations(
            params, inputs, state, train, acts_rng, masks, rnn_state
        )
        total = jnp.asarray(0.0)
        out_rngs = (
            jax.random.split(out_rng, len(conf.network_outputs))
            if out_rng is not None else [None] * len(conf.network_outputs)
        )
        for i, out_name in enumerate(conf.network_outputs):
            vertex = conf.vertices[out_name]
            if not (isinstance(vertex, LayerVertex) and vertex.is_output_layer):
                raise ValueError(
                    f"Training output '{out_name}' is not an output layer vertex"
                )
            ins = [acts[src] for src in conf.vertex_inputs[out_name]]
            with jax.named_scope("loss"), jax.named_scope(out_name):
                h = vertex.pre_output_input(ins)
                h32 = h.astype(jnp.float32) if h.dtype == jnp.bfloat16 else h
                p = params[out_name]
                if conf.dtype == "bfloat16":
                    p = jax.tree_util.tree_map(
                        lambda a: a.astype(jnp.float32), p)
                lm = labels_masks[i] if labels_masks is not None else None
                total = total + vertex.layer.compute_loss(
                    p, h32, labels[i], lm, train=train, rng=out_rngs[i]
                )
        with jax.named_scope("loss"):
            reg = sum(
                (self.conf.vertices[n].regularization_loss(params[n])
                 for n in self._topo),
                start=jnp.asarray(0.0),
            )
        return total + reg, new_state, new_rnn

    def loss_fn(self, params, inputs, labels, *, train=False, state=None, rng=None,
                labels_masks=None, masks=None):
        """Pure scalar loss of params — the gradient-check entry point."""
        st = state if state is not None else self.state
        val, _, _ = self._loss(params, st, inputs, labels, rng, train, labels_masks, masks)
        return val

    # ------------------------------------------------------------- train step
    def _build_train_step(self, with_grad_stats: bool = False,
                          with_telemetry: bool = False):
        """Jitted step; ``with_grad_stats`` also returns gradient/update
        pytrees for StatsListener histograms, ``with_telemetry`` only the
        in-step-reduced metrics vector (see MultiLayerNetwork note)."""
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)

        def dl4j_graph_train_step(params, opt_state, state, inputs, labels,
                                  rng, labels_masks, masks):
            from ...telemetry import device as _tdev  # noqa: PLC0415

            # a counting layer's state holds the last step's counts here
            state = {n: _tdev.zero_layer_counters(s)
                     for n, s in state.items()}

            def loss_of(p):
                loss, new_state, _ = self._loss(
                    p, state, inputs, labels, rng, True, labels_masks, masks
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
                from ...telemetry import device as _tdev  # noqa: PLC0415

                return (new_params, new_opt, new_state, loss,
                        _tdev.step_stats(loss, grads))
            return new_params, new_opt, new_state, loss

        from ...tune.knobs import donation_enabled

        donate = ((0, 1, 2) if jax.default_backend() != "cpu"
                  and donation_enabled() else ())
        return jax.jit(self._kernel_scoped(dl4j_graph_train_step),
                       donate_argnums=donate)

    # ------------------------------------------------- on-device multi-step
    def _build_multi_step(self, steps_cap: int, with_masks: bool = False,
                          with_telemetry: bool = False):
        """ONE device dispatch for a window of steps — ``lax.fori_loop`` over
        batches staged in HBM (each input/label stacked ``[K, B, ...]``, step
        i uses batch ``i % n_batches``). See
        MultiLayerNetwork._build_multi_step: same RNG split chain as
        sequential ``_fit_batch`` (numerics identical to per-step dispatch)
        and device-scalar step/batch counts (changing them reuses one
        executable). ``xmasks``/``ymasks``: per-input features masks and
        per-output labels masks (None entries allowed), stacked ``[K, ...]``
        — the bucketed stager's padded batches flow through here.

        Layout-applied graphs pin output placements to the declared specs
        (see MultiLayerNetwork._staged_out_constraint — the ZeRO-1 updated-
        params drift fix)."""
        from ..multilayer import MultiLayerNetwork

        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)
        constrain = MultiLayerNetwork._staged_out_constraint(self)

        def dl4j_graph_staged(params, opt_state, state, rng, n_steps,
                              n_batches, xs_list, ys_list, xmasks, ymasks):
            from ...telemetry import device as _tdev  # noqa: PLC0415

            losses0 = jnp.zeros((steps_cap,), jnp.float32)
            mvecs0 = (jnp.zeros((steps_cap, _tdev.NUM_SLOTS), jnp.float32)
                      if with_telemetry else None)
            # what layers count, they count from the dispatch's start
            state = {n: _tdev.zero_layer_counters(s)
                     for n, s in state.items()}

            def pick(arr, idx):
                return jax.lax.dynamic_index_in_dim(arr, idx, 0,
                                                    keepdims=False)

            def body(i, carry):
                params, opt, st, rng, losses, mvecs = carry
                rng, step_key = jax.random.split(rng)
                idx = i % n_batches
                inputs = [pick(x, idx) for x in xs_list]
                labels = [pick(y, idx) for y in ys_list]
                masks = None
                lms = None
                # the mask branches test pytree STRUCTURE (None-ness) —
                # trace-static, not a traced value
                if with_masks and xmasks is not None and any(  # dl4jtpu: ignore[DT104]
                        m is not None for m in xmasks):
                    masks = {
                        name: (None if m is None else pick(m, idx))
                        for name, m in zip(self.conf.network_inputs, xmasks)
                    }
                if with_masks and ymasks is not None and any(  # dl4jtpu: ignore[DT104]
                        m is not None for m in ymasks):
                    lms = [None if m is None else pick(m, idx)
                           for m in ymasks]

                def loss_of(p):
                    loss, new_state, _ = self._loss(
                        p, st, inputs, labels, step_key, True, lms, masks
                    )
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

        from ...tune.knobs import donation_enabled

        donate = ((0, 1, 2, 3) if jax.default_backend() != "cpu"
                  and donation_enabled() else ())
        return jax.jit(dl4j_graph_staged, donate_argnums=donate)

    @staticmethod
    def _as_stage_list(value, n: int, kind: str):
        """Normalize a masks argument to a length-``n`` list (None entries
        allowed); a bare array is accepted for single-input/-output graphs."""
        if value is None:
            return None
        if not isinstance(value, (list, tuple)):
            value = [value]
        value = [None if v is None else v for v in value]
        if len(value) != n:
            raise ValueError(f"{kind} has {len(value)} entries, expected {n}")
        return list(value)

    def _staged_args(self, xs_list, ys_list, steps, fmasks, lmasks,
                     real_batches):
        """Validate + canonicalize (see MultiLayerNetwork._staged_args)."""
        from ..multilayer import _staged_dim0
        from ...runtime.compile_manager import next_pow2

        num_slots = _staged_dim0(xs_list[0])
        if num_slots == 0:
            raise ValueError("fit_on_device needs at least one staged batch")
        # dynamic_index_in_dim CLAMPS out-of-range indices — a K mismatch in
        # any input/label would silently pair the wrong batches
        for i, arr in enumerate(xs_list + ys_list):
            if _staged_dim0(arr) != num_slots:
                kind = "input" if i < len(xs_list) else "label"
                idx = i if i < len(xs_list) else i - len(xs_list)
                raise ValueError(
                    f"{kind} array {idx} stages "
                    f"{_staged_dim0(arr)} batches, expected {num_slots}"
                )
        for masks, kind in ((fmasks, "features mask"), (lmasks, "labels mask")):
            for i, m in enumerate(masks or []):
                if m is not None and _staged_dim0(m) != num_slots:
                    raise ValueError(
                        f"{kind} {i} stages {_staged_dim0(m)} batches, "
                        f"expected {num_slots}"
                    )
        n_real = num_slots if real_batches is None else int(real_batches)
        if not 1 <= n_real <= num_slots:
            raise ValueError(f"real_batches={n_real} outside [1, {num_slots}]")
        n_steps = int(steps) if steps is not None else n_real
        steps_cap = num_slots if n_steps <= num_slots else next_pow2(n_steps)
        with_masks = fmasks is not None or lmasks is not None
        args = (self.params, self.opt_state, self.state, self._rng,
                jnp.asarray(n_steps, jnp.int32),
                jnp.asarray(n_real, jnp.int32),
                xs_list, ys_list, fmasks, lmasks)
        return steps_cap, with_masks, n_steps, args

    def _staged_executable(self, steps_cap, with_masks, with_telemetry, args):
        from ...runtime.compile_manager import get_compile_manager, signature

        cm = get_compile_manager()
        # token stays the key's FIRST element (drop_token matches on it)
        key = (self._cm_token, "graph_multi_step",
               signature(steps_cap, with_masks, with_telemetry, args))
        return cm.aot(
            key,
            lambda: self._build_multi_step(steps_cap, with_masks,
                                           with_telemetry),
            args,
        )

    def warmup(self, features, labels, steps: Optional[int] = None,
               features_masks=None, labels_masks=None,
               real_batches: Optional[int] = None) -> "ComputationGraph":
        """Compile-ahead for the staged path (see MultiLayerNetwork.warmup);
        arrays may be real data or ``jax.ShapeDtypeStruct`` shells."""
        self.init()
        from ...tune import store as _tuned

        _tuned.auto_apply(self, "warmup")  # tuned telemetry cadence etc.
        if not isinstance(features, (list, tuple)):
            features = [features]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]

        def _shell(a):
            if a is None or isinstance(a, jax.ShapeDtypeStruct):
                return a
            a = np.asarray(a) if not hasattr(a, "dtype") else a
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        fmasks = self._as_stage_list(features_masks,
                                     len(self.conf.network_inputs),
                                     "features_masks")
        lmasks = self._as_stage_list(labels_masks,
                                     len(self.conf.network_outputs),
                                     "labels_masks")
        steps_cap, with_masks, _, args = self._staged_args(
            [_shell(x) for x in features], [_shell(y) for y in labels],
            steps,
            None if fmasks is None else [_shell(m) for m in fmasks],
            None if lmasks is None else [_shell(m) for m in lmasks],
            real_batches)
        self._staged_executable(steps_cap, with_masks,
                                self.telemetry is not None, args)
        return self

    def fit_on_device(self, features, labels, steps: Optional[int] = None,
                      features_masks=None, labels_masks=None,
                      real_batches: Optional[int] = None) -> np.ndarray:
        """Whole training loop in ONE dispatch (TPU-native fit; see
        MultiLayerNetwork.fit_on_device). ``features``/``labels``: lists (one
        per network input/output) of stacked batches ``[K, B, ...]``; a single
        array is accepted for single-input/-output graphs.
        ``features_masks``/``labels_masks``: per-input/-output stacked masks
        (None entries allowed) — the bucketed stager threads padded batches
        through here. ``real_batches`` marks how many leading slots hold real
        data (trailing slots may be dummy padding, never indexed). TBPTT is
        not supported on this path — use :meth:`fit`."""
        self.init()
        if self.conf.backprop_type == "tbptt":
            raise ValueError("fit_on_device does not support TBPTT; use fit()")
        with span("dl4j.fit.dispatch", net="graph") as dispatch:
            with span("dl4j.fit.prepare"):
                if not isinstance(features, (list, tuple)):
                    features = [features]
                if not isinstance(labels, (list, tuple)):
                    labels = [labels]
                xs_list = [jnp.asarray(x) for x in features]
                ys_list = [jnp.asarray(y) for y in labels]
                fmasks = self._as_stage_list(features_masks,
                                             len(self.conf.network_inputs),
                                             "features_masks")
                lmasks = self._as_stage_list(labels_masks,
                                             len(self.conf.network_outputs),
                                             "labels_masks")
                if fmasks is not None:
                    fmasks = [None if m is None else jnp.asarray(m)
                              for m in fmasks]
                    if all(m is None for m in fmasks):
                        fmasks = None
                if lmasks is not None:
                    lmasks = [None if m is None else jnp.asarray(m)
                              for m in lmasks]
                    if all(m is None for m in lmasks):
                        lmasks = None
                tel = self.telemetry
                steps_cap, with_masks, n_steps, args = self._staged_args(
                    xs_list, ys_list, steps, fmasks, lmasks, real_batches)
                fn = self._staged_executable(steps_cap, with_masks,
                                             tel is not None, args)
            slots, batch = (int(d) for d in xs_list[0].shape[:2])
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
            # host fetch = the sync point; buffer tails slice off HOST-side
            # (a device-side slice would compile per distinct step count)
            with span("dl4j.fit.fetch"):
                losses = np.asarray(losses)[:n_steps]
                if mvecs is not None:
                    mvecs = np.asarray(mvecs)[:n_steps]
                self._publish_layer_counters()
            elapsed = time.perf_counter() - t0
            if tel is not None:
                if tel.flight is not None:
                    # dispatch event rings BEFORE on_staged reads the
                    # metrics: an anomaly found there auto-dumps with the
                    # dispatch already on record
                    tel.flight.record(
                        "staged_dispatch", net="graph", steps=int(n_steps),
                        slots=slots, batch=batch, seconds=round(elapsed, 6))
                tel.on_staged(self.iteration + 1, mvecs,
                              per_step_time_s=elapsed / max(len(losses), 1))
            self.last_batch_size = batch
            self.staged_steps_total += len(losses)
            # see MultiLayerNetwork.fit_on_device: even per-step attribution
            # for throughput listeners during the tight replay loop
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

    def _publish_layer_counters(self) -> None:
        """The dispatch's layer counters into the default registry (see
        ``telemetry/device.py``); nothing for a graph without a counting
        layer."""
        from ...telemetry import device as _tdev  # noqa: PLC0415

        _tdev.publish_layer_counters(
            (n, v.layer, self.state.get(n))
            for n, v in self.conf.vertices.items() if hasattr(v, "layer"))

    def fit(self, data, epochs: int = 1,
            stage_on_device: Optional[int] = None,
            bucketing: bool = True) -> "ComputationGraph":
        """Train (reference: ComputationGraph.fit(MultiDataSet):743).

        ``data``: MultiDataSet, DataSet, (x, y) tuple, or an iterator of any.

        ``stage_on_device=K``: buffer K batches and run the window as ONE
        on-device dispatch, double-buffered (see MultiLayerNetwork.fit);
        left unset, a matching TUNED.json staging window auto-applies
        (explicit values — including 0 — always win).
        With ``bucketing`` (default) ragged/masked batches stay on the
        staged path — trailing partial batches pad up with masked rows,
        variable sequence lengths pad to power-of-two time buckets, and the
        trailing partial window runs with device-scalar step counts;
        ``bucketing=False`` restores the strict legacy contract (only full
        uniform mask-free groups stage). TBPTT/grad-stats batches always
        train per-batch.
        """
        from ...datasets.iterators import AsyncDataSetIterator, as_iterator

        self.init()
        if self._train_step is None:
            self._train_step = self._step_callable()
        from ...tune import store as _tuned

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
            stage = 0  # opt-in contract: see IterationListener.supports_staged
        for _ in range(epochs):
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_start"):
                    lst.on_epoch_start(self, self.epoch)
            it = as_iterator(data)
            if hasattr(it, "reset"):
                it.reset()
            if getattr(it, "prefetch_supported", False):
                it = AsyncDataSetIterator(it)
            if stage > 1:
                self._fit_epoch_staged(it, stage, bucketing)
            else:
                for ds in it:
                    self._fit_batch(self._as_multi(ds))
            self.epoch += 1
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self, self.epoch)
        if self.telemetry is not None:
            self.telemetry.flush()  # drain a partial K-window at fit end
        return self

    def _pad_examples_ok(self) -> bool:
        """Row padding is exact only for per-example models (see
        MultiLayerNetwork._pad_examples_ok)."""
        from ..layers.normalization import BatchNormalization

        return not any(
            isinstance(getattr(v, "layer", None), BatchNormalization)
            for v in self.conf.vertices.values()
        )

    def _fit_epoch_staged(self, it, stage: int, bucketing: bool = True) -> None:
        """See MultiLayerNetwork._fit_epoch_staged: bucketed windows run as
        one on-device dispatch, double-buffered (window i+1's device_put
        overlaps window i's compute); unstageable batches train per-batch in
        stream order."""
        from ...datasets.bucketing import BucketedStager

        stager = BucketedStager(stage, bucketing=bucketing,
                                pad_examples=self._pad_examples_ok())

        def normalize(ds):
            mds = self._as_multi(ds)
            n_in, n_out = len(mds.features), len(mds.labels)
            return (
                [np.asarray(f) for f in mds.features],
                [np.asarray(l) for l in mds.labels],
                list(mds.features_masks or [None] * n_in),
                list(mds.labels_masks or [None] * n_out),
            )

        def to_device(win):
            put = jax.device_put  # async: overlaps the pending dispatch

            def opt(ms):
                return None if ms is None else [
                    None if m is None else put(m) for m in ms]

            win.features = [put(a) for a in win.features]
            win.labels = [put(a) for a in win.labels]
            win.features_masks = opt(win.features_masks)
            win.labels_masks = opt(win.labels_masks)
            return win

        def dispatch(win):
            self.fit_on_device(
                win.features, win.labels, steps=win.n_real,
                features_masks=win.features_masks,
                labels_masks=win.labels_masks,
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
                self._fit_batch(self._as_multi(payload))
        if pending is not None:
            dispatch(pending)
        self._check_padding_waste(stager)

    def _check_padding_waste(self, stager) -> None:
        """DT205 epoch hook (see MultiLayerNetwork._check_padding_waste)."""
        try:
            from ...analysis.ir_checks import (check_padding_waste,
                                               record_findings)

            findings = check_padding_waste(
                stager.padding_stats(),
                source=f"<{type(self).__name__} epoch {self.epoch}>")
            registry = (self.telemetry.registry
                        if self.telemetry is not None else None)
            record_findings(findings, registry=registry)
        except Exception:  # observability must never break fit
            pass

    @staticmethod
    def _as_multi(ds):
        from ...datasets.iterators import DataSet, MultiDataSet

        if isinstance(ds, MultiDataSet):
            return ds
        if isinstance(ds, (tuple, list)) and len(ds) == 2:
            ds = DataSet(ds[0], ds[1])
        if isinstance(ds, DataSet):
            return MultiDataSet(
                features=[ds.features],
                labels=[ds.labels],
                features_masks=[ds.features_mask],
                labels_masks=[ds.labels_mask],
                example_metadata=getattr(ds, "example_metadata", None),
            )
        raise TypeError(f"Cannot convert {type(ds).__name__} to MultiDataSet")

    def _fit_batch(self, mds) -> None:
        self.last_batch_size = mds.num_examples()
        if self.conf.backprop_type == "tbptt" and any(
            np.ndim(f) == 3 for f in mds.features
        ):
            self._fit_tbptt(mds)
            return
        self._rng, step_key = jax.random.split(self._rng)
        masks = None
        if mds.features_masks is not None:
            masks = {
                name: m
                for name, m in zip(self.conf.network_inputs, mds.features_masks)
            }
        lmasks = mds.labels_masks
        if lmasks is not None and all(m is None for m in lmasks):
            lmasks = None
        tel = self.telemetry
        mvec = None
        if self._wants_grad_stats():
            if self._grad_stats_step is None:
                self._grad_stats_step = self._step_callable("grad_stats")
            (self.params, self.opt_state, self.state, loss,
             self._last_grads, self._last_updates) = self._grad_stats_step(
                self.params, self.opt_state, self.state,
                list(mds.features), list(mds.labels), step_key, lmasks, masks,
            )
            if tel is not None:
                from ...telemetry import device as _tdev  # noqa: PLC0415

                mvec = _tdev.step_stats(loss, self._last_grads)
        elif tel is not None:
            if self._telemetry_step is None:
                self._telemetry_step = self._step_callable("telemetry")
            (self.params, self.opt_state, self.state, loss, mvec) = \
                self._telemetry_step(
                    self.params, self.opt_state, self.state,
                    list(mds.features), list(mds.labels), step_key, lmasks,
                    masks,
                )
        else:
            self.params, self.opt_state, self.state, loss = self._train_step(
                self.params, self.opt_state, self.state,
                list(mds.features), list(mds.labels), step_key, lmasks, masks,
            )
        self._last_loss = loss
        self.iteration += 1
        if tel is not None and mvec is not None:
            tel.on_step(self.iteration, mvec)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, loss)
        # listeners have copied what they need; free the grad/update buffers
        self._last_grads = None
        self._last_updates = None

    # ------------------------------------------------------- TBPTT (graphs)
    def _init_rnn_states(self, batch: int):
        """Per-vertex streaming state dict ({} for stateless vertices)."""
        return {
            name: (
                self.conf.vertices[name].init_recurrent_state(batch)
                if getattr(self.conf.vertices[name], "is_recurrent", False)
                else {}
            )
            for name in self._topo
        }

    def _build_tbptt_step(self):
        """One param update per time segment, recurrent state carried across
        segments with gradients stopped (reference: the doTruncatedBPTT path
        invoked from ComputationGraph.fit; tbptt_back_length < fwd_length
        truncates the backward window like tbpttBackwardLength does)."""
        tx = self._tx
        ls = getattr(self.conf, "loss_scale", None)
        back_len = int(self.conf.tbptt_back_length or 0)

        def slice_t(arrs, sl):
            return [a[:, sl] if a.ndim == 3 else a for a in arrs]

        def slice_mask_dict(md, sl):
            if md is None:
                return None
            return {n: (None if m is None else m[:, sl]) for n, m in md.items()}

        def dl4j_graph_tbptt_step(params, opt_state, state, rnn, xs, ys, rng,
                                  labels_masks, masks):
            seg_len = next(a.shape[1] for a in xs if a.ndim == 3)
            k = seg_len if back_len <= 0 else min(back_len, seg_len)
            if k < seg_len:
                split = seg_len - k
                pre_rng, rng = jax.random.split(rng)
                _, state_in, rnn_in = jax.lax.stop_gradient(
                    self._forward(
                        params, slice_t(xs, slice(None, split)), state, True,
                        pre_rng, slice_mask_dict(masks, slice(None, split)), rnn,
                    )
                )
                xs_g = slice_t(xs, slice(split, None))
                ys_g = slice_t(ys, slice(split, None))
                lm_g = (
                    None if labels_masks is None
                    else [None if m is None else m[:, split:] for m in labels_masks]
                )
                m_g = slice_mask_dict(masks, slice(split, None))
            else:
                xs_g, ys_g, lm_g, m_g = xs, ys, labels_masks, masks
                state_in, rnn_in = state, rnn

            def loss_of(p):
                loss, new_state, new_rnn = self._loss(
                    p, state_in, xs_g, ys_g, rng, True, lm_g, m_g, rnn_state=rnn_in
                )
                return scaled_loss(loss, ls), (new_state, new_rnn)

            (loss, (new_state, new_rnn)), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(params)
            loss = unscale_loss(loss, ls)
            grads = unscale_grads(grads, ls)
            updates, new_opt, new_params = optimizer_update(
                tx, grads, opt_state, params)
            # segment boundary = truncation boundary: h/c re-enter the next
            # call as constants
            new_rnn = jax.lax.stop_gradient(new_rnn)
            return new_params, new_opt, new_state, new_rnn, loss

        return jax.jit(self._kernel_scoped(dl4j_graph_tbptt_step))

    def _fit_tbptt(self, mds) -> None:
        # TBPTT bypasses the grad-stats step; drop stale grads (see MLN note).
        self._last_grads = None
        self._last_updates = None
        feats = [np.asarray(f) for f in mds.features]
        labs = [np.asarray(l) for l in mds.labels]
        n_in, n_out = len(feats), len(labs)
        fmasks = list(mds.features_masks or [None] * n_in)
        lmasks = list(mds.labels_masks or [None] * n_out)
        seq_lens = {a.shape[1] for a in feats + labs if a.ndim == 3}
        if len(seq_lens) != 1:
            raise ValueError(
                f"TBPTT requires one shared sequence length; got {sorted(seq_lens)}"
            )
        T, L = seq_lens.pop(), self.conf.tbptt_fwd_length
        if self._tbptt_step is None:
            self._tbptt_step = self._build_tbptt_step()
        rnn = self._init_rnn_states(feats[0].shape[0])
        for t0 in range(0, T, L):
            seg = slice(t0, t0 + min(L, T - t0))
            xs = [a[:, seg] if a.ndim == 3 else a for a in feats]
            ys = [a[:, seg] if a.ndim == 3 else a for a in labs]
            fms = [None if m is None else np.asarray(m)[:, seg] for m in fmasks]
            lms = [None if m is None else np.asarray(m)[:, seg] for m in lmasks]
            masks = (
                dict(zip(self.conf.network_inputs, fms))
                if any(m is not None for m in fms) else None
            )
            lms = None if all(m is None for m in lms) else lms
            self._rng, step_key = jax.random.split(self._rng)
            (self.params, self.opt_state, self.state, rnn, loss) = self._tbptt_step(
                self.params, self.opt_state, self.state, rnn,
                xs, ys, step_key, lms, masks,
            )
            self._last_loss = loss
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, loss)

    # ------------------------------------------------------------- streaming
    def rnn_time_step(self, *inputs, features_masks=None):
        """Stateful streaming inference (reference: ComputationGraph.rnnTimeStep:1801).

        Each input: [batch, features] (one step) or [batch, time, features].
        Recurrent vertices' h/c persist across calls until
        :meth:`rnn_clear_previous_state`.

        XLA shape note: single-step 2-D inputs normalize to [B, 1, F] and
        reuse one traced program; multi-step calls compile once per distinct
        (batch, T) — bucket T for variable-length streaming (pad via
        ``datasets.iterators.pad_to_bucket`` and pass ``features_masks``;
        masked steps hold recurrent h/c).

        Fast path (default): routed through ``runtime/inference.py`` — time
        axes pow2-bucket with auto-synthesized masks, the program is
        AOT-admitted via the compile manager, RNN state + inputs donated on
        accelerators. ``DL4JTPU_INFER=legacy`` restores the per-net
        ``jax.jit`` dispatch below.
        """
        from ...runtime import inference as _inf

        if len(inputs) == 1 and isinstance(inputs[0], (list, tuple)):
            inputs = tuple(inputs[0])
        if _inf.fast_path_enabled():
            outs = _inf.graph_rnn_step(self, list(inputs),
                                       features_masks=features_masks)
            return outs[0] if len(outs) == 1 else outs
        self.init()
        xs = [jnp.asarray(x) for x in inputs]
        single_step = all(x.ndim == 2 for x in xs)
        if single_step:
            xs = [x[:, None, :] for x in xs]
        if features_masks is not None and not isinstance(
            features_masks, (list, tuple, dict)
        ):
            features_masks = [features_masks]
        if isinstance(features_masks, (list, tuple)):
            if len(features_masks) != len(self.conf.network_inputs):
                raise ValueError(
                    f"features_masks has {len(features_masks)} entries but the "
                    f"graph has {len(self.conf.network_inputs)} inputs "
                    f"({self.conf.network_inputs})"
                )
            features_masks = dict(zip(self.conf.network_inputs, features_masks))
        if features_masks is not None:
            features_masks = {k: None if m is None else jnp.asarray(m)
                              for k, m in features_masks.items()}
        batch = int(xs[0].shape[0])
        leaves = (
            jax.tree_util.tree_leaves(self._rnn_state)
            if self._rnn_state is not None else []
        )
        if self._rnn_state is None or (leaves and leaves[0].shape[0] != batch):
            self._rnn_state = self._init_rnn_states(batch)
        if self._rnn_step_fn is None:
            self._rnn_step_fn = jax.jit(
                lambda params, state, rnn, xs, masks: self._forward(
                    params, xs, state, False, None, masks, rnn
                )[::2]  # (outs, new_rnn) — per-token dispatch stays on device
            )
        outs, self._rnn_state = self._rnn_step_fn(
            self.params, self.state, self._rnn_state, xs, features_masks
        )
        if single_step:
            outs = [o[:, 0, :] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self) -> None:
        """Reference: ComputationGraph.rnnClearPreviousState."""
        self._rnn_state = None

    def rnn_get_previous_state(self, vertex_name: str):
        """Reference: ComputationGraph.rnnGetPreviousState(layerName)."""
        if self._rnn_state is None:
            return None
        st = self._rnn_state.get(vertex_name)
        return st if st else None

    # -------------------------------------------------------------- inference
    def output(self, *inputs, train: bool = False, masks=None):
        """Output activations (reference: ComputationGraph.output). Returns a
        single array for single-output graphs, else a list.

        Served by the AOT-bucketed inference fast path
        (``runtime/inference.py``): boundary dtype canonicalization, pow2
        row/time bucketing with exact masked padding, compile-manager AOT
        admission, host-array return with the padding sliced off.
        ``DL4JTPU_INFER=legacy`` restores the per-net ``jax.jit``
        dispatch."""
        from ...runtime import inference as _inf

        self.init()
        if len(inputs) == 1 and isinstance(inputs[0], (list, tuple)):
            inputs = tuple(inputs[0])
        if _inf.fast_path_enabled():
            outs = _inf.graph_output(self, list(inputs), masks=masks)
            return outs[0] if len(outs) == 1 else outs
        if self._eval_forward is None:
            self._eval_forward = jax.jit(
                lambda params, state, xs, masks: self._forward(
                    params, xs, state, False, None, masks
                )[0]
            )  # _forward returns (outs, state, rnn); [0] = outputs
        outs = self._eval_forward(
            self.params, self.state, [jnp.asarray(x) for x in inputs], masks
        )
        return outs[0] if len(outs) == 1 else outs

    def predict(self, *inputs, masks=None):
        """Class indices per output (reference: MultiLayerNetwork.predict's
        graph twin). The argmax is fused into the compiled inference
        executable — only int32 indices cross the device boundary. Returns
        one array for single-output graphs, else a list."""
        from ...runtime import inference as _inf

        self.init()
        if len(inputs) == 1 and isinstance(inputs[0], (list, tuple)):
            inputs = tuple(inputs[0])
        if _inf.fast_path_enabled():
            outs = _inf.graph_output(self, list(inputs), masks=masks,
                                     argmax=True)
        else:
            outs = self.output(*inputs, masks=masks)
            if not isinstance(outs, list):
                outs = [outs]
            outs = [np.asarray(jnp.argmax(o, axis=-1)) for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def _input_masks(self, mds):
        if mds.features_masks is None or all(m is None for m in mds.features_masks):
            return None
        return dict(zip(self.conf.network_inputs, mds.features_masks))

    def score(self, dataset=None) -> float:
        if dataset is None:
            return float(self._last_loss) if self._last_loss is not None else float("nan")
        self.init()
        mds = self._as_multi(dataset)
        lmasks = mds.labels_masks
        if lmasks is not None and all(m is None for m in lmasks):
            lmasks = None
        return float(
            self.loss_fn(
                self.params, list(mds.features), list(mds.labels),
                labels_masks=lmasks, masks=self._input_masks(mds),
            )
        )

    def evaluate(self, data, top_n: int = 1):
        """Classification eval (reference: ComputationGraph.evaluate).

        Single-output graphs return one :class:`Evaluation`. Multi-output
        graphs return ``{output_name: Evaluation}`` — every output is scored
        (round-1 weak #6: only the first output was silently evaluated).
        """
        from ...eval.evaluation import Evaluation
        from ...datasets.iterators import as_iterator

        # Only classification heads get a classification Evaluation —
        # argmaxing a regression output would report nonsense accuracy.
        class_losses = {"mcxent", "negativeloglikelihood", "xent", "binary_xent"}
        names = []
        for n in self.conf.network_outputs:
            layer = getattr(self.conf.vertices[n], "layer", None)
            if getattr(layer, "loss", None) in class_losses or len(
                self.conf.network_outputs
            ) == 1:
                names.append(n)
        if not names:
            raise ValueError(
                "evaluate(): no classification output heads (losses: "
                + ", ".join(
                    str(getattr(getattr(self.conf.vertices[n], "layer", None), "loss", None))
                    for n in self.conf.network_outputs
                )
                + "); use score()/RegressionEvaluation for regression heads"
            )
        idx = {n: i for i, n in enumerate(self.conf.network_outputs)}
        evs = [Evaluation(top_n=top_n) for _ in names]
        for ds in as_iterator(data):
            mds = self._as_multi(ds)
            out = self.output(*mds.features, masks=self._input_masks(mds))
            outs = out if isinstance(out, list) else [out]
            if len(outs) != len(mds.labels):
                raise ValueError(
                    f"{len(outs)} outputs but {len(mds.labels)} label arrays"
                )
            for ev, n in zip(evs, names):
                # record provenance when present (Prediction records; skipped
                # for time-series outputs, which flatten to B*T rows)
                meta = getattr(mds, "example_metadata", None)
                if meta is not None and np.ndim(outs[idx[n]]) == 3:
                    meta = None
                ev.eval(mds.labels[idx[n]], outs[idx[n]], record_metadata=meta)
        return (
            evs[0]
            if len(self.conf.network_outputs) == 1
            else dict(zip(names, evs))
        )

    # ------------------------------------------------------------------ misc
    def clone(self) -> "ComputationGraph":
        from ..conf.computation_graph import ComputationGraphConfiguration

        other = ComputationGraph(
            ComputationGraphConfiguration.from_dict(self.conf.to_dict())
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
