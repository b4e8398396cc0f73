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

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ...telemetry.spans import span
from ..engine import TrainingEngine
from ..multilayer import (
    _carry_params_dtype,
    _cast_input,
    _cast_layer_params,
    _format_summary_table,
)
from .vertices import LayerVertex


class ComputationGraph(TrainingEngine):
    """DAG network over a :class:`ComputationGraphConfiguration`; training
    is :class:`~deeplearning4j_tpu.nn.engine.TrainingEngine`'s."""

    _KIND = "graph"

    def __init__(self, conf: "ComputationGraphConfiguration"):  # noqa: F821
        super().__init__(conf)
        self._topo = conf.topological_order()

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
            self._tx = self._build_tx()
            self.opt_state = self._tx.init(self.params)
            self.iteration = 0
            self._invalidate_compiled()
        return self

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
            sources = conf.vertex_inputs[name]
            ins = [acts[src] for src in sources]
            stepping = new_rnn is not None and bool(new_rnn.get(name))
            # a vertex that hands its first input on: the vertices after it
            # read that input from here (BaseVertex.hands_input_on)
            hands_on = train and not stepping and vertex.hands_input_on
            apply = vertex.apply_handing_on if hands_on else vertex.apply
            # every operation of a vertex carries its configured name
            with jax.named_scope(name):
                if stepping:
                    acts[name], new_rnn[name] = vertex.apply_seq(
                        params[name], ins, new_rnn[name], train=train, rng=r,
                        masks=vmasks
                    )
                elif train and conf.remat:
                    # per-vertex jax.checkpoint: keep only vertex-boundary
                    # activations for backward (see
                    # MultiLayerConfiguration.remat)
                    def _ck(p_, ins_, st_, r_, m_, _apply=apply):
                        return _apply(p_, ins_, st_, train=True, rng=r_,
                                      masks=m_)

                    acts[name], new_state[name] = jax.checkpoint(_ck)(
                        params[name], ins, state[name], r, vmasks
                    )
                else:
                    acts[name], new_state[name] = apply(
                        params[name], ins, state[name], train=train, rng=r,
                        masks=vmasks
                    )
            if hands_on:
                acts[name], acts[sources[0]] = acts[name]
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

    # ------------------------------------------- what the engine asks of us
    # (nn/engine.py: a batch is a list of arrays per network input/output,
    # features masks reach ``_loss`` as a dict by input name)
    @staticmethod
    def _as_stage_list(value, n: int, kind: str):
        """Normalize a masks argument to a length-``n`` list (None entries
        allowed); a bare array is accepted for single-input/-output graphs."""
        if value is None:
            return None
        if not isinstance(value, (list, tuple)):
            value = [value]
        if len(value) != n:
            raise ValueError(f"{kind} has {len(value)} entries, expected {n}")
        return list(value)

    def _stage(self, features, labels, features_masks, labels_masks, leaf):
        def arrays(value):
            if not isinstance(value, (list, tuple)):
                value = [value]
            return [leaf(a) for a in value]

        def masks(value, n, kind):
            value = self._as_stage_list(value, n, kind)
            if value is None or all(m is None for m in value):
                return None
            return [None if m is None else leaf(m) for m in value]

        return (arrays(features), arrays(labels),
                masks(features_masks, len(self.conf.network_inputs),
                      "features_masks"),
                masks(labels_masks, len(self.conf.network_outputs),
                      "labels_masks"))

    def _loss_masks(self, features_masks, labels_masks):
        # the branches test pytree STRUCTURE (None-ness): trace-static
        def some(ms):
            return ms is not None and any(m is not None for m in ms)

        return (dict(zip(self.conf.network_inputs, features_masks))
                if some(features_masks) else None,
                labels_masks if some(labels_masks) else None)

    def _batch_lists(self, ds):
        mds = self._as_multi(ds)
        return (list(mds.features), list(mds.labels),
                list(mds.features_masks or [None] * len(mds.features)),
                list(mds.labels_masks or [None] * len(mds.labels)))

    def _from_lists(self, features, labels, features_masks, labels_masks):
        return features, labels, features_masks, labels_masks

    def _layer_states(self):
        return ((n, v.layer, self.state.get(n))
                for n, v in self.conf.vertices.items() if hasattr(v, "layer"))

    def _pad_examples_ok(self) -> bool:
        from ..layers.normalization import BatchNormalization

        return not any(
            isinstance(getattr(v, "layer", None), BatchNormalization)
            for v in self.conf.vertices.values()
        )

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

    def _warm_state(self, params, xs, state, rng, masks, rnn):
        return self._forward(params, xs, state, True, rng, masks, rnn)[1:]

    def _time_slice(self, arrays, sl):
        return [a[:, sl] if a.ndim == 3 else a for a in arrays]

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
