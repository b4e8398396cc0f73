"""MultiLayerNetwork: sequential model with a jit-compiled train step.

TPU-native equivalent of the reference's ``MultiLayerNetwork``
(nn/multilayer/MultiLayerNetwork.java — init():382, fit(DataSetIterator):917,
backprop():988, feedForward:652, output:1505; call stack SURVEY.md §3.1).

Architecture differences, by design:
- The reference's Solver/ConvexOptimizer/StepFunction tier (optimize/solvers/*)
  collapses into ONE pure jitted ``train_step`` (``nn/engine.py``, shared with
  ``ComputationGraph``; this class keeps the forward pass, the loss and the
  parameter tree).
- Flattened param vector + gradient views (initGradientsView:470) → param
  pytree ``(dict_per_layer, ...)``.
- ``backpropGradient`` per layer → ``jax.grad`` end to end.
- Mutable layer state (BN running stats, RNN streaming state) is an explicit
  state pytree threaded through ``apply``, never hidden mutation.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.spans import span
from .engine import TrainingEngine
from .updaters import optimizer_update


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


class MultiLayerNetwork(TrainingEngine):
    """Sequential network over a :class:`MultiLayerConfiguration`; training
    is :class:`~deeplearning4j_tpu.nn.engine.TrainingEngine`'s."""

    _KIND = "mln"

    # ------------------------------------------------------------------ init
    def init(self, params=None, force: bool = False) -> "MultiLayerNetwork":
        """Initialize params/state/updater (reference: MultiLayerNetwork.init():382)."""
        if self.params is not None and not force and params is None:
            return self
        with span("dl4j.net.init", net=self._KIND):
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
            self._tx = self._build_tx()
            self.opt_state = self._tx.init(self.params)
            self.iteration = 0
            self._invalidate_compiled()
        return self

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

    # ------------------------------------------- what the engine asks of us
    # (nn/engine.py: a batch is one array, a mask one array or None)
    def _stage(self, features, labels, features_masks, labels_masks, leaf):
        return tuple(None if a is None else leaf(a) for a in
                     (features, labels, features_masks, labels_masks))

    def _loss_masks(self, features_masks, labels_masks):
        return features_masks, labels_masks

    def _batch_lists(self, ds):
        return ([ds.features], [ds.labels],
                [getattr(ds, "features_mask", None)],
                [getattr(ds, "labels_mask", None)])

    def _from_lists(self, features, labels, features_masks, labels_masks):
        return tuple(None if l is None else l[0] for l in
                     (features, labels, features_masks, labels_masks))

    def _layer_states(self):
        return ((self.layer_scope(i), layer, self.state[i])
                for i, layer in enumerate(self.conf.layers))

    def _pad_examples_ok(self) -> bool:
        from .layers.normalization import BatchNormalization

        return not any(isinstance(l, BatchNormalization)
                       for l in self.conf.layers)

    def _init_rnn_states(self, batch: int):
        """Per-layer streaming state tuple ({} for stateless layers)."""
        return tuple(
            layer.init_recurrent_state(batch)
            if hasattr(layer, "init_recurrent_state") and layer.is_recurrent
            else {}
            for layer in self.conf.layers
        )

    def _warm_state(self, params, x, state, rng, features_mask, rnn):
        return self._forward(
            params, x, state, True, rng, upto=len(self.conf.layers) - 1,
            features_mask=features_mask, rnn_state=rnn)[1:]

    def _time_slice(self, arrays, sl):
        return arrays[:, sl]

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
