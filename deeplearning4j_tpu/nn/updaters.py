"""Updaters: gradient transforms with learning-rate schedules and clipping.

TPU-native equivalent of the reference's updater tier (SURVEY.md §2.1 "Updater
layer"): ND4J ``GradientUpdater`` implementations (Sgd/Adam/AdaDelta/Nesterovs/
AdaGrad/RmsProp/NoOp) + ``LayerUpdater.update`` (lr/momentum schedules, gradient
normalization/clipping, minibatch division) + the flattened updater-state view
array that made checkpoints resumable
(deeplearning4j-nn/.../nn/updater/LayerUpdater.java:73-113).

Here the whole tier is **optax-style pure transforms with an explicit state
pytree**: ``build_updater(conf)`` returns an ``optax.GradientTransformation``;
its state is part of the checkpoint triple (config, params, opt_state) exactly
like the reference's ``updaterState.bin`` (ModelSerializer.java:56-135).

Differences by design (documented, not accidental):
- L1/L2 regularization enters through the *loss* (autodiff then routes it through
  the updater like any other gradient term) rather than the reference's
  post-updater gradient addition (LayerUpdater.postApply:103-113).
- Minibatch division is implicit: losses are means over the batch.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax


# ---------------------------------------------------------------------------
# Learning-rate schedules (reference: LearningRatePolicy enum + applyLrDecayPolicy)
# ---------------------------------------------------------------------------

def build_schedule(
    lr: float,
    policy: str = "none",
    decay_rate: float = 0.0,
    power: float = 0.0,
    steps: float = 1.0,
    gamma: float = 0.0,
    max_iterations: int = 1,
    schedule: Optional[Dict[int, float]] = None,
) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Return iteration -> learning-rate, mirroring the reference's policies."""
    policy = (policy or "none").lower()
    if policy == "none":
        return lambda it: jnp.asarray(lr)
    if policy == "exponential":
        return lambda it: lr * jnp.power(decay_rate, it)
    if policy == "inverse":
        return lambda it: lr / jnp.power(1.0 + decay_rate * it, power)
    if policy == "poly":
        return lambda it: lr * jnp.power(1.0 - jnp.minimum(it / max_iterations, 1.0), power)
    if policy == "sigmoid":
        return lambda it: lr / (1.0 + jnp.exp(-gamma * (it - steps)))
    if policy == "step":
        return lambda it: lr * jnp.power(decay_rate, jnp.floor(it / steps))
    if policy == "schedule":
        # piecewise-constant map {iteration: lr}, like conf.learningRateSchedule
        sched = sorted((int(k), float(v)) for k, v in (schedule or {}).items())
        boundaries = jnp.asarray([k for k, _ in sched]) if sched else jnp.asarray([0])
        values = jnp.asarray([lr] + [v for _, v in sched])

        def fn(it):
            idx = jnp.sum(it >= boundaries)
            return values[idx]

        return fn
    if policy == "torch_step":  # alias
        return lambda it: lr * jnp.power(decay_rate, jnp.floor(it / steps))
    raise ValueError(f"Unknown learning-rate policy '{policy}'")


# ---------------------------------------------------------------------------
# Gradient normalization (reference: GradientNormalization enum, applied in
# BaseUpdater.preApply before the per-param updater runs)
# ---------------------------------------------------------------------------

def _per_leaf_l2(g):
    return jnp.sqrt(jnp.maximum(jnp.sum(g * g), 1e-12))


def gradient_normalization(kind: str, threshold: float = 1.0) -> optax.GradientTransformation:
    """Build the reference's GradientNormalization modes as an optax transform.

    Layer granularity note: the reference's "PerLayer" modes normalize over all
    params of one layer jointly; "PerParamType" per tensor. Params here are a
    pytree ``[{'W':..,'b':..}, ...]`` so per-layer = per top-level element.
    """
    kind = (kind or "none").lower()

    def init_fn(params):
        return optax.EmptyState()

    def per_layer(fn):
        def update_fn(updates, state, params=None):
            # updates is a list/tuple of per-layer dicts (possibly empty)
            def layer_map(layer_updates):
                leaves = jax.tree_util.tree_leaves(layer_updates)
                if not leaves:
                    return layer_updates
                norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves) + 1e-12)
                return jax.tree_util.tree_map(lambda g: fn(g, norm), layer_updates)

            if isinstance(updates, (list, tuple)):
                new = type(updates)(layer_map(lu) for lu in updates)
            else:
                new = layer_map(updates)
            return new, state

        return update_fn

    if kind == "none":
        return optax.identity()
    if kind == "renormalizel2perlayer":
        return optax.GradientTransformation(
            init_fn, per_layer(lambda g, norm: g / norm)
        )
    if kind == "renormalizel2perparamtype":
        def update_fn(updates, state, params=None):
            new = jax.tree_util.tree_map(lambda g: g / _per_leaf_l2(g), updates)
            return new, state
        return optax.GradientTransformation(init_fn, update_fn)
    if kind == "clipelementwiseabsolutevalue":
        def update_fn(updates, state, params=None):
            new = jax.tree_util.tree_map(
                lambda g: jnp.clip(g, -threshold, threshold), updates
            )
            return new, state
        return optax.GradientTransformation(init_fn, update_fn)
    if kind == "clipl2perlayer":
        return optax.GradientTransformation(
            init_fn,
            per_layer(lambda g, norm: jnp.where(norm > threshold, g * threshold / norm, g)),
        )
    if kind == "clipl2perparamtype":
        def update_fn(updates, state, params=None):
            def clip(g):
                n = _per_leaf_l2(g)
                return jnp.where(n > threshold, g * threshold / n, g)
            return jax.tree_util.tree_map(clip, updates), state
        return optax.GradientTransformation(init_fn, update_fn)
    raise ValueError(f"Unknown gradient normalization '{kind}'")


# ---------------------------------------------------------------------------
# Fused optimizer update (kernel-selection site "optimizer")
# ---------------------------------------------------------------------------

def _maybe_fused_adam(sched, b1: float, b2: float, eps: float,
                      beside: Optional[str] = None
                      ) -> optax.GradientTransformation:
    """optax.adam with a cost-model-guided fused fast path.

    ``init`` is exactly ``optax.adam``'s, so the optimizer-state pytree
    (checkpoints, donation signatures) is identical either way. At trace
    time ``update`` asks the ``optimizer`` kernel-selection site; on the
    reference choice it delegates to optax verbatim, on the fused choice the
    whole moment/bias-correct/scale chain runs as one Pallas pass per
    parameter leaf (ops.fused_adam_update — bit-matching optax's
    ``scale_by_adam`` + schedule-scale math). Any state layout this wrapper
    does not recognize falls back to optax, never breaks. ``beside`` is
    handed to the site with every call (:meth:`UpdaterConfig.build`).
    """
    ref = optax.adam(learning_rate=sched, b1=b1, b2=b2, eps=eps)

    def init_fn(params):
        return ref.init(params)

    def update_fn(updates, state, params=None):
        from ..ops import (  # noqa: PLC0415 - trace-time only
            fused_adam_update, select_optimizer_variant)

        leaves = jax.tree_util.tree_leaves(updates)
        if not leaves:
            return ref.update(updates, state, params)
        n_elems = sum(int(l.size) for l in leaves)
        itemsize = max(l.dtype.itemsize for l in leaves)
        choice = select_optimizer_variant(n_elems, itemsize, "adam",
                                          n_leaves=len(leaves), beside=beside)
        adam_i = next((i for i, s in enumerate(state)
                       if isinstance(s, optax.ScaleByAdamState)), None)
        sched_i = next((i for i, s in enumerate(state)
                        if isinstance(s, optax.ScaleByScheduleState)), None)
        if choice != "fused" or adam_i is None or sched_i is None:
            return ref.update(updates, state, params)
        adam_state, sched_state = state[adam_i], state[sched_i]
        count_inc = optax.safe_int32_increment(adam_state.count)
        lr = sched(sched_state.count)
        bc1 = 1.0 - jnp.asarray(b1) ** count_inc
        bc2 = 1.0 - jnp.asarray(b2) ** count_inc
        g_flat, treedef = jax.tree_util.tree_flatten(updates)
        mu_flat = jax.tree_util.tree_leaves(adam_state.mu)
        nu_flat = jax.tree_util.tree_leaves(adam_state.nu)
        outs = [fused_adam_update(g, m, v, lr, bc1, bc2, b1, b2, eps)
                for g, m, v in zip(g_flat, mu_flat, nu_flat)]
        unflat = jax.tree_util.tree_unflatten
        new_updates = unflat(treedef, [o[0] for o in outs])
        new_mu = unflat(treedef, [o[1] for o in outs])
        new_nu = unflat(treedef, [o[2] for o in outs])
        new_state = list(state)
        new_state[adam_i] = adam_state._replace(count=count_inc, mu=new_mu,
                                                nu=new_nu)
        new_state[sched_i] = sched_state._replace(
            count=optax.safe_int32_increment(sched_state.count))
        return new_updates, tuple(new_state)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Updater config (reference: Updater enum + per-updater hyperparams on
# NeuralNetConfiguration.Builder:486-514)
# ---------------------------------------------------------------------------

@dataclass
class UpdaterConfig:
    """JSON-serializable updater description -> optax transform via build()."""

    updater: str = "sgd"
    learning_rate: float = 0.1
    # momentum family
    momentum: float = 0.9
    # adam family
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    # rmsprop / adadelta
    rms_decay: float = 0.95
    rho: float = 0.95
    # schedules
    lr_policy: str = "none"
    lr_policy_decay_rate: float = 0.0
    lr_policy_power: float = 0.0
    lr_policy_steps: float = 1.0
    lr_policy_gamma: float = 0.0
    max_iterations: int = 1
    learning_rate_schedule: Optional[Dict[int, float]] = None
    # gradient normalization (reference: GradientNormalization)
    gradient_normalization: str = "none"
    gradient_normalization_threshold: float = 1.0

    def to_dict(self) -> dict:
        from dataclasses import asdict
        return asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "UpdaterConfig":
        d = dict(d)
        if d.get("learning_rate_schedule"):
            d["learning_rate_schedule"] = {
                int(k): float(v) for k, v in d["learning_rate_schedule"].items()
            }
        return UpdaterConfig(**d)

    # -- build ---------------------------------------------------------------
    def build(self, beside: Optional[str] = None
              ) -> optax.GradientTransformation:
        """``beside``: what in the net that builds this updater the in-place
        Adam kernel gives way beside (``nn.engine
        .adam_kernel_gives_way_beside``), or None; no other updater reads
        it."""
        sched = build_schedule(
            self.learning_rate,
            self.lr_policy,
            self.lr_policy_decay_rate,
            self.lr_policy_power,
            self.lr_policy_steps,
            self.lr_policy_gamma,
            self.max_iterations,
            self.learning_rate_schedule,
        )
        name = self.updater.lower()
        if name == "sgd":
            core = optax.sgd(learning_rate=sched)
        elif name == "nesterovs":
            core = optax.sgd(learning_rate=sched, momentum=self.momentum, nesterov=True)
        elif name == "momentum":
            core = optax.sgd(learning_rate=sched, momentum=self.momentum)
        elif name == "adam":
            core = _maybe_fused_adam(sched, self.beta1, self.beta2,
                                     self.epsilon, beside)
        elif name == "adamw":
            core = optax.adamw(learning_rate=sched, b1=self.beta1, b2=self.beta2,
                               eps=self.epsilon)
        elif name == "adamax":
            core = optax.adamax(learning_rate=sched, b1=self.beta1, b2=self.beta2,
                                eps=self.epsilon)
        elif name == "adadelta":
            core = optax.adadelta(learning_rate=1.0, rho=self.rho, eps=self.epsilon)
        elif name == "adagrad":
            core = optax.adagrad(learning_rate=sched, eps=self.epsilon)
        elif name == "rmsprop":
            core = optax.rmsprop(learning_rate=sched, decay=self.rms_decay,
                                 eps=self.epsilon)
        elif name == "lamb":
            core = optax.lamb(learning_rate=sched)
        elif name == "lion":
            core = optax.lion(learning_rate=sched)
        elif name in ("none", "noop"):
            core = optax.set_to_zero()
        else:
            raise ValueError(f"Unknown updater '{self.updater}'")

        norm = gradient_normalization(
            self.gradient_normalization, self.gradient_normalization_threshold
        )
        return optax.chain(norm, core)


# ---------------------------------------------------------------------------
# Mixed-precision update island + loss scaling (DT502/DT505 contract)
# ---------------------------------------------------------------------------

def _is_low_float(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating) \
        and jnp.dtype(x.dtype).itemsize < 4


def _has_low_float(tree) -> bool:
    return any(_is_low_float(l) for l in jax.tree_util.tree_leaves(tree))


def _to_f32(tree):
    return jax.tree_util.tree_map(
        lambda l: l.astype(jnp.float32) if _is_low_float(l) else l, tree)


def _like(tree, ref):
    return jax.tree_util.tree_map(
        lambda l, r: l.astype(r.dtype) if l.dtype != r.dtype else l,
        tree, ref)


def optimizer_update(tx: optax.GradientTransformation, grads, opt_state,
                     params):
    """``tx.update`` + ``apply_updates`` honoring the precision contract.

    Under a sub-f32 storage policy (``PrecisionPolicy(params_dtype=
    "bfloat16")``) params, grads and moments all arrive in the storage
    dtype — but the update *arithmetic* (moment EMAs, bias correction,
    ``p - lr*u``) belongs to the compute dtype: run in bf16 it rounds the
    moment EMAs every step and silently drops updates smaller than one
    bf16 ulp of the parameter (~0.8% at magnitude 1). This helper is the
    single update site for every train-step variant: when any leaf is
    sub-f32 it upcasts grads/opt_state/params to an f32 island, applies
    the optimizer there, and casts the results back per-leaf — storage,
    checkpoints and collectives stay in the declared dtype, accumulation
    is exact in f32. With all-f32 trees it is exactly
    ``tx.update`` + ``optax.apply_updates`` (no extra casts traced).

    Returns ``(updates, new_opt_state, new_params)``; ``updates`` are in
    compute precision for grad-stats consumers.
    """
    if not (_has_low_float(grads) or _has_low_float(opt_state)
            or _has_low_float(params)):
        updates, new_opt = tx.update(grads, opt_state, params)
        return updates, new_opt, optax.apply_updates(params, updates)
    p32 = _to_f32(params)
    updates, new_opt32 = tx.update(_to_f32(grads), _to_f32(opt_state), p32)
    new_p32 = optax.apply_updates(p32, updates)
    return updates, _like(new_opt32, opt_state), _like(new_p32, params)


def scaled_loss(loss, loss_scale):
    """Scale a loss for sub-f32 backprop (``None``/falsy scale: identity).

    Multiplying the loss by a power-of-two ``loss_scale`` shifts every
    gradient's exponent up before the backward pass casts cotangents to
    the bf16/f16 storage dtype, keeping small gradients out of the
    flush-to-zero range. Pair with :func:`unscale_grads` right after
    ``value_and_grad`` so everything downstream (grad stats, telemetry,
    the optimizer) sees true-magnitude gradients.
    """
    if not loss_scale:
        return loss
    return loss * jnp.asarray(loss_scale, dtype=loss.dtype)


def unscale_loss(loss, loss_scale):
    """Undo :func:`scaled_loss` on the reported loss value (exact for the
    power-of-two scales the policy defaults to)."""
    if not loss_scale:
        return loss
    return loss / jnp.asarray(loss_scale, dtype=loss.dtype)


def unscale_grads(grads, loss_scale):
    """Undo :func:`scaled_loss` on the gradient tree, in f32.

    Sub-f32 leaves are upcast before the divide so the unscale itself
    cannot re-flush: with a power-of-two scale the upcast + exponent
    shift is bit-exact. No-op (returns ``grads`` untouched) when
    ``loss_scale`` is falsy.
    """
    if not loss_scale:
        return grads
    inv = 1.0 / float(loss_scale)

    def one(g):
        if not jnp.issubdtype(g.dtype, jnp.floating):
            return g
        g32 = g.astype(jnp.float32) if _is_low_float(g) else g
        return g32 * jnp.asarray(inv, dtype=g32.dtype)

    return jax.tree_util.tree_map(one, grads)
