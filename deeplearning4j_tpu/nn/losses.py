"""Loss-function catalog.

TPU-native equivalent of the reference's ``ILossFunction`` catalog (ND4J
LossFunctions, consumed by output layers — reference
deeplearning4j-nn/.../conf/layers/OutputLayer, applied in BaseOutputLayer).
Each loss is a pure function ``(labels, preout, activation_name, mask) -> scalar``
returning the *mean over examples* (the reference divides the summed score by
minibatch size in BaseOptimizer / LayerUpdater — see SURVEY.md §2.1 "Updater layer").

``jax.grad`` differentiates straight through the loss+activation composition, so
the reference's hand-written ``computeGradient`` implementations are unnecessary.
Numerically-fused forms (softmax+cross-entropy, sigmoid+binary-xent) are used
when the paired activation is detected, mirroring ND4J's fused
LossMCXENT/softmax path.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from .activations import get_activation

EPS = 1e-7

# A loss maps (labels, preout, activation, mask) -> (per_example_scores,)
LossFn = Callable[..., jnp.ndarray]

_REGISTRY: Dict[str, LossFn] = {}


def register_loss(name: str, fn: LossFn) -> None:
    _REGISTRY[name.lower()] = fn


def get_loss(name: str) -> LossFn:
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"Unknown loss '{name}'. Known: {sorted(_REGISTRY)}") from None


def _per_example(scores: jnp.ndarray) -> jnp.ndarray:
    """Sum all trailing dims -> one score per example (row)."""
    return scores.reshape(scores.shape[0], -1).sum(axis=-1)


def _apply_mask(per_ex: jnp.ndarray, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    if mask is None:
        return per_ex.mean()
    mask = mask.reshape(per_ex.shape)
    return (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _activated(preout: jnp.ndarray, activation: str) -> jnp.ndarray:
    return get_activation(activation)(preout)


def mcxent(labels, preout, activation="softmax", mask=None):
    """Multi-class cross entropy (reference: LossMCXENT). Fused with softmax
    numerically always; fused *physically* (one Pallas VMEM pass instead of
    the max/exp/sum/log HBM round trips) when the ``softmax_xent``
    kernel-selection site picks the fused variant for these shapes — see
    ops.kernel_select. Both net classes' output layers route here, so every
    softmax loss head inherits the selection.

    ``labels`` may be integer class ids with one axis fewer than ``preout``
    (a language model's targets): the same loss as their one-hot form, and no
    one-hot array is built on either path."""
    lab = jnp.asarray(labels)
    if jnp.issubdtype(lab.dtype, jnp.integer) and lab.ndim == preout.ndim - 1:
        return _mcxent_ids(lab, preout, activation, mask)
    if activation == "softmax":
        if preout.ndim == 2 and lab.shape == preout.shape:
            from .. import ops as _ops  # noqa: PLC0415

            return _apply_mask(_ops.softmax_xent_rows(lab, preout), mask)
        # >=f32 compute for the unfused n-D path, matching the fused
        # kernel's contract: log-sum-exp and the label reduction lose
        # mantissa in bf16/f16 even though log_softmax subtracts the max
        cdt = jnp.promote_types(preout.dtype, jnp.float32)
        logp = jax.nn.log_softmax(preout.astype(cdt), axis=-1)
    else:
        act = _activated(preout, activation)
        cdt = jnp.promote_types(act.dtype, jnp.float32)
        logp = jnp.log(jnp.clip(act.astype(cdt), EPS, 1.0))
    scores = -(jnp.asarray(labels).astype(logp.dtype) * logp)
    return _apply_mask(_per_example(scores), mask)


def _mcxent_ids(ids, preout, activation, mask):
    """``mcxent`` for integer class ids ``[...]`` against ``[..., C]``."""
    if activation == "softmax":   # rows of [N, C]: the softmax_xent site's
        from .. import ops as _ops  # noqa: PLC0415

        scores = _ops.softmax_xent_rows_ids(
            ids.reshape(-1), preout.reshape(-1, preout.shape[-1])
        ).reshape(ids.shape)
    else:
        act = _activated(preout, activation)
        cdt = jnp.promote_types(act.dtype, jnp.float32)
        logp = jnp.log(jnp.clip(act.astype(cdt), EPS, 1.0))
        scores = -jnp.take_along_axis(
            logp, ids.astype(jnp.int32)[..., None], axis=-1)[..., 0]
    return _apply_mask(_per_example(scores), mask)


def xent(labels, preout, activation="sigmoid", mask=None):
    """Binary cross entropy (reference: LossBinaryXENT). Fused with sigmoid."""
    if activation == "sigmoid":
        # log(sigmoid(x)) = -softplus(-x); log(1-sigmoid(x)) = -softplus(x)
        scores = labels * jax.nn.softplus(-preout) + (1.0 - labels) * jax.nn.softplus(preout)
    else:
        p = jnp.clip(_activated(preout, activation), EPS, 1.0 - EPS)
        scores = -(labels * jnp.log(p) + (1.0 - labels) * jnp.log(1.0 - p))
    return _apply_mask(_per_example(scores), mask)


def negativeloglikelihood(labels, preout, activation="softmax", mask=None):
    """Reference: LossNegativeLogLikelihood == MCXENT for one-hot labels."""
    return mcxent(labels, preout, activation, mask)


def mse(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    scores = (out - labels) ** 2
    # reference LossMSE averages over output dims (score normalized by label width)
    return _apply_mask(_per_example(scores) / labels.shape[-1], mask)


def l2(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    return _apply_mask(_per_example((out - labels) ** 2), mask)


def mae(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    return _apply_mask(_per_example(jnp.abs(out - labels)) / labels.shape[-1], mask)


def l1(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    return _apply_mask(_per_example(jnp.abs(out - labels)), mask)


def _signed_labels(labels):
    # Accepts {0,1} one-hot or {-1,+1} conventions; jit-safe (no data-dependent
    # Python control flow): >0.5 -> +1, else -1 maps both correctly.
    return jnp.where(labels > 0.5, 1.0, -1.0)


def hinge(labels, preout, activation="identity", mask=None):
    """labels in {-1, +1} or one-hot; reference: LossHinge."""
    out = _activated(preout, activation)
    scores = jnp.maximum(0.0, 1.0 - _signed_labels(labels) * out)
    return _apply_mask(_per_example(scores), mask)


def squared_hinge(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    scores = jnp.maximum(0.0, 1.0 - _signed_labels(labels) * out) ** 2
    return _apply_mask(_per_example(scores), mask)


def kl_divergence(labels, preout, activation="softmax", mask=None):
    out = jnp.clip(_activated(preout, activation), EPS, 1.0)
    lab = jnp.clip(labels, EPS, 1.0)
    scores = lab * (jnp.log(lab) - jnp.log(out))
    return _apply_mask(_per_example(scores), mask)


def cosine_proximity(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    ln = jnp.linalg.norm(labels, axis=-1, keepdims=True)
    on = jnp.linalg.norm(out, axis=-1, keepdims=True)
    cos = (labels * out).sum(-1) / jnp.maximum(ln.squeeze(-1) * on.squeeze(-1), EPS)
    return _apply_mask(-cos.reshape(cos.shape[0], -1).sum(-1), mask)


def poisson(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    scores = out - labels * jnp.log(jnp.maximum(out, EPS))
    return _apply_mask(_per_example(scores), mask)


def mape(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    scores = 100.0 * jnp.abs((labels - out) / jnp.maximum(jnp.abs(labels), EPS))
    return _apply_mask(_per_example(scores) / labels.shape[-1], mask)


def msle(labels, preout, activation="identity", mask=None):
    out = _activated(preout, activation)
    # labels are clamped like predictions: log1p(x) for x <= -1 is -inf/nan
    scores = (jnp.log1p(jnp.maximum(out, -1 + EPS))
              - jnp.log1p(jnp.maximum(labels, -1 + EPS))) ** 2
    return _apply_mask(_per_example(scores) / labels.shape[-1], mask)


_REGISTRY.update(
    {
        "mcxent": mcxent,
        "xent": xent,
        "negativeloglikelihood": negativeloglikelihood,
        "mse": mse,
        "l2": l2,
        "mae": mae,
        "l1": l1,
        "hinge": hinge,
        "squared_hinge": squared_hinge,
        "kl_divergence": kl_divergence,
        "reconstruction_crossentropy": xent,
        "cosine_proximity": cosine_proximity,
        "poisson": poisson,
        "mape": mape,
        "msle": msle,
    }
)
