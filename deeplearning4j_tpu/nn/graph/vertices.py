"""Graph vertices: the DAG building blocks beyond layers.

Reference parity: nn/conf/graph/* (configs) + nn/graph/vertex/impl/* (impls) —
ElementWise, Merge, Subset, Stack, Unstack, Scale, L2, L2Normalize,
Preprocessor, LastTimeStep, DuplicateToTimeSeries (SURVEY.md §2.1
"Graph vertices"). As with layers, one dataclass per vertex is both the
JSON-serializable config and the pure forward function; every ``doBackward``
comes from autodiff.

Vertex SPI:
- ``get_output_type(*input_types)`` — static shape inference
- ``init_params(key, *input_types)`` / ``init_state(*input_types)``
- ``apply(params, inputs, state, train, rng, masks)`` — ``inputs`` is the list
  of activations from this vertex's declared input vertices, in order.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from ..layers.base import BaseLayer, Params, State, layer_from_dict

VERTEX_REGISTRY: Dict[str, Type["BaseVertex"]] = {}


def register_vertex(cls):
    VERTEX_REGISTRY[cls.__name__] = cls
    return cls


def vertex_from_dict(d: dict) -> "BaseVertex":
    d = dict(d)
    type_name = d.pop("@type")
    cls = VERTEX_REGISTRY.get(type_name)
    if cls is None:
        raise ValueError(f"Unknown vertex type '{type_name}'. Known: {sorted(VERTEX_REGISTRY)}")
    return cls._from_dict_fields(d)


def _jsonify(v):
    if isinstance(v, tuple):
        return [_jsonify(x) for x in v]
    if isinstance(v, dict):
        return {k: _jsonify(x) for k, x in v.items()}
    return v


@dataclass
class BaseVertex:
    """Vertex SPI (reference: nn/graph/vertex/GraphVertex.java)."""

    def to_dict(self) -> dict:
        d = {"@type": type(self).__name__}
        for f in dataclasses.fields(self):
            d[f.name] = _jsonify(getattr(self, f.name))
        return d

    @classmethod
    def _from_dict_fields(cls, d: dict) -> "BaseVertex":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    # ---- SPI ----
    @property
    def has_params(self) -> bool:
        return False

    @property
    def is_output_layer(self) -> bool:
        return False

    def get_output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def init_params(self, key: jax.Array, *input_types: InputType) -> Params:
        return {}

    def init_state(self, *input_types: InputType) -> State:
        return {}

    def regularization_loss(self, params: Params) -> jnp.ndarray:
        return jnp.asarray(0.0)

    def apply(
        self,
        params: Params,
        inputs: Sequence[jnp.ndarray],
        state: State,
        *,
        train: bool = False,
        rng: Optional[jax.Array] = None,
        masks: Optional[Dict[str, jnp.ndarray]] = None,
    ) -> Tuple[jnp.ndarray, State]:
        raise NotImplementedError

    @property
    def hands_input_on(self) -> bool:
        """Whether a training graph should call :meth:`apply_handing_on`:
        ``((output, first input), state)``, the first input handed on for
        the vertices that read it after this one. What they send back for it
        then reaches this vertex's backward pass beside its output's
        cotangent, and a vertex whose backward pass writes the whole of that
        input's cotangent adds onto it where it lies."""
        return False


@register_vertex
@dataclass
class LayerVertex(BaseVertex):
    """A layer as a graph vertex (reference: nn/conf/graph/LayerVertex.java).

    Single input; an optional input preprocessor runs first, exactly like the
    reference's (layer, preprocessor) pair inside its LayerVertex.
    """

    layer: Optional[BaseLayer] = None
    preprocessor: Optional[object] = None

    def to_dict(self) -> dict:
        return {
            "@type": "LayerVertex",
            "layer": self.layer.to_dict(),
            "preprocessor": self.preprocessor.to_dict() if self.preprocessor else None,
        }

    @classmethod
    def _from_dict_fields(cls, d: dict) -> "LayerVertex":
        from ..conf.preprocessors import preprocessor_from_dict

        return cls(
            layer=layer_from_dict(d["layer"]),
            preprocessor=(
                preprocessor_from_dict(d["preprocessor"]) if d.get("preprocessor") else None
            ),
        )

    @property
    def has_params(self) -> bool:
        return self.layer.has_params

    @property
    def is_output_layer(self) -> bool:
        return self.layer.is_output_layer

    def _preprocessed_type(self, input_type: InputType) -> InputType:
        if self.preprocessor is not None:
            return self.preprocessor.get_output_type(input_type)
        return input_type

    def get_output_type(self, *input_types: InputType) -> InputType:
        assert len(input_types) == 1, "LayerVertex takes exactly one input"
        return self.layer.get_output_type(self._preprocessed_type(input_types[0]))

    def init_params(self, key, *input_types) -> Params:
        return self.layer.init_params(key, self._preprocessed_type(input_types[0]))

    def init_state(self, *input_types) -> State:
        return self.layer.init_state(self._preprocessed_type(input_types[0]))

    def regularization_loss(self, params: Params) -> jnp.ndarray:
        return self.layer.regularization_loss(params)

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]
        if self.preprocessor is not None:
            x = self.preprocessor.apply(x)
        mask = None if masks is None else masks.get("features")
        return self.layer.apply(params, x, state, train=train, rng=rng, mask=mask)

    @property
    def hands_input_on(self) -> bool:
        return self.preprocessor is None \
            and hasattr(self.layer, "apply_handing_on")

    def apply_handing_on(self, params, inputs, state, *, train=False,
                         rng=None, masks=None):
        mask = None if masks is None else masks.get("features")
        return self.layer.apply_handing_on(params, inputs[0], state,
                                           train=train, rng=rng, mask=mask)

    # ---- streaming/TBPTT support (reference: ComputationGraph.rnnTimeStep
    # :1801 routes through each vertex's rnnTimeStep; only layer vertices
    # carry recurrent state) ------------------------------------------------
    @property
    def is_recurrent(self) -> bool:
        return bool(getattr(self.layer, "is_recurrent", False)) and hasattr(
            self.layer, "init_recurrent_state"
        )

    def init_recurrent_state(self, batch: int):
        return self.layer.init_recurrent_state(batch)

    def apply_seq(self, params, inputs, rstate, *, train=False, rng=None, masks=None):
        """Like apply() but threads recurrent h/c state across calls."""
        x = inputs[0]
        if self.preprocessor is not None:
            x = self.preprocessor.apply(x)
        mask = None if masks is None else masks.get("features")
        return self.layer.apply_seq(
            params, x, rstate, mask=mask, train=train, rng=rng
        )

    def pre_output_input(self, inputs):
        x = inputs[0]
        if self.preprocessor is not None:
            x = self.preprocessor.apply(x)
        return x


@register_vertex
@dataclass
class ElementWiseVertex(BaseVertex):
    """Pointwise combine (reference: nn/conf/graph/ElementWiseVertex.java).

    ops: add | subtract (2 inputs) | product | average | max.
    """

    op: str = "add"

    def get_output_type(self, *input_types: InputType) -> InputType:
        first = input_types[0]
        for t in input_types[1:]:
            if t.example_shape() != first.example_shape():
                raise ValueError(
                    f"ElementWiseVertex inputs must have identical shapes, got "
                    f"{[it.example_shape() for it in input_types]}"
                )
        if self.op.lower() == "subtract" and len(input_types) != 2:
            raise ValueError("ElementWise subtract requires exactly 2 inputs")
        return first

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        op = self.op.lower()
        if op == "add":
            out = sum(inputs[1:], start=inputs[0])
        elif op == "subtract":
            if len(inputs) != 2:
                raise ValueError("subtract requires exactly 2 inputs")
            out = inputs[0] - inputs[1]
        elif op == "product":
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
        elif op == "average":
            out = sum(inputs[1:], start=inputs[0]) / len(inputs)
        elif op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(f"Unknown ElementWise op '{self.op}'")
        return out, state


@register_vertex
@dataclass
class MergeVertex(BaseVertex):
    """Concatenate along the feature axis (reference: nn/conf/graph/MergeVertex.java).

    FF: [b, f] on axis 1; RNN: [b, t, f] on axis 2; CNN (NHWC here): channel
    axis = -1. All three are the last axis under this framework's layouts.
    """

    def get_output_type(self, *input_types: InputType) -> InputType:
        first = input_types[0]
        if first.kind == "ff":
            return InputType.feed_forward(sum(t.size for t in input_types))
        if first.kind == "rnn":
            return InputType.recurrent(sum(t.size for t in input_types), first.timesteps)
        if first.kind == "cnn":
            return InputType.convolutional(
                first.height, first.width, sum(t.channels for t in input_types)
            )
        if first.kind == "cnn_flat":
            # flat concat is NOT channel-wise NHWC concat — the result is an
            # opaque feature vector, so type it as such
            return InputType.feed_forward(sum(t.flat_size() for t in input_types))
        raise ValueError(f"MergeVertex: unsupported input kind {first.kind}")

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return jnp.concatenate(list(inputs), axis=-1), state


@register_vertex
@dataclass
class SubsetVertex(BaseVertex):
    """Feature-range slice [from, to] INCLUSIVE (reference: nn/conf/graph/SubsetVertex.java)."""

    from_idx: int = 0
    to_idx: int = 0

    def get_output_type(self, *input_types: InputType) -> InputType:
        n = self.to_idx - self.from_idx + 1
        t = input_types[0]
        if t.kind in ("ff", "cnn_flat"):
            # a slice of a flat vector is a flat vector (apply slices axis -1)
            return InputType.feed_forward(n)
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timesteps)
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width, n)
        raise ValueError(f"SubsetVertex: unsupported input kind {t.kind}")

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return inputs[0][..., self.from_idx : self.to_idx + 1], state


@register_vertex
@dataclass
class StackVertex(BaseVertex):
    """Concatenate along the batch (example) axis (reference: nn/conf/graph/StackVertex.java)."""

    def get_output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return jnp.concatenate(list(inputs), axis=0), state


@register_vertex
@dataclass
class UnstackVertex(BaseVertex):
    """Select batch-slice ``from_idx`` of ``stack_size`` equal slices
    (reference: nn/conf/graph/UnstackVertex.java) — the inverse of StackVertex."""

    from_idx: int = 0
    stack_size: int = 1

    def get_output_type(self, *input_types: InputType) -> InputType:
        return input_types[0]

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_idx * step : (self.from_idx + 1) * step], state


@register_vertex
@dataclass
class ScaleVertex(BaseVertex):
    """Multiply by a fixed scalar (reference: nn/conf/graph/ScaleVertex.java)."""

    scale_factor: float = 1.0

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return inputs[0] * self.scale_factor, state


@register_vertex
@dataclass
class ShiftVertex(BaseVertex):
    """Add a fixed scalar (reference: nn/conf/graph/ShiftVertex.java)."""

    shift: float = 0.0

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return inputs[0] + self.shift, state


@register_vertex
@dataclass
class L2Vertex(BaseVertex):
    """Pairwise L2 distance between two inputs → [batch, 1]
    (reference: nn/conf/graph/L2Vertex.java). ``eps`` keeps the sqrt gradient
    finite at zero distance, as the reference's implementation does."""

    eps: float = 1e-8

    def get_output_type(self, *input_types: InputType) -> InputType:
        return InputType.feed_forward(1)

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        a, b = inputs
        d = (a - b).reshape(a.shape[0], -1)
        return jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps), state


@register_vertex
@dataclass
class L2NormalizeVertex(BaseVertex):
    """x / max(||x||_2, eps) over non-batch dims (reference: nn/conf/graph/L2NormalizeVertex.java)."""

    eps: float = 1e-8

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]
        flat = x.reshape(x.shape[0], -1)
        norm = jnp.sqrt(jnp.sum(flat * flat, axis=1) + self.eps)
        norm = norm.reshape((-1,) + (1,) * (x.ndim - 1))
        return x / norm, state


@register_vertex
@dataclass
class PreprocessorVertex(BaseVertex):
    """A standalone InputPreProcessor as a vertex (reference: nn/conf/graph/PreprocessorVertex.java)."""

    preprocessor: Optional[object] = None

    def to_dict(self) -> dict:
        return {"@type": "PreprocessorVertex", "preprocessor": self.preprocessor.to_dict()}

    @classmethod
    def _from_dict_fields(cls, d: dict) -> "PreprocessorVertex":
        from ..conf.preprocessors import preprocessor_from_dict

        return cls(preprocessor=preprocessor_from_dict(d["preprocessor"]))

    def get_output_type(self, *input_types: InputType) -> InputType:
        return self.preprocessor.get_output_type(input_types[0])

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        return self.preprocessor.apply(inputs[0]), state


@register_vertex
@dataclass
class LastTimeStepVertex(BaseVertex):
    """[b, t, f] → [b, f]: the last *unmasked* timestep per example
    (reference: nn/conf/graph/rnn/LastTimeStepVertex.java). ``mask_input``
    names the network input whose mask [b, t] decides "last"; without a mask
    the final timestep is taken."""

    mask_input: Optional[str] = None

    def get_output_type(self, *input_types: InputType) -> InputType:
        t = input_types[0]
        return InputType.feed_forward(t.size)

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]  # [b, t, f]
        mask = None
        if masks is not None and self.mask_input is not None:
            mask = masks.get(self.mask_input)
        if mask is None:
            return x[:, -1, :], state
        # index of last 1 in each row of mask [b, t]
        idx = x.shape[1] - 1 - jnp.argmax(jnp.flip(mask, axis=1), axis=1)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :], state


@register_vertex
@dataclass
class DuplicateToTimeSeriesVertex(BaseVertex):
    """[b, f] → [b, t, f], broadcasting over the time length of the named
    network input (reference: nn/conf/graph/rnn/DuplicateToTimeSeriesVertex.java).

    ``apply`` receives that reference activation as a SECOND input (the config
    tier wires it in), so the time length is read from a traced shape —
    static under jit, as XLA requires."""

    ts_input: str = ""

    def get_output_type(self, *input_types: InputType) -> InputType:
        f = input_types[0]
        t = input_types[1].timesteps if len(input_types) > 1 else None
        return InputType.recurrent(f.size, t)

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]  # [b, f]
        t = inputs[1].shape[1]  # reference series [b, t, ...]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], t, x.shape[1])), state


@register_vertex
@dataclass
class ReshapeVertex(BaseVertex):
    """Reshape non-batch dims (reference: nn/conf/graph/ReshapeVertex.java)."""

    shape: Tuple[int, ...] = ()

    def get_output_type(self, *input_types: InputType) -> InputType:
        s = tuple(self.shape)
        if len(s) == 1:
            return InputType.feed_forward(s[0])
        if len(s) == 2:
            return InputType.recurrent(s[1], s[0])
        if len(s) == 3:
            return InputType.convolutional(s[0], s[1], s[2])
        raise ValueError(f"ReshapeVertex: unsupported target shape {s}")

    def apply(self, params, inputs, state, *, train=False, rng=None, masks=None):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape)), state
