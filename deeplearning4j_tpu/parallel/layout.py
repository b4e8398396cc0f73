"""MeshLayout: ONE sharding layer under training AND serving (dp×fsdp×tp).

`parallel/` grew four overlapping scale paths (wrapper, param_server,
training_master, pipeline), each doing its own mesh handling, and none of
them could shard *parameters* over a data-parallel axis — the largest
trainable model was bounded by one chip's HBM. This module is the single
GSPMD-style layout authority (per Xu et al., *GSPMD*; ZeRO-style parameter
sharding per Rajbhandari et al., *ZeRO*) the ROADMAP tentpole names:

- **One named mesh** with ``("data", "fsdp", "tp", "seq", "pipe")`` axes.
  Any axis of size 1 collapses out of the emitted PartitionSpecs (the mesh
  keeps all names so specs stay portable across layouts).
- **Parameter-name→spec assignment** in the style of SNIPPETS.md [2]
  (``SpecLayout``): 2-D+ kernels shard their last dim over ``tp`` when
  divisible and a divisible non-tp dim over ``fsdp``; 1-D vectors follow
  the legacy tp rule; exactly-3-D expert-stacked MoE weights shard dim 0
  over an expert axis. Optimizer moments mirror their param's shape, so the
  same shape rule lands them on the same spec ("moments follow their
  param").
- **Batch sharding** over ``data×fsdp`` (the ZeRO convention: fsdp ranks
  see different data; GSPMD inserts the per-step all-gather of params and
  reduce-scatter of gradients).
- **Precision policy**: ``params_dtype="bfloat16"`` carries parameters,
  gradients and optimizer moments in bf16 *storage* while the forward/
  backward compute (and the loss/psum accumulation) runs in f32 — the
  promoted form of the ``__graft_entry__`` §8 dryrun. bf16 leaves shard
  exactly like f32 ones, so fsdp + bf16 compound: per-device param bytes
  drop by ``2 × fsdp`` and gradient all-reduce bytes halve.

ParallelWrapper, the TrainingMasters and the serving stack
(`runtime/inference.py`, `serving/service.py`) are thin strategy wrappers
over this class — none of them constructs a NamedSharding/PartitionSpec of
its own. Every layout is validated by the DT008 ``check_partition_specs``
rule (here via :meth:`MeshLayout.validate`, and automatically at
``CompileManager.aot`` admission for any executable compiled with sharded
arguments). See docs/distributed.md.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["MeshLayout", "PrecisionPolicy", "layout_of"]


@dataclass(frozen=True)
class PrecisionPolicy:
    """Storage-vs-compute dtype contract of a layout.

    ``params_dtype`` is what parameter/gradient/moment *leaves* are stored
    (and communicated) in; ``compute_dtype`` is what the forward/backward
    math runs in (``nn.multilayer._compute_cast`` upcasts bf16 storage to
    f32 per step when they differ — loss and reductions accumulate in f32).
    """

    params_dtype: Optional[str] = None  # None = keep the model's own dtype
    compute_dtype: str = "float32"
    # loss scaling for sub-f32 grad flow: gradients transit the storage
    # dtype (the cast transpose), so small cotangents flush to zero in
    # bf16/f16. None = auto: DEFAULT_LOSS_SCALE under a sub-f32
    # params_dtype, no scaling otherwise. Keep explicit values a power of
    # two — the exponent shift is then bit-exact through scale/unscale.
    loss_scale: Optional[float] = None

    #: power-of-two default applied when ``params_dtype`` is sub-f32
    DEFAULT_LOSS_SCALE = 4096.0

    def effective_loss_scale(self) -> Optional[float]:
        """The loss scale this policy implies (explicit, or the sub-f32
        default, or None when storage is full precision)."""
        if self.loss_scale:
            return float(self.loss_scale)
        if self.params_dtype in ("bfloat16", "float16"):
            return self.DEFAULT_LOSS_SCALE
        return None

    def apply_to_net(self, net) -> None:
        """Stamp the policy onto a net: conf carries it forward (JSON
        round-trips), and already-initialized params/opt-state leaves are
        cast to the storage dtype in place."""
        if self.params_dtype is None:
            return
        import jax
        import jax.numpy as jnp

        net.conf.params_dtype = self.params_dtype
        net.conf.loss_scale = self.effective_loss_scale()
        # the compiled step closed over the old loss_scale/update island
        net._train_step = None
        if net.params is None:
            return

        target = jnp.dtype(self.params_dtype)

        def cast(a):
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) \
                    and a.dtype != target:
                return a.astype(target)
            return a

        net.params = jax.tree_util.tree_map(cast, net.params)
        if net.opt_state is not None:
            # moments mirror their param's storage (scalar counts stay int)
            net.opt_state = jax.tree_util.tree_map(cast, net.opt_state)

    def describe(self) -> dict:
        return {"params_dtype": self.params_dtype,
                "compute_dtype": self.compute_dtype,
                "loss_scale": self.effective_loss_scale()}


def _is_spec(x) -> bool:
    from jax.sharding import PartitionSpec

    return isinstance(x, PartitionSpec)


def layout_of(net) -> Optional["MeshLayout"]:
    """The MeshLayout a net was sharded with (``MeshLayout.apply``), or
    None — how the serving fast path discovers mesh placement."""
    return getattr(net, "_mesh_layout", None)


class MeshLayout:
    """One named mesh + the spec rules every scale path shares."""

    def __init__(self, data: Optional[int] = None, fsdp: int = 1, tp: int = 1,
                 seq: int = 1, pipe: int = 1, *,
                 devices: Optional[Sequence] = None,
                 params_dtype: Optional[str] = None,
                 loss_scale: Optional[float] = None, zero_stage: int = 3,
                 roles: bool = False):
        import jax
        from jax.sharding import Mesh

        fsdp, tp, seq, pipe = int(fsdp), int(tp), int(seq), int(pipe)
        if fsdp < 1 or tp < 1 or seq < 1 or pipe < 1:
            raise ValueError(
                f"axis sizes must be >= 1, got fsdp={fsdp} tp={tp} "
                f"seq={seq} pipe={pipe}")
        devs = list(devices) if devices is not None else jax.devices()
        if data is None:
            data = max(1, len(devs) // (fsdp * tp * seq * pipe))
        data = int(data)
        need = data * fsdp * tp * seq * pipe
        if need > len(devs):
            raise ValueError(
                f"layout data={data} x fsdp={fsdp} x tp={tp} x seq={seq} "
                f"x pipe={pipe} needs {need} devices, have {len(devs)}")
        arr = np.array(devs[:need]).reshape(data, fsdp, tp, seq, pipe)
        self.mesh = Mesh(arr, axis_names=("data", "fsdp", "tp", "seq",
                                          "pipe"))
        self._init_axes({"data": data, "fsdp": fsdp, "tp": tp, "seq": seq,
                         "pipe": pipe},
                        params_dtype=params_dtype, loss_scale=loss_scale,
                        zero_stage=zero_stage, roles=roles)

    def _init_axes(self, sizes: dict, *, params_dtype: Optional[str],
                   loss_scale: Optional[float] = None,
                   zero_stage: int, canonical: bool = True,
                   model_axis: Optional[str] = None,
                   expert_axis: Optional[str] = None,
                   roles: bool = False) -> None:
        if int(zero_stage) not in (1, 3):
            raise ValueError(
                f"zero_stage must be 1 (moments-only fsdp sharding) or 3 "
                f"(params+grads+moments), got {zero_stage}")
        self._axis_sizes = {str(a): int(s) for a, s in sizes.items()}
        if canonical:
            # the canonical dp x fsdp x tp mesh: size-1 axes collapse out
            self._batch_axes = tuple(
                a for a in ("data", "fsdp") if self._axis_sizes.get(a, 1) > 1)
            self._fsdp_axis = "fsdp" if self._axis_sizes.get("fsdp", 1) > 1 \
                else None
            self._tp_axis = "tp" if self._axis_sizes.get("tp", 1) > 1 else None
            self._expert_axis = None
            self._seq_axis = ("seq" if self._axis_sizes.get("seq", 1) > 1
                              else None)
            self._pipe_axis = ("pipe" if self._axis_sizes.get("pipe", 1) > 1
                               else None)
        else:
            # legacy from_mesh semantics: every non-model/expert axis is a
            # batch axis, size-1 included (spec spellings feed cache keys).
            # An axis literally named "pipe" carries pipeline stages, never
            # batch rows — the legacy GPipe path's silent divergence was
            # exactly a hand-rolled rule set that had to know this.
            self._batch_axes = tuple(
                a for a in self._axis_sizes
                if a not in (model_axis, expert_axis, "pipe"))
            self._fsdp_axis = "fsdp" if (
                self._axis_sizes.get("fsdp", 1) > 1
                and "fsdp" not in (model_axis, expert_axis)) else None
            self._tp_axis = model_axis
            self._expert_axis = expert_axis
            self._seq_axis = ("seq" if (
                self._axis_sizes.get("seq", 1) > 1
                and "seq" not in (model_axis, expert_axis)) else None)
            if self._seq_axis is not None:
                self._batch_axes = tuple(
                    a for a in self._batch_axes if a != "seq")
            self._pipe_axis = "pipe" if "pipe" in self._axis_sizes else None
        self.zero_stage = int(zero_stage)
        self.precision = PrecisionPolicy(params_dtype=params_dtype,
                                         loss_scale=loss_scale)
        self.roles = bool(roles)
        # layer-semantics binding (MeshLayout.bind): path-suffix
        # (layer key, param name) -> (role, layer). None until bound.
        self._role_map = None
        self._role_ctx: dict = {}
        self._role_sites: List[dict] = []

    @classmethod
    def from_mesh(cls, mesh, model_axis: Optional[str] = None,
                  expert_axis: Optional[str] = None,
                  params_dtype: Optional[str] = None,
                  loss_scale: Optional[float] = None,
                  zero_stage: int = 3) -> "MeshLayout":
        """Wrap an existing mesh (the legacy ParallelWrapper construction
        path): ``model_axis`` plays the tp role, ``expert_axis`` enables the
        MoE expert-stacked rule, every other axis is a batch axis. A named
        axis absent from the mesh raises — a typo must fail loudly, not
        silently train replicated."""
        self = cls.__new__(cls)
        for ax, label in ((model_axis, "model_axis"),
                          (expert_axis, "expert_axis")):
            if ax is not None and ax not in mesh.shape:
                raise ValueError(
                    f"{label} '{ax}' not in mesh axes {tuple(mesh.shape)}")
        self.mesh = mesh
        self._init_axes(dict(mesh.shape), params_dtype=params_dtype,
                        loss_scale=loss_scale,
                        zero_stage=zero_stage, canonical=False,
                        model_axis=model_axis, expert_axis=expert_axis)
        return self

    @classmethod
    def abstract(cls, data: int = 1, fsdp: int = 1, tp: int = 1,
                 seq: int = 1, pipe: int = 1, *,
                 params_dtype: Optional[str] = None,
                 loss_scale: Optional[float] = None,
                 zero_stage: int = 3, roles: bool = False) -> "MeshLayout":
        """A device-less layout: pure spec algebra (``param_spec``,
        ``batch_spec``, the sharding-flow pass) with NO jax mesh behind it —
        the CLI ``--mesh`` flag analyzes a 64-chip layout from a laptop.
        Methods that place real data (``sharding``/``put``/``apply``)
        raise."""
        self = cls.__new__(cls)
        self.mesh = None
        self._init_axes({"data": int(data), "fsdp": int(fsdp),
                         "tp": int(tp), "seq": int(seq), "pipe": int(pipe)},
                        params_dtype=params_dtype, loss_scale=loss_scale,
                        zero_stage=zero_stage, roles=roles)
        return self

    # ------------------------------------------------------------ geometry
    @property
    def axis_sizes(self) -> dict:
        return dict(self._axis_sizes)

    def _size(self, axis: Optional[str]) -> int:
        return int(self._axis_sizes.get(axis, 1)) if axis is not None else 1

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return self._batch_axes

    @property
    def batch_factor(self) -> int:
        """How many ways the batch dim shards (global batch must divide it)."""
        return int(np.prod([self.mesh.shape[a] for a in self._batch_axes],
                           dtype=np.int64)) if self._batch_axes else 1

    @property
    def pipe_axis(self) -> Optional[str]:
        return self._pipe_axis

    @property
    def pipe_size(self) -> int:
        """Pipeline stage count (1 = no pipe axis)."""
        return self._size(self._pipe_axis) if self._pipe_axis else int(
            self._axis_sizes.get("pipe", 1))

    @property
    def num_devices(self) -> int:
        if self.mesh is None:  # abstract layout: the sizes ARE the geometry
            return int(np.prod(list(self._axis_sizes.values()),
                               dtype=np.int64))
        return int(self.mesh.devices.size)

    # ---------------------------------------------------------------- specs
    def batch_spec(self):
        """Dim-0 (batch/replica) spec over every batch axis (data×fsdp)."""
        from jax.sharding import PartitionSpec as P

        return P(self._batch_axes) if self._batch_axes else P()

    def staged_batch_spec(self):
        """Spec for staged windows/groups ``[K, B, ...]`` — batch dim is 1."""
        from jax.sharding import PartitionSpec as P

        return P(None, self._batch_axes) if self._batch_axes else P()

    def input_spec(self, ndim: Optional[int] = None):
        """Spec for one input/label tensor: dim 0 over the batch axes, and —
        under an active seq axis — dim 1 (time, ``[B, T, ...]``) over
        ``seq``. Rank-2-or-less tensors (and layouts without a seq axis)
        fall back to :meth:`batch_spec`."""
        from jax.sharding import PartitionSpec as P

        if self._seq_axis is not None and ndim is not None and ndim >= 3:
            return P(self._batch_axes or None, self._seq_axis)
        return self.batch_spec()

    def stage_spec(self, shape=None):
        """Spec for a stage-stacked leaf ``[P, ...]``: dim 0 over the pipe
        axis, every other dim replicated — the one rule the pipeline path
        shares with everything else (``pipeline_shardings`` routes here
        instead of hand-building NamedShardings)."""
        from jax.sharding import PartitionSpec as P

        if self._pipe_axis is None and "pipe" not in self._axis_sizes:
            raise ValueError(
                "stage_spec needs a pipe axis; this layout has axes "
                f"{tuple(self._axis_sizes)}")
        return P("pipe")

    def stage_specs(self, tree):
        """PartitionSpec pytree for a stage-stacked param tree."""
        import jax

        return jax.tree_util.tree_map(
            lambda a: self.stage_spec(np.shape(a)), tree)

    def input_sharding(self, arr=None):
        """NamedSharding for one input tensor (:meth:`input_spec` of its
        rank — pass the array/struct, or nothing for the plain batch
        sharding)."""
        ndim = len(np.shape(arr)) if arr is not None else None
        return self.sharding(self.input_spec(ndim))

    def param_spec(self, shape) -> "Any":
        """The fsdp/tp/expert rule set for one parameter shape:

        - exactly-3-D leaves whose dim 0 divides an expert axis (MoE
          expert-stacked ``[E, F, H]``) shard dim 0 over it;
        - 2-D+ kernels shard the last dim over ``tp`` when divisible, then
          the first remaining divisible dim over ``fsdp``;
        - 1-D vectors shard over ``fsdp`` when divisible (ZeRO shards
          biases too — and GSPMD's own propagation picks exactly this
          placement, so declaring it keeps executable outputs at the
          declared specs: zero warm recompiles), else over ``tp`` when
          divisible (legacy parity);
        - everything else replicates.

        Under ``zero_stage=1`` params (and so grads) skip the fsdp rule and
        stay replicated over the fsdp axis — only optimizer moments shard
        (:meth:`opt_spec`): the cheaper default for small meshes where the
        per-step ZeRO param all-gather costs more than it saves.
        """
        return self._shape_spec(
            shape, with_fsdp=(self.zero_stage >= 3))

    def opt_spec(self, shape) -> "Any":
        """Spec for one optimizer-moment leaf: the FULL fsdp/tp rule at
        every zero stage — ZeRO-1 shards the moments even while params
        replicate (that is its entire point: Adam moments are 2x param
        bytes and nothing in the step needs them gathered)."""
        return self._shape_spec(shape, with_fsdp=True)

    def _shape_spec(self, shape, *, with_fsdp: bool,
                    with_tp: bool = True) -> "Any":
        from jax.sharding import PartitionSpec as P

        shape = tuple(int(s) for s in shape)
        esize = self._size(self._expert_axis)
        tsize = self._size(self._tp_axis) if with_tp else 1
        fsize = self._size(self._fsdp_axis) if with_fsdp else 1
        if (self._expert_axis and len(shape) == 3 and esize > 1
                and shape[0] % esize == 0 and shape[0] >= esize):
            return P(self._expert_axis, *([None] * (len(shape) - 1)))
        entries: List[Any] = [None] * len(shape)
        if len(shape) >= 2:
            if tsize > 1 and shape[-1] > 0 and shape[-1] % tsize == 0:
                entries[-1] = self._tp_axis
            if fsize > 1:
                for d, size in enumerate(shape):
                    if entries[d] is None and size % fsize == 0 \
                            and size >= fsize:
                        entries[d] = self._fsdp_axis
                        break
        elif len(shape) == 1:
            if fsize > 1 and shape[0] % fsize == 0 and shape[0] >= fsize:
                entries[0] = self._fsdp_axis
            elif tsize > 1 and shape[0] % tsize == 0 and shape[0] >= tsize:
                entries[0] = self._tp_axis
        while entries and entries[-1] is None:
            entries.pop()  # canonical form: P() not P(None,) — GSPMD emits
            #               the trimmed spelling, and cache keys compare it
        return P(*entries)

    # ------------------------------------------------------------ shardings
    def sharding(self, spec):
        from jax.sharding import NamedSharding

        if self.mesh is None:
            raise RuntimeError(
                "this MeshLayout is abstract (MeshLayout.abstract): it can "
                "compute specs and run the sharding-flow analysis but has "
                "no devices to build a NamedSharding on")
        return NamedSharding(self.mesh, spec)

    def replicated(self):
        from jax.sharding import PartitionSpec as P

        return self.sharding(P())

    def batch_sharding(self):
        return self.sharding(self.batch_spec())

    def staged_batch_sharding(self):
        return self.sharding(self.staged_batch_spec())

    def replica_sharding(self):
        """Leading-replica-axis sharding for the periodic-averaging mode
        (one independent replica per batch-axis slot). tp/expert layouts
        have no replica semantics — :class:`ParallelWrapper` refuses the
        combination before this is ever called."""
        if self._tp_axis is not None or self._expert_axis is not None:
            raise ValueError(
                "replica (periodic-averaging) placement is undefined for "
                "tp/expert layouts; use sync mode (averaging_frequency=1)")
        return self.batch_sharding()

    # ------------------------------------------------------ role resolution
    def bind(self, net) -> "MeshLayout":
        """Resolve the layer-semantics registry against ``net``'s layers
        (``roles=True`` layouts only — a no-op otherwise): every param whose
        layer declares a role gets a role-resolved spec keyed by its tree
        path suffix ``(layer key, param name)``, so optimizer moments (and
        any shape-mirroring tree) follow their param's role. Divisibility
        is checked here — ``apply``/``validate``/``describe`` all reject a
        tp size that does not divide a head count or row dim instead of
        silently falling back (:class:`roles.RoleDivisibilityError`)."""
        if not self.roles:
            return self
        from . import roles as R

        conf = net.conf
        if hasattr(conf, "vertices"):
            items = [(str(k), getattr(v, "layer", v))
                     for k, v in conf.vertices.items()]
        else:
            items = [(str(i), l) for i, l in enumerate(conf.layers)]
        tsize = self._size(self._tp_axis)
        role_map: dict = {}
        role_ctx: dict = {}
        sites: List[dict] = []
        prev = None
        for key, layer in items:
            # ffn_down is row-parallel ONLY when the producing stage is
            # feature-local math (attention/dense): after an LSTM scan the
            # row-parallel backward would send a tp-sharded cotangent into
            # every scan step — replicate the head over tp instead
            ctx = {"after_scan": prev is not None
                   and "LSTM" in type(prev).__name__}
            prev = layer
            rmap = R.roles_for(layer)
            if not any(r != R.GENERIC for r in rmap.values()):
                continue
            role_map[key] = layer
            role_ctx[key] = ctx
            for pname, role in sorted(rmap.items()):
                if role == R.GENERIC:
                    continue
                sites.append({"layer": key,
                              "layer_type": type(layer).__name__,
                              "param": pname, "role": role, **ctx})
                # early divisibility rejection for checks that need only
                # layer attrs (n_heads); shape-dependent ones re-check at
                # spec resolution
                if role in R.HEAD_AWARE_ROLES:
                    heads = getattr(layer, "n_heads", None)
                    if heads is not None and tsize > 1 \
                            and int(heads) % tsize != 0:
                        R.check_role_site(layer, key, pname, role, (),
                                          tsize)
        self._role_map = role_map
        self._role_ctx = role_ctx
        self._role_sites = sites
        return self

    @property
    def role_sites(self) -> List[dict]:
        """Every (layer, param, role) the binding resolved — empty until
        :meth:`bind` (``apply`` binds automatically)."""
        return list(self._role_sites)

    def role_resolved_types(self) -> set:
        """Layer type names whose params resolved through a HEAD-AWARE role
        rule (attention_qkv/attention_out/lstm_gates) — the DT305 advisory
        skips these sites."""
        from . import roles as R

        return {s["layer_type"] for s in self._role_sites
                if s["role"] in R.HEAD_AWARE_ROLES}

    def _path_site(self, path):
        """(layer key, param name) from a tree-path SUFFIX, or None. Param
        trees end ``(..., layer key, param name)`` on both net classes —
        and optax moment trees mirror params, so the same suffix matches
        ``mu``/``nu`` leaves without knowing the optimizer's structure."""
        if self._role_map is None or len(path) < 2:
            return None
        name_k, layer_k = path[-1], path[-2]
        name = getattr(name_k, "key", None)
        if not isinstance(name, str):
            return None
        layer = getattr(layer_k, "key", None)
        if layer is None:
            layer = getattr(layer_k, "idx", None)
        if layer is None:
            return None
        return (str(layer), name)

    def _resolve_leaf_spec(self, path, shape, *, with_fsdp: bool):
        """Role spec for one leaf when bound and matched, else the generic
        shape rule."""
        site = self._path_site(path)
        if site is not None:
            layer = self._role_map.get(site[0])
            if layer is not None:
                from . import roles as R

                role = R.role_of(layer, site[1])
                if role is not None and role != R.GENERIC:
                    ctx = getattr(self, "_role_ctx", {}).get(site[0]) or {}
                    R.check_role_site(layer, site[0], site[1], role, shape,
                                      self._size(self._tp_axis), ctx=ctx)
                    spec = R.resolve_role_spec(self, role, site[1], shape,
                                               with_fsdp=with_fsdp, ctx=ctx)
                    if spec is not None:
                        return spec
        return self._shape_spec(shape, with_fsdp=with_fsdp)

    def _spec_tree(self, tree, *, with_fsdp: bool):
        import jax

        if self._role_map is None:
            return jax.tree_util.tree_map(
                lambda a: self._shape_spec(np.shape(a),
                                           with_fsdp=with_fsdp), tree)
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [self._resolve_leaf_spec(p, np.shape(l),
                                              with_fsdp=with_fsdp)
                      for p, l in flat])

    def param_specs(self, tree):
        """PartitionSpec pytree for params — or any shape-mirroring tree
        (scalar bookkeeping replicates). Role-resolved per site after
        :meth:`bind`; the generic shape rules otherwise."""
        return self._spec_tree(tree, with_fsdp=(self.zero_stage >= 3))

    def param_shardings(self, tree):
        import jax

        return jax.tree_util.tree_map(
            self.sharding, self.param_specs(tree),
            is_leaf=_is_spec)

    def opt_specs(self, tree):
        """PartitionSpec pytree for optimizer state (moments follow their
        param's shape rule — and, once bound, their param's ROLE — at
        zero_stage=3; ZeRO-1 shards them over fsdp while params
        replicate)."""
        return self._spec_tree(tree, with_fsdp=True)

    def opt_shardings(self, tree):
        import jax

        return jax.tree_util.tree_map(
            self.sharding, self.opt_specs(tree),
            is_leaf=_is_spec)

    # -------------------------------------------------------------- devices
    def put(self, arr, sharding=None):
        """Place host data on the mesh (multi-process safe — delegates to
        :func:`parallel.mesh.global_put`). Default: batch sharding."""
        from .mesh import global_put

        return global_put(arr, sharding if sharding is not None
                          else self.batch_sharding())

    def put_params(self, tree):
        """device_put a param-shaped pytree leaf-wise on its layout specs
        (role-resolved per site once :meth:`bind` ran)."""
        import jax

        from .mesh import global_put

        return jax.tree_util.tree_map(
            lambda a, s: global_put(a, self.sharding(s)),
            tree, self.param_specs(tree))

    def put_opt_state(self, tree):
        """device_put optimizer state on its moment specs (= param specs at
        zero_stage=3; fsdp-sharded even under ZeRO-1)."""
        import jax

        from .mesh import global_put

        return jax.tree_util.tree_map(
            lambda a, s: global_put(a, self.sharding(s)),
            tree, self.opt_specs(tree))

    def put_replicated(self, tree):
        import jax

        from .mesh import global_put

        rep = self.replicated()
        return jax.tree_util.tree_map(lambda a: global_put(a, rep), tree)

    # ------------------------------------------------------------- networks
    def apply(self, net) -> "MeshLayout":
        """Make ``net`` live on this layout: apply the precision policy,
        shard params + optimizer state by the rule set (state replicates),
        and stamp the layout so the serving fast path (and a later
        ParallelWrapper) discovers the placement. Idempotent."""
        import jax

        if self._pipe_axis is not None:
            raise ValueError(
                f"pipe={self._size(self._pipe_axis)} stages layers across "
                "devices — generic leaf-wise placement cannot express it. "
                "Use parallel.pipeline.PipelinedTrainer(net, layout) for "
                "pipelined training")
        net.init()
        self.bind(net)
        if self._seq_axis is not None:
            self._install_seq(net)
        self.precision.apply_to_net(net)
        net.params = self.put_params(net.params)
        if net.opt_state is not None:
            net.opt_state = self.put_opt_state(net.opt_state)
        if jax.tree_util.tree_leaves(net.state):
            net.state = self.put_replicated(net.state)
        if getattr(net, "_mesh_layout", None) is not self:
            # programs traced for another placement must not be reused:
            # kernel variants are chosen per placement (a Mosaic kernel
            # picked for one device cannot be partitioned over this mesh)
            net._invalidate_compiled()
        net._mesh_layout = self
        return self

    def _install_seq(self, net) -> None:
        """Wire the sequence axis: attention layers route q/k/v through the
        shard_map ring/all-to-all kernels (``parallel/ring_attention.py``)
        on this mesh — the escape hatch where GSPMD's own propagation would
        reshard K/V every block. Recurrent scan layers consume time
        sequentially, so a seq axis cannot shard their scan — reject loudly
        instead of silently training with per-step resharding."""
        conf = net.conf
        if hasattr(conf, "vertices"):
            layers = [getattr(v, "layer", v) for v in conf.vertices.values()]
        else:
            layers = list(conf.layers)
        recurrent = [type(l).__name__ for l in layers
                     if "LSTM" in type(l).__name__]
        if recurrent:
            raise ValueError(
                f"seq={self._size(self._seq_axis)} shards the time dim, but "
                f"{', '.join(sorted(set(recurrent)))} consumes time "
                "sequentially inside lax.scan — the seq axis supports "
                "attention nets (ring/all-to-all sequence parallelism); "
                "use data/fsdp/tp for recurrent nets")
        if any(hasattr(l, "n_heads") for l in layers):
            from ..nn.layers.attention import set_attention_mesh

            set_attention_mesh(self.mesh, "seq", nets=(net,),
                               batch_axes=self._batch_axes)

    def shard_params(self, net):
        """:meth:`apply` returning the param sharding pytree (checkpoint
        restore wants it) — the layout twin of the legacy
        ``parallel.sharding.shard_params``."""
        self.apply(net)
        return self.param_shardings(net.params)

    # ------------------------------------------------------------ validation
    def validate(self, params=None, *, net=None,
                 source: str = "<MeshLayout>"):
        """DT008 ``check_partition_specs`` over this layout's param specs
        (axis membership, duplicate axes, divisibility when ``params`` is
        given). Role-resolved specs are validated too: pass ``net`` (or
        :meth:`bind` first) and a tp size that does not divide a head count
        or row dim comes back as an ERROR finding naming the layer and dim
        instead of silently falling back. Returns analysis findings — empty
        means clean."""
        from ..analysis import check_partition_specs

        findings = []
        if net is not None and self.roles and self._role_map is None:
            try:
                self.bind(net)
            except ValueError as e:
                from ..analysis.rules import get_rule

                return [get_rule("DT008").finding(str(e), file=source,
                                                  context="roles")]
        tree = params if params is not None else {}
        try:
            specs = self.param_specs(tree) if params is not None else {}
        except ValueError as e:
            from ..analysis.rules import get_rule

            return [get_rule("DT008").finding(str(e), file=source,
                                              context="roles")]
        findings += check_partition_specs(specs, self.mesh, params,
                                          source=source)
        return findings

    # ------------------------------------------------------- fsdp HBM math
    def _leaf_bytes(self, leaf, *, storage: bool, sharded: bool,
                    spec_fn=None) -> float:
        import jax.numpy as jnp

        shape = getattr(leaf, "shape", None)
        if shape is None:
            return 0.0
        dt = np.dtype(leaf.dtype)
        if storage and self.precision.params_dtype is not None \
                and jnp.issubdtype(dt, np.floating):
            dt = np.dtype(self.precision.params_dtype)
        n = float(np.prod(shape, dtype=np.float64)) * dt.itemsize
        if not sharded:
            return n
        factor = 1
        for entry in tuple((spec_fn or self.param_spec)(shape)):
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
                factor *= self._size(ax)
        return n / factor

    def _activation_factor(self, shape, activation_factors=None) -> int:
        """Shard factor of one activation shape: the propagated spec from
        the sharding-flow pass when available (tp-sharded hidden dims count
        — the PR 9 preflight bugfix), else the batch factor."""
        shape = tuple(int(s) for s in shape or ())
        if activation_factors:
            f = activation_factors.get(shape)
            if f:
                return int(f)
        return self.batch_factor

    def sharded_totals(self, net, report: dict,
                       activation_factors: Optional[dict] = None) -> dict:
        """Per-device byte projection of a :func:`telemetry.memory_report`
        under this layout — the fsdp HBM math ``preflight(layout=...)``
        checks against the budget:

        - params/grads divide by each leaf's ``param_spec`` factor (under
          ZeRO-1 that factor has no fsdp term — params replicate), moments
          by their ``opt_spec`` factor, and both drop to the storage dtype
          under the precision policy;
        - activations divide by their PROPAGATED shard factor when the
          sharding-flow pass supplied one (``activation_factors``: shape ->
          factor — a tp-sharded hidden activation counts its tp split, the
          bug the old batch-factor-only projection had), else by the batch
          factor; inputs divide by the batch factor.
        """
        import jax

        def _tree_bytes(tree, spec_tree):
            leaves = jax.tree_util.tree_leaves(tree)
            specs = jax.tree_util.tree_leaves(spec_tree, is_leaf=_is_spec)
            return sum(self._leaf_bytes(l, storage=True, sharded=True,
                                        spec_fn=lambda _s, s=s: s)
                       for l, s in zip(leaves, specs))

        # per-leaf spec TREES, not shape rules: once a net is bound, two
        # same-shaped params can resolve to different role specs
        p_pd = _tree_bytes(net.params, self.param_specs(net.params))
        o_pd = _tree_bytes(net.opt_state, self.opt_specs(net.opt_state))
        bf = self.batch_factor
        act_pd = 0.0
        rows = report.get("layers") or []
        for row in rows:
            act_pd += row["activation_bytes"] / self._activation_factor(
                row.get("activation_shape"), activation_factors)
        if not rows:
            act_pd = report["totals"]["activation_bytes"] / bf
        in_pd = report["totals"]["input_bytes"] / bf
        projected = 2 * p_pd + o_pd + act_pd + in_pd
        return {
            "param_bytes": int(p_pd),
            "grad_bytes": int(p_pd),
            "opt_state_bytes": int(o_pd),
            "activation_bytes": int(act_pd),
            "input_bytes": int(in_pd),
            "projected_peak_bytes": int(projected),
            "batch_factor": bf,
            "zero_stage": self.zero_stage,
        }

    # ---------------------------------------------------------------- misc
    def describe(self) -> dict:
        """JSON-ready layout summary (serving stats / flight events). A
        bound roles layout lists its resolved sites; binding already
        rejected non-divisible tp sizes, so a describable layout is a
        valid one."""
        out = {
            "axes": self.axis_sizes,
            "batch_axes": list(self._batch_axes),
            "fsdp_axis": self._fsdp_axis,
            "tp_axis": self._tp_axis,
            "seq_axis": self._seq_axis,
            "pipe_axis": self._pipe_axis,
            "expert_axis": self._expert_axis,
            "devices": self.num_devices,
            "zero_stage": self.zero_stage,
            "roles": self.roles,
            "precision": self.precision.describe(),
        }
        if self._role_map is not None:
            out["role_sites"] = self.role_sites
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        sizes = "x".join(f"{a}={s}" for a, s in self.axis_sizes.items())
        return f"MeshLayout({sizes}, params_dtype={self.precision.params_dtype})"
