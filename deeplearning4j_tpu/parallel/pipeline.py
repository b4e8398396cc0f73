"""Pipeline parallelism: a first-class "pipe" mesh axis on MeshLayout.

No counterpart exists in the reference (SURVEY.md §2.4: DL4J 0.7's only
strategy is data parallelism) — this is the last axis of the framework's
distributed-first extension set (dp / fsdp / tp / sp / **pp**).

Two tiers live here:

1. The legacy GPipe primitives (``stack_stage_params`` /
   ``pipeline_shardings`` / ``pipeline_apply`` / ``sequential_apply``):
   homogeneous stacked blocks, a ``lax.scan`` over the schedule ticks.
   ``pipeline_shardings`` used to hand-build its own NamedSharding rule —
   it now routes through :meth:`MeshLayout.from_mesh` + ``stage_spec`` so
   the one layout/spec source covers it (and DT008 validates the result).
   ``sequential_apply`` stays bit-exact as the regression oracle.

2. :class:`PipelinedTrainer`: ``MeshLayout(pipe=P)`` stages a
   MultiLayerNetwork's layer list across the pipe axis with an interleaved
   micro-batch schedule (stage *s* runs micro-batch *m* at tick ``m + s``;
   the backward pipeline — one backward per forward, in reverse tick order
   — falls out of ``jax.grad`` through the unrolled schedule). Stage
   handoffs are ``shard_map`` ``ppermute`` sends over ICI with
   double-buffered activation stashes (the in-flight ``recv`` buffer plus
   the tick's outgoing ``y``); stage partitioning is cost-balanced by the
   per-layer FLOPs/bytes walker (:func:`plan_stages`) instead of naive
   equal-count splits. The whole step is ONE jitted SPMD program admitted
   through the CompileManager (zero warm compiles), the sharding-flow pass
   walks it natively (per-microbatch ppermute attribution, DT306), HBM
   preflight projects stage params + stashed activations × in-flight
   micro-batches, and the roofline gains the bubble term
   ``(P-1)/(M+P-1)``.

Composition contract (see docs/distributed.md "Pipeline axis"):

- **pipe × data**: micro-batches shard over the batch axes inside the
  manual region; the gradient all-reduce over ``data`` is inserted by
  shard_map's transpose (stage params carry no data axis in their specs).
- **pipe × fsdp**: the packed per-stage parameter vector STORES its flat
  dim sharded over ``fsdp`` (ZeRO-3), but the region's in_spec drops the
  fsdp name, so GSPMD un-shards it ONCE at the region boundary per step —
  never per micro-batch (DT306 polices the per-tick variant).
- **pipe × tp**: the stage bodies run full-manual (this jaxlib cannot
  partially-auto a shard_map region — XLA hard-crashes on
  ``IsManualSubgroup`` mismatches), so tp applies to the replicated output
  head via the ordinary spec rules, not inside stages.
- **pipe × seq**: rejected loudly — the schedule owns the region and the
  ring kernels cannot run inside it.

The schedule ticks are Python-unrolled (M + P - 1 ticks), deliberately:
the measured census parses post-SPMD HLO *text*, where a collective inside
``lax.scan`` appears once regardless of trip count — unrolling keeps
predicted == measured per-microbatch attribution exact.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "PipelinePlan",
    "PipelinedTrainer",
    "pipeline_apply",
    "pipeline_shardings",
    "plan_stages",
    "sequential_apply",
    "stack_stage_params",
]


# --------------------------------------------------------------- legacy GPipe
def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


def pipeline_shardings(stacked_params, mesh, axis: str = "pipe"):
    """NamedShardings placing each stage's slice on its pipe-axis device.

    Routed through :meth:`MeshLayout.from_mesh` — the layout is the ONE
    sharding rule source (``stage_spec``: dim 0 over the pipe axis), and the
    resulting specs are DT008-validated against the mesh before any data
    moves. The old hand-built NamedSharding rule silently diverged from the
    layout layer; a bad axis/mesh combination now fails loudly here."""
    from jax.sharding import PartitionSpec as P

    from ..analysis import check_partition_specs
    from .layout import MeshLayout

    if axis not in mesh.shape:
        raise ValueError(
            f"pipeline axis '{axis}' not in mesh axes {tuple(mesh.shape)}")
    layout = MeshLayout.from_mesh(mesh)
    if axis == "pipe":
        specs = layout.stage_specs(stacked_params)
    else:  # a legacy mesh that names its stage axis differently
        specs = jax.tree_util.tree_map(lambda a: P(axis), stacked_params)
    findings = check_partition_specs(specs, mesh, stacked_params,
                                     source="<pipeline_shardings>")
    if findings:
        raise ValueError(
            "pipeline_shardings failed DT008 validation: "
            + "; ".join(f.message for f in findings))
    return jax.tree_util.tree_map(
        layout.sharding, specs,
        is_leaf=lambda x: isinstance(x, P))


def pipeline_apply(block_fn: Callable, stacked_params, microbatches, mesh,
                   axis: str = "pipe"):
    """Apply P homogeneous stages as a pipeline over M microbatches.

    ``block_fn(stage_params, x) -> y`` with y.shape == x.shape (homogeneous
    contract); ``stacked_params``: leaves [P, ...] (use
    :func:`stack_stage_params` / :func:`pipeline_shardings`);
    ``microbatches``: [M, mb, ...]. Returns [M, mb, ...] — the composition
    block_{P-1}(...block_0(x)) per microbatch, computed with the GPipe
    schedule. Differentiable end-to-end.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    n_stacked = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stacked != n_stages:
        # a divisible mismatch would otherwise silently run a SUBSET of
        # stages (each device keeps only slice [0] of its local shard)
        raise ValueError(
            f"{n_stacked} stacked stages but the '{axis}' mesh axis has "
            f"{n_stages} devices; one stage per device is the contract"
        )

    def per_stage(params, xs):
        # params: local stage slice with leading dim 1; xs: full [M, mb, ...]
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(recv, t):
            # stage 0 injects microbatch t; later stages consume what the
            # previous stage sent last tick (drained-feed ticks are bubble
            # ticks, replaced below)
            feed = xs[jnp.clip(t, 0, m - 1)]
            x_in = jnp.where(idx == 0, feed, recv)
            # Bubble ticks (stage idx is busy only for idx <= t < m + idx)
            # must compute on SAFE inputs, not the zero filler: reverse-mode
            # AD multiplies the dropped outputs' zero cotangents by the
            # block's partials, and 0 * NaN = NaN (the jnp.where trap) — a
            # block like x/||x|| would poison gradients from the zeros.
            valid = (t >= idx) & (t < m + idx)
            x_in = jnp.where(valid, x_in, jnp.ones(mb_shape, xs.dtype))
            y = block_fn(params, x_in)
            return jax.lax.ppermute(y, axis, perm), y

        recv0 = jnp.zeros(mb_shape, xs.dtype)
        _, ys = jax.lax.scan(tick, recv0, jnp.arange(m + n_stages - 1))
        # microbatch j completes on the LAST stage at tick j + P - 1; a
        # masked psum hands every stage the gathered outputs (out_specs
        # replicate, so each device must return the same array). where (not
        # multiply) so bubble-tick NaNs on earlier stages cannot poison the
        # sum (NaN * 0 == NaN).
        outs = ys[n_stages - 1 :]  # [M, mb, ...]
        outs = jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params
    )
    fn = shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, microbatches)


def sequential_apply(block_fn: Callable, stacked_params, microbatches):
    """Reference semantics: the same composition without the pipeline —
    the bit-exact regression oracle for tests and single-device fallback."""
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def one(x):
        for i in range(n_stages):
            params_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
            x = block_fn(params_i, x)
        return x

    return jax.vmap(one)(microbatches)


# ------------------------------------------------------------ stage planning
@dataclass(frozen=True)
class PipelinePlan:
    """Contiguous assignment of a net's hidden layers to pipeline stages.

    ``stages[s]`` lists the layer indices stage ``s`` runs (in order);
    ``costs[s]`` is the stage's static roofline weight (compute seconds +
    memory seconds at the planning batch). The output layer (index
    ``out_index``) never joins a stage — it runs replicated outside the
    pipelined region so the loss head composes with tp/fsdp via the
    ordinary spec rules."""

    stages: Tuple[Tuple[int, ...], ...]
    costs: Tuple[float, ...]
    layer_costs: Tuple[float, ...]
    out_index: int
    balanced: bool

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def max_cost(self) -> float:
        return max(self.costs) if self.costs else 0.0

    def describe(self) -> dict:
        return {
            "stages": [list(s) for s in self.stages],
            "stage_costs": [round(c, 9) for c in self.costs],
            "max_stage_cost": round(self.max_cost, 9),
            "out_index": self.out_index,
            "balanced": self.balanced,
        }


def _hidden_layer_costs(net, batch_or_struct) -> List[float]:
    """Static per-hidden-layer weight from the FLOPs/bytes walker: cost of
    the forward prefix through layer ``i`` minus the prefix through
    ``i - 1`` (preprocessors and dtype casts land on the layer that owns
    them). Falls back to the memory report's per-layer bytes when the
    walker cannot trace a layer."""
    from ..analysis.cost_model import roofline_params, static_cost
    from ..telemetry.memory import _input_structs

    net.init()
    out_idx = len(net.conf.layers) - 1
    x_struct = _input_structs(net, batch_or_struct)[0]
    rl = roofline_params()
    peak = float(rl.get("peak_flops") or 1.0)
    bw = float(rl.get("hbm_gbps") or 1.0) * 1e9
    try:
        prefix = [0.0]
        for i in range(1, out_idx + 1):
            cost = static_cost(
                lambda p, x, _i=i: net._forward(
                    p, x, net.state, False, None, upto=_i)[0],
                net.params, x_struct)
            prefix.append(cost["flops"] / peak + cost["hbm_bytes"] / bw)
        return [max(prefix[i + 1] - prefix[i], 1e-12)
                for i in range(out_idx)]
    except Exception:
        from ..telemetry.memory import memory_report

        rows = memory_report(net, batch_or_struct)["layers"]
        return [max(float(rows[i]["total_bytes"]), 1.0) / bw
                for i in range(out_idx)]


def _balanced_partition(costs: Sequence[float], k: int) -> List[Tuple[int, ...]]:
    """Contiguous partition of ``costs`` into ``k`` non-empty groups
    minimizing the max group sum (classic linear-partition DP)."""
    n = len(costs)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + float(c))

    def seg(i, j):  # cost of layers [i, j)
        return prefix[j] - prefix[i]

    # best[g][j] = minimal max-cost splitting the first j layers into g
    best = [[math.inf] * (n + 1) for _ in range(k + 1)]
    cut = [[0] * (n + 1) for _ in range(k + 1)]
    best[0][0] = 0.0
    for g in range(1, k + 1):
        for j in range(g, n + 1):
            for i in range(g - 1, j):
                cand = max(best[g - 1][i], seg(i, j))
                if cand < best[g][j]:
                    best[g][j] = cand
                    cut[g][j] = i
    bounds = [n]
    for g in range(k, 0, -1):
        bounds.append(cut[g][bounds[-1]])
    bounds.reverse()
    return [tuple(range(bounds[g], bounds[g + 1])) for g in range(k)]


def plan_stages(net, n_stages: int, batch_or_struct=None, *,
                balance: bool = True) -> PipelinePlan:
    """Partition a net's hidden layers into ``n_stages`` contiguous pipeline
    stages. ``balance=True`` (default) minimizes the max per-stage static
    cost via the per-layer FLOPs/bytes walker; ``balance=False`` is the
    naive equal-count split (kept for A/B benchmarking — the balanced plan
    must beat it on skewed models, tests/test_pipeline_axis.py asserts
    it)."""
    net.init()
    conf = net.conf
    if hasattr(conf, "vertices"):
        # ComputationGraph: topo order is the staging order; per-vertex
        # bytes from the memory report weigh the split
        from ..telemetry.memory import memory_report

        rows = memory_report(net, batch_or_struct)["layers"]
        n_hidden = len(rows) - 1
        costs = [max(float(rows[i]["total_bytes"]), 1.0)
                 for i in range(n_hidden)]
        out_idx = n_hidden
    else:
        out_idx = len(conf.layers) - 1
        costs = _hidden_layer_costs(net, batch_or_struct)
    if out_idx < n_stages:
        raise ValueError(
            f"cannot stage {out_idx} hidden layers across {n_stages} "
            "pipeline stages; need at least one layer per stage")
    if balance:
        stages = _balanced_partition(costs, n_stages)
    else:
        per = out_idx // n_stages
        extra = out_idx % n_stages
        stages, start = [], 0
        for s in range(n_stages):
            size = per + (1 if s < extra else 0)
            stages.append(tuple(range(start, start + size)))
            start += size
    stage_costs = tuple(sum(costs[i] for i in grp) for grp in stages)
    return PipelinePlan(stages=tuple(stages), costs=stage_costs,
                        layer_costs=tuple(costs), out_index=out_idx,
                        balanced=bool(balance))


# --------------------------------------------------------- pipelined trainer
def _flat_meta(tree):
    """(treedef, [(shape, dtype, size)...], total) for one layer's params."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    meta = [(tuple(np.shape(l)), np.dtype(l.dtype),
             int(np.prod(np.shape(l), dtype=np.int64)) if np.shape(l)
             else 1) for l in leaves]
    return treedef, meta, sum(m[2] for m in meta)


class PipelinedTrainer:
    """Train a ``MultiLayerNetwork`` on a ``MeshLayout(pipe=P)`` mesh.

    Hidden layers are staged across the pipe axis (:func:`plan_stages`,
    cost-balanced); each stage's parameters are packed into one flat
    per-stage vector (``[P, Lmax]``, dim 0 sharded over ``pipe``, dim 1
    over ``fsdp`` at zero_stage=3) so heterogeneous stages ride one
    ``lax.switch`` inside a single full-manual ``shard_map`` region. The
    output layer stays outside the region (replicated / tp-sharded by the
    ordinary spec rules) and sees the gathered hidden states in original
    batch order — the loss, regularization, RNG split chain and optimizer
    update all mirror ``nn/engine.py``'s ``_build_train_step``, which is
    what makes trajectory parity vs the unpiped net hold to float
    tolerance.

    Restrictions (all rejected loudly in ``__init__``): MultiLayerNetwork
    only, stateless deterministic hidden layers (no BN running stats, no
    dropout RNG inside stages), no seq axis, uniform parameter dtype."""

    def __init__(self, net, layout, *, microbatches: Optional[int] = None,
                 plan: Optional[PipelinePlan] = None, balance: bool = True,
                 batch_struct=None):
        from .layout import MeshLayout  # noqa: F401 (typing/doc aid)

        if layout.mesh is None:
            raise ValueError("PipelinedTrainer needs a concrete (non-"
                             "abstract) MeshLayout")
        if layout.pipe_size < 2:
            raise ValueError(
                f"layout has pipe={layout.pipe_size}; a pipeline needs "
                "pipe >= 2 (use MeshLayout(pipe=P))")
        if getattr(layout, "_seq_axis", None) is not None:
            raise ValueError(
                "pipe x seq is not supported: the pipelined region is "
                "full-manual over the whole mesh and the seq-axis ring "
                "kernels cannot run inside it; compose pipe with "
                "data/fsdp/tp instead")
        if hasattr(net.conf, "vertices"):
            raise NotImplementedError(
                "PipelinedTrainer stages MultiLayerNetwork layer lists; "
                "ComputationGraph vertex DAGs are plan-only for now "
                "(plan_stages works on both)")
        if microbatches is None:
            from ..tune.knobs import get_knob

            microbatches = int(get_knob("pipe_microbatches").default)
        if microbatches < 1:
            raise ValueError(f"microbatches must be >= 1, got {microbatches}")

        net.init()
        layout.precision.apply_to_net(net)
        self.net = net
        self.layout = layout
        self.mesh = layout.mesh
        self.n_stages = int(layout.pipe_size)
        self.microbatches = int(microbatches)
        self.plan = plan if plan is not None else plan_stages(
            net, self.n_stages, batch_struct, balance=balance)
        if self.plan.n_stages != self.n_stages:
            raise ValueError(
                f"plan has {self.plan.n_stages} stages but the layout's "
                f"pipe axis has {self.n_stages}")
        self._out_idx = self.plan.out_index
        layers = net.conf.layers
        for i in range(self._out_idx):
            if jax.tree_util.tree_leaves(net.state[i]):
                raise ValueError(
                    f"layer[{i}] ({type(layers[i]).__name__}) carries "
                    "mutable state; pipelined stages must be stateless")
        self._has_reg = any(
            getattr(l, a, 0) for l in layers
            for a in ("l1", "l2", "l1_bias", "l2_bias"))
        self._pack_params()
        self._place_train_state()
        self._boundaries = None  # resolved on first fit/analyze (needs mb)
        self._compiled = None
        self._exe_key = None
        from ..runtime.compile_manager import get_compile_manager

        self._cm = get_compile_manager()
        self._token = self._cm.new_token()
        self._rng = net._rng

    # ------------------------------------------------------------- packing
    def _pack_params(self) -> None:
        net, plan = self.net, self.plan
        fsdp = (self.layout._size(self.layout._fsdp_axis)
                if self.layout.zero_stage >= 3 else 1)
        dtypes = {np.dtype(l.dtype)
                  for i in range(self._out_idx)
                  for l in jax.tree_util.tree_leaves(net.params[i])}
        if len(dtypes) > 1:
            raise ValueError(
                f"pipelined stages need one uniform param dtype, found "
                f"{sorted(str(d) for d in dtypes)}")
        self._pack_dtype = dtypes.pop() if dtypes else np.dtype("float32")
        self._layer_meta = {}
        stage_lens = []
        for s, grp in enumerate(plan.stages):
            off = 0
            for li in grp:
                treedef, meta, size = _flat_meta(net.params[li])
                self._layer_meta[li] = (s, off, treedef, meta)
                off += size
            stage_lens.append(off)
        lmax = max(stage_lens) if stage_lens else 1
        if fsdp > 1:
            lmax = ((lmax + fsdp - 1) // fsdp) * fsdp
        self._stage_lens = stage_lens
        self._lmax = int(max(lmax, 1))
        packed = np.zeros((self.n_stages, self._lmax), self._pack_dtype)
        for li, (s, off, _td, meta) in self._layer_meta.items():
            pos = off
            for leaf, (_shape, _dt, size) in zip(
                    jax.tree_util.tree_leaves(net.params[li]), meta):
                packed[s, pos:pos + size] = np.asarray(leaf).reshape(-1)
                pos += size
        self._packed_host = packed
        self._fsdp_packed = fsdp > 1

    def _unpack_layer(self, flat, li):
        """Layer ``li``'s param pytree from one stage's flat vector."""
        s, off, treedef, meta = self._layer_meta[li]
        leaves, pos = [], off
        for shape, dt, size in meta:
            leaves.append(flat[pos:pos + size].reshape(shape).astype(dt))
            pos += size
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def unpack_to_net(self):
        """Write the live packed stage params (and the head) back onto
        ``net.params`` — checkpointing and the parity tests read the net."""
        packed = np.asarray(self._pt["stages"])
        params = list(self.net.params)
        for li in range(self._out_idx):
            s, _off, _td, _meta = self._layer_meta[li]
            params[li] = self._unpack_layer(jnp.asarray(packed[s]), li)
        params[self._out_idx] = self._pt["head"]
        self.net.params = params if isinstance(self.net.params, list) \
            else type(self.net.params)(params)
        return self.net

    # ----------------------------------------------------------- placement
    def _specs(self):
        from jax.sharding import PartitionSpec as P

        packed_spec = P("pipe", "fsdp") if self._fsdp_packed else P("pipe")
        head_specs = self.layout.param_specs(self.net.params[self._out_idx])
        return {"stages": packed_spec, "head": head_specs}

    def _opt_specs_tree(self, opt_state):
        """Moment leaves mirror their param's shape — match [P, Lmax]
        leaves to the packed spec, head-shaped leaves to head specs,
        scalars replicate (the same 'moments follow their param' rule
        MeshLayout.opt_specs applies)."""
        from jax.sharding import PartitionSpec as P

        packed_spec = (P("pipe", "fsdp") if self._fsdp_packed
                       else P("pipe"))
        head_shapes = {
            tuple(np.shape(l))
            for l in jax.tree_util.tree_leaves(
                self.net.params[self._out_idx])}
        packed_shape = (self.n_stages, self._lmax)

        def spec_of(leaf):
            shape = tuple(np.shape(leaf))
            if shape == packed_shape:
                return packed_spec
            if shape in head_shapes and shape:
                return self.layout.param_spec(shape)
            return P()

        return jax.tree_util.tree_map(spec_of, opt_state)

    def _place_train_state(self) -> None:
        lo = self.layout
        specs = self._specs()
        pt = {"stages": jnp.asarray(self._packed_host),
              "head": self.net.params[self._out_idx]}
        self._pt = jax.tree_util.tree_map(
            lambda a, s: lo.put(a, lo.sharding(s)), pt,
            {"stages": specs["stages"], "head": specs["head"]},
            is_leaf=lambda x: not isinstance(x, dict))
        opt = self.net._tx.init(self._pt)
        opt_specs = self._opt_specs_tree(opt)
        self._opt = jax.tree_util.tree_map(
            lambda a, s: lo.put(a, lo.sharding(s)), opt, opt_specs)
        self._pt_specs = specs
        self._opt_spec_tree = opt_specs

    # ---------------------------------------------------------- boundaries
    def _resolve_boundaries(self, mb: int, feat_shape, dtype) -> dict:
        """Per-microbatch boundary shapes entering each stage (plus the
        head), and the flat-padded handoff width Dmax. ``feat_shape`` is
        the REAL per-example feature shape — recurrent nets must trace at
        the batch's actual sequence length, not a probe default."""
        net = self.net
        x_struct = jax.ShapeDtypeStruct((mb,) + tuple(feat_shape),
                                        np.dtype(dtype))
        firsts = [grp[0] for grp in self.plan.stages] + [self._out_idx]
        shapes = []
        for k in firsts:
            if k == 0:
                shapes.append(tuple(x_struct.shape))
                continue
            h = jax.eval_shape(
                lambda x, _k=k: net._forward(
                    net.params, x, net.state, False, None, upto=_k)[0],
                x_struct)
            shapes.append(tuple(h.shape))
        elems = [int(np.prod(s[1:], dtype=np.int64)) for s in shapes]
        return {
            "mb": int(mb),
            "feat": tuple(feat_shape),
            "in_shapes": shapes[:-1],      # entering stage s
            "head_shape": shapes[-1],      # entering the output layer
            "in_elems": elems[:-1],
            "head_elems": elems[-1],
            "dmax": int(max(elems)),
            "x_dtype": x_struct.dtype,
        }

    # ------------------------------------------------------------ the step
    def _stage_branches(self, bnd, compute_dtype):
        """One branch per stage: unpad -> reshape -> preprocessor+layer
        chain -> flatten -> pad. All branches share the signature
        ``(x_pad [mb_local, Dmax], flat [Lmax]) -> y_pad`` lax.switch
        needs."""
        net, plan = self.net, self.plan
        layers = net.conf.layers
        dmax = bnd["dmax"]

        def make_branch(s):
            in_shape = bnd["in_shapes"][s]
            in_elems = bnd["in_elems"][s]

            def branch(x_pad, flat):
                mb_local = x_pad.shape[0]
                x = x_pad[:, :in_elems].reshape(
                    (mb_local,) + in_shape[1:])
                for li in plan.stages[s]:
                    pre = net.conf.preprocessors.get(li)
                    if pre is not None:
                        x = pre.apply(x)
                    p_li = self._unpack_layer(flat, li)
                    p_li = jax.tree_util.tree_map(
                        lambda a: a.astype(compute_dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a,
                        p_li)
                    x, _st = layers[li].apply(
                        p_li, x, net.state[li], train=True, rng=None,
                        mask=None)
                y = x.reshape(mb_local, -1)
                pad = dmax - y.shape[1]
                if pad:
                    y = jnp.pad(y, ((0, 0), (0, pad)))
                return y

            return branch

        return [make_branch(s) for s in range(self.n_stages)]

    def _build_step_fn(self, bnd):
        """The pure step: ``(pt, opt_state, xs_pad, y, rng) ->
        (pt, opt_state, loss)`` — value_and_grad through the pipelined
        forward, optax update, output shardings pinned to the declared
        specs (zero warm compiles: GSPMD must hand params back exactly
        where the next dispatch expects them)."""
        import optax
        from jax import shard_map
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..nn.multilayer import _compute_cast

        net, lo = self.net, self.layout
        n_stages, m = self.n_stages, self.microbatches
        batch_axes = lo.batch_axes
        out_idx = self._out_idx
        conf_dtype = getattr(net.conf, "dtype", "float32")
        compute_dtype = jnp.dtype(
            "float32" if conf_dtype == "bfloat16" else conf_dtype)
        # x64 test runs trace f64 activations through f32-conf nets; the
        # handoff buffers follow whatever dtype the cast input carries
        branches = self._stage_branches(bnd, compute_dtype)
        dmax, mb = bnd["dmax"], bnd["mb"]
        head_shape, head_elems = bnd["head_shape"], bnd["head_elems"]
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        ticks = m + n_stages - 1

        def region(flat_local, xs_local, sid_local):
            # flat_local [1, Lmax]: the stage's FULL flat vector — under
            # ZeRO-3 the storage spec is P("pipe", "fsdp") but the region's
            # in_spec is P("pipe"), so GSPMD un-shards the packed params
            # ONCE at the region boundary (never per micro-batch tick), and
            # the shard_map transpose's automatic psum over the absent
            # batch axes is the gradient sync
            flat = flat_local[0]
            s = sid_local[0]
            recv = jnp.zeros(xs_local.shape[1:], xs_local.dtype)
            ys = []
            # Python-unrolled schedule: stage s computes micro-batch
            # (t - s) at tick t; unrolling (not lax.scan) keeps the
            # measured HLO census' per-microbatch ppermute counts equal to
            # the predicted ones (a collective inside scan shows up ONCE
            # in HLO text regardless of trip count)
            for t in range(ticks):
                feed = xs_local[min(t, m - 1)]
                x_in = jnp.where(s == 0, feed, recv)
                # bubble ticks compute on SAFE inputs (ones, not the zero
                # filler): 0 cotangent x NaN partial = NaN otherwise
                valid = (t >= s) & (t < m + s)
                x_in = jnp.where(valid, x_in, jnp.ones_like(x_in))
                y = jax.lax.switch(s, branches, x_in, flat)
                recv = jax.lax.ppermute(y, "pipe", perm)
                if t >= n_stages - 1:
                    ys.append(y)
            outs = jnp.stack(ys)  # [M, mb_local, Dmax]
            outs = jnp.where(s == n_stages - 1, outs, jnp.zeros_like(outs))
            outs = jax.lax.psum(outs, "pipe")
            # merge micro-batches INSIDE the manual region: the global
            # result is batch-sharded on dim 0 directly (each device's M
            # local micro-batch slices stay its rows), so the head sees a
            # canonically-sharded [B, Dmax] with NO resharding all-to-all —
            # _prepare_batch permutes the labels to the same row order
            return outs.reshape(-1, outs.shape[-1])

        packed_spec = self._pt_specs["stages"]
        region_sm = shard_map(
            region, mesh=self.mesh,
            in_specs=(P("pipe"), P(None, batch_axes or None), P("pipe")),
            out_specs=P(batch_axes or None),
            check_vma=False)

        layers = net.conf.layers

        def regularization(packed, head):
            reg = jnp.asarray(0.0)
            if not self._has_reg:
                return reg
            for li in range(out_idx):
                s, _o, _t, _m2 = self._layer_meta[li]
                reg = reg + layers[li].regularization_loss(
                    self._unpack_layer(packed[s], li))
            return reg + layers[out_idx].regularization_loss(head)

        def loss_of(pt, xs_pad, y, rng):
            fwd_rng, out_rng = (jax.random.split(rng)
                                if rng is not None else (None, None))
            del fwd_rng  # hidden stages are deterministic (no dropout)
            cast_packed, xs_pad = _compute_cast(
                conf_dtype, pt["stages"], xs_pad)
            sid = jnp.arange(n_stages, dtype=jnp.int32)
            h_pad = region_sm(cast_packed, xs_pad, sid)  # [M*mb, Dmax]
            h = h_pad[:, :head_elems].reshape(
                (m * mb,) + head_shape[1:])
            pre = net.conf.preprocessors.get(out_idx)
            if pre is not None:
                h = pre.apply(h)
            h32 = h.astype(jnp.float32) if h.dtype == jnp.bfloat16 else h
            # scalar shell, not h32[:1]: a batch-sharded row slice would
            # read as a (predicted) batch-axis gather in the flow pass
            cast_head, _ = _compute_cast(conf_dtype, pt["head"],
                                         jnp.zeros((), h32.dtype))
            loss = layers[out_idx].compute_loss(
                cast_head, h32, y, None, train=True, rng=out_rng)
            return loss + regularization(pt["stages"], pt["head"])

        tx = net._tx
        pt_shardings = {
            "stages": NamedSharding(self.mesh, packed_spec),
            "head": jax.tree_util.tree_map(
                lo.sharding, self._pt_specs["head"],
                is_leaf=lambda x: isinstance(x, P)),
        }
        opt_shardings = jax.tree_util.tree_map(
            lo.sharding, self._opt_spec_tree)

        ls = getattr(net.conf, "loss_scale", None)

        def step(pt, opt_state, xs_pad, y, rng):
            from ..nn.updaters import (  # noqa: PLC0415
                optimizer_update, scaled_loss, unscale_grads, unscale_loss)

            def scaled_loss_of(*a):
                return scaled_loss(loss_of(*a), ls)

            loss, grads = jax.value_and_grad(scaled_loss_of)(pt, xs_pad, y, rng)
            loss = unscale_loss(loss, ls)
            grads = unscale_grads(grads, ls)
            _, new_opt, new_pt = optimizer_update(tx, grads, opt_state, pt)
            new_pt = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_pt, pt_shardings,
                is_leaf=lambda x: not isinstance(x, dict))
            new_opt = jax.tree_util.tree_map(
                jax.lax.with_sharding_constraint, new_opt, opt_shardings)
            return new_pt, new_opt, loss

        return step

    # -------------------------------------------------------------- fitting
    def _prepare_batch(self, x, y):
        """[B, ...] -> padded micro-batch stack [M, mb, Dmax] on the mesh
        (+ labels at the batch sharding)."""
        lo, m = self.layout, self.microbatches
        x = np.asarray(x)
        b = x.shape[0]
        if b % m:
            raise ValueError(
                f"batch of {b} rows does not divide into {m} micro-batches")
        mb = b // m
        bf = lo.batch_factor
        if mb % max(bf, 1):
            raise ValueError(
                f"micro-batch of {mb} rows does not divide the batch "
                f"shard factor {bf} (data x fsdp)")
        if self._boundaries is None or self._boundaries["mb"] != mb \
                or self._boundaries["feat"] != tuple(x.shape[1:]):
            self._boundaries = self._resolve_boundaries(
                mb, x.shape[1:], x.dtype)
        bnd = self._boundaries
        flat = x.reshape(m, mb, -1)
        if flat.shape[-1] < bnd["dmax"]:
            flat = np.pad(flat, ((0, 0), (0, 0),
                                 (0, bnd["dmax"] - flat.shape[-1])))
        from jax.sharding import PartitionSpec as P

        batch_axes = lo.batch_axes or None
        xs_pad = lo.put(jnp.asarray(flat),
                        lo.sharding(P(None, batch_axes)))
        # the region emits [M*mb] rows grouped device-major (each batch
        # shard keeps its M micro-batch slices contiguous); permute the
        # labels to that order on the host — the row-wise loss + mean is
        # permutation-invariant, so the scalar and every gradient match
        # the unpiped step exactly
        y = np.asarray(y)
        g = np.arange(b)
        mbl = mb // max(bf, 1)
        d, rem = g // (m * mbl), g % (m * mbl)
        y_d = lo.put(jnp.asarray(y[rem // mbl * mb + d * mbl + rem % mbl]),
                     lo.batch_sharding())
        return xs_pad, y_d, bnd

    def _ensure_compiled(self, xs_pad, y_d):
        key = (self._token, "pipeline_step", self.microbatches,
               self.n_stages, tuple(xs_pad.shape), str(xs_pad.dtype),
               tuple(np.shape(y_d)))
        if self._exe_key == key and self._compiled is not None:
            return self._compiled
        bnd = self._boundaries
        step = self._build_step_fn(bnd)
        args = (self._pt, self._opt, xs_pad, y_d, self._rng)
        self._compiled = self._cm.aot(key, lambda: jax.jit(step), args)
        self._exe_key = key
        self._step_fn = step
        return self._compiled

    def fit_batch(self, x, y) -> float:
        """One pipelined optimizer step over ``x``/``y`` (B rows split into
        M micro-batches). Returns the loss."""
        xs_pad, y_d, _bnd = self._prepare_batch(x, y)
        exe = self._ensure_compiled(xs_pad, y_d)
        self._rng, step_key = jax.random.split(self._rng)
        self._pt, self._opt, loss = exe(self._pt, self._opt, xs_pad, y_d,
                                        step_key)
        return float(loss)

    def fit(self, x, y, steps: int = 1) -> List[float]:
        """``steps`` pipelined optimizer steps over the same batch (the
        bench/warmup loop). The first call pays the one AOT compile; every
        later call reuses the admitted executable (zero warm compiles).
        The batch is prepared and placed ONCE and the per-step losses are
        fetched at the end, so steady-state steps dispatch back-to-back
        without a host round-trip between them."""
        xs_pad, y_d, _bnd = self._prepare_batch(x, y)
        exe = self._ensure_compiled(xs_pad, y_d)
        losses = []
        for _ in range(int(steps)):
            self._rng, step_key = jax.random.split(self._rng)
            self._pt, self._opt, loss = exe(self._pt, self._opt, xs_pad,
                                            y_d, step_key)
            losses.append(loss)
        return [float(v) for v in losses]

    def warm_up(self, x, y) -> None:
        """Pay the AOT compile without taking an optimizer step."""
        xs_pad, y_d, _ = self._prepare_batch(x, y)
        self._ensure_compiled(xs_pad, y_d)

    # ------------------------------------------------------------- analysis
    def analyze(self, x, y) -> dict:
        """The sharding-flow pass over the REAL pipelined step (zero device
        dispatches): predicted collective census with per-microbatch
        ppermute attribution, DT300-DT306 findings (DT306 = per-microbatch
        collective inside a stage body), per-step comm bytes."""
        from jax.sharding import PartitionSpec as P

        from ..analysis.shard_flow import analyze_shard_flow

        xs_pad, y_d, bnd = self._prepare_batch(x, y)
        step = self._build_step_fn(bnd)
        batch_axes = self.layout.batch_axes or None
        in_specs = (
            {"stages": self._pt_specs["stages"],
             "head": self._pt_specs["head"]},
            self._opt_spec_tree,
            P(None, batch_axes),
            self.layout.batch_spec(),
            P(),
        )
        return analyze_shard_flow(
            step, (self._pt, self._opt, xs_pad, y_d, self._rng),
            in_specs, self.layout, param_argnums=(0, 1),
            pipeline_microbatches=self.microbatches,
            source="<pipelined_step>")

    def measured_census(self, x, y) -> List[dict]:
        """Collective census parsed from the compiled step's post-SPMD HLO
        (compiles on first use via the same AOT admission as fit)."""
        from ..analysis.shard_flow import hlo_collective_census

        xs_pad, y_d, _ = self._prepare_batch(x, y)
        exe = self._ensure_compiled(xs_pad, y_d)
        return hlo_collective_census(exe.as_text(), self.layout)

    def roofline(self, x, y) -> dict:
        """Static roofline of the pipelined step with the bubble-fraction
        term: per-device work divides across P stages and the schedule
        idles ``(P-1)/(M+P-1)`` of the mesh."""
        from ..analysis.cost_model import apply_roofline, static_cost

        xs_pad, y_d, bnd = self._prepare_batch(x, y)
        step = self._build_step_fn(bnd)
        cost = static_cost(step, self._pt, self._opt, xs_pad, y_d,
                           self._rng)
        flow = self.analyze(x, y)
        apply_roofline(cost, comm_bytes=flow["comm_bytes_per_step"],
                       pipeline={"stages": self.n_stages,
                                 "microbatches": self.microbatches})
        return cost

    def preflight(self, x, y=None, *, limit_bytes: Optional[int] = None,
                  headroom: float = 0.9) -> dict:
        """Per-device HBM projection of the pipelined step: the stage's
        packed param share (param + grad + moments over pipe/fsdp), the
        replicated head, the stashed activations — per-microbatch stage
        activations × the in-flight micro-batch count (every forward
        micro-batch's residuals wait for its backward) — and the
        double-buffered handoffs. Raises
        :class:`~deeplearning4j_tpu.telemetry.memory.MemoryPreflightError`
        when the worst stage exceeds the budget (an over-stash
        ``microbatches`` choice fails HERE, before a doomed compile)."""
        from ..telemetry.memory import (MemoryPreflightError, _hbm_limit,
                                        memory_report)

        m, p = self.microbatches, self.n_stages
        x = np.asarray(x)
        mb = x.shape[0] // m if x.shape[0] >= m else 1
        if self._boundaries is None or self._boundaries["mb"] != mb \
                or self._boundaries["feat"] != tuple(x.shape[1:]):
            self._boundaries = self._resolve_boundaries(
                mb, x.shape[1:], x.dtype)
        bnd = self._boundaries
        report = memory_report(self.net, x.shape[0])
        rows = report["layers"]
        itemsize = np.dtype(self._pack_dtype).itemsize
        fsdp = (self.layout._size(self.layout._fsdp_axis)
                if self._fsdp_packed else 1)
        packed_pd = self._lmax * itemsize / fsdp
        # moments: optax adam = 2 leaves mirroring the packed vector; read
        # the real opt tree instead of assuming
        opt_pd = sum(
            int(np.prod(np.shape(l), dtype=np.int64)) *
            np.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(self._opt)
            if tuple(np.shape(l)) == (p, self._lmax)) / (p * fsdp)
        head_pd = sum(r["param_bytes"] * 2 + r["opt_state_bytes"]
                      for r in rows[self._out_idx:self._out_idx + 1])
        bf = max(self.layout.batch_factor, 1)
        in_flight = m + p - 1  # unrolled ticks each stash residuals
        stage_rows = []
        for s, grp in enumerate(self.plan.stages):
            act_mb = sum(rows[i]["activation_bytes"] for i in grp) \
                / max(x.shape[0] // mb, 1) / bf
            handoff = 2 * mb * bnd["dmax"] * itemsize / bf
            stage_rows.append({
                "stage": s,
                "layers": list(grp),
                "param_bytes": int(2 * packed_pd),
                "opt_state_bytes": int(opt_pd),
                "stash_bytes": int(act_mb * in_flight),
                "handoff_bytes": int(handoff),
                "total_bytes": int(2 * packed_pd + opt_pd + head_pd
                                   + act_mb * in_flight + handoff),
            })
        projected = max(r["total_bytes"] for r in stage_rows)
        source = "explicit limit_bytes"
        if limit_bytes is None:
            limit_bytes, source = _hbm_limit()
        report["pipeline"] = {
            "stages": stage_rows,
            "microbatches": m,
            "in_flight": in_flight,
            "projected_peak_bytes_per_device": int(projected),
            "plan": self.plan.describe(),
        }
        if limit_bytes is None:
            report["preflight"] = {"checked": False, "reason": source}
            return report
        budget = int(limit_bytes * headroom)
        report["preflight"] = {
            "checked": True,
            "fits": projected <= budget,
            "projected_peak_bytes": int(projected),
            "per_device": True,
            "limit_bytes": int(limit_bytes),
            "headroom": headroom,
            "limit_source": source,
        }
        if projected > budget:
            worst = max(stage_rows, key=lambda r: r["total_bytes"])
            raise MemoryPreflightError(
                f"projected per-device pipeline peak "
                f"{projected / 2**20:.1f} MiB (stage {worst['stage']}: "
                f"{worst['stash_bytes'] / 2**20:.1f} MiB stashed over "
                f"{in_flight} in-flight micro-batch ticks) exceeds "
                f"{budget / 2**20:.1f} MiB ({headroom:.0%} of "
                f"{limit_bytes / 2**20:.1f} MiB from {source}); lower "
                "microbatches= or raise the budget",
                report, int(projected), int(limit_bytes))
        return report

    def describe(self) -> dict:
        return {
            "layout": self.layout.describe(),
            "plan": self.plan.describe(),
            "microbatches": self.microbatches,
            "bubble_fraction": round(
                (self.n_stages - 1)
                / (self.microbatches + self.n_stages - 1), 6),
            "packed_bytes": int(self.n_stages * self._lmax
                                * np.dtype(self._pack_dtype).itemsize),
        }
