"""Pass 4: static sharding-propagation over a traced jaxpr (DT3xx).

PR 8 gave every program ONE sharding source of truth (``parallel.MeshLayout``)
but nothing could predict what GSPMD *does* with those specs: the implicit
all-gathers, producer/consumer reshards and per-scan-step collectives only
show up in the post-SPMD HLO after a compile. This pass abstract-interprets
the jaxpr with the layout's PartitionSpecs as the abstract values — per-eqn
propagation rules calibrated against the measured post-SPMD census of this
container's XLA (tests/test_shard_flow.py holds them to parity):

- elementwise eqns take the per-dim union of their operands' axes; when one
  mesh axis would land on two different dims, the smaller-payload operand is
  gathered (GSPMD's choice for the broadcast bias under fsdp);
- ``dot_general``/``conv``: a contraction dim sharded identically on both
  sides becomes partial sums → a predicted **all-reduce** with the exact
  payload bytes; a contraction dim sharded on ONE side (or fighting a kept
  dim for the axis) gathers that operand first — kept-dim shards win, which
  is what GSPMD picks for both the ZeRO param gather and the tp activation
  gather;
- ``reshape``/``slice``/``concatenate``/``pad`` that split, merge or cut a
  sharded dim force an all-gather (only a merge-major / split-major sharded
  dim survives);
- ``reduce_*`` over a sharded dim is an all-reduce of the result;
- ``scan`` multiplies its body's collectives by the trip count (gathers of
  loop-invariant consts are hoisted and count once); ``while`` counts one
  iteration (per-step semantics, the staged fori path).

Collective payloads are **per-device bytes** (global bytes divided by the
factor of every mesh axis still sharding the tensor) — exactly the shapes
the post-SPMD HLO prints, so the predicted census and the measured census
key identically: ``(kind, mesh axes) -> {count, bytes}``.

Outputs: a predicted collective census, the DT300-DT305 rule family
(implicit activation all-gather / producer-consumer reshard / oversized
non-batch contraction all-reduce / batch axis dropped / per-scan-step
collective / head-aware-tp advisory), and the communication bytes that feed
the ``DL4JTPU_ICI_GBPS`` roofline term. :func:`hlo_collective_census` parses
the measured twin out of a compiled executable's HLO text and
:func:`compare_census` holds the two to byte-level parity — the ground truth
that keeps this pass honest (``BENCH_MODEL=shard`` runs it per variant).

Everything is host-side spec algebra over ``jax.make_jaxpr`` traces: no
compile, no dispatch — cheap enough to run at CompileManager admission.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .cost_model import _aval_bytes
from .findings import Finding, merge_findings
from .rules import get_rule

__all__ = [
    "analyze_shard_flow",
    "propagate_jaxpr",
    "check_network_shard_flow",
    "hlo_collective_census",
    "compare_census",
    "flow_report",
]

IR_SOURCE = "<shardflow>"

# DT300/DT301 only fire above this payload: tiny gathers (a broadcast bias)
# are GSPMD's normal cost of doing business, not a finding
DT300_FLOOR_BYTES = 1 << 20  # 1 MiB
DT301_FLOOR_BYTES = 1 << 20
# DT302: a single non-batch-axis contraction all-reduce at/above this payload
# is "oversized" (tp activation all-reduces; grad syncs over batch axes are
# DT207's expected territory and exempt)
DT302_FLOOR_BYTES = 8 << 20  # 8 MiB

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_HLO_KINDS = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "collective_permute",
}

# jaxpr-level collective primitive -> census kind
_PRIM_KINDS = {
    "psum": "all_reduce", "pmax": "all_reduce", "pmin": "all_reduce",
    "pmean": "all_reduce", "all_gather": "all_gather",
    "reduce_scatter": "reduce_scatter", "psum_scatter": "reduce_scatter",
    "all_to_all": "all_to_all", "ppermute": "collective_permute",
    "pbroadcast": "all_reduce",
}


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}"
        n /= 1024.0
    return f"{n:.1f} TiB"


# --------------------------------------------------------------- spec algebra
def _norm_spec(pspec, ndim: int) -> Tuple[Tuple[str, ...], ...]:
    """A PartitionSpec (or tuple) as ndim per-dim tuples of axis names."""
    entries = tuple(pspec) if pspec is not None else ()
    out = []
    for d in range(ndim):
        e = entries[d] if d < len(entries) else None
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(str(a) for a in e))
        else:
            out.append((str(e),))
    return tuple(out)


def _spec_axes(spec) -> frozenset:
    return frozenset(a for dim in spec for a in dim)


class _St:
    """Abstract value of one var: its spec, the gather payload basis
    (``charge`` — global bytes, looked through broadcasts so gathering a
    broadcast bias charges the pre-broadcast vector), two lineage flags
    (``param``: descends from a parameter invar, so its gather is the
    documented ZeRO cost; ``invariant``: loop-invariant inside scan — its
    gather is hoisted and counted once), and ``pending``: mesh axes over
    which the value is an UNREDUCED partial sum. GSPMD keeps partial sums
    lazy through additive accumulation (the per-time-step dW adds into the
    scan carry; ONE all-reduce fires after the loop), so the all-reduce is
    emitted at the first non-linear consumer, not at the contraction."""

    __slots__ = ("spec", "charge", "param", "invariant", "pending", "psrc")

    def __init__(self, spec, charge: int, param: bool = False,
                 invariant: bool = False,
                 pending: frozenset = frozenset(), psrc: str = ""):
        self.spec = spec
        self.charge = int(charge)
        self.param = param
        self.invariant = invariant
        self.pending = frozenset(pending)
        self.psrc = psrc


class _Flow:
    """One propagation run over a closed jaxpr (plus nested sub-jaxprs)."""

    def __init__(self, axis_sizes: Dict[str, int],
                 batch_axes: Sequence[str]):
        self.sizes = {str(k): int(v) for k, v in axis_sizes.items()}
        self.batch_axes = frozenset(str(a) for a in batch_axes)
        self.events: List[dict] = []
        # inside a shard_map body: every mesh axis is manual, GSPMD inserts
        # nothing — only explicit collectives count, and check_rep's
        # pbroadcast bookkeeping compiles to nothing
        self._manual = False
        # shape -> {shard factor: #vars} over every eqn output (activation
        # projection for preflight's per-device estimate)
        self.shape_factors: Dict[Tuple[int, ...], Dict[int, int]] = {}

    # ------------------------------------------------------------- helpers
    def _factor(self, spec, exclude: frozenset = frozenset()) -> int:
        f = 1
        for a in _spec_axes(spec):
            if a not in exclude:
                f *= self.sizes.get(a, 1)
        return max(1, f)

    def _emit(self, kind: str, axes: Iterable[str], payload: int, *,
              cause: str, prim: str, mult: int, scope: str,
              trip: int, record: bool, param: bool = False) -> None:
        if not record or payload <= 0:
            return
        # size-1 mesh axes compile to nothing (XLA elides the trivial
        # replica group) — shard_map's transpose still psums over every
        # axis absent from an in_spec, so a 5-axis mesh with tp=seq=1
        # would otherwise predict phantom all-reduces the measured HLO
        # census can never show
        axes = tuple(sorted({a for a in axes if self.sizes.get(a, 1) > 1}))
        if not axes:
            return
        if kind == "all_reduce" and len(axes) > 1:
            # XLA lowers a multi-axis all-reduce as one stage PER mesh axis
            # (measured HLO shows e.g. data-groups then seq-groups, full
            # payload each) — mirror that so the censuses line up
            for a in axes:
                self.events.append({
                    "kind": kind, "axes": (a,), "bytes": int(payload),
                    "count": int(max(1, mult)), "cause": cause,
                    "prim": prim, "scope": scope, "trip": int(trip),
                    "param": bool(param), "manual": bool(self._manual),
                })
            return
        self.events.append({
            "kind": kind, "axes": axes, "bytes": int(payload),
            "count": int(max(1, mult)), "cause": cause, "prim": prim,
            "scope": scope, "trip": int(trip), "param": bool(param),
            "manual": bool(self._manual),
        })

    def _gather(self, st: _St, dim_axes: Dict[int, set], *, cause: str,
                prim: str, mult: int, scope: str, trip: int,
                record: bool) -> None:
        """Strip ``dim_axes`` from ``st`` (in place — every later consumer
        sees the gathered tensor, modeling GSPMD's reuse of one all-gather)
        and emit the event. Payload = per-device bytes of the gathered
        result: charge / factor of the axes that KEEP sharding it."""
        removed = set()
        new_spec = list(st.spec)
        for d, axes in dim_axes.items():
            keep = tuple(a for a in new_spec[d] if a not in axes)
            removed |= set(new_spec[d]) - set(keep)
            new_spec[d] = keep
        if not removed:
            return
        payload = st.charge // self._factor(tuple(new_spec))
        eff_mult = 1 if (st.invariant and scope == "scan") else mult
        st.spec = tuple(new_spec)
        self._emit("all_gather", removed, payload, cause=cause, prim=prim,
                   mult=eff_mult, scope=scope, trip=trip, record=record,
                   param=st.param)

    def _materialize(self, st: _St, *, mult, scope, trip, record) -> None:
        """Emit the deferred all-reduce of a partial-sum value (in place —
        every later consumer sees it reduced)."""
        if not st.pending:
            return
        payload = st.charge // self._factor(st.spec)
        eff_mult = 1 if (st.invariant and scope == "scan") else mult
        self._emit("all_reduce", st.pending, payload, cause="contraction",
                   prim=st.psrc or "partial_sum", mult=eff_mult, scope=scope,
                   trip=trip, record=record, param=st.param)
        st.pending = frozenset()

    def _note_shape(self, aval, spec) -> None:
        shape = tuple(int(s) for s in getattr(aval, "shape", ()) or ())
        if not shape:
            return
        row = self.shape_factors.setdefault(shape, {})
        f = self._factor(spec)
        row[f] = row.get(f, 0) + 1

    # ------------------------------------------------------------ the walk
    def walk(self, closed, in_states: Sequence[_St], *, mult: int = 1,
             scope: str = "top", trip: int = 1,
             record: bool = True) -> List[_St]:
        from jax.extend import core  # noqa: PLC0415

        jaxpr = closed.jaxpr
        env: Dict[Any, _St] = {}

        def fresh(aval, **kw):
            ndim = len(getattr(aval, "shape", ()) or ())
            return _St(tuple(() for _ in range(ndim)), _aval_bytes(aval), **kw)

        def read(v) -> _St:
            if isinstance(v, core.Literal):
                return fresh(v.aval)
            st = env.get(v)
            if st is None:
                st = fresh(v.aval)
                env[v] = st
            return st

        # copy the caller's states: gathers mutate specs in place (one
        # gather serves every later consumer), and a probe walk (carry
        # fixpoint) must not leak its gathers into the recorded walk
        for v, st in zip(jaxpr.invars, in_states):
            env[v] = _St(st.spec, st.charge, param=st.param,
                         invariant=st.invariant, pending=st.pending,
                         psrc=st.psrc)
        for v in jaxpr.constvars:
            env[v] = fresh(v.aval, invariant=True)

        for eqn in jaxpr.eqns:
            outs = self._eqn(eqn, read, mult=mult, scope=scope, trip=trip,
                             record=record)
            for v, st in zip(eqn.outvars, outs):
                env[v] = st
                if record:
                    self._note_shape(v.aval, st.spec)
        return [read(v) for v in jaxpr.outvars]

    # -------------------------------------------------------- eqn handlers
    def _eqn(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        name = eqn.primitive.name
        kw = dict(mult=mult, scope=scope, trip=trip, record=record)
        if name == "dot_general":
            return self._dot(eqn, read, **kw)
        if name == "conv_general_dilated":
            return self._conv(eqn, read, **kw)
        if name in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "argmax", "argmin",
                    "reduce_precision") and "axes" in eqn.params:
            return self._reduce(eqn, read, **kw)
        if name == "broadcast_in_dim":
            return self._broadcast(eqn, read)
        if name == "reshape":
            return self._reshape(eqn, read, **kw)
        if name == "transpose":
            return self._transpose(eqn, read)
        if name == "squeeze":
            return self._squeeze(eqn, read)
        if name in ("slice", "dynamic_slice"):
            return self._slice(eqn, read, **kw)
        if name == "split":
            return self._split(eqn, read, **kw)
        if name == "concatenate":
            return self._concat(eqn, read, **kw)
        if name == "pad":
            return self._pad(eqn, read, **kw)
        if name in ("cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
                    "sort"):
            return self._axis_op(eqn, read, **kw)
        if name.startswith("reduce_window"):
            return self._reduce_window(eqn, read, **kw)
        if name in ("gather",) or name.startswith("scatter"):
            return self._gather_scatter(eqn, read, **kw)
        if name in _PRIM_KINDS:
            return self._explicit_collective(eqn, read, **kw)
        if name == "scan":
            return self._scan(eqn, read, **kw)
        if name == "while":
            return self._while(eqn, read, **kw)
        if name == "cond":
            return self._cond(eqn, read, **kw)
        if name == "shard_map":
            return self._shard_map(eqn, read, **kw)
        sub = self._wrapped_jaxpr(eqn)
        if sub is not None and len(sub.jaxpr.invars) == len(eqn.invars):
            outs = self.walk(sub, [read(v) for v in eqn.invars], **kw)
            if len(outs) == len(eqn.outvars):
                return outs
            return [self._default_out(eqn, read, i)
                    for i in range(len(eqn.outvars))]
        return [self._meet(eqn, read, i, **kw)
                for i in range(len(eqn.outvars))]

    @staticmethod
    def _wrapped_jaxpr(eqn):
        """The single nested jaxpr of a 1:1 wrapper (pjit / remat /
        custom_jvp / custom_vjp / closed_call), or None."""
        from jax.extend import core  # noqa: PLC0415

        found = None
        for v in eqn.params.values():
            j = None
            if isinstance(v, core.ClosedJaxpr):
                j = v
            elif isinstance(v, core.Jaxpr):
                j = core.ClosedJaxpr(v, ())
            if j is not None:
                if found is not None:
                    return None  # more than one: not a simple wrapper
                found = j
        return found

    def _default_out(self, eqn, read, i) -> _St:
        """Outputs of unknown prims inherit the spec of a same-shaped
        operand (prefer a sharded one), else replicate."""
        out = eqn.outvars[i].aval
        shape = tuple(getattr(out, "shape", ()) or ())
        best = None
        for v in eqn.invars:
            st = read(v)
            if tuple(getattr(v.aval, "shape", ()) or ()) == shape:
                if best is None or (_spec_axes(st.spec)
                                    and not _spec_axes(best.spec)):
                    best = st
        if best is None:
            return _St(tuple(() for _ in shape), _aval_bytes(out))
        return _St(best.spec, _aval_bytes(out), param=best.param,
                   invariant=best.invariant)

    def _meet(self, eqn, read, i, *, mult, scope, trip, record) -> _St:
        """Elementwise meet with numpy broadcasting (dims align from the
        right, size-1 dims are unsharded): per-out-dim union over the
        operands; a mesh axis claimed for two different out dims gathers
        the smaller-charge claimant (the broadcast bias, under fsdp)."""
        out = eqn.outvars[i].aval
        shape = tuple(int(s) for s in getattr(out, "shape", ()) or ())
        aligned: List[Tuple[_St, int]] = []  # (state, out-dim offset)
        for v in eqn.invars:
            vshape = tuple(getattr(v.aval, "shape", ()) or ())
            if len(vshape) > len(shape):
                continue
            off = len(shape) - len(vshape)
            if all(vshape[d] in (1, shape[off + d])
                   for d in range(len(vshape))):
                aligned.append((read(v), off))
        if not aligned:
            return _St(tuple(() for _ in shape), _aval_bytes(out))
        # axis -> out dim -> [(state, local dim)]
        claims: Dict[str, Dict[int, List[Tuple[_St, int]]]] = {}
        for st, off in aligned:
            for d, axes in enumerate(st.spec):
                for a in axes:
                    claims.setdefault(a, {}).setdefault(
                        off + d, []).append((st, d))
        for a, by_dim in claims.items():
            if len(by_dim) <= 1:
                continue
            # keep the dim claimed by the largest payload; gather the rest
            keep_dim = max(by_dim, key=lambda d: max(
                s.charge for s, _ in by_dim[d]))
            for d, sts in by_dim.items():
                if d == keep_dim:
                    continue
                for st, local in sts:
                    self._gather(
                        st, {local: {a}},
                        cause=("param_gather" if st.param else "mismatch"),
                        prim=eqn.primitive.name, mult=mult, scope=scope,
                        trip=trip, record=record)
        # additive ops carry partial sums through (add_any is autodiff's
        # cotangent accumulator — the per-step dW += path); anything else
        # forces the deferred all-reduce first. convert_element_type is NOT
        # in the list: XLA all-reduces in the math dtype BEFORE a narrowing
        # cast (measured: fsdp+bf16 grads all-reduce in f32).
        if eqn.primitive.name in ("add", "sub", "add_any"):
            pend = frozenset().union(*(st.pending for st, _ in aligned))
            psrc = next((st.psrc for st, _ in aligned if st.psrc), "")
        else:
            for st, _ in aligned:
                self._materialize(st, mult=mult, scope=scope, trip=trip,
                                  record=record)
            pend, psrc = frozenset(), ""
        spec = []
        for d in range(len(shape)):
            axes = set()
            for st, off in aligned:
                local = d - off
                if 0 <= local < len(st.spec):
                    axes |= set(st.spec[local])
            spec.append(tuple(sorted(axes)))
        return _St(tuple(spec), _aval_bytes(out),
                   param=all(st.param for st, _ in aligned),
                   invariant=all(st.invariant for st, _ in aligned),
                   pending=pend, psrc=psrc)

    # dot_general: the heart of the pass
    def _dot(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs_v, rhs_v = eqn.invars[0], eqn.invars[1]
        ls, rs = read(lhs_v), read(rhs_v)
        for st in (ls, rs):
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        out = eqn.outvars[0].aval

        def role(side, d):
            cdims, bdims = (lc, lb) if side == 0 else (rc, rb)
            if d in cdims:
                return "contract"
            if d in bdims:
                return "batch"
            return "kept"

        claims: Dict[str, List[Tuple[int, int, str, _St]]] = {}
        for side, st in ((0, ls), (1, rs)):
            for d, axes in enumerate(st.spec):
                for a in axes:
                    claims.setdefault(a, []).append((side, d, role(side, d),
                                                     st))
        partial: set = set()
        for a, cl in claims.items():
            roles = {c[2] for c in cl}
            contract_cl = [c for c in cl if c[2] == "contract"]
            if roles == {"contract"} and len({c[0] for c in cl}) == 2:
                partial.add(a)  # sharded contraction on BOTH sides
                continue
            if roles == {"batch"}:
                continue  # batch-dim sharding flows to the result
            if roles == {"contract"} and len({c[0] for c in cl}) == 1:
                # one-sided sharded contraction where the OTHER operand (and
                # hence the result) never touches the axis: GSPMD slices the
                # unsharded side locally (free) and keeps the result an
                # unreduced partial sum — the row-parallel Megatron pattern
                # (attention_out / lstm_gates W / ffn_down role specs). No
                # gather is emitted; ONE all-reduce fires at the first
                # non-linear consumer. ZeRO layouts never take this route:
                # fsdp also shards the activation batch dim, so the fsdp
                # axis carries mixed roles and falls through to the gather.
                partial.add(a)
                continue
            if contract_cl:
                # one-sided contraction shard (or contraction fighting a
                # kept dim for the axis): gather the contraction side —
                # kept-dim shards win, matching GSPMD (ZeRO param gather,
                # tp activation gather)
                for side, d, _, st in contract_cl:
                    self._gather(
                        st, {d: {a}},
                        cause=("param_gather" if st.param
                               else "activation_gather"),
                        prim="dot_general", mult=mult, scope=scope,
                        trip=trip, record=record)
                continue
            if len(cl) > 1:
                # the axis claims kept dims on both sides: keep the bigger;
                # on a tie keep the RHS claim — in autodiff's dW dots the
                # cotangent is the lhs and GSPMD gathers it ONCE (every
                # consumer reuses the gather and dW comes out in the
                # param's orientation, so the optimizer adds stay local)
                keep = max(cl, key=lambda c: (c[3].charge, c[0]))
                for side, d, _, st in cl:
                    if (side, d) == (keep[0], keep[1]):
                        continue
                    self._gather(
                        st, {d: {a}},
                        cause=("param_gather" if st.param else "mismatch"),
                        prim="dot_general", mult=mult, scope=scope,
                        trip=trip, record=record)

        # result spec: [batch dims..., lhs kept..., rhs kept...]
        lkept = [d for d in range(len(ls.spec)) if d not in lc and d not in lb]
        rkept = [d for d in range(len(rs.spec)) if d not in rc and d not in rb]
        entries: List[Tuple[str, ...]] = []
        for bl, br in zip(lb, rb):
            entries.append(tuple(sorted(set(ls.spec[bl]) | set(rs.spec[br]))))
        entries += [ls.spec[d] for d in lkept]
        entries += [rs.spec[d] for d in rkept]
        spec = tuple(entries)
        # a sharded contraction leaves the result an UNREDUCED partial sum:
        # the all-reduce stays lazy through additive accumulation and fires
        # at the first non-linear consumer (GSPMD keeps the per-step dW
        # partial through the backward scan and reduces once after it)
        return [_St(spec, _aval_bytes(out), pending=frozenset(partial),
                    psrc="dot_general")]

    def _conv(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        dn = eqn.params["dimension_numbers"]
        ls, rs = read(eqn.invars[0]), read(eqn.invars[1])
        for st in (ls, rs):
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        out = eqn.outvars[0].aval
        # sharded lhs spatial dims need halo exchange — model as a gather
        spatial = set(dn.lhs_spec[2:])
        strip = {d: set(ls.spec[d]) for d in spatial if ls.spec[d]}
        if strip:
            self._gather(ls, strip, cause="activation_gather", prim="conv",
                         mult=mult, scope=scope, trip=trip, record=record)
        partial = set(ls.spec[dn.lhs_spec[1]]) & set(rs.spec[dn.rhs_spec[1]])
        one_sided = ((set(ls.spec[dn.lhs_spec[1]])
                      | set(rs.spec[dn.rhs_spec[1]])) - partial)
        for st, d in ((ls, dn.lhs_spec[1]), (rs, dn.rhs_spec[1])):
            axes = set(st.spec[d]) & one_sided
            if axes:
                self._gather(st, {d: axes},
                             cause=("param_gather" if st.param
                                    else "activation_gather"),
                             prim="conv", mult=mult, scope=scope, trip=trip,
                             record=record)
        entries = [()] * len(getattr(out, "shape", ()))
        entries[dn.out_spec[0]] = ls.spec[dn.lhs_spec[0]]
        entries[dn.out_spec[1]] = rs.spec[dn.rhs_spec[0]]
        # one axis cannot shard two result dims: the kernel's claim loses
        batch_axes_here = set(entries[dn.out_spec[0]])
        dup = batch_axes_here & set(entries[dn.out_spec[1]])
        if dup:
            self._gather(rs, {dn.rhs_spec[0]: dup},
                         cause=("param_gather" if rs.param else "mismatch"),
                         prim="conv", mult=mult, scope=scope, trip=trip,
                         record=record)
            entries[dn.out_spec[1]] = rs.spec[dn.rhs_spec[0]]
        spec = tuple(entries)
        return [_St(spec, _aval_bytes(out), pending=frozenset(partial),
                    psrc="conv")]

    def _reduce(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        st = read(eqn.invars[0])
        name = eqn.primitive.name
        axes = tuple(eqn.params["axes"])
        reduced = {a for d in axes for a in st.spec[d]}
        spec = tuple(e for d, e in enumerate(st.spec) if d not in axes)
        if name == "reduce_sum":
            # additive: the cross-device reduce joins the pending partial
            # sums and stays lazy (the bias grad / loss mean pattern)
            pend = st.pending | frozenset(reduced)
            return [_St(spec, _aval_bytes(ov.aval), param=st.param,
                        invariant=st.invariant, pending=pend,
                        psrc=st.psrc or name) for ov in eqn.outvars]
        # max/min/prod/arg reductions are not additive: materialize the
        # operand, then the cross-device reduce fires eagerly
        self._materialize(st, mult=mult, scope=scope, trip=trip,
                          record=record)
        outs = [_St(spec, _aval_bytes(ov.aval), param=st.param,
                    invariant=st.invariant) for ov in eqn.outvars]
        if reduced:
            payload = outs[0].charge // self._factor(spec)
            self._emit("all_reduce", reduced, payload, cause="reduce",
                       prim=name, mult=mult, scope=scope,
                       trip=trip, record=record)
        return outs

    def _broadcast(self, eqn, read) -> List[_St]:
        st = read(eqn.invars[0])
        out = eqn.outvars[0].aval
        in_shape = tuple(eqn.invars[0].aval.shape)
        bdims = tuple(eqn.params["broadcast_dimensions"])
        entries = [()] * len(out.shape)
        for i, bd in enumerate(bdims):
            if in_shape[i] == out.shape[bd]:
                entries[bd] = st.spec[i]
        # charge looks through the broadcast: gathering the broadcast bias
        # costs the pre-broadcast vector (GSPMD hoists the gather above it)
        return [_St(tuple(entries), st.charge, param=st.param,
                    invariant=st.invariant, pending=st.pending,
                    psrc=st.psrc)]

    def _transpose(self, eqn, read) -> List[_St]:
        st = read(eqn.invars[0])
        perm = tuple(eqn.params["permutation"])
        return [_St(tuple(st.spec[p] for p in perm),
                    _aval_bytes(eqn.outvars[0].aval), param=st.param,
                    invariant=st.invariant, pending=st.pending,
                    psrc=st.psrc)]

    def _squeeze(self, eqn, read) -> List[_St]:
        st = read(eqn.invars[0])
        dims = set(eqn.params["dimensions"])
        spec = tuple(e for d, e in enumerate(st.spec) if d not in dims)
        return [_St(spec, _aval_bytes(eqn.outvars[0].aval), param=st.param,
                    invariant=st.invariant, pending=st.pending,
                    psrc=st.psrc)]

    def _reshape(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        st = read(eqn.invars[0])
        in_shape = tuple(int(s) for s in eqn.invars[0].aval.shape)
        out_shape = tuple(int(s) for s in eqn.outvars[0].aval.shape)
        spec, lost = _reshape_spec(in_shape, out_shape, st.spec, self.sizes)
        if lost:
            self._gather(st, {d: set(a) for d, a in lost.items()},
                         cause=("param_gather" if st.param else "reshape"),
                         prim="reshape", mult=mult, scope=scope, trip=trip,
                         record=record)
            spec, _ = _reshape_spec(in_shape, out_shape, st.spec, self.sizes)
        return [_St(spec, _aval_bytes(eqn.outvars[0].aval), param=st.param,
                    invariant=st.invariant, pending=st.pending,
                    psrc=st.psrc)]

    def _slice(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        st = read(eqn.invars[0])
        in_shape = tuple(int(s) for s in eqn.invars[0].aval.shape)
        out_shape = tuple(int(s) for s in eqn.outvars[0].aval.shape)
        strip = {d: set(st.spec[d]) for d in range(len(in_shape))
                 if st.spec[d] and out_shape[d] != in_shape[d]}
        if strip:
            self._gather(st, strip, cause=("param_gather" if st.param
                                           else "slice"),
                         prim=eqn.primitive.name, mult=mult, scope=scope,
                         trip=trip, record=record)
        return [_St(st.spec, _aval_bytes(eqn.outvars[0].aval),
                    param=st.param, invariant=st.invariant)]

    def _split(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        st = read(eqn.invars[0])
        axis = int(eqn.params.get("axis", 0))
        if st.spec[axis]:
            self._gather(st, {axis: set(st.spec[axis])},
                         cause=("param_gather" if st.param else "slice"),
                         prim="split", mult=mult, scope=scope, trip=trip,
                         record=record)
        return [_St(st.spec, _aval_bytes(ov.aval), param=st.param,
                    invariant=st.invariant, pending=st.pending,
                    psrc=st.psrc) for ov in eqn.outvars]

    def _concat(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        dim = int(eqn.params["dimension"])
        out = eqn.outvars[0].aval
        states = [read(v) for v in eqn.invars]
        for st in states:
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        for st in states:
            if dim < len(st.spec) and st.spec[dim]:
                self._gather(st, {dim: set(st.spec[dim])},
                             cause=("param_gather" if st.param else "concat"),
                             prim="concatenate", mult=mult, scope=scope,
                             trip=trip, record=record)
        entries = []
        for d in range(len(out.shape)):
            axes = set()
            for st in states:
                if d < len(st.spec):
                    axes |= set(st.spec[d])
            entries.append(tuple(sorted(axes)) if d != dim else ())
        return [_St(tuple(entries), _aval_bytes(out))]

    def _pad(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        st = read(eqn.invars[0])
        cfg = eqn.params["padding_config"]
        strip = {d: set(st.spec[d]) for d, (lo, hi, interior)
                 in enumerate(cfg)
                 if st.spec[d] and (lo or hi or interior)}
        if strip:
            self._gather(st, strip, cause=("param_gather" if st.param
                                           else "pad"),
                         prim="pad", mult=mult, scope=scope, trip=trip,
                         record=record)
        return [_St(st.spec, _aval_bytes(eqn.outvars[0].aval),
                    param=st.param, invariant=st.invariant)]

    def _axis_op(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        """cumsum/sort-style ops that couple every element along one dim:
        a sharded op dim must be gathered first."""
        st = read(eqn.invars[0])
        self._materialize(st, mult=mult, scope=scope, trip=trip,
                          record=record)
        d = int(eqn.params.get("axis", eqn.params.get("dimension", 0)))
        if d < len(st.spec) and st.spec[d]:
            self._gather(st, {d: set(st.spec[d])},
                         cause=("param_gather" if st.param else "slice"),
                         prim=eqn.primitive.name, mult=mult, scope=scope,
                         trip=trip, record=record)
        return [_St(st.spec, _aval_bytes(ov.aval), param=st.param,
                    invariant=st.invariant) for ov in eqn.outvars]

    def _reduce_window(self, eqn, read, *, mult, scope, trip,
                       record) -> List[_St]:
        """Pooling: dims with window 1 keep their sharding; a sharded
        windowed (spatial) dim needs halo exchange — model as a gather."""
        st = read(eqn.invars[0])
        self._materialize(st, mult=mult, scope=scope, trip=trip,
                          record=record)
        window = tuple(eqn.params.get("window_dimensions",
                                      (1,) * len(st.spec)))
        strip = {d: set(st.spec[d]) for d in range(len(st.spec))
                 if st.spec[d] and d < len(window) and window[d] != 1}
        if strip:
            self._gather(st, strip, cause="activation_gather",
                         prim=eqn.primitive.name, mult=mult, scope=scope,
                         trip=trip, record=record)
        out = eqn.outvars[0].aval
        spec = tuple(st.spec[d] if d < len(st.spec) else ()
                     for d in range(len(out.shape)))
        return [_St(spec, _aval_bytes(out), param=st.param,
                    invariant=st.invariant)]

    def _gather_scatter(self, eqn, read, *, mult, scope, trip,
                        record) -> List[_St]:
        """Dynamic indexing into a sharded operand: model as a full gather
        of the operand (upper bound — GSPMD sometimes does better)."""
        st = read(eqn.invars[0])
        self._materialize(st, mult=mult, scope=scope, trip=trip,
                          record=record)
        if _spec_axes(st.spec):
            self._gather(st, {d: set(st.spec[d])
                              for d in range(len(st.spec)) if st.spec[d]},
                         cause=("param_gather" if st.param else "gather_op"),
                         prim=eqn.primitive.name, mult=mult, scope=scope,
                         trip=trip, record=record)
        return [self._default_out(eqn, read, i)
                for i in range(len(eqn.outvars))]

    def _explicit_collective(self, eqn, read, *, mult, scope, trip,
                             record) -> List[_St]:
        if eqn.primitive.name == "pbroadcast" and self._manual:
            # shard_map check_rep replication bookkeeping — compiles to
            # nothing, never a wire transfer
            return [self._default_out(eqn, read, i)
                    for i in range(len(eqn.outvars))]
        for v in eqn.invars:
            self._materialize(read(v), mult=mult, scope=scope, trip=trip,
                              record=record)
        axes = eqn.params.get("axes") or eqn.params.get("axis_name") or ()
        if not isinstance(axes, (tuple, list)):
            axes = (axes,)
        payload = sum(_aval_bytes(v.aval) for v in eqn.invars)
        self._emit(_PRIM_KINDS[eqn.primitive.name],
                   [str(a) for a in axes], payload, cause="explicit",
                   prim=eqn.primitive.name, mult=mult, scope=scope,
                   trip=trip, record=record)
        return [self._default_out(eqn, read, i)
                for i in range(len(eqn.outvars))]

    def _shard_map(self, eqn, read, *, mult, scope, trip,
                   record) -> List[_St]:
        """Manual region (the ring / all-to-all attention kernels ride
        shard_map). Every mesh axis is manual inside, so GSPMD inserts NO
        collectives in the body — the walk models only the explicit ones
        (ppermute, psum, ...), whose payloads are the body's per-shard aval
        bytes: the same per-device convention the measured census counts.
        At the boundary, an outer sharding axis that ``in_specs`` does not
        carry on that dim forces an all-gather (manual axes absent from the
        spec require replicated inputs); outputs take their specs straight
        from ``out_specs``."""
        from jax.extend import core  # noqa: PLC0415

        body = eqn.params.get("jaxpr")
        if isinstance(body, core.Jaxpr):
            body = core.ClosedJaxpr(body, ())
        in_specs = eqn.params.get("in_specs")
        out_specs = eqn.params.get("out_specs")
        kw = dict(mult=mult, scope=scope, trip=trip, record=record)
        if (not isinstance(body, core.ClosedJaxpr) or in_specs is None
                or out_specs is None
                or len(body.jaxpr.invars) != len(eqn.invars)
                or len(body.jaxpr.outvars) != len(eqn.outvars)):
            return [self._meet(eqn, read, i, **kw)
                    for i in range(len(eqn.outvars))]

        def names_spec(pspec, ndim):
            """A PartitionSpec as this walker's per-dim axis tuples."""
            entries = tuple(pspec) + (None,) * (ndim - len(pspec))
            return tuple(
                () if e is None else
                tuple(str(a) for a in (e if isinstance(e, tuple) else (e,)))
                for e in entries[:ndim])

        inner_in = []
        for v, iv, pspec in zip(eqn.invars, body.jaxpr.invars, in_specs):
            st = read(v)
            self._materialize(st, **kw)
            want = names_spec(pspec, len(st.spec))
            need = {d: set(st.spec[d]) - set(want[d])
                    for d in range(len(st.spec))
                    if set(st.spec[d]) - set(want[d])}
            if need:
                self._gather(st, need,
                             cause=("param_gather" if st.param
                                    else "mismatch"),
                             prim="shard_map", **kw)
            ishape = tuple(getattr(iv.aval, "shape", ()) or ())
            inner_in.append(_St(tuple(() for _ in ishape),
                                _aval_bytes(iv.aval)))
        prev_manual = self._manual
        self._manual = True
        try:
            self.walk(body, inner_in, **kw)
        finally:
            self._manual = prev_manual
        outs = []
        for ov, pspec in zip(eqn.outvars, out_specs):
            oshape = tuple(getattr(ov.aval, "shape", ()) or ())
            spec = names_spec(pspec, len(oshape))
            outs.append(_St(spec, _aval_bytes(ov.aval)))
        return outs

    # ------------------------------------------------------- control flow
    def _carry_fixpoint(self, probe, carry: List[_St]) -> List[_St]:
        """Stable carry specs for a loop body: iterate carry-in <- body-out
        (GSPMD may shard a replicated init to match the body) up to 3
        rounds; on oscillation fall back to the in/out intersection."""
        for _ in range(3):
            outs = probe(carry)
            changed = False
            nxt = []
            for st, out in zip(carry, outs):
                spec = out.spec if len(out.spec) == len(st.spec) else st.spec
                if spec != st.spec:
                    changed = True
                nxt.append(_St(spec, st.charge, param=st.param))
            carry = nxt
            if not changed:
                return carry
        outs = probe(carry)
        return [
            _St(tuple(tuple(a for a in st.spec[d]
                            if d < len(out.spec) and a in set(out.spec[d]))
                      for d in range(len(st.spec))),
                st.charge, param=st.param)
            for st, out in zip(carry, outs)]

    def _scan(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        from jax.extend import core  # noqa: PLC0415

        body = eqn.params["jaxpr"]
        if isinstance(body, core.Jaxpr):
            body = core.ClosedJaxpr(body, ())
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 1))
        in_states = [read(v) for v in eqn.invars]
        for st in in_states:
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        consts = []
        for st in in_states[:n_consts]:
            consts.append(_St(st.spec, st.charge, param=st.param,
                              invariant=True))
        carry = [_St(st.spec, st.charge, param=st.param)
                 for st in in_states[n_consts:n_consts + n_carry]]
        xs = []
        for st, v in zip(in_states[n_consts + n_carry:],
                         eqn.invars[n_consts + n_carry:]):
            # the body sees per-step slices: drop the leading scan dim
            # (a sharded scan dim would be gathered; unsupported layout)
            xs.append(_St(tuple(st.spec[1:]),
                          st.charge // max(1, int(v.aval.shape[0])),
                          param=st.param))
        # Carry fixpoint, GSPMD-style: the carry may BECOME sharded when the
        # body produces it sharded (resharding the init is a one-time free
        # slice), so iterate carry-in <- body-out until stable; if it
        # oscillates, settle on the intersection (axes that survive the
        # loop) — that direction only under-shards, never invents sharding.
        carry = self._carry_fixpoint(
            lambda c: self.walk(body, consts + c + xs, mult=mult * length,
                                scope="scan", trip=length,
                                record=False)[:len(carry)], carry)
        outs = self.walk(body, consts + carry + xs, mult=mult * length,
                         scope="scan", trip=length, record=record)
        result = []
        for i, ov in enumerate(eqn.outvars):
            st = outs[i] if i < len(outs) else None
            if st is None:
                result.append(_St(tuple(() for _ in ov.aval.shape),
                                  _aval_bytes(ov.aval)))
            elif i < n_carry:
                # the carry leaves the loop still pending: the accumulated
                # partial dW all-reduces ONCE, outside the scan
                result.append(_St(st.spec, _aval_bytes(ov.aval),
                                  pending=st.pending, psrc=st.psrc))
            else:  # stacked ys gain a leading unsharded time dim
                result.append(_St(((),) + tuple(st.spec),
                                  _aval_bytes(ov.aval), pending=st.pending,
                                  psrc=st.psrc))
        return result

    def _while(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        from jax.extend import core  # noqa: PLC0415

        def closed(j):
            return (core.ClosedJaxpr(j, ()) if isinstance(j, core.Jaxpr)
                    else j)

        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond = closed(eqn.params["cond_jaxpr"])
        body = closed(eqn.params["body_jaxpr"])
        in_states = [read(v) for v in eqn.invars]
        for st in in_states:
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        cc = in_states[:cn]
        bc = in_states[cn:cn + bn]
        carry = [_St(st.spec, st.charge, param=st.param)
                 for st in in_states[cn + bn:]]
        carry = self._carry_fixpoint(
            lambda c: self.walk(body, bc + c, mult=mult, scope="while",
                                trip=1, record=False)[:len(carry)], carry)
        self.walk(cond, cc + carry, mult=mult, scope="while", trip=1,
                  record=record)
        outs = self.walk(body, bc + carry, mult=mult, scope="while", trip=1,
                         record=record)
        return [_St(st.spec, _aval_bytes(ov.aval), pending=st.pending,
                    psrc=st.psrc)
                for st, ov in zip(outs, eqn.outvars)]

    def _cond(self, eqn, read, *, mult, scope, trip, record) -> List[_St]:
        from jax.extend import core  # noqa: PLC0415

        branches = [core.ClosedJaxpr(b, ()) if isinstance(b, core.Jaxpr)
                    else b for b in eqn.params["branches"]]
        ops = [read(v) for v in eqn.invars[1:]]
        for st in ops:
            self._materialize(st, mult=mult, scope=scope, trip=trip,
                              record=record)
        best_events: Optional[List[dict]] = None
        best_outs: Optional[List[_St]] = None
        best_bytes = -1
        for br in branches:
            mark = len(self.events)
            outs = self.walk(br, [(_St(s.spec, s.charge, param=s.param))
                                  for s in ops],
                             mult=mult, scope=scope, trip=trip,
                             record=record)
            ev = self.events[mark:]
            del self.events[mark:]
            total = sum(e["bytes"] * e["count"] for e in ev)
            if total > best_bytes:
                best_bytes, best_events, best_outs = total, ev, outs
        if record and best_events:
            self.events.extend(best_events)
        outs = best_outs or []
        return [(outs[i] if i < len(outs)
                 else _St(tuple(() for _ in ov.aval.shape),
                          _aval_bytes(ov.aval)))
                for i, ov in enumerate(eqn.outvars)]


def _reshape_spec(in_shape, out_shape, spec, sizes):
    """Map a sharding spec through a reshape. Returns ``(out_spec, lost)``
    where ``lost`` maps input dims to axes that cannot survive (a sharded
    dim merged as a minor factor, or split such that the shard factor does
    not divide the major output factor) — GSPMD keeps only a MAJOR-most
    sharded factor whose shard count divides the major output dim."""
    groups = _reshape_groups(in_shape, out_shape)
    out_entries = [()] * len(out_shape)
    lost: Dict[int, set] = {}
    for in_dims, out_dims in groups:
        sharded = [(d, spec[d]) for d in in_dims if d < len(spec) and spec[d]]
        if not sharded:
            continue
        if len(in_dims) == 1 and len(out_dims) == 1:
            out_entries[out_dims[0]] = spec[in_dims[0]]
            continue
        d0 = in_dims[0]
        for d, axes in sharded:
            factor = 1
            for a in axes:
                factor *= sizes.get(a, 1)
            if (d == d0 and out_dims and out_shape[out_dims[0]] % factor == 0):
                out_entries[out_dims[0]] = axes
            else:
                lost.setdefault(d, set()).update(axes)
    return tuple(out_entries), lost


def _reshape_groups(in_shape, out_shape):
    """Partition the dims of a reshape into minimal groups with equal
    element products (the standard factor-matching walk)."""
    groups = []
    i = j = 0
    while i < len(in_shape) or j < len(out_shape):
        gi, gj = [i], [j]
        pi = in_shape[i] if i < len(in_shape) else 1
        pj = out_shape[j] if j < len(out_shape) else 1
        while pi != pj:
            if pi < pj and gi[-1] + 1 < len(in_shape):
                gi.append(gi[-1] + 1)
                pi *= in_shape[gi[-1]]
            elif pj < pi and gj[-1] + 1 < len(out_shape):
                gj.append(gj[-1] + 1)
                pj *= out_shape[gj[-1]]
            else:
                break
        groups.append((
            [d for d in gi if d < len(in_shape)],
            [d for d in gj if d < len(out_shape)]))
        i = gi[-1] + 1
        j = gj[-1] + 1
    return groups


# ------------------------------------------------------------- entry points
def propagate_jaxpr(closed_jaxpr, in_specs, layout, *,
                    declared_out_specs: Optional[Sequence] = None,
                    param_flags: Optional[Sequence[bool]] = None) -> _Flow:
    """Run the propagation over ``closed_jaxpr``.

    ``in_specs``: one PartitionSpec (or None) per flat invar.
    ``param_flags``: True for invars that are parameters/optimizer moments
    (their gathers are the documented ZeRO cost, not DT300 material).
    ``declared_out_specs``: specs the leading outvars are REQUIRED to have
    (the declared param/opt placements); a propagated spec that gained
    extra axes predicts the output-boundary all-gather (ZeRO-1's per-step
    param gather).
    """
    sizes = dict(layout.axis_sizes)
    flow = _Flow(sizes, layout.batch_axes)
    invars = closed_jaxpr.jaxpr.invars
    states = []
    for i, v in enumerate(invars):
        ndim = len(getattr(v.aval, "shape", ()) or ())
        spec = _norm_spec(in_specs[i] if i < len(in_specs) else None, ndim)
        # drop axes the layout does not know (defensive) and axes of size 1
        spec = tuple(tuple(a for a in dim if sizes.get(a, 1) > 1)
                     for dim in spec)
        states.append(_St(
            spec, _aval_bytes(v.aval),
            param=(bool(param_flags[i])
                   if param_flags and i < len(param_flags) else False)))
    outs = flow.walk(closed_jaxpr, states, record=True)
    # outputs must be materialized: a partial-sum result crossing the
    # program boundary pays its deferred all-reduce (the loss mean, a grad
    # returned raw)
    for st in outs:
        flow._materialize(st, mult=1, scope="top", trip=1, record=True)
    if declared_out_specs:
        for i, decl in enumerate(declared_out_specs):
            if decl is None or i >= len(outs):
                continue
            ov = closed_jaxpr.jaxpr.outvars[i]
            ndim = len(getattr(ov.aval, "shape", ()) or ())
            want = _spec_axes(tuple(
                tuple(a for a in dim if sizes.get(a, 1) > 1)
                for dim in _norm_spec(decl, ndim)))
            have = _spec_axes(outs[i].spec)
            extra = have - want
            if extra:
                payload = _aval_bytes(ov.aval) // flow._factor(
                    tuple((tuple(want),)) if want else ((),))
                flow._emit("all_gather", extra, payload, cause="output",
                           prim="output", mult=1, scope="top", trip=1,
                           record=True, param=True)
    return flow


def _census_rows(events: List[dict]) -> List[dict]:
    agg: Dict[Tuple[str, Tuple[str, ...]], dict] = {}
    for e in events:
        key = (e["kind"], e["axes"])
        row = agg.setdefault(key, {"kind": e["kind"],
                                   "axes": list(e["axes"]),
                                   "count": 0, "bytes": 0})
        row["count"] += e["count"]
        row["bytes"] += e["bytes"] * e["count"]
    return sorted(agg.values(), key=lambda r: (-r["bytes"], r["kind"]))


def flow_report(flow: _Flow) -> dict:
    """JSON-ready summary of one propagation run: the predicted census
    (per-device payload bytes, keyed like the measured HLO census), the
    communication total feeding the ICI roofline term, and the per-shape
    shard factors preflight's activation projection uses."""
    census = _census_rows(flow.events)
    factors = []
    for shape, counts in sorted(flow.shape_factors.items()):
        f = max(counts, key=lambda k: (counts[k], k))
        factors.append({"shape": list(shape), "factor": int(f)})
    return {
        "census": census,
        "comm_bytes_per_step": int(sum(r["bytes"] for r in census)),
        "events": len(flow.events),
        "activation_factors": factors,
    }


def shard_findings(flow: _Flow, *, source: str = IR_SOURCE,
                   dt300_floor: int = DT300_FLOOR_BYTES,
                   dt301_floor: int = DT301_FLOOR_BYTES,
                   dt302_floor: int = DT302_FLOOR_BYTES,
                   pipeline_microbatches: Optional[int] = None,
                   pipe_axis: str = "pipe") -> List[Finding]:
    """DT300-DT304 over the recorded events (DT305 needs layer knowledge
    and is emitted by :func:`check_network_shard_flow`); DT306 — the piped
    twin of DT304 — when ``pipeline_microbatches`` is given: a collective
    inside a pipeline stage body repeating once per micro-batch tick."""
    findings: List[Finding] = []
    batch = flow.batch_axes
    for e in flow.events:
        payload = e["bytes"]
        axes = ", ".join(e["axes"])
        where = f" inside {e['scope']}" if e["scope"] in ("scan",
                                                          "while") else ""
        if e["kind"] == "all_gather" and not e["param"] \
                and e["cause"] not in ("output",) \
                and payload >= dt300_floor:
            findings.append(get_rule("DT300").finding(
                f"{e['prim']}{where} forces a full all-gather of a sharded "
                f"tensor over ({axes}): ~{_fmt_bytes(payload)} "
                f"materialized per step (cause: {e['cause']})",
                file=source, context=e["prim"]))
        if e["cause"] == "mismatch" and not e["param"] \
                and payload >= dt301_floor:
            findings.append(get_rule("DT301").finding(
                f"producer/consumer sharding mismatch at {e['prim']}"
                f"{where}: GSPMD reshards ~{_fmt_bytes(payload)} over "
                f"({axes}) between the two placements",
                file=source, context=e["prim"]))
        if e["kind"] == "all_reduce" and payload >= dt302_floor \
                and not set(e["axes"]) <= batch:
            findings.append(get_rule("DT302").finding(
                f"{e['prim']}{where} contraction over a ({axes})-sharded "
                f"dim all-reduces ~{_fmt_bytes(payload)} of activations "
                "per step — larger than a gradient sync has any right to be",
                file=source, context=e["prim"]))
        if e["kind"] == "all_gather" and not e["param"] \
                and e["cause"] not in ("output",) \
                and set(e["axes"]) & batch:
            findings.append(get_rule("DT303").finding(
                f"{e['prim']}{where} drops the batch axis ({axes}): "
                "downstream compute runs replicated on every device "
                f"(~{_fmt_bytes(payload)} gathered, parallel speedup lost)",
                file=source, context=e["prim"]))
        if e["scope"] == "scan" and e["trip"] > 1 and e["count"] > 1:
            findings.append(get_rule("DT304").finding(
                f"{e['kind']} inside a scan body runs every step: "
                f"{e['count']}x ~{_fmt_bytes(payload)} over ({axes}) per "
                f"optimizer step (trip count {e['trip']})",
                file=source, context=e["prim"]))
    if pipeline_microbatches and pipeline_microbatches > 1:
        # DT306: inside the (manual) pipelined region, the pipe-axis
        # ppermute handoffs and final psum ARE the schedule — but any other
        # collective appearing >= M times is running once per micro-batch
        # tick (e.g. an fsdp param gather traced inside a stage body
        # instead of hoisted before the tick loop)
        per_tick: Dict[Tuple[str, Tuple[str, ...], str], dict] = {}
        for e in flow.events:
            if not e.get("manual"):
                continue
            if pipe_axis in e["axes"]:
                continue
            key = (e["kind"], e["axes"], e["prim"])
            row = per_tick.setdefault(key, {"count": 0, "bytes": 0})
            row["count"] += e["count"]
            row["bytes"] += e["bytes"] * e["count"]
        for (kind, e_axes, prim), row in sorted(per_tick.items()):
            if row["count"] >= pipeline_microbatches:
                findings.append(get_rule("DT306").finding(
                    f"{kind} over ({', '.join(e_axes)}) repeats inside the "
                    f"pipeline stage body: {row['count']}x per step "
                    f"(~{_fmt_bytes(row['bytes'])} total) with "
                    f"{pipeline_microbatches} micro-batches — hoist it "
                    "above the tick loop so it runs once per step, not "
                    "once per micro-batch",
                    file=source, context=prim))
    return merge_findings(findings)


def _flatten_specs(spec_tree) -> List[Any]:
    """Flatten a pytree of PartitionSpecs. P is a tuple subclass, so a
    plain tree_flatten would explode it into its entries — treat every
    PartitionSpec as a leaf."""
    import jax  # noqa: PLC0415
    from jax.sharding import PartitionSpec  # noqa: PLC0415

    return jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]


def analyze_shard_flow(fn, example_args, in_specs, layout, *,
                       declared_out_specs=None, param_argnums: Sequence[int]
                       = (), pipeline_microbatches: Optional[int] = None,
                       source: str = IR_SOURCE) -> dict:
    """Trace ``fn`` over ``example_args`` (arrays or ShapeDtypeStructs —
    nothing executes) and run the propagation seeded with ``in_specs`` (a
    pytree-of-PartitionSpecs per argument, or flat list). Returns
    ``{"findings": [...], **flow_report}``. ``pipeline_microbatches``
    enables the DT306 per-microbatch-collective advisory for pipelined
    steps."""
    import jax  # noqa: PLC0415

    closed = jax.make_jaxpr(fn)(*example_args)
    flat_specs = _flatten_specs(in_specs)
    flags = []
    for i, a in enumerate(example_args):
        n = len(jax.tree_util.tree_leaves(a))
        flags += [i in set(param_argnums)] * n
    flow = propagate_jaxpr(closed, flat_specs, layout,
                           declared_out_specs=(
                               _flatten_specs(declared_out_specs)
                               if declared_out_specs is not None else None),
                           param_flags=flags)
    report = flow_report(flow)
    report["findings"] = shard_findings(
        flow, source=source, pipeline_microbatches=pipeline_microbatches)
    return report


_HEAD_AWARE_LAYERS = ("LSTM", "Attention")


def check_network_shard_flow(net, batch_or_struct=None, layout=None, *,
                             train: bool = True,
                             timesteps_probe: Optional[int] = None,
                             source: str = IR_SOURCE) -> dict:
    """The shard-flow pass over a net's REAL train step (or forward pass
    with ``train=False``) under ``layout``: params/moments seeded with
    ``param_specs``/``opt_specs``, the batch with ``batch_spec``. Returns
    ``{"findings": [...], "census": [...], "comm_bytes_per_step": ...}``.
    Zero device dispatches — pure ``jax.make_jaxpr`` spec algebra."""
    import jax  # noqa: PLC0415

    from ..telemetry.memory import (  # noqa: PLC0415
        DEFAULT_TIMESTEPS_PROBE, _input_structs)
    from .ir_checks import _label_structs, _shell_tree  # noqa: PLC0415

    if layout is None:
        raise ValueError("check_network_shard_flow needs a MeshLayout")
    t_probe = (DEFAULT_TIMESTEPS_PROBE if timesteps_probe is None
               else int(timesteps_probe))
    net.init()
    if getattr(layout, "roles", False) and hasattr(layout, "bind"):
        layout.bind(net)  # resolve role sites so param_specs are head-aware
    inputs = _input_structs(net, batch_or_struct, timesteps_probe=t_probe)
    conf_dtype = getattr(net.conf, "dtype", "float32")
    params = _shell_tree(net.params, conf_dtype)
    is_graph = hasattr(net.conf, "vertices")
    x_arg = inputs if is_graph else inputs[0]
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    param_specs = layout.param_specs(params)
    batch = layout.batch_spec()
    _in_fn = getattr(layout, "input_spec", None)

    def _in_spec(leaf):
        # seq-axis layouts shard [B,T,..] request tensors on time too
        if _in_fn is not None:
            return _in_fn(getattr(leaf, "ndim", None))
        return batch

    # seq-axis layouts execute attention through the shard_map ring
    # kernels (layout.apply installs the mesh) — trace the SAME program
    # here, else the census models a local kernel the net will never run
    _restore = None
    _seq_axis = getattr(layout, "_seq_axis", None)
    if _seq_axis is not None:
        from ..nn.layers.attention import (  # noqa: PLC0415
            get_attention_mesh, set_attention_mesh)
        _prev = get_attention_mesh()
        set_attention_mesh(layout.mesh, _seq_axis, nets=(net,),
                           batch_axes=getattr(layout, "_batch_axes", ()))

        def _restore():
            if _prev is None:
                set_attention_mesh(None, nets=(net,))
            else:
                set_attention_mesh(
                    _prev[0], _prev[1], nets=(net,),
                    batch_axes=_prev[2] if len(_prev) > 2 else ())

    try:
        if train:
            opt_state = _shell_tree(net.opt_state, conf_dtype)
            state = _shell_tree(net.state, conf_dtype)
            rng = jax.ShapeDtypeStruct(tuple(net._rng.shape), net._rng.dtype)
            labels = _label_structs(net, int(inputs[0].shape[0]), t_probe)
            step = net._build_train_step()
            inner = getattr(step, "__wrapped__", step)
            args = (params, opt_state, state, x_arg, labels, rng, None, None)
            opt_specs = (layout.opt_specs(opt_state)
                         if hasattr(layout, "opt_specs")
                         else layout.param_specs(opt_state))
            in_spec_tree = (param_specs, opt_specs,
                            jax.tree_util.tree_map(lambda _: P(), state),
                            jax.tree_util.tree_map(_in_spec, x_arg),
                            jax.tree_util.tree_map(_in_spec, labels),
                            P(), None, None)
            n_param = len(jax.tree_util.tree_leaves(params))
            n_opt = len(jax.tree_util.tree_leaves(opt_state))
            flags = [True] * (n_param + n_opt)
            declared = (_flatten_specs(param_specs)
                        + _flatten_specs(opt_specs))
        else:
            state = _shell_tree(net.state, conf_dtype)
            if is_graph:
                def inner(p, xs):
                    acts, _, _ = net._activations(p, xs, state, False, None,
                                                  None)
                    return acts
            else:
                def inner(p, x):
                    out, _, _ = net._forward(p, x, state, False, None)
                    return out
            args = (params, x_arg)
            in_spec_tree = (param_specs,
                            jax.tree_util.tree_map(_in_spec, x_arg))
            flags = [True] * len(jax.tree_util.tree_leaves(params))
            declared = None

        closed = jax.make_jaxpr(inner)(*args)
    finally:
        if _restore is not None:
            _restore()
    flat_specs = _flatten_specs(in_spec_tree)
    flow = propagate_jaxpr(closed, flat_specs, layout,
                           declared_out_specs=declared, param_flags=flags)
    report = flow_report(flow)
    report["layout"] = layout.describe()
    findings = shard_findings(flow, source=source)

    # DT305: generic tp specs on attention/LSTM-gate sites — the per-step
    # tp collectives on their activations would vanish under head-aware
    # specs (shard heads/gates, not the flat last dim). Advisory. A site
    # that RESOLVED through a head-aware role rule (attention_qkv/
    # attention_out/lstm_gates via MeshLayout(roles=True)) is exempt: its
    # remaining tp traffic is the intended ONE-all-reduce Megatron pattern.
    tp_axis = getattr(layout, "_tp_axis", None)
    if tp_axis is not None:
        conf = net.conf
        if hasattr(conf, "vertices"):
            layer_types = [type(getattr(v, "layer", v)).__name__
                           for v in conf.vertices.values()]
        else:
            layer_types = [type(l).__name__ for l in conf.layers]
        resolved = (layout.role_resolved_types()
                    if getattr(layout, "roles", False)
                    and hasattr(layout, "role_resolved_types") else set())
        sites = sorted({t for t in layer_types
                        if any(k in t for k in _HEAD_AWARE_LAYERS)
                        and t not in resolved})
        tp_events = [e for e in flow.events
                     if tp_axis in e["axes"] and not e["param"]]
        if sites and tp_events:
            total = sum(e["bytes"] * e["count"] for e in tp_events)
            findings.append(get_rule("DT305").finding(
                f"{len(tp_events)} per-step tp collective(s) "
                f"(~{_fmt_bytes(total)}) land on activations of "
                f"{', '.join(sites)}: the generic last-dim tp spec splits "
                "heads/gates across devices — resolve these sites through "
                "the layer-roles registry: MeshLayout(..., roles=True) "
                "reads the layers' PARAM_ROLES declarations, and "
                "parallel.roles.register_layer_role(layer_cls, param, "
                "role) opts custom layers in (docs/distributed.md, 'Layer "
                "roles & head-aware tp')", file=source, context="tp"))
    report["findings"] = merge_findings(findings)
    return report


# ----------------------------------------------------- measured census (HLO)
_HLO_OP_RE = re.compile(
    r"=\s*(?P<result>\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]+|pred)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[0-9,{} ]*\}\}|\[[0-9,]+\]<=\[[0-9,]+\]"
    r"(?:T\([0-9,]+\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{[0-9, ]+\},?)*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _parse_groups(text: str) -> Optional[frozenset]:
    """replica_groups in either literal ``{{0,1},{2,3}}`` or iota
    ``[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)`` form -> frozenset of
    frozensets of device ids."""
    text = text.strip()
    if text.startswith("{"):
        groups = re.findall(r"\{([0-9, ]+)\}", text)
        return frozenset(frozenset(int(x) for x in g.split(","))
                         for g in groups if g.strip())
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", text)
    if not m:
        return None
    gshape = [int(x) for x in m.group(1).split(",")]
    ishape = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(int(np.prod(ishape))).reshape(ishape)
    if m.group(3):
        perm = [int(x) for x in m.group(3).split(",")]
        ids = ids.transpose(perm)
    ids = ids.reshape(gshape)
    return frozenset(frozenset(int(x) for x in row) for row in ids)


def _axis_groups(mesh) -> List[Tuple[Tuple[str, ...], frozenset]]:
    """Every non-trivial subset of mesh axes -> its replica-group set."""
    import itertools  # noqa: PLC0415

    names = list(mesh.axis_names)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    out = []
    live = [n for n in names if mesh.shape[n] > 1]
    for r in range(1, len(live) + 1):
        for sub in itertools.combinations(live, r):
            sub_dims = [names.index(n) for n in sub]
            other = [d for d in range(len(names)) if d not in sub_dims]
            moved = np.transpose(ids, other + sub_dims)
            moved = moved.reshape(-1, int(np.prod(
                [ids.shape[d] for d in sub_dims], dtype=np.int64)))
            groups = frozenset(frozenset(int(x) for x in row)
                               for row in moved)
            out.append((tuple(sub), groups))
    return out


def hlo_collective_census(hlo_text: str, layout=None) -> List[dict]:
    """The MEASURED census: parse a compiled executable's post-SPMD HLO for
    collective ops. Each row: ``{kind, axes, count, bytes}`` — bytes are the
    per-device ``max(operands, results)`` payload (the convention the
    predicted census uses), axes the mesh axes whose replica groups match
    (``["?"]`` when no axis subset of the given layout's mesh matches).
    All-gathers of the same source operands over the same groups/dims are
    one LOGICAL collective counted once — XLA may materialize extra copies
    purely for consumer layouts; ``layout_dups`` on the row records them.
    """
    mesh = getattr(layout, "mesh", None) if layout is not None else None
    axis_groups = _axis_groups(mesh) if mesh is not None else []
    rows: Dict[Tuple[str, Tuple[str, ...]], dict] = {}
    seen_gathers: Dict[tuple, dict] = {}
    # long tuples carry /*index=5*/ position comments, whose "=" would end
    # the result-shape match early and drop the fused gradient all-reduce
    for line in re.sub(r"/\*.*?\*/", "", hlo_text).splitlines():
        m = _HLO_OP_RE.search(line)
        if not m:
            continue
        kind = _HLO_KINDS[m.group("op")]
        result_bytes = sum(_shape_bytes(d, s)
                           for d, s in _SHAPE_RE.findall(m.group("result")))
        operands = line[m.end():]
        # operand list ends at the first attribute (channel_id=, dimensions=,
        # replica_groups=, to_apply=, metadata=)
        op_text = re.split(r"\b(?:channel_id|dimensions|replica_groups|"
                           r"source_target_pairs|to_apply|metadata)=",
                           operands)[0]
        operand_bytes = sum(_shape_bytes(d, s)
                            for d, s in _SHAPE_RE.findall(op_text))
        payload = max(result_bytes, operand_bytes)
        axes: Tuple[str, ...] = ("?",)
        gm = _GROUPS_RE.search(line)
        if gm:
            groups = _parse_groups(gm.group(1))
            if groups is not None:
                if all(len(g) <= 1 for g in groups):
                    continue  # degenerate single-device groups
                for sub, expected in axis_groups:
                    if groups == expected:
                        axes = sub
                        break
        elif kind == "collective_permute":
            # permutes carry source_target_pairs, not replica_groups:
            # attribute to the smallest axis subset whose groups contain
            # every pair (a seq-ring's hops stay within each seq group)
            pm = _PAIRS_RE.search(line)
            if pm:
                pairs = [tuple(int(x) for x in p.split(","))
                         for p in re.findall(r"\{([0-9, ]+)\}",
                                             pm.group(1))]
                if pairs and all(s != t for s, t in pairs):
                    for sub, expected in axis_groups:
                        if all(any({s, t} <= g for g in expected)
                               for s, t in pairs):
                            axes = sub
                            break
        row = rows.setdefault((kind, axes), {
            "kind": kind, "axes": list(axes), "count": 0, "bytes": 0})
        if kind == "all_gather":
            # XLA materializes the SAME logical gather once per consumer
            # physical layout (CSE stops at layout boundaries — e.g. the
            # saved attention context re-gathered for each backward dot's
            # preferred operand order). One logical collective, several
            # wire copies the static pass cannot see: count it once and
            # record the duplication on the row.
            ops = tuple(re.findall(r"%[\w.\-]+", op_text))
            dm = re.search(r"dimensions=\{([0-9,]*)\}", line)
            key = (axes, ops, dm.group(1) if dm else None)
            if ops and key in seen_gathers:
                dup = seen_gathers[key]
                dup["layout_dups"] = dup.get("layout_dups", 0) + 1
                continue
            seen_gathers[key] = row
        row["count"] += 1
        row["bytes"] += payload
    return sorted(rows.values(), key=lambda r: (-r["bytes"], r["kind"]))


def compare_census(predicted: List[dict], measured: List[dict], *,
                   byte_tolerance: float = 1.5,
                   minor_fraction: float = 0.10) -> dict:
    """Hold the predicted census to the measured one.

    Rules: every kind carrying at least ``minor_fraction`` of the measured
    (or predicted) bytes must appear on the other side with the same mesh
    axes, and both the per-major-kind and total byte sums must agree within
    ``byte_tolerance`` in either direction. Small resharding noise (the
    few-KiB all-to-alls GSPMD sprinkles) stays below the fraction floor.
    """
    def by_kind(rows):
        out: Dict[str, dict] = {}
        for r in rows:
            row = out.setdefault(r["kind"], {"bytes": 0, "count": 0,
                                             "axes": set(), "rows": []})
            row["bytes"] += r["bytes"]
            row["count"] += r["count"]
            row["rows"].append(r)
        for row in out.values():
            # axes come only from rows that are major WITHIN the kind —
            # a 2 KiB resharding gather must not pollute the axes of the
            # 80 KiB param gathers
            for r in row["rows"]:
                if r["bytes"] >= minor_fraction * max(row["bytes"], 1):
                    row["axes"] |= set(r["axes"])
            del row["rows"]
        return out

    p, m = by_kind(predicted), by_kind(measured)
    p_total = sum(r["bytes"] for r in p.values())
    m_total = sum(r["bytes"] for r in m.values())
    problems: List[str] = []
    detail: Dict[str, dict] = {}
    majors = set()
    for kind, row in m.items():
        if row["bytes"] >= minor_fraction * max(m_total, 1):
            majors.add(kind)
    for kind, row in p.items():
        if row["bytes"] >= minor_fraction * max(p_total, 1):
            majors.add(kind)
    for kind in sorted(majors):
        pr, mr = p.get(kind), m.get(kind)
        if pr is None or mr is None:
            problems.append(f"kind {kind} only "
                            f"{'measured' if pr is None else 'predicted'}")
            detail[kind] = {"predicted": pr and pr["bytes"],
                            "measured": mr and mr["bytes"]}
            continue
        ratio = (pr["bytes"] / mr["bytes"]) if mr["bytes"] else float("inf")
        detail[kind] = {"predicted_bytes": pr["bytes"],
                        "measured_bytes": mr["bytes"],
                        "ratio": round(ratio, 4),
                        "predicted_axes": sorted(pr["axes"]),
                        "measured_axes": sorted(mr["axes"])}
        if not (1.0 / byte_tolerance <= ratio <= byte_tolerance):
            problems.append(f"{kind} bytes off {ratio:.2f}x")
        if "?" in mr["axes"]:
            problems.append(f"{kind} measured groups match no mesh axes")
        elif pr["axes"] != mr["axes"]:
            problems.append(
                f"{kind} axes differ: predicted {sorted(pr['axes'])} vs "
                f"measured {sorted(mr['axes'])}")
    total_ratio = (p_total / m_total) if m_total else (
        1.0 if not p_total else float("inf"))
    if m_total or p_total:
        if not (1.0 / byte_tolerance <= total_ratio <= byte_tolerance):
            problems.append(f"total bytes off {total_ratio:.2f}x")
    return {
        "ok": not problems,
        "problems": problems,
        "total_ratio": (round(total_ratio, 4)
                        if m_total or p_total else 1.0),
        "predicted_total_bytes": int(p_total),
        "measured_total_bytes": int(m_total),
        "kinds": detail,
    }
