"""Pass 3: IR lint (DT2xx) over the traced jaxpr + compiled artifacts.

PR 1's passes stop at Python AST and layer-graph level; this pass asks the
question neither can answer — *what did the compiler actually do to the step
function?* It traces the real train step with ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` shells (zero device dispatches — proven by a
counting-tracer test) and walks the eqns:

- **DT200** strong float64 appearing from non-f64 inputs (silent promotion)
- **DT201** host callbacks traced into the step
- **DT202** requested buffer donation the compiler will drop (audited by
  replaying jax's own shape/dtype output-matching over the donated avals)
- **DT203** materialization blow-ups (output ≫ operands)
- **DT204** gather/scatter with traced (non-constant) indices — constness
  is propagated forward AND across nested-jaxpr boundaries (a baked numpy
  index array threaded into a scanned/pjit sub-jaxpr stays constant)
- **DT205** padding waste from the BucketedStager's pow2 buckets vs the
  real batch statistics of an epoch
- **DT206** arithmetic intensity below the roofline ridge (memory-bound)
- **DT207** per-step collective count + payload volume

The static roofline numbers come from :mod:`.cost_model`; the compile
manager calls :func:`admission_check` on every AOT executable it admits
(findings → ``dl4jtpu_ir_findings_total{rule}`` + flight-recorder events,
cost reports next to the PR 4 memory records), and ``preflight()`` folds the
same report in so "donation dropped, step predicted HBM-bound" arrives
before the first real dispatch.

IR findings carry no source line, so line pragmas cannot suppress them; use
the ``ignore=("DT204", ...)`` argument (or the CLI ``--ignore`` flag).
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Sequence, Tuple

from .cost_model import jaxpr_cost, subjaxprs
from .findings import Finding, merge_findings
from .rules import get_rule

__all__ = [
    "check_jaxpr_ir",
    "audit_donation",
    "check_network_ir",
    "analyze_config_ir",
    "check_padding_waste",
    "record_findings",
    "ir_findings_family",
    "admission_check",
]

IR_SOURCE = "<ir>"

# DT203 thresholds: an eqn only counts as a blow-up when its output is BOTH
# this many times bigger than its operands AND big in absolute terms (tiny
# bias broadcasts are free — XLA fuses them)
DT203_RATIO = 8.0
DT203_FLOOR_BYTES = 32 << 20  # 32 MiB

# DT205 default: warn when >30% of staged elements were padding
DT205_THRESHOLD = 0.30

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "debug_print"}


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0:
            return f"{n:.1f} {unit}"
        n /= 1024.0
    return f"{n:.1f} TiB"


def _is_strong_f64(aval) -> bool:
    import numpy as np

    dt = getattr(aval, "dtype", None)
    return (dt is not None and dt == np.dtype("float64")
            and not getattr(aval, "weak_type", False))


def _is_f64(aval) -> bool:
    import numpy as np

    dt = getattr(aval, "dtype", None)
    return dt is not None and dt == np.dtype("float64")


def _nested_const_invars(eqn, nested, const_flags):
    """Map constness across a call boundary: for each ``(sub, mult)`` in
    ``nested`` (the :func:`subjaxprs` output for ``eqn``), the set of the
    sub-jaxpr's invars that receive a trace-time constant.

    ``const_flags[i]`` says whether ``eqn.invars[i]`` is constant in the
    enclosing jaxpr. Primitive-specific layouts:

    - ``scan``: invars are ``[*consts, *carry, *xs]``; consts map 1:1 and a
      constant stacked ``xs`` array stays constant per-slice, but the carry
      mutates across iterations and is never propagated.
    - ``while``: ``[*cond_consts, *body_consts, *carry]``; each sub-jaxpr
      sees its own consts followed by the (non-const) carry.
    - ``cond``: ``[pred, *operands]``; every branch sees the operands.
    - generic wrappers (pjit/remat/custom_*): 1:1 when the arities match,
      conservatively nothing otherwise.
    """
    name = eqn.primitive.name
    out = []
    if name == "scan":
        n_consts = int(eqn.params.get("num_consts", 0))
        n_carry = int(eqn.params.get("num_carry", 0))
        for sub, _ in nested:
            iv = sub.jaxpr.invars
            cs = set()
            for j in range(min(n_consts, len(iv), len(const_flags))):
                if const_flags[j]:
                    cs.add(iv[j])
            base = n_consts + n_carry
            for k in range(base, min(len(iv), len(const_flags))):
                if const_flags[k]:
                    cs.add(iv[k])
            out.append(cs)
        return out
    if name == "while":
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        offsets = []
        if eqn.params.get("cond_jaxpr") is not None:
            offsets.append((0, cn))
        if eqn.params.get("body_jaxpr") is not None:
            offsets.append((cn, bn))
        for (off, n), (sub, _) in zip(offsets, nested):
            iv = sub.jaxpr.invars
            cs = set()
            for j in range(min(n, len(iv))):
                if off + j < len(const_flags) and const_flags[off + j]:
                    cs.add(iv[j])
            out.append(cs)
        return out
    if name == "cond":
        for sub, _ in nested:
            iv = sub.jaxpr.invars
            cs = {v for j, v in enumerate(iv)
                  if 1 + j < len(const_flags) and const_flags[1 + j]}
            out.append(cs)
        return out
    for sub, _ in nested:
        iv = sub.jaxpr.invars
        cs = ({v for v, flag in zip(iv, const_flags) if flag}
              if len(iv) == len(const_flags) else set())
        out.append(cs)
    return out


def _iter_leaf_eqns(closed):
    """Yield ``(eqn, const_derived)`` for every leaf eqn (no nested jaxpr),
    recursing through pjit/scan/while/cond/remat wrappers.

    ``const_derived`` is the set of vars in the eqn's enclosing jaxpr that
    are trace-time constants — the constvars plus anything computed from
    constants alone (forward const propagation, so indices that pass
    through a ``convert_element_type`` of a baked numpy array still read as
    static). Constness crosses nested-jaxpr boundaries: a baked index array
    threaded into a scanned/cond/pjit sub-jaxpr as an argument arrives there
    as a constant (:func:`_nested_const_invars` maps the positions), closing
    the DT204 per-jaxpr limitation PR 5 shipped with.
    """
    from jax.extend import core  # noqa: PLC0415

    stack = [(closed, frozenset())]
    seen = set()
    while stack:
        c, const_in = stack.pop()
        key = (id(c.jaxpr), tuple(sorted(id(v) for v in const_in)))
        if key in seen:
            continue
        seen.add(key)
        constish = set(c.jaxpr.constvars) | set(const_in)
        for eqn in c.jaxpr.eqns:
            nested = subjaxprs(eqn)
            if nested:
                flags = [isinstance(v, core.Literal) or v in constish
                         for v in eqn.invars]
                stack.extend(
                    (sub, frozenset(cs)) for (sub, _), cs in zip(
                        nested, _nested_const_invars(eqn, nested, flags)))
            else:
                yield eqn, constish
            if eqn.invars and all(
                    isinstance(v, core.Literal) or v in constish
                    for v in eqn.invars):
                constish.update(eqn.outvars)


# ------------------------------------------------------------- jaxpr checks
def check_jaxpr_ir(closed_jaxpr, *, source: str = IR_SOURCE,
                   cost: Optional[dict] = None,
                   blowup_ratio: float = DT203_RATIO,
                   blowup_floor_bytes: int = DT203_FLOOR_BYTES) -> List[Finding]:
    """DT200/201/203/204 over the eqns of a traced jaxpr, plus DT206/207
    from a :func:`~.cost_model.jaxpr_cost` report (computed here when not
    passed in). Findings are aggregated per (rule, primitive, signature) so
    a promotion repeated through the backward pass reads as ONE finding."""
    from .cost_model import _aval_bytes  # noqa: PLC0415 - shared helper

    findings: List[Finding] = []
    promo: dict = {}
    callbacks: dict = {}
    blowups: dict = {}
    dynamic_idx: dict = {}

    for eqn, const_derived in _iter_leaf_eqns(closed_jaxpr):
        name = eqn.primitive.name
        ins = [getattr(v, "aval", None) for v in eqn.invars]
        outs = [getattr(v, "aval", None) for v in eqn.outvars]

        # DT200: a strong f64 result from at least one non-f64 operand is
        # the promotion POINT; all-f64 eqns are downstream of one already.
        # Scalar results are exempt — x64-mode scalar bookkeeping (optax
        # bias correction etc.) runs on the scalar core for free; the
        # hazard is a promoted TENSOR dragging its dataflow cone to f64.
        from .cost_model import _aval_elems  # noqa: PLC0415

        if ins and any(not _is_f64(a) for a in ins) and any(
                _is_strong_f64(o) and _aval_elems(o) > 1 for o in outs):
            sig = (name, tuple(str(getattr(a, "dtype", "?")) for a in ins))
            promo[sig] = promo.get(sig, 0) + 1

        # DT201: host callbacks traced into the step
        if name in _CALLBACK_PRIMS:
            cb = eqn.params.get("callback")
            label = getattr(cb, "__name__", None) or str(cb or name)
            callbacks[(name, label)] = callbacks.get((name, label), 0) + 1

        # DT203: output bytes dwarf operand bytes
        in_bytes = sum(_aval_bytes(a) for a in ins if a is not None)
        out_bytes = sum(_aval_bytes(a) for a in outs if a is not None)
        if (out_bytes >= blowup_floor_bytes
                and out_bytes >= blowup_ratio * max(in_bytes, 1)):
            shape = tuple(getattr(outs[0], "shape", ()))
            key = (name, shape)
            row = blowups.setdefault(key, {"count": 0, "in": in_bytes,
                                           "out": out_bytes})
            row["count"] += 1

        # DT204: gather/scatter whose indices operand is a traced value
        if name == "gather" or name.startswith("scatter"):
            from jax.extend import core  # noqa: PLC0415

            idx = eqn.invars[1] if len(eqn.invars) > 1 else None
            traced = (idx is not None and not isinstance(idx, core.Literal)
                      and idx not in const_derived)
            if traced:
                shape = tuple(getattr(getattr(idx, "aval", None), "shape", ()))
                dynamic_idx[(name, shape)] = dynamic_idx.get(
                    (name, shape), 0) + 1

    for (name, in_dtypes), count in sorted(promo.items()):
        findings.append(get_rule("DT200").finding(
            f"{name} produces strong float64 from operands "
            f"({', '.join(in_dtypes)}) — {count} occurrence(s) in the "
            "traced step", file=source, context=name))
    for (name, label), count in sorted(callbacks.items()):
        findings.append(get_rule("DT201").finding(
            f"{name} ({label}) traced into the step function, "
            f"{count} occurrence(s): every execution round-trips to the "
            "Python host", file=source, context=name))
    for (name, shape), row in sorted(blowups.items()):
        findings.append(get_rule("DT203").finding(
            f"{name} materializes {_fmt_bytes(row['out'])} "
            f"(shape {list(shape)}) from {_fmt_bytes(row['in'])} of "
            f"operands ({row['count']} occurrence(s)) — "
            f">{blowup_ratio:.0f}x blow-up", file=source, context=name))
    for (name, shape), count in sorted(dynamic_idx.items()):
        findings.append(get_rule("DT204").finding(
            f"{name} with traced indices (shape {list(shape)}), "
            f"{count} occurrence(s): dynamic addressing defeats TPU "
            "vectorization", file=source, context=name))

    if cost is None:
        cost = jaxpr_cost(closed_jaxpr)
    rl = cost["roofline"]
    ai = cost["arithmetic_intensity"]
    if cost["flops"] and ai < rl["ridge_flops_per_byte"]:
        findings.append(get_rule("DT206").finding(
            f"arithmetic intensity {ai:.2f} FLOPs/byte is below the "
            f"roofline ridge {rl['ridge_flops_per_byte']:.1f} "
            f"({rl['peak_flops']:.3g} FLOP/s / {rl['hbm_gbps']:.0f} GB/s): "
            "the step is projected memory-bound "
            f"(predicted {rl['predicted_step_seconds']:.3g}s/step)",
            file=source, context="roofline"))
    col = cost["collectives"]
    if col["count"]:
        # census rows carry mesh-axis labels, so the message (and the
        # machine-readable census) key exactly like the measured post-SPMD
        # census: (kind, axes) -> count/bytes
        rows = col.get("census") or [
            {"kind": n, "axes": r.get("axes", []), "count": r["count"]}
            for n, r in sorted(col["by_primitive"].items())]
        parts = ", ".join(
            f"{r['kind']}[{','.join(r['axes']) or '?'}]×{r['count']}"
            for r in rows)
        findings.append(get_rule("DT207").finding(
            f"{col['count']} collective eqn(s) per optimizer step ({parts}), "
            f"~{_fmt_bytes(col['bytes'])} moved per step",
            file=source, context="collectives"))
    return findings


# ---------------------------------------------------------- donation audit
def _flat_avals(tree) -> List[Tuple[Tuple[int, ...], str]]:
    import jax  # noqa: PLC0415

    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            out.append((tuple(leaf.shape), str(leaf.dtype)))
    return out


def _match_donations(donated: Sequence[Tuple], outputs: Sequence[Tuple]):
    """Replay jax's donation matching: each donated input aliases at most
    one remaining output of identical (shape, dtype). Returns the donated
    avals that find no match — the ones the compiler silently drops."""
    pool: dict = {}
    for o in outputs:
        pool[o] = pool.get(o, 0) + 1
    dropped = []
    for d in donated:
        if pool.get(d, 0) > 0:
            pool[d] -= 1
        else:
            dropped.append(d)
    return dropped


def audit_donation(fn, args, donate_argnums: Sequence[int] = (), *,
                   source: str = IR_SOURCE,
                   context: str = "donation") -> List[Finding]:
    """DT202: would the donations requested for ``fn`` survive compilation?

    Pure tracing (``jax.make_jaxpr`` over arrays or ShapeDtypeStruct
    shells — nothing compiles or dispatches): a donated argument whose
    (shape, dtype) matches no remaining output cannot be aliased, and XLA
    drops the donation with only a UserWarning — params stay
    double-buffered. ``fn`` may be jitted (the unjitted ``__wrapped__`` is
    traced so passthrough outputs aren't elided)."""
    import jax  # noqa: PLC0415

    if not donate_argnums:
        return []
    inner = getattr(fn, "__wrapped__", fn)
    closed = jax.make_jaxpr(inner)(*args)
    donated = []
    for i in donate_argnums:
        donated += _flat_avals(args[int(i)])
    outputs = [(tuple(v.aval.shape), str(v.aval.dtype))
               for v in closed.jaxpr.outvars if hasattr(v, "aval")]
    dropped = _match_donations(donated, outputs)
    if not dropped:
        return []
    import numpy as np

    drop_bytes = sum(
        int(np.prod(s, dtype=np.int64)) * np.dtype(d).itemsize
        for s, d in dropped)
    examples = ", ".join(f"{d}{list(s)}" for s, d in dropped[:3])
    more = f" (+{len(dropped) - 3} more)" if len(dropped) > 3 else ""
    return [get_rule("DT202").finding(
        f"{len(dropped)} of {len(donated)} donated buffers match no output "
        f"and will NOT be aliased ({examples}{more}): "
        f"{_fmt_bytes(drop_bytes)} stays double-buffered",
        file=source, context=context)]


# ------------------------------------------------------------ network entry
def _shell_tree(tree, conf_dtype: Optional[str] = None):
    """ShapeDtypeStruct shells of a pytree. With ``conf_dtype`` (and unless
    it is float64 itself), float64 leaves are re-dtyped to the configured
    compute dtype: under an x64-enabled host (the test env) ``init()``
    inflates params to f64, and analyzing THAT trace would drown DT200 in
    findings about the host config rather than the step — the production
    trace (x64 off) is what the analysis models. Mirrors
    ``graph_checks._retype_floats``."""
    import jax  # noqa: PLC0415
    import numpy as np  # noqa: PLC0415

    target = None
    if conf_dtype and conf_dtype != "float64":
        target = np.dtype("float32")

    def one(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            dt = a.dtype
            try:
                if target is not None and np.dtype(dt) == np.dtype("float64"):
                    dt = target
            except TypeError:
                pass  # extended dtypes (PRNG keys)
            return jax.ShapeDtypeStruct(tuple(a.shape), dt)
        return a

    return jax.tree_util.tree_map(one, tree)


def _label_structs(net, batch: int, timesteps_probe: int):
    """ShapeDtypeStruct shells for the labels the train step expects."""
    import jax  # noqa: PLC0415
    import numpy as np  # noqa: PLC0415

    conf = net.conf

    def shape_of(it):
        if getattr(it, "kind", None) == "rnn" and it.timesteps is None:
            return (timesteps_probe, it.size)
        return it.example_shape()

    if hasattr(conf, "vertices"):
        return [jax.ShapeDtypeStruct((batch,) + tuple(shape_of(t)),
                                     np.float32)
                for t in conf.output_types()]
    return jax.ShapeDtypeStruct(
        (batch,) + tuple(shape_of(conf.output_type())), np.float32)


def check_network_ir(net, batch_or_struct=None, *,
                     ignore: Iterable[str] = (),
                     timesteps_probe: Optional[int] = None,
                     layout=None,
                     numerics: bool = True,
                     numerics_input_bound: Optional[float] = None,
                     source: str = IR_SOURCE) -> dict:
    """The DT2xx pass + static cost model over a net's real train step.

    Traces ``net._build_train_step()`` with ``jax.make_jaxpr`` over
    ShapeDtypeStruct shells of params/optimizer state/batch — pure abstract
    interpretation, zero device dispatches (``net.init()`` must already
    have run or will run once here; the analysis itself never executes).

    Returns ``{"findings": [...], "static_cost": {...}}``. The donation
    audit always checks the TPU contract (``donate_argnums=(0, 1, 2)``)
    even on backends where the fit path skips donation.

    ``layout``: a :class:`~deeplearning4j_tpu.parallel.MeshLayout` — adds
    the DT3xx sharding-flow pass (``analysis/shard_flow.py``): the report
    gains a ``"shard_flow"`` block (predicted collective census, per-step
    communication bytes), the DT300-DT305 findings join the list, and the
    roofline's interconnect term (``DL4JTPU_ICI_GBPS``) is fed the
    predicted census so ``predicted_step_seconds`` covers the
    communication-bound regime.

    ``numerics`` (default on): the DT5xx dtype-flow + value-range pass
    (``analysis/numerics.py``) walks the SAME traced jaxpr — one
    ``make_jaxpr``, two walks — seeding input/param/label invars at
    ``numerics_input_bound`` (default ±1e3) and optimizer moments from
    their structural invariants. The report gains a ``"numerics"``
    summary block and the DT500-DT505 findings join the list.
    """
    import jax  # noqa: PLC0415

    from ..telemetry.memory import (  # noqa: PLC0415 - shared struct builder
        DEFAULT_TIMESTEPS_PROBE, _input_structs)

    t_probe = (DEFAULT_TIMESTEPS_PROBE if timesteps_probe is None
               else int(timesteps_probe))
    net.init()
    inputs = _input_structs(net, batch_or_struct, timesteps_probe=t_probe)
    batch = int(inputs[0].shape[0])
    labels = _label_structs(net, batch, t_probe)
    conf_dtype = getattr(net.conf, "dtype", "float32")
    params = _shell_tree(net.params, conf_dtype)
    opt_state = _shell_tree(net.opt_state, conf_dtype)
    state = _shell_tree(net.state, conf_dtype)
    rng = jax.ShapeDtypeStruct(tuple(net._rng.shape), net._rng.dtype)

    step = net._build_train_step()
    inner = getattr(step, "__wrapped__", step)
    is_graph = hasattr(net.conf, "vertices")
    x_arg = inputs if is_graph else inputs[0]
    args = (params, opt_state, state, x_arg, labels, rng, None, None)

    closed = jax.make_jaxpr(inner)(*args)
    cost = jaxpr_cost(closed)
    findings = check_jaxpr_ir(closed, source=source, cost=cost)
    findings += audit_donation(inner, args, donate_argnums=(0, 1, 2),
                               source=source, context="train_step donation")
    report = {"static_cost": cost}
    if layout is not None:
        from .cost_model import apply_roofline  # noqa: PLC0415
        from .shard_flow import check_network_shard_flow  # noqa: PLC0415

        flow = check_network_shard_flow(
            net, batch_or_struct, layout, timesteps_probe=timesteps_probe,
            source=source)
        findings += flow.pop("findings")
        report["shard_flow"] = flow
        apply_roofline(cost, comm_bytes=cost["collectives"]["bytes"]
                       + flow["comm_bytes_per_step"])
    if numerics:
        from .numerics import (  # noqa: PLC0415
            DEFAULT_INPUT_BOUND, network_numerics)

        bound = (DEFAULT_INPUT_BOUND if numerics_input_bound is None
                 else float(numerics_input_bound))
        block = network_numerics(net, closed, args, source=source,
                                 input_bound=bound)
        findings += block["findings"]
        report["numerics"] = block["summary"]
    ignore = frozenset(ignore)
    findings = [f for f in findings if f.rule_id not in ignore]
    report["findings"] = merge_findings(findings)
    return report


def analyze_config_ir(conf, *, batch: int = 4,
                      timesteps_probe: Optional[int] = None,
                      source: str = IR_SOURCE, layout=None,
                      numerics: bool = False,
                      ignore: Iterable[str] = ()) -> Tuple[List[Finding], dict]:
    """Headless DT2xx entry for a config (the CLI ``--ir`` path): builds the
    matching network class, initializes it, and runs
    :func:`check_network_ir`. Returns ``(findings, static_cost)`` — with
    ``layout`` (e.g. the CLI ``--mesh`` flag's abstract MeshLayout) the
    static_cost carries the DT3xx ``shard_flow`` census block too.
    ``numerics=True`` (the CLI ``--ir --numerics`` composition) adds the
    DT5xx pass over the same trace and a ``"numerics"`` cost block —
    default off so the ``ir``/``numerics`` flags stay independent."""
    if hasattr(conf, "vertices"):
        from ..nn.graph import ComputationGraph  # noqa: PLC0415

        net = ComputationGraph(conf)
    else:
        from ..nn.multilayer import MultiLayerNetwork  # noqa: PLC0415

        net = MultiLayerNetwork(conf)
    report = check_network_ir(net, batch, timesteps_probe=timesteps_probe,
                              source=source, ignore=ignore, layout=layout,
                              numerics=numerics)
    cost = report["static_cost"]
    if "shard_flow" in report or "numerics" in report:
        cost = dict(cost)
    if "shard_flow" in report:
        cost["shard_flow"] = {
            k: v for k, v in report["shard_flow"].items()
            if k in ("census", "comm_bytes_per_step", "layout")}
    if "numerics" in report:
        cost["numerics"] = report["numerics"]
    return report["findings"], cost


# ------------------------------------------------------------ padding waste
def check_padding_waste(stats: Optional[dict], *,
                        threshold: float = DT205_THRESHOLD,
                        source: str = "<BucketedStager>") -> List[Finding]:
    """DT205: compare the stager's pow2 bucket shapes against the real batch
    statistics it accumulated over an epoch; flag when more than
    ``threshold`` of the staged elements (hence FLOPs) were padding."""
    if not stats or not stats.get("windows"):
        return []
    frac = float(stats.get("padding_fraction", 0.0))
    if frac <= threshold:
        return []
    return [get_rule("DT205").finding(
        f"{frac:.0%} of staged elements were padding this epoch "
        f"({stats['windows']} window(s), {stats['batches']} batch(es), "
        f"{_fmt_bytes(stats.get('staged_bytes', 0))} staged for "
        f"{_fmt_bytes(stats.get('real_bytes', 0))} of real data) — "
        f"above the {threshold:.0%} threshold",
        file=source, context="padding")]


# ----------------------------------------------------------- observability
def ir_findings_family(registry):
    """The single owning declaration of ``dl4jtpu_ir_findings_total`` —
    :func:`record_findings` and the compile manager both draw the family
    from here so the schema (labels, help text) cannot drift (DT406)."""
    return registry.counter(
        "dl4jtpu_ir_findings_total",
        "IR-lint (DT2xx) findings from admission/preflight/epoch scans",
        labelnames=("rule",))


def record_findings(findings: Sequence[Finding], *, registry=None,
                    flight=None) -> None:
    """Route IR findings into telemetry: one
    ``dl4jtpu_ir_findings_total{rule}`` increment and one flight-recorder
    ``ir_finding`` event per finding. ``registry=False`` skips the counter
    (for callers that already own the metric family). Never raises —
    observability must not break the path that produced the findings."""
    if not findings:
        return
    if registry is not False:
        try:
            if registry is None:
                from ..telemetry import get_registry  # noqa: PLC0415

                registry = get_registry()
            fam = ir_findings_family(registry)
            for f in findings:
                fam.labels(rule=f.rule_id).inc()
        except Exception:
            pass
    try:
        if flight is None:
            from ..telemetry.flight_recorder import get_flight_recorder  # noqa: PLC0415

            flight = get_flight_recorder()
        for f in findings:
            flight.record("ir_finding", rule=f.rule_id, severity=f.severity,
                          context=f.context, message=f.message[:300])
    except Exception:
        pass


# ------------------------------------------------------ compile admission
def admission_check(jitted, compiled, args, *, kind: str = "aot") -> Tuple[
        List[Finding], dict]:
    """IR lint + cost model for an executable the compile manager is about
    to admit. ``jitted`` is the jit-wrapped callable (re-traced host-side —
    the XLA compile it just paid dwarfs this), ``compiled`` the AOT
    executable (its ``memory_analysis`` corroborates the donation audit).
    Returns ``(findings, static_cost)``."""
    import jax  # noqa: PLC0415

    closed = jax.make_jaxpr(jitted)(*args)
    cost = jaxpr_cost(closed)
    source = f"<ir:{kind}>"
    findings = check_jaxpr_ir(closed, source=source, cost=cost)

    # DT3xx sharding-flow at admission: when the program is compiled with
    # mesh-sharded arguments, propagate those ACTUAL shardings through the
    # jaxpr and predict the collective census before lower() runs. Invars
    # are spec-indistinguishable here (a ZeRO param shard and a batch shard
    # both read P('fsdp')), so invar gathers are treated as the documented
    # param cost and never fire DT300/DT303 — net.analyze_ir(layout=...)
    # is the precise entry. A failing sub-analysis never breaks
    # compilation, but it is reported: its error lands under
    # cost["analysis_errors"][stage], which the compile manager counts.
    def failed(stage: str, exc: Exception) -> None:
        cost.setdefault("analysis_errors", {})[stage] = (
            f"{type(exc).__name__}: {exc}"[:300])

    try:
        flat, _ = jax.tree_util.tree_flatten(args)
        mesh = None
        specs = []
        flags = []
        for leaf in flat:
            sh = getattr(leaf, "sharding", None)
            if type(sh).__name__ == "NamedSharding" \
                    and sh.mesh.devices.size > 1:
                mesh = mesh or sh.mesh
                specs.append(sh.spec)
                flags.append(True)
            else:
                specs.append(None)
                flags.append(False)
        if mesh is not None:
            from ..parallel.layout import MeshLayout  # noqa: PLC0415
            from .cost_model import apply_roofline  # noqa: PLC0415
            from .shard_flow import (  # noqa: PLC0415
                flow_report, propagate_jaxpr, shard_findings)

            tp = ("tp" if "tp" in mesh.shape and mesh.shape["tp"] > 1
                  else None)
            layout = MeshLayout.from_mesh(mesh, model_axis=tp)
            flow = propagate_jaxpr(closed, specs, layout, param_flags=flags)
            findings += shard_findings(flow, source=source)
            cost["shard_flow"] = flow_report(flow)
            apply_roofline(
                cost, comm_bytes=cost["collectives"]["bytes"]
                + cost["shard_flow"]["comm_bytes_per_step"])
    except Exception as e:
        failed("shard_flow", e)

    # DT5xx numerics at admission: same jaxpr, one extra host-side walk.
    # No declared ranges/policy are available for an arbitrary executable,
    # so invars stay unknown — hazard rules only fire on evidence the
    # trace itself provides (literal clamps, structural softmax shape,
    # low-precision accumulation dtypes); net.analyze_ir is the seeded,
    # policy-aware entry. Failures are reported like the DT3xx block's.
    try:
        from .numerics import check_jaxpr_numerics  # noqa: PLC0415

        num_findings, num_summary = check_jaxpr_numerics(
            closed, source=source)
        findings += num_findings
        cost["numerics"] = num_summary
    except Exception as e:
        failed("numerics", e)

    # DT202 at admission: the jit eqn records the donation actually
    # requested; a requested donation with ZERO aliased bytes in the
    # compiler's own memory analysis was dropped wholesale
    try:
        eqn = closed.jaxpr.eqns[0] if closed.jaxpr.eqns else None
        donated_invars = (eqn.params.get("donated_invars", ())
                          if eqn is not None and eqn.primitive.name == "jit"
                          else ())
        n_donated = sum(1 for d in donated_invars if d)
        if n_donated:
            ma = None
            try:
                ma = compiled.memory_analysis()
            except Exception:
                ma = None
            alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0) \
                if ma is not None else None
            if alias == 0:
                findings.append(get_rule("DT202").finding(
                    f"{n_donated} donated buffer(s) requested but the "
                    "compiled executable aliases 0 bytes: donation was "
                    "dropped — params/optimizer state are double-buffered",
                    file=source, context=kind))
    except Exception as e:
        failed("donation_audit", e)
    return merge_findings(findings), cost
