"""DT5xx numerics pass: dtype-flow + value-range abstract interpretation.

Pass 6 of the analysis stack. The DT2xx tier reads the traced train step
for *structural* problems (f64 promotion, dropped donation); this tier
reads the same jaxpr for *numerical* ones, before a single step runs —
predicting at trace/admission time what the runtime Watchdog can only
observe at step N:

- **Dtype-flow** tracks the effective accumulation precision of every
  value: DT500 (dot/conv/reduce accumulating in bf16/f16 without an f32
  ``preferred_element_type``), DT501 (low-precision scan/while carry
  rewritten across >= ``carry_steps`` iterations — the LSTM/streaming
  drift shape) and DT502 (grads or optimizer moments combined below the
  declared PrecisionPolicy compute dtype at an update site).
- **Value-range** interval abstract interpretation seeds invars from
  declared input ranges / initializer bounds and propagates ``[lo, hi]``
  per eqn: DT503 (exp/log/div/sqrt/rsqrt whose input interval admits
  overflow, log(<=0) or divide-through-zero without a clamp), DT504
  (softmax-shaped exp not dominated by a subtract-max — structural) and
  DT505 (advisory: sub-f32 grad flow with no loss scaling configured).

Soundness polarity: an *unknown* bound is ``+/-inf`` and never fires —
hazard rules need evidence, which either a declared seed range or a
traced clamp/literal provides. ``jnp.clip(x, 0, 1)`` therefore makes a
downstream ``log`` fire (zero is admitted) while ``jnp.clip(x, EPS, 1)``
silences it: the clamp IS the guard the hint asks for. The structural
DT504 check needs no intervals at all, so a naive softmax over unknown
logits is still caught.

The walker rides the same traced ``ClosedJaxpr`` the DT2xx pass already
built (``check_network_ir(numerics=True)`` — one ``make_jaxpr``, two
walks), recurses through scan/while/cond/pjit/custom-wrapper eqns like
``shard_flow``, and runs loop bodies to a small widening fixpoint before
the recording pass so carried intervals are sound across iterations.
Findings carry no source line (they describe traced programs), so
suppression is ``ignore=(...)`` / ``--ignore``, as with DT2xx/DT3xx.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .findings import Finding, merge_findings
from .rules import get_rule

NUM_SOURCE = "<numerics>"

# Accumulating >= this many elements in bf16/f16 before DT500 fires on a
# reduce (a handful of terms round once; hundreds stop accumulating).
DT500_MIN_REDUCE = 32
# Carries rewritten across >= this many iterations before DT501 fires.
DT501_MIN_STEPS = 8
# Default declared magnitude bound for network inputs/labels/params when
# the caller does not pass one — wide enough to catch unguarded exp/log,
# finite so the interval domain stays informative.
DEFAULT_INPUT_BOUND = 1e3

_LOW = ("bfloat16", "float16")
_INF = math.inf

# log(finfo(dtype).max): an exp argument above this overflows to inf.
_EXP_MAX = {"float64": 709.78, "float32": 88.72, "bfloat16": 88.5,
            "float16": 11.09}

__all__ = [
    "NUM_SOURCE", "DT500_MIN_REDUCE", "DT501_MIN_STEPS",
    "DEFAULT_INPUT_BOUND", "check_jaxpr_numerics", "network_numerics",
    "check_network_numerics", "analyze_config_numerics",
]


# ------------------------------------------------------------- intervals
def _san(lo: float, hi: float) -> Tuple[float, float]:
    if math.isnan(lo):
        lo = -_INF
    if math.isnan(hi):
        hi = _INF
    return (lo, hi) if lo <= hi else (-_INF, _INF)


def _mulc(a: float, b: float) -> float:
    # corner product with the interval convention 0 * inf = 0
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


def _iv_add(x, y):
    return _san(x[0] + y[0], x[1] + y[1])


def _iv_neg(x):
    return (-x[1], -x[0])


def _iv_mul(x, y):
    c = (_mulc(x[0], y[0]), _mulc(x[0], y[1]),
         _mulc(x[1], y[0]), _mulc(x[1], y[1]))
    return _san(min(c), max(c))


def _iv_div(x, y):
    if y[0] > 0.0 or y[1] < 0.0:  # divisor bounded away from zero
        c = []
        for a in x:
            for b in y:
                c.append(a / b if not (math.isinf(a) and math.isinf(b))
                         else 0.0)
        lo, hi = min(c), max(c)
        if math.isinf(x[0]) or math.isinf(x[1]):
            lo, hi = -_INF, _INF
        return _san(lo, hi)
    return (-_INF, _INF)


def _iv_union(x, y):
    return (min(x[0], y[0]), max(x[1], y[1]))


def _iv_max(x, y):
    return (max(x[0], y[0]), max(x[1], y[1]))


def _iv_min(x, y):
    return (min(x[0], y[0]), min(x[1], y[1]))


def _exp_b(v: float) -> float:
    if v >= 700.0:
        return _INF
    if v == -_INF:
        return 0.0
    return math.exp(v)


def _log_b(v: float) -> float:
    if v <= 0.0:
        return -_INF
    if v == _INF:
        return _INF
    return math.log(v)


# -------------------------------------------------------- abstract value
class _Av:
    """Abstract value for one jaxpr var: interval + structural flags.

    ``vid`` is a canonical value identity propagated through
    value-preserving ops (convert/broadcast/reshape/stop_gradient/...),
    so ``sub(x, broadcast(reduce_max(x)))`` is recognizable as a
    subtract-max regardless of the plumbing between.
    """

    __slots__ = ("lo", "hi", "vid", "maxof", "shifted", "is_exp",
                 "sumexp_of", "lineage")

    def __init__(self, lo=-_INF, hi=_INF, vid=None, maxof=frozenset(),
                 shifted=None, is_exp=None, sumexp_of=None,
                 lineage=frozenset()):
        self.lo, self.hi = lo, hi
        self.vid = vid
        self.maxof = maxof          # vids this value is a reduce_max of
        self.shifted = shifted      # vid x when value == x - max(x)
        self.is_exp = is_exp        # None | True (stable) | False
        self.sumexp_of = sumexp_of  # vid of the exp var this sums
        self.lineage = lineage      # subset of {"param", "opt"}

    def iv(self):
        return (self.lo, self.hi)


def _dtype_str(v) -> str:
    try:
        return str(v.aval.dtype)
    except Exception:
        return ""


def _is_float(dt: str) -> bool:
    return dt.startswith("float") or dt in _LOW


def _aval_size(v) -> int:
    try:
        n = 1
        for d in v.aval.shape:
            n *= int(d)
        return n
    except Exception:
        return 1


# value-preserving primitives: interval, identity and flags pass through
_IDENT = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev", "copy",
    "convert_element_type", "stop_gradient", "reduce_precision",
    "device_put", "expand_dims", "with_sharding_constraint",
    "sharding_constraint", "optimization_barrier",
}
# interval-preserving but identity-erasing (element subset / reorder)
_SUBSET = {"slice", "dynamic_slice", "gather", "sort", "top_k"}
# DT502 update-site arithmetic
_ARITH = {"add", "add_any", "sub", "mul", "div"}

_BOUNDED = {"tanh": (-1.0, 1.0), "logistic": (0.0, 1.0),
            "erf": (-1.0, 1.0), "sin": (-1.0, 1.0), "cos": (-1.0, 1.0),
            "sign": (-1.0, 1.0), "is_finite": (0.0, 1.0)}
_CMP = {"eq", "ne", "lt", "le", "gt", "ge", "and", "or", "not", "xor"}


# eqns whose params hold a 1:1 inner jaxpr (same in/out signature).
# NOT a generic "has a jaxpr param" sniff: the generic `reduce` prim
# carries its scalar combinator as params["jaxpr"] with coincidentally
# matching arity and must be evaluated as a reduction, not inlined.
_WRAPPERS = {
    "jit", "closed_call", "core_closed_call", "xla_call", "remat",
    "remat2", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
}


def _wrapped_closed(eqn):
    """The 1:1-wrapped inner jaxpr of a pjit/remat/custom_*-style eqn."""
    from jax.extend import core  # noqa: PLC0415

    if eqn.primitive.name not in _WRAPPERS:
        return None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        inner = eqn.params.get(key)
        if inner is None:
            continue
        if isinstance(inner, core.Jaxpr):
            if inner.constvars:
                return None
            inner = core.ClosedJaxpr(inner, ())
        if isinstance(inner, core.ClosedJaxpr) \
                and len(inner.jaxpr.invars) == len(eqn.invars) \
                and len(inner.jaxpr.outvars) == len(eqn.outvars):
            return inner
    return None


class _NumFlow:
    """One combined dtype-flow + value-range walk over a closed jaxpr."""

    def __init__(self, *, compute_dtype=None, params_dtype=None,
                 carry_steps=DT501_MIN_STEPS,
                 reduce_elems=DT500_MIN_REDUCE):
        self.compute_dtype = compute_dtype
        self.params_dtype = params_dtype
        self.carry_steps = int(carry_steps)
        self.reduce_elems = int(reduce_elems)
        self._next_vid = 0
        self.record = True
        self.eqns = 0
        # (rule_id, agg_key) -> [count, first_message]
        self.agg: Dict[Tuple[str, str], list] = {}
        # vid -> (agg_key, message) for unstable exps that may later be
        # reclassified from DT503-overflow to DT504 by a softmax shape
        self.pending_exp: Dict[int, Tuple[str, str]] = {}

    # ------------------------------------------------------------ helpers
    def fresh(self, **kw) -> _Av:
        self._next_vid += 1
        return _Av(vid=self._next_vid, **kw)

    def _hit(self, rule_id: str, key: str, message: str) -> None:
        if not self.record:
            return
        slot = self.agg.setdefault((rule_id, key), [0, message])
        slot[0] += 1

    def _read(self, env, v) -> _Av:
        from jax.extend import core  # noqa: PLC0415

        if isinstance(v, core.Literal):
            return self._const_av(v.val)
        av = env.get(id(v))
        if av is None:
            av = self.fresh()
            env[id(v)] = av
        return av

    def _const_av(self, val) -> _Av:
        import numpy as np  # noqa: PLC0415

        try:
            arr = np.asarray(val)
            if arr.size and arr.dtype.kind in "fiub" \
                    and arr.size <= 4_000_000:
                return self.fresh(lo=float(arr.min()), hi=float(arr.max()))
        except Exception:
            pass
        return self.fresh()

    # --------------------------------------------------------------- walk
    def walk(self, closed, in_avs: Sequence[_Av]) -> List[_Av]:
        consts = [self._const_av(c) for c in closed.consts]
        return self._jaxpr(closed.jaxpr, consts, list(in_avs))

    def _jaxpr(self, jaxpr, const_avs, in_avs) -> List[_Av]:
        env: Dict[int, _Av] = {}
        for v, av in zip(jaxpr.constvars, const_avs):
            env[id(v)] = av
        for v, av in zip(jaxpr.invars, in_avs):
            env[id(v)] = av
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _eqn(self, eqn, env) -> None:
        name = eqn.primitive.name
        if name == "scan":
            self._scan(eqn, env)
            return
        if name == "while":
            self._while(eqn, env)
            return
        if name == "cond":
            self._cond(eqn, env)
            return
        if name.startswith("pallas_call"):
            # kernel bodies operate on Refs — opaque to this walker; the
            # shipped kernels carry their own >=f32 subtract-max contract
            for v in eqn.outvars:
                env[id(v)] = self.fresh()
            return
        inner = _wrapped_closed(eqn)
        if inner is not None:
            in_avs = [self._read(env, v) for v in eqn.invars]
            outs = self.walk(inner, in_avs)
            for v, av in zip(eqn.outvars, outs):
                env[id(v)] = av
            return
        if self.record:
            self.eqns += 1
        self._prim(eqn, env, name)

    # ----------------------------------------------------- primitive eval
    def _prim(self, eqn, env, name) -> None:
        ins = [self._read(env, v) for v in eqn.invars]
        out_dt = _dtype_str(eqn.outvars[0]) if eqn.outvars else ""
        lineage = frozenset().union(*(a.lineage for a in ins)) \
            if ins else frozenset()
        av = None

        if name in _IDENT and ins:
            a = ins[0]
            av = _Av(lo=a.lo, hi=a.hi, vid=a.vid, maxof=a.maxof,
                     shifted=a.shifted, is_exp=a.is_exp,
                     sumexp_of=a.sumexp_of, lineage=a.lineage)
        elif name in _SUBSET and ins:
            a = ins[0]
            av = self.fresh(lo=a.lo, hi=a.hi, lineage=a.lineage)
        elif name in _CMP:
            av = self.fresh(lo=0.0, hi=1.0, lineage=lineage)
        elif name in _BOUNDED:
            lo, hi = _BOUNDED[name]
            av = self.fresh(lo=lo, hi=hi, lineage=lineage)
        elif name in ("add", "add_any"):
            iv = _iv_add(ins[0].iv(), ins[1].iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "sub":
            a, b = ins
            iv = _iv_add(a.iv(), _iv_neg(b.iv()))
            shifted = a.vid if (a.vid is not None and a.vid in b.maxof) \
                else None
            hi = min(iv[1], 0.0) if shifted is not None else iv[1]
            av = self.fresh(lo=iv[0], hi=hi, shifted=shifted,
                            lineage=lineage)
        elif name == "mul":
            a, b = ins
            if a.vid is not None and a.vid == b.vid:  # x*x >= 0
                m = max(abs(a.lo), abs(a.hi))
                av = self.fresh(lo=0.0, hi=_mulc(m, m), lineage=lineage)
            else:
                iv = _iv_mul(a.iv(), b.iv())
                av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "div":
            a, b = ins
            self._div_hazard(a, b, out_dt, eqn)
            iv = _iv_div(a.iv(), b.iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "neg":
            iv = _iv_neg(ins[0].iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "abs":
            a = ins[0]
            lo = 0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi))
            av = self.fresh(lo=lo, hi=max(abs(a.lo), abs(a.hi)),
                            lineage=lineage)
        elif name == "max":
            a, b = ins
            # max(x, -inf) == x: jnp.max inserts this wrapper around
            # reduce_max — pass identity/flags through or the stable-
            # softmax maxof chain breaks at it
            ident = a if b.lo == b.hi == -_INF else \
                (b if a.lo == a.hi == -_INF else None)
            if ident is not None:
                av = _Av(lo=ident.lo, hi=ident.hi, vid=ident.vid,
                         maxof=ident.maxof, shifted=ident.shifted,
                         is_exp=ident.is_exp, sumexp_of=ident.sumexp_of,
                         lineage=ident.lineage)
            else:
                iv = _iv_max(a.iv(), b.iv())
                av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "min":
            a, b = ins
            ident = a if b.lo == b.hi == _INF else \
                (b if a.lo == a.hi == _INF else None)
            if ident is not None:
                av = _Av(lo=ident.lo, hi=ident.hi, vid=ident.vid,
                         maxof=ident.maxof, shifted=ident.shifted,
                         is_exp=ident.is_exp, sumexp_of=ident.sumexp_of,
                         lineage=ident.lineage)
            else:
                iv = _iv_min(a.iv(), b.iv())
                av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "clamp":  # clamp(lo_b, x, hi_b) = min(max(x, lo), hi)
            lo_b, x, hi_b = ins
            iv = _iv_min(_iv_max(x.iv(), lo_b.iv()), hi_b.iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "exp":
            av = self._exp(ins[0], out_dt, lineage)
        elif name == "expm1":
            base = self._exp(ins[0], out_dt, lineage)
            av = self.fresh(lo=base.lo - 1.0, hi=base.hi - 1.0,
                            is_exp=base.is_exp, lineage=lineage)
            if base.is_exp is False and base.vid in self.pending_exp:
                self.pending_exp[av.vid] = self.pending_exp.pop(base.vid)
        elif name in ("log", "log1p"):
            a = ins[0]
            off = 0.0 if name == "log" else 1.0
            floor = 0.0 if name == "log" else -1.0
            if self.record and a.lo <= floor and a.lo > -_INF:
                self._hit("DT503", f"{name}-domain",
                          f"{name} input interval [{a.lo:.3g}, {a.hi:.3g}] "
                          f"admits {name}(<= {floor:g}) -> -inf/NaN with no "
                          "clamp in between")
            av = self.fresh(lo=_log_b(a.lo + off), hi=_log_b(a.hi + off),
                            lineage=lineage)
        elif name == "sqrt":
            a = ins[0]
            if self.record and a.lo < 0.0 and a.lo > -_INF:
                self._hit("DT503", "sqrt-domain",
                          f"sqrt input interval [{a.lo:.3g}, {a.hi:.3g}] "
                          "admits a negative -> NaN with no clamp in "
                          "between")
            av = self.fresh(lo=math.sqrt(max(a.lo, 0.0)),
                            hi=math.sqrt(a.hi) if a.hi not in (_INF,)
                            else _INF, lineage=lineage)
        elif name == "rsqrt":
            a = ins[0]
            if self.record and a.lo <= 0.0 and a.lo > -_INF:
                self._hit("DT503", "rsqrt-domain",
                          f"rsqrt input interval [{a.lo:.3g}, {a.hi:.3g}] "
                          "admits <= 0 -> inf/NaN with no clamp in between")
            if a.lo > 0.0:
                av = self.fresh(lo=1.0 / math.sqrt(a.hi)
                                if a.hi != _INF else 0.0,
                                hi=1.0 / math.sqrt(a.lo), lineage=lineage)
            else:
                av = self.fresh(lineage=lineage)
        elif name == "integer_pow":
            y = int(eqn.params.get("y", 1))
            a = ins[0]
            if y >= 0 and y % 2 == 0:
                m = max(abs(a.lo), abs(a.hi))
                av = self.fresh(lo=0.0, hi=_mulc(m, m) if y == 2
                                else (m ** y if m != _INF else _INF),
                                lineage=lineage)
            elif y >= 0:
                av = self.fresh(lo=a.lo ** y if a.lo != -_INF else -_INF,
                                hi=a.hi ** y if a.hi != _INF else _INF,
                                lineage=lineage)
            else:
                if self.record and a.lo <= 0.0 <= a.hi \
                        and a.lo > -_INF and _is_float(out_dt):
                    self._hit("DT503", "pow-domain",
                              f"x**{y} base interval [{a.lo:.3g}, "
                              f"{a.hi:.3g}] admits 0 -> divide-through-"
                              "zero with no clamp in between")
                av = self.fresh(lineage=lineage)
        elif name == "pow":
            a, b = ins
            av = self._pow(a, b, lineage)
        elif name == "iota":
            n = _aval_size(eqn.outvars[0])
            av = self.fresh(lo=0.0, hi=float(max(n - 1, 0)))
        elif name == "select_n":
            iv = ins[1].iv() if len(ins) > 1 else (-_INF, _INF)
            for c in ins[2:]:
                iv = _iv_union(iv, c.iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name in ("concatenate", "dynamic_update_slice", "pad",
                      "scatter", "scatter-add", "scatter_add"):
            iv = ins[0].iv()
            for c in ins[1:]:
                if _is_float(_dtype_str(eqn.outvars[0])) or True:
                    iv = _iv_union(iv, c.iv())
            av = self.fresh(lo=iv[0], hi=iv[1], lineage=lineage)
        elif name == "reduce_max":
            a = ins[0]
            av = self.fresh(lo=a.lo, hi=a.hi,
                            maxof=frozenset({a.vid}) | a.maxof,
                            lineage=lineage)
        elif name == "reduce_min":
            a = ins[0]
            av = self.fresh(lo=a.lo, hi=a.hi, lineage=lineage)
        elif name in ("reduce_sum", "cumsum", "reduce_window_sum"):
            av = self._reduce_sum(eqn, ins[0], name, out_dt, lineage)
        elif name == "reduce_prod":
            av = self.fresh(lineage=lineage)
        elif name == "reduce":
            # generic lax.reduce: fire DT500 only for an add combinator
            # (a sum accumulating at operand precision); other monoids
            # (max/min/or) don't compound rounding per element
            k = 1
            try:
                shape = eqn.invars[0].aval.shape
                for d in eqn.params.get("dimensions", ()):
                    k *= int(shape[d])
            except Exception:
                k = 1
            body = eqn.params.get("jaxpr")
            body = getattr(body, "jaxpr", body)
            is_add = (body is not None and len(body.eqns) == 1
                      and body.eqns[0].primitive.name in ("add", "add_any"))
            if self.record and is_add and out_dt in _LOW \
                    and k >= self.reduce_elems:
                self._hit("DT500", f"reduce:{out_dt}",
                          f"lax.reduce(add) accumulates {k} element(s) "
                          f"in {out_dt} — the running sum rounds at "
                          "every add")
            av = self.fresh(lineage=lineage)
        elif name in ("argmax", "argmin"):
            av = self.fresh(lo=0.0, hi=float(max(_aval_size(eqn.invars[0])
                                                 - 1, 0)))
        elif name == "dot_general":
            av = self._dot(eqn, ins, out_dt, lineage)
        elif name == "conv_general_dilated":
            av = self._conv(eqn, ins, out_dt, lineage)
        elif name in ("threefry2x32", "random_bits"):
            av = self.fresh(lo=0.0, hi=4.3e9)
        else:
            av = self.fresh(lineage=lineage)

        # DT502: update-site arithmetic below the declared compute dtype
        if name in _ARITH and self.record \
                and self.compute_dtype == "float32" and out_dt in _LOW \
                and (lineage & {"param", "opt"}):
            kind = "optimizer moments" if "opt" in lineage else "parameters"
            self._hit("DT502", f"{name}:{out_dt}",
                      f"{kind} combined by `{name}` in {out_dt} while the "
                      "declared PrecisionPolicy compute dtype is float32 "
                      "— the optimizer update runs below the compute "
                      "contract")

        for v in eqn.outvars:
            env[id(v)] = av if av is not None else self.fresh()
        if len(eqn.outvars) > 1 and av is not None:
            # independent identities for secondary outputs
            for v in eqn.outvars[1:]:
                env[id(v)] = self.fresh(lo=av.lo, hi=av.hi,
                                        lineage=av.lineage)

    # --------------------------------------------------- hazard sub-evals
    def _exp(self, a: _Av, out_dt: str, lineage) -> _Av:
        cap = _EXP_MAX.get(out_dt, 88.72)
        stable = a.shifted is not None or a.hi <= cap
        if a.shifted is not None:
            av = self.fresh(lo=0.0, hi=min(_exp_b(a.hi), 1.0),
                            is_exp=True, lineage=lineage)
        else:
            av = self.fresh(lo=_exp_b(a.lo), hi=_exp_b(a.hi),
                            is_exp=stable, lineage=lineage)
        if not stable and self.record:
            # deferred: a later softmax shape upgrades this to DT504
            overflow = a.hi > cap and a.hi < _INF
            msg = (f"exp input interval [{a.lo:.3g}, {a.hi:.3g}] exceeds "
                   f"log({out_dt or 'float32'}_max)~{cap:.4g} -> overflow "
                   "to inf with no clamp or subtract-max in between")
            self.pending_exp[av.vid] = ("exp-overflow", msg if overflow
                                        else "")
        return av

    def _pow(self, a: _Av, b: _Av, lineage) -> _Av:
        if self.record and b.hi < 0.0 and a.lo <= 0.0 <= a.hi \
                and a.lo > -_INF:
            self._hit("DT503", "pow-domain",
                      f"pow base interval [{a.lo:.3g}, {a.hi:.3g}] admits "
                      "0 with a negative exponent -> divide-through-zero "
                      "with no clamp in between")
        if 0.0 < a.lo and a.hi < _INF:
            try:
                corners = [a.lo ** b.lo if b.lo > -_INF else
                           (_INF if a.lo < 1.0 else 0.0),
                           a.lo ** b.hi if b.hi < _INF else
                           (0.0 if a.lo < 1.0 else _INF),
                           a.hi ** b.lo if b.lo > -_INF else
                           (_INF if a.hi < 1.0 else 0.0),
                           a.hi ** b.hi if b.hi < _INF else
                           (0.0 if a.hi < 1.0 else _INF)]
                return self.fresh(lo=min(corners), hi=max(corners),
                                  lineage=lineage)
            except OverflowError:
                pass
        return self.fresh(lineage=lineage)

    def _div_hazard(self, a: _Av, b: _Av, out_dt: str, eqn) -> None:
        if not self.record or not _is_float(out_dt):
            return
        # softmax shape: exp(x) normalized by its own sum
        if a.is_exp is not None and b.sumexp_of is not None \
                and b.sumexp_of == a.vid:
            if a.is_exp is False:
                self.pending_exp.pop(a.vid, None)
                self._hit("DT504", "softmax",
                          "softmax-shaped exp(x)/sum(exp(x)) whose "
                          "exponent is not dominated by a subtract-max "
                          "(and not provably bounded) — one hot logit "
                          "overflows the row to inf/inf = NaN")
            return
        if b.lo <= 0.0 <= b.hi and (b.lo > -_INF or b.hi < _INF):
            self._hit("DT503", "div-zero",
                      f"divisor interval [{b.lo:.3g}, {b.hi:.3g}] admits "
                      "zero -> divide-through-zero with no clamp in "
                      "between")

    def _reduce_sum(self, eqn, a: _Av, name: str, out_dt: str,
                    lineage) -> _Av:
        n_in = _aval_size(eqn.invars[0])
        n_out = _aval_size(eqn.outvars[0])
        k = max(n_in // max(n_out, 1), 1)
        if name == "cumsum":
            k = max(n_in // max(n_out, 1), 1) if n_out else 1
            # cumsum preserves shape; accumulation depth is the axis len
            axis = eqn.params.get("axis", 0)
            try:
                k = int(eqn.invars[0].aval.shape[axis])
            except Exception:
                k = 1
        if name == "reduce_window_sum":
            k = 1
            for d in eqn.params.get("window_dimensions", ()):
                k *= int(d)
        if self.record and out_dt in _LOW and k >= self.reduce_elems:
            self._hit("DT500", f"{name}:{out_dt}",
                      f"`{name}` accumulates {k} element(s) in {out_dt} "
                      "— the running sum rounds at every add")
        kf = float(k)
        lo = _mulc(kf, a.lo) if a.lo < 0.0 else min(a.lo, _mulc(kf, a.lo))
        hi = _mulc(kf, a.hi) if a.hi > 0.0 else max(a.hi, _mulc(kf, a.hi))
        sumexp = a.vid if a.is_exp is not None else None
        if a.is_exp is True:
            # the max element contributes exp(0) = 1 to a stable-softmax
            # row sum: log/div of this sum is safe by construction
            lo = max(lo, 1.0)
        return self.fresh(lo=lo, hi=hi, sumexp_of=sumexp, lineage=lineage)

    def _dot(self, eqn, ins, out_dt: str, lineage) -> _Av:
        a, b = ins[0], ins[1]
        dims = eqn.params.get("dimension_numbers")
        k = 1
        try:
            (lc, _rc), _ = dims
            shape = eqn.invars[0].aval.shape
            for d in lc:
                k *= int(shape[d])
        except Exception:
            k = 1
        pref = eqn.params.get("preferred_element_type")
        pref_s = str(pref) if pref is not None else None
        in_dts = [_dtype_str(v) for v in eqn.invars[:2]]
        if self.record and all(dt in _LOW for dt in in_dts) \
                and (pref_s is None or pref_s in _LOW) and out_dt in _LOW:
            self._hit("DT500", f"dot_general:{out_dt}",
                      f"dot_general contracts {k} element(s) with "
                      f"{in_dts[0]} operands and no f32 "
                      "preferred_element_type — the MXU accumulates at "
                      "operand precision")
        m = _mulc(max(abs(a.lo), abs(a.hi)), max(abs(b.lo), abs(b.hi)))
        bound = _mulc(float(k), m)
        return self.fresh(lo=-bound, hi=bound, lineage=lineage)

    def _conv(self, eqn, ins, out_dt: str, lineage) -> _Av:
        a, b = ins[0], ins[1]
        k = 1
        try:
            dn = eqn.params["dimension_numbers"]
            rhs = eqn.invars[1].aval.shape
            k = 1
            for i, d in enumerate(rhs):
                if i != dn.rhs_spec[0]:
                    k *= int(d)
        except Exception:
            k = 1
        pref = eqn.params.get("preferred_element_type")
        pref_s = str(pref) if pref is not None else None
        in_dts = [_dtype_str(v) for v in eqn.invars[:2]]
        if self.record and all(dt in _LOW for dt in in_dts) \
                and (pref_s is None or pref_s in _LOW) and out_dt in _LOW:
            self._hit("DT500", f"conv:{out_dt}",
                      f"conv_general_dilated accumulates {k} element(s) "
                      f"per output in {in_dts[0]} with no f32 "
                      "preferred_element_type")
        m = _mulc(max(abs(a.lo), abs(a.hi)), max(abs(b.lo), abs(b.hi)))
        bound = _mulc(float(k), m)
        return self.fresh(lo=-bound, hi=bound, lineage=lineage)

    # ------------------------------------------------------ control flow
    def _fixpoint(self, run_body, carry: List[_Av]) -> List[_Av]:
        """Two widening passes (silent), returning stabilized carry avs."""
        was = self.record
        self.record = False
        try:
            for _ in range(2):
                outs = run_body(carry)
                changed = False
                nxt = []
                for c, o in zip(carry, outs):
                    lo, hi = c.lo, c.hi
                    if o.lo < lo:
                        lo, changed = -_INF, True
                    if o.hi > hi:
                        hi, changed = _INF, True
                    nxt.append(_Av(lo=lo, hi=hi, vid=c.vid,
                                   lineage=c.lineage | o.lineage))
                carry = nxt
                if not changed:
                    break
        finally:
            self.record = was
        return carry

    def _dt501(self, body_jaxpr, carry_in: List[_Av], carry_vars,
               body_outvars, trip: Optional[int], kind: str) -> None:
        if not self.record:
            return
        from jax.extend import core  # noqa: PLC0415

        if trip is not None and trip < self.carry_steps:
            return
        for i, v in enumerate(carry_vars):
            dt = _dtype_str(v)
            if dt not in _LOW:
                continue
            out_v = body_outvars[i]
            if out_v is v or isinstance(out_v, core.Literal):
                continue  # passthrough carry: no per-step rounding
            if self.params_dtype == dt \
                    and (carry_in[i].lineage & {"param", "opt"}):
                continue  # declared-storage params/moments: sanctioned
            steps = str(trip) if trip is not None else ">=? (while)"
            self._hit("DT501", f"{kind}:{dt}:{i}",
                      f"{kind} carry slot {i} ({dt} "
                      f"{tuple(getattr(v.aval, 'shape', ()))}) is "
                      f"rewritten across {steps} iterations — rounding "
                      "error compounds once per step")

    def _scan(self, eqn, env) -> None:
        closed = eqn.params["jaxpr"]
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        trip = eqn.params.get("length")
        ins = [self._read(env, v) for v in eqn.invars]
        consts, carry, xs = ins[:nc], ins[nc:nc + ncar], ins[nc + ncar:]
        xs_avs = [self.fresh(lo=a.lo, hi=a.hi, lineage=a.lineage)
                  for a in xs]

        def run(c):
            return self.walk(closed, consts + list(c) + xs_avs)[:ncar]

        stable = self._fixpoint(run, list(carry))
        body = closed.jaxpr
        self._dt501(body, stable, body.invars[nc:nc + ncar],
                    body.outvars[:ncar],
                    int(trip) if trip is not None else None, "scan")
        outs = self.walk(closed, consts + stable + xs_avs)
        for i, (v, av) in enumerate(zip(eqn.outvars, outs)):
            if i < ncar:
                joined = _iv_union(stable[i].iv(), av.iv())
                env[id(v)] = self.fresh(lo=joined[0], hi=joined[1],
                                        lineage=av.lineage)
            else:
                env[id(v)] = self.fresh(lo=av.lo, hi=av.hi,
                                        lineage=av.lineage)

    def _while(self, eqn, env) -> None:
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_j = eqn.params["cond_jaxpr"]
        body_j = eqn.params["body_jaxpr"]
        ins = [self._read(env, v) for v in eqn.invars]
        cond_c, body_c = ins[:cn], ins[cn:cn + bn]
        carry = ins[cn + bn:]

        def run(c):
            return self.walk(body_j, body_c + list(c))

        stable = self._fixpoint(run, list(carry))
        was = self.record
        self.record = False
        try:
            self.walk(cond_j, cond_c + stable)
        finally:
            self.record = was
        nbody = len(body_j.jaxpr.invars) - bn
        self._dt501(body_j.jaxpr, stable,
                    body_j.jaxpr.invars[bn:bn + nbody],
                    body_j.jaxpr.outvars, None, "while")
        outs = self.walk(body_j, body_c + stable)
        for v, av, st in zip(eqn.outvars, outs, stable):
            joined = _iv_union(st.iv(), av.iv())
            env[id(v)] = self.fresh(lo=joined[0], hi=joined[1],
                                    lineage=av.lineage)

    def _cond(self, eqn, env) -> None:
        branches = eqn.params["branches"]
        ins = [self._read(env, v) for v in eqn.invars]
        ops = ins[1:]
        outs = None
        for br in branches:
            o = self.walk(br, ops)
            if outs is None:
                outs = o
            else:
                outs = [self.fresh(lo=min(x.lo, y.lo), hi=max(x.hi, y.hi),
                                   lineage=x.lineage | y.lineage)
                        for x, y in zip(outs, o)]
        for v, av in zip(eqn.outvars, outs or []):
            env[id(v)] = av

    # ------------------------------------------------------------ results
    def findings(self, source: str) -> List[Finding]:
        # flush exp candidates no softmax shape reclassified
        for key, msg in self.pending_exp.values():
            if msg:
                slot = self.agg.setdefault(("DT503", key), [0, msg])
                slot[0] += 1
        self.pending_exp.clear()
        out: List[Finding] = []
        for (rid, key), (count, msg) in self.agg.items():
            if count > 1:
                msg = f"{msg} [{count} site(s)]"
            out.append(get_rule(rid).finding(
                msg, file=source, context=f"numerics:{key}"))
        return out

    def summary(self) -> dict:
        rules: Dict[str, int] = {}
        for (rid, _k), (count, _m) in self.agg.items():
            rules[rid] = rules.get(rid, 0) + count
        return {"eqns": self.eqns, "rules": rules}


# ------------------------------------------------------------ public API
def check_jaxpr_numerics(closed, *, source: str = NUM_SOURCE,
                         in_ranges: Optional[Sequence] = None,
                         in_lineage: Optional[Sequence] = None,
                         compute_dtype: Optional[str] = None,
                         params_dtype: Optional[str] = None,
                         carry_steps: int = DT501_MIN_STEPS,
                         reduce_elems: int = DT500_MIN_REDUCE,
                         ignore: Iterable[str] = ()
                         ) -> Tuple[List[Finding], dict]:
    """DT5xx numerics lint over a traced ``ClosedJaxpr``.

    ``in_ranges``: optional per-invar ``(lo, hi)`` seeds (None entries
    stay unknown). ``in_lineage``: optional per-invar ``"param"`` /
    ``"opt"`` markers feeding the DT502 update-site check. Returns
    ``(findings, summary)``; findings are aggregated per (rule, site
    kind), deterministic across runs of the same program.
    """
    flow = _NumFlow(compute_dtype=compute_dtype, params_dtype=params_dtype,
                    carry_steps=carry_steps, reduce_elems=reduce_elems)
    invars = closed.jaxpr.invars
    in_avs: List[_Av] = []
    seeded = 0
    for i, v in enumerate(invars):
        rng = None
        if in_ranges is not None and i < len(in_ranges):
            rng = in_ranges[i]
        lin = None
        if in_lineage is not None and i < len(in_lineage):
            lin = in_lineage[i]
        kw = {}
        if rng is not None:
            kw["lo"], kw["hi"] = float(rng[0]), float(rng[1])
            seeded += 1
        if lin:
            kw["lineage"] = frozenset({lin})
        in_avs.append(flow.fresh(**kw))
    flow.walk(closed, in_avs)
    ignore = frozenset(ignore)
    findings = [f for f in flow.findings(source)
                if f.rule_id not in ignore]
    summary = flow.summary()
    summary["invars_seeded"] = seeded
    summary["rules"] = {r: c for r, c in summary["rules"].items()
                        if r not in ignore}
    return merge_findings(findings), summary


def _opt_state_ranges(opt_state, bound: float) -> Optional[List]:
    """Per-leaf seed ranges for an optax state tree, matched against the
    jax flatten order. Second-moment leaves (EMAs of squared grads, field
    name ``nu``/``v``) are non-negative by construction — the invariant
    that keeps ``sqrt(nu)+eps`` out of DT503; step counters count up from
    zero. Returns None when the structure can't be walked safely."""
    import jax  # noqa: PLC0415

    out: List = []

    def rec(obj, hint: str) -> None:
        if obj is None:
            return
        if hasattr(obj, "_fields"):  # NamedTuple (optax states)
            for name, child in zip(obj._fields, obj):
                rec(child, name)
            return
        if isinstance(obj, dict):
            for k in sorted(obj):  # jax flattens dicts by sorted key
                rec(obj[k], hint)
            return
        if isinstance(obj, (tuple, list)):
            for child in obj:
                rec(child, hint)
            return
        if not (hasattr(obj, "shape") or isinstance(obj, (int, float))):
            return
        h = hint.lower()
        if "count" in h or "step" in h:
            out.append((0.0, 1e9))
        elif h in ("nu", "v") or h.endswith("_sq") or "second" in h:
            out.append((0.0, bound * bound))
        else:
            out.append((-bound, bound))

    try:
        rec(opt_state, "")
        if len(out) != len(jax.tree_util.tree_leaves(opt_state)):
            return None
        return out
    except Exception:
        return None


def network_numerics(net, closed, args, *, source: str = NUM_SOURCE,
                     ignore: Iterable[str] = (),
                     input_bound: float = DEFAULT_INPUT_BOUND) -> dict:
    """Numerics pass over a net's already-traced train step.

    ``closed``/``args`` are the ``make_jaxpr`` result and the shell args
    it was traced with (``check_network_ir`` shares its trace — one
    ``make_jaxpr``, two walks). Seeds: inputs/labels/params at the
    declared ``input_bound``, optimizer second moments at ``[0, B^2]``
    (non-negative by construction), step counters at ``[0, 1e9]``.
    Returns ``{"findings": [...], "summary": {...}}``.
    """
    import jax  # noqa: PLC0415

    conf = net.conf
    compute_dtype = getattr(conf, "dtype", "float32")
    params_dtype = getattr(conf, "params_dtype", None)
    loss_scale = getattr(conf, "loss_scale", None)

    params, opt_state = args[0], args[1]
    n_params = len(jax.tree_util.tree_leaves(params))
    n_opt = len(jax.tree_util.tree_leaves(opt_state))
    b = float(input_bound)

    ranges: List = [(-b, b)] * n_params
    opt_ranges = _opt_state_ranges(net.opt_state, b)
    ranges += opt_ranges if opt_ranges is not None \
        else [(-b, b)] * n_opt
    lineage: List = ["param"] * n_params + ["opt"] * n_opt
    for leaf_ in jax.tree_util.tree_leaves(args[2:]):
        dt = str(getattr(leaf_, "dtype", ""))
        ranges.append((-b, b) if _is_float(dt) else None)
        lineage.append(None)

    n_invars = len(closed.jaxpr.invars)
    if len(ranges) != n_invars:  # unexpected flattening: stay sound
        ranges = [None] * n_invars
        lineage = [None] * n_invars

    findings, summary = check_jaxpr_numerics(
        closed, source=source, in_ranges=ranges, in_lineage=lineage,
        compute_dtype=compute_dtype, params_dtype=params_dtype,
        ignore=ignore)

    # DT505 (net-level): sub-f32 grad flow (storage dtype below f32 means
    # the cast transpose emits grads at that dtype) with no loss scale
    low_storage = sorted({
        str(p.dtype) for p in jax.tree_util.tree_leaves(params)
        if str(getattr(p, "dtype", "")) in _LOW})
    if low_storage and not loss_scale and "DT505" not in frozenset(ignore):
        dt = low_storage[0]
        findings = merge_findings(findings + [get_rule("DT505").finding(
            f"parameters are stored in {dt} (gradients flow at {dt} "
            "through the cast transpose) but no loss scale is "
            "configured — set conf.loss_scale / "
            "MeshLayout(params_dtype=..., loss_scale=...) / "
            "PrecisionPolicy(loss_scale=...)",
            file=source, context="numerics:loss-scale")])
        summary["rules"]["DT505"] = summary["rules"].get("DT505", 0) + 1
    summary["policy"] = {"compute_dtype": compute_dtype,
                         "params_dtype": params_dtype,
                         "loss_scale": loss_scale}
    return {"findings": findings, "summary": summary}


def check_network_numerics(net, batch_or_struct=None, *,
                           ignore: Iterable[str] = (),
                           timesteps_probe: Optional[int] = None,
                           input_bound: float = DEFAULT_INPUT_BOUND,
                           source: str = NUM_SOURCE) -> dict:
    """Standalone DT5xx entry over a net's real train step. Traces once
    via :func:`~deeplearning4j_tpu.analysis.ir_checks.check_network_ir`
    (which shares the jaxpr between the DT2xx and DT5xx walks) and
    returns only the numerics block: ``{"findings", "summary"}``."""
    from .ir_checks import check_network_ir  # noqa: PLC0415

    rep = check_network_ir(net, batch_or_struct, ignore=ignore,
                           timesteps_probe=timesteps_probe, source=source,
                           numerics=True, numerics_input_bound=input_bound)
    return {"findings": [f for f in rep["findings"]
                         if f.rule_id.startswith("DT5")],
            "summary": rep["numerics"]}


def analyze_config_numerics(conf, *, batch: int = 4,
                            timesteps_probe: Optional[int] = None,
                            source: str = NUM_SOURCE,
                            ignore: Iterable[str] = (),
                            input_bound: float = DEFAULT_INPUT_BOUND
                            ) -> Tuple[List[Finding], dict]:
    """Headless DT5xx entry for a config (the CLI ``--numerics`` path):
    builds the matching network class and scans its train step. Returns
    ``(findings, summary)``."""
    if hasattr(conf, "vertices"):
        from ..nn.graph import ComputationGraph  # noqa: PLC0415

        net = ComputationGraph(conf)
    else:
        from ..nn.multilayer import MultiLayerNetwork  # noqa: PLC0415

        net = MultiLayerNetwork(conf)
    block = check_network_numerics(
        net, batch, ignore=ignore, timesteps_probe=timesteps_probe,
        input_bound=input_bound, source=source)
    return block["findings"], block["summary"]
