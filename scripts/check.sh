#!/usr/bin/env bash
# Tier-1 verify flow: static analysis first (fails in seconds), then tests.
#
#   scripts/check.sh            # self-check + tier-1 tests
#   scripts/check.sh --lint     # self-check only
#
# The self-check is also enforced inside the suite
# (tests/test_analysis.py::TestSelfHosting), so a plain pytest run cannot
# silently skip it.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== dl4jtpu-check: analyzer self-check (deeplearning4j_tpu/ --fail-on error)"
env JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.analysis deeplearning4j_tpu/ --fail-on error

echo "== dl4jtpu-check: telemetry package held to --fail-on warning"
env JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.analysis deeplearning4j_tpu/telemetry/ --fail-on warning

echo "== dl4jtpu-check: compile/bucketing/serving/fleet/layout/online/tune/resilience modules held to --fail-on warning"
env JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.analysis \
    deeplearning4j_tpu/runtime/compile_manager.py \
    deeplearning4j_tpu/runtime/inference.py \
    deeplearning4j_tpu/runtime/online.py \
    deeplearning4j_tpu/runtime/checkpoint.py \
    deeplearning4j_tpu/runtime/resilience.py \
    deeplearning4j_tpu/datasets/bucketing.py \
    deeplearning4j_tpu/serving/ \
    deeplearning4j_tpu/fleet/ \
    deeplearning4j_tpu/testing/ \
    deeplearning4j_tpu/utils/subproc.py \
    deeplearning4j_tpu/parallel/layout.py \
    deeplearning4j_tpu/parallel/roles.py \
    deeplearning4j_tpu/parallel/ring_attention.py \
    deeplearning4j_tpu/parallel/pipeline.py \
    deeplearning4j_tpu/parallel/param_server.py \
    deeplearning4j_tpu/analysis/shard_flow.py \
    deeplearning4j_tpu/analysis/concurrency.py \
    deeplearning4j_tpu/analysis/runtime_checks.py \
    deeplearning4j_tpu/tune/ \
    --fail-on warning

echo "== dl4jtpu-check: DT4xx runtime-guard self-scan (serving/fleet/runtime/telemetry/streaming, --fail-on warning)"
# The concurrency/env/telemetry tier applied to the threaded stack it was
# built for: races (DT400), blocking-under-lock (DT401), lock-order
# inversions (DT402), raw environ writes (DT403), bare sleeps (DT404),
# trace-unsafe handler mutations (DT405), metric/event schema drift
# (DT406). Every pragma in these trees carries its justification inline.
if env JAX_PLATFORMS=cpu python -c 'import deeplearning4j_tpu.analysis.concurrency' 2>/dev/null; then
    env JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.analysis --concurrency \
        deeplearning4j_tpu/serving/ \
        deeplearning4j_tpu/fleet/ \
        deeplearning4j_tpu/runtime/ \
        deeplearning4j_tpu/telemetry/ \
        deeplearning4j_tpu/streaming/ \
        --fail-on warning

    echo "== dl4jtpu-check: full-tree DT406 telemetry-schema audit"
    # Metric declarations and flight-recorder event kinds live all over the
    # tree, not just the five runtime dirs — schema drift is global.
    env JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.analysis --concurrency \
        deeplearning4j_tpu/ \
        --ignore DT400,DT401,DT402,DT403,DT404,DT405 \
        --fail-on warning
else
    # bootstrap fallback: if the analyzer itself can't import (mid-rebase,
    # broken deps), keep at least the original grep gate on retry sleeps
    echo "== dl4jtpu-check: DT4xx unavailable; falling back to sleep grep gate"
    if grep -nE 'time\.sleep\(' \
        deeplearning4j_tpu/fleet/*.py \
        deeplearning4j_tpu/runtime/online.py \
        deeplearning4j_tpu/runtime/checkpoint.py; then
        echo "FAIL: bespoke time.sleep in a failure-handling module — use" \
             "RetryPolicy/Deadline from deeplearning4j_tpu/runtime/resilience.py" >&2
        exit 1
    fi
fi

echo "== dl4jtpu-irlint: IR self-scan of the repo's own step functions (--fail-on warning)"
env JAX_PLATFORMS=cpu python - <<'PY'
# DT2xx over the real train steps of both network classes (dense MLP and a
# graph twin) — the jaxpr-level analog of the analyzer self-check above.
# Must be clean at warning level (DT206 "memory-bound" is info by design).
from deeplearning4j_tpu import (ComputationGraph, ComputationGraphConfiguration,
                                DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.analysis import SEVERITY_ORDER

mln = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=128, activation="relu"),
            DenseLayer(n_out=128, activation="relu"),
            OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(128),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3)))
graph = ComputationGraph(
    ComputationGraphConfiguration.builder()
    .add_inputs("in")
    .add_layer("h", DenseLayer(n_out=64, activation="relu"), "in")
    .add_layer("out", OutputLayer(n_out=8, activation="softmax",
                                  loss="mcxent"), "h")
    .set_outputs("out")
    .set_input_types(InputType.feed_forward(32))
    .build())
bad = []
for net in (mln, graph):
    rep = net.analyze_ir(64)
    assert rep["static_cost"]["flops"] > 0
    bad += [f for f in rep["findings"]
            if SEVERITY_ORDER[f.severity] >= SEVERITY_ORDER["warning"]]
for f in bad:
    print(f.format_human())
assert not bad, f"{len(bad)} DT2xx warning+ finding(s) in the repo's own steps"
print("IR self-scan clean (both net classes, warning threshold)")
PY

echo "== dl4jtpu-numlint: DT5xx numerics self-scan (both net classes, f32 + bf16 storage) + overhead smoke"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 20 acceptance: the dtype-flow + value-range pass over the repo's
# OWN train steps. The f32 variants must be clean at warning level; the
# bf16-storage variants must be clean OUTRIGHT — DT505 is info-severity
# and would slip a warning gate, and it is exactly the rule the
# PrecisionPolicy default loss scale is supposed to retire (the f32
# update island retires DT502 the same way). Then the admission-overhead
# smoke: a numerics-enabled analyze_ir trace must stay within 1.3x of
# the DT2xx-only trace.
import time

from deeplearning4j_tpu import (ComputationGraph, ComputationGraphConfiguration,
                                DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.analysis import SEVERITY_ORDER
from deeplearning4j_tpu.analysis.ir_checks import check_network_ir
from deeplearning4j_tpu.analysis.numerics import check_network_numerics
from deeplearning4j_tpu.parallel.layout import PrecisionPolicy


def mln():
    return MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=128, activation="relu"),
                DenseLayer(n_out=128, activation="relu"),
                OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(128),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3)))


def graph():
    return ComputationGraph(
        ComputationGraphConfiguration.builder()
        .add_inputs("in")
        .add_layer("h", DenseLayer(n_out=64, activation="relu"), "in")
        .add_layer("out", OutputLayer(n_out=8, activation="softmax",
                                      loss="mcxent"), "h")
        .set_outputs("out")
        .set_input_types(InputType.feed_forward(32))
        .build())


for label, build, storage in (("mln/f32", mln, None),
                              ("graph/f32", graph, None),
                              ("mln/bf16", mln, "bfloat16"),
                              ("graph/bf16", graph, "bfloat16")):
    net = build().init()
    if storage:
        PrecisionPolicy(params_dtype=storage).apply_to_net(net)
    block = check_network_numerics(net, 64)
    bad = (block["findings"] if storage else
           [f for f in block["findings"]
            if SEVERITY_ORDER[f.severity] >= SEVERITY_ORDER["warning"]])
    for f in bad:
        print(f.format_human())
    assert not bad, (label, f"{len(bad)} DT5xx finding(s)")
    pol = block["summary"].get("policy") or {}
    if storage:
        assert pol.get("loss_scale"), (label, pol)
    print(f"  {label}: clean ({block['summary']['eqns']} eqns, "
          f"seeded {block['summary']['invars_seeded']} invars, "
          f"policy {pol})")

# overhead smoke: the DT5xx walk rides the same trace as DT2xx, so the
# numerics-enabled analyze must stay within 1.3x of the DT2xx-only one
# (best-of-3 each; a 50 ms absolute slack absorbs timer noise on tiny
# CPU traces).
net = mln().init()
check_network_ir(net, 64, numerics=False)  # warm import paths once
check_network_ir(net, 64, numerics=True)


def best(numerics, reps=3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        check_network_ir(net, 64, numerics=numerics)
        ts.append(time.perf_counter() - t0)
    return min(ts)


base, full = best(False), best(True)
ratio = full / base
assert ratio <= 1.3 or full - base < 0.05, (
    f"numerics-enabled analyze_ir {full:.3f}s is {ratio:.2f}x the "
    f"DT2xx-only {base:.3f}s (budget 1.3x)")
print(f"numerics self-scan OK: 4/4 variants clean, overhead "
      f"{ratio:.2f}x ({base * 1e3:.0f} -> {full * 1e3:.0f} ms)")
PY

echo "== roofline smoke: static cost model on the bench MLP"
env JAX_PLATFORMS=cpu python - <<'PY'
# the bench MLP's predicted FLOPs must match the closed form and the
# roofline must produce a finite, positive step-time prediction
from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)

B, H = 512, 1000
net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=H, activation="relu"),
            OutputLayer(n_out=10, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(784),
    updater=UpdaterConfig(updater="sgd", learning_rate=0.1)))
cost = net.analyze_ir(B)["static_cost"]
# fwd+bwd matmul floor: first layer pays fwd + dL/dW (no dL/dx — inputs
# are not differentiated), the head pays fwd + dL/dW + dL/dh
floor = 2 * (2 * B * 784 * H) + 3 * (2 * B * H * 10)
assert cost["flops"] >= floor, (cost["flops"], floor)
rl = cost["roofline"]
assert rl["predicted_step_seconds"] > 0 and rl["ridge_flops_per_byte"] > 0
assert cost["arithmetic_intensity"] > 0
print(f"roofline smoke OK: {cost['flops']:,} FLOPs/step "
      f"(floor {floor:,}), AI {cost['arithmetic_intensity']:.2f}, "
      f"predicted {rl['predicted_step_seconds']:.3g}s/step ({rl['bound']})")
PY

echo "== kernel-selection self-scan: auto must pick fused where memory-bound"
env JAX_PLATFORMS=cpu python - <<'PY'
# Build charrnn + attention configs, trace their REAL train steps with the
# fused variants allowed to compete (force_available scores them off-TPU in
# interpret mode, exactly as a TPU backend would), and assert the roofline
# picks the fused kernels at the memory-bound shapes, the selection
# telemetry is populated, and fused-vs-reference parity holds (smoke).
import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, UpdaterConfig)
from deeplearning4j_tpu.models.char_rnn import char_rnn
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.ops import kernel_select as ks
from deeplearning4j_tpu.telemetry import get_registry

ks.reset()
ks.set_force_available(True)

# charrnn config: the ISSUE 6 acceptance workload (LSTM + softmax loss
# head) at its bench shape — B=64, T=256 (timesteps_probe), H=512
net = MultiLayerNetwork(char_rnn(vocab_size=96, hidden_size=512,
                                 num_layers=2)).init()
rep = net.analyze_ir(64, timesteps_probe=256)
assert rep["static_cost"]["roofline"]["bound"] == "memory", "charrnn step \
should be memory-bound on the roofline"
picked = {r["site"]: r["variant"] for r in ks.selection_log()}
assert picked.get("lstm_seq") == "seqfused", picked
assert picked.get("softmax_xent") == "fused", picked
assert picked.get("optimizer") == "fused", picked

# attention config: flash above the seq threshold, xla below
attn = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[SelfAttentionLayer(n_out=64, n_heads=8, causal=True),
            RnnOutputLayer(n_out=8, activation="softmax", loss="mcxent")],
    input_type=InputType.recurrent(64, 1024),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()
attn.analyze_ir(2)
picked = {r["site"]: r["variant"] for r in ks.selection_log()}
assert picked.get("attention") == "flash", picked
assert ks.select("attention", {"B": 2, "heads": 8, "T": 64, "D": 8,
                               "itemsize": 4, "causal": True}) == "xla"

# selection telemetry counters populated (dl4jtpu_kernel_selected_total)
fam = get_registry().get("dl4jtpu_kernel_selected_total")
assert fam is not None
counts = {key: child.value for key, child in fam._items()}
assert sum(counts.values()) >= 4, counts

# parity smoke: fused softmax+xent fwd/grad vs the XLA form
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
lab = jnp.asarray(np.eye(96, dtype=np.float32)[rng.integers(0, 96, 64)])
from deeplearning4j_tpu.ops.pallas_kernels import fused_softmax_xent
ref = -(lab * jax.nn.log_softmax(x, axis=-1)).sum(-1)
np.testing.assert_allclose(fused_softmax_xent(x, lab), ref, atol=1e-5)
gf = jax.grad(lambda a: fused_softmax_xent(a, lab).sum())(x)
gr = jax.grad(lambda a: (-(lab * jax.nn.log_softmax(a, -1)).sum(-1)).sum())(x)
np.testing.assert_allclose(gf, gr, atol=1e-5)
ks.reset()
print(f"kernel-selection self-scan OK: {len(counts)} (site,variant) "
      "counters, charrnn -> seqfused+fused-xent+fused-adam, "
      "attention -> flash@1024/xla@64, parity smoke clean")
PY

echo "== mesh-layout self-scan: DT008-clean canonical layouts + preflight-proves-fsdp-fits"
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'PY'
# ISSUE 8 acceptance smoke: canonical MeshLayouts on a forced 4-device CPU
# mesh must be (1) DT008-clean against a real model's params — including at
# CompileManager admission, (2) capability-jump-proven: a net whose
# param+grad+opt bytes exceed a synthetic single-device HBM limit raises
# MemoryPreflightError unsharded, passes preflight under fsdp=4 + bf16
# storage, and then actually trains to a finite loss, sharded.
import os

from __graft_entry__ import _force_cpu_mesh

_force_cpu_mesh(4)

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.datasets.iterators import DataSet
from deeplearning4j_tpu.parallel import MeshLayout, ParallelWrapper
from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
from deeplearning4j_tpu.telemetry import MemoryPreflightError, get_registry

net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=1024, activation="relu"),
            DenseLayer(n_out=1024, activation="relu"),
            OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(784),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()

layouts = {
    "dp": MeshLayout(data=4),
    "dp_fsdp": MeshLayout(data=2, fsdp=2),
    "dp_tp": MeshLayout(data=2, tp=2),
    "fsdp_bf16": MeshLayout(data=1, fsdp=4, params_dtype="bfloat16"),
}
for name, lo in layouts.items():
    findings = lo.validate(net.params, source=f"<check:{name}>")
    assert findings == [], (name, [f.format_human() for f in findings])

# param+grad+opt ≈ 4 × 7.2 MiB ≈ 29 MiB > the 24 MiB synthetic limit;
# fsdp=4 + bf16 storage lands the per-device share well under it.
# DL4JTPU_* mutations go through the restore-on-exit scope, never raw
# os.environ writes (tune/knobs.py is the one sanctioned path).
from deeplearning4j_tpu.tune.knobs import EnvScope

_hbm_scope = EnvScope()
_hbm_scope.set("DL4JTPU_HBM_LIMIT_BYTES", 24 << 20)
try:
    net.preflight(32)
    raise SystemExit("unsharded preflight unexpectedly fit the limit")
except MemoryPreflightError as e:
    msg = str(e)
assert "exceeds" in msg, msg

fsdp = layouts["fsdp_bf16"]
report = net.preflight(32, layout=fsdp)
assert report["preflight"]["checked"] and report["preflight"]["fits"], \
    report["preflight"]
per_dev = report["totals"]["per_device"]["projected_peak_bytes"]

wrapper = ParallelWrapper(net, layout=fsdp)
rng = np.random.default_rng(0)
x = rng.normal(size=(32, 784)).astype(np.float32)
y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 32)]
wrapper.fit(DataSet(x, y))
assert jnp.isfinite(net._last_loss), net._last_loss
W = net.params[0]["W"]
assert W.dtype == jnp.bfloat16 and "fsdp" in str(W.sharding.spec)

# DT008 admission stayed green for every sharded program compiled above
fam = get_registry().get("dl4jtpu_ir_findings_total")
dt008 = 0
if fam is not None:  # family key = label-value tuple in ("rule",) order
    dt008 = sum(child.value for key, child in fam._items()
                if key and key[0] == "DT008")
assert dt008 == 0, f"{dt008} DT008 finding(s) from the layout self-scan"
_hbm_scope.restore()
assert "DL4JTPU_HBM_LIMIT_BYTES" not in os.environ
print(f"mesh-layout self-scan OK: {len(layouts)} layouts DT008-clean, "
      f"preflight {msg.split(';')[0][:60]!r} -> fsdp per-device "
      f"{per_dev >> 20} MiB fits, trained sharded bf16 to finite loss, "
      f"admission DT008=0")
PY

echo "== shard-flow self-scan: DT3xx clean/expected on the canonical layouts + census parity"
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'PY'
# ISSUE 9 acceptance smoke: (1) the static sharding-flow pass over the four
# canonical PR 8 layouts must come back DT3xx-clean on the dense self-scan
# net (fsdp's ZeRO param gathers and grad all-reduces are the documented
# cost, not findings), with tp allowed only its expected advisories;
# (2) predicted census == measured post-SPMD census (same kinds/axes,
# bytes within 1.5x) for dp and fsdp, compiled on the forced 4-device CPU
# mesh; (3) ZeRO-1 layouts are collective-free on the forward pass.
from __graft_entry__ import _force_cpu_mesh

_force_cpu_mesh(4)

import numpy as np
import jax

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.analysis.shard_flow import (
    check_network_shard_flow, compare_census, hlo_collective_census)
from deeplearning4j_tpu.parallel import MeshLayout

net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=1024, activation="relu"),
            DenseLayer(n_out=1024, activation="relu"),
            OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(784),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()

layouts = {
    "dp": MeshLayout(data=4),
    "dp_fsdp": MeshLayout(data=2, fsdp=2),
    "dp_tp": MeshLayout(data=2, tp=2),
    "fsdp_bf16": MeshLayout(data=1, fsdp=4, params_dtype="bfloat16"),
}
for name, lo in layouts.items():
    flow = check_network_shard_flow(net, 64, lo)
    rules = sorted({f.rule_id for f in flow["findings"]})
    assert not rules, (name, rules,
                       [f.format_human() for f in flow["findings"]])
    if lo._fsdp_axis or lo.batch_factor > 1:
        assert flow["census"], (name, "expected a non-empty census")
print("  DT3xx self-scan clean on", ", ".join(layouts))

# census parity, compiled: dp (grad all-reduce only) + fsdp (param
# all-gather + grad all-reduce), measured from the post-SPMD HLO
rng = np.random.default_rng(0)
x = rng.normal(size=(64, 784)).astype(np.float32)
y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 64)]
for name, lo in (("dp", MeshLayout(data=4)),
                 ("fsdp", MeshLayout(data=1, fsdp=4))):
    n2 = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=1024, activation="relu"),
                OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(784),
        updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()
    lo.apply(n2)
    step = n2._build_train_step()
    hlo = step.lower(n2.params, n2.opt_state, n2.state,
                     lo.put(x, lo.batch_sharding()),
                     lo.put(y, lo.batch_sharding()),
                     n2._rng, None, None).compile().as_text()
    measured = hlo_collective_census(hlo, lo)
    predicted = check_network_shard_flow(n2, 64, lo)["census"]
    res = compare_census(predicted, measured)
    assert res["ok"], (name, res["problems"], predicted, measured)
    kinds = sorted({r["kind"] for r in measured})
    if name == "dp":
        assert kinds == ["all_reduce"], kinds
    else:
        assert "all_gather" in kinds and "all_reduce" in kinds, kinds
    print(f"  census parity {name}: ratio {res['total_ratio']} "
          f"({len(measured)} measured rows)")

# ZeRO-1: moments shard, params replicate, forward collective-free
z1 = MeshLayout(data=1, fsdp=4, zero_stage=1)
from jax.sharding import PartitionSpec as P
assert z1.param_spec((1024, 1024)) == P()
assert z1.opt_spec((1024, 1024)) == P("fsdp")
fwd = check_network_shard_flow(net, 64, z1, train=False)
assert fwd["census"] == [], fwd["census"]
print("  ZeRO-1 forward collective-free, moments sharded / params replicated")

# ISSUE 15: head-aware tp on an attention net. Training through admission
# must leave dl4jtpu_ir_findings_total{rule="DT305"} at ZERO (the layer-
# roles registry eliminated the per-step activation collectives the
# generic tp spec pays), and the compiled census must hold parity.
from deeplearning4j_tpu.datasets.iterators import DataSet
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.telemetry import get_registry

attn = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[SelfAttentionLayer(n_out=128, n_heads=4, activation="identity"),
            RnnOutputLayer(n_in=128, n_out=16, activation="softmax",
                           loss="mcxent")],
    input_type=InputType.recurrent(64),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()
ha = MeshLayout(data=2, tp=2, roles=True)
flow = check_network_shard_flow(attn, 8, ha, timesteps_probe=32)
assert flow["findings"] == [], [f.format_human() for f in flow["findings"]]
xa = rng.normal(size=(8, 32, 64)).astype(np.float32)
ya = np.eye(16, dtype=np.float32)[rng.integers(0, 16, (8, 32))]
ParallelWrapper(attn, layout=ha).fit(DataSet(xa, ya))
fam = get_registry().get("dl4jtpu_ir_findings_total")
dt305 = 0
if fam is not None:
    dt305 = sum(child.value for key, child in fam._items()
                if key and key[0] == "DT305")
assert dt305 == 0, \
    f'dl4jtpu_ir_findings_total{{rule="DT305"}} = {dt305} under roles=True'
step = attn._build_train_step()
hlo = step.lower(attn.params, attn.opt_state, attn.state,
                 ha.put(xa, ha.input_sharding(xa)),
                 ha.put(ya, ha.input_sharding(ya)),
                 attn._rng, None, None).compile().as_text()
res = compare_census(flow["census"], hlo_collective_census(hlo, ha))
assert res["ok"], (res["problems"], flow["census"])
tp_ar = [r for r in flow["census"]
         if r["kind"] == "all_reduce" and r["axes"] == ["tp"]]
assert sum(r["count"] for r in tp_ar) <= 2, flow["census"]
print(f"  head-aware tp: DT305=0 through admission, census parity "
      f"ratio {res['total_ratio']}, deferred tp all-reduces only")
print("shard-flow self-scan OK")
PY

echo "== pipeline self-scan: pipe=2 x dp=2 DT3xx-clean + census parity + preflight"
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" python - <<'PY'
# ISSUE 18 acceptance smoke: the 1F1B pipelined step on a pipe=2 x dp=2
# mesh must (1) come back DT3xx-clean from the static sharding-flow pass
# (the per-tick ppermute handoffs are the documented cost, not findings),
# (2) hold predicted-vs-measured census parity against the compiled step's
# post-SPMD HLO, and (3) project per-stage HBM — stashed activations x
# in-flight micro-batches — tightly enough that an over-stash micro-batch
# count fails the preflight BEFORE any compile.
from __graft_entry__ import _force_cpu_mesh

_force_cpu_mesh(4)

import numpy as np

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.analysis.shard_flow import compare_census
from deeplearning4j_tpu.parallel import MeshLayout, PipelinedTrainer
from deeplearning4j_tpu.telemetry.memory import MemoryPreflightError

net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=256, activation="relu"),
            DenseLayer(n_out=256, activation="relu"),
            OutputLayer(n_out=16, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(128),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()
tr = PipelinedTrainer(net, MeshLayout(data=2, pipe=2), microbatches=4)
rng = np.random.default_rng(0)
x = rng.normal(size=(64, 128)).astype(np.float32)
y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 64)]

flow = tr.analyze(x, y)
rules = sorted({f.rule_id for f in flow["findings"]})
assert not rules, (rules, [f.format_human() for f in flow["findings"]])
assert any(r["kind"] == "collective_permute" and r["axes"] == ["pipe"]
           for r in flow["census"]), flow["census"]
print("  pipelined step DT3xx-clean, ppermute handoffs in predicted census")

res = compare_census(flow["census"], tr.measured_census(x, y))
assert res["ok"], (res["problems"], flow["census"])
print(f"  census parity piped: ratio {res['total_ratio']}")

rep = tr.preflight(x, y)
peak = rep["pipeline"]["projected_peak_bytes_per_device"]
assert rep["pipeline"]["in_flight"] == 4 + 2 - 1
try:
    tr.preflight(x, y, limit_bytes=peak // 2)
    raise SystemExit("over-stash preflight did not raise")
except MemoryPreflightError as e:
    assert "micro-batch" in str(e)
print(f"  preflight OK: projected peak {peak >> 10} KiB/device, "
      f"over-stash budget raises MemoryPreflightError")
print("pipeline self-scan OK")
PY

echo "== compile-count smoke: varying steps/tails must not recompile"
env JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
    tests/test_compile_manager.py::TestRecompileElimination

echo "== flight-recorder smoke: induced NaN loss must leave a parseable dump"
env JAX_PLATFORMS=cpu python - <<'PY'
import json
import tempfile

import numpy as np

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.telemetry import (FlightRecorder, MetricsRegistry,
                                          Telemetry, Watchdog)

conf = MultiLayerConfiguration(
    layers=[DenseLayer(n_out=8, activation="relu"),
            OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(6),
    updater=UpdaterConfig(updater="sgd", learning_rate=0.1))
net = MultiLayerNetwork(conf).init()
reg = MetricsRegistry()
fr = FlightRecorder(dump_dir=tempfile.mkdtemp(prefix="dl4jtpu_flight_"),
                    registry=reg)
fr.attach_memory_report(net.memory_report(8))
net.set_telemetry(Telemetry(registry=reg, fetch_every=4,
                            watchdog=Watchdog(sinks=[], registry=reg),
                            flight_recorder=fr))
rng = np.random.default_rng(0)
xs = rng.normal(size=(2, 8, 6)).astype(np.float32)
ys = np.eye(4, dtype=np.float32)[rng.integers(0, 4, (2, 8))]
xs[0, 0, 0] = np.nan  # induce the NaN loss
net.fit_on_device(xs, ys, steps=3)
assert fr.dumps, "NaN loss produced no flight-recorder dump"
bundle = json.loads(open(fr.dumps[0]).read())
assert bundle["schema"] == "dl4jtpu-flight-v1"
kinds = {e["kind"] for e in bundle["events"]}
assert {"step", "anomaly", "staged_dispatch"} <= kinds, kinds
assert bundle["memory"]["report"]["totals"]["param_bytes"] > 0
assert "dl4jtpu_train_steps_total" in bundle["registry"]
print(f"flight dump OK: {fr.dumps[0]} ({len(bundle['events'])} events)")
PY

echo "== /metrics smoke scrape (in-process UI server)"
env JAX_PLATFORMS=cpu python - <<'PY'
import urllib.request

from deeplearning4j_tpu.telemetry import get_registry
from deeplearning4j_tpu.ui.server import UIServer

get_registry().counter("dl4jtpu_check_smoke_total", "check.sh scrape probe").inc()
server = UIServer.get_instance(port=0)
try:
    url = f"http://127.0.0.1:{server.port}/metrics"
    body = urllib.request.urlopen(url, timeout=10).read().decode()
    assert "dl4jtpu_check_smoke_total 1" in body, body[:400]
    print(f"scraped {url}: {len(body)} bytes, smoke counter present")
finally:
    server.stop()
PY

echo "== serving smoke: concurrent mixed shapes, zero warm compiles, p99 budget"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 7 acceptance smoke: in-process HTTP serving front-end under
# concurrent mixed-shape traffic must (1) pay ZERO compiles after warmup —
# the compile-manager counter is the proof, (2) keep exact p99 under a
# generous CPU budget, (3) populate /api/serving and the dl4jtpu_serve_*
# series on /metrics.
import json
import threading
import urllib.request

import numpy as np

from deeplearning4j_tpu import (DenseLayer, InputType, MultiLayerConfiguration,
                                MultiLayerNetwork, OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.runtime.compile_manager import get_compile_manager
from deeplearning4j_tpu.serving import get_service
from deeplearning4j_tpu.ui.server import UIServer

net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=64, activation="relu"),
            OutputLayer(n_out=10, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(32),
    updater=UpdaterConfig(updater="adam", learning_rate=1e-3))).init()
svc = get_service()
svc.register("smoke", net)
svc.warmup("smoke", np.zeros((1, 32), np.float32), argmax=True)
server = UIServer.get_instance(port=0)
base = f"http://127.0.0.1:{server.port}"

cm = get_compile_manager()
compiles_before = cm.compiles.value
rng = np.random.default_rng(0)
errors = []

def client(ci):
    try:
        for i in range(12):
            rows = 1 + (ci + i) % 6  # mixed request shapes
            body = json.dumps({
                "model": "smoke",
                "features": rng.normal(size=(rows, 32)).tolist(),
                "argmax": bool(i % 2)}).encode()
            req = urllib.request.Request(
                base + "/serving/predict", body,
                {"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req, timeout=30).read())
            got = out.get("classes" if i % 2 else "output")
            assert len(got) == rows, (rows, out)
    except Exception as e:  # surfaced after join
        errors.append(e)

threads = [threading.Thread(target=client, args=(ci,)) for ci in range(8)]
for t in threads: t.start()
for t in threads: t.join()
assert not errors, errors
warm = cm.compiles.value - compiles_before
assert warm == 0, f"{warm} compiles paid by warm serving traffic"

stats = json.loads(urllib.request.urlopen(base + "/api/serving",
                                          timeout=10).read())
m = stats["models"]["smoke"]
assert m["requests_total"] >= 96, m
p99 = m["latency_seconds"]["p99"]
assert p99 is not None and p99 < 0.25, f"p99 {p99}s over the 250ms budget"
metrics = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
for name in ("dl4jtpu_serve_requests_total", "dl4jtpu_serve_latency_seconds",
             "dl4jtpu_serve_queue_depth", "dl4jtpu_serve_batch_fill_ratio"):
    assert name in metrics, f"{name} missing from /metrics"
server.stop()
svc.stop()
print(f"serving smoke OK: {int(m['requests_total'])} requests, 0 warm "
      f"compiles, p99 {p99*1000:.1f}ms, fill "
      f"{m['mean_batch_fill_ratio']}, /api/serving + /metrics populated")
PY

echo "== online-learning self-scan: short chaos soak (ingest → snapshot → hot-swap → NaN → rollback)"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 10 acceptance smoke: the in-process soak drives the whole live
# loop — staged ingest, versioned checkpoint, train→serve hot-swap, a NaN
# burst, watchdog rollback, source outage/reconnect — and run_soak itself
# asserts the contract: trainer alive, >=1 rollback, a flight bundle as
# the artifact, ZERO steady-state compiles, swaps served.
from __graft_entry__ import _force_cpu_mesh

_force_cpu_mesh(1)

import sys

sys.path.insert(0, "scripts")
from chaos_soak import run_soak

summary = run_soak(records=1024, nan_bursts=1, deadline_s=180)
print(f"online self-scan OK: {summary['records']} records at "
      f"{summary['samples_per_sec']}/s, {summary['rollbacks']} rollback(s), "
      f"{summary['reconnects']} reconnect(s), {summary['swaps']} swap(s), "
      f"{summary['warm_compiles']:.0f} warm compiles, "
      f"{len(summary['flight_bundles'])} flight bundle(s)")
PY

echo "== autopilot self-scan: short mlp search, env bit-identical, tuned config auto-applies"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 12 acceptance smoke: a short real autotune over a tiny MLP must
# (1) finish with a measured winner no worse than the default within noise,
# (2) pay ZERO compiles inside any timed trial region, (3) leave os.environ
# bit-identical to the pre-search snapshot, (4) persist TUNED.json, and
# (5) prove the startup half of the loop: a FRESH InferenceService.register
# of a matching model picks the tuned batcher knobs up, counted by
# dl4jtpu_tuned_config_applied_total.
import os
import tempfile

from deeplearning4j_tpu.tune import TunedStore, run_autotune, scoped_env
from deeplearning4j_tpu.tune import store as tuned_store
from deeplearning4j_tpu.tune.search import MlpFitWorkload

tuned_path = os.path.join(
    tempfile.mkdtemp(prefix="dl4jtpu_check_tuned_"), "TUNED.json")
with scoped_env(DL4JTPU_TUNED_PATH=tuned_path):
    def knob_env():  # jax mutates os.environ on its own (CUDA_ROOT, ...)
        return {k: v for k, v in os.environ.items()
                if k.startswith(("DL4JTPU_", "DL4J_TPU_"))}

    env_before = knob_env()
    wl = MlpFitWorkload(hidden=64, features=32, classes=8)
    result = run_autotune(
        workload=wl, budget_s=45.0, rungs=1, fidelities=(2,),
        space={"train_batch": (16, 64, 128), "stage_window": (2, 4)},
        log=lambda m: print(f"  {m}"))
    assert knob_env() == env_before, "search leaked env state"
    assert result.env_ok
    default, best = result.default.measured, result.best.measured
    assert default and default > 0, "default config was never measured"
    assert best >= 0.8 * default, \
        f"tuned {best:.1f} worse than default {default:.1f} beyond noise"
    assert all(t.compiles_measured == 0 for t in result.trials
               if t.measured is not None), "compile inside a timed region"
    entry = TunedStore(tuned_path).get(wl.key())
    assert entry and "train_batch" in entry["config"], entry

    # startup half: seed serve knobs under the SAME key, register fresh
    from deeplearning4j_tpu.serving import InferenceService
    from deeplearning4j_tpu.telemetry import MetricsRegistry, get_registry

    assert os.environ.get("DL4JTPU_SERVE_MAX_DELAY_MS") is None
    serve_net = wl._build_net("float32")  # serve signature differs from the
    #                                       bf16 fit net: key off THIS model
    TunedStore(tuned_path).put(
        tuned_store.key_for(serve_net),
        {"serve_max_delay_ms": 0.5, "serve_max_batch": 32},
        objective="serve")
    counter = get_registry().counter(
        "dl4jtpu_tuned_config_applied_total",
        "tuned-config knobs auto-applied at startup, by context",
        labelnames=("context",)).labels(context="serve")
    before = counter.value
    service = InferenceService(registry=MetricsRegistry())
    service.register("autopilot", serve_net)
    batcher = service.stats()["models"]["autopilot"]["batcher"]
    assert batcher["max_delay_ms"] == 0.5 and batcher["max_batch"] == 32, \
        batcher
    assert counter.value == before + 2, (before, counter.value)
    service.unregister("autopilot")
assert "DL4JTPU_TUNED_PATH" not in os.environ or \
    os.environ["DL4JTPU_TUNED_PATH"] != tuned_path
print(f"autopilot self-scan OK: {len([t for t in result.trials if t.measured is not None])} "
      f"measured trial(s), {len(result.pruned)} prior-pruned, tuned/default "
      f"{best / default:.2f}x, 0 timed-region compiles, env restored, "
      f"auto-apply counted +2")
PY

echo "== dl4jtpu-fleet self-scan: warm boot, rolling rollout, respawn, drain"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 13 acceptance, end to end in one fleet: 2 worker PROCESSES boot warm
# from the shared checkpoint store's bundle (0 backend compiles before first
# traffic — each worker's in-process jax.monitoring counter is the proof), a
# new version published to the store rolls out worker-by-worker with zero
# recompiles and changed served predictions, a SIGKILLed worker respawns
# warm at the served version, and drain refuses new work afterwards.
import os
import signal
import tempfile
import time

import numpy as np

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.fleet import FleetRouter, build_bundle, save_bundle
from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
from deeplearning4j_tpu.runtime.resilience import Deadline

with tempfile.TemporaryDirectory() as work:
    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7)).init()
    store_dir = os.path.join(work, "store")
    store = CheckpointStore(store_dir)
    store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, 8), np.float32), argmax=True, max_batch=8))

    router = FleetRouter(store_dir, workers=2, poll_s=0.2,
                         worker_args={"max_delay_ms": 0,
                                      "max_batch": 8}).start()
    try:
        probe = np.linspace(-1, 1, 8, dtype=np.float32).reshape(1, 8)
        status, body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 200, (status, body)
        ref1 = np.asarray(body["output"], np.float32)
        for handle in router.workers:
            router._check_worker(handle)
        snaps = router.stats()["workers"]
        assert all(s["ready"] for s in snaps), snaps
        assert all(s["compiles_since_ready"] == 0 for s in snaps), snaps
        assert all(h.last_health.get("bundle_installed")
                   for h in router.workers), "worker booted without bundle"

        # publish v2 from a REAL OnlineTrainer -> the supervisor rolls the
        # fleet by itself: the shared CheckpointStore is the entire
        # train->fleet bus, no coordination code between the processes
        from deeplearning4j_tpu.runtime.online import OnlineTrainer
        from deeplearning4j_tpu.streaming import QueueSource

        rng = np.random.default_rng(0)
        source = QueueSource(maxsize=4096)
        trainer = OnlineTrainer(store.restore(1), source, batch=16, stage=2,
                                linger=0.05, checkpoint_store=store,
                                name="fleet-scan")
        trainer.start()
        try:
            w = rng.normal(size=(8, 4))
            for _ in range(96):
                x = rng.normal(size=8).astype(np.float32)
                y = np.eye(4, dtype=np.float32)[int(np.argmax(x @ w))]
                source.put(x, y)
            deadline = Deadline(60)
            while (trainer.stats()["steps_total"] < 1
                   and deadline.pace(0.05)):
                pass
            assert trainer.stats()["steps_total"] >= 1
            v2 = trainer.checkpoint_now(swap=False)
        finally:
            trainer.stop(checkpoint=False)
        assert v2 == 2, v2
        deadline = Deadline(60)
        while True:
            stats = router.stats()
            if stats["rollouts"] >= 1 and all(
                    w["version"] == 2 for w in stats["workers"]
                    if w["ready"]):
                break
            if not deadline.pace(0.1):
                break
        stats = router.stats()
        assert stats["rollouts"] >= 1, stats
        assert all(w["version"] == 2 for w in stats["workers"]
                   if w["ready"]), stats
        assert all(w["compiles_since_ready"] == 0
                   for w in stats["workers"] if w["ready"]), stats
        status, body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 200, (status, body)
        ref2 = np.asarray(body["output"], np.float32)
        assert not np.array_equal(ref1, ref2), "rollout served same params"

        # SIGKILL one worker -> the supervisor respawns it warm at v2
        victim = router.workers[0]
        os.kill(victim.proc.pid, signal.SIGKILL)
        deadline = Deadline(90)
        while True:
            snap = router.stats()["workers"][0]
            if snap["ready"] and snap["respawns"] >= 1:
                break
            if not deadline.pace(0.2):
                break
        snap = router.stats()["workers"][0]
        assert snap["ready"] and snap["respawns"] >= 1, snap
        assert snap["version"] == 2, snap
        status, _body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 200, status

        assert router.drain(timeout_s=30)
        status, body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 503, (status, body)
        print("fleet self-scan OK: 2 warm-booted workers (0 compiles before "
              "traffic), OnlineTrainer checkpoint rolled the fleet to v2 "
              "with 0 recompiles + changed outputs, SIGKILLed worker "
              f"respawned warm at v2 (respawns={snap['respawns']}), drain "
              "refuses new work")
    finally:
        router.stop()
PY

echo "== dl4jtpu-failsafe self-scan: seeded chaos (corrupt boot, hung worker, NaN rollback+replay, SIGKILL)"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 14 acceptance: the fleet under a SEEDED FaultPlan. The store's
# newest version is corrupted through the plan's checkpoint.write hook, so
# two cold worker PROCESSES must quarantine it and warm-boot the previous
# good version with zero compiles; a hung worker (healthz frozen by the
# env-transported plan, at-most-once across the fleet via marker file) is
# detected by the health Deadline and respawned with reason="hung"; a NaN
# burst injected at a plan-scheduled record index rolls the online trainer
# back, replays the poisoned span, and the recovered checkpoint still
# rolls out; a SIGKILLed worker respawns; /api/resilience reports the
# shared policies' live state.
import json
import os
import signal
import tempfile
import time
import urllib.request

import numpy as np

from deeplearning4j_tpu import (
    DenseLayer,
    InputType,
    MultiLayerConfiguration,
    MultiLayerNetwork,
    OutputLayer,
    UpdaterConfig,
)
from deeplearning4j_tpu.fleet import FleetRouter, build_bundle, save_bundle
from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
from deeplearning4j_tpu.runtime.online import OnlineTrainer
from deeplearning4j_tpu.runtime.resilience import Deadline
from deeplearning4j_tpu.streaming import QueueSource, ReplayBufferSource
from deeplearning4j_tpu.testing.chaos import ChaosSource, FaultPlan
from deeplearning4j_tpu.tune import scoped_env

SEED = 1405


def wait_for(pred, seconds, what):
    d = Deadline(seconds)
    while True:
        if pred():
            return
        if not d.pace(0.1):
            raise AssertionError(f"chaos self-scan: {what} never happened")


with tempfile.TemporaryDirectory() as work:
    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7)).init()
    store_dir = os.path.join(work, "store")
    write_plan = FaultPlan(SEED, [{"site": "checkpoint.write",
                                   "fault": "corrupt-checkpoint",
                                   "at": [2]}])
    store = CheckpointStore(store_dir, chaos=write_plan)
    store.save(net)  # v1 — the good version
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, 8), np.float32), argmax=True, max_batch=8))
    store.save(net)  # v2 — byte-corrupted by the plan as it lands
    assert [f["fault"] for f in write_plan.fired] == ["corrupt-checkpoint"]

    marker = os.path.join(work, "hang.marker")
    hang_plan = FaultPlan(SEED, [{"site": "worker.healthz",
                                  "fault": "hang-worker", "at": [3],
                                  "params": {"seconds": 30},
                                  "marker": marker}])
    with scoped_env(DL4JTPU_CHAOS_PLAN=hang_plan.to_env()):
        router = FleetRouter(store_dir, workers=2, poll_s=0.2,
                             health_timeout_s=2.0,
                             worker_args={"max_delay_ms": 0,
                                          "max_batch": 8}).start()
    try:
        # --- corrupt-latest cold boot: quarantine + serve previous good
        for handle in router.workers:
            router._check_worker(handle)
        snaps = router.stats()["workers"]
        ready = [s for s in snaps if s["ready"]]
        assert ready, snaps
        assert all(s["version"] == 1 for s in ready), snaps
        assert all(s["compiles_since_ready"] == 0 for s in ready), snaps
        assert os.path.exists(os.path.join(
            store_dir, "model-v00000002.zip.quarantine")), \
            os.listdir(store_dir)
        probe = np.linspace(-1, 1, 8, dtype=np.float32).reshape(1, 8)
        status, body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 200, (status, body)

        # --- hung worker: frozen healthz → Deadline expiry → kill+respawn
        hung = router._m_respawns.labels(reason="hung")
        wait_for(lambda: hung.value >= 1, 60, "hung-worker detection")
        assert os.path.exists(marker)
        wait_for(lambda: all(s["ready"]
                             for s in router.stats()["workers"]),
                 90, "respawn after hang")

        # --- NaN burst → rollback → poisoned-span replay → rollout
        src_plan = FaultPlan(SEED, [{"site": "source.record",
                                     "fault": "nan-burst", "at": [260],
                                     "params": {"records": 32}}])
        queue = QueueSource(maxsize=4096)
        source = ReplayBufferSource(ChaosSource(queue, src_plan))
        trainer = OnlineTrainer(store.restore(), source, batch=16, stage=2,
                                linger=0.05, name="chaos-scan",
                                checkpoint_store=store,
                                checkpoint_every_steps=8)
        trainer.start()
        try:
            rng = np.random.default_rng(SEED)
            w = rng.normal(size=(8, 4))

            def put(n):
                for _ in range(n):
                    x = rng.normal(size=8).astype(np.float32)
                    y = np.eye(4, dtype=np.float32)[int(np.argmax(x @ w))]
                    queue.put(x, y)

            put(256)
            wait_for(lambda: trainer.stats()["steps_total"] >= 8, 90,
                     "online ingest")
            put(128)  # deliveries 257..384; the plan poisons 260..291
            wait_for(lambda: trainer.stats()["rollbacks_total"] >= 1, 90,
                     "NaN rollback")
            wait_for(lambda: trainer.stats()["replays_total"] >= 1, 30,
                     "poisoned-span replay")
            st = trainer.stats()
            assert st["last_replay"]["outcome"] in (
                "poisoned", "clean", "empty"), st
            assert trainer.alive
            final_v = trainer.checkpoint_now(swap=False)
        finally:
            trainer.stop(checkpoint=False)
        assert final_v >= 3, final_v
        wait_for(lambda: (lambda ws: any(s["ready"] for s in ws) and all(
            s["version"] == final_v for s in ws if s["ready"]))(
                router.stats()["workers"]),
            90, f"fleet rollout to v{final_v}")
        assert all(s["compiles_since_ready"] == 0
                   for s in router.stats()["workers"] if s["ready"]), \
            router.stats()["workers"]

        # --- SIGKILL → crash respawn through the shared backoff policy
        crash = router._m_respawns.labels(reason="crash")
        crash_before = crash.value
        os.kill(router.workers[0].proc.pid, signal.SIGKILL)
        wait_for(lambda: crash.value > crash_before, 90, "crash respawn")
        wait_for(lambda: router.stats()["workers"][0]["ready"], 90,
                 "killed worker back in rotation")
        status, _body, _ = router.route_predict({"features": probe.tolist()})
        assert status == 200, status

        # --- /api/resilience: the shared policies report live state
        res = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/api/resilience",
            timeout=10).read())
        sites = res["sites"]
        for name in ("fleet.router.respawn", "fleet.router.failover",
                     "fleet.router.health", "fleet.router.boot"):
            assert name in sites, sorted(sites)
        assert sites["fleet.router.health"]["expired_total"] >= 1, sites
        assert sites["fleet.router.respawn"]["retries_total"] >= 1, sites

        assert router.drain(timeout_s=30)
        print("failsafe self-scan OK: corrupt v2 quarantined at cold boot "
              "(served v1, 0 compiles), hung worker respawned "
              f"(hung={hung.value:.0f}), NaN rollback replayed the poisoned "
              f"span ({st['last_replay']['outcome']}), fleet converged on "
              f"v{final_v} with 0 recompiles, SIGKILL respawned, "
              "/api/resilience live")
    finally:
        router.stop()
PY

echo "== dl4jtpu-tracing self-scan: end-to-end fleet trace + SLO burn breach"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 17 acceptance: one sampled request through a REAL 2-worker fleet
# produces ONE merged Chrome trace chaining router -> worker -> admission
# -> micro-batch coalesce (with fan-in links) -> device dispatch (with the
# compile-cache annotation proving zero warm compiles); a forced latency-
# budget breach fires the slo-burn watchdog anomaly and auto-dumps a
# flight bundle naming the offending trace ids.
import glob
import json
import os
import tempfile
import urllib.request

import numpy as np

with tempfile.TemporaryDirectory() as work:
    os.environ["DL4JTPU_TRACE_SAMPLE"] = "1"  # every request traced
    # a sub-microsecond budget makes EVERY request an SLO violation
    os.environ["DL4JTPU_SLO_LATENCY_BUDGET_MS"] = "0.001"
    os.environ["DL4JTPU_FLIGHT_DIR"] = work

    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.fleet import (FleetRouter, build_bundle,
                                          save_bundle)
    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
    from deeplearning4j_tpu.telemetry.slo import get_slo_monitor

    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7)).init()
    store_dir = os.path.join(work, "store")
    store = CheckpointStore(store_dir)
    store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, 8), np.float32), argmax=True, max_batch=8))
    router = FleetRouter(store_dir, workers=2, poll_s=0.2,
                         worker_args={"max_delay_ms": 0,
                                      "max_batch": 8}).start()
    try:
        base = f"http://127.0.0.1:{router.port}"

        def predict():
            req = urllib.request.Request(
                base + "/predict",
                json.dumps({"features": np.zeros((1, 8)).tolist()}).encode(),
                {"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return json.loads(resp.read()), dict(resp.headers)

        out, headers = predict()
        tid = headers["x-dl4jtpu-trace-id"]
        assert headers["x-dl4jtpu-trace-sampled"] == "1", headers
        with urllib.request.urlopen(f"{base}/api/trace/{tid}",
                                    timeout=30) as resp:
            doc = json.loads(resp.read())
        events = doc["traceEvents"]
        hops = {e["name"] for e in events}
        need = {"fleet.request", "fleet.attempt", "worker.predict",
                "serve.request", "serve.batch", "infer.dispatch"}
        assert need <= hops, f"merged trace missing hops: {need - hops}"
        assert len(hops) >= 6, hops
        batch = [e for e in events if e["name"] == "serve.batch"][0]
        assert batch["args"]["links"], "coalesced dispatch lost its fan-in"
        dispatch = [e for e in events if e["name"] == "infer.dispatch"][0]
        assert dispatch["args"]["compiles"] == 0, dispatch["args"]

        # force the burn: every request violates the 1us budget, so both
        # the fast and the slow window exceed their thresholds
        for _ in range(19):
            predict()
        # maybe_evaluate() on the request path fired the breach already
        # (evaluate() here would be rate-limited); read the recorded one
        get_slo_monitor().evaluate()
        breaches = [b for b in
                    get_slo_monitor().stats()["recent_breaches"]
                    if b["objective"] == "latency" and b["offending_traces"]]
        assert breaches, get_slo_monitor().stats()
        offending = breaches[0]["offending_traces"]
        dumps = glob.glob(os.path.join(work, "*slo-burn*.json"))
        assert dumps, f"no slo-burn flight bundle in {work}"
        bundle = json.load(open(dumps[0]))
        dumped = json.dumps(bundle)
        assert any(t in dumped for t in offending), (
            "offending trace ids missing from the flight bundle")
        metrics = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        for name in ("dl4jtpu_slo_burn_rate", "dl4jtpu_slo_breaches_total",
                     "dl4jtpu_trace_spans_total"):
            assert name in metrics, f"{name} missing from router /metrics"
        print(f"tracing self-scan OK: merged trace {tid[:8]}... spans "
              f"{len(events)} across hops {sorted(hops)}; slo-burn breach "
              f"dumped {os.path.basename(dumps[0])} naming "
              f"{len(offending)} offending trace(s)")
    finally:
        router.stop()
        for key in ("DL4JTPU_TRACE_SAMPLE", "DL4JTPU_SLO_LATENCY_BUDGET_MS",
                    "DL4JTPU_FLIGHT_DIR"):
            os.environ.pop(key, None)
PY

echo "== dl4jtpu-tracing overhead gate: default sampling within 3% of disabled"
env JAX_PLATFORMS=cpu python - <<'PY'
# The unsampled hot path costs one thread-local read per hop: the serve
# path at DL4JTPU_TRACE_SAMPLE=1/256 must stay within 3% of tracing
# disabled (interleaved trials, medians, warm compile cache throughout).
import os
import statistics
import time

import numpy as np

from deeplearning4j_tpu import (DenseLayer, InputType,
                                MultiLayerConfiguration, MultiLayerNetwork,
                                OutputLayer, UpdaterConfig)
from deeplearning4j_tpu.serving import InferenceService

net = MultiLayerNetwork(MultiLayerConfiguration(
    layers=[DenseLayer(n_out=16, activation="relu"),
            OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
    input_type=InputType.feed_forward(8),
    updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
    seed=7)).init()
svc = InferenceService(max_delay_ms=0.0)
svc.register("m", net)
probe = np.zeros((1, 8), np.float32)
for _ in range(50):  # warm the compiled path + the batcher
    svc.predict("m", probe)

def trial(n=200):
    t0 = time.perf_counter()
    for _ in range(n):
        svc.predict("m", probe)
    return time.perf_counter() - t0

off, on = [], []
try:
    for _ in range(5):  # interleaved so drift hits both arms equally
        os.environ["DL4JTPU_TRACE_SAMPLE"] = "0"
        off.append(trial())
        os.environ["DL4JTPU_TRACE_SAMPLE"] = "1/256"
        on.append(trial())
finally:
    os.environ.pop("DL4JTPU_TRACE_SAMPLE", None)
    svc.stop()
m_off, m_on = statistics.median(off), statistics.median(on)
ratio = m_on / m_off
assert ratio <= 1.03, (
    f"default-sampled serving {ratio:.3f}x of disabled (>3% overhead): "
    f"on={m_on:.4f}s off={m_off:.4f}s")
print(f"tracing overhead gate OK: 1/256 sampling at {ratio:.3f}x of "
      f"disabled ({m_on*1000:.1f}ms vs {m_off*1000:.1f}ms per 200 requests)")
PY

echo "== dl4jtpu-history self-scan: fleet scrape plane + recording rules + rollout annotation"
env JAX_PLATFORMS=cpu python - <<'PY'
# ISSUE 19 acceptance: a REAL 2-worker warm-booted fleet under scripted
# traffic grows downsampled history for every recording-rule series, a
# rolling rollout lands on the timeline as an annotation, and the
# derived p99 series agrees with /api/fleet's instantaneous exact p99
# at the latest sample point.
import json
import tempfile
import time
import urllib.request

import numpy as np

with tempfile.TemporaryDirectory() as work:
    from deeplearning4j_tpu import (
        DenseLayer,
        InputType,
        MultiLayerConfiguration,
        MultiLayerNetwork,
        OutputLayer,
        UpdaterConfig,
    )
    from deeplearning4j_tpu.fleet import (FleetRouter, build_bundle,
                                          save_bundle)
    from deeplearning4j_tpu.runtime.checkpoint import CheckpointStore
    from deeplearning4j_tpu.telemetry.history import RECORDING_RULES

    net = MultiLayerNetwork(MultiLayerConfiguration(
        layers=[DenseLayer(n_out=16, activation="relu"),
                OutputLayer(n_out=4, activation="softmax", loss="mcxent")],
        input_type=InputType.feed_forward(8),
        updater=UpdaterConfig(updater="sgd", learning_rate=1e-2),
        seed=7)).init()
    store = CheckpointStore(work + "/store")
    store.save(net)
    save_bundle(store, build_bundle(
        net, example=np.zeros((1, 8), np.float32), argmax=True,
        max_batch=8))
    router = FleetRouter(work + "/store", workers=2, poll_s=0.2,
                         scrape_s=0.5, history=True,
                         worker_args={"max_delay_ms": 0,
                                      "max_batch": 8}).start()
    try:
        base = f"http://127.0.0.1:{router.port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=15) as r:
                return json.loads(r.read())

        probe = np.linspace(-1, 1, 8).reshape(1, 8)
        body = json.dumps({"features": probe.tolist()}).encode()

        def traffic(n):
            for _ in range(n):
                req = urllib.request.Request(
                    base + "/predict", body,
                    {"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=30).read()

        traffic(12)
        router.scrape_once()   # baseline tick for the rate sensors
        time.sleep(1.1)
        traffic(6)
        tick = router.scrape_once()
        assert tick["scraped"] == 2, tick
        names = set(router.history.series_names())
        missing = set(RECORDING_RULES) - names
        assert not missing, f"recording rules absent: {missing}"

        # publish v2 -> automatic rolling rollout -> timeline annotation
        import jax
        loader = store.restore(1)
        loader.params = jax.tree_util.tree_map(
            lambda p: p * np.float32(0.5), loader.params)
        store.save(loader)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if router.stats()["rollouts"] >= 1:
                break
            time.sleep(0.2)
        assert router.stats()["rollouts"] >= 1, "rollout never happened"
        router.scrape_once()
        anns = {a["kind"] for a in get(
            "/api/history?range_s=600")["annotations"]}
        assert "fleet_rollout" in anns, anns

        # derived p99 == instantaneous exact p99 at the latest sample
        fstats = get("/api/fleet")
        router.scrape_once()
        out = get("/api/history?series=fleet.latency_p99_seconds"
                  "&range_s=600")
        pts = [p for p in out["series"][0]["points"] if p[1] is not None]
        want = fstats["latency_seconds"]["p99"]
        assert abs(pts[-1][1] - want) < 1e-9, (pts[-1], want)
        hstats = router.history.stats()
        assert hstats["bytes"] <= hstats["byte_budget"], hstats
        print(f"history self-scan OK: {hstats['series']} series, "
              f"{hstats['samples_total']} samples in "
              f"{hstats['bytes']/1024:.0f} KiB "
              f"(budget {hstats['byte_budget']/2**20:.0f} MiB), "
              f"all {len(RECORDING_RULES)} recording rules live, "
              f"rollout annotated, p99 history==exact at latest sample")
    finally:
        router.stop()
PY

if [[ "${1:-}" == "--lint" ]]; then
    exit 0
fi

echo "== bench regression gate (explicit-CPU mlp mode vs BENCH_BASELINE.json)"
# One real CPU bench run, gated against the persisted per-mode baselines —
# a silent mlp-style throughput drop (r03 7888 -> r04 5508) now fails the
# check. Re-anchor intentionally with: scripts/bench_gate.py --refresh.
rm -f /tmp/_bench_gate_line.json
BENCH_FORCE_CPU=1 python bench.py | tail -1 \
    > /tmp/_bench_gate_line.json
python scripts/bench_gate.py /tmp/_bench_gate_line.json

echo "== bench regression gate (serve mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_serve.json
BENCH_FORCE_CPU=1 BENCH_MODEL=serve python bench.py \
    | tail -1 > /tmp/_bench_gate_serve.json
python scripts/bench_gate.py /tmp/_bench_gate_serve.json

echo "== bench regression gate (online mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_online.json
BENCH_FORCE_CPU=1 BENCH_MODEL=online python bench.py \
    | tail -1 > /tmp/_bench_gate_online.json
python scripts/bench_gate.py /tmp/_bench_gate_online.json
python - <<'PY'
# ISSUE 10 acceptance: sustained ingest completes at zero warm compiles and
# the mid-run hot-swap changed served predictions without a restart
import json

d = json.load(open("/tmp/_bench_gate_online.json"))
assert d.get("completed"), d
assert d.get("warm_compiles") == 0, f"warm_compiles={d.get('warm_compiles')}"
assert d["swap"]["served_changed"] and d["swap"]["swaps_total"] >= 1, d["swap"]
print(f"online gate OK: {d['value']} records/sec sustained, 0 warm "
      f"compiles, swap v{d['swap']['version']} changed served predictions")
PY

echo "== bench regression gate (shard mode vs BENCH_BASELINE.json + HBM ratio)"
rm -f /tmp/_bench_gate_shard.json
BENCH_FORCE_CPU=1 BENCH_MODEL=shard python bench.py \
    | tail -1 > /tmp/_bench_gate_shard.json
python scripts/bench_gate.py /tmp/_bench_gate_shard.json
python - <<'PY'
# ISSUE 8 acceptance: fsdp+bf16 per-device HBM < 0.6x replicated f32 (from
# the XLA memory_analysis records of the staged executables)
import json

d = json.load(open("/tmp/_bench_gate_shard.json"))
ratio = d.get("hbm_fsdp_bf16_vs_replicated")
assert ratio is not None, "shard bench carried no HBM records"
assert ratio < 0.6, f"fsdp+bf16 per-device HBM ratio {ratio} >= 0.6x replicated"
print(f"shard HBM gate OK: fsdp+bf16 runs at {ratio:.3f}x the replicated "
      f"f32 per-device footprint")

# ISSUE 9 acceptance: per-variant predicted-vs-measured census parity —
# the static sharding-flow pass must match the post-SPMD ground truth
# (same major collective kinds + mesh axes, byte totals within 1.5x)
for name, variant in d["variants"].items():
    col = variant.get("collectives") or {}
    assert "error" not in col, (name, col.get("error"))
    match = col.get("match") or {}
    assert match.get("ok"), (name, match.get("problems"), col)
    print(f"census parity gate OK [{name}]: predicted/measured byte ratio "
          f"{match['total_ratio']}")

# ISSUE 15 acceptance: head-aware tp must beat generic tp on the same
# attention net + mesh (the eliminated DT305 activation collectives ARE
# the speedup) with zero warm recompiles, and only the head-aware variant
# may be DT305-clean
gen, head = d["variants"]["tp_generic"], d["variants"]["tp_headaware"]
assert head["samples_per_sec"] >= gen["samples_per_sec"], (
    f"tp_headaware {head['samples_per_sec']} < "
    f"tp_generic {gen['samples_per_sec']} samples/sec")
assert head["warm_compiles"] == 0, head["warm_compiles"]
assert "DT305" in (gen["collectives"].get("findings") or []), \
    "generic tp lost its DT305 advisory"
assert "DT305" not in (head["collectives"].get("findings") or []), \
    "head-aware tp still carries DT305"
print(f"head-aware tp gate OK: {head['samples_per_sec']} vs generic "
      f"{gen['samples_per_sec']} samples/sec "
      f"({d['tp_headaware_vs_generic']}x), zero warm compiles")
PY

echo "== bench regression gate (pipeline mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_pipeline.json
BENCH_FORCE_CPU=1 BENCH_MODEL=pipeline python bench.py \
    | tail -1 > /tmp/_bench_gate_pipeline.json
python scripts/bench_gate.py /tmp/_bench_gate_pipeline.json
python - <<'PY'
# ISSUE 18 acceptance: the 1F1B schedule's measured bubble (affine
# intercept of step time in the micro-batch count, fixed micro-batch
# size) must sit within 1.5x of apply_roofline's (P-1)/(M+P-1) term, and
# every timed piped fit must reuse its one AOT executable (bench.py
# asserts both before emitting the line — here we surface the numbers)
import json

d = json.load(open("/tmp/_bench_gate_pipeline.json"))
bub = d.get("bubble") or {}
assert bub.get("within_1p5x"), bub
for m, run in (d.get("runs") or {}).items():
    assert run["warm_compiles"] == 0, (m, run)
print(f"pipeline gate OK: {d['value']} samples/sec piped "
      f"({d['piped_vs_unpiped']}x unpiped), measured bubble "
      f"{bub['measured']} vs predicted {bub['predicted']} "
      f"(ratio {bub['ratio']}), zero warm compiles")
PY

echo "== bench regression gate (autotune mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_autotune.json
BENCH_FORCE_CPU=1 BENCH_MODEL=autotune \
    BENCH_AUTOTUNE_BUDGET_S=60 python bench.py | tail -1 \
    > /tmp/_bench_gate_autotune.json
python scripts/bench_gate.py /tmp/_bench_gate_autotune.json
python - <<'PY'
# ISSUE 12 acceptance: the tuned-vs-default ratio is measured at equal
# fidelity with zero compiles in timed regions and a bit-identical env
import json

d = json.load(open("/tmp/_bench_gate_autotune.json"))
assert d.get("env_ok"), d
assert d.get("compiles_in_timed_regions") == 0, d
assert d.get("tuned_key"), d
print(f"autotune gate OK: tuned/default {d['value']}x "
      f"(default {d['default_samples_per_sec']}, tuned "
      f"{d['tuned_samples_per_sec']} samples/sec), best {d['best_config']}, "
      f"key {d['tuned_key']}")
PY

echo "== bench regression gate (fleet mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_fleet.json
BENCH_FORCE_CPU=1 BENCH_MODEL=fleet python bench.py \
    | tail -1 > /tmp/_bench_gate_fleet.json
python scripts/bench_gate.py /tmp/_bench_gate_fleet.json
python - <<'PY'
# ISSUE 13 acceptance: the offered-load sweep completes with zero errors and
# ZERO warm compiles in every worker process (warm boot did its job), and —
# only on a host with enough cores for the processes to actually overlap —
# 2 workers clear 1.5x the 1-worker rate. On fewer cores the ratio is
# recorded but not enforced (the workers time-slice one core).
import json
import os

d = json.load(open("/tmp/_bench_gate_fleet.json"))
assert d.get("errors_total") == 0, d.get("errors_total")
assert d.get("warm_compiles_total") == 0, \
    f"warm_compiles_total={d.get('warm_compiles_total')}"
ratio = d["scale_out_ratio"]
cores = os.cpu_count() or 1
if cores >= 4:
    assert ratio >= 1.5, \
        f"2-worker scale-out {ratio}x < 1.5x on a {cores}-core host"
    print(f"fleet gate OK: {d['value']} samples/sec, scale-out {ratio}x "
          f"(>=1.5x enforced, {cores} cores), 0 errors, 0 warm compiles")
else:
    print(f"fleet gate OK: {d['value']} samples/sec, scale-out {ratio}x "
          f"(recorded only — {cores} core(s), floor needs >=4), "
          f"0 errors, 0 warm compiles")
PY

echo "== bench regression gate (history mode vs BENCH_BASELINE.json)"
rm -f /tmp/_bench_gate_history.json
BENCH_FORCE_CPU=1 BENCH_MODEL=history python bench.py \
    | tail -1 > /tmp/_bench_gate_history.json
python scripts/bench_gate.py /tmp/_bench_gate_history.json
python - <<'PY'
# ISSUE 19 acceptance: sampler + scrape plane within 3% of disabled
# throughput (interleaved trials on ONE warm fleet, medians), zero warm
# compiles, and the store stayed inside its documented byte budget.
import json

d = json.load(open("/tmp/_bench_gate_history.json"))
ratio = d["overhead_ratio"]
assert ratio <= 1.03, (
    f"history-on serving {ratio}x of disabled (>3% overhead): "
    f"on={d['value']} off={d['samples_per_sec_off']} samples/sec")
assert sum(d.get("warm_compiles") or [1]) == 0, d.get("warm_compiles")
assert d["history_bytes"] <= d["history_byte_budget"], d
print(f"history gate OK: on {d['value']} vs off "
      f"{d['samples_per_sec_off']} samples/sec ({ratio}x, <=1.03), "
      f"{d['history_series']} series / {d['history_samples_total']} "
      f"samples ingested, 0 warm compiles")
PY

echo "== tier-1 tests"
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit $rc
