"""The readers that came with ``nemotron3_nano_train_1chip`` (PR 30): the
scan kernels' share of the busy time and of their roofline, the expert
blocks' share by scope, and the two ratios of the expert layers' counters.
Synthetic traces: the kernels' names are what the v5e's trace carries
(``%ssd_scan_bwd.7``), the times are made up."""

import os

import pytest

from bench_presets import REPO
from benchmarks.harness import scopes as sc
from benchmarks.harness import trace as tr
from benchmarks.harness.discovery import load_module

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = (1, 8192, 64, 64, 8, 128, 128, 2)   # B T H P G N L itemsize
CALL = ('%{name} = bf16[1,8192,4096]{{2,1,0}} custom-call(bf16[1,8192,4096]'
        '{{2,1,0}} %a), custom_call_target="tpu_custom_call"')


def metric(name):
    return load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                    name + ".py"))


class Run:
    def __init__(self, trace, cell=None, peaks=V5E):
        self.trace, self.cell, self.peaks = trace, cell, peaks


class Cell:
    params = {"batch_per_chip": 1, "seq_len": 8192}
    sizes = {"mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
             "ssm_state_size": 128, "chunk_size": 128, "dtype": "bfloat16",
             "n_routed_experts": 8}


def window(*ops, end=10_000_000):
    return tr.TraceData([tr.DeviceTrace("d", ops=list(ops))], spans=[],
                        window=(0, end))


def kernel(name, start, end):
    return tr.Op(start, end, CALL.format(name=name), "pallas")


def test_ssd_scan_operations_and_bytes_come_from_the_shapes():
    roof = metric("ssd_scan_roofline")
    B, T, H, P, G, N, L, item = PUBLISHED
    chunks = T // L
    flops, moved = roof.flops_and_bytes("ssd_scan_fwd", *PUBLISHED)
    assert flops == chunks * (G * 2 * L * L * N
                              + H * (2 * L * L * P + 4 * L * N * P))
    assert flops == pytest.approx(27.92e9, rel=1e-3)
    # x B C in, y out (bfloat16); dt and its running sum in two layouts and
    # the state at each chunk's start (float32)
    assert moved == (T * (2 * H * P + 2 * G * N) * item + 4 * T * H * 4
                     + chunks * H * P * N * 4)
    bflops, bmoved = roof.flops_and_bytes("ssd_scan_bwd", *PUBLISHED)
    assert bflops == chunks * (G * 6 * L * L * N
                               + H * (4 * L * L * P + 10 * L * N * P))
    # x B C and dx dB dC, dy (bfloat16); dt and its sum in, their four
    # gradient arrays out, the saved states (float32)
    assert bmoved == (T * (3 * H * P + 4 * G * N) * item + 8 * T * H * 4
                      + chunks * H * P * N * 4)
    # both are bound by memory at these sizes (0.38 and 0.51 ms an event
    # against 0.14 and 0.34 ms of products at the bf16 peak)
    assert roof.least_seconds("ssd_scan_fwd", PUBLISHED, V5E) \
        == pytest.approx(moved / 819e9)
    assert roof.least_seconds("ssd_scan_bwd", PUBLISHED, V5E) \
        == pytest.approx(bmoved / 819e9)
    assert bmoved / 819e9 > bflops / 197e12


def test_scan_kernels_share_of_the_busy_time_and_of_their_roofline():
    roof, share = metric("ssd_scan_roofline"), metric("ssd_scan_time_share")
    fwd_least = roof.least_seconds("ssd_scan_fwd", PUBLISHED, V5E)
    bwd_least = roof.least_seconds("ssd_scan_bwd", PUBLISHED, V5E)
    fwd_ns, bwd_ns = int(4e9 * fwd_least), int(5e9 * bwd_least)
    other = tr.Op(0, 1_000_000, "%fusion.1 = f32[] fusion()", "mxu")
    trace = window(other,
                   kernel("ssd_scan_fwd.3", 1_000_000, 1_000_000 + fwd_ns),
                   kernel("ssd_scan_bwd.7", 3_000_000, 3_000_000 + bwd_ns))
    run = Run(trace, Cell)
    busy_ns = 1_000_000 + fwd_ns + bwd_ns
    assert share.read(run) == pytest.approx(
        100.0 * (fwd_ns + bwd_ns) / busy_ns)
    want = 100.0 * (fwd_least + bwd_least) / ((fwd_ns + bwd_ns) / 1e9)
    assert roof.read(run) == pytest.approx(want)
    assert 20.0 < want < 25.0
    # a program without the kernels (the parent, the CPU, a mesh): 0.0 of the
    # busy time, and no share of a roofline at all
    without = Run(window(other), Cell)
    assert share.read(without) == 0.0
    assert roof.read(without) is None
    # an untraced run reads nothing
    assert share.read(Run(None, Cell)) is None
    assert roof.read(Run(None, Cell)) is None


def test_expert_blocks_share_reads_the_vertex_scopes_of_e_blocks():
    reader = metric("expert_blocks_time_share")
    ops = [tr.Op(0, 400, "%fusion.1 = f32[] fusion()", "mxu"),
           tr.Op(400, 700, "%fusion.2 = f32[] fusion()", "mxu"),
           tr.Op(700, 900, "%fusion.3 = f32[] fusion()", "mxu"),
           tr.Op(900, 1000, "%copy.4 = f32[] copy()", "copy")]
    joined = sc.Scopes({
        "fusion.1": "jit(dl4j_graph_staged)/while/body/b0M_mixer/ssd_scan/x",
        "fusion.2": "jit(dl4j_graph_staged)/while/body/"
                    "transpose(jvp(b1E_mixer))/experts/ragged_dot",
        "fusion.3": "jit(dl4j_graph_staged)/while/body/b1E_norm/mul"})
    trace = window(*ops, end=1000)
    assert reader.share(trace, joined) == pytest.approx(0.5)
    assert reader.share(trace, sc.Scopes({})) == 0.0
    # XLA's grouped product carries no scope of the program: counted by name
    grouped = tr.Op(1000, 1500, CALL.format(name="ragged-dot-none.2"),
                    "pallas")
    trace = window(*ops, grouped, end=1500)
    assert sc.kernel_name(grouped.name) == "ragged-dot-none"
    assert reader.share(trace, joined) == pytest.approx(1000 / 1500)


def test_expert_counter_ratios_from_the_programs_registry(monkeypatch):
    from deeplearning4j_tpu.telemetry import registry
    from deeplearning4j_tpu.telemetry.device import LAYER_COUNTER_FAMILY

    rows, load = metric("moe_rows_per_token"), metric("moe_load_max_over_mean")
    assert rows.FAMILY == LAYER_COUNTER_FAMILY
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "_GLOBAL_REGISTRY", fresh)
    # a program without the counters: nothing, not 0
    assert rows.read(Run(None, Cell)) is None
    assert load.read(Run(None, Cell)) is None
    family = fresh.counter(LAYER_COUNTER_FAMILY, "test",
                           labelnames=("layer", "counter"))
    for layer, held, fullest in (("b1E_mixer", 3000, 500),
                                 ("b3E_mixer", 3144, 524)):
        family.labels(layer=layer, counter="rows_held").inc(held)
        family.labels(layer=layer, counter="rows_fullest").inc(fullest)
        family.labels(layer=layer, counter="tokens").inc(8192)
        family.labels(layer=layer, counter="rows_dropped").inc(0)
    assert rows.read(Run(None, Cell)) == pytest.approx(6144 / 16384)
    assert load.read(Run(None, Cell)) == pytest.approx(1024 * 8 / 6144)


def test_grouped_product_kernels_share_of_their_roofline(monkeypatch):
    from deeplearning4j_tpu.telemetry import registry
    from deeplearning4j_tpu.telemetry.device import LAYER_COUNTER_FAMILY

    roof = metric("grouped_matmul_roofline")

    class Hybrid(Cell):
        sizes = dict(Cell.sizes, hidden_size=2688, moe_intermediate_size=1856)

    # 3072 rows of [2688] against 8 matrices [2688, 1856] in bfloat16: the
    # products take longer than the bytes at the v5e's peaks
    flops, moved = roof.flops_and_bytes(3072, 2688, 1856, 8, 2)
    assert flops == 2 * 3072 * 2688 * 1856
    assert moved == 2 * (8 * 2688 * 1856 + 3072 * (2688 + 1856))
    least = roof.least_seconds(3072, 2688, 1856, 8, 2, V5E)
    assert least == pytest.approx(flops / 197e12) and least > moved / 819e9
    fresh = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "_GLOBAL_REGISTRY", fresh)
    ns = int(2e9 * least)
    trace = window(kernel("grouped_matmul_fwd.3", 0, ns),
                   kernel("grouped_matmul_drhs.9", 2 * ns, 3 * ns),
                   kernel("ssd_scan_fwd.1", 4 * ns, 5 * ns))
    # without the program's counters the rows are unknown: nothing
    assert roof.read(Run(trace, Hybrid)) is None
    family = fresh.counter(LAYER_COUNTER_FAMILY, "test",
                           labelnames=("layer", "counter"))
    family.labels(layer="b1E_mixer", counter="rows_held").inc(4 * 3072)
    family.labels(layer="b1E_mixer", counter="tokens").inc(4 * 8192)
    assert roof.read(Run(trace, Hybrid)) == pytest.approx(50.0, rel=1e-4)
    # no such kernel in the window (ragged_dot ran): nothing, never 0
    assert roof.read(Run(window(kernel("ssd_scan_fwd.1", 0, ns)),
                         Hybrid)) is None
    assert roof.read(Run(None, Hybrid)) is None
    # the expert blocks' share counts the kernels by name, as ragged-dot
    blocks = metric("expert_blocks_time_share")
    assert blocks.share(trace, sc.Scopes({})) == pytest.approx(2 / 3)


def test_first_gradient_is_read_from_adams_first_moment():
    import numpy as np

    gen = load_module(os.path.join(REPO, "benchmarks", "generators",
                                   "staged_training_gradcheck.py"))
    rng = np.random.default_rng(0)
    reference = {"a": {"W": rng.normal(size=(8, 4)), "bias": np.zeros(4)},
                 "b": {"W": rng.normal(size=(3,))}}
    exact = {v: {k: 0.1 * g for k, g in p.items()}
             for v, p in reference.items()}
    # mu = (1 - beta1) g after one step; a parameter whose reference
    # gradient is zero (a bias that only selects) is left out
    assert gen.gradient_distances(exact, 0.9, reference) == pytest.approx(
        {"a/W": 0.0, "b/W": 0.0}, abs=1e-12)
    # a step that left its state as it was reads 1
    unchanged = {v: {k: np.zeros_like(g) for k, g in p.items()}
                 for v, p in reference.items()}
    assert gen.gradient_distances(unchanged, 0.9, reference) \
        == pytest.approx({"a/W": 1.0, "b/W": 1.0})
    # a gradient with one leaf's sign wrong reads 2 there and 0 elsewhere
    wrong = dict(exact, b={"W": -exact["b"]["W"]})
    assert gen.gradient_distances(wrong, 0.9, reference) == pytest.approx(
        {"a/W": 0.0, "b/W": 2.0}, abs=1e-12)


def test_gradcheck_generator_reads_the_timed_nets_own_adam_state():
    import numpy as np

    from bench_presets import tiny_cell

    cell = tiny_cell("nemotron3_nano_train_1chip")
    gen, cfg = cell.generator_module(), cell.config_module()
    assert cell.generator == "staged_training_gradcheck"
    net = cfg.build(cell.sizes, 3)
    xs, ys = cfg.make_batches(cell.sizes, cell.params, 3, 2)
    loss, reference = cfg.reference_gradients(
        net.params, xs[0], ys[0], cell.sizes, ["b1E_mixer", "head"])
    first = net.fit_on_device(xs, ys, steps=1)
    assert float(first[0]) == pytest.approx(loss, rel=1e-3)
    mu, beta1 = gen.first_moments(net)
    assert beta1 == 0.9
    off = gen.gradient_distances(mu, beta1, reference)
    # the selection bias has no gradient and is left out; every other
    # parameter of the sampled layers is compared, and is close
    assert set(off) == {"b1E_mixer/W_down", "b1E_mixer/W_up", "b1E_mixer/Wr",
                        "b1E_mixer/Ws_down", "b1E_mixer/Ws_up", "head/W"}
    assert 0.0 < max(off.values()) < 0.1
    # after a second step the first moment is no single gradient any more
    net.fit_on_device(xs, ys, steps=1)
    later = gen.gradient_distances(gen.first_moments(net)[0], beta1, reference)
    assert min(later.values()) > max(off.values())
    assert np.isfinite(list(later.values())).all()
