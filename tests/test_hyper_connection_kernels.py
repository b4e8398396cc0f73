"""The Mosaic kernels of the hyper-connected residual
(``ops/hyper_connections.py``) in interpret mode against the jax.numpy
``reference`` variant of the same layer objects: forward and every gradient,
float32 and bfloat16, two and four streams, a row count that is no whole
number of row tiles, under ``jax.checkpoint``; and what the
``hyper_connection`` selection site says for which shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import hyper_connections as hc
from deeplearning4j_tpu.ops import hyper_connections as hk
from deeplearning4j_tpu.ops import kernel_select as ks
from deeplearning4j_tpu.ops import select_hyper_connection_variant

B, T, D = 2, 100, 256     # 200 token rows: two tiles of 128, the second short
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.fixture(autouse=True)
def _selection():
    ks.reset()
    yield
    ks.reset()


def run(variant, fn, *args):
    """``fn(*args)`` with the ``hyper_connection`` site held to ``variant``
    (the kernels in interpret mode off the TPU)."""
    ks.set_force_available(True)
    ks.set_site_override("hyper_connection", variant)
    try:
        return fn(*args)
    finally:
        ks.set_site_override("hyper_connection", None)
        ks.set_force_available(False)


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    off = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert off < tol, f"{what}: {off:.3g} of {tol}"


def operands(n, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    f = jnp.float32
    x = jax.random.normal(k[0], (B, T, n * D), f).astype(dtype)
    maps = jax.random.uniform(k[1], (B, T, n * (n + 2)), f)
    y = jax.random.normal(k[2], (B, T, D), f).astype(dtype)
    gamma = 1.0 + 0.1 * jax.random.normal(k[3], (D,), f)
    return x, maps, y, gamma, k[4], k[5]


def piece(op, n, norm):
    """``(fn, argument names)`` of one piece through its layer object; the
    gradient is taken of a weighted sum of the output."""
    if op == "maps":
        layer = hc.HyperConnectionMapsLayer(n_streams=n)
        return (lambda x, P, a, b: layer.apply(
            {"P": P, "a": a, "b": b}, x, {})[0]), ("x", "P", "a", "b")
    if op == "read":
        vertex = hc.HyperConnectionVertex(op="read", n_streams=n,
                                          norm_eps=1e-6 if norm else 0.0)
        if norm:
            return (lambda x, maps, gamma: vertex.apply(
                {"gamma": gamma}, [x, maps], {})[0]), ("x", "maps", "gamma")
        return (lambda x, maps: vertex.apply({}, [x, maps], {})[0]), \
            ("x", "maps")
    vertex = hc.HyperConnectionVertex(op="write", n_streams=n)
    return (lambda x, maps, y: vertex.apply({}, [x, maps, y], {})[0]), \
        ("x", "maps", "y")


def piece_args(op, n, dtype, norm):
    x, maps, y, gamma, k1, k2 = operands(n, dtype)
    if op == "maps":
        layer = hc.HyperConnectionMapsLayer(n_streams=n)
        p = layer.init_params(k1, InputType.recurrent(n * D, T))
        # gates of order one: the maps depend on the token
        return (x, p["P"].astype(jnp.float32),
                jnp.asarray([0.7, -0.4, 0.9], jnp.float32),
                p["b"].astype(jnp.float32))
    if op == "read":
        return (x, maps, gamma) if norm else (x, maps)
    return x, maps, y


CASES = [(op, n, dtype, norm)
         for op, norms in (("maps", [False]), ("read", [False, True]),
                           ("write", [False]))
         for n in (2, 4) for dtype in ("float32", "bfloat16")
         for norm in norms]


@pytest.mark.parametrize("op,n,dtype,norm", CASES)
def test_kernels_match_the_reference_forward_and_every_gradient(
        op, n, dtype, norm):
    fn, names = piece(op, n, norm)
    args = piece_args(op, n, jnp.dtype(dtype), norm)
    want = run("reference", fn, *args)
    got = run("fused", fn, *args)
    assert got.dtype == want.dtype
    tol = TOL[dtype] if op != "maps" else 5e-5 if dtype == "float32" else 2e-4
    close(got, want, tol, f"{op} forward")
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)  # noqa: E731
    grad = jax.grad(loss, argnums=tuple(range(len(args))))
    want_g = run("reference", grad, *args)
    got_g = run("fused", grad, *args)
    for name, g, r in zip(names, got_g, want_g):
        assert g.dtype == r.dtype
        close(g, r, TOL[dtype] if name in ("x", "y") else 2e-4,
              f"{op} d{name}")
    # the kernels ran: one selection a piece, fused, with its row tile
    log = [r for r in ks.selection_log() if r["variant"] == "fused"]
    assert log and all(r["site"] == "hyper_connection"
                       and r["ctx"]["op"] == op and r["row_tile"] == 128
                       for r in log)


@pytest.mark.parametrize("op,norm", [("maps", False), ("read", True),
                                     ("read", False), ("write", False)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_under_checkpoint_the_gradients_are_the_same(op, norm, dtype):
    fn, names = piece(op, 4, norm)
    args = piece_args(op, 4, jnp.dtype(dtype), norm)
    w = jax.random.normal(jax.random.PRNGKey(9), fn(*args).shape, jnp.float32)
    argnums = tuple(range(len(args)))
    plain = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                     argnums=argnums)
    kept = jax.grad(lambda *a: jnp.sum(
        jax.checkpoint(fn)(*a).astype(jnp.float32) * w), argnums=argnums)
    for name, g, r in zip(names, run("fused", kept, *args),
                          run("fused", plain, *args)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32), name)


@pytest.mark.parametrize("op,calls", [("read", 1), ("write", 1), ("maps", 2)])
def test_the_forward_kernel_runs_again_under_checkpoint_only_for_the_maps(
        op, calls):
    """The residuals of the read and of the write are their inputs: the
    forward that ``jax.checkpoint`` re-runs before the backward pass is dead
    code there. The projection's result feeds the Sinkhorn's backward pass,
    so its kernel runs twice."""
    fn, _ = piece(op, 4, op == "read")
    args = piece_args(op, 4, jnp.dtype("bfloat16"), op == "read")
    grad = jax.grad(lambda *a: jnp.sum(
        jax.checkpoint(fn)(*a).astype(jnp.float32) ** 2))

    def kernels(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                kernels(sub, found)
        return found

    from jax._src.interpreters import partial_eval as pe

    closed = run("fused", jax.make_jaxpr(grad), *args)
    live, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    found = kernels(live, [])
    name = {"maps": "hc_maps", "read": "hc_read", "write": "hc_write"}[op]
    assert found.count(name + "_fwd") == calls
    assert found.count(name + "_bwd") == 1


@pytest.mark.parametrize("op", ["maps", "read"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_handing_on_adds_the_later_readers_cotangent_in_the_kernel(op, dtype):
    """``x`` taken from the function that hands it on: what its later
    readers send back is added inside the backward kernel, and the gradient
    is that of reading the same ``x`` twice."""
    dt = jnp.dtype(dtype)
    x, maps, y, gamma, k1, _ = operands(4, dt)
    flat = lambda a: a.reshape(-1, a.shape[-1])  # noqa: E731
    x, maps = flat(x), flat(maps)
    later = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)
    if op == "maps":
        P = 0.05 * jax.random.normal(k1, (x.shape[1], 24), jnp.float32)
        assert [o.shape for o in hk.hc_project(x, P, 4)] == [(24, 200),
                                                             (1, 200)]
        handing = lambda x: hk.hc_project_handing_on(x, P, 4)  # noqa: E731
        plain = lambda x: hk.hc_project(x, P, 4)  # noqa: E731
    else:
        handing = lambda x: hk.hc_read_handing_on(  # noqa: E731
            x, maps, gamma, 4, 1e-6)
        plain = lambda x: (hk.hc_read(x, maps, gamma, 4, 1e-6),)  # noqa: E731
    mix = lambda outs: sum(jnp.sum(o.astype(jnp.float32) ** 2)  # noqa: E731
                           for o in outs)

    def handed(x):
        *outs, on = handing(x)
        return mix(outs) + jnp.sum(on.astype(jnp.float32) * later)

    def twice(x):
        return mix(plain(x)) + jnp.sum(x.astype(jnp.float32) * later)

    got, want = jax.grad(handed)(x), jax.grad(twice)(x)
    assert got.dtype == want.dtype == dt
    close(got, want, TOL[dtype], "dx")
    # the kernel took the cotangent seen so far as an operand tied to dX
    text = str(jax.make_jaxpr(jax.grad(handed))(x))
    assert "input_output_aliases=((5, 0),)" in text if op == "maps" \
        else "input_output_aliases=((4, 0),)" in text


@pytest.mark.parametrize("remat", [False, True])
def test_a_graph_of_hyper_connected_sublayers_trains_the_same_on_the_kernels(
        remat):
    """Two sublayers through ``ComputationGraph``: the maps and the read
    hand the streams on, the loss and every gradient equal the reference
    variant's."""
    from deeplearning4j_tpu import ComputationGraph
    from deeplearning4j_tpu.models.xing4 import xing4_conf

    def net():
        conf = xing4_conf(
            n_dense=1, n_expert=0, hidden_size=128, vocab_size=64, seq_len=24,
            num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            intermediate_size=32, hc_mult=2, hc_sinkhorn_iters=3,
            remat=remat, seed=3)
        return ComputationGraph(conf).init()

    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 64)
    labels = jnp.roll(ids, -1, axis=1)

    def loss_and_gradients(variant):
        model = net()
        return run(variant, jax.value_and_grad(
            lambda p: model.loss_fn(p, [ids], [labels], train=True)),
            model.params)

    # the suite runs under x64, where a float32 net computes in float64
    with jax.enable_x64(False):
        want_loss, want = loss_and_gradients("reference")
        got_loss, got = loss_and_gradients("fused")
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    fused = {(r["ctx"]["op"], r["variant"]) for r in ks.selection_log()
             if r["site"] == "hyper_connection"}
    assert {(op, "fused") for op in ("maps", "read", "write")} <= fused
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree_util.tree_leaves(got)):
        close(g, w, 2e-4, jax.tree_util.keystr(path))


def test_ten_sublayers_of_one_shape_share_one_lowering_a_kernel():
    x, maps, y, gamma, *_ = operands(4, jnp.bfloat16)
    fn, _ = piece("write", 4, False)

    def stack(x, maps, y):
        for _ in range(3):
            x = fn(x, maps, y)
        return jnp.sum(x.astype(jnp.float32))

    text = run("fused", lambda: jax.jit(jax.value_and_grad(stack)).lower(
        x, maps, y).as_text())
    for entry in ("_write_fwd_call", "_write_bwd_call"):
        assert text.count(f"func.func private @{entry}") == 1
        assert text.count(f"call @{entry}") == 3


def test_three_bfloat16_pieces_sum_to_the_float32_value():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 24), jnp.float32) \
        * jnp.exp(3.0 * jax.random.normal(jax.random.PRNGKey(1), (64, 24),
                                          jnp.float32))
    pieces = jax.jit(lambda a: hk._split(a, 3))(a)
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    np.testing.assert_array_equal(
        np.asarray(sum(p.astype(jnp.float32) for p in pieces)), np.asarray(a))


CELL = dict(N=8192, n=4, D=3584, itemsize=2)


@pytest.mark.parametrize("op", ["maps", "read", "write"])
def test_the_site_takes_the_kernels_at_the_cells_shape(op):
    ks.set_force_available(True)
    assert select_hyper_connection_variant(op, **CELL) == "fused"
    rec = ks.selection_log()[-1]
    assert rec["site"] == "hyper_connection" and rec["reason"] == "auto"
    assert rec["ctx"] == dict(CELL, op=op)
    assert rec["row_tile"] == hk.hc_row_tile(op, 4, 3584, 2) == 128
    assert rec["predicted_s"]["fused"] < rec["predicted_s"]["reference"]


@pytest.mark.parametrize("why", ["cpu", "lanes", "partitioned", "float64",
                                 "many_streams", "mode", "helpers_off"])
def test_the_site_falls_to_the_reference(why, monkeypatch):
    shape = dict(CELL)
    if why != "cpu":
        ks.set_force_available(True)
    if why == "lanes":
        shape["D"] = 3584 + 64
    if why == "float64":
        shape["itemsize"] = 8
    if why == "many_streams":       # 3 x 48 pieces do not fit a lane tile
        shape["n"] = 6
    if why == "mode":
        ks.set_mode("reference")
    if why == "helpers_off":
        from deeplearning4j_tpu import ops

        monkeypatch.setattr(ops, "_FORCED", False)
    if why == "partitioned":
        with ks.partitioned_program():
            got = select_hyper_connection_variant("write", **shape)
    else:
        got = select_hyper_connection_variant("write", **shape)
    assert got == "reference"
    rec = ks.selection_log()[-1]
    if why in ("lanes", "partitioned", "float64", "many_streams"):
        assert rec["reason"] == "fallback" and rec["infeasible"] == ["fused"]
    if why == "partitioned":
        assert rec["ctx"]["partitioned"] is True


@pytest.mark.parametrize("op", ["maps", "read", "write"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_tile_of_128_tokens_fits_the_vmem_budget_at_the_cells_widths(
        op, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    assert hk.hc_row_tile(op, 4, 3584, itemsize) == 128
    assert hk.hc_footprint(op, 4, 3584, itemsize) <= 64 << 20
    # four streams of 16384 do not: the site gives way
    assert hk.hc_layout_ok(4, 16384, itemsize)
    assert hk.hc_row_tile(op, 4, 16384, itemsize) is None
    ks.set_force_available(True)
    assert select_hyper_connection_variant(op, 8192, 4, 16384, itemsize) \
        == "reference"
