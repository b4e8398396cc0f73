"""The seq-fused LSTM kernels compiled by the TPU's own compiler for a v5e
that is described, not attached: what interpret mode cannot show (Mosaic's
tiling rules, the VMEM a kernel may use against the ``vmem_limit_bytes`` the
kernels reckon for themselves). Nothing runs, so nothing here is a time.

All such compiles live in this one file and describe the topology inside a
fixture: only the worker that is given the file loads the TPU's library."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("T,B,H,dtype,masked,block", [
    (256, 64, 512, "bfloat16", False, 8),   # charrnn_train_1chip's layers
    (256, 64, 512, "bfloat16", True, 8),    # a padded batch of them
    (256, 64, 512, "float32", False, 8),    # chip_smoke's f32: 43 MiB of VMEM
    (50, 32, 256, "bfloat16", False, 5),    # a TBPTT segment
    (251, 64, 512, "bfloat16", False, 1),   # prime T: one step a grid step
])
def test_seq_kernels_compile_for_the_v5e_within_their_own_vmem_limit(
        one_chip, monkeypatch, T, B, H, dtype, masked, block):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert pk._seq_time_block(T, B, H, dt.itemsize) == block
    s = lambda *shape: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (s(T, B, 4 * H), s(B, H), s(B, H), s(H, 4 * H), s(H), s(H), s(H))
    mask = s(T, B, 1)

    def fused(mask, *a):
        if masked:
            return pk.fused_lstm_sequence_masked(a[0], mask, *a[1:],
                                                 "tanh", "sigmoid")
        return pk.fused_lstm_sequence(*a, "tanh", "sigmoid")

    def loss(mask, *a):
        ys, hT, cT = fused(mask, *a)
        return (jnp.sum(ys.astype(jnp.float32) ** 2)
                + jnp.sum(hT.astype(jnp.float32))
                + jnp.sum(jnp.tanh(cT.astype(jnp.float32))))

    grad = jax.jit(jax.grad(loss, argnums=tuple(range(1, 8))))
    # the suite runs under x64 (conftest), the chip's programs do not: a
    # 64-bit block index is nothing Mosaic lowers
    with jax.enable_x64(False):
        text = grad.lower(mask, *args).compile().as_text()
        lean = jax.jit(fused).lower(mask, *args).compile().as_text()
    prefix = "lstm_seq_masked_" if masked else "lstm_seq_"
    assert text.count("tpu_custom_call") == 2
    assert prefix + "fwd" in text and prefix + "bwd" in text
    assert lean.count("tpu_custom_call") == 1 and "lstm_seq_lean" in lean


@pytest.mark.parametrize("B,T,H,P,G,N,chunk,dtype", [
    (1, 8192, 64, 64, 8, 128, 128, "bfloat16"),  # nemotron3_nano_train_1chip
    (1, 8192, 64, 64, 8, 128, 128, "float32"),   # chip_smoke's f32
    (2, 1000, 64, 64, 8, 128, 128, "bfloat16"),  # T padded to whole chunks
    (1, 512, 16, 64, 8, 128, 128, "bfloat16"),   # two heads a group: one pack
])
def test_ssd_scan_kernels_compile_for_the_v5e_at_the_published_shapes(
        one_chip, monkeypatch, B, T, H, P, G, N, chunk, dtype):
    from deeplearning4j_tpu.ops import ssd_scan as ss

    monkeypatch.setattr(ss, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert ss.ssd_layout_ok(chunk, P, N, H // G)
    assert ss.ssd_fits(chunk, P, N, H // G, dt.itemsize)
    s = lambda shape, d=dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, d, sharding=one_chip)
    args = (s((B, T, H, P)), s((B, T, H), jnp.float32), s((H,), jnp.float32),
            s((B, T, G, N)), s((B, T, G, N)))

    def loss(*a):
        y = ss.ssd_scan_fused(*a, chunk)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    with jax.enable_x64(False):
        text = grad.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


_KDA_KERNELS = ("kda_fwd", "kda_bwd", "kda_scores_fwd", "kda_scores_bwd")
_KERNEL_NAME = re.compile(r'kernel_name\s*=\s*"?(kda_\w+)')


def _kda_args(one_chip, B, T, H, K, V, dtype):
    """``q``, ``k``, ``v`` in ``dtype``, the log-decays and ``beta`` in
    float32, as a layer hands them over."""
    s = lambda shape, d=dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, d, sharding=one_chip)
    return (s((B, T, H, K)), s((B, T, H, K)), s((B, T, H, V)),
            s((B, T, H, K), jnp.float32), s((B, T, H), jnp.float32))


def _lowered_gradient(fn, args):
    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    # a function of its own each time: jit would hand back the first trace
    with jax.enable_x64(False):
        return jax.jit(lambda *a: jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
            *a)).lower(*args).as_text()


@pytest.mark.parametrize("B,T,H,K,V,chunk,dtype", [
    (1, 8192, 32, 128, 128, 64, "bfloat16"),   # kimi_linear_train_1chip
    (1, 8192, 32, 128, 128, 64, "float32"),    # chip_smoke's f32
    (2, 1000, 4, 128, 128, 64, "bfloat16"),    # T padded to whole chunks
    (1, 512, 2, 128, 256, 32, "bfloat16"),     # values two lane tiles wide
])
def test_kda_kernels_compile_for_the_v5e_at_the_published_shapes(
        one_chip, monkeypatch, B, T, H, K, V, chunk, dtype):
    """``kda_fwd`` / ``kda_bwd`` and the score kernels ``kda_scores_fwd`` /
    ``kda_scores_bwd`` with their float32 products at ``highest`` through
    Mosaic: a compile that succeeds held them inside the
    ``vmem_limit_bytes`` their footprints state."""
    from deeplearning4j_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert kda.kda_layout_ok(chunk, K, V)
    assert kda.kda_fits(chunk, K, V, dt.itemsize)
    args = _kda_args(one_chip, B, T, H, K, V, dt)

    def loss(*a):
        o = kda.kda_fused(*a, chunk=chunk, scale=K ** -0.5)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    with jax.enable_x64(False):
        text = grad.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    assert {name: len(re.findall(rf"%{name}[.\d]* = ", text))
            for name in _KDA_KERNELS} == {name: 1 for name in _KDA_KERNELS}


def test_kda_fused_program_holds_no_pairwise_array(one_chip, monkeypatch):
    """The ``fused`` gradient lowered for a described v5e, so that the
    kernels are custom calls and not interpret-mode HLO: no ``[.., 16, 16,
    128]`` array of every pair's factor is in its text, where
    ``kda_chunked``'s, the control, has one."""
    from deeplearning4j_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    args = _kda_args(one_chip, 1, 1024, 4, 128, 128, jnp.bfloat16)
    pairwise = re.compile(r"x16x16x128xf32>")

    def text(fn):
        return _lowered_gradient(
            lambda *a: fn(*a, chunk=64, scale=128 ** -0.5), args)

    assert pairwise.search(text(kda.kda_chunked))
    fused = text(kda.kda_fused)
    assert "kda_scores_fwd" in fused and "kda_scores_bwd" in fused
    assert not pairwise.search(fused)


def test_kda_kernels_are_lowered_once_a_program(one_chip, monkeypatch):
    """The gradient of four ``jax.checkpoint``ed sublayers, as the engine's
    ``remat`` runs them, lowers each of the four kernels once: every call
    site shares the jitted call's one trace, and JAX lowers a kernel of one
    trace once and hands every caller that lowering (the forward kernels
    stand in the forward pass and in the recomputed forward, with one body
    between them). Without the ``jax.jit`` around the calls, the control,
    every call site traces and lowers its kernels anew: that is what set-up
    time paid for each sublayer."""
    from jax._src.pallas.mosaic import lowering as mosaic

    from deeplearning4j_tpu.ops import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    lowered = collections.Counter()
    real = mosaic.lower_jaxpr_to_module

    def counted(*a, **kw):
        module = real(*a, **kw)
        lowered[str(module.operation.attributes["sym_name"]).strip('"')] += 1
        return module

    monkeypatch.setattr(mosaic, "lower_jaxpr_to_module", counted)
    args = _kda_args(one_chip, 1, 1024, 4, 128, 128, jnp.bfloat16)

    def sublayer(q, k, v, g, beta):
        return kda.kda_fused(q, k, v, g, beta, chunk=64, scale=128 ** -0.5)

    def four(q, k, v, g, beta):
        for i in range(4):      # a function of its own, as each layer is
            with jax.named_scope(f"b{i}K_mixer"):
                v = jax.checkpoint(lambda *a: sublayer(*a))(q, k, v, g, beta)
        return v

    text = _lowered_gradient(four, args)
    assert lowered == {name: 1 for name in _KDA_KERNELS}
    bodies = collections.defaultdict(set)
    for line in text.splitlines():
        if "tpu_custom_call" in line and _KERNEL_NAME.search(line):
            bodies[_KERNEL_NAME.search(line).group(1)].add(
                re.search(r'backend_config = "([^"]*)', line).group(1))
    assert {name: len(b) for name, b in bodies.items()} == {
        name: 1 for name in _KDA_KERNELS}
    lowered.clear()
    monkeypatch.setattr(kda, "_scores_call", kda._scores_call.__wrapped__)
    monkeypatch.setattr(kda, "_kda_call", kda._kda_call.__wrapped__)
    _lowered_gradient(four, args)
    assert all(lowered[name] >= 4 for name in _KDA_KERNELS), lowered


def test_kda_site_records_which_form_the_scores_take(one_chip, monkeypatch):
    """The site's record says what formed the scores: ``kernel`` where it
    resolves ``fused`` (and the program then holds the score kernels),
    ``jnp`` on the reference."""
    from deeplearning4j_tpu.ops import kda
    from deeplearning4j_tpu.ops import kernel_select as ks

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    args = _kda_args(one_chip, 1, 1024, 4, 128, 128, jnp.bfloat16)
    ks.reset()
    try:
        for mode, scores in (("fused", "kernel"), ("reference", "jnp")):
            with ks.forced_mode(mode), jax.enable_x64(False):
                text = jax.jit(lambda *a: kda.kda_recurrence(
                    *a, chunk=64)).lower(*args).as_text()
            (rec,) = [r for r in ks.selection_log()
                      if r["site"] == "kda_recurrence"]
            assert rec["variant"] == mode and rec["scores"] == scores
            assert ("kda_scores_fwd" in text) == (mode == "fused")
            ks.reset()
    finally:
        ks.reset()


@pytest.mark.parametrize("M,K,N,E,dtype", [
    (14336, 2688, 1856, 8, "bfloat16"),   # nemotron3_nano_train_1chip: up
    (14336, 1856, 2688, 8, "bfloat16"),   # ... and down
    (51200, 2688, 1856, 8, "bfloat16"),   # the buffer for all that can land
    (14336, 2688, 1856, 8, "float32"),    # chip_smoke's f32
    (512, 64, 32, 2, "bfloat16"),         # widths short of a lane tile
])
def test_grouped_matmul_kernels_compile_for_the_v5e_at_the_published_shapes(
        one_chip, monkeypatch, M, K, N, E, dtype):
    from deeplearning4j_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert gm.gmm_layout_ok(M, K, N) and gm.gmm_fits(K, N, dt.itemsize)
    s = lambda shape, d=dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, d, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        group, _, _, padded = gm.aligned_layout(sizes, gm.ROW_TILE, M)
        out = gm.grouped_matmul_fused(lhs, rhs, group, padded, jnp.float32)
        return jnp.sum(out ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)))
    with jax.enable_x64(False):
        text = grad.lower(s((M, K)), s((E, K, N)),
                          s((E,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("fwd", "dlhs", "drhs"):
        assert "grouped_matmul_" + name in text


@pytest.mark.parametrize("B,H,Hkv,T,D,dtype,causal,masked,latent", [
    (1, 32, 2, 8192, 128, "bfloat16", True, False, None),  # nemotron3_nano_train_1chip
    (1, 32, 2, 8192, 128, "bfloat16", False, True, None),  # every tile, a key mask
    (2, 4, 4, 256, 64, "float32", True, True, None),       # chip_smoke's f32
    (2, 4, 2, 1000, 64, "bfloat16", True, False, None),    # T padded to 1024
    (2, 4, 2, 100, 64, "bfloat16", True, False, None),     # one tile short of a lane tile
    # xing4_train_1chip: 4 heads held, scores over 128 + a rotary 64 whose
    # key every head shares, values of 128
    (1, 4, 4, 8192, 128, "bfloat16", True, False, (64, 128)),
    (1, 4, 4, 8192, 128, "bfloat16", False, True, (64, 128)),
    (2, 4, 4, 300, 128, "float32", True, True, (64, 128)),  # chip_smoke's f32
    (2, 4, 2, 1000, 192, "bfloat16", True, False, (0, 128)),  # d_qk != d_v alone
])
def test_flash_kernels_compile_for_the_v5e_at_their_default_tiles(
        one_chip, monkeypatch, B, H, Hkv, T, D, dtype, causal, masked, latent):
    """The tiles ``default_blocks`` picks fit the compiler's default scoped
    VMEM beside the strips (no call states a limit), the traced loop bounds
    lower, and bfloat16 operands reach the MXU as they are. ``latent``:
    ``(rotary width, value width)`` of a call whose score and value products
    differ in size."""
    import importlib

    fa = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    s = lambda shape, d=dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, d, sharding=one_chip)
    d_rope, d_v = latent or (0, D)
    args = [s((B, H, T, D)), s((B, Hkv, T, D)), s((B, Hkv, T, d_v))]
    if d_rope:
        args += [s((B, H, T, d_rope)), s((B, 1, T, d_rope))]
    if masked:
        args.append(s((B, T), jnp.float32))

    def loss(q, k, v, *rest):
        rest = list(rest)
        mask = rest.pop() if masked else None
        out = fa.flash_attention(q, k, v, causal=causal, key_mask=mask,
                                 q_rope=rest[0] if rest else None,
                                 k_rope=rest[1] if rest else None)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=tuple(range(5 if d_rope else 3))))
    # a float32 contract precision on bfloat16 operands is nothing Mosaic
    # takes: the kernels' products state their own
    with jax.enable_x64(False), jax.default_matmul_precision("float32"):
        text = grad.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert name in text
    assert "vmem_limit_bytes" not in text


_HLO_LINE = re.compile(r"^\s*(ROOT\s+)?%(\S+) = .*? ([a-z-]+)\(([^)]*)\)")
_TIED = re.compile(r"\{(\d)\}: \((\d), \{\}\)")   # {result}: (operand, {})


@pytest.mark.parametrize("N,n,D,dtype", [
    (8192, 4, 3584, "bfloat16"),   # xing4_train_1chip's streams
    (8192, 4, 3584, "float32"),    # chip_smoke's f32
    (1000, 2, 256, "bfloat16"),    # two streams, rows padded to whole tiles
])
def test_hyper_connection_kernels_compile_for_the_v5e_as_a_sublayer_runs_them(
        one_chip, monkeypatch, N, n, D, dtype):
    """The six kernels within the VMEM limit each reckons for itself, as one
    remat'd sublayer chains them: the maps and the read hand the streams on,
    so their backward kernels add onto the cotangent seen so far where it
    lies and no add over the streams is left; the re-run forward of the read
    and of the write is dead code, the projection's runs again."""
    from deeplearning4j_tpu.ops import hyper_connections as hk

    monkeypatch.setattr(hk, "_interpret", lambda: False)
    dt = jnp.dtype(dtype)
    assert hk.hc_layout_ok(n, D, dt.itemsize)
    s = lambda shape, d=dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, d, sharding=one_chip)
    f32, m = jnp.float32, n * (n + 2)
    args = (s((N, n * D)), s((n * D, m), f32), s((N, m), f32), s((D,), f32),
            s((N, D)))

    def normed_projection(x, P):   # its backward needs the kernel's results
        xp, ms, x = hk.hc_project_handing_on(x, P, n)
        return (xp * jax.lax.rsqrt(ms + 1e-6)).T, x

    def loss(x, P, maps, gamma, y):
        raw, x = jax.checkpoint(normed_projection)(x, P)
        h, x = jax.checkpoint(lambda x, maps, gamma: hk.hc_read_handing_on(
            x, maps, gamma, n, 1e-6))(x, maps + raw, gamma)
        out = jax.checkpoint(lambda x, maps, y: hk.hc_write(x, maps, y, n))(
            x, maps * raw, y + h)
        return jnp.sum(out.astype(f32) ** 2)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
    with jax.enable_x64(False):
        text = grad.lower(*args).compile().as_text()
    calls = {name: len(re.findall(rf"%{name}[.\d]* = ", text))
             for name in ("hc_maps_fwd", "hc_maps_bwd", "hc_read_fwd",
                          "hc_read_bwd", "hc_write_fwd", "hc_write_bwd")}
    assert calls == {"hc_maps_fwd": 2, "hc_maps_bwd": 1, "hc_read_fwd": 1,
                     "hc_read_bwd": 1, "hc_write_fwd": 1, "hc_write_bwd": 1}
    for name, operand in (("hc_maps_bwd", 5), ("hc_read_bwd", 4)):
        line = next(ln for ln in text.splitlines()
                    if re.match(rf"\s*(ROOT\s+)?%{name}[.\d]* = ", ln))
        assert (0, operand) in {(int(r), int(o))
                                for r, o in _TIED.findall(line)}
    streams = f"{dt.name.replace('bfloat', 'bf').replace('float', 'f')}" \
        f"[{N},{n * D}]"
    assert not [ln for ln in text.splitlines()
                if re.match(rf"\s*(ROOT\s+)?%\S+ = {re.escape(streams)}\S* "
                            r"(add|fusion)\(", ln) and "add" in ln.split("=")[0]]


def _copies_between_adam_and_the_carry(text: str) -> int:
    """Copies of a loop's carried buffer on the way into an ``adam_update``
    call, or of such a call's result on the way into the body's root tuple
    (XLA puts the copy a fresh result forces on either side of the call),
    bitcasts and tuple elements looked through."""
    ins, roots = {}, set()
    for line in text.splitlines():
        m = _HLO_LINE.match(re.sub(r"/\*index=\d+\*/", "", line))
        if m:
            ins[m.group(2)] = (m.group(3), re.findall(r"%([^\s,]+)",
                                                      m.group(4)))
            if m.group(1) and m.group(3) == "tuple":
                roots.add(m.group(2))
    through = ("bitcast", "get-tuple-element", "reshape")

    def origin(name):
        while name in ins and ins[name][0] in through:
            name = ins[name][1][0]
        return name

    def users(name):
        for user, (op, operands) in ins.items():
            if name in operands:
                yield from users(user) if op in through else [user]

    adam = lambda name: name.startswith("adam_update")  # noqa: E731
    return sum(
        1 for name, (op, operands) in ins.items() if op == "copy" and (
            (adam(origin(operands[0])) and roots & set(users(name)))
            or (ins.get(origin(operands[0]), ("",))[0] == "parameter"
                and any(map(adam, users(name))))))


def test_adam_kernel_in_a_loop_writes_its_carry_where_it_lies(
        one_chip, monkeypatch):
    """The staged step's shape of use: the moments and the parameter carried
    by a ``fori_loop`` whose arguments are donated. Every result of every
    ``adam_update`` call is tied to an operand, and no copy stands between
    the carry and a call. With the aliases taken off the same call, which is
    the kernel as it was before PR 33, XLA copies each moment of each leaf on
    its way from carry to carry: the reader must find those, or it proves
    nothing."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(pk, "_interpret", lambda: False)
    leaves = [(8, 2688, 1856), (2688, 16384)]   # swapped; as it lies

    def loop(ms, vs, ps, n):
        def body(i, carry):
            out = []
            for m, v, p in zip(*carry):
                g = jnp.sin(p) * (i + 1)
                u, m, v = pk.fused_adam_update(
                    g, m, v, 1e-3, 0.1, 0.001, 0.9, 0.999, 1e-8)
                out.append((m, v, p + u))
            return tuple(list(leaf) for leaf in zip(*out))
        return jax.lax.fori_loop(0, n, body, (ms, vs, ps))

    tree = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
            for shape in leaves]
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def compiled_text():
        # a function of its own each time: jit would hand back the first trace
        with jax.enable_x64(False):
            return jax.jit(lambda *a: loop(*a), donate_argnums=(0, 1, 2)
                           ).lower(tree, tree, tree, n).compile().as_text()

    text = compiled_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == len(leaves)
    for call in calls:
        pairs = _TIED.findall(call.split("output_to_operand_aliasing=")[1]
                              .split(")}")[0] + ")")
        assert sorted(pairs) == [("0", "0"), ("1", "1"), ("2", "2")]
    assert _copies_between_adam_and_the_carry(text) == 0

    real = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, input_output_aliases=None, **kw: real(*a, **kw))
    before = compiled_text()
    assert "output_to_operand_aliasing" not in before
    assert _copies_between_adam_and_the_carry(before) == 2 * len(leaves)
