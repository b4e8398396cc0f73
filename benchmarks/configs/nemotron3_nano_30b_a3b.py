"""nemotron3_nano_30b_a3b: builder through the public API, what a sample is,
model FLOPs from the shapes, seeded token ids made on the device, and the
plain reference that ``correct`` is decided against.

The plain reference is float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` and shares no code with the
program: the Mamba recurrence is the sequential ``lax.scan`` over positions
(never the chunked form), attention materialises its scores (in blocks of
query rows, so that [heads, T, T] need not fit beside a 12 GB net), the
experts are a loop over the experts held with a dense mask. It is given the
program's share: the experts held, the vocabulary slice. Departures from the
published description are marked ``DEPARTURE``.
"""

from __future__ import annotations

import math

EPS_DEN = 1e-20   # the router's normalisation, as the model code has it


def builder_kwargs(sizes: dict) -> dict:
    """The sizes as ``models.nemotron_h.nemotron_h_conf`` names them."""
    return dict(
        hidden_size=sizes["hidden_size"], vocab_size=sizes["vocab_size"],
        mamba_num_heads=sizes["mamba_num_heads"],
        mamba_head_dim=sizes["mamba_head_dim"], n_groups=sizes["n_groups"],
        ssm_state_size=sizes["ssm_state_size"],
        conv_kernel=sizes["conv_kernel"], chunk_size=sizes["chunk_size"],
        time_step_min=sizes["time_step_min"],
        time_step_max=sizes["time_step_max"],
        time_step_floor=sizes["time_step_floor"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], n_routed_experts=sizes["router_width"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=sizes[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=sizes["routed_scaling_factor"],
        norm_topk_prob=sizes["norm_topk_prob"],
        experts_held=(sizes["experts_held_first"], sizes["n_routed_experts"]),
        norm_eps=sizes["norm_eps"],
        rescale_layers=(sizes["published"]["num_hidden_layers"]
                        if sizes["rescale_prenorm_residual"] else 0),
    )


def build(sizes: dict, seed: int):
    """The net as a user builds it: ``models.nemotron_h.nemotron_h_conf`` +
    ``ComputationGraph``; weights come from ``seed``."""
    from deeplearning4j_tpu import ComputationGraph
    from deeplearning4j_tpu.models.nemotron_h import nemotron_h_conf
    from deeplearning4j_tpu.nn.updaters import UpdaterConfig

    conf = nemotron_h_conf(
        sizes["pattern_run"], dtype=sizes["dtype"], remat=sizes["remat"],
        updater=UpdaterConfig(updater=sizes["updater"],
                              learning_rate=sizes["learning_rate"]),
        seed=seed, **builder_kwargs(sizes))
    return ComputationGraph(conf).init()


def samples_per_example(sizes: dict, params: dict) -> int:
    return int(params["seq_len"])


def expected_first_loss(sizes: dict) -> float:
    return math.log(sizes["classes"])


# ------------------------------------------------- operations from the shapes
def params_a_token_meets(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied with, by block kind and for
    the head (the embedding is a lookup). Of the routed experts a token meets
    ``top_k * held / router_width`` on average: the even share of its picks
    that land on the experts held here."""
    d = sizes["hidden_size"]
    inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    bc = 2 * sizes["n_groups"] * sizes["ssm_state_size"]
    mamba = d * (2 * inner + bc + sizes["mamba_num_heads"]) + inner * d
    qo = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    attention = d * (qo + 2 * kv) + qo * d
    expert = 2 * d * sizes["moe_intermediate_size"]
    landed = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
              / sizes["router_width"])
    experts = (d * sizes["router_width"]
               + 2 * d * sizes["moe_shared_expert_intermediate_size"]
               + landed * expert)
    return {"M": mamba, "*": attention, "E": experts,
            "head": d * sizes["vocab_size"]}


def scan_flops_per_token(sizes: dict) -> float:
    """Forward operations of the chunked scan a token of one Mamba block: a
    group's C B^T scores, then a head's M x, C S and the state's update."""
    L, N = sizes["chunk_size"], sizes["ssm_state_size"]
    H, P, G = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
               sizes["n_groups"])
    return G * 2.0 * L * N + H * (2.0 * L * P + 4.0 * N * P)


def model_flops_per_sample(sizes: dict) -> float:
    """Forward + backward operations a trained token, from the shapes, the
    same whatever kernel runs and nothing recomputed: 6 x the matrix
    parameters the token meets, causal attention at ``train_seq_len`` (a
    token attends to half the sequence on average: 2 x 2 x T/2 x head_dim
    a query head forward), and the scan's own products."""
    met = params_a_token_meets(sizes)
    pattern = sizes["pattern_run"]
    params = sum(met[ch] for ch in pattern) + met["head"]
    attention = (pattern.count("*") * 2.0 * sizes["train_seq_len"]
                 * sizes["head_dim"] * sizes["num_attention_heads"])
    scan = pattern.count("M") * scan_flops_per_token(sizes)
    return 6.0 * params + 3.0 * (attention + scan)


def make_batches(sizes: dict, params: dict, seed: int, global_batch: int):
    """``(xs, ys)``: ``slots`` staged batches of int32 token ids ``[slots, B,
    T]`` from the vocabulary slice and the ids that follow them, made on the
    device in one jitted call. The text is a seeded random walk (each id the
    last plus 1, 2 or 3, modulo the slice), so there is something to learn:
    the best loss is ln 3 against ln(vocabulary) at the start."""
    import jax
    import jax.numpy as jnp

    s, b, t, v = (int(params["slots"]), int(global_batch),
                  int(params["seq_len"]), int(sizes["vocab_size"]))
    if t != int(sizes["train_seq_len"]):
        raise ValueError(
            f"the cell trains at seq_len {t}, the configuration counts its "
            f"attention FLOPs at train_seq_len {sizes['train_seq_len']}")

    @jax.jit
    def gen(key):
        k1, k2 = jax.random.split(key)
        first = jax.random.randint(k1, (s, b, 1), 0, v)
        step = jax.random.randint(k2, (s, b, t + 1), 1, 4)
        idx = ((first + jnp.cumsum(step, axis=-1)) % v).astype(jnp.int32)
        return idx[:, :, :-1], idx[:, :, 1:]

    return gen(jax.random.PRNGKey(seed))


# ----------------------------------------------------------- plain reference
def _rmsnorm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def reference_mamba(p, x, sizes, segment: int = 128):
    """The Mamba-2 mixer over ``x`` [B, T, F]: the recurrence position by
    position. The scan runs in segments of ``segment`` positions, each under
    ``jax.checkpoint``, only so that its gradient need not keep the state of
    every position (it is still one step a position, in order)."""
    import jax
    import jax.numpy as jnp

    H, P, G, N, K = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                     sizes["n_groups"], sizes["ssm_state_size"],
                     sizes["conv_kernel"])
    Bsz, T, _ = x.shape
    inner = H * P
    zxbcdt = x @ p["W_in"]
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-H], zxbcdt[..., -H:])
    # causal depthwise convolution, tap by tap
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + T] * p["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :inner].reshape(Bsz, T, G, H // G, P)
    Bm = xbc[..., inner:inner + G * N].reshape(Bsz, T, G, N)
    Cm = xbc[..., inner + G * N:].reshape(Bsz, T, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(Bsz, T, G, H // G)
    A = -jnp.exp(p["A_log"]).reshape(G, H // G)

    def step(S, inp):                       # S [B, G, H/G, P, N]
        x_t, dt_t, b_t, c_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return S, jnp.einsum("bghpn,bgn->bghp", S, c_t)

    @jax.checkpoint
    def run_segment(S, inp):
        return jax.lax.scan(step, S, inp)

    pad = (-T) % segment
    seq = [jnp.moveaxis(jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)),
                        1, 0) for a in (xs, dt, Bm, Cm)]
    seq = [a.reshape((-1, segment) + a.shape[1:]) for a in seq]
    S0 = jnp.zeros((Bsz, G, H // G, P, N), jnp.float32)
    _, ys = jax.lax.scan(run_segment, S0, tuple(seq))
    y = jnp.moveaxis(ys.reshape((-1,) + ys.shape[2:])[:T], 0, 1)
    y = y + xs * p["D"].reshape(G, H // G)[..., None]
    y = y.reshape(Bsz, T, inner) * jax.nn.silu(z)
    # the norm after the gate, over each of the G groups of channels
    yg = y.reshape(Bsz, T, G, inner // G)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                       + sizes["norm_eps"])
    return (yg.reshape(Bsz, T, inner) * p["norm_w"]) @ p["W_out"]


def reference_attention(p, x, sizes, rows: int = 256):
    """Causal grouped-query attention with the full scores of ``rows`` query
    positions at a time; no rotary embedding (the configuration's
    ``assumed`` says why), no bias, scale 1/sqrt(head_dim)."""
    import jax
    import jax.numpy as jnp

    H, Hkv, D = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    Bsz, T, _ = x.shape
    q = (x @ p["Wq"]).reshape(Bsz, T, Hkv, H // Hkv, D)
    k = (x @ p["Wk"]).reshape(Bsz, T, Hkv, D)
    v = (x @ p["Wv"]).reshape(Bsz, T, Hkv, D)

    rows = min(rows, T)
    blocks = -(-T // rows)
    q = jnp.pad(q, ((0, 0), (0, blocks * rows - T)) + ((0, 0),) * 3)

    @jax.checkpoint      # a gradient keeps no block of scores but its own
    def some_rows(block):
        qb, t0 = block
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, k) / math.sqrt(D)
        ok = (t0 + jnp.arange(rows)[:, None]) >= jnp.arange(T)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(s, axis=-1), v)

    outs = jax.lax.map(some_rows, (
        jnp.moveaxis(q.reshape(Bsz, blocks, rows, Hkv, H // Hkv, D), 1, 0),
        jnp.arange(blocks) * rows))
    out = jnp.moveaxis(outs, 0, 1).reshape(Bsz, blocks * rows, H * D)[:, :T]
    return out @ p["Wo"]


def reference_experts(p, x, sizes, shared: bool = True):
    """Sigmoid-routed experts: all ``router_width`` experts are scored, the
    ``top_k`` of largest score + bias chosen, their scores normalised and
    scaled; the experts held here are looped over with a dense mask, the
    shared expert added once."""
    import jax
    import jax.numpy as jnp

    k, first = sizes["num_experts_per_tok"], sizes["experts_held_first"]
    tokens = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(tokens @ p["Wr"])
    order = jnp.argsort(-(s + p["e_bias"]), axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(s).at[
        jnp.arange(tokens.shape[0])[:, None], order].set(1.0)
    w = s * picked
    if sizes["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + EPS_DEN)
    w = w * sizes["routed_scaling_factor"]

    @jax.checkpoint      # a gradient keeps no expert's hidden rows but its own
    def expert(up, down, tokens, weight):
        return (_relu2(tokens @ up) @ down) * weight

    out = jnp.zeros_like(tokens)
    for e in range(p["W_up"].shape[0]):     # DEPARTURE: the share held here
        out = out + expert(p["W_up"][e], p["W_down"][e], tokens,
                           w[:, first + e:first + e + 1])
    if shared and "Ws_up" in p:
        out = out + _relu2(tokens @ p["Ws_up"]) @ p["Ws_down"]
    return out.reshape(x.shape)


MIXERS = {"M": reference_mamba, "A": reference_attention,
          "E": reference_experts}


def reference_hidden(net_params, ids, sizes):
    """The stack's output before the head, float32 [B, T, F]."""
    import jax
    import jax.numpy as jnp

    p32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 dict(net_params))
    h = jnp.take(p32["embed"]["W"], ids, axis=0)
    eps = sizes["norm_eps"]

    for i, ch in enumerate(sizes["pattern_run"]):
        kind = "A" if ch == "*" else ch
        name = f"b{i}{kind}"
        normed = _rmsnorm(h, p32[f"{name}_norm"]["gamma"], eps)
        h = h + MIXERS[kind](p32[f"{name}_mixer"], normed, sizes)
    return _rmsnorm(h, p32["norm_f"]["gamma"], eps)


def reference_token_losses(head_w, h, labels, rows: int = 2048):
    """-log softmax(h @ W)[label] of every position, ``rows`` at a time."""
    import jax
    import jax.numpy as jnp

    flat, lab = h.reshape(-1, h.shape[-1]), labels.reshape(-1)
    out = []
    for r0 in range(0, flat.shape[0], rows):
        logits = flat[r0:r0 + rows] @ head_w
        lse = jax.nn.logsumexp(logits, axis=-1)
        out.append(lse - jnp.take_along_axis(
            logits, lab[r0:r0 + rows, None], axis=-1)[:, 0])
    return jnp.concatenate(out)


def reference_loss(net_params, net_state, x, y, sizes: dict) -> float:
    """Mean cross-entropy a token of one batch of ids at ``net_params``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss(p, ids, labels):
        with jax.default_matmul_precision("highest"):
            h = reference_hidden(p, ids, sizes)
            w = jnp.asarray(p["head"]["W"], jnp.float32)
            return jnp.mean(reference_token_losses(w, h, labels))

    return float(loss(dict(net_params), x, y))


def reference_gradients(net_params, x, y, sizes: dict, vertices):
    """``(loss, {vertex: {parameter: gradient}})`` of one batch of ids at
    ``net_params``: the plain reference's loss differentiated for the
    parameters of ``vertices`` (blocks' norms and mixers, ``norm_f``,
    ``head``), float32. Computed a block at a time so that it fits beside a
    12 GB net: the forward keeps every block's input, then each block is
    differentiated alone, last to first, from the gradient of its output (the
    recurrence still one step a position, attention still its full scores)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = sizes["norm_eps"]
    p32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                 dict(net_params))

    def block(kind, norm, mixer, h):
        with jax.default_matmul_precision("highest"):
            return h + MIXERS[kind](mixer, _rmsnorm(h, norm["gamma"], eps),
                                    sizes)

    forward = jax.jit(block, static_argnums=0)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(kind, norm, mixer, h, dh):
        return jax.vjp(functools.partial(block, kind), norm, mixer, h)[1](dh)

    @jax.jit
    def head(norm, w, h, labels):
        def loss_of(norm, w, h):
            with jax.default_matmul_precision("highest"):
                return jnp.mean(reference_token_losses(
                    w["W"], _rmsnorm(h, norm["gamma"], eps), labels))
        return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(norm, w, h)

    names = [f"b{i}{'A' if ch == '*' else ch}"
             for i, ch in enumerate(sizes["pattern_run"])]
    inputs, h = [], jnp.take(p32["embed"]["W"], x, axis=0)
    for name in names:
        inputs.append(h)
        h = forward(name[-1], p32[f"{name}_norm"], p32[f"{name}_mixer"], h)
    loss, (d_norm, d_head, dh) = head(p32["norm_f"], p32["head"], h, y)
    found = {"norm_f": d_norm, "head": d_head}
    for name, h in zip(reversed(names), reversed(inputs)):
        found[f"{name}_norm"], found[f"{name}_mixer"], dh = backward(
            name[-1], p32[f"{name}_norm"], p32[f"{name}_mixer"], h, dh)
        for v in (f"{name}_norm", f"{name}_mixer"):   # kept off the chip
            found[v] = jax.tree_util.tree_map(np.asarray, found[v]) \
                if v in vertices else None
    return float(loss), {v: found[v] for v in vertices}
