"""xing4_29b_a4b: builder through the public API, what a sample is, model
FLOPs from the shapes, seeded token ids made on the device, and the plain
reference that ``correct`` is decided against.

The plain reference is float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` and shares no code with the
program: latent attention materialises its scores over the concatenated
``[nope, rope]`` width (in blocks of query rows, so that [heads, T, T] need
not fit beside a 12 GB net) with the rotary key repeated for every head, the
rotary embedding works on the interleaved pairs directly, the experts are a
Python loop over the experts held with a dense mask, the hyper-connection
maps normalise ``vec(X)`` first and the Sinkhorn normalisation is a loop over
``[n, n]`` matrices a token. It is given the program's share: the heads and
experts held, the vocabulary slice. Departures from the published description
are marked ``DEPARTURE``; what the description leaves open is under
``assumed`` in the configuration's file.
"""

from __future__ import annotations

import math

EPS_DEN = 1e-20   # the router's normalisation, as the model code has it


def builder_kwargs(sizes: dict) -> dict:
    """The sizes as ``models.xing4.xing4_conf`` names them."""
    keys = ("hidden_size", "vocab_size", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rope_scaling", "intermediate_size",
            "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts", "routed_scaling_factor", "norm_topk_prob",
            "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "rms_norm_eps")
    return dict(
        {k: sizes[k] for k in keys},
        n_dense=sizes["first_k_dense_replace"],
        n_expert=sizes["num_hidden_layers"] - sizes["first_k_dense_replace"],
        num_attention_heads=sizes["published"]["num_attention_heads"],
        heads_held=(sizes["heads_held_first"], sizes["num_attention_heads"]),
        n_routed_experts=sizes["router_width"],
        experts_held=(sizes["experts_held_first"], sizes["n_routed_experts"]),
        rescale_layers=sizes["published"]["num_hidden_layers"],
    )


def build(sizes: dict, seed: int):
    """The net as a user builds it: ``models.xing4.xing4_conf`` +
    ``ComputationGraph``; weights come from ``seed``."""
    from deeplearning4j_tpu import ComputationGraph
    from deeplearning4j_tpu.models.xing4 import xing4_conf
    from deeplearning4j_tpu.nn.updaters import UpdaterConfig

    conf = xing4_conf(
        dtype=sizes["dtype"], remat=sizes["remat"],
        updater=UpdaterConfig(updater=sizes["updater"],
                              learning_rate=sizes["learning_rate"]),
        seed=seed, **builder_kwargs(sizes))
    return ComputationGraph(conf).init()


def samples_per_example(sizes: dict, params: dict) -> int:
    return int(params["seq_len"])


def expected_first_loss(sizes: dict) -> float:
    return math.log(sizes["classes"])


def sublayers(sizes: dict) -> list:
    """``[(vertex prefix of the sublayer, of its hyper-connection), ...]``
    in order: ``("b0A", "b0H"), ("b1D", "b1H"), ("b2A", "b2H"), ("b3E",
    "b3H"), ...``."""
    dense = sizes["first_k_dense_replace"]
    kinds = "AD" * dense + "AE" * (sizes["num_hidden_layers"] - dense)
    return [(f"b{i}{k}", f"b{i}H") for i, k in enumerate(kinds)]


# ------------------------------------------------- operations from the shapes
def params_a_token_meets(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied with, by sublayer kind, for
    a hyper-connection's maps (``H``) and for the head (the embedding is a
    lookup). Of the routed experts a token meets ``top_k * held /
    router_width`` on average: the even share of its picks that land on the
    experts held here."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    attention = (d * sizes["q_lora_rank"] + sizes["q_lora_rank"] * heads * qk
                 + d * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
                 + sizes["kv_lora_rank"] * heads
                 * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
                 + heads * sizes["v_head_dim"] * d)
    expert = 3 * d * sizes["moe_intermediate_size"]
    landed = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
              / sizes["router_width"])
    n = sizes["hc_mult"]
    return {"A": attention, "D": 3 * d * sizes["intermediate_size"],
            "E": (d * sizes["router_width"]
                  + sizes["n_shared_experts"] * expert + landed * expert),
            "H": n * d * n * (2 + n), "head": d * sizes["vocab_size"]}


def stream_flops_per_token(sizes: dict) -> float:
    """Forward operations of one sublayer's stream arithmetic a token: the
    read (``n`` multiply-adds a feature) and the write (``n * n + n``)."""
    n, d = sizes["hc_mult"], sizes["hidden_size"]
    return 2.0 * d * (n + n * n + n)


def model_flops_per_sample(sizes: dict) -> float:
    """Forward + backward operations a trained token, from the shapes, the
    same whatever kernel runs and nothing recomputed: 6 x the matrix
    parameters the token meets, causal attention at ``train_seq_len`` (a
    token attends to half the sequence on average: a score product over
    ``nope + rope`` and a value product over ``v`` a head held), and the
    streams' reads and writes."""
    met = params_a_token_meets(sizes)
    kinds = [name[-1] for name, _ in sublayers(sizes)]
    params = sum(met[k] + met["H"] for k in kinds) + met["head"]
    attention = (kinds.count("A") * sizes["train_seq_len"]
                 * sizes["num_attention_heads"]
                 * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
                    + sizes["v_head_dim"]))
    streams = len(kinds) * stream_flops_per_token(sizes)
    return 6.0 * params + 3.0 * (attention + streams)


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

    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if w is None else y * w


def yarn_frequencies(sizes: dict):
    """``(inv_freq [rope/2], cos/sin scale, softmax scale)`` of the rotary
    embedding as the DeepSeek-V3 family's model code computes them from
    ``rope_scaling``."""
    import numpy as np

    dim, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    rs = sizes["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    def get_mscale(scale, mscale):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    j = np.arange(0, dim, 2, dtype=np.float64)
    freq_extra = 1.0 / base ** (j / dim)
    freq_inter = 1.0 / (factor * base ** (j / dim))
    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    inv_freq = freq_inter * (1 - keep) + freq_extra * keep
    magnitude = (get_mscale(factor, rs["mscale"])
                 / get_mscale(factor, rs["mscale_all_dim"]))
    scale = (sizes["qk_nope_head_dim"] + dim) ** -0.5
    if rs["mscale_all_dim"]:
        m = get_mscale(factor, rs["mscale_all_dim"])
        scale *= m * m
    return inv_freq.astype(np.float32), magnitude, scale


def _rotate(x, inv_freq, magnitude):
    """The rotary embedding of ``x`` [B, T, heads, dim] on its interleaved
    pairs: lanes ``(2j, 2j + 1)`` turn by ``t * inv_freq[j]``. (The model
    code de-interleaves first and rotates halves; queries and keys are
    permuted alike, so the scores are these.)"""
    import jax.numpy as jnp

    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = t[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * magnitude)[None, :, None, :]
    sin = (jnp.sin(ang) * magnitude)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def reference_attention(p, x, sizes, rows: int = 256):
    """Causal latent attention over the heads held, full scores of ``rows``
    query positions at a time. DEPARTURE: the heads held here only (their
    partial sum through ``W_o``)."""
    import jax
    import jax.numpy as jnp

    dn, dr, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    rank, eps = sizes["kv_lora_rank"], sizes["rms_norm_eps"]
    Bsz, T, _ = x.shape
    H = p["W_qb"].shape[1] // (dn + dr)
    inv_freq, magnitude, scale = yarn_frequencies(sizes)
    c_q = _rmsnorm(x @ p["W_qa"], p["q_norm"], eps)
    q = (c_q @ p["W_qb"]).reshape(Bsz, T, H, dn + dr)
    kva = x @ p["W_kva"]
    c_kv = _rmsnorm(kva[..., :rank], p["kv_norm"], eps)
    kv = (c_kv @ p["W_kvb"]).reshape(Bsz, T, H, dn + dv)
    k_rope = _rotate(kva[..., None, rank:], inv_freq, magnitude)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], inv_freq,
                                              magnitude)], axis=-1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_rope, (Bsz, T, H, dr))], axis=-1)
    v = kv[..., dn:]

    rows = min(rows, T)
    blocks = -(-T // rows)
    q = jnp.pad(q, ((0, 0), (0, blocks * rows - T), (0, 0), (0, 0)))

    @jax.checkpoint      # a gradient keeps no block of scores but its own
    def some_rows(block):
        qb, t0 = block
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        ok = (t0 + jnp.arange(rows)[:, None]) >= jnp.arange(T)[None, :]
        s = jnp.where(ok, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    outs = jax.lax.map(some_rows, (
        jnp.moveaxis(q.reshape(Bsz, blocks, rows, H, dn + dr), 1, 0),
        jnp.arange(blocks) * rows))
    out = jnp.moveaxis(outs, 0, 1).reshape(Bsz, blocks * rows, H * dv)[:, :T]
    return out @ p["W_o"]


def _gated(tokens, gate, up, down):
    import jax

    return (jax.nn.silu(tokens @ gate) * (tokens @ up)) @ down


def reference_dense(p, x, sizes):
    return _gated(x, p["W_gate"], p["W_up"], p["W_down"])


def reference_experts(p, x, sizes, shared: bool = True):
    """Sigmoid-routed gated experts: all ``router_width`` experts are
    scored, the ``top_k`` of largest score + bias chosen, their scores
    normalised and scaled; the experts held here are looped over with a
    dense mask, the shared expert added once."""
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
    def expert(gate, up, down, tokens, weight):
        return _gated(tokens, gate, up, down) * weight

    out = jnp.zeros_like(tokens)
    for e in range(p["W_up"].shape[0]):     # DEPARTURE: the share held here
        out = out + expert(p["W_gate"][e], p["W_up"][e], p["W_down"][e],
                           tokens, w[:, first + e:first + e + 1])
    if shared and "Ws_up" in p:
        out = out + _gated(tokens, p["Ws_gate"], p["Ws_up"], p["Ws_down"])
    return out.reshape(x.shape)


def reference_maps(p, X, sizes):
    """``(H_pre [.., n], H_post [.., n], H_res [.., n, n])`` of the streams
    ``X`` [.., n, D]."""
    import jax
    import jax.numpy as jnp

    n = sizes["hc_mult"]
    flat = X.reshape(X.shape[:-2] + (-1,))
    xn = _rmsnorm(flat, None, sizes["rms_norm_eps"])
    raw = xn @ p["P"]
    pre = p["a"][0] * raw[..., :n] + p["b"][:n]
    post = p["a"][1] * raw[..., n:2 * n] + p["b"][n:2 * n]
    res = (p["a"][2] * raw[..., 2 * n:] + p["b"][2 * n:]).reshape(
        raw.shape[:-1] + (n, n))
    m = jnp.exp(jnp.clip(res, sizes["mhc_h_res_clamp_min"],
                         sizes["mhc_h_res_clamp_max"]))
    for _ in range(sizes["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + sizes["hc_eps"])
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + sizes["hc_eps"])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


MIXERS = {"A": reference_attention, "D": reference_dense,
          "E": reference_experts}


def reference_sublayer(kind, maps, norm, mixer, X, sizes):
    """``X' = H_res X + H_post^T F(norm(H_pre X))`` over ``X`` [B, T, n, D]."""
    import jax.numpy as jnp

    pre, post, res = reference_maps(maps, X, sizes)
    h = jnp.einsum("bts,btsd->btd", pre, X)
    y = MIXERS[kind](mixer, _rmsnorm(h, norm["gamma"], sizes["rms_norm_eps"]),
                     sizes)
    return (jnp.einsum("btij,btjd->btid", res, X)
            + post[..., None] * y[..., None, :])


def _streams_in(embed_w, ids, sizes):
    import jax.numpy as jnp

    h = jnp.take(embed_w, ids, axis=0)
    return jnp.broadcast_to(h[..., None, :], h.shape[:-1]
                            + (sizes["hc_mult"], h.shape[-1]))


def _float32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  dict(tree))


def reference_hidden(net_params, ids, sizes):
    """The stack's output before the head, float32 [B, T, F]."""
    import jax.numpy as jnp

    p32 = _float32(net_params)
    X = _streams_in(p32["embed"]["W"], ids, sizes)
    for name, hc in sublayers(sizes):
        X = reference_sublayer(name[-1], p32[f"{hc}_maps"],
                               p32[f"{hc}_pre"], p32[f"{name}_mixer"], X,
                               sizes)
    return _rmsnorm(jnp.sum(X, axis=-2), p32["norm_f"]["gamma"],
                    sizes["rms_norm_eps"])


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
    parameters of ``vertices`` (a sublayer's ``_mixer``, its ``b<i>H_maps``
    and ``b<i>H_pre``, which holds its pre-norm's scale, ``norm_f``,
    ``head``), float32. Computed a sublayer at a
    time so that it fits beside a 12 GB net: the forward keeps every
    sublayer's input streams (on the host), then each sublayer is differentiated alone,
    last to first, from the gradient of its output (attention still its full
    scores, the Sinkhorn still its loop).

    A ``b<i>H_maps`` vertex gives ``P`` alone, not its three gate scalars
    ``a`` nor its 24 offsets ``b``. At seeded weights the streams are still
    near copies of one another, so the read map's gradient is zero (the
    pre-norm takes no notice of its input's scale) and the stream map's too
    (its rows sum to 1): three to eight orders under the write map's in this
    reference at the tests' sizes, at every depth. What is left of ``a`` and
    ``b`` is the write map's few numbers (1 and 4), each one sum over every
    token of terms of random sign that carry bfloat16 noise from the streams'
    inner products, beside the noise of the parts that should be zero. Its
    distance from this reference is a ratio with a denominator that is near
    zero on some seeds: ``a`` read 0.012 to 3.93 over five seeds on the v5e,
    ``b`` 0.006 to 0.504 over 14 (seed 1222730791 read 0.504 with every other
    parameter inside its usual range; my chip runs, PR 34). No limit holds
    such a reading and no lower precision can be told from it. ``P``'s
    gradient is made of the same per-token terms, each times that token's
    14336 normed features, so nothing cancels there: it read 0.037 to 0.049.
    ``chip_smoke.py`` leg G holds ``a`` and ``b`` at the block, with streams
    that differ and gates of order one, input rounded once for both sides
    (0.0021 sound, 0.0095 with the maps in bfloat16)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = sizes["rms_norm_eps"]
    p32 = _float32(net_params)

    def block(kind, maps, norm, mixer, X):
        with jax.default_matmul_precision("highest"):
            return reference_sublayer(kind, maps, norm, mixer, X, sizes)

    forward = jax.jit(block, static_argnums=0)

    @functools.partial(jax.jit, static_argnums=0)
    def backward(kind, maps, norm, mixer, X, dX):
        return jax.vjp(functools.partial(block, kind), maps, norm, mixer,
                       X)[1](dX)

    @jax.jit
    def head(norm, w, X, labels):
        def loss_of(norm, w, X):
            with jax.default_matmul_precision("highest"):
                return jnp.mean(reference_token_losses(
                    w["W"], _rmsnorm(jnp.sum(X, axis=-2), norm["gamma"], eps),
                    labels))
        return jax.value_and_grad(loss_of, argnums=(0, 1, 2))(norm, w, X)

    names = sublayers(sizes)
    inputs, X = [], _streams_in(p32["embed"]["W"], x, sizes)
    for name, hc in names:
        inputs.append(np.asarray(X))    # 0.47 GB a sublayer: kept off the chip
        X = forward(name[-1], p32[f"{hc}_maps"], p32[f"{hc}_pre"],
                    p32[f"{name}_mixer"], X)
    loss, (d_norm, d_head, dX) = head(p32["norm_f"], p32["head"], X, y)
    found = {"norm_f": d_norm, "head": d_head}
    for (name, hc), X in zip(reversed(names), reversed(inputs)):
        keys = (f"{hc}_maps", f"{hc}_pre", f"{name}_mixer")
        *grads, dX = backward(name[-1], *(p32[k] for k in keys),
                              jnp.asarray(X), dX)
        for left_out in ("a", "b"):       # see above
            grads[0].pop(left_out)
        for k, g in zip(keys, grads):     # kept off the chip
            found[k] = jax.tree_util.tree_map(np.asarray, g) \
                if k in vertices else None
    return float(loss), {v: found[v] for v in vertices}
