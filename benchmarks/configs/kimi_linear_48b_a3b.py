"""kimi_linear_48b_a3b: builder through the public API, what a sample is,
model FLOPs from the shapes, seeded token ids made on the device, and the
plain reference that ``correct`` is decided against.

The plain reference is float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` and shares no code with the
program: Kimi Delta Attention runs its delta rule as a ``lax.scan`` over
positions (no chunks, no triangular system: the state is decayed, read,
corrected and read again, one position at a time; for a gradient the scan is
taken in segments under ``jax.checkpoint``, so that 8192 states of 2 MiB need
not be kept), its short convolutions are sums over explicitly shifted copies,
latent attention materialises its scores over the concatenated ``[nope,
shared]`` width in blocks of query rows with the shared key part repeated for
every head, and the experts are a Python loop over the experts held with a
dense mask. It is given the program's share: the experts held, the vocabulary
slice. Departures from the published description are marked ``DEPARTURE``;
what the description leaves open is under ``assumed`` in the configuration's
file. One function here calls the program: ``recurrence_distances`` runs
``ops.kda.kda_recurrence`` beside the reference's delta rule, because that
comparison is what holds the recurrence's precision (``reference_gradients``
makes it on the way, and ``chip_smoke.py`` leg H with its control).
"""

from __future__ import annotations

import math

EPS_DEN = 1e-20   # the router's normalisation, as the model code has it
L2_EPS = 1e-6     # of the l2 norms of q and k, as the model code has it
SEGMENT = 64      # positions of the delta rule kept as one checkpoint


def builder_kwargs(sizes: dict) -> dict:
    """The sizes as ``models.kimi_linear.kimi_linear_conf`` names them."""
    keys = ("hidden_size", "vocab_size", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "intermediate_size", "num_experts_per_token",
            "moe_intermediate_size", "num_shared_experts",
            "routed_scaling_factor", "moe_renormalize", "rms_norm_eps")
    kda = sizes["linear_attn_config"]
    return dict(
        {k: sizes[k] for k in keys},
        mixers=sizes["mixers_run"], n_dense=sizes["first_k_dense_replace"],
        num_heads=kda["num_heads"], head_dim=kda["head_dim"],
        short_conv_kernel_size=kda["short_conv_kernel_size"],
        chunk=sizes["kda_chunk"],
        num_experts=sizes["router_width"],
        experts_held=(sizes["experts_held_first"], sizes["num_experts"]),
        rescale_layers=sizes["published"]["num_hidden_layers"],
    )


def build(sizes: dict, seed: int):
    """The net as a user builds it: ``models.kimi_linear.kimi_linear_conf`` +
    ``ComputationGraph``; weights come from ``seed``."""
    from deeplearning4j_tpu import ComputationGraph
    from deeplearning4j_tpu.models.kimi_linear import kimi_linear_conf
    from deeplearning4j_tpu.nn.updaters import UpdaterConfig

    conf = kimi_linear_conf(
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
    """The vertex prefix of every sublayer in order: ``b0K``, ``b1D``,
    ``b2K``, ``b3E``, ..., ``b6A``, ``b7E``, ...: a layer's mixer, then its
    feed-forward (dense in the leading layers)."""
    kinds = "".join(m + ("D" if i < sizes["first_k_dense_replace"] else "E")
                    for i, m in enumerate(sizes["mixers_run"]))
    assert len(kinds) == 2 * sizes["num_hidden_layers"]
    return [f"b{i}{k}" for i, k in enumerate(kinds)]


# ------------------------------------------------- operations from the shapes
def params_a_token_meets(sizes: dict) -> dict:
    """Matrix parameters one token is multiplied with, by sublayer kind, and
    for the head (the embedding is a lookup). Of the routed experts a token
    meets ``top_k * held / router_width`` on average: the even share of its
    picks that land on the experts held here."""
    d, kda = sizes["hidden_size"], sizes["linear_attn_config"]
    inner, rank = kda["num_heads"] * kda["head_dim"], kda["head_dim"]
    delta = (4 * d * inner + 2 * (d * rank + rank * inner)
             + d * kda["num_heads"]
             + 3 * kda["short_conv_kernel_size"] * inner)
    heads = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    latent = (d * heads * qk
              + d * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
              + sizes["kv_lora_rank"] * heads
              * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
              + heads * sizes["v_head_dim"] * d)
    expert = 3 * d * sizes["moe_intermediate_size"]
    landed = (sizes["num_experts_per_token"] * sizes["num_experts"]
              / sizes["router_width"])
    return {"K": delta, "A": latent, "D": 3 * d * sizes["intermediate_size"],
            "E": (d * sizes["router_width"]
                  + sizes["num_shared_experts"] * expert + landed * expert),
            "head": d * sizes["vocab_size"]}


def delta_rule_flops_per_token(sizes: dict) -> float:
    """Forward operations of one KDA sublayer's recurrence a token, as the
    chunk equations at ``kda_chunk`` need them, every head: the two score
    matrices and the triangular system over the earlier half of a chunk on
    average (``C/2`` columns of ``K``, ``K`` and ``V`` wide, and ``C/2`` of
    ``V`` for the output's own part), and three products with the ``K x V``
    state (the correction's read, the output's read, the update)."""
    kda = sizes["linear_attn_config"]
    K = V = kda["head_dim"]
    half = sizes["kda_chunk"] / 2.0
    return 2.0 * kda["num_heads"] * (half * (2 * K + 2 * V) + 3 * K * V)


def model_flops_per_sample(sizes: dict) -> float:
    """Forward + backward operations a trained token, from the shapes, the
    same whatever kernel runs and nothing recomputed: 6 x the matrix
    parameters the token meets, causal attention at ``train_seq_len`` in the
    latent-attention sublayers (a token attends to half the sequence on
    average: a score product over ``nope + shared`` and a value product over
    ``v`` a head), and the delta rule's own products."""
    met = params_a_token_meets(sizes)
    kinds = [name[-1] for name in sublayers(sizes)]
    params = sum(met[k] for k in kinds) + met["head"]
    attention = (kinds.count("A") * sizes["train_seq_len"]
                 * sizes["num_attention_heads"]
                 * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
                    + sizes["v_head_dim"]))
    delta = kinds.count("K") * delta_rule_flops_per_token(sizes)
    return 6.0 * params + 3.0 * (attention + delta)


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


def _short_conv(x, taps):
    """``y[t] = sum_j taps[j] * x[t - (K - 1) + j]`` over ``x`` [B, T, C],
    zeros before the start: each tap's copy shifted by hand."""
    import jax.numpy as jnp

    K, T = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(K):
        back = K - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :T - back]], axis=1)
        y = y + shifted * taps[j]
    return y


def delta_rule(q, k, v, g, beta, segment: int = SEGMENT):
    """``o`` [B, T, H, V]: the gated delta rule one position at a time, from
    a zero state ``S`` [B, H, K, V]: decay every row of ``S`` by its
    channel's ``exp(g)``, read ``k``'s prediction of ``v``, move ``S`` by
    ``beta`` times the error along ``k``, read ``q``. Positions in segments
    of ``segment`` under ``jax.checkpoint``: a gradient keeps one state a
    segment and recomputes the others."""
    import jax
    import jax.numpy as jnp

    Bsz, T, H, K = k.shape
    pad = (-T) % segment

    def position(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        S = S * jnp.exp(g_t)[..., None]
        seen = jnp.sum(S * k_t[..., None], axis=-2)          # S^T k
        S = S + (b_t[..., None] * (v_t - seen))[..., None, :] * k_t[..., None]
        return S, jnp.sum(S * q_t[..., None], axis=-2)       # S^T q

    @jax.checkpoint
    def some(S, inp):
        return jax.lax.scan(position, S, inp)

    def shaped(a):      # [B, T, ...] -> [segments, segment, B, ...]
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((-1, segment) + a.shape[1:])

    S0 = jnp.zeros((Bsz, H, K, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(some, S0, tuple(shaped(a)
                                        for a in (q, k, v, g, beta)))
    o = o.reshape((-1,) + o.shape[2:])[:T]
    return jnp.moveaxis(o, 0, 1)


def delta_rule_operands(p, x, sizes):
    """``(q, k, v, g, beta)`` as the recurrence takes them of ``x`` [B, T,
    F], by head: ``q`` not yet scaled, ``g`` the log-decay a channel."""
    import jax
    import jax.numpy as jnp

    kda = sizes["linear_attn_config"]
    H, D = kda["num_heads"], kda["head_dim"]
    Bsz, T, _ = x.shape
    heads = lambda a: a.reshape(Bsz, T, H, D)  # noqa: E731

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    q = unit(heads(jax.nn.silu(_short_conv(x @ p["W_q"], p["conv_q"]))))
    k = unit(heads(jax.nn.silu(_short_conv(x @ p["W_k"], p["conv_k"]))))
    v = heads(jax.nn.silu(_short_conv(x @ p["W_v"], p["conv_v"])))
    g = -jnp.exp(p["A_log"])[:, None] * heads(
        jax.nn.softplus((x @ p["W_fa"]) @ p["W_fb"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["W_beta"])
    return q, k, v, g, beta


def _q_scale(sizes) -> float:
    return sizes["linear_attn_config"]["head_dim"] ** -0.5


def reference_delta_attention(p, x, sizes):
    """Kimi Delta Attention over ``x`` [B, T, F], every head."""
    import jax

    Bsz, T, _ = x.shape
    q, k, v, g, beta = delta_rule_operands(p, x, sizes)
    o = delta_rule(q * _q_scale(sizes), k, v, g, beta)
    gate = jax.nn.sigmoid(
        ((x @ p["W_ga"]) @ p["W_gb"] + p["b_g"]).reshape(o.shape))
    o = _rmsnorm(o, p["norm_w"], sizes["rms_norm_eps"]) * gate
    return o.reshape(Bsz, T, -1) @ p["W_o"]


# The program's recurrence by itself against the delta rule above: the
# relative L2 distance of ``o`` and of the gradient of each operand, with
# ``q``, ``k``, ``v`` and the cotangent rounded to the compute dtype once for
# both sides. Each limit lies between what the program reads at 8192
# positions and what it reads with its running sums, solved system and carried
# states rounded to bfloat16 (my chip runs, PR 36, call 41: five sound readings
# and four of the control, a seed each, in PERF.md's findings of that PR). A
# float32 result (the gradients of ``g`` and ``beta``) read 0.91e-4 to 1.43e-4
# and 3.87e-3 to 6.37e-3; a result that comes back in bfloat16 (``o``, the
# gradients of ``q``, ``k``, ``v``) read 1.66e-3 every time, which is what
# rounding it costs, and 4.75e-3 to 6.84e-3.
RECURRENCE_RTOL = 7e-4
RECURRENCE_RTOL_BF16 = 2.8e-3


def recurrence_distances(operands, sizes, want=None):
    """``({name: (distance, limit)}, want)``: ``ops.kda.kda_recurrence``, as
    kernel selection resolves it for ``operands`` (``q``, ``k``, ``v`` in the
    program's compute dtype, ``g`` and ``beta`` float32, as a layer hands
    them over), against :func:`delta_rule` on the same values in float32:
    ``o`` and the gradient of every operand for one seeded cotangent, as
    relative L2 distances, each beside the limit of its own dtype
    (``RECURRENCE_RTOL``, ``RECURRENCE_RTOL_BF16``). ``want``: the delta
    rule's side, if a caller has it from an earlier call. The whole net's
    first gradient cannot tell the recurrence's precision from the rounding
    of every product around it (``PERF.md``); this can, so
    ``reference_gradients`` holds the net's first delta-rule sublayer to it
    before a step is timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.kda import kda_recurrence

    scale, chunk = _q_scale(sizes), int(sizes["kda_chunk"])
    v = operands[2]
    w = jax.random.normal(jax.random.PRNGKey(0), v.shape, jnp.float32)
    w = w.astype(v.dtype).astype(jnp.float32)

    def both(fn, args):
        def run(*a):
            out, pull = jax.vjp(fn, *a)
            return (out,) + pull(w.astype(out.dtype))
        return jax.block_until_ready(jax.jit(run)(*args))    # traced anew

    if want is None:
        with jax.default_matmul_precision("highest"):
            want = both(lambda q, *rest: delta_rule(q * scale, *rest),
                        tuple(a.astype(jnp.float32) for a in operands))
    got = both(lambda *a: kda_recurrence(*a, chunk=chunk, scale=scale),
               operands)
    off = {}
    for name, a, b in zip(("out", "d_q", "d_k", "d_v", "d_g", "d_beta"),
                          got, want):
        limit = (RECURRENCE_RTOL_BF16 if a.dtype == jnp.bfloat16
                 else RECURRENCE_RTOL)
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        off[name] = (float(np.linalg.norm(a - b) / np.linalg.norm(b)), limit)
    return off, want


def reference_latent_attention(p, x, sizes, rows: int = 256):
    """Causal latent attention without positions, every head, full scores of
    ``rows`` query positions at a time."""
    import jax
    import jax.numpy as jnp

    dn, ds, dv = (sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
                  sizes["v_head_dim"])
    rank, eps = sizes["kv_lora_rank"], sizes["rms_norm_eps"]
    H = sizes["num_attention_heads"]
    Bsz, T, _ = x.shape
    q = (x @ p["W_q"]).reshape(Bsz, T, H, dn + ds)
    kva = x @ p["W_kva"]
    c_kv = _rmsnorm(kva[..., :rank], p["kv_norm"], eps)
    kv = (c_kv @ p["W_kvb"]).reshape(Bsz, T, H, dn + dv)
    shared = jnp.broadcast_to(kva[..., None, rank:], (Bsz, T, H, ds))
    k = jnp.concatenate([kv[..., :dn], shared], axis=-1)
    v = kv[..., dn:]
    scale = (dn + ds) ** -0.5

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
        jnp.moveaxis(q.reshape(Bsz, blocks, rows, H, dn + ds), 1, 0),
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

    k, first = sizes["num_experts_per_token"], sizes["experts_held_first"]
    tokens = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(tokens @ p["Wr"])
    order = jnp.argsort(-(s + p["e_bias"]), axis=-1, stable=True)[:, :k]
    picked = jnp.zeros_like(s).at[
        jnp.arange(tokens.shape[0])[:, None], order].set(1.0)
    w = s * picked
    if sizes["moe_renormalize"]:
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


MIXERS = {"K": reference_delta_attention, "A": reference_latent_attention,
          "D": reference_dense, "E": reference_experts}


def reference_sublayer(kind, norm, mixer, x, sizes):
    """``x + F(rmsnorm(x))`` over ``x`` [B, T, F]."""
    return x + MIXERS[kind](
        mixer, _rmsnorm(x, norm["gamma"], sizes["rms_norm_eps"]), sizes)


def _float32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  dict(tree))


def reference_hidden(net_params, ids, sizes):
    """The stack's output before the head, float32 [B, T, F]."""
    import jax.numpy as jnp

    p32 = _float32(net_params)
    x = jnp.take(p32["embed"]["W"], ids, axis=0)
    for name in sublayers(sizes):
        x = reference_sublayer(name[-1], p32[f"{name}_norm"],
                               p32[f"{name}_mixer"], x, sizes)
    return _rmsnorm(x, p32["norm_f"]["gamma"], sizes["rms_norm_eps"])


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


# parameters of a few numbers a head or a channel whose gradient is one sum
# over every token of terms of either sign: a ratio to the reference's has a
# denominator near zero on some seeds (PERF.md, PR 34's lesson), so a
# sampled vertex gives its matrices and norm scales and not these
NOT_SAMPLED = ("A_log",)
# of an expert sublayer, the parameters whose gradient goes through the
# router's choice of 8 in 256: where bfloat16 activations move a token's
# eighth-best expert across a near tie, program and reference send the token
# to different experts, so these read 0.15-0.30 on every seed whatever the
# arithmetic does (PERF.md, PR 36), and a limit set over them holds nothing
# else. chip_smoke leg H holds them at the block, where both sides route alike
ROUTED = ("Wr", "W_gate", "W_up", "W_down")


def _hold_the_recurrence(p32, names, inputs, sizes) -> None:
    """The program's recurrence on the operands of the first delta-rule
    sublayer of ``names`` (from the reference's own forward pass, ``q``, ``k``
    and ``v`` rounded to the net's compute dtype) within its limits of the
    delta rule, or a ``RuntimeError`` before anything is timed; the readings
    go to standard error either way."""
    import sys

    import jax
    import jax.numpy as jnp

    at = next((i for i, name in enumerate(names) if name[-1] == "K"), None)
    if at is None:
        return
    name, cdt = names[at], jnp.dtype(sizes["dtype"])

    @jax.jit
    def operands(norm, mixer, h):
        with jax.default_matmul_precision("highest"):
            q, k, v, g, beta = delta_rule_operands(
                mixer, _rmsnorm(h, norm["gamma"], sizes["rms_norm_eps"]),
                sizes)
        return q.astype(cdt), k.astype(cdt), v.astype(cdt), g, beta

    off, _ = recurrence_distances(
        operands(p32[f"{name}_norm"], p32[f"{name}_mixer"], inputs[at]),
        sizes)
    said = ", ".join(f"{k} {d:.3g} (limit {l:g})" for k, (d, l) in off.items())
    print(f"# the recurrence of {name} alone off the delta rule: {said}",
          file=sys.stderr, flush=True)
    over = [k for k, (d, l) in off.items() if not d <= l]
    if over:
        raise RuntimeError(f"the program's recurrence is off the delta rule "
                           f"in {over}: {said}")


def reference_gradients(net_params, x, y, sizes: dict, vertices):
    """``(loss, {vertex: {parameter: gradient}})`` of one batch of ids at
    ``net_params``: the plain reference's loss differentiated for the
    parameters of ``vertices`` (a sublayer's ``_mixer`` or ``_norm``,
    ``norm_f``, ``head``), float32. Computed a sublayer at a time so that it
    fits beside an 11 GB net: the forward keeps every sublayer's input, then
    each sublayer is differentiated alone, last to first, from the gradient
    of its output (the delta rule still one position at a time, attention
    still its full scores, the experts still a loop). ``NOT_SAMPLED``
    parameters are left out, and an expert sublayer's ``ROUTED`` ones. On the
    way the program's recurrence is held to the delta rule by itself
    (:func:`_hold_the_recurrence`)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    eps = sizes["rms_norm_eps"]
    p32 = _float32(net_params)

    def block(kind, norm, mixer, h):
        with jax.default_matmul_precision("highest"):
            return reference_sublayer(kind, norm, mixer, h, sizes)

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

    names = sublayers(sizes)
    inputs, h = [], jnp.take(p32["embed"]["W"], x, axis=0)
    for name in names:
        inputs.append(h)                 # 75 MB a sublayer at 8192 x 2304
        h = forward(name[-1], p32[f"{name}_norm"], p32[f"{name}_mixer"], h)
    _hold_the_recurrence(p32, names, inputs, sizes)
    loss, (d_norm, d_head, dh) = head(p32["norm_f"], p32["head"], h, y)
    found = {"norm_f": d_norm, "head": d_head}
    for name, h in zip(reversed(names), reversed(inputs)):
        keys = (f"{name}_norm", f"{name}_mixer")
        *grads, dh = backward(name[-1], *(p32[k] for k in keys), h, dh)
        for k, g in zip(keys, grads):     # kept off the chip
            if k in vertices:
                out = NOT_SAMPLED + (ROUTED if name[-1] == "E" else ())
                found[k] = {n: np.asarray(a) for n, a in g.items()
                            if n not in out}
    return float(loss), {v: found[v] for v in vertices}
