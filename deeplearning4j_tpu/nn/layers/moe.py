"""Mixture-of-Experts layer with expert parallelism.

No counterpart exists in the reference (2016) — like attention, this extends
the framework per the distributed-first design requirement (the driver's
tp/pp/dp/sp/EP sharding axes). The design is TPU-native Switch/Mesh-TF
routing: top-k gating, capacity-bucketed dense dispatch (one-hot position
within each expert's token buffer built from a cumulative sum — no
data-dependent shapes, everything einsum), expert FFNs evaluated as one
batched einsum over the expert dimension, then a weighted combine.

Expert parallelism is pure GSPMD: the expert-stacked weights [E, F, H] shard
dim 0 over an "expert" mesh axis (parallel/sharding.py ``expert_axis``), and
XLA inserts the dispatch/combine all-to-alls from the einsum sharding — no
hand-written collectives (SURVEY.md §5.8's design rule).

Tokens routed past an expert's capacity are dropped by the combine (their MoE
contribution is zero); the default residual connection keeps their
representation flowing — the standard Switch-Transformer treatment.

``DroplessExpertsLayer`` stands beside it, not in it: sigmoid scores with a
selection bias, no capacity and no dropped token, experts computed as grouped
matrix products over rows sorted by expert, and a chip's share of the experts
(``experts_held_*``). Nothing of the capacity path's dispatch survives in it
(the [N, E, C] one-hots, the softmax gates, the per-expert biases, the GSPMD
expert axis), so one class with both would be two bodies under a flag; the two
share :func:`expert_row_counts`, what their diagnostics count rows with.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..conf.inputs import InputType
from .base import BaseLayer, Params, State, register_layer, maybe_dropout


def expert_row_counts(assignments, n_experts: int):
    """Rows each of ``n_experts`` experts is given by ``assignments`` (any
    shape of expert indices; an index outside ``[0, n_experts)`` counts for
    no expert): the one counting function under ``load_balance_stats`` and
    the dropless layer's on-device counters."""
    flat = jnp.asarray(assignments).reshape(-1)
    inside = (flat >= 0) & (flat < n_experts)
    return jnp.bincount(jnp.where(inside, flat, n_experts),
                        length=n_experts + 1)[:n_experts].astype(jnp.int32)


@register_layer
@dataclass
class MixtureOfExpertsLayer(BaseLayer):
    """Top-k routed expert FFN block over [B, T, F] (or [B, F]) inputs."""

    n_out: int = 0
    n_experts: int = 4
    hidden: int = 0  # expert FFN hidden width (default 4*n_out)
    top_k: int = 1  # 1 = Switch routing, 2 = GShard-style
    capacity_factor: float = 1.25
    residual: bool = True  # x + moe(x); requires n_out == n_in
    expert_activation: str = "relu"

    @property
    def is_recurrent(self) -> bool:
        return False  # shape-agnostic over leading dims

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        if self.residual and n_in != self.n_out:
            raise ValueError(
                f"residual MoE needs n_in == n_out, got {n_in} != {self.n_out}"
            )
        h = self.hidden or 4 * self.n_out
        e = self.n_experts
        kg, k1, k2 = jax.random.split(key, 3)
        return {
            "Wg": self._init_weight(kg, (n_in, e), n_in, e),
            "W1": self._init_weight(k1, (e, n_in, h), n_in, h),
            "b1": self._init_bias((e, h)),
            "W2": self._init_weight(k2, (e, h, self.n_out), h, self.n_out),
            "b2": self._init_bias((e, self.n_out)),
        }

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        from ..activations import get_activation  # noqa: PLC0415

        lead = x.shape[:-1]
        f = x.shape[-1]
        tokens = x.reshape(-1, f)  # [N, F]
        n = tokens.shape[0]
        e = self.n_experts
        capacity = self._capacity(n)

        # padded timesteps ([B,T] mask) must not claim expert capacity or
        # contribute output — flatten the mask alongside the tokens
        token_mask = None
        if mask is not None and x.ndim == 3 and mask.ndim == 2:
            token_mask = mask.reshape(-1).astype(jnp.int32)  # [N]

        logits = tokens @ params["Wg"]  # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)

        # top-k dispatch: iteratively take the best expert, build its
        # capacity-bucketed one-hot dispatch, then mask it out and repeat.
        dispatch = jnp.zeros((n, e, capacity), x.dtype)
        combine = jnp.zeros((n, e, capacity), x.dtype)
        remaining = probs
        # position of each token within its expert's buffer must count ALL
        # tokens assigned so far across the k rounds
        expert_fill = jnp.zeros((e,), jnp.int32)
        for _ in range(self.top_k):
            idx = jnp.argmax(remaining, axis=-1)  # [N]
            gate = jnp.take_along_axis(remaining, idx[:, None], axis=-1)[:, 0]
            onehot = jax.nn.one_hot(idx, e, dtype=jnp.int32)  # [N, E]
            if token_mask is not None:
                onehot = onehot * token_mask[:, None]  # pad tokens: no slot
            pos = jnp.cumsum(onehot, axis=0) - 1 + expert_fill[None, :]  # [N, E]
            expert_fill = expert_fill + onehot.sum(axis=0)
            within = (pos < capacity) & (onehot > 0)
            pos_onehot = jax.nn.one_hot(
                jnp.where(within, pos, capacity), capacity + 1, dtype=x.dtype
            )[..., :capacity]  # [N, E, C], rows past capacity all-zero
            dispatch = dispatch + pos_onehot
            combine = combine + pos_onehot * gate[:, None, None]
            remaining = remaining * (1 - onehot.astype(remaining.dtype))

        act = get_activation(self.expert_activation)
        expert_in = jnp.einsum("nec,nf->ecf", dispatch, tokens)  # [E, C, F]
        hcur = act(jnp.einsum("ecf,efh->ech", expert_in, params["W1"])
                   + params["b1"][:, None, :])
        expert_out = (jnp.einsum("ech,eho->eco", hcur, params["W2"])
                      + params["b2"][:, None, :])  # [E, C, O]
        out = jnp.einsum("nec,eco->no", combine, expert_out)  # [N, O]
        if self.residual:
            out = out + tokens
        out = out.reshape(lead + (self.n_out,))
        out = maybe_dropout(out, self.dropout, train, rng)
        return self._activate(out), state

    def _capacity(self, n_tokens: int) -> int:
        """One formula shared by apply() and the diagnostics."""
        return max(1, int(self.capacity_factor * n_tokens * self.top_k
                          / self.n_experts))

    def load_balance_stats(self, params, x) -> dict:
        """Routing diagnostics over UNMASKED tokens — all top_k assignments
        counted with apply()'s capacity formula (fractions sum to top_k);
        the host-side analog of an aux balance loss, call outside jit. For
        padded batches pass only the real tokens (apply()'s mask path
        excludes pad tokens from dispatch)."""
        tokens = jnp.asarray(x).reshape(-1, x.shape[-1])
        probs = jax.nn.softmax(tokens @ params["Wg"], axis=-1)
        counts = jnp.zeros((self.n_experts,), jnp.int32)
        remaining = probs
        for _ in range(self.top_k):
            idx = jnp.argmax(remaining, axis=-1)
            counts = counts + expert_row_counts(idx, self.n_experts)
            remaining = remaining * (1 - jax.nn.one_hot(idx, self.n_experts,
                                                        dtype=remaining.dtype))
        cap = self._capacity(tokens.shape[0])
        dropped = jnp.maximum(counts - cap, 0).sum()
        return {"expert_fraction": counts / tokens.shape[0],
                "dropped_tokens": int(dropped), "capacity": cap}


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


@register_layer
@dataclass
class DroplessExpertsLayer(BaseLayer):
    """Sigmoid-routed experts without capacity, holding a share of them.

    Over ``tokens`` [N, F] (any leading dims are flattened):

        s = sigmoid(float32(tokens) @ float32(Wr))            [N, n_experts]
        chosen = the top_k experts by s + e_bias              (bias selects only)
        w = s[chosen] / sum(s[chosen]) * routed_scaling       (norm_topk_prob)
        out = sum_k w_k * expert_k(token) + shared_expert(token)

    with ``expert(x) = act(x @ W_up) @ W_down`` (no bias), or with ``gated``
    ``expert(x) = (act(x @ W_gate) * (x @ W_up)) @ W_down``: ``W_gate`` and
    ``W_up`` are two stacks and two grouped products, and the shared expert
    is gated likewise (``Ws_gate``).
    ``experts_held_first`` / ``experts_held_count`` (0: all) name the experts
    whose weights live here: the router scores all ``n_experts``, and only the
    held experts' part of the result is computed; what the others would add is
    left out (the sum over every share, with the shared expert counted once,
    is the whole layer). There is no capacity: the rows that landed here are
    sorted by expert and both products run as grouped matrix products over
    them (site ``grouped_matmul``: Mosaic kernels over row tiles that each
    belong to one expert, or ``jax.lax.ragged_dot``). Shapes stay static by
    sizing the row buffer for four times the even share and, when more rows
    land (``lax.cond``), for the most that can.

    ``state["counters"]`` (int32 [4]) counts, since the dispatch began: rows
    that landed on held experts, the fullest held expert's rows (summed over
    steps), tokens routed, rows dropped (always 0). ``fit_on_device`` zeroes
    it before a dispatch and publishes it after (``telemetry/device.py``).
    """

    n_out: int = 0
    n_experts: int = 128
    top_k: int = 6
    hidden: int = 0               # a routed expert's width
    shared_hidden: int = 0        # the shared expert's width; 0: none
    experts_held_first: int = 0
    experts_held_count: int = 0   # 0: all n_experts
    routed_scaling: float = 1.0
    norm_topk_prob: bool = True
    expert_activation: str = "relu2"
    gated: bool = False           # act(x W_gate) * (x W_up) before W_down
    init_std: float = 0.02
    rescale_layers: int = 0       # > 0: down projections at init_std / sqrt(it)

    PARAM_ROLES = {"Ws_up": "ffn_up", "Ws_gate": "ffn_up",
                   "Ws_down": "ffn_down"}
    FLOAT32_PARAMS = ("Wr", "e_bias")   # the router scores in float32
    COUNTERS = ("rows_held", "rows_fullest", "tokens", "rows_dropped")

    @property
    def is_recurrent(self) -> bool:
        return False  # shape-agnostic over leading dims

    @property
    def held(self):
        """(first, count) of the experts whose weights are here."""
        return (self.experts_held_first,
                self.experts_held_count or self.n_experts)

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type) -> Params:
        n_in = input_type.size
        if n_in != self.n_out:
            raise ValueError(f"experts keep the width: n_in {n_in} != n_out "
                             f"{self.n_out}")
        first, count = self.held
        if not 0 <= first <= first + count <= self.n_experts:
            raise ValueError(f"experts held [{first}, {first + count}) are not "
                             f"among the {self.n_experts} routed")
        dt = jnp.result_type(float)
        kr, ku, kd, ksu, ksd = jax.random.split(key, 5)
        h = self.hidden or 4 * self.n_out
        down = self.init_std / math.sqrt(self.rescale_layers or 1)
        normal = jax.random.normal
        p = {
            "Wr": self.init_std * normal(kr, (n_in, self.n_experts), dt),
            "e_bias": jnp.zeros((self.n_experts,), dt),
            "W_up": self.init_std * normal(ku, (count, n_in, h), dt),
            "W_down": down * normal(kd, (count, h, self.n_out), dt),
        }
        if self.shared_hidden:
            p["Ws_up"] = self.init_std * normal(
                ksu, (n_in, self.shared_hidden), dt)
            p["Ws_down"] = down * normal(
                ksd, (self.shared_hidden, self.n_out), dt)
        if self.gated:   # keys of their own: the other draws stay as they were
            kg, ksg = jax.random.split(jax.random.fold_in(key, 1))
            p["W_gate"] = self.init_std * normal(kg, (count, n_in, h), dt)
            if self.shared_hidden:
                p["Ws_gate"] = self.init_std * normal(
                    ksg, (n_in, self.shared_hidden), dt)
        return p

    def init_state(self, input_type) -> State:
        return {"counters": jnp.zeros((len(self.COUNTERS),), jnp.int32)}

    def _act(self):
        from ..activations import get_activation  # noqa: PLC0415

        return _relu2 if self.expert_activation == "relu2" \
            else get_activation(self.expert_activation)

    def route(self, params, tokens):
        """(chosen experts [N, top_k], their weights [N, top_k] float32)."""
        f = jnp.promote_types(tokens.dtype, jnp.float32)
        logits = jnp.dot(tokens.astype(f), params["Wr"].astype(f),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + params["e_bias"].astype(f), self.top_k)
        w = jnp.take_along_axis(s, chosen, axis=-1)
        if self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return chosen, w * self.routed_scaling

    def routed(self, params, tokens, token_mask=None):
        """The held experts' part of the routed sum, [N, n_out], and the
        counters of this call."""
        n, _ = tokens.shape
        first, count = self.held
        k = self.top_k
        with jax.named_scope("router"):
            chosen, w = self.route(params, tokens)
        from ...ops import select_grouped_matmul_variant  # noqa: PLC0415
        from ...ops.grouped_matmul import (ROW_TILE, aligned_layout,
                                           grouped_matmul)

        hidden = params["W_up"].shape[-1]
        # four times the even share and a tile of padding a group, whole
        # 512-row tiles; all that can land here when more do
        usual, most = (-(-(r + count * ROW_TILE) // 512) * 512 for r in
                       (4 * n * k * count // self.n_experts,
                        n * min(k, count)))
        variant = select_grouped_matmul_variant(
            min(usual, most), self.n_out, hidden, count,
            tokens.dtype.itemsize)
        align = ROW_TILE if variant == "fused" else 1
        with jax.named_scope("dispatch"):
            local = chosen.reshape(-1) - first                 # [N * k]
            here = (local >= 0) & (local < count)
            if token_mask is not None:   # a padded token is routed nowhere
                here = here & (jnp.repeat(token_mask.reshape(-1), k) > 0)
            key = jnp.where(here, local, count)                # elsewhere: last
            order = jnp.argsort(key, stable=True)
            sizes = expert_row_counts(key, count)
            rows = jnp.sum(sizes)
        act = self._act()

        def compute(m):
            """Both products over a buffer of ``m`` rows, each group's rows
            laid out from a multiple of ``align``."""
            with jax.named_scope("dispatch"):
                group, index, valid, padded = aligned_layout(sizes, align, m)
                pick = order[index]
                token_of = pick // k
                xs = jnp.where(valid[:, None],
                               jnp.take(tokens, token_of, axis=0), 0)
                w_rows = jnp.where(valid, w.reshape(-1)[pick], 0)
            with jax.named_scope("experts"):
                acc = jnp.promote_types(tokens.dtype, jnp.float32)
                hid = grouped_matmul(xs, params["W_up"], group, padded,
                                     variant, acc)
                hid = act(hid) if not self.gated else hid * act(
                    grouped_matmul(xs, params["W_gate"], group, padded,
                                   variant, acc))
                out = grouped_matmul(hid.astype(tokens.dtype),
                                     params["W_down"], group, padded,
                                     variant, acc)
            with jax.named_scope("combine"):
                out = jnp.where(valid[:, None], out, 0) \
                    * w_rows[:, None].astype(acc)
                y = jnp.zeros((n, self.n_out), acc).at[token_of].add(out)
            return y.astype(tokens.dtype), jnp.sum(valid).astype(jnp.int32)

        if usual >= most:
            y, computed = compute(most)
        else:
            needed = jnp.sum(aligned_layout(sizes, align, 1)[3])
            y, computed = jax.lax.cond(needed <= usual,
                                       lambda: compute(usual),
                                       lambda: compute(most))
        tokens_routed = n if token_mask is None \
            else jnp.sum(token_mask > 0).astype(jnp.int32)
        counters = jnp.stack([rows, jnp.max(sizes),
                              jnp.asarray(tokens_routed, jnp.int32),
                              rows - computed]).astype(jnp.int32)
        return y, counters

    def shared(self, params, tokens):
        """The shared expert, which every share computes alike."""
        with jax.named_scope("shared_expert"):
            hid = tokens @ params["Ws_up"]
            hid = self._act()(hid) if not self.gated \
                else self._act()(tokens @ params["Ws_gate"]) * hid
            return hid @ params["Ws_down"]

    def apply(self, params, x, state, *, train=False, rng=None, mask=None):
        lead = x.shape[:-1]
        tokens = x.reshape(-1, x.shape[-1])
        token_mask = None
        if mask is not None and x.ndim == 3 and mask.ndim == 2:
            token_mask = mask.reshape(-1)
        out, counters = self.routed(params, tokens, token_mask)
        if self.shared_hidden:
            out = out + self.shared(params, tokens)
        out = maybe_dropout(out.reshape(lead + (self.n_out,)), self.dropout,
                            train, rng)
        new_state = dict(state)
        if "counters" in state:
            new_state["counters"] = state["counters"] + counters
        return self._activate(out), new_state

    def load_balance_stats(self, params, x) -> dict:
        """Routing diagnostics of one batch, outside jit: the share of the
        top_k assignments each of the ``n_experts`` experts is given."""
        tokens = jnp.asarray(x).reshape(-1, x.shape[-1])
        chosen, _ = self.route(params, tokens)
        counts = expert_row_counts(chosen, self.n_experts)
        first, count = self.held
        return {"expert_fraction": counts / tokens.shape[0],
                "rows_held": int(jnp.sum(counts[first:first + count])),
                "dropped_tokens": 0}
