"""charrnn_2x512: builder through the public API, what a sample is, model
FLOPs from the shapes, seeded data made on the device, and the plain
reference (straightforward jax.numpy, float32, no kernels) that ``correct``
is decided against."""

from __future__ import annotations

import math


def build(sizes: dict, seed: int):
    """The net as a user builds it: ``models.char_rnn.char_rnn`` +
    ``MultiLayerNetwork``; weights come from ``seed``."""
    from deeplearning4j_tpu import MultiLayerNetwork
    from deeplearning4j_tpu.models.char_rnn import char_rnn

    conf = char_rnn(vocab_size=sizes["vocab_size"],
                    hidden_size=sizes["rnn_size"],
                    num_layers=sizes["num_layers"],
                    learning_rate=sizes["learning_rate"],
                    dtype=sizes["dtype"], seed=seed)
    conf.backprop_type = sizes["backprop_type"]
    return MultiLayerNetwork(conf).init()


def samples_per_example(sizes: dict, params: dict) -> int:
    return int(params["seq_len"])


def expected_first_loss(sizes: dict) -> float:
    return math.log(sizes["classes"])


def model_flops_per_sample(sizes: dict) -> float:
    """Forward multiply-adds x 2 x 3 (forward + backward) per character:
    per LSTM layer 4H(I+H) for the input and recurrent projections, plus the
    H x vocab output layer. Gates, peepholes and softmax are not counted."""
    h, v = sizes["rnn_size"], sizes["vocab_size"]
    macs, n_in = 0, v
    for _ in range(sizes["num_layers"]):
        macs += 4 * h * (n_in + h)
        n_in = h
    macs += h * v
    return 2.0 * 3.0 * macs


def make_batches(sizes: dict, params: dict, seed: int, global_batch: int):
    """``(xs, ys)``: ``slots`` staged batches of one-hot characters
    ``[slots, B, T, vocab]`` float32 and their next characters, made on the
    device in one jitted call. The text is a seeded random walk over the
    vocabulary (each character is the last plus 1, 2 or 3), so there is
    something to learn: the best loss is ln 3 = 1.10 against ln 96 = 4.56 at
    the start, and the check that training trains has room to see it."""
    import jax
    import jax.numpy as jnp

    s, b, t, v = (int(params["slots"]), int(global_batch),
                  int(params["seq_len"]), int(sizes["vocab_size"]))

    @jax.jit
    def gen(key):
        k1, k2 = jax.random.split(key)
        first = jax.random.randint(k1, (s, b, 1), 0, v)
        step = jax.random.randint(k2, (s, b, t + 1), 1, 4)
        idx = (first + jnp.cumsum(step, axis=-1)) % v
        hot = jax.nn.one_hot(idx, v, dtype=jnp.float32)
        return hot[:, :, :-1], hot[:, :, 1:]

    return gen(jax.random.PRNGKey(seed))


# ----------------------------------------------------------- plain reference
def _lstm_layer(p, x):
    """GravesLSTM over ``x [B, T, I]`` from zero state; gate columns
    [a, f, o, i] and peepholes as DL4J's LSTMHelpers has them."""
    import jax
    import jax.numpy as jnp

    h_size = p["RW"].shape[0]
    zx = jnp.einsum("bti,ij->btj", x, p["W"]) + p["b"]

    def step(carry, z_t):
        h, c = carry
        z = z_t + h @ p["RW"]
        a = jnp.tanh(z[:, :h_size])
        f = jax.nn.sigmoid(z[:, h_size:2 * h_size] + c * p["pF"])
        i = jax.nn.sigmoid(z[:, 3 * h_size:] + c * p["pI"])
        c2 = f * c + i * a
        o = jax.nn.sigmoid(z[:, 2 * h_size:3 * h_size] + c2 * p["pO"])
        h2 = o * jnp.tanh(c2)
        return (h2, c2), h2

    zero = jnp.zeros((x.shape[0], h_size), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero), jnp.swapaxes(zx, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def reference_probs(net_params, x):
    """Softmax outputs ``[B, T, vocab]`` for one-hot ``x [B, T, vocab]``."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        p32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                     tuple(net_params))
        h = jnp.asarray(x, jnp.float32)
        for layer in p32[:-1]:
            h = _lstm_layer(layer, h)
        logits = h @ p32[-1]["W"] + p32[-1]["b"]
        return jax.nn.softmax(logits, axis=-1)


def reference_loss(net_params, net_state, x, y, sizes: dict) -> float:
    """Mean cross-entropy per character of one batch at ``net_params``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss(p, x, y):
        probs = reference_probs(p, x)
        return -jnp.mean(jnp.sum(y * jnp.log(probs + 1e-30), axis=-1))

    return float(loss(tuple(net_params), x, y))
