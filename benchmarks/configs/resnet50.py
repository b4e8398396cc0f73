"""resnet50: builder through the public API, what a sample is, model FLOPs
from the layer shapes, seeded data made on the device, and the plain
reference (straightforward jax.numpy, float32, batch statistics) that
``correct`` is decided against."""

from __future__ import annotations

import math


def build(sizes: dict, seed: int):
    """The net as a user builds it: ``resnet50_conf`` + ``ComputationGraph``."""
    from deeplearning4j_tpu.models.resnet import resnet_conf
    from deeplearning4j_tpu.nn.graph.computation_graph import ComputationGraph

    conf = resnet_conf(list(sizes["blocks"]), bottleneck=True,
                       num_classes=sizes["classes"],
                       image_size=(sizes["image_size"], sizes["image_size"]),
                       channels=sizes["channels"], dtype=sizes["dtype"],
                       seed=seed)
    return ComputationGraph(conf).init()


def samples_per_example(sizes: dict, params: dict) -> int:
    return 1


def expected_first_loss(sizes: dict) -> float:
    return math.log(sizes["classes"])


def _convs(sizes: dict):
    """Every convolution as ``(name, kernel, stride, c_in, c_out, out_hw)``
    in forward order, from the sizes alone (Table 1, 50-layer column)."""
    hw = -(-sizes["image_size"] // 2)
    yield "stem", 7, 2, sizes["channels"], sizes["stem_channels"], hw
    hw = -(-hw // 2)  # 3x3 max pool, stride 2
    c_in = sizes["stem_channels"]
    for stage, (n, mid) in enumerate(zip(sizes["blocks"],
                                         sizes["stage_widths"])):
        out = mid * sizes["bottleneck_expansion"]
        for i in range(n):
            stride = 2 if (stage > 0 and i == 0) else 1
            hw = -(-hw // stride)
            name = f"s{stage}_b{i}"
            yield f"{name}_a", 1, stride, c_in, mid, hw
            yield f"{name}_b", 3, 1, mid, mid, hw
            yield f"{name}_c", 1, 1, mid, out, hw
            if i == 0 and (stride != 1 or c_in != out):
                yield f"{name}_proj", 1, stride, c_in, out, hw
            c_in = out


def forward_macs(sizes: dict) -> float:
    macs = sum(k * k * ci * co * hw * hw
               for _, k, _, ci, co, hw in _convs(sizes))
    final = sizes["stage_widths"][-1] * sizes["bottleneck_expansion"]
    return float(macs + final * sizes["classes"])


def model_flops_per_sample(sizes: dict) -> float:
    """Forward multiply-adds x 2 x 3 (forward + backward) per image, from
    the convolution and classifier shapes; batch norm, ReLU, pooling and the
    optimizer are not counted."""
    return 2.0 * 3.0 * forward_macs(sizes)


def make_batches(sizes: dict, params: dict, seed: int, global_batch: int):
    """``(xs, ys)``: ``slots`` staged batches of standard-normal images
    ``[slots, B, H, W, C]`` float32 and one-hot labels ``[slots, B, classes]``,
    made on the device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp

    s, b = int(params["slots"]), int(global_batch)
    hw, c, n = sizes["image_size"], sizes["channels"], sizes["classes"]

    @jax.jit
    def gen(key):
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (s, b, hw, hw, c), jnp.float32)
        y = jax.nn.one_hot(jax.random.randint(k2, (s, b), 0, n), n,
                           dtype=jnp.float32)
        return x, y

    return gen(jax.random.PRNGKey(seed))


# ----------------------------------------------------------- plain reference
def reference_loss(net_params, net_state, x, y, sizes: dict) -> float:
    """Mean cross-entropy of one batch at ``net_params`` in training mode
    (batch statistics in every batch norm), float32 throughout."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = sizes["batch_norm_eps"]

    def conv_bn(p, h, name, stride, relu=True):
        h = lax.conv_general_dilated(
            h, p[f"{name}_conv"]["W"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        mean = jnp.mean(h, axis=(0, 1, 2))
        var = jnp.var(h, axis=(0, 1, 2))
        bn = p[f"{name}_bn"]
        h = (h - mean) * lax.rsqrt(var + eps) * bn["gamma"] + bn["beta"]
        return jnp.maximum(h, 0.0) if relu else h

    @jax.jit
    def loss(p, x, y):
        with jax.default_matmul_precision("highest"):
            p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
            h = conv_bn(p, jnp.asarray(x, jnp.float32), "stem", 2)
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
            convs = list(_convs(sizes))[1:]
            blocks = {}
            for name, _k, stride, *_ in convs:
                blocks.setdefault(name.rsplit("_", 1)[0], {})[
                    name.rsplit("_", 1)[1]] = stride
            for block, parts in blocks.items():
                t = conv_bn(p, h, f"{block}_a", parts["a"])
                t = conv_bn(p, t, f"{block}_b", 1)
                t = conv_bn(p, t, f"{block}_c", 1, relu=False)
                if "proj" in parts:
                    h = conv_bn(p, h, f"{block}_proj", parts["proj"],
                                relu=False)
                h = jnp.maximum(t + h, 0.0)
            h = jnp.mean(h, axis=(1, 2))
            logits = h @ p["out"]["W"] + p["out"]["b"]
            return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits), axis=-1))

    return float(loss(dict(net_params), x, y))
