"""ResNet-50 (arXiv:1512.03385, v1.5 stride placement) with its training
step, in plain ``jax.numpy`` and float32: forward with BatchNorm in training
mode, mean softmax cross-entropy, gradients, SGD with momentum.

It imports nothing of the program. Its weights come from the seed, under the
names the program's checkpoint uses (``stage2_block1/conv1/kernel``), so the
two trees are compared leaf by leaf with no table between them.

``precision`` is what the matrix units see: ``"float32"`` (the reference,
every product at ``highest``), or ``"fp8"``, the control, the step below the
bfloat16 that the configuration states: each convolution's and the head's
inputs and weights rounded to float8 e4m3 going forward, the incoming gradient
to e5m2 going backward, one scale per tensor, as a float8 training recipe
does. ``"bf16"`` rounds the same places to bfloat16: a second witness of what
rounding alone does, for looking at a reading, never for deciding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_CAFFE_MEAN = (103.939, 116.779, 123.68)  # BGR


# -- weights from the seed ---------------------------------------------------

def _conv_shapes(cfg: dict) -> dict:
    """name -> (kh, kw, cin, cout), every convolution of the network."""
    w, e = cfg["width"], cfg["bottleneck_expansion"]
    shapes = {"stem_conv": (7, 7, cfg["channels"], w)}
    cin = w
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** i
        for j in range(n_blocks):
            b = f"stage{i + 1}_block{j + 1}"
            shapes[f"{b}/conv1"] = (1, 1, cin, f)
            shapes[f"{b}/conv2"] = (3, 3, f, f)
            shapes[f"{b}/conv3"] = (1, 1, f, f * e)
            if j == 0:
                shapes[f"{b}/proj_conv"] = (1, 1, cin, f * e)
            cin = f * e
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ..., "batch_stats": ...}`` in float32, one traced call."""
    params, stats = {}, {}
    shapes = _conv_shapes(cfg)
    keys = jax.random.split(key, len(shapes) + 1)
    for k, (name, shp) in zip(keys, sorted(shapes.items())):
        fan_in = shp[0] * shp[1] * shp[2]
        set_leaf(params, name, "kernel",
             jax.random.normal(k, shp, jnp.float32) / math.sqrt(fan_in))
        bn = name.replace("conv", "bn") if name != "stem_conv" else "stem_bn"
        c = shp[3]
        set_leaf(params, bn, "scale", jnp.ones((c,), jnp.float32))
        set_leaf(params, bn, "bias", jnp.zeros((c,), jnp.float32))
        set_leaf(stats, bn, "mean", jnp.zeros((c,), jnp.float32))
        set_leaf(stats, bn, "var", jnp.ones((c,), jnp.float32))
    feat = cfg["width"] * 2 ** (len(cfg["stage_sizes"]) - 1) \
        * cfg["bottleneck_expansion"]
    params["head"] = {
        "kernel": jax.random.normal(
            keys[-1], (feat, cfg["num_classes"]), jnp.float32)
        / math.sqrt(feat),
        "bias": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return {"params": params, "batch_stats": stats}


# -- the forward pass ----------------------------------------------------------

def _conv(x, w, stride: int, padding, precision: str):
    conv = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(stride, stride),
        padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return narrow(conv, precision)(x, w)


def _bn(x, p, eps: float):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _block(x, p, stride: int, eps: float, precision: str):
    y = _conv(x, p["conv1"]["kernel"], 1, "SAME", precision)
    y = jax.nn.relu(_bn(y, p["bn1"], eps))
    y = _conv(y, p["conv2"]["kernel"], stride, "SAME", precision)
    y = jax.nn.relu(_bn(y, p["bn2"], eps))
    y = _conv(y, p["conv3"]["kernel"], 1, "SAME", precision)
    y = _bn(y, p["bn3"], eps)
    if "proj_conv" in p:
        x = _conv(x, p["proj_conv"]["kernel"], stride, "SAME", precision)
        x = _bn(x, p["proj_bn"], eps)
    return jax.nn.relu(y + x)


def loss_fn(cfg: dict, params: dict, batch: dict, precision: str = "float32"):
    """Mean softmax cross-entropy of one batch, BatchNorm on batch statistics.
    Each block is recomputed in the backward pass, so that float32 at the
    timed batch fits beside nothing else on the chip."""
    eps = cfg["bn_epsilon"]
    x = batch["image"].astype(jnp.float32)[..., ::-1] \
        - jnp.asarray(_CAFFE_MEAN, jnp.float32)
    x = _conv(x, params["stem_conv"]["kernel"], 2, [(3, 3), (3, 3)], precision)
    x = jax.nn.relu(_bn(x, params["stem_bn"], eps))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    for i, n_blocks in enumerate(cfg["stage_sizes"]):
        for j in range(n_blocks):
            stride = 2 if i > 0 and j == 0 else 1
            blk = jax.checkpoint(functools.partial(
                _block, stride=stride, eps=eps, precision=precision))
            x = blk(x, params[f"stage{i + 1}_block{j + 1}"])
    x = jnp.mean(x, axis=(1, 2))
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    logits = narrow(dot, precision)(x, params["head"]["kernel"])
    logits = logits + params["head"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
    return -jnp.mean(picked)


# -- the optimizer -------------------------------------------------------------

def trainable(weights: dict) -> dict:
    return weights["params"]


def opt_init(cfg: dict, params: dict):
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def opt_update(cfg: dict, params: dict, grads: dict, opt, step: int):
    """SGD with momentum: v <- m v + g; p <- p - lr v."""
    m, lr = cfg["momentum"], cfg["learning_rate"]
    opt = jax.tree_util.tree_map(lambda v, g: m * v + g, opt, grads)
    params = jax.tree_util.tree_map(lambda p, v: p - lr * v, params, opt)
    return params, opt
