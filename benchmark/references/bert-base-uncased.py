"""BERT-base (google-research/bert, uncased_L-12_H-768_A-12) with a
sequence-classification head and its training step, in plain ``jax.numpy``
and float32: embeddings, post-LayerNorm encoder layers with dense softmax
attention and exact GELU, pooler, classifier, mean softmax cross-entropy,
gradients, AdamW.

It imports nothing of the program. Weights come from the seed under the
names the program's checkpoint uses. Dropout is 0.0 in the configuration, so
none is drawn. No padding mask: every position attends to every other.

``precision``: ``"float32"`` (every product at ``highest``), or ``"fp8"``, the
control: each matrix product's two operands rounded to float8 e4m3 going
forward, the incoming gradient to e5m2 going backward, one scale per tensor,
as a float8 training recipe does. ``"bf16"`` rounds the same places to
bfloat16: a second witness for looking at a reading, never for deciding.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST


# -- weights from the seed ---------------------------------------------------

def _dense_shapes(cfg: dict) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    shapes = {}
    for i in range(cfg["num_hidden_layers"]):
        b = f"bert/layer_{i}"
        for n in ("query", "key", "value", "attention_output"):
            shapes[f"{b}/attention/{n}"] = (h, h)
        shapes[f"{b}/intermediate"] = (h, f)
        shapes[f"{b}/output_dense"] = (f, h)
    shapes["bert/pooler"] = (h, h)
    shapes["classifier"] = (h, cfg["num_classes"])
    return shapes


def _norm_names(cfg: dict) -> list:
    names = ["bert/embeddings_norm"]
    for i in range(cfg["num_hidden_layers"]):
        names += [f"bert/layer_{i}/attention_norm",
                  f"bert/layer_{i}/output_norm"]
    return names


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: normal(0, 0.02), LayerNorm scale 1,
    biases 0 — the published ``initializer_range``."""
    params = {}
    h = cfg["hidden_size"]
    dense = _dense_shapes(cfg)
    embeds = {"bert/word_embeddings": cfg["vocab_size"],
              "bert/position_embeddings": cfg["max_position_embeddings"],
              "bert/token_type_embeddings": cfg["type_vocab_size"]}
    keys = iter(jax.random.split(key, len(dense) + len(embeds)))
    for name, shp in sorted(dense.items()):
        set_leaf(params, name, "kernel",
             0.02 * jax.random.normal(next(keys), shp, jnp.float32))
        set_leaf(params, name, "bias", jnp.zeros((shp[1],), jnp.float32))
    for name, rows in sorted(embeds.items()):
        set_leaf(params, name, "embedding",
             0.02 * jax.random.normal(next(keys), (rows, h), jnp.float32))
    for name in _norm_names(cfg):
        set_leaf(params, name, "scale", jnp.ones((h,), jnp.float32))
        set_leaf(params, name, "bias", jnp.zeros((h,), jnp.float32))
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _dense(x, p, precision: str):
    return _mm("...i,io->...o", x, p["kernel"], precision) + p["bias"]


def _layer_norm(x, p, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _layer(x, p, heads: int, eps: float, precision: str):
    b, s, h = x.shape
    d = h // heads
    split = lambda t: t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
    att = p["attention"]
    q = split(_dense(x, att["query"], precision))
    k = split(_dense(x, att["key"], precision))
    v = split(_dense(x, att["value"], precision))
    scores = _mm("bhqd,bhkd->bhqk", q, k, precision) / math.sqrt(d)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bhkd->bhqd", probs, v, precision)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h)
    a = _dense(o, att["attention_output"], precision)
    x = _layer_norm(x + a, p["attention_norm"], eps)
    f = _dense(x, p["intermediate"], precision)
    f = jax.nn.gelu(f, approximate=False)
    f = _dense(f, p["output_dense"], precision)
    return _layer_norm(x + f, p["output_norm"], eps)


def loss_fn(cfg: dict, params: dict, batch: dict, precision: str = "float32"):
    """Mean softmax cross-entropy of one batch. Each encoder layer is
    recomputed in the backward pass, so that float32 at the timed batch fits."""
    eps, enc = cfg["layer_norm_eps"], params["bert"]
    ids = batch["input_ids"]
    s = ids.shape[1]
    x = enc["word_embeddings"]["embedding"][ids] \
        + enc["position_embeddings"]["embedding"][None, :s] \
        + enc["token_type_embeddings"]["embedding"][0][None, None, :]
    x = _layer_norm(x, enc["embeddings_norm"], eps)
    layer = jax.checkpoint(functools.partial(
        _layer, heads=cfg["num_attention_heads"], eps=eps,
        precision=precision))
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, enc[f"layer_{i}"])
    pooled = jnp.tanh(_dense(x[:, 0], enc["pooler"], precision))
    logits = _dense(pooled, params["classifier"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, batch["label"][:, None], axis=-1)
    return -jnp.mean(picked)


# -- the optimizer -------------------------------------------------------------

def trainable(weights: dict) -> dict:
    return weights["params"]


def opt_init(cfg: dict, params: dict):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def opt_update(cfg: dict, params: dict, grads: dict, opt, step):
    """AdamW, decoupled weight decay on every parameter, constant rate."""
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    eps, lr, wd = cfg["adam_eps"], cfg["learning_rate"], cfg["weight_decay"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                opt["nu"], grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(p, m, n):
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p)

    params = jax.tree_util.tree_map(one, params, mu, nu)
    return params, {"mu": mu, "nu": nu}
