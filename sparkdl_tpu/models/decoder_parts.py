"""What the trained decoders share (``lfm2.py``, ``phi4flash.py``,
``granite_hybrid.py``, ``qwen3_next.py``, ``smallthinker.py``): the layers'
parts, and the causal-LM shell around a stack of layers. The shell's
functions take the calling module, as :func:`count` does, and make their
parameters in it, so no module level stands between a model and its
parameters; they read its ``cfg`` (``vocab_size``, ``hidden_size``),
``dtype`` and ``attn_fn``."""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import SAVE_KERNEL_RESIDUALS, resolve_attn_fn
from ..parallel.ring_attention import dense_attention
from ..utils import scopes
from .lm_loss import folded_counters

# a routed layer's counters (``parallel.moe.held_experts_ffn``), summed over
# the layers
MOE_FOLDS = dict.fromkeys(
    ("moe_assignments", "moe_assignments_held", "moe_held_load_max",
     "moe_held_load_mean", "moe_dropped"), jnp.sum)


def dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` in float32, in ``x``'s dtype."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def rope_rotate_half(x, theta: float, rotary_dim: int | None = None):
    """Rotate-half RoPE over the first ``rotary_dim`` of the head's dims (all
    of them by default); the rest pass through. ``x``: ``[B, H, S, D]``,
    positions 0..S-1."""
    d = rotary_dim or x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xf = x[..., :d].astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return jnp.concatenate([(xf * cos + rot * sin).astype(x.dtype),
                            x[..., d:]], axis=-1)


def causal_taps(x, kernel):
    """The causal depthwise convolution of ``x [B, S, C]`` by ``kernel
    [T, C]``: ``y[t] = sum_j kernel[j] * x[t - T + 1 + j]``, zero before the
    first position."""
    taps, s = kernel.shape[0], x.shape[1]
    g = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j] * g[:, j:j + s] for j in range(taps))


def split_heads(t, n: int):
    """``[B, S, n * D] -> [B, n, S, D]``."""
    return t.reshape(*t.shape[:2], n, -1).transpose(0, 2, 1, 3)


def merge_heads(o):
    """``[B, H, S, D] -> [B, S, H * D]``."""
    b, _, s, _ = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1)


def grouped_query_attention(q, k, v, attn_fn, window=None):
    """Causal softmax attention of ``q [B, H, S, D]`` over ``k, v
    [B, H_kv, S, D]``, each key/value head serving ``H // H_kv`` query heads
    in a row, under a window of ``window`` keys where one is given: ``o
    [B, H, S, D]``. ``attn_fn`` as in ``models/bert.py``: ``"auto"`` is the
    flash kernel at long sequences on a TPU, dense attention elsewhere."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    attn = resolve_attn_fn(attn_fn) or dense_attention
    return attn(q, k, v, causal=True, **({"window": window} if window else {}))


def embed(module: nn.Module, ids, multiplier: float | None = None):
    """``(x, E)``: ``E`` the module's ``embed_tokens/embedding``
    ``[vocab_size, hidden_size]``, and ``x = E[ids]`` (times ``multiplier``
    where one is given) in the module's dtype."""
    c = module.cfg
    emb = module.param("embed_tokens", lambda k, s: {
        "embedding": nn.initializers.normal(0.02)(k, s)},
        (c.vocab_size, c.hidden_size))["embedding"]
    with scopes.layer("embed_tokens"):
        x = jnp.take(emb, ids, axis=0)
        if multiplier is not None:
            x = x * multiplier
        return x.astype(module.dtype), emb


def layer_stack(module: nn.Module, layer, x, layers):
    """``x`` through ``layer(cfg, l, dtype, attn_fn)`` for each ``l`` of
    ``layers``, named ``layer_0``, ``layer_1``, ...; each is recomputed in
    the backward pass, all but what its kernels wrote
    (``ops.SAVE_KERNEL_RESIDUALS``)."""
    layer = nn.remat(layer, policy=SAVE_KERNEL_RESIDUALS)
    for i, l in enumerate(layers):
        x = layer(module.cfg, l, module.dtype, module.attn_fn,
                  name=f"layer_{i}")(x)
    return x


def head(module: nn.Module, x, tied=None, logits_scaling=None):
    """The logits ``[B, S, V]`` in float32: ``x E^T`` with ``tied`` the
    embedding ``E``, else ``x W`` with ``W`` the module's ``lm_head/kernel``
    ``[hidden_size, vocab_size]``; over ``logits_scaling`` where one is
    given."""
    with scopes.layer("lm_head_loss"):
        if tied is None:
            c = module.cfg
            w, spec = module.param("lm_head", lambda k, s: {
                "kernel": nn.initializers.normal(0.02)(k, s)},
                (c.hidden_size, c.vocab_size))["kernel"], "bsd,dv->bsv"
        else:
            w, spec = tied, "bsd,vd->bsv"
        logits = jnp.einsum(spec, x, w.astype(module.dtype),
                            preferred_element_type=jnp.float32)
        return logits if logits_scaling is None else logits / logits_scaling


def apply_counted(model: nn.Module, variables, ids, folds: dict):
    """``fit``'s ``apply_fn``: ``(logits, counters)``, ``folds[name]``
    folding the layers' readings of counter ``name`` into one number."""
    logits, mut = model.apply(variables, ids, mutable=["counters"])
    return logits, folded_counters(mut, folds)


def count(module: nn.Module, name: str, value):
    """One layer's reading of a counter; the model's ``apply_with_counters``
    folds the layers' readings (:func:`apply_counted`)."""
    module.sow("counters", name,
               jax.lax.stop_gradient(value.astype(jnp.float32)))


def dt_bias_init(key, shape):
    """``softplus^-1(dt0)``, ``dt0`` log-uniform in [1e-3, 1e-1]."""
    dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    return dt0 + jnp.log(-jnp.expm1(-dt0))


def decay_mask(params, also: tuple = ()):
    """True for the leaves weight decay touches: the matrices and the
    embedding (``optax.adamw(..., mask=decay_mask)``), and the leaves named
    in ``also`` (a routed model's expert stacks); none on norms, ``A_log``,
    ``D``, biases, the convolution's taps, the ``lambda`` vectors."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) in (
            "kernel", "embedding", *also),
        params)


def routed_decay_mask(params):
    """:func:`decay_mask` of a routed model: the expert stacks too."""
    return decay_mask(params, also=("w1", "w3", "w2"))
