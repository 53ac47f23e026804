"""LFM2-MoE decoder (``model_type`` ``lfm2_moe``; HF ``modeling_lfm2_moe.py``).

Layers of two kinds by ``layer_types``: a gated short convolution, or
grouped-query attention with an RMSNorm on every head of q and k before
rotate-half RoPE (``models/llama.py`` ``rope`` is the interleaved form). The
feed-forward is a dense SwiGLU in the leading ``num_dense_layers`` and routed
after them (:class:`~sparkdl_tpu.parallel.moe.RoutedExperts`: sigmoid scores
over all ``num_experts``, a selection-only bias, top-k, no token dropped, and
only the held experts' part computed). The head is tied to the embedding. All
linear maps are without bias.

    y  = x + op_i(RMSNorm(x));   x' = y + ffn_i(RMSNorm(y))

Each layer is recomputed in the backward pass (``nn.remat`` on the layer:
``fit(remat=True)`` wraps the whole forward, which does not lower the peak),
all but what its kernel wrote: the flash forward's ``o`` and ``lse`` are kept
by name (``ops.SAVE_KERNEL_RESIDUALS``; 67 + 2 MB for the one attention layer
at 2 x 8192 positions in bf16), because the backward kernels read them and
the forward kernel is the dearest thing in the layer to run again. Trained
through ``ctx.fit`` like any other model::

    model = Lfm2ForCausalLM(cfg, dtype=jnp.bfloat16)
    ctx.fit(loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
            params=variables, tx=optax.adamw(1e-4, weight_decay=0.1,
                                             mask=trainable_mask), ...)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import SAVE_KERNEL_RESIDUALS
from ..parallel.moe import RoutedExperts
from ..utils import scopes
from .llama import RMSNorm

CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = (CONV, CONV, ATTENTION, CONV, CONV, CONV, ATTENTION,
                          CONV, CONV, CONV, ATTENTION, CONV, CONV, CONV,
                          ATTENTION, CONV, CONV, CONV, ATTENTION, CONV, CONV,
                          ATTENTION, CONV, CONV)
    num_dense_layers: int = 2
    num_experts: int = 32            # the router's width
    num_experts_per_tok: int = 4
    experts_held: tuple | None = None   # (first, count); None: all of them
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_dict(cls, cfg: dict) -> "Lfm2Config":
        """From the keys of a published ``config.json``: every expert held.
        One chip's share is ``dataclasses.replace(..., experts_held=...)``."""
        if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
            raise ValueError(f"{len(cfg['layer_types'])} layer types for "
                             f"{cfg['num_hidden_layers']} layers")
        same = ("vocab_size", "hidden_size", "intermediate_size",
                "moe_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_dense_layers", "num_experts",
                "num_experts_per_tok", "conv_L_cache", "norm_eps",
                "norm_topk_prob", "use_expert_bias")
        return cls(layer_types=tuple(cfg["layer_types"]),
                   rope_theta=float(cfg["rope_theta"]),
                   routed_scaling_factor=float(cfg["routed_scaling_factor"]),
                   **{k: cfg[k] for k in same})

    @classmethod
    def tiny(cls) -> "Lfm2Config":
        """Every mechanism at a size the CPU tests step in seconds."""
        return cls(vocab_size=96, hidden_size=32, intermediate_size=48,
                   moe_intermediate_size=16, num_attention_heads=4,
                   num_key_value_heads=2,
                   layer_types=(CONV, ATTENTION, CONV), num_dense_layers=1)


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


def _dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


class Lfm2ShortConv(nn.Module):
    """``[B, C, z] = split3(u W_in)``; a causal depthwise convolution of
    ``conv_L_cache`` taps over ``B * z``; ``(C * conv) W_out``."""
    cfg: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        d, taps = self.cfg.hidden_size, self.cfg.conv_L_cache
        with scopes.layer("short_conv"):
            b, c, z = jnp.split(_dense(3 * d, self.dtype, "in_proj")(u), 3,
                                axis=-1)
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (taps, d)).astype(self.dtype)
            g = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
            s = u.shape[1]
            conv = sum(kernel[j] * g[:, j:j + s] for j in range(taps))
            return _dense(d, self.dtype, "out_proj")(c * conv)


class Lfm2Attention(nn.Module):
    """Grouped-query causal attention, q and k normalised per head before
    RoPE. ``attn_fn`` as in ``models/bert.py``: ``"auto"`` is the flash
    kernel at long sequences on a TPU, dense attention elsewhere."""
    cfg: Lfm2Config
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, u):
        from ..ops.flash_attention import resolve_attn_fn
        from ..parallel.ring_attention import dense_attention
        c = self.cfg
        bsz, s, _ = u.shape
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def heads(name, n):
            t = _dense(n * hd, self.dtype, name)(u)
            return t.reshape(bsz, s, n, hd).transpose(0, 2, 1, 3)

        q, k, v = heads("q_proj", h), heads("k_proj", hkv), heads("v_proj", hkv)
        q = rope_rotate_half(RMSNorm(c.norm_eps, name="q_layernorm")(q),
                             c.rope_theta)
        k = rope_rotate_half(RMSNorm(c.norm_eps, name="k_layernorm")(k),
                             c.rope_theta)
        # each key/value head serves h // hkv query heads
        k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
        attn = resolve_attn_fn(self.attn_fn) or dense_attention
        o = attn(q, k, v, causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(bsz, s, h * hd)
        return _dense(c.hidden_size, self.dtype, "out_proj")(o)


class Lfm2MLP(nn.Module):
    cfg: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        f = self.cfg.intermediate_size
        gate = jax.nn.silu(_dense(f, self.dtype, "w1")(x))
        return _dense(self.cfg.hidden_size, self.dtype, "w2")(
            gate * _dense(f, self.dtype, "w3")(x))


class Lfm2DecoderLayer(nn.Module):
    cfg: Lfm2Config
    index: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        u = RMSNorm(c.norm_eps, name="operator_norm")(x)
        if c.layer_types[self.index] == ATTENTION:
            x = x + Lfm2Attention(c, self.dtype, self.attn_fn,
                                  name="self_attn")(u)
        else:
            x = x + Lfm2ShortConv(c, self.dtype, name="conv")(u)
        f = RMSNorm(c.norm_eps, name="ffn_norm")(x)
        if self.index < c.num_dense_layers:
            return x + Lfm2MLP(c, self.dtype, name="feed_forward")(f)
        return x + RoutedExperts(
            c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            held=c.experts_held, norm_topk_prob=c.norm_topk_prob,
            routed_scaling_factor=c.routed_scaling_factor,
            use_expert_bias=c.use_expert_bias, dtype=self.dtype,
            name="feed_forward")(f)


class Lfm2ForCausalLM(nn.Module):
    """``ids [B, S] -> logits [B, S, V]`` in float32, head tied to the
    embedding. The expert layers' counters land in the ``counters``
    collection: :meth:`apply_with_counters` hands them to the loss."""
    cfg: Lfm2Config
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, ids):
        c = self.cfg
        emb = self.param("embed_tokens", lambda k, s: {
            "embedding": nn.initializers.normal(0.02)(k, s)},
            (c.vocab_size, c.hidden_size))["embedding"]
        with scopes.layer("embed_tokens"):
            x = jnp.take(emb, ids, axis=0).astype(self.dtype)
        layer = nn.remat(Lfm2DecoderLayer, policy=SAVE_KERNEL_RESIDUALS)
        for i in range(len(c.layer_types)):
            x = layer(c, i, self.dtype, self.attn_fn, name=f"layer_{i}")(x)
        x = RMSNorm(c.norm_eps, name="embedding_norm")(x)
        with scopes.layer("lm_head_loss"):
            return jnp.einsum("bsd,vd->bsv", x, emb.astype(self.dtype),
                              preferred_element_type=jnp.float32)

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)``, the counters summed
        over the expert layers (``moe_assignments``, ``moe_assignments_held``,
        ``moe_held_load_max``, ``moe_held_load_mean``, ``moe_dropped``)."""
        logits, mut = self.apply(variables, ids, mutable=["counters"])
        total: dict = {}
        for path, v in jax.tree_util.tree_flatten_with_path(
                mut.get("counters", {}))[0]:
            total[path[-1].key] = total.get(path[-1].key, 0.0) + v
        return logits, total


def trainable_mask(params):
    """True for every leaf an optimizer may move: all but ``expert_bias``
    (``optax.adamw(..., mask=trainable_mask)`` spares it the weight decay;
    its gradient is zero, so Adam's own update of it is exactly zero)."""
    from ..parallel.sharding import path_str
    return jax.tree_util.tree_map_with_path(
        lambda path, _: "expert_bias" not in path_str(path).split("/"),
        params)
