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

from ..parallel.moe import RoutedExperts
from ..utils import scopes
from .decoder_parts import (MOE_FOLDS, RMSNorm, apply_counted, causal_taps,
                            dense, embed, grouped_query_attention, head,
                            layer_stack, merge_heads, rope_rotate_half,
                            split_heads)

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


class Lfm2ShortConv(nn.Module):
    """``[B, C, z] = split3(u W_in)``; a causal depthwise convolution of
    ``conv_L_cache`` taps over ``B * z``; ``(C * conv) W_out``."""
    cfg: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        d, taps = self.cfg.hidden_size, self.cfg.conv_L_cache
        with scopes.layer("short_conv"):
            b, c, z = jnp.split(dense(3 * d, self.dtype, "in_proj")(u), 3,
                                axis=-1)
            kernel = self.param("conv_kernel", nn.initializers.normal(0.02),
                                (taps, d)).astype(self.dtype)
            return dense(d, self.dtype, "out_proj")(
                c * causal_taps(b * z, kernel))


class Lfm2Attention(nn.Module):
    """Grouped-query causal attention, q and k normalised per head before
    RoPE. ``attn_fn`` as in ``models/bert.py``: ``"auto"`` is the flash
    kernel at long sequences on a TPU, dense attention elsewhere."""
    cfg: Lfm2Config
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def heads(name, n):
            return split_heads(dense(n * hd, self.dtype, name)(u), n)

        q, k, v = heads("q_proj", h), heads("k_proj", hkv), heads("v_proj", hkv)
        q = rope_rotate_half(RMSNorm(c.norm_eps, name="q_layernorm")(q),
                             c.rope_theta)
        k = rope_rotate_half(RMSNorm(c.norm_eps, name="k_layernorm")(k),
                             c.rope_theta)
        o = grouped_query_attention(q, k, v, self.attn_fn)
        return dense(c.hidden_size, self.dtype, "out_proj")(merge_heads(o))


class Lfm2MLP(nn.Module):
    cfg: Lfm2Config
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        f = self.cfg.intermediate_size
        gate = jax.nn.silu(dense(f, self.dtype, "w1")(x))
        return dense(self.cfg.hidden_size, self.dtype, "w2")(
            gate * dense(f, self.dtype, "w3")(x))


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
        x, emb = embed(self, ids)
        x = layer_stack(self, Lfm2DecoderLayer, x, range(len(c.layer_types)))
        return head(self, RMSNorm(c.norm_eps, name="embedding_norm")(x),
                    tied=emb)

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)``, the counters summed
        over the expert layers (``moe_assignments``, ``moe_assignments_held``,
        ``moe_held_load_max``, ``moe_held_load_mean``, ``moe_dropped``)."""
        return apply_counted(self, variables, ids, MOE_FOLDS)


def trainable_mask(params):
    """True for every leaf an optimizer may move: all but ``expert_bias``
    (``optax.adamw(..., mask=trainable_mask)`` spares it the weight decay;
    its gradient is zero, so Adam's own update of it is exactly zero)."""
    from ..parallel.sharding import path_str
    return jax.tree_util.tree_map_with_path(
        lambda path, _: "expert_bias" not in path_str(path).split("/"),
        params)
