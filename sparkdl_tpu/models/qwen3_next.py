"""Qwen3-Next (``model_type`` ``qwen3_next``: ``Qwen3-Next-80B-A3B``): Gated
DeltaNet linear-attention layers (arXiv:2412.06464) with an output-gated
softmax-attention layer every ``full_attention_interval``-th, and a routed
expert layer with a gated shared expert behind every mixer; equations as in
the published ``modeling_qwen3_next.py``.

With ``l`` the PUBLISHED index of a layer (a cut keeps it: ``layers_kept``),
no bias in any projection, and ``RMSNorm0(x) = x / sqrt(mean(x^2) + eps) *
(1 + w)`` (zero-centred: every norm but the gated one)::

    y = x + mixer_l(RMSNorm0(x));   x' = y + moe(RMSNorm0(y))
    logits = RMSNorm0(x_last) W_head                  (the head is untied)

- ``(l + 1) % full_attention_interval == 0``: ``[q_h | gate_h]`` a head out
  of one projection, ``k``, ``v`` as ``H_kv`` heads; ``RMSNorm0`` over each
  head of q and k; rotate-half RoPE over the first ``partial_rotary_factor``
  of a head's dims; causal softmax attention at ``1 / sqrt(d)``;
  ``(attn * sigmoid(gate)) W_o``.
- else linear attention: ``[q | k | v | z] = u W_qkvz``, ``[b | a] = u W_ba``;
  ``[q | k | v] = silu(causal_depthwise_conv([q | k | v]))``; q and k of unit
  length a head; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` a value head, float32; the gated delta rule
  (:func:`~sparkdl_tpu.ops.gated_delta.gated_delta_rule`); then the norm
  BEFORE the gate, a head at a time: ``(RMSNorm(o) w_n * silu(z)) W_o``.
- the expert layer (:class:`~sparkdl_tpu.parallel.moe.RoutedExperts`,
  ``scoring="softmax"``): softmax scores over all ``num_experts`` in float32,
  the top ``num_experts_per_tok`` normalised, only the held experts' part
  computed; plus ``sigmoid(f w_s) * SwiGLU_shared(f)``.

The multi-token-prediction module of the published model has no key in its
``config.json`` and is not built. Each layer is recomputed in the backward
pass (``nn.remat``), all but what its kernel wrote, which is kept by name
(``ops.SAVE_KERNEL_RESIDUALS``): a linear layer's ``o`` and chunk-start
states, the attention layer's ``o`` and ``lse``. Trained through ``ctx.fit``
like any other model::

    model = Qwen3NextForCausalLM(cfg, dtype=jnp.bfloat16)
    ctx.fit(loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
            params=variables, tx=optax.adamw(1e-4, weight_decay=0.1,
                                             mask=decay_mask), ...)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.gated_delta import DEFAULT_CHUNK
from ..parallel.moe import RoutedExperts
from ..utils import scopes
from .decoder_parts import (MOE_FOLDS, apply_counted, causal_taps, count,
                            dense, embed, grouped_query_attention, head,
                            layer_stack, merge_heads, rope_rotate_half,
                            split_heads)
from .decoder_parts import routed_decay_mask as decay_mask  # noqa: F401

LINEAR, ATTENTION = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    full_attention_interval: int = 4
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512           # the router's width
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    layers_kept: tuple | None = None    # published indices; None: all of them
    experts_held: tuple | None = None   # (first, count); None: all of them
    gated_delta_chunk: int = DEFAULT_CHUNK   # the kernel's, not the model's

    @property
    def layers(self) -> tuple:
        """The published indices of the layers held, in order."""
        return tuple(self.layers_kept if self.layers_kept is not None
                     else range(self.num_hidden_layers))

    def kind(self, l: int) -> str:
        return ATTENTION if (l + 1) % self.full_attention_interval == 0 \
            else LINEAR

    @classmethod
    def from_dict(cls, cfg: dict) -> "Qwen3NextConfig":
        """From the keys of a published ``config.json``: every layer and
        expert held. One chip's cut is ``dataclasses.replace(...,
        layers_kept=..., experts_held=..., vocab_size=...)``."""
        refused = {
            "mlp_only_layers": bool(cfg.get("mlp_only_layers")),
            "decoder_sparse_step": cfg.get("decoder_sparse_step", 1) != 1,
            "use_sliding_window": bool(cfg.get("use_sliding_window")),
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings"))}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"{key} = {cfg[key]!r} is not built here: "
                                 "every layer is routed, attention is full "
                                 "and causal, RoPE unscaled, the head untied")
        same = [f.name for f in dataclasses.fields(cls)
                if f.name not in ("layers_kept", "experts_held",
                                  "gated_delta_chunk")]
        return cls(**{k: cfg[k] for k in same})

    @classmethod
    def tiny(cls) -> "Qwen3NextConfig":
        """Every mechanism at a size the CPU tests step in seconds."""
        return cls(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   full_attention_interval=2, linear_key_head_dim=8,
                   linear_value_head_dim=8, linear_num_key_heads=2,
                   linear_num_value_heads=4, moe_intermediate_size=16,
                   shared_expert_intermediate_size=16, num_experts=8,
                   num_experts_per_tok=3, gated_delta_chunk=8)


class RMSNorm0(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``, ``weight`` from zero."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        w = self.param("weight", nn.initializers.zeros, (x.shape[-1],))
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * (1.0 + w)).astype(x.dtype)


def decay_rate_init(key, shape, dt0: float = 1.0):
    """``A_log`` such that at ``a = 0`` and ``dt_bias = dt0`` a head's state
    halves every ``n`` positions, ``n`` log-uniform in [64, 8192]: ``g =
    -exp(A_log) softplus(dt0) = -ln 2 / n``."""
    life = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(64.0),
                                      math.log(8192.0)))
    return jnp.log(math.log(2.0) / (life * math.log1p(math.exp(dt0))))


class Qwen3NextGatedDeltaNet(nn.Module):
    """The linear-attention mixer: two fused projections, one convolution
    over q, k and v together, the gated delta rule a value head, a norm over
    each head's channels and then the gate."""
    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        from ..ops.gated_delta import gated_delta_rule
        c = self.cfg
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv, taps = (c.linear_key_head_dim, c.linear_value_head_dim,
                        c.linear_conv_kernel_dim)
        bsz, s, _ = u.shape
        keys, values = hk * dk, hv * dv
        with scopes.layer("mamba_in_proj"):
            qkv, z = jnp.split(dense(2 * keys + 2 * values, self.dtype,
                                     "in_proj_qkvz")(u), [2 * keys + values],
                               axis=-1)
            b, a = jnp.split(dense(2 * hv, self.dtype, "in_proj_ba")(u), 2,
                             axis=-1)
        with scopes.layer("mamba_conv"):
            bound = 1.0 / math.sqrt(taps)
            kernel = self.param(
                "conv_kernel", lambda k, shp: jax.random.uniform(
                    k, shp, jnp.float32, -bound, bound),
                (taps, 2 * keys + values)).astype(self.dtype)
            qkv = jax.nn.silu(causal_taps(qkv, kernel))
        with scopes.layer("gated_delta_prep"):
            q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)

            def unit(t):        # a head's dims to unit length, in float32
                t = t.reshape(bsz, s, hk, dk).astype(jnp.float32)
                return (t * jax.lax.rsqrt(
                    jnp.sum(t * t, -1, keepdims=True) + 1e-6)).astype(
                        self.dtype)

            beta = jax.nn.sigmoid(b.astype(jnp.float32))
            g = -jnp.exp(self.param("A_log", decay_rate_init, (hv,))) \
                * jax.nn.softplus(a.astype(jnp.float32) + self.param(
                    "dt_bias", nn.initializers.ones, (hv,)))
        o, _ = gated_delta_rule(unit(q), unit(k), v.reshape(bsz, s, hv, dv),
                                g, beta, chunk=c.gated_delta_chunk)
        # G at each chunk's end: what the chunk hands on of a state, as a log
        n_k = -(-s // c.gated_delta_chunk)
        ends = jnp.pad(g, ((0, 0), (0, n_k * c.gated_delta_chunk - s),
                           (0, 0))).reshape(bsz, n_k, -1, hv).sum(2)
        count(self, "gated_delta_chunk_log_decay_min", jnp.min(ends))
        count(self, "gated_delta_chunk_log_decay_median", jnp.median(ends))
        with scopes.layer("gdn_gated_norm"):
            of = o.astype(jnp.float32)
            w_n = self.param("norm", lambda k, shp: {
                "weight": jnp.ones(shp, jnp.float32)}, (dv,))["weight"]
            normed = of * jax.lax.rsqrt(
                jnp.mean(of * of, -1, keepdims=True) + c.rms_norm_eps) * w_n
            gated = normed.reshape(bsz, s, values) * jax.nn.silu(
                z.astype(jnp.float32))
        with scopes.layer("mamba_out_proj"):
            return dense(c.hidden_size, self.dtype, "out_proj")(
                gated.astype(self.dtype))


class Qwen3NextAttention(nn.Module):
    """Grouped-query causal attention with a gate a channel of its output.
    ``attn_fn`` as in ``models/bert.py``: ``"auto"`` is the flash kernel at
    long sequences on a TPU, dense attention elsewhere."""
    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, u):
        c = self.cfg
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        rotary = int(hd * c.partial_rotary_factor)

        def heads(name, n):
            return split_heads(dense(n * hd, self.dtype, name)(u), n)

        qg = dense(2 * h * hd, self.dtype, "q_proj")(u)
        k, v = heads("k_proj", hkv), heads("v_proj", hkv)
        with scopes.layer("attn_gate"):
            q, gate = jnp.split(split_heads(qg, h), 2, axis=-1)
            q = rope_rotate_half(RMSNorm0(c.rms_norm_eps, name="q_norm")(q),
                                 c.rope_theta, rotary)
            k = rope_rotate_half(RMSNorm0(c.rms_norm_eps, name="k_norm")(k),
                                 c.rope_theta, rotary)
        o = grouped_query_attention(q, k, v, self.attn_fn)
        with scopes.layer("attn_gate"):
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            o = merge_heads(o)
        return dense(c.hidden_size, self.dtype, "o_proj")(o)


class Qwen3NextSparseMoe(nn.Module):
    """The held experts' part of the routed layer, and the shared expert
    under its gate (every chip of a layer's group computes that alike)."""
    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, f):
        c = self.cfg
        routed = RoutedExperts(
            c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            held=c.experts_held, norm_topk_prob=c.norm_topk_prob,
            scoring="softmax", dtype=self.dtype, name="routed")(f)
        with scopes.layer("shared_expert"):
            wide = c.shared_expert_intermediate_size

            def proj(name, n=wide):
                return dense(n, self.dtype, name)

            hidden = jax.nn.silu(proj("gate_proj")(f)) * proj("up_proj")(f)
            shared = proj("down_proj", c.hidden_size)(hidden)
            gate = jax.nn.sigmoid(proj("shared_expert_gate", 1)(f).astype(
                jnp.float32))
            return routed + (gate * shared).astype(routed.dtype)


class Qwen3NextDecoderLayer(nn.Module):
    """Published layer ``l``."""
    cfg: Qwen3NextConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        u = RMSNorm0(c.rms_norm_eps, name="input_layernorm")(x)
        if c.kind(self.l) == LINEAR:
            x = x + Qwen3NextGatedDeltaNet(c, self.dtype,
                                           name="linear_attn")(u)
        else:
            x = x + Qwen3NextAttention(c, self.dtype, self.attn_fn,
                                       name="self_attn")(u)
        f = RMSNorm0(c.rms_norm_eps, name="post_attention_layernorm")(x)
        return x + Qwen3NextSparseMoe(c, self.dtype, name="mlp")(f)


# how the layers' readings of a counter fold into the step's one number
_FOLDS = {"gated_delta_chunk_log_decay_min": jnp.min,
          "gated_delta_chunk_log_decay_median": jnp.median, **MOE_FOLDS}


class Qwen3NextForCausalLM(nn.Module):
    """``ids [B, S] -> logits [B, S, V]`` in float32, the head untied. The
    layers' counters land in the ``counters`` collection:
    :meth:`apply_with_counters` hands them to the loss."""
    cfg: Qwen3NextConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, ids):
        c = self.cfg
        x, _ = embed(self, ids)
        x = layer_stack(self, Qwen3NextDecoderLayer, x, c.layers)
        return head(self, RMSNorm0(c.rms_norm_eps, name="norm")(x))

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)``. The five ``moe_*``
        of :func:`~sparkdl_tpu.parallel.moe.held_experts_ffn` summed over the
        layers; ``gated_delta_chunk_log_decay_min`` / ``_median``: the most
        negative and the median ``G`` at a chunk's end over the linear layers,
        their heads and chunks (the log of how much of a state crosses a
        chunk's boundary: at -87 and under nothing does, in float32)."""
        return apply_counted(self, variables, ids, _FOLDS)
