"""Granite 4.0-H (``model_type`` ``granitemoehybrid`` with no routed experts:
``granite-4.0-h-micro``): Mamba-2 layers (arXiv:2405.21060) with a
grouped-query attention layer among them, equations as in the
``granitemoehybrid`` / Bamba modelling code.

With ``l`` the PUBLISHED index of a layer (a cut keeps it: ``layers_kept``),
RMSNorm with a scale, no bias in any projection, no positional encoding of
any kind (``position_embedding_type`` ``nope``) and four multipliers::

    x0 = embedding_multiplier * E[ids]
    x <- x + residual_multiplier * mixer_l(RMSNorm(x))
    x <- x + residual_multiplier * W_out(silu(g) * u),  [g, u] = RMSNorm(x) W_in
    logits = RMSNorm(x_last) E^T / logits_scaling        (tied embedding E)

- ``layer_types[l] == "attention"``: ``q`` as H heads, ``k``, ``v`` as H_kv
  heads; ``softmax(q k^T * attention_multiplier + causal) v``. The attention
  functions fix their scale at ``1 / sqrt(d)``, so the model hands them
  ``q * attention_multiplier * sqrt(d)`` (0.125 as published: a power of two,
  exact in every dtype).
- ``layer_types[l] == "mamba"``: ``[z, xBC, dt] = h W_in``;
  ``xBC = silu(causal_depthwise_conv(xBC) + b)``; ``[x, B, C] = xBC``;
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one number a head;
  the recurrence (:func:`~sparkdl_tpu.ops.ssd_scan.ssd_scan`; ``dt``, ``A``
  and the state in float32); then the gate BEFORE the norm:
  ``RMSNorm(y * silu(z)) W_out`` over all ``d_inner`` channels (one group).

Each layer is recomputed in the backward pass (``nn.remat``), all but what
its kernel wrote, which is kept by name (``ops.SAVE_KERNEL_RESIDUALS``)
because the backward kernels read it and a forward kernel is the dearest
thing in a layer to run again: a Mamba-2 layer's ``y`` and chunk-start states
(67 + 67 MB a layer at 8192 positions), the attention layer's ``o`` and
``lse`` (34 + 1 MB). Trained through ``ctx.fit`` like any other model::

    model = GraniteHybridForCausalLM(cfg, dtype=jnp.bfloat16)
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

from ..ops import SAVE_KERNEL_RESIDUALS
from ..utils import scopes
from .hybrid_common import (count, decay_mask, dense,  # noqa: F401
                            dt_bias_init)
from .llama import RMSNorm
from .lm_loss import folded_counters

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    layers_kept: tuple | None = None   # published indices; None: all of them

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def layers(self) -> tuple:
        """The published indices of the layers held, in order."""
        return tuple(self.layers_kept if self.layers_kept is not None
                     else range(len(self.layer_types)))

    @classmethod
    def from_dict(cls, cfg: dict) -> "GraniteHybridConfig":
        """From the keys of a published ``config.json``. One chip's cut is
        ``dataclasses.replace(..., layers_kept=..., vocab_size=...)``."""
        if cfg.get("num_local_experts", 0) > 0:
            raise ValueError(
                f"num_local_experts = {cfg['num_local_experts']}: the routed "
                "siblings of this family are not held (no expert layer "
                "beside these mixers)")
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise ValueError("position_embedding_type "
                             f"{cfg['position_embedding_type']!r}: only "
                             "'nope' (no positional encoding) is held")
        if not cfg.get("tie_word_embeddings", True):
            raise ValueError("an untied head is not held")
        if cfg["mamba_expand"] * cfg["hidden_size"] != \
                cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
            raise ValueError("mamba_expand * hidden_size is not "
                             "mamba_n_heads * mamba_d_head")
        same = [f.name for f in dataclasses.fields(cls)
                if f.name not in ("layer_types", "layers_kept")]
        return cls(layer_types=tuple(cfg["layer_types"]),
                   **{k: cfg[k] for k in same})


class GraniteHybridMamba(nn.Module):
    """The Mamba-2 mixer: one projection split three ways, a convolution
    over ``x``, ``B`` and ``C`` together, one ``B`` / ``C`` group for all
    heads, the gate before an RMSNorm over the ``d_inner`` channels."""
    cfg: GraniteHybridConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u):
        from ..ops.ssd_scan import chunk_decay, ssd_scan
        c = self.cfg
        di, n, taps = c.d_inner, c.mamba_d_state, c.mamba_d_conv
        heads, groups = c.mamba_n_heads, c.mamba_n_groups
        bsz, s, _ = u.shape
        wide = di + 2 * groups * n
        with scopes.layer("mamba_in_proj"):
            z, xbc, dt = jnp.split(
                dense(di + wide + heads, self.dtype, "in_proj")(u),
                [di, di + wide], axis=-1)
        with scopes.layer("mamba_conv"):
            bound = 1.0 / math.sqrt(taps)
            kernel = self.param(
                "conv_kernel", lambda k, shp: jax.random.uniform(
                    k, shp, jnp.float32, -bound, bound),
                (taps, wide)).astype(self.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros, (wide,))
            g = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
            xbc = jax.nn.silu(sum(kernel[j] * g[:, j:j + s]
                                  for j in range(taps))
                              + bias.astype(self.dtype))
            x, b_t, c_t = jnp.split(xbc, [di, di + groups * n], axis=-1)
            dt = jax.nn.softplus(
                dt.astype(jnp.float32)
                + self.param("dt_bias", dt_bias_init, (heads,)))
        a_neg = -jnp.exp(self.param(
            "A_log", lambda k, shp: jnp.log(jax.random.uniform(
                k, shp, jnp.float32, 1.0, 16.0)), (heads,)))
        skip = self.param("D", nn.initializers.ones, (heads,))
        y, last = ssd_scan(
            x.reshape(bsz, s, heads, c.mamba_d_head), dt, a_neg,
            b_t.reshape(bsz, s, groups, n), c_t.reshape(bsz, s, groups, n),
            skip, chunk=c.mamba_chunk_size)
        count(self, "ssm_state_absmax", jnp.max(jnp.abs(last)))
        count(self, "ssm_dt_mean", jnp.mean(dt))
        # dt > 0 and A < 0: the running sum falls, and its least entry is at
        # some chunk's end. In the log domain: exp of it underflows float32
        # on the fastest heads (-570 to -670 a chunk of 256 as seeded)
        count(self, "ssd_chunk_log_decay_min", jnp.min(
            chunk_decay(dt, a_neg, c.mamba_chunk_size)))
        with scopes.layer("mamba_gated_norm"):
            gated = y.reshape(bsz, s, di).astype(jnp.float32) \
                * jax.nn.silu(z.astype(jnp.float32))
            gated = RMSNorm(c.rms_norm_eps, name="norm")(gated)
        with scopes.layer("mamba_out_proj"):
            return dense(c.hidden_size, self.dtype, "out_proj")(
                gated.astype(self.dtype))


class GraniteHybridAttention(nn.Module):
    """Grouped-query causal attention with no positional encoding and the
    softmax scale ``attention_multiplier``. ``attn_fn`` as in
    ``models/bert.py``: ``"auto"`` is the flash kernel at long sequences on
    a TPU, dense attention elsewhere."""
    cfg: GraniteHybridConfig
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
            t = dense(n * hd, self.dtype, name)(u)
            return t.reshape(bsz, s, n, hd).transpose(0, 2, 1, 3)

        with scopes.layer("nope_attention"):
            # the attention functions scale by 1 / sqrt(d)
            q = heads("q_proj", h) * (c.attention_multiplier * math.sqrt(hd))
            k, v = (jnp.repeat(heads(name, hkv), h // hkv, axis=1)
                    for name in ("k_proj", "v_proj"))
            attn = resolve_attn_fn(self.attn_fn) or dense_attention
            o = attn(q, k, v, causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(bsz, s, h * hd)
            return dense(c.hidden_size, self.dtype, "o_proj")(o)


class GraniteHybridMLP(nn.Module):
    cfg: GraniteHybridConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate, up = jnp.split(dense(2 * self.cfg.shared_intermediate_size,
                                    self.dtype, "input_linear")(x), 2,
                             axis=-1)
        return dense(self.cfg.hidden_size, self.dtype, "output_linear")(
            jax.nn.silu(gate) * up)


class GraniteHybridDecoderLayer(nn.Module):
    """Published layer ``l``."""
    cfg: GraniteHybridConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        u = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
        if c.layer_types[self.l] == MAMBA:
            mix = GraniteHybridMamba(c, self.dtype, name="mamba")(u)
        else:
            mix = GraniteHybridAttention(c, self.dtype, self.attn_fn,
                                         name="self_attn")(u)
        x = x + c.residual_multiplier * mix
        f = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
        return x + c.residual_multiplier * GraniteHybridMLP(
            c, self.dtype, name="shared_mlp")(f)


# how the layers' readings of a counter fold into the step's one number
_FOLDS = {"ssm_state_absmax": jnp.max, "ssm_dt_mean": jnp.mean,
          "ssd_chunk_log_decay_min": jnp.min}


class GraniteHybridForCausalLM(nn.Module):
    """``ids [B, S] -> logits [B, S, V]`` in float32, head tied to the
    embedding. The layers' counters land in the ``counters`` collection:
    :meth:`apply_with_counters` hands them to the loss."""
    cfg: GraniteHybridConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, ids):
        c = self.cfg
        emb = self.param("embed_tokens", lambda k, s: {
            "embedding": nn.initializers.normal(0.02)(k, s)},
            (c.vocab_size, c.hidden_size))["embedding"]
        with scopes.layer("embed_tokens"):
            x = (jnp.take(emb, ids, axis=0)
                 * c.embedding_multiplier).astype(self.dtype)
        layer = nn.remat(GraniteHybridDecoderLayer,
                         policy=SAVE_KERNEL_RESIDUALS)
        for i, l in enumerate(c.layers):
            x = layer(c, l, self.dtype, self.attn_fn, name=f"layer_{i}")(x)
        x = RMSNorm(c.rms_norm_eps, name="final_layernorm")(x)
        with scopes.layer("lm_head_loss"):
            return jnp.einsum("bsd,vd->bsv", x, emb.astype(self.dtype),
                              preferred_element_type=jnp.float32) \
                / c.logits_scaling

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)`` over the Mamba
        layers. ``ssm_state_absmax`` is the largest ``|H|`` at a sequence's
        end (the scan's numeric range), ``ssm_dt_mean`` the mean step size,
        ``ssd_chunk_log_decay_min`` the smallest ``s_Q`` over chunks and
        heads: the log of how much of a state a chunk hands on at the least
        (under -87 its ``exp`` is 0 in float32: such a head hands on nothing),
        and the range the chunked form's exponents live in."""
        logits, mut = self.apply(variables, ids, mutable=["counters"])
        return logits, folded_counters(mut, _FOLDS)
