"""Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``): the SambaY
decoder-hybrid-decoder of arXiv:2507.06607, equations as in the repository's
``modeling_phi4flash.py``.

Every layer is ``x <- x + mixer_l(LN(x)); x <- x + W_down(silu(g) * u)`` with
``[g, u] = LN(x) W_gate_up``, LayerNorm with scale and bias, no bias in any
projection and no positional encoding; the head is tied to the embedding. The
mixer goes by the PUBLISHED index ``l`` of the layer (a cut keeps it:
``layers_kept``), ``n = num_hidden_layers``:

- ``l`` even, ``l <= n/2``: Mamba-1 (:func:`~sparkdl_tpu.ops.selective_scan.
  selective_scan`; recurrence, ``dt`` and ``A`` in float32). Layer ``n/2``
  also hands its scan output ``m = y`` (before the gate) to the layers after.
- ``l`` odd: differential attention (arXiv:2410.05258) over the flash kernels
  — heads pair up, two softmax maps a pair, values twice as wide as the keys,
  ``(P1 - lambda P2) V`` through an RMSNorm — under a window of
  ``sliding_window`` keys for ``l < n/2`` and over the whole prefix at
  ``l = n/2 + 1``, which hands its keys and values on.
- ``l`` even, ``l >= n/2 + 2``: a gated memory unit, ``(m * silu(x W_1)) W_2``.
- ``l`` odd, ``l >= n/2 + 3``: cross attention, its own queries over layer
  ``n/2 + 1``'s keys and values.

Each layer is recomputed in the backward pass (``nn.remat``), all but what
its kernels wrote, which is kept by name (``ops.SAVE_KERNEL_RESIDUALS``)
because the backward kernels read it and a forward kernel is the dearest
thing in a layer to run again: an attention layer's ``o`` and ``lse`` (84 +
1.3 MB a layer at 8192 positions in bf16), a Mamba layer's ``y`` and
chunk-start states (84 + 21 MB). What a layer hands on is an output of it, so
it is kept and its cotangent flows back. Trained through ``ctx.fit`` like any
other model::

    model = Phi4FlashForCausalLM(cfg, dtype=jnp.bfloat16)
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
from .decoder_parts import (apply_counted, causal_taps,  # noqa: F401
                            count, decay_mask, dense, dt_bias_init, embed,
                            head, merge_heads, split_heads)

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    num_hidden_layers: int = 32       # the published depth: the layer rule's n
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None  # hidden_size / 16
    layers_kept: tuple | None = None  # published indices; None: all of them

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or self.hidden_size // 16

    def kind_of(self, l: int) -> str:
        """The mixer of published layer ``l``."""
        half = self.num_hidden_layers // 2
        if l % self.mb_per_layer == 0:
            return MAMBA if l <= half else GMU
        if l < half:
            return WINDOW
        return FULL if l == half + 1 else CROSS

    @property
    def layers(self) -> tuple:
        """The published indices of the layers held, in order. A layer that
        reads what another hands on needs that other one held."""
        kept = tuple(self.layers_kept if self.layers_kept is not None
                     else range(self.num_hidden_layers))
        half = self.num_hidden_layers // 2
        for l in kept:
            need = {GMU: half, CROSS: half + 1}.get(self.kind_of(l))
            if need is not None and need not in kept[:kept.index(l)]:
                raise ValueError(f"layer {l} ({self.kind_of(l)}) reads what "
                                 f"layer {need} hands on, which is not kept")
        return kept

    def lambda_init(self, l: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * l)

    @classmethod
    def from_dict(cls, cfg: dict) -> "Phi4FlashConfig":
        """From the keys of a published ``config.json`` (it has none for the
        Mamba sizes: Mamba-1's defaults, which ``modeling_phi4flash.py``
        keeps). One chip's cut is ``dataclasses.replace(..., layers_kept=...,
        vocab_size=...)``."""
        same = ("vocab_size", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_hidden_layers", "mb_per_layer", "sliding_window",
                "layer_norm_eps")
        return cls(**{k: cfg[k] for k in same})


class _DtProj(nn.Module):
    """``r W_dt`` with a float32 result: ``dt`` is float32 from here on."""
    features: int
    dtype: Any

    @nn.compact
    def __call__(self, r):
        kernel = self.param("kernel", nn.initializers.normal(0.02),
                            (r.shape[-1], self.features))
        return jnp.einsum("bsr,rc->bsc", r.astype(self.dtype),
                          kernel.astype(self.dtype),
                          preferred_element_type=jnp.float32)


class Phi4FlashMamba(nn.Module):
    """``(out, y)``: the mixer's output and the scan's, before the gate."""
    cfg: Phi4FlashConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        from ..ops.selective_scan import selective_scan
        c = self.cfg
        di, n, taps = c.d_inner, c.mamba_d_state, c.mamba_d_conv
        with scopes.layer("mamba_in_proj"):
            u, z = jnp.split(dense(2 * di, self.dtype, "in_proj")(x), 2,
                             axis=-1)
        with scopes.layer("mamba_conv"):
            bound = 1.0 / math.sqrt(taps)
            kernel = self.param(
                "conv_kernel", lambda k, shp: jax.random.uniform(
                    k, shp, jnp.float32, -bound, bound),
                (taps, di)).astype(self.dtype)
            bias = self.param("conv_bias", nn.initializers.zeros, (di,))
            u = jax.nn.silu(causal_taps(u, kernel) + bias.astype(self.dtype))
            r, b_t, c_t = jnp.split(
                dense(c.dt_rank + 2 * n, self.dtype, "x_proj")(u),
                [c.dt_rank, c.dt_rank + n], axis=-1)
            dt = jax.nn.softplus(
                _DtProj(di, self.dtype, name="dt_proj")(r)
                + self.param("dt_bias", dt_bias_init, (di,)))
        a_log = self.param("A_log", lambda k, shp: jnp.broadcast_to(jnp.log(
            jnp.arange(1, n + 1, dtype=jnp.float32)), shp), (di, n))
        skip = self.param("D", nn.initializers.ones, (di,))
        y, last = selective_scan(u, dt, -jnp.exp(a_log), b_t, c_t, skip)
        count(self, "ssm_state_absmax", jnp.max(jnp.abs(last)))
        count(self, "ssm_dt_mean", jnp.mean(dt))
        with scopes.layer("mamba_out_proj"):
            out = dense(c.hidden_size, self.dtype, "out_proj")(
                y * jax.nn.silu(z))
        return out, y


class Phi4FlashAttention(nn.Module):
    """Differential attention of published layer ``l``: ``(out, (k, v))``,
    ``k, v [B, H_kv, S, D]`` its own keys and values, or the ones it was
    given (cross attention). ``attn_fn`` as in ``models/bert.py``: ``"auto"``
    is the flash kernel at long sequences on a TPU, dense attention
    elsewhere; it is called with ``window=`` where the layer has one and with
    values twice as wide as the keys."""
    cfg: Phi4FlashConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x, kv=None):
        from ..ops.flash_attention import resolve_attn_fn
        from ..parallel.ring_attention import dense_attention
        c, kind = self.cfg, self.cfg.kind_of(self.l)
        bsz, s, _ = x.shape
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        pairs, rep = h // 2, h // hkv
        scope = "cross_attention" if kind == CROSS else "diff_attention"
        with scopes.layer(scope):
            if kind == CROSS:
                q = split_heads(dense(h * hd, self.dtype, "Wq")(x), h)
                k, v = kv
            else:
                q, k, v = jnp.split(
                    dense((h + 2 * hkv) * hd, self.dtype, "Wqkv")(x),
                    [h * hd, (h + hkv) * hd], axis=-1)
                q, k, v = split_heads(q, h), split_heads(k, hkv), \
                    split_heads(v, hkv)
            # query head 2j + e reads key head 2 (j // rep) + e, and both
            # heads of a pair the values [v1 ; v2] of that key/value pair
            k_all = jnp.repeat(k.reshape(bsz, hkv // 2, 2, s, hd), rep,
                               axis=1).reshape(bsz, h, s, hd)
            v_wide = v.reshape(bsz, hkv // 2, 2, s, hd).transpose(
                0, 1, 3, 2, 4).reshape(bsz, hkv // 2, s, 2 * hd)
            v_all = jnp.repeat(v_wide, 2 * rep, axis=1)
            attn = resolve_attn_fn(self.attn_fn) or dense_attention
            window = {"window": c.sliding_window} if kind == WINDOW else {}
            o = attn(q, k_all, v_all, causal=True, **window)
            o = o.reshape(bsz, pairs, 2, s, 2 * hd).astype(jnp.float32)
            vec = {n: self.param(n, nn.initializers.normal(0.1), (hd,))
                   for n in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2")}
            lam_init = c.lambda_init(self.l)
            lam = jnp.exp(jnp.sum(vec["lambda_q1"] * vec["lambda_k1"])) \
                - jnp.exp(jnp.sum(vec["lambda_q2"] * vec["lambda_k2"])) \
                + lam_init
            count(self, "diff_lambda_mean", lam)
            o = o[:, :, 0] - lam * o[:, :, 1]
            scale = self.param("subln", lambda k_, shp: {
                "scale": jnp.ones(shp, jnp.float32)}, (2 * hd,))["scale"]
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                  + c.layer_norm_eps) * scale
            o = merge_heads((o * (1.0 - lam_init)).astype(self.dtype))
            return dense(c.hidden_size, self.dtype, "out_proj")(o), (k, v)


class Phi4FlashGatedMemory(nn.Module):
    cfg: Phi4FlashConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, m):
        with scopes.layer("gated_memory"):
            gate = jax.nn.silu(dense(self.cfg.d_inner, self.dtype,
                                      "in_proj")(x))
            return dense(self.cfg.hidden_size, self.dtype, "out_proj")(
                m.astype(self.dtype) * gate)


class Phi4FlashMLP(nn.Module):
    cfg: Phi4FlashConfig
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        gate, up = jnp.split(dense(2 * self.cfg.intermediate_size,
                                    self.dtype, "gate_up_proj")(x), 2,
                             axis=-1)
        return dense(self.cfg.hidden_size, self.dtype, "down_proj")(
            jax.nn.silu(gate) * up)


class Phi4FlashDecoderLayer(nn.Module):
    """``(x', handed on)`` of published layer ``l``; ``shared`` is what an
    earlier layer handed on to this one (``m``, or ``(k, v)``)."""
    cfg: Phi4FlashConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x, shared=None):
        c, kind = self.cfg, self.cfg.kind_of(self.l)
        half = c.num_hidden_layers // 2

        def norm(name):
            return nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=self.dtype,
                                name=name)

        u, handed = norm("input_layernorm")(x), None
        if kind == MAMBA:
            mix, y = Phi4FlashMamba(c, self.dtype, name="mamba")(u)
            handed = y if self.l == half else None
        elif kind == GMU:
            mix = Phi4FlashGatedMemory(c, self.dtype, name="gmu")(u, shared)
        else:
            mix, kv = Phi4FlashAttention(c, self.l, self.dtype, self.attn_fn,
                                         name="attn")(u, shared)
            handed = kv if kind == FULL else None
        x = x + mix
        return x + Phi4FlashMLP(c, self.dtype, name="mlp")(
            norm("post_attention_layernorm")(x)), handed


# how the layers' readings of a counter fold into the step's one number
_FOLDS = {"ssm_state_absmax": jnp.max, "ssm_dt_mean": jnp.mean,
          "diff_lambda_mean": jnp.mean}


class Phi4FlashForCausalLM(nn.Module):
    """``ids [B, S] -> logits [B, S, V]`` in float32, head tied to the
    embedding. The layers' counters land in the ``counters`` collection:
    :meth:`apply_with_counters` hands them to the loss."""
    cfg: Phi4FlashConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, ids):
        c = self.cfg
        x, emb = embed(self, ids)
        layer = nn.remat(Phi4FlashDecoderLayer, policy=SAVE_KERNEL_RESIDUALS)
        handed = {}
        for i, l in enumerate(c.layers):
            kind = c.kind_of(l)
            x, out = layer(c, l, self.dtype, self.attn_fn,
                           name=f"layer_{i}")(
                x, handed.get({GMU: MAMBA, CROSS: FULL}.get(kind)))
            if out is not None:
                handed[kind] = out
        x = nn.LayerNorm(epsilon=c.layer_norm_eps, dtype=self.dtype,
                         name="final_layernorm")(x)
        return head(self, x, tied=emb)

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)``. ``ssm_state_absmax``
        is the largest ``|h|`` at a sequence's end over the Mamba layers (the
        scan's numeric range), ``ssm_dt_mean`` their mean step size,
        ``diff_lambda_mean`` the mean ``lambda`` over the attention layers
        (the second map's weight, which drifts with training)."""
        return apply_counted(self, variables, ids, _FOLDS)
