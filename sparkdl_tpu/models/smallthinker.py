"""SmallThinker (``model_name`` ``smallthinker_21b_instruct``:
``SmallThinker-21BA3B``; PowerInfer, arXiv:2507.20984): grouped-query
attention of two kinds by a layer's PUBLISHED index, and a routed ReGLU
expert layer in every layer whose router reads the layer's input BEFORE the
attention.

With ``l`` the published index of a layer (a cut keeps it: ``layers_kept``),
no bias in any projection and ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``::

    n1 = RMSNorm1(x);   h = x + attn_l(n1)
    n2 = RMSNorm2(h);   x' = h + sum_{e in top_k(softmax(n1 W_r))} w_e E_e(n2)
    E_e(n) = (relu(n W1_e) * (n W3_e)) W2_e                        (ReGLU)
    logits = RMSNorm(x_last) W_head                  (the head is untied)

- ``attn_l``: H query over H_kv key/value heads of ``head_dim``, causal
  softmax attention at ``1 / sqrt(head_dim)``; where ``rope_layout[l]``
  rotate-half RoPE over the whole head at ``rope_theta``, else no positions
  (NoPE); where ``sliding_window_layout[l]`` row ``t`` sees keys ``t -
  sliding_window_size + 1 .. t``, else the whole prefix. The published layout
  is ``[0, 1, 1, 1]`` in both lists: a global NoPE layer, then three RoPE
  layers under the window.
- the expert layer (:class:`~sparkdl_tpu.parallel.moe.RoutedExperts`,
  ``scoring="softmax"``, ``activation="relu"``): softmax over all
  ``moe_num_primary_experts`` in float32, the top
  ``moe_num_active_primary_experts`` over their sum, only the held experts'
  part computed. No shared expert.

Each layer is recomputed in the backward pass (``nn.remat``), all but what the
flash kernel wrote, which is kept by name (``ops.SAVE_KERNEL_RESIDUALS``: its
``o`` and ``lse``). Trained through ``ctx.fit`` like any other model::

    model = SmallThinkerForCausalLM(cfg, dtype=jnp.bfloat16)
    ctx.fit(loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
            params=variables, tx=optax.adamw(1e-4, weight_decay=0.1,
                                             mask=decay_mask), ...)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from ..ops import SAVE_KERNEL_RESIDUALS
from ..parallel.moe import RoutedExperts
from ..utils import scopes
from . import hybrid_common
from .hybrid_common import dense
from .lfm2 import rope_rotate_half
from .llama import RMSNorm
from .lm_loss import folded_counters

# weight decay on the matrices, the embedding, the head and the expert stacks
decay_mask = functools.partial(hybrid_common.decay_mask,
                               also=("w1", "w3", "w2"))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    rope_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_layout: tuple = (0, 1, 1, 1) * 13
    sliding_window_size: int = 4096
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64          # the router's width
    moe_num_active_primary_experts: int = 6
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    layers_kept: tuple | None = None    # published indices; None: all of them
    experts_held: tuple | None = None   # (first, count); None: all of them

    @property
    def layers(self) -> tuple:
        """The published indices of the layers held, in order."""
        return tuple(self.layers_kept if self.layers_kept is not None
                     else range(self.num_hidden_layers))

    def rope(self, l: int) -> bool:
        return bool(self.rope_layout[l])

    def window(self, l: int) -> int | None:
        """Layer ``l``'s window in keys, or None: the whole prefix."""
        return self.sliding_window_size if self.sliding_window_layout[l] \
            else None

    @classmethod
    def from_dict(cls, cfg: dict) -> "SmallThinkerConfig":
        """From the keys of a published ``config.json``: every layer and
        expert held. One chip's cut is ``dataclasses.replace(...,
        layers_kept=..., experts_held=..., vocab_size=...)``."""
        refused = {
            "moe_primary_router_apply_softmax":
                not cfg.get("moe_primary_router_apply_softmax", True),
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings"))}
        for key, bad in refused.items():
            if bad:
                raise ValueError(f"{key} = {cfg[key]!r} is not built here: "
                                 "the router's scores are a softmax, RoPE "
                                 "unscaled, the head untied")
        for key in ("rope_layout", "sliding_window_layout"):
            if len(cfg[key]) != cfg["num_hidden_layers"]:
                raise ValueError(f"{len(cfg[key])} entries of {key} for "
                                 f"{cfg['num_hidden_layers']} layers")
        same = [f.name for f in dataclasses.fields(cls)
                if f.name not in ("layers_kept", "experts_held", "rope_theta",
                                  "rope_layout", "sliding_window_layout")]
        return cls(rope_theta=float(cfg["rope_theta"]),
                   rope_layout=tuple(cfg["rope_layout"]),
                   sliding_window_layout=tuple(cfg["sliding_window_layout"]),
                   **{k: cfg[k] for k in same})

    @classmethod
    def tiny(cls) -> "SmallThinkerConfig":
        """Every mechanism at a size the CPU tests step in seconds: a group
        of 7 query heads, both kinds of layer, a window of 8."""
        return cls(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                   num_attention_heads=7, num_key_value_heads=1, head_dim=8,
                   rope_theta=1e4, rope_layout=(0, 1, 1, 1),
                   sliding_window_layout=(0, 1, 1, 1), sliding_window_size=8,
                   moe_ffn_hidden_size=16, moe_num_primary_experts=8,
                   moe_num_active_primary_experts=3)


class SmallThinkerAttention(nn.Module):
    """Grouped-query causal attention of published layer ``l``: with or
    without RoPE, under the window or over the whole prefix, by the layer's
    entries of the two layouts. ``attn_fn`` as in ``models/bert.py``:
    ``"auto"`` is the flash kernel at long sequences on a TPU, dense attention
    elsewhere; it is called with ``window=`` where the layer has one."""
    cfg: SmallThinkerConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, u):
        from ..ops.flash_attention import resolve_attn_fn
        from ..parallel.ring_attention import dense_attention
        c, window = self.cfg, self.cfg.window(self.l)
        bsz, s, _ = u.shape
        h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

        def heads(name, n):
            t = dense(n * hd, self.dtype, name)(u)
            return t.reshape(bsz, s, n, hd).transpose(0, 2, 1, 3)

        with scopes.layer("attn_window" if window else "attn_global"):
            q, k, v = heads("q_proj", h), heads("k_proj", hkv), \
                heads("v_proj", hkv)
            if c.rope(self.l):
                q = rope_rotate_half(q, c.rope_theta)
                k = rope_rotate_half(k, c.rope_theta)
            # each key/value head serves h // hkv query heads
            k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
            attn = resolve_attn_fn(self.attn_fn) or dense_attention
            o = attn(q, k, v, causal=True,
                     **({"window": window} if window else {}))
            o = o.transpose(0, 2, 1, 3).reshape(bsz, s, h * hd)
            return dense(c.hidden_size, self.dtype, "o_proj")(o)


class SmallThinkerDecoderLayer(nn.Module):
    """Published layer ``l``: the router reads ``n1``, the experts ``n2``."""
    cfg: SmallThinkerConfig
    l: int
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        n1 = RMSNorm(c.rms_norm_eps, name="input_layernorm")(x)
        x = x + SmallThinkerAttention(c, self.l, self.dtype, self.attn_fn,
                                      name="self_attn")(n1)
        n2 = RMSNorm(c.rms_norm_eps, name="post_attention_layernorm")(x)
        return x + RoutedExperts(
            c.moe_num_primary_experts, c.moe_num_active_primary_experts,
            c.moe_ffn_hidden_size, held=c.experts_held,
            norm_topk_prob=c.norm_topk_prob, scoring="softmax",
            activation="relu", dtype=self.dtype,
            name="block_sparse_moe")(n2, route_from=n1)


# the routed layers' counters, summed over the layers
_FOLDS = {name: jnp.sum for name in (
    "moe_assignments", "moe_assignments_held", "moe_held_load_max",
    "moe_held_load_mean", "moe_dropped", "moe_reglu_active",
    "moe_reglu_units")}


class SmallThinkerForCausalLM(nn.Module):
    """``ids [B, S] -> logits [B, S, V]`` in float32, the head untied. The
    layers' counters land in the ``counters`` collection:
    :meth:`apply_with_counters` hands them to the loss."""
    cfg: SmallThinkerConfig
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
        layer = nn.remat(SmallThinkerDecoderLayer,
                         policy=SAVE_KERNEL_RESIDUALS)
        for i, l in enumerate(c.layers):
            x = layer(c, l, self.dtype, self.attn_fn, name=f"layer_{i}")(x)
        x = RMSNorm(c.rms_norm_eps, name="norm")(x)
        with scopes.layer("lm_head_loss"):
            head = self.param("lm_head", lambda k, s: {
                "kernel": nn.initializers.normal(0.02)(k, s)},
                (c.hidden_size, c.vocab_size))["kernel"]
            return jnp.einsum("bsd,dv->bsv", x, head.astype(self.dtype),
                              preferred_element_type=jnp.float32)

    def apply_with_counters(self, variables, ids):
        """``fit``'s ``apply_fn``: ``(logits, counters)``, the seven
        ``moe_*`` of :func:`~sparkdl_tpu.parallel.moe.held_experts_ffn`
        under ReLU summed over the layers (``moe_reglu_active`` over
        ``moe_reglu_units`` is the share of the live slots' gate units the
        ReLU leaves nonzero)."""
        logits, mut = self.apply(variables, ids, mutable=["counters"])
        return logits, folded_counters(mut, _FOLDS)
