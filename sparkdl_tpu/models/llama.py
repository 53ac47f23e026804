"""Llama-style decoder-only transformer (flax) with LoRA — the stretch
family (BASELINE config 5: "Llama-3-8B LoRA fine-tune via XlaRunner +
registerUDF batch inference").

TPU-first design:

- module names (``q_proj``/``k_proj``/``v_proj``/``o_proj``,
  ``gate_proj``/``up_proj``/``down_proj``, ``embed_tokens``, ``lm_head``)
  match ``parallel.transformer_tp_rules`` — the 2-D mesh TP layout applies
  by pattern, no per-model sharding code;
- LoRA adapters are ``lora_a``/``lora_b`` Dense submodules inside each
  projection, so ``parallel.lora_rules`` inherits the base kernel's
  partitioning and ``lora_mask`` freezes everything else for optax;
- attention is pluggable: dense (default) or sequence-parallel ring/Ulysses
  from ``parallel.ring_attention`` via ``attn_fn`` — long context rides the
  ICI ring instead of blowing HBM;
- GQA via ``jnp.repeat`` of KV heads (static), RoPE precomputed per call
  (fuses), RMSNorm in f32, everything else dtype-parameterized for bf16.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from collections.abc import Mapping
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .lm_loss import causal_lm_loss_fn  # noqa: F401  (re-exported)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    # LoRA: rank 0 disables adapters entirely (no extra params).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ("q_proj", "v_proj")

    @classmethod
    def llama3_8b(cls, lora_rank: int = 0) -> "LlamaConfig":
        return cls(lora_rank=lora_rank)

    @classmethod
    def tiny(cls, lora_rank: int = 0) -> "LlamaConfig":
        """For tests/dryruns: 2 layers, 128-wide, GQA 4:2."""
        return cls(vocab_size=512, hidden_size=128, num_layers=2,
                   num_heads=4, num_kv_heads=2, intermediate_size=256,
                   rope_theta=10000.0, lora_rank=lora_rank)

    @classmethod
    def small(cls, lora_rank: int = 0) -> "LlamaConfig":
        """~1B-class config (TinyLlama-shaped) — fits one v5e chip with KV
        cache; the single-chip serving-bench model."""
        return cls(vocab_size=32000, hidden_size=2048, num_layers=16,
                   num_heads=16, num_kv_heads=8, intermediate_size=5632,
                   rope_theta=10000.0, lora_rank=lora_rank)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


class QuantDense(nn.Module):
    """Weight-quantized Dense (ISSUE 18): when the stored ``kernel`` is
    int8 the matmul runs against the codes and folds the absmax
    per-output-channel ``kernel_scale`` AFTER the contraction
    (``(x @ q)·s`` — the scale is constant down each output column), so
    no dequantized copy of the weight ever materializes. Param paths
    mirror ``nn.Dense`` (same ``kernel``/``bias`` names under the same
    module name), so :func:`quantize_params` converts a float
    checkpoint in place and the ``parallel.transformer_tp_rules``
    patterns keyed on ``.../kernel`` still apply; ``kernel_scale``
    rides alongside and shards with the kernel's output dim where that
    dim is column-parallel. A float kernel (an unconverted checkpoint)
    runs the plain dense path unchanged."""
    features: int
    use_bias: bool = False
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        x = x.astype(self.dtype)
        if kernel.dtype == jnp.int8:
            scale = self.param("kernel_scale", nn.initializers.ones,
                               (self.features,))
            y = jnp.dot(x, kernel.astype(self.dtype))
            # f32 accumulate for the dequant multiply, back to dtype —
            # a bf16 scale would throw away most of the absmax's
            # precision for free.
            y = (y * scale.astype(jnp.float32)).astype(self.dtype)
        else:
            y = jnp.dot(x, kernel.astype(self.dtype))
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,))
            y = y + bias.astype(self.dtype)
        return y


class LoRADense(nn.Module):
    """Dense with optional LoRA: y = xW + (alpha/r)·(xA)B.

    A is gaussian-init, B zero-init (adapter starts as identity). The base
    ``kernel`` and the adapters are separate leaves so the base can be frozen
    (``lora_mask``) while adapters train. ``quant`` ('int8') swaps the
    base for :class:`QuantDense` — same param paths, dequant folded
    into the matmul; adapters stay float (they are ~0.1% of params).
    """
    features: int
    rank: int = 0
    alpha: float = 16.0
    use_bias: bool = False
    dtype: Any = jnp.float32
    quant: Any = None

    @nn.compact
    def __call__(self, x):
        if self.quant is not None:
            y = QuantDense(self.features, use_bias=self.use_bias,
                           dtype=self.dtype, name="base")(x)
        else:
            y = nn.Dense(self.features, use_bias=self.use_bias,
                         dtype=self.dtype, name="base")(x)
        if self.rank > 0:
            a = nn.Dense(self.rank, use_bias=False, dtype=self.dtype,
                         kernel_init=nn.initializers.normal(0.02),
                         name="lora_a")(x)
            b = nn.Dense(self.features, use_bias=False, dtype=self.dtype,
                         kernel_init=nn.initializers.zeros,
                         name="lora_b")(a)
            y = y + (self.alpha / self.rank) * b
        return y


def rope(x, positions, theta: float):
    """Rotary position embedding. x: [B, H, S, D]; positions: [S] (shared)
    or [B, S] (per-row — left-padded serving, where row r's first real
    token sits at a different slot)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [...,S,D/2]
    if angles.ndim == 3:
        angles = angles[:, None]  # [B, 1, S, D/2] broadcasts over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    r1 = xf1 * cos - xf2 * sin
    r2 = xf2 * cos + xf1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _gather_leaf(leaf, tables):
    """One pool leaf ``[P, Hkv, bs, hd]`` through ``[B, MB]`` tables →
    the dense per-slot view ``[B, Hkv, MB·bs, hd]`` (the per-leaf body
    of :func:`_gather_view`, shared with the in-layer kernel
    fallback)."""
    v = leaf[tables]                       # [B, MB, Hkv, bs, hd]
    v = jnp.transpose(v, (0, 2, 1, 3, 4))  # [B, Hkv, MB, bs, hd]
    return v.reshape(v.shape[0], v.shape[1], -1, v.shape[4])


def _table_blocks(tables, bi, real):
    """Physical pool block for each logical block index ``bi``, with
    every position whose ``real`` flag is False routed to the trash
    block 0 — the trash-route-NEVER-clamp rule shared by the paged
    chunk prefill and the in-layer decode/verify writes (an
    out-of-table or pad position must land where nobody reads, never
    slide back over a committed block). ``tables`` is indexed along
    its last axis: a ``[MB]`` row (the chunk primitive) or
    ``[B, MB]`` slot tables (the slot-step paths); the ``min`` clamp
    only keeps the gather in-bounds — clamped positions are ~real and
    route to trash."""
    mb = tables.shape[-1]
    safe = jnp.minimum(bi, mb - 1)
    blk = tables[safe] if tables.ndim == 1 else \
        jnp.take_along_axis(tables, safe, axis=1)
    return jnp.where(real, blk, 0)


def _dense_slot_attention(q, k_all, v_all, qpos, pads, cfg, dtype):
    """Masked dense causal-vs-cache attention for the per-slot
    (``slot_cur``) serving paths — ONE definition shared by the paged
    and unpaged kernel fallbacks: query i of row r attends cache
    columns ``[pads[r], qpos[r, i]]``. This masking math is the
    token-identity contract the kernel-equivalence tests pin — keep it
    single-sourced. GQA runs against the untiled cache (group axis in
    the einsum, no ``jnp.repeat`` of K/V); masked columns get exactly
    zero probability (exp underflow of -1e30), so table-aliased
    garbage never perturbs live rows bitwise."""
    B, S = qpos.shape
    hd = cfg.head_dim
    rep = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, cfg.num_kv_heads, rep, S, hd)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k_all) / math.sqrt(hd)
    col = jnp.arange(k_all.shape[2])[None, None, :]
    valid = (col <= qpos[..., None]) & (col >= pads[:, None, None])
    s = jnp.where(valid[:, None, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(dtype)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p, v_all).reshape(
        B, cfg.num_heads, S, hd)


# ---------------------------------------------------------------------------
# Block-quantized KV (ISSUE 18): the paged pool's K/V leaves store
# int8 (or fp8) CODES and a parallel ``kv_scale`` [pool_blocks, Hkv, 2]
# f32 plane holds one absmax scale per (physical block, kv head,
# K-or-V): dequant is codes·scale. The scale is a property of the
# PHYSICAL block, so radix grafts (table pointer copies) and
# copy-on-write (block row copies) move scales with their codes for
# free, and the flash-decode kernel dequantizes in-VMEM — no float
# copy of the cache ever exists in HBM.
# ---------------------------------------------------------------------------

KV_QUANT_DTYPES: dict = {"int8": (jnp.int8, 127.0),
                         "fp8": (jnp.float8_e4m3fn, 448.0)}


def kv_quant_spec(name: str):
    """(storage dtype, qmax) for a KV quant mode name — raises with the
    available modes on a miss, never silently falls back."""
    try:
        return KV_QUANT_DTYPES[name]
    except KeyError:
        raise ValueError(
            f"unknown KV quant dtype {name!r}; available: "
            f"{sorted(KV_QUANT_DTYPES)}") from None


def kv_quant_name(dtype) -> Optional[str]:
    """Quant mode name for a stored K/V dtype, or None for a float
    cache — quantization is detected from the POOL, not a model flag,
    so one compiled model serves both."""
    for name, (dt, _) in KV_QUANT_DTYPES.items():
        if jnp.dtype(dtype) == jnp.dtype(dt):
            return name
    return None


def _kv_qmax(dtype) -> float:
    for dt, qmax in KV_QUANT_DTYPES.values():
        if jnp.dtype(dtype) == jnp.dtype(dt):
            return qmax
    raise ValueError(f"not a KV quant storage dtype: {dtype}")


def _requant(x, qdt, qmax):
    """f32 values (already divided by scale) → codes: round+clip for
    int storage, clip+cast for fp8 (the cast itself rounds)."""
    if jnp.issubdtype(jnp.dtype(qdt), jnp.integer):
        x = jnp.round(x)
    return jnp.clip(x, -qmax, qmax).astype(qdt)


def _quant_insert_rows(codes, plane, ch, blk, off, rows):
    """Insert float ``rows`` [N, Hkv, hd] at pool positions
    ``(blk[n], :, off[n], :)`` of a quantized ``codes`` leaf,
    maintaining the shared per-(block, head) scale ``plane[..., ch]``
    (ch 0 = K, 1 = V). ONE routine serves the in-layer decode/verify
    writes, the chunk-prefill scatter and the blocking-prefill scatter.

    Scale discipline, in scatter order:
    1. an ``off == 0`` row is a block's FIRST write (positions fill
       sequentially under the write-frontier invariant), so its scale
       resets to 0 — a freed-then-reallocated block must not inherit
       the previous tenant's (possibly larger) scale forever;
    2. scatter-max of the incoming rows' absmax/qmax grows the scale
       (duplicate blocks in ``blk`` accumulate — a multi-row write into
       one block yields the block's true absmax);
    3. surviving rows of every touched block requantize by
       old_s/new_s — exact (round of an integer) when the scale did
       not grow, one ≤½-LSB rounding when it did; ratio 0 (fresh or
       virgin block) wipes stale codes;
    4. the new rows quantize at the final scale.
    Trash-routed rows (blk == 0) follow the same path — block 0 is
    never read live, and duplicate trash writes stay deterministic
    (identical content per duplicate). Returns ``(codes, plane)``."""
    qdt = codes.dtype
    qmax = _kv_qmax(qdt)
    rows = rows.astype(jnp.float32)
    first = off == 0
    plane = plane.at[jnp.where(first, blk, 0), :, ch].set(0.0)
    amax = jnp.max(jnp.abs(rows), axis=-1)          # [N, Hkv]
    old_s = plane[blk, :, ch]
    plane = plane.at[blk, :, ch].max(amax / qmax)
    new_s = plane[blk, :, ch]
    safe = jnp.maximum(new_s, 1e-30)
    ratio = jnp.where(new_s > 0, old_s / safe, 0.0)
    cur = codes[blk].astype(jnp.float32) * ratio[:, :, None, None]
    codes = codes.at[blk].set(_requant(cur, qdt, qmax))
    q = _requant(rows / safe[:, :, None], qdt, qmax)
    return codes.at[blk, :, off, :].set(q), plane


def _gather_dequant(leaf, plane, ch, tables, dtype):
    """Dense dequantized per-slot view of one quantized pool leaf —
    the quant twin of :func:`_gather_leaf`: gather codes through the
    tables, multiply by each block's per-head scale, cast to the
    compute dtype. Kernel-fallback and reference-view path only (the
    kernel itself dequantizes in-VMEM)."""
    v = _gather_leaf(leaf, tables).astype(jnp.float32)
    s = plane[tables][..., ch]                       # [B, MB, Hkv]
    s = jnp.repeat(jnp.transpose(s, (0, 2, 1)), leaf.shape[2], axis=2)
    return (v * s[..., None]).astype(dtype)


def _map_attn_dicts(fn, tree, *rest):
    """tree_map at the attention-DICT level: apply ``fn`` to every
    mapping holding both "k" and "v" (the per-layer cache dicts),
    recursing elsewhere; ``rest`` trees zip-walk by key. The quantized
    pool needs cross-leaf work (codes and ``kv_scale`` move together,
    and the scatter's dense twin LACKS the scale leaf), which
    leaf-level ``tree_map`` cannot express."""
    if isinstance(tree, Mapping):
        if "k" in tree and "v" in tree:
            return fn(dict(tree), *[dict(r) for r in rest])
        return {k: _map_attn_dicts(fn, v, *[r[k] for r in rest])
                for k, v in tree.items()}
    return tree


def _pool_quant(pool) -> Optional[str]:
    """KV quant mode of a pool ('int8'/'fp8'/None) from its stored K/V
    dtype."""
    for leaf in jax.tree_util.tree_leaves(pool):
        if getattr(leaf, "ndim", 0) == 4:
            return kv_quant_name(leaf.dtype)
    raise ValueError("pool holds no 4-D K/V leaves")


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.float32
    # (q,k,v,causal=...) → o; "auto" (default) resolves to the Pallas flash
    # kernel on TPU and in-model dense attention elsewhere (ops.resolve_attn_fn)
    attn_fn: Any = "auto"
    # Mesh(('tp',)) of the tensor-parallel serving backends (ISSUE 15):
    # a pallas_call does not partition under GSPMD, so the decode
    # kernels dispatch under shard_map over this mesh's head axis
    # instead (parallel.sharding.head_sharded_kernel). None everywhere
    # else — the single-device paths are untouched.
    kernel_mesh: Any = None
    # 'int8' → projection base kernels run QuantDense (ISSUE 18); pair
    # with params converted by quantize_params.
    weight_quant: Any = None

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, pad_lens=None,
                 first_chunk: bool = False, slot_cur=None,
                 block_tables=None):
        c, d = self.cfg, self.dtype
        B, S, _ = x.shape
        hd = c.head_dim

        def proj(name, heads, lora):
            dense = LoRADense(heads * hd, rank=c.lora_rank if lora else 0,
                              alpha=c.lora_alpha, dtype=d,
                              quant=self.weight_quant, name=name)
            out = dense(x)
            return out.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)

        q = proj("q_proj", c.num_heads, "q_proj" in c.lora_targets)
        k = proj("k_proj", c.num_kv_heads, "k_proj" in c.lora_targets)
        v = proj("v_proj", c.num_kv_heads, "v_proj" in c.lora_targets)

        rep = c.num_heads // c.num_kv_heads  # GQA tiling factor (static)

        from ..ops.flash_attention import resolve_attn_fn
        resolved_attn = resolve_attn_fn(self.attn_fn)

        def prefill_attn_fn(need_mask: bool):
            """The attention to run at prefill: the resolved attn_fn when
            it can express the left-pad mask contract (flash can; ring/
            Ulysses cannot — they fall back to the dense cache path)."""
            fn = resolved_attn
            if fn is None or not need_mask:
                return fn
            import inspect
            try:
                params = inspect.signature(fn).parameters
            except (TypeError, ValueError):
                return None
            # Only an explicit kv_mask parameter proves support — a
            # **kwargs wrapper would swallow the mask and silently attend
            # to pad tokens.
            return fn if "kv_mask" in params else None

        if decode:
            # KV-cache serving path. The cache is sized by the *init* call's
            # sequence length (= max_len); apply() calls then write chunks —
            # the whole prompt at prefill, one token per decode step — at the
            # running index. See ``init_cache``/``generate``.
            # ``pad_lens`` [B] (left-padded serving): row r's first pad_lens[r]
            # cache slots are dead — masked out of attention, and rope
            # positions count from the first REAL token, so ONE compiled
            # prefill serves every prompt length (udf.registerGenerationUDF).
            # PREFILL (S > 1, cache index 0) runs through ``attn_fn`` when it
            # supports the mask contract: causal over the square S-slice +
            # kv_mask for pad slots — long prompts never materialize the
            # O(S·max_len) score matrix (flash is the TPU default), and a
            # ring/Ulysses attn_fn shards the prefill's S^2 compute over the
            # sp mesh axis (sequence-parallel serving; unpadded prompts).
            # Per-token DECODE steps (S == 1) pair with the flash prefill:
            # when the resolved attn_fn is the flash kernel, the step runs
            # through ops.flash_decode — HBM traffic O(cur), not
            # O(max_len), dead cache blocks are never fetched. Any other
            # attn_fn (dense, ring/Ulysses — sequence-sharding doesn't
            # apply to a replicated cache) keeps the dense cache path.
            k_cache = self.variable("cache", "k", jnp.zeros,
                                    (B, c.num_kv_heads, S, hd), d)
            v_cache = self.variable("cache", "v", jnp.zeros,
                                    (B, c.num_kv_heads, S, hd), d)
            idx = self.variable("cache", "idx",
                                lambda: jnp.zeros((), jnp.int32))
            if slot_cur is not None and not self.is_initializing():
                # Continuous-batching decode step / speculative verify
                # window (serving.engine): every cache row is an
                # INDEPENDENT in-flight request at its own fill index
                # ``slot_cur[r]``. S == 1 is the decode step — the token
                # writes at the frontier and attention masks per row to
                # [pad_lens[r], slot_cur[r]]. S == k+1 is the VERIFY
                # window (ISSUE 12): row r's S tokens (current token +
                # k drafts) write at [slot_cur[r], slot_cur[r]+S) and
                # query i attends [pad_lens[r], slot_cur[r]+i] — dense
                # causal-vs-cache attention under the chunked-prefill
                # write-frontier invariant: every row at/past the
                # frontier is (re)written before any attention can read
                # it, so rejected drafts leave inert garbage the next
                # real write overwrites. Writes past the row's end are
                # DROPPED (scatter mode="drop"), never clamped back
                # over committed rows. The shared ``idx`` variable is
                # NOT consulted or advanced (the engine owns per-slot
                # fill state host-side), so slot refills never disturb
                # the other rows' decode.
                pads = (jnp.zeros((B,), jnp.int32) if pad_lens is None
                        else pad_lens)
                qpos = slot_cur[:, None] + jnp.arange(S)[None, :]  # [B,S]
                pos = jnp.maximum(qpos - pads[:, None], 0)
                q = rope(q, pos, c.rope_theta)
                k = rope(k, pos, c.rope_theta)
                if block_tables is not None:
                    # PAGED slot step (ISSUE 15): the cache leaves are
                    # the SHARED pool [pool_blocks, Hkv, bs, hd] and
                    # ``block_tables`` [B, max_blocks] names each slot's
                    # blocks. Writes scatter through the table (the
                    # final-chunk trash-routing rule: a position past
                    # the table — an overhanging draft column — lands
                    # on trash block 0 where no live range reads);
                    # attention reads the pool THROUGH the table: via
                    # the paged flash-decode kernel when it engages (no
                    # gathered view exists in the program, per-step HBM
                    # traffic O(cur) per slot), else a per-layer dense
                    # gather view — the portable fallback, the exact
                    # PR 11 math.
                    bs_p = k_cache.value.shape[2]
                    mb = block_tables.shape[1]
                    bi = qpos // bs_p
                    blk = _table_blocks(block_tables, bi, bi < mb)
                    off = qpos % bs_p
                    quant = kv_quant_name(k_cache.value.dtype)
                    scl = None
                    if quant is None:
                        k_pool = k_cache.value.at[blk, :, off, :].set(
                            k.transpose(0, 2, 1, 3).astype(
                                k_cache.value.dtype))
                        v_pool = v_cache.value.at[blk, :, off, :].set(
                            v.transpose(0, 2, 1, 3).astype(
                                v_cache.value.dtype))
                    else:
                        # QUANTIZED pool (ISSUE 18): the leaves are
                        # codes and the ``kv_scale`` plane rides the
                        # same cache collection — declared here (only
                        # on the quantized paged path) so mut["cache"]
                        # carries it and float pools keep their exact
                        # pytree. Rows flatten to [B·S] for the shared
                        # insert primitive.
                        kv_scale = self.variable(
                            "cache", "kv_scale", jnp.zeros,
                            (k_cache.value.shape[0], c.num_kv_heads, 2),
                            jnp.float32)
                        fb, fo = blk.reshape(-1), off.reshape(-1)
                        kr = k.transpose(0, 2, 1, 3).reshape(
                            -1, c.num_kv_heads, hd)
                        vr = v.transpose(0, 2, 1, 3).reshape(
                            -1, c.num_kv_heads, hd)
                        scl = kv_scale.value
                        k_pool, scl = _quant_insert_rows(
                            k_cache.value, scl, 0, fb, fo, kr)
                        v_pool, scl = _quant_insert_rows(
                            v_cache.value, scl, 1, fb, fo, vr)
                        kv_scale.value = scl
                    k_cache.value, v_cache.value = k_pool, v_pool
                    from ..ops import paged_flash_decode as pfd
                    o = None
                    dec = pfd.paged_decode_fn_for(resolved_attn,
                                                  self.kernel_mesh)
                    if dec is not None:
                        reason = pfd.support_reason(bs_p, kv_dtype=quant)
                        if reason is None:
                            # the scale plane rides positionally so the
                            # head-sharded shard_map wrapper shards it
                            # with its heads (float pools pass nothing).
                            extra = () if scl is None else (scl,)
                            o = dec(q, k_pool, v_pool, block_tables,
                                    slot_cur, pads, *extra)
                        elif pfd.kernel_mode() == "force":
                            pfd.warn_fallback(reason)
                    if o is None:
                        if quant is None:
                            k_all = _gather_leaf(k_pool, block_tables)
                            v_all = _gather_leaf(v_pool, block_tables)
                        else:
                            k_all = _gather_dequant(k_pool, scl, 0,
                                                    block_tables, d)
                            v_all = _gather_dequant(v_pool, scl, 1,
                                                    block_tables, d)
                        o = _dense_slot_attention(q, k_all, v_all,
                                                  qpos, pads, c, d)
                else:
                    max_len = k_cache.value.shape[2]
                    rows_ix = jnp.arange(B)[:, None]
                    cols = jnp.where(qpos < max_len, qpos,
                                     max_len)  # OOB→drop
                    k_all = k_cache.value.at[rows_ix, :, cols, :].set(
                        k.transpose(0, 2, 1, 3), mode="drop")
                    v_all = v_cache.value.at[rows_ix, :, cols, :].set(
                        v.transpose(0, 2, 1, 3), mode="drop")
                    k_cache.value, v_cache.value = k_all, v_all
                    o = None
                    if S == 1:
                        from ..ops import flash_decode as fd
                        dec = fd.decode_fn_for(resolved_attn,
                                               self.kernel_mesh)
                        if dec is not None and fd.supports(max_len):
                            # per-row cur: each slot's HBM traffic
                            # scales with its own fill level (the
                            # kernel's dead-block clamp is per row).
                            o = dec(q, k_all, v_all, slot_cur + 1, pads)
                    if o is None:
                        o = _dense_slot_attention(q, k_all, v_all,
                                                  qpos, pads, c, d)
                # falls through to the shared o_proj tail below — the
                # serving path must ride the exact same output
                # projection as static generate() (token identity).
            elif not self.is_initializing():
                cur = idx.value
                if pad_lens is None:
                    pos = cur + jnp.arange(S)  # [S], shared across rows
                    valid_extra = None
                else:
                    # per-row positions relative to the first real token
                    pos = jnp.maximum(
                        cur + jnp.arange(S)[None, :]
                        - pad_lens[:, None], 0)  # [B, S]
                    valid_extra = pad_lens
                q = rope(q, pos, c.rope_theta)
                k = rope(k, pos, c.rope_theta)
                k_all = jax.lax.dynamic_update_slice(
                    k_cache.value, k, (0, 0, cur, 0))
                v_all = jax.lax.dynamic_update_slice(
                    v_cache.value, v, (0, 0, cur, 0))
                k_cache.value, v_cache.value = k_all, v_all
                idx.value = cur + S
                # Prefill through attn_fn over the square S-slice:
                # generate()'s contract writes the whole prompt at cache
                # index 0, where every slot past S is causally dead — so
                # attention over (q, k, v) with causal + a pad-slot
                # kv_mask equals the masked dense-vs-cache compute,
                # without materializing O(S·max_len) scores (flash), or
                # sharding the S^2 compute over the sp axis (ring).
                # Gated on the EXPLICIT first_chunk=True opt-in (only
                # _prefill passes it): a chunked multi-call prefill must
                # attend earlier cache too, so the default takes the
                # dense full-cache path.
                flash = (prefill_attn_fn(valid_extra is not None)
                         if S > 1 and first_chunk else None)
                o = None
                if flash is not None:
                    kf = jnp.repeat(k, rep, axis=1) if rep != 1 else k
                    vf = jnp.repeat(v, rep, axis=1) if rep != 1 else v
                    # Shape constraints of a sequence-parallel attn_fn
                    # (e.g. a ring whose sp axis doesn't divide S)
                    # surface at TRACE time as ValueError/TypeError —
                    # fall back to the dense path instead of turning a
                    # previously working generate() into a crash. The
                    # Pallas kernels are exempt: an error from them is
                    # a lowering failure, and densifying it would make
                    # a run without the kernel look like one with it.
                    # Other exception types (a genuinely broken
                    # attn_fn) propagate too.
                    from ..ops.flash_attention import (
                        adaptive_attention, flash_attention)
                    try:
                        if valid_extra is None:
                            o = flash(q, kf, vf, causal=True)
                        else:
                            kv_mask = (jnp.arange(S)[None, :]
                                       >= valid_extra[:, None]).astype(
                                           jnp.float32)
                            o = flash(q, kf, vf, causal=True,
                                      kv_mask=kv_mask)
                    except (TypeError, ValueError) as e:
                        if flash in (adaptive_attention, flash_attention):
                            raise
                        _warn_prefill_fallback(flash, e)
                        o = None
                if o is None and S == 1:
                    from ..ops import flash_decode as fd
                    dec = fd.decode_fn_for(resolved_attn,
                                           self.kernel_mesh)
                    if dec is not None and fd.supports(k_all.shape[2]):
                        # slots < cur+1 are live (the step's own token
                        # attends to itself — the dense path's col <= row
                        # with row == cur); left-pad slots masked per row.
                        o = dec(q, k_all, v_all, cur + 1, pad_lens)
                if o is None:
                    # grouped-query attention against the UNtiled cache:
                    # fold the GQA tiling into the einsum group axis instead
                    # of jnp.repeat-copying the whole cache every step
                    max_len = k_all.shape[2]
                    qg = q.reshape(B, c.num_kv_heads, rep, S, hd)
                    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg,
                                   k_all) / math.sqrt(hd)
                    col = jnp.arange(max_len)[None, :]
                    row = cur + jnp.arange(S)[:, None]
                    valid = (col <= row)  # [S, max_len] causal-vs-cache
                    if valid_extra is not None:
                        # [B, S, max_len]: also exclude each row's pad slots
                        valid = valid[None] & (
                            col[None] >= valid_extra[:, None, None])
                        valid = valid[:, None, None]  # [B,1,1,S,max_len]
                    s = jnp.where(valid, s.astype(jnp.float32), -1e30)
                    p = jax.nn.softmax(s, axis=-1).astype(d)
                    o = jnp.einsum("bgrqk,bgkd->bgrqd", p, v_all).reshape(
                        B, c.num_heads, S, hd)
            else:
                o = jnp.zeros((B, c.num_heads, S, hd), d)
        else:
            q = rope(q, positions, c.rope_theta)
            k = rope(k, positions, c.rope_theta)
            if rep != 1:
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            if resolved_attn is not None:
                o = resolved_attn(q, k, v, causal=True)
            else:
                s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
                mask = jnp.tril(jnp.ones((S, S), bool))
                s = jnp.where(mask, s.astype(jnp.float32), -1e30)
                p = jax.nn.softmax(s, axis=-1).astype(d)
                o = jnp.einsum("bhqk,bhkd->bhqd", p, v)

        o = o.transpose(0, 2, 1, 3).reshape(B, S, c.num_heads * hd)
        return LoRADense(c.hidden_size, rank=c.lora_rank if "o_proj" in
                         c.lora_targets else 0, alpha=c.lora_alpha,
                         dtype=d, quant=self.weight_quant,
                         name="o_proj")(o)


class LlamaMLP(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.float32
    weight_quant: Any = None

    @nn.compact
    def __call__(self, x):
        c, d = self.cfg, self.dtype
        lr = c.lora_rank
        wq = self.weight_quant
        gate = LoRADense(c.intermediate_size, rank=lr if "gate_proj" in
                         c.lora_targets else 0, dtype=d, quant=wq,
                         name="gate_proj")(x)
        up = LoRADense(c.intermediate_size, rank=lr if "up_proj" in
                       c.lora_targets else 0, dtype=d, quant=wq,
                       name="up_proj")(x)
        h = nn.silu(gate) * up
        return LoRADense(c.hidden_size, rank=lr if "down_proj" in
                         c.lora_targets else 0, dtype=d, quant=wq,
                         name="down_proj")(h)


class LlamaLayer(nn.Module):
    cfg: LlamaConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"
    kernel_mesh: Any = None
    weight_quant: Any = None

    @nn.compact
    def __call__(self, x, positions, decode: bool = False, pad_lens=None,
                 first_chunk: bool = False, slot_cur=None,
                 block_tables=None):
        c = self.cfg
        x = x + LlamaAttention(c, self.dtype, self.attn_fn,
                               self.kernel_mesh,
                               weight_quant=self.weight_quant,
                               name="attn")(
            RMSNorm(c.rms_norm_eps, name="attn_norm")(x), positions, decode,
            pad_lens, first_chunk, slot_cur, block_tables)
        x = x + LlamaMLP(c, self.dtype, weight_quant=self.weight_quant,
                         name="mlp")(
            RMSNorm(c.rms_norm_eps, name="mlp_norm")(x))
        return x


class LlamaModel(nn.Module):
    """Token ids [B, S] → logits [B, S, vocab]."""
    cfg: LlamaConfig
    dtype: Any = jnp.float32
    attn_fn: Any = "auto"  # flash on TPU, dense elsewhere; or a callable
    kernel_mesh: Any = None  # Mesh(('tp',)) → shard_map decode kernels
    weight_quant: Any = None  # 'int8' + quantize_params → int8 matmuls

    @nn.compact
    def __call__(self, input_ids, decode: bool = False, pad_lens=None,
                 first_chunk: bool = False, slot_cur=None,
                 block_tables=None):
        """``first_chunk`` (decode mode, static): True ONLY when this
        apply() writes at cache index 0 — generate()'s single-call prefill
        passes it explicitly (``_prefill``). It enables the square flash
        fast path, which attends over the current chunk alone; at any
        other cache index that would silently ignore earlier cache, so
        the default is False and unaware multi-call chunked-prefill
        callers get the (correct) dense attention over the full cache.

        ``slot_cur`` (decode mode, ``[B]`` int32, traced): the
        continuous-batching step — row r writes its S tokens at its OWN
        cache fill index ``[slot_cur[r], slot_cur[r]+S)`` and query i
        attends ``[pad_lens[r], slot_cur[r]+i]`` of its row. S == 1 is
        the per-slot decode step; S == k+1 is the speculative VERIFY
        window (``slot_verify_step``). The shared ``idx`` cache
        variable is neither read nor advanced (the serving engine owns
        per-slot fill state).

        ``block_tables`` (decode mode with ``slot_cur``, ``[B,
        max_blocks]`` int32, traced): the PAGED slot step (ISSUE 15) —
        the provided cache leaves are the shared ``[pool_blocks, Hkv,
        block_size, hd]`` pool and row r's logical position p lives at
        pool position ``(block_tables[r, p // bs], p % bs)``. Writes
        scatter through the table (positions past it trash-route to
        block 0); attention reads the pool through the table — the
        paged flash-decode kernel when it engages
        (``ops.paged_flash_decode``), else a per-layer dense gather
        view."""
        c = self.cfg
        if pad_lens is not None and not decode:
            raise ValueError(
                "pad_lens is a KV-cache serving feature (decode=True); the "
                "training path has no left-pad masking — feed right-padded "
                "batches with a loss mask instead")
        S = input_ids.shape[1]
        if slot_cur is not None and not decode:
            raise ValueError(
                "slot_cur is the per-slot decode step / verify-window "
                f"feature (decode=True); got decode={decode} — prefill a "
                "slot via prefill_into_slot instead")
        if block_tables is not None and slot_cur is None:
            raise ValueError(
                "block_tables is the paged slot-step feature: the cache "
                "must be the shared block pool and every row needs its "
                "own fill index — pass slot_cur (see "
                "paged_slot_decode_step)")
        positions = jnp.arange(S)
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     name="embed_tokens")(input_ids)
        for i in range(c.num_layers):
            x = LlamaLayer(c, self.dtype, self.attn_fn, self.kernel_mesh,
                           weight_quant=self.weight_quant,
                           name=f"layer_{i}")(x, positions, decode,
                                              pad_lens, first_chunk,
                                              slot_cur, block_tables)
        x = RMSNorm(c.rms_norm_eps, name="final_norm")(x)
        return nn.Dense(c.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(x)


# ---------------------------------------------------------------------------
# Generation (KV-cache serving — the registerUDF inference path of
# BASELINE config 5)
# ---------------------------------------------------------------------------

def init_cache(model: LlamaModel, batch_size: int, max_len: int,
               kv_sharding=None, scalar_sharding=None):
    """Zeroed KV cache pytree sized (batch, kv_heads, max_len, head_dim) per
    layer. Built via ``jax.eval_shape`` over ``init`` — no parameter compute,
    just the variable-tree structure.

    ``kv_sharding`` (a ``jax.sharding.Sharding``) places the 4-D K/V
    leaves at creation — the tensor-parallel serving backend passes the
    head-sharded ``Mesh(('tp',))`` spec so a big cache is born
    distributed (each device allocates its ``1/tp`` shard) instead of
    materialized on one device and re-shuffled. ``scalar_sharding``
    places the scalar ``idx`` leaves (replicated under a mesh)."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((batch_size, max_len), jnp.int32),
                           decode=True))

    def make(s):
        sh = kv_sharding if len(s.shape) == 4 else scalar_sharding
        if sh is not None:
            return jax.make_array_from_callback(
                s.shape, sh, lambda idx: np.zeros(
                    tuple(len(range(*i.indices(d)))
                          for i, d in zip(idx, s.shape)), s.dtype))
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree_util.tree_map(make, shapes["cache"])


def _sample(logits, key, temperature: float, top_k: int = 0,
            top_p: float = 1.0):
    """Greedy (temperature<=0) or temperature sampling with optional
    top-k / nucleus (top-p) truncation. All branches are static (compiled
    into the decode program); the filtering is rank-based so shapes stay
    fixed."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if (top_k and top_k > 0) or top_p < 1.0:
        # ONE sort serves both filters (this runs inside the per-token
        # decode scan — a second O(V log V) sort per step is pure waste).
        sl = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
        if top_k and top_k > 0:
            ranks = jnp.arange(sl.shape[-1])
            sl = jnp.where(ranks < top_k, sl, -jnp.inf)
        if top_p < 1.0:
            probs = jax.nn.softmax(sl, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix with cumulative prob >= top_p
            # (rank 0 always kept: cum - probs is 0 there)
            sl = jnp.where(cum - probs < top_p, sl, -jnp.inf)
        # cutoff = smallest surviving logit; ties at the cutoff stay in
        cutoff = jnp.min(jnp.where(jnp.isfinite(sl), sl, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("model",))
def _prefill(model, params, prompt_ids, cache, pad_lens=None):
    """Whole prompt in one chunked cache write → (last-pos logits, cache).
    Compiled per (batch, prompt_len, max_len) signature. With left-padded
    prompts (``pad_lens``), ONE (batch, Lmax, max_len) program serves every
    prompt length — the newest real token is always the last position."""
    logits, mut = model.apply({"params": params, "cache": cache},
                              prompt_ids, decode=True, pad_lens=pad_lens,
                              first_chunk=True, mutable=["cache"])
    return logits[:, -1].astype(jnp.float32), mut["cache"]


@functools.partial(
    jax.jit, static_argnames=("model", "max_new_tokens", "temperature",
                              "top_k", "top_p", "eos_id"))
def _decode(model, params, cache, last_logits, rng, pad_lens=None, *,
            max_new_tokens: int, temperature: float, top_k: int = 0,
            top_p: float = 1.0, eos_id: int | None = None):
    """One token per step; compiled per (batch, max_len) signature —
    independent of the prompt length, so varying-length prompts with a
    shared cache size reuse ONE decode program.

    Without ``eos_id``: a ``lax.scan`` of exactly max_new_tokens steps.
    With ``eos_id``: a ``lax.while_loop`` that STOPS as soon as every row
    has emitted eos — an all-done batch pays only the steps it used, not
    max_new_tokens (round-3 verdict Next #6: compute-side early exit, not
    just host-side tail trimming). Unwritten output slots hold eos_id,
    which is exactly what the fixed-length scan emitted for done rows.

    Returns ``(tokens [B, max_new_tokens], n_steps)`` where n_steps is the
    number of decode-loop iterations actually executed (== max_new_tokens
    for the scan path)."""
    rng, key = jax.random.split(rng)
    tok = _sample(last_logits, key, temperature, top_k, top_p)

    def model_step(cache, tok, rng):
        logits, mut = model.apply({"params": params, "cache": cache},
                                  tok[:, None], decode=True,
                                  pad_lens=pad_lens, mutable=["cache"])
        rng, key = jax.random.split(rng)
        nxt = _sample(logits[:, -1].astype(jnp.float32), key, temperature,
                      top_k, top_p)
        return mut["cache"], nxt, rng

    if eos_id is None:
        # each step emits the already-sampled token and samples the next;
        # after n steps the emitted sequence is exactly the n new tokens
        def step(carry, _):
            cache, nxt, rng = model_step(*carry)
            return (cache, nxt, rng), carry[1]

        _, toks = jax.lax.scan(step, (cache, tok, rng), None,
                               length=max_new_tokens)
        return jnp.moveaxis(toks, 0, 1), jnp.asarray(max_new_tokens)

    out0 = jnp.full((tok.shape[0], max_new_tokens), eos_id, jnp.int32)

    def cond(carry):
        _, _, _, done, i, _ = carry
        return (i < max_new_tokens) & ~jnp.all(done)

    def body(carry):
        cache, tok, rng, done, i, out = carry
        out = out.at[:, i].set(tok)
        cache, nxt, rng = model_step(cache, tok, rng)
        nxt = jnp.where(done, eos_id, nxt)
        return (cache, nxt, rng, done | (nxt == eos_id), i + 1, out)

    carry = jax.lax.while_loop(
        cond, body,
        (cache, tok, rng, tok == eos_id, jnp.asarray(0), out0))
    return carry[5], carry[4]


def left_pad_prompts(prompts, pad_id: int = 0, pad_to: int | None = None):
    """Variable-length prompt lists → (ids [B, Lmax] left-padded, pad_lens
    [B]). Left padding keeps every row's newest token at the last position,
    so one prefill program + one decode program serve mixed lengths.

    ``pad_to`` pins Lmax externally — chunked callers (the streaming
    generation UDF) pass the column-wide max so every chunk shares one
    compiled (rows, Lmax) signature."""
    import numpy as np
    lens = [len(p) for p in prompts]
    if min(lens, default=0) < 1:
        raise ValueError("every prompt needs at least one token id")
    lmax = max(lens)
    if pad_to is not None:
        if pad_to < lmax:
            raise ValueError(f"pad_to={pad_to} < longest prompt {lmax}")
        lmax = pad_to
    ids = np.full((len(prompts), lmax), pad_id, dtype=np.int32)
    for r, p in enumerate(prompts):
        ids[r, lmax - len(p):] = np.asarray(p, dtype=np.int32)
    return ids, np.asarray([lmax - n for n in lens], dtype=np.int32)


_warned_attn_fn_ignored = False
_warned_prefill_fallback: set = set()


def _warn_prefill_fallback(fn, err) -> None:
    """Once per (fn, error) pair host-side — not once per layer per trace
    (a 32-layer model would otherwise emit 32 identical warnings)."""
    key = (repr(fn), f"{type(err).__name__}: {err}")
    if key not in _warned_prefill_fallback:
        import logging
        logging.getLogger(__name__).warning(
            "prefill attn_fn %s failed at trace time (%s); using dense "
            "cache attention", key[0], key[1])
        _warned_prefill_fallback.add(key)


def generate(model: LlamaModel, variables, prompt_ids, max_new_tokens: int,
             temperature: float = 0.0, rng=None, pad_to: int | None = None,
             pad_lens=None, top_k: int = 0, top_p: float = 1.0,
             eos_id: int | None = None, return_steps: bool = False):
    """Greedy / temperature sampling with a KV cache.

    Two jitted programs: a prefill pass writes the prompt's cache in a
    single chunked update, then a decode loop emits one token per step
    (compiled per (batch, cache-size) only). For mixed-length columns,
    left-pad with :func:`left_pad_prompts` and pass ``pad_lens`` — the
    prefill then also compiles ONCE for the whole column (positions count
    from each row's first real token; pad slots are masked out of
    attention). With ``eos_id`` the decode is a ``lax.while_loop`` that
    exits as soon as every row has finished — the compute-side early stop.

    ``prompt_ids``: [B, Lp] int32, Lp >= 1. Returns [B, Lp+max_new_tokens]
    (left-pad slots included when ``pad_lens`` is used — strip
    ``pad_lens[r]`` leading ids per row). With ``return_steps=True``
    returns ``(ids, n_decode_steps)`` — the observable for early-exit
    tests and serving telemetry.
    """
    global _warned_attn_fn_ignored
    # Warn only for an EXPLICITLY configured attn_fn — the "auto" default
    # resolving to flash for prefill is not a user setting being ignored.
    if callable(model.attn_fn) and not _warned_attn_fn_ignored:
        # Host-side, once — not inside the traced apply (fires per trace).
        import logging
        logging.getLogger(__name__).warning(
            "LlamaModel.attn_fn applies to the PREFILL pass during "
            "generation (flash/ring/Ulysses; left-padded prefill "
            "additionally needs kv_mask support, which only flash has); "
            "per-token decode runs the cache-aware flash decode kernel "
            "when attn_fn is the flash kernel (ops.flash_decode), and "
            "dense cache attention for every other attn_fn")
        _warned_attn_fn_ignored = True
    if not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p} — 0 would "
                         f"mask every token and degenerate to id 0")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0 (0 disables), got {top_k}")
    if eos_id is not None and (isinstance(eos_id, bool)
                               or not isinstance(eos_id, (int, np.integer))):
        raise TypeError(f"eos_id must be an int token id or None, "
                        f"got {eos_id!r}")
    prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
    b, lp = prompt_ids.shape
    if lp < 1:
        raise ValueError("prompt_ids must contain at least one token")
    max_len = pad_to or (lp + max_new_tokens)
    if max_len < lp + max_new_tokens:
        raise ValueError(f"pad_to={pad_to} < prompt+new ="
                         f" {lp + max_new_tokens}")
    from ..ops import flash_decode as _fd
    from ..ops.flash_attention import resolve_attn_fn as _resolve_attn
    if (pad_to is None
            and _fd.decode_fn_for(_resolve_attn(model.attn_fn)) is not None
            and not _fd.supports(max_len)):
        # Round the DEFAULT cache size up to the decode kernel's KV-block
        # multiple so the flash decode path engages without an explicit
        # pad_to; a few spare KV slots cost far less than every step
        # reading the cache dense. An EXPLICIT pad_to is honored verbatim
        # — callers sizing the cache to an HBM budget must get exactly
        # what they asked for (a non-multiple then takes the dense path,
        # by supports()).
        max_len = ((max_len + _fd.KV_BLOCK - 1)
                   // _fd.KV_BLOCK) * _fd.KV_BLOCK
    params = variables["params"] if "params" in variables else variables
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if pad_lens is not None:
        pad_lens = jnp.asarray(pad_lens, jnp.int32)
    cache = init_cache(model, b, int(max_len))
    last_logits, cache = _prefill(model, params, prompt_ids, cache, pad_lens)
    toks, n_steps = _decode(model, params, cache, last_logits, rng, pad_lens,
                            max_new_tokens=int(max_new_tokens),
                            temperature=float(temperature), top_k=int(top_k),
                            top_p=float(top_p),
                            eos_id=None if eos_id is None else int(eos_id))
    ids = jnp.concatenate([prompt_ids, toks], axis=1)
    return (ids, int(n_steps)) if return_steps else ids


# ---------------------------------------------------------------------------
# Slot-level serving primitives (continuous batching — serving.engine)
# ---------------------------------------------------------------------------
# The static generate() path above runs whole batches in lockstep: every
# row prefills together and the decode loop drains together. The two
# functions below are the per-SLOT halves the in-flight batching engine
# composes instead: ``prefill_into_slot`` writes one new request's cache
# into one row of a shared slot cache (the other rows' in-flight state
# untouched), and ``slot_decode_step`` advances EVERY slot one token at
# its own fill index. Both are jitted with donated caches; the decode
# step compiles once per (num_slots, max_len) and never re-traces across
# refills — slot/cur/pad all ride as traced operands.


@functools.partial(
    jax.jit, static_argnames=("model", "temperature", "top_k", "top_p"),
    donate_argnames=("cache",))
def prefill_into_slot(model, params, prompt_ids, pad_len, cache, slot, rng,
                      *, temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0):
    """Prefill ONE request into row ``slot`` of the engine's slot cache.

    ``prompt_ids``: ``[1, Lb]`` int32, left-padded to the engine's bucket
    length (``pad_len``: ``[1]`` int32 — same contract as
    :func:`left_pad_prompts`); ``cache``: the ``[num_slots, ...]`` slot
    cache (donated); ``slot``: traced int32 row index. The prompt runs
    through the standard first-chunk prefill against a private
    ``[1, Lb]``-length scratch cache (so compute is O(Lb²), never
    O(Lb·max_len)), and the written K/V rows are scattered into the slot
    row — positions count from the first real token, exactly the
    ``generate()`` left-pad contract, so a refilled slot's logits are
    bit-identical to a fresh static run of the same prompt.

    Compiled once per bucket length ``Lb``; ``slot``/``pad_len`` are
    traced, so refills into different slots share one program. Returns
    ``(first_token [1] int32, cache)``.
    """
    lb = prompt_ids.shape[1]
    small_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, lb), jnp.int32), decode=True))
    small = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), small_shapes["cache"])
    logits, mut = model.apply({"params": params, "cache": small},
                              prompt_ids, decode=True, pad_lens=pad_len,
                              first_chunk=True, mutable=["cache"])

    def scatter(big, sm):
        # K/V leaves are [slots, Hkv, L, hd] vs [1, Hkv, Lb, hd]; the
        # scalar ``idx`` leaf is the static path's shared fill index —
        # the engine tracks per-slot fill host-side, so it stays as-is.
        if getattr(sm, "ndim", 0) == 4:
            return jax.lax.dynamic_update_slice(
                big, sm.astype(big.dtype), (slot, 0, 0, 0))
        return big

    cache = jax.tree_util.tree_map(scatter, cache, mut["cache"])
    tok = _sample(logits[:, -1].astype(jnp.float32), rng, temperature,
                  top_k, top_p)
    return tok, cache


@functools.partial(
    jax.jit,
    static_argnames=("model", "window", "temperature", "top_k", "top_p"),
    donate_argnames=("cache",))
def prefill_chunk_into_slot(model, params, chunk_ids, cache, slot, offset,
                            n_valid, rng, *, window: int | None = None,
                            temperature: float = 0.0,
                            top_k: int = 0, top_p: float = 1.0):
    """Consume ``C`` prompt tokens of ONE request into row ``slot`` at
    cache positions ``[offset, offset + C)`` — the stall-free serving
    engine's chunk primitive: a long prompt is fed through this in
    fixed-size chunks *interleaved with* ``slot_decode_step``, so a
    refill never monopolizes the device for a whole O(L²) prefill.

    ``chunk_ids``: ``[1, C]`` int32 — the chunked-prefill contract is
    **zero-aligned** (no left padding: the prompt's token ``i`` lives at
    cache position ``i``, rope position ``i``), the FINAL chunk
    right-pads with zeros and ``n_valid`` (traced int32 scalar) names
    how many of this chunk's tokens are real. The pad tail's K/V rows
    are written but harmless: causality bounds every real query at or
    left of itself, and the decode step overwrites position ``L`` first
    (each write lands before the attention that could read it).
    ``cache``: the ``[num_slots, ...]`` slot cache (donated); ``slot``/
    ``offset`` traced, so chunked prefill compiles one program per
    (C, window) where the bucketed whole-prompt path compiles one per
    bucket. ``window`` (static, default the full row) bounds how many
    of the slot's rows the chunk touches: the caller passes the
    request's chunk-aligned total prompt length, so a 48-token prompt's
    chunks gather/attend/scatter a 48-row window instead of paying
    O(C·max_len) attention and full-row copies per chunk — window
    values are chunk multiples, so the program count stays bounded by
    max_len/C. Every row the chunk may attend ([0, offset+C)) is inside
    the window by construction.

    The chunk runs through the model's standard multi-call decode path
    (write at the fill index, dense attention over the window with the
    causal-vs-cache mask) against the slot's own row gathered as a B=1
    cache — attending only to that slot's rows, never the neighbors'.
    Returns ``(tok [1] int32, cache)`` where ``tok`` is sampled from the
    logits at the last REAL position — meaningful only on the final
    chunk (the engine delivers it as the request's first token).
    """
    def gather(leaf):
        # K/V leaves are [slots, Hkv, L, hd]; scalar leaves are the
        # per-layer ``idx`` fill index — pinned to ``offset`` so the
        # multi-call decode path writes this chunk at the right rows.
        if getattr(leaf, "ndim", 0) == 4:
            w = leaf.shape[2] if window is None \
                else min(int(window), leaf.shape[2])
            return jax.lax.dynamic_slice(
                leaf, (slot, 0, 0, 0),
                (1, leaf.shape[1], w, leaf.shape[3]))
        return jnp.asarray(offset, jnp.int32)

    row = jax.tree_util.tree_map(gather, cache)
    logits, mut = model.apply({"params": params, "cache": row},
                              chunk_ids, decode=True, mutable=["cache"])

    def scatter(big, sm):
        if getattr(sm, "ndim", 0) == 4:
            return jax.lax.dynamic_update_slice(
                big, sm.astype(big.dtype), (slot, 0, 0, 0))
        return big  # the shared static-path idx leaf stays as-is

    cache = jax.tree_util.tree_map(scatter, cache, mut["cache"])
    # Logits at the last REAL token of the chunk (a padded final chunk's
    # tail logits are garbage); traced index -> one program.
    last = jax.lax.dynamic_slice(
        logits, (0, jnp.maximum(n_valid - 1, 0), 0),
        (1, 1, logits.shape[2]))[:, 0]
    tok = _sample(last.astype(jnp.float32), rng, temperature, top_k, top_p)
    return tok, cache


@functools.partial(
    jax.jit, static_argnames=("model", "temperature", "top_k", "top_p"),
    donate_argnames=("cache",))
def slot_decode_step(model, params, cache, tokens, slot_cur, pad_lens, rng,
                     *, temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 1.0):
    """One in-flight batching decode iteration: every slot advances one
    token at its OWN fill index.

    ``tokens``: ``[num_slots]`` int32 (each slot's current token — for
    idle slots the value is irrelevant, their output is discarded
    host-side); ``slot_cur``: ``[num_slots]`` int32 per-slot fill
    indices (the token writes there; attention masks to
    ``[pad_lens[r], slot_cur[r]]``); ``cache`` donated. Compiled ONCE
    per (num_slots, max_len) signature — the engine's steady-state hot
    program; slot refills and retirements never re-trace it. Returns
    ``(next_tokens [num_slots] int32, cache)``.
    """
    logits, mut = model.apply({"params": params, "cache": cache},
                              tokens[:, None], decode=True,
                              pad_lens=pad_lens, slot_cur=slot_cur,
                              mutable=["cache"])
    nxt = _sample(logits[:, -1].astype(jnp.float32), rng, temperature,
                  top_k, top_p)
    return nxt, mut["cache"]


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("cache",))
def slot_verify_step(model, params, cache, tokens, slot_cur, pad_lens):
    """Speculative VERIFY window — the fourth jitted donated-cache slot
    primitive (ISSUE 12): one batched target forward checks k drafted
    tokens per slot in a single program dispatch.

    ``tokens``: ``[num_slots, k+1]`` int32 — column 0 is each slot's
    current token (exactly what ``slot_decode_step`` would consume),
    columns 1..k its draft candidates (pad freely: a slot drafting
    fewer than k just computes discarded columns). Row r writes its
    k+1 K/V rows at ``[slot_cur[r], slot_cur[r]+k]`` and query i
    attends dense causal-vs-cache to ``[pad_lens[r], slot_cur[r]+i]``
    — the chunked-prefill write-frontier invariant makes the
    misspeculated tail inert: rejected rows sit at/past the new
    frontier and are overwritten before any attention can read them,
    so **reject is a pure host-side ``cur`` non-advance** — no cache
    rollback program exists or is needed. Writes past ``max_len`` are
    dropped in-graph (never clamped back over committed rows); the
    engine separately caps how many proposals it COMMITS to rows that
    were really written.

    Returns ``(proposals [num_slots, k+1] int32, cache)`` where
    ``proposals[r, i]`` is the greedy argmax of the logits at position
    ``slot_cur[r] + i`` — the token the target emits after consuming
    ``tokens[r, :i+1]``. Greedy-only by construction (argmax IS the
    acceptance rule); the engine gates speculation on
    ``temperature <= 0``. Compiled ONCE per (num_slots, k+1, max_len)
    — drafting, acceptance and rejection are host-side and never
    re-trace it.

    Arithmetic note: the window's logits come from the dense
    causal-vs-cache attention path (S > 1 never rides the
    flash-decode kernel), so on a backend whose flash and dense
    reductions round differently an exact logit TIE could argmax-flip
    a token relative to a flash-decoded ``generate()`` stream; the
    pinned backends (CPU dense + stub) are exact, and the serve
    bench's ``spec_token_identical`` gate is the on-chip check."""
    logits, mut = model.apply({"params": params, "cache": cache},
                              tokens, decode=True, pad_lens=pad_lens,
                              slot_cur=slot_cur, mutable=["cache"])
    props = jnp.argmax(logits.astype(jnp.float32), axis=-1)
    return props.astype(jnp.int32), mut["cache"]


# ---------------------------------------------------------------------------
# Paged slot primitives (block-table serving — ISSUE 11)
# ---------------------------------------------------------------------------
# The three slot primitives above address a PRIVATE [num_slots, ...,
# max_len, ...] cache row per slot: HBM is reserved at num_slots x
# max_len whatever requests actually use. The paged variants below
# address ONE shared pool of [pool_blocks, Hkv, block_size, hd] K/V
# blocks per layer through a per-slot block TABLE ([max_blocks] int32,
# traced): logical cache position p of a slot lives at pool position
# (table[p // block_size], p % block_size). The decode / verify
# primitives route the pool + tables straight into apply(): each layer
# writes only the newly produced positions through the table (a shared
# prefix block is written once and read by every slot whose table
# names it) and attends the pool THROUGH the table — the paged
# flash-decode kernel (ops.paged_flash_decode, ISSUE 15) fuses the
# block gather into its BlockSpec index map, so no dense per-slot view
# exists and per-step HBM traffic is O(cur) per slot; where the kernel
# stands down, a per-layer dense gather view keeps the portable PR 11
# math. The chunk / whole-prompt prefill primitives keep their
# window-bounded gather (already O(window), and prefill is
# compute-bound, not cache-bandwidth-bound).
# Program signatures depend on (num_slots, max_blocks, pool_blocks)
# and the static chunk/window sizes only — tables, slots, offsets and
# fill indices are traced, so refills, grafts and block allocation
# never re-trace (the same no-re-trace property the per-slot
# primitives pin).


def paged_pool_spec(model: LlamaModel, pool_blocks: int, block_size: int,
                    kv_quant: Optional[str] = None):
    """``ShapeDtypeStruct`` pytree of the paged pool — the single
    source of truth for allocation (:func:`init_paged_pool`) AND byte
    accounting (``serving.backend.pool_bytes_per_block``). With
    ``kv_quant`` ('int8'/'fp8') the K/V leaves store codes in the
    quant dtype and every attention dict gains a ``kv_scale``
    ``[pool_blocks, Hkv, 2]`` f32 plane (``[..., 0]`` = K scales,
    ``[..., 1]`` = V — one absmax scale per physical block per kv
    head)."""
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((int(pool_blocks), int(block_size)),
                                     jnp.int32), decode=True))["cache"]
    if kv_quant is None:
        return shapes
    qdt, _ = kv_quant_spec(kv_quant)

    def q(attn):
        for name in ("k", "v"):
            attn[name] = jax.ShapeDtypeStruct(attn[name].shape, qdt)
        p, hkv = attn["k"].shape[:2]
        attn["kv_scale"] = jax.ShapeDtypeStruct((p, hkv, 2), jnp.float32)
        return attn

    return _map_attn_dicts(q, shapes)


def init_paged_pool(model: LlamaModel, pool_blocks: int, block_size: int,
                    kv_sharding=None, scalar_sharding=None,
                    kv_quant: Optional[str] = None, scale_sharding=None):
    """Zeroed shared K/V pool: per layer ``[pool_blocks, kv_heads,
    block_size, head_dim]`` — structurally a ``init_cache`` with
    batch=pool_blocks and max_len=block_size, which is exactly the
    block-major paged layout. Block 0 is conventionally the trash block
    (``serving.paging.BlockAllocator``): idle slots' tables point at
    it, so masked garbage writes land where no request reads.
    ``kv_sharding`` places every block's ``Hkv`` axis over a tp mesh —
    block ids stay logical/device-count-agnostic, each device holds
    ``1/tp`` of every block (see :func:`init_cache`).

    ``kv_quant`` stores K/V as codes with a per-block ``kv_scale``
    plane (:func:`paged_pool_spec`); ``scale_sharding`` places the 3-D
    plane leaves — the tp backends shard them over the same head axis
    as their codes."""
    spec = paged_pool_spec(model, pool_blocks, block_size, kv_quant)

    def make(s):
        nd = len(s.shape)
        sh = {4: kv_sharding, 3: scale_sharding}.get(nd, scalar_sharding)
        if sh is not None:
            return jax.make_array_from_callback(
                s.shape, sh, lambda idx: np.zeros(
                    tuple(len(range(*i.indices(d)))
                          for i, d in zip(idx, s.shape)), s.dtype))
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree_util.tree_map(make, spec)


def _pool_block_size(pool) -> int:
    """Static block size from the pool's K/V leaf shapes."""
    for leaf in jax.tree_util.tree_leaves(pool):
        if getattr(leaf, "ndim", 0) == 4:
            return leaf.shape[2]
    raise ValueError("pool holds no 4-D K/V leaves")


def _gather_view(pool, tables):
    """Dense per-slot cache view through the block tables:
    ``[P, Hkv, bs, hd]`` pool leaves + ``[S, MB]`` tables →
    ``[S, Hkv, MB*bs, hd]`` rows (scalar leaves → zeros placeholders,
    keeping the cache pytree structure apply() expects). Since ISSUE
    15 the decode/verify primitives route the pool straight into
    ``apply()`` (writes and reads go through the table in-layer, the
    kernel fuses the gather away); this tree-level view remains the
    REFERENCE the equivalence tests compare against. A quantized pool
    yields the DEQUANTIZED f32 view (codes·per-block scale) — the
    reference the interpret-mode kernel pins run against."""
    def g_attn(attn):
        plane = attn.get("kv_scale")
        out = {}
        for name, leaf in attn.items():
            if getattr(leaf, "ndim", 0) != 4:
                out[name] = jnp.zeros((), jnp.int32)
            elif plane is None:
                out[name] = _gather_leaf(leaf, tables)
            else:
                out[name] = _gather_dequant(
                    leaf, plane, 0 if name == "k" else 1, tables,
                    jnp.float32)
        return out

    return _map_attn_dicts(g_attn, pool)


@functools.partial(
    jax.jit, static_argnames=("model", "temperature", "top_k", "top_p"),
    donate_argnames=("pool",))
def paged_slot_decode_step(model, params, pool, tables, tokens, slot_cur,
                           pad_lens, rng, *, temperature: float = 0.0,
                           top_k: int = 0, top_p: float = 1.0):
    """One in-flight decode iteration over the BLOCK-TABLE cache: every
    slot advances one token at its own fill index, reading its cache
    through ``tables`` (``[num_slots, max_blocks]`` int32, traced) and
    writing exactly its one new position back into the pool.

    Compiled ONCE per (num_slots, max_blocks, pool_blocks) — block
    allocation, frees, grafts and refills mutate the (traced) tables,
    never the program. Idle or block-stalled slots' writes land at
    whatever their table names at the frontier — the engine parks those
    entries on the trash block, so the masked garbage is contained.
    Returns ``(next_tokens [num_slots] int32, pool)``.

    Since ISSUE 15 the pool rides into ``apply()`` DIRECTLY with the
    block tables (no tree-level ``_gather_view`` / scatter-back): each
    layer writes its one new position through the table and attends the
    pool through the table — via the paged flash-decode kernel when it
    engages (``ops.paged_flash_decode``: the program holds NO
    ``[S, Hkv, max_blocks·bs, hd]`` gather and per-step HBM traffic is
    O(cur) per slot), else a per-layer dense gather view with the exact
    PR 11 math (masked garbage contributes exactly-zero probability, so
    committed tokens are unchanged either way).
    """
    logits, mut = model.apply({"params": params, "cache": pool},
                              tokens[:, None], decode=True,
                              pad_lens=pad_lens, slot_cur=slot_cur,
                              block_tables=tables, mutable=["cache"])
    nxt = _sample(logits[:, -1].astype(jnp.float32), rng, temperature,
                  top_k, top_p)
    return nxt, mut["cache"]


@functools.partial(jax.jit, static_argnames=("model",),
                   donate_argnames=("pool",))
def paged_slot_verify_step(model, params, pool, tables, tokens, slot_cur,
                           pad_lens):
    """``slot_verify_step`` through the block tables — the paged
    speculative verify window (ISSUE 12): row r's k+1 positions
    ``[slot_cur[r], slot_cur[r]+k]`` write through ``tables`` into the
    shared pool, with the draft window's growth blocks allocated UP
    FRONT by the engine (``ensure_block_for`` per draft position — a
    position whose block the pool could not serve routes to the trash
    block 0 and its proposal is never committed). The k+1 writes go
    through the tables in-layer (overhanging positions trash-route —
    same rule as the chunk primitive: never clamp onto live blocks) and
    attention reads the pool through the tables exactly like
    ``paged_slot_decode_step`` — the paged flash-decode kernel covers
    this S = k+1 window too (query i attends ``[pads[r],
    slot_cur[r]+i]``), with the per-layer gather view as the fallback.
    Reject is the same pure host-side ``cur`` non-advance — the
    misspeculated rows are garbage past the frontier, overwritten
    (or trash-routed) before any attention reads them. Compiled ONCE
    per (num_slots, max_blocks, pool_blocks, k+1); tables/fill indices
    traced, so allocation, grafts and refills never re-trace it.
    Returns ``(proposals [num_slots, k+1] int32, pool)``."""
    logits, mut = model.apply({"params": params, "cache": pool},
                              tokens, decode=True, pad_lens=pad_lens,
                              slot_cur=slot_cur, block_tables=tables,
                              mutable=["cache"])
    props = jnp.argmax(logits.astype(jnp.float32), axis=-1)
    return props.astype(jnp.int32), mut["cache"]


@functools.partial(
    jax.jit,
    static_argnames=("model", "window", "temperature", "top_k", "top_p"),
    donate_argnames=("pool",))
def paged_prefill_chunk_into_slot(model, params, chunk_ids, pool,
                                  table_row, offset, n_valid, rng, *,
                                  window: int,
                                  temperature: float = 0.0,
                                  top_k: int = 0, top_p: float = 1.0):
    """``prefill_chunk_into_slot`` through a block table: consume ``C``
    zero-aligned prompt tokens at logical positions
    ``[offset, offset + C)`` of the slot whose table is ``table_row``
    (``[max_blocks]`` int32, traced). The chunk attends a dense view of
    the table's first ``ceil(window / block_size)`` blocks — ``window``
    (static, a chunk multiple covering the request's aligned prompt
    length) bounds the gather exactly like the per-slot variant's
    window bounds its slice — and scatters only its own C written
    positions back through the table, so a grafted shared-prefix block
    is READ here, never written. One compiled program per
    (C, window-blocks); slot identity rides entirely in the table.
    Returns ``(tok [1] int32, pool)`` — the last-real-position sample,
    meaningful on the final chunk."""
    bs = _pool_block_size(pool)
    c = chunk_ids.shape[1]
    # The VIEW must span the whole window (>= offset + C for every
    # chunk of the plan): the multi-call decode path writes the chunk
    # at [offset, offset+C) with dynamic_update_slice, which CLAMPS a
    # write extending past the view — sliding it back over committed
    # prompt rows. A window past the table (a resume whose chunk-
    # aligned length exceeds max_len) gathers every table block and
    # pads the view with scratch rows instead: writes land in-place,
    # and only real positions scatter back to the pool.
    wb = -(-int(window) // bs)

    def gather_attn(attn):
        plane = attn.get("kv_scale")
        out = {}
        for name, leaf in attn.items():
            if name == "kv_scale":
                # the dense window view is FLOAT — the model's
                # non-paged branch (this apply carries no
                # block_tables) declares only k/v/idx, so the view
                # must not grow a scale leaf.
                continue
            if getattr(leaf, "ndim", 0) != 4:
                # scalar idx leaves: pin the multi-call decode path's
                # write index at the chunk's offset (same contract as
                # the un-paged chunk primitive)
                out[name] = jnp.asarray(offset, jnp.int32)
                continue
            mbv = min(wb, table_row.shape[0])
            v = leaf[table_row[:mbv]]              # [mbv, Hkv, bs, hd]
            if plane is not None:
                # dequantize the window into the model's compute
                # dtype; scratch pad rows (below) stay zero — never
                # read live.
                s = plane[table_row[:mbv], :, 0 if name == "k" else 1]
                v = (v.astype(jnp.float32)
                     * s[:, :, None, None]).astype(model.dtype)
            v = jnp.transpose(v, (1, 0, 2, 3))
            v = v.reshape(1, leaf.shape[1], mbv * bs, leaf.shape[3])
            if wb > mbv:
                v = jnp.concatenate(
                    [v, jnp.zeros((1, leaf.shape[1], (wb - mbv) * bs,
                                   leaf.shape[3]), v.dtype)], axis=2)
            out[name] = v
        return out

    row = _map_attn_dicts(gather_attn, pool)
    logits, mut = model.apply({"params": params, "cache": row},
                              chunk_ids, decode=True, mutable=["cache"])
    pos = offset + jnp.arange(c)                   # [C] logical
    bi = pos // bs
    mb = table_row.shape[0]
    # Only REAL tokens' rows are persisted: the final chunk's pad tail
    # (pos >= offset + n_valid) and anything past the table route to
    # the trash block 0 — never clamp onto a live block (a resume
    # whose chunk-aligned length pads past max_len would otherwise
    # scatter garbage over committed rows), and pad-only blocks then
    # need no allocation at all (the reservation covers real rows +
    # one decode block; decode's first write lands at the frontier
    # before any attention can read it — the PR 9 invariant).
    real = (pos < offset + n_valid) & (bi < mb)
    blk = _table_blocks(table_row, bi, real)
    off = pos % bs

    def scatter_attn(attn, dense):
        # zip-walk: the dense twin came from the FLOAT window apply, so
        # it lacks the kv_scale leaf a quantized pool carries — a
        # leaf-level tree_map would reject the structure mismatch.
        plane = attn.get("kv_scale")
        out = dict(attn)
        for ch, name in enumerate(("k", "v")):
            new = jnp.take_along_axis(
                dense[name], pos[None, None, :, None], axis=2)[0]
            new = jnp.moveaxis(new, 1, 0)          # [C, Hkv, hd]
            if plane is None:
                out[name] = attn[name].at[blk, :, off, :].set(
                    new.astype(attn[name].dtype))
            else:
                out[name], plane = _quant_insert_rows(
                    attn[name], plane, ch, blk, off, new)
        if plane is not None:
            out["kv_scale"] = plane
        return out

    pool = _map_attn_dicts(scatter_attn, pool, mut["cache"])
    last = jax.lax.dynamic_slice(
        logits, (0, jnp.maximum(n_valid - 1, 0), 0),
        (1, 1, logits.shape[2]))[:, 0]
    tok = _sample(last.astype(jnp.float32), rng, temperature, top_k, top_p)
    return tok, pool


@functools.partial(
    jax.jit, static_argnames=("model", "temperature", "top_k", "top_p"),
    donate_argnames=("pool",))
def paged_prefill_into_slot(model, params, prompt_ids, pad_len, pool,
                            table_row, rng, *, temperature: float = 0.0,
                            top_k: int = 0, top_p: float = 1.0):
    """``prefill_into_slot`` through a block table — the blocking
    (whole-prompt, left-padded bucket) refill for paged backends: the
    prompt runs the standard first-chunk prefill against a private
    ``[1, Lb]`` scratch cache, then every one of its ``Lb`` rows
    scatters to the pool position the table names (left-pad rows
    included — they carry the same masked-garbage contract as the
    per-slot variant). Compiled once per bucket length; returns
    ``(first_token [1] int32, pool)``."""
    bs = _pool_block_size(pool)
    lb = prompt_ids.shape[1]
    small_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, lb), jnp.int32), decode=True))
    small = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), small_shapes["cache"])
    logits, mut = model.apply({"params": params, "cache": small},
                              prompt_ids, decode=True, pad_lens=pad_len,
                              first_chunk=True, mutable=["cache"])
    pos = jnp.arange(lb)
    blk = table_row[pos // bs]
    off = pos % bs

    def scatter_attn(attn, sm):
        plane = attn.get("kv_scale")
        out = dict(attn)
        for ch, name in enumerate(("k", "v")):
            new = jnp.transpose(sm[name][0], (1, 0, 2))  # [Lb, Hkv, hd]
            if plane is None:
                out[name] = attn[name].at[blk, :, off, :].set(
                    new.astype(attn[name].dtype))
            else:
                out[name], plane = _quant_insert_rows(
                    attn[name], plane, ch, blk, off, new)
        if plane is not None:
            out["kv_scale"] = plane
        return out

    pool = _map_attn_dicts(scatter_attn, pool, mut["cache"])
    tok = _sample(logits[:, -1].astype(jnp.float32), rng, temperature,
                  top_k, top_p)
    return tok, pool


@functools.partial(jax.jit, donate_argnames=("pool",))
def copy_pool_block(pool, src, dst):
    """Copy one physical block's K/V (every layer) — the paged
    copy-on-write primitive: a write that would land in a SHARED block
    (refcount >= 2 after a radix graft) first duplicates it so the
    other holders keep reading the original. ``src``/``dst`` traced —
    one tiny compiled program per pool signature. The 3-D ``kv_scale``
    planes of a quantized pool copy with their codes (both are indexed
    by physical block), so copy-on-write stays EXACT — the duplicate
    dequantizes bit-identically to the original."""
    def cp(leaf):
        nd = getattr(leaf, "ndim", 0)
        if nd not in (3, 4):
            return leaf
        row = jax.lax.dynamic_slice(
            leaf, (src,) + (0,) * (nd - 1),
            (1,) + leaf.shape[1:])
        return jax.lax.dynamic_update_slice(
            leaf, row, (dst,) + (0,) * (nd - 1))

    return jax.tree_util.tree_map(cp, pool)


# int8 weight serving (ISSUE 18): the Megatron-sharded projection
# matmuls — attention q/k/v/o and MLP gate/up/down. lm_head, embed,
# norms and LoRA adapters stay float (logits keep full precision;
# adapters are ~0.1% of params).
WEIGHT_QUANT_TARGETS = frozenset(
    ("q_proj", "k_proj", "v_proj", "o_proj",
     "gate_proj", "up_proj", "down_proj"))


def quantize_params(params, name: str = "int8"):
    """Host-side weight quantization: every projection base kernel in
    ``WEIGHT_QUANT_TARGETS`` → int8 codes + an absmax per-OUTPUT-channel
    f32 ``kernel_scale`` (``s = max|col| / 127``; an all-zero column
    gets scale 1 so dequant stays finite). Pair with
    ``model.clone(weight_quant='int8')`` — :class:`QuantDense` engages
    on the stored dtype and folds the dequant after each matmul.
    Returns a new params pytree; everything outside the targets is
    passed through untouched."""
    if name != "int8":
        raise ValueError(
            f"unsupported weight quant dtype {name!r} (int8 only)")

    def convert(base):
        kern = jnp.asarray(base["kernel"], jnp.float32)
        s = jnp.max(jnp.abs(kern), axis=0) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        out = dict(base)
        out["kernel"] = jnp.clip(
            jnp.round(kern / s), -127, 127).astype(jnp.int8)
        out["kernel_scale"] = s.astype(jnp.float32)
        return out

    def walk(tree, parent):
        if not isinstance(tree, Mapping):
            return tree
        return {
            k: (convert(v) if k == "base"
                and parent in WEIGHT_QUANT_TARGETS
                and isinstance(v, Mapping) and "kernel" in v
                else walk(v, k))
            for k, v in tree.items()}

    return walk(params, "")


# ---------------------------------------------------------------------------
# LoRA training utilities
# ---------------------------------------------------------------------------

def lora_mask(params) -> Any:
    """Boolean pytree: True for LoRA adapter leaves (trainable), False for
    base weights (frozen). Feed to ``optax.masked`` — the LoRA fine-tune
    trains ~0.1% of params, the rest stay untouched in HBM."""
    from ..parallel.sharding import path_str

    return jax.tree_util.tree_map_with_path(
        lambda path, _: ("lora_a" in path_str(path)
                         or "lora_b" in path_str(path)), params)


def lora_optimizer(learning_rate: float = 1e-4):
    """Adam on LoRA adapters only; base params get zero updates (frozen).

    Uses multi_transform, not optax.masked — masked passes non-masked
    updates through *unchanged* (i.e. raw gradients), it does not freeze.
    """
    import optax

    def labels(params):
        return jax.tree_util.tree_map(
            lambda m: "lora" if m else "frozen", lora_mask(params))

    return optax.multi_transform(
        {"lora": optax.adam(learning_rate), "frozen": optax.set_to_zero()},
        labels)
