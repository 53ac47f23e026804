"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Entirely absent from the 2017-era reference (SURVEY.md §2.4, §5.7) — this is
the framework's long-context story, designed TPU-first:

- **Ring attention** (``ring_attention``): sequence sharded over a mesh axis;
  KV blocks rotate around the ICI ring via ``jax.lax.ppermute`` inside a
  ``shard_map``-ed ``lax.fori_loop``, with flash-style streaming-softmax
  accumulation so each hop's compute overlaps the neighbor transfer and no
  chip ever materializes the full [S, S] score matrix. Memory per chip is
  O(S/n · S/n) scores + O(S/n) KV — sequence length scales linearly with
  ring size.
- **Ulysses** (``ulysses_attention``): the all-to-all alternative — swap the
  sequence sharding for a head sharding (`all_to_all` over ICI), run dense
  local attention on full sequences for the local head subset, swap back.
  Cheaper at moderate S (two all-to-alls vs n ppermute hops) but caps the
  parallelism degree at num_heads.

Both are jit-compatible, causal-mask aware via global position arithmetic,
and verified equivalent to single-device dense attention in
tests/test_parallel.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # large-but-finite: -inf breaks the streaming-softmax max


def dense_attention(q, k, v, causal: bool = False, kv_mask=None,
                    window: int | None = None):
    """Reference single-device attention. [B, H, S, D] layout (``v`` may
    have a width of its own). ``window`` (with ``causal``): position ``t``
    attends keys ``t - window + 1 .. t``.

    ``kv_mask`` ([B, S] 0/1) follows the flash kernel's contract exactly,
    including the edge the streaming kernel gets for free: a row whose
    mask is ALL zero outputs zeros, not the uniform mean(v) that finite
    NEG_INF scores would give softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((S, S), bool), -window)
        s = jnp.where(mask, s, NEG_INF)
    if kv_mask is not None:
        valid = kv_mask.astype(bool)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    if kv_mask is not None and window is not None:
        # a row whose whole band is masked: zeros, as the kernel gives
        seen = (mask & valid[:, None, :]).any(-1)
        o = o * seen.astype(o.dtype)[:, None, :, None]
    elif kv_mask is not None:
        o = o * valid.any(-1).astype(o.dtype)[:, None, None, None]
    return o


def _ring_shard(q, k, v, *, axis_name: str, causal: bool):
    """Per-shard body: q/k/v are local blocks [B, H, T, D]; T = S/ring."""
    ring = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)

    perm = [(j, (j + 1) % ring) for j in range(ring)]
    q_pos = my_idx * T + jnp.arange(T)  # global query positions

    def accumulate(o, m, l, kb, vb, src):
        # ``src``: ring index the KV block originated on → global key
        # positions for causal masking.
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * T + jnp.arange(T)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return o_new, m_new, l_new

    def hop(i, carry):
        o, m, l, kv = carry
        # Rotate first, then accumulate: ring-1 ppermutes total (the local
        # block was consumed before the loop), and XLA overlaps each
        # ppermute with the previous iteration's einsums.
        kv = jax.lax.ppermute(kv, axis_name, perm)
        o, m, l = accumulate(o, m, l, *kv, src=(my_idx - (i + 1)) % ring)
        return o, m, l, kv

    o0 = jnp.zeros((B, H, T, D), jnp.float32)
    m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    o, m, l = accumulate(o0, m0, l0, k, v, src=my_idx)
    o, m, l, _ = jax.lax.fori_loop(0, ring - 1, hop, (o, m, l, (k, v)))
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = False, batch_axis: str | None = None,
                   head_axis: str | None = None):
    """Sequence-parallel attention over mesh axis ``axis``.

    Inputs [B, H, S, D] sharded (or shardable) on S over ``axis``; output has
    the same layout. Jit-safe; compose inside larger jitted programs.

    ``batch_axis``/``head_axis`` name mesh axes the batch/head dims are
    ALREADY sharded over — the DP×TP×SP composition on one 3-D mesh
    (batch rows on the data axis, Megatron head-sharded activations on
    the model axis). The ring body is independent across B and H, so
    these are pure layout declarations: without them shard_map's specs
    would demand replication over those axes and GSPMD would insert
    all-gathers that undo the DP/TP sharding around every attention.
    """
    body = functools.partial(_ring_shard, axis_name=axis, causal=causal)
    spec = P(batch_axis, head_axis, axis, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _ulysses_shard(q, k, v, *, axis_name: str, causal: bool, local_attn):
    """Per-shard body: [B, H, T, D] seq-sharded in → seq-sharded out."""
    n = jax.lax.axis_size(axis_name)

    def seq_to_heads(x):
        # [B, H, S/n, D] → all_to_all: scatter heads, gather sequence →
        # [B, H/n, S, D]. split_axis=1 (heads), concat_axis=2 (sequence).
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    o = local_attn(qh, kh, vh, causal=causal)
    return heads_to_seq(o)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = False, local_attn=None,
                      batch_axis: str | None = None,
                      head_axis: str | None = None):
    """Ulysses-style sequence parallelism: all_to_all head-scatter /
    seq-gather, attention on local heads over the FULL sequence, inverse
    all_to_all. Requires num_heads % axis_size == 0 (per-TP-shard heads
    when ``head_axis`` is set).

    ``local_attn``: the per-shard attention over [B, H/n, S, D]. Default
    ``None`` → dense (materializes an [S, S] score block per local head).
    Pass ``ops.flash_attention`` (or ``"auto"``: flash on TPU, dense
    elsewhere) to keep the local compute streaming — at long S this is
    where the memory goes, so the flash kernel composes with the
    all-to-all layout exactly as SURVEY §5.7 prescribes.

    ``batch_axis``/``head_axis`` compose with DP / Megatron TP on one
    mesh exactly as in :func:`ring_attention`: B is independent
    throughout, and with ``head_axis`` the all_to_all simply scatters
    the TP-LOCAL head set over ``axis`` (the DeepSpeed Ulysses+TP
    layout) — so the divisibility requirement becomes
    (num_heads / tp) % axis_size == 0.
    """
    n = mesh.shape[axis]
    tp = mesh.shape[head_axis] if head_axis else 1
    if q.shape[1] % tp:
        raise ValueError(
            f"num_heads={q.shape[1]} not divisible by {head_axis}={tp}")
    local_h = q.shape[1] // tp
    if local_h % n:
        raise ValueError(
            f"per-shard num_heads={local_h} not divisible by {axis}={n}")
    if local_attn == "auto":
        from ..ops.flash_attention import resolve_attn_fn
        local_attn = resolve_attn_fn("auto")
    body = functools.partial(_ulysses_shard, axis_name=axis, causal=causal,
                             local_attn=local_attn or dense_attention)
    spec = P(batch_axis, head_axis, axis, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
