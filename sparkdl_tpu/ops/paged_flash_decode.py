"""Paged flash-decode attention: block-table cache reads WITHOUT the
gather, as a Pallas TPU kernel.

The paged serving engine (PRs 11-14) stores every slot's K/V in one
shared pool of ``[pool_blocks, Hkv, block_size, hd]`` blocks addressed
through a per-slot block TABLE. Until this kernel, every decode /
verify step materialized a dense per-slot view of the pool
(``models.llama._gather_view``): HBM traffic and a full gathered copy
of O(num_slots x max_blocks x block_size) per layer per step,
regardless of how little of each table is actually live. This kernel
is the PagedAttention move (Kwon et al., SOSP '23) fused with the
existing flash-decode dead-block clamp:

- the flattened block tables, per-slot fill indices (``slot_cur``) and
  pad lengths ride in as **scalar-prefetch** operands
  (``pltpu.PrefetchScalarGridSpec`` — exactly how ``ops.flash_decode``
  prefetches ``cur``/``pad_lens``), so the KV BlockSpec index map can
  chase the table before the body runs;
- grid step ``j`` of slot ``s`` resolves to POOL block
  ``table[s, j]``: the kernel reads K/V straight from the pool — no
  gathered intermediate exists in the program at all (the acceptance
  jaxpr pin);
- blocks at or past slot ``s``'s frontier clamp to its last LIVE
  table entry — consecutive equal index tuples skip the DMA, so
  per-step HBM traffic is O(cur) per slot, not
  O(max_blocks x block_size) per slot. A slot parked entirely on the
  trash block (idle / block-stalled) costs one block read whose
  output the engine discards;
- ONE kernel covers both serving windows: ``S = 1`` is the decode
  step, ``S = k+1`` the speculative VERIFY window — query ``i`` of
  slot ``s`` attends logical positions
  ``[pad_lens[s], slot_cur[s] + i]``, the exact mask of the dense
  causal-vs-cache path (``models.llama`` slot_cur branch). Positions
  past the table (an overhanging draft column) have no column to
  attend — identical to the gather view, whose OOB writes are
  dropped/trash-routed.

``interpret=True`` (auto on non-TPU) runs the same kernel through the
Pallas interpreter — tier-1 CPU tests pin the block-table index map,
trash-block routing and per-row clamp bitwise against
``ops.flash_decode`` over the gathered dense view (same math, same
block walk, densely addressed).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import NEG_INF, _LANES, _resolve

#: the explicit engagement knob: ``0`` off, ``1`` force (engage
#: whenever ``supports()`` passes, any platform — interpret mode off
#: TPU; standing down then WARNS once), unset/``auto`` = engage exactly
#: when the dense flash-decode kernel would for the same config.
PAGED_KERNEL_ENV = "SPARKDL_SERVE_PAGED_KERNEL"


def _paged_decode_kernel(tbl_ref, cur_ref, pad_ref, q_ref, k_ref, v_ref,
                         *rest, sm_scale: float, h_kv: int, bs: int,
                         s_q: int, rep: int, quant: bool = False):
    """Grid = (B·Hkv, max_blocks); the KV BlockSpec index map (below)
    already resolved grid step ``j`` to the pool block the slot's table
    names, so the body is the standard online-softmax update over one
    ``(bs, hd)`` pool block. Rows of the query tile are (query i,
    GQA group g) pairs flattened as ``i * rep + g`` (pad rows clip to
    the last query and are sliced off outside).

    ``quant`` (ISSUE 18): K/V are int8/fp8 CODES and ``rest`` leads
    with a (1, 1, 2) SMEM ref holding this block's (K, V) scales for
    this kv head. Dequant folds AFTER each contraction — ``(q·kᵀ)·s_k``
    and ``(p·v)·s_v``, exact because the scale is constant over the block —
    so the kernel reads quantized bytes from HBM and no dequantized
    block ever exists outside VMEM."""
    if quant:
        scl_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bh, j = pl.program_id(0), pl.program_id(1)
    n_kv = pl.num_programs(1)
    slot = bh // h_kv
    cur = cur_ref[slot]   # the slot's write frontier BEFORE this window
    pad = pad_ref[slot]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Block j holds logical positions [j*bs, (j+1)*bs): dead for every
    # query of this slot once j*bs > cur + s_q - 1.
    @pl.when(j * bs < cur + s_q)
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale       # (R, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (bs, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (R, bs)
        if quant:
            s = s * scl_ref[0, 0, 0]
        rows = q.shape[0]
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        qi = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0) // rep,
            s_q - 1)
        # query i attends [pad, cur + i] of its own row — the dense
        # slot_cur-branch mask (S=1: col <= cur, i.e. col < cur+1)
        valid = (col <= cur + qi) & (col >= pad)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(m_new[:, None] <= NEG_INF, 0.0, p)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
        if quant:
            pv = pv * scl_ref[0, 0, 1]
        acc_ref[:] = acc_ref[:] * alpha[:, None] + pv
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe_l = jnp.where(l > 0, l, 1.0)  # trash-parked rows (cur == 0)
        o_ref[0] = (acc_ref[:] / safe_l[:, None]).astype(o_ref.dtype)


def support_reason(block_size: int,
                   kv_dtype: str | None = None) -> str | None:
    """None when the kernel covers the config, else a human-readable
    reason string — what the stand-down path logs so "dense attention
    was chosen" always says WHY (ISSUE 18 satellite; the
    ``ops.flash_decode.support_reason`` twin). Capability itself:
    the per-block KV tile is ``(block_size, head_dim)`` and the sublane
    dim must stay 8-aligned for Mosaic (the engine's default
    block_size 16 qualifies); a quantized pool additionally needs a
    registered ``kv_dtype`` (the scale-fused kernel variant)."""
    if block_size < 8 or block_size % 8:
        return (f"block_size {block_size} is not an 8-multiple >= 8 "
                f"(the Mosaic sublane constraint on the per-block KV "
                f"tile)")
    if kv_dtype is not None:
        from ..models.llama import KV_QUANT_DTYPES
        if kv_dtype not in KV_QUANT_DTYPES:
            return (f"KV quant dtype {kv_dtype!r} has no fused-dequant "
                    f"kernel variant (available: "
                    f"{sorted(KV_QUANT_DTYPES)})")
    return None


def supports(block_size: int, kv_dtype: str | None = None) -> bool:
    """Boolean twin of :func:`support_reason` (kept for call sites that
    only branch)."""
    return support_reason(block_size, kv_dtype) is None


def paged_flash_decode(q, k_pool, v_pool, tables, slot_cur, pad_lens=None,
                       kv_scales=None, *, interpret: bool | None = None):
    """Block-table cache attention over the shared pool. ``q``:
    ``[B, Hq, S, D]`` — ``S = 1`` is the per-slot decode step,
    ``S = k+1`` the speculative verify window; ``k_pool``/``v_pool``:
    ``[pool_blocks, Hkv, block_size, D]`` (``Hq % Hkv == 0``, GQA);
    ``tables``: ``[B, max_blocks]`` int32 — logical position ``p`` of
    slot ``r`` lives at pool position ``(tables[r, p // bs], p % bs)``;
    ``slot_cur``: ``[B]`` int32 per-slot write frontiers BEFORE the
    window (the window's own tokens must already be written through the
    table — the write-frontier invariant); ``pad_lens``: optional
    ``[B]`` int32 left-pad exclusion. Query ``i`` of slot ``r`` attends
    logical positions ``[pad_lens[r], slot_cur[r] + i]``. Returns
    ``[B, Hq, S, D]``.

    ``kv_scales`` (ISSUE 18): the quantized pool's
    ``[pool_blocks, Hkv, 2]`` f32 scale plane — required exactly when
    the pool leaves hold int8/fp8 codes. Each grid step's (K, V) scale
    pair rides a (1, 1, 2) SMEM block whose index map chases the table
    like the KV specs, and dequant folds after the two dots in-kernel:
    the HBM read stays quantized end to end.

    HBM traffic per step is O(cur) per slot: the index map clamps every
    dead grid step to the slot's last live table entry (repeat DMAs are
    skipped) and ``pl.when`` gates its compute off. No dense per-slot
    view is ever materialized — the gather is fused into the BlockSpec
    index map.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, hq, s_q, d = q.shape
    pool_blocks, h_kv, bs, _ = k_pool.shape
    quant = kv_scales is not None
    if not quant and jnp.dtype(k_pool.dtype).itemsize == 1:
        # int8/fp8 codes without their scale plane would silently
        # attend over raw code values — refuse loudly instead.
        raise ValueError(
            f"pool dtype {jnp.dtype(k_pool.dtype).name} holds quantized "
            f"codes; pass the [pool_blocks, Hkv, 2] kv_scales plane")
    if hq % h_kv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={h_kv}")
    reason = support_reason(bs)
    if reason is not None:
        raise ValueError(
            f"unsupported config ({reason}); use the gather view "
            f"(see support_reason())")
    if tables.ndim != 2 or tables.shape[0] != b:
        raise ValueError(f"tables must be [B={b}, max_blocks], got "
                         f"shape {tables.shape}")
    mb = tables.shape[1]
    rep = hq // h_kv
    sm_scale = 1.0 / math.sqrt(d)

    # [B, Hq, S, D] -> [B*Hkv, R, D]: kv-head-major, rows are
    # (query i, group g) flattened i*rep + g, padded to an 8-multiple.
    r0 = s_q * rep
    r_pad = -(-r0 // 8) * 8
    q3 = q.reshape(b, h_kv, rep, s_q, d).transpose(0, 1, 3, 2, 4)
    q3 = q3.reshape(b * h_kv, r0, d)
    if r_pad != r0:
        q3 = jnp.pad(q3, ((0, 0), (0, r_pad - r0), (0, 0)))
    tbl = jnp.asarray(tables, jnp.int32).reshape(b * mb)
    cur_arr = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(slot_cur, jnp.int32)), (b,))
    pad_arr = (jnp.zeros((b,), jnp.int32) if pad_lens is None
               else jnp.asarray(pad_lens, jnp.int32))

    def kv_index(bh, j, tbl_ref, cur_ref, pad_ref):
        # Chase the slot's table: live grid steps read the pool block
        # the table names; dead steps (past the frontier) re-reference
        # the last live entry, so their DMA is skipped — each slot's
        # bandwidth scales with its own fill, through the table.
        slot = bh // h_kv
        last_live = jnp.maximum(
            pl.cdiv(cur_ref[slot] + s_q, bs) - 1, 0)
        jc = jnp.minimum(j, last_live)
        return (tbl_ref[slot * mb + jc], bh % h_kv, 0, 0)

    in_specs = [
        pl.BlockSpec((1, r_pad, d), lambda bh, j, t, c, p: (bh, 0, 0)),
        pl.BlockSpec((1, 1, bs, d), kv_index),
        pl.BlockSpec((1, 1, bs, d), kv_index),
    ]
    operands = [tbl, cur_arr, pad_arr, q3, k_pool, v_pool]
    if quant:
        # Pre-gather the scale pairs into grid order — [B·Hkv·MB, 1, 2]
        # f32, two floats riding SMEM per grid step. The pair is 3-D so
        # that the (1, 1, 2) block's trailing dims EQUAL the array's:
        # the TPU lowering refuses a (1, 2) block over [N, 2] (sublane
        # 1 is neither an 8-multiple nor N) — found on the chip, the
        # interpreter takes either (the lse layout in flash_attention
        # is the same rule). All of it as a scalar-prefetch operand
        # lowers too, but overflows the 1 MB SMEM at 32 slots x 8k
        # context. The index map mirrors kv_index's dead-step clamp so
        # repeat fetches are skipped the same way.
        scl = kv_scales[tables]                  # [B, MB, Hkv, 2]
        scl = scl.transpose(0, 2, 1, 3).reshape(b * h_kv * mb, 1, 2)
        scl = scl.astype(jnp.float32)

        def scl_index(bh, j, tbl_ref, cur_ref, pad_ref):
            slot = bh // h_kv
            last_live = jnp.maximum(
                pl.cdiv(cur_ref[slot] + s_q, bs) - 1, 0)
            jc = jnp.minimum(j, last_live)
            return (bh * mb + jc, 0, 0)

        in_specs.append(pl.BlockSpec((1, 1, 2), scl_index,
                                     memory_space=pltpu.SMEM))
        operands.append(scl)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * h_kv, mb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, r_pad, d),
                               lambda bh, j, t, c, p: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((r_pad, d), jnp.float32),       # acc
            pltpu.VMEM((r_pad, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((r_pad, _LANES), jnp.float32),  # normalizer l
        ],
    )
    o3 = pl.pallas_call(
        functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                          h_kv=h_kv, bs=bs, s_q=s_q, rep=rep,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h_kv, r_pad, d), q.dtype),
        interpret=_resolve(interpret),
    )(*operands)
    o = o3[:, :r0].reshape(b, h_kv, s_q, rep, d)
    return o.transpose(0, 1, 3, 2, 4).reshape(b, hq, s_q, d)


def kernel_mode() -> str:
    """``SPARKDL_SERVE_PAGED_KERNEL`` → ``"off"`` / ``"force"`` /
    ``"auto"`` (see :data:`PAGED_KERNEL_ENV`; one parser shared with
    the tp-dispatch knob)."""
    from .flash_decode import tri_state_env
    return tri_state_env(PAGED_KERNEL_ENV)


def paged_decode_fn_for(attn_fn, mesh=None):
    """Call-site resolver (``models.llama`` paged slot_cur branch) —
    the :func:`ops.flash_decode.decode_fn_for` twin for the block-table
    pool. ``"auto"`` (the default) engages exactly when the dense
    flash-decode kernel would for the same config: single-device, when
    the model's resolved ``attn_fn`` is the flash kernel (explicitly or
    via the ``"auto"``-on-TPU default); under a ``Mesh(('tp',))``
    (``mesh``), when the sharded dispatch is on (TPU, or
    ``SPARKDL_SERVE_TP_KERNEL=1``) — the kernel then runs per head
    shard under ``shard_map`` (``parallel.sharding
    .head_sharded_kernel``), closing the ROADMAP item 3 gap where tp
    serving rode dense cache attention. ``SPARKDL_SERVE_PAGED_KERNEL=1``
    forces engagement on any platform (interpret mode off TPU);
    ``=0`` disables. Force does NOT override the tp ablation: under a
    mesh, ``SPARKDL_SERVE_TP_KERNEL=0`` always restores dense cache
    attention (the documented pre-PR-15 baseline) — a leftover forced
    paged knob must not contaminate that comparison leg. Callers must
    still gate on :func:`supports` — a forced-but-unsupported config
    stands down to the gather view with a one-time warning
    (:func:`warn_fallback`)."""
    mode = kernel_mode()
    if mode == "off":
        return None
    if mesh is not None:
        from .flash_decode import (TP_KERNEL_ENV, _tp_kernel_mode,
                                   _tp_kernel_on)
        if not _tp_kernel_on():
            if mode == "force" and _tp_kernel_mode() != "off":
                # force + tp on a non-TPU backend: the sharded dispatch
                # defaulted off — never densify a forced knob silently
                warn_fallback(
                    f"the sharded tp dispatch is off ({TP_KERNEL_ENV} "
                    f"auto = TPU only; set {TP_KERNEL_ENV}=1 to force "
                    f"it off-chip)")
            return None
    if mode == "auto":
        from .flash_decode import decode_fn_for
        if decode_fn_for(attn_fn, mesh) is None:
            return None
    fn = paged_flash_decode
    if mesh is not None:
        from ..parallel.sharding import head_sharded_kernel
        fn = head_sharded_kernel(fn, mesh)
    return fn


_warned_fallback: set = set()


def warn_fallback(reason: str) -> None:
    """One-time (per reason, host-side) warning when an EXPLICITLY
    requested paged kernel (``SPARKDL_SERVE_PAGED_KERNEL=1``) stands
    down to the gather view — silently densifying would change the HBM
    profile the knob was set to pin (the ``_warn_prefill_fallback``
    pattern in ``models.llama``)."""
    if reason not in _warned_fallback:
        import logging
        logging.getLogger(__name__).warning(
            "%s=1 requested the paged flash-decode kernel but %s; "
            "using the dense gather view (O(max_blocks·block_size) "
            "HBM traffic per slot per step)", PAGED_KERNEL_ENV, reason)
        _warned_fallback.add(reason)
