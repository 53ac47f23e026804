"""Flash attention as a Pallas TPU kernel.

The per-device attention hot op (layout ``[B, H, S, D]``, the convention of
``parallel.ring_attention``). The reference framework had no attention at all
(2017-era image models — SURVEY.md §2.4); this kernel exists for the
transformer families (BERT/Llama) and composes with the shard-level
sequence parallelism: ring attention moves KV blocks across chips over ICI,
and each hop's local compute can run through this kernel.

Design (the standard streaming-softmax factorization, written for the MXU):
- grid = (batch·heads, Q tiles, KV tiles); pallas pipelines each (BK, D)
  KV tile from HBM through the innermost grid dimension while the running
  max ``m``, normalizer ``l``, and unnormalized f32 accumulator persist
  in VMEM scratch across KV steps.
- S·S attention scores never materialize and no full K/V is ever VMEM
  resident — VMEM holds one Q, K, V tile + one score tile, so sequence
  length is bounded by HBM, not VMEM.
- precision, all three kernels: the MXU takes the tiles in the dtype they
  arrive in, with float32 accumulation (float32 callers get float32
  products, bf16 callers bf16 ones); ``p`` (and the backward's ``ds``) is
  rounded to that dtype before its second product, as
  ``parallel.ring_attention.dense_attention`` rounds ``p``; the scale is
  applied after the product; the running max, the exponent, ``l``, the
  accumulators, ``lse`` and ``delta = rowsum(do·o)`` are float32.
- masks, all three kernels (``_when_live``, ``_kv_tile``/``_q_tile``):
  causal dead tile pairs are skipped AND not fetched (their index is
  clamped to the nearest live tile's); only a pair that straddles the
  diagonal builds the causal compare; ``kv_mask`` ([B, S] 0/1) streams as
  (1, BK) tiles and masks padded key positions — the BERT attention-mask
  contract, so flash drops into padded encoder batches, not just causal
  LMs. A query with no attendable key outputs zeros.
- the forward holds its score tile TRANSPOSED, (BK, BQ): a query is a lane,
  so the softmax's max and sum over the keys are elementwise vector work
  down the sublanes, not reductions across the lanes; ``m``, ``l`` and the
  logsumexp are (1, BQ) rows and the accumulator is (D, BQ), transposed
  once per Q tile into the output.
- the logsumexp output is blocked (1, BQ) per q-tile program — every store
  is a full-block write, no dynamic lane-dim slicing (round-1 advisor
  flagged the previous ``pl.ds`` store as a Mosaic alignment risk).
- backward: custom_vjp over two more kernels, ``flash_attention_bwd_dkv``
  (grid (batch·heads, KV tiles, Q tiles): float32 dk/dv accumulate in VMEM
  while Q/dO tiles stream) and ``flash_attention_bwd_dq`` (the forward's
  grid; dq accumulates). Each recomputes its score tile from the saved
  logsumexp (``p = exp(q·kᵀ·scale − lse)``), so activations stay O(S·D) —
  the flash-attention memory contract — and no score-sized array is ever
  an HBM operand. The dkv kernel holds its score tile transposed too, so
  the row statistics broadcast from their lane-oriented blocks and no
  score-sized transpose is needed in any kernel. The ``fwd`` rule names
  what the backward reads of the forward's outputs (``RESIDUAL_NAMES``: ``o``
  and ``lse``, through ``checkpoint_name``), so a ``jax.checkpoint`` whose
  policy saves those names (``ops.SAVE_KERNEL_RESIDUALS``, on the decoder
  layers' ``nn.remat``) runs the forward kernel once; under any other
  checkpoint, or none, the names are the identity.

``interpret=True`` (or platform != tpu) runs the same kernel through the
Pallas interpreter — how CPU tests validate kernel semantics; a TPU-gated
compiled-mode test runs in the bench environment.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..utils import scopes

NEG_INF = -1e30


_LANES = 128  # the backward keeps per-row stats broadcast across a lane tile


def _tile_is_live(qi, ki, block_q: int, block_k: int, window=None):
    """Under ``causal``: whether Q tile ``qi`` has any row at or past KV
    tile ``ki``'s first column — the one rule all three kernels'
    ``pl.when`` and index maps follow (ints in, bool out; traced in, traced
    out). Under a ``window`` (row ``t`` sees keys ``t - window + 1 .. t``)
    also whether the tile's last column is inside the first row's band."""
    live = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        live = live & (ki * block_k + block_k - 1 > qi * block_q - window)
    return live


def _first_live_q(ki, block_q: int, block_k: int):
    """Under ``causal`` the Q tiles dead for KV tile ``ki`` are the leading
    ones: the first live one."""
    return (ki * block_k) // block_q


def _last_live_kv(qi, block_q: int, block_k: int):
    """Under ``causal`` the KV tiles dead for Q tile ``qi`` are the trailing
    ones: the last live one."""
    return (qi * block_q + block_q - 1) // block_k


def _first_live_kv(qi, block_q: int, block_k: int, window: int):
    """Under a ``window`` the leading KV tiles are dead for Q tile ``qi``
    too: the tile of the first key its first row sees."""
    first_key = qi * block_q - (window - 1)
    return (first_key + abs(first_key)) // 2 // block_k    # max(., 0)


def _last_live_q(ki, block_q: int, block_k: int, window: int):
    """Under a ``window`` the trailing Q tiles are dead for KV tile ``ki``:
    the tile of the last row that sees its last key (it may lie past the
    sequence's last tile)."""
    return (ki * block_k + block_k - 1 + window - 1) // block_q


def _band_steps_kv(n_q: int, block_q: int, block_k: int, window: int) -> int:
    """The most KV tiles any Q tile's band touches: under a window the
    forward's and the dq kernel's innermost grid axis spans these and no more
    (a dead grid step still costs its ~0.35 us). 2 at 512-blocks and a
    window of 512, whatever the sequence. (The edges are written out here:
    the grid's size is not the index maps' clamp.)"""
    return max((qi * block_q + block_q - 1) // block_k
               - max(qi * block_q - (window - 1), 0) // block_k + 1
               for qi in range(n_q))


def _band_steps_q(n_q: int, n_kv: int, block_q: int, block_k: int,
                  window: int) -> int:
    """``_band_steps_kv``'s twin for the dkv kernel's Q axis."""
    return max(min((ki * block_k + block_k - 1 + window - 1) // block_q,
                   n_q - 1) - (ki * block_k) // block_q + 1
               for ki in range(n_kv))


def _kv_index(qi, step, block_q: int, block_k: int, window):
    """The KV tile grid step ``step`` of Q tile ``qi`` stands for: the step
    itself, or under a window the step counted from the band's lower edge."""
    if window is None:
        return step
    return _first_live_kv(qi, block_q, block_k, window) + step


def _q_index(ki, step, block_q: int, block_k: int, window):
    """``_kv_index``'s twin for the kernel that streams Q tiles past a KV
    tile: under a window the steps count from the diagonal."""
    if window is None:
        return step
    return _first_live_q(ki, block_q, block_k) + step


def _kv_tile(qi, step, block_q: int, block_k: int, causal: bool,
             window=None):
    """The KV tile a (Q tile ``qi``, grid step ``step``) fetches: a dead
    pair does no work and fetches nothing — its index is clamped to the
    nearest live tile's, whose block the pipeline already holds."""
    ki = _kv_index(qi, step, block_q, block_k, window)
    return (jnp.minimum(ki, _last_live_kv(qi, block_q, block_k))
            if causal else ki)


def _q_tile(step, ki, block_q: int, block_k: int, causal: bool,
            window=None, n_q=None):
    """``_kv_tile``'s twin for the kernel that streams Q tiles past a KV
    tile."""
    if window is None:
        return (jnp.maximum(step, _first_live_q(ki, block_q, block_k))
                if causal else step)
    last = jnp.minimum(_last_live_q(ki, block_q, block_k, window), n_q - 1)
    return jnp.minimum(_q_index(ki, step, block_q, block_k, window), last)


_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract both operands' last dim
_TN = (((0,), (0,)), ((), ()))  # a.T @ b: contract both operands' first dim


def _as_column(row):
    """(1, N) lane-oriented row -> (N, _LANES) with every lane of row n
    holding ``row[0, n]``: per-position values arrive in ``lse``'s
    lane-oriented layout and a score tile needs them down its sublanes.
    A sublane broadcast and one aligned 2-D transpose. The backward
    kernels do it once per accumulator into VMEM scratch; the forward,
    whose keys change every grid step, once per tile pair for the (1, BK)
    key-validity row (0.65 of its 13.3 ms at the training cell's shape)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _when_live(update, qi, ki, block_q: int, block_k: int, causal: bool,
               window=None, n_q=None):
    """Run ``update(on_diagonal, on_edge)`` for a live tile pair and nothing
    for a dead one. Only a pair that straddles the diagonal (some column past
    some row) pays for the causal compare, and only one that straddles the
    band's lower edge (some column at or before some row's ``t - window``)
    for the window's; a pair wholly between them is as unmasked as a
    non-causal one. ``n_q``: the Q tiles there are, where the grid's Q steps
    may count past them (the dkv kernel under a window)."""
    if not causal:
        update(False)
        return
    live = _tile_is_live(qi, ki, block_q, block_k, window)
    straddles = ki * block_k + block_k - 1 > qi * block_q
    if window is None:
        pl.when(live & straddles)(functools.partial(update, True))
        # no column past any row: wholly below the diagonal, so live
        pl.when(jnp.logical_not(straddles))(functools.partial(update, False))
        return
    if n_q is not None:
        live = live & (qi < n_q)
    on_edge = qi * block_q + block_q - 1 - ki * block_k >= window
    for diag in (False, True):
        for edge in (False, True):
            pl.when(live & (straddles == diag) & (on_edge == edge))(
                functools.partial(update, diag, edge))


def _band_mask(keep, qi, ki, block_q: int, block_k: int, shape, q_axis: int,
               on_diagonal: bool, on_edge: bool, window):
    """``keep`` and the causal compare (``on_diagonal``) and the window's
    (``on_edge``) over a score tile of ``shape`` whose queries run along
    ``q_axis``."""
    if not (on_diagonal or on_edge):
        return keep
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                   1 - q_axis)
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    if on_diagonal:
        keep = keep & (cols <= rows)
    if on_edge:
        keep = keep & (cols > rows - window)
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, causal: bool, sm_scale: float,
                seq_len: int, window=None):
    """Grid = (B·H, Q tiles, KV tiles); KV tiles stream through VMEM via the
    innermost grid dimension (pallas pipelines the HBM loads), while the
    accumulator and the per-query (m, l) stats persist in VMEM scratch
    across KV steps. VMEM holds one Q, one K, one V tile + scratch — never
    the full sequence.

    The score tile is held TRANSPOSED, (BK, BQ), as the dkv kernel holds
    its own: a query is a lane, so the softmax's max and sum over the keys
    run down the sublanes — elementwise vector work — and not across the
    lanes (512 x 512 row reductions across lanes were 10 of this kernel's
    25 ms at the training cell's shape). ``m``, ``l`` and ``lse`` are
    (1, BQ) rows, the accumulator is (D, BQ), and the one transpose is the
    output's, once per Q tile. Both products take the tiles in the dtype
    they arrive in (``p`` rounded to ``v``'s) with float32 accumulation;
    the max, the exponent, ``alpha``, ``l``, the accumulator, ``lse`` and
    the final division are float32."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi, step = pl.program_id(1), pl.program_id(2)
    ki = _kv_index(qi, step, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _update(on_diagonal: bool, on_edge: bool = False):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = (cols < seq_len) & (mask_ref[0, 0] > 0)     # (1, BK)
        keep = _as_column(valid.astype(jnp.float32))[:, :1] > 0   # (BK, 1)
        keep = _band_mask(keep, qi, ki, block_q, block_k,
                          (block_k, block_q), 1, on_diagonal, on_edge, window)
        st = jnp.where(keep, st, NEG_INF)
        m_prev = m_ref[:]                                   # (1, BQ)
        m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
        # A masked entry is exactly NEG_INF: less any real maximum it is
        # still -1e30 and its exponent exactly 0. A query with no key so
        # far has m_new = NEG_INF; 0 stands in for it, so its p is 0 too
        # and its accumulator stays at exact zero (no select on the tile).
        pt = jnp.exp(st - jnp.where(m_new <= NEG_INF, 0.0, m_new))  # (BK, BQ)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(pt, axis=0, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            v, pt.astype(v.dtype), _TN, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    _when_live(_update, qi, ki, block_q, block_k, causal, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l > 0, l, 1.0)  # fully-masked rows (padding)
        o_ref[0] = (acc_ref[:] / safe_l).T.astype(o_ref.dtype)
        # lse rides in the PRE-BLOCKED 4-D layout (B·H, Sq tiles, 1, BQ):
        # its (1, 1, 1, BQ) block's trailing dims (1, BQ) EQUAL the array
        # dims, which satisfies Mosaic's block rule (sublane ∈ 8ℤ ∪
        # {array dim}, lane ∈ 128ℤ ∪ {array dim}) for ANY BQ, and the
        # in-kernel store stays a plain 2-D (1, BQ) lane-oriented write —
        # the layout the stats are kept in. The real chip rejects the
        # flat layouts ((1, BQ) block over (B·H, S): sublane 1 ∤ 8 ≠ B·H;
        # (…, 1, BQ) block over (B·H, 1, S): BQ < 128 ∤ 128) — a round-5
        # on-chip finding the interpreter cannot reproduce.
        lse_ref[0, 0] = m_ref[:] + jnp.log(safe_l)


def _tiles(s: int, block_q: int, block_k: int):
    """(bq, bk, s_pad) for a sequence of ``s``. Blocks never shrink below
    the 128-lane alignment: a sequence shorter than the block is PADDED up
    to it instead (the seq_len mask keeps the math exact). Shrinking to odd
    sizes (min(block, s) with s=37) would hand Mosaic 37-wide score tiles —
    an alignment hazard the interpret-mode tests cannot catch. Callers may
    still pass smaller explicit blocks for interpret-mode tests."""
    bq = min(block_q, pl.cdiv(s, _LANES) * _LANES)
    bk = min(block_k, pl.cdiv(s, _LANES) * _LANES)
    unit = math.lcm(bq, bk)
    return bq, bk, pl.cdiv(s, unit) * unit


def _pad_rows(x3, s_pad: int):
    """[B·H, S, D] zero-padded to S = ``s_pad``."""
    return jnp.pad(x3, ((0, 0), (0, s_pad - x3.shape[1]), (0, 0)))


def _blocked_rows(x2, s_pad: int, block: int):
    """[B·H, S] per-position values -> the pre-blocked 4-D float32 stream
    (B·H, S/block, 1, block), zero-padded: each (1, 1, 1, block) block's
    trailing dims (1, block) EQUAL the array dims, so the layout is
    Mosaic-legal for ANY block and a kernel reads or writes a plain 2-D
    (1, block) lane-oriented tile (see the lse comment in _fwd_kernel for
    the rejected flat layouts)."""
    x2 = jnp.pad(x2.astype(jnp.float32), ((0, 0), (0, s_pad - x2.shape[1])))
    return x2.reshape(x2.shape[0], s_pad // block, 1, block)


def _per_head(kv_mask, h: int):
    """[B, S] 0/1 kv mask -> [B·H, S] (tiny next to K/V tiles)."""
    b, s = kv_mask.shape
    return jnp.broadcast_to(kv_mask[:, None, :], (b, h, s)).reshape(b * h, s)


def _rows3(t, s_pad: int):
    """[B, H, S, D] -> [B·H, S padded, D]."""
    b, h, s, d = t.shape
    return _pad_rows(t.reshape(b * h, s, d), s_pad)


def _fwd(q, k, v, kv_mask, causal: bool, block_q: int, block_k: int,
         interpret: bool, window=None):
    b, h, s, d = q.shape
    dv = v.shape[-1]             # the values may be wider than the keys
    bq, bk, s_pad = _tiles(s, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    q3, k3, v3 = (_rows3(t, s_pad) for t in (q, k, v))
    m4 = _blocked_rows(_per_head(kv_mask, h), s_pad, bk)
    from jax.experimental.pallas import tpu as pltpu

    n_kv = s_pad // bk
    if window is not None:       # the KV axis spans the band and no more
        n_kv = _band_steps_kv(s_pad // bq, bq, bk, window)
    grid = (b * h, s_pad // bq, n_kv)

    def kv_tile(bh, i, j):
        return bh, _kv_tile(i, j, bq, bk, causal, window)

    def rows_kv(width):
        return pl.BlockSpec((1, bk, width),
                            lambda bh, i, j: (*kv_tile(bh, i, j), 0))

    call = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          seq_len=s, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            rows_kv(d), rows_kv(dv),
            pl.BlockSpec((1, 1, 1, bk),
                         lambda bh, i, j: (*kv_tile(bh, i, j), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda bh, i, j: (bh, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_pad // bq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),   # acc, transposed
            pltpu.VMEM((1, bq), jnp.float32),    # running max m
            pltpu.VMEM((1, bq), jnp.float32),    # normalizer l
        ],
        # (bh, q-tile) carry no cross-step state — only the innermost kv
        # dimension threads the (acc, m, l) scratch — so Mosaic may
        # parallelize/reorder the outer grid freely
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )
    with scopes.layer("flash_attention_fwd"):
        o3, lse2 = call(q3, k3, v3, m4)
    return (o3[:, :s].reshape(b, h, s, dv),
            lse2.reshape(b * h, s_pad)[:, :s].reshape(b, h, s))


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_ref, dk_ref, dv_ref, dk_acc, dv_acc, valid_ref, *,
                    causal: bool, sm_scale: float, seq_len: int,
                    window=None, n_q=None):
    """Grid = (B·H, KV tiles, Q tiles), Q innermost: one K/V tile stays in
    VMEM while the Q/dO tiles stream past it and float32 ``dk``/``dv``
    accumulate in scratch. The score tile is held TRANSPOSED, (BK, BQ):
    ``lse`` and ``delta`` then broadcast down the sublanes straight from
    their (1, BQ) blocks, and all four products are plain ``a @ b`` /
    ``a @ b.T`` — no score-sized transpose."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    ki, step = pl.program_id(1), pl.program_id(2)
    qi = _q_index(ki, step, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = (cols < seq_len) & (mask_ref[0, 0] > 0)
        valid_ref[:] = _as_column(valid.astype(jnp.float32))

    def _update(on_diagonal: bool, on_edge: bool = False):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * sm_scale
        keep = valid_ref[:, :1] > 0                         # (BK, 1)
        keep = _band_mask(keep, qi, ki, block_q, block_k,
                          (block_k, block_q), 1, on_diagonal, on_edge, window)
        # exp first, select after: a masked entry may overflow (a fully
        # masked row's lse is NEG_INF) and the select drops it
        pt = jnp.where(keep, jnp.exp(st - lse_ref[0, 0]), 0.0)   # (BK, BQ)
        dv_acc[:] += jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, 0])    # sm_scale: once, at the end
        dk_acc[:] += jnp.dot(dst.astype(q.dtype), q,
                             preferred_element_type=jnp.float32)

    _when_live(_update, qi, ki, block_q, block_k, causal, window, n_q)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                   dq_ref, dq_acc, lse_col, delta_col, *, causal: bool,
                   sm_scale: float, seq_len: int, window=None):
    """Grid = (B·H, Q tiles, KV tiles), KV innermost, as the forward: one
    Q/dO tile stays while K/V tiles stream and float32 ``dq`` accumulates.
    The score tile is (BQ, BK) here, so ``kv_mask`` broadcasts from its
    (1, BK) block and ``lse``/``delta`` are turned into columns once per
    Q tile."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi, step = pl.program_id(1), pl.program_id(2)
    ki = _kv_index(qi, step, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        lse_col[:] = _as_column(lse_ref[0, 0])
        delta_col[:] = _as_column(delta_ref[0, 0])

    def _update(on_diagonal: bool, on_edge: bool = False):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * sm_scale
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        keep = (cols < seq_len) & (mask_ref[0, 0] > 0)      # (1, BK)
        keep = _band_mask(keep, qi, ki, block_q, block_k,
                          (block_q, block_k), 0, on_diagonal, on_edge, window)
        p = jnp.where(keep, jnp.exp(s - lse_col[:, :1]), 0.0)    # (BQ, BK)
        dp = jax.lax.dot_general(
            do, v, _NT, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col[:, :1])
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    _when_live(_update, qi, ki, block_q, block_k, causal, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * sm_scale).astype(dq_ref.dtype)


def _bwd(q, k, v, kv_mask, o, lse, do, causal: bool, block_q: int,
         block_k: int, interpret: bool, window=None):
    """dq, dk, dv by the two kernels above, from the forward's residuals.
    Tiles are recomputed from ``lse``; nothing score-sized touches HBM."""
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    dv = v.shape[-1]
    bq, bk, s_pad = _tiles(s, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    q3, k3, v3, do3 = (_rows3(t, s_pad) for t in (q, k, v, do))
    # padded query rows: lse 0 keeps p finite, do = delta = 0 keep it unused
    lse4 = _blocked_rows(lse.reshape(b * h, s), s_pad, bq)
    delta4 = _blocked_rows(delta.reshape(b * h, s), s_pad, bq)
    m4 = _blocked_rows(_per_head(kv_mask, h), s_pad, bk)
    n_q, n_kv = s_pad // bq, s_pad // bk
    steps_q, steps_kv = n_q, n_kv
    if window is not None:       # each inner axis spans the band and no more
        steps_q = _band_steps_q(n_q, n_kv, bq, bk, window)
        steps_kv = _band_steps_kv(n_q, bq, bk, window)
    kernel_args = dict(causal=causal, sm_scale=sm_scale, seq_len=s,
                       window=window)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def q_tile(bh, j, i):
        return bh, _q_tile(i, j, bq, bk, causal, window, n_q)

    def kv_tile(bh, i, j):
        return bh, _kv_tile(i, j, bq, bk, causal, window)

    def rows_q(width):
        return pl.BlockSpec((1, bq, width),
                            lambda bh, j, i: (*q_tile(bh, j, i), 0))

    def rows_kv(width):
        return pl.BlockSpec((1, bk, width), lambda bh, j, i: (bh, j, 0))

    stat_q = pl.BlockSpec((1, 1, 1, bq),
                          lambda bh, j, i: (*q_tile(bh, j, i), 0, 0))
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, **kernel_args),
        grid=(b * h, n_kv, steps_q),
        in_specs=[rows_q(d), rows_kv(d), rows_kv(dv), rows_q(dv), stat_q,
                  stat_q,
                  pl.BlockSpec((1, 1, 1, bk), lambda bh, j, i: (bh, j, 0, 0))],
        out_specs=[rows_kv(d), rows_kv(dv)],
        out_shape=[jax.ShapeDtypeStruct((b * h, s_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s_pad, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),        # dk
                        pltpu.VMEM((bk, dv), jnp.float32),       # dv
                        pltpu.VMEM((bk, _LANES), jnp.float32)],  # key validity
        compiler_params=semantics, interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q3, k3, v3, do3, lse4, delta4, m4)

    def rows_q(width):
        return pl.BlockSpec((1, bq, width), lambda bh, i, j: (bh, i, 0))

    def rows_kv(width):
        return pl.BlockSpec((1, bk, width),
                            lambda bh, i, j: (*kv_tile(bh, i, j), 0))

    stat_q = pl.BlockSpec((1, 1, 1, bq), lambda bh, i, j: (bh, i, 0, 0))
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kernel_args),
        grid=(b * h, n_q, steps_kv),
        in_specs=[rows_q(d), rows_kv(d), rows_kv(dv), rows_q(dv), stat_q,
                  stat_q,
                  pl.BlockSpec((1, 1, 1, bk),
                               lambda bh, i, j: (*kv_tile(bh, i, j), 0, 0))],
        out_specs=rows_q(d),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),        # dq
                        pltpu.VMEM((bq, _LANES), jnp.float32),   # lse, columns
                        pltpu.VMEM((bq, _LANES), jnp.float32)],  # delta
        compiler_params=semantics, interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q3, k3, v3, do3, lse4, delta4, m4)
    return tuple(t[:, :s].reshape(b, h, s, -1) for t in (dq3, dk3, dv3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, kv_mask, causal: bool, block_q: int, block_k: int,
                interpret: bool, window=None):
    o, _ = _fwd(q, k, v, kv_mask, causal, block_q, block_k, interpret,
                window)
    return o


# What the forward kernel writes and the backward pair reads, by the names
# ``_flash_fwd`` gives them (``ops.SAVE_KERNEL_RESIDUALS`` keeps them).
RESIDUAL_NAMES = ("flash_attention_o", "flash_attention_lse")


def _flash_fwd(q, k, v, kv_mask, causal, block_q, block_k, interpret,
               window):
    o, lse = map(checkpoint_name, _fwd(
        q, k, v, kv_mask, causal, block_q, block_k, interpret, window),
        RESIDUAL_NAMES)
    return o, (q, k, v, kv_mask, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, res, do):
    q, k, v, kv_mask, o, lse = res
    with scopes.layer("flash_attention_bwd"):
        dq, dk, dv = _bwd(q, k, v, kv_mask, o, lse, do, causal, block_q,
                          block_k, interpret, window)
    return dq, dk, dv, jnp.zeros_like(kv_mask)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False, *, kv_mask=None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None,
                    window: int | None = None):
    """Pallas flash attention. q/k: ``[B, H, S, D]``, v: ``[B, H, S, Dv]``
    (``Dv`` may differ from ``D``: differential attention's values are twice
    as wide as its keys) → ``[B, H, S, Dv]``.

    ``window`` (with ``causal``): position ``t`` attends keys
    ``t - window + 1 .. t``. A tile pair outside that band is neither
    computed nor fetched, and the kernels' innermost grid axis spans the
    band's tiles only.

    ``kv_mask``: optional ``[B, S]`` 0/1 array — key positions with 0 are
    excluded from every query's softmax (the BERT attention-mask contract).
    ``interpret=None`` auto-selects: compiled kernel on TPU, interpreter
    elsewhere (CPU tests). Same (q, k, v, causal=...) signature as
    ``parallel.dense_attention``, so it drops into ``LlamaModel(attn_fn=…)``
    and ``BertEncoder(attn_fn=…)``.

    ``block_q``/``block_k`` default from ``SPARKDL_FLASH_BLOCK_Q``/``_K``
    when set, else from ``_default_block``'s measured cost model — the
    round-5 on-chip sweep with a trustworthy barrier (fetch-closed scan
    chains; bench flash leg) measured 512-blocks fastest at EVERY swept
    length (s512 0.042 ms vs 0.107 at 128; s2048 0.43 vs 1.34), so the
    default prefers the largest block unless the padding it forces on a
    ragged length outweighs its per-work advantage.
    """
    import os
    if window is not None and not (causal and window >= 1):
        raise ValueError("a window needs causal=True and at least one key")
    s_len = q.shape[2]
    if block_q is None:
        env_q = os.environ.get("SPARKDL_FLASH_BLOCK_Q")
        block_q = int(env_q) if env_q else _default_block(s_len)
    if block_k is None:
        env_k = os.environ.get("SPARKDL_FLASH_BLOCK_K")
        block_k = int(env_k) if env_k else _default_block(s_len)
    b, _, s, _ = q.shape
    if kv_mask is None:
        kv_mask = jnp.ones((b, s), jnp.float32)
    else:
        kv_mask = kv_mask.astype(jnp.float32)
    return _flash_core(q, k, v, kv_mask, causal, block_q, block_k,
                       _resolve(interpret), window)


# Relative per-unit-work kernel speed by block size, measured on TPU v5
# lite (round-5 bench flash leg, fetch-closed scan-chain timing): 512-
# blocks run ~2.5x faster per tile-work than 128, 256 ~1.45x — fewer grid
# steps, better DMA amortization, and the MXU fed 512-row tiles.
_BLOCK_SPEED = {128: 1.0, 256: 1.45, 512: 2.5}


def _default_block(s_len: int) -> int:
    """Pick the block minimizing estimated cost = (padded work) / (per-
    work speed).  Bigger blocks are uniformly faster per unit work on v5e
    (see _BLOCK_SPEED), but a ragged length pads up to the block multiple
    and the extra tiles are real MXU/HBM work: at s=640 a 512-block pads
    to 1024 (2.56x the tile area) and loses to 256; at s=1152 even the
    33% pad of a 512-block wins on its 2.5x speed."""
    s128 = pl.cdiv(s_len, _LANES) * _LANES

    def cost(blk):
        padded = pl.cdiv(s128, blk) * blk
        return (padded / s128) ** 2 / _BLOCK_SPEED[blk]

    return min((512, 256, 128), key=cost)


def _resolve(interpret: bool | None) -> bool:
    if interpret is None:
        from sparkdl_tpu.utils.platform import is_tpu_backend
        return not is_tpu_backend()
    return interpret


def dense_attention_masked(q, k, v, causal: bool = False, kv_mask=None,
                           window: int | None = None):
    """The short-sequence arm of :func:`adaptive_attention`: delegates to
    ``parallel.ring_attention.dense_attention`` (ONE source of truth for
    the reference numerics, including the flash kernel's fully-masked-
    row-outputs-zeros contract)."""
    from ..parallel.ring_attention import dense_attention
    return dense_attention(q, k, v, causal, kv_mask, window)


def _flash_min_seq() -> int:
    import os
    return int(os.environ.get("SPARKDL_FLASH_MIN_SEQ", "2048"))


def adaptive_attention(q, k, v, causal: bool = False, *, kv_mask=None,
                       interpret: bool | None = None,
                       window: int | None = None):
    """Length-adaptive attention: the Pallas flash kernel at and above
    ``SPARKDL_FLASH_MIN_SEQ`` (default 2048), XLA dense attention below.

    The round-5 on-chip measurements (fetch-closed scan-chain timing, v5e)
    put the crossover between S=1024 and S=2048 for [B=2, H=8, D=64]:
    dense 0.014/0.054/1.14 ms at S=512/1024/2048 vs flash (512-blocks)
    0.042/0.146/0.43 ms — below the crossover XLA's fused dense attention
    wins outright (the S^2 scores still fit VMEM tiles), above it dense
    goes HBM-bound on the materialized scores and the streaming kernel
    takes over.  The branch is on a static shape, so under jit each
    sequence length traces exactly one arm."""
    if q.shape[2] >= _flash_min_seq():
        return flash_attention(q, k, v, causal, kv_mask=kv_mask,
                               interpret=interpret, window=window)
    return dense_attention_masked(q, k, v, causal, kv_mask, window)


def auto_attn_fn():
    """The default-attention policy: :func:`adaptive_attention` on TPU
    (flash kernel at long S, XLA dense below the measured crossover),
    ``None`` (dense attention in-model) elsewhere. Models accept the
    returned value as their ``attn_fn``; pass through to
    ``LlamaModel(attn_fn=auto_attn_fn())`` / ``BertEncoder(attn_fn=…)``."""
    from sparkdl_tpu.utils.platform import is_tpu_backend
    if is_tpu_backend():
        return adaptive_attention
    return None


def resolve_attn_fn(attn_fn):
    """Model-side resolver: the sentinel ``"auto"`` (the BERT/Llama module
    default) becomes :func:`auto_attn_fn`'s pick at TRACE time — flash on
    TPU, in-model dense elsewhere; any explicit callable or None passes
    through untouched."""
    if attn_fn == "auto":
        return auto_attn_fn()
    return attn_fn
