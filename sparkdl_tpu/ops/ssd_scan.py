"""Mamba-2's recurrence (state-space duality, arXiv:2405.21060) in its
chunked, matrix-product form, as a Pallas TPU kernel pair.

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t outer B_t        [P, N] a head
    y_t = H_t C_t + D_h x_t

``x: [B, S, H, P]``, ``dt: [B, S, H]`` (float32), ``A, D: [H]``,
``B, C: [B, S, G, N]`` (one group: ``G = 1``) -> ``y: [B, S, H, P]`` in
``x``'s dtype, and the state after the last position ``[B, H, P, N]``. With
``a_t = dt_t A_h`` and ``s_t`` its running sum inside a chunk of Q positions:

    Y_intra[t] = sum_{r <= t} exp(s_t - s_r) (C_t . B_r) dt_r x_r
    Y_inter[t] = exp(s_t) C_t . H_start
    H_end      = exp(s_Q) H_start + sum_r exp(s_Q - s_r) dt_r x_r outer B_r

Neither a ``[Q, Q]`` tile nor a state per position ever exists in HBM.

Design:
- ``s`` (a cumulative sum of ``dt * A`` inside each chunk) is plain jax, in
  float32, outside the kernels; its gradient goes back to ``dt`` and ``A``
  through jax's own transpose. The kernels take ``s`` and ``dt`` with the
  positions on the lanes (``[B, H / block_h, 2 block_h, S]``) and turn a head's
  row into a column by one aligned transpose (``flash_attention._as_column``).
- grid = (batch, head blocks, chunks), the chunk axis innermost and
  ``arbitrary``: the ``[block_h * P, N]`` float32 state persists in VMEM
  scratch across a sequence's chunks. ``x`` and ``y`` stay ``[B, S, H * P]``:
  a head narrower than the 128 lanes shares its lanes with its neighbours
  (two heads of 64), and a product is taken over the shared block and kept
  on the head's own lanes by a select, which costs the MXU nothing (its
  columns are 128 wide either way).
- products on the MXU in the inputs' dtype with float32 accumulation
  (``C B^T`` once a head block; per head the masked ``[Q, Q]`` product; the
  state's three products); ``s``, every exponent, the state and all sums in
  float32. Every exponent is of a difference ``<= 0``: the causal mask is
  laid on ``s_t - s_r`` before ``exp``.
- forward (``ssd_scan_fwd``): writes ``y``, the state at each chunk's START
  (``[B, S / Q, H * P, N]`` float32) and the state after the last position.
- backward (``ssd_scan_bwd``): walks the chunks in reverse with the state's
  cotangent in VMEM scratch and rebuilds a chunk's tiles from its saved
  start. ``dB``, ``dC`` leave as per-head-block partial sums, ``dD`` per
  channel; the caller adds them up.
- a ragged ``S`` is padded with ``dt = 0, x = 0`` (the state passes through
  unchanged, exactly).

``interpret=None`` resolves as in ``ops/flash_attention.py``: compiled on a
TPU, interpreted elsewhere (the CPU tests). Compiled, ``chunk`` is a multiple
of 128 and ``block_h * P`` a multiple of 128 (or all of ``H * P``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..utils import scopes
from .flash_attention import _LANES, _NT, _TN, _as_column, _resolve

DEFAULT_CHUNK = 256
DEFAULT_BLOCK_H = 16
_VMEM_LIMIT = 64 * 1024 * 1024
_F32 = jnp.float32


def chunk_cumsum(a, chunk: int):
    """``[B, S, H]``: the running sum of ``a`` inside each chunk of ``chunk``
    positions (it starts anew at every chunk), float32."""
    bsz, s, h = a.shape
    n_k = pl.cdiv(s, chunk)
    a = jnp.pad(a.astype(_F32), ((0, 0), (0, n_k * chunk - s), (0, 0)))
    return jnp.cumsum(a.reshape(bsz, n_k, chunk, h), axis=2).reshape(
        bsz, n_k * chunk, h)[:, :s]


def chunk_decay(dt, A, chunk: int = DEFAULT_CHUNK):
    """``s [B, S, H]``: the running sum of ``dt * A`` inside each chunk of
    ``chunk`` positions, float32. Its entry at a chunk's end is the log of
    what that chunk hands on of a state."""
    return chunk_cumsum(dt.astype(_F32) * A.astype(_F32), chunk)


def _as_row(col):
    """``(Q, 1)`` column -> ``(1, Q)`` lane-oriented row: :func:`_as_column`
    the other way round."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1]


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _head_columns(row_ref, j: int, hb: int):
    """Head ``j`` of the block: ``s`` and ``dt`` as ``(1, Q)`` rows and as
    ``(Q, 1)`` columns, and ``s`` at the chunk's end ``(1, 1)``."""
    s_row = row_ref[0, 0, j:j + 1, :]
    dt_row = row_ref[0, 0, hb + j:hb + j + 1, :]
    s_col, dt_col = _as_column(s_row)[:, :1], _as_column(dt_row)[:, :1]
    q = s_col.shape[0]
    return s_row, dt_row, s_col, dt_col, s_col[q - 1:q, :]


def _causal(q: int):
    """``r <= t`` over a ``(Q, Q)`` tile of rows ``t`` and columns ``r``."""
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _decay_tile(s_col, s_row, causal):
    """``exp(s_t - s_r)`` for ``r <= t``, 0 past the diagonal: the mask is
    laid on the exponent, which is then never positive."""
    return jnp.exp(jnp.where(causal, s_col - s_row, -jnp.inf))


def _on_head(width: int, p: int, i: int, axis: int):
    """A mask of the ``p`` channels of head ``i`` among the ``width`` that a
    lane group holds, along ``axis`` of a 2-D tile."""
    shape = (1, width) if axis == 1 else (width, 1)
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (at >= i * p) & (at < (i + 1) * p)


def _fwd_kernel(x_ref, row_ref, b_ref, c_ref, d_ref, y_ref, start_ref,
                last_ref, h_scr, *, hb: int, p: int, g: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    start_ref[0, 0] = h_scr[:]
    bm, cm = b_ref[0], c_ref[0]                               # (Q, N)
    dtype, q, w = x_ref.dtype, x_ref.shape[1], g * p
    cb = _dot(cm, bm, _NT)                                    # (Q, Q): t, r
    causal = _causal(q)
    for j0 in range(0, hb, g):
        lanes = slice(j0 * p, j0 * p + w)
        xg = x_ref[0, :, lanes]                               # (Q, w)
        h0 = h_scr[lanes, :]                                  # (w, N)
        inter = _dot(cm, h0.astype(dtype), _NT)               # (Q, w)
        y = jnp.zeros((q, w), _F32)
        weight = jnp.zeros((q, w), _F32)      # exp(s_Q - s_r) dt_r, by lane
        kept = jnp.zeros((w, 1), _F32)        # exp(s_Q), by state row
        for i in range(g):
            s_row, dt_row, s_col, dt_col, s_end = _head_columns(
                row_ref, j0 + i, hb)
            m = (cb * _decay_tile(s_col, s_row, causal)
                 * dt_row).astype(dtype)
            mine = _on_head(w, p, i, 1)
            y = jnp.where(mine, _dot(m, xg) + jnp.exp(s_col) * inter, y)
            weight = jnp.where(mine, jnp.exp(s_end - s_col) * dt_col, weight)
            kept = jnp.where(_on_head(w, p, i, 0), jnp.exp(s_end), kept)
        xf = xg.astype(_F32)
        y_ref[0, :, lanes] = (y + d_ref[:, lanes] * xf).astype(dtype)
        h_scr[lanes, :] = kept * h0 + _dot((xf * weight).astype(dtype), bm,
                                           _TN)

    @pl.when(k == pl.num_programs(2) - 1)
    def _last():
        last_ref[0] = h_scr[:]


def _bwd_kernel(x_ref, row_ref, b_ref, c_ref, d_ref, dy_ref, start_ref,
                dx_ref, drow_ref, db_ref, dc_ref, dd_ref, dh_scr, *,
                hb: int, p: int, g: int):
    """One chunk, the chunks walked last to first. ``dh_scr`` carries the
    cotangent of the state this chunk hands on; ``start_ref`` is the state it
    received. ``drow_ref``: ``d s`` and the direct part of ``d dt``, rows as
    in ``row_ref``."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    bm, cm = b_ref[0], c_ref[0]
    dtype, q, w = x_ref.dtype, x_ref.shape[1], g * p
    cb = _dot(cm, bm, _NT)
    causal = _causal(q)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    sublane = jax.lax.broadcasted_iota(jnp.int32, (2 * hb, 1), 0)
    dcb = jnp.zeros((q, q), _F32)
    db = jnp.zeros(bm.shape, _F32)
    dc = jnp.zeros(cm.shape, _F32)
    drow = jnp.zeros((2 * hb, q), _F32)
    for j0 in range(0, hb, g):
        lanes = slice(j0 * p, j0 * p + w)
        xg, dyg = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf, dyf = xg.astype(_F32), dyg.astype(_F32)
        h0, dh1 = start_ref[0, 0, lanes, :], dh_scr[lanes, :]  # (w, N)
        # the two places a state meets the chunk's rows: y's inter-chunk part
        # (before its exp(s_t)) and what the handed-on state's cotangent
        # sends back to each x_r (before its exp(s_Q - s_r) dt_r)
        inter = dyf * _dot(cm, h0.astype(dtype), _NT)          # (Q, w)
        z = _dot(bm, dh1.astype(dtype), _NT)                   # (Q, w)
        xz, hh = xf * z, dh1 * h0
        dx = jnp.zeros((q, w), _F32)
        seen = jnp.zeros((q, w), _F32)        # exp(s_t), by lane
        weight = jnp.zeros((q, w), _F32)      # exp(s_Q - s_r) dt_r, by lane
        kept = jnp.zeros((w, 1), _F32)        # exp(s_Q), by state row
        for i in range(g):
            j = j0 + i
            s_row, dt_row, s_col, dt_col, s_end = _head_columns(
                row_ref, j, hb)
            decay = _decay_tile(s_col, s_row, causal)
            mine, rows = _on_head(w, p, i, 1), _on_head(w, p, i, 0)

            def own(t, mask=mine):            # a head's own lanes, summed
                return jnp.sum(jnp.where(mask, t, 0.0), axis=1, keepdims=True)

            m = (cb * decay * dt_row).astype(dtype)
            t = _dot(jnp.where(mine, dyg, jnp.zeros_like(dyg)), xg,
                     _NT) * decay                              # (Q, Q): t, r
            dcb = dcb + t * dt_row
            u = t * cb
            ddt_row = jnp.sum(u, axis=0, keepdims=True)        # (1, Q)
            e, e_end = jnp.exp(s_col), jnp.exp(s_end)
            f = jnp.exp(s_end - s_col)
            wgt, z_own = f * dt_col, own(xz)
            sent = wgt * z_own                                 # (Q, 1)
            at_q = jnp.sum(sent, axis=0, keepdims=True) + e_end * jnp.sum(
                jnp.where(rows, hh, 0.0), keepdims=True)
            ds_col = jnp.sum(u * dt_row, axis=1, keepdims=True) \
                + e * own(inter) - sent + jnp.where(at_end, at_q, 0.0)
            ds_row = _as_row(ds_col) - ddt_row * dt_row
            drow = jnp.where(sublane == j, ds_row, drow)
            drow = jnp.where(sublane == hb + j,
                             ddt_row + _as_row(f * z_own), drow)
            dx = jnp.where(mine, _dot(m, dyg, _TN) + wgt * z, dx)
            seen = jnp.where(mine, e, seen)
            weight = jnp.where(mine, wgt, weight)
            kept = jnp.where(rows, e_end, kept)
        dy_seen = (dyf * seen).astype(dtype)
        dc = dc + _dot(dy_seen, h0.astype(dtype))
        db = db + _dot((xf * weight).astype(dtype), dh1.astype(dtype))
        dh_scr[lanes, :] = kept * dh1 + _dot(dy_seen, cm, _TN)
        dx_ref[0, :, lanes] = (dx + d_ref[:, lanes] * dyf).astype(dtype)
        dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    dcb = dcb.astype(dtype)
    dc_ref[0, 0] = dc + _dot(dcb, bm)
    db_ref[0, 0] = db + _dot(dcb, cm, _TN)
    drow_ref[0, 0] = drow


def _sizes(s: int, h: int, p: int, chunk: int, block_h: int):
    """``(chunk, block_h, heads a lane group, s_pad)``: a chunk never passes
    the (lane-aligned) length, a head block divides the heads, and heads
    narrower than the lanes share them."""
    chunk = min(chunk, pl.cdiv(s, _LANES) * _LANES) if chunk >= _LANES \
        else chunk
    hb = next(n for n in range(min(block_h, h), 0, -1) if h % n == 0)
    g = next(n for n in range(min(hb, max(1, _LANES // p)), 0, -1)
             if hb % n == 0)
    return chunk, hb, g, pl.cdiv(s, chunk) * chunk


def _operands(x, dt, A, B, C, D, chunk: int, hb: int, s_pad: int):
    """The kernels' operands: ``x`` as ``[B, S, H P]``, the rows of ``s`` and
    ``dt`` by head block, ``B``, ``C`` as ``[B, S, N]``, ``D`` a channel."""
    bsz, s, h, p = x.shape
    if B.shape[2] != 1 or C.shape[2] != 1:
        raise NotImplementedError(
            f"ssd_scan holds one B/C group; got {B.shape[2]}")
    pad = ((0, 0), (0, s_pad - s), (0, 0))
    dt = jnp.pad(dt.astype(_F32), pad)
    rows = jnp.concatenate(
        [t.reshape(bsz, s_pad, h // hb, hb).transpose(0, 2, 3, 1)
         for t in (chunk_decay(dt, A, chunk), dt)], axis=2)
    return (jnp.pad(x.reshape(bsz, s, h * p), pad), rows,
            jnp.pad(B[:, :, 0], pad), jnp.pad(C[:, :, 0], pad),
            jnp.repeat(D.astype(_F32), p)[None, :])


def _params(interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _fwd(x, dt, A, B, C, D, chunk: int, block_h: int, interpret: bool):
    """``(y [B, S, H, P], chunk-start states [B, S / chunk, H P, N], last
    state [B, H P, N])``."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, h, p = x.shape
    n = B.shape[-1]
    chunk, hb, g, s_pad = _sizes(s, h, p, chunk, block_h)
    ops = _operands(x, dt, A, B, C, D, chunk, hb, s_pad)
    n_k, wide = s_pad // chunk, hb * p
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, g=g),
        grid=(bsz, h // hb, n_k),
        in_specs=[
            pl.BlockSpec((1, chunk, wide), lambda b, j, k: (b, k, j)),
            pl.BlockSpec((1, 1, 2 * hb, chunk), lambda b, j, k: (b, j, 0, k)),
            pl.BlockSpec((1, chunk, n), lambda b, j, k: (b, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, j, k: (b, k, 0)),
            pl.BlockSpec((1, wide), lambda b, j, k: (0, j))],
        out_specs=[
            pl.BlockSpec((1, chunk, wide), lambda b, j, k: (b, k, j)),
            pl.BlockSpec((1, 1, wide, n), lambda b, j, k: (b, k, j, 0)),
            pl.BlockSpec((1, wide, n), lambda b, j, k: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s_pad, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_k, h * p, n), _F32),
                   jax.ShapeDtypeStruct((bsz, h * p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((wide, n), _F32)],
        name="ssd_scan_fwd", **_params(interpret))
    with scopes.layer("ssd_scan_fwd"):
        y, starts, last = call(*ops)
    return y[:, :s].reshape(bsz, s, h, p), starts, last


def _bwd(x, dt, A, B, C, D, starts, dy, chunk: int, block_h: int,
         interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, h, p = x.shape
    n = B.shape[-1]
    chunk, hb, g, s_pad = _sizes(s, h, p, chunk, block_h)
    ops = _operands(x, dt, A, B, C, D, chunk, hb, s_pad)
    dy = jnp.pad(dy.reshape(bsz, s, h * p), ((0, 0), (0, s_pad - s), (0, 0)))
    n_k, n_h, wide = s_pad // chunk, h // hb, hb * p

    def back(k):    # grid step k works on the k-th chunk from the end
        return n_k - 1 - k

    wide_rows = pl.BlockSpec((1, chunk, wide),
                             lambda b, j, k: (b, back(k), j))
    head_rows = pl.BlockSpec((1, 1, 2 * hb, chunk),
                             lambda b, j, k: (b, j, 0, back(k)))
    cols = pl.BlockSpec((1, chunk, n), lambda b, j, k: (b, back(k), 0))
    partial_cols = pl.BlockSpec((1, 1, chunk, n),
                                lambda b, j, k: (b, j, back(k), 0))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, g=g),
        grid=(bsz, n_h, n_k),
        in_specs=[wide_rows, head_rows, cols, cols,
                  pl.BlockSpec((1, wide), lambda b, j, k: (0, j)), wide_rows,
                  pl.BlockSpec((1, 1, wide, n),
                               lambda b, j, k: (b, back(k), j, 0))],
        out_specs=[wide_rows, head_rows, partial_cols, partial_cols,
                   pl.BlockSpec((1, 1, wide), lambda b, j, k: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s_pad, h * p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, n_h, 2 * hb, s_pad), _F32),
                   jax.ShapeDtypeStruct((bsz, n_h, s_pad, n), _F32),
                   jax.ShapeDtypeStruct((bsz, n_h, s_pad, n), _F32),
                   jax.ShapeDtypeStruct((bsz, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((wide, n), _F32)],
        name="ssd_scan_bwd", **_params(interpret))
    with scopes.layer("ssd_scan_bwd"):
        dx, drow, db, dc, dd = call(*ops, dy, starts)
        # d s goes back to dt and A through the running sum's own transpose
        by_head = drow.reshape(bsz, n_h, 2, hb, s_pad).transpose(
            2, 0, 4, 1, 3).reshape(2, bsz, s_pad, h)
        _, decay_vjp = jax.vjp(
            lambda t, a: chunk_decay(t, a, chunk), dt.astype(_F32),
            A.astype(_F32))
        ddt, da = decay_vjp(by_head[0, :, :s])
    return (dx[:, :s].reshape(x.shape),
            (ddt + by_head[1, :, :s]).astype(dt.dtype), da.astype(A.dtype),
            db.sum(1)[:, :s, None].astype(B.dtype),
            dc.sum(1)[:, :s, None].astype(C.dtype),
            dd.sum((0, 1)).reshape(h, p).sum(1).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_core(x, dt, A, B, C, D, chunk: int, block_h: int, interpret: bool):
    y, _, last = _fwd(x, dt, A, B, C, D, chunk, block_h, interpret)
    return y, last


# What the forward kernel writes, by the names ``_scan_fwd`` gives them
# (``ops.SAVE_KERNEL_RESIDUALS`` keeps them: the backward reads ``starts``,
# the layer ``y`` and the counter ``last``).
RESIDUAL_NAMES = ("ssd_scan_y", "ssd_scan_starts", "ssd_scan_last")


def _scan_fwd(x, dt, A, B, C, D, chunk, block_h, interpret):
    y, starts, last = map(checkpoint_name, _fwd(
        x, dt, A, B, C, D, chunk, block_h, interpret), RESIDUAL_NAMES)
    return (y, last), (x, dt, A, B, C, D, starts)


def _scan_bwd(chunk, block_h, interpret, res, cts):
    return _bwd(*res, cts[0], chunk, block_h, interpret)


_scan_core.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
             block_h: int = DEFAULT_BLOCK_H, interpret: bool | None = None):
    """``(y [B, S, H, P], last [B, H, P, N])`` of the recurrence above: ``y``
    differentiable in all six operands, ``last`` the state after the last
    position in float32, for reading (the kernel writes it either way; it
    carries no gradient). ``block_h``: heads a grid step; the default is what
    the chip read fastest at the published shape (PERF.md section 5) and
    only the tests and that sweep set another."""
    bsz, _, h, p = x.shape
    y, last = _scan_core(x, dt, A, B, C, D, chunk, block_h,
                         _resolve(interpret))
    return y, jax.lax.stop_gradient(last.reshape(bsz, h, p, -1))
