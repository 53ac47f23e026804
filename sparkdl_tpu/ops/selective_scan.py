"""The selective state-space recurrence (Mamba-1, arXiv:2312.00752) as a
Pallas TPU kernel pair.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) outer B_t      [C, N] a position
    y_t = h_t C_t + D * u_t

``u, dt: [B, S, C]``, ``A: [C, N]``, ``B, C: [B, S, N]``, ``D: [C]`` ->
``y: [B, S, C]`` in ``u``'s dtype, and the last position's state. The per-position state ``[S, C, N]``
(2.7 GB a layer at S = 8192, C = 5120, N = 16) never exists: it lives in VMEM,
forward and backward.

Design:
- grid = (batch, channel blocks, sequence chunks), the chunk axis innermost
  and ``arbitrary``: the ``(N, block_c)`` float32 state persists in VMEM
  scratch across a sequence's chunks. The state is held TRANSPOSED, states on
  the sublanes and channels on the lanes: ``dt_t`` and ``u_t`` are lane rows
  broadcast down the sublanes, ``B_t`` and ``C_t`` sublane columns broadcast
  across the lanes, and ``y_t`` is a sum down the sublanes.
- ``B`` and ``C`` arrive transposed, ``(N, chunk)`` blocks with the positions
  on the lanes; position ``t``'s column is picked by a one-lane mask and a sum
  across the lanes (no dynamic lane slice), and the backward writes ``dB_t``,
  ``dC_t`` into their ``(N, chunk)`` accumulators by the same mask.
- everything inside is float32 whatever the inputs' dtypes (each block is
  cast once into VMEM scratch, which also makes every per-position row load a
  32-bit one); ``y``, ``du`` leave in ``u``'s dtype, ``ddt`` in ``dt``'s.
- forward (``selective_scan_fwd``): writes ``y`` and the state at each
  chunk's START (``[B, S / chunk, N, C]`` float32: 21 MB at the cell's shape
  and chunk 128), and the state after the last position.
- backward (``selective_scan_bwd``): walks the chunks in reverse. A chunk's
  states are rebuilt from its saved start into VMEM scratch
  (``(chunk + 1, N, block_c)``), then one reverse pass carries
  ``dL/dh`` across positions and chunks; ``dA`` and ``dD`` accumulate in their
  VMEM-resident output blocks over a sequence's chunks; ``dB``, ``dC`` leave
  as per-channel-block partial sums (``[B, C / block_c, N, S]``) that the
  caller adds up.
- a ragged ``S`` is padded with ``dt = 0, u = 0`` (the state passes through
  unchanged, exactly), ragged channels with zeros.

``interpret=None`` resolves as in ``ops/flash_attention.py``: compiled on a
TPU, interpreted elsewhere (the CPU tests). Compiled, ``chunk`` and
``block_c`` are multiples of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..utils import scopes

_LANES = 128
DEFAULT_CHUNK = 128
DEFAULT_BLOCK_C = 1024
_VMEM_LIMIT = 48 * 1024 * 1024
_UNROLL = 8


def _positions(chunk: int, body, carry):
    """``body(t, carry)`` over the chunk's positions in order, ``_UNROLL``
    positions to one loop iteration (the kernels' ``fori_loop`` unrolls
    wholly or not at all): one position's loads, exponent and column picks
    do not wait on the position before, and in one basic block the scheduler
    can run them under its recurrence."""
    unroll = next(n for n in (_UNROLL, 4, 2, 1) if chunk % n == 0)

    def some(i, carry):
        for j in range(unroll):
            carry = body(i * unroll + j, carry)
        return carry

    return jax.lax.fori_loop(0, chunk // unroll, some, carry)


def _column(rows_t, lane, t):
    """Column ``t`` of an ``(N, chunk)`` block as ``(N, 1)``: a one-lane mask
    and a sum across the lanes."""
    return jnp.sum(jnp.where(lane == t, rows_t, 0.0), axis=1, keepdims=True)


def _step(h, t, a, uf, dtf, bt, lane):
    """``h_t`` from ``h_{t-1}`` (``(N, block_c)``) at position ``t`` of the
    chunk: the one recurrence both kernels run."""
    dt_t = dtf[pl.ds(t, 1), :]
    return jnp.exp(dt_t * a) * h \
        + (dt_t * uf[pl.ds(t, 1), :]) * _column(bt, lane, t)


def _fwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref,
                y_ref, start_ref, last_ref, h_scr, uf, dtf, yf):
    chunk = u_ref.shape[1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_scr[:] = jnp.zeros_like(h_scr)

    start_ref[0, 0] = h_scr[:]
    uf[:] = u_ref[0].astype(jnp.float32)
    dtf[:] = dt_ref[0].astype(jnp.float32)
    a = a_ref[:]
    bt = bt_ref[0].astype(jnp.float32)
    ct = ct_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def body(t, h):
        h = _step(h, t, a, uf, dtf, bt, lane)
        yf[pl.ds(t, 1), :] = jnp.sum(h * _column(ct, lane, t), axis=0,
                                     keepdims=True)
        return h

    h = _positions(chunk, body, h_scr[:])
    h_scr[:] = h
    y_ref[0] = (yf[:] + d_ref[:] * uf[:]).astype(y_ref.dtype)

    @pl.when(k == pl.num_programs(2) - 1)
    def _last():
        last_ref[0] = h


def _bwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, dy_ref,
                start_ref, du_ref, ddt_ref, dbt_ref, dct_ref, da_ref, dd_ref,
                w_scr, hall, uf, dtf, dyf, duf, ddtf):
    """One chunk, the chunks walked last to first. ``w_scr`` carries
    ``exp(dt_{t+1} A) * dL/dh_{t+1}`` from the chunk after; ``hall[t + 1]``
    is ``h_t`` rebuilt from the saved start ``hall[0]``."""
    chunk = u_ref.shape[1]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        w_scr[:] = jnp.zeros_like(w_scr)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    uf[:] = u_ref[0].astype(jnp.float32)
    dtf[:] = dt_ref[0].astype(jnp.float32)
    dyf[:] = dy_ref[0].astype(jnp.float32)
    a = a_ref[:]
    bt = bt_ref[0].astype(jnp.float32)
    ct = ct_ref[0].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    hall[0] = start_ref[0, 0]

    def rebuild(t, h):
        h = _step(h, t, a, uf, dtf, bt, lane)
        hall[t + 1] = h
        return h

    _positions(chunk, rebuild, hall[0])

    def reverse(i, carry):
        w, da, dbt, dct = carry
        t = chunk - 1 - i
        dt_t, u_t = dtf[pl.ds(t, 1), :], uf[pl.ds(t, 1), :]
        dy_t = dyf[pl.ds(t, 1), :]
        at = (lane == t).astype(jnp.float32)                 # (1, chunk)
        g = _column(ct, lane, t) * dy_t + w                  # dL/dh_t
        dct = dct + jnp.sum(hall[t + 1] * dy_t, axis=1, keepdims=True) * at
        decay = jnp.exp(dt_t * a)
        d_exponent = g * hall[t] * decay                     # d(dt_t * A)
        da = da + d_exponent * dt_t
        d_dtu = jnp.sum(g * _column(bt, lane, t), axis=0, keepdims=True)
        dbt = dbt + jnp.sum(g * (dt_t * u_t), axis=1, keepdims=True) * at
        ddtf[pl.ds(t, 1), :] = jnp.sum(d_exponent * a, axis=0,
                                       keepdims=True) + d_dtu * u_t
        duf[pl.ds(t, 1), :] = d_dtu * dt_t
        return decay * g, da, dbt, dct

    zeros_t = jnp.zeros(bt.shape, jnp.float32)
    w, da, dbt, dct = _positions(
        chunk, reverse, (w_scr[:], jnp.zeros_like(a), zeros_t, zeros_t))
    w_scr[:] = w
    da_ref[0] += da
    dd_ref[0] += jnp.sum(dyf[:] * uf[:], axis=0, keepdims=True)
    dbt_ref[0, 0] = dbt
    dct_ref[0, 0] = dct
    du_ref[0] = (duf[:] + d_ref[:] * dyf[:]).astype(du_ref.dtype)
    ddt_ref[0] = ddtf[:].astype(ddt_ref.dtype)


def _sizes(s: int, c: int, chunk: int, block_c: int):
    """``(chunk, block_c, s_pad, c_pad)``: blocks never pass the (lane-
    aligned) extent, the extents are padded up to whole blocks."""
    c128 = pl.cdiv(c, _LANES) * _LANES
    block_c = min(block_c, c128)
    chunk = min(chunk, pl.cdiv(s, _LANES) * _LANES) if chunk >= _LANES \
        else chunk
    return (chunk, block_c, pl.cdiv(s, chunk) * chunk,
            pl.cdiv(c, block_c) * block_c)


def _padded(u, dt, A, B, C, D, s_pad: int, c_pad: int):
    """The kernels' operands: ``u, dt`` padded, ``A`` as ``(N, C)``, ``B, C``
    as ``[B, N, S]``, ``D`` as ``(1, C)``."""
    _, s, c = u.shape
    pad_sc = ((0, 0), (0, s_pad - s), (0, c_pad - c))
    pad_s = ((0, 0), (0, 0), (0, s_pad - s))
    return (jnp.pad(u, pad_sc), jnp.pad(dt, pad_sc),
            jnp.pad(A.astype(jnp.float32).T, ((0, 0), (0, c_pad - c))),
            jnp.pad(B.swapaxes(1, 2), pad_s), jnp.pad(C.swapaxes(1, 2), pad_s),
            jnp.pad(D.astype(jnp.float32)[None, :], ((0, 0), (0, c_pad - c))))


def _params(interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _fwd(u, dt, A, B, C, D, chunk: int, block_c: int, interpret: bool):
    """``(y, chunk-start states [B, S / chunk, N, C_pad], last state
    [B, N, C_pad])``."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, c = u.shape
    n = A.shape[1]
    chunk, block_c, s_pad, c_pad = _sizes(s, c, chunk, block_c)
    ops = _padded(u, dt, A, B, C, D, s_pad, c_pad)
    n_k = s_pad // chunk
    rows = pl.BlockSpec((1, chunk, block_c), lambda b, j, k: (b, k, j))
    cols = pl.BlockSpec((1, n, chunk), lambda b, j, k: (b, 0, k))
    state = pl.BlockSpec((n, block_c), lambda b, j, k: (0, j))
    skip = pl.BlockSpec((1, block_c), lambda b, j, k: (0, j))
    f32 = functools.partial(pltpu.VMEM, dtype=jnp.float32)
    call = pl.pallas_call(
        _fwd_kernel, grid=(bsz, c_pad // block_c, n_k),
        in_specs=[rows, rows, state, cols, cols, skip],
        out_specs=[rows,
                   pl.BlockSpec((1, 1, n, block_c),
                                lambda b, j, k: (b, k, 0, j)),
                   pl.BlockSpec((1, n, block_c), lambda b, j, k: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s_pad, c_pad), u.dtype),
                   jax.ShapeDtypeStruct((bsz, n_k, n, c_pad), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n, c_pad), jnp.float32)],
        scratch_shapes=[f32((n, block_c)), f32((chunk, block_c)),
                        f32((chunk, block_c)), f32((chunk, block_c))],
        name="selective_scan_fwd", **_params(interpret))
    with scopes.layer("selective_scan_fwd"):
        y, starts, last = call(*ops)
    return y[:, :s, :c], starts, last


def _bwd(u, dt, A, B, C, D, starts, dy, chunk: int, block_c: int,
         interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, c = u.shape
    n = A.shape[1]
    chunk, block_c, s_pad, c_pad = _sizes(s, c, chunk, block_c)
    ops = _padded(u, dt, A, B, C, D, s_pad, c_pad)
    dy = jnp.pad(dy, ((0, 0), (0, s_pad - s), (0, c_pad - c)))
    n_k, n_c = s_pad // chunk, c_pad // block_c

    def back(k):    # grid step k works on the k-th chunk from the end
        return n_k - 1 - k

    rows = pl.BlockSpec((1, chunk, block_c),
                        lambda b, j, k: (b, back(k), j))
    cols = pl.BlockSpec((1, n, chunk), lambda b, j, k: (b, 0, back(k)))
    state = pl.BlockSpec((n, block_c), lambda b, j, k: (0, j))
    skip = pl.BlockSpec((1, block_c), lambda b, j, k: (0, j))
    partial_cols = pl.BlockSpec((1, 1, n, chunk),
                                lambda b, j, k: (b, j, 0, back(k)))
    f32 = functools.partial(pltpu.VMEM, dtype=jnp.float32)
    call = pl.pallas_call(
        _bwd_kernel, grid=(bsz, n_c, n_k),
        in_specs=[rows, rows, state, cols, cols, skip, rows,
                  pl.BlockSpec((1, 1, n, block_c),
                               lambda b, j, k: (b, back(k), 0, j))],
        out_specs=[rows, rows, partial_cols, partial_cols,
                   pl.BlockSpec((1, n, block_c), lambda b, j, k: (b, 0, j)),
                   pl.BlockSpec((1, 1, block_c), lambda b, j, k: (b, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s_pad, c_pad), u.dtype),
                   jax.ShapeDtypeStruct((bsz, s_pad, c_pad), dt.dtype),
                   jax.ShapeDtypeStruct((bsz, n_c, n, s_pad), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n_c, n, s_pad), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, n, c_pad), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, 1, c_pad), jnp.float32)],
        scratch_shapes=[f32((n, block_c)), f32((chunk + 1, n, block_c))]
        + [f32((chunk, block_c))] * 5,
        name="selective_scan_bwd", **_params(interpret))
    with scopes.layer("selective_scan_bwd"):
        du, ddt, dbt, dct, da, dd = call(*ops, dy, starts)
    return (du[:, :s, :c], ddt[:, :s, :c],
            da.sum(0)[:, :c].T.astype(A.dtype),
            dbt.sum(1)[:, :, :s].swapaxes(1, 2).astype(B.dtype),
            dct.sum(1)[:, :, :s].swapaxes(1, 2).astype(C.dtype),
            dd.sum((0, 1))[:c].astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_core(u, dt, A, B, C, D, chunk: int, block_c: int, interpret: bool):
    y, _, last = _fwd(u, dt, A, B, C, D, chunk, block_c, interpret)
    return y, last


# What the forward kernel writes, by the names ``_scan_fwd`` gives them
# (``ops.SAVE_KERNEL_RESIDUALS`` keeps them: the backward reads ``starts``,
# the layer ``y`` and the counter ``last``).
RESIDUAL_NAMES = ("selective_scan_y", "selective_scan_starts",
                  "selective_scan_last")


def _scan_fwd(u, dt, A, B, C, D, chunk, block_c, interpret):
    y, starts, last = map(checkpoint_name, _fwd(
        u, dt, A, B, C, D, chunk, block_c, interpret), RESIDUAL_NAMES)
    return (y, last), (u, dt, A, B, C, D, starts)


def _scan_bwd(chunk, block_c, interpret, res, cts):
    u, dt, A, B, C, D, starts = res
    return _bwd(u, dt, A, B, C, D, starts, cts[0], chunk, block_c, interpret)


_scan_core.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(u, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
                   block_c: int = DEFAULT_BLOCK_C,
                   interpret: bool | None = None):
    """``(y [B, S, C], last [B, C, N])`` of the recurrence above: ``y``
    differentiable in all six operands, ``last`` the state after the last
    position in float32, for reading (the kernel writes it either way; it
    carries no gradient)."""
    from .flash_attention import _resolve
    y, last = _scan_core(u, dt, A, B, C, D, chunk, block_c,
                         _resolve(interpret))
    return y, jax.lax.stop_gradient(last[:, :, :u.shape[2]].swapaxes(1, 2))
