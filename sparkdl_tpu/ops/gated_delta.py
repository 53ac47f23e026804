"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
form: a Pallas TPU kernel pair for the preparation of a chunk's tiles and
one for the recurrence across chunks.

A value head's state ``S`` is ``[Dk, Dv]``; ``g_t <= 0`` is the log of its
decay and ``beta_t`` the strength of the write, one number a position and
value head, float32::

    S~  = exp(g_t) S_{t-1}
    d_t = beta_t (v_t - S~^T k_t)
    S_t = S~ + k_t d_t^T
    o_t = S_t^T q_t * scale                        scale = 1 / sqrt(Dk)

``q, k: [B, S, Hk, Dk]``, ``v: [B, S, Hv, Dv]`` (each key head serves
``Hv / Hk`` value heads in a row), ``g, beta: [B, S, Hv]`` -> ``o: [B, S,
Hv, Dv]`` in ``v``'s dtype and the state after the last position ``[B, Hv,
Dk, Dv]``. With ``G_t`` the running sum of ``g`` inside a chunk of Q
positions and ``S0`` the state the chunk receives::

    A  = strictly_lower(diag(beta) K K^T * exp(G_t - G_r)),  T = (I + A)^-1
    U  = T diag(beta) V,        W = T diag(beta) (K * exp(G))
    V' = U - W S0
    O  = (Q * exp(G)) S0 + lower(Q K^T * exp(G_t - G_r)) V'
    S1 = exp(G_Q) S0 + (K * exp(G_Q - G))^T V'

Design:
- ``G`` (a running sum) is plain jax. ``A``, ``T``, ``U`` and ``W`` are a
  chunk's own, nothing crosses a chunk there: going forward one kernel
  (``gated_delta_fwd_prep``) makes them with every ``[Q, Q]`` tile in VMEM
  (in plain jax each factor of the inverse was a pass over 134 MB of float32
  tiles, 9 ms a layer and pass at the cell's shape, PERF.md section 5), and
  writes ``U``, ``W`` and ``T`` in the inputs' dtype. ``T`` is built by
  block substitution: the 16-wide diagonal blocks of ``I + A`` inverted by
  forward substitution in float32 on the VPU, then neighbouring blocks
  merged in pairs, ``T_21 = -T_22 A_21 T_11`` from 16 to ``Q``, each level
  two products at three bf16 passes (18 MXU passes a head and chunk at ``Q
  = 128``). It holds where the keys inside a chunk point the same way,
  where a product of powers ``(I - A)(I + A^2)(I + A^4)...`` does not (the
  powers grow as binomial sums and cancel: PERF.md section 6). Going
  backward a second kernel (``gated_delta_bwd_prep``) reads ``T`` as the
  first wrote it, never rebuilding the inverse, and takes
  ``dU`` and ``dW`` to ``dk``, ``dv``, ``dG`` and ``dbeta`` a chunk at a
  time around the inverse's cotangent ``-T^T dT T^T``, every ``[Q, Q]``
  tile in VMEM and every product at one bf16 pass. The running sum, its
  transpose and the rest that is plain jax stand under the scope
  ``gated_delta_prep``; :func:`_apply` and :func:`_tiles` are the two
  kernels' plain-jax reference.
- the recurrence is the kernel pair. grid = (batch, value-head blocks,
  chunks), the chunk axis innermost and ``arbitrary``: the ``[block_h * Dk,
  Dv]`` float32 state persists in VMEM scratch across a sequence's chunks.
  ``q``, ``k`` stay ``[B, S, Hk Dk]`` and ``u``, ``w``, ``o`` ``[B, S, Hv
  D]``; ``G`` arrives with the positions on the lanes (``[B, Hv / block_h,
  block_h, S]``) and a head's row becomes a column by one aligned transpose
  (``flash_attention._as_column``). Neither a state per position nor a
  ``[Q, Q]`` tile is an HBM operand of either kernel of the recurrence:
  ``Q K^T`` and its decay mask are made in VMEM, once a key head.
- products on the MXU in the inputs' dtype with float32 accumulation; ``G``,
  every exponent, the state and all sums in float32. Every exponent is of a
  difference ``<= 0``: the causal mask is laid on ``G_t - G_r`` before
  ``exp``.
- forward (``gated_delta_fwd``): writes ``o``, the state at each chunk's
  START (``[B, S / Q, Hv Dk, Dv]`` float32) and the state after the last
  position, and names them (``RESIDUAL_NAMES``).
- backward (``gated_delta_bwd``): walks the chunks in reverse with the
  state's cotangent in VMEM scratch, rebuilds a chunk's ``V'`` and tiles
  from its saved start, and writes ``dq``, ``dk`` (summed over a key head's
  value heads), ``dU``, ``dW`` and ``dG``.
- a ragged ``S`` is padded with ``g = 0, beta = 0, k = 0, v = 0`` (nothing is
  written and the state passes through unchanged, exactly).

``interpret=None`` resolves as in ``ops/flash_attention.py``: compiled on a
TPU, interpreted elsewhere (the CPU tests). Compiled, ``chunk`` is a multiple
of 128 and ``Dk``, ``Dv`` multiples of 128.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..utils import scopes
from .flash_attention import _LANES, _NT, _TN, _as_column, _resolve
from .ssd_scan import (_as_row, _causal, _decay_tile, _dot, _params,
                       chunk_cumsum)

DEFAULT_CHUNK = 128
DEFAULT_BLOCK_H = 8
PREP_BLOCK_H = 8       # value heads a grid step of the preparation
PREP_BWD_BLOCK_H = 8   # ... and of its backward (PERF.md section 5)
_BLOCK = 16            # widest diagonal block of T solved by substitution
_F32 = jnp.float32


def chunk_log_decay(g, chunk: int = DEFAULT_CHUNK):
    """``G [B, S, H]``: the running sum of ``g`` inside each chunk of
    ``chunk`` positions, float32. Its entry at a chunk's end is the log of
    what that chunk hands on of the state it received."""
    return chunk_cumsum(g, chunk)


def _by_chunk(t, chunk: int):
    """``[B, S, H, ...] -> [B, S / chunk, H, chunk, ...]``."""
    bsz, s = t.shape[:2]
    return jnp.moveaxis(t.reshape(bsz, s // chunk, chunk, *t.shape[2:]), 2, 3)


def _tiles(k, gamma, beta, chunk: int):
    """``A [B, S / Q, Hv, Q, Q]`` float32, the strictly lower part of
    ``diag(beta) K K^T * exp(G_t - G_r)``: the preparation kernels' plain-jax
    reference."""
    kc, gc = _by_chunk(k, chunk), _by_chunk(gamma, chunk)
    kk = jnp.repeat(jnp.einsum("bnhtd,bnhrd->bnhtr", kc, kc,
                               preferred_element_type=_F32),
                    gamma.shape[2] // k.shape[2], axis=2)
    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    decay = jnp.exp(jnp.where(below, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    return _by_chunk(beta, chunk)[..., :, None] * kk * decay


def _apply(t, k, v, gamma, beta, chunk: int):
    """``(U, W) = (T diag(beta) V, T diag(beta) (K * exp(G)))`` as ``[B, S,
    Hv, D]`` in ``v``'s dtype, ``t [B, S / Q, Hv, Q, Q]`` given: the
    preparation kernels' plain-jax reference."""
    bsz, s, hv, _ = v.shape
    gc, bc = _by_chunk(gamma, chunk), _by_chunk(beta, chunk)
    bv = (_by_chunk(v, chunk).astype(_F32) * bc[..., None]).astype(v.dtype)
    bk = (jnp.repeat(_by_chunk(k, chunk), hv // k.shape[2], axis=2).astype(
        _F32) * (bc * jnp.exp(gc))[..., None]).astype(v.dtype)

    def rows(x):                          # T x, back to [B, S, Hv, D]
        y = jnp.einsum("bnhtr,bnhrd->bnthd", t, x,
                       preferred_element_type=_F32).astype(v.dtype)
        return y.reshape(bsz, s, hv, x.shape[-1])

    return rows(bv), rows(bk)


def _dot3(a, b):
    """A float32 product at three bf16 passes (the MXU's ``HIGH``): inside a
    kernel a float32 product at the default precision is ONE bf16 pass, which
    the inverse's merges would not bear."""
    ah, bh = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    al = (a - ah.astype(_F32)).astype(jnp.bfloat16)
    bl = (b - bh.astype(_F32)).astype(jnp.bfloat16)
    return _dot(ah, bh) + _dot(ah, bl) + _dot(al, bh)


def _diagonal_inverse(a, w: int):
    """``(I + a_bb)^-1 - I`` of every ``w``-wide diagonal block ``a_bb`` of a
    strictly lower ``(Q, Q)`` float32 tile, as one block-diagonal tile:
    forward substitution in float32 on the VPU, no product on the MXU. The
    blocks' rows lie folded onto one ``(w, Q)`` slab, block ``b``'s on its
    own lanes, so that a step of the substitution is one multiply-add over
    the slab for every block at once. ``w``: a power of two that divides
    ``Q`` and the lanes of a vreg."""
    q = a.shape[0]
    lanes, shift = min(q, _LANES), w.bit_length() - 1
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >> shift) == (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) >> shift)
    p = jnp.sum(jnp.where(diagonal, a, 0.0).reshape(q // w, w, q), axis=0)
    # every step's coefficients by one lane gather: c[k w + i, l] =
    # a_bb[i, k] on every lane l of block b
    at = (jax.lax.broadcasted_iota(jnp.int32, (w * w, lanes), 1) & -w) + (
        jax.lax.broadcasted_iota(jnp.int32, (w * w, lanes), 0) >> shift)
    stacked = jnp.concatenate([p] * w, axis=0)
    c = jnp.concatenate([
        jnp.take_along_axis(stacked[:, j:j + lanes], at, axis=1,
                            mode="promise_in_bounds")
        for j in range(0, q, lanes)], axis=1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (w, q), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (w, q), 1) & (w - 1)
           ).astype(_F32)
    x = eye
    for k in range(w - 1):
        x = x - c[k * w:(k + 1) * w] * x[k:k + 1]   # row k is final
    return jnp.where(diagonal, jnp.concatenate([x - eye] * (q // w), axis=0),
                     0.0)


def _merge(n, a, w: int):
    """``n = T - I``, ``T`` the inverse of ``I + a``'s ``w``-wide diagonal
    blocks, with neighbouring blocks merged in pairs: ``T_21 = -T_22 a_21
    T_11`` for all pairs by two float32 products over the whole tile and a
    mask (a ragged last block waits for a later level); ``w`` a power of two.
    The products take ``n``, not ``T``: ``T_22 a_21 T_11 = P + P n_11`` with
    ``P = a_21 + n_22 a_21``, the identity's part added exactly, so that
    their rounding scales with ``n``."""
    q = a.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    pair = w.bit_length()                           # log2 of a pair's width
    lower_left = (((rows & w) != 0) & ((cols & w) == 0)
                  & ((rows >> pair) == (cols >> pair)))
    p = jnp.where(lower_left, a, 0.0)
    p = p + _dot3(n, p)
    return n - jnp.where(lower_left, p + _dot3(p, n), 0.0)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for a strictly lower-triangular ``(Q, Q)`` float32 tile
    in VMEM: the diagonal blocks, ``_BLOCK`` wide or the widest power of two
    that divides ``Q``, by substitution, then merged level by level up to
    ``Q`` (three levels, six three-pass products, at ``Q = 128``)."""
    q = a.shape[0]
    w = math.gcd(q, _BLOCK)
    n = _diagonal_inverse(a, w)
    while w < q:
        n, w = _merge(n, a, w), 2 * w
    return n + (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)).astype(_F32)


def _prep_kernel(k_ref, v_ref, row_ref, u_ref, w_ref, t_ref, *, hb: int,
                 rep: int, dk: int, dv: int):
    """One chunk of ``hb`` value heads: ``A``, ``T``, ``U``, ``W``.
    ``row_ref``: ``G`` in rows ``0 .. hb - 1``, ``beta`` in the next ``hb``."""
    dtype, q = k_ref.dtype, k_ref.shape[1]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    below = t_idx > jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    for jk in range(hb // rep):
        kh = k_ref[0, :, jk * dk:(jk + 1) * dk]
        kf = kh.astype(_F32)
        kk = _dot(kh, kh, _NT)                                  # (Q, Q): t, r
        for i in range(jk * rep, (jk + 1) * rep):
            g_row, g_col, _ = _head(row_ref, i)
            b_col = _as_column(row_ref[0, 0, hb + i:hb + i + 1, :])[:, :1]
            decay = jnp.exp(jnp.where(below, g_col - g_row, -jnp.inf))
            t = _unit_lower_inverse(b_col * kk * decay).astype(dtype)
            t_ref[0, 0, i] = t
            bv = (v_ref[0, :, i * dv:(i + 1) * dv].astype(_F32)
                  * b_col).astype(dtype)
            bk = (kf * (b_col * jnp.exp(g_col))).astype(dtype)
            u_ref[0, :, i * dv:(i + 1) * dv] = _dot(t, bv).astype(dtype)
            w_ref[0, :, i * dk:(i + 1) * dk] = _dot(t, bk).astype(dtype)


def _prep_call(k, v, gamma, beta, chunk: int, interpret: bool):
    """``(U [B, S, Hv, Dv], W [B, S, Hv, Dk], T [B, S / Q, Hv, Q, Q])`` in
    ``v``'s dtype; ``S`` a multiple of ``chunk``."""
    bsz, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    hb = _block_h(hv, rep, PREP_BLOCK_H)
    call = pl.pallas_call(
        functools.partial(_prep_kernel, hb=hb, rep=rep, dk=dk, dv=dv),
        grid=(bsz, hv // hb, s // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, hb // rep * dk),
                         lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, chunk, hb * dv), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, 1, 2 * hb, chunk),
                         lambda b, j, c: (b, j, 0, c))],
        out_specs=[
            pl.BlockSpec((1, chunk, hb * dv), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, chunk, hb * dk), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, 1, hb, chunk, chunk),
                         lambda b, j, c: (b, c, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, s, hv * dk), v.dtype),
                   jax.ShapeDtypeStruct((bsz, s // chunk, hv, chunk, chunk),
                                        v.dtype)],
        name="gated_delta_fwd_prep", **_params(interpret))
    with scopes.layer("gated_delta_fwd_prep"):
        u, w, t = call(_flat(k), _flat(v), _prep_rows(gamma, beta, hb))
    return u.reshape(bsz, s, hv, dv), w.reshape(bsz, s, hv, dk), t


def _prep_rows(gamma, beta, hb: int):
    """``[B, Hv / hb, 2 hb, S]``: a block's ``G`` rows, then its ``beta``."""
    return jnp.concatenate([_gamma_rows(t, hb) for t in (gamma, beta)],
                           axis=2)


def _prep_bwd_kernel(k_ref, v_ref, row_ref, t_ref, du_ref, dw_ref, dk_ref,
                     dv_ref, drow_ref, *, hb: int, rep: int, dk: int,
                     dv: int):
    """The transpose of :func:`_prep_kernel` for one chunk of ``hb`` value
    heads, from the ``T`` it wrote. ``drow_ref`` takes ``dG`` in rows ``0 ..
    hb - 1`` and ``dbeta`` in the next ``hb``, as ``row_ref`` holds ``G`` and
    ``beta``. With ``Y = dU V^T`` and ``Z = dW K^T``, ``dT = Y diag(beta) +
    Z diag(beta e^G)``, and the row sums of ``(T^T dU) * V`` and ``(T^T dW)
    * K`` are the column sums of ``T * Y`` and ``T * Z``: every sum a
    position comes out as a row but one. A head's ``dkk`` (the cotangent of
    ``K K^T``) is summed over its key head's value heads and taken to ``dk``
    by one product."""
    dtype, q = k_ref.dtype, k_ref.shape[1]
    below = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, (2 * hb, 1), 0)
    drow = jnp.zeros((2 * hb, q), _F32)

    def colsum(t):
        return jnp.sum(t, axis=0, keepdims=True)

    for jk in range(hb // rep):
        of_key = slice(jk * dk, (jk + 1) * dk)
        kh = k_ref[0, :, of_key]
        kk = _dot(kh, kh, _NT)                                  # (Q, Q): t, r
        dkk, dkey = jnp.zeros((q, q), _F32), jnp.zeros((q, dk), _F32)
        for i in range(jk * rep, (jk + 1) * rep):
            rows, cols = slice(i * dk, (i + 1) * dk), slice(i * dv,
                                                            (i + 1) * dv)
            g_row, g_col, _ = _head(row_ref, i)
            b_row = row_ref[0, 0, hb + i:hb + i + 1, :]
            b_col = _as_column(b_row)[:, :1]
            e_row = jnp.exp(g_row)
            decay = jnp.exp(jnp.where(below, g_col - g_row, -jnp.inf))
            t, du, dw = t_ref[0, 0, i], du_ref[0, :, cols], dw_ref[0, :, rows]
            y = _dot(du, v_ref[0, :, cols], _NT)                # dU V^T
            z = _dot(dw, kh, _NT)                               # dW K^T
            ndt = (y * -b_row + z * -(e_row * b_row)).astype(dtype)
            # dA = -T^T dT T^T, strictly lower: D is 0 everywhere else
            p = _dot(_dot(t, ndt, _TN).astype(dtype), t, _NT) * decay
            pk = p * kk
            dbv, dbk = _dot(t, du, _TN), _dot(t, dw, _TN)       # T^T dU, dW
            dv_ref[0, :, cols] = (b_col * dbv).astype(dv_ref.dtype)
            dkey = dkey + (b_col * jnp.exp(g_col)) * dbk
            dkk = dkk + b_col * p
            tf = t.astype(_F32)
            r_kp = e_row * colsum(tf * z) + colsum(pk.T)
            dg_row = b_row * r_kp - colsum(b_col * pk)
            drow = jnp.where(sublane == i, dg_row, drow)
            drow = jnp.where(sublane == hb + i, colsum(tf * y) + r_kp, drow)
        dkey = dkey + _dot((dkk + dkk.T).astype(dtype), kh)
        dk_ref[0, :, of_key] = dkey.astype(dk_ref.dtype)
    drow_ref[0, 0] = drow


def _prep_bwd_call(k, v, gamma, beta, t, du, dw, chunk: int,
                   interpret: bool):
    """``(dk, dv, dG, dbeta)`` of ``(U, W)``'s cotangents ``(du, dw)``, in
    ``k``'s, ``v``'s and float32; ``t`` as :func:`_prep_call` wrote it."""
    bsz, s, hk, dk = k.shape
    hv, dv = v.shape[2:]
    rep = hv // hk
    hb = _block_h(hv, rep, PREP_BWD_BLOCK_H)
    keys = pl.BlockSpec((1, chunk, hb // rep * dk), lambda b, j, c: (b, c, j))
    values = pl.BlockSpec((1, chunk, hb * dv), lambda b, j, c: (b, c, j))
    head_rows = pl.BlockSpec((1, 1, 2 * hb, chunk),
                             lambda b, j, c: (b, j, 0, c))
    call = pl.pallas_call(
        functools.partial(_prep_bwd_kernel, hb=hb, rep=rep, dk=dk, dv=dv),
        grid=(bsz, hv // hb, s // chunk),
        in_specs=[
            keys, values, head_rows,
            pl.BlockSpec((1, 1, hb, chunk, chunk),
                         lambda b, j, c: (b, c, j, 0, 0)),
            values,
            pl.BlockSpec((1, chunk, hb * dk), lambda b, j, c: (b, c, j))],
        out_specs=[keys, values, head_rows],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hk * dk), k.dtype),
                   jax.ShapeDtypeStruct((bsz, s, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, hv // hb, 2 * hb, s), _F32)],
        name="gated_delta_bwd_prep", **_params(interpret))
    with scopes.layer("gated_delta_bwd_prep"):
        dkey, dval, drow = call(_flat(k), _flat(v),
                                _prep_rows(gamma, beta, hb), t, _flat(du),
                                _flat(dw))
    drow = drow.reshape(bsz, hv // hb, 2, hb, s).transpose(2, 0, 4, 1, 3)
    return (dkey.reshape(k.shape), dval.reshape(v.shape),
            *drow.reshape(2, bsz, s, hv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _prep(k, v, gamma, beta, chunk: int, interpret: bool):
    """``(U, W)``: the kernel ``gated_delta_fwd_prep`` going forward and
    ``gated_delta_bwd_prep`` going backward."""
    return _prep_call(k, v, gamma, beta, chunk, interpret)[:2]


def _prep_fwd(k, v, gamma, beta, chunk, interpret):
    u, w, t = _prep_call(k, v, gamma, beta, chunk, interpret)
    return (u, w), (k, v, gamma, beta, t)


def _prep_bwd(chunk, interpret, res, cts):
    return _prep_bwd_call(*res, *cts, chunk, interpret)


_prep.defvjp(_prep_fwd, _prep_bwd)


def _down(value, n: int):
    """A ``(1, 1)`` value laid down an ``(n, 1)`` column by a select: Mosaic
    refuses its broadcast in both directions at once."""
    return jnp.where(jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) >= 0,
                     value, 0.0)


def _head(row_ref, i: int):
    """Value head ``i`` of the block: ``G`` as a ``(1, Q)`` row, a ``(Q, 1)``
    column, and at the chunk's end ``(1, 1)``."""
    g_row = row_ref[0, 0, i:i + 1, :]
    g_col = _as_column(g_row)[:, :1]
    q = g_col.shape[0]
    return g_row, g_col, g_col[q - 1:q, :]


def _fwd_kernel(q_ref, k_ref, u_ref, w_ref, row_ref, o_ref, start_ref,
                last_ref, s_scr, *, hb: int, rep: int, dk: int, dv: int,
                scale: float):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    start_ref[0, 0] = s_scr[:]
    dtype, causal = q_ref.dtype, _causal(q_ref.shape[1])
    for jk in range(hb // rep):
        of_key = slice(jk * dk, (jk + 1) * dk)
        qh, kh = q_ref[0, :, of_key], k_ref[0, :, of_key]       # (Q, Dk)
        qk = _dot(qh, kh, _NT)                                  # (Q, Q): t, r
        for i in range(jk * rep, (jk + 1) * rep):
            g_row, g_col, g_end = _head(row_ref, i)
            rows = slice(i * dk, (i + 1) * dk)
            s0 = s_scr[rows, :]                                 # (Dk, Dv)
            sb = s0.astype(dtype)
            vp = (u_ref[0, :, i * dv:(i + 1) * dv].astype(_F32)
                  - _dot(w_ref[0, :, rows], sb)).astype(dtype)  # (Q, Dv)
            p = (qk * _decay_tile(g_col, g_row, causal)).astype(dtype)
            o = jnp.exp(g_col) * _dot(qh, sb) + _dot(p, vp)
            o_ref[0, :, i * dv:(i + 1) * dv] = (scale * o).astype(dtype)
            kd = (kh.astype(_F32) * jnp.exp(g_end - g_col)).astype(dtype)
            kept = _down(jnp.exp(g_end), dk)
            s_scr[rows, :] = kept * s0 + _dot(kd, vp, _TN)

    @pl.when(c == pl.num_programs(2) - 1)
    def _last():
        last_ref[0] = s_scr[:]


def _bwd_kernel(q_ref, k_ref, u_ref, w_ref, row_ref, do_ref, start_ref,
                dq_ref, dk_ref, du_ref, dw_ref, drow_ref, ds_scr, *, hb: int,
                rep: int, dk: int, dv: int, scale: float):
    """One chunk, the chunks walked last to first. ``ds_scr`` carries the
    cotangent of the state this chunk hands on; ``start_ref`` is the state it
    received."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    dtype, q = q_ref.dtype, q_ref.shape[1]
    causal = _causal(q)
    at_end = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    sublane = jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
    drow = jnp.zeros((hb, q), _F32)

    def rowsum(t):
        return jnp.sum(t, axis=1, keepdims=True)

    for jk in range(hb // rep):
        of_key = slice(jk * dk, (jk + 1) * dk)
        qh, kh = q_ref[0, :, of_key], k_ref[0, :, of_key]
        qf, kf = qh.astype(_F32), kh.astype(_F32)
        qk = _dot(qh, kh, _NT)
        dq, dkey = jnp.zeros((q, dk), _F32), jnp.zeros((q, dk), _F32)
        for i in range(jk * rep, (jk + 1) * rep):
            g_row, g_col, g_end = _head(row_ref, i)
            rows, cols = slice(i * dk, (i + 1) * dk), slice(i * dv,
                                                            (i + 1) * dv)
            e, f, e_end = jnp.exp(g_col), jnp.exp(g_end - g_col), \
                jnp.exp(g_end)
            decay = _decay_tile(g_col, g_row, causal)
            s0, ds1 = start_ref[0, 0, rows, :], ds_scr[rows, :]
            sb, ds1b = s0.astype(dtype), ds1.astype(dtype)
            wh = w_ref[0, :, rows]
            vp = (u_ref[0, :, cols].astype(_F32)
                  - _dot(wh, sb)).astype(dtype)
            p = qk * decay
            do = (scale * do_ref[0, :, cols].astype(_F32)).astype(dtype)
            qe, kdf = qf * e, kf * f
            kd = kdf.astype(dtype)
            dqe = _dot(do, sb, _NT)                             # (Q, Dk)
            dp = _dot(do, vp, _NT)                              # (Q, Q): t, r
            dvp = (_dot(p.astype(dtype), do, _TN)
                   + _dot(kd, ds1b)).astype(dtype)              # (Q, Dv)
            dkd = _dot(vp, ds1b, _NT)                           # (Q, Dk)
            du_ref[0, :, cols] = dvp
            dw_ref[0, :, rows] = (-_dot(dvp, sb, _NT)).astype(dtype)
            ds_scr[rows, :] = _down(e_end, dk) * ds1 \
                + _dot(qe.astype(dtype), do, _TN) - _dot(wh, dvp, _TN)
            dqk = (dp * decay).astype(dtype)
            dq = dq + _dot(dqk, kh) + dqe * e
            dkey = dkey + _dot(dqk, qh, _TN) + dkd * f
            # d G: the decay tile's rows and columns, exp(G_t) before the
            # state's read-out, exp(G_Q - G_r) before its update, exp(G_Q)
            m = dp * p
            sent = rowsum(dkd * kdf)                            # (Q, 1)
            at_q = jnp.sum(sent, axis=0, keepdims=True) \
                + e_end * jnp.sum(ds1 * s0, keepdims=True)
            dg_col = rowsum(m) + rowsum(dqe * qe) - sent \
                + jnp.where(at_end, at_q, 0.0)
            dg_row = _as_row(dg_col) - jnp.sum(m, axis=0, keepdims=True)
            drow = jnp.where(sublane == i, dg_row, drow)
        dq_ref[0, :, of_key] = dq.astype(dtype)
        dk_ref[0, :, of_key] = dkey.astype(dtype)
    drow_ref[0, 0] = drow


def _block_h(hv: int, rep: int, block_h: int) -> int:
    """Value heads a grid step: a divisor of ``hv`` that holds whole key
    heads' groups."""
    return next(n for n in range(min(max(block_h, rep), hv), 0, -1)
                if hv % n == 0 and n % rep == 0)


def _flat(t):
    return t.reshape(*t.shape[:2], -1)


def _gamma_rows(gamma, hb: int):
    bsz, s, hv = gamma.shape
    return gamma.reshape(bsz, s, hv // hb, hb).transpose(0, 2, 3, 1)


def _fwd(q, k, u, w, gamma, chunk: int, block_h: int, interpret: bool):
    """``(o [B, S, Hv, Dv], chunk-start states [B, S / chunk, Hv Dk, Dv],
    last state [B, Hv Dk, Dv])``; ``S`` a multiple of ``chunk``."""
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, hk, dk = q.shape
    hv, dv = u.shape[2:]
    rep = hv // hk
    hb = _block_h(hv, rep, block_h)
    n_k, wide_k, wide_v, wide_w = s // chunk, hb // rep * dk, hb * dv, hb * dk
    keys = pl.BlockSpec((1, chunk, wide_k), lambda b, j, c: (b, c, j))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, rep=rep, dk=dk, dv=dv,
                          scale=1.0 / math.sqrt(dk)),
        grid=(bsz, hv // hb, n_k),
        in_specs=[
            keys, keys,
            pl.BlockSpec((1, chunk, wide_v), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, chunk, wide_w), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, 1, hb, chunk), lambda b, j, c: (b, j, 0, c))],
        out_specs=[
            pl.BlockSpec((1, chunk, wide_v), lambda b, j, c: (b, c, j)),
            pl.BlockSpec((1, 1, wide_w, dv), lambda b, j, c: (b, c, j, 0)),
            pl.BlockSpec((1, wide_w, dv), lambda b, j, c: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hv * dv), u.dtype),
                   jax.ShapeDtypeStruct((bsz, n_k, hv * dk, dv), _F32),
                   jax.ShapeDtypeStruct((bsz, hv * dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((wide_w, dv), _F32)],
        name="gated_delta_fwd", **_params(interpret))
    with scopes.layer("gated_delta_fwd"):
        o, starts, last = call(_flat(q), _flat(k), _flat(u), _flat(w),
                               _gamma_rows(gamma, hb))
    return o.reshape(bsz, s, hv, dv), starts, last


def _bwd(q, k, u, w, gamma, starts, do, chunk: int, block_h: int,
         interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    bsz, s, hk, dk = q.shape
    hv, dv = u.shape[2:]
    rep = hv // hk
    hb = _block_h(hv, rep, block_h)
    n_k, n_h = s // chunk, hv // hb
    wide_k, wide_v, wide_w = hb // rep * dk, hb * dv, hb * dk

    def back(c):    # grid step c works on the c-th chunk from the end
        return n_k - 1 - c

    keys = pl.BlockSpec((1, chunk, wide_k), lambda b, j, c: (b, back(c), j))
    values = pl.BlockSpec((1, chunk, wide_v), lambda b, j, c: (b, back(c), j))
    writes = pl.BlockSpec((1, chunk, wide_w), lambda b, j, c: (b, back(c), j))
    head_rows = pl.BlockSpec((1, 1, hb, chunk),
                             lambda b, j, c: (b, j, 0, back(c)))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, rep=rep, dk=dk, dv=dv,
                          scale=1.0 / math.sqrt(dk)),
        grid=(bsz, n_h, n_k),
        in_specs=[keys, keys, values, writes, head_rows, values,
                  pl.BlockSpec((1, 1, wide_w, dv),
                               lambda b, j, c: (b, back(c), j, 0))],
        out_specs=[keys, keys, values, writes, head_rows],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, hk * dk), q.dtype),
                   jax.ShapeDtypeStruct((bsz, s, hk * dk), k.dtype),
                   jax.ShapeDtypeStruct((bsz, s, hv * dv), u.dtype),
                   jax.ShapeDtypeStruct((bsz, s, hv * dk), w.dtype),
                   jax.ShapeDtypeStruct((bsz, n_h, hb, s), _F32)],
        scratch_shapes=[pltpu.VMEM((wide_w, dv), _F32)],
        name="gated_delta_bwd", **_params(interpret))
    with scopes.layer("gated_delta_bwd"):
        dq, dkey, du, dw, drow = call(
            _flat(q), _flat(k), _flat(u), _flat(w), _gamma_rows(gamma, hb),
            _flat(do), starts)
    return (dq.reshape(q.shape), dkey.reshape(k.shape), du.reshape(u.shape),
            dw.reshape(w.shape),
            drow.transpose(0, 3, 1, 2).reshape(bsz, s, hv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _core(q, k, u, w, gamma, chunk: int, block_h: int, interpret: bool):
    o, _, last = _fwd(q, k, u, w, gamma, chunk, block_h, interpret)
    return o, last


# What the forward kernel writes, by the names ``_core_fwd`` gives them
# (``ops.SAVE_KERNEL_RESIDUALS`` keeps them: the backward reads ``starts``,
# the layer ``o`` and the counter ``last``).
RESIDUAL_NAMES = ("gated_delta_o", "gated_delta_starts", "gated_delta_last")


def _core_fwd(q, k, u, w, gamma, chunk, block_h, interpret):
    o, starts, last = map(checkpoint_name, _fwd(
        q, k, u, w, gamma, chunk, block_h, interpret), RESIDUAL_NAMES)
    return (o, last), (q, k, u, w, gamma, starts)


def _core_bwd(chunk, block_h, interpret, res, cts):
    return _bwd(*res, cts[0], chunk, block_h, interpret)


_core.defvjp(_core_fwd, _core_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                     block_h: int = DEFAULT_BLOCK_H,
                     interpret: bool | None = None):
    """``(o [B, S, Hv, Dv], last [B, Hv, Dk, Dv])`` of the recurrence above:
    ``o`` differentiable in all five operands, ``last`` the state after the
    last position in float32, for reading (the kernel writes it either way; it
    carries no gradient). ``chunk``: the positions a grid step takes and a
    saved state stands for (the mathematics is the same at any; 128 fills the
    MXU's tiles, PERF.md section 5); ``block_h``: value heads a grid step."""
    bsz, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if hv % hk or k.shape != q.shape:
        raise ValueError(f"{hv} value heads over keys {k.shape}, queries "
                         f"{q.shape}")
    if chunk >= _LANES:     # a chunk never passes the (lane-aligned) length
        chunk = min(chunk, pl.cdiv(s, _LANES) * _LANES)
    s_pad = pl.cdiv(s, chunk) * chunk

    def pad(t):
        return jnp.pad(t, ((0, 0), (0, s_pad - s)) + ((0, 0),) * (t.ndim - 2))

    q, k, v, g, beta = map(pad, (q, k, v, g.astype(_F32), beta.astype(_F32)))
    interpret = _resolve(interpret)
    with scopes.layer("gated_delta_prep"):
        gamma = chunk_log_decay(g, chunk)
        u, w = _prep(k, v, gamma, beta, chunk, interpret)
    o, last = _core(q, k, u, w, gamma, chunk, block_h, interpret)
    return o[:, :s], jax.lax.stop_gradient(last.reshape(bsz, hv, dk, dv))
