"""The routed expert layer's row movement (``parallel/moe.py``
``held_experts_ffn``) as a Pallas TPU kernel pair that touches only the rows
of the held slots.

The layer sorts its ``R = N k`` assignments by expert: the first ``n_live``
sorted slots belong to the held experts' groups, the rest (the dead slots) to
none, and ``tok [R]`` is the token of each slot. The pair, each the other's
transpose:

- :func:`gather_rows` ``(src [N, D], tok, n_live)`` -> ``rows [R, D]``,
  ``rows[s] = src[tok[s]]`` for ``s < n_live``; the rows past ``n_live`` are
  left unwritten. With ``weight [R]`` and ``other [R, D]`` a row is
  ``weight[s] * src[tok[s]]`` (rounded once, from float32), and it also
  gives ``dot[s] = <src[tok[s]], other[s]>`` in float32.
- :func:`scatter_rows` ``(rows [R, D], tok, n_live, n)`` -> ``out [N, D]``,
  ``out[t]`` = the sum over the slots ``s < n_live`` of token ``t`` of
  ``weight[s] * rows[s]``, in float32, rounded once; the rows past
  ``n_live`` are never read, so whatever they hold (``nan``) cannot enter a
  sum, and a token without a live slot reads zero.

:func:`dispatch` and :func:`combine` are the layer's two moves with their
gradients: going backward the dispatch is a scatter (weight 1) and the
combine a gather scaled by the weights, whose ``dot`` with the combine's own
rows is the weights' gradient.

Design. A bf16 row is half of a packed 32-bit word, and a DMA moves, and a
load of a packed array reads, whole tiles of 8 or 16 rows: no single row of
``src`` or ``rows`` can be moved on its own. So the TOKEN side lives in VMEM
in float32, a column chunk at a time (all of ``D`` where ``N x D`` float32
fits in 32 MiB): ``gather_rows`` fills its table from ``src[:, chunk]`` by
double-buffered DMAs of 512 rows once a chunk, and ``scatter_rows`` zeroes
its table, sums into it and writes it out the same way, once a chunk. There
a row is one dynamic-sublane load or store. The SLOT side streams through
the pipeline in blocks of 1024 slots: grid = (column chunks, slot blocks),
both ``arbitrary``. Every slot-side block index maps the blocks past
``n_live`` to the last live one (scalar-prefetched ``n_live``), so a dead
block is neither fetched nor written back, and the body skips it. Inside a
block a ``fori_loop`` walks the live slots, eight a step, its trip count
read from SMEM: the lowered kernel is the same whatever ``N``, ``k`` or the
held share. Each kernel is one ``jax.jit`` a shape and dtype, so the layers
and the backward share its lowering (the recomputation lowers the forward's
gather once more): nothing is traced or lowered a layer.

``interpret=None`` resolves as in ``ops/flash_attention.py``: compiled on a
TPU, interpreted elsewhere (the CPU tests). Compiled, ``D`` is a multiple of
128 and ``N`` of 512 (or small enough for one DMA).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _LANES, _resolve

_F32 = jnp.float32
_SLOTS = 1024                # slots a grid step: a 1-D int32 operand's tile
_ROWS = 512                  # token rows a DMA of the table
_TABLE_BYTES = 32 << 20      # the float32 token table, a column chunk
_VMEM_LIMIT = 64 << 20


def _sizes(n: int, r: int, d: int):
    """``(slot block, slot blocks, chunk width, token rows a DMA)``."""
    bs = min(_SLOTS, r)
    width = d
    if n * d * 4 > _TABLE_BYTES and d % _LANES == 0:
        width = max([w for w in range(_LANES, d, _LANES)
                     if d % w == 0 and n * w * 4 <= _TABLE_BYTES]
                    or [_LANES])
    return bs, pl.cdiv(r, bs), width, _ROWS if n % _ROWS == 0 else n


def _last_live(live_ref, bs: int):
    return jnp.maximum(pl.cdiv(live_ref[0], bs) - 1, 0)


def _slot_spec(bs: int, width: int | None = None):
    """A slot-side block: the blocks past the live slots map to the last live
    block, which is then neither fetched nor written back again."""
    from jax.experimental.pallas import tpu as pltpu
    if width is None:
        return pl.BlockSpec((bs,), lambda c, b, live: (
            jnp.minimum(b, _last_live(live, bs)),),
            memory_space=pltpu.SMEM)
    return pl.BlockSpec((bs, width), lambda c, b, live: (
        jnp.minimum(b, _last_live(live, bs)), c))


def _table_copy(hbm, stage, sem, i, slot, c, rows: int, width: int,
                into_table: bool):
    from jax.experimental.pallas import tpu as pltpu
    part = hbm.at[pl.ds(i * rows, rows), pl.ds(c * width, width)]
    src, dst = (part, stage.at[slot]) if into_table else (stage.at[slot], part)
    return pltpu.make_async_copy(src, dst, sem.at[slot])


def _load_table(src_hbm, table, stage, sem, c, rows: int, width: int):
    """``table = src[:, chunk c]`` in float32, by DMAs of ``rows`` rows, the
    next in flight while one is converted."""
    steps = table.shape[0] // rows
    copy = functools.partial(_table_copy, src_hbm, stage, sem, c=c,
                             rows=rows, width=width, into_table=True)
    copy(i=0, slot=0).start()

    def one(i, carry):
        slot = i % 2

        @pl.when(i + 1 < steps)
        def _():
            copy(i=i + 1, slot=1 - slot).start()

        copy(i=i, slot=slot).wait()
        table[pl.ds(pl.multiple_of(i * rows, rows), rows), :] = \
            stage[slot].astype(_F32)
        return carry

    jax.lax.fori_loop(0, steps, one, 0)


def _store_table(table, out_hbm, stage, sem, c, rows: int, width: int):
    """``out[:, chunk c] = table`` in ``out``'s dtype, by DMAs of ``rows``
    rows, two in flight."""
    steps = table.shape[0] // rows
    copy = functools.partial(_table_copy, out_hbm, stage, sem, c=c,
                             rows=rows, width=width, into_table=False)

    def one(i, carry):
        slot = i % 2

        @pl.when(i >= 2)
        def _():
            copy(i=i - 2, slot=slot).wait()

        stage[slot] = table[pl.ds(pl.multiple_of(i * rows, rows), rows),
                            :].astype(stage.dtype)
        copy(i=i, slot=slot).start()
        return carry

    jax.lax.fori_loop(0, steps, one, 0)
    for i in range(max(steps - 2, 0), steps):
        copy(i=i, slot=i % 2).wait()


def _walk(live, one):
    """``one(s)`` for ``s`` in ``[0, live)``: eight a step while eight are
    left (the slot side's sublane then known), then one a step."""
    def eight(g, carry):
        base = pl.multiple_of(g * 8, 8)
        for i in range(8):
            one(base + i)
        return carry

    def single(s, carry):
        one(s)
        return carry

    jax.lax.fori_loop(0, live // 8, eight, 0)
    jax.lax.fori_loop(live // 8 * 8, live, single, 0)


def _gather_kernel(live_ref, tok_ref, *refs, bs: int, rows: int, width: int,
                   weighted: bool):
    if weighted:
        (weight_ref, src_hbm, other_ref, out_ref, dot_ref, table, stage, sem,
         got, col) = refs
    else:
        src_hbm, out_ref, table, stage, sem, got, col = refs
    c, b = pl.program_id(0), pl.program_id(1)
    live = jnp.clip(live_ref[0] - b * bs, 0, bs)

    @pl.when((b == 0) & (live_ref[0] > 0))
    def _():
        _load_table(src_hbm, table, stage, sem, c, rows, width)

    @pl.when(live > 0)
    def _():
        def one(s):
            got[pl.ds(s, 1), :] = table[pl.ds(tok_ref[s], 1), :]
            if weighted:
                col[pl.ds(s, 1), :] = jnp.full((1, col.shape[1]),
                                               weight_ref[s], _F32)

        _walk(live, one)
        g = got[...]
        out_ref[...] = (g * col[:, :1] if weighted else g).astype(
            out_ref.dtype)
        if weighted:
            part = jnp.sum(g * other_ref[...].astype(_F32), axis=1,
                           keepdims=True)                  # [bs, 1]
            dot_ref[...] = jnp.broadcast_to(part, (bs, _LANES)).T[:1].reshape(
                dot_ref.shape)


def _scatter_kernel(live_ref, tok_ref, *refs, bs: int, rows: int, width: int,
                    weighted: bool):
    refs = list(refs)
    weight_ref = refs.pop(0) if weighted else None
    rows_ref, out_hbm, table, stage, sem, got = refs
    c, b = pl.program_id(0), pl.program_id(1)
    live = jnp.clip(live_ref[0] - b * bs, 0, bs)

    @pl.when(b == 0)
    def _():
        table[...] = jnp.zeros(table.shape, _F32)

    @pl.when(live > 0)
    def _():
        got[...] = rows_ref[...].astype(_F32)

        def one(s):
            row = got[pl.ds(s, 1), :]
            t = tok_ref[s]
            table[pl.ds(t, 1), :] += row * weight_ref[s] if weighted else row

        _walk(live, one)

    @pl.when(b == pl.num_programs(1) - 1)
    def _():
        _store_table(table, out_hbm, stage, sem, c, rows, width)


def _params(interpret: bool):
    from jax.experimental.pallas import tpu as pltpu
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _padded(t, length: int):
    return jnp.pad(t, (0, length - t.shape[0]))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(src, tok, n_live, weight=None, other=None, *,
                interpret: bool = False):
    """``(rows [R, D] in src's dtype, dot [R] float32 or None)``: see the
    module's docstring. ``tok [R]`` int32, ``n_live [1]`` int32; ``weight``
    and ``other`` come both or neither."""
    from jax.experimental.pallas import tpu as pltpu
    n, d = src.shape
    r = tok.shape[0]
    bs, nb, width, rows = _sizes(n, r, d)
    nc = d // width
    weighted = weight is not None
    if weighted != (other is not None):
        raise ValueError("gather_rows takes weight and other together")
    args = [_padded(tok, nb * bs)]
    in_specs = [_slot_spec(bs)]
    if weighted:
        args.append(_padded(weight.astype(_F32), nb * bs))
        in_specs.append(_slot_spec(bs))
    args.append(src)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out_specs = [_slot_spec(bs, width)]
    out_shape = [jax.ShapeDtypeStruct((r, d), src.dtype)]
    if weighted:
        args.append(other)
        in_specs.append(_slot_spec(bs, width))
        out_specs.append(pl.BlockSpec((1, 1, bs), lambda c, b, live: (
            c, 0, jnp.minimum(b, _last_live(live, bs)))))
        out_shape.append(jax.ShapeDtypeStruct((nc, 1, nb * bs), _F32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, bs=bs, rows=rows, width=width,
                          weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nc, nb), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, width), _F32),
                            pltpu.VMEM((2, rows, width), src.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((bs, width), _F32),
                            pltpu.VMEM((bs if weighted else 8, _LANES),
                                       _F32)]),
        out_shape=out_shape, name="moe_gather_rows", **_params(interpret),
    )(n_live, *args)
    if not weighted:
        return out[0], None
    return out[0], jnp.sum(out[1][:, 0, :r], axis=0)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def scatter_rows(rows, tok, n_live, weight=None, *, n: int,
                 interpret: bool = False):
    """``out [n, D]`` in ``rows``' dtype: see the module's docstring."""
    from jax.experimental.pallas import tpu as pltpu
    r, d = rows.shape
    bs, nb, width, token_rows = _sizes(n, r, d)
    nc = d // width
    weighted = weight is not None
    args = [_padded(tok, nb * bs)]
    in_specs = [_slot_spec(bs)]
    if weighted:
        args.append(_padded(weight.astype(_F32), nb * bs))
        in_specs.append(_slot_spec(bs))
    args.append(rows)
    in_specs.append(_slot_spec(bs, width))
    return pl.pallas_call(
        functools.partial(_scatter_kernel, bs=bs, rows=token_rows,
                          width=width, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nc, nb), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, width), _F32),
                            pltpu.VMEM((2, token_rows, width), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((bs, width), _F32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), rows.dtype),
        name="moe_scatter_rows", **_params(interpret),
    )(n_live, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch(h, tok, n_live, interpret: bool | None = None):
    """``rows [R, D]``: ``rows[s] = h[tok[s]]`` for the live slots, the rest
    unwritten. Its gradient sums a token's live slots (:func:`scatter_rows`)
    and never reads a dead one."""
    return gather_rows(h, tok, n_live, interpret=_resolve(interpret))[0]


def _dispatch_fwd(h, tok, n_live, interpret):
    return dispatch(h, tok, n_live, interpret), (tok, n_live, h.shape[0])


def _dispatch_bwd(interpret, saved, g):
    tok, n_live, n = saved
    return (scatter_rows(g, tok, n_live, n=int(n),
                         interpret=_resolve(interpret)), None, None)


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def combine(y, weight, tok, n_live, n: int, interpret: bool | None = None):
    """``out [n, D]``: the weighted sum of each token's live slots of ``y``
    (:func:`scatter_rows`). Its gradient is the gather of ``weight * g`` into
    the live slots and, for ``weight``, ``<g[tok[s]], y[s]>`` there and zero
    at a dead slot (a select: a dead row may hold ``nan``)."""
    return scatter_rows(y, tok, n_live, weight, n=n,
                        interpret=_resolve(interpret))


def _combine_fwd(y, weight, tok, n_live, n, interpret):
    return combine(y, weight, tok, n_live, n, interpret), (y, weight, tok,
                                                           n_live)


def _combine_bwd(n, interpret, saved, g):
    y, weight, tok, n_live = saved
    dy, dw = gather_rows(g, tok, n_live, weight, y,
                         interpret=_resolve(interpret))
    live = jnp.arange(tok.shape[0]) < n_live[0]
    return dy, jnp.where(live, dw, 0.0), None, None


combine.defvjp(_combine_fwd, _combine_bwd)
