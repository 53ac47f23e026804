"""Pallas kernel tests — flash attention vs the dense reference.

Runs through the Pallas interpreter on the CPU test mesh (conftest), exactly
the semantics the compiled TPU kernel executes.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import flash_attention
from sparkdl_tpu.parallel.ring_attention import dense_attention
from sparkdl_tpu.utils.platform import is_tpu_backend

# the module itself: ``sparkdl_tpu.ops.flash_attention`` names the function
_fa = sys.modules["sparkdl_tpu.ops.flash_attention"]

# Compiled-on-TPU runs (SPARKDL_TEST_PLATFORM=tpu) compare against a dense
# reference that XLA computes with the MXU's default f32 precision (bf16
# passes), so elementwise agreement is ~1e-4, not 1e-6 — round-5 on-chip
# measurement: max|Δ| 2.8e-4 on the forward. Interpret mode stays tight.
FWD_ATOL = 2e-3 if is_tpu_backend() else 2e-5
BWD_ATOL = 5e-3 if is_tpu_backend() else 5e-4
MODEL_ATOL = 5e-3 if is_tpu_backend() else 1e-3


def _rand_qkv(b=2, h=3, s=128, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_dense(causal):
    q, k, v = _rand_qkv()
    o = flash_attention(q, k, v, causal, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=FWD_ATOL)


@pytest.mark.parametrize("s", [100, 96, 130, 64])
def test_ragged_sequence_lengths(s):
    q, k, v = _rand_qkv(s=s, seed=s)
    o = flash_attention(q, k, v, True, block_q=64, block_k=32)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=FWD_ATOL)


@pytest.mark.parametrize("s", [4, 37, 100, 130])
def test_ragged_with_default_blocks(s):
    """Arbitrary sequence lengths through the DEFAULT (128) blocks — the
    shapes the generation-UDF prefill hands the kernel on TPU. Blocks stay
    lane-aligned; S pads up inside _fwd."""
    q, k, v = _rand_qkv(s=s, seed=s)
    o = flash_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(dense_attention(q, k, v, True)),
                               atol=FWD_ATOL)
    lens = np.minimum([s, max(1, s // 2)], s)
    kv_mask = jnp.asarray((np.arange(s)[None, :]
                           < np.asarray(lens)[:, None]).astype(np.float32))
    o2 = flash_attention(q, k, v, False, kv_mask=kv_mask)
    np.testing.assert_allclose(
        np.asarray(o2), np.asarray(_masked_dense(q, k, v, kv_mask, False)),
        atol=FWD_ATOL)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    q, k, v = _rand_qkv(s=96, d=16)

    def lf(a, b, c):
        return (flash_attention(a, b, c, causal, block_q=32, block_k=32) ** 2).sum()

    def lr(a, b, c):
        return (dense_attention(a, b, c, causal) ** 2).sum()

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=BWD_ATOL)


def _len_mask(s, lens):
    return jnp.asarray((np.arange(s)[None, :] < np.asarray(lens)[:, None])
                       .astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kvmask"])
@pytest.mark.parametrize("s,blocks", [
    (128, (32, 32)), (128, (64, 32)), (128, (32, 64)),   # block multiples
    (37, (None, None)), (200, (None, None)),             # ragged, defaults
    (100, (64, 32)),                                     # ragged, explicit
], ids=lambda x: str(x).replace(" ", ""))
def test_backward_kernels_match_dense(causal, masked, s, blocks):
    """dq, dk, dv of the Pallas backward pair, through the public
    ``flash_attention``, against the dense reference: every mask the
    forward takes (causal, a padded tail, S padded to the block), equal and
    unequal blocks. ``kv_mask`` itself gets a zero cotangent."""
    q, k, v = _rand_qkv(s=s, d=16, seed=s)
    kv_mask = _len_mask(s, [s, max(1, (2 * s) // 5)]) if masked else None
    bq, bk = blocks
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)

    def lf(a, b, c, m):
        return (flash_attention(a, b, c, causal, kv_mask=m, block_q=bq,
                                block_k=bk) * w).sum()

    def lr(a, b, c):
        if kv_mask is None:
            return (dense_attention(a, b, c, causal) * w).sum()
        return (_masked_dense(a, b, c, kv_mask, causal) * w).sum()

    argnums = (0, 1, 2, 3) if masked else (0, 1, 2)
    gf = jax.grad(lf, argnums=argnums)(q, k, v, kv_mask)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=BWD_ATOL, err_msg=name)
    if masked:
        np.testing.assert_array_equal(np.asarray(gf[3]), 0.0)


def test_backward_bf16_inputs():
    """bf16 in, bf16 gradients out; ``p`` and ``ds`` enter their second
    products rounded to bf16 (the dense path's own rounding of ``p``), so
    against the float32 dense gradient of the same bf16 values the gap is
    bf16's: 2**-8 relative on an O(0.3) gradient, with room."""
    q, k, v = [x.astype(jnp.bfloat16) for x in _rand_qkv(s=256, d=64)]

    def lf(a, b, c):
        return (flash_attention(a, b, c, True, block_q=128, block_k=128)
                .astype(jnp.float32) ** 2).sum()

    def lr(a, b, c):
        return (dense_attention(a, b, c, True) ** 2).sum()

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):   # the chip's default
        gr = jax.grad(lr, argnums=(0, 1, 2))(       # rounds p to bf16 too
            *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b in zip(("dq", "dk", "dv"), gf, gr):
        assert a.dtype == jnp.bfloat16, name
        a, b = np.asarray(a, np.float32), np.asarray(b)
        np.testing.assert_allclose(a, b, atol=2e-2 if is_tpu_backend()
                                   else 1e-2, err_msg=name)
        rel = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert rel < 1e-2, (name, rel)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_fully_masked_rows(causal):
    """Rows with no attendable key (batch 0: every key masked; batch 1
    under ``causal``: the first rows see only masked keys) output zeros, so
    their ``dq`` is zero, they add nothing to ``dk``/``dv`` — and their
    saved ``lse`` of NEG_INF puts no NaN or inf anywhere."""
    s = 64
    q, k, v = _rand_qkv(s=s, d=16, seed=13)
    kv_mask = jnp.asarray(np.stack([np.zeros(s), np.r_[np.zeros(20),
                                                       np.ones(s - 20)]])
                          .astype(np.float32))

    def lf(a, b, c):
        return (flash_attention(a, b, c, causal, kv_mask=kv_mask,
                                block_q=32, block_k=16) ** 2).sum()

    dq, dk, dv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    for g in (dq, dk, dv):
        np.testing.assert_array_equal(np.asarray(g[0]), 0.0)
    if causal:
        np.testing.assert_array_equal(np.asarray(dq[1, :, :20]), 0.0)
    gr = jax.grad(lambda a, b, c: (_masked_dense(
        a[1:], b[1:], c[1:], kv_mask[1:], causal)[:, :, 20 if causal else 0:]
        ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), gr):
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   atol=BWD_ATOL)


def test_causal_live_tile_rule():
    """The one rule all three kernels' ``pl.when`` and index maps
    follow: at the cell's shape (S = 8192, 512-blocks) 136 of the 256 tile
    pairs are live; the clamped index of a dead pair is the nearest live
    tile's and a live pair's is its own."""
    n = 8192 // 512
    assert sum(_fa._tile_is_live(i, j, 512, 512)
               for i in range(n) for j in range(n)) == 136
    for bq, bk, s in [(512, 512, 8192), (64, 32, 256), (32, 64, 256)]:
        for i in range(s // bq):
            for j in range(s // bk):
                is_live = _fa._tile_is_live(i, j, bq, bk)
                # some column of the KV tile is at or before some row
                assert is_live == (j * bk <= i * bq + bq - 1)
                assert (max(i, _fa._first_live_q(j, bq, bk)) == i) == is_live
                assert (min(j, _fa._last_live_kv(i, bq, bk)) == j) == is_live
                assert _fa._tile_is_live(
                    max(i, _fa._first_live_q(j, bq, bk)), j, bq, bk)
                assert _fa._tile_is_live(
                    i, min(j, _fa._last_live_kv(i, bq, bk)), bq, bk)


@pytest.mark.parametrize("clamped", [True, False],
                         ids=["clamped", "unclamped"])
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64)])
def test_backward_skips_dead_causal_tiles(bq, bk, clamped, monkeypatch):
    """A dead tile pair does no work: NaN planted in the first Q tile's
    q/dO rows, or in the last KV tile's k/v rows, reaches only the tiles
    that legitimately read them. A kernel that computed a dead pair and
    masked it afterwards would multiply p = 0 by NaN and poison every
    row (0 * NaN = NaN). ``unclamped`` takes the index maps' clamp away,
    so that a dead pair's own (NaN) blocks ARE fetched and the kernels'
    ``pl.when`` alone has to keep them out; ``clamped`` is the code as it
    runs."""
    if not clamped:
        monkeypatch.setattr(_fa, "_first_live_q", lambda ki, bq, bk: 0)
        monkeypatch.setattr(_fa, "_last_live_kv", lambda qi, bq, bk: 1 << 20)
    s, blk = 256, max(bq, bk)
    q, k, v = _rand_qkv(s=s, d=16, seed=21)
    w = jnp.asarray(np.random.RandomState(2).randn(*q.shape), jnp.float32)

    def grads(q, k, v, w):
        return jax.grad(lambda a, b, c: (flash_attention(
            a, b, c, True, block_q=bq, block_k=bk) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)

    clean = grads(q, k, v, w)
    nan = jnp.full((2, 3, blk, 16), jnp.nan)
    # the dkv kernel: Q tile 0 is dead for every KV tile past the first
    got = grads(q.at[:, :, :blk].set(nan), k, v, w.at[:, :, :blk].set(nan))
    for name, a, b in zip(("dq", "dk", "dv"), got, clean):
        np.testing.assert_allclose(np.asarray(a[:, :, blk:]),
                                   np.asarray(b[:, :, blk:]),
                                   atol=1e-6, err_msg=name)
    # the dq kernel: the last KV tile is dead for every Q tile before the
    # last (whose own p is NaN, so dk and dv are NaN throughout, rightly)
    dq = grads(q, k.at[:, :, -blk:].set(nan), v.at[:, :, -blk:].set(nan),
               w)[0]
    np.testing.assert_allclose(np.asarray(dq[:, :, :-blk]),
                               np.asarray(clean[0][:, :, :-blk]), atol=1e-6)


def _fwd_outputs(q, k, v, kv_mask, causal, blocks):
    """(o, lse) of the forward kernel alone, at explicit or default blocks."""
    b, _, s, _ = q.shape
    if kv_mask is None:
        kv_mask = jnp.ones((b, s), jnp.float32)
    bq, bk = (blk or _fa._default_block(s) for blk in blocks)
    return _fa._fwd(q, k, v, kv_mask, causal, bq, bk, _fa._resolve(None))


def _lse_reference(q, k, kv_mask, causal):
    """float32 logsumexp of the masked, scaled scores."""
    s = q.shape[2]
    sc = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    valid = jnp.ones((1, 1, s, s), bool)
    if kv_mask is not None:
        valid = valid & (kv_mask[:, None, None, :] > 0)
    if causal:
        valid = valid & jnp.tril(jnp.ones((s, s), bool))
    return jax.nn.logsumexp(jnp.where(valid, sc, -jnp.inf), axis=-1)


# Float32 inputs against a reference at the HIGHEST precision. Interpreted,
# the kernel's float32 products are exact; compiled on the chip they run at
# the default precision, one bf16 pass that rounds q, k, p and v (PR 32's
# on-chip reading: one entry of 24,576 off by 0.0038 under ``causal``, where
# the first rows' outputs are O(1)) — FWD_ATOL's 2e-3 there was read against
# a dense reference rounded the same way.
F32_ATOL = 8e-3 if is_tpu_backend() else FWD_ATOL


@pytest.mark.parametrize("blocks", [(64, 64), (64, 32), (None, None)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "kvmask"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_forward_precision_follows_the_inputs(dtype, causal, masked, blocks):
    """The forward kernel hands the MXU the inputs' dtype. float32 callers
    get float32 products and stay at FWD_ATOL; bf16 callers get bf16
    products with ``p`` rounded to bf16 before the second one: against
    float32 dense attention of the same bf16 values the gap is bf16's
    (2**-9 of an output of up to ~1, the output's own rounding included;
    readings 0.0004-0.0014), and against the program's own dense path on
    the bf16 values (which rounds ``p`` the same way, and on the CPU the
    scores too: its own gap to float32 reads 0.0027) a little more. ``lse``
    comes from the float32 accumulation of exact bf16 products, so it
    meets a float32 ``logsumexp`` at float32's tolerance: the backward
    pair recomputes ``p`` from it."""
    s = 128
    q, k, v = (x.astype(dtype) for x in _rand_qkv(s=s))
    kv_mask = _len_mask(s, [s, 51]) if masked else None
    o, lse = _fwd_outputs(q, k, v, kv_mask, causal, blocks)
    assert o.dtype == dtype and lse.dtype == jnp.float32
    o = np.asarray(o, np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(dense_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), causal, kv_mask))
        want_lse = np.asarray(_lse_reference(q, k, kv_mask, causal))
    if dtype == jnp.float32:
        np.testing.assert_allclose(o, ref, atol=F32_ATOL)
    else:
        np.testing.assert_allclose(o, ref, atol=4e-3)
        assert np.linalg.norm(o - ref) / np.linalg.norm(ref) < 4e-3
        same_rounding = np.asarray(dense_attention(q, k, v, causal, kv_mask),
                                   np.float32)
        np.testing.assert_allclose(o, same_rounding, atol=8e-3)
    np.testing.assert_allclose(np.asarray(lse), want_lse,
                               atol=FWD_ATOL if dtype == jnp.bfloat16
                               else F32_ATOL)


@pytest.mark.parametrize("wide", ["k", "v"])
def test_forward_mixed_dtypes(wide):
    """One float32 operand among bf16 ones: the first product promotes as
    ``jnp`` does, ``p`` takes ``v``'s dtype (the dense path's convention),
    and the output keeps ``q``'s."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _rand_qkv())
    if wide == "k":
        k = k.astype(jnp.float32)
    else:
        v = v.astype(jnp.float32)
    o = flash_attention(q, k, v, True, block_q=64, block_k=64)
    assert o.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        ref = dense_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                              True)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(ref),
                               atol=4e-3)


def _walk_eqns(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its
    equations' parameters, with the names of the pallas calls around it."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        within = inside
        if eqn.primitive.name == "pallas_call":
            within = inside + (eqn.params["name"],)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub, within)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_forward_products_take_the_inputs_dtype(dtype, causal):
    """Structural: every ``dot_general`` traced inside ``flash_attention_fwd``
    has both operands in the inputs' dtype and accumulates in float32 — so a
    later edit cannot put the float32 up-cast back unseen. Nothing else
    would notice: on the v5e a default-precision float32 product inside a
    kernel is one bf16 pass, so neither the result nor the time moves (PR
    32's ladder); the up-cast doubles the tiles' VMEM and leaves the
    kernel's speed to the compiler's default precision."""
    q = jnp.ones((1, 2, 128, 64), dtype)
    jaxpr = jax.make_jaxpr(lambda a, b, c: flash_attention(
        a, b, c, causal, block_q=64, block_k=64))(q, q, q)
    dots = [eqn for eqn, inside in _walk_eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "dot_general"
            and inside[-1:] == ("flash_attention_fwd",)]
    # two products an update; a causal kernel traces the update twice
    # (on and below the diagonal)
    assert len(dots) == (4 if causal else 2)
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
        assert eqn.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("clamped", [True, False],
                         ids=["clamped", "unclamped"])
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64)])
def test_forward_skips_dead_causal_tiles(bq, bk, clamped, monkeypatch):
    """The forward's twin of ``test_backward_skips_dead_causal_tiles``: NaN
    in the last KV tile's k, v and mask reaches only the Q tile that
    legitimately reads it; ``o`` and ``lse`` of every other Q tile are
    clean. ``unclamped`` takes the index maps' clamp away, so the dead
    pairs' NaN blocks ARE fetched and ``pl.when`` alone keeps them out."""
    if not clamped:
        monkeypatch.setattr(_fa, "_last_live_kv", lambda qi, bq, bk: 1 << 20)
    s, blk = 256, max(bq, bk)
    q, k, v = _rand_qkv(s=s, d=16, seed=21)
    ones = jnp.ones((2, s), jnp.float32)
    clean_o, clean_lse = _fwd_outputs(q, k, v, ones, True, (bq, bk))
    o, lse = _fwd_outputs(q, k.at[:, :, -blk:].set(jnp.nan),
                          v.at[:, :, -blk:].set(jnp.nan),
                          ones.at[:, -blk:].set(jnp.nan), True, (bq, bk))
    np.testing.assert_allclose(np.asarray(o[:, :, :-blk]),
                               np.asarray(clean_o[:, :, :-blk]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse[:, :, :-blk]),
                               np.asarray(clean_lse[:, :, :-blk]), atol=1e-6)
    assert np.isnan(np.asarray(o[:, :, -blk:])).any()   # the NaN was there


def test_jit_and_blocks_smaller_than_seq():
    q, k, v = _rand_qkv(s=256)
    f = jax.jit(lambda a, b, c: flash_attention(a, b, c, True, block_q=128, block_k=64))
    np.testing.assert_allclose(np.asarray(f(q, k, v)),
                               np.asarray(dense_attention(q, k, v, True)),
                               atol=FWD_ATOL)


def test_llama_with_flash_attention():
    """flash_attention drops into LlamaModel's attn_fn slot.

    The reference arm pins ``attn_fn=None`` (in-model XLA dense) so the
    comparison does not depend on what the platform's "auto" policy
    resolves to.  COMPILED on the chip this is a real two-implementation
    comparison: the kernel's MXU dots and XLA's fused dense attention
    round f32 differently (isolated-kernel parity is ~1.8e-3,
    bench flash leg), and the per-layer delta is amplified through the
    model's layers and the vocab projection onto O(1)-magnitude logits —
    the 2026-07-31 on-chip run measured max 0.041 — so the model-level
    bound is wider than the kernel-level one, with a mean bound keeping
    sensitivity to real masking/offset bugs (which shift whole rows, not
    rounding tails)."""
    from sparkdl_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny()
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(2, 32))
    base = LlamaModel(cfg, attn_fn=None)
    variables = base.init(jax.random.PRNGKey(0), jnp.asarray(ids))
    logits_dense = base.apply(variables, jnp.asarray(ids))
    flash_model = LlamaModel(cfg, attn_fn=flash_attention)
    logits_flash = flash_model.apply(variables, jnp.asarray(ids))
    diff = np.abs(np.asarray(logits_flash) - np.asarray(logits_dense))
    atol = 6e-2 if is_tpu_backend() else MODEL_ATOL
    assert diff.max() < atol, f"max {diff.max():.4f} >= {atol}"
    assert diff.mean() < atol / 6, f"mean {diff.mean():.4f} >= {atol / 6}"


def _masked_dense(q, k, v, kv_mask, causal):
    import math
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    valid = kv_mask[:, None, None, :].astype(bool)
    if causal:
        S = q.shape[2]
        valid = valid & jnp.tril(jnp.ones((S, S), bool))[None, None]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_matches_masked_dense(causal):
    """Padded key positions (the BERT attention-mask contract) are excluded
    from every query's softmax — forward and gradients."""
    q, k, v = _rand_qkv(s=96, d=16, seed=7)
    lens = np.array([96, 40])
    kv_mask = jnp.asarray((np.arange(96)[None, :] < lens[:, None])
                          .astype(np.float32))
    o = flash_attention(q, k, v, causal, kv_mask=kv_mask,
                        block_q=32, block_k=32)
    ref = _masked_dense(q, k, v, kv_mask, causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=FWD_ATOL)

    gf = jax.grad(lambda a: (flash_attention(
        a, k, v, causal, kv_mask=kv_mask, block_q=32, block_k=32) ** 2)
        .sum())(q)
    gr = jax.grad(lambda a: (_masked_dense(a, k, v, kv_mask, causal) ** 2)
                  .sum())(q)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=BWD_ATOL)


def test_fully_masked_rows_produce_zeros():
    q, k, v = _rand_qkv(s=32, d=16, seed=9)
    kv_mask = jnp.zeros((2, 32))  # nothing attendable
    o = flash_attention(q, k, v, False, kv_mask=kv_mask,
                        block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(o), 0.0, atol=1e-6)


def test_auto_attn_fn_policy():
    from sparkdl_tpu.ops.flash_attention import adaptive_attention, auto_attn_fn
    fn = auto_attn_fn()
    if is_tpu_backend():
        assert fn is adaptive_attention
    else:
        assert fn is None


def test_adaptive_attention_arms():
    """Both arms of the length-adaptive policy agree with the dense
    reference, with and without kv_mask, on either side of the
    SPARKDL_FLASH_MIN_SEQ crossover (forced low to reach the flash arm
    at test-scale shapes)."""
    from sparkdl_tpu.ops.flash_attention import adaptive_attention

    q, k, v = _rand_qkv(s=64, seed=11)
    ref = dense_attention(q, k, v, True)
    # dense arm (64 < min_seq default)
    np.testing.assert_allclose(np.asarray(adaptive_attention(q, k, v, True)),
                               np.asarray(ref), atol=FWD_ATOL)
    # flash arm, forced by dropping the crossover below s
    os.environ["SPARKDL_FLASH_MIN_SEQ"] = "32"
    try:
        np.testing.assert_allclose(
            np.asarray(adaptive_attention(q, k, v, True)),
            np.asarray(ref), atol=FWD_ATOL)
    finally:
        del os.environ["SPARKDL_FLASH_MIN_SEQ"]
    # kv_mask contract holds on the dense arm (flash arm's is kernel-tested)
    kv_mask = jnp.asarray(np.r_[np.ones(40), np.zeros(24)][None, :]
                          .repeat(2, 0).astype(np.float32))
    got = adaptive_attention(q, k, v, False, kv_mask=kv_mask)
    sc = np.einsum("bhqd,bhkd->bhqk",
                   np.asarray(q), np.asarray(k)) / np.sqrt(q.shape[-1])
    sc = np.where(np.asarray(kv_mask)[:, None, None, :] > 0, sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))
    np.testing.assert_allclose(np.asarray(got), want, atol=FWD_ATOL)
    # fully-masked rows output ZEROS on the dense arm too — the flash
    # kernel's contract (test_fully_masked_rows_produce_zeros), which a
    # finite NEG_INF softmax would otherwise turn into mean(v)
    all_dead = jnp.zeros((2, 64))
    o0 = adaptive_attention(q, k, v, False, kv_mask=all_dead)
    np.testing.assert_allclose(np.asarray(o0), 0.0, atol=1e-6)


@pytest.mark.skipif(
    not is_tpu_backend(),
    reason="compiled-mode kernel needs a real TPU "
           "(run with SPARKDL_TEST_PLATFORM=tpu)")
def test_compiled_flash_on_tpu():
    """COMPILED (non-interpret) kernel on the chip: forward + grads vs the
    dense reference, causal and masked variants (round-2 verdict weak #3)."""
    q, k, v = _rand_qkv(s=256, d=64)
    o = flash_attention(q, k, v, True, interpret=False)
    np.testing.assert_allclose(np.asarray(o),
                               np.asarray(dense_attention(q, k, v, True)),
                               atol=2e-3)
    lens = np.array([256, 100])
    kv_mask = jnp.asarray((np.arange(256)[None, :] < lens[:, None])
                          .astype(np.float32))
    o2 = flash_attention(q, k, v, False, kv_mask=kv_mask, interpret=False)
    np.testing.assert_allclose(
        np.asarray(o2), np.asarray(_masked_dense(q, k, v, kv_mask, False)),
        atol=2e-3)
    g = jax.grad(lambda a: (flash_attention(
        a, k, v, True, interpret=False) ** 2).sum())(q)
    gr = jax.grad(lambda a: (dense_attention(a, k, v, True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=5e-2)
    # the backward pair at the training cell's head shape (64 heads of 64,
    # bf16) at S = 2048 and 8192, against dense float32: chip_smoke's check
    import chip_smoke
    rec = chip_smoke.check_flash_attention(
        np.random.RandomState(0), interpret=False, seq=2048, heads=16,
        head_dim=128)
    for s in (2048, 8192):
        grad = rec[f"flash_attention_grad_S{s}"]
        assert grad["max_err"] <= chip_smoke.KERNEL_ATOL, grad
        for name in ("dq", "dk", "dv"):
            assert abs(grad[f"{name}_norm_ratio"] - 1) < 5e-3, grad


# -- a window (position t attends keys t - window + 1 .. t) ---------------------

@pytest.mark.parametrize("dv", [16, 32], ids=["v-as-wide", "v-twice-as-wide"])
@pytest.mark.parametrize("s,window,blocks", [
    (128, 32, (32, 32)),      # block multiples, window = block
    (128, 40, (32, 64)),      # unequal blocks, the band's edge inside a tile
    (128, 40, (64, 32)),
    (100, 24, (32, 32)),      # ragged S
    (130, 33, (64, 32)),      # ragged S, unequal blocks
    (96, 1, (32, 32)),        # the position itself and nothing else
    (96, 500, (32, 32)),      # wider than the sequence: plain causal
    (70, 16, (None, None)),   # the default 128-aligned blocks
], ids=lambda v: str(v).replace(" ", ""))
def test_window_matches_dense_masked_attention(s, window, blocks, dv):
    """Forward and all three gradients under a window, against dense
    attention under the same mask; values as wide as the keys and twice as
    wide (differential attention's)."""
    q, k, _ = _rand_qkv(s=s, d=16, seed=31)
    rng = np.random.RandomState(32)
    v = jnp.asarray(rng.randn(2, 3, s, dv).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(2, 3, s, dv).astype(np.float32))
    bq, bk = blocks

    def flash(q, k, v):
        return flash_attention(q, k, v, True, window=window, block_q=bq,
                               block_k=bk)

    def dense(q, k, v):
        return dense_attention(q, k, v, True, None, window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=FWD_ATOL)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (dense(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=BWD_ATOL, err_msg=name)


def test_window_with_a_kv_mask_and_fully_masked_rows():
    q, k, v = _rand_qkv(s=96, d=16, seed=33)
    mask = _len_mask(96, [96, 50])
    got = flash_attention(q, k, v, True, kv_mask=mask, window=20,
                          block_q=32, block_k=32)
    want = dense_attention(q, k, v, True, mask, 20)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=FWD_ATOL)
    # row 80 of the second sequence sees keys 61..80, all padding: zeros
    assert not np.asarray(got[1, :, 80]).any()


def test_a_window_needs_causal():
    q, k, v = _rand_qkv(s=64, d=16)
    with pytest.raises(ValueError, match="window needs causal"):
        flash_attention(q, k, v, False, window=16)
    with pytest.raises(ValueError, match="window needs causal"):
        flash_attention(q, k, v, True, window=0)


def test_window_live_tile_rule_counted_by_hand():
    """At the new cell's shape (S = 8192, 512-blocks, a window of 512) a Q
    tile's band is its own KV tile and the one before: 16 + 15 = 31 live
    pairs of 256 (136 under the causal mask alone), and the innermost grid
    axis spans 2 steps, not 16. The diagonal pair pays the causal compare
    only, the one before it the window's only."""
    n, b, w = 8192 // 512, 512, 512
    live = [(i, j) for i in range(n) for j in range(n)
            if _fa._tile_is_live(i, j, b, b, w)]
    assert len(live) == 31
    assert live == sorted([(i, i) for i in range(n)]
                          + [(i, i - 1) for i in range(1, n)])
    assert _fa._band_steps_kv(n, b, b, w) == 2
    assert _fa._band_steps_q(n, n, b, b, w) == 2
    for i in range(n):
        first, last = (_fa._first_live_kv(i, b, b, w),
                       _fa._last_live_kv(i, b, b))
        assert (first, last) == (max(i - 1, 0), i)
        assert int(_fa._kv_tile(i, 0, b, b, True, w)) == first
        assert int(_fa._kv_tile(i, 1, b, b, True, w)) == last  # clamped at 0
    for j in range(n):
        assert _fa._first_live_q(j, b, b) == j
        assert _fa._last_live_q(j, b, b, w) == j + 1   # past the end at n-1
        assert int(_fa._q_tile(1, j, b, b, True, w, n)) == min(j + 1, n - 1)
    # a window of 513 still starts in the tile before (key 512 i - 512);
    # one key more and the band touches a third tile
    assert _fa._band_steps_kv(n, b, b, w + 1) == 2
    assert _fa._band_steps_kv(n, b, b, w + 2) == 3
    # unequal blocks, by the definition: some (row, column) of the pair with
    # column <= row < column + window
    for bq, bk, win, s in [(64, 32, 40, 256), (32, 64, 40, 256),
                           (32, 32, 1, 128)]:
        for i in range(s // bq):
            for j in range(s // bk):
                by_hand = any(c <= r < c + win
                              for r in range(i * bq, i * bq + bq)
                              for c in range(j * bk, j * bk + bk))
                assert bool(_fa._tile_is_live(i, j, bq, bk, win)) == by_hand
            inside = [j for j in range(s // bk)
                      if _fa._tile_is_live(i, j, bq, bk, win)]
            assert inside == list(range(
                _fa._first_live_kv(i, bq, bk, win),
                _fa._last_live_kv(i, bq, bk) + 1))
            assert len(inside) <= _fa._band_steps_kv(s // bq, bq, bk, win)


@pytest.mark.parametrize("clamped", [True, False],
                         ids=["clamped", "unclamped"])
@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64)])
def test_window_skips_tiles_outside_the_band(bq, bk, clamped, monkeypatch):
    """A tile pair outside the band does no work, in all three kernels: NaN
    planted in the first tile's k/v rows reaches only the queries whose band
    holds them (the tiles before the band's lower edge are not in the grid at
    all), and NaN in the last tile's q/dO rows only the keys those rows see.
    ``unclamped`` takes the index maps' clamps away (to the last tile there
    is, so that a dead step's own NaN block IS fetched) and ``pl.when`` alone
    has to keep it out."""
    s, blk, window = 256, max(bq, bk), 40
    if not clamped:
        monkeypatch.setattr(_fa, "_last_live_kv",
                            lambda qi, bq_, bk_: s // bk_ - 1)
        monkeypatch.setattr(_fa, "_last_live_q",
                            lambda ki, bq_, bk_, w_: 1 << 20)
    q, k, v = _rand_qkv(s=s, d=16, seed=41)
    w = jnp.asarray(np.random.RandomState(3).randn(*q.shape), jnp.float32)

    def attend(q, k, v):
        return flash_attention(q, k, v, True, window=window, block_q=bq,
                               block_k=bk)

    def grads(q, k, v, w):
        return jax.grad(lambda a, b, c: (attend(a, b, c) * w).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    clean_o, clean = attend(q, k, v), grads(q, k, v, w)
    nan = jnp.full((2, 3, blk, 16), jnp.nan)
    # keys 0 .. blk-1 are seen by rows under blk + window - 1 and no other:
    # the forward and the dq kernel, from the first Q tile wholly past them
    row0 = -(-(blk + window - 1) // blk) * blk
    k_nan, v_nan = k.at[:, :, :blk].set(nan), v.at[:, :, :blk].set(nan)
    o = attend(q, k_nan, v_nan)
    np.testing.assert_allclose(np.asarray(o[:, :, row0:]),
                               np.asarray(clean_o[:, :, row0:]), atol=1e-6)
    assert np.isnan(np.asarray(o[:, :, :blk])).any()    # the NaN was there
    dq = grads(q, k_nan, v_nan, w)[0]
    np.testing.assert_allclose(np.asarray(dq[:, :, row0:]),
                               np.asarray(clean[0][:, :, row0:]), atol=1e-6)
    # the last KV tile is past the diagonal for every Q tile before the last
    o = attend(q, k.at[:, :, -blk:].set(nan), v.at[:, :, -blk:].set(nan))
    np.testing.assert_allclose(np.asarray(o[:, :, :-blk]),
                               np.asarray(clean_o[:, :, :-blk]), atol=1e-6)
    # the dkv kernel: rows s-blk .. s-1 see keys from s - blk - window + 1
    key1 = (s - blk - window + 1) // blk * blk
    got = grads(q.at[:, :, -blk:].set(nan), k, v, w.at[:, :, -blk:].set(nan))
    for name, a, b in zip(("dk", "dv"), got[1:], clean[1:]):
        np.testing.assert_allclose(np.asarray(a[:, :, :key1]),
                                   np.asarray(b[:, :, :key1]), atol=1e-6,
                                   err_msg=name)
        assert np.isnan(np.asarray(a[:, :, -blk:])).any(), name
    # and the first Q tile is before the diagonal of every KV tile past it
    got = grads(q.at[:, :, :blk].set(nan), k, v, w.at[:, :, :blk].set(nan))
    for name, a, b in zip(("dk", "dv"), got[1:], clean[1:]):
        np.testing.assert_allclose(np.asarray(a[:, :, blk:]),
                                   np.asarray(b[:, :, blk:]), atol=1e-6,
                                   err_msg=name)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32)], ids=str)
def test_without_a_window_nothing_changed(blocks):
    """``window=None`` is the causal kernel it always was (its lowered
    program is pinned in ``tests/test_flash_aot.py``): the same results as a
    window that covers the whole prefix, bit for bit the same from call to
    call, and no trace of the window's arithmetic in its jaxpr."""
    q, k, v = _rand_qkv(s=128, d=16, seed=51)
    bq, bk = blocks
    plain = flash_attention(q, k, v, True, block_q=bq, block_k=bk)
    wide = flash_attention(q, k, v, True, window=128, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(wide),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(dense_attention(q, k, v, True)),
        atol=FWD_ATOL)

    def grid_of(window):
        jaxpr = jax.make_jaxpr(lambda *a: flash_attention(
            *a, True, window=window, block_q=bq, block_k=bk))(q, k, v)
        return [eqn.params["grid_mapping"].grid
                for eqn, _ in _walk_eqns(jaxpr.jaxpr)
                if eqn.primitive.name == "pallas_call"]

    assert grid_of(None) == [(6, 128 // bq, 128 // bk)]
    assert grid_of(16) == [(6, 128 // bq, _fa._band_steps_kv(
        128 // bq, bq, bk, 16))]
