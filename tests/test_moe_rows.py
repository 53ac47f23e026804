"""The routed layer's row kernels (``ops/moe_rows.py``), interpreted on the CPU,
against plain jax: ``h[tok]`` for the gather, a masked sum for the scatter,
and the two moves' gradients (``dispatch``, ``combine``) against the VJPs of
those plain forms. The slots come sorted as ``held_experts_ffn`` sorts them, at
both routed cells' top-k and held experts (tiny ``N``), with 0, 1, a ragged
middle and all ``N k`` of them held. The dead rows hold ``nan``: nothing may
read them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import moe_rows

# (top k, held experts, experts): the LFM2 cell's and the Qwen3-Next cell's
CELLS = {"lfm2": (4, 8, 32), "qwen3next": (10, 32, 512)}
N, D = 24, 16


def sorted_slots(k, held, experts, n_held, seed, n=N):
    """``(tok [n k], n_live [1], weights [n k])`` of ``n_held`` held picks,
    numbered and sorted as ``held_experts_ffn`` does."""
    rng = np.random.default_rng(seed)
    is_held = np.zeros(n * k, bool)
    is_held[rng.choice(n * k, n_held, replace=False)] = True
    local = np.where(is_held, rng.integers(0, held, n * k), held)
    order = np.argsort(local, kind="stable").astype(np.int32)
    return (jnp.asarray(order % n), jnp.asarray([n_held], jnp.int32),
            jnp.asarray(rng.random(n * k), jnp.float32))


def held_counts(k):
    return {"none": 0, "one": 1, "ragged": 3 * N * k // 8 + 1, "all": N * k}


CASES = [(cell, count) for cell in CELLS for count in held_counts(1)]


def case(cell, count, seed=0):
    k, held, experts = CELLS[cell]
    n_held = held_counts(k)[count]
    tok, n_live, w = sorted_slots(k, held, experts, n_held, seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(keys[0], (N, D))
    y = jax.random.normal(keys[1], (N * k, D))
    g = jax.random.normal(keys[2], (N, D))
    return tok, n_live, w, h, y, g, n_held


def soiled(y, n_held):
    return y.at[n_held:].set(jnp.nan)


def plain_combine(y, w, tok, n_held):
    live = (jnp.arange(tok.shape[0]) < n_held)[:, None]
    return jax.ops.segment_sum(jnp.where(live, w[:, None] * y, 0), tok, N)


@pytest.mark.parametrize("cell,count", CASES)
def test_the_gather_and_the_scatter_against_plain_jax(cell, count):
    tok, n_live, w, h, y, _, n_held = case(cell, count)
    rows, dot = moe_rows.gather_rows(h, tok, n_live, interpret=True)
    assert dot is None and rows.shape == (tok.shape[0], D)
    np.testing.assert_array_equal(rows[:n_held], h[tok[:n_held]])
    out = moe_rows.scatter_rows(soiled(y, n_held), tok, n_live, w, n=N,
                                interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, plain_combine(y, w, tok, n_held),
                               rtol=1e-5, atol=1e-6)
    unweighted = moe_rows.scatter_rows(soiled(y, n_held), tok, n_live, n=N,
                                       interpret=True)
    np.testing.assert_allclose(
        unweighted, plain_combine(y, jnp.ones_like(w), tok, n_held),
        rtol=1e-5, atol=1e-6)
    if n_held == 0:
        assert not np.asarray(out).any()


@pytest.mark.parametrize("cell,count", CASES)
def test_the_two_moves_gradients_against_plain_jax(cell, count):
    """``dispatch``'s gradient sums a token's live slots; ``combine``'s gives
    ``w * g`` in the live slots and, for the weights, ``<g, y>`` there and
    zero at a dead slot -- finite with ``nan`` in every dead row of ``y`` and
    of the cotangent of ``rows``."""
    tok, n_live, w, h, y, g, n_held = case(cell, count, seed=1)
    live = (jnp.arange(tok.shape[0]) < n_held)[:, None]
    cot_rows = soiled(jax.random.normal(jax.random.PRNGKey(5), y.shape),
                      n_held)
    rows, back = jax.vjp(
        lambda x: moe_rows.dispatch(x, tok, n_live, True), h)
    (dh,) = back(cot_rows)
    _, plain_back = jax.vjp(lambda x: jnp.where(live, x[tok], 0), h)
    np.testing.assert_allclose(dh, plain_back(jnp.where(live, cot_rows, 0))[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(rows[:n_held], h[tok[:n_held]])

    out, back = jax.vjp(
        lambda a, b: moe_rows.combine(a, b, tok, n_live, N, True),
        soiled(y, n_held), w)
    dy, dw = back(g)
    want, plain_back = jax.vjp(
        lambda a, b: plain_combine(a, b, tok, n_held), y, w)
    want_dy, want_dw = plain_back(g)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dy[:n_held], want_dy[:n_held], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-6)
    for x in (out, dh, dw, dy[:n_held]):
        assert np.isfinite(np.asarray(x)).all()


def test_every_block_chunk_and_table_copy_at_small_sizes(monkeypatch):
    """Slot blocks of 32 (the dead ones past the live slots map to the last
    live block), column chunks of 128 of a 384-wide table and DMAs of 16 token
    rows: the kernels' every loop runs more than once."""
    monkeypatch.setattr(moe_rows, "_SLOTS", 32)
    monkeypatch.setattr(moe_rows, "_ROWS", 16)
    monkeypatch.setattr(moe_rows, "_TABLE_BYTES", 64 * 128 * 4)
    n, d, k = 64, 384, 4
    assert moe_rows._sizes(n, n * k, d) == (32, 8, 128, 16)
    tok, n_live, w = sorted_slots(k, 8, 32, 101, seed=3, n=n)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(keys[0], (n, d))
    y = jax.random.normal(keys[1], (n * k, d))
    g = jax.random.normal(keys[2], (n, d))
    rows, dot = moe_rows.gather_rows(h, tok, n_live, w, soiled(y, 101),
                                     interpret=True)
    np.testing.assert_allclose(rows[:101], w[:101, None] * h[tok[:101]],
                               rtol=1e-6)
    np.testing.assert_allclose(dot[:101], (h[tok[:101]] * y[:101]).sum(-1),
                               rtol=1e-5, atol=1e-5)
    out = moe_rows.scatter_rows(soiled(y, 101), tok, n_live, w, n=n,
                                interpret=True)
    live = (jnp.arange(n * k) < 101)[:, None]
    np.testing.assert_allclose(
        out, jax.ops.segment_sum(jnp.where(live, w[:, None] * y, 0), tok, n),
        rtol=1e-5, atol=1e-6)
    rows = moe_rows.gather_rows(g, tok, n_live, interpret=True)[0]
    np.testing.assert_array_equal(rows[:101], g[tok[:101]])
