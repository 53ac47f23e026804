"""The chunked state-space-dual kernel pair (``ops/ssd_scan.py``), interpreted
on the CPU, against Mamba-2's recurrence walked position by position in
float32: forward, every gradient, the state carried across chunks. The
kernels compiled for a described chip are in ``tests/test_flash_aot.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.ssd_scan import chunk_decay, ssd_scan

NAMES = ("x", "dt", "A", "B", "C", "D")


def recurrence(x, dt, A, B, C, D):
    """``(y, last state)`` one position at a time, float32: ``x [B, S, H,
    P]``, ``dt [B, S, H]``, ``A, D [H]``, ``B, C [B, S, 1, N]``."""
    x, dt, B, C = (t.astype(jnp.float32) for t in (x, dt, B, C))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return h, jnp.sum(h * c_t[:, None, None, :], -1)

    xs = tuple(jnp.swapaxes(t, 0, 1) for t in (x, dt, B[:, :, 0], C[:, :, 0]))
    h, y = jax.lax.scan(step, jnp.zeros(
        (x.shape[0], x.shape[2], x.shape[3], B.shape[-1])), xs)
    return jnp.swapaxes(y, 0, 1) + D[:, None] * x, h


def operands(bsz, s, h, p, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (bsz, s, h, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (h,))),
            jax.random.normal(ks[3], (bsz, s, 1, n)).astype(dtype),
            jax.random.normal(ks[4], (bsz, s, 1, n)).astype(dtype),
            jax.random.normal(ks[5], (h,)))


SHAPES = {
    # (batch, S, heads, P, N, chunk, heads a block)
    "ragged-S-heads-sharing-lanes": (2, 37, 4, 8, 16, 8, 4),
    "chunk-1": (1, 12, 2, 8, 4, 1, 2),
    "chunk-past-S": (2, 20, 6, 8, 16, 64, 2),
    "two-head-blocks-of-64-wide-pairs": (1, 32, 4, 64, 16, 8, 2),
    "a-head-as-wide-as-the-lanes": (1, 24, 2, 128, 8, 8, 2),
    "S-a-multiple-of-the-chunk": (2, 32, 3, 16, 8, 8, 8),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_forward_and_last_state_match_the_recurrence(shape):
    bsz, s, h, p, n, chunk, block_h = shape
    args = operands(bsz, s, h, p, n)
    y, last = ssd_scan(*args, chunk=chunk, block_h=block_h)
    want, state = recurrence(*args)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, state, rtol=1e-5, atol=1e-6)
    assert y.shape == (bsz, s, h, p) and last.shape == (bsz, h, p, n)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("wrt", range(6), ids=NAMES)
def test_every_gradient_matches_the_recurrence(shape, wrt):
    bsz, s, h, p, n, chunk, block_h = shape
    args = operands(bsz, s, h, p, n, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (bsz, s, h, p))
    got = jax.grad(lambda *a: (ssd_scan(
        *a, chunk=chunk, block_h=block_h)[0] * w).sum(), argnums=wrt)(*args)
    want = jax.grad(lambda *a: (recurrence(*a)[0] * w).sum(),
                    argnums=wrt)(*args)
    assert got.shape == args[wrt].shape and got.dtype == args[wrt].dtype
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_bf16_inputs_keep_the_decay_and_the_state_in_float32():
    """``x, B, C`` in bfloat16, ``dt`` and ``A`` float32 (the model's call):
    the products take bfloat16 operands (the decay-weighted tile rounded once
    before its product, as the flash kernels round ``p``), the running sum,
    every exponent and the state stay float32; gradients come back in the
    operands' dtypes."""
    args = operands(2, 48, 4, 16, 16, seed=2, dtype=jnp.bfloat16)
    y, _ = ssd_scan(*args, chunk=16)
    want, _ = recurrence(*args)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=2e-2,
                               atol=2e-2 * float(jnp.abs(want).max()))
    g = jax.grad(lambda *a: ssd_scan(*a, chunk=16)[0].astype(
        jnp.float32).sum(), argnums=tuple(range(6)))(*args)
    g0 = jax.grad(lambda *a: recurrence(*a)[0].sum(),
                  argnums=tuple(range(6)))(*args)
    for name, a, b, x in zip(NAMES, g, g0, args):
        assert a.dtype == x.dtype, name
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=3e-2,
            atol=3e-2 * float(jnp.abs(b.astype(jnp.float32)).max()),
            err_msg=name)


@pytest.mark.parametrize("planted", ["x", "dt", "B", "C"])
def test_a_nan_in_a_later_chunk_never_reaches_an_earlier_chunk(planted):
    """The state flows forward only: a NaN planted in the third chunk's
    inputs leaves ``y`` of the first two chunks as it was, and poisons every
    chunk after its own through the carried state (for ``x``, ``dt`` and
    ``B``; ``C`` reads a state and writes none)."""
    chunk, at = 8, 19
    args = list(operands(1, 40, 4, 8, 4, seed=3))
    clean, _ = ssd_scan(*args, chunk=chunk)
    i = NAMES.index(planted)
    args[i] = args[i].at[0, at].set(jnp.nan)
    y, _ = ssd_scan(*args, chunk=chunk)
    np.testing.assert_array_equal(y[:, :2 * chunk], clean[:, :2 * chunk])
    assert bool(jnp.isnan(y[:, at]).any())
    later = bool(jnp.isnan(y[:, 3 * chunk:]).all())
    assert later == (planted != "C")


def test_the_state_is_carried_across_chunks_and_not_restarted():
    """Two halves scanned apart differ from one scan exactly by what the
    first half's last state adds to the second."""
    args = operands(1, 32, 4, 8, 4, seed=4)
    whole, _ = ssd_scan(*args, chunk=8)
    halves = [tuple(a[:, half] if a.ndim > 1 else a for a in args)
              for half in (slice(0, 16), slice(16, 32))]
    first, state = ssd_scan(*halves[0], chunk=8)
    second_alone, _ = ssd_scan(*halves[1], chunk=8)
    np.testing.assert_allclose(whole[:, :16], first, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(whole[:, 16:] - second_alone).max()) > 1e-3
    # what the carried state adds: exp(s_t) C_t . H, position by position
    x, dt, A, B, C, D = halves[1]
    decay = jnp.exp(jnp.cumsum(dt * A, axis=1))                # [B, S, H]
    added = jnp.einsum("bsh,bsn,bhpn->bshp", decay, C[:, :, 0], state)
    np.testing.assert_allclose(whole[:, 16:] - second_alone, added,
                               rtol=1e-4, atol=1e-5)


def test_a_decay_of_minus_80_a_position_stays_finite():
    """``dt * A = -80`` at every position: the running sum reaches -20,000
    inside a chunk, ``exp(s_t) * exp(-s_r)`` would be ``0 * inf``. Every
    exponent the kernels take is of a difference ``<= 0``, so forward and
    every gradient are finite, and equal to the recurrence's."""
    args = list(operands(1, 32, 2, 8, 4, seed=6))
    args[1] = jnp.full_like(args[1], 8.0)
    args[2] = jnp.full_like(args[2], -10.0)
    y, last = ssd_scan(*args, chunk=16)
    want, state = recurrence(*args)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, state, rtol=1e-5, atol=1e-6)
    g = jax.grad(lambda *a: (ssd_scan(*a, chunk=16)[0] ** 2).sum(),
                 argnums=tuple(range(6)))(*args)
    g0 = jax.grad(lambda *a: (recurrence(*a)[0] ** 2).sum(),
                  argnums=tuple(range(6)))(*args)
    for name, a, b in zip(NAMES, g, g0):
        assert bool(jnp.isfinite(a).all()), name
        # dA is of the order exp(-80) here: under float32's normal range
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-20,
                                   err_msg=name)
    assert float(jnp.exp(jnp.min(chunk_decay(args[1], args[2], 16)))) == 0.0


def test_the_last_state_carries_no_gradient():
    args = operands(1, 16, 2, 8, 4, seed=5)
    g = jax.grad(lambda x: ssd_scan(x, *args[1:], chunk=8)[1].sum())(args[0])
    assert not np.asarray(g).any()


def test_more_than_one_group_is_refused_by_name():
    args = list(operands(1, 16, 2, 8, 4))
    args[3] = jnp.repeat(args[3], 2, axis=2)
    args[4] = jnp.repeat(args[4], 2, axis=2)
    with pytest.raises(NotImplementedError, match="one B/C group"):
        ssd_scan(*args, chunk=8)


def test_chunk_decay_restarts_at_every_chunk():
    dt = jnp.ones((1, 10, 2))
    s = chunk_decay(dt, jnp.array([-1.0, -2.0]), 4)
    np.testing.assert_allclose(
        s[0, :, 0], [-1, -2, -3, -4, -1, -2, -3, -4, -1, -2])
    np.testing.assert_allclose(s[0, :, 1], 2 * s[0, :, 0])
