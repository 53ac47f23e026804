"""The selective-scan kernel pair (``ops/selective_scan.py``), interpreted on
the CPU, against the position-by-position recurrence in float32: forward,
every gradient, the state carried across chunks. The kernels compiled for a
described chip are in ``tests/test_flash_aot.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.selective_scan import selective_scan

NAMES = ("u", "dt", "A", "B", "C", "D")


def recurrence(u, dt, A, B, C, D):
    """``(y, last state)`` one position at a time, float32."""
    u, dt, B, C = (t.astype(jnp.float32) for t in (u, dt, B, C))

    def step(h, x):
        u_t, dt_t, b_t, c_t = x
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)

    xs = tuple(jnp.swapaxes(t, 0, 1) for t in (u, dt, B, C))
    h, y = jax.lax.scan(
        step, jnp.zeros((u.shape[0], u.shape[2], A.shape[1])), xs)
    return jnp.swapaxes(y, 0, 1) + D * u, h


def operands(bsz, s, c, n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (bsz, s, c)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, c)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n))),
            jax.random.normal(ks[3], (bsz, s, n)).astype(dtype),
            jax.random.normal(ks[4], (bsz, s, n)).astype(dtype),
            jax.random.normal(ks[5], (c,)))


SHAPES = {
    # (batch, S, channels, states, chunk, block_c)
    "ragged-S-and-channels": (2, 37, 24, 4, 8, 128),
    "chunk-1": (1, 12, 8, 4, 1, 128),
    "chunk-past-S": (2, 20, 130, 16, 64, 128),
    "two-channel-blocks": (1, 40, 200, 16, 16, 128),
    "S-a-multiple-of-the-chunk": (2, 32, 16, 8, 8, 128),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_forward_and_last_state_match_the_recurrence(shape):
    bsz, s, c, n, chunk, block_c = shape
    args = operands(bsz, s, c, n)
    y, last = selective_scan(*args, chunk=chunk, block_c=block_c)
    want, h = recurrence(*args)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(last, h, rtol=1e-5, atol=1e-6)
    assert y.shape == (bsz, s, c) and last.shape == (bsz, c, n)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("wrt", range(6), ids=NAMES)
def test_every_gradient_matches_the_recurrence(shape, wrt):
    bsz, s, c, n, chunk, block_c = shape
    args = operands(bsz, s, c, n, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (bsz, s, c))
    got = jax.grad(lambda *a: (selective_scan(
        *a, chunk=chunk, block_c=block_c)[0] * w).sum(), argnums=wrt)(*args)
    want = jax.grad(lambda *a: (recurrence(*a)[0] * w).sum(),
                    argnums=wrt)(*args)
    assert got.shape == args[wrt].shape and got.dtype == args[wrt].dtype
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_bf16_inputs_run_the_recurrence_in_float32():
    """``u, B, C`` in bfloat16, ``dt`` and ``A`` float32 (the model's call):
    the result is the float32 recurrence over the same bfloat16 values,
    rounded once at the end; gradients come back in the operands' dtypes."""
    args = operands(2, 48, 32, 16, seed=2, dtype=jnp.bfloat16)
    y, _ = selective_scan(*args, chunk=16)
    want, _ = recurrence(*args)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2 * float(jnp.abs(want).max()))
    g = jax.grad(lambda *a: selective_scan(*a, chunk=16)[0].astype(
        jnp.float32).sum(), argnums=tuple(range(6)))(*args)
    g0 = jax.grad(lambda *a: recurrence(*a)[0].sum(),
                  argnums=tuple(range(6)))(*args)
    for name, a, b, x in zip(NAMES, g, g0, args):
        assert a.dtype == x.dtype, name
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=2e-2,
            atol=2e-2 * float(jnp.abs(b.astype(jnp.float32)).max()),
            err_msg=name)


@pytest.mark.parametrize("planted", ["u", "dt", "B", "C"])
def test_a_nan_in_a_later_chunk_never_reaches_an_earlier_position(planted):
    """The state flows forward only: a NaN planted in the third chunk's
    inputs leaves ``y`` of the first two chunks as it was, and poisons what
    comes after it (through the carried state, for ``u``, ``dt`` and ``B``;
    at its own position alone for ``C``)."""
    chunk, at = 8, 19
    args = list(operands(1, 40, 16, 4, seed=3))
    clean, _ = selective_scan(*args, chunk=chunk)
    i = NAMES.index(planted)
    args[i] = args[i].at[0, at].set(jnp.nan)
    y, _ = selective_scan(*args, chunk=chunk)
    np.testing.assert_array_equal(y[:, :at], clean[:, :at])
    assert bool(jnp.isnan(y[:, at]).any())
    later = bool(jnp.isnan(y[:, 3 * chunk:]).all())
    assert later == (planted != "C")


def test_the_state_is_carried_across_chunks_and_not_restarted():
    """Two halves scanned apart differ from one scan exactly by what the
    first half's last state adds to the second."""
    args = operands(1, 32, 16, 4, seed=4)
    whole, _ = selective_scan(*args, chunk=8)
    first, _ = selective_scan(
        *(a[:, :16] if a.ndim == 3 else a for a in args), chunk=8)
    second_alone, _ = selective_scan(
        *(a[:, 16:] if a.ndim == 3 else a for a in args), chunk=8)
    np.testing.assert_allclose(whole[:, :16], first, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(whole[:, 16:] - second_alone).max()) > 1e-3


def test_the_last_state_carries_no_gradient():
    args = operands(1, 16, 8, 4, seed=5)
    g = jax.grad(lambda u: selective_scan(
        u, *args[1:], chunk=8)[1].sum())(args[0])
    assert not np.asarray(g).any()
