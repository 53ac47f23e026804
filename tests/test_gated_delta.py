"""The gated delta rule's kernel pair (``ops/gated_delta.py``), interpreted on
the CPU, against the recurrence walked position by position in float32:
forward, last state, every gradient, the hand-over between chunks. The
kernels compiled for a described chip are in ``tests/test_flash_aot.py``."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import gated_delta as gd
from sparkdl_tpu.ops.gated_delta import chunk_log_decay, gated_delta_rule

NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta, reset_every: int = 0):
    """``(o, last state)`` one position at a time, float32: ``q, k [B, S, Hk,
    Dk]``, ``v [B, S, Hv, Dv]``, ``g, beta [B, S, Hv]``. ``reset_every``: the
    planted fault, a state of zeros every so many positions."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))

    def step(carry, inp):
        state, t = carry                      # [B, H, Dk, Dv]
        q_t, k_t, v_t, g_t, b_t = inp
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        state = jnp.exp(g_t)[..., None, None] * state
        delta = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state,
                                                   k_t))
        state = state + k_t[..., :, None] * delta[..., None, :]
        return (state, t + 1), jnp.einsum("bhkv,bhk->bhv", state, q_t)

    zeros = jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]))
    (state, _), o = jax.lax.scan(
        step, (zeros, 0), tuple(jnp.swapaxes(t, 0, 1)
                                for t in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1) / math.sqrt(q.shape[-1]), state


def operands(bsz, s, hk, hv, dk, dv, seed=0, dtype=jnp.float32,
             half_life=(4.0, 64.0)):
    """q and k of unit length a head, as the model hands them over; a decay
    whose half-life is log-uniform over ``half_life`` positions."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key, h, d):
        x = jax.random.normal(key, (bsz, s, h, d))
        return (x / jnp.linalg.norm(x, axis=-1, keepdims=True)).astype(dtype)

    lo, hi = (math.log(x) for x in half_life)
    life = jnp.exp(jax.random.uniform(ks[3], (bsz, s, hv), minval=lo,
                                      maxval=hi))
    return (unit(ks[0], hk, dk), unit(ks[1], hk, dk),
            jax.random.normal(ks[2], (bsz, s, hv, dv)).astype(dtype),
            -math.log(2.0) / life,
            jax.nn.sigmoid(jax.random.normal(ks[4], (bsz, s, hv))))


SHAPES = {
    # (batch, S, key heads, value heads, Dk, Dv, chunk, value heads a block)
    "ragged-S": (2, 37, 2, 4, 8, 16, 8, 4),
    "chunk-2": (1, 12, 1, 2, 8, 4, 2, 2),
    "chunk-past-S": (2, 20, 3, 6, 8, 16, 64, 2),
    "two-head-blocks": (1, 32, 2, 4, 16, 16, 8, 2),
    "heads-as-wide-as-the-lanes": (1, 24, 1, 2, 128, 128, 8, 2),
    "one-value-head-a-key-head": (2, 32, 3, 3, 16, 8, 16, 8),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_forward_and_last_state_match_the_recurrence(shape):
    bsz, s, hk, hv, dk, dv, chunk, block_h = shape
    args = operands(bsz, s, hk, hv, dk, dv)
    o, last = gated_delta_rule(*args, chunk=chunk, block_h=block_h)
    want, state = recurrence(*args)
    np.testing.assert_allclose(o, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(last, state, rtol=2e-5, atol=2e-5)
    assert o.shape == (bsz, s, hv, dv) and last.shape == (bsz, hv, dk, dv)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("wrt", range(5), ids=NAMES)
def test_every_gradient_matches_the_recurrence(shape, wrt):
    bsz, s, hk, hv, dk, dv, chunk, block_h = shape
    args = operands(bsz, s, hk, hv, dk, dv, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), (bsz, s, hv, dv))
    got = jax.grad(lambda *a: (gated_delta_rule(
        *a, chunk=chunk, block_h=block_h)[0] * w).sum(), argnums=wrt)(*args)
    want = jax.grad(lambda *a: (recurrence(*a)[0] * w).sum(),
                    argnums=wrt)(*args)
    assert got.shape == args[wrt].shape and got.dtype == args[wrt].dtype
    np.testing.assert_allclose(got, want, rtol=5e-4,
                               atol=5e-5 * float(jnp.abs(want).max()))


def test_the_chunked_form_at_the_published_chunk_matches_the_recurrence():
    """``Q = 64``, the published code's chunk, and 128, the kernel's default:
    the same numbers."""
    args = operands(1, 256, 1, 2, 16, 16, seed=7, half_life=(16.0, 512.0))
    want, state = recurrence(*args)
    for chunk in (64, 128):
        o, last = gated_delta_rule(*args, chunk=chunk)
        np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(last, state, rtol=1e-4, atol=1e-4)


def test_bf16_inputs_keep_the_decay_and_the_state_in_float32():
    """``q, k, v`` in bfloat16, ``g`` and ``beta`` float32 (the model's
    call): gradients come back in the operands' dtypes."""
    args = operands(2, 48, 2, 4, 16, 16, seed=2, dtype=jnp.bfloat16)
    o, _ = gated_delta_rule(*args, chunk=16)
    want, _ = recurrence(*args)
    assert o.dtype == jnp.bfloat16
    np.testing.assert_allclose(o.astype(jnp.float32), want, rtol=3e-2,
                               atol=3e-2 * float(jnp.abs(want).max()))
    grads = jax.grad(lambda *a: gated_delta_rule(*a, chunk=16)[0].astype(
        jnp.float32).sum(), argnums=tuple(range(5)))(*args)
    wanted = jax.grad(lambda *a: recurrence(*a)[0].sum(),
                      argnums=tuple(range(5)))(*args)
    for name, a, b, x in zip(NAMES, grads, wanted, args):
        assert a.dtype == x.dtype, name
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=5e-2,
            atol=5e-2 * float(jnp.abs(b.astype(jnp.float32)).max()),
            err_msg=name)


def test_the_state_is_carried_across_chunks_and_not_restarted():
    """Two halves run apart differ from one run by what the first half's last
    state adds to the second; handing that state on by hand (as the
    recurrence's start) gives the whole run's second half."""
    args = operands(1, 32, 2, 4, 8, 8, seed=4, half_life=(32.0, 256.0))
    whole, last = gated_delta_rule(*args, chunk=8)
    first, state = gated_delta_rule(*(a[:, :16] for a in args), chunk=8)
    second_alone, _ = gated_delta_rule(*(a[:, 16:] for a in args), chunk=8)
    np.testing.assert_allclose(whole[:, :16], first, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(whole[:, 16:] - second_alone).max()) > 1e-2
    one_chunk, last_one = gated_delta_rule(*args, chunk=32)
    np.testing.assert_allclose(whole, one_chunk, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(last, last_one, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(state).max()) > 1e-2


def test_a_state_reset_at_every_chunk_is_seen_as_order_one():
    """The fault the benchmark plants in its reference: the state started
    from zeros at every chunk. With half-lives of tens of positions and more
    it moves the output by its own size, and the kernel pair agrees with the
    sound recurrence, never with the reset one."""
    args = operands(1, 64, 1, 2, 16, 16, seed=5, half_life=(64.0, 512.0))
    o, _ = gated_delta_rule(*args, chunk=16)
    want, _ = recurrence(*args)
    reset, _ = recurrence(*args, reset_every=16)
    size = float(jnp.linalg.norm(want[:, 16:]))
    assert float(jnp.linalg.norm((reset - want)[:, 16:])) > 0.3 * size
    assert float(jnp.linalg.norm(o - want)) < 1e-4 * size


def test_a_decay_of_minus_80_a_position_stays_finite():
    """``g = -80`` at every position: ``exp(G_t) * exp(-G_r)`` would be
    ``0 * inf``. Every exponent taken is of a difference ``<= 0``."""
    args = list(operands(1, 32, 1, 2, 8, 8, seed=6))
    args[3] = jnp.full_like(args[3], -80.0)
    o, last = gated_delta_rule(*args, chunk=16)
    want, state = recurrence(*args)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(last).all())
    np.testing.assert_allclose(o, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(last, state, rtol=1e-5, atol=1e-6)
    grads = jax.grad(lambda *a: (gated_delta_rule(*a, chunk=16)[0] ** 2).sum(),
                     argnums=tuple(range(5)))(*args)
    for name, a in zip(NAMES, grads):
        assert bool(jnp.isfinite(a).all()), name


def test_the_last_state_carries_no_gradient():
    args = operands(1, 16, 1, 2, 8, 4, seed=5)
    g = jax.grad(lambda v: gated_delta_rule(
        args[0], args[1], v, *args[3:], chunk=8)[1].sum())(args[2])
    assert not np.asarray(g).any()


def test_value_heads_that_do_not_divide_over_the_key_heads_are_refused():
    q, k, v, g, beta = operands(1, 16, 2, 4, 8, 4)
    with pytest.raises(ValueError, match="value heads"):
        gated_delta_rule(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


def test_chunk_log_decay_restarts_at_every_chunk():
    g = -jnp.ones((1, 10, 2)) * jnp.array([1.0, 2.0])
    s = chunk_log_decay(g, 4)
    np.testing.assert_allclose(
        s[0, :, 0], [-1, -2, -3, -4, -1, -2, -3, -4, -1, -2])
    np.testing.assert_allclose(s[0, :, 1], 2 * s[0, :, 0])


@pytest.mark.parametrize("chunk", [2, 8, 48, 64, 128])
def test_the_preparation_kernel_against_a_plain_inverse(chunk):
    """``T = (I + A)^-1`` by a library inverse, ``U = T (beta V)`` and ``W = T
    (beta K e^G)``, a chunk and value head at a time; the kernel's substitution
    and merges are exact up to their three-pass products (48: a ragged last
    block to merge)."""
    q, k, v, g, beta = operands(1, 2 * chunk, 1, 2, 8, 8, seed=chunk,
                                half_life=(2.0, 64.0))
    gamma = chunk_log_decay(g, chunk)
    u, w, t = gd._prep_call(k, v, gamma, beta, chunk, True)
    a = gd._tiles(k, gamma, beta, chunk)
    assert a.shape == t.shape == (1, 2, 2, chunk, chunk)
    assert not np.asarray(jnp.triu(a)).any()          # strictly lower
    want = jnp.linalg.inv(jnp.eye(chunk) + a)
    np.testing.assert_allclose(t, want, rtol=1e-4, atol=1e-5)
    uu, ww = gd._apply(want, k, v, gamma, beta, chunk)
    np.testing.assert_allclose(u, uu, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(w, ww, rtol=1e-4, atol=1e-5)


def correlated_keys(s, hk, dk, cosine, seed):
    """Keys of unit length whose pairwise cosine is ``cosine`` on average: a
    direction shared by a key head's positions plus noise of their own."""
    shared, noise = (jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.PRNGKey(seed)),
        ((1, 1, hk, dk), (1, s, hk, dk))))

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return unit(math.sqrt(cosine) * unit(shared)
                + math.sqrt(1.0 - cosine) * unit(noise))


def largest_gap(got, want):
    """``max |got - want| / max |want|``, the worst over ``[..., Q, D]``
    tiles."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float((np.abs(got - want).max(axis=(-2, -1))
                  / np.abs(want).max(axis=(-2, -1))).max())


@pytest.mark.parametrize("chunk", [16, 64, 128])
@pytest.mark.parametrize("cosine", [0.3, 0.5, 0.9])
def test_the_preparation_kernel_holds_where_keys_correlate(cosine, chunk):
    """Keys that point the same way inside a chunk (a fine-tune's neighbours
    do), ``beta`` up to 0.95, the cell's half-lives of 64 to 8,192
    positions: ``T`` within 1e-4 of its largest entry of a float64 inverse,
    and ``U``, ``W`` of what that inverse gives. A product of powers of
    ``A`` loses every digit here: its powers grow as binomial sums."""
    s, hk, hv, d = 2 * chunk, 1, 2, 16
    _, _, v, g, _ = operands(1, s, hk, hv, d, d, seed=chunk,
                             half_life=(64.0, 8192.0))
    k = correlated_keys(s, hk, d, cosine, seed=chunk + 1)
    beta = jax.random.uniform(jax.random.PRNGKey(chunk + 2), (1, s, hv),
                              minval=0.5, maxval=0.95)
    gamma = chunk_log_decay(g, chunk)
    u, w, t = gd._prep_call(k, v, gamma, beta, chunk, True)
    a = np.asarray(gd._tiles(k, gamma, beta, chunk), np.float64)
    want = np.linalg.inv(np.eye(chunk) + a)
    assert largest_gap(t, want) < 1e-4
    uu, ww = gd._apply(jnp.asarray(want, jnp.float32), k, v, gamma, beta,
                       chunk)
    for got, ref in ((u, uu), (w, ww)):
        assert largest_gap(gd._by_chunk(got, chunk),
                           gd._by_chunk(ref, chunk)) < 1e-4


@pytest.mark.parametrize("q, width", [(128, 16), (48, 16), (32, 16),
                                      (128, 8)])
def test_the_substitution_and_a_merge_invert_a_tile_of_equal_keys(q, width):
    """Every key alike, ``beta = 0.95``, no decay: ``A = 0.95`` below the
    diagonal, where the product form's powers reach ``C(127, 63)`` and lose
    every digit. The diagonal blocks by substitution (exact in float32) are
    the library's inverse of ``I + A``'s diagonal blocks, one merge level
    that of its blocks twice as wide (a ragged last block as it stands), and
    the whole inverse that of ``I + A``, each within 1e-4 of its largest
    entry (1) as the kernel tests hold ``T``."""
    a = 0.95 * jnp.tril(jnp.ones((q, q), jnp.float32), -1)
    rows, cols = np.indices((q, q))

    def inverse_of_blocks(w):
        blocks = np.where(rows // w == cols // w, np.asarray(a, np.float64),
                          0.0)
        return np.linalg.inv(np.eye(q) + blocks)

    n = gd._diagonal_inverse(a, width)              # T - I, as it is merged
    np.testing.assert_allclose(n + np.eye(q), inverse_of_blocks(width),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(gd._merge(n, a, width) + np.eye(q),
                               inverse_of_blocks(2 * width), rtol=0,
                               atol=1e-4)
    np.testing.assert_allclose(gd._unit_lower_inverse(a),
                               np.linalg.inv(np.eye(q) + np.asarray(
                                   a, np.float64)), rtol=0, atol=1e-4)


def plain_prep_bwd(k, v, gamma, beta, t, du, dw, chunk):
    """``(dk, dv, dG, dbeta)`` by jax's own transposes of :func:`gd._apply`
    and :func:`gd._tiles` around the inverse's cotangent ``-T^T dT T^T``:
    the preparation's backward in plain jax, ``gated_delta_bwd_prep``'s
    reference."""
    _, through_t = jax.vjp(functools.partial(gd._apply, chunk=chunk), t, k, v,
                           gamma, beta)
    dt, dk1, dv, dg1, db1 = through_t((du, dw))
    t_t = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(t_t, dt, preferred_element_type=jnp.float32)
                     .astype(t.dtype), t_t,
                     preferred_element_type=jnp.float32)
    _, through_a = jax.vjp(functools.partial(gd._tiles, chunk=chunk), k,
                           gamma, beta)
    dk2, dg2, db2 = through_a(da)
    return dk1 + dk2, dv, dg1 + dg2, db1 + db2


PREP_BWD_SHAPES = {
    # (S, key heads, value heads, chunk)
    **{f"chunk-{c}-rep-{r}": (2 * c, 2, 2 * r, c)
       for c in (8, 64, 128) for r in (1, 2)},
    "ragged-S": (37, 2, 4, 8),
}


@pytest.mark.parametrize("shape", PREP_BWD_SHAPES.values(),
                         ids=PREP_BWD_SHAPES.keys())
def test_the_preparation_backward_kernel_against_plain_transposes(shape):
    """``gated_delta_bwd_prep`` (through ``_prep``'s own gradient) gives the
    four gradients plain jax's transposes give from the same ``T``; a
    ragged ``S`` is padded as :func:`gated_delta_rule` pads it."""
    s, hk, hv, chunk = shape
    _, k, v, g, beta = operands(1, s, hk, hv, 16, 8, seed=s + hv,
                                half_life=(2.0, 64.0))
    s_pad = -(-s // chunk) * chunk
    k, v, g, beta = (jnp.pad(x, ((0, 0), (0, s_pad - s))
                             + ((0, 0),) * (x.ndim - 2))
                     for x in (k, v, g, beta))
    gamma = chunk_log_decay(g, chunk)
    (u, w), pull = jax.vjp(lambda *a: gd._prep(*a, chunk, True), k, v, gamma,
                           beta)
    keys = jax.random.split(jax.random.PRNGKey(s), 2)
    du, dw = (jax.random.normal(key, x.shape) for key, x in zip(keys, (u, w)))
    t = gd._prep_call(k, v, gamma, beta, chunk, True)[2]
    want = plain_prep_bwd(k, v, gamma, beta, t, du, dw, chunk)
    for name, got, ref, x in zip(("dk", "dv", "dG", "dbeta"), pull((du, dw)),
                                 want, (k, v, gamma, beta)):
        assert got.shape == x.shape and got.dtype == x.dtype, name
        np.testing.assert_allclose(got, ref, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(ref).max()),
                                   err_msg=name)


def test_three_bf16_passes_hold_a_float32_product():
    a, b = (jax.random.normal(jax.random.PRNGKey(i), (64, 64)) for i in (0, 1))
    want = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    one = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(gd._dot3(a, b) - want).max()) < 1e-4 * scale
    assert float(jnp.abs(one - want).max()) > 1e-3 * scale


def test_the_kernels_take_no_tile_and_no_state_per_position():
    """Neither a ``[Q, Q]`` tile a chunk nor a state a position is an operand
    of either ``pallas_call`` of the recurrence: the largest is the
    chunk-start states."""
    bsz, s, hk, hv, dk, dv, chunk = 1, 64, 1, 2, 16, 16, 16
    args = operands(bsz, s, hk, hv, dk, dv)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: gated_delta_rule(
        *a, chunk=chunk)[0].sum(), argnums=(0, 1, 2, 3, 4)))(*args)
    calls = [e for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert sorted(_name(e) for e in calls) == [
        "gated_delta_bwd", "gated_delta_bwd_prep", "gated_delta_fwd",
        "gated_delta_fwd_prep"]
    states = bsz * (s // chunk) * hv * dk * dv
    for e in calls:
        # the preparation's pair: the forward writes T, its backward reads it
        if _name(e) in ("gated_delta_fwd_prep", "gated_delta_bwd_prep"):
            continue
        for var in (*e.invars, *e.outvars):
            assert math.prod(var.aval.shape) <= max(states,
                                                    bsz * s * hv * dk), var
            assert var.aval.shape[-2:] != (chunk, chunk)


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub)


def _name(eqn):
    meta = eqn.params.get("metadata") or {}
    return eqn.params.get("name") or meta.get("name") or str(
        eqn.params.get("name_and_src_info", "")).split(" ")[0]


def test_the_block_of_heads_holds_whole_key_groups():
    assert gd._block_h(32, 2, 8) == 8
    assert gd._block_h(6, 2, 4) == 2
    assert gd._block_h(4, 4, 2) == 4
    assert gd._block_h(3, 1, 8) == 3
