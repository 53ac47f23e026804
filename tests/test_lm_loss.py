"""The next-token loss reads the logits whole and picks each label by
comparison (ISSUE 39): the same loss, ``perplexity`` and gradient as
``optax.softmax_cross_entropy_with_integer_labels(logits[:, :-1], ids[:, 1:])
.mean()``, by a program whose gradient holds no gather, no scatter and no
slice of the logits, and forms the logits' cotangent once. What the chip's
compiler makes of it at the Phi head's
shape is pinned beside the other described-v5e compiles, in
``tests/test_flash_aot.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn


def _draw(b, s, v, seed=0, dtype=jnp.float32):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = (3.0 * jax.random.normal(k1, (b, s, v))).astype(dtype)
    ids = jax.random.randint(k2, (b, s), 0, v, dtype=jnp.int32)
    return logits, ids


def _loss(logits, ids, counters=None):
    """The loss over given logits: ``apply_fn`` hands them back, bare or
    beside a model's counters."""
    def apply_fn(params, got):
        assert got is ids
        return params if counters is None else (params, counters)

    return causal_lm_loss_fn()(logits, apply_fn, {"input_ids": ids})


def _sliced(logits, ids):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1].astype(jnp.float32), ids[:, 1:]).mean()


def _equations(fn, *args):
    """Every equation of ``fn``'s jaxpr, those of its inner jaxprs too."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _primitives(fn, *args):
    return {e.primitive.name for e in _equations(fn, *args)}


# a vocabulary that is a multiple of the 128 lanes, and three that are not
# (25,008, the Phi cell's, is 195 x 128 + 48)
@pytest.mark.parametrize("b, s, v", [
    (2, 16, 256), (2, 16, 200), (3, 9, 136), (1, 8, 128), (2, 2, 7)])
def test_loss_perplexity_and_gradient_equal_the_sliced_gather_loss(b, s, v):
    logits, ids = _draw(b, s, v, seed=b * s + v)
    (loss, aux), grad = jax.value_and_grad(
        lambda l: _loss(l, ids), has_aux=True)(logits)
    want, want_grad = jax.value_and_grad(_sliced)(logits, ids)
    assert loss.dtype == jnp.float32 and grad.shape == (b, s, v)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(aux["perplexity"], jnp.exp(want), rtol=2e-6)
    assert set(aux) == {"perplexity"}
    # entries are up to 1 / (b (s - 1)): float32 rounding of those
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5,
                               atol=1e-7 / (b * (s - 1)))
    # the last position weighs nothing, exactly
    assert not np.any(np.asarray(grad[:, -1]))
    assert np.all(np.any(np.asarray(grad[:, :-1]) != 0, axis=-1))


def test_bfloat16_logits_are_summed_in_float32():
    logits, ids = _draw(2, 16, 200, seed=3, dtype=jnp.bfloat16)
    loss, _ = _loss(logits, ids)
    assert loss.dtype == jnp.float32
    np.testing.assert_allclose(loss, _sliced(logits, ids), rtol=2e-6)


def test_a_models_counters_ride_beside_perplexity():
    logits, ids = _draw(2, 8, 136)
    counters = {"moe_dropped": jnp.int32(0), "ssm_dt_mean": jnp.float32(0.5)}
    loss, aux = _loss(logits, ids, counters)
    assert set(aux) == {"perplexity", "moe_dropped", "ssm_dt_mean"}
    assert aux["moe_dropped"] is counters["moe_dropped"]
    assert aux["ssm_dt_mean"] is counters["ssm_dt_mean"]
    np.testing.assert_allclose(aux["perplexity"], jnp.exp(loss), rtol=1e-6)


def test_the_last_positions_label_cannot_matter():
    """The shift wraps the first id round to the last position, whose weight
    is 0: two batches that differ in the first id alone, over the same
    logits, give one loss and one gradient, bit for bit."""
    logits, ids = _draw(2, 16, 200, seed=5)
    other = ids.at[:, 0].set((ids[:, 0] + 1) % 200)
    assert np.any(np.asarray(other) != np.asarray(ids))
    a, ga = jax.value_and_grad(lambda l: _loss(l, ids)[0])(logits)
    b, gb = jax.value_and_grad(lambda l: _loss(l, other)[0])(logits)
    assert np.asarray(a) == np.asarray(b)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
    # and a label that does matter moves it
    moved = ids.at[:, 1].set((ids[:, 1] + 1) % 200)
    assert np.asarray(_loss(logits, moved)[0]) != np.asarray(a)


def test_a_last_position_that_overflows_stays_out_of_loss_and_gradient():
    """Masked by a select, not a product by zero: ``inf`` logits at the last
    position (``logsumexp`` = inf there) leave the loss finite."""
    logits, ids = _draw(1, 8, 136, seed=7)
    wild = logits.at[:, -1].set(jnp.inf)
    np.testing.assert_array_equal(np.asarray(_loss(wild, ids)[0]),
                                  np.asarray(_loss(logits, ids)[0]))


def test_the_gradients_program_indexes_nothing():
    """The mechanism, where no chip is needed: the gradient with respect to
    the logits is selects, reductions and elementwise passes. The sliced
    gather loss holds ``gather``, ``scatter-add`` (its label pick and that
    pick's transpose, which on the chip became a relayout of the whole
    float32 gradient: PERF.md section 6, PR 39) and ``pad`` (the slice's)."""
    logits, ids = _draw(2, 16, 200)
    banned = {"gather", "scatter", "scatter-add", "dynamic_slice",
              "dynamic_update_slice", "while", "scan", "pad"}
    mine = _primitives(jax.grad(lambda l: _loss(l, ids)[0]), logits)
    assert not mine & banned, mine & banned
    assert {"iota", "eq", "select_n"} <= mine
    # the yardstick sees what it is meant to see
    theirs = _primitives(jax.grad(_sliced), logits, ids)
    assert {"gather", "scatter-add", "pad"} <= theirs


def test_the_logits_cotangent_is_a_value_formed_once():
    """The head's two backward products read one cotangent: the gradient's
    program fences it (``optimization_barrier``) at the logits' own shape and
    type, once, so the compiler cannot fold its expression, exponent and
    all, into each reader (``lm_loss._cotangent_formed_once``)."""
    logits, ids = _draw(2, 16, 200)
    fences = [e for e in _equations(jax.grad(lambda l: _loss(l, ids)[0]),
                                    logits)
              if e.primitive.name == "optimization_barrier"]
    assert len(fences) == 1
    (var,) = fences[0].outvars
    assert (var.aval.shape, var.aval.dtype) == (logits.shape, jnp.float32)
    # the loss alone, with no gradient asked for, holds no fence
    assert "optimization_barrier" not in _primitives(
        lambda l: _loss(l, ids)[0], logits)


def test_no_value_of_the_gradients_program_has_one_row_fewer():
    """Every pass runs over the ``S`` rows the head wrote: nothing of the
    logits' size is sliced to ``S - 1`` (no tile divides 8,191)."""
    b, s, v = 2, 16, 200
    logits, ids = _draw(b, s, v)
    seen = {tuple(var.aval.shape)
            for e in _equations(
                jax.value_and_grad(lambda l: _loss(l, ids)[0]), logits)
            for var in e.outvars}
    assert (b, s, v) in seen
    assert not any(len(sh) == 3 and sh[1] == s - 1 for sh in seen), seen
