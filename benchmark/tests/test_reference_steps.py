"""``run_steps`` steps the reference in the memory a training step costs, and
reads what it read before: against an undonated loop written out here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from harness import loader
from harness.reference_run import (flat, jitted_calls, leaf_norms,
                                   make_weights, run_steps)
from harness.traffic import make_pool

CELL = "bert-base.finetune-s384-b32"      # Adam: two trees of state


def _tiny(cell=CELL, seed=11):
    res = tiny.tiny_job(cell)["resolved"]
    cfg, traffic = res["config"], res["traffic"]
    ref = loader.load_module(*res["files"]["reference"])
    weights = jax.tree_util.tree_map(np.asarray, make_weights(ref, cfg, seed))
    return ref, cfg, weights, make_pool(traffic, cfg, seed, 1)[:3]


def _undonated(ref, cfg, weights, batches, precision="float32", rows=None,
               frozen=False):
    """The loop as it stood before PR 33: nothing donated, the change taken
    on the device from a second copy of the parameters."""
    with jax.default_matmul_precision("highest"):
        params0 = ref.trainable(weights)
        grad = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss_fn(cfg, p, b, precision)))
        update = jax.jit(lambda p, g, o, i: ref.opt_update(cfg, p, g, o, i))
        params, opt = params0, ref.opt_init(cfg, params0)
        losses, g1 = [], None
        for i, b in enumerate(batches):
            b = {k: jnp.asarray(v[rows] if rows is not None else v)
                 for k, v in b.items()}
            loss, g = grad(params, b)
            losses.append(float(loss))
            if i == 0:
                g1 = leaf_norms(g)
                if frozen:
                    g1 = dict.fromkeys(g1, 0.0)
            if not frozen:
                params, opt = update(params, g, opt, jnp.float32(i + 1))
        delta = jax.tree_util.tree_map(lambda a, b_: a - b_, params, params0)
        return {"losses": losses, "grad1": g1, "delta": leaf_norms(delta)}


def test_the_update_donates_the_parameters_and_the_optimizers_state():
    ref, cfg, weights, _ = _tiny()
    params = ref.trainable(weights)
    opt = ref.opt_init(cfg, params)
    _, update = jitted_calls(ref, cfg)
    p, g, o, i = update.lower(params, params, opt, jnp.float32(1)).args_info[0]
    given = jax.tree_util.tree_leaves
    assert all(a.donated for a in given(p)) and given(p)
    assert all(a.donated for a in given(o)) and len(given(o)) == 2 * len(given(p))
    # no output could take the gradient's buffer: its caller drops it
    assert not any(a.donated for a in given(g)) and not i.donated


@pytest.mark.parametrize("kw", [{}, {"frozen": True},
                                {"rows": slice(0, 4)}, {"precision": "fp8"}],
                         ids=["plain", "frozen", "rows", "fp8"])
def test_run_steps_reads_what_the_undonated_loop_reads(kw):
    ref, cfg, weights, batches = _tiny()
    before = jax.tree_util.tree_map(np.copy, weights)
    got = run_steps(ref, cfg, weights, batches, **kw)
    want = _undonated(ref, cfg, weights, batches, **kw)
    assert got["losses"] == want["losses"]
    assert got["grad1"] == want["grad1"]
    assert list(got["delta"]) == list(want["delta"])
    for k, v in want["delta"].items():
        assert got["delta"][k] == pytest.approx(v, rel=1e-12, abs=1e-30), k
    # the caller's weights are the caller's still, on the host or the device
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(weights)):
        np.testing.assert_array_equal(a, b)


def test_device_weights_survive_a_donated_run():
    res = tiny.tiny_job("resnet50.train-b128")["resolved"]
    ref = loader.load_module(*res["files"]["reference"])
    cfg = res["config"]
    weights = make_weights(ref, cfg, 3)          # on the device
    batches = make_pool(res["traffic"], cfg, 3, 1)[:3]
    got = run_steps(ref, cfg, weights, batches)
    again = run_steps(ref, cfg, weights, batches)   # nothing was deleted
    assert got == again
    assert max(got["delta"].values()) > 0
    assert set(got["delta"]) == set(flat(ref.trainable(weights)))
