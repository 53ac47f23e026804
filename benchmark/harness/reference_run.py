"""Drive a plain reference through the first steps of a run.

The reference module gives ``init_weights``, ``trainable``, ``loss_fn``,
``opt_init`` and ``opt_update``; this file owns the loop, so that every
configuration's reference is stepped and read alike: each step's loss, the
first gradient, and the parameters' change after the last step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    s = abs(int(seed))
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)


def make_weights(ref, cfg: dict, seed: int):
    """The configuration's weights from the seed, on the device, in float32,
    in one jitted call."""
    return jax.jit(lambda k: ref.init_weights(cfg, k))(seed_key(seed))


def flat(tree) -> dict:
    """``{"a/b/c": leaf}`` of a nested dict."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                     for k in path)] = leaf
    return out


def leaf_norms(tree) -> dict:
    """The 2-norm of every leaf, in float64 on the host."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in flat(tree).items()}


def run_steps(ref, cfg: dict, weights, batches: list, *,
              precision: str = "float32", rows=None,
              frozen: bool = False) -> dict:
    """Step the reference over ``batches`` from ``weights``.

    ``rows``: a slice of each batch's rows to keep (the planted fault "half
    of the batch left out, the mean taken over the rest"); None keeps all.
    Returns the losses, the leaf norms of the first gradient and of the
    parameters' change after the last step."""
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
                if frozen:   # an optimizer state that never changes holds none
                    g1 = dict.fromkeys(g1, 0.0)
            if not frozen:
                params, opt = update(params, g, opt, jnp.float32(i + 1))
        delta = jax.tree_util.tree_map(lambda a, b_: a - b_, params, params0)
        return {"losses": losses, "grad1": g1, "delta": leaf_norms(delta)}
