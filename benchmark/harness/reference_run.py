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


def jitted_calls(ref, cfg: dict, precision: str = "float32") -> tuple:
    """``(gradient, update)``: the reference's two jitted calls, built here
    and nowhere else. The update donates the parameters and the optimizer's
    state, so its outputs take their buffers: at the update the device holds
    parameters, gradient and the optimizer's trees once (16 bytes a parameter
    under Adam), as a training step does. The gradient is not donated: no
    output could take its buffer; its caller drops it after the update."""
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss_fn(cfg, p, b, precision)))
    update = jax.jit(lambda p, g, o, i: ref.opt_update(cfg, p, g, o, i),
                     donate_argnums=(0, 2))
    return grad, update


def run_steps(ref, cfg: dict, weights, batches: list, *,
              precision: str = "float32", rows=None,
              frozen: bool = False) -> dict:
    """Step the reference over ``batches`` from ``weights``.

    ``rows``: a slice of each batch's rows to keep (the planted fault "half
    of the batch left out, the mean taken over the rest"); None keeps all.
    Returns the losses, the leaf norms of the first gradient and of the
    parameters' change after the last step. ``weights`` are left as they
    were: the parameters stepped are a device copy of their host values, and
    the change is taken on the host."""
    with jax.default_matmul_precision("highest"):
        params0 = flat(jax.device_get(ref.trainable(weights)))
        grad, update = jitted_calls(ref, cfg, precision)
        # copies: a donated buffer is the caller's no longer, and a state
        # whose trees share their zeros cannot be donated twice
        params = jax.tree_util.tree_map(jnp.array, ref.trainable(weights))
        opt = jax.tree_util.tree_map(jnp.array, ref.opt_init(cfg, params))
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
            del g    # or it would wait on the device through the next call
        # the change is taken on the host, leaf by leaf: a second copy of the
        # parameters on the device is memory a large cell does not have
        delta = leaf_norms({k: np.asarray(v) - params0[k]
                            for k, v in flat(params).items()})
        return {"losses": losses, "grad1": g1, "delta": delta}
