"""The next-token loss that every causal language model here trains with."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils import scopes


def causal_lm_loss_fn():
    """Next-token loss for RunnerContext.fit: batch = {input_ids} (labels =
    input_ids shifted left; last position dropped).

    ``fit``'s ``apply_fn(params, ids)`` gives the logits ``[B, S, V]``, or
    ``(logits, counters)`` with ``counters`` a dict of scalars the model
    counted on the way (a routed model's assignments): they ride in the
    step's metrics beside ``perplexity``, and ``fit`` writes them into the
    event ring at every log boundary."""
    import optax

    def loss_fn(params, apply_fn, batch):
        ids = batch["input_ids"]
        out = apply_fn(params, ids)
        logits, counters = out if isinstance(out, tuple) else (out, {})
        with scopes.layer("lm_head_loss"):
            logits = logits[:, :-1].astype(jnp.float32)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, ids[:, 1:]).mean()
        return loss, {"perplexity": jnp.exp(loss), **counters}

    return loss_fn


def folded_counters(mutated: dict, folds: dict) -> dict:
    """One number a counter out of what the layers of a model sowed into its
    ``counters`` collection (``model.apply(..., mutable=["counters"])[1]``):
    ``folds[name]`` folds the layers' readings of ``name``, stacked."""
    seen: dict = {}
    for path, v in jax.tree_util.tree_flatten_with_path(
            mutated.get("counters", {}))[0]:
        name = next(k.key for k in reversed(path) if hasattr(k, "key"))
        seen.setdefault(name, []).append(v)
    return {k: folds[k](jnp.stack(v)) for k, v in seen.items()}
