"""The next-token loss that every causal language model here trains with."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils import scopes


@jax.custom_vjp
def _cotangent_formed_once(x):
    """The identity; ``x``'s cotangent is a value of the program, formed in
    one pass, and not an expression for the compiler to repeat.

    Behind the loss the logits' cotangent, ``(softmax - [v == label]) *
    weight``, has two readers, the head's two backward products. Left to
    itself the TPU compiler folds the whole expression into each of them as
    an operand, exponent and all, and keeps the float32 logits alive for
    both: free at one vocabulary and batch, twice a product's time at
    another, with the rest of the step scheduled around a gigabyte more
    (PERF.md section 6, PR 39)."""
    return x


_cotangent_formed_once.defvjp(
    lambda x: (x, None),
    lambda _, g: (jax.lax.optimization_barrier(g),))


def causal_lm_loss_fn():
    """Next-token loss for RunnerContext.fit: batch = {input_ids} (labels =
    input_ids shifted left; the last position weighs nothing).

    ``fit``'s ``apply_fn(params, ids)`` gives the logits ``[B, S, V]``, or
    ``(logits, counters)`` with ``counters`` a dict of scalars the model
    counted on the way (a routed model's assignments): they ride in the
    step's metrics beside ``perplexity``, and ``fit`` writes them into the
    event ring at every log boundary.

    The logits are read whole, where the head left them. A label's logit is
    picked by comparing an iota over the vocabulary with the label, never by
    an index: the transpose of a select is a select, which fuses into the
    softmax's own pass over the logits' gradient, where the transpose of a
    gather is a scatter into a gradient the compiler lays out anew for it.
    The last position is masked, not sliced off: a slice copies the block.
    That one pass writes the gradient once, for both of its readers."""

    def loss_fn(params, apply_fn, batch):
        ids = batch["input_ids"]
        out = apply_fn(params, ids)
        logits, counters = out if isinstance(out, tuple) else (out, {})
        with scopes.layer("lm_head_loss"):
            logits = _cotangent_formed_once(logits.astype(jnp.float32))
            n_batch, n_pos, n_vocab = logits.shape
            # the last position's label is never read: its weight is 0
            labels = jnp.roll(ids, -1, axis=1)
            is_label = jnp.arange(n_vocab) == labels[..., None]
            picked = jnp.sum(jnp.where(is_label, logits, 0.0), axis=-1)
            nll = jax.nn.logsumexp(logits, axis=-1) - picked
            counted = jnp.arange(n_pos) < n_pos - 1
            loss = (jnp.sum(jnp.where(counted, nll, 0.0))
                    / (n_batch * (n_pos - 1)))
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
