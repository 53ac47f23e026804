"""The program's side of ``lfm2-8b-a1b``: what a user of ``ctx.fit`` writes.
``Lfm2ForCausalLM`` over the configuration's keys in bf16 with float32
parameters, ``attn_fn`` left at its default (``"auto"``),
``causal_lm_loss_fn()`` over ``fit``'s ``apply_fn``, ``optax.adamw``.
``model_config`` turns the cut file's keys into the model's own (the model
reads a published ``config.json`` and is told which experts it holds; the
cut is this benchmark's). ``log_every=1``: ``fit`` writes each
step's metrics, the expert layers' counters among them, into its event ring
(since PR 28 a log boundary drains nothing).
"""

from __future__ import annotations


def model_config(cfg: dict):
    """One chip's share as the model is told it: the layers of
    ``layers_kept`` out of the published ``layer_types``, a router as wide as
    ``num_routed_experts``, and of its experts the ``num_experts`` from
    ``first_expert_held`` on."""
    import dataclasses

    from sparkdl_tpu.models.lfm2 import Lfm2Config

    kinds = [cfg["layer_types"][i] for i in cfg["layers_kept"]]
    whole = Lfm2Config.from_dict(dict(
        cfg, layer_types=kinds, num_experts=cfg["num_routed_experts"]))
    return dataclasses.replace(whole, experts_held=(
        cfg["first_expert_held"], cfg["num_experts"]))


def fit_kwargs(cfg: dict, weights: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.lfm2 import Lfm2ForCausalLM, trainable_mask
    from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn

    model = Lfm2ForCausalLM(model_config(cfg),
                            dtype=jnp.dtype(cfg["compute_dtype"]))
    return dict(
        loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
        params={"params": weights["params"]},
        tx=optax.adamw(cfg["learning_rate"], b1=cfg["adam_b1"],
                       b2=cfg["adam_b2"], eps=cfg["adam_eps"],
                       weight_decay=cfg["weight_decay"], mask=trainable_mask),
        log_every=1)


def first_gradient(cfg: dict, opt_state):
    """Adam's first moment starts at zero: after one step mu = (1 - b1) g.
    Fetched to the host before dividing: a second copy of it on the device,
    beside a step in flight, is memory this cell does not have."""
    import jax
    import optax
    for s in opt_state:
        if isinstance(s, optax.ScaleByAdamState):
            return jax.tree_util.tree_map(
                lambda m: m / (1.0 - cfg["adam_b1"]), jax.device_get(s.mu))
    raise ValueError(f"no Adam state in {type(opt_state)}")


def trainable(params):
    """The program's parameter tree, in the reference's layout."""
    return params["params"]
