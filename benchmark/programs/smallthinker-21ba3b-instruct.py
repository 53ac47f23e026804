"""The program's side of ``smallthinker-21ba3b-instruct``: what a user of
``ctx.fit`` writes. ``SmallThinkerForCausalLM`` over the configuration's keys
in bf16 with float32 parameters, ``attn_fn`` left at its default
(``"auto"``), ``causal_lm_loss_fn()`` over ``fit``'s ``apply_fn``,
``optax.adamw`` with the model's own ``decay_mask``. ``model_config`` turns
the cut file's keys into the model's own (the model reads a published
``config.json`` and is told which layers, experts and how much of the
vocabulary it holds; the cut is this benchmark's). ``log_every=1``: ``fit``
writes each step's metrics, the model's counters among them, into its event
ring.
"""

from __future__ import annotations


def model_config(cfg: dict):
    """One chip's cut as the model is told it: the router's published width
    (``num_routed_experts``; the file's ``moe_num_primary_experts`` counts
    the experts held), the experts held out of them, the layers of
    ``layers_kept`` by their published indices (the two layouts are copied
    whole), the vocabulary's slice."""
    import dataclasses

    from sparkdl_tpu.models.smallthinker import SmallThinkerConfig

    published = dict(cfg, moe_num_primary_experts=cfg["num_routed_experts"],
                     num_hidden_layers=len(cfg["rope_layout"]))
    return dataclasses.replace(
        SmallThinkerConfig.from_dict(published),
        layers_kept=tuple(cfg["layers_kept"]),
        experts_held=(cfg["first_expert_held"],
                      cfg["moe_num_primary_experts"]),
        vocab_size=cfg["vocab_size"])


def fit_kwargs(cfg: dict, weights: dict) -> dict:
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
    from sparkdl_tpu.models.smallthinker import (SmallThinkerForCausalLM,
                                                 decay_mask)

    model = SmallThinkerForCausalLM(model_config(cfg),
                                    dtype=jnp.dtype(cfg["compute_dtype"]))
    return dict(
        loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
        params={"params": weights["params"]},
        tx=optax.adamw(cfg["learning_rate"], b1=cfg["adam_b1"],
                       b2=cfg["adam_b2"], eps=cfg["adam_eps"],
                       weight_decay=cfg["weight_decay"], mask=decay_mask),
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
