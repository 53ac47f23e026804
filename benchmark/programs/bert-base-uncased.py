"""The program's side of ``bert-base-uncased``: what a user of ``ctx.fit``
writes. ``BertForSequenceClassification`` over ``BertConfig.base()`` in bf16
with float32 parameters, ``attn_fn`` left at its default (``"auto"``),
``bert_finetune_loss``, ``optax.adamw``.
"""

from __future__ import annotations


def fit_kwargs(cfg: dict, weights: dict) -> dict:
    import dataclasses

    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.bert import (BertConfig,
                                         BertForSequenceClassification,
                                         bert_finetune_loss)

    dtype = jnp.dtype(cfg["compute_dtype"])
    bc = dataclasses.replace(
        BertConfig.base(),
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        dropout_rate=cfg["dropout_rate"])
    model = BertForSequenceClassification(
        bc, num_classes=cfg["num_classes"], dtype=dtype)
    # dropout 0.0 draws nothing, so no rng is plumbed (with_rng stays False)
    return dict(
        loss_fn=bert_finetune_loss(model),
        params={"params": weights["params"]},
        tx=optax.adamw(cfg["learning_rate"], b1=cfg["adam_b1"],
                       b2=cfg["adam_b2"], eps=cfg["adam_eps"],
                       weight_decay=cfg["weight_decay"]))


def first_gradient(cfg: dict, opt_state):
    """Adam's first moment starts at zero: after one step mu = (1 - b1) g."""
    import jax
    import optax
    for s in opt_state:
        if isinstance(s, optax.ScaleByAdamState):
            return jax.tree_util.tree_map(
                lambda m: m / (1.0 - cfg["adam_b1"]), s.mu)
    raise ValueError(f"no Adam state in {type(opt_state)}")


def trainable(params):
    """The program's parameter tree, in the reference's layout."""
    return params["params"]
