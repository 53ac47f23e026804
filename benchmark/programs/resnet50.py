"""The program's side of ``resnet50``: what a user of ``ctx.fit`` writes.

``models/resnet.py`` ResNet50 through ``get_model``, bf16 compute with float32
parameters, BatchNorm state threaded (``mutable=True``), ``bn_classifier_loss``
with the registry's own preprocessing, ``optax.sgd`` with momentum.
"""

from __future__ import annotations


def fit_kwargs(cfg: dict, weights: dict) -> dict:
    """Keyword arguments of ``ctx.fit`` for this configuration, from weights
    in the checkpoint's own layout (host numpy)."""
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.registry import get_model
    from sparkdl_tpu.runner import bn_classifier_loss

    assert cfg["preprocess"] == "caffe"
    dtype = jnp.dtype(cfg["compute_dtype"])
    spec = get_model("ResNet50")
    model = spec.build(dtype=dtype, num_classes=cfg["num_classes"],
                       stage_sizes=cfg["stage_sizes"], width=cfg["width"],
                       stride_on_3x3=cfg["stride_on_3x3"])
    return dict(
        loss_fn=bn_classifier_loss(model, spec.preprocess),
        params=weights["params"],
        model_state={"batch_stats": weights["batch_stats"]},
        tx=optax.sgd(cfg["learning_rate"], momentum=cfg["momentum"]),
        mutable=True)


def first_gradient(cfg: dict, opt_state):
    """The first gradient as the optimizer got it, from its state after one
    step: the momentum trace starts at zero, so after one step it is g."""
    import optax
    for s in opt_state:
        if isinstance(s, optax.TraceState):
            return s.trace
    raise ValueError(f"no momentum trace in {type(opt_state)}")


def trainable(params):
    """The program's parameter tree, in the reference's layout."""
    return params
