"""What the hybrid state-space decoders share (``phi4flash.py``: Mamba-1
layers over ``ops/selective_scan.py``; ``granite_hybrid.py``: Mamba-2 layers
over ``ops/ssd_scan.py``): their bias-free projection, a Mamba layer's draw of
``dt_bias``, a layer's counter, the weight-decay mask."""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp


def dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name,
                    kernel_init=nn.initializers.normal(0.02))


def count(module: nn.Module, name: str, value):
    """One layer's reading of a counter; the model's ``apply_with_counters``
    folds the layers' readings (``lm_loss.folded_counters``)."""
    module.sow("counters", name,
               jax.lax.stop_gradient(value.astype(jnp.float32)))


def dt_bias_init(key, shape):
    """``softplus^-1(dt0)``, ``dt0`` log-uniform in [1e-3, 1e-1]."""
    dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    return dt0 + jnp.log(-jnp.expm1(-dt0))


def decay_mask(params, also: tuple = ()):
    """True for the leaves weight decay touches: the matrices and the
    embedding (``optax.adamw(..., mask=decay_mask)``), and the leaves named
    in ``also`` (a routed model's expert stacks); none on norms, ``A_log``,
    ``D``, biases, the convolution's taps, the ``lambda`` vectors."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) in (
            "kernel", "embedding", *also),
        params)
