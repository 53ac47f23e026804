"""Narrow floating-point formats for the references' matrix products.

A reference computes in float32. Its control recomputes the same step with
every matrix product's operands and incoming gradient rounded to a narrower
format, as a low-precision training recipe does: ``"fp8"`` is e4m3 going
forward and e5m2 going backward, one scale per tensor, the step below the
bfloat16 that the configurations state. ``"bf16"`` rounds the same places to
bfloat16: a second witness of what rounding alone does, for looking at a
reading, never for deciding. It imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_FORMATS = {            # exponent bits, mantissa bits, largest finite value
    "e4m3": (4, 3, 240.0), "e5m2": (5, 2, 57344.0), "bf16": (8, 7, None)}
_RECIPES = {            # precision -> (format going forward, going backward)
    "fp8": ("e4m3", "e5m2"), "bf16": ("bf16", "bf16")}


def _q(x, fmt: str):
    """Round to a narrow float, with one scale per tensor where the format's
    range needs it. Through ``reduce_precision``: a pair of casts is something
    the compiler may drop (``xla_allow_excess_precision`` did, for bfloat16)."""
    ebits, mbits, top = _FORMATS[fmt]
    if top is None:
        return jax.lax.reduce_precision(x, ebits, mbits)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return jax.lax.reduce_precision(x / scale, ebits, mbits) * scale


def narrow(product, precision: str):
    """``product(a, b)`` as a narrow training recipe computes it: both operands
    rounded going forward, and going backward the incoming gradient rounded
    against the same rounded operands. ``float32`` leaves it as it is."""
    if precision == "float32":
        return product
    fwd_fmt, bwd_fmt = _RECIPES[precision]

    @jax.custom_vjp
    def f(a, b):
        return product(_q(a, fwd_fmt), _q(b, fwd_fmt))

    def fwd(a, b):
        aq, bq = _q(a, fwd_fmt), _q(b, fwd_fmt)
        return product(aq, bq), (aq, bq)

    def bwd(saved, dy):
        _, vjp = jax.vjp(product, *saved)
        return vjp(_q(dy, bwd_fmt))

    f.defvjp(fwd, bwd)
    return f


def set_leaf(tree: dict, path: str, leaf: str, value):
    """``tree["a"]["b"][leaf] = value`` for ``path`` ``"a/b"``."""
    node = tree
    for part in path.split("/"):
        node = node.setdefault(part, {})
    node[leaf] = value
