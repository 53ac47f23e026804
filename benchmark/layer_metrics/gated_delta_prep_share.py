"""Share (%) of the traced steady window that the first chip spends under the
scopes ``gated_delta_prep`` and ``gdn_gated_norm``: what a linear-attention
layer costs around its kernel pair -- q and k to unit length, ``beta`` and
``g``, the running sum, a chunk's ``K K^T``, the unit-lower-triangular inverse,
``U`` and ``W``, and behind the kernel the norm a head and its gate --
forward, recomputation and backward together. The kernels' own scopes are
innermost where they run and are never inside this share
(``gated_delta_share``). Self time by the innermost registered scope
(``harness/scope_time.py``); None where that join fails or the program opens
neither scope."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("gated_delta_prep", "gdn_gated_norm"))
