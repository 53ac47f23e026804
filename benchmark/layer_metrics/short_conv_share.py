"""Share (%) of the traced steady window that the first chip spends under the
scope ``short_conv``: the gated short convolution's ``in_proj``, its taps and
gates and its ``out_proj``, forward, recomputation and backward together.
Self time by the innermost registered scope (``harness/scope_time.py``);
None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("short_conv",))
