"""Share (%) of the traced steady window that the first chip spends under the
scope ``mamba_conv``: a state-space layer's causal convolution taps and what
the model computes of the scan's inputs beside them, forward, recomputation
and backward together. The scan kernels' own scopes are innermost where they
run, so their time is never inside this share. Self time by the innermost
registered scope (``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("mamba_conv",))
