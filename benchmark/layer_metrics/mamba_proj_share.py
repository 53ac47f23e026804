"""Share (%) of the traced steady window that the first chip spends under the
scopes ``mamba_in_proj`` and ``mamba_out_proj``: a state-space layer's two
matrix products and the split of the first one's output, forward,
recomputation and backward together. The scan kernels' own scopes are
innermost where they run, so their time is never inside this share
(``selective_scan_share``, ``ssd_scan_share``). Self time by the innermost
registered scope (``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("mamba_in_proj", "mamba_out_proj"))
