"""Share (%) of the traced steady window that the first chip spends under the
scope ``moe_experts``: what the held experts do BETWEEN their grouped products
(the ``silu(a) * b`` fusions, the weights' casts and transposes), forward,
recomputation and backward together. The products themselves are not in it
on this chip: the compiler writes ``ragged_dot`` as custom calls named
``ragged-dot-none.N`` whose ``op_name`` keeps no path, so they read under
``unscoped_share`` (PERF.md section 5); a grouped-product kernel opened under
this scope would move its time here. Self time by the innermost registered
scope (``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("moe_experts",))
