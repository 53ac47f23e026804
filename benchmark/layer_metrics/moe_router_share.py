"""Share (%) of the traced steady window that the first chip spends under the
scope ``moe_router``: a routed layer's scores over every expert in float32 at
``highest`` and its top k, forward, recomputation and backward together (it
is inside ``moe_dispatch_combine_share`` too, which adds the row movement). At
32 outputs and a top 4 it is a thousandth of the layer; at 512 and a top 10 it
is not. Self time by the innermost registered scope
(``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("moe_router",))
