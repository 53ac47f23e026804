"""Share (%) of the traced steady window that the first chip spends under the
scopes ``moe_router``, ``moe_dispatch`` and ``moe_combine``: what a routed
layer costs OUTSIDE its grouped products (scores and top-k, the sort plan,
the row gathers into expert order and back, the weighted sum over picks),
forward, recomputation and backward together. ``moe_experts`` is innermost
where the products run and is never inside this share
(``moe_experts_share``). Self time by the innermost registered scope
(``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(
        ctx, ("moe_router", "moe_dispatch", "moe_combine"))
