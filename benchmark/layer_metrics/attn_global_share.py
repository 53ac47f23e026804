"""Share (%) of the traced steady window that the first chip spends under the
scope ``attn_global``: the attention of the layers that see the whole prefix,
all but its flash kernels, which open scopes of their own: the four
projections (and the rotary turn of q and k where the layer has one), the
key/value heads' repeat and the output projection, forward, recomputation and
backward together. Self time by the innermost registered scope
(``harness/scope_time.py``); None where that join fails or no operation ran
under the scope (a program that opens none)."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("attn_global",))
