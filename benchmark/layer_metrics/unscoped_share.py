"""Share (%) of the traced steady window that the first chip spends in
operations of the step program that carry no scope at all: no
``utils.scopes`` name, no flax module, nothing but the jit wrappers, or no
metadata (the compiler's own copies, relayout loops and waits on its
asynchronous copies; instructions it writes in place of the program's, such
as the custom calls of a grouped product; program code traced outside every
module and scope).
Self time (a loop counts what its body does not), joined to the program's
own table of its step by ``harness/scope_time.py``; None where that join
fails. What reads here has no owner yet: name it before optimising it."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, (scope_time.UNSCOPED,))
