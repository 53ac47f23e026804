"""Share (%) of the traced steady window that the first chip spends under the
scope ``lm_head_loss``: the tied head's product over the vocabulary, the
log-softmax and the loss, forward and backward together (the scope is a path
component in both). Self time by the innermost registered scope
(``harness/scope_time.py``); None where that join fails."""

from harness import scope_time


def read(ctx: dict):
    return scope_time.share(ctx, ("lm_head_loss",))
