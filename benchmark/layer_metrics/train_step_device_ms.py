"""Median device time of the train step's program (the ``XLA Modules`` event
that takes most of the trace), averaged over the chips."""


def read(ctx: dict):
    summary = ctx.get("device_summary")
    if not summary:
        return None
    return sum(d["step_ms"] for d in summary) / len(summary)
