"""The whole step's share of the chip's bf16 peak: operations the model needs
(forward and backward, nothing recomputed, from ``flops/<config>.py``) for the
examples of the steps in the traced steady window, over that window's seconds,
the chips and the table's peak."""


def read(ctx: dict):
    summary = ctx.get("device_summary")
    if not summary:
        return None
    d = summary[0]
    flops = ctx["flops_per_example"] * ctx["global_batch"] * d["steps"]
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (d["window_s"] * peak)
