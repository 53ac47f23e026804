"""The share of a step's expert assignments (tokens x experts per token, over
the routed layers) that landed on the experts this chip holds: the median of
``moe_assignments_held / moe_assignments`` over ``fit``'s ``step_metrics``
events of the traced stretch. The expert layer's work goes with it; an even
router over a quarter of the experts reads 25."""

from harness import step_metrics


def read(ctx: dict):
    share = step_metrics.median_ratio(
        ctx, "moe_assignments_held", "moe_assignments")
    return None if share is None else 100.0 * share
