"""How uneven the held experts' load is: tokens of the busiest held expert
over tokens of the mean one (each summed over the routed layers), the median
over ``fit``'s ``step_metrics`` events of the traced stretch. 1 is even; the
grouped products' tiles and, across chips, the exchange wait for the
busiest."""

from harness import step_metrics


def read(ctx: dict):
    return step_metrics.median_ratio(
        ctx, "moe_held_load_max", "moe_held_load_mean")
