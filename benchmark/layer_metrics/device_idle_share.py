"""Share of the traced steady window in which no operation ran on the chip:
1 - (union of the ``XLA Ops`` intervals) / window, averaged over the chips."""


def read(ctx: dict):
    summary = ctx.get("device_summary")
    if not summary:
        return None
    shares = [1.0 - d["busy_s"] / d["window_s"] for d in summary]
    return 100.0 * sum(shares) / len(shares)
