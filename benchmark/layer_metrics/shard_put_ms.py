"""Median ``shard_put`` span of ``runner/events.py`` in the traced part of the
window: the host's time inside ``shard_batch`` for one batch."""

import statistics


def read(ctx: dict):
    durs = [s["dur_s"] for s in ctx.get("spans", [])
            if s["name"] == "shard_put"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
