"""The comparison that decides ``correct`` for a training cell.

Both sides give, for the first steps of a run: each step's loss, the 2-norm
of every leaf of the first gradient, and the 2-norm of every leaf of the
parameters' change after the last step. The numbers compared:

    loss_<i>     |program - reference| / |reference|, for each step
    grad1_leaf   the worst leaf's gap of gradient norms
    delta_leaf   the worst leaf's gap of the norms of the change
    grad1_all    the gap of the first gradient's norm over all leaves together
    delta_all    the same of the change

Every one is computed and printed; a cell's ``limits/`` file names those that
decide ``correct`` (PERF.md gives the readings each choice rests on).

A leaf's gap is ``|program's norm - reference's norm|`` over the reference's
norm of that leaf or of the median leaf, whichever is larger: some gradients
are all but zero. Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone under Adam, and are left out of
``delta_leaf`` by that rule, not by name.
"""

from __future__ import annotations

import math
import statistics


def worst_leaf(prog: dict, ref: dict, skip=()) -> tuple:
    """(gap, leaf) of the leaf whose norms differ most. A leaf the program
    lacks, or a norm that is not finite, reads infinity."""
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        p = prog.get(name)
        if p is None or not math.isfinite(p):
            return math.inf, name
        gap = abs(p - r) / max(r, med, 1e-300)
        if gap > worst:
            worst, at = gap, name
    return worst, at


def all_leaves_gap(prog: dict, ref: dict, skip=()) -> float:
    """The gap of the norm over all leaves together: steady from seed to seed
    where one small leaf's gap is not."""
    def total(d):
        return math.sqrt(sum(d.get(k, math.inf) ** 2 for k in ref
                             if k not in skip))
    gap = abs(total(prog) - total(ref)) / total(ref)
    return gap if math.isfinite(gap) else math.inf


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Every leaf's gap, for looking at how the gaps spread over the leaves."""
    med = statistics.median(ref.values())
    return {k: abs(prog.get(k, math.inf) - r) / max(r, med, 1e-300)
            for k, r in ref.items()}


def gap_stats(prog: dict, ref: dict, skip=()) -> dict:
    """How the leaves' gaps spread: worst, 90th and 75th percentile, median,
    and the gap of the norm over all leaves together."""
    gaps = sorted(v for k, v in leaf_gaps(prog, ref).items() if k not in skip)
    n = len(gaps)
    return {"worst": gaps[-1], "p90": gaps[int(0.9 * (n - 1))],
            "p75": gaps[int(0.75 * (n - 1))], "median": gaps[n // 2],
            "all_leaves": all_leaves_gap(prog, ref, skip)}


def dead_leaves(ref_grad: dict) -> set:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v < med * 1e-3}


def training_numbers(prog: dict, ref: dict) -> tuple:
    """``({name: number}, {name: leaf})`` of program against reference."""
    numbers, where = {}, {}
    n = len(ref["losses"])
    for i in range(n):
        lp = prog["losses"][i] if i < len(prog["losses"]) else math.nan
        gap = abs(lp - ref["losses"][i]) / abs(ref["losses"][i])
        numbers[f"loss_{i + 1}"] = gap if math.isfinite(gap) else math.inf
    dead = dead_leaves(ref["grad1"])
    numbers["grad1_leaf"], where["grad1_leaf"] = worst_leaf(
        prog["grad1"], ref["grad1"])
    numbers["delta_leaf"], where["delta_leaf"] = worst_leaf(
        prog["delta"], ref["delta"], skip=dead)
    numbers["grad1_all"] = all_leaves_gap(prog["grad1"], ref["grad1"])
    numbers["delta_all"] = all_leaves_gap(prog["delta"], ref["delta"], dead)
    return numbers, where


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, {name: {"value", "limit"}})``. Every number named in
    ``limits`` has to be there and at or under its limit."""
    compared, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name, math.inf)
        v = v if math.isfinite(v) else 1e30   # JSON has no infinity
        compared[name] = {"value": v, "limit": limit}
        ok = ok and v <= limit
    return ok, compared
