"""Device time under the program's own scopes, for the by-scope readers.

The trace the driver hands a reader names an operation ``fusion.106`` and no
more (``trace.read_xplane`` keeps name, start and duration). The program knows
which scope each operation of its step program was traced under
(``sparkdl_tpu.runner.analysis.step_program_scopes``: the table ``fit`` left
the means to build), so the two are joined here by the operation's name and
handed to the program's one classifier, ``analysis.scope_seconds``: each
operation's self time (a loop counts what its body does not) under the
innermost registered scope of its path, forward, recomputation and backward
together.

A join that fails must never read as a number: where the program offers no
table (an older program, a step that is no jit function), the trace has no
steady window, or the operations the table does not know are worth more than
``MISSING_LIMIT`` of the window's busy time, every reader here returns None.
"""

from __future__ import annotations

import sys

from harness import trace as trace_lib

MISSING_LIMIT = 0.01
UNSCOPED = "(unscoped)"     # scope_seconds' key for an operation under none
_MEMO = "scope_time"        # the key of ``ctx`` that holds a run's join


def seconds_by_name(ctx: dict):
    """``({scope name: seconds}, window seconds)`` of the first chip's
    ``XLA Ops`` line inside its steady window, or None (see above). Joined
    once a run: the readers share it through ``ctx``."""
    if _MEMO not in ctx:
        ctx[_MEMO] = _join(ctx)
    return ctx[_MEMO]


def _join(ctx: dict):
    planes = trace_lib.device_planes(ctx.get("trace") or {})
    if not planes:
        return None
    plane = next(iter(planes.values()))
    win = trace_lib.steady_window(plane)
    if win is None:
        return None
    lo, hi, _ = win
    try:
        from sparkdl_tpu.core import runtime
        from sparkdl_tpu.runner import analysis
        from sparkdl_tpu.utils import scopes
        build = analysis.step_program_scopes
    except (ImportError, AttributeError):
        return None             # a program from before the table
    cache0 = runtime.persistent_cache_stats()
    table = build()
    if table is None:
        return None
    cache1 = runtime.persistent_cache_stats()
    triples, missing_ns = [], 0.0
    for name, s, d in plane[trace_lib.OPS_LINE]:
        if not lo <= s < hi:
            continue
        d = min(s + d, hi) - s
        op_name = table.get(trace_lib.short_name(name))
        if op_name is None:
            missing_ns += d
            op_name = ""
        triples.append((op_name, s, d))
    busy_s = trace_lib.union_seconds(
        ((s, s + d) for _, s, d in triples), lo, hi)
    missing = missing_ns / 1e9 / busy_s if busy_s else 1.0
    print("scope_time: %d instructions in the step program's table, built "
          "in %.2f s (compile cache %+d hits, %+d misses); %.3f%% of %.4f s "
          "busy is of operations it does not know" % (
              len(table), analysis.step_program_build_s() or 0.0,
              cache1["hits"] - cache0["hits"],
              cache1["misses"] - cache0["misses"], 100 * missing, busy_s),
          file=sys.stderr)
    if missing > MISSING_LIMIT:
        return None
    rep = analysis.scope_seconds(triples, names=scopes.names())
    return rep["by_name"], (hi - lo) / 1e9


def share(ctx: dict, names):
    """Share (%) of the steady window that the first chip spends in
    operations whose innermost registered scope is one of ``names``. None
    where :func:`seconds_by_name` has none, or no such operation ran."""
    got = seconds_by_name(ctx)
    if got is None:
        return None
    by_name, window_s = got
    seconds = sum(by_name.get(n, 0.0) for n in names)
    if seconds <= 0:
        return None
    return 100.0 * seconds / window_s
