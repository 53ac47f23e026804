"""Bottleneck attribution over span streams and telemetry snapshots
(ISSUE 6, layer 3).

The streamed scorer delivers 82–287 img/s against a 2541 img/s device
roofline (ROADMAP item 2); the spans from PR 3 can prove exactly *where*
the wall time goes, but until now proving it meant hand-jq'ing raw JSONL.
This module turns a span stream — the flight recorder's ring tail, a rank's
``events_rank{i}.jsonl``, or a whole event dir — into a per-stage
utilization breakdown:

- **busy_s** — summed span durations (slot-seconds; two pool workers busy
  one wall second contribute 2.0);
- **wall_busy_s** — the union of the stage's active intervals (wall
  seconds during which >= 1 span of the stage was open);
- **busy_frac** — wall-busy over the stream's elapsed wall: the
  bottleneck signal, in [0, 1] by construction;
- **exclusive_s** — wall seconds during which ONLY this stage was active
  (a timeline sweep across all stages): the Amdahl-relevant quantity —
  eliminating the stage entirely saves at most its exclusive time;
- **idle_s** — wall seconds where *no* stage was active (gaps the spans
  do not explain: GC, scheduling, untraced work).

Beside the host's spans, :func:`device_time_by_scope` reads a profile's
device plane: seconds per ``jax.named_scope`` / flax module prefix and per
phase (forward, backward, ``optimizer_update``), so that ``fusion.106`` has
a name. ``python -m sparkdl_tpu.runner.analysis --profile DIR`` prints it,
and with ``--named`` the seconds under each of the program's own scopes
(``utils.scopes``). :func:`step_program_scopes` gives the same names to a
trace that lost them: the scope of every operation of the step program the
newest ``fit`` ran, by the operation's name.

Attribution names the **dominant stage** (highest busy fraction) and the
Amdahl-style projection: with the dominant stage wall-busy fraction f,
perfecting everything else yields at most **1/f** speedup ("decode pool
94% busy → ≤1.06x from fixing anything else") — so effort goes where the
time actually is. Stdlib-only; ``scripts/bottleneck_report.py`` is the
CLI over it.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Iterable

__all__ = ["intervals_from_events", "read_span_stream", "load_event_dir",
           "union_seconds", "analyze", "utilization_from_events",
           "format_report", "request_summary", "format_request_summary",
           "scope_seconds", "device_time_by_scope", "format_scope_report",
           "named_scope_of", "hlo_scopes", "note_step_program",
           "step_program_scopes"]

_EVENT_FILE_RE = re.compile(r"events_rank(\d+)\.jsonl$")
# Span names that are not pipeline *stages*: whole-run envelopes whose
# duration would swamp every real stage's busy fraction.
_NON_STAGE_SPANS = frozenset({"eval", "serve_request"})


def read_span_stream(path: str) -> list[dict]:
    """All records of one ``events_rank*.jsonl`` file (full read — this is
    the offline analysis tool, not the supervisor's bounded tail)."""
    recs = []
    with open(path, "rb") as f:
        for line in f:
            try:
                recs.append(json.loads(line))
            except ValueError:
                continue  # torn tail line from a killed rank
    return recs


def load_event_dir(event_dir: str) -> list[dict]:
    """Every rank's span stream under ``event_dir``, merged — plus the
    NEWEST non-empty ``gang-*/`` subdir supervised gangs stream into.
    Newest only, the same rule as ``telemetry.aggregate_snapshots``: a
    reused SPARKDL_EVENT_DIR accumulates one kept gang-* subdir per
    supervise() run, and merging unrelated runs into one timeline would
    turn the gap between them into fictitious idle time and collapse
    every busy fraction."""
    recs: list[dict] = []
    try:
        names = sorted(os.listdir(event_dir))
    except OSError:
        return recs
    for fn in names:
        if _EVENT_FILE_RE.match(fn):
            try:
                recs.extend(read_span_stream(os.path.join(event_dir, fn)))
            except OSError:
                continue
    gang_dirs = [os.path.join(event_dir, fn) for fn in names
                 if fn.startswith("gang-")
                 and os.path.isdir(os.path.join(event_dir, fn))]
    try:
        gang_dirs.sort(key=os.path.getmtime, reverse=True)
    except OSError:
        pass
    for gd in gang_dirs:
        gang_recs = load_event_dir(gd)
        if gang_recs:
            recs.extend(gang_recs)
            break
    return recs


def intervals_from_events(events: Iterable[dict]) -> dict[str, list]:
    """stage → [(t0, t1, rows, bytes), ...] from span END records (the E
    event carries ``t`` and ``dur_s``, so t0 = t - dur_s; B events are
    not needed and a stream truncated mid-span degrades gracefully)."""
    out: dict[str, list] = {}
    for r in events:
        if r.get("ph") != "E":
            continue
        dur = r.get("dur_s")
        name = r.get("name")
        if not isinstance(name, str) or name in _NON_STAGE_SPANS \
                or not isinstance(dur, (int, float)) or dur < 0:
            continue
        t1 = r.get("t")
        if not isinstance(t1, (int, float)):
            continue
        out.setdefault(name, []).append(
            (t1 - dur, t1, int(r.get("rows") or 0),
             int(r.get("bytes") or 0)))
    return out


def union_seconds(intervals: list) -> float:
    """Total length of the union of (t0, t1, ...) intervals."""
    if not intervals:
        return 0.0
    ivs = sorted((iv[0], iv[1]) for iv in intervals)
    total = 0.0
    cur0, cur1 = ivs[0]
    for t0, t1 in ivs[1:]:
        if t0 > cur1:
            total += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    return total + (cur1 - cur0)


def _sweep(per_stage: dict[str, list]) -> tuple[dict[str, float], float]:
    """Timeline sweep over all stages' intervals → (exclusive seconds per
    stage, idle seconds). A slice of wall time is *exclusive* to a stage
    when that stage alone is active; *idle* when none is."""
    points: list[tuple[float, int, str]] = []
    for name, ivs in per_stage.items():
        for iv in ivs:
            points.append((iv[0], +1, name))
            points.append((iv[1], -1, name))
    if not points:
        return {}, 0.0
    points.sort(key=lambda p: (p[0], -p[1]))  # opens before closes at ties
    active: dict[str, int] = {}
    exclusive = {name: 0.0 for name in per_stage}
    idle = 0.0
    prev_t = points[0][0]
    for t, delta, name in points:
        dt = t - prev_t
        if dt > 0:
            live = [s for s, n in active.items() if n > 0]
            if len(live) == 1:
                exclusive[live[0]] += dt
            elif not live:
                idle += dt
        prev_t = t
        active[name] = active.get(name, 0) + delta
    return exclusive, idle


def analyze(events: Iterable[dict] | None = None,
            event_dir: str | None = None) -> dict | None:
    """Per-stage utilization breakdown + bottleneck attribution.

    Pass raw records (``events``) or a directory of per-rank streams
    (``event_dir``). Returns None when no spans are found. The report is
    internally consistent by construction: every ``busy_frac`` is a
    clamped interval-union over the measured wall, exclusive+overlap
    never exceeds wall, and ``idle_s`` is what the spans leave
    unexplained.
    """
    if events is None:
        events = load_event_dir(event_dir) if event_dir else []
    events = list(events)
    per_stage = intervals_from_events(events)
    if not per_stage:
        return None
    t_begin = min(iv[0] for ivs in per_stage.values() for iv in ivs)
    t_end = max(iv[1] for ivs in per_stage.values() for iv in ivs)
    wall = max(t_end - t_begin, 1e-9)
    exclusive, idle = _sweep(per_stage)
    stages = {}
    for name, ivs in sorted(per_stage.items()):
        busy = sum(iv[1] - iv[0] for iv in ivs)
        wall_busy = min(union_seconds(ivs), wall)
        excl = min(exclusive.get(name, 0.0), wall_busy)
        stages[name] = {
            "count": len(ivs),
            "busy_s": round(busy, 6),
            "wall_busy_s": round(wall_busy, 6),
            "busy_frac": round(min(1.0, wall_busy / wall), 4),
            "exclusive_s": round(excl, 6),
            "exclusive_frac": round(min(1.0, excl / wall), 4),
            "avg_concurrency": round(busy / wall_busy, 2)
            if wall_busy > 0 else 0.0,
            "rows": sum(iv[2] for iv in ivs),
            "bytes": sum(iv[3] for iv in ivs),
        }
        if stages[name]["rows"] and wall > 0:
            stages[name]["rows_per_sec"] = round(
                stages[name]["rows"] / wall, 2)
    dominant = max(stages, key=lambda s: stages[s]["busy_frac"])
    dom_frac = stages[dominant]["busy_frac"]
    # Amdahl bound: the dominant stage stays on the critical path for its
    # wall-busy seconds however fast everything else gets — perfecting
    # the rest yields at most wall / wall_busy_dominant.
    max_speedup_others = round(1.0 / dom_frac, 2) if dom_frac > 0 else None
    # And per the dominant stage itself: removing only ITS exclusive time
    # (the overlapped part is hidden behind other stages already).
    dom_excl = stages[dominant]["exclusive_s"]
    dom_speedup = round(wall / max(wall - dom_excl, 1e-9), 2)
    return {
        "wall_s": round(wall, 6),
        "idle_s": round(idle, 6),
        "idle_frac": round(min(1.0, idle / wall), 4),
        "stages": stages,
        "dominant_stage": dominant,
        "dominant_busy_frac": dom_frac,
        "max_speedup_fixing_others": max_speedup_others,
        "max_speedup_fixing_dominant": dom_speedup,
    }


def utilization_from_events(events: Iterable[dict]) -> dict | None:
    """Compact ``stage_utilization`` block for bench records: the analyze
    report minus the per-stage exclusive sweep detail."""
    rep = analyze(events=events)
    if rep is None:
        return None
    return {
        "wall_s": rep["wall_s"],
        "idle_frac": rep["idle_frac"],
        "dominant_stage": rep["dominant_stage"],
        "max_speedup_fixing_others": rep["max_speedup_fixing_others"],
        "stages": {name: {k: st[k] for k in
                          ("busy_s", "busy_frac", "avg_concurrency",
                           "count", "rows")}
                   for name, st in rep["stages"].items()},
    }


def _pct(sorted_vals: list, q: float):
    """Nearest-rank percentile of an ascending list (exact values —
    offline trace analysis needs no bucket resolution)."""
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q * (len(sorted_vals) - 1)))))
    return round(sorted_vals[i], 6)


def request_summary(events: Iterable[dict], top_n: int = 8,
                    tail_frac: float = 0.01) -> dict | None:
    """Request-trace tail analysis over a span stream (ISSUE 13): the
    assembled per-request traces (``telemetry.assemble_request_traces``
    — the same fold the live collector runs), exact latency/TTFT
    percentiles, the slowest ``top_n`` with phase attribution, and the
    **dominant cause of the p99 tail** — the phase holding the most
    wall time across the slowest ``tail_frac`` of requests. None when
    the stream holds no completed ``serve_*`` traces.

    Also reports the attribution residual: ``max_unattributed_frac``
    over completed (non-error) traces is the "phases provably sum to
    measured latency" observable (the serve_bench acceptance bound is
    0.05). When objectives are armed (``SPARKDL_SLO_*``), an ``slo``
    compliance block is attached (exact per-trace values — the offline
    twin of the live burn-rate monitor)."""
    from . import slo, telemetry
    col = telemetry.assemble_request_traces(events)
    traces = col.traces()
    if not traces:
        return None
    by_slow = sorted(traces, key=lambda t: -t["latency_s"])
    lats = sorted(t["latency_s"] for t in traces)
    ttfts = sorted(t["ttft_s"] for t in traces
                   if t.get("ttft_s") is not None)
    n_tail = max(1, int(round(len(traces) * tail_frac)))
    tail = by_slow[:n_tail]
    tail_phases: dict[str, float] = {}
    for t in tail:
        for k, v in (t.get("phases") or {}).items():
            tail_phases[k] = tail_phases.get(k, 0.0) + v
    tail_wall = sum(tail_phases.values()) or 1e-9
    dominant = max(tail_phases, key=tail_phases.get) if tail_phases \
        else None
    complete = [t for t in traces
                if t.get("finish") != "error" and not t.get("partial")
                and t["latency_s"] > 0]
    unattr = [abs(t["unattributed_s"]) / t["latency_s"]
              for t in complete]
    out = {
        "completed": len(traces),
        "errors": sum(1 for t in traces if t.get("finish") == "error"),
        "open": col.open_count(),
        "latency_s": {"p50": _pct(lats, 0.50), "p95": _pct(lats, 0.95),
                      "p99": _pct(lats, 0.99),
                      "max": round(lats[-1], 6)},
        "ttft_s": {"p50": _pct(ttfts, 0.50), "p99": _pct(ttfts, 0.99)}
        if ttfts else None,
        "slowest": by_slow[:top_n],
        "tail_n": n_tail,
        "tail_dominant_phase": dominant,
        "tail_phase_frac": {k: round(v / tail_wall, 4)
                            for k, v in sorted(tail_phases.items())},
        "max_unattributed_frac": round(max(unattr), 4) if unattr
        else None,
        "mean_unattributed_frac": round(sum(unattr) / len(unattr), 4)
        if unattr else None,
    }
    slo_block = slo.compliance_from_traces(traces)
    if slo_block:
        out["slo"] = slo_block
    return out


def format_request_summary(req: dict) -> str:
    """Human rendering shared by ``scripts/request_report.py`` and
    ``scripts/bottleneck_report.py``: slowest-requests table with phase
    attribution, the p99-tail dominant cause, and the SLO compliance
    block when objectives are armed."""
    lines = []
    lat, ttft = req["latency_s"], req.get("ttft_s")
    lines.append(
        f"request traces: {req['completed']} completed "
        f"({req['errors']} errors, {req['open']} still open) — latency "
        f"p50 {lat['p50']}s p95 {lat['p95']}s p99 {lat['p99']}s "
        f"max {lat['max']}s"
        + (f"; TTFT p50 {ttft['p50']}s p99 {ttft['p99']}s" if ttft
           else ""))
    if req.get("max_unattributed_frac") is not None:
        lines.append(
            f"phase attribution residual: max "
            f"{100 * req['max_unattributed_frac']:.1f}% of latency "
            f"unattributed (mean "
            f"{100 * req['mean_unattributed_frac']:.1f}%)")
    cols = ("req", "latency_s", "queue", "prefill", "pf_wait",
            "blk_stall", "draft", "decode", "unattr", "toks", "finish",
            "dominant")
    rows = []
    for t in req["slowest"]:
        ph = t.get("phases") or {}
        rows.append((
            str(t["request"]), f"{t['latency_s']:.4f}",
            f"{ph.get('queue', 0):.4f}", f"{ph.get('prefill', 0):.4f}",
            f"{ph.get('prefill_wait', 0):.4f}",
            f"{ph.get('block_stall', 0):.4f}",
            f"{ph.get('draft', 0):.4f}", f"{ph.get('decode', 0):.4f}",
            f"{t['unattributed_s']:.4f}", str(t.get("tokens_out", 0)),
            str(t.get("finish")), str(t.get("dominant_phase"))))
    widths = [max(len(c), *(len(r[i]) for r in rows))
              for i, c in enumerate(cols)]
    lines.append("  ".join(c.ljust(widths[i])
                           for i, c in enumerate(cols)))
    lines += ["  ".join(v.ljust(widths[i]) for i, v in enumerate(r))
              for r in rows]
    if req.get("tail_dominant_phase"):
        fr = req["tail_phase_frac"].get(req["tail_dominant_phase"], 0)
        lines.append(
            f"p99 tail (slowest {req['tail_n']} request(s)): dominant "
            f"cause = {req['tail_dominant_phase']} "
            f"({100 * fr:.1f}% of tail wall)")
    slo_block = req.get("slo")
    if slo_block:
        lines.append("SLO compliance (whole stream, exact traces):")
        for name, ob in sorted(slo_block.items()):
            thr = ob.get("threshold_s", ob.get("max_error_rate"))
            comp = ob.get("compliance")
            lines.append(
                f"  {name} (<= {thr}"
                + ("s" if "threshold_s" in ob else " error rate")
                + f", target {ob['target']}): compliance "
                + (f"{comp:.4f}" if comp is not None else "n/a")
                + (" — MET" if ob.get("met")
                   else " — VIOLATED" if comp is not None else ""))
    return "\n".join(lines)


def format_report(rep: dict) -> str:
    """Human rendering: one aligned row per stage, attribution last."""
    cols = ("stage", "n", "busy_s", "busy%", "excl_s", "avg_par", "rows",
            "MB")
    rows = []
    for name, st in sorted(rep["stages"].items(),
                           key=lambda kv: -kv[1]["busy_frac"]):
        rows.append((
            name, str(st["count"]), f"{st['busy_s']:.3f}",
            f"{100 * st['busy_frac']:.1f}", f"{st['exclusive_s']:.3f}",
            f"{st['avg_concurrency']:.2f}", str(st["rows"]),
            f"{st['bytes'] / 1e6:.1f}"))
    widths = [max(len(c), *(len(r[i]) for r in rows))
              for i, c in enumerate(cols)]
    lines = ["  ".join(c.ljust(widths[i]) for i, c in enumerate(cols))]
    lines += ["  ".join(v.ljust(widths[i]) for i, v in enumerate(r))
              for r in rows]
    lines.append(
        f"wall {rep['wall_s']:.3f}s, idle (no stage active) "
        f"{rep['idle_s']:.3f}s ({100 * rep['idle_frac']:.1f}%)")
    dom = rep["dominant_stage"]
    lines.append(
        f"dominant stage: {dom} "
        f"({100 * rep['dominant_busy_frac']:.1f}% busy) — fixing anything "
        f"else yields <= {rep['max_speedup_fixing_others']}x; eliminating "
        f"{dom}'s exclusive time yields <= "
        f"{rep['max_speedup_fixing_dominant']}x")
    return "\n".join(lines)


# -- device time by named scope (ISSUE 27) -------------------------------------
# The profiler names a device operation by its HLO line (``%fusion.106 = ...``);
# the scope it was traced under (``jit(step)/jvp(ResNet)/stage1_block1/...``,
# or the step's own ``optimizer_update``) is the ``tf_op`` stat of the
# operation's METADATA. ``jax.profiler.ProfileData`` (jax 0.9.0) hands out an
# event's own stats only (``device_offset_ps``, ``device_duration_ps``), so
# the ``.xplane.pb`` is read here from the wire: five messages of
# tsl/profiler/protobuf/xplane.proto, stdlib only.

_OPS_LINE = "XLA Ops"
_SCOPE_STAT = "tf_op"
_PHASES = ("forward", "backward", "optimizer_update", "grad_allreduce",
           "unscoped")
_WRAPPER = re.compile(r"^(?:jit|pjit|shard_map|checkpoint|remat)\b")
UNSCOPED = "(unscoped)"      # by_name: an operation under no scope at all
MODULE_ONLY = "(module)"     # by_name: under flax module names alone


def _varint(buf, i: int):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _msg(buf) -> dict:
    """``{field number: [values]}`` of one protobuf message: ints for varint
    fields, memoryviews for length-delimited and fixed ones."""
    out: dict = {}
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"xplane: wire type {wire} at byte {i}")
            val = buf[i:i + size]
            i += size
        out.setdefault(key >> 3, []).append(val)
    return out


def _text(msg: dict, field: int) -> str:
    return str(msg[field][0], "utf-8", "replace") if field in msg else ""


def _int(msg: dict, field: int) -> int:
    """An int64 field (two's complement on the wire), 0 where absent."""
    v = msg.get(field, [0])[0]
    return v - (1 << 64) if v >= 1 << 63 else v


def _scope_stat(stats: list, stat_names: dict) -> str:
    """The ``tf_op`` among XStat messages, "" if none: a string, or a
    reference to another stat's metadata whose name is the string."""
    for raw in stats:
        st = _msg(raw)
        if stat_names.get(_int(st, 1)) == _SCOPE_STAT:
            return _text(st, 5) or stat_names.get(_int(st, 7), "")
    return ""


def read_xplane_ops(path: str) -> dict:
    """``{plane: [(op, scope, start_ns, dur_ns), ...]}`` for every device
    plane of an ``.xplane.pb`` that has an ``XLA Ops`` line. ``scope`` is the
    operation's ``tf_op`` stat, "" where it carries none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for raw_plane in _msg(space).get(1, []):
        plane = _msg(raw_plane)
        if not _text(plane, 2).startswith("/device:"):
            continue    # a host plane's lines hold millions of events
        lines = [ln for ln in map(_msg, plane.get(3, []))
                 if _text(ln, 2) == _OPS_LINE]
        if not lines:
            continue
        # the two maps' entries are (key = 1, value = 2) messages
        stat_names = {}
        for entry in plane.get(5, []):
            meta = _msg(_msg(entry)[2][0])
            stat_names[_int(meta, 1)] = _text(meta, 2)
        ops_meta = {}
        for entry in plane.get(4, []):
            meta = _msg(_msg(entry)[2][0])
            ops_meta[_int(meta, 1)] = (
                _text(meta, 2), _scope_stat(meta.get(5, []), stat_names))
        ops = []
        for ln in lines:
            t0 = _int(ln, 3)
            for raw in ln.get(4, []):
                ev = _msg(raw)
                name, scope = ops_meta.get(_int(ev, 1), ("", ""))
                ops.append((name, scope,
                            t0 + _int(ev, 2) / 1e3, _int(ev, 3) / 1e3))
        out[_text(plane, 2)] = ops
    return out


def _scope_parts(scope: str) -> list:
    """``jit(step)/jit(main)/jvp(ResNet)/Block_3/conv:`` ->
    ``["jvp(ResNet)", "Block_3"]``: the jit wrappers in front and the
    primitive at the end say nothing about where the time went."""
    parts = [p for p in scope.rstrip(":").split("/") if p]
    while parts and _WRAPPER.match(parts[0]):
        parts.pop(0)
    return parts[:-1]


def _unwrapped(part: str) -> str:
    """``transpose(jvp(lm_head_loss))`` -> ``lm_head_loss``: a transform is
    written around the first scope opened under it, whatever that scope is."""
    while part.endswith(")") and "(" in part:
        part = part[part.index("(") + 1:-1]
    return part


def _innermost(parts: list, names) -> str:
    for part in reversed(parts):
        part = _unwrapped(part)
        if part in names:
            return part
    return ""


def named_scope_of(op_name: str, names) -> str:
    """The innermost path component of an operation's ``op_name`` / ``tf_op``
    that is one of ``names`` (``utils.scopes.names()``), "" if none is. The
    jit wrappers in front and the primitive at the end are no scopes, and a
    ``jvp(...)`` / ``transpose(...)`` around a component is looked through:
    ``jit(step)/transpose(jvp(M))/layers_1/checkpoint/mamba_conv/mul`` is
    ``mamba_conv``, and so is ``jit(step)/jvp(mamba_conv)/mul``."""
    return _innermost(_scope_parts(op_name), names)


def _name_key(parts: list, names) -> str:
    """``by_name``'s key: the innermost registered name; ``(module)`` where
    the path holds none (flax module names alone); ``(unscoped)`` where it
    holds no scope at all once the wrappers are gone."""
    if all(_WRAPPER.match(p) for p in parts):
        return UNSCOPED
    return _innermost(parts, names) or MODULE_ONLY


def _phase(parts: list) -> str:
    for name in ("optimizer_update", "grad_allreduce"):
        if name in parts:
            return name
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    if any(p.startswith("jvp(") for p in parts):
        return "forward"
    return "unscoped"


def scope_seconds(triples: Iterable[tuple], depth: int = 2,
                  names=None) -> dict:
    """Device seconds by scope prefix and by phase, from ``(scope, start_ns,
    dur_ns)`` triples of ONE device line. An operation that encloses others
    (a loop and its body) counts its self time only, so the sums never pass
    the line's busy time. A pure function of its arguments.

    Returns ``{"total_s", "by_scope": {prefix: s}, "by_phase": {phase: s}}``
    with prefixes cut to ``depth`` parts (jit wrappers and the primitive's
    own name dropped) and phases forward (``jvp(...)``), backward
    (``transpose(jvp(...))``), ``optimizer_update``, ``grad_allreduce`` and
    unscoped. With ``names`` (``utils.scopes.names()``) also ``"by_name":
    {name: s}``: each operation under the innermost of those names on its
    path (:func:`named_scope_of`; forward, recomputation and backward
    together), ``"(module)"`` where the path holds flax module names alone
    and ``"(unscoped)"`` where it holds nothing."""
    evs = sorted(((s, s + d, sc) for sc, s, d in triples),
                 key=lambda e: (e[0], -e[1]))
    self_ns = [e[1] - e[0] for e in evs]
    stack = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    by_scope: dict = {}
    by_phase = dict.fromkeys(_PHASES, 0.0)
    by_name: dict = {}
    for (_, _, scope), ns in zip(evs, self_ns):
        parts = _scope_parts(scope)
        key = "/".join(parts[:depth]) or UNSCOPED
        by_scope[key] = by_scope.get(key, 0.0) + ns / 1e9
        by_phase[_phase(parts)] += ns / 1e9
        if names is not None:
            key = _name_key(parts, names)
            by_name[key] = by_name.get(key, 0.0) + ns / 1e9
    out = {"total_s": sum(self_ns) / 1e9, "by_scope": by_scope,
           "by_phase": by_phase}
    if names is not None:
        out["by_name"] = by_name
    return out


def device_time_by_scope(profile_dir: str, depth: int = 2,
                         names=None) -> dict:
    """:func:`scope_seconds` of the newest ``.xplane.pb`` under
    ``profile_dir`` (what ``fit(profile_dir=...)`` or ``runner.trace``
    wrote), averaged over the device planes that have an ``XLA Ops`` line.
    Adds ``"planes"`` and ``"path"``; with ``names``, ``"by_name"`` too."""
    hits = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    planes = read_xplane_ops(hits[-1])
    if not planes:
        raise ValueError(f"{hits[-1]} has no {_OPS_LINE!r} line: the profile "
                         "holds no device operations")
    reps = [scope_seconds(((sc, s, d) for _, sc, s, d in ops), depth, names)
            for ops in planes.values()]
    n = len(reps)
    out = {"total_s": sum(r["total_s"] for r in reps) / n,
           "by_scope": {}, "by_phase": dict.fromkeys(_PHASES, 0.0),
           "planes": sorted(planes), "path": hits[-1]}
    if names is not None:
        out["by_name"] = {}
    for r in reps:
        for table in ("by_scope", "by_phase", "by_name"):
            for k, v in r.get(table, {}).items():
                out[table][k] = out[table].get(k, 0.0) + v / n
    return out


def format_scope_report(rep: dict, top: int = 20) -> str:
    total = rep["total_s"] or 1.0
    lines = [f"device seconds by scope ({rep.get('path', '')}; "
             f"{len(rep.get('planes', []))} device plane(s), "
             f"{rep['total_s']:.4f} s busy per plane)"]

    def row(k, v):
        lines.append(f"  {100 * v / total:6.2f}%  {v:9.5f} s  {k}")

    for k, v in sorted(rep["by_scope"].items(), key=lambda kv: -kv[1])[:top]:
        row(k, v)
    lines.append("by phase:")
    for k in _PHASES:
        row(k, rep["by_phase"][k])
    if "by_name" in rep:
        lines.append("by the program's own scopes (utils.scopes), forward, "
                     "recomputation and backward together:")
        for k, v in sorted(rep["by_name"].items(), key=lambda kv: -kv[1]):
            row(k, v)
    return "\n".join(lines)


# -- the step program's own table of scopes (ISSUE 38) -------------------------
# A trace read without its metadata (``jax.profiler.ProfileData``: the
# benchmark's reader) names an operation ``fusion.106`` and nothing more. The
# compiled program knows better: every instruction of its optimised HLO carries
# the ``op_name`` it was traced under. ``fit`` leaves behind what it takes to
# print that program again; the table is built only for who asks.

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_STEP_PROGRAM: dict | None = None   # the newest fit's; see note_step_program


def hlo_scopes(text: str) -> dict:
    """``{instruction name: op_name}`` for every instruction of every
    computation of an optimised HLO module's text (``compiled.as_text()``):
    ``fusion.106``, ``while.3``, ``flash_attention_fwd.1``, a loop body's
    and a fusion's own instructions alike (names are unique across a
    module). "" for an instruction the compiler made without metadata."""
    table = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            scope = _OP_NAME.search(line, m.end())
            table[m.group(1)] = scope.group(1) if scope else ""
    return table


def note_step_program(make_step, *args) -> None:
    """Keep what names the step: ``make_step()`` gives the step function
    ``fit`` dispatches, ``args`` are its arguments, kept as
    ``ShapeDtypeStruct``s with their shardings. One ``tree_map`` over the
    arguments, no device work, no trace; it replaces what an earlier ``fit``
    left. The step function itself is NOT kept: while it lives its
    executable stays loaded, and on a TPU the program's scratch with it
    (3.5 GB reserved in the LFM2 cell: my chip run, PR 38), which the next
    thing the process runs may need."""
    global _STEP_PROGRAM
    import jax
    try:
        avals = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), args)
    except AttributeError:      # a leaf that is no device array
        avals = None
    _STEP_PROGRAM = {"make_step": make_step, "avals": avals, "table": None,
                     "build_s": None}


def step_program_scopes() -> dict | None:
    """:func:`hlo_scopes` of the step program the newest ``fit`` of this
    process ran: the step function made again, traced and lowered for the
    noted arguments and compiled, then read as text; the second executable
    is dropped at once. The persistent compile cache answers the compile
    where the module is the one ``fit`` compiled, byte for byte. It is that
    but for the Pallas kernels' serialized bodies, which hold the Python
    call stack of the trace as debug locations: where those reach up to
    ``fit``'s own frames (the scan kernels) the first build in a cache
    directory compiles once more, the same program under another key, and
    every later build there hits (my chip runs, PR 38: PERF.md). Built on
    the first call and kept (:func:`step_program_build_s` says what that
    cost); None, never part of a table, where no ``fit`` has run or its step
    is not a jit function."""
    slot = _STEP_PROGRAM
    if slot is None:
        return None
    if slot["table"] is None and slot["avals"] is not None:
        t0 = time.perf_counter()
        step_fn = slot["make_step"]()
        if hasattr(step_fn, "lower"):
            text = step_fn.lower(*slot["avals"]).compile().as_text()
            slot["table"] = hlo_scopes(text)
            slot["build_s"] = time.perf_counter() - t0
        # the recipe has served: let go of what it closes over
        slot["make_step"] = slot["avals"] = None
    return slot["table"]


def step_program_build_s() -> float | None:
    """Seconds the first :func:`step_program_scopes` call took (lowering,
    the compile or its cache load, the text, the parse); None before it."""
    return _STEP_PROGRAM["build_s"] if _STEP_PROGRAM else None


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Device time by named scope, from a jax profile")
    ap.add_argument("--profile", required=True,
                    help="directory given to fit(profile_dir=...)")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--named", action="store_true",
                    help="also by the program's own scopes: those this "
                    "process has opened (utils.scopes.names()), or --scopes")
    ap.add_argument("--scopes", default="",
                    help="a,b,c: the scope names, where the profile is read "
                    "in another process than the one that traced the program")
    args = ap.parse_args(argv)
    names = None
    if args.named or args.scopes:
        from ..utils import scopes
        names = frozenset(filter(None, args.scopes.split(","))) \
            or scopes.names()
        if not names:
            ap.error("--named: this process has opened no scope; name them "
                     "with --scopes a,b,c")
    report = device_time_by_scope(args.profile, args.depth, names)
    print(json.dumps(report) if args.json
          else format_scope_report(report, args.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
