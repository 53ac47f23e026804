"""Read a profiler trace into plain lists, and the arithmetic on intervals.

A trace is ``{plane: {line: [(name, start_ns, dur_ns), ...]}}``. It is read
from the ``.xplane.pb`` that ``jax.profiler`` writes (with nothing but JAX),
or from a Perfetto/Chrome JSON such as ``PROFILE_TRACE.json.gz``, so that the
reducers can be checked against a recorded trace.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import statistics

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SHORT_GAP_NS = 50e3
SHORT_GAPS = "(gaps under 50 us, between operations)"
BETWEEN_STEPS = "between steps: the host feeds, dispatches or fetches a loss"
INSIDE_STEP = "inside the step's program"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def read_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def read_chrome_json(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            tname[(e["pid"], e["tid"])] = e["args"]["name"]
    out = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        plane = pname.get(e["pid"], str(e["pid"]))
        line = tname.get((e["pid"], e["tid"]), str(e["tid"]))
        out.setdefault(plane, {}).setdefault(line, []).append(
            (e["name"], float(e["ts"]) * 1e3, float(e.get("dur", 0)) * 1e3))
    return out


def device_planes(trace: dict) -> dict:
    """The planes of the chips' tensor cores: ``/device:TPU:<n>``."""
    return {k: v for k, v in sorted(trace.items())
            if k.startswith("/device:TPU:") and OPS_LINE in v}


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` inside
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle ``(start_ns, end_ns)`` stretches of ``[lo, hi]``."""
    out, edge = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > edge:
            out.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi))
    return out


def main_module(plane: dict):
    """``(name, events)`` of the module that took most device time: the step."""
    by_name = {}
    for name, s, d in plane.get(MODULES_LINE, []):
        by_name.setdefault(name, []).append((s, d))
    if not by_name:
        return None, []
    name = max(by_name, key=lambda n: sum(d for _, d in by_name[n]))
    return name, sorted(by_name[name])


def steady_window(plane: dict):
    """``(lo_ns, hi_ns, steps)``: from the start of the SECOND step program in
    the trace to the start of the last, a whole number of step periods. The
    trace starts wherever the host starts it, as a rule inside a step: its
    first program is cut, with a false gap before it and part of its
    operations missing, so it is left out, like the last. None where under
    four programs ran."""
    _, evs = main_module(plane)
    if len(evs) < 4:
        return None
    return evs[1][0], evs[-1][0], len(evs) - 2


def device_summary(trace: dict):
    """Per device plane: the steady window, busy seconds in it, and the
    step's device times. None where the trace holds no device plane."""
    out = []
    for name, plane in device_planes(trace).items():
        win = steady_window(plane)
        if win is None:
            continue
        lo, hi, steps = win
        ops = [(s, s + d) for _, s, d in plane[OPS_LINE]]
        mod_name, mods = main_module(plane)
        out.append({
            "plane": name, "lo": lo, "hi": hi, "steps": steps,
            "window_s": (hi - lo) / 1e9,
            "busy_s": union_seconds(ops, lo, hi),
            "module": mod_name,
            "step_ms": statistics.median(d for _, d in mods) / 1e6})
    return out or None


def short_name(op: str) -> str:
    """``%fusion.106 = bf16[...] fusion(...)`` -> ``fusion.106``: the trace
    names an operation by its whole HLO line."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def top_device_ops(trace: dict, n: int = 10) -> list:
    """``[[name, seconds], ...]`` of the operations that took most device
    time in the steady window, averaged over the chips."""
    planes = device_planes(trace)
    total = {}
    for plane in planes.values():
        win = steady_window(plane)
        if win is None:
            continue
        lo, hi, _ = win
        for name, s, d in plane[OPS_LINE]:
            if lo <= s < hi:
                name = short_name(name)
                total[name] = total.get(name, 0.0) + d / 1e9 / len(planes)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def host_events(trace: dict) -> list:
    """``(name, start_ns, end_ns)`` of every host-side event."""
    out = []
    for pname, plane in trace.items():
        if pname.startswith("/device:"):
            continue
        for evs in plane.values():
            out.extend((n, s, s + d) for n, s, d in evs)
    return out


def idle_gaps_by_host(trace: dict, n: int = 10) -> list:
    """``[[what the host was doing, idle seconds], ...]``: every idle stretch
    of the first chip in the steady window, summed by name. A stretch is named
    by the shortest host event that covers its middle (the innermost). Where
    the trace holds no host event there (the benchmark traces the device
    alone, because host tracing slows the feed it is looking at), it is named
    by where it lies: between two runs of the step's program, or inside one."""
    planes = device_planes(trace)
    if not planes:
        return []
    plane = next(iter(planes.values()))
    win = steady_window(plane)
    if win is None:
        return []
    lo, hi, _ = win
    ops = [(s, s + d) for _, s, d in plane[OPS_LINE]]
    steps = [(s, s + d) for s, d in main_module(plane)[1]]
    host = host_events(trace)
    total = {}
    for s, e in gaps(ops, lo, hi):
        if e - s < SHORT_GAP_NS:
            # between two operations of one step: the device's own doing
            name = SHORT_GAPS
        else:
            mid = (s + e) / 2
            covering = [(he - hs, nm) for nm, hs, he in host if hs <= mid <= he]
            if covering:
                name = min(covering)[1]
            elif any(ss <= mid <= se for ss, se in steps):
                name = INSIDE_STEP
            else:
                name = BETWEEN_STEPS
        total[name] = total.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
