"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

A cell names a configuration, a traffic mix and its chips. Everything else
is found from those names under ``benchmark/``:

    configs/<config>.json         the sizes as they are run
    traffic/<traffic>.json        the mix's parameters; names its driver
    drivers/<driver>.py           runs the program under a mix
    programs/<config>.py          builds the program's side of a configuration
    references/<config>.py        the plain float32 reference
    flops/<config>.py             operations per example, from the shapes
    limits/<cell>.json            the limits of the numbers ``correct`` compares
    layer_metrics/<metric>.py     one reader per per-layer metric
    peaks.json                    the chips' published peaks, by device_kind
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ResolutionError(Exception):
    """A name in BENCHMARK.json has no file, or a file lacks a key."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def bench_path(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold ``-``)."""
    path = bench_path(kind, name + ".py")
    if not os.path.isfile(path):
        raise ResolutionError(f"no {kind}/{name}.py under benchmark/")
    mod_name = "bench_%s_%s" % (kind, "".join(
        c if c.isalnum() else "_" for c in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(path: str | None = None) -> dict:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def resolve_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry, configuration, traffic and the names of its files.
    Raises :class:`ResolutionError` on the first thing it cannot find; loads
    no module, so it is safe where no JAX may be touched."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise ResolutionError(
            f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise ResolutionError(f"cell {name!r} names config "
                              f"{cell['config']!r}, which is not listed")
    entry = configs[cell["config"]]
    cfg_file = os.path.join(ROOT, entry["file"])
    traffic_file = bench_path("traffic", cell["traffic"] + ".json")
    for p in (cfg_file, traffic_file):
        if not os.path.isfile(p):
            raise ResolutionError(f"missing {os.path.relpath(p, ROOT)}")
    traffic = load_json(traffic_file)
    if "driver" not in traffic:
        raise ResolutionError(f"{cell['traffic']}.json names no driver")
    files = {
        "driver": ("drivers", traffic["driver"]),
        "program": ("programs", cell["config"]),
        "reference": ("references", cell["config"]),
        "flops": ("flops", cell["config"]),
    }
    for kind, nm in files.values():
        if not os.path.isfile(bench_path(kind, nm + ".py")):
            raise ResolutionError(f"missing benchmark/{kind}/{nm}.py")
    # a cell's limits default to those of the one-chip cell of the same
    # configuration and traffic: the numbers compared are per example
    limits_file = bench_path("limits", name + ".json")
    if not os.path.isfile(limits_file):
        limits_file = bench_path(
            "limits", f"{cell['config']}.{cell['traffic']}.json")
    if not os.path.isfile(limits_file):
        raise ResolutionError(
            f"no limits for {name!r}: neither limits/{name}.json nor "
            f"limits/{cell['config']}.{cell['traffic']}.json")
    return {
        "name": name, "cell": cell, "config_entry": entry,
        "config": load_json(cfg_file), "traffic": traffic,
        "files": files, "limits": load_json(limits_file),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def peak_for(device_kind: str) -> dict:
    """The published peaks of a chip. A kind not in the table is an error."""
    peaks = load_json(bench_path("peaks.json"))
    if device_kind not in peaks["chips"]:
        raise ResolutionError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(peaks['chips'])}): add it with its source")
    return peaks["chips"][device_kind]
