"""run.py resolves a cell to its files by name, and refuses what it must."""

import copy
import os
import subprocess
import sys

import pytest

import tiny
from harness import loader

RUN = os.path.join(tiny.BENCH_DIR, "run.py")


def test_every_cell_resolves_to_files_that_exist():
    bench = loader.load_benchmark()
    for w in bench["workloads"]:
        res = loader.resolve_cell(w["name"], bench)
        assert res["traffic"]["driver"] == "train_fit"
        assert {m["name"] for m in res["end_to_end"]} >= {
            "train_examples_per_s", "setup_s"}
        for m in res["per_layer"]:
            assert os.path.isfile(loader.bench_path(
                "layer_metrics", m["name"] + ".py")), m["name"]
        assert set(res["limits"]["limits"]) <= {
            "loss_1", "loss_2", "loss_3", "grad1_leaf", "delta_leaf",
            "grad1_all", "delta_all"}
        assert res["limits"]["limits"]


def test_the_four_chip_cell_resolves_to_files_of_its_own_name():
    bench = loader.load_benchmark()
    res = loader.resolve_cell("resnet50.train-b128-dp4", bench)
    assert res["cell"]["chips"] == 4
    assert res["files"]["reference"] == ("references", "resnet50")
    # a pair of configuration and traffic appears once: the mix has the
    # one-chip cell's parameters under a name of its own
    one = loader.resolve_cell("resnet50.train-b128", bench)
    assert res["cell"]["traffic"] != one["cell"]["traffic"]
    same = ("driver", "per_chip_batch", "pool_batches", "warmup_steps",
            "inputs")
    assert {k: res["traffic"][k] for k in same} == {
        k: one["traffic"][k] for k in same}
    assert os.path.isfile(loader.bench_path(
        "limits", "resnet50.train-b128-dp4.json"))
    # the same numbers decide, at limits set from this cell's own readings
    assert set(res["limits"]["limits"]) == set(one["limits"]["limits"])
    assert res["limits"] != one["limits"]
    names = {m["name"] for m in res["per_layer"]}
    assert "grad_allreduce_share" in names and "train_step_mfu" in names
    assert "grad_allreduce_share" not in {m["name"] for m in one["per_layer"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_run_py_holds_no_model_or_cell_name():
    src = open(RUN).read().lower()
    for word in ("resnet", "bert", "imagenet", "squad"):
        assert word not in src


def test_unknown_names_are_errors():
    with pytest.raises(loader.ResolutionError):
        loader.resolve_cell("no-such-cell")
    with pytest.raises(loader.ResolutionError):
        loader.peak_for("TPU v9 imaginary")
    assert loader.peak_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def _run(args, cwd=tiny.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_a_platform_that_is_no_tpu_is_refused_with_no_result_line():
    cell = loader.load_benchmark()["workloads"][0]["name"]
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_an_unknown_workload_is_refused_with_no_result_line():
    p = _run(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert p.returncode == 3 and p.stdout.strip() == ""


def test_without_the_program_there_is_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = loader.load_benchmark()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_json_keeps_to_the_contract_limits():
    import re
    bench = loader.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
