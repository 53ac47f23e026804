"""``correct`` comes out false for the control and for each fault that a
one-chip training cell can have, and true for a sound run.

At a size a test can hold, on the CPU, with the cells' own limits. The program
computes in float32 here (bfloat16 on a net this small, with BatchNorm over 8
rows, is not the cell's arithmetic), so a sound run sits far under the limits
and what fails them is the fault alone. The look for a chip is skipped: the
driver is called as ``run.py`` calls it.
"""

import pytest

import tiny
from harness import compare, loader
from harness.reference_run import make_weights, run_steps
from harness.traffic import make_pool

CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]


def _drive(cell, seed=7):
    job = tiny.tiny_job(cell, seed=seed)
    job["resolved"]["config"]["compute_dtype"] = "float32"
    driver = loader.load_module(*job["resolved"]["files"]["driver"])
    return driver.run(job)


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell):
    r = _drive(cell)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        cell, monkeypatch):
    from sparkdl_tpu.runner import xla_runner
    orig = xla_runner.make_train_step

    def broken(loss_fn, mesh, **kw):
        real = orig(loss_fn, mesh, **{**kw, "donate": False})

        def step(state, batch):
            _, m = real(state, batch)
            return state, m
        return step

    monkeypatch.setattr(xla_runner, "make_train_step", broken)
    r = _drive(cell)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(cell, monkeypatch):
    import jax
    from sparkdl_tpu.runner.xla_runner import RunnerContext
    orig = RunnerContext.shard_batch

    def half(self, batch):
        n = len(jax.tree_util.tree_leaves(batch)[0])
        return orig(self, jax.tree_util.tree_map(lambda x: x[: n // 2], batch))

    monkeypatch.setattr(RunnerContext, "shard_batch", half)
    r = _drive(cell)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_in_float8_is_not_correct(cell):
    """The reference in the precision below the configuration's, put in the
    program's place, fails one of the cell's numbers on every seed tried.
    (The gap of two norms is of second order in a random error, so on a net
    this small it can read low by chance: these seeds are ones on which it
    does not. At the cells' own size it reads steadily: PERF.md section 2.)"""
    res = tiny.tiny_job(cell)["resolved"]
    cfg, traffic = res["config"], res["traffic"]
    ref = loader.load_module("references", res["cell"]["config"])
    for seed in (4, 6, 2 ** 31 + 5):
        weights = make_weights(ref, cfg, seed)
        batches = make_pool(traffic, cfg, seed, 1)[:3]
        base = run_steps(ref, cfg, weights, batches)
        ctrl = run_steps(ref, cfg, weights, batches, precision="fp8")
        numbers, _ = compare.training_numbers(ctrl, base)
        ok, compared = compare.judge(numbers, res["limits"]["limits"])
        assert not ok, compared


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [1.0], "grad1": {"a": 2.0, "b": 3.0},
           "delta": {"a": 0.5, "b": 0.1}}
    prog = {"losses": [1.0], "grad1": {"a": 2.0, "b": 3.0},
            "delta": {"a": 0.0, "b": 0.0}}
    numbers, where = compare.training_numbers(prog, ref)
    assert numbers["delta_leaf"] == pytest.approx(1.0)
    assert numbers["delta_all"] == pytest.approx(1.0)
    assert numbers["grad1_leaf"] == numbers["grad1_all"] == 0.0


def test_the_reference_with_its_state_frozen_reads_one():
    res = tiny.tiny_job(CELLS[0])["resolved"]
    cfg, traffic = res["config"], res["traffic"]
    ref = loader.load_module("references", res["cell"]["config"])
    weights = make_weights(ref, cfg, 5)
    batches = make_pool(traffic, cfg, 5, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    frozen = run_steps(ref, cfg, weights, batches, frozen=True)
    numbers, _ = compare.training_numbers(frozen, base)
    assert numbers["delta_all"] == pytest.approx(1.0)
    assert numbers["grad1_all"] == pytest.approx(1.0)
    assert numbers["loss_1"] == pytest.approx(0.0, abs=1e-6)


def test_leaves_with_no_gradient_are_left_out_of_the_change_by_rule():
    ref = {"losses": [1.0], "grad1": {"a": 1.0, "b": 1.0, "dead": 1e-9},
           "delta": {"a": 1.0, "b": 1.0, "dead": 1.0}}
    prog = {"losses": [1.0], "grad1": dict(ref["grad1"]),
            "delta": {"a": 1.0, "b": 1.0, "dead": 3.0}}
    numbers, _ = compare.training_numbers(prog, ref)
    assert numbers["delta_leaf"] == 0.0
    assert compare.dead_leaves(ref["grad1"]) == {"dead"}
