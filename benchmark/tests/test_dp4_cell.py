"""The cell on four chips: its one reader of its own, and the fault that
only a cell across chips can have."""

import pytest

import tiny
from harness import loader

DP4 = "resnet50.train-b128-dp4"


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


def _trace(ops_per_step, steps=4, step_ns=10_000_000, planes=4):
    mods, ops = [], []
    for i in range(steps + 1):
        t = 1_000 + i * step_ns
        mods.append(("jit_step", t, step_ns))
        at = t
        for name, dur in ops_per_step:
            ops.append((name, at, dur))
            at += dur
    return {"/device:TPU:%d" % i: {"XLA Modules": list(mods),
                                   "XLA Ops": list(ops)}
            for i in range(planes)}


def test_the_all_reduce_share_is_the_collectives_time_over_the_window():
    ar = "%all-reduce.416 = f32[25557032] all-reduce(f32[25557032] %fusion.3)"
    start = "%all-reduce-start.2 = f32[64] all-reduce-start(f32[64] %p)"
    consumer = "%fusion.7 = f32[64] fusion(f32[25557032] %all-reduce.416)"
    tr = _trace([("%fusion.3 = f32[] fusion()", 8_000_000), (ar, 300_000),
                 (start, 100_000), (consumer, 1_000_000)])
    # 0.4 ms of collectives in each 10 ms step of the steady window; the
    # cut first program's are left out with it
    assert _read("grad_allreduce_share", {"trace": tr}) == pytest.approx(4.0)
    # on one chip the step holds no such operation: nothing to read
    one = _trace([("%fusion.3 = f32[] fusion()", 8_000_000),
                  (consumer, 1_000_000)], planes=1)
    assert _read("grad_allreduce_share", {"trace": one}) is None
    assert _read("grad_allreduce_share", {}) is None


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """With no gradient (and no batch statistic) crossing the chips, the
    first chip, whose state the driver reads, steps on its own rows alone:
    planted so, in the program's feed, under the whole driver."""
    import jax
    from sparkdl_tpu.runner.xla_runner import RunnerContext
    orig = RunnerContext.shard_batch

    def own_rows(self, batch):
        n = len(jax.tree_util.tree_leaves(batch)[0])
        return orig(self, jax.tree_util.tree_map(
            lambda x: x[: n // self.size], batch))

    job = tiny.tiny_job(DP4, seed=7)
    assert job["resolved"]["cell"]["chips"] == 4
    job["resolved"]["config"]["compute_dtype"] = "float32"
    driver = loader.load_module(*job["resolved"]["files"]["driver"])
    monkeypatch.setattr(RunnerContext, "shard_batch", own_rows)
    r = driver.run(job)
    assert not r["correct"], r["compared"]
