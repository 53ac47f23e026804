#!/usr/bin/env python
"""Elastic gang supervision smoke (ISSUE 16 acceptance), end-to-end on CPU.

Two legs over one deterministic 12-batch GLOBAL dataset (leading dim 12 —
divisible by every world size the run passes through):

1. **Elastic run** — ``supervise(np=4, elastic=True, max_restarts=1)``
   launches a 4-rank training gang (``ListDataset(shard=True)`` over the
   global stream, checkpoint every 2 steps) with a chaos plan that
   ``decimate``\\ s rank 2 at step 5: the rank dies AND its slot stays dead
   — every later attempt at world size 4 re-kills it on entry. Expected
   recovery: budgeted restart after the first death → the relaunched rank
   2 dies again immediately → the supervisor correlates (same rank, same
   world size, consecutive) → **free shrink to 3** → the 3-rank gang
   restores the 4-rank checkpoint through the elastic reshard path and
   finishes. ``max_restarts=1`` makes completion itself the budget proof:
   if the shrink consumed budget the run would have given up instead.
   The batch ledger must show every batch consumed exactly once across
   the resize, with the ``world`` column switching 4 → 3.
2. **Counterfactual** — ``SPARKDL_ELASTIC=0``, the pre-ISSUE-16 behavior
   pinned: the same permanently dead rank death-loops the supervisor
   through its whole restart budget (``GangFailure: giving up``).

Prints one JSON line and exits 0 on success.

Run: ``JAX_PLATFORMS=cpu python scripts/elastic_smoke.py``
"""

import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# The supervisor never queries devices — the workers own the chips.
from sparkdl_tpu.runner.chaos import Fault, FaultPlan  # noqa: E402
from sparkdl_tpu.runner.data import read_ledger  # noqa: E402
from sparkdl_tpu.runner.launcher import (GangFailure,  # noqa: E402
                                         supervise)

N_BATCHES = 12     # one epoch, one batch per step
NUM_STEPS = 12
GLOBAL_ROWS = 12   # divisible by world sizes 4, 3, 2, 1
START_NP = 4
DEAD_RANK = 2
KILL_STEP = 5

_WORKER = """
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import optax
from sparkdl_tpu.runner import (ListDataset, XlaRunner,
                                softmax_cross_entropy_loss)

out_dir = sys.argv[1]
num_steps = int(sys.argv[2])
# np=-1 (default): size to whatever the launcher's env says — pinning a
# world size here would defeat the elastic relaunch.
runner = XlaRunner(checkpoint_dir=os.path.join(out_dir, "ckpt"))
params = {{"w": np.random.RandomState(0).randn(4, 3).astype(np.float32)}}
# GLOBAL batches (shard=True slices each rank's rows at draw time): the
# leading dim must divide evenly at every world size the gang visits.
batches = [{{"image": np.random.RandomState(i).randn({rows}, 4)
                 .astype(np.float32),
            "label": np.random.RandomState(i).randint(0, 3, ({rows},))}}
           for i in range({n_batches})]

res = runner.run(lambda ctx: ctx.fit(
    loss_fn=softmax_cross_entropy_loss(), params=params, tx=optax.sgd(0.1),
    apply_fn=lambda p, x: x @ p["w"],
    data=ListDataset(batches, shard=True),
    num_steps=num_steps, checkpoint_every=2, log_every=1))
rank = os.environ.get("SPARKDL_PROCESS_ID", "0")
with open(os.path.join(out_dir, f"result_rank{{rank}}.jsonl"), "a") as f:
    f.write(json.dumps({{
        "final_step": int(res["state"].step),
        "final_loss": float(res["history"][-1]["loss"]),
        "world": int(os.environ.get("SPARKDL_NUM_PROCESSES", "1"))}})
        + "\\n")
"""

def _write(out_dir: str, name: str, body: str, **fmt) -> str:
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        f.write(body.format(repo=_REPO, **fmt))
    return path


def _audit_ledger(ledger_dir: str, num_steps: int, n_batches: int):
    """Exactly-once audit over rank 0's ledger (shard=True: batch indices
    are GLOBAL, so one rank's ledger describes the whole gang). Returns
    (exactly_once, replay_consistent, worlds_seen)."""
    ledger = read_ledger(ledger_dir)
    by_step: dict = {}
    replay_consistent = True
    for e in ledger:
        step, bi = e["step"], e["batch_index"]
        prev = by_step.get(step)
        if prev is not None and prev != bi \
                and prev not in (e.get("skip_list") or []):
            replay_consistent = False
        by_step[step] = bi
    consumed = sorted(by_step.values())
    exactly_once = (consumed == list(range(n_batches))
                    and sorted(by_step) == list(range(num_steps)))
    worlds = sorted({e.get("world") for e in ledger if e.get("world")})
    return exactly_once, replay_consistent, worlds


def _decimate_plan() -> FaultPlan:
    return FaultPlan([
        Fault("step_start", "decimate", at_step=KILL_STEP, rank=DEAD_RANK)])


def main() -> int:
    checks: dict = {}
    worker_env = {"JAX_PLATFORMS": "cpu",
                  "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}

    # -- 1. elastic: permanent rank death -> free shrink -> completion ----
    out_dir = tempfile.mkdtemp(prefix="sparkdl-elastic-smoke-")
    ledger_dir = os.path.join(out_dir, "ledger")
    worker = _write(out_dir, "worker.py", _WORKER,
                    n_batches=N_BATCHES, rows=GLOBAL_ROWS)
    res = supervise(worker, np=START_NP, args=[out_dir, str(NUM_STEPS)],
                    env={**worker_env, "SPARKDL_BATCH_LEDGER": ledger_dir},
                    plan=_decimate_plan(), elastic=True,
                    max_restarts=1,  # completion proves the resize was free
                    timeout_s=300.0, backoff_s=0.1, poll_s=0.25)
    survivors = []
    for r in range(START_NP - 1):
        path = os.path.join(out_dir, f"result_rank{r}.jsonl")
        if os.path.exists(path):
            survivors += [json.loads(ln) for ln in open(path)]
    checks["job_completed_at_ws3"] = (
        len(survivors) == START_NP - 1
        and all(s["final_step"] == NUM_STEPS and s["world"] == START_NP - 1
                for s in survivors))
    checks["supervisor_resized"] = (
        res.resizes == 1 and res.final_np == START_NP - 1
        and "resized" in res.failure_kinds)
    checks["resize_was_free"] = res.restarts == 2  # 2 relaunches, budget 1
    degr_names = {d.get("name") for d in res.degradations}
    checks["degradations_narrate_resize"] = (
        "gang_resized" in degr_names and "train_resume" in degr_names
        and "checkpoint_resharded" in degr_names)

    exactly_once, replay_consistent, worlds = _audit_ledger(
        ledger_dir, NUM_STEPS, N_BATCHES)
    checks["ledger_exactly_once_across_resize"] = exactly_once
    checks["ledger_replay_deterministic"] = replay_consistent
    checks["ledger_records_resize"] = worlds == [START_NP - 1, START_NP]

    # -- 2. counterfactual: SPARKDL_ELASTIC=0 exhausts the budget ---------
    cf_dir = tempfile.mkdtemp(prefix="sparkdl-elastic-smoke-cf-")
    cf_worker = _write(cf_dir, "worker.py", _WORKER,
                       n_batches=N_BATCHES, rows=GLOBAL_ROWS)
    try:
        supervise(cf_worker, np=START_NP, args=[cf_dir, str(NUM_STEPS)],
                  env={**worker_env, "SPARKDL_ELASTIC": "0"},
                  plan=_decimate_plan(), max_restarts=2,
                  timeout_s=300.0, backoff_s=0.1, poll_s=0.25)
        checks["counterfactual_death_loops"] = False
    except GangFailure as e:
        checks["counterfactual_death_loops"] = "giving up after 2" in str(e)

    ok = all(checks.values())
    print(json.dumps({
        "ok": ok, **checks,
        "restarts": res.restarts,
        "failure_kinds": res.failure_kinds,
        "resizes": res.resizes,
        "final_np": res.final_np,
        "ledger_worlds": worlds,
        "out_dir": out_dir,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
