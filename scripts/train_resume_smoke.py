#!/usr/bin/env python
"""Train-resume chaos smoke: the exactly-once training data plane
(ISSUE 5 acceptance), end-to-end through the supervisor on CPU.

Three legs over one deterministic 13-batch dataset:

1. **Supervised run** — ``supervise()`` launches a single-rank training
   worker (ListDataset, checkpoint every 2 steps)
   with a chaos plan injecting (a) one SIGKILL at step 5 (fires once,
   persisted via the plan state_dir) and (b) a deterministic **poison
   batch**: batch index 8 NaN-poisoned at the ``data_fetch`` site on
   every attempt. Expected recovery: retryable restart after the SIGKILL
   → resume at the exact batch; fatal ``TrainingDivergedError`` at batch
   8 → one probe restart → same signature again → batch 8 quarantined
   onto the skip-list → final attempt finishes. The batch-id ledger
   (``SPARKDL_BATCH_LEDGER``) must show every step consuming the same
   batch in every attempt that executed it (deterministic replay) and
   batches 0..12 minus {8} each consumed by exactly one step.
   ``SuperviseResult.degradations`` must name both the restart-resume
   (``train_resume``) and the ``train_batch_quarantined`` events.
2. **Clean run** — same worker, no chaos, skip-list pre-seeded to {8}:
   its final loss must equal the supervised run's exactly (same batch
   lineage ⇒ same floats — the strongest exactly-once proof).
3. **Counterfactual** — the pre-ISSUE-5 behavior, pinned: the same poison
   batch shaped as a retryable fault with ``quarantine_batches=False``
   death-loops the supervisor through its whole restart budget
   (``GangFailure: giving up``).

Prints one JSON line and exits 0 on success.

Run: ``JAX_PLATFORMS=cpu python scripts/train_resume_smoke.py``
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

N_BATCHES = 13
NUM_STEPS = 12
POISON_BATCH = 8

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
runner = XlaRunner(checkpoint_dir=os.path.join(out_dir, "ckpt"))
params = {{"w": np.random.RandomState(0).randn(4, 3).astype(np.float32)}}
batches = [{{"image": np.random.RandomState(i).randn(8, 4)
                 .astype(np.float32),
            "label": np.random.RandomState(i).randint(0, 3, (8,))}}
           for i in range({n_batches})]

res = runner.run(lambda ctx: ctx.fit(
    loss_fn=softmax_cross_entropy_loss(), params=params, tx=optax.sgd(0.1),
    apply_fn=lambda p, x: x @ p["w"], data=ListDataset(batches),
    num_steps=num_steps, checkpoint_every=2, log_every=1))
with open(os.path.join(out_dir, "result.jsonl"), "a") as f:
    f.write(json.dumps({{
        "final_step": int(res["state"].step),
        "final_loss": float(res["history"][-1]["loss"]),
        "steps_this_attempt": res["meter"].steps}}) + "\\n")
"""


def _write_worker(out_dir: str) -> str:
    worker = os.path.join(out_dir, "worker.py")
    with open(worker, "w") as f:
        f.write(_WORKER.format(repo=_REPO, n_batches=N_BATCHES))
    return worker


def _run_leg(name: str, **kw):
    out_dir = tempfile.mkdtemp(prefix=f"sparkdl-resume-smoke-{name}-")
    worker = _write_worker(out_dir)
    res = supervise(worker, np=1, args=[out_dir, str(NUM_STEPS)],
                    timeout_s=300.0, backoff_s=0.1, poll_s=0.25, **kw)
    return out_dir, res


def main() -> int:
    checks: dict = {}

    # -- 1. supervised: SIGKILL + deterministic poison batch --------------
    plan = FaultPlan([
        Fault("step_start", "sigkill", at_step=5),
        Fault("data_fetch", "poison", at_step=POISON_BATCH, once=False),
    ])
    ledger_dir = tempfile.mkdtemp(prefix="sparkdl-resume-ledger-")
    out_dir, res = _run_leg("supervised", max_restarts=3, plan=plan,
                            env={"SPARKDL_BATCH_LEDGER": ledger_dir})
    results = [json.loads(ln) for ln in open(
        os.path.join(out_dir, "result.jsonl"))]
    degr_names = {d.get("name") for d in res.degradations}
    checks["job_completed"] = (
        len(results) == 1 and results[0]["final_step"] == NUM_STEPS)
    checks["quarantined_batches"] = res.quarantined_batches == [POISON_BATCH]
    checks["kinds_show_recovery"] = "quarantined" in res.failure_kinds
    checks["degradations_narrate_resume_and_quarantine"] = (
        "train_resume" in degr_names
        and "train_batch_quarantined" in degr_names)

    # -- exactly-once ledger audit ----------------------------------------
    # Across ALL attempts (the ledger is append-mode, chronological):
    # every step that executed consumed the SAME batch in every attempt —
    # deterministic replay — with exactly one legal remap:
    # a step may move off a batch that was quarantined in between (the
    # entry's skip_list records the context). The final step→batch
    # mapping must cover every batch exactly once, minus the quarantined
    # one: no replays into the surviving lineage, no gaps.
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
    expected = [i for i in range(N_BATCHES) if i != POISON_BATCH]
    checks["ledger_replay_deterministic"] = replay_consistent
    checks["ledger_exactly_once"] = (
        consumed == expected
        and sorted(by_step) == list(range(NUM_STEPS)))

    # -- 2. clean run on the same skip-list: identical final loss ---------
    clean_dir, clean_res = _run_leg(
        "clean", max_restarts=0,
        env={"SPARKDL_SKIP_BATCHES": json.dumps([POISON_BATCH])})
    clean = [json.loads(ln) for ln in open(
        os.path.join(clean_dir, "result.jsonl"))]
    checks["clean_run_restartless"] = clean_res.restarts == 0
    checks["final_loss_matches_clean_run"] = (
        len(clean) == 1
        and clean[0]["final_loss"] == results[0]["final_loss"])

    # -- 3. counterfactual: no skip-list => restart-budget death-loop -----
    cf_plan = FaultPlan([
        Fault("data_fetch", "preempt", at_step=POISON_BATCH, once=False)])
    try:
        _run_leg("counterfactual", max_restarts=2, plan=cf_plan,
                 quarantine_batches=False)
        checks["counterfactual_death_loops"] = False
    except GangFailure as e:
        checks["counterfactual_death_loops"] = "giving up after 2" in str(e)

    ok = all(checks.values())
    print(json.dumps({
        "ok": ok, **checks,
        "restarts": res.restarts,
        "failure_kinds": res.failure_kinds,
        "final_loss": results[0]["final_loss"] if results else None,
        "ledger_steps": len(by_step),
        "out_dir": out_dir,
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
