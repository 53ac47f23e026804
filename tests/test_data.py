"""Exactly-once training data plane (ISSUE 5): cursor round-trips,
skip-lists, adapters, manifest-persisted cursors, and fit() threading —
the preempt-and-resume and legacy-manifest degradation cases pinned fast.

All CPU-only; the supervised end-to-end (SIGKILL + poison batch +
quarantine) lives in scripts/train_resume_smoke.py (slow, test_chaos.py);
the supervisor's correlation logic is pinned fast in test_multiprocess.py.
"""

import json
import os

import numpy as np
import optax
import pytest

from sparkdl_tpu.runner import (CheckpointManager, ListDataset, XlaRunner,
                                softmax_cross_entropy_loss)
from sparkdl_tpu.runner import chaos, events
from sparkdl_tpu.runner import data as data_lib
from sparkdl_tpu.runner import metrics as metrics_lib
from sparkdl_tpu.runner.chaos import Fault, FaultPlan, InjectedPreemption
from sparkdl_tpu.runner.data import (ArrowDataset, FactoryDataset,
                                     as_dataset, env_skip_list, read_ledger)


def _batches(n, rows=8):
    return [{"image": np.random.RandomState(i).randn(rows, 4)
                 .astype(np.float32),
             "label": np.random.RandomState(i).randint(0, 3, (rows,))}
            for i in range(n)]


def _ids(pairs):
    """[(epoch, batch_index), ...] drawn from indexed() pairs."""
    return [(c["epoch"], c["batch_index"] - 1) for c, _ in pairs]


class TestCursorRoundTrip:
    def test_state_restore_resumes_at_exact_batch(self):
        ds = ListDataset(_batches(6))
        it = ds.indexed()
        first = [next(it) for _ in range(3)]
        cursor = first[-1][0]  # after batch 2
        ds2 = ListDataset(_batches(6))
        ds2.restore(cursor)
        rest = list(ds2.indexed())
        assert _ids(rest) == [(0, 3), (0, 4), (0, 5)]
        # and the replayed batches are the SAME arrays, not re-generated
        np.testing.assert_array_equal(rest[0][1]["image"],
                                      _batches(6)[3]["image"])

    def test_restore_records_shuffle_seed_mismatch(self):
        """Review regression: a CRC-valid cursor from a run with a
        different shuffle_seed maps positions to different batches —
        restore() must put that on record, not silently replay wrong."""
        rec = events.reset()
        src = ListDataset(_batches(4), shuffle_seed=7)
        next(src.indexed())
        ds = ListDataset(_batches(4), shuffle_seed=3)
        ds.restore(src.state())
        evs = [e for e in rec.tail()
               if e["name"] == "unverified_data_cursor"]
        assert evs and "shuffle_seed mismatch" in evs[0]["reason"]
        # same seed: no spurious degradation
        rec = events.reset()
        ListDataset(_batches(4), shuffle_seed=7).restore(src.state())
        assert not [e for e in rec.tail()
                    if e["name"] == "unverified_data_cursor"]

    def test_cursor_is_jsonable_and_round_trips(self):
        ds = ListDataset(_batches(3), shuffle_seed=7)
        next(ds.indexed())
        state = json.loads(json.dumps(ds.state()))
        ds2 = ListDataset(_batches(3), shuffle_seed=7)
        ds2.restore(state)
        assert ds2.state()["batch_index"] == state["batch_index"]
        assert state["shuffle_seed"] == 7

    def test_skip_list_honored_and_recorded(self):
        rec = events.reset()
        ds = ListDataset(_batches(5), skip_list=[1, 3])
        out = _ids(ds.indexed())
        assert out == [(0, 0), (0, 2), (0, 4)]
        skipped = [e for e in rec.tail()
                   if e["name"] == "train_batch_skipped"]
        assert [e["batch_index"] for e in skipped] == [1, 3]
        # the cursor carries the skip-list forward
        assert ds.state()["skip_list"] == [1, 3]

    def test_epochs_advance_and_restore_mid_epoch(self):
        ds = ListDataset(_batches(3), epochs=2)
        assert _ids(ds.indexed()) == [(0, 0), (0, 1), (0, 2),
                                      (1, 0), (1, 1), (1, 2)]
        ds2 = ListDataset(_batches(3), epochs=2)
        ds2.restore({"epoch": 1, "batch_index": 1, "skip_list": []})
        assert _ids(ds2.indexed()) == [(1, 1), (1, 2)]

    def test_shuffle_is_deterministic_per_epoch(self):
        def content(ds):
            return [float(b["image"][0, 0]) for _, b in ds.indexed()]

        a = content(ListDataset(_batches(8), epochs=2, shuffle_seed=3))
        b = content(ListDataset(_batches(8), epochs=2, shuffle_seed=3))
        assert a == b  # identically seeded -> identical order (replayable)
        assert a[:8] != a[8:]  # permutation re-seeded per epoch
        assert sorted(a[:8]) == sorted(a[8:])  # same batches, new order


class TestAdapters:
    def test_factory_dataset_fresh_iterator_per_epoch(self):
        calls = []

        def factory():
            calls.append(1)
            return iter(_batches(2))

        ds = FactoryDataset(factory, epochs=2)
        assert _ids(ds.indexed()) == [(0, 0), (0, 1), (1, 0), (1, 1)]
        assert len(calls) == 2

    def test_epoch_aware_factory_gets_the_epoch(self):
        seen = []

        def factory(epoch):
            seen.append(epoch)
            return iter(_batches(1))

        list(FactoryDataset(factory, epochs=3).indexed())
        assert seen == [0, 1, 2]

    def test_defaulted_factory_param_is_not_epoch_aware(self):
        """Review regression: `lambda n=2: ...` is configuration, not an
        epoch slot — passing epoch 0 as n would yield an empty epoch and
        silently end the dataset at step 0."""
        ds = FactoryDataset(lambda n=2: iter(_batches(n)), epochs=1)
        assert len(list(ds.indexed())) == 2

    def test_arrow_skipped_indices_never_converted(self):
        """Review regression: a record whose DECODE is the poison must be
        skippable — skip-listed indices yield raw, unconverted."""
        import pyarrow as pa

        import sparkdl_tpu as sdl
        df = sdl.DataFrame.fromArrow(
            pa.table({"x": np.arange(12, dtype=np.float32)}),
            numPartitions=2)

        def convert(rb):
            out = {"x": rb.column("x").to_numpy(zero_copy_only=False)}
            if out["x"][0] == 4.0:  # batch index 1 is the poison
                raise RuntimeError("decode poison")
            return out

        poisoned = ArrowDataset(df, batch_size=4, convert=convert)
        with pytest.raises(RuntimeError, match="decode poison"):
            list(poisoned.indexed())
        skipping = ArrowDataset(df, batch_size=4, convert=convert,
                                skip_list=[1])
        got = [b["x"][0] for _, b in skipping.indexed()]
        assert got == [0.0, 8.0]  # batch 1 skipped without decoding

    def test_arrow_dataset_round_trip(self):
        import pyarrow as pa

        import sparkdl_tpu as sdl
        df = sdl.DataFrame.fromArrow(
            pa.table({"x": np.arange(10, dtype=np.float32),
                      "label": np.arange(10) % 3}), numPartitions=3)
        ds = ArrowDataset(df, batch_size=4)
        got = list(ds.indexed())
        assert [len(b["x"]) for _, b in got] == [4, 4, 2]
        np.testing.assert_array_equal(got[1][1]["x"],
                                      np.arange(4, 8, dtype=np.float32))
        # restore replays the tail exactly
        ds2 = ArrowDataset(df, batch_size=4)
        ds2.restore(got[0][0])
        np.testing.assert_array_equal(
            next(ds2.indexed())[1]["x"], got[1][1]["x"])

    def test_as_dataset_coercions(self):
        assert isinstance(as_dataset(_batches(2)), ListDataset)
        assert isinstance(as_dataset(lambda: iter(_batches(2))),
                          FactoryDataset)
        ds = ListDataset(_batches(1))
        assert as_dataset(ds) is ds
        # a bare generator is consumable-once: no cursor, legacy path
        assert as_dataset(iter(_batches(2))) is None

    def test_env_skip_list_parsing(self, monkeypatch):
        monkeypatch.setenv(data_lib.SKIP_ENV, "[3, 5]")
        assert env_skip_list() == [3, 5]
        monkeypatch.setenv(data_lib.SKIP_ENV, "not json")
        assert env_skip_list() == []
        monkeypatch.delenv(data_lib.SKIP_ENV)
        assert env_skip_list() == []

    def test_rank_sharding_is_opt_in(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_NUM_PROCESSES", "2")
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "1")
        # default: fit's gang contract — data is ALREADY the local shard;
        # the dataset must not silently re-slice it (review finding)
        _, untouched = next(ListDataset(_batches(2)).indexed())
        assert len(untouched["image"]) == 8
        # shard=True: global stream, rank slices its contiguous share
        ds = ListDataset(_batches(2), shard=True)
        cur, local = next(ds.indexed())
        assert len(local["image"]) == 4  # 8 global rows -> 4 local
        np.testing.assert_array_equal(local["image"],
                                      _batches(2)[0]["image"][4:])
        # cursor stays GLOBAL: rank 1's cursor == rank 0's
        assert cur["batch_index"] == 1
        # non-sliceable leaves replicate instead of crashing
        ds2 = ListDataset([{"x": np.ones((8, 2), np.float32),
                            "frac": 0.5}], shard=True)
        _, b = next(ds2.indexed())
        assert b["frac"] == 0.5 and len(b["x"]) == 4


class TestManifestCursor:
    def _state(self):
        from sparkdl_tpu.runner import TrainState
        return TrainState.create(
            None, {"w": np.ones((4, 3), np.float32)}, optax.sgd(0.1))

    def test_cursor_persists_and_verifies(self, tmp_path):
        m = CheckpointManager(str(tmp_path / "c"), async_save=False)
        cur = {"epoch": 0, "batch_index": 4, "skip_list": [2]}
        m.save(4, self._state(), wait=True, data_cursor=cur)
        assert m.data_cursor(4) == cur
        m.close()

    def test_tampered_cursor_is_rejected_with_degradation(self, tmp_path):
        m = CheckpointManager(str(tmp_path / "c"), async_save=False)
        m.save(2, self._state(), wait=True,
               data_cursor={"epoch": 0, "batch_index": 2, "skip_list": []})
        path = str(tmp_path / "c" / "manifest_step_2.json")
        man = json.load(open(path))
        man["data_cursor"]["batch_index"] = 7  # bit-rot / hand edit
        json.dump(man, open(path, "w"))
        rec = events.reset()
        assert m.data_cursor(2) is None
        evs = [e for e in rec.tail()
               if e["name"] == "unverified_data_cursor"]
        assert evs and "checksum" in evs[0]["reason"]
        m.close()

    def test_legacy_manifest_without_cursor_degrades(self, tmp_path):
        """A pre-ISSUE-5 manifest (no data_cursor key) restores with a
        recorded unverified_data_cursor degradation, not a crash."""
        m = CheckpointManager(str(tmp_path / "c"), async_save=False)
        m.save(1, self._state(), wait=True)  # no cursor (legacy shape)
        rec = events.reset()
        assert m.data_cursor(1) is None
        evs = [e for e in rec.tail()
               if e["name"] == "unverified_data_cursor"]
        assert evs and "pre-cursor" in evs[0]["reason"]
        m.close()


def _fit(ckpt_dir, data, num_steps, **kw):
    kw.setdefault("log_every", 100)
    runner = XlaRunner(checkpoint_dir=str(ckpt_dir))
    params = {"w": np.random.RandomState(0).randn(4, 3).astype(np.float32)}
    return runner.run(lambda ctx: ctx.fit(
        loss_fn=softmax_cross_entropy_loss(), params=params,
        tx=optax.sgd(0.1), apply_fn=lambda p, x: x @ p["w"], data=data,
        num_steps=num_steps, checkpoint_every=2, **kw))


class TestFitCursorThreading:
    def test_resume_continues_at_exact_batch(self, tmp_path, monkeypatch):
        """Two fits over one checkpoint dir: the second must resume the
        DATA at batch 4, not replay 0..3 (pinned via the batch ledger)."""
        monkeypatch.setenv(data_lib.LEDGER_ENV, str(tmp_path / "led"))
        batches = _batches(8)
        _fit(tmp_path / "ck", ListDataset(batches), 4)
        _fit(tmp_path / "ck", ListDataset(batches), 8)
        led = read_ledger(str(tmp_path / "led"))
        assert [(e["step"], e["batch_index"]) for e in led] == \
            [(i, i) for i in range(8)]

    @pytest.mark.parametrize("at_step", [2, 3, 4, 5])
    @pytest.mark.parametrize("log_every", [1, 3])
    def test_every_checkpointed_step_is_ledgered_and_logged(
            self, tmp_path, monkeypatch, at_step, log_every):
        """fit runs one step ahead of the chip, but never past a save:
        the step a checkpoint holds is retired (ledgered, logged) before
        the save, so an attempt that dies on the very next iteration
        (at_step 2 and 4, checkpoint_every=2) leaves no step that neither
        lineage ledgers or logs. At the odd steps the in-flight step dies
        unledgered and the resume replays it."""
        monkeypatch.setenv(data_lib.LEDGER_ENV, str(tmp_path / "led"))
        batches = _batches(8)
        logged = []
        monkeypatch.setattr(
            metrics_lib.MetricsLogger, "log",
            lambda self, step, m: "loss" in m and logged.append(step))
        chaos.install(FaultPlan(
            [Fault("step_start", "preempt", at_step=at_step)]))
        try:
            with pytest.raises(InjectedPreemption):
                _fit(tmp_path / "ck", ListDataset(batches), 8,
                     log_every=log_every)
        finally:
            chaos.uninstall()
        saved = at_step - at_step % 2
        led = read_ledger(str(tmp_path / "led"))
        assert [e["step"] for e in led] == list(range(saved))
        _fit(tmp_path / "ck", ListDataset(batches), 8, log_every=log_every)
        led = read_ledger(str(tmp_path / "led"))
        assert [(e["step"], e["batch_index"]) for e in led] == \
            [(i, i) for i in range(8)]
        assert logged == [s for s in range(1, 9)
                          if s % log_every == 0 or s == 8]

    def test_fit_honors_env_skip_list(self, tmp_path, monkeypatch):
        monkeypatch.setenv(data_lib.LEDGER_ENV, str(tmp_path / "led"))
        monkeypatch.setenv(data_lib.SKIP_ENV, "[1]")
        _fit(tmp_path / "ck", ListDataset(_batches(5)), 4)
        led = read_ledger(str(tmp_path / "led"))
        assert [e["batch_index"] for e in led] == [0, 2, 3, 4]

    def test_draw_failure_attributed_to_failing_batch(self, tmp_path,
                                                      monkeypatch):
        """Review finding: a failure raised while DRAWING batch X must
        postmortem as batch X, not as the previous step's batch — a wrong
        index would make the supervisor quarantine good data."""
        monkeypatch.setenv(events.RECORDER_DIR_ENV, str(tmp_path / "ev"))
        events.reset()
        chaos.install(FaultPlan(
            [Fault("data_fetch", "fatal", at_step=3, once=False)]))
        try:
            with pytest.raises(chaos.InjectedFatal):
                _fit(tmp_path / "ck", ListDataset(_batches(8)), 8)
        finally:
            chaos.uninstall()
            monkeypatch.delenv(events.RECORDER_DIR_ENV)
            events.reset()
        pm = json.load(open(tmp_path / "ev" / "postmortem_rank0.json"))
        assert pm["batch_index"] == 3 and pm["epoch"] == 0
        # the data_fetch SPAN error event — usually the timeline's
        # earliest evidence, hence what the supervisor's signature reads
        # — must carry the tag too (verify-drive regression: without it
        # first_failure had no batch_index and quarantine never fired)
        evs = [json.loads(ln) for ln in
               open(tmp_path / "ev" / "events_rank0.jsonl")]
        span_err = [e for e in evs if e["name"] == "data_fetch"
                    and e.get("error")]
        assert span_err and span_err[0]["batch_index"] == 3

    def test_step_start_failure_not_attributed_to_previous_batch(
            self, tmp_path, monkeypatch):
        """Review regression: a failure at the step_start hook (before
        this step's batch is drawn) must carry NO batch attribution —
        cur_cursor still holding the previous step's batch would make
        the supervisor quarantine innocent data."""
        monkeypatch.setenv(events.RECORDER_DIR_ENV, str(tmp_path / "ev"))
        events.reset()
        chaos.install(FaultPlan(
            [Fault("step_start", "fatal", at_step=2, once=False)]))
        try:
            with pytest.raises(chaos.InjectedFatal):
                _fit(tmp_path / "ck", ListDataset(_batches(8)), 8)
        finally:
            chaos.uninstall()
            monkeypatch.delenv(events.RECORDER_DIR_ENV)
            events.reset()
        pm = json.load(open(tmp_path / "ev" / "postmortem_rank0.json"))
        assert pm["batch_index"] is None

    def test_diverged_attribution_suppressed_unless_log_every_1(
            self, tmp_path, monkeypatch):
        """Review finding: with log_every > 1 the NaN producer is
        anywhere in the window — the postmortem must carry NO
        batch_index (no quarantine) rather than name the detection
        step's innocent batch."""
        from sparkdl_tpu.runner.failures import TrainingDivergedError
        monkeypatch.setenv(events.RECORDER_DIR_ENV, str(tmp_path / "ev"))
        events.reset()
        chaos.install(FaultPlan(
            [Fault("data_fetch", "poison", at_step=2, once=False)]))
        try:
            with pytest.raises(TrainingDivergedError):
                _fit(tmp_path / "ck", ListDataset(_batches(8)), 8,
                     log_every=3)
        finally:
            chaos.uninstall()
            monkeypatch.delenv(events.RECORDER_DIR_ENV)
            events.reset()
        pm = json.load(open(tmp_path / "ev" / "postmortem_rank0.json"))
        assert pm["batch_index"] is None
        # ...while log_every=1 attributes exactly (train_resume_smoke
        # relies on this): pinned in-process too
        monkeypatch.setenv(events.RECORDER_DIR_ENV, str(tmp_path / "ev2"))
        events.reset()
        chaos.install(FaultPlan(
            [Fault("data_fetch", "poison", at_step=2, once=False)]))
        try:
            with pytest.raises(TrainingDivergedError):
                _fit(tmp_path / "ck2", ListDataset(_batches(8)), 8,
                     log_every=1)
        finally:
            chaos.uninstall()
            monkeypatch.delenv(events.RECORDER_DIR_ENV)
            events.reset()
        pm = json.load(open(tmp_path / "ev2" / "postmortem_rank0.json"))
        assert pm["batch_index"] == 2

    def _diverge(self, tmp_path, monkeypatch, poison_at, num_steps,
                 n_batches=8, **kw):
        """fit over ``n_batches`` with batch ``poison_at`` poisoned;
        returns (the TrainingDivergedError, the postmortem)."""
        from sparkdl_tpu.runner.failures import TrainingDivergedError
        monkeypatch.setenv(events.RECORDER_DIR_ENV, str(tmp_path / "ev"))
        events.reset()
        chaos.install(FaultPlan(
            [Fault("data_fetch", "poison", at_step=poison_at, once=False)]))
        try:
            with pytest.raises(TrainingDivergedError) as ei:
                _fit(tmp_path / "ck", ListDataset(_batches(n_batches)),
                     num_steps, **kw)
        finally:
            chaos.uninstall()
            monkeypatch.delenv(events.RECORDER_DIR_ENV)
            events.reset()
        return ei.value, json.load(
            open(tmp_path / "ev" / "postmortem_rank0.json"))

    @pytest.mark.parametrize("num_steps,log_every,batch_index", [
        (7, 1, 6),       # num_steps reached, exact attribution
        (7, 3, None),    # boundary because last: detected, not attributed
        (100, 1, 6),     # the data ran out first
    ], ids=["reached-log1", "reached-log3", "data_out-log1"])
    def test_poison_at_the_last_step_is_still_raised(
            self, tmp_path, monkeypatch, num_steps, log_every, batch_index):
        """ISSUE 28: the last step has no successor whose iteration would
        retire it — fit retires it after the loop, under the same guard
        and the same postmortem (step 7 is no checkpoint boundary here:
        checkpoint_every=2, so only the retire can catch it)."""
        err, pm = self._diverge(tmp_path, monkeypatch, poison_at=6,
                                num_steps=num_steps, n_batches=7,
                                log_every=log_every)
        assert err.step == 7
        assert pm["site"] == "fit" and pm["step"] == 6
        assert pm["batch_index"] == batch_index

    def test_diverged_attribution_names_the_batch_not_its_successor(
            self, tmp_path, monkeypatch):
        """ISSUE 28: step 4's NaN is seen while step 5 (batch 4) is
        already dispatched — the postmortem must name batch 3 and step
        index 3, or the supervisor quarantines an innocent batch."""
        err, pm = self._diverge(tmp_path, monkeypatch, poison_at=3,
                                num_steps=8, log_every=1)
        assert err.step == 4
        assert pm["step"] == 3 and pm["batch_index"] == 3 \
            and pm["epoch"] == 0

    def test_ledger_holds_the_diverged_step_and_not_its_successor(
            self, tmp_path, monkeypatch):
        """ISSUE 28: step 4 (batch 3) is dispatched before step 3's NaN
        is seen. A ledger line for it would read, after the quarantine of
        batch 2, as step 4 moving off a batch nobody quarantined — so a
        step is ledgered when it is retired, not when it is dispatched."""
        monkeypatch.setenv(data_lib.LEDGER_ENV, str(tmp_path / "led"))
        self._diverge(tmp_path, monkeypatch, poison_at=2, num_steps=8,
                      log_every=1)
        led = read_ledger(str(tmp_path / "led"))
        assert [(e["step"], e["batch_index"]) for e in led] == \
            [(i, i) for i in range(3)]

    @pytest.mark.parametrize("poison_at,log_every", [
        (3, 100),   # step 4 is a checkpoint boundary: its own guard syncs
        (3, 1),     # the same, with every step retired and checked
        (2, 1),     # step 3 diverges; step 4's save must never be reached
    ])
    def test_nan_checkpoint_is_never_saved(self, tmp_path, monkeypatch,
                                           poison_at, log_every):
        err, _ = self._diverge(tmp_path, monkeypatch, poison_at=poison_at,
                               num_steps=8, log_every=log_every)
        assert err.step == poison_at + 1
        saved = sorted(int(p.stem.rsplit("_", 1)[1])
                       for p in (tmp_path / "ck").glob("manifest_step_*.json"))
        assert saved == [2]

    def test_bare_iterator_keeps_legacy_path(self, tmp_path, monkeypatch):
        """A generator (not replayable) must train exactly as before —
        no cursor in the manifest, no ledger entries."""
        monkeypatch.setenv(data_lib.LEDGER_ENV, str(tmp_path / "led"))
        res = _fit(tmp_path / "ck", iter(_batches(4)), 4)
        assert int(res["state"].step) == 4
        assert read_ledger(str(tmp_path / "led")) == []
        man = json.load(open(tmp_path / "ck" / "manifest_step_4.json"))
        assert "data_cursor" not in man
