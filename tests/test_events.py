"""Flight recorder tests (ISSUE 2 tentpole): structured event tracing
through the real training machinery — ring-buffer bounds, JSONL streaming,
crash postmortems, merged gang timelines, step-time percentiles, and MFU —
plus the observability satellites (atomic heartbeats, robust trace(),
MetricsLogger hardening).
"""

import json
import os
import sys
import time
import types

import jax
import numpy as np
import optax
import pytest

from sparkdl_tpu.runner import (Fault, FaultPlan, GangFailure, StepTimeStats,
                                ThroughputMeter, XlaRunner, chaos, events,
                                launcher, run_stats,
                                softmax_cross_entropy_loss, supervise)
from sparkdl_tpu.runner import metrics as metrics_lib
from sparkdl_tpu.runner.metrics import MetricsLogger

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Every test starts with a fresh recorder, no stream dir, and zeroed
    process-wide stats."""
    monkeypatch.delenv("SPARKDL_EVENT_DIR", raising=False)
    monkeypatch.delenv("SPARKDL_EVENT_RING", raising=False)
    monkeypatch.delenv("SPARKDL_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("SPARKDL_MFU_ESTIMATE", raising=False)
    monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    chaos.uninstall()
    events.reset()
    metrics_lib.global_step_stats.reset()
    run_stats.reset()
    yield
    chaos.uninstall()
    events.reset()
    metrics_lib.global_step_stats.reset()
    run_stats.reset()


def _linear_apply(params, x):
    return x @ params["w"]


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(4, 3).astype(np.float32)}


def _data(n_batches=64, seed=1):
    rng = np.random.RandomState(seed)
    for _ in range(n_batches):
        x = rng.randn(16, 4).astype(np.float32)
        yield {"image": x, "label": rng.randint(0, 3, (16,))}


def _fit(ctx, **kw):
    kw.setdefault("num_steps", 4)
    kw.setdefault("log_every", 100)
    return ctx.fit(loss_fn=softmax_cross_entropy_loss(), params=_params(),
                   tx=optax.sgd(0.1), apply_fn=_linear_apply, data=_data(),
                   **kw)


class TestRecorder:
    def test_ring_is_bounded(self):
        rec = events.reset(ring_size=16)
        for i in range(100):
            rec.event("e", i=i)
        tail = rec.tail()
        assert len(tail) == 16
        assert tail[0]["i"] == 84 and tail[-1]["i"] == 99

    def test_span_records_duration_and_error(self):
        rec = events.reset()
        with events.span("ok", step=3):
            time.sleep(0.002)
        with pytest.raises(ValueError, match="boom"):
            with events.span("bad"):
                raise ValueError("boom")
        ok_end = [e for e in rec.tail() if e["name"] == "ok"
                  and e["ph"] == "E"][0]
        assert ok_end["dur_s"] >= 0.002 and ok_end["step"] == 3
        bad_end = [e for e in rec.tail() if e["name"] == "bad"
                   and e["ph"] == "E"][0]
        assert bad_end["error"] == "ValueError: boom"

    def test_data_exhaustion_is_not_an_error(self):
        """A span closed by StopIteration (fit's data_fetch around next())
        marks end_of_data — NOT error — so a rank that merely finished its
        data can never be named the gang's first failure."""
        rec = events.reset()
        it = iter([])
        try:
            with events.span("data_fetch", step=0):
                next(it)
        except StopIteration:
            pass
        end = rec.tail()[-1]
        assert end["ph"] == "E" and end.get("end_of_data") is True
        assert "error" not in end

    def test_block_on_error_does_not_mask_region_error(self, monkeypatch):
        """When the region raised AND block_until_ready also fails, the
        region's exception is the story — the block error is recorded in
        the end event, never raised over it (classification depends on
        the right exception propagating)."""
        rec = events.reset()
        monkeypatch.setattr(jax, "block_until_ready", lambda t: (_ for _ in
                            ()).throw(RuntimeError("UNAVAILABLE: device")))
        with pytest.raises(ValueError, match="diverged-ish"):
            with events.span("step", block_on=object()):
                raise ValueError("diverged-ish user error")
        end = rec.tail()[-1]
        assert end["error"].startswith("ValueError")
        assert end["block_error"].startswith("RuntimeError")
        # clean region: the device error DOES surface
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            with events.span("step", block_on=object()):
                pass
        assert rec.tail()[-1]["error"].startswith("RuntimeError")

    def test_no_dir_means_no_io(self, tmp_path):
        rec = events.reset()
        for i in range(50):
            rec.event("e", i=i)
        assert rec._file is None  # never opened a stream
        assert list(tmp_path.iterdir()) == []

    def test_streams_jsonl_per_rank(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "3")
        rec = events.reset()
        rec.event("alpha", step=1)
        with rec.span("beta"):
            pass
        path = tmp_path / "events_rank3.jsonl"
        recs = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert [r["name"] for r in recs] == ["alpha", "beta", "beta"]
        assert [r["ph"] for r in recs] == ["P", "B", "E"]
        assert all(r["rank"] == 3 for r in recs)
        assert recs[0]["step"] == 1

    def test_stream_cap_bounds_file_ring_keeps_recording(self, tmp_path,
                                                         monkeypatch):
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "0")
        monkeypatch.setenv("SPARKDL_EVENT_MAX_MB", "0.0005")  # ~520 bytes
        rec = events.reset()
        for i in range(100):
            rec.event("e", i=i)
        lines = (tmp_path / "events_rank0.jsonl").read_text().splitlines()
        recs = [json.loads(ln) for ln in lines]
        assert recs[-1]["name"] == "event_stream_truncated"
        assert len(recs) < 100  # file bounded...
        assert len(rec.tail()) > len(recs)  # ...ring kept recording
        size = (tmp_path / "events_rank0.jsonl").stat().st_size
        rec.event("after")  # no further growth past the marker
        assert (tmp_path / "events_rank0.jsonl").stat().st_size == size

    def test_stream_cap_survives_recorder_reset(self, tmp_path,
                                                monkeypatch):
        """The cap budget is seeded from the file already on disk: a
        reset()-per-attempt retry loop must not grow the stream
        N_attempts x cap."""
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "0")
        monkeypatch.setenv("SPARKDL_EVENT_MAX_MB", "0.0005")
        rec = events.reset()
        for i in range(100):
            rec.event("e", i=i)
        size = (tmp_path / "events_rank0.jsonl").stat().st_size
        rec2 = events.reset()  # fresh recorder, same dir, same file
        for i in range(100):
            rec2.event("e", i=i)
        assert (tmp_path / "events_rank0.jsonl").stat().st_size == size

    def test_enable_flight_recorder(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "0")
        # setenv first so monkeypatch restores the pre-test absence even
        # though enable_flight_recorder writes os.environ directly
        monkeypatch.setenv("SPARKDL_EVENT_DIR", "overwritten")
        monkeypatch.setenv("SPARKDL_EVENT_RING", "overwritten")
        from sparkdl_tpu.runner.api import enable_flight_recorder
        rec = enable_flight_recorder(str(tmp_path), ring_size=32)
        assert os.environ["SPARKDL_EVENT_DIR"] == str(tmp_path)
        rec.event("hello")
        assert (tmp_path / "events_rank0.jsonl").exists()
        assert rec.ring.maxlen == 32

    def test_timer_is_the_span_primitive(self):
        from sparkdl_tpu.utils import Timer
        assert Timer is events.Timer
        with Timer() as t:
            time.sleep(0.002)
        assert t.seconds >= 0.002
        # spans ARE timers — one timing primitive in the codebase
        assert issubclass(type(events.span("x")), Timer)


class TestStepTimeStats:
    def test_percentiles_on_synthetic_sequence(self):
        st = StepTimeStats()
        for ms in range(1, 101):  # 1..100 ms
            st.record(ms / 1000.0)
        s = st.summary()
        assert s["n"] == 100
        assert s["p50_s"] == pytest.approx(0.050)
        assert s["p95_s"] == pytest.approx(0.095)
        assert s["p99_s"] == pytest.approx(0.099)
        assert s["max_s"] == pytest.approx(0.100)
        assert s["mean_s"] == pytest.approx(0.0505)

    def test_reservoir_bounds_memory_keeps_max_exact(self):
        st = StepTimeStats(capacity=50)
        for i in range(1000):
            st.record(0.001 * (i % 97 + 1))
        assert len(st._sample) == 50
        assert st.count == 1000
        assert st.summary()["max_s"] == pytest.approx(0.097)
        assert 0.001 <= st.percentile(50) <= 0.097

    def test_meter_summary_carries_percentiles_and_mfu(self, monkeypatch):
        m = ThroughputMeter(n_chips=4, warmup_steps=0)
        for _ in range(10):
            m.step_stats.record(0.1)
        # FLOPs unknown -> MFU is null, not zero
        assert m.summary()["mfu"] is None
        monkeypatch.setenv("SPARKDL_PEAK_FLOPS", "1e12")
        m.flops_per_step = 4e10  # global step over 4 chips at 1e12 peak
        s = m.summary()
        # 4e10 / 0.1s / (1e12 * 4 chips) = 0.1
        assert s["mfu"] == pytest.approx(0.1)
        assert s["step_time"]["p50_s"] == pytest.approx(0.1)

    def test_fit_populates_step_time(self):
        res = XlaRunner(np=8).run(_fit)
        s = res["meter"].summary()
        assert s["step_time"]["n"] == 3  # 4 steps - 1 warmup
        assert s["step_time"]["p99_s"] >= s["step_time"]["p50_s"] > 0
        assert s["mfu"] is None  # no FLOP count supplied
        # the process-wide reservoir (bench's source) saw the same steps
        assert metrics_lib.global_step_stats.count == 3

    def test_fit_mfu_estimate_via_cost_analysis(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_MFU_ESTIMATE", "1")
        monkeypatch.setenv("SPARKDL_PEAK_FLOPS", "1e12")
        res = XlaRunner(np=8).run(_fit)
        m = res["meter"]
        assert m.flops_per_step is not None and m.flops_per_step > 0
        assert m.summary()["mfu"] is not None


class TestPostmortem:
    def test_fit_failure_writes_postmortem(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "0")
        events.reset()
        chaos.install(FaultPlan([Fault("step_start", "preempt", at_step=2)]))
        with pytest.raises(Exception, match="UNAVAILABLE"):
            XlaRunner(np=8).run(_fit)
        pm = json.loads((tmp_path / "postmortem_rank0.json").read_text())
        assert pm["error"]["type"] == "InjectedPreemption"
        assert pm["error"]["kind"] == "retryable"
        assert pm["site"] == "fit" and pm["step"] == 2
        names = [e["name"] for e in pm["events"]]
        assert "fit_start" in names and "chaos" in names
        assert "step_compute" in names and "compile" in names
        # the stream holds the same trail (flushed line-by-line)
        lines = (tmp_path / "events_rank0.jsonl").read_text().splitlines()
        assert any(json.loads(ln)["name"] == "chaos" for ln in lines)

    def test_chaos_fire_lands_in_trace(self):
        rec = events.reset()
        chaos.install(FaultPlan([Fault("batch_fetch", "nan", at_step=0)]))
        chaos.fire("batch_fetch", step=0,
                   batch={"x": np.ones(3, np.float32)})
        ev = [e for e in rec.tail() if e["name"] == "chaos"]
        assert ev and ev[0]["site"] == "batch_fetch" \
            and ev[0]["kind"] == "nan" and ev[0]["step"] == 0


class TestOverheadBounded:
    def test_recorder_off_is_ring_only_no_sync(self, tmp_path, monkeypatch):
        """Acceptance: with SPARKDL_EVENT_DIR unset, a recorded fit() does
        no event I/O and introduces no extra host syncs — exactly the
        loop's own: one wait per retired step (ISSUE 28) and the one
        block_until_ready at the end of fit()."""
        rec = events.reset()
        calls = []
        orig = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda tree: (calls.append(1), orig(tree))[1])
        res = XlaRunner(np=8).run(_fit)
        assert int(res["state"].step) == 4
        assert len(calls) == 4 + 1  # 4 steps retired + fit()'s final sync
        assert rec._file is None  # no stream was ever opened
        assert list(tmp_path.iterdir()) == []
        assert any(e["name"] == "step_compute" for e in rec.tail())


class TestMergeTimeline:
    def _write(self, d, rank, recs):
        with open(os.path.join(d, f"events_rank{rank}.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def test_merged_order_and_first_failure(self, tmp_path):
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "step_compute", "ph": "B", "rank": 0,
             "step": 0},
            {"t": 101.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 1},
            {"t": 102.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 2},
        ])
        self._write(d, 1, [
            {"t": 100.1, "name": "step_compute", "ph": "E", "rank": 1,
             "step": 0},
            {"t": 100.6, "name": "chaos", "ph": "P", "rank": 1,
             "site": "step_start", "kind": "preempt", "step": 1},
        ])
        with open(os.path.join(d, "postmortem_rank1.json"), "w") as f:
            json.dump({"t": 100.7, "rank": 1, "site": "fit", "step": 1,
                       "error": {"type": "InjectedPreemption",
                                 "kind": "retryable",
                                 "message": "UNAVAILABLE: injected"}}, f)
        hb = tmp_path / "hb"
        hb.mkdir()
        (hb / "rank0.hb").write_text(json.dumps({"step": 2, "time": 102.0}))
        tl = events.merge_timeline(d, heartbeat_dir=str(hb))
        assert tl["first_failing_rank"] == 1
        assert tl["first_failure"]["site"] == "step_start"
        assert tl["first_failure"]["step"] == 1
        assert tl["ranks"]["1"]["last_step"] == 1
        assert tl["ranks"]["0"]["last_step"] == 2
        assert tl["ranks"]["0"]["heartbeat"]["step"] == 2
        assert tl["first_stalled_rank"] == 1  # its trace ends earliest
        ts = [e["t"] for e in tl["events"]]
        assert ts == sorted(ts)  # one merged, time-ordered stream
        text = events.format_timeline(tl)
        assert "rank 1" in text and "step_start" in text

    def test_finished_rank_does_not_mask_real_failure(self, tmp_path):
        """Regression: rank 0 exhausts its data (end_of_data) BEFORE rank 1
        faults — the later, real fault must still be the first failure."""
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "data_fetch", "ph": "E", "rank": 0,
             "step": 5, "end_of_data": True, "dur_s": 0.001},
        ])
        self._write(d, 1, [
            {"t": 101.0, "name": "chaos", "ph": "P", "rank": 1,
             "site": "step_start", "kind": "preempt", "step": 4},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failing_rank"] == 1
        assert tl["first_failure"]["site"] == "step_start"

    def test_recovered_restart_does_not_outrank_terminal_fault(
            self, tmp_path):
        """An in-process restart RECOVERED from its error — the later
        fault that actually killed the gang must be the first failure."""
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "restart", "ph": "P", "rank": 0,
             "attempt": 1, "kind": "retryable",
             "error": "XlaRuntimeError: UNAVAILABLE (recovered)"},
            {"t": 150.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 40, "dur_s": 0.01},
        ])
        self._write(d, 1, [
            {"t": 140.0, "name": "chaos", "ph": "P", "rank": 1,
             "site": "step_start", "kind": "fatal", "step": 30},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failing_rank"] == 1
        assert tl["first_failure"]["site"] == "step_start"
        # ...but with no terminal evidence, the recovered error is named
        os.unlink(os.path.join(d, "events_rank1.jsonl"))
        tl = events.merge_timeline(d)
        assert tl["first_failing_rank"] == 0
        assert tl["first_failure"].get("recovered") is True

    def test_recovered_attempts_chaos_evidence_is_demoted_too(
            self, tmp_path):
        """Not just the restart event: the recovered attempt's own chaos/
        span-error evidence precedes its restart and must rank below the
        fault that killed the gang."""
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "chaos", "ph": "P", "rank": 0,
             "site": "step_start", "kind": "preempt", "step": 3},
            {"t": 100.5, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 3, "dur_s": 0.01,
             "error": "InjectedPreemption: UNAVAILABLE"},
            {"t": 101.0, "name": "restart", "ph": "P", "rank": 0,
             "attempt": 1, "kind": "retryable",
             "error": "InjectedPreemption: UNAVAILABLE"},
        ])
        self._write(d, 1, [
            {"t": 140.0, "name": "chaos", "ph": "P", "rank": 1,
             "site": "step_start", "kind": "fatal", "step": 30},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failing_rank"] == 1
        assert tl["first_failure"]["step"] == 30
        assert "recovered" not in tl["first_failure"]

    def test_hang_outranks_recovered_error_for_attribution(self, tmp_path):
        """A rank that RECOVERED its error must not be blamed for a later
        hang on another rank: with no terminal evidence, the stall
        heuristic names the rank that went quiet."""
        d = str(tmp_path)
        self._write(d, 0, [  # hangs after step 5 — goes quiet at t=150
            {"t": 150.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 5, "dur_s": 0.01},
        ])
        self._write(d, 1, [  # recovered at t=100, kept training to t=190
            {"t": 100.0, "name": "restart", "ph": "P", "rank": 1,
             "attempt": 1, "kind": "retryable",
             "error": "XlaRuntimeError: UNAVAILABLE (recovered)"},
            {"t": 190.0, "name": "step_compute", "ph": "E", "rank": 1,
             "step": 30, "dur_s": 0.01},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failing_rank"] == 0  # the hung rank, not rank 1
        assert tl["first_stalled_rank"] == 0
        text = events.format_timeline(tl)
        assert "rank 0 stalled first" in text
        assert "recovered in-process" in text  # narrative, not blame

    def test_stall_pick_consults_heartbeats(self, tmp_path):
        """A rank whose event stream froze (size cap / never streamed) but
        whose heartbeat is fresh must not be blamed as first-stalled."""
        d = str(tmp_path)
        self._write(d, 0, [{"t": 100.0, "name": "step_compute", "ph": "E",
                            "rank": 0, "step": 1, "dur_s": 0.01}])
        self._write(d, 1, [{"t": 200.0, "name": "step_compute", "ph": "E",
                            "rank": 1, "step": 50, "dur_s": 0.01}])
        hb = tmp_path / "hb"
        hb.mkdir()
        # rank 0 kept beating long after its trace froze; rank 1 went
        # silent at t=200 with no heartbeat at all
        (hb / "rank0.hb").write_text(
            json.dumps({"step": 300, "time": 500.0}))
        tl = events.merge_timeline(d, heartbeat_dir=str(hb))
        assert tl["first_stalled_rank"] == 1

    def test_empty_dir_yields_no_ranks(self, tmp_path):
        tl = events.merge_timeline(str(tmp_path))
        assert tl["ranks"] == {} and tl["first_failing_rank"] is None

    def test_clear_rank_files_globs_all_ranks(self, tmp_path):
        """A reused event dir from an earlier, LARGER gang must not leak a
        stale high-rank trace into the next attempt's timeline."""
        d = str(tmp_path)
        self._write(d, 7, [{"t": 1.0, "name": "chaos", "ph": "P",
                            "rank": 7, "site": "worker",
                            "kind": "fatal"}])
        (tmp_path / "postmortem_rank7.json").write_text("{}")
        events.clear_rank_files(d)  # rank 7 cleared (glob, not 0..np-1)
        assert list(tmp_path.iterdir()) == []

    def test_last_step_ignores_prefetch_feed_events(self, tmp_path):
        """A feed event can carry a step AHEAD of compute (a feeder that
        prefetches; a draw that failed before its step ran) — the timeline
        must report the last step the rank actually computed, not the
        feed position."""
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 1.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 10, "dur_s": 0.01},
            {"t": 1.1, "name": "data_fetch", "ph": "E", "rank": 0,
             "step": 14, "dur_s": 0.001},  # prefetcher, 4 steps ahead
        ])
        tl = events.merge_timeline(d)
        assert tl["ranks"]["0"]["last_step"] == 10

    def test_clear_rank_files_removes_stale_gang_timeline(self, tmp_path):
        (tmp_path / events.GANG_TIMELINE_FILE).write_text("{}")
        events.clear_rank_files(str(tmp_path))
        assert list(tmp_path.iterdir()) == []

    def test_torn_tail_line_is_skipped(self, tmp_path):
        d = str(tmp_path)
        with open(os.path.join(d, "events_rank0.jsonl"), "w") as f:
            f.write(json.dumps({"t": 1.0, "name": "a", "ph": "P",
                                "rank": 0, "step": 5}) + "\n")
            f.write('{"t": 2.0, "name": "tru')  # SIGKILL mid-write
        tl = events.merge_timeline(d)
        assert tl["ranks"]["0"]["n_events"] == 1
        assert tl["ranks"]["0"]["last_step"] == 5


_TIMELINE_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
from sparkdl_tpu.runner import chaos, events
rank = int(os.environ["SPARKDL_PROCESS_ID"])
for step in range(4):
    with events.span("step_compute", step=step):
        try:
            chaos.fire("step_start", step=step)
        except Exception as e:
            events.postmortem(e, site="step_start", step=step)
            raise
        time.sleep(0.05)
time.sleep(60)  # survivor: wait for the gang kill
"""


class TestGangTimeline:
    def test_supervise_failure_carries_merged_timeline(self, tmp_path):
        """Acceptance: a chaos-injected gang failure under supervise()
        produces a merged, time-ordered gang-timeline postmortem naming
        the first-failing rank, its last step, and the fault site."""
        script = tmp_path / "w.py"
        script.write_text(_TIMELINE_WORKER.format(repo=_REPO))
        event_dir = tmp_path / "events"
        plan = FaultPlan([Fault("step_start", "preempt", at_step=2,
                                rank=1)])
        with pytest.raises(GangFailure) as ei:
            supervise(str(script), np=2, timeout_s=120.0, max_restarts=0,
                      backoff_s=0.05, poll_s=0.25, plan=plan,
                      event_dir=str(event_dir))
        err = ei.value
        assert err.timeline is not None
        assert err.timeline["first_failing_rank"] == 1
        assert err.timeline["first_failure"]["site"] == "step_start"
        assert err.timeline["first_failure"]["step"] == 2
        assert err.timeline["ranks"]["1"]["last_step"] == 2
        ts = [e["t"] for e in err.timeline["events"]]
        assert ts == sorted(ts)
        # written next to the salvaged stderr, and named in the message
        merged = event_dir / events.GANG_TIMELINE_FILE
        assert merged.exists()
        assert json.loads(merged.read_text())["first_failing_rank"] == 1
        assert "gang timeline" in str(err)
        assert "first failure on rank 1" in str(err)


class TestGangEventDirIsolation:
    def test_supervise_does_not_clobber_driver_event_stream(
            self, tmp_path, monkeypatch):
        """A driver with its own recorder streaming to SPARKDL_EVENT_DIR
        must keep its events_rank0.jsonl across supervise(): the gang gets
        a subdir, so per-attempt clearing can't unlink the driver's live
        file or conflate driver events with worker rank 0's."""
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "0")
        rec = events.reset()
        rec.event("driver_alive")
        script = tmp_path / "w.py"
        script.write_text("import sys; sys.exit(1)\n")
        with pytest.raises(GangFailure):
            supervise(str(script), np=1, timeout_s=30.0, max_restarts=0,
                      backoff_s=0.05, poll_s=0.25)
        rec.event("driver_still_alive")
        lines = (tmp_path / "events_rank0.jsonl").read_text().splitlines()
        assert [json.loads(ln)["name"] for ln in lines] == \
            ["driver_alive", "driver_still_alive"]
        # the gang ran in its own unique subdir namespace — and since the
        # jax-free worker streamed nothing, the empty dir was pruned on
        # the give-up path rather than left as clutter
        assert not any(p.name.startswith("gang-")
                       for p in tmp_path.iterdir() if p.is_dir())


class TestHeartbeatSatellite:
    def test_touch_heartbeat_is_atomic_json(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SPARKDL_HEARTBEAT_DIR", str(tmp_path))
        monkeypatch.setenv("SPARKDL_PROCESS_ID", "2")
        t0 = time.time()
        metrics_lib.touch_heartbeat(7)
        body = json.loads((tmp_path / "rank2.hb").read_text())
        assert body["step"] == 7
        assert t0 - 1 <= body["time"] <= time.time() + 1
        # no tmp droppings left behind (the os.replace committed)
        assert [p.name for p in tmp_path.iterdir()] == ["rank2.hb"]

    def test_watchdog_parses_json_and_legacy_bodies(self, tmp_path):
        (tmp_path / "rank0.hb").write_text(
            json.dumps({"step": 12, "time": 1.0}))
        (tmp_path / "rank1.hb").write_text("34")  # pre-PR-2 bare body
        ages = launcher._heartbeat_ages(str(tmp_path), 2, time.time())
        assert ages[0][1] == "12"
        assert ages[1][1] == "34"


class TestMetricsLoggerSatellite:
    def test_tb_unavailable_falls_back_to_log(self, tmp_path, monkeypatch,
                                              caplog):
        monkeypatch.setitem(sys.modules, "tensorboardX", None)
        logger = MetricsLogger(str(tmp_path / "tb"))
        assert logger._tb is None  # fell back without raising
        with caplog.at_level("INFO", logger="sparkdl_tpu.runner"):
            logger.log(1, {"loss": 0.5})
        assert "loss" in caplog.text
        logger.close()

    def test_non_numeric_values_do_not_crash(self, caplog):
        logger = MetricsLogger(None)
        with caplog.at_level("INFO", logger="sparkdl_tpu.runner"):
            logger.log(2, {"loss": np.float32(1.5), "note": "warmup",
                           "arr": np.ones(3)})  # .item-bearing, not scalar
        assert "warmup" in caplog.text
        logger.close()

    def test_close_is_idempotent(self, tmp_path, monkeypatch):
        # A fake tensorboardX: importing the real one costs ~20s of the
        # tier-1 budget (ISSUE 10 headroom satellite) and the close
        # contract is about MetricsLogger's state machine, not the
        # writer. The fallback path has its own test above.
        closes = []

        class _FakeWriter:
            def __init__(self, log_dir):
                self.log_dir = log_dir

            def add_scalar(self, *a):
                pass

            def close(self):
                closes.append(1)

        fake = types.ModuleType("tensorboardX")
        fake.SummaryWriter = _FakeWriter
        monkeypatch.setitem(sys.modules, "tensorboardX", fake)
        logger = MetricsLogger(str(tmp_path / "tb"))
        assert logger._tb is not None
        logger.close()
        logger.close()  # second close must be a no-op
        assert closes == [1]  # the writer closed exactly once
        assert logger._tb is None
        logger.log(1, {"loss": 1.0})  # and logging still works (text path)

    def test_log_summary_flattens_nested_blocks(self, caplog):
        logger = MetricsLogger(None)
        with caplog.at_level("INFO", logger="sparkdl_tpu.runner"):
            logger.log_summary(10, {"examples_per_sec": 5.0, "mfu": None,
                                    "step_time": {"p50_s": 0.1}})
        assert "step_time_p50_s" in caplog.text
        assert "mfu" not in caplog.text  # None dropped, not logged as null
        logger.close()


class TestTraceSatellite:
    def test_region_failure_still_stops_profiler(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d: calls.append("start"))

        def stop():
            calls.append("stop")
            raise RuntimeError("No profiler session running")

        monkeypatch.setattr(jax.profiler, "stop_trace", stop)
        # failed region: stop IS attempted, its error does not mask ours
        with pytest.raises(ValueError, match="user bug"):
            with metrics_lib.trace("/tmp/x"):
                raise ValueError("user bug")
        assert calls == ["start", "stop"]

    def test_stop_error_propagates_when_region_succeeded(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

        def stop():
            raise RuntimeError("profiler broke")

        monkeypatch.setattr(jax.profiler, "stop_trace", stop)
        with pytest.raises(RuntimeError, match="profiler broke"):
            with metrics_lib.trace("/tmp/x"):
                pass

    def test_trace_emits_event_with_dir(self, monkeypatch):
        rec = events.reset()
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        ctx = XlaRunner(np=8).make_context()
        with ctx.trace("/tmp/sparkdl_trace_test"):
            pass
        ev = [e for e in rec.tail() if e["name"] == "profile_trace"]
        assert ev and ev[0]["trace_dir"] == "/tmp/sparkdl_trace_test"


class _Mirror:
    """A span mirror that records what it is asked to do."""

    def __init__(self, log, fail=()):
        self.log, self.fail = log, fail

    def __call__(self, name):
        if "make" in self.fail:
            raise RuntimeError("mirror factory broke")
        mirror = self

        class _Ctx:
            def __enter__(self):
                mirror.log.append(("enter", name))
                if "enter" in mirror.fail:
                    raise RuntimeError("mirror enter broke")

            def __exit__(self, *exc):
                mirror.log.append(("exit", name))
                if "exit" in mirror.fail:
                    raise RuntimeError("mirror exit broke")
        return _Ctx()


class TestSpanMirror:
    """ISSUE 27: every span also opens a profiler annotation of its own
    name, through a hook that events.py (jax-free) does not know."""

    @pytest.fixture(autouse=True)
    def _restore_mirror(self):
        saved = events._MIRROR
        yield
        events.set_span_mirror(saved)

    def test_mirror_entered_and_exited_once_per_span_with_its_name(self):
        log = []
        events.set_span_mirror(_Mirror(log))
        with events.span("outer", step=1):
            with events.span("inner"):
                pass
        assert log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
        # the region's own exception still closes the mirror, and escapes
        log.clear()
        with pytest.raises(ValueError):
            with events.span("bad"):
                raise ValueError("user bug")
        assert log == [("enter", "bad"), ("exit", "bad")]

    @pytest.mark.parametrize("fail", ["make", "enter", "exit"])
    def test_raising_mirror_never_reaches_the_caller(self, fail):
        log = []
        events.set_span_mirror(_Mirror(log, fail={fail}))
        rec = events.get_recorder()
        with events.span("region", step=3) as sp:
            pass
        assert sp.seconds >= 0
        ends = [e for e in rec.tail() if e["ph"] == "E"]
        assert [e["name"] for e in ends] == ["region"]
        assert "error" not in ends[0]

    def test_span_closed_on_another_thread_is_not_mirrored(self):
        import threading
        log = []
        events.set_span_mirror(_Mirror(log))
        sp = events.span("handed_over")
        sp.__enter__()
        t = threading.Thread(target=sp.__exit__, args=(None, None, None))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert log == [("enter", "handed_over")]
        assert [e["ph"] for e in events.get_recorder().tail()] == ["B", "E"]

    def test_no_mirror_means_plain_spans(self):
        events.set_span_mirror(None)
        with events.span("plain"):
            pass
        assert [e["ph"] for e in events.get_recorder().tail()] == ["B", "E"]

    def test_runner_installs_the_profilers_annotation(self):
        assert events._MIRROR is jax.profiler.TraceAnnotation

    def test_events_module_alone_imports_no_jax(self):
        """The launcher's rule: events.py by itself is stdlib-only — the
        mirror is installed from runner.metrics, which has jax anyway."""
        import subprocess
        path = os.path.join(_REPO, "sparkdl_tpu", "runner", "events.py")
        code = (
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('ev', {path!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['ev'] = m\n"
            "spec.loader.exec_module(m)\n"
            "with m.span('s'):\n    pass\n"
            "assert m._MIRROR is None and len(m.get_recorder().tail()) == 2\n"
            "bad = [k for k in sys.modules if k.split('.')[0] in"
            " ('jax', 'jaxlib', 'numpy')]\n"
            "assert not bad, bad\n")
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=60)
        assert p.returncode == 0, p.stderr

    def test_default_ring_holds_a_whole_benchmark_window(self):
        assert events.FlightRecorder().ring.maxlen == 4096


class TestLossFetchSpan:
    """ISSUE 27: the metrics fetch at a log boundary carries a span."""

    def test_fit_emits_loss_fetch_at_each_log_boundary(self):
        rec = events.get_recorder()
        XlaRunner(np=8).run(lambda ctx: _fit(ctx, num_steps=25, log_every=10))
        ends = [e for e in rec.tail()
                if e["name"] == "loss_fetch" and e["ph"] == "E"]
        assert [e["step"] for e in ends] == [10, 20, 25]
        assert all(e["every"] == 10 and e["dur_s"] >= 0 for e in ends)
        # nothing else in the loop was renamed: the benchmark reads these
        names = {e["name"] for e in rec.tail()}
        assert {"data_fetch", "shard_put", "step_compute"} <= names
        # each fetch follows the dispatch of the step AFTER the one it
        # names (ISSUE 28: step_compute counts from 0, loss_fetch from 1)
        order = [(e["name"], e.get("step")) for e in rec.tail()
                 if e["ph"] == "E" and e["name"] in ("step_compute",
                                                     "loss_fetch")]
        assert order.index(("loss_fetch", 10)) == \
            order.index(("step_compute", 10)) + 1


class TestStepMetricsEvent:
    """ISSUE 29: each log boundary's metrics land in the ring, the loss
    function's own keys among them."""

    def test_fit_records_one_step_metrics_event_per_boundary(self):
        def loss_fn(params, apply_fn, batch):
            loss, aux = softmax_cross_entropy_loss()(params, apply_fn, batch)
            # "name" would overwrite the record's own: it is left out
            return loss, {**aux, "rows_seen": np.float32(16), "name": 7.0}

        rec = events.get_recorder()
        res = XlaRunner(np=8).run(lambda ctx: ctx.fit(
            loss_fn=loss_fn, params=_params(), tx=optax.sgd(0.1),
            apply_fn=_linear_apply, data=_data(), num_steps=7, log_every=3))
        got = [e for e in rec.tail() if e["name"] == "step_metrics"]
        assert [e["step"] for e in got] == [3, 6, 7]
        assert all(e["ph"] == "P" and e["rows_seen"] == 16.0 for e in got)
        assert [e["loss"] for e in got] == [h["loss"] for h in res["history"]]
        assert all("examples_per_sec_per_chip" in e for e in got)
        # each follows its boundary's loss_fetch directly
        names = [(e["name"], e.get("step")) for e in rec.tail()
                 if e["name"] == "step_metrics" or
                 (e["name"] == "loss_fetch" and e["ph"] == "E")]
        assert names == [(n, s) for s in (3, 6, 7)
                         for n in ("loss_fetch", "step_metrics")]


class TestStepRetire:
    """ISSUE 28: fit retires each step one step behind its dispatch. Ring
    order only — no timing."""

    @staticmethod
    def _ends(rec):
        return [(e["name"], e.get("step")) for e in rec.tail()
                if e["ph"] == "E"]

    def test_retire_sits_between_next_dispatch_and_the_fetch_after(self):
        rec = events.get_recorder()
        XlaRunner(np=8).run(lambda ctx: _fit(ctx, num_steps=6, log_every=2))
        ends = self._ends(rec)
        # step_retire and loss_fetch count steps from 1; step_compute and
        # data_fetch count batches from 0: step n+1 is step_compute n
        for n in range(1, 5):
            at = ends.index(("step_retire", n))
            assert ends.index(("step_compute", n)) < at \
                < ends.index(("data_fetch", n + 1)), n
        # step 1 is retired after the SECOND dispatch, not after its own
        assert ends.index(("step_retire", 1)) > \
            ends.index(("step_compute", 1))
        # a boundary step's fetch follows its own retire directly
        for n in (2, 4, 6):
            assert ends.index(("loss_fetch", n)) == \
                ends.index(("step_retire", n)) + 1

    @pytest.mark.parametrize("num_steps,n_batches", [(6, 64), (100, 6)],
                             ids=["num_steps_reached", "data_ran_out"])
    def test_last_step_is_retired_and_logged(self, num_steps, n_batches):
        rec = events.get_recorder()
        res = XlaRunner(np=8).run(lambda ctx: ctx.fit(
            loss_fn=softmax_cross_entropy_loss(), params=_params(),
            tx=optax.sgd(0.1), apply_fn=_linear_apply,
            data=_data(n_batches=n_batches), num_steps=num_steps,
            log_every=3))
        assert int(res["state"].step) == 6
        ends = self._ends(rec)
        assert [s for name, s in ends if name == "step_retire"] == \
            [1, 2, 3, 4, 5, 6]
        assert [s for name, s in ends if name == "loss_fetch"] == [3, 6]
        assert [h["step"] for h in res["history"]] == [3, 6]
        assert res["meter"].steps == 6
        # the last retire comes after everything the loop dispatched
        assert ends.index(("step_retire", 6)) > \
            ends.index(("step_compute", 5))

    def test_one_step_fit_retires_it_after_the_loop(self):
        rec = events.get_recorder()
        res = XlaRunner(np=8).run(
            lambda ctx: _fit(ctx, num_steps=1, log_every=10))
        assert [h["step"] for h in res["history"]] == [1]
        assert ("step_retire", 1) in self._ends(rec)


class TestDeviceTimeByScope:
    """ISSUE 27: device seconds by named scope, a pure function of
    (scope, start, duration) triples, and the .xplane.pb reader under it."""

    def test_scope_seconds_by_prefix_and_phase(self):
        from sparkdl_tpu.runner import analysis
        ms = 1_000_000
        triples = [
            ("jit(step)/jit(main)/jvp(Net)/Block_0/Conv_0/conv:", 0, 4 * ms),
            ("jit(step)/jit(main)/jvp(Net)/Block_0/BatchNorm_0/reduce:",
             4 * ms, 1 * ms),
            ("jit(step)/jit(main)/transpose(jvp(Net))/Block_0/Conv_0/conv:",
             5 * ms, 8 * ms),
            ("jit(step)/jit(main)/optimizer_update/mul:", 13 * ms, 2 * ms),
            ("jit(step)/shard_map/grad_allreduce/psum:", 15 * ms, 1 * ms),
            ("", 16 * ms, 3 * ms),
            ("jit(step)/jit(main)/add:", 19 * ms, 1 * ms),
        ]
        rep = analysis.scope_seconds(triples, depth=2)
        assert rep["total_s"] == pytest.approx(0.020)
        assert rep["by_scope"] == pytest.approx({
            "jvp(Net)/Block_0": 0.005, "transpose(jvp(Net))/Block_0": 0.008,
            "optimizer_update": 0.002, "grad_allreduce": 0.001,
            "(unscoped)": 0.004})
        assert rep["by_phase"] == pytest.approx({
            "forward": 0.005, "backward": 0.008, "optimizer_update": 0.002,
            "grad_allreduce": 0.001, "unscoped": 0.004})
        deep = analysis.scope_seconds(triples, depth=3)["by_scope"]
        assert deep["jvp(Net)/Block_0/Conv_0"] == pytest.approx(0.004)
        assert deep["jvp(Net)/Block_0/BatchNorm_0"] == pytest.approx(0.001)

    def test_an_enclosing_operation_counts_its_self_time(self):
        from sparkdl_tpu.runner import analysis
        triples = [("jit(f)/jvp(Net)/scan/while:", 0, 100),
                   ("jit(f)/jvp(Net)/scan/Dense_0/dot:", 10, 30),
                   ("jit(f)/optimizer_update/mul:", 50, 40)]
        rep = analysis.scope_seconds(triples, depth=1)
        assert rep["total_s"] == pytest.approx(100e-9)
        assert rep["by_scope"] == pytest.approx(
            {"jvp(Net)": 60e-9, "optimizer_update": 40e-9})

    def test_reads_the_scope_off_an_operations_metadata(self, tmp_path):
        """The scope is a stat of the operation's METADATA (as a string, or
        as a reference to another stat's name), which ProfileData's event
        stats do not carry: read from the wire."""
        from jax.profiler import ProfileData
        from sparkdl_tpu.runner import analysis
        text = """
        planes { name: "/device:TPU:0"
          lines { name: "XLA Ops" timestamp_ns: 100
            events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000
                     stats { metadata_id: 2 int64_value: 7 } }
            events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
            events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 } }
          lines { name: "XLA Modules" timestamp_ns: 100
            events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
          event_metadata { key: 1 value { id: 1 name: "fusion.1" stats {
            metadata_id: 1 str_value: "jit(f)/optimizer_update/mul:" } } }
          event_metadata { key: 2 value { id: 2 name: "fusion.2" stats {
            metadata_id: 1 ref_value: 3 } } }
          event_metadata { key: 3 value { id: 3 name: "copy.3" } }
          stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
          stat_metadata { key: 2 value { id: 2 name: "run_id" } }
          stat_metadata { key: 3 value { id: 3
            name: "jit(f)/transpose(jvp(Net))/Dense_0/dot_general:" } } }
        planes { name: "/host:CPU" lines { name: "python" } }"""
        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(text))
        ops = analysis.read_xplane_ops(str(d / "host.xplane.pb"))
        assert list(ops) == ["/device:TPU:0"]
        assert ops["/device:TPU:0"][0] == (
            "fusion.1", "jit(f)/optimizer_update/mul:", 1100.0, 2000.0)
        rep = analysis.device_time_by_scope(str(tmp_path), depth=2)
        assert rep["planes"] == ["/device:TPU:0"]
        assert rep["by_phase"]["backward"] == pytest.approx(3e-6)
        assert rep["by_phase"]["optimizer_update"] == pytest.approx(2e-6)
        assert rep["by_phase"]["unscoped"] == pytest.approx(1e-6)
        assert "transpose(jvp(Net))/Dense_0" in rep["by_scope"]
        assert "optimizer_update" in analysis.format_scope_report(rep)
        with pytest.raises(FileNotFoundError):
            analysis.device_time_by_scope(str(tmp_path / "plugins" / "none"))


class TestDegradations:
    """ISSUE 4: survived-fault events (retry / quarantine / rollback) are
    timeline NARRATIVE — collected, rendered, never failure evidence."""

    def _write(self, d, rank, recs):
        with open(os.path.join(d, f"events_rank{rank}.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def _recs(self):
        return [
            {"t": 100.0, "name": "retry", "ph": "P", "rank": 0,
             "stage": "dispatch", "attempt": 1,
             "error": "InjectedPreemption: UNAVAILABLE"},
            {"t": 100.5, "name": "quarantine", "ph": "P", "rank": 0,
             "rows": 3, "error_class": "ValueError", "total": 3},
            {"t": 101.0, "name": "checkpoint_rollback", "ph": "P",
             "rank": 0, "from_step": 4, "to_step": 2},
            {"t": 102.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 3},
        ]

    def test_merge_timeline_collects_degradations(self, tmp_path):
        d = str(tmp_path)
        self._write(d, 0, self._recs() + [
            {"t": 103.0, "name": "chaos", "ph": "P", "rank": 0,
             "site": "step_start", "kind": "preempt", "step": 4}])
        tl = events.merge_timeline(d)
        kinds = [dg["kind"] for dg in tl["degradations"]]
        assert kinds == ["retry", "quarantine", "checkpoint_rollback"]
        # the retry's error text did NOT become failure evidence: the
        # later chaos fire is still the first failure
        assert tl["first_failure"]["site"] == "step_start"
        assert tl["first_failure"]["t"] == 103.0
        rendered = events.format_timeline(tl)
        assert "survived degradations" in rendered
        assert "checkpoint_rollback x1" in rendered

    def test_collect_degradations_success_path(self, tmp_path):
        d = str(tmp_path)
        self._write(d, 0, self._recs())
        self._write(d, 1, [{"t": 99.0, "name": "retry", "ph": "P",
                            "rank": 1, "stage": "fetch", "attempt": 1}])
        out = events.collect_degradations(d)
        assert [r["name"] for r in out] == [
            "retry", "retry", "quarantine", "checkpoint_rollback"]
        assert out[0]["rank"] == 1  # time-ordered across ranks
        assert events.collect_degradations(str(tmp_path / "missing")) == []
