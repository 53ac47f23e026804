"""Live telemetry plane tests (ISSUE 6): registry, stage accountant math,
exporter lifecycle, Prometheus endpoint, gang aggregation, bottleneck
attribution, doc-drift lint — and the overhead pin that the disabled
plane stays ≈ free (PR 2's rule: observability must cost nothing when
off).

Fast and jax-free where possible: the registry/accountant/analysis tests
feed synthetic records; only the meter-summary and fit-integration tests
touch jax (already resident via conftest). The end-to-end smoke
(scripts/obs_smoke.py: live snapshot mid-run + bottleneck report naming
the decode stage) is slow-marked in test_chaos.py.
"""

import json
import os
import re
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from sparkdl_tpu.runner import analysis, events, telemetry
from sparkdl_tpu.runner.telemetry import (MetricsRegistry, StageAccountant,
                                          render_prometheus)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane():
    """Every test gets a stopped, fresh plane and a clean recorder; env
    arming from one test must not leak into the next."""
    telemetry.reset()
    yield
    telemetry.reset()
    events.reset()


def _span_records(stage, pairs, rank=0, **attrs):
    """Synthetic B/E record pairs: pairs = [(t0, t1), ...]."""
    recs = []
    for t0, t1 in pairs:
        recs.append({"t": t0, "name": stage, "ph": "B", "rank": rank})
        recs.append({"t": t1, "name": stage, "ph": "E", "rank": rank,
                     "dur_s": round(t1 - t0, 6), **attrs})
    return recs


class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(2.5)
        reg.gauge("g").set(3)
        reg.gauge("g").set(1)  # value drops, max holds
        reg.histogram("h", buckets=(0.1, 1.0)).observe(0.05)
        reg.histogram("h").observe(0.5)
        reg.histogram("h").observe(5.0)
        snap = reg.snapshot()
        assert snap["counters"]["c"] == 3.5
        assert snap["gauges"]["g"] == {"value": 1, "max": 3}
        h = snap["histograms"]["h"]
        assert h["count"] == 3 and abs(h["sum"] - 5.55) < 1e-9
        # cumulative buckets: le=0.1 -> 1, le=1.0 -> 2 (+Inf implicit = 3)
        assert h["buckets"] == [1, 2]

    def test_counter_inc_is_thread_safe(self):
        reg = MetricsRegistry()
        c = reg.counter("n")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(4)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value == 4000

    def test_prometheus_rendering(self):
        reg = MetricsRegistry()
        reg.counter("rows").inc(7)
        reg.gauge("depth").set(2)
        reg.histogram("lat", buckets=(0.5,)).observe(0.3)
        snap = {"rank": 3, "elapsed_s": 1.5,
                "stages": {"decode": {"busy_s": 0.5, "wall_busy_s": 0.4,
                                      "busy_frac": 0.27, "count": 9,
                                      "rows": 36, "bytes": 1024,
                                      "errors": 0, "active": 1,
                                      "max_concurrency": 2}}}
        snap.update(reg.snapshot())
        txt = render_prometheus(snap)
        assert '# TYPE sparkdl_stage_busy_seconds counter' in txt
        assert 'sparkdl_stage_busy_seconds{rank="3",stage="decode"} 0.5' \
            in txt
        assert 'sparkdl_stage_busy_frac{rank="3",stage="decode"} 0.27' \
            in txt
        assert 'sparkdl_rows_total{rank="3"} 7' in txt
        assert 'sparkdl_depth{rank="3"} 2' in txt
        # histogram label values quoted too — one unquoted rank= fails
        # the WHOLE scrape, not just the histogram family
        assert 'sparkdl_lat_bucket{le="0.5",rank="3"} 1' in txt
        assert 'sparkdl_lat_bucket{le="+Inf",rank="3"} 1' in txt
        assert 'sparkdl_lat_count{rank="3"} 1' in txt
        assert re.search(r'rank=(?!")', txt) is None  # no unquoted rank


class TestStageAccountant:
    def test_busy_books_on_synthetic_spans(self):
        """Two overlapping decode spans: busy_s sums both (slot-seconds),
        wall_busy_s is the union — the wall is counted once."""
        acc = StageAccountant()
        # decode A [0, 2], decode B [1, 3] -> busy 4.0, union 3.0
        for r in [{"t": 0.0, "name": "decode", "ph": "B"},
                  {"t": 1.0, "name": "decode", "ph": "B"},
                  {"t": 2.0, "name": "decode", "ph": "E", "dur_s": 2.0,
                   "rows": 8, "bytes": 100},
                  {"t": 3.0, "name": "decode", "ph": "E", "dur_s": 2.0,
                   "rows": 8, "bytes": 100},
                  # dispatch [3, 4]: closes the elapsed window at 4.0
                  {"t": 3.0, "name": "dispatch", "ph": "B"},
                  {"t": 4.0, "name": "dispatch", "ph": "E", "dur_s": 1.0,
                   "error": "boom"}]:
            acc.on_event(r)
        snap = acc.snapshot(now=4.0)
        assert snap["elapsed_s"] == 4.0
        d = snap["stages"]["decode"]
        assert d["busy_s"] == 4.0
        assert d["wall_busy_s"] == 3.0
        assert d["busy_frac"] == 0.75
        assert d["rows"] == 16 and d["bytes"] == 200
        assert d["max_concurrency"] == 2 and d["active"] == 0
        dis = snap["stages"]["dispatch"]
        assert dis["errors"] == 1 and dis["busy_frac"] == 0.25
        # all fractions in [0, 1] — the acceptance-criteria invariant
        assert all(0.0 <= s["busy_frac"] <= 1.0
                   for s in snap["stages"].values())

    def test_open_span_counts_as_busy_in_live_snapshot(self):
        """A wedged stage with an open span must read busy mid-run, not
        idle — the live view is the whole point of the plane."""
        acc = StageAccountant()
        acc.on_event({"t": 10.0, "name": "dispatch", "ph": "B"})
        snap = acc.snapshot(now=40.0)
        st = snap["stages"]["dispatch"]
        assert st["active"] == 1
        assert st["wall_busy_s"] == 30.0
        assert snap["elapsed_s"] == 30.0
        assert st["busy_frac"] == 1.0

    def test_point_events_tallied(self):
        acc = StageAccountant()
        acc.on_event({"t": 1.0, "name": "quarantine", "ph": "P", "rows": 3})
        acc.on_event({"t": 2.0, "name": "quarantine", "ph": "P", "rows": 2})
        acc.on_event({"t": 2.5, "name": "retry", "ph": "P"})
        snap = acc.snapshot(now=3.0)
        assert snap["events"] == {"quarantine": 2, "retry": 1}
        assert snap["event_rows"] == {"quarantine": 5}

    def test_tee_feeds_accountant_through_recorder(self):
        telemetry.start()  # no dir/port: tee only
        rec = events.reset()  # fresh ring; module-level tee survives reset
        with events.span("pad", rows=4):
            pass
        with events.span("pad", rows=4):
            pass
        snap = telemetry.accountant().snapshot()
        assert snap["stages"]["pad"]["count"] == 2
        assert snap["stages"]["pad"]["rows"] == 8
        assert rec.tail()  # the ring saw them too


class TestExporterLifecycle:
    def test_snapshot_files_appear_and_survive_stop(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv("SPARKDL_METRICS_INTERVAL_S", "0.05")
        d = str(tmp_path / "m")
        telemetry.start(metrics_dir=d)
        with events.span("decode", rows=2):
            pass
        deadline = time.time() + 5.0
        path = os.path.join(d, "metrics_rank0.json")
        while not os.path.exists(path) and time.time() < deadline:
            time.sleep(0.02)
        assert os.path.exists(path), "exporter never wrote a snapshot"
        snap = json.load(open(path))
        assert snap["stages"]["decode"]["count"] == 1
        # SIGKILL-survivability proxy: the latest file is always a
        # COMPLETE atomic write — no .tmp leftovers, parseable JSON
        # (the writer is tmp+os.replace; a kill between ticks leaves the
        # previous complete snapshot).
        telemetry.stop()
        final = json.load(open(path))
        assert final["stages"]["decode"]["count"] == 1
        hist = open(os.path.join(d, "metrics_rank0.jsonl")).readlines()
        assert all(json.loads(ln) for ln in hist)

    def test_start_and_stop_are_idempotent(self, tmp_path):
        d = str(tmp_path / "m")
        p1 = telemetry.start(metrics_dir=d)
        p2 = telemetry.start(metrics_dir=str(tmp_path / "other"))
        assert p1 is p2
        assert p2.metrics_dir == d  # second start did not rewire
        assert telemetry.enabled()
        telemetry.stop()
        telemetry.stop()  # no-op
        assert not telemetry.enabled()
        # tee removed: new spans no longer account
        before = telemetry.accountant().snapshot()["stages"].get(
            "pad", {}).get("count", 0)
        with events.span("pad"):
            pass
        after = telemetry.accountant().snapshot()["stages"].get(
            "pad", {}).get("count", 0)
        assert after == before

    def test_http_endpoint_serves_prometheus_and_json(self):
        telemetry.start(port=0)  # ephemeral
        port = telemetry.server_port()
        assert port
        with events.span("fetch", rows=4):
            pass
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert 'sparkdl_stage_count{rank="0",stage="fetch"} 1' in txt
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert js["stages"]["fetch"]["rows"] == 4
        telemetry.stop()

    def test_healthz_endpoint(self):
        """ISSUE 17: /healthz answers 200 with pid + uptime next to
        /metrics — the cheap liveness probe orchestrators can poll at a
        rate the full snapshot endpoint shouldn't pay."""
        telemetry.start(port=0)
        port = telemetry.server_port()
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert resp.status == 200
        body = json.loads(resp.read())
        assert body["status"] == "ok"
        assert body["pid"] == os.getpid()
        assert body["rank"] == 0
        assert isinstance(body["uptime_s"], (int, float))
        assert body["uptime_s"] >= 0
        # unknown paths still 404 — /healthz did not become a catch-all
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
        telemetry.stop()

    def test_healthz_bind_failure_degrades(self, tmp_path):
        """A taken port must degrade to no-endpoint (port=None) while the
        rest of the plane — exporter, registry, tee — keeps running; the
        same never-kill rule the /metrics endpoint pins."""
        import socket
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        sock.listen(1)
        taken = sock.getsockname()[1]
        try:
            telemetry.start(metrics_dir=str(tmp_path / "m"), port=taken)
            assert telemetry.server_port() is None  # degraded, not dead
            assert telemetry.enabled()
            with events.span("pad"):
                pass
            telemetry.flush_snapshot()
            snap = json.load(
                open(os.path.join(str(tmp_path / "m"),
                                  "metrics_rank0.json")))
            assert snap["stages"]["pad"]["count"] == 1
        finally:
            sock.close()
            telemetry.stop()

    def test_maybe_start_from_env(self, tmp_path, monkeypatch):
        assert telemetry.maybe_start_from_env() is False  # nothing set
        assert not telemetry.enabled()
        monkeypatch.setenv("SPARKDL_METRICS_DIR", str(tmp_path / "m"))
        assert telemetry.maybe_start_from_env() is True
        assert telemetry.enabled()

    def test_unparseable_port_alone_does_not_arm(self, monkeypatch):
        """SPARKDL_METRICS_PORT=abc with no metrics dir: arming would pay
        the tee + accountant with no exporter and no endpoint — all
        overhead, no telemetry. Stay off."""
        monkeypatch.delenv("SPARKDL_METRICS_DIR", raising=False)
        monkeypatch.setenv("SPARKDL_METRICS_PORT", "abc")
        assert telemetry.maybe_start_from_env() is False
        assert not telemetry.enabled()
        assert events._TEES == []

    def test_history_capped_latest_keeps_updating(self, tmp_path,
                                                  monkeypatch):
        """SPARKDL_METRICS_MAX_MB bounds the .jsonl history (same rule as
        SPARKDL_EVENT_MAX_MB): one truncation marker, no further growth —
        while the atomic latest-snapshot file keeps updating."""
        monkeypatch.setenv("SPARKDL_METRICS_MAX_MB", "0.0002")  # ~200 B
        monkeypatch.setenv("SPARKDL_METRICS_INTERVAL_S", "60")
        d = str(tmp_path / "m")
        telemetry.start(metrics_dir=d)
        for _ in range(20):
            telemetry.flush_snapshot()
        hpath = os.path.join(d, "metrics_rank0.jsonl")
        lines = open(hpath).read().splitlines()
        marker = json.loads(lines[-1])
        assert marker["name"] == "metrics_history_truncated"
        assert sum(1 for ln in lines
                   if '"metrics_history_truncated"' in ln) == 1
        n = len(lines)
        telemetry.flush_snapshot()
        telemetry.flush_snapshot()
        assert len(open(hpath).read().splitlines()) == n  # capped
        # the latest file is still a live, parseable snapshot
        with events.span("decode"):
            pass
        telemetry.flush_snapshot()
        latest = json.load(open(os.path.join(d, "metrics_rank0.json")))
        assert latest["stages"]["decode"]["count"] == 1
        telemetry.stop()

    def test_concurrent_flush_and_tick_never_tear_snapshot(self, tmp_path,
                                                           monkeypatch):
        """flush_snapshot (fit_end/postmortem/atexit) races the exporter
        tick in the same process; the snapshot lock must keep the latest
        file and every history line parseable."""
        monkeypatch.setenv("SPARKDL_METRICS_INTERVAL_S", "0.05")
        d = str(tmp_path / "m")
        telemetry.start(metrics_dir=d)
        with events.span("pad"):
            pass

        def flusher():
            for _ in range(25):
                telemetry.flush_snapshot()

        threads = [threading.Thread(target=flusher) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        telemetry.stop()
        snap = json.load(open(os.path.join(d, "metrics_rank0.json")))
        assert snap["stages"]["pad"]["count"] == 1
        for ln in open(os.path.join(d, "metrics_rank0.jsonl")):
            json.loads(ln)  # no torn/interleaved line


class TestOverheadBounded:
    def test_disabled_plane_is_free(self, tmp_path, monkeypatch):
        """ISSUE 6 acceptance: with SPARKDL_METRICS_DIR unset the plane
        adds no hot-path work — no tee registered, no exporter thread, no
        registry traffic, no files; mirrors PR 2's recorder-off pin."""
        monkeypatch.delenv("SPARKDL_METRICS_DIR", raising=False)
        monkeypatch.delenv("SPARKDL_METRICS_PORT", raising=False)
        assert telemetry.maybe_start_from_env() is False
        assert events._TEES == []  # emit()'s per-event check is one falsy
        n_threads = threading.active_count()
        rec = events.reset()
        for i in range(200):
            with events.span("pad", rows=1):
                pass
        assert threading.active_count() == n_threads
        assert list(tmp_path.iterdir()) == []
        # plane never armed: a later snapshot shows nothing recorded
        assert telemetry.accountant().snapshot()["stages"] == {}
        assert rec.tail()  # recording itself still worked

    def test_broken_tee_never_breaks_the_hot_path(self):
        def bad(rec):
            raise RuntimeError("telemetry bug")

        events.add_tee(bad)
        try:
            with events.span("pad"):
                pass  # must not raise
            events.event("x")
        finally:
            events.remove_tee(bad)


class TestGangAggregation:
    def _write_snap(self, d, rank, stages, elapsed=10.0, events_=None):
        os.makedirs(d, exist_ok=True)
        snap = {"t": 100.0 + rank, "rank": rank, "pid": 1,
                "elapsed_s": elapsed, "stages": stages}
        if events_:
            snap["events"] = events_
        with open(os.path.join(d, f"metrics_rank{rank}.json"), "w") as f:
            json.dump(snap, f)

    def test_aggregate_sums_stages_across_ranks(self, tmp_path):
        d = str(tmp_path)
        st = {"count": 5, "busy_s": 4.0, "wall_busy_s": 4.0,
              "busy_frac": 0.4, "rows": 50, "bytes": 1000, "errors": 0,
              "active": 0, "max_concurrency": 2}
        self._write_snap(d, 0, {"decode": dict(st)},
                         events_={"quarantine": 1})
        self._write_snap(d, 1, {"decode": dict(st, busy_s=6.0,
                                               wall_busy_s=6.0, rows=70)},
                         events_={"quarantine": 2})
        agg = telemetry.aggregate_snapshots(d)
        assert agg["n_ranks"] == 2
        dec = agg["stages"]["decode"]
        assert dec["busy_s"] == 10.0 and dec["rows"] == 120
        assert dec["count"] == 10 and dec["max_concurrency"] == 2
        # gang busy fraction: 10s wall-busy over 2 ranks x 10s elapsed
        assert dec["busy_frac"] == 0.5
        assert agg["events"] == {"quarantine": 3}

    def test_aggregate_empty_dir_is_none(self, tmp_path):
        assert telemetry.aggregate_snapshots(str(tmp_path)) is None
        assert telemetry.aggregate_snapshots(
            str(tmp_path / "missing")) is None

    def test_clear_rank_files(self, tmp_path):
        d = str(tmp_path)
        self._write_snap(d, 0, {})
        (tmp_path / "metrics_rank0.jsonl").write_text("{}\n")
        (tmp_path / "keep.txt").write_text("x")
        telemetry.clear_rank_files(d)
        assert sorted(os.listdir(d)) == ["keep.txt"]

    def test_supervise_attaches_gang_metrics(self, tmp_path):
        """Jax-free supervisor e2e: a worker that exports a telemetry
        snapshot → SuperviseResult.metrics carries the aggregated gang
        view (the ISSUE 6 supervise() contract)."""
        from sparkdl_tpu.runner.launcher import supervise
        mdir = tmp_path / "metrics"
        script = tmp_path / "w.py"
        script.write_text("""
import json, os, sys
d = os.environ["SPARKDL_METRICS_DIR"]
os.makedirs(d, exist_ok=True)
rank = os.environ.get("SPARKDL_PROCESS_ID", "0")
snap = {"t": 1.0, "rank": int(rank), "pid": os.getpid(), "elapsed_s": 2.0,
        "stages": {"step_compute": {"count": 4, "busy_s": 1.5,
                                    "wall_busy_s": 1.5, "busy_frac": 0.75,
                                    "rows": 32, "bytes": 0, "errors": 0,
                                    "active": 0, "max_concurrency": 1}}}
tmp = os.path.join(d, f"metrics_rank{rank}.json.tmp")
open(tmp, "w").write(json.dumps(snap))
os.replace(tmp, os.path.join(d, f"metrics_rank{rank}.json"))
""")
        res = supervise(str(script), np=2, timeout_s=30.0, max_restarts=0,
                        poll_s=0.2,
                        env={"SPARKDL_METRICS_DIR": str(mdir)})
        assert res.metrics is not None
        assert res.metrics["n_ranks"] == 2
        assert res.metrics["stages"]["step_compute"]["rows"] == 64

    def test_launch_failure_metrics_ignore_stale_rank_files(self, tmp_path):
        """A reused SPARKDL_METRICS_DIR holding a dead earlier gang's
        high-rank snapshots must not be aggregated as THIS gang's failure
        evidence: launch() gives the gang a fresh gang-* subdir, same
        isolation supervise() has."""
        from sparkdl_tpu.runner.launcher import GangFailure, launch
        mdir = tmp_path / "metrics"
        st = {"count": 9, "busy_s": 9.0, "wall_busy_s": 9.0,
              "busy_frac": 0.9, "rows": 999, "bytes": 0, "errors": 0,
              "active": 0, "max_concurrency": 1}
        for r in (2, 3):  # earlier 4-rank run's leftovers
            self._write_snap(str(mdir), r, {"stale_stage": dict(st)})
        edir = tmp_path / "events"
        script = tmp_path / "w.py"
        script.write_text("""
import json, os, sys, time
rank = os.environ.get("SPARKDL_PROCESS_ID", "0")
d = os.environ["SPARKDL_METRICS_DIR"]
os.makedirs(d, exist_ok=True)
snap = {"t": 1.0, "rank": int(rank), "pid": os.getpid(), "elapsed_s": 2.0,
        "stages": {"step_compute": {"count": 4, "busy_s": 1.5,
                                    "wall_busy_s": 1.5, "busy_frac": 0.75,
                                    "rows": 32, "bytes": 0, "errors": 0,
                                    "active": 0, "max_concurrency": 1}}}
tmp = os.path.join(d, f"metrics_rank{rank}.json.tmp")
open(tmp, "w").write(json.dumps(snap))
os.replace(tmp, os.path.join(d, f"metrics_rank{rank}.json"))
with open(os.path.join(os.environ["SPARKDL_EVENT_DIR"],
                       f"events_rank{rank}.jsonl"), "w") as f:
    f.write(json.dumps({"t": time.time(), "name": "step_compute",
                        "ph": "E", "dur_s": 0.1, "rank": int(rank)}) + "\\n")
if rank == "0":
    time.sleep(0.5)  # let rank 1 land its files before the gang dies
    sys.exit(1)
""")
        with pytest.raises(GangFailure) as ei:
            launch(str(script), np=2, timeout_s=30.0, poll_s=0.2,
                   capture=True, event_dir=str(edir),
                   env={"SPARKDL_METRICS_DIR": str(mdir)})
        tl = ei.value.timeline
        assert tl is not None and tl.get("metrics") is not None
        assert tl["metrics"]["n_ranks"] == 2  # not 4
        assert "stale_stage" not in tl["metrics"]["stages"]
        assert tl["metrics"]["stages"]["step_compute"]["rows"] == 64
        # the workers exported into a gang-* subdir; the stale parent
        # files are untouched
        assert any(fn.startswith("gang-") for fn in os.listdir(mdir))
        assert (mdir / "metrics_rank3.json").exists()


class TestAnalysis:
    def test_union_seconds(self):
        assert analysis.union_seconds([]) == 0.0
        assert analysis.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4.0

    def test_attribution_on_synthetic_spans(self):
        """decode saturates [0,10] on two workers; dispatch covers [2,5];
        the report must name decode, keep every fraction in [0,1], and
        project the Amdahl bound off decode's busy fraction."""
        recs = []
        recs += _span_records("decode",
                              [(0.0, 5.0), (0.5, 5.5), (5.0, 10.0)],
                              rows=4)
        recs += _span_records("dispatch", [(2.0, 5.0)], rows=4)
        rep = analysis.analyze(events=recs)
        assert rep["dominant_stage"] == "decode"
        d = rep["stages"]["decode"]
        assert d["busy_frac"] == 1.0          # union covers the whole wall
        assert d["busy_s"] == 15.0            # slot-seconds sum
        assert d["avg_concurrency"] == 1.5
        # decode exclusive = wall minus dispatch's [2,5] overlap
        assert abs(d["exclusive_s"] - 7.0) < 1e-6
        assert rep["stages"]["dispatch"]["busy_frac"] == 0.3
        assert rep["stages"]["dispatch"]["exclusive_s"] == 0.0
        assert rep["max_speedup_fixing_others"] == 1.0
        assert rep["idle_s"] == 0.0
        assert all(0.0 <= s["busy_frac"] <= 1.0
                   for s in rep["stages"].values())

    def test_idle_gap_reported(self):
        recs = _span_records("fetch", [(0.0, 1.0), (3.0, 4.0)])
        rep = analysis.analyze(events=recs)
        assert rep["wall_s"] == 4.0
        assert rep["idle_s"] == 2.0
        assert rep["idle_frac"] == 0.5

    def test_no_spans_is_none(self):
        assert analysis.analyze(events=[{"name": "x", "ph": "P",
                                         "t": 1.0}]) is None
        assert analysis.analyze(events=[]) is None

    def test_format_report_names_dominant(self):
        recs = _span_records("decode", [(0.0, 9.4)], rows=100) \
            + _span_records("fetch", [(9.4, 10.0)])
        rep = analysis.analyze(events=recs)
        txt = analysis.format_report(rep)
        assert "dominant stage: decode (94.0% busy)" in txt
        assert "<= 1.06x" in txt  # 1 / 0.94

    def test_event_dir_loader_includes_gang_subdirs(self, tmp_path):
        (tmp_path / "gang-x").mkdir()
        with open(tmp_path / "events_rank0.jsonl", "w") as f:
            for r in _span_records("pad", [(0.0, 1.0)]):
                f.write(json.dumps(r) + "\n")
        with open(tmp_path / "gang-x" / "events_rank1.jsonl", "w") as f:
            for r in _span_records("pad", [(1.0, 2.0)], rank=1):
                f.write(json.dumps(r) + "\n")
        rep = analysis.analyze(event_dir=str(tmp_path))
        assert rep["stages"]["pad"]["count"] == 2

    def test_event_dir_loader_merges_only_newest_gang_subdir(self, tmp_path):
        """A reused event dir accumulates one kept gang-* subdir per
        supervise() run; splicing two runs into one timeline would turn
        the gap between them into fictitious idle. Newest non-empty gang
        wins (empty ones are skipped), same rule as aggregate_snapshots."""
        old = tmp_path / "gang-old"
        new = tmp_path / "gang-new"
        empty = tmp_path / "gang-zzz-empty"
        for d in (old, new, empty):
            d.mkdir()
        with open(old / "events_rank0.jsonl", "w") as f:
            for r in _span_records("pad", [(0.0, 1.0)]):
                f.write(json.dumps(r) + "\n")
        with open(new / "events_rank0.jsonl", "w") as f:
            for r in _span_records("pad", [(1000.0, 1001.0)]):
                f.write(json.dumps(r) + "\n")
        os.utime(old, (1, 1))        # oldest
        os.utime(new, (100, 100))    # newest non-empty
        os.utime(empty, (200, 200))  # newest overall but no streams
        rep = analysis.analyze(event_dir=str(tmp_path))
        assert rep["stages"]["pad"]["count"] == 1
        # wall is the newest run's 1s, not 1001s of spliced runs
        assert rep["wall_s"] == 1.0
        assert rep["idle_s"] == 0.0

    def test_bottleneck_report_cli(self, tmp_path, capsys):
        """In-process main() call — no fresh jax-importing interpreters
        in a tier-1 test (the slow obs smoke runs the script as a real
        subprocess); same import route as the env-docs lint tests."""
        sys.path.insert(0, os.path.join(_REPO, "scripts"))
        try:
            import bottleneck_report
        finally:
            sys.path.pop(0)
        d = tmp_path / "ev"
        d.mkdir()
        with open(d / "events_rank0.jsonl", "w") as f:
            for r in _span_records("decode", [(0.0, 2.0)], rows=8) \
                    + _span_records("dispatch", [(2.0, 2.5)]):
                f.write(json.dumps(r) + "\n")
        assert bottleneck_report.main([str(d), "--json"]) == 0
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rec["report"]["dominant_stage"] == "decode"
        # empty dir → exit 2, not a crash
        empty = tmp_path / "empty"
        empty.mkdir()
        assert bottleneck_report.main([str(empty)]) == 2


class TestMeterIntegration:
    def test_summary_carries_stage_utilization_when_armed(self):
        from sparkdl_tpu.runner.metrics import ThroughputMeter
        telemetry.start()
        events.reset()
        with events.span("decode", rows=4):
            time.sleep(0.002)
        with events.span("dispatch", rows=4):
            pass
        s = ThroughputMeter().summary()
        su = s["stage_utilization"]
        assert su is not None
        assert su["dominant_stage"] == "decode"
        assert set(su["stages"]) == {"decode", "dispatch"}
        telemetry.stop()

    def test_summary_block_is_none_when_off(self):
        from sparkdl_tpu.runner.metrics import ThroughputMeter
        assert ThroughputMeter().summary()["stage_utilization"] is None

    def test_log_summary_flattens_doubly_nested_blocks(self, caplog):
        """ISSUE 6 satellite: nested summary blocks (compile_cache's
        persistent sub-dict, stage_utilization's stages) flatten to
        scalar keys recursively — no stringified dicts in TB/CSV."""
        from sparkdl_tpu.runner.metrics import MetricsLogger
        logger = MetricsLogger(None)
        with caplog.at_level("INFO", logger="sparkdl_tpu.runner"):
            logger.log_summary(10, {
                "examples_per_sec": 5.0,
                "compile_cache": {"hits": 2,
                                  "persistent": {"hits": 1, "misses": 0}},
                "stage_utilization": {
                    "dominant_stage": "decode",
                    "stages": {"decode": {"busy_frac": 0.9}}},
            })
        assert "compile_cache_persistent_hits" in caplog.text
        assert "stage_utilization_stages_decode_busy_frac" in caplog.text
        assert "{'hits'" not in caplog.text  # nothing stringified
        logger.close()


def _check_env_docs():
    sys.path.insert(0, os.path.join(_REPO, "scripts"))
    try:
        import check_env_docs
    finally:
        sys.path.pop(0)
    return check_env_docs


class TestEnvDocsLint:
    def test_repo_has_no_drift(self):
        """The lint itself, as a tier-1 gate: every SPARKDL_* var in the
        package is documented in README.md."""
        check_env_docs = _check_env_docs()
        missing = check_env_docs.missing_vars()
        assert missing == [], \
            f"undocumented SPARKDL_* env vars: {missing}"
        # sanity: the scanner actually sees known vars on both sides
        assert "SPARKDL_EVENT_DIR" in check_env_docs.code_env_vars()
        assert "SPARKDL_EVENT_DIR" in check_env_docs.documented_env_vars()

    def test_lint_catches_synthetic_drift(self, tmp_path):
        """The mechanism, not just the current state: an undocumented var
        in a synthetic tree is reported."""
        check_env_docs = _check_env_docs()
        pkg = tmp_path / "sparkdl_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'import os\nX = os.environ.get("SPARKDL_TOTALLY_NEW_KNOB")\n')
        (tmp_path / "scripts").mkdir()
        (tmp_path / "README.md").write_text("docs say nothing")
        missing = check_env_docs.missing_vars(
            root=str(tmp_path), readme=str(tmp_path / "README.md"))
        assert missing == ["SPARKDL_TOTALLY_NEW_KNOB"]

    def test_repo_documents_no_var_that_nothing_reads(self):
        """The other direction: a knob that was deleted leaves no row
        behind in the README."""
        stale = _check_env_docs().stale_vars()
        assert stale == [], \
            f"README.md documents SPARKDL_* names no code reads: {stale}"

    def test_lint_catches_synthetic_stale_var(self, tmp_path):
        """A documented var with no reader is reported; a name ending in
        ``_`` is a prefix and passes only while some var still starts
        with it; a test file that sets a var is not a reader, the
        suite's conftest is."""
        check_env_docs = _check_env_docs()
        pkg = tmp_path / "sparkdl_tpu"
        pkg.mkdir()
        (pkg / "mod.py").write_text(
            'import os\nX = os.environ.get("SPARKDL_SLO_TTFT_S")\n')
        tests = tmp_path / "tests"
        tests.mkdir()
        (tests / "conftest.py").write_text(
            'import os\nP = os.environ.get("SPARKDL_HARNESS_KNOB")\n')
        (tests / "test_x.py").write_text(
            'def test(monkeypatch):\n'
            '    monkeypatch.setenv("SPARKDL_DEAD_KNOB", "1")\n')
        readme = tmp_path / "README.md"
        readme.write_text(
            "`SPARKDL_SLO_TTFT_S`, every `SPARKDL_SLO_*`, "
            "`SPARKDL_HARNESS_KNOB`; gone: `SPARKDL_DEAD_KNOB` and "
            "every `SPARKDL_OLDFAMILY_*`")
        assert check_env_docs.stale_vars(
            root=str(tmp_path), readme=str(readme)) == \
            ["SPARKDL_DEAD_KNOB", "SPARKDL_OLDFAMILY_"]

    def test_readme_names_only_files_in_the_tree(self, tmp_path):
        """Every `scripts/x.py`, `tests/x.py`, bare `x.py` and root
        `X.json` the README names exists — in the repo, and the
        mechanism on a synthetic tree (a bare script name resolves
        under scripts/; a lower-case json is a run's output)."""
        check_env_docs = _check_env_docs()
        gone = check_env_docs.missing_paths()
        assert gone == [], f"README.md names files that are gone: {gone}"
        (tmp_path / "scripts").mkdir()
        (tmp_path / "scripts" / "report.py").write_text("")
        (tmp_path / "RECORD.json").write_text("{}")
        readme = tmp_path / "README.md"
        readme.write_text(
            "run `scripts/report.py` (or `report.py`), read `RECORD.json` "
            "and the run's `gang_timeline.json`; once there were "
            "`scripts/old_gate.py`, `tests/test_old.py`, `old.py` and "
            "`OLD_r01.json`")
        assert check_env_docs.missing_paths(
            root=str(tmp_path), readme=str(readme)) == \
            ["OLD_r01.json", "old.py", "scripts/old_gate.py",
             "tests/test_old.py"]


class TestScorerGauges:
    def test_stream_scorer_sets_queue_gauges(self):
        """The pending/backlog deque depths land as gauges when armed."""
        import numpy as np
        import pyarrow as pa

        from sparkdl_tpu.transformers.streaming import StreamScorer

        class StubRunner:
            prefetch = 2
            batch_size = 2

            def run_stream(self, stream):
                for arr, entry in stream:
                    yield np.asarray(arr) * 2.0, entry

        telemetry.start()
        events.reset()
        scorer = StreamScorer(
            StubRunner(), "y",
            make_decoder=lambda rb: (
                lambda start, length:
                np.full((length, 1), 1.0, np.float32)),
            encode=lambda r: pa.array([float(v) for v in r[:, 0]],
                                      type=pa.float64()),
            empty_array=lambda: pa.array([], type=pa.float64()),
            chunk_rows=2, decode_workers=0)
        batch = pa.RecordBatch.from_arrays(
            [pa.array([1.0, 2.0, 3.0, 4.0])], ["x"])
        out = list(scorer(iter([batch])))
        assert len(out) == 1
        snap = telemetry.registry().snapshot()
        assert "scorer_pending_partitions" in snap["gauges"]
        assert "scorer_encode_backlog" in snap["gauges"]
        assert snap["gauges"]["scorer_encode_backlog"]["max"] >= 1
        # decode spans accounted too (rows attr rides the span)
        acc = telemetry.accountant().snapshot()
        assert acc["stages"]["decode"]["rows"] == 4
        telemetry.stop()

    def test_run_stream_occupancy_gauge_is_a_fraction(self):
        """Slot occupancy is read AFTER the window pop: a keeping-up feed
        reads 1.0 — never a perpetual (prefetch+1)/prefetch > 1."""
        import numpy as np

        from sparkdl_tpu.core import runtime
        telemetry.start()
        events.reset()
        runner = runtime.BatchRunner(lambda x: x + 1.0, batch_size=4,
                                     prefetch=2)
        batches = [np.ones((3, 2), np.float32) for _ in range(8)]
        out = list(runner.run_stream((b, i) for i, b in enumerate(batches)))
        assert len(out) == 8
        g = telemetry.registry().snapshot()["gauges"]
        assert 0.0 < g["run_stream_slot_occupancy"]["max"] <= 1.0
        assert g["run_stream_window_depth"]["max"] <= 2
        telemetry.stop()
