"""Causal trace plane tests (ISSUE 17 tentpole): span-id/parent-id
propagation through the flight recorder, supervisor-minted trace ids
shipped to ranks via env, the merged Chrome-trace export
(``runner/traceview.py`` + ``scripts/trace_export.py``), the
``gang_resized`` never-failure-evidence rule under elastic resizes, and
the engine's request-span parentage.

Fast and jax-free where possible: synthetic streams feed traceview and
merge_timeline; the one subprocess test launches hand-rolled stdlib
workers. The end-to-end proof (2-rank supervised gang + serving requests
→ one validated Perfetto trace) rides the slow obs_smoke leg in
test_chaos.py.
"""

import importlib.util
import json
import os
import sys
import threading

import pytest

from sparkdl_tpu.runner import events, launcher, telemetry, traceview

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Fresh recorder, no stream dir, no trace env — arming is per-test."""
    for v in ("SPARKDL_EVENT_DIR", events.TRACE_ID_ENV,
              events.TRACE_PARENT_ENV):
        monkeypatch.delenv(v, raising=False)
    events.reset()
    telemetry.reset()
    yield
    events.reset()
    telemetry.reset()


def _arm(monkeypatch, trace_id="t" * 16, parent=None):
    monkeypatch.setenv(events.TRACE_ID_ENV, trace_id)
    if parent:
        monkeypatch.setenv(events.TRACE_PARENT_ENV, parent)
    return trace_id


class TestTraceContext:
    def test_untraced_records_are_byte_identical(self):
        """With no SPARKDL_TRACE_ID the machinery must be invisible: no
        span_id/parent_id/trace_id keys anywhere — PR 2's record shape,
        unchanged."""
        rec = events.reset()
        with events.span("step_compute", step=1):
            events.event("chaos", site="step_start")
        for r in rec.tail():
            assert "span_id" not in r
            assert "parent_id" not in r
            assert "trace_id" not in r

    def test_armed_spans_chain_and_carry_trace_id(self, monkeypatch):
        tid = _arm(monkeypatch)
        rec = events.reset()
        with events.span("outer"):
            with events.span("inner"):
                events.event("chaos", site="x")
        by = {}
        for r in rec.tail():
            by.setdefault((r["name"], r["ph"]), r)
        outer = by[("outer", "B")]
        inner = by[("inner", "B")]
        point = by[("chaos", "P")]
        assert all(r["trace_id"] == tid for r in (outer, inner, point))
        assert outer["span_id"] and "parent_id" not in outer
        assert inner["parent_id"] == outer["span_id"]
        # a bare point event inside the region parents to the innermost
        # open span
        assert point["parent_id"] == inner["span_id"]
        # B and E of one span carry the SAME span_id
        assert by[("inner", "E")]["span_id"] == inner["span_id"]

    def test_sibling_after_exit_parents_to_enclosing(self, monkeypatch):
        _arm(monkeypatch)
        rec = events.reset()
        with events.span("outer"):
            with events.span("first"):
                pass
            with events.span("second"):
                pass
        by = {(r["name"], r["ph"]): r for r in rec.tail()}
        outer_id = by[("outer", "B")]["span_id"]
        assert by[("first", "B")]["parent_id"] == outer_id
        # the closed first span did NOT stay on the stack
        assert by[("second", "B")]["parent_id"] == outer_id

    def test_env_parent_is_the_outermost_fallback(self, monkeypatch):
        """A rank's outermost span — and a point event outside any span —
        chain to the supervise() attempt span shipped via env."""
        _arm(monkeypatch, parent="driver-span-7")
        rec = events.reset()
        events.event("restart", attempt=1)
        with events.span("step_compute", step=0):
            pass
        by = {(r["name"], r["ph"]): r for r in rec.tail()}
        assert by[("restart", "P")]["parent_id"] == "driver-span-7"
        assert by[("step_compute", "B")]["parent_id"] == "driver-span-7"

    def test_completed_span_mints_ids(self, monkeypatch):
        _arm(monkeypatch, parent="root-1")
        rec = events.reset()
        events.completed_span("serve_decode", 0.5, request=3)
        (r,) = [x for x in rec.tail()
                if x["name"] == "serve_decode" and x["ph"] == "E"]
        assert r["span_id"] and r["parent_id"] == "root-1"
        # explicit ids win over ambient context (the engine's
        # request-parented emission path)
        events.completed_span("serve_decode", 0.1, request=4,
                              span_id="S", parent_id="P")
        (r2,) = [x for x in rec.tail()
                 if x.get("request") == 4 and x["ph"] == "E"]
        assert r2["span_id"] == "S" and r2["parent_id"] == "P"

    def test_span_stack_is_thread_local(self, monkeypatch):
        """A feed thread's spans must never parent under the training
        loop's open span — each thread has its own stack."""
        _arm(monkeypatch)
        rec = events.reset()

        def feeder():
            with events.span("data_fetch"):
                pass

        with events.span("step_compute"):
            t = threading.Thread(target=feeder)
            t.start()
            t.join()
        by = {(r["name"], r["ph"]): r for r in rec.tail()}
        assert "parent_id" not in by[("data_fetch", "B")]

    def test_exception_exit_still_pops(self, monkeypatch):
        _arm(monkeypatch)
        rec = events.reset()
        with pytest.raises(RuntimeError):
            with events.span("outer"):
                with events.span("boom"):
                    raise RuntimeError("x")
        # the stack fully unwound: a new span is a root again
        with events.span("after"):
            pass
        by = {(r["name"], r["ph"]): r for r in rec.tail()}
        assert "parent_id" not in by[("after", "B")]


class TestLauncherPropagation:
    _WORKER = """
import json, os, sys
rank = int(os.environ["SPARKDL_PROCESS_ID"])
d = os.environ["SPARKDL_EVENT_DIR"]
rec = {"t": 100.0 + rank, "name": "worker_span", "ph": "E", "rank": rank,
       "dur_s": 0.5, "trace_id": os.environ.get("SPARKDL_TRACE_ID"),
       "span_id": f"w{rank}",
       "parent_id": os.environ.get("SPARKDL_TRACE_PARENT")}
with open(os.path.join(d, f"events_rank{rank}.jsonl"), "w") as f:
    f.write(json.dumps(rec) + "\\n")
"""

    def test_supervise_ships_trace_context_and_writes_manifest(
            self, tmp_path):
        """Both ranks inherit ONE trace id and a parent span id that
        resolves to the attempt span in the supervisor's manifest — the
        whole chain ends at the run root."""
        script = tmp_path / "w.py"
        script.write_text(self._WORKER)
        event_dir = str(tmp_path / "ev")
        launcher.supervise(str(script), np=2, timeout_s=60.0,
                           max_restarts=0, backoff_s=0.1, poll_s=0.1,
                           event_dir=event_dir)
        manifest = traceview.find_trace_manifest(event_dir)
        assert manifest and manifest["trace_id"]
        spans = {s["span_id"]: s for s in manifest["spans"]}
        root = manifest["root_span_id"]
        assert spans[root]["parent_id"] is None
        attempt = [s for s in manifest["spans"]
                   if s["name"] == "gang_attempt"]
        assert attempt and attempt[0]["parent_id"] == root
        for rank in (0, 1):
            with open(os.path.join(event_dir,
                                   f"events_rank{rank}.jsonl")) as f:
                (rec,) = [json.loads(ln) for ln in f]
            assert rec["trace_id"] == manifest["trace_id"]
            # the shipped parent IS the newest attempt span
            assert rec["parent_id"] == attempt[-1]["span_id"]

    def test_trace_env_of_caller_is_respected(self, tmp_path):
        """An outer orchestrator's trace id (env=) is adopted, not
        replaced — nested supervision joins the existing trace."""
        script = tmp_path / "w.py"
        script.write_text(self._WORKER)
        event_dir = str(tmp_path / "ev")
        launcher.supervise(str(script), np=1, timeout_s=60.0,
                           max_restarts=0, backoff_s=0.1, poll_s=0.1,
                           event_dir=event_dir,
                           env={events.TRACE_ID_ENV: "feedcafe01234567"})
        manifest = traceview.find_trace_manifest(event_dir)
        assert manifest["trace_id"] == "feedcafe01234567"


class TestMergeTimelineResize:
    def _write(self, d, rank, recs):
        with open(os.path.join(d, f"events_rank{rank}.jsonl"), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")

    def test_gang_resized_is_narrative_never_failure_evidence(
            self, tmp_path):
        """ISSUE 17 satellite: under an elastic resize the timeline must
        show `gang_resized` in the degradation narrative — and even when
        the resize record carries error text (the dead rank's reason), it
        must never be promoted to failure evidence."""
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "gang_resized", "ph": "P", "rank": 0,
             "from_np": 4, "to_np": 3, "reason": "rank_died",
             "error": "rank 2 exited 137 (permanent)"},
            {"t": 101.0, "name": "step_compute", "ph": "E", "rank": 0,
             "step": 10, "dur_s": 0.01},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failure"] is None  # resize is not a fault
        kinds = [dg["kind"] for dg in tl["degradations"]]
        assert "gang_resized" in kinds
        assert "gang_resized" in events.format_timeline(tl)

    def test_resize_then_real_fault_attributes_to_the_fault(
            self, tmp_path):
        d = str(tmp_path)
        self._write(d, 0, [
            {"t": 100.0, "name": "gang_resized", "ph": "P", "rank": 0,
             "from_np": 2, "to_np": 1, "reason": "rank_died",
             "error": "rank 1 exited 137"},
            {"t": 105.0, "name": "chaos", "ph": "P", "rank": 0,
             "site": "step_start", "kind": "fatal", "step": 7},
        ])
        tl = events.merge_timeline(d)
        assert tl["first_failure"]["site"] == "step_start"
        assert tl["first_failure"]["step"] == 7
        assert any(dg["kind"] == "gang_resized"
                   for dg in tl["degradations"])


class TestTraceview:
    def _seed(self, tmp_path, with_manifest=True):
        ev = tmp_path / "ev"
        ev.mkdir()
        if with_manifest:
            (ev / "trace_manifest.json").write_text(json.dumps({
                "trace_id": "abc123", "root_span_id": "root",
                "spans": [{"span_id": "root", "parent_id": None,
                           "name": "supervise", "t": 100.0},
                          {"span_id": "a1", "parent_id": "root",
                           "name": "gang_attempt", "t": 100.2,
                           "attempt": 1}]}))
        recs0 = [
            {"t": 101.0, "name": "step_compute", "ph": "E", "rank": 0,
             "dur_s": 0.5, "trace_id": "abc123", "span_id": "s0",
             "parent_id": "a1", "step": 1},
            {"t": 101.2, "name": "chaos", "ph": "P", "rank": 0,
             "site": "step_start", "trace_id": "abc123",
             "parent_id": "s0"},
        ]
        recs1 = [
            {"t": 101.1, "name": "step_compute", "ph": "E", "rank": 1,
             "dur_s": 0.4, "trace_id": "abc123", "span_id": "s1",
             "parent_id": "a1", "step": 1},
        ]
        for rank, recs in ((0, recs0), (1, recs1)):
            with open(ev / f"events_rank{rank}.jsonl", "w") as f:
                for r in recs:
                    f.write(json.dumps(r) + "\n")
        return str(ev)

    def test_chrome_trace_shape(self, tmp_path):
        ev = self._seed(tmp_path)
        tr = traceview.chrome_trace(ev)
        assert tr["displayTimeUnit"] == "ms"
        evs = tr["traceEvents"]
        x = [e for e in evs if e["ph"] == "X"]
        i = [e for e in evs if e["ph"] == "i"]
        m = [e for e in evs if e["ph"] == "M"]
        # rank spans: ts back-dated by dur, µs scale
        s0 = next(e for e in x if e["args"].get("span_id") == "s0")
        assert s0["pid"] == 0
        assert s0["ts"] == pytest.approx((101.0 - 0.5) * 1e6)
        assert s0["dur"] == pytest.approx(0.5 * 1e6)
        # instants carry a scope
        assert all(e["s"] == "t" for e in i)
        # driver manifest spans on the synthetic driver pid
        driver = [e for e in x if e["pid"] == traceview.DRIVER_PID]
        assert {e["name"] for e in driver} == {"supervise",
                                               "gang_attempt"}
        # process/thread naming metadata present
        assert any(e["name"] == "process_name"
                   and e["args"]["name"] == "driver" for e in m)
        assert any(e["name"] == "process_name"
                   and e["args"]["name"] == "rank 1" for e in m)
        # skew is annotated even with no heartbeat dir — never silent
        skew = tr["otherData"]["clock_skew"]
        assert skew["measured"] is False and "unmeasured" in skew["note"]

    def test_counter_tracks_from_metrics_history(self, tmp_path):
        ev = self._seed(tmp_path)
        mdir = tmp_path / "m"
        mdir.mkdir()
        with open(mdir / "metrics_rank0.jsonl", "w") as f:
            for t, depth in ((101.0, 2), (101.5, 5)):
                f.write(json.dumps(
                    {"t": t, "rank": 0,
                     "gauges": {"serving_queue_depth":
                                {"value": depth, "max": 5}},
                     "counters": {"steps_total": t - 100.0}}) + "\n")
        tr = traceview.chrome_trace(ev, metrics_dir=str(mdir))
        c = [e for e in tr["traceEvents"] if e["ph"] == "C"]
        depths = [e["args"]["value"] for e in c
                  if e["name"] == "serving_queue_depth"]
        assert depths == [2, 5]
        assert any(e["name"] == "steps_total" for e in c)

    def test_validate_accepts_good_and_flags_broken_chains(
            self, tmp_path):
        ev = self._seed(tmp_path)
        tr = traceview.chrome_trace(ev)
        good = traceview.validate_chrome_trace(tr, require_ranks=2)
        assert good["ok"], good["problems"]
        assert good["ranks"] == [0, 1]
        # break a parent chain: an id that resolves nowhere
        tr["traceEvents"].append(
            {"ph": "X", "name": "orphan", "pid": 0, "tid": 9,
             "ts": 0, "dur": 1,
             "args": {"span_id": "zz", "parent_id": "missing"}})
        bad = traceview.validate_chrome_trace(tr)
        assert not bad["ok"]
        assert any("resolves to no known span" in p
                   for p in bad["problems"])

    def test_validate_flags_foreign_trace_id(self, tmp_path):
        ev = self._seed(tmp_path)
        tr = traceview.chrome_trace(ev)
        tr["traceEvents"].append(
            {"ph": "X", "name": "alien", "pid": 1, "tid": 9,
             "ts": 0, "dur": 1,
             "args": {"span_id": "zz", "trace_id": "OTHER"}})
        bad = traceview.validate_chrome_trace(tr)
        assert any("FOREIGN trace_id" in p for p in bad["problems"])

    def test_manifest_found_in_newest_gang_subdir(self, tmp_path):
        """Supervised runs write the manifest into the adopted gang-*
        subdir; the exporter must find it by the same newest-only rule
        the analysis reader uses."""
        ev = tmp_path / "ev"
        old = ev / "gang-1111-aaaa"
        new = ev / "gang-2222-bbbb"
        for d, tid in ((old, "oldtrace"), (new, "newtrace")):
            d.mkdir(parents=True)
            (d / "trace_manifest.json").write_text(json.dumps(
                {"trace_id": tid, "root_span_id": "r",
                 "spans": [{"span_id": "r", "parent_id": None,
                            "name": "supervise", "t": 1.0}]}))
            (d / "events_rank0.jsonl").write_text(json.dumps(
                {"t": 2.0, "name": "s", "ph": "E", "rank": 0,
                 "dur_s": 0.1}) + "\n")
        os.utime(old, (1, 1))
        m = traceview.find_trace_manifest(str(ev))
        assert m["trace_id"] == "newtrace"

    def test_clock_skew_measured_from_heartbeats(self, tmp_path):
        ev = self._seed(tmp_path)
        hb = tmp_path / "hb"
        hb.mkdir()
        p = hb / "rank0.hb"
        p.write_text(json.dumps({"step": 3, "time": 500.0}))
        os.utime(p, (500.0, 500.25))  # mtime (host) 0.25s after body
        skew = traceview.measure_clock_skew(str(hb))
        assert skew["measured"] is True
        assert skew["per_rank_s"]["0"] == pytest.approx(-0.25)
        tr = traceview.chrome_trace(ev, heartbeat_dir=str(hb))
        assert tr["otherData"]["clock_skew"]["measured"] is True

    def test_request_summary_track(self, tmp_path):
        """Completed serve_* folds become one summary span per request on
        the owning rank's `requests` lane."""
        ev = tmp_path / "ev"
        ev.mkdir()
        recs = [
            {"t": 10.2, "name": "serve_queue", "ph": "E", "rank": 0,
             "request": 1, "dur_s": 0.2},
            {"t": 10.5, "name": "serve_prefill", "ph": "E", "rank": 0,
             "request": 1, "dur_s": 0.3, "tokens": 3},
            {"t": 11.0, "name": "serve_decode", "ph": "E", "rank": 0,
             "request": 1, "dur_s": 0.5, "reason": "stop",
             "new_tokens": 4},
        ]
        with open(ev / "events_rank0.jsonl", "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        tr = traceview.chrome_trace(str(ev))
        assert tr["otherData"]["requests"] == 1
        req = next(e for e in tr["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "request 1")
        assert req["pid"] == 0
        assert req["args"]["finish"] == "stop"


class TestTraceExportScript:
    def test_cli_roundtrip_and_validation_gate(self, tmp_path):
        mod = _load_script("trace_export")
        ev = tmp_path / "ev"
        ev.mkdir()
        (ev / "trace_manifest.json").write_text(json.dumps(
            {"trace_id": "abc", "root_span_id": "r",
             "spans": [{"span_id": "r", "parent_id": None,
                        "name": "supervise", "t": 1.0}]}))
        (ev / "events_rank0.jsonl").write_text(json.dumps(
            {"t": 2.0, "name": "s", "ph": "E", "rank": 0, "dur_s": 0.1,
             "trace_id": "abc", "span_id": "x", "parent_id": "r"}) + "\n")
        out = tmp_path / "t.json"
        rc = mod.main([str(ev), "--out", str(out), "--validate"])
        assert rc == 0
        trace = json.load(open(out))
        assert trace["otherData"]["trace_id"] == "abc"
        # demanding a second rank must flip the gate
        rc = mod.main([str(ev), "--out", str(out), "--validate",
                       "--require-ranks", "2"])
        assert rc == 1
        # an empty dir is its own exit code
        empty = tmp_path / "empty"
        empty.mkdir()
        assert mod.main([str(empty)]) == 2


class TestEngineParentage:
    def _engine(self):
        from sparkdl_tpu.serving import GenerationEngine, StubBackend
        return GenerationEngine(StubBackend(2, 64, step_s=0.0),
                                prefill_chunk=8)

    def test_serve_spans_parent_under_request_envelope(self, monkeypatch):
        """Every request-scoped serve_* record parents (transitively) to
        the request's admission span; the serve_request envelope closes
        the chain to the submitter's context."""
        _arm(monkeypatch, parent="attempt-9")
        rec = events.reset()
        eng = self._engine()
        h = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run_until_idle()
        assert h.wait(30) and h.finish_reason == "length"
        recs = [r for r in rec.tail() if r["name"].startswith("serve_")]
        env_rec = next(r for r in recs if r["name"] == "serve_request")
        assert env_rec["span_id"]  # the admission span
        assert env_rec["parent_id"] == "attempt-9"
        assert env_rec["finish"] == "length"
        scoped = [r for r in recs if r["name"] != "serve_request"
                  and r.get("request") is not None and r["ph"] != "B"]
        assert scoped  # queue/prefill/decode all present
        for r in scoped:
            assert r["parent_id"] == env_rec["span_id"], r["name"]
            assert r["trace_id"] == env_rec["trace_id"]

    def test_untraced_engine_emits_no_ids(self):
        rec = events.reset()
        eng = self._engine()
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run_until_idle()
        recs = [r for r in rec.tail() if r["name"].startswith("serve_")]
        assert recs
        assert not any(r["name"] == "serve_request" for r in recs)
        for r in recs:
            assert "span_id" not in r and "parent_id" not in r
