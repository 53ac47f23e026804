"""Request-scoped tracing, live engine inspector, and SLO burn-rate
monitoring (ISSUE 13): trace assembly (live tee + offline fold), the
phases-sum-to-latency invariant, the serve_* attribution drift guard,
the /serving endpoint, the SLO monitor's multi-window burn math and
breach flip, the request_report / bottleneck_report CLIs (in-process,
per the tier-1 lean rule), the check_metric_docs lint, serve_bench's
new record fields — and the off-plane overhead pins (zero registration,
no tee, no per-token event growth; the PR 6 rule).

Fast and jax-free throughout: everything rides StubBackend and
synthetic records.
"""

import json
import os
import re
import sys
import time
import urllib.request

import pytest

from sparkdl_tpu.runner import analysis, events, slo, telemetry
from sparkdl_tpu.serving import (ENGINE_SCOPED_EVENTS,
                                 REQUEST_SCOPED_EVENTS, GenerationEngine,
                                 StubBackend, introspect)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane(monkeypatch):
    """Fresh plane/recorder/SLO monitor per test; SLO env never leaks."""
    for v in ("SPARKDL_SLO_TTFT_S", "SPARKDL_SLO_LATENCY_S",
              "SPARKDL_SLO_ERROR_RATE", "SPARKDL_SLO_TARGET",
              "SPARKDL_SLO_WINDOWS_S", "SPARKDL_SLO_BURN_THRESHOLD",
              "SPARKDL_TRACE_RING", "SPARKDL_TRACE_SLOWEST"):
        monkeypatch.delenv(v, raising=False)
    telemetry.reset()
    slo.reset()
    events.reset()
    yield
    telemetry.reset()
    slo.reset()
    events.reset()


def _drain(eng, handles, timeout=30):
    eng.run_until_idle()
    for h in handles:
        assert h.wait(timeout)


# ---------------------------------------------------------------------------
# Trace assembly
# ---------------------------------------------------------------------------

class TestTraceCollector:
    def test_engine_run_assembles_traces_summing_to_latency(self):
        """The acceptance invariant: every completed request has a trace
        whose phases sum to its measured latency within 5%
        (unattributed_s bounded)."""
        telemetry.start()
        eng = GenerationEngine(StubBackend(4, 128, step_s=0.001),
                               prefill_chunk=8)
        hs = [eng.submit([1 + i, 2, 3], max_new_tokens=12)
              for i in range(10)]
        _drain(eng, hs)
        traces = telemetry.request_traces().traces()
        assert len(traces) == 10
        for t in traces:
            assert t["finish"] == "length"
            assert t["tokens_out"] == 12
            assert t["latency_s"] > 0
            assert abs(t["unattributed_s"]) <= 0.05 * t["latency_s"]
            total = (t["queue_s"] + t["prefill_s"] + t["prefill_wait_s"]
                     + t["decode_s"] + t["unattributed_s"])
            assert total == pytest.approx(t["latency_s"], abs=1e-4)
            assert t["ttft_s"] is not None
            assert t["dominant_phase"] in t["phases"]

    def test_slowest_and_ring_bounds(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_TRACE_RING", "8")
        monkeypatch.setenv("SPARKDL_TRACE_SLOWEST", "3")
        telemetry.start()
        eng = GenerationEngine(StubBackend(2, 64, step_s=0.0002),
                               prefill_chunk=8)
        hs = [eng.submit([1 + i, 2], max_new_tokens=4)
              for i in range(20)]
        _drain(eng, hs)
        col = telemetry.request_traces()
        assert len(col.traces()) == 8          # ring bound
        slowest = col.slowest()
        assert len(slowest) == 3               # slowest-N bound
        lats = [t["latency_s"] for t in slowest]
        assert lats == sorted(lats, reverse=True)
        summ = col.summary()
        assert summ["completed"] == 20
        assert summ["in_ring"] == 8
        assert len(summ["slowest"]) == 3

    def test_quarantined_request_finalizes_as_error(self):
        class FailingPrefill(StubBackend):
            def prefill_chunk(self, *a, **kw):
                raise RuntimeError("poisoned prompt")

        telemetry.start()
        eng = GenerationEngine(FailingPrefill(2, 64), retries=1,
                               prefill_chunk=8)
        h = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run_until_idle()
        assert h.state == "failed"
        traces = telemetry.request_traces().traces()
        assert len(traces) == 1
        assert traces[0]["finish"] == "error"
        assert traces[0]["retries"] >= 1

    def test_spec_and_preemption_fields(self):
        """Paged + speculative run: traces carry the spec ledger (mean
        accept length) and preemption/block-stall evidence when the
        pool is tight."""
        telemetry.start()
        eng = GenerationEngine(
            StubBackend(4, 128, vocab_size=8, block_size=8,
                        pool_blocks=12), prefill_chunk=8, spec_k=2)
        hs = [eng.submit([1, 2, 3], max_new_tokens=20)
              for _ in range(6)]
        _drain(eng, hs)
        traces = telemetry.request_traces().traces()
        assert len(traces) == 6
        spec = [t for t in traces if t["spec_windows"] > 0]
        assert spec, "speculation ran but no trace carries its ledger"
        for t in spec:
            assert 1.0 <= t["spec_mean_accept_len"] <= 3.0
        assert eng.stats["preemptions"] == sum(
            t["preemptions"] for t in traces)

    def test_offline_assembly_matches_live(self, tmp_path, monkeypatch):
        """request_report's offline fold and the live tee are the same
        implementation: traces assembled from the streamed JSONL equal
        the live collector's."""
        monkeypatch.setenv("SPARKDL_EVENT_DIR", str(tmp_path))
        events.reset()
        telemetry.start()
        eng = GenerationEngine(StubBackend(2, 64, step_s=0.0005),
                               prefill_chunk=8)
        hs = [eng.submit([1 + i, 2], max_new_tokens=6)
              for i in range(5)]
        _drain(eng, hs)
        live = {t["request"]: t
                for t in telemetry.request_traces().traces()}
        telemetry.stop()
        events.reset()  # close the stream
        recs = analysis.load_event_dir(str(tmp_path))
        offline = {t["request"]: t for t in
                   telemetry.assemble_request_traces(recs).traces()}
        assert live.keys() == offline.keys()
        for rid, t in live.items():
            assert offline[rid] == t


# ---------------------------------------------------------------------------
# Drift guard: serve_* attribution (satellite 4)
# ---------------------------------------------------------------------------

class TestAttributionDriftGuard:
    def test_every_emitted_serve_event_is_classified_and_attributed(
            self):
        """Drive every scheduler path (chunked, blocking, paged +
        preemption, speculation, retry + quarantine, reject) with a tee
        capturing records: every serve_* name must be classified in
        exactly one scope set, and every REQUEST-scoped record must
        carry request= — the trace collector silently degrades without
        it."""
        seen: list = []
        events.add_tee(
            lambda rec: seen.append(dict(rec))
            if str(rec.get("name", "")).startswith("serve_") else None)
        try:
            # chunked + spec
            eng = GenerationEngine(StubBackend(2, 64, vocab_size=8),
                                   prefill_chunk=8, spec_k=2)
            hs = [eng.submit([1, 2, 3], max_new_tokens=8)
                  for _ in range(3)]
            _drain(eng, hs)
            # blocking
            engb = GenerationEngine(StubBackend(2, 64),
                                    stall_free=False)
            hb = engb.submit([1, 2, 3], max_new_tokens=4)
            _drain(engb, [hb])
            # paged, pool tight enough to preempt and admission-wait
            engp = GenerationEngine(
                StubBackend(4, 128, block_size=8, pool_blocks=10),
                prefill_chunk=8)
            hp = [engp.submit([1, 2, 3], max_new_tokens=24)
                  for _ in range(6)]
            _drain(engp, hp)
            assert engp.stats["preemptions"] > 0 \
                or engp.stats["block_stall_events"] > 0

            # prefill failure: retry then quarantine
            class Flaky(StubBackend):
                def prefill_chunk(self, *a, **kw):
                    raise RuntimeError("boom")

            engf = GenerationEngine(Flaky(1, 64), retries=1,
                                    prefill_chunk=8)
            hf = engf.submit([1, 2], max_new_tokens=2)
            engf.run_until_idle()
            assert hf.state == "failed"

            # blocking-path prefill failure (serve_prefill_retry)
            class FlakyBlocking(StubBackend):
                def prefill(self, *a, **kw):
                    raise RuntimeError("boom")

            engfb = GenerationEngine(FlakyBlocking(1, 64), retries=1,
                                     stall_free=False)
            hfb = engfb.submit([1, 2], max_new_tokens=2)
            engfb.run_until_idle()
            assert hfb.state == "failed"

            # decode-step failure: step retry + suspect eviction
            class FlakyStep(StubBackend):
                def step(self, active):
                    raise RuntimeError("step boom")

            engs = GenerationEngine(FlakyStep(1, 64), retries=1,
                                    prefill_chunk=8)
            hs2 = engs.submit([1, 2], max_new_tokens=4)
            engs.run_until_idle()
            assert hs2.state == "failed"
            # rejection (pre-admission — engine-scoped by design)
            with pytest.raises(Exception):
                eng.submit([], max_new_tokens=2)
        finally:
            events._TEES.clear()
        names = {r["name"] for r in seen}
        unclassified = names - REQUEST_SCOPED_EVENTS \
            - ENGINE_SCOPED_EVENTS
        assert not unclassified, (
            f"new serve_* emissions must be classified request- or "
            f"engine-scoped: {sorted(unclassified)}")
        for r in seen:
            if r["name"] in REQUEST_SCOPED_EVENTS:
                assert "request" in r, \
                    f"{r['name']} dropped request= attribution: {r}"
        # the paths above must actually exercise the interesting names
        assert {"serve_queue", "serve_prefill", "serve_decode",
                "serve_request_quarantined",
                "serve_prefill_chunk_retry", "serve_prefill_retry",
                "serve_step_retry", "serve_reject"} <= names

    def test_engine_source_emissions_all_classified(self):
        """Static completeness: every serve_* literal passed to
        events.event/span/completed_span in engine.py appears in one of
        the scope sets — adding an emission without classifying it
        fails here even if no runtime path above reaches it."""
        src = open(os.path.join(
            _REPO, "sparkdl_tpu", "serving", "engine.py")).read()
        emitted = set(re.findall(
            r"events\.(?:event|span|completed_span)\(\s*\n?\s*"
            r"['\"](serve_[a-z_]+)['\"]", src))
        assert emitted, "expected serve_* emissions in engine.py"
        unclassified = emitted - REQUEST_SCOPED_EVENTS \
            - ENGINE_SCOPED_EVENTS
        assert not unclassified, sorted(unclassified)


# ---------------------------------------------------------------------------
# Off-plane overhead pins (satellite 4)
# ---------------------------------------------------------------------------

class TestOffPlaneOverhead:
    def test_zero_registration_and_no_tee_when_plane_off(self):
        """Plane off: no tee (collector included), zero metric
        registration from a full engine run (slo gauges included), no
        traces collected."""
        assert events._TEES == []
        eng = GenerationEngine(StubBackend(2, 64, vocab_size=8),
                               prefill_chunk=8, spec_k=2)
        hs = [eng.submit([1, 2, 3], max_new_tokens=8)
              for _ in range(3)]
        _drain(eng, hs)
        assert events._TEES == []
        assert telemetry.registry().snapshot() == {
            "counters": {}, "gauges": {}, "histograms": {}}
        assert telemetry.request_traces().traces() == []
        assert telemetry.request_traces().summary() is None
        # and the snapshot carries neither a traces nor an slo block
        snap = telemetry.snapshot()
        assert "request_traces" not in snap
        assert "slo" not in snap

    def test_no_per_token_event_cost(self):
        """The per-request emission count is independent of output
        length: tracing attribution rides the three lifecycle spans,
        never per-token events."""
        def count_serve_records(max_new):
            rec = events.reset()
            eng = GenerationEngine(StubBackend(1, 256),
                                   prefill_chunk=8)
            h = eng.submit([1, 2, 3], max_new_tokens=max_new)
            _drain(eng, [h])
            return sum(1 for r in rec.tail()
                       if str(r.get("name", "")).startswith("serve_"))

        assert count_serve_records(4) == count_serve_records(64)

    def test_slo_monitor_off_without_env(self):
        assert slo.monitor() is None
        assert slo.evaluate({"t": time.time()}) is None


# ---------------------------------------------------------------------------
# Live engine inspector (/serving)
# ---------------------------------------------------------------------------

class TestIntrospect:
    def test_debug_state_paged_engine(self):
        eng = GenerationEngine(
            StubBackend(3, 64, block_size=8, pool_blocks=30),
            prefill_chunk=8)
        h = eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
        st = eng.debug_state()
        assert st["num_slots"] == 3
        assert st["queue"]["depth"] == 1
        assert st["queue"]["head"]["request"] == h.id
        assert st["queue"]["head"]["age_s"] >= 0
        assert [s["slot"] for s in st["slots"]] == [0, 1, 2]
        assert all(s["state"] == "idle" for s in st["slots"])
        assert all("kv_blocks" in s for s in st["slots"])
        assert "blocks_free" in st["kv_pool"]
        eng.run_until_idle()
        st = eng.debug_state()
        assert st["slots_busy"] == 0
        assert st["stats"]["completed"] == 1
        assert st["fatal"] is None

    def test_debug_state_mid_run_slot_map(self):
        eng = GenerationEngine(StubBackend(2, 64), prefill_chunk=8)
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.submit([4, 5, 6], max_new_tokens=4)
        eng._admit()
        st = eng.debug_state()
        busy = [s for s in st["slots"] if s["state"] != "idle"]
        assert len(busy) == 2
        for s in busy:
            assert s["state"] == "prefilling"
            assert s["chunks_total"] == 1
            assert s["tokens_out"] == 0
        eng.run_until_idle()

    def test_serving_endpoint_live(self):
        """/serving on the telemetry HTTP server returns every live
        engine's state as JSON."""
        telemetry.start(port=0)
        port = telemetry.server_port()
        assert port is not None
        eng = GenerationEngine(StubBackend(2, 64), prefill_chunk=8)
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng._admit()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/serving", timeout=10) as resp:
            body = json.loads(resp.read().decode())
        ours = [e for e in body["engines"]
                if e.get("backend") == "StubBackend"
                and e.get("slots_busy", 0) > 0]
        assert ours, body
        assert ours[0]["slots"][0]["state"] == "prefilling"
        eng.run_until_idle()


# ---------------------------------------------------------------------------
# SLO monitor
# ---------------------------------------------------------------------------

def _hist(bounds, buckets, count=None, s=0.0):
    return {"bounds": list(bounds), "buckets": list(buckets),
            "count": count if count is not None else buckets[-1],
            "sum": s}


class TestSloMonitor:
    def test_fraction_below(self):
        h = _hist((0.1, 1.0, 10.0), [50, 90, 100])
        assert telemetry.histogram_fraction_below(h, 0.1) == 0.5
        # interpolated inside (0.1, 1.0]: 50 + 40*(0.55-0.1)/0.9 = 70
        assert telemetry.histogram_fraction_below(h, 0.55) == \
            pytest.approx(0.7, abs=1e-6)
        assert telemetry.histogram_fraction_below(h, 10.0) == 1.0
        assert telemetry.histogram_fraction_below(h, 100.0) == 1.0
        assert telemetry.histogram_fraction_below({}, 1.0) is None
        # +Inf-bucket observations count as above any finite threshold
        h2 = _hist((0.1,), [5], count=10)
        assert telemetry.histogram_fraction_below(h2, 0.5) == 0.5

    def test_burn_rate_windows_and_breach_flip(self, monkeypatch):
        """Synthetic history: compliant traffic, then a burst of
        violations — burn must exceed the threshold in every window and
        the breach event fire exactly once per transition."""
        monkeypatch.setenv("SPARKDL_SLO_TTFT_S", "1.0")
        mon = slo.SloMonitor(slo.objectives_from_env(),
                             windows_s=(10.0, 60.0))
        rec = events.reset()

        def snap_at(t, good, bad):
            return {"t": t, "histograms": {"serving_ttft_s": _hist(
                (1.0, 5.0), [good, good + bad])}}

        b0 = mon.evaluate(snap_at(1000.0, 100, 0))
        ob = b0["objectives"]["ttft"]
        assert ob["compliance"] == 1.0 and not ob["breaching"]
        # 30s later: 100 new requests, 10 violations — burn 10x in both
        # the 10s and 60s windows (window diffs vs history)
        b1 = mon.evaluate(snap_at(1030.0, 190, 10))
        ob = b1["objectives"]["ttft"]
        assert ob["breaching"] is True
        assert ob["burn_rate"] == pytest.approx(10.0, rel=0.01)
        names = [e["name"] for e in rec.tail()]
        assert names.count("slo_breach") == 1
        # recovery: clean traffic, short window clean -> not breaching
        b2 = mon.evaluate(snap_at(1045.0, 290, 10))
        assert b2["objectives"]["ttft"]["breaching"] is False
        names = [e["name"] for e in rec.tail()]
        assert names.count("slo_recovered") == 1

    def test_error_rate_objective(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_SLO_ERROR_RATE", "0.1")
        mon = slo.SloMonitor(slo.objectives_from_env(),
                             windows_s=(10.0,))
        c0 = {"t": 0.0, "counters": {
            "serving_requests_completed_total": 90.0,
            "serving_requests_quarantined_total": 0.0}}
        mon.evaluate(c0)
        c1 = {"t": 20.0, "counters": {
            "serving_requests_completed_total": 140.0,
            "serving_requests_quarantined_total": 50.0}}
        ob = mon.evaluate(c1)["objectives"]["errors"]
        # window: 50 completed + 50 errors -> error rate 0.5, burn 5x
        assert ob["breaching"] is True
        assert ob["burn_rate"] == pytest.approx(5.0, rel=0.01)

    def test_plane_snapshot_carries_slo_block_and_gauges(
            self, monkeypatch):
        monkeypatch.setenv("SPARKDL_SLO_TTFT_S", "0.001")
        monkeypatch.setenv("SPARKDL_SLO_WINDOWS_S", "5,30")
        slo.reset()
        telemetry.start()
        eng = GenerationEngine(StubBackend(2, 64, step_s=0.002),
                               prefill_chunk=8)
        hs = [eng.submit([1 + i, 2], max_new_tokens=4)
              for i in range(4)]
        _drain(eng, hs)
        snap = telemetry.snapshot()  # every TTFT > 1ms: burning
        ob = snap["slo"]["objectives"]["ttft"]
        assert ob["breaching"] is True
        telemetry.snapshot()  # gauges land for the NEXT read
        gauges = telemetry.registry().snapshot()["gauges"]
        assert gauges["slo_ttft_burn_rate"]["value"] > 1.0
        assert gauges["slo_ttft_compliance"]["value"] < 0.99

    def test_armed_objective_without_traffic_registers_no_gauges(
            self, monkeypatch):
        """An armed objective that has seen NO traffic must export
        nothing — a default-0.0 compliance gauge would read as a total
        SLO failure when the truth is 'no data'."""
        monkeypatch.setenv("SPARKDL_SLO_TTFT_S", "1.0")
        slo.reset()
        telemetry.start()
        telemetry.snapshot()
        telemetry.snapshot()
        assert telemetry.registry().snapshot()["gauges"] == {}

    def test_compliance_from_traces(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_SLO_TTFT_S", "0.5")
        monkeypatch.setenv("SPARKDL_SLO_LATENCY_S", "2.0")
        monkeypatch.setenv("SPARKDL_SLO_ERROR_RATE", "0.3")
        traces = [
            {"ttft_s": 0.1, "latency_s": 1.0, "finish": "length"},
            {"ttft_s": 0.9, "latency_s": 3.0, "finish": "length"},
            {"ttft_s": None, "latency_s": 0.2, "finish": "error"},
        ]
        out = slo.compliance_from_traces(traces)
        assert out["ttft"]["compliance"] == 0.5
        # latency population mirrors the live histogram: COMPLETED
        # requests only (the engine observes serving_request_latency_s
        # at _retire) — the 0.2s error trace is excluded, so 1 of the
        # 2 completed traces is under the 2.0s threshold
        assert out["latency"]["compliance"] == 0.5
        assert out["errors"]["compliance"] == pytest.approx(2 / 3)
        assert out["errors"]["met"] is False
        # a partial trace (fabricated attributed-sum latency) is
        # excluded from the latency population too
        traces.append({"ttft_s": None, "latency_s": 0.01,
                       "partial": True, "finish": "length"})
        out2 = slo.compliance_from_traces(traces)
        assert out2["latency"]["compliance"] == 0.5
        assert out2["latency"]["total"] == 2


# ---------------------------------------------------------------------------
# CLIs (in-process — tier-1 lean rule) + lint + bench fields
# ---------------------------------------------------------------------------

def _run_serving_workload(event_dir, monkeypatch):
    monkeypatch.setenv("SPARKDL_EVENT_DIR", str(event_dir))
    events.reset()
    eng = GenerationEngine(StubBackend(2, 64, step_s=0.001,
                                       prefill_s=0.004),
                           prefill_chunk=8)
    hs = [eng.submit([1 + i, 2, 3], max_new_tokens=8)
          for i in range(8)]
    _drain(eng, hs)
    events.reset()  # close the stream
    monkeypatch.delenv("SPARKDL_EVENT_DIR")


def _load_check_metric_docs():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_metric_docs",
        os.path.join(_REPO, "scripts", "check_metric_docs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestReportClis:
    def test_request_report_cli(self, tmp_path, monkeypatch, capsys):
        _run_serving_workload(tmp_path, monkeypatch)
        monkeypatch.setenv("SPARKDL_SLO_TTFT_S", "5.0")
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "request_report",
            os.path.join(_REPO, "scripts", "request_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main([str(tmp_path), "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "8 completed" in out
        assert "dominant cause" in out
        assert "SLO compliance" in out and "ttft" in out
        # JSON mode round-trips
        assert mod.main([str(tmp_path), "--json"]) == 0
        rec = json.loads(capsys.readouterr().out)
        assert rec["completed"] == 8
        assert rec["tail_dominant_phase"] in rec["tail_phase_frac"]
        assert rec["max_unattributed_frac"] <= 0.05
        assert rec["slo"]["ttft"]["met"] is True
        # empty dir -> exit 2
        empty = tmp_path / "empty"
        empty.mkdir()
        assert mod.main([str(empty)]) == 2

    def test_bottleneck_report_appends_request_block(
            self, tmp_path, monkeypatch, capsys):
        """Satellite: with serve_* spans in the event dir the existing
        stage report gains the SLO-compliance block and the
        phase-attributed slowest-requests table."""
        _run_serving_workload(tmp_path, monkeypatch)
        monkeypatch.setenv("SPARKDL_SLO_LATENCY_S", "10.0")
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "bottleneck_report",
            os.path.join(_REPO, "scripts", "bottleneck_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "dominant stage" in out      # the PR 6 stage report
        assert "request traces:" in out     # the ISSUE 13 block
        assert "SLO compliance" in out
        assert "latency" in out
        assert mod.main([str(tmp_path), "--json"]) == 0
        rec = json.loads(capsys.readouterr().out)
        assert rec["requests"]["completed"] == 8
        assert rec["report"] is not None

    def test_check_metric_docs_lint(self, tmp_path):
        mod = _load_check_metric_docs()
        # the repo itself must be clean
        assert mod.missing_metrics() == []
        # synthetic drift is caught
        pkg = tmp_path / "sparkdl_tpu"
        pkg.mkdir()
        (pkg / "x.py").write_text(
            'reg.counter("totally_new_metric_total").inc()\n'
            '_metric("gauge", "another_new_gauge", 1)\n')
        (tmp_path / "README.md").write_text("nothing documented\n")
        missing = mod.missing_metrics(root=str(tmp_path),
                                      readme=str(tmp_path / "README.md"))
        assert missing == ["another_new_gauge",
                           "totally_new_metric_total"]

    def test_readme_metric_tables_name_only_registered_metrics(self):
        """The lint in reverse, on the repo: no row of the README's
        metric tables outlives the metric it documents."""
        mod = _load_check_metric_docs()
        stale = mod.stale_metrics()
        assert stale == [], \
            f"README.md tables name metrics no code registers: {stale}"

    def test_metric_lint_catches_synthetic_stale_row(self, tmp_path):
        """Only table rows count (prose may name a metric that went), a
        row may hold several names and several types, and each name is
        checked on its own."""
        mod = _load_check_metric_docs()
        pkg = tmp_path / "sparkdl_tpu"
        pkg.mkdir()
        (pkg / "x.py").write_text(
            'reg.counter("kept_total").inc()\n'
            'reg.histogram("kept_s").observe(1.0)\n')
        readme = tmp_path / "README.md"
        readme.write_text(
            "prose about `gone_in_prose_total`\n\n"
            "| Metric | Type | Meaning |\n|---|---|---|\n"
            "| `kept_total` / `gone_total` | counter | x |\n"
            "| `kept_s` / `gone_s` | counter / histogram | y |\n"
            "| `not_a_metric` | string | z |\n")
        assert mod.stale_metrics(root=str(tmp_path),
                                 readme=str(readme)) == \
            ["gone_s", "gone_total"]

    def test_serve_bench_leg_records_slo_and_slowest_trace(self):
        """Satellite: run_engine_leg's record carries the SLO
        compliance numbers, the slowest-trace phase breakdown, and the
        attribution residual."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "serve_bench",
            os.path.join(_REPO, "scripts", "serve_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        workload = [([1 + i, 2, 3], 6) for i in range(12)]
        leg = mod.run_engine_leg(
            lambda: GenerationEngine(StubBackend(2, 64,
                                                 step_s=0.0005),
                                     prefill_chunk=8),
            workload, concurrency=4)
        assert leg["completed"] == 12
        assert leg["slo"]["ttft_compliance"] >= 0.99
        assert leg["slo"]["latency_compliance"] >= 0.99
        assert leg["trace_attribution"]["within_5pct"] is True
        st = leg["slowest_trace"]
        assert st["dominant_phase"] in (
            "queue", "prefill", "prefill_wait", "block_stall", "draft",
            "decode", "unattributed")

    def test_serve_bench_survivability_leg_and_gating(self):
        """ISSUE 19 satellite: the survivability leg reports one
        injected failover's recovery latency + the exactly-once
        token-identity float."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "serve_bench",
            os.path.join(_REPO, "scripts", "serve_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        surv = mod.run_survivability_comparison(n_requests=8,
                                                concurrency=4)
        assert surv["failovers"] == 1
        assert surv["token_identical"] == 1.0  # float, NOT bool
        assert not isinstance(surv["token_identical"], bool)
        assert surv["recovery_s"] is not None and surv["recovery_s"] > 0
        assert surv["clean"]["completed"] == 8
        assert surv["faulted"]["completed"] == 8

    def test_serve_bench_fleet_leg_and_gating(self):
        """ISSUE 20 satellite: the fleet leg reports the radix-vs-
        round-robin routing comparison plus one unclean replica kill's
        recovery latency and the cross-replica exactly-once float."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "serve_bench",
            os.path.join(_REPO, "scripts", "serve_bench.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        flt = mod.run_fleet_comparison(n_requests=12, step_s=0.001)
        assert flt["token_identical"] == 1.0  # float, NOT bool
        assert not isinstance(flt["token_identical"], bool)
        assert flt["recovery_s"] is not None and flt["recovery_s"] > 0
        assert flt["readmissions"] >= 1
        for leg in (flt["radix"], flt["round_robin"]):
            assert leg["completed"] == flt["requests"]
            assert leg["reused_tokens"] >= 0

    def test_gang_aggregation_merges_trace_blocks(self, tmp_path):
        """aggregate_snapshots re-ranks the per-rank slowest lists into
        one gang tail."""
        for rank, lat in ((0, 1.0), (1, 9.0)):
            snap = {"t": 1.0, "rank": rank, "elapsed_s": 1.0,
                    "stages": {}, "request_traces": {
                        "completed": 2, "open": 0,
                        "slowest": [{"request": rank * 10,
                                     "latency_s": lat}]}}
            (tmp_path / f"metrics_rank{rank}.json").write_text(
                json.dumps(snap))
        agg = telemetry.aggregate_snapshots(str(tmp_path))
        tb = agg["request_traces"]
        assert tb["completed"] == 4
        assert tb["slowest"][0]["request"] == 10  # rank 1's 9.0s leads

    def test_gang_aggregation_honors_slowest_knob(self, tmp_path,
                                                  monkeypatch):
        """The gang re-rank trims to SPARKDL_TRACE_SLOWEST — the same
        bound each rank's export honors, not the compile-time
        default."""
        monkeypatch.setenv("SPARKDL_TRACE_SLOWEST", "2")
        for rank in (0, 1):
            snap = {"t": 1.0, "rank": rank, "elapsed_s": 1.0,
                    "stages": {}, "request_traces": {
                        "completed": 2, "open": 0,
                        "slowest": [{"request": rank * 10 + i,
                                     "latency_s": float(i)}
                                    for i in range(2)]}}
            (tmp_path / f"metrics_rank{rank}.json").write_text(
                json.dumps(snap))
        agg = telemetry.aggregate_snapshots(str(tmp_path))
        assert len(agg["request_traces"]["slowest"]) == 2


class TestEngineInspectorIntegrity:
    def test_introspect_registry_is_weak(self):
        import gc
        import weakref
        eng = GenerationEngine(StubBackend(1, 32))
        assert eng in introspect.live_engines()
        wr = weakref.ref(eng)
        del eng
        gc.collect()
        # the registry holds no strong ref: the engine is collectable
        # and therefore gone from the live list
        assert wr() is None
        assert all(wr() is not e for e in introspect.live_engines())

    def test_serving_snapshot_degrades_per_engine(self):
        eng = GenerationEngine(StubBackend(1, 32))
        eng.backend.pool_stats = None  # not callable -> fine
        snap = introspect.serving_snapshot()
        assert snap["n_engines"] >= 1
        assert all("slots" in e or "error" in e
                   for e in snap["engines"])

    def test_debug_state_exposes_failover_and_delivery_cursors(self):
        """ISSUE 19: the /serving view carries the failover state
        machine block, and each occupied slot row shows the exactly-once
        audit fields (delivery cursor + per-request failover count)."""
        eng = GenerationEngine(StubBackend(1, 32, vocab_size=997))
        eng.submit([5], max_new_tokens=8)
        for _ in range(3):
            eng.step()
        state = introspect.engine_debug_state(eng)
        fo = state["failover"]
        assert fo["state"] == "healthy"
        assert fo["count"] == 0 and fo["quarantined_total"] == 0
        row = state["slots"][0]
        assert row["state"] == "running"
        # the delivery cursor must sit exactly at the emitted frontier
        # at every iteration boundary — that equality IS exactly-once
        assert row["delivered"] == row["tokens_out"] > 0
        assert row["failovers"] == 0
        # snapshot() (the aggregate-counters view) carries it too
        assert eng.snapshot()["failover"]["state"] == "healthy"
