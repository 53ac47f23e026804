"""Online anomaly sentinel tests (ISSUE 17): rolling-baseline drift
detection over step-time/TTFT/decode/queue-depth — a 5x slowdown must
fire an ``anomaly`` flight-recorder event + counter within one rolling
window, the baseline must NOT absorb anomalous samples (a sustained
slowdown can't normalize itself), and with the sentinel off the hook is
pinned ≈ free (PR 6's plane-off rule). Jax-free throughout.
"""

import json
import os
import threading

import pytest

from sparkdl_tpu.runner import events, sentinel, telemetry
from sparkdl_tpu.runner.metrics import ThroughputMeter


@pytest.fixture(autouse=True)
def _fresh():
    """Every test starts disarmed with clean recorder/registry; env
    arming from one test must not leak into the next."""
    sentinel.disarm()
    telemetry.reset()
    events.reset()
    yield
    sentinel.disarm()
    telemetry.reset()
    events.reset()


class TestRollingBaseline:
    def test_detects_5x_slowdown_within_one_window(self):
        b = sentinel.RollingBaseline("step_time", ratio=2.0, window=8,
                                     min_n=8)
        for _ in range(16):
            assert b.observe(0.01) is None  # healthy: builds baseline
        fired = []
        for i in range(8):  # one window of 5x-slow steps
            a = b.observe(0.05)
            if a:
                fired.append((i, a))
        assert len(fired) == 1  # edge-triggered: ONE event per episode
        i, a = fired[0]
        assert i < 8  # detected within one rolling window
        assert a["metric"] == "step_time"
        assert a["window_p95"] >= 0.05
        assert a["baseline_p95"] == pytest.approx(0.01)

    def test_anomalous_samples_do_not_poison_baseline(self):
        """A sustained slowdown must keep reading as anomalous — if the
        slow samples were absorbed, the baseline would drift up and the
        episode would self-normalize."""
        b = sentinel.RollingBaseline("m", ratio=2.0, window=8, min_n=8)
        for _ in range(16):
            b.observe(0.01)
        n_before = len(b._baseline)
        for _ in range(50):
            b.observe(0.05)
        assert len(b._baseline) == n_before  # nothing absorbed
        assert b.summary()["anomalous"] is True
        assert b.baseline_p95() == pytest.approx(0.01)

    def test_recovery_rearms_the_edge(self):
        b = sentinel.RollingBaseline("m", ratio=2.0, window=4, min_n=8)
        for _ in range(16):
            b.observe(0.01)
        assert any(b.observe(0.05) for _ in range(4))  # episode 1
        healthy = [b.observe(0.01) for _ in range(8)]  # full recovery
        assert not any(healthy)
        assert b.summary()["anomalous"] is False
        assert any(b.observe(0.05) for _ in range(4))  # episode 2 fires
        assert b.summary()["anomalies"] == 2

    def test_zero_baseline_never_divides_or_fires(self):
        """An all-zero baseline (idle queue depth) must not fire on the
        first nonzero sample — ratio-vs-zero is not drift evidence."""
        b = sentinel.RollingBaseline("queue_depth", ratio=2.0, window=4,
                                     min_n=8)
        for _ in range(16):
            assert b.observe(0.0) is None
        for _ in range(8):
            assert b.observe(3.0) is None


class TestSentinelPlane:
    def test_anomaly_emits_event_and_counter(self):
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        for _ in range(16):
            sentinel.observe("step_time", 0.01)
        for _ in range(8):
            sentinel.observe("step_time", 0.05)
        anomalies = [e for e in events.get_recorder().tail()
                     if e["name"] == "anomaly"]
        assert len(anomalies) == 1
        assert anomalies[0]["metric"] == "step_time"
        assert anomalies[0]["ph"] == "P"
        counters = telemetry.registry().snapshot()["counters"]
        assert counters["sentinel_anomalies_total"] == 1
        assert sentinel.anomaly_counts() == {"step_time": 1}

    def test_metrics_are_independent(self):
        """Drift in one metric must not consume another's baseline."""
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        for _ in range(16):
            sentinel.observe("ttft", 0.01)
            sentinel.observe("decode_step", 0.002)
        for _ in range(8):
            sentinel.observe("ttft", 0.05)
            sentinel.observe("decode_step", 0.002)  # still healthy
        assert sentinel.anomaly_counts() == {"ttft": 1}
        st = sentinel.stats()
        assert st["decode_step"]["anomalies"] == 0

    def test_throughput_meter_feeds_step_time(self, monkeypatch):
        """The fit()-side hook: a metered loop whose steps suddenly run
        5x slower must trip the sentinel through ThroughputMeter alone."""
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        now = [100.0]
        monkeypatch.setattr("sparkdl_tpu.runner.metrics.time.perf_counter",
                            lambda: now[0])
        meter = ThroughputMeter(warmup_steps=0)
        for _ in range(20):
            now[0] += 0.01
            meter.update(8)
        for _ in range(8):
            now[0] += 0.05  # injected 5x slowdown
            meter.update(8)
        assert sentinel.anomaly_counts().get("step_time") == 1

    def test_arm_from_env_and_knobs(self, monkeypatch):
        monkeypatch.delenv(sentinel.SENTINEL_ENV, raising=False)
        assert sentinel.maybe_arm_from_env() is None
        assert not sentinel.armed()
        monkeypatch.setenv(sentinel.SENTINEL_ENV, "1")
        monkeypatch.setenv(sentinel.RATIO_ENV, "3.5")
        monkeypatch.setenv(sentinel.WINDOW_ENV, "16")
        monkeypatch.setenv(sentinel.MIN_N_ENV, "10")
        s = sentinel.maybe_arm_from_env()
        assert s is not None and sentinel.armed()
        assert s.ratio == 3.5 and s.window == 16 and s.min_n == 10

    def test_bad_env_values_degrade_to_defaults(self, monkeypatch):
        monkeypatch.setenv(sentinel.SENTINEL_ENV, "1")
        monkeypatch.setenv(sentinel.RATIO_ENV, "fast")
        monkeypatch.setenv(sentinel.WINDOW_ENV, "abc")
        s = sentinel.maybe_arm_from_env()
        assert s is not None
        assert s.ratio == sentinel._DEFAULT_RATIO
        assert s.window == sentinel._DEFAULT_WINDOW
        # a hostile window value still leaves a judgeable deque
        rb = sentinel.RollingBaseline("m", ratio=2.0, window=-3, min_n=4)
        for _ in range(16):
            rb.observe(0.01)
        assert rb.observe(0.05) is not None  # clamped, still detects


class TestOffIsFree:
    def test_off_registers_nothing(self):
        """ISSUE 17 acceptance: with the sentinel off, the same slowdown
        registers nothing — no events, no counters, no state."""
        for _ in range(16):
            sentinel.observe("step_time", 0.01)
        for _ in range(8):
            sentinel.observe("step_time", 0.05)
        assert sentinel._SENTINEL is None  # no state was ever built
        assert sentinel.anomaly_counts() == {}
        assert not any(e["name"] == "anomaly"
                       for e in events.get_recorder().tail())
        assert "sentinel_anomalies_total" not in \
            telemetry.registry().snapshot()["counters"]

    def test_off_adds_no_per_step_overhead(self):
        """The hot-path pin (PR 6's rule): disarmed observe() is one
        global read + return — no lock, no dict, no allocation. Pinned
        structurally: the fast path must bail before any attribute
        access on a Sentinel instance."""
        import dis
        ops = list(dis.get_instructions(sentinel.observe))
        idx = next(i for i, op in enumerate(ops)
                   if op.argval == "_SENTINEL")
        # nothing executes before the disarmed None-check's global read
        assert not any("CALL" in op.opname for op in ops[:idx])

    def test_disarm_after_arm_really_disarms(self):
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        assert sentinel.armed()
        sentinel.disarm()
        assert not sentinel.armed()
        sentinel.observe("step_time", 99.0)
        assert sentinel.anomaly_counts() == {}


class TestBenchLedger:
    def test_anomaly_counts_shape_rides_failure_stats(self):
        """anomaly_counts() is embedded in json records — the shape must
        stay a flat {metric: int} json-serializable dict."""
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        for _ in range(16):
            sentinel.observe("ttft", 0.01)
        for _ in range(8):
            sentinel.observe("ttft", 0.05)
        counts = sentinel.anomaly_counts()
        assert counts == json.loads(json.dumps(counts))
        assert all(isinstance(k, str) and isinstance(v, int)
                   for k, v in counts.items())


class TestConcurrency:
    def test_concurrent_observe_is_safe(self):
        """submit() threads and the engine loop observe concurrently —
        total anomaly accounting must survive the race."""
        sentinel.arm(ratio=2.0, window=8, min_n=8)
        for _ in range(32):
            sentinel.observe("queue_depth", 1.0)

        def hammer():
            for _ in range(200):
                sentinel.observe("queue_depth", 5.0)

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # exactly one edge-triggered anomaly for the sustained episode
        assert sentinel.anomaly_counts() == {"queue_depth": 1}
