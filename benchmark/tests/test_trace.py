"""The trace reducers against hand-made intervals and the recorded trace."""

import os

import pytest

import tiny
from harness import loader, trace


def test_union_and_gaps_of_hand_made_intervals():
    iv = [(0, 10), (5, 20), (30, 40), (100, 120)]
    assert trace.union_seconds(iv, 0, 100) == pytest.approx(30e-9)
    assert trace.union_seconds(iv, 8, 110) == pytest.approx(32e-9)
    assert trace.gaps(iv, 0, 100) == [(20, 30), (40, 100)]


T0 = 201_000


def _synthetic():
    # the trace starts inside a step: what is left of that program, a false
    # gap, then three steps of 100 us every 150 us, two ops of 40 us in each
    mods = [("jit_step", 1000, 60_000)]
    ops = [("fusion.2", 1000 + 10_000, 40_000)]
    for i in range(3):
        t = T0 + 150_000 * i
        mods.append(("jit_step", t, 100_000))
        ops += [("fusion.1", t, 40_000), ("fusion.2", t + 50_000, 40_000)]
    host = [("bench_feed", T0 + 100_000, 45_000),
            ("bench_feed", T0 + 250_000, 45_000)]
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops},
            "/host:CPU": {"python": host}}


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


def test_the_steady_window_runs_from_the_second_program_to_the_last():
    plane = _synthetic()["/device:TPU:0"]
    # the cut first program, its operations and the gap after it are outside
    assert trace.steady_window(plane) == (T0, T0 + 300_000, 2)
    # three programs hold one whole step at the most: nothing to read
    plane["XLA Modules"] = plane["XLA Modules"][:3]
    assert trace.steady_window(plane) is None
    assert trace.device_summary({"/device:TPU:0": plane}) is None


def test_device_idle_share_on_synthetic_steps():
    tr = _synthetic()
    summary = trace.device_summary(tr)
    assert summary[0]["steps"] == 2
    assert summary[0]["window_s"] == pytest.approx(300e-6)
    assert summary[0]["busy_s"] == pytest.approx(160e-6)
    ctx = {"device_summary": summary, "flops_per_example": 1e6,
           "global_batch": 10, "chips": 1,
           "peak": {"bf16_flops_per_s": 1e12}}
    assert _read("device_idle_share", ctx) == pytest.approx(100 * 140 / 300)
    assert _read("train_step_device_ms", ctx) == pytest.approx(0.1)
    # 2 steps * 10 examples * 1e6 over 300 us at 1e12/s
    assert _read("train_step_mfu", ctx) == pytest.approx(100 * 2e7 / 3e8)
    assert trace.top_device_ops(tr)[0][1] == pytest.approx(80e-6)


def test_idle_gaps_are_named_by_the_host_event_over_them():
    tr = _synthetic()
    named = dict(trace.idle_gaps_by_host(tr))
    # the 60 us between steps lies under bench_feed; the 10 us inside a step
    # is the device's own
    assert named["bench_feed"] == pytest.approx(2 * 60e-6)
    assert named[trace.SHORT_GAPS] == pytest.approx(4 * 10e-6 + 0e-6, abs=21e-6)
    # with the device traced alone, the same gaps are named by where they lie
    del tr["/host:CPU"]
    named = dict(trace.idle_gaps_by_host(tr))
    assert named[trace.BETWEEN_STEPS] == pytest.approx(2 * 60e-6)


def test_readers_return_nothing_where_nothing_is_to_read():
    for m in ("device_idle_share", "train_step_device_ms", "train_step_mfu",
              "step_dispatch_ms", "shard_put_ms"):
        assert _read(m, {"device_summary": None, "spans": []}) is None


def test_span_readers_take_medians():
    spans = [{"name": "step_compute", "dur_s": d, "t": 0} for d in (1e-3, 2e-3, 9e-3)]
    spans += [{"name": "shard_put", "dur_s": 4e-3, "t": 0}]
    assert _read("step_dispatch_ms", {"spans": spans}) == pytest.approx(2.0)
    assert _read("shard_put_ms", {"spans": spans}) == pytest.approx(4.0)


def test_recorded_trace_reduces():
    path = os.path.join(tiny.ROOT, "PROFILE_TRACE.json.gz")
    if not os.path.isfile(path):
        pytest.skip("PROFILE_TRACE.json.gz is gone")
    tr = trace.read_chrome_json(path)
    planes = trace.device_planes(tr)
    assert list(planes) == ["/device:TPU:0"]
    ops = [(s, s + d) for _, s, d in planes["/device:TPU:0"]["XLA Ops"]]
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    busy = trace.union_seconds(ops, lo, hi)
    # the union can never pass the window nor the plain sum of durations
    assert 0 < busy <= (hi - lo) / 1e9 + 1e-12
    assert busy <= sum(e - s for s, e in ops) / 1e9 + 1e-12
    idle = sum(e - s for s, e in trace.gaps(ops, lo, hi)) / 1e9
    assert busy + idle == pytest.approx((hi - lo) / 1e9)
    summary = trace.device_summary(tr)
    if summary:   # the recording holds whole steps
        share = 1 - summary[0]["busy_s"] / summary[0]["window_s"]
        assert 0 <= share < 1
