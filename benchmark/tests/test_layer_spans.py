"""The four drain readers on a hand-made ring and a hand-made trace: the two
explanations of the idle time between steps must read differently."""

import pytest

import tiny  # noqa: F401  (puts the checkout and benchmark/ on sys.path)
from harness import loader, trace
from sparkdl_tpu.runner import events

EVERY, STEPS, STEP_NS = 10, 40, 1_000_000
DRAINS = ("step_period_ms", "drain_refill_ms", "drain_gap_ms",
          "drain_idle_share")


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


def _device(gap_ns):
    """What is left of the step the trace started in, a false gap, then
    STEPS + 1 runs of the step's program, one operation each, and before
    step i an idle gap of ``gap_ns(i)``."""
    mods, ops = [("jit_step", 5_000, STEP_NS // 2)], \
        [("fusion.1", 5_000, STEP_NS // 2)]
    t = 5_000 + STEP_NS // 2 + 300_000
    for i in range(STEPS + 1):
        t += gap_ns(i)
        mods.append(("jit_step", t, STEP_NS))
        ops.append(("fusion.1", t, STEP_NS))
        t += STEP_NS
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def _ring(period_s, refill_s, fetch_every=EVERY, steps=STEPS, t0=1000.0):
    """What ``fit`` records: a put and a dispatch each step, and a fetch at
    every boundary, after which the next dispatch ends ``refill_s`` later.
    Returns ``ctx["spans"]``, as the driver's tee keeps them."""
    rec = events.reset()
    spans, t = [], t0
    for i in range(steps):
        for name, at in (("shard_put", t + 1e-4), ("step_compute", t + 4e-4)):
            rec.emit(name, "B", {"step": i}, t=at - 1e-4)
            rec.emit(name, "E", {"step": i, "dur_s": 1e-4}, t=at)
            spans.append({"name": name, "t": at, "dur_s": 1e-4})
        if (i + 1) % fetch_every == 0:
            # the host waits out the steps it ran ahead of, then refills
            t = t0 + (i + 1) * period_s
            rec.emit("loss_fetch", "B", {"step": i + 1, "every": fetch_every},
                     t=t - 1e-3)
            rec.emit("loss_fetch", "E", {"step": i + 1, "every": fetch_every,
                                         "dur_s": 1e-3}, t=t)
            t += refill_s - 4e-4
        else:
            t += 5e-4
    return spans


@pytest.fixture(autouse=True)
def _fresh_ring():
    yield
    events.reset()


def _ctx(tr, spans):
    return {"trace": tr, "spans": spans,
            "device_summary": trace.device_summary(tr)}


def test_all_idle_at_the_drains_reads_as_the_whole_idle_share():
    tr = _device(lambda i: 400_000 if i and i % EVERY == 0 else 0)
    ctx = _ctx(tr, _ring(period_s=1.04e-3, refill_s=2e-3))
    idle = _read("device_idle_share", ctx)
    assert idle == pytest.approx(100 * 4 * 0.4 / (40 + 4 * 0.4))
    assert _read("drain_idle_share", ctx) == pytest.approx(idle)
    assert _read("drain_gap_ms", ctx) == pytest.approx(0.4)
    assert _read("step_period_ms", ctx) == pytest.approx(1.04, rel=1e-3)
    assert _read("drain_refill_ms", ctx) == pytest.approx(2.0, rel=1e-3)


def test_idle_spread_over_every_step_reads_as_n_over_steps_of_it():
    tr = _device(lambda i: 40_000)
    ctx = _ctx(tr, _ring(period_s=1.04e-3, refill_s=2e-3))
    idle = _read("device_idle_share", ctx)
    assert idle == pytest.approx(100 * 0.04 / 1.04)
    # four drains in forty steps: a tenth of the idle time, not all of it
    assert _read("drain_idle_share", ctx) == pytest.approx(idle * 4 / 40)
    assert _read("drain_gap_ms", ctx) == pytest.approx(0.04)
    # the host's clock reads the same in both: only the trace tells them apart
    assert _read("step_period_ms", ctx) == pytest.approx(1.04, rel=1e-3)


def test_the_number_of_drains_comes_from_the_spans_step_distance():
    tr = _device(lambda i: 400_000 if i and i % 5 == 0 else 0)
    ctx = _ctx(tr, _ring(period_s=1.08e-3, refill_s=2e-3, fetch_every=5))
    assert _read("drain_idle_share", ctx) == pytest.approx(
        _read("device_idle_share", ctx))
    assert _read("drain_gap_ms", ctx) == pytest.approx(0.4)


def test_spans_outside_the_traced_stretch_are_left_out():
    tr = _device(lambda i: 400_000 if i and i % EVERY == 0 else 0)
    spans = _ring(period_s=1.04e-3, refill_s=2e-3)
    # only the first 25 steps were traced: fetches at steps 10 and 20
    ctx = _ctx(tr, [s for s in spans if s["t"] < 1000.0 + 25 * 1.04e-3])
    assert _read("step_period_ms", ctx) == pytest.approx(1.04, rel=1e-3)
    ctx = _ctx(tr, [s for s in spans if s["t"] < 1000.0 + 15 * 1.04e-3])
    assert all(_read(m, ctx) is None for m in DRAINS)


@pytest.mark.parametrize("steps", [0, 12])
def test_under_two_fetches_there_is_nothing_to_read(steps):
    tr = _device(lambda i: 40_000)
    ctx = _ctx(tr, _ring(period_s=1.04e-3, refill_s=2e-3, steps=steps))
    assert all(_read(m, ctx) is None for m in DRAINS)


def test_without_a_device_plane_the_span_metrics_still_read():
    ctx = _ctx({}, _ring(period_s=1.04e-3, refill_s=2e-3))
    assert _read("step_period_ms", ctx) == pytest.approx(1.04, rel=1e-3)
    assert _read("drain_gap_ms", ctx) is None
    assert _read("drain_idle_share", ctx) is None
