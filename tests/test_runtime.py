"""Runtime tests: mesh construction, padding, prefetch pipeline, BatchRunner."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.core import runtime

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_make_mesh_default_and_2d():
    m = runtime.make_mesh()
    assert m.axis_names == ("data",)
    assert m.devices.size == 8

    m2 = runtime.make_mesh({"data": 4, "model": 2})
    assert m2.axis_names == ("data", "model")
    assert m2.devices.shape == (4, 2)

    m3 = runtime.make_mesh({"data": -1, "model": 2})
    assert m3.devices.shape == (4, 2)

    with pytest.raises(ValueError):
        runtime.make_mesh({"data": 3})
    with pytest.raises(ValueError):
        runtime.make_mesh({"data": -1, "model": -1})


def test_pad_batch():
    x = np.ones((3, 4), np.float32)
    padded, n = runtime.pad_batch(x, 8)
    assert padded.shape == (8, 4) and n == 3
    np.testing.assert_array_equal(padded[3:], np.ones((5, 4)))

    d, n = runtime.pad_batch({"a": x, "b": np.zeros((3,))}, 4)
    assert d["a"].shape == (4, 4) and d["b"].shape == (4,) and n == 3

    same, n = runtime.pad_batch(x, 3)
    assert n == 3 and same.shape == (3, 4)

    with pytest.raises(ValueError):
        runtime.pad_batch(x, 2)


def test_prefetch_to_device_preserves_order_and_content():
    batches = [np.full((2, 2), i, np.float32) for i in range(7)]
    out = list(runtime.prefetch_to_device(iter(batches), size=3))
    assert len(out) == 7
    for i, b in enumerate(out):
        assert isinstance(b, jax.Array)
        np.testing.assert_array_equal(np.asarray(b), batches[i])


def test_prefetch_to_device_threaded_transfer_matches_inline():
    """transfer_workers > 0 (the concurrent-put mode) must
    preserve order and content exactly like the inline path, including
    sharded placement and an iterator shorter than the in-flight depth."""
    for n in (1, 7):
        batches = [np.full((2, 2), i, np.float32) for i in range(n)]
        out = list(runtime.prefetch_to_device(iter(batches), size=2,
                                              transfer_workers=3))
        assert len(out) == n
        for i, b in enumerate(out):
            assert isinstance(b, jax.Array)
            np.testing.assert_array_equal(np.asarray(b), batches[i])
    mesh = runtime.make_mesh()
    sharding = runtime.data_sharding(mesh)
    (dev_b,) = runtime.prefetch_to_device(
        [np.arange(16, dtype=np.float32).reshape(8, 2)],
        sharding=sharding, transfer_workers=2)
    assert len(dev_b.sharding.device_set) == 8


def test_prefetch_size_zero_yields_everything():
    """size=0 (prefetching disabled) must still stream every batch —
    not silently drop the input."""
    batches = [np.full((2,), i, np.float32) for i in range(3)]
    out = list(runtime.prefetch_to_device(iter(batches), size=0))
    assert len(out) == 3
    for i, b in enumerate(out):
        np.testing.assert_array_equal(np.asarray(b), batches[i])


def test_prefetch_transfer_workers_env_default(monkeypatch):
    monkeypatch.setenv("SPARKDL_TRANSFER_WORKERS", "2")
    assert runtime.transfer_workers_default() == 2
    batches = [np.full((2,), i, np.float32) for i in range(4)]
    out = list(runtime.prefetch_to_device(iter(batches), size=2))
    for i, b in enumerate(out):
        np.testing.assert_array_equal(np.asarray(b), batches[i])


def test_prefetch_sharded_across_mesh():
    mesh = runtime.make_mesh()
    sharding = runtime.data_sharding(mesh)
    batches = [np.arange(16, dtype=np.float32).reshape(8, 2)]
    (dev_b,) = list(runtime.prefetch_to_device(iter(batches), sharding=sharding))
    assert len(dev_b.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(dev_b), batches[0])


def test_batch_runner_pads_runs_unpads():
    traces = []

    def fn(x):
        traces.append(x.shape)
        return x * 2.0

    runner = runtime.BatchRunner(fn, batch_size=4)
    batches = [np.ones((4, 3), np.float32), np.ones((4, 3), np.float32),
               np.ones((2, 3), np.float32)]  # ragged tail
    outs = list(runner.run(iter(batches)))
    assert [o.shape for o in outs] == [(4, 3), (4, 3), (2, 3)]
    np.testing.assert_allclose(outs[2], 2.0)
    # one trace only: static shape held across full and padded batches
    assert traces == [(4, 3)]


def test_batch_runner_dict_batches():
    def fn(d):
        return {"s": d["a"] + d["b"]}

    runner = runtime.BatchRunner(fn, batch_size=4)
    out = next(iter(runner.run([{"a": np.ones((3, 2), np.float32),
                                 "b": np.ones((3, 2), np.float32)}])))
    assert out["s"].shape == (3, 2)
    np.testing.assert_allclose(out["s"], 2.0)


def test_compile_cache_counts():
    cache = runtime.CompileCache()
    f = cache.get("f", lambda x: x + 1)
    f(jnp.ones((2,)))
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    assert cache.misses == 2 and cache.hits == 1


def test_batch_runner_input_cast_and_pipelining():
    """uint8 host feed + in-graph cast must match a float32 feed, across a
    stream long enough to exercise the in-flight window (round-3 perf fix:
    fetch of batch k overlaps compute of batch k+1)."""
    fn = lambda b: b.sum(axis=(1, 2, 3))
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, 256, size=(4, 5, 5, 3)).astype(np.uint8)
               for _ in range(7)]
    cast_runner = runtime.BatchRunner(fn, batch_size=4, input_cast=jnp.float32)
    plain_runner = runtime.BatchRunner(fn, batch_size=4)
    got = list(cast_runner.run(iter(batches)))
    want = list(plain_runner.run(b.astype(np.float32) for b in batches))
    assert len(got) == 7
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w)


def test_background_iter_order_and_error():
    assert list(runtime.background_iter(iter(range(20)), maxsize=3)) \
        == list(range(20))

    def boom():
        yield 1
        raise RuntimeError("decode failed")

    it = runtime.background_iter(boom(), maxsize=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="decode failed"):
        list(it)


def test_background_iter_cancellation_releases_producer():
    """Abandoning the generator (consumer error path) must unblock the
    producer thread rather than leaving it parked on a full queue forever
    (code-review r3)."""
    import threading
    import time

    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield i

    before = threading.active_count()
    it = runtime.background_iter(gen(), maxsize=1)
    assert next(it) == 0
    it.close()  # abandon mid-stream
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "producer thread leaked"
    assert len(produced) < 100, "producer ran unbounded after close"


def test_parallel_map_iter_order_error_and_inline():
    """The decode pool preserves order under parallelism, re-raises at the
    consumption point, and workers<=0 degrades to inline map."""
    import time as _time

    def slow_sq(i):
        _time.sleep(0.01 * ((i * 7) % 3))  # jittered: tempt reordering
        return i * i

    got = list(runtime.parallel_map_iter(slow_sq, range(20), workers=4))
    assert got == [i * i for i in range(20)]
    assert list(runtime.parallel_map_iter(slow_sq, range(5), workers=0)) \
        == [i * i for i in range(5)]

    def boom(i):
        if i == 3:
            raise RuntimeError("decode failed")
        return i

    it = runtime.parallel_map_iter(boom, range(6), workers=2)
    assert next(it) == 0
    with pytest.raises(RuntimeError, match="decode failed"):
        list(it)


def test_parallel_map_iter_env_default(monkeypatch):
    monkeypatch.setenv("SPARKDL_DECODE_WORKERS", "3")
    assert runtime.decode_workers_default() == 3
    monkeypatch.setenv("SPARKDL_DECODE_WORKERS", "junk")
    assert runtime.decode_workers_default() == 2


def test_run_stream_threads_meta_and_matches_run():
    """run_stream carries host-side metadata through the window untouched
    and unpads exactly like run()."""
    fn = lambda x: x + 1.0
    runner = runtime.BatchRunner(fn, batch_size=4)
    batches = [np.full((3, 2), i, np.float32) for i in range(6)]
    metas = [("part", i) for i in range(6)]
    out = list(runner.run_stream(zip(batches, metas)))
    assert [m for _, m in out] == metas
    for i, (o, _) in enumerate(out):
        assert o.shape == (3, 2)
        np.testing.assert_allclose(o, i + 1.0)
    # meta-less wrapper agrees
    out2 = list(runner.run(iter(batches)))
    for (o, _), o2 in zip(out, out2):
        np.testing.assert_array_equal(o, o2)


def test_run_stream_no_drain_at_partition_boundaries():
    """THE no-drain pin (ISSUE 3 acceptance): with a full prefetch window,
    dispatches run ahead across 'partition' boundaries — before the FIRST
    output is even fetched, chunks of later partitions have already been
    dispatched. The old per-partition run() dispatched exactly one chunk
    per partition before yielding its output."""
    runner = runtime.BatchRunner(lambda x: x * 2.0, batch_size=2,
                                 prefetch=2)
    dispatched = []
    inner = runner._jitted
    runner._jitted = lambda b: (dispatched.append(1), inner(b))[1]
    # 5 single-chunk "partitions"
    stream = runner.run_stream(
        (np.full((2, 2), i, np.float32), i) for i in range(5))
    out0, meta0 = next(stream)
    assert meta0 == 0
    np.testing.assert_allclose(out0, 0.0)
    # window depth prefetch=2 → chunks from partitions 0,1,2 (and with the
    # put lookahead possibly 3) dispatched before partition 0's output was
    # yielded: the window crossed ≥2 partition boundaries without draining.
    assert len(dispatched) >= 3, dispatched
    rest = list(stream)
    assert [m for _, m in rest] == [1, 2, 3, 4]
    assert len(dispatched) == 5


def test_compile_cache_emits_recompile_events():
    from sparkdl_tpu.runner import events
    rec = events.reset()
    try:
        cache = runtime.CompileCache()
        f = cache.get("probe_fn", lambda x: x * 2)
        f(jnp.ones((2,)))
        f(jnp.ones((2,)))
        f(jnp.ones((3,)))
        names = [e["name"] for e in rec.tail()]
        assert names.count("recompile") == 2
        ev = [e for e in rec.tail() if e["name"] == "recompile"][-1]
        assert ev["fn"] == "probe_fn" and ev["misses"] == 2
        assert cache.snapshot() == {"hits": 1, "misses": 2}
    finally:
        events.reset()


_CACHE_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
before = jax.config.jax_compilation_cache_dir
import sparkdl_tpu
from sparkdl_tpu.core import runtime
jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(37.0))
cfg = jax.config
print(json.dumps({"before": before,
                  "after": cfg.jax_compilation_cache_dir,
                  "min_s": cfg.jax_persistent_cache_min_compile_time_secs,
                  "stats": runtime.persistent_cache_stats()}))
"""


def _cache_probe(env_dir: str | None) -> dict:
    """One fresh interpreter: import sparkdl_tpu, compile one program,
    report what jax's compile-cache config and the hit/miss tally say."""
    import json
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=_REPO)
    assert proc.returncode == 0, proc.stderr[-800:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_placed_by_env_and_second_process_hits(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it (already in place before
    sparkdl_tpu is imported), the program assigns no directory of its own,
    the cache is written THERE — and a second process compiling the same
    program loads it from disk (a hit, not a second miss)."""
    cache_dir = str(tmp_path / "xla_cache")
    first = _cache_probe(cache_dir)
    assert first["before"] == first["after"] == cache_dir
    assert first["stats"]["dir"] == cache_dir
    assert first["min_s"] == 0  # small programs cache too
    assert first["stats"]["misses"] > 0
    assert os.listdir(cache_dir)
    second = _cache_probe(cache_dir)
    assert second["stats"]["hits"] > 0


def test_compile_cache_defaults_to_checkout_dir():
    """Unset: <checkout>/.jax_cache, armed by ``import sparkdl_tpu`` — a
    fixed path, never a temporary name, a pid or the time."""
    rec = _cache_probe(None)
    assert rec["before"] is None
    assert rec["after"] == os.path.join(_REPO, ".jax_cache")
    assert rec["after"] == runtime.DEFAULT_COMPILE_CACHE_DIR


def test_compile_cache_events_reach_the_flight_recorder(monkeypatch):
    """Hits and misses land in persistent_cache_stats() and as
    ``compile_cache`` events (what supervise() postmortems read)."""
    from sparkdl_tpu.runner import events
    rec = events.reset()
    try:
        before = runtime.persistent_cache_stats()
        runtime._on_compile_cache_event("/jax/compilation_cache/cache_misses")
        runtime._on_compile_cache_event("/jax/compilation_cache/cache_hits")
        runtime._on_compile_cache_event("/jax/some/other/event")
        after = runtime.persistent_cache_stats()
        assert after["misses"] == before["misses"] + 1
        assert after["hits"] == before["hits"] + 1
        outcomes = [e.get("outcome") for e in rec.tail()
                    if e["name"] == "compile_cache"]
        assert outcomes == ["miss", "hit"]
    finally:
        events.reset()


def test_make_mesh_topology_aware_dispatch(monkeypatch):
    """On multi-chip TPU device sets make_mesh must route through
    mesh_utils.create_device_mesh (ICI-torus-aware placement — BASELINE
    "chip-topology aware"); CPU/virtual devices use the plain reshape, and
    a mesh_utils failure degrades to reshape with a warning, not an error."""
    calls = []

    class FakeTpu:
        platform = "tpu"

        def __init__(self, i):
            self.id = i

        def __repr__(self):
            return f"FakeTpu({self.id})"

    fakes = [FakeTpu(i) for i in range(8)]

    from jax.experimental import mesh_utils as mu

    def fake_create(shape, devices=None):
        calls.append(tuple(shape))
        return np.array(devices).reshape(shape)

    monkeypatch.setattr(mu, "create_device_mesh", fake_create)
    grid = runtime._device_grid(fakes, [4, 2])
    assert calls == [(4, 2)] and grid.shape == (4, 2)

    # CPU devices: no mesh_utils call
    mesh = runtime.make_mesh({"data": 4, "model": 2},
                             devices_=jax.devices()[:8])
    assert mesh.shape == {"data": 4, "model": 2}
    assert calls == [(4, 2)]  # unchanged — cpu path didn't call it

    # mesh_utils blowing up degrades to reshape
    def boom(shape, devices=None):
        raise ValueError("no topology")

    monkeypatch.setattr(mu, "create_device_mesh", boom)
    grid = runtime._device_grid(fakes, [8])
    assert [d.id for d in grid] == list(range(8))


# ---------------------------------------------------------------------------
# run_stream fault tolerance (ISSUE 4): bounded retry, give-up, stall
# ---------------------------------------------------------------------------

@pytest.fixture
def fast_backoff(monkeypatch):
    monkeypatch.setenv("SPARKDL_DISPATCH_BACKOFF_S", "0.01")
    from sparkdl_tpu.runner import chaos, events, metrics
    metrics.run_stats.reset()
    rec = events.reset()
    yield rec
    chaos.uninstall()
    events.reset()
    metrics.run_stats.reset()


@pytest.mark.chaos
def test_dispatch_transient_fault_retried_once(fast_backoff):
    """ISSUE 4 acceptance: an injected once-only dispatch preemption is
    retried and the job succeeds, with a `retry` event recorded."""
    from sparkdl_tpu.runner import metrics
    from sparkdl_tpu.runner.chaos import Fault, FaultPlan, install
    install(FaultPlan([Fault("dispatch", "preempt", prob=1.0, once=True)]))
    r = runtime.BatchRunner(lambda b: b * 2.0, 4)
    out = list(r.run(iter([np.ones((4, 2), np.float32),
                           np.full((3, 2), 3.0, np.float32)])))
    assert len(out) == 2
    np.testing.assert_allclose(out[0], 2.0)
    np.testing.assert_allclose(out[1], 6.0)
    assert out[1].shape == (3, 2)  # pad rows still sliced on the retry path
    names = [e["name"] for e in fast_backoff.tail()]
    assert "retry" in names and "give_up" not in names
    assert metrics.run_stats.dispatch_retries == 1


@pytest.mark.chaos
def test_dispatch_persistent_fault_exhausts_backoff(fast_backoff):
    """A persistent retryable fault exhausts the budget and fails with a
    classified error naming the stage (+ give_up event)."""
    from sparkdl_tpu.runner import metrics
    from sparkdl_tpu.runner.chaos import Fault, FaultPlan, install
    from sparkdl_tpu.runner.failures import (ScoringStageError,
                                             classify_exception)
    install(FaultPlan([Fault("dispatch", "preempt", prob=1.0, once=False)]))
    r = runtime.BatchRunner(lambda b: b * 2.0, 4)
    with pytest.raises(ScoringStageError, match="stage 'dispatch'") as ei:
        list(r.run(iter([np.ones((4, 2), np.float32)])))
    assert ei.value.attempts == 1 + runtime.dispatch_retries_default()
    assert classify_exception(ei.value) == "retryable"
    evs = fast_backoff.tail()
    assert [e["name"] for e in evs].count("retry") == \
        runtime.dispatch_retries_default()
    assert any(e["name"] == "give_up" and e["stage"] == "dispatch"
               for e in evs)
    assert metrics.run_stats.dispatch_giveups == 1


@pytest.mark.chaos
def test_dispatch_fatal_fault_not_retried(fast_backoff):
    from sparkdl_tpu.runner import metrics
    from sparkdl_tpu.runner.chaos import Fault, FaultPlan, install
    from sparkdl_tpu.runner.failures import (ScoringStageError,
                                             classify_exception)
    install(FaultPlan([Fault("dispatch", "fatal", prob=1.0, once=False)]))
    r = runtime.BatchRunner(lambda b: b * 2.0, 4)
    with pytest.raises(ScoringStageError) as ei:
        list(r.run(iter([np.ones((4, 2), np.float32)])))
    assert ei.value.attempts == 1  # fatal: no retry burned
    assert classify_exception(ei.value) == "fatal"
    assert metrics.run_stats.dispatch_retries == 0


def test_retries_disabled_restores_lean_path(fast_backoff, monkeypatch):
    """SPARKDL_DISPATCH_RETRIES=0: no host copy pinned, first error
    raises as the classified stage error with attempts=1."""
    monkeypatch.setenv("SPARKDL_DISPATCH_RETRIES", "0")
    from sparkdl_tpu.runner.chaos import Fault, FaultPlan, install
    from sparkdl_tpu.runner.failures import ScoringStageError
    install(FaultPlan([Fault("dispatch", "preempt", prob=1.0, once=True)]))
    r = runtime.BatchRunner(lambda b: b * 2.0, 4)
    with pytest.raises(ScoringStageError, match="1 attempt"):
        list(r.run(iter([np.ones((4, 2), np.float32)])))


def test_stall_watchdog_names_the_stage(fast_backoff, monkeypatch):
    """No progress for SPARKDL_DISPATCH_TIMEOUT_S -> a classified
    ScoringStallError naming the stage, not a silent hang. (On the
    synchronous CPU backend the hang blocks dispatch; on TPU it would
    surface at fetch — the watchdog covers both.)"""
    import time as time_mod
    from sparkdl_tpu.runner.failures import (ScoringStallError,
                                             classify_exception)
    r = runtime.BatchRunner(lambda b: b * 2.0, 4)
    # warm the compile OUTSIDE the watchdog window: the timeout must
    # bound steady-state progress, not the first-call XLA compile
    list(r.run(iter([np.ones((4, 2), np.float32)])))
    monkeypatch.setenv("SPARKDL_DISPATCH_TIMEOUT_S", "0.4")

    def wedge(b):
        def cb(x):
            time_mod.sleep(2.0)
            return np.asarray(x)
        return jax.pure_callback(cb, jax.ShapeDtypeStruct(b.shape, b.dtype),
                                 b)

    r2 = runtime.BatchRunner(wedge, 4)
    t0 = time_mod.perf_counter()
    with pytest.raises(ScoringStallError, match="no progress") as ei:
        list(r2.run(iter([np.ones((4, 2), np.float32)])))
    assert ei.value.stage in ("dispatch", "fetch")
    assert classify_exception(ei.value) == "retryable"
    assert time_mod.perf_counter() - t0 < 1.9  # did NOT wait out the hang
    assert any(e["name"] == "give_up" and e.get("stalled")
               for e in fast_backoff.tail())
