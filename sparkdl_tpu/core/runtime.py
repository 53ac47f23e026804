"""Device runtime: mesh construction, compile cache, and the HBM feed pipeline.

This layer plays the role the TF C++ runtime + TensorFrames JNI bridge played
for the reference (SURVEY.md §2.3): getting partition batches from the columnar
data plane into accelerator memory and running compiled programs over them.
TPU-first design:

- **Static shapes**: every batch entering a jitted function is padded to the
  configured batch size, so XLA compiles exactly one program per (fn, shape)
  — recompilation is the TPU equivalent of a cache miss storm.
- **Double buffering**: ``prefetch_to_device`` keeps N batches in flight —
  ``jax.device_put`` of batch k+1 overlaps with compute on batch k, hiding
  host→HBM transfer latency behind MXU work. This is the "mapPartitions
  batching feeding HBM directly" of the BASELINE north star.
- **One mesh abstraction**: `make_mesh` builds a ``jax.sharding.Mesh`` over
  the real device topology (or the virtual CPU devices in tests); all
  parallelism (DP/TP/...) is expressed as shardings over its named axes and
  compiled to ICI collectives by XLA — never hand-rolled NCCL-style calls.
"""

from __future__ import annotations

import collections
import itertools
import logging
import math
import os
import queue as queue_mod
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import ingest

log = logging.getLogger("sparkdl_tpu.runtime")


def _events():
    """Flight recorder, lazily (the runner package imports heavyweight
    siblings; resolving it per call is a sys.modules hit after the first)."""
    from sparkdl_tpu.runner import events
    return events


def _chaos():
    from sparkdl_tpu.runner import chaos
    return chaos


def _failures():
    from sparkdl_tpu.runner import failures
    return failures


def _run_stats():
    from sparkdl_tpu.runner import metrics
    return metrics.run_stats


def _telemetry():
    """Live telemetry plane (ISSUE 6), lazily — stdlib-only module, same
    sys.modules-hit-after-first pattern as _events()."""
    from sparkdl_tpu.runner import telemetry
    return telemetry


def devices() -> list:
    return jax.devices()


def device_count() -> int:
    return len(jax.devices())


def default_device():
    return jax.devices()[0]


def platform() -> str:
    return jax.devices()[0].platform


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------

def make_mesh(axes: dict[str, int] | None = None,
              devices_: Sequence | None = None) -> Mesh:
    """Build a named-axis device mesh, topology-aware on real hardware.

    ``axes`` maps axis name → size, e.g. ``{"data": 8}`` or
    ``{"data": 4, "model": 2}``. A size of ``-1`` means "whatever is left".
    Default: one ``data`` axis over all devices (pure DP — the reference's
    only training parallelism, SURVEY.md §2.4).

    On a multi-chip TPU slice the device order is assigned by
    ``jax.experimental.mesh_utils.create_device_mesh``, which lays mesh
    axes along the ICI torus so the *innermost (last) axis rides
    nearest-neighbor links* — put the most bandwidth-hungry axis last
    (e.g. ``{"data": D, "model": T}`` for Megatron-style TP, or a pure
    ``{"data": N}`` DP mesh whose allreduce then stays on-torus). This is
    the "Spark executor placement becomes chip-topology aware" piece of
    the BASELINE north star. Virtual/CPU device sets (tests, the driver
    dryrun) fall back to a plain reshape.
    """
    devs = list(devices_ if devices_ is not None else jax.devices())
    if axes is None:
        axes = {"data": len(devs)}
    names, sizes = list(axes.keys()), list(axes.values())
    if sizes.count(-1) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if -1 in sizes:
        known = math.prod(s for s in sizes if s != -1)
        if len(devs) % known:
            raise ValueError(f"{len(devs)} devices not divisible by {known}")
        sizes[sizes.index(-1)] = len(devs) // known
    total = math.prod(sizes)
    if total != len(devs):
        raise ValueError(
            f"Mesh axes {dict(zip(names, sizes))} need {total} devices, "
            f"have {len(devs)}")
    arr = _device_grid(devs, sizes)
    return Mesh(arr, axis_names=tuple(names))


def _device_grid(devs: list, sizes: list[int]) -> np.ndarray:
    """Arrange ``devs`` into a ``sizes``-shaped grid.

    Real multi-chip TPU → ``mesh_utils.create_device_mesh`` (ICI-torus-
    aware axis assignment). Single device, CPU, or anything mesh_utils
    can't place (virtual topologies) → row-major reshape, which is exactly
    what the torus-aware path degenerates to there anyway."""
    if len(devs) > 1 and devs[0].platform == "tpu":
        try:
            from jax.experimental import mesh_utils
            return mesh_utils.create_device_mesh(sizes, devices=devs)
        except (ValueError, AssertionError, NotImplementedError) as e:
            log.warning(
                "mesh_utils.create_device_mesh failed (%s); falling back "
                "to row-major device order — collectives may cross "
                "non-adjacent ICI links", e)
    return np.array(devs).reshape(sizes)


def data_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Batch-dim sharding: leading dim split over the data axis."""
    return NamedSharding(mesh, P(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Batch padding (static shapes for XLA)
# ---------------------------------------------------------------------------

def pad_batch(arrays: dict[str, np.ndarray] | np.ndarray, batch_size: int):
    """Pad leading dim up to ``batch_size``; returns (padded, n_valid).

    Padding replicates row 0 (not zeros) so that models with
    normalization/pooling never see degenerate inputs; validity is tracked by
    count and the pad rows are sliced off after the computation.
    """
    single = not isinstance(arrays, dict)
    d = {"x": arrays} if single else arrays
    n = next(iter(d.values())).shape[0]
    if n > batch_size:
        raise ValueError(f"Batch of {n} rows exceeds batch size {batch_size}")
    if n < batch_size:
        out = {}
        for k, v in d.items():
            pad = np.broadcast_to(v[:1], (batch_size - n,) + v.shape[1:])
            out[k] = np.concatenate([v, pad], axis=0)
        d = out
    return (d["x"] if single else d), n


# ---------------------------------------------------------------------------
# HBM prefetch pipeline
# ---------------------------------------------------------------------------

def transfer_workers_default() -> int:
    """How many threads issue ``jax.device_put`` concurrently in the feed
    pipeline (``SPARKDL_TRANSFER_WORKERS``; 0 = inline single-threaded).

    ``device_put`` is an async DMA handoff, so extra threads only add
    overhead unless a backend holds the calling thread for the wire time
    — hence default 0."""
    return int(os.environ.get("SPARKDL_TRANSFER_WORKERS", "0"))


# THE submit-ahead window — one copy, in the jax-free ingest module;
# every feed path here rides it.
_windowed_apply = ingest.windowed_apply


def _put_fn(sharding: NamedSharding | None) -> Callable:
    """The one device_put closure shared by the feed paths."""
    def put(batch):
        if sharding is not None:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), batch)
        return jax.tree_util.tree_map(jax.device_put, batch)
    return put


def prefetch_to_device(iterator: Iterable, size: int = 2,
                       sharding: NamedSharding | None = None,
                       transfer_workers: int | None = None) -> Iterator:
    """Double-buffered ``jax.device_put`` — the HBM feed pipeline.

    Eagerly transfers up to ``size`` pytrees ahead of the consumer, so
    host→device DMA of the next batch overlaps with device compute on the
    current one. With a ``sharding``, each leaf is placed sharded across the
    mesh (multi-chip feeding over ICI); otherwise onto the default device.

    ``transfer_workers`` > 0 issues the puts from a thread pool (consumed
    strictly in order): when a put blocks its calling thread for the wire
    time, N workers keep N transfers in flight. Default
    from ``SPARKDL_TRANSFER_WORKERS`` (0 = inline). NOTE: with workers >
    size the in-flight depth rises to ``workers`` (idle threads would
    defeat the knob's purpose) — budget host/HBM headroom for
    ``max(size, workers)`` batches when enabling it.
    """
    workers = (transfer_workers_default() if transfer_workers is None
               else transfer_workers)
    yield from _windowed_apply(_put_fn(sharding), iterator, size, workers,
                               "sparkdl-put")


def background_iter(iterator: Iterable, maxsize: int = 2) -> Iterator:
    """Drive ``iterator`` in a daemon thread through a bounded queue.

    Wraps host-side producers (image decode/pack) so their work overlaps
    device compute instead of serializing with it: the worker thread stays
    ``maxsize`` items ahead of the consumer. Exceptions re-raise at the
    consumption point. Closing/abandoning the generator (including an error
    raised by the consumer mid-stream) cancels the producer thread — it
    stops at the next queue hand-off rather than parking forever on a full
    queue with its buffered batches pinned.
    """
    # Queue(0) would mean *unbounded* — clamp to preserve backpressure.
    q: queue_mod.Queue = queue_mod.Queue(maxsize=max(1, maxsize))
    sentinel = object()
    cancelled = threading.Event()
    failure: list[BaseException] = []

    def put_bounded(item) -> bool:
        """Put with cancellation polling — a cancelled consumer can't
        strand the producer on a full queue. True iff delivered."""
        while not cancelled.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def work():
        try:
            for item in iterator:
                if not put_bounded(item):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            failure.append(e)
        finally:
            # The sentinel must actually arrive while the consumer lives —
            # dropping it on a transiently-full queue would strand the
            # consumer in q.get().
            put_bounded(sentinel)

    threading.Thread(target=work, daemon=True,
                     name="sparkdl-feed").start()
    try:
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        if failure:
            raise failure[0]
    finally:
        cancelled.set()


def dispatch_retries_default() -> int:
    """Bounded retry budget for transient dispatch/fetch errors in
    ``BatchRunner.run_stream`` (``SPARKDL_DISPATCH_RETRIES``, default 2;
    0 disables retries AND releases the per-slot host batch copy the
    re-dispatch path needs — the leanest-memory mode)."""
    try:
        return max(0, int(os.environ.get("SPARKDL_DISPATCH_RETRIES", "2")))
    except ValueError:
        return 2


def dispatch_backoff_default() -> float:
    """Base backoff (seconds) between dispatch/fetch retries; doubles per
    attempt (``SPARKDL_DISPATCH_BACKOFF_S``, default 0.2)."""
    try:
        return max(0.0, float(
            os.environ.get("SPARKDL_DISPATCH_BACKOFF_S", "0.2")))
    except ValueError:
        return 0.2


def dispatch_timeout_default() -> float:
    """Stall watchdog on the in-flight window: a blocking fetch that makes
    no progress for this many seconds raises a classified
    ``ScoringStallError`` naming the stage instead of hanging the job
    forever (``SPARKDL_DISPATCH_TIMEOUT_S``; default 0 = disabled — the
    watchdog costs one helper thread per fetch while armed)."""
    try:
        return float(os.environ.get("SPARKDL_DISPATCH_TIMEOUT_S", "0"))
    except ValueError:
        return 0.0


def _call_with_timeout(fn: Callable, timeout_s: float, stage: str):
    """Run ``fn`` on a helper thread, bounded by ``timeout_s``. On timeout
    the (possibly wedged) call is abandoned on its daemon thread and a
    classified :class:`ScoringStallError` names the stage — turning a
    silent device/interconnect hang into a supervisable failure."""
    result: dict = {}
    done = threading.Event()

    def work():
        try:
            result["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            result["error"] = e
        finally:
            done.set()

    threading.Thread(target=work, daemon=True,
                     name="sparkdl-fetch-watchdog").start()
    if not done.wait(timeout_s):
        raise _failures().ScoringStallError(stage, timeout_s)
    if "error" in result:
        raise result["error"]
    return result["value"]


def decode_workers_default() -> int:
    """Host decode parallelism for the inference feed
    (``SPARKDL_DECODE_WORKERS``; default 2). The Arrow→NHWC pack and PIL
    resize release the GIL, so N workers keep N cores decoding — one
    background thread (the pre-streaming design) caps the feed at a single
    core's decode rate however fast the device drains it. 0 = decode
    inline on the consumer thread (no overlap; debugging)."""
    try:
        return int(os.environ.get("SPARKDL_DECODE_WORKERS", "2"))
    except ValueError:
        return 2


def parallel_map_iter(fn: Callable, items: Iterable, workers: int | None = None,
                      maxsize: int | None = None,
                      backend: str | None = None) -> Iterator:
    """Order-preserving parallel map over an iterator — the host decode pool.

    Up to ``max(workers, maxsize)`` applications of ``fn`` stay in flight on
    a worker pool; results yield strictly in submission order, so a
    slow-to-decode chunk never reorders the stream. Like
    :func:`prefetch_to_device`, submission is pull-driven: each yield tops
    the window back up, so the pool runs ahead of the consumer by the
    window depth and no producer thread needs cancelling. Exceptions from
    ``fn`` re-raise at the consumption point; closing the generator cancels
    whatever has not started.

    ``workers=None`` → :func:`decode_workers_default`; ``workers<=0`` maps
    inline (serial). ``backend`` (default: ``SPARKDL_DECODE_BACKEND``):
    ``thread``, or ``process`` to run ``fn`` on the shared
    ``ProcessPoolExecutor`` (``ingest.get_decode_executor``) — GIL-bound
    decode then scales past ~2 workers, but ``fn`` and every item must be
    picklable (the streaming scorer ships module-level factories +
    compacted Arrow chunks; see ``ingest.run_decode_task``). Callers
    whose ``fn`` closes over un-picklable state pass ``backend="thread"``
    explicitly rather than inheriting the env.
    """
    workers = decode_workers_default() if workers is None else int(workers)
    if backend is None:
        backend = ingest.decode_backend_default()
    if backend == "process" and workers > 0:
        pool = ingest.acquire_decode_executor(workers)
        try:
            # stall_s: a pool child deadlocked at fork (the documented
            # fork-a-threaded-parent hazard) must surface as a classified
            # decode stall, not an eternal hang — armed BY DEFAULT
            # (ingest.decode_stall_resolved), unlike the opt-in
            # dispatch/fetch watchdog, because the hang needs no device
            # wedge to happen; a SET SPARKDL_DISPATCH_TIMEOUT_S (incl.
            # an explicit 0 = off) takes precedence.
            yield from _windowed_apply(
                fn, items, max(workers, maxsize or 0), workers, "",
                executor=pool,
                stall_s=ingest.decode_stall_resolved(),
                stall_stage="decode")
        except _failures().ScoringStallError:
            # The stalled future's child is wedged but ALIVE — it never
            # sets _broken, so the cached pool would re-stall every
            # later stream on a permanently lost worker slot. Evict it;
            # the next request builds fresh workers.
            ingest.invalidate_decode_executor(pool)
            raise
        finally:
            ingest.release_decode_executor()
        return
    # depth 0 when inline: decode is synchronous CPU work — running it
    # ahead on the consumer thread would serialize identically, unlike
    # the async device_put feed.
    yield from _windowed_apply(
        fn, items, 0 if workers <= 0 else max(workers, maxsize or 0),
        workers, "sparkdl-decode")


_runner_ids = itertools.count()


class BatchRunner:
    """Drives one jitted function over a stream of host batches.

    The execution engine behind every inference transformer: pads to a static
    batch, prefetches into HBM, runs the compiled program, and slices off pad
    rows. One XLA compilation per (fn, batch_size); the first call pays the
    compile, subsequent calls are cached.

    Execution is *pipelined*: up to ``prefetch`` executions stay in flight
    with their device→host copies started asynchronously, so the fetch of
    batch k overlaps compute on batch k+1 — serializing put→run→fetch per
    batch would pay every host round-trip in line; the in-flight window
    hides all but the last.

    :meth:`run_stream` is the streaming-engine entry point: it drives the
    SAME window over one continuous batch stream with arbitrary host-side
    metadata riding alongside each batch — callers feed the whole dataset
    (all partitions) through one call, so the in-flight window never
    drains at a partition boundary. :meth:`run` is the meta-less wrapper.
    Every stage emits flight-recorder spans (``pad``/``put``/``dispatch``/
    ``fetch``) so postmortems and bench can see where scoring time goes.
    """

    def __init__(self, fn: Callable, batch_size: int,
                 donate: bool | None = None,
                 prefetch: int = 2, mesh: Mesh | None = None,
                 data_axis: str = "data", input_cast=None,
                 preprocess: Callable | None = None):
        """``mesh``: when given, input batches are device_put *sharded* over
        ``data_axis`` and the jitted program runs SPMD across all mesh
        devices (the reference's partition-parallel inference, SURVEY.md
        §2.4 row 2, with Spark executors → mesh devices). batch_size is
        rounded up to a multiple of the axis size so shards stay equal.

        ``input_cast``: a dtype (e.g. ``jnp.float32``): every input leaf is
        cast to it *inside* the jitted program. Feed uint8 host batches and
        the cast fuses into the first consumer op — 4x fewer bytes over the
        host→HBM link than pre-cast float32 feeds.

        ``preprocess``: a jittable fn applied INSIDE the compiled program
        between the input cast and ``fn`` — the fused preprocess prologue
        (ISSUE 7): channel flips / ``jax.image.resize`` / normalization
        compile into the same XLA program as the model, so the host ships
        raw storage-dtype batches and does zero per-pixel math. Input
        shapes are static at trace time, so a prologue may branch on
        ``x.shape`` (e.g. resize only when the wire size differs from the
        model size); each distinct wire shape is one compilation, visible
        as a ``recompile`` event.

        ``donate``: donate the input buffer to the program — XLA may alias
        it for outputs/scratch, shaving one HBM buffer per in-flight batch.
        Default from ``SPARKDL_INFER_DONATE`` (off: on backends that cannot
        alias a given shape jax warns per dispatch, and inference inputs
        rarely match output shapes)."""
        if donate is None:
            donate = os.environ.get("SPARKDL_INFER_DONATE", "") \
                in ("1", "true", "yes")
        # Per-runner identity for recompile accounting: each runner owns
        # its own jit cache, so the same shapes through a NEW runner are a
        # real recompile, not a hit.
        self._sig_name = (f"BatchRunner:{getattr(fn, '__name__', 'fn')}"
                          f":{next(_runner_ids)}")
        self.batch_size = int(batch_size)
        if mesh is not None:
            n_shard = int(mesh.shape[data_axis])
            self.batch_size = -(-self.batch_size // n_shard) * n_shard
            self._sharding = data_sharding(mesh, data_axis)
        else:
            self._sharding = None
        self.prefetch = prefetch
        if preprocess is not None:
            inner_fn = fn

            def fn(batch):  # noqa: F811 — deliberate wrap
                return inner_fn(preprocess(batch))
        if input_cast is not None:
            inner = fn

            def fn(batch):  # noqa: F811 — deliberate wrap
                return inner(jax.tree_util.tree_map(
                    lambda x: x.astype(input_cast), batch))
        self._jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())

    def run(self, batches: Iterable[np.ndarray | dict]) -> Iterator[np.ndarray]:
        """batches: iterator of host arrays/dicts with leading batch dim ≤
        batch_size. Yields numpy outputs with pad rows removed."""
        for out, _ in self.run_stream((b, None) for b in batches):
            yield out

    def run_stream(self, batches: Iterable[tuple]) -> Iterator[tuple]:
        """Persistent pipeline over one continuous batch stream.

        ``batches``: iterator of ``(host_batch, meta)`` — ``meta`` is any
        host-side value (the streaming transformers carry partition
        identity/row counts here) and rides the pipeline untouched. Yields
        ``(numpy_output_with_pad_rows_removed, meta)`` in input order.

        The in-flight window (``prefetch`` dispatched executions with
        async device→host copies, plus the same depth of pending
        ``device_put``) spans the WHOLE stream: feeding every partition of
        a dataset through one call keeps the device busy across partition
        boundaries instead of draining per partition. ``n_valid`` threads
        through the window next to each batch.

        Fault tolerance (ISSUE 4): transient *retryable* dispatch/fetch
        errors (``failures.classify_exception`` — UNAVAILABLE, preemption,
        connection flakes) are retried up to ``SPARKDL_DISPATCH_RETRIES``
        times with exponential backoff (``SPARKDL_DISPATCH_BACKOFF_S``),
        each retry re-putting the batch from its host copy and emitting a
        ``retry`` flight-recorder event; exhaustion (or a fatal error)
        emits ``give_up`` and raises :class:`ScoringStageError` naming the
        stage. The retry path pins one padded HOST copy per window slot —
        ``SPARKDL_DISPATCH_RETRIES=0`` disables retries and restores the
        no-host-copy lean mode. ``SPARKDL_DISPATCH_TIMEOUT_S`` > 0 arms a
        stall watchdog on the blocking fetch: no progress for that long
        raises a classified ``ScoringStallError`` instead of hanging.
        """
        ev = _events()
        chaos = _chaos()
        tel = _telemetry()
        # Env-armed live telemetry (ISSUE 6): with SPARKDL_METRICS_DIR /
        # SPARKDL_METRICS_PORT unset this is two dict lookups and the
        # plane stays off — the accountant tees off the spans below only
        # when armed. Gauges are fetched once per stream, set per batch.
        tel.maybe_start_from_env()
        depth_gauge = occupancy_gauge = None
        if tel.enabled():
            depth_gauge = tel.registry().gauge("run_stream_window_depth")
            occupancy_gauge = tel.registry().gauge(
                "run_stream_slot_occupancy")
        retries = dispatch_retries_default()
        backoff_s = dispatch_backoff_default()
        stall_s = dispatch_timeout_default()
        batch_ids = itertools.count()
        # Reused host staging (ISSUE 7): short batches pad into POOLED
        # per-shape buffers (acquired here, released once the batch's
        # fetch completed — a buffer is never recycled while a possibly
        # zero-copy-aliasing device_put might still read it) instead of
        # a fresh np.concatenate per batch; full batches pass through
        # untouched, so a zero-copy Arrow view flows straight into
        # device_put. SPARKDL_STAGE_BUFFERS=0 restores the old path.
        staging = ingest.StagingPool() if ingest.stage_buffers_default() \
            else None

        def staged():
            for b, meta in batches:
                with ev.span("pad") as sp:
                    if staging is not None:
                        padded, n, lease, copied = ingest.stage_batch(
                            b, self.batch_size, staging)
                        # bytes here = host bytes COPIED to stage this
                        # batch (0 = zero-copy pass-through): the proof
                        # ledger that staging stopped re-copying the
                        # stream, next to put's bytes-over-the-wire.
                        sp.set(rows=n, bytes=copied)
                    else:
                        padded, n = pad_batch(b, self.batch_size)
                        lease = None
                        sp.set(rows=n)
                yield padded, n, meta, next(batch_ids), lease

        put = _put_fn(self._sharding)

        def put_slot(slot):
            # n/meta ride each window slot (never tee'd) through the
            # shared submit-ahead window — same contract as
            # prefetch_to_device, with SPARKDL_TRANSFER_WORKERS pooling.
            # The padded host batch is kept only while retries are
            # enabled: it is what the re-dispatch path re-puts.
            padded, n, meta, idx, lease = slot
            # rows/bytes on the put span: host→HBM traffic is the
            # telemetry plane's bytes-moved ledger (the PCIe/wire story
            # ROADMAP item 2 is chasing); nbytes is attr reads, not math.
            nbytes = sum(getattr(leaf, "nbytes", 0)
                         for leaf in jax.tree_util.tree_leaves(padded))
            with ev.span("put", rows=n, bytes=nbytes):
                return put(padded), (padded if retries else None), n, \
                    meta, idx, lease

        def put_stream():
            return _windowed_apply(put_slot, staged(), self.prefetch,
                                   transfer_workers_default(),
                                   "sparkdl-put")

        def dispatch_once(dev_batch, n, idx):
            # Signature accounting BEFORE the dispatch: a pad bug or
            # mixed-shape stream shows up as `recompile` events (and in
            # meter.summary()["compile_cache"]) instead of a silent
            # 20-40s stall per odd-shaped chunk.
            GLOBAL_COMPILE_CACHE.note(self._sig_name, (
                jax.tree_util.tree_structure(dev_batch),
                tuple((leaf.shape, str(leaf.dtype))
                      for leaf in jax.tree_util.tree_leaves(dev_batch))))
            with ev.span("dispatch", rows=n):
                chaos.fire("dispatch", step=idx)
                if stall_s > 0:
                    # On synchronous backends (CPU; some pathological
                    # compiles) a hang blocks the dispatch call itself and
                    # never reaches the fetch — the armed watchdog covers
                    # both ends of the window.
                    out = _call_with_timeout(
                        lambda: self._jitted(dev_batch), stall_s,
                        "dispatch")
                else:
                    out = self._jitted(dev_batch)
                # Start the device→host copy now; block only when popped.
                for leaf in jax.tree_util.tree_leaves(out):
                    if hasattr(leaf, "copy_to_host_async"):
                        leaf.copy_to_host_async()
            return out

        def retry_or_raise(stage, exc, host, n, idx, state):
            """One retry decision + (on retry) the serial re-put +
            re-dispatch. Returns a fresh ``out``; raises the classified
            stage error when the budget is spent or the error is fatal."""
            failures = _failures()
            while True:
                kind = failures.classify_exception(exc)
                if host is None or kind != "retryable" \
                        or state["attempts"] > retries:
                    ev.event("give_up", stage=stage,
                             attempts=state["attempts"], kind=kind,
                             error=f"{type(exc).__name__}: {exc}"[:300],
                             batch=idx)
                    if kind == "retryable" and host is not None:
                        _run_stats().record_retry(giveup=True)
                    raise failures.ScoringStageError(
                        stage, state["attempts"], exc) from exc
                delay = backoff_s * (2 ** (state["attempts"] - 1))
                ev.event("retry", stage=stage, attempt=state["attempts"],
                         delay_s=round(delay, 3),
                         error=f"{type(exc).__name__}: {exc}"[:300],
                         batch=idx)
                _run_stats().record_retry()
                state["attempts"] += 1
                if delay:
                    time.sleep(delay)
                try:
                    # Rare path, so serial: fresh device buffers from the
                    # host copy (the originals may be donated/poisoned),
                    # then re-dispatch.
                    with ev.span("put"):
                        dev = put(host)
                    return dispatch_once(dev, n, idx)
                except failures.ScoringStallError:
                    # The retry itself wedged: same no-re-dispatch rule
                    # as the top-level stalls — surface it NOW instead of
                    # burning the remaining budget stall_s at a time.
                    ev.event("give_up", stage=stage, stalled=True,
                             timeout_s=stall_s, batch=idx)
                    raise
                except Exception as e:  # noqa: BLE001 — reclassified above
                    exc = e

        def fetch(slot):
            out, host, n, meta, idx, state, lease = slot
            failures = _failures()
            while True:
                try:
                    with ev.span("fetch", rows=n):
                        if stall_s > 0:
                            out_np = _call_with_timeout(
                                lambda: jax.tree_util.tree_map(
                                    np.asarray, out), stall_s, "fetch")
                        else:
                            out_np = jax.tree_util.tree_map(np.asarray, out)
                    if lease is not None:
                        # Fetch completed ⇒ this batch's transfer AND
                        # compute are done — only now may its staging
                        # buffer be recycled for a later batch.
                        staging.release(lease)
                    return (jax.tree_util.tree_map(lambda x: x[:n], out_np),
                            meta)
                except failures.ScoringStallError:
                    # A wedged fetch is not fixed by re-dispatching onto
                    # the same wedged device — surface it for the
                    # process-level supervisor (classified retryable).
                    ev.event("give_up", stage="fetch", stalled=True,
                             timeout_s=stall_s, batch=idx)
                    raise
                except Exception as e:  # noqa: BLE001 — reclassified
                    # Async device errors materialize here; a retry must
                    # redo put+dispatch for this batch, then re-fetch.
                    out = retry_or_raise("fetch", e, host, n, idx, state)

        window: collections.deque = collections.deque()
        for dev_batch, host, n, meta, idx, lease in put_stream():
            state = {"attempts": 1}
            try:
                out = dispatch_once(dev_batch, n, idx)
            except _failures().ScoringStallError:
                # A wedged dispatch is not fixed by re-dispatching onto
                # the same wedged device (same rule as the fetch stall).
                ev.event("give_up", stage="dispatch", stalled=True,
                         timeout_s=stall_s, batch=idx)
                raise
            except Exception as e:  # noqa: BLE001 — reclassified
                out = retry_or_raise("dispatch", e, host, n, idx, state)
            window.append((out, host, n, meta, idx, state, lease))
            oldest = window.popleft() if len(window) > self.prefetch \
                else None
            if depth_gauge is not None:
                # Live in-flight view: window depth + slot occupancy
                # (fraction of the prefetch capacity holding a dispatched
                # execution) — a persistently sub-1 occupancy means the
                # feed, not the device, is the bottleneck. Read AFTER the
                # pop: a keeping-up feed reads 1.0, not a perpetual
                # (prefetch+1)/prefetch.
                depth_gauge.set(len(window))
                occupancy_gauge.set(len(window) / max(self.prefetch, 1))
            if oldest is not None:
                yield fetch(oldest)
        while window:
            if depth_gauge is not None:
                depth_gauge.set(len(window))
            yield fetch(window.popleft())


def run_batched(fn: Callable, batches: Iterable, batch_size: int,
                prefetch: int = 2) -> Iterator:
    return BatchRunner(fn, batch_size, prefetch=prefetch).run(batches)


# ---------------------------------------------------------------------------
# Shape-cached jitted NHWC resize (the fused-preprocess building block)
# ---------------------------------------------------------------------------

_RESIZE_JITS: dict[tuple, Callable] = {}


def jit_resize_nhwc(height: int, width: int,
                    method: str = "bilinear") -> Callable:
    """One jitted ``jax.image.resize``-to-``(height, width)`` per target
    (+ method), cached for the process lifetime.

    ``jax.image.resize`` called bare re-traces (and eagerly re-dispatches
    the gather chain) on EVERY call; wrapping it in a cached ``jax.jit``
    makes each (input shape → target) pair one compilation ever, with
    jit's own signature cache handling per-shape reuse. The returned fn
    maps NHWC (device or host) batches to a DEVICE array — callers
    feeding ``device_put``/another jit keep it on device instead of
    forcing a host round-trip."""
    key = (int(height), int(width), str(method))
    fn = _RESIZE_JITS.get(key)
    if fn is None:
        h, w = key[0], key[1]

        def _resize(x):
            return jax.image.resize(x, (x.shape[0], h, w, x.shape[-1]),
                                    method=method)

        fn = _RESIZE_JITS.setdefault(key, jax.jit(_resize))
    return fn


# ---------------------------------------------------------------------------
# Compile-once helper with explicit cache keying (diagnostics)
# ---------------------------------------------------------------------------

class CompileCache:
    """Explicit jit cache keyed by (name, input treedef/shapes/dtypes).

    jax.jit already caches per-signature; this wrapper adds *observability*
    (hit/miss counters, recompile warnings) because silent recompilation is
    the primary TPU performance failure mode."""

    def __init__(self):
        self._fns: dict[str, Any] = {}
        self._keys: dict[str, set] = {}
        self._lock = threading.Lock()
        self.misses = 0
        self.hits = 0

    def note(self, name: str, key) -> bool:
        """Record one call signature; True when it is NEW for ``name``.

        Silent recompilation is the primary TPU perf failure mode — every
        new (fn, signature) pair becomes a visible flight-recorder
        ``recompile`` event, so traces/postmortems show a recompile storm
        instead of mysterious step-time spikes. Shared by the jit wrapper
        below and ``BatchRunner``'s dispatch loop."""
        with self._lock:
            seen = self._keys.setdefault(name, set())
            if key in seen:
                self.hits += 1
                return False
            seen.add(key)
            self.misses += 1
            misses = self.misses
        _events().event("recompile", fn=name, misses=misses,
                        shapes=str(key)[:200])
        return True

    def get(self, name: str, fn: Callable, static_argnums=()) -> Callable:
        with self._lock:
            if name not in self._fns:
                self._fns[name] = jax.jit(fn, static_argnums=static_argnums)
        jitted = self._fns[name]

        def wrapped(*args, **kwargs):
            key = jax.tree_util.tree_structure((args, kwargs)), tuple(
                (getattr(x, "shape", None), str(getattr(x, "dtype", "")))
                for x in jax.tree_util.tree_leaves((args, kwargs)))
            self.note(name, key)
            return jitted(*args, **kwargs)

        return wrapped

    def snapshot(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses}

    def signatures(self, name: str) -> int:
        """How many distinct call signatures ``name`` has compiled — the
        re-trace observable (the serving bench pins "no decode-step
        re-trace after warmup" as ``signatures('serve_decode_step')``
        staying constant across the measured run)."""
        with self._lock:
            return len(self._keys.get(name, ()))


GLOBAL_COMPILE_CACHE = CompileCache()


# ---------------------------------------------------------------------------
# Persistent (on-disk) XLA compilation cache
# ---------------------------------------------------------------------------
# One rule, wired once. If JAX_COMPILATION_CACHE_DIR is set, JAX reads it
# and nothing here names a directory. If it is unset, the cache lives at
# <checkout>/.jax_cache — a fixed path, because a cache directory that
# moves between runs never hits. Armed at import, so every entry point
# (chip_smoke.py, the benchmark, launcher ranks, scoring jobs) has it: a
# second process compiling the same program (a supervised gang restart, a
# repeat scoring job) loads the executable from disk instead of
# recompiling.

DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_PERSISTENT_CACHE_STATS = {"hits": 0, "misses": 0}
_persistent_cache_lock = threading.Lock()


def _on_compile_cache_event(event: str, **_attrs) -> None:
    """jax.monitoring listener: every persistent-cache hit/miss bumps
    :func:`persistent_cache_stats` and lands in the flight recorder as a
    ``compile_cache`` event (a hit = this process skipped the XLA
    compile, a miss = it paid it — the signals supervise() postmortems
    and the score smoke read)."""
    if event == "/jax/compilation_cache/cache_hits":
        key, outcome = "hits", "hit"
    elif event == "/jax/compilation_cache/cache_misses":
        key, outcome = "misses", "miss"
    else:
        return
    with _persistent_cache_lock:
        _PERSISTENT_CACHE_STATS[key] += 1
        n = _PERSISTENT_CACHE_STATS[key]
    _events().event("compile_cache", outcome=outcome, count=n)


def persistent_cache_stats() -> dict:
    """``{"hits": N, "misses": N, "dir": path|None}`` for the persistent
    compilation cache; ``dir`` is what JAX itself reports."""
    with _persistent_cache_lock:
        return {**_PERSISTENT_CACHE_STATS,
                "dir": jax.config.jax_compilation_cache_dir}


def _arm_persistent_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        try:
            os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        except OSError as e:
            # a read-only checkout runs uncached; it must still run (this
            # executes at import in every process, gang workers included)
            log.warning("persistent compile cache off: cannot create %s "
                        "(%s)", DEFAULT_COMPILE_CACHE_DIR, e)
        else:
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_COMPILE_CACHE_DIR)
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        # cache every program, not only the slow ones: a serving engine's
        # small copy/scatter programs re-trace on every restart too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.monitoring.register_event_listener(_on_compile_cache_event)


_arm_persistent_compile_cache()
