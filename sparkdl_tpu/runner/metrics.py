"""Runner observability: throughput metering, step tracing, debug modes.

The reference had no in-tree profiling — users got the Spark UI's stage/task
timing (SURVEY.md §5.1). Here per-step examples/s/chip is a first-class runner
output (it is *the* BASELINE metric), and ``jax.profiler`` traces are one
call away.
"""

from __future__ import annotations

import contextlib
import json
import logging
import math
import os
import random
import time
from dataclasses import dataclass, field

import jax

from . import events
from . import sentinel
from . import telemetry

log = logging.getLogger("sparkdl_tpu.runner")

# Every flight-recorder span also opens a profiler annotation of the same
# name (idle when no trace is running): events.py itself stays jax-free.
events.set_span_mirror(jax.profiler.TraceAnnotation)


@dataclass
class RunStats:
    """Process-wide failure/recovery counters (ISSUE 1 tentpole): the
    restart machinery and the chaos subsystem both record here so the
    emitted metrics JSON carries ``restarts``, ``faults_injected``, and
    ``last_failure_kind`` next to the throughput numbers.

    ``run_with_restarts`` records restarts/failures; ``chaos.fire`` records
    injections.
    Cumulative per process — tests isolate with ``reset()``.
    """
    restarts: int = 0
    faults_injected: int = 0
    last_failure_kind: str | None = None
    last_failure: str | None = None
    fault_sites: list = field(default_factory=list)
    # Data-plane fault tolerance (ISSUE 4): the streaming scorer and the
    # verified-checkpoint machinery count their degradations here so
    # meter.summary() / bench records carry them next to throughput.
    rows_quarantined: int = 0
    dispatch_retries: int = 0
    dispatch_giveups: int = 0
    checkpoint_rollbacks: int = 0
    last_rollback: str | None = None
    # Training data plane (ISSUE 5): poison batches the supervisor
    # quarantined onto the dataset skip-list.
    train_batches_quarantined: int = 0
    # Elastic gang supervision (ISSUE 16): world-size changes the
    # supervisor made around permanently dead ranks.
    resizes: int = 0
    last_resize: str | None = None

    def record_restart(self):
        self.restarts += 1

    def record_resize(self, from_np: int, to_np: int,
                      rank: int | None = None):
        self.resizes += 1
        self.last_resize = (f"np {from_np} -> {to_np}"
                            + (f" (rank {rank} dead)"
                               if rank is not None else ""))[:300]

    def record_failure(self, kind: str, detail: str | None = None):
        self.last_failure_kind = kind
        self.last_failure = (detail or "")[:500] or None

    def record_fault(self, site: str, kind: str):
        self.faults_injected += 1
        self.fault_sites.append(f"{site}:{kind}")

    def record_quarantine(self, rows: int = 1):
        self.rows_quarantined += int(rows)

    def record_retry(self, giveup: bool = False):
        if giveup:
            self.dispatch_giveups += 1
        else:
            self.dispatch_retries += 1

    def record_batch_quarantine(self, n: int = 1):
        self.train_batches_quarantined += int(n)

    def record_rollback(self, from_step, to_step, reason: str | None = None):
        self.checkpoint_rollbacks += 1
        self.last_rollback = (f"step {from_step} -> {to_step}"
                              + (f" ({reason})" if reason else ""))[:300]

    def snapshot(self) -> dict:
        return {"restarts": self.restarts,
                "faults_injected": self.faults_injected,
                "last_failure_kind": self.last_failure_kind,
                "last_failure": self.last_failure,
                "fault_sites": list(self.fault_sites),
                "rows_quarantined": self.rows_quarantined,
                "dispatch_retries": self.dispatch_retries,
                "dispatch_giveups": self.dispatch_giveups,
                "checkpoint_rollbacks": self.checkpoint_rollbacks,
                "last_rollback": self.last_rollback,
                "train_batches_quarantined": self.train_batches_quarantined,
                "resizes": self.resizes,
                "last_resize": self.last_resize}

    def degraded(self) -> bool:
        """True when any fault-tolerance machinery actually engaged —
        the gate bench/summaries use to keep all-zero ledgers out of
        every record."""
        return bool(self.restarts or self.faults_injected
                    or self.rows_quarantined or self.dispatch_retries
                    or self.dispatch_giveups or self.checkpoint_rollbacks
                    or self.train_batches_quarantined or self.resizes)

    def reset(self):
        self.restarts = 0
        self.faults_injected = 0
        self.last_failure_kind = None
        self.last_failure = None
        self.fault_sites = []
        self.rows_quarantined = 0
        self.dispatch_retries = 0
        self.dispatch_giveups = 0
        self.checkpoint_rollbacks = 0
        self.last_rollback = None
        self.train_batches_quarantined = 0
        self.resizes = 0
        self.last_resize = None


run_stats = RunStats()


def touch_heartbeat(step: int | None = None):
    """Per-rank liveness beacon for the gang supervisor's hang watchdog.

    ``fit()`` calls this every step; with ``SPARKDL_HEARTBEAT_DIR`` unset
    (the non-supervised case) it is a no-op. The body is JSON
    ``{"step": N, "time": <unix>}`` — the step shows where each rank
    stopped making progress, the wall clock lets postmortems line beats up
    against the event timeline. Written to a tmp file + ``os.replace`` so
    the watchdog can never read a torn/empty body mid-write.
    """
    hb_dir = os.environ.get("SPARKDL_HEARTBEAT_DIR")
    if not hb_dir:
        return
    rank = os.environ.get("SPARKDL_PROCESS_ID", "0")
    try:
        os.makedirs(hb_dir, exist_ok=True)
        events.atomic_write_json(
            os.path.join(hb_dir, f"rank{rank}.hb"),
            {"step": step, "time": round(time.time(), 3)})
    except OSError:  # a torn-down tmpdir must not kill the train loop
        pass


# -- step-time statistics & MFU ----------------------------------------------

# bf16 peak FLOPs/s per chip by device_kind substring (first match wins —
# "v5 lite"/"v5e" must be probed before a bare "v5"). SPARKDL_PEAK_FLOPS
# overrides (raw FLOPs, e.g. "197e12").
_PEAK_FLOPS_BY_KIND = (
    ("v5 lite", 197e12), ("v5litepod", 197e12), ("v5e", 197e12),
    ("v5p", 459e12), ("v6 lite", 918e12), ("v6e", 918e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
)


def peak_flops_per_chip() -> float | None:
    """Per-chip peak FLOPs/s for the MFU denominator: the
    ``SPARKDL_PEAK_FLOPS`` env override, else the device table keyed on
    ``device_kind``; None (→ MFU null) when neither knows the hardware."""
    env = os.environ.get("SPARKDL_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            log.warning("ignoring unparseable SPARKDL_PEAK_FLOPS=%r", env)
    try:
        kind = jax.devices()[0].device_kind.lower()
    except Exception:
        return None
    for pat, val in _PEAK_FLOPS_BY_KIND:
        if pat in kind:
            return val
    return None


class StepTimeStats:
    """Bounded reservoir of per-step wall times → p50/p95/p99/max.

    Reservoir sampling (seeded, deterministic) keeps memory O(capacity)
    over arbitrarily long runs while ``max`` and ``mean`` stay exact over
    ALL recorded steps — a straggler spike is never sampled away from the
    max, only from the quantile sample.
    """

    def __init__(self, capacity: int = 2048):
        self._cap = max(capacity, 1)
        self._sample: list[float] = []
        self._rng = random.Random(0xC0FFEE)
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0

    def record(self, dt_s: float):
        if dt_s < 0:
            return
        self.count += 1
        self.total_s += dt_s
        if dt_s > self.max_s:
            self.max_s = dt_s
        if len(self._sample) < self._cap:
            self._sample.append(dt_s)
        else:
            j = self._rng.randrange(self.count)
            if j < self._cap:
                self._sample[j] = dt_s

    @staticmethod
    def _nearest_rank(sorted_sample: list[float], q: float) -> float:
        idx = max(0, min(len(sorted_sample) - 1,
                         math.ceil(q / 100.0 * len(sorted_sample)) - 1))
        return sorted_sample[idx]

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the sample (exact when the run is
        shorter than the reservoir)."""
        if not self._sample:
            return 0.0
        return self._nearest_rank(sorted(self._sample), q)

    def summary(self) -> dict:
        if not self.count:
            return {}
        s = sorted(self._sample)  # one sort for all three percentiles
        return {
            "n": self.count,
            "mean_s": round(self.total_s / self.count, 6),
            "p50_s": round(self._nearest_rank(s, 50), 6),
            "p95_s": round(self._nearest_rank(s, 95), 6),
            "p99_s": round(self._nearest_rank(s, 99), 6),
            "max_s": round(self.max_s, 6),
        }

    def reset(self):
        self._sample = []
        self._rng = random.Random(0xC0FFEE)
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0


# Process-wide accumulator (the run_stats pattern): every meter also records
# here, so step-time percentiles for whatever trained in-process can be
# read without threading meter objects through.
global_step_stats = StepTimeStats()


@dataclass
class ThroughputMeter:
    """Tracks examples/s and examples/s/chip over a training run.

    ``update(n)`` per step after the step's results are *ready* (the caller
    controls ``block_until_ready`` discipline — metering must not force extra
    host syncs on the hot path, so by default only every ``sync_every`` steps
    block).

    Step times (``step_stats`` and the derived MFU): the recorded dt is
    host wall time between ``update`` calls; the meter itself never forces
    a sync. ``fit()`` calls ``update`` as it *retires* a step — once that
    step's metrics have arrived, one step behind the dispatch — so each
    interval is the time between two steps finishing: the step's device
    time when the chip sets the pace, the host's time per step when
    ``data`` does. p50/p95/p99 are therefore real step times, whatever
    ``log_every`` is. A caller that updates after a bare dispatch still
    meters dispatch-scale intervals, with the queued compute absorbed
    wherever it syncs (only ``mean_s`` is honest there).
    """
    n_chips: int = 1
    warmup_steps: int = 1  # first step includes XLA compile; exclude it
    flops_per_step: float | None = None  # GLOBAL per-step FLOPs (for MFU)
    peak_flops_per_chip: float | None = None  # default: device table / env
    step_stats: StepTimeStats = field(default_factory=StepTimeStats)
    _t0: float | None = None
    _last_t: float | None = None
    _steps: int = 0
    _examples: int = 0
    _window: list = field(default_factory=list)

    def update(self, n_examples: int):
        now = time.perf_counter()
        self._steps += 1
        if self._steps <= self.warmup_steps:
            self._t0 = now
            self._last_t = now
            return
        self._examples += n_examples
        if self._last_t is not None:
            dt = now - self._last_t
            self.step_stats.record(dt)
            global_step_stats.record(dt)
            # Online drift detection (ISSUE 17): one global read + return
            # when the sentinel is off — the pinned ≈-free posture.
            sentinel.observe("step_time", dt)
        self._last_t = now
        self._window.append((now, n_examples))
        if len(self._window) > 50:
            self._window.pop(0)

    @property
    def steps(self) -> int:
        return self._steps

    def examples_per_sec(self) -> float:
        if self._t0 is None or self._steps <= self.warmup_steps:
            return 0.0
        dt = time.perf_counter() - self._t0
        return self._examples / dt if dt > 0 else 0.0

    def examples_per_sec_per_chip(self) -> float:
        return self.examples_per_sec() / max(self.n_chips, 1)

    def recent_examples_per_sec(self) -> float:
        if len(self._window) < 2:
            return self.examples_per_sec()
        dt = self._window[-1][0] - self._window[0][0]
        n = sum(n for _, n in self._window[1:])
        return n / dt if dt > 0 else 0.0

    def _mfu_from(self, step_summary: dict) -> float | None:
        if not self.flops_per_step:
            return None
        peak = self.peak_flops_per_chip or peak_flops_per_chip()
        if not peak or not step_summary or step_summary["mean_s"] <= 0:
            return None
        return self.flops_per_step / step_summary["mean_s"] / (
            peak * max(self.n_chips, 1))

    def mfu(self) -> float | None:
        """Model FLOPs utilization: achieved FLOPs/s over hardware peak.
        Needs a per-step FLOP count (user-supplied or XLA cost-analysis
        estimated — see ``fit(flops_per_step=...)``) and a known peak;
        None otherwise, so consumers can tell "unknown" from "terrible"."""
        return self._mfu_from(self.step_stats.summary())

    def summary(self) -> dict:
        st = self.step_stats.summary()  # computed once for mfu + report
        mfu = self._mfu_from(st)
        return {
            "steps": self._steps,
            "examples": self._examples,
            "examples_per_sec": round(self.examples_per_sec(), 2),
            "examples_per_sec_per_chip":
                round(self.examples_per_sec_per_chip(), 2),
            "n_chips": self.n_chips,
            "step_time": st or None,
            "mfu": round(mfu, 4) if mfu is not None else None,
            "compile_cache": compile_cache_summary(),
            "fault_tolerance": fault_tolerance_summary(),
            # Live telemetry plane (ISSUE 6): per-stage busy fractions +
            # the dominant stage, from the armed accountant. None when
            # the plane is off — clean summaries stay clean.
            "stage_utilization": telemetry.stage_utilization_summary(),
        }


def fault_tolerance_summary() -> dict | None:
    """Quarantine / dispatch-retry / checkpoint-rollback counters for
    ``meter.summary()`` (ISSUE 4) — the degradations a job survived,
    next to its throughput. None when nothing engaged, so clean runs
    stay clean."""
    if not run_stats.degraded():
        return None
    snap = run_stats.snapshot()
    return {k: v for k, v in snap.items()
            if k in ("restarts", "faults_injected", "rows_quarantined",
                     "dispatch_retries", "dispatch_giveups",
                     "checkpoint_rollbacks", "last_rollback",
                     "train_batches_quarantined", "resizes", "last_resize")
            and v}


def compile_cache_summary() -> dict | None:
    """Process-wide compilation visibility for ``meter.summary()``:
    jit-signature hits/misses from ``runtime.GLOBAL_COMPILE_CACHE``
    (every miss is a recompile — the stated primary TPU perf failure
    mode, previously invisible outside its own counters) plus the
    persistent on-disk cache's hit/miss tally when armed. None when
    nothing has been recorded, so quiet runs stay quiet."""
    try:
        from sparkdl_tpu.core.runtime import (GLOBAL_COMPILE_CACHE,
                                              persistent_cache_stats)
    except Exception:
        return None
    out: dict = {}
    snap = GLOBAL_COMPILE_CACHE.snapshot()
    if snap["hits"] or snap["misses"]:
        out.update(snap)
    pstats = persistent_cache_stats()
    if pstats.get("dir"):
        out["persistent"] = pstats
    return out or None


class MetricsLogger:
    """Scalar metrics sink: stdlib logging always; TensorBoard event files
    when a ``log_dir`` is given (via tensorboardX, SURVEY.md §5.5)."""

    def __init__(self, log_dir: str | None = None):
        self._tb = None
        if log_dir:
            try:
                from tensorboardX import SummaryWriter
                self._tb = SummaryWriter(log_dir)
            except Exception:  # tensorboardX optional
                log.warning("tensorboardX unavailable; metrics to log only")

    def log(self, step: int, metrics: dict):
        """Emit to TB and the text log. Cadence is the caller's job (fit()
        gates on log_every) — no re-gating here, or final/eval metrics at
        off-cadence steps would be silently dropped. Non-numeric values
        (strings, multi-element arrays) pass through to the text line
        instead of crashing the train loop."""
        if self._tb is not None:
            for k, v in metrics.items():
                try:
                    self._tb.add_scalar(k, float(v), step)
                except (TypeError, ValueError):
                    pass

        def _fmt(v):
            if isinstance(v, (int, float)) or hasattr(v, "item"):
                try:
                    return round(float(v), 5)
                except (TypeError, ValueError):
                    return str(v)  # e.g. a multi-element array
            return v

        flat = {k: _fmt(v) for k, v in metrics.items()}
        log.info("step %d %s", step, json.dumps(flat, default=str))

    def log_summary(self, step: int, summary: dict):
        """Flatten a ``meter.summary()`` into scalars and emit once —
        percentiles, MFU, and the nested subsystem blocks
        (``fault_tolerance``, ``compile_cache``, ``stage_utilization``)
        land in TB/text next to the per-step series. Flattening is
        RECURSIVE (ISSUE 6 satellite): a doubly-nested block like
        ``compile_cache.persistent.hits`` becomes the scalar key
        ``compile_cache_persistent_hits`` instead of a stringified dict
        that TB silently drops and CSV consumers can't parse."""
        flat: dict = {}

        def _flatten(prefix: str, v):
            if isinstance(v, dict):
                for k2, v2 in v.items():
                    _flatten(f"{prefix}_{k2}" if prefix else str(k2), v2)
            elif v is not None:
                flat[prefix] = v

        _flatten("", summary)
        self.log(step, flat)

    def close(self):
        """Idempotent: fit() closes on the success path and callers close
        again in their own cleanup."""
        tb, self._tb = self._tb, None
        if tb is not None:
            tb.close()


def start_profiler_trace(log_dir: str):
    """Start a jax profiler trace + the flight-recorder event linking
    postmortems to the profile on disk. Pair with
    :func:`stop_profiler_trace` (or use the :func:`trace` context
    manager)."""
    events.event("profile_trace", trace_dir=log_dir)
    jax.profiler.start_trace(log_dir)


def stop_profiler_trace(failed: bool = False):
    """The ONE implementation of the guarded profiler stop: if the traced
    region already ``failed``, a ``stop_trace`` error (a region that died
    mid-trace can leave the profiler in a state stop rejects) is logged,
    not raised — a profiling hiccup must never mask the real failure. On
    a clean region the stop error propagates."""
    try:
        jax.profiler.stop_trace()
    except Exception:
        if not failed:
            raise
        log.warning("profiler stop failed during exception unwind",
                    exc_info=True)


@contextlib.contextmanager
def trace(log_dir: str):
    """Profile a region to a TensorBoard-viewable trace:
    ``with runner.trace("/tmp/tb"): run_steps()``.

    The profiler is closed even when the region raises, without the stop
    masking the region's own exception (see :func:`stop_profiler_trace`).
    """
    start_profiler_trace(log_dir)
    failed = False
    try:
        yield
    except BaseException:
        failed = True
        raise
    finally:
        stop_profiler_trace(failed)


def step_annotation(step: int):
    """Per-step trace annotation so the profiler groups ops by train step."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


@contextlib.contextmanager
def debug_mode(nans: bool = True):
    """Debug sanitizer mode (SURVEY.md §5.2): XLA SPMD is data-race-free by
    construction, so the TPU-relevant sanitizer is numeric — NaN checking
    forces a recompile with NaN traps on every op."""
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", nans)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)
