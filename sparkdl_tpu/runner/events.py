"""Flight recorder — structured per-rank event tracing (ISSUE 2 tentpole).

PR 1 made failures a tested subsystem; this module makes them *diagnosable*.
Every interesting moment in the runner (step phases, checkpoint saves,
injected faults, profiler traces, restarts) becomes a structured event:

- :func:`event(name, **attrs)` — a point event
- :func:`span(name, **attrs)` — a context manager emitting begin/end events
  with the measured duration (and the exception, when the region fails)

Events land in a bounded in-memory **ring buffer** (``SPARKDL_EVENT_RING``
entries, default 4096: a ten-second ``fit`` window of ~220 steps is ~1,800
records). With ``SPARKDL_EVENT_DIR`` unset the hot-path cost is a dict
build + deque append — no I/O, no host sync, no jax import. With it
set, each event is also streamed as one JSON line to
``$SPARKDL_EVENT_DIR/events_rank{i}.jsonl`` (line-buffered, so a SIGKILLed
rank's trace survives up to its last completed event).

On any failure path (``fit()``, ``run_with_restarts``) the ring is flushed
as a **crash postmortem** — last N events + the exception — to
``postmortem_rank{i}.json``. The gang supervisor (``launcher.supervise``)
merges all ranks' event files, postmortems, and heartbeats into a single
time-ordered **gang timeline** (:func:`merge_timeline`) naming which rank
failed or stalled first, at what step, and at which site.

Spans are also mirrored onto the profiler's clock: ``runner.metrics``
installs ``jax.profiler.TraceAnnotation`` as the span mirror
(:func:`set_span_mirror`), so a host-traced profile shows ``shard_put``,
``step_compute``, ``loss_fetch`` … beside the device's ``XLA Ops``.

This module is stdlib-only at import time (the supervising launcher must
stay jax-free); :class:`Timer` lazily imports jax only when asked to block
on a device pytree. ``utils.Timer`` is a thin alias of it — one timing
primitive in the codebase.
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import os
import re
import threading
import time
import uuid

__all__ = ["FlightRecorder", "Timer", "RECORDER_DIR_ENV", "RING_ENV",
           "TRACE_ID_ENV", "TRACE_PARENT_ENV",
           "event", "span", "postmortem", "get_recorder", "reset",
           "enable_flight_recorder", "merge_timeline", "format_timeline",
           "write_gang_postmortem", "clear_rank_files",
           "collect_degradations", "add_tee", "remove_tee",
           "set_span_mirror",
           "trace_armed", "new_trace_id", "new_span_id", "current_span_id"]

log = logging.getLogger("sparkdl_tpu.runner")

RECORDER_DIR_ENV = "SPARKDL_EVENT_DIR"
RING_ENV = "SPARKDL_EVENT_RING"
# what every record says of itself: an attribute of these names would
# overwrite it (``event(name, step=..., **metrics)`` filters a user's keys)
RECORD_KEYS = ("name", "ph", "t", "rank", "step")
STREAM_CAP_ENV = "SPARKDL_EVENT_MAX_MB"
# Causal trace context (ISSUE 17): the driver mints one run-level trace id
# and ships it to every rank; each gang attempt/resize gets a parent span
# id so a rank's whole stream chains under the supervise() attempt that
# launched it. Both ride the environment — the same channel coordinator
# address and rank already use — so a rank inherits its causal position
# with zero protocol.
TRACE_ID_ENV = "SPARKDL_TRACE_ID"
TRACE_PARENT_ENV = "SPARKDL_TRACE_PARENT"
_DEFAULT_RING = 4096
_DEFAULT_STREAM_CAP_MB = 256  # per-rank JSONL cap; ring keeps recording
_POSTMORTEM_TAIL = 128  # events carried in a crash postmortem


def _rank() -> int:
    try:
        return int(os.environ.get("SPARKDL_PROCESS_ID", "0"))
    except ValueError:
        return 0


# Event tees (ISSUE 6): consumers that see every emitted record in-process
# — the telemetry plane's StageAccountant rides here, turning span exits
# into per-stage busy-seconds without touching any instrumentation site.
# Module-level (not per-recorder) so a tests' events.reset() cannot
# silently detach a live accountant. Empty by default: the hot-path cost
# of an unused tee list is one falsy check per emit.
_TEES: list = []


def add_tee(cb) -> None:
    """Register ``cb(record_dict)`` to observe every emitted event.
    Idempotent per callable."""
    if cb not in _TEES:
        _TEES.append(cb)


def remove_tee(cb) -> None:
    try:
        _TEES.remove(cb)
    except ValueError:
        pass


# Span mirror: ``factory(name)`` -> a context manager entered and exited with
# every span, on the span's thread. ``runner.metrics`` installs
# ``jax.profiler.TraceAnnotation`` (this module imports no jax), which puts
# the program's phases on the profiler's timeline under the span's own name.
# Same rule as the tees: a mirror that raises never reaches the caller.
_MIRROR = None


def set_span_mirror(factory) -> None:
    """Install (or, with None, remove) the span mirror."""
    global _MIRROR
    _MIRROR = factory


# -- trace context (ISSUE 17) -------------------------------------------------
# Spans gain span_id/parent_id from a thread-local span stack, so nested
# regions chain causally WITHIN a thread and a feed thread's spans never
# parent under the training loop's. The machinery is armed only when
# SPARKDL_TRACE_ID is set: untraced runs keep emitting byte-identical
# records (one env lookup per span, the same cost class as emit's
# existing RECORDER_DIR_ENV read).

_TRACE_TLS = threading.local()
_SPAN_SEQ = itertools.count(1)


def trace_armed() -> bool:
    """True when a run-level trace id is in the environment."""
    return bool(os.environ.get(TRACE_ID_ENV))


def new_trace_id() -> str:
    """Mint a run-level trace id (driver side, once per supervise/launch)."""
    return uuid.uuid4().hex[:16]


def new_span_id() -> str:
    """Cheap process-unique span id: rank + pid + per-process counter.
    No randomness on the hot path — uniqueness comes from the (pid, seq)
    pair, and the rank prefix makes raw streams greppable by origin."""
    return f"{_rank()}-{os.getpid():x}-{next(_SPAN_SEQ):x}"


def current_span_id() -> str | None:
    """Innermost open span on THIS thread, else the env-shipped parent
    (the supervise() attempt span that launched this process), else None.
    The fallback is what chains a rank's outermost spans — and a bare
    point event emitted outside any span — to the driver's attempt."""
    st = getattr(_TRACE_TLS, "stack", None)
    if st:
        return st[-1]
    return os.environ.get(TRACE_PARENT_ENV) or None


def _push_span(span_id: str) -> None:
    st = getattr(_TRACE_TLS, "stack", None)
    if st is None:
        st = _TRACE_TLS.stack = []
    st.append(span_id)


def _pop_span(span_id: str) -> None:
    st = getattr(_TRACE_TLS, "stack", None)
    if not st:
        return
    if st[-1] == span_id:
        st.pop()
    else:
        # A span exited out of order (generator-held context manager, or
        # exit on a different thread than enter): drop just that id —
        # corrupting the WHOLE stack would mis-parent every later span.
        try:
            st.remove(span_id)
        except ValueError:
            pass


class Timer:
    """``with Timer() as t: ...`` then ``t.seconds`` — blocks on ``block_on``
    (a jax pytree) before stopping, so device work is actually counted.

    The base of the span API: a span is a Timer that also records events.
    """

    __slots__ = ("seconds", "_block_on", "_t0")

    def __init__(self, block_on=None):
        self._block_on = block_on
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._block_on is not None:
            import jax  # lazy: the recorder itself must stay jax-free
            jax.block_until_ready(self._block_on)
        self.seconds = time.perf_counter() - self._t0
        return False


class _Span(Timer):
    """Begin/end event pair around a region; duration and (on failure) the
    exception ride the end event."""

    __slots__ = ("_rec", "_name", "_attrs", "_span_id", "_mirror")

    def __init__(self, rec: "FlightRecorder", name: str, block_on=None,
                 **attrs):
        super().__init__(block_on)
        self._rec = rec
        self._name = name
        self._attrs = attrs
        self._span_id = None
        self._mirror = None

    def __enter__(self):
        if _MIRROR is not None:
            try:
                m = _MIRROR(self._name)
                m.__enter__()
                self._mirror = (m, threading.get_ident())
            except Exception:  # noqa: BLE001 — see _MIRROR
                self._mirror = None
        super().__enter__()
        if trace_armed():
            # span_id/parent_id land in _attrs so BOTH the B and the E
            # record carry them; an explicit span_id/parent_id kwarg
            # (the serving engine parenting under a request's admission
            # span) wins over the ambient stack.
            self._span_id = self._attrs.get("span_id") or new_span_id()
            parent = self._attrs.get("parent_id") or current_span_id()
            if parent is not None:
                self._attrs.setdefault("parent_id", parent)
            self._attrs["span_id"] = self._span_id
            _push_span(self._span_id)
        self._rec.emit(self._name, "B", self._attrs)
        return self

    def set(self, **attrs) -> "_Span":
        """Attach attrs discovered INSIDE the region (bytes copied, rows
        staged) — they land on the end event; the begin event has already
        been emitted without them."""
        self._attrs.update(attrs)
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._span_id is not None:
            # Pop before the end event: anything emitted from here on
            # (including the E record itself, which carries explicit ids)
            # belongs to the enclosing scope, not the closed region.
            _pop_span(self._span_id)
        block_err = None
        try:
            super().__exit__(exc_type, exc, tb)
        except BaseException as be:
            # block_on is where async device errors materialize — the one
            # span that observed the failure must still land its end
            # event (with the error) before the exception propagates.
            self.seconds = time.perf_counter() - self._t0
            block_err = be
        if self._mirror is not None:
            m, tid = self._mirror
            self._mirror = None
            # the profiler files an annotation under the thread that closes
            # it: a span that exits elsewhere is not mirrored
            if tid == threading.get_ident():
                try:
                    m.__exit__(exc_type, exc, tb)
                except Exception:  # noqa: BLE001 — see _MIRROR
                    pass
        end = dict(self._attrs)
        end["dur_s"] = round(self.seconds, 6)
        if exc is not None:
            # Exactly-once data plane (ISSUE 5): a draw-time failure is
            # tagged by the dataset with the batch being drawn
            # (data._tag_batch). The span that observed it is usually the
            # timeline's EARLIEST error evidence — it must carry the
            # attribution, or the supervisor's poison-batch quarantine
            # never sees a batch_index on first_failure.
            bi = getattr(exc, "_sparkdl_batch_index", None)
            if bi is not None:
                end["batch_index"] = bi
                ep = getattr(exc, "_sparkdl_batch_epoch", None)
                if ep is not None:
                    end["epoch"] = ep
        if exc_type is not None:
            if exc_type in (StopIteration, GeneratorExit):
                # Normal stream exhaustion (fit's data_fetch span around
                # next()) — mark it, but NOT as an error: merge_timeline
                # treats error-bearing events as failure evidence, and a
                # rank that finished its data must never be named the
                # gang's first failure.
                end["end_of_data"] = True
            else:
                end["error"] = f"{exc_type.__name__}: {exc}"[:300]
            if block_err is not None:  # both failed: record, don't mask
                end["block_error"] = \
                    f"{type(block_err).__name__}: {block_err}"[:300]
        elif block_err is not None:
            end["error"] = f"{type(block_err).__name__}: {block_err}"[:300]
        self._rec.emit(self._name, "E", end)
        if block_err is not None and exc_type is None:
            # Surface the device error from a clean region; when the
            # region ALREADY raised, its exception is the story — the
            # block error must not replace it (same never-mask rule as
            # stop_profiler_trace).
            raise block_err
        return False


class FlightRecorder:
    """Bounded event ring + optional per-rank JSONL stream.

    Record shape (flat, jq-friendly): ``{"t": <unix wall time>, "name": ...,
    "ph": "P"|"B"|"E", "rank": <int>, ...attrs}``. ``t``/``name``/``ph``/
    ``rank`` are reserved keys. Wall time (not perf_counter) so traces from
    different ranks on one host merge into one timeline.
    """

    def __init__(self, ring_size: int | None = None):
        if ring_size is None:
            try:
                ring_size = int(os.environ.get(RING_ENV, _DEFAULT_RING))
            except ValueError:
                ring_size = _DEFAULT_RING
        self.ring: collections.deque = collections.deque(
            maxlen=max(ring_size, 8))
        self._lock = threading.Lock()  # feed threads emit shard_put spans
        self._file = None
        self._dir = None
        self._stream_bytes = 0
        self._stream_cap = 0
        self._stream_capped = False

    # -- emission ---------------------------------------------------------
    def emit(self, name: str, ph: str = "P", attrs: dict | None = None,
             t: float | None = None):
        rec = {"t": round(time.time() if t is None else t, 6),
               "name": name, "ph": ph, "rank": _rank()}
        if attrs:
            rec.update(attrs)
        tid = os.environ.get(TRACE_ID_ENV)
        if tid:
            rec.setdefault("trace_id", tid)
            if "span_id" not in rec and "parent_id" not in rec:
                # Bare point events (chaos fires, anomaly, slo_breach)
                # parent under the innermost open span — or the
                # env-shipped attempt span when emitted outside any.
                parent = current_span_id()
                if parent is not None:
                    rec["parent_id"] = parent
        self.ring.append(rec)
        if _TEES:
            for cb in _TEES:
                try:
                    cb(rec)
                except Exception:  # noqa: BLE001 — telemetry must never
                    pass  # kill the hot path, nor one broken tee starve
                    # the others of the event (per-callback isolation)
        d = os.environ.get(RECORDER_DIR_ENV)
        if d:
            self._write(d, rec)

    def event(self, name: str, **attrs):
        self.emit(name, "P", attrs)

    def span(self, name: str, block_on=None, **attrs) -> _Span:
        return _Span(self, name, block_on=block_on, **attrs)

    def completed_span(self, name: str, dur_s: float, **attrs):
        """Land a span that ALREADY ran (its region executed where this
        recorder could not see it — a process-pool child whose ring dies
        with the child): B back-dated by ``dur_s``, E now. Downstream
        consumers (`analysis`, the telemetry accountant) read E events'
        ``t - dur_s``, so attribution matches a live span up to the
        child→parent hand-off delay; concurrent child regions reported
        sequentially can overlap-union slightly high, which `analysis`
        clamps."""
        t1 = time.time()
        if trace_armed():
            attrs.setdefault("span_id", new_span_id())
            parent = current_span_id()
            if parent is not None:
                attrs.setdefault("parent_id", parent)
        self.emit(name, "B", attrs, t=t1 - max(0.0, dur_s))
        end = dict(attrs)
        end["dur_s"] = round(max(0.0, dur_s), 6)
        self.emit(name, "E", end, t=t1)

    def _write(self, d: str, rec: dict):
        try:
            with self._lock:
                if self._file is None or self._dir != d:
                    if self._file is not None:
                        self._file.close()
                    os.makedirs(d, exist_ok=True)
                    self._dir = d
                    # append + line-buffered: a restart in the same process
                    # continues the file, and every completed event is on
                    # disk before a SIGKILL can land
                    self._file = open(
                        os.path.join(d, f"events_rank{_rank()}.jsonl"),
                        "a", buffering=1)
                    # Cap resolved once per open (not per event — this is
                    # the hot path) and budget seeded from what's already
                    # on disk (append mode sits at EOF): a reset()-per-
                    # attempt retry loop must not restart at 0 and grow
                    # the file N_attempts x cap.
                    self._stream_cap = self._stream_cap_bytes()
                    self._stream_bytes = self._file.tell()
                    self._stream_capped = \
                        self._stream_bytes > self._stream_cap
                if self._stream_capped:
                    return
                line = json.dumps(rec, default=str) + "\n"
                # len() == encoded bytes: json.dumps defaults to
                # ensure_ascii, so the line is pure ASCII by construction.
                self._stream_bytes += len(line)
                # Bounded stream (SPARKDL_EVENT_MAX_MB): a multi-day
                # supervised run must not fill the disk with per-step
                # spans. The ring keeps recording past the cap, so crash
                # postmortems stay complete; the marker line makes the
                # truncation visible to timeline readers.
                if self._stream_bytes > self._stream_cap:
                    self._stream_capped = True
                    self._file.write(json.dumps(
                        {"t": round(time.time(), 6),
                         "name": "event_stream_truncated", "ph": "P",
                         "rank": _rank(),
                         "cap_mb": self._stream_cap // 2 ** 20}
                    ) + "\n")
                    return
                self._file.write(line)
        except (OSError, ValueError):
            pass  # a torn-down tmpdir must not kill the train loop

    @staticmethod
    def _stream_cap_bytes() -> int:
        try:
            mb = float(os.environ.get(STREAM_CAP_ENV,
                                      _DEFAULT_STREAM_CAP_MB))
        except ValueError:
            mb = _DEFAULT_STREAM_CAP_MB
        return int(mb * 2 ** 20)

    # -- inspection / teardown -------------------------------------------
    def tail(self, n: int | None = None) -> list[dict]:
        # Feed-pool threads may still be appending (postmortem runs from
        # fit's exception handler BEFORE the pool shuts down); iterating a
        # deque under concurrent append can raise — retry, never let a
        # snapshot race replace the original training exception.
        for _ in range(5):
            try:
                evs = list(self.ring)
                break
            except RuntimeError:
                continue
        else:
            evs = []
        return evs if n is None else evs[-n:]

    def postmortem(self, exc: BaseException | None = None,
                   **attrs) -> dict:
        """Flush the ring tail + exception as a crash postmortem.

        Always returns the postmortem dict (and logs a compact line); when
        ``SPARKDL_EVENT_DIR`` is set it is also written atomically to
        ``postmortem_rank{i}.json`` so the gang supervisor can merge it.
        """
        info: dict = {"t": round(time.time(), 6), "rank": _rank()}
        if attrs:
            info.update(attrs)
        if exc is not None:
            try:  # lazy sibling import: no package-init work on the hot path
                from .failures import exception_summary
                info["error"] = exception_summary(exc)
            except Exception:
                info["error"] = {"type": type(exc).__name__,
                                 "message": str(exc)[:2000]}
        info["events"] = self.tail(_POSTMORTEM_TAIL)
        d = os.environ.get(RECORDER_DIR_ENV)
        if d:
            try:
                os.makedirs(d, exist_ok=True)
                atomic_write_json(
                    os.path.join(d, f"postmortem_rank{_rank()}.json"), info)
            except OSError:
                pass
        err = info.get("error", {})
        log.warning("flight recorder postmortem: rank %d, %d events, "
                    "error=%s", info["rank"], len(info["events"]),
                    err.get("type") if isinstance(err, dict) else None)
        return info

    def close(self):
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None
                self._dir = None


# -- process-global recorder --------------------------------------------------

_RECORDER: FlightRecorder | None = None


def get_recorder() -> FlightRecorder:
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = FlightRecorder()
    return _RECORDER


def reset(ring_size: int | None = None) -> FlightRecorder:
    """Fresh recorder (tests; ring-size changes). Closes any open stream."""
    global _RECORDER
    if _RECORDER is not None:
        _RECORDER.close()
    _RECORDER = FlightRecorder(ring_size=ring_size)
    return _RECORDER


def event(name: str, **attrs):
    get_recorder().event(name, **attrs)


def span(name: str, block_on=None, **attrs) -> _Span:
    return get_recorder().span(name, block_on=block_on, **attrs)


def completed_span(name: str, dur_s: float, **attrs) -> None:
    get_recorder().completed_span(name, dur_s, **attrs)


def postmortem(exc: BaseException | None = None, **attrs) -> dict:
    return get_recorder().postmortem(exc, **attrs)


def enable_flight_recorder(event_dir: str | None = None,
                           ring_size: int | None = None) -> FlightRecorder:
    """Public switch (``runner.api.enable_flight_recorder``): stream events
    to ``event_dir`` (also exported to child processes via the env var) and
    optionally resize the ring. ``event_dir=None`` keeps ring-only mode."""
    if event_dir is not None:
        os.environ[RECORDER_DIR_ENV] = event_dir
    if ring_size is not None:
        os.environ[RING_ENV] = str(ring_size)
    return reset(ring_size=ring_size)


# -- gang timeline (supervisor side) ------------------------------------------

_EVENT_FILE_RE = re.compile(r"events_rank(\d+)\.jsonl$")
_POSTMORTEM_FILE_RE = re.compile(r"postmortem_rank(\d+)\.json$")
GANG_TIMELINE_FILE = "gang_timeline.json"
# Supervisor-side span tree (ISSUE 17): trace id, run-root span, and one
# entry per gang attempt/resize. Lives NEXT TO the per-rank streams but is
# NOT cleared per attempt (clear_rank_files deletes by the rank-file
# patterns only) — the manifest is how trace_export resolves a rank
# stream's env-shipped parent chain back to the run root after earlier
# attempts' streams have been cleared.
TRACE_MANIFEST_FILE = "trace_manifest.json"
_MERGE_TAIL_BYTES = 1 << 20  # per-rank read cap when merging timelines
# Survived-fault narrative (ISSUE 4/5): engaged-and-recovered machinery.
# `give_up` is NOT here — an exhausted retry budget is failure evidence.
# ISSUE 5 adds the training data plane's narrative: a resume from
# checkpoint after a gang death (`train_resume`), a quarantined poison
# batch (`train_batch_quarantined`, emitted supervisor-side), the skips
# it causes on later attempts (`train_batch_skipped`), and a resume that
# could not verify a data cursor (`unverified_data_cursor` — legacy
# manifest or CRC mismatch: batches before the restored step re-consume).
# ISSUE 13 adds the SLO monitor's breach transitions: a burn-rate breach
# is service degradation the run survived — timeline narrative a
# postmortem should show, never failure evidence that could outrank the
# fault that actually killed the gang.
# ISSUE 16 adds the elastic narrative: a gang that shrank (or grew back)
# around a permanently dead rank (`gang_resized`, supervisor-side) and a
# checkpoint re-laid-out onto a different mesh at restore
# (`checkpoint_resharded`) both SURVIVED — degraded capacity, not failure.
_DEGRADATION_EVENTS = ("retry", "quarantine", "checkpoint_rollback",
                       "checkpoint_quarantine", "train_resume",
                       "train_batch_quarantined", "train_batch_skipped",
                       "unverified_data_cursor", "slo_breach",
                       "slo_recovered", "gang_resized",
                       "checkpoint_resharded")


def atomic_write_json(path: str, obj) -> str:
    """The ONE tmp-file + ``os.replace`` JSON writer (postmortems, gang
    timelines, heartbeats ride it): a reader can never observe a torn or
    empty body, and a kill between write and replace leaves only a pid-
    tagged .tmp file behind."""
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f, default=str)
    os.replace(tmp, path)
    return path


def _read_jsonl_tail(path: str, cap: int = _MERGE_TAIL_BYTES):
    """Parse the last ``cap`` bytes of a JSONL stream. Returns
    (records, truncated). Bounded on purpose: an 8-rank gang at the
    256 MB stream cap must not make the lightweight supervisor load
    gigabytes of events to build a postmortem — failure evidence lives
    in the tail."""
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        start = max(0, size - cap)
        f.seek(start)
        data = f.read()
    lines = data.decode("utf-8", "replace").splitlines()
    if start > 0 and lines:
        lines = lines[1:]  # the seek likely landed mid-line
    recs = []
    for line in lines:
        try:
            recs.append(json.loads(line))
        except ValueError:
            continue  # torn tail line from a killed rank
    return recs, start > 0


def clear_rank_files(event_dir: str):
    """Remove one attempt's event/postmortem files before relaunch — the
    timeline of attempt N must not splice attempt N-1's trace. Deletes by
    the SAME patterns ``merge_timeline`` globs (every rank, so a reused
    dir from an earlier, larger gang cannot leak a stale high-rank trace
    into the next failure's timeline). The merged ``gang_timeline.json``
    goes too — after a successful retry a user-supplied dir must not keep
    advertising the recovered failure."""
    try:
        names = os.listdir(event_dir)
    except OSError:
        return
    for fn in names:
        if _EVENT_FILE_RE.match(fn) or _POSTMORTEM_FILE_RE.match(fn) \
                or fn == GANG_TIMELINE_FILE:
            try:
                os.unlink(os.path.join(event_dir, fn))
            except OSError:
                pass


def parse_heartbeat_body(body: str) -> dict:
    """The ONE decoder of the heartbeat format contract (shared with the
    launcher's watchdog): JSON ``{"step": N, "time": T}`` from the atomic
    writer (``metrics.touch_heartbeat``), with bare step-number bodies
    (hand-rolled workers, pre-PR-2 format) still accepted."""
    try:
        d = json.loads(body)
        if isinstance(d, dict):
            return {k: d[k] for k in ("step", "time") if k in d}
    except ValueError:
        pass
    return {"step": body.strip() or None}


def _read_heartbeat(path: str) -> dict | None:
    try:
        st = os.stat(path)
        with open(path) as f:
            body = f.read()
    except OSError:
        return None
    hb = {"mtime": round(st.st_mtime, 3)}
    hb.update(parse_heartbeat_body(body))
    return hb


def merge_timeline(event_dir: str, heartbeat_dir: str | None = None,
                   max_events: int = 200) -> dict:
    """Merge all ranks' event streams, postmortems, and heartbeats into one
    time-ordered gang timeline.

    Returns ``{"ranks": {rank: {...}}, "first_failing_rank",
    "first_failure", "first_stalled_rank", "events": [...]}``. The
    first-failing rank is the one with the earliest error evidence (chaos
    event, failed span, or postmortem); when nothing errored (a hang), the
    first-*stalled* rank — earliest last event — is the lead suspect.
    """
    ranks: dict[int, dict] = {}
    merged: list[dict] = []
    errors: list[dict] = []  # (t, rank, site, step, error) candidates
    recovered: list[dict] = []  # in-process restarts: second-tier evidence
    last_restart: dict[int, float] = {}  # rank -> latest restart event t
    degradations: list[dict] = []  # survived faults: rollback/retry/quarantine
    try:
        names = sorted(os.listdir(event_dir))
    except OSError:
        names = []
    for fn in names:
        m = _EVENT_FILE_RE.match(fn)
        if not m:
            continue
        rank = int(m.group(1))
        try:
            recs, truncated = _read_jsonl_tail(os.path.join(event_dir, fn))
        except OSError:
            continue
        merged.extend(recs)
        # last_step from COMPUTE evidence (step_compute spans, chaos
        # fires), not feed events: a data_fetch span carries the step its
        # batch is drawn FOR, which the loop may never reach (the draw or
        # the put can be what fails), and a postmortem naming a step the
        # rank never computed would misdirect the resume/diagnosis. Fall
        # back to any step attr for hand-rolled traces that never emit
        # step_compute.
        compute_steps = [r["step"] for r in recs
                         if r.get("name") in ("step_compute", "chaos")
                         and isinstance(r.get("step"), (int, float))]
        any_steps = compute_steps or [
            r["step"] for r in recs
            if isinstance(r.get("step"), (int, float))]
        last = recs[-1] if recs else None
        ranks[rank] = {
            "n_events": len(recs),  # tail-bounded when truncated
            "last_step": int(max(any_steps)) if any_steps else None,
            "last_event": ({"t": last.get("t"), "name": last.get("name")}
                           if last else None),
        }
        if truncated:
            ranks[rank]["tail_truncated"] = True
        for r in recs:
            if r.get("name") == "chaos":
                e = {"t": r.get("t", 0), "rank": rank,
                     "site": r.get("site"), "step": r.get("step"),
                     "error": f"injected {r.get('kind')}"}
                # At the data_fetch site the hook's step IS the dataset's
                # global batch index — surface it so the supervisor can
                # correlate consecutive failures to one batch (the
                # poison-batch quarantine trigger).
                if r.get("site") == "data_fetch" \
                        and r.get("step") is not None:
                    e["batch_index"] = r.get("step")
                errors.append(e)
            elif r.get("name") == "restart":
                # An in-process restart (run_with_restarts) RECOVERED from
                # its error — second-tier evidence only, or it would
                # outrank the later fault that actually killed the gang.
                t = r.get("t", 0)
                last_restart[rank] = max(last_restart.get(rank, 0), t)
                recovered.append({"t": t, "rank": rank,
                                  "site": r.get("name"),
                                  "step": r.get("step"),
                                  "error": r.get("error"),
                                  "recovered": True})
            elif r.get("name") in _DEGRADATION_EVENTS:
                # Fault-tolerance machinery that ENGAGED AND RECOVERED
                # (ISSUE 4): a dispatch retry, quarantined rows, a
                # checkpoint rollback. Narrative, never failure evidence —
                # these events carry error text describing what was
                # survived, and must not outrank the fault that actually
                # killed the gang.
                degradations.append({"t": r.get("t", 0), "rank": rank,
                                     "kind": r.get("name"),
                                     "detail": {k: v for k, v in r.items()
                                                if k not in ("t", "ph",
                                                             "rank")}})
            elif "error" in r:
                e = {"t": r.get("t", 0), "rank": rank,
                     "site": r.get("name"), "step": r.get("step"),
                     "error": r["error"]}
                if r.get("batch_index") is not None:
                    e["batch_index"] = r.get("batch_index")
                errors.append(e)
    for fn in names:
        m = _POSTMORTEM_FILE_RE.match(fn)
        if not m:
            continue
        rank = int(m.group(1))
        try:
            with open(os.path.join(event_dir, fn)) as f:
                pm = json.load(f)
        except (OSError, ValueError):
            continue
        entry = ranks.setdefault(rank, {"n_events": 0, "last_step": None,
                                        "last_event": None})
        err = pm.get("error")
        entry["postmortem"] = {"t": pm.get("t"), "error": err,
                               "site": pm.get("site"),
                               "step": pm.get("step"),
                               "batch_index": pm.get("batch_index")}
        if entry["last_step"] is None and pm.get("step") is not None:
            entry["last_step"] = pm.get("step")
        if err:
            msg = err.get("message", "") if isinstance(err, dict) else \
                str(err)
            typ = err.get("type", "") if isinstance(err, dict) else ""
            e = {"t": pm.get("t", 0), "rank": rank,
                 "site": pm.get("site"), "step": pm.get("step"),
                 "error": f"{typ}: {msg}"[:300].strip(": ")}
            if pm.get("batch_index") is not None:
                e["batch_index"] = pm.get("batch_index")
            errors.append(e)
    if heartbeat_dir:
        try:
            hb_names = os.listdir(heartbeat_dir)
        except OSError:
            hb_names = []
        for fn in hb_names:
            m = re.match(r"rank(\d+)\.hb$", fn)
            if not m:
                continue
            rank = int(m.group(1))
            hb = _read_heartbeat(os.path.join(heartbeat_dir, fn))
            if hb is not None:
                ranks.setdefault(rank, {"n_events": 0, "last_step": None,
                                        "last_event": None})
                ranks[rank]["heartbeat"] = hb
    merged.sort(key=lambda r: r.get("t", 0))
    # Tiering: a rank's restart event marks everything before it on that
    # rank (chaos, failed spans, postmortems of the recovered attempt) as
    # survived — only evidence AFTER the last restart is terminal. A
    # recovered error is narrative, never attribution: a hang (stall) on
    # another rank outranks it.
    terminal = [e for e in errors
                if e["t"] > last_restart.get(e["rank"], -1)]
    survived = recovered + [dict(e, recovered=True) for e in errors
                            if e["t"] <= last_restart.get(e["rank"], -1)]
    candidates = terminal or survived
    first_failure = min(candidates, key=lambda e: e["t"]) \
        if candidates else None

    def _last_activity(d) -> float | None:
        """Freshest evidence a rank was alive: last event OR heartbeat.
        Heartbeats matter — a rank whose event stream hit the size cap
        (or never streamed) keeps beating, and the stall heuristic must
        not blame it for having the oldest frozen trace."""
        le = d.get("last_event") or {}
        hb = d.get("heartbeat") or {}
        cands = [x for x in (le.get("t"), hb.get("time"), hb.get("mtime"))
                 if isinstance(x, (int, float))]
        return max(cands) if cands else None

    stalled = None
    activity = {r: _last_activity(d) for r, d in ranks.items()}
    activity = {r: t for r, t in activity.items() if t is not None}
    if activity:
        stalled = min(activity, key=activity.get)
    # Rank attribution: terminal evidence wins; with only recovered
    # evidence the STALL heuristic wins (the gang died of something the
    # recovered rank already survived — blame whoever went quiet first);
    # a recovered rank is named only when it is also the only signal.
    if terminal:
        first_failing = first_failure["rank"]
    elif stalled is not None:
        first_failing = stalled
    else:
        first_failing = first_failure["rank"] if first_failure else None
    degradations.sort(key=lambda d: d.get("t", 0))
    return {
        "ranks": {str(r): ranks[r] for r in sorted(ranks)},
        "first_failing_rank": first_failing,
        "first_failure": first_failure,
        "first_stalled_rank": stalled,
        "degradations": degradations[-50:],
        "events": merged[-max_events:],
    }


def collect_degradations(event_dir: str) -> list[dict]:
    """Degradation events (``retry``/``quarantine``/``checkpoint_rollback``/
    ``checkpoint_quarantine``) from every rank's stream tail — the gang
    supervisor's SUCCESS path reads these so a run that recovered by
    rolling back a corrupt checkpoint or retrying a flaky dispatch
    reports what it survived instead of looking pristine."""
    out: list[dict] = []
    try:
        names = sorted(os.listdir(event_dir))
    except OSError:
        return out
    for fn in names:
        if not _EVENT_FILE_RE.match(fn):
            continue
        try:
            recs, _ = _read_jsonl_tail(os.path.join(event_dir, fn))
        except OSError:
            continue
        out.extend(r for r in recs
                   if r.get("name") in _DEGRADATION_EVENTS)
    out.sort(key=lambda r: r.get("t", 0))
    return out


def format_timeline(tl: dict) -> str:
    """Compact human rendering for the GangFailure message."""
    lines = []
    ff = tl.get("first_failure")
    stalled = tl.get("first_stalled_rank")
    if ff is not None and not ff.get("recovered"):
        lines.append(
            f"gang timeline: first failure on rank {ff['rank']} at "
            f"site {ff.get('site') or '?'}"
            + (f" step {ff['step']}" if ff.get("step") is not None else "")
            + (f" batch {ff['batch_index']}"
               if ff.get("batch_index") is not None else "")
            + (f" ({ff['error']})" if ff.get("error") else ""))
    elif stalled is not None:
        line = (f"gang timeline: no terminal error recorded; rank "
                f"{stalled} stalled first")
        if ff is not None:  # recovered narrative rides as context only
            line += (f" (earlier error on rank {ff['rank']} was "
                     f"recovered in-process: {ff.get('error')})")
        lines.append(line)
    elif ff is not None:
        lines.append(
            f"gang timeline: only recovered errors on record — rank "
            f"{ff['rank']} at site {ff.get('site') or '?'}"
            + (f" ({ff['error']})" if ff.get("error") else ""))
    degr = tl.get("degradations") or []
    if degr:
        kinds = collections.Counter(d.get("kind") for d in degr)
        lines.append(
            "  survived degradations: "
            + ", ".join(f"{k} x{n}" for k, n in sorted(kinds.items())))
    for r, d in tl.get("ranks", {}).items():
        le = d.get("last_event") or {}
        hb = d.get("heartbeat") or {}
        lines.append(
            f"  rank {r}: last_step={d.get('last_step')} "
            f"last_event={le.get('name')} events={d.get('n_events')}"
            + (f" heartbeat_step={hb.get('step')}" if hb else ""))
    return "\n".join(lines)


def write_gang_postmortem(event_dir: str, tl: dict) -> str:
    """Atomically write the merged timeline next to the per-rank files."""
    return atomic_write_json(os.path.join(event_dir, GANG_TIMELINE_FILE), tl)
