"""Checkpointable training data plane (ISSUE 5 tentpole).

PR 4 made the *scoring* data plane fault-tolerant; this module does the
same for training. The gap it closes: ``fit()`` used to stream an opaque
iterator, so a mid-loop failure dropped the batches the feed had
already drawn, a restart replayed the stream from wherever the caller's
iterator happened to sit, and a deterministic poison batch death-looped
the supervisor through its whole restart budget.

A :class:`CheckpointableDataset` is a *replayable* batch source with a
tiny JSON-able cursor:

- ``state()`` → ``{"epoch": E, "batch_index": B, "skip_list": [...]}`` —
  the position *before* the next batch to draw (plus adapter extras such
  as ``shuffle_seed``).
- ``restore(state)`` — reposition so the next drawn batch is exactly
  ``(E, B)``; the skip-list is unioned in.
- ``indexed()`` — the iterator ``fit()`` consumes: yields
  ``(cursor_after, batch)`` pairs, where ``cursor_after`` is the state to
  restore to in order to replay everything *after* this batch. ``fit()``
  persists the cursor of the last batch consumed by a **completed** step
  into the checkpoint manifest (``CheckpointManager.save(...,
  data_cursor=)``), so a batch drawn for a step that never completed is
  replayed on restart, never dropped.

Iteration is deterministic by contract: the same epoch must yield the
same batches in the same order on every pass (lists and Arrow frames are
naturally so; generator factories must be seeded). Under a multi-process
gang, ``shard=True`` opts a dataset into GLOBAL-stream iteration — every
rank draws the same batches and row-slices its contiguous local shard —
so batch indices, the cursor, and the skip-list describe the whole gang;
the default keeps ``fit()``'s existing contract (``data`` yields
already-LOCAL shards, batch indices then count the local stream, which
is position-identical across ranks for a deterministically partitioned
source).

The **skip-list** is the poison-batch quarantine: indices on it are
consumed (they keep their position in the stream) but never yielded —
and never *examined*: skipped values are discarded untouched, so
adapters can defer the dangerous work past the skip check
(``ArrowDataset`` only decodes unskipped indices, making decode-poisons
skippable). A poison the source ITSELF raises on while drawing (a
non-seekable generator dying mid-iteration) cannot be skipped at any
layer; the supervisor detects a skip-listed batch that still kills the
gang and fails fast instead of re-quarantining. ``launcher.supervise``
grows the skip-list across restarts via the ``SPARKDL_SKIP_BATCHES``
env var when consecutive gang failures are attributed to the same
batch, bounded by ``SPARKDL_MAX_SKIPPED_BATCHES`` (fatal
:class:`~sparkdl_tpu.runner.failures.PoisonDataError` past it).

With ``SPARKDL_BATCH_LEDGER`` set to a directory, ``fit()`` appends one
JSON line per step (``{"step", "epoch", "batch_index", "skip_list"}``)
to ``ledger_rank{i}.jsonl`` — written when the step is RETIRED (its
metrics have arrived: one step behind the dispatch, and always before a
checkpoint that holds the step is saved), so every checkpointed step is
on record, a step that diverged is too, and a step whose attempt later
dies is superseded by its replay entry: audit by LAST entry per step,
with each entry's skip_list giving the remap context. That is exactly what the
exactly-once smoke (``scripts/train_resume_smoke.py``) asserts: across
all restart attempts every step maps to the same batch (deterministic
replay, modulo batches quarantined in between) and the final step→batch
mapping consumes every batch exactly once, except quarantined ones.

Import surface: stdlib + numpy (worker-side only; the jax-free
supervisor never needs this module).
"""

from __future__ import annotations

import inspect
import json
import logging
import os
import time
from typing import Any, Callable, Iterable, Iterator

from . import chaos, events

__all__ = ["CheckpointableDataset", "ListDataset", "FactoryDataset",
           "ArrowDataset", "as_dataset", "env_skip_list", "append_ledger",
           "read_ledger", "record_batch_to_numpy", "SKIP_ENV", "LEDGER_ENV"]

log = logging.getLogger("sparkdl_tpu.runner")

SKIP_ENV = "SPARKDL_SKIP_BATCHES"
LEDGER_ENV = "SPARKDL_BATCH_LEDGER"


def _tag_batch(exc: BaseException, epoch: int, batch_index: int):
    """Attach the (epoch, batch_index) being drawn when ``exc`` was
    raised; ``fit``'s postmortem prefers this over the last staged
    batch's cursor, so draw-time failures are attributed exactly."""
    try:
        exc._sparkdl_batch_epoch = epoch
        exc._sparkdl_batch_index = batch_index
    except Exception:
        pass  # exceptions with __slots__: lose the tag, not the raise


class CheckpointableDataset:
    """Base class: deterministic, restartable, skip-list-aware batch source.

    Subclasses implement :meth:`_epoch_iter` — a FRESH iterator over one
    epoch's batches, identical on every call with the same ``epoch`` (this
    is what makes restart replay exact). ``epochs=None`` loops forever;
    ``epochs=k`` stops after k passes.

    ``shard=True`` opts into per-rank row sharding: the dataset yields
    the GLOBAL batch stream and each rank slices its contiguous row
    share, so one cursor and one skip-list describe the whole gang. The
    default (``False``) preserves ``fit()``'s existing gang contract —
    under a multi-process launch, ``data`` yields batches that are
    ALREADY this rank's local shard — so pre-existing callers are never
    silently re-sliced. With ``shard=True`` the global batch's leading
    dim should be at least the process count (remainder rows are cropped
    so every rank keeps an equal leading dim); non-sliceable leaves
    (scalars, 0-d arrays) pass through untouched.
    """

    def __init__(self, epochs: int | None = 1, shard: bool = False,
                 skip_list: Iterable[int] | None = None):
        self.epochs = epochs
        self.skip_list: set[int] = {int(i) for i in (skip_list or ())}
        self._epoch = 0
        self._start_index = 0  # next in-epoch batch index to draw
        self._shard = shard

    # -- subclass contract -------------------------------------------------
    def _epoch_iter(self, epoch: int) -> Iterator[Any]:
        raise NotImplementedError

    # -- cursor ------------------------------------------------------------
    def state(self) -> dict:
        """Small JSON-able cursor: position before the next batch to draw."""
        return {"epoch": self._epoch, "batch_index": self._start_index,
                "skip_list": sorted(self.skip_list)}

    def restore(self, state: dict):
        """Reposition iteration at ``state`` (union its skip-list in).
        Call before :meth:`indexed` — a live iterator is not rewound."""
        self._epoch = int(state.get("epoch", 0))
        self._start_index = int(state.get("batch_index", 0))
        self.extend_skip(state.get("skip_list") or ())

    def extend_skip(self, indices: Iterable[int]):
        self.skip_list.update(int(i) for i in indices)

    # -- iteration ---------------------------------------------------------
    def indexed(self) -> Iterator[tuple[dict, Any]]:
        """Yield ``(cursor_after, batch)``: the batch plus the state that
        replays everything after it. Fast-forward past an earlier restore
        point is draw-and-discard (adapters with random access may
        override :meth:`_epoch_iter` to seek); skip-listed indices are
        consumed but not yielded (a ``train_batch_skipped`` event marks
        each), and the ``data_fetch`` chaos site fires per drawn batch
        with the batch index, so a poison fault can target one batch
        deterministically across restarts."""
        epoch, start = self._epoch, self._start_index
        while self.epochs is None or epoch < self.epochs:
            drew = 0
            it = enumerate(self._epoch_iter(epoch))
            while True:
                try:
                    idx, batch = next(it)
                except StopIteration:
                    break
                except BaseException as e:
                    # A draw-time failure (decode error in the source) is
                    # attributable to the batch being drawn — tag it so
                    # fit's postmortem names THIS index, not the previous
                    # step's batch (which the supervisor would then
                    # wrongly quarantine). The failing index == number of
                    # draws so far: enumerate counts every draw from 0,
                    # fast-forward included.
                    _tag_batch(e, epoch, drew)
                    raise
                drew += 1
                if idx < start:
                    continue
                self._epoch, self._start_index = epoch, idx + 1
                if idx in self.skip_list:
                    events.event("train_batch_skipped", epoch=epoch,
                                 batch_index=idx)
                    continue
                try:
                    batch = chaos.fire("data_fetch", step=idx, batch=batch)
                except BaseException as e:
                    _tag_batch(e, epoch, idx)
                    raise
                yield ({"epoch": epoch, "batch_index": idx + 1,
                        "skip_list": sorted(self.skip_list)},
                       self._shard_rows(batch))
            if not drew:
                return  # empty epoch: a looping source must not spin
            epoch, start = epoch + 1, 0
            self._epoch, self._start_index = epoch, 0

    def __iter__(self) -> Iterator[Any]:
        return (batch for _, batch in self.indexed())

    # -- per-rank sharding (opt-in: shard=True) ----------------------------
    def _shard_rows(self, batch):
        world = int(os.environ.get("SPARKDL_NUM_PROCESSES", "1"))
        if not self._shard or world <= 1:
            return batch
        rank = int(os.environ.get("SPARKDL_PROCESS_ID", "0"))

        def cut(x):
            if isinstance(x, dict):
                return {k: cut(v) for k, v in x.items()}
            if isinstance(x, (list, tuple)):
                return type(x)(cut(v) for v in x)
            try:
                per = len(x) // world
            except TypeError:
                return x  # scalar / 0-d leaf: replicate, don't crash
            return x[rank * per:(rank + 1) * per]

        return cut(batch)


class ListDataset(CheckpointableDataset):
    """In-memory list of batches. ``shuffle_seed`` reshuffles per epoch
    with a deterministic permutation (``RandomState(seed + epoch)``), so
    restore replays the identical order; the seed rides in the cursor for
    auditability.

    Skip-list caveat under per-epoch reshuffle: skip indices are
    STREAM POSITIONS, stable within any one epoch (restart replay —
    including the quarantine flow, which resumes into the failing epoch —
    is exact) but mapping to a different underlying batch each epoch. A
    quarantined poison record therefore re-enters in later epochs at a
    new position (the supervisor spends another quarantine slot on it)
    while its old position shields an innocent batch. Keep
    quarantine-critical runs on a stable order (no ``shuffle_seed``, or
    ``epochs=1``); a warning logs when the two are combined."""

    def __init__(self, batches: list, epochs: int | None = 1,
                 shuffle_seed: int | None = None, **kw):
        super().__init__(epochs=epochs, **kw)
        self._batches = list(batches)
        self.shuffle_seed = shuffle_seed
        self._warned_shuffle_skip = False
        self._warn_shuffle_skip()

    def extend_skip(self, indices: Iterable[int]):
        # The hazard check lives HERE, not only in __init__: in the real
        # quarantine flow skips arrive after construction (fit() applies
        # SPARKDL_SKIP_BATCHES / the restored cursor via extend_skip).
        super().extend_skip(indices)
        self._warn_shuffle_skip()

    def _warn_shuffle_skip(self):
        if self._warned_shuffle_skip or self.shuffle_seed is None \
                or self.epochs == 1 or not self.skip_list:
            return
        self._warned_shuffle_skip = True
        log.warning(
            "ListDataset: skip-list positions are per-epoch; with "
            "shuffle_seed and multiple epochs a skipped position "
            "shields a different batch each epoch (see docstring)")

    def _epoch_iter(self, epoch: int) -> Iterator[Any]:
        order: Iterable[int] = range(len(self._batches))
        if self.shuffle_seed is not None:
            import numpy as np
            order = np.random.RandomState(
                (self.shuffle_seed + epoch) % (2 ** 32)).permutation(
                    len(self._batches))
        return (self._batches[int(i)] for i in order)

    def state(self) -> dict:
        d = super().state()
        if self.shuffle_seed is not None:
            d["shuffle_seed"] = self.shuffle_seed
        return d

    def restore(self, state: dict):
        # The cursor's positions are only meaningful under the SAME
        # permutation schedule: a seed mismatch (script edited between
        # runs) would replay a different order under a CRC-valid cursor —
        # record it like an unverifiable cursor instead of silently
        # training some batches twice and others never.
        saved = state.get("shuffle_seed")
        if saved is not None and saved != self.shuffle_seed:
            log.warning(
                "ListDataset.restore: cursor was saved with "
                "shuffle_seed=%s but this dataset uses %s — positions "
                "map to different batches; restoring anyway, on record",
                saved, self.shuffle_seed)
            events.event("unverified_data_cursor",
                         reason=f"shuffle_seed mismatch: cursor has "
                                f"{saved}, dataset has {self.shuffle_seed}")
        super().restore(state)


class FactoryDataset(CheckpointableDataset):
    """Wrap a generator *factory*: ``factory()`` (or ``factory(epoch)``
    when the callable takes an argument) returns a fresh batch iterator
    per epoch. The factory must be deterministic — same epoch, same
    batches — or restart replay silently trains on different data."""

    def __init__(self, factory: Callable, epochs: int | None = 1, **kw):
        super().__init__(epochs=epochs, **kw)
        self._factory = factory
        try:
            # Epoch-aware = a REQUIRED positional param; a defaulted one
            # (lambda n=100: ...) is configuration, and silently passing
            # the epoch number as n would e.g. make epoch 0 an empty
            # epoch and end the dataset at step 0.
            params = [
                p for p in inspect.signature(factory).parameters.values()
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
                and p.default is inspect.Parameter.empty]
            self._epoch_aware = len(params) >= 1
        except (TypeError, ValueError):
            self._epoch_aware = False

    def _epoch_iter(self, epoch: int) -> Iterator[Any]:
        it = self._factory(epoch) if self._epoch_aware else self._factory()
        return iter(it)


def record_batch_to_numpy(rb) -> dict:
    """Arrow RecordBatch → ``{column: numpy array}`` (the host-batch shape
    ``fit()`` consumes). Numeric columns convert zero-copy where Arrow
    allows; nested list columns fall back through ``to_pylist`` (2-D when
    rectangular)."""
    import numpy as np
    out = {}
    for name, col in zip(rb.schema.names, rb.columns):
        try:
            arr = col.to_numpy(zero_copy_only=False)
        except Exception:
            arr = np.asarray(col.to_pylist())
        if getattr(arr, "dtype", None) is not None and arr.dtype == object:
            arr = np.asarray(col.to_pylist())
        out[name] = arr
    return out


class ArrowDataset(CheckpointableDataset):
    """Adapter over ``DataFrame.iterBatches(batch_size)`` — the scorer's
    feeder input becomes a checkpointable trainer input. ``convert``
    (default :func:`record_batch_to_numpy`) maps each RecordBatch to the
    host-numpy batch dict the step function expects."""

    def __init__(self, df, batch_size: int, convert: Callable | None = None,
                 epochs: int | None = 1, **kw):
        super().__init__(epochs=epochs, **kw)
        self._df = df
        self._batch_size = int(batch_size)
        self._convert = convert or record_batch_to_numpy

    def _epoch_iter(self, epoch: int) -> Iterator[Any]:
        # Skip-listed indices yield the RAW RecordBatch, never converted:
        # indexed() discards skipped values unexamined, so a record whose
        # DECODE is the poison is skippable without touching it (a poison
        # the underlying iterBatches itself raises on remains unskippable
        # — no source seek — and the supervisor then fails fast instead
        # of re-quarantining; see launcher.supervise).
        return (rb if i in self.skip_list else self._convert(rb)
                for i, rb in enumerate(
                    self._df.iterBatches(self._batch_size)))


def as_dataset(data) -> CheckpointableDataset | None:
    """Coerce ``fit(data=...)``'s argument to a checkpointable dataset.

    - a :class:`CheckpointableDataset` passes through;
    - a callable becomes a :class:`FactoryDataset` (one deterministic
      epoch per call);
    - a list/tuple of batches becomes a one-pass :class:`ListDataset`
      (identical batch sequence to the old ``iter(list)`` path, now with
      a cursor);
    - anything else (a bare generator/iterator — consumable once, not
      replayable) returns None: ``fit`` keeps the legacy uncursored path.
    """
    if isinstance(data, CheckpointableDataset):
        return data
    if callable(data):
        return FactoryDataset(data)
    if isinstance(data, (list, tuple)):
        return ListDataset(list(data))
    return None


def env_skip_list(environ=None) -> list[int]:
    """Decode ``SPARKDL_SKIP_BATCHES`` (JSON int list, the supervisor→
    worker quarantine transport). Malformed values log and return [] —
    a bad env var must degrade to no-skip, not kill the worker."""
    text = (environ if environ is not None else os.environ).get(SKIP_ENV)
    if not text:
        return []
    try:
        return [int(i) for i in json.loads(text)]
    except (ValueError, TypeError):
        log.warning("ignoring unparseable %s=%r", SKIP_ENV, text)
        return []


def append_ledger(step: int, cursor: dict | None):
    """Batch-id ledger: one JSON line per RETIRED step (``fit`` runs one
    step ahead of the chip and ledgers a step when its metrics have
    arrived, before the divergence guard reads them: a diverged step is
    on record, the step dispatched after it is not; the replayed attempt
    supersedes an entry, so audits take the last entry per step).
    Append-mode: survives SIGKILL up to the last retired step and
    accumulates ACROSS restart attempts (the exactly-once audit needs all
    lineages).
    No-op unless ``SPARKDL_BATCH_LEDGER`` names a directory.

    Each line carries the WORLD SIZE in force when the batch was drawn
    (ISSUE 16): an elastic resize shows up in the ledger as the ``world``
    column changing mid-run, so the exactly-once audit can see — not
    infer — where the gang shrank or grew. The cursor itself is
    world-size-agnostic (it tracks the GLOBAL batch stream; per-rank
    slices are cut at draw time from the live env), which is what makes
    replay at a different world size correct at all — but only for
    ``shard=True`` datasets over the global stream; per-rank *distinct*
    sources cannot be resharded and keep fixed-size semantics."""
    d = os.environ.get(LEDGER_ENV)
    if not d or cursor is None:
        return
    rank = os.environ.get("SPARKDL_PROCESS_ID", "0")
    try:
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"ledger_rank{rank}.jsonl"), "a") as f:
            f.write(json.dumps({
                "step": int(step),
                "epoch": cursor.get("epoch"),
                "batch_index": int(cursor.get("batch_index", 0)) - 1,
                # Skip-list in force when this batch was drawn: the audit
                # needs it to tell a legal remap (step S moved off a
                # batch that was quarantined in between) from a replay
                # divergence (the actual exactly-once violation).
                "skip_list": cursor.get("skip_list") or [],
                "world": int(os.environ.get("SPARKDL_NUM_PROCESSES", "1")),
                "t": round(time.time(), 3)}) + "\n")
    except OSError:
        pass  # a torn-down tmpdir must not kill the train loop


def read_ledger(directory: str, rank: int = 0) -> list[dict]:
    """Parse one rank's batch-id ledger (tests / the resume smoke)."""
    path = os.path.join(directory, f"ledger_rank{rank}.jsonl")
    out = []
    try:
        with open(path) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue  # torn tail line from a killed rank
    except OSError:
        pass
    return out
