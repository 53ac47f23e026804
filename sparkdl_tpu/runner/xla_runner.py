"""XlaRunner — the HorovodRunner replacement (SURVEY.md §3.5, §7.6).

Reference behavior: ``HorovodRunner(np=N).run(main_fn, **kwargs)`` pickled
``main_fn``, acquired N Spark executor slots in barrier mode, ``mpirun``-ed a
Python process per slot, and let Horovod's NCCL ring-allreduce average
gradients outside the TF graph.

TPU-native inversion: JAX is a *single-controller SPMD* system — one Python
process drives all local chips, and multi-host pods run the **same** program
per host with ``jax.distributed`` providing rendezvous. So ``run`` does not
fork N workers; it builds an N-device ``jax.sharding.Mesh``, hands ``main_fn``
a :class:`RunnerContext`, and the "allreduce" happens *inside* the compiled
train step as an XLA collective riding ICI (see ``train_state.py``). The
``np=N`` API shape is preserved for migration; ``np=-1`` means all devices.

Multi-host: pass ``coordinator="host:port", num_processes=H, process_id=i``
(or set the standard TPU pod env) and each host calls ``run`` with the same
program — ``jax.distributed.initialize`` does the rendezvous that mpirun did,
DCN carries the cross-host legs of the collectives, ICI the intra-slice legs.
"""

from __future__ import annotations

import functools
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, NamedTuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import runtime
from . import analysis as analysis_lib
from . import chaos
from . import data as data_lib
from . import events
from . import metrics as metrics_lib
from . import sentinel as sentinel_lib
from . import telemetry as telemetry_lib
from .checkpoint import CheckpointManager
from .failures import TrainingDivergedError
from .train_state import (TrainState, make_eval_step, make_shard_map_step,
                          make_train_step)

log = logging.getLogger("sparkdl_tpu.runner")


class _Dispatched(NamedTuple):
    """A train step ``fit`` has dispatched and not yet retired."""
    index: int             # 0-based: the step's number is index + 1
    metrics: dict          # the step's metrics, device values
    examples: int          # global examples the step consumed
    cursor: dict | None    # data-plane position after the step's batch


_CURRENT_CONTEXT: list["RunnerContext"] = []
_DISTRIBUTED_INITIALIZED = False


def _maybe_init_distributed(coordinator: str | None,
                            num_processes: int | None,
                            process_id: int | None) -> None:
    """jax.distributed rendezvous — the mpirun/barrier-mode replacement.

    Explicit args win; otherwise the ``SPARKDL_*`` env set by
    ``runner.launcher`` is picked up, so worker scripts construct
    ``XlaRunner`` identically on 1 or N processes. Idempotent.
    """
    global _DISTRIBUTED_INITIALIZED
    if coordinator is None:
        coordinator = os.environ.get("SPARKDL_COORDINATOR")
        if coordinator:
            num_processes = int(os.environ["SPARKDL_NUM_PROCESSES"])
            process_id = int(os.environ["SPARKDL_PROCESS_ID"])
    if coordinator is None or _DISTRIBUTED_INITIALIZED:
        return
    # Cross-process CPU collectives need a real transport; gloo ships with
    # jaxlib. Set it unconditionally (it only affects CPU client creation,
    # harmless on TPU) — keying on the env var would miss runs where the
    # platform merely RESOLVES to cpu, and probing the resolved backend here
    # would initialize it before jax.distributed, which must come first.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    _DISTRIBUTED_INITIALIZED = True
    log.info("jax.distributed initialized: process %d/%d via %s",
             jax.process_index(), jax.process_count(), coordinator)


@dataclass
class RunnerContext:
    """What ``main_fn`` receives — the hvd.{rank,size,...} surface plus the
    mesh-first primitives the TPU design is actually built on."""
    mesh: Mesh
    data_axis: str = "data"
    checkpoint_dir: str | None = None
    log_dir: str | None = None
    _ckpt: CheckpointManager | None = field(default=None, repr=False)

    # -- hvd-compat identity --------------------------------------------
    @property
    def size(self) -> int:  # total chips (hvd.size ≈ world size)
        return self.mesh.devices.size

    @property
    def rank(self) -> int:  # process index (hvd.rank for the controller)
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    @property
    def local_device_count(self) -> int:
        return jax.local_device_count()

    # -- shardings -------------------------------------------------------
    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.data_axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_batch(self, batch):
        """Host numpy pytree → global array sharded over the data axis.

        Single-controller: ``batch`` is the GLOBAL batch, split across the
        mesh by ``device_put``. Multi-process SPMD: each process passes its
        LOCAL shard (HorovodRunner semantics — every rank loads its own
        slice) and the global array is assembled across processes; the
        leading dim must be equal on every process.
        """
        sh = self.data_sharding()
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sh), batch)

        def put(x):
            x = np.asarray(x)
            global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
            return jax.make_array_from_process_local_data(
                sh, x, global_shape=global_shape)

        return jax.tree_util.tree_map(put, batch)

    def put_replicated(self, tree):
        """Host pytree → arrays replicated over the (global) mesh; works
        under both single-controller and multi-process (where plain
        ``device_put`` would reject non-addressable devices)."""
        rep = self.replicated()
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(np.asarray(x), rep), tree)

        def put(x):
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                rep, x, global_shape=x.shape)

        return jax.tree_util.tree_map(put, tree)

    # -- compiled steps ---------------------------------------------------
    def make_train_step(self, loss_fn, explicit_collectives: bool = False,
                        **kw):
        maker = make_shard_map_step if explicit_collectives else make_train_step
        return maker(loss_fn, self.mesh, data_axis=self.data_axis, **kw)

    def make_eval_step(self, eval_fn):
        return make_eval_step(eval_fn, self.mesh, data_axis=self.data_axis)

    # -- aux subsystems ----------------------------------------------------
    @property
    def checkpoints(self) -> CheckpointManager | None:
        if self._ckpt is None and self.checkpoint_dir:
            self._ckpt = CheckpointManager(self.checkpoint_dir)
        return self._ckpt

    def _close_checkpoints(self):
        """Error-path cleanup (ISSUE 4 satellite): close the manager
        exactly once — ``CheckpointManager.close`` is idempotent and
        finalizes any in-flight async save + its manifest; dropping the
        cached instance lets the property re-open for a retry on the
        same context."""
        ckpt, self._ckpt = self._ckpt, None
        if ckpt is not None:
            try:
                ckpt.close()
            except Exception:
                log.warning("checkpoint close on error path failed",
                            exc_info=True)

    def trace(self, log_dir: str | None = None):
        # metrics.trace emits the flight-recorder event carrying the trace
        # dir, so a postmortem's event tail links to the profile on disk.
        return metrics_lib.trace(log_dir or (self.log_dir or "/tmp/sparkdl_tb"))

    def meter(self, warmup_steps: int = 1) -> metrics_lib.ThroughputMeter:
        return metrics_lib.ThroughputMeter(n_chips=self.size,
                                           warmup_steps=warmup_steps)

    # -- batteries-included training loop ---------------------------------
    def fit(self, *, loss_fn: Callable, params: Any, tx,
            data: Iterable, num_steps: int,
            apply_fn: Callable | None = None,
            model_state: Any = None, mutable: bool = False,
            with_rng: bool = False,
            eval_fn: Callable | None = None, eval_data: Iterable | None = None,
            eval_every: int = 0, checkpoint_every: int = 0,
            log_every: int = 10, explicit_collectives: bool = False,
            resume: bool = True, profile_dir: str | None = None,
            remat: bool = False, accum_steps: int = 1,
            flops_per_step: float | None = None) -> dict:
        """Run a full training loop; returns {state, meter, history}.

        Streams ``data``, shards each batch over the data axis, runs the
        compiled step, meters examples/s/chip, checkpoints every
        ``checkpoint_every`` steps, and resumes from the latest checkpoint
        when ``resume`` and one exists — the checkpoint-and-restart
        failure-recovery story (SURVEY.md §5.3).

        ``data`` may be a bare iterator of host-numpy batch dicts (the
        original contract), or — for **exactly-once resume semantics** — a
        :class:`~sparkdl_tpu.runner.data.CheckpointableDataset`, a list of
        batches, or a generator *factory* (``data_lib.as_dataset``
        coerces). With a dataset, the loop threads a data **cursor**: each
        checkpoint manifest records the position after the last batch
        consumed by a *completed* step, resume restores the dataset there
        (a legacy manifest without a cursor records an
        ``unverified_data_cursor`` degradation and starts the dataset from
        its current position), the supervisor-grown skip-list
        (``SPARKDL_SKIP_BATCHES``) is honored, and with
        ``SPARKDL_BATCH_LEDGER`` set every completed step appends its
        ``(step, epoch, batch_index)`` to a batch-id ledger.

        A tail batch skipped/cropped by ``accum_steps`` alignment does not
        consume a step slot: the loop draws a replacement batch, so it
        always runs ``num_steps`` steps when the data suffices (before
        round 5 a skipped batch silently burned its step).

        The loop is flight-recorded (``runner.events``): per-step
        ``data_fetch``/``shard_put``/``step_compute`` spans (``step_compute``
        is the host's time to DISPATCH the step, not the step's compute), a
        ``step_retire`` span each step (below), a ``loss_fetch`` span around
        the conversion of a ``log_every`` boundary step's metrics to floats,
        checkpoint and eval spans, a ``compile`` event from
        first-step timing, and — on
        any failure — a crash postmortem carrying the last events plus the
        exception. Ring-buffer only (no I/O, no host sync) unless
        ``SPARKDL_EVENT_DIR`` is set. ``flops_per_step`` (GLOBAL FLOPs per
        step) feeds the meter's MFU; leave None and set
        ``SPARKDL_MFU_ESTIMATE=1`` to ask XLA's cost analysis instead (one
        extra host-side trace at startup).

        The loop runs ONE step ahead of the chip, whatever ``log_every``
        is: after dispatching step n+1 it *retires* step n — waits for
        step n's metrics under a ``step_retire`` span (``dur_s`` = how long
        the host waited for the chip: about a step when the chip sets the
        pace, about nothing when ``data`` does) and only then meters it
        and, at a log boundary, logs it. When the wait returns step n+1 is
        running, so the host has a whole device step to fetch, put and
        dispatch step n+2, and the device queue never empties; two or
        three batches sit in HBM, not ``log_every`` of them. ``history``
        and the logged values are those of a loop that synced at each
        boundary; an entry appears one iteration later, and a divergence
        is raised one iteration later, naming the step (and, at
        ``log_every=1``, the batch) that produced it. A checkpoint never
        runs ahead: the step it holds is retired (ledgered, metered,
        logged) before the save, which syncs on that step anyway.
        """
        state = TrainState.create(apply_fn or (lambda p, x: p), params, tx,
                                  model_state=model_state)
        # Exactly-once data plane (ISSUE 5): replayable sources get a
        # cursor threaded through checkpoints; bare iterators keep the
        # legacy uncursored contract.
        dataset = data_lib.as_dataset(data)
        if dataset is not None:
            dataset.extend_skip(data_lib.env_skip_list())
        start_step = 0
        if resume and self.checkpoints and \
                self.checkpoints.latest_step() is not None:
            # mesh = the CURRENT layout: restore's topology guard compares
            # it against the manifest's save-time topology and — elastic
            # (SPARKDL_ELASTIC=1) — reshards through a host template when
            # the gang shrank/grew; the host leaves are replicated below
            # by put_replicated exactly like a fresh start.
            state = self.checkpoints.restore(state, mesh=self.mesh)
            start_step = int(state.step)
            cursor = None
            if dataset is not None and start_step > 0:
                # data_cursor() records the unverified_data_cursor
                # degradation itself when the manifest carries none.
                cursor = self.checkpoints.data_cursor(start_step)
                if cursor is not None:
                    dataset.restore(cursor)
            # A resume is survived-failure narrative (the gang timeline's
            # "restart-resume" degradation), never failure evidence.
            events.event("train_resume", step=start_step,
                         batch_index=(cursor or {}).get("batch_index"),
                         epoch=(cursor or {}).get("epoch"),
                         verified_cursor=cursor is not None)
            log.info("resumed from checkpoint at step %d%s", start_step,
                     f" (data cursor {cursor})" if cursor else "")
        # Replicate state over the mesh: fresh params arrive on one device
        # (and orbax restores there too); the sharded batch needs the state
        # addressable on every mesh device.
        state = self.put_replicated(state)

        make_step = functools.partial(
            self.make_train_step, loss_fn,
            explicit_collectives=explicit_collectives, mutable=mutable,
            with_rng=with_rng, remat=remat, accum_steps=accum_steps)
        step_fn = make_step()
        meter = self.meter()
        meter.flops_per_step = flops_per_step
        estimate_flops = (flops_per_step is None
                          and _env_flag("SPARKDL_MFU_ESTIMATE"))
        logger = metrics_lib.MetricsLogger(self.log_dir)
        # Live telemetry plane (ISSUE 6): env-armed, ≈ free when
        # SPARKDL_METRICS_DIR/PORT are unset (two dict lookups).
        telemetry_lib.maybe_start_from_env()
        # Online anomaly sentinel (ISSUE 17): same env-armed, ≈-free-when-
        # off posture — step times feed it via ThroughputMeter.update.
        sentinel_lib.maybe_arm_from_env()
        events.event("fit_start", start_step=start_step,
                     num_steps=num_steps, n_chips=self.size)
        eval_step = self.make_eval_step(eval_fn) if eval_fn else None
        history: list[dict] = []

        # The feed is (cursor_after | None, batch) pairs: the cursor rides
        # WITH its batch through crop and staging, so the step that
        # consumes the batch knows where the data plane stood after it.
        if dataset is not None:
            data_it = dataset.indexed()
        else:
            data_it = ((None, b) for b in iter(data))

        def _crop(batch):
            """accum tail-crop; None = skip this batch entirely."""
            if accum_steps > 1:
                # A ragged tail batch can't split into k equal
                # microbatches — crop to the largest size that keeps
                # micro_split's shard-aligned fast path: the GLOBAL
                # batch (this LOCAL shard x num_processes, which is
                # what jit sees) must divide accum_steps x the mesh
                # DATA-axis size (the data axis can differ from
                # local_device_count on TP meshes and spans all
                # processes; this subsumes plain shardability). Per
                # LOCAL shard that's accum_steps x the axis's
                # per-process extent. Dropping leftover rows beats
                # aborting the run at its last step.
                axis = int(self.mesh.shape[self.data_axis])
                div = accum_steps * max(
                    1, axis // self.num_processes)
                lead = len(jax.tree_util.tree_leaves(batch)[0])
                keep = (lead // div) * div
                if keep == 0:
                    log.warning(
                        "skipping tail batch of %d rows (< "
                        "accum_steps x per-process data extent = %d)",
                        lead, div)
                    return None
                if keep != lead:
                    log.warning(
                        "cropping tail batch %d -> %d rows for "
                        "accum_steps=%d x per-process data extent %d",
                        lead, keep, accum_steps, div // accum_steps)
                    batch = jax.tree_util.tree_map(
                        lambda x: x[:keep], batch)
            return batch

        def _staged(limit: int):
            """(local_rows, sharded_batch, cursor_after) stream: crop
            applied, at most ``limit`` batches drawn from ``data_it`` and
            nothing pulled past that cap (checked BEFORE each next()): a
            reused bare iterator sits exactly ``limit`` produced batches
            on when the loop ends."""
            produced = 0
            while produced < limit:
                try:
                    # The span closes on StopIteration too, marking
                    # end_of_data in the trace before the except
                    # swallows it (PEP 479: it must not escape here).
                    with events.span("data_fetch",
                                     step=start_step + produced):
                        cur, batch = next(data_it)
                except StopIteration:
                    return
                batch = _crop(batch)
                if batch is None:
                    continue
                batch = chaos.fire("batch_fetch",
                                   step=start_step + produced,
                                   batch=batch)
                produced += 1
                leaves = jax.tree_util.tree_leaves(batch)
                n = len(leaves[0])
                # rows/bytes ride the span so the stage accountant's
                # bytes-moved ledger covers the training feed too.
                nbytes = sum(getattr(x, "nbytes", 0) for x in leaves)
                with events.span("shard_put", rows=n, bytes=nbytes):
                    sharded = self.shard_batch(batch)
                yield n, sharded, cur

        staged_it = _staged(num_steps - start_step)
        if profile_dir:
            metrics_lib.start_profiler_trace(profile_dir)
        last_m = None
        i = start_step
        failed = False
        # Data-plane position of the step being processed / last
        # completed: cur_cursor names the in-flight batch (postmortem
        # attribution — the supervisor's poison-batch quarantine keys on
        # it), last_cursor the one a completed step consumed (what the
        # checkpoint manifest persists).
        cur_cursor: dict | None = None
        last_cursor: dict | None = None
        # The loop's run-ahead, depth 1: the one dispatched step not yet
        # retired.
        in_flight: _Dispatched | None = None

        def _retire(d: _Dispatched | None):
            """Wait for step ``d``'s metrics (never its state: that was
            donated to the next step), ledger and meter it, and at a log
            boundary convert, guard, log and record it. None: nothing in
            flight. A failure here belongs to step ``d`` and the batch it
            carried, not to the loop's iteration, whose batch went into
            the step dispatched after it: the exception says so."""
            if d is None:
                return
            step = d.index + 1
            try:
                with events.span("step_retire", step=step):
                    jax.block_until_ready(d.metrics)
                # The step ran to its end: its batch-id ledger line when
                # SPARKDL_BATCH_LEDGER is set (the exactly-once audit
                # trail across restart attempts). Before the guard, so a
                # diverged step is on record, and never for the step
                # dispatched after it.
                data_lib.append_ledger(d.index, d.cursor)
                meter.update(d.examples)
                if step % log_every == 0 or step == num_steps:
                    # The step is finished: the span times the device→host
                    # copy of its scalars alone, once per boundary (the
                    # benchmark reads log_every off consecutive `step`s).
                    with events.span("loss_fetch", step=step,
                                     every=log_every):
                        m = {k: float(v) for k, v in d.metrics.items()}
                    _assert_finite_loss(m, step)
                    m["examples_per_sec_per_chip"] = \
                        meter.recent_examples_per_sec() / max(self.size, 1)
                    logger.log(step, m)
                    # the boundary's metrics in the ring too, the loss
                    # function's own counters among them: per-layer readers
                    # take them from there
                    events.event("step_metrics", step=step, **{
                        k: v for k, v in m.items()
                        if k not in events.RECORD_KEYS})
                    history.append({"step": step, **m})
            except BaseException as e:
                e._sparkdl_retiring = (d.index, d.cursor)
                raise

        try:
            for i in range(start_step, num_steps):
                # Cleared BEFORE anything this iteration can raise (the
                # step_start chaos hook included): if staging or the hook
                # raises, the postmortem must not inherit the PREVIOUS
                # step's batch (the supervisor would quarantine an
                # innocent batch and walk backwards through the dataset).
                # Draw-time failures carry their own exact index via the
                # dataset's exception tag instead.
                cur_cursor = None
                # Per-step fault-injection hook (no-op without a plan).
                chaos.fire("step_start", step=i)
                try:
                    n_local, sharded, cur_cursor = next(staged_it)
                except StopIteration:
                    break
                if estimate_flops:
                    estimate_flops = False
                    meter.flops_per_step = _estimate_step_flops(
                        step_fn, state, sharded)
                    events.event("flops_estimate",
                                 flops=meter.flops_per_step)
                # Multi-process: `data` yields LOCAL shards (shard_batch
                # contract) — the global step consumed n * process_count
                # examples, and per-chip rates divide by GLOBAL chip count.
                n = n_local * self.num_processes
                with metrics_lib.step_annotation(i), \
                        events.span("step_compute", step=i) as sp:
                    state, m = step_fn(state, sharded)
                if i == start_step:
                    # First-step wall time is dominated by XLA
                    # trace+compile (dispatch of a compiled step returns
                    # in microseconds) — record it as the compile cost.
                    events.event("compile", step=i,
                                 dur_s=round(sp.seconds, 6))
                    # What names the step's operations for whoever asks
                    # afterwards (analysis.step_program_scopes): how the step
                    # function is made, and the shapes and shardings every
                    # later step is dispatched with (the state this call
                    # returned). Not `step_fn` itself: the function keeps its
                    # executable loaded, and with it the program's scratch
                    # reserved on the chip, for as long as it lives.
                    analysis_lib.note_step_program(make_step, state, sharded)
                # Liveness beacon for the gang supervisor's hang watchdog
                # (no-op unless SPARKDL_HEARTBEAT_DIR is set). AFTER the
                # step call, not before it: a rank becomes watchdog-
                # eligible at its first beat, and the first step_fn call
                # blocks through XLA compilation — beating first would arm
                # the watchdog and then let a >watchdog_s compile read as
                # a hang, deterministically burning the restart budget.
                metrics_lib.touch_heartbeat(i)
                # Step i consumed its batch: the cursor to persist (it goes
                # with `state`, the newest step's, into a checkpoint).
                if cur_cursor is not None:
                    last_cursor = cur_cursor
                # last_m stays the NEWEST dispatched step's metrics (device
                # values): the finalize guard and the benchmark's driver,
                # which reads this frame, both want the newest step's loss.
                last_m = m
                # One step behind the dispatch: step i+1 is queued, so
                # waiting for step i here never empties the device queue.
                _retire(in_flight)
                in_flight = _Dispatched(i, m, n, cur_cursor)
                if checkpoint_every and self.checkpoints and \
                        (i + 1) % checkpoint_every == 0:
                    # A save takes the newest state, so the newest step is
                    # retired first: nothing is checkpointed that is not
                    # ledgered, metered and logged (a resume starts past
                    # it and would never replay it). The guard below syncs
                    # on this step anyway, so the retire costs nothing.
                    _retire(in_flight)
                    in_flight = None
                    # Divergence guard BEFORE the save: a NaN checkpoint
                    # would poison every subsequent resume (the host sync
                    # it costs rides the checkpoint's own sync cadence).
                    _assert_finite_loss(m, i + 1)
                    self.checkpoints.save(i + 1, state,
                                          data_cursor=last_cursor)
                if eval_step and eval_every and (i + 1) % eval_every == 0 \
                        and eval_data is not None:
                    with events.span("eval", step=i + 1):
                        evm = _run_eval(eval_step, state, eval_data,
                                        self.shard_batch)
                    logger.log(i + 1, {f"eval_{k}": v for k, v in evm.items()})
            # num_steps reached or the data ran out: retire the last step.
            _retire(in_flight)
        except BaseException as e:
            failed = True
            # Crash postmortem (ISSUE 2 tentpole): the ring tail + the
            # exception, flushed to SPARKDL_EVENT_DIR when set — the gang
            # supervisor merges these into its timeline. The marker keeps
            # outer handlers (run_with_restarts) from overwriting this
            # step-bearing record with a step-less one. batch_index names
            # the batch the failure is attributable to (ISSUE 5): two
            # successive gang failures attributed to the same
            # (step, batch_index) trigger the supervisor's poison-batch
            # quarantine — so attribution must be exact or absent, never
            # approximate (a wrong index quarantines good data). Draw-time
            # failures carry the dataset's exception tag; in-step failures
            # use the staged batch's cursor — EXCEPT a divergence detected
            # at a log_every > 1 boundary, where the NaN-producing batch
            # is anywhere in the window and naming the detection step's
            # batch would be a guess.
            # A failure raised while RETIRING a step carries that step's
            # index and cursor (`_retire`'s tag), not iteration i's.
            at, at_cursor = getattr(e, "_sparkdl_retiring", (i, cur_cursor))
            bi = getattr(e, "_sparkdl_batch_index", None)
            ep = getattr(e, "_sparkdl_batch_epoch", None)
            if bi is None and at_cursor is not None and not (
                    isinstance(e, TrainingDivergedError)
                    and log_every != 1):
                bi = at_cursor["batch_index"] - 1
                ep = at_cursor.get("epoch")
            events.postmortem(e, site="fit", step=at,
                              batch_index=bi, epoch=ep)
            # The dying rank's last telemetry snapshot is failure
            # evidence too (which stage was starving when the gang died)
            # — flush it next to the postmortem. No-op when disarmed.
            telemetry_lib.flush_snapshot()
            e._sparkdl_postmortemed = True
            raise
        finally:
            if profile_dir:
                # When the loop is already unwinding, a profiler-stop
                # failure must not replace the real training error (the
                # supervisor would classify the wrong exception); explicit
                # flag, not sys.exc_info() — fit() may itself be called
                # from inside a caller's except block.
                metrics_lib.stop_profiler_trace(failed)
            # Finalize in-flight async checkpoint saves even when the loop
            # is unwinding on a failure: the whole point of dying mid-run
            # is resuming from the last save, which must not be left
            # half-committed (latest_step would skip it and the restart
            # would silently redo checkpoint_every extra steps). On the
            # error path the manager is then CLOSED (exactly once —
            # close() is idempotent and subsumes the wait): the resumed
            # attempt opens its own.
            if failed:
                self._close_checkpoints()
            elif self._ckpt is not None:
                try:
                    self._ckpt.wait()
                except Exception:
                    log.warning("checkpoint finalize on exit failed",
                                exc_info=True)
        try:
            # Finalize under the same postmortem contract as the loop: on
            # async backends a step's error often only materializes at
            # this block_until_ready, and the divergence guard / final
            # save can raise too — "any failure path" includes the tail.
            jax.block_until_ready(state.params)
            if self.checkpoints:
                if last_m is not None:
                    _assert_finite_loss(last_m, int(state.step))
                self.checkpoints.save(num_steps, state, wait=True,
                                      data_cursor=last_cursor)
        except BaseException as e:
            events.postmortem(e, site="fit_finalize", step=i)
            e._sparkdl_postmortemed = True
            self._close_checkpoints()
            raise
        # Final telemetry: percentiles + MFU land in the logger (TB/text)
        # and the fit_end event, next to the per-step series.
        summary = meter.summary()
        logger.log_summary(num_steps, summary)
        events.event("fit_end", final_step=num_steps,
                     steps=meter.steps, mfu=summary.get("mfu"))
        # Exact-at-the-boundary snapshot (not one export interval stale):
        # the supervisor's gang aggregation reads this file.
        telemetry_lib.flush_snapshot()
        logger.close()
        return {"state": state, "meter": meter, "history": history}


def _env_flag(name: str) -> bool:
    """Boolean env knob: '1'/'true'/'yes' → on, everything else (incl. a
    user's SPARKDL_MFU_ESTIMATE=0) → off."""
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes")


def _estimate_step_flops(step_fn, state, sharded) -> float | None:
    """XLA's own FLOP count for one global step, from jit cost analysis
    (host-side retrace only — no device work, and deliberately NO
    ``lowered.compile()`` fallback: that would pay a full discarded AOT
    compile, doubling startup on big models and the window the gang
    watchdog must tolerate before the first heartbeat). None when the
    step isn't a jit function or the backend doesn't expose the estimate
    pre-compile; callers who know the step's FLOPs pass
    ``fit(flops_per_step=...)`` instead."""
    try:
        lowered = step_fn.lower(state, sharded)
        cost = lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return float(cost.get("flops", 0.0)) or None
    except Exception:
        log.debug("flops estimate unavailable", exc_info=True)
        return None


def _assert_finite_loss(m: dict, step: int):
    """The train-loop divergence guard (ISSUE 1 tentpole): a NaN/inf loss
    is the user's bug (or poisoned data) — fail fast as FATAL with the
    offending step instead of checkpointing garbage or letting the restart
    budget burn on a failure that will recur deterministically."""
    v = m.get("loss")
    if v is None:
        return
    v = float(v)  # device value at checkpoint boundaries: forces the sync
    if not np.isfinite(v):
        raise TrainingDivergedError(step, v)


def _run_eval(eval_step, state, eval_data, shard):
    totals: dict[str, float] = {}
    n = 0
    for batch in eval_data:
        m = eval_step(state, shard(batch))
        bs = len(jax.tree_util.tree_leaves(batch)[0])
        for k, v in m.items():
            totals[k] = totals.get(k, 0.0) + float(v) * bs
        n += bs
    return {k: v / max(n, 1) for k, v in totals.items()}


def current_context() -> RunnerContext | None:
    return _CURRENT_CONTEXT[-1] if _CURRENT_CONTEXT else None


class XlaRunner:
    """``XlaRunner(np=N).run(main_fn, **kwargs)`` — HorovodRunner, TPU-style.

    ``np``: number of chips to span (-1 = all visible). ``axes``: optional
    mesh axes dict (e.g. ``{"data": 4, "model": 2}``) for beyond-DP layouts;
    default is one ``data`` axis — the reference's only strategy.
    """

    def __init__(self, np: int = -1, axes: dict[str, int] | None = None,
                 checkpoint_dir: str | None = None,
                 log_dir: str | None = None,
                 coordinator: str | None = None,
                 num_processes: int | None = None,
                 process_id: int | None = None):
        # Multi-host rendezvous — explicit args or the launcher's SPARKDL_*
        # env (no-op on a single process with neither).
        _maybe_init_distributed(coordinator, num_processes, process_id)
        devs = jax.devices()
        n = len(devs) if np in (-1, None) else int(np)
        if n > len(devs):
            raise ValueError(
                f"np={n} exceeds visible devices ({len(devs)}). Multi-host "
                "scaling uses coordinator/num_processes, not np inflation.")
        self.devices = devs[:n]
        self.axes = axes or {"data": n}
        self.checkpoint_dir = checkpoint_dir
        self.log_dir = log_dir

    def make_context(self) -> RunnerContext:
        mesh = runtime.make_mesh(self.axes, self.devices)
        data_axis = next(iter(self.axes))
        return RunnerContext(mesh=mesh, data_axis=data_axis,
                             checkpoint_dir=self.checkpoint_dir,
                             log_dir=self.log_dir)

    def run(self, main_fn: Callable, **kwargs) -> Any:
        """Invoke ``main_fn(ctx, **kwargs)`` under an active mesh.

        Unlike HorovodRunner there is no pickling/forking: SPMD means one
        program, and that program is already here.
        """
        chaos.fire("worker")  # worker-start chaos site (no-op unplanned)
        ctx = self.make_context()
        _CURRENT_CONTEXT.append(ctx)
        try:
            with ctx.mesh:
                return main_fn(ctx, **kwargs)
        finally:
            _CURRENT_CONTEXT.pop()

    def run_with_restarts(self, main_fn: Callable, max_restarts: int = 2,
                          backoff_s: float = 1.0, retry_all: bool = False,
                          diagnose: bool = False, **kwargs) -> Any:
        """Checkpoint-and-restart supervision (SURVEY.md §5.3): re-invoke
        ``main_fn`` on failure; with a checkpoint_dir set, ``ctx.fit`` resumes
        from the last saved step, so a restart loses at most
        ``checkpoint_every`` steps — the reference's whole-job-retry story,
        minus losing the whole job.

        Failures are classified (``failures.classify_exception``): only
        infrastructure flakes (backend UNAVAILABLE, rendezvous timeouts,
        preemption) restart; program errors (ValueError & co) re-raise
        immediately — retrying the user's bug wastes the restart budget.
        ``retry_all=True`` restores indiscriminate retry. ``diagnose=True``
        wraps each attempt in cloud-tpu-diagnostics stack-trace collection.
        """
        from . import failures

        attempt = 0
        while True:
            try:
                if diagnose:
                    with failures.diagnose_context():
                        return self.run(main_fn, **kwargs)
                return self.run(main_fn, **kwargs)
            except Exception as e:
                kind = failures.classify_exception(e)
                metrics_lib.run_stats.record_failure(
                    kind, f"{type(e).__name__}: {e}")
                attempt += 1
                if (kind == "fatal" and not retry_all) \
                        or attempt > max_restarts:
                    # Failures inside fit() already wrote a postmortem
                    # carrying the failing step/site — do NOT overwrite it
                    # with this step-less one; this write covers main_fn
                    # failures outside fit.
                    if not getattr(e, "_sparkdl_postmortemed", False):
                        events.postmortem(e, site="run_with_restarts",
                                          kind=kind, attempt=attempt)
                    raise
                metrics_lib.run_stats.record_restart()
                events.event("restart", attempt=attempt, kind=kind,
                             error=f"{type(e).__name__}: {e}"[:300])
                log.exception("run failed (%s); restart %d/%d", kind,
                              attempt, max_restarts)
                time.sleep(backoff_s * attempt)
