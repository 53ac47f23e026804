"""Drive ``XlaRunner(np=-1).run(main)`` -> ``ctx.fit`` once, under a training
mix, and time it from the data iterator that ``fit`` is given.

The driver owns ``fit``'s ``data``: an iterator that hands out warm-up batches
(set-up), starts the clock on a batch boundary at which ``fit`` has just
fetched a loss and so drained the device, hands out timed batches until
``--seconds`` have passed, and stops. The window closes when ``fit`` has
returned, its state is ready and one of its leaves has been fetched.

What ``correct`` compares comes from that same ``fit`` call: during the first
steps the iterator reads the train state and the step's loss out of ``fit``'s
own frame (the program offers no hook between steps), so the state compared is
the state the window then drives, not one built alike.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time

CHECK_STEPS = 3
TRACE_AT_S = 2.0      # the trace starts this far inside the window
TRACE_LEN_S = 3.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _fit_locals():
    """The locals of the frame up the stack that holds a train state: an
    object with ``params``, ``opt_state`` and ``step``. None if there is none."""
    f = sys._getframe(2)
    while f is not None:
        for v in f.f_locals.values():
            if all(hasattr(v, a) for a in ("params", "opt_state", "step")):
                return f.f_locals, v
        f = f.f_back
    return None, None


class Feeder:
    """The ``data`` iterator of one run. Plain methods, one thread: ``fit``
    with its default inline feed asks for batch k after dispatching step k-1."""

    def __init__(self, job, pool, weights0, prog, cfg):
        self.job, self.pool, self.prog, self.cfg = job, pool, prog, cfg
        self.weights0 = weights0      # the trainable leaves before step 1
        self.warmup = int(job["resolved"]["traffic"]["warmup_steps"])
        self.seconds = float(job["seconds"])
        self.k = 0                    # batches handed out so far
        self.timed = 0
        self.t0 = None                # perf_counter at the window's start
        self.readings = {"losses": {}, "grad1": None, "delta": None}
        self.trace_dir = None
        self.trace_wall = [None, None]
        self.compiles_in_window = 0
        self.compile_s_setup = 0.0
        self.spans = []
        self.done = False
        self.marks = {}               # perf_counter at the parts of set-up
        self.memory = {}              # the first chip's memory at each mark

    # -- listeners -----------------------------------------------------------
    def on_duration(self, event, duration, **_kw):
        if event == _COMPILE_EVENT:
            if self.t0 is not None and not self.done:
                self.compiles_in_window += 1
            elif self.t0 is None:
                self.compile_s_setup += duration

    def on_event(self, rec):
        if rec.get("ph") == "E" and rec.get("name") in (
                "step_compute", "shard_put") and self.t0 is not None:
            self.spans.append({"name": rec["name"], "t": rec["t"],
                               "dur_s": rec["dur_s"],
                               "bytes": rec.get("bytes")})

    def mark(self, name: str) -> float:
        """Note the first chip's memory at a part of set-up, then the time."""
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        self.memory[name] = {k: int(stats.get(k, 0)) for k in (
            "bytes_in_use", "peak_bytes_in_use")}
        self.marks[name] = time.perf_counter()
        return self.marks[name]

    # -- the first steps: what ``correct`` compares -----------------------------
    def _snapshot(self):
        import jax
        from harness.reference_run import leaf_norms
        loc, state = _fit_locals()
        if state is None:
            return
        step = int(state.step)
        if not 1 <= step <= CHECK_STEPS or step in self.readings["losses"]:
            return
        m = loc.get("last_m")
        if not (isinstance(m, dict) and "loss" in m):
            m = next((v for v in loc.values()
                      if isinstance(v, dict) and "loss" in v), None)
        if m is not None:
            self.readings["losses"][step] = float(m["loss"])
        if step == 1:
            g = jax.device_get(self.prog.trainable(
                self.prog.first_gradient(self.cfg, state.opt_state)))
            self.readings["grad1"] = leaf_norms(g)
        if step == CHECK_STEPS:
            p = jax.device_get(self.prog.trainable(state.params))
            delta = jax.tree_util.tree_map(lambda a, b: a - b, p,
                                           self.weights0)
            self.readings["delta"] = leaf_norms(delta)
            self.weights0 = None

    # -- the iterator ----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        import jax
        if self.k <= 1:
            # asked for batch 0: fit has placed its state; for batch 1: the
            # first step is traced, compiled or loaded, and dispatched
            self.mark("fit_asks_batch_%d" % self.k)
        if self.k <= CHECK_STEPS + 1:
            self._snapshot()
        if self.k == self.warmup:
            # fit has just fetched the loss of step k (k % log_every == 0):
            # the device is drained, the clock starts
            self.t0 = self.mark("window_opens")
        if self.t0 is not None:
            elapsed = time.perf_counter() - self.t0
            if elapsed >= self.seconds:
                if self.trace_wall[0] and not self.trace_wall[1]:
                    self._stop_trace()
                raise StopIteration
            if self.job["trace"]:
                if self.trace_wall[0] is None and elapsed >= TRACE_AT_S:
                    self._start_trace()
                elif self.trace_wall[0] and not self.trace_wall[1] \
                        and elapsed >= TRACE_AT_S + TRACE_LEN_S:
                    self._stop_trace()
            self.timed += 1
        with jax.profiler.TraceAnnotation("bench_feed"):
            batch = self.pool[self.k % len(self.pool)]
            self.k += 1
            return batch

    def _start_trace(self):
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.trace_wall[0] = time.time()

    def _stop_trace(self):
        import jax
        self.trace_wall[1] = time.time()
        jax.profiler.stop_trace()


def run(job: dict) -> dict:
    import jax
    import numpy as np

    import sparkdl_tpu as sdl
    from sparkdl_tpu.runner import events

    from harness import compare, loader, trace as trace_lib
    from harness.reference_run import make_weights, run_steps
    from harness.traffic import make_pool

    res = job["resolved"]
    cfg, traffic, chips = res["config"], res["traffic"], res["cell"]["chips"]
    ref = loader.load_module(*res["files"]["reference"])
    prog = loader.load_module(*res["files"]["program"])
    flops = loader.load_module(*res["files"]["flops"])
    if traffic["pool_batches"] < CHECK_STEPS:
        raise loader.ResolutionError("pool_batches under the steps compared")
    if traffic["warmup_steps"] < CHECK_STEPS + 2:
        raise loader.ResolutionError("warmup_steps under the steps compared")

    t_imports = time.perf_counter()
    devices = jax.devices()
    runner = sdl.XlaRunner(np=-1 if len(devices) == chips else chips)
    used = runner.devices
    state_box = {}

    def main(ctx):
        # weights from the seed on the device in one call, then to the host:
        # fit takes host parameters and places them itself
        weights = jax.tree_util.tree_map(
            np.asarray, make_weights(ref, cfg, job["seed"]))
        feeder = Feeder(job, None, ref.trainable(weights), prog, cfg)
        feeder.mark("weights")
        pool = feeder.pool = make_pool(traffic, cfg, job["seed"], ctx.size)
        feeder.mark("pool")
        jax.monitoring.register_event_duration_secs_listener(
            feeder.on_duration)
        if job["trace"]:
            events.add_tee(feeder.on_event)
        try:
            out = ctx.fit(data=feeder, num_steps=10 ** 9, resume=False,
                          **prog.fit_kwargs(cfg, weights))
            state = out["state"]
            jax.block_until_ready(state)
            t_ready = time.perf_counter()
            # the window closes on a host fetch of a leaf the last step wrote
            leaves = jax.tree_util.tree_leaves(state.params)
            np.asarray(min(leaves, key=lambda x: x.size))
            t1 = time.perf_counter()
            feeder.done = True
        finally:
            events.remove_tee(feeder.on_event)
        state_box.update(
            steps=int(state.step), t1=t1, barrier_gap_ms=1e3 * (t1 - t_ready),
            log_every_losses=[h["loss"] for h in out["history"]][:64])
        del out, state, leaves
        return feeder, weights, pool

    feeder, weights, pool = runner.run(main)
    if feeder.t0 is None:
        raise RuntimeError("fit stopped before the window opened")
    if feeder.compiles_in_window:
        raise RuntimeError(f"{feeder.compiles_in_window} compilations "
                           "inside the measured window")
    window_s = state_box["t1"] - feeder.t0
    global_batch = int(traffic["per_chip_batch"]) * chips
    stats = [d.memory_stats() or {} for d in used]
    # arrays (state, batches in flight) and the step program's own scratch are
    # counted apart by this runtime, in disjoint regions: the peak is their sum
    memory_peak = max(int(s.get("peak_bytes_in_use", 0))
                      + int(s.get("peak_bytes_reserved", 0)) for s in stats)
    attempted = feeder.timed
    failed = max(0, feeder.warmup + feeder.timed - state_box["steps"])

    kind = used[0].device_kind
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"attempted": attempted, "failed": failed, "metrics": {},
              "device": device}
    units = {m["name"]: m["unit"] for m in
             res["end_to_end"] + res["per_layer"]}

    if not job["trace"]:
        values = {
            "train_examples_per_s":
                (attempted - failed) * global_batch / window_s / chips,
            "setup_s": feeder.t0 - job["t_start"]}
        for m in res["end_to_end"]:
            if m["name"] in values:
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"]}
    else:
        tr = trace_lib.read_xplane(trace_lib.find_xplane(feeder.trace_dir))
        shutil.rmtree(feeder.trace_dir, ignore_errors=True)
        if job.get("dump_trace"):
            job["dump_trace"](tr)
        summary = trace_lib.device_summary(tr)
        if not summary:
            raise RuntimeError("the trace holds no device operations")
        lo, hi = feeder.trace_wall
        ctx = {"trace": tr, "device_summary": summary,
               "spans": [s for s in feeder.spans if lo <= s["t"] <= hi],
               "flops_per_example": flops.train_flops_per_example(
                   cfg, traffic),
               "global_batch": global_batch, "chips": chips,
               "peak": loader.peak_for(kind)}
        for m in res["per_layer"]:
            v = loader.load_module("layer_metrics", m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": units[m["name"]]}
        device["busy_s"] = sum(d["busy_s"] for d in summary) / len(summary)
        device["window_s"] = sum(d["window_s"] for d in summary) / len(summary)
        result["breakdown"] = {
            "device_ops": trace_lib.top_device_ops(tr),
            "idle_gaps": trace_lib.idle_gaps_by_host(tr)}
        del tr, ctx

    # -- correct: the plain reference over the same first steps ---------------
    t_ref = time.perf_counter()
    r = feeder.readings
    prog_read = {"losses": [r["losses"].get(i + 1, float("nan"))
                            for i in range(CHECK_STEPS)],
                 "grad1": r["grad1"] or {}, "delta": r["delta"] or {}}
    ref_read = run_steps(ref, cfg, weights, pool[:CHECK_STEPS])
    # the runtime's peak only grows: this reads the larger of the program's
    # peak and the reference's (memory_peak_bytes was taken before it ran)
    after_reference = int((used[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    numbers, where = compare.training_numbers(prog_read, ref_read)
    correct, compared = compare.judge(numbers, res["limits"]["limits"])
    result["correct"] = bool(correct)
    # where set-up went: seconds from the process's start to each mark
    marks = dict(imports=t_imports, **feeder.marks)
    result["setup_parts_s"] = {k: v - job["t_start"]
                               for k, v in sorted(marks.items(),
                                                  key=lambda kv: kv[1])}
    result["window"] = {
        "seconds": window_s, "steps": attempted,
        "warmup_steps": feeder.warmup,
        "compile_s_in_setup": feeder.compile_s_setup,
        "barrier_gap_ms": state_box["barrier_gap_ms"],
        "reference_s": time.perf_counter() - t_ref,
        "loss_at_log_every": state_box["log_every_losses"],
        "reference_losses": ref_read["losses"],
        "program_losses": prog_read["losses"],
        "worst_leaves": where,
        "not_compared": {k: v for k, v in numbers.items()
                         if k not in res["limits"]["limits"]},
        "peak_bytes_in_use": int(stats[0].get("peak_bytes_in_use", 0)),
        "peak_bytes_reserved": int(stats[0].get("peak_bytes_reserved", 0)),
        "peak_bytes_in_use_after_reference": after_reference,
        "bytes_limit": int(stats[0].get("bytes_limit", 0)),
        "memory_at_marks": feeder.memory}
    result["compared"] = compared
    if job.get("keep_readings"):
        result["readings"] = {"program": prog_read, "reference": ref_read}
    return result
