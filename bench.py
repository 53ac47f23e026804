"""Benchmark driver — BOTH BASELINE.json metrics, hardened + fail-fast.

Headline: ResNet-50 data-parallel training throughput (img/s/chip) through
XlaRunner's compiled SPMD step — BASELINE.json metric M1 ("HorovodRunner
ResNet-50 img/s/chip"). Secondary legs: DeepImageFeaturizer rows/s (M2,
through the FULL transformer path: image-struct DataFrame → Arrow decode →
NHWC pack → jitted InceptionV3 featurize → vector column), BERT-base
fine-tune tokens/s/chip (BASELINE configs[3]), and a compiled-flash-kernel
parity + timing check. An MFU estimate (XLA cost-analysis flops / step time
/ peak chip flops) rides along.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": "img/s/chip", "vs_baseline": N,
     "extra": {featurizer rows/s, MFU, backend info, ...}}
and on failure a machine-readable error record (value 0.0, "error": {...})
— never a bare traceback and NEVER silence (round-3: a hung backend ate the
whole driver window and left `parsed: null`; the r04 contract is that the
record always prints).

Hardening:
- A cheap backend-liveness PROBE subprocess runs first with a short timeout.
  If `import jax; jax.devices()` hangs (the r01/r03 outage signature), the
  driver emits the error record within ~BENCH_PROBE_TIMEOUT_S and exits —
  no metric attempts against a dead backend.
- An overall wall-clock budget (BENCH_WALL_S) bounds the whole run: each
  leg's subprocess timeout is clamped to the remaining budget, remaining
  legs/retries are skipped (recorded as budget_exhausted) when it is nearly
  spent, and the record prints no matter what.
- Each metric runs in a SUBPROCESS with a hard timeout, bounded retries
  with backoff around transient infra failures (classified by
  sparkdl_tpu.runner.failures — fatal program errors do not burn retries);
  partial results are emitted if only some legs land.

Env knobs: BENCH_WALL_S (1200 overall), BENCH_PROBE_TIMEOUT_S (180),
BENCH_TIMEOUT_S (720 per attempt; timeouts of >=300s attempts are not
retried — a long hang must not starve the remaining legs), BENCH_RETRIES
(1), BENCH_BATCH_PER_CHIP ("64,128,256" — comma list is swept, the best
is the headline), BENCH_STREAM_BATCH (128 — the ONE sweep point that
runs the streamed-feed variants; falls back to the first
swept size), BENCH_STEPS (20), BENCH_MODEL (ResNet50), BENCH_IMAGE_SIZE (224),
BENCH_FEAT_ROWS (1024), BENCH_FEAT_BATCH (128), BENCH_FEAT_MODEL
(InceptionV3), BENCH_BERT_BATCH (32), BENCH_BERT_SEQ (128),
BENCH_GEN_BATCH (8), BENCH_GEN_PROMPT (128), BENCH_GEN_NEW (64),
BENCH_PEAK_TFLOPS (197 — v5e bf16 peak; set 275 for v4 pairs etc.),
BENCH_SKIP_FEATURIZER / BENCH_SKIP_BERT / BENCH_SKIP_GEN /
BENCH_SKIP_FLASH / BENCH_SKIP_ELASTIC,
BENCH_FAKE_HANG_S (test knob: every worker sleeps this long first, to
simulate the hung-backend outage in hardening tests).

The reference published no numbers (SURVEY.md §6; BASELINE.json
`"published": {}`), so ``vs_baseline`` compares against the last good
locally recorded run: ``BENCH_BASELINE.json`` is WRITTEN after every
successful run and read on the next; `extra.last_good` reports the prior
value the ratio was computed against.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))


def _env_flag(name: str) -> bool:
    """'1'/'true'/'yes' → True; ''/'0'/'false'/'no'/unset → False (a bare
    bool(getenv) would treat BENCH_REMAT=0 as enabled)."""
    return os.environ.get(name, "").strip().lower() in ("1", "true", "yes")


def _peak_flops() -> float:
    """Per-chip peak FLOPs/s for MFU: BENCH_PEAK_TFLOPS override (TFLOPs),
    else the runner's shared device table / SPARKDL_PEAK_FLOPS knob
    (raw FLOPs), else the v5e bf16 default — so bench MFU and
    meter.summary() MFU divide by the SAME peak on the same hardware.
    Worker-side only (the helper queries devices)."""
    env = os.environ.get("BENCH_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    try:
        from sparkdl_tpu.runner.metrics import peak_flops_per_chip
        peak = peak_flops_per_chip()
        if peak:
            return peak
    except Exception:
        pass
    return 197e12


# ---------------------------------------------------------------------------
# Workers (run in a subprocess each; emit one JSON line on stdout)
# ---------------------------------------------------------------------------

def _force(x):
    """Completion barrier: materialize bytes on the host. Callers pass a
    SMALL array (a scalar loss, a token row) that data-depends on the work
    being timed, so the extra transfer is one host round-trip.
    """
    import jax
    return jax.device_get(x)


def _compile_and_time(step, state, sharded, warmup: int, steps: int):
    """Shared measurement protocol for the training legs: AOT-compile the
    step (lower().compile() does not populate the jit call cache — execute
    the compiled object), read XLA's flops for MFU, then warmup + timed
    loop closed by a host fetch of the final loss (_force) — the last
    step's loss data-depends on every prior step via the state chain, so
    fetching it bounds the whole loop's real execution.

    Returns (step, final_state, metrics, sec_per_step, flops, bytes_acc)
    — ``step`` is the compiled executable when AOT succeeded, else the
    jit fallback. ``bytes_acc`` is XLA's bytes-accessed estimate, the
    numerator of the roofline memory term.
    """
    import jax
    import numpy as np

    flops = None
    bytes_acc = None
    try:
        compiled = step.lower(state, sharded).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0)) or None
        bytes_acc = float(cost.get("bytes accessed", 0.0)) or None
        step = compiled
    except Exception:
        pass  # fall back to the jit path

    for _ in range(warmup):
        state, m = step(state, sharded)
    _force(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, sharded)
    last = _force(m["loss"])  # inside the bracket: the real barrier
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(float(last)), "training diverged"
    return step, state, m, dt, flops, bytes_acc


def _roofline(flops, bytes_acc, peak_flops: float) -> dict:
    """The quantitative MFU ceiling (round-4 verdict Next #3's fallback):
    a step cannot run faster than max(compute time, HBM time), so
    achievable MFU is bounded by t_compute / max(t_compute, t_memory).
    When the bound itself sits below the 0.4 target, the gap is
    memory-bound by construction — the analysis the verdict asked to be
    published rides in the bench record automatically."""
    if not flops or not bytes_acc:
        return {}
    hbm = float(os.environ.get("BENCH_HBM_GBPS", "819")) * 1e9  # v5e HBM
    t_c = flops / peak_flops
    t_m = bytes_acc / hbm
    return {"bytes_per_step": bytes_acc,
            "ai_flops_per_byte": round(flops / bytes_acc, 2),
            "roofline_mfu_bound": round(t_c / max(t_c, t_m), 4),
            "hbm_gbps_assumed": hbm / 1e9}


def _worker_resnet50_train() -> dict:
    """Training throughput, swept over per-chip batch sizes, plus a
    STREAMED-feed variant (fresh host batches through the ctx.fit feed
    path — shard_batch per step) so the host→HBM leg is measured under
    training load, not assumed (round-2 verdict weak #2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from sparkdl_tpu.models.registry import get_model
    from sparkdl_tpu.runner import TrainState, XlaRunner, bn_classifier_loss

    sweep = [int(x) for x in
             os.environ.get("BENCH_BATCH_PER_CHIP", "64,128,256").split(",")]
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    model_name = os.environ.get("BENCH_MODEL", "ResNet50")
    img = int(os.environ.get("BENCH_IMAGE_SIZE", "224"))
    warmup = 3
    peak = _peak_flops()

    runner = XlaRunner(np=-1)

    def main(ctx):
        spec = get_model(model_name)
        # bf16 activations/params on the MXU; the loss reduction upcasts to
        # f32 inside the step (train_state.py).
        model = spec.build(dtype=jnp.bfloat16)

        @jax.jit
        def init(key):
            return model.init(key, jnp.zeros((1, img, img, 3)), train=False)

        variables = jax.tree_util.tree_map(
            np.asarray, init(jax.random.PRNGKey(0)))

        # ONE optimizer object: optax transforms carry fresh function
        # objects each construction, and they ride in TrainState's static
        # pytree metadata — a second optax.sgd() would mismatch the AOT-
        # compiled executable's input pytree.
        tx = optax.sgd(1e-3, momentum=0.9)

        def fresh_state():
            state = TrainState.create(
                None, variables["params"], tx,
                model_state={"batch_stats": variables["batch_stats"]})
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(np.asarray(x), ctx.replicated()),
                state)

        def measure(batch_per_chip, with_streamed=True):
            state = fresh_state()
            n = batch_per_chip * ctx.size
            rng = np.random.RandomState(0)
            batch = {
                "image": rng.randint(0, 256, size=(n, img, img, 3))
                           .astype(np.float32),
                "label": rng.randint(0, 1000, size=(n,)),
            }
            step_fn = ctx.make_train_step(
                bn_classifier_loss(model, spec.preprocess), mutable=True,
                remat=_env_flag("BENCH_REMAT"))
            sharded = ctx.shard_batch(batch)
            step, state, m, dt_step, flops, nbytes = _compile_and_time(
                step_fn, state, sharded, warmup, steps)
            rec = {"batch_per_chip": batch_per_chip,
                   "img_s_chip": n / dt_step / ctx.size,
                   "step_time_s": dt_step}
            if flops:
                rec["mfu"] = flops / dt_step / (peak * ctx.size)
                rec["flops_per_step"] = flops
                rec.update(_roofline(flops, nbytes, peak * ctx.size))

            # Streamed variant: FOUR distinct host batches cycle through
            # shard_batch each step — exactly ctx.fit's feed path, so
            # host→HBM transfer rides the async dispatch pipeline. Its own
            # try/except: a failure here (e.g. host OOM on the extra
            # batches) must not discard the base measurement above.
            # Gated per sweep point: one batch size of feed evidence is
            # the A/B the record needs.
            if not with_streamed:
                return rec
            try:
                hosts = []
                for s in range(4):
                    r = np.random.RandomState(s)
                    hosts.append({
                        "image": r.randint(0, 256, size=(n, img, img, 3))
                                   .astype(np.float32),
                        "label": r.randint(0, 1000, size=(n,)),
                    })
                state = fresh_state()
                for _ in range(warmup):
                    state, m = step(state, ctx.shard_batch(hosts[0]))
                _force(m["loss"])
                t0 = time.perf_counter()
                for i in range(steps):
                    state, m = step(state, ctx.shard_batch(hosts[i % 4]))
                _force(m["loss"])
                dt_s = time.perf_counter() - t0
                rec["streamed_img_s_chip"] = (steps * n) / dt_s / ctx.size

                # uint8 wire variant: 4x fewer host→HBM bytes, cast
                # in-graph by the preprocess fn (registry._as_float) —
                # the training-feed twin of the inference path's uint8
                # wire. Different input dtype = different program
                # signature, so this goes through the JITTED step_fn
                # (the AOT `step` executable is locked to f32 avals and
                # would raise TypeError), which traces/compiles the u8
                # signature on its first warmup call.
                hosts_u8 = [{"image": h["image"].astype(np.uint8),
                             "label": h["label"]} for h in hosts]
                state = fresh_state()
                for _ in range(warmup):
                    state, m = step_fn(state, ctx.shard_batch(hosts_u8[0]))
                _force(m["loss"])
                t0 = time.perf_counter()
                for i in range(steps):
                    state, m = step_fn(state,
                                       ctx.shard_batch(hosts_u8[i % 4]))
                _force(m["loss"])
                dt_u8 = time.perf_counter() - t0
                rec["streamed_u8_img_s_chip"] = (steps * n) / dt_u8 \
                    / ctx.size

                # feed-lookahead twin: batch k+1's shard_batch runs in a
                # worker thread while step k executes (the fit(
                # feed_lookahead=1) path) — the wire time then overlaps
                # compute instead of serializing with it
                from concurrent.futures import ThreadPoolExecutor
                state = fresh_state()
                for _ in range(warmup):
                    state, m = step_fn(state, ctx.shard_batch(hosts_u8[0]))
                _force(m["loss"])
                with ThreadPoolExecutor(1) as pool:
                    t0 = time.perf_counter()
                    fut = pool.submit(ctx.shard_batch, hosts_u8[0])
                    for i in range(steps):
                        sharded = fut.result()
                        if i + 1 < steps:
                            fut = pool.submit(ctx.shard_batch,
                                              hosts_u8[(i + 1) % 4])
                        state, m = step_fn(state, sharded)
                    _force(m["loss"])
                    dt_la = time.perf_counter() - t0
                rec["streamed_u8_lookahead_img_s_chip"] = \
                    (steps * n) / dt_la / ctx.size
            except Exception as e:
                rec["streamed_error"] = f"{type(e).__name__}: {e}"[:200]
            return rec

        stream_b = int(os.environ.get("BENCH_STREAM_BATCH", "128"))
        if stream_b not in sweep:
            stream_b = sweep[0]
        results = []
        for b in sweep:
            try:
                results.append(measure(b, with_streamed=(b == stream_b)))
            except Exception as e:  # OOM at large batch: record and move on
                results.append({"batch_per_chip": b,
                                "error": f"{type(e).__name__}: {e}"[:300]})
        ok = [r for r in results if "img_s_chip" in r]
        if not ok:
            raise RuntimeError(f"all batch sizes failed: {results}")
        best = max(ok, key=lambda r: r["img_s_chip"])
        streamed = next((r for r in ok
                         if r["batch_per_chip"] == stream_b), None)
        if streamed is None:
            # the one point carrying the feed A/B failed outright —
            # surface WHY instead of silently-null streamed keys
            failed = next((r for r in results
                           if r["batch_per_chip"] == stream_b), {})
            streamed = {"streamed_error":
                        f"stream point batch={stream_b} failed: "
                        f"{failed.get('error', 'unknown')}"[:300]}

        from sparkdl_tpu.ops.flash_attention import auto_attn_fn
        return {"img_s_chip": best["img_s_chip"], "n_chips": ctx.size,
                "remat": _env_flag("BENCH_REMAT"),
                "batch_per_chip": best["batch_per_chip"], "steps": steps,
                "model": model_name, "image_size": img,
                "step_time_s": best["step_time_s"],
                "flops_per_step": best.get("flops_per_step"),
                "mfu": best.get("mfu"),
                "roofline_mfu_bound": best.get("roofline_mfu_bound"),
                "ai_flops_per_byte": best.get("ai_flops_per_byte"),
                "streamed_batch_per_chip":
                    streamed.get("batch_per_chip"),
                "streamed_img_s_chip": streamed.get("streamed_img_s_chip"),
                "streamed_u8_img_s_chip":
                    streamed.get("streamed_u8_img_s_chip"),
                "streamed_u8_lookahead_img_s_chip":
                    streamed.get("streamed_u8_lookahead_img_s_chip"),
                **({"streamed_error": streamed["streamed_error"]}
                   if "streamed_error" in streamed else {}),
                "sweep": results,
                "flash_attention_default": auto_attn_fn() is not None}

    return runner.run(main)


def _worker_featurizer() -> dict:
    import numpy as np

    from sparkdl_tpu.core.frame import DataFrame
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    rows = int(os.environ.get("BENCH_FEAT_ROWS", "1024"))
    batch = int(os.environ.get("BENCH_FEAT_BATCH", "128"))
    model_name = os.environ.get("BENCH_FEAT_MODEL", "InceptionV3")

    rng = np.random.RandomState(0)
    from sparkdl_tpu.models.registry import get_model
    h, w = get_model(model_name).input_size

    def make_df(n):
        import pyarrow as pa
        structs = [imageIO.imageArrayToStruct(
            rng.randint(0, 256, size=(h, w, 3)).astype(np.uint8),
            origin=f"synthetic_{i}") for i in range(n)]
        return DataFrame.fromArrow(
            pa.table({"image": pa.array(structs, type=imageIO.imageSchema)}),
            numPartitions=max(1, n // max(batch, 1)))

    feat = DeepImageFeaturizer(
        modelName=model_name, inputCol="image", outputCol="features",
        batchSize=batch,
        # bf16 activations on the MXU — the standard TPU inference dtype
        computeDtype=os.environ.get("BENCH_FEAT_DTYPE", "bfloat16"))
    # Warmup: param init + XLA compile on a small slice.
    feat.transform(make_df(batch)).collect()

    # Per-stage engine telemetry for the timed run: the streaming scorer
    # spans every stage (decode/pad/put/dispatch/fetch/encode), so the
    # record shows WHERE inference wall time goes, not just the rate.
    from sparkdl_tpu.core.runtime import decode_workers_default
    from sparkdl_tpu.runner import events as events_lib
    rec = events_lib.reset(ring_size=65536)
    df = make_df(rows)
    t0 = time.perf_counter()
    out = feat.transform(df).collect()
    dt = time.perf_counter() - t0
    assert len(out) == rows
    assert len(out[0]["features"]) == feat.featureDim()
    stage_seconds: dict = {}
    for e in rec.tail():
        if e.get("ph") == "E" and "dur_s" in e:
            stage_seconds[e["name"]] = round(
                stage_seconds.get(e["name"], 0.0) + e["dur_s"], 4)
    # Bottleneck evidence per revision (ISSUE 6 satellite): overlap-aware
    # busy fractions + the dominant stage, next to the raw stage_seconds
    # sums — BENCH_* files then say WHICH stage bounds the rate, not just
    # how the seconds added up across concurrent workers.
    from sparkdl_tpu.runner import analysis as analysis_lib
    stage_utilization = analysis_lib.utilization_from_events(rec.tail())
    events_lib.reset()

    # A/B: same transform with 4 concurrent transfer threads
    # (SPARKDL_TRANSFER_WORKERS) — where device_put holds its thread
    # for the wire time and the link pipelines, this is the M2 feed
    # un-serialized; recorded next to the default so one chip run
    # answers whether to ship the knob on. Same timing
    # window as the baseline (df built outside), errors degrade to a
    # recorded field, and a caller-exported knob value is restored.
    dt_w = None
    ab_err = None
    prior_w = os.environ.get("SPARKDL_TRANSFER_WORKERS")
    try:
        df_w = make_df(rows)
        os.environ["SPARKDL_TRANSFER_WORKERS"] = "4"
        t0 = time.perf_counter()
        out_w = feat.transform(df_w).collect()
        dt_w = time.perf_counter() - t0
        assert len(out_w) == rows
        dt_w = None if dt_w <= 0 else dt_w
    except Exception as e:
        ab_err = f"{type(e).__name__}: {e}"[:200]
    finally:
        if prior_w is None:
            os.environ.pop("SPARKDL_TRANSFER_WORKERS", None)
        else:
            os.environ["SPARKDL_TRANSFER_WORKERS"] = prior_w

    # Phase breakdown (round-2 verdict task 1: "with the breakdown
    # recorded"): where does the wall time go relative to each leg's
    # standalone rate? Each leg measured on one device batch, warm.
    breakdown = {}
    try:
        import jax

        from sparkdl_tpu.core.runtime import pad_batch
        tbl = df.toArrow()
        col = tbl.column("image").combine_chunks().slice(0, batch)
        n_probe = len(col)  # may be < batch when rows < batch
        t = time.perf_counter()
        nhwc = imageIO.imageColumnToNHWC(col, h, w, dtype=np.uint8)
        breakdown["decode_rows_per_sec"] = n_probe / (time.perf_counter() - t)
        # pad to the configured batch so the probe hits the SAME compiled
        # program as the measured transform (no fresh compile, honest rate)
        nhwc, _ = pad_batch(nhwc, batch)
        # Brackets closed by a tiny dependent host fetch (_force). The
        # fetch costs one host round-trip, so each rate is the
        # DIFFERENCE between a 2x and a 1x bracket (RTT cancels) — same
        # methodology as the flash leg's scan chains.
        probe = jax.jit(lambda a: a.ravel()[0])

        def bracket(work, reps, attempts=2):
            best = float("inf")
            for _ in range(attempts):
                t0 = time.perf_counter()
                r = None
                for _ in range(reps):
                    r = work()
                _force(probe(r))
                best = min(best, time.perf_counter() - t0)
            return best

        dev = jax.device_put(nhwc)
        _force(probe(dev))  # warm the shape's transfer path
        put_s = (bracket(lambda: jax.device_put(nhwc), 2)
                 - bracket(lambda: jax.device_put(nhwc), 1))
        if put_s > 0:
            breakdown["device_put_mb_per_sec"] = nhwc.nbytes / 1e6 / put_s
        fn = feat._get_runner()._jitted
        _force(probe(fn(dev)))  # warm
        apply_s = (bracket(lambda: fn(dev), 2) - bracket(lambda: fn(dev), 1))
        if apply_s > 0:
            breakdown["apply_rows_per_sec"] = batch / apply_s

        # Concurrent-transfer scaling probe (SPARKDL_TRANSFER_WORKERS
        # sizing evidence): wall time of 4 device_puts issued serially vs
        # from a thread pool. Where a put holds its thread for the wire
        # time and the link pipelines, the pool wall divides by
        # ~workers and the feed's worker knob is worth
        # setting. One fetch closes each bracket (same RTT both sides).
        from concurrent.futures import ThreadPoolExecutor
        probe4 = jax.jit(lambda a, b, c, d: (a.ravel()[0] + b.ravel()[0]
                                             + c.ravel()[0] + d.ravel()[0]))
        _force(probe4(dev, dev, dev, dev))  # compile off the clock
        t0 = time.perf_counter()
        rs = [jax.device_put(nhwc) for _ in range(4)]
        _force(probe4(*rs))
        serial_s = time.perf_counter() - t0
        breakdown["put4_serial_s"] = serial_s
        for w in (2, 4):
            with ThreadPoolExecutor(w) as pool:
                t0 = time.perf_counter()
                rs = [f.result() for f in
                      [pool.submit(jax.device_put, nhwc) for _ in range(4)]]
                _force(probe4(*rs))
                breakdown[f"put4_pool{w}_s"] = time.perf_counter() - t0
        o = fn(dev)
        _force(probe(o))  # complete before timing the host fetch alone
        t = time.perf_counter()
        np.asarray(o)
        breakdown["fetch_s"] = time.perf_counter() - t
    except Exception as e:
        breakdown["error"] = f"{type(e).__name__}: {e}"[:200]
    from sparkdl_tpu import native as native_mod
    return {"rows_per_sec": rows / dt, "rows": rows, "batch_size": batch,
            "rows_per_sec_workers4": (rows / dt_w) if dt_w else None,
            **({"workers4_error": ab_err} if ab_err else {}),
            "model": model_name, "wall_s": dt,
            "compute_dtype": os.environ.get("BENCH_FEAT_DTYPE", "bfloat16"),
            "native_packer": native_mod.available(),
            "decode_workers": decode_workers_default(),
            "stage_seconds": stage_seconds,
            "stage_utilization": stage_utilization,
            "breakdown": {k: round(v, 3) if isinstance(v, float) else v
                          for k, v in breakdown.items()}}


def _synthetic_image_df(rows: int, batch: int, h: int, w: int):
    """Lazily-RENDERED image column: the stored partitions hold only an
    int64 index (8 bytes/row); a pending row-wise op renders each chunk's
    images at stream time, so however large ``rows`` is, at most one
    ~``batch``-row chunk of decoded images is live on the host — the
    shape of the north-star 1M-image scoring job."""
    import numpy as np
    import pyarrow as pa

    from sparkdl_tpu.core.frame import DataFrame, _row_wise_op
    from sparkdl_tpu.image import imageIO

    base = np.random.RandomState(0).randint(
        0, 256, size=(h, w, 3)).astype(np.uint8)

    def render(b: "pa.RecordBatch") -> "pa.RecordBatch":
        idx = b.column("idx").to_numpy()
        imgs = np.broadcast_to(base, (len(idx),) + base.shape).copy()
        imgs[:, 0, 0, 0] = (idx & 0xFF).astype(np.uint8)  # distinct rows
        col = imageIO.nhwcToImageColumn(
            imgs, origins=[f"synthetic_{i}" for i in idx],
            # synthetic bytes are already at-rest order; imgs is fresh
            # per chunk and never touched again → zero-copy wrap is safe
            channelOrder="BGR", copy=False)
        return pa.RecordBatch.from_arrays([col], ["image"])

    df = DataFrame.fromArrow(
        pa.table({"idx": pa.array(range(rows), type=pa.int64())}),
        numPartitions=max(1, rows // max(batch, 1)))
    return df.mapBatches(_row_wise_op(render))


def _worker_northstar() -> dict:
    """North-star-scale sustained featurize (BASELINE north_star:
    "batch-scores 1M images"; round-4 verdict Next #6): stream
    BENCH_NORTHSTAR_ROWS lazily-rendered images through
    DeepImageFeaturizer into a parquet sink written row-group-at-a-time,
    recording sustained rows/s and the peak-RSS delta across the run —
    the proof that host memory stays O(batch) at scale, not just in
    unit tests. Off by default (BENCH_NORTHSTAR_ROWS=0)."""
    import resource
    import tempfile

    import pyarrow.parquet as pq

    from sparkdl_tpu.models.registry import get_model
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    rows = int(os.environ.get("BENCH_NORTHSTAR_ROWS", "0"))
    batch = int(os.environ.get("BENCH_NORTHSTAR_BATCH", "128"))
    model_name = os.environ.get("BENCH_NORTHSTAR_MODEL", "InceptionV3")
    h, w = get_model(model_name).input_size

    feat = DeepImageFeaturizer(
        modelName=model_name, inputCol="image", outputCol="features",
        batchSize=batch,
        computeDtype=os.environ.get("BENCH_FEAT_DTYPE", "bfloat16"))
    # Compile + param init outside the timed / RSS-delta window.
    feat.transform(_synthetic_image_df(batch, batch, h, w)).collect()

    rss0_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    t0 = time.perf_counter()
    n_out = 0
    with tempfile.TemporaryDirectory() as td:
        sink = os.path.join(td, "features.parquet")
        writer = None
        try:
            out = feat.transform(_synthetic_image_df(rows, batch, h, w))
            for part in out.select("features").iterPartitions():
                if writer is None:
                    writer = pq.ParquetWriter(sink, part.schema)
                writer.write_batch(part)
                n_out += part.num_rows
        finally:
            if writer is not None:
                writer.close()
        sink_mb = os.path.getsize(sink) / 1e6
    dt = time.perf_counter() - t0
    rss1_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    assert n_out == rows, f"sink got {n_out} of {rows} rows"
    # Optional jax profiler capture (chip evidence: host-vs-device time
    # split). A SHORT bounded slice
    # AFTER both timing and RSS reads: trace buffers grow host RSS and
    # stop_trace flushes for seconds, and ru_maxrss is a monotone
    # high-water mark — profiling first would mask the measured run's
    # true delta (an always-pass O(batch) "proof").
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:
        import jax
        jax.profiler.start_trace(profile_dir)
        try:
            feat.transform(_synthetic_image_df(
                min(rows, 4 * batch), batch, h, w)).collect()
        finally:
            jax.profiler.stop_trace()
    return {"northstar_rows": rows,
            "northstar_rows_per_sec": rows / dt,
            "northstar_wall_s": dt,
            "northstar_batch": batch,
            "northstar_model": model_name,
            # growth of the process's peak RSS across the streamed run —
            # O(batch) streaming keeps this far below the materialized
            # input size, which is the line item that proves the claim.
            "northstar_peak_rss_delta_mb": (rss1_kb - rss0_kb) / 1024,
            "northstar_input_mb_if_materialized": rows * h * w * 3 / 1e6,
            "northstar_sink_mb": sink_mb}


def _worker_probe() -> dict:
    """Cheap liveness check: backend init + one tiny compiled add.

    Runs FIRST with a short timeout; if this hangs, the backend is down
    (the r01/r03 outage signature) and no metric leg is attempted. Also
    records what platform resolved and whether the flash default fires on
    it.
    """
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ops.flash_attention import auto_attn_fn
    from sparkdl_tpu.utils.platform import backend_info

    info = backend_info()
    x = jax.jit(lambda a: a * 2 + 1)(jnp.arange(8.0))
    jax.block_until_ready(x)
    info["compiled_ok"] = bool(float(x[3]) == 7.0)
    info["flash_attention_default"] = auto_attn_fn() is not None
    return info


def _worker_bert_train() -> dict:
    """BERT-base GLUE-shaped fine-tune throughput — BASELINE configs[3].

    tokens/s/chip + MFU at seq 128, bf16, flash attention on when the
    platform gate fires (recorded either way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from sparkdl_tpu.models.bert import (BertConfig,
                                         BertForSequenceClassification,
                                         bert_finetune_loss)
    from sparkdl_tpu.ops.flash_attention import auto_attn_fn
    from sparkdl_tpu.runner import TrainState, XlaRunner

    batch_per_chip = int(os.environ.get("BENCH_BERT_BATCH", "32"))
    seq = int(os.environ.get("BENCH_BERT_SEQ", "128"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    warmup = 3
    peak = _peak_flops()

    runner = XlaRunner(np=-1)

    def main(ctx):
        cfg = (BertConfig.tiny()
               if os.environ.get("BENCH_BERT_CONFIG") == "tiny"
               else BertConfig.base())
        model = BertForSequenceClassification(
            cfg, num_classes=2, dtype=jnp.bfloat16)
        n = batch_per_chip * ctx.size
        rng = np.random.RandomState(0)
        batch = {
            "input_ids": rng.randint(0, cfg.vocab_size, size=(n, seq)),
            "label": rng.randint(0, 2, size=(n,)),
        }

        # "params" here is the full flax variables dict — the loss fn calls
        # model.apply(params, ...) (the framework-wide convention; see
        # bert_finetune_loss / glue_loss_fn).
        variables = jax.tree_util.tree_map(np.asarray, jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)))
        state = TrainState.create(None, variables, optax.adamw(2e-5))
        state = jax.tree_util.tree_map(
            lambda x: jax.device_put(np.asarray(x), ctx.replicated()), state)

        step = ctx.make_train_step(bert_finetune_loss(model))
        sharded = ctx.shard_batch(batch)
        step, state, m, dt_step, flops, nbytes = _compile_and_time(
            step, state, sharded, warmup, steps)

        rec = {"bert_tokens_s_chip": n * seq / dt_step / ctx.size,
               "bert_batch_per_chip": batch_per_chip, "bert_seq": seq,
               "bert_step_time_s": dt_step,
               "flash_attention_active": auto_attn_fn() is not None}
        if flops:
            rec["bert_mfu"] = flops / dt_step / (peak * ctx.size)
            rec.update({f"bert_{k}": v for k, v in
                        _roofline(flops, nbytes, peak * ctx.size).items()})
        return rec

    return runner.run(main)


def _worker_flash() -> dict:
    """Compiled (non-interpret) Pallas flash kernel on the chip: parity vs
    dense at S=512/1024 plus a timing ratio — the round-3 verdict's
    "one compiled run on record" requirement (Next #2b)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.ops.flash_attention import flash_attention
    from sparkdl_tpu.parallel.ring_attention import dense_attention
    from sparkdl_tpu.utils.platform import backend_info, is_tpu_backend

    out: dict = {"backend": backend_info()}
    # On a non-TPU backend the compiled Mosaic kernel cannot lower — record
    # that rather than crash the leg (it means the platform gate correctly
    # kept flash off).
    compiled = is_tpu_backend()
    out["compiled_mode"] = compiled

    # enough chained iterations that N x kernel-time dwarfs the host
    # fetch's jitter between attempts.  Off-TPU (interpret-mode smoke
    # runs) the interpreter is ~1000x slower — two iterations suffice.
    iters = int(os.environ.get("BENCH_FLASH_ITERS",
                               "150" if compiled else "2"))

    def timed(attn, q, k, v, reps=5):
        """Per-call kernel time via in-jit scan chains: each iteration's
        output feeds the next call's q (a hard data dependency XLA cannot
        elide) and each bracket closes on a host fetch of a reduced
        scalar (_force).  The fetch costs a host round-trip that can
        exceed the kernels being timed, so the per-call number is the
        DIFFERENCE between a 2N-iteration and an N-iteration scan: the
        round-trip and every other constant overhead cancel."""
        def scanned(n):
            def run(a, b, c):
                def body(carry, _):
                    return attn(carry, b, c), ()
                o, _ = jax.lax.scan(body, a, None, length=n)
                return jnp.sum(o)
            f = jax.jit(run)
            _force(f(q, k, v))  # compile + first run off the clock
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                _force(f(q, k, v))
                best = min(best, time.perf_counter() - t0)
            return best
        t = (scanned(2 * iters) - scanned(iters)) / iters
        # an unlucky RTT window can make the subtraction <= 0 (pure
        # noise); record that honestly rather than a negative time
        return t if t > 0 else None

    seqs = [int(x) for x in
            os.environ.get("BENCH_FLASH_SEQS", "512,1024").split(",")]
    # BENCH_FLASH_DTYPE=bfloat16: the in-model wire dtype (models run
    # bf16 QKV; the kernel upcasts tiles to f32 on the MXU) — parity
    # tolerance scales with the wire precision
    bf16 = os.environ.get("BENCH_FLASH_DTYPE") == "bfloat16"
    out["dtype"] = "bfloat16" if bf16 else "float32"
    for s in seqs:
        rng = np.random.RandomState(s)
        q, k, v = [jnp.asarray(rng.randn(2, 8, s, 64).astype(np.float32) * .3,
                               dtype=jnp.bfloat16 if bf16 else jnp.float32)
                   for _ in range(3)]
        flash = jax.jit(lambda a, b, c: flash_attention(
            a, b, c, causal=True, interpret=not compiled))
        dense = jax.jit(lambda a, b, c: dense_attention(a, b, c, True))
        # parity on the direct (unchained) call
        o_f = flash(q, k, v)
        o_d = dense(q, k, v)
        t_f = timed(lambda a, b, c: flash_attention(
            a, b, c, causal=True, interpret=not compiled), q, k, v)
        t_d = timed(lambda a, b, c: dense_attention(a, b, c, True), q, k, v)
        err = float(jnp.max(jnp.abs(
            o_f.astype(jnp.float32) - o_d.astype(jnp.float32))))
        # accumulation error grows with softmax length (measured on chip:
        # 1.8e-3 @ S=1024, 2.1e-3 @ S=2048); a wrong kernel is O(1) off
        tol = (2e-2 if bf16 else 2e-3) * max(1.0, s / 1024)
        assert err < tol, f"flash/dense mismatch at S={s}: {err}"
        ms = lambda t: t * 1e3 if t is not None else None
        out[f"s{s}"] = {"max_abs_err": err, "flash_ms": ms(t_f),
                        "dense_ms": ms(t_d),
                        "speedup": t_d / t_f if t_f and t_d else None}
        # Block-size sweep (BENCH_FLASH_BLOCKS="128,256,512"): the
        # on-chip tuning pass — kernels re-timed per (block_q=block_k=B)
        # and the best recorded, so a chip window directly yields the
        # SPARKDL_FLASH_BLOCK_Q/_K setting to deploy.
        blocks_env = os.environ.get("BENCH_FLASH_BLOCKS")
        if blocks_env:
            sweep = {}
            # t_f above ran with the DEFAULT blocks — env override if
            # set, else the kernel's length-adaptive pick (_default_block;
            # assuming a fixed 128 here would file the adaptive default's
            # timing under the wrong sweep key). Reuse t_f only for that
            # exact config.
            from sparkdl_tpu.ops.flash_attention import _default_block
            env_q = os.environ.get("SPARKDL_FLASH_BLOCK_Q")
            env_k = os.environ.get("SPARKDL_FLASH_BLOCK_K")
            env_blk = (int(env_q) if env_q else _default_block(s),
                       int(env_k) if env_k else _default_block(s))
            for tok in blocks_env.split(","):
                try:
                    blk = int(tok)
                except ValueError:  # stray token must not kill the leg
                    if tok.strip():
                        sweep[tok.strip()[:20]] = "bad_value"
                    continue
                if (blk, blk) == env_blk:
                    sweep[str(blk)] = ms(t_f)
                    continue
                try:
                    t_b = timed(lambda a, b, c, _blk=blk: flash_attention(
                        a, b, c, causal=True, block_q=_blk, block_k=_blk,
                        interpret=not compiled), q, k, v)
                    sweep[str(blk)] = ms(t_b)
                except Exception as e:
                    sweep[str(blk)] = f"{type(e).__name__}"[:60]
            timings = {int(kk): vv for kk, vv in sweep.items()
                       if isinstance(vv, float)}
            out[f"s{s}"]["block_sweep_ms"] = sweep
            if timings:
                out[f"s{s}"]["best_block"] = min(timings, key=timings.get)
    return out


def _worker_generate() -> dict:
    """Llama KV-cache generation throughput — the registerUDF inference
    half of BASELINE configs[4] (config 5). Decode tokens/s on a ~1B-class
    model (random init — zero-egress env; throughput is weight-value-
    independent), plus the EOS early-exit machinery exercised compiled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models.llama import LlamaConfig, LlamaModel, generate

    cfg = (LlamaConfig.tiny()
           if os.environ.get("BENCH_GEN_CONFIG") == "tiny"
           else LlamaConfig.small())
    b = int(os.environ.get("BENCH_GEN_BATCH", "8"))
    lp = int(os.environ.get("BENCH_GEN_PROMPT", "128"))
    new = int(os.environ.get("BENCH_GEN_NEW", "64"))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(b, lp)).astype(np.int32)
    # cache sized to the next 128-slot block multiple: explicit pad_to is
    # honored verbatim by generate(), and the flash decode kernel needs
    # block-tiled caches (flash_decode.supports)
    cache = -(-(lp + new) // 128) * 128
    model = LlamaModel(cfg, dtype=jnp.bfloat16)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    jnp.asarray(ids[:1]))
    # Serving-dtype weight cast (registerGenerationUDF params_dtype):
    # decode is weight-HBM-bound, so f32-stored params would both halve
    # the roofline below and make XLA re-cast+spill the whole tree per
    # dispatch. BENCH_GEN_PARAMS_DTYPE=float32 opts back out.
    params_dtype = os.environ.get("BENCH_GEN_PARAMS_DTYPE", "bfloat16")
    if params_dtype != "float32":
        from sparkdl_tpu.models.pretrained import cast_float_leaves
        variables = cast_float_leaves(variables, params_dtype)

    # Warm BOTH signatures (full and 1-token) so the decode-only number
    # below is compile-free. Decode rate = extra tokens / extra time over
    # the 1-token run — the prefill cost cancels out of the subtraction
    # instead of polluting the "decode tokens/s" metric.
    # pad_to pins one cache size for both run lengths → identical prefill
    # program; only the (warmed) decode scan length differs.
    for warm_new in (1, new):
        _force(generate(model, variables, ids, warm_new, pad_to=cache))

    def timed(n_new, reps=3):
        """Bracket closed by fetching the (small) token array itself
        (_force). The fetch round-trip appears
        identically in the 1-token and n-token runs, so it cancels out of
        the decode-rate subtraction below."""
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = _force(generate(model, variables, ids, n_new,
                                  pad_to=cache))
            best = min(best, time.perf_counter() - t0)
        return out, best

    out1, dt1 = timed(1)
    out, dt = timed(new)
    assert out.shape == (b, lp + new)

    # Decode-only rate via subtraction; when the diff is inside timing
    # noise (tiny models/CPU) the number is meaningless — report null
    # rather than a nonsense rate.
    decode_s = (b * (new - 1) / (dt - dt1)) if dt - dt1 > 1e-4 else None
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(variables))
    # Decode roofline: every step re-reads the whole parameter set from
    # HBM (batch 8's activations are noise next to it), so the decode
    # rate is bounded by b * HBM_bw / param_bytes, with param_bytes from
    # the tree as STORED (post-cast above). Per-step KV-cache reads add
    # to the true denominator, so the bound is optimistic. Provenance
    # note for records WITHOUT gen_params_dtype (windows 1-3): weights
    # were stored f32 and window 3's 2641 tok/s beat the f32-read bound
    # (~1848) — XLA hoists the per-dispatch f32→bf16 cast out of the
    # decode loop, so steps actually read bf16; storing bf16 (the
    # default now) makes stored == read and the recorded bound
    # meaningful.
    hbm = float(os.environ.get("BENCH_HBM_GBPS", "819")) * 1e9
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(variables))
    rec = {"gen_decode_tokens_s": decode_s,
           "gen_decode_roofline_tokens_s": b * hbm / param_bytes,
           "gen_params_dtype": params_dtype,
           "gen_e2e_tokens_s": b * new / dt, "gen_batch": b,
           "gen_prompt_len": lp, "gen_new_tokens": new,
           "gen_wall_s": dt, "gen_prefill_plus_1_s": dt1,
           "gen_model_params": int(n_params)}

    # EOS while_loop leg: the early-exit decode path, compiled on this
    # backend. Replicate row 0 so every row greedily emits the same
    # sequence, then pick as eos_id a token whose FIRST emission lands
    # mid-stream (nearest to new/2): the recorded step count k with
    # 0 < k < new proves the while_loop actually ITERATED k steps and
    # exited — not the degenerate step-0 all-done case where the loop
    # body never runs (round-4 weak #5).
    try:
        same = np.repeat(ids[:1], b, axis=0)
        seq = np.asarray(generate(model, variables, same, new,
                                  pad_to=cache))[0, lp:].tolist()
        first: dict = {}
        for step, tok in enumerate(seq):
            first.setdefault(int(tok), step)
        mid = sorted((s for s in first.values() if 0 < s < new),
                     key=lambda s: abs(s - new // 2))
        k = mid[0] if mid else 0  # no mid-stream first emission: step 0
        eos = next(t for t, s in first.items() if s == k)
        t0 = time.perf_counter()
        _, n_steps = generate(model, variables, same, new, pad_to=cache,
                              eos_id=eos, return_steps=True)
        n_steps = _force(n_steps)  # barrier inside the bracket
        rec["gen_eos_wall_s"] = time.perf_counter() - t0
        rec["gen_eos_steps"] = int(n_steps)
        rec["gen_eos_expected_step"] = k
        # mid-stream: the loop ran 1..new-1 steps, then stopped early
        rec["gen_eos_early_exit"] = 0 < n_steps < new
    except Exception as e:
        rec["gen_eos_error"] = f"{type(e).__name__}: {e}"[:200]

    # Long-context-cache decode ablation: short prompts decoding into a
    # BIG pre-sized cache — registerGenerationUDF's serving shape (one
    # compiled cache size for a whole column). Dense decode reads all
    # max_len cache slots every step; the flash decode kernel's HBM
    # traffic is O(fill level) (dead blocks clamped in the index map, DMA
    # skipped), so the gap here is the kernel's designed win. Models are
    # separate instances because the decode-path choice is baked at trace
    # time (attn_fn "auto" → flash+flash_decode on TPU; None → dense).
    try:
        lc_prompt = int(os.environ.get("BENCH_GEN_LC_PROMPT", "64"))
        lc_cache = int(os.environ.get("BENCH_GEN_LC_CACHE", "4096"))
        lc_new = int(os.environ.get("BENCH_GEN_LC_NEW", "32"))
        ids_lc = rng.randint(0, cfg.vocab_size,
                             size=(b, lc_prompt)).astype(np.int32)
        rec["gen_lc_cache"] = lc_cache
        rec["gen_lc_prompt"] = lc_prompt
        # Whether the "flash" leg really runs the decode kernel: on a
        # non-TPU fallback "auto" resolves to dense and the two legs
        # measure the SAME path — a reader must not mistake that for
        # "the kernel has no win" (cf. flash_attention_default in the
        # train leg).
        from sparkdl_tpu.ops.flash_attention import resolve_attn_fn
        from sparkdl_tpu.ops.flash_decode import decode_fn_for, supports
        rec["gen_lc_flash_decode_active"] = bool(
            decode_fn_for(resolve_attn_fn("auto")) is not None
            and supports(lc_cache))
        for name, m in (("flash", model),
                        ("dense", LlamaModel(cfg, dtype=jnp.bfloat16,
                                             attn_fn=None))):
            for warm_new in (1, lc_new):
                _force(generate(
                    m, variables, ids_lc, warm_new, pad_to=lc_cache))
            best = {}
            for n_new in (1, lc_new):
                t_best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    _force(generate(
                        m, variables, ids_lc, n_new, pad_to=lc_cache))
                    t_best = min(t_best, time.perf_counter() - t0)
                best[n_new] = t_best
            d = best[lc_new] - best[1]
            rec[f"gen_lc_decode_tokens_s_{name}"] = (
                b * (lc_new - 1) / d if d > 1e-4 else None)
    except Exception as e:
        rec["gen_lc_error"] = f"{type(e).__name__}: {e}"[:200]
    return rec


def _worker_host_ingest() -> dict:
    """Backend-free host-ingest rate (ISSUE 7): decode→pack→stage rows/s
    against a STUB device (``scripts/ingest_bench.py``). No jax, no
    backend — this leg measures the host side of the scoring feed and
    records even when the TPU probe fails, so ``BENCH_*`` carries a real
    trajectory number through ``backend_unavailable`` stretches. The
    record embeds the pre-ISSUE-7 feed (``legs.f32_host``) next to the
    new default (``legs.u8_fused``) — before/after on the same workload."""
    # Default NOT divisible by the 64-row bench batch: the tail chunk is
    # what exercises the StagingPool (see scripts/ingest_bench.py).
    rows = int(os.environ.get("BENCH_INGEST_ROWS", "1000"))
    return _load_script_module("ingest_bench.py").run(rows=rows)


def _load_script_module(name: str):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name.replace(".py", ""), os.path.join(_HERE, "scripts", name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _elastic_block(budget=None) -> dict:
    """Elastic-supervision evidence (ISSUE 16) for ``failure_stats``: the
    jax-free policy leg from ``scripts/elastic_smoke.py`` — a stdlib
    worker gang loses one rank PERMANENTLY (``decimate``), the supervisor
    shrinks it without burning restart budget, and the batch ledger is
    audited for exactly-once replay across the resize. Zero jax in the
    supervisor or workers, so the block rides ``backend_unavailable``
    records too. ``BENCH_SKIP_ELASTIC=1`` skips; the leg costs ~30s of
    gang relaunches, so it also yields when the wall budget is nearly
    spent; any failure is reported in-band — this leg must never kill a
    bench record."""
    if os.environ.get("BENCH_SKIP_ELASTIC"):
        return {"skipped": "env"}
    if budget is not None and budget.remaining() < 90:
        return {"skipped": "budget",
                "detail": f"{budget.remaining():.0f}s left"}
    t0 = time.monotonic()
    try:
        return _load_script_module("elastic_smoke.py").policy_block()
    except Exception as e:  # noqa: BLE001 — in-band, never fatal
        return {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        if budget is not None:
            budget.leg_times["elastic"] = round(time.monotonic() - t0, 1)


def _quant_block(budget=None) -> dict:
    """Quantized-serving evidence (ISSUE 18) for ``failure_stats``: the
    ``scripts/serve_smoke.py quant_block`` leg — a paged + speculative
    tiny-llama engine at int8 KV + int8 weights vs f32 on CPU, yielding
    the greedy-stream agreement (gate >= 0.8 lcp fraction), the
    speculative accept-rate pair + delta (the end-to-end quality
    monitor) and the pool-blocks multiplier at equal ``kv_pool_mb``.
    ``BENCH_SKIP_QUANT=1`` skips; the leg costs ~1 min of tiny-model
    CPU serving, so it yields when the wall budget is nearly spent;
    any failure is reported in-band — never fatal to the record."""
    if os.environ.get("BENCH_SKIP_QUANT"):
        return {"skipped": "env"}
    if budget is not None and budget.remaining() < 120:
        return {"skipped": "budget",
                "detail": f"{budget.remaining():.0f}s left"}
    t0 = time.monotonic()
    try:
        return _load_script_module("serve_smoke.py").quant_block()
    except Exception as e:  # noqa: BLE001 — in-band, never fatal
        return {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        if budget is not None:
            budget.leg_times["quant"] = round(time.monotonic() - t0, 1)


def _worker_serve() -> dict:
    """Continuous-batching serving leg (ISSUE 8): aggregate tokens/s at
    closed-loop concurrency 1/8/32 through ``serving.GenerationEngine``
    vs the static whole-batch ``generate()`` path on the same workload,
    with latency percentiles from the telemetry histograms and the
    no-decode-retrace pin (``scripts/serve_bench.py``)."""
    return _load_script_module("serve_bench.py").run(mode="llama")


def _worker_serve_stub() -> dict:
    """Scheduler-only serving leg on the jax-free ``StubBackend`` with a
    synthetic per-step device time — queue/slot mechanics and the
    batching win stay measured inside a ``backend_unavailable`` record
    (the same never-host-blind rule as the host-ingest leg)."""
    return _load_script_module("serve_bench.py").run(mode="stub")


def _serve_headline(serve: dict) -> dict:
    """The ISSUE 8/10 headline numbers pulled from a serve-bench record:
    aggregate tokens/s at the highest measured concurrency, prefix-cache
    hit rate and prefill-induced decode-stall seconds right next to it
    (the stall-free scheduler's before/after must be readable without
    digging into the legs), and the stall-free-vs-blocking ratios. Used
    by BOTH the healthy-backend record and the backend_unavailable
    error record."""
    top = max((serve.get("engine") or {}).items(),
              key=lambda kv: int(kv[0]), default=(None, {}))[1]
    out = {"serve_tokens_s": top.get("tokens_s"),
           "serve_decode_stall_s": top.get("decode_stall_s"),
           "serve_prefix_cache_hit_rate":
               (top.get("prefix_cache") or {}).get("hit_rate")}
    # ISSUE 13: SLO compliance + the slowest request's phase breakdown
    # ride the headline in BOTH the healthy and backend_unavailable
    # records (never-host-blind rule) — the bench states compliance,
    # not just percentiles, and the attribution residual proves the
    # trace phases sum to measured latency.
    leg_slo = top.get("slo") or {}
    out["serve_slo_ttft_compliance"] = leg_slo.get("ttft_compliance")
    out["serve_slo_latency_compliance"] = \
        leg_slo.get("latency_compliance")
    if top.get("slowest_trace") is not None:
        out["serve_slowest_trace"] = top["slowest_trace"]
    ta = top.get("trace_attribution") or {}
    if ta.get("max_unattributed_frac") is not None:
        out["serve_trace_max_unattributed_frac"] = \
            ta["max_unattributed_frac"]
    for k in ("speedup_vs_blocking", "ttft_p99_ratio",
              "decode_stall_ratio"):
        if serve.get(k) is not None:
            out[f"serve_{k}"] = serve[k]
    # ISSUE 11: the paged-KV high-churn evidence (jax-free stub leg,
    # rides both healthy and backend_unavailable records) — pool
    # utilization, shared-block fraction, admission-wait stats and the
    # paged-vs-per-slot speedup at fixed pool bytes.
    churn = serve.get("churn") or {}
    for src, dst in (("paged_speedup", "serve_paged_speedup"),
                     ("kv_pool_utilization", "serve_kv_pool_utilization"),
                     ("blocks_shared_frac", "serve_blocks_shared_frac"),
                     ("admission_block_waits",
                      "serve_admission_block_waits"),
                     ("preemptions", "serve_preemptions")):
        if churn.get(src) is not None:
            out[dst] = churn[src]
    # ISSUE 15: paged flash-decode kernel headline. The churn sub-leg
    # (stub, rides BOTH records) carries the scheduler-invariance
    # tokens/s ratio + the deterministic attention-bytes model; the
    # healthy llama record additionally carries the real-kernel leg's
    # token identity and CPU (interpret-mode) tokens/s ratio — the
    # on-chip speedup claim is the next TPU probe's.
    pk = churn.get("paged_kernel") or {}
    # serve_paged_kernel_speedup is the MODELED HBM number (>= 1.0 by
    # construction; decode is bandwidth-bound so bytes ratio ~ modeled
    # speedup) — the stub's measured on/off pair is an A/A
    # scheduler-invariance check and is deliberately NOT forwarded as
    # a speedup (see the leg's honest_label).
    for src, dst in (("modeled_hbm_speedup",
                      "serve_paged_kernel_speedup"),
                     ("attn_bytes_ratio",
                      "serve_paged_kernel_attn_bytes_ratio")):
        if pk.get(src) is not None:
            out[dst] = pk[src]
    # ISSUE 18: quantized-KV bytes model from the churn sub-leg — the
    # per-step f32/quant traffic multiplier at equal positions read
    # (>= 2x acceptance for int8). Named *_x, NOT *_ratio: bench_trend
    # infers direction from the name and this one is higher-is-better.
    if pk.get("kv_quant_bytes_ratio") is not None:
        out["serve_kv_quant_bytes_x"] = pk["kv_quant_bytes_ratio"]
    lpk = serve.get("paged_kernel") or {}
    if lpk.get("token_identical") is not None:
        out["serve_paged_kernel_token_identical"] = lpk["token_identical"]
    if lpk.get("cpu_speedup") is not None:
        out["serve_paged_kernel_cpu_speedup"] = lpk["cpu_speedup"]
    # ISSUE 12: speculative-decoding headline — single-stream tokens/s
    # over the k=0 engine on the high-acceptance mix, and the top-k
    # leg's draft acceptance rate (rides healthy AND outage records).
    spec = serve.get("spec") or {}
    for src, dst in (("spec_speedup", "serve_spec_speedup"),
                     ("spec_accept_rate", "serve_spec_accept_rate"),
                     ("spec_mean_accept_len",
                      "serve_spec_mean_accept_len")):
        if spec.get(src) is not None:
            out[dst] = spec[src]
    # ISSUE 19: survivability headline — recovery latency for one
    # injected failover and the exactly-once token-identity gate (a
    # float, 1.0 = every faulted stream matched the clean run, so
    # bench_trend's numeric gating covers it; _s suffix makes
    # recovery auto lower-is-better). Stub leg, rides healthy AND
    # backend_unavailable records.
    surv = serve.get("survivability") or {}
    if surv.get("recovery_s") is not None:
        out["serve_recovery_s"] = surv["recovery_s"]
    if surv.get("token_identical") is not None:
        out["serve_failover_token_identical"] = surv["token_identical"]
    # ISSUE 20: fleet headline — kill-to-first-re-admitted-token latency
    # and the cross-replica exactly-once gate (same float convention as
    # the engine-level pair above), plus the radix-vs-round-robin
    # fleet-wide prefix reuse ratio. Stub leg, rides healthy AND
    # backend_unavailable records.
    flt = serve.get("fleet") or {}
    if flt.get("recovery_s") is not None:
        out["fleet_recovery_s"] = flt["recovery_s"]
    if flt.get("token_identical") is not None:
        out["fleet_token_identical"] = flt["token_identical"]
    if flt.get("reuse_ratio") is not None:
        out["fleet_prefix_reuse_ratio"] = flt["reuse_ratio"]
    # ISSUE 14: tensor-parallel headline — greedy identity across the
    # tp degrees, per-device KV pool bytes (the 1/tp shrink), and
    # zero-re-trace evidence, from the 8-virtual-device subprocess leg
    # (semantics/economics only — see the leg's honest_label).
    tp = serve.get("tp") or {}
    if tp.get("tp_identical") is not None:
        out["serve_tp_identical"] = tp["tp_identical"]
    if tp.get("kv_pool_device_bytes"):
        out["serve_tp_kv_pool_device_bytes"] = tp["kv_pool_device_bytes"]
    if tp.get("kv_pool_device_frac"):
        out["serve_tp_kv_pool_device_frac"] = tp["kv_pool_device_frac"]
    retr = [leg.get("decode_retrace_after_warmup", 0)
            + leg.get("verify_retrace_after_warmup", 0)
            for leg in (tp.get("degrees") or {}).values()]
    if retr:
        out["serve_tp_retraces_after_warmup"] = sum(retr)
    return out


_WORKERS = {"resnet50_train": _worker_resnet50_train,
            "host_ingest": _worker_host_ingest,
            "featurizer": _worker_featurizer,
            "bert_train": _worker_bert_train,
            "flash": _worker_flash,
            "generate": _worker_generate,
            "serve": _worker_serve,
            "serve_stub": _worker_serve_stub,
            "northstar": _worker_northstar,
            "probe": _worker_probe}


# ---------------------------------------------------------------------------
# Orchestrator
# ---------------------------------------------------------------------------

def _classify_failure(text: str) -> str:
    """Retryable vs fatal, by the runner's failure taxonomy (works on the
    child's stderr text so a dead child can still be classified). The
    policy lives in failures.classify_text — one regex set shared with the
    gang supervisor, so bench retries and supervise restarts can't drift."""
    try:
        from sparkdl_tpu.runner.failures import classify_text
        return classify_text(text)
    except Exception:
        return "retryable"


def _headline_config() -> dict:
    """The knobs that change the headline number. Stored inside
    BENCH_BASELINE.json and compared on read, so a knob-degraded smoke run
    can never silently poison vs_baseline for a default run (or vice
    versa)."""
    return {"batch_per_chip": os.environ.get("BENCH_BATCH_PER_CHIP",
                                             "64,128,256"),
            "steps": os.environ.get("BENCH_STEPS", "20"),
            "model": os.environ.get("BENCH_MODEL", "ResNet50"),
            "image_size": os.environ.get("BENCH_IMAGE_SIZE", "224"),
            # methodology is part of the config: numbers timed with the
            # old block_until_ready bracket must never be the denominator
            # of a host-fetch-timed run's vs_baseline
            "timing": "host_fetch"}


class _Budget:
    """Overall wall-clock budget. A hung backend must cost at most the
    probe timeout, and the record must print before the driver's own
    window closes — never again a SIGKILL mid-retry with `parsed: null`
    (round-3 headline failure)."""

    def __init__(self, wall_s: float):
        self.wall_s = wall_s
        self.t0 = time.monotonic()
        self.leg_times: dict = {}  # leg name -> wall seconds
        # Driver-level failure ledger (routed into the record next to the
        # workers' own run_stats — ISSUE 1: the emitted JSON reports
        # restarts / faults_injected / last_failure_kind).
        self.restarts = 0
        self.last_failure_kind: str | None = None

    def remaining(self) -> float:
        return self.wall_s - (time.monotonic() - self.t0)

    def spent(self) -> float:
        return time.monotonic() - self.t0


def _run_worker(name: str, timeout_s: float, retries: int,
                budget: _Budget) -> tuple[dict | None, dict | None]:
    """Run one metric in a subprocess with timeout+retries, clamped to the
    remaining wall budget. Leg wall time lands on ``budget.leg_times``
    (serialized under extra["budget"]["leg_times_s"]).

    Returns (result, error): exactly one is non-None."""
    t_leg = time.monotonic()
    try:
        return _run_worker_inner(name, timeout_s, retries, budget)
    finally:
        budget.leg_times[name] = round(time.monotonic() - t_leg, 1)


def _run_worker_inner(name: str, timeout_s: float, retries: int,
                      budget: _Budget) -> tuple[dict | None, dict | None]:
    last_err: dict = {}
    for attempt in range(retries + 1):
        if attempt:
            backoff = min(15.0 * (2 ** (attempt - 1)), 60.0)
            if budget.remaining() < backoff + 90:
                last_err = {"kind": "budget_exhausted",
                            "detail": f"no budget for retry {attempt} "
                                      f"({budget.remaining():.0f}s left); "
                                      f"last error: {last_err}"[:400]}
                break
            print(f"bench[{name}]: retry {attempt}/{retries} "
                  f"after {backoff:.0f}s", file=sys.stderr)
            time.sleep(backoff)
            budget.restarts += 1
        # Leave ~30s of slack for the driver to assemble + print the record.
        attempt_timeout = min(timeout_s, budget.remaining() - 30)
        if attempt_timeout < min(timeout_s, 30):
            last_err = last_err or {
                "kind": "budget_exhausted",
                "detail": f"{budget.remaining():.0f}s of "
                          f"{budget.wall_s:.0f}s budget left"}
            break
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--worker", name],
                capture_output=True, text=True, timeout=attempt_timeout,
                cwd=_HERE)
        except subprocess.TimeoutExpired:
            last_err = {"kind": "timeout",
                        "detail": f"worker exceeded {attempt_timeout:.0f}s "
                                  "(backend init hang?)"}
            budget.last_failure_kind = "timeout"
            if attempt_timeout >= 300:
                # A LONG timeout is a hang, not a transient blip:
                # retrying would burn another long attempt and starve the
                # remaining legs of the wall budget (the cheap flash
                # proof leg must still land). Short-timeout legs (the
                # probe-scale ones) keep their retry.
                last_err["detail"] += "; not retried (long attempt)"
                break
            continue  # short timeouts are retryable (budget permitting)
        if proc.returncode == 0:
            for line in reversed(proc.stdout.strip().splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        return json.loads(line), None
                    except json.JSONDecodeError:
                        break
            last_err = {"kind": "bad_output", "detail": proc.stdout[-500:]}
        else:
            tail = (proc.stderr or proc.stdout or "")[-2000:]
            kind = _classify_failure(tail)
            last_err = {"kind": kind, "rc": proc.returncode,
                        "detail": tail[-500:]}
            budget.last_failure_kind = kind
            if kind == "fatal":
                break  # a program bug won't fix itself on retry
    return None, last_err


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        # Child mode: run one metric, print its JSON line.
        hang = float(os.environ.get("BENCH_FAKE_HANG_S", "0"))
        if hang:  # hardening-test knob: simulate the hung-backend outage
            time.sleep(hang)
        result = _WORKERS[sys.argv[2]]()
        try:
            # Worker-side failure/chaos ledger rides the result (only when
            # something actually happened — the common all-zero snapshot
            # would just be noise in every leg).
            from sparkdl_tpu.runner.metrics import (global_step_stats,
                                                    run_stats)
            # degraded() also covers the ISSUE 4 data-plane counters
            # (rows_quarantined / dispatch_retries / checkpoint_rollbacks)
            # so a leg that survived faults carries its ledger.
            if isinstance(result, dict) and run_stats.degraded():
                result.setdefault("failure_stats", run_stats.snapshot())
            # Step-time percentiles (ISSUE 2): whatever trained through a
            # metered loop in this worker recorded into the process-wide
            # reservoir — p50/p95/p99/max ride the record next to the
            # mean-throughput numbers.
            st = global_step_stats.summary()
            if isinstance(result, dict) and st:
                result.setdefault("step_time", st)
            # Anomaly-sentinel verdicts (ISSUE 17): per-metric counts of
            # rolling-p95 drift events the worker's sentinel fired —
            # only when it fired, same no-noise rule as run_stats.
            from sparkdl_tpu.runner import sentinel
            an = sentinel.anomaly_counts()
            if isinstance(result, dict) and an:
                result.setdefault("failure_stats",
                                  {})["sentinel_anomalies"] = an
        except Exception:
            pass
        print(json.dumps(result))
        return

    budget = _Budget(float(os.environ.get("BENCH_WALL_S", "1200")))
    # 720 default: roomy for the resnet leg (3-point AOT sweep + one
    # batch size of feed variants); the overall wall budget still clamps
    # every attempt, so a per-leg timeout cannot blow the record deadline.
    timeout_s = float(os.environ.get("BENCH_TIMEOUT_S", "720"))
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "180"))
    retries = int(os.environ.get("BENCH_RETRIES", "1"))

    extra: dict = {}

    # ---- Fail-fast liveness probe (no retries: a hung init stays hung) ----
    probe, probe_err = _run_worker("probe", probe_timeout, 0, budget)
    if probe:
        extra["backend"] = probe
    else:
        err_extra = {"probe_error": probe_err}
        # The backend is down, but the HOST is not: the jax-free ingest
        # leg still measures (ISSUE 7) so the record is never blind on
        # the host-side trajectory during an outage. Same skip knob as
        # the healthy-backend path.
        if os.environ.get("BENCH_SKIP_INGEST"):
            ingest_rec, ingest_err = None, {"kind": "skipped",
                                            "detail": "env"}
        else:
            ingest_rec, ingest_err = _run_worker("host_ingest",
                                                 probe_timeout, 0, budget)
        if ingest_rec:
            err_extra["host_ingest"] = ingest_rec
        elif ingest_err:
            err_extra["host_ingest_error"] = ingest_err
        # The serving leg rides the outage record too (ISSUE 8 satellite,
        # same never-host-blind rule), as the jax-free stub leg only:
        # scheduler throughput under its own name. The real-model leg
        # needs the backend that is down — a CPU re-run would put a CPU
        # number under the names a chip run uses.
        if os.environ.get("BENCH_SKIP_SERVE"):
            serve_stub, stub_err = None, {"kind": "skipped",
                                          "detail": "env"}
        else:
            serve_stub, stub_err = _run_worker("serve_stub",
                                               probe_timeout, 0, budget)
        if serve_stub:
            err_extra["serving_stub"] = serve_stub
        elif stub_err:
            err_extra["serving_stub_error"] = stub_err
        # Elastic policy evidence survives the outage too (ISSUE 16):
        # supervisor + stdlib workers, no jax anywhere in the leg.
        err_extra["failure_stats"] = {"elastic": _elastic_block(budget)}
        err_extra["budget"] = {"wall_s": budget.wall_s,
                               "spent_s": round(budget.spent(), 1),
                               "leg_times_s": dict(budget.leg_times)}
        record = {
            "metric": "resnet50_dp_train_throughput",
            "value": 0.0, "unit": "img/s/chip", "vs_baseline": 0.0,
            "extra": err_extra,
            "error": {"kind": "backend_unavailable",
                      "detail": f"liveness probe failed "
                                f"({probe_err.get('kind')}): backend did "
                                f"not come up within "
                                f"{probe_timeout:.0f}s — no metric "
                                f"attempted. {probe_err.get('detail', '')}"
                                [:600]},
        }
        print(json.dumps(record))
        return

    # ---- Metric legs, headline first; each clamped to remaining budget ----
    train, train_err = _run_worker("resnet50_train", timeout_s, retries,
                                   budget)

    def leg(name: str, skip_env: str):
        if os.environ.get(skip_env):
            return None, {"kind": "skipped", "detail": "env"}
        return _run_worker(name, timeout_s, retries, budget)

    # flash runs before bert/gen: it is the cheapest leg and carries the
    # compiled-kernel evidence — if the budget runs dry, lose a throughput
    # number, not the proof.
    # host-ingest first: cheapest leg, jax-free, and the ISSUE 7
    # before/after evidence — never starved by the heavy legs.
    ingest_rec, ingest_err = leg("host_ingest", "BENCH_SKIP_INGEST")
    feat, feat_err = leg("featurizer", "BENCH_SKIP_FEATURIZER")
    flash, flash_err = leg("flash", "BENCH_SKIP_FLASH")
    bert, bert_err = leg("bert_train", "BENCH_SKIP_BERT")
    gen, gen_err = leg("generate", "BENCH_SKIP_GEN")
    serve, serve_err = leg("serve", "BENCH_SKIP_SERVE")
    # north-star scale leg: opt-in (expensive), LAST so it can only
    # starve itself of budget, never the headline legs
    ns, ns_err = (None, None)
    if int(os.environ.get("BENCH_NORTHSTAR_ROWS", "0")) > 0:
        ns, ns_err = _run_worker("northstar", timeout_s, retries, budget)

    if train:
        extra.update({k: round(v, 6) if isinstance(v, float) else v
                      for k, v in train.items() if k != "img_s_chip"})
    if ingest_rec:
        extra["host_ingest"] = ingest_rec
    elif ingest_err:
        extra["host_ingest_error"] = ingest_err
    if feat:
        extra["featurizer_rows_per_sec"] = round(feat["rows_per_sec"], 2)
        extra["featurizer_config"] = {
            k: feat[k] for k in ("rows", "batch_size", "compute_dtype",
                                 "native_packer")}
        extra["featurizer_breakdown"] = feat.get("breakdown", {})
        # The inference-throughput record, next to the training one: the
        # streaming engine's rate + per-stage span breakdown (ISSUE 3).
        extra["inference"] = {
            "rows_per_sec": round(feat["rows_per_sec"], 2),
            "decode_workers": feat.get("decode_workers"),
            "stage_seconds": feat.get("stage_seconds", {}),
            # ISSUE 6: per-stage busy fractions + dominant stage, so the
            # per-revision record carries bottleneck attribution.
            "stage_utilization": feat.get("stage_utilization")}
    elif feat_err:
        extra["featurizer_error"] = feat_err
    if bert:
        extra.update({k: round(v, 6) if isinstance(v, float) else v
                      for k, v in bert.items()})
    elif bert_err:
        extra["bert_error"] = bert_err
    if gen:
        extra.update({k: round(v, 6) if isinstance(v, float) else v
                      for k, v in gen.items()})
    elif gen_err:
        extra["gen_error"] = gen_err
    if serve:
        extra.update(_serve_headline(serve))
        extra["serving"] = serve
    elif serve_err:
        extra["serving_error"] = serve_err
    if flash:
        extra["flash"] = flash
    elif flash_err:
        extra["flash_error"] = flash_err
    if ns:
        extra.update({k: round(v, 3) if isinstance(v, float) else v
                      for k, v in ns.items()})
    elif ns_err:
        extra["northstar_error"] = ns_err

    value = float(train["img_s_chip"]) if train else 0.0
    # vs_baseline: 0.0 = hard failure, null = ran but no stored baseline
    # to compare against (round-4 weak #3: reporting 1.0 with no baseline
    # read as "matches baseline"), a real ratio otherwise.
    vs = 0.0 if not train else None
    # BENCH_BASELINE_PATH override: tests point this at a temp path so
    # the CPU smoke run neither reads a real chip baseline (which would
    # yield a nonsense CPU/TPU ratio) nor depends on repo state
    base_path = os.environ.get("BENCH_BASELINE_PATH") or \
        os.path.join(_HERE, "BENCH_BASELINE.json")
    prior = None
    if os.path.exists(base_path):
        try:
            prior = json.load(open(base_path))
        except (ValueError, OSError):
            prior = None
    if train and prior and prior.get("value"):
        if prior.get("config", _headline_config()) != _headline_config():
            extra["baseline_ignored"] = {
                "reason": "config mismatch", "stored": prior.get("config")}
        else:
            vs = value / float(prior["value"])
            extra["last_good"] = {"value": prior["value"],
                                  "ts_unix": prior.get("ts_unix")}
    if train and vs is None:
        extra["baseline"] = "none"

    # Methodology marker: all timing brackets close on a host fetch of a
    # small dependent array (_force). Records without this key (r02,
    # BENCH_TPU_MEASURED/2) used block_until_ready brackets.
    extra["timing_barrier"] = "host_fetch"
    # Failure/recovery ledger (ISSUE 1): driver-level retry restarts plus
    # whatever the workers' run_stats recorded (chaos injections, in-worker
    # run_with_restarts), so the record shows HOW the number was survived.
    fs = {"restarts": budget.restarts, "faults_injected": 0,
          "last_failure_kind": budget.last_failure_kind}
    sentinel_counts: dict = {}
    for r in (train, feat, flash, bert, gen, serve, ns):
        ws = (r or {}).get("failure_stats") if isinstance(r, dict) else None
        if isinstance(ws, dict):
            fs["restarts"] += int(ws.get("restarts") or 0)
            fs["faults_injected"] += int(ws.get("faults_injected") or 0)
            fs["last_failure_kind"] = (ws.get("last_failure_kind")
                                       or fs["last_failure_kind"])
            # Sentinel anomaly counts (ISSUE 17): summed per metric
            # across the worker legs that fired any.
            for k, v in (ws.get("sentinel_anomalies") or {}).items():
                sentinel_counts[k] = sentinel_counts.get(k, 0) + int(v)
    if sentinel_counts:
        fs["sentinel_anomalies"] = sentinel_counts
    # Elastic gang supervision (ISSUE 16): resizes / final world size /
    # exactly-once verdict from the jax-free policy leg.
    fs["elastic"] = _elastic_block(budget)
    # Quantized serving (ISSUE 18): int8-vs-f32 greedy agreement,
    # accept-rate delta and the equal-MB pool-blocks multiplier. The
    # numeric scalars ALSO land top-level in extra so bench_trend's
    # series gate watches them (nested failure_stats dicts are not
    # picked up by its extra[] scan): *_x / *_frac read higher-is-
    # better, the accept delta is named *_skew so the trend gate
    # treats growth as a regression.
    fs["quant"] = _quant_block(budget)
    q = fs["quant"]
    if isinstance(q, dict) and not q.get("error") \
            and not q.get("skipped"):
        for src, dst in (
                ("token_match_frac", "serve_quant_token_match_frac"),
                ("effective_blocks_x", "serve_quant_effective_blocks_x"),
                ("accept_rate_delta", "serve_quant_accept_skew")):
            if isinstance(q.get(src), (int, float)):
                extra[dst] = q[src]
    extra["failure_stats"] = fs
    extra["budget"] = {"wall_s": budget.wall_s,
                       "spent_s": round(budget.spent(), 1),
                       # per-leg wall seconds: shows how the budget was
                       # spent and which leg to trim if it ever overruns
                       "leg_times_s": dict(budget.leg_times)}
    try:  # map the numbers to the code that produced them
        extra["git_rev"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=_HERE, timeout=10).stdout.strip() or None
    except Exception:
        pass

    record = {
        "metric": "resnet50_dp_train_throughput",
        "value": round(value, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(vs, 3) if vs is not None else None,
        "extra": extra,
    }
    if train_err:
        record["error"] = train_err
    print(json.dumps(record))

    # Persist the last good run so the next round's vs_baseline is real
    # (round-3 weak #1: BENCH_BASELINE.json was read but never written).
    # TPU-only: a CPU smoke run must not poison the chip-to-chip ratio.
    if train and extra.get("backend", {}).get("is_tpu"):
        try:
            with open(base_path, "w") as f:
                json.dump({"value": record["value"],
                           "ts_unix": int(time.time()),
                           "config": _headline_config(),
                           "extra": {k: extra.get(k) for k in
                                     ("mfu", "featurizer_rows_per_sec",
                                      "bert_tokens_s_chip",
                                      "batch_per_chip")}},
                          f)
        except OSError as e:
            print(f"bench: could not write BENCH_BASELINE.json: {e}",
                  file=sys.stderr)


if __name__ == "__main__":
    main()
