"""Bench orchestrator contract tests (round-3 verdict Next #1).

The hard requirement: bench.py ALWAYS prints exactly one parsed JSON
record, fast, whatever the backend does — a hung backend (the r01/r03
outage) must produce a machine-readable error within the probe timeout,
and an exhausted wall budget must surface as budget_exhausted, never as
silence or a SIGKILL with no record.

These run bench.py as a real subprocess with the test env inherited
(conftest pins JAX_PLATFORMS=cpu), so the probe worker exercises the same
code path the driver does.
"""

import json
import os
import subprocess
import sys
import time

import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _run(env_extra: dict, timeout: float = 120) -> dict:
    env = dict(os.environ)
    env.update(env_extra)
    proc = subprocess.run([sys.executable, _BENCH], capture_output=True,
                          text=True, timeout=timeout, env=env)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.strip().startswith("{")]
    assert lines, f"no JSON record printed; stdout={proc.stdout[-400:]!r} " \
                  f"stderr={proc.stderr[-400:]!r}"
    return json.loads(lines[-1])


def test_hung_backend_yields_error_record_fast():
    """Simulated hang (every worker sleeps): the record must print within
    roughly the probe timeout, with the outage machine-readable."""
    t0 = time.monotonic()
    rec = _run({"BENCH_FAKE_HANG_S": "300", "BENCH_PROBE_TIMEOUT_S": "5",
                "BENCH_WALL_S": "60"})
    wall = time.monotonic() - t0
    assert rec["value"] == 0.0
    assert rec["vs_baseline"] == 0.0
    assert rec["error"]["kind"] == "backend_unavailable"
    assert rec["extra"]["probe_error"]["kind"] == "timeout"
    assert wall < 30, f"error record took {wall:.0f}s"
    # no real-model serving numbers without the backend: only the
    # jax-free stub leg may ride the outage record
    assert "serving" not in rec["extra"]
    assert "serve_tokens_s" not in rec["extra"]


def test_exhausted_budget_yields_error_record():
    """A wall budget too small for even the probe must still produce the
    record, flagged budget_exhausted."""
    rec = _run({"BENCH_WALL_S": "1"})
    assert rec["value"] == 0.0
    assert rec["error"]["kind"] == "backend_unavailable"
    assert rec["extra"]["probe_error"]["kind"] == "budget_exhausted"


def _load_serve_bench():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(os.path.dirname(_BENCH), "scripts",
                                    "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    return sb


def _retry_once(run, ok):
    """Wall-clock stub comparisons ride time.sleep() on a shared CI
    host: one retry absorbs a loaded-host scheduling hiccup without
    weakening the floors (both attempts must run the SAME deterministic
    workload — flakiness here is timer noise, never workload noise)."""
    rec = run()
    if ok(rec):
        return rec
    return run()


def test_stub_scheduler_stall_free_beats_blocking():
    """ISSUE 10 regression pin without hardware: on the long-prompt mix
    with deterministic synthetic device costs (jax-free StubBackend),
    the stall-free scheduler (chunked prefill + shared-prefix reuse)
    must beat the PR 8 blocking engine on aggregate tokens/s (floor
    1.2x — bench-record target 1.3x), cut prefill-induced decode-stall
    wall time (floor 2.5x — record target 5x), and improve TTFT p99
    (floor 1.2x — record target 2x)."""
    sb = _load_serve_bench()
    rec = _retry_once(
        lambda: sb.run_stub_scheduler_comparison(n_requests=96),
        lambda r: (r["speedup_vs_blocking"] >= 1.2
                   and r["decode_stall_ratio"] >= 2.5
                   and r["ttft_p99_ratio"] >= 1.2))
    assert rec["speedup_vs_blocking"] >= 1.2, rec
    assert rec["decode_stall_ratio"] >= 2.5, rec
    assert rec["ttft_p99_ratio"] >= 1.2, rec
    # the win comes from the prefix cache + chunking, and the record
    # proves it: warm traffic hits the cache
    assert rec["prefix_cache"]["hit_rate"] >= 0.5, rec["prefix_cache"]


def test_paged_engine_beats_per_slot_on_high_churn():
    """ISSUE 11 regression pin without hardware: at FIXED pool bytes on
    the short-output high-churn mix, the paged 32-slot engine must beat
    the PR 9 per-slot 8-slot engine on tokens/s (floor 1.3x), run the
    pool hot (peak utilization >= 0.8 — throughput is bounded by pool
    bytes, not max_len x slots), and hold the shared preamble as ONE
    physical block set (blocks_shared_frac > 0)."""
    sb = _load_serve_bench()
    rec = _retry_once(
        lambda: sb.run_paged_churn_comparison(n_requests=192),
        lambda r: (r.get("paged_speedup", 0) >= 1.3
                   and (r.get("kv_pool_utilization") or 0) >= 0.8))
    assert rec["paged_speedup"] >= 1.3, rec
    assert rec["kv_pool_utilization"] >= 0.8, rec
    assert rec["blocks_shared_frac"] > 0, rec
    assert rec["paged"]["completed"] == rec["paged"]["requests"], rec
    # the admission-wait stats ride the record (healthy pool: ~0; a
    # too-small pool shows up here instead of as a crash)
    assert "admission_block_waits" in rec and "preemptions" in rec


def test_stub_spec_leg_beats_k0_engine():
    """ISSUE 12 regression pin without hardware: on the repetitive-text
    mix (small-vocab stub streams are periodic, so the request's own
    output is self-predictive — the default n-gram provider's home
    turf), the k=4 speculative engine must beat the k=0 engine >= 1.5x
    single-stream tokens/s (bench-record target 2x on the CPU-llama
    leg), with a sane draft-acceptance floor and token-identical
    output."""
    sb = _load_serve_bench()
    rec = _retry_once(
        lambda: sb.run_spec_comparison_stub(
            n_requests=16, ks=(0, 4), concurrencies=(1,),
            step_s=0.0015, n_new=32),
        lambda r: r.get("spec_speedup", 0) >= 1.5)
    assert rec["spec_speedup"] >= 1.5, rec
    assert rec["spec_accept_rate"] >= 0.3, rec  # acceptance sanity floor
    assert rec["spec_token_identical"] is True, rec


def test_serve_headline_carries_tp_fields():
    """ISSUE 14: the tp leg's identity / per-device-pool-bytes /
    re-trace evidence must ride ``_serve_headline`` into BOTH the
    healthy and backend_unavailable records (never-host-blind rule) —
    jax-free mapping pin on a synthetic serve record."""
    import bench

    serve = {
        "engine": {"8": {"tokens_s": 100.0}},
        "tp": {
            "tp_identical": True,
            "kv_pool_device_bytes": {"1": 1000, "2": 500, "4": 250},
            "kv_pool_device_frac": {"1": 1.0, "2": 0.5, "4": 0.25},
            "degrees": {
                "1": {"decode_retrace_after_warmup": 0,
                      "verify_retrace_after_warmup": 0},
                "2": {"decode_retrace_after_warmup": 0,
                      "verify_retrace_after_warmup": 0},
            },
        },
    }
    out = bench._serve_headline(serve)
    assert out["serve_tp_identical"] is True
    assert out["serve_tp_kv_pool_device_bytes"]["4"] == 250
    assert out["serve_tp_kv_pool_device_frac"]["2"] == 0.5
    assert out["serve_tp_retraces_after_warmup"] == 0
    # a tp-less record (BENCH_SKIP_TP / subprocess failure) adds none
    assert "serve_tp_identical" not in bench._serve_headline(
        {"engine": {}})


def test_multi_chunk_budget_admits_multiple_slots_per_iteration():
    """The ISSUE 11 budget pin: where the one-chunk PR 9 budget fills 1
    slot per iteration, SPARKDL_SERVE_PREFILL_BUDGET = 2 chunks fills
    2 — jax-free, deterministic (no sleeps)."""
    from sparkdl_tpu.serving import GenerationEngine, StubBackend

    def refills_completed_after_one_iteration(budget):
        eng = GenerationEngine(
            StubBackend(4, 64, vocab_size=100, block_size=4,
                        pool_blocks=80),
            prefill_chunk=4, prefill_budget=budget)
        for b in (1, 20, 40):  # one-chunk prompts: 1 chunk = 1 refill
            eng.submit(list(range(b, b + 4)), max_new_tokens=1)
        eng.step()
        done = eng.snapshot()["prefills"]
        eng.run_until_idle()
        return done

    assert refills_completed_after_one_iteration(None) == 1  # PR 9 cap
    assert refills_completed_after_one_iteration(8) == 2     # 2 slots
    assert refills_completed_after_one_iteration(12) == 3    # 3 slots


@pytest.mark.slow
def test_all_metric_legs_run_end_to_end_tiny_cpu():
    """Every metric leg's BODY executes end-to-end at tiny config on CPU
    (round-4 verdict Next #2): a leg regression must turn the suite red,
    never be discovered on chip time. Asserts the one-record contract,
    every leg's keys present, no *_error fields, an honest null
    vs_baseline when no baseline exists (BENCH_BASELINE_PATH pointed at
    a nonexistent temp path, so a real chip baseline in the repo never
    leaks into this CPU run), and the EOS leg proving a MID-STREAM
    while_loop exit (0 < steps < new)."""
    import tempfile
    _tmp = tempfile.mkdtemp()
    rec = _run({"BENCH_BASELINE_PATH": os.path.join(_tmp, "none.json"),
                "BENCH_MODEL": "ResNet18", "BENCH_IMAGE_SIZE": "64",
                "BENCH_BATCH_PER_CHIP": "8", "BENCH_STEPS": "3",
                "BENCH_FEAT_ROWS": "16", "BENCH_FEAT_BATCH": "8",
                "BENCH_BERT_CONFIG": "tiny", "BENCH_BERT_BATCH": "4",
                "BENCH_BERT_SEQ": "64", "BENCH_GEN_CONFIG": "tiny",
                "BENCH_GEN_BATCH": "2", "BENCH_GEN_PROMPT": "16",
                "BENCH_GEN_NEW": "8", "BENCH_FLASH_SEQS": "256",
                "BENCH_GEN_LC_PROMPT": "8", "BENCH_GEN_LC_CACHE": "256",
                "BENCH_GEN_LC_NEW": "4",
                # serve leg (ISSUE 8) at smoke scale: the leg BODY must
                # run, compile-free steady state and token identity are
                # asserted below; the >=3x speedup is a bench-record
                # criterion, not a tiny-CPU one
                "BENCH_SERVE_REQUESTS": "32", "BENCH_SERVE_SLOTS": "4",
                "BENCH_SERVE_CONCURRENCY": "1,8",
                # tp leg (ISSUE 14) at smoke scale: tp in {1,2} keeps
                # the 8-virtual-device subprocess inside the budget
                # while still proving identity + the 1/2 pool shrink
                "BENCH_TP_REQUESTS": "12", "BENCH_TP_DEGREES": "1,2",
                # the train leg compiles TWO signatures per swept batch
                # size since the uint8-streamed variant landed — the old
                # 480s/900s budgets left it no headroom on a loaded host
                "BENCH_TIMEOUT_S": "900",
                "BENCH_WALL_S": "1800"}, timeout=1800)
    assert rec["value"] > 0, rec
    assert rec["vs_baseline"] is None  # no baseline file -> null, not 1.0
    assert rec["extra"]["baseline"] == "none"
    assert "error" not in rec
    extra = rec["extra"]
    errs = [k for k in extra if k.endswith("_error")]
    assert not errs, {k: extra[k] for k in errs}
    for key in ("mfu", "featurizer_rows_per_sec", "featurizer_breakdown",
                "inference", "bert_tokens_s_chip", "gen_e2e_tokens_s",
                "flash", "host_ingest", "serving", "serve_tokens_s"):
        assert key in extra, f"leg output missing {key}: {sorted(extra)}"
    # serving leg (ISSUE 8): engine legs + static comparator recorded,
    # the decode step never re-traced after warmup, greedy continuous
    # batching token-identical to the static path
    sv = extra["serving"]
    assert extra["serve_tokens_s"] and extra["serve_tokens_s"] > 0
    assert sv["static"]["tokens_s"] > 0
    assert sv["decode_retrace_after_warmup"] == 0, sv
    assert sv["token_identical_spot_check"] is True
    assert all(leg["completed"] == leg["requests"]
               for leg in sv["engine"].values()), sv["engine"]
    # speculative leg (ISSUE 12): rides the serve record — greedy
    # identity + zero verify re-traces even at smoke scale, headline
    # mirrored next to serve_tokens_s
    spq = sv["spec"]
    assert spq["spec_token_identical"] is True, spq
    assert spq["verify_retrace_after_warmup"] == 0, spq
    assert extra["serve_spec_speedup"] == spq["spec_speedup"]
    assert extra["serve_spec_accept_rate"] == spq["spec_accept_rate"]
    # tensor-parallel leg (ISSUE 14): greedy identity across degrees,
    # per-device pool bytes halved at tp=2, zero re-traces — mirrored
    # into the headline next to serve_tokens_s
    tpq = sv["tp"]
    assert tpq["tp_identical"] is True, tpq
    assert extra["serve_tp_identical"] is True
    assert extra["serve_tp_kv_pool_device_frac"]["2"] == 0.5, tpq
    assert extra["serve_tp_retraces_after_warmup"] == 0, tpq
    # backend-free ingest leg (ISSUE 7): a real host-side number with
    # before/after deltas — the record that survives TPU outages
    hi = extra["host_ingest"]
    assert hi["value"] > 0 and hi["legs"]["f32_host"]["rows_per_sec"] > 0
    assert hi["deltas"]["rows_per_sec_vs_f32_host"] >= 2.0, hi["deltas"]
    assert hi["deltas"]["wire_bytes_ratio_f32_over_u8"] >= 4.0, hi["deltas"]
    # the inference-throughput record (ISSUE 3): rate + per-stage spans
    assert extra["inference"]["rows_per_sec"] > 0
    assert {"decode", "dispatch", "fetch", "encode"} <= \
        set(extra["inference"]["stage_seconds"]), extra["inference"]
    # bottleneck evidence per revision (ISSUE 6): overlap-aware busy
    # fractions + the named dominant stage ride next to stage_seconds
    su = extra["inference"]["stage_utilization"]
    assert su and su["dominant_stage"] in su["stages"], extra["inference"]
    assert all(0.0 <= s["busy_frac"] <= 1.0 for s in su["stages"].values())
    assert "gen_eos_error" not in extra
    # mid-stream EOS exit: the loop iterated, then stopped early
    assert 0 < extra["gen_eos_steps"] < extra["gen_new_tokens"], extra
    assert extra["gen_eos_steps"] == extra["gen_eos_expected_step"]
    assert extra["gen_eos_early_exit"] is True


@pytest.mark.slow
def test_northstar_leg_streams_in_o_batch_memory():
    """The north-star-scale leg (round-4 verdict Next #6) at reduced N:
    the streamed featurize→parquet run's peak-RSS growth must stay FAR
    below the materialized input size — the in-suite pin of the
    O(batch)-at-scale claim (measured 36 MB vs 226 MB materialized on
    CPU; bound set at 3x headroom)."""
    env = dict(os.environ)
    env.update({"BENCH_NORTHSTAR_ROWS": "1500",
                "BENCH_NORTHSTAR_BATCH": "64",
                "BENCH_NORTHSTAR_MODEL": "ResNet18",
                # single device, like the real single-chip deployment:
                # the 8-virtual-device test mesh multiplies XLA's
                # per-device allocator overhead into the RSS reading,
                # which is runtime noise, not data-plane residency
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    proc = subprocess.run(
        [sys.executable, _BENCH, "--worker", "northstar"],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, proc.stderr[-600:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["northstar_rows"] == 1500
    assert rec["northstar_rows_per_sec"] > 0
    materialized = rec["northstar_input_mb_if_materialized"]
    assert materialized > 200  # the leg is actually at a meaningful N
    assert rec["northstar_peak_rss_delta_mb"] < min(materialized / 2, 120)


@pytest.mark.slow
def test_probe_worker_records_backend_identity():
    """The probe leg must report what the backend registers as (the
    record's platform/device_kind evidence)."""
    proc = subprocess.run(
        [sys.executable, _BENCH, "--worker", "probe"],
        capture_output=True, text=True, timeout=180, env=dict(os.environ))
    assert proc.returncode == 0, proc.stderr[-400:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    for key in ("default_backend", "device_kind", "is_tpu", "compiled_ok",
                "flash_attention_default"):
        assert key in rec, f"probe record missing {key}: {rec}"
    assert rec["compiled_ok"] is True
