#!/usr/bin/env python
"""Serving smoke: concurrent submitters against a live engine (ISSUE 8).

End-to-end proof on CPU with ``LlamaConfig.tiny``:

1. N closed-loop clients submit mixed-length requests concurrently into
   a background-threaded engine; **every request completes** (nothing
   starves — the queue is FIFO and slots refill independently);
2. aggregate tokens/s at concurrency > single-stream tokens/s on the
   same workload (the continuous-batching point);
3. the compiled decode step is **never re-traced** once warm
   (``GLOBAL_COMPILE_CACHE.signatures``);
4. greedy engine output is token-identical to the static ``generate()``
   path;
5. ISSUE 18 quant leg: the paged engine at ``kv_dtype=int8`` +
   ``weight_dtype=int8`` vs the paged f32 engine — greedy streams
   within the documented tolerance gate (mean longest-common-prefix
   fraction >= 0.8 — int8 rounding may legitimately flip a late token
   on the random tiny model, full divergence may not), and the
   speculative accept-rate delta is reported.

The closed-loop client harness is ``serve_bench.run_engine_leg`` — ONE
driver shared with the bench, so smoke and bench cannot disagree on
how a workload is offered.

Wired as a slow test in tests/test_serving.py (run in-process — the
tier-1 lean rule); standalone:

    JAX_PLATFORMS=cpu python scripts/serve_smoke.py
"""

import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def _serve_bench():
    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(_REPO, "scripts", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quant_block(n_requests: int = 24) -> dict:
    """ISSUE 18 quant evidence leg: a paged + speculative
    tiny-llama engine at ``kv_dtype=int8`` + ``weight_dtype=int8`` vs
    the same engine at f32, on CPU.

    Returns the greedy-stream agreement (mean longest-common-prefix
    fraction — the documented gate is >= 0.8: a late rounding-flipped
    token is legitimate quantization noise, wholesale divergence is a
    bug), the speculative accept-rate pair + delta (the end-to-end
    quality monitor), and the pool-blocks multiplier at equal
    ``kv_pool_mb`` (the capacity win pool_stats proves)."""
    import jax

    from sparkdl_tpu.models import llama as L
    from sparkdl_tpu.serving import GenerationEngine

    sb = _serve_bench()
    cfg = L.LlamaConfig.tiny()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    rng = np.random.RandomState(7)
    workload = [(rng.randint(0, cfg.vocab_size,
                             size=int(rng.choice((2, 5, 9)))).tolist(),
                 int(rng.choice((3, 5, 24), p=(0.5, 0.3, 0.2))))
                for _ in range(n_requests)]

    def make_engine(kv=None, wq=None, **kw):
        return GenerationEngine.from_model(
            model, variables, num_slots=4, max_len=128,
            block_size=16, kv_dtype=kv, weight_dtype=wq,
            spec_k=2, min_bucket=8, queue_capacity=64, **kw)

    leg_f = sb.run_engine_leg(lambda: make_engine(), workload, 4)
    leg_q = sb.run_engine_leg(lambda: make_engine("int8", "int8"),
                              workload, 4)

    def streams(make):
        eng = make()
        hs = [eng.submit(p, max_new_tokens=n)
              for p, n in workload[:6]]
        eng.run_until_idle()
        return [h.result(1) for h in hs]

    fracs = []
    for a, b in zip(streams(lambda: make_engine()),
                    streams(lambda: make_engine("int8", "int8"))):
        lcp = 0
        for x, y in zip(a, b):
            if x != y:
                break
            lcp += 1
        fracs.append(lcp / max(1, max(len(a), len(b))))
    accept_f = leg_f.get("spec_accept_rate")
    accept_q = leg_q.get("spec_accept_rate")
    # capacity win at EQUAL pool MB — construction only, nothing runs
    bf = make_engine(kv_pool_mb=1.0).backend.pool_stats()["blocks_total"]
    bq = make_engine("int8", kv_pool_mb=1.0) \
        .backend.pool_stats()["blocks_total"]
    return {
        "kv_dtype": "int8", "weight_dtype": "int8",
        "requests": n_requests,
        "completed_f32": leg_f.get("completed"),
        "completed_int8": leg_q.get("completed"),
        "token_match_frac": round(sum(fracs) / len(fracs), 4),
        "accept_rate_f32": accept_f,
        "accept_rate_int8": accept_q,
        "accept_rate_delta": round(abs(accept_f - accept_q), 4)
        if accept_f is not None and accept_q is not None else None,
        "effective_blocks_x": round(bq / bf, 2) if bf else None,
    }


def main() -> int:
    import jax

    from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE
    from sparkdl_tpu.models import llama as L
    from sparkdl_tpu.serving import GenerationEngine

    sb = _serve_bench()
    cfg = L.LlamaConfig.tiny()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    num_slots, max_len = 4, 128
    rng = np.random.RandomState(7)
    workload = [(rng.randint(0, cfg.vocab_size,
                             size=int(rng.choice((2, 5, 9)))).tolist(),
                 int(rng.choice((3, 5, 24), p=(0.5, 0.3, 0.2))))
                for _ in range(24)]

    def make_engine():
        return GenerationEngine.from_model(
            model, variables, num_slots=num_slots, max_len=max_len,
            min_bucket=8, queue_capacity=64)

    # warm every program (buckets 8/16 + the decode step), then pin sigs
    warm = sb.run_engine_leg(make_engine, workload[:4], 4)
    assert warm["completed"] == 4, warm
    sig_decode = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")

    single = sb.run_engine_leg(make_engine, workload, 1)
    multi = sb.run_engine_leg(make_engine, workload, 8)

    # 1) nothing starves — every request completed, both legs
    assert single["completed"] == len(workload), single
    assert multi["completed"] == len(workload), multi
    # 2) concurrency beats single-stream aggregate tokens/s
    assert multi["tokens_s"] > single["tokens_s"], (multi, single)
    # 3) steady state never re-traced the decode step
    retrace = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step") \
        - sig_decode
    assert retrace == 0, f"decode step re-traced {retrace}x"
    # 4) greedy token identity vs the static path (inline drive)
    eng = make_engine()
    handles = [eng.submit(p, max_new_tokens=n) for p, n in workload[:3]]
    eng.run_until_idle()
    for (prompt, new), h in zip(workload[:3], handles):
        ids, lens = L.left_pad_prompts([prompt])
        ref = np.asarray(L.generate(model, variables, ids, new,
                                    pad_lens=lens, pad_to=max_len))[0]
        want = ref[int(lens[0]) + len(prompt):].tolist()
        assert h.result(1) == want, (prompt, h.tokens, want)

    # 5) ISSUE 18 quant leg (see quant_block): greedy tolerance gate +
    # accept-rate delta + >= 2x pool blocks at equal MB.
    quant = quant_block(n_requests=len(workload))
    assert quant["completed_f32"] == quant["requests"], quant
    assert quant["completed_int8"] == quant["requests"], quant
    assert quant["token_match_frac"] >= 0.8, \
        f"int8 greedy streams diverged: {quant}"
    assert quant["effective_blocks_x"] >= 2.0, \
        f"int8 pool bought < 2x blocks at equal MB: {quant}"

    print(json.dumps({
        "ok": True, "requests": len(workload),
        "single_stream_tokens_s": single["tokens_s"],
        "concurrent_tokens_s": multi["tokens_s"],
        "speedup": round(multi["tokens_s"] / single["tokens_s"], 2),
        "decode_retraces": retrace, "token_identical": True,
        "quant": quant}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
