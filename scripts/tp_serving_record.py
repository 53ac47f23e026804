#!/usr/bin/env python
"""Tensor-parallel serving MULTICHIP record (ISSUE 14 acceptance).

Drives the full serving composition — paged KV + radix grafts ×
chunked prefill × speculative decoding × preemption-resume — through
tensor-parallel engines at tp ∈ {1, 2, 4} on the 8-virtual-device CPU
mesh (the same host-platform validation surface as the driver's
multichip dryrun), and writes a ``MULTICHIP_r<N>.json``-style record
proving:

- greedy output at every tp degree is TOKEN-IDENTICAL to the
  single-device engine AND to static ``generate()`` — including a
  mid-decode preemption whose resume must continue bit-exactly;
- zero decode/verify re-traces after warmup (compile-cache signatures);
- per-device KV pool bytes measured at ~``1/tp`` of the tp=1 engine.

Output is auto-numbered: the record is
written to the next FREE ``MULTICHIP_r<N>.json`` at the repo root (git
does not preserve mtimes, so reusing a name would mis-rank the
records; ``--out`` overrides). And — the r05 lesson, where an
injected-chaos traceback sat undifferentiated in the tail — the record
SEPARATES fault-injection evidence from real failures: the chaos leg's
deliberately injected retryable restart lands under
``injected_chaos`` (``expected: true``), anything else under
``failures``; ``ok`` means "no REAL failure", not "no restart ever
happened".

Run:  python scripts/tp_serving_record.py [--out PATH] [--degrees 1,2,4]
"""

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_DEVICES = 8


def _force_virtual_devices():
    """8 virtual CPU devices. jax reads JAX_PLATFORMS when it is
    imported (the package import below pulls it in), so that one is set
    first; XLA_FLAGS is read when the backend starts."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    from sparkdl_tpu.runner.launcher import host_device_flags
    os.environ["XLA_FLAGS"] = host_device_flags(
        os.environ.get("XLA_FLAGS", ""), N_DEVICES)
    import jax
    return jax


def next_multichip_path(root: str = _REPO) -> str:
    """The next free ``MULTICHIP_r<N>.json`` (auto-numbering — never
    clobber or mis-rank an earlier record)."""
    n = 1
    while True:
        p = os.path.join(root, f"MULTICHIP_r{n:02d}.json")
        if not os.path.exists(p):
            return p
        n += 1


def _tp_config():
    """The serve_bench tp-leg model (num_kv_heads=4: exact head split
    at tp=4) — ONE definition, imported from the bench script so the
    record and the bench leg cannot drift apart."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(_REPO, "scripts", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._tp_config()


def _drive_one_degree(GenerationEngine, GLOBAL_COMPILE_CACHE,
                      HistoryDraft, model, variables, tp, max_len, new,
                      pa, pb, refs):
    """One degree's composition drive: chunked prefill → speculative
    decode → forced mid-decode preemption → resumed + grafted streams.
    Returns (streams, snapshot, engine, (sig_d, sig_v))."""
    prov = HistoryDraft()
    prov.observe(pa, refs[0])  # warm retrieval: high-acceptance
    prov.observe(pb, refs[1])  # verify windows on every iteration
    eng = GenerationEngine.from_model(
        model, variables, num_slots=2, max_len=max_len,
        prefill_chunk=8, block_size=8, prefill_budget=16, spec_k=3,
        draft_provider=prov, tp=tp)
    ha = eng.submit(pa, max_new_tokens=new)
    eng.step()   # 2 of pa's 3 chunks (budget 16)
    eng.step()   # final chunk + first token (+ a verify window)
    eng.step()   # >= 1 speculative verify
    sig_d = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")
    sig_v = GLOBAL_COMPILE_CACHE.signatures("serve_verify_step")
    assert ha.state == "running" and 0 < len(ha.tokens) < new
    eng._preempt_newest([(ha.slot, ha)])   # forced preemption
    hb = eng.submit(pb, max_new_tokens=new)  # grafts pa's head
    eng.run_until_idle()
    return ([ha.result(1), hb.result(1)], eng.snapshot(), eng,
            (sig_d, sig_v))


def run_tp_composition(degrees, tail: list, failures: list) -> dict:
    """The ISSUE 14 acceptance drive (see module doc). Degrees the
    visible devices cannot host are skipped with a recorded reason,
    and one degree's failure lands in ``failures`` without discarding
    the other degrees' already-measured evidence."""
    import jax
    import numpy as np

    from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE
    from sparkdl_tpu.models import llama as L
    from sparkdl_tpu.serving import GenerationEngine
    from sparkdl_tpu.serving.draft import HistoryDraft

    cfg = _tp_config()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    rng = np.random.RandomState(7)
    max_len, new = 64, 12
    head = rng.randint(0, cfg.vocab_size, 16).tolist()  # 2 radix blocks
    pa = head + rng.randint(0, cfg.vocab_size, 3).tolist()
    pb = head + rng.randint(0, cfg.vocab_size, 6).tolist()

    # static generate() references — the ground truth every engine
    # (every tp degree, through every composition layer) must hit
    ids, lens = L.left_pad_prompts([pa, pb])
    ref_out = np.asarray(L.generate(model, variables, np.asarray(ids),
                                    new, pad_lens=np.asarray(lens),
                                    pad_to=max_len))
    refs = [ref_out[i][int(lens[i]) + len(p):].tolist()
            for i, p in enumerate([pa, pb])]

    n_dev = len(jax.devices())
    usable, skipped = [], []
    for d in degrees:
        if d > n_dev:
            skipped.append({"degree": d,
                            "reason": f"needs {d} devices, {n_dev} "
                                      f"visible"})
        else:
            usable.append(d)
    degrees = usable
    out: dict = {"degrees": {}, "skipped_degrees": skipped, "config": {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
        "num_kv_heads": cfg.num_kv_heads,
        "composition": ["paged block tables", "radix prefix graft",
                        "chunked prefill (budget 16, chunk 8)",
                        "speculative decode k=3 (HistoryDraft)",
                        "mid-decode preemption-resume"]}}
    streams: dict = {}
    for tp in degrees:
        try:
            streams[tp], snap, eng, sigs = _drive_one_degree(
                GenerationEngine, GLOBAL_COMPILE_CACHE, HistoryDraft,
                model, variables, tp, max_len, new, pa, pb, refs)
        except Exception as e:  # noqa: BLE001 — one degree's failure
            # must not discard the others' already-measured evidence
            failures.append({"leg": f"tp={tp}",
                             "error": f"{type(e).__name__}: {e}"[:500]})
            tail.append(f"tp={tp}: FAILED ({type(e).__name__})")
            continue
        sig_d, sig_v = sigs
        leg = {
            "tp_degree": tp,
            "identical_to_static": streams[tp] == refs,
            "kv_pool_device_bytes": eng.kv_pool_device_bytes,
            "decode_retrace_after_warmup":
                GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")
                - sig_d,
            "verify_retrace_after_warmup":
                GLOBAL_COMPILE_CACHE.signatures("serve_verify_step")
                - sig_v,
            "preemptions": snap["preemptions"],
            "spec_verifies": snap["spec_verifies"],
            "spec_tokens_accepted": snap["spec_tokens_accepted"],
            "prefix_hits": (snap.get("prefix_cache") or {}).get("hits"),
        }
        out["degrees"][str(tp)] = leg
        tail.append(
            f"tp={tp}: identical_to_static={leg['identical_to_static']} "
            f"preemptions={leg['preemptions']} "
            f"spec_verifies={leg['spec_verifies']} "
            f"kv_pool_device_bytes={leg['kv_pool_device_bytes']} "
            f"retraces={leg['decode_retrace_after_warmup'] + leg['verify_retrace_after_warmup']}")
    # ONE measured degree is no cross-degree evidence: report None,
    # never a vacuous True (serve_bench's tp leg applies the same rule)
    if len(streams) >= 2:
        base = streams[min(streams)]
        out["tp_identical_across_degrees"] = all(
            s == base for s in streams.values())
    else:
        out["tp_identical_across_degrees"] = None
    out["tp_identical_to_static"] = all(
        d["identical_to_static"] for d in out["degrees"].values()) \
        if out["degrees"] else None
    out["retraces_after_warmup"] = sum(
        d["decode_retrace_after_warmup"] + d["verify_retrace_after_warmup"]
        for d in out["degrees"].values())
    bytes_by_tp = {k: d["kv_pool_device_bytes"]
                   for k, d in out["degrees"].items()}
    out["kv_pool_device_bytes"] = bytes_by_tp
    b1 = bytes_by_tp.get("1")
    if b1:
        out["kv_pool_device_frac"] = {
            k: round(v / b1, 4) for k, v in bytes_by_tp.items()}
    return out


def run_chaos_leg(tail: list) -> dict:
    """One DELIBERATE retryable failure absorbed by supervision — the
    fault-injection leg every multichip record carries, now labeled as
    such so its traceback can never read as a real failure (the r05
    lesson)."""
    import numpy as np
    import optax

    from sparkdl_tpu.runner import XlaRunner, softmax_cross_entropy_loss

    rng = np.random.RandomState(11)
    params = {"w": rng.randn(4, 3).astype(np.float32) * 0.1}
    batch = {"image": rng.randn(4, 4).astype(np.float32),
             "label": rng.randint(0, 3, (4,))}
    attempts = []

    def data(n_ok):
        def gen():
            from sparkdl_tpu.runner.chaos import announce_injection
            for i in range(3):
                if n_ok is not None and i == n_ok:
                    announce_injection()
                    raise RuntimeError("injected chip failure")
                yield batch
        return gen()

    def flaky(ctx):
        attempts.append(1)
        return ctx.fit(data=data(2 if len(attempts) == 1 else None),
                       num_steps=3,
                       loss_fn=softmax_cross_entropy_loss(),
                       params=params, tx=optax.sgd(0.1),
                       apply_fn=lambda p, x: x @ p["w"], log_every=100)

    res = XlaRunner(np=1).run_with_restarts(flaky, max_restarts=2,
                                            backoff_s=0.0)
    entry = {"kind": "retryable", "expected": True,
             "injected": "chip failure at batch 2 of attempt 1",
             "restarts": len(attempts) - 1,
             "recovered": int(res["state"].step) == 3}
    tail.append(f"chaos leg: injected retryable restart absorbed "
                f"(restarts={entry['restarts']}, "
                f"recovered={entry['recovered']}) — EXPECTED")
    return entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="output path (default: next free "
                         "MULTICHIP_r<N>.json)")
    ap.add_argument("--degrees", default="1,2,4")
    ap.add_argument("--skip-chaos", action="store_true")
    ns = ap.parse_args(argv)
    # Acceptance evidence must not bend to ambient serving knobs —
    # the shared hygiene helper (see its docstring); process-wide by
    # design, this script IS the measurement process.
    from sparkdl_tpu.serving.engine import scrub_serving_env
    scrub_serving_env()
    jax = _force_virtual_devices()
    degrees = [int(d) for d in ns.degrees.split(",") if d]
    tail: list = []
    rec: dict = {"kind": "tp_serving", "n_devices": len(jax.devices()),
                 "platform": jax.default_backend(),
                 "honest_label": (
                     "8 virtual CPU devices: multi-chip SEMANTICS "
                     "(identity, re-traces, 1/tp per-device KV bytes) "
                     "— not wall-clock speedup"),
                 "injected_chaos": [], "failures": []}
    try:
        rec.update(run_tp_composition(degrees, tail, rec["failures"]))
    except Exception as e:  # noqa: BLE001 — a real failure is the record
        rec["failures"].append(
            {"leg": "tp_composition",
             "error": f"{type(e).__name__}: {e}"[:500]})
    if not ns.skip_chaos:
        try:
            rec["injected_chaos"].append(run_chaos_leg(tail))
        except Exception as e:  # noqa: BLE001
            rec["failures"].append(
                {"leg": "chaos",
                 "error": f"{type(e).__name__}: {e}"[:500]})
    bytes_by_tp = rec.get("kv_pool_device_bytes") or {}
    shrink_exact = bool(bytes_by_tp) and all(
        bytes_by_tp.get("1", 0) == v * int(k)
        for k, v in bytes_by_tp.items()) if "1" in bytes_by_tp else None
    rec["kv_pool_device_shrink_exact"] = shrink_exact
    # ok means "no real failure AND nothing measured contradicted the
    # claims" — None fields (a single measured degree has no
    # cross-degree evidence, no tp=1 no shrink baseline) are honest
    # gaps stated in the record, not failures; False anywhere is.
    rec["ok"] = (not rec["failures"]
                 and rec.get("tp_identical_to_static") is True
                 and rec.get("tp_identical_across_degrees") is not False
                 and rec.get("retraces_after_warmup") == 0
                 and shrink_exact is not False)
    rec["skipped"] = False
    rec["tail"] = "\n".join(tail)
    out_path = ns.out or next_multichip_path()
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(json.dumps({"ok": rec["ok"], "out": out_path,
                      "failures": rec["failures"]}))
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
