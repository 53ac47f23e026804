"""Batch text generation through the UDF registry — the registerUDF
inference half of BASELINE config 5.

Part 1 (token columns): mixed-length prompts run as exactly two compiled
programs (left-padded prefill + while_loop decode with EOS early exit),
streamed from the DataFrame in batchRows chunks.

Part 2 (STRING columns, zero external assets): train the in-repo
ByteBPETokenizer on a local corpus, then drive a text column through
registerTextGenerationUDF — string → tokens → generate → string without
downloading anything.

Part 3 (online serving): the same prompts through the
continuous-batching engine (sparkdl_tpu.serving) — mixed lengths stream
through a 2-slot table with in-flight refill, tokens stream per request
via callback, and greedy output is token-identical to the static
two-program path of Part 1.

Run: JAX_PLATFORMS=cpu python examples/generation_serving.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import sparkdl_tpu as sdl
from sparkdl_tpu.models.llama import LlamaConfig, LlamaModel
from sparkdl_tpu.models.tokenizer import ByteBPETokenizer
from sparkdl_tpu.udf import (applyUDF, registerGenerationUDF,
                             registerTextGenerationUDF)


def token_column_serving(model, variables, cfg):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (5, 2, 7, 3, 6)]
    df = sdl.DataFrame.fromPydict({"prompt": prompts}, numPartitions=2)

    registerGenerationUDF("complete", model, variables,
                          max_new_tokens=8, temperature=0.7, top_p=0.9,
                          seed=0, batchRows=4)
    out = applyUDF(df, "complete", "prompt", "completion").toPandas()
    for p, c in zip(out["prompt"], out["completion"]):
        p, c = list(map(int, p)), list(map(int, c))
        print(f"  {p} -> {c[len(p):]}")
    assert all(len(c) == len(p) + 8 for p, c in
               zip(out["prompt"], out["completion"]))
    print("5 prompts, 3 lengths, ONE prefill + ONE decode program.")


def string_column_serving(model, variables):
    # Train the tokenizer on any local text — here, this very script.
    # (A real deployment would train on its domain corpus and .save()
    # the merges next to the model checkpoint.)
    with open(os.path.abspath(__file__)) as f:
        corpus = f.read().splitlines()
    tok = ByteBPETokenizer.train(corpus, vocab_size=400)
    print(f"tokenizer: {tok.vocab_size} ids "
          f"({len(tok.merges)} learned merges)")

    df = sdl.DataFrame.fromPydict({"text": [
        "batch text generation",
        "the DataFrame streams prompts",
        "left-padded prefill",
    ]})
    registerTextGenerationUDF(
        "continue", model, variables, encode=tok.encode, decode=tok.decode,
        max_new_tokens=6, seed=0, batchRows=2,
        eos_id=ByteBPETokenizer.EOS)
    out = applyUDF(df, "continue", "text", "completion").toPandas()
    for t, c in zip(out["text"], out["completion"]):
        print(f"  {t!r} -> {c!r}")
    assert all(isinstance(c, str) for c in out["completion"])
    print("string column -> tokenize -> generate -> detokenize, "
          "in-repo tokenizer only.")


def continuous_batching_serving(model, variables, cfg):
    """Part 3: the static path waits for the whole batch; the engine
    retires and refills each slot independently. Greedy decoding makes
    the two paths exactly comparable — token-identical per request."""
    from sparkdl_tpu.models.llama import generate, left_pad_prompts
    from sparkdl_tpu.serving import GenerationEngine

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (5, 2, 7, 3, 6)]  # Part 1's prompts
    engine = GenerationEngine.from_model(model, variables, num_slots=2,
                                         max_len=64, min_bucket=8)
    streamed: dict = {}
    handles = [
        engine.submit(p, max_new_tokens=8,
                      stream_cb=lambda r, t:
                      streamed.setdefault(r.id, []).append(t))
        for p in prompts]
    engine.run_until_idle()
    for p, h in zip(prompts, handles):
        ids, lens = left_pad_prompts([p])
        ref = np.asarray(generate(model, variables, ids, 8,
                                  pad_lens=lens, pad_to=64))[0]
        want = ref[int(lens[0]) + len(p):].tolist()
        got = h.result()
        assert got == want, (p, got, want)
        # the stream callback saw every token, in emission order
        assert streamed[h.id] == got
        print(f"  {p} -> {got}")
    snap = engine.snapshot()
    assert snap["completed"] == len(prompts)
    assert snap["peak_slots_busy"] == 2  # requests genuinely overlapped
    print(f"5 requests over 2 slots ({snap['steps']} decode iterations, "
          f"{snap['prefills']} slot prefills): continuous batching is "
          f"token-identical to the static two-program path.")


def main():
    cfg = LlamaConfig.tiny()  # random init — swap in load_pretrained(...)
    model = LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    token_column_serving(model, variables, cfg)
    string_column_serving(model, variables)
    continuous_batching_serving(model, variables, cfg)


if __name__ == "__main__":
    main()
