#!/usr/bin/env python
"""Scoring smoke: the streaming inference engine end-to-end on CPU
(ISSUE 3 satellite, next to ``chaos_smoke``/``obs_smoke``).

Two CHILD scoring processes share one ``JAX_COMPILATION_CACHE_DIR``. Each
scores a synthetic image frame through ``XlaImageTransformer`` — parallel
host decode, one continuous cross-partition device stream, overlap-worker
Arrow encode — and prints examples/s plus the per-stage time breakdown
aggregated from the flight-recorder event stream. The parent asserts:

- every scoring stage (decode/pad/put/dispatch/fetch/encode) emitted spans;
- the FIRST process paid persistent compilation-cache misses;
- the SECOND process logged compilation-cache HITS — a gang restart or
  repeat scoring job skips the recompile instead of paying it again.

Run: ``JAX_PLATFORMS=cpu python scripts/score_smoke.py``
"""

import json
import os
import shutil
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROWS = int(os.environ.get("SCORE_SMOKE_ROWS", "96"))
BATCH = int(os.environ.get("SCORE_SMOKE_BATCH", "16"))
PARTS = int(os.environ.get("SCORE_SMOKE_PARTS", "12"))


def child() -> int:
    """One scoring process: synthetic frame → streaming engine → JSON."""
    import numpy as np
    import pyarrow as pa

    import sparkdl_tpu as sdl
    from sparkdl_tpu.core import runtime
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.runner import events

    rec = events.reset(ring_size=8192)  # hold every span of the run
    rng = np.random.RandomState(0)
    structs = [imageIO.imageArrayToStruct(
        rng.randint(0, 256, size=(24, 24, 3)).astype(np.uint8),
        origin=f"synthetic_{i}") for i in range(ROWS)]
    df = sdl.DataFrame.fromArrow(
        pa.table({"image": pa.array(structs, type=imageIO.imageSchema)}),
        numPartitions=PARTS)

    t = sdl.XlaImageTransformer(
        inputCol="image", outputCol="features",
        fn=lambda b: b.mean(axis=(1, 2)),
        inputSize=(16, 16), batchSize=BATCH)
    t0 = time.perf_counter()
    rows = t.transform(df).collect()
    wall = time.perf_counter() - t0
    assert len(rows) == ROWS, f"scored {len(rows)} of {ROWS} rows"

    stages: dict = {}
    for e in rec.tail():
        if e.get("ph") == "E" and "dur_s" in e:
            stages[e["name"]] = round(
                stages.get(e["name"], 0.0) + e["dur_s"], 6)
    print(json.dumps({
        "rows": ROWS,
        "partitions": PARTS,
        "examples_per_sec": round(ROWS / wall, 2),
        "wall_s": round(wall, 4),
        "decode_workers": runtime.decode_workers_default(),
        "stages": stages,
        "compile_cache": runtime.persistent_cache_stats(),
    }))
    return 0


def main() -> int:
    # A fixed sub-directory of the checkout's cache, emptied first: the
    # first child must MISS and the second HIT, and a cache path made from
    # a temporary name could never be found again by anyone.
    cache_dir = os.path.join(_REPO, ".jax_cache", "score_smoke")
    shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir

    def run_child() -> dict:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            capture_output=True, text=True, env=env, timeout=300)
        if proc.returncode != 0:
            print(proc.stdout, end="")
            print(proc.stderr, end="", file=sys.stderr)
            raise RuntimeError(f"scoring child exited {proc.returncode}")
        line = [ln for ln in proc.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        return json.loads(line)

    first = run_child()
    second = run_child()

    stage_names = {"decode", "pad", "put", "dispatch", "fetch", "encode"}
    ok = (stage_names <= set(first["stages"])
          and first["compile_cache"]["misses"] > 0
          # the second process loads the SAME programs from the shared
          # on-disk cache — a hit logged instead of a recompile
          and second["compile_cache"]["hits"] > 0
          and second["rows"] == ROWS)

    print("per-stage breakdown (first run, seconds summed over spans):")
    for name in sorted(first["stages"], key=first["stages"].get,
                       reverse=True):
        print(f"  {name:10s} {first['stages'][name]:8.4f}")
    print(f"examples/s: first={first['examples_per_sec']} "
          f"second={second['examples_per_sec']}")
    print(f"compile cache: first={first['compile_cache']} "
          f"second={second['compile_cache']}")
    print(json.dumps({"ok": ok, "first_run": first, "second_run": second,
                      "cache_dir": cache_dir}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(child() if "--child" in sys.argv else main())
