#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell of ``BENCHMARK.json`` to its files by name (it holds no
model's or cell's name itself), refuses anything but the TPUs the cell asks
for, hands the job to the traffic mix's driver, and prints the result as the
last line of standard output. Exit codes: 0 a result was printed; 2 no TPU or
too few chips; 3 a name that resolves to nothing or no program to run; any
other: the run itself failed. In each of those no result line is printed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)

# The compile cache lives inside the checkout, at a fixed path and with no
# cap on its size, whatever the machine's environment says: two checkouts
# share nothing, and only a cell's first run in a checkout compiles. The
# program reads the directory from this variable when it is imported.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)


def check_devices(chips: int):
    """The devices JAX found, or exit 2 before any work."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell asks for {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


def main(argv=None) -> int:
    from harness import loader

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        resolved = loader.resolve_cell(args.workload)
    except (loader.ResolutionError, OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "sparkdl_tpu")):
        print("benchmark: no program here (sparkdl_tpu/ is missing)",
              file=sys.stderr)
        return 3

    devices = check_devices(resolved["cell"]["chips"])
    loader.peak_for(devices[0].device_kind)  # a miss is an error, not a default
    driver = loader.load_module(*resolved["files"]["driver"])
    result = driver.run({
        "resolved": resolved, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "t_start": T_START})

    # correct first, what was compared last: the driver reads the keys it
    # knows and ignores the rest
    compared = result.pop("compared")
    line = {"correct": result.pop("correct"), **result, "compared": compared}
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} value {c['value']:.6g} limit {c['limit']:.6g}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
