"""One general generator of training batches, driven by a traffic file.

A mix is data: the per-chip batch, and for each input its shape per example,
its type and the range its whole numbers are drawn from. A range's end may
name a key of the configuration (``"num_classes"``, ``"vocab_size"``), so one
mix serves any configuration that has it. The same seed gives the same pool;
every row of the pool differs.
"""

from __future__ import annotations

import numpy as np


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """``--seed`` is any whole number; a little over 2**31 must work."""
    return np.random.default_rng([abs(int(seed)), stream])


def _bound(v, config: dict) -> int:
    return int(config[v]) if isinstance(v, str) else int(v)


def make_pool(traffic: dict, config: dict, seed: int, chips: int) -> list:
    """``pool_batches`` host batches of ``per_chip_batch * chips`` rows each,
    as dicts of numpy arrays, in the types the mix states."""
    rng = seed_rng(seed, stream=1)
    rows = int(traffic["per_chip_batch"]) * chips
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        batch = {}
        for name, spec in traffic["inputs"].items():
            shape = (rows, *spec["shape"])
            low, high = _bound(spec["low"], config), _bound(spec["high"], config)
            if high - low <= 256:
                # bytes are the cheapest way to many small whole numbers
                draw = rng.integers(0, high - low, size=shape, dtype=np.uint8)
                arr = draw.astype(spec["dtype"])
                if low:
                    arr += np.asarray(low, arr.dtype)
            else:
                arr = rng.integers(low, high, size=shape).astype(spec["dtype"])
            batch[name] = arr
        pool.append(batch)
    return pool
