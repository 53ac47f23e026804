"""Tiny copies of the cells, for tests on the CPU: the same files and code
paths, with the sizes cut so that a run takes seconds."""

import copy
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import loader  # noqa: E402

TINY = {
    "resnet50.train-b128": {
        "config": {"stage_sizes": [1, 1, 1, 1], "width": 8, "image_size": 32,
                   "num_classes": 10},
        "traffic": {"per_chip_batch": 8, "warmup_steps": 10,
                    "inputs": {"image": {"shape": [32, 32, 3]}}}},
    "bert-base.finetune-s384-b32": {
        "config": {"vocab_size": 1000, "hidden_size": 128,
                   "num_hidden_layers": 4, "num_attention_heads": 4,
                   "intermediate_size": 512, "max_position_embeddings": 64},
        "traffic": {"per_chip_batch": 8, "warmup_steps": 10,
                    "inputs": {"input_ids": {"shape": [64]}}}},
}


def tiny_job(cell: str, seed: int = 1, seconds: float = 0.3,
             limits: dict | None = None) -> dict:
    res = copy.deepcopy(loader.resolve_cell(cell))
    res["config"].update(TINY[cell]["config"])
    t = TINY[cell]["traffic"]
    res["traffic"].update({k: v for k, v in t.items() if k != "inputs"})
    for name, over in t["inputs"].items():
        res["traffic"]["inputs"][name].update(over)
    if limits is not None:
        res["limits"] = {"limits": limits}
    return {"resolved": res, "seed": seed, "seconds": seconds,
            "trace": False, "t_start": time.perf_counter()}
