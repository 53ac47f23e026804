"""Tiny sizes for the cells that came after ``tiny.py`` (its table is keyed by
cell), and four virtual devices for the cell that asks for four chips. Read
before any test module imports ``tiny`` or JAX."""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

tiny.TINY.setdefault("lfm2-8b-a1b.pretrain-s8192-b2", {
    "config": {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_key_value_heads": 2},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [32]}}}})
tiny.TINY.setdefault("resnet50.train-b128-dp4",
                     tiny.TINY["resnet50.train-b128"])
