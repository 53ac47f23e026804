"""Tiny sizes for the cells that came after ``tiny.py`` (its table is keyed by
cell). Read before any test module imports ``tiny``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

tiny.TINY.setdefault("lfm2-8b-a1b.pretrain-s8192-b2", {
    "config": {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_attention_heads": 4,
               "num_key_value_heads": 2},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [32]}}}})
