"""The tiny sizes of the cell PR 34 added, registered where pytest loads them
whichever test file is named: ``tests/conftest.py`` does this for the cells
before it, and is an accepted benchmark file that only a ``benchmark`` PR may
edit, so this entry sits one directory up (pytest reads every ``conftest.py``
from the root down to the test's directory). ``tiny`` imports no JAX."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tests"))
import tiny  # noqa: E402

tiny.TINY.setdefault("phi-4-mini-flash.sft-s8192-b1", {
    "config": {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "sliding_window": 8, "mamba_dt_rank": 4},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [32]}}}})
