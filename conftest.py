"""The tiny sizes of the later decoder cells (Granite, Qwen3-Next,
SmallThinker), registered where pytest loads them whichever test file under
``benchmark/tests/`` is named. The cells before them are registered in
``benchmark/tests/conftest.py`` and ``benchmark/conftest.py``, accepted
benchmark files, so these entries sit one directory further up (pytest reads
every ``conftest.py`` from the root down to the test's directory). ``tiny``
imports no JAX; the tests under ``tests/`` never read its table."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark", "tests"))
import tiny  # noqa: E402

tiny.TINY.setdefault("granite-4.0-h-micro.sft-s8192-b1", {
    # 256 wide, not 64 as the cells before it: the float8 control's gap of
    # norms grows with the width (0.0017 at 64, 0.0022 to 0.0053 at 256,
    # 0.0083 at the cell's 2048), and at 64 it read under the cell's limits
    "config": {"vocab_size": 256, "hidden_size": 256,
               "shared_intermediate_size": 384, "num_attention_heads": 8,
               "num_key_value_heads": 2, "mamba_n_heads": 8,
               "mamba_d_head": 64, "mamba_d_state": 16,
               "mamba_chunk_size": 8},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [32]}}}})

tiny.TINY.setdefault("qwen3-next-80b-a3b.sft-s8192-b1", {
    # 256 wide as the Granite cell's, for the same reason; both kinds of
    # layer twice, 4 of 16 experts held at top 4, a chunk of 8 in 32 positions
    "config": {"vocab_size": 256, "hidden_size": 256,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "full_attention_interval": 2,
               "linear_key_head_dim": 16, "linear_value_head_dim": 16,
               "linear_num_key_heads": 2, "linear_num_value_heads": 4,
               "moe_intermediate_size": 64,
               "shared_expert_intermediate_size": 64, "num_experts": 4,
               "num_routed_experts": 16, "num_experts_per_tok": 4,
               "gated_delta_chunk": 8},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [32]}}}})

tiny.TINY.setdefault("smallthinker-21b-a3b.sft-s16384-b1", {
    # 256 wide as the Granite cell's, for the same reason; a group of 7 query
    # heads over one key/value head, the published layers 0-3 (a global NoPE
    # layer, three RoPE layers under a window of 8), 4 of 16 experts held at
    # the published top 6; 64 positions, not 32: at 32 the float8 control's
    # first gradient read under the cell's limits on two of three seeds
    "config": {"vocab_size": 256, "hidden_size": 256,
               "num_attention_heads": 7, "num_key_value_heads": 1,
               "head_dim": 32, "sliding_window_size": 8,
               "moe_ffn_hidden_size": 64, "moe_num_primary_experts": 4,
               "num_routed_experts": 16},
    "traffic": {"per_chip_batch": 4, "warmup_steps": 10,
                "inputs": {"input_ids": {"shape": [64]}}}})
