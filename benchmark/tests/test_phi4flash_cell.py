"""The cell PR 34 added, ``phi-4-mini-flash.sft-s8192-b1``: it resolves dry,
its configuration keeps every published width, its operation counts match a
hand count at a small shape, each new reader reads a made-up trace, and at a
tiny size on the CPU ``correct`` is true for a sound run and false for the
control and each planted fault.

The cell's tiny sizes are registered in ``benchmark/conftest.py``, which
pytest loads whichever file of this directory is named."""

import functools

import pytest

import tiny
from harness import compare, kernel_time, loader
from harness.reference_run import make_weights, run_steps
from harness.traffic import make_pool

CELL = "phi-4-mini-flash.sft-s8192-b1"
CONFIG = "phi-4-mini-flash-reasoning"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


# -- the cell resolves -----------------------------------------------------------

def test_the_cell_resolves_to_its_own_files():
    res = loader.resolve_cell(CELL)
    assert res["cell"]["chips"] == 1
    assert res["files"] == {
        "driver": ("drivers", "train_fit"), "program": ("programs", CONFIG),
        "reference": ("references", CONFIG), "flops": ("flops", CONFIG)}
    assert res["traffic"]["inputs"]["input_ids"] == {
        "shape": [8192], "dtype": "int32", "low": 0, "high": "vocab_size"}
    assert (res["traffic"]["per_chip_batch"], res["traffic"]["pool_batches"],
            res["traffic"]["warmup_steps"]) == (1, 4, 10)
    names = {m["name"] for m in res["per_layer"]}
    assert names >= {"train_step_mfu", "selective_scan_fwd_roofline",
                     "selective_scan_bwd_roofline", "selective_scan_share",
                     "flash_attention_fwd_roofline",
                     "flash_attention_bwd_roofline"}
    assert "moe_held_share" not in names
    assert set(res["limits"]["limits"]) == {"grad1_all", "grad1_leaf",
                                            "delta_all", "delta_leaf"}
    bench = loader.load_benchmark()
    assert len(bench["configs"]) == 4 and len(bench["workloads"]) == 5
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_examples_per_s"
    # one kernel's roofline goes by one name: the flash kernels' two accepted
    # shares gain this cell at the end of their lists, and no reader is forked
    for m in bench["per_layer"]:
        if m["name"].startswith("flash_attention_"):
            assert m["workloads"] == ["lfm2-8b-a1b.pretrain-s8192-b2", CELL]


def test_the_configuration_keeps_every_published_width():
    res = loader.resolve_cell(CELL)
    entry, cfg = res["config_entry"], res["config"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/microsoft/"
                               "Phi-4-mini-flash-reasoning/blob/main/"
                               "config.json")
    published = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 10240, "layer_norm_eps": 1e-05,
                 "max_position_embeddings": 262144, "mb_per_layer": 2,
                 "model_type": "phi4flash", "num_attention_heads": 40,
                 "num_key_value_heads": 20, "resid_pdrop": 0,
                 "sliding_window": 512, "tie_word_embeddings": True,
                 "mlp_bias": False, "lm_head_bias": False}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 32,
                                "vocab_size": 200064}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (6, 25008)
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["layers_kept"] == [14, 15, 16, 17, 18, 19]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert "8 chips share each layer's vocabulary rows" in cfg["deployment"]
    assert {"mamba", "layer_rule", "differential_attention", "head_pairing",
            "window_edge", "memory", "weights", "optimizer",
            "dtypes"} <= set(cfg["assumed"])
    flops = loader.load_module("flops", CONFIG)
    assert flops.kinds(cfg) == ["mamba", "window", "mamba", "full", "gmu",
                                "cross"]


def test_the_parameter_count_from_the_files_shapes_is_697_1_million():
    import jax
    import numpy as np
    res = loader.resolve_cell(CELL)
    ref = loader.load_module("references", CONFIG)
    shapes = jax.eval_shape(lambda k: ref.init_weights(res["config"], k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 697073792
    # and the program's own tree is the reference's, leaf for leaf
    prog = loader.load_module("programs", CONFIG)
    from sparkdl_tpu.models.phi4flash import Phi4FlashForCausalLM
    mine = jax.eval_shape(
        lambda k: Phi4FlashForCausalLM(prog.model_config(res["config"])).init(
            k, jax.numpy.zeros((1, 8), jax.numpy.int32)),
        jax.random.PRNGKey(0))
    a = {jax.tree_util.keystr(p): x.shape for p, x in
         jax.tree_util.tree_flatten_with_path(mine["params"])[0]}
    b = {jax.tree_util.keystr(p): x.shape for p, x in
         jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert a == b


# -- operations, against a hand count at a small shape ---------------------------

SMALL = {"hidden_size": 8, "intermediate_size": 12, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 10, "mb_per_layer": 2,
         "sliding_window": 3, "num_hidden_layers": 6,
         "published": {"num_hidden_layers": 8},
         "layers_kept": [2, 3, 4, 5, 6, 7], "mamba_d_state": 4,
         "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 2}
SMALL_TRAFFIC = {"inputs": {"input_ids": {"shape": [6]}}}


def test_operations_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    assert f.kinds(SMALL) == ["mamba", "window", "mamba", "full", "gmu",
                              "cross"]
    # d 8, d_inner 16, N 4, rank 2, heads 4 / 2 of 2
    mamba = 8 * 32 + 16 * (2 + 8) + 2 * 16 + 16 * 8          # 576
    self_attn = 8 * (4 + 2 + 2) * 2 + 8 * 8                  # 192
    cross = 8 * 8 + 8 * 8                                    # 128
    gmu = 2 * 8 * 16                                         # 256
    mlp = 3 * 8 * 12                                         # 288
    params = 10 * 8 + 2 * mamba + 2 * self_attn + cross + gmu + 6 * mlp
    assert f.matmul_params_per_token(SMALL) == params == 3728
    # live pairs of 6 positions: causal 21; a window of 3: 1 + 2 + 3 * 4 = 15
    assert f.live_pairs(6) == 21 and f.live_pairs(6, 3) == 15
    assert f.live_pairs(6, 6) == f.live_pairs(6, 100) == 21
    assert f.live_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert f.attention_pairs(SMALL, 6) == 15 + 21 + 21
    # a head and pair: q k^T 2 * 2, p v 2 * 4: 12 operations, 4 heads
    attn = 57 * 4 * 12
    assert f.attention_flops_per_sequence(SMALL, 6) == attn == 2736
    # two convolutions: 4 taps * 16 channels, multiply and add
    fwd = 6 * (2 * params + 2 * 2 * 4 * 16) + attn
    assert f.forward_flops_per_sequence(SMALL, 6) == fwd
    assert f.train_flops_per_example(SMALL, SMALL_TRAFFIC) == 3 * fwd


def test_the_kernels_needs_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    scan = f.selective_scan_fwd_per_example(SMALL, SMALL_TRAFFIC)
    # two Mamba layers; 9 operations per 6 * 16 * 4 positions x channels x
    # states; u and y in bf16 and dt in float32 at [6, 16], B and C in bf16
    # at [6, 4], one chunk's start state 4 * 16 in float32
    assert scan["flops"] == 2 * 9 * 6 * 16 * 4
    assert scan["bytes"] == 2 * (6 * 16 * 8 + 6 * 4 * 4 + 4 * 16 * 4)
    back = f.selective_scan_bwd_per_example(SMALL, SMALL_TRAFFIC)
    assert back["flops"] == 2 * 22 * 6 * 16 * 4
    # u, dy, du in bf16 and dt, ddt in float32; B, C in bf16 and dB, dC f32
    assert back["bytes"] == 2 * (6 * 16 * 14 + 6 * 4 * 12 + 4 * 16 * 4)
    att = f.flash_attention_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert att["flops"] == 2736
    # a layer: q 6*8 and o 6*16 in bf16, k and v 2*6*4 in bf16, lse 6*4 f32
    assert att["bytes"] == 3 * (96 + 192 + 96 + 96)
    attb = f.flash_attention_bwd_per_example(SMALL, SMALL_TRAFFIC)
    # scores again, dq, dk (4 each) and dv, dp (8 each): 28 for 12
    assert attb["flops"] == pytest.approx(57 * 4 * 28)
    # q, dq; o, do twice as wide; k, v, dk, dv; two statistics
    assert attb["bytes"] == 3 * (2 * 96 + 2 * 192 + 4 * 48 + 2 * 96)


def test_at_the_cells_size_a_step_is_38_tflop_and_the_scan_is_memory_bound():
    f = loader.load_module("flops", CONFIG)
    res = loader.resolve_cell(CELL)
    step = f.train_flops_per_example(res["config"], res["traffic"])
    assert 37e12 < step < 40e12, step
    per_token = f.forward_flops_per_sequence(res["config"], 8192) / 8192
    assert 2 * 697e6 < per_token < 2 * 697e6 + 0.2e9, per_token
    scan = f.selective_scan_fwd_per_example(res["config"], res["traffic"])
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert 0.8e-3 < scan["bytes"] / 819e9 < 0.95e-3     # two layers
    att = f.flash_attention_fwd_per_example(res["config"], res["traffic"])
    assert att["flops"] / 197e12 > att["bytes"] / 819e9  # compute-bound
    # the windowed layer adds an eighth of one full layer's pairs
    assert f.attention_pairs(res["config"], 8192) == \
        2 * (8192 * 8193 // 2) + 4063488


# -- the readers, on a made-up trace ---------------------------------------------

def _trace(ops_per_step, steps=4, step_ns=100_000_000):
    mods, ops = [], []
    for i in range(steps + 1):
        t = 1_000 + i * step_ns
        mods.append(("jit_step", t, step_ns))
        at = t
        for name, dur in ops_per_step:
            ops.append((name, at, dur))
            at += dur
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


def _ctx(tr, res, flops):
    from harness import trace as trace_lib
    return {"trace": tr, "device_summary": trace_lib.device_summary(tr),
            "spans": [], "peak": PEAK, "chips": res["cell"]["chips"],
            "global_batch": res["traffic"]["per_chip_batch"]
            * res["cell"]["chips"],
            "flops_per_example": flops.train_flops_per_example(
                res["config"], res["traffic"])}


FWD = "%selective_scan_fwd.2 = (bf16[1,8192,5120]) custom-call()"
BWD = "%selective_scan_bwd.1 = (bf16[1,8192,5120]) custom-call()"
ATT = "%flash_attention_fwd.5 = (bf16[40,8192,128]) custom-call()"
DKV = "%flash_attention_bwd_dkv.2 = (bf16[40,8192,64]) custom-call()"
DQ = "%flash_attention_bwd_dq.2 = bf16[40,8192,64] custom-call()"
OTHER = "%fusion.7 = f32[] fusion(%selective_scan_fwd.2, %flash_attention_fwd.5)"


def test_each_roofline_reader_counts_its_own_kernel_and_no_other():
    res = loader.resolve_cell(CELL)
    flops = loader.load_module("flops", CONFIG)

    def least_ns(need):
        n = getattr(flops, need)(res["config"], res["traffic"])
        return 1e9 * max(n["flops"] / 197e12, n["bytes"] / 819e9)

    # every kernel at four times its least time in a step, the forward ones
    # in two calls (the step recomputes its layers): each share reads 25
    per_step = [
        (FWD, int(2 * least_ns("selective_scan_fwd_per_example"))),
        (ATT, int(2 * least_ns("flash_attention_fwd_per_example"))),
        (OTHER, 1_000_000),
        (FWD, int(2 * least_ns("selective_scan_fwd_per_example"))),
        (ATT, int(2 * least_ns("flash_attention_fwd_per_example"))),
        (BWD, int(4 * least_ns("selective_scan_bwd_per_example"))),
        (DKV, int(2 * least_ns("flash_attention_bwd_per_example"))),
        (DQ, int(2 * least_ns("flash_attention_bwd_per_example")))]
    step_ns = 2 * sum(d for _, d in per_step)
    ctx = _ctx(_trace(per_step, step_ns=step_ns), res, flops)
    assert kernel_time.cell_of("selective_scan_fwd_roofline", ctx)["name"] \
        == CELL
    # the flash shares find this cell's need in its run and LFM2's in LFM2's
    assert kernel_time.cell_of("flash_attention_bwd_roofline", ctx)["name"] \
        == CELL
    lfm2 = loader.resolve_cell("lfm2-8b-a1b.pretrain-s8192-b2")
    theirs = _ctx({}, lfm2, loader.load_module(*lfm2["files"]["flops"]))
    assert kernel_time.cell_of("flash_attention_bwd_roofline", theirs)[
        "name"] == lfm2["name"]
    for metric in ("selective_scan_fwd_roofline",
                   "selective_scan_bwd_roofline",
                   "flash_attention_fwd_roofline",
                   "flash_attention_bwd_roofline"):
        assert _read(metric, ctx) == pytest.approx(25.0, rel=1e-3), metric
    scan_ns = sum(d for n, d in per_step if n in (FWD, BWD))
    assert _read("selective_scan_share", ctx) == pytest.approx(
        100.0 * scan_ns / step_ns, rel=1e-3)
    # in a cell the metrics do not list, and from a program without the
    # kernels (the parent's), every reader returns nothing and does not raise
    bare = _ctx(_trace([(OTHER, 1_000_000)]), res, flops)
    for metric in ("selective_scan_fwd_roofline",
                   "selective_scan_bwd_roofline", "selective_scan_share",
                   "flash_attention_fwd_roofline",
                   "flash_attention_bwd_roofline"):
        assert _read(metric, bare) is None, metric
        assert _read(metric, {}) is None, metric
        if metric != "selective_scan_share":
            assert _read(metric, dict(ctx, global_batch=128)) is None, metric


# -- correct, at a tiny size on the CPU ------------------------------------------

def _tiny():
    res = tiny.tiny_job(CELL)["resolved"]
    return res, loader.load_module("references", CONFIG)


def test_the_tiny_cell_holds_every_kind_of_layer_and_a_window_inside_it():
    res, ref = _tiny()
    cfg = res["config"]
    assert cfg["layers_kept"] == [14, 15, 16, 17, 18, 19]
    assert cfg["sliding_window"] < res["traffic"]["inputs"]["input_ids"][
        "shape"][0]
    prog = loader.load_module("programs", CONFIG)
    c = prog.model_config(cfg)
    assert (c.hidden_size, c.d_inner, c.dt_rank, c.sliding_window) == (
        64, 128, 4, 8)


@pytest.mark.parametrize("fault", ["window_ignored", "second_map_dropped",
                                   "gated_output_as_memory"])
def test_each_planted_fault_is_not_correct(fault):
    """The reference with a fault planted, put in the program's place, fails
    at least one of the cell's limits on every seed tried."""
    res, ref = _tiny()
    assert fault in ref.FAULTS
    cfg, traffic = res["config"], res["traffic"]
    for seed in (4, 6, 2 ** 31 + 5):
        weights = make_weights(ref, cfg, seed)
        batches = make_pool(traffic, cfg, seed, 1)[:3]
        base = run_steps(ref, cfg, weights, batches)
        got = run_steps(ref, cfg, weights, batches,
                        precision="float32+" + fault)
        numbers, _ = compare.training_numbers(got, base)
        ok, compared = compare.judge(numbers, res["limits"]["limits"])
        assert not ok, (fault, seed, compared)


@functools.lru_cache(maxsize=None)
def _reference_steps(seed):
    res, ref = _tiny()
    cfg, traffic = res["config"], res["traffic"]
    return run_steps(ref, cfg, make_weights(ref, cfg, seed),
                     make_pool(traffic, cfg, seed, 1)[:3])


def _over_their_limits(got, base):
    numbers, _ = compare.training_numbers(got, base)
    _, compared = compare.judge(numbers, _tiny()[0]["limits"]["limits"])
    return sorted(n for n, c in compared.items() if c["value"] > c["limit"])


@pytest.mark.parametrize("leaf", [
    "layer_0/mlp/gate_up_proj/kernel", "layer_1/attn/Wqkv/kernel",
    "layer_2/mamba/D", "layer_3/attn/subln/scale",
    "layer_4/gmu/in_proj/kernel", "layer_5/attn/Wq/kernel",
    "layer_5/input_layernorm/bias", "final_layernorm/scale"])
def test_one_leaf_left_unmoved_is_not_correct_by_delta_leaf_alone(leaf):
    """An optimizer that skips one leaf (a wrong ``decay_mask``, a frozen
    vector): the reference's own numbers with that leaf's change at nothing.
    ``delta_all`` is carried by the large matrices and lets even one of them
    through, and the first gradient is sound: ``delta_leaf`` is the number
    that fails."""
    for seed in (4, 2 ** 31 + 5):
        base = _reference_steps(seed)
        got = dict(base, delta={**base["delta"], leaf: 0.0})
        assert _over_their_limits(got, base) == ["delta_leaf"], (leaf, seed)


def test_a_state_left_unchanged_reads_one_on_delta_leaf():
    base = _reference_steps(4)
    got = dict(base, delta={k: 0.0 for k in base["delta"]})
    numbers, _ = compare.training_numbers(got, base)
    assert numbers["delta_leaf"] == numbers["delta_all"] == 1.0
    assert _over_their_limits(got, base) == ["delta_all", "delta_leaf"]


def test_the_bf16_witness_of_the_reference_stays_finite():
    res, ref = _tiny()
    cfg, traffic = res["config"], res["traffic"]
    weights = make_weights(ref, cfg, 3)
    batches = make_pool(traffic, cfg, 3, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    got = run_steps(ref, cfg, weights, batches, precision="bf16")
    numbers, _ = compare.training_numbers(got, base)
    assert all(v < 0.5 for v in numbers.values()), numbers
