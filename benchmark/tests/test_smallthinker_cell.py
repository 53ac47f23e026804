"""The cell ``smallthinker-21b-a3b.sft-s16384-b1``: it resolves dry, its
configuration keeps every published width, its operation counts match a hand
count at a small shape, each new reader reads a made-up trace, a tiny job
runs through the driver and reads ``correct``, and at a tiny size on the CPU
``correct`` is false for each planted fault the limits claim to see
(``test_correct.py`` and ``test_resolve.py`` take every cell of
``BENCHMARK.json``, this one among them: a sound run, an unchanged state,
half a batch, the float8 control). Every assertion goes by name and
membership, never by a count or a place in the whole benchmark, which later
cells change.

The cell's tiny sizes are registered in the ``conftest.py`` at the root of
the repository, which pytest loads whichever file of this directory is
named."""

import json
import os

import pytest

import tiny
from harness import compare, kernel_time, loader
from harness.reference_run import make_weights, run_steps
from harness.traffic import make_pool

CELL = "smallthinker-21b-a3b.sft-s16384-b1"
CONFIG = "smallthinker-21ba3b-instruct"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("attn_window_share", "attn_global_share")
JOINED = ("flash_attention_fwd_roofline", "flash_attention_bwd_roofline",
          "lm_head_loss_share", "moe_held_share", "moe_load_max_over_mean",
          "moe_dispatch_combine_share", "moe_experts_share",
          "moe_router_share")


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


# -- the cell resolves --------------------------------------------------------

def test_the_cell_resolves_to_its_own_files():
    res = loader.resolve_cell(CELL)
    assert res["cell"]["chips"] == 1
    assert res["cell"]["config"] == CONFIG
    assert res["cell"]["traffic"] == "causal-s16384-b1"
    assert res["files"] == {
        "driver": ("drivers", "train_fit"), "program": ("programs", CONFIG),
        "reference": ("references", CONFIG), "flops": ("flops", CONFIG)}
    assert res["traffic"]["inputs"]["input_ids"]["shape"] == [16384]
    assert res["traffic"]["per_chip_batch"] == 1
    names = {m["name"] for m in res["per_layer"]}
    assert names >= {"train_step_mfu", "unscoped_share", *NEW_METRICS,
                     *JOINED}
    assert not names & {"ssd_scan_share", "selective_scan_share",
                        "short_conv_share", "gated_delta_share",
                        "mamba_proj_share", "grad_allreduce_share"}
    assert {m["name"] for m in res["end_to_end"]} == {
        "train_examples_per_s", "setup_s"}
    bench = loader.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_examples_per_s"
        assert by_name[name]["layer"] == "attention layer"
        assert os.path.isfile(loader.bench_path("layer_metrics",
                                                name + ".py"))
    # an accepted list is lengthened at its end, by this cell
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert len(res["cell"]["why"]) <= 200
    assert len(res["config_entry"]["why"]) <= 200


def test_the_configuration_keeps_every_published_width():
    res = loader.resolve_cell(CELL)
    entry, cfg = res["config_entry"], res["config"]
    assert entry["reduced"] == ["num_hidden_layers",
                                "moe_num_primary_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/PowerInfer/"
                               "SmallThinker-21BA3B-Instruct/blob/main/"
                               "config.json")
    # every key the cut changed is named, with its published value
    assert set(cfg["published"]) == set(entry["reduced"])
    assert all(cfg[k] != v for k, v in cfg["published"].items())
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"],
            cfg["num_routed_experts"], cfg["moe_num_active_primary_experts"],
            cfg["sliding_window_size"], cfg["max_position_embeddings"]) == (
                2560, 128, 28, 4, 768, 64, 6, 4096, 16384)
    assert (cfg["rms_norm_eps"], cfg["rope_theta"]) == (1e-6, 1500000)
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == \
        [0, 1, 1, 1] * 13
    assert cfg["tie_word_embeddings"] is False
    assert cfg["moe_primary_router_apply_softmax"] is True
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 16, 18992)
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    assert cfg["layers_kept"] == [0, 1, 2, 3]
    assert cfg["moe_num_primary_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert "4 chips share each layer" in cfg["deployment"]
    assert "8 chips share the vocabulary" in cfg["deployment"]
    assert {"reglu", "router_input", "window", "routing", "attention",
            "left_out", "weights", "optimizer", "dtypes"} <= \
        set(cfg["assumed"])
    flops = loader.load_module("flops", CONFIG)
    assert flops.kinds(cfg) == [(False, None)] + [(True, 4096)] * 3


def test_the_parameter_count_from_the_files_shapes_is_559_290_880():
    import jax
    import numpy as np
    res = loader.resolve_cell(CELL)
    ref = loader.load_module("references", CONFIG)
    shapes = jax.eval_shape(lambda k: ref.init_weights(res["config"], k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 559_290_880
    assert "559,290,880" in res["config"]["parameters"]
    # and the program's own tree is the reference's, leaf for leaf
    prog = loader.load_module("programs", CONFIG)
    from sparkdl_tpu.models.smallthinker import SmallThinkerForCausalLM
    mine = jax.eval_shape(
        lambda k: SmallThinkerForCausalLM(
            prog.model_config(res["config"])).init(
                k, jax.numpy.zeros((1, 8), jax.numpy.int32)),
        jax.random.PRNGKey(0))
    a = {jax.tree_util.keystr(p): x.shape for p, x in
         jax.tree_util.tree_flatten_with_path(mine["params"])[0]}
    b = {jax.tree_util.keystr(p): x.shape for p, x in
         jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert a == b


def test_the_reference_imports_nothing_of_the_program():
    src = open(loader.bench_path("references", CONFIG + ".py")).read()
    assert "sparkdl_tpu" not in src and "programs" not in src
    assert "Precision.HIGHEST" in src


# -- operations, against a hand count at a small shape ------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "vocab_size": 10, "num_hidden_layers": 3,
         "layers_kept": [3, 4, 5], "rope_layout": [0, 1, 1, 1] * 2,
         "sliding_window_layout": [0, 1, 1, 1] * 2,
         "sliding_window_size": 32, "moe_ffn_hidden_size": 6,
         "moe_num_primary_experts": 2, "num_routed_experts": 8,
         "moe_num_active_primary_experts": 2}
SMALL_TRAFFIC = {"inputs": {"input_ids": {"shape": [128]}}}


def test_operations_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    assert f.kinds(SMALL) == [(True, 32), (False, None), (True, 32)]
    # q 8 x 8, k and v 8 x 4 each, o 8 x 8; router 8 x 8; 2 * 2 / 8 = half
    # a held pick a token of 3 x 8 x 6
    layer = 64 + 2 * 32 + 64 + 64 + 0.5 * 3 * 8 * 6
    params = 10 * 8 + 3 * layer
    assert f.matmul_params_per_token(SMALL) == params == 1064
    seq = 128
    # under the window: 32 * 33 / 2 + 96 * 32; the global layer every pair
    window, full = 32 * 33 // 2 + 96 * 32, seq * (seq + 1) // 2
    assert f.live_pairs(seq, 32) == window == 3600
    assert f.live_pairs(seq) == full and f.live_pairs(seq, 4096) == full
    att = (2 * window + full) * 2 * 4 * 4
    assert f.attention_flops_per_sequence(SMALL, seq) == att
    fwd = seq * 2 * params + att
    assert f.forward_flops_per_sequence(SMALL, seq) == pytest.approx(fwd)
    assert f.train_flops_per_example(SMALL, SMALL_TRAFFIC) == pytest.approx(
        3 * fwd)


def test_the_kernels_needs_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    seq = 128
    att = f.flash_attention_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert att["flops"] == f.attention_flops_per_sequence(SMALL, seq)
    # three layers: q and o 128 * 8 in bf16, k and v 128 * 4, lse f32
    assert att["bytes"] == 3 * (2 * seq * 8 * 2 + 2 * seq * 4 * 2
                                + seq * 2 * 4)
    back = f.flash_attention_bwd_per_example(SMALL, SMALL_TRAFFIC)
    assert back["flops"] == 2.5 * att["flops"]
    assert back["bytes"] == 3 * (4 * seq * 8 * 2 + 4 * seq * 4 * 2
                                 + 2 * seq * 2 * 4)


def test_at_the_cells_size_a_step_is_30_tflop_of_which_attention_45_pct():
    f = loader.load_module("flops", CONFIG)
    res = loader.resolve_cell(CELL)
    cfg = res["config"]
    step = f.train_flops_per_example(cfg, res["traffic"])
    assert 29.5e12 < step < 30.2e12, step
    att = f.attention_flops_per_sequence(cfg, 16384)
    assert 4.4e12 < att < 4.5e12 and 0.44 < 3 * att / step < 0.46
    # a window layer's live pairs are 43.7% of the global layer's
    assert f.live_pairs(16384, 4096) / f.live_pairs(16384) == \
        pytest.approx(0.4375, abs=5e-4)
    fwd = f.flash_attention_fwd_per_example(cfg, res["traffic"])
    assert fwd["flops"] / 197e12 > fwd["bytes"] / 819e9    # compute-bound


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


ATT = "%flash_attention_fwd.5 = (bf16[28,16384,128]) custom-call()"
DKV = "%flash_attention_bwd_dkv.2 = (bf16[28,16384,128]) custom-call()"
DQ = "%flash_attention_bwd_dq.2 = bf16[28,16384,128] custom-call()"
OTHER = "%fusion.7 = f32[] fusion(%flash_attention_fwd.5)"


def test_the_flash_rooflines_count_the_window_and_read_this_cell():
    res = loader.resolve_cell(CELL)
    flops = loader.load_module("flops", CONFIG)

    def least_ns(need):
        n = getattr(flops, need)(res["config"], res["traffic"])
        return 1e9 * max(n["flops"] / 197e12, n["bytes"] / 819e9)

    # each kernel at four times its least time in a step: each share is 25
    per_step = [
        (ATT, int(4 * least_ns("flash_attention_fwd_per_example"))),
        (OTHER, 1_000_000),
        (DKV, int(2 * least_ns("flash_attention_bwd_per_example"))),
        (DQ, int(2 * least_ns("flash_attention_bwd_per_example")))]
    step_ns = 2 * sum(d for _, d in per_step)
    ctx = _ctx(_trace(per_step, step_ns=step_ns), res, flops)
    for metric in ("flash_attention_fwd_roofline",
                   "flash_attention_bwd_roofline"):
        assert kernel_time.cell_of(metric, ctx)["name"] == CELL
        assert _read(metric, ctx) == pytest.approx(25.0, rel=1e-3), metric


def test_the_scope_readers_add_up_their_scopes(monkeypatch):
    from harness import scope_time
    monkeypatch.setattr(scope_time, "seconds_by_name", lambda ctx: (
        {"attn_window": 0.06, "attn_global": 0.02, "flash_attention_fwd": 0.1,
         "moe_router": 0.01}, 1.0))
    assert _read("attn_window_share", {}) == pytest.approx(6.0)
    assert _read("attn_global_share", {}) == pytest.approx(2.0)
    # a program that opens neither scope (the parent's) reads nothing
    monkeypatch.setattr(scope_time, "seconds_by_name",
                        lambda ctx: ({"flash_attention_fwd": 0.1}, 1.0))
    for metric in NEW_METRICS:
        assert _read(metric, {}) is None, metric
    monkeypatch.setattr(scope_time, "seconds_by_name", lambda ctx: None)
    for metric in NEW_METRICS:
        assert _read(metric, {}) is None, metric


# -- a tiny job, and correct at a tiny size on the CPU ------------------------

def _tiny():
    res = tiny.tiny_job(CELL)["resolved"]
    return res, loader.load_module("references", CONFIG)


def test_the_tiny_cell_holds_both_kinds_of_layer_and_a_group_of_seven():
    res, _ = _tiny()
    cfg = res["config"]
    seq = res["traffic"]["inputs"]["input_ids"]["shape"][0]
    c = loader.load_module("programs", CONFIG).model_config(cfg)
    assert [(c.rope(l), c.window(l)) for l in c.layers] == [
        (False, None)] + [(True, 8)] * 3
    assert c.window(1) < seq
    assert c.num_attention_heads // c.num_key_value_heads == 7
    assert (c.moe_num_primary_experts, c.experts_held,
            c.moe_num_active_primary_experts) == (16, (0, 4), 6)


def test_a_tiny_job_runs_through_the_driver_and_is_correct():
    job = tiny.tiny_job(CELL, seed=2 ** 31 + 11)
    job["resolved"]["config"]["compute_dtype"] = "float32"
    driver = loader.load_module(*job["resolved"]["files"]["driver"])
    r = driver.run(job)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["compared"]) == set(res_limits())


def res_limits():
    return loader.resolve_cell(CELL)["limits"]["limits"]


def _seen(limits) -> list:
    """The planted faults the limits file claims to see: every one of the
    reference's that ``not_seen`` does not open with (its text names them
    ahead of their readings, which begin at the first bracket)."""
    ref = loader.load_module("references", CONFIG)
    unseen = limits.get("not_seen", "").split("(")[0]
    return [f for f in ref.FAULTS if f not in unseen]


def test_the_limits_file_accounts_for_every_fault():
    limits = loader.resolve_cell(CELL)["limits"]
    ref = loader.load_module("references", CONFIG)
    assert set(limits["limits"]) <= {"grad1_all", "grad1_leaf", "delta_all",
                                     "delta_leaf"}
    told = json.dumps(limits["set_from"]) + limits.get("not_seen", "")
    for fault in ref.FAULTS:
        assert fault in told, fault
    assert "rule" in limits["set_from"]


@pytest.mark.parametrize("fault", loader.load_module(
    "references", CONFIG).FAULTS)
def test_each_planted_fault_the_limits_see_is_not_correct(fault):
    """The reference with a fault planted, put in the program's place, fails
    at least one of the cell's limits on every seed tried."""
    limits = loader.resolve_cell(CELL)["limits"]
    if fault not in _seen(limits):
        pytest.skip(f"{fault} stands under not_seen, with its readings")
    res, ref = _tiny()
    cfg, traffic = res["config"], res["traffic"]
    for seed in (4, 6, 2 ** 31 + 5):
        weights = make_weights(ref, cfg, seed)
        batches = make_pool(traffic, cfg, seed, 1)[:3]
        base = run_steps(ref, cfg, weights, batches)
        got = run_steps(ref, cfg, weights, batches,
                        precision="float32+" + fault)
        numbers, _ = compare.training_numbers(got, base)
        ok, compared = compare.judge(numbers, limits["limits"])
        assert not ok, (fault, seed, compared)
