"""The cell PR 40 added, ``qwen3-next-80b-a3b.sft-s8192-b1``: it resolves dry,
its configuration keeps every published width, its operation counts match a
hand count at a small shape, each new reader reads a made-up trace, and at a
tiny size on the CPU ``correct`` is false for each planted fault the limits
claim to see (``test_correct.py`` and ``test_resolve.py`` take every cell of
``BENCHMARK.json``, this one among them: a sound run, an unchanged state,
half a batch, the float8 control).

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

CELL = "qwen3-next-80b-a3b.sft-s8192-b1"
CONFIG = "qwen3-next-80b-a3b-instruct"
LFM2 = "lfm2-8b-a1b.pretrain-s8192-b2"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("gated_delta_fwd_roofline", "gated_delta_bwd_roofline",
               "gated_delta_share", "gated_delta_prep_share",
               "moe_router_share")
JOINED = ("flash_attention_fwd_roofline", "flash_attention_bwd_roofline",
          "lm_head_loss_share", "moe_held_share", "moe_load_max_over_mean",
          "moe_dispatch_combine_share", "moe_experts_share",
          "mamba_proj_share", "mamba_conv_share")


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


# -- the cell resolves -----------------------------------------------------------

def test_the_cell_resolves_to_its_own_files():
    res = loader.resolve_cell(CELL)
    assert res["cell"]["chips"] == 1
    assert res["files"] == {
        "driver": ("drivers", "train_fit"), "program": ("programs", CONFIG),
        "reference": ("references", CONFIG), "flops": ("flops", CONFIG)}
    # the accepted traffic file, shared with the Phi and Granite cells
    assert res["cell"]["traffic"] == "causal-s8192-b1"
    names = {m["name"] for m in res["per_layer"]}
    assert names >= {"train_step_mfu", "unscoped_share", *NEW_METRICS,
                     *JOINED}
    assert not names & {"ssd_scan_share", "selective_scan_share",
                        "short_conv_share", "grad_allreduce_share"}
    bench = loader.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS[:-1]:
        assert by_name[name]["workloads"] == [CELL]
    assert by_name["moe_router_share"]["workloads"] == [CELL, LFM2]
    for name in NEW_METRICS:
        assert by_name[name]["moves"] == "train_examples_per_s"
        assert os.path.isfile(loader.bench_path("layer_metrics",
                                                name + ".py"))
    # an accepted list is lengthened at its end, and by this cell alone
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert len(res["cell"]["why"]) <= 200


def test_the_configuration_keeps_every_published_width():
    res = loader.resolve_cell(CELL)
    entry, cfg = res["config_entry"], res["config"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/Qwen/Qwen3-Next-80B-"
                               "A3B-Instruct/blob/main/config.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == entry["source"]
        differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differing == set(entry["reduced"]), differing
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["linear_num_key_heads"],
            cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_routed_experts"],
            cfg["num_experts_per_tok"]) == (2048, 256, 16, 2, 16, 32, 128,
                                            128, 4, 512, 512, 512, 10)
    assert (cfg["rms_norm_eps"], cfg["rope_theta"],
            cfg["partial_rotary_factor"], cfg["full_attention_interval"]) == (
                1e-6, 10000000, 0.25, 4)
    assert cfg["tie_word_embeddings"] is False
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    # the guide's floors: a whole period, 8 experts, an eighth of the rows
    assert cfg["layers_kept"] == [0, 1, 2, 3] and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert "16 chips share each layer" in cfg["deployment"]
    assert "8 chips share the vocabulary" in cfg["deployment"]
    assert {"left_out", "column_order", "linear_mixer", "attention",
            "expert_layer", "decay_draw", "gated_delta_chunk", "weights",
            "optimizer", "dtypes"} <= set(cfg["assumed"])
    flops = loader.load_module("flops", CONFIG)
    assert flops.kinds(cfg) == ["linear"] * 3 + ["full"]


def test_the_parameter_count_from_the_files_shapes_is_625_667_136():
    import jax
    import numpy as np
    res = loader.resolve_cell(CELL)
    ref = loader.load_module("references", CONFIG)
    shapes = jax.eval_shape(lambda k: ref.init_weights(res["config"], k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 625_667_136
    # and the program's own tree is the reference's, leaf for leaf
    prog = loader.load_module("programs", CONFIG)
    from sparkdl_tpu.models.qwen3_next import Qwen3NextForCausalLM
    mine = jax.eval_shape(
        lambda k: Qwen3NextForCausalLM(
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
    assert "Precision.HIGHEST" in src and "lax.scan(step" in src


# -- operations, against a hand count at a small shape ---------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "full_attention_interval": 2, "vocab_size": 10,
         "num_hidden_layers": 3, "layers_kept": [0, 1, 2],
         "linear_conv_kernel_dim": 4, "linear_key_head_dim": 3,
         "linear_value_head_dim": 5, "linear_num_key_heads": 1,
         "linear_num_value_heads": 2, "moe_intermediate_size": 6,
         "shared_expert_intermediate_size": 6, "num_experts": 2,
         "num_routed_experts": 8, "num_experts_per_tok": 2}
SMALL_TRAFFIC = {"inputs": {"input_ids": {"shape": [128]}}}


def test_operations_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    assert f.kinds(SMALL) == ["linear", "full", "linear"]
    # keys 3, values 10: in_proj_qkvz 8 x 26, in_proj_ba 8 x 4, out 10 x 8
    linear = 8 * 26 + 8 * 4 + 10 * 8                          # 320
    # q and gate 8 x 16, k and v 8 x 4 each, o 8 x 8
    full = 8 * 16 + 2 * 8 * 4 + 8 * 8                         # 256
    # router 8 x 8, 2 * 2 / 8 = half a held pick a token, shared, its gate
    moe = 64 + 0.5 * 3 * 8 * 6 + 3 * 8 * 6 + 8                # 288
    params = 10 * 8 + 2 * linear + full + 3 * moe
    assert f.matmul_params_per_token(SMALL) == params == 1840
    seq = 128
    assert f.attention_flops_per_sequence(SMALL, seq) == \
        seq * (seq + 1) // 2 * 2 * 16
    # two chunks of 64 a layer; a key head 4 * 64^2 * 3; a value head the
    # solve 2 * 64^3 / 3, U and W 2 * 64^2 * (5 + 3), the tile's product
    # 2 * 64^2 * 5, three products with a state 2 * 64 * 15 each
    q = 64
    chunk = 4 * q * q * 3 + 2 * (2 * q ** 3 / 3 + 2 * q * q * 13
                                 + 3 * 2 * q * 15)
    assert f.delta_flops_per_sequence(SMALL, seq) == pytest.approx(
        2 * 2 * chunk)
    rebuilt = 4 * q * q * 3 + 2 * (2 * q ** 3 / 3 + 2 * q * q * 8
                                   + 2 * q * 15)
    assert f.delta_flops_per_sequence(SMALL, seq, rebuilt_only=True) == \
        pytest.approx(2 * 2 * rebuilt)
    # two convolutions: 4 taps * 16 channels, multiply and add
    fwd = seq * (2 * params + 2 * 2 * 4 * 16) + seq * (seq + 1) // 2 * 32 \
        + 2 * 2 * chunk
    assert f.forward_flops_per_sequence(SMALL, seq) == pytest.approx(fwd)
    assert f.train_flops_per_example(SMALL, SMALL_TRAFFIC) == pytest.approx(
        3 * fwd)


def test_the_kernels_needs_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    seq = 128
    fwd = f.gated_delta_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert fwd["flops"] == f.delta_flops_per_sequence(SMALL, seq)
    # two layers: q, k [128, 3] and v, o [128, 10] in bf16, g and beta
    # [128, 2] in float32; no chunk-start state (the program's, not the need)
    assert fwd["bytes"] == 2 * seq * (3 * 4 + 10 * 4 + 2 * 8)
    back = f.gated_delta_bwd_per_example(SMALL, SMALL_TRAFFIC)
    assert back["flops"] == pytest.approx(
        2 * fwd["flops"]
        + f.delta_flops_per_sequence(SMALL, seq, rebuilt_only=True))
    # q, k, dq, dk; v, do, dv; g, beta, dg, dbeta
    assert back["bytes"] == 2 * seq * (3 * 8 + 10 * 6 + 2 * 16)
    att = f.flash_attention_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert att["flops"] == f.attention_flops_per_sequence(SMALL, seq)
    # one layer: q and o 128 * 8 in bf16, k and v 128 * 4 in bf16, lse f32
    assert att["bytes"] == 2 * seq * 8 * 2 + 2 * seq * 4 * 2 + seq * 2 * 4
    attb = f.flash_attention_bwd_per_example(SMALL, SMALL_TRAFFIC)
    assert attb["flops"] == 2.5 * att["flops"]
    assert attb["bytes"] == 4 * seq * 8 * 2 + 4 * seq * 4 * 2 \
        + 2 * seq * 2 * 4


def test_at_the_cells_size_a_step_is_11_5_tflop_whatever_the_kernels_chunk():
    f = loader.load_module("flops", CONFIG)
    res = loader.resolve_cell(CELL)
    step = f.train_flops_per_example(res["config"], res["traffic"])
    assert 11.3e12 < step < 11.7e12, step
    other = dict(res["config"], gated_delta_chunk=256)
    assert f.train_flops_per_example(other, res["traffic"]) == step
    for need in ("gated_delta_fwd_per_example", "gated_delta_bwd_per_example"):
        assert getattr(f, need)(other, res["traffic"]) == \
            getattr(f, need)(res["config"], res["traffic"])
    fwd = f.gated_delta_fwd_per_example(res["config"], res["traffic"])
    # three layers: 0.22 ms of products and 0.25 ms of bytes a layer
    assert 0.20e-3 < fwd["flops"] / 197e12 / 3 < 0.24e-3
    assert 0.24e-3 < fwd["bytes"] / 819e9 / 3 < 0.26e-3
    back = f.gated_delta_bwd_per_example(res["config"], res["traffic"])
    assert back["flops"] / 197e12 > back["bytes"] / 819e9   # compute-bound


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


FWD = "%gated_delta_fwd.2 = (bf16[1,8192,4096]) custom-call()"
BWD = "%gated_delta_bwd.1 = (bf16[1,8192,2048]) custom-call()"
ATT = "%flash_attention_fwd.5 = (bf16[16,8192,256]) custom-call()"
DKV = "%flash_attention_bwd_dkv.2 = (bf16[16,8192,256]) custom-call()"
DQ = "%flash_attention_bwd_dq.2 = bf16[16,8192,256] custom-call()"
OTHER = "%fusion.7 = f32[] fusion(%gated_delta_fwd.2, %flash_attention_fwd.5)"
ROOFLINES = ("gated_delta_fwd_roofline", "gated_delta_bwd_roofline",
             "flash_attention_fwd_roofline", "flash_attention_bwd_roofline")


def test_each_roofline_reader_counts_its_own_kernel_and_no_other():
    res = loader.resolve_cell(CELL)
    flops = loader.load_module("flops", CONFIG)

    def least_ns(need):
        n = getattr(flops, need)(res["config"], res["traffic"])
        return 1e9 * max(n["flops"] / 197e12, n["bytes"] / 819e9)

    # every kernel at four times its least time in a step: each share is 25
    per_step = [
        (FWD, int(4 * least_ns("gated_delta_fwd_per_example"))),
        (ATT, int(4 * least_ns("flash_attention_fwd_per_example"))),
        (OTHER, 1_000_000),
        (BWD, int(4 * least_ns("gated_delta_bwd_per_example"))),
        (DKV, int(2 * least_ns("flash_attention_bwd_per_example"))),
        (DQ, int(2 * least_ns("flash_attention_bwd_per_example")))]
    step_ns = 2 * sum(d for _, d in per_step)
    ctx = _ctx(_trace(per_step, step_ns=step_ns), res, flops)
    for metric in ROOFLINES:
        assert kernel_time.cell_of(metric, ctx)["name"] == CELL
        assert _read(metric, ctx) == pytest.approx(25.0, rel=1e-3), metric
    theirs = loader.resolve_cell(LFM2)
    their_ctx = _ctx({}, theirs, loader.load_module(*theirs["files"]["flops"]))
    assert kernel_time.cell_of("flash_attention_bwd_roofline",
                               their_ctx)["name"] == LFM2
    assert kernel_time.cell_of("gated_delta_fwd_roofline", their_ctx) is None
    delta_ns = sum(d for n, d in per_step if n in (FWD, BWD))
    assert _read("gated_delta_share", ctx) == pytest.approx(
        100.0 * delta_ns / step_ns, rel=1e-3)
    # in a cell the metrics do not list, and from a program without the
    # kernels or the scopes (the parent's), every reader returns nothing and
    # does not raise
    bare = _ctx(_trace([(OTHER, 1_000_000)]), res, flops)
    for metric in (*ROOFLINES, "gated_delta_share"):
        assert _read(metric, bare) is None, metric
        assert _read(metric, {}) is None, metric
    for metric in ROOFLINES:
        assert _read(metric, dict(ctx, global_batch=128)) is None, metric
    for metric in ("gated_delta_prep_share", "moe_router_share"):
        assert _read(metric, {}) is None, metric
        assert _read(metric, dict(bare)) is None, metric   # no step table


def test_the_scope_readers_add_up_their_scopes(monkeypatch):
    from harness import scope_time
    monkeypatch.setattr(scope_time, "seconds_by_name", lambda ctx: (
        {"gated_delta_prep": 0.03, "gdn_gated_norm": 0.01,
         "moe_router": 0.02, "mamba_conv": 0.5}, 1.0))
    assert _read("gated_delta_prep_share", {}) == pytest.approx(4.0)
    assert _read("moe_router_share", {}) == pytest.approx(2.0)
    # a program that opens neither scope (the parent's) reads nothing
    monkeypatch.setattr(scope_time, "seconds_by_name",
                        lambda ctx: ({"mamba_conv": 0.5}, 1.0))
    assert not _read("gated_delta_prep_share", {})


# -- correct, at a tiny size on the CPU ------------------------------------------

def _tiny():
    res = tiny.tiny_job(CELL)["resolved"]
    return res, loader.load_module("references", CONFIG)


def test_the_tiny_cell_holds_both_kinds_of_layer_and_chunks_inside_it():
    res, _ = _tiny()
    cfg = res["config"]
    seq = res["traffic"]["inputs"]["input_ids"]["shape"][0]
    assert seq // cfg["gated_delta_chunk"] >= 4     # a state handed on thrice
    c = loader.load_module("programs", CONFIG).model_config(cfg)
    assert [c.kind(l) for l in c.layers] == [
        "linear_attention", "full_attention"] * 2
    assert (c.hidden_size, c.num_experts, c.experts_held,
            c.num_experts_per_tok, c.gated_delta_chunk) == (
                256, 16, (0, 4), 4, 8)


def _fault_numbers(fault, seed):
    res, ref = _tiny()
    assert fault in ref.FAULTS
    cfg, traffic = res["config"], res["traffic"]
    weights = make_weights(ref, cfg, seed)
    batches = make_pool(traffic, cfg, seed, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    got = run_steps(ref, cfg, weights, batches, precision="float32+" + fault)
    return compare.training_numbers(got, base)[0], res["limits"]


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
    assert set(limits["limits"]) == {"grad1_all", "grad1_leaf", "delta_all",
                                     "delta_leaf"}
    told = json.dumps(limits["set_from"]) + limits.get("not_seen", "")
    for fault in ref.FAULTS:
        assert fault in told, fault
    # the one the issue singles out: the hand-over between chunks is seen
    assert "state_reset_at_chunk" in _seen(limits)


@pytest.mark.parametrize("fault", loader.load_module(
    "references", CONFIG).FAULTS)
def test_each_planted_fault_the_limits_see_is_not_correct(fault):
    """The reference with a fault planted, put in the program's place, fails
    at least one of the cell's limits on every seed tried."""
    limits = loader.resolve_cell(CELL)["limits"]
    if fault not in _seen(limits):
        pytest.skip(f"{fault} stands under not_seen, with its readings")
    for seed in (4, 6, 2 ** 31 + 5):
        numbers, limits = _fault_numbers(fault, seed)
        ok, compared = compare.judge(numbers, limits["limits"])
        assert not ok, (fault, seed, compared)


def test_the_bf16_witness_of_the_reference_stays_finite():
    res, ref = _tiny()
    cfg, traffic = res["config"], res["traffic"]
    weights = make_weights(ref, cfg, 3)
    batches = make_pool(traffic, cfg, 3, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    got = run_steps(ref, cfg, weights, batches, precision="bf16")
    numbers, _ = compare.training_numbers(got, base)
    assert all(v < 0.5 for v in numbers.values()), numbers
