"""The cell PR 36 added, ``granite-4.0-h-micro.sft-s8192-b1``: it resolves dry,
its configuration keeps every published width and multiplier, its operation
counts match a hand count at a small shape, each new reader reads a made-up
trace, and at a tiny size on the CPU ``correct`` is false for each planted
fault (``test_correct.py`` and ``test_resolve.py`` take every cell of
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

CELL = "granite-4.0-h-micro.sft-s8192-b1"
CONFIG = "granite-4.0-h-micro"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("ssd_scan_fwd_roofline", "ssd_scan_bwd_roofline",
               "ssd_scan_share")


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


# -- the cell resolves -----------------------------------------------------------

def test_the_cell_resolves_to_its_own_files():
    res = loader.resolve_cell(CELL)
    assert res["cell"]["chips"] == 1
    assert res["files"] == {
        "driver": ("drivers", "train_fit"), "program": ("programs", CONFIG),
        "reference": ("references", CONFIG), "flops": ("flops", CONFIG)}
    # the accepted traffic file, shared with the Phi cell
    assert res["cell"]["traffic"] == "causal-s8192-b1"
    assert (res["traffic"]["per_chip_batch"], res["traffic"]["pool_batches"],
            res["traffic"]["warmup_steps"]) == (1, 4, 10)
    names = {m["name"] for m in res["per_layer"]}
    assert names >= {"train_step_mfu", *NEW_METRICS,
                     "flash_attention_fwd_roofline",
                     "flash_attention_bwd_roofline"}
    assert not names & {"moe_held_share", "selective_scan_share"}
    # by name, never by place or by a count of the whole benchmark: the next
    # configuration's entries come after these
    bench = loader.load_benchmark()
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_examples_per_s"
    # one kernel's roofline goes by one name: the flash kernels' two accepted
    # shares list this cell too, and no reader is forked
    for name in ("flash_attention_fwd_roofline",
                 "flash_attention_bwd_roofline"):
        assert CELL in by_name[name]["workloads"]
        assert "lfm2-8b-a1b.pretrain-s8192-b2" in by_name[name]["workloads"]


def test_the_configuration_keeps_every_published_width_and_multiplier():
    res = loader.resolve_cell(CELL)
    entry, cfg = res["config_entry"], res["config"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/ibm-granite/"
                               "granite-4.0-h-micro/blob/main/config.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == CONFIG)
        assert row["source_url"] == entry["source"]
        differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert differing == set(entry["reduced"]), differing
    assert (cfg["hidden_size"], cfg["shared_intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"]) == (2048, 8192, 32, 8, 64, 64, 128, 1,
                                         4, 256)
    assert (cfg["embedding_multiplier"], cfg["residual_multiplier"],
            cfg["attention_multiplier"], cfg["logits_scaling"]) == (
                12, 0.22, 0.015625, 8)
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["tie_word_embeddings"] is True and cfg["rms_norm_eps"] == 1e-5
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["layers_kept"] == list(range(10))
    assert len(cfg["layer_types"]) == 40
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert "8 chips share each layer's vocabulary rows" in cfg["deployment"]
    assert {"head_width", "mlp", "mamba_mixer", "positions", "weights",
            "optimizer", "dtypes"} <= set(cfg["assumed"])
    flops = loader.load_module("flops", CONFIG)
    assert flops.kinds(cfg) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def test_the_parameter_count_from_the_files_shapes_is_772_160_448():
    import jax
    import numpy as np
    res = loader.resolve_cell(CELL)
    ref = loader.load_module("references", CONFIG)
    shapes = jax.eval_shape(lambda k: ref.init_weights(res["config"], k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert n == 772_160_448
    # and the program's own tree is the reference's, leaf for leaf
    prog = loader.load_module("programs", CONFIG)
    from sparkdl_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    mine = jax.eval_shape(
        lambda k: GraniteHybridForCausalLM(
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

SMALL = {"hidden_size": 8, "shared_intermediate_size": 12,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "vocab_size": 10, "num_hidden_layers": 3, "layers_kept": [1, 2, 3],
         "layer_types": ["mamba", "mamba", "attention", "mamba"],
         "mamba_n_heads": 4, "mamba_d_head": 4, "mamba_d_state": 6,
         "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 3}
SMALL_TRAFFIC = {"inputs": {"input_ids": {"shape": [6]}}}


def test_operations_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    assert f.kinds(SMALL) == ["mamba", "attention", "mamba"]
    # d 8, d_inner 16, N 6: in_proj 8 x (16 + 28 + 4), out_proj 16 x 8
    mamba = 8 * 48 + 16 * 8                                   # 512
    attn = 8 * (4 + 2 + 2) * 2 + 8 * 8                        # 192
    mlp = 3 * 8 * 12                                          # 288
    params = 10 * 8 + 2 * mamba + attn + 3 * mlp
    assert f.matmul_params_per_token(SMALL) == params == 2160
    # 21 causal pairs of 6 positions, 4 heads, q k^T and p v of 2 * 2 each
    assert f.attention_flops_per_sequence(SMALL, 6) == 21 * 4 * 8 == 672
    # two chunks of 3 a layer: C B^T 2 * 9 * 6 once, and per head the tile
    # 2 * 9 * 4 and two products with the state 2 * 3 * 4 * 6 each
    chunk = 108 + 4 * (72 + 2 * 144)
    assert f.scan_flops_per_sequence(SMALL, 6) == 2 * 2 * chunk == 6192
    # two convolutions: 4 taps * 28 channels, multiply and add
    fwd = 6 * (2 * params + 2 * 2 * 4 * 28) + 672 + 6192
    assert f.forward_flops_per_sequence(SMALL, 6) == fwd
    assert f.train_flops_per_example(SMALL, SMALL_TRAFFIC) == 3 * fwd


def test_the_kernels_needs_against_a_hand_count():
    f = loader.load_module("flops", CONFIG)
    scan = f.ssd_scan_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert scan["flops"] == 6192
    # two layers: x and y in bf16 at [6, 16], dt in float32 at [6, 4], B and
    # C in bf16 at [6, 6], two chunks' start states [16, 6] in float32
    assert scan["bytes"] == 2 * (6 * (16 * 4 + 4 * 4 + 6 * 4) + 2 * 16 * 6 * 4)
    back = f.ssd_scan_bwd_per_example(SMALL, SMALL_TRAFFIC)
    # three group products, two tiles and five state products a head
    assert back["flops"] == 2 * 2 * (3 * 108 + 4 * (2 * 72 + 5 * 144))
    # x, dy, dx in bf16; dt, ddt in float32; B, C in bf16 and dB, dC float32
    assert back["bytes"] == 2 * (6 * (16 * 6 + 4 * 8 + 6 * 12)
                                 + 2 * 16 * 6 * 4)
    att = f.flash_attention_fwd_per_example(SMALL, SMALL_TRAFFIC)
    assert att["flops"] == 672
    # one layer: q and o 6 * 8 in bf16, k and v 6 * 4 in bf16, lse 6 * 4 f32
    assert att["bytes"] == 2 * 96 + 2 * 48 + 96
    attb = f.flash_attention_bwd_per_example(SMALL, SMALL_TRAFFIC)
    assert attb["flops"] == 2.5 * 672
    assert attb["bytes"] == 4 * 96 + 4 * 48 + 2 * 96


def test_at_the_cells_size_a_step_is_39_7_tflop():
    f = loader.load_module("flops", CONFIG)
    res = loader.resolve_cell(CELL)
    step = f.train_flops_per_example(res["config"], res["traffic"])
    assert 39e12 < step < 40.5e12, step
    # all but the norms, taps and per-head vectors are matrix products
    assert f.matmul_params_per_token(res["config"]) == 771_883_008
    scan = f.ssd_scan_fwd_per_example(res["config"], res["traffic"])
    # nine layers: 0.18 ms of products and 0.25 ms of bytes a layer
    assert 0.17e-3 < scan["flops"] / 197e12 / 9 < 0.19e-3
    assert 0.24e-3 < scan["bytes"] / 819e9 / 9 < 0.26e-3
    back = f.ssd_scan_bwd_per_example(res["config"], res["traffic"])
    assert back["flops"] / 197e12 > back["bytes"] / 819e9   # compute-bound
    # the one attention layer is the LFM2 cell's, at half the batch
    lfm2 = loader.resolve_cell("lfm2-8b-a1b.pretrain-s8192-b2")
    theirs = loader.load_module(*lfm2["files"]["flops"])
    for need in ("flash_attention_fwd_per_example",
                 "flash_attention_bwd_per_example"):
        assert getattr(f, need)(res["config"], res["traffic"]) == \
            getattr(theirs, need)(lfm2["config"], lfm2["traffic"])


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


FWD = "%ssd_scan_fwd.2 = (bf16[1,8192,4096]) custom-call()"
BWD = "%ssd_scan_bwd.1 = (bf16[1,8192,4096]) custom-call()"
ATT = "%flash_attention_fwd.5 = (bf16[32,8192,64]) custom-call()"
DKV = "%flash_attention_bwd_dkv.2 = (bf16[32,8192,64]) custom-call()"
DQ = "%flash_attention_bwd_dq.2 = bf16[32,8192,64] custom-call()"
OTHER = "%fusion.7 = f32[] fusion(%ssd_scan_fwd.2, %flash_attention_fwd.5)"
ROOFLINES = ("ssd_scan_fwd_roofline", "ssd_scan_bwd_roofline",
             "flash_attention_fwd_roofline", "flash_attention_bwd_roofline")


def test_each_roofline_reader_counts_its_own_kernel_and_no_other():
    res = loader.resolve_cell(CELL)
    flops = loader.load_module("flops", CONFIG)

    def least_ns(need):
        n = getattr(flops, need)(res["config"], res["traffic"])
        return 1e9 * max(n["flops"] / 197e12, n["bytes"] / 819e9)

    # every kernel at four times its least time in a step, the forward ones
    # in two calls (the step recomputes its layers): each share reads 25
    per_step = [
        (FWD, int(2 * least_ns("ssd_scan_fwd_per_example"))),
        (ATT, int(2 * least_ns("flash_attention_fwd_per_example"))),
        (OTHER, 1_000_000),
        (FWD, int(2 * least_ns("ssd_scan_fwd_per_example"))),
        (ATT, int(2 * least_ns("flash_attention_fwd_per_example"))),
        (BWD, int(4 * least_ns("ssd_scan_bwd_per_example"))),
        (DKV, int(2 * least_ns("flash_attention_bwd_per_example"))),
        (DQ, int(2 * least_ns("flash_attention_bwd_per_example")))]
    step_ns = 2 * sum(d for _, d in per_step)
    ctx = _ctx(_trace(per_step, step_ns=step_ns), res, flops)
    # each share finds this cell's need in its run, and the flash shares
    # the other decoders' in theirs
    for metric in ROOFLINES:
        assert kernel_time.cell_of(metric, ctx)["name"] == CELL
        assert _read(metric, ctx) == pytest.approx(25.0, rel=1e-3), metric
    for other in ("lfm2-8b-a1b.pretrain-s8192-b2",
                  "phi-4-mini-flash.sft-s8192-b1"):
        theirs = loader.resolve_cell(other)
        their_ctx = _ctx({}, theirs,
                         loader.load_module(*theirs["files"]["flops"]))
        assert kernel_time.cell_of("flash_attention_bwd_roofline",
                                   their_ctx)["name"] == other
        assert kernel_time.cell_of("ssd_scan_fwd_roofline", their_ctx) is None
    scan_ns = sum(d for n, d in per_step if n in (FWD, BWD))
    assert _read("ssd_scan_share", ctx) == pytest.approx(
        100.0 * scan_ns / step_ns, rel=1e-3)
    # in a cell the metrics do not list, and from a program without the
    # kernels (the parent's), every reader returns nothing and does not raise
    bare = _ctx(_trace([(OTHER, 1_000_000)]), res, flops)
    for metric in (*ROOFLINES, "ssd_scan_share"):
        assert _read(metric, bare) is None, metric
        assert _read(metric, {}) is None, metric
        if metric != "ssd_scan_share":
            assert _read(metric, dict(ctx, global_batch=128)) is None, metric


# -- correct, at a tiny size on the CPU ------------------------------------------

def _tiny():
    res = tiny.tiny_job(CELL)["resolved"]
    return res, loader.load_module("references", CONFIG)


def test_the_tiny_cell_holds_both_kinds_of_layer_and_chunks_inside_it():
    res, _ = _tiny()
    cfg = res["config"]
    assert cfg["layers_kept"] == list(range(10))
    seq = res["traffic"]["inputs"]["input_ids"]["shape"][0]
    assert seq // cfg["mamba_chunk_size"] >= 4     # a state handed on thrice
    c = loader.load_module("programs", CONFIG).model_config(cfg)
    assert (c.hidden_size, c.d_inner, c.mamba_d_state, c.head_dim) == (
        256, 512, 16, 32)
    assert [c.layer_types[l] for l in c.layers].count("attention") == 1


def _fault_numbers(fault, seed):
    res, ref = _tiny()
    assert fault in ref.FAULTS
    cfg, traffic = res["config"], res["traffic"]
    weights = make_weights(ref, cfg, seed)
    batches = make_pool(traffic, cfg, seed, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    got = run_steps(ref, cfg, weights, batches, precision="float32+" + fault)
    return compare.training_numbers(got, base)[0], res["limits"]


@pytest.mark.parametrize("fault", ["gate_after_norm",
                                   "attention_scale_sqrt_d"])
def test_each_planted_fault_the_limits_see_is_not_correct(fault):
    """The reference with a fault planted, put in the program's place, fails
    at least one of the cell's limits on every seed tried."""
    for seed in (4, 6, 2 ** 31 + 5):
        numbers, limits = _fault_numbers(fault, seed)
        ok, compared = compare.judge(numbers, limits["limits"])
        assert not ok, (fault, seed, compared)


def test_the_state_reset_moves_every_number_and_the_limits_file_owns_up():
    """``state_reset_at_chunk`` moves the norms of the gradient's leaves by
    less than bfloat16 rounding does (on the chip 1.5 to 4 times the sound
    runs' largest, PERF.md section 2; here, in float32, it is the only thing
    that moves them): the comparison of norms cannot hold it, the limits file
    says so under ``not_seen``, and ``tests/test_ssd_scan.py`` and
    ``chip_smoke.py`` hold the carried state instead."""
    numbers, limits = _fault_numbers("state_reset_at_chunk", 4)
    assert numbers["grad1_leaf"] > 1e-5 and numbers["delta_leaf"] > 1e-5
    assert "state_reset_at_chunk" in limits["not_seen"]
    assert "chip_smoke.py" in limits["not_seen"]


@pytest.mark.parametrize("fault", ["intra_chunk_dropped",
                                   "recurrence_dropped"])
def test_the_scan_faults_move_the_numbers_here_and_fail_on_the_chip(fault):
    """The two faults the review of PR 36 asked for: the masked product
    inside a chunk dropped, and the whole recurrence (``y = D x``). At the
    tiny size (state 16, float32) the recurrence is all but inert in the
    norms and they mostly pass; at the cell's size each fails three or four
    of the limits on every seed read (the limits file's readings, from
    ``tools/limit_readings.py`` on the chip)."""
    numbers, limits = _fault_numbers(fault, 4)
    assert numbers["grad1_leaf"] > 1e-4 and numbers["delta_all"] > 1e-4
    # the smallest reading the file keeps of the fault is over the limit
    for number in ("grad1_all", "grad1_leaf", "delta_all"):
        smallest = float(limits["set_from"][number][fault].split()[0])
        assert smallest > limits["limits"][number], number


def test_the_readings_tool_halves_one_sequence_by_its_positions():
    """``tools/limit_readings.py`` picks faults by name and, where a batch
    holds one row, keeps half of its positions (half of one row is none)."""
    import numpy as np
    tool = loader.load_module("tools", "limit_readings")
    _, ref = _tiny()
    one = [{"input_ids": np.arange(16).reshape(1, 16)}]
    kinds = dict(tool.kinds_of(ref, ["control", "faults", "half", "unchanged"],
                               ["recurrence_dropped"], one))
    assert list(kinds) == ["control_fp8", "recurrence_dropped", "half_batch",
                           "unchanged"]
    assert kinds["recurrence_dropped"] == {
        "precision": "float32+recurrence_dropped"}
    assert one[0]["input_ids"][kinds["half_batch"]["rows"]].tolist() == [
        list(range(8))]
    four = [{"input_ids": np.zeros((4, 16))}]
    rows = dict(tool.kinds_of(ref, ["half"], [], four))["half_batch"]["rows"]
    assert four[0]["input_ids"][rows].shape == (2, 16)
    assert [k for k, _ in tool.kinds_of(ref, ["faults"], [], one)] == list(
        ref.FAULTS)
    with pytest.raises(SystemExit):
        tool.kinds_of(ref, ["faults"], ["no_such_fault"], one)


def test_the_bf16_witness_of_the_reference_stays_finite():
    res, ref = _tiny()
    cfg, traffic = res["config"], res["traffic"]
    weights = make_weights(ref, cfg, 3)
    batches = make_pool(traffic, cfg, 3, 1)[:3]
    base = run_steps(ref, cfg, weights, batches)
    got = run_steps(ref, cfg, weights, batches, precision="bf16")
    numbers, _ = compare.training_numbers(got, base)
    assert all(v < 0.5 for v in numbers.values()), numbers
