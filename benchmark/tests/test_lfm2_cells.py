"""The cells PR 29 added: they resolve dry, the new configuration's operation
counts match a hand count, and each new reader reads a made-up ring and trace."""

import pytest

import tiny
from harness import kernel_time, loader, step_metrics
from sparkdl_tpu.runner import events

LFM2 = "lfm2-8b-a1b.pretrain-s8192-b2"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(metric, ctx):
    return loader.load_module("layer_metrics", metric).read(ctx)


@pytest.fixture(autouse=True)
def _fresh_ring():
    yield
    events.reset()


# -- the cells resolve ---------------------------------------------------------

def test_the_lfm2_cell_resolves_to_its_own_files():
    res = loader.resolve_cell(LFM2)
    assert res["cell"]["chips"] == 1
    assert res["files"]["reference"] == ("references", "lfm2-8b-a1b")
    assert res["traffic"]["inputs"]["input_ids"]["shape"] == [8192]
    assert res["traffic"]["per_chip_batch"] == 2
    names = {m["name"] for m in res["per_layer"]}
    assert names >= {"train_step_mfu", "moe_held_share",
                     "moe_load_max_over_mean", "flash_attention_fwd_roofline",
                     "flash_attention_bwd_roofline"}
    assert set(res["limits"]["limits"]) <= {
        "loss_1", "loss_2", "loss_3", "grad1_leaf", "delta_leaf", "grad1_all",
        "delta_all"} and res["limits"]["limits"]


def test_the_configuration_keeps_every_published_width():
    entry = loader.resolve_cell(LFM2)["config_entry"]
    cfg = loader.resolve_cell(LFM2)["config"]
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/LiquidAI/LFM2-8B-A1B/"
                               "blob/main/config.json")
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 7168, "max_position_embeddings": 128000,
                 "moe_intermediate_size": 1792, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_experts_per_tok": 4, "num_key_value_heads": 8,
                 "rope_theta": 1000000, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "model_type": "lfm2_moe"}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == 24      # the published list, whole
    assert cfg["published"] == {"num_hidden_layers": 24,
                                "num_dense_layers": 2, "num_experts": 32,
                                "vocab_size": 65536}
    assert set(cfg["reduced"]) == set(entry["reduced"])
    assert "4 chips share each layer" in cfg["deployment"]
    assert cfg["num_routed_experts"] == 32 and cfg["num_experts"] == 8


# -- operations, against a hand count at a tiny size ---------------------------

TINY_CFG = {"hidden_size": 8, "intermediate_size": 12,
            "moe_intermediate_size": 4, "num_attention_heads": 2,
            "num_key_value_heads": 1, "conv_L_cache": 3, "vocab_size": 10,
            "layer_types": ["conv", "full_attention", "conv"],
            "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 2,
            "num_routed_experts": 8, "num_experts_per_tok": 4}
TINY_TRAFFIC = {"inputs": {"input_ids": {"shape": [6]}}}


def test_lfm2_operations_against_a_hand_count():
    f = loader.load_module("flops", "lfm2-8b-a1b")
    # head 10*8; conv layer 0: 4*64 + dense 3*8*12; attention layer 1:
    # q and o 2*64, k and v 2*8*4, router 8*8, experts 4*2/8 = 1 assignment
    # a token of 3*8*4; conv layer 2: 4*64 + router + experts
    params = 80 + (256 + 288) + (128 + 64 + 64 + 96) + (256 + 64 + 96)
    assert f.matmul_params_per_token(TINY_CFG) == params == 1392
    # one attention layer, 2 heads of 4: 4 * 8 per pair, 6 * 7 / 2 pairs
    assert f.attention_flops_per_sequence(TINY_CFG, 6) == 32 * 21 == 672
    # two short convolutions: 3 taps * 8 channels, multiply and add
    fwd = 6 * (2 * params + 2 * 48) + 672
    assert f.forward_flops_per_sequence(TINY_CFG, 6) == fwd
    assert f.train_flops_per_example(TINY_CFG, TINY_TRAFFIC) == 3 * fwd
    k = f.flash_attention_fwd_per_example(TINY_CFG, TINY_TRAFFIC)
    assert k["flops"] == 672
    # q and o 2*6*8, k and v 2*6*4, in bf16; statistics 6*2 float32
    assert k["bytes"] == 2 * (96 + 48) + 48


def test_lfm2_at_the_cells_size_is_433_mflop_a_token_forward():
    f = loader.load_module("flops", "lfm2-8b-a1b")
    res = loader.resolve_cell(LFM2)
    per_token = f.forward_flops_per_sequence(res["config"], 8192) / 8192
    assert 430e6 < per_token < 436e6, per_token
    step = 2 * f.train_flops_per_example(res["config"], res["traffic"])
    assert 21e12 < step < 21.6e12, step


# -- the readers, on a made-up ring and trace ----------------------------------

def _ring(rows, t0=1000.0):
    """``step_metrics`` events one second apart, and the driver's spans
    around all but the last of them."""
    rec = events.reset()
    for i, row in enumerate(rows):
        rec.emit("step_metrics", "P", {"step": i + 1, **row}, t=t0 + i)
    return [{"name": "step_compute", "t": t0 - 0.5, "dur_s": 1e-3},
            {"name": "step_compute", "t": t0 + len(rows) - 1.5,
             "dur_s": 1e-3}]


def test_moe_readers_take_the_median_over_the_traced_stretch():
    rows = [{"moe_assignments": 400.0, "moe_assignments_held": 100.0,
             "moe_held_load_max": 30.0, "moe_held_load_mean": 12.5},
            {"moe_assignments": 400.0, "moe_assignments_held": 120.0,
             "moe_held_load_max": 45.0, "moe_held_load_mean": 15.0},
            {"moe_assignments": 400.0, "moe_assignments_held": 80.0,
             "moe_held_load_max": 20.0, "moe_held_load_mean": 10.0},
            # after the traced stretch: not read
            {"moe_assignments": 400.0, "moe_assignments_held": 400.0,
             "moe_held_load_max": 400.0, "moe_held_load_mean": 1.0}]
    ctx = {"spans": _ring(rows)}
    assert len(step_metrics.records(ctx)) == 3
    assert _read("moe_held_share", ctx) == pytest.approx(25.0)
    assert _read("moe_load_max_over_mean", ctx) == pytest.approx(2.4)


def test_moe_readers_read_nothing_from_a_program_without_the_event():
    ctx = {"spans": _ring([{"loss": 1.0}, {"loss": 0.9}])}
    assert _read("moe_held_share", ctx) is None
    assert _read("moe_load_max_over_mean", ctx) is None
    events.reset()
    assert _read("moe_held_share", {"spans": []}) is None
    assert _read("moe_held_share", {}) is None


def _trace(ops_per_step, steps=4, step_ns=10_000_000):
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


def test_flash_roofline_counts_the_models_need_against_the_kernels_time():
    res = loader.resolve_cell(LFM2)
    flops = loader.load_module("flops", "lfm2-8b-a1b")
    need = flops.flash_attention_fwd_per_example(res["config"], res["traffic"])
    least_s = 2 * need["flops"] / 197e12       # two sequences a step
    assert need["flops"] / 197e12 > need["bytes"] / 819e9   # compute-bound
    kernel = ("%flash_attention_fwd.3 = (bf16[64,8192,64], f32[64,16,1,512]) "
              "custom-call(bf16[64,8192,64] %bitcast.1)")
    consumer = ("%fusion.1 = f32[] fusion(bf16[64,8192,64] "
                "%flash_attention_fwd.3)")
    # the kernel runs twice a step (forward and recomputation), each at
    # twice the least time: a quarter of the roofline
    dur = int(2 * least_s * 1e9)
    tr = _trace([(kernel, dur), (consumer, 1_000_000), (kernel, dur)],
                step_ns=20_000_000)
    ctx = _ctx(tr, res, flops)
    assert kernel_time.cell_of("flash_attention_fwd_roofline", ctx)["name"] \
        == LFM2
    assert _read("flash_attention_fwd_roofline", ctx) == pytest.approx(
        25.0, rel=1e-3)
    # in a cell the metric does not list, and on a trace without the kernel
    assert _read("flash_attention_fwd_roofline",
                 dict(ctx, global_batch=128)) is None
    bare = _trace([("%fusion.1 = f32[] fusion()", 1_000_000)])
    assert _read("flash_attention_fwd_roofline", _ctx(bare, res, flops)) \
        is None
    assert _read("flash_attention_fwd_roofline", {}) is None


def test_flash_backward_need_against_a_hand_count():
    res = loader.resolve_cell(LFM2)
    flops = loader.load_module("flops", "lfm2-8b-a1b")
    need = flops.flash_attention_bwd_per_example(res["config"], res["traffic"])
    # one attention layer: five products of 2 x 64 operations a head and
    # pair, 32 heads, 8192 x 8193 / 2 causal pairs
    assert need["flops"] == 5 * 2 * 64 * 32 * 8192 * 8193 // 2
    fwd = flops.flash_attention_fwd_per_example(res["config"], res["traffic"])
    assert need["flops"] == pytest.approx(2.5 * fwd["flops"])
    # q, o, do, dq at 32 heads and k, v, dk, dv at 8, bf16; lse and
    # rowsum(do * o) in float32 a head and row
    assert need["bytes"] == 4 * 8192 * 2048 * 2 + 4 * 8192 * 512 * 2 \
        + 2 * 8192 * 32 * 4
    # 6.98 ms a step of two sequences at the bf16 peak (PERF.md section 5)
    assert 2 * need["flops"] / 197e12 == pytest.approx(6.98e-3, rel=2e-3)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9   # compute-bound


def test_flash_backward_roofline_sums_the_pair_and_nothing_else():
    res = loader.resolve_cell(LFM2)
    flops = loader.load_module("flops", "lfm2-8b-a1b")
    need = flops.flash_attention_bwd_per_example(res["config"], res["traffic"])
    least_s = 2 * need["flops"] / 197e12       # two sequences a step
    dkv = "%flash_attention_bwd_dkv.1 = (bf16[64,8192,64]) custom-call()"
    dq = "%flash_attention_bwd_dq.1 = bf16[64,8192,64] custom-call()"
    fwd = "%flash_attention_fwd.3 = (bf16[64,8192,64]) custom-call()"
    consumer = "%fusion.9 = f32[] fusion(%flash_attention_bwd_dq.1)"
    # the pair takes four times the least time between them: a quarter
    dur = int(2 * least_s * 1e9)
    tr = _trace([(fwd, 1_000_000), (dkv, dur), (consumer, 1_000_000),
                 (dq, dur)], step_ns=80_000_000)
    ctx = _ctx(tr, res, flops)
    assert _read("flash_attention_bwd_roofline", ctx) == pytest.approx(
        25.0, rel=1e-3)
    # the forward's reader, through the same helper, sees its own kernel only
    assert _read("flash_attention_fwd_roofline", ctx) > 100   # 1 ms: made up
    # in a cell the metric does not list, and on a trace without the pair
    assert _read("flash_attention_bwd_roofline",
                 dict(ctx, global_batch=128)) is None
    bare = _trace([(fwd, 1_000_000)])
    assert _read("flash_attention_bwd_roofline", _ctx(bare, res, flops)) \
        is None
    assert _read("flash_attention_bwd_roofline", {}) is None


def test_the_roofline_readers_start_at_the_second_program():
    """A trace that starts inside a step holds the rest of that step's
    program and only its later kernel calls: counted as a step, it read
    need x N over 2N - 1 calls (PERF.md section 6, PR 30)."""
    res = loader.resolve_cell(LFM2)
    flops = loader.load_module("flops", "lfm2-8b-a1b")
    need = flops.flash_attention_fwd_per_example(res["config"], res["traffic"])
    dur = int(2 * 2 * need["flops"] / 197e12 * 1e9)
    kernel = "%flash_attention_fwd.3 = (bf16[64,8192,64]) custom-call()"
    tr = _trace([(kernel, dur), (kernel, dur)], step_ns=20_000_000)
    whole = _read("flash_attention_fwd_roofline", _ctx(tr, res, flops))
    plane = tr["/device:TPU:0"]
    # cut the first program: its first kernel call ran before the trace
    name, t, d = plane["XLA Modules"][0]
    plane["XLA Modules"][0] = (name, t + dur, d - dur)
    plane["XLA Ops"] = plane["XLA Ops"][1:]
    assert _read("flash_attention_fwd_roofline", _ctx(tr, res, flops)) \
        == pytest.approx(whole) == pytest.approx(25.0, rel=1e-3)
