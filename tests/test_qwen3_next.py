"""Qwen3-Next-80B-A3B-Instruct (``models/qwen3_next.py`` over
``ops/gated_delta.py``, the flash kernels and ``parallel/moe.py``) against the
plain float32 reference the benchmark keeps
(``benchmark/references/qwen3-next-80b-a3b-instruct.py``, which imports
nothing of the program and walks the delta rule position by position), at a
tiny size (``TINY``) on the CPU, with seeded weights; kernels interpreted."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.models.qwen3_next import (ATTENTION, LINEAR,
                                           Qwen3NextSparseMoe, decay_mask)
from sparkdl_tpu.ops.flash_attention import flash_attention
from sparkdl_tpu.runner import XlaRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

NAME = "qwen3-next-80b-a3b-instruct"
REF = loader.load_module("references", NAME)
# both kinds of layer twice over at a size the CPU steps in seconds; a chunk
# of 8 in a sequence of 24: the state is handed on twice a layer. Half of the
# eight experts held, from the third on
TINY = dataclasses.replace(
    Qwen3NextConfig.tiny(), num_hidden_layers=4, experts_held=(2, 4))
COUNTERS = {"gated_delta_chunk_log_decay_min",
            "gated_delta_chunk_log_decay_median", "moe_assignments",
            "moe_assignments_held", "moe_held_load_max", "moe_held_load_mean",
            "moe_dropped"}
TOL = dict(rtol=2e-4, atol=2e-6)
FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)


def ref_cfg(c: Qwen3NextConfig, **over) -> dict:
    """The reference's configuration dict of a program config."""
    cfg = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)
           if f.name not in ("layers_kept", "experts_held")}
    first, held = c.experts_held or (0, c.num_experts)
    cfg.update(layers_kept=list(c.layers), num_hidden_layers=len(c.layers),
               num_routed_experts=c.num_experts, num_experts=held,
               first_expert_held=first, learning_rate=1e-3, adam_b1=0.9,
               adam_b2=0.95, adam_eps=1e-8, weight_decay=0.1, **over)
    return cfg


def seeded(c: Qwen3NextConfig, seed: int = 0):
    cfg = ref_cfg(c)
    return cfg, REF.init_weights(cfg, jax.random.PRNGKey(seed))


def ids_of(c, rows: int = 2, seq: int = 24, seed: int = 1):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (rows, seq)).astype(np.int32)


def leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def grads(model, w, ids):
    loss_fn = causal_lm_loss_fn()
    (loss, aux), g = jax.value_and_grad(
        lambda p: loss_fn(p, model.apply_with_counters, {"input_ids": ids}),
        has_aux=True)(w)
    return loss, aux, g["params"]


def count(c: Qwen3NextConfig) -> int:
    shapes = jax.eval_shape(
        lambda k: Qwen3NextForCausalLM(c).init(
            k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))


# -- the configuration ---------------------------------------------------------

def cut_file() -> dict:
    return loader.load_json(loader.bench_path("configs", NAME + ".json"))


def published() -> dict:
    cfg = cut_file()
    return dict(cfg, **cfg["published"])


def test_the_catalogs_keys_give_the_published_model():
    c = Qwen3NextConfig.from_dict(published())
    assert c == Qwen3NextConfig()
    assert c.layers == tuple(range(48))
    assert [l for l in c.layers if c.kind(l) == ATTENTION] == list(
        range(3, 48, 4))
    assert [c.kind(l) for l in range(4)] == [LINEAR] * 3 + [ATTENTION]
    assert (c.hidden_size, c.head_dim, c.num_attention_heads,
            c.num_key_value_heads, c.linear_num_key_heads,
            c.linear_num_value_heads, c.linear_key_head_dim,
            c.linear_value_head_dim, c.linear_conv_kernel_dim, c.num_experts,
            c.num_experts_per_tok, c.moe_intermediate_size,
            c.shared_expert_intermediate_size, c.vocab_size) == (
                2048, 256, 16, 2, 16, 32, 128, 128, 4, 512, 10, 512, 512,
                151936)
    assert (c.rms_norm_eps, c.rope_theta, c.partial_rotary_factor) == (
        1e-6, 1e7, 0.25)


def test_the_parameter_counts_from_shapes_without_allocating():
    assert count(Qwen3NextConfig.from_dict(published())) == 79_674_391_296
    cut = loader.load_module("programs", NAME).model_config(cut_file())
    assert cut.layers == (0, 1, 2, 3) and cut.vocab_size == 18992
    assert cut.experts_held == (0, 32) and cut.num_experts == 512
    assert count(cut) == 625_667_136


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True)])
def test_what_the_model_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        Qwen3NextConfig.from_dict(dict(published(), **{key: value}))


# -- against the reference ------------------------------------------------------

@pytest.mark.parametrize("attn_fn", ["auto", FLASH], ids=["dense", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(attn_fn):
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    model = Qwen3NextForCausalLM(TINY, attn_fn=attn_fn)
    logits, counters = model.apply_with_counters(w, ids)
    np.testing.assert_allclose(logits, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
    assert set(counters) == COUNTERS
    loss, aux, g = grads(model, w, ids)
    assert COUNTERS <= set(aux)
    rl, rg = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, {"input_ids": ids}))(w["params"])
    np.testing.assert_allclose(loss, rl, rtol=1e-5)
    got, want = leaves(g), leaves(rg)
    assert got.keys() == want.keys()
    for name, leaf in want.items():
        scale = float(jnp.linalg.norm(leaf))
        assert scale > 0, name          # no leaf of this model is dead
        np.testing.assert_allclose(got[name], leaf, rtol=2e-3,
                                   atol=1e-3 * scale, err_msg=name)


def test_the_counters_are_what_they_say():
    _, w = seeded(TINY)
    ids = ids_of(TINY)
    _, c = Qwen3NextForCausalLM(TINY).apply_with_counters(w, ids)
    picks = ids.size * TINY.num_experts_per_tok * len(TINY.layers)
    assert float(c["moe_assignments"]) == picks
    assert 0 < float(c["moe_assignments_held"]) < picks
    assert float(c["moe_dropped"]) == 0
    assert float(c["moe_held_load_max"]) >= float(c["moe_held_load_mean"]) > 0
    # a chunk of 8 positions at half-lives of 64 positions and more, the
    # per-position rate up to a few times its value at a = 0: the logs
    assert -2.0 < float(c["gated_delta_chunk_log_decay_min"]) \
        < float(c["gated_delta_chunk_log_decay_median"]) < 0


def fit_three_steps(c, w, batches, lr=1e-3):
    model = Qwen3NextForCausalLM(c)
    return XlaRunner(np=1).run(lambda ctx: ctx.fit(
        loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
        params={"params": w["params"]},
        tx=optax.adamw(lr, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                       mask=decay_mask),
        data=iter(batches), num_steps=3, log_every=1, resume=False))


def test_three_fit_steps_match_the_references():
    from sparkdl_tpu.runner import events
    cfg, w = seeded(TINY, seed=3)
    batches = [{"input_ids": ids_of(TINY, rows=2, seq=16, seed=20 + i)}
               for i in range(3)]
    tail = events.get_recorder().tail()
    t0 = tail[-1]["t"] if tail else 0.0
    res = fit_three_steps(TINY, w, batches)
    params, opt = w["params"], REF.opt_init(cfg, w["params"])
    losses = []
    for i, b in enumerate(batches):
        loss, g = jax.value_and_grad(
            lambda p: REF.loss_fn(cfg, p, b))(params)
        losses.append(float(loss))
        params, opt = REF.opt_update(cfg, params, g, opt, jnp.float32(i + 1))
    np.testing.assert_allclose([h["loss"] for h in res["history"]], losses,
                               rtol=1e-5)
    got = leaves(jax.device_get(res["state"].params["params"]))
    for name, leaf in leaves(params).items():
        np.testing.assert_allclose(got[name], leaf, rtol=1e-3, atol=2e-5,
                                   err_msg=name)
    # the counters ride in every step's metrics and reach fit's ring events
    for h in res["history"]:
        assert COUNTERS <= set(h)
        assert all(np.isfinite(h[k]) for k in COUNTERS)
    recs = [r for r in events.get_recorder().tail()
            if r.get("name") == "step_metrics" and r["t"] > t0]
    assert len(recs) >= 3
    assert all(np.isfinite(r[k]) for r in recs for k in COUNTERS)


def test_weight_decay_is_on_the_matrices_the_stacks_the_embedding_and_head():
    _, w = seeded(TINY)
    mask = leaves(decay_mask(w["params"]))
    decayed = ("['kernel']", "['embedding']", "['w1']", "['w3']", "['w2']")
    for name, decays in mask.items():
        assert decays == name.endswith(decayed), name
    for spared in ("conv_kernel", "A_log", "dt_bias"):
        assert not mask[f"['layer_0']['linear_attn']['{spared}']"]
    assert not mask["['layer_0']['linear_attn']['norm']['weight']"]
    assert mask["['layer_0']['mlp']['routed']['experts']['w2']"]
    assert mask["['layer_0']['mlp']['routed']['router']['kernel']"]
    assert mask["['lm_head']['kernel']"]


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_is_seen(fault):
    """A sound program differs from the reference with the fault planted,
    far beyond rounding, in some leaf of the gradient."""
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    b = {"input_ids": ids}
    _, _, g = grads(Qwen3NextForCausalLM(TINY), w, ids)
    fg = jax.grad(lambda p: REF.loss_fn(cfg, p, b, "float32+" + fault))(
        w["params"])
    gap = max(float(jnp.linalg.norm(a - b_) / (jnp.linalg.norm(b_) + 1e-30))
              for a, b_ in zip(jax.tree_util.tree_leaves(g),
                               jax.tree_util.tree_leaves(fg)))
    assert gap > 1e-2, gap
    with pytest.raises(ValueError):
        REF.loss_fn(cfg, w["params"], b, "float32+no_such_fault")


# -- the cut ---------------------------------------------------------------------

def test_the_shares_routed_parts_and_the_shared_expert_once_make_the_layer():
    """Sixteen chips share a layer, two experts each of 32 here: the routed
    parts that the sixteen shares of the PROGRAM's layer give, with the shared
    expert (which every chip computes alike) counted once, add up to what the
    uncut REFERENCE gives for the whole layer."""
    shares, each = 16, 2
    whole = dataclasses.replace(TINY, num_experts=shares * each,
                                num_experts_per_tok=5, experts_held=None)
    cfg, w = seeded(whole)
    p = w["params"]["layer_0"]["mlp"]
    f = jax.random.normal(jax.random.PRNGKey(5), (2, 12, whole.hidden_size))
    rows = f.reshape(-1, whole.hidden_size)
    shared = REF.shared_part(rows, p)
    want = REF.routed_part(rows, p["routed"], cfg) + shared
    total, held_picks = 0.0, 0.0
    for share in range(shares):
        lo = share * each
        cut = dataclasses.replace(whole, experts_held=(lo, each))
        mine = jax.tree_util.tree_map(lambda x: x, p)
        mine["routed"] = dict(p["routed"], experts={
            n: x[lo:lo + each] for n, x in p["routed"]["experts"].items()})
        out, mut = Qwen3NextSparseMoe(cut).apply(
            {"params": mine}, f, mutable=["counters"])
        total = total + (out.reshape(rows.shape) - shared)
        held_picks += float(mut["counters"]["routed"]["moe_assignments_held"])
    np.testing.assert_allclose(total + shared, want, rtol=1e-4, atol=1e-6)
    assert held_picks == rows.shape[0] * whole.num_experts_per_tok
    assert float(jnp.linalg.norm(shared)) > 0.1 * float(jnp.linalg.norm(want))


def test_the_vocabulary_slices_logits_are_the_unsliced_models_columns():
    _, w = seeded(TINY)
    cut = dataclasses.replace(TINY, vocab_size=48)
    ids = ids_of(cut)
    whole = Qwen3NextForCausalLM(TINY).apply(w, ids, mutable=["counters"])[0]
    p = jax.tree_util.tree_map(lambda x: x, w["params"])
    p["embed_tokens"] = {"embedding": p["embed_tokens"]["embedding"][:48]}
    p["lm_head"] = {"kernel": p["lm_head"]["kernel"][:, :48]}
    sliced = Qwen3NextForCausalLM(cut).apply({"params": p}, ids,
                                             mutable=["counters"])[0]
    np.testing.assert_allclose(sliced, whole[..., :48], rtol=1e-5, atol=1e-6)


def test_layers_kept_goes_by_the_published_index():
    """Layers 1 to 3 of the four (linear, full, linear, full at an interval
    of 2): the attention layer leads, as published layer 1 is, and the
    reference's cut agrees."""
    cut = dataclasses.replace(TINY, layers_kept=(1, 2, 3))
    cfg, w = seeded(cut)
    assert set(w["params"]["layer_0"]) >= {"self_attn"}
    assert set(w["params"]["layer_1"]) >= {"linear_attn"}
    ids = ids_of(cut)
    got = Qwen3NextForCausalLM(cut).apply(w, ids, mutable=["counters"])[0]
    np.testing.assert_allclose(got, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)


def test_rope_turns_the_leading_quarter_of_a_head_and_no_more():
    from sparkdl_tpu.models.lfm2 import rope_rotate_half
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 6, 16))
    part = rope_rotate_half(x, 1e4, 4)
    np.testing.assert_array_equal(part[..., 4:], x[..., 4:])
    np.testing.assert_allclose(part[..., :4],
                               rope_rotate_half(x[..., :4], 1e4), rtol=1e-6)
    assert float(jnp.abs(part[:, :, 1:, :4] - x[:, :, 1:, :4]).max()) > 1e-2
    np.testing.assert_array_equal(rope_rotate_half(x, 1e4, 16),
                                  rope_rotate_half(x, 1e4))


def test_the_seeded_states_halve_in_64_to_8192_positions():
    cfg, w = seeded(TINY, seed=7)
    for i, l in enumerate(TINY.layers):
        if TINY.kind(l) != LINEAR:
            continue
        p = w["params"][f"layer_{i}"]["linear_attn"]
        rate = jnp.exp(p["A_log"]) * jax.nn.softplus(p["dt_bias"])
        life = np.log(2.0) / np.asarray(rate)
        assert (life >= 64 * 0.999).all() and (life <= 8192 * 1.001).all()
