"""Phi-4-mini-flash (``models/phi4flash.py``, ``ops/selective_scan.py``, the
windowed flash kernels) against the plain float32 reference the benchmark
keeps (``benchmark/references/phi-4-mini-flash-reasoning.py``, which imports
nothing of the program), at a tiny size (``TINY``) on the CPU, with seeded
weights; kernels interpreted."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.models.phi4flash import (CROSS, FULL, GMU, MAMBA, WINDOW,
                                          Phi4FlashConfig,
                                          Phi4FlashForCausalLM, decay_mask)
from sparkdl_tpu.ops.flash_attention import flash_attention
from sparkdl_tpu.runner import XlaRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

REF = loader.load_module("references", "phi-4-mini-flash-reasoning")
# every kind of layer at a size the CPU steps in seconds: Mamba 0, 2, 4;
# window 1, 3; full 5; gated memory 6; cross 7
TINY = Phi4FlashConfig(vocab_size=96, hidden_size=32, intermediate_size=48,
                       num_attention_heads=4, num_key_value_heads=2,
                       num_hidden_layers=8, sliding_window=5, mamba_d_state=4)
TOL = dict(rtol=2e-4, atol=2e-6)
# the flash kernels in place of dense attention (interpreted here), small
# blocks so that a 24-position sequence has tiles inside and outside the band
FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)


def ref_cfg(c: Phi4FlashConfig, **over) -> dict:
    """The reference's configuration dict of a program config."""
    cfg = {k: getattr(c, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "mb_per_layer",
        "sliding_window", "layer_norm_eps", "mamba_d_state", "mamba_d_conv",
        "mamba_expand")}
    cfg.update(mamba_dt_rank=c.dt_rank, layers_kept=list(c.layers),
               num_hidden_layers=len(c.layers),
               published={"num_hidden_layers": c.num_hidden_layers},
               learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
               weight_decay=0.1, **over)
    return cfg


def seeded(c: Phi4FlashConfig, seed: int = 0):
    cfg = ref_cfg(c)
    return cfg, REF.init_weights(cfg, jax.random.PRNGKey(seed))


def ids_of(c: Phi4FlashConfig, rows: int = 2, seq: int = 24, seed: int = 1):
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


# -- the configuration ---------------------------------------------------------

def test_the_layer_rule_for_the_published_depth():
    c = Phi4FlashConfig()
    kinds = [c.kind_of(l) for l in range(32)]
    assert [kinds.count(k) for k in (MAMBA, WINDOW, FULL, GMU, CROSS)] == [
        9, 8, 1, 7, 7]
    assert [l for l, k in enumerate(kinds) if k == MAMBA] == list(
        range(0, 17, 2))
    assert [l for l, k in enumerate(kinds) if k == WINDOW] == list(
        range(1, 16, 2))
    assert kinds[17] == FULL
    assert [l for l, k in enumerate(kinds) if k == GMU] == list(
        range(18, 32, 2))
    assert [l for l, k in enumerate(kinds) if k == CROSS] == list(
        range(19, 32, 2))
    assert [REF.kind_of(l, 32, 2) for l in range(32)] == kinds


def test_the_parameter_count_from_shapes_is_the_published_3_85_billion():
    c = Phi4FlashConfig()
    shapes = jax.eval_shape(
        lambda k: Phi4FlashForCausalLM(c).init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert 3.84e9 < n < 3.86e9, n
    cut = dataclasses.replace(c, layers_kept=(14, 15, 16, 17, 18, 19),
                              vocab_size=25008)
    shapes = jax.eval_shape(
        lambda k: Phi4FlashForCausalLM(cut).init(
            k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(shapes["params"]))
    assert abs(n - 697.1e6) < 0.1e6, n


def test_config_reads_published_keys_and_the_benchmarks_cut_file():
    cfg = loader.load_json(loader.bench_path(
        "configs", "phi-4-mini-flash-reasoning.json"))
    c = loader.load_module(
        "programs", "phi-4-mini-flash-reasoning").model_config(cfg)
    assert c.layers == (14, 15, 16, 17, 18, 19)
    assert [c.kind_of(l) for l in c.layers] == [
        MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS]
    assert (c.hidden_size, c.intermediate_size, c.head_dim, c.d_inner,
            c.dt_rank, c.sliding_window, c.vocab_size,
            c.num_hidden_layers) == (2560, 10240, 64, 5120, 160, 512, 25008,
                                     32)
    published = dict(cfg, **cfg["published"])
    assert Phi4FlashConfig.from_dict(published) == Phi4FlashConfig()


def test_a_cut_that_drops_the_layer_another_reads_is_refused():
    with pytest.raises(ValueError, match="reads what layer 16 hands on"):
        dataclasses.replace(Phi4FlashConfig(),
                            layers_kept=(15, 17, 18)).layers
    with pytest.raises(ValueError, match="reads what layer 17 hands on"):
        dataclasses.replace(Phi4FlashConfig(),
                            layers_kept=(16, 18, 19)).layers


def test_layers_kept_keeps_the_published_index_in_lambda_init():
    """The same weights under two cuts that hold the same KINDS of layer at
    other published indices give other logits: ``lambda_init`` goes by the
    published index (0.8 - 0.6 exp(-0.3 l))."""
    c = Phi4FlashConfig()
    assert c.lambda_init(15) == pytest.approx(0.8 - 0.6 * np.exp(-4.5))
    a = dataclasses.replace(TINY, layers_kept=(2, 3, 4, 5, 6, 7))
    b = dataclasses.replace(TINY, layers_kept=(2, 1, 4, 5, 6, 7))
    assert [a.kind_of(l) for l in a.layers] == [b.kind_of(l)
                                                for l in b.layers]
    cfg, w = seeded(a)
    ids = ids_of(a)
    la = Phi4FlashForCausalLM(a).apply(w, ids, mutable=["counters"])[0]
    lb = Phi4FlashForCausalLM(b).apply(w, ids, mutable=["counters"])[0]
    np.testing.assert_allclose(la, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
    np.testing.assert_allclose(
        lb, REF.logits_fn(ref_cfg(b), w["params"], ids), **TOL)
    assert float(jnp.abs(la - lb).max()) > 1e-4


# -- against the reference ------------------------------------------------------

@pytest.mark.parametrize("attn_fn", ["auto", FLASH], ids=["dense", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(attn_fn):
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    model = Phi4FlashForCausalLM(TINY, attn_fn=attn_fn)
    logits, counters = model.apply_with_counters(w, ids)
    np.testing.assert_allclose(logits, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
    assert set(counters) == {"ssm_state_absmax", "ssm_dt_mean",
                             "diff_lambda_mean"}
    loss, aux, g = grads(model, w, ids)
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
    cfg, w = seeded(TINY)
    _, counters = Phi4FlashForCausalLM(TINY).apply_with_counters(
        w, ids_of(TINY))
    lam = [TINY.lambda_init(l) for l in TINY.layers
           if TINY.kind_of(l) in (WINDOW, FULL, CROSS)]
    # lambda - lambda_init = exp(lq1.lk1) - exp(lq2.lk2), small at 0.1 std
    assert abs(float(counters["diff_lambda_mean"]) - np.mean(lam)) < 0.2
    assert 1e-3 < float(counters["ssm_dt_mean"]) < 0.2
    assert 0 < float(counters["ssm_state_absmax"]) < 1e3


def fit_three_steps(c, w, batches, lr=1e-3):
    model = Phi4FlashForCausalLM(c)
    return XlaRunner(np=1).run(lambda ctx: ctx.fit(
        loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
        params={"params": w["params"]},
        tx=optax.adamw(lr, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                       mask=decay_mask),
        data=iter(batches), num_steps=3, log_every=1, resume=False))


def test_three_fit_steps_match_the_references():
    cfg, w = seeded(TINY, seed=3)
    batches = [{"input_ids": ids_of(TINY, rows=2, seq=16, seed=20 + i)}
               for i in range(3)]
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
    # the counters ride in every step's metrics
    for h in res["history"]:
        assert {"ssm_state_absmax", "ssm_dt_mean",
                "diff_lambda_mean"} <= set(h)
        assert all(np.isfinite(h[k]) for k in (
            "ssm_state_absmax", "ssm_dt_mean", "diff_lambda_mean"))


def test_the_counters_reach_fits_step_metrics_events():
    from sparkdl_tpu.runner import events
    cfg, w = seeded(TINY, seed=4)
    batches = [{"input_ids": ids_of(TINY, rows=2, seq=16, seed=30 + i)}
               for i in range(3)]
    t0 = events.get_recorder().tail()[-1]["t"] if \
        events.get_recorder().tail() else 0.0
    fit_three_steps(TINY, w, batches)
    recs = [r for r in events.get_recorder().tail()
            if r.get("name") == "step_metrics" and r["t"] > t0]
    assert len(recs) >= 3
    assert all(np.isfinite(r[k]) for r in recs for k in (
        "ssm_state_absmax", "ssm_dt_mean", "diff_lambda_mean"))


def test_weight_decay_spares_all_but_the_matrices_and_the_embedding():
    _, w = seeded(TINY)
    mask = leaves(decay_mask(w["params"]))
    for name, decays in mask.items():
        assert decays == (name.endswith("['kernel']")
                          or name.endswith("['embedding']")), name
    assert not mask["['layer_0']['mamba']['conv_kernel']"]
    assert not mask["['layer_1']['attn']['lambda_q1']"]


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_is_seen(fault):
    """A sound program differs from the reference with the fault planted:
    in the loss and in the gradient, far beyond rounding."""
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    b = {"input_ids": ids}
    loss, _, g = grads(Phi4FlashForCausalLM(TINY), w, ids)
    fl, fg = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, b, "float32+" + fault))(w["params"])
    assert abs(float(fl) - float(loss)) > 1e-6
    gap = max(float(jnp.linalg.norm(a - b_) / (jnp.linalg.norm(b_) + 1e-30))
              for a, b_ in zip(jax.tree_util.tree_leaves(g),
                               jax.tree_util.tree_leaves(fg)))
    assert gap > 1e-2, gap
    with pytest.raises(ValueError):
        REF.loss_fn(cfg, w["params"], b, "float32+no_such_fault")


# -- what one layer hands to the layers after it ---------------------------------

def test_the_vocabulary_slices_logits_are_the_unsliced_models_columns():
    cfg, w = seeded(TINY)
    ids = ids_of(dataclasses.replace(TINY, vocab_size=48))
    whole = Phi4FlashForCausalLM(TINY).apply(w, ids, mutable=["counters"])[0]
    cut = dataclasses.replace(TINY, vocab_size=48)
    p = jax.tree_util.tree_map(lambda x: x, w["params"])
    p["embed_tokens"] = {"embedding": p["embed_tokens"]["embedding"][:48]}
    sliced = Phi4FlashForCausalLM(cut).apply({"params": p}, ids,
                                             mutable=["counters"])[0]
    np.testing.assert_allclose(sliced, whole[..., :48], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("giver,readers", [
    (4, ("layer_6",)), (5, ("layer_7",))], ids=["m", "kv"])
def test_gradients_reach_the_giving_layer_through_what_it_hands_on(
        giver, readers):
    """Zero the weights through which only the READERS use what layer
    ``giver`` handed on (the gated memory unit's ``W_2``, the cross
    attention's ``W_o``): the giver's gradient changes, so part of it came
    back through ``m`` (layer n/2 = 4) or ``k, v`` (layer n/2 + 1 = 5)."""
    _, w = seeded(TINY)
    ids = ids_of(TINY)
    model = Phi4FlashForCausalLM(TINY)
    _, _, g = grads(model, w, ids)
    cut = jax.tree_util.tree_map(lambda x: x, w)
    for name in readers:
        sub = cut["params"][name]["gmu" if "gmu" in cut["params"][name]
                                  else "attn"]
        sub["out_proj"] = {"kernel": jnp.zeros_like(
            sub["out_proj"]["kernel"])}
    _, _, g_cut = grads(model, cut, ids)
    part = "mamba" if giver == 4 else "attn"
    leaf = {"mamba": "x_proj", "attn": "Wqkv"}[part]
    a = g[f"layer_{giver}"][part][leaf]["kernel"]
    b = g_cut[f"layer_{giver}"][part][leaf]["kernel"]
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)) > 1e-3
    if giver == 5:
        # the values' columns of W_qkv feed this layer's own attention too;
        # with the reader cut they still get a gradient, only another one
        assert float(jnp.linalg.norm(b)) > 0
