"""granite-4.0-h-micro (``models/granite_hybrid.py`` over ``ops/ssd_scan.py``
and the flash kernels) against the plain float32 reference the benchmark
keeps (``benchmark/references/granite-4.0-h-micro.py``, which imports nothing
of the program and walks the recurrence position by position), at a tiny size
(``TINY``) on the CPU, with seeded weights; kernels interpreted."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM
from sparkdl_tpu.models.granite_hybrid import ATTENTION, MAMBA, decay_mask
from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.ops.flash_attention import flash_attention
from sparkdl_tpu.runner import XlaRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

NAME = "granite-4.0-h-micro"
REF = loader.load_module("references", NAME)
# both kinds of layer at a size the CPU steps in seconds; a chunk of 8 in a
# sequence of 24: the state is handed on twice a layer
TINY = GraniteHybridConfig(
    vocab_size=96, hidden_size=32, shared_intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2,
    layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA), mamba_n_heads=4,
    mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8)
COUNTERS = {"ssm_state_absmax", "ssm_dt_mean", "ssd_chunk_log_decay_min"}
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
TOL = dict(rtol=2e-4, atol=2e-6)
FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)


def ref_cfg(c: GraniteHybridConfig, **over) -> dict:
    """The reference's configuration dict of a program config."""
    cfg = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)
           if f.name != "layers_kept"}
    cfg.update(layer_types=list(c.layer_types), layers_kept=list(c.layers),
               num_hidden_layers=len(c.layers), learning_rate=1e-3,
               adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8, weight_decay=0.1,
               **over)
    return cfg


def seeded(c: GraniteHybridConfig, seed: int = 0):
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


def count(c: GraniteHybridConfig) -> int:
    shapes = jax.eval_shape(
        lambda k: GraniteHybridForCausalLM(c).init(
            k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))


# -- the configuration ---------------------------------------------------------

def published() -> dict:
    cfg = loader.load_json(loader.bench_path("configs", NAME + ".json"))
    return dict(cfg, **cfg["published"])


def test_the_layer_kinds_by_published_index():
    c = GraniteHybridConfig.from_dict(published())
    assert len(c.layer_types) == 40 and c.layers == tuple(range(40))
    assert [l for l, k in enumerate(c.layer_types) if k == ATTENTION] == [
        5, 15, 25, 35]
    assert c.layer_types.count(MAMBA) == 36
    assert (c.hidden_size, c.shared_intermediate_size, c.head_dim, c.d_inner,
            c.mamba_d_state, c.mamba_chunk_size, c.vocab_size) == (
                2048, 8192, 64, 4096, 128, 256, 100352)
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (12, 0.22, 0.015625,
                                                          8)
    assert GraniteHybridConfig().layer_types == c.layer_types[:10]


def test_the_parameter_counts_from_shapes_without_allocating():
    whole = GraniteHybridConfig.from_dict(published())
    assert count(whole) == 3_191_396_096
    cut = loader.load_module("programs", NAME).model_config(
        loader.load_json(loader.bench_path("configs", NAME + ".json")))
    assert cut.layers == tuple(range(10)) and cut.vocab_size == 12544
    assert [cut.layer_types[l] for l in cut.layers] == [MAMBA] * 5 + [
        ATTENTION] + [MAMBA] * 4
    assert count(cut) == 772_160_448


@pytest.mark.parametrize("key,value,what", [
    ("num_local_experts", 8, "routed"),
    ("position_embedding_type", "rope", "nope"),
    ("tie_word_embeddings", False, "untied"),
    ("mamba_expand", 3, "mamba_expand")])
def test_what_the_model_does_not_hold_is_refused(key, value, what):
    with pytest.raises(ValueError, match=what):
        GraniteHybridConfig.from_dict(dict(published(), **{key: value}))


# -- against the reference ------------------------------------------------------

@pytest.mark.parametrize("attn_fn", ["auto", FLASH], ids=["dense", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(attn_fn):
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    model = GraniteHybridForCausalLM(TINY, attn_fn=attn_fn)
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
    _, counters = GraniteHybridForCausalLM(TINY).apply_with_counters(
        w, ids_of(TINY))
    assert 1e-3 < float(counters["ssm_dt_mean"]) < 0.2
    assert 0 < float(counters["ssm_state_absmax"]) < 1e3
    # a chunk of 8 positions at dt A of at most 0.2 * 16 a position hands on
    # more than exp(-26) and less than all of a state; the counter is the log
    assert -26 < float(counters["ssd_chunk_log_decay_min"]) < 0


def fit_three_steps(c, w, batches, lr=1e-3):
    model = GraniteHybridForCausalLM(c)
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


def test_weight_decay_spares_all_but_the_matrices_and_the_embedding():
    _, w = seeded(TINY)
    mask = leaves(decay_mask(w["params"]))
    for name, decays in mask.items():
        assert decays == (name.endswith("['kernel']")
                          or name.endswith("['embedding']")), name
    for spared in ("conv_kernel", "conv_bias", "A_log", "D", "dt_bias"):
        assert not mask[f"['layer_0']['mamba']['{spared}']"]
    assert not mask["['layer_0']['mamba']['norm']['scale']"]
    assert mask["['layer_0']['mamba']['in_proj']['kernel']"]


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_is_seen(fault):
    """A sound program differs from the reference with the fault planted,
    far beyond rounding, in some leaf of the gradient."""
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    b = {"input_ids": ids}
    _, _, g = grads(GraniteHybridForCausalLM(TINY), w, ids)
    fg = jax.grad(lambda p: REF.loss_fn(cfg, p, b, "float32+" + fault))(
        w["params"])
    gap = max(float(jnp.linalg.norm(a - b_) / (jnp.linalg.norm(b_) + 1e-30))
              for a, b_ in zip(jax.tree_util.tree_leaves(g),
                               jax.tree_util.tree_leaves(fg)))
    assert gap > 1e-2, gap
    with pytest.raises(ValueError):
        REF.loss_fn(cfg, w["params"], b, "float32+no_such_fault")


# -- the cut, the multipliers, the scale ------------------------------------------

def test_the_vocabulary_slices_logits_are_the_unsliced_models_columns():
    _, w = seeded(TINY)
    cut = dataclasses.replace(TINY, vocab_size=48)
    ids = ids_of(cut)
    whole = GraniteHybridForCausalLM(TINY).apply(w, ids,
                                                 mutable=["counters"])[0]
    p = jax.tree_util.tree_map(lambda x: x, w["params"])
    p["embed_tokens"] = {"embedding": p["embed_tokens"]["embedding"][:48]}
    sliced = GraniteHybridForCausalLM(cut).apply({"params": p}, ids,
                                                 mutable=["counters"])[0]
    np.testing.assert_allclose(sliced, whole[..., :48], rtol=1e-5, atol=1e-6)


def test_layers_kept_goes_by_the_published_index():
    """Layers 1 to 3 of the four: the attention layer leads, as published
    layer 1 is, and the reference's cut agrees."""
    cut = dataclasses.replace(TINY, layers_kept=(1, 2, 3))
    cfg, w = seeded(cut)
    assert set(w["params"]["layer_0"]) >= {"self_attn"}
    ids = ids_of(cut)
    got = GraniteHybridForCausalLM(cut).apply(w, ids, mutable=["counters"])[0]
    np.testing.assert_allclose(got, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_is_seen(name):
    """Set one multiplier to 1 and the loss and its gradient move (seeded
    weights of 0.02 leave the attention's softmax nearly flat, so its scale
    shows in the gradient of ``W_q`` and hardly in the loss), in the program
    and in the reference alike."""
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    _, _, g = grads(GraniteHybridForCausalLM(TINY), w, ids)
    other = dataclasses.replace(TINY, **{name: 1.0})
    moved, _, g_moved = grads(GraniteHybridForCausalLM(other), w, ids)
    gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
              for a, b in zip(jax.tree_util.tree_leaves(g),
                              jax.tree_util.tree_leaves(g_moved)))
    assert gap > 0.5, (name, gap)
    np.testing.assert_allclose(
        moved, REF.loss_fn(ref_cfg(other), w["params"], {"input_ids": ids}),
        rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_scaled_query_is_a_softmax_at_one_sixty_fourth(dtype):
    """Heads of 64 through an attention function that scales by
    ``1 / sqrt(64)``: ``q * 0.125`` is exact in either dtype (a power of
    two), and the result is the dense softmax at ``attention_multiplier``."""
    from sparkdl_tpu.parallel.ring_attention import dense_attention
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key, (1, 2, 16, 64)).astype(dtype)
               for key in ks)
    scaled = q * (0.015625 * 8.0)
    assert scaled.dtype == dtype
    np.testing.assert_array_equal(scaled.astype(jnp.float32) * 8.0,
                                  q.astype(jnp.float32))
    got = dense_attention(scaled, k, v, causal=True).astype(jnp.float32)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * 0.015625
    seen = jnp.arange(16)[None, :] <= jnp.arange(16)[:, None]
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), vf)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
