"""SmallThinker-21BA3B-Instruct (``models/smallthinker.py`` over the flash
kernels and ``parallel/moe.py``) against the plain float32 reference the
benchmark keeps (``benchmark/references/smallthinker-21ba3b-instruct.py``,
which imports nothing of the program), at a tiny size (``TINY``) on the CPU,
with seeded weights; the flash kernels interpreted.

``TINY`` keeps what the cut keeps: a group of 7 query heads over one
key/value head, both kinds of layer by their published indices (a global NoPE
layer and three RoPE layers under a window of 8, in sequences of 24), and
fewer experts held (4, from the third) than the router scores (8, top 3).

Tolerances: program and reference both compute in float32 here, the
reference's products at ``highest``; they differ by the order of their sums
alone, ~1e-7 of the largest entry (PERF.md). Each tolerance below is written
with that reason and lies far under bfloat16's rounding (2^-8 = 0.0039 of a
value), so a product left in bfloat16 would fail it."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models import SmallThinkerConfig, SmallThinkerForCausalLM
from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.models.smallthinker import (SmallThinkerAttention,
                                             SmallThinkerDecoderLayer,
                                             decay_mask)
from sparkdl_tpu.ops.flash_attention import flash_attention
from sparkdl_tpu.parallel import moe
from sparkdl_tpu.runner import XlaRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

NAME = "smallthinker-21ba3b-instruct"
REF = loader.load_module("references", NAME)
TINY = dataclasses.replace(SmallThinkerConfig.tiny(), experts_held=(2, 4))
COUNTERS = {"moe_assignments", "moe_assignments_held", "moe_held_load_max",
            "moe_held_load_mean", "moe_dropped", "moe_reglu_active",
            "moe_reglu_units"}
# float32 against float32 at highest: the order of the sums alone
TOL = dict(rtol=2e-4, atol=2e-6)
FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)


def ref_cfg(c: SmallThinkerConfig, **over) -> dict:
    """The reference's configuration dict of a program config."""
    cfg = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)
           if f.name not in ("layers_kept", "experts_held")}
    first, held = c.experts_held or (0, c.moe_num_primary_experts)
    cfg.update(layers_kept=list(c.layers), num_hidden_layers=len(c.layers),
               num_routed_experts=c.moe_num_primary_experts,
               moe_num_primary_experts=held, first_expert_held=first,
               learning_rate=1e-3, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
               weight_decay=0.1, **over)
    return cfg


def seeded(c: SmallThinkerConfig, seed: int = 0):
    cfg = ref_cfg(c)
    return cfg, REF.init_weights(cfg, jax.random.PRNGKey(seed))


def ids_of(c, rows: int = 2, seq: int = 24, seed: int = 1):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (rows, seq)).astype(np.int32)


def leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def count(c: SmallThinkerConfig) -> int:
    shapes = jax.eval_shape(
        lambda k: SmallThinkerForCausalLM(c).init(
            k, jnp.zeros((1, 8), jnp.int32)), jax.random.PRNGKey(0))
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes["params"]))


# -- the configuration --------------------------------------------------------

def cut_file() -> dict:
    return loader.load_json(loader.bench_path("configs", NAME + ".json"))


def published() -> dict:
    cfg = cut_file()
    return dict(cfg, **cfg["published"])


def test_the_catalogs_keys_give_the_published_model():
    c = SmallThinkerConfig.from_dict(published())
    assert c == SmallThinkerConfig()
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.moe_ffn_hidden_size, c.moe_num_primary_experts,
            c.moe_num_active_primary_experts, c.sliding_window_size,
            c.vocab_size) == (2560, 28, 4, 128, 768, 64, 6, 4096, 151936)
    assert (c.rms_norm_eps, c.rope_theta) == (1e-6, 1.5e6)


def test_the_layer_kinds_go_by_the_published_index():
    """Layer ``l % 4 == 0`` is global and NoPE, the other three turn q and k
    by RoPE under the window: 13 and 39 of the 52, in both layouts alike."""
    c = SmallThinkerConfig.from_dict(published())
    glob = [l for l in c.layers if c.window(l) is None]
    assert glob == list(range(0, 52, 4))
    assert [l for l in c.layers if not c.rope(l)] == glob
    assert all(c.window(l) == 4096 for l in c.layers if l % 4)
    cut = loader.load_module("programs", NAME).model_config(cut_file())
    assert cut.layers == (0, 1, 2, 3)
    assert [(cut.rope(l), cut.window(l)) for l in cut.layers] == [
        (False, None)] + [(True, 4096)] * 3
    assert REF.kinds(ref_cfg(cut)) == [(False, None)] + [(True, 4096)] * 3


def test_the_parameter_counts_from_shapes_without_allocating():
    assert count(SmallThinkerConfig.from_dict(published())) == 21_506_562_560
    cut = loader.load_module("programs", NAME).model_config(cut_file())
    assert cut.vocab_size == 18992 and cut.experts_held == (0, 16)
    assert cut.moe_num_primary_experts == 64
    assert count(cut) == 559_290_880


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("rope_scaling", {"a": 1}),
    ("tie_word_embeddings", True), ("rope_layout", [0, 1])])
def test_what_the_model_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        SmallThinkerConfig.from_dict(dict(published(), **{key: value}))


# -- against the reference ----------------------------------------------------

@pytest.mark.parametrize("attn_fn", ["auto", FLASH], ids=["dense", "flash"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(attn_fn):
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    model = SmallThinkerForCausalLM(TINY, attn_fn=attn_fn)
    logits, counters = model.apply_with_counters(w, ids)
    np.testing.assert_allclose(logits, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
    assert set(counters) == COUNTERS
    loss_fn = causal_lm_loss_fn()
    (loss, aux), g = jax.value_and_grad(
        lambda p: loss_fn(p, model.apply_with_counters, {"input_ids": ids}),
        has_aux=True)(w)
    assert COUNTERS <= set(aux)
    rl, rg = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, {"input_ids": ids}))(w["params"])
    # a scalar of ~4.6 summed over 46 rows in float32
    np.testing.assert_allclose(loss, rl, rtol=1e-5)
    got, want = leaves(g["params"]), leaves(rg)
    assert got.keys() == want.keys()
    for name, leaf in want.items():
        scale = float(jnp.linalg.norm(leaf))
        assert scale > 0, name          # no leaf of this model is dead
        # a gradient leaf sums products over every row: 1e-3 of its norm is
        # 10^4 times the gap read (4e-7), a quarter of bfloat16's rounding
        np.testing.assert_allclose(got[name], leaf, rtol=2e-3,
                                   atol=1e-3 * scale, err_msg=name)


def test_the_counters_are_what_they_say():
    _, w = seeded(TINY)
    ids = ids_of(TINY)
    _, c = SmallThinkerForCausalLM(TINY).apply_with_counters(w, ids)
    picks = ids.size * TINY.moe_num_active_primary_experts * len(TINY.layers)
    assert float(c["moe_assignments"]) == picks
    held = float(c["moe_assignments_held"])
    assert 0 < held < picks and float(c["moe_dropped"]) == 0
    assert float(c["moe_held_load_max"]) >= float(c["moe_held_load_mean"]) > 0
    # every live slot has its 16 gate units; about half of them pass the ReLU
    assert float(c["moe_reglu_units"]) == held * TINY.moe_ffn_hidden_size
    assert 0.3 < float(c["moe_reglu_active"]) / float(c["moe_reglu_units"]) \
        < 0.7


@pytest.mark.parametrize("attn_fn", ["auto", FLASH], ids=["dense", "flash"])
def test_a_window_layer_never_reads_keys_older_than_its_window(attn_fn):
    """Positions 0-7 changed: under the window of 8 rows 15-23 see none of
    them and stay as they were, bit for bit; rows 8-14 and, in the global
    layer, every row move."""
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 24, TINY.hidden_size))
    moved = u.at[:, :8].add(jax.random.normal(jax.random.PRNGKey(4),
                                              (2, 8, TINY.hidden_size)))
    for l, untouched in ((1, slice(15, 24)), (0, slice(24, 24))):
        attn = SmallThinkerAttention(TINY, l, attn_fn=attn_fn)
        v = attn.init(jax.random.PRNGKey(5), u)
        a, b = attn.apply(v, u), attn.apply(v, moved)
        np.testing.assert_array_equal(a[:, untouched], b[:, untouched])
        changed = np.abs(np.asarray(a - b)).max(axis=(0, 2))
        assert (changed[8:untouched.start] > 1e-3).all(), (l, changed)


def test_the_routers_picks_do_not_move_when_the_attention_weights_do(
        monkeypatch):
    """The router reads the layer's input, not what the attention made of
    it: with every attention weight redrawn the picks and their weights are
    the same, while the experts' output moves. The reference with the router
    read after the attention (its planted fault) picks otherwise."""
    seen = []
    real = moe.softmax_topk_route

    def spy(h, *a, **kw):
        out = real(h, *a, **kw)
        seen.append(out)
        return out

    monkeypatch.setattr(moe, "softmax_topk_route", spy)
    cfg, w = seeded(TINY)
    layer = SmallThinkerDecoderLayer(TINY, 1)
    p = w["params"]["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, TINY.hidden_size))
    out, _ = layer.apply({"params": p}, x, mutable=["counters"])
    redrawn = dict(p, self_attn=jax.tree_util.tree_map(
        lambda t: 3.0 * jax.random.normal(jax.random.PRNGKey(9), t.shape),
        p["self_attn"]))
    out2, _ = layer.apply({"params": redrawn}, x, mutable=["counters"])
    (idx, wt), (idx2, wt2) = seen
    np.testing.assert_array_equal(idx, idx2)
    np.testing.assert_array_equal(wt, wt2)
    assert float(jnp.abs(out - out2).max()) > 1e-2
    # the reference's router, read where the program reads it and after
    n1 = REF._rms(x, p["input_layernorm"], TINY.rms_norm_eps)
    router = p["block_sparse_moe"]["router"]["kernel"]
    ref_idx, _ = REF.route(n1.reshape(48, -1), router, cfg)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ref_idx, -1))
    h = x + REF.attention(n1, p["self_attn"], cfg, *REF.kinds(cfg)[1])
    n2 = REF._rms(h, p["post_attention_layernorm"], TINY.rms_norm_eps)
    late, _ = REF.route(n2.reshape(48, -1), router, cfg)
    assert (np.sort(late, -1) != np.sort(idx, -1)).any()


def fit_three_steps(c, w, batches, lr=1e-3):
    model = SmallThinkerForCausalLM(c)
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
        # Adam's step is 1e-3 a leaf entry: 2e-5 of it is 1/50 of a step
        np.testing.assert_allclose(got[name], leaf, rtol=1e-3, atol=2e-5,
                                   err_msg=name)
    for h in res["history"]:
        assert COUNTERS <= set(h)
        assert all(np.isfinite(h[k]) for k in COUNTERS)


def test_the_cells_embedding_is_seeded_at_its_own_scale_and_the_rest_alike():
    """``embedding_std``, as the cell's file sets it (1.0): the embedding is
    the same draw at that scale, every other leaf the draw it is without the
    key (the matrices at 0.02, the norms at 1 + 0.02 noise)."""
    cfg = ref_cfg(TINY)
    std = cut_file()["embedding_std"]
    assert std == 1.0
    key = jax.random.PRNGKey(7)
    plain = leaves(REF.init_weights(cfg, key))
    cell = leaves(REF.init_weights(dict(cfg, embedding_std=std), key))
    assert plain.keys() == cell.keys()
    for name, leaf in plain.items():
        if name.endswith("['embedding']"):
            np.testing.assert_allclose(cell[name], leaf * std / 0.02,
                                       rtol=1e-6, err_msg=name)
            assert abs(float(np.std(cell[name])) - std) < 0.05 * std
        else:
            np.testing.assert_array_equal(cell[name], leaf, err_msg=name)


def test_weight_decay_is_on_the_matrices_the_stacks_the_embedding_and_head():
    _, w = seeded(TINY)
    mask = leaves(decay_mask(w["params"]))
    decayed = ("['kernel']", "['embedding']", "['w1']", "['w3']", "['w2']")
    for name, decays in mask.items():
        assert decays == name.endswith(decayed), name
    assert not mask["['layer_0']['input_layernorm']['scale']"]
    assert mask["['layer_0']['block_sparse_moe']['router']['kernel']"]
    assert mask["['layer_0']['block_sparse_moe']['experts']['w2']"]


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_each_planted_fault_is_seen(fault):
    """A sound program differs from the reference with the fault planted,
    far beyond rounding, in some leaf of the gradient."""
    cfg, w = seeded(TINY)
    ids = ids_of(TINY)
    b = {"input_ids": ids}
    loss_fn = causal_lm_loss_fn()
    g = jax.grad(lambda p: loss_fn(
        p, SmallThinkerForCausalLM(TINY).apply_with_counters, b)[0])(w)
    fg = jax.grad(lambda p: REF.loss_fn(cfg, p, b, "float32+" + fault))(
        w["params"])
    gap = max(float(jnp.linalg.norm(a - b_) / (jnp.linalg.norm(b_) + 1e-30))
              for a, b_ in zip(jax.tree_util.tree_leaves(g["params"]),
                               jax.tree_util.tree_leaves(fg)))
    assert gap > 1e-2, gap
    with pytest.raises(ValueError):
        REF.loss_fn(cfg, w["params"], b, "float32+no_such_fault")


# -- the cut ------------------------------------------------------------------

def test_four_shares_of_the_experts_add_up_to_the_uncut_reference_layer():
    """Four chips share a layer, four experts each of 16 here: the parts the
    four shares of the PROGRAM's layer give, each routing on ``n1`` and
    computing on ``n2``, add up to what the uncut REFERENCE gives."""
    shares, each = 4, 4
    whole = dataclasses.replace(TINY, moe_num_primary_experts=shares * each,
                                moe_num_active_primary_experts=6,
                                experts_held=None)
    cfg, w = seeded(whole)
    p = w["params"]["layer_0"]["block_sparse_moe"]
    n1, n2 = (jax.random.normal(jax.random.PRNGKey(s), (2, 12,
                                                         whole.hidden_size))
              for s in (5, 6))
    d = whole.hidden_size
    want = REF.routed_part(n1.reshape(-1, d), n2.reshape(-1, d), p, cfg)
    total, held_picks = 0.0, 0.0
    for share in range(shares):
        lo = share * each
        layer = moe.RoutedExperts(
            whole.moe_num_primary_experts, 6, whole.moe_ffn_hidden_size,
            held=(lo, each), scoring="softmax", activation="relu")
        mine = dict(p, experts={n: x[lo:lo + each]
                                for n, x in p["experts"].items()})
        out, mut = layer.apply({"params": mine}, n2, route_from=n1,
                               mutable=["counters"])
        total = total + out.reshape(-1, d)
        held_picks += float(mut["counters"]["moe_assignments_held"])
    # float32 sums in another order: ~1e-7 of the largest entry
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)
    assert held_picks == 24 * 6
    assert float(jnp.linalg.norm(want)) > 0


def test_the_vocabulary_slices_logits_are_the_unsliced_models_columns():
    _, w = seeded(TINY)
    cut = dataclasses.replace(TINY, vocab_size=48)
    ids = ids_of(cut)
    whole = SmallThinkerForCausalLM(TINY).apply(w, ids,
                                                mutable=["counters"])[0]
    p = dict(w["params"],
             embed_tokens={"embedding": w["params"]["embed_tokens"][
                 "embedding"][:48]},
             lm_head={"kernel": w["params"]["lm_head"]["kernel"][:, :48]})
    sliced = SmallThinkerForCausalLM(cut).apply({"params": p}, ids,
                                                mutable=["counters"])[0]
    np.testing.assert_allclose(sliced, whole[..., :48], rtol=1e-5, atol=1e-6)


def test_layers_kept_goes_by_the_published_index():
    """Published layers 3 to 5: two window layers around the global layer 4,
    which leads no more; the reference's cut agrees."""
    cut = dataclasses.replace(TINY, num_hidden_layers=8,
                              rope_layout=(0, 1, 1, 1) * 2,
                              sliding_window_layout=(0, 1, 1, 1) * 2,
                              layers_kept=(3, 4, 5))
    cfg, w = seeded(cut)
    assert REF.kinds(cfg) == [(True, 8), (False, None), (True, 8)]
    ids = ids_of(cut)
    got = SmallThinkerForCausalLM(cut).apply(w, ids, mutable=["counters"])[0]
    np.testing.assert_allclose(got, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
