"""LFM2-MoE (``models/lfm2.py``, ``parallel/moe.py`` ``RoutedExperts``) against
the plain float32 reference the benchmark keeps
(``benchmark/references/lfm2-8b-a1b.py``, which imports nothing of the
program), at ``Lfm2Config.tiny()`` on the CPU, with seeded weights and a
nonzero ``expert_bias``."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models.lfm2 import (ATTENTION, CONV, Lfm2Attention,
                                     Lfm2Config, Lfm2ForCausalLM,
                                     Lfm2ShortConv, trainable_mask)
from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.parallel.moe import (RoutedExperts, held_experts_ffn,
                                      sigmoid_topk_route, softmax_topk_route)
from sparkdl_tpu.runner import XlaRunner

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

REF = loader.load_module("references", "lfm2-8b-a1b")
TINY = Lfm2Config.tiny()
TOL = dict(rtol=2e-4, atol=2e-6)


def ref_cfg(c: Lfm2Config, **over) -> dict:
    """The reference's configuration dict of a program config."""
    first, held = c.experts_held or (0, c.num_experts)
    cfg = {k: getattr(c, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
        "num_dense_layers", "num_experts_per_tok", "conv_L_cache", "norm_eps",
        "rope_theta", "norm_topk_prob", "routed_scaling_factor",
        "use_expert_bias")}
    cfg.update(layer_types=list(c.layer_types),
               num_hidden_layers=len(c.layer_types), num_experts=held,
               num_routed_experts=c.num_experts, first_expert_held=first,
               expert_bias_std=0.5, learning_rate=1e-3, adam_b1=0.9,
               adam_b2=0.95, adam_eps=1e-8, weight_decay=0.1, **over)
    return cfg


def seeded(c: Lfm2Config, seed: int = 0):
    cfg = ref_cfg(c)
    return cfg, REF.init_weights(cfg, jax.random.PRNGKey(seed))


def ids_of(c: Lfm2Config, rows: int = 2, seq: int = 12, seed: int = 1):
    return np.random.default_rng(seed).integers(
        0, c.vocab_size, (rows, seq)).astype(np.int32)


def norms(tree) -> dict:
    return {jax.tree_util.keystr(p): float(jnp.linalg.norm(v)) for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_config_reads_published_keys_and_the_benchmarks_cut_file():
    cfg = loader.load_json(loader.bench_path("configs", "lfm2-8b-a1b.json"))
    c = loader.load_module("programs", "lfm2-8b-a1b").model_config(cfg)
    assert c.layer_types == (CONV, ATTENTION, CONV, CONV, CONV)
    assert (c.num_experts, c.experts_held, c.num_dense_layers) == (
        32, (0, 8), 1)
    assert (c.hidden_size, c.intermediate_size, c.moe_intermediate_size,
            c.head_dim, c.vocab_size) == (2048, 7168, 1792, 64, 16384)
    published = dict(cfg, num_hidden_layers=24, num_experts=32)
    assert Lfm2Config.from_dict(published) == dataclasses.replace(
        Lfm2Config(), num_dense_layers=1, vocab_size=16384)
    with pytest.raises(ValueError, match="24 layer types for 5 layers"):
        Lfm2Config.from_dict(cfg)


@pytest.mark.parametrize("held", [None, (8, 8)],
                         ids=["all_experts", "experts_8_to_15"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(held):
    c = dataclasses.replace(TINY, experts_held=held)
    cfg, w = seeded(c)
    ids = ids_of(c)
    model = Lfm2ForCausalLM(c)
    logits, counters = model.apply_with_counters(w, ids)
    np.testing.assert_allclose(logits, REF.logits_fn(cfg, w["params"], ids),
                               **TOL)
    assert float(counters["moe_dropped"]) == 0.0
    assert float(counters["moe_assignments"]) == 2 * ids.size * 4
    loss_fn = causal_lm_loss_fn()
    (loss, aux), g = jax.value_and_grad(
        lambda p: loss_fn(p, model.apply_with_counters, {"input_ids": ids}),
        has_aux=True)(w)
    rl, rg = jax.value_and_grad(
        lambda p: REF.loss_fn(cfg, p, {"input_ids": ids}))(w["params"])
    np.testing.assert_allclose(loss, rl, rtol=1e-5)
    got, want = norms(g["params"]), norms(rg)
    assert got.keys() == want.keys()
    for path, leaf in jax.tree_util.tree_flatten_with_path(rg)[0]:
        name = jax.tree_util.keystr(path)
        mine = g["params"]
        for k in path:
            mine = mine[k.key]
        np.testing.assert_allclose(
            mine, leaf, rtol=2e-3, atol=1e-3 * max(want[name], 1e-9),
            err_msg=name)
    assert all(want[k] == 0.0 == got[k] for k in want if "expert_bias" in k)


def test_short_convolution_against_a_loop_over_t():
    c = TINY
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 9, c.hidden_size))
    conv = Lfm2ShortConv(c)
    v = conv.init(jax.random.PRNGKey(3), u)
    p = v["params"]
    bcz = np.asarray(u @ p["in_proj"]["kernel"])
    b, cc, z = np.split(bcz, 3, axis=-1)
    g = b * z
    k = np.asarray(p["conv_kernel"])          # [taps, D]
    out = np.zeros_like(g)
    for t in range(g.shape[1]):
        for j in range(c.conv_L_cache):
            src = t - (c.conv_L_cache - 1) + j
            if src >= 0:
                out[:, t] += k[j] * g[:, src]
    want = (cc * out) @ np.asarray(p["out_proj"]["kernel"])
    np.testing.assert_allclose(conv.apply(v, u), want, rtol=1e-4, atol=1e-7)
    # causal: the output at t does not see the input after t
    u2 = u.at[:, 5:].set(0.0)
    np.testing.assert_allclose(conv.apply(v, u2)[:, :5],
                               conv.apply(v, u)[:, :5], rtol=1e-6)


def test_attention_with_qk_norms_and_rotate_half_rope():
    c = TINY
    u = jax.random.normal(jax.random.PRNGKey(4), (2, 10, c.hidden_size))
    attn = Lfm2Attention(c, attn_fn=None)
    v = attn.init(jax.random.PRNGKey(5), u)
    p = jax.tree_util.tree_map(np.asarray, v["params"])
    p["q_layernorm"]["scale"] = np.linspace(0.5, 1.5, c.head_dim,
                                            dtype=np.float32)
    h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    un = np.asarray(u)

    def rms(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + c.norm_eps) * w

    def rope(x, t):                       # one head's vector at position t
        inv = 1.0 / c.rope_theta ** (np.arange(0, hd, 2) / hd)
        ang = np.concatenate([t * inv, t * inv])
        rot = np.concatenate([-x[hd // 2:], x[:hd // 2]])
        return x * np.cos(ang) + rot * np.sin(ang)

    want = np.zeros((2, 10, h * hd), np.float32)
    for b in range(2):
        q = (un[b] @ p["q_proj"]["kernel"]).reshape(10, h, hd)
        k = (un[b] @ p["k_proj"]["kernel"]).reshape(10, hkv, hd)
        val = (un[b] @ p["v_proj"]["kernel"]).reshape(10, hkv, hd)
        q = rms(q, p["q_layernorm"]["scale"])
        k = rms(k, p["k_layernorm"]["scale"])
        for head in range(h):
            kv = head // (h // hkv)
            for t in range(10):
                qt = rope(q[t, head], t)
                sc = np.array([qt @ rope(k[s, kv], s) for s in range(t + 1)])
                pr = np.exp(sc / np.sqrt(hd) - (sc / np.sqrt(hd)).max())
                pr /= pr.sum()
                want[b, t, head * hd:(head + 1) * hd] = pr @ val[:t + 1, kv]
    want = want @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(attn.apply({"params": p}, u), want,
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(
        REF._attn_op(u, p, ref_cfg(c), "float32"), want, rtol=2e-4, atol=1e-6)


def per_token_loop(h, w_router, bias, w1, w3, w2, k, first=0):
    """The routed layer one token and one pick at a time, in numpy."""
    h = np.asarray(h, np.float64)
    out = np.zeros_like(h)
    picks = []
    for n in range(h.shape[0]):
        s = 1.0 / (1.0 + np.exp(-(h[n] @ np.asarray(w_router, np.float64))))
        top = np.argsort(-(s + np.asarray(bias)), kind="stable")[:k]
        picks.append(sorted(top.tolist()))
        total = s[top].sum() + 1e-6
        for e in top:
            j = e - first
            if 0 <= j < w1.shape[0]:
                a = h[n] @ np.asarray(w1[j], np.float64)
                silu = a / (1.0 + np.exp(-a))
                out[n] += s[e] / total * (
                    (silu * (h[n] @ np.asarray(w3[j], np.float64)))
                    @ np.asarray(w2[j], np.float64))
    return out, picks


def expert_weights(key, e=32, d=32, f=16, held=32):
    ks = jax.random.split(key, 5)
    return (0.3 * jax.random.normal(ks[0], (d, e)),
            0.5 * jax.random.normal(ks[1], (e,)),
            0.2 * jax.random.normal(ks[2], (held, d, f)),
            0.2 * jax.random.normal(ks[3], (held, d, f)),
            0.2 * jax.random.normal(ks[4], (held, f, d)))


def dense_experts(h, idx, w, w1, w3, w2, first=0):
    """The held experts' part with every held expert run on every token and
    the weights of the picks that name it: plain jnp, differentiable in
    ``h`` and ``w``."""
    out = 0.0
    for j in range(w1.shape[0]):
        y = (jax.nn.silu(h @ w1[j]) * (h @ w3[j])) @ w2[j]
        out = out + jnp.sum(jnp.where(idx == first + j, w, 0.0), -1,
                            keepdims=True) * y
    return out


def steered(wr, logits):
    """A token whose router logits are ``logits`` (least squares)."""
    return jnp.asarray(np.linalg.lstsq(
        np.asarray(wr, np.float64).T, np.asarray(logits, np.float64),
        rcond=None)[0], jnp.float32)


@pytest.mark.parametrize("first,held", [(0, 32), (8, 8)],
                         ids=["all_experts_held", "uneven_groups_of_8_held"])
def test_expert_layer_against_the_per_token_loop(first, held):
    """Output, ``dh`` and the counters. With experts 8-15 held of 32 the
    groups are uneven, token 0 picks four held experts (8-11) and token 1
    only absent ones (0, 1, 20, 31): its rows of the result and of ``dh`` are
    zero."""
    wr, bias, w1, w3, w2 = expert_weights(jax.random.PRNGKey(6), held=held)
    bias = 0.1 * bias
    h = jax.random.normal(jax.random.PRNGKey(7), (24, 32))
    if held < 32:
        h = h.at[0].set(steered(wr, np.where(
            np.isin(np.arange(32), [8, 9, 10, 11]), 6.0, -6.0)))
        h = h.at[1].set(steered(wr, np.where(
            np.isin(np.arange(32), [0, 1, 20, 31]), 6.0, -6.0)))
    cot = jax.random.normal(jax.random.PRNGKey(18), h.shape)

    def layer(x):
        idx, w = sigmoid_topk_route(x, wr, bias, 4)
        out, counters = held_experts_ffn(x, idx, w, w1, w3, w2, first)
        return (out * cot).sum(), (out, counters, idx)

    (_, (out, counters, idx)), dh = jax.value_and_grad(
        layer, has_aux=True)(h)
    want, picks = per_token_loop(h, wr, bias, w1, w3, w2, 4, first)
    assert [sorted(r) for r in np.asarray(idx).tolist()] == picks
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=1e-6)
    want_dh = jax.grad(lambda x: (dense_experts(
        x, idx, sigmoid_topk_route(x, wr, bias, 4)[1], w1, w3, w2, first)
        * cot).sum())(h)
    np.testing.assert_allclose(dh, want_dh, rtol=2e-4, atol=2e-6)
    loads = np.bincount(np.concatenate(picks), minlength=32)[
        first:first + held]
    assert float(counters["moe_assignments"]) == 24 * 4
    assert float(counters["moe_assignments_held"]) == loads.sum()
    assert float(counters["moe_held_load_max"]) == loads.max()
    np.testing.assert_allclose(counters["moe_held_load_mean"], loads.mean(),
                               rtol=1e-6)
    assert float(counters["moe_dropped"]) == 0.0
    if held < 32:
        assert picks[0] == [8, 9, 10, 11] and picks[1] == [0, 1, 20, 31]
        assert len(set(loads.tolist())) > 2          # uneven groups
        assert not np.asarray(out[1]).any() and np.asarray(out[0]).any()
        assert not np.asarray(dh[1]).any() and np.asarray(dh[0]).any()


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    wr, bias, w1, w3, w2 = expert_weights(jax.random.PRNGKey(8), held=8)
    bias = jnp.zeros_like(bias).at[jnp.array([3, 5, 6, 7])].set(50.0)
    h = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    idx, w = sigmoid_topk_route(h, wr, bias, 4)
    assert set(np.asarray(idx).ravel().tolist()) == {3, 5, 6, 7}
    out, counters = held_experts_ffn(h, idx, w, w1, w3, w2)
    want, _ = per_token_loop(h, wr, bias, w1, w3, w2, 4)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=1e-6)
    assert float(counters["moe_dropped"]) == 0.0
    assert float(counters["moe_assignments_held"]) == 160.0
    assert float(counters["moe_held_load_max"]) == 40.0
    assert float(counters["moe_held_load_mean"]) == 20.0


def dense_reglu(h, idx, w, w1, w3, w2, first=0):
    """``dense_experts`` with a ReLU for the gate (ReGLU)."""
    out = 0.0
    for j in range(w1.shape[0]):
        y = (jax.nn.relu(h @ w1[j]) * (h @ w3[j])) @ w2[j]
        out = out + jnp.sum(jnp.where(idx == first + j, w, 0.0), -1,
                            keepdims=True) * y
    return out


@pytest.mark.parametrize("first,held", [(0, 32), (8, 8)],
                         ids=["all_experts_held", "uneven_groups_of_8_held"])
def test_relu_experts_and_their_gradient_against_the_dense_form(first, held):
    """``activation="relu"``: output, ``dh``, the three weights' gradients
    and the router's against every held expert run on every token, and the
    two ReGLU counters against a count of the live slots' gate units. Both
    sides in float32; they differ by the order of their sums (~1e-6 of the
    largest entry), far under bfloat16's 2^-8."""
    wr, _, w1, w3, w2 = expert_weights(jax.random.PRNGKey(19), held=held)
    h = jax.random.normal(jax.random.PRNGKey(20), (24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(21), h.shape)

    def layer(x, router, experts, ffn):
        idx, w = softmax_topk_route(x, router, 4)
        out = ffn(x, idx, w, *experts, first)
        return (out[0] if isinstance(out, tuple) else out) * cot

    grad = jax.grad(lambda *a: layer(*a).sum(), argnums=(0, 1, 2))
    relu = functools.partial(held_experts_ffn, activation="relu")
    got = layer(h, wr, (w1, w3, w2), relu)
    np.testing.assert_allclose(got, layer(h, wr, (w1, w3, w2), dense_reglu),
                               rtol=2e-4, atol=1e-6)
    silu = layer(h, wr, (w1, w3, w2), held_experts_ffn)
    assert float(jnp.abs(got - silu).max()) > 1e-2     # another gate
    for name, mine, theirs in zip(
            ("h", "router", "w1", "w3", "w2"),
            jax.tree_util.tree_leaves(grad(h, wr, (w1, w3, w2), relu)),
            jax.tree_util.tree_leaves(grad(h, wr, (w1, w3, w2),
                                           dense_reglu))):
        assert np.asarray(theirs).any(), name
        np.testing.assert_allclose(mine, theirs, rtol=2e-4, atol=2e-6,
                                   err_msg=name)
    idx, w = softmax_topk_route(h, wr, 4)
    _, counters = relu(h, idx, w, w1, w3, w2, first)
    units = active = 0
    for t, e in zip(*np.nonzero((np.asarray(idx) >= first)
                                & (np.asarray(idx) < first + held))):
        gate = np.asarray(h[t] @ w1[int(idx[t, e]) - first])
        units, active = units + gate.size, active + int((gate > 0).sum())
    assert float(counters["moe_reglu_units"]) == units > 0
    assert float(counters["moe_reglu_active"]) == active
    assert 0.3 * units < active < 0.7 * units
    _, plain = held_experts_ffn(h, idx, w, w1, w3, w2, first)
    assert set(counters) - set(plain) == {"moe_reglu_units",
                                          "moe_reglu_active"}
    with pytest.raises(ValueError, match="silu or relu"):
        held_experts_ffn(h, idx, w, w1, w3, w2, activation="tanh")


def test_route_from_and_activation_leave_the_layer_as_it_was_by_default():
    """``activation="silu"`` is the default and ``route_from=None`` routes on
    ``x``: the same tree and the same output bit for bit as the layer built
    and called without them. Given another tensor, the picks follow it and
    the experts still compute on ``x``."""
    x = jax.random.normal(jax.random.PRNGKey(22), (2, 12, 32))
    r = jax.random.normal(jax.random.PRNGKey(23), (2, 12, 32))
    kw = dict(num_experts=8, top_k=3, d_ff=16, held=(2, 4),
              scoring="softmax")
    plain, named = RoutedExperts(**kw), RoutedExperts(**kw, activation="silu")
    v = plain.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(
        named.init(jax.random.PRNGKey(0), x))
    out = plain.apply(v, x, mutable=["counters"])[0]
    np.testing.assert_array_equal(
        out, named.apply(v, x, route_from=x, mutable=["counters"])[0])
    elsewhere = plain.apply(v, x, route_from=r, mutable=["counters"])[0]
    e = v["params"]["experts"]
    idx, w = softmax_topk_route(r.reshape(24, 32),
                                v["params"]["router"]["kernel"], 3)
    want, _ = held_experts_ffn(x.reshape(24, 32), idx, w, e["w1"], e["w3"],
                               e["w2"], 2)
    np.testing.assert_array_equal(elsewhere.reshape(24, 32), want)
    assert float(jnp.abs(elsewhere - out).max()) > \
        0.1 * float(jnp.abs(out).max())


@pytest.mark.parametrize("left", [1e4, float("nan"), float("inf")],
                         ids=["1e4", "nan", "inf"])
def test_rows_past_the_held_groups_are_never_read(monkeypatch, left):
    """On the chip a grouped product neither reads nor writes the rows past
    its groups, in its result and in its gradient alike (PR 29: the first
    gradient read 16-20% high). Here such a product is planted, which leaves
    ``left`` in those rows: the layer's output and its gradients with respect
    to its input, the experts' three weights and the router's must not
    change. ``nan`` and ``inf`` pass only where every mask is a select."""
    wr, bias, w1, w3, w2 = expert_weights(jax.random.PRNGKey(15), held=8)
    h = jax.random.normal(jax.random.PRNGKey(16), (24, 32))
    cot = jax.random.normal(jax.random.PRNGKey(17), h.shape)
    real = jax.lax.ragged_dot

    def soil(x, group_sizes):
        dead = jnp.arange(x.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(dead[:, None], left, x)

    @jax.custom_vjp
    def dirty(lhs, rhs, group_sizes):
        return soil(real(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        return dirty(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)

    def bwd(saved, g):
        lhs, rhs, group_sizes = saved
        dl, dr = jax.vjp(lambda a, b: real(a, b, group_sizes), lhs, rhs)[1](g)
        return soil(dl, group_sizes), dr, None

    dirty.defvjp(fwd, bwd)

    def layer(x, router, experts):
        idx, w = sigmoid_topk_route(x, router, bias, 4)
        return (held_experts_ffn(x, idx, w, *experts)[0] * cot).sum()

    grad = jax.value_and_grad(layer, argnums=(0, 1, 2))
    want, want_g = grad(h, wr, (w1, w3, w2))
    monkeypatch.setattr(
        jax.lax, "ragged_dot",
        lambda lhs, rhs, group_sizes, **kw: dirty(lhs, rhs, group_sizes))
    got, got_g = grad(h, wr, (w1, w3, w2))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for name, mine, theirs in zip(
            ("h", "router", "w1", "w3", "w2"),
            jax.tree_util.tree_leaves(got_g),
            jax.tree_util.tree_leaves(want_g)):
        assert np.asarray(theirs).any(), name
        np.testing.assert_allclose(mine, theirs, rtol=1e-6, atol=1e-7,
                                   err_msg=name)


def test_expert_bias_moves_the_selection_and_not_the_weights():
    wr, bias, *_ = expert_weights(jax.random.PRNGKey(10))
    h = jax.random.normal(jax.random.PRNGKey(11), (64, 32))
    idx0, w0 = sigmoid_topk_route(h, wr, jnp.zeros_like(bias), 4)
    idx1, w1 = sigmoid_topk_route(h, wr, bias, 4)
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()
    s = jax.nn.sigmoid(h @ wr)
    picked = jnp.take_along_axis(s, idx1, axis=-1)
    np.testing.assert_allclose(
        w1, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    g = jax.grad(lambda b: sigmoid_topk_route(h, wr, b, 4)[1].sum())(bias)
    assert not np.asarray(g).any()


@pytest.mark.parametrize("norm", [True, False], ids=["normalised", "raw"])
def test_softmax_scores_against_a_plain_top_k(norm):
    """Softmax over every expert in float32, the largest k of them a token by
    a plain sort, their scores over their own sum where asked; no bias."""
    wr = 0.5 * jax.random.normal(jax.random.PRNGKey(12), (32, 24))
    h = jax.random.normal(jax.random.PRNGKey(13), (64, 32))
    idx, w = softmax_topk_route(h, wr, 5, norm_topk_prob=norm)
    p = np.asarray(jax.nn.softmax(h @ wr, axis=-1))
    order = np.argsort(-p, axis=-1)[:, :5]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(order, -1))
    picked = np.take_along_axis(p, np.asarray(idx), axis=-1)
    want = picked / picked.sum(-1, keepdims=True) if norm else picked
    np.testing.assert_allclose(w, want, rtol=1e-5)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    assert (np.diff(np.asarray(w), axis=-1) <= 0).all()   # largest first
    _, scaled = softmax_topk_route(h, wr, 5, norm_topk_prob=norm, scaling=2.5)
    np.testing.assert_allclose(scaled, 2.5 * w, rtol=1e-6)


def test_the_scoring_field_leaves_sigmoid_layers_as_they_were():
    """``scoring="sigmoid"`` is the default: the same parameter tree, the
    same output bit for bit, as the layer built without the field; softmax
    has no ``expert_bias`` and other outputs; anything else is refused."""
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 12, 32))
    kw = dict(num_experts=8, top_k=3, d_ff=16, held=(2, 4))
    plain, named = RoutedExperts(**kw), RoutedExperts(**kw, scoring="sigmoid")
    v = plain.init(jax.random.PRNGKey(0), x)
    v2 = named.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(v2)
    for a, b in zip(jax.tree_util.tree_leaves(v), jax.tree_util.tree_leaves(v2)):
        np.testing.assert_array_equal(a, b)
    assert set(v["params"]) == {"router", "expert_bias", "experts"}
    out = plain.apply(v, x, mutable=["counters"])[0]
    np.testing.assert_array_equal(out, named.apply(v, x,
                                                   mutable=["counters"])[0])
    soft = RoutedExperts(**kw, scoring="softmax")
    vs = soft.init(jax.random.PRNGKey(0), x)
    assert set(vs["params"]) == {"router", "experts"}
    got = soft.apply(vs, x, mutable=["counters"])[0]
    idx, w = softmax_topk_route(x.reshape(24, 32),
                                vs["params"]["router"]["kernel"], 3)
    e = vs["params"]["experts"]
    want, _ = held_experts_ffn(x.reshape(24, 32), idx, w, e["w1"], e["w3"],
                               e["w2"], 2)
    np.testing.assert_allclose(got.reshape(24, 32), want, rtol=1e-6)
    assert float(jnp.abs(got - out).max()) > 1e-6
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        RoutedExperts(**kw, scoring="tanh").init(jax.random.PRNGKey(0), x)


def fit_three_steps(c, w, batches, lr=1e-3):
    model = Lfm2ForCausalLM(c)
    host = jax.tree_util.tree_map(np.asarray, w)
    return XlaRunner(np=1).run(lambda ctx: ctx.fit(
        loss_fn=causal_lm_loss_fn(), apply_fn=model.apply_with_counters,
        params=host,
        tx=optax.adamw(lr, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                       mask=trainable_mask),
        data=iter(batches), num_steps=3, log_every=1, resume=False))


def test_three_fit_steps_match_the_references_and_spare_expert_bias():
    cfg, w = seeded(TINY, seed=3)
    batches = [{"input_ids": ids_of(TINY, rows=2, seq=12, seed=20 + i)}
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
    got = jax.device_get(res["state"].params["params"])
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        mine = got
        for k in path:
            mine = mine[k.key]
        if "expert_bias" in jax.tree_util.keystr(path):
            assert np.array_equal(mine, w["params"][path[0].key][
                "feed_forward"]["expert_bias"])       # bit-equal
            assert np.asarray(mine).any()
        else:
            np.testing.assert_allclose(mine, leaf, rtol=1e-3, atol=2e-5,
                                       err_msg=jax.tree_util.keystr(path))
    assert all(h["moe_dropped"] == 0.0 for h in res["history"])


def test_the_four_shares_add_up_to_the_uncut_reference():
    """Experts 0-7, 8-15, 16-23, 24-31 of 32: the shares' outputs of one
    expert layer sum to the uncut reference's output for the whole layer, and
    so do the gradients with respect to the layer's input."""
    c = TINY
    cfg = ref_cfg(c)
    wr, bias, w1, w3, w2 = expert_weights(jax.random.PRNGKey(12))
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 10, c.hidden_size))
    cot = jax.random.normal(jax.random.PRNGKey(14), x.shape)
    p_all = {"router": {"kernel": wr}, "expert_bias": bias,
             "experts": {"w1": w1, "w3": w3, "w2": w2}}
    want, want_g = jax.value_and_grad(
        lambda x_: (REF._routed_ffn(x_, p_all, cfg, "float32", "") * cot
                    ).sum())(x)
    whole = REF._routed_ffn(x, p_all, cfg, "float32", "")
    total, total_g = 0.0, 0.0
    for first in (0, 8, 16, 24):
        layer = RoutedExperts(32, 4, c.moe_intermediate_size, held=(first, 8))
        sl = slice(first, first + 8)
        p = {"params": {"router": {"kernel": wr}, "expert_bias": bias,
                        "experts": {"w1": w1[sl], "w3": w3[sl],
                                    "w2": w2[sl]}}}
        out, g = jax.value_and_grad(lambda x_: (
            layer.apply(p, x_, mutable=["counters"])[0] * cot).sum())(x)
        total, total_g = total + out, total_g + g
        share = layer.apply(p, x, mutable=["counters"])[0]
        rcfg = dict(cfg, num_experts=8, first_expert_held=first)
        np.testing.assert_allclose(
            share, REF._routed_ffn(x, p["params"], rcfg, "float32", ""),
            rtol=2e-4, atol=1e-6)
        whole = whole - share
    np.testing.assert_allclose(total, want, rtol=1e-4)
    np.testing.assert_allclose(total_g, want_g, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(whole, jnp.zeros_like(whole), atol=2e-6)


@pytest.mark.parametrize("fault", REF.FAULTS)
def test_the_references_planted_faults_move_a_share_and_only_a_share(fault):
    c = dataclasses.replace(TINY, experts_held=(0, 8))
    cfg, w = seeded(c)
    b = {"input_ids": ids_of(c)}
    sound = float(REF.loss_fn(cfg, w["params"], b))
    assert abs(float(REF.loss_fn(cfg, w["params"], b, "float32+" + fault))
               - sound) > 1e-7
    with pytest.raises(ValueError):
        REF.loss_fn(cfg, w["params"], b, "float32+no_such_fault")
