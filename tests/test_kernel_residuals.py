"""What the decoder layers' ``nn.remat`` keeps: the forward kernels' own
outputs, by name (``ops.SAVE_KERNEL_RESIDUALS``), so a step holds one forward
``pallas_call`` a calling layer and not two, with the gradient unchanged to
the last bit; and the names are inert wherever no checkpoint carries the
policy. CPU, kernels interpreted, tiny sizes.

``jax.checkpoint`` caches a function's trace, and the trace holds the
``custom_vjp``'s rules: every variant here is built in a function object of
its own."""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu import ops
from sparkdl_tpu.models import (decoder_parts, granite_hybrid, lfm2,
                                phi4flash, qwen3_next, smallthinker)
from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
from sparkdl_tpu.ops import gated_delta as delta_module
from sparkdl_tpu.ops import selective_scan as selective_module
from sparkdl_tpu.ops import ssd_scan as ssd_module
from sparkdl_tpu.ops.flash_attention import flash_attention

FORWARD_KERNELS = ("flash_attention_fwd", "selective_scan_fwd",
                   "ssd_scan_fwd", "gated_delta_fwd")
FLASH = functools.partial(flash_attention, block_q=8, block_k=8,
                          interpret=True)


def forward_calls(fn, *args) -> dict:
    """How many ``pallas_call``s of each forward kernel ``fn``'s jaxpr holds,
    the bodies of its checkpoints and calls included."""
    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    seen = collections.Counter(
        str(e.params["name"]) for e in eqns(jax.make_jaxpr(fn)(*args).jaxpr)
        if e.primitive.name == "pallas_call")
    return {k: seen[k] for k in FORWARD_KERNELS if seen[k]}


def same_bits(a, b):
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                            jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=jax.tree_util.keystr(path))


# model, the module whose ``nn.remat`` reads the policy (Phi keeps its own
# loop over the layers), (kernel, layers that call it) at the tiny size
MODELS = {
    "lfm2": (decoder_parts, lambda: lfm2.Lfm2ForCausalLM(
        lfm2.Lfm2Config.tiny(), attn_fn=FLASH), {"flash_attention_fwd": 1}),
    "phi4flash": (phi4flash, lambda: phi4flash.Phi4FlashForCausalLM(
        phi4flash.Phi4FlashConfig(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=8, sliding_window=5, mamba_d_state=4),
        attn_fn=FLASH),
        # Mamba 0, 2, 4; window 1, 3; full 5; gated memory 6; cross 7
        {"flash_attention_fwd": 4, "selective_scan_fwd": 3}),
    "granite_hybrid": (
        decoder_parts, lambda: granite_hybrid.GraniteHybridForCausalLM(
            granite_hybrid.GraniteHybridConfig(
                vocab_size=96, hidden_size=32, shared_intermediate_size=48,
                num_attention_heads=4, num_key_value_heads=2,
                layer_types=("mamba", "attention", "mamba", "mamba"),
                mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                mamba_chunk_size=8), attn_fn=FLASH),
        {"flash_attention_fwd": 1, "ssd_scan_fwd": 3}),
    # linear 0, 2; full 1, 3. (The preparation's kernel, whose outputs are
    # not kept, runs again with the rest of the layer: it is no recurrence.)
    "qwen3_next": (decoder_parts, lambda: qwen3_next.Qwen3NextForCausalLM(
        dataclasses.replace(qwen3_next.Qwen3NextConfig.tiny(),
                            num_hidden_layers=4), attn_fn=FLASH),
        {"flash_attention_fwd": 2, "gated_delta_fwd": 2}),
    # global 0; under the window 1, 2, 3
    "smallthinker": (
        decoder_parts, lambda: smallthinker.SmallThinkerForCausalLM(
            smallthinker.SmallThinkerConfig.tiny(), attn_fn=FLASH),
        {"flash_attention_fwd": 4}),
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_layer_keeps_its_kernels_outputs_and_runs_each_forward_once(
        name, monkeypatch):
    module, build, layers = MODELS[name]
    ids = np.random.default_rng(1).integers(0, 96, (2, 24)).astype(np.int32)
    w = build().init(jax.random.PRNGKey(0), ids)

    def grad_fn():      # a model, a loss and a trace of its own each call
        model, loss_fn = build(), causal_lm_loss_fn()
        return jax.grad(lambda p: loss_fn(
            p, model.apply_with_counters, {"input_ids": ids})[0])

    assert forward_calls(grad_fn(), w) == layers
    # taken operation by operation: under one jit the CPU compiler fuses the
    # two programs differently and a sum's order moves a last bit
    kept = grad_fn()(w)
    # the same layers under nn.remat with no policy: all of a layer is
    # recomputed, its forward kernel with it
    monkeypatch.setattr(module, "SAVE_KERNEL_RESIDUALS", None)
    assert forward_calls(grad_fn(), w) == {
        k: 2 * n for k, n in layers.items()}
    same_bits(kept, grad_fn()(w))


def _flash_loss():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 24, 8))
               for i in range(3))
    return (lambda q, k, v: jnp.sum(FLASH(q, k, v, causal=True) ** 2),
            (q, k, v))


def _selective_scan_loss():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(keys[0], (1, 24, 8))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, 24, 8)))
    a = -jnp.exp(jax.random.normal(keys[2], (8, 4)))
    b, c = (jax.random.normal(k, (1, 24, 4)) for k in keys[3:5])
    d = jax.random.normal(keys[5], (8,))

    def loss(*operands):
        y, last = selective_module.selective_scan(*operands, chunk=8,
                                                  interpret=True)
        return jnp.sum(y ** 2) + jnp.max(jnp.abs(last))   # a counter's read
    return loss, (u, dt, a, b, c, d)


def _ssd_scan_loss():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(keys[0], (1, 24, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (1, 24, 4)))
    a = -jnp.exp(jax.random.normal(keys[2], (4,)))
    b, c = (jax.random.normal(k, (1, 24, 1, 8)) for k in keys[3:5])
    d = jax.random.normal(keys[5], (4,))

    def loss(*operands):
        y, last = ssd_module.ssd_scan(*operands, chunk=8, block_h=2,
                                      interpret=True)
        return jnp.sum(y ** 2) + jnp.max(jnp.abs(last))   # a counter's read
    return loss, (x, dt, a, b, c, d)


def _gated_delta_loss():
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k = (jax.random.normal(key, (1, 24, 2, 8)) for key in keys[:2])
    q, k = (t / jnp.linalg.norm(t, axis=-1, keepdims=True) for t in (q, k))
    v = jax.random.normal(keys[2], (1, 24, 4, 8))
    g = -0.05 * jax.nn.softplus(jax.random.normal(keys[3], (1, 24, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 24, 4)))

    def loss(*operands):
        o, last = delta_module.gated_delta_rule(*operands, chunk=8,
                                                block_h=2, interpret=True)
        return jnp.sum(o ** 2) + jnp.max(jnp.abs(last))   # a counter's read
    return loss, (q, k, v, g, beta)


KERNELS = {"flash_attention": _flash_loss,
           "selective_scan": _selective_scan_loss,
           "ssd_scan": _ssd_scan_loss,
           "gated_delta": _gated_delta_loss}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_the_names_are_inert_without_the_policy(name):
    make, forward = KERNELS[name], name + "_fwd"
    _, args = make()
    every = tuple(range(len(args)))

    def grad_of(wrap):      # a loss, hence a trace, of its own each call
        return jax.grad(wrap(make()[0]), argnums=every)

    plain = grad_of(lambda f: f)
    assert forward_calls(plain, *args) == {forward: 1}
    # as fit(remat=True) and parallel/pipeline.py wrap their callers
    recomputed = grad_of(jax.checkpoint)
    assert forward_calls(recomputed, *args) == {forward: 2}
    kept = grad_of(functools.partial(jax.checkpoint,
                                     policy=ops.SAVE_KERNEL_RESIDUALS))
    assert forward_calls(kept, *args) == {forward: 1}
    want = jax.jit(plain)(*args)
    same_bits(want, jax.jit(recomputed)(*args))
    same_bits(want, jax.jit(kept)(*args))
