"""The decoders' shared parts (``models/decoder_parts.py``): the causal taps
against a loop over positions, the grouped-query core against dense attention
over heads repeated by hand, rotate-half RoPE, the parameter tree each decoder
builds through them, and the rule that no model file takes a part from
another model's file."""

import ast
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import (granite_hybrid, lfm2, phi4flash, qwen3_next,
                                smallthinker)
from sparkdl_tpu.models.decoder_parts import (causal_taps,
                                              grouped_query_attention,
                                              rope_rotate_half)
from sparkdl_tpu.parallel.ring_attention import dense_attention

MODELS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "sparkdl_tpu", "models")
DECODERS = ("lfm2", "phi4flash", "granite_hybrid", "qwen3_next",
            "smallthinker")


def test_rope_turns_the_leading_quarter_of_a_head_and_no_more():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 6, 16))
    part = rope_rotate_half(x, 1e4, 4)
    np.testing.assert_array_equal(part[..., 4:], x[..., 4:])
    np.testing.assert_allclose(part[..., :4],
                               rope_rotate_half(x[..., :4], 1e4), rtol=1e-6)
    assert float(jnp.abs(part[:, :, 1:, :4] - x[:, :, 1:, :4]).max()) > 1e-2
    np.testing.assert_array_equal(rope_rotate_half(x, 1e4, 16),
                                  rope_rotate_half(x, 1e4))


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_causal_taps_are_a_sum_over_each_position_and_those_before(taps):
    rng = np.random.default_rng(taps)
    x = rng.standard_normal((2, 9, 5)).astype(np.float32)
    kernel = rng.standard_normal((taps, 5)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(x.shape[1]):
        for j in range(taps):
            src = t - taps + 1 + j
            if src >= 0:
                want[:, t] += kernel[j] * x[:, src]
    np.testing.assert_allclose(causal_taps(jnp.asarray(x), kernel), want,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("window", [None, 8])
def test_a_key_value_head_serves_its_group_of_query_heads_in_a_row(
        kv_heads, window):
    group, s, d = 7, 20, 8
    keys = jax.random.split(jax.random.PRNGKey(kv_heads), 3)
    q = jax.random.normal(keys[0], (2, group * kv_heads, s, d))
    k, v = (jax.random.normal(kk, (2, kv_heads, s, d)) for kk in keys[1:])
    # query head h reads key/value head h // group
    by_hand = [jnp.stack([t[:, h // group] for h in range(group * kv_heads)],
                         axis=1) for t in (k, v)]
    np.testing.assert_allclose(
        grouped_query_attention(q, k, v, "auto", window),
        dense_attention(q, *by_hand, causal=True, window=window),
        rtol=1e-6, atol=1e-6)


# every decoder at a size the CPU tests use, and the sorted (path, shape,
# dtype) list of its ``init`` tree, pinned: a part that moves between files
# renames, reshapes or recasts no parameter. (leaves, the first 16 hex digits
# of the sha256 of the list's repr)
TREES = {
    "lfm2": (lambda: lfm2.Lfm2ForCausalLM(lfm2.Lfm2Config.tiny()),
             43, "27f5e7501515d8c4"),
    "phi4flash": (lambda: phi4flash.Phi4FlashForCausalLM(
        phi4flash.Phi4FlashConfig(
            vocab_size=96, hidden_size=32, intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2,
            num_hidden_layers=8, sliding_window=5, mamba_d_state=4)),
        118, "93896d81805bd655"),
    "granite_hybrid": (lambda: granite_hybrid.GraniteHybridForCausalLM(
        granite_hybrid.GraniteHybridConfig(
            vocab_size=96, hidden_size=32, shared_intermediate_size=48,
            num_attention_heads=4, num_key_value_heads=2,
            layer_types=("mamba", "attention", "mamba", "mamba"),
            mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
            mamba_chunk_size=8)),
        55, "a364232aa219e1b6"),
    "qwen3_next": (lambda: qwen3_next.Qwen3NextForCausalLM(
        qwen3_next.Qwen3NextConfig.tiny()), 48, "86e1d662f9aa2c94"),
    "smallthinker": (lambda: smallthinker.SmallThinkerForCausalLM(
        smallthinker.SmallThinkerConfig.tiny()), 71, "ded4786c4d8c2143"),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_a_decoder_builds_the_parameter_tree_it_always_built(name):
    build, n, digest = TREES[name]
    shapes = jax.eval_shape(build().init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))
    leaves = sorted(
        ("/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p),
         tuple(x.shape), str(x.dtype))
        for p, x in jax.tree_util.tree_flatten_with_path(shapes)[0])
    assert ("params/embed_tokens/embedding", (96, 32), "float32") in leaves
    assert len(leaves) == n
    assert hashlib.sha256(repr(leaves).encode()).hexdigest()[:16] == digest


def _imports(path: str):
    """``(module, names)`` of each import of a file under ``models/``, the
    module as it is spelled after ``sparkdl_tpu.models.``."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.level == 1:
                mod = node.module or ""
            elif (node.module or "").startswith("sparkdl_tpu.models"):
                mod = node.module[len("sparkdl_tpu.models."):]
            else:
                continue
            names = [a.name for a in node.names]
            if not mod:         # from . import x
                yield from ((n, []) for n in names)
            else:
                yield mod, names
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("sparkdl_tpu.models."):
                    yield a.name[len("sparkdl_tpu.models."):], []


def test_no_model_file_takes_a_part_from_a_decoder_or_from_llama():
    files = sorted(f for f in os.listdir(MODELS_DIR)
                   if f.endswith(".py") and f != "__init__.py")
    assert "decoder_parts.py" in files and "hybrid_common.py" not in files
    for f in files:
        for mod, names in _imports(os.path.join(MODELS_DIR, f)):
            assert mod.split(".")[0] not in DECODERS, (f, mod)
            assert not (mod == "llama" and "RMSNorm" in names), f


def test_the_decoder_parts_import_no_model_file_but_the_loss():
    imported = {mod.split(".")[0] for mod, _ in _imports(
        os.path.join(MODELS_DIR, "decoder_parts.py"))}
    assert imported == {"lm_loss"}
