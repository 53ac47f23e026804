"""The flash-attention kernels compiled for a DESCRIBED v5e chip, no chip
attached: what Mosaic refuses (tile alignment, scoped VMEM, a layout) and
what the compiled gradient keeps in HBM, at the training cell's real widths.
Nothing runs, so nothing here is a time or a result (interpret-mode tests and
the chip-gated ``test_compiled_flash_on_tpu`` hold those).

The topology is described inside a fixture and nowhere at import: only the
worker that runs this file loads the TPU's library. Keep every such test in
this one file.
"""

import math
import re

import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_grad(one_chip, shape, dtype, causal, *, masked=False,
                   block_q=None, block_k=None):
    b, _, s, _ = shape
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    m = jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=one_chip)

    def loss(q, k, v, kv_mask):
        o = flash_attention(q, k, v, causal,
                            kv_mask=kv_mask if masked else None,
                            block_q=block_q, block_k=block_k,
                            interpret=False)
        return (o.astype(jnp.float32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, m).compile()


def test_training_cell_gradient_keeps_no_score_tile_in_hbm(one_chip):
    """2 x 32 heads of 64 at S = 8192, bf16, causal — the LFM2 cell's
    attention layer: forward + the backward pair compile at the default
    512-blocks, and no buffer of the compiled gradient is score-sized
    ([B·H, S, 512] or more elements: 1.07 GB in float32, what the plain-jax
    scan wrote per key block before PR 30)."""
    b, h, s, d = 2, 32, 8192, 64
    compiled = _compiled_grad(one_chip, (b, h, s, d), jnp.bfloat16, True)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert name in text
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < b * h * s * 512, largest
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("shape,dtype,causal,masked,blocks", [
    ((2, 8, 2048, 64), jnp.float32, True, True, (None, None)),
    ((2, 3, 200, 32), jnp.float32, False, True, (None, None)),   # ragged S
    ((2, 8, 1024, 128), jnp.bfloat16, True, False, (256, 512)),  # bq != bk
    ((1, 12, 2048, 64), jnp.bfloat16, False, True, (None, None)),  # BERT's
], ids=["f32-causal-mask", "f32-ragged-mask", "bf16-d128-256x512",
        "bf16-noncausal-mask"])
def test_other_callers_shapes_compile(one_chip, shape, dtype, causal, masked,
                                      blocks):
    """Float32 inputs (the tests' dtype, twice the VMEM a tile), a ragged
    S through the padding, head size 128 (llama), unequal blocks, and the
    non-causal masked form BERT would take at S >= 2048."""
    compiled = _compiled_grad(one_chip, shape, dtype, causal, masked=masked,
                              block_q=blocks[0], block_k=blocks[1])
    assert compiled.as_text().count("tpu_custom_call") == 3


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_forward_alone_at_llamas_head(one_chip, dtype):
    """The forward alone at D = 128 and 512 x 512 blocks, causal — llama's
    head, the largest VMEM need of the forward's body (a (512, 512) float32
    score tile and its exponent, a (128, 512) float32 accumulator and its
    transpose into the output) — in bf16 and in the tests' float32."""
    x = jax.ShapeDtypeStruct((1, 8, 4096, 128), dtype, sharding=one_chip)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, block_q=512, block_k=512, interpret=False)).lower(
            x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_attention_fwd" in text
