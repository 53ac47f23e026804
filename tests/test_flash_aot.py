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


# -- the window, the wider values, and the selective scan (PR 34) -----------------

# sha256 of each kernel's Mosaic module at the LFM2 cell's shape, printed
# without debug locations, as the tree before the window lowered it (PR 33's
# commit, this container's jax). A kernel edit that is meant to change the
# causal program re-pins them: ``_kernel_modules`` of the new lowering.
LFM2_KERNELS = {
    "flash_attention_fwd":
        "c6bf1a3344388bda6753be54a7bd107af6271c8bd4d6a5348cea9ef619779b17",
    "flash_attention_bwd_dkv":
        "657b7239b09e6258e1ec0dfbcd09e3e8b9deac2b779589e58c2c48a634ed0b9e",
    "flash_attention_bwd_dq":
        "782c5368fe393bc015310c59eb2c6068af4fe52bd4446cb2f2568c3574865305",
}


def _kernel_modules(lowered_text: str) -> dict:
    """``{kernel name: its Mosaic module as text, debug locations left
    out}`` of every ``tpu_custom_call`` of a lowered program."""
    import base64
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jaxlib.mlir import ir
    out = {}
    for line in lowered_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        cfg = re.search(r'backend_config = "(.*?)"(?=[,}])', line).group(1)
        body = json.loads(cfg.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            text = module.operation.get_asm(enable_debug_info=False)
        out[re.search(r"module @(\w+)", text).group(1)] = text
    return out


def test_without_a_window_the_kernels_lower_to_the_programs_they_were(
        one_chip):
    """2 x 32 heads of 64 at S = 8192, bf16, causal, no window — the LFM2
    cell's call: each of the three kernels' Mosaic modules is, operation for
    operation, the one the tree before the window built."""
    import hashlib
    x = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, interpret=False).astype(
            jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).as_text()
    modules = _kernel_modules(text)
    assert set(modules) == set(LFM2_KERNELS)
    for name, module in modules.items():
        assert hashlib.sha256(module.encode()).hexdigest() == \
            LFM2_KERNELS[name], name


@pytest.mark.parametrize("window", [512, None], ids=["window-512", "full"])
def test_differential_attentions_gradient_at_the_cells_shape(one_chip,
                                                             window):
    """40 heads of 64 with values 128 wide at S = 8192, bf16 — the new cell's
    attention layers, windowed (layer 15) and not (17, 19): forward and the
    backward pair compile, and under the window the innermost grid axis of
    all three spans the band's 2 tiles, not 16."""
    b, h, s = 1, 40, 8192
    qk = jax.ShapeDtypeStruct((b, h, s, 64), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, s, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, window=window,
                                interpret=False).astype(jnp.float32)
                ** 2).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(qk, qk, v)
    grids = [re.search(r"iteration_bounds = array<i64: ([0-9, ]+)>",
                       module).group(1)
             for module in _kernel_modules(lowered.as_text()).values()]
    assert grids == ["40, 16, %d" % (2 if window else 16)] * 3, grids
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 3
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < b * h * s * 512, largest


def test_a_window_of_a_quarter_of_16384_positions_at_28_heads(one_chip):
    """28 heads of 128 at S = 16384 under a window of 4096, bf16 — the
    SmallThinker cell's window layers (its key/value heads repeated to 28
    before the call): forward and the backward pair compile, and the
    innermost grid axis of all three spans the band's 9 tiles of 512, not 32.
    Structure, not time: nothing runs."""
    b, h, s = 1, 28, 16384
    x = jax.ShapeDtypeStruct((b, h, s, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, window=4096,
                                interpret=False).astype(jnp.float32)
                ** 2).sum()

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)
    grids = [re.search(r"iteration_bounds = array<i64: ([0-9, ]+)>",
                       module).group(1)
             for module in _kernel_modules(lowered.as_text()).values()]
    assert grids == ["28, 32, 9"] * 3, grids
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") == 3
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < b * h * s * 512, largest


def test_the_cells_scan_gradient_keeps_no_state_per_position(one_chip):
    """One sequence of 8192 positions, 5120 channels, 16 states, ``u, B, C``
    in bf16 and ``dt`` in float32 — the new cell's Mamba layer: the forward
    and the backward kernel compile at the default chunk and channel block,
    by name, and no buffer of the compiled gradient holds
    ``S * 5120 * 16`` elements or more (the per-position state, 2.7 GB in
    float32, stays in VMEM)."""
    from sparkdl_tpu.ops.selective_scan import selective_scan
    s, c, n = 8192, 5120, 16

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((1, s, c), jnp.bfloat16), sd((1, s, c), jnp.float32),
            sd((c, n), jnp.float32), sd((1, s, n), jnp.bfloat16),
            sd((1, s, n), jnp.bfloat16), sd((c,), jnp.float32))

    def loss(*a):
        return (selective_scan(*a, interpret=False)[0].astype(jnp.float32)
                ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "selective_scan_fwd" in text and "selective_scan_bwd" in text
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < s * c * n, largest
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_scan_compiles_at_a_ragged_length_in_float32(one_chip):
    """The tests' dtype and a length that is no multiple of the chunk."""
    from sparkdl_tpu.ops.selective_scan import selective_scan
    x = jax.ShapeDtypeStruct((2, 300, 256), jnp.float32, sharding=one_chip)
    cols = jax.ShapeDtypeStruct((2, 300, 16), jnp.float32, sharding=one_chip)
    a = jax.ShapeDtypeStruct((256, 16), jnp.float32, sharding=one_chip)
    d = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.grad(lambda *t: selective_scan(
        *t, interpret=False)[0].sum(), argnums=tuple(range(6)))).lower(
            x, x, a, cols, cols, d).compile().as_text()
    assert text.count("tpu_custom_call") == 2


# -- the chunked scan, and the flash kernels under a third decoder (PR 36) ------------

# sha256 of each flash kernel's Mosaic module, printed without debug
# locations, at the shapes of the other two decoders' attention calls, as the
# tree before PR 36 lowered them (``ops/flash_attention.py`` is untouched by
# it; this container's jax): the Phi cell's windowed and full differential
# attention (40 heads, values 128 wide) and the Granite cell's one layer (the
# LFM2 cell's call at half the batch, ``q`` handed over already scaled: no
# ``scale`` argument went into the kernels).
OTHER_CELLS_KERNELS = {
    "phi-window-512": ((1, 40, 128, 512), {
        "flash_attention_fwd":
            "0a77f802976315a2063a622524f4fc68c3b4cf56ba05219bf7fc4770b63332f8",
        "flash_attention_bwd_dkv":
            "c9a184054e5a98f9a36a53642e1abd3287e2b76066c1be810aff0c277cb48b14",
        "flash_attention_bwd_dq":
            "dbeda05144dd43d246b3cfaa918fb88cedc8cab7535a9b2c73e4d30e7220a9b8"}),
    "phi-full": ((1, 40, 128, None), {
        "flash_attention_fwd":
            "ccd220eb872d9feadb03775cc611a3c1fcc2addb50466e68e4046d200e4cc783",
        "flash_attention_bwd_dkv":
            "9c1bc0764d45a12770bfe74b6d7f1d2b3ab5a2ed43808d2bfc46e97c5d5306f6",
        "flash_attention_bwd_dq":
            "db0b58863592f5e3666f5e69d8dbe081c9c8923b4d53a51b8c3e46e9b379ac3b"}),
    "granite": ((1, 32, 64, None), {
        "flash_attention_fwd":
            "4212efe787dfdf24e57b0a0813b60ad7c9a6ddcddbcc9685a4e897cf56a2646c",
        "flash_attention_bwd_dkv":
            "f7d13badffe3356dfefbbb4edf9306628003dcf21c117d0a0458382ab0fae152",
        "flash_attention_bwd_dq":
            "8dc8c671f917707e649d09c6d72d52fc3b715117de274794c2ccf1fdbfc4a0a8"}),
}


@pytest.mark.parametrize("call", OTHER_CELLS_KERNELS.values(),
                         ids=OTHER_CELLS_KERNELS.keys())
def test_the_other_decoders_flash_kernels_lower_to_the_programs_they_were(
        one_chip, call):
    import hashlib
    (b, h, dv, window), pinned = call
    qk = jax.ShapeDtypeStruct((b, h, 8192, 64), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, h, 8192, dv), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, window=window,
                                interpret=False).astype(jnp.float32)
                ** 2).sum()

    modules = _kernel_modules(jax.jit(jax.grad(
        loss, argnums=(0, 1, 2))).lower(qk, qk, v).as_text())
    assert set(modules) == set(pinned)
    for name, module in modules.items():
        assert hashlib.sha256(module.encode()).hexdigest() == pinned[name], \
            name


def _ssd_args(one_chip, s, h, p, n, dtype):
    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (sd((1, s, h, p), dtype), sd((1, s, h), jnp.float32),
            sd((h,), jnp.float32), sd((1, s, 1, n), dtype),
            sd((1, s, 1, n), dtype), sd((h,), jnp.float32))


def test_the_cells_chunked_scan_gradient_keeps_no_tile_and_no_state_a_position(
        one_chip):
    """One sequence of 8192 positions, 64 heads of 64, 128 states, ``x, B,
    C`` in bf16 and ``dt`` in float32, chunks of 256 — the Granite cell's
    Mamba-2 layer: the forward and the backward kernel compile at the default
    head block, by name, and no buffer of the compiled gradient holds
    ``S / Q * H * Q * Q`` elements or more (the decay-masked score tiles,
    537 MB in float32, which the plain chunked form writes several times a
    layer) — so none holds a state a position either (``S * H * P * N``, 128
    times that). The largest are ``x``'s own 33.5 M."""
    from sparkdl_tpu.ops.ssd_scan import ssd_scan
    s, h, p, n, q = 8192, 64, 64, 128, 256

    def loss(*a):
        return (ssd_scan(*a, chunk=q, interpret=False)[0].astype(jnp.float32)
                ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        *_ssd_args(one_chip, s, h, p, n, jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < s // q * h * q * q, largest
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_the_chunked_scan_compiles_at_a_ragged_length_in_float32(one_chip):
    """The tests' dtype, a length that is no multiple of the chunk, heads as
    wide as the lanes."""
    from sparkdl_tpu.ops.ssd_scan import ssd_scan
    text = jax.jit(jax.grad(lambda *t: ssd_scan(
        *t, chunk=128, interpret=False)[0].sum(),
        argnums=tuple(range(6)))).lower(
            *_ssd_args(one_chip, 300, 4, 128, 64, jnp.float32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2


# -- a head of 256 and the gated delta rule (PR 40) --------------------------------

def test_the_three_flash_kernels_hold_their_tiles_at_a_head_of_256(one_chip):
    """One sequence of 8192 positions, 16 heads of 256, bf16, causal -- the
    Qwen3-Next cell's attention layer, the widest head any cell calls the
    kernels at (tiles of ``[512, 256]``): forward and the backward pair
    compile at the default 512-blocks, by name, with no score-sized buffer."""
    b, h, s, d = 1, 16, 8192, 256
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


def _delta_args(one_chip, s, hk, hv, d, dtype):
    def sd(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return (sd((1, s, hk, d), dtype), sd((1, s, hk, d), dtype),
            sd((1, s, hv, d), dtype), sd((1, s, hv), jnp.float32),
            sd((1, s, hv), jnp.float32))


def test_the_cells_delta_rule_gradient_hands_its_kernels_no_tile_and_no_state_a_position(
        one_chip):
    """One sequence of 8192 positions, 16 key and 32 value heads of 128,
    ``q, k, v`` in bf16 and ``g, beta`` in float32, chunks of 128 -- the
    Qwen3-Next cell's linear-attention layer: the preparation's kernel pair
    and the recurrence's forward and backward kernels compile at their
    default head blocks, by name; no operand or result of the recurrence's
    two is a ``[Q, Q]`` tile a chunk (the preparation's forward writes ``T``
    and its backward reads it, ``S / Q * Hv * Q * Q`` elements) and the
    largest any of the four touches is as large as the chunk-start states;
    no buffer of the whole gradient holds a state a position (``S * Hv * Dk
    * Dv``)."""
    from sparkdl_tpu.ops.gated_delta import gated_delta_rule
    s, hk, hv, d, q = 8192, 16, 32, 128, 128

    def loss(*a):
        return (gated_delta_rule(*a, chunk=q, interpret=False)[0].astype(
            jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        *_delta_args(one_chip, s, hk, hv, d, jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    for name in ("gated_delta_fwd_prep", "gated_delta_fwd.",
                 "gated_delta_bwd.", "gated_delta_bwd_prep"):
        assert name in text
    states = s // q * hv * d * d
    for line in text.splitlines():
        if "tpu_custom_call" not in line or " custom-call(" not in line:
            continue
        shapes = [tuple(map(int, dims.split(","))) for dims in
                  re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", line)]
        assert shapes and max(map(math.prod, shapes)) == states, line[:200]
        tiles = [x for x in shapes if x[-2:] == (q, q) and len(x) > 3]
        assert bool(tiles) == any(
            name in line for name in ("gated_delta_fwd_prep",
                                      "gated_delta_bwd_prep")), line[:200]
    largest = max(
        math.prod(map(int, dims.split(",")))
        for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text))
    assert largest < s * hv * d * d // 64, largest


def test_the_delta_rule_compiles_at_a_ragged_length_in_float32(one_chip):
    """The tests' dtype, a length that is no multiple of the chunk, one value
    head a key head."""
    from sparkdl_tpu.ops.gated_delta import gated_delta_rule
    text = jax.jit(jax.grad(lambda *t: gated_delta_rule(
        *t, chunk=128, interpret=False)[0].sum(),
        argnums=tuple(range(5)))).lower(
            *_delta_args(one_chip, 300, 4, 4, 128, jnp.float32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 4


# -- the routed layer's row movement (PR 35) --------------------------------------

def _entry_results(text: str) -> list:
    """``(opcode, dtype, dims)`` of every array that an instruction of the
    ENTRY computation yields (a fused computation's inner shapes are never
    materialised, so they are left out)."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    out = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\(",
                     line)
        if m:
            out += [(m.group(2), dtype, tuple(map(int, dims.split(","))))
                    for dtype, dims in re.findall(r"(\w+)\[([0-9,]+)\]",
                                                  m.group(1))]
    return out


@pytest.mark.parametrize("n,k,f,held", [(16384, 4, 1792, 8),
                                         (8192, 10, 512, 32)],
                         ids=["lfm2", "qwen3next"])
def test_the_routed_layer_moves_each_held_row_once(one_chip, n, k, f, held):
    """``held_experts_ffn`` and its gradient under ``jax.checkpoint`` at the
    LFM2 cell's shape (16,384 tokens, top 4, 2048 wide, experts of 1792, 8
    held) and the Qwen3-Next cell's (8,192 tokens, top 10, experts of 512, 32
    held), bf16: the assignment rows are ``[N k, 2048]`` (268 and 336 MB).
    Outside the grouped products and the row kernels (``custom-call``s), the
    compiled program writes such a buffer ONCE, the sum of the two products'
    ``d rows`` (8 times before the row kernels: the six gathers, that sum and
    the combine's gradient), in bf16, and never a broadcast of ``h``: every
    other move of a row is a kernel's, over the held slots alone. Structure,
    not time: nothing runs."""
    from sparkdl_tpu.parallel.moe import held_experts_ffn
    d = 2048

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sd((n, d), jnp.bfloat16), sd((n, k), jnp.float32),
            sd((held, d, f), jnp.float32), sd((held, d, f), jnp.float32),
            sd((held, f, d), jnp.float32), sd((n, k), jnp.int32))

    @jax.checkpoint
    def layer(h, w, w1, w3, w2, idx):
        return held_experts_ffn(h, idx, w, w1, w3, w2, interpret=False)[0]

    def loss(*a):
        return (layer(*a).astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    rows = [(op, dtype, dims) for op, dtype, dims in _entry_results(text)
            if math.prod(dims) == n * k * d]
    assert rows and {dims for _, _, dims in rows} == {(n * k, d)}, rows
    assert {dtype for _, dtype, _ in rows} == {"bf16"}, rows
    written = [op for op, _, _ in rows if op not in (
        "custom-call", "bitcast", "reshape", "get-tuple-element")]
    assert written == ["add"], written
    for name in ("moe_gather_rows", "moe_scatter_rows"):
        assert name in text


def _kernel_bodies(lowered_text: str) -> list:
    """``(kernel name, its Mosaic module as serialized)`` of every
    ``tpu_custom_call`` of a lowered program, in order."""
    out = []
    for line in lowered_text.splitlines():
        if "tpu_custom_call" in line:
            out.append((re.search(r'kernel_name = "(\w+)"', line).group(1),
                        re.search(r'\\22body\\22: \\22([^\\]+)',
                                  line).group(1)))
    return out


def test_the_row_kernels_lower_to_one_program_whatever_the_rows(one_chip):
    """Each row kernel's Mosaic module, printed without debug locations, at
    the LFM2 cell's 16,384 x 4 slots and the Qwen3-Next cell's 8,192 x 10
    differs in its numbers alone (shapes and trip counts), and stays under
    100,000 characters: the rows are walked by a loop whose trip count is
    read on the chip, eight a step, never unrolled over a block's 1,024
    slots (which would print one body a slot)."""
    from sparkdl_tpu.ops import moe_rows
    d = 2048

    def modules(n, k):
        def sd(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        tok, live = sd((n * k,), jnp.int32), sd((1,), jnp.int32)
        w, y = sd((n * k,), jnp.float32), sd((n * k, d), jnp.bfloat16)
        h = sd((n, d), jnp.bfloat16)
        text = "\n".join(jax.jit(f).lower(*a).as_text() for f, a in (
            (lambda h, t, l: moe_rows.gather_rows(h, t, l)[0], (h, tok, live)),
            (lambda h, t, l, w, y: moe_rows.gather_rows(h, t, l, w, y),
             (h, tok, live, w, y)),
            (lambda y, t, l, w: moe_rows.scatter_rows(y, t, l, w, n=n),
             (y, tok, live, w)),
            (lambda y, t, l: moe_rows.scatter_rows(y, t, l, n=n),
             (y, tok, live))))
        return [re.sub(r"[0-9]+", "0", m)
                for m in _kernel_modules_in_order(text)]

    lfm2, qwen = modules(16384, 4), modules(8192, 10)
    assert len(lfm2) == len(qwen) == 4
    for a, b in zip(lfm2, qwen):
        assert a == b
        assert len(a) < 100_000, len(a)


def _kernel_modules_in_order(lowered_text: str) -> list:
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jaxlib.mlir import ir
    out = []
    for _, body in _kernel_bodies(lowered_text):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            out.append(module.operation.get_asm(enable_debug_info=False))
    return out


def test_two_routed_layers_hold_each_row_kernel_as_one_layer_does(one_chip):
    """The gradient of two routed layers, each under ``jax.checkpoint``,
    lowered for the chip: each row kernel is ONE ``jax.jit`` a shape and
    dtype, so the second layer, the recomputation and the backward call the
    lowerings the first layer made, and the lowered program holds each
    kernel's Mosaic body exactly as often as the one-layer program does --
    the forward's gather once more for the recomputation, every other body
    once. Nothing is lowered a layer."""
    import collections

    from sparkdl_tpu.parallel.moe import held_experts_ffn
    n, k, d, f, held = 1024, 4, 256, 128, 8

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @jax.checkpoint
    def layer(h, w, w1, w3, w2, idx):
        return held_experts_ffn(h, idx, w, w1, w3, w2, interpret=False)[0]

    def bodies(layers):
        def loss(h, w, experts, idx):
            for e in experts:
                h = layer(h, w, *e, idx)
            return (h.astype(jnp.float32) ** 2).sum()
        e = (sd((held, d, f), jnp.float32), sd((held, d, f), jnp.float32),
             sd((held, f, d), jnp.float32))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            sd((n, d), jnp.bfloat16), sd((n, k), jnp.float32),
            (e,) * layers, sd((n, k), jnp.int32)).as_text()
        return collections.Counter(_kernel_bodies(text))

    one, two = bodies(1), bodies(2)
    assert one == two
    assert sorted(collections.Counter(
        name for name, _ in one.elements()).items()) == [
        ("moe_gather_rows", 3), ("moe_scatter_rows", 2)]
    assert sorted(one.values()) == [1, 1, 1, 2]


# -- the loss around the tied head (PR 39) -------------------------------------

def test_the_head_and_loss_gradient_lays_the_logits_out_once(one_chip):
    """The tied head's product and ``causal_lm_loss_fn`` over it, with the
    gradient, at the Phi cell's shape (8,192 positions, 2,560 wide, a
    vocabulary of 25,008 = 195 x 128 + 48): the program keeps two blocks of
    the logits' size, the float32 logits (819 MB) and their cotangent
    ``(softmax - [v == label]) / N``, which one fusion writes in bf16 for
    the two backward products (410 MB). With a label picked by
    ``take_along_axis`` the transpose was a scatter, for which the compiler
    flattened the gradient to ``f32[204840528]`` and laid it back in two
    ``while`` loops with a zero fill each, beside a slice's copy to 8,191
    rows (2.46 GB of temporaries). Structure, not time: nothing runs."""
    from sparkdl_tpu.models.lm_loss import causal_lm_loss_fn
    s, d, v = 8192, 2560, 25008

    def loss(x, emb, ids):
        def head(_, got):
            return jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype),
                              preferred_element_type=jnp.float32)
        return causal_lm_loss_fn()(None, head, {"input_ids": ids})[0]

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((v, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    for op in ("while", "scatter", "gather", "dynamic-update-slice",
               "dynamic-slice"):
        assert not re.search(r" %s\(" % op, text), op
    assert str(s * v) not in text and str((s - 1) * v) not in text
    assert not re.search(r"\[(1,)?%d,%d\]|\[(1,)?%d,%d\]"
                         % (s - 1, v, v, s - 1), text)
    logits_sized = [(op, dtype, dims) for op, dtype, dims
                    in _entry_results(text) if math.prod(dims) >= s * v
                    and op not in ("get-tuple-element", "bitcast")]
    assert sorted((dtype, math.prod(dims), op)
                  for op, dtype, dims in logits_sized) == [
        ("bf16", s * v, "fusion"), ("f32", s * v, "fusion")], logits_sized
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * 6 * s * v
