"""Quantized serving (ISSUE 18): int8/fp8 block-quantized paged KV
fused into the flash-decode kernel, plus int8 projection weights.

Layers, leanest first: the `_quant_insert_rows` scale discipline
(round-trip error bounded by ½ LSB of the per-block scale — the
documented tolerance gate; scale reset on block reuse; requant when a
later row grows a block's amax), the `support_reason` contract (every
stand-down names WHY — the boolean `supports` twin never disagrees),
the fused-dequant kernel's equivalence to the dequantized gather view
in interpret mode (S=1 decode and the S=k+1 verify window), the
`QuantDense` int8 weight path (absmax per-output-channel), and the
backend-level fallback regression (an unsupported block size serves
through the dense gather view and the log says why).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import llama as L
from sparkdl_tpu.ops import flash_decode as fd
from sparkdl_tpu.ops import paged_flash_decode as pfd

# ---------------------------------------------------------------------------
# scale discipline (_quant_insert_rows)
# ---------------------------------------------------------------------------


def _fresh(pool=6, hkv=2, bs=8, d=16, name="int8"):
    qdt, _ = L.kv_quant_spec(name)
    codes = jnp.zeros((pool, hkv, bs, d), qdt)
    plane = jnp.zeros((pool, hkv, 2), jnp.float32)
    return codes, plane


class TestQuantInsertRows:
    @pytest.mark.parametrize("name", sorted(L.KV_QUANT_DTYPES))
    def test_round_trip_error_within_documented_gate(self, name):
        """THE tolerance gate the README documents: after quantizing a
        full block of rows, dequantized values sit within ½ LSB of the
        block scale for int8 (round-to-nearest of codes), and within
        an e4m3 mantissa step (2^-3 relative, plus the absmax scale)
        for fp8."""
        rng = np.random.RandomState(0)
        codes, plane = _fresh(name=name)
        bs, hkv, d = 8, 2, 16
        rows = jnp.asarray(rng.randn(bs, hkv, d), jnp.float32) * 3.0
        blk = jnp.full((bs,), 2, jnp.int32)
        off = jnp.arange(bs, dtype=jnp.int32)
        codes, plane = L._quant_insert_rows(codes, plane, 0, blk, off,
                                            rows)
        scale = np.asarray(plane)[2, :, 0]              # [Hkv]
        got = np.asarray(codes)[2].astype(np.float32) \
            * scale[:, None, None]                      # [Hkv, bs, d]
        want = np.transpose(np.asarray(rows), (1, 0, 2))
        err = np.abs(got - want)
        if name == "int8":
            assert (err <= 0.5 * scale[:, None, None] + 1e-7).all()
        else:  # fp8 e4m3: relative mantissa step, scaled
            amax = np.abs(want).max(axis=(1, 2), keepdims=True)
            assert (err <= amax * 2.0 ** -3).all()
        # the scale is the block absmax over qmax — no clipping happened
        qmax = L.kv_quant_spec(name)[1]
        np.testing.assert_allclose(
            scale, want.reshape(hkv, -1).__abs__().max(-1) / qmax,
            rtol=1e-6)

    def test_scale_grows_and_resident_rows_requantize(self):
        """A later row with a larger absmax grows the shared block
        scale; rows already resident requantize by old/new — still
        within ½ NEW LSB of their original values."""
        codes, plane = _fresh()
        small = jnp.ones((1, 2, 16), jnp.float32) * 0.5
        big = jnp.ones((1, 2, 16), jnp.float32) * 8.0
        blk = jnp.asarray([3], jnp.int32)
        codes, plane = L._quant_insert_rows(
            codes, plane, 1, blk, jnp.asarray([0], jnp.int32), small)
        s0 = float(plane[3, 0, 1])
        codes, plane = L._quant_insert_rows(
            codes, plane, 1, blk, jnp.asarray([1], jnp.int32), big)
        s1 = float(plane[3, 0, 1])
        assert s1 > s0
        deq = np.asarray(codes)[3, :, 0].astype(np.float32) * s1
        assert np.abs(deq - 0.5).max() <= 0.5 * s1 + 1e-7
        deq1 = np.asarray(codes)[3, :, 1].astype(np.float32) * s1
        assert np.abs(deq1 - 8.0).max() <= 0.5 * s1 + 1e-7

    def test_block_reuse_resets_scale_not_inherits(self):
        """An off == 0 write is a block's FIRST row (write-frontier
        invariant): a freed-then-reallocated block must take the NEW
        tenant's scale, not keep amplifying under the old one."""
        codes, plane = _fresh()
        blk = jnp.asarray([4], jnp.int32)
        codes, plane = L._quant_insert_rows(
            codes, plane, 0, blk, jnp.asarray([0], jnp.int32),
            jnp.ones((1, 2, 16), jnp.float32) * 100.0)
        assert float(plane[4, 0, 0]) == pytest.approx(100.0 / 127.0)
        codes, plane = L._quant_insert_rows(
            codes, plane, 0, blk, jnp.asarray([0], jnp.int32),
            jnp.ones((1, 2, 16), jnp.float32) * 0.25)
        assert float(plane[4, 0, 0]) == pytest.approx(0.25 / 127.0)
        deq = float(codes[4, 0, 0, 0]) * float(plane[4, 0, 0])
        assert deq == pytest.approx(0.25, abs=0.5 * 0.25 / 127.0)

    def test_gather_dequant_matches_manual(self):
        rng = np.random.RandomState(5)
        codes, plane = _fresh()
        rows = jnp.asarray(rng.randn(8, 2, 16), jnp.float32)
        blk = jnp.asarray([1] * 4 + [5] * 4, jnp.int32)
        off = jnp.asarray([0, 1, 2, 3] * 2, jnp.int32)
        codes, plane = L._quant_insert_rows(codes, plane, 0, blk, off,
                                            rows)
        tables = jnp.asarray([[1, 5, 0]], jnp.int32)
        got = np.asarray(L._gather_dequant(codes, plane, 0, tables,
                                           jnp.float32))
        c = np.asarray(codes)
        s = np.asarray(plane)[..., 0]
        manual = np.concatenate(
            [c[b].astype(np.float32) * s[b][:, None, None]
             for b in (1, 5, 0)], axis=1)[None]
        np.testing.assert_allclose(got, manual, rtol=1e-6)


# ---------------------------------------------------------------------------
# support_reason contract (ISSUE 18 satellite: stand-downs name WHY)
# ---------------------------------------------------------------------------


class TestSupportReason:
    def test_paged_reasons_and_boolean_twin_agree(self):
        assert pfd.support_reason(16) is None
        assert pfd.support_reason(16, kv_dtype="int8") is None
        r = pfd.support_reason(12)
        assert r is not None and "12" in r and "8-multiple" in r
        r = pfd.support_reason(16, kv_dtype="int3")
        assert r is not None and "int3" in r and "available" in r
        for bs, kv in ((16, None), (12, None), (16, "int8"),
                       (16, "nope"), (7, None)):
            assert pfd.supports(bs, kv) == \
                (pfd.support_reason(bs, kv) is None)

    def test_dense_reasons_and_boolean_twin_agree(self):
        assert fd.support_reason(256) is None
        r = fd.support_reason(100)
        assert r is not None and "100" in r
        for ml in (256, 100, 64, 130):
            assert fd.supports(ml) == (fd.support_reason(ml) is None)

    def test_backend_stand_down_logs_the_reason(self, caplog):
        """The fallback regression: a paged backend at a block size the
        kernel cannot take still serves (dense gather view) and the
        construction log NAMES the reason — 'dense attention was
        chosen' never again without a why."""
        from sparkdl_tpu.serving import GenerationEngine

        cfg = L.LlamaConfig.tiny()
        model = L.LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 4), np.int32))
        with caplog.at_level(logging.INFO, "sparkdl_tpu.serving"):
            eng = GenerationEngine.from_model(
                model, variables, num_slots=1, max_len=24,
                block_size=12, kv_dtype="int8")
        msgs = [r.getMessage() for r in caplog.records
                if "stands down" in r.getMessage()]
        assert msgs and "8-multiple" in msgs[0]
        h = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run_until_idle()
        assert len(h.result(1)) == 4  # served through the gather view


# ---------------------------------------------------------------------------
# fused-dequant kernel vs the dequantized gather view (interpret mode)
# ---------------------------------------------------------------------------


def _quantized_pool(seed=0, *, hkv=2, bs=8, mb=3, pool=7, d=16):
    """An adversarial quantized layout built through the REAL insert
    routine: non-contiguous live blocks, a trash-parked slot, mixed
    fills — the paged-flash-decode test harness shape, quantized."""
    rng = np.random.RandomState(seed)
    k_codes, k_plane = _fresh(pool, hkv, bs, d)
    v_codes, _ = _fresh(pool, hkv, bs, d)
    plane = k_plane
    tables = np.zeros((3, mb), np.int32)
    tables[0] = [5, 2, 0]
    tables[1] = [3, 1, 6]
    tables[2] = 0                       # trash-parked
    cur = np.asarray([11, 22, 0], np.int32)
    pads = np.asarray([0, 4, 0], np.int32)
    for slot in range(2):
        for p in range(int(cur[slot])):
            blk = jnp.asarray([tables[slot][p // bs]], jnp.int32)
            off = jnp.asarray([p % bs], jnp.int32)
            kr = jnp.asarray(rng.randn(1, hkv, d), jnp.float32)
            vr = jnp.asarray(rng.randn(1, hkv, d), jnp.float32)
            k_codes, plane = L._quant_insert_rows(k_codes, plane, 0,
                                                  blk, off, kr)
            v_codes, plane = L._quant_insert_rows(v_codes, plane, 1,
                                                  blk, off, vr)
    return (k_codes, v_codes, plane, jnp.asarray(tables),
            jnp.asarray(cur), jnp.asarray(pads))


class TestQuantKernelParity:
    @pytest.mark.parametrize("s_q", [1, 3])
    def test_kernel_equals_dequant_gather_reference(self, s_q):
        """Decode (S=1) and the speculative verify window (S=k+1): the
        fused-dequant paged kernel must match dense flash-decode over
        the DEQUANTIZED gather view. The fold point differs (kernel
        scales after each dot, reference before), so the pin is
        allclose at float-assoc tolerance, not bitwise."""
        k_codes, v_codes, plane, tables, cur, pads = _quantized_pool()
        hkv, bs, d = 2, 8, 16
        q = jnp.asarray(np.random.RandomState(9).randn(
            3, hkv * 2, s_q, d), jnp.float32)
        got = pfd.paged_flash_decode(q, k_codes, v_codes, tables, cur,
                                     pads, kv_scales=plane,
                                     interpret=True)
        kg = L._gather_dequant(k_codes, plane, 0, tables, jnp.float32)
        vg = L._gather_dequant(v_codes, plane, 1, tables, jnp.float32)
        want = jnp.concatenate(
            [fd.flash_decode(q[:, :, i:i + 1], kg, vg, cur + i + 1,
                             pads, block_k=bs, interpret=True)
             for i in range(s_q)], axis=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert np.isfinite(np.asarray(got[2])).all()  # trash-parked

    def test_quantized_pool_requires_scales(self):
        k_codes, v_codes, plane, tables, cur, pads = _quantized_pool()
        q = jnp.zeros((3, 4, 1, 16), jnp.float32)
        with pytest.raises(ValueError, match="kv_scales"):
            pfd.paged_flash_decode(q, k_codes, v_codes, tables, cur,
                                   pads, interpret=True)


# ---------------------------------------------------------------------------
# int8 weights (QuantDense / quantize_params)
# ---------------------------------------------------------------------------


class TestWeightQuant:
    def _model(self):
        cfg = L.LlamaConfig.tiny()
        model = L.LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 4), np.int32))
        return cfg, model, variables

    def test_quantize_params_targets_and_shapes(self):
        _, model, variables = self._model()
        qp = L.quantize_params(variables["params"], "int8")
        seen = set()
        def walk(tree, path=""):
            for k, v in tree.items():
                p = f"{path}/{k}"
                if isinstance(v, dict) and "kernel" in v:
                    name = path.rsplit("/", 1)[-1] if k == "base" else k
                    kern = v["kernel"]
                    if name in L.WEIGHT_QUANT_TARGETS:
                        seen.add(name)
                        assert kern.dtype == jnp.int8, p
                        assert v["kernel_scale"].shape == \
                            (kern.shape[1],), p
                    else:
                        assert kern.dtype != jnp.int8, p
                if isinstance(v, dict):
                    walk(v, p)
        walk(qp)
        assert seen == set(L.WEIGHT_QUANT_TARGETS)

    def _ids(self, cfg):
        return np.random.RandomState(3).randint(
            0, cfg.vocab_size, (2, 6)).astype(np.int32)

    def test_int8_forward_tracks_f32(self):
        """The quantized model's logits stay within absmax-per-channel
        int8 error of the f32 model's: ``|out - ref|_2 / |ref|_2`` under
        a limit set between the sound reading and the planted faults'.
        Readings on this tiny model over ids seeds 3..6: int8
        0.0144-0.0161; scales 5% off 0.060-0.072; one scale a tensor in
        place of one a channel 0.092-0.102; scales left out 1.16-1.27.
        No argmax identity: on random weights the top two logits of a
        row can sit closer than int8 rounding moves them."""
        cfg, model, variables = self._model()
        ids = self._ids(cfg)
        ref = np.asarray(model.apply(variables, ids), np.float64)
        qmodel = model.clone(weight_quant="int8")
        qp = L.quantize_params(variables["params"], "int8")

        def gap(params):
            out = np.asarray(qmodel.apply({"params": params}, ids),
                             np.float64)
            return np.linalg.norm(out - ref) / np.linalg.norm(ref)

        def with_scales(tree, f):
            if not isinstance(tree, dict):
                return tree
            return {k: f(v) if k == "kernel_scale" else with_scales(v, f)
                    for k, v in tree.items()}

        limit = 0.03
        assert gap(qp) < limit
        # the limit is a limit: the mildest planted fault reads over it
        assert gap(with_scales(qp, lambda s: s * 1.05)) > limit
        assert gap(with_scales(qp, jnp.ones_like)) > limit

    def test_quant_model_with_float_params_is_bitwise_f32(self):
        """The SAME quantized-model clone fed UNCONVERTED float params
        takes the plain dense path and matches the f32 model bitwise
        (graceful unconverted checkpoint)."""
        cfg, model, variables = self._model()
        ids = self._ids(cfg)
        ref = model.apply(variables, ids)
        exact = model.clone(weight_quant="int8").apply(variables, ids)
        np.testing.assert_array_equal(np.asarray(exact),
                                      np.asarray(ref))


# ---------------------------------------------------------------------------
# engine-level guards
# ---------------------------------------------------------------------------


class TestEngineGuards:
    def test_kv_dtype_without_paging_raises(self):
        from sparkdl_tpu.serving import GenerationEngine

        cfg = L.LlamaConfig.tiny()
        model = L.LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 4), np.int32))
        with pytest.raises(ValueError, match="paged"):
            GenerationEngine.from_model(model, variables, num_slots=1,
                                        max_len=32, kv_dtype="int8")

    def test_unknown_dtypes_raise_loudly(self):
        with pytest.raises(ValueError, match="available"):
            L.kv_quant_spec("int4")
        from sparkdl_tpu.serving.backend import PagedLlamaSlotBackend
        cfg = L.LlamaConfig.tiny()
        model = L.LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 4), np.int32))
        with pytest.raises(ValueError, match="int4"):
            PagedLlamaSlotBackend(model, variables, 1, 32,
                                  kv_dtype="int4")
