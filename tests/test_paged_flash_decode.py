"""Paged flash-decode kernel (ISSUE 15): block-table attention without
the gather.

Reference = ``ops.flash_decode`` over the block-GATHERED dense view at
``block_k = block_size``: both kernels then execute the identical
online-softmax block walk — the paged kernel merely addresses each
block through the table instead of through a materialized copy — so
equivalence is asserted BITWISE (interpret mode, the same kernel the
chip compiles). Covered: ragged per-slot fills, slots parked entirely
on trash block 0, tables whose live blocks are non-contiguous pool
ids, the S = k+1 verify window, the engagement resolver + env knob,
the forced-fallback warning, the no-gather jaxpr pin, and the
kernel-on engine's token identity to static ``generate()`` with zero
decode/verify re-traces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import paged_flash_decode as pfd
from sparkdl_tpu.ops.flash_decode import flash_decode
from sparkdl_tpu.utils.platform import is_tpu_backend

#: the tp kernel gate's DEFAULTS are platform policy (auto = TPU only);
#: these tests pin the CPU side of it on the virtual 8-device mesh
cpu_mesh_only = pytest.mark.skipif(
    is_tpu_backend(), reason="pins the CPU-mesh defaults of the tp gate")


def _pool_and_tables(seed=0, *, b=4, h_kv=2, bs=8, mb=4, pool=13, d=16):
    """A deliberately adversarial layout: non-contiguous live pool ids,
    one slot parked entirely on trash block 0, mixed fill levels."""
    rng = np.random.RandomState(seed)
    k_pool = jnp.asarray(rng.randn(pool, h_kv, bs, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(pool, h_kv, bs, d), jnp.float32)
    tables = np.zeros((b, mb), np.int32)
    tables[0] = [7, 3, 11, 0]    # non-contiguous, trailing unallocated
    tables[1] = [2, 9, 0, 0]
    tables[2] = [5, 1, 10, 4]    # fully allocated
    tables[3] = 0                # parked on the trash block (idle slot)
    cur = jnp.asarray([17, 9, 31, 0], jnp.int32)
    pads = jnp.asarray([0, 3, 5, 0], jnp.int32)
    return k_pool, v_pool, jnp.asarray(tables), cur, pads


def _gather(pool, tables):
    """The dense per-slot view the pre-kernel primitives materialized
    (models.llama._gather_view, one leaf)."""
    v = pool[tables]                       # [B, MB, Hkv, bs, d]
    v = jnp.transpose(v, (0, 2, 1, 3, 4))
    return v.reshape(v.shape[0], v.shape[1], -1, v.shape[4])


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_decode_step_bitwise_equals_flash_on_gather_view(rep):
    k_pool, v_pool, tables, cur, pads = _pool_and_tables(rep)
    b, h_kv, bs, d = 4, 2, 8, 16
    q = jnp.asarray(np.random.RandomState(rep + 50).randn(
        b, h_kv * rep, 1, d), jnp.float32)
    got = pfd.paged_flash_decode(q, k_pool, v_pool, tables, cur, pads,
                                 interpret=True)
    want = flash_decode(q, _gather(k_pool, tables),
                        _gather(v_pool, tables), cur + 1, pads,
                        block_k=bs, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the trash-parked slot's output is finite garbage, never NaN (the
    # engine discards it, but a NaN would poison the o_proj matmul)
    assert np.isfinite(np.asarray(got[3])).all()


def test_verify_window_bitwise_equals_per_query_flash():
    """S = k+1 (the speculative verify window): query i of slot r must
    attend [pads[r], cur[r]+i] — bitwise the dense-flash run of each
    query column at its own fill level."""
    k_pool, v_pool, tables, cur, pads = _pool_and_tables(9)
    b, h_kv, rep, bs, d, s_q = 4, 2, 2, 8, 16, 4
    q = jnp.asarray(np.random.RandomState(77).randn(
        b, h_kv * rep, s_q, d), jnp.float32)
    got = pfd.paged_flash_decode(q, k_pool, v_pool, tables, cur, pads,
                                 interpret=True)
    kg, vg = _gather(k_pool, tables), _gather(v_pool, tables)
    want = jnp.concatenate(
        [flash_decode(q[:, :, i:i + 1], kg, vg, cur + i + 1, pads,
                      block_k=bs, interpret=True) for i in range(s_q)],
        axis=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the per-query causal offset is real: reversing the window's
    # queries changes the answer (each query sees a different prefix)
    flipped = pfd.paged_flash_decode(q[:, :, ::-1], k_pool, v_pool,
                                     tables, cur, pads, interpret=True)
    assert not np.allclose(np.asarray(flipped[2, :, -1]),
                           np.asarray(got[2, :, -1]), atol=1e-3)


def test_one_signature_serves_every_table_and_fill(monkeypatch):
    """Tables / fill indices / pads are traced operands: block
    allocation, grafts and refills must reuse ONE compiled program
    (the no-re-trace contract the slot primitives pin)."""
    k_pool, v_pool, tables, cur, pads = _pool_and_tables(3)
    q = jnp.asarray(np.random.RandomState(5).randn(4, 4, 1, 16),
                    jnp.float32)
    traces = []

    @jax.jit
    def step(tables, cur, pads):
        traces.append(1)
        return pfd.paged_flash_decode(q, k_pool, v_pool, tables, cur,
                                      pads, interpret=True)

    kg, vg = None, None
    for roll in range(3):
        t = jnp.roll(tables, roll, axis=0)
        c = jnp.roll(cur, roll)
        p = jnp.roll(pads, roll)
        got = step(t, c, p)
        want = flash_decode(q, _gather(k_pool, t), _gather(v_pool, t),
                            c + 1, p, block_k=8, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert len(traces) == 1


def test_supports_contract_and_shape_validation():
    assert pfd.supports(8) and pfd.supports(16) and pfd.supports(32)
    assert not pfd.supports(4)    # sublane misalignment
    assert not pfd.supports(12)   # not an 8-multiple
    k_pool, v_pool, tables, cur, pads = _pool_and_tables(1)
    q = jnp.zeros((4, 4, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="block_size"):
        pfd.paged_flash_decode(q, k_pool[:, :, :4], v_pool[:, :, :4],
                               tables, cur, pads, interpret=True)
    with pytest.raises(ValueError, match="tables"):
        pfd.paged_flash_decode(q, k_pool, v_pool, tables[:2], cur, pads,
                               interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        pfd.paged_flash_decode(jnp.zeros((4, 3, 1, 16)), k_pool, v_pool,
                               tables, cur, pads, interpret=True)


class TestResolverAndKnob:
    def test_auto_mode_mirrors_dense_flash_resolution(self, monkeypatch):
        from sparkdl_tpu.ops.flash_attention import flash_attention
        monkeypatch.delenv(pfd.PAGED_KERNEL_ENV, raising=False)
        assert pfd.paged_decode_fn_for(flash_attention) is \
            pfd.paged_flash_decode
        assert pfd.paged_decode_fn_for(None) is None
        # the global flash-decode ablation lever gates auto mode too
        monkeypatch.setenv("SPARKDL_FLASH_DECODE", "0")
        assert pfd.paged_decode_fn_for(flash_attention) is None

    def test_force_and_off(self, monkeypatch):
        monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, "1")
        assert pfd.paged_decode_fn_for(None) is pfd.paged_flash_decode
        monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, "0")
        from sparkdl_tpu.ops.flash_attention import flash_attention
        assert pfd.paged_decode_fn_for(flash_attention) is None
        assert pfd.kernel_mode() == "off"

    @cpu_mesh_only
    def test_mesh_routes_through_shard_map_gate(self, monkeypatch):
        from sparkdl_tpu.serving.backend import tp_mesh
        mesh = tp_mesh(2)
        # auto on CPU: the sharded dispatch is off (TPU-only default)
        monkeypatch.delenv(pfd.PAGED_KERNEL_ENV, raising=False)
        monkeypatch.setenv("SPARKDL_SERVE_TP_KERNEL", "0")
        assert pfd.paged_decode_fn_for(None, mesh) is None
        # the tp ablation beats force: a leftover forced paged knob
        # must not contaminate the dense-attention tp baseline leg
        # (explicit =0 is the documented override — no warning)
        monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, "1")
        monkeypatch.setattr(pfd, "_warned_fallback", set())
        assert pfd.paged_decode_fn_for(None, mesh) is None
        assert not pfd._warned_fallback
        # but force + tp with the dispatch merely DEFAULTED off (auto
        # on CPU) must warn — a forced knob never densifies silently
        monkeypatch.delenv("SPARKDL_SERVE_TP_KERNEL")
        assert pfd.paged_decode_fn_for(None, mesh) is None
        assert any("sharded tp dispatch" in r for r in pfd._warned_fallback)
        monkeypatch.delenv(pfd.PAGED_KERNEL_ENV)
        # forced on: a head-sharded wrapper around the kernel
        monkeypatch.setenv("SPARKDL_SERVE_TP_KERNEL", "1")
        fn = pfd.paged_decode_fn_for(None, mesh)
        assert fn is not None and fn.__wrapped__ is pfd.paged_flash_decode

    @cpu_mesh_only
    def test_dense_decode_fn_for_mesh_gating(self, monkeypatch):
        from sparkdl_tpu.ops import flash_decode as fd
        from sparkdl_tpu.serving.backend import tp_mesh
        mesh = tp_mesh(2)
        monkeypatch.setenv(fd.TP_KERNEL_ENV, "0")
        assert fd.decode_fn_for(None, mesh) is None
        monkeypatch.setenv(fd.TP_KERNEL_ENV, "1")
        fn = fd.decode_fn_for(None, mesh)
        assert fn is not None and fn.__wrapped__ is fd.flash_decode
        # the global ablation lever still wins under a mesh
        monkeypatch.setenv("SPARKDL_FLASH_DECODE", "0")
        assert fd.decode_fn_for(None, mesh) is None


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two devices")
def test_head_sharded_kernel_matches_unsharded():
    """shard_map over the tp head axis must be a pure layout change:
    per-head attention needs no collective, so the sharded dispatch is
    bitwise the single-device kernel."""
    from sparkdl_tpu.parallel.sharding import head_sharded_kernel
    from sparkdl_tpu.serving.backend import tp_mesh
    k_pool, v_pool, tables, cur, pads = _pool_and_tables(13)
    q = jnp.asarray(np.random.RandomState(29).randn(4, 4, 1, 16),
                    jnp.float32)
    want = pfd.paged_flash_decode(q, k_pool, v_pool, tables, cur, pads,
                                  interpret=True)
    sharded = head_sharded_kernel(pfd.paged_flash_decode, tp_mesh(2))
    got = jax.jit(lambda *a: sharded(*a, interpret=True))(
        q, k_pool, v_pool, tables, cur, pads)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_forced_fallback_warns_once(monkeypatch, caplog):
    """SPARKDL_SERVE_PAGED_KERNEL=1 with an unsupported block size must
    stand down to the gather view with ONE warning — silently changing
    the HBM profile the knob pinned is the hazard (ISSUE 15
    satellite)."""
    import logging

    from sparkdl_tpu.models import llama as L
    monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, "1")
    monkeypatch.setattr(pfd, "_warned_fallback", set())
    cfg = L.LlamaConfig.tiny()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    pool = L.init_paged_pool(model, 7, 4)  # block_size 4: unsupported
    tables = jnp.zeros((2, 3), jnp.int32)
    zeros = jnp.zeros((2,), jnp.int32)
    with caplog.at_level(logging.WARNING,
                         logger="sparkdl_tpu.ops.paged_flash_decode"):
        tok, pool = L.paged_slot_decode_step(
            model, variables["params"], pool, tables, zeros, zeros,
            zeros, jax.random.PRNGKey(0))
        warns = [r for r in caplog.records
                 if "paged flash-decode" in r.getMessage()]
        # once per reason host-side, not once per layer per trace
        assert len(warns) == 1
        assert "block_size 4" in warns[0].getMessage()
        # a second step (same signature, no re-trace; and even a fresh
        # trace of the same reason) stays silent
        tok, pool = L.paged_slot_decode_step(
            model, variables["params"], pool, tables, zeros, zeros,
            zeros, jax.random.PRNGKey(1))
        assert len([r for r in caplog.records
                    if "paged flash-decode" in r.getMessage()]) == 1


def test_kernel_engagement_drops_the_gather(monkeypatch):
    """The acceptance jaxpr pin: with the kernel engaged the lowered
    decode step holds NO materialized [S, Hkv, max_blocks·bs, hd]
    view; with it off, the per-layer gather view is exactly there.
    (Distinct slot counts per leg — the jit cache keys on traced
    shapes, not the env knob, so same-signature relowers would reuse
    the first trace.)"""
    from sparkdl_tpu.models import llama as L
    cfg = L.LlamaConfig.tiny()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    mb, bs = 3, 8
    pool = L.init_paged_pool(model, 9, bs)
    key = jax.random.PRNGKey(0)
    for env_val, slots, expect_gather in (("1", 3, False), ("0", 5, True)):
        monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, env_val)
        tables = jnp.zeros((slots, mb), jnp.int32)
        zeros = jnp.zeros((slots,), jnp.int32)
        view = (f"tensor<{slots}x{cfg.num_kv_heads}x{mb * bs}x"
                f"{cfg.head_dim}xf32>")
        txt = L.paged_slot_decode_step.lower(
            model, variables["params"], pool, tables, zeros, zeros,
            zeros, key).as_text()
        assert (view in txt) == expect_gather, (env_val, view)
        # the verify window composes with the same dispatch
        toks = jnp.zeros((slots, 3), jnp.int32)
        txt = L.paged_slot_verify_step.lower(
            model, variables["params"], pool, tables, toks, zeros,
            zeros).as_text()
        assert (view in txt) == expect_gather, (env_val, "verify")


class TestKernelOnEngine:
    def test_token_identity_and_zero_retraces(self, monkeypatch):
        """The kernel-engaged paged engine (forced — CPU runs the same
        kernel interpreted) through chunked prefill × radix grafts ×
        speculation: greedy streams bit-identical to static
        ``generate()``, zero decode/verify re-traces after warmup.
        Odd slot count / max_len keep the signatures private to this
        test — the process-global jit cache would otherwise hand the
        engine a program traced with the kernel off."""
        from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE
        from sparkdl_tpu.models import llama as L
        from sparkdl_tpu.serving import GenerationEngine
        from sparkdl_tpu.serving.draft import HistoryDraft

        monkeypatch.setenv(pfd.PAGED_KERNEL_ENV, "1")
        cfg = L.LlamaConfig.tiny()
        model = L.LlamaModel(cfg)
        variables = model.init(jax.random.PRNGKey(0),
                               np.zeros((1, 4), np.int32))
        rng = np.random.RandomState(23)
        max_len, new = 40, 6
        head = rng.randint(0, cfg.vocab_size, 16).tolist()  # 2 blocks
        prompts = [head + rng.randint(0, cfg.vocab_size, n).tolist()
                   for n in (3, 7)]
        ids, lens = L.left_pad_prompts(prompts)
        out = np.asarray(L.generate(model, variables, np.asarray(ids),
                                    new, pad_lens=np.asarray(lens),
                                    pad_to=max_len))
        refs = [out[i][int(lens[i]) + len(p):].tolist()
                for i, p in enumerate(prompts)]

        prov = HistoryDraft()
        for p, r in zip(prompts, refs):
            prov.observe(p, r)  # high-acceptance verify windows
        eng = GenerationEngine.from_model(
            model, variables, num_slots=3, max_len=max_len,
            block_size=8, prefill_chunk=8, spec_k=3,
            draft_provider=prov)
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run_until_idle()
        assert [h.result(1) for h in hs] == refs
        assert eng.snapshot()["spec_verifies"] >= 1
        sig_d = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")
        sig_v = GLOBAL_COMPILE_CACHE.signatures("serve_verify_step")
        # second wave: grafts the shared head, refills other slots —
        # and must not re-trace the kernel-engaged programs
        hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        eng.run_until_idle()
        assert [h.result(1) for h in hs] == refs
        assert GLOBAL_COMPILE_CACHE.signatures(
            "serve_decode_step") == sig_d
        assert GLOBAL_COMPILE_CACHE.signatures(
            "serve_verify_step") == sig_v


@pytest.mark.skipif(
    not is_tpu_backend(),
    reason="compiled-mode kernel needs a real TPU "
           "(run with SPARKDL_TEST_PLATFORM=tpu)")
@pytest.mark.parametrize("kv_dtype", [None, "int8", "fp8"])
@pytest.mark.parametrize("block_size", [8, 16, 32])
def test_compiled_paged_flash_decode_on_tpu(block_size, kv_dtype):
    """COMPILED (non-interpret) kernel on the chip, at the shapes the
    engine serves (head_dim 128, GQA 16/8, bf16 queries, 8 slots of
    2048): the decode step and the S=5 verify window against the dense
    reference, over a float pool and over int8/fp8 codes + scale plane,
    at every block size ``support_reason()`` admits — what it refuses
    is skipped by name, so this test is the record of what lowers."""
    import chip_smoke

    reason = pfd.support_reason(block_size, kv_dtype)
    if reason is not None:
        pytest.skip(reason)
    checks = chip_smoke.check_paged_flash_decode(
        np.random.RandomState(block_size), interpret=False, slots=8,
        heads=16, kv_heads=8, head_dim=128, max_len=2048,
        block_size=block_size, kv_dtype=kv_dtype, windows=(1, 5))
    for name, rec in checks.items():
        assert rec["max_err"] <= chip_smoke.KERNEL_ATOL, (name, rec)
