"""Tensor-parallel serving (ISSUE 14): the engine spanning a
``Mesh(('tp',))`` with head-sharded weights + KV cache/pool.

Fast tier: SpecLayout/divisibility units, the launcher's topology-aware
placement (jax-free), the tp-mesh offset contract, the tp=1
exact-existing-path pin (types + compile-cache signature equality), and
ONE lean tp=2 composition identity test (paging + radix graft + chunked
prefill + speculation + preemption-resume vs static ``generate()``,
per-device KV bytes at 1/2, zero re-traces, tp gauges). The full
degree × layout matrix runs behind ``slow``.

The suite rides the conftest-forced 8-virtual-device CPU mesh — the
same surface the driver's multichip dryrun validates on.
"""

import numpy as np
import pytest

from sparkdl_tpu.runner.launcher import _tp_degree, tp_placement_env
from sparkdl_tpu.serving import GenerationEngine


def _tiny_model():
    """LlamaConfig.tiny(): num_kv_heads=2 — exact head split at tp=2."""
    import jax

    from sparkdl_tpu.models import llama as L
    cfg = L.LlamaConfig.tiny()
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           np.zeros((1, 4), np.int32))
    return cfg, model, variables


def _tp4_model():
    """num_kv_heads=4 — exact head split at every degree in {1,2,4}."""
    import jax

    from sparkdl_tpu.models import llama as L
    cfg = L.LlamaConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, num_kv_heads=4,
                        intermediate_size=256, rope_theta=10000.0)
    model = L.LlamaModel(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           np.zeros((1, 4), np.int32))
    return cfg, model, variables


def _static_refs(model, variables, prompts, new, max_len=64):
    from sparkdl_tpu.models import llama as L
    ids, lens = L.left_pad_prompts(prompts)
    out = np.asarray(L.generate(model, variables, np.asarray(ids), new,
                                pad_lens=np.asarray(lens),
                                pad_to=max_len))
    return [out[i][int(lens[i]) + len(p):].tolist()
            for i, p in enumerate(prompts)]


def _global_kv_bytes(cache):
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache)
               if getattr(x, "ndim", 0) == 4)


class TestSpecLayout:
    def test_layout_fields_and_head_validation(self):
        from sparkdl_tpu.parallel import serving_tp_layout
        lay = serving_tp_layout(2)
        assert lay.degree == 2 and lay.axis == "tp"
        assert tuple(lay.kv_cache) == (None, "tp", None, None)
        assert tuple(lay.replicated) == ()

        class C:
            num_kv_heads = 2
            num_heads = 4

        serving_tp_layout(2, C)  # exact split: fine
        serving_tp_layout(1, C)  # degenerate: always fine
        with pytest.raises(ValueError, match="num_kv_heads"):
            serving_tp_layout(4, C)
        with pytest.raises(ValueError, match="tp must be >= 1"):
            serving_tp_layout(0)

    def test_divisible_rules_drop_uneven_axes(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from sparkdl_tpu.parallel import divisible_rules, make_rules
        mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
        base = make_rules([(r"odd_vocab", P(None, "tp")),
                           (r"kernel", P(None, "tp"))])
        rules = divisible_rules(base, mesh)
        # 5 % 2 != 0: the tp axis is dropped (replicated), not an error
        assert rules(("odd_vocab",), np.zeros((4, 5))) == P(None, None)
        assert rules(("kernel",), np.zeros((4, 6))) == P(None, "tp")
        # non-matching leaves keep the empty default untouched
        assert rules(("bias",), np.zeros((3,))) == P()


class TestTpPlacement:
    """Launcher topology-aware placement — jax-free policy units."""

    def test_tp1_adds_nothing(self):
        assert tp_placement_env(0, 1, {"JAX_PLATFORMS": "cpu"}) == {}

    def test_cpu_forces_per_rank_virtual_devices(self):
        add = tp_placement_env(2, 4, {"JAX_PLATFORMS": "cpu"})
        assert "--xla_force_host_platform_device_count=4" in \
            add["XLA_FLAGS"]
        assert add["SPARKDL_TP_DEVICE_OFFSET"] == "0"

    def test_cpu_respects_caller_pinned_flag(self):
        env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=16"}
        add = tp_placement_env(0, 4, env)
        assert "XLA_FLAGS" not in add

    def test_fallback_platform_list_routes_to_accelerator_branch(self):
        # JAX_PLATFORMS="tpu,cpu" initializes the TPU backend (first
        # entry wins), so placement must pin chip visibility — the old
        # substring test would have given every rank the same chips
        add = tp_placement_env(1, 2, {"JAX_PLATFORMS": "tpu,cpu"})
        assert add["TPU_VISIBLE_CHIPS"] == "2,3"
        assert "XLA_FLAGS" not in add
        # and "cpu,tpu" (cpu first) is genuinely the CPU regime
        add = tp_placement_env(1, 2, {"JAX_PLATFORMS": "cpu,tpu"})
        assert "TPU_VISIBLE_CHIPS" not in add
        assert "host_platform_device_count=2" in add["XLA_FLAGS"]

    def test_accelerator_pins_disjoint_chip_groups(self):
        a0 = tp_placement_env(0, 4, {})
        a1 = tp_placement_env(1, 4, {})
        assert a0["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert a1["TPU_VISIBLE_CHIPS"] == "4,5,6,7"
        # visibility IS the placement: each rank meshes from offset 0
        assert a1["SPARKDL_TP_DEVICE_OFFSET"] == "0"

    def test_caller_pinned_visibility_uses_inprocess_offsets(self):
        env = {"TPU_VISIBLE_CHIPS": "0,1,2,3,4,5,6,7"}
        add = tp_placement_env(1, 4, env)
        assert "TPU_VISIBLE_CHIPS" not in add
        assert add["SPARKDL_TP_DEVICE_OFFSET"] == "4"

    def test_explicit_offset_never_overridden(self):
        env = {"TPU_VISIBLE_CHIPS": "0,1", "SPARKDL_TP_DEVICE_OFFSET": "6"}
        assert tp_placement_env(1, 2, env) == {}

    def test_plain_gang_on_a_chip_host_is_refused_before_spawn(
            self, monkeypatch, tmp_path):
        """A chip belongs to one process: np>1 plain ranks on a host that
        exposes TPU device nodes would all open the same chips, all but
        one dying on a 'busy' error that the taxonomy calls retryable —
        launch()/supervise() refuse BEFORE spawning anything, naming the
        single-controller form. CPU gangs and a tp gang that fits its
        disjoint chip groups pass."""
        from sparkdl_tpu.runner import launcher
        marker = tmp_path / "spawned"
        script = tmp_path / "w.py"
        script.write_text(f"open({str(marker)!r}, 'w').close()\n")
        monkeypatch.setattr(launcher, "local_tpu_chips", lambda: 4)
        for fn in (launcher.launch, launcher.supervise):
            with pytest.raises(ValueError, match=r"XlaRunner\(np=-1\)"):
                fn(str(script), np=2, env={"JAX_PLATFORMS": "tpu,cpu"})
        assert not marker.exists()
        refuse = launcher._refuse_shared_chips
        refuse(1, {"JAX_PLATFORMS": ""})            # one rank owns the host
        refuse(2, {"JAX_PLATFORMS": "cpu"})         # CPU gang
        refuse(2, {"JAX_PLATFORMS": "", "SPARKDL_SERVE_TP": "2"})
        with pytest.raises(ValueError, match="np\\*tp <= 4"):
            refuse(4, {"JAX_PLATFORMS": "", "SPARKDL_SERVE_TP": "2"})
        # no TPU device nodes (this sandbox): nothing to refuse
        monkeypatch.setattr(launcher, "local_tpu_chips", lambda: 0)
        refuse(2, {"JAX_PLATFORMS": ""})

    def test_tp_degree_parse(self):
        assert _tp_degree({"SPARKDL_SERVE_TP": "4"}) == 4
        assert _tp_degree({}) == 0
        assert _tp_degree({"SPARKDL_SERVE_TP": ""}) == 0
        # a gang env that ASKS for tp with a value we cannot honor
        # fails the spawn loudly (ranks fighting over chips is worse)
        with pytest.raises(ValueError, match="not an integer"):
            _tp_degree({"SPARKDL_SERVE_TP": "nope"})
        with pytest.raises(ValueError, match="negative"):
            _tp_degree({"SPARKDL_SERVE_TP": "-2"})

    def test_ambient_knob_never_rewrites_an_unrelated_gang(
            self, tmp_path, monkeypatch):
        """A shell-exported SPARKDL_SERVE_TP must NOT inject chip
        visibility into a gang that did not ask for tp placement in
        its OWN env= — only the caller's explicit dict gates it."""
        import json

        from sparkdl_tpu.runner import launcher
        worker = tmp_path / "env_worker.py"
        worker.write_text(
            "import json, os, sys\n"
            "rank = os.environ['SPARKDL_PROCESS_ID']\n"
            "json.dump({k: os.environ.get(k) for k in\n"
            "           ('TPU_VISIBLE_CHIPS', 'SPARKDL_TP_DEVICE_OFFSET')},\n"
            "          open(sys.argv[1] + f'/rank{rank}.json', 'w'))\n")
        monkeypatch.setenv("SPARKDL_SERVE_TP", "2")  # ambient only
        launcher.launch(str(worker), np=1, args=[str(tmp_path)],
                        env={"JAX_PLATFORMS": ""}, timeout_s=60.0,
                        capture=True)
        got = json.load(open(tmp_path / "rank0.json"))
        assert got["TPU_VISIBLE_CHIPS"] is None
        assert got["SPARKDL_TP_DEVICE_OFFSET"] is None
        # the same knob in the CALLER's env= dict does gate placement
        launcher.launch(str(worker), np=1, args=[str(tmp_path)],
                        env={"JAX_PLATFORMS": "", "SPARKDL_SERVE_TP": "2"},
                        timeout_s=60.0, capture=True)
        got = json.load(open(tmp_path / "rank0.json"))
        assert got["TPU_VISIBLE_CHIPS"] == "0,1"


class TestTpMesh:
    def test_offset_env_and_bounds(self, monkeypatch):
        from sparkdl_tpu.serving.backend import tp_mesh
        m = tp_mesh(2)
        assert int(m.shape["tp"]) == 2
        assert [d.id for d in m.devices.flat] == [0, 1]
        monkeypatch.setenv("SPARKDL_TP_DEVICE_OFFSET", "6")
        m2 = tp_mesh(2)
        assert [d.id for d in m2.devices.flat] == [6, 7]
        monkeypatch.setenv("SPARKDL_TP_DEVICE_OFFSET", "7")
        with pytest.raises(ValueError, match="visible"):
            tp_mesh(2)
        monkeypatch.delenv("SPARKDL_TP_DEVICE_OFFSET")
        with pytest.raises(ValueError, match=">= 1"):
            tp_mesh(0)


class TestTp1ExactExistingPath:
    """The ISSUE 14 zero-overhead pin: tp<=1 must construct the EXACT
    single-device backends — same classes (not subclasses), no mesh,
    and byte-for-byte the same compiled program set."""

    def test_tp1_constructs_base_classes(self):
        from sparkdl_tpu.serving.backend import (
            LlamaSlotBackend, PagedLlamaSlotBackend)
        cfg, model, variables = _tiny_model()
        eng = GenerationEngine.from_model(model, variables, num_slots=2,
                                          max_len=32, tp=1)
        assert type(eng.backend) is LlamaSlotBackend
        assert eng.tp_degree == 1
        assert not hasattr(eng.backend, "mesh")
        engp = GenerationEngine.from_model(model, variables, num_slots=2,
                                           max_len=32, block_size=8,
                                           tp=1)
        assert type(engp.backend) is PagedLlamaSlotBackend
        # the per-device byte accounting exists on the base classes too
        # (the whole cache on one device)
        assert eng.kv_pool_device_bytes == \
            _global_kv_bytes(eng.backend.cache)

    def test_explicit_mesh_without_tp_is_inferred_not_dropped(self):
        """A caller who built the Mesh(('tp',)) themselves but forgot
        tp= must get a tensor-parallel engine of the mesh's extent —
        never a silent single-device engine with the full unsharded
        KV footprint."""
        from sparkdl_tpu.serving.backend import (
            TensorParallelLlamaSlotBackend, tp_mesh)
        cfg, model, variables = _tiny_model()
        eng = GenerationEngine.from_model(model, variables, num_slots=2,
                                          max_len=32, mesh=tp_mesh(2))
        assert type(eng.backend) is TensorParallelLlamaSlotBackend
        assert eng.tp_degree == 2

    def test_tp_mesh_disagreement_and_bad_env_raise(self, monkeypatch):
        """tp= disagreeing with the passed mesh's extent would validate
        heads against one degree and shard over another (wrong
        per-device budget math, wrong observables) — reject it; and a
        malformed SPARKDL_SERVE_TP raises instead of silently losing
        tensor parallelism (the SPARKDL_SERVE_SPEC_DRAFT rule)."""
        from sparkdl_tpu.serving.backend import tp_mesh
        cfg, model, variables = _tiny_model()
        with pytest.raises(ValueError, match="disagrees"):
            GenerationEngine.from_model(model, variables, num_slots=2,
                                        max_len=32, tp=4,
                                        mesh=tp_mesh(2))
        # an EXPLICIT tp=1 (the pinned single-device baseline) is a
        # disagreement with a 2-device mesh, not an inference input
        with pytest.raises(ValueError, match="disagrees"):
            GenerationEngine.from_model(model, variables, num_slots=2,
                                        max_len=32, tp=1,
                                        mesh=tp_mesh(2))
        monkeypatch.setenv("SPARKDL_SERVE_TP", "four")
        with pytest.raises(ValueError, match="not an integer"):
            GenerationEngine.from_model(model, variables, num_slots=2,
                                        max_len=32)
        monkeypatch.setenv("SPARKDL_SERVE_TP", "-4")
        with pytest.raises(ValueError, match="negative"):
            GenerationEngine.from_model(model, variables, num_slots=2,
                                        max_len=32)

    def test_scrub_serving_env_removes_and_returns(self, monkeypatch):
        from sparkdl_tpu.serving.engine import scrub_serving_env
        monkeypatch.setenv("SPARKDL_SERVE_KV_POOL_MB", "64")
        monkeypatch.setenv("SPARKDL_TP_DEVICE_OFFSET", "4")
        monkeypatch.setenv("SPARKDL_METRICS_DIR", "/tmp/keep")
        import os
        removed = scrub_serving_env()
        assert removed == {"SPARKDL_SERVE_KV_POOL_MB": "64",
                           "SPARKDL_TP_DEVICE_OFFSET": "4"}
        assert "SPARKDL_SERVE_KV_POOL_MB" not in os.environ
        assert os.environ["SPARKDL_METRICS_DIR"] == "/tmp/keep"
        os.environ.update(removed)  # restorable (monkeypatch undoes)
        # dict form: scrubs a COPY the caller owns, same key policy
        env = {"SPARKDL_SERVE_TP": "2", "OTHER": "x"}
        assert scrub_serving_env(env) == {"SPARKDL_SERVE_TP": "2"}
        assert env == {"OTHER": "x"}

    def test_tp1_signature_equality_with_plain_construction(self):
        """Run the same workload through ``from_model(tp=1)`` and a
        plain-constructed backend: the compile-cache signature sets
        must not grow — tp=1 is the same program, not a wrapper."""
        from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE
        from sparkdl_tpu.serving.backend import LlamaSlotBackend
        cfg, model, variables = _tiny_model()
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, cfg.vocab_size, 5).tolist()

        eng = GenerationEngine.from_model(
            model, variables, num_slots=2, max_len=32, prefill_chunk=8,
            prefix_cache_mb=0, tp=1)
        h = eng.submit(prompt, max_new_tokens=3)
        eng.run_until_idle()
        sig_d = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")
        sig_c = GLOBAL_COMPILE_CACHE.signatures("serve_prefill_chunk")

        eng2 = GenerationEngine(
            LlamaSlotBackend(model, variables, 2, 32,
                             prefix_cache_bytes=0),
            prefill_chunk=8)
        h2 = eng2.submit(prompt, max_new_tokens=3)
        eng2.run_until_idle()
        assert h2.result(1) == h.result(1)
        assert GLOBAL_COMPILE_CACHE.signatures(
            "serve_decode_step") == sig_d
        assert GLOBAL_COMPILE_CACHE.signatures(
            "serve_prefill_chunk") == sig_c


class TestTpEngineOnCpu:
    def test_tp2_composition_identity_lean(self):
        """The ISSUE 14 lean fast test: ONE tp=2 engine through paged
        block tables × radix graft × chunked prefill × speculation ×
        mid-decode preemption-resume — greedy output bit-identical to
        static ``generate()``, per-device KV pool bytes exactly 1/2 of
        the pool's global bytes, zero decode/verify re-traces after
        warmup, and the tp gauges landing when the plane is armed.
        (The full degree × layout matrix is the ``slow`` twin below.)"""
        from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE
        from sparkdl_tpu.runner import telemetry
        from sparkdl_tpu.serving.backend import (
            TensorParallelPagedLlamaSlotBackend)
        from sparkdl_tpu.serving.draft import HistoryDraft

        cfg, model, variables = _tiny_model()
        rng = np.random.RandomState(7)
        max_len, new = 64, 10
        head = rng.randint(0, cfg.vocab_size, 16).tolist()  # 2 blocks
        pa = head + rng.randint(0, cfg.vocab_size, 3).tolist()
        pb = head + rng.randint(0, cfg.vocab_size, 6).tolist()
        refs = _static_refs(model, variables, [pa, pb], new, max_len)

        prov = HistoryDraft()
        prov.observe(pa, refs[0])  # warm retrieval: verify windows run
        prov.observe(pb, refs[1])  # with high acceptance every step
        base_d = GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")
        base_v = GLOBAL_COMPILE_CACHE.signatures("serve_verify_step")
        telemetry.reset()
        telemetry.start()
        try:
            eng = GenerationEngine.from_model(
                model, variables, num_slots=2, max_len=max_len,
                prefill_chunk=8, block_size=8, prefill_budget=16,
                spec_k=3, draft_provider=prov, tp=2)
            assert type(eng.backend) is TensorParallelPagedLlamaSlotBackend
            assert eng.paged and eng.tp_degree == 2
            ha = eng.submit(pa, max_new_tokens=new)
            eng.step()  # 2 of pa's 3 chunks (budget 16)
            eng.step()  # final chunk + first token
            eng.step()  # >= 1 speculative verify
            # NOTE: signatures are keyed on traced shapes, which other
            # tests' engines may share — "a verify ran" is pinned via
            # engine stats, the signature set only via non-growth below.
            sig_v = GLOBAL_COMPILE_CACHE.signatures("serve_verify_step")
            assert eng.stats["spec_verifies"] >= 1
            assert ha.state == "running" and 0 < len(ha.tokens) < new
            eng._preempt_newest([(ha.slot, ha)])
            hb = eng.submit(pb, max_new_tokens=new)  # grafts pa's head
            eng.run_until_idle()
            assert ha.result(1) == refs[0]  # resumed, bit-exact
            assert hb.result(1) == refs[1]  # grafted, bit-exact
            snap = eng.snapshot()
            assert snap["preemptions"] == 1
            assert snap["spec_verifies"] >= 1
            assert (snap.get("prefix_cache") or {}).get("hits", 0) >= 1
            # allocation/graft/preempt/resume never re-trace under tp:
            # this engine adds at most ONE decode and at most ONE
            # verify signature over its whole lifetime (the cache is
            # process-global, so compare deltas — a second new
            # signature would be the re-trace regression), and the
            # preempt-resume half adds NONE at all
            assert GLOBAL_COMPILE_CACHE.signatures(
                "serve_decode_step") - base_d <= 1
            assert GLOBAL_COMPILE_CACHE.signatures(
                "serve_verify_step") - base_v <= 1
            assert GLOBAL_COMPILE_CACHE.signatures(
                "serve_verify_step") == sig_v  # none after preempt
            # per-device pool bytes: exactly half the global pool, and
            # exported through snapshot + the armed-plane gauges
            total = _global_kv_bytes(eng.backend.cache)
            assert eng.kv_pool_device_bytes * 2 == total
            assert snap["tp_degree"] == 2
            assert snap["kv_pool_device_bytes"] == \
                eng.kv_pool_device_bytes
            reg = telemetry.registry()
            assert reg.gauge("serving_tp_degree").snapshot()["max"] == 2
            assert reg.gauge(
                "serving_kv_pool_device_bytes").snapshot()["value"] == \
                eng.kv_pool_device_bytes
            # the live inspector names the degree + per-device bytes
            dbg = eng.debug_state()
            assert dbg["tp_degree"] == 2
            assert dbg["kv_pool_device_bytes"] == \
                eng.kv_pool_device_bytes
        finally:
            telemetry.reset()

    def test_tp2_sharded_decode_kernels_token_identity(self, monkeypatch):
        """ISSUE 15: with ``SPARKDL_SERVE_TP_KERNEL=1`` (forced — auto
        is TPU-only) the tp engines stop riding dense cache attention:
        the paged backend dispatches the paged flash-decode kernel and
        the unpaged backend the dense flash-decode kernel, each under
        ``shard_map`` over the head axis — and the greedy streams stay
        bit-identical to static ``generate()``. Odd slot counts keep
        the jit signatures private to this test (the cache keys on
        traced shapes, not the env knob — a kernel-off program traced
        by the other tp tests must not be reused here)."""
        monkeypatch.setenv("SPARKDL_SERVE_TP_KERNEL", "1")
        cfg, model, variables = _tiny_model()
        rng = np.random.RandomState(19)
        new = 6
        prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
                   for n in (5, 11, 8)]
        refs = _static_refs(model, variables, prompts, new, 128)

        # paged: block_size 8 passes the paged supports(); auto mode
        # engages because the sharded dense dispatch is forced on
        engp = GenerationEngine.from_model(
            model, variables, num_slots=3, max_len=48, block_size=8,
            prefill_chunk=8, tp=2)
        hs = [engp.submit(p, max_new_tokens=new) for p in prompts]
        engp.run_until_idle()
        assert [h.result(1) for h in hs] == refs
        # unpaged: max_len 128 = the dense kernel's KV-block multiple
        engd = GenerationEngine.from_model(
            model, variables, num_slots=3, max_len=128,
            prefill_chunk=8, tp=2)
        hs = [engd.submit(p, max_new_tokens=new) for p in prompts]
        engd.run_until_idle()
        assert [h.result(1) for h in hs] == refs

    def test_tp_int8_token_parity(self, monkeypatch):
        """ISSUE 18: the int8-quantized paged engine (int8 KV codes +
        scale plane + int8 projection weights) is degree-invariant —
        tp ∈ {1, 2} and kernel-off vs kernel-forced all emit the SAME
        greedy streams. int8 may legitimately differ from the f32
        static reference; it may NOT differ across shardings of the
        same quantized program (the scale plane sharding with heads and
        the absmax channel scales sharding with their projections are
        exactly what this pins). Odd slot count keeps jit signatures
        private (the shape-keyed cache rule from the kernel test)."""
        import jax

        cfg, model, variables = _tiny_model()
        rng = np.random.RandomState(23)
        new = 8
        prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
                   for n in (4, 9, 13)]

        def run(tp, kernel):
            # one knob per backend family: paged single-device vs the
            # shard_map head-sharded dispatch under tp
            monkeypatch.setenv("SPARKDL_SERVE_PAGED_KERNEL", kernel)
            monkeypatch.setenv("SPARKDL_SERVE_TP_KERNEL", kernel)
            eng = GenerationEngine.from_model(
                model, variables, num_slots=3, max_len=48, block_size=8,
                prefill_chunk=8, kv_dtype="int8", weight_dtype="int8",
                tp=tp)
            hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
            eng.run_until_idle()
            return [h.result(1) for h in hs], eng

        base, _ = run(1, "0")
        assert all(len(s) == new for s in base)
        for tp, kernel in ((1, "1"), (2, "0"), (2, "1")):
            got, eng = run(tp, kernel)
            assert got == base, (tp, kernel)
        # the last engine is tp=2 kernel-forced: codes halve per device
        # and the scale plane shards over its head axis alongside them
        # (kv_pool_device_bytes counts BOTH — codes + the 3-dim plane)
        import jax.tree_util as jtu
        plane_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jtu.tree_leaves(eng.backend.cache)
            if getattr(x, "ndim", 0) == 3)
        assert plane_bytes > 0
        assert eng.kv_pool_device_bytes * 2 == \
            _global_kv_bytes(eng.backend.cache) + plane_bytes
        plane = eng.backend.cache["layer_0"]["attn"]["kv_scale"]
        # jax normalizes away the trailing None of P(None, 'tp', None)
        assert plane.sharding.spec == \
            jax.sharding.PartitionSpec(None, "tp")
        ps = eng.backend.pool_stats()
        assert ps["kv_dtype"] == "int8"
        assert ps["kv_scale_bytes_per_block"] > 0

    def test_tp_gauges_zero_registration_when_plane_off(self):
        from sparkdl_tpu.runner import telemetry
        from sparkdl_tpu.serving import StubBackend
        assert not telemetry.enabled()
        eng = GenerationEngine(StubBackend(2, 32), prefill_chunk=8)
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run_until_idle()
        assert h.result(1)
        assert eng.tp_degree == 1  # duck-typed default
        assert eng.kv_pool_device_bytes is None  # stub has no pool
        assert telemetry.registry().snapshot()["gauges"] == {}

    @pytest.mark.slow
    def test_tp_full_matrix(self):
        """The full composition matrix: tp ∈ {2, 4} × {paged+spec,
        unpaged no-spec}, every stream identical to the tp=1 engine
        AND to static generate(); per-device bytes at 1/tp."""
        from sparkdl_tpu.serving.draft import HistoryDraft

        cfg, model, variables = _tp4_model()
        rng = np.random.RandomState(5)
        max_len, new = 64, 8
        head = rng.randint(0, cfg.vocab_size, 16).tolist()
        prompts = [head + rng.randint(0, cfg.vocab_size, n).tolist()
                   for n in (3, 6, 11)]
        refs = _static_refs(model, variables, prompts, new, max_len)

        for paged in (True, False):
            streams, dev_bytes = {}, {}
            for tp in (1, 2, 4):
                kw = dict(num_slots=2, max_len=max_len, prefill_chunk=8,
                          tp=tp)
                if paged:
                    prov = HistoryDraft()
                    for p, r in zip(prompts, refs):
                        prov.observe(p, r)
                    kw.update(block_size=8, prefill_budget=16, spec_k=3,
                              draft_provider=prov)
                eng = GenerationEngine.from_model(model, variables, **kw)
                hs = [eng.submit(p, max_new_tokens=new) for p in prompts]
                eng.run_until_idle()
                streams[tp] = [h.result(1) for h in hs]
                dev_bytes[tp] = eng.kv_pool_device_bytes
            assert streams[1] == refs, f"paged={paged}: tp=1 != static"
            assert streams[2] == refs and streams[4] == refs, \
                f"paged={paged}: tp engine diverged"
            assert dev_bytes[2] * 2 == dev_bytes[1]
            assert dev_bytes[4] * 4 == dev_bytes[1]

    def test_per_device_kv_pool_mb_budget_buys_tp_times_blocks(self):
        """SPARKDL_SERVE_KV_POOL_MB is a PER-DEVICE budget under tp:
        the same MB figure must buy ~tp× the pool blocks (each device
        holds 1/tp of every block)."""
        cfg, model, variables = _tp4_model()
        mb = 0.25
        eng1 = GenerationEngine.from_model(
            model, variables, num_slots=2, max_len=32, block_size=8,
            kv_pool_mb=mb, tp=1)
        eng2 = GenerationEngine.from_model(
            model, variables, num_slots=2, max_len=32, block_size=8,
            kv_pool_mb=mb, tp=2)
        b1 = eng1.backend.pool_blocks
        b2 = eng2.backend.pool_blocks
        assert b2 >= 2 * b1 - 1, (b1, b2)  # -1: trash-block rounding
        # and the per-device bytes stay inside the budget either way
        assert eng2.kv_pool_device_bytes <= mb * 2 ** 20
