"""chip_smoke.py on the CPU: the command refuses, the phases run tiny.

The no-argument command line is the chip contract (a TPU or a non-zero
exit); what tier-1 can hold is (a) that it refuses the CPU quickly and
prints no result, (b) that every phase function runs the real entry points
at a tiny size on the virtual CPU mesh — kernels interpreted by explicit
argument, never by auto-select — and (c) that a failing phase is not caught
and reported as success.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke
from sparkdl_tpu.models.llama import LlamaConfig

_SMOKE = chip_smoke.__file__


def test_command_refuses_cpu_and_prints_no_result():
    proc = subprocess.run([sys.executable, _SMOKE], capture_output=True,
                          text=True, timeout=120,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "nothing was run" in proc.stderr
    assert proc.stdout.strip() == ""


def test_trainer_phase_tiny():
    rec = chip_smoke.phase_trainer(model_name="ResNet18", image_size=32,
                                   per_chip=2, steps=3, platform="cpu")
    assert rec["steps"] == 3 and rec["chips"] == 8
    assert rec["param_devices"] == rec["batch_shard_devices"] == 8


def test_scorer_phase_tiny():
    rec = chip_smoke.phase_scorer(
        model_name="ResNet18", rows=10, batch=4,
        sizes=((224, 224), (40, 60), (300, 200)))
    assert rec["rows_out"] == 10 and rec["feature_dim"] == 512
    assert rec["native_packer"] is True


def test_kernel_phase_tiny_interpreted():
    rec = chip_smoke.phase_kernels(
        interpret=True, seq=256, slots=3, heads=4, kv_heads=2,
        head_dim=32, max_len=128, block_size=8, verify_window=3,
        grad_seqs=(256,), grad_heads=3, grad_head_dim=32, grad_window=40,
        grad_wide=(2, 48),
        scan=dict(seq=40, channels=200, states=4, dense_channels=128),
        ssd=dict(seq=40, heads=6, head_dim=8, states=4, chunk=8,
                 dense_heads=2),
        delta=dict(seq=40, key_heads=2, value_heads=4, head_dim=8, chunk=8,
                   dense_heads=2))
    assert rec["interpret"] is True
    assert abs(rec["flash_attention_grad_S256"]["dk_norm_ratio"] - 1) < 1e-2
    assert {"flash_attention", "flash_decode", "paged_flash_decode_bf16_S1",
            "paged_flash_decode_int8_S3", "selective_scan",
            "flash_attention_grad_S256_w40"} <= set(rec)
    assert rec["flash_attention_grad_S256_w40"]["window"] == 40
    assert abs(rec["flash_attention_grad_S256_w40"]["dq_norm_ratio"] - 1) \
        < 1e-2
    assert rec["selective_scan"]["max_err"] <= chip_smoke.SCAN_TOL
    assert {"du_err", "ddt_err", "dA_err", "dB_err", "dC_err",
            "dD_err"} <= set(rec["selective_scan"])
    assert rec["ssd_scan"]["max_err"] <= chip_smoke.SSD_TOL
    assert {"dx_err", "ddt_err", "dA_err", "dB_err", "dC_err",
            "dD_err"} <= set(rec["ssd_scan"])
    assert rec["gated_delta"]["max_err"] <= chip_smoke.GATED_DELTA_TOL
    assert {"dv_err", "dq_err", "dk_err", "dg_err", "dbeta_err",
            "last_state_err"} <= set(rec["gated_delta"])
    assert rec["flash_attention_grad_S256_d48"]["heads"] == 2
    assert abs(rec["flash_attention_grad_S256_d48"]["dv_norm_ratio"] - 1) \
        < 1e-2


def test_the_scan_check_sees_a_scan_that_restarts_its_state(monkeypatch):
    """A kernel that restarted its state halfway is O(1) off, and the check
    says so."""
    import jax.numpy as jnp
    import numpy as np
    from sparkdl_tpu.ops import selective_scan as ss
    real = ss.selective_scan

    def restarted(u, dt, A, B, C, D, **kw):
        half = u.shape[1] // 2
        parts = [real(u[:, sl], dt[:, sl], A, B[:, sl], C[:, sl], D, **kw)
                 for sl in (slice(0, half), slice(half, None))]
        return jnp.concatenate([y for y, _ in parts], axis=1), parts[1][1]

    monkeypatch.setattr(ss, "selective_scan", restarted)
    with pytest.raises(AssertionError):
        chip_smoke.check_selective_scan(
            np.random.RandomState(0), interpret=True, seq=32, channels=128,
            states=4, dense_channels=128)


def test_the_chunked_scan_check_sees_a_state_that_is_not_handed_on(
        monkeypatch):
    """A kernel that starts every other chunk from nothing is O(1) off, and
    the check says so."""
    import jax.numpy as jnp
    import numpy as np
    from sparkdl_tpu.ops import ssd_scan as ssd
    real = ssd.ssd_scan

    def restarted(x, dt, A, B, C, D, **kw):
        half = x.shape[1] // 2
        parts = [real(x[:, sl], dt[:, sl], A, B[:, sl], C[:, sl], D, **kw)
                 for sl in (slice(0, half), slice(half, None))]
        return jnp.concatenate([y for y, _ in parts], axis=1), parts[1][1]

    monkeypatch.setattr(ssd, "ssd_scan", restarted)
    with pytest.raises(AssertionError):
        chip_smoke.check_ssd_scan(
            np.random.RandomState(0), interpret=True, seq=32, heads=2,
            head_dim=8, states=4, chunk=8, dense_heads=2)


def test_the_delta_rule_check_sees_a_state_that_is_not_handed_on(monkeypatch):
    """A kernel pair that starts the second half from nothing is O(1) off, and
    the check says so."""
    import jax.numpy as jnp
    import numpy as np
    from sparkdl_tpu.ops import gated_delta as gd
    real = gd.gated_delta_rule

    def restarted(q, k, v, g, beta, **kw):
        half = q.shape[1] // 2
        parts = [real(*(t[:, sl] for t in (q, k, v, g, beta)), **kw)
                 for sl in (slice(0, half), slice(half, None))]
        return jnp.concatenate([o for o, _ in parts], axis=1), parts[1][1]

    monkeypatch.setattr(gd, "gated_delta_rule", restarted)
    with pytest.raises(AssertionError):
        chip_smoke.check_gated_delta(
            np.random.RandomState(0), interpret=True, seq=32, key_heads=1,
            value_heads=2, head_dim=8, chunk=8, dense_heads=2)


def test_server_phase_tiny():
    rec = chip_smoke.phase_server(
        cfg=LlamaConfig.tiny(), num_slots=3, max_len=128,
        prompt_lens=(5, 20, 40, 70), new_tokens=(6, 9), block_size=8,
        spec_k=2, tp_degrees=(2,), expect_kernel=False, platform="cpu")
    assert rec["unpaged"]["paged"] is False and rec["paged"]["paged"]
    tp = rec["paged_tp2"]
    assert tp["tp"] == 2
    assert tp["kv_pool_device_bytes"] * 2 == tp["kv_pool_global_bytes"]
    # off the chip the kernels stand down and the record says so
    assert not any(rec["paged"]["mosaic_in_lowered"].values())


def test_a_failing_phase_is_not_reported_as_success(monkeypatch, capsys):
    """Past the platform gate (a stand-in device), with the second phase
    made to fail: main() must raise — exit code non-zero, no result
    line — and must not run the phases after it."""
    import jax

    class FakeTpu:
        platform, device_kind = "tpu", "fake"

    ran = []

    def boom():
        raise RuntimeError("scorer broke")

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "phase_trainer",
                        lambda: ran.append("trainer") or {})
    monkeypatch.setattr(chip_smoke, "phase_scorer", boom)
    monkeypatch.setattr(chip_smoke, "phase_kernels",
                        lambda **kw: ran.append("kernels") or {})
    monkeypatch.setattr(chip_smoke, "phase_server",
                        lambda **kw: ran.append("server") or {})
    with pytest.raises(RuntimeError, match="scorer broke"):
        chip_smoke.main([])
    assert ran == ["trainer"]
    assert capsys.readouterr().out.strip() == ""

    # the same wiring with every phase passing prints the report, then
    # as the LAST line the result with exactly the contract's keys
    monkeypatch.setattr(chip_smoke, "phase_scorer", lambda: {})
    assert chip_smoke.main([]) == 0
    report, last = capsys.readouterr().out.strip().splitlines()[-2:]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "fake", "count": 1}}
    report = json.loads(report)["report"]
    assert set(report) == {"server_layers", "compile_cache", "phases"}
    assert set(report["phases"]) == {"trainer", "scorer", "kernels",
                                     "server"}
