#!/usr/bin/env python
"""First contact: drive the trainer, the scorer, the Pallas kernels and the
serving engine once on the TPU, through the entry points a user calls.

    python chip_smoke.py            # the chip contract: needs a TPU

One process, every visible chip. It refuses any platform but ``tpu`` before
doing any work, runs four phases at the full width of the models the repo
serves (depth is the only cut, ``--layers``), checks each phase by the repo's
own means, and ends stdout with two JSON lines: the report (server depth,
compile-cache directory and hits/misses, what each phase ran and the seconds
it spent compiling), then — the last line, these keys and no others — the
result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

A phase that fails raises; nothing is caught and carried on, so a non-zero
exit with no result line is the only other outcome. The numbers in the report
are set-up facts (what ran, what compiled, where it was placed) — not
performance numbers.

The phases are plain functions of their sizes, so ``tests/test_chip_smoke.py``
drives the same code tiny on the CPU mesh (kernels interpreted there by
explicit argument). Only the no-argument command line is the chip contract.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time

import numpy as np

#: the Mosaic kernel's custom-call target in a lowered program
MOSAIC_CALL = "tpu_custom_call"

# Kernel-vs-dense tolerance at bf16, |q|,|k|,|v| ~ N(0,1), head_dim 128. The
# reference is f32 at "highest" matmul precision over the same bf16 inputs.
# The kernels accumulate in f32 but their two dots run the MXU's default
# single bf16 pass (q·scale and the softmax weights each round to bf16,
# relative 2^-9) and the output rounds once more to bf16; a slot attending
# one or two positions returns |o| up to ~4.5, so each of the three
# roundings is worth up to ~9e-3. Observed on the v5e: at most 1.5e-2 (PR 21
# chip runs, float and int8/fp8 pools alike — both sides dequantize the same
# codes). 4e-2 covers the three roundings stacking; a masking or indexing
# error is O(1) on these inputs.
KERNEL_ATOL = 4e-2

# The selective scan against the plain recurrence, as a share of the
# reference's largest entry. Both run the recurrence in float32 over the same
# bf16 ``u, B, C``; the kernel rounds ``y`` (and ``du``) to bf16 once, half an
# ulp of its own size, 2^-9, and the float32 sums differ in order. 2^-7 leaves
# both room (observed on the v5e, PR 34: 1.6e-3 on y, 2e-4 on a gradient); a
# wrong column, chunk edge or carried state is O(1).
SCAN_TOL = 2.0 ** -7

# The chunked state-space-dual scan against the same plain recurrence, as a
# share of the reference's largest entry. Unlike the selective scan, its sums
# over positions are MXU products: the decay-weighted [Q, Q] tile, the state
# and the cotangents each round to bf16 (2^-9 of their size) before a product
# whose float32 accumulator adds up to 256 of them, and ``y``, ``dx`` round
# once more; independent roundings grow with the root of their number.
# Observed on the v5e at the cell's shape (PR 36): 5.8e-3 and, on another
# draw, 6.6e-3 at the worst (``dB``, ``dx``; ``dC`` 3.4e-3 to 4.5e-3, ``dA``
# up to 2.3e-3, ``ddt`` 8e-4). 2^-6 leaves that 2.4 times of room; a wrong
# mask, chunk edge or carried state is O(1).
SSD_TOL = 2.0 ** -6

# The gated delta rule's kernel pair against its plain recurrence, as a share
# of the reference's largest entry. Its sums over positions are MXU products
# too, and a chunk's ``V'`` passes the bf16 triangular inverse and two more
# products before the state takes it in: more roundings stacked than the
# chunked scan's, over states that live for thousands of positions. 2^-5; a
# wrong mask, chunk edge or carried state is O(1).
GATED_DELTA_TOL = 2.0 ** -5


class CompileClock:
    """Seconds XLA spent compiling, from jax's own monitoring events."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self._EVENT:
            self.total += duration

    @contextlib.contextmanager
    def phase(self, out: dict):
        """Stamp ``out`` with the wall and compile seconds of the block."""
        c0, t0 = self.total, time.perf_counter()
        yield
        out["compile_s"] = round(self.total - c0, 1)
        out["wall_s"] = round(time.perf_counter() - t0, 1)


def _platforms(tree) -> set:
    import jax
    return {d.platform for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


# ---------------------------------------------------------------------------
# Phase 1 — trainer
# ---------------------------------------------------------------------------

def phase_trainer(*, model_name: str = "ResNet50", image_size: int = 224,
                  per_chip: int = 64, steps: int = 5,
                  platform: str = "tpu") -> dict:
    """``XlaRunner(np=-1).run(main)`` → ``ctx.fit`` (the
    ``examples/distributed_training.py`` entry), bf16, BatchNorm state
    threaded with ``mutable=True``, seeded synthetic batches."""
    import jax
    import jax.numpy as jnp
    import optax

    import sparkdl_tpu as sdl
    from sparkdl_tpu.models.registry import get_model
    from sparkdl_tpu.runner import bn_classifier_loss

    def main(ctx):
        spec = get_model(model_name)
        model = spec.build(dtype=jnp.bfloat16)
        variables = jax.tree_util.tree_map(np.asarray, jax.jit(
            lambda key: model.init(
                key, jnp.zeros((1, image_size, image_size, 3)),
                train=False))(jax.random.PRNGKey(0)))
        n = per_chip * ctx.size
        batches = []
        for i in range(steps):
            rng = np.random.RandomState(i)
            batches.append({
                "image": rng.randint(
                    0, 256, size=(n, image_size, image_size, 3))
                .astype(np.float32),
                "label": rng.randint(0, spec.num_classes, size=(n,))})
        res = ctx.fit(
            loss_fn=bn_classifier_loss(model, spec.preprocess),
            params=variables["params"],
            model_state={"batch_stats": variables["batch_stats"]},
            tx=optax.sgd(1e-3, momentum=0.9), mutable=True,
            data=batches, num_steps=steps, log_every=1, resume=False)
        state = res["state"]
        losses = [h["loss"] for h in res["history"]]
        assert len(losses) == steps and np.isfinite(losses).all(), losses
        assert int(state.step) == steps, int(state.step)
        # placement: params replicated on every chip, the batch split over
        # all of them, everything on the platform asked for
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        param_devices = len(leaf.sharding.device_set)
        sharded = ctx.shard_batch(batches[0])["image"]
        shard_devices = len({s.device.id
                             for s in sharded.addressable_shards})
        assert leaf.sharding.is_fully_replicated
        assert param_devices == shard_devices == ctx.size, \
            (param_devices, shard_devices, ctx.size)
        assert sharded.addressable_shards[0].data.shape[0] == per_chip
        assert _platforms(state.params) == _platforms(sharded) \
            == {platform}, (_platforms(state.params), _platforms(sharded))
        return {"model": model_name, "dtype": "bfloat16",
                "image_size": image_size, "per_chip": per_chip,
                "chips": ctx.size, "steps": int(state.step),
                "loss_first": round(losses[0], 4),
                "loss_last": round(losses[-1], 4),
                "param_devices": param_devices,
                "batch_shard_devices": shard_devices}

    return sdl.XlaRunner(np=-1).run(main)


# ---------------------------------------------------------------------------
# Phase 2 — scorer
# ---------------------------------------------------------------------------

def phase_scorer(*, model_name: str = "InceptionV3", rows: int = 256,
                 batch: int = 64,
                 sizes=((299, 299), (240, 320), (480, 360), (96, 64))
                 ) -> dict:
    """``DeepImageFeaturizer`` over a DataFrame of seeded image structs of
    mixed sizes, through ``Pipeline.transform`` and the C++ packer."""
    import pyarrow as pa

    import sparkdl_tpu as sdl
    from sparkdl_tpu import native
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.models.registry import get_model

    # Built from native/packing.cpp here and now, or this raises: the PIL
    # fallback would be another path than the one under test.
    native.require()
    rng = np.random.RandomState(0)
    structs = []
    for i in range(rows):
        h, w = sizes[i % len(sizes)]
        structs.append(imageIO.imageArrayToStruct(
            rng.randint(0, 256, size=(h, w, 3)).astype(np.uint8),
            origin=f"synthetic_{i}"))
    df = sdl.DataFrame.fromArrow(
        pa.table({"image": pa.array(structs, type=imageIO.imageSchema)}),
        numPartitions=max(1, rows // batch))
    feat = sdl.DeepImageFeaturizer(
        modelName=model_name, inputCol="image", outputCol="features",
        batchSize=batch, computeDtype="bfloat16")
    out = sdl.Pipeline(stages=[feat]).fit(df).transform(df).collect()
    dim = get_model(model_name).feature_dim
    assert len(out) == rows, (len(out), rows)
    feats = np.asarray([r["features"] for r in out], np.float32)
    assert feats.shape == (rows, dim), feats.shape
    assert np.isfinite(feats).all()
    assert feats.std() > 0  # a real forward pass, not a constant
    return {"model": model_name, "rows_in": rows, "rows_out": len(out),
            "feature_dim": dim, "image_sizes": len(sizes),
            "native_packer": native.available()}


# ---------------------------------------------------------------------------
# Phase 3 — kernels
# ---------------------------------------------------------------------------

def _max_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    return float(np.abs(got - np.asarray(want, np.float32)).max())


def _paged_case(rng, *, slots, kv_heads, head_dim, max_len, block_size,
                dtype, kv_dtype):
    """A live-looking pool: every slot at its own fill level (one empty,
    one full), its blocks scattered over the pool, block 0 the trash."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import llama as L
    mb = max_len // block_size
    pool_blocks = slots * mb + 1
    shape = (pool_blocks, kv_heads, block_size, head_dim)
    scales = None
    if kv_dtype is None:
        k_pool = jnp.asarray(rng.randn(*shape), dtype)
        v_pool = jnp.asarray(rng.randn(*shape), dtype)
    else:
        qdt, qmax = L.kv_quant_spec(kv_dtype)
        scales = jnp.asarray(
            rng.uniform(0.5, 1.5, (pool_blocks, kv_heads, 2)) / qmax * 3,
            jnp.float32)
        k_pool, v_pool = (jnp.clip(jnp.asarray(
            rng.randn(*shape) * qmax / 3, jnp.float32), -qmax, qmax)
            .astype(qdt) for _ in range(2))
    perm = rng.permutation(np.arange(1, pool_blocks)).reshape(slots, mb)
    cur = rng.randint(1, max_len - 8, size=slots)
    cur[0], cur[-1] = 0, max_len - 8
    live = -(-(cur + 8) // block_size)
    tables = np.where(np.arange(mb)[None, :] < live[:, None], perm, 0)
    pads = np.minimum(rng.randint(0, 4, size=slots), cur)
    return (k_pool, v_pool, scales, jnp.asarray(tables, jnp.int32),
            jnp.asarray(cur, jnp.int32), jnp.asarray(pads, jnp.int32))


def _cache_ref(q, k_all, v_all, qpos, pads):
    """Dense causal-vs-cache attention in f32 — the masking math the
    engine's own kernel fallback runs (``llama._dense_slot_attention``)."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import llama as L
    _, heads, _, head_dim = q.shape
    cfg = L.LlamaConfig(hidden_size=heads * head_dim, num_heads=heads,
                        num_kv_heads=k_all.shape[1])
    with jax.default_matmul_precision("highest"):
        return L._dense_slot_attention(
            *(jnp.asarray(x, jnp.float32) for x in (q, k_all, v_all)),
            qpos, pads, cfg, jnp.float32)


def check_flash_attention(rng, *, interpret, seq, heads, head_dim,
                          grad_seqs=(2048, 8192), grad_heads=64,
                          grad_head_dim=64, dense_heads=2,
                          grad_window=512, grad_wide=(16, 256)) -> dict:
    """Causal prefill at S=seq, and the gradient through the backward
    kernel pair at the training cell's head shape (``grad_heads`` heads of
    ``grad_head_dim``, bf16) at each of ``grad_seqs``. No default engine
    path reaches this kernel — chunked prefill attends dense-vs-cache — so
    it is called directly.

    The gradient's reference is dense float32 attention over the same bf16
    values; its scores are [heads, S, S] float32 (17 GB for 64 heads at
    8192), so it is taken over the first ``dense_heads`` heads — heads do
    not mix, so those heads of the full call are held to it exactly. The
    record gives each gradient's largest gap as a share of the reference's
    largest entry (``max_err``, held to the kernels' tolerance) and the
    ratio of the 2-norms. At the longest of ``grad_seqs`` the gradient is
    taken once more under a window of ``grad_window`` keys
    (``flash_attention_grad_S*_w*``), against dense attention under the same
    band, and once at ``grad_wide`` heads of that width
    (``flash_attention_grad_S*_d*``: 16 heads of 256, the widest head a cell
    calls the kernels at)."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ops.flash_attention import flash_attention
    from sparkdl_tpu.parallel.ring_attention import dense_attention
    q, k, v = (jnp.asarray(rng.randn(1, heads, seq, head_dim),
                           jnp.bfloat16) for _ in range(3))
    got = flash_attention(q, k, v, True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = dense_attention(*(jnp.asarray(x, jnp.float32)
                                 for x in (q, k, v)), True)
    out = {"flash_attention": {"S": seq, "max_err": _max_err(got, want)}}

    def grads(attn, *qkvw):   # all four are arguments: none a constant
        return jax.jit(jax.grad(
            lambda a, b, c, w: (attn(a, b, c).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2)))(*qkvw)

    cases = [(s, None, grad_heads, grad_head_dim) for s in grad_seqs]
    if grad_window:
        cases.append((max(grad_seqs), grad_window, grad_heads, grad_head_dim))
    if grad_wide:
        cases.append((max(grad_seqs), None, *grad_wide))
    for s, window, n_heads, width in cases:
        q, k, v, w = (jnp.asarray(rng.randn(1, n_heads, s, width),
                                  jnp.bfloat16) for _ in range(4))
        got = grads(lambda a, b, c: flash_attention(
            a, b, c, True, interpret=interpret, window=window), q, k, v, w)
        with jax.default_matmul_precision("highest"):
            want = grads(lambda a, b, c: dense_attention(
                a, b, c, True, None, window),
                *(jnp.asarray(x[:, :dense_heads], jnp.float32)
                  for x in (q, k, v, w)))
        rec = {"S": s, "heads": n_heads, "max_err": 0.0}
        if window:
            rec["window"] = window
        wide = f"_d{width}" if width != grad_head_dim else ""
        for name, g, r in zip(("dq", "dk", "dv"), got, want):
            assert g.dtype == jnp.bfloat16 and np.isfinite(
                np.asarray(g, np.float32)).all(), name
            r = np.asarray(r)
            g = np.asarray(g[:, :dense_heads], np.float32)
            rec[f"{name}_norm_ratio"] = float(
                np.linalg.norm(g) / np.linalg.norm(r))
            rec["max_err"] = max(rec["max_err"],
                                 float(np.abs(g - r).max() / np.abs(r).max()))
        out[f"flash_attention_grad_S{s}" + (f"_w{window}" if window
                                            else wide)] = rec
    return out


def _scan_gaps(scan, plain, args, w, first, names, summed) -> dict:
    """Forward and every gradient of ``scan`` at all of ``args`` against
    ``plain`` on what ``first`` keeps of them, each gap as a share of the
    reference's largest entry: ``{"max_err", "<name>_err", ...}``. The
    gradients named in ``summed`` add up over what ``first`` cuts away, so
    they are taken from a second call over the kept part alone."""
    import jax
    import jax.numpy as jnp

    def grads(fn, args, w):
        return jax.jit(jax.grad(
            lambda *a: (fn(*a).astype(jnp.float32)
                        * w.astype(jnp.float32)).sum(),
            argnums=tuple(range(len(args)))))(*args)

    few = tuple(first(t) for t in args)
    y = jax.jit(scan)(*args)
    assert y.dtype == jnp.bfloat16 and y.shape == args[0].shape
    want = jax.jit(plain)(*few)
    rec = {"max_err": _max_err(first(y), want) / float(jnp.abs(want).max())}
    got, got_few = grads(scan, args, w), grads(scan, few, first(w))
    ref = grads(plain, few, first(w))
    for i, name in enumerate(names):
        g = got_few[i] if name in summed else first(got[i])
        r = np.asarray(ref[i], np.float32)
        err = _max_err(g, r) / float(np.abs(r).max())
        rec[f"{name}_err"] = err
        rec["max_err"] = max(rec["max_err"], err)
    return rec


def check_selective_scan(rng, *, interpret, seq=8192, channels=5120,
                         states=16, dense_channels=256) -> dict:
    """The selective-scan kernel pair at the Mamba cell's shape (one
    sequence, ``u, B, C`` in bf16, ``dt`` and ``A`` float32, the recurrence
    in float32): forward and every gradient at all ``channels``, against the
    plain position-by-position recurrence in float32 on the first
    ``dense_channels`` (channels do not mix; ``dB`` and ``dC`` sum over them,
    so those two are taken from a second call over the first channels
    alone). ``max_err`` is the largest gap, forward or gradient, as a share
    of the reference's largest entry, held to ``SCAN_TOL``."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ops import selective_scan as scan_ops

    def plain(u, dt, A, B, C, D):
        u, B, C = (t.astype(jnp.float32) for t in (u, B, C))

        def step(h, x):
            u_t, dt_t, b_t, c_t = x
            h = jnp.exp(dt_t[..., None] * A) * h \
                + (dt_t * u_t)[..., None] * b_t[:, None, :]
            return h, jnp.sum(h * c_t[:, None, :], -1)

        xs = tuple(jnp.swapaxes(t, 0, 1) for t in (u, dt, B, C))
        _, y = jax.lax.scan(
            step, jnp.zeros((u.shape[0], u.shape[2], A.shape[1])), xs)
        return jnp.swapaxes(y, 0, 1) + D * u

    u, w = (jnp.asarray(rng.randn(1, seq, channels), jnp.bfloat16)
            for _ in range(2))
    dt = jax.nn.softplus(jnp.asarray(rng.randn(1, seq, channels) - 4.0,
                                     jnp.float32))
    A = -jnp.exp(jnp.broadcast_to(
        jnp.log(jnp.arange(1, states + 1, dtype=jnp.float32)),
        (channels, states)))
    B, C = (jnp.asarray(rng.randn(1, seq, states), jnp.bfloat16)
            for _ in range(2))
    D = jnp.ones((channels,), jnp.float32)
    args, nc = (u, dt, A, B, C, D), dense_channels

    def first(t):
        """The first ``dense_channels`` channels of an operand."""
        if t.ndim == 3 and t.shape[-1] == channels:
            return t[..., :nc]
        return t[:nc] if t.shape[0] == channels else t

    def scan(*a):
        return scan_ops.selective_scan(*a, interpret=interpret)[0]

    rec = {"S": seq, "channels": channels, **_scan_gaps(
        scan, plain, args, w, first,
        ("du", "ddt", "dA", "dB", "dC", "dD"), ("dB", "dC"))}
    assert rec["max_err"] <= SCAN_TOL, rec
    return {"selective_scan": rec}


def check_ssd_scan(rng, *, interpret, seq=8192, heads=64, head_dim=64,
                   states=128, chunk=256, dense_heads=4) -> dict:
    """The chunked-scan kernel pair at the Mamba-2 cell's shape (one
    sequence, ``x, B, C`` in bf16, ``dt`` and ``A`` float32): forward and
    every gradient at all ``heads``, against the plain position-by-position
    recurrence in float32 on the first ``dense_heads`` (heads do not mix;
    ``dB`` and ``dC`` sum over them, so those two are taken from a second
    call over the first heads alone). ``max_err`` is the largest gap, forward
    or gradient, as a share of the reference's largest entry, held to
    ``SSD_TOL``."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ops import ssd_scan as ssd_ops

    def plain(x, dt, A, B, C, D):
        x, B, C = (t.astype(jnp.float32) for t in (x, B, C))

        def step(h, inp):
            x_t, dt_t, b_t, c_t = inp
            h = jnp.exp(dt_t * A)[..., None, None] * h \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
            return h, jnp.sum(h * c_t[:, None, None, :], -1)

        xs = tuple(jnp.swapaxes(t, 0, 1)
                   for t in (x, dt, B[:, :, 0], C[:, :, 0]))
        _, y = jax.lax.scan(step, jnp.zeros(
            (x.shape[0], x.shape[2], x.shape[3], B.shape[-1])), xs)
        return jnp.swapaxes(y, 0, 1) + D[:, None] * x

    x, w = (jnp.asarray(rng.randn(1, seq, heads, head_dim), jnp.bfloat16)
            for _ in range(2))
    dt = jax.nn.softplus(jnp.asarray(rng.randn(1, seq, heads) - 4.0,
                                     jnp.float32))
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (heads,)), jnp.float32)
    B, C = (jnp.asarray(rng.randn(1, seq, 1, states), jnp.bfloat16)
            for _ in range(2))
    D = jnp.ones((heads,), jnp.float32)
    args, nh = (x, dt, A, B, C, D), dense_heads

    def first(t):
        """The first ``dense_heads`` heads of an operand."""
        if t.ndim >= 3 and t.shape[2] == heads:
            return t[:, :, :nh]
        return t[:nh] if t.shape[0] == heads else t

    def scan(*a):
        return ssd_ops.ssd_scan(*a, chunk=chunk, interpret=interpret)[0]

    rec = {"S": seq, "heads": heads, **_scan_gaps(
        scan, plain, args, w, first,
        ("dx", "ddt", "dA", "dB", "dC", "dD"), ("dB", "dC"))}
    assert rec["max_err"] <= SSD_TOL, rec
    return {"ssd_scan": rec}


def check_gated_delta(rng, *, interpret, seq=8192, key_heads=16,
                      value_heads=32, head_dim=128, chunk=128,
                      dense_heads=4) -> dict:
    """The gated delta rule's kernel pair at the Qwen3-Next cell's shape (one
    sequence, ``q, k, v`` in bf16, q and k of unit length, ``g`` and ``beta``
    float32, half-lives of 64 to 8,192 positions): forward, last state and
    every gradient at all the heads, against the plain position-by-position
    recurrence in float32 on the first ``dense_heads`` value heads and the key
    heads that serve them (heads do not mix). ``max_err`` is the largest gap
    as a share of the reference's largest entry, held to
    ``GATED_DELTA_TOL``."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.ops import gated_delta as gd
    rep = value_heads // key_heads

    def recurrence(v, q, k, g, beta):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))

        def step(state, inp):
            q_t, k_t, v_t, g_t, b_t = inp
            state = jnp.exp(g_t)[..., None, None] * state
            delta = b_t[..., None] * (v_t - jnp.sum(
                state * k_t[..., :, None], axis=-2))
            state = state + k_t[..., :, None] * delta[..., None, :]
            return state, jnp.sum(state * q_t[..., :, None], axis=-2)

        last, o = jax.lax.scan(
            step, jnp.zeros((v.shape[0], v.shape[2], q.shape[3], v.shape[3])),
            tuple(jnp.swapaxes(t, 0, 1) for t in (q, k, v, g, beta)))
        return jnp.swapaxes(o, 0, 1) / np.sqrt(q.shape[-1]), last

    def unit(shape):
        x = rng.randn(*shape)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True),
                           jnp.bfloat16)

    q, k = (unit((1, seq, key_heads, head_dim)) for _ in range(2))
    v, w = (jnp.asarray(rng.randn(1, seq, value_heads, head_dim),
                        jnp.bfloat16) for _ in range(2))
    life = np.exp(rng.uniform(np.log(64.0), np.log(8192.0),
                              (1, seq, value_heads)))
    g = jnp.asarray(-np.log(2.0) / life, jnp.float32)
    beta = jax.nn.sigmoid(jnp.asarray(rng.randn(1, seq, value_heads),
                                      jnp.float32))
    args, nh = (v, q, k, g, beta), dense_heads

    def first(t):
        """The first ``dense_heads`` value heads of an operand, or the key
        heads that serve them."""
        return t[:, :, :nh if t.shape[2] == value_heads else nh // rep]

    def rule(v, q, k, g, beta):
        return gd.gated_delta_rule(q, k, v, g, beta, chunk=chunk,
                                   interpret=interpret)

    rec = {"S": seq, "heads": value_heads, **_scan_gaps(
        lambda *a: rule(*a)[0], lambda *a: recurrence(*a)[0], args, w, first,
        ("dv", "dq", "dk", "dg", "dbeta"), ())}
    last, want = jax.jit(rule)(*args)[1], jax.jit(recurrence)(
        *(first(t) for t in args))[1]
    rec["last_state_err"] = _max_err(last[:, :nh], want) / float(
        jnp.abs(want).max())
    rec["max_err"] = max(rec["max_err"], rec["last_state_err"])
    assert rec["max_err"] <= GATED_DELTA_TOL, rec
    return {"gated_delta": rec}


def check_flash_decode(rng, *, interpret, slots, heads, kv_heads, head_dim,
                       max_len) -> dict:
    """The un-paged engine's decode step: a per-row ``[B]`` fill vector,
    one row nearly empty and one full."""
    import jax.numpy as jnp

    from sparkdl_tpu.ops.flash_decode import flash_decode
    q = jnp.asarray(rng.randn(slots, heads, 1, head_dim), jnp.bfloat16)
    k_c, v_c = (jnp.asarray(rng.randn(slots, kv_heads, max_len, head_dim),
                            jnp.bfloat16) for _ in range(2))
    cur = rng.randint(1, max_len, size=slots)
    cur[0], cur[-1] = 1, max_len
    pads = jnp.asarray(np.minimum(rng.randint(0, 4, size=slots), cur - 1),
                       jnp.int32)
    cur = jnp.asarray(cur, jnp.int32)
    got = flash_decode(q, k_c, v_c, cur, pads, interpret=interpret)
    want = _cache_ref(q, k_c, v_c, cur[:, None] - 1, pads)
    return {"flash_decode": {"cur": "[B]", "max_len": max_len,
                             "max_err": _max_err(got, want)}}


def check_paged_flash_decode(rng, *, interpret, slots, heads, kv_heads,
                             head_dim, max_len, block_size, kv_dtype,
                             windows) -> dict:
    """The paged engine's decode step (S=1) and verify window, over a
    bf16 pool (``kv_dtype`` None) or quantized codes + scale plane."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import llama as L
    from sparkdl_tpu.ops.paged_flash_decode import (paged_flash_decode,
                                                    support_reason)
    reason = support_reason(block_size, kv_dtype=kv_dtype)
    assert reason is None, reason
    k_pool, v_pool, scales, tables, cur, pads = _paged_case(
        rng, slots=slots, kv_heads=kv_heads, head_dim=head_dim,
        max_len=max_len, block_size=block_size, dtype=jnp.bfloat16,
        kv_dtype=kv_dtype)
    if kv_dtype is None:
        k_all, v_all = (L._gather_leaf(p, tables) for p in (k_pool, v_pool))
    else:
        k_all, v_all = (L._gather_dequant(p, scales, ch, tables,
                                          jnp.float32)
                        for ch, p in enumerate((k_pool, v_pool)))
    out = {}
    for s_q in windows:
        q = jnp.asarray(rng.randn(slots, heads, s_q, head_dim),
                        jnp.bfloat16)
        got = paged_flash_decode(q, k_pool, v_pool, tables, cur, pads,
                                 scales, interpret=interpret)
        qpos = cur[:, None] + jnp.arange(s_q)[None, :]
        want = _cache_ref(q, k_all, v_all, qpos, pads)
        out[f"paged_flash_decode_{kv_dtype or 'bf16'}_S{s_q}"] = {
            "block_size": block_size, "max_err": _max_err(got, want)}
    return out


def phase_kernels(*, interpret: bool, seq: int = 2048, slots: int = 8,
                  heads: int = 16, kv_heads: int = 8, head_dim: int = 128,
                  max_len: int = 2048, block_size: int = 16,
                  verify_window: int = 5, kv_dtypes=(None, "int8"),
                  atol: float = KERNEL_ATOL, scan=None, ssd=None, delta=None,
                  **flash_grad) -> dict:
    """Each Pallas kernel against the dense reference at the shapes the
    server phase serves (and, for the flash kernel's backward pair, the
    training cell's: ``flash_grad`` overrides ``check_flash_attention``'s
    ``grad_*`` sizes, ``scan`` ``check_selective_scan``'s, ``ssd``
    ``check_ssd_scan``'s, ``delta`` ``check_gated_delta``'s). ``interpret`` is
    passed to every call explicitly:
    False compiles through Mosaic, True is the CPU test's interpreter."""
    rng = np.random.RandomState(0)
    shape = dict(interpret=interpret, slots=slots, heads=heads,
                 kv_heads=kv_heads, head_dim=head_dim, max_len=max_len)
    checks = {
        **check_flash_attention(rng, interpret=interpret, seq=seq,
                                heads=heads, head_dim=head_dim,
                                **flash_grad),
        **check_selective_scan(rng, interpret=interpret, **(scan or {})),
        **check_ssd_scan(rng, interpret=interpret, **(ssd or {})),
        **check_gated_delta(rng, interpret=interpret, **(delta or {})),
        **check_flash_decode(rng, **shape)}
    for kv in kv_dtypes:
        checks.update(check_paged_flash_decode(
            rng, block_size=block_size, kv_dtype=kv,
            windows=(1, verify_window), **shape))
    assert max(c["max_err"] for c in checks.values()) <= atol, checks
    return {"interpret": interpret, "atol": atol, **checks}


# ---------------------------------------------------------------------------
# Phase 4 — server
# ---------------------------------------------------------------------------

def _lowered_steps(backend, spec_k: int) -> dict:
    """The engine's decode (and verify) step, lowered exactly as the
    backend calls it — the text says whether the Mosaic kernel is in."""
    import jax.numpy as jnp

    from sparkdl_tpu.models import llama as L
    be = backend
    tok, cur, pads = (jnp.asarray(x) for x in
                      (be._tokens, be._cur, be._pads))
    kw = dict(temperature=be.temperature, top_k=be.top_k, top_p=be.top_p)
    if not getattr(be, "paged", False):
        return {"decode": L.slot_decode_step.lower(
            be.model, be.params, be.cache, tok, cur, pads, be._rng,
            **kw).as_text()}
    tables = jnp.asarray(be.tables)
    out = {"decode": L.paged_slot_decode_step.lower(
        be.model, be.params, be.cache, tables, tok, cur, pads, be._rng,
        **kw).as_text()}
    if spec_k:
        toks = jnp.zeros((be.num_slots, spec_k + 1), jnp.int32)
        out["verify"] = L.paged_slot_verify_step.lower(
            be.model, be.params, be.cache, tables, toks, cur,
            pads).as_text()
    return out


def serve_once(model, variables, *, num_slots: int, max_len: int,
               prompt_lens, new_tokens, expect_kernel: bool,
               block_size: int | None = None, spec_k: int = 0,
               tp: int = 1, platform: str = "tpu",
               timeout_s: float = 900.0) -> dict:
    """One engine through ``GenerationEngine.from_model`` + ``with eng:``:
    a warm-up request, then the rest at once. Token identity across
    engines is NOT asserted (bf16 kernels round differently); the kernel
    phase is the numeric oracle."""
    import jax

    import sparkdl_tpu as sdl
    from sparkdl_tpu.core.runtime import GLOBAL_COMPILE_CACHE

    def sig():
        return GLOBAL_COMPILE_CACHE.signatures("serve_decode_step")

    rng = np.random.RandomState(len(prompt_lens) + (block_size or 0) + tp)
    vocab = model.cfg.vocab_size
    prompts = [rng.randint(1, vocab, size=n).tolist() for n in prompt_lens]
    eng = sdl.GenerationEngine.from_model(
        model, variables, num_slots=num_slots, max_len=max_len,
        block_size=block_size, spec_k=spec_k, tp=tp)
    with eng:
        first = eng.submit(prompts[0], max_new_tokens=new_tokens[0])
        first.result(timeout=timeout_s)
        sig_warm = sig()
        rest = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[1:], new_tokens[1:])]
        for h in rest:
            h.result(timeout=timeout_s)
    handles = [first] + rest
    for h, n in zip(handles, new_tokens):
        assert h.finish_reason == "length" and len(h.tokens) == n, \
            (h.id, h.finish_reason, len(h.tokens), n)
        assert all(0 <= t < vocab for t in h.tokens)
    snap = eng.snapshot()
    assert snap["quarantined"] == 0 and snap["failed"] == 0, snap
    assert snap["failovers"] == 0 and snap["failover"]["count"] == 0, snap
    assert snap["completed"] == len(handles), snap
    # no re-trace: the decode step's signature count is constant after
    # warm-up (a tp engine shares its single-device twin's signature —
    # shapes and dtypes, not placement)
    assert sig_warm >= 1 and sig() == sig_warm, (sig_warm, sig())
    be = eng.backend
    assert _platforms(be.params) == _platforms(be.cache) == {platform}
    lowered = _lowered_steps(be, eng.spec_k)
    kernel_in = {name: MOSAIC_CALL in text for name, text in lowered.items()}
    if expect_kernel:
        assert all(kernel_in.values()), kernel_in
    rec = {"paged": eng.paged, "tp": eng.tp_degree, "spec_k": eng.spec_k,
           "requests": len(handles), "tokens_out": snap["tokens_out"],
           "prefill_chunks": snap["prefill_chunks"], "steps": snap["steps"],
           "spec_verifies": snap["spec_verifies"],
           "decode_signatures": sig(),
           "mosaic_in_lowered": kernel_in,
           "kv_pool_device_bytes": eng.kv_pool_device_bytes}
    if tp > 1:
        # K/V pool leaves head-sharded over tp devices, 1/tp bytes each
        leaves = [x for x in jax.tree_util.tree_leaves(be.cache)
                  if getattr(x, "ndim", 0) == 4]
        total = sum(x.size * x.dtype.itemsize for x in leaves)
        for x in leaves:
            # PartitionSpec(None, 'tp', None, None); a donated round trip
            # through jit may drop the trailing Nones
            spec = tuple(x.sharding.spec)
            assert spec + (None,) * (4 - len(spec)) == \
                (None, "tp", None, None), x.sharding
            assert len(x.sharding.device_set) == tp
            assert x.addressable_shards[0].data.shape[1] * tp == x.shape[1]
        assert eng.kv_pool_device_bytes * tp == total, \
            (eng.kv_pool_device_bytes, total)
        rec["kv_pool_global_bytes"] = total
    return rec


def phase_server(*, cfg=None, num_slots: int = 8, max_len: int = 2048,
                 prompt_lens=(8, 20, 31, 100, 115, 128, 490, 500, 512,
                              1480, 1490, 1500),
                 new_tokens=(32, 48, 64), block_size: int = 16,
                 spec_k: int = 4, tp_degrees=(1,), expect_kernel: bool = True,
                 platform: str = "tpu") -> dict:
    """Seeded random weights cast to bf16 the way the generation UDF
    serves them, then the un-paged engine (the default), the paged engine
    with a speculation window, and — for every degree > 1 in
    ``tp_degrees`` — one paged engine spanning that many chips."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.llama import LlamaConfig, LlamaModel
    from sparkdl_tpu.models.pretrained import cast_float_leaves

    cfg = cfg or LlamaConfig.small()
    model = LlamaModel(cfg, dtype=jnp.bfloat16)
    variables = jax.jit(lambda key: cast_float_leaves(model.init(
        key, jnp.zeros((1, 4), jnp.int32)), "bfloat16"))(
        jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(variables))
    news = [new_tokens[i % len(new_tokens)]
            for i in range(len(prompt_lens))]
    common = dict(num_slots=num_slots, max_len=max_len,
                  prompt_lens=prompt_lens, new_tokens=news,
                  expect_kernel=expect_kernel, platform=platform)
    out = {"model": {"hidden": cfg.hidden_size, "layers": cfg.num_layers,
                     "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
                     "head_dim": cfg.head_dim, "vocab": cfg.vocab_size,
                     "params": int(n_params), "dtype": "bfloat16"},
           "unpaged": serve_once(model, variables, **common),
           "paged": serve_once(model, variables, block_size=block_size,
                               spec_k=spec_k, **common)}
    for tp in tp_degrees:
        if tp > 1:
            out[f"paged_tp{tp}"] = serve_once(
                model, variables, block_size=block_size, spec_k=spec_k,
                tp=tp, **common)
    return out


# ---------------------------------------------------------------------------
# Command line — the chip contract
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--layers", type=int, default=None,
                    help="server depth (default: the model's own 16); the "
                         "only cut the contract allows, printed in the "
                         "result")
    ns = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # With JAX_PLATFORMS unset jax falls back to the CPU with only a
        # warning — the first hidden fallback. Refuse before any work.
        print(f"chip_smoke: needs a TPU, but jax found platform "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())

    from sparkdl_tpu.core import runtime
    from sparkdl_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig.small()
    if ns.layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=ns.layers)

    clock = CompileClock()
    phases: dict = {}
    for name, fn in (
            ("trainer", phase_trainer),
            ("scorer", phase_scorer),
            ("kernels", lambda: phase_kernels(interpret=False)),
            ("server", lambda: phase_server(
                cfg=cfg, tp_degrees=(n_dev,) if n_dev > 1 else ()))):
        print(f"chip_smoke: {name} ...", file=sys.stderr, flush=True)
        rec: dict = {}
        with clock.phase(rec):
            rec.update(fn())
        phases[name] = rec
        print(f"chip_smoke: {name} ok {json.dumps(rec)}", file=sys.stderr,
              flush=True)

    print(json.dumps({"report": {
        "server_layers": cfg.num_layers,
        "compile_cache": runtime.persistent_cache_stats(),
        "phases": phases}}))
    # The contract's result line: exactly these keys, the device as jax
    # reports it. Everything else is in the report line above.
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
