"""XlaRunner tests on the virtual 8-device CPU mesh.

Strategy mirrors the reference's (SURVEY.md §4): a local-mode engine exercises
the full distributed machinery in-process, and correctness is equivalence —
the sharded SPMD step must match a single-device numpy/jax reference step
bit-for-bit (same inputs, same update math).
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest

from sparkdl_tpu.runner import (CheckpointManager, TrainState, ThroughputMeter,
                                XlaRunner, make_shard_map_step,
                                make_train_step, softmax_cross_entropy_loss)
from sparkdl_tpu.runner import api as hvd
from sparkdl_tpu.core import runtime


def _linear_apply(params, x):
    return x @ params["w"] + params["b"]


def _make_problem(seed=0, dim=4, classes=3):
    rng = np.random.RandomState(seed)
    # Host numpy (not jnp): donated train steps delete their input device
    # buffers, so each TrainState gets its own device copy of these.
    params = {"w": rng.randn(dim, classes).astype(np.float32),
              "b": np.zeros((classes,), np.float32)}
    x = rng.randn(16, dim).astype(np.float32)
    y = rng.randint(0, classes, size=(16,))
    return params, {"image": x, "label": y}


def _reference_step(params, batch, lr=0.1):
    """Plain single-device step for equivalence checking."""
    def loss(p):
        logits = _linear_apply(p, jnp.asarray(batch["image"]))
        onehot = jax.nn.one_hot(batch["label"], logits.shape[-1])
        return optax.softmax_cross_entropy(logits, onehot).mean()

    grads = jax.grad(loss)(params)
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)


@pytest.fixture(scope="module")
def runner():
    return XlaRunner(np=8)


class TestTrainStep:
    @pytest.mark.parametrize("explicit", [False, True])
    def test_matches_single_device_reference(self, runner, explicit):
        """The SPMD step (implicit XLA collective or explicit shard_map
        pmean) must equal the plain single-device SGD step."""
        ctx = runner.make_context()
        params, batch = _make_problem()
        loss_fn = softmax_cross_entropy_loss()
        state = TrainState.create(_linear_apply, params,
                                  optax.sgd(0.1))
        step = ctx.make_train_step(loss_fn, explicit_collectives=explicit)
        with ctx.mesh:
            new_state, metrics = step(state, ctx.shard_batch(batch))
        expected = _reference_step(params, batch)
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(new_state.params[k]),
                                       np.asarray(expected[k]),
                                       rtol=2e-5, atol=2e-6)
        assert float(metrics["loss"]) > 0
        assert int(new_state.step) == 1

    def test_explicit_and_implicit_agree(self, runner):
        ctx = runner.make_context()
        params, batch = _make_problem(seed=1)
        loss_fn = softmax_cross_entropy_loss()
        tx = optax.adam(1e-2)
        with ctx.mesh:
            s1, _ = make_train_step(loss_fn, ctx.mesh)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
            s2, _ = make_shard_map_step(loss_fn, ctx.mesh)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(s1.params[k]),
                                       np.asarray(s2.params[k]),
                                       rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("explicit", [False, True])
    def test_step_names_its_own_work_for_the_profiler(self, runner,
                                                      explicit):
        """ISSUE 27: flax names the model's operations; the step's own work
        (optimizer update, the explicit gradient mean) carries a
        jax.named_scope, which reaches the compiled HLO's op_name."""
        ctx = runner.make_context()
        params, batch = _make_problem()
        state = TrainState.create(_linear_apply, params,
                                  optax.sgd(0.1, momentum=0.9))
        step = ctx.make_train_step(softmax_cross_entropy_loss(),
                                   explicit_collectives=explicit)
        with ctx.mesh:
            text = step.lower(state, ctx.shard_batch(batch)).compile() \
                .as_text()
        names = re.findall(r'op_name="([^"]*)"', text)
        assert any("/optimizer_update/" in n for n in names)
        assert any("/grad_allreduce/" in n for n in names) == explicit

    def test_remat_same_gradients(self, runner):
        """remat=True recomputes activations in the backward pass — a
        scheduling change, not a math change: updated params must equal
        the non-remat step's."""
        ctx = runner.make_context()
        params, batch = _make_problem(seed=2)
        loss_fn = softmax_cross_entropy_loss()
        tx = optax.sgd(0.1)
        with ctx.mesh:
            s1, _ = ctx.make_train_step(loss_fn)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
            s2, _ = ctx.make_train_step(loss_fn, remat=True)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(s1.params[k]),
                                       np.asarray(s2.params[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_gradient_accumulation_equals_full_batch(self, runner):
        """accum_steps=k microbatch scan must produce the SAME update as
        one full-batch step (mean-reduced loss ⇒ averaged microbatch
        grads == full grad), also composed with remat."""
        ctx = runner.make_context()
        params, batch = _make_problem(seed=3)
        loss_fn = softmax_cross_entropy_loss()
        tx = optax.sgd(0.1)
        with ctx.mesh:
            full, _ = ctx.make_train_step(loss_fn)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
            acc, m = ctx.make_train_step(loss_fn, accum_steps=4)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
            accr, _ = ctx.make_train_step(loss_fn, accum_steps=4,
                                          remat=True)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(acc.params[k]),
                                       np.asarray(full.params[k]),
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(np.asarray(accr.params[k]),
                                       np.asarray(full.params[k]),
                                       rtol=1e-5, atol=1e-6)
        assert np.isfinite(float(m["loss"]))
        # shard-aligned split path: batch divisible by k x shards (the
        # zero-reshard fast path; the 16-row case above exercises the
        # contiguous fallback)
        rng = np.random.RandomState(9)
        big = {"image": rng.randn(64, 4).astype(np.float32),
               "label": rng.randint(0, 3, (64,))}
        bparams = {"w": rng.randn(4, 3).astype(np.float32) * 0.1,
                   "b": np.zeros(3, np.float32)}
        with ctx.mesh:
            bf, _ = ctx.make_train_step(loss_fn)(
                TrainState.create(_linear_apply, bparams, tx),
                ctx.shard_batch(big))
            ba, _ = ctx.make_train_step(loss_fn, accum_steps=4)(
                TrainState.create(_linear_apply, bparams, tx),
                ctx.shard_batch(big))
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(ba.params[k]),
                                       np.asarray(bf.params[k]),
                                       rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="mutable"):
            make_train_step(loss_fn, ctx.mesh, mutable=True, accum_steps=2)
        with pytest.raises(ValueError, match="accum_steps"):
            make_train_step(loss_fn, ctx.mesh, accum_steps=0)
        # explicit-collective path: remat composes, accum raises clearly
        with ctx.mesh:
            er, _ = ctx.make_train_step(loss_fn, explicit_collectives=True,
                                        remat=True)(
                TrainState.create(_linear_apply, params, tx),
                ctx.shard_batch(batch))
        for k in ("w", "b"):
            np.testing.assert_allclose(np.asarray(er.params[k]),
                                       np.asarray(full.params[k]),
                                       rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="explicit_collectives"):
            ctx.make_train_step(loss_fn, explicit_collectives=True,
                                accum_steps=2)

    def test_fit_accum_crops_ragged_tail(self, runner):
        """fit(accum_steps=k) must survive a data iterator whose tail
        batches are not divisible by k x local devices: crop (and skip
        tiny leftovers), never abort at the run's last step."""
        def apply_fn(p, x):
            return x @ p["w"]

        rng = np.random.RandomState(4)
        params = {"w": rng.randn(4, 3).astype(np.float32) * 0.1}

        def data():
            for nrows in (32, 20, 3):  # full, ragged (crop), tiny (skip)
                yield {"image": rng.randn(nrows, 4).astype(np.float32),
                       "label": rng.randint(0, 3, (nrows,))}

        res = runner.run(lambda ctx: ctx.fit(
            loss_fn=softmax_cross_entropy_loss(), params=params,
            tx=optax.sgd(0.1), apply_fn=apply_fn, data=data(),
            num_steps=3, log_every=1, accum_steps=2))
        steps = [h["step"] for h in res["history"]]
        # 32 runs whole; 20 crops to 16 (accum 2 x data-axis 8 = 16);
        # 3 is skipped entirely -> two optimizer steps happened
        assert steps == [1, 2]
        assert all(np.isfinite(h["loss"]) for h in res["history"])

    def test_batch_actually_sharded(self, runner):
        """The input batch must land split over the data axis — 8 shards."""
        ctx = runner.make_context()
        _, batch = _make_problem()
        sharded = ctx.shard_batch(batch)
        assert len(sharded["image"].sharding.device_set) == 8
        shard_shapes = {s.data.shape for s in sharded["image"].addressable_shards}
        assert shard_shapes == {(2, 4)}  # 16 rows / 8 devices


class TestRunnerApi:
    def test_run_passes_context(self):
        out = XlaRunner(np=8).run(lambda ctx, k: (ctx.size, k), k=42)
        assert out == (8, 42)

    def test_np_subset(self):
        assert XlaRunner(np=4).run(lambda ctx: ctx.mesh.devices.size) == 4

    def test_np_too_large(self):
        with pytest.raises(ValueError):
            XlaRunner(np=99)

    def test_init_shutdown_init_cycle(self):
        """Regression (ISSUE 1 satellite): shutdown() popped the context
        stack but left _default_runner cached, so a second init() could
        ride a stale runner. The cycle must yield a FRESH context honoring
        the new np."""
        from sparkdl_tpu.runner.xla_runner import current_context
        ctx1 = hvd.init(np=4)
        assert ctx1.size == 4
        hvd.shutdown()
        assert current_context() is None
        assert hvd._default_runner is None
        ctx2 = hvd.init(np=8)
        try:
            assert ctx2 is not ctx1
            assert ctx2.size == 8
            assert hvd.size() == 8
        finally:
            hvd.shutdown()
        assert current_context() is None

    def test_hvd_compat_shim(self):
        def main(ctx):
            assert hvd.size() == 8
            assert hvd.rank() == 0
            s = hvd.allreduce(jnp.ones((3,)), average=False)
            np.testing.assert_allclose(np.asarray(s), 8 * np.ones(3))
            m = hvd.allreduce(jnp.full((3,), 2.0), average=True)
            np.testing.assert_allclose(np.asarray(m), 2 * np.ones(3))
            return True

        assert XlaRunner(np=8).run(lambda ctx: main(ctx))


class TestFitLoop:
    def _data(self, n_batches=12, bs=16, seed=0):
        rng = np.random.RandomState(seed)
        w_true = rng.randn(4, 3).astype(np.float32)
        for _ in range(n_batches):
            x = rng.randn(bs, 4).astype(np.float32)
            y = (x @ w_true).argmax(-1)
            yield {"image": x, "label": y}

    def test_fit_learns_and_meters(self, tmp_path):
        runner = XlaRunner(np=8, checkpoint_dir=str(tmp_path / "ckpt"))
        params, _ = _make_problem(seed=3)

        def main(ctx):
            return ctx.fit(loss_fn=softmax_cross_entropy_loss(),
                           params=params, tx=optax.adam(5e-2),
                           apply_fn=_linear_apply,
                           data=self._data(), num_steps=12,
                           checkpoint_every=5, log_every=4)

        res = runner.run(main)
        assert int(res["state"].step) == 12
        losses = [h["loss"] for h in res["history"]]
        assert losses[-1] < losses[0]
        assert res["meter"].steps == 12

    @pytest.mark.parametrize("log_every", [1, 3, 10])
    def test_history_equals_a_loop_that_syncs_at_each_boundary(
            self, log_every):
        """ISSUE 28: retiring a step one step behind its dispatch changes
        WHEN an entry appears, never which steps are logged or their
        values — pinned against the former loop's order (dispatch, then
        convert the boundary step's metrics at once) on a fixed seed."""
        params, _ = _make_problem(seed=9)
        loss_fn, tx, num_steps = softmax_cross_entropy_loss(), \
            optax.sgd(0.1), 11
        batches = list(self._data(n_batches=num_steps, seed=2))

        def synced(ctx):
            state = ctx.put_replicated(
                TrainState.create(_linear_apply, params, tx))
            step_fn = ctx.make_train_step(loss_fn)
            history = []
            for i, batch in enumerate(batches):
                state, m = step_fn(state, ctx.shard_batch(batch))
                if (i + 1) % log_every == 0 or i + 1 == num_steps:
                    history.append((i + 1, float(m["loss"])))
            return history

        want = XlaRunner(np=8).run(synced)
        res = XlaRunner(np=8).run(lambda ctx: ctx.fit(
            loss_fn=loss_fn, params=params, tx=tx, apply_fn=_linear_apply,
            data=iter(batches), num_steps=num_steps, log_every=log_every))
        assert [(h["step"], h["loss"]) for h in res["history"]] == want
        assert all(np.isfinite(h["examples_per_sec_per_chip"])
                   for h in res["history"])

    @pytest.mark.parametrize("accum_steps", [1, 2])
    def test_fit_never_overconsumes_iterator(self, accum_steps):
        """A reused data iterator sits exactly ``num_steps`` PRODUCED
        batches on when fit returns (epoch-style sequential fit() calls on
        one iterator): the feed draws nothing the step loop won't run,
        with the loop's one-step run-ahead in it, and under
        ``accum_steps=2`` a tail batch the crop skips does not count
        against the cap."""
        params, _ = _make_problem(seed=8)

        def stream():
            for k, b in enumerate(self._data(n_batches=10)):
                if accum_steps > 1 and k == 2:
                    # 3 rows < accum 2 x data-axis 8: skipped, not a step
                    yield {"image": np.ones((3, 4), np.float32),
                           "label": np.zeros((3,), np.int64)}
                yield b

        it = stream()
        res = XlaRunner(np=8).run(lambda ctx: ctx.fit(
            loss_fn=softmax_cross_entropy_loss(), params=params,
            tx=optax.sgd(0.1), apply_fn=_linear_apply, data=it,
            num_steps=4, accum_steps=accum_steps, log_every=100))
        assert int(res["state"].step) == 4
        assert sum(1 for _ in it) == 6  # 10 - exactly num_steps produced

    def test_checkpoint_resume(self, tmp_path):
        """Kill-and-restart: a second fit with the same checkpoint_dir must
        resume from the saved step, not from scratch (SURVEY.md §5.3)."""
        ckpt = str(tmp_path / "ckpt")
        params, _ = _make_problem(seed=4)
        kw = dict(loss_fn=softmax_cross_entropy_loss(), params=params,
                  tx=optax.sgd(0.1), apply_fn=_linear_apply,
                  checkpoint_every=3, log_every=100)

        r1 = XlaRunner(np=8, checkpoint_dir=ckpt).run(
            lambda ctx: ctx.fit(data=self._data(), num_steps=6, **kw))
        assert int(r1["state"].step) == 6

        seen = []

        def main2(ctx):
            res = ctx.fit(data=self._data(), num_steps=9, **kw)
            seen.append(res)
            return res

        r2 = XlaRunner(np=8, checkpoint_dir=ckpt).run(main2)
        # resumed at 6 → only 3 more steps ran
        assert int(r2["state"].step) == 9
        assert r2["meter"].steps == 3

    def test_run_with_restarts_fault_injection(self, tmp_path):
        """Fault injection (SURVEY.md §5.3): main_fn dies mid-training once;
        supervision restarts it and it resumes from the checkpoint."""
        ckpt = str(tmp_path / "ckpt")
        params, _ = _make_problem(seed=5)
        attempts = []

        def main(ctx):
            attempts.append(1)
            res = ctx.fit(loss_fn=softmax_cross_entropy_loss(), params=params,
                          tx=optax.sgd(0.1), apply_fn=_linear_apply,
                          data=self._data(), num_steps=4,
                          checkpoint_every=2, log_every=100)
            if len(attempts) == 1:
                raise RuntimeError("injected chip failure")
            return res

        res = XlaRunner(np=8, checkpoint_dir=ckpt).run_with_restarts(
            main, max_restarts=2, backoff_s=0.0)
        assert len(attempts) == 2
        assert int(res["state"].step) == 4


class TestCheckpointManager:
    def test_roundtrip(self, tmp_path):
        params, _ = _make_problem()
        state = TrainState.create(_linear_apply, params, optax.adam(1e-3))
        mngr = CheckpointManager(str(tmp_path), async_save=False)
        mngr.save(7, state, wait=True)
        assert mngr.latest_step() == 7

        fresh = TrainState.create(_linear_apply,
                                  jax.tree_util.tree_map(jnp.zeros_like,
                                                         params),
                                  optax.adam(1e-3))
        restored = mngr.restore(fresh)
        np.testing.assert_allclose(np.asarray(restored.params["w"]),
                                   np.asarray(params["w"]))
        mngr.close()


class TestMutableAndRng:
    """BatchNorm model_state + dropout RNG plumbing through the steps."""

    def _bn_model(self):
        import flax.linen as nn

        class TinyBN(nn.Module):
            @nn.compact
            def __call__(self, x, train=False):
                x = nn.Dense(8)(x)
                x = nn.BatchNorm(use_running_average=not train,
                                 momentum=0.9)(x)
                return nn.Dense(3)(x)

        return TinyBN()

    def test_mutable_step_updates_batch_stats(self, runner):
        from sparkdl_tpu.runner import bn_classifier_loss
        ctx = runner.make_context()
        model = self._bn_model()
        rng = np.random.RandomState(0)
        x = rng.randn(16, 4).astype(np.float32) * 3 + 1
        variables = jax.tree_util.tree_map(np.asarray, model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4))))
        state = TrainState.create(
            None, variables["params"], optax.sgd(0.01),
            model_state={"batch_stats": variables["batch_stats"]})
        step = ctx.make_train_step(bn_classifier_loss(model), mutable=True)
        with ctx.mesh:
            new_state, m = step(state, ctx.shard_batch(
                {"image": x, "label": rng.randint(0, 3, size=(16,))}))
        old_mean = variables["batch_stats"]["BatchNorm_0"]["mean"]
        new_mean = new_state.model_state["batch_stats"]["BatchNorm_0"]["mean"]
        assert not np.allclose(np.asarray(old_mean), np.asarray(new_mean))
        assert np.isfinite(float(m["loss"]))

    def test_mutable_checkpoint_roundtrip_and_legacy(self, tmp_path):
        """model_state survives save/restore; restoring a checkpoint saved
        WITHOUT model_state into a template WITH it keeps the fresh stats
        (the upgrade path) instead of crashing."""
        params = {"w": np.ones((2, 2), np.float32)}
        ms = {"batch_stats": {"mean": np.full((2,), 5.0, np.float32)}}
        mngr = CheckpointManager(str(tmp_path / "a"), async_save=False)
        state = TrainState.create(None, params, optax.sgd(0.1),
                                  model_state=ms)
        mngr.save(1, state, wait=True)
        fresh = TrainState.create(
            None, jax.tree_util.tree_map(np.zeros_like, params),
            optax.sgd(0.1),
            model_state=jax.tree_util.tree_map(np.zeros_like, ms))
        restored = mngr.restore(fresh)
        np.testing.assert_allclose(
            np.asarray(restored.model_state["batch_stats"]["mean"]),
            5.0 * np.ones(2))
        mngr.close()

        # legacy: checkpoint without model_state, template with it
        mngr2 = CheckpointManager(str(tmp_path / "b"), async_save=False)
        mngr2.save(1, TrainState.create(None, params, optax.sgd(0.1)),
                   wait=True)
        restored2 = mngr2.restore(fresh)
        np.testing.assert_allclose(np.asarray(restored2.params["w"]),
                                   np.ones((2, 2)))
        # template's fresh stats kept
        np.testing.assert_allclose(
            np.asarray(restored2.model_state["batch_stats"]["mean"]),
            np.zeros(2))
        mngr2.close()

    def test_with_rng_dropout_plumbing(self, runner):
        """with_rng steps feed fresh per-step dropout noise; without it the
        model runs deterministic. A minimal flax dropout model, not BERT:
        the contract under test is the RUNNER's rng threading into
        ``apply(..., rngs={'dropout': ...})``, and four tiny-BERT
        train-step compiles cost ~13s of tier-1 budget for the same
        proof (ISSUE 10 headroom satellite; BERT's own dropout behavior
        is covered in test_transformer_models)."""
        import flax.linen as nn

        class DropNet(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = True):
                h = nn.Dense(8)(x)
                h = nn.Dropout(0.5, deterministic=not train)(h)
                return nn.Dense(2)(h)

        ctx = runner.make_context()
        model = DropNet()
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.uniform(size=(8, 16)).astype(np.float32),
                 "label": rng.randint(0, 2, size=(8,))}
        variables = jax.tree_util.tree_map(np.asarray, model.init(
            jax.random.PRNGKey(0), jnp.asarray(batch["input_ids"]),
            train=False))

        def loss_fn(params, apply_fn, batch, rng=None):
            det = rng is None
            logits = model.apply(
                params, batch["input_ids"], train=not det,
                rngs=None if det else {"dropout": rng})
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["label"]).mean()
            return loss, {}

        def one(with_rng, seed):
            state = TrainState.create(None, variables, optax.sgd(0.0))
            step = ctx.make_train_step(loss_fn, with_rng=with_rng)
            if with_rng:
                from sparkdl_tpu.runner import make_train_step
                step = make_train_step(loss_fn, ctx.mesh, with_rng=True,
                                       rng_seed=seed)
            with ctx.mesh:
                _, m = step(state, ctx.shard_batch(batch))
            return float(m["loss"])

        det1, det2 = one(False, 0), one(False, 1)
        assert det1 == det2  # deterministic path ignores seed
        s0, s1 = one(True, 0), one(True, 1)
        assert s0 != s1  # different dropout noise → different loss


def test_throughput_meter_warmup():
    m = ThroughputMeter(n_chips=8, warmup_steps=1)
    m.update(64)  # warmup (compile) step — excluded
    for _ in range(5):
        m.update(64)
    s = m.summary()
    assert s["examples"] == 5 * 64
    assert s["n_chips"] == 8
    assert s["examples_per_sec"] > 0
