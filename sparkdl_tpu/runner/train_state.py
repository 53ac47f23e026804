"""Train state and compiled SPMD train steps.

The reference's distributed-training core was Horovod's
``DistributedOptimizer``: an *outside-the-graph* hook that intercepted
gradients after backprop and ring-allreduced them over NCCL (SURVEY.md §3.5).
The TPU-native inversion lives here: the gradient average is **inside** the
compiled program — either implicitly (``make_train_step``: batch sharded over
the ``data`` mesh axis, params replicated, XLA's SPMD partitioner inserts the
cross-chip reduce) or explicitly (``make_shard_map_step``: ``jax.lax.pmean``
over the mesh axis under ``shard_map`` — the literal "psum over ICI" of the
BASELINE north star). For stateless models the two produce identical
updates; the explicit form exists so collective semantics are testable and
visible. With ``mutable=True`` (BatchNorm) they intentionally differ — see
the per-function docstrings.

Design rules (TPU/XLA):
- one compilation per (step_fn, shapes): state/batch shapes are static.
- donation: the old state buffer is donated to the new one, so optimizer
  state never doubles HBM.
- loss is computed in float32 even under bfloat16 params (mixed precision à
  la MXU: matmuls in bf16, reductions in f32).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import scopes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Minimal functional train state (flax-style, dependency-free).

    ``apply_fn`` and ``tx`` are static (not traced); params/opt_state/step are
    the pytree leaves that flow through the compiled step. ``model_state``
    carries non-trainable collections (BatchNorm running stats) — updated by
    the step, never differentiated.
    """
    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any
    apply_fn: Callable = dataclasses.field(metadata=dict(static=True))
    tx: optax.GradientTransformation = dataclasses.field(
        metadata=dict(static=True))

    @classmethod
    def create(cls, apply_fn: Callable, params: Any,
               tx: optax.GradientTransformation,
               model_state: Any = None) -> "TrainState":
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params),
                   model_state={} if model_state is None else model_state,
                   apply_fn=apply_fn, tx=tx)

    def apply_gradients(self, grads: Any) -> "TrainState":
        # flax names the model's operations in the profile; the step's own
        # work gets its scope here, once for every step variant
        with scopes.layer("optimizer_update"):
            updates, new_opt = self.tx.update(grads, self.opt_state,
                                              self.params)
            params = optax.apply_updates(self.params, updates)
        return dataclasses.replace(self, step=self.step + 1, params=params,
                                   opt_state=new_opt)


def state_sharding(state: TrainState, mesh: Mesh,
                   rules: Callable[[tuple, Any], P] | None = None):
    """Sharding pytree for a TrainState: replicated by default (pure DP), or
    per-leaf PartitionSpec via ``rules(path, leaf) -> P`` for TP/FSDP."""
    if rules is None:
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state)

    def spec(path, leaf):
        return NamedSharding(mesh, rules(path, leaf))

    return jax.tree_util.tree_map_with_path(spec, state)


def make_train_step(loss_fn: Callable, mesh: Mesh, data_axis: str = "data",
                    param_rules: Callable | None = None,
                    donate: bool = True, mutable: bool = False,
                    with_rng: bool = False, rng_seed: int = 0,
                    remat: bool = False, accum_steps: int = 1,
                    batch_spec: P | None = None) -> Callable:
    """Compile an SPMD train step: ``step(state, batch) -> (state, metrics)``.

    ``loss_fn(params, apply_fn, batch) -> (loss, aux_dict)``; with
    ``mutable=True`` (BatchNorm-style models):
    ``loss_fn(params, model_state, apply_fn, batch) -> (loss, aux,
    new_model_state)``. With ``with_rng=True`` the loss_fn additionally
    receives ``rng=`` — a per-step PRNG key (folded from ``rng_seed`` by step
    count) for dropout and other stochastic layers. The batch enters sharded
    over ``data_axis``; params follow ``param_rules`` (default: replicated =
    pure DP). The cross-chip gradient mean is inserted by XLA — no explicit
    collective in user code. Under this path batch statistics reduce over the
    *global* batch (sync-BN for free: the batch dim is sharded, the mean is
    global).

    ``remat=True`` wraps the loss forward in ``jax.checkpoint``: the
    backward pass recomputes activations instead of keeping them in HBM —
    the standard FLOPs-for-memory trade that unlocks larger per-chip
    batches when activation memory (not weights) is the HBM ceiling. Same
    gradients either way (it is a scheduling change, not a math change).

    ``accum_steps=k`` > 1 gradient-accumulates: the batch splits into k
    equal microbatches scanned sequentially (one microbatch of
    activations resident at a time — composes with remat), gradients
    average across them, ONE optimizer update per step. For mean-reduced
    losses this equals the full-batch gradient exactly. The batch's
    leading dim must divide by k (and by k x the data-axis size for even
    shards). Not supported with ``mutable`` (BatchNorm batch stats would
    silently become last-microbatch stats).

    ``batch_spec`` overrides the default rows-over-``data_axis`` entry
    layout (e.g. ``P("data", "sp")`` pins sequence sharding for the
    DP×TP×SP composition). Caveat (advisor): the spec applies
    **rank-truncated to EVERY batch leaf** — there is one spec, not a
    per-leaf pytree. Under ``P("data", "sp")`` a 1-D ``[B]`` label leaf
    constrains as ``P("data")`` (truncation does the right thing), but
    ANY 2-D leaf gets its second dim sp-sharded, token dim or not: a
    ``[B, K]`` float side-input (per-example weights, aux features) is
    silently split over ``sp`` and XLA inserts a reshard at first
    non-sequence use. Keep non-token >=2-D leaves out of the batch (or
    feed them replicated outside it) when pinning a multi-axis spec; an
    optional per-leaf spec pytree is the natural extension if that
    becomes limiting.
    """
    if accum_steps > 1 and mutable:
        raise ValueError(
            "accum_steps > 1 with mutable=True is not supported: BatchNorm "
            "statistics would come from single microbatches, silently "
            "changing the model's normalization semantics")
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    base_key = jax.random.PRNGKey(rng_seed)

    def step(state: TrainState, batch):
        if param_rules is not None:
            # Pin the TP/FSDP layout inside the program: without the
            # constraint XLA would keep whatever placement params arrived
            # with (fully replicated for host arrays).
            state = dataclasses.replace(
                state, params=jax.tree_util.tree_map_with_path(
                    lambda path, leaf: jax.lax.with_sharding_constraint(
                        leaf, NamedSharding(mesh, param_rules(path, leaf))),
                    state.params))
        kw = ({"rng": jax.random.fold_in(base_key, state.step)}
              if with_rng else {})
        if mutable:
            def loss_wrapped(params):
                loss, aux, new_ms = loss_fn(params, state.model_state,
                                            state.apply_fn, batch, **kw)
                return loss.astype(jnp.float32), (aux, new_ms)

            if remat:
                loss_wrapped = jax.checkpoint(loss_wrapped)
            (loss, (aux, new_ms)), grads = jax.value_and_grad(
                loss_wrapped, has_aux=True)(state.params)
            new_state = dataclasses.replace(
                state.apply_gradients(grads), model_state=new_ms)
        elif accum_steps == 1:
            def loss_wrapped(params):
                loss, aux = loss_fn(params, state.apply_fn, batch, **kw)
                return loss.astype(jnp.float32), aux

            if remat:
                loss_wrapped = jax.checkpoint(loss_wrapped)
            (loss, aux), grads = jax.value_and_grad(
                loss_wrapped, has_aux=True)(state.params)
            new_state = state.apply_gradients(grads)
        else:
            # Gradient accumulation: lax.scan over k microbatches — one
            # microbatch of activations in flight, grads averaged, one
            # optimizer update. Equals the full-batch gradient for
            # mean-reduced losses (any equal-size row partition does).
            n_shard = int(mesh.shape[data_axis])

            def micro_split(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"batch dim {x.shape[0]} not divisible by "
                        f"accum_steps={accum_steps}")
                if x.shape[0] % (accum_steps * n_shard) == 0:
                    # Shard-aligned split: each chip's LOCAL rows divide
                    # among the k microbatches, so every microbatch stays
                    # evenly sharded over the data axis with zero
                    # cross-chip movement (row regrouping is free: the
                    # loss is mean-reduced, so any equal-size partition
                    # yields the same averaged gradient).
                    local = x.shape[0] // (accum_steps * n_shard)
                    x = x.reshape((n_shard, accum_steps, local)
                                  + x.shape[1:])
                    x = jnp.moveaxis(x, 1, 0)
                    x = x.reshape((accum_steps, n_shard * local)
                                  + x.shape[3:])
                    # microbatch layout = leading accum dim + the step's
                    # batch spec (rank-truncated per leaf): a batch_spec
                    # pinning seq-over-sp must survive the split, not be
                    # re-replicated here
                    eff = batch_spec if batch_spec is not None \
                        else P(data_axis)
                    return jax.lax.with_sharding_constraint(
                        x, NamedSharding(
                            mesh, P(None, *tuple(eff)[:x.ndim - 1])))
                # Not enough rows per chip for the aligned split —
                # contiguous reshape; GSPMD may reshard across chips.
                return x.reshape((accum_steps, -1) + x.shape[1:])

            micro = jax.tree_util.tree_map(micro_split, batch)

            def micro_loss(params, mb, key):
                mkw = {"rng": key} if with_rng else {}
                loss, aux = loss_fn(params, state.apply_fn, mb, **mkw)
                return loss.astype(jnp.float32), aux

            if remat:
                micro_loss = jax.checkpoint(micro_loss)
            grad_fn = jax.value_and_grad(micro_loss, has_aux=True)
            step_key = kw.get("rng", base_key)

            def body(carry, idx_mb):
                idx, mb = idx_mb
                gsum, lsum = carry
                (loss, aux), g = grad_fn(
                    state.params, mb, jax.random.fold_in(step_key, idx))
                # accumulate in f32 whatever the param dtype — k bf16
                # additions would round away small-gradient contributions
                gsum = jax.tree_util.tree_map(
                    lambda s, x: s + x.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), aux

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), auxs = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)),
                (jnp.arange(accum_steps), micro))
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / accum_steps).astype(p.dtype),
                gsum, state.params)
            loss = lsum / accum_steps
            aux = jax.tree_util.tree_map(lambda a: a.mean(axis=0), auxs)
            new_state = state.apply_gradients(grads)
        metrics = dict(loss=loss, **aux)
        return new_state, metrics

    # ``batch_spec`` overrides the default rows-over-data_axis layout —
    # e.g. P("data", "sp") pins SEQUENCE sharding through the step entry
    # for the DP×TP×SP composition, so the constraint doesn't silently
    # replicate the seq dim that ring attention then re-shards. Applied
    # per leaf with the spec truncated to the leaf's rank (a [B] label
    # leaf under P("data", "sp") constrains as P("data")), matching
    # make_rules' truncation convention.
    entry_spec = batch_spec if batch_spec is not None else P(data_axis)

    def _constrain(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(
                mesh, P(*tuple(entry_spec)[:getattr(x, "ndim", 0)])))
    # state sharding resolved lazily at first call (needs the concrete state
    # treedef); jax.jit handles that via in_shardings=None for the state and
    # explicit constraint on the batch.
    def with_constraints(state, batch):
        batch = jax.tree_util.tree_map(_constrain, batch)
        return step(state, batch)

    return jax.jit(with_constraints, donate_argnums=(0,) if donate else ())


def make_shard_map_step(loss_fn: Callable, mesh: Mesh,
                        data_axis: str = "data",
                        donate: bool = True,
                        mutable: bool = False,
                        with_rng: bool = False,
                        rng_seed: int = 0,
                        remat: bool = False,
                        accum_steps: int = 1) -> Callable:
    """The explicit-collective twin of ``make_train_step``.

    Runs per-shard forward/backward under ``shard_map`` and averages gradients
    with ``jax.lax.pmean`` over the mesh axis — the direct analogue of
    Horovod's ring-allreduce, except compiled into the XLA program so the
    collective overlaps with surrounding compute on ICI.

    ``mutable=True`` note: BatchNorm here normalizes by *per-shard local*
    batch statistics (each chip sees its own slice), and only the updated
    running stats are pmean-ed — exactly Horovod's default (non-sync) BN.
    The implicit ``make_train_step`` instead reduces batch stats over the
    global batch (sync-BN). The two therefore diverge numerically for BN
    models at small per-chip batch; pick by BN semantics, not by style.

    ``remat=True`` composes (jax.checkpoint inside the shard body);
    ``accum_steps`` is only implemented on the implicit path.
    """
    if accum_steps != 1:
        raise ValueError(
            "accum_steps is not supported with explicit_collectives / "
            "make_shard_map_step — use the implicit make_train_step path")
    shard_map = jax.shard_map
    base_key = jax.random.PRNGKey(rng_seed)

    def per_shard(state: TrainState, batch):
        # Distinct dropout noise per shard: fold in the shard index too.
        kw = ({"rng": jax.random.fold_in(
            jax.random.fold_in(base_key, state.step),
            jax.lax.axis_index(data_axis))} if with_rng else {})
        if mutable:
            def loss_wrapped(params):
                loss, aux, new_ms = loss_fn(params, state.model_state,
                                            state.apply_fn, batch, **kw)
                return loss.astype(jnp.float32), (aux, new_ms)

            if remat:
                loss_wrapped = jax.checkpoint(loss_wrapped)
            (loss, (aux, new_ms)), grads = jax.value_and_grad(
                loss_wrapped, has_aux=True)(state.params)
            with scopes.layer("grad_allreduce"):
                new_ms = jax.lax.pmean(new_ms, axis_name=data_axis)
        else:
            def loss_wrapped(params):
                loss, aux = loss_fn(params, state.apply_fn, batch, **kw)
                return loss.astype(jnp.float32), aux

            if remat:
                loss_wrapped = jax.checkpoint(loss_wrapped)
            (loss, aux), grads = jax.value_and_grad(
                loss_wrapped, has_aux=True)(state.params)
            new_ms = None
        # THE collective: gradient mean over the data axis (ICI ring).
        with scopes.layer("grad_allreduce"):
            grads = jax.lax.pmean(grads, axis_name=data_axis)
            loss = jax.lax.pmean(loss, axis_name=data_axis)
            aux = jax.lax.pmean(aux, axis_name=data_axis)
        new_state = state.apply_gradients(grads)
        if mutable:
            new_state = dataclasses.replace(new_state, model_state=new_ms)
        return new_state, dict(loss=loss, **aux)

    def step(state, batch):
        batch_spec = jax.tree_util.tree_map(lambda _: P(data_axis), batch)
        state_spec = jax.tree_util.tree_map(lambda _: P(), state)
        return shard_map(
            per_shard, mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False)(state, batch)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_eval_step(eval_fn: Callable, mesh: Mesh,
                   data_axis: str = "data") -> Callable:
    """Compile ``eval(state, batch) -> metrics`` with the batch sharded over
    the data axis; metrics are reduced on device."""
    batch_sharding = NamedSharding(mesh, P(data_axis))

    def step(state: TrainState, batch):
        batch = jax.lax.with_sharding_constraint(batch, batch_sharding)
        return eval_fn(state.params, state.apply_fn, batch)

    return jax.jit(step)


def bn_classifier_loss(model, preprocess: Callable | None = None,
                       label_key: str = "label",
                       input_key: str = "image") -> Callable:
    """Stateful classification loss for flax BatchNorm models (use with
    ``mutable=True`` steps): params = the 'params' collection; model_state
    carries 'batch_stats', updated in train mode each step."""

    def loss_fn(params, model_state, _apply_fn, batch):
        variables = {"params": params, **model_state}
        x = batch[input_key]
        if preprocess is not None:
            x = preprocess(x)
        logits, new_vars = model.apply(variables, x, train=True,
                                       mutable=["batch_stats"])
        logits = logits.astype(jnp.float32)
        labels = batch[label_key]
        onehot = jax.nn.one_hot(labels, logits.shape[-1])
        loss = optax.softmax_cross_entropy(logits, onehot).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return loss, {"accuracy": acc.astype(jnp.float32)}, dict(new_vars)

    return loss_fn


def softmax_cross_entropy_loss(num_classes: int | None = None,
                               label_key: str = "label",
                               input_key: str = "image") -> Callable:
    """Standard classification loss_fn for the runner: bf16-friendly
    (logits upcast to f32 before the softmax reduction)."""

    def loss_fn(params, apply_fn, batch):
        logits = apply_fn(params, batch[input_key])
        logits = logits.astype(jnp.float32)
        labels = batch[label_key]
        if labels.ndim == logits.ndim:  # one-hot
            onehot = labels.astype(jnp.float32)
        else:
            onehot = jax.nn.one_hot(labels, logits.shape[-1])
        loss = optax.softmax_cross_entropy(logits, onehot).mean()
        acc = (logits.argmax(-1) == (labels if labels.ndim < logits.ndim
                                     else labels.argmax(-1))).mean()
        return loss, {"accuracy": acc.astype(jnp.float32)}

    return loss_fn
