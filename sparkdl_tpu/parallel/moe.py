"""Expert parallelism — Switch/GShard-style mixture-of-experts.

Absent from the reference (SURVEY.md §2.4: EP "out of scope; none of the
[BASELINE] configs are MoE") — included to complete the parallelism
inventory the TPU way: experts hold stacked parameters with a leading
``(num_experts, ...)`` axis sharded over an ``ep`` mesh axis, and token
routing is expressed as dense one-hot dispatch/combine einsums (the
GShard formulation) — XLA lowers the sharded einsums to all_to_all-style
collectives over ICI; no hand-written routing code.

Top-1 (Switch) routing with capacity: each token goes to its argmax expert;
tokens beyond ``capacity_factor * tokens/experts`` at an expert are dropped
(pass through the residual). The load-balancing auxiliary loss is sowed
into the ``intermediates`` collection as ``moe_aux_loss``.

:class:`RoutedExperts` is its successor: top-k over sigmoid or softmax
scores, no token ever dropped, told which experts it holds, grouped matrix
products over the rows sorted by expert. Its cost goes with the assignments
that land on the held experts, not with tokens x experts x capacity.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..utils import scopes


class _ExpertFFN(nn.Module):
    d_ff: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.d_ff, dtype=self.dtype, name="wi")(x)
        return nn.Dense(x.shape[-1], dtype=self.dtype, name="wo")(
            nn.gelu(h))


class SwitchMoE(nn.Module):
    """Top-1 routed MoE FFN: (B, T, D) → (B, T, D).

    Parameters live under ``experts`` with a leading num_experts axis —
    shard with ``moe_rules`` (P("ep") on that axis).
    """
    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        e = self.num_experts
        n = b * t
        cap = max(1, int(self.capacity_factor * n / e))
        xf = x.reshape(n, d)

        gate_logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32))                       # (N, E)
        probs = jax.nn.softmax(gate_logits, axis=-1)
        expert_idx = jnp.argmax(probs, axis=-1)           # (N,)
        gate = jnp.max(probs, axis=-1)                    # (N,)

        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (N, E)
        # position of each token in its expert's queue (0-based; -1 for
        # not-this-expert, which one_hot maps to all-zeros)
        pos = (jnp.cumsum(onehot, axis=0) * onehot - 1.0).astype(jnp.int32)
        keep = (pos >= 0) & (pos < cap)
        dispatch = jnp.where(keep, onehot, 0.0)           # (N, E)
        slot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)  # (N, E, C)
        dispatch3 = dispatch[..., None] * slot            # (N, E, C)

        # (E, C, D): the sharded-einsum boundary — with experts on "ep",
        # XLA turns this into the token all_to_all
        expert_in = jnp.einsum("nec,nd->ecd", dispatch3,
                               xf.astype(jnp.float32)).astype(self.dtype)

        experts = nn.vmap(
            _ExpertFFN,
            in_axes=0, out_axes=0,
            variable_axes={"params": 0},
            split_rngs={"params": True},
        )(self.d_ff, self.dtype, name="experts")
        expert_out = experts(expert_in)                   # (E, C, D)

        combine3 = dispatch3 * gate[:, None, None]        # (N, E, C)
        out = jnp.einsum("nec,ecd->nd", combine3,
                         expert_out.astype(jnp.float32))

        # Switch load-balancing loss: E * sum_e(frac_tokens_e * mean_prob_e)
        frac_tokens = jnp.mean(onehot, axis=0)
        mean_probs = jnp.mean(probs, axis=0)
        self.sow("intermediates", "moe_aux_loss",
                 e * jnp.sum(frac_tokens * mean_probs))

        return out.reshape(b, t, d).astype(x.dtype)


def sigmoid_topk_route(h, w_router, expert_bias, k: int, *,
                        norm_topk_prob: bool = True, scaling: float = 1.0):
    """``(idx [N, k] int32, w [N, k] float32)`` of ``h [N, D]``: sigmoid
    scores over every expert in float32 (the product at ``highest``: it is
    a thousandth of the layer's work, and near-ties decide which expert
    runs); the top ``k`` of score + ``expert_bias``, the bias taking part
    in the selection only; the weights are the scores themselves, over
    their sum plus 1e-6 where ``norm_topk_prob``."""
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick = s if expert_bias is None else \
        s + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(pick, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), w * scaling


def softmax_topk_route(h, w_router, k: int, *, norm_topk_prob: bool = True,
                       scaling: float = 1.0):
    """``(idx [N, k] int32, w [N, k] float32)`` of ``h [N, D]``: softmax
    scores over every expert in float32 (the product at ``highest``, as
    :func:`sigmoid_topk_route`'s); the top ``k`` of them; the weights are the
    scores themselves, over their sum where ``norm_topk_prob``. No bias takes
    part."""
    s = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(s, k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scaling


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """``x[perm]`` for a permutation whose inverse the caller has: the
    gradient is a gather by ``inv_perm``, never a scatter-add."""
    return x[perm]


def _permute_rows_fwd(x, perm, inv_perm):
    return x[perm], (perm, inv_perm)


def _permute_rows_bwd(saved, g):
    perm, inv_perm = saved
    return g[inv_perm], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def held_experts_ffn(h, idx, w, w1, w3, w2, first_held: int = 0,
                     interpret: bool | None = None, activation: str = "silu"):
    """The held experts' part of a routed gated layer, and its counters.

    ``h [N, D]``; ``idx, w [N, k]`` from the router, over ALL experts;
    ``w1, w3 [H, D, F]`` and ``w2 [H, F, D]`` are the H held experts
    ``first_held .. first_held + H - 1``, each ``E_e(h) = (act(h w1) * (h
    w3)) w2`` with ``act`` the gate's ``activation``: ``"silu"`` (SwiGLU) or
    ``"relu"`` (ReGLU). Returns ``sum over the picks e of a token that are
    held of w_e * E_e(h)``, ``[N, D]`` in ``h``'s type: what the absent
    experts would add is left out.

    No token is dropped. The ``N x k`` assignments are numbered PICK-MAJOR
    (pick ``j`` of token ``t`` is assignment ``j * N + t``) and sorted by
    expert (those of absent experts last; inside an expert by pick, then
    token), and each projection is ONE grouped product over the held
    experts' groups (``jax.lax.ragged_dot``). Shapes are static: the row
    buffer holds all ``N x k`` assignments, the worst case; the first
    ``n_held`` sorted slots are the held groups' and the slots past them (the
    dead slots) belong to no group.

    The row movement around the products touches the ``n_held`` live slots
    alone (``ops/moe_rows.py``, a Pallas kernel pair; ``interpret`` as
    there), forward and backward: the dispatch gathers ``h``'s row of each
    live slot (``moe_gather_rows``) and the combine sums each token's live
    slots, weighted, in float32 (``moe_scatter_rows``); going backward the
    dispatch is that sum (weight 1) and the combine the gather of ``w * g``,
    with the weights' gradient read beside it. A grouped product neither
    reads nor WRITES the dead slots, going forward or going backward: on the
    chip they hold whatever was there, ``inf`` or ``nan``. No kernel reads
    them: each walks the slots below ``n_held``, its trip count read on the
    chip, so the dead rows of ``y`` and of the products' ``d rows`` never
    enter a sum, and the dead rows the dispatch and the combine's gradient
    leave are never read in their turn. What the combine's gradient gives
    the weights of dead slots is selected away (a SELECT, never a product by
    zero).

    Under ``"relu"`` two counters more: ``moe_reglu_units``, the gate's
    units on the live slots (``n_held x F``), and ``moe_reglu_active``, those
    of them the ReLU leaves nonzero.
    """
    from ..ops import moe_rows      # ops imports this package's sharding

    if activation not in _GATES:
        raise ValueError(f"activation {activation!r}: {' or '.join(_GATES)}")
    n, k = idx.shape
    held = w1.shape[0]
    with scopes.layer("moe_dispatch"):
        flat = idx.T.reshape(k * n) - first_held        # pick j, token t: j*n+t
        is_held = (flat >= 0) & (flat < held)
        local = jnp.where(is_held, flat, held)          # absent sort last
        order = jnp.argsort(local, stable=True).astype(jnp.int32)
        inv_order = jnp.argsort(order).astype(jnp.int32)
        group_sizes = jnp.bincount(local, length=held + 1)[:held].astype(
            jnp.int32)
        n_held = jnp.sum(group_sizes)
        tok, n_live = order % n, n_held.reshape(1)
        rows = moe_rows.dispatch(h, tok, n_live, interpret)
    with scopes.layer("moe_experts"):
        dot = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                preferred_element_type=h.dtype)
        a = dot(rows, w1.astype(h.dtype))
        b = dot(rows, w3.astype(h.dtype))
        y = dot(_GATES[activation](a) * b, w2.astype(h.dtype))
        counters = {}
        if activation == "relu":
            # the dead slots' rows of ``a`` hold whatever was there: a select
            live = jnp.arange(n * k) < n_held
            counters["moe_reglu_active"] = jnp.sum(
                jnp.where(live[:, None], a > 0, False)).astype(jnp.float32)
            counters["moe_reglu_units"] = (n_held * a.shape[1]).astype(
                jnp.float32)
    with scopes.layer("moe_combine"):
        w_slot = _permute_rows(w.T.reshape(k * n), order, inv_order)
        out = moe_rows.combine(y, w_slot, tok, n_live, n, interpret)
    sizes = group_sizes.astype(jnp.float32)
    assigned_here = jnp.sum(is_held)
    counters.update({
        "moe_assignments": jnp.float32(n * k),
        "moe_assignments_held": assigned_here.astype(jnp.float32),
        "moe_held_load_max": jnp.max(sizes),
        "moe_held_load_mean": jnp.mean(sizes),
        # every held assignment has a row in a group: the buffer is N x k
        "moe_dropped": (assigned_here - n_held).astype(jnp.float32)})
    return out, counters


class RoutedExperts(nn.Module):
    """No-drop top-k routed gated experts: ``(B, T, D) -> (B, T, D)``.

    The router scores all ``num_experts`` by ``scoring``, sigmoid or softmax
    scores (:func:`sigmoid_topk_route`, :func:`softmax_topk_route`), of
    ``route_from`` where the call is given one (a tensor of ``x``'s shape: a
    layer whose router reads its input before the attention) and of ``x``
    itself where not; this layer holds the experts ``held[0] .. held[0] +
    held[1] - 1`` (``None``: all of them) and returns their part of the
    result (:func:`held_experts_ffn`, the gate's ``activation`` ``"silu"`` or
    ``"relu"``). ``expert_bias`` (sigmoid scores only) moves the selection
    only, takes no gradient, and no rule here moves it.
    Parameters: ``router/kernel``, ``expert_bias``, and ``experts/{w1,w3,w2}``
    with a leading held-experts axis (``moe_rules`` shards it over ``ep``).
    The layer's counters are summed into the ``counters`` collection.
    """
    num_experts: int
    top_k: int
    d_ff: int
    held: tuple | None = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    dtype: Any = jnp.float32
    scoring: str = "sigmoid"
    activation: str = "silu"

    @nn.compact
    def __call__(self, x, route_from=None):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r}: sigmoid or softmax")
        b, t, d = x.shape
        first, held = self.held or (0, self.num_experts)
        init = nn.initializers.normal(0.02)
        xf = x.reshape(b * t, d).astype(self.dtype)
        rf = xf if route_from is None else \
            route_from.reshape(b * t, d).astype(self.dtype)
        w_router = self.param("router", lambda k, s: {
            "kernel": init(k, s)}, (d, self.num_experts))["kernel"]
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (self.num_experts,)) \
            if self.use_expert_bias and self.scoring == "sigmoid" else None
        experts = self.param("experts", lambda k, _: {
            n: init(kk, s) for n, kk, s in zip(
                ("w1", "w3", "w2"), jax.random.split(k, 3),
                ((held, d, self.d_ff), (held, d, self.d_ff),
                 (held, self.d_ff, d)))}, None)
        with scopes.layer("moe_router"):
            how = dict(norm_topk_prob=self.norm_topk_prob,
                       scaling=self.routed_scaling_factor)
            if self.scoring == "sigmoid":
                idx, w = sigmoid_topk_route(rf, w_router, bias, self.top_k,
                                            **how)
            else:
                idx, w = softmax_topk_route(rf, w_router, self.top_k, **how)
        out, counters = held_experts_ffn(
            xf, idx, w, experts["w1"], experts["w3"], experts["w2"], first,
            activation=self.activation)
        for name, v in counters.items():
            self.sow("counters", name, v, init_fn=lambda: jnp.float32(0),
                     reduce_fn=lambda a, c: a + c)
        return out.reshape(b, t, d).astype(x.dtype)


def moe_rules(base_rules: Callable | None = None,
              ep_axis: str = "ep") -> Callable:
    """Sharding rules: expert-stacked params (path contains ``experts``)
    get P(ep_axis) on the leading axis; everything else falls through to
    ``base_rules`` (or replicated)."""
    from .sharding import path_str

    def rules(path, leaf) -> P:
        # exact path-segment match, not substring: a layer named
        # "experts_gate" must NOT be expert-sharded
        if "experts" in path_str(path).split("/"):
            return P(ep_axis, *([None] * (leaf.ndim - 1)))
        if base_rules is not None:
            return base_rules(path, leaf)
        return P()

    return rules


def moe_aux_loss(intermediates) -> jnp.ndarray:
    """Sum every sowed ``moe_aux_loss`` in an intermediates collection."""
    from ..utils.trees import flatten_with_paths

    total = 0.0
    for path, leaf in flatten_with_paths(intermediates):
        if "moe_aux_loss" in path.split("/"):
            total = total + jnp.sum(leaf)
    return jnp.asarray(total)
