"""Sharding-rule helpers: pattern-matched PartitionSpecs over param pytrees.

The reference had exactly one parallelism layout (replicated params, Horovod
DP — SURVEY.md §2.4); everything beyond it is TPU-native design space. This
module is the one place layouts are expressed: a rule list maps param-path
patterns to ``PartitionSpec``s, and everything downstream (train steps,
checkpointing, the dryrun) consumes the resulting sharding pytree. XLA turns
the specs into ICI collectives; no manual comms anywhere.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, Callable, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def path_str(path) -> str:
    """jax key-path → '/'-joined string (e.g. 'params/dense/kernel')."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def make_rules(patterns: Sequence[tuple[str, P]],
               default: P = P()) -> Callable[[tuple, Any], P]:
    """Build a ``rules(path, leaf) -> PartitionSpec`` fn from
    (regex, spec) pairs, first match wins. Regexes are ``re.search`` over the
    '/'-joined param path."""
    compiled = [(re.compile(pat), spec) for pat, spec in patterns]

    def match_str(s: str, leaf) -> P:
        for rx, spec in compiled:
            if rx.search(s):
                # Drop trailing axes the leaf doesn't have (a bias matching a
                # kernel rule).
                nd = getattr(leaf, "ndim", None)
                if nd is not None and len(spec) > nd:
                    spec = P(*spec[:nd])
                return spec
        return default

    def rules(path, leaf) -> P:
        return match_str(path_str(path), leaf)

    rules.match_str = match_str
    return rules


def shard_params(params: Any, mesh: Mesh, rules: Callable) -> Any:
    """Place a param pytree according to the rules (host → sharded HBM)."""
    def put(path, leaf):
        return jax.device_put(leaf, NamedSharding(mesh, rules(path, leaf)))

    return jax.tree_util.tree_map_with_path(put, params)


def sharding_pytree(params: Any, mesh: Mesh, rules: Callable) -> Any:
    """NamedSharding pytree (for jit in_shardings / orbax restore args)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, rules(path, leaf)), params)


def describe(params: Any, rules: Callable) -> dict[str, str]:
    """path → spec string, for debugging/sharding audits."""
    out = {}

    def visit(path, leaf):
        out[path_str(path)] = str(rules(path, leaf))
        return leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return out


# ---------------------------------------------------------------------------
# Canonical transformer TP layouts (Megatron-style, mesh axis 'model')
# ---------------------------------------------------------------------------

def transformer_tp_rules(model_axis: str = "model",
                         data_axis: str | None = None,
                         mesh: Mesh | None = None) -> Callable:
    """Tensor-parallel rules for the transformer families in ``models/``:

    - attention q/k/v projections: shard the head (output) dim → each chip
      computes a head subset; the out-projection shards its *input* dim so
      the follow-up matmul contracts locally and one psum restores the sum.
    - MLP: up-projection output-sharded, down-projection input-sharded —
      the classic pair that needs exactly one allreduce per block.
    - embedding tables (vocab, hidden): hidden-dim sharded — GSPMD
      all-gathers the looked-up rows, avoiding the masked-lookup+psum dance
      of vocab-parallel embeddings; lm_head (hidden, vocab) is genuinely
      vocab-sharded.
    - everything else (norms, biases): replicated.

    With ``data_axis`` set, the TP rules are extended to the 2-D
    FSDP×TP layout via :func:`fsdp_rules` (each kernel's first
    TP-unsharded dim additionally shards over the data axis; pass
    ``mesh`` so indivisible dims — a 50257 vocab on data=4 — are skipped,
    see the :func:`fsdp_rules` docstring).
    """
    m = model_axis
    # (/base)? skips the LoRADense wrapper segment (models/llama.py): the
    # frozen kernel lives at e.g. 'q_proj/base/kernel'.
    rules = make_rules([
        # kernel_scale rules MUST precede the kernel rules: re.search
        # lets '.../kernel' match inside '.../kernel_scale', and the
        # trailing-axis drop would then collapse the 2-D kernel spec
        # onto the 1-D scale — replicating a column-parallel scale that
        # must shard with the output channels it scales (QuantDense
        # int8 serving, ISSUE 18). Row-parallel kernels shard their
        # INPUT dim, so their per-output scale replicates.
        (r"(q_proj|k_proj|v_proj|query|key|value)(/base)?/kernel_scale",
         P(m)),
        (r"(o_proj|out_proj|attention_output)(/base)?/kernel_scale",
         P()),
        (r"(up_proj|gate_proj|intermediate|fc1|mlp_in)(/base)?"
         r"/kernel_scale", P(m)),
        (r"(down_proj|output_dense|fc2|mlp_out)(/base)?/kernel_scale",
         P()),
        (r"(q_proj|k_proj|v_proj|query|key|value)(/base)?/kernel",
         P(None, m)),
        (r"(o_proj|out_proj|attention_output)(/base)?/kernel", P(m, None)),
        (r"(up_proj|gate_proj|intermediate|fc1|mlp_in)(/base)?/kernel",
         P(None, m)),
        (r"(down_proj|output_dense|fc2|mlp_out)(/base)?/kernel", P(m, None)),
        (r"(embed_tokens|embedding|lm_head|word_embeddings)/(embedding|kernel)",
         P(None, m)),
    ])
    return fsdp_rules(rules, data_axis, mesh=mesh) if data_axis else rules


def fsdp_rules(base_rules: Callable | None = None,
               data_axis: str = "data",
               mesh: Mesh | None = None) -> Callable:
    """ZeRO-3 / FSDP-style parameter sharding, GSPMD-idiomatic: every
    >=2-D kernel additionally shards its first base-unsharded dim over
    the DATA axis, so per-chip param (and optimizer-state) residency
    drops by the data-axis size. XLA inserts the all-gather before each
    use and the corresponding reduce-scatter on the gradients — the
    weight-stationary FSDP schedule falls out of the layout, no wrapper
    class or hook. Composes with Megatron TP by passing
    ``transformer_tp_rules()`` as ``base_rules`` (or just use
    ``transformer_tp_rules(data_axis=...)``); 1-D leaves (norm scales,
    biases) stay on the base layout — sharding them saves nothing and
    costs a gather per use.

    Divisibility (advisor, round 5): with ``mesh`` given, the data axis
    is only assigned to a dim whose size divides evenly by
    ``mesh.shape[data_axis]`` — an uneven split (a 50257-vocab embedding
    on data=4) makes GSPMD pad-and-reshard the tensor on every use,
    costing more than the residency it saves. Later free dims are tried
    in order; when no dim divides, the leaf falls back to the base spec
    (replicated over data). Limitation: WITHOUT ``mesh`` the axis extent
    is unknown here, so the first free dim is taken unchecked (the
    pre-fix behavior) — pass ``mesh`` whenever the layout includes
    odd-sized tables."""
    axis_size = int(mesh.shape[data_axis]) if mesh is not None else None

    def rules(path, leaf) -> P:
        base = base_rules(path, leaf) if base_rules is not None else P()
        ndim = getattr(leaf, "ndim", 0)
        # idempotent: a base spec already carrying data_axis (e.g.
        # fsdp_rules(transformer_tp_rules(data_axis=...))) must not gain
        # a duplicate mesh axis
        if ndim < 2 or data_axis in base:
            return base
        shape = getattr(leaf, "shape", None)
        spec = list(base) + [None] * (ndim - len(base))
        for i, s in enumerate(spec):
            if s is not None:
                continue
            if axis_size is not None and shape is not None \
                    and i < len(shape) and shape[i] % axis_size:
                continue  # uneven split: try a later free dim
            spec[i] = data_axis
            return P(*spec)
        return base  # no evenly-divisible free dim: keep the base layout

    # forward the base TP matcher: lora_rules derives adapter specs from
    # the BASE kernel's TP dims through this attribute — adapters inherit
    # the TP layout and deliberately stay UNsharded on the data axis
    # (rank-r dims are tiny; FSDP-sharding them costs a gather per use
    # and saves nothing)
    rules.match_str = getattr(base_rules, "match_str", None)
    return rules


def divisible_rules(base_rules: Callable, mesh: Mesh) -> Callable:
    """Wrap a rule fn so any spec axis that does not divide its leaf dim
    evenly is dropped (that dim replicated) instead of failing at
    ``device_put``. GSPMD would pad-and-reshard an uneven split on every
    use — worse than replicating the one odd leaf (typically a
    non-power-of-two vocab table). The same policy ``fsdp_rules`` applies
    to the data axis, generalized to every axis of the spec."""
    def rules(path, leaf) -> P:
        spec = base_rules(path, leaf)
        shape = getattr(leaf, "shape", None)
        if shape is None or not any(spec):
            return spec
        out = []
        for i, ax in enumerate(spec):
            if ax is not None and (i >= len(shape)
                                   or shape[i] % int(mesh.shape[ax])):
                ax = None  # uneven split: replicate this dim
            out.append(ax)
        return P(*out)

    rules.match_str = getattr(base_rules, "match_str", None)
    return rules


def head_sharded_kernel(fn, mesh: Mesh, axis: str = "tp"):
    """Wrap a flash-decode-style kernel in ``shard_map`` over the
    mesh's head axis (ISSUE 15): a ``pallas_call`` does not partition
    under GSPMD, which is why the tensor-parallel serving backends rode
    dense cache attention — but per-head attention needs no collective,
    so each device can run the UNMODIFIED kernel on its local head
    shard. The first three operands (q / K cache-or-pool / V, head axis
    at dim 1) shard over ``axis``; every trailing operand (block
    tables, fill indices, pad lengths) is replicated; the output shards
    like q. Works for both :func:`ops.flash_decode.flash_decode`
    (``[B, H*, L, d]`` cache operands) and
    :func:`ops.paged_flash_decode.paged_flash_decode`
    (``[pool, Hkv, bs, d]`` pool operands) — dim 1 is the head axis in
    both layouts. GQA stays exact per shard: the serving layout
    requires ``tp`` to divide both head counts
    (:func:`serving_tp_layout`), so each shard keeps the global
    Hq/Hkv ratio. A trailing 3-D operand whose leading two dims match
    the K operand's is a quantized pool's ``[pool, Hkv, 2]`` scale
    plane (ISSUE 18) — it shards with its heads like the codes it
    scales."""
    spec_h = P(None, axis, None, None)

    def rest_spec(r, k):
        if getattr(r, "ndim", 0) == 3 and r.shape[:2] == k.shape[:2]:
            return P(None, axis, None)  # per-(block, head) scale plane
        return P()

    def wrapped(q, k, v, *rest, **kw):
        inner = functools.partial(fn, **kw) if kw else fn
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(spec_h, spec_h, spec_h)
            + tuple(rest_spec(r, k) for r in rest),
            out_specs=spec_h, check_vma=False)(q, k, v, *rest)

    wrapped.__name__ = f"head_sharded_{getattr(fn, '__name__', 'kernel')}"
    wrapped.__wrapped__ = fn
    return wrapped


# ---------------------------------------------------------------------------
# Named layouts (SpecLayout) — serving tensor parallelism (ISSUE 14)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpecLayout:
    """A self-contained sharding layout: the param rules plus the specs
    for every non-param tensor a consumer must place. Param-pattern
    rules alone are not a layout — the serving backend also owns a KV
    cache (or paged pool) and a handful of replicated host vectors, and
    the three specs must agree on the mesh axis or GSPMD silently
    reshards per call. Bundling them is what lets the slot backends
    apply tensor parallelism without any per-tensor sharding code."""

    rules: Callable          # param-path pattern rules (first match wins)
    kv_cache: P              # [B|pool, Hkv, S|bs, hd] K/V leaves
    replicated: P            # tokens / fill indices / tables / rng
    axis: str = "tp"         # the mesh axis the layout shards over
    degree: int = 1          # axis extent (1 = no sharding anywhere)


def serving_tp_layout(tp: int, cfg: Any = None, *,
                      axis: str = "tp") -> SpecLayout:
    """The serving-engine tensor-parallel layout (Megatron-style, ISSUE
    14): attention q/k/v head-sharded (the KV cache's ``Hkv`` axis
    shards with them, so each device holds ``1/tp`` of every cache row
    or pool block), o_proj row-sharded, MLP column-then-row — ONE
    all-reduce per block, inserted by GSPMD from the layout; logits and
    the sampled argmax come out replicated, so the jax-free scheduler's
    greedy contract is untouched.

    ``cfg`` (optional, any object with the ``LlamaConfig`` head fields)
    is validated up front: head-sharding is only exact when the KV-head
    and Q-head counts divide by ``tp`` — an uneven KV split would give
    devices different slices of the cache's sharded axis, which the
    block-table arithmetic (and the 1/tp per-device byte contract)
    cannot express. Weight dims are handled more leniently: the rules
    are wrapped per-mesh by :func:`divisible_rules` at ``shard_params``
    time (an odd vocab table replicates instead of erroring)."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if cfg is not None and tp > 1:
        for field in ("num_kv_heads", "num_heads"):
            v = getattr(cfg, field, None)
            if v is not None and v % tp:
                raise ValueError(
                    f"{field}={v} is not divisible by tp={tp}: "
                    f"head-sharded serving needs an even head split "
                    f"(pick tp from the divisors of {field})")
    return SpecLayout(rules=transformer_tp_rules(model_axis=axis),
                      kv_cache=P(None, axis, None, None),
                      replicated=P(), axis=axis, degree=int(tp))


def lora_rules(base_rules: Callable, model_axis: str = "model") -> Callable:
    """LoRA adapter sharding consistent with the base layout: the A factor
    (in×r) follows the base kernel's input partitioning, the B factor (r×out)
    its output partitioning. r is tiny → keep r replicated."""
    match = getattr(base_rules, "match_str", None)

    def rules(path, leaf) -> P:
        s = path_str(path)
        if match is not None and ("lora_a" in s or "lora_b" in s):
            # Look up the spec the *base* kernel at this site would get
            # (strip the adapter segment so 'q_proj/lora_a/kernel' matches
            # the 'q_proj/kernel' rule), then inherit one of its dims.
            base = match(s.replace("/lora_a", "").replace("/lora_b", ""),
                         None)
            if "lora_a" in s:  # A: (in, r) — inherit input-dim sharding
                return P(base[0] if len(base) > 0 else None, None)
            return P(None, base[1] if len(base) > 1 else None)  # B: (r, out)
        return base_rules(path, leaf)

    return rules
