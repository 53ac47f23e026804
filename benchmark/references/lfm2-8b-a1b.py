"""LFM2-8B-A1B (LiquidAI, ``model_type`` ``lfm2_moe``; HF ``modeling_lfm2_moe.py``)
and its training step, in plain ``jax.numpy`` and float32: one chip's share of
a deployment in which four chips share each layer (the configuration's file).

All linear maps are without bias; ``RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w``.

    layer i:   y = x + op_i(RMSNorm(x));   x' = y + ffn_i(RMSNorm(y))
    after the last layer RMSNorm, then logits = h @ E^T with the embedding E

    conv op:   [B, C, z] = split3(u @ W_in);  g = B * z
               c_t = sum_j k[j] * g_{t-(L-1)+j}   (depthwise, causal, L taps)
               op = (C * c) @ W_out
    attention: q, k, v = u @ W_q, u @ W_k, u @ W_v; RMSNorm on every head of q
               and k; rotate-half RoPE over all of the head's dims; each
               key/value head serves H / H_kv query heads; causal softmax at
               scale 1/sqrt(head); op = concat(heads) @ W_o. Dense, computed
               in query blocks.
    dense ffn: (silu(h @ W_1) * (h @ W_3)) @ W_2
    routed:    s = sigmoid(h @ W_r);  T = top_k(s + b), b in the selection only
               w_e = s_e / (sum_{e' in T} s_e' + 1e-6), times the scaling factor
               ffn = sum_{e in T and held} w_e * E_e(h), E_e a SwiGLU
               a dense loop over the held experts with a mask: no sort, no
               grouped product. What the absent experts would add is left out.

It imports nothing of the program. Weights come from the seed under the names
the program's checkpoint uses. Departures from the published model, each by
the configuration's ``assumed``: the head is tied to the embedding; and
``expert_bias`` is drawn from the seed and then fixed — it takes no gradient
(``stop_gradient``) and ``opt_update`` leaves it alone, because the balancing
rule that moves it is not in the config and none is written. It stays in the
tree that ``trainable`` returns, since ``loss_fn(cfg, p, b, precision)`` has
no other way to be given it: its gradient and its change read 0 on both sides.

``precision``: ``"float32"`` (every product at ``highest``), ``"fp8"`` the
control and ``"bf16"`` the second witness (``harness/narrow.py``). A planted
fault rides behind a ``+``: ``"float32+held_norm"`` normalises ``w_e`` over
the held experts of ``T`` alone, ``"float32+bias_in_weight"`` adds ``b`` into
``w_e``. Both are things a sharded expert layer gets wrong in silence.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("held_norm", "bias_in_weight")
ATTN_BLOCK = 256        # query rows of one block of the dense attention
LOSS_BLOCK = 2048       # rows of one block of the head and its loss
FROZEN = "expert_bias"


# -- the configuration ---------------------------------------------------------

def _kinds(cfg: dict) -> list:
    kinds = list(cfg["layer_types"])
    if "layers_kept" in cfg:
        kinds = [kinds[i] for i in cfg["layers_kept"]]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kinds)} layer types for "
                         f"{cfg['num_hidden_layers']} layers")
    return kinds


def _router_width(cfg: dict) -> int:
    return cfg.get("num_routed_experts", cfg["num_experts"])


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# -- weights from the seed -----------------------------------------------------

def _shapes(cfg: dict) -> dict:
    """``{path: (leaf name, shape)}`` of every matrix, by the program's names."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    hd, held = _head_dim(cfg), cfg["num_experts"]
    shapes = {"embed_tokens": ("embedding", (cfg["vocab_size"], d))}
    for i, kind in enumerate(_kinds(cfg)):
        b = f"layer_{i}"
        if kind == "conv":
            shapes[f"{b}/conv/in_proj"] = ("kernel", (d, 3 * d))
            shapes[f"{b}/conv"] = ("conv_kernel", (cfg["conv_L_cache"], d))
            shapes[f"{b}/conv/out_proj"] = ("kernel", (d, d))
        else:
            for n, heads in (("q_proj", cfg["num_attention_heads"]),
                             ("k_proj", cfg["num_key_value_heads"]),
                             ("v_proj", cfg["num_key_value_heads"])):
                shapes[f"{b}/self_attn/{n}"] = ("kernel", (d, heads * hd))
            shapes[f"{b}/self_attn/out_proj"] = ("kernel", (d, d))
        if i < cfg["num_dense_layers"]:
            shapes[f"{b}/feed_forward/w1"] = ("kernel", (d, f))
            shapes[f"{b}/feed_forward/w3"] = ("kernel", (d, f))
            shapes[f"{b}/feed_forward/w2"] = ("kernel", (f, d))
        else:
            shapes[f"{b}/feed_forward/router"] = (
                "kernel", (d, _router_width(cfg)))
            for n, shp in (("w1", (held, d, fe)), ("w3", (held, d, fe)),
                           ("w2", (held, fe, d))):
                shapes[f"{b}/feed_forward/experts/{n}"] = (n, shp)
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: normal(0, 0.02), norm scales 1,
    ``expert_bias`` normal(0, ``expert_bias_std``) for the experts of one
    chip's share, the same values on every other share: uneven within a chip,
    and every chip of the deployment with the same expected load."""
    params = {}
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    shapes = _shapes(cfg)
    kinds = _kinds(cfg)
    keys = iter(jax.random.split(key, len(shapes) + len(kinds)))
    for path, (leaf, shp) in sorted(shapes.items()):
        w = 0.02 * jax.random.normal(next(keys), shp, jnp.float32)
        if path.endswith("/experts/" + leaf):
            set_leaf(params, path.rsplit("/", 1)[0], leaf, w)
        else:
            set_leaf(params, path, leaf, w)
    set_leaf(params, "embedding_norm", "scale", jnp.ones((d,), jnp.float32))
    for i, kind in enumerate(kinds):
        b = f"layer_{i}"
        for n in ("operator_norm", "ffn_norm"):
            set_leaf(params, f"{b}/{n}", "scale", jnp.ones((d,), jnp.float32))
        if kind != "conv":
            for n in ("q_layernorm", "k_layernorm"):
                set_leaf(params, f"{b}/self_attn/{n}", "scale",
                         jnp.ones((hd,), jnp.float32))
        k = next(keys)
        if i >= cfg["num_dense_layers"] and cfg["use_expert_bias"]:
            # one chip's share of values, the same on every chip's share
            share = cfg["expert_bias_std"] * jax.random.normal(
                k, (cfg["num_experts"],), jnp.float32)
            set_leaf(params, f"{b}/feed_forward", FROZEN, jnp.tile(
                share, _router_width(cfg) // cfg["num_experts"]))
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _rms(x, p, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _conv_op(u, p, cfg: dict, precision: str):
    taps, s = cfg["conv_L_cache"], u.shape[1]
    b, c, z = jnp.split(
        _mm("bsd,df->bsf", u, p["in_proj"]["kernel"], precision), 3, axis=-1)
    g = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(p["conv_kernel"][j] * g[:, j:j + s] for j in range(taps))
    return _mm("bsd,df->bsf", c * conv, p["out_proj"]["kernel"], precision)


def _rope(x, theta: float):
    """Rotate-half, over all dims of the head. ``x``: ``[B, S, H, D]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attend_block(q, k, v, first_row, precision: str):
    """Causal softmax attention of one block of query rows ``[B, Q, H, D]``,
    the first of them row ``first_row``, over the keys ``[B, S, H, D]``."""
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(q.shape[-1])
    rows = first_row + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(jnp.arange(k.shape[1])[None, :] <= rows, scores,
                       -jnp.inf)
    return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
               precision)


def _attn_op(u, p, cfg: dict, precision: str):
    bsz, s, d = u.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    eps = cfg["norm_eps"]

    def heads(name, n):
        return _mm("bsd,df->bsf", u, p[name]["kernel"], precision).reshape(
            bsz, s, n, hd)

    q = _rope(_rms(heads("q_proj", h), p["q_layernorm"], eps),
              float(cfg["rope_theta"]))
    k = _rope(_rms(heads("k_proj", hkv), p["k_layernorm"], eps),
              float(cfg["rope_theta"]))
    v = heads("v_proj", hkv)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    # one block of query rows at a time, over all keys under the mask
    rows = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, k=k, v=v, precision=precision))
    o = jax.lax.map(
        lambda qb: block(qb[0], first_row=qb[1]),
        (q.reshape(bsz, s // rows, rows, h, hd).swapaxes(0, 1),
         jnp.arange(0, s, rows)))
    o = o.swapaxes(0, 1).reshape(bsz, s, h * hd)
    return _mm("bsd,df->bsf", o, p["out_proj"]["kernel"], precision)


def _swiglu(x, w1, w3, w2, precision: str):
    a = _mm("nd,df->nf", x, w1, precision)
    b = _mm("nd,df->nf", x, w3, precision)
    return _mm("nf,fd->nd", jax.nn.silu(a) * b, w2, precision)


def _routed_ffn(x, p, cfg: dict, precision: str, fault: str):
    bsz, s, d = x.shape
    h = x.reshape(bsz * s, d)
    first, held = cfg.get("first_expert_held", 0), cfg["num_experts"]
    scores = jax.nn.sigmoid(
        _mm("nd,de->ne", h, p["router"]["kernel"], precision))
    bias = jax.lax.stop_gradient(p[FROZEN]) if cfg["use_expert_bias"] else 0.0
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(
        scores + bias if fault == "bias_in_weight" else scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        here = (idx >= first) & (idx < first + held)
        total = jnp.where(here, w, 0.0) if fault == "held_norm" else w
        w = w / (jnp.sum(total, axis=-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    @jax.checkpoint
    def weighted(share, w1, w3, w2):
        return share[:, None] * _swiglu(h, w1, w3, w2, precision)

    def one_expert(out, held_expert):
        j, w1, w3, w2 = held_expert
        share = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        return out + weighted(share, w1, w3, w2), None

    e = p["experts"]     # one expert at a time: a scan, so none overlap
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (jnp.arange(held), e["w1"], e["w3"], e["w2"]))
    return out.reshape(bsz, s, d)


def _layer(x, p, kind: str, dense: bool, cfg: dict, precision: str,
           fault: str):
    u = _rms(x, p["operator_norm"], cfg["norm_eps"])
    if kind == "conv":
        x = x + _conv_op(u, p["conv"], cfg, precision)
    else:
        x = x + _attn_op(u, p["self_attn"], cfg, precision)
    f = _rms(x, p["ffn_norm"], cfg["norm_eps"])
    ff = p["feed_forward"]
    if dense:
        return x + _swiglu(f.reshape(-1, f.shape[-1]), ff["w1"]["kernel"],
                           ff["w3"]["kernel"], ff["w2"]["kernel"],
                           precision).reshape(x.shape)
    return x + _routed_ffn(f, ff, cfg, precision, fault)


def _loss_block(h, emb, targets, weight, precision: str):
    logits = _mm("nd,vd->nv", h, emb, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -jnp.sum(picked * weight)


def forward(cfg: dict, params: dict, ids, precision: str = "float32"):
    """The last layer's normalised output ``[B, S, D]``; each layer is
    recomputed in the backward pass."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}; there are {FAULTS}")
    x = params["embed_tokens"]["embedding"][ids]
    for i, kind in enumerate(_kinds(cfg)):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, dense=i < cfg["num_dense_layers"],
            cfg=cfg, precision=precision, fault=fault))
        x = layer(x, params[f"layer_{i}"])
    return _rms(x, params["embedding_norm"], cfg["norm_eps"])


def logits_fn(cfg: dict, params: dict, ids, precision: str = "float32"):
    """``[B, S, V]``, whole: for the tests' small sizes."""
    h = forward(cfg, params, ids, precision)
    return _mm("bsd,vd->bsv", h, params["embed_tokens"]["embedding"],
               precision.partition("+")[0])


def loss_fn(cfg: dict, params: dict, batch: dict, precision: str = "float32"):
    """Mean next-token cross-entropy: position t predicts id t+1, the last
    position of a sequence predicts nothing. The head and its loss run over
    row blocks, each recomputed in the backward pass."""
    ids = batch["input_ids"]
    bsz, s = ids.shape
    h = forward(cfg, params, ids, precision).reshape(bsz * s, -1)
    targets = jnp.roll(ids, -1, axis=1).reshape(bsz * s)
    weight = jnp.broadcast_to(jnp.arange(s) < s - 1, (bsz, s)).reshape(
        bsz * s).astype(jnp.float32)
    emb = params["embed_tokens"]["embedding"]
    block = jax.checkpoint(_loss_block, static_argnums=(4,))
    total = 0.0
    for lo in range(0, bsz * s, LOSS_BLOCK):
        hi = min(lo + LOSS_BLOCK, bsz * s)
        total = total + block(h[lo:hi], emb, targets[lo:hi], weight[lo:hi],
                              precision.partition("+")[0])
    return total / (bsz * (s - 1))


# -- the optimizer -------------------------------------------------------------

def trainable(weights: dict) -> dict:
    """Every leaf the loss is a function of. ``expert_bias`` is among them
    and is not trained: see the module's text."""
    return weights["params"]


def _is_frozen(path) -> bool:
    return any(getattr(k, "key", None) == FROZEN for k in path)


def opt_init(cfg: dict, params: dict):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def opt_update(cfg: dict, params: dict, grads: dict, opt, step):
    """AdamW, decoupled weight decay on every trainable leaf, constant rate;
    ``expert_bias`` is returned as it came."""
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    eps, lr, wd = cfg["adam_eps"], cfg["learning_rate"], cfg["weight_decay"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                opt["nu"], grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(path, p, m, n):
        if _is_frozen(path):
            return p
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p)

    params = jax.tree_util.tree_map_with_path(one, params, mu, nu)
    return params, {"mu": mu, "nu": nu}
