"""SmallThinker-21BA3B-Instruct (PowerInfer, ``model_name``
``smallthinker_21b_instruct``; the SmallThinker report, arXiv:2507.20984) and
its training step, in plain ``jax.numpy`` and float32: one chip's cut (the
configuration's file).

No projection has a bias. ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``.
With ``l`` the PUBLISHED index of a layer (``layers_kept`` keeps it):

    n1 = RMSNorm1(x);   h = x + attn_l(n1)
    n2 = RMSNorm2(h);   x' = h + moe(n1, n2)
    logits = RMSNorm(x_last) W_head                     (the head is untied)

    attn_l, H query over H_kv key/value heads of d (a key/value head serves
    H / H_kv query heads in a row):
        q = n1 W_q, k = n1 W_k, v = n1 W_v, a head at a time
        rope_layout[l] = 1: rotate-half RoPE over all d dims at rope_theta;
            0: no positions (NoPE)
        sliding_window_layout[l] = 1: row t sees keys t - W + 1 .. t (W =
            sliding_window_size); 0: keys 0 .. t
        out = softmax(q k^T / sqrt(d) + mask) v W_o
        Dense, in checkpointed blocks of query rows; a window layer's block
        reads the band of keys its rows can see and no more.
    moe(n1, n2): p = softmax(n1 W_r) over all num_routed_experts (the router
        reads the layer's input, BEFORE the attention); the top
        moe_num_active_primary_experts; w_e = p_e / sum_top p;
        sum_{e in top and held} w_e (relu(n2 W1_e) * (n2 W3_e)) W2_e
        (ReGLU), a dense loop over the held experts with a mask: no sort, no
        grouped product. What the absent experts would add is left out.

It imports nothing of the program. Weights come from the seed under the names
the program's checkpoint uses. Departures from the published model, each by the
configuration's ``assumed``: the secondary experts are not built (the config
has no key for them).

``precision``: ``"float32"`` (every product at ``highest``), ``"fp8"`` the
control and ``"bf16"`` the second witness (``harness/narrow.py``). A planted
fault rides behind a ``+``: ``"float32+window_dropped"`` lets every window
layer see the whole prefix, ``"float32+rope_on_global"`` turns the global
layer's q and k by RoPE too, ``"float32+router_after_attention"`` routes on
``n2`` (the experts' own input), ``"float32+swiglu_for_reglu"`` gates the
experts by SiLU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("window_dropped", "rope_on_global", "router_after_attention",
          "swiglu_for_reglu")
ATTN_BLOCK = 256        # query rows of one block of the dense attention
ROW_BLOCK = 2048        # rows of one block of the head and its loss
DECAYED = ("kernel", "embedding", "w1", "w3", "w2")   # weight decay's leaves


# -- the configuration ---------------------------------------------------------

def kinds(cfg: dict) -> list:
    """``(rope, window)`` of each layer held, by its published index: a bool
    and a window in keys or None."""
    kept = list(cfg.get("layers_kept", range(cfg["num_hidden_layers"])))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} layers kept for "
                         f"{cfg['num_hidden_layers']} layers")
    return [(bool(cfg["rope_layout"][l]),
             cfg["sliding_window_size"] if cfg["sliding_window_layout"][l]
             else None) for l in kept]


def _dims(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                fe=cfg["moe_ffn_hidden_size"],
                held=cfg["moe_num_primary_experts"],
                routed=cfg.get("num_routed_experts",
                               cfg["moe_num_primary_experts"]),
                first=cfg.get("first_expert_held", 0))


# -- weights from the seed -----------------------------------------------------

def _matrices(cfg: dict) -> dict:
    """``{path: (leaf, shape)}`` of every matrix, by the program's names."""
    z = _dims(cfg)
    d, h, hkv, hd = z["d"], z["h"], z["hkv"], z["hd"]
    shapes = {"embed_tokens": ("embedding", (cfg["vocab_size"], d)),
              "lm_head": ("kernel", (d, cfg["vocab_size"]))}
    for i in range(len(kinds(cfg))):
        m = f"layer_{i}/self_attn"
        shapes[f"{m}/q_proj"] = ("kernel", (d, h * hd))
        shapes[f"{m}/k_proj"] = ("kernel", (d, hkv * hd))
        shapes[f"{m}/v_proj"] = ("kernel", (d, hkv * hd))
        shapes[f"{m}/o_proj"] = ("kernel", (h * hd, d))
        m = f"layer_{i}/block_sparse_moe"
        shapes[f"{m}/router"] = ("kernel", (d, z["routed"]))
        for n, shp in (("w1", (z["held"], d, z["fe"])),
                       ("w3", (z["held"], d, z["fe"])),
                       ("w2", (z["held"], z["fe"], d))):
            shapes[f"{m}/experts#{n}"] = (n, shp)
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: matrices, expert stacks, the router
    and the head normal(0, 0.02); the embedding normal(0, ``embedding_std``,
    0.02 where the configuration gives none); norm weights 1 + normal(0,
    0.02)."""
    d = cfg["hidden_size"]
    params: dict = {}
    shapes = _matrices(cfg)
    norms = ["norm"] + [f"layer_{i}/{n}" for i in range(len(kinds(cfg)))
                        for n in ("input_layernorm",
                                  "post_attention_layernorm")]
    keys = iter(jax.random.split(key, len(shapes) + len(norms)))
    for path, (leaf, shp) in sorted(shapes.items()):
        std = cfg.get("embedding_std", 0.02) if leaf == "embedding" else 0.02
        set_leaf(params, path.split("#")[0], leaf,
                 std * jax.random.normal(next(keys), shp, jnp.float32))
    for path in norms:
        set_leaf(params, path, "scale", 1.0 + 0.02 * jax.random.normal(
            next(keys), (d,), jnp.float32))
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _rms(x, p, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta: float):
    """Rotate-half over all of a head's dims. ``x``: ``[B, S, H, D]``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attend_block(q, first_row, k, v, window, precision: str):
    """One block of query rows ``[B, R, H, D]`` (the first of them row
    ``first_row``) over the keys and values ``[B, S, H, D]``: under a window
    the band of ``window + R - 1`` keys that holds every key the rows see."""
    rows = q.shape[1]
    first_key = jnp.int32(0)
    if window is not None and window + rows - 1 < k.shape[1]:
        band = window + rows - 1
        first_key = jnp.clip(first_row - window + 1, 0, k.shape[1] - band)
        k = jax.lax.dynamic_slice_in_dim(k, first_key, band, axis=1)
        v = jax.lax.dynamic_slice_in_dim(v, first_key, band, axis=1)
    t = first_row + jnp.arange(rows)[:, None]
    j = first_key + jnp.arange(k.shape[1])[None, :]
    seen = j <= t
    if window is not None:
        seen = seen & (j > t - window)
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, precision)


def attention(u, p, cfg: dict, rope: bool, window, precision: str = "float32"):
    """``attn_l(u)`` of a layer of the given kind: ``[B, S, D]``."""
    z = _dims(cfg)
    bsz, s, _ = u.shape
    h, hkv, hd = z["h"], z["hkv"], z["hd"]

    def proj(name, count):
        return _mm("bsd,df->bsf", u, p[name]["kernel"], precision).reshape(
            bsz, s, count, hd)

    q, k = proj("q_proj", h), proj("k_proj", hkv)
    if rope:
        theta = float(cfg["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, proj("v_proj", hkv)))
    rows = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, k=k, v=v, window=window, precision=precision))
    o = jax.lax.map(lambda qb: block(qb[0], qb[1]), (
        q.reshape(bsz, s // rows, rows, h, hd).swapaxes(0, 1),
        jnp.arange(0, s, rows)))
    o = o.swapaxes(0, 1).reshape(bsz, s, h * hd)
    return _mm("bsf,fd->bsd", o, p["o_proj"]["kernel"], precision)


def route(n, w_router, cfg: dict, precision: str = "float32"):
    """``(idx, w) [N, k]``: the top experts of each row of ``n [N, D]`` out of
    all the router's, and their softmax scores over their sum."""
    scores = jax.nn.softmax(_mm("nd,de->ne", n, w_router, precision), axis=-1)
    w, idx = jax.lax.top_k(scores, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def routed_part(route_rows, rows, p, cfg: dict, precision: str = "float32",
                gate=jax.nn.relu):
    """The held experts' part of the routed layer: the router reads
    ``route_rows [N, D]``, the experts ``rows [N, D]``; one expert at a time
    over every row, under a mask. ``gate``: the experts' gate activation."""
    z = _dims(cfg)
    idx, w = route(route_rows, p["router"]["kernel"], cfg, precision)

    @jax.checkpoint
    def weighted(share, w1, w3, w2):
        a = _mm("nd,df->nf", rows, w1, precision)
        b = _mm("nd,df->nf", rows, w3, precision)
        return share[:, None] * _mm("nf,fd->nd", gate(a) * b, w2, precision)

    def one_expert(out, held_expert):
        j, w1, w3, w2 = held_expert
        share = jnp.sum(jnp.where(idx == z["first"] + j, w, 0.0), axis=-1)
        return out + weighted(share, w1, w3, w2), None

    e = p["experts"]     # a scan, so no two experts' products overlap
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(rows), (
        jnp.arange(z["held"]), e["w1"], e["w3"], e["w2"]))
    return out


def _layer(x, p, kind: tuple, cfg: dict, precision: str, fault: str):
    eps = cfg["rms_norm_eps"]
    rope, window = kind
    if fault == "window_dropped":
        window = None
    if fault == "rope_on_global" and kind[1] is None:
        rope = True
    n1 = _rms(x, p["input_layernorm"], eps)
    x = x + attention(n1, p["self_attn"], cfg, rope, window, precision)
    n2 = _rms(x, p["post_attention_layernorm"], eps)
    d = x.shape[-1]
    route_rows = n2 if fault == "router_after_attention" else n1
    gate = jax.nn.silu if fault == "swiglu_for_reglu" else jax.nn.relu
    moe = routed_part(route_rows.reshape(-1, d), n2.reshape(-1, d),
                      p["block_sparse_moe"], cfg, precision, gate)
    return x + moe.reshape(x.shape)


def forward(cfg: dict, params: dict, ids, precision: str = "float32"):
    """The last layer's normalised output ``[B, S, D]``; each layer is
    recomputed in the backward pass."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}; there are {FAULTS}")
    x = params["embed_tokens"]["embedding"][ids]
    for i, kind in enumerate(kinds(cfg)):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, cfg=cfg, precision=precision, fault=fault))
        x = layer(x, params[f"layer_{i}"])
    return _rms(x, params["norm"], cfg["rms_norm_eps"])


def logits_fn(cfg: dict, params: dict, ids, precision: str = "float32"):
    """``[B, S, V]``, whole: for the tests' small sizes."""
    h = forward(cfg, params, ids, precision)
    return _mm("bsd,dv->bsv", h, params["lm_head"]["kernel"],
               precision.partition("+")[0])


def _loss_rows(rows, head, precision: str):
    """The summed loss of one block of ``(h, target, weight)`` rows."""
    h, targets, weight = rows
    logp = jax.nn.log_softmax(_mm("nd,dv->nv", h, head, precision), axis=-1)
    picked = jnp.take_along_axis(
        logp, targets[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return -jnp.sum(picked * weight)


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
    block = jax.checkpoint(_loss_rows, static_argnums=(2,))
    n = bsz * s
    blk = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    total = 0.0
    for lo in range(0, n, blk):
        total = total + block(
            (h[lo:lo + blk], targets[lo:lo + blk], weight[lo:lo + blk]),
            params["lm_head"]["kernel"], precision.partition("+")[0])
    return total / (bsz * (s - 1))


# -- the optimizer -------------------------------------------------------------

def trainable(weights: dict) -> dict:
    return weights["params"]


def opt_init(cfg: dict, params: dict):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def opt_update(cfg: dict, params: dict, grads: dict, opt, step):
    """AdamW, constant rate; decoupled weight decay on the matrices, the
    expert stacks, the router, the embedding and the head only (none on the
    norms)."""
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    eps, lr, wd = cfg["adam_eps"], cfg["learning_rate"], cfg["weight_decay"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                opt["nu"], grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(path, p, m, n):
        decay = wd * p if getattr(path[-1], "key", None) in DECAYED else 0.0
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + decay)

    params = jax.tree_util.tree_map_with_path(one, params, mu, nu)
    return params, {"mu": mu, "nu": nu}
