"""Qwen3-Next-80B-A3B-Instruct (Qwen, ``model_type`` ``qwen3_next``; Gated
DeltaNet, arXiv:2412.06464; equations as in the published
``modeling_qwen3_next.py``) and its training step, in plain ``jax.numpy`` and
float32: one chip's cut (the configuration's file).

No projection has a bias. ``RMSNorm0(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(zero-centred: every norm but the gated one). With ``l`` the PUBLISHED index of
a layer (``layers_kept`` keeps it):

    y = x + mixer_l(RMSNorm0(x));   x' = y + moe(RMSNorm0(y))
    logits = RMSNorm0(x_last) W_head                    (the head is untied)

    full attention ((l + 1) % full_attention_interval == 0), H query over H_kv
    key/value heads of d:
        [q_h | gate_h] = (u W_q)_h a head;  k = u W_k, v = u W_v
        RMSNorm0 over each head of q and of k; rotate-half RoPE over the first
        partial_rotary_factor * d dims of a head
        P = softmax(q k^T / sqrt(d) + causal);  out = ((P v) * sigmoid(gate)) W_o
        Dense, in blocks of query rows.
    linear attention (Gated DeltaNet), Hk key and Hv value heads of 128:
        [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
        [q | k | v] = silu(causal_depthwise_conv([q | k | v]))    (no bias)
        q, k of unit length a head (x / sqrt(sum x^2 + 1e-6)); a key head
        serves Hv / Hk value heads in a row
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias), a value head
        S~ = exp(g_t) S_{t-1};  d_t = beta_t (v_t - S~^T k_t)
        S_t = S~ + k_t d_t^T;   o_t = S_t^T q_t / sqrt(128)
        out = ((RMSNorm(o) * w_n) * silu(z)) W_o    (a head's 128 dims at a
        time; the norm FIRST, the gate second)
        The recurrence is walked position by position (never the chunked form
        the program computes): a ``lax.scan`` over positions, in checkpointed
        blocks of positions.
    expert layer: p = softmax(f W_r) over all num_routed_experts; the top
        num_experts_per_tok; w_e = p_e / sum_top p;
        sum_{e in top and held} w_e SwiGLU_e(f)  +  sigmoid(f w_s) SwiGLU_shared(f)
        a dense loop over the held experts with a mask: no sort, no grouped
        product. What the absent experts would add is left out.

It imports nothing of the program. Weights come from the seed under the names
the program's checkpoint uses. Departures from the published model, each by the
configuration's ``assumed``: the multi-token-prediction module is not built
(the config has no key for it); the fused projections' columns are ``[q | k | v
| z]`` and ``[b | a]`` (the checkpoint interleaves them by key head: with
seeded weights the two are one distribution); ``A_log`` is drawn so that a
head's state halves every 64 to 8,192 positions at ``a = 0`` (the published
initial draw forgets within a few positions, and then nothing crosses a chunk
for ``correct`` to see).

``precision``: ``"float32"`` (every product at ``highest``), ``"fp8"`` the
control and ``"bf16"`` the second witness (``harness/narrow.py``); the
recurrence stays float32 in all of them. A planted fault rides behind a ``+``:
``"float32+state_reset_at_chunk"`` starts every ``gated_delta_chunk``
positions (the kernel's own chunk) from a state of zeros,
``"float32+delta_term_dropped"`` writes ``d_t = beta_t v_t`` (plain gated
linear attention), ``"float32+attention_gate_dropped"`` leaves the attention's
output ungated, ``"float32+shared_gate_dropped"`` the shared expert's,
``"float32+rope_over_whole_head"`` rotates all of a head's dims,
``"float32+gate_before_norm"`` computes ``RMSNorm(o * silu(z)) * w_n``
(Granite's order).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_reset_at_chunk", "delta_term_dropped",
          "attention_gate_dropped", "shared_gate_dropped",
          "rope_over_whole_head", "gate_before_norm")
ATTN_BLOCK = 256        # query rows of one block of the dense attention
SCAN_BLOCK = 64         # positions of one checkpointed block of the scan
ROW_BLOCK = 2048        # rows of one block of the head and its loss
DECAYED = ("kernel", "embedding", "w1", "w3", "w2")   # weight decay's leaves
HALF_LIFE = (64.0, 8192.0)   # positions, log-uniform, at a = 0


# -- the configuration ---------------------------------------------------------

def _kinds(cfg: dict) -> list:
    """The kind of each layer held, by its published index."""
    kept = list(cfg.get("layers_kept", range(cfg["num_hidden_layers"])))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} layers kept for "
                         f"{cfg['num_hidden_layers']} layers")
    return ["full" if (l + 1) % cfg["full_attention_interval"] == 0
            else "linear" for l in kept]


def _dims(cfg: dict) -> dict:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return dict(d=cfg["hidden_size"], hk=hk, hv=hv, dk=dk, dv=dv,
                keys=hk * dk, values=hv * dv,
                taps=cfg["linear_conv_kernel_dim"],
                h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], fe=cfg["moe_intermediate_size"],
                fs=cfg["shared_expert_intermediate_size"],
                held=cfg["num_experts"],
                routed=cfg.get("num_routed_experts", cfg["num_experts"]),
                first=cfg.get("first_expert_held", 0))


# -- weights from the seed -----------------------------------------------------

def _matrices(cfg: dict) -> dict:
    """``{path: (leaf, shape)}`` of every matrix, by the program's names."""
    z = _dims(cfg)
    d = z["d"]
    shapes = {"embed_tokens": ("embedding", (cfg["vocab_size"], d)),
              "lm_head": ("kernel", (d, cfg["vocab_size"]))}
    for i, kind in enumerate(_kinds(cfg)):
        b = f"layer_{i}"
        if kind == "linear":
            m = f"{b}/linear_attn"
            shapes[f"{m}/in_proj_qkvz"] = ("kernel", (
                d, 2 * z["keys"] + 2 * z["values"]))
            shapes[f"{m}/in_proj_ba"] = ("kernel", (d, 2 * z["hv"]))
            shapes[f"{m}/out_proj"] = ("kernel", (z["values"], d))
        else:
            m = f"{b}/self_attn"
            shapes[f"{m}/q_proj"] = ("kernel", (d, 2 * z["h"] * z["hd"]))
            shapes[f"{m}/k_proj"] = ("kernel", (d, z["hkv"] * z["hd"]))
            shapes[f"{m}/v_proj"] = ("kernel", (d, z["hkv"] * z["hd"]))
            shapes[f"{m}/o_proj"] = ("kernel", (z["h"] * z["hd"], d))
        m = f"{b}/mlp"
        shapes[f"{m}/routed/router"] = ("kernel", (d, z["routed"]))
        for n, shp in (("w1", (z["held"], d, z["fe"])),
                       ("w3", (z["held"], d, z["fe"])),
                       ("w2", (z["held"], z["fe"], d))):
            shapes[f"{m}/routed/experts#{n}"] = (n, shp)
        shapes[f"{m}/gate_proj"] = ("kernel", (d, z["fs"]))
        shapes[f"{m}/up_proj"] = ("kernel", (d, z["fs"]))
        shapes[f"{m}/down_proj"] = ("kernel", (z["fs"], d))
        shapes[f"{m}/shared_expert_gate"] = ("kernel", (d, 1))
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: matrices, expert stacks, the embedding
    and the head normal(0, 0.02); norm weights normal(0, 0.02) about their
    published start (0 for the zero-centred norms, 1 for the gated one: a
    wrong ``1 +`` is then seen); the convolution's taps uniform in
    +-1/sqrt(taps); ``dt_bias = 1`` and ``A_log`` such that at ``a = 0`` a
    head's state halves every ``n`` positions, ``n`` log-uniform over
    ``HALF_LIFE``, one a value head."""
    z = _dims(cfg)
    d = z["d"]
    params: dict = {}
    shapes, kinds = _matrices(cfg), _kinds(cfg)
    keys = iter(jax.random.split(key, len(shapes) + 6 * len(kinds) + 1))
    for path, (leaf, shp) in sorted(shapes.items()):
        set_leaf(params, path.split("#")[0], leaf,
                 0.02 * jax.random.normal(next(keys), shp, jnp.float32))

    def norm(path, width, k, about=0.0):
        set_leaf(params, path, "weight",
                 about + 0.02 * jax.random.normal(k, (width,), jnp.float32))

    norm("norm", d, next(keys))
    for i, kind in enumerate(kinds):
        b = f"layer_{i}"
        ks = [next(keys) for _ in range(6)]
        norm(f"{b}/input_layernorm", d, ks[0])
        norm(f"{b}/post_attention_layernorm", d, ks[1])
        if kind == "full":
            norm(f"{b}/self_attn/q_norm", z["hd"], ks[2])
            norm(f"{b}/self_attn/k_norm", z["hd"], ks[3])
            continue
        m = f"{b}/linear_attn"
        bound = 1.0 / math.sqrt(z["taps"])
        set_leaf(params, m, "conv_kernel", jax.random.uniform(
            ks[2], (z["taps"], 2 * z["keys"] + z["values"]), jnp.float32,
            -bound, bound))
        life = jnp.exp(jax.random.uniform(
            ks[3], (z["hv"],), jnp.float32, math.log(HALF_LIFE[0]),
            math.log(HALF_LIFE[1])))
        set_leaf(params, m, "A_log", jnp.log(
            math.log(2.0) / (life * math.log1p(math.e))))
        set_leaf(params, m, "dt_bias", jnp.ones((z["hv"],), jnp.float32))
        norm(f"{m}/norm", z["dv"], ks[4], about=1.0)
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _unit_rms(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rms0(x, p, eps: float):
    return _unit_rms(x, eps) * (1.0 + p["weight"])


def _scan_block(carry, blk, chunk: int, fault: str):
    """One block of positions of the delta rule. ``carry``: the state ``[B,
    Hv, Dk, Dv]`` and the position; ``blk``: ``(q, k, v, g, beta)`` with
    positions leading, q and k a value head each."""
    def step(carry, inp):
        state, t = carry
        q, k, v, g, beta = inp          # [B, H, Dk] x 2, [B, H, Dv], [B, H] x 2
        if fault == "state_reset_at_chunk":
            state = jnp.where(t % chunk == 0, 0.0, state)
        state = jnp.exp(g)[..., None, None] * state
        seen = 0.0 if fault == "delta_term_dropped" else \
            jnp.sum(state * k[..., :, None], axis=-2)
        delta = beta[..., None] * (v - seen)
        state = state + k[..., :, None] * delta[..., None, :]
        return (state, t + 1), jnp.sum(state * q[..., :, None], axis=-2)
    return jax.lax.scan(step, carry, blk)


def delta_recurrence(q, k, v, g, beta, chunk: int = 0, fault: str = ""):
    """``o [B, S, Hv, Dv]`` of the gated delta rule, position by position;
    ``q, k [B, S, Hk, Dk]``. A state per position lives for one block of
    positions only. ``chunk`` matters to the planted fault alone."""
    bsz, s, hv, dv = v.shape
    rep = hv // k.shape[2]
    q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))
    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def blocks(t):
        return jnp.moveaxis(t.reshape(bsz, s // blk, blk, *t.shape[2:]), 0, 2)

    body = jax.checkpoint(functools.partial(_scan_block, chunk=chunk,
                                            fault=fault))
    s0 = jnp.zeros((bsz, hv, q.shape[-1], dv), jnp.float32)
    _, o = jax.lax.scan(body, (s0, jnp.int32(0)),
                        tuple(blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 2, 0).reshape(bsz, s, hv, dv) \
        / math.sqrt(q.shape[-1])


def _linear_op(u, p, cfg: dict, precision: str, fault: str):
    z = _dims(cfg)
    keys, values, taps = z["keys"], z["values"], z["taps"]
    bsz, s, _ = u.shape
    qkv, gate = jnp.split(
        _mm("bsd,df->bsf", u, p["in_proj_qkvz"]["kernel"], precision),
        [2 * keys + values], axis=-1)
    b, a = jnp.split(
        _mm("bsd,df->bsf", u, p["in_proj_ba"]["kernel"], precision), 2,
        axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_kernel"][j] * padded[:, j:j + s]
                          for j in range(taps)))
    q, k, v = jnp.split(qkv, [keys, 2 * keys], axis=-1)

    def unit(t):
        t = t.reshape(bsz, s, z["hk"], z["dk"])
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_recurrence(unit(q), unit(k), v.reshape(bsz, s, z["hv"], z["dv"]),
                         g, jax.nn.sigmoid(b), cfg.get("gated_delta_chunk", 0),
                         fault)
    gate = jax.nn.silu(gate).reshape(o.shape)
    eps, w_n = cfg["rms_norm_eps"], p["norm"]["weight"]
    if fault == "gate_before_norm":
        o = _unit_rms(o * gate, eps) * w_n
    else:
        o = _unit_rms(o, eps) * w_n * gate
    return _mm("bsf,fd->bsd", o.reshape(bsz, s, values),
               p["out_proj"]["kernel"], precision)


def _rope(x, theta: float, rotary: int):
    """Rotate-half over the first ``rotary`` dims of a head. ``x``: ``[B, S,
    H, D]``."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                           / rotary))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    turned, rest = x[..., :rotary], x[..., rotary:]
    rot = jnp.concatenate([-turned[..., rotary // 2:],
                           turned[..., :rotary // 2]], axis=-1)
    return jnp.concatenate(
        [turned * jnp.cos(ang) + rot * jnp.sin(ang), rest], axis=-1)


def _attend_block(q, first_row, k, v, precision: str):
    """One block of query rows ``[B, Q, H, D]`` (the first of them row
    ``first_row``) over the keys and values ``[B, S, H, D]``."""
    rows = first_row + jnp.arange(q.shape[1])[:, None]
    seen = jnp.arange(k.shape[1])[None, :] <= rows
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, precision)


def _attention_op(u, p, cfg: dict, precision: str, fault: str):
    z = _dims(cfg)
    bsz, s, _ = u.shape
    h, hkv, hd = z["h"], z["hkv"], z["hd"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rotary = hd if fault == "rope_over_whole_head" \
        else int(hd * cfg["partial_rotary_factor"])

    def proj(name, count):
        return _mm("bsd,df->bsf", u, p[name]["kernel"], precision).reshape(
            bsz, s, count, -1)

    q, gate = jnp.split(proj("q_proj", h), 2, axis=-1)
    q = _rope(_rms0(q, p["q_norm"], eps), theta, rotary)
    k = _rope(_rms0(proj("k_proj", hkv), p["k_norm"], eps), theta, rotary)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, proj("v_proj", hkv)))
    rows = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, k=k, v=v, precision=precision))
    o = jax.lax.map(lambda qb: block(qb[0], qb[1]), (
        q.reshape(bsz, s // rows, rows, h, hd).swapaxes(0, 1),
        jnp.arange(0, s, rows)))
    o = o.swapaxes(0, 1).reshape(bsz, s, h, hd)
    if fault != "attention_gate_dropped":
        o = o * jax.nn.sigmoid(gate)
    return _mm("bsf,fd->bsd", o.reshape(bsz, s, h * hd),
               p["o_proj"]["kernel"], precision)


def _swiglu(x, w1, w3, w2, precision: str):
    a = _mm("nd,df->nf", x, w1, precision)
    b = _mm("nd,df->nf", x, w3, precision)
    return _mm("nf,fd->nd", jax.nn.silu(a) * b, w2, precision)


def route(h, w_router, cfg: dict, precision: str = "float32"):
    """``(idx, w) [N, k]``: the top experts of each row of ``h [N, D]`` out of
    all the router's, and their normalised softmax scores."""
    scores = jax.nn.softmax(_mm("nd,de->ne", h, w_router, precision), axis=-1)
    w, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def routed_part(h, p, cfg: dict, precision: str = "float32"):
    """The held experts' part of the routed layer for the rows ``h [N, D]``:
    one expert at a time over every row, under a mask."""
    z = _dims(cfg)
    idx, w = route(h, p["router"]["kernel"], cfg, precision)

    @jax.checkpoint
    def weighted(share, w1, w3, w2):
        return share[:, None] * _swiglu(h, w1, w3, w2, precision)

    def one_expert(out, held_expert):
        j, w1, w3, w2 = held_expert
        share = jnp.sum(jnp.where(idx == z["first"] + j, w, 0.0), axis=-1)
        return out + weighted(share, w1, w3, w2), None

    e = p["experts"]     # a scan, so no two experts' products overlap
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), (
        jnp.arange(z["held"]), e["w1"], e["w3"], e["w2"]))
    return out


def shared_part(h, p, precision: str = "float32", fault: str = ""):
    """``sigmoid(h w_s) * SwiGLU_shared(h)``: what every chip of a layer's
    group computes alike."""
    out = _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                  p["down_proj"]["kernel"], precision)
    if fault == "shared_gate_dropped":
        return out
    return jax.nn.sigmoid(_mm("nd,df->nf", h, p["shared_expert_gate"]["kernel"],
                              precision)) * out


def _layer(x, p, kind: str, cfg: dict, precision: str, fault: str):
    eps = cfg["rms_norm_eps"]
    u = _rms0(x, p["input_layernorm"], eps)
    if kind == "linear":
        x = x + _linear_op(u, p["linear_attn"], cfg, precision, fault)
    else:
        x = x + _attention_op(u, p["self_attn"], cfg, precision, fault)
    f = _rms0(x, p["post_attention_layernorm"], eps)
    rows = f.reshape(-1, f.shape[-1])
    moe = routed_part(rows, p["mlp"]["routed"], cfg, precision) \
        + shared_part(rows, p["mlp"], precision, fault)
    return x + moe.reshape(x.shape)


def forward(cfg: dict, params: dict, ids, precision: str = "float32"):
    """The last layer's normalised output ``[B, S, D]``; each layer is
    recomputed in the backward pass."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}; there are {FAULTS}")
    x = params["embed_tokens"]["embedding"][ids]
    for i, kind in enumerate(_kinds(cfg)):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, cfg=cfg, precision=precision, fault=fault))
        x = layer(x, params[f"layer_{i}"])
    return _rms0(x, params["norm"], cfg["rms_norm_eps"])


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
    expert stacks, the router, the embedding and the head only (none on
    norms, ``A_log``, ``dt_bias`` and the taps)."""
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
