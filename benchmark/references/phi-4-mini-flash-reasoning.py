"""Phi-4-mini-flash-reasoning (Microsoft, ``model_type`` ``phi4flash``; the
SambaY decoder-hybrid-decoder of arXiv:2507.06607, equations as in the
repository's ``modeling_phi4flash.py``) and its training step, in plain
``jax.numpy`` and float32: one chip's cut (the configuration's file).

``LN`` is LayerNorm with scale and bias; no projection has a bias; there is no
positional encoding of any kind. With ``l`` the PUBLISHED index of a layer
(``layers_kept`` keeps it), ``n = published.num_hidden_layers``:

    x <- x + mixer_l(LN(x));  x <- x + W_down(silu(g) * u), [g, u] = LN(x) W_gate_up
    after the last layer LN, then logits = h E^T with the embedding E (tied)

    Mamba (l even, l <= n/2):
        [u, z] = x W_in;  u = silu(causal_depthwise_conv(u) + b_conv)
        [r, B_t, C_t] = u W_x;  dt = softplus(r W_dt + b_dt);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + (dt_t u_t) outer B_t;  y_t = h_t C_t + D u_t
        out = (y * silu(z)) W_out.   Layer n/2 hands m = y on (before the gate).
        A ``lax.scan`` over positions, in checkpointed blocks of positions.
    attention (l odd: a window of ``sliding_window`` keys for l < n/2, the
    whole prefix for l = n/2 + 1), differential (arXiv:2410.05258):
        [q, k, v] = x W_qkv as H / H_kv / H_kv heads; heads pair up (2j, 2j+1):
        query pair j reads key/value pair j // (H / H_kv)
        P1 = softmax(q1 k1^T / sqrt(d) + mask), P2 likewise, V = [v1 ; v2]
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
        lambda_init = 0.8 - 0.6 exp(-0.3 l)
        o_j = (1 - lambda_init) RMSNorm_2d((P1 - lambda P2) V);  out = concat(o_j) W_o
        Layer n/2 + 1 hands its k, v on. Dense, in blocks of query rows.
    gated memory unit (l even, l >= n/2 + 2):  out = (m * silu(x W_1)) W_2
    cross attention (l odd, l >= n/2 + 3):  q = x W_q; the same differential
        attention (its own lambda vectors, norm scale, W_o), causal, over the
        handed-on k, v.

It imports nothing of the program. Weights come from the seed under the names
the program's checkpoint uses.

``precision``: ``"float32"`` (every product at ``highest``), ``"fp8"`` the
control and ``"bf16"`` the second witness (``harness/narrow.py``); the
recurrence stays float32 in all of them. A planted fault rides behind a ``+``:
``"float32+window_ignored"`` lets the windowed layers see the whole prefix,
``"float32+second_map_dropped"`` sets ``lambda = 0``,
``"float32+gated_output_as_memory"`` hands on ``m = y * silu(z)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("window_ignored", "second_map_dropped", "gated_output_as_memory")
ATTN_BLOCK = 256        # query rows of one block of the dense attention
SCAN_BLOCK = 256        # positions of one checkpointed block of the scan
LOSS_BLOCK = 2048       # rows of one block of the head and its loss
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
NO_DECAY_BUT = ("kernel", "embedding")   # the leaves weight decay touches


# -- the configuration ---------------------------------------------------------

def _published_layers(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_hidden_layers",
                                        cfg["num_hidden_layers"])


def _kept(cfg: dict) -> list:
    kept = list(cfg.get("layers_kept", range(cfg["num_hidden_layers"])))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} layers kept for "
                         f"{cfg['num_hidden_layers']} layers")
    return kept


def kind_of(l: int, n: int, mb: int) -> str:
    """The mixer of published layer ``l`` of ``n``."""
    if l % mb == 0:
        return MAMBA if l <= n // 2 else GMU
    if l < n // 2:
        return WINDOW
    return FULL if l == n // 2 + 1 else CROSS


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return dict(d=d, di=cfg.get("mamba_expand", 2) * d,
                n=cfg.get("mamba_d_state", 16),
                taps=cfg.get("mamba_d_conv", 4),
                rank=cfg.get("mamba_dt_rank", d // 16),
                h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=d // cfg["num_attention_heads"],
                f=cfg["intermediate_size"])


def _lambda_init(l: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * l)


# -- weights from the seed -----------------------------------------------------

def _matrices(cfg: dict) -> dict:
    """``{path: shape}`` of every ``kernel``, by the program's names."""
    z = _dims(cfg)
    d, di, hd = z["d"], z["di"], z["hd"]
    n_pub, mb = _published_layers(cfg), cfg["mb_per_layer"]
    shapes = {}
    for i, l in enumerate(_kept(cfg)):
        b, kind = f"layer_{i}", kind_of(l, n_pub, mb)
        if kind == MAMBA:
            shapes[f"{b}/mamba/in_proj"] = (d, 2 * di)
            shapes[f"{b}/mamba/x_proj"] = (di, z["rank"] + 2 * z["n"])
            shapes[f"{b}/mamba/dt_proj"] = (z["rank"], di)
            shapes[f"{b}/mamba/out_proj"] = (di, d)
        elif kind == GMU:
            shapes[f"{b}/gmu/in_proj"] = (d, di)
            shapes[f"{b}/gmu/out_proj"] = (di, d)
        else:
            if kind == CROSS:
                shapes[f"{b}/attn/Wq"] = (d, z["h"] * hd)
            else:
                shapes[f"{b}/attn/Wqkv"] = (d, (z["h"] + 2 * z["hkv"]) * hd)
            shapes[f"{b}/attn/out_proj"] = (z["h"] * hd, d)
        shapes[f"{b}/mlp/gate_up_proj"] = (d, 2 * z["f"])
        shapes[f"{b}/mlp/down_proj"] = (z["f"], d)
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: matrices and the embedding
    normal(0, 0.02), norm scales 1 and biases 0, the convolution's taps
    uniform in +-1/sqrt(taps) (Mamba-1's default) and its bias normal(0, 0.02), ``A_log = log(1..N)`` on every channel, ``D = 1``,
    ``dt_bias = softplus^-1(dt0)`` with ``dt0`` log-uniform in [1e-3, 1e-1],
    the ``lambda`` vectors normal(0, 0.1)."""
    z = _dims(cfg)
    d, di = z["d"], z["di"]
    params: dict = {}
    shapes = _matrices(cfg)
    kept = _kept(cfg)
    keys = iter(jax.random.split(key, len(shapes) + 8 * len(kept) + 1))
    set_leaf(params, "embed_tokens", "embedding", 0.02 * jax.random.normal(
        next(keys), (cfg["vocab_size"], d), jnp.float32))
    for path, shp in sorted(shapes.items()):
        set_leaf(params, path, "kernel",
                 0.02 * jax.random.normal(next(keys), shp, jnp.float32))

    def norm(path, width):
        set_leaf(params, path, "scale", jnp.ones((width,), jnp.float32))
        set_leaf(params, path, "bias", jnp.zeros((width,), jnp.float32))

    norm("final_layernorm", d)
    n_pub, mb = _published_layers(cfg), cfg["mb_per_layer"]
    for i, l in enumerate(kept):
        b, kind = f"layer_{i}", kind_of(l, n_pub, mb)
        norm(f"{b}/input_layernorm", d)
        norm(f"{b}/post_attention_layernorm", d)
        ks = [next(keys) for _ in range(8)]
        if kind == MAMBA:
            m = f"{b}/mamba"
            bound = 1.0 / math.sqrt(z["taps"])
            set_leaf(params, m, "conv_kernel", jax.random.uniform(
                ks[0], (z["taps"], di), jnp.float32, -bound, bound))
            set_leaf(params, m, "conv_bias", 0.02 * jax.random.normal(
                ks[1], (di,), jnp.float32))
            dt0 = jnp.exp(jax.random.uniform(
                ks[2], (di,), jnp.float32, math.log(1e-3), math.log(1e-1)))
            set_leaf(params, m, "dt_bias", dt0 + jnp.log(-jnp.expm1(-dt0)))
            set_leaf(params, m, "A_log", jnp.broadcast_to(jnp.log(
                jnp.arange(1, z["n"] + 1, dtype=jnp.float32)), (di, z["n"])))
            set_leaf(params, m, "D", jnp.ones((di,), jnp.float32))
        elif kind != GMU:
            a = f"{b}/attn"
            for k, name in zip(ks, ("lambda_q1", "lambda_k1", "lambda_q2",
                                    "lambda_k2")):
                set_leaf(params, a, name, 0.1 * jax.random.normal(
                    k, (z["hd"],), jnp.float32))
            set_leaf(params, f"{a}/subln", "scale",
                     jnp.ones((2 * z["hd"],), jnp.float32))
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _ln(x, p, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _scan_block(h, blk, a_neg):
    """One block of positions of the recurrence. ``h``: ``[B, C, N]``;
    ``blk``: ``(u, dt, b, c)`` with positions leading."""
    def step(h, x):
        u, dt, b, c = x
        h = jnp.exp(dt[..., None] * a_neg) * h \
            + (dt * u)[..., None] * b[:, None, :]
        return h, jnp.sum(h * c[:, None, :], axis=-1)
    return jax.lax.scan(step, h, blk)


def selective_scan(u, dt, a_neg, b, c, d_skip):
    """``y [B, S, C]`` of the recurrence, position by position; per-position
    state lives for one block of positions only."""
    bsz, s, ch = u.shape
    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    xs = tuple(t.reshape(bsz, s // blk, blk, -1).transpose(1, 2, 0, 3)
               for t in (u, dt, b, c))
    body = jax.checkpoint(functools.partial(_scan_block, a_neg=a_neg))
    _, y = jax.lax.scan(body, jnp.zeros((bsz, ch, a_neg.shape[-1]),
                                        jnp.float32), xs)
    return y.transpose(2, 0, 1, 3).reshape(bsz, s, ch) + d_skip * u


def _mamba_op(x, p, cfg: dict, precision: str, fault: str):
    z = _dims(cfg)
    di, n, taps, s = z["di"], z["n"], z["taps"], x.shape[1]
    u, gate = jnp.split(
        _mm("bsd,df->bsf", x, p["in_proj"]["kernel"], precision), 2, axis=-1)
    g = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(p["conv_kernel"][j] * g[:, j:j + s]
                        for j in range(taps)) + p["conv_bias"])
    r, b, c = jnp.split(
        _mm("bsf,fr->bsr", u, p["x_proj"]["kernel"], precision),
        [z["rank"], z["rank"] + n], axis=-1)
    dt = jax.nn.softplus(
        _mm("bsr,rf->bsf", r, p["dt_proj"]["kernel"], precision)
        + p["dt_bias"])
    y = selective_scan(u, dt, -jnp.exp(p["A_log"]), b, c, p["D"])
    gated = y * jax.nn.silu(gate)
    out = _mm("bsf,fd->bsd", gated, p["out_proj"]["kernel"], precision)
    return out, (gated if fault == "gated_output_as_memory" else y)


def _attend_block(q1, q2, first_row, k1, k2, v, window, precision: str):
    """Both softmax maps of one block of query rows ``[B, Q, J, D]`` (the
    first of them row ``first_row``) over the keys ``[B, S, J, D]``, each
    applied to ``v [B, S, J, 2D]``."""
    rows = first_row + jnp.arange(q1.shape[1])[:, None]
    cols = jnp.arange(k1.shape[1])[None, :]
    seen = cols <= rows
    if window is not None:
        seen = seen & (cols > rows - window)

    def one(q, k):
        scores = _mm("bqjd,bkjd->bjqk", q, k, precision) \
            / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm("bjqk,bkje->bqje", p, v, precision)

    return one(q1, k1), one(q2, k2)


def _diff_attention(q, k, v, p, l: int, window, cfg: dict, precision: str,
                    fault: str):
    """``q [B, S, H, D]`` over ``k, v [B, S, H_kv, D]`` -> ``[B, S, H D]``."""
    bsz, s, h, hd = q.shape
    rep = h // k.shape[2]
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1, k2 = (jnp.repeat(t, rep, axis=2) for t in (k[:, :, 0::2],
                                                   k[:, :, 1::2]))
    vv = jnp.repeat(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1),
                    rep, axis=2)
    rows = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, k1=k1, k2=k2, v=vv, window=window,
        precision=precision))

    def blocks(t):
        return t.reshape(bsz, s // rows, rows, h // 2, hd).swapaxes(0, 1)

    o1, o2 = jax.lax.map(lambda qb: block(qb[0], qb[1], qb[2]),
                         (blocks(q1), blocks(q2), jnp.arange(0, s, rows)))
    o1, o2 = (t.swapaxes(0, 1).reshape(bsz, s, h // 2, 2 * hd)
              for t in (o1, o2))
    lam_init = _lambda_init(l)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init
    if fault == "second_map_dropped":
        lam = 0.0
    o = o1 - lam * o2
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["layer_norm_eps"]) * p["subln"]["scale"]
    return (o * (1.0 - lam_init)).reshape(bsz, s, h * hd)


def _layer(x, p, shared, l: int, kind: str, cfg: dict, precision: str,
           fault: str):
    """``(x', handed on)``: ``m`` from the last Mamba layer, ``(k, v)`` from
    the full-attention layer, else ``None``."""
    z, eps = _dims(cfg), cfg["layer_norm_eps"]
    bsz, s, _ = x.shape
    u = _ln(x, p["input_layernorm"], eps)
    out = None
    if kind == MAMBA:
        mix, y = _mamba_op(u, p["mamba"], cfg, precision, fault)
        if l == _published_layers(cfg) // 2:
            out = y
    elif kind == GMU:
        g = p["gmu"]
        gate = jax.nn.silu(_mm("bsd,df->bsf", u, g["in_proj"]["kernel"],
                               precision))
        mix = _mm("bsf,fd->bsd", shared * gate, g["out_proj"]["kernel"],
                  precision)
    else:
        a = p["attn"]
        h, hkv, hd = z["h"], z["hkv"], z["hd"]
        if kind == CROSS:
            q = _mm("bsd,df->bsf", u, a["Wq"]["kernel"], precision)
            k, v = shared
        else:
            q, k, v = jnp.split(
                _mm("bsd,df->bsf", u, a["Wqkv"]["kernel"], precision),
                [h * hd, (h + hkv) * hd], axis=-1)
            k, v = (t.reshape(bsz, s, hkv, hd) for t in (k, v))
            if kind == FULL:
                out = (k, v)
        window = cfg["sliding_window"] if (
            kind == WINDOW and fault != "window_ignored") else None
        o = _diff_attention(q.reshape(bsz, s, h, hd), k, v, a, l, window,
                            cfg, precision, fault)
        mix = _mm("bsf,fd->bsd", o, a["out_proj"]["kernel"], precision)
    x = x + mix
    f = _ln(x, p["post_attention_layernorm"], eps)
    gate, up = jnp.split(_mm("bsd,df->bsf", f,
                             p["mlp"]["gate_up_proj"]["kernel"], precision),
                         2, axis=-1)
    x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up,
                p["mlp"]["down_proj"]["kernel"], precision)
    return x, out


def forward(cfg: dict, params: dict, ids, precision: str = "float32"):
    """The last layer's normalised output ``[B, S, D]``; each layer is
    recomputed in the backward pass."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}; there are {FAULTS}")
    n_pub, mb = _published_layers(cfg), cfg["mb_per_layer"]
    x = params["embed_tokens"]["embedding"][ids]
    handed = {}
    for i, l in enumerate(_kept(cfg)):
        kind = kind_of(l, n_pub, mb)
        layer = jax.checkpoint(functools.partial(
            _layer, l=l, kind=kind, cfg=cfg, precision=precision,
            fault=fault))
        x, out = layer(x, params[f"layer_{i}"],
                       handed.get({GMU: MAMBA, CROSS: FULL}.get(kind)))
        if out is not None:
            handed[kind] = out
    return _ln(x, params["final_layernorm"], cfg["layer_norm_eps"])


def logits_fn(cfg: dict, params: dict, ids, precision: str = "float32"):
    """``[B, S, V]``, whole: for the tests' small sizes."""
    h = forward(cfg, params, ids, precision)
    return _mm("bsd,vd->bsv", h, params["embed_tokens"]["embedding"],
               precision.partition("+")[0])


def _loss_block(h, emb, targets, weight, precision: str):
    logits = _mm("nd,vd->nv", h, emb, precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
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
    return weights["params"]


def _decays(path) -> bool:
    return getattr(path[-1], "key", None) in NO_DECAY_BUT


def opt_init(cfg: dict, params: dict):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": zeros}


def opt_update(cfg: dict, params: dict, grads: dict, opt, step):
    """AdamW, constant rate; decoupled weight decay on the matrices and the
    embedding only (none on norms, ``A_log``, ``D``, biases, the taps, the
    ``lambda`` vectors)."""
    b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
    eps, lr, wd = cfg["adam_eps"], cfg["learning_rate"], cfg["weight_decay"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                                opt["mu"], grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                opt["nu"], grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def one(path, p, m, n):
        decay = wd * p if _decays(path) else 0.0
        return p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps) + decay)

    params = jax.tree_util.tree_map_with_path(one, params, mu, nu)
    return params, {"mu": mu, "nu": nu}
