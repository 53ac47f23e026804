"""granite-4.0-h-micro (IBM, ``model_type`` ``granitemoehybrid`` with no routed
experts; Mamba-2, arXiv:2405.21060; equations as in the ``granitemoehybrid`` /
Bamba modelling code) and its training step, in plain ``jax.numpy`` and
float32: one chip's cut (the configuration's file).

RMSNorm with a scale, eps ``rms_norm_eps``; no projection has a bias; there is
no positional encoding of any kind. With ``l`` the PUBLISHED index of a layer
(``layers_kept`` keeps it) and the four multipliers of the file:

    x0 = embedding_multiplier * E[ids]
    x <- x + residual_multiplier * mixer_l(RMSNorm(x))
    x <- x + residual_multiplier * W_out(silu(g) * u),  [g, u] = RMSNorm(x) W_in
    logits = RMSNorm(x_last) E^T / logits_scaling      (the embedding E, tied)

    attention (layer_types[l] == "attention"):
        q = h W_q as H heads, k = h W_k, v = h W_v as H_kv heads of d
        P = softmax(q k^T * attention_multiplier + causal);  out = (P v) W_o
        Dense, in blocks of query rows.
    Mamba-2 (layer_types[l] == "mamba"), heads of P channels, N states, one
    B / C group:
        [z, xBC, dt] = h W_in;  xBC = silu(causal_depthwise_conv(xBC) + b_conv)
        [x, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log), a head
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t outer B_t;  y_t = H_t C_t + D x_t
        out = (RMSNorm(y * silu(z)) * w) W_out     (the gate BEFORE the norm,
        the mean square over all heads' channels)
        The recurrence is walked position by position (never the chunked
        form the program computes): a ``lax.scan`` over positions, in
        checkpointed blocks of positions.

It imports nothing of the program. Weights come from the seed under the names
the program's checkpoint uses.

``precision``: ``"float32"`` (every product at ``highest``), ``"fp8"`` the
control and ``"bf16"`` the second witness (``harness/narrow.py``); the
recurrence stays float32 in all of them. A planted fault rides behind a ``+``:
``"float32+state_reset_at_chunk"`` starts every ``mamba_chunk_size`` positions
from a state of zeros (nothing handed across a chunk's boundary),
``"float32+gate_after_norm"`` computes ``RMSNorm(y) * silu(z)``,
``"float32+attention_scale_sqrt_d"`` scales the scores by ``1 / sqrt(d)``,
``"float32+intra_chunk_dropped"`` reads ``y`` off the state its chunk received,
decayed to the position (what enters the state inside the chunk is handed on
but never read there: the chunked form without its masked ``[Q, Q]`` product),
``"float32+recurrence_dropped"`` leaves ``y = D x`` (no state at all).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness.narrow import narrow, set_leaf

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("state_reset_at_chunk", "gate_after_norm", "attention_scale_sqrt_d",
          "intra_chunk_dropped", "recurrence_dropped")
ATTN_BLOCK = 256        # query rows of one block of the dense attention
SCAN_BLOCK = 64         # positions of one checkpointed block of the scan
ROW_BLOCK = 2048        # rows of one block of the MLP, the head and its loss
NO_DECAY_BUT = ("kernel", "embedding")   # the leaves weight decay touches


# -- the configuration ---------------------------------------------------------

def _kinds(cfg: dict) -> list:
    """The kind of each layer held, by its published index."""
    kept = list(cfg.get("layers_kept", range(cfg["num_hidden_layers"])))
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} layers kept for "
                         f"{cfg['num_hidden_layers']} layers")
    return [cfg["layer_types"][l] for l in kept]


def _dims(cfg: dict) -> dict:
    d, heads, p = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    if groups != 1:
        raise ValueError(f"one B / C group is written out here, not {groups}")
    return dict(d=d, heads=heads, p=p, di=heads * p, n=n,
                wide=heads * p + 2 * n, taps=cfg["mamba_d_conv"],
                h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=d // cfg["num_attention_heads"],
                f=cfg["shared_intermediate_size"])


# -- weights from the seed -----------------------------------------------------

def _matrices(cfg: dict) -> dict:
    """``{path: shape}`` of every ``kernel``, by the program's names."""
    z = _dims(cfg)
    d, shapes = z["d"], {}
    for i, kind in enumerate(_kinds(cfg)):
        b = f"layer_{i}"
        if kind == "mamba":
            shapes[f"{b}/mamba/in_proj"] = (d, z["di"] + z["wide"]
                                            + z["heads"])
            shapes[f"{b}/mamba/out_proj"] = (z["di"], d)
        else:
            shapes[f"{b}/self_attn/q_proj"] = (d, z["h"] * z["hd"])
            shapes[f"{b}/self_attn/k_proj"] = (d, z["hkv"] * z["hd"])
            shapes[f"{b}/self_attn/v_proj"] = (d, z["hkv"] * z["hd"])
            shapes[f"{b}/self_attn/o_proj"] = (z["h"] * z["hd"], d)
        shapes[f"{b}/shared_mlp/input_linear"] = (d, 2 * z["f"])
        shapes[f"{b}/shared_mlp/output_linear"] = (z["f"], d)
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """``{"params": ...}`` in float32: matrices and the embedding
    normal(0, 0.02), norm scales 1, the convolution's taps uniform in
    +-1/sqrt(taps) and its bias normal(0, 0.02), ``A_log = log(a)`` with
    ``a`` uniform in [1, 16], ``D = 1``, ``dt_bias = softplus^-1(dt0)`` with
    ``dt0`` log-uniform in [1e-3, 1e-1], one of each a head."""
    z = _dims(cfg)
    d = z["d"]
    params: dict = {}
    shapes, kinds = _matrices(cfg), _kinds(cfg)
    keys = iter(jax.random.split(key, len(shapes) + 4 * len(kinds) + 1))
    set_leaf(params, "embed_tokens", "embedding", 0.02 * jax.random.normal(
        next(keys), (cfg["vocab_size"], d), jnp.float32))
    for path, shp in sorted(shapes.items()):
        set_leaf(params, path, "kernel",
                 0.02 * jax.random.normal(next(keys), shp, jnp.float32))

    def ones(path, width):
        set_leaf(params, path, "scale", jnp.ones((width,), jnp.float32))

    ones("final_layernorm", d)
    for i, kind in enumerate(kinds):
        b = f"layer_{i}"
        ones(f"{b}/input_layernorm", d)
        ones(f"{b}/post_attention_layernorm", d)
        ks = [next(keys) for _ in range(4)]
        if kind != "mamba":
            continue
        m = f"{b}/mamba"
        bound = 1.0 / math.sqrt(z["taps"])
        set_leaf(params, m, "conv_kernel", jax.random.uniform(
            ks[0], (z["taps"], z["wide"]), jnp.float32, -bound, bound))
        set_leaf(params, m, "conv_bias", 0.02 * jax.random.normal(
            ks[1], (z["wide"],), jnp.float32))
        dt0 = jnp.exp(jax.random.uniform(
            ks[2], (z["heads"],), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        set_leaf(params, m, "dt_bias", dt0 + jnp.log(-jnp.expm1(-dt0)))
        set_leaf(params, m, "A_log", jnp.log(jax.random.uniform(
            ks[3], (z["heads"],), jnp.float32, 1.0, 16.0)))
        set_leaf(params, m, "D", jnp.ones((z["heads"],), jnp.float32))
        ones(f"{m}/norm", z["di"])
    return {"params": params}


# -- the forward pass ----------------------------------------------------------

def _mm(spec: str, a, b, precision: str):
    return narrow(functools.partial(jnp.einsum, spec, precision=_HI),
                  precision)(a, b)


def _rms(x, p, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def _by_row_blocks(fn, rows):
    """``fn`` over blocks of ``rows [R, ...]``, each block recomputed in the
    backward pass: a block's products live, never the whole layer's."""
    n = rows.shape[0]
    blk = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    out = jax.lax.map(jax.checkpoint(fn),
                      rows.reshape(n // blk, blk, *rows.shape[1:]))
    return out.reshape(n, *out.shape[2:])


def _scan_block(carry, blk, a_neg, chunk: int, fault: str):
    """One block of positions of the recurrence. ``carry``: the state
    ``[B, H, P, N]``, under ``intra_chunk_dropped`` the state the chunk
    received, decayed to here (else None), and the position; ``blk``:
    ``(x, dt, b, c)`` with positions leading."""
    def step(carry, inp):
        h, held, t = carry
        x, dt, b, c = inp               # [B, H, P], [B, H], [B, N], [B, N]
        if fault == "state_reset_at_chunk":
            h = jnp.where(t % chunk == 0, 0.0, h)
        decay = jnp.exp(dt * a_neg)[..., None, None]
        if fault == "intra_chunk_dropped":
            held = decay * jnp.where(t % chunk == 0, h, held)
        h = decay * h + (dt[..., None] * x)[..., None] * b[:, None, None, :]
        read = h if held is None else held
        return (h, held, t + 1), jnp.sum(read * c[:, None, None, :], axis=-1)
    return jax.lax.scan(step, carry, blk)


def ssm_recurrence(x, dt, a_neg, b, c, d_skip, chunk: int = 0,
                   fault: str = ""):
    """``y [B, S, H, P]`` of the recurrence, position by position; a state
    per position lives for one block of positions only. ``chunk`` matters to
    the planted faults alone."""
    bsz, s, heads, p = x.shape
    if fault == "recurrence_dropped":
        return d_skip[:, None] * x
    blk = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def blocks(t):
        return jnp.moveaxis(t.reshape(bsz, s // blk, blk, *t.shape[2:]),
                            0, 2)
    body = jax.checkpoint(functools.partial(
        _scan_block, a_neg=a_neg, chunk=chunk, fault=fault))
    h0 = jnp.zeros((bsz, heads, p, b.shape[-1]), jnp.float32)
    held = h0 if fault == "intra_chunk_dropped" else None
    _, y = jax.lax.scan(body, (h0, held, jnp.int32(0)),
                        tuple(blocks(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(y, 2, 0).reshape(bsz, s, heads, p) \
        + d_skip[:, None] * x


def _mamba_op(u, p, cfg: dict, precision: str, fault: str):
    z = _dims(cfg)
    di, n, taps = z["di"], z["n"], z["taps"]
    bsz, s, _ = u.shape
    gate, xbc, dt = jnp.split(
        _mm("bsd,df->bsf", u, p["in_proj"]["kernel"], precision),
        [di, di + z["wide"]], axis=-1)
    g = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(p["conv_kernel"][j] * g[:, j:j + s]
                          for j in range(taps)) + p["conv_bias"])
    x, b, c = jnp.split(xbc, [di, di + n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssm_recurrence(
        x.reshape(bsz, s, z["heads"], z["p"]), dt, -jnp.exp(p["A_log"]), b, c,
        p["D"], cfg["mamba_chunk_size"], fault).reshape(bsz, s, di)
    eps = cfg["rms_norm_eps"]
    if fault == "gate_after_norm":
        y = _rms(y, p["norm"], eps) * jax.nn.silu(gate)
    else:
        y = _rms(y * jax.nn.silu(gate), p["norm"], eps)
    return _mm("bsf,fd->bsd", y, p["out_proj"]["kernel"], precision)


def _attend_block(q, first_row, k, v, scale: float, precision: str):
    """One block of query rows ``[B, Q, H, D]`` (the first of them row
    ``first_row``) over the keys and values ``[B, S, H, D]``."""
    rows = first_row + jnp.arange(q.shape[1])[:, None]
    seen = jnp.arange(k.shape[1])[None, :] <= rows
    scores = _mm("bqhd,bkhd->bhqk", q, k, precision) * scale
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return _mm("bhqk,bkhd->bqhd", p, v, precision)


def _attention_op(u, p, cfg: dict, precision: str, fault: str):
    z = _dims(cfg)
    bsz, s, _ = u.shape
    h, hkv, hd = z["h"], z["hkv"], z["hd"]

    def heads(name, count):
        return _mm("bsd,df->bsf", u, p[name]["kernel"], precision).reshape(
            bsz, s, count, hd)

    q = heads("q_proj", h)
    k, v = (jnp.repeat(heads(name, hkv), h // hkv, axis=2)
            for name in ("k_proj", "v_proj"))
    scale = 1.0 / math.sqrt(hd) if fault == "attention_scale_sqrt_d" \
        else cfg["attention_multiplier"]
    rows = ATTN_BLOCK if s % ATTN_BLOCK == 0 else s
    block = jax.checkpoint(functools.partial(
        _attend_block, k=k, v=v, scale=scale, precision=precision))
    o = jax.lax.map(lambda qb: block(qb[0], qb[1]), (
        q.reshape(bsz, s // rows, rows, h, hd).swapaxes(0, 1),
        jnp.arange(0, s, rows)))
    o = o.swapaxes(0, 1).reshape(bsz, s, h * hd)
    return _mm("bsf,fd->bsd", o, p["o_proj"]["kernel"], precision)


def _mlp_rows(rows, p, precision: str):
    gate, up = jnp.split(_mm("nd,df->nf", rows, p["input_linear"]["kernel"],
                             precision), 2, axis=-1)
    return _mm("nf,fd->nd", jax.nn.silu(gate) * up,
               p["output_linear"]["kernel"], precision)


def _layer(x, p, kind: str, cfg: dict, precision: str, fault: str):
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = _rms(x, p["input_layernorm"], eps)
    if kind == "mamba":
        mix = _mamba_op(u, p["mamba"], cfg, precision, fault)
    else:
        mix = _attention_op(u, p["self_attn"], cfg, precision, fault)
    x = x + res * mix
    f = _rms(x, p["post_attention_layernorm"], eps)
    bsz, s, d = f.shape
    mlp = _by_row_blocks(functools.partial(
        _mlp_rows, p=p["shared_mlp"], precision=precision),
        f.reshape(bsz * s, d))
    return x + res * mlp.reshape(bsz, s, d)


def forward(cfg: dict, params: dict, ids, precision: str = "float32"):
    """The last layer's normalised output ``[B, S, D]``; each layer is
    recomputed in the backward pass."""
    precision, _, fault = precision.partition("+")
    if fault and fault not in FAULTS:
        raise ValueError(f"no planted fault {fault!r}; there are {FAULTS}")
    x = cfg["embedding_multiplier"] * params["embed_tokens"]["embedding"][ids]
    for i, kind in enumerate(_kinds(cfg)):
        layer = jax.checkpoint(functools.partial(
            _layer, kind=kind, cfg=cfg, precision=precision, fault=fault))
        x = layer(x, params[f"layer_{i}"])
    return _rms(x, params["final_layernorm"], cfg["rms_norm_eps"])


def logits_fn(cfg: dict, params: dict, ids, precision: str = "float32"):
    """``[B, S, V]``, whole: for the tests' small sizes."""
    h = forward(cfg, params, ids, precision)
    return _mm("bsd,vd->bsv", h, params["embed_tokens"]["embedding"],
               precision.partition("+")[0]) / cfg["logits_scaling"]


def _loss_rows(rows, emb, scaling: float, precision: str):
    """The summed loss of one block of ``(h, target, weight)`` rows."""
    h, targets, weight = rows
    logits = _mm("nd,vd->nv", h, emb, precision) / scaling
    logp = jax.nn.log_softmax(logits, axis=-1)
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
    emb = params["embed_tokens"]["embedding"]
    block = jax.checkpoint(_loss_rows, static_argnums=(2, 3))
    n = bsz * s
    blk = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    total = 0.0
    for lo in range(0, n, blk):
        total = total + block(
            (h[lo:lo + blk], targets[lo:lo + blk], weight[lo:lo + blk]), emb,
            cfg["logits_scaling"], precision.partition("+")[0])
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
    embedding only (none on norms, ``A_log``, ``D``, ``dt_bias``, the taps
    and their bias)."""
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
