"""Operations Phi-4-mini-flash's cut on one chip needs per sequence, from the
configuration's shapes.

Per token: 2 operations for each parameter of a matrix product (the embedding
is a look-up, the tied head is a product), the convolution's taps, and
differential attention's products over the LIVE pairs of a sequence only
(``S (S + 1) / 2`` causal pairs, or under the window ``w (w + 1) / 2 +
(S - w) w``): both score maps of every head pair, values twice as wide as the
keys, so ``2 d + 2 (2 d)`` operations a head and pair. The selective scan's
elementwise operations are no MXU work and are left out of the step's count.
Training is three times the forward pass; nothing recomputed is counted.

For the kernels, from the same shapes, in ONE pass over their layers (the
step's recomputation runs each forward kernel again and is not the model's
need, so no forward share can pass 50 and no share of forward and backward
together 71):

- the selective scan, forward: ``u`` and ``y`` at ``[S, d_inner]`` in
  bfloat16 and ``dt`` in float32 (the dtypes handed over), ``B`` and ``C`` at
  ``[S, N]``, the state at each chunk's start once; 9 elementwise operations
  per ``S * d_inner * N``. Backward: ``u, dt, dy`` in and ``du, ddt`` out,
  ``B, C`` in and ``dB, dC`` out, the saved states once; 22 elementwise
  operations (5 to rebuild a state, 17 on the way back). Both are bound by the
  memory's rate by this count (0.4 ms a layer forward) and run on the VPU, not
  the MXU whose peak the operations are held to: the share reads well under
  100 and its use is its TREND, a kernel that halves its time doubles it.
- the flash kernels under differential attention (layers of kind window,
  full, cross), under the names the accepted ``flash_attention_fwd_roofline``
  and ``flash_attention_bwd_roofline`` read in every cell on their lists:
  forward the two products over the live pairs, q in, the output (twice as
  wide) out, each key and value head once, the row statistics; backward the
  five products (scores again, dv, dp, dq, dk), q, o, do in and dq out, k, v
  in and dk, dv out, both row statistics.
"""

from __future__ import annotations

MAMBA, WINDOW, FULL, GMU, CROSS = "mamba", "window", "full", "gmu", "cross"
SCAN_CHUNK = 128        # positions between two saved states (the kernel's)
SCAN_FWD_OPS, SCAN_BWD_OPS = 9, 22
BF16, F32 = 2, 4


def _kind(l: int, n: int, mb: int) -> str:
    if l % mb == 0:
        return MAMBA if l <= n // 2 else GMU
    if l < n // 2:
        return WINDOW
    return FULL if l == n // 2 + 1 else CROSS


def kinds(cfg: dict) -> list:
    n = cfg.get("published", {}).get("num_hidden_layers",
                                     cfg["num_hidden_layers"])
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [_kind(l, n, cfg["mb_per_layer"]) for l in kept]


def _dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return dict(d=d, di=cfg.get("mamba_expand", 2) * d,
                n=cfg.get("mamba_d_state", 16),
                taps=cfg.get("mamba_d_conv", 4),
                rank=cfg.get("mamba_dt_rank", d // 16),
                h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=d // cfg["num_attention_heads"],
                f=cfg["intermediate_size"])


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    z = _dims(cfg)
    d, di, hd = z["d"], z["di"], z["hd"]
    if kind == MAMBA:
        return (d * 2 * di + di * (z["rank"] + 2 * z["n"]) + z["rank"] * di
                + di * d)
    if kind == GMU:
        return 2 * d * di
    wo = z["h"] * hd * d
    if kind == CROSS:
        return d * z["h"] * hd + wo
    return d * (z["h"] + 2 * z["hkv"]) * hd + wo


def matmul_params_per_token(cfg: dict) -> int:
    """Weights a token meets in matrix products, the head among them."""
    z = _dims(cfg)
    return cfg["vocab_size"] * z["d"] + sum(
        mixer_matmul_params(cfg, k) + 3 * z["d"] * z["f"] for k in kinds(cfg))


def live_pairs(seq: int, window=None) -> int:
    """(query, key) pairs a causal mask leaves, under a window if any."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_pairs(cfg: dict, seq: int) -> int:
    """Live pairs summed over the attention layers."""
    return sum(live_pairs(seq, cfg["sliding_window"] if k == WINDOW else None)
               for k in kinds(cfg) if k in (WINDOW, FULL, CROSS))


def attention_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Every head's ``q k^T`` (2 d) and ``p v`` (2 * 2 d) over the live
    pairs: both score maps, values twice as wide as the keys."""
    z = _dims(cfg)
    return float(attention_pairs(cfg, seq) * z["h"] * 6 * z["hd"])


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    z = _dims(cfg)
    conv = kinds(cfg).count(MAMBA) * 2 * z["taps"] * z["di"]
    return float(seq * (2 * matmul_params_per_token(cfg) + conv)
                 + attention_flops_per_sequence(cfg, seq))


def _seq(traffic: dict) -> int:
    return int(traffic["inputs"]["input_ids"]["shape"][0])


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * forward_flops_per_sequence(cfg, _seq(traffic))


def _scan(cfg: dict, traffic: dict, ops: int, rows_bytes: int,
          cols_bytes: int) -> dict:
    z, seq = _dims(cfg), _seq(traffic)
    layers = kinds(cfg).count(MAMBA)
    states = -(-seq // SCAN_CHUNK) * z["n"] * z["di"] * F32
    return {"flops": float(layers * ops * seq * z["di"] * z["n"]),
            "bytes": float(layers * (seq * z["di"] * rows_bytes
                                     + seq * z["n"] * cols_bytes + states))}


def selective_scan_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the scan's forward kernel
    in one pass over the Mamba layers: u, y in bfloat16 and dt in float32,
    B and C, the chunk-start states written once."""
    return _scan(cfg, traffic, SCAN_FWD_OPS, 2 * BF16 + F32, 2 * BF16)


def selective_scan_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The same of the backward kernel: u, dy in and du out in bfloat16, dt in
    and ddt out in float32, B and C in, dB and dC out in float32, the
    chunk-start states read once."""
    return _scan(cfg, traffic, SCAN_BWD_OPS, 3 * BF16 + 2 * F32,
                 2 * BF16 + 2 * F32)


def _attention_layers(cfg: dict) -> int:
    return sum(k in (WINDOW, FULL, CROSS) for k in kinds(cfg))


def flash_attention_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the flash forward kernel
    in one pass over the attention layers."""
    z, seq = _dims(cfg), _seq(traffic)
    q_in = seq * z["h"] * z["hd"] * BF16
    o_out = seq * z["h"] * 2 * z["hd"] * BF16
    k_and_v = 2 * seq * z["hkv"] * z["hd"] * BF16
    stats = seq * z["h"] * F32
    return {"flops": attention_flops_per_sequence(cfg, seq),
            "bytes": float(_attention_layers(cfg)
                           * (q_in + o_out + k_and_v + stats))}


def flash_attention_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The backward pair: the scores again, dq and dk (2 d each), dv and dp
    (2 * 2 d each) over the live pairs, 14 d a head and pair for the
    forward's 6 d; q in and dq out, o and do in (twice as wide), k, v in and
    dk, dv out, the two row statistics."""
    z, seq = _dims(cfg), _seq(traffic)
    q_side = 2 * seq * z["h"] * z["hd"] * BF16
    o_side = 2 * seq * z["h"] * 2 * z["hd"] * BF16
    kv_side = 4 * seq * z["hkv"] * z["hd"] * BF16
    stats = 2 * seq * z["h"] * F32
    return {"flops": attention_flops_per_sequence(cfg, seq) * 14 / 6,
            "bytes": float(_attention_layers(cfg)
                           * (q_side + o_side + kv_side + stats))}
