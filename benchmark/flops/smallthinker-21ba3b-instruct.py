"""Operations SmallThinker-21BA3B-Instruct's cut on one chip needs per
sequence, from the configuration's shapes.

Per token: 2 operations for each parameter of a matrix product (the embedding
is a look-up, the untied head is a product), the routed experts at the
EXPECTED number of held assignments a token, ``moe_num_active_primary_experts
* held / routed`` (1.5 for 16 of 64 at top 6: the count of a step goes with
its routing, the model's need does not), the router whole; per sequence each
attention layer's two products over its LIVE pairs only: ``S (S + 1) / 2``
causal pairs in a global layer, ``w (w + 1) / 2 + (S - w) w`` under a window
of ``w`` (``4 d`` a head and pair). Norms, RoPE, the softmax and the ReLU run
on the VPU and are no MXU work. Training is three times the forward pass;
nothing recomputed is counted.

For the kernels, from the same shapes, in ONE pass over the four attention
layers (the step keeps the forward kernel's outputs and runs it once), under
the names the accepted ``flash_attention_fwd_roofline`` and
``flash_attention_bwd_roofline`` read in every cell on their lists: forward
the two products over the live pairs, q in and o out at the query heads'
width, each key and value head once, the row statistic; backward the five
products (scores again, dv, dp, dq, dk) over the live pairs, q, o, do in and
dq out, k, v in and dk, dv out, both row statistics.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def kinds(cfg: dict) -> list:
    """``(rope, window)`` of each layer held, by its published index."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [(bool(cfg["rope_layout"][l]),
             cfg["sliding_window_size"] if cfg["sliding_window_layout"][l]
             else None) for l in kept]


def _dims(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                fe=cfg["moe_ffn_hidden_size"],
                held=cfg["moe_num_primary_experts"],
                routed=cfg.get("num_routed_experts",
                               cfg["moe_num_primary_experts"]))


def layer_matmul_params_per_token(cfg: dict) -> float:
    """The attention's four projections, the router and the expected held
    picks' experts."""
    z = _dims(cfg)
    held_per_token = cfg["moe_num_active_primary_experts"] * z["held"] \
        / z["routed"]
    return z["d"] * (2 * z["h"] + 2 * z["hkv"]) * z["hd"] \
        + z["d"] * z["routed"] + held_per_token * 3 * z["d"] * z["fe"]


def matmul_params_per_token(cfg: dict) -> float:
    """Weights a token meets in matrix products, the head among them."""
    return cfg["vocab_size"] * cfg["hidden_size"] \
        + len(kinds(cfg)) * layer_matmul_params_per_token(cfg)


def live_pairs(seq: int, window=None) -> int:
    """(query, key) pairs a causal mask leaves, under a window if any."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Every head's ``q k^T`` and ``p v`` (2 d each) over each layer's live
    pairs."""
    z = _dims(cfg)
    pairs = sum(live_pairs(seq, window) for _, window in kinds(cfg))
    return float(pairs * z["h"] * 4 * z["hd"])


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    return float(seq * 2 * matmul_params_per_token(cfg)
                 + attention_flops_per_sequence(cfg, seq))


def _seq(traffic: dict) -> int:
    return int(traffic["inputs"]["input_ids"]["shape"][0])


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * forward_flops_per_sequence(cfg, _seq(traffic))


def flash_attention_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the flash forward kernel
    in ONE pass over the attention layers."""
    z, seq = _dims(cfg), _seq(traffic)
    q_and_o = 2 * seq * z["h"] * z["hd"] * BF16
    k_and_v = 2 * seq * z["hkv"] * z["hd"] * BF16
    stats = seq * z["h"] * F32
    return {"flops": attention_flops_per_sequence(cfg, seq),
            "bytes": float(len(kinds(cfg)) * (q_and_o + k_and_v + stats))}


def flash_attention_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The backward pair: five products over the live pairs for the
    forward's two."""
    z, seq = _dims(cfg), _seq(traffic)
    q_side = 4 * seq * z["h"] * z["hd"] * BF16
    kv_side = 4 * seq * z["hkv"] * z["hd"] * BF16
    stats = 2 * seq * z["h"] * F32
    return {"flops": 2.5 * attention_flops_per_sequence(cfg, seq),
            "bytes": float(len(kinds(cfg)) * (q_side + kv_side + stats))}
