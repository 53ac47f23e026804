"""Operations LFM2-8B-A1B's share on one chip needs per sequence, from the
configuration's shapes.

Per token: 2 operations for each parameter of a matrix product (the embedding
is a look-up, the tied head is a product), the routed experts at the EXPECTED
number of held assignments a token, ``num_experts_per_tok * held / routed``
(1 for 8 of 32 at top 4: the count of a step goes with its routing, the
model's need does not), the short convolution's taps, and causal attention's
two products over ``S (S + 1) / 2`` pairs of a sequence. Training is three
times the forward pass; nothing recomputed is counted.

For the flash-attention forward kernel, from the same shapes: the operations
of those two products, and the bytes it cannot avoid: q in, the output out,
each key and value head once, the row statistics. For the backward kernel
pair: five products over the same pairs (the scores again, dv, dp, dq, dk),
2.5 times the forward's operations, and q, k, v, o, do in and dq, dk, dv out
once, with both row statistics.
"""

from __future__ import annotations


def _kinds(cfg: dict) -> list:
    kinds = list(cfg["layer_types"])
    if "layers_kept" in cfg:
        kinds = [kinds[i] for i in cfg["layers_kept"]]
    return kinds


def matmul_params_per_token(cfg: dict) -> float:
    """Weights a token meets in matrix products, the head among them."""
    d, f, fe = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    hd = d // cfg["num_attention_heads"]
    routed = cfg.get("num_routed_experts", cfg["num_experts"])
    held_per_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed
    total = float(cfg["vocab_size"] * d)
    for i, kind in enumerate(_kinds(cfg)):
        if kind == "conv":
            total += 4 * d * d
        else:
            total += 2 * d * d + 2 * d * cfg["num_key_value_heads"] * hd
        if i < cfg["num_dense_layers"]:
            total += 3 * d * f
        else:
            total += d * routed + held_per_token * 3 * d * fe
    return total


def attention_flops_per_sequence(cfg: dict, seq: int) -> float:
    """QK^T and PV over the causal pairs of one sequence, all attention
    layers: 2 products x 2 operations x head size, for every head and pair."""
    layers = sum(k != "conv" for k in _kinds(cfg))
    return float(layers * 4 * cfg["hidden_size"] * seq * (seq + 1) // 2)


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    conv = sum(k == "conv" for k in _kinds(cfg)) \
        * 2 * cfg["conv_L_cache"] * cfg["hidden_size"]
    return float(seq * (2 * matmul_params_per_token(cfg) + conv)
                 + attention_flops_per_sequence(cfg, seq))


def _seq(traffic: dict) -> int:
    return int(traffic["inputs"]["input_ids"]["shape"][0])


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * forward_flops_per_sequence(cfg, _seq(traffic))


def flash_attention_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the flash forward kernel
    in ONE pass over its attention layers (the step's recomputation runs the
    kernel again and is not the model's need)."""
    seq, d = _seq(traffic), cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    layers = sum(k != "conv" for k in _kinds(cfg))
    act = 2  # bytes of a bfloat16
    q_and_o = 2 * seq * d * act
    k_and_v = 2 * seq * cfg["num_key_value_heads"] * hd * act
    stats = seq * cfg["num_attention_heads"] * 4
    return {"flops": attention_flops_per_sequence(cfg, seq),
            "bytes": float(layers * (q_and_o + k_and_v + stats))}


def flash_attention_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of attention's backward
    pass: five products over the causal pairs for the forward's two; q, o, do
    in and dq out at the query heads' width, k, v in and dk, dv out at the
    key/value heads', the two row statistics (lse, rowsum(do * o))."""
    seq, d = _seq(traffic), cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    layers = sum(k != "conv" for k in _kinds(cfg))
    act = 2  # bytes of a bfloat16
    q_side = 4 * seq * d * act
    kv_side = 4 * seq * cfg["num_key_value_heads"] * hd * act
    stats = 2 * seq * cfg["num_attention_heads"] * 4
    return {"flops": 2.5 * attention_flops_per_sequence(cfg, seq),
            "bytes": float(layers * (q_side + kv_side + stats))}
