"""Operations Qwen3-Next-80B-A3B-Instruct's cut on one chip needs per sequence,
from the configuration's shapes.

Per token: 2 operations for each parameter of a matrix product (the embedding
is a look-up, the untied head is a product), the routed experts at the
EXPECTED number of held assignments a token, ``num_experts_per_tok * held /
routed`` (0.625 for 32 of 512 at top 10: the count of a step goes with its
routing, the model's need does not), the shared expert and its gate whole,
the convolution's taps; per sequence the attention layer's two products over
the ``S (S + 1) / 2`` causal pairs at a head width of 256, and the delta
rule's products in the chunked form that the architecture itself publishes,
at its chunk of ``Q = 64`` WHATEVER chunk the kernel uses. A chunk, with Dk
and Dv a head's widths:

- a key head (``K K^T`` and ``Q K^T``; the decay mask is no product):
  ``2 x 2 Q^2 Dk``;
- a value head: the unit-lower-triangular solve for ``T`` (``Q^3 / 3``
  multiply-adds), ``U = T (beta V)`` and ``W = T (beta K e^G)`` (``2 Q^2 Dv``,
  ``2 Q^2 Dk``), the masked tile's product with ``V'`` (``2 Q^2 Dv``), and the
  three products a state takes part in (``2 Q Dk Dv`` each: ``W S``, the
  read-out ``(Q e^G) S``, and ``K^T V'`` summed into the state handed on; the
  hand-over itself is a scaling).

Exponents, masks, norms and sums run on the VPU and are no MXU work. Training
is three times the forward pass; nothing recomputed is counted.

For the kernels, from the same shapes, in ONE pass over their layers (the
step's recomputation is not the model's need):

- the delta rule, forward: the products above, ALL of them (the program's
  kernel ``gated_delta_fwd_prep`` makes ``T``, ``U`` and ``W`` and its kernel
  ``gated_delta_fwd`` walks the chunks: both go by the name ``gated_delta_fwd*``
  and are read together); q, k, v in and o out in bfloat16, g and beta in
  float32. Backward, its own count: every forward product's two transposes
  and, because no tile and no ``V'`` is kept, the forward's products again
  except the output's two and the state's one; q, k, v, do in and dq, dk, dv
  out in bfloat16, g, beta in and dg, dbeta out in float32. The states at
  the chunks' starts, which the forward writes and the backward reads, are
  NOT in the need: how many there are goes with the chunk a program picks
  (268 MB a layer at the published 64, 134 MB at the kernel's 128), so they
  are the program's cost and not the model's. (Counted at 64, as the issue
  of PR 40 had them, the forward share read 98.2 on the first traced run
  with half of those bytes never written: a need counted too high.) At the
  cell's shape the forward is bound by bytes and products alike (0.25 ms
  and 0.22 ms a layer) and the backward by products (0.58 ms): the shares
  read well under 100 and their use is their TREND.
- the flash kernels under the one attention layer, as ``flops/lfm2-8b-a1b.py``
  counts them, at this shape (16 query over 2 key/value heads of 256), under
  the names the accepted ``flash_attention_fwd_roofline`` and
  ``flash_attention_bwd_roofline`` read in every cell on their lists.
"""

from __future__ import annotations

BF16, F32 = 2, 4
PUBLISHED_CHUNK = 64


def kinds(cfg: dict) -> list:
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
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
                routed=cfg.get("num_routed_experts", cfg["num_experts"]))


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    z = _dims(cfg)
    if kind == "linear":
        return z["d"] * (2 * z["keys"] + 2 * z["values"] + 2 * z["hv"]) \
            + z["values"] * z["d"]
    return z["d"] * (2 * z["h"] + 2 * z["hkv"]) * z["hd"] \
        + z["h"] * z["hd"] * z["d"]


def expert_layer_params_per_token(cfg: dict) -> float:
    """The router, the expected held picks' experts, the shared expert and
    its gate."""
    z = _dims(cfg)
    held_per_token = cfg["num_experts_per_tok"] * z["held"] / z["routed"]
    return z["d"] * z["routed"] + held_per_token * 3 * z["d"] * z["fe"] \
        + 3 * z["d"] * z["fs"] + z["d"]


def matmul_params_per_token(cfg: dict) -> float:
    """Weights a token meets in matrix products, the head among them."""
    return cfg["vocab_size"] * cfg["hidden_size"] + sum(
        mixer_matmul_params(cfg, k) + expert_layer_params_per_token(cfg)
        for k in kinds(cfg))


def attention_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Every head's ``q k^T`` and ``p v`` (2 d each) over the causal pairs."""
    z = _dims(cfg)
    return float(kinds(cfg).count("full") * (seq * (seq + 1) // 2)
                 * z["h"] * 4 * z["hd"])


def _chunks(seq: int) -> int:
    return -(-seq // PUBLISHED_CHUNK)


def delta_flops_per_sequence(cfg: dict, seq: int, rebuilt_only: bool = False
                             ) -> float:
    """The chunked form's products over the linear layers of one sequence;
    ``rebuilt_only``: those the backward makes again (all but the output's
    two and the state's one)."""
    z, q = _dims(cfg), PUBLISHED_CHUNK
    dk, dv = z["dk"], z["dv"]
    per_key = 2 * 2 * q * q * dk
    per_value = 2 * q ** 3 / 3 + 2 * q * q * dv + 2 * q * q * dk \
        + 2 * q * dk * dv
    if not rebuilt_only:
        per_value += 2 * q * q * dv + 2 * 2 * q * dk * dv
    return float(kinds(cfg).count("linear") * _chunks(seq)
                 * (z["hk"] * per_key + z["hv"] * per_value))


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    z = _dims(cfg)
    conv = kinds(cfg).count("linear") * 2 * z["taps"] * (
        2 * z["keys"] + z["values"])
    return float(seq * (2 * matmul_params_per_token(cfg) + conv)
                 + attention_flops_per_sequence(cfg, seq)
                 + delta_flops_per_sequence(cfg, seq))


def _seq(traffic: dict) -> int:
    return int(traffic["inputs"]["input_ids"]["shape"][0])


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * forward_flops_per_sequence(cfg, _seq(traffic))


def _delta_bytes(cfg: dict, seq: int, key_rows: int, value_rows: int,
                 head_rows: int) -> float:
    """Bytes of ``[S, Hk Dk]``, ``[S, Hv Dv]`` and ``[S, Hv]`` arrays at the
    given bytes an element, over the linear layers."""
    z = _dims(cfg)
    return float(kinds(cfg).count("linear") * seq * (
        z["keys"] * key_rows + z["values"] * value_rows
        + z["hv"] * head_rows))


def gated_delta_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the delta rule's forward
    in one pass over the linear layers: q, k, v in and o out in bfloat16, g
    and beta in float32."""
    seq = _seq(traffic)
    return {"flops": delta_flops_per_sequence(cfg, seq),
            "bytes": _delta_bytes(cfg, seq, 2 * BF16, 2 * BF16, 2 * F32)}


def gated_delta_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The same of the backward: two transposes a forward product and the
    rebuilt ones; q, k in and dq, dk out, v, do in and dv out in bfloat16, g,
    beta in and dg, dbeta out in float32."""
    seq = _seq(traffic)
    return {"flops": 2 * delta_flops_per_sequence(cfg, seq)
            + delta_flops_per_sequence(cfg, seq, rebuilt_only=True),
            "bytes": _delta_bytes(cfg, seq, 4 * BF16, 3 * BF16, 4 * F32)}


def flash_attention_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the flash forward kernel
    in ONE pass over the attention layer: q in and o out, each key and value
    head once, the row statistic."""
    z, seq = _dims(cfg), _seq(traffic)
    layers = kinds(cfg).count("full")
    q_and_o = 2 * seq * z["h"] * z["hd"] * BF16
    k_and_v = 2 * seq * z["hkv"] * z["hd"] * BF16
    stats = seq * z["h"] * F32
    return {"flops": attention_flops_per_sequence(cfg, seq),
            "bytes": float(layers * (q_and_o + k_and_v + stats))}


def flash_attention_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The backward pair: five products over the causal pairs for the
    forward's two; q, o, do in and dq out at the query heads' width, k, v in
    and dk, dv out at the key/value heads', the two row statistics."""
    z, seq = _dims(cfg), _seq(traffic)
    layers = kinds(cfg).count("full")
    q_side = 4 * seq * z["h"] * z["hd"] * BF16
    kv_side = 4 * seq * z["hkv"] * z["hd"] * BF16
    stats = 2 * seq * z["h"] * F32
    return {"flops": 2.5 * attention_flops_per_sequence(cfg, seq),
            "bytes": float(layers * (q_side + kv_side + stats))}
