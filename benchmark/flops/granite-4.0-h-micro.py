"""Operations granite-4.0-h-micro's cut on one chip needs per sequence, from
the configuration's shapes.

Per token: 2 operations for each parameter of a matrix product (the embedding
is a look-up, the tied head is a product) and the convolution's taps; per
sequence the attention layer's two products over the ``S (S + 1) / 2`` causal
pairs, and the Mamba-2 layers' products in the chunked form that the
architecture itself publishes (``mamba_chunk_size`` Q = 256): per chunk
``C B^T`` once a group (``2 Q^2 N``), per head the decay-masked product
(``2 Q^2 P``) and the two products a state takes part in (``2 Q P N`` each:
the state the chunk receives read out through ``C``, and ``x outer B`` summed
into the state it hands on; the hand-over itself is a scaling by
``exp(s_Q)``, no product). The exponents, masks and sums run on the VPU and are no
MXU work. Training is three times the forward pass; nothing recomputed is
counted.

For the kernels, from the same shapes, in ONE pass over their layers (the
step's recomputation runs each forward kernel again and is not the model's
need, so no forward share can pass 50):

- the chunked scan, forward: the products above; ``x`` and ``y`` at
  ``[S, d_inner]`` in bfloat16 (the dtype handed over), ``dt`` at ``[S, H]`` in
  float32, ``B`` and ``C`` at ``[S, N]``, the ``[H, P, N]`` float32 state at
  each chunk's start once. Operations and bytes nearly balance at the cell's
  shape (0.18 ms of products, 0.25 ms of bytes a layer), and the kernel's
  exponents and masks run on the VPU beside them: the share reads well under
  100 and its use is its TREND, a kernel that halves its time doubles it.
  Backward, its own count: ``C B^T`` again and the two products that take its
  cotangent to ``dB`` and ``dC`` a group, per head two ``[Q, Q]`` products
  (``dx``, and ``dy x^T`` for the tile's cotangent) and five with a state
  (the read-out again, its cotangent to ``C`` and to the state, the
  handed-on state's cotangent to ``x`` and to ``B``); ``x``, ``dy`` in and
  ``dx`` out, ``dt`` in and ``ddt`` out, ``B``, ``C`` in and ``dB``, ``dC`` out
  in float32, the saved states once.
- the flash kernels under the one attention layer, as ``flops/lfm2-8b-a1b.py``
  counts them (the same shape: 32 query over 8 key/value heads of 64), under
  the names the accepted ``flash_attention_fwd_roofline`` and
  ``flash_attention_bwd_roofline`` read in every cell on their lists.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def kinds(cfg: dict) -> list:
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return [cfg["layer_types"][l] for l in kept]


def _dims(cfg: dict) -> dict:
    d, heads, p = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return dict(d=d, heads=heads, p=p, di=heads * p, n=n, groups=groups,
                wide=heads * p + 2 * groups * n, taps=cfg["mamba_d_conv"],
                q=cfg["mamba_chunk_size"], h=cfg["num_attention_heads"],
                hkv=cfg["num_key_value_heads"],
                hd=d // cfg["num_attention_heads"],
                f=cfg["shared_intermediate_size"])


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    z = _dims(cfg)
    if kind == "mamba":
        return z["d"] * (z["di"] + z["wide"] + z["heads"]) + z["di"] * z["d"]
    return z["d"] * (z["h"] + 2 * z["hkv"]) * z["hd"] \
        + z["h"] * z["hd"] * z["d"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights a token meets in matrix products, the head among them."""
    z = _dims(cfg)
    return cfg["vocab_size"] * z["d"] + sum(
        mixer_matmul_params(cfg, k) + 3 * z["d"] * z["f"] for k in kinds(cfg))


def attention_flops_per_sequence(cfg: dict, seq: int) -> float:
    """Every head's ``q k^T`` and ``p v`` (2 d each) over the causal pairs."""
    z = _dims(cfg)
    return float(kinds(cfg).count("attention") * (seq * (seq + 1) // 2)
                 * z["h"] * 4 * z["hd"])


def _chunks(cfg: dict, seq: int) -> int:
    return -(-seq // cfg["mamba_chunk_size"])


def scan_flops_per_sequence(cfg: dict, seq: int, tiles: int = 1,
                            with_state: int = 2, per_group: int = 1) -> float:
    """The chunked form's products over the Mamba-2 layers of one sequence:
    ``per_group`` products of ``2 Q^2 N`` a group, ``tiles`` of ``2 Q^2 P``
    and ``with_state`` of ``2 Q P N`` a head, a chunk."""
    z = _dims(cfg)
    q = z["q"]
    chunk = per_group * z["groups"] * 2 * q * q * z["n"] + z["heads"] * (
        tiles * 2 * q * q * z["p"] + with_state * 2 * q * z["p"] * z["n"])
    return float(kinds(cfg).count("mamba") * _chunks(cfg, seq) * chunk)


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    z = _dims(cfg)
    conv = kinds(cfg).count("mamba") * 2 * z["taps"] * z["wide"]
    return float(seq * (2 * matmul_params_per_token(cfg) + conv)
                 + attention_flops_per_sequence(cfg, seq)
                 + scan_flops_per_sequence(cfg, seq))


def _seq(traffic: dict) -> int:
    return int(traffic["inputs"]["input_ids"]["shape"][0])


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    return 3 * forward_flops_per_sequence(cfg, _seq(traffic))


def _scan_bytes(cfg: dict, seq: int, wide_rows: int, head_rows: int,
                state_cols: int) -> float:
    """Bytes of ``[S, d_inner]``, ``[S, H]`` and ``[S, G N]`` arrays at the
    given bytes an element, and the chunk-start states once, over the
    Mamba-2 layers."""
    z = _dims(cfg)
    states = _chunks(cfg, seq) * z["di"] * z["n"] * F32
    return float(kinds(cfg).count("mamba") * (
        seq * (z["di"] * wide_rows + z["heads"] * head_rows
               + z["groups"] * z["n"] * state_cols) + states))


def ssd_scan_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the scan's forward kernel
    in one pass over the Mamba-2 layers: x in and y out in bfloat16, dt in
    float32, B and C in bfloat16, the chunk-start states written once."""
    seq = _seq(traffic)
    return {"flops": scan_flops_per_sequence(cfg, seq),
            "bytes": _scan_bytes(cfg, seq, 2 * BF16, F32, 2 * BF16)}


def ssd_scan_bwd_per_example(cfg: dict, traffic: dict) -> dict:
    """The same of the backward kernel: three products a group, two tiles and
    five products with a state a head; x, dy in and dx out in bfloat16, dt in
    and ddt out in float32, B, C in (bfloat16) and dB, dC out (float32), the
    chunk-start states read once."""
    seq = _seq(traffic)
    return {"flops": scan_flops_per_sequence(cfg, seq, tiles=2, with_state=5,
                                             per_group=3),
            "bytes": _scan_bytes(cfg, seq, 3 * BF16, 2 * F32,
                                 2 * BF16 + 2 * F32)}


def flash_attention_fwd_per_example(cfg: dict, traffic: dict) -> dict:
    """``{"flops", "bytes"}`` one sequence needs of the flash forward kernel
    in ONE pass over the attention layer: q in and o out, each key and value
    head once, the row statistic."""
    z, seq = _dims(cfg), _seq(traffic)
    layers = kinds(cfg).count("attention")
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
    layers = kinds(cfg).count("attention")
    q_side = 4 * seq * z["h"] * z["hd"] * BF16
    kv_side = 4 * seq * z["hkv"] * z["hd"] * BF16
    stats = 2 * seq * z["h"] * F32
    return {"flops": 2.5 * attention_flops_per_sequence(cfg, seq),
            "bytes": float(layers * (q_side + kv_side + stats))}
