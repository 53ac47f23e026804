"""Operations BERT-base needs per sequence, from the configuration's shapes.

Per token: 2 operations for each parameter of a matrix product in the encoder
layers (the embeddings are look-ups, not products), plus attention's two
products over the sequence, 4 * s * h per layer. The pooler and the classifier
see one token per sequence. Training is three times the forward pass; nothing
recomputed is counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights of the encoder layers' matrix products (biases aside)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)


def forward_flops_per_sequence(cfg: dict, seq: int) -> float:
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_token = 2 * matmul_params(cfg) + layers * 4 * seq * h
    head = 2 * (h * h + h * cfg["num_classes"])
    return float(seq * per_token + head)


def train_flops_per_example(cfg: dict, traffic: dict) -> float:
    seq = int(traffic["inputs"]["input_ids"]["shape"][0])
    return 3 * forward_flops_per_sequence(cfg, seq)
