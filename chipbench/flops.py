"""Model FLOPs of a dense decoder-only transformer, from its sizes.

Training FLOPs per token = 3 x the forward's (forward, and a backward of
twice the forward), counting only the products the mathematics needs:

- every weight matrix: 2 FLOPs per parameter in the forward, so
  6 x matmul parameters in all. The matrices are the attention's q, k,
  v and output projections, the SwiGLU's gate, up and down, and the
  output head. A tied embedding is counted once, as the head (the
  lookup is no product). Biases and norm scales are not matrices.
- causal attention's two products, QK^T and PV: the query at position
  i meets i + 1 keys, so over a sequence of S positions each product
  costs 2 H d S (S + 1) / 2 FLOPs per layer in the forward, and the
  two together 6 H d (S + 1) per token per layer for training.

Recomputation (rematerialised forwards) is work the program chooses to
do again, and is not counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    ff = cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    head = cfg["vocab_size"] * d  # an untied input table is a lookup only
    return cfg["num_hidden_layers"] * per_layer + head


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return 6.0 * cfg["num_hidden_layers"] * h * hd * (seq_len + 1)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq_len)
