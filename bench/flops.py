"""Model FLOPs per trained token, from a configuration's shapes.

Counts what the forward and backward passes require: 6 FLOPs per token for
every weight of a matrix product the token passes through (2 forward, 4
backward), the embedding lookup excluded and the output head included, plus
the attention scores and values, 12 * layers * (heads * head_dim) * T per
token at sequence length T. In a mixture of experts only the token's
``num_experts_per_tok`` experts and its router count. Not counted: activation
recomputation, the experts' dispatch and combine, capacity padding, norms and
softmaxes.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights of the matrix products one token passes through."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    attn = d * h * hd * 2 + d * kv * hd * 2          # q, o and k, v
    experts = cfg.get("num_local_experts", 0)
    if experts:
        ffn = cfg["num_experts_per_tok"] * 3 * d * ff + d * experts
    else:
        ffn = 3 * d * ff
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def per_token(cfg: dict, seq_len: int) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return (6.0 * matmul_params(cfg)
            + 12.0 * cfg["num_hidden_layers"] * h * hd * seq_len)
