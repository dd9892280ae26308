"""Model FLOPs of one prefill of a dense GQA decoder: every projection
and MLP matmul at 2 FLOPs per multiply-add over the prompt, causal
attention over the S(S+1)/2 query-key pairs (QK^T and PV), and the LM
head at the last position only (the served prefill returns only its
logits). Norms, rotary and softmax are not counted."""
from __future__ import annotations


def layer_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    return d * (H * hd + 2 * KV * hd) + H * hd * d + 3 * d * f


def attention_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def flops(cfg: dict, S: int) -> int:
    L = cfg["num_hidden_layers"]
    return (2 * S * L * layer_matmul_params(cfg)
            + attention_flops(cfg, S * (S + 1) // 2)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])
