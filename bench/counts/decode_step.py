"""FLOPs and bytes of one decode step of a dense GQA decoder over a
batch whose sequences hold ``ctx`` tokens before the step.

FLOPs: every projection, MLP and LM-head matmul for each new token, and
attention of each new token over its ctx + 1 keys.

Bytes: what the algorithm must move, once: the bfloat16 weights (layers,
the LM head, norms), each sequence's ctx cached keys and values read,
and the new token's key and value written. Copies that a particular
implementation adds (joining per-request caches, a float32 copy of the
head) are not counted, so they show as a lower roofline share.
"""
from __future__ import annotations

from bench.counts import prefill_step as _prefill

BF16 = 2


def kv_bytes_per_token(cfg: dict) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def weight_bytes(cfg: dict) -> int:
    L, d, V = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["vocab_size"])
    norms = L * 2 * d + d + (2 * L * cfg["head_dim"]
                             if cfg["qk_norm"] else 0)
    return BF16 * (L * _prefill.layer_matmul_params(cfg) + d * V + norms)


def flops(cfg: dict, ctx: list) -> int:
    B = len(ctx)
    per_token = (cfg["num_hidden_layers"]
                 * _prefill.layer_matmul_params(cfg)
                 + cfg["hidden_size"] * cfg["vocab_size"])
    return (2 * B * per_token
            + _prefill.attention_flops(cfg, sum(c + 1 for c in ctx)))


def bytes(cfg: dict, ctx: list) -> int:
    kv = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + kv * sum(ctx) + kv * len(ctx)
