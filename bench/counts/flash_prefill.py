"""FLOPs and bytes of one call of the flash-prefill kernel (one layer of
one prefill) at the unpadded causal length S: QK^T and PV over the
S(S+1)/2 (query, key) pairs at 2 FLOPs per multiply-add, and q, k, v
read and the output written once in bfloat16. Work the kernel spends on
padding or on masked blocks is not counted, so it shows as a lower
roofline share."""
from __future__ import annotations

BF16 = 2


def flops(cfg: dict, S: int) -> int:
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (S * (S + 1) // 2))


def bytes(cfg: dict, S: int) -> int:
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return BF16 * S * hd * (2 * H + 2 * KV)
