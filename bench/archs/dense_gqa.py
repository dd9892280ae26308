"""The dense GQA decoder (the Llama / Qwen3 layout: RMSNorm, optional
per-head q/k RMSNorm, rotary embeddings on split halves, causal softmax
attention, SwiGLU MLP, tied or separate LM head), as the benchmark knows
it: the program's configuration, the parameter tree and each leaf's
scale, the plain float32 reference, and the FLOP and byte counts of its
prefill, decode step and flash-prefill kernel.

It reads these keys of a configuration file: ``registry``,
``num_hidden_layers``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
``vocab_size``, ``rms_norm_eps``, ``rope_theta``, ``tie_word_embeddings``,
``qk_norm`` and ``torch_dtype``.

Nothing of the program is imported here but inside ``program_config``, so
the reference stays independent of the program.
"""
from __future__ import annotations

import dataclasses
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, _linear, _rms_norm, _rope
from bench.weights import STD


def program_config(cfg: dict):
    """The program's ``ModelConfig`` with every size from the file."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(cfg["registry"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=cfg["qk_norm"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------
def shapes(cfg: dict) -> dict:
    """Parameter shapes and types (layer-stacked on a leading axis)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)     # noqa: E731
    attn = {"wq": bf16(L, d, H * hd), "wk": bf16(L, d, KV * hd),
            "wv": bf16(L, d, KV * hd), "wo": bf16(L, H * hd, d)}
    if cfg["qk_norm"]:
        attn.update(q_norm=bf16(L, hd), k_norm=bf16(L, hd))
    embed = {"embedding": bf16(V, d), "final_norm": bf16(d)}
    if not cfg["tie_word_embeddings"]:
        embed["lm_head"] = bf16(d, V)
    return {"embed": embed,
            "layers": {"attn": attn,
                       "mlp": {"w_up": bf16(L, d, f), "w_down": bf16(L, f, d),
                               "w_gate": bf16(L, d, f)},
                       "norm_attn": bf16(L, d), "norm_mlp": bf16(L, d)}}


def std(name: str, cfg: dict) -> float:
    if name in ("wo", "w_down"):
        return STD / np.sqrt(2 * cfg["num_hidden_layers"])
    return STD


# ----------------------------------------------------------------------
# reference
# ----------------------------------------------------------------------
def _forward(params, tokens, *, cfg, first, fp8):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    B, S = tokens.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)

    def layer(x, p):
        p = f32(p)
        a = p["attn"]
        h = _rms_norm(x, p["norm_attn"], eps)
        q = _linear(h, a["wq"], fp8).reshape(B, S, H, hd)
        k = _linear(h, a["wk"], fp8).reshape(B, S, KV, hd)
        v = _linear(h, a["wv"], fp8).reshape(B, S, KV, hd)
        if cfg["qk_norm"]:
            q = _rms_norm(q, a["q_norm"], eps)
            k = _rms_norm(k, a["k_norm"], eps)
        q = _rope(q, pos, cfg["rope_theta"])
        k = _rope(k, pos, cfg["rope_theta"])
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HI)
        x = x + _linear(o.reshape(B, S, H * hd), a["wo"], fp8)
        h = _rms_norm(x, p["norm_mlp"], eps)
        m = p["mlp"]
        g = jax.nn.silu(_linear(h, m["w_gate"], fp8))
        x = x + _linear(g * _linear(h, m["w_up"], fp8), m["w_down"], fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    emb = f32(params["embed"])
    x = _rms_norm(x[:, first:], emb["final_norm"], eps)
    head = emb["embedding"].T if cfg["tie_word_embeddings"] \
        else emb["lm_head"]
    return _linear(x, head, fp8)


@functools.lru_cache(maxsize=8)
def _compiled(cfg_items, first, fp8):
    return jax.jit(functools.partial(_forward, cfg=dict(cfg_items),
                                     first=first, fp8=fp8))


def logits(params, cfg: dict, tokens, first: int, fp8: bool = False):
    """float32 logits [B, S - first, V] at positions first .. S-1 of
    ``tokens`` [B, S] (row j predicts token first + j + 1)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "qk_norm", "rope_theta", "tie_word_embeddings")
    items = tuple((k, cfg[k]) for k in keys)
    return _compiled(items, first, fp8)(params, tokens)


# ----------------------------------------------------------------------
# counts
# ----------------------------------------------------------------------
BF16 = 2


def layer_matmul_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    return d * (H * hd + 2 * KV * hd) + H * hd * d + 3 * d * f


def attention_flops(cfg: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def _prefill_flops(cfg: dict, S: int) -> int:
    L = cfg["num_hidden_layers"]
    return (2 * S * L * layer_matmul_params(cfg)
            + attention_flops(cfg, S * (S + 1) // 2)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


# Model FLOPs of one prefill: every projection and MLP matmul at 2 FLOPs
# per multiply-add over the prompt, causal attention over the S(S+1)/2
# query-key pairs (QK^T and PV), and the LM head at the last position only
# (the served prefill returns only its logits). Norms, rotary and softmax
# are not counted.
prefill_step = SimpleNamespace(flops=_prefill_flops,
                               layer_matmul_params=layer_matmul_params,
                               attention_flops=attention_flops)


def kv_bytes_per_token(cfg: dict) -> int:
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def weight_bytes(cfg: dict) -> int:
    L, d, V = (cfg["num_hidden_layers"], cfg["hidden_size"],
               cfg["vocab_size"])
    norms = L * 2 * d + d + (2 * L * cfg["head_dim"]
                             if cfg["qk_norm"] else 0)
    return BF16 * (L * layer_matmul_params(cfg) + d * V + norms)


def _decode_flops(cfg: dict, ctx: list) -> int:
    B = len(ctx)
    per_token = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                 + cfg["hidden_size"] * cfg["vocab_size"])
    return 2 * B * per_token + attention_flops(cfg, sum(c + 1 for c in ctx))


def _decode_bytes(cfg: dict, ctx: list) -> int:
    kv = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + kv * sum(ctx) + kv * len(ctx)


# One decode step over a batch whose sequences hold ``ctx`` tokens before
# the step. FLOPs: every projection, MLP and LM-head matmul for each new
# token, and attention of each new token over its ctx + 1 keys. Bytes:
# what the algorithm must move, once: the bfloat16 weights (layers, the LM
# head, norms), each sequence's ctx cached keys and values read, and the
# new token's key and value written. Copies that a particular
# implementation adds (joining per-request caches, a float32 copy of the
# head) are not counted, so they show as a lower roofline share.
decode_step = SimpleNamespace(flops=_decode_flops, bytes=_decode_bytes,
                              kv_bytes_per_token=kv_bytes_per_token,
                              weight_bytes=weight_bytes)


def _flash_flops(cfg: dict, S: int) -> int:
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (S * (S + 1) // 2))


def _flash_bytes(cfg: dict, S: int) -> int:
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return BF16 * S * hd * (2 * H + 2 * KV)


# One call of the flash-prefill kernel (one layer of one prefill) at the
# unpadded causal length S: QK^T and PV over the S(S+1)/2 (query, key)
# pairs at 2 FLOPs per multiply-add, and q, k, v read and the output
# written once in bfloat16. Work the kernel spends on padding or on masked
# blocks is not counted, so it shows as a lower roofline share.
flash_prefill = SimpleNamespace(flops=_flash_flops, bytes=_flash_bytes)
