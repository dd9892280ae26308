"""Plain float32 reference of a dense GQA decoder (the Llama / Qwen3
layout: RMSNorm, optional per-head q/k RMSNorm, rotary embeddings on
split halves, causal softmax attention, SwiGLU MLP, tied or separate
LM head), written from the published description in ``jax.numpy``.

It imports nothing of the program. It reads the bfloat16 weights that
``bench.weights`` makes from the seed, casts one layer at a time to
float32 inside a scan (so the float32 copy of the whole model never
exists), and runs every matmul at ``Precision.HIGHEST``.

``fp8=True`` is the control: the same forward with every linear layer's
weights (per output channel) and inputs (per row) rounded to float8
e4m3, the precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, fp8):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...d,de->...e", x, w, precision=HI)


def _rms_norm(x, scale, eps):
    return scale * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                      + eps))


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


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


def widest_gap(ref_logits, chosen) -> float:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best, over rows: 0 where every choice is the
    reference's argmax."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    picked = np.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return float(np.max(ref.max(-1) - picked))
