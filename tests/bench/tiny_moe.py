"""A tiny mixture-of-experts architecture module, written as a later
configuration would bring one: copied by the tests into a temporary
benchmark root as ``archs/tiny_moe.py``, never into ``bench/``.

It maps onto the program's ``moe`` family: GQA attention, the first
``first_k_dense_replace`` layers with a dense SwiGLU of width
``intermediate_size``, the rest with ``n_routed_experts`` SwiGLU experts
of width ``moe_intermediate_size`` chosen top-``num_experts_per_tok`` by
a softmax router (weights renormalised over the chosen), plus
``n_shared_experts`` always-on experts; untied LM head.
"""
from __future__ import annotations

import dataclasses
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import HI, _fp8, _linear, _rms_norm, _rope
from bench.weights import STD


def program_config(cfg: dict):
    from repro.configs import MoEConfig, get_config
    E, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return dataclasses.replace(
        get_config(cfg["registry"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=False, qk_norm=False,
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        # capacity E / K holds every token of a prefill on one expert, so
        # the program drops none and the drop-free reference is its match
        moe=MoEConfig(num_experts=E, top_k=K,
                      num_shared_experts=cfg["n_shared_experts"],
                      d_expert=cfg["moe_intermediate_size"],
                      first_k_dense=cfg["first_k_dense_replace"],
                      dense_d_ff=cfg["intermediate_size"],
                      capacity_factor=E / K))


def shapes(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    bf16 = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)     # noqa: E731

    def block(L, ffn):
        return {"attn": {"wq": bf16(L, d, H * hd), "wk": bf16(L, d, KV * hd),
                         "wv": bf16(L, d, KV * hd), "wo": bf16(L, H * hd, d)},
                "ffn": ffn, "norm_attn": bf16(L, d), "norm_mlp": bf16(L, d)}

    def swiglu(L, f):
        return {"w_gate": bf16(L, d, f), "w_up": bf16(L, d, f),
                "w_down": bf16(L, f, d)}

    fi = cfg["intermediate_size"]
    return {"embed": {"embedding": bf16(V, d), "final_norm": bf16(d),
                      "lm_head": bf16(d, V)},
            "dense_layers": block(n_dense, swiglu(n_dense, fi)),
            "moe_layers": block(n_moe, {
                # the program keeps its router in float32
                "router": jax.ShapeDtypeStruct((n_moe, d, E), jnp.float32),
                "w_gate": bf16(n_moe, E, d, f), "w_up": bf16(n_moe, E, d, f),
                "w_down": bf16(n_moe, E, f, d),
                "shared": swiglu(n_moe, fs)})}


def std(name: str, cfg: dict) -> float:
    if name == "router":
        return 1 / np.sqrt(cfg["hidden_size"])     # decisive routing
    if name in ("wo", "w_down"):
        return STD / np.sqrt(2 * cfg["num_hidden_layers"])
    return STD


def _swiglu(h, m, fp8):
    g = jax.nn.silu(_linear(h, m["w_gate"], fp8))
    return _linear(g * _linear(h, m["w_up"], fp8), m["w_down"], fp8)


def _experts(h, w, fp8, spec):
    if fp8:
        h, w = _fp8(h, -1), _fp8(w, 1)
    return jnp.einsum(spec, h, w, precision=HI)


def _moe(h, p, cfg, fp8):
    """Every token through every expert, combined by the top-k weights:
    no capacity, nothing dropped."""
    E, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", h, p["router"],
                                      precision=HI), -1)
    top, idx = jax.lax.top_k(probs, K)
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], -2)  # [B,S,E]
    g = jax.nn.silu(_experts(h, p["w_gate"], fp8, "bsd,edf->bsef"))
    g = g * _experts(h, p["w_up"], fp8, "bsd,edf->bsef")
    y = _experts(g, p["w_down"], fp8, "bsef,efd->bsed")
    return jnp.einsum("bse,bsed->bsd", gate, y, precision=HI) \
        + _swiglu(h, p["shared"], fp8)


def _forward(params, tokens, *, cfg, first, fp8):
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    B, S = tokens.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p, dense):
        p = f32(p)
        a = p["attn"]
        h = _rms_norm(x, p["norm_attn"], eps)
        q = _rope(_linear(h, a["wq"], fp8).reshape(B, S, H, hd), pos,
                  cfg["rope_theta"])
        k = _rope(_linear(h, a["wk"], fp8).reshape(B, S, KV, hd), pos,
                  cfg["rope_theta"])
        v = _linear(h, a["wv"], fp8).reshape(B, S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HI)
        x = x + _linear(o.reshape(B, S, H * hd), a["wo"], fp8)
        h = _rms_norm(x, p["norm_mlp"], eps)
        ffn = _swiglu(h, p["ffn"], fp8) if dense else _moe(h, p["ffn"], cfg,
                                                            fp8)
        return x + ffn, None

    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(layer, dense=True), x,
                        params["dense_layers"])
    x, _ = jax.lax.scan(functools.partial(layer, dense=False), x,
                        params["moe_layers"])
    emb = f32(params["embed"])
    x = _rms_norm(x[:, first:], emb["final_norm"], eps)
    return _linear(x, emb["lm_head"], fp8)


@functools.lru_cache(maxsize=8)
def _compiled(cfg_items, first, fp8):
    return jax.jit(functools.partial(_forward, cfg=dict(cfg_items),
                                     first=first, fp8=fp8))


def logits(params, cfg: dict, tokens, first: int, fp8: bool = False):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "n_routed_experts",
            "num_experts_per_tok")
    items = tuple((k, cfg[k]) for k in keys)
    return _compiled(items, first, fp8)(params, tokens)


# counts: only the experts a token is routed to do work for it
def _attn_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (H * hd + 2 * KV * hd) + H * hd * d


def active_params(cfg: dict) -> int:
    """Matmul parameters one token multiplies by, over all layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    experts = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    moe = d * cfg["n_routed_experts"] + experts * 3 * d * f
    return (cfg["num_hidden_layers"] * _attn_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"] + n_moe * moe)


def _attention_flops(cfg, pairs):
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def _prefill_flops(cfg, S):
    return (2 * S * active_params(cfg)
            + _attention_flops(cfg, S * (S + 1) // 2)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def _decode_flops(cfg, ctx):
    per_token = active_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
    return (2 * len(ctx) * per_token
            + _attention_flops(cfg, sum(c + 1 for c in ctx)))


prefill_step = SimpleNamespace(flops=_prefill_flops,
                               active_params=active_params)
decode_step = SimpleNamespace(flops=_decode_flops)
