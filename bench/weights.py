"""Random weights from the seed, made on the device in one jitted call,
in bfloat16 (the type they are served in), in the layout of the dense
transformer's parameter tree. The reference calls this again after the
program's state is freed, so both read the same values; nothing here
imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02          # projection and embedding init scale
NORM_STD = 0.1      # norm scales are 1 + NORM_STD * N(0, 1)


def rng_key(seed: int, salt: int = 0):
    """A JAX key from any non-negative seed, 64-bit ones included."""
    state = np.random.SeedSequence([seed, salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(state))


def shapes(cfg: dict) -> dict:
    """Parameter shapes (layer-stacked on a leading axis)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    attn = {"wq": (L, d, H * hd), "wk": (L, d, KV * hd),
            "wv": (L, d, KV * hd), "wo": (L, H * hd, d)}
    if cfg["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    embed = {"embedding": (V, d), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        embed["lm_head"] = (d, V)
    return {"embed": embed,
            "layers": {"attn": attn,
                       "mlp": {"w_up": (L, d, f), "w_down": (L, f, d),
                               "w_gate": (L, d, f)},
                       "norm_attn": (L, d), "norm_mlp": (L, d)}}


def _std(name: str, cfg: dict) -> float:
    if name in ("wo", "w_down"):
        return STD / np.sqrt(2 * cfg["num_hidden_layers"])
    return STD


def _make(key, cfg: dict):
    tree = shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        z = jax.random.normal(k, shape, jnp.bfloat16)
        if "norm" in name:
            out.append(1 + NORM_STD * z)
        else:
            out.append(z * jnp.bfloat16(_std(name, cfg)))
    return jax.tree.unflatten(treedef, out)


def make_params(cfg: dict, seed: int, device):
    """The whole parameter tree on ``device``, from ``seed``."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(lambda key: _make(key, cfg), out_shardings=sharding)
    return fn(jax.device_put(rng_key(seed), device))
