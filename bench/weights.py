"""Random weights from the seed, made on the device in one jitted call,
drawn in bfloat16 and kept in the type each leaf is served in, in the
layout of the parameter tree that the configuration's architecture module
gives (``shapes``, ``std``). The reference calls this again after the
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


def _make(key, arch, cfg: dict):
    leaves, treedef = jax.tree.flatten_with_path(arch.shapes(cfg))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, leaf) in zip(keys, leaves):
        name = path[-1].key
        z = jax.random.normal(k, leaf.shape, jnp.bfloat16)
        if "norm" in name:
            z = 1 + NORM_STD * z
        else:
            z = z * jnp.bfloat16(arch.std(name, cfg))
        out.append(z.astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def make_params(arch, cfg: dict, seed: int, device):
    """The whole parameter tree of ``cfg`` in the layout of its
    architecture module ``arch``, on ``device``, from ``seed``."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    fn = jax.jit(lambda key: _make(key, arch, cfg), out_shardings=sharding)
    return fn(jax.device_put(rng_key(seed), device))
