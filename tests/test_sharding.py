"""Sharding rules: every parameter of every arch gets a legal spec on the
production meshes (divisibility respected; fallback chain ends replicated)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.dist.sharding import (batch_spec, param_spec, state_spec)
from repro.models import get_model

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _path_str(path):
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["1pod", "2pod"])
def test_all_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    model = get_model(cfg)
    abstract = model.abstract_params()
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    n_sharded = 0
    for path, leaf in flat:
        spec = param_spec(_path_str(path), leaf.shape, mesh, cfg)
        assert len(spec) <= len(leaf.shape)
        for dim, names in enumerate(spec):
            if names is None:
                continue
            size = mesh.shape[names] if isinstance(names, str) else \
                int(np.prod([mesh.shape[n] for n in names]))
            assert leaf.shape[dim] % size == 0, \
                f"{arch}: {_path_str(path)} dim {dim} " \
                f"({leaf.shape[dim]}) not divisible by {names}={size}"
            n_sharded += 1
    assert n_sharded > 0, f"{arch}: nothing sharded at all"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_big_params_are_sharded(arch):
    """Every parameter >= 8M elements must shard on 'model' (a replicated
    34B matrix would never fit 16 GB HBM)."""
    cfg = get_config(arch)
    model = get_model(cfg)
    flat = jax.tree_util.tree_flatten_with_path(model.abstract_params())[0]
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        if n >= 8_000_000:
            spec = param_spec(_path_str(path), leaf.shape, SINGLE, cfg)
            assert any(s is not None for s in spec), \
                f"{arch}: large param {_path_str(path)} {leaf.shape} " \
                f"replicated"


def test_moe_experts_expert_parallel():
    cfg = get_config("deepseek-moe-16b")
    spec = param_spec("moe_layers/ffn/w_gate", (27, 64, 2048, 1408),
                      SINGLE, cfg)
    assert spec[1] == "model"        # E dim after the layer-stack dim


def test_embedding_vocab_parallel_when_divisible():
    cfg = get_config("yi-34b")       # vocab 64000 = 16 * 4000
    spec = param_spec("embed/embedding", (64000, 7168), SINGLE, cfg)
    assert spec[0] == "model"
    # internvl vocab 92553 does NOT divide -> d_model fallback
    cfg2 = get_config("internvl2-2b")
    spec2 = param_spec("embed/embedding", (92553, 2048), SINGLE, cfg2)
    assert spec2[0] is None and spec2[1] == "model"


def test_norms_replicated():
    cfg = get_config("yi-34b")
    assert param_spec("layers/norm_attn", (60, 7168), SINGLE, cfg) == \
        P(None, None)


def test_batch_spec_handles_small_batch():
    assert batch_spec((256, 4096), SINGLE) == P(("data",), None)
    assert batch_spec((1, 524288), SINGLE) == P(None, None)   # long_500k
    assert batch_spec((256, 4096), MULTI) == P(("pod", "data"), None)


def test_state_spec_kv_cache():
    # [L, B, S, KV, hd]: batch on data, hd on model (KV=8 doesn't divide).
    # PartitionSpec normalizes 1-tuples to bare names.
    s = state_spec((28, 128, 32768, 8, 128), SINGLE)
    assert s[1] in ("data", ("data",))
    assert s[4] == "model"
    # rwkv state [L, B, NH, hd, hd]
    s2 = state_spec((32, 128, 40, 64, 64), SINGLE)
    assert s2[1] in ("data", ("data",)) and s2[4] == "model"
