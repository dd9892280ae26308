"""Perf-flag semantics: optimizations must preserve model outputs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import REGISTRY, reduce_for_smoke
from repro.dist import opt_flags
from repro.dist.sharding import state_spec
from repro.models import get_model


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    opt_flags.set_flags("")


def test_unknown_flag_rejected():
    with pytest.raises(ValueError):
        opt_flags.set_flags("definitely_not_a_flag")


def test_flag_roundtrip():
    opt_flags.set_flags("remat_dots,bf16_logits")
    assert opt_flags.enabled("remat_dots")
    assert opt_flags.enabled("bf16_logits")
    assert not opt_flags.enabled("seq_shard_kv")
    opt_flags.set_flags("")
    assert not opt_flags.active()


@pytest.mark.parametrize("arch", ["moonshot-v1-16b-a3b", "qwen3-1.7b",
                                  "zamba2-2.7b"])
def test_opt_flags_preserve_forward(arch):
    cfg = reduce_for_smoke(REGISTRY[arch])
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0,
                              cfg.vocab_size)
    base = model.forward(params, {"tokens": toks})
    opt_flags.set_flags("local_moe_dispatch,remat_dots")
    tuned = model.forward(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(base, np.float32),
                               np.asarray(tuned, np.float32), atol=1e-5)


def test_opt_flags_preserve_grads():
    cfg = reduce_for_smoke(REGISTRY["moonshot-v1-16b-a3b"])
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.sample_batch(jax.random.PRNGKey(1), 2, 32)

    def loss(p):
        return model.loss(p, batch, remat=True)[0]

    g_base = jax.grad(loss)(params)
    opt_flags.set_flags("remat_dots,local_moe_dispatch")
    g_opt = jax.grad(loss)(params)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), g_base, g_opt)
    assert max(jax.tree.leaves(errs)) < 1e-4


def test_seq_shard_kv_changes_cache_spec():
    mesh = AbstractMesh((16, 16), ("data", "model"))
    kv_shape = (28, 128, 32768, 8, 128)
    base = state_spec(kv_shape, mesh)
    assert base[4] == "model" and base[2] is None
    opt_flags.set_flags("seq_shard_kv")
    tuned = state_spec(kv_shape, mesh)
    assert tuned[2] == "model" and tuned[4] is None
    # recurrent states (4-D) are unaffected
    assert state_spec((32, 128, 40, 64), mesh)[1] in ("data", ("data",))


def test_bf16_logits_keeps_dtype():
    cfg = reduce_for_smoke(REGISTRY["qwen3-1.7b"]).replace(
        param_dtype="bfloat16", compute_dtype="bfloat16")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                              cfg.vocab_size)
    opt_flags.set_flags("bf16_logits")
    out = model.forward(params, {"tokens": toks})
    assert out.dtype == jnp.bfloat16
    opt_flags.set_flags("")
    out2 = model.forward(params, {"tokens": toks})
    assert out2.dtype == jnp.float32


def test_masked_cache_update_decode_equivalence():
    cfg = reduce_for_smoke(REGISTRY["qwen2-0.5b"])
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0,
                              cfg.vocab_size)
    _, state = model.prefill(params, {"tokens": toks[:, :15]}, s_max=16)
    pos = jnp.full((2,), 15, jnp.int32)
    base, _ = model.decode_step(params, toks[:, 15], state, pos)
    opt_flags.set_flags("masked_cache_update")
    _, state2 = model.prefill(params, {"tokens": toks[:, :15]}, s_max=16)
    tuned, _ = model.decode_step(params, toks[:, 15], state2, pos)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tuned))


def test_flash_gqa_regroup_exact_over_head_configs():
    """pad_heads must be bit-exact for every (H, KV) shape class."""
    from repro.models import layers as L
    for H, KV in [(56, 8), (14, 2), (7, 1), (24, 8), (40, 8), (12, 4)]:
        B, S, hd = 1, 32, 16
        q = jax.random.normal(jax.random.PRNGKey(H), (B, S, H, hd))
        k = jax.random.normal(jax.random.PRNGKey(KV), (B, S, KV, hd))
        v = jax.random.normal(jax.random.PRNGKey(H + KV), (B, S, KV, hd))
        opt_flags.set_flags("")
        base = L.flash_gqa(q, k, v, causal=True)
        opt_flags.set_flags("pad_heads")
        tuned = L.flash_gqa(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(base), np.asarray(tuned),
                                   atol=1e-6, err_msg=f"H={H} KV={KV}")
    opt_flags.set_flags("")
