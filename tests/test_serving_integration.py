"""End-to-end real-mode serving: identical token streams across ALL five
setups (the KV-handoff correctness proof), for multiple model families —
including the paper's dense case WITH eviction/recompute forced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduce_for_smoke
from repro.core import Cluster, RealExecutor, SETUPS, random_workload
from repro.models import get_model


def _run_all_setups(arch, *, n_req=3, in_len=48, out_len=6,
                    pool_tokens=None, page_size=8, budget=32, tmp=None):
    cfg = reduce_for_smoke(REGISTRY[arch])
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def factory(acc):
        return RealExecutor(model, params)

    kv_tok = max(cfg.kv_bytes_per_token(), 1)
    pool_bytes = kv_tok * (pool_tokens or (in_len + out_len) * n_req * 2)
    outs, results = {}, {}
    for setup in SETUPS:
        reqs = random_workload(n_req, input_len=in_len, output_len=out_len,
                               vocab_size=cfg.vocab_size, seed=11)
        res = Cluster(setup, cfg, executor_factory=factory,
                      pool_bytes=pool_bytes, page_size=page_size,
                      prefill_token_budget=budget).run(reqs)
        outs[setup] = [r.output_tokens for r in
                       sorted(res.requests, key=lambda r: r.req_id)]
        results[setup] = res
    return outs, results


@pytest.mark.parametrize("arch", ["llama32-3b", "qwen3-1.7b",
                                  "moonshot-v1-16b-a3b", "rwkv6-3b",
                                  "zamba2-2.7b"])
def test_identical_tokens_across_setups(arch):
    outs, _ = _run_all_setups(arch)
    base = outs["co-1gpu"]
    assert all(len(t) == 6 for t in base)
    for setup, toks in outs.items():
        assert toks == base, f"{setup} diverged from co-1gpu"


def test_identical_tokens_under_eviction():
    """Pool sized at ~1.5 sequences: colocated must preempt+recompute and
    STILL produce the same tokens (recompute correctness)."""
    outs, results = _run_all_setups("llama32-3b", n_req=4,
                                    pool_tokens=int(54 * 1.6))
    base = outs["co-1gpu"]
    for setup, toks in outs.items():
        assert toks == base, f"{setup} diverged under memory pressure"
    co = results["co-1gpu"].metrics
    assert co.total_evictions > 0, "pressure did not trigger eviction"


def test_disaggregated_metrics_structure():
    _, results = _run_all_setups("llama32-3b")
    for setup, res in results.items():
        m = res.metrics
        assert m.median_ttft_s > 0 and m.median_tpot_s >= 0
        assert res.energy.total_j > 0
        for r in res.requests:
            assert r.prefill_done_s is not None
            assert r.finish_s >= r.first_token_s >= r.arrival_s
            if setup.startswith("dis"):
                assert r.transfer_done_s is not None
                assert r.first_token_s >= r.prefill_done_s


def test_transfer_medium_orders_ttft():
    _, results = _run_all_setups("llama32-3b", n_req=4)
    ttft = {s: results[s].metrics.median_ttft_s for s in results}
    assert ttft["dis-ici"] <= ttft["dis-host"] <= ttft["dis-disk"]


def test_rwkv_state_handoff_is_tiny():
    """Attention-free arch: the transferred state must be seq-len
    independent (the degenerate-transfer case, DESIGN.md section 8)."""
    from repro.core import CostModel
    cfg = REGISTRY["rwkv6-3b"]
    cost = CostModel(cfg)
    assert cost.kv_bytes(16_384) == cost.kv_bytes(128)
    dense = CostModel(REGISTRY["llama32-3b"])
    assert dense.kv_bytes(16_384) > 100 * cost.kv_bytes(16_384)


def test_kv_reuse_improves_ttft_in_simulation():
    """PIC reuse on a warm cache must cut prefill work (paper II-C)."""
    import numpy as np
    from repro.configs import get_config
    from repro.core import Cluster, random_workload
    from repro.core.prefix_cache import PrefixCache
    cfg = get_config("llama32-3b")

    def wl():
        rng = np.random.default_rng(0)
        doc = rng.integers(0, cfg.vocab_size, 4096)
        reqs = random_workload(8, input_len=16_384, output_len=32,
                               vocab_size=cfg.vocab_size, seed=1)
        for r in reqs:
            r.prompt_tokens[512:512 + 4096] = doc
        return reqs

    base = Cluster("co-2gpus", cfg).run(wl())
    cache = PrefixCache(200_000, page_size=16, pic=True)
    reqs = wl()
    cache.insert(reqs[0].prompt_tokens)
    cluster = Cluster("co-2gpus", cfg)
    for e in cluster.engines:
        e.prefix_cache = cache
    reused = cluster.run(reqs)
    assert sum(r.reused_tokens for r in reused.requests) > 8 * 3000
    assert reused.metrics.median_ttft_s < base.metrics.median_ttft_s
