"""End-to-end disaggregated serving with REAL KV transfer (deliverable b).

Runs the same request batch through colocated and all three disaggregated
transfer paths with an actual (reduced) model executing on CPU: prefill on
engine 0, KV handoff through the medium (including a real disk round
trip), decode on engine 1 — and proves the token streams are identical.

  PYTHONPATH=src python examples/serve_disaggregated.py --arch rwkv6-3b
"""
import argparse

import jax

from repro.configs import get_config, reduce_for_smoke
from repro.core import Cluster, RealExecutor, SETUPS, random_workload
from repro.models import get_model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32-3b",
                    help="any zoo arch (dense/moe/ssm/hybrid)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--input-len", type=int, default=48)
    ap.add_argument("--output-len", type=int, default=8)
    args = ap.parse_args(argv)

    cfg = reduce_for_smoke(get_config(args.arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"arch={cfg.name} family={cfg.family} "
          f"params={model.param_count():,}")
    state_note = ("fixed-size recurrent state" if cfg.family == "ssm" else
                  "KV cache" if cfg.family != "hybrid" else
                  "mixed SSM state + shared-block KV")
    print(f"handoff payload: {state_note}")

    def factory(acc):
        return RealExecutor(model, params)

    streams = {}
    for setup in SETUPS:
        reqs = random_workload(args.requests, input_len=args.input_len,
                               output_len=args.output_len,
                               vocab_size=cfg.vocab_size, seed=3)
        res = Cluster(setup, cfg, executor_factory=factory).run(reqs)
        ordered = sorted(res.requests, key=lambda r: r.req_id)
        streams[setup] = [r.output_tokens for r in ordered]
        m = res.metrics
        print(f"{setup:10s} TTFT={m.median_ttft_s:7.3f}s "
              f"TPOT={m.median_tpot_s * 1e3:7.2f}ms "
              f"tokens[req0]={streams[setup][0]}")

    base = streams["co-1gpu"]
    ok = all(s == base for s in streams.values())
    print("token streams identical across all setups:", ok)
    assert ok


if __name__ == "__main__":
    main()
