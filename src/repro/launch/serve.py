"""Serving launcher: run the paper's setups on any zoo architecture.

Two modes:
  * simulation (default): one declarative ``repro.exp`` Experiment —
    TPU-target timing/energy via the roofline cost model, memoized in
    the content-addressed result cache like every figure cell.
  * --real: the registry config at its published widths executed on the
    available devices (a TPU chip, or the CPU), with real KV transfers
    between engines; accelerator n of the fleet runs on device
    n % len(jax.devices()). Timing stays the cost model's; the token
    streams are real and compared across setups. --smoke swaps in the
    reduced config of the same family (CPU tests). Real runs use live
    executors, so they simulate directly and are never cached.

``--setup`` takes a legacy setup name, the intra-GPU P/D split
("intra-gpu" / "intra-<k>": SM-sliced prefill+decode engines sharing
one KV pool, repro.sched), or any fleet shape ("2P2D-ici", "co-3"; see
repro.fleet.FleetSpec.parse).

  PYTHONPATH=src python -m repro.launch.serve --arch llama32-3b \
      --setup dis-ici --batch-size 16
  PYTHONPATH=src python -m repro.launch.serve --setup 2P2D-ici
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ModelConfig, get_config, reduce_for_smoke
from repro.core import RealExecutor, SETUPS, make_cluster, random_workload
from repro.exp import Experiment
from repro.exp import run as run_exp
from repro.fleet import FleetSpec
from repro.launch.cache import use_compile_cache
from repro.models import get_model


def serve(arch, setup: str, *, batch_size: int = 16,
          input_len: int = 16_384, output_len: int = 256,
          phi: float = 1.0, governor: str = None, real: bool = False,
          smoke: bool = False, seed: int = 0, verbose: bool = True):
    """Serve one closed batch. ``arch`` is a registry name, or (real mode
    only) a ``ModelConfig``; ``smoke`` reduces it for the CPU."""
    if real:
        cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
        if smoke:
            cfg = reduce_for_smoke(cfg)
        arch = cfg.name
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        devices = jax.devices()
        placed = {}                    # one params copy per device

        def executor_factory(acc):
            dev = devices[acc % len(devices)]
            if dev not in placed:
                placed[dev] = jax.device_put(params, dev)
            return RealExecutor(model, placed[dev], device=dev)

        reqs = random_workload(batch_size, input_len=input_len,
                               output_len=output_len,
                               vocab_size=cfg.vocab_size, seed=seed)
        kw = {"governor": governor} if governor else {}
        cluster = make_cluster(setup, cfg, phi=phi,
                               executor_factory=executor_factory, **kw)
        res = cluster.run(reqs)
    else:
        exp = Experiment.closed(setup, batch_size, arch=arch,
                                input_len=input_len,
                                output_len=output_len,
                                seed=seed).with_phi(phi=phi)
        if governor:
            exp = exp.with_governor(governor)
        res = run_exp(exp)
    if verbose:
        m = res.metrics
        gov = f" governor={governor}" if governor else ""
        print(f"[serve] {setup} arch={arch} bs={batch_size} "
              f"phi={phi}{gov}")
        if real:
            print("  devices: " + "  ".join(
                f"{e.name}={e.executor.device}" for e in cluster.engines)
                + "  (times and energy below: cost model)")
        print(f"  median TTFT {m.median_ttft_s:.3f}s  "
              f"median TPOT {m.median_tpot_s * 1e3:.2f}ms")
        print(f"  prefill tput {m.prefill_throughput_tok_s:.0f} tok/s  "
              f"decode tput {m.decode_throughput_tok_s:.0f} tok/s")
        print(f"  energy {res.energy.total_j / 1e3:.2f} kJ  "
              f"({res.joules_per_token:.4f} J/token)  "
              f"evictions={m.total_evictions}")
        print(f"  breakdown: " + "  ".join(
            f"{k}={v / 1e3:.2f}kJ" for k, v in
            sorted(res.energy.breakdown().items())))
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32-3b")
    ap.add_argument("--setup", default="dis-ici",
                    help=f"one of {SETUPS}, the intra-GPU P/D split "
                         "'intra-gpu' (repro.sched), or a fleet shape "
                         "like '2P2D-ici' / 'co-3' / 'intra-2'")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--input-len", type=int, default=16_384)
    ap.add_argument("--output-len", type=int, default=256)
    ap.add_argument("--phi", type=float, default=1.0)
    ap.add_argument("--governor", default=None,
                    help="online DVFS governor (repro.govern): "
                         "static / queue-depth / slo-slack")
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="with --real: the reduced config (CPU tests)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.setup not in SETUPS:
        try:
            FleetSpec.parse(args.setup)
        except ValueError as e:
            ap.error(str(e))          # usage error, not a traceback
    use_compile_cache()
    serve(args.arch, args.setup, batch_size=args.batch_size,
          input_len=args.input_len, output_len=args.output_len,
          phi=args.phi, governor=args.governor, real=args.real,
          smoke=args.smoke, seed=args.seed)


if __name__ == "__main__":
    main()
