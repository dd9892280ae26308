import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a compile proof on the CPU backend: never take a chip that another
# process (the parent that started this one) may hold
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell, lower + compile the
appropriate step (train_step / prefill / serve_step) on the production
meshes — single-pod (16 data x 16 model = 256 chips) and multi-pod
(2 pod x 16 x 16 = 512 chips) — and report memory_analysis (fits?) +
cost_analysis (FLOPs/bytes for the roofline).

The environment lines above MUST run before any jax import: jax locks the
device count and platform at first init. Do not move them; do not set
them globally.

Cost-number methodology (DESIGN.md section 2): XLA counts a while-loop
body once, so the full-size compile (rolled scan; fast, and the actual
compile/memory proof) cannot give whole-model FLOPs. Roofline terms come
from two small FULLY-UNROLLED lowerings at 1 and 2 layer-periods and exact
linear extrapolation (layer stacks are homogeneous, so cost(L) = a + b*L).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out dryrun_results.json
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from typing import Dict, Optional, Tuple

import jax

from repro.configs import (ALL_SHAPES, ASSIGNED_ARCHS, SHAPES, applicable,
                           get_config, skip_reason)
from repro.configs.base import ModelConfig
from repro.dist.hlo_analysis import (RooflineTerms, collective_stats,
                                     cost_numbers, linear_extrapolate,
                                     model_flops, structural_memory_floor,
                                     vmem_resident_traffic)
from repro.launch.mesh import make_production_mesh
from repro.models import layers as model_layers
from repro.serve.steps import build_step


# ----------------------------------------------------------------------
def with_periods(cfg: ModelConfig, n: int) -> ModelConfig:
    """Same arch at n layer-periods (for the unrolled cost lowerings)."""
    if cfg.family == "hybrid":
        return cfg.replace(num_layers=n * cfg.hybrid.shared_attn_every)
    if cfg.family == "encdec":
        return cfg.replace(
            num_layers=n,
            encdec=dataclasses.replace(cfg.encdec, num_encoder_layers=n,
                                       num_decoder_layers=n))
    if cfg.family == "moe":
        return cfg.replace(num_layers=cfg.moe.first_k_dense + n)
    return cfg.replace(num_layers=n)


def full_periods(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid.shared_attn_every
    if cfg.family == "encdec":
        return cfg.encdec.num_decoder_layers
    if cfg.family == "moe":
        return cfg.num_layers - cfg.moe.first_k_dense
    return cfg.num_layers


def _lower_compile(cfg, shape, mesh, unroll) -> Tuple:
    model_layers.set_scan_unroll(unroll)
    try:
        with mesh:
            bundle = build_step(shape.kind, cfg, mesh, shape)
            lowered = bundle.fn.lower(*bundle.abstract_args)
            compiled = lowered.compile()
        return lowered, compiled
    finally:
        model_layers.set_scan_unroll(1)


# ----------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, analyze: bool = True) -> Dict:
    """Lower + compile one (arch, shape, mesh) cell; returns the record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_chips = 512 if multi_pod else 256
    rec: Dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": shape.kind}
    if not applicable(cfg, shape):
        rec["status"] = "skip"
        rec["reason"] = skip_reason(cfg, shape)
        return rec
    try:
        # --- 1) full-size rolled compile: THE dry-run proof -----------
        t0 = time.time()
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, compiled = _lower_compile(cfg, shape, mesh, unroll=1)
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        coll_rolled = collective_stats(hlo)
        rec.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "collectives_rolled": {
                "bytes_by_kind": coll_rolled.bytes_by_kind,
                "count_by_kind": coll_rolled.count_by_kind,
            },
        })

        # --- 2) roofline terms via small unrolled lowerings -----------
        if analyze:
            t1 = time.time()
            n_full = full_periods(cfg)
            n1, n2 = 1, 2
            vals = {}
            for n in (n1, n2):
                c_small = with_periods(cfg, n)
                _, comp = _lower_compile(c_small, shape, mesh, unroll=True)
                fl, by = cost_numbers(comp)
                cb = collective_stats(comp.as_text()).total_bytes
                vals[n] = (fl, by, cb)
            flops = linear_extrapolate(vals[n1][0], vals[n2][0], n1, n2,
                                       n_full)
            hbm = linear_extrapolate(vals[n1][1], vals[n2][1], n1, n2,
                                     n_full)
            coll = linear_extrapolate(vals[n1][2], vals[n2][2], n1, n2,
                                      n_full)
            terms = RooflineTerms(
                flops=flops, hbm_bytes=hbm, collective_bytes=coll,
                n_chips=n_chips,
                model_flops=model_flops(cfg, shape, n_chips),
                vmem_resident_bytes=vmem_resident_traffic(cfg, shape,
                                                          n_chips),
                memory_floor_bytes=structural_memory_floor(cfg, shape,
                                                           n_chips))
            rec["roofline"] = terms.as_dict()
            rec["analyze_s"] = round(time.time() - t1, 1)
    except Exception as e:   # a failure here is a sharding bug — report it
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if verbose:
        _print_rec(rec)
    return rec


def _print_rec(rec: Dict) -> None:
    if rec["status"] == "skip":
        print(f"[SKIP] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s}"
              f" -- {rec['reason'][:60]}", flush=True)
        return
    if rec["status"] == "fail":
        print(f"[FAIL] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s}"
              f" -- {rec['error'][:120]}", flush=True)
        return
    msg = (f"[ OK ] {rec['arch']:22s} {rec['shape']:12s} {rec['mesh']:8s} "
           f"args={rec['argument_bytes']/2**30:8.1f}GiB "
           f"temp={rec['temp_bytes']/2**30:7.1f}GiB "
           f"compile={rec['compile_s']:5.0f}s")
    if "roofline" in rec:
        r = rec["roofline"]
        msg += (f" | comp={r['compute_s']:.3f}s mem={r['memory_s']:.3f}s "
                f"coll={r['collective_s']:.3f}s dom={r['dominant']}"
                f" useful={r['useful_flops_ratio']:.2f}")
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: all assigned)")
    ap.add_argument("--shape", default=None,
                    help="shape name (default: all four)")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="all assigned archs x all shapes")
    ap.add_argument("--no-analyze", action="store_true",
                    help="compile proof only (skip roofline lowerings)")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else [s.name for s in ALL_SHAPES]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                # roofline table is single-pod only (assignment)
                records.append(run_cell(arch, shape, mp,
                                        analyze=not args.no_analyze
                                        and not mp))

    n_fail = sum(r["status"] == "fail" for r in records)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    print(f"\n== dry-run: {n_ok} ok, {n_skip} skip, {n_fail} fail "
          f"/ {len(records)} cells")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
