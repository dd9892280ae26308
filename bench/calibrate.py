"""Readings that the limits in ``limits/<cell>.json`` are set from.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
      [--control | --served-control] [--out FILE]

For each seed, in one process: the weights from the seed, the cell's own
bursts through the timed path until a run's sample of finished requests
is due, then, with the program's state freed, the widest gap of the
served tokens against the float32 reference (``gap``, the number each
run compares) and, with ``--control``, the widest gap of the tokens the
fp8 control puts first at the same positions (``control_gap``), judged
by ``check.judge`` as a run would be (``control_correct``). One JSON
line per seed.

``--served-control`` instead makes, for each seed, a whole run of the
cell (``harness.run``, a window of one burst) with the fp8 control
serving every token in the program's place (``bench.control``), and
prints its ``correct`` and ``compared``. The benchmark's own runs never
run a control.
"""
import time

T_START = time.perf_counter()

import argparse                                     # noqa: E402
import json                                         # noqa: E402
import math                                         # noqa: E402
import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

_REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_REPO), str(_REPO / "src")]

from bench import check, control, harness, spec, traffic  # noqa: E402


def calibrate(cell_name: str, seeds: list, control: bool, *,
              root=spec.BENCH_DIR, bench=None, require_chip: bool = True,
              fault=None, log=print) -> list:
    bench = bench or spec.load_benchmark()
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.load_config(cell["config"], root)
    mix = traffic.check_mix(spec.load_traffic(cell["traffic"], root))
    limits = spec.load_limits(cell["name"], root)
    n_sample = limits["sample_requests"]
    devices, _ = harness.open_devices(cell, require_chip)
    server = harness.Server(cfg, mix, devices, fault=fault, root=root)
    n_bursts = max(1, math.ceil(n_sample / mix["burst_requests"]))
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        server.load(seed)
        reqs = []
        for index in range(1, n_bursts + 1):
            reqs.extend(server.burst(seed, index)[1])
        server.unload()
        sample = check.sample(reqs, seed, n_sample)
        r = check.readings(server.arch, cfg, seed, devices[0], sample,
                           control=control)
        if control:
            r["control_correct"] = check.judge(
                {**r, "gap": r["control_gap"]}, limits, len(reqs), 0)[0]
        r.update(seed=seed, seconds=time.perf_counter() - t0)
        log(json.dumps(r))
        out.append(r)
    return out


def served_control(cell_name: str, seeds: list, *, root=spec.BENCH_DIR,
                   bench=None, require_chip: bool = True,
                   log=print) -> list:
    """A whole run of the cell per seed, a window of one burst, with the
    fp8 control serving every token; (seed, correct, compared) each."""
    bench = bench or spec.load_benchmark()
    cell = spec.find_cell(bench, cell_name)
    cfg = spec.load_config(cell["config"], root)
    fault = control.served(spec.load_arch(cfg, root), cfg)
    out = []
    for seed in seeds:
        args = harness.parse_args(["--workload", cell_name, "--seed",
                                   str(seed), "--seconds", "0.01",
                                   "--trace", "0"])
        res = harness.run(args, time.perf_counter(), root=root, bench=bench,
                          fault=fault,
                          require_chip=require_chip)
        r = {"seed": seed, "correct": res["correct"],
             "compared": res["compared"]}
        log(json.dumps(r))
        out.append(r)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--served-control", action="store_true")
    ap.add_argument("--out", default=None, help="append the lines here")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.served_control:
        rows = served_control(args.workload, seeds)
        if args.out:
            with open(args.out, "a") as f:
                for r in rows:
                    f.write(json.dumps({"workload": args.workload,
                                        "served_control": True, **r}) + "\n")
        print(json.dumps({"workload": args.workload, "served_control": True,
                          "correct": [r["correct"] for r in rows]}))
        return 0
    rows = calibrate(args.workload, seeds, args.control)
    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps({"workload": args.workload, **r}) + "\n")
    gaps = [r["gap"] for r in rows]
    line = {"workload": args.workload, "max_gap": max(gaps),
            "seconds": time.perf_counter() - T_START}
    if args.control:
        line["min_control_gap"] = min(r["control_gap"] for r in rows)
        line["control_correct"] = [r["control_correct"] for r in rows]
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
