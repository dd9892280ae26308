"""Find a cell's parts by name: its configuration, traffic mix, limits,
per-layer metric readers, FLOP and byte counts, and the device's peaks.

Each part is a file of its own under ``bench/``, so a new cell, mix or
metric is added as new files and entries, never by editing one:

  configs/<config>.json        sizes as run, with source and cuts
  traffic/<mix>.json           parameters of one closed-burst mix
  limits/<cell>.json           the limit of each number compared
  layer_metrics/<metric>.py    ``read(ctx)`` -> value, or None
  counts/<name>.py             FLOPs and bytes of one kernel or step
  peaks.json                   peaks by ``device_kind``, with source
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent


def _json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


def load_benchmark(repo: Path = REPO_DIR) -> dict:
    return _json(repo / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")


def load_config(name: str, root: Path = BENCH_DIR) -> dict:
    return _json(root / "configs" / f"{name}.json")


def load_traffic(name: str, root: Path = BENCH_DIR) -> dict:
    return _json(root / "traffic" / f"{name}.json")


def load_limits(cell: str, root: Path = BENCH_DIR) -> dict:
    return _json(root / "limits" / f"{cell}.json")


def load_peaks(device_kind: str, root: Path = BENCH_DIR) -> dict:
    table = _json(root / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({', '.join(table)})")
    return table[device_kind]


def _load_module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: Path = BENCH_DIR):
    """The ``read(ctx)`` of ``layer_metrics/<metric>.py``."""
    return _load_module(root / "layer_metrics" / f"{metric}.py").read


def load_counts(name: str):
    """The module ``counts/<name>.py`` (``flops``, and ``bytes`` where
    a roofline needs them)."""
    return importlib.import_module(f"bench.counts.{name}")


def metrics_of(bench: dict, cell: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    a per-layer metric where its ``workloads`` lists the cell, an
    end-to-end one where its ``workloads`` lists it or where it has
    none."""
    out = []
    for m in bench[kind]:
        if kind == "per_layer" and "workloads" not in m:
            raise KeyError(f"per-layer metric {m['name']!r} lists no "
                           "workloads")
        if cell["name"] in m.get("workloads", [cell["name"]]):
            out.append(m)
    return out
