"""The harness finds a configuration, a traffic mix, a limit and a
per-layer metric by name, each one file, and a cell added as new files
runs with no file of ``bench/`` edited. Also checks BENCHMARK.json
against the files it names."""
import json
import re
import time

import pytest

from bench import harness, spec, trace

import bench_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_new_files_are_found_by_name(tmp_path):
    bench = bench_tiny.make_root(tmp_path)
    (tmp_path / "layer_metrics/tiny.count.py").write_text(
        "def read(ctx):\n    return float(len(ctx.prefills))\n")
    bench["per_layer"] = [{"name": "tiny.count", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "executor", "moves": "ttft_p95_s",
                           "workloads": ["tiny.mix"]}]
    cell = spec.find_cell(bench, "tiny.mix")
    assert spec.load_config(cell["config"], tmp_path)["hidden_size"] == 128
    assert spec.load_traffic(cell["traffic"], tmp_path)["prompt_len"] == 32
    assert spec.load_limits("tiny.mix", tmp_path)["sample_requests"] == 4
    [m] = spec.metrics_of(bench, cell, "per_layer")
    read = spec.load_reader(m["name"], tmp_path)
    ctx = harness.LayerContext(trace.Trace(), {}, {}, 1, [])
    ctx.prefills = [32, 32]
    assert read(ctx) == 2.0


def test_cell_of_new_files_runs(tmp_path):
    before = {p: p.read_bytes() for p in spec.BENCH_DIR.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    bench = bench_tiny.make_root(tmp_path, requests=2, prompt=16, out=3)
    args = harness.parse_args(["--workload", "tiny.mix", "--seed", "5",
                               "--seconds", "0.2", "--trace", "0"])
    out = harness.run(args, time.perf_counter(), root=tmp_path,
                      bench=bench, require_chip=False)
    assert out["correct"] is True
    assert set(out["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    after = {p: p.read_bytes() for p in spec.BENCH_DIR.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert before == after


def test_per_layer_metric_without_workloads_is_an_error(tmp_path):
    bench = bench_tiny.make_root(tmp_path)
    cell = spec.find_cell(bench, "tiny.mix")
    bench["per_layer"] = [{"name": "device.idle", "moves": "output_tok_s"}]
    with pytest.raises(KeyError):
        spec.metrics_of(bench, cell, "per_layer")
    bench["per_layer"][0]["workloads"] = ["other.cell"]
    assert spec.metrics_of(bench, cell, "per_layer") == []


def test_disaggregated_mix_is_refused(tmp_path):
    bench = bench_tiny.make_root(tmp_path, setup="2P2D-ici")
    args = harness.parse_args(["--workload", "tiny.mix", "--seed", "5",
                               "--seconds", "0.2", "--trace", "0"])
    with pytest.raises(ValueError, match="colocated"):
        harness.run(args, time.perf_counter(), root=tmp_path, bench=bench,
                    require_chip=False)


def test_unknown_names_are_errors(tmp_path):
    with pytest.raises(FileNotFoundError):
        spec.load_config("no-such-config", tmp_path)
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v99")
    with pytest.raises(KeyError):
        spec.find_cell(spec.load_benchmark(), "no.such.cell")


def test_benchmark_json_names_its_files():
    b = spec.load_benchmark()
    assert b["command"][:2] == ["python3", "bench/run.py"]
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert NAME.match(c["name"])
        f = spec.load_config(c["name"])
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert sorted(c["reduced"]) == sorted(f["reduced"])
    names = {c["name"] for c in b["configs"]}
    cells = [w["name"] for w in b["workloads"]]
    assert len(cells) == len(set(cells))
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        spec.load_traffic(w["traffic"])
        spec.load_limits(w["name"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        spec.load_reader(m["name"])
        assert m["workloads"] and set(m["workloads"]) <= set(cells)


def test_peaks_table_has_the_v5e():
    pk = spec.load_peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in pk["source"]
    assert json.loads((spec.BENCH_DIR / "peaks.json").read_text())
