"""``bench/run.py`` exits nonzero, printing no result line, on a host
with no TPU, and in a directory that holds only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

from bench import spec

CELL = "qwen3-1.7b.co-1gpu.prefill-burst"


def _run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "4294967296", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict)
                    and {"metrics", "device"} & set(obj)), line


def test_no_tpu_exits_nonzero_without_result():
    r = _run(spec.REPO_DIR)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    _no_result(r.stdout)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(spec.REPO_DIR / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(spec.REPO_DIR / "tests/bench", tmp_path / "tests/bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    _no_result(r.stdout)
