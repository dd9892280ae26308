"""The trace reduction: on a hand-made trace with worked answers, and on
a small trace recorded on a v5e chip (one burst of the qwen3-1.7b
prefill-burst cell, reduced by ``bench.trace.load``), checked against a
brute-force sampling of the same intervals."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, spec, trace

DATA = Path(__file__).with_name("data") / "trace_small.json.gz"


def _hand_trace():
    return trace.Trace(
        devices={"/device:TPU:0": {
            "ops": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),
                    ("flash_attention.7", 4.0, 5.0),
                    ("fusion.3", 9.0, 11.0)],
            "modules": [("jit_prefill(1)", 1.0, 3.0),
                        ("jit_decode_step(2)", 4.0, 5.0)]}},
        host=[("bench.window", 0.0, 10.0), ("bench.burst", 0.5, 8.0),
              ("bench.prefill", 1.0, 3.5),
              ("bench.decode_batch", 3.5, 6.0), ("bench.gc", 8.0, 8.5)])


def test_interval_arithmetic():
    assert trace.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert trace.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert trace.subtract([(0, 10)], [(1, 2), (3, 4), (9, 12)]) == [
        (0, 1), (2, 3), (4, 9)]
    assert trace.length([(0, 1), (2, 4)]) == 3


def test_hand_trace_idle_and_kernel_time():
    tr = _hand_trace()
    assert trace.window(tr) == (0.0, 10.0)
    # busy [1, 3] + [4, 5] + [9, 10] inside the window
    assert trace.busy_s(tr) == pytest.approx(4.0)
    assert trace.device_time(tr, "ops", "flash_attention") == 1.0
    assert trace.device_time(tr, "modules", "jit_prefill") == 2.0
    ctx = harness.LayerContext(tr, {}, {}, 1, [])
    assert spec.load_reader("device.idle")(ctx) == pytest.approx(60.0)
    # the host is in prefill or decode_batch over [1, 6]
    assert spec.load_reader("loop.host_share")(ctx) == pytest.approx(50.0)


def test_hand_trace_idle_gap_attribution():
    gaps = dict(trace.idle_gaps(_hand_trace()))
    # idle [0, 1], [3, 4], [5, 9], put down to the innermost span
    assert gaps == pytest.approx({"decode_batch": 1.5, "prefill": 0.5,
                                  "gc": 0.5, "loop": 2.5, "window": 1.0})
    assert sum(gaps.values()) == pytest.approx(6.0)


def _recorded():
    with gzip.open(DATA, "rt") as f:
        return trace.Trace.from_json(json.load(f))


def _grid_busy(tr, dev, lo, hi, n=200_000):
    t = np.linspace(lo, hi, n, endpoint=False) + (hi - lo) / (2 * n)
    on = np.zeros(n, bool)
    for _, s, e in tr.devices[dev]["ops"]:
        on |= (t >= s) & (t < e)
    return on.mean() * (hi - lo)


def test_recorded_trace_busy_matches_brute_force():
    tr = _recorded()
    lo, hi = trace.window(tr)
    [dev] = tr.devices
    assert trace.busy_s(tr) == pytest.approx(_grid_busy(tr, dev, lo, hi),
                                             rel=2e-3)
    assert 0 < trace.busy_s(tr) < hi - lo


def test_recorded_trace_programs_and_spans():
    tr = _recorded()
    lo, hi = trace.window(tr)
    prefill = trace.device_time(tr, "modules", "jit_prefill")
    decode = trace.device_time(tr, "modules", "jit_decode_step")
    flash = trace.device_time(tr, "ops", "flash_attention")
    assert 0 < flash < prefill < hi - lo
    assert decode > 0
    # every decode step ran inside a decode_batch call
    calls = trace.spans(tr, "bench.decode_batch")
    steps = trace.events(tr, "modules", "jit_decode_step")
    assert len(steps) == len(calls)
    gaps = trace.idle_gaps(tr)
    idle = (hi - lo) - trace.busy_s(tr)
    assert sum(v for _, v in gaps) == pytest.approx(idle, rel=1e-6)
    assert gaps[0][0] in ("decode_batch", "prefill", "loop")
