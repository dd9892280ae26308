"""The trace reduction: on a hand-made trace with worked answers, and on
a small trace recorded on a v5e chip (one burst of the qwen3-1.7b
prefill-burst cell, reduced by ``bench.trace.load``), checked against a
brute-force sampling of the same intervals."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, proxy, spec, trace

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


# ----------------------------------------------------------------------
# coverage: readers that divide calls by device time read only the
# bursts whose every call holds its program's event
# ----------------------------------------------------------------------
CHANGED = ("device.idle", "decode.host_ms", "prefill.mfu",
           "flash_prefill_roofline", "decode_step_roofline", "decode.mfu")


def _bursts(n):
    """(trace, stamps) of ``n`` bursts 10 s apart, each a prefill and
    two decode steps, with a collector pause between bursts; the window
    closes with the last burst."""
    ops, mods, host, stamps = [], [], [], []
    for k in range(n):
        t = 10.0 * k
        ops += [("fusion.1", t + 1, t + 2),
                ("flash_attention.7", t + 2, t + 3),
                ("fusion.4", t + 4, t + 4.5), ("fusion.4", t + 5.5, t + 6.5),
                ("fusion.9", t + 7.2, t + 7.4)]
        # the prefill's event begins before its span, as on the chip
        mods += [("jit_prefill(1)", t + 0.9, t + 3),
                 ("jit_decode_step(2)", t + 4, t + 4.5),
                 ("jit_decode_step(2)", t + 5.5, t + 6.5)]
        host += [("bench.burst", t + 0.5, t + 8),
                 ("bench.prefill", t + 1, t + 3.5),
                 ("bench.decode_batch", t + 3.5, t + 5),
                 ("bench.decode_batch", t + 5, t + 7)]
        if k:
            host.append(("bench.gc", t - 1.5, t - 1))
        st = proxy.Stamps()
        st.prefills = [1000 + k]
        st.decode_steps = [[1000 + k] * 4, [1001 + k] * 4]
        stamps.append(st)
    host.append(("bench.window", 0.0, 10.0 * (n - 1) + 8))
    return (trace.Trace(devices={"/device:TPU:0": {"ops": ops,
                                                  "modules": mods}},
                        host=sorted(host, key=lambda e: e[1])), stamps)


def _drop(tr, lo, hi, prefix=""):
    """The trace with the device events that start in [lo, hi) and whose
    name starts with ``prefix`` removed."""
    return trace.Trace(
        devices={d: {ln: [ev for ev in evs if not (
            lo <= ev[1] < hi and ev[0].startswith(prefix))]
            for ln, evs in lines.items()}
            for d, lines in tr.devices.items()},
        host=list(tr.host))


def _ctx(tr, stamps):
    return harness.LayerContext(tr, spec.load_config("qwen3-1.7b"),
                                spec.load_peaks("TPU v5 lite"), 1, stamps)


def _values(tr, stamps):
    """Every changed reader's value."""
    ctx = _ctx(tr, stamps)
    return {name: spec.load_reader(name)(ctx) for name in CHANGED}


def _breakdown(tr, stamps):
    """What a traced run reports from the covered stretches besides the
    readers: busy and window seconds, top ops and idle gaps."""
    region = _ctx(tr, stamps).region
    return {"busy_s": trace.busy_s(tr, region),
            "window_s": trace.length(region),
            "top_ops": trace.top_ops(tr, region=region),
            "idle_gaps": trace.idle_gaps(tr, region=region)}


def test_lost_last_burst_reads_as_the_first_two_alone():
    tr, stamps = _bursts(3)
    lost = _drop(tr, 20.0, 30.0)
    two, two_stamps = _bursts(2)
    assert _values(lost, stamps) == _values(two, two_stamps)
    assert _breakdown(lost, stamps) == _breakdown(two, two_stamps)
    ctx = _ctx(lost, stamps)
    assert ctx.covered == [0, 1]
    assert ctx.coverage() == ("trace covers 2 of 3 bursts, 2 of 3 "
                              "prefills, 4 of 6 decode steps")


def test_lost_decode_event_leaves_its_burst_out():
    tr, stamps = _bursts(3)
    # the second decode step of the middle burst lost its event; it
    # reads as if the whole burst had lost its device events
    one = _drop(tr, 15.5, 16.0, "jit_decode_step")
    burst = _drop(tr, 10.0, 20.0)
    assert _values(one, stamps) == _values(burst, stamps)
    assert _values(one, stamps) != _values(tr, stamps)
    assert _breakdown(one, stamps) == _breakdown(burst, stamps)
    assert _ctx(one, stamps).covered == [0, 2]


@pytest.mark.parametrize("name", CHANGED)
def test_no_covered_burst_raises(name):
    tr, stamps = _bursts(2)
    lost = _drop(tr, 0.0, 30.0, "jit_prefill")
    ctx = _ctx(lost, stamps)
    with pytest.raises(RuntimeError, match="covers none of the window's "
                                           "2 bursts"):
        spec.load_reader(name)(ctx)
    assert ctx.coverage() == ("trace covers 0 of 2 bursts, 0 of 2 "
                              "prefills, 0 of 4 decode steps")


# what the readers read when they read the whole window, before they
# read covered bursts, on ``_bursts(3)`` and on the recorded trace with
# the calls below; a whole trace reads them still
WHOLE_BEFORE = {
    "device.idle": 60.35714285714286, "decode.host_ms": 1000.0,
    "prefill.mfu": 0.709945748121505,
    "flash_prefill_roofline": 0.058392065905922164,
    "decode_step_roofline": 0.6350913849409849,
    "decode.mfu": 0.009938007672419627}
RECORDED_BEFORE = {
    "device.idle": 29.09489902154433, "decode.host_ms": 29.717028562500026,
    "prefill.mfu": 61.989136257126354,
    "flash_prefill_roofline": 17.17401676797268,
    "decode_step_roofline": 51.82279702443945,
    "decode.mfu": 1.2726601014547114}


def test_whole_trace_reads_as_the_whole_window():
    tr, stamps = _bursts(3)
    got = _values(tr, stamps)
    for name in CHANGED:
        assert got[name] == pytest.approx(WHOLE_BEFORE[name], rel=1e-12)
    lo, hi = trace.window(tr)
    assert _breakdown(tr, stamps) == {
        "busy_s": trace.busy_s(tr), "window_s": hi - lo,
        "top_ops": trace.top_ops(tr), "idle_gaps": trace.idle_gaps(tr)}
    ctx = _ctx(tr, stamps)
    assert ctx.covered == [0, 1, 2]
    assert ctx.region == [(lo, hi)]


def test_recorded_trace_is_covered_and_reads_as_before():
    # the recorded burst's calls, as its spans hold: 8 prefills and 16
    # decode steps at batch 8; the lengths are this test's own
    st = proxy.Stamps()
    st.prefills = [2000] * 8
    st.decode_steps = [[2000 + k] * 8 for k in range(16)]
    tr = _recorded()
    ctx = _ctx(tr, [st])
    for name in CHANGED:
        assert spec.load_reader(name)(ctx) == pytest.approx(
            RECORDED_BEFORE[name], rel=1e-12)
    assert ctx.coverage() == ("trace covers 1 of 1 bursts, 8 of 8 "
                              "prefills, 16 of 16 decode steps")
    assert ctx.region == [trace.window(tr)]
