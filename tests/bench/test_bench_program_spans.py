"""The program's own wall-clock spans (``repro.*``, written by
``RealExecutor``) in a real trace: one tiny colocated burst run by the
harness under ``jax.profiler`` on the CPU and reduced by
``bench.trace.load``. Every ``repro.decode`` holds join, dispatch, sync
and split, nested, disjoint and in that order; every ``repro.prefill``
its dispatch and sync; and each lies inside the benchmark's span around
the same call. ``load`` keeps ``bench.`` spans only, so the fixture
widens its prefix to read the program's.
"""
import jax
import pytest

from bench import harness, trace

import bench_tiny

CASES = {
    "decode": ("repro.decode", ("repro.decode.join", "repro.decode.dispatch",
                                "repro.decode.sync", "repro.decode.split"),
               "bench.decode_batch"),
    "prefill": ("repro.prefill", ("repro.prefill.dispatch",
                                  "repro.prefill.sync"),
                "bench.prefill")}
MIX = {"setup": "co-1gpu", "burst_requests": 3, "prompt_len": 24,
       "output_len": 5}


@pytest.fixture(scope="module")
def burst_trace(tmp_path_factory):
    """(trace, Stamps, requests) of one traced burst, compiled outside
    the trace."""
    seed = 4294967311
    server = harness.Server(bench_tiny.tiny_config(), MIX,
                            jax.devices()[:1])
    server.load(seed)
    server.burst(seed, 0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    out = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            _, reqs, st = server.burst(seed, 1)
    finally:
        jax.profiler.stop_trace()
    server.unload()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "SPAN_PREFIX", ("bench.", "repro."))
        tr = trace.load(str(out))
    return tr, st, reqs


def _inside(outer, inner):
    return [iv for iv in inner if outer[0] <= iv[0] and iv[1] <= outer[1]]


@pytest.mark.parametrize("call", sorted(CASES))
def test_program_spans_nest_in_a_tiny_burst(burst_trace, call):
    tr, st, reqs = burst_trace
    parent, children, bench_span = CASES[call]
    parents = trace.spans(tr, parent)
    want = (len(st.decode_steps) if call == "decode" else len(st.prefills))
    assert len(parents) == want > 0
    assert want == (MIX["output_len"] - 1 if call == "decode" else len(reqs))
    for p in parents:
        held = []
        for child in children:
            [c] = _inside(p, trace.spans(tr, child))
            held.append(c)
        for a, b in zip(held, held[1:]):
            assert a[1] <= b[0]
    # no child outside a parent
    for child in children:
        assert len(trace.spans(tr, child)) == len(parents)
    calls = trace.spans(tr, bench_span)
    assert len(calls) == len(parents)
    for c in calls:
        assert len(_inside(c, parents)) == 1
