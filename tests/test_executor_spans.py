"""``RealExecutor``'s wall-clock spans, recorded in place of
``jax.profiler.TraceAnnotation`` through a tiny colocated burst: every
``prefill`` and ``decode_batch`` call opens the same fixed sequence of
named spans whatever the batch size (none per request), each by one
constant name from ``repro.obs.trace`` and no other argument."""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import RealExecutor, make_cluster
from repro.core.request import Request
from repro.models import get_model
from repro.obs import trace as obs_trace

PREFILL_CALL = (
    ("enter", obs_trace.PREFILL_SPAN),
    ("enter", obs_trace.PREFILL_DISPATCH_SPAN),
    ("exit", obs_trace.PREFILL_DISPATCH_SPAN),
    ("enter", obs_trace.PREFILL_SYNC_SPAN),
    ("exit", obs_trace.PREFILL_SYNC_SPAN),
    ("exit", obs_trace.PREFILL_SPAN))
DECODE_CALL = (("enter", obs_trace.DECODE_SPAN),) + tuple(
    ev for child in (obs_trace.DECODE_JOIN_SPAN,
                     obs_trace.DECODE_DISPATCH_SPAN,
                     obs_trace.DECODE_SYNC_SPAN,
                     obs_trace.DECODE_SPLIT_SPAN)
    for ev in (("enter", child), ("exit", child))) + (
    ("exit", obs_trace.DECODE_SPAN),)


class _Recorder:
    """Stands in for ``TraceAnnotation``: logs each span's enter and
    exit, and refuses any argument besides the name."""

    def __init__(self):
        self.log = []

    def __call__(self, *args, **kwargs):
        assert not kwargs and len(args) == 1, (args, kwargs)
        [name] = args
        log = self.log

        class _Span:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Span()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = reduce_for_smoke(get_config("qwen3-1.7b"))
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_each_call_opens_the_same_spans_at_any_batch(tiny_model, batch):
    cfg, model, params = tiny_model
    rec = _Recorder()
    ex = RealExecutor(model, params)
    ex._span = rec
    calls, sizes = [], []
    prefill, decode_batch = ex.prefill, ex.decode_batch

    def traced_prefill(seq):
        calls.append(("prefill", len(rec.log)))
        return prefill(seq)

    def traced_decode(seq_batch):
        calls.append(("decode", len(rec.log)))
        sizes.append(len(seq_batch))
        return decode_batch(seq_batch)

    ex.prefill, ex.decode_batch = traced_prefill, traced_decode
    reqs = [Request(req_id=i, prompt_len=12, output_len=4, arrival_s=0.0,
                    prompt_tokens=np.arange(12, dtype=np.int32) + i)
            for i in range(batch)]
    make_cluster("co-1gpu", cfg, executor_factory=lambda acc: ex).run(reqs)

    kinds = [k for k, _ in calls]
    assert kinds.count("prefill") == batch
    assert kinds.count("decode") >= 3
    assert max(sizes) == batch
    starts = [at for _, at in calls] + [len(rec.log)]
    for (kind, at), end in zip(calls, starts[1:]):
        want = PREFILL_CALL if kind == "prefill" else DECODE_CALL
        assert tuple(rec.log[at:end]) == want
