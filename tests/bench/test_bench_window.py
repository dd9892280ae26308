"""Window accounting on synthetic token stamps (no device)."""
import numpy as np
import pytest

from bench import window


def _burst(submit, first, step, requests=2, out=3, start_gap=0.0):
    """Requests whose first tokens come ``start_gap`` apart from
    ``submit + first``, then one token every ``step``."""
    toks = {}
    for i in range(requests):
        t0 = submit + first + i * start_gap
        toks[i] = [t0 + k * step for k in range(out)]
    end = max(ts[-1] for ts in toks.values())
    return window.Burst(submit=submit, end=end, tokens=toks,
                        requests=requests, output_len=out)


def test_window_is_whole_bursts():
    bursts = [_burst(0.0, 0.5, 0.25), _burst(1.0, 0.5, 0.25)]
    s = window.summarize(bursts, t_open=0.0)
    # 2 bursts x 2 requests x 3 tokens over 0 .. 2.0 s
    assert s["output_tokens"] == 12
    assert s["window_s"] == pytest.approx(2.0)
    assert s["output_tok_s"] == pytest.approx(6.0)
    assert s["requests"] == 4 and s["bursts"] == 2 and s["failed"] == 0


@pytest.mark.parametrize("end,closes", [(9.99, False), (10.0, True),
                                        (12.5, True)])
def test_burst_straddling_seconds_closes_the_window(end, closes):
    # the burst that ends at or after --seconds is the last, and whole
    assert window.closes(t_open=0.0, burst_end=end, seconds=10.0) is closes


def test_straddling_burst_counts_all_its_tokens():
    bursts = [_burst(0.0, 1.0, 1.0, out=5), _burst(5.0, 1.0, 1.0, out=5)]
    assert window.closes(0.0, bursts[-1].end, seconds=7.0)
    s = window.summarize(bursts, t_open=0.0)
    assert s["output_tokens"] == 20
    assert s["output_tok_s"] == pytest.approx(20 / 10.0)


def test_ttft_and_itl_p95_over_all_requests_and_gaps():
    b = _burst(0.0, 0.1, 0.02, requests=10, out=4, start_gap=0.1)
    s = window.summarize([b], t_open=0.0)
    ttft = [0.1 + 0.1 * i for i in range(10)]
    assert s["ttft_p95_s"] == pytest.approx(np.percentile(ttft, 95))
    # every gap is 20 ms, from every request
    assert s["itl_p95_ms"] == pytest.approx(20.0)


def test_itl_p95_pools_gaps_across_requests():
    toks = {0: [0.0, 0.01, 0.02, 0.03], 1: [0.0, 1.0]}
    b = window.Burst(submit=0.0, end=1.0, tokens=toks, requests=2,
                     output_len=4)
    gaps = [0.01, 0.01, 0.01, 1.0]
    s = window.summarize([b], t_open=0.0)
    assert s["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95) * 1e3)
    # request 1 yielded 2 of its 4 tokens
    assert s["failed"] == 1
