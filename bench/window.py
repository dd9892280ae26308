"""The measured window and its end-to-end metrics, from token stamps.

The window is made of whole closed bursts. It opens at the first burst's
submission and closes when the first burst that ends at or after
``seconds`` (counted from the opening) completes, so no burst is cut and
no token falls outside it.

  ttft_p95_s    95th percentile, over every request of the window, of its
                first token's time minus its burst's submission
  itl_p95_ms    95th percentile, over every gap between consecutive
                tokens of one request, over all requests
  output_tok_s  every output token of the window over the window's wall
                time (bursts, and the collector between them, included)

Percentiles are numpy's linear interpolation.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class Burst:
    submit: float                 # host clock at the call to run()
    end: float                    # host clock at its return
    tokens: dict = field(default_factory=dict)   # req_id -> [t, ...]
    requests: int = 0
    output_len: int = 0


def closes(t_open: float, burst_end: float, seconds: float) -> bool:
    """Whether the burst that ended at ``burst_end`` is the window's last."""
    return burst_end - t_open >= seconds


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def failed(b: Burst) -> int:
    """Requests of the burst that did not yield all their tokens."""
    got = [len(b.tokens.get(i, ())) for i in range(b.requests)]
    return sum(n != b.output_len for n in got)


def summarize(bursts: list, t_open: float) -> dict:
    ttft, gaps, n_tokens = [], [], 0
    for b in bursts:
        for ts in b.tokens.values():
            ttft.append(ts[0] - b.submit)
            gaps.extend(np.diff(ts).tolist())
            n_tokens += len(ts)
    window_s = bursts[-1].end - t_open
    return {
        "ttft_p95_s": p95(ttft),
        "itl_p95_ms": p95(gaps) * 1e3,
        "output_tok_s": n_tokens / window_s,
        "window_s": window_s,
        "output_tokens": n_tokens,
        "requests": sum(b.requests for b in bursts),
        "failed": sum(failed(b) for b in bursts),
        "bursts": len(bursts),
    }
