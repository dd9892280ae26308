"""The benchmark's own hooks around the program: wall-clock token stamps,
host spans for the trace, and a record of the work each call did. No
program file is edited; ``ExecutorProxy`` wraps a ``RealExecutor`` after
construction, and runs ``prefill`` and ``decode_batch`` inside a
``jax.profiler.TraceAnnotation`` named ``bench.<method>``.

Only colocated fleets are measured: the first token is stamped where the
program records ``first_token_s`` on a colocated engine, the return of
``prefill``. Each later token is stamped at the return of the
``decode_batch`` that made it, which has already copied the tokens to
the host.
"""
from __future__ import annotations

import time

import jax

now = time.perf_counter


class Stamps:
    """Token times of one burst by request id, and the calls made."""

    def __init__(self):
        self.tokens = {}        # req_id -> [t_first, t_2, ...]
        self.prefills = []      # prompt length of each prefill call
        self.decode_steps = []  # per decode_batch call: context lengths
        self.call_s = {}        # executor method -> wall seconds in it

    def stamp(self, req_id: int, t: float) -> None:
        self.tokens.setdefault(req_id, []).append(t)


class ExecutorProxy:
    def __init__(self, inner, stamps_ref):
        self._inner = inner
        self._stamps = stamps_ref       # callable -> current Stamps

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _call(self, method: str, *args):
        """(result, end time) of the inner executor's ``method``."""
        t0 = now()
        with jax.profiler.TraceAnnotation(f"bench.{method}"):
            out = getattr(self._inner, method)(*args)
        t1 = now()
        calls = self._stamps().call_s
        calls[method] = calls.get(method, 0.0) + t1 - t0
        return out, t1

    def prefill(self, seq):
        out, t = self._call("prefill", seq)
        st = self._stamps()
        st.prefills.append(int(seq.prefill_target))
        st.stamp(seq.req.req_id, t)
        return out

    def decode_batch(self, batch):
        ctx = [int(s.ctx) for s in batch]
        _, t = self._call("decode_batch", batch)
        st = self._stamps()
        st.decode_steps.append(ctx)
        for s in batch:
            st.stamp(s.req.req_id, t)
