"""The fp8 control put in the program's place.

``served(arch, cfg)`` wraps each executor so that every token it serves,
the first from ``prefill`` and each later one from ``decode_batch``, is
the argmax of the reference of the architecture module ``arch`` computed
in float8 (its ``logits`` with ``fp8=True``) over the request's prompt and
the tokens served before it.
The program still runs underneath and its cache is fed the control's
tokens, so the rest of a run (window, sample, reference, ``check.judge``)
is the timed path's own. A sound comparison reads such a run as not
correct.

It is slow (one reference forward per token), so it runs only from
``bench/calibrate.py --served-control`` and in tests, over a window of
one burst; the benchmark's own runs never use it.
"""
from __future__ import annotations

import jax
import numpy as np


class Fp8Served:
    def __init__(self, inner, arch, cfg: dict):
        self._inner = inner
        self._arch = arch
        self._cfg = cfg

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def _next(self, seq) -> int:
        """The fp8 reference's first choice after the prompt and the
        tokens served so far. Every row has the request's full length
        (causal, so the zeros after the context change nothing), so one
        compiled program serves every position."""
        req = seq.req
        ctx = list(req.prompt_tokens) + list(req.output_tokens)
        toks = np.zeros((1, req.prompt_len + req.output_len - 1), np.int32)
        toks[0, :len(ctx)] = ctx
        first = req.prompt_len - 1
        dev = self._inner.device
        logits = self._arch.logits(self._inner.params, self._cfg,
                                   jax.device_put(toks, dev), first, fp8=True)
        return int(np.asarray(logits[0, len(ctx) - 1 - first]).argmax())

    def prefill(self, seq):
        state, logits, _ = self._inner.prefill(seq)
        return state, logits, self._next(seq)

    def decode_batch(self, batch) -> None:
        self._inner.decode_batch(batch)
        for s in batch:
            s.next_token = self._next(s)


def served(arch, cfg: dict):
    """The ``fault`` for ``harness.run``: the fp8 control of the
    architecture module ``arch`` in place of each executor's tokens."""
    return lambda inner: Fp8Served(inner, arch, cfg)
