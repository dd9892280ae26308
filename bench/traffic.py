"""The one generator of closed-burst traffic: a mix file gives the
parameters, the seed and the burst's index give the token ids.

A mix (``traffic/<mix>.json``) holds:

  setup            the fleet shape served, as ``make_cluster`` takes it
                   (``co-1gpu``, ``2P2D-ici``, ...)
  burst_requests   requests submitted together at the start of a burst
  prompt_len       prompt tokens of every request
  output_len       output tokens of every request (greedy)

Every burst has the same sizes, so a seed changes token ids, not work.
Burst 0 is the warm-up; the window's bursts are 1, 2, ...
"""
from __future__ import annotations

import numpy as np

MIX_KEYS = ("setup", "burst_requests", "prompt_len", "output_len")


def check_mix(mix: dict) -> dict:
    missing = [k for k in MIX_KEYS if k not in mix]
    if missing:
        raise KeyError(f"traffic mix lacks {missing}")
    for k in MIX_KEYS[1:]:
        if not (isinstance(mix[k], int) and mix[k] > 0):
            raise ValueError(f"traffic mix: {k} must be a positive int")
    return mix


def burst_tokens(mix: dict, vocab_size: int, seed: int,
                 index: int) -> np.ndarray:
    """[burst_requests, prompt_len] int32 prompt ids of one burst."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab_size,
                        (mix["burst_requests"], mix["prompt_len"]),
                        dtype=np.int64).astype(np.int32)


def burst(mix: dict, vocab_size: int, seed: int, index: int) -> list:
    """The program's ``Request`` objects of one burst, all due at once."""
    from repro.core.request import Request
    toks = burst_tokens(mix, vocab_size, seed, index)
    return [Request(req_id=i, prompt_len=mix["prompt_len"],
                    output_len=mix["output_len"], arrival_s=0.0,
                    prompt_tokens=toks[i])
            for i in range(mix["burst_requests"])]
