"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
of the window's finished requests, drawn from the seed, is run through
the float32 reference of the configuration's architecture module
(``archs/<arch>.py``) over its prompt and the tokens the timed path
served. The number compared is the widest gap by which a served token's
reference logit lies below the reference's best at that position; greedy
decoding that matches the reference reads 0.

``limits/<cell>.json`` gives, per cell, ``sample_requests`` and, for each
number compared, its ``limit`` with the readings it was set from.
"""
from __future__ import annotations

import numpy as np

from bench import reference, weights


def sample(requests: list, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest (by
    served tokens) always among them."""
    done = [r for r in requests if r.finish_s is not None]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].output_tokens))
    rng = np.random.default_rng([seed, 1])
    rest = [i for i in range(len(done)) if i != longest]
    picked = rng.choice(rest, size=min(n - 1, len(rest)), replace=False) \
        if n > 1 and rest else []
    return [done[longest]] + [done[int(i)] for i in picked]


def _row(req):
    """Reference input (prompt + served tokens but the last), the served
    tokens, and the first position whose logits are read."""
    prompt = np.asarray(req.prompt_tokens, np.int32)
    served = np.asarray(req.output_tokens, np.int32)
    toks = np.concatenate([prompt, served[:-1]])[None, :]
    return toks, served, len(prompt) - 1


def readings(arch, cfg: dict, seed: int, device, reqs: list,
             control: bool = False) -> dict:
    """Widest gap of the served tokens against the reference of the
    architecture module ``arch`` (``gap``), and with ``control`` the
    widest gap of the tokens the fp8 control puts first
    (``control_gap``), over the requests ``reqs``."""
    import jax
    params = weights.make_params(arch, cfg, seed, device)
    gaps, ctl, n = [], [], 0
    for req in reqs:
        toks, served, first = _row(req)
        toks = jax.device_put(toks, device)
        ref = np.asarray(arch.logits(params, cfg, toks, first))[0]
        gaps.append(reference.widest_gap(ref, served))
        n += len(served)
        if control:
            low = arch.logits(params, cfg, toks, first, fp8=True)
            ctl.append(reference.widest_gap(
                ref, np.asarray(low[0].argmax(-1))))
    del params
    out = {"gap": max(gaps) if gaps else None, "tokens": n,
           "requests": len(reqs), "per_request": gaps}
    if control:
        out["control_gap"] = max(ctl) if ctl else None
        out["control_per_request"] = ctl
    return out


def judge(reading: dict, limits: dict, attempted: int,
          failed: int) -> tuple:
    """(correct, compared) where ``compared`` maps each number compared
    to its value, its limit and which side of the limit passes."""
    compared = {
        "max_logit_gap": {"value": reading["gap"], "limit":
                          limits["max_logit_gap"]["limit"], "pass": "<="},
        "served_tokens": {"value": reading["tokens"],
                          "limit": limits["min_tokens"], "pass": ">="},
        "failed_requests": {"value": failed, "limit": 0, "pass": "<="},
    }
    correct = attempted > 0 and reading["gap"] is not None and all(
        c["value"] <= c["limit"] if c["pass"] == "<=" else
        c["value"] >= c["limit"] for c in compared.values())
    return correct, compared
