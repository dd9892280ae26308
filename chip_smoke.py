"""Smoke run of the served path on a TPU chip.

Drives qwen3-1.7b at its published widths (random weights from a seed)
through ``repro.launch.serve.serve(..., real=True)``: the engines' jitted
prefill, which runs the Pallas flash-attention kernel, the decode steps,
and the KV handoff between engines.

  python chip_smoke.py              one chip: co-1gpu, dis-ici, dis-host and
                                    dis-disk, plus a kernel and logits check
  python chip_smoke.py --chips 4    four chips: dis-ici across devices 0->1
                                    and 2P2D-ici across 0-3, against co-1gpu

It fails, printing no result, where JAX finds no TPU or where
REPRO_KERNEL_BACKEND asks for a fallback. Its last line is one JSON object
naming the device. The times it prints are informational, not a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ONE_CHIP_SETUPS = ("co-1gpu", "dis-ici", "dis-host", "dis-disk")
FOUR_CHIP_SETUPS = ("co-1gpu", "dis-ici", "2P2D-ici")
# bf16 tolerances, relative to the largest magnitude of the reference:
# one flash-attention call (the kernel tests' bf16 bound), and the
# last-position logits after every layer of the model
ATTN_TOL = 2e-2
LOGITS_TOL = 5e-2


def _close(got, want, tol):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    return err, scale, err <= tol * scale


def _check(ok, msg):
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(msg)


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def check_prefill(cfg, *, backend, n_requests, input_len, output_len, seed,
                  log=print):
    """Compile one prefill and one decode step of the served model; check
    the prefill program and its logits against the ``ref`` backend.
    Returns request 0's greedy first token."""
    import jax
    import jax.numpy as jnp
    from repro.core import random_workload
    from repro.kernels import ops, ref
    from repro.models import get_model

    # the flash kernel alone, at this model's attention shape
    key = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(key, 3)
    dt = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    q = jax.random.normal(kq, (1, input_len, cfg.num_heads, cfg.head_dim), dt)
    k = jax.random.normal(kk, (1, input_len, cfg.num_kv_heads, cfg.head_dim),
                          dt)
    v = jax.random.normal(kv, k.shape, dt)
    out = ops.flash_attention(q, k, v)
    err, scale, ok = _close(out, ref.flash_attention_ref(q, k, v), ATTN_TOL)
    log(f"[smoke] flash attention {backend} vs ref at S={input_len}: "
        f"max|diff| {err!r} over max|ref| {scale!r} (tol {ATTN_TOL})")
    _check(ok, "flash attention kernel disagrees with the reference")

    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    prompt = random_workload(1, input_len=input_len, output_len=output_len,
                             vocab_size=cfg.vocab_size,
                             seed=seed)[0].prompt_tokens
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)[None, :]}
    s_max = input_len + output_len + 2          # as RealExecutor.prefill

    t0 = time.perf_counter()
    prefill = model.jit_prefill.lower(params, batch, s_max=s_max).compile()
    prefill_compile_s = time.perf_counter() - t0
    if backend == "pallas":
        _check("tpu_custom_call" in prefill.as_text(),
               "the compiled prefill holds no Pallas kernel")
    state = model.init_decode_state(n_requests, s_max)
    toks = jnp.zeros((n_requests,), jnp.int32)
    pos = jnp.full((n_requests,), input_len, jnp.int32)
    t0 = time.perf_counter()
    decode = model.jit_decode_step.lower(params, toks, state, pos).compile()
    decode_compile_s = time.perf_counter() - t0
    log(f"[smoke] compile seconds: prefill {prefill_compile_s!r}  "
        f"decode(B={n_requests}) {decode_compile_s!r}")

    (logits, _), _ = _timed(prefill, params, batch)
    prefill_s = [_timed(prefill, params, batch)[1] for _ in range(3)]
    decode_s = [_timed(decode, params, toks, state, pos)[1]
                for _ in range(3)]
    log(f"[smoke] wall seconds (block_until_ready): prefill(1x{input_len}) "
        f"{prefill_s!r}  decode step(B={n_requests}) {decode_s!r}")

    ops.set_default_backend("ref")
    ref_logits = jax.jit(
        lambda p, b: model.prefill(p, b, s_max=s_max))(params, batch)[0]
    ops.set_default_backend(backend)
    err, scale, ok = _close(logits, ref_logits, LOGITS_TOL)
    log(f"[smoke] prefill logits {backend} vs ref: max|diff| {err!r} over "
        f"max|ref| {scale!r} (tol {LOGITS_TOL})")
    _check(ok, "prefill logits disagree with the reference backend")
    return int(jnp.argmax(logits[0]))


def serve_setups(cfg, setups, *, n_requests, input_len, output_len, seed,
                 log=print):
    """Serve one closed batch per setup through ``serve(real=True)``;
    check every request completes and first tokens agree with co-1gpu.
    Returns {setup: [tokens of request i]}."""
    from repro.launch.serve import serve

    outs = {}
    for setup in setups:
        res = serve(cfg, setup, batch_size=n_requests, input_len=input_len,
                    output_len=output_len, real=True, seed=seed)
        toks = [r.output_tokens for r in
                sorted(res.requests, key=lambda r: r.req_id)]
        _check(len(toks) == n_requests
               and all(len(t) == output_len for t in toks),
               f"{setup}: not every request produced {output_len} tokens")
        outs[setup] = toks
        log(f"[smoke] {setup}: {n_requests} requests x {output_len} tokens; "
            f"request 0: {toks[0]}")
        # a disaggregated cluster and its engines reference each other,
        # so only the cycle collector frees their params before the next
        # setup allocates its own
        del res
        gc.collect()
    base = outs["co-1gpu"]
    for setup, toks in outs.items():
        _check([t[0] for t in toks] == [t[0] for t in base],
               f"{setup}: first tokens differ from co-1gpu")
        same = sum(a == b for ta, tb in zip(toks, base)
                   for a, b in zip(ta, tb))
        log(f"[smoke] {setup}: first tokens match co-1gpu; "
            f"{same}/{n_requests * output_len} tokens equal to co-1gpu")
    return outs


def run(cfg, *, chips=1, backend="pallas", n_requests=8, input_len=1000,
        output_len=16, seed=0, log=print):
    """The smoke's phases on ``cfg`` with the given kernel backend.
    ``chips=4`` runs only the multi-device setups and their comparison."""
    from repro.kernels import ops
    ops.set_default_backend(backend)
    kw = dict(n_requests=n_requests, input_len=input_len,
              output_len=output_len, seed=seed, log=log)
    if chips == 4:
        return serve_setups(cfg, FOUR_CHIP_SETUPS, **kw)
    first = check_prefill(cfg, backend=backend, **kw)
    outs = serve_setups(cfg, ONE_CHIP_SETUPS, **kw)
    _check(outs["co-1gpu"][0][0] == first,
           "served first token differs from the checked prefill's")
    return outs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    from repro.configs import get_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                 "nothing was run")
    asked = os.environ.get("REPRO_KERNEL_BACKEND", "pallas")
    if asked not in ("pallas", "auto"):
        sys.exit(f"chip_smoke: REPRO_KERNEL_BACKEND={asked} would bypass "
                 "the Pallas kernels")
    count = len(jax.devices())
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {count}")
    print(f"[smoke] {count} x {dev.device_kind}; compile cache {cache_dir}")

    run(get_config("qwen3-1.7b"), chips=args.chips, backend="pallas")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"[smoke] peak_bytes_in_use on device {d.id}: "
              f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
