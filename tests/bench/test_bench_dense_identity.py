"""The dense architecture module reads as the code it came from: golden
values recorded from the harness before its dense parts moved into
``bench/archs/dense_gqa.py``, at the tiny configuration of ``bench_tiny``
(and the same with a tied head and no q/k norm) and one fixed seed: a
checksum of every weight leaf, the float32 reference's logits at four
positions with fp8 off and on, and every count at qwen3-1.7b's and Yi's
sizes. All must match exactly.

The logits are computed in a child process held to one CPU: the CPU
backend's float32 sums depend on how many threads share a matmul, and the
goldens were recorded on one.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import spec

HERE = Path(__file__).resolve().parent
GOLDEN = json.loads((HERE / "data" / "dense_gqa_golden.json").read_text())
TOKENS_SALT, SEQ = 9, 40


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()


def variants(out_path: str) -> None:
    """Child: weight checksums and reference logits of each variant, as
    JSON at ``out_path``; run on one CPU, before JAX is imported."""
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    import jax
    import bench_tiny
    from bench import weights
    seed, dev, out = GOLDEN["seed"], jax.devices()[0], {}
    for name, v in GOLDEN["variants"].items():
        cfg = {**bench_tiny.tiny_config(), **v["overrides"]}
        arch = spec.load_arch(cfg)
        p = weights.make_params(arch, cfg, seed, dev)
        w = {"/".join(k.key for k in path): _sha(np.asarray(a).view(np.uint16))
             for path, a in jax.tree.flatten_with_path(p)[0]}
        toks = np.random.default_rng([seed, TOKENS_SALT]).integers(
            0, cfg["vocab_size"], (1, SEQ)).astype(np.int32)
        first, lg = v["tokens_first"], {}
        for fp8 in (False, True):
            a = np.asarray(arch.logits(p, cfg, jax.device_put(toks, dev),
                                       first, fp8=fp8), np.float32)
            lg["fp8" if fp8 else "f32"] = {
                "sha256": _sha(a), "shape": list(a.shape),
                "argmax": a[0].argmax(-1).tolist(),
                "max": [float(x) for x in a[0].max(-1)]}
        out[name] = {"overrides": v["overrides"], "weights_sha256": w,
                     "tokens_first": first, "logits": lg}
    Path(out_path).write_text(json.dumps(out))


@pytest.fixture(scope="module")
def computed(tmp_path_factory):
    out = tmp_path_factory.mktemp("golden") / "variants.json"
    code = (f"import sys; sys.path[:0] = {[str(HERE), str(spec.REPO_DIR)]!r}; "
            f"import test_bench_dense_identity as t; t.variants({str(out)!r})")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    return json.loads(out.read_text())


@pytest.mark.parametrize("variant", sorted(GOLDEN["variants"]))
def test_weights_match_the_parent(computed, variant):
    assert (computed[variant]["weights_sha256"]
            == GOLDEN["variants"][variant]["weights_sha256"])


@pytest.mark.parametrize("variant", sorted(GOLDEN["variants"]))
def test_reference_logits_match_the_parent(computed, variant):
    assert computed[variant]["logits"] == GOLDEN["variants"][variant]["logits"]


def _counts(cfg: dict) -> dict:
    pf, dc, fl = (spec.load_counts(n, cfg) for n in
                  ("prefill_step", "decode_step", "flash_prefill"))
    c = {"prefill_step.layer_matmul_params": pf.layer_matmul_params(cfg),
         "prefill_step.attention_flops.2001000":
             pf.attention_flops(cfg, 2001000),
         "decode_step.kv_bytes_per_token": dc.kv_bytes_per_token(cfg),
         "decode_step.weight_bytes": dc.weight_bytes(cfg)}
    for S in (1, 37, 1500, 2000):
        c[f"prefill_step.flops.{S}"] = pf.flops(cfg, S)
        c[f"flash_prefill.flops.{S}"] = fl.flops(cfg, S)
        c[f"flash_prefill.bytes.{S}"] = fl.bytes(cfg, S)
    for label, ctx in (("16x1020", [1020] * 16),
                       ("8x1500+k", [1500 + k for k in range(8)]),
                       ("1x1", [1])):
        c[f"decode_step.flops.{label}"] = dc.flops(cfg, ctx)
        c[f"decode_step.bytes.{label}"] = dc.bytes(cfg, ctx)
    return c


@pytest.mark.parametrize("config", sorted(GOLDEN["counts"]))
def test_counts_match_the_parent(config):
    assert _counts(spec.load_config(config)) == GOLDEN["counts"][config]
