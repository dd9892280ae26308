"""A tiny cell made only of new files, for runs of the harness on the
CPU."""
import json
import shutil
from pathlib import Path

# small enough for the CPU, wide enough that the served tokens depend on
# the context and on the precision (an untied head, so the current
# token's own embedding does not decide the next one)
TINY = dict(name="tiny", hidden_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            intermediate_size=256, vocab_size=1024,
            tie_word_embeddings=False)


def tiny_config() -> dict:
    """The tiny configuration: qwen3-1.7b's file with the sizes above."""
    from bench import spec
    cfg = json.loads((spec.BENCH_DIR / "configs/qwen3-1.7b.json")
                     .read_text())
    cfg.update(TINY)
    return cfg


def make_root(tmp: Path, setup: str = "co-1gpu", limit: float = 0.003,
              requests: int = 4, prompt: int = 32, out: int = 8,
              config: dict = None) -> dict:
    """A benchmark directory with one tiny cell ``<config>.mix`` (by
    default ``tiny.mix``), made only of new files; returns the
    BENCHMARK.json-like dict for it."""
    from bench import spec
    for d in ("configs", "traffic", "limits", "layer_metrics", "archs"):
        (tmp / d).mkdir(parents=True, exist_ok=True)
    for d in ("layer_metrics", "archs"):
        for f in (spec.BENCH_DIR / d).glob("*.py"):
            shutil.copy(f, tmp / d)
    config = config or tiny_config()
    cell = f"{config['name']}.mix"
    (tmp / f"configs/{config['name']}.json").write_text(json.dumps(config))
    (tmp / "traffic/mix.json").write_text(json.dumps(
        {"setup": setup, "burst_requests": requests, "prompt_len": prompt,
         "output_len": out}))
    (tmp / f"limits/{cell}.json").write_text(json.dumps(
        {"sample_requests": requests, "min_tokens": requests * out,
         "max_logit_gap": {"limit": limit}}))
    committed = spec.load_benchmark()
    return {"workloads": [{"name": cell, "config": config["name"],
                           "traffic": "mix", "chips": 1}],
            "end_to_end": committed["end_to_end"], "per_layer": []}
