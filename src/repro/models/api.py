"""Unified model API over the zoo — every engine/launcher call site uses this.

``Model(cfg)`` dispatches on ``cfg.family`` and normalizes the per-family
signatures to:

  init(rng) -> params
  loss(params, batch) -> (scalar, metrics)          batch: dict (train)
  forward(params, batch) -> logits
  prefill(params, batch, s_max) -> (logits[B,V], decode_state)
  decode_step(params, tokens[B], state, pos[B]) -> (logits[B,V], state)
  init_decode_state(batch_size, s_max) -> state pytree (zeros)
  train_inputs/prefill_inputs/decode_inputs(shape) -> ShapeDtypeStruct dicts
      (the dry-run stand-ins; weak-type-correct, no allocation)

The decode state is an opaque pytree: dense KV cache (dense/moe/vlm),
fixed-size recurrent state (ssm), mixed (hybrid), self+cross KV (encdec).
That opacity is what lets the serving core treat the paper's KV-transfer
paths uniformly across all ten architectures.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from . import encdec as ED
from . import layers as L
from . import mamba2 as MB
from . import moe as MOE
from . import rwkv6 as RW
from . import transformer as TF
from . import vlm as VL


def _hybrid_window(cfg: ModelConfig, seq_len: int) -> int:
    """The shared attention block goes sliding-window at long context."""
    if cfg.family != "hybrid":
        return cfg.sliding_window
    w = cfg.hybrid.long_context_window
    return w if seq_len > 4 * w else 0


class Model:
    """Family-dispatched, signature-normalized model handle."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.family = cfg.family

    # ------------------------------------------------------------------
    def init(self, rng) -> Any:
        return {
            "dense": TF.init, "moe": MOE.init, "ssm": RW.init,
            "hybrid": MB.init, "encdec": ED.init, "vlm": VL.init,
        }[self.family](rng, self.cfg)

    def abstract_params(self) -> Any:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    def param_count(self) -> int:
        import math
        return sum(math.prod(l.shape)
                   for l in jax.tree.leaves(self.abstract_params()))

    # ------------------------------------------------------------------
    def loss(self, params, batch: Dict[str, jnp.ndarray],
             remat: bool = True) -> Tuple[jnp.ndarray, Dict]:
        fn = {
            "dense": TF.loss_fn, "moe": MOE.loss_fn, "ssm": RW.loss_fn,
            "hybrid": MB.loss_fn, "encdec": ED.loss_fn, "vlm": VL.loss_fn,
        }[self.family]
        return fn(params, batch, self.cfg, remat=remat)

    def forward(self, params, batch: Dict[str, jnp.ndarray],
                remat: bool = False) -> jnp.ndarray:
        cfg = self.cfg
        if self.family in ("dense",):
            return TF.forward(params, batch["tokens"], cfg, remat)
        if self.family == "moe":
            return MOE.forward(params, batch["tokens"], cfg, remat)[0]
        if self.family == "ssm":
            return RW.forward(params, batch["tokens"], cfg, remat)
        if self.family == "hybrid":
            return MB.forward(params, batch["tokens"], cfg, remat)
        if self.family == "encdec":
            return ED.forward(params, batch, cfg, remat)
        if self.family == "vlm":
            return VL.forward(params, batch, cfg, remat)
        raise ValueError(self.family)

    # ------------------------------------------------------------------
    def prefill(self, params, batch: Dict[str, jnp.ndarray],
                s_max: Optional[int] = None) -> Tuple[jnp.ndarray, Any]:
        cfg = self.cfg
        if self.family == "dense":
            return TF.prefill(params, batch["tokens"], cfg, s_max)
        if self.family == "moe":
            return MOE.prefill(params, batch["tokens"], cfg, s_max)
        if self.family == "ssm":
            return RW.prefill(params, batch["tokens"], cfg, s_max)
        if self.family == "hybrid":
            S = batch["tokens"].shape[1]
            return MB.prefill(params, batch["tokens"], cfg, s_max,
                              window=_hybrid_window(cfg, s_max or S))
        if self.family == "encdec":
            return ED.prefill(params, batch, cfg, s_max)
        if self.family == "vlm":
            return VL.prefill(params, batch, cfg, s_max)
        raise ValueError(self.family)

    def decode_step(self, params, tokens: jnp.ndarray, state: Any,
                    pos: jnp.ndarray) -> Tuple[jnp.ndarray, Any]:
        cfg = self.cfg
        if self.family == "dense":
            return TF.decode_step(params, tokens, state, pos, cfg)
        if self.family == "moe":
            return MOE.decode_step(params, tokens, state, pos, cfg)
        if self.family == "ssm":
            return RW.decode_step(params, tokens, state, pos, cfg)
        if self.family == "hybrid":
            window = (cfg.hybrid.long_context_window
                      if state.attn_k.shape[2] == cfg.hybrid.long_context_window
                      else 0)
            return MB.decode_step(params, tokens, state, pos, cfg,
                                  window=window)
        if self.family == "encdec":
            return ED.decode_step(params, tokens, state, pos, cfg)
        if self.family == "vlm":
            return VL.decode_step(params, tokens, state, pos, cfg)
        raise ValueError(self.family)

    # The served path's compiled entry points, one jit cache per model so
    # every executor of the model shares its compiled programs. The
    # kernel backend (repro.kernels.ops) is read when a shape is first
    # traced.
    @functools.cached_property
    def jit_prefill(self):
        return jax.jit(self.prefill, static_argnames=("s_max",))

    @functools.cached_property
    def jit_decode_step(self):
        return jax.jit(self.decode_step)

    # ------------------------------------------------------------------
    def init_decode_state(self, batch_size: int, s_max: int,
                          dtype=jnp.bfloat16, s_src: int = 0) -> Any:
        cfg = self.cfg
        if self.family in ("dense", "moe", "vlm"):
            return TF.empty_cache(cfg, batch_size, s_max, dtype)
        if self.family == "ssm":
            return RW.init_state(cfg, batch_size, dtype)
        if self.family == "hybrid":
            return MB.init_state(cfg, batch_size, s_max, dtype,
                                 window=_hybrid_window(cfg, s_max))
        if self.family == "encdec":
            e = cfg.encdec
            Ld, kv, hd = e.num_decoder_layers, cfg.num_kv_heads, cfg.head_dim
            s_src = s_src or min(s_max, e.max_source_len)
            z = lambda s: jnp.zeros((Ld, batch_size, s, kv, hd), dtype)
            return ED.EncDecState(self_k=z(s_max), self_v=z(s_max),
                                  cross_k=z(s_src), cross_v=z(s_src))
        raise ValueError(self.family)

    # ------------------------------------------------------------------
    # Dry-run input stand-ins (ShapeDtypeStruct; no allocation)
    # ------------------------------------------------------------------
    def train_inputs(self, shape: InputShape) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
        if self.family == "encdec":
            return {"src_embeds": bf16((B, S, cfg.encdec.frontend_dim)),
                    "tokens": i32((B, S)), "targets": i32((B, S))}
        if self.family == "vlm":
            Np = cfg.vision.num_patches
            return {"patches": bf16((B, Np, cfg.vision.frontend_dim)),
                    "tokens": i32((B, S - Np)), "targets": i32((B, S - Np))}
        return {"tokens": i32((B, S)), "targets": i32((B, S))}

    def prefill_inputs(self, shape: InputShape) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
        if self.family == "encdec":
            # prompt == the source utterance; decoder starts from BOS
            return {"src_embeds": bf16((B, S, cfg.encdec.frontend_dim)),
                    "tokens": i32((B, 1))}
        if self.family == "vlm":
            Np = cfg.vision.num_patches
            return {"patches": bf16((B, Np, cfg.vision.frontend_dim)),
                    "tokens": i32((B, S - Np))}
        return {"tokens": i32((B, S))}

    def decode_inputs(self, shape: InputShape) -> Dict[str, Any]:
        """serve_step operands: one new token + the seq_len-deep state."""
        B, S = shape.global_batch, shape.seq_len
        state = jax.eval_shape(
            lambda: self.init_decode_state(B, S))
        return {
            "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
            "state": state,
            "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
        }

    # ------------------------------------------------------------------
    # Concrete sample batches (CPU smoke tests / integration tests)
    # ------------------------------------------------------------------
    def sample_batch(self, rng, batch_size: int, seq_len: int,
                     kind: str = "train") -> Dict[str, jnp.ndarray]:
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(rng, 3)
        toks = lambda k, shp: jax.random.randint(k, shp, 0, cfg.vocab_size)
        if self.family == "encdec":
            src = jax.random.normal(
                k3, (batch_size, seq_len, cfg.encdec.frontend_dim),
                jnp.float32) * 0.1
            if kind == "prefill":
                return {"src_embeds": src,
                        "tokens": toks(k1, (batch_size, 1))}
            return {"src_embeds": src,
                    "tokens": toks(k1, (batch_size, seq_len)),
                    "targets": toks(k2, (batch_size, seq_len))}
        if self.family == "vlm":
            Np = cfg.vision.num_patches
            S_txt = max(seq_len - Np, 1)
            patches = jax.random.normal(
                k3, (batch_size, Np, cfg.vision.frontend_dim),
                jnp.float32) * 0.1
            b = {"patches": patches, "tokens": toks(k1, (batch_size, S_txt))}
            if kind != "prefill":
                b["targets"] = toks(k2, (batch_size, S_txt))
            return b
        b = {"tokens": toks(k1, (batch_size, seq_len))}
        if kind != "prefill":
            b["targets"] = toks(k2, (batch_size, seq_len))
        return b


def get_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
