"""What every architecture's plain float32 reference shares, written from
the published descriptions in ``jax.numpy``: linear layers at
``Precision.HIGHEST``, RMSNorm, rotary embeddings on split halves, and the
widest logit gap that ``bench.check`` compares. Each architecture module
(``archs/<arch>.py``) writes its forward with these; it reads the
bfloat16 weights that ``bench.weights`` makes from the seed and casts one
layer at a time to float32 inside a scan, so the float32 copy of the whole
model never exists.

Nothing here imports the program.

``fp8=True`` is the control: every linear layer's weights (per output
channel) and inputs (per row) rounded to float8 e4m3, the precision below
the configuration's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0     # largest finite float8_e4m3fn


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, fp8):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...d,de->...e", x, w, precision=HI)


def _rms_norm(x, scale, eps):
    return scale * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                      + eps))


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def widest_gap(ref_logits, chosen) -> float:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best, over rows: 0 where every choice is the
    reference's argmax."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = np.asarray(chosen)
    picked = np.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return float(np.max(ref.max(-1) - picked))
