"""Pallas TPU kernel: causal flash attention for the prefill stage.

Prefill is the compute-bound stage (paper section II-A) and sets TTFT. The
kernel is an online-softmax flash attention with:

  * BlockSpec VMEM tiling: q tile [G, bq, hd] stays resident; K/V stream
    through VMEM in [bk, hd] tiles (HBM -> VMEM pipelined by pallas grid).
  * GQA folded into the q tile: the grid iterates kv-heads and each q tile
    carries its G = H/KV query heads, so K/V tiles are fetched once per
    kv-head (not once per query head) — GQA's bandwidth saving realized.
    The tile is collapsed to [G*bq, hd] for one matmul; with bq a multiple
    of 16 that collapse is tile-aligned for bf16, which Mosaic requires.
  * Tiles of at most 256 (q) x 256 (kv). Short prompts are padded up to a
    block (q to a multiple of 16, kv to a multiple of 128 lanes) instead of
    shrinking the block to the prompt length, which would not fit the
    tiling; the kpos < seq_len mask keeps the padding exact.
  * Causal block skipping: kv-blocks strictly above the diagonal contribute
    nothing and are skipped with pl.when (the dominant saving at 32k seq).
  * Optional sliding window (zamba2's shared block at long context).

Accumulators (m, l, acc) live in VMEM scratch and persist across the
innermost (kv) grid dimension — TPU grids execute sequentially, which is
what makes this single-pass online softmax legal.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, bq: int, bk: int,
                  seq_len: int, q_offset: int):
    qi = pl.program_id(2)          # query block index
    kj = pl.program_id(3)          # kv block index
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level causal/window skip: query rows span
    # [q_offset + qi*bq, q_offset + (qi+1)*bq); kv cols span [kj*bk, (kj+1)*bk).
    q_lo = q_offset + qi * bq
    q_hi = q_lo + bq - 1
    k_lo = kj * bk
    k_hi = k_lo + bk - 1
    needed = True
    if causal:
        needed = k_lo <= q_hi
    if window > 0:
        needed = jnp.logical_and(needed, k_hi > q_lo - window)

    @pl.when(needed)
    def _body():
        g, hd = q_ref.shape[0], q_ref.shape[-1]
        q = q_ref[...].reshape(g * bq, hd)
        k = k_ref[...]
        v = v_ref[...]

        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G*bq, bk]

        # row r of the collapsed tile is head r // bq at query r % bq
        rows = jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 0)
        qpos = q_lo + jax.lax.rem(rows, bq)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_next
        l_ref[...] = l_next

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / safe
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [B, S, H, hd]; k, v: [B, T, KV, hd] -> [B, S, H, hd]."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, -(-S // 16) * 16)
    bk = min(block_k, -(-T // 128) * 128)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(T, bk)
    scale = 1.0 / np.sqrt(hd)

    qg = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)  # [B,KV,G,S,hd]
    kg = k.transpose(0, 2, 1, 3)                              # [B,KV,T,hd]
    vg = v.transpose(0, 2, 1, 3)
    # zero-pad to block multiples: OOB block reads would otherwise feed
    # undefined values into p @ v (0 * garbage != 0 when garbage is NaN);
    # the in-kernel kpos < seq_len mask keeps the math exact
    if nq * bq > S:
        qg = jnp.pad(qg, [(0, 0), (0, 0), (0, 0), (0, nq * bq - S), (0, 0)])
    if nk * bk > T:
        pad = [(0, 0), (0, 0), (0, nk * bk - T), (0, 0)]
        kg = jnp.pad(kg, pad)
        vg = jnp.pad(vg, pad)

    grid = (B, KV, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, bq=bq,
        bk=bk, seq_len=T, q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, G, bq, hd),
                         lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((None, None, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, bq, hd),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((G * bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((G * bq, hd), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(qg, kg, vg)

    out = out[:, :, :, :S].transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
    return out
