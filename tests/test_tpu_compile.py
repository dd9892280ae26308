"""The served path's Pallas kernels compile for a TPU v5e chip.

Interpret mode (tests/test_kernels.py) cannot see what Mosaic refuses: a
reshape that is not tile-aligned, a block that does not fit the tiling, or
too much VMEM. These tests compile each kernel at qwen3-1.7b widths
(H=16, KV=8, hd=128) for a described, not attached, v5e chip. Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and a collection-time call
would give the test workers different tests to collect.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_prefill, paged_decode

H, KV, HD = 16, 8, 128          # qwen3-1.7b attention widths


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [2048, 1000, 37])
def test_flash_prefill_compiles_for_v5e(one_chip, S):
    """1000 and 37 are not block multiples: the wrapper pads them."""
    q = _spec((1, S, H, HD), jnp.bfloat16, one_chip)
    kv = _spec((1, S, KV, HD), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_prefill.flash_attention(q, k, v)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_compiles_for_v5e(one_chip):
    B, page, max_pages, pages = 8, 16, 64, 512
    q = _spec((B, H, HD), jnp.bfloat16, one_chip)
    kp = _spec((pages, page, KV, HD), jnp.bfloat16, one_chip)
    bt = _spec((B, max_pages), jnp.int32, one_chip)
    lens = _spec((B,), jnp.int32, one_chip)
    compiled = jax.jit(paged_decode.paged_attention).lower(
        q, kp, kp, bt, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()
