"""KV transfer paths: cost-model orderings + REAL byte-movement round
trips (including disk serialization) + hypothesis monotonicity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.transfer import DiskPath, HostPath, ICIPath, make_path


PATHS = [ICIPath(), HostPath(), DiskPath()]


def test_store_latency_ordering():
    """Paper F3: deeper memory tier => slower store (TTFT order)."""
    nbytes = int(1.8e9)    # one 16k-token llama KV payload
    ici, host, disk = (p.store_cost(nbytes).latency_s for p in PATHS)
    assert ici < host < disk


def test_fetch_latency_ordering():
    nbytes = int(1.8e9)
    ici, host, disk = (p.fetch_cost(nbytes).latency_s for p in PATHS)
    assert ici <= host < disk
    assert ici == 0.0      # pushed straight into decode HBM


def test_energy_deepens_with_tier():
    """Paper Fig 4: deeper tiers burn more non-accelerator energy."""
    nbytes = int(1.8e9)
    totals = [sum(p.store_cost(nbytes).energy_j.values())
              + sum(p.fetch_cost(nbytes).energy_j.values()) for p in PATHS]
    assert totals[0] < totals[1] < totals[2]
    assert "disk" in DiskPath().store_cost(nbytes).energy_j
    assert "dram" in HostPath().store_cost(nbytes).energy_j


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 10**10), st.integers(1, 10**10))
def test_costs_monotone_in_bytes(a, b):
    lo, hi = min(a, b), max(a, b)
    for p in PATHS:
        assert p.store_cost(lo).latency_s <= p.store_cost(hi).latency_s
        assert p.fetch_cost(lo).latency_s <= p.fetch_cost(hi).latency_s


# ----------------------------------------------------------------------
def _payload():
    k = jax.random.PRNGKey(0)
    return {
        "cache": jnp.asarray(jax.random.normal(k, (2, 1, 8, 2, 4)),
                             jnp.bfloat16),
        "state": jax.random.normal(jax.random.fold_in(k, 1), (1, 3, 3)),
        "logits": jax.random.normal(jax.random.fold_in(k, 2), (1, 17)),
    }


@pytest.mark.parametrize("name", ["ici", "host", "disk"])
def test_real_roundtrip_bit_exact(name, tmp_path):
    kw = {"scratch_dir": str(tmp_path)} if name == "disk" else {}
    path = make_path(name, **kw)
    payload = _payload()
    device = jax.devices()[-1]        # the decode engine's device
    handle = path.store(payload, device)
    back = path.fetch(handle, device)
    for key in payload:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(payload[key]))
        assert back[key].dtype == payload[key].dtype
        assert back[key].devices() == {device}


def test_disk_file_removed_after_fetch(tmp_path):
    import os
    path = DiskPath(scratch_dir=str(tmp_path))
    handle = path.store(_payload())
    assert os.path.exists(handle)
    path.fetch(handle)
    assert not os.path.exists(handle)
