"""Distributed collectives under shard_map on an 8-device host-platform
mesh. Runs in a SUBPROCESS so the forced device count never leaks into the
rest of the suite (smoke tests must see 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from jax import shard_map

    from repro.dist.collectives import (bucketed_psum, compressed_psum,
                                        halo_exchange, ring_allgather,
                                        ring_pass)

    mesh = jax.make_mesh((8,), ("dp",), axis_types=(AxisType.Auto,))
    results = {}

    # --- compressed all-reduce: mean within int8 tolerance + EF ----------
    key = jax.random.PRNGKey(0)
    grads = {"a": jax.random.normal(key, (8, 64)),
             "b": jax.random.normal(jax.random.fold_in(key, 1), (8, 17))}

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("dp"),), out_specs=P("dp"))
    def cmean(g):
        g = jax.tree.map(lambda x: x[0], g)          # local shard
        mean, err = compressed_psum(g, "dp")
        return jax.tree.map(lambda x: x[None], mean)

    got = cmean(grads)
    want = jax.tree.map(lambda x: jnp.mean(x, 0, keepdims=True)
                        .repeat(8, 0), grads)
    errs = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                           (jnp.max(jnp.abs(b)) + 1e-9)), got, want)
    results["compressed_rel_err"] = max(jax.tree.leaves(errs))

    # --- error feedback makes repeated compression unbiased -------------
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("dp"),), out_specs=P("dp"))
    def accumulate(g):
        gl = jax.tree.map(lambda x: x[0], g)
        err = None
        tot = jax.tree.map(jnp.zeros_like, gl)
        for _ in range(50):
            mean, err = compressed_psum(gl, "dp", err)
            tot = jax.tree.map(lambda t, m: t + m, tot, mean)
        return jax.tree.map(lambda x: x[None], tot)

    tot = accumulate(grads)
    want_tot = jax.tree.map(
        lambda x: 50 * jnp.mean(x, 0, keepdims=True).repeat(8, 0), grads)
    ef_err = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) /
                           (jnp.max(jnp.abs(b)) + 1e-9)), tot, want_tot)))
    results["ef_rel_err"] = ef_err

    # --- bucketed psum == plain psum -------------------------------------
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("dp"),), out_specs=P("dp"))
    def bsum(g):
        gl = jax.tree.map(lambda x: x[0], g)
        out = bucketed_psum(gl, "dp", bucket_bytes=256)
        return jax.tree.map(lambda x: x[None], out)

    got_b = bsum(grads)
    want_b = jax.tree.map(lambda x: jnp.sum(x, 0, keepdims=True)
                          .repeat(8, 0), grads)
    results["bucket_err"] = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), got_b, want_b)))

    # --- halo exchange ----------------------------------------------------
    x = jnp.arange(8 * 4 * 2, dtype=jnp.float32).reshape(8, 4, 2)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("dp"),), out_specs=P("dp"))
    def halo(xs):
        out = halo_exchange(xs, "dp", halo=1, seq_axis=1)
        return out

    h = halo(x)                       # [8, 5, 2] global (per-shard 1x5x2)
    ok = bool(jnp.all(h[1:, 0] == x[:-1, -1])) and bool(
        jnp.all(h[0, 0] == 0.0)) and bool(jnp.all(h[:, 1:] == x))
    results["halo_ok"] = ok

    # --- ring allgather == all values, correctly ordered -----------------
    v = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P("dp"),), out_specs=P("dp"))
    def gather(vs):
        flat = ring_allgather(vs, "dp")          # [8] on every shard
        return flat.reshape(1, 8)

    g = gather(v)
    results["ring_ok"] = bool(jnp.all(
        g == jnp.arange(8, dtype=jnp.float32)[None, :]))

    print("RESULTS:" + json.dumps(results))
""").replace("json.dumps", "__import__('json').dumps")


@pytest.fixture(scope="module")
def worker_results():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


def test_compressed_psum_close(worker_results):
    assert worker_results["compressed_rel_err"] < 0.02   # int8 tolerance


def test_error_feedback_unbiased(worker_results):
    """50 accumulated compressed steps stay within ~1% of the true sum —
    error feedback prevents drift."""
    assert worker_results["ef_rel_err"] < 0.01


def test_bucketed_psum_exact(worker_results):
    assert worker_results["bucket_err"] < 1e-5


def test_halo_exchange(worker_results):
    assert worker_results["halo_ok"]


def test_ring_allgather(worker_results):
    assert worker_results["ring_ok"]
