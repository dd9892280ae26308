"""chip_smoke.py's control flow on the CPU: the one-chip phases at a reduced
qwen3-1.7b with the Pallas kernels in interpret mode, and the four-chip
phase on 4 forced host devices (in a SUBPROCESS, so the device count never
leaks into the rest of the suite), where every KV handoff must land on the
decode engine's own device."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_config, reduce_for_smoke  # noqa: E402
from repro.kernels import ops  # noqa: E402

SMALL = dict(n_requests=3, input_len=37, output_len=5)


def test_one_chip_phases_run_on_cpu(monkeypatch):
    monkeypatch.setattr(ops, "_DEFAULT", ops._DEFAULT)   # run() sets it
    outs = chip_smoke.run(reduce_for_smoke(get_config("qwen3-1.7b")),
                          backend="pallas_interpret", **SMALL)
    assert set(outs) == set(chip_smoke.ONE_CHIP_SETUPS)
    for toks in outs.values():
        assert toks == outs["co-1gpu"]


_WORKER = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json
    import sys
    sys.path.insert(0, {str(ROOT)!r})
    import jax
    import chip_smoke
    from repro.configs import get_config, reduce_for_smoke
    from repro.core import RealExecutor
    from repro.models import get_model

    landed = []                 # (executor device, payload devices)
    fetch = RealExecutor.fetch

    def spy(self, payload):
        landed.append((self.device.id, sorted(
            {{d.id for x in jax.tree.leaves(payload) for d in x.devices()}})))
        return fetch(self, payload)

    RealExecutor.fetch = spy
    cfg = reduce_for_smoke(get_config("qwen3-1.7b"))
    per_setup = {{}}
    import repro.launch.serve as serve_mod
    serve = serve_mod.serve

    def serve_logged(*a, **k):
        landed.clear()
        res = serve(*a, **k)
        per_setup[a[1]] = list(landed)
        return res

    serve_mod.serve = serve_logged
    outs = chip_smoke.run(cfg, chips=4, backend="pallas_interpret",
                          n_requests={SMALL["n_requests"]},
                          input_len={SMALL["input_len"]},
                          output_len={SMALL["output_len"]})

    devs = jax.devices()
    model = get_model(cfg)
    ex = RealExecutor(model, model.init(jax.random.PRNGKey(0)),
                      device=devs[1])
    try:
        ex.fetch(jax.device_put(jax.numpy.zeros(3), devs[0]))
        stray_caught = False
    except RuntimeError:
        stray_caught = True
    print("RESULTS:" + json.dumps({{"outs": outs, "landed": per_setup,
                                    "stray_caught": stray_caught}}))
""")


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_KERNEL_BACKEND", None)
    proc = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS:")][0]
    return json.loads(line[len("RESULTS:"):])


def test_four_device_handoffs_land_on_decode_devices(four_devices):
    landed = four_devices["landed"]
    n = SMALL["n_requests"]
    assert landed["co-1gpu"] == []                   # no handoff at all
    # dis-ici: prefill acc0 -> device 0, decode acc1 -> device 1
    assert landed["dis-ici"] == [[1, [1]]] * n
    # 2P2D-ici: decode accelerators 2 and 3, each payload on its own one
    assert len(landed["2P2D-ici"]) == n
    assert all(dev in (2, 3) and on == [dev]
               for dev, on in landed["2P2D-ici"])
    assert four_devices["stray_caught"]


def test_four_device_tokens_match_colocated(four_devices):
    outs = four_devices["outs"]
    assert set(outs) == set(chip_smoke.FOUR_CHIP_SETUPS)
    for setup, toks in outs.items():
        assert toks == outs["co-1gpu"], setup
