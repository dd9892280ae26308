"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for each fault a colocated served cell can have, and for the fp8
control serving in the program's place, and true with none."""
import time

import pytest

from bench import control, harness

import bench_tiny


class _Fault:
    """Forwards to the executor; subclasses break one thing."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)


class AlteredToken(_Fault):
    """Each decoded token is changed where it is produced."""

    def decode_batch(self, batch):
        self._inner.decode_batch(batch)
        for s in batch:
            s.next_token = (s.next_token + 1) % 1024


class StateUnchanged(_Fault):
    """The decode step returns the cache it was given: no new key or
    value is ever written."""

    def decode_batch(self, batch):
        old = [s.state for s in batch]
        self._inner.decode_batch(batch)
        for s, st in zip(batch, old):
            s.state = st


class HalfBatch(_Fault):
    """Only the first half of the batch is decoded; the rest keep their
    last token and cache."""

    def decode_batch(self, batch):
        self._inner.decode_batch(batch[:max(1, len(batch) // 2)])


def _run(tmp_path, fault=None):
    # a window of one burst, whose requests are all in the sample
    bench = bench_tiny.make_root(tmp_path)
    args = harness.parse_args(["--workload", "tiny.mix", "--seed",
                               "4294967311", "--seconds", "0.01",
                               "--trace", "0"])
    return harness.run(args, time.perf_counter(), root=tmp_path,
                       bench=bench, fault=fault, require_chip=False)


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 4
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", [AlteredToken, StateUnchanged, HalfBatch])
def test_fault_is_not_correct(tmp_path, fault):
    out = _run(tmp_path, fault=fault)
    assert out["correct"] is False
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_served_fp8_control_is_not_correct(tmp_path):
    cfg = bench_tiny.tiny_config()
    out = _run(tmp_path, fault=control.served(cfg))
    assert out["correct"] is False
    assert out["failed"] == 0
    gap = out["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
