"""``RealExecutor`` keeps the decode batch's cache resident between
steps and re-forms it in one jitted regroup only when the batch's
membership changes. The tokens must equal those of the old per-step
join and split around the same ``jit_decode_step``, whatever the
arrivals, finishes, preemptions or step composer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import RealExecutor, make_cluster
from repro.core.engine import EngineSeq, _regroup, batch_axis
from repro.core.request import Request
from repro.fleet import FleetSpec
from repro.models import get_model


class JoinSplitOracle(RealExecutor):
    """The executor before the resident batch: each step joins every
    request's cache along the batch axis and slices it back out."""

    def decode_batch(self, batch):
        tokens = jnp.asarray([s.next_token for s in batch], jnp.int32)
        pos = jnp.asarray([s.ctx for s in batch], jnp.int32)
        joined = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=batch_axis(xs[0])),
            *[s.state for s in batch])
        logits, new = self.model.jit_decode_step(self.params, tokens,
                                                 joined, pos)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for i, seq in enumerate(batch):
            seq.state = jax.tree.map(
                lambda x: x[:, i:i + 1] if x.ndim > 1 else x[i:i + 1], new)
            seq.next_token = int(nxt[i])


_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = reduce_for_smoke(get_config(arch))
        model = get_model(cfg)
        _MODELS[arch] = cfg, model, model.init(jax.random.PRNGKey(0))
    return _MODELS[arch]


def _requests(cfg, shapes, seed=5):
    """One request per (prompt_len, output_len, arrival_s). Every
    prompt_len + output_len is equal, so the caches share one length
    and can be batched."""
    rng = np.random.default_rng(seed)
    return [Request(req_id=i, prompt_len=p, output_len=o, arrival_s=t,
                    prompt_tokens=rng.integers(0, cfg.vocab_size, p))
            for i, (p, o, t) in enumerate(shapes)]


def _serve(arch, setup, shapes, executor_cls, **kw):
    cfg, model, params = _model(arch)
    made = []

    def factory(acc):
        made.append(executor_cls(model, params))
        return made[-1]

    if "pool_tokens" in kw:
        kw = dict(kw, pool_bytes=max(cfg.kv_bytes_per_token(), 1)
                  * kw.pop("pool_tokens"))
    res = make_cluster(setup, cfg, executor_factory=factory, **kw).run(
        _requests(cfg, shapes))
    toks = [r.output_tokens for r in sorted(res.requests,
                                            key=lambda r: r.req_id)]
    return toks, res, made


_STEADY = [(16, 6, 0.0)] * 3
# later arrivals join a decoding batch; shorter outputs leave it early
_STAGGERED = [(10, 10, 0.0), (14, 6, 0.0), (12, 8, 4e-7), (16, 4, 9e-7),
              (13, 7, 1.5e-6)]
_EVICTION = [(44, 10, 0.0), (46, 8, 0.0), (48, 6, 0.0), (50, 4, 0.0)]
_CHUNKED = FleetSpec(n_colocated=1, scheduler={
    "composer": "chunked-interleave", "chunk_tokens": 24})

CASES = {
    "steady-dense": ("qwen3-1.7b", "co-1gpu", _STEADY, {}),
    "staggered-dense": ("qwen3-1.7b", "co-1gpu", _STAGGERED, {}),
    # a pool of under two sequences: a decoding sequence is preempted,
    # leaves the batch, and is prefilled again
    "eviction-dense": ("llama32-3b", "co-1gpu", _EVICTION, dict(
        pool_tokens=96, page_size=8, prefill_token_budget=32)),
    "chunked-interleave": ("qwen3-1.7b", _CHUNKED, _STAGGERED, {}),
    "handoff-dis-host": ("qwen3-1.7b", "dis-host", _STAGGERED, {}),
    "staggered-ssm": ("rwkv6-3b", "co-1gpu", _STAGGERED, {}),
    "staggered-hybrid": ("zamba2-2.7b", "co-1gpu", _STAGGERED, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tokens_equal_join_split_oracle(case):
    arch, setup, shapes, kw = CASES[case]
    want, _, _ = _serve(arch, setup, shapes, JoinSplitOracle, **kw)
    got, res, made = _serve(arch, setup, shapes, RealExecutor, **kw)
    assert [len(t) for t in got] == [o for _, o, _ in shapes]
    assert got == want
    regroups = sum(ex.decode_regroups for ex in made)
    calls = sum(ex.decode_calls for ex in made)
    assert 1 <= regroups < calls
    if case.startswith("eviction"):
        assert res.metrics.total_evictions > 0
    if case != "steady-dense":
        assert regroups > 1     # the batch's membership did change
    assert all(ex._resident is None for ex in made)


def test_closed_burst_regroups_once():
    n, out = 4, 7
    _, _, [ex] = _serve("qwen3-1.7b", "co-1gpu", [(16, out, 0.0)] * n,
                        RealExecutor)
    assert ex.decode_regroups == 1
    assert ex.decode_calls == out - 1
    assert ex._resident is None     # released when the batch emptied


def _prefilled(ex, cfg, shapes):
    seqs = []
    for req in _requests(cfg, shapes):
        seq = EngineSeq(req=req, prefill_target=req.prompt_len)
        seq.state, seq.last_logits, seq.next_token = ex.prefill(seq)
        seq.ctx = req.prompt_len
        seqs.append(seq)
    return seqs


def _step(ex, batch):
    ex.decode_batch(batch)
    for seq in batch:
        seq.ctx += 1


def test_steady_step_passes_the_returned_cache_back(monkeypatch):
    cfg, model, params = _model("qwen3-1.7b")
    ex = RealExecutor(model, params)
    seen = []
    step = model.jit_decode_step

    def recording(params, tokens, state, pos):
        seen.append(state)
        out = step(params, tokens, state, pos)
        seen.append(out[1])
        return out

    monkeypatch.setattr(model, "jit_decode_step", recording)
    batch = _prefilled(ex, cfg, [(16, 6, 0.0)] * 3)
    for _ in range(3):
        _step(ex, batch)
    # steps 2 and 3 get, leaf by leaf, the very arrays the step before
    # returned: no join, copy or per-request op came between
    for returned, given in ((seen[1], seen[2]), (seen[3], seen[4])):
        assert all(a is b for a, b in zip(jax.tree.leaves(returned),
                                          jax.tree.leaves(given)))
    assert (ex.decode_calls, ex.decode_regroups) == (3, 1)
    assert all(s.state is None for s in batch)


def test_staggered_arrival_regroups_once_more():
    cfg, model, params = _model("qwen3-1.7b")
    ex = RealExecutor(model, params)
    a, b, c, late = _prefilled(ex, cfg, [(16, 6, 0.0)] * 4)
    for _ in range(2):
        _step(ex, [a, b, c])
    for _ in range(3):
        _step(ex, [a, b, c, late])
    assert (ex.decode_calls, ex.decode_regroups) == (5, 2)


def test_resident_row_prefilled_again_is_a_newcomer():
    """A preempted sequence prefilled again before the next step keeps
    its id among the resident rows but brings a fresh state; that state,
    not the stale row, is what it decodes from. A recurrent state shows
    the difference: the stale row has taken in the tokens since."""
    cfg, model, params = _model("rwkv6-3b")
    runs = {}
    for cls in (RealExecutor, JoinSplitOracle):
        ex = cls(model, params)
        a, b, c = _prefilled(ex, cfg, [(16, 6, 0.0)] * 3)
        toks = []
        for batch in ([a, b, c], [a, b, c], "refill", [a, c, b], [a, c, b]):
            if batch == "refill":
                b.ctx = b.prefill_target      # recompute the prompt only
                b.state, _, b.next_token = ex.prefill(b)
                continue
            _step(ex, batch)
            toks.append([s.next_token for s in (a, b, c)])
        runs[cls] = toks, ex
    assert runs[RealExecutor][0] == runs[JoinSplitOracle][0]
    assert runs[RealExecutor][1].decode_regroups == 2


def test_empty_batch_drops_resident_and_strays_raise():
    cfg, model, params = _model("qwen3-1.7b")
    ex = RealExecutor(model, params)
    batch = _prefilled(ex, cfg, [(16, 6, 0.0)] * 2)
    _step(ex, batch)
    assert ex._resident is not None
    ex.decode_batch([])
    assert ex._resident is None and ex.decode_calls == 1
    # its rows are gone, and the sequences carry no state of their own
    with pytest.raises(RuntimeError, match="neither a decode state"):
        ex.decode_batch(batch)
    [stray] = _prefilled(ex, cfg, [(16, 6, 0.0)])
    stray.state = None
    with pytest.raises(RuntimeError, match="neither a decode state"):
        ex.decode_batch([stray])


@pytest.mark.parametrize("keep,n_new,order", [
    (None, 2, None), ([2, 0], 0, None), ([2, 0], 1, None),
    ([1], 2, [1, 0, 2])])
def test_regroup_gathers_rows_on_each_leafs_batch_axis(keep, n_new, order):
    """The regroup of a pytree with rank-1 leaves (batch axis 0) beside
    rank-3 ones (batch axis 1), against the same rows picked by hand."""
    rng = np.random.default_rng(0)

    def state(b):
        return {"kv": rng.normal(size=(2, b, 3)).astype(np.float32),
                "pos": rng.normal(size=(b,)).astype(np.float32)}

    resident = state(3)
    newcomers = [state(1) for _ in range(n_new)]
    got = jax.jit(_regroup)(
        resident if keep is not None else None,
        None if keep is None else np.asarray(keep, np.int32), newcomers,
        None if order is None else np.asarray(order, np.int32))
    for name, ax in (("kv", 1), ("pos", 0)):
        rows = ([np.take(resident[name], keep, axis=ax)]
                if keep is not None else []) + [x[name] for x in newcomers]
        want = np.concatenate(rows, axis=ax)
        if order is not None:
            want = np.take(want, order, axis=ax)
        np.testing.assert_array_equal(np.asarray(got[name]), want)
