"""One run of one cell: set-up, warm-up, the measured window, and the
comparison that decides ``correct``. ``bench/run.py`` is the entry.

Set-up is everything from process start to the window's first burst:
importing, making the weights on the device from the seed, building one
``RealExecutor`` (wrapped in ``bench.proxy.ExecutorProxy``) per device,
and one whole warm-up burst of the cell's own traffic, which meets every
prefill length and decode batch size the window will. Then the cycle
collector runs and the surviving objects are frozen out of its view.

The window is whole bursts (``bench.window``), each one
``make_cluster(setup, cfg, executor_factory=...).run(requests)`` call,
with the collector run at every burst boundary and nowhere else.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import shutil
import sys

from bench import check, proxy, spec, trace, traffic, weights, window

TRACE_DIR = spec.REPO_DIR / ".bench_trace"


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; exits nonzero, printing no result,
    where JAX finds no TPU or fewer than ``n``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX found {devs[0].platform}); "
                 "nothing was run")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def open_devices(cell: dict, require_chip: bool = True):
    """(devices, compile-cache directory) for ``cell``. With a chip, the
    persistent compilation cache is on and keeps every program, the
    small ones too; without one (tests) neither is asked for."""
    import jax
    if not require_chip:
        return jax.devices()[:cell["chips"]], None
    from repro.launch.cache import use_compile_cache
    devices = require_chips(cell["chips"])
    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return devices, cache_dir


def program_config(cfg: dict):
    """The program's ``ModelConfig`` with every size from the file."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config(cfg["registry"]),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=cfg["qk_norm"],
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


class CompileCounter:
    """New executables (XLA compiles and persistent-cache loads) and the
    seconds spent compiling, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    @property
    def executables(self) -> int:
        return self.compiles + self.cache_hits


class Server:
    """The model, its executors on ``devices`` and the cell's traffic."""

    def __init__(self, cfg: dict, mix: dict, devices: list, fault=None):
        from repro.fleet.spec import as_fleet_spec
        from repro.models import get_model
        self.cfg, self.mix, self.devices = cfg, mix, devices
        self.model_cfg = program_config(cfg)
        self.model = get_model(self.model_cfg)
        if not as_fleet_spec(mix["setup"]).is_colocated:
            raise ValueError(f"setup {mix['setup']!r}: only colocated "
                             "fleets are measured; a disaggregated one "
                             "needs first-token and handoff hooks")
        self.fault = fault          # wraps each executor: a planted
                                    # fault, or the served fp8 control
        self.executors = {}
        self.params = {}
        self.stamps = proxy.Stamps()

    def load(self, seed: int) -> None:
        """Weights from the seed on the first device, one copy per
        device; executors are made as the clusters ask for them."""
        import jax
        p = weights.make_params(self.cfg, seed, self.devices[0])
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           p)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (w.shape, w.dtype) != (g.shape, g.dtype) for w, g in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError("bench weights do not match the program's "
                               "parameter tree")
        self.params = {d: (p if d == self.devices[0]
                           else jax.device_put(p, d)) for d in self.devices}
        jax.block_until_ready(list(self.params.values()))

    def unload(self) -> None:
        self.executors.clear()
        self.params.clear()
        gc.unfreeze()
        gc.collect()

    def _executor(self, acc: int):
        from repro.core import RealExecutor
        if acc not in self.executors:
            dev = self.devices[acc % len(self.devices)]
            inner = RealExecutor(self.model, self.params[dev], device=dev)
            if self.fault is not None:
                inner = self.fault(inner)
            self.executors[acc] = proxy.ExecutorProxy(
                inner, lambda: self.stamps)
        return self.executors[acc]

    def burst(self, seed: int, index: int):
        """One closed burst; returns (Burst, requests, Stamps)."""
        import jax
        from repro.core import make_cluster
        reqs = traffic.burst(self.mix, self.cfg["vocab_size"], seed, index)
        self.stamps = st = proxy.Stamps()
        with jax.profiler.TraceAnnotation("bench.burst"):
            t0 = proxy.now()
            cluster = make_cluster(self.mix["setup"], self.model_cfg,
                                   executor_factory=self._executor)
            cluster.run(reqs)
            t1 = proxy.now()
        b = window.Burst(submit=t0, end=t1, tokens=st.tokens,
                         requests=len(reqs),
                         output_len=self.mix["output_len"])
        return b, reqs, st


def peak_bytes(devices) -> int:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]
    return int(max(vals))


def measure(server: Server, seed: int, seconds: float, counter):
    """The window: whole bursts until one ends at or after ``seconds``.
    Returns (bursts, requests, stamps, compiles inside)."""
    import jax
    bursts, reqs, stamps = [], [], []
    before = counter.executables
    gc.disable()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            index = 1
            while True:
                if bursts:
                    with jax.profiler.TraceAnnotation("bench.gc"):
                        gc.collect()
                b, r, st = server.burst(seed, index)
                bursts.append(b)
                reqs.extend(r)
                stamps.append(st)
                calls = ", ".join(f"{k} {v!r}" for k, v in st.call_s.items())
                log(f"burst {index}: {b.end - b.submit!r} s ({calls})")
                if window.closes(bursts[0].submit, b.end, seconds):
                    break
                index += 1
    finally:
        gc.enable()
    return bursts, reqs, stamps, counter.executables - before


class LayerContext:
    """What a per-layer metric reader sees. ``prefills`` and
    ``decode_steps`` are the whole window's calls, for readers that read
    no device event. A reader that divides work by device time takes
    ``region``, ``covered_prefills`` and ``covered_decode_steps``: the
    covered bursts' stretches of the window and their calls
    (``trace.covered``), since a trace may lose device events."""

    def __init__(self, tr, cfg, peaks, chips, stamps):
        self.trace, self.cfg, self.peaks, self.chips = tr, cfg, peaks, chips
        self.stamps = stamps            # one Stamps per burst, in order
        self.prefills = [S for st in stamps for S in st.prefills]
        self.decode_steps = [c for st in stamps for c in st.decode_steps]
        self.notes = []

    @functools.cached_property
    def bursts(self) -> list:
        return trace.bursts(self.trace)

    @functools.cached_property
    def covered(self) -> list:
        return trace.covered(self.trace)

    def _need_cover(self) -> None:
        if not self.covered:
            raise RuntimeError(
                f"the trace covers none of the window's {len(self.bursts)} "
                f"bursts: in each, some executor call's program event is "
                f"missing from the {len(self.trace.devices)} device(s) "
                "traced, so no device time matches the calls it would be "
                "divided by")

    @property
    def region(self) -> list:
        self._need_cover()
        return trace.segments(self.trace, self.covered)

    @functools.cached_property
    def _covered_stamps(self) -> list:
        """The covered bursts' Stamps, each with as many calls as the
        burst's call spans."""
        self._need_cover()
        if len(self.stamps) != len(self.bursts):
            raise RuntimeError(f"{len(self.stamps)} bursts ran, the trace "
                               f"holds {len(self.bursts)} bench.burst spans")
        out = []
        for i in self.covered:
            st, held = self.stamps[i], trace.calls(self.trace,
                                                   self.bursts[i])
            if (len(st.prefills), len(st.decode_steps)) != (
                    len(held["bench.prefill"]),
                    len(held["bench.decode_batch"])):
                raise RuntimeError(f"burst {i}: the calls made and the "
                                   "trace's call spans differ in number")
            out.append(st)
        return out

    @property
    def covered_prefills(self) -> list:
        return [S for st in self._covered_stamps for S in st.prefills]

    @property
    def covered_decode_steps(self) -> list:
        return [c for st in self._covered_stamps for c in st.decode_steps]

    def coverage(self) -> str:
        cov = self._covered_stamps if self.covered else []
        return (f"trace covers {len(self.covered)} of {len(self.bursts)} "
                f"bursts, {sum(len(st.prefills) for st in cov)} of "
                f"{len(self.prefills)} prefills, "
                f"{sum(len(st.decode_steps) for st in cov)} of "
                f"{len(self.decode_steps)} decode steps")


def run(args, t_start: float, *, root=spec.BENCH_DIR, bench=None,
        fault=None, require_chip: bool = True) -> dict:
    """One run of ``args.workload``; returns the result line's object."""
    bench = bench or spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfg = spec.load_config(cell["config"], root)
    mix = traffic.check_mix(spec.load_traffic(cell["traffic"], root))
    limits = spec.load_limits(cell["name"], root)

    import jax
    devices, cache_dir = open_devices(cell, require_chip)
    kind = devices[0].device_kind
    peaks = spec.load_peaks(kind, root) if require_chip else None
    counter = CompileCounter()
    log(f"{args.workload}: {len(devices)} x {kind}; compile cache "
        f"{cache_dir}")

    server = Server(cfg, mix, devices, fault=fault)
    t_import = proxy.now() - t_start
    server.load(args.seed)
    t_init = proxy.now() - t_start
    server.burst(args.seed, 0)
    gc.collect()
    gc.freeze()
    t_warm = proxy.now() - t_start
    log(f"set-up parts (s from start): imports {t_import!r}, weights "
        f"{t_init!r}, warm-up burst {t_warm!r}; executables "
        f"{counter.executables} ({counter.compiles} compiled in "
        f"{counter.compile_s!r} s, {counter.cache_hits} from the cache)")

    traced = bool(args.trace)
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        bursts, reqs, stamps, inside = measure(server, args.seed,
                                               args.seconds, counter)
    finally:
        if traced:
            jax.profiler.stop_trace()
    setup_s = bursts[0].submit - t_start
    summary = window.summarize(bursts, bursts[0].submit)
    log(f"window: {summary['bursts']} bursts, {summary['requests']} "
        f"requests, {summary['output_tokens']} output tokens in "
        f"{summary['window_s']!r} s; compilations inside the window: "
        f"{inside}")
    peak = peak_bytes(devices) if require_chip else 0

    result_metrics, device_extra, breakdown = {}, {}, None
    if traced:
        tr = trace.load(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = LayerContext(tr, cfg, peaks, len(devices), stamps)
        log(ctx.coverage())
        region = ctx.region
        device_extra = {"busy_s": trace.busy_s(tr, region),
                        "window_s": trace.length(region)}
        for m in spec.metrics_of(bench, cell, "per_layer"):
            value = spec.load_reader(m["name"], root)(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        for note in ctx.notes:
            log(note)
        breakdown = {"device_ops": trace.top_ops(tr, region=region),
                     "idle_gaps": trace.idle_gaps(tr, region=region)}
    else:
        summary["setup_s"] = setup_s
        for m in spec.metrics_of(bench, cell, "end_to_end"):
            result_metrics[m["name"]] = {"value": summary[m["name"]],
                                         "unit": m["unit"]}

    # the reference runs once the program's state is freed
    server.unload()
    sample = check.sample(reqs, args.seed, limits["sample_requests"])
    t_ref = proxy.now()
    reading = check.readings(cfg, args.seed, devices[0], sample)
    log(f"reference over {reading['requests']} requests, "
        f"{reading['tokens']} served tokens: {proxy.now() - t_ref!r} s")
    correct, compared = check.judge(reading, limits, summary["requests"],
                                    summary["failed"])
    if inside:
        log(f"WARNING: {inside} compilations inside the window")

    dev = devices[0]
    out = {"correct": correct, "attempted": summary["requests"],
           "failed": summary["failed"], "metrics": result_metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": peak,
                      **device_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def main(argv=None, t_start: float = None) -> int:
    t_start = proxy.now() if t_start is None else t_start
    args = parse_args(argv)
    out = run(args, t_start)
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r}, passes {c['pass']} "
              f"{c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
