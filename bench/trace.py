"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
``Trace``: for each device, its ops (line ``XLA Ops``, each named by
``op_label``) and its programs (line ``XLA Modules``); on the host, the
benchmark's own spans (names starting ``bench.``). Times are seconds on
the profiler's clock, which host and device events share.

Everything else here is interval arithmetic over a ``Trace`` and is
checked on a small recorded trace in ``tests/bench/``.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# host span -> what an idle device gap inside it is put down to; the
# executor's calls first, then the collector, the
# rest of a burst (the fleet loop and scheduler), the rest of the window
GAP_LABELS = (("bench.decode_batch", "decode_batch"),
              ("bench.prefill", "prefill"), ("bench.gc", "gc"),
              ("bench.burst", "loop"), ("bench.window", "window"))
EXECUTOR_SPANS = ("bench.decode_batch", "bench.prefill")
# ops whose time is their body's, which the trace lists as well
CONTAINER_KINDS = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
_KIND = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_label(hlo: str) -> str:
    """``"%fusion.12 = bf16[8,2048]{1,0} fusion(...), ..."`` ->
    ``"fusion.12 bf16[8,2048] fusion"``: the op's name, its first result
    shape and its kind, short and stable across runs of one program."""
    if " = " not in hlo:
        return hlo
    name, rhs = hlo.split(" = ", 1)
    rhs = _LAYOUT.sub("", rhs)
    shape, kind = _SHAPE.search(rhs), _KIND.search(rhs)
    return " ".join([name.lstrip("%")] + ([shape.group()] if shape else [])
                    + ([kind.group(1)] if kind else []))


@dataclass
class Trace:
    # device name -> line ("ops" / "modules") -> [(name, start_s, end_s)]
    devices: dict = field(default_factory=dict)
    host: list = field(default_factory=list)   # [(name, start_s, end_s)]

    def to_json(self) -> dict:
        return {"devices": self.devices, "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(devices={k: {ln: [tuple(e) for e in evs]
                                for ln, evs in v.items()}
                            for k, v in d["devices"].items()},
                   host=[tuple(e) for e in d["host"]])


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    data = ProfileData.from_file(files[0])
    tr = Trace()
    labels = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    evs = []
                    for e in line.events:
                        name = e.name
                        if name not in labels:
                            labels[name] = op_label(name)
                        evs.append((labels[name], e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
                    lines[key] = evs
            if lines:
                tr.devices[plane.name] = lines
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.host.extend((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events
                               if e.name.startswith(SPAN_PREFIX))
    tr.host.sort(key=lambda e: e[1])
    return tr


# ----------------------------------------------------------------------
# interval arithmetic on sorted, disjoint [(lo, hi)] lists
# ----------------------------------------------------------------------
def union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return [tuple(x) for x in out]


def intersect(a: list, b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """a minus b."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            if cur >= hi:
                break
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def length(a: list) -> float:
    return float(sum(hi - lo for lo, hi in a))


# ----------------------------------------------------------------------
# what the per-layer metrics read
# ----------------------------------------------------------------------
def spans(tr: Trace, name: str) -> list:
    return [(lo, hi) for n, lo, hi in tr.host if n == name]


def window(tr: Trace) -> tuple:
    """(start, end) of the ``bench.window`` span."""
    w = spans(tr, "bench.window")
    if len(w) != 1:
        raise RuntimeError(f"expected one bench.window span, found {len(w)}")
    return w[0]


def busy(tr: Trace, dev: str, lo: float, hi: float) -> list:
    evs = [(s, e) for _, s, e in tr.devices[dev].get("ops", [])]
    return intersect(union(evs), [(lo, hi)])


def busy_s(tr: Trace) -> float:
    """Seconds of the window in which an op ran, averaged over devices."""
    lo, hi = window(tr)
    if not tr.devices:
        return 0.0
    return float(np.mean([length(busy(tr, d, lo, hi))
                          for d in tr.devices]))


def events(tr: Trace, line: str, prefix: str) -> list:
    """(device, name, start, end) of ``line`` events whose name starts
    with ``prefix``, inside the window."""
    lo, hi = window(tr)
    return [(d, n, s, e) for d, lines in tr.devices.items()
            for n, s, e in lines.get(line, [])
            if n.startswith(prefix) and s >= lo and e <= hi]


def device_time(tr: Trace, line: str, prefix: str) -> float:
    """Summed device seconds of matching events in the window."""
    return float(sum(e - s for _, _, s, e in events(tr, line, prefix)))


def host_share(tr: Trace, names) -> float:
    """Share of the window covered by the union of the named spans."""
    lo, hi = window(tr)
    covered = intersect(union([iv for n in names for iv in spans(tr, n)]),
                        [(lo, hi)])
    return length(covered) / (hi - lo)


def idle_gaps(tr: Trace, top: int = 10) -> list:
    """[(label, seconds)] of device idle time in the window, put down to
    the innermost benchmark span the host was in, averaged over
    devices, longest first."""
    lo, hi = window(tr)
    totals = defaultdict(float)
    label_ivs = [(label, union(spans(tr, name)))
                 for name, label in GAP_LABELS]
    for dev in tr.devices:
        idle = subtract([(lo, hi)], busy(tr, dev, lo, hi))
        for label, ivs in label_ivs:
            hit = intersect(idle, ivs)
            totals[label] += length(hit) / len(tr.devices)
            idle = subtract(idle, hit)
        totals["outside spans"] += length(idle) / len(tr.devices)
    ranked = sorted(((k, v) for k, v in totals.items() if v > 0),
                    key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def top_ops(tr: Trace, top: int = 10) -> list:
    """[(op label, device seconds summed over devices)], longest first;
    loops and calls are left out, their bodies' ops are counted."""
    lo, hi = window(tr)
    totals = defaultdict(float)
    for lines in tr.devices.values():
        for n, s, e in lines.get("ops", []):
            if s >= lo and e <= hi and n.rsplit(" ", 1)[-1] \
                    not in CONTAINER_KINDS:
                totals[n] += e - s
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]
