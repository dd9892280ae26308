"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
``Trace``: for each device, its ops (line ``XLA Ops``, each named by
``op_label``) and its programs (line ``XLA Modules``); on the host, the
benchmark's own spans (names starting ``bench.``). Times are seconds on
the profiler's clock, which host and device events share.

Everything else here is interval arithmetic over a ``Trace`` and is
checked on a small recorded trace in ``tests/bench/``.

A long or busy traced window can lose device events: the profiler keeps
a bounded number of trace buffers a chip, while every host span stays.
So a reading that divides the window's calls by their device time takes
both over the bursts whose every executor call holds its program's
event (``covered``), and over those bursts' stretches of the window
(``segments``). Where every burst is covered, the stretches are the
window, and every reading is what the whole window gives.
"""
from __future__ import annotations

import glob
import re
from bisect import bisect_right
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
# executor call span -> the program each call runs once, synced inside it
PROGRAMS = (("bench.prefill", "jit_prefill"),
            ("bench.decode_batch", "jit_decode_step"))
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
# what the per-layer metrics read; ``region`` is a sorted, disjoint
# [(lo, hi)] list inside the window, by default the whole window
# ----------------------------------------------------------------------
def spans(tr: Trace, name: str) -> list:
    return [(lo, hi) for n, lo, hi in tr.host if n == name]


def window(tr: Trace) -> tuple:
    """(start, end) of the ``bench.window`` span."""
    w = spans(tr, "bench.window")
    if len(w) != 1:
        raise RuntimeError(f"expected one bench.window span, found {len(w)}")
    return w[0]


def _region(tr: Trace, region) -> list:
    return [window(tr)] if region is None else region


def _inside(s: float, e: float, region: list) -> bool:
    for lo, hi in region:
        if s >= lo and e <= hi:
            return True
    return False


def busy(tr: Trace, dev: str, region: list) -> list:
    evs = [(s, e) for _, s, e in tr.devices[dev].get("ops", [])]
    return intersect(union(evs), region)


def busy_s(tr: Trace, region=None) -> float:
    """Seconds of ``region`` in which an op ran, averaged over devices."""
    region = _region(tr, region)
    if not tr.devices:
        return 0.0
    return float(np.mean([length(busy(tr, d, region))
                          for d in tr.devices]))


def events(tr: Trace, line: str, prefix: str, region=None) -> list:
    """(device, name, start, end) of ``line`` events whose name starts
    with ``prefix``, inside ``region``."""
    region = _region(tr, region)
    return [(d, n, s, e) for d, lines in tr.devices.items()
            for n, s, e in lines.get(line, [])
            if n.startswith(prefix) and _inside(s, e, region)]


def device_time(tr: Trace, line: str, prefix: str, region=None) -> float:
    """Summed device seconds of matching events in ``region``."""
    return float(sum(e - s for _, _, s, e in
                     events(tr, line, prefix, region)))


def span_time(tr: Trace, name: str, region=None) -> float:
    """Host seconds of the named spans, clipped to ``region``."""
    return sum(min(e, hi) - max(s, lo) for s, e in spans(tr, name)
               for lo, hi in _region(tr, region) if e > lo and s < hi)


def host_share(tr: Trace, names) -> float:
    """Share of the window covered by the union of the named spans."""
    lo, hi = window(tr)
    covered = intersect(union([iv for n in names for iv in spans(tr, n)]),
                        [(lo, hi)])
    return length(covered) / (hi - lo)


def bursts(tr: Trace) -> list:
    """The window's ``bench.burst`` spans, in order."""
    lo, hi = window(tr)
    return sorted(iv for iv in spans(tr, "bench.burst")
                  if iv[0] >= lo and iv[1] <= hi)


def calls(tr: Trace, burst: tuple) -> dict:
    """Executor call span name -> that span's intervals inside
    ``burst``."""
    return {name: [iv for iv in spans(tr, name)
                   if iv[0] >= burst[0] and iv[1] <= burst[1]]
            for name, _ in PROGRAMS}


def _held(evs: list, ivs: list) -> list:
    """For each of the sorted, disjoint spans ``ivs``, how many of the
    events ``evs`` overlap it more than any other span. Events are
    matched by overlap, not by containment: on the profiler's clock a
    program's event may begin a fraction of a millisecond before the
    host span of the call that launched it."""
    starts = [lo for lo, _ in ivs]
    held = [0] * len(ivs)
    for s, e in evs:
        best, most = None, 0.0
        for k in range(bisect_right(starts, e) - 1, -1, -1):
            lo, hi = ivs[k]
            if hi <= s:
                break
            if min(e, hi) - max(s, lo) > most:
                best, most = k, min(e, hi) - max(s, lo)
        if best is not None:
            held[best] += 1
    return held


def covered(tr: Trace) -> list:
    """Indices into ``bursts(tr)`` of the bursts whose every executor
    call span holds exactly one event of its program (``PROGRAMS``) on
    the devices' module lines. Both programs are synced inside their
    call, so a call whose event is missing lost it to the profiler."""
    lo, hi = window(tr)
    whole = {}
    for span, program in PROGRAMS:
        ivs = sorted(iv for iv in spans(tr, span)
                     if iv[0] >= lo and iv[1] <= hi)
        evs = [(s, e) for lines in tr.devices.values()
               for n, s, e in lines.get("modules", [])
               if n.startswith(program)]
        whole.update((iv, n == 1) for iv, n in zip(ivs, _held(evs, ivs)))
    return [i for i, b in enumerate(bursts(tr))
            if all(whole[iv] for ivs in calls(tr, b).values()
                   for iv in ivs)]


def segments(tr: Trace, idx) -> list:
    """The stretches of the window that belong to bursts ``idx``: each
    runs from the end of the burst before it (the window's start, for
    the first) to its own end (the window's end, for the last), so the
    collector's pause between two bursts goes with the later one.
    Adjacent stretches are merged, so all bursts' make ``[window(tr)]``."""
    lo, hi = window(tr)
    bs = bursts(tr)
    ends = [e for _, e in bs[:-1]] + [hi]
    return union([((ends[i - 1] if i else lo), ends[i]) for i in idx])


def idle_gaps(tr: Trace, top: int = 10, region=None) -> list:
    """[(label, seconds)] of device idle time in ``region``, put down to
    the innermost benchmark span the host was in, averaged over
    devices, longest first."""
    region = _region(tr, region)
    totals = defaultdict(float)
    label_ivs = [(label, union(spans(tr, name)))
                 for name, label in GAP_LABELS]
    for dev in tr.devices:
        idle = subtract(region, busy(tr, dev, region))
        for label, ivs in label_ivs:
            hit = intersect(idle, ivs)
            totals[label] += length(hit) / len(tr.devices)
            idle = subtract(idle, hit)
        totals["outside spans"] += length(idle) / len(tr.devices)
    ranked = sorted(((k, v) for k, v in totals.items() if v > 0),
                    key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def top_ops(tr: Trace, top: int = 10, region=None) -> list:
    """[(op label, device seconds summed over devices)] in ``region``,
    longest first; loops and calls are left out, their bodies' ops are
    counted."""
    region = _region(tr, region)
    totals = defaultdict(float)
    for lines in tr.devices.values():
        for n, s, e in lines.get("ops", []):
            if _inside(s, e, region) and n.rsplit(" ", 1)[-1] \
                    not in CONTAINER_KINDS:
                totals[n] += e - s
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]
