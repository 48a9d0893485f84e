"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData`` only.  Each chip is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation
executed on the device and its ``XLA Modules`` line one event per program
execution.  The harness's own host spans (``jax.profiler.TraceAnnotation``
named ``bench.*``) are events on host threads; ``bench.window`` marks the
measured window, and the others say what the host was doing when the
device sat idle.

* busy: the union of the operation intervals inside the window, per chip,
  averaged over the chips used;
* per operation and per program: summed device time and count, the
  operation keyed by the program it ran in;
* idle gaps: the holes in the union on the first chip, each named by the
  innermost harness span that covers its middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
_ID_SUFFIX = re.compile(r"\(\d+\)$")
_HLO_LINE = re.compile(r"^%?([^\s=]+) = ")


def op_name(name: str) -> str:
    """An operation's instruction name: a TPU trace names each operation
    event by its whole HLO line, ``%body.5 = f32[...] custom-call(...)``;
    this keeps ``body.5``."""
    m = _HLO_LINE.match(name)
    return m.group(1) if m else name


def module_name(name: str) -> str:
    """A program's stable name: ``jit_decode_step(123)`` ->
    ``jit_decode_step``."""
    return _ID_SUFFIX.sub("", name)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    #: (program, operation) -> [count, seconds], first chip
    ops: dict
    #: program -> [count, seconds], first chip
    modules: dict
    #: (what the host was doing, seconds), longest first, first chip
    gaps: list
    chips: int

    def op_seconds(self, match) -> tuple[int, float]:
        """Count and device seconds of the operations for which
        ``match(program, op)`` holds."""
        n, s = 0, 0.0
        for (mod, op), (c, t) in self.ops.items():
            if match(mod, op):
                n += c
                s += t
        return n, s

    def module_seconds(self, match) -> tuple[int, float]:
        n, s = 0, 0.0
        for mod, (c, t) in self.modules.items():
            if match(mod):
                n += c
                s += t
        return n, s

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        return {"device_ops": [[f"{m}/{o}", t] for (m, o), (_, t) in top],
                "idle_gaps": [[name, s] for name, s in self.gaps[:10]]}


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def _host_spans(planes) -> list[tuple[int, int, str]]:
    spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name.startswith(SPAN_PREFIX):
                    spans.append((s, e, name))
    return spans


def _name_gap(spans, mid: int) -> str:
    """The innermost harness span covering ``mid``, or ``no span``."""
    best = None
    for s, e, name in spans:
        if s <= mid < e and name != WINDOW and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no span"


def reduce(planes, chips: int) -> Reduced:
    planes = list(planes)
    spans = _host_spans(planes)
    windows = [(s, e) for s, e, name in spans if name == WINDOW]
    if not windows:
        raise ValueError("no bench.window span in the trace")
    lo, hi = windows[0]
    devices = sorted(((int(DEVICE_PLANE.match(p.name).group(1)), p)
                      for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda t: t[0])[:chips]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    busy = []
    ops: dict = collections.defaultdict(lambda: [0, 0.0])
    modules: dict = collections.defaultdict(lambda: [0, 0.0])
    gaps: list = []
    for k, (_, plane) in enumerate(devices):
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line; lines: "
                             f"{sorted(lines)}")
        mods = []
        if MODULES_LINE in lines:
            for name, s, e in _events(lines[MODULES_LINE]):
                c = _clip(s, e, lo, hi)
                if c is None:
                    continue
                mods.append((s, e, module_name(name)))
                if k == 0:
                    modules[module_name(name)][0] += 1
                    modules[module_name(name)][1] += (c[1] - c[0]) / 1e9
        mods.sort()
        starts = [m[0] for m in mods]
        intervals = []
        for name, s, e in _events(lines[OPS_LINE]):
            c = _clip(s, e, lo, hi)
            if c is None:
                continue
            intervals.append(c)
            if k == 0:
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= e else "?"
                ops[(mod, op_name(name))][0] += 1
                ops[(mod, op_name(name))][1] += (c[1] - c[0]) / 1e9
        merged = union(intervals)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if k == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            holes = [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
            holes.sort(key=lambda h: h[0] - h[1])
            gaps = [(_name_gap(spans, (s + e) // 2), (e - s) / 1e9)
                    for s, e in holes[:10]]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy),
                   ops=dict(ops), modules=dict(modules), gaps=gaps,
                   chips=len(devices))


def trace_file(trace_dir: str) -> str:
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {found}")
    return found[0]


def reduce_file(path: str, chips: int) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path).planes, chips)


def reduce_dir(trace_dir: str, chips: int) -> Reduced:
    return reduce_file(trace_file(trace_dir), chips)
