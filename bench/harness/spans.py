"""The program's own spans in a JAX profiler trace, beside the device.

While a profiler session is collecting, ``repro.obs`` forwards each of
the program's spans to it: a host event named ``repro.<cat>/<name>``,
with the span's args as its stats, on the row of the thread that ran it,
on the same clock as the device planes.  This reads them next to what
``bench.harness.xplane`` reduces:

* ``program_spans``: the ``repro.*`` events that start inside
  ``bench.window``, clipped to it, in seconds from the window's start;
* ``self_seconds``: for each span of one name, its duration less the
  time that spans of a second name cover inside it on its thread;
* ``named_gaps``: the first chip's idle gaps, longest first, each named
  by the innermost span, harness or program, that covers its middle.  A
  span on another thread (a background re-solve) that covers the middle
  too joins the name as ``<innermost> & <other>``.

``xplane.reduce`` keeps none of this (its gaps are named by harness
spans alone); ``attach`` adds both to a reduced trace, which is how
``bench/program_spans.py`` reads them.
"""
from __future__ import annotations

import collections
import dataclasses

from bench.harness import xplane

PREFIX = "repro."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    #: (host plane, row): one row per thread
    thread: tuple
    #: seconds from the window's start
    start_s: float
    end_s: float
    args: dict


def _host_events(planes):
    """(name, thread, start ns, end ns, event) of every harness and
    program span on the host planes."""
    for plane in planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith((xplane.SPAN_PREFIX, PREFIX)):
                    s = int(ev.start_ns)
                    yield (ev.name, (plane.name, k), s,
                           s + int(ev.duration_ns), ev)


def _window(events) -> tuple[int, int]:
    for name, _, s, e, _ in events:
        if name == xplane.WINDOW:
            return s, e
    raise ValueError("no bench.window span in the trace")


def program_spans(planes) -> list[Span]:
    events = list(_host_events(planes))
    lo, hi = _window(events)
    kept = [Span(name, thread, (s - lo) / 1e9, (min(e, hi) - lo) / 1e9,
                 dict(getattr(ev, "stats", ())))
            for name, thread, s, e, ev in events
            if name.startswith(PREFIX) and lo <= s < hi]
    return sorted(kept, key=lambda sp: sp.start_s)


def self_seconds(spans, parent: str, child: str) -> list[float]:
    """Per span named ``parent``, in start order: its duration less the
    union of the spans named ``child`` inside it on its thread."""
    kids = collections.defaultdict(list)
    for sp in spans:
        if sp.name == child:
            kids[sp.thread].append((sp.start_s, sp.end_s))
    out = []
    for sp in spans:
        if sp.name != parent:
            continue
        inside = [(max(s, sp.start_s), min(e, sp.end_s))
                  for s, e in kids[sp.thread]
                  if s < sp.end_s and e > sp.start_s]
        covered = sum(e - s for s, e in xplane.union(inside))
        out.append(sp.end_s - sp.start_s - covered)
    return out


def _gap_name(events, mid: int) -> str:
    """The innermost span covering ``mid`` (``no span`` if none), and
    the innermost on each other thread that covers it too."""
    covering = sorted((e - s, thread, name)
                      for name, thread, s, e, _ in events
                      if s <= mid < e and name != xplane.WINDOW)
    if not covering:
        return "no span"
    inner = covering[0]
    others: dict = {}
    for _, thread, name in covering:
        if thread != inner[1]:
            others.setdefault(thread, name)
    return " & ".join([inner[2], *others.values()])


def named_gaps(planes) -> list[tuple[str, float]]:
    """The first chip's longest idle gaps in the window, named by
    ``_gap_name``; the holes are ``xplane.reduce``'s."""
    planes = list(planes)
    events = list(_host_events(planes))
    lo, hi = _window(events)
    devices = sorted((p for p in planes if xplane.DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(xplane.DEVICE_PLANE.match(p.name)[1]))
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lines = {line.name: line for line in devices[0].lines}
    merged = xplane.union(
        c for c in (xplane._clip(s, e, lo, hi)
                    for _, s, e in xplane._events(lines[xplane.OPS_LINE]))
        if c is not None)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    holes.sort(key=lambda h: h[0] - h[1])
    return [(_gap_name(events, (s + e) // 2), (e - s) / 1e9)
            for s, e in holes[:10]]


def attach(reduced: xplane.Reduced, planes) -> xplane.Reduced:
    """``reduced`` with the program's spans (``reduced.spans``) and its
    idle gaps named by them."""
    planes = list(planes)
    reduced.spans = program_spans(planes)
    reduced.gaps = named_gaps(planes)
    return reduced
