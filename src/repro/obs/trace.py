"""Per-request spans, to a bounded ring buffer and to the JAX profiler.

Spans cover the whole request path (submit → admission → program lookup
→ dispatch → timed sync → fallback/canary), the LM decode loop (prefill,
then per token: sync, dispatch, sample), the background plan refresh and
bucket pre-solve, and the solver phases (fusion, enumeration,
chunk-merge).  Each span has two sinks:

* the ring buffer, on with ``enabled`` (``REPRO_OBS_TRACE``): one short
  lock around a ``deque(maxlen=...)`` append.  Export is Chrome-trace
  JSON (``chrome_trace()``), which Perfetto and ``chrome://tracing``
  both load directly; ``scripts/obs_dump.py`` writes it to disk.
* the JAX profiler, whenever a profiler session is collecting: the span
  is a ``jax.profiler.TraceAnnotation`` named ``repro.<cat>/<name>``
  with its args as metadata, so it lands on the host plane of the same
  ``.xplane.pb`` as the device operations, on the same clock.  The check
  is made only once ``jax`` is imported, so this module never imports
  jax itself (solver worker processes stay free of it).

With both sinks off a span site returns one shared null span.
``Tracer.record`` (a span measured after the fact, such as the
batcher's queue wait) reaches the ring buffer only: the profiler takes
no retroactive events.

Span taxonomy (category / name; ``rid`` ties the spans of one
``PlanEngine.submit`` together, ``gid`` and ``t`` those of one
``Engine.generate`` and its tokens):

* ``request/submit``      — the whole of ``PlanEngine.submit``
* ``request/admission``   — semaphore wait + deadline check in ``submit``
* ``request/resolve``     — compiled-program lookup (``miss``: built and
  compiled on this request)
* ``request/execute``     — optimized program dispatch (one clone)
* ``request/sync``        — device sync of a timed run (``reason``:
  ``drift``, ``canary``, ``straggler`` or ``nan_guard``)
* ``request/fallback``    — plain-jit fallback run
* ``request/canary``      — canary validation of the optimized answer
* ``request/queue_wait``  — batcher enqueue → flush pick-up (ring only)
* ``request/batch_coalesce`` — stacking + batched submit of one bucket
* ``generate/prefill``    — prefill dispatch and the first sample
* ``decode/token``        — one token of the decode loop, parent of
  ``decode/sync`` (waiting for the sampled token on the host),
  ``decode/dispatch`` (the jitted decode step) and ``decode/sample``
* ``plan/refresh``        — background drift/stale re-solve loop
* ``plan/presolve``       — background batch-bucket pre-solve
* ``solver/fuse``, ``solver/enumerate``, ``solver/chunk_merge``
* ``store/load``, ``store/save``
* ``frontend/trace``      — jaxpr capture + lowering
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

__all__ = ["Span", "Tracer", "tracer", "configure", "chrome_trace"]

DEFAULT_CAPACITY = 4096


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() in {"1", "true", "on", "yes"}


@dataclass
class Span:
    name: str
    cat: str
    start_s: float          # time.perf_counter() at span start
    dur_s: float            # duration in seconds
    tid: int                # recording thread id
    args: dict = field(default_factory=dict)


class _NullSpan:
    """No-op context manager returned when both sinks are off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        return self


_NULL = _NullSpan()

#: ``jax.profiler.TraceAnnotation`` once jax is imported.
_annotation = None


def _profiler_annotation():
    """``TraceAnnotation`` while a JAX profiler session is collecting,
    else ``None``.  Never imports jax: until something else has, there
    is no profiler to collect."""
    global _annotation
    ann = _annotation
    if ann is None:
        if sys.modules.get("jax") is None:
            return None
        from jax.profiler import TraceAnnotation as ann
        _annotation = ann
    return ann if ann.is_enabled() else None


class _LiveSpan:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict,
                 annotation):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = None if annotation is None \
            else annotation(f"repro.{cat}/{name}", **args)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, etype, exc, tb):
        t1 = time.perf_counter()
        if etype is not None:
            self.set(error=etype.__name__)
        if self._ann is not None:
            self._ann.__exit__(etype, exc, tb)
        self._tracer.record(self.name, self.cat, self._t0, t1 - self._t0,
                            self.args)
        return False

    def set(self, **kw):
        self.args.update(kw)
        if self._ann is not None:
            self._ann.set_metadata(**kw)
        return self


class Tracer:
    """Bounded span recorder.  With ``enabled`` off and no profiler
    session collecting, a span site costs one attribute read and one
    profiler check."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled: bool | None = None):
        if enabled is None:
            enabled = _env_truthy("REPRO_OBS_TRACE")
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._buf: deque[Span] = deque(maxlen=max(1, int(capacity)))
        self._dropped = 0
        self._recorded = 0

    # -- recording ------------------------------------------------------
    def span(self, name: str, cat: str = "request", **args):
        """Context manager timing a block into each sink that is on."""
        ann = _profiler_annotation()
        if not self.enabled and ann is None:
            return _NULL
        return _LiveSpan(self, name, cat, args, ann)

    def record(self, name: str, cat: str, start_s: float, dur_s: float,
               args: dict | None = None) -> None:
        """Record a completed span in the ring buffer (used for queue
        waits measured after the fact, where a context manager can't
        straddle threads; the profiler takes no retroactive events)."""
        if not self.enabled:
            return
        sp = Span(name, cat, start_s, dur_s, threading.get_ident(),
                  args or {})
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self._dropped += 1
            self._buf.append(sp)
            self._recorded += 1

    # -- reading --------------------------------------------------------
    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0
            self._recorded = 0

    def resize(self, capacity: int) -> None:
        with self._lock:
            self._buf = deque(self._buf, maxlen=max(1, int(capacity)))

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "capacity": self._buf.maxlen,
                "buffered": len(self._buf),
                "recorded": self._recorded,
                "dropped": self._dropped,
            }


def chrome_trace(spans: list[Span]) -> dict:
    """Render spans as a Chrome-trace / Perfetto-loadable JSON object.

    Complete events (``ph: "X"``) with microsecond timestamps relative
    to the earliest span, one virtual thread row per recording thread.
    """
    base = min((s.start_s for s in spans), default=0.0)
    pid = os.getpid()
    events = []
    tids: dict[int, int] = {}
    for s in spans:
        tid = tids.setdefault(s.tid, len(tids))
        events.append({
            "name": s.name,
            "cat": s.cat,
            "ph": "X",
            "ts": (s.start_s - base) * 1e6,
            "dur": s.dur_s * 1e6,
            "pid": pid,
            "tid": tid,
            "args": s.args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome_trace(spans: list[Span], path: str) -> None:
    with open(path, "w") as f:
        json.dump(chrome_trace(spans), f)


_tracer = Tracer()


def tracer() -> Tracer:
    """Process-wide tracer shared by every layer."""
    return _tracer


def configure(enabled: bool | None = None, capacity: int | None = None) -> Tracer:
    if enabled is not None:
        _tracer.enabled = bool(enabled)
    if capacity is not None:
        _tracer.resize(capacity)
    return _tracer
