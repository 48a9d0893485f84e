"""repro.obs — observability for the serving/solver stack.

* :mod:`repro.obs.trace`   — per-request spans into a bounded ring buffer,
  exportable as Chrome-trace/Perfetto JSON (``scripts/obs_dump.py``),
  and into the JAX profiler's trace whenever a profiler session is
  collecting (``jax.profiler.TraceAnnotation``, ``repro.<cat>/<name>``).
* :mod:`repro.obs.metrics` — counters/gauges/histograms registry with
  Prometheus text exposition; backs ``PlanEngine.stats()``.
* :mod:`repro.obs.drift`   — cost-model predicted vs. observed latency
  EMA; drift triggers the background re-solve + plan-store refresh path.

Everything here is stdlib-only (importable without jax; the profiler
sink is looked up only once something else has imported jax).
``configure_logging()`` wires the ``repro`` logger family to the
``REPRO_LOG`` env level so background daemon threads (breaker re-solve,
bucket presolve, stale plan refresh) leave a record instead of retrying
silently.
"""

from __future__ import annotations

import logging
import os

from .drift import DriftConfig, DriftDetector, DriftEvent
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, default_registry
from .trace import Span, Tracer, chrome_trace, configure, dump_chrome_trace, tracer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "Span",
    "Tracer",
    "tracer",
    "configure",
    "chrome_trace",
    "dump_chrome_trace",
    "DriftConfig",
    "DriftDetector",
    "DriftEvent",
    "configure_logging",
]

ENV_LOG = "REPRO_LOG"
_LOG_CONFIGURED = False


def configure_logging(level: str | int | None = None, force: bool = False) -> logging.Logger:
    """Configure the ``repro`` logger family from ``REPRO_LOG``.

    ``REPRO_LOG=debug|info|warning|error`` sets the level; unset leaves
    the default (WARNING) so normal runs stay quiet.  Idempotent unless
    ``force``.  Records carry a timestamp, level, logger name, and the
    message — background loops embed entry name / attempt / backoff as
    ``key=value`` pairs in the message for grep-ability.
    """
    global _LOG_CONFIGURED
    log = logging.getLogger("repro")
    if _LOG_CONFIGURED and not force and level is None:
        return log
    raw = level if level is not None else os.environ.get(ENV_LOG, "")
    if isinstance(raw, str):
        resolved = logging.getLevelName(raw.strip().upper()) if raw.strip() else logging.WARNING
        if not isinstance(resolved, int):
            resolved = logging.WARNING
    else:
        resolved = int(raw)
    log.setLevel(resolved)
    if not log.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s %(message)s"
        ))
        log.addHandler(h)
        log.propagate = False
    _LOG_CONFIGURED = True
    return log
