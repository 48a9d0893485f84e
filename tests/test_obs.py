"""Observability layer (``repro.obs``) + its serving integration.

What these pin down:

* the metrics registry is the single source of truth — ``stats()``,
  ``expose()`` and the legacy counter attributes all read the same
  numbers, and the accounting closures (``ok+fallbacks == completed``,
  ``completed+expired+errors == enqueued``) hold under threaded chaos;
* the span tracer is bounded (ring buffer drops, never grows) and its
  Chrome-trace export is loadable JSON with microsecond complete events;
* spans reach the JAX profiler's trace while a profiler session is
  collecting, nested and tagged per request (``rid``) and per token
  (``gid``, ``t``); with both sinks off a span site is the null span, and
  ``repro.obs`` imports without jax;
* drift detection is deterministic on an injected clock: min-samples,
  threshold band (both directions), cooldown, and EMA reset on re-plan;
* ``stats()`` never deadlocks against a concurrent submit storm — the
  lock-ordering regression test for the nested-lock assembly bug.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SolverOptions
from repro.ft import ChaosPlan
from repro.obs import (DriftConfig, DriftDetector, MetricsRegistry, Tracer,
                       chrome_trace)
from repro.serve import BatchConfig, PlanEngine, ServeConfig

_RNG = np.random.default_rng(0)
_WA = jnp.asarray(_RNG.standard_normal((16, 16)).astype(np.float32) * 0.1)
_X = jnp.asarray(_RNG.standard_normal((8, 16)).astype(np.float32))


def _mm(x):
    return x @ _WA


def _engine(sc: ServeConfig | None = None, name: str = "f") -> PlanEngine:
    eng = PlanEngine(sc=sc or ServeConfig())
    tf = eng.register_function(name, _mm, (_X,),
                               solver_opts=SolverOptions(time_budget_s=0.5))
    assert tf is not None
    return eng


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_counter_inc_returns_new_value_and_snapshot():
    m = MetricsRegistry()
    c = m.counter("t_total", "help")
    assert c.inc() == 1
    assert c.inc(4) == 5
    assert c.value == 5
    assert c.snapshot() == {(): 5}


def test_registry_get_or_create_and_type_mismatch():
    m = MetricsRegistry()
    a = m.counter("x_total")
    b = m.counter("x_total")
    assert a is b
    with pytest.raises(TypeError):
        m.gauge("x_total")


def test_labeled_children_and_remove():
    m = MetricsRegistry()
    c = m.counter("per_entry_total", labelnames=("entry",))
    c.labels("a").inc(3)
    c.labels("b").inc()
    assert m.value("per_entry_total", "a") == 3
    assert c.snapshot() == {("a",): 3, ("b",): 1}
    c.remove("a")
    assert c.snapshot() == {("b",): 1}
    assert m.value("per_entry_total", "a") == 0     # never-touched => 0
    with pytest.raises(ValueError):
        c.labels("a", "too-many")


def test_gauge_set_inc_dec_and_fn_backed():
    m = MetricsRegistry()
    g = m.gauge("depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    f = m.gauge("live", fn=lambda: 42)
    assert f.value == 42


def test_histogram_buckets_count_sum_quantile():
    m = MetricsRegistry()
    h = m.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 2.0):
        h.observe(v)
    snap = h.snapshot()[()]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(2.605)
    assert snap["counts"] == [1, 2, 1, 1]       # last is the +Inf tail
    assert h.quantile(0.5) == 0.1               # upper-bound interpolation


def test_expose_prometheus_text_format():
    m = MetricsRegistry()
    m.counter("req_total", "requests").inc(3)
    m.counter("per_total", "per entry", ("entry",)).labels('a"\\b').inc()
    m.gauge("inflight", "in flight").set(2)
    h = m.histogram("rt_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    text = m.expose()
    lines = text.strip().split("\n")
    assert "# HELP req_total requests" in lines
    assert "# TYPE req_total counter" in lines
    assert "req_total 3" in lines
    # label values escaped per the text format
    assert 'per_total{entry="a\\"\\\\b"} 1' in lines
    assert "inflight 2" in lines
    # histogram: cumulative buckets ending at +Inf == _count
    assert 'rt_seconds_bucket{le="0.1"} 1' in lines
    assert 'rt_seconds_bucket{le="1"} 1' in lines
    assert 'rt_seconds_bucket{le="+Inf"} 2' in lines
    assert "rt_seconds_count 2" in lines
    # every sample line is "name{...} value" with a numeric value
    for ln in lines:
        if ln.startswith("#"):
            continue
        float(ln.rsplit(" ", 1)[1])


def test_invariants_checked_from_registry():
    m = MetricsRegistry()
    a = m.counter("a_total")
    b = m.counter("b_total")
    m.register_invariant("a==b", lambda: a.value == b.value)
    assert m.check_invariants() == []
    a.inc()
    assert m.check_invariants() == ["a==b"]
    b.inc()
    assert m.check_invariants() == []


# ---------------------------------------------------------------------------
# Span tracer
# ---------------------------------------------------------------------------
def test_tracer_ring_buffer_bounds_and_drop_count():
    t = Tracer(capacity=4, enabled=True)
    for i in range(10):
        t.record("s", "test", float(i), 0.001, {"i": i})
    st = t.stats()
    assert st["buffered"] == 4 and st["recorded"] == 10
    assert st["dropped"] == 6
    names = [s.args["i"] for s in t.snapshot()]
    assert names == [6, 7, 8, 9]                # oldest evicted first


def test_disabled_tracer_is_noop():
    t = Tracer(capacity=4, enabled=False)
    with t.span("x", "test", entry="e") as sp:
        sp.set(more=1)                          # null span accepts set()
    t.record("y", "test", 0.0, 1.0)
    assert t.snapshot() == []
    assert t.stats()["recorded"] == 0


def test_live_span_times_block_and_records_error_class():
    t = Tracer(capacity=16, enabled=True)
    with t.span("ok", "test", entry="e") as sp:
        time.sleep(0.01)
        sp.set(extra=7)
    with pytest.raises(ValueError):
        with t.span("boom", "test"):
            raise ValueError("injected")
    ok, boom = t.snapshot()
    assert ok.name == "ok" and ok.dur_s >= 0.009
    assert ok.args == {"entry": "e", "extra": 7}
    assert boom.args["error"] == "ValueError"


def test_chrome_trace_export_round_trips():
    t = Tracer(capacity=16, enabled=True)
    with t.span("a", "request", entry="e"):
        time.sleep(0.002)
    t.record("b", "solver", 100.0, 0.5, {"k": 1})
    doc = json.loads(json.dumps(chrome_trace(t.snapshot())))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] > 0
        assert set(ev) >= {"name", "cat", "pid", "tid", "args"}
    # timestamps are rebased to the earliest span
    assert min(ev["ts"] for ev in evs) == 0


# ---------------------------------------------------------------------------
# Drift detection (fake clock)
# ---------------------------------------------------------------------------
def _detector(**kw):
    clock = {"t": 0.0}
    cfg = DriftConfig(**{"sample_every": 1, "min_samples": 3,
                         "ratio_threshold": 2.0, "cooldown_s": 10.0, **kw})
    return DriftDetector(cfg, clock=lambda: clock["t"]), clock


def test_drift_needs_min_samples_and_prediction():
    det, _ = _detector()
    for _ in range(5):
        assert det.observe("m", 1.0) is None    # no prediction yet
    det.note_predicted("m", 0.1)                # resets the EMA
    assert det.observe("m", 1.0) is None        # samples 1, 2 < min
    assert det.observe("m", 1.0) is None
    ev = det.observe("m", 1.0)
    assert ev is not None and ev.ratio > 2.0 and ev.samples == 3


def test_drift_fires_both_directions_and_cooldown():
    det, clock = _detector()
    det.note_predicted("m", 1.0)
    for _ in range(3):
        assert det.observe("m", 1.0) is None    # ratio 1.0: in band
    # 10x slower than predicted: fires once, then cooldown suppresses
    assert det.observe("m", 30.0) is not None
    assert det.observe("m", 30.0) is None
    clock["t"] += 11.0                          # past cooldown: re-fires
    assert det.observe("m", 30.0) is not None
    # 10x faster also counts as drift (stale pessimistic plan)
    det.note_predicted("m", 1.0)
    det.note_predicted("m", 100.0)              # changed => EMA reset
    clock["t"] += 11.0
    for _ in range(2):
        det.observe("m", 1.0)
    ev = det.observe("m", 1.0)
    assert ev is not None and ev.ratio < 0.5


def test_note_predicted_same_value_keeps_ema():
    det, _ = _detector()
    det.note_predicted("m", 1.0)
    det.observe("m", 5.0)
    det.note_predicted("m", 1.0)                # unchanged: no reset
    assert det.stats()["entries"]["m"]["samples"] == 1
    det.note_predicted("m", 2.0)                # changed: reset
    assert det.stats()["entries"]["m"]["samples"] == 0
    det.forget("m")
    assert det.stats()["entries"] == {}


def test_drift_stats_shape():
    det, _ = _detector()
    det.note_predicted("m", 1.0)
    for _ in range(3):
        det.observe("m", 4.0)
    st = det.stats()
    assert st["triggers"] == 1
    e = st["entries"]["m"]
    assert e["drifted"] is True
    assert e["ratio"] == pytest.approx(4.0)
    assert e["predicted_s"] == 1.0


# ---------------------------------------------------------------------------
# Engine integration: registry is the single source of truth
# ---------------------------------------------------------------------------
def test_engine_stats_exposition_and_invariants_agree():
    eng = _engine()
    try:
        for _ in range(5):
            eng.submit("f", (_X,))
        st = eng.stats()
        assert st["requests"] == 5 == eng.requests
        assert st["per_name"]["f"] == 5
        assert eng.metrics.value("repro_requests_total") == 5
        assert eng.metrics.value("repro_entry_ok_total", "f") == 5
        text = eng.metrics.expose()
        assert "repro_requests_total 5" in text
        assert 'repro_entry_requests_total{entry="f"} 5' in text
        assert "# TYPE repro_request_seconds histogram" in text
        assert eng.check_invariants() == []
        assert st["drift"]["entries"]["f"]["predicted_s"] > 0
    finally:
        eng.shutdown()


def test_unregister_drops_labeled_children():
    eng = _engine()
    try:
        eng.submit("f", (_X,))
        assert eng.per_name == {"f": 1}
        eng.unregister("f")
        assert eng.per_name == {}
        assert 'entry="f"' not in eng.metrics.expose()
    finally:
        eng.shutdown()


def test_drift_triggers_background_plan_refresh():
    """An absurd predicted latency must fire drift and kick the existing
    background re-solve + store-refresh path (the PR's closing loop)."""
    sc = ServeConfig(drift=DriftConfig(sample_every=1, min_samples=3,
                                       ratio_threshold=2.0, cooldown_s=3600.0))
    eng = _engine(sc=sc)
    try:
        eng.note_predicted_latency("f", 1e-12)  # everything looks drifted
        for _ in range(6):
            eng.submit("f", (_X,))
        st = eng.stats()
        assert st["drift"]["triggers"] >= 1
        assert st["drift"]["entries"]["f"]["drifted"] is True
        # the refresh lands asynchronously (backoff before first attempt)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if eng.plan_refreshes >= 1:
                break
            time.sleep(0.05)
        assert eng.stats()["plan_store"]["refreshes"] >= 1
        assert eng.metrics.value("repro_drift_triggers_total") >= 1
        # serving continued throughout: accounting still closes
        assert eng.check_invariants() == []
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Threaded chaos stress: accounting closures under injected faults
# ---------------------------------------------------------------------------
def test_threaded_chaos_accounting_closes():
    cp = ChaosPlan(batch_fail_at=(0,), execute_fail_at=(3, 7))
    sc = ServeConfig(chaos=cp,
                     batching=BatchConfig(max_batch=4, max_wait_s=0.001))
    eng = _engine(sc=sc)
    try:
        n_threads, per_thread = 6, 8
        barrier = threading.Barrier(n_threads)
        futures: list = []
        flock = threading.Lock()
        errors: list[BaseException] = []

        def worker():
            try:
                barrier.wait()
                mine = [eng.submit_async("f", (_X,))
                        for _ in range(per_thread)]
                with flock:
                    futures.extend(mine)
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        for f in futures:
            f.result(timeout=120)               # no dropped futures
        st = eng.stats()["batching"]
        total = n_threads * per_thread
        assert st["enqueued"] == total
        assert st["ok"] + st["fallbacks"] == st["completed"]
        assert (st["completed"] + st["expired"] + st["errors"]
                == st["enqueued"])
        assert st["completed"] == total and st["errors"] == 0
        # the same closures, asserted where they live: the registry
        assert eng.check_invariants() == []
        # chaos really fired (the closures held under faults, not calm)
        resil = eng.stats()["resilience"]["entries"]
        assert st["batch_failures"] >= 1 or any(
            e["fallbacks"] >= 1 for e in resil.values())
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Deadlock regression: stats() vs. a concurrent submit storm
# ---------------------------------------------------------------------------
def test_stats_never_deadlocks_against_submit_storm():
    """The old ``stats()`` assembled nested output while holding the
    engine lock and calling into sub-objects that take their own locks
    (breaker, batcher, program cache) — one inverted acquisition away
    from deadlock.  The rewrite snapshots the registry first and holds
    the engine lock only over plain-data copies; this pins it with a
    storm of submits racing stats()/expose() readers under a watchdog."""
    sc = ServeConfig(batching=BatchConfig(max_batch=4, max_wait_s=0.001))
    eng = _engine(sc=sc)
    try:
        stop = threading.Event()
        errors: list[BaseException] = []

        def submitter():
            try:
                while not stop.is_set():
                    eng.submit("f", (_X,))
                    eng.submit_async("f", (_X,)).result(timeout=60)
            except BaseException as e:
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    st = eng.stats()
                    assert "drift" in st and "requests" in st
                    eng.metrics.expose()
                    eng.check_invariants()
            except BaseException as e:
                errors.append(e)

        threads = ([threading.Thread(target=submitter) for _ in range(3)]
                   + [threading.Thread(target=reader) for _ in range(3)])
        for t in threads:
            t.start()
        time.sleep(2.0)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        stuck = [t for t in threads if t.is_alive()]
        assert not stuck, f"deadlocked threads: {stuck}"
        assert not errors
        # the storm really exercised both paths
        assert eng.requests > 0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Profiler sink: spans on the JAX profiler's clock
# ---------------------------------------------------------------------------
def _profiled(tmp_path, fn) -> list:
    """Run ``fn`` under a JAX profiler session; the trace's ``repro.*``
    host events, with the thread row each is on."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append(NS(name=ev.name[len("repro."):],
                                  row=(plane.name, k), start=ev.start_ns,
                                  end=ev.start_ns + ev.duration_ns,
                                  args=dict(ev.stats)))
    return out


def _inside(child, parent) -> bool:
    return child.row == parent.row and parent.start <= child.start \
        and child.end <= parent.end


def test_span_site_is_null_with_both_sinks_off(tmp_path):
    from repro.obs import trace as T
    t = Tracer(capacity=4, enabled=False)
    assert t.span("x", "test", k=1) is T._NULL
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = t.span("x", "test", k=1)     # the profiler alone makes it live
        with sp:
            pass
    finally:
        jax.profiler.stop_trace()
    assert sp is not T._NULL
    assert t.snapshot() == []             # ... and the ring stays off
    assert t.span("x", "test") is T._NULL


def test_obs_imports_without_jax():
    """Solver worker processes import ``repro.obs`` through the solver;
    neither the import nor a span site may pull jax in."""
    import repro.obs
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        repro.obs.__file__)))
    code = "\n".join([
        "import sys",
        "sys.modules['jax'] = None",      # any import of jax now fails
        "import repro.obs as o",
        "t = o.Tracer(enabled=True)",
        "with t.span('x', 'test', k=1) as sp:",
        "    sp.set(j=2)",
        "assert [s.name for s in t.snapshot()] == ['x']",
        "assert o.Tracer(enabled=False).span('y') is o.trace._NULL",
        "assert not [m for m, v in sys.modules.items()",
        "            if v is not None and m.split('.')[0] == 'jax']",
    ])
    env = {k: v for k, v in os.environ.items() if k != "REPRO_OBS_TRACE"}
    env["PYTHONPATH"] = src
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)


def test_plan_submit_spans_reach_the_profiler_trace(tmp_path):
    """Two submits under a profiler session: the first builds its
    program and is canary-sampled (a timed run), the second is a warm
    dispatch.  Every span of a submit carries its ``rid`` and nests in
    its ``request/submit`` on the same thread."""
    from repro.codegen import clear_program_cache
    clear_program_cache()
    eng = _engine(ServeConfig(canary_every=2,
                              drift=DriftConfig(enabled=False)))
    try:
        spans = _profiled(tmp_path, lambda: [eng.submit("f", (_X,))
                                             for _ in range(2)])
    finally:
        eng.shutdown()
    subs = sorted((s for s in spans if s.name == "request/submit"),
                  key=lambda s: s.start)
    assert len(subs) == 2 and subs[0].args["rid"] != subs[1].args["rid"]
    kids = []
    for sub in subs:
        mine = [s for s in spans if s is not sub
                and s.args.get("rid") == sub.args["rid"]]
        assert all(_inside(s, sub) for s in mine)
        assert all(s.args["entry"] == "f" for s in mine + [sub])
        kids.append({s.name: s for s in mine})
    cold, warm = kids
    assert set(cold) == {"request/resolve", "request/execute",
                         "request/sync", "request/canary"}
    assert set(warm) == {"request/resolve", "request/execute"}
    assert cold["request/resolve"].args["miss"] == 1
    assert warm["request/resolve"].args["miss"] == 0
    assert cold["request/sync"].args["reason"] == "canary"
    # dispatch only: the timed run's sync follows the execute span
    assert cold["request/execute"].end <= cold["request/sync"].start
    assert eng.metrics.value("repro_program_builds_total") == 1
    assert eng.metrics.value("repro_request_syncs_total", "canary") == 1


def test_generate_spans_reach_the_profiler_trace(tmp_path):
    """One ``Engine.generate`` of 3 tokens: a prefill span, then one
    ``decode/token`` per token, each the parent of one sync, one
    dispatch and one sample, all tagged with the call's ``gid``."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import smoke
    from repro.models import model as M
    from repro.serve.engine import Engine
    cfg = dataclasses.replace(smoke(get_config("qwen3-0.6b")),
                              compute_dtype="float32",
                              kv_cache_dtype="float32")
    eng = Engine(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                 ServeConfig(max_len=16))
    prompts = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    eng.generate(prompts, 3)              # compiles outside the trace
    spans = _profiled(tmp_path, lambda: eng.generate(prompts, 3))
    (prefill,) = [s for s in spans if s.name == "generate/prefill"]
    gid = prefill.args["gid"]
    assert prefill.args["batch"] == 2
    tokens = sorted((s for s in spans if s.name == "decode/token"),
                    key=lambda s: s.start)
    assert [s.args["t"] for s in tokens] == [0, 1, 2]
    assert prefill.end <= tokens[0].start
    for tok in tokens:
        assert tok.args["gid"] == gid and tok.args["batch"] == 2
        kids = [s for s in spans if s.name.startswith("decode/")
                and s is not tok and s.args["t"] == tok.args["t"]]
        assert sorted(s.name for s in kids) == [
            "decode/dispatch", "decode/sample", "decode/sync"]
        assert all(_inside(s, tok) and s.args["gid"] == gid for s in kids)


def test_train_spans_reach_the_profiler_trace(tmp_path):
    """Three steps of ``train``, saved after the second and the last: each
    step's ``train/data`` wait, then its ``train/step`` with one
    ``train/sync`` inside, all tagged with the step; the save's stall is
    ``train/checkpoint``.  The counters count steps and tokens."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import smoke
    from repro.obs import default_registry
    from repro.train.loop import TrainConfig, train
    cfg = dataclasses.replace(smoke(get_config("qwen1.5-32b")), n_layers=2)
    tc = TrainConfig(total_steps=3, checkpoint_every=2,
                     checkpoint_dir=str(tmp_path / "ck"), global_batch=4,
                     seq_len=16, log_every=100)
    train(cfg, dataclasses.replace(tc, checkpoint_dir=str(tmp_path / "w")))
    # ... compiled outside the trace
    reg = default_registry()
    before = [reg.value(k) for k in ("repro_train_steps_total",
                                     "repro_train_tokens_total")]
    spans = _profiled(tmp_path / "trace", lambda: train(cfg, tc))
    after = [reg.value(k) for k in ("repro_train_steps_total",
                                    "repro_train_tokens_total")]
    assert [a - b for a, b in zip(after, before)] == [3, 3 * 4 * 16]
    steps = sorted((s for s in spans if s.name == "train/step"),
                   key=lambda s: s.start)
    assert [s.args["step"] for s in steps] == [0, 1, 2]
    for st in steps:
        (sync,) = [s for s in spans if s.name == "train/sync"
                   and s.args["step"] == st.args["step"]]
        (data,) = [s for s in spans if s.name == "train/data"
                   and s.args["step"] == st.args["step"]]
        assert _inside(sync, st)
        assert data.row == st.row and data.end <= st.start
    ckpt = [s for s in spans if s.name == "train/checkpoint"]
    assert sorted(s.args["step"] for s in ckpt) == [1, 2]
    for c in ckpt:
        assert steps[c.args["step"]].end <= c.start
