"""The program's spans in the trace (``bench.harness.spans``) and the
readers that use them: spans kept and clipped to the window, self time
under children, idle gaps named by harness and program spans, the span
metrics and their count checks; and the recorded 3mm trace, which holds
no program spans, reduced exactly as the parent reducer reduced it."""
import glob
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench.harness import core, spans, xplane
from bench.harness.peaks import PEAKS
from bench.harness.record import Record

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def plane(name, *lines):
    return NS(name=name, lines=[NS(name=n, events=evs) for n, evs in lines])


def made_up():
    """A window [100, 1100) with two requests on the main thread and a
    background re-solve on a second thread that outlasts the window;
    device busy 100-300, 700-800 and 1000-1100."""
    main = ("python3", [
        ev("bench.window", 100, 1100),
        ev("repro.request/submit", 50, 90, rid=0),       # before: dropped
        ev("bench.request", 100, 600),
        ev("repro.request/submit", 110, 560, rid=1),
        ev("repro.request/resolve", 120, 400, rid=1, miss=1),
        ev("repro.request/execute", 420, 450, rid=1),
        ev("repro.request/sync", 460, 540, rid=1, reason="drift"),
        ev("bench.request", 600, 1100),
        ev("repro.request/submit", 610, 1090, rid=2),
        ev("repro.request/execute", 620, 640, rid=2)])
    bg = ("repro-plan-refresh", [
        ev("repro.plan/refresh", 200, 1200, entry="m")])  # clipped
    dev = plane("/device:TPU:0", ("XLA Modules", []), ("XLA Ops", [
        ev("fusion.1", 100, 300), ev("fusion.2", 700, 800),
        ev("fusion.3", 1000, 1100)]))
    return [plane("/host:CPU", main, bg), dev]


def test_program_spans_kept_and_clipped_to_the_window():
    kept = spans.program_spans(made_up())
    assert [sp.name for sp in kept] == [
        "repro.request/submit", "repro.request/resolve",
        "repro.plan/refresh", "repro.request/execute",
        "repro.request/sync", "repro.request/submit",
        "repro.request/execute"]
    sub1, resolve, refresh = kept[:3]
    assert (sub1.start_s, sub1.end_s) == pytest.approx((10e-9, 460e-9))
    assert resolve.args == {"rid": 1, "miss": 1}
    assert (refresh.start_s, refresh.end_s) == pytest.approx(
        (100e-9, 1000e-9))                             # clipped
    assert refresh.thread == ("/host:CPU", 1)
    assert {sp.thread for sp in kept if sp is not refresh} == {
        ("/host:CPU", 0)}


def test_self_seconds_subtracts_children_on_the_same_thread():
    kept = spans.program_spans(made_up())
    # submit 1: 450 ns less its 80 ns sync; submit 2: 480 ns, no sync
    assert spans.self_seconds(kept, "repro.request/submit",
                              "repro.request/sync") == pytest.approx(
        [370e-9, 480e-9])
    other = spans.Span("repro.request/sync", ("/host:CPU", 1), 0.0, 1.0, {})
    assert spans.self_seconds(kept + [other], "repro.request/submit",
                              "repro.request/sync") == pytest.approx(
        [370e-9, 480e-9])


def test_gaps_named_by_program_spans_and_the_background_thread():
    # holes: 300-700 (middle 500: resolve ended, sync covers it) and
    # 800-1000 (middle 900: submit 2), both under the background refresh
    assert spans.named_gaps(made_up()) == [
        ("repro.request/sync & repro.plan/refresh", pytest.approx(400e-9)),
        ("repro.request/submit & repro.plan/refresh",
         pytest.approx(200e-9))]


def test_gap_named_by_harness_span_or_none():
    host = plane("/host:CPU", ("python3", [
        ev("bench.window", 0, 100), ev("bench.request", 0, 40)]))
    dev = plane("/device:TPU:0", ("XLA Ops", [ev("f", 40, 60)]))
    assert spans.named_gaps([host, dev]) == [
        ("bench.request", pytest.approx(40e-9)),
        ("no span", pytest.approx(40e-9))]


def test_attach_adds_spans_and_named_gaps():
    planes = made_up()
    r = spans.attach(xplane.reduce(planes, chips=1), planes)
    assert len(r.spans) == 7
    assert r.gaps[0][0] == "repro.request/sync & repro.plan/refresh"
    assert r.breakdown()["idle_gaps"][0][0] == r.gaps[0][0]


def run_of(kept, **kw):
    base = dict(trace=NS(spans=kept), record=Record(), mix={})
    base.update(kw)
    return NS(**base)


def read(name, run):
    return core.metric_reader(name)(run)


def test_submit_host_ms_reads_self_time_per_request():
    kept = spans.program_spans(made_up())
    r = run_of(kept, record=Record(attempted=2))
    assert read("submit_host_ms.plan", r) == pytest.approx(
        1e3 * (370e-9 + 480e-9) / 2)
    assert read("submit_host_ms.plan", run_of(
        kept, record=Record(attempted=3))) is None
    assert read("submit_host_ms.plan", run_of(
        [], record=Record(attempted=0))) is None
    # a reduced trace without the program's spans: nothing to read
    assert read("submit_host_ms.plan", NS(trace=None,
                                          record=Record())) is None


def test_decode_host_ms_reads_self_time_per_token():
    def tok(t, start, sync):
        th = ("/host:CPU", 0)
        return [spans.Span("repro.decode/token", th, start, start + 3.0,
                           {"t": t}),
                spans.Span("repro.decode/sync", th, start, start + sync,
                           {"t": t})]
    kept = tok(0, 0.0, 2.0) + tok(1, 3.0, 1.0)
    rec = Record(batches=[{"prompt": 8}])
    r = run_of(kept, record=rec, mix={"new_tokens": 2})
    assert read("decode_host_ms.model", r) == pytest.approx(1e3 * 1.5)
    r = run_of(kept, record=rec, mix={"new_tokens": 3})
    assert read("decode_host_ms.model", r) is None
    assert read("decode_host_ms.model", NS(trace=None, record=rec,
                                           mix={"new_tokens": 2})) is None


def test_program_spans_of_a_real_profiler_trace(tmp_path):
    """The spans a ``PlanEngine`` forwards to a CPU profiler session, as
    this module reads them: one submit per request, each with its own
    ``rid``, its self time no longer than its span."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    from repro.core import SolverOptions
    from repro.obs import DriftConfig
    from repro.serve import PlanEngine, ServeConfig

    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 16), jnp.float32)
    eng = PlanEngine(sc=ServeConfig(drift=DriftConfig(enabled=False)))
    eng.register_function("mm", lambda a: a @ w, (x,),
                          solver_opts=SolverOptions(time_budget_s=0.5))
    try:
        eng.submit("mm", (x,))
        jax.profiler.start_trace(str(tmp_path))
        try:
            with TraceAnnotation("bench.window"):
                for _ in range(5):
                    jax.block_until_ready(eng.submit("mm", (x,)))
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    kept = spans.program_spans(ProfileData.from_file(path).planes)
    subs = [sp for sp in kept if sp.name == "repro.request/submit"]
    assert len(subs) == 5 and len({sp.args["rid"] for sp in subs}) == 5
    host = spans.self_seconds(kept, "repro.request/submit",
                              "repro.request/sync")
    assert all(0 < h <= sp.end_s - sp.start_s for h, sp in zip(host, subs))
    assert {sp.name for sp in kept} == {
        "repro.request/submit", "repro.request/resolve",
        "repro.request/execute"}


def test_gc_collections_become_harness_spans(tmp_path):
    """``bench/program_spans.py`` traces Python's collections of
    generations 1 and 2, so an idle gap inside one is named ``bench.gc``."""
    import gc

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from bench import program_spans
    on_gc = program_spans.trace_gc()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with TraceAnnotation("bench.window"):
                gc.collect(0)
                gc.collect(2)
        finally:
            jax.profiler.stop_trace()
    finally:
        gc.callbacks.remove(on_gc)
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = [dict(ev.stats) for p in ProfileData.from_file(path).planes
             for line in p.lines for ev in line.events
             if ev.name == "bench.gc"]
    assert {"generation": 2} in found
    assert {"generation": 0} not in found


RECORDED = os.path.join(DATA, "mm3-xl-closed.xplane.pb")


def test_recorded_trace_reduces_as_before():
    """Every field ``xplane.reduce`` gives the recorded 3mm trace, and the
    four metrics read from it, as the parent reducer gave them (pinned in
    ``mm3-xl-closed.reduced.json``); the trace holds no program spans, so
    the program-span reduction names its gaps the same."""
    with open(os.path.join(DATA, "mm3-xl-closed.reduced.json")) as f:
        want = json.load(f)
    r = xplane.reduce_file(RECORDED, chips=1)
    assert (r.window_s, r.busy_s, r.chips) == (
        want["window_s"], want["busy_s"], want["chips"])
    assert r.modules == {k: [c, pytest.approx(t)]
                         for k, (c, t) in want["modules"].items()}
    ops = sorted([m, o, c, t] for (m, o), (c, t) in r.ops.items())
    assert [op[:3] for op in ops] == [op[:3] for op in want["ops"]]
    assert [op[3] for op in ops] == pytest.approx(
        [op[3] for op in want["ops"]])
    assert r.gaps == [(n, pytest.approx(s)) for n, s in want["gaps"]]
    mm3 = core.load_json(f"{core.BENCH}/configs/polybench-3mm-xl.json")
    run = NS(trace=r, record=Record(window_s=r.window_s, completed=15,
                                    optimized=15),
             config=mm3, peaks=PEAKS["TPU v5 lite"],
             entry=NS(plan_latency_s=0.000219))
    assert {n: read(n, run) for n in want["metrics"]} == pytest.approx(
        want["metrics"])
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(RECORDED).planes)
    assert spans.program_spans(planes) == []
    assert spans.named_gaps(planes) == r.gaps
