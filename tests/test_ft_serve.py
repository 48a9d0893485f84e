"""Fault-tolerant serving: breakers, chaos injection, graceful degradation.

Every failure here is injected deterministically (``ChaosPlan``, injectable
breaker clocks, fake calibration measurements), so the degradation paths —
fallback-to-jit, quarantine, background re-solve, straggler rotation,
admission rejection — are pinned down bit-for-bit with no real faults and
no timing flakes.
"""
from __future__ import annotations

import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.codegen import (allclose, clear_program_cache, random_inputs,
                           reference_executor)
from repro.core import SolverOptions, THREE_SLICE, polybench, solve
from repro.ft import (BackoffPolicy, BreakerState, ChaosPlan, CircuitBreaker,
                      DeadlineExceeded, EngineOverloaded, InjectedFailure,
                      StragglerConfig, atomic_write_json, load_json,
                      payload_checksum, quarantine_file, scrub_cache_dir)
from repro.ft.artifacts import ArtifactError
from repro.serve import PlanEngine, ServeConfig


def _solved(name: str, budget: float = 1.0):
    g = polybench.build(name)
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=budget))
    return g, plan


def _mm_inputs(seed: int = 0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(6, 5)).astype(np.float32))
    return a, b


FAST = dict(resolve_backoff_s=0.01, resolve_backoff_mult=1.0,
            resolve_max_retries=4)


def _wait_recovered(eng, name, timeout=30.0):
    assert eng._health_for(name).recovered_event.wait(timeout), \
        f"background re-solve of {name!r} did not finish in {timeout}s"


# ---------------------------------------------------------------------------
# Circuit breaker state machine (injected clock — no real sleeping)
# ---------------------------------------------------------------------------
def test_breaker_open_half_open_close_transitions():
    clock = {"t": 0.0}
    br = CircuitBreaker(threshold=2, reset_s=10.0, clock=lambda: clock["t"])
    assert br.state is BreakerState.CLOSED and br.allow()
    assert not br.record_failure()              # 1/2: still closed
    assert br.record_failure()                  # 2/2: opened now
    assert br.state is BreakerState.OPEN
    assert not br.allow()                       # quarantined
    clock["t"] = 9.9
    assert not br.allow()                       # reset_s not elapsed
    clock["t"] = 10.0
    assert br.allow()                           # half-open: one probe
    assert br.state is BreakerState.HALF_OPEN
    assert not br.allow()                       # second probe refused
    br.record_success()
    assert br.state is BreakerState.CLOSED and br.allow()
    assert br.stats()["transitions"] == ["open", "half_open", "closed"]


def test_breaker_half_open_failure_reopens():
    clock = {"t": 0.0}
    br = CircuitBreaker(threshold=1, reset_s=5.0, clock=lambda: clock["t"])
    assert br.record_failure() and br.state is BreakerState.OPEN
    clock["t"] = 5.0
    assert br.allow() and br.state is BreakerState.HALF_OPEN
    # a failed probe re-opens AND reports it, so recovery is re-triggered
    assert br.record_failure()
    assert br.state is BreakerState.OPEN
    clock["t"] = 9.0                    # reset clock restarted at t=5
    assert not br.allow()
    clock["t"] = 10.0
    assert br.allow()


def test_breaker_force_open_and_thread_safety():
    br = CircuitBreaker(threshold=100, reset_s=1e9)
    br.force_open()
    assert br.state is BreakerState.OPEN and not br.allow()
    hits = []
    br2 = CircuitBreaker(threshold=4, reset_s=1e9)

    def hammer(_):
        if br2.record_failure():
            hits.append(1)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(hits) == 1               # exactly one thread opened it


def test_backoff_schedule_is_deterministic_and_capped():
    p = BackoffPolicy(base_s=0.1, mult=2.0, max_s=0.5, retries=5)
    assert p.delays() == [0.1, 0.2, 0.4, 0.5, 0.5]
    assert p.delays() == p.delays()     # pure


# ---------------------------------------------------------------------------
# ChaosPlan determinism
# ---------------------------------------------------------------------------
def test_chaos_plan_fires_each_index_once_per_name():
    cp = ChaosPlan(compile_fail_at=(1,), execute_fail_at=(0,))
    cp.on_compile("a")                          # index 0: clean
    with pytest.raises(InjectedFailure):
        cp.on_compile("a")                      # index 1: fires once
    cp.on_compile("a")                          # index 2: clean again
    with pytest.raises(InjectedFailure):
        cp.on_execute("a")
    cp.on_execute("a")
    assert ("compile", "a", 1) in cp.events
    assert ("execute", "a", 0) in cp.events


def test_chaos_plan_only_restricts_entry_and_corrupts_floats():
    cp = ChaosPlan(corrupt_at=(0,), only="victim")
    out = {"x": jnp.ones((2, 2)), "i": jnp.arange(3)}
    same = cp.corrupt_outputs("bystander", out)
    assert same is out                          # wrong name: untouched
    bad = cp.corrupt_outputs("victim", out)
    assert bool(jnp.isnan(bad["x"]).all())
    assert bad["i"].dtype == out["i"].dtype     # ints pass through
    assert cp.corrupt_outputs("victim", out) is out     # fired already


def test_chaos_corrupt_file_modes(tmp_path):
    p = tmp_path / "f.json"
    p.write_text('{"ok": 1}')
    ChaosPlan.corrupt_file(str(p))
    with pytest.raises(Exception):
        json.loads(p.read_text(errors="ignore") or "x")
    p.write_text('{"ok": 1}')
    ChaosPlan.corrupt_file(str(p), mode="truncate")
    assert os.path.getsize(p) == 0


# ---------------------------------------------------------------------------
# Checksummed atomic artifacts
# ---------------------------------------------------------------------------
def test_artifact_checksum_round_trip_and_detection(tmp_path):
    p = str(tmp_path / "a.json")
    atomic_write_json(p, {"x": [1, 2], "y": "z"})
    d = load_json(p, require_checksum=True)
    assert d == {"x": [1, 2], "y": "z"}
    assert payload_checksum(d) == payload_checksum({"y": "z", "x": [1, 2]})
    # flip a byte inside the payload: checksum must catch it
    raw = open(p).read().replace('"z"', '"q"')
    open(p, "w").write(raw)
    with pytest.raises(ArtifactError):
        load_json(p)
    ChaosPlan.corrupt_file(p)               # non-JSON garbage
    with pytest.raises(ArtifactError):
        load_json(p)


def test_quarantine_and_scrub(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("garbage")
    moved = quarantine_file(str(p), reason="test")
    assert not p.exists() and moved.endswith(".corrupt")
    (tmp_path / "empty.bin").write_bytes(b"")
    (tmp_path / "x.tmp123").write_bytes(b"partial")
    (tmp_path / "keep.bin").write_bytes(b"data")
    removed = scrub_cache_dir(str(tmp_path))
    assert len(removed) == 2
    assert (tmp_path / "keep.bin").exists()


def test_persistent_cache_metadata_survives_corruption(tmp_path):
    import jax

    from repro.codegen import enable_persistent_cache
    from repro.codegen import program as program_mod
    d = str(tmp_path / "aot")
    old_dir = program_mod._persistent_dir
    try:
        enable_persistent_cache(d)
        meta = os.path.join(d, "repro-cache-metadata.json")
        doc = load_json(meta, require_checksum=True)
        assert doc["schema"] == 1
        ChaosPlan.corrupt_file(meta)
        enable_persistent_cache(d)          # quarantine + rewrite, no crash
        assert os.path.exists(meta + ".corrupt")
        assert load_json(meta, require_checksum=True)["schema"] == 1
        # crash leftovers in the cache dir are scrubbed on (re-)enable
        open(os.path.join(d, "entry.tmp.123"), "wb").close()
        enable_persistent_cache(d)
        assert not os.path.exists(os.path.join(d, "entry.tmp.123"))
    finally:
        program_mod._persistent_dir = old_dir
        jax.config.update("jax_compilation_cache_dir", old_dir)


def test_corrupted_calibration_profile_is_regenerated(tmp_path, monkeypatch):
    from repro.calibrate import cached_profile, calibrate, profile_path
    from test_calibrate import FakeBench
    monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
    calibrate(bench=FakeBench())
    path = profile_path("fake", 1, 2)
    ChaosPlan.corrupt_file(path)
    # quiet path: quarantines, returns None, never raises
    assert cached_profile(path=path) is None
    assert os.path.exists(path + ".corrupt") and not os.path.exists(path)
    # explicit path: re-measures and regenerates a valid profile
    prof = calibrate(bench=FakeBench())
    assert prof.dispatch_s == 5e-5
    assert cached_profile(path=path) is not None


# ---------------------------------------------------------------------------
# Graceful degradation: fallback equals the jax.jit oracle
# ---------------------------------------------------------------------------
def test_compile_failure_falls_back_then_recovers():
    clear_program_cache()
    a, b = _mm_inputs()
    chaos = ChaosPlan(compile_fail_at=(0,))
    eng = PlanEngine(impl="xla", sc=ServeConfig(chaos=chaos, **FAST))
    eng.register_function("mm", lambda x, y: x @ y, (a, b),
                          solver_opts=SolverOptions(time_budget_s=1.0))
    expect = np.asarray(a @ b)
    out = eng.submit("mm", (a, b))          # injected compile failure
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4)
    h = eng.stats()["resilience"]["entries"]["mm"]
    assert h["failures"] == 1 and h["fallbacks"] == 1 and h["ok"] == 0
    # one failure < threshold: breaker still closed, next submit optimized
    assert h["state"] == "ok"
    out2 = eng.submit("mm", (a, b))
    np.testing.assert_allclose(np.asarray(out2), expect, rtol=2e-4)
    h = eng.stats()["resilience"]["entries"]["mm"]
    assert h["ok"] == 1
    assert h["ok"] + h["fallbacks"] == eng.stats()["per_name"]["mm"]


def test_repeated_failures_quarantine_and_background_resolve():
    clear_program_cache()
    a, b = _mm_inputs()
    chaos = ChaosPlan(execute_fail_at=(0, 1), only="mm")
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        chaos=chaos, breaker_threshold=2, breaker_reset_s=1e9, **FAST))
    eng.register_function("mm", lambda x, y: x @ y, (a, b),
                          solver_opts=SolverOptions(time_budget_s=1.0))
    expect = np.asarray(a @ b)
    for _ in range(2):                      # both injected failures
        out = eng.submit("mm", (a, b))
        np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4)
    h = eng.stats()["resilience"]["entries"]["mm"]
    assert h["state"] == "quarantined" and h["failures"] == 2
    # quarantined: submits keep answering correctly via the fallback
    out = eng.submit("mm", (a, b))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4)
    _wait_recovered(eng, "mm")
    h = eng.stats()["resilience"]["entries"]["mm"]
    assert h["state"] == "ok" and h["recovered"] == 1
    assert h["resolve_attempts"] >= 1
    out = eng.submit("mm", (a, b))          # optimized path again
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4)
    assert eng.stats()["resilience"]["entries"]["mm"]["ok"] >= 1
    eng.shutdown()


def test_canary_catches_miscompile_and_quarantines_immediately():
    """Corrupted kernel output (NaN injected post-execution) never reaches
    the caller: the canary catches it, the entry quarantines in ONE
    failure (miscompiles are never transient), the request is re-served
    by the oracle path."""
    clear_program_cache()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    ref = reference_executor(g)(ins)
    chaos = ChaosPlan(corrupt_at=(0,))
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        chaos=chaos, canary_every=1, breaker_reset_s=1e9, **FAST))
    eng.register("m", g, plan)
    out = eng.submit("m", ins)
    assert all(allclose(out[k], ref[k]) for k in ref)   # correct anyway
    h = eng.stats()["resilience"]["entries"]["m"]
    assert h["state"] == "quarantined"
    assert h["canaries"] == 1 and h["failures"] == 1
    assert "MiscompileError" in h["last_error"]
    _wait_recovered(eng, "m")
    out = eng.submit("m", ins)
    assert all(allclose(out[k], ref[k]) for k in ref)
    h = eng.stats()["resilience"]["entries"]["m"]
    assert h["state"] == "ok" and h["ok"] == 1
    eng.shutdown()


def test_canary_validates_function_entries_against_jit_oracle():
    clear_program_cache()
    a, b = _mm_inputs()
    eng = PlanEngine(impl="xla", sc=ServeConfig(canary_every=1))
    eng.register_function("mm", lambda x, y: x @ y, (a, b),
                          solver_opts=SolverOptions(time_budget_s=1.0))
    for _ in range(3):
        out = eng.submit("mm", (a, b))
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                                   rtol=2e-4)
    h = eng.stats()["resilience"]["entries"]["mm"]
    assert h["canaries"] == 3 and h["canary_failures"] == 0
    assert h["state"] == "ok" and h["ok"] == 3


def test_registration_failure_degrades_to_plain_jit():
    """A function the frontend cannot serve (lowers to an empty graph)
    still registers: every submit is answered by jax.jit, stats() shows
    the entry as fallback, and re-solve attempts are bounded."""
    clear_program_cache()
    x = jnp.arange(6, dtype=jnp.float32)
    eng = PlanEngine(impl="xla", sc=ServeConfig(**FAST))
    tf = eng.register_function("ident", lambda v: v, (x,))
    assert tf is None                       # degraded registration
    out = eng.submit("ident", (x,))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    h = eng.stats()["resilience"]["entries"]["ident"]
    assert h["state"] == "fallback" and h["fallbacks"] == 1
    # without fallback the same registration raises instead
    eng2 = PlanEngine(impl="xla", sc=ServeConfig(fallback=False))
    with pytest.raises(ValueError):
        eng2.register_function("ident", lambda v: v, (x,))
    eng.shutdown()


def test_failed_submit_does_not_corrupt_accounting():
    """The first satellite fix: a failure mid-submit must leave request
    counters, per-name counts and pool cursors conservation-clean."""
    clear_program_cache()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    chaos = ChaosPlan(execute_fail_at=(1, 3))
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        pool_size=2, chaos=chaos, breaker_threshold=10))
    eng.register("m", g, plan)
    warm = eng.stats()["requests"]
    for _ in range(6):
        eng.submit("m", ins)
    s = eng.stats()
    assert s["requests"] == warm + 6
    h = s["resilience"]["entries"]["m"]
    assert h["failures"] == 2 and h["fallbacks"] == 2
    assert h["ok"] + h["fallbacks"] == s["per_name"]["m"]
    # pool cursor advanced exactly once per *completed* optimized
    # execution — injected execute failures fire before dispatch
    pool = s["pools"]["m/xla"]
    assert pool["calls"] == warm + h["ok"]


def test_user_errors_raise_and_are_not_counted():
    clear_program_cache()
    a, b = _mm_inputs()
    eng = PlanEngine(impl="xla")
    eng.register_function("mm", lambda x, y: x @ y, (a, b),
                          solver_opts=SolverOptions(time_budget_s=1.0))
    before = eng.stats()["per_name"].get("mm", 0)
    with pytest.raises(KeyError):
        eng.submit("nope", (a, b))          # unknown entry: caller bug
    with pytest.raises((TypeError, ValueError)):
        eng.submit("mm", (a,))              # wrong arity: caller bug
    s = eng.stats()
    # neither request was counted
    assert s["per_name"].get("mm", 0) == before
    assert s["resilience"]["entries"]["mm"]["failures"] == 0


# ---------------------------------------------------------------------------
# Admission control + deadlines
# ---------------------------------------------------------------------------
def test_admission_rejects_when_inflight_depth_full():
    clear_program_cache()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        max_inflight=1, admission_timeout_s=0.02))
    eng.register("m", g, plan)
    eng.warmup("m", ins)
    assert eng._inflight_sem.acquire(timeout=1.0)   # occupy the only slot
    try:
        with pytest.raises(EngineOverloaded):
            eng.submit("m", ins)
        with pytest.raises(DeadlineExceeded):
            eng.submit("m", ins, deadline_s=0.005)
    finally:
        eng._inflight_sem.release()
    out = eng.submit("m", ins)              # slot free: served normally
    ref = reference_executor(g)(ins)
    assert all(allclose(out[k], ref[k]) for k in ref)
    r = eng.stats()["resilience"]
    assert r["rejected"] == 1 and r["deadline_rejected"] == 1


def test_deadline_miss_is_counted_not_fatal():
    clear_program_cache()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    eng = PlanEngine(impl="xla", sc=ServeConfig(deadline_s=1e-9))
    eng.register("m", g, plan)
    out = eng.submit("m", ins)              # admitted; finishes late
    ref = reference_executor(g)(ins)
    assert all(allclose(out[k], ref[k]) for k in ref)
    assert eng.stats()["resilience"]["deadline_misses"] >= 1


# ---------------------------------------------------------------------------
# Straggler rotation: a persistently slow pool clone leaves round-robin
# ---------------------------------------------------------------------------
def test_slow_clone_is_rotated_out_of_round_robin():
    clear_program_cache()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    chaos = ChaosPlan(slow_clone=1, slow_s=0.05)
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        pool_size=2, chaos=chaos,
        straggler=StragglerConfig(threshold=1.5, patience=2, min_steps=1,
                                  ema=0.5)))
    # The monitor sees each run's time on the engine's clock.  On the wall
    # clock, warm-up's first run of clone 0 includes its compile (0.2 s
    # alone, longer under parallel test workers): that seeds clone 0's
    # EMA so high that the 50 ms clone stays under 1.5x it for most of
    # the eight submits, and is flagged twice in a row only when the
    # compile was quick.  On a clock that only the injected delay moves,
    # clone 1 reads 50 ms and clone 0 nothing, every time.
    now = [0.0]
    eng._clock = lambda: now[0]
    eng._sleep = lambda s: now.__setitem__(0, now[0] + s)
    eng.register("m", g, plan)
    eng.warmup("m", ins)
    for _ in range(8):
        eng.submit("m", ins)
    s = eng.stats()
    assert s["pools"]["m/xla"]["disabled_clones"] == [1]
    assert s["resilience"]["entries"]["m"]["rotated_clones"] == [1]
    # post-rotation submits all land on the healthy clone and stay correct
    ref = reference_executor(g)(ins)
    out = eng.submit("m", ins)
    assert all(allclose(out[k], ref[k]) for k in ref)


# ---------------------------------------------------------------------------
# The acceptance scenario: three faults in one run, zero wrong answers
# ---------------------------------------------------------------------------
def test_chaos_run_compile_fail_miscompile_corrupt_calibration(
        tmp_path, monkeypatch):
    from repro.calibrate import cached_profile, calibrate, profile_path
    from test_calibrate import FakeBench
    monkeypatch.setenv("REPRO_CALIBRATION_DIR", str(tmp_path))
    calibrate(bench=FakeBench())
    cal_path = profile_path("fake", 1, 2)
    ChaosPlan.corrupt_file(cal_path)        # fault 1: torn calibration
    # the quiet profile-load path hits the torn file first: it must be
    # quarantined and reported absent, never crash the consumer
    assert cached_profile(path=cal_path) is None
    assert os.path.exists(cal_path + ".corrupt")
    regenerated = calibrate(bench=FakeBench())      # cold path regenerates
    assert regenerated.dispatch_s == 5e-5

    clear_program_cache()
    a, b = _mm_inputs()
    g, plan = _solved("2-madd")
    ins = random_inputs(g, seed=0)
    ref = reference_executor(g)(ins)
    expect_mm = np.asarray(a @ b)
    chaos = ChaosPlan(compile_fail_at=(0,),   # fault 2: compile failure
                      corrupt_at=(0,))        # fault 3: miscompile
    eng = PlanEngine(impl="xla", sc=ServeConfig(
        chaos=chaos, canary_every=1, breaker_threshold=1,
        breaker_reset_s=1e9, **FAST))
    # corrupted profile must not crash registration's solve path
    eng.register_function("mm", lambda x, y: x @ y, (a, b),
                          solver_opts=SolverOptions(time_budget_s=1.0))
    eng.register("m", g, plan)

    for i in range(4):                      # every submit answers correctly
        out = eng.submit("mm", (a, b))
        np.testing.assert_allclose(np.asarray(out), expect_mm, rtol=2e-4)
        out = eng.submit("m", ins)
        assert all(allclose(out[k], ref[k]) for k in ref)

    s = eng.stats()["resilience"]["entries"]
    assert s["mm"]["failures"] >= 1         # compile fault fired + fell back
    assert s["m"]["canary_failures"] >= 0 and s["m"]["failures"] >= 1
    assert {("compile", "mm", 0), ("corrupt", "m", 0)} <= set(chaos.events)
    # the miscompiled entry quarantined, then the breaker closed again
    # after background re-solve validated a rebuilt program
    _wait_recovered(eng, "m")
    assert eng.stats()["resilience"]["entries"]["m"]["state"] == "ok"
    out = eng.submit("m", ins)
    assert all(allclose(out[k], ref[k]) for k in ref)
    # conservation: every admitted request landed in exactly one bucket
    s = eng.stats()
    for name in ("mm", "m"):
        h = s["resilience"]["entries"][name]
        assert h["ok"] + h["fallbacks"] == s["per_name"][name]
    eng.shutdown()
