"""Integration tests: persistent plan store (repro/store) + parallel
solver sweep (SolverOptions.workers) — the cold-solve-off-the-request-path
PR."""
from __future__ import annotations

import os

import pytest

from repro.core import THREE_SLICE, Hardware, SolverOptions, polybench, solve
from repro.core.fingerprint import (graph_fingerprint, hardware_fingerprint,
                                    plan_fingerprint,
                                    solver_options_fingerprint)
from repro.core.plan import ExecutionPlan
from repro.store import PlanStore, default_store, set_default_dir

FAST = SolverOptions(time_budget_s=10.0)


@pytest.fixture(scope="module")
def atax_plan():
    g = polybench.build("atax")
    return g, solve(g, THREE_SLICE, FAST, store=None)


# ---------------------------------------------------------------------------
# Plan serialization round-trip
# ---------------------------------------------------------------------------
def test_plan_jsonable_round_trip_is_exact(atax_plan):
    g, plan = atax_plan
    back = ExecutionPlan.from_jsonable(plan.to_jsonable())
    assert back.graph_name == plan.graph_name
    assert back.latency_s == plan.latency_s
    assert back.useful_flops == plan.useful_flops
    assert set(back.configs) == set(plan.configs)
    for tid, cfg in plan.configs.items():
        b = back.configs[tid]
        assert b.perm == cfg.perm
        assert b.slice_id == cfg.slice_id
        assert {k: t.tile for k, t in b.tiles.items()} == \
            {k: t.tile for k, t in cfg.tiles.items()}
        assert b.placements == cfg.placements
        assert b.to_jsonable() == cfg.to_jsonable()
    for tid, rep in plan.reports.items():
        assert back.reports[tid] == rep
    # fingerprints are content hashes: the round-tripped plan is the
    # same plan
    assert plan_fingerprint(back) == plan_fingerprint(plan)
    # provenance flags are runtime-only, never persisted
    assert "store_hit" not in plan.to_jsonable()
    assert back.store_hit is False and back.stale_hw is False


def test_fingerprints_are_stable_and_discriminating(atax_plan):
    g, _ = atax_plan
    assert graph_fingerprint(g) == graph_fingerprint(polybench.build("atax"))
    assert graph_fingerprint(g) != graph_fingerprint(polybench.build("bicg"))
    assert hardware_fingerprint(THREE_SLICE) != hardware_fingerprint(
        Hardware.make(n_slices=3, dispatch_s=1e-6))
    a = solver_options_fingerprint(FAST)
    assert a == solver_options_fingerprint(SolverOptions(time_budget_s=10.0))
    assert a != solver_options_fingerprint(
        SolverOptions(time_budget_s=10.0, seed=7))
    # worker count must NOT key the store: replicas with different core
    # counts share entries
    assert a == solver_options_fingerprint(
        SolverOptions(time_budget_s=10.0, workers=4))


# ---------------------------------------------------------------------------
# Store hit / miss / refresh
# ---------------------------------------------------------------------------
def test_store_hit_skips_the_sweep(tmp_path, atax_plan):
    g, cold = atax_plan
    st = PlanStore(str(tmp_path))
    st.save(g, THREE_SLICE, FAST, cold)
    warm = solve(g, THREE_SLICE, FAST, store=st)
    assert warm.store_hit and not warm.stale_hw
    assert warm.n_evaluated == 0           # no sweep ran
    assert warm.latency_s == cold.latency_s
    assert {t: c.to_jsonable() for t, c in warm.configs.items()} == \
        {t: c.to_jsonable() for t, c in cold.configs.items()}
    assert st.stats()["hits"] == 1


def test_refresh_bypasses_load_but_updates_store(tmp_path, atax_plan):
    g, cold = atax_plan
    st = PlanStore(str(tmp_path))
    st.save(g, THREE_SLICE, FAST, cold)
    fresh = solve(g, THREE_SLICE, FAST, store=st, refresh=True)
    assert not fresh.store_hit and fresh.n_evaluated > 0
    assert st.stats()["writes"] == 2       # seed + refreshed entry


def test_corrupt_entry_is_quarantined_and_resolved(tmp_path, atax_plan):
    g, cold = atax_plan
    st = PlanStore(str(tmp_path))
    path = st.save(g, THREE_SLICE, FAST, cold)
    with open(path, "w") as f:
        f.write('{"schema": 1, "plan": tru')      # torn write
    plan = solve(g, THREE_SLICE, FAST, store=st)
    assert not plan.store_hit and plan.n_evaluated > 0   # re-solved
    assert os.path.exists(path + ".corrupt")             # quarantined
    assert st.stats()["corrupt"] == 1
    # the re-solve overwrote the slot: next load hits again
    assert solve(g, THREE_SLICE, FAST, store=st).store_hit


def test_stale_hardware_hit_requires_allow_stale(tmp_path, atax_plan):
    g, cold = atax_plan
    st = PlanStore(str(tmp_path))
    st.save(g, THREE_SLICE, FAST, cold)
    drifted = Hardware.make(n_slices=3, dispatch_s=1e-6)
    miss = solve(g, drifted, FAST, store=st)
    assert not miss.store_hit              # exact key: drift is a miss
    st2 = PlanStore(str(tmp_path))         # fresh counters; drifted entry
    hit = st2.load(g, Hardware.make(n_slices=3, dispatch_s=2e-6),
                   FAST, allow_stale=True)
    assert hit is not None and hit.stale_hw and hit.n_evaluated == 0


def test_store_is_bounded_by_mtime_eviction(tmp_path, atax_plan):
    g, plan = atax_plan
    st = PlanStore(str(tmp_path), max_entries=2)
    for i, seed in enumerate((1, 2, 3)):
        st.save(g, THREE_SLICE, SolverOptions(time_budget_s=10.0,
                                              seed=seed), plan)
        os.utime(st._path(*st.key(g, THREE_SLICE,
                                  SolverOptions(time_budget_s=10.0,
                                                seed=seed))),
                 (i, i))                   # deterministic mtime order
    assert len(st) == 2
    # the oldest (seed=1) was evicted
    assert st.load(g, THREE_SLICE,
                   SolverOptions(time_budget_s=10.0, seed=1)) is None
    assert st.load(g, THREE_SLICE,
                   SolverOptions(time_budget_s=10.0, seed=3)) is not None


def test_default_store_is_env_gated(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_PLAN_STORE_DIR", raising=False)
    set_default_dir(None)
    assert default_store() is None         # disabled: seed behavior
    monkeypatch.setenv("REPRO_PLAN_STORE_DIR", str(tmp_path))
    st = default_store()
    assert st is not None and st.root == str(tmp_path)
    set_default_dir(str(tmp_path / "override"))
    assert default_store().root == str(tmp_path / "override")
    set_default_dir(None)


# ---------------------------------------------------------------------------
# Parallel sweep (SolverOptions.workers)
# ---------------------------------------------------------------------------
def test_parallel_sweep_latency_no_worse_than_serial():
    g = polybench.build("2mm")
    opts_ser = SolverOptions(time_budget_s=30.0, workers=1)
    opts_par = SolverOptions(time_budget_s=30.0, workers=2)
    serial = solve(g, THREE_SLICE, opts_ser, store=None)
    par = solve(g, THREE_SLICE, opts_par, store=None)
    # pruning only discards candidates whose lower bound cannot win, so
    # the parallel plan is never worse on the same seed
    assert par.latency_s <= serial.latency_s * (1 + 1e-12)
    assert par.configs and not par.timed_out


def test_workers_do_not_change_the_store_key():
    g = polybench.build("atax")
    k1 = PlanStore.key(g, THREE_SLICE, SolverOptions(workers=1))
    k2 = PlanStore.key(g, THREE_SLICE, SolverOptions(workers=8))
    assert k1 == k2


# ---------------------------------------------------------------------------
# Deadline accounting (solve() includes fusion + enumeration)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["prometheus", "sisyphus"])
def test_tiny_budget_returns_best_feasible_not_raise(mode):
    # scale 64: every 3mm extent a multiple of 128, so the TPU block rule
    # leaves both modes a space too large for the budget
    g = polybench.build("3mm", scale=64)
    plan = solve(g, THREE_SLICE,
                 SolverOptions(mode=mode, time_budget_s=0.05), store=None)
    assert plan.configs                    # feasible, not an exception
    assert plan.latency_s > 0
    assert plan.timed_out                  # and honest about it
    # solver_seconds covers the whole call (fusion + enumeration +
    # search), so it cannot be simultaneously timed-out and near-zero
    assert plan.solver_seconds >= 0.04
