"""Integration tests: NLP solver (core/solver.py) — paper §4 / §6."""
from __future__ import annotations

import pytest

from repro.core import (ONE_SLICE, THREE_SLICE, Hardware, SolverOptions,
                        polybench, solve)

FAST = SolverOptions(time_budget_s=10.0)


def _gf(plan):
    return plan.useful_flops / plan.latency_s / 1e9


@pytest.fixture(scope="module")
def plans_3mm():
    g = polybench.build("3mm")
    return {mode: solve(g, THREE_SLICE if mode == "prometheus" else ONE_SLICE,
                        SolverOptions(mode=mode, time_budget_s=20.0))
            for mode in ("prometheus", "sisyphus", "streamhls", "autodse")}


def test_all_modes_produce_feasible_plans(plans_3mm):
    for mode, plan in plans_3mm.items():
        assert plan.latency_s > 0, mode
        assert plan.configs, mode
        for tid, rep in plan.reports.items():
            assert rep.vmem_bytes <= ONE_SLICE.vmem * 3 + 1, (mode, tid)


def test_prometheus_dominates_restricted_modes(plans_3mm):
    """Paper Table 6: the full space at least matches every restriction."""
    p = _gf(plans_3mm["prometheus"])
    for mode in ("sisyphus", "streamhls", "autodse"):
        assert p >= _gf(plans_3mm[mode]) * 0.999, mode


def test_pragma_only_modes_are_far_slower(plans_3mm):
    """autodse/streamhls lack tiling -> orders of magnitude behind
    (paper: 1.74 / 174 GF/s vs 368 GF/s at FPGA scale)."""
    assert _gf(plans_3mm["prometheus"]) > 10 * _gf(plans_3mm["autodse"])
    assert _gf(plans_3mm["prometheus"]) > 10 * _gf(plans_3mm["streamhls"])


def test_sisyphus_joint_space_blowup():
    """Table 10 story: Sisyphus couples every task's (perm, tiles) choice
    in one product space that its budget cannot cover, while Prometheus's
    decoupled per-task sweep finishes.  On 3mm at scale 64 every extent is
    a multiple of 128, so the TPU block rule leaves each loop a menu of
    tiles; at the paper's medium extents it leaves Sisyphus (which cannot
    pad) about one tile per loop, and no space to blow up."""
    g = polybench.build("3mm", scale=64)
    sis = solve(g, ONE_SLICE, SolverOptions(mode="sisyphus",
                                            time_budget_s=2.0), store=None)
    pro = solve(g, THREE_SLICE, SolverOptions(time_budget_s=20.0),
                store=None)
    assert sis.space_size > 50 * pro.space_size
    assert sis.timed_out
    assert not pro.timed_out


def test_solver_deterministic():
    g = polybench.build("atax")
    a = solve(g, THREE_SLICE, SolverOptions(time_budget_s=8.0, seed=3))
    b = solve(g, THREE_SLICE, SolverOptions(time_budget_s=8.0, seed=3))
    assert a.latency_s == b.latency_s
    assert {t: c.to_jsonable() for t, c in a.configs.items()} == \
        {t: c.to_jsonable() for t, c in b.configs.items()}


def test_multi_slice_helps_compute_bound_not_memory_bound():
    """Paper Table 8 observation: 3 SLRs help 2mm/3mm (compute-bound),
    not atax/bicg (memory-bound).  TPU-scale datasets put the O(N)-reuse
    kernels in the paper's compute-bound regime (DESIGN.md §2)."""
    for name, expect_speedup in (("3mm", True), ("bicg", False)):
        g = polybench.build(name, scale=16)
        one = solve(g, ONE_SLICE, SolverOptions(time_budget_s=15.0))
        three = solve(g, THREE_SLICE, SolverOptions(time_budget_s=15.0))
        ratio = one.latency_s / three.latency_s
        if expect_speedup:
            assert ratio > 1.05, f"{name}: {ratio}"
        else:
            assert ratio < 1.5, f"{name}: {ratio}"


def test_vmem_constraint_respected_under_tiny_budget():
    g = polybench.build("gemm")
    tiny = Hardware.make(n_slices=1, vmem_frac=0.02)   # ~320 KiB
    plan = solve(g, tiny, SolverOptions(time_budget_s=8.0))
    for rep in plan.reports.values():
        assert rep.vmem_bytes <= tiny.slices[0].vmem + 1


def test_padding_only_in_padding_capable_modes():
    g = polybench.build("gemm")      # trip counts 200/220/240
    pro = solve(g, ONE_SLICE, SolverOptions(time_budget_s=10.0))
    sis = solve(g, ONE_SLICE, SolverOptions(mode="sisyphus",
                                            time_budget_s=10.0))
    for cfg in sis.configs.values():
        assert all(t.pad == 0 for t in cfg.tiles.values()), \
            "sisyphus mode must not pad"
    # prometheus may pad (not asserted — solver choice), but any padding
    # must keep tiles dividing the padded extent
    for cfg in pro.configs.values():
        for t in cfg.tiles.values():
            assert t.padded_tc % t.tile == 0


def test_concurrency_only_in_dataflow_modes():
    g = polybench.build("3mm")
    sis = solve(g, THREE_SLICE, SolverOptions(mode="sisyphus",
                                              time_budget_s=10.0))
    slices = {c.slice_id for c in sis.configs.values()}
    assert slices == {0}, "sisyphus is single-slice"


@pytest.mark.parametrize("name", ["2mm", "atax", "gesummv", "3-madd"])
def test_modes_solve_quickly_on_more_kernels(name):
    g = polybench.build(name)
    for mode in ("prometheus", "streamhls", "autodse"):
        plan = solve(g, THREE_SLICE, SolverOptions(mode=mode,
                                                   time_budget_s=10.0))
        assert plan.latency_s > 0
        assert plan.solver_seconds < 60
