"""No plan the solver can emit breaks the TPU's block rule.

Mosaic accepts a kernel block only when its last dim is a multiple of 128
lanes (rank-1 blocks: 128 x the dtype's packing) and its second-to-last a
multiple of 8 sublanes — or the dim spans the whole (padded) array.  These
tests check every candidate menu of every solver mode, and every
``ContractionSpec`` the codegen emits for a solved plan, against that rule
stated here independently of the solver.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro import frontend
from repro.codegen.lower import lower_task
from repro.core import SolverOptions, THREE_SLICE, polybench, solve
from repro.core.fusion import fuse
from repro.core.solver import CAPS, candidate_tiles

# the graphs tests/test_codegen.py executes
EXECUTABLE = ["3mm", "2mm", "gemm", "atax", "bicg", "mvt", "gesummv",
              "gemver", "madd", "2-madd", "3-madd"]


def _legal(block, padded, itemsize: int) -> bool:
    if len(block) == 1:
        lane = 128 * (4 // itemsize)
        return block[0] % lane == 0 or block[0] == padded[0]
    return (block[-1] % 128 == 0 or block[-1] == padded[-1]) and \
        (block[-2] % 8 == 0 or block[-2] == padded[-2])


def _ffn(x, w1, w3, w2):
    a = x @ w1
    g = x @ w3
    h = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * g
    return h @ w2


def _graph(name: str):
    if name == "ffn":
        # the qwen3-0.6b FFN block's widths, bf16 (trace only: no compute)
        shapes = ((512, 1024), (1024, 3072), (1024, 3072), (3072, 1024))
        return frontend.trace(_ffn, *(jnp.zeros(s, jnp.bfloat16)
                                      for s in shapes)).graph
    return polybench.build(name)


@pytest.mark.parametrize("name", EXECUTABLE + ["ffn"])
def test_candidate_tiles_obey_block_rule(name):
    """Every tile any mode may pick keeps every block it shapes legal."""
    g = _graph(name)
    for task in fuse(g).tasks:
        tcs = task.trip_counts
        for mode in CAPS:
            menus = candidate_tiles(task, SolverOptions(mode=mode))
            for stmt in task.statements:
                for acc in tuple(stmt.reads) + tuple(stmt.writes):
                    if not acc.iters or any(it not in task.main.loops
                                            for it in acc.iters):
                        continue
                    itemsize = g.arrays[acc.array].dtype_bytes
                    for pos in (-1, -2)[:len(acc.iters)]:
                        it = acc.iters[pos]
                        for opt in menus[it]:
                            block = [1] * len(acc.iters)
                            padded = [1] * len(acc.iters)
                            block[pos], padded[pos] = opt.tile, opt.padded_tc
                            if pos == -2:       # test the sublane dim alone
                                block[-1] = padded[-1] = 128
                            assert _legal(block, padded, itemsize), \
                                (name, mode, acc, opt, tcs[it])


@pytest.mark.parametrize("name", EXECUTABLE + ["ffn"])
def test_emitted_contraction_specs_obey_block_rule(name):
    """Every block of every kernel the codegen builds from a solved plan."""
    g = _graph(name)
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=4.0, workers=1),
                 store=None)
    fg = fuse(g)
    n_specs = 0
    for task in fg.tasks:
        lw = lower_task(fg, task, plan.configs[task.tid], "pallas")
        for unit in lw.units:
            spec = unit.spec
            if spec is None:
                continue
            n_specs += 1
            for o in spec.all_reads:
                assert _legal(spec.block_shape(o), spec.padded_shape(o),
                              g.arrays[o.array].dtype_bytes), (name, o, spec)
            assert _legal(spec.out_block, spec.out_padded, 4), (name, spec)
    assert n_specs >= 1
