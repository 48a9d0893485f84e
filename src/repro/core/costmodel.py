"""Analytic latency model — the paper's NLP objective (Eqs. 12-16), TPU terms.

Structure mirrors the paper exactly:

* Eq. 15 (intra-task base case): one fully-"unrolled" intra-tile executes on
  the MXU/VPU; latency = issue overhead + FLOP time (de-rated by lane/sublane
  alignment) + a reduction-tree drain term ``RED_LATENCY * log2(red_elems)``.
* Eq. 16 (pipelined reduction): inter-tile *reduction* loops revisit the same
  output tile, pipelined with initiation interval II = steady-state tile time.
* Eq. 14 (level recursion): every non-reduction inter-tile loop level adds
  ``trips * max(inner, comm)`` when double/triple-buffered (computation-
  communication overlap) or ``trips * (inner + comm)`` when not, plus
  prologue/epilogue fill terms.
* Eqs. 12-13 (DAG): per-task latencies compose over the fused dataflow graph
  with producer->consumer ``shift`` terms for streamed (FIFO) edges, a
  per-slice serialization constraint (a TPU core runs one task at a time —
  concurrency comes from placing tasks on different slices, the SLR
  adaptation), and makespan = latest sink finish.

All byte volumes honour padding (padded trip counts cost real compute and
real transfer bytes) and burst packing (minor-dim alignment de-rates HBM
bandwidth) — the paper's padding-for-computation / padding-for-communication
trade-off is therefore visible to the solver.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping

from .fusion import FusedGraph, FusedTask
from .plan import ArrayPlacement, TaskConfig, TaskReport
from .resources import (LANE, SUBLANE, Hardware, STEP_OVERHEAD_S,
                        RED_LATENCY_S, VMEM_BW, alignment_efficiency,
                        packing_efficiency)
from .taskgraph import Access


# ---------------------------------------------------------------------------
# Kernel blocks: the Mosaic block rule and the VMEM they hold
# ---------------------------------------------------------------------------
def _block_iters(task: FusedTask, acc: Access) -> list[str] | None:
    """The main-statement loops that shape ``acc``'s kernel block.

    A fused pointwise statement keeps private iterators; the codegen runs
    it on the main statement's output tile (epilogue fold), so its private
    iterators map right-aligned onto the output's.  ``None`` when the
    access cannot be mapped (broadcast dims, higher rank than the output).
    """
    main = task.main
    if not acc.iters or any(it is None for it in acc.iters):
        return None
    out_iters = main.writes[0].iters
    off = len(out_iters) - len(acc.iters)
    mapped = []
    for i, it in enumerate(acc.iters):
        if it in main.loops:
            mapped.append(it)
        elif off + i >= 0:
            mapped.append(out_iters[off + i])
        else:
            return None
    return mapped


def block_multiples(task: FusedTask) -> dict[str, int]:
    """Per main loop, the multiple its tile must be — unless the tile is
    the loop's full padded extent — for every kernel block it shapes to be
    legal on the TPU (Mosaic): the last block dim a multiple of 128 lanes,
    the second-to-last a multiple of 8 sublanes.  A rank-1 block needs 256
    (128 lanes x the bf16 packing), whatever the dtype, so the rule does
    not depend on which dtype a graph was traced at."""
    need: dict[str, int] = {}
    for s in task.statements:
        for acc in tuple(s.reads) + tuple(s.writes):
            its = _block_iters(task, acc)
            if its is None:
                continue
            last = LANE * (2 if len(its) == 1 else 1)
            need[its[-1]] = math.lcm(need.get(its[-1], 1), last)
            if len(its) >= 2:
                need[its[-2]] = math.lcm(need.get(its[-2], 1), SUBLANE)
    return need


def kernel_vmem_bytes(task: FusedTask, tiles) -> int:
    """VMEM the task's contraction kernel holds for ``tiles``: every input
    block double-buffered, the output block double-buffered, and the
    accumulator scratch when the main statement reduces — each element at
    4 bytes, since the kernels compute in f32.  Inputs are the arrays the
    task reads from outside itself; the solver keeps this under the same
    VMEM budget the kernels are compiled with
    (``resources.VMEM_BYTES``)."""
    main = task.main
    produced = {w.array for s in task.statements for w in s.writes}

    def elems(acc: Access) -> int:
        its = _block_iters(task, acc) or [it for it in acc.iters if it]
        n = 1
        for it in its:
            n *= tiles[it].tile
        return n

    seen: set[str] = set()
    total = 0
    for s in task.statements:
        for acc in s.reads:
            if acc.array in produced or acc.array in seen:
                continue
            seen.add(acc.array)
            total += 2 * elems(acc)
    out = elems(main.writes[0])
    total += (3 if main.reduction_loops else 2) * out
    return 4 * total


# ---------------------------------------------------------------------------
# Footprints (paper f_{a,l}) and transfer counts
# ---------------------------------------------------------------------------
def _access_of(task: FusedTask, array: str) -> Access:
    """First access of ``array`` in the task, memoized per task.

    This sits in the solver's innermost enumeration loop (every footprint /
    transfer-count query lands here); a linear rescan of all statements per
    call dominated solve time.  The cache lives on the task object and is
    rebuilt if fusion appends statements after a lookup.
    """
    cache = getattr(task, "_access_cache", None)
    if cache is None or cache[0] != len(task.statements):
        mapping: dict[str, Access] = {}
        for s in task.statements:
            for acc in tuple(s.reads) + tuple(s.writes):
                mapping.setdefault(acc.array, acc)
        cache = (len(task.statements), mapping)
        task._access_cache = cache
    try:
        return cache[1][array]
    except KeyError:
        raise KeyError(f"array {array!r} not accessed by task {task.name}") \
            from None


def tile_extent(cfg: TaskConfig, task: FusedTask, it: str, level: int) -> int:
    """Extent along iterator ``it`` of the data-tile transferred at ``level``.

    If the loop carrying ``it`` encloses the transfer (its level < given
    level) each transfer covers one tile of it; otherwise the transfer must
    cover all remaining iterations (full padded extent)."""
    t = cfg.tiles[it]
    if it in cfg.perm and cfg.level_of(it) <= level:
        return t.tile
    return t.padded_tc


def footprint_elems(cfg: TaskConfig, task: FusedTask, array: str,
                    level: int) -> int:
    acc = _access_of(task, array)
    n = 1
    for it in acc.iters:
        n *= tile_extent(cfg, task, it, level)
    return n


def minor_dim_elems(cfg: TaskConfig, task: FusedTask, array: str,
                    level: int) -> int:
    acc = _access_of(task, array)
    if not acc.iters:
        return 1
    return tile_extent(cfg, task, acc.iters[-1], level)


def n_transfers(cfg: TaskConfig, task: FusedTask, array: str,
                placement: ArrayPlacement) -> int:
    """How many times the data-tile of ``array`` is (re)loaded.

    Product of inter-tile trip counts of loops enclosing the transfer level,
    *skipping* loops that do not index the array when the buffer is defined
    at or above that loop (data reuse across that loop — the paper's
    d_{a,l} mechanism, e.g. array E reused across j0 in Listing 6)."""
    acc = _access_of(task, array)
    used = set(acc.iters)
    total = 1
    for pos, loop in enumerate(cfg.perm):
        level_of_loop = pos + 1
        if level_of_loop > placement.transfer_level:
            break
        if loop in used or placement.define_level >= level_of_loop:
            total *= cfg.tiles[loop].n_tiles
    return total


# ---------------------------------------------------------------------------
# Per-task latency (Eqs. 14-16)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StreamRates:
    """Bandwidths for each array feeding/leaving a task."""

    hbm_bw: float
    ici_bw: float


def task_report(task: FusedTask, cfg: TaskConfig, graph: FusedGraph,
                hw: Hardware, bw_share: float = 1.0) -> TaskReport:
    """``bw_share`` divides HBM bandwidth among concurrently-active slices
    (the DRAM channels are a board-level resource shared by SLR regions —
    paper §2.2.2 economics: compute scales with slices, bandwidth doesn't).
    """
    sl = hw.slices[cfg.slice_id]
    main = task.main
    out_arr = task.output_array
    arrays = graph.graph.arrays

    # ----- intra-tile (Eq. 15) ------------------------------------------
    red_loops = [l for l in main.loops if l in main.reduction_loops]
    intra_elems = 1.0
    for l in main.loops:
        intra_elems *= cfg.tiles[l].tile
    out_acc = _access_of(task, out_arr)
    out_block = [cfg.tiles[it].tile for it in out_acc.iters]
    eff = alignment_efficiency(out_block)
    flops_tile = intra_elems * main.flops_per_iter * main.density
    t_mxu = flops_tile / max(sl.flops * eff, 1.0)
    red_elems = 1
    for l in red_loops:
        red_elems *= cfg.tiles[l].tile
    lat_intra = STEP_OVERHEAD_S + t_mxu \
        + RED_LATENCY_S * math.log2(max(red_elems, 1) or 1)

    # ----- pipelined inter-tile reduction loops (Eq. 16) ----------------
    red_trips = 1
    for l in red_loops:
        red_trips *= cfg.tiles[l].n_tiles
    ii = max(t_mxu, RED_LATENCY_S)           # initiation interval, seconds
    lat_red_chain = lat_intra + ii * (red_trips - 1)

    # Reduction loops sit innermost (paper §3.4); the level recursion below
    # walks the *non-reduction* inter-tile loops outermost-first.  Arrays
    # transferred "inside" reduction levels stream per reduction step.
    red_level_start = len(cfg.perm) - len(red_loops) + 1

    def bw_of(array: str, placement: ArrayPlacement, level: int) -> float:
        if placement.onchip:
            return VMEM_BW            # shared-buffer handoff on the same slice
        if placement.stream:
            return hw.ici_bw          # FIFO across slices (inter-SLR analogue)
        pk = packing_efficiency(
            minor_dim_elems(cfg, task, array, level),
            arrays[array].dtype_bytes)
        return sl.hbm_bw * bw_share * pk

    # Total transfer seconds & bytes per array (amortised over reuse).
    reads = [a for a in task.read_arrays()]
    load_s_total = 0.0
    hbm_bytes = 0.0
    stream_bytes = 0.0
    per_level_load_s: dict[int, float] = {}
    for a in reads:
        pl = cfg.placements[a]
        tile_b = footprint_elems(cfg, task, a, pl.transfer_level) \
            * arrays[a].dtype_bytes
        cnt = n_transfers(cfg, task, a, pl)
        secs = cnt * tile_b / bw_of(a, pl, pl.transfer_level)
        load_s_total += secs
        if pl.stream:
            stream_bytes += cnt * tile_b
        else:
            hbm_bytes += cnt * tile_b
        per_level_load_s[pl.transfer_level] = \
            per_level_load_s.get(pl.transfer_level, 0.0) + secs

    # Output: stored (or sent) once per output tile — output-stationary.
    out_pl = cfg.placements[out_arr]
    out_tile_b = footprint_elems(cfg, task, out_arr, out_pl.transfer_level) \
        * arrays[out_arr].dtype_bytes
    out_cnt = n_transfers(cfg, task, out_arr, out_pl)
    store_s_total = out_cnt * out_tile_b / bw_of(out_arr, out_pl,
                                                 out_pl.transfer_level)
    if out_pl.stream:
        stream_bytes += out_cnt * out_tile_b
    else:
        hbm_bytes += out_cnt * out_tile_b

    # ----- level recursion (Eq. 14) -------------------------------------
    # Amortised per-execution transfer time at each level; levels are the
    # non-reduction inter-tile loops in permutation order.
    nonred_perm = [l for l in cfg.perm if l not in red_loops]

    def execs_of_level(level: int) -> int:
        n = 1
        for pos, loop in enumerate(cfg.perm):
            if pos + 1 > level:
                break
            n *= cfg.tiles[loop].n_tiles
        return n

    def level_lat(idx: int) -> float:
        """Latency of one execution of the loop at position idx (0-based in
        nonred_perm) including everything inside it."""
        if idx >= len(nonred_perm):
            # Innermost: one pipelined reduction chain plus transfers assigned
            # inside reduction levels (streamed per reduction step).  One
            # chain = one execution of the subtree below the last
            # non-reduction loop; amortise the total red-level transfer time
            # over the number of chains.
            n_chains = max(execs_of_level(red_level_start - 1), 1)
            comm = sum(per_level_load_s.get(lv, 0.0)
                       for lv in range(red_level_start, len(cfg.perm) + 1)) \
                / n_chains
            overlapped = all(cfg.placements[a].buffers >= 2 for a in reads) \
                if reads else True
            if overlapped:
                return max(lat_red_chain, comm) + (comm / max(red_trips, 1))
            return lat_red_chain + comm

        loop = nonred_perm[idx]
        level = cfg.perm.index(loop) + 1
        trips = cfg.tiles[loop].n_tiles
        inner = level_lat(idx + 1)
        # per-iteration-of-this-loop amortised transfer time at this level
        n_iters = max(execs_of_level(level), 1)
        load_tile = per_level_load_s.get(level, 0.0) / n_iters
        store_here = store_s_total / n_iters \
            if out_pl.transfer_level == level else 0.0
        overlapped = any(cfg.placements[a].buffers >= 2 for a in reads) \
            or out_pl.buffers >= 2
        if overlapped:
            steady = max(inner, load_tile + store_here)
            # prologue: first load; epilogue: last store (the alpha term)
            return trips * steady + load_tile + store_here
        return trips * (inner + load_tile + store_here)

    body = level_lat(0)
    # Level-0 transfers (before any loop): strictly serial prologue/epilogue.
    pre = per_level_load_s.get(0, 0.0)
    post = store_s_total if out_pl.transfer_level == 0 else 0.0
    latency = pre + body + post

    compute_total = execs_of_level(len(cfg.perm)) / max(red_trips, 1) \
        * lat_red_chain
    # Padded vs useful FLOPs: useful uses original trip counts.
    useful = task.flops
    padded = useful
    for l in cfg.perm:
        t = cfg.tiles[l]
        if t.ori_tc:
            padded *= t.padded_tc / t.ori_tc

    # ----- VMEM occupancy (Eq. 7) ----------------------------------------
    vmem = 0.0
    for a in reads + [out_arr]:
        pl = cfg.placements[a]
        buf = footprint_elems(cfg, task, a, pl.define_level) \
            * arrays[a].dtype_bytes * pl.buffers
        vmem += buf

    return TaskReport(
        latency_s=latency,
        compute_s=compute_total,
        load_s=load_s_total,
        store_s=store_s_total,
        vmem_bytes=vmem,
        hbm_bytes=hbm_bytes,
        stream_bytes=stream_bytes,
        useful_flops=useful,
        padded_flops=padded,
        fill_s=pre + post,
    )


# ---------------------------------------------------------------------------
# DAG latency (Eqs. 12-13) with slice serialization + streaming shifts
# ---------------------------------------------------------------------------
def emission_order(task: FusedTask, cfg: TaskConfig, array: str) \
        -> tuple[int, ...]:
    """Order in which array dims are visited (outer->inner) by the task."""
    acc = _access_of(task, array)
    order: list[int] = []
    for loop in cfg.perm:
        for d, it in enumerate(acc.iters):
            if it == loop and d not in order:
                order.append(d)
    return tuple(order)


def edge_order_compatible(fg: FusedGraph, configs: Mapping[int, TaskConfig],
                          u: int, v: int, arr: str) -> bool:
    """FIFO legality (paper §6.4): the consumer visits the array's dims in
    the producer's emission order, or full-buffers it (define level 0)."""
    pl = configs[v].placements.get(arr)
    if pl is not None and pl.define_level == 0:
        return True
    return emission_order(fg.tasks[u], configs[u], arr) == \
        emission_order(fg.tasks[v], configs[v], arr)


def dag_latency(fg: FusedGraph, configs: Mapping[int, TaskConfig],
                reports: Mapping[int, TaskReport],
                dispatch_s: float = 0.0) -> float:
    """Makespan of the DAG (Eqs. 12-13).

    ``dispatch_s`` is the fixed per-task host dispatch overhead (calibrated
    ``Hardware.dispatch_s``; 0 under the static model).  It serializes with
    the task on its slice, so co-locating independent tasks pays it once
    per task back-to-back while spreading them overlaps it — the measured
    "dispatch saving" the solver weighs against stream cost.
    """
    start: dict[int, float] = {}
    finish: dict[int, float] = {}
    slice_free: dict[int, float] = {}
    for tid in fg.topo_order():
        cfg = configs[tid]
        rep = reports[tid]
        ready = 0.0
        for (u, arr) in fg.preds(tid):
            pl = configs[tid].placements.get(arr)
            streamed = pl is not None and pl.stream
            if streamed and edge_order_compatible(fg, configs, u, tid, arr):
                # Eq. 12 shift: consumer starts once the first tile arrives
                # through the FIFO...
                out_tiles = max(_n_out_tiles(fg, u, configs[u]), 1)
                first_tile = reports[u].latency_s / out_tiles
                ready = max(ready, start[u] + dispatch_s + first_tile)
                # ...but cannot drain the last tile before the producer
                # emits it: finish >= producer finish + one tile hop.
                ready = max(ready, finish[u] + first_tile - rep.latency_s)
            else:
                ready = max(ready, finish[u])
        s0 = max(ready, slice_free.get(cfg.slice_id, 0.0))
        start[tid] = s0
        finish[tid] = s0 + dispatch_s + rep.latency_s
        slice_free[cfg.slice_id] = finish[tid]
    return max(finish[t] for t in fg.sinks())


def _n_out_tiles(fg: FusedGraph, tid: int, cfg: TaskConfig) -> int:
    task = fg.tasks[tid]
    out = task.output_array
    acc = _access_of(task, out)
    n = 1
    for it in acc.iters:
        if it in cfg.tiles:
            n *= cfg.tiles[it].n_tiles
    return n


def topo_waves(fg: FusedGraph) -> dict[int, int]:
    """Topological level of every task: wave ``w`` tasks have all producers
    in waves ``< w``, so same-wave tasks are mutually independent.  This is
    the cost model's view of the wave schedule the executors run
    (``repro.codegen.schedule`` derives its waves from this function).

    Memoized on the graph object (the ``_access_of`` idiom): the solver's
    assignment search calls ``plan_latency`` thousands of times per solve
    and the waves depend only on graph structure, never on the candidate
    plan.  Callers must treat the returned dict as read-only.
    """
    cache = getattr(fg, "_wave_cache", None)
    if cache is None or cache[0] != len(fg.tasks):
        preds = {t.tid: [u for (u, _) in fg.preds(t.tid)] for t in fg.tasks}
        wave_of: dict[int, int] = {}
        for tid in fg.topo_order():
            wave_of[tid] = 1 + max((wave_of[u] for u in preds[tid]),
                                   default=-1)
        cache = (len(fg.tasks), wave_of)
        fg._wave_cache = cache
    return cache[1]


def plan_latency(fg: FusedGraph, configs: Mapping[int, TaskConfig],
                 hw: Hardware) -> tuple[float, dict[int, TaskReport]]:
    """DAG makespan + per-task reports under ``hw``.

    HBM bandwidth is shared among the slices *concurrently active in the
    same wave*, not among every slice the plan uses anywhere: a 3-wave
    plan whose waves each run on one slice keeps full bandwidth per task.
    (Charging the whole-plan slice count overcharged multi-wave plans and
    biased the solver toward single-slice assignments.)
    """
    wave_of = topo_waves(fg)
    wave_slices: dict[int, set[int]] = {}
    for t in fg.tasks:
        wave_slices.setdefault(wave_of[t.tid], set()) \
            .add(configs[t.tid].slice_id)
    reports = {
        t.tid: task_report(
            t, configs[t.tid], fg, hw,
            bw_share=hw.bw_share_at(len(wave_slices[wave_of[t.tid]])))
        for t in fg.tasks}
    return dag_latency(fg, configs, reports,
                       dispatch_s=hw.dispatch_s), reports
