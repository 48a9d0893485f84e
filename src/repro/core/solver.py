"""NLP-based design-space exploration (paper §4) — self-contained solver.

The paper formulates tile sizes, loop orders, transfer levels, buffer counts
and SLR assignments as one Non-Linear Program and solves it with AMPL+Gurobi.
This container is offline, so the solver is built here from scratch — which
is itself faithful to the *shape* of the problem:

* Per-task enumeration with constraint propagation and Pareto pruning
  (latency vs VMEM) over the factored discrete space
  (permutation x tiles x placements) — exact for the spaces we generate.
* A global placement phase (slice assignment = ``slr_t``, Eq. 11; streaming
  vs shared-buffer routing of dataflow edges) solved exactly for small task
  counts and by seeded simulated annealing beyond that.
* The **mode** switch reproduces the paper's comparison frameworks as
  restrictions of the same space (Table 1):

    ``prometheus``  full space (this work)
    ``sisyphus``    tiling+permutation, NO padding / dataflow / overlap /
                    multi-slice; the search is *joint* across tasks (shared
                    buffers couple them) — reproducing the Table 10 blowup.
    ``streamhls``   dataflow streaming + permutation, data assumed on-chip
                    (transfers pinned to level 0), parallelism limited to
                    FIFO width, no tiling/padding/overlap.
    ``autodse``     pragma-only: no code transformation; innermost unroll
                    factors restricted to trip-count divisors; whole arrays
                    buffered; no dataflow/overlap/multi-slice.

Determinism: all enumeration orders are sorted; annealing uses a fixed seed.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import itertools
import multiprocessing
import os
import random
import sys
import time

from ..obs import tracer as _obs_tracer
from .costmodel import (_access_of, block_multiples, footprint_elems,
                        kernel_vmem_bytes, n_transfers, plan_latency,
                        task_report)
from .fusion import FusedGraph, FusedTask, fuse
from .padding import TileOption, tile_options
from .plan import ArrayPlacement, ExecutionPlan, TaskConfig, TaskReport
from .resources import TPU_KINDS, Hardware, THREE_SLICE, alignment_efficiency
from .taskgraph import TaskGraph, legal_permutations


@dataclasses.dataclass(frozen=True)
class ModeCaps:
    tiling: bool
    permutation: bool
    padding: bool
    streaming: bool
    concurrency: bool
    overlap: bool
    multi_slice: bool
    joint_search: bool = False      # couple tasks in one product space


CAPS: dict[str, ModeCaps] = {
    "prometheus": ModeCaps(True, True, True, True, True, True, True),
    "sisyphus": ModeCaps(True, True, False, False, False, False, False,
                         joint_search=True),
    "streamhls": ModeCaps(False, True, False, True, True, False, False),
    "autodse": ModeCaps(False, False, False, False, False, False, False),
}


@dataclasses.dataclass
class SolverOptions:
    mode: str = "prometheus"
    max_tile: int = 256
    tile_menu: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128, 256)
    max_options_per_loop: int = 6
    top_k: int = 8
    time_budget_s: float = 120.0
    anneal_iters: int = 4000
    seed: int = 0
    # Process-pool fan-out for the candidate sweep.  ``None`` resolves to
    # ``os.cpu_count() - 1`` (REPRO_SOLVER_WORKERS overrides); ``1`` is
    # today's exact serial sweep, bit-for-bit.  workers > 1 additionally
    # enables cost-model-guided pruning (compute lower bounds against the
    # shared best-so-far), so its candidate set is a subset of serial's.
    workers: int | None = None
    # Sweeps smaller than this many (perm, tiles) points stay serial even
    # with workers > 1 — pool spin-up would dominate.
    min_parallel_units: int = 192

    @property
    def caps(self) -> ModeCaps:
        return CAPS[self.mode]

    @property
    def effective_workers(self) -> int:
        if self.workers is not None:
            return max(1, int(self.workers))
        env = os.environ.get("REPRO_SOLVER_WORKERS")
        if env:
            try:
                return max(1, int(env))
            except ValueError:
                pass
        return max(1, (os.cpu_count() or 2) - 1)

    def fingerprint(self) -> str:
        """Plan-store key component — see
        :func:`repro.core.fingerprint.solver_options_fingerprint` for what
        is (and deliberately is not) part of the identity."""
        from .fingerprint import solver_options_fingerprint
        return solver_options_fingerprint(self)


@dataclasses.dataclass
class SolveStats:
    n_evaluated: int = 0
    timed_out: bool = False
    space_size: float = 0.0          # estimated raw product-space size


# ---------------------------------------------------------------------------
# Candidate generation
# ---------------------------------------------------------------------------
# Candidate menus depend only on the task's *content* and the option fields
# below — memoize them so coordinate-descent sweeps and repeated solves of
# the same kernel (benchmark tables re-solve per mode/budget/seed) stop
# recomputing identical menus.  FusedTask is mutable/unhashable, so keys are
# content-derived, never identity-derived.  Bounded: long-lived processes
# sweeping many (graph, mode, scale) combinations must not grow forever.
_CAND_MEMO: dict[tuple, object] = {}
_CAND_MEMO_MAX = 1024


def _memo_put(key: tuple, value):
    if len(_CAND_MEMO) >= _CAND_MEMO_MAX:
        _CAND_MEMO.pop(next(iter(_CAND_MEMO)))      # FIFO eviction
    _CAND_MEMO[key] = value
    return value


def _task_key(task: FusedTask) -> tuple:
    return (task.tid, task.name,
            tuple(s.content_key() for s in task.statements))


def _opts_key(opts: SolverOptions) -> tuple:
    return (opts.mode, opts.max_tile, tuple(opts.tile_menu),
            opts.max_options_per_loop)


def candidate_tiles(task: FusedTask, opts: SolverOptions) \
        -> dict[str, list[TileOption]]:
    """Per-loop tile options under the mode's transformation capabilities
    (memoized on task content — callers must not mutate the menus)."""
    key = ("tiles", _task_key(task), _opts_key(opts))
    hit = _CAND_MEMO.get(key)
    if hit is None:
        hit = _memo_put(key, _candidate_tiles(task, opts))
    return hit


def _candidate_tiles(task: FusedTask, opts: SolverOptions) \
        -> dict[str, list[TileOption]]:
    caps = opts.caps
    tcs = task.trip_counts
    out: dict[str, list[TileOption]] = {}
    main = task.main
    multiples = block_multiples(task)
    for loop in task.loops:
        tc = tcs[loop]
        if loop not in main.loops:
            # Loops private to fused pointwise statements (traced chains keep
            # per-statement iterators): pin to the full extent — the tail is
            # evaluated whole per output tile, and enumerating tiles here
            # would multiply the search space without changing the kernel.
            out[loop] = [TileOption(tc, tc, tc)]
            continue
        if not caps.tiling:
            if opts.mode == "streamhls":
                # parallelism only via FIFO width on the innermost loop
                if loop == main.loops[-1]:
                    opts_l = [t for t in tile_options(tc, 0, max_tile=16)]
                else:
                    opts_l = [TileOption(1, tc, tc)]
            elif opts.mode == "autodse":
                # pragma unroll on the innermost loop, divisors only
                if loop == main.loops[-1]:
                    opts_l = [t for t in tile_options(tc, 0, max_tile=64)]
                else:
                    opts_l = [TileOption(1, tc, tc)]
            else:
                opts_l = [TileOption(1, tc, tc)]
        else:
            max_pad = max(16, tc // 8) if caps.padding else 0
            opts_l = tile_options(tc, max_pad=max_pad, max_tile=opts.max_tile)
        out[loop] = _legal_tiles(opts_l, tc, multiples.get(loop, 1), opts)
    return out


def _legal_tiles(options: list[TileOption], tc: int, multiple: int,
                 opts: SolverOptions) -> list[TileOption]:
    """The mode's options whose blocks the TPU compiler accepts — a tile
    that is a multiple of ``multiple`` or the loop's full padded extent
    (the Mosaic block rule, ``costmodel.block_multiples``) — pruned to a
    small menu.  Every mode gets the rule, so a graph has one plan whatever
    kernel implementation runs it.  When none of the mode's options is
    legal, the smallest legal tile stands in: ``multiple`` itself (the
    extent padded up to it) or, for a shorter loop, its full extent."""
    legal = [t for t in options
             if t.tile % multiple == 0 or t.tile == t.padded_tc]
    if not legal:
        return [TileOption(multiple, -(-tc // multiple) * multiple, tc)
                if tc > multiple else TileOption(tc, tc, tc)]
    return _prune_tiles(legal, tc, opts)


def _prune_tiles(options: list[TileOption], tc: int,
                 opts: SolverOptions) -> list[TileOption]:
    """Keep a small, well-spread menu: tile=1, the full unpadded extent,
    aligned (8-multiple) sizes from the menu, and the largest plain
    divisors — the shapes the MXU/VPU and the HBM bursts care about."""
    by_tile = {}
    for t in options:
        cur = by_tile.get(t.tile)
        if cur is None or t.padded_tc < cur.padded_tc:
            by_tile[t.tile] = t
    keep: dict[int, TileOption] = {}

    def add(tile: int) -> None:
        if tile in by_tile and tile not in keep:
            keep[tile] = by_tile[tile]

    add(1)
    add(tc)                                   # full extent, no padding
    for m in sorted((x for x in opts.tile_menu if x > 1), reverse=True):
        if len(keep) >= opts.max_options_per_loop:
            break
        add(m)
    # largest plain (unpadded) divisors — the Sisyphus-style choices
    plain = sorted((t.tile for t in by_tile.values()
                    if t.pad == 0 and t.tile not in keep), reverse=True)
    for d in plain[:2]:
        if len(keep) >= opts.max_options_per_loop + 2:
            break
        add(d)
    return sorted(keep.values(), key=lambda t: t.tile)


def candidate_perms(task: FusedTask, opts: SolverOptions) \
        -> list[tuple[str, ...]]:
    """Legal inter-tile loop orders for the task (memoized on content)."""
    key = ("perms", _task_key(task), _opts_key(opts))
    hit = _CAND_MEMO.get(key)
    if hit is None:
        hit = _memo_put(key, _candidate_perms(task, opts))
    return hit


def _candidate_perms(task: FusedTask, opts: SolverOptions) \
        -> list[tuple[str, ...]]:
    main = task.main
    perms = legal_permutations(main)
    if not opts.caps.permutation:
        red = [l for l in main.loops if l in main.reduction_loops]
        par = [l for l in main.loops if l not in red]
        perms = [tuple(par) + tuple(red)]
    # Extend with any extra loops from other fused statements (appended at
    # their natural position: before the reductions).
    extra = [l for l in task.loops if l not in main.loops]
    if extra:
        perms = [p[:len(p) - len(main.reduction_loops)] + tuple(extra)
                 + p[len(p) - len(main.reduction_loops):] for p in perms]
    return perms


def _placement_options(task: FusedTask, perm: tuple[str, ...],
                       tiles: dict[str, TileOption], fg: FusedGraph,
                       hw: Hardware, opts: SolverOptions, array: str,
                       is_output: bool, overlap: bool = True) \
        -> list[ArrayPlacement]:
    """Enumerate (transfer level, define level) for one array under a given
    buffering regime, pruned to the Pareto frontier of
    (transfer bytes, buffer bytes).  ``overlap`` sets N_a (paper Table 2):
    2 for double-buffered streams, 1 otherwise."""
    caps = opts.caps
    n_levels = len(perm)
    main = task.main
    red = set(main.reduction_loops)
    n_nonred = len([l for l in perm if l not in red])
    buffers = 2 if (caps.overlap and overlap) else 1
    if is_output:
        # Output-stationary: store once per output tile — at the level just
        # below the last non-reduction loop, or hoisted fully (level 0).
        return [ArrayPlacement(transfer_level=lv, define_level=lv,
                               buffers=buffers)
                for lv in sorted({0, n_nonred})]
    if not caps.tiling and opts.mode in ("streamhls", "autodse"):
        # on-chip / whole-array assumption: everything loaded up front.
        # When the array does not fit VMEM (TPU-scale data), the
        # assumption breaks — model the buffer as HBM-resident, re-
        # streamed per innermost tile (the paper's critique of this
        # assumption, §2.3: "often results in low QoR on real hardware").
        cfg0 = TaskConfig(perm=perm, tiles=tiles, placements={}, slice_id=0)
        whole = footprint_elems(cfg0, task, array, 0) \
            * fg.graph.arrays[array].dtype_bytes
        if whole <= hw.vmem:
            return [ArrayPlacement(0, 0, buffers=1)]
        return [ArrayPlacement(n_levels, n_levels, buffers=1)]
    scored: list[tuple[float, float, ArrayPlacement]] = []
    for lv in range(0, n_levels + 1):
        for dv in sorted({0, lv}):
            pl = ArrayPlacement(transfer_level=lv, define_level=dv,
                                buffers=buffers)
            cfg = TaskConfig(perm=perm, tiles=tiles,
                             placements={array: pl}, slice_id=0)
            tile_b = footprint_elems(cfg, task, array, lv) \
                * fg.graph.arrays[array].dtype_bytes
            cnt = n_transfers(cfg, task, array, pl)
            buf_b = footprint_elems(cfg, task, array, dv) \
                * fg.graph.arrays[array].dtype_bytes * buffers
            if buf_b > hw.vmem:
                continue
            scored.append((cnt * tile_b, buf_b, pl))
    # Pareto prune on (transfer bytes, buffer bytes)
    scored.sort(key=lambda x: (x[0], x[1]))
    front: list[tuple[float, float, ArrayPlacement]] = []
    best_buf = float("inf")
    for tb, bb, pl in scored:
        if bb < best_buf - 1e-9:
            front.append((tb, bb, pl))
            best_buf = bb
    return [pl for (_, _, pl) in front[:4]] or \
        [ArrayPlacement(n_levels, n_levels, buffers=buffers)]


# ---------------------------------------------------------------------------
# Per-task enumeration
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TaskChoice:
    cfg: TaskConfig
    report: TaskReport


def _eval_combo(task: FusedTask, fg: FusedGraph, hw: Hardware,
                opts: SolverOptions, perm: tuple[str, ...],
                tiles: dict[str, TileOption],
                per_combo: int) -> tuple[list[TaskChoice], int]:
    """Evaluate every placement option of one (perm, tiles) point; returns
    the ``per_combo`` locally-best feasible choices and the number of
    placements evaluated.  Shared verbatim by the serial sweep and the
    process-pool workers so both paths score identically."""
    sl = hw.slices[0]
    if kernel_vmem_bytes(task, tiles) > sl.vmem:
        return [], 0                  # the kernel's blocks overflow VMEM
    reads = task.read_arrays()
    overlap_opts = (True, False) if opts.caps.overlap else (False,)
    local: list[TaskChoice] = []
    n = 0
    for overlap in overlap_opts:   # N_a: buffering is a variable
        out_opts = _placement_options(
            task, perm, tiles, fg, hw, opts, task.output_array,
            is_output=True, overlap=overlap)
        read_opts = [
            _placement_options(task, perm, tiles, fg, hw, opts, a,
                               is_output=False, overlap=overlap)
            for a in reads]
        for out_pl in out_opts:
            for read_sel in itertools.product(*read_opts) \
                    if read_opts else [()]:
                placements = dict(zip(reads, read_sel))
                placements[task.output_array] = out_pl
                cfg = TaskConfig(perm=perm, tiles=tiles,
                                 placements=placements, slice_id=0)
                rep = task_report(task, cfg, fg, hw)
                n += 1
                if rep.vmem_bytes > sl.vmem:
                    continue
                local.append(TaskChoice(cfg, rep))
    local.sort(key=lambda c: c.report.latency_s)
    return local[:per_combo], n


# Pruning margin for the parallel sweep's compute-only lower bound: a
# (perm, tiles) point is skipped when even its *compute floor* (padded
# FLOPs at its alignment efficiency — invariant under placement, routing
# and slice assignment) exceeds this multiple of the best full local
# latency already found.  > 1 keeps headroom for the global phase's
# rewiring, which can only make the *kept* candidates cheaper.
_PRUNE_MARGIN = 2.0


def _combo_lower_bound(task: FusedTask, tiles: dict[str, TileOption],
                       sl) -> float:
    """Lower bound on any placement's latency for (task, tiles): the MXU
    time of the padded compute at the output block's alignment efficiency.
    ``task_report``'s latency is >= t_mxu x total tile executions, which
    is exactly this quantity, for every placement choice."""
    main = task.main
    flops = main.flops_per_iter * main.density
    for l in main.loops:
        flops *= tiles[l].padded_tc
    out_acc = _access_of(task, task.output_array)
    eff = alignment_efficiency([tiles[it].tile for it in out_acc.iters])
    return flops / max(sl.flops * eff, 1.0)


def _decode_combo(menu_lists: list[list[TileOption]], loops: list[str],
                  idx: int) -> dict[str, TileOption]:
    """Map a flat combo index to the tile selection ``itertools.product``
    would emit at that position (first menu varies slowest) — workers
    address sweep points by index instead of shipping the selections.
    Insertion order matches ``dict(zip(loops, sel))`` exactly: tile dicts
    feed ``repr``-based plan fingerprints, so key order is identity."""
    digits: list[int] = []
    for menu in reversed(menu_lists):
        idx, r = divmod(idx, len(menu))
        digits.append(r)
    digits.reverse()
    return {loop: menu[d]
            for loop, menu, d in zip(loops, menu_lists, digits)}


def enumerate_task(task: FusedTask, fg: FusedGraph, hw: Hardware,
                   opts: SolverOptions, stats: SolveStats, deadline: float,
                   per_combo: int = 2, cap: int = 2048,
                   pool: "_SweepPool | None" = None) -> list[TaskChoice]:
    """Candidate configs for one task, sorted by local latency.

    Keeps the ``per_combo`` best placement combos for every (perm, tiles)
    pair so the global phase (which rewires edges to on-chip buffers or ICI
    streams and re-costs) can coordinate-descend over a rich list.  Local
    costs assume off-chip edges — a lower bound refined globally.

    With a live ``pool`` (workers > 1) the (perm, tiles) grid is split
    into chunked work units fanned out to worker processes, with the
    best-so-far latency shared between waves as a pruning bound."""
    perms = candidate_perms(task, opts)
    tiles_menu = candidate_tiles(task, opts)
    loops = list(task.loops)
    combos = 1
    for l in loops:
        combos *= len(tiles_menu[l])
    stats.space_size += len(perms) * combos

    if pool is not None and pool.alive \
            and len(perms) * combos >= opts.min_parallel_units:
        result = _enumerate_task_parallel(task, fg, hw, opts, stats,
                                          deadline, per_combo, cap, pool,
                                          perms, tiles_menu, loops, combos)
        if result is not None:
            return result
        # broken pool: fall through to the serial sweep below

    out: list[TaskChoice] = []
    for perm in perms:
        for tile_sel in itertools.product(*(tiles_menu[l] for l in loops)):
            # honour the deadline only once at least one feasible config
            # exists (under heavy CPU contention the budget can elapse
            # before the first evaluation — never return empty-handed)
            if out and time.monotonic() > deadline:
                stats.timed_out = True
                return _sorted_choices(out, cap)
            tiles = dict(zip(loops, tile_sel))
            local, n = _eval_combo(task, fg, hw, opts, perm, tiles,
                                   per_combo)
            stats.n_evaluated += n
            out.extend(local)
    return _sorted_choices(out, cap)


def _enumerate_task_parallel(task, fg, hw, opts, stats, deadline, per_combo,
                             cap, pool, perms, tiles_menu, loops,
                             combos) -> "list[TaskChoice] | None":
    """Fan the (perm, tiles) grid out to the process pool in deterministic
    waves.  The pruning bound only advances between waves (from the merged
    results of ALL earlier waves), so the evaluated set — and therefore
    the candidate list — is a pure function of (task, opts, workers),
    independent of worker scheduling."""
    menu_lists = [tiles_menu[l] for l in loops]
    chunk = max(16, -(-combos * len(perms) // (pool.workers * 8)))
    payloads: list[tuple] = []
    for pi in range(len(perms)):
        start = 0
        while start < combos:
            payloads.append((task.tid, pi, start,
                             min(start + chunk, combos), per_combo))
            start += chunk

    # Seed the pruning bound before the first wave: one aligned, largest-
    # tile point evaluated in-process (its chunk re-evaluates it later —
    # a duplicate costing one combo, never a lost candidate).
    bound = float("inf")
    seed_tiles = {l: menu[-1] for l, menu in zip(loops, menu_lists)}
    seeded, n = _eval_combo(task, fg, hw, opts, perms[0], seed_tiles,
                            per_combo)
    stats.n_evaluated += n
    for c in seeded:
        bound = min(bound, c.report.latency_s)

    out: list[TaskChoice] = []
    wave = pool.workers * 2
    try:
        for i in range(0, len(payloads), wave):
            now = time.monotonic()
            if out and now > deadline:
                stats.timed_out = True
                break
            budget = max(deadline - now, 0.25)
            futs = [pool.submit(_w_enum_chunk, p + (bound, budget))
                    for p in payloads[i:i + wave]]
            for f in futs:
                choices, n_eval, timed_out = f.result()
                stats.n_evaluated += n_eval
                stats.timed_out |= timed_out
                out.extend(choices)
            for c in out:
                bound = min(bound, c.report.latency_s)
    except (concurrent.futures.process.BrokenProcessPool, OSError):
        pool.alive = False
        return None
    return _sorted_choices(out, cap)


def _sorted_choices(choices: list[TaskChoice], cap: int) -> list[TaskChoice]:
    return sorted(choices, key=lambda c: (c.report.latency_s,
                                          c.report.vmem_bytes))[:cap]


# ---------------------------------------------------------------------------
# Process-pool sweep infrastructure
# ---------------------------------------------------------------------------
# Worker-process context, installed once per worker by the pool initializer
# (the fused graph, hardware and options are pickled exactly once per
# worker, not once per chunk — chunks carry only indices and bounds).
# repro.core is deliberately JAX-free, so workers never pay a JAX import.
_WORKER_CTX: tuple | None = None


def _pool_init(fg: FusedGraph, hw: Hardware, opts: SolverOptions) -> None:
    global _WORKER_CTX
    # A chip belongs to one process: should anything in a worker import
    # jax after all, it gets the CPU backend, never the parent's chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _WORKER_CTX = (fg, hw, opts)


def _w_jax_imported(_=None) -> bool:
    return "jax" in sys.modules


def sweep_workers_import_jax(workers: int = 2) -> bool:
    """Start a sweep pool as :func:`solve` does and report whether any
    worker has imported jax.  ``False`` means no worker can have started a
    JAX backend, so none competes for the chip its parent holds."""
    pool = _SweepPool(workers, None, None, None)
    try:
        futs = [pool.submit(_w_jax_imported) for _ in range(2 * workers)]
        return any(f.result() for f in futs)
    finally:
        pool.shutdown()


class _SweepPool:
    """A per-solve ``ProcessPoolExecutor`` whose workers hold the solve
    context as process globals.  ``fork`` start where available (cheap,
    inherits the warm interpreter); ``spawn`` elsewhere — workers then
    re-import ``repro.core`` only.

    ``alive`` flips to False the first time the pool breaks (workers
    killed, spawn unable to re-import an interactive ``__main__``, fd
    exhaustion...); every call site then falls back to the serial sweep —
    a broken pool degrades throughput, never the solve."""

    def __init__(self, workers: int, fg: FusedGraph, hw: Hardware,
                 opts: SolverOptions):
        self.workers = workers
        self.alive = True
        # fork is cheap but unsafe once JAX's runtime threads exist
        # (os.fork + multithreaded XLA can deadlock the child); spawn
        # re-imports only the JAX-free repro.core chain, so it stays
        # correct — just slower to start — whenever jax is loaded.
        if sys.platform.startswith("linux") and "jax" not in sys.modules:
            method = "fork"
        else:
            method = "spawn"
        ctx = multiprocessing.get_context(method)
        self._ex = concurrent.futures.ProcessPoolExecutor(
            max_workers=workers, mp_context=ctx,
            initializer=_pool_init, initargs=(fg, hw, opts))

    def submit(self, fn, *args):
        return self._ex.submit(fn, *args)

    def shutdown(self) -> None:
        self._ex.shutdown(wait=True, cancel_futures=True)


def _w_enum_chunk(payload: tuple) -> tuple[list[TaskChoice], int, bool]:
    """Worker: evaluate combo indices [start, stop) of one permutation.

    Refines the shipped pruning bound with its own discoveries as it
    scans (deterministic: sequential within the chunk).  Honors the
    remaining time budget, but — like the serial sweep — never before
    producing at least one feasible choice."""
    tid, perm_idx, start, stop, per_combo, bound, budget_s = payload
    fg, hw, opts = _WORKER_CTX
    task = fg.tasks[tid]
    sl = hw.slices[0]
    perm = candidate_perms(task, opts)[perm_idx]
    tiles_menu = candidate_tiles(task, opts)
    loops = list(task.loops)
    menu_lists = [tiles_menu[l] for l in loops]
    deadline = time.monotonic() + budget_s
    choices: list[TaskChoice] = []
    n_eval = 0
    timed_out = False
    for ci in range(start, stop):
        if choices and time.monotonic() > deadline:
            timed_out = True
            break
        tiles = _decode_combo(menu_lists, loops, ci)
        if bound < float("inf") and \
                _combo_lower_bound(task, tiles, sl) > bound * _PRUNE_MARGIN:
            continue
        local, n = _eval_combo(task, fg, hw, opts, perm, tiles, per_combo)
        n_eval += n
        choices.extend(local)
        for c in local:
            bound = min(bound, c.report.latency_s)
    return choices, n_eval, timed_out


def _w_eval_chunk(payload: tuple) -> tuple[float, int, int]:
    """Worker: score trial plans against the global DAG objective.

    One of the coordinate-descent inner loops, chunked: the base choice
    (one ``TaskChoice`` per task) is fixed; each element of ``cands``
    swaps task ``tid``'s choice (or, with ``tid is None``, swaps the
    slice assignment).  Candidates whose compute floor already exceeds
    the incumbent makespan are skipped — sound, because any plan's
    makespan >= each task's compute time under every routing.  Returns
    (best latency, its candidate index, evaluations)."""
    tid, base, assign, cands, bound, budget_s = payload
    fg, hw, opts = _WORKER_CTX
    deadline = time.monotonic() + budget_s
    best_lat, best_idx, n_eval = float("inf"), -1, 0
    for idx, cand in cands:
        if n_eval and time.monotonic() > deadline:
            break
        if tid is not None:
            if bound < float("inf") and cand.report.compute_s >= bound:
                continue
            trial = dict(base)
            trial[tid] = cand
            lat, _, _ = _evaluate(fg, trial, assign, hw, opts)
        else:
            lat, _, _ = _evaluate(fg, base, cand, hw, opts)
        n_eval += 1
        if lat < best_lat:
            best_lat, best_idx = lat, idx
    return best_lat, best_idx, n_eval


def _parallel_argmin(pool: "_SweepPool", tid, base: dict, assign,
                     cands: list[tuple], bound: float, deadline: float) \
        -> "tuple[float, int, int] | None":
    """Chunk one coordinate's candidates across the pool and merge to the
    argmin.  Merging walks chunks in submission order with a strict ``<``,
    so ties resolve to the lowest candidate index — the same winner the
    serial scan picks.  ``None`` when the pool broke (caller goes serial).
    """
    budget = max(deadline - time.monotonic(), 0.25)
    chunk = max(8, -(-len(cands) // (pool.workers * 2)))
    try:
        with _obs_tracer().span("chunk_merge", "solver", task=tid,
                                candidates=len(cands), chunk=chunk) as sp:
            futs = [pool.submit(_w_eval_chunk,
                                (tid, base, assign, cands[s:s + chunk], bound,
                                 budget))
                    for s in range(0, len(cands), chunk)]
            best_lat, best_idx, n_eval = float("inf"), -1, 0
            for f in futs:
                lat, idx, ne = f.result()
                n_eval += ne
                if lat < best_lat:
                    best_lat, best_idx = lat, idx
            sp.set(chunks=len(futs), n_evaluated=n_eval)
    except (concurrent.futures.process.BrokenProcessPool, OSError):
        pool.alive = False
        return None
    return best_lat, best_idx, n_eval


def _pool_for(fg: FusedGraph, hw: Hardware,
              opts: SolverOptions) -> "_SweepPool | None":
    """A sweep pool when the options ask for one, else None (serial)."""
    workers = opts.effective_workers
    if workers <= 1:
        return None
    try:
        return _SweepPool(workers, fg, hw, opts)
    except (OSError, ValueError):    # no fork/sem support: stay serial
        return None


# ---------------------------------------------------------------------------
# Edge routing: shared on-chip buffer (same slice) vs ICI stream (cross)
# ---------------------------------------------------------------------------
def _rewire_edges(fg: FusedGraph, choice: dict[int, TaskChoice],
                  assign: dict[int, int], hw: Hardware,
                  opts: SolverOptions) -> dict[int, TaskConfig]:
    """Route each dataflow edge and rewrite BOTH endpoint placements.

    Routing per edge:
      same slice  -> shared VMEM buffer handoff when the consumer buffer
                     fits (``onchip``), else HBM bounce;
      cross slice -> the bytes traverse ICI either way (distributed
                     memory), so both endpoints are marked ``stream``;
                     whether the consumer may *start early* (the paper's
                     FIFO shift, Eq. 12) is decided in ``dag_latency`` from
                     emission-order compatibility.
    A producer feeding several consumers takes the most conservative
    routing (HBM if any edge bounces, stream if any crosses slices).
    """
    cfgs: dict[int, TaskConfig] = {}
    for t in fg.tasks:
        cfgs[t.tid] = dataclasses.replace(choice[t.tid].cfg,
                                          slice_id=assign[t.tid])
    producer_route: dict[int, set[str]] = {t.tid: set() for t in fg.tasks}
    for (u, v, arr) in fg.edges:
        ccfg = cfgs[v]
        if arr not in ccfg.placements:
            continue
        pl = ccfg.placements[arr]
        same = assign[u] == assign[v]
        if same:
            consumer = fg.tasks[v]
            buf = footprint_elems(ccfg, consumer, arr, pl.define_level) \
                * fg.graph.arrays[arr].dtype_bytes * pl.buffers
            if buf <= hw.vmem:
                new = pl.replace(onchip=True, stream=False)
                producer_route[u].add("onchip")
            else:
                new = pl.replace(onchip=False, stream=False)
                producer_route[u].add("hbm")
        else:
            new = pl.replace(stream=True, onchip=False)
            producer_route[u].add("stream")
        placements = dict(ccfg.placements)
        placements[arr] = new
        cfgs[v] = dataclasses.replace(ccfg, placements=placements)
    # Producer output placements
    for (u, v, arr) in fg.edges:
        ucfg = cfgs[u]
        out_arr = fg.tasks[u].output_array
        if out_arr != arr or out_arr not in ucfg.placements:
            continue
        routes = producer_route[u]
        upl = ucfg.placements[out_arr]
        if "hbm" in routes or not routes:
            new = upl.replace(stream=False, onchip=False)
        elif "stream" in routes:
            new = upl.replace(stream=True, onchip=False)
        else:
            new = upl.replace(onchip=True, stream=False)
        uplace = dict(ucfg.placements)
        uplace[out_arr] = new
        cfgs[u] = dataclasses.replace(ucfg, placements=uplace)
    return cfgs


# ---------------------------------------------------------------------------
# Global phase: slice assignment + config choice
# ---------------------------------------------------------------------------
def _evaluate(fg: FusedGraph, choice: dict[int, TaskChoice],
              assign: dict[int, int], hw: Hardware, opts: SolverOptions) \
        -> tuple[float, dict[int, TaskConfig], dict[int, TaskReport]]:
    cfgs = _rewire_edges(fg, choice, assign, hw, opts)
    lat, reports = plan_latency(fg, cfgs, hw)
    # VMEM feasibility after rewiring (on-chip buffers count on both sides)
    for t in fg.tasks:
        if reports[t.tid].vmem_bytes > hw.slices[assign[t.tid]].vmem:
            lat = float("inf")
    return lat, cfgs, reports


def default_hardware(n_slices: int = 3) -> Hardware:
    """The board ``solve`` uses when the caller passes ``hw=None``: this
    host's cached calibrated profile (``repro.calibrate``) so slice and
    stream decisions answer to measured rates, falling back to the static
    TPU v5e constants when the host was never calibrated.  Never measures —
    run ``scripts/calibrate.py`` (or ``repro.calibrate.calibrate()``) once
    per host to materialize the profile.

    Raises on a TPU whose ``device_kind`` is not a v5e: the static
    constants would price it wrong.  A process that has not imported jax
    holds no chip, and is not asked."""
    from ..calibrate import cached_hardware
    hw = cached_hardware(n_slices=n_slices)
    if hw is not None:
        return hw
    jax = sys.modules.get("jax")
    if jax is not None:
        dev = jax.devices()[0]
        if dev.platform == "tpu" and dev.device_kind not in TPU_KINDS:
            raise RuntimeError(
                f"no hardware model for {dev.device_kind!r}: the static "
                f"board describes {TPU_KINDS[0]!r} (core/resources.py)")
    return THREE_SLICE if n_slices == 3 else Hardware.make(n_slices=n_slices)


def _resolve_store(store):
    """``"auto"`` -> the env-configured default store (None when
    ``REPRO_PLAN_STORE_DIR`` is unset), ``None`` -> disabled, anything
    else is used as a ``PlanStore`` directly."""
    if store is None:
        return None
    if store == "auto":
        from ..store import default_store
        return default_store()
    return store


def _sweep_units(fg: FusedGraph, opts: SolverOptions) -> int:
    """Total (perm, tiles) points across tasks — decides whether spinning
    up a process pool can pay for itself."""
    total = 0
    for t in fg.tasks:
        combos = 1
        for l in t.loops:
            combos *= len(candidate_tiles(t, opts)[l])
        total += len(candidate_perms(t, opts)) * combos
    return total


def solve(graph: TaskGraph, hw: Hardware | None = None,
          opts: SolverOptions | None = None, *, store="auto",
          allow_stale: bool = False, refresh: bool = False) -> ExecutionPlan:
    """Solve ``graph`` for ``hw`` under ``opts``.

    ``store`` routes the persistent plan store (``repro.store``): the
    default ``"auto"`` uses the ``REPRO_PLAN_STORE_DIR``-configured store
    when one is set (hit -> return the stored plan with ``store_hit=True``
    and zero evaluations; solve -> persist the result), ``None`` disables
    it, or pass a ``PlanStore``.  ``allow_stale`` additionally accepts a
    stored plan keyed to an older hardware fingerprint (``stale_hw=True``
    on the result — callers should schedule a background ``refresh``).
    ``refresh=True`` skips the lookup (never trust the entry being
    replaced) but still persists the fresh result.
    """
    opts = opts or SolverOptions()
    if hw is None:
        hw = default_hardware()
    caps = opts.caps
    t0 = time.monotonic()
    deadline = t0 + opts.time_budget_s

    st = _resolve_store(store)
    if st is not None and not refresh:
        hit = st.load(graph, hw, opts, allow_stale=allow_stale)
        if hit is not None:
            hit.solver_seconds = time.monotonic() - t0
            return hit

    stats = SolveStats()
    tr = _obs_tracer()
    with tr.span("fuse", "solver", statements=len(graph.statements)) as sp:
        fg = fuse(graph)
        sp.set(fused_tasks=len(fg.tasks))
    pool = None
    if opts.effective_workers > 1 and \
            _sweep_units(fg, opts) >= opts.min_parallel_units:
        pool = _pool_for(fg, hw, opts)
    try:
        with tr.span("enumerate", "solver", mode=opts.mode,
                     joint=caps.joint_search,
                     workers=0 if pool is None else pool.workers) as sp:
            if caps.joint_search:
                plan = _solve_joint(fg, hw, opts, stats, deadline, pool)
            else:
                plan = _solve_decomposed(fg, hw, opts, stats, deadline, pool)
            sp.set(n_evaluated=stats.n_evaluated, timed_out=stats.timed_out)
    finally:
        if pool is not None:
            pool.shutdown()
    plan.solver_seconds = time.monotonic() - t0
    plan.n_evaluated = stats.n_evaluated
    plan.mode = opts.mode
    plan.space_size = stats.space_size
    plan.timed_out = stats.timed_out
    if st is not None:
        st.save(graph, hw, opts, plan)
    return plan


def _solve_decomposed(fg: FusedGraph, hw: Hardware, opts: SolverOptions,
                      stats: SolveStats, deadline: float,
                      pool: _SweepPool | None = None) -> ExecutionPlan:
    """Prometheus decomposition (paper §6.4): dataflow decouples tasks, so
    the search is per-task candidate lists + a global placement phase
    (slice assignment x candidate picks) refined by coordinate descent on
    the true DAG objective.  Effective work is SUM of per-task spaces times
    a few sweeps — not the PRODUCT the shared-buffer formulation needs."""
    caps = opts.caps
    per_task = {t.tid: enumerate_task(t, fg, hw, opts, stats, deadline,
                                      pool=pool)
                for t in fg.tasks}
    for tid, cands in per_task.items():
        if not cands:
            raise RuntimeError(f"no feasible config for task {tid} "
                               f"(VMEM too small?)")
    n_slices = hw.n_slices if (caps.concurrency and caps.multi_slice) else 1
    tids = [t.tid for t in fg.tasks]

    best = (float("inf"), None, None, None)
    pick = {tid: 0 for tid in tids}
    assign = {tid: 0 for tid in tids}

    def evaluate(assign_: dict[int, int], pick_: dict[int, int]) -> float:
        nonlocal best
        choice = {tid: per_task[tid][pick_[tid]] for tid in tids}
        lat, cfgs, reports = _evaluate(fg, choice, assign_, hw, opts)
        stats.n_evaluated += 1
        if lat < best[0]:
            best = (lat, dict(assign_), cfgs, reports)
        return lat

    def assignment_search(pick_: dict[int, int]) -> dict[int, int]:
        """Exact slice-assignment enumeration (symmetry-broken) for small
        graphs, greedy + local moves otherwise."""
        if n_slices == 1:
            return {tid: 0 for tid in tids}
        best_a = (float("inf"), {tid: 0 for tid in tids})
        if len(tids) <= 7:
            assigns = []
            for combo in itertools.product(range(n_slices),
                                           repeat=len(tids) - 1):
                a = {tids[0]: 0}
                for tid, s in zip(tids[1:], combo):
                    a[tid] = s
                assigns.append(a)
            if pool is not None and pool.alive and len(assigns) >= 64:
                base = {tid: per_task[tid][pick_[tid]] for tid in tids}
                res = _parallel_argmin(
                    pool, None, base, None,
                    list(enumerate(assigns)), float("inf"), deadline)
                if res is not None:
                    lat, idx, n_eval = res
                    stats.n_evaluated += n_eval
                    if idx >= 0:
                        # one in-process re-eval of the winner records its
                        # cfgs/reports in ``best``
                        evaluate(assigns[idx], pick_)
                        best_a = (lat, dict(assigns[idx]))
                    if time.monotonic() > deadline:
                        stats.timed_out = True
                    return best_a[1]
            for a in assigns:
                lat = evaluate(a, pick_)
                if lat < best_a[0]:
                    best_a = (lat, dict(a))
                if time.monotonic() > deadline:
                    stats.timed_out = True
                    break
        else:
            rng = random.Random(opts.seed)
            a = {tid: tid % n_slices for tid in tids}
            cur = evaluate(a, pick_)
            best_a = (cur, dict(a))
            for it in range(opts.anneal_iters):
                if time.monotonic() > deadline:
                    stats.timed_out = True
                    break
                tid = rng.choice(tids)
                old = a[tid]
                a[tid] = rng.randrange(n_slices)
                lat = evaluate(a, pick_)
                temp = max(1e-12, 1.0 - it / max(opts.anneal_iters, 1))
                if lat < cur or rng.random() < temp * 0.05:
                    cur = lat
                    if lat < best_a[0]:
                        best_a = (lat, dict(a))
                else:
                    a[tid] = old
        return best_a[1]

    evaluate(assign, pick)
    assign = assignment_search(pick)

    # Coordinate descent over per-task candidate lists against the global
    # DAG objective, interleaved with assignment re-search.  One tid's
    # inner loop is an argmin over its candidate list with the others
    # fixed — which is what the chunked parallel path computes, skipping
    # candidates whose compute floor already exceeds the incumbent.
    for _sweep in range(6):
        improved = False
        for tid in tids:
            cur_lat = best[0]
            cur_k = pick[tid]
            if pool is not None and pool.alive and len(per_task[tid]) >= 32:
                base = {t: per_task[t][pick[t]] for t in tids}
                cands = [(k, per_task[tid][k])
                         for k in range(len(per_task[tid])) if k != cur_k]
                res = _parallel_argmin(
                    pool, tid, base, assign, cands, cur_lat, deadline)
                if res is not None:
                    lat, k, n_eval = res
                    stats.n_evaluated += n_eval
                    if k >= 0 and lat < cur_lat:
                        trial = dict(pick)
                        trial[tid] = k
                        evaluate(assign, trial)     # records cfgs/reports
                        pick = trial
                        improved = True
                    if time.monotonic() > deadline:
                        stats.timed_out = True
                        break
                    continue
            for k in range(len(per_task[tid])):
                if time.monotonic() > deadline:
                    stats.timed_out = True
                    break
                if k == cur_k:
                    continue
                trial = dict(pick)
                trial[tid] = k
                lat = evaluate(assign, trial)
                if lat < cur_lat:
                    cur_lat = lat
                    pick = trial
                    improved = True
            if time.monotonic() > deadline:
                break
        if improved and n_slices > 1:
            new_assign = assignment_search(pick)
            if new_assign != assign:
                assign = new_assign
                continue
        if not improved or time.monotonic() > deadline:
            break

    lat, assign, cfgs, reports = best
    if cfgs is None:
        raise RuntimeError("solver found no feasible plan")
    useful = sum(t.flops for t in fg.tasks)
    return ExecutionPlan(graph_name=fg.graph.name, configs=cfgs,
                         reports=reports, latency_s=lat,
                         useful_flops=useful)


def _joint_choice(task: FusedTask, fg: FusedGraph, hw: Hardware,
                  opts: SolverOptions, perm, tiles) -> TaskChoice | None:
    """Min-transfer placements, greedily demoted (next Pareto option:
    smaller buffer, more transfers) until the joint VMEM budget fits.
    Module-level (not a closure) so pool workers run it too."""
    if kernel_vmem_bytes(task, tiles) > hw.slices[0].vmem:
        return None
    reads = task.read_arrays()
    options: dict[str, list[ArrayPlacement]] = {}
    for a in reads:
        options[a] = _placement_options(task, perm, tiles, fg, hw,
                                        opts, a, is_output=False)
    out_arr = task.output_array
    options[out_arr] = _placement_options(task, perm, tiles, fg, hw,
                                          opts, out_arr, is_output=True)
    pick = {a: 0 for a in options}

    def buf_bytes(a: str) -> float:
        pl = options[a][pick[a]]
        return footprint_elems(
            TaskConfig(perm=perm, tiles=tiles,
                       placements={a: pl}, slice_id=0),
            task, a, pl.define_level) \
            * fg.graph.arrays[a].dtype_bytes * pl.buffers

    vmem_budget = hw.slices[0].vmem
    for _ in range(sum(len(v) for v in options.values())):
        if sum(buf_bytes(a) for a in options) <= vmem_budget:
            break
        # demote the biggest buffer that still has a next option
        cand = sorted(options, key=buf_bytes, reverse=True)
        for a in cand:
            if pick[a] + 1 < len(options[a]):
                pick[a] += 1
                break
        else:
            return None
    placements = {a: options[a][pick[a]] for a in options}
    cfg = TaskConfig(perm=perm, tiles=tiles, placements=placements,
                     slice_id=0)
    rep = task_report(task, cfg, fg, hw)
    if rep.vmem_bytes > hw.slices[0].vmem:
        return None
    return TaskChoice(cfg, rep)


def _w_joint_chunk(payload: tuple) \
        -> tuple[list[tuple[int, TaskChoice | None]], int, bool]:
    """Worker: derive joint-mode choices for point indices [start, stop)
    of one task's coupled (perm x tiles) space, pruning points whose
    compute floor exceeds the shared bound."""
    tid, start, stop, bound, budget_s = payload
    fg, hw, opts = _WORKER_CTX
    task = fg.tasks[tid]
    sl = hw.slices[0]
    perms = candidate_perms(task, opts)
    tiles_menu = candidate_tiles(task, opts)
    loops = list(task.loops)
    menu_lists = [tiles_menu[l] for l in loops]
    combos = 1
    for m in menu_lists:
        combos *= len(m)
    deadline = time.monotonic() + budget_s
    results: list[tuple[int, TaskChoice | None]] = []
    n_eval = 0
    timed_out = False
    found = False
    for i in range(start, stop):
        if found and time.monotonic() > deadline:
            timed_out = True
            break
        pi, ci = divmod(i, combos)
        perm = perms[pi]
        tiles = _decode_combo(menu_lists, loops, ci)
        if bound < float("inf") and \
                _combo_lower_bound(task, tiles, sl) > bound * _PRUNE_MARGIN:
            results.append((i, None))
            continue
        ch = _joint_choice(task, fg, hw, opts, perm, tiles)
        n_eval += 1
        results.append((i, ch))
        if ch is not None:
            found = True
            bound = min(bound, ch.report.latency_s)
    return results, n_eval, timed_out


def _joint_init_parallel(pool: _SweepPool, fg: FusedGraph, tid: int,
                         spaces: dict, choice_memo: dict,
                         stats: SolveStats,
                         deadline: float) -> "list[TaskChoice] | None":
    """Fan one task's joint space across the pool in deterministic waves
    (same wave/bound discipline as the decomposed enumeration), filling
    ``choice_memo`` for the descent sweeps."""
    n = len(spaces[tid])
    chunk = max(16, -(-n // (pool.workers * 8)))
    payloads = [(tid, s, min(s + chunk, n)) for s in range(0, n, chunk)]
    cands: list[TaskChoice] = []
    bound = float("inf")
    wave = pool.workers * 2
    try:
        for i in range(0, len(payloads), wave):
            now = time.monotonic()
            if cands and now > deadline:
                stats.timed_out = True
                break
            budget = max(deadline - now, 0.25)
            futs = [pool.submit(_w_joint_chunk, p + (bound, budget))
                    for p in payloads[i:i + wave]]
            for f in futs:
                results, n_eval, timed_out = f.result()
                stats.n_evaluated += n_eval
                stats.timed_out |= timed_out
                for idx, ch in results:
                    choice_memo[(tid, idx)] = ch
                    if ch is not None:
                        cands.append(ch)
            for c in cands:
                bound = min(bound, c.report.latency_s)
    except (concurrent.futures.process.BrokenProcessPool, OSError):
        pool.alive = False
        return None
    return cands


def _solve_joint(fg: FusedGraph, hw: Hardware, opts: SolverOptions,
                 stats: SolveStats, deadline: float,
                 pool: _SweepPool | None = None) -> ExecutionPlan:
    """Sisyphus-style shared-buffer formulation: permutations and tiles are
    coupled across tasks (one product space).  This is the formulation whose
    size explodes with task count (paper Table 10: 3mm times out at 4 h).

    We record the raw product-space size (the blowup) and, like a good NLP
    solver under a time budget, navigate it with coordinate descent: sweep
    tasks, re-optimizing each against the fixed others, until a fixpoint or
    the deadline.  ``timed_out`` is set when the exhaustive space could not
    have been covered within the budget (the Table 10 condition)."""
    tids = [t.tid for t in fg.tasks]
    spaces: dict[int, list[tuple]] = {}
    for t in fg.tasks:
        perms = candidate_perms(t, opts)
        tiles_menu = candidate_tiles(t, opts)
        loops = list(t.loops)
        combos = []
        for perm in perms:
            for sel in itertools.product(*(tiles_menu[l] for l in loops)):
                combos.append((perm, dict(zip(loops, sel))))
        spaces[t.tid] = combos
    size = 1.0
    for tid in tids:
        size *= len(spaces[tid])
    stats.space_size = size

    assign = {tid: 0 for tid in tids}

    # _joint_choice is deterministic per (task, point) — memoize so the
    # coordinate-descent sweeps below re-score points instead of re-deriving
    # their placements every sweep.  A hit still counts as an evaluated
    # point: n_evaluated feeds the evals_per_s coverage estimate behind the
    # Table 10 timed_out condition, which measures points *examined*, not
    # placements derived.
    choice_memo: dict[tuple[int, int], TaskChoice | None] = {}

    def cached_choice(tid: int, idx: int) -> TaskChoice | None:
        key = (tid, idx)
        if key in choice_memo:
            stats.n_evaluated += 1
            return choice_memo[key]
        perm, tiles = spaces[tid][idx]
        choice_memo[key] = _joint_choice(fg.tasks[tid], fg, hw, opts,
                                         perm, tiles)
        stats.n_evaluated += 1
        return choice_memo[key]

    # init: per-task locally-best feasible config.  Deadline-checked —
    # a budget that elapses mid-enumeration keeps the best feasible
    # choices found so far instead of scanning on (the solve then
    # returns a best-effort plan, never raises past first-feasible).
    choice: dict[int, TaskChoice] = {}
    for tid in tids:
        cands: "list[TaskChoice] | None" = None
        if pool is not None and pool.alive \
                and len(spaces[tid]) >= opts.min_parallel_units:
            cands = _joint_init_parallel(pool, fg, tid, spaces, choice_memo,
                                         stats, deadline)
        if cands is None:
            cands = []
            for i in range(len(spaces[tid])):
                c = cached_choice(tid, i)
                if c is not None:
                    cands.append(c)
                if cands and time.monotonic() > deadline:
                    stats.timed_out = True
                    break
        if not cands:
            raise RuntimeError(f"no feasible sisyphus config for task {tid}")
        choice[tid] = min(cands, key=lambda c: c.report.latency_s)
    best = _evaluate(fg, choice, assign, hw, opts)

    improved = True
    while improved and time.monotonic() < deadline:
        improved = False
        for tid in tids:
            cur = best[0]
            if pool is not None and pool.alive and len(spaces[tid]) >= 32:
                cands2 = [(idx, choice_memo.get((tid, idx)))
                          for idx in range(len(spaces[tid]))]
                cands2 = [(i, c) for i, c in cands2 if c is not None]
                res = _parallel_argmin(
                    pool, tid, choice, assign, cands2, cur, deadline)
                if res is not None:
                    lat, idx, n_eval = res
                    stats.n_evaluated += n_eval
                    if idx >= 0 and lat < cur:
                        trial = dict(choice)
                        trial[tid] = choice_memo[(tid, idx)]
                        lat2, cfgs, reports = _evaluate(fg, trial, assign,
                                                        hw, opts)
                        choice = trial
                        best = (lat2, cfgs, reports)
                        improved = True
                    if time.monotonic() > deadline:
                        break
                    continue
            for idx in range(len(spaces[tid])):
                if time.monotonic() > deadline:
                    break
                cand = cached_choice(tid, idx)
                if cand is None:
                    continue
                trial = dict(choice)
                trial[tid] = cand
                lat, cfgs, reports = _evaluate(fg, trial, assign, hw, opts)
                if lat < cur:
                    cur = lat
                    choice = trial
                    best = (lat, cfgs, reports)
                    improved = True
    # Exhaustive coverage check: the joint product space vs what the budget
    # allowed — this is what times out for 3mm in the paper.
    evals_per_s = max(stats.n_evaluated, 1) / max(
        time.monotonic() - (deadline - opts.time_budget_s), 1e-6)
    if size > evals_per_s * opts.time_budget_s:
        stats.timed_out = True

    lat, cfgs, reports = best
    useful = sum(t.flops for t in fg.tasks)
    return ExecutionPlan(graph_name=fg.graph.name, configs=cfgs,
                         reports=reports, latency_s=lat,
                         useful_flops=useful)


# ---------------------------------------------------------------------------
# Measured execution (solve-time validation = serve-time executables)
# ---------------------------------------------------------------------------
def build_graph(name: str, scale: int = 1) -> TaskGraph:
    """One graph build per (kernel, scale) — solving, measuring and serving
    the same kernel share the graph (and therefore its fingerprint, i.e.
    its program-cache entries).  Treat the result read-only.

    ``traced:<fp16>`` names resolve through the frontend's trace cache
    (``repro.frontend.trace`` must have captured the function in this
    process), so traced workloads flow through ``measure_plan`` and the
    benchmark tables exactly like PolyBench kernels; ``scale`` does not
    apply to traced sources (shapes are frozen at trace time).  Traced
    names deliberately bypass the polybench lru: their lifetime is owned
    by the *bounded* trace cache — pinning them here would defeat its
    LRU and serve stale graphs after a re-trace.
    """
    if name.startswith("traced:"):
        from ..frontend import traced_graph
        return traced_graph(name)
    return _build_polybench(name, scale)


@functools.lru_cache(maxsize=None)
def _build_polybench(name: str, scale: int) -> TaskGraph:
    from . import polybench
    return polybench.build(name, scale=scale)


def steady_state_s(exe, ins, *, batch: int = 10, samples: int = 7) -> float:
    """Best per-call seconds over ``samples`` timed batches of ``batch``
    back-to-back calls (one block at the batch end).  The ONE timing
    methodology every benchmark uses: batching amortizes scheduler noise on
    contended hosts far better than single-call timings, and best-of
    filters the remaining interference."""
    out = exe(ins)                              # compile + warm up
    for v in out.values():
        v.block_until_ready()                   # drain async dispatch
    best = float("inf")
    for _ in range(samples):
        t0 = time.perf_counter()
        for _ in range(batch):
            out = exe(ins)
        for v in out.values():
            v.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / batch)
    return best


def measure_plan(name: str, plan: ExecutionPlan, *, graph=None,
                 scale: int = 1, impl: str | None = None, repeats: int = 3,
                 validate: bool = True, mode: str = "program",
                 pool_size: int | None = None):
    """Execute a plan through the codegen subsystem and time it.

    Returns ``(seconds, gflops, validated)`` — the measured counterpart of
    the model-predicted GF/s, timed with :func:`steady_state_s` (``repeats``
    = samples).  ``mode="program"`` runs the whole-plan compiled program
    resolved through the SAME process-wide program cache (and executable
    pool) the serving engine uses, so solve-time measurement and serve-time
    execution hit identical executables; ``mode="per_task"`` runs the
    host-driven per-task dispatch for comparison.  ``graph`` lets callers
    pass the already-built graph (:func:`build_graph` otherwise caches the
    rebuild).  Triangular-density kernels are not executable; callers
    should catch ``NotImplementedError``.
    """
    from ..codegen import (allclose, plan_executor, random_inputs,
                           reference_executor)
    g = graph if graph is not None else build_graph(name, scale)
    exe = plan_executor(g, plan, impl=impl, mode=mode, pool_size=pool_size)
    ins = random_inputs(g, seed=0)
    best = steady_state_s(exe, ins, samples=repeats)
    ok = True
    if validate:
        ref = reference_executor(g)(ins)
        out = exe(ins)
        ok = all(allclose(out[k], ref[k]) for k in ref)
    gflops = g.total_flops() / best / 1e9 if best else 0.0
    return best, gflops, ok
