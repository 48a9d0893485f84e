"""Lowering pass: one fused task + its TaskConfig -> one jitted callable.

This is the paper's §5 code generation, per fused task:

* the task's statements are grouped into *units*: an init statement followed
  by an accumulating contraction collapses into ONE kernel invocation (the
  init value seeds the accumulator on the first reduction step) — fusion
  decisions become real kernel fusion, not just shared scheduling;
* each unit becomes a :class:`ContractionSpec` — grid order from the plan's
  loop permutation (``TaskConfig.perm``, reduction loops innermost), block
  shapes from the plan's tile sizes (``TaskConfig.tiles``, with the
  computation padding applied by the kernel wrapper and sliced back), and
  pipelining semantics from the placement's buffer counts;
* statements outside the affine-contraction subset fall back to the
  statement-level einsum evaluator (identical semantics, no plan tiling);
* the whole task body — all units in order — is exposed as one raw
  traceable callable; the whole-plan engine inlines every task body into a
  single program-wide ``jax.jit`` (``repro.codegen.program``), while the
  per-task debug executor jits each body on its own.

Tile sizes for loops the plan left unspecified are clamped to the loop's
(padded) extent instead of a blanket 128 so small graphs are not over-padded.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax

from ..core.fusion import FusedGraph, FusedTask
from ..core.padding import pad_to_multiple
from ..core.plan import TaskConfig
from ..core.taskgraph import Statement
from ..kernels.contraction import (ACC, ContractionSpec, EpiOp, LoopDim,
                                   Operand)
from ..kernels.contraction import ops as contraction_ops
from .reference import OPAQUE_PREFIX, eval_statement


@dataclasses.dataclass(frozen=True)
class LoweredUnit:
    """One kernel invocation inside a task body."""

    kind: str                           # "contraction" | "einsum" | "opaque"
    spec: ContractionSpec | None        # set when kind == "contraction"
    statements: tuple[Statement, ...]   # source statements (1 or 2)
    operands: tuple[str, ...]           # env arrays, spec operand order
    out_array: str


@dataclasses.dataclass
class TaskLowering:
    """A fused task lowered against one plan config + kernel impl.

    ``body`` is the raw traceable callable — the whole-plan engine
    (:mod:`repro.codegen.program`) inlines it into one program-wide
    ``jax.jit`` so XLA sees every task kernel at once.  ``fn`` wraps the
    same body in a per-task ``jax.jit`` for the debug/validation executor;
    it is built lazily so the fused path never pays for it.
    """

    tid: int
    name: str
    units: tuple[LoweredUnit, ...]
    in_arrays: tuple[str, ...]          # env arrays the task consumes
    out_array: str
    slice_id: int
    body: Callable[..., jax.Array]      # raw: (*in_arrays) -> out array
    _fn: Callable[..., jax.Array] | None = dataclasses.field(
        default=None, repr=False)

    @property
    def fn(self) -> Callable[..., jax.Array]:
        """Per-task jitted entry point (debug/per-task executor path)."""
        if self._fn is None:
            self._fn = jax.jit(self.body)
        return self._fn

    @property
    def kind(self) -> str:
        kinds = {u.kind for u in self.units}
        if kinds == {"contraction"}:
            return "contraction"
        return "opaque" if "opaque" in kinds else "einsum"

    @property
    def grid(self) -> tuple[int, ...] | None:
        """Pallas grid of the dominant (largest-domain) contraction unit."""
        specs = [u.spec for u in self.units if u.spec is not None]
        if not specs:
            return None
        return max(specs, key=lambda s: len(s.loops)).grid


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------
def _loop_dim(cfg: TaskConfig, loop: str, tc: int) -> LoopDim:
    opt = cfg.tiles.get(loop)
    if opt is not None and opt.ori_tc == tc:
        return LoopDim(loop, opt.tile, opt.padded_tc, tc)
    # Plan did not tile this loop (or tiled a different extent): clamp the
    # block to the loop extent rather than defaulting to 128 and over-padding.
    tile = min(128, tc)
    return LoopDim(loop, tile, pad_to_multiple(tc, tile), tc)


def _affine(stmt: Statement) -> bool:
    """Within the kernel's subset: dense, unique non-None iters per access.

    Rank-0 accesses (scalar operands of traced elementwise statements,
    opaque-segment reads) stay on the einsum/eval fallback: a 0-d BlockSpec
    has no tile for the grid pipeline to carry.  Opaque ops are evaluated
    through their registered callables, never a contraction kernel."""
    if stmt.density != 1.0:
        return False
    if stmt.op not in ("mul", "add", "sub"):
        return False
    for acc in tuple(stmt.reads) + tuple(stmt.writes):
        if len(acc.iters) == 0:
            return False
        if any(it is None for it in acc.iters):
            return False
        if len(set(acc.iters)) != len(acc.iters):
            return False
    return True


def _plan_tiled(task: FusedTask, stmt: Statement) -> bool:
    """Some access of ``stmt`` runs over a loop the plan tiles (the task's
    main loops).  A pointwise statement whose every iterator is private
    has no tiling in the plan: a kernel for it would hold whole arrays in
    VMEM, so it runs as a plain XLA op (or rides a producer's epilogue)."""
    main = set(task.main.loops)
    return any(it in main for acc in tuple(stmt.reads) + tuple(stmt.writes)
               for it in acc.iters)


def _acc_reads(stmt: Statement):
    out = stmt.writes[0]
    return [a for a in stmt.reads if a.array == out.array]


def _is_plain_accumulation(stmt: Statement) -> bool:
    """Reads its own output exactly at the write's iterators (``+=``)."""
    out = stmt.writes[0]
    accs = _acc_reads(stmt)
    return bool(accs) and all(tuple(a.iters) == tuple(out.iters)
                              for a in accs)


def _is_pointwise_def(stmt: Statement) -> bool:
    """A definition with no reduction and no self-read — fusable as init."""
    return not _acc_reads(stmt) and not stmt.reduction_loops


def _ordered_loops(cfg: TaskConfig, used: set[str], red: set[str],
                   tcs: dict[str, int]) -> list[str]:
    """Grid order: the plan permutation restricted to the unit's loops, with
    reduction loops kept innermost (the solver pins them there; enforce it
    for robustness)."""
    in_perm = [l for l in cfg.perm if l in used]
    extra = [l for l in tcs if l in used and l not in cfg.perm]
    seq = in_perm + extra
    return [l for l in seq if l not in red] + [l for l in seq if l in red]


def _unit_spec(cfg: TaskConfig, main: Statement,
               init: Statement | None, prior: bool) -> ContractionSpec:
    out = main.writes[0]
    reads = [a for a in main.reads if a.array != out.array]
    init_reads: list = []
    init_op = "mul"
    if init is not None:
        init_reads = list(init.reads)
        init_op = init.op
    elif prior:
        init_reads = [out]          # previous value of the output array
        init_op = "mul"

    init_coeff, init_offset = 1.0, 0.0
    if init is not None:
        init_coeff, init_offset = init.coeff, init.offset

    tcs = dict(main.trip_counts)
    if init is not None:
        for l, n in init.trip_counts.items():
            tcs.setdefault(l, n)
    # Grid loops = loops some operand or the output actually indexes.  A
    # reduction loop touched by no access contributes nothing in the
    # reference einsum semantics, so it must not enter the grid either.
    used = {it for a in reads + init_reads + [out] for it in a.iters}
    red = set(main.reduction_loops) & used
    loops = _ordered_loops(cfg, used, red, tcs)

    overlapped = all(
        cfg.placements[a.array].buffers >= 2
        for a in reads if a.array in cfg.placements) if reads else True
    return ContractionSpec(
        loops=tuple(_loop_dim(cfg, l, tcs[l]) for l in loops),
        reduction=tuple(l for l in loops if l in red),
        op=main.op,
        reads=tuple(Operand(a.array, tuple(a.iters)) for a in reads),
        out_iters=tuple(out.iters),
        init_reads=tuple(Operand(a.array, tuple(a.iters))
                         for a in init_reads),
        init_op=init_op,
        buffers=2 if overlapped else 1,
        coeff=main.coeff,
        offset=main.offset,
        init_coeff=init_coeff,
        init_offset=init_offset,
    )


# ---------------------------------------------------------------------------
# Task lowering
# ---------------------------------------------------------------------------
def _build_units(fg: FusedGraph, task: FusedTask,
                 cfg: TaskConfig) -> list[LoweredUnit]:
    g = fg.graph
    names = [s.name for s in g.statements]
    units: list[LoweredUnit] = []
    pending_init: Statement | None = None
    produced = False       # the task has already written its output array

    def flush_init() -> None:
        nonlocal pending_init, produced
        if pending_init is not None:
            units.append(_make_unit(cfg, pending_init, None, prior=False))
            pending_init = None
            produced = True

    for stmt in task.statements:
        if stmt.density != 1.0:
            raise NotImplementedError(
                f"{stmt.name}: triangular-density statements are "
                "cost-modeled only (rectangular execution would compute a "
                "different function)")
        if not _affine(stmt) or not _plan_tiled(task, stmt):
            # outside the kernel subset: eval fallback, one statement —
            # "opaque" marks frontend passthrough segments (registered
            # residual callables), "einsum" the affine-but-untiled rest
            flush_init()
            srcs = tuple(dict.fromkeys(a.array for a in stmt.reads))
            kind = "opaque" if stmt.op.startswith(OPAQUE_PREFIX) \
                else "einsum"
            units.append(LoweredUnit(kind=kind, spec=None,
                                     statements=(stmt,), operands=srcs,
                                     out_array=stmt.writes[0].array))
            produced = True
            continue
        if _acc_reads(stmt) and not _is_plain_accumulation(stmt):
            # A self-read at iterators other than the write's (e.g. a
            # transposed in-place update) carries a loop-borne dependence
            # neither the kernel nor the reference executes faithfully —
            # refuse loudly rather than mis-lower.
            raise NotImplementedError(
                f"{stmt.name}: reads its own output at non-write "
                "iterators; only plain '+=' accumulation is executable")
        if _is_plain_accumulation(stmt):
            fusable = pending_init is not None and \
                tuple(pending_init.writes[0].iters) == \
                tuple(stmt.writes[0].iters)
            if fusable:
                # init + accumulate -> ONE kernel (the fusion payoff)
                units.append(_make_unit(cfg, stmt, pending_init,
                                        prior=False))
                pending_init = None
                produced = True
                continue
            flush_init()
            # Accumulation with no in-task init: seed from the array's prior
            # value when one exists (earlier task / external input) —
            # matching the reference, which only adds env values it finds.
            out = stmt.writes[0].array
            idx = names.index(stmt.name)
            prior = produced or g.producer_of(out, idx) is not None \
                or out in g.external_inputs()
            units.append(_make_unit(cfg, stmt, None, prior=prior))
            produced = True
            continue
        if _is_pointwise_def(stmt):
            # hold: it may seed the accumulator of the next statement
            flush_init()
            pending_init = stmt
            continue
        # a non-accumulating contraction definition (e.g. gesummv y_sum)
        flush_init()
        units.append(_make_unit(cfg, stmt, None, prior=False))
        produced = True
    flush_init()
    return units


def _make_unit(cfg: TaskConfig, main: Statement, init: Statement | None,
               prior: bool) -> LoweredUnit:
    spec = _unit_spec(cfg, main, init, prior)
    out = main.writes[0].array
    operands = tuple(o.array for o in spec.reads + spec.init_reads)
    stmts = (init, main) if init is not None else (main,)
    return LoweredUnit(kind="contraction", spec=spec, statements=stmts,
                       operands=operands, out_array=out)


# ---------------------------------------------------------------------------
# Epilogue folding (traced graphs): elementwise tails ride inside the kernel
# ---------------------------------------------------------------------------
def _epi_stmt_ok(stmt: Statement) -> bool:
    """A statement foldable as one EpiOp: pointwise over its write domain
    (no reduction or broadcast ``z`` loops), no self-read, an op from the
    kernel's elementwise families."""
    if not (stmt.op in ("mul", "add", "sub")
            or stmt.op.startswith(("unary:", "binary:"))):
        return False
    if stmt.density != 1.0 or len(stmt.writes) != 1:
        return False
    w = stmt.writes[0]
    if any(it is None for it in w.iters) or \
            len(set(w.iters)) != len(w.iters):
        return False
    if set(stmt.loops) != set(w.iters):
        return False
    return not any(r.array == w.array for r in stmt.reads)


def _fold_epilogues(fg: FusedGraph, task: FusedTask,
                    units: list[LoweredUnit]) -> list[LoweredUnit]:
    """Fold single-consumer elementwise units into the contraction unit that
    produces their input: the tail becomes a :class:`EpiOp` on the producer's
    spec, applied to the finished output tile at store time — one kernel,
    no intermediate buffer.  Iterators are renamed onto the producer's
    ``out_iters`` via the positional map of the tail's read of the producer
    output; a tail that transposes, reduces, broadcasts, or whose input is
    consumed anywhere else stays a separate unit."""
    g = fg.graph
    outside = set(g.final_outputs())
    for t in fg.tasks:
        if t.tid != task.tid:
            for s in t.statements:
                outside.update(a.array for a in s.reads)

    def unit_reads(u: LoweredUnit) -> set[str]:
        if u.kind == "contraction":
            return set(u.operands)
        return {a.array for s in u.statements for a in s.reads}

    changed = True
    while changed:
        changed = False
        for vi, V in enumerate(units):
            if len(V.statements) != 1 or V.kind == "opaque":
                continue
            s = V.statements[0]
            if not _epi_stmt_ok(s):
                continue
            fold = _try_fold(units, vi, s, outside, unit_reads)
            if fold is not None:
                ui, new_unit = fold
                units[ui] = new_unit
                del units[vi]
                changed = True
                break
    return units


def _try_fold(units: list[LoweredUnit], vi: int, s: Statement, outside,
              unit_reads) -> tuple[int, LoweredUnit] | None:
    read_arrays = {r.array for r in s.reads}
    for ui in range(vi - 1, -1, -1):
        U = units[ui]
        if U.kind != "contraction" or U.spec is None:
            continue
        if U.out_array not in read_arrays or U.out_array in outside:
            continue
        if any(U.out_array in unit_reads(w)
               for wi, w in enumerate(units) if wi != vi):
            continue
        spec = U.spec
        # Positional rename: the tail's read of the producer output maps its
        # iterators onto the spec's out_iters (must be consistent if read
        # more than once).
        m: dict[str, str] | None = None
        ok = True
        for r in s.reads:
            if r.array != U.out_array:
                continue
            if len(r.iters) != len(spec.out_iters) \
                    or any(it is None for it in r.iters) \
                    or len(set(r.iters)) != len(r.iters):
                ok = False
                break
            mm = dict(zip(r.iters, spec.out_iters))
            if m is None:
                m = mm
            elif mm != m:
                ok = False
                break
        if not ok or m is None or set(s.loops) != set(m):
            continue
        w = s.writes[0]
        if tuple(m[it] for it in w.iters) != tuple(spec.out_iters):
            continue                      # transposed store — keep separate
        if any(s.trip_counts[it] != spec.dim(oit).ori
               for it, oit in m.items()):
            continue
        # Extra operands must be elementwise-aligned and already available
        # when the producer unit runs (task inputs or earlier units' outs).
        later_outs = {units[k].out_array for k in range(ui, len(units))}
        epi_ok = True
        reads: list[Operand] = []
        for r in s.reads:
            if r.array == U.out_array:
                reads.append(Operand(ACC, tuple(spec.out_iters)))
                continue
            if any(it is None or it not in m for it in r.iters) \
                    or r.array in later_outs:
                epi_ok = False
                break
            reads.append(Operand(r.array, tuple(m[it] for it in r.iters)))
        if not epi_ok:
            continue
        new_spec = dataclasses.replace(
            spec, epilogue=spec.epilogue + (EpiOp(
                op=s.op, reads=tuple(reads),
                coeff=s.coeff, offset=s.offset),))
        return ui, LoweredUnit(
            kind="contraction", spec=new_spec,
            statements=U.statements + (s,),
            operands=tuple(o.array for o in new_spec.all_reads),
            out_array=w.array)
    return None


def lower_task(fg: FusedGraph, task: FusedTask, cfg: TaskConfig,
               impl: str) -> TaskLowering:
    """Lower one fused task to a single jitted callable honouring the plan."""
    units = _build_units(fg, task, cfg)
    if fg.graph.traced:
        units = _fold_epilogues(fg, task, units)
    out_array = task.output_array

    # Environment arrays consumed (external to the task body): everything an
    # einsum unit reads plus every contraction operand, minus arrays the
    # task itself produced before that unit runs.
    in_arrays: list[str] = []
    written: set[str] = set()
    for u in units:
        srcs = u.operands if u.kind == "contraction" else tuple(
            dict.fromkeys([a.array for s in u.statements for a in s.reads]))
        for a in srcs:
            if a not in written and a not in in_arrays:
                in_arrays.append(a)
        written.add(u.out_array)

    def body(*arrays: jax.Array) -> jax.Array:
        env = dict(zip(in_arrays, arrays))
        val = None
        for u in units:
            if u.kind == "contraction":
                operands = [env[a] for a in u.operands]
                val = contraction_ops.contract(u.spec, *operands, impl=impl)
            else:
                for s in u.statements:
                    val = eval_statement(s, env)
            env[u.out_array] = val
        return env[out_array]

    return TaskLowering(
        tid=task.tid,
        name=task.name,
        units=tuple(units),
        in_arrays=tuple(in_arrays),
        out_array=out_array,
        slice_id=cfg.slice_id,
        body=body,
    )
