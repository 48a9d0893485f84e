"""jaxpr -> TaskGraph lowering: the frontend's translation pass.

The paper's flow is source-to-source: unannotated affine code in, optimized
accelerator program out.  This module is that front door for JAX: it walks a
closed jaxpr (nested ``jit`` calls inlined, so ``jax.nn``-style jitted
helpers are seen through) and lowers the **affine subset** to
:class:`~repro.core.taskgraph.Statement` objects the solver/codegen stack
already understands:

========================  =================================================
primitive                 lowering
========================  =================================================
``dot_general``           contraction statement (``op="mul"``): batch +
                          free dims become output iterators, contracting
                          dims become reduction iterators;
                          ``flops_per_iter=2``
``add``/``sub``           elementwise statement (``op="add"``/``"sub"``);
                          size-1 operand dims read through a private
                          trip-1 iterator (exact under the projection
                          semantics); a scalar-*literal* operand folds
                          into the statement's affine ``offset``
``mul``                   elementwise joint-product statement
                          (``op="mul"``); a scalar-literal operand folds
                          into the statement ``coeff`` (``x * 2.0`` stays
                          affine)
``div``                   ``x / c`` folds to ``coeff = 1/c``; tensor
                          divisors lower to ``op="binary:div"``
``neg``                   affine copy with ``coeff = -1``
``max``/``min``           scalar-literal bound folds to
                          ``unary:max_const:<c>`` (relu's ``max(x, 0)``);
                          tensor bounds lower to ``binary:max``/``min``
``tanh``/``logistic``/
``exp``/``log``/...       pointwise ``unary:<name>`` statement (see
                          ``repro.kernels.contraction.ref``)
``integer_pow``           ``unary:pow_<k>``
``transpose``             projection copy (``op="add"``, permuted iters)
``broadcast_in_dim``      projection copy; new output dims broadcast,
                          size-1 source dims read through a trip-1 iter
``reshape``/``squeeze``   projection copy when only size-1 dims are
                          inserted/removed (the non-unit dim sequence is
                          unchanged); other reshapes go opaque
``convert_element_type``  float->float casts alias the operand (zero-cost
                          passthrough: statements compute in f32 and the
                          executable casts at function outputs only)
``reduce_sum``            projection statement with real reduction
                          iterators (rank-0 results fall back to opaque)
========================  =================================================

Nested ``jit``, ``custom_jvp_call`` and ``custom_vjp_call`` sub-jaxprs are
inlined (primal semantics), so ``jax.nn``-style helpers (relu/silu/gelu)
are seen through.  Any floating dtype of at most 4 bytes is accepted —
statements evaluate in f32 internally and the lowering records the
narrowest traced float width (``precision_bytes``) so validation widens
its tolerance accordingly.

Everything else — comparisons, gathers, control flow, integer or f64
dtypes — is carved into **opaque passthrough segments**: maximal runs of
unsupported equations re-evaluated verbatim (``primitive.bind``) inside a
single statement whose semantics live in the codegen opaque registry.
Each opaque output statement reads only the segment inputs its own prefix
actually uses, so unrelated outputs do not inflate consumer counts.
Opaque statements still participate in graph dependencies, scheduling and
the whole-plan program; they are simply not tiled or permuted.  The
per-trace :class:`Coverage` records how much of the function the optimizer
actually owns.

Const values never enter the lowering result: jaxpr constvars become named
off-chip input arrays whose values are bound per
:class:`~repro.frontend.executable.TracedFunction`, so two traces with the
same structure share one graph (and therefore one program-cache entry).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal, Var

from ..codegen.reference import OPAQUE_PREFIX, register_opaque
from ..core.taskgraph import (Access, Statement, TaskGraph, copy_statement,
                              intermediate, iter_names)

#: Pointwise primitives lowered to ``unary:<name>`` statements.
UNARY_PRIMITIVES = ("tanh", "logistic", "exp", "log", "log1p", "expm1",
                    "sqrt", "rsqrt", "cbrt", "erf", "sin", "cos", "abs",
                    "sign", "floor", "ceil", "round")

#: Primitives lowered to affine statements (everything else goes opaque).
SUPPORTED_PRIMITIVES = ("dot_general", "add", "sub", "mul", "div", "neg",
                        "max", "min", "integer_pow", "transpose",
                        "broadcast_in_dim", "reshape", "squeeze",
                        "convert_element_type", "reduce_sum") \
    + UNARY_PRIMITIVES

#: Floating dtypes statements accept (computed in f32 internally; f64 stays
#: opaque so the lowering never silently narrows a wider request).
_FLOAT_OK = ("float32", "bfloat16", "float16")


# ---------------------------------------------------------------------------
# jaxpr flattening (nested-jit inlining)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FlatEqn:
    """One primitive application with its inputs resolved through every
    inlined nested-``jit`` boundary (invars are parent-scope atoms)."""

    eqn: Any                       # the original JaxprEqn
    invars: tuple[Any, ...]        # resolved atoms: Var | Literal
    outvars: tuple[Any, ...]


def flatten_jaxpr(jaxpr) -> tuple[list[FlatEqn], list[Any], dict]:
    """Inline nested-``jit`` sub-jaxprs into one flat equation list.

    Returns ``(flat_eqns, resolved_outvars, sub_consts)`` where
    ``sub_consts`` maps sub-jaxpr constvars to their (structural) values —
    these become static graph inputs and feed the trace fingerprint.
    """
    subst: dict[Var, Any] = {}
    sub_consts: dict[Var, Any] = {}
    out: list[FlatEqn] = []

    def resolve(a):
        while isinstance(a, Var) and a in subst:
            a = subst[a]
        return a

    def inline(closed, eqn) -> bool:
        """Substitute a sub-jaxpr call in place; False if shapes mismatch."""
        sj = closed.jaxpr
        if len(sj.invars) != len(eqn.invars) \
                or len(sj.outvars) < len(eqn.outvars):
            return False
        for cv, cval in zip(sj.constvars, closed.consts):
            sub_consts[cv] = cval
        for iv, a in zip(sj.invars, eqn.invars):
            subst[iv] = resolve(a)
        walk(sj)
        for ov, sov in zip(eqn.outvars, sj.outvars):
            subst[ov] = resolve(sov)
        return True

    def walk(jx) -> None:
        for eqn in jx.eqns:
            if eqn.primitive.name == "jit":
                if inline(eqn.params["jaxpr"], eqn):
                    continue
            elif eqn.primitive.name in ("custom_jvp_call",
                                        "custom_vjp_call"):
                # Primal semantics: the call_jaxpr IS the function being
                # differentiated — inline it exactly like a nested-jit body
                # (the jvp/fwd/bwd rules only matter under differentiation,
                # which a traced executable never performs).
                closed = eqn.params.get("call_jaxpr") \
                    or eqn.params.get("fun_jaxpr")
                if closed is not None and inline(closed, eqn):
                    continue
            out.append(FlatEqn(eqn, tuple(resolve(a) for a in eqn.invars),
                               tuple(eqn.outvars)))

    walk(jaxpr)
    resolved_outs = [resolve(v) for v in jaxpr.outvars]
    return out, resolved_outs, sub_consts


def fingerprint_jaxpr(closed, sub_consts: dict) -> str:
    """Content hash of a closed jaxpr: structure + input/const avals +
    inlined sub-jaxpr const values.  Two closures with the same structure
    but different top-level const *values* share a fingerprint on purpose —
    the graph is identical, only the bound values differ."""
    h = hashlib.sha256()
    h.update(str(closed.jaxpr).encode())
    for v in closed.jaxpr.invars:
        h.update(repr((tuple(v.aval.shape), str(v.aval.dtype))).encode())
    for c in closed.consts:
        h.update(repr((tuple(np.shape(c)),
                       str(np.result_type(c)))).encode())
    for v in sub_consts.values():
        h.update(np.asarray(v).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Lowering result
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Coverage:
    """How much of the traced function the optimizer owns."""

    n_eqns: int
    n_supported: int
    supported_flops: float
    opaque_flops_est: float        # 1 flop per output element per opaque eqn

    @property
    def eqn_ratio(self) -> float:
        return self.n_supported / self.n_eqns if self.n_eqns else 1.0

    @property
    def flop_ratio(self) -> float:
        total = self.supported_flops + self.opaque_flops_est
        return self.supported_flops / total if total else 1.0

    def to_jsonable(self) -> dict:
        return {"n_eqns": self.n_eqns, "n_supported": self.n_supported,
                "eqn_ratio": round(self.eqn_ratio, 4),
                "flop_ratio": round(self.flop_ratio, 4)}


@dataclasses.dataclass(frozen=True)
class OutSpec:
    """How one flat function output is produced.

    ``kind="array"``: read from the executed graph's outputs under ``ref``;
    ``kind="binding"``: read straight from the bound input dict (an input,
    const or literal forwarded unchanged).  ``promoted`` marks rank-0
    values carried as shape-(1,) arrays inside the graph."""

    kind: str
    ref: str
    promoted: bool = False


@dataclasses.dataclass
class LoweredJaxpr:
    """The trace-cache value: everything derived from jaxpr *structure*.

    Const values are deliberately absent (bound per TracedFunction);
    ``static_bindings`` holds values that ARE structure — literals,
    inlined sub-jaxpr consts and synthetic constants the lowering itself
    introduced (the scalar zero seeding ``neg``)."""

    fingerprint: str
    graph: TaskGraph
    in_names: tuple[str, ...]                  # one per flat invar
    const_names: tuple[str, ...]               # one per closed.consts entry
    static_bindings: dict[str, jax.Array]
    in_avals: tuple[tuple[tuple[int, ...], Any], ...]
    out_specs: tuple[OutSpec, ...]
    out_avals: tuple[tuple[tuple[int, ...], Any], ...]
    coverage: Coverage
    opaque_ops: tuple[str, ...] = ()    # registry entries owned by this record
    #: Narrowest floating itemsize among supported statements' avals —
    #: statements compute in f32, so validation against the traced function
    #: must widen its tolerance to this precision band (bf16 intermediates
    #: in the jit baseline carry ~1e-2 relative error the f32 graph lacks).
    precision_bytes: int = 4
    plan_cache: dict = dataclasses.field(default_factory=dict)

    @property
    def graph_name(self) -> str:
        return self.graph.name


def graph_name_of(fingerprint: str) -> str:
    return f"traced:{fingerprint[:16]}"


# ---------------------------------------------------------------------------
# Opaque segment evaluation
# ---------------------------------------------------------------------------
def eval_flat_eqns(feqns: list[FlatEqn], env: dict) -> None:
    """Re-evaluate flat equations against a Var->value environment (the
    ``jax.core.eval_jaxpr`` loop, over resolved atoms)."""
    for fe in feqns:
        vals = [a.val if isinstance(a, Literal) else env[a]
                for a in fe.invars]
        subfuns, bind_params = fe.eqn.primitive.get_bind_params(
            fe.eqn.params)
        outs = fe.eqn.primitive.bind(*subfuns, *vals, **bind_params)
        if not fe.eqn.primitive.multiple_results:
            outs = [outs]
        for ov, o in zip(fe.outvars, outs):
            env[ov] = o


def _segment_callable(feqns: list[FlatEqn], in_vars: tuple,
                      unpromote: tuple[bool, ...], out_var,
                      promote_out: bool) -> Callable:
    """Traceable residual computing one needed output of an opaque segment.

    Each output statement re-derives the segment prefix up to its producer;
    in program mode XLA CSE collapses the duplicates back into one
    computation, so a k-output segment costs one evaluation."""

    def run(*vals):
        env: dict = {}
        for v, val, unp in zip(in_vars, vals, unpromote):
            val = jnp.reshape(val, ()) if unp else val
            # Statements compute in f32 regardless of the traced dtype —
            # restore the dtype the segment's jaxpr was traced with so its
            # primitives see the avals they were bound against.
            if val.dtype != v.aval.dtype:
                val = val.astype(v.aval.dtype)
            env[v] = val
        eval_flat_eqns(feqns, env)
        out = env[out_var]
        return jnp.reshape(out, (1,)) if promote_out else out

    return run


# ---------------------------------------------------------------------------
# Lowering context
# ---------------------------------------------------------------------------
class _Ctx:
    def __init__(self, fingerprint: str):
        self.fingerprint = fingerprint
        self.arrays: dict[str, Any] = {}
        self.statements: list[Statement] = []
        self.var_name: dict[Var, str] = {}
        self.promoted: set[str] = set()
        self.static: dict[str, jax.Array] = {}
        self._literals: dict[tuple, str] = {}
        self._n = 0
        self.supported_flops = 0.0
        self.opaque_flops_est = 0.0
        self.opaque_ops: list[str] = []

    def fresh(self, stem: str) -> str:
        self._n += 1
        return f"t{self._n}_{stem}"

    def add_array(self, name: str, shape, dtype) -> str:
        self.arrays[name] = intermediate(
            name, tuple(shape), dtype_bytes=np.dtype(dtype).itemsize)
        return name

    def name_of(self, atom) -> str:
        if isinstance(atom, Literal):
            return self.static_value(atom.val)
        return self.var_name[atom]

    def static_value(self, val: np.ndarray) -> str:
        """Materialize a structural constant as a named static input."""
        val = np.asarray(val)
        key = (val.tobytes(), str(val.dtype), val.shape)
        name = self._literals.get(key)
        if name is None:
            name = f"lit{len(self._literals)}"
            self._literals[key] = name
            self.add_array(name, val.shape, val.dtype)
            self.static[name] = jnp.asarray(val)
        return name

    def static_scalar(self, value: float) -> str:
        return self.static_value(np.float32(value))

    def emit(self, stmt: Statement, outvar, shape=None, dtype=None) -> None:
        out = stmt.writes[0].array
        aval = outvar.aval
        self.add_array(out, aval.shape if shape is None else shape,
                       aval.dtype if dtype is None else dtype)
        self.statements.append(stmt)
        self.var_name[outvar] = out


# ---------------------------------------------------------------------------
# Supported-primitive handlers (one Statement each)
# ---------------------------------------------------------------------------
def _h_dot_general(ctx: _Ctx, fe: FlatEqn) -> None:
    (lc, rc), (lb, rb) = fe.eqn.params["dimension_numbers"]
    lhs, rhs = fe.invars
    lshape, rshape = lhs.aval.shape, rhs.aval.shape
    out_aval = fe.outvars[0].aval
    name = ctx.fresh("dot")
    out_its = iter_names(name, len(out_aval.shape))
    red_its = iter_names(name, len(lc), "r")
    lfree = [d for d in range(len(lshape)) if d not in lb and d not in lc]
    rfree = [d for d in range(len(rshape)) if d not in rb and d not in rc]
    lits: list[str] = [""] * len(lshape)
    for i, d in enumerate(lb):
        lits[d] = out_its[i]
    for i, d in enumerate(lc):
        lits[d] = red_its[i]
    for i, d in enumerate(lfree):
        lits[d] = out_its[len(lb) + i]
    rits: list[str] = [""] * len(rshape)
    for i, d in enumerate(rb):
        rits[d] = out_its[i]
    for i, d in enumerate(rc):
        rits[d] = red_its[i]
    for i, d in enumerate(rfree):
        rits[d] = out_its[len(lb) + len(lfree) + i]
    trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
    for i, d in enumerate(lc):
        trip[red_its[i]] = int(lshape[d])
    stmt = Statement(
        name=name, loops=tuple(out_its) + tuple(red_its), trip_counts=trip,
        reads=(Access(ctx.name_of(lhs), tuple(lits)),
               Access(ctx.name_of(rhs), tuple(rits))),
        writes=(Access(name, out_its),), flops_per_iter=2.0, op="mul")
    ctx.emit(stmt, fe.outvars[0])


def _ew_access(ctx: _Ctx, atom, out_its, out_shape, name: str,
               z_its: list[str], trip: dict[str, int]) -> Access:
    """Access map of one elementwise operand: same-size dims share the
    output iterator; size-1 broadcast dims read through a private trip-1
    iterator (summed out exactly); scalars read with rank-0 access."""
    shp = atom.aval.shape
    if len(shp) == 0:
        return Access(ctx.name_of(atom), ())
    its = []
    for d, (s, os) in enumerate(zip(shp, out_shape)):
        if int(s) == int(os):
            its.append(out_its[d])
        else:                                   # s == 1: broadcast dim
            z = f"{name}_z{len(z_its)}"
            z_its.append(z)
            trip[z] = 1
            its.append(z)
    return Access(ctx.name_of(atom), tuple(its))


def _h_elementwise(op: str):
    def handler(ctx: _Ctx, fe: FlatEqn) -> None:
        out_aval = fe.outvars[0].aval
        name = ctx.fresh(op)
        out_its = iter_names(name, len(out_aval.shape))
        trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
        z_its: list[str] = []
        reads = tuple(_ew_access(ctx, a, out_its, out_aval.shape, name,
                                 z_its, trip) for a in fe.invars)
        stmt = Statement(
            name=name, loops=tuple(out_its) + tuple(z_its),
            trip_counts=trip, reads=reads,
            writes=(Access(name, out_its),), flops_per_iter=1.0, op=op)
        ctx.emit(stmt, fe.outvars[0])
    return handler


def _scalar_literal(atom) -> float | None:
    """The float value of a rank-0 numeric literal operand, else None —
    the foldable subset (value is structure, not a bound input)."""
    if isinstance(atom, Literal) and np.ndim(atom.val) == 0 \
            and np.issubdtype(np.result_type(atom.val), np.number):
        return float(atom.val)
    return None


def _emit_scaled_copy(ctx: _Ctx, fe: FlatEqn, src, coeff: float,
                      offset: float, stem: str) -> None:
    """``out = coeff * src + offset`` as a single-read affine statement —
    scalar-literal mul/add/sub/div/neg all land here."""
    out_aval = fe.outvars[0].aval
    name = ctx.fresh(stem)
    out_its = iter_names(name, len(out_aval.shape))
    trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
    z_its: list[str] = []
    read = _ew_access(ctx, src, out_its, out_aval.shape, name, z_its, trip)
    stmt = Statement(
        name=name, loops=tuple(out_its) + tuple(z_its), trip_counts=trip,
        reads=(read,), writes=(Access(name, out_its),),
        flops_per_iter=1.0, op="add", coeff=coeff, offset=offset)
    ctx.emit(stmt, fe.outvars[0])


def _h_mul(ctx: _Ctx, fe: FlatEqn) -> None:
    a, b = fe.invars
    ca, cb = _scalar_literal(a), _scalar_literal(b)
    if ca is not None and cb is None:
        return _emit_scaled_copy(ctx, fe, b, ca, 0.0, "smul")
    if cb is not None and ca is None:
        return _emit_scaled_copy(ctx, fe, a, cb, 0.0, "smul")
    _h_elementwise("mul")(ctx, fe)


def _h_add_sub(op: str):
    def handler(ctx: _Ctx, fe: FlatEqn) -> None:
        a, b = fe.invars
        ca, cb = _scalar_literal(a), _scalar_literal(b)
        if cb is not None and ca is None:
            return _emit_scaled_copy(
                ctx, fe, a, 1.0, cb if op == "add" else -cb, "sadd")
        if ca is not None and cb is None:
            if op == "add":
                return _emit_scaled_copy(ctx, fe, b, 1.0, ca, "sadd")
            return _emit_scaled_copy(ctx, fe, b, -1.0, ca, "sadd")
        _h_elementwise(op)(ctx, fe)
    return handler


def _h_neg(ctx: _Ctx, fe: FlatEqn) -> None:
    _emit_scaled_copy(ctx, fe, fe.invars[0], -1.0, 0.0, "neg")


def _h_binary(name: str):
    """Pointwise two-operand family (``binary:max``/``min``/``div``) —
    operand order preserved (division is not commutative)."""
    def handler(ctx: _Ctx, fe: FlatEqn) -> None:
        out_aval = fe.outvars[0].aval
        sname = ctx.fresh(name)
        out_its = iter_names(sname, len(out_aval.shape))
        trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
        z_its: list[str] = []
        reads = tuple(_ew_access(ctx, a, out_its, out_aval.shape, sname,
                                 z_its, trip) for a in fe.invars)
        stmt = Statement(
            name=sname, loops=tuple(out_its) + tuple(z_its),
            trip_counts=trip, reads=reads,
            writes=(Access(sname, out_its),), flops_per_iter=1.0,
            op=f"binary:{name}")
        ctx.emit(stmt, fe.outvars[0])
    return handler


def _h_div(ctx: _Ctx, fe: FlatEqn) -> None:
    c = _scalar_literal(fe.invars[1])
    if c is not None and c != 0.0:
        return _emit_scaled_copy(ctx, fe, fe.invars[0], 1.0 / c, 0.0,
                                 "sdiv")
    _h_binary("div")(ctx, fe)


def _h_minmax(name: str):
    def handler(ctx: _Ctx, fe: FlatEqn) -> None:
        a, b = fe.invars
        ca, cb = _scalar_literal(a), _scalar_literal(b)
        src, c = (b, ca) if ca is not None else (a, cb)
        if c is not None and (ca is None or cb is None):
            # clamp against a folded constant: relu's ``max(x, 0.0)``
            return _h_unary(f"{name}_const:{c!r}", stem=name)(
                ctx, dataclasses.replace(fe, invars=(src,)))
        _h_binary(name)(ctx, fe)
    return handler


def _h_unary(name: str, flops: float = 2.0, stem: str | None = None):
    def handler(ctx: _Ctx, fe: FlatEqn) -> None:
        out_aval = fe.outvars[0].aval
        sname = ctx.fresh(stem or name)
        out_its = iter_names(sname, len(out_aval.shape))
        stmt = Statement(
            name=sname, loops=out_its,
            trip_counts={it: int(n)
                         for it, n in zip(out_its, out_aval.shape)},
            reads=(Access(ctx.name_of(fe.invars[0]), out_its),),
            writes=(Access(sname, out_its),), flops_per_iter=flops,
            op=f"unary:{name}")
        ctx.emit(stmt, fe.outvars[0])
    return handler


def _h_integer_pow(ctx: _Ctx, fe: FlatEqn) -> None:
    _h_unary(f"pow_{int(fe.eqn.params['y'])}", stem="pow")(ctx, fe)


def _h_convert(ctx: _Ctx, fe: FlatEqn) -> None:
    """float->float casts are pure aliases: statements compute in f32 and
    the executable casts at function outputs, so the cast costs nothing
    (the jit baseline pays a real convert here)."""
    src = fe.invars[0]
    name = ctx.name_of(src)
    ctx.var_name[fe.outvars[0]] = name


def _h_transpose(ctx: _Ctx, fe: FlatEqn) -> None:
    perm = tuple(fe.eqn.params["permutation"])
    out_aval = fe.outvars[0].aval
    name = ctx.fresh("tr")
    out_its = iter_names(name, len(out_aval.shape))
    src_its = tuple(out_its[perm.index(d)] for d in range(len(perm)))
    ctx.emit(copy_statement(
        name, name, ctx.name_of(fe.invars[0]), src_its, out_its,
        {it: int(n) for it, n in zip(out_its, out_aval.shape)}),
        fe.outvars[0])


def _h_broadcast_in_dim(ctx: _Ctx, fe: FlatEqn) -> None:
    bd = tuple(fe.eqn.params["broadcast_dimensions"])
    src = fe.invars[0]
    out_aval = fe.outvars[0].aval
    name = ctx.fresh("bc")
    out_its = iter_names(name, len(out_aval.shape))
    trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
    z_its: list[str] = []
    its: list[str] = []
    for p, s in enumerate(src.aval.shape):
        if int(s) == int(out_aval.shape[bd[p]]):
            its.append(out_its[bd[p]])
        else:                                   # size-1 source dim
            z = f"{name}_z{len(z_its)}"
            z_its.append(z)
            trip[z] = 1
            its.append(z)
    stmt = Statement(
        name=name, loops=tuple(out_its) + tuple(z_its), trip_counts=trip,
        reads=(Access(ctx.name_of(src), tuple(its)),),
        writes=(Access(name, out_its),), flops_per_iter=0.0, op="add")
    ctx.emit(stmt, fe.outvars[0])


def _h_reshape(ctx: _Ctx, fe: FlatEqn) -> None:
    """Singleton-insert/remove reshapes (and ``squeeze``) as projection
    copies: non-unit dims keep their order, so each non-unit source dim
    reads the matching output iterator; size-1 source dims read through a
    trip-1 iterator and size-1 output dims are broadcast."""
    src = fe.invars[0]
    out_aval = fe.outvars[0].aval
    out_shape = tuple(int(n) for n in out_aval.shape)
    src_shape = tuple(int(n) for n in src.aval.shape)
    name = ctx.fresh("rs")
    out_its = iter_names(name, len(out_shape))
    trip = {it: int(n) for it, n in zip(out_its, out_shape)}
    nz_out = [i for i, n in enumerate(out_shape) if n != 1]
    z_its: list[str] = []
    src_its: list[str] = []
    k = 0
    for s in src_shape:
        if s == 1:
            z = f"{name}_z{len(z_its)}"
            z_its.append(z)
            trip[z] = 1
            src_its.append(z)
        else:
            src_its.append(out_its[nz_out[k]])
            k += 1
    stmt = Statement(
        name=name, loops=tuple(out_its) + tuple(z_its), trip_counts=trip,
        reads=(Access(ctx.name_of(src), tuple(src_its)),),
        writes=(Access(name, out_its),), flops_per_iter=0.0, op="add")
    ctx.emit(stmt, fe.outvars[0])


def _h_reduce_sum(ctx: _Ctx, fe: FlatEqn) -> None:
    axes = tuple(fe.eqn.params["axes"])
    src = fe.invars[0]
    out_aval = fe.outvars[0].aval
    name = ctx.fresh("rsum")
    out_its = iter_names(name, len(out_aval.shape))
    red_its = iter_names(name, len(axes), "r")
    trip = {it: int(n) for it, n in zip(out_its, out_aval.shape)}
    its: list[str] = []
    kept = 0
    for d, s in enumerate(src.aval.shape):
        if d in axes:
            r = red_its[axes.index(d)]
            trip[r] = int(s)
            its.append(r)
        else:
            its.append(out_its[kept])
            kept += 1
    stmt = Statement(
        name=name, loops=tuple(out_its) + tuple(red_its), trip_counts=trip,
        reads=(Access(ctx.name_of(src), tuple(its)),),
        writes=(Access(name, out_its),), flops_per_iter=1.0, op="add")
    ctx.emit(stmt, fe.outvars[0])


HANDLERS: dict[str, Callable[[_Ctx, FlatEqn], None]] = {
    "dot_general": _h_dot_general,
    "add": _h_add_sub("add"),
    "sub": _h_add_sub("sub"),
    "mul": _h_mul,
    "div": _h_div,
    "neg": _h_neg,
    "max": _h_minmax("max"),
    "min": _h_minmax("min"),
    "integer_pow": _h_integer_pow,
    "transpose": _h_transpose,
    "broadcast_in_dim": _h_broadcast_in_dim,
    "reshape": _h_reshape,
    "squeeze": _h_reshape,
    "convert_element_type": _h_convert,
    "reduce_sum": _h_reduce_sum,
    **{p: _h_unary(p) for p in UNARY_PRIMITIVES},
}


def _float_ok(dtype) -> bool:
    return str(np.dtype(dtype)) in _FLOAT_OK


def _nonunit(shape) -> tuple[int, ...]:
    return tuple(int(n) for n in shape if int(n) != 1)


def _prim_supported(fe: FlatEqn) -> bool:
    """Per-primitive structural constraints beyond the generic gate."""
    name = fe.eqn.primitive.name
    if name == "reshape":
        if fe.eqn.params.get("dimensions") is not None:
            return False                     # fused transpose-reshape
        return _nonunit(fe.invars[0].aval.shape) == \
            _nonunit(fe.outvars[0].aval.shape)
    if name == "squeeze":
        return True
    return True


def _supported(fe: FlatEqn, eqn_produced: set) -> bool:
    if fe.eqn.primitive.name not in HANDLERS:
        return False
    if len(fe.outvars) != 1:
        return False
    out_aval = fe.outvars[0].aval
    if not _float_ok(out_aval.dtype) or len(out_aval.shape) == 0:
        return False
    if any(int(n) == 0 for n in out_aval.shape):
        return False
    for a in fe.invars:
        if not _float_ok(a.aval.dtype):
            # non-float operands are only acceptable as foldable scalar
            # literals (``x * 2`` with an int literal)
            if _scalar_literal(a) is None:
                return False
        if any(int(n) == 0 for n in a.aval.shape):
            return False
        # A rank-0 value produced by an equation comes out of an opaque
        # segment promoted to shape (1,); affine statements cannot read
        # it — the consumer joins the opaque segment instead.
        if isinstance(a, Var) and a in eqn_produced \
                and len(a.aval.shape) == 0:
            return False
    return _prim_supported(fe)


# ---------------------------------------------------------------------------
# Main lowering pass
# ---------------------------------------------------------------------------
def lower_flat(closed, flat_eqns: list[FlatEqn], resolved_outs: list,
               sub_consts: dict, fingerprint: str) -> LoweredJaxpr:
    """Lower one flattened closed jaxpr into a :class:`LoweredJaxpr`."""
    ctx = _Ctx(fingerprint)
    jaxpr = closed.jaxpr

    in_names = []
    for i, v in enumerate(jaxpr.invars):
        name = f"in{i}"
        ctx.add_array(name, v.aval.shape, v.aval.dtype)
        ctx.var_name[v] = name
        in_names.append(name)
    const_names = []
    for i, v in enumerate(jaxpr.constvars):
        name = f"c{i}"
        ctx.add_array(name, v.aval.shape, v.aval.dtype)
        ctx.var_name[v] = name
        const_names.append(name)
    for i, (v, val) in enumerate(sub_consts.items()):
        name = f"sc{i}"
        arr = np.asarray(val)
        ctx.add_array(name, arr.shape, arr.dtype)
        ctx.static[name] = jnp.asarray(val)
        ctx.var_name[v] = name

    eqn_produced: set = set()
    n_supported = 0
    pending: list[tuple[int, FlatEqn]] = []
    # vars needed outside any opaque segment: read by a later equation or
    # returned by the function
    last_reader: dict[Var, int] = {}
    for idx, fe in enumerate(flat_eqns):
        for a in fe.invars:
            if isinstance(a, Var):
                last_reader[a] = idx
    needed_late = {a for a in resolved_outs if isinstance(a, Var)}

    def flush_opaque() -> None:
        nonlocal pending
        if not pending:
            return
        seg = pending
        pending = []
        seg_first, seg_last = seg[0][0], seg[-1][0]
        feqns = [fe for (_, fe) in seg]
        defined = {ov for fe in feqns for ov in fe.outvars}
        # outputs needed beyond the segment
        outs = []
        for fi, fe in enumerate(feqns):
            for ov in fe.outvars:
                if ov in needed_late or last_reader.get(ov, -1) > seg_last:
                    outs.append((fi, ov))
        ctx.opaque_flops_est += sum(
            float(np.prod(ov.aval.shape)) if ov.aval.shape else 1.0
            for fe in feqns for ov in fe.outvars)
        for k, (fi, ov) in enumerate(outs):
            # Each output statement re-runs only its own prefix, so it
            # reads only the external inputs that prefix actually uses —
            # otherwise every segment output would count as a consumer of
            # every segment input and inflate materialization boundaries.
            prefix = feqns[:fi + 1]
            ins: list[Var] = []
            for pfe in prefix:
                for a in pfe.invars:
                    if isinstance(a, Var) and a not in defined \
                            and a not in ins:
                        ins.append(a)
            in_names_seg = tuple(ctx.name_of(a) for a in ins)
            unpromote = tuple(n in ctx.promoted for n in in_names_seg)
            promote = len(ov.aval.shape) == 0
            shape = (1,) if promote else tuple(int(n)
                                               for n in ov.aval.shape)
            name = ctx.fresh("opq")
            digest = hashlib.sha256(
                f"{fingerprint}:{seg_first}:{k}".encode()).hexdigest()
            op = f"{OPAQUE_PREFIX}{digest[:24]}"
            register_opaque(op, _segment_callable(
                prefix, tuple(ins), unpromote, ov, promote))
            ctx.opaque_ops.append(op)
            out_its = iter_names(name, len(shape))
            stmt = Statement(
                name=name, loops=out_its,
                trip_counts={it: int(n)
                             for it, n in zip(out_its, shape)},
                reads=tuple(Access(n, ()) for n in in_names_seg),
                writes=(Access(name, out_its),),
                flops_per_iter=1.0, op=op)
            ctx.emit(stmt, ov, shape=shape, dtype=ov.aval.dtype)
            if promote:
                ctx.promoted.add(name)

    precision_bytes = 4
    for idx, fe in enumerate(flat_eqns):
        if _supported(fe, eqn_produced):
            flush_opaque()
            n_before = len(ctx.statements)
            HANDLERS[fe.eqn.primitive.name](ctx, fe)
            n_supported += 1
            # dtype aliases (convert_element_type) emit no statement
            ctx.supported_flops += sum(
                s.flops for s in ctx.statements[n_before:])
            for a in tuple(fe.invars) + tuple(fe.outvars):
                dt = np.dtype(a.aval.dtype)
                # jnp.issubdtype: ml_dtypes (bfloat16) are not numpy floats
                if jnp.issubdtype(dt, jnp.floating):
                    precision_bytes = min(precision_bytes, dt.itemsize)
        else:
            pending.append((idx, fe))
        eqn_produced.update(fe.outvars)
    flush_opaque()

    # ---- function outputs -------------------------------------------------
    produced = {s.writes[0].array for s in ctx.statements}
    read_anywhere = {a.array for s in ctx.statements for a in s.reads}
    out_specs: list[OutSpec] = []
    out_avals: list[tuple] = []
    copied: dict[str, str] = {}
    for v in resolved_outs:
        if isinstance(v, Literal):
            name = ctx.name_of(v)
            out_specs.append(OutSpec("binding", name))
            val = np.asarray(v.val)
            out_avals.append((val.shape, val.dtype))
            continue
        name = ctx.var_name[v]
        aval = v.aval
        out_avals.append((tuple(int(n) for n in aval.shape), aval.dtype))
        promoted = name in ctx.promoted
        if name not in produced:
            out_specs.append(OutSpec("binding", name, promoted))
            continue
        if name in read_anywhere:
            # consumed downstream: forward through a copy so the value
            # stays a *final* graph output
            cname = copied.get(name)
            if cname is None:
                cname = f"{name}_out"
                arr = ctx.arrays[name]
                its = iter_names(cname, len(arr.shape))
                ctx.statements.append(copy_statement(
                    cname, cname, name, its, its,
                    dict(zip(its, arr.shape))))
                ctx.arrays[cname] = intermediate(
                    cname, arr.shape, dtype_bytes=arr.dtype_bytes)
                copied[name] = cname
                if promoted:
                    ctx.promoted.add(cname)
            out_specs.append(OutSpec("array", cname, promoted))
        else:
            out_specs.append(OutSpec("array", name, promoted))

    # Work-reducing rewrites before the graph freezes: matmul chains keep
    # the user's association order in the jaxpr, but the graph may legally
    # re-parenthesize to the cheapest order (final outputs stay put).
    from ..core.rewrite import reassociate_matmul_chains
    reassociate_matmul_chains(
        ctx.arrays, ctx.statements,
        protected={spec.ref for spec in out_specs if spec.kind == "array"})
    graph = TaskGraph(name=graph_name_of(fingerprint),
                      arrays=ctx.arrays, statements=ctx.statements,
                      traced=True)
    coverage = Coverage(
        n_eqns=len(flat_eqns), n_supported=n_supported,
        supported_flops=ctx.supported_flops,
        opaque_flops_est=ctx.opaque_flops_est)
    return LoweredJaxpr(
        fingerprint=fingerprint,
        graph=graph,
        in_names=tuple(in_names),
        const_names=tuple(const_names),
        static_bindings=dict(ctx.static),
        in_avals=tuple((tuple(int(n) for n in v.aval.shape), v.aval.dtype)
                       for v in jaxpr.invars),
        out_specs=tuple(out_specs),
        out_avals=tuple(out_avals),
        coverage=coverage,
        opaque_ops=tuple(ctx.opaque_ops),
        precision_bytes=precision_bytes,
    )
