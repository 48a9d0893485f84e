"""Generalized tiled-contraction Pallas kernel — plan-faithful codegen.

Where the ``matmul`` kernel hard-codes the ``(i,k)x(k,j)`` pattern, this
kernel is *generated from* a :class:`ContractionSpec`: the grid is the plan's
inter-tile loop nest in permutation order (reduction loops innermost, as the
solver pins them), each operand's BlockSpec carries the plan's tile sizes,
and the fused init statement's value seeds the accumulator on the first
visit to an output tile.  One ``pallas_call`` therefore executes one fused
task — the paper's §5 claim that fusion/tiling/permutation decisions are
*lowered into the kernel*, not merely cost-modeled.

Pipelining: the Pallas grid pipeline double-buffers HBM->VMEM transfers;
``dimension_semantics`` marks non-reduction grid dims ``parallel`` when the
plan chose ``buffers >= 2`` (computation-communication overlap) and
``arbitrary`` (strictly sequential) otherwise, so the plan's buffering
decision reaches the Mosaic scheduler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..dispatch import compiler_params
from .ref import apply_epilogue, combine_terms, project_term, scale_offset
from .spec import ContractionSpec, Operand


def _index_map(loop_names: tuple[str, ...], opnd: Operand):
    pos = tuple(loop_names.index(it) for it in opnd.iters)
    return lambda *g: tuple(g[p] for p in pos)


def _make_kernel(spec: ContractionSpec):
    n_reads = len(spec.reads)
    n_init = len(spec.init_reads)
    n_epi = len(spec.epi_reads)
    red_dims = spec.reduction_dims
    n_red = {d: spec.grid[d] for d in red_dims}
    out_sub = spec.out_subscript
    read_subs = spec.einsum_inputs(spec.reads)
    init_subs = spec.einsum_inputs(spec.init_reads)
    out_block = spec.out_block

    def contrib(read_vals):
        return combine_terms(read_subs, out_sub, spec.op, read_vals,
                             out_block)

    def init_val(init_vals):
        if not spec.init_reads:
            return jnp.zeros(out_block, jnp.float32)
        return scale_offset(
            combine_terms(init_subs, out_sub, spec.init_op, init_vals,
                          out_block),
            spec.init_coeff, spec.init_offset)

    def split(refs):
        # blocks keep their dtype: combine_terms computes in f32 and picks
        # the contraction's precision from the operands' dtype
        vals = [r[...] for r in refs[:n_reads + n_init + n_epi]]
        return (vals[:n_reads], vals[n_reads:n_reads + n_init],
                vals[n_reads + n_init:], refs[n_reads + n_init + n_epi])

    def finish(total, inits, epis):
        """total -> stored value: scale, add init, run the fused tail."""
        val = scale_offset(total, spec.coeff, spec.offset)
        if spec.init_reads:
            val = val + init_val(inits)
        return apply_epilogue(spec, val, epis)

    if not red_dims:
        def kernel(*refs):
            reads, inits, epis, o_ref = split(refs)
            o_ref[...] = finish(contrib(reads), inits, epis) \
                .astype(o_ref.dtype)
        return kernel, False

    def _at_zero(dims) -> jax.Array | None:
        pred = None
        for d in dims:
            p = pl.program_id(d) == 0
            pred = p if pred is None else jnp.logical_and(pred, p)
        return pred

    loop_names = spec.loop_names

    def red_contrib(read_vals):
        if spec.op == "mul":
            # The joint contraction is linear in each reduction block, so
            # summing per-block einsums over the reduction grid is exact.
            return contrib(read_vals)
        # "add"/"sub": an operand missing a reduction iterator is constant
        # across that reduction's blocks — count its term once (on the
        # first visit), not once per block, matching the einsum projection.
        total = jnp.zeros(out_block, jnp.float32)
        for i, (sub, opnd, v) in enumerate(zip(read_subs, spec.reads,
                                               read_vals)):
            term = project_term(sub, out_sub, v, out_block)
            missing = [d for d in red_dims
                       if loop_names[d] not in opnd.iters]
            pred = _at_zero(missing)
            if pred is not None:
                term = jnp.where(pred, term, jnp.zeros_like(term))
            if spec.op == "sub" and i > 0:
                term = -term
            total += term
        return total

    def kernel(*refs):
        reads, inits, epis, o_ref = split(refs[:-1])
        acc_ref = refs[-1]

        first = _at_zero(red_dims)
        last = None
        for d in red_dims:
            l = pl.program_id(d) == n_red[d] - 1
            last = l if last is None else jnp.logical_and(last, l)

        # The accumulator holds the raw contribution sum; scaling, the init
        # value and the elementwise epilogue are applied once, at store time
        # on the final reduction step (the init block's index map depends
        # only on output dims, so its value is the same at every step).
        @pl.when(first)
        def _seed():
            acc_ref[...] = jnp.zeros(out_block, jnp.float32)

        acc_ref[...] += red_contrib(reads)

        @pl.when(last)
        def _store():
            o_ref[...] = finish(acc_ref[...], inits, epis) \
                .astype(o_ref.dtype)

    return kernel, True


def _dimension_semantics(spec: ContractionSpec) -> tuple[str, ...]:
    red = set(spec.reduction_dims)
    if spec.buffers < 2:
        return tuple("arbitrary" for _ in spec.loops)
    return tuple("arbitrary" if d in red else "parallel"
                 for d in range(len(spec.loops)))


@functools.lru_cache(maxsize=None)
def build_contraction(spec: ContractionSpec, interpret: bool = False):
    """Build (and cache) the pallas_call for one spec.

    The returned callable takes the *padded* operands (spec.reads, then
    spec.init_reads, then spec.epi_reads order) and returns the padded
    output.
    """
    body, has_scratch = _make_kernel(spec)
    loop_names = spec.loop_names
    in_specs = [
        pl.BlockSpec(spec.block_shape(o), _index_map(loop_names, o))
        for o in spec.all_reads
    ]
    out_spec = pl.BlockSpec(spec.out_block,
                            _index_map(loop_names,
                                       Operand("<out>", spec.out_iters)))
    kwargs = {}
    if has_scratch:
        kwargs["scratch_shapes"] = [pltpu.VMEM(spec.out_block, jnp.float32)]
    return pl.pallas_call(
        body,
        grid=spec.grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(spec.out_padded, jnp.float32),
        interpret=interpret,
        compiler_params=compiler_params(_dimension_semantics(spec)),
        **kwargs,
    )


def contract(spec: ContractionSpec, *operands: jax.Array,
             interpret: bool = False) -> jax.Array:
    """Run the kernel on padded operands; returns the padded output."""
    return build_contraction(spec, interpret)(*operands)
