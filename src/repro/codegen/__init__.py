"""Code generation subsystem: ExecutionPlan -> plan-faithful executables.

The paper (§5) emits HLS-C++ + OpenCL host code from the NLP solution; here
the same lowering targets JAX/Pallas:

* ``lower.py``      per-fused-task lowering: statements -> ContractionSpecs
                    (grid = plan permutation, blocks = plan tiles, fused
                    init+accumulate, buffering semantics), one raw traceable
                    body per task;
* ``schedule.py``   wave schedule: topological levels x slice assignment,
                    cross-slice transfer timing, buffer liveness/donation;
* ``program.py``    whole-plan engine: the entire fused DAG in ONE
                    ``jax.jit`` program per impl, with a process-wide cache
                    keyed by (graph fingerprint, plan fingerprint, impl);
* ``executor.py``   ``PlanExecutable``: program mode (default, fused) and
                    per-task mode (debug/validation, overlap- and
                    donation-aware host dispatch);
* ``reference.py``  naive statement-order einsum oracle for bit-level
                    validation (run the executable under
                    ``kernel_impl("pallas_interpret")`` to validate the
                    actual kernel bodies against it).

``repro.core.apply`` remains as a deprecation shim over this package.
"""
from .executor import PlanExecutable, plan_executor
from .lower import LoweredUnit, TaskLowering, lower_task
from .program import (PlanProgram, ProgramCache, cache_stats,
                      clear_program_cache, compile_cache_dir,
                      compiled_program, enable_compile_cache,
                      enable_persistent_cache, graph_fingerprint,
                      persistent_cache_dir, plan_fingerprint, program_cache,
                      program_key, set_program_cache_size)
from .reference import (OPAQUE_PREFIX, allclose, assert_close,
                        eval_statement, opaque_fn, random_inputs,
                        reference_executor, register_opaque,
                        unregister_opaque)
from .schedule import Transfer, WaveSchedule, wave_schedule

__all__ = [
    "PlanExecutable", "plan_executor",
    "LoweredUnit", "TaskLowering", "lower_task",
    "PlanProgram", "ProgramCache", "compiled_program", "cache_stats",
    "clear_program_cache", "graph_fingerprint", "plan_fingerprint",
    "program_cache", "program_key", "set_program_cache_size",
    "compile_cache_dir", "enable_compile_cache", "enable_persistent_cache",
    "persistent_cache_dir",
    "Transfer", "WaveSchedule", "wave_schedule",
    "allclose", "assert_close", "eval_statement",
    "random_inputs", "reference_executor",
    "OPAQUE_PREFIX", "opaque_fn", "register_opaque", "unregister_opaque",
]
