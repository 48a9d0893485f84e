"""Quickstart: the paper's 3mm walkthrough (§2.4) end-to-end.

    PYTHONPATH=src python examples/quickstart.py

1. Build the 3mm affine task graph (Listing 4).
2. Maximal distribution + output-stationary fusion (Fig. 3 -> Listing 6).
3. Solve the unified NLP (tiling x permutation x padding x buffering x
   concurrency x slice placement) in all four solver modes.
4. Generate JAX code from the winning plan and validate it bit-for-bit
   against the naive reference executor.
5. The new front door: trace an *arbitrary JAX function* (a 2-layer MLP —
   never hand-modeled) into the same pipeline via ``repro.frontend``.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import frontend
from repro.codegen import (allclose, enable_compile_cache, plan_executor,
                           random_inputs, reference_executor)
from repro.core import (ONE_SLICE, THREE_SLICE, SolverOptions, polybench,
                        solve)
from repro.core.fusion import fuse


def main() -> None:
    enable_compile_cache()
    g = polybench.build("3mm")
    print(f"== task graph: {g.name} ==")
    print(f"statements: {[s.name for s in g.statements]}")
    print(f"inputs: {g.external_inputs()}  outputs: {g.final_outputs()}")

    fg = fuse(g)
    print(f"\n== fused dataflow graph (paper Fig. 3) ==")
    for t in fg.tasks:
        print(f"  {t.name}: {[s.name for s in t.statements]} "
              f"-> {t.output_array}")
    print(f"  edges: {fg.edges}")

    print("\n== NLP solve, all modes (TPU-scale datasets) ==")
    gtpu = polybench.build("3mm", scale=polybench.TPU_SCALE)
    plans = {}
    for mode in ("prometheus", "sisyphus", "streamhls", "autodse"):
        hw = THREE_SLICE if mode == "prometheus" else ONE_SLICE
        plan = solve(gtpu, hw, SolverOptions(mode=mode, time_budget_s=15))
        plans[mode] = plan
        print(f"  {mode:11s} {plan.gflops:10.1f} GF/s  "
              f"(solved in {plan.solver_seconds:5.2f}s, "
              f"{plan.n_evaluated} configs, "
              f"space {plan.space_size:.1e}"
              f"{', TIMEOUT' if plan.timed_out else ''})")

    best = plans["prometheus"]
    print("\n== winning plan ==")
    print(best.summary())

    print("\n== codegen + validation (paper-exact medium sizes) ==")
    plan_m = solve(g, THREE_SLICE, SolverOptions(time_budget_s=10))
    exe = plan_executor(g, plan_m)
    for tid, lw in sorted(exe.lowerings("xla").items()):
        print(f"  {lw.name}: kind={lw.kind} grid={lw.grid} "
              f"slice={lw.slice_id} inputs={list(lw.in_arrays)} "
              f"-> {lw.out_array}")
    ins = random_inputs(g, seed=0)
    ref = reference_executor(g)(ins)
    out = exe(ins)
    for k in ref:
        ok = allclose(out[k], ref[k])
        print(f"  {k}: allclose={ok}")
        assert ok

    print("\n== frontend: trace an arbitrary JAX function ==")

    def mlp(params, x):
        """2-layer MLP nobody hand-modeled: the frontend's job."""
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    rng = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32)),
        "b1": jnp.asarray(rng.normal(size=(128,)).astype(np.float32)),
        "w2": jnp.asarray(rng.normal(size=(128, 32)).astype(np.float32)),
        "b2": jnp.asarray(rng.normal(size=(32,)).astype(np.float32)),
    }
    x = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))

    tf = frontend.trace(mlp, params, x)
    cov = tf.coverage
    print(f"  {tf!r}")
    print(f"  coverage: {cov.n_supported}/{cov.n_eqns} equations "
          f"supported ({cov.flop_ratio:.0%} of est. FLOPs); the tanh "
          "lowers through the unary pointwise family")
    plan_t = tf.solve(opts=SolverOptions(time_budget_s=10))
    print(f"  solved: {plan_t.latency_s * 1e6:.2f}us model latency, "
          f"{len(plan_t.configs)} tasks")
    exe = tf.executable(plan=plan_t)          # whole-plan compiled program
    got = exe(params, x)
    want = jax.jit(mlp)(params, x)
    ok = allclose(got, want)
    print(f"  traced program vs jax.jit oracle: allclose={ok}")
    assert ok
    print("quickstart OK")


if __name__ == "__main__":
    main()
