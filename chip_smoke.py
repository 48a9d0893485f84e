"""Bring-up check: drive both halves of the system once on one TPU chip.

    python chip_smoke.py                # one chip: phases 0-2
    python chip_smoke.py --four-chips   # four chips: phase 3 only

Phases, all in this one process (a chip belongs to one process at a time):

0. device     a TPU, or exit non-zero; print its ``device_kind``.
1. model      qwen3-0.6b at its published config (nothing cut), random
              weights from ``--seed``, served by ``serve.Engine``: 4 prompts
              x 128 tokens, 32 greedy tokens each.  Every step's logits are
              compared with a float32 forward pass over the same prefix at
              ``precision=highest``.  The prefill runs again with
              ``attn_impl="pallas"`` (the flash-attention kernel).
2. plan       a SwiGLU FFN block at qwen3-0.6b widths (512 tokens x 1024 ->
              3072 -> 1024, bf16) served by ``serve.PlanEngine`` with
              ``fallback=False``: outputs vs ``jax.jit(fn)``, zero
              fallbacks, full equation coverage, Mosaic kernels
              (``tpu_custom_call``) in the served program.  Then 3mm at
              ``polybench.TPU_SCALE`` through ``plan_executor`` vs the oracle.
3. four chips (``--four-chips``, nothing else): training steps of
              qwen3-0.6b cut to 4 layers on a 2x2 (data, model) mesh against
              the same steps on a 1x1 mesh; a 3-slice 3mm plan placed across
              the chips, validated against the oracle.

Kernels run compiled (``pallas``), never through the ``auto`` resolution.
Every phase runs even when an earlier one failed; any failure exits 1 and
suppresses the result line.  The last line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

``--rehearse`` runs the same phases at tiny sizes on whatever JAX finds,
the kernels in interpret mode (``JAX_PLATFORMS=cpu``, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for
``--four-chips``).  It checks control flow only, so it prints no result
line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# Tolerances, each with what it compares.
#: bf16 model vs float32 forward: max |logit error| / max |f32 logit|.
MODEL_TOL = 5e-2
#: bf16 plan vs bf16 jax.jit: the frontend's half-precision band.
PLAN_BF16_RTOL = 2e-2
#: f32 plan vs the statement oracle at precision=highest (scale-aware).
ORACLE_RTOL = 2e-4
#: four-chip vs one-chip training: |loss difference| / loss.
LOSS_RTOL = 2e-2


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def rel_err(got, ref) -> float:
    """max |got - ref| / max |ref|, in float32 on the device."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phase 1: model serving
# ---------------------------------------------------------------------------
def phase_model(args, impl: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import smoke
    from repro.kernels import kernel_impl
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig

    cfg = get_config("qwen3-0.6b")
    batch, plen, new = 4, 128, 32
    if args.rehearse:
        cfg, plen, new = smoke(cfg), 16, 4
    log("model", f"{cfg.name}: layers={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab} compute={cfg.compute_dtype}")
    params, secs = timed(M.init_params, cfg, jax.random.PRNGKey(args.seed))
    log("model", f"params {M.param_count(params):,} (random, seed "
        f"{args.seed}) made in {secs:.3f}s")
    prompts = np.random.default_rng(args.seed).integers(
        0, cfg.vocab, size=(batch, plen)).astype(np.int32)
    sc = ServeConfig(max_len=plen + new)

    eng = Engine(cfg, params, sc)
    (toks, logits), first = timed(eng.generate, prompts, new,
                                  return_logits=True)
    (toks2, _), steady = timed(eng.generate, prompts, new,
                               return_logits=True)
    check(np.array_equal(toks, toks2), "greedy generation not repeatable")
    log("model", f"Engine.generate {batch}x{plen} prompt +{new} tokens: "
        f"first call {first:.3f}s (compile included), second {steady:.3f}s"
        f" -> compile ~{first - steady:.3f}s, "
        f"{batch * new / steady:.1f} tok/s")

    # float32 reference over every prefix the engine decoded from
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    seq = jnp.asarray(np.concatenate([prompts, toks[:, :-1]], axis=1))

    @jax.jit
    def ref_logits(p, t):
        return M.logits_fn(p, cfg32, M.forward(p, cfg32, t))

    with jax.default_matmul_precision("highest"):
        ref, secs = timed(ref_logits, params, seq)
    ref = ref[:, plen - 1:]                       # (B, new, V)
    errs = [rel_err(logits[:, t], ref[:, t]) for t in range(new)]
    agree = float(np.mean(np.asarray(jnp.argmax(ref, -1)) == toks))
    log("model", f"vs float32 forward (precision=highest, {secs:.3f}s): "
        f"max|err|/max|ref| prefill {errs[0]:.3e}, decode max "
        f"{max(errs[1:] or [0.0]):.3e} over {new - 1} steps "
        f"(tolerance {MODEL_TOL}); greedy token = f32 argmax on "
        f"{agree:.1%}")
    check(max(errs) <= MODEL_TOL, f"model logits error {max(errs):.3e} > "
          f"{MODEL_TOL}")

    # the prefill again through the flash-attention kernel
    cfgp = dataclasses.replace(cfg, attn_impl="pallas")
    engp = Engine(cfgp, params, sc)
    with kernel_impl(impl):
        (_, lp), secs = timed(engp.generate, prompts, 1, return_logits=True)
        hlo = engp._prefill.lower(params=params, tokens=jnp.asarray(prompts),
                                  max_len=sc.max_len).as_text()
    err = rel_err(lp[:, 0], ref[:, 0])
    kernel = "tpu_custom_call" in hlo
    log("model", f"prefill attn_impl=pallas ({impl}) {secs:.3f}s (compile "
        f"included): max|err|/max|ref| {err:.3e} vs float32 forward "
        f"(tolerance {MODEL_TOL}); tpu_custom_call in prefill HLO: {kernel}")
    check(err <= MODEL_TOL, f"pallas prefill error {err:.3e} > {MODEL_TOL}")
    check(kernel or args.rehearse, "flash kernel not in the prefill program")


# ---------------------------------------------------------------------------
# phase 2: plan serving
# ---------------------------------------------------------------------------
def ffn(x, w1, w3, w2):
    """SwiGLU FFN block as ``models.ffn.swiglu`` computes it."""
    import jax
    import jax.numpy as jnp
    a = x @ w1
    g = x @ w3
    h = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * g
    return h @ w2


def phase_plan(args, impl: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.codegen import (allclose, compiled_program, plan_executor,
                               random_inputs, reference_executor)
    from repro.core import SolverOptions, polybench, solve
    from repro.kernels import kernel_impl
    from repro.serve.engine import PlanEngine, ServeConfig

    t, d, f = (128, 256, 768) if args.rehearse else (512, 1024, 3072)
    bf = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    w1 = jax.random.normal(keys[0], (d, f), bf) * d ** -0.5
    w3 = jax.random.normal(keys[1], (d, f), bf) * d ** -0.5
    w2 = jax.random.normal(keys[2], (f, d), bf) * f ** -0.5
    xs = [jax.random.normal(k, (t, d), bf) for k in keys[3:7]]

    eng = PlanEngine(impl=impl, sc=ServeConfig(fallback=False))
    t0 = time.perf_counter()
    tf = eng.register_function("ffn", ffn, (xs[0], w1, w3, w2))
    reg = time.perf_counter() - t0
    cov = tf.coverage
    plan = tf.solve()                    # the registered (cached) plan
    log("plan", f"SwiGLU FFN {t}x{d}->{f}->{d} bf16: traced + solved in "
        f"{reg:.3f}s ({plan.n_evaluated} evaluations); coverage "
        f"{cov.n_supported}/{cov.n_eqns} equations")
    oracle = jax.jit(ffn)
    errs = []
    for i, x in enumerate(xs):
        out, secs = timed(eng.submit, "ffn", (x, w1, w3, w2))
        errs.append(rel_err(out, oracle(x, w1, w3, w2)))
        check(allclose(out, oracle(x, w1, w3, w2), rtol=PLAN_BF16_RTOL),
              f"request {i}: plan output differs from jax.jit(fn)")
        log("plan", f"submit {i}: {secs:.3f}s"
            + (" (compile included)" if i == 0 else ""))
    health = eng.stats()["resilience"]["entries"]["ffn"]
    prog = compiled_program(tf.graph, plan, impl)
    hlo = "\n".join(low.as_text() for low in
                    prog.lower(tf.bind_args((xs[0], w1, w3, w2))))
    kernel = "tpu_custom_call" in hlo
    log("plan", f"vs jax.jit(fn): max|err|/max|ref| {max(errs):.3e} "
        f"(scale-aware rtol {PLAN_BF16_RTOL}); state {health['state']}, "
        f"ok {health['ok']}, fallbacks {health['fallbacks']}; units "
        f"{prog.unit_kinds()}; tpu_custom_call in served HLO: {kernel}")
    check(cov.eqn_ratio == 1.0, "equation coverage below 100%")
    check(health["state"] == "ok" and health["fallbacks"] == 0
          and health["ok"] == len(xs), f"resilience: {health}")
    check(kernel or args.rehearse, "no Mosaic kernel in the served program")
    eng.shutdown()

    scale = 1 if args.rehearse else polybench.TPU_SCALE
    g = polybench.build("3mm", scale=scale)
    p3, secs = timed(solve, g, None, SolverOptions(time_budget_s=30.0))
    log("plan", f"3mm x{scale} {[a.shape for a in g.arrays.values()]}: "
        f"solved in {secs:.3f}s")
    exe = plan_executor(g, p3, impl=impl, mode="program")
    ins = random_inputs(g, seed=args.seed)
    out, first = timed(exe, ins)
    out, steady = timed(exe, ins)
    with jax.default_matmul_precision("highest"):
        ref = reference_executor(g)(ins)
    err = rel_err(out["G"], ref["G"])
    log("plan", f"3mm program: first call {first:.3f}s (compile included), "
        f"second {steady:.3f}s; vs oracle (precision=highest) "
        f"max|err|/max|ref| {err:.3e} (scale-aware rtol {ORACLE_RTOL}); "
        f"units {exe.program().unit_kinds()}")
    check(all(allclose(out[k], ref[k], rtol=ORACLE_RTOL) for k in ref),
          "3mm differs from the oracle")


# ---------------------------------------------------------------------------
# phase 3: four chips
# ---------------------------------------------------------------------------
def phase_four_chips(args, impl: str) -> None:
    import jax
    import numpy as np
    from repro.codegen import (allclose, plan_executor, random_inputs,
                               reference_executor)
    from repro.configs import get_config
    from repro.configs.base import smoke
    from repro.core import SolverOptions, THREE_SLICE, polybench, solve
    from repro.launch.mesh import make_mesh
    from repro.train.loop import TrainConfig, train

    devices = jax.devices()
    check(len(devices) >= 4, f"need 4 devices, have {len(devices)}")
    cfg = get_config("qwen3-0.6b")
    cfg = smoke(cfg) if args.rehearse else dataclasses.replace(
        cfg, n_layers=4)
    steps = 3
    log("four", f"training {cfg.name}: n_layers={cfg.n_layers} (published "
        f"28), d={cfg.d_model}, vocab={cfg.vocab}; {steps} steps, global "
        f"batch 8 x 128 tokens, seed {args.seed}")
    losses = {}
    for shape in ((1, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"), devices)
        with tempfile.TemporaryDirectory() as ckpt:
            tc = TrainConfig(total_steps=steps, checkpoint_every=steps + 1,
                             checkpoint_dir=ckpt, global_batch=8,
                             seq_len=128, seed=args.seed)
            t0 = time.perf_counter()
            final, history, _ = train(cfg, tc, mesh=mesh)
            secs = time.perf_counter() - t0
        losses[shape] = [loss for _, loss in history]
        emb = final.params["embed"]
        log("four", f"mesh {shape}: {secs:.3f}s (compile included), losses "
            f"{losses[shape]}; embed {emb.shape} on "
            f"{sorted(d.id for d in emb.devices())} as {emb.sharding.spec}")
    diff = max(abs(a - b) / abs(b) for a, b in
               zip(losses[(2, 2)], losses[(1, 1)]))
    log("four", f"2x2 vs 1x1 losses: max |diff|/loss {diff:.3e} "
        f"(tolerance {LOSS_RTOL})")
    check(len(losses[(2, 2)]) == steps and diff <= LOSS_RTOL,
          "sharded training diverges from one chip")

    scale = 1 if args.rehearse else polybench.TPU_SCALE
    g = polybench.build("3mm", scale=scale)
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=30.0))
    if args.rehearse:     # tiny 3mm fits one slice; place it as the chip does
        plan = dataclasses.replace(plan, configs={
            tid: dataclasses.replace(c, slice_id=tid % 3)
            for tid, c in plan.configs.items()})
    slices = {tid: c.slice_id for tid, c in plan.configs.items()}
    ins = random_inputs(g, seed=args.seed)
    with jax.default_matmul_precision("highest"):
        ref = reference_executor(g)(ins)
    check(len(set(slices.values())) >= 2, "plan uses one slice only")
    for mode in ("program", "per_task"):
        exe = plan_executor(g, plan, impl=impl, mode=mode)
        out, secs = timed(exe, ins)
        err = rel_err(out["G"], ref["G"])
        log("four", f"3mm x{scale} on THREE_SLICE, mode={mode}: task->slice "
            f"{slices}; {secs:.3f}s (compile included), multi-slice "
            f"schedule {exe.schedule.multi_slice}, G on "
            f"{sorted(d.id for d in out['G'].devices())}; vs oracle "
            f"max|err|/max|ref| {err:.3e}")
        check(all(allclose(out[k], ref[k], rtol=ORACLE_RTOL) for k in ref),
              f"3-slice {mode} run differs from the oracle")
    # A program segment is one jit on one device: the device_put inside it
    # moves nothing.  Say where its compiled code runs.
    segs = exe.program(impl).lower(ins)
    placed = {d.id for low in segs for s in
              jax.tree.leaves(low.compile().output_shardings)
              for d in s.device_set}
    log("four", f"mode=program: {len(segs)} segment(s), outputs compiled "
        f"onto device(s) {sorted(placed)}")
    # each task on its slice's device, as the per-task executor places it
    lows = exe.lowerings(impl)
    env = dict(ins)
    for tid in exe.schedule.order:
        lw = lows[tid]
        dev = devices[lw.slice_id % len(devices)]
        env[lw.out_array] = lw.fn(*[jax.device_put(env[a], dev)
                                    for a in lw.in_arrays])
        log("four", f"task {lw.name} ({lw.out_array}) slice {lw.slice_id}: "
            f"on devices {sorted(d.id for d in env[lw.out_array].devices())}")
    check(len({next(iter(env[lows[t].out_array].devices())).id
               for t in lows}) >= 2, "task outputs all on one device")
    check(allclose(np.asarray(env["G"]), ref["G"], rtol=ORACLE_RTOL),
          "placed tasks differ from the oracle")


# ---------------------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip phase (and no other)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, interpret-mode kernels, any backend; "
                         "prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"[device] no TPU found: JAX's first device is "
              f"{dev.platform} ({dev.device_kind}); this check needs a TPU",
              flush=True)
        return 2
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.codegen import enable_compile_cache
        from repro.core import solver
    except ImportError as e:
        print(f"[device] the repro package is not next to this script "
              f"({e})", flush=True)
        return 2
    log("device", f"compile cache at {enable_compile_cache()}")
    impl = "pallas_interpret" if args.rehearse else "pallas"
    log("device", f"kernels run as {impl!r}; solver sweep workers import "
        f"jax: {solver.sweep_workers_import_jax()}")

    phases = [("four", phase_four_chips)] if args.four_chips else \
        [("model", phase_model), ("plan", phase_plan)]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(args, impl)
        except Exception:
            failed.append(name)
            log(name, "FAILED\n" + traceback.format_exc())
        log(name, f"phase wall {time.perf_counter() - t0:.3f}s")
    if failed:
        print(f"failed phases: {failed}", flush=True)
        return 1
    if args.rehearse:
        print("rehearsal passed (no result line: not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
