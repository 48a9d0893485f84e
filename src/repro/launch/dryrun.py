import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: ``.lower().compile()`` every (arch x shape x mesh).

The two lines above run before ANY other import (jax locks the device
count on first init) — 512 placeholder CPU devices stand in for the
production meshes: single-pod 16x16 = 256 chips, multi-pod 2x16x16 = 512.

Per cell this script:
  1. builds abstract params/opt-state/caches via jax.eval_shape (no
     allocation anywhere),
  2. jits the train_step / prefill_step / decode_step with the production
     shardings and lowers + compiles it,
  3. records memory_analysis(), cost_analysis(), the HLO collective
     traffic (launch.hlo_parse) and the three roofline terms to JSON for
     EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_config, list_archs
from repro.distributed import sharding as sh
from repro.launch import hlo_parse
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import RooflineReport, model_flops
from repro.models import model as M
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import make_train_step

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# long_500k needs sub-quadratic attention / bounded state (DESIGN.md §5).
LONG_OK = {"recurrentgemma-9b", "rwkv6-1.6b", "mixtral-8x7b"}


def cells():
    for arch in list_archs():
        for shape in SHAPES:
            if shape == "long_500k" and arch not in LONG_OK:
                continue
            yield arch, shape


def input_specs(cfg, shape: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    spec = SHAPES[shape]
    b, s = spec["batch"], spec["seq"]
    if cfg.embed_input:
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:
        tokens = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.float32)
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return {"tokens": tokens, "labels": labels, "batch": b, "seq": s,
            "kind": spec["kind"]}


def abstract_params(cfg):
    return jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def lower_cell(cfg, shape: str, mesh, attn_impl: str | None = None,
               extra_cfg: dict | None = None, microbatches: int = 1):
    """Returns (lowered, aux) for one cell."""
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    ins = input_specs(cfg, shape)
    b, s, kind = ins["batch"], ins["seq"], ins["kind"]
    params = abstract_params(cfg)
    p_shard = sh.shard_params(mesh, params)

    if kind == "train":
        opt_cfg = AdamWConfig()
        lowered_fn, _ = make_train_step(mesh, cfg, opt_cfg, params, b, s,
                                        microbatches=microbatches)
        opt = jax.eval_shape(init_opt_state, params)
        lowered = lowered_fn.lower(params, opt, ins["tokens"],
                                   ins["labels"])
        n_tokens = b * s
    elif kind == "prefill":
        t_shard = sh.tokens_sharding(
            mesh, b, extra_dims=(1 if cfg.embed_input else 2))

        def prefill_step(p, t):
            return M.prefill(p, cfg, t, max_len=s)

        fn = jax.jit(prefill_step, in_shardings=(p_shard, t_shard))
        lowered = fn.lower(params, ins["tokens"])
        n_tokens = b * s
    elif kind == "decode":
        cache = jax.eval_shape(
            functools.partial(M.init_cache, cfg, b, s))
        c_shard = sh.shard_cache(mesh, cache, b, cfg.kv_heads)
        if cfg.embed_input:
            tok = jax.ShapeDtypeStruct((b,), jnp.int32)
            t_sh = NamedSharding(mesh, sh.batch_spec(mesh, b))
        else:
            tok = jax.ShapeDtypeStruct((b, cfg.d_model), jnp.float32)
            t_sh = sh.tokens_sharding(mesh, b, extra_dims=1)

        def serve_step(p, c, t):
            return M.decode_step(p, cfg, c, t)

        fn = jax.jit(serve_step,
                     in_shardings=(p_shard, c_shard, t_sh),
                     donate_argnums=(1,))
        lowered = fn.lower(params, cache, tok)
        n_tokens = b
    else:
        raise ValueError(kind)
    return lowered, {"n_tokens": n_tokens, "kind": kind, "cfg": cfg,
                     "microbatches": microbatches}


def _cost_analysis(compiled) -> dict:
    """Normalize ``cost_analysis()`` across jax versions: newer releases
    return one dict, older ones a list with one dict per partition."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def _measure(compiled) -> dict[str, float]:
    """flops / bytes / collective bytes of one compiled executable."""
    cost = _cost_analysis(compiled)
    coll = hlo_parse.total_collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll)}


def calibrated_cost(cfg, shape: str, mesh) -> dict[str, float]:
    """Scan-corrected per-chip cost terms (dry-run fidelity).

    ``HloCostAnalysis`` counts a while-loop body ONCE, so the layer-group
    scan, the loss-chunk scan and the attention chunk maps all undercount.
    Fix: compile two *python-unrolled* shallow variants — 1 group and 2
    groups of the layer pattern (loss in one chunk; attention maps
    unrolled) — whose HLO counts are exact.  Cost is affine in group count
    (groups are structurally identical), so

        F(n_groups) = F(1g) + (n_groups - 1) * (F(2g) - F(1g))

    is exact for the scan part; the tail (n_layers % pattern) is covered
    by the fractional group count.  Sequence-step recurrences (rglru /
    rwkv6 lax.scan over time) remain counted once — their per-step work is
    O(d) vs the layer's O(d^2) matmuls (<1%), noted in EXPERIMENTS.md.

    Validated against a full python-unroll on archs small enough to
    compile (tests/test_dryrun.py)."""
    n_pat = len(cfg.pattern)
    groups_eff = cfg.n_layers / n_pat

    def unrolled(n_groups: int) -> dict[str, float]:
        cal_cfg = dataclasses.replace(
            cfg, n_layers=n_groups * n_pat, unroll_layers=True,
            loss_chunk=1 << 30)
        lowered, _ = lower_cell(cal_cfg, shape, mesh)
        return _measure(lowered.compile())

    f1 = unrolled(1)
    f2 = unrolled(2)
    # Per-group deltas are non-negative by construction; tiny cells can
    # measure f2 < f1 on the 'bytes' proxy (XLA fuses the two programs
    # differently) — clamp so extrapolation never goes below the
    # 1-group measurement.
    return {k: f1[k] + (groups_eff - 1.0) * max(f2[k] - f1[k], 0.0)
            for k in f1}


def analyze(lowered, compiled, *, arch: str, shape: str, mesh_name: str,
            n_chips: int, cfg, n_tokens: float, kind: str,
            corrected: dict[str, float] | None = None) -> dict:
    cost = _cost_analysis(compiled)
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes") if hasattr(mem, k)}
    except Exception:   # noqa: BLE001 — backend-dependent
        mem_d = {}
    hlo = compiled.as_text()
    coll = hlo_parse.collective_summary(hlo)
    use = corrected or {"flops": flops, "bytes": bytes_accessed,
                        "coll_bytes": float(coll["total_bytes"])}
    rep = RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, n_chips=n_chips,
        flops_per_chip=use["flops"],
        hbm_bytes_per_chip=use["bytes"],
        coll_bytes_per_chip=use["coll_bytes"],
        model_flops_total=model_flops(cfg, n_tokens, kind))
    return {**rep.to_dict(), "memory_analysis": mem_d,
            "collectives": coll, "cost_analysis_keys": sorted(cost),
            "raw_scanned": {"flops": flops, "bytes": bytes_accessed,
                            "coll_bytes": float(coll["total_bytes"])},
            "scan_corrected": corrected is not None}


HBM_BUDGET = 16 * 2 ** 30       # v5e HBM per chip


def _hbm_use(compiled, kind: str = "") -> float:
    """Per-chip HBM estimate from memory_analysis.

    For decode cells the donated KV cache updates in place on TPU
    (dynamic-update-slice aliases the donated buffer); the CPU backend
    does not implement while-loop/donation aliasing and materialises one
    extra cache copy in temp (verified: scan vs unrolled both carry it;
    tests/test_distributed).  Subtract that phantom copy — bounded by the
    alias (donated) size — from the decode temp estimate."""
    try:
        mem = compiled.memory_analysis()
        args = float(mem.argument_size_in_bytes)
        temp = float(mem.temp_size_in_bytes)
        out = float(mem.output_size_in_bytes)
        alias = float(mem.alias_size_in_bytes)
        if kind == "decode":
            temp = max(temp - alias, 0.0)
        return args + temp + out - alias
    except Exception:   # noqa: BLE001
        return 0.0


def regeneration_ladder(kind: str):
    """Paper §5.7 automated: when a design does not fit, re-solve with
    tightened constraints.  Each rung is (label, extra_cfg_patch,
    microbatches).  Rungs compose left-to-right."""
    if kind == "train":
        return [("mb4", {}, 4), ("mb16", {}, 16),
                ("mb16+chunked", {"attn_impl": "chunked"}, 16),
                ("mb16+chunked256", {"attn_impl": "chunked",
                                     "attn_chunk": 256}, 16)]
    if kind == "prefill":
        return [("chunked", {"attn_impl": "chunked"}, 1),
                ("chunked256", {"attn_impl": "chunked",
                                "attn_chunk": 256}, 1)]
    # decode: int8 KV halves the cache; blocked reads shrink the
    # dequantisation temp from the whole cache to one block
    return [("kv_int8", {"kv_cache_dtype": "int8"}, 1),
            ("kv_int8+blocked", {"kv_cache_dtype": "int8",
                                 "decode_chunk": 2048}, 1)]


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str,
             attn_impl: str | None = None,
             extra_cfg: dict | None = None, tag: str = "",
             calibrate: bool = True,
             shard_override: dict | None = None,
             auto_regenerate: bool = True) -> dict:
    sh.set_overrides(shard_override or {})
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_chips = mesh.devices.size
    t0 = time.time()
    lowered, aux = lower_cell(cfg, shape, mesh, attn_impl, extra_cfg)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    # ----- §5.7 design regeneration: tighten until the design fits -----
    regenerations: list[dict] = []
    hbm = _hbm_use(compiled, aux["kind"])
    if auto_regenerate and hbm > HBM_BUDGET:
        base_extra = dict(extra_cfg or {})
        best = (hbm, lowered, compiled, aux, extra_cfg)
        for label, patch, mb in regeneration_ladder(aux["kind"]):
            trial_extra = {**base_extra, **patch}
            lowered, aux = lower_cell(cfg, shape, mesh, attn_impl,
                                      trial_extra, microbatches=mb)
            compiled = lowered.compile()
            new_hbm = _hbm_use(compiled, aux["kind"])
            regenerations.append(
                {"rung": label, "hbm_gib": new_hbm / 2 ** 30,
                 "fits": bool(new_hbm <= HBM_BUDGET)})
            if new_hbm < best[0]:
                best = (new_hbm, lowered, compiled, aux, trial_extra)
            if new_hbm <= HBM_BUDGET:
                break
        # keep the best rung seen (a later rung may regress)
        hbm, lowered, compiled, aux, extra_cfg = best

    corrected = calibrated_cost(aux["cfg"], shape, mesh) if calibrate \
        else None
    result = analyze(lowered, compiled, arch=arch, shape=shape,
                     mesh_name=mesh_name, n_chips=n_chips, cfg=aux["cfg"],
                     n_tokens=aux["n_tokens"], kind=aux["kind"],
                     corrected=corrected)
    result.update({"lower_s": t_lower, "compile_s": t_compile,
                   "status": "ok", "tag": tag,
                   "extra_cfg": extra_cfg or {},
                   "shard_override": shard_override or {},
                   "microbatches": aux.get("microbatches", 1),
                   "hbm_gib": hbm / 2 ** 30,
                   "fits_hbm": bool(hbm <= HBM_BUDGET),
                   "regenerations": regenerations})
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}_{shape}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--extra-cfg", default=None,
                    help="JSON dict of ModelConfig overrides")
    ap.add_argument("--shard-override", default=None,
                    help='JSON dict of sharding-rule overrides, e.g. '
                         '{"lm_head$": [null, "model"]}')
    args = ap.parse_args()

    extra = json.loads(args.extra_cfg) if args.extra_cfg else None
    shard_ov = json.loads(args.shard_override) if args.shard_override \
        else None
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = list(cells()) if args.all else [(args.arch, args.shape)]
    failures = 0
    for arch, shape in todo:
        for mesh_name in meshes:
            try:
                # cost calibration on the single-pod mesh only (the
                # roofline table is single-pod; multi checks feasibility)
                r = run_cell(arch, shape, mesh_name, args.out,
                             args.attn_impl, extra, args.tag,
                             calibrate=(mesh_name == "single"),
                             shard_override=shard_ov)
                print(f"OK   {arch:24s} {shape:12s} {mesh_name:6s} "
                      f"bound={r['bound']:10s} "
                      f"t=({r['t_compute_s']:.2e},{r['t_memory_s']:.2e},"
                      f"{r['t_collective_s']:.2e})s "
                      f"useful={r['useful_ratio']:.2f} "
                      f"roofline={r['roofline_fraction']:.3f} "
                      f"hbm={r['hbm_gib']:.1f}G"
                      f"{'' if r['fits_hbm'] else '(!)'} "
                      f"regen={len(r['regenerations'])} "
                      f"compile={r['compile_s']:.0f}s", flush=True)
            except Exception as exc:    # noqa: BLE001
                failures += 1
                print(f"FAIL {arch} {shape} {mesh_name}: {exc}",
                      flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
