"""Serving engine: batched generation, greedy consistency, throughput."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import smoke
from repro.models import model as M
from repro.serve.engine import Engine, ServeConfig, throughput_stats


@pytest.fixture(scope="module")
def engine():
    cfg = dataclasses.replace(smoke(get_config("qwen3-0.6b")),
                              compute_dtype="float32",
                              kv_cache_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(max_len=64))


def test_generate_shapes_and_determinism(engine):
    prompts = np.array([[1, 2, 3, 4], [5, 6, 7, 8]], np.int32)
    a = engine.generate(prompts, max_new_tokens=8)
    b = engine.generate(prompts, max_new_tokens=8)
    assert a.shape == (2, 8)
    np.testing.assert_array_equal(a, b)     # greedy is deterministic


def test_engine_programs_have_stable_names(engine):
    """The jitted steps lower to modules named after them, which is how
    the profiler trace names their device executions."""
    cfg, params = engine.cfg, engine.params
    tokens = jax.ShapeDtypeStruct((2, 4), jax.numpy.int32)
    prefill = engine._prefill.lower(params=params, tokens=tokens,
                                    max_len=64)
    assert prefill.as_text().startswith("module @jit_prefill ")
    _, cache = jax.eval_shape(
        lambda p, t: M.prefill(p, cfg, t, max_len=64), params, tokens)
    decode = engine._decode.lower(
        params=params, cache=cache,
        tokens=jax.ShapeDtypeStruct((2,), jax.numpy.int32))
    assert decode.as_text().startswith("module @jit_decode_step ")


def test_decode_step_updates_cache_in_place(engine):
    """The step program donates every cache leaf (each aliases an output),
    repeats no KV head and builds no zero-filled stacked cache: nothing
    cache-sized is broadcast.  Two generates in a row on the donated
    cache give the same tokens."""
    import re
    cfg, params = engine.cfg, engine.params
    tokens = jax.ShapeDtypeStruct((2, 4), jax.numpy.int32)
    _, cache = jax.eval_shape(
        lambda p, t: M.prefill(p, cfg, t, max_len=64), params, tokens)
    text = engine._decode.lower(
        params=params, cache=cache,
        tokens=jax.ShapeDtypeStruct((2,), jax.numpy.int32)).as_text()
    main = next(line for line in text.splitlines()
                if "func.func public @main" in line)
    args = re.findall(r"%arg\d+: tensor<([\dx]*)\w+>( \{[^}]*\})?",
                      main.split(") -> ")[0])
    assert len(args) == len(jax.tree.leaves((params, cache))) + 1
    donated = sorted(shape for shape, attrs in args
                     if "tf.aliasing_output" in attrs)
    assert donated == sorted("".join(f"{n}x" for n in leaf.shape)
                             for leaf in jax.tree.leaves(cache))
    layer = np.prod(cache["layers"][0]["k"].shape[1:])  # one layer's K
    for shape in re.findall(
            r"stablehlo.broadcast_in_dim .*-> tensor<([\dx]+)x\w+>", text):
        assert np.prod([int(n) for n in shape.split("x")]) < layer, shape
    prompts = np.array([[2, 7, 1, 8], [2, 8, 1, 8]], np.int32)
    np.testing.assert_array_equal(engine.generate(prompts, 12),
                                  engine.generate(prompts, 12))


def test_generate_matches_stepwise_decode(engine):
    """The engine's batched loop equals manual prefill + decode steps."""
    cfg, params = engine.cfg, engine.params
    prompts = np.array([[3, 1, 4, 1, 5]], np.int32)
    out = engine.generate(prompts, max_new_tokens=4)
    logits, cache = M.prefill(params, cfg, jax.numpy.asarray(prompts),
                              max_len=64)
    toks = []
    tok = np.argmax(np.asarray(logits), -1).astype(np.int32)
    for _ in range(4):
        toks.append(tok.copy())
        logits, cache = M.decode_step(params, cfg, cache,
                                      jax.numpy.asarray(tok))
        tok = np.argmax(np.asarray(logits), -1).astype(np.int32)
    np.testing.assert_array_equal(out[0], np.stack(toks, -1)[0])


def test_batch_order_invariance(engine):
    """Each slot's continuation is independent of its batch neighbours."""
    p1 = np.array([[1, 2, 3, 4]], np.int32)
    p2 = np.array([[9, 8, 7, 6]], np.int32)
    both = np.concatenate([p1, p2], 0)
    o_both = engine.generate(both, max_new_tokens=6)
    o_1 = engine.generate(p1, max_new_tokens=6)
    o_2 = engine.generate(p2, max_new_tokens=6)
    np.testing.assert_array_equal(o_both[0], o_1[0])
    np.testing.assert_array_equal(o_both[1], o_2[0])


def test_eos_stops_early():
    cfg = dataclasses.replace(smoke(get_config("qwen3-0.6b")),
                              compute_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_len=64, eos_id=0))
    prompts = np.array([[1, 2, 3, 4]], np.int32)
    out = eng.generate(prompts, max_new_tokens=16)
    if (out[0] == 0).any():
        first = int(np.argmax(out[0] == 0))
        assert (out[0, first + 1:] == 0).all()


def test_temperature_sampling_runs():
    cfg = dataclasses.replace(smoke(get_config("qwen3-0.6b")),
                              compute_dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_len=64, temperature=1.0))
    out = eng.generate(np.array([[1, 2, 3, 4]], np.int32),
                       max_new_tokens=8)
    assert out.shape == (1, 8)
    assert (out >= 0).all() and (out < cfg.vocab).all()


def test_throughput_stats():
    s = throughput_stats(1000, 2.0)
    assert s["tokens_per_s"] == 500.0


# ---------------------------------------------------------------------------
# Plan serving: repeated requests hit the whole-plan compiled-program cache
# ---------------------------------------------------------------------------
def test_plan_engine_serves_from_program_cache():
    from repro.codegen import (allclose, cache_stats, clear_program_cache,
                               random_inputs, reference_executor)
    from repro.core import SolverOptions, THREE_SLICE, polybench, solve
    from repro.serve import PlanEngine

    g = polybench.build("2-madd")
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=2.0))
    ins = random_inputs(g, seed=0)
    ref = reference_executor(g)(ins)

    clear_program_cache()
    eng = PlanEngine(impl="xla")
    eng.register("2-madd", g, plan)
    cold = eng.warmup("2-madd", ins)
    assert cold >= 0.0
    assert cache_stats()["misses"] == 1

    out = eng.submit("2-madd", ins)             # steady-state request
    assert all(allclose(out[k], ref[k]) for k in ref)

    # a brand-new engine (new replica) still hits the same compiled program
    eng2 = PlanEngine(impl="xla")
    eng2.register("m", g, plan)
    out2 = eng2.submit("m", ins)
    assert all(allclose(out2[k], ref[k]) for k in ref)
    stats = eng2.stats()
    # exactly one compile ever; the replica's first submit is a cache hit
    # (later submits resolve engine-locally, no fingerprinting per request)
    assert stats["misses"] == 1 and stats["hits"] >= 1
    assert eng.stats()["requests"] == 2


def test_plan_engine_admission_evicts_lru_registration():
    from repro.codegen import clear_program_cache, random_inputs
    from repro.core import SolverOptions, THREE_SLICE, polybench, solve
    from repro.serve import PlanEngine, ServeConfig

    clear_program_cache()
    eng = PlanEngine(impl="xla", sc=ServeConfig(max_plans=2))
    graphs = {}
    for name in ("2-madd", "3-madd"):
        g = polybench.build(name)
        plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=1.0))
        graphs[name] = (g, plan)
        eng.register(name, g, plan)
    assert eng.names() == ["2-madd", "3-madd"]
    # 3-madd becomes most recently used; admitting a third plan evicts
    # the LRU registration (2-madd)
    eng.submit("3-madd", random_inputs(graphs["3-madd"][0], seed=0))
    g, plan = graphs["2-madd"]
    eng.register("copy", g, plan)
    assert eng.names() == ["3-madd", "copy"]


def test_plan_engine_stats_pools_and_hit_rate():
    from repro.codegen import clear_program_cache, random_inputs
    from repro.core import SolverOptions, THREE_SLICE, polybench, solve
    from repro.serve import PlanEngine, ServeConfig

    clear_program_cache()
    g = polybench.build("2-madd")
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=1.0))
    eng = PlanEngine(impl="xla", sc=ServeConfig(pool_size=2))
    eng.register("m", g, plan)
    ins = random_inputs(g, seed=0)
    for _ in range(3):
        eng.submit("m", ins)
    s = eng.stats()
    assert s["requests"] == 3 and s["per_name"] == {"m": 3}
    pool = s["pools"]["m/xla"]
    assert pool["pool_size"] == 2 and pool["calls"] == 3
    assert pool["next"] == 1                    # 3 calls round-robin of 2
    assert 0.0 <= s["hit_rate"] <= 1.0
    assert s["capacity"] >= 1 and "evictions" in s
    # entries detail rides along for dashboards
    assert any(e["pool_size"] == 2 for e in s["entries"].values())


def test_plan_engine_surfaces_trace_cache_stats():
    """stats() exposes the frontend trace cache feeding register_function:
    hits, size, and per-entry coverage of every cached lowering."""
    import jax.numpy as jnp

    from repro import frontend
    from repro.core import SolverOptions
    from repro.serve import PlanEngine

    frontend.clear_trace_cache()
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(8, 6)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(6, 5)).astype(np.float32))
    fn = lambda x, y: x @ y                     # noqa: E731

    eng = PlanEngine(impl="xla")
    eng.register_function("mm", fn, (a, b),
                          solver_opts=SolverOptions(time_budget_s=2.0))
    out = eng.submit("mm", (a, b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=2e-4)

    tc = eng.stats()["trace_cache"]
    assert tc["size"] == 1 and tc["misses"] >= 1
    (entry,) = tc["entries"].values()           # fully covered single dot
    assert entry["n_supported"] == entry["n_eqns"] >= 1
    assert entry["coverage_eqns"] == 1.0
    assert entry["coverage_flops"] == 1.0

    # re-registering the same structure is a trace-cache hit, not a new
    # lowering — replicas share one record
    eng.register_function("mm2", fn, (a, b),
                          solver_opts=SolverOptions(time_budget_s=2.0))
    tc2 = eng.stats()["trace_cache"]
    assert tc2["hits"] > tc["hits"] and tc2["size"] == 1


def test_plan_engine_reasserts_its_pool_contract():
    """Another caller rebuilding the cache entry with a different pool must
    not silently downgrade an engine configured for a larger pool."""
    from repro.codegen import (clear_program_cache, compiled_program,
                               random_inputs)
    from repro.core import SolverOptions, THREE_SLICE, polybench, solve
    from repro.serve import PlanEngine, ServeConfig

    clear_program_cache()
    g = polybench.build("2-madd")
    plan = solve(g, THREE_SLICE, SolverOptions(time_budget_s=1.0))
    eng = PlanEngine(impl="xla", sc=ServeConfig(pool_size=2))
    eng.register("m", g, plan)
    ins = random_inputs(g, seed=0)
    eng.submit("m", ins)
    compiled_program(g, plan, "xla", pool_size=1)   # foreign rebuild
    eng.submit("m", ins)
    assert eng.stats()["pools"]["m/xla"]["pool_size"] == 2


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_generate_counts_kv_blocks_read_and_skipped(impl):
    """``Engine.generate`` adds the closed-form counts of the KV blocks its
    decode steps read and skip to ``repro_decode_kv_blocks_total``: where
    the decode attention kernel serves the cache, a step at position p
    reads the ceil((p + 1) / 128) blocks below its length of a layer's
    Sc / 128; where it does not, every block is read.  Read plus skipped
    is steps x attention layers x blocks either way."""
    from repro.kernels import kernel_impl
    from repro.obs import default_registry
    cfg = dataclasses.replace(smoke(get_config("qwen3-0.6b")),
                              compute_dtype="float32", head_dim=128)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(max_len=256))
    blocks = default_registry().counter("repro_decode_kv_blocks_total",
                                        labelnames=("kind",))
    before = [blocks.labels(k).value for k in ("read", "skipped")]
    prompt, steps, layers, per_layer = 120, 12, cfg.n_layers, 256 // 128
    prompts = np.arange(2 * prompt, dtype=np.int32).reshape(2, -1) \
        % cfg.vocab
    with kernel_impl(impl):
        eng.generate(prompts, steps)
    read, skipped = (blocks.labels(k).value - b
                     for k, b in zip(("read", "skipped"), before))
    # positions 120..131: lengths 121..128 cover one block, 129..132 two
    want = layers * (8 + 4 * 2) if impl == "pallas_interpret" \
        else layers * steps * per_layer
    assert (read, skipped) == (want, layers * steps * per_layer - want)
