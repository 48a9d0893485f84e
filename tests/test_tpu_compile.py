"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers a kernel against a described (not attached)
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode accepts — blocks that break the (8, 128) rule, more
VMEM than the scoped limit, a dot precision Mosaic lacks.  Widths are
qwen3-0.6b's (FFN, attention, decode attention and the whole decode step
at the decode and prefill cells' batch and cache), 3mm at
``polybench.TPU_SCALE`` (f32), recurrentgemma-9b's RG-LRU width and
rwkv6-1.6b's heads.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
this file.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import frontend
from repro.codegen import compiled_program
from repro.configs import get_config
from repro.core import SolverOptions, polybench, solve
from repro.kernels import kernel_impl
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import kernel as flash_kernel
from repro.kernels.rglru import ops as rglru_ops
from repro.kernels.rwkv6 import ops as rwkv6_ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _ffn(x, w1, w3, w2):
    a = x @ w1
    g = x @ w3
    h = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * g
    return h @ w2


def _served_program(name: str, one_chip):
    """The program ``PlanEngine`` serves for a SwiGLU FFN at qwen3-0.6b
    widths, bf16 (512 tokens x 1024 -> 3072 -> 1024), or for 3mm at
    ``TPU_SCALE``, f32, each segment lowered for the described chip."""
    if name == "ffn":
        t, d, f = 512, 1024, 3072
        args = tuple(jnp.zeros(s, jnp.bfloat16)
                     for s in ((t, d), (d, f), (d, f), (f, d)))
        tf = frontend.trace(_ffn, *args)
        graph, inputs = tf.graph, tf.bind_args(args)
    else:
        graph = polybench.build(name, scale=polybench.TPU_SCALE)
        inputs = {a: jax.ShapeDtypeStruct(graph.arrays[a].shape, jnp.float32)
                  for a in graph.external_inputs()}
    plan = solve(graph, None, SolverOptions(time_budget_s=20.0, workers=1),
                 store=None)
    prog = compiled_program(graph, plan, "pallas")
    return prog, prog.lower(inputs, sharding=one_chip)


@pytest.mark.parametrize("name", ["ffn", "3mm"])
def test_plan_contraction_kernels_compile(one_chip, name):
    prog, lowered = _served_program(name, one_chip)
    assert prog.unit_kinds()["contraction"] == 3
    text = "\n".join(low.compile().as_text() for low in lowered)
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"


@pytest.mark.parametrize("seq", [128, 2048])
def test_flash_attention_compiles_at_qwen3_prefill(one_chip, seq):
    b, hq, hkv, hd = 4, 16, 8, 128
    bf = jnp.bfloat16
    _compile(lambda q, k, v: flash_kernel.flash_attention(q, k, v),
             one_chip, ((b * hq, seq, hd), bf), ((b * hkv, seq, hd), bf),
             ((b * hkv, seq, hd), bf))


@pytest.mark.parametrize("b,sc", [(32, 768), (8, 4224)],
                         ids=["decode", "prefill"])
def test_decode_attention_compiles_at_qwen3_cells(one_chip, b, sc):
    """The decode attention kernel at the qwen3-0.6b cells' batch and
    cache: 28 stacked layers, 8 KV heads of 128, groups of 2, bf16."""
    layers, hkv, g, d = 28, 8, 2, 128
    bf, i32 = jnp.bfloat16, jnp.int32
    cache = ((layers, b, sc, hkv * d), bf)
    _compile(lambda q, kc, vc, kn, vn, at, length, slot:
             decode_ops.decode_attention(q, kc, vc, at, length, slot, kn, vn,
                                         impl="pallas"),
             one_chip, ((b, 1, hkv * g, d), bf), cache, cache,
             ((b, 1, hkv * d), bf), ((b, 1, hkv * d), bf), ((), i32),
             ((), i32), ((), i32))


@pytest.mark.parametrize("b,sc,kernel", [(32, 768, True), (8, 2051, False)],
                         ids=["decode", "odd-cache"])
def test_decode_step_updates_cache_in_place_on_v5e(one_chip, b, sc, kernel):
    """The whole decode step of qwen3-0.6b, compiled by the chip's compiler
    as ``serve.Engine`` jits it: every cache leaf aliases an output, and
    nothing but the parameter, the loop's tuple elements and the one-row
    dynamic-update-slice (alone or fused) has the stacked cache's shape —
    no copy, transpose or other fusion rewrites it.  At the decode cell's shapes (batch
    32, cache 768) the kernel reads the cache; a cache of 2051 positions
    splits into no tiled block, so the XLA path reads it, and the step
    still compiles."""
    from repro.models import model as M
    cfg = get_config("qwen3-0.6b")

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda key: M.init_params(cfg, key, jnp.bfloat16),
        jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(lambda: M.init_cache(cfg, b, sc)))
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)

    def decode_step(params, cache, tokens):
        return M.decode_step(params, cfg, cache, tokens)

    with kernel_impl("pallas"):
        text = jax.jit(decode_step, donate_argnames=("cache",)).lower(
            params, cache, tokens).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel, "Mosaic kernel or not"
    aliased = {int(n) for n in re.findall(
        r"\{\d*\}: \((\d+), \{\}", text.split("entry_computation_layout")[0])}
    first = len(jax.tree.leaves(params))
    assert aliased == set(range(first, first + len(jax.tree.leaves(cache))))
    shape = "bf16[%s]" % ",".join(map(str, cache["layers"][0]["k"].shape))
    # a fusion XLA names after the dynamic-update-slice it wraps is that
    # update, done in place
    ops = {"dynamic-update-slice" if "dynamic-update-slice" in name else op
           for name, op in re.findall(
               r"%%([\w.-]+) = %s\{[^}]*\} ([\w-]+)\(" % re.escape(shape),
               text)}
    assert ops <= {"parameter", "get-tuple-element",
                   "dynamic-update-slice"}, ops


def test_rglru_compiles_at_d4096(one_chip):
    shape = (2, 512, 4096)
    _compile(lambda a, u: rglru_ops.rglru(a, u, impl="pallas"), one_chip,
             (shape, jnp.float32), (shape, jnp.float32))


def test_rwkv6_compiles_at_32_heads_of_64(one_chip):
    bh, s, dk = 2 * 32, 256, 64
    f = jnp.float32
    _compile(lambda r, k, v, w, u: rwkv6_ops.rwkv6(r, k, v, w, u,
                                                   impl="pallas"),
             one_chip, ((bh, s, dk), f), ((bh, s, dk), f), ((bh, s, dk), f),
             ((bh, s, dk), f), ((bh, dk), f))
