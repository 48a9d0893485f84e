"""Multi-device tests (subprocess with fake CPU devices): sharding specs,
pipeline parallelism, gradient compression, and the sharded train step
compiled at published widths."""
from __future__ import annotations

import textwrap

import pytest

from conftest import run_subprocess


def _run(code: str, n_devices: int = 8, timeout: int = 560):
    r = run_subprocess(textwrap.dedent(code), n_devices, timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a (2,4) mesh computes the same loss and
    parameter update as an unsharded run — sharding is semantics-free."""
    out = _run("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.base import smoke
        from repro.models import model as M
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.train.train_step import make_train_step, train_step
        cfg = dataclasses.replace(smoke(get_config('qwen1.5-0.5b')),
                                  n_layers=2, remat=False,
                                  compute_dtype='float32')
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        opt_cfg = AdamWConfig(warmup_steps=1, total_steps=10)
        fn, _ = make_train_step(mesh, cfg, opt_cfg, shapes, 8, 32)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
        labels = jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)
        p2, o2, m2 = fn(params, opt, toks, labels)
        # reference: plain single-device step
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        p1, o1, m1 = train_step(params, init_opt_state(params), toks, labels,
                                cfg=cfg, opt_cfg=opt_cfg)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-4, \\
            (float(m1['loss']), float(m2['loss']))
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 2e-4, d
        print('OK sharded==single')
    """)
    assert "OK sharded==single" in out


def test_all_archs_shard_on_test_mesh():
    """Every arch's full-size param tree gets a valid NamedSharding on a
    (2,4) mesh (abstract — eval_shape only, no allocation)."""
    out = _run("""
        import functools, jax
        from repro.configs import get_config, list_archs
        from repro.distributed import sharding as sh
        from repro.models import model as M
        mesh = jax.make_mesh((2, 4), ('data', 'model'))
        for arch in list_archs():
            cfg = get_config(arch)
            params = jax.eval_shape(
                functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))
            specs = sh.shard_params(mesh, params)
            flat_p = jax.tree.leaves(params)
            flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, 'spec'))
            assert len(flat_p) == len(flat_s)
            for p, s in zip(flat_p, flat_s):
                # every sharded dim must divide
                for dim, axes in zip(p.shape, s.spec):
                    if axes is None: continue
                    size = sh.axis_size(mesh, axes)
                    assert dim % size == 0, (arch, p.shape, s.spec)
        print('OK all archs shard')
    """)
    assert "OK all archs shard" in out


def test_pipeline_parallel_equals_sequential():
    """GPipe shard_map pipeline over 4 stages == sequential layer stack."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply
        n_stages, m, mb, d = 4, 8, 2, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (n_stages, d, d)) / d ** 0.5
        def stage_fn(p, x):
            return jnp.tanh(x @ p['w'])
        mesh = jax.make_mesh((4,), ('stage',))
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
        out = pipeline_apply(stage_fn, mesh, 'stage', {'w': ws}, x)
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print('OK pipeline==sequential')
    """, n_devices=4)
    assert "OK pipeline==sequential" in out


def test_gradient_compression_roundtrip():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression as C
        mesh = jax.make_mesh((4,), ('dp',))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32))
        def run(fn):
            return jax.shard_map(fn, mesh=mesh, in_specs=P('dp'),
                                 out_specs=P(), check_vma=False)(g)
        mean_ref = np.asarray(jnp.mean(g, 0))
        out32 = run(lambda x: C.allreduce_mean({'g': x[0]}, 'dp')['g'])
        # psum may associate the 4-way sum differently than jnp.mean
        np.testing.assert_allclose(np.asarray(out32), mean_ref,
                                   rtol=1e-6, atol=1e-6)
        out16 = run(lambda x: C.allreduce_mean_bf16({'g': x[0]}, 'dp')['g'])
        assert np.abs(np.asarray(out16) - mean_ref).max() < 0.02
        def int8_fn(x):
            e = C.zeros_like_errors({'g': x[0]})
            m, e2 = C.allreduce_mean_int8_ef({'g': x[0]}, e, 'dp')
            return m['g']
        out8 = run(int8_fn)
        assert np.abs(np.asarray(out8) - mean_ref).max() < 0.05
        # wire accounting
        assert C.compressed_bytes({'g': g[0]}, 'int8') < \\
            C.compressed_bytes({'g': g[0]}, 'fp32') // 3
        print('OK compression')
    """, n_devices=4)
    assert "OK compression" in out


def test_error_feedback_reduces_bias():
    """With error feedback, repeated compressed reductions of a CONSTANT
    gradient converge to the true mean (bias telescopes)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import compression as C
        mesh = jax.make_mesh((4,), ('dp',))
        g = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 8)) * \\
            jnp.logspace(-3, 0, 8)[None, None, :]   # ill-scaled rows
        mean_ref = np.asarray(jnp.mean(g, 0))
        def run(x):
            def fn(xs):
                e = C.zeros_like_errors({'g': xs[0]})
                acc = jnp.zeros_like(xs[0])
                for _ in range(8):
                    m, e = C.allreduce_mean_int8_ef({'g': xs[0]}, e, 'dp')
                    acc = acc + m['g']
                return acc / 8
            return jax.shard_map(fn, mesh=mesh, in_specs=P('dp'),
                                 out_specs=P(), check_vma=False)(x)
        avg8 = np.asarray(run(g))
        one = np.asarray(run(g))  # deterministic
        err_avg = np.abs(avg8 - mean_ref).max()
        assert err_avg < 0.02, err_avg
        print('OK error feedback')
    """, n_devices=4)
    assert "OK error feedback" in out


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "rwkv6-1.6b",
                                  "recurrentgemma-9b"])
def test_sharded_train_step_compiles(arch):
    """The train step the trainer runs, at published widths cut to a few
    layers and a short vocabulary, lowers from ShapeDtypeStructs and
    compiles on a (2, 4) ``data`` x ``model`` mesh of ``Auto`` axes, as
    the training cell builds its mesh, with its weights sharded."""
    out = _run(f"""
        import dataclasses, functools, re, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import model as M
        from repro.train.optimizer import AdamWConfig, init_opt_state
        from repro.train.train_step import make_train_step
        arch = {arch!r}
        cfg = dataclasses.replace(
            get_config(arch), vocab=4096, attn_chunk=128,
            n_layers=6 if arch == 'recurrentgemma-9b' else 4)
        b, s = 8, 256
        mesh = make_mesh((2, 4), ('data', 'model'))
        params = jax.eval_shape(functools.partial(M.init_params, cfg),
                                jax.random.PRNGKey(0))
        opt = jax.eval_shape(init_opt_state, params)
        fn, _ = make_train_step(mesh, cfg, AdamWConfig(), params, b, s)
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
        text = fn.lower(params, opt, toks, toks).compile().as_text()
        assert re.search(r"all-gather|reduce-scatter|all-reduce", text)
        print('OK compiled', arch)
    """, n_devices=8)
    assert f"OK compiled {arch}" in out
