"""Tests for the §Perf / feasibility features added beyond the baseline:
microbatched gradient accumulation, fp32-master mixed precision, blocked
decode attention, attention score-dtype / grouped-GQA levers, and chunked
rwkv6 backward memory."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import smoke
from repro.models import model as M
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import train_step


def _setup(arch="qwen1.5-0.5b", **over):
    cfg = dataclasses.replace(smoke(get_config(arch)), n_layers=2,
                              remat=False, **over)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0,
                                cfg.vocab)
    return cfg, params, toks, labels


# ---------------------------------------------------------------------------
# microbatching
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [2, 4, 8])
def test_microbatched_step_matches_full_batch(k):
    """Gradient accumulation is the same optimizer step (fp32 accum)."""
    cfg, params, toks, labels = _setup(compute_dtype="float32")
    oc = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10,
                     min_lr_frac=1.0)
    p1, o1, m1 = train_step(params, init_opt_state(params), toks, labels,
                            cfg=cfg, opt_cfg=oc, microbatches=1)
    p2, o2, m2 = train_step(params, init_opt_state(params), toks, labels,
                            cfg=cfg, opt_cfg=oc, microbatches=k)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-5)


def test_microbatches_must_divide_batch():
    cfg, params, toks, labels = _setup()
    with pytest.raises(AssertionError):
        train_step(params, init_opt_state(params), toks, labels,
                   cfg=cfg, opt_cfg=AdamWConfig(), microbatches=3)


# ---------------------------------------------------------------------------
# fp32 master weights (bf16 params)
# ---------------------------------------------------------------------------
def test_bf16_params_track_fp32_training():
    losses = {}
    for pd in ("float32", "bfloat16"):
        cfg, params, toks, labels = _setup(param_dtype=pd)
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32), params)   # same init values
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16 if pd == "bfloat16"
                               else jnp.float32), params)
        opt = init_opt_state(params)
        if pd == "bfloat16":
            assert opt.master is not None            # fp32 master exists
            for mw in jax.tree.leaves(opt.master):
                assert mw.dtype == jnp.float32
        else:
            assert opt.master is None
        oc = AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=10)
        ls = []
        for _ in range(5):
            params, opt, m = train_step(params, opt, toks, labels,
                                        cfg=cfg, opt_cfg=oc)
            ls.append(float(m["loss"]))
        losses[pd] = ls
    # trajectories amplify rounding; require tracking, not equality
    for a, b in zip(losses["float32"], losses["bfloat16"]):
        assert a == pytest.approx(b, rel=2e-2)


def test_master_keeps_precision_at_tiny_lr():
    """Without a master, bf16 weights swallow tiny updates; the master
    accumulates them."""
    p = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    opt = init_opt_state(p)
    oc = AdamWConfig(lr=1e-5, warmup_steps=0, weight_decay=0.0,
                     min_lr_frac=1.0, grad_clip=1e9)
    g = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    from repro.train.optimizer import adamw_update
    master0 = float(opt.master["w"][0, 0])
    for _ in range(3):
        p, opt, _ = adamw_update(oc, p, g, opt)
    assert float(opt.master["w"][0, 0]) < master0   # master moved
    # and the running master is consistent with the bf16 projection
    assert float(p["w"][0, 0]) == pytest.approx(
        float(opt.master["w"][0, 0]), abs=0.01)


# ---------------------------------------------------------------------------
# blocked decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_blocked_decode_matches_unblocked(kv_dtype):
    cfg0 = dataclasses.replace(smoke(get_config("yi-34b")),
                               compute_dtype="float32",
                               kv_cache_dtype=kv_dtype)
    cfg1 = dataclasses.replace(cfg0, decode_chunk=8)
    params = M.init_params(cfg0, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 13), 0,
                              cfg0.vocab)
    _, cache = M.prefill(params, cfg0, toks[:, :12], max_len=32)
    l0, _ = M.decode_step(params, cfg0, cache, toks[:, 12])
    l1, _ = M.decode_step(params, cfg1, cache, toks[:, 12])
    tol = 1e-5 if kv_dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# attention levers
# ---------------------------------------------------------------------------
def test_model_level_levers_preserve_function():
    """score bf16 / grouped GQA / bf16 FFN activations change numerics
    within bf16 tolerance only."""
    base = dataclasses.replace(smoke(get_config("yi-34b")),
                               compute_dtype="float32")
    params = M.init_params(base, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0,
                              base.vocab)
    h0 = M.forward(params, base, toks)
    for over in ({"gqa_grouped": True}, {"ffn_act_f32": False},
                 {"attn_score_dtype": "bfloat16"}):
        cfg = dataclasses.replace(base, **over)
        h1 = M.forward(params, cfg, toks)
        err = float(jnp.abs(h1 - h0).max())
        tol = 1e-5 if over.get("gqa_grouped") else 0.15
        assert err < tol, (over, err)


# ---------------------------------------------------------------------------
# rwkv6 chunked-checkpoint backward memory
# ---------------------------------------------------------------------------
def test_rwkv6_chunked_grad_correct():
    from repro.kernels.rwkv6 import ref
    bh, s, dk, dv = 2, 64, 8, 8
    r = jax.random.normal(jax.random.PRNGKey(0), (bh, s, dk)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, s, dk)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, s, dv)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3),
                                         (bh, s, dk)))
    u = jax.random.normal(jax.random.PRNGKey(4), (bh, dk)) * 0.5

    def loss(chunk):
        return jnp.sum(ref.rwkv6(r, k, v, w, u, chunk=chunk) ** 2)

    g16 = jax.grad(lambda x: jnp.sum(
        ref.rwkv6(x, k, v, w, u, chunk=16) ** 2))(r)
    g64 = jax.grad(lambda x: jnp.sum(
        ref.rwkv6(x, k, v, w, u, chunk=64) ** 2))(r)
    np.testing.assert_allclose(np.asarray(g16), np.asarray(g64),
                               rtol=1e-4, atol=1e-4)
    assert loss(16) == pytest.approx(loss(64), rel=1e-5)
