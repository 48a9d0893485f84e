"""Per-architecture smoke tests + model-level equivalences.

Assignment deliverable (f): every assigned arch instantiates a REDUCED
config of the same family and runs forward/train steps on CPU asserting
output shapes + no NaNs.  Plus: attention implementation equivalence and
prefill/decode consistency (the serving path computes the same function as
the parallel path).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.base import smoke
from repro.kernels import kernel_impl
from repro.kernels.decode_attention import ops as decode_ops
from repro.models import attention as attn_mod
from repro.models import model as M
from repro.models import rglru_block as rg_mod
from repro.models import rwkv6_block as rwkv_mod
from repro.models.common import apply_rope, rms_norm, rope_angles
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import train_step

ARCHS = list_archs()
# archs with attention layers: where the decode attention kernel can serve
# the KV cache (under ``pallas_interpret``, with heads 128 wide)
ATTN_ARCHS = [a for a in ARCHS
              if {"attn", "swa"} & set(get_config(a).pattern)]
KERNEL = "pallas_interpret"


def _under(impl, cfg):
    """The config and the kernel scope of a case: ``impl`` None keeps the
    default implementation and the smoke widths; ``KERNEL`` widens heads
    to 128 so the decode attention kernel reads bf16 and float32 caches."""
    if impl is None:
        return cfg, contextlib.nullcontext()
    return dataclasses.replace(cfg, head_dim=128), kernel_impl(impl)


def _inputs(cfg, b=2, s=24, seed=0):
    key = jax.random.PRNGKey(seed)
    if cfg.embed_input:
        toks = jax.random.randint(key, (b, s), 0, cfg.vocab)
    else:
        toks = jax.random.normal(key, (b, s, cfg.d_model))
    labels = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, s), 0,
                                cfg.vocab)
    return toks, labels


def test_all_ten_archs_registered():
    assert len(ARCHS) == 10
    expected = {"recurrentgemma-9b", "qwen3-moe-235b-a22b", "mixtral-8x7b",
                "musicgen-medium", "qwen1.5-0.5b", "yi-34b", "qwen1.5-32b",
                "qwen3-0.6b", "rwkv6-1.6b", "internvl2-76b"}
    assert set(ARCHS) == expected


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward(arch):
    cfg = smoke(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks, _ = _inputs(cfg)
    h = M.forward(params, cfg, toks)
    assert h.shape == (2, 24, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = smoke(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    toks, labels = _inputs(cfg)
    new_p, new_o, metrics = train_step(
        params, opt, toks, labels, cfg=cfg,
        opt_cfg=AdamWConfig(warmup_steps=1, total_steps=10))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) > 0
    assert int(new_o.step) == 1
    # parameters actually moved
    delta = sum(float(jnp.sum(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(new_p),
                                jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize(
    "arch,impl", [pytest.param(a, None, id=a) for a in ARCHS]
    + [pytest.param(a, KERNEL, id=f"{a}-{KERNEL}") for a in ATTN_ARCHS])
def test_arch_prefill_decode_consistency(arch, impl):
    """prefill(t[:s]) then decode(t[s]) must equal prefill(t[:s+1]) logits,
    also where the decode attention kernel reads the cache."""
    cfg = smoke(get_config(arch))
    # fp32 end-to-end; capacity=inf so MoE token drops (which legitimately
    # depend on batch composition) don't mask the equivalence being tested
    cfg = dataclasses.replace(cfg, compute_dtype="float32",
                              kv_cache_dtype="float32",
                              capacity_factor=float("inf"))
    cfg, scope = _under(impl, cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 12
    toks, _ = _inputs(cfg, b=b, s=s + 1, seed=7)
    logits_full, _ = M.prefill(params, cfg, toks, max_len=32)
    with scope:
        logits_pre, cache = M.prefill(params, cfg, toks[:, :s], max_len=32)
        logits_dec, _ = M.decode_step(params, cfg, cache, toks[:, s])
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_matches_forward(arch):
    """The prefill's last-position logits are the training forward's: both
    project q/k/v through the same layer code and attend alike."""
    cfg = dataclasses.replace(smoke(get_config(arch)),
                              compute_dtype="float32",
                              kv_cache_dtype="float32",
                              capacity_factor=float("inf"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks, _ = _inputs(cfg, seed=3)
    logits, _ = M.prefill(params, cfg, toks, max_len=32)
    ref = M.logits_fn(params, cfg, M.forward(params, cfg, toks))[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_full_config_exact_dimensions(arch):
    """The registered config matches the published architecture table."""
    cfg = get_config(arch)
    published = {
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "qwen1.5-32b": (64, 5120, 40, 8, 27392, 152064),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151936),
        "rwkv6-1.6b": (24, 2048, 32, 32, 7168, 65536),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
    }[arch]
    L, d, h, kv, ff, v = published
    assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab == v
    assert cfg.d_ff == ff
    if arch != "rwkv6-1.6b":       # attn-free arch: heads are wkv heads
        assert (cfg.n_heads, cfg.n_kv_heads) == (h, kv)


def test_moe_and_window_flags():
    moe = get_config("qwen3-moe-235b-a22b")
    assert moe.n_experts == 128 and moe.moe_top_k == 8
    mix = get_config("mixtral-8x7b")
    assert mix.n_experts == 8 and mix.moe_top_k == 2
    assert mix.window is not None                 # SWA
    qw = get_config("qwen1.5-0.5b")
    assert qw.qkv_bias
    q3 = get_config("qwen3-0.6b")
    assert q3.qk_norm
    rg = get_config("recurrentgemma-9b")
    assert rg.pattern == ("rglru", "rglru", "swa")   # local attn is windowed
    assert rg.window is not None
    assert not get_config("musicgen-medium").embed_input   # stub frontend
    assert not get_config("internvl2-76b").embed_input


# ---------------------------------------------------------------------------
# attention implementation equivalence (the solver's choice axis)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["chunked", "recursive", "pallas"])
def test_attention_impls_match_naive(impl):
    b, s, h, hkv, d = 2, 192, 4, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, s, hkv, d))
    ref = attn_mod.attention(q, k, v, impl="naive")
    if impl == "pallas":
        from repro.kernels import kernel_impl
        with kernel_impl("pallas_interpret"):
            out = attn_mod.attention(q, k, v, impl="pallas")
    else:
        out = attn_mod.attention(q, k, v, impl=impl, chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [16, 48, 500])
def test_windowed_attention_matches_naive(window):
    b, s, h, d = 1, 160, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(5), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(6), (b, s, h, d))
    ref = attn_mod.attention(q, k, v, impl="naive", window=window)
    out = attn_mod.attention(q, k, v, impl="chunked", window=window,
                             chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("with_new", [False, True])
@pytest.mark.parametrize("path", ["plain", "blocks"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_decode_attention_matches_full(group, path, with_new):
    """Grouped decode attention (K/V at their stored heads) equals
    attention against repeated heads, unblocked and blocked, with and
    without the token being decoded merged as its own piece."""
    b, s, hkv, d, sc, chunk = 2, 40, 2, 16, 64, 16
    h = hkv * group
    q = jax.random.normal(jax.random.PRNGKey(10), (b, 1, h, d))
    kc = jax.random.normal(jax.random.PRNGKey(11), (b, sc, hkv, d))
    vc = jax.random.normal(jax.random.PRNGKey(12), (b, sc, hkv, d))
    kn = jax.random.normal(jax.random.PRNGKey(13), (b, 1, hkv, d))
    vn = jax.random.normal(jax.random.PRNGKey(14), (b, 1, hkv, d))
    slot = 17
    new_kv = (kn, vn, slot) if with_new else None
    if path == "plain":
        out = attn_mod.decode_attention(q, kc, vc, length=s, new_kv=new_kv)
    else:
        def read_chunk(i):
            return (jax.lax.dynamic_slice_in_dim(kc, i * chunk, chunk, 1),
                    jax.lax.dynamic_slice_in_dim(vc, i * chunk, chunk, 1))
        out = attn_mod.decode_attention_blocks(q, read_chunk, sc // chunk,
                                               chunk, s, new_kv=new_kv)
    # oracle: the token written into its slot, explicit slicing, repeated
    # heads
    if with_new:
        kc = kc.at[:, slot].set(kn[:, 0])
        vc = vc.at[:, slot].set(vn[:, 0])
    kk = jnp.repeat(kc[:, :s], group, axis=2)
    vv = jnp.repeat(vc[:, :s], group, axis=2)
    logit = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
    p = jax.nn.softmax(logit, axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# multi-step decode against the plain algorithm
# ---------------------------------------------------------------------------
def _reference_attn_decode(layer, cfg, x, cache_layer, pos):
    """The plain decode attention layer: write the token into this layer's
    cache, then attend over the whole cache with repeated KV heads."""
    cd = M._cd(cfg)
    b = x.shape[0]
    p = layer["attn"]
    hq, hkv, hd = cfg.q_heads, cfg.kv_heads, cfg.head_dim
    h = rms_norm(x, layer["norm1"]).astype(cd)
    q, k, v = (h @ p[w].astype(cd) for w in ("wq", "wk", "wv"))
    if cfg.qkv_bias:
        q, k, v = (t + p[bias].astype(cd)
                   for t, bias in zip((q, k, v), ("bq", "bk", "bv")))
    q = q.reshape(b, 1, hq, hd)
    k = k.reshape(b, 1, hkv, hd)
    v = v.reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    cos, sin = rope_angles(pos[None, None], hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    sc = cache_layer["k"].shape[1]
    new_cache = {**cache_layer,
                 **M._store_kv(cfg, cache_layer, k, v, (pos % sc)[None])}
    kk, vv = M._read_kv(cfg, new_cache)
    kernel = decode_ops.serves(new_cache["k"].dtype, hd, hkv, sc)
    if kernel or (cfg.decode_chunk and sc > cfg.decode_chunk):
        # the blocked path computes in q's dtype, the kernel in the dtype
        # q and the cache promote to; their blocks, merged by online
        # softmax, make the same function as one block
        kk, vv = kk.astype(q.dtype), vv.astype(q.dtype)
    kk = jnp.repeat(kk, hq // hkv, axis=2)
    vv = jnp.repeat(vv, hq // hkv, axis=2)
    sco = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                     preferred_element_type=jnp.float32) * hd ** -0.5
    valid = jnp.arange(sc) < jnp.minimum(pos + 1, sc)
    sco = jnp.where(valid[None, None, None], sco, attn_mod.NEG_INF)
    prob = jax.nn.softmax(sco, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(vv.dtype), vv,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    o = o.reshape(b, 1, hq * hd) @ p["wo"].astype(cd)
    return x + o.astype(x.dtype), new_cache


def _reference_layer_decode(layer, cfg, mixer, x, cache_layer, pos):
    cd = M._cd(cfg)
    if mixer in ("attn", "swa"):
        x, nc = _reference_attn_decode(layer, cfg, x, cache_layer, pos)
    elif mixer == "rglru":
        out, nc = rg_mod.rglru_block_decode(
            layer["rec"], rms_norm(x, layer["norm1"]), cache_layer, cd)
        x = x + out
    else:
        out, nc = rwkv_mod.time_mix_decode(
            layer["rwkv"], rms_norm(x, layer["norm1"]), cache_layer,
            cfg.n_heads, cd)
        x = x + out
    if cfg.ffn == "rwkv_cm":
        out, nc = rwkv_mod.channel_mix_decode(
            layer["rwkv"], rms_norm(x, layer["norm2"]), nc, cd)
        return x + out, nc
    return M._ffn_apply(layer, cfg, x), nc


def _reference_decode_step(params, cfg, cache, tokens):
    """The plain decode step: the stacked cache goes through the layer scan
    as xs and comes back as new stacked ys."""
    pos = cache["pos"]
    x = M.embed_tokens(params, cfg, tokens[:, None])

    def group_body(h, scanned):
        group_params, group_cache = scanned
        new = []
        for p, mixer in enumerate(cfg.pattern):
            h, nc = _reference_layer_decode(group_params[p], cfg, mixer, h,
                                            group_cache[p], pos)
            new.append(nc)
        return h, tuple(new)

    layers = cache["layers"]
    if cfg.n_groups > 0:
        x, ys = jax.lax.scan(group_body, x, (tuple(params["layers"]),
                                             tuple(layers)))
        layers = list(ys)
    tail = []
    for i, mixer in enumerate(cfg.tail_pattern):
        x, nc = _reference_layer_decode(params["tail"][i], cfg, mixer, x,
                                        cache["tail"][i], pos)
        tail.append(nc)
    h = rms_norm(x, params["final_norm"])
    logits = M.logits_fn(params, cfg, h)[:, 0]
    return logits, {"layers": layers, "tail": tail, "pos": pos + 1}


def _decode_step_cases():
    """arch x KV cache dtype x decode_chunk, as they are, then again under
    the decode attention kernel for one arch of each attention shape: GQA
    with q/k norms, a sliding-window ring with MoE, MQA between recurrent
    layers with a tail layer, and multi-head attention with q/k/v bias."""
    grid = [(kv, chunk) for kv in ("bfloat16", "float32", "int8")
            for chunk in (None, 8)]
    kernel_archs = ("qwen3-0.6b", "mixtral-8x7b", "recurrentgemma-9b",
                    "qwen1.5-0.5b")
    return ([pytest.param(a, kv, chunk, None, id=f"{a}-{kv}-{chunk}")
             for a in ARCHS for kv, chunk in grid]
            + [pytest.param(a, kv, chunk, KERNEL,
                            id=f"{a}-{kv}-{chunk}-{KERNEL}")
               for a in kernel_archs for kv, chunk in grid])


@pytest.mark.parametrize("arch,kv_dtype,decode_chunk,impl",
                         _decode_step_cases())
def test_decode_steps_match_plain_algorithm(arch, kv_dtype, decode_chunk,
                                            impl):
    """Prefill, then decode past the sliding-window ring's wrap: the step
    that updates the cache in place and reads grouped KV heads gives the
    logits of the plain algorithm at every step; under the kernel, bf16
    and float32 caches are read by the decode attention kernel (three
    blocks of 8 at max_len 24, whatever ``decode_chunk``), int8 ones by
    the XLA path."""
    cfg = dataclasses.replace(smoke(get_config(arch)),
                              compute_dtype="float32",
                              kv_cache_dtype=kv_dtype,
                              decode_chunk=decode_chunk,
                              capacity_factor=float("inf"))
    cfg, scope = _under(impl, cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s, steps = 2, 12, 8             # positions 12..19: the ring of 16
    toks, _ = _inputs(cfg, b=b, s=s + steps, seed=5)   # wraps at 16
    _, cache = M.prefill(params, cfg, toks[:, :s], max_len=24)
    step = jax.jit(lambda c, t: M.decode_step(params, cfg, c, t))
    ref_step = jax.jit(lambda c, t: _reference_decode_step(params, cfg, c,
                                                           t))
    ref_cache = cache
    with scope:
        for t in range(s, s + steps):
            logits, cache = step(cache, toks[:, t])
            ref_logits, ref_cache = ref_step(ref_cache, toks[:, t])
            np.testing.assert_allclose(np.asarray(logits),
                                       np.asarray(ref_logits),
                                       rtol=2e-3, atol=2e-3)
    assert int(cache["pos"]) == s + steps


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------
def test_moe_capacity_inf_matches_reference():
    from repro.models import ffn
    key = jax.random.PRNGKey(0)
    params = ffn.init_moe(key, 16, 32, n_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    ref = ffn.moe_ffn_reference(params, x, top_k=2)
    out = ffn.moe_ffn(params, x, top_k=2, capacity_factor=float("inf"),
                      compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_drops_overflow_only():
    """Finite capacity output differs from oracle only on dropped tokens,
    and never produces NaNs."""
    from repro.models import ffn
    key = jax.random.PRNGKey(0)
    params = ffn.init_moe(key, 16, 32, n_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16))
    out = ffn.moe_ffn(params, x, top_k=2, capacity_factor=1.0,
                      compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert out.shape == x.shape


# ---------------------------------------------------------------------------
# losses & numerics
# ---------------------------------------------------------------------------
def test_lm_loss_matches_dense_xent():
    cfg = smoke(get_config("qwen3-0.6b"))
    cfg = dataclasses.replace(cfg, compute_dtype="float32", loss_chunk=32)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks, labels = _inputs(cfg, b=2, s=16)
    hidden = M.forward(params, cfg, toks)
    loss = M.lm_loss(params, cfg, hidden, labels)
    logits = M.logits_fn(params, cfg, hidden)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    expect = jnp.mean(logz - gold)
    np.testing.assert_allclose(float(loss), float(expect), rtol=1e-5)


def test_int8_kv_cache_close_to_bf16():
    cfg = smoke(get_config("qwen1.5-32b"))
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32",
                                kv_cache_dtype="float32")
    cfg8 = dataclasses.replace(cfg, compute_dtype="float32",
                               kv_cache_dtype="int8")
    params = M.init_params(cfg32, jax.random.PRNGKey(0))
    toks, _ = _inputs(cfg32, b=2, s=12)
    lf, cf = M.prefill(params, cfg32, toks, max_len=16)
    lq, cq = M.prefill(params, cfg8, toks, max_len=16)
    # int8 KV introduces bounded error on the next-token logits
    lf2, _ = M.decode_step(params, cfg32, cf, toks[:, -1])
    lq2, _ = M.decode_step(params, cfg8, cq, toks[:, -1])
    err = np.abs(np.asarray(lf2) - np.asarray(lq2))
    rel = err.max() / (np.abs(np.asarray(lf2)).max() + 1e-9)
    assert rel < 0.08, rel
