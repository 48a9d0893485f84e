"""Decoder model assembly: config, init, forward / prefill / decode.

Layer stacking uses ``lax.scan`` over *groups* (one group = one repetition
of the mixer ``pattern``, e.g. RecurrentGemma's (rglru, rglru, attn)), with
the non-dividing remainder unrolled as ``tail`` layers.  Scan keeps the HLO
O(1) in depth — required for the 512-device dry-run compiles — and is remat
boundary.

Mixers: ``attn`` (full causal), ``swa`` (sliding window), ``rglru``
(RecurrentGemma recurrent block), ``rwkv6`` (Finch time-mix).
FFNs:   ``swiglu``, ``gelu``, ``moe``, ``rwkv_cm`` (channel-mix).

Head-count padding (``pad_heads_to``/``pad_kv_heads_to``) applies the
paper's padding-for-computation to tensor-parallel divisibility (yi-34b
56->64 q heads etc.); the padded heads are real parameters — extra compute
traded for legal parallelism, exactly the Listing 1 trade.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import HEADS, RESIDUAL, constrain
from ..kernels.decode_attention import ops as decode_ops
from . import attention as attn_mod
from . import ffn as ffn_mod
from . import rglru_block as rg_mod
from . import rwkv6_block as rwkv_mod
from .common import (apply_rope, dense_init, embed_init, rms_norm,
                     rope_angles, split_keys)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    pattern: tuple[str, ...] = ("attn",)
    ffn: str = "swiglu"
    n_experts: int = 0
    moe_top_k: int = 0
    window: int | None = None            # for "swa" mixers
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e6
    embed_input: bool = True             # False: stub frontend feeds embeds
    attn_impl: str = "recursive"
    attn_chunk: int = 512
    loss_chunk: int = 1024
    capacity_factor: float = 1.25
    d_rnn: int = 0                       # rglru recurrence width
    remat: bool = True
    # Dry-run fidelity: python-unroll the layer/loss scans so XLA's
    # HloCostAnalysis (which counts while bodies ONCE) sees every layer.
    unroll_layers: bool = False
    compute_dtype: str = "bfloat16"      # or "float32" (tests/debug)
    # Parameter storage dtype.  "bfloat16" stores model weights in bf16
    # (casts vanish from the forward pass; gradients and their DP
    # all-reduce go bf16) with an fp32 master copy living in the
    # optimizer state — the standard mixed-precision recipe.  §Perf lever.
    param_dtype: str = "float32"
    kv_cache_dtype: str = "bfloat16"     # or "int8" / "float32"
    # §Perf levers (beyond-paper; defaults are the faithful baseline):
    attn_score_dtype: str = "float32"    # "bfloat16": bf16 score maps
    gqa_grouped: bool = False            # grouped GQA einsum (no KV repeat)
    ffn_act_f32: bool = True             # False: bf16 FFN activations
    # Sequence-blocked decode attention (paged-attention-lite): the KV
    # cache is read/dequantised one block at a time — the live working
    # set shrinks from the whole cache to one block.  None = unblocked.
    # Unused where the decode attention kernel serves the cache, which
    # reads the blocks below the length in blocks of its own.
    decode_chunk: int | None = None
    pad_heads_to: int | None = None      # computation padding for TP
    pad_kv_heads_to: int | None = None

    @property
    def q_heads(self) -> int:
        return self.pad_heads_to or self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.pad_kv_heads_to or self.n_kv_heads

    @property
    def n_groups(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def tail_pattern(self) -> tuple[str, ...]:
        return self.pattern[:self.n_layers % len(self.pattern)]

    @property
    def rnn_width(self) -> int:
        return self.d_rnn or self.d_model

    def mixer_at(self, layer: int) -> str:
        return self.pattern[layer % len(self.pattern)]


def _cd(cfg: "ModelConfig"):
    return jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32


def _sd(cfg: "ModelConfig"):
    return jnp.bfloat16 if cfg.attn_score_dtype == "bfloat16" \
        else jnp.float32


def _group_slice(stacked, g: int):
    return tuple(jax.tree.map(lambda a: a[g], pos) for pos in stacked)


def _stack_groups(per_group: list):
    # list over groups of tuples over positions -> tuple of stacked trees
    n_pos = len(per_group[0])
    return tuple(
        jax.tree.map(lambda *xs: jnp.stack(xs),
                     *[per_group[g][p] for g in range(len(per_group))])
        for p in range(n_pos))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn(key, cfg: ModelConfig, dtype) -> dict:
    ks = split_keys(key, ["wq", "wk", "wv", "wo"])
    d, hq, hkv, hd = cfg.d_model, cfg.q_heads, cfg.kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(ks["wq"], (d, hq * hd), dtype),
        "wk": dense_init(ks["wk"], (d, hkv * hd), dtype),
        "wv": dense_init(ks["wv"], (d, hkv * hd), dtype),
        "wo": dense_init(ks["wo"], (hq * hd, d), dtype, fan_in=hq * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _init_ffn(key, cfg: ModelConfig, dtype) -> dict:
    if cfg.ffn == "swiglu":
        return ffn_mod.init_swiglu(key, cfg.d_model, cfg.d_ff, dtype)
    if cfg.ffn == "gelu":
        return ffn_mod.init_gelu(key, cfg.d_model, cfg.d_ff, dtype)
    if cfg.ffn == "moe":
        return ffn_mod.init_moe(key, cfg.d_model, cfg.d_ff, cfg.n_experts,
                                dtype)
    if cfg.ffn == "rwkv_cm":
        return {}                         # lives inside the mixer params
    raise ValueError(cfg.ffn)


def _init_layer(key, cfg: ModelConfig, mixer: str, dtype) -> dict:
    ks = split_keys(key, ["mix", "ffn"])
    d = cfg.d_model
    layer: dict[str, Any] = {
        "norm1": jnp.zeros((d,), dtype),
        "norm2": jnp.zeros((d,), dtype),
    }
    if mixer in ("attn", "swa"):
        layer["attn"] = _init_attn(ks["mix"], cfg, dtype)
    elif mixer == "rglru":
        layer["rec"] = rg_mod.init_rglru_block(ks["mix"], d, cfg.rnn_width,
                                               dtype)
    elif mixer == "rwkv6":
        layer["rwkv"] = rwkv_mod.init_rwkv6_block(ks["mix"], d, cfg.n_heads,
                                                  cfg.d_ff, dtype)
    else:
        raise ValueError(mixer)
    if cfg.ffn != "rwkv_cm":
        layer["ffn"] = _init_ffn(ks["ffn"], cfg, dtype)
    return layer


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=None) -> dict:
    if dtype is None:
        dtype = jnp.bfloat16 if cfg.param_dtype == "bfloat16" \
            else jnp.float32
    ks = split_keys(key, ["embed", "layers", "tail", "head"])
    params: dict[str, Any] = {}
    if cfg.embed_input:
        params["embed"] = embed_init(ks["embed"], (cfg.vocab, cfg.d_model),
                                     dtype)
    # scanned groups: one stacked pytree per pattern position
    lkeys = jax.random.split(ks["layers"],
                             max(cfg.n_groups, 1) * len(cfg.pattern))
    stacked = []
    for p, mixer in enumerate(cfg.pattern):
        per_group = [
            _init_layer(lkeys[g * len(cfg.pattern) + p], cfg, mixer, dtype)
            for g in range(cfg.n_groups)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
                       if per_group else None)
    params["layers"] = stacked
    tkeys = jax.random.split(ks["tail"], max(len(cfg.tail_pattern), 1))
    params["tail"] = [
        _init_layer(tkeys[i], cfg, mixer, dtype)
        for i, mixer in enumerate(cfg.tail_pattern)]
    params["final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    params["lm_head"] = dense_init(ks["head"], (cfg.d_model, cfg.vocab),
                                   dtype)
    return params


def decay_mask(params: dict) -> dict:
    """Per leaf, whether weight decay applies: the matrices, not the norm
    gains and biases.  Leaves of the scanned groups carry a leading layer
    axis, so their rank within a layer is one less."""
    def matrix(path, x):
        return x.ndim - (path[0].key == "layers") >= 2
    return jax.tree_util.tree_map_with_path(matrix, params)


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------
def _attn_apply(layer: dict, cfg: ModelConfig, mixer: str, x: jax.Array,
                cos, sin) -> jax.Array:
    compute_dtype = _cd(cfg)
    b, s, d = x.shape
    p = layer["attn"]
    h = rms_norm(x, layer["norm1"]).astype(compute_dtype)
    hq, hkv, hd = cfg.q_heads, cfg.kv_heads, cfg.head_dim
    q = h @ p["wq"].astype(compute_dtype)
    k = h @ p["wk"].astype(compute_dtype)
    v = h @ p["wv"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    q = constrain(q.reshape(b, s, hq, hd), HEADS)
    k = constrain(k.reshape(b, s, hkv, hd), HEADS)
    v = constrain(v.reshape(b, s, hkv, hd), HEADS)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    window = cfg.window if mixer == "swa" else None
    o = attn_mod.attention(q, k, v, impl=cfg.attn_impl, window=window,
                           chunk=cfg.attn_chunk, unroll=cfg.unroll_layers,
                           score_dtype=_sd(cfg), gqa_grouped=cfg.gqa_grouped)
    o = constrain(o, HEADS).reshape(b, s, hq * hd) \
        @ p["wo"].astype(compute_dtype)
    return constrain(x + o.astype(x.dtype), RESIDUAL)


def _ffn_apply(layer: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    compute_dtype = _cd(cfg)
    if cfg.ffn == "rwkv_cm":
        h = rms_norm(x, layer["norm2"])
        return x + rwkv_mod.channel_mix(layer["rwkv"], h, compute_dtype)
    h = rms_norm(x, layer["norm2"])
    if cfg.ffn == "swiglu":
        out = ffn_mod.swiglu(layer["ffn"], h, compute_dtype,
                             act_f32=cfg.ffn_act_f32)
    elif cfg.ffn == "gelu":
        out = ffn_mod.gelu_mlp(layer["ffn"], h, compute_dtype,
                               act_f32=cfg.ffn_act_f32)
    elif cfg.ffn == "moe":
        out = ffn_mod.moe_ffn(layer["ffn"], h, top_k=cfg.moe_top_k,
                              capacity_factor=cfg.capacity_factor,
                              compute_dtype=compute_dtype,
                              act_f32=cfg.ffn_act_f32)
    else:
        raise ValueError(cfg.ffn)
    return x + out


def _layer_apply(layer: dict, cfg: ModelConfig, mixer: str, x: jax.Array,
                 cos, sin) -> jax.Array:
    if mixer in ("attn", "swa"):
        x = _attn_apply(layer, cfg, mixer, x, cos, sin)
    elif mixer == "rglru":
        h = rms_norm(x, layer["norm1"])
        x = x + rg_mod.rglru_block(layer["rec"], h, _cd(cfg))
    elif mixer == "rwkv6":
        h = rms_norm(x, layer["norm1"])
        x = x + rwkv_mod.time_mix(layer["rwkv"], h, cfg.n_heads, _cd(cfg))
    else:
        raise ValueError(mixer)
    return _ffn_apply(layer, cfg, x)


def embed_tokens(params: dict, cfg: ModelConfig,
                 tokens: jax.Array) -> jax.Array:
    compute_dtype = _cd(cfg)
    if cfg.embed_input:
        return params["embed"][tokens].astype(compute_dtype)
    # stub frontend: tokens already are embeddings (B, S, D)
    return tokens.astype(compute_dtype)


def forward(params: dict, cfg: ModelConfig, tokens: jax.Array,
            positions: jax.Array | None = None) -> jax.Array:
    """tokens (B,S) int32 (or (B,S,D) embeddings for stub-frontend archs)
    -> final hidden states (B,S,D) after the last norm."""
    x = constrain(embed_tokens(params, cfg, tokens), RESIDUAL)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None]
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    def group_body(carry, group_params):
        h = carry
        for p, mixer in enumerate(cfg.pattern):
            h = constrain(_layer_apply(
                jax.tree.map(lambda a: a, group_params[p]), cfg, mixer, h,
                cos, sin), RESIDUAL)
        return h, None

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(group_body, prevent_cse=False)
    if cfg.n_groups > 0:
        if cfg.unroll_layers:
            for g in range(cfg.n_groups):
                x, _ = body(x, _group_slice(tuple(params["layers"]), g))
        else:
            x, _ = jax.lax.scan(body, x, tuple(params["layers"]))
    for i, mixer in enumerate(cfg.tail_pattern):
        x = _layer_apply(params["tail"][i], cfg, mixer, x, cos, sin)
    return rms_norm(x, params["final_norm"])


def logits_fn(params: dict, cfg: ModelConfig,
              hidden: jax.Array) -> jax.Array:
    compute_dtype = _cd(cfg)
    return (hidden.astype(compute_dtype)
            @ params["lm_head"].astype(compute_dtype)).astype(jnp.float32)


def lm_loss(params: dict, cfg: ModelConfig, hidden: jax.Array,
            labels: jax.Array) -> jax.Array:
    """Chunked softmax cross-entropy: logits are never materialised for the
    whole sequence (vocab 256k x 4k tokens would not fit HBM).  A chunk is
    every row's next ``loss_chunk // B`` positions, so under a mesh each
    data shard computes the loss of its own rows."""
    b, s, d = hidden.shape
    chunk = max(1, min(s, cfg.loss_chunk // b))
    pad = (-s) % chunk
    h, y = hidden, labels
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)), constant_values=-1)
    n = h.shape[1] // chunk
    h = jnp.moveaxis(h.reshape(b, n, chunk, d), 1, 0)
    y = jnp.moveaxis(y.reshape(b, n, chunk), 1, 0)

    def chunk_loss(carry, hy):
        h_c, y_c = hy
        logits = logits_fn(params, cfg, constrain(h_c, RESIDUAL))
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y_c, 0)[..., None], axis=-1)[..., 0]
        valid = (y_c >= 0).astype(jnp.float32)
        nll = (logz - gold) * valid
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(valid)), None

    if cfg.unroll_layers:
        carry = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        for i in range(n):
            carry, _ = chunk_loss(carry, (h[i], y[i]))
        total, count = carry
    else:
        (total, count), _ = jax.lax.scan(chunk_loss, (0.0, 0.0), (h, y))
    return total / jnp.maximum(count, 1.0)


# ---------------------------------------------------------------------------
# KV / recurrent caches
# ---------------------------------------------------------------------------
def _cache_len(cfg: ModelConfig, mixer: str, max_len: int) -> int:
    if mixer == "swa" and cfg.window is not None:
        return min(cfg.window, max_len)
    return max_len


def _kv_dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "int8": jnp.int8,
            "float32": jnp.float32}[cfg.kv_cache_dtype]


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Stacked caches mirroring the scanned/tail param structure.  K and V
    are stored as the projections make them, (B, Sc, Hkv*hd): head h is
    the columns [h*hd, (h+1)*hd), so a block of positions is a lane-dense
    tile; int8 caches keep one scale per (position, head), (B, Sc, Hkv)."""
    cache_dtype = _kv_dtype(cfg)

    def one(mixer: str) -> dict:
        if mixer in ("attn", "swa"):
            sc = _cache_len(cfg, mixer, max_len)
            row = (batch, sc, cfg.kv_heads * cfg.head_dim)
            c = {"k": jnp.zeros(row, cache_dtype),
                 "v": jnp.zeros(row, cache_dtype)}
            if cfg.kv_cache_dtype == "int8":
                c["k_scale"] = jnp.zeros((batch, sc, cfg.kv_heads),
                                         jnp.float32)
                c["v_scale"] = jnp.zeros((batch, sc, cfg.kv_heads),
                                         jnp.float32)
            return c
        if mixer == "rglru":
            return rg_mod.init_rglru_state(batch, cfg.rnn_width)
        if mixer == "rwkv6":
            return rwkv_mod.init_rwkv6_state(batch, cfg.d_model,
                                             cfg.n_heads)
        raise ValueError(mixer)

    stacked = []
    for mixer in cfg.pattern:
        per_group = [one(mixer) for _ in range(cfg.n_groups)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
                       if per_group else None)
    return {
        "layers": stacked,
        "tail": [one(m) for m in cfg.tail_pattern],
        "pos": jnp.zeros((), jnp.int32),
    }


def _quant_kv(cfg: ModelConfig, k, v) -> dict:
    """k/v (B, S, Hkv, hd) as cache entries (B, S, Hkv*hd): int8 caches
    store per-vector scales (B, S, Hkv) beside the rounded values, the
    others the values cast."""
    def rows(x):
        return x.reshape(x.shape[0], x.shape[1], -1)

    if cfg.kv_cache_dtype == "int8":
        def quant(x):
            amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                           keepdims=True)
            scale = jnp.maximum(amax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
            return rows(q), scale[..., 0]
        kq, ks = quant(k)
        vq, vs = quant(v)
        return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    return {"k": rows(k).astype(_kv_dtype(cfg)),
            "v": rows(v).astype(_kv_dtype(cfg))}


def _store_kv(cfg: ModelConfig, cache_layer: dict, k, v, idx):
    """Write k/v (B, S, Hkv, hd) at positions ``idx`` (S,), quantizing for
    int8 caches."""
    return {name: cache_layer[name].at[:, idx].set(entry)
            for name, entry in _quant_kv(cfg, k, v).items()}


def _read_kv(cfg: ModelConfig, cache_layer: dict):
    """One layer's cache entries (B, S, Hkv*hd) as k/v (B, S, Hkv, hd),
    dequantized for int8 caches."""
    def heads(x):
        return x.reshape(x.shape[0], x.shape[1], cfg.kv_heads, cfg.head_dim)

    if cfg.kv_cache_dtype == "int8":
        k = heads(cache_layer["k"]).astype(jnp.float32) \
            * cache_layer["k_scale"][..., None]
        v = heads(cache_layer["v"]).astype(jnp.float32) \
            * cache_layer["v_scale"][..., None]
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return heads(cache_layer["k"]), heads(cache_layer["v"])


# ---------------------------------------------------------------------------
# decode
#
# The stacked cache is the layer scan's carry and is updated in place: each
# layer reads its own K/V by dynamic index, then writes its one new position
# with a dynamic-update-slice at [layer, :, pos % sc].  Jitted with the
# cache donated (``serve.Engine``), the step reads each cache byte once and
# makes no cache-sized temporary.  Where the decode attention kernel serves
# the cache (``decode_ops.serves``: a Pallas implementation, bf16 or
# float32 entries, heads a multiple of 128 wide, a length that splits into
# tiled blocks), it reads only the blocks below the step's length, straight
# from the stacked cache.
# ---------------------------------------------------------------------------
def _attn_decode(layer: dict, cfg: ModelConfig, x: jax.Array, cache: dict,
                 at, pos: jax.Array):
    compute_dtype = _cd(cfg)
    b = x.shape[0]
    p = layer["attn"]
    h = rms_norm(x, layer["norm1"]).astype(compute_dtype)
    hq, hkv, hd = cfg.q_heads, cfg.kv_heads, cfg.head_dim
    q = h @ p["wq"].astype(compute_dtype)
    k = h @ p["wk"].astype(compute_dtype)
    v = h @ p["wv"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    q = q.reshape(b, 1, hq, hd)
    k = k.reshape(b, 1, hkv, hd)
    v = v.reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    cos, sin = rope_angles(pos[None, None], cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    sc = cache["k"].shape[2]
    slot = pos % sc
    new = _quant_kv(cfg, k, v)
    length = jnp.minimum(pos + 1, sc)

    def read(start, size: int):
        lay = {name: jax.lax.dynamic_slice(
                   a, (at, 0, start, 0), (1, b, size, a.shape[3]))[0]
               for name, a in cache.items()}
        return _read_kv(cfg, lay)

    # the layer's K/V are read as they stood, with the new token attended
    # beside them, so the read does not wait on the write below: XLA then
    # keeps the cache in its own layout and updates it in place
    block = decode_ops.serves(cache["k"].dtype, hd, hkv, sc)
    if block:
        o = decode_ops.decode_attention(
            q, cache["k"], cache["v"], at, length, slot, new["k"], new["v"],
            block=block)
    elif cfg.decode_chunk and sc > cfg.decode_chunk:
        chunk = cfg.decode_chunk
        o = attn_mod.decode_attention_blocks(
            q.astype(compute_dtype), lambda i: read(i * chunk, chunk),
            sc // chunk, chunk, length, unroll=cfg.unroll_layers,
            new_kv=(*_read_kv(cfg, new), slot))
    else:
        o = attn_mod.decode_attention(q, *read(0, sc), length,
                                      new_kv=(*_read_kv(cfg, new), slot))
    cache = {name: jax.lax.dynamic_update_slice(
                 a, new[name][None], (at, 0, slot, 0))
             for name, a in cache.items()}
    o = o.reshape(b, 1, hq * hd) @ p["wo"].astype(compute_dtype)
    return x + o.astype(x.dtype), cache


def _layer_decode(layer: dict, cfg: ModelConfig, mixer: str, x: jax.Array,
                  cache: dict, at, pos: jax.Array):
    """One layer's step.  ``cache`` is its pattern position's stacked cache
    (leading layer axis) and ``at`` the layer's index there; returns the
    stacked cache with that layer updated."""
    if mixer in ("attn", "swa"):
        x, cache = _attn_decode(layer, cfg, x, cache, at, pos)
        return _ffn_apply(layer, cfg, x), cache
    state = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, keepdims=False), cache)
    h = rms_norm(x, layer["norm1"])
    if mixer == "rglru":
        out, state = rg_mod.rglru_block_decode(layer["rec"], h, state,
                                               _cd(cfg))
    elif mixer == "rwkv6":
        out, state = rwkv_mod.time_mix_decode(layer["rwkv"], h, state,
                                              cfg.n_heads, _cd(cfg))
    else:
        raise ValueError(mixer)
    x = x + out
    if cfg.ffn == "rwkv_cm":
        h = rms_norm(x, layer["norm2"])
        out, state = rwkv_mod.channel_mix_decode(layer["rwkv"], h, state,
                                                 _cd(cfg))
        x = x + out
    else:
        x = _ffn_apply(layer, cfg, x)
    cache = jax.tree.map(
        lambda a, s: jax.lax.dynamic_update_index_in_dim(
            a, s.astype(a.dtype), at, 0), cache, state)
    return x, cache


def decode_kv_blocks(cfg: ModelConfig, max_len: int, start: int,
                     steps: int) -> tuple[int, int]:
    """(read, skipped) KV cache blocks over ``steps`` decode steps from
    position ``start``, summed over the attention layers: what the decode
    attention kernel reads and skips where it serves the cache, and every
    block read where it does not.  Counted on the host from shapes, by the
    decision ``_attn_decode`` takes (``decode_ops.serves``)."""
    mixers = list(cfg.pattern) * cfg.n_groups + list(cfg.tail_pattern)
    lengths = np.arange(start + 1, start + steps + 1)
    read = skipped = 0
    for mixer in mixers:
        if mixer in ("attn", "swa"):
            sc = _cache_len(cfg, mixer, max_len)
            block = decode_ops.serves(_kv_dtype(cfg), cfg.head_dim,
                                      cfg.kv_heads, sc)
            r, s = decode_ops.kv_blocks(sc, lengths, block)
            read, skipped = read + r, skipped + s
    return read, skipped


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                tokens: jax.Array) -> tuple[jax.Array, dict]:
    """One decoding step.  tokens (B,) int32 (or (B,D) embeddings for stub
    archs) -> (logits (B,V), new cache)."""
    pos = cache["pos"]
    if cfg.embed_input:
        x = params["embed"][tokens][:, None].astype(_cd(cfg))
    else:
        x = tokens[:, None].astype(_cd(cfg))

    def group_body(carry, scanned):
        h, caches = carry
        g, group_params = scanned
        caches = list(caches)
        for p, mixer in enumerate(cfg.pattern):
            h, caches[p] = _layer_decode(group_params[p], cfg, mixer, h,
                                         caches[p], g, pos)
        return (h, tuple(caches)), None

    layers = tuple(cache["layers"])
    if cfg.n_groups > 0:
        if cfg.unroll_layers:
            for g in range(cfg.n_groups):
                (x, layers), _ = group_body(
                    (x, layers), (g, _group_slice(tuple(params["layers"]),
                                                  g)))
        else:
            (x, layers), _ = jax.lax.scan(
                group_body, (x, layers),
                (jnp.arange(cfg.n_groups), tuple(params["layers"])))
    new_tail = []
    for i, mixer in enumerate(cfg.tail_pattern):
        one = jax.tree.map(lambda a: a[None], cache["tail"][i])
        x, one = _layer_decode(params["tail"][i], cfg, mixer, x, one, 0, pos)
        new_tail.append(jax.tree.map(lambda a: a[0], one))
    h = rms_norm(x, params["final_norm"])
    logits = logits_fn(params, cfg, h)[:, 0]
    return logits, {"layers": list(layers), "tail": new_tail,
                    "pos": pos + 1}


# ---------------------------------------------------------------------------
# prefill: parallel forward that also fills caches / recurrent states
# ---------------------------------------------------------------------------
def _attn_prefill(layer: dict, cfg: ModelConfig, mixer: str, x: jax.Array,
                  cos, sin, cache_layer: dict):
    compute_dtype = _cd(cfg)
    """Full attention layer computing q/k/v once: returns (x', cache')."""
    b, s, d = x.shape
    p = layer["attn"]
    h = rms_norm(x, layer["norm1"]).astype(compute_dtype)
    hq, hkv, hd = cfg.q_heads, cfg.kv_heads, cfg.head_dim
    q = h @ p["wq"].astype(compute_dtype)
    k = h @ p["wk"].astype(compute_dtype)
    v = h @ p["wv"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    q = q.reshape(b, s, hq, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    sc = cache_layer["k"].shape[1]
    if sc < s:   # ring buffer: only the last sc positions survive
        idx = jnp.arange(s - sc, s) % sc
        nc = {**cache_layer,
              **_store_kv(cfg, cache_layer, k[:, -sc:], v[:, -sc:], idx)}
    else:
        nc = {**cache_layer,
              **_store_kv(cfg, cache_layer, k, v, jnp.arange(s))}
    window = cfg.window if mixer == "swa" else None
    o = attn_mod.attention(q, k, v, impl=cfg.attn_impl, window=window,
                           chunk=cfg.attn_chunk, unroll=cfg.unroll_layers,
                           score_dtype=_sd(cfg), gqa_grouped=cfg.gqa_grouped)
    o = o.reshape(b, s, hq * hd) @ p["wo"].astype(compute_dtype)
    return x + o.astype(x.dtype), nc


def _layer_prefill(layer: dict, cfg: ModelConfig, mixer: str, x: jax.Array,
                   cos, sin, cache_layer: dict):
    if mixer in ("attn", "swa"):
        x, nc = _attn_prefill(layer, cfg, mixer, x, cos, sin, cache_layer)
    elif mixer == "rglru":
        h = rms_norm(x, layer["norm1"])
        out, nc = rg_mod.rglru_block_with_state(layer["rec"], h, _cd(cfg))
        x = x + out
    elif mixer == "rwkv6":
        h = rms_norm(x, layer["norm1"])
        out, tm_state = rwkv_mod.time_mix_with_state(
            layer["rwkv"], h, cfg.n_heads, _cd(cfg))
        x = x + out
        nc = {**cache_layer, **tm_state}
    else:
        raise ValueError(mixer)
    if cfg.ffn == "rwkv_cm":
        h = rms_norm(x, layer["norm2"])
        nc = {**nc, "cm_last": h.astype(jnp.float32)[:, -1:]}
        x = x + rwkv_mod.channel_mix(layer["rwkv"], h, _cd(cfg))
    else:
        x = _ffn_apply(layer, cfg, x)
    return x, nc


def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            max_len: int | None = None) -> tuple[jax.Array, dict]:
    """Run the prompt in parallel, returning (last-position logits (B,V),
    filled cache).  Recurrent mixers return their final states from the
    scan kernels; attention mixers bulk-write (ring-buffered) KV caches."""
    x = embed_tokens(params, cfg, tokens)
    b, s = x.shape[0], x.shape[1]
    max_len = max_len or s
    cache = init_cache(cfg, b, max_len)
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    def group_body(carry, scanned):
        h = carry
        group_params, group_cache = scanned
        new_caches = []
        for p, mixer in enumerate(cfg.pattern):
            h, nc = _layer_prefill(group_params[p], cfg, mixer, h,
                                   cos, sin, group_cache[p])
            new_caches.append(nc)
        return h, tuple(new_caches)

    body = group_body
    if cfg.remat:
        body = jax.checkpoint(group_body, prevent_cse=False)
    new_cache: dict[str, Any] = {"pos": jnp.asarray(s, jnp.int32)}
    if cfg.n_groups > 0:
        if cfg.unroll_layers:
            collected = []
            for g in range(cfg.n_groups):
                x, ncg = body(
                    x, (_group_slice(tuple(params["layers"]), g),
                        _group_slice(tuple(cache["layers"]), g)))
                collected.append(ncg)
            new_cache["layers"] = list(_stack_groups(collected))
        else:
            x, ncl = jax.lax.scan(body, x,
                                  (tuple(params["layers"]),
                                   tuple(cache["layers"])))
            new_cache["layers"] = list(ncl)
    else:
        new_cache["layers"] = cache["layers"]
    new_tail = []
    for i, mixer in enumerate(cfg.tail_pattern):
        x, nc = _layer_prefill(params["tail"][i], cfg, mixer, x, cos, sin,
                               cache["tail"][i])
        new_tail.append(nc)
    new_cache["tail"] = new_tail
    h = rms_norm(x, params["final_norm"])
    logits = logits_fn(params, cfg, h[:, -1:])[:, 0]
    return logits, new_cache
