"""Attention with selectable implementations (the solver's choice axis).

Implementations (``AttnImpl``):

  ``naive``      full (S,S) masked logits — the oracle; O(S^2) memory.
  ``chunked``    query chunks, each against the keys up to its own end —
                 ~S^2/2 FLOPs, O(S*C) memory in the forward and, each
                 chunk recomputed there, in the backward pass (what a
                 config that trains at long rows chooses: qwen1.5-32b).
  ``recursive``  recursive-halving causal attention: the strictly-causal
                 part decomposes into log2(S/C) levels of *unmasked*
                 rectangular attention (upper-half Q vs lower-half K/V,
                 batched across sub-blocks) plus masked diagonal base
                 blocks.  ~S^2/2 + S*C FLOPs with static shapes — the
                 XLA-visible analogue of flash-attention block skipping;
                 a beyond-paper optimization measured in §Perf.
  ``windowed``   sliding-window attention in O(S*(W+C)) via per-chunk
                 dynamic KV slices (mixtral SWA, recurrentgemma local).
  ``pallas``     the flash-attention Pallas kernel (TPU; interpret in
                 tests).

All paths share fp32 softmax statistics and merge via the online-softmax
(acc, m, l) triple.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import ops as flash_ops

AttnImpl = Literal["naive", "chunked", "recursive", "windowed", "pallas"]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# online-softmax piece algebra: a piece is (acc, m, l) with
#   out = acc / l,  acc = sum_j exp(s_j - m) v_j,  l = sum_j exp(s_j - m)
# ---------------------------------------------------------------------------
def _piece(q, k, v, *, scale: float, masked: bool = True,
           row0=0, col0=0, causal: bool = True, window: int | None = None,
           score_dtype=jnp.float32, gqa_grouped: bool = False):
    """Attention piece of q (B,Sq,H,D) against k/v (B,Sk,Hkv,D).

    Masking uses absolute positions: row = row0 + r, col = col0 + c;
    valid iff (col <= row if causal) and (col > row - window) and col >= 0.
    ``masked=False`` skips masking entirely (unmasked cross blocks in the
    recursive decomposition).

    §Perf levers (beyond-paper; baseline keeps the faithful defaults):
      score_dtype=bf16   keeps the O(S^2) score/prob maps in bf16 — the
                         row statistics (max, sum) stay fp32, which is
                         what a fused TPU kernel holds in registers;
      gqa_grouped=True   grouped einsum over (Hkv, G) instead of
                         materialising repeated KV heads.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    group = h // hkv
    if group > 1 and not gqa_grouped:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if group > 1 and gqa_grouped:
        qg = q.reshape(b, sq, hkv, group, d)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                       preferred_element_type=score_dtype) * scale
        sm_axes = (0, 1, 2)        # (b, hkv, g) leading axes
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=score_dtype) * scale
        sm_axes = (0, 1)
    lead = (1,) * len(sm_axes)
    if masked:
        rows = row0 + jnp.arange(sq)[:, None]
        cols = col0 + jnp.arange(sk)[None, :]
        valid = cols >= 0
        if causal:
            valid &= cols <= rows
        if window is not None:
            valid &= cols > rows - window
        s = jnp.where(valid.reshape(lead + (sq, sk)), s,
                      jnp.asarray(NEG_INF, s.dtype))
    m = jnp.max(s, axis=-1, keepdims=True).astype(jnp.float32)
    p = jnp.exp((s - m.astype(s.dtype)))
    if masked:
        p = jnp.where(valid.reshape(lead + (sq, sk)), p,
                      jnp.asarray(0.0, p.dtype))
    l = jnp.sum(p.astype(jnp.float32), axis=-1, keepdims=True)
    if group > 1 and gqa_grouped:
        acc = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        acc = acc.reshape(b, sq, h, d)
        m = jnp.transpose(m, (0, 3, 1, 2, 4)).reshape(b, sq, h, 1)
        l = jnp.transpose(l, (0, 3, 1, 2, 4)).reshape(b, sq, h, 1)
    else:
        acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        m = jnp.transpose(m, (0, 2, 1, 3))                      # (B,Sq,H,1)
        l = jnp.transpose(l, (0, 2, 1, 3))
    return acc.astype(jnp.float32), m, l


def _merge(p1, p2):
    acc1, m1, l1 = p1
    acc2, m2, l2 = p2
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return acc1 * c1 + acc2 * c2, m, l1 * c1 + l2 * c2


def _finalize(piece, dtype):
    acc, _, l = piece
    return (acc / jnp.maximum(l, 1e-30)).astype(dtype)


# ---------------------------------------------------------------------------
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              impl: AttnImpl = "chunked", window: int | None = None,
              chunk: int = 512, scale: float | None = None,
              unroll: bool = False, score_dtype=jnp.float32,
              gqa_grouped: bool = False) -> jax.Array:
    """Causal (optionally sliding-window) self attention.

    q (B,S,H,D); k,v (B,S,Hkv,D) with H % Hkv == 0.  Returns (B,S,H,D).
    ``window`` counts the current token (window=1 sees only itself).
    ``unroll`` python-unrolls the chunk maps (dry-run cost fidelity:
    HloCostAnalysis counts a loop body once; unrolled bodies count fully).
    """
    b, s, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    if impl == "pallas":
        return flash_ops.flash_attention(q, k, v, causal=True, window=window,
                                         scale=scale)
    kw = dict(score_dtype=score_dtype, gqa_grouped=gqa_grouped)
    if window is not None and impl != "naive":
        if s > window:
            return _windowed(q, k, v, window, min(chunk, s), scale, unroll,
                             **kw)
        # window covers everything: plain causal
        window = None
    if impl == "naive" or s <= chunk:
        return _finalize(
            _piece(q, k, v, scale=scale, causal=True, window=window, **kw),
            q.dtype)
    if impl == "chunked":
        return _chunked(q, k, v, chunk, scale, **kw)
    if impl == "recursive":
        return _recursive(q, k, v, chunk, scale, **kw)
    raise ValueError(f"unknown attention impl {impl!r}")


def _map(fn, args, unroll: bool):
    """lax.map, or a python loop when ``unroll`` (cost-visible HLO)."""
    if not unroll:
        return jax.lax.map(fn, args)
    n = args[0].shape[0]
    outs = [fn(tuple(a[i] for a in args)) for i in range(n)]
    return jnp.stack(outs)


def _chunked(q, k, v, chunk, scale, **kw):
    """Query chunks, each against the keys up to its own end (a static
    slice): ~S^2/2 + S*C/2 FLOPs.  Each chunk is recomputed in the
    backward pass, so neither pass holds more than one chunk's scores."""
    s = q.shape[1]
    outs = []
    for lo in range(0, s, chunk):
        hi = min(lo + chunk, s)

        def one(q_i, k_i, v_i, lo=lo):
            return _finalize(_piece(q_i, k_i, v_i, scale=scale, row0=lo,
                                    causal=True, **kw), q.dtype)

        outs.append(jax.checkpoint(one)(q[:, lo:hi], k[:, :hi], v[:, :hi]))
    return jnp.concatenate(outs, axis=1)


def _recursive(q, k, v, base, scale, **kw):
    """Recursive halving: FLOPs ~ S^2/2 + S*base, static shapes."""
    b, s, h, d = q.shape

    def rec(q_, k_, v_):
        bb, ss = q_.shape[0], q_.shape[1]
        if ss <= base:
            return _piece(q_, k_, v_, scale=scale, causal=True, **kw)
        half = ss // 2
        q1, q2 = q_[:, :half], q_[:, half:]
        k1, k2 = k_[:, :half], k_[:, half:]
        v1, v2 = v_[:, :half], v_[:, half:]
        # both halves recurse together as a doubled batch
        qs = jnp.concatenate([q1, q2], axis=0)
        ks = jnp.concatenate([k1, k2], axis=0)
        vs = jnp.concatenate([v1, v2], axis=0)
        acc, m, l = rec(qs, ks, vs)
        piece1 = (acc[:bb], m[:bb], l[:bb])
        piece2 = (acc[bb:], m[bb:], l[bb:])
        # upper-half queries also see the whole lower half — unmasked
        cross = _piece(q2, k1, v1, scale=scale, masked=False, **kw)
        acc2, m2, l2 = _merge(piece2, cross)
        return (jnp.concatenate([piece1[0], acc2], axis=1),
                jnp.concatenate([piece1[1], m2], axis=1),
                jnp.concatenate([piece1[2], l2], axis=1))

    # pad to a power-of-two multiple of base (computation padding);
    # padded KEY rows sit at positions >= s, masked by causality for all
    # real rows; padded QUERY rows are sliced off.
    target = base
    while target < s:
        target *= 2
    if target != s:
        pad = target - s
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = _finalize(rec(q, k, v), q.dtype)
    return out[:, :s]


def _windowed(q, k, v, window, chunk, scale, unroll=False, **kw):
    """Sliding window: each q chunk gathers a (window+chunk) KV slice.

    KV is left-padded by ``span`` so slices are fixed-size; masking uses
    absolute positions so the padding (col < 0) is excluded exactly."""
    b, s, h, d = q.shape
    pad_s = (-s) % chunk
    if pad_s:
        q = jnp.pad(q, ((0, 0), (0, pad_s), (0, 0), (0, 0)))
    n = q.shape[1] // chunk
    span = window + chunk
    kp = jnp.pad(k, ((0, 0), (span, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (span, 0), (0, 0), (0, 0)))
    qc = jnp.moveaxis(q.reshape(b, n, chunk, h, d), 1, 0)

    def one(args):
        i, q_i = args
        # original-coordinate slice [ (i+1)*chunk - span, (i+1)*chunk )
        lo = (i + 1) * chunk              # in padded coords (shift +span)
        k_i = jax.lax.dynamic_slice_in_dim(kp, lo, span, axis=1)
        v_i = jax.lax.dynamic_slice_in_dim(vp, lo, span, axis=1)
        piece = _piece(q_i, k_i, v_i, scale=scale,
                       row0=i * chunk, col0=(i + 1) * chunk - span,
                       causal=True, window=window, **kw)
        return _finalize(piece, q.dtype)

    out = _map(one, (jnp.arange(n), qc), unroll)
    return jnp.moveaxis(out, 0, 1).reshape(b, n * chunk, h, d)[:, :s]


# ---------------------------------------------------------------------------
# decode: one new token against a KV cache
# ---------------------------------------------------------------------------
def _grouped_scores(q, k, scale):
    """Scores of one query token q (B,1,H,D) against k (B,Sk,Hkv,D) at its
    stored heads: the H query heads are read as (Hkv, G) groups,
    G = H // Hkv, so no KV head is repeated (G = 1 is plain multi-head
    attention).  -> (B,Hkv,G,Sk) float32."""
    b, _, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, h // hkv, d)
    return jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                      preferred_element_type=jnp.float32) * scale


def _grouped_values(p, v):
    """Weights p (B,Hkv,G,Sk) against v (B,Sk,Hkv,D) -> (B,1,H,D) f32."""
    b, hkv, g, _ = p.shape
    acc = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc.reshape(b, 1, hkv * g, v.shape[-1])


def _key_mask(valid, b: int):
    """(Sk,) or (B,Sk) -> (B,1,1,Sk), to mask grouped scores."""
    return jnp.broadcast_to(valid, (b, valid.shape[-1]))[:, None, None, :]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length, *, scale: float | None = None,
                     new_kv=None) -> jax.Array:
    """q (B,1,H,D); caches (B,Sc,Hkv,D); ``length`` (B,) or scalar = number
    of valid cache entries.  For ring-buffer (windowed) caches the caller
    passes length = cache size once full.

    ``new_kv`` = (k, v, slot): the token being decoded, k/v (B,1,Hkv,D),
    which the caller writes to cache slot ``slot`` after this read.  The
    slot's old entry is left out and the token joins the softmax as one
    more key, so reading the cache does not wait on the write."""
    b, _, _, d = q.shape
    if scale is None:
        scale = d ** -0.5
    pos = jnp.arange(k_cache.shape[1])[None, :]
    valid = pos < jnp.asarray(length).reshape(-1, 1)
    if new_kv is not None:
        valid &= pos != new_kv[2]
    s = jnp.where(_key_mask(valid, b), _grouped_scores(q, k_cache, scale),
                  NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if new_kv is not None:
        s_new = _grouped_scores(q, new_kv[0], scale)
        m = jnp.maximum(m, s_new)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    if new_kv is not None:
        e_new = jnp.exp(s_new - m)
        l = l + e_new
    out = _grouped_values(e / l, v_cache)
    if new_kv is not None:
        out = out + _grouped_values(e_new / l, new_kv[1])
    return out.astype(q.dtype)


def _decode_piece(q, k, v, valid, scale):
    """Online-softmax piece of one query token against k/v (B,Sk,Hkv,D);
    ``valid`` (Sk,) or (B,Sk) masks the keys.  A fully-masked piece has
    m = NEG_INF and l = 0, so :func:`_merge` ignores it."""
    b, _, h, _ = q.shape
    mask = _key_mask(valid, b)
    s = jnp.where(mask, _grouped_scores(q, k, scale), NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return (_grouped_values(p, v), m.reshape(b, 1, h, 1),
            l.reshape(b, 1, h, 1))


def decode_attention_blocks(q: jax.Array, read_chunk, n_chunks: int,
                            chunk: int, length, *,
                            scale: float | None = None,
                            unroll: bool = False, new_kv=None) -> jax.Array:
    """Sequence-blocked decode attention (paged-attention-lite).

    ``read_chunk(i)`` returns the (k, v) block (B, C, Hkv, D) for chunk i
    — dequantisation happens per block, so the live working set is one
    block instead of the whole (possibly int8-packed) cache (the temp
    that blows HBM for 32k x batch-128 decode cells).  Pieces merge by
    online softmax; fully-masked chunks contribute l = 0.  ``new_kv`` as
    in :func:`decode_attention`, merged as a piece of its own.
    """
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5

    def piece_of(i):
        kk, vv = read_chunk(i)
        pos = i * chunk + jnp.arange(kk.shape[1])
        valid = pos < length
        if new_kv is not None:
            valid &= pos != new_kv[2]
        return _decode_piece(q, kk.astype(q.dtype), vv.astype(q.dtype),
                             valid, scale)

    if unroll:
        out = piece_of(0)
        for i in range(1, n_chunks):
            out = _merge(out, piece_of(jnp.asarray(i)))
    else:
        acc, m, l = jax.lax.map(piece_of, jnp.arange(n_chunks))
        out = (acc[0], m[0], l[0])
        for i in range(1, n_chunks):
            out = _merge(out, (acc[i], m[i], l[i]))
    if new_kv is not None:
        k, v, _ = new_kv
        out = _merge(out, _decode_piece(q, k.astype(q.dtype),
                                        v.astype(q.dtype),
                                        jnp.ones((1,), bool), scale))
    return _finalize(out, q.dtype)
