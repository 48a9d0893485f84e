"""Plain reference of a Qwen2 decoder's training loss and its gradients
(HF ``Qwen2ForCausalLM``, the architecture of Qwen1.5).

Straight ``jax.numpy`` in float32 at ``precision=highest``: no kernels, no
sharding, one sequence at a time and one layer at a time.  It follows the
published model: pre-norm RMSNorm, q/k/v projections with bias and an
output projection without, rotate-half RoPE, grouped-query causal
attention, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``) and an untied
output head.  The loss is the mean next-token cross-entropy over the
configuration's vocabulary.  It imports nothing of the program under test.

The weights are made from the seed one tensor at a time
(``weight``): each tensor's key is one ``fold_in`` of the seed's key by
the CRC-32 of its name (``layers.<i>.<name>``, ``embed``, ``final_norm``,
``lm_head``), in float32.  Linear weights are N(0, 1/fan_in), the
embedding and the q/k/v biases N(0, 0.02^2), and every RMSNorm gain is 1
(held as an offset ``w`` of 0 with gain ``1 + w``, as the published gain
``g`` is ``1 + (g - 1)``).

``loss_and_grads`` runs the forward pass layer by layer, keeping only each
layer's input, then the backward pass layer by layer with ``jax.vjp``,
regenerating each layer's weights as it goes; attention is computed one
kv head group at a time and recomputed for its gradient.  It returns the
gradient at the entries asked for and the norm of the whole gradient
(which sets the step's clip factor).  The sequences of a batch are spread
over the devices given, one block of them on each (every device makes its
own copy of the weights), so they run side by side.

``adamw_first_step`` is AdamW's first step from zero moments, entry by
entry: decoupled weight decay on matrices only, the learning rate at the
first step of a linear warm-up (``first_lr``).

``quant="fp8"`` is the control: every linear layer's operands are rounded
to float8 e4m3 in the forward pass (scaled per row and per column, as in
``bench.refs.qwen3``), with the rounding passed straight through in the
backward pass: the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.refs.qwen3 import HIGHEST, fp8_round, seed_key

LAYER = ("attn_norm", "q", "q_bias", "k", "k_bias", "v", "v_bias", "o",
         "mlp_norm", "gate", "up", "down")


def dims(cfg: dict) -> tuple[int, int, int, int, int, int, int]:
    d, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d, cfg["intermediate_size"], hq, cfg["num_key_value_heads"],
            d // hq, cfg["num_hidden_layers"], cfg["vocab_size"])


def layer_shapes(cfg: dict) -> dict:
    d, f, hq, hkv, hd, _, _ = dims(cfg)
    return {"attn_norm": (d,), "q": (d, hq * hd), "q_bias": (hq * hd,),
            "k": (d, hkv * hd), "k_bias": (hkv * hd,), "v": (d, hkv * hd),
            "v_bias": (hkv * hd,), "o": (hq * hd, d), "mlp_norm": (d,),
            "gate": (d, f), "up": (d, f), "down": (f, d)}


def shapes(cfg: dict) -> dict:
    """Every tensor's name and shape."""
    d, _, _, _, _, n, v = dims(cfg)
    out = {"embed": (v, d), "final_norm": (d,), "lm_head": (d, v)}
    for i in range(n):
        out.update({f"layers.{i}.{k}": s
                    for k, s in layer_shapes(cfg).items()})
    return out


def _std(name: str, shape: tuple) -> float:
    base = name.rsplit(".", 1)[-1]
    if base.endswith("norm"):
        return 0.0
    if base in ("embed",) or base.endswith("bias"):
        return 0.02
    return float(shape[0]) ** -0.5       # linear: 1 / sqrt(fan_in)


def _crc(name: str) -> np.uint32:
    return np.uint32(zlib.crc32(name.encode()))


def _normal(key, crc, shape: tuple, std: float) -> jax.Array:
    return jax.random.normal(jax.random.fold_in(key, crc), shape,
                             jnp.float32) * std


def weight(key: jax.Array, name: str, shape: tuple) -> jax.Array:
    """The seeded tensor ``name`` (float32); ``key`` is the seed's key."""
    return _normal(key, _crc(name), shape, _std(name, shape))


_weight = jax.jit(weight, static_argnums=(1, 2))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(key, crcs, cfg_items):
    return {base: _normal(key, crcs[base], shape, _std(base, shape))
            for base, shape in layer_shapes(dict(cfg_items)).items()}


def layer_weights(key: jax.Array, cfg_items: tuple, i: int) -> dict:
    """Layer ``i``'s tensors, by their names without the layer prefix."""
    return _layer_weights(key, {b: _crc(f"layers.{i}.{b}") for b in LAYER},
                          cfg_items)


# ---------------------------------------------------------------------------
def _round(x: jax.Array, axis: int, quant: str | None) -> jax.Array:
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    return x + jax.lax.stop_gradient(fp8_round(x, axis) - x)


def _linear(x, w, quant, b=None):
    y = jnp.dot(_round(x, -1, quant), _round(w, 0, quant),
                precision=HIGHEST)
    return y if b is None else y + b


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1, HF rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@jax.checkpoint
def _group_attention(q, k, v):
    """One kv head and its query heads: q (G, S, D), k/v (S, D)."""
    s, d = k.shape
    sc = jnp.einsum("gqd,kd->gqk", q, k, precision=HIGHEST) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=HIGHEST)


def _layer(w: dict, x: jax.Array, cfg_items: tuple, quant):
    cfg = dict(cfg_items)
    _, _, hq, hkv, hd, _, _ = dims(cfg)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], eps)
    q = _linear(h, w["q"], quant, w["q_bias"]).reshape(s, hkv, hq // hkv, hd)
    k = _linear(h, w["k"], quant, w["k_bias"]).reshape(s, hkv, hd)
    v = _linear(h, w["v"], quant, w["v_bias"]).reshape(s, hkv, hd)
    q = _rope(q.reshape(s, hq, hd), theta).reshape(s, hkv, hq // hkv, hd)
    k = _rope(k, theta)
    o = jax.lax.map(lambda a: _group_attention(*a),
                    (jnp.transpose(q, (1, 2, 0, 3)),
                     jnp.transpose(k, (1, 0, 2)),
                     jnp.transpose(v, (1, 0, 2))))        # (Hkv, G, S, D)
    o = jnp.transpose(o, (2, 0, 1, 3)).reshape(s, hq * hd)
    x = x + _linear(o, w["o"], quant)
    h = _rms(x, w["mlp_norm"], eps)
    g = jax.nn.silu(_linear(h, w["gate"], quant))
    return x + _linear(g * _linear(h, w["up"], quant), w["down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_fwd(w, x, cfg_items, quant):
    return jax.vmap(lambda x_: _layer(w, x_, cfg_items, quant))(x)


def _at(g, idx):
    return g[tuple(idx.T)]


def _sq(g):
    return jnp.sum(jnp.square(g))


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_bwd(w, x, gy, idx, cfg_items, quant):
    """Each sequence's backward pass through the layer: the weights'
    gradient summed over the sequences, at ``idx`` and its sum of squares,
    and the input's gradient."""
    def one(x_, gy_):
        _, vjp = jax.vjp(lambda w_, xx: _layer(w_, xx, cfg_items, quant),
                         w, x_)
        return vjp(gy_)

    gw, gx = jax.vmap(one)(x, gy)
    gw = {b: jnp.sum(g, axis=0) for b, g in gw.items()}
    return ({b: _at(g, idx[b]) for b, g in gw.items()},
            sum(_sq(g) for g in gw.values()), gx)


def _head(x, final_norm, lm_head, labels, eps, quant):
    h = _rms(x, final_norm, eps)
    logits = _linear(h, lm_head, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_grad(x, final_norm, lm_head, labels, idx, scale, eps, quant):
    """Summed next-token NLL of every sequence, the gradient of ``scale``
    times it with respect to the head's input, and the head's weights'
    gradient at ``idx`` and its sum of squares."""
    def one(x_, lab_):
        nll, vjp = jax.vjp(lambda *a: _head(*a, lab_, eps, quant), x_,
                           final_norm, lm_head)
        return (nll,) + vjp(scale)

    nll, gx, g_norm, g_head = jax.vmap(one)(x, labels)
    g_norm, g_head = jnp.sum(g_norm, axis=0), jnp.sum(g_head, axis=0)
    return jnp.sum(nll), gx, {"final_norm": _at(g_norm, idx["final_norm"]),
                              "lm_head": _at(g_head, idx["lm_head"])}, \
        _sq(g_norm) + _sq(g_head)


@functools.partial(jax.jit, static_argnames=("shape",))
def _embed(key, tokens, shape):
    return weight(key, "embed", shape)[tokens]


@functools.partial(jax.jit, static_argnames=("rows",))
def _embed_grad(gx, tokens, idx, rows):
    """The embedding's gradient (each row the sum of ``gx`` (B, S, d) over
    the positions holding its token) at ``idx`` (k, 2), and its sum of
    squares."""
    g = jax.ops.segment_sum(gx.reshape(-1, gx.shape[-1]),
                            tokens.reshape(-1), num_segments=rows)
    return _at(g, idx), _sq(g)


def loss_and_grads(cfg: dict, seed: int, tokens, labels, entries: dict,
                   quant: str | None = None, devices=None):
    """Mean cross-entropy of ``labels`` given ``tokens`` ((B, S) each),
    its gradient at ``entries`` ({tensor name: (k, ndim) indices}) and the
    whole gradient's norm.  Returns (loss, {name: (k,) float64}, norm)."""
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, bool, str))))
    d, _, _, _, _, n, v = dims(cfg)
    eps = cfg["rms_norm_eps"]
    rows, s = np.shape(tokens)
    devices = list(devices or jax.devices())
    mesh = Mesh(np.asarray(devices[:math.gcd(rows, len(devices))]),
                ("rows",))
    same = NamedSharding(mesh, P())
    by_row = NamedSharding(mesh, P("rows"))
    key = jax.device_put(seed_key(seed), same)
    tok = jax.device_put(np.asarray(tokens, np.int32), by_row)
    lab = jax.device_put(np.asarray(labels, np.int32), by_row)
    idx = jax.device_put({name: np.asarray(ix, np.int32)
                          for name, ix in entries.items()}, same)
    xs = [_embed(key, tok, (v, d))]
    for i in range(n):
        xs.append(_layer_fwd(layer_weights(key, cfg_items, i), xs[-1],
                             cfg_items, quant))
    head = {name: _weight(key, name, shapes(cfg)[name])
            for name in ("final_norm", "lm_head")}
    nll, gx, got, sq = _head_grad(xs[-1], head["final_norm"],
                                  head["lm_head"], lab,
                                  {k: idx[k] for k in head},
                                  jnp.float32(1.0 / (rows * s)), eps, quant)
    sqs = [sq]
    for i in reversed(range(n)):
        gw, sq, gx = _layer_bwd(layer_weights(key, cfg_items, i), xs[i], gx,
                                {b: idx[f"layers.{i}.{b}"] for b in LAYER},
                                cfg_items, quant)
        got.update({f"layers.{i}.{b}": g for b, g in gw.items()})
        sqs.append(sq)
    got["embed"], sq = _embed_grad(gx, tok, idx["embed"], v)
    sqs.append(sq)
    norm = math.sqrt(sum(float(x) for x in sqs))
    return float(nll) / (rows * s), {
        name: np.asarray(got[name], np.float64) for name in entries}, norm


def first_lr(opt: dict) -> float:
    """The learning rate at the first step of a linear warm-up."""
    return opt["lr"] * min(1.0, 1.0 / max(opt["warmup_steps"], 1))


def adamw_first_step(opt: dict, g: np.ndarray, p0: np.ndarray,
                     decays: bool) -> tuple[np.ndarray, np.ndarray]:
    """AdamW's first step from zero moments at the (clipped) gradient
    ``g`` of entries whose value is ``p0``: returns (v, the entries'
    change).  ``opt`` holds ``lr``, ``warmup_steps``, ``b1``, ``b2``,
    ``eps`` and ``weight_decay``; ``decays`` is whether the tensor is a
    matrix (norm gains and biases are not decayed)."""
    lr, b1, b2 = first_lr(opt), opt["b1"], opt["b2"]
    m, v = (1 - b1) * g, (1 - b2) * g * g
    m_hat, v_hat = m / (1 - b1), v / (1 - b2)
    wd = opt["weight_decay"] if decays else 0.0
    return v, -lr * (m_hat / (np.sqrt(v_hat) + opt["eps"]) + wd * p0)
