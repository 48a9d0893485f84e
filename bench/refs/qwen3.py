"""Plain reference of a Qwen3 decoder (HF ``Qwen3ForCausalLM``).

Straight ``jax.numpy`` in float32 at ``precision=highest``: no kernels,
no cache, no batching, one sequence at a time.  It follows the published
model: pre-norm RMSNorm, q/k RMSNorm over the head dimension, rotate-half
RoPE, grouped-query causal attention, a SwiGLU MLP (``down(silu(gate(x))
* up(x))``) and, with ``tie_word_embeddings``, logits against the
embedding.  It imports nothing of the program under test.

The weights are made here from the seed (``make_weights``), in the
published layout and dtype.  Norm gains are held as ``w`` with gain
``1 + w``, which is the published gain ``g`` written as an offset; the
reference multiplies by ``1 + w`` in float32.

``quant="fp8"`` is the control: every linear layer's operands are rounded
to float8 e4m3 (activations scaled per row, weights per column, to the
format's range) before the product, the step below the bfloat16 the
configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                  # largest finite float8_e4m3fn


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed up to 64 bits (JAX keys take 32)."""
    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def shapes(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    n = cfg["num_hidden_layers"]
    return {
        "embed": (cfg["vocab_size"], d),
        "attn_norm": (n, d), "q": (n, d, hq * hd), "k": (n, d, hkv * hd),
        "v": (n, d, hkv * hd), "o": (n, hq * hd, d),
        "q_norm": (n, hd), "k_norm": (n, hd), "mlp_norm": (n, d),
        "gate": (n, d, f), "up": (n, d, f), "down": (n, f, d),
        "final_norm": (d,),
    }


def _scale(name: str, shape: tuple) -> float:
    if name == "embed":
        return 0.02
    if name.endswith("norm"):
        return 0.1                       # offset of the gain from 1
    return float(shape[-2]) ** -0.5      # linear: 1 / sqrt(fan_in)


def weights_body(cfg: dict):
    """``key -> weights`` for ``cfg`` (bfloat16), to be jitted whole."""
    shp = sorted(shapes(cfg).items())

    def make(key):
        keys = jax.random.split(key, len(shp))
        return {name: (jax.random.normal(k, s, jnp.float32)
                       * _scale(name, s)).astype(jnp.bfloat16)
                for k, (name, s) in zip(keys, shp)}

    return make


def make_weights(cfg: dict, seed: int) -> dict:
    return jax.jit(weights_body(cfg))(seed_key(seed))


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (S, H, D), positions 0..S-1, HF rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits(w: dict, tokens: jax.Array, read: jax.Array, cfg_items: tuple,
            quant: str | None) -> jax.Array:
    cfg = dict(cfg_items)
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    s = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    layers = {k: w[k] for k in ("attn_norm", "q", "k", "v", "o", "q_norm",
                                "k_norm", "mlp_norm", "gate", "up", "down")}

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _linear(h, lw["q"], quant).reshape(s, hq, hd)
        k = _linear(h, lw["k"], quant).reshape(s, hkv, hd)
        v = _linear(h, lw["v"], quant).reshape(s, hkv, hd)
        q = _rope(_rms(q, lw["q_norm"], eps), theta)
        k = _rope(_rms(k, lw["k_norm"], eps), theta)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _linear(o.reshape(s, hq * hd), lw["o"], quant)
        h = _rms(x, lw["mlp_norm"], eps)
        g = jax.nn.silu(_linear(h, lw["gate"], quant))
        x = x + _linear(g * _linear(h, lw["up"], quant), lw["down"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, layers)
    h = _rms(x[read], w["final_norm"], eps)
    if not cfg["tie_word_embeddings"]:
        raise ValueError("only tied embeddings are described here")
    return _linear(h, w["embed"].T, quant)


def logits(w: dict, cfg: dict, tokens, read, quant: str | None = None):
    """Logits (len(read), vocab) float32 at positions ``read`` of the
    sequence ``tokens`` (S,).  Positions after the last one read do not
    change them (causal), so callers may pad ``tokens`` to one length."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    return _logits(w, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(read, jnp.int32), items, quant)


def served_gaps(ref: jax.Array, served) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position: ``max(ref) - ref[token]``, >= 0."""
    served = jnp.asarray(served, jnp.int32)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - got)
