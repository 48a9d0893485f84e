"""Plain references and seeded data of the plan cells.

Nothing here imports the program.  Inputs and weights are made on the
device, each cell's in one jitted call, from the seed; the check makes
them again from the seed after the program is gone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.refs.qwen3 import fp8_round, seed_key



def rel_err(got, want) -> float:
    """max |got - want| / max |want|, in float32."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# -- PolyBench 3mm -----------------------------------------------------------
def mm3_shapes(cfg: dict) -> dict:
    ni, nj, nk, nl, nm = (cfg[k] for k in ("NI", "NJ", "NK", "NL", "NM"))
    return {"A": (ni, nk), "B": (nk, nj), "C": (nj, nm), "D": (nm, nl)}


def graph_inputs(cfg: dict, mix: dict, seed: int) -> list[dict]:
    """``mix["input_sets"]`` sets of 3mm inputs, float32 N(0, 1)."""
    shapes = sorted(mm3_shapes(cfg).items())
    n = mix["input_sets"]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, n * len(shapes)).reshape(
            n, len(shapes), -1)
        return [{name: jax.random.normal(keys[i, j], s, jnp.float32)
                 for j, (name, s) in enumerate(shapes)} for i in range(n)]

    return make(seed_key(seed))


def _dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _dot_bf16x3(a, b):
    """float32 product from three bfloat16 products with float32
    accumulation, ``hi.hi + hi.lo + lo.hi``: what ``precision=high``
    computes on a TPU, written out so that it computes the same on any
    backend.  The splits round with ``reduce_precision``, which the
    compiler keeps; a float32 -> bfloat16 -> float32 round trip may be
    folded away (excess precision), leaving ``lo`` zero and one pass."""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):
        # operands hold 8 significant bits, so every product is exact
        return _dot_highest(x, y)

    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


def _mm3_body(dot):
    def run(a, b, c, d):
        e = dot(a, b)
        f = dot(c, d)
        return {"E": e, "F": f, "G": dot(e, f)}
    return jax.jit(run)


_MM3 = {"highest": _mm3_body(_dot_highest), "high": _mm3_body(_dot_bf16x3)}


def mm3(ins: dict, precision: str = "highest") -> dict:
    """PolyBench 3mm: E = A.B, F = C.D, G = E.F, in float32 at
    ``precision=highest``, or with ``precision="high"`` three bfloat16
    passes per product (the control)."""
    return _MM3[precision](ins["A"], ins["B"], ins["C"], ins["D"])


# -- the SwiGLU FFN block ----------------------------------------------------
def swiglu_ffn(x, w1, w3, w2):
    """The block as served: ``silu(x.w1) * (x.w3)`` then ``.w2``, bf16
    operands, the activation in float32."""
    a = x @ w1
    g = x @ w3
    h = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype) * g
    return h @ w2


def _dot(x, w, quant):
    if quant == "fp8":
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return _dot_highest(x, w)


@jax.jit
def _ffn_f32(x, w1, w3, w2):
    return _ffn_body(x, w1, w3, w2, None)


@jax.jit
def _ffn_fp8(x, w1, w3, w2):
    return _ffn_body(x, w1, w3, w2, "fp8")


def _ffn_body(x, w1, w3, w2, quant):
    x, w1, w3, w2 = (jnp.asarray(v, jnp.float32) for v in (x, w1, w3, w2))
    h = jax.nn.silu(_dot(x, w1, quant)) * _dot(x, w3, quant)
    return _dot(h, w2, quant)


def swiglu_ffn_f32(x, w1, w3, w2, quant: str | None = None):
    """The same block in float32 at ``precision=highest`` (the reference),
    or with every product's operands rounded to float8 e4m3 (the
    control)."""
    fn = {None: _ffn_f32, "fp8": _ffn_fp8}[quant]
    return fn(x, w1, w3, w2)


def ffn_data(cfg: dict, mix: dict, seed: int):
    """Weights (w1, w3, w2) and ``mix["input_pool"]`` inputs x, bf16."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    t, n = cfg["tokens_per_request"], mix["input_pool"]

    @jax.jit
    def make(key):
        k = jax.random.split(key, 4)
        bf = jnp.bfloat16
        w1 = (jax.random.normal(k[0], (d, f)) * d ** -0.5).astype(bf)
        w3 = (jax.random.normal(k[1], (d, f)) * d ** -0.5).astype(bf)
        w2 = (jax.random.normal(k[2], (f, d)) * f ** -0.5).astype(bf)
        xs = jax.random.normal(k[3], (n, t, d)).astype(bf)
        return (w1, w3, w2), xs

    return make(seed_key(seed))
