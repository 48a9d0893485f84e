"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes, dtypes and block sizes (assignment deliverable (c))."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kernel_impl
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.matmul import ops as mm_ops, ref as mm_ref
from repro.kernels.quant import ops as q_ops, ref as q_ref
from repro.kernels.rglru import ops as rg_ops, ref as rg_ref
from repro.kernels.rwkv6 import ops as wk_ops, ref as wk_ref


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale) \
        .astype(dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(32, 32, 32), (64, 96, 32), (128, 64, 96),
                                   (190, 210, 170),    # paper-style irregular
                                   (8, 256, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    x = _rand(0, (m, k), dtype)
    y = _rand(1, (k, n), dtype)
    ref = mm_ref.matmul(x, y)
    out = mm_ops.matmul(x, y, bm=32, bn=32, bk=32, impl="pallas_interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bm,bn,bk", [(16, 16, 16), (32, 64, 16),
                                      (64, 32, 128), (128, 128, 128)])
def test_matmul_block_shape_sweep(bm, bn, bk):
    """The solver's intra-tile choice must never change the function."""
    x = _rand(2, (96, 160))
    y = _rand(3, (160, 224))
    ref = np.asarray(x, np.float32) @ np.asarray(y, np.float32)
    out = mm_ops.matmul(x, y, bm=bm, bn=bn, bk=bk, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_matmul_padding_exactness():
    """Computation padding (zero rows/cols) must be exact for matmul."""
    x = _rand(4, (37, 53))
    y = _rand(5, (53, 41))
    out = mm_ops.matmul(x, y, bm=32, bn=32, bk=32, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x) @ np.asarray(y),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("s,h,hkv,d", [(128, 4, 4, 32), (256, 4, 2, 32),
                                       (128, 8, 1, 64)])
def test_flash_attention_causal_gqa(s, h, hkv, d):
    b = 2
    q = _rand(10, (b, s, h, d))
    k = _rand(11, (b, s, hkv, d))
    v = _rand(12, (b, s, hkv, d))
    ref = fa_ops.flash_attention(q, k, v, causal=True, impl="xla")
    out = fa_ops.flash_attention(q, k, v, causal=True, bq=64, bk=64,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [32, 64, 100])
def test_flash_attention_sliding_window(window):
    b, s, h, d = 1, 256, 2, 32
    q = _rand(13, (b, s, h, d))
    k = _rand(14, (b, s, h, d))
    v = _rand(15, (b, s, h, d))
    ref = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                 impl="xla")
    out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                 bq=64, bk=64, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_unpadded_seq():
    """Sequence padding inside ops.flash_attention is mask-exact."""
    b, s, h, d = 1, 100, 2, 32          # 100 % 64 != 0
    q = _rand(16, (b, s, h, d))
    k = _rand(17, (b, s, h, d))
    v = _rand(18, (b, s, h, d))
    ref = fa_ops.flash_attention(q, k, v, impl="xla")
    out = fa_ops.flash_attention(q, k, v, bq=64, bk=64,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_bf16():
    b, s, h, d = 1, 128, 2, 32
    q = _rand(19, (b, s, h, d), jnp.bfloat16)
    k = _rand(20, (b, s, h, d), jnp.bfloat16)
    v = _rand(21, (b, s, h, d), jnp.bfloat16)
    ref = fa_ops.flash_attention(q, k, v, impl="xla")
    out = fa_ops.flash_attention(q, k, v, bq=64, bk=64,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


# ---------------------------------------------------------------------------
# rglru
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,s,d,bs", [(2, 64, 16, 16), (1, 128, 32, 64),
                                      (3, 100, 8, 32)])   # 100 % 32 != 0
def test_rglru_matches_scan(b, s, d, bs):
    a = jax.nn.sigmoid(_rand(30, (b, s, d)))       # decay in (0,1)
    u = _rand(31, (b, s, d), scale=0.5)
    ref = rg_ref.rglru(a, u)
    out = rg_ops.rglru(a, u, bs=bs, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_rglru_state_carries_across_blocks():
    """Splitting the sequence into blocks must not reset the recurrence."""
    b, s, d = 1, 64, 8
    a = jnp.full((b, s, d), 0.9)
    u = jnp.ones((b, s, d))
    full = rg_ops.rglru(a, u, bs=64, impl="pallas_interpret")
    blocked = rg_ops.rglru(a, u, bs=16, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(full), np.asarray(blocked),
                               rtol=1e-6, atol=1e-6)
    # analytic fixed point: h_inf = 1 / (1 - 0.9) = 10
    assert np.asarray(full)[0, -1, 0] == pytest.approx(10.0, rel=1e-2)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bh,s,dk,dv,bs", [(2, 64, 16, 16, 32),
                                           (4, 96, 8, 24, 32),
                                           (1, 50, 16, 16, 16)])
def test_rwkv6_matches_scan(bh, s, dk, dv, bs):
    r = _rand(40, (bh, s, dk), scale=0.5)
    k = _rand(41, (bh, s, dk), scale=0.5)
    v = _rand(42, (bh, s, dv), scale=0.5)
    w = jax.nn.sigmoid(_rand(43, (bh, s, dk)))     # decay in (0,1)
    u = _rand(44, (bh, dk), scale=0.5)
    ref = wk_ref.rwkv6(r, k, v, w, u)
    out = wk_ops.rwkv6(r, k, v, w, u, bs=bs, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_rwkv6_final_state_matches():
    bh, s, dk, dv = 2, 64, 8, 8
    r = _rand(45, (bh, s, dk), scale=0.5)
    k = _rand(46, (bh, s, dk), scale=0.5)
    v = _rand(47, (bh, s, dv), scale=0.5)
    w = jax.nn.sigmoid(_rand(48, (bh, s, dk)))
    u = _rand(49, (bh, dk), scale=0.5)
    _, st_ref = wk_ref.rwkv6(r, k, v, w, u, return_state=True)
    _, st_out = wk_ops.rwkv6(r, k, v, w, u, bs=32, impl="pallas_interpret",
                             return_state=True)
    np.testing.assert_allclose(np.asarray(st_out), np.asarray(st_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# quant
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,d", [(64, 32), (100, 16), (256, 128)])
def test_quant_roundtrip(n, d):
    x = _rand(50, (n, d), scale=3.0)
    q, s = q_ops.quantize(x, bn=32, impl="pallas_interpret")
    qr, sr = q_ref.quantize(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    deq = q_ops.dequantize(q, s)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    # quantization error bounded by scale/2 per element
    bound = np.asarray(s) * 0.5 + 1e-6
    assert (err <= bound).all()


def test_quant_int8_range():
    x = _rand(51, (32, 32), scale=100.0)
    q, _ = q_ops.quantize(x, impl="pallas_interpret")
    assert np.asarray(q).min() >= -127 and np.asarray(q).max() <= 127


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------
def test_dispatch_context_controls_impl():
    from repro.kernels import current_impl
    with kernel_impl("pallas_interpret"):
        assert current_impl() == "pallas_interpret"
        with kernel_impl("xla"):
            assert current_impl() == "xla"
        assert current_impl() == "pallas_interpret"


def test_dispatch_rejects_bad_impl():
    with pytest.raises(ValueError):
        with kernel_impl("cuda"):
            pass


# ---------------------------------------------------------------------------
# contraction precision
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype, highest", [(jnp.float32, True),
                                            (jnp.bfloat16, False)])
def test_contraction_precision_follows_operand_dtype(dtype, highest):
    """An f32 contraction asks for HIGHEST: the TPU's default multiplies
    f32 in one bf16 pass.  bf16 operands keep the one-pass default."""
    from repro.kernels.contraction.ref import combine_terms
    a, b = jnp.ones((8, 16), dtype), jnp.ones((16, 4), dtype)
    jaxpr = jax.make_jaxpr(lambda a, b: combine_terms(
        ["ik", "kj"], "ij", "mul", [a, b], (8, 4)))(a, b)
    assert "dot_general" in str(jaxpr)
    assert ("Precision.HIGHEST" in str(jaxpr)) == highest
