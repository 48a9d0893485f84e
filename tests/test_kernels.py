"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes, dtypes and block sizes (assignment deliverable (c))."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kernel_impl
from repro.kernels.decode_attention import kernel as dec_kernel
from repro.kernels.decode_attention import ops as dec_ops
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
# decode attention over the stacked cache
# ---------------------------------------------------------------------------
DEC_C, DEC_SC, DEC_D = 8, 32, 128        # KV block, cache length, head width


def _decode_case(hkv, g, dtype, seed=30, b=2, layers=3):
    """q (B,1,H,D), stacked caches (L,B,Sc,Hkv*D), new k/v (B,1,Hkv*D)."""
    f = hkv * DEC_D
    return (_rand(seed, (b, 1, hkv * g, DEC_D), dtype),
            _rand(seed + 1, (layers, b, DEC_SC, f), dtype),
            _rand(seed + 2, (layers, b, DEC_SC, f), dtype),
            _rand(seed + 3, (b, 1, f), dtype),
            _rand(seed + 4, (b, 1, f), dtype))


def _decode_xla(q, kc, vc, at, length, slot, kn, vn):
    """The model's XLA decode attention on layer ``at``, heads split."""
    from repro.models import attention as attn_mod
    def heads(x):
        return x.reshape(x.shape[0], x.shape[1], -1, DEC_D)

    return attn_mod.decode_attention(
        q, heads(kc[at]), heads(vc[at]), length,
        new_kv=(heads(kn), heads(vn), slot))


def _poisoned(c, length):
    """NaN in every cache position at or past ``length``."""
    pos = jnp.arange(c.shape[2])[None, None, :, None]
    return jnp.where(pos >= length, jnp.nan, c)


@pytest.mark.parametrize("slot_at", ["edge", "inside"])
@pytest.mark.parametrize("length", [1, DEC_C - 1, DEC_C, DEC_C + 1, DEC_SC])
@pytest.mark.parametrize("hkv,g", [(2, 1), (2, 2), (2, 5), (1, 8), (1, 4)],
                         ids=["g1", "g2", "g5", "g8", "mqa"])
def test_decode_attention_kernel_matches_xla(hkv, g, length, slot_at):
    """The kernel over the blocks below the length equals the XLA decode
    attention over the whole layer, for GQA groups and MQA, at lengths
    around a block edge and the full ring, with the new token's slot at
    the edge of the valid range or inside it.  Positions at or past the
    length hold NaN for the kernel: they never reach its output."""
    q, kc, vc, kn, vn = _decode_case(hkv, g, jnp.float32)
    at = 1
    slot = length - 1 if slot_at == "edge" else (length - 1) // 2
    ref = _decode_xla(q, kc, vc, at, length, slot, kn, vn)
    out = dec_ops.decode_attention(
        q, _poisoned(kc, length), _poisoned(vc, length), at, length, slot,
        kn, vn, block=DEC_C, impl="pallas_interpret")
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel_never_reads_past_length(dtype):
    """The poison case: NaN at and past a length that ends inside a block,
    one sequence per block, so the grid walks two sequence blocks; the
    output is finite and equals the clean cache's through the XLA path."""
    q, kc, vc, kn, vn = _decode_case(2, 2, dtype, seed=40)
    at, length, slot = 2, 2 * DEC_C + 3, 2 * DEC_C + 2
    ref = _decode_xla(q, kc, vc, at, length, slot, kn, vn)
    b, _, h, d = q.shape
    out = dec_kernel.decode_attention(
        q.reshape(b, 2, 2, d), _poisoned(kc, length), _poisoned(vc, length),
        kn, vn, jnp.array([at, length, slot], jnp.int32), block=DEC_C,
        seqs=1, scale=d ** -0.5, interpret=True).reshape(b, 1, h, d)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_block_counts():
    """Blocks of 128 where they divide the cache, a divisor that keeps the
    (8, 128) tiling where not, and no kernel where none does, nor for
    int8 caches, 64-wide heads, a block whose one sequence outgrows a grid
    step's budget, or the ``xla`` implementation; the count of blocks read
    and skipped is closed-form."""
    bf = jnp.bfloat16
    assert dec_ops.block_size(768) == 128
    assert dec_ops.block_size(4224) == 128
    assert dec_ops.block_size(24) == 8
    assert dec_ops.block_size(100) is None
    assert dec_ops.block_size(2051) is None
    with kernel_impl("pallas"):
        assert dec_ops.serves(bf, 128, 8, 768) == 128
        assert dec_ops.serves(jnp.float32, 256, 1, 4096) == 128
        assert dec_ops.serves(bf, 128, 8, 2051) is None
        assert dec_ops.serves(jnp.int8, 128, 8, 768) is None
        assert dec_ops.serves(bf, 64, 8, 768) is None
        assert dec_ops.serves(jnp.float32, 128, 64, 768) is None
    with kernel_impl("xla"):
        assert dec_ops.serves(bf, 128, 8, 768) is None
    assert dec_ops.seq_block(32, 128, 1024 * 2) == 8
    assert dec_ops.seq_block(6, 128, 1024 * 2) == 6
    lengths = np.array([1, 128, 129, 768, 900])
    read, skipped = dec_ops.kv_blocks(768, lengths, 128)
    assert (read, skipped) == (1 + 1 + 2 + 6 + 6, 5 + 5 + 4 + 0 + 0)
    assert dec_ops.kv_blocks(2051, lengths, None) == (5 * 17, 0)


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
