"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, st
from repro.kernels import ops, ref

SHAPES = [(8, 16, 8), (32, 64, 16), (40, 100, 30), (128, 256, 64)]
BLOCKS = [(8, 8, 16), (16, 16, 32)]


@pytest.mark.parametrize("shape", SHAPES)
def test_interval_matmul_matches_ref(shape):
    M, K, N = shape
    rng = np.random.RandomState(M + K)
    x = rng.randn(M, K).astype(np.float32)
    r = np.abs(rng.randn(M, K)).astype(np.float32) * 0.01
    w = rng.randn(K, N).astype(np.float32)
    lo, hi = x - r, x + r
    klo, khi, kmag = ops.interval_matmul_rigorous(
        lo, hi, w, block_m=16, block_n=16, block_k=32)
    rlo, rhi, rmag = ref.interval_matmul_ref(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(w))
    scale = np.abs(np.asarray(rmag)).max() + 1
    # kernel applies the rigorous gamma-slop widening (grows with K);
    # the ref uses a fixed 1e-6 slop — allow for the difference
    tol = (ref.gamma_in_u(2 * K + 2, 2.0 ** -23) * 2.0 ** -23 + 1e-5) * scale
    assert np.allclose(klo, rlo, atol=tol)
    assert np.allclose(khi, rhi, atol=tol)
    assert np.allclose(kmag, rmag, rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_interval_matmul_enclosure(shape):
    M, K, N = shape
    rng = np.random.RandomState(K)
    x = rng.randn(M, K).astype(np.float32)
    r = np.abs(rng.randn(M, K)).astype(np.float32) * 0.05
    w = rng.randn(K, N).astype(np.float32)
    klo, khi, _ = ops.interval_matmul_rigorous(
        x - r, x + r, w, block_m=16, block_n=16, block_k=32)
    for _ in range(5):
        xs = x - r + 2 * r * rng.rand(M, K).astype(np.float32)
        y = xs.astype(np.float64) @ w.astype(np.float64)
        assert bool(np.all(y >= np.asarray(klo) - 1e-9))
        assert bool(np.all(y <= np.asarray(khi) + 1e-9))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("g", [0.5, 3.0])
def test_caa_matmul_matches_ref(shape, g):
    M, K, N = shape
    rng = np.random.RandomState(N)
    x = rng.randn(M, K).astype(np.float32)
    d = np.abs(rng.randn(M, K)).astype(np.float32)
    w = rng.randn(K, N).astype(np.float32)
    val, err = ops.caa_matmul_fused(x, d, w, g=g, block_m=16, block_n=16,
                                    block_k=32)
    rval, rerr = ref.caa_matmul_ref(jnp.asarray(x), jnp.asarray(d),
                                    jnp.asarray(w), g)
    assert np.allclose(val, rval, rtol=1e-4, atol=1e-4)
    assert np.allclose(err, rerr, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("k", [4, 8, 11, 16])
def test_quant_matmul_matches_ref(shape, k):
    M, K, N = shape
    rng = np.random.RandomState(k)
    x = rng.randn(M, K).astype(np.float32)
    w = rng.randn(K, N).astype(np.float32)
    out = ops.quant_matmul_emulated(x, w, k=k, block_m=16, block_n=16,
                                    block_k=32)
    rout = ref.quant_matmul_ref(jnp.asarray(x), jnp.asarray(w), k)
    # accumulation-order differences are bounded by γ_K at f32 precision,
    # then quantisation can flip one k-bit ulp
    tol = max(2.0 ** (1 - k), 1e-5) * (np.abs(np.asarray(rout)).max() + 1)
    assert np.allclose(out, rout, atol=tol)


def test_quant_matmul_inputs_already_quantized_exact():
    """With operands already on the k-bit grid and tiny K, result is exact."""
    k = 8
    from repro.core import quantize, formats
    rng = np.random.RandomState(0)
    x = np.asarray(quantize.quantize(rng.randn(16, 16).astype(np.float32), k))
    w = np.asarray(quantize.quantize(rng.randn(16, 16).astype(np.float32), k))
    out = ops.quant_matmul_emulated(x, w, k=k, block_m=16, block_n=16,
                                    block_k=16)
    rout = ref.quant_matmul_ref(jnp.asarray(x), jnp.asarray(w), k)
    assert bool(jnp.array_equal(out, rout))


@pytest.mark.parametrize("k", [3, 8, 11, 16, 24])
def test_quant_matmul_dynamic_k_matches_ref_bitwise(k):
    """The scalar-k-as-argument GEMM is bitwise the static-k reference: same
    operand rounding, same f32 accumulation, same output rounding — only the
    dropped-bit count is data instead of Python."""
    from repro.kernels.quant_matmul import quant_matmul_dynamic_k
    rng = np.random.RandomState(k)
    x = jnp.asarray(rng.randn(24, 40).astype(np.float32))
    w = jnp.asarray(rng.randn(40, 16).astype(np.float32))
    out = quant_matmul_dynamic_k(x, w, jnp.asarray(k, jnp.int32))
    assert bool(jnp.array_equal(out, ref.quant_matmul_ref(x, w, k)))


def test_quant_matmul_dynamic_k_single_compile_over_grid():
    """One jit compilation serves the whole k grid — the per-k-recompile
    elimination the probe ladder and mixed serving rely on."""
    from repro.kernels.quant_matmul import quant_matmul_dynamic_k
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    w = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    f = jax.jit(quant_matmul_dynamic_k)
    for k in (24, 16, 11, 8, 5, 3):
        got = f(x, w, jnp.asarray(k, jnp.int32))
        assert bool(jnp.array_equal(got, ref.quant_matmul_ref(x, w, k)))
    assert f._cache_size() == 1


@given(st.integers(min_value=2, max_value=24),
       st.integers(min_value=0, max_value=10 ** 6))
def test_property_quant_matmul_dynamic_k_differential(k, seed):
    from repro.kernels.quant_matmul import quant_matmul_dynamic_k
    rng = np.random.RandomState(seed % 2 ** 31)
    x = jnp.asarray((rng.randn(8, 12) * 10.0 ** rng.randint(-3, 4))
                    .astype(np.float32))
    w = jnp.asarray(rng.randn(12, 6).astype(np.float32))
    out = quant_matmul_dynamic_k(x, w, jnp.asarray(k, jnp.int32))
    assert bool(jnp.array_equal(out, ref.quant_matmul_ref(x, w, k),
                                equal_nan=True))


def test_padding_path():
    """Non-tile-aligned shapes go through the zero-padding wrapper."""
    rng = np.random.RandomState(5)
    x = rng.randn(7, 13).astype(np.float32)
    d = np.abs(rng.randn(7, 13)).astype(np.float32)
    w = rng.randn(13, 9).astype(np.float32)
    val, err = ops.caa_matmul_fused(x, d, w, g=1.0, block_m=8, block_n=8,
                                    block_k=8)
    rval, rerr = ref.caa_matmul_ref(jnp.asarray(x), jnp.asarray(d),
                                    jnp.asarray(w), 1.0)
    assert np.allclose(val, rval, rtol=1e-4, atol=1e-5)
    assert np.allclose(err, rerr, rtol=1e-4, atol=1e-5)


def test_batched_inputs():
    rng = np.random.RandomState(6)
    x = rng.randn(2, 5, 32).astype(np.float32)
    w = rng.randn(32, 8).astype(np.float32)
    out = ops.quant_matmul_emulated(x, w, k=10, block_m=8, block_n=8,
                                    block_k=16)
    assert out.shape == (2, 5, 8)


@pytest.mark.parametrize("shape", [(2, 2, 4, 16, 64), (1, 8, 8, 32, 128),
                                   (3, 1, 4, 48, 512)])
def test_flash_decode_matches_ref(shape):
    from repro.kernels.flash_decode import flash_decode_attention
    B, K, G, S, D = shape[0], shape[1], shape[2], shape[4], shape[3]
    rng = np.random.RandomState(B + S)
    q = rng.randn(B, K, G, D).astype(np.float32)
    k = rng.randn(B, S, K, D).astype(np.float32)
    v = rng.randn(B, S, K, D).astype(np.float32)
    lengths = rng.randint(1, S + 1, size=(B,)).astype(np.int32)
    out = flash_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(lengths),
                                 block_s=16, interpret=True)
    ref_out = ref.flash_decode_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_full_length():
    from repro.kernels.flash_decode import flash_decode_attention
    rng = np.random.RandomState(0)
    B, K, G, S, D = 1, 2, 2, 64, 32
    q = rng.randn(B, K, G, D).astype(np.float32)
    k = rng.randn(B, S, K, D).astype(np.float32)
    v = rng.randn(B, S, K, D).astype(np.float32)
    lengths = np.full((B,), S, np.int32)
    out = flash_decode_attention(jnp.asarray(q), jnp.asarray(k),
                                 jnp.asarray(v), jnp.asarray(lengths),
                                 block_s=32, interpret=True)
    ref_out = ref.flash_decode_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-5, atol=2e-5)

@pytest.mark.parametrize("fmt", [(8, 15, -14), (4, 8, -6), (11, 30, -30)])
@pytest.mark.parametrize("lead", [(12,), (2, 5)])
@pytest.mark.parametrize("K", [40, 1536], ids=["one_k_block", "three"])
def test_quant_matmul_format_dispatch_bitwise(fmt, lead, K):
    """The serving dispatch (FormatQuantJOps.matmul) must be bitwise
    IDENTICAL through both of its arms: eager ref on CPU, the scalar-
    prefetch Pallas kernel on TPU (interpret mode here) — also when the
    contraction spans several format_block_k blocks, which the reference
    sums in the kernel's order. Batched leading dims flatten through the
    kernel and restore."""
    from repro.kernels.quant_matmul import (format_block_k,
                                            quant_matmul_format_dispatch,
                                            quant_matmul_format_ref)
    assert K // format_block_k(K) == (1 if K == 40 else 3)
    rng = np.random.RandomState(fmt[0] + len(lead))
    x = jnp.asarray(rng.randn(*lead, K).astype(np.float32))
    w = jnp.asarray(rng.randn(K, 24).astype(np.float32))
    f = jnp.asarray(fmt, jnp.int32)
    want = quant_matmul_format_ref(x, w, f)
    eager = quant_matmul_format_dispatch(x, w, f, force_kernel=False)
    kernel = quant_matmul_format_dispatch(x, w, f, force_kernel=True,
                                          interpret=True)
    assert bool(jnp.array_equal(eager, want))
    assert bool(jnp.array_equal(kernel, want))
    assert kernel.shape == (*lead, 24)
