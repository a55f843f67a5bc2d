"""Pallas TPU kernel: emulated k-bit-mantissa GEMM (low-precision serving).

Once the CAA analysis has certified a precision k (Table-I end-game), the
serving path runs with operands rounded to k mantissa bits. On real silicon
that would be a narrow datapath; on today's TPUs we *emulate*: RNE-truncate
the f32 mantissa to k bits in-register (bit twiddling on the tile — zero
extra HBM traffic), accumulate on the MXU in f32, and round the result once.
That matches the `quantize.quantize`/MXU model the analysis assumes
(`emulate_accum=False` mode), so certified bounds apply to what this kernel
computes.

The RNE bit-twiddle: with s = 23-(k-1) dropped bits,
   q = (b + ((b >> s) & 1) + (2^{s-1} - 1)) & ~(2^s - 1)
carries into the exponent correctly on mantissa overflow.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _rne_to_k_bits(x, k: int):
    if k >= 24:
        return x
    s = 24 - k
    one = jnp.uint32(1)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    half = (one << (s - 1)) - one
    lsb = (bits >> s) & one
    q = (bits + half + lsb) & ~((one << s) - one)
    out = jax.lax.bitcast_convert_type(q, jnp.float32)
    return jnp.where(jnp.isfinite(x), out, x)


def _quant_matmul_kernel(x_ref, w_ref, o_ref, acc, *, n_k_steps: int, k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xq = _rne_to_k_bits(x_ref[...].astype(jnp.float32), k)
    wq = _rne_to_k_bits(w_ref[...].astype(jnp.float32), k)
    acc[...] += jnp.dot(xq, wq, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _done():
        o_ref[...] = _rne_to_k_bits(acc[...], k).astype(o_ref.dtype)


def block_candidates(M: int, K: int, N: int, *,
                     tiles=(128, 256, 512), max_candidates: int = 4):
    """Valid (block_m, block_n, block_k) Pallas tile candidates for an
    [M,K]@[K,N] GEMM — the autotune axis the kernel profiler sweeps.

    Candidates are built from MXU-friendly tile edges (capped to each
    dimension, which the kernels do anyway via ``min``), keeping only
    shapes that satisfy the kernels' divisibility contract, largest tiles
    first (fewer grid steps → usually fastest), deduplicated, truncated to
    ``max_candidates`` so a profile sweep stays bounded."""
    def _edges(dim):
        opts = [t for t in tiles if t <= dim and dim % t == 0]
        return opts or [dim]

    out, seen = [], set()
    for bk in sorted(_edges(K), reverse=True):
        for bm in sorted(_edges(M), reverse=True):
            for bn in sorted(_edges(N), reverse=True):
                cand = (bm, bn, bk)
                if cand not in seen:
                    seen.add(cand)
                    out.append(cand)
    return out[:max_candidates]


def quant_matmul_dynamic_k(x: jax.Array, w: jax.Array, k) -> jax.Array:
    """Emulated k-bit GEMM with ``k`` as a (possibly traced) scalar argument.

    Same rounding semantics as :func:`quant_matmul` — RNE-truncate both
    operands to k mantissa bits, accumulate in f32, round the result once —
    but the dropped-bit count is computed in integer arithmetic
    (:func:`repro.core.quantize.quantize_to_k`), so a single jit compilation
    serves every k: the mixed-precision serving path feeds per-layer k out of
    a scanned array, and the certificate probe ladder sweeps a whole k grid,
    neither paying a recompile per precision.
    """
    from repro.core.quantize import quantize_to_k

    xq = quantize_to_k(jnp.asarray(x, jnp.float32), k)
    wq = quantize_to_k(jnp.asarray(w, jnp.float32), k)
    out = jnp.matmul(xq, wq, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return quantize_to_k(out, k)


def quant_matmul_format_ref(x: jax.Array, w: jax.Array, fmt,
                            has_subnormals: bool = True,
                            saturating: bool = True) -> jax.Array:
    """Eager full-format GEMM oracle: operands and result rounded into the
    custom (k, emax, emin) format via
    :func:`repro.core.quantize.quantize_to_format`, f32 accumulation.

    ``fmt`` is an i32[3] array/sequence (k, emax, emin) — possibly traced,
    so one jit compilation serves every certified format (the serving
    backend's per-scope maps and the scanned per-layer arrays both rely on
    it); the subnormal/saturation flags are static (a v3 serving map is
    flag-uniform by construction). The contraction is summed in
    :func:`format_block_k` blocks, in order, exactly as the Pallas kernel
    below walks its K grid axis — the function that kernel must match
    bitwise.
    """
    from repro.core.quantize import quantize_to_format

    fmt = jnp.asarray(fmt, jnp.int32)
    k, emax, emin = fmt[0], fmt[1], fmt[2]
    q = lambda v: quantize_to_format(v, k, emax, emin,
                                     has_subnormals, saturating)
    xq = q(jnp.asarray(x, jnp.float32))
    wq = q(jnp.asarray(w, jnp.float32))
    K, N = wq.shape
    bk = format_block_k(K)
    xb = jnp.moveaxis(xq.reshape(*xq.shape[:-1], K // bk, bk), -2, 0)
    wb = wq.reshape(K // bk, bk, N)

    def add_block(acc, blk):
        xk, wk = blk
        return acc + jnp.matmul(xk, wk, precision=HIGHEST,
                                preferred_element_type=jnp.float32), None

    acc, _ = jax.lax.scan(add_block,
                          jnp.zeros(xq.shape[:-1] + (N,), jnp.float32),
                          (xb, wb))
    return q(acc)


def smem_format(fmt_ref):
    """The (k, emax, emin) triple of a scalar-prefetched i32[3] ref, each
    broadcast to an i32[1, 1] vector: Mosaic bitcasts only vectors, and
    the format's power-of-two constants are built by bitcasting."""
    return tuple(jnp.full((1, 1), fmt_ref[i], jnp.int32) for i in range(3))


def _quant_matmul_format_kernel(fmt_ref, x_ref, w_ref, o_ref, acc, *,
                                n_k_steps: int, has_subnormals: bool,
                                saturating: bool):
    from repro.core.quantize import quantize_to_format

    k, emax, emin = smem_format(fmt_ref)
    q = lambda v: quantize_to_format(v, k, emax, emin,
                                     has_subnormals, saturating)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(q(x_ref[...].astype(jnp.float32)),
                        q(w_ref[...].astype(jnp.float32)),
                        precision=HIGHEST,
                        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k_steps - 1)
    def _done():
        o_ref[...] = q(acc[...]).astype(o_ref.dtype)


def quant_matmul_format(x: jax.Array, w: jax.Array, fmt, *,
                        has_subnormals: bool = True, saturating: bool = True,
                        block_m: int = 256, block_n: int = 256,
                        block_k: int = 512, interpret: bool = False):
    """Emulated custom-format GEMM, format delivered by SCALAR PREFETCH.

    ``fmt`` = i32[3] (k, emax, emin). The triple rides in SMEM via
    ``pltpu.PrefetchScalarGridSpec`` and is read before the tiles stream,
    so ONE compiled kernel serves every certified format — swapping the
    serving format (or serving a per-scope v3 map) costs zero recompiles,
    vs one full Mosaic compile per format for the static-``k`` kernel
    above (benchmarks/analysis_speed.py measures the difference). Rounding
    semantics are exactly :func:`quant_matmul_format_ref`'s; with
    ``block_k = format_block_k(K)`` the two also sum the contraction in
    the same blocks and order — the acceptance test for v3 certificates
    serves through both and compares bits.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    kernel = functools.partial(_quant_matmul_format_kernel, n_k_steps=nk,
                               has_subnormals=has_subnormals,
                               saturating=saturating)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, fmt_ref: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk, fmt_ref: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, fmt_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(fmt, jnp.int32), x, w)


def _pick_block(dim: int, target: int) -> int:
    """Largest divisor of ``dim`` that is ≤ target (dim itself when small)."""
    if dim <= target:
        return dim
    for b in range(target, 0, -1):
        if dim % b == 0:
            return b
    return dim


def format_block_k(K: int) -> int:
    """Contraction block of the format GEMM, shared by the kernel and its
    eager mirror. 512 keeps an f32 weight tile at 512 KiB (a whole
    18944-deep down-projection column block would be 19 MB of VMEM)."""
    return _pick_block(K, 512)


def quant_matmul_format_dispatch(x: jax.Array, w: jax.Array, fmt,
                                 has_subnormals: bool = True,
                                 saturating: bool = True, *,
                                 force_kernel=None,
                                 interpret: bool = False) -> jax.Array:
    """Serving dispatch for the full-format GEMM: the scalar-prefetch
    Pallas kernel on TPU, :func:`quant_matmul_format_ref` elsewhere.

    Batched ``x`` ([..., K]) is flattened to [M, K] for the kernel and
    restored after. The kernel steps through K in
    :func:`format_block_k` blocks, the order the eager reference sums
    in; the differential test serves the same GEMM through both paths
    and compares bits. ``force_kernel`` overrides the platform check
    (tests exercise the kernel in interpret mode on CPU)."""
    use_kernel = force_kernel
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return quant_matmul_format_ref(x, w, fmt,
                                       has_subnormals=has_subnormals,
                                       saturating=saturating)
    lead = x.shape[:-1]
    K = x.shape[-1]
    M = 1
    for d in lead:
        M *= d
    N = w.shape[-1]
    out = quant_matmul_format(
        jnp.asarray(x, jnp.float32).reshape(M, K), jnp.asarray(w, jnp.float32),
        fmt, has_subnormals=has_subnormals, saturating=saturating,
        block_m=_pick_block(M, 256), block_n=_pick_block(N, 256),
        block_k=format_block_k(K), interpret=interpret)
    return out.reshape(*lead, N)


def quant_matmul(x: jax.Array, w: jax.Array, *, k: int,
                 block_m: int = 256, block_n: int = 256, block_k: int = 512,
                 interpret: bool = False):
    """Emulated k-bit GEMM: [M,K] @ [K,N] → [M,N] (f32 carrier)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    kernel = functools.partial(_quant_matmul_kernel, n_k_steps=nk, k=int(k))
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, w)
