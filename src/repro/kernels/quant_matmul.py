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
    nk = -(-K // bk)
    if nk * bk != K:        # the kernel's last block is masked to zeros
        xq = jnp.pad(xq, [(0, 0)] * (xq.ndim - 1) + [(0, nk * bk - K)])
        wq = jnp.pad(wq, ((0, nk * bk - K), (0, 0)))
    xb = jnp.moveaxis(xq.reshape(*xq.shape[:-1], nk, bk), -2, 0)
    wb = wq.reshape(nk, bk, N)

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


def _quant_matmul_format_kernel(fmt_ref, *refs, n_k_steps: int,
                                has_subnormals: bool, saturating: bool,
                                n_scalar: int = 0, k_tail: int = 0):
    """One (row block, column block, K step) of the format GEMM. The dense
    grid and the grouped grid share it: ``refs`` opens with ``n_scalar``
    further scalar-prefetch refs, which only the grouped grid's index maps
    read. ``k_tail`` > 0 is the width of a last K block that overhangs
    the contraction: its columns of x and rows of w past the end are
    masked to zero, as the eager mirror pads them."""
    from repro.core.quantize import quantize_to_format

    x_ref, w_ref, o_ref, acc = refs[n_scalar:]
    k, emax, emin = smem_format(fmt_ref)
    q = lambda v: quantize_to_format(v, k, emax, emin,
                                     has_subnormals, saturating)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    if k_tail:
        i32 = lambda v: jnp.full((1, 1), v, jnp.int32)   # no 64-bit scalars
        width = jnp.where(pl.program_id(2) == n_k_steps - 1, i32(k_tail),
                          i32(x.shape[1]))
        x = jnp.where(jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
                      < width, x, 0.0)
        w = jnp.where(jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
                      < width, w, 0.0)
    acc[...] += jnp.dot(q(x), q(w), precision=HIGHEST,
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
    serves through both and compares bits. N and K need not be whole
    blocks: a last column block overhangs into columns that are dropped,
    a last K block is masked.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0
    nk = -(-K // bk)
    kernel = functools.partial(_quant_matmul_format_kernel, n_k_steps=nk,
                               has_subnormals=has_subnormals,
                               saturating=saturating, k_tail=K % bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // bm, -(-N // bn), nk),
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
    """Contraction block of the format GEMM, shared by the kernels and
    their eager mirrors. 512 keeps an f32 weight tile at 512 KiB (a whole
    18944-deep down-projection column block would be 19 MB of VMEM) and
    is a whole number of 128-wide lanes; a K that is no multiple of it
    ends in a shorter block (10944 = 21 x 512 + 192)."""
    return min(K, 512)


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
        block_m=_pick_block(M, 256), block_n=256,
        block_k=format_block_k(K), interpret=interpret)
    return out.reshape(*lead, N)


def _grouped_block_n(bk: int, N: int) -> int:
    """Column block of the grouped GEMM: every column at once while an f32
    weight block stays within 4 MiB (so a tile walks its expert's weights
    in a few large steps), else 256."""
    return N if bk * N * 4 <= 4 << 20 else 256


def grouped_quant_matmul_format(x: jax.Array, w: jax.Array, tile_expert,
                                meta, fmt, *, tm: int,
                                has_subnormals: bool = True,
                                saturating: bool = True,
                                interpret: bool = False):
    """Grouped format GEMM over stacked expert weights, read in place.

    ``x`` [R, K] holds rows in tiles of ``tm``; every row of tile t goes
    to expert ``tile_expert[t]`` of layer ``meta[0]`` of ``w`` [L, E, K,
    N]; ``meta[1]`` tiles are real, the rest pad the grid to its static
    size. The grid is the dense kernel's (tile, column block, K step)
    around the same body, with the format, the tile→expert table and
    ``meta`` prefetched as scalars: the weight block of step (t, j, kk) is
    block (layer, expert[t], kk, j) of the whole stack, so no layer or
    expert is copied out of it. A padding tile reads the blocks of the
    last real tile's last step again, which its index does not change, so
    nothing is fetched for it; its rows are unspecified here (the
    dispatch zeroes them). Operand and result rounding are the dense
    kernel's."""
    R, K = x.shape
    _, _, K2, N = w.shape
    assert K == K2 and R % tm == 0
    bk = format_block_k(K)
    nk = -(-K // bk)
    bn = _grouped_block_n(bk, N)
    nj = -(-N // bn)
    kernel = functools.partial(_quant_matmul_format_kernel, n_k_steps=nk,
                               has_subnormals=has_subnormals,
                               saturating=saturating, n_scalar=2,
                               k_tail=K % bk)

    # the index maps stay in i32: Mosaic lowers no 64-bit index (x64 mode)
    def pick(real, a, b):
        return jax.lax.select(real, jnp.asarray(a, jnp.int32),
                              jnp.asarray(b, jnp.int32))

    def x_map(t, j, kk, fmt_ref, te_ref, meta_ref):
        real = t < meta_ref[1]
        return pick(real, t, meta_ref[1] - 1), pick(real, kk, nk - 1)

    def w_map(t, j, kk, fmt_ref, te_ref, meta_ref):
        real = t < meta_ref[1]
        return (meta_ref[0], te_ref[t], pick(real, kk, nk - 1),
                pick(real, j, nj - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R // tm, nj, nk),
        in_specs=[pl.BlockSpec((tm, bk), x_map),
                  pl.BlockSpec((None, None, bk, bn), w_map)],
        out_specs=pl.BlockSpec((tm, bn), lambda t, j, kk, *_: (t, j)),
        scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N), jnp.float32),
        # two 4 MiB weight blocks and their rounded copies pass the 16 MiB
        # default scope; a v5e core has 128 MiB of VMEM
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
        interpret=interpret,
    )(jnp.asarray(fmt, jnp.int32), jnp.asarray(tile_expert, jnp.int32),
      jnp.asarray(meta, jnp.int32), x, w)


def grouped_quant_matmul_format_ref(x: jax.Array, w: jax.Array, tile_expert,
                                    meta, fmt, *, tm: int,
                                    has_subnormals: bool = True,
                                    saturating: bool = True) -> jax.Array:
    """Eager mirror of :func:`grouped_quant_matmul_format`: tile by tile,
    :func:`quant_matmul_format_ref` of the tile's rows with its expert's
    weights, so each contraction is summed in :func:`format_block_k`
    blocks in the kernel's order (zero rows give zero rows)."""
    R, K = x.shape
    layer = jnp.asarray(meta, jnp.int32)[0]
    w_layer = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)

    def one_tile(args):
        rows, e = args
        we = jax.lax.dynamic_index_in_dim(w_layer, e, 0, keepdims=False)
        return quant_matmul_format_ref(rows, we, fmt, has_subnormals,
                                       saturating)

    out = jax.lax.map(one_tile, (jnp.asarray(x, jnp.float32).reshape(
        R // tm, tm, K), jnp.asarray(tile_expert, jnp.int32)))
    return out.reshape(R, -1)


def grouped_quant_matmul_format_dispatch(x, w, tile_expert, meta, fmt, *,
                                         tm: int, has_subnormals=True,
                                         saturating=True, force_kernel=None,
                                         interpret: bool = False):
    """Serving dispatch of the grouped format GEMM: the kernel on TPU, its
    eager mirror elsewhere (``force_kernel`` as in
    :func:`quant_matmul_format_dispatch`). The rows of padding tiles,
    which the kernel leaves unspecified, are zeroed, as the mirror makes
    them from zero rows."""
    use_kernel = force_kernel
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    kw = dict(tm=tm, has_subnormals=has_subnormals, saturating=saturating)
    if not use_kernel:
        return grouped_quant_matmul_format_ref(x, w, tile_expert, meta, fmt,
                                               **kw)
    out = grouped_quant_matmul_format(
        jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
        tile_expert, meta, fmt, interpret=interpret, **kw)
    rows = jnp.arange(out.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(rows < jnp.asarray(meta, jnp.int32)[1] * tm, out, 0.0)


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
