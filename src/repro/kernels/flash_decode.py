"""Pallas TPU kernel: flash decode attention (online-softmax, GQA).

The decode hot spot: one query token per sequence attends to a [S, K, Dh]
KV cache. A naive lowering materialises the [H, S] score row in HBM and
reads the cache twice (scores, then values). This kernel streams the cache
once in S-blocks, keeping the online-softmax state (running max m, running
sum l, output accumulator) in VMEM scratch — the standard flash recurrence

    m' = max(m, rowmax(s));  α = e^{m−m'}
    l' = α·l + rowsum(e^{s−m'});  o' = α·o + e^{s−m'}·V_blk

TPU adaptation: grid (B, K, S/bs) with the S loop innermost so scratch
persists across cache blocks; block sizes 128-aligned for the MXU; GQA
groups (G = H/K query heads per KV head) processed together so the kv
block is read once per group. Variable sequence lengths are masked from a
scalar-prefetched length vector.

The CERTIFICATE-AWARE variant (:func:`flash_decode_certified`) additionally
rounds the q/k/v tiles into a certified custom (k, emax, emin) format
in-register before the MXU contractions, with the triple delivered by
SCALAR PREFETCH exactly like ``quant_matmul_format`` — so ONE compiled
kernel serves every certified format and every per-layer lane of a v3
serving map. :func:`flash_decode_quantized_ref` is the eager oracle
(bitwise-identical with a single S block — the off-TPU serving fallback),
and :func:`certified_decode_attention` is the dispatch the serving
backends call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _flash_decode_kernel(fmt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, n_s_steps: int,
                         block_s: int, scale: float, quantize: bool,
                         has_subnormals: bool, saturating: bool):
    if quantize:
        from repro.core.quantize import quantize_to_format
        from repro.kernels.quant_matmul import smem_format
        kk, emax, emin = smem_format(fmt_ref)
        qf = lambda t: quantize_to_format(t, kk, emax, emin,
                                          has_subnormals, saturating)
    else:
        qf = lambda t: t
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = qf(q_ref[0, 0].astype(jnp.float32))       # [G, D]
    k = qf(k_ref[0].astype(jnp.float32))          # [bs, D]
    v = qf(v_ref[0].astype(jnp.float32))          # [bs, D]
    length = len_ref[pl.program_id(0)]

    s = jnp.dot(q, k.T, precision=HIGHEST,
                preferred_element_type=jnp.float32) * scale
    pos = s_idx * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, jnp.float32(NEG))   # no f64 under x64

    m_prev = m_ref[...]                   # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                # [G, bs]
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_new = alpha * acc_ref[...] + jnp.dot(
        p, v, precision=HIGHEST, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(s_idx == n_s_steps - 1)
    def _done():
        o_ref[0, 0] = qf(acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _flash_decode(q, k, v, lengths, fmt, *, quantize: bool,
                  has_subnormals: bool, saturating: bool, block_s: int,
                  interpret: bool) -> jax.Array:
    B, K, G, D = q.shape
    S = k.shape[1]
    bs = min(block_s, S)
    assert S % bs == 0
    n_s = S // bs
    kernel = functools.partial(_flash_decode_kernel, n_s_steps=n_s,
                               block_s=bs, scale=D ** -0.5,
                               quantize=quantize,
                               has_subnormals=has_subnormals,
                               saturating=saturating)
    # the cache is read as [B, S, K*D] (a free row-major reshape): head h
    # is lane block h of width D, so each KV block is a (bs, D) tile that
    # meets the TPU's (8, 128) tiling, where a (1, D) slice of [.., K, D]
    # would not
    kv_spec = pl.BlockSpec((1, bs, D), lambda b, h, s, fmt, ln: (b, s, h))
    # i32 zeros: a literal 0 would be an i64 block index under x64
    q_spec = pl.BlockSpec((1, 1, G, D), lambda b, h, s, fmt, ln: (
        b, h, jnp.int32(0), jnp.int32(0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_s),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(fmt, jnp.int32), jnp.asarray(lengths, jnp.int32), q,
      k.reshape(B, S, K * D), v.reshape(B, S, K * D))


def flash_decode_attention(q, k, v, lengths, *, block_s: int = 256,
                           interpret: bool = False) -> jax.Array:
    """q: [B, K, G, D] (grouped query heads); k, v: [B, S, K, D];
    lengths: [B] valid cache lengths. Returns [B, K, G, D]."""
    return _flash_decode(q, k, v, lengths, jnp.zeros((3,), jnp.int32),
                         quantize=False, has_subnormals=True,
                         saturating=True, block_s=block_s,
                         interpret=interpret)


# --------------------------------------------------------------------------
# certificate-aware decode: per-layer (k, emax, emin) via scalar prefetch
# --------------------------------------------------------------------------

def flash_decode_certified(q, k, v, lengths, fmt, *,
                           has_subnormals: bool = True,
                           saturating: bool = True,
                           block_s: int = 256,
                           interpret: bool = False) -> jax.Array:
    """Certificate-aware flash decode: q/k/v tiles rounded into the
    (k, emax, emin) format in-kernel, output rounded once — the decode
    twin of ``quant_matmul_format``'s serving semantics.

    ``fmt`` (i32[3]) and ``lengths`` (i32[B]) ride in SMEM via
    ``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=2)``, so one
    compiled kernel serves every certified format across every per-layer
    lane — swapping formats costs zero recompiles (the ladder-compile
    contract the serving scan relies on). With a single S block
    (block_s ≥ S) the result is bitwise
    :func:`flash_decode_quantized_ref`.
    """
    return _flash_decode(q, k, v, lengths, fmt, quantize=True,
                         has_subnormals=has_subnormals,
                         saturating=saturating, block_s=block_s,
                         interpret=interpret)


def flash_decode_quantized_ref(q, k, v, lengths, fmt, *,
                               has_subnormals: bool = True,
                               saturating: bool = True) -> jax.Array:
    """Eager oracle for :func:`flash_decode_certified` — mirrors the
    kernel's op order for the single-S-block case (one dot per (b, h)
    head pair, same NEG masking, same acc/l division), with the same
    traced-format rounding. This is the off-TPU serving fallback the
    certified decode path runs on CPU CI — bitwise what the kernel
    computes with block_s ≥ S."""
    from repro.core.quantize import quantize_to_format

    fmt = jnp.asarray(fmt, jnp.int32)
    kk, emax, emin = fmt[0], fmt[1], fmt[2]
    qf = lambda t: quantize_to_format(t.astype(jnp.float32), kk, emax, emin,
                                      has_subnormals, saturating)
    B, K, G, D = q.shape
    scale = D ** -0.5
    qq, kq, vq = qf(q), qf(k), qf(v)

    def one(qb, kb, vb, ln):      # [G,D], [S,D], [S,D], scalar length
        s = jnp.dot(qb, kb.T, precision=HIGHEST,
                    preferred_element_type=jnp.float32) * scale
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < ln, s, NEG)
        m = jnp.maximum(jnp.full_like(s[:, :1], NEG),
                        jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        acc = jnp.dot(p, vb, precision=HIGHEST,
                      preferred_element_type=jnp.float32)
        return qf(acc / l)

    out = jax.vmap(jax.vmap(one, in_axes=(0, 1, 1, None)),
                   in_axes=(0, 0, 0, 0))(
        qq, kq, vq, jnp.asarray(lengths, jnp.int32))
    return out.astype(q.dtype)


def certified_decode_attention(q, k, v, lengths, fmt, *,
                               has_subnormals: bool = True,
                               saturating: bool = True,
                               block_s: int = 256,
                               force_kernel=None,
                               interpret: bool = False) -> jax.Array:
    """Serving dispatch: the Pallas certified kernel on TPU, the eager
    oracle elsewhere. ``force_kernel`` overrides the platform check (tests
    run the kernel in interpret mode on CPU)."""
    use_kernel = force_kernel
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        return flash_decode_certified(
            q, k, v, lengths, fmt, has_subnormals=has_subnormals,
            saturating=saturating, block_s=block_s, interpret=interpret)
    return flash_decode_quantized_ref(
        q, k, v, lengths, fmt, has_subnormals=has_subnormals,
        saturating=saturating)
