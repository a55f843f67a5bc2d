"""Attention variants: GQA/MQA (+ sliding window, softcap, QKV-bias), MLA.

Backend-generic (CAA-analysable); the decode paths take a KV cache of raw
arrays and an absolute position, covering the ``decode_*``/``long_*`` shape
families. Softmax here is *the* paper object: its abs→rel error conversion
(×≤5.5) is what keeps low-precision attention accurate.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import layers as L


class KVCache(NamedTuple):
    """One layer's view of the stacked decode cache: the buffers stay whole
    ([L, ...]) so a scanned layer loop can write them in place in its
    carry; ``layer`` picks the slice this attention writes and reads.
    Positions run along each buffer's second-to-last axis."""
    k: jax.Array       # [L, B, K, Smax, Dh]  (MLA: compressed c_kv [L, B, Smax, R])
    v: jax.Array       # [L, B, K, Smax, Dh]  (MLA: rope key     [L, B, Smax, Dr])
    index: jax.Array   # int32 tokens already present in this layer: scalar,
    #                    or [B] when lanes advance independently
    layer: Any         # the layer index: a Python int or a traced scalar


def layer_of(stacked, layer):
    """Layer ``layer`` (a Python int or a traced index) of a stacked
    ``[L, ...]`` cache leaf."""
    return jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)


def with_layer(stacked, layer, value):
    """``stacked`` with layer ``layer`` replaced by ``value`` (in place
    where ``stacked`` is a scan carry)."""
    return jax.lax.dynamic_update_index_in_dim(
        stacked, value.astype(stacked.dtype), layer, 0)


def _cache_write(buf, upd, layer, index):
    """Write ``upd`` into layer ``layer`` of the stacked ``buf`` at
    position ``index`` of its second-to-last (sequence) axis, touching
    nothing else: ``buf`` [L, B, ..., Smax, X], ``upd`` [B, ..., S, X].
    A scalar index writes the whole batch at one offset (the classic
    lock-step decode); a [B] vector writes each lane at its own offset
    (continuous batching) as one scatter of B windows. Offsets clamp so
    the window fits, as ``dynamic_update_slice`` clamps."""
    i32 = lambda t: jnp.asarray(t, jnp.int32)
    seq = buf.ndim - 2
    if getattr(index, "ndim", 0) == 0:
        starts = [i32(0)] * buf.ndim
        starts[0], starts[seq] = i32(layer), i32(index)
        return jax.lax.dynamic_update_slice(buf, upd[None], starts)
    B = upd.shape[0]
    where = jnp.stack([jnp.broadcast_to(i32(layer), (B,)),
                       jnp.arange(B, dtype=jnp.int32), i32(index)], axis=1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(1, upd.ndim)),
        inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1, seq))
    return jax.lax.scatter(buf, where, upd, dnums, indices_are_sorted=True,
                           unique_indices=True,
                           mode=jax.lax.GatherScatterMode.CLIP)


def _mask5(mask):
    """Broadcast a [q,kv] (shared) or [B,q,kv] (per-lane) mask to the
    [B,K,G,q,s] score layout."""
    if mask.ndim == 3:
        return mask[:, None, None, :, :]
    return mask[None, None, None, :, :]


def _split_heads(bk, x, n_heads: int, d_head: int):
    b, s, _ = bk.shape_of(x)
    return bk.reshape(x, (b, s, n_heads, d_head))


def gqa_attention(
    bk, x, p, *,
    n_heads: int, n_kv_heads: int, d_head: int,
    cos, sin, mask,
    softcap: Optional[float] = None,
    qkv_bias: bool = False,
    cache: Optional[KVCache] = None,
    q_offset=0,
    fused_decode: bool = False,
):
    """Grouped-query attention. x: [B,S,d]. Returns (out, new_cache).

    With ``cache`` set this is a decode/prefill step at absolute position
    ``q_offset``; keys/values are appended into layer ``cache.layer`` of
    the stacked buffers, and the returned cache holds them whole.
    ``fused_decode`` (set by the caller only when the mask is plain causal)
    offers the S==1 step to ``bk.decode_attention`` — the certificate-aware
    flash decode hook; a backend returning None falls back to the composed
    einsum/softmax path.
    """
    B, S, d = bk.shape_of(x)
    G = n_heads // n_kv_heads

    q = bk.matmul(x, bk.param(p["wq"]))
    k = bk.matmul(x, bk.param(p["wk"]))
    v = bk.matmul(x, bk.param(p["wv"]))
    if qkv_bias:
        q = bk.add(q, bk.param(p["bq"]))
        k = bk.add(k, bk.param(p["bk"]))
        v = bk.add(v, bk.param(p["bv"]))

    q = _split_heads(bk, q, n_heads, d_head)
    k = _split_heads(bk, k, n_kv_heads, d_head)
    v = _split_heads(bk, v, n_kv_heads, d_head)

    q = L.apply_rope(bk, q, cos, sin)
    k = L.apply_rope(bk, k, cos, sin)

    # keys/values by position: [B,S,K,Dh] fresh, [B,K,Smax,Dh] from the
    # cache, whose heads-major layout lets each attention product read
    # its layer straight out of the stacked buffer
    kv = "bskd"
    new_cache = None
    if cache is not None:
        kr = jnp.swapaxes(bk.value_of(k), 1, 2).astype(cache.k.dtype)
        vr = jnp.swapaxes(bk.value_of(v), 1, 2).astype(cache.v.dtype)
        new_cache = cache._replace(
            k=_cache_write(cache.k, kr, cache.layer, cache.index),
            v=_cache_write(cache.v, vr, cache.layer, cache.index),
            index=cache.index + S)
        ck = layer_of(new_cache.k, cache.layer)
        cv = layer_of(new_cache.v, cache.layer)
        if fused_decode and S == 1 and not softcap:
            lengths = new_cache.index
            if getattr(lengths, "ndim", 0) == 0:
                lengths = jnp.full((B,), lengths, jnp.int32)
            q4 = bk.reshape(q, (B, n_kv_heads, G, d_head))
            fused = bk.decode_attention(q4, jnp.swapaxes(ck, 1, 2),
                                        jnp.swapaxes(cv, 1, 2),
                                        lengths.astype(jnp.int32))
            if fused is not None:
                out = bk.reshape(fused, (B, S, n_heads * d_head))
                return bk.matmul(out, bk.param(p["wo"])), new_cache
        k = bk.input(ck)
        v = bk.input(cv)
        kv = "bksd"

    # group the query heads: [B,S,K,G,Dh]; in training, hint sequence
    # parallelism on q (shards the S×S score tensor over "model")
    if cache is None:
        q = bk.shard_hint(q, "q_seq")
    q = bk.reshape(q, (B, S, n_kv_heads, G, d_head))
    scale = d_head ** -0.5
    scores = bk.einsum(f"bqkgd,{kv}->bkgqs", q, k)
    scores = bk.scale(scores, scale)
    if softcap:
        scores = bk.softcap(scores, softcap)
    neg = bk.const(L.NEG_BIG)
    scores = bk.where(_mask5(mask), scores, neg)
    probs = bk.softmax(scores, axis=-1)
    probs = bk.record("attn_probs", probs, kind="softmax")
    out = bk.einsum(f"bkgqs,{kv}->bqkgd", probs, v)
    if bk.is_analysis:
        # convex-combination fact: Σ_s probs = 1, probs ≥ 0 ⇒ out lies in
        # the value hull (IA cannot see the simplex constraint)
        vlo = jnp.min(v.exact.lo, axis=kv.index("s"))[:, None, :, None, :]
        vhi = jnp.max(v.exact.hi, axis=kv.index("s"))[:, None, :, None, :]
        out = bk.clamp_range(out, vlo, vhi)
    out = bk.reshape(out, (B, S, n_heads * d_head))
    out = bk.matmul(out, bk.param(p["wo"]))
    return out, new_cache


def init_gqa(key, d: int, n_heads: int, n_kv_heads: int, d_head: int,
             qkv_bias: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, n_heads * d_head),
        "wk": L.dense_init(ks[1], d, n_kv_heads * d_head),
        "wv": L.dense_init(ks[2], d, n_kv_heads * d_head),
        "wo": L.dense_init(ks[3], n_heads * d_head, d),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads * d_head,), jnp.float32)
        p["bk"] = jnp.zeros((n_kv_heads * d_head,), jnp.float32)
        p["bv"] = jnp.zeros((n_kv_heads * d_head,), jnp.float32)
    return p


# --------------------------------------------------------------------------
# Multi-head Latent Attention (MiniCPM3 / DeepSeek style)
# --------------------------------------------------------------------------

def mla_attention(
    bk, x, p, *,
    n_heads: int, q_rank: Optional[int], kv_rank: int,
    d_nope: int, d_rope: int, d_v: int,
    cos, sin, mask,
    cache: Optional[KVCache] = None,
    q_offset=0,
    softmax_scale: Optional[float] = None,
):
    """MLA: queries via low-rank down/up (straight from ``wq`` where
    ``q_rank`` is None, as in DeepSeek-V2-Lite); KV via a shared
    compressed latent (cached) + a shared rope key. Decode uses the
    absorbed form (scores in latent space) so the cache stays
    [B,S,kv_rank(+d_rope)]. Scores are scaled by ``softmax_scale``,
    (d_nope + d_rope)^-1/2 by default.

    Chained low-rank GEMMs are exactly two γ_n contractions in the CAA view
    (see DESIGN.md arch table)."""
    B, S, d = bk.shape_of(x)
    H = n_heads

    # --- queries ---
    if q_rank is None:
        q = bk.matmul(x, bk.param(p["wq"]))             # [B,S,H*(dn+dr)]
    else:
        qc = bk.matmul(x, bk.param(p["wq_a"]))          # [B,S,q_rank]
        qc = L.rmsnorm(bk, qc, p["q_norm"])
        q = bk.matmul(qc, bk.param(p["wq_b"]))          # [B,S,H*(dn+dr)]
    q = bk.reshape(q, (B, S, H, d_nope + d_rope))
    q_nope = bk.slice(q, (Ellipsis, slice(0, d_nope)))
    q_rope = bk.slice(q, (Ellipsis, slice(d_nope, d_nope + d_rope)))
    q_rope = L.apply_rope(bk, q_rope, cos, sin)

    # --- compressed KV latent ---
    ckv = bk.matmul(x, bk.param(p["wkv_a"]))            # [B,S,kv_rank+dr]
    c = bk.slice(ckv, (Ellipsis, slice(0, kv_rank)))
    k_rope = bk.slice(ckv, (Ellipsis, slice(kv_rank, kv_rank + d_rope)))
    c = L.rmsnorm(bk, c, p["kv_norm"])
    k_rope = L.apply_rope(
        bk, bk.reshape(k_rope, (B, S, 1, d_rope)), cos, sin
    )
    k_rope = bk.reshape(k_rope, (B, S, d_rope))

    new_cache = None
    if cache is not None:
        cr = bk.value_of(c).astype(cache.k.dtype)
        rr = bk.value_of(k_rope).astype(cache.v.dtype)
        new_cache = cache._replace(
            k=_cache_write(cache.k, cr, cache.layer, cache.index),
            v=_cache_write(cache.v, rr, cache.layer, cache.index),
            index=cache.index + S)
        c = bk.input(layer_of(new_cache.k, cache.layer))
        k_rope = bk.input(layer_of(new_cache.v, cache.layer))

    # absorbed scores: q_nope projected into latent space through W_uk
    # wkv_b packs [kv_rank, H*(dn+dv)] → W_uk = [...,:dn], W_uv = [...,dn:]
    wkv_b = bk.param(p["wkv_b"])
    wkv_b = bk.reshape(wkv_b, (kv_rank, H, d_nope + d_v))
    w_uk = bk.slice(wkv_b, (Ellipsis, slice(0, d_nope)))
    w_uv = bk.slice(wkv_b, (Ellipsis, slice(d_nope, d_nope + d_v)))
    q_lat = bk.einsum("bqhd,rhd->bqhr", q_nope, w_uk)   # [B,S,H,kv_rank]
    s_nope = bk.einsum("bqhr,bsr->bhqs", q_lat, c)
    s_rope = bk.einsum("bqhd,bsd->bhqs", q_rope, k_rope)
    scale = (softmax_scale if softmax_scale is not None
             else (d_nope + d_rope) ** -0.5)
    scores = bk.scale(bk.add(s_nope, s_rope), scale)
    neg = bk.const(L.NEG_BIG)
    mb = mask[:, None, :, :] if mask.ndim == 3 else mask[None, None, :, :]
    scores = bk.where(mb, scores, neg)
    probs = bk.softmax(scores, axis=-1)
    probs = bk.record("attn_probs", probs, kind="softmax")
    out_lat = bk.einsum("bhqs,bsr->bqhr", probs, c)     # [B,S,H,kv_rank]
    if bk.is_analysis:
        clo = jnp.min(c.exact.lo, axis=1)[:, None, None, :]
        chi = jnp.max(c.exact.hi, axis=1)[:, None, None, :]
        out_lat = bk.clamp_range(out_lat, clo, chi)
    out = bk.einsum("bqhr,rhd->bqhd", out_lat, w_uv)    # [B,S,H,dv]
    out = bk.reshape(out, (B, S, H * d_v))
    out = bk.matmul(out, bk.param(p["wo"]))
    return out, new_cache


def init_mla(key, d: int, n_heads: int, q_rank: Optional[int], kv_rank: int,
             d_nope: int, d_rope: int, d_v: int):
    ks = jax.random.split(key, 5)
    p = {
        "wkv_a": L.dense_init(ks[2], d, kv_rank + d_rope),
        "wkv_b": L.dense_init(ks[3], kv_rank, n_heads * (d_nope + d_v)),
        "wo": L.dense_init(ks[4], n_heads * d_v, d),
        "kv_norm": jnp.ones((kv_rank,), jnp.float32),
    }
    if q_rank is None:
        p["wq"] = L.dense_init(ks[0], d, n_heads * (d_nope + d_rope))
    else:
        p.update(wq_a=L.dense_init(ks[0], d, q_rank),
                 wq_b=L.dense_init(ks[1], q_rank, n_heads * (d_nope + d_rope)),
                 q_norm=jnp.ones((q_rank,), jnp.float32))
    return p
