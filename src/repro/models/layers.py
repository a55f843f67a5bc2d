"""Backend-generic building blocks shared by every architecture.

All functions take the arithmetic backend ``bk`` first — with
:class:`repro.core.backend.JOps` they are ordinary jnp (jit/pjit-able); with
:class:`repro.core.backend.CaaOps` they propagate rigorous CAA error bounds
(the paper's operator-overloading trick, JAX-style). Parameters arrive as
raw arrays and are wrapped via ``bk.param``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# initialisers (plain numpy-free jax, used by every arch's init)
# --------------------------------------------------------------------------

def dense_init(key, n_in: int, n_out: int, scale: Optional[float] = None):
    scale = scale if scale is not None else (1.0 / math.sqrt(n_in))
    return jax.random.normal(key, (n_in, n_out), jnp.float32) * scale


def embed_init(key, vocab: int, d: int):
    return jax.random.normal(key, (vocab, d), jnp.float32) * 0.02


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

import numpy as _np


def rmsnorm(bk, x, gamma, eps: float = 1e-6):
    """x * rsqrt(mean(x², -1) + eps) * γ — re-anchors ranges to O(1), the
    'activation layers recover accuracy' effect the paper highlights.

    Global insight injected for the analysis: |x_i|/√(mean(x²)+eps) ≤ √n
    always (x_i² ≤ n·mean(x²)) — IA alone pairs x_hi with 1/√eps and
    explodes; the clamp is the algebraic fact it cannot see."""
    g = bk.param(gamma)
    ms = bk.mean(bk.square(x), axis=-1, keepdims=True)
    inv = bk.rsqrt(bk.shift(ms, eps))
    y = bk.mul(bk.mul(x, inv), g)
    n = bk.shape_of(x)[-1]
    bound = (_np.sqrt(n) * 1.0000001) * jnp.abs(jnp.asarray(gamma, jnp.float64))
    return bk.clamp_range(y, -bound, bound)


def groupless_norm_bound(n: int):
    return _np.sqrt(n) * 1.0000001


def layernorm(bk, x, gamma, beta, eps: float = 1e-5):
    """Same global-insight clamp as rmsnorm: |(x−μ)/σ| ≤ √n."""
    mu = bk.mean(x, axis=-1, keepdims=True)
    xc = bk.sub(x, mu)
    var = bk.mean(bk.square(xc), axis=-1, keepdims=True)
    inv = bk.rsqrt(bk.shift(var, eps))
    y = bk.add(bk.mul(bk.mul(xc, inv), bk.param(gamma)), bk.param(beta))
    n = bk.shape_of(x)[-1]
    g64 = jnp.abs(jnp.asarray(gamma, jnp.float64))
    b64 = jnp.asarray(beta, jnp.float64)
    bound = (_np.sqrt(n) * 1.0000001) * g64
    return bk.clamp_range(y, b64 - bound, b64 + bound)


# --------------------------------------------------------------------------
# embeddings / heads
# --------------------------------------------------------------------------

def embed(bk, table, ids):
    """Exact gather of format-stored rows."""
    return bk.take(bk.param(table), ids, axis=0)


def logits_head(bk, x, table, softcap: Optional[float] = None):
    """Final projection (tied or untied); optional gemma-style softcap —
    the paper's tanh rule (×2.63) is load-bearing here."""
    y = bk.einsum("bsd,vd->bsv", x, bk.param(table))
    if softcap:
        y = bk.softcap(y, softcap)
    return y


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def mlp_gated(bk, x, w_gate, w_up, w_down, act: str = "silu"):
    """LLaMA-style gated MLP: down( act(x@Wg) * (x@Wu) )."""
    g = bk.matmul(x, bk.param(w_gate))
    u = bk.matmul(x, bk.param(w_up))
    a = getattr(bk, act)(g)
    return bk.matmul(bk.mul(a, u), bk.param(w_down))


def mlp_plain(bk, x, w_in, b_in, w_out, b_out, act: str = "gelu"):
    h = bk.add(bk.matmul(x, bk.param(w_in)), bk.param(b_in))
    h = getattr(bk, act)(h)
    return bk.add(bk.matmul(h, bk.param(w_out)), bk.param(b_out))


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------

def rope_tables(positions, d_head: int, theta: float = 10000.0,
                yarn: Optional[tuple] = None):
    """cos/sin tables for the given positions: [S, d_head//2] each.
    ``yarn`` = (factor, original_max_positions, beta_fast, beta_slow,
    mscale, mscale_all_dim) gives YaRN's frequencies and table scale
    (:func:`yarn_inv_freq`)."""
    half = d_head // 2
    scale = 1.0
    if yarn is None:
        freqs = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
    else:
        freqs = yarn_inv_freq(d_head, theta, *yarn[:4])
        factor, _, _, _, mscale, mscale_all_dim = yarn
        scale = yarn_mscale(factor, mscale) / yarn_mscale(factor,
                                                          mscale_all_dim)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN rotary frequencies (DeepseekV2YarnRotaryEmbedding): the
    extrapolated base frequencies below the correction range
    [low, high], those interpolated by ``factor`` above it, and a linear
    ramp across it."""
    def corr_dim(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    half = dim // 2
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = 1.0 / (factor * theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inter * (1.0 - keep) + extra * keep


def apply_rope(bk, x, cos, sin):
    """x: [B, S, H, Dh]; tables [S, Dh/2] — or [B, S, Dh/2] for the ragged
    decode path (per-lane absolute positions). Tables enter as stored
    params (rounded transcendental constants) for analysis honesty."""
    dh = bk.shape_of(x)[-1]
    half = dh // 2
    x1 = bk.slice(x, (Ellipsis, slice(0, half)))
    x2 = bk.slice(x, (Ellipsis, slice(half, dh)))
    if getattr(cos, "ndim", 2) == 3:        # per-lane tables [B, S, Dh/2]
        c = bk.param(cos[:, :, None, :])
        s = bk.param(sin[:, :, None, :])
    else:
        c = bk.param(cos[None, :, None, :])
        s = bk.param(sin[None, :, None, :])
    r1 = bk.sub(bk.mul(x1, c), bk.mul(x2, s))
    r2 = bk.add(bk.mul(x2, c), bk.mul(x1, s))
    return bk.concat([r1, r2], axis=-1)


# --------------------------------------------------------------------------
# masks (exact integer logic — no FP error involved)
# --------------------------------------------------------------------------

def causal_mask(q_len: int, kv_len: int, q_offset: int = 0,
                window: Optional[int] = None):
    """Boolean [q_len, kv_len]: True = attendable. ``window`` gives sliding-
    window (SWA) masking; q_offset places queries at absolute positions for
    decode."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    k_pos = jnp.arange(kv_len)[None, :]
    ok = k_pos <= q_pos
    if window is not None:
        ok = ok & (k_pos > q_pos - window)
    return ok


def lane_causal_mask(q_len: int, kv_len: int, q_offsets,
                     window: Optional[int] = None):
    """Per-lane boolean [B, q_len, kv_len] for the ragged decode path:
    lane b's queries sit at absolute positions ``q_offsets[b] + arange``.
    Exact integer logic, same attendability rule as :func:`causal_mask`."""
    q_pos = q_offsets[:, None, None] + jnp.arange(q_len)[None, :, None]
    k_pos = jnp.arange(kv_len)[None, None, :]
    ok = k_pos <= q_pos
    if window is not None:
        ok = ok & (k_pos > q_pos - window)
    return ok


NEG_BIG = -1e9  # mask value: exact constant, exp(-1e9)=0 under IA too
