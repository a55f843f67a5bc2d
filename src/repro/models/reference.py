"""Plain float32 reference forward of the dense GQA decoder (qwen2 family).

Straight ``jax.numpy`` written from the published layer equations — no
arithmetic backend, kernels, KV cache, paging or batching machinery — so
it shares nothing with the serving path it checks except the parameter
tree of :func:`repro.models.transformer.init_params`:

    h   = x + Wo · attn(rope(RMSNorm(x) Wq + bq), rope(· Wk + bk), · Wv + bv)
    out = h + W_down (silu(RMSNorm(h) W_gate) ⊙ RMSNorm(h) W_up)

with RMSNorm ε = 1e-6, rotary embeddings over the whole head in the
half-split convention, grouped-query causal softmax attention scaled by
1/√d_head, and an untied LM head. Callers on a TPU run it under
``jax.default_matmul_precision("highest")``: an f32 product at default
precision runs there in bf16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rmsnorm(x, g, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):                      # x [B, S, H, D]
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def dense_forward(params, cfg, tokens) -> jax.Array:
    """Logits ``[B, S, vocab]`` (f32) of ``tokens`` ``[B, S]`` at positions
    0..S-1, every position attending causally to those before it."""
    if (cfg.family != "dense" or cfg.mla or cfg.rwkv or cfg.hybrid
            or cfg.window or cfg.local_global_period or cfg.softcap_attn
            or cfg.softcap_final or cfg.embed_scale or cfg.tie_embeddings
            or cfg.norm != "rmsnorm" or cfg.act != "silu"):
        raise NotImplementedError(
            f"the plain reference covers the dense GQA decoder, not "
            f"{cfg.name}")
    B, S = tokens.shape
    H, K, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                        # [q, s]
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        h = _rmsnorm(x, p["ln1"])
        q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
        if cfg.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(B, S, H, D), pos, cfg.rope_theta)
        k = _rope(k.reshape(B, S, K, D), pos, cfg.rope_theta)
        v = v.reshape(B, S, K, D)
        q = q.reshape(B, S, K, H // K, D)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * D ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        o = jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(scores, -1), v)
        x = x + o.reshape(B, S, H * D) @ a["wo"]
        h = _rmsnorm(x, p["ln2"])
        m = p["mlp"]
        x = x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rmsnorm(x, params["final_norm"]) @ params["head"].T
