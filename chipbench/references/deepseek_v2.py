"""Plain float32 reference of the DeepSeek-V2 decoder (arXiv:2405.04434;
DeepSeek-V2-Lite's ``modeling_deepseek.py``: DeepseekV2Attention,
DeepseekV2YarnRotaryEmbedding, DeepseekV2MoE), and the weights that it and
the program are both given.

Straight ``jax.numpy`` from the published layer equations; it imports
nothing of the program. Per layer, with h = RMSNorm(x):

    q          = h Wq                     (no query compression) → [H, dn+dr]
    c, k_r     = h Wkv_a                  → latent [R], rope key [dr]
    c          = RMSNorm(c)
    k_n, v     = c Wkv_b                  → per head [dn], [dv]
    score_hts  = (q_n,h,t · k_n,h,s + rope(q_r,h,t) · rope(k_r,s)) · scale
    x'         = x + Wo · concat_h(causal softmax_s(score) v_h)

    layer 0 (dense):   out = x' + W_down(silu(h' W_gate) ⊙ h' W_up)
    layers 1.. (MoE):  p = softmax(h' W_router) over all E experts,
                       g_e = p_e · routed_scaling if e is among the k
                       largest p (greedy), else 0 (no renormalisation:
                       ``norm_topk_prob`` false),
                       out = x' + Σ_e g_e E_e(h') + S(h')

with h' = RMSNorm(x'), E_e expert e's gated silu MLP and S the shared
experts, one gated MLP of width n_shared · moe_intermediate_size.
Attention is written in the non-absorbed form (keys and values expanded
per head from the latent); every expert runs on every token, gated by the
top-k mask, with no capacity.

RoPE is YaRN's: base frequencies θ^(-2i/dr) where they turn fewer than
``beta_fast`` times over the original context, the same divided by
``factor`` where they turn more than ``beta_slow`` times, a linear ramp
between; cos and sin are scaled by mscale(mscale)/mscale(mscale_all_dim)
with mscale(m) = 0.1 m ln(factor) + 1, and the softmax scale is
(dn + dr)^-1/2 · mscale(mscale_all_dim)^2.

Departure: the published model rotates an interleaved layout of the rope
columns (pairs (2i, 2i+1)); this reference and the program rotate the
half-split layout (i, i + dr/2). With weights drawn at random the two are
the same function up to a fixed permutation of the rope columns of Wq and
Wkv_a.

Every product goes through :func:`dot`, in one of two precisions:
``"highest"``, float32 (``Precision.HIGHEST``), and ``"bf16x3"``, the
three bfloat16 passes of ``Precision.HIGH`` written out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _yarn(hf):
    r = hf["rope_scaling"]
    return (float(r["factor"]), int(r["original_max_position_embeddings"]),
            float(r["beta_fast"]), float(r["beta_slow"]), float(r["mscale"]),
            float(r["mscale_all_dim"]))


def program_arch(hf, name: str) -> dict:
    """Keyword arguments of the program's ``ArchConfig`` for ``hf``."""
    H = hf["num_attention_heads"]
    return dict(name=name, family="moe", n_layers=hf["num_hidden_layers"],
                d_model=hf["hidden_size"], n_heads=H, n_kv_heads=H,
                d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
                mla=True, q_rank=hf["q_lora_rank"],
                kv_rank=hf["kv_lora_rank"], d_nope=hf["qk_nope_head_dim"],
                d_rope=hf["qk_rope_head_dim"], d_v=hf["v_head_dim"],
                n_experts=hf["n_routed_experts"],
                top_k=hf["num_experts_per_tok"],
                moe_d_ff=hf["moe_intermediate_size"],
                first_k_dense=hf["first_k_dense_replace"],
                n_shared_experts=hf["n_shared_experts"],
                norm_topk_prob=hf["norm_topk_prob"],
                routed_scaling=float(hf["routed_scaling_factor"]),
                rope_theta=float(hf["rope_theta"]), yarn=_yarn(hf),
                tie_embeddings=hf["tie_word_embeddings"])


def init_weights(hf, seed_lo, seed_hi):
    """The weight tree, drawn from the seed (two uint32 halves, traced, so
    one compiled program serves every seed), in the program's layout:
    the leading dense layers under ``dense_layers``, the MoE layers under
    ``layers`` with each routed-expert weight stacked ``[L, E, K, N]``.
    Scales keep activations O(1): N(0, 1/fan_in) projections, N(0, 0.02²)
    embeddings and head, norm gains 1 + N(0, 0.1²). Each layer's expert
    weights are drawn on their own, so the largest temporary is one
    layer's."""
    d, V = hf["hidden_size"], hf["vocab_size"]
    H, R = hf["num_attention_heads"], hf["kv_lora_rank"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    ff, f, E = (hf["intermediate_size"], hf["moe_intermediate_size"],
                hf["n_routed_experts"])
    fs = hf["n_shared_experts"] * f
    nd = hf["first_k_dense_replace"]
    nm = hf["num_hidden_layers"] - nd
    key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    ks = iter(jax.random.split(key, 40))

    def normal(shape, scale, mean=0.0):
        return mean + scale * jax.random.normal(next(ks), shape, jnp.float32)

    def by_layer(n, shape, scale):
        return jax.lax.map(
            lambda k: scale * jax.random.normal(k, shape, jnp.float32),
            jax.random.split(next(ks), n))

    def attn(n):
        return {"wq": normal((n, d, H * (dn + dr)), d ** -0.5),
                "wkv_a": normal((n, d, R + dr), d ** -0.5),
                "wkv_b": normal((n, R, H * (dn + dv)), R ** -0.5),
                "wo": normal((n, H * dv, d), (H * dv) ** -0.5),
                "kv_norm": normal((n, R), 0.1, 1.0)}

    def mlp(n, width):
        return {"w_gate": normal((n, d, width), d ** -0.5),
                "w_up": normal((n, d, width), d ** -0.5),
                "w_down": normal((n, width, d), width ** -0.5)}

    return {
        "embed": normal((V, d), 0.02),
        "dense_layers": {"ln1": normal((nd, d), 0.1, 1.0),
                         "ln2": normal((nd, d), 0.1, 1.0),
                         "attn": attn(nd), "mlp": mlp(nd, ff)},
        "layers": {
            "ln1": normal((nm, d), 0.1, 1.0),
            "ln2": normal((nm, d), 0.1, 1.0),
            "attn": attn(nm),
            "moe": {"w_router": normal((nm, d, E), d ** -0.5),
                    "w_gate": by_layer(nm, (E, d, f), d ** -0.5),
                    "w_up": by_layer(nm, (E, d, f), d ** -0.5),
                    "w_down": by_layer(nm, (E, f, d), f ** -0.5),
                    "shared": mlp(nm, fs)},
        },
        "final_norm": normal((d,), 0.1, 1.0),
        "head": normal((V, d), 0.02),
    }


def dot(eq: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    if precision != "bf16x3":
        raise ValueError(f"unknown reference precision {precision!r}")
    ah, al = _bf16_split(a)
    bh, bl = _bf16_split(b)
    one = lambda x, y: jnp.einsum(eq, x, y,
                                  preferred_element_type=jnp.float32)
    return (one(al, bh) + one(ah, bl)) + one(ah, bh)


def _bf16_split(x):
    """``x`` as a bfloat16 head and tail, rounded by ``reduce_precision``:
    a float32 -> bfloat16 -> float32 round trip by ``astype`` is one that
    XLA may drop as excess precision, which would leave the tail zero."""
    rp = lambda v: jax.lax.reduce_precision(v, exponent_bits=8,
                                            mantissa_bits=7)
    hi = rp(x)
    return hi.astype(jnp.bfloat16), rp(x - hi).astype(jnp.bfloat16)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn_tables(pos, hf):
    """cos, sin ``[S, dr/2]`` of DeepseekV2YarnRotaryEmbedding."""
    dim, base = hf["qk_rope_head_dim"], float(hf["rope_theta"])
    factor, orig, fast, slow, ms, ms_all = _yarn(hf)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** exps
    inter = 1.0 / (factor * base ** exps)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv_freq = inter * (1.0 - mask) + extra * mask
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    scale = _mscale(factor, ms) / _mscale(factor, ms_all)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _rope(x, cos, sin):                        # x [S, h, dr]
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def hidden(w, hf, tokens, precision: str = "highest"):
    """Final normed hidden states ``[S, d]`` of one sequence ``tokens``
    ``[S]``, every position attending causally to those before it. The
    layers run one at a time (a scan), and in a layer the experts run one
    at a time, so a long sequence fits."""
    H, R = hf["num_attention_heads"], hf["kv_lora_rank"]
    dn, dr, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                  hf["v_head_dim"])
    eps = hf["rms_norm_eps"]
    top_k = hf["num_experts_per_tok"]
    factor, *_, ms_all = _yarn(hf)
    scale = (dn + dr) ** -0.5 * _mscale(factor, ms_all) ** 2
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    cos, sin = _yarn_tables(pos, hf)
    mm = lambda eq, a, b: dot(eq, a, b, precision)

    def attention(x, p):
        a = p["attn"]
        h = _rmsnorm(x, p["ln1"], eps)
        q = mm("sd,de->se", h, a["wq"]).reshape(S, H, dn + dr)
        q_n, q_r = q[..., :dn], _rope(q[..., dn:], cos, sin)
        ckv = mm("sd,de->se", h, a["wkv_a"])
        c = _rmsnorm(ckv[:, :R], a["kv_norm"], eps)
        k_r = _rope(ckv[:, None, R:], cos, sin)[:, 0]
        kv = mm("sr,re->se", c, a["wkv_b"]).reshape(S, H, dn + dv)
        k_n, v = kv[..., :dn], kv[..., dn:]
        s = (mm("thd,shd->hts", q_n, k_n)
             + mm("thd,sd->hts", q_r, k_r)) * scale
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("hts,shd->thd", jax.nn.softmax(s, -1), v)
        return x + mm("se,ed->sd", o.reshape(S, H * dv), a["wo"])

    def mlp(h, m):
        g = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"]))
        u = mm("sd,df->sf", h, m["w_up"])
        return mm("sf,fd->sd", g * u, m["w_down"])

    def dense_layer(x, p):
        x = attention(x, p)
        return x + mlp(_rmsnorm(x, p["ln2"], eps), p["mlp"]), None

    def moe_layer(x, p):
        x = attention(x, p)
        h = _rmsnorm(x, p["ln2"], eps)
        m = p["moe"]
        probs = jax.nn.softmax(mm("sd,de->se", h, m["w_router"]), -1)
        _, top = jax.lax.top_k(probs, top_k)
        chosen = jnp.zeros_like(probs).at[
            jnp.arange(S)[:, None], top].set(1.0)
        gates = probs * chosen
        if hf["norm_topk_prob"]:
            gates = gates / jnp.sum(gates, -1, keepdims=True)
        gates = gates * hf["routed_scaling_factor"]

        def expert(y, e):
            wg, wu, wd, g = e
            return y + g[:, None] * mlp(h, {"w_gate": wg, "w_up": wu,
                                            "w_down": wd}), None

        y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                            (m["w_gate"], m["w_up"], m["w_down"], gates.T))
        return x + y + mlp(h, m["shared"]), None

    x = w["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(dense_layer, x, w["dense_layers"])
    x, _ = jax.lax.scan(moe_layer, x, w["layers"])
    return _rmsnorm(x, w["final_norm"], eps)


def logits(w, hf, h, precision: str = "highest"):
    """LM-head logits ``[R, vocab]`` of hidden rows ``h`` ``[R, d]``."""
    return dot("rd,vd->rv", h, w["head"], precision)
