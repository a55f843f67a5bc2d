"""Plain float32 reference of the Qwen2 decoder (arXiv:2407.10671), and the
weights that it and the program are both given.

Straight ``jax.numpy`` from the published layer equations; it imports
nothing of the program:

    h   = x + Wo · attn(rope(RMSNorm(x) Wq + bq), rope(· Wk + bk), · Wv + bv)
    out = h + W_down (silu(RMSNorm(h) W_gate) ⊙ RMSNorm(h) W_up)

RMSNorm ε = ``rms_norm_eps``, rotary embeddings over the whole head in the
half-split convention with base ``rope_theta``, grouped-query causal
softmax attention scaled by 1/√d_head, untied LM head.

Every product goes through :func:`dot`, in one of two precisions:
``"highest"``, float32 (``Precision.HIGHEST``), and ``"bf16x3"``, the
three bfloat16 passes of ``Precision.HIGH`` written out, so that the
control computes the same arithmetic on every platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def program_arch(hf, name: str) -> dict:
    """Keyword arguments of the program's ``ArchConfig`` for ``hf``."""
    return dict(name=name, family="dense", n_layers=hf["num_hidden_layers"],
                d_model=hf["hidden_size"],
                n_heads=hf["num_attention_heads"],
                n_kv_heads=hf["num_key_value_heads"],
                d_head=hf["hidden_size"] // hf["num_attention_heads"],
                d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
                qkv_bias=True, rope_theta=float(hf["rope_theta"]),
                tie_embeddings=hf["tie_word_embeddings"])


def _shapes(hf):
    d, ff, V = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    H, K = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = d // H
    n = hf["num_hidden_layers"]
    return d, ff, V, H, K, D, n


def init_weights(hf, seed_lo, seed_hi):
    """The weight tree, drawn from the seed (two uint32 halves, traced, so
    one compiled program serves every seed), in the program's layout.
    Scales keep activations O(1): N(0, 1/fan_in) projections, N(0, 0.02²)
    embeddings and head, biases N(0, 0.02²), norm gains 1 + N(0, 0.1²)."""
    d, ff, V, H, K, D, n = _shapes(hf)
    key = jax.random.fold_in(jax.random.key(seed_lo), seed_hi)
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale, mean=0.0):
        return mean + scale * jax.random.normal(next(ks), shape, jnp.float32)

    return {
        "embed": normal((V, d), 0.02),
        "layers": {
            "ln1": normal((n, d), 0.1, 1.0),
            "ln2": normal((n, d), 0.1, 1.0),
            "attn": {"wq": normal((n, d, H * D), d ** -0.5),
                     "wk": normal((n, d, K * D), d ** -0.5),
                     "wv": normal((n, d, K * D), d ** -0.5),
                     "wo": normal((n, H * D, d), (H * D) ** -0.5),
                     "bq": normal((n, H * D), 0.02),
                     "bk": normal((n, K * D), 0.02),
                     "bv": normal((n, K * D), 0.02)},
            "mlp": {"w_gate": normal((n, d, ff), d ** -0.5),
                    "w_up": normal((n, d, ff), d ** -0.5),
                    "w_down": normal((n, ff, d), ff ** -0.5)},
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


def _rope(x, pos, theta):                      # x [S, H, D]
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def hidden(w, hf, tokens, precision: str = "highest"):
    """Final normed hidden states ``[S, d]`` of one sequence ``tokens``
    ``[S]``, every position attending causally to those before it. The
    layers run one at a time (a scan), so a long sequence fits."""
    d, ff, V, H, K, D, n = _shapes(hf)
    eps = hf["rms_norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    mm = lambda eq, a, b: dot(eq, a, b, precision)
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        h = _rmsnorm(x, p["ln1"], eps)
        q = mm("sd,de->se", h, a["wq"]) + a["bq"]
        k = mm("sd,de->se", h, a["wk"]) + a["bk"]
        v = mm("sd,de->se", h, a["wv"]) + a["bv"]
        q = _rope(q.reshape(S, H, D), pos, hf["rope_theta"])
        k = _rope(k.reshape(S, K, D), pos, hf["rope_theta"])
        q = q.reshape(S, K, H // K, D)
        v = v.reshape(S, K, D)
        s = mm("qkgd,skd->kgqs", q, k) * D ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
        x = x + mm("se,ed->sd", o.reshape(S, H * D), a["wo"])
        h = _rmsnorm(x, p["ln2"], eps)
        m = p["mlp"]
        g = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"]))
        u = mm("sd,df->sf", h, m["w_up"])
        return x + mm("sf,fd->sd", g * u, m["w_down"]), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rmsnorm(x, w["final_norm"], eps)


def logits(w, hf, h, precision: str = "highest"):
    """LM-head logits ``[R, vocab]`` of hidden rows ``h`` ``[R, d]``."""
    return dot("rd,vd->rv", h, w["head"], precision)
