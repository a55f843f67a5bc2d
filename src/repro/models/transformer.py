"""LM backbone assembly: one config-driven forward covering every registered
architecture (dense / GQA / MLA / MoE / SWA / local-global+softcap / RWKV6 /
hybrid attn+mamba), backend-generic (JOps for train/serve, CaaOps for the
paper's rigorous error analysis).

Layers are stacked along a leading axis and iterated with
``backend.layer_loop`` (lax.scan under JOps — O(1) HLO in depth, which is
what keeps 512-device compiles of 56-layer models tractable).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import layers as L
from . import moe as M
from . import ssm as S


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                       # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: Optional[int] = None      # default d_model // n_heads
    act: str = "silu"
    norm: str = "rmsnorm"
    qkv_bias: bool = False
    softcap_attn: Optional[float] = None
    softcap_final: Optional[float] = None
    window: Optional[int] = None               # SWA for every attn layer
    local_global_period: Optional[int] = None  # gemma2: even layers local
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    embed_scale: bool = False                  # gemma-style sqrt(d) scaling
    # YaRN rotary scaling (factor, original_max_positions, beta_fast,
    # beta_slow, mscale, mscale_all_dim); None: plain RoPE
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    # MoE
    n_experts: Optional[int] = None
    top_k: Optional[int] = None
    moe_d_ff: Optional[int] = None
    first_k_dense: int = 0          # leading dense layers (width d_ff)
    n_shared_experts: int = 0       # one gated MLP of n_shared·moe_d_ff
    norm_topk_prob: bool = True     # renormalise the k chosen gates
    routed_scaling: float = 1.0
    # MLA (q_rank None: queries straight from wq, no compression)
    mla: bool = False
    q_rank: Optional[int] = 768
    kv_rank: int = 256
    d_nope: int = 64
    d_rope: int = 32
    d_v: int = 64
    # SSM / hybrid
    rwkv: bool = False
    hybrid: bool = False
    ssm_state: int = 16
    mamba_expand: int = 2
    # enc-dec (whisper) & modality frontends (stubs per assignment)
    enc_dec: bool = False
    n_enc_layers: int = 0
    frontend: Optional[str] = None   # 'audio' | 'vision'
    frontend_seq: int = 0            # frames / patches supplied by the stub
    frontend_dim: int = 0            # stub embedding dim
    max_decode_seq: int = 448        # whisper decoder context cap

    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def subquadratic(self) -> bool:
        """True if decode state is O(1) in context (rwkv) or the arch is
        hybrid with bounded-window attention — the long_500k gate."""
        return self.rwkv or self.hybrid

    @property
    def attention_free(self) -> bool:
        return self.rwkv

    def param_count(self, params=None) -> int:
        if params is None:
            return -1
        return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, dense: bool = False) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    d, dh = cfg.d_model, cfg.head_dim
    p: Dict[str, Any] = {
        "ln1": jnp.ones((d,), jnp.float32),
        "ln2": jnp.ones((d,), jnp.float32),
    }
    if cfg.norm == "layernorm":
        p["ln1_b"] = jnp.zeros((d,), jnp.float32)
        p["ln2_b"] = jnp.zeros((d,), jnp.float32)
    if cfg.rwkv:
        p["tmix"] = S.init_rwkv_tmix(ks[0], d, cfg.n_heads)
        p["cmix"] = S.init_rwkv_cmix(ks[1], d, cfg.d_ff)
        return p
    if cfg.mla:
        p["attn"] = A.init_mla(ks[0], d, cfg.n_heads, cfg.q_rank, cfg.kv_rank,
                               cfg.d_nope, cfg.d_rope, cfg.d_v)
    else:
        p["attn"] = A.init_gqa(ks[0], d, cfg.n_heads, cfg.n_kv_heads, dh,
                               cfg.qkv_bias)
    if cfg.hybrid:
        p["mamba"] = S.init_mamba(ks[1], d, cfg.mamba_expand * d, cfg.ssm_state)
    if cfg.family == "moe" and not dense:
        p["moe"] = M.init_moe(ks[2], d, cfg.moe_d_ff or cfg.d_ff,
                              cfg.n_experts, cfg.n_shared_experts)
    else:
        p["mlp"] = {
            "w_gate": L.dense_init(ks[3], d, cfg.d_ff),
            "w_up": L.dense_init(ks[4], d, cfg.d_ff),
            "w_down": L.dense_init(ks[5], cfg.d_ff, d),
        }
    return p


def init_params(key, cfg: ArchConfig) -> Dict[str, Any]:
    k_emb, k_layers, k_head, k_enc, k_fr = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    nd = cfg.first_k_dense
    params: Dict[str, Any] = {
        "embed": L.embed_init(k_emb, cfg.vocab, cfg.d_model),
        "layers": jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys[nd:]),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: _init_layer(k, cfg, dense=True))(layer_keys[:nd])
    if not cfg.tie_embeddings:
        params["head"] = L.embed_init(k_head, cfg.vocab, cfg.d_model)
    if cfg.enc_dec:
        enc_keys = jax.random.split(k_enc, cfg.n_enc_layers)
        enc_cfg = dataclasses.replace(cfg, rwkv=False, hybrid=False,
                                      mla=False, family="dense")
        params["enc_layers"] = jax.vmap(lambda k: _init_layer(k, enc_cfg))(enc_keys)
        params["enc_norm"] = jnp.ones((cfg.d_model,), jnp.float32)
        params["cross"] = jax.vmap(
            lambda k: A.init_gqa(k, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim)
        )(jax.random.split(k_enc, cfg.n_layers))
    if cfg.frontend:
        params["frontend_proj"] = L.dense_init(
            k_fr, cfg.frontend_dim, cfg.d_model
        )
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _norm(bk, x, p, cfg, which: str):
    if cfg.norm == "layernorm":
        return L.layernorm(bk, x, p[which], p[which + "_b"])
    return L.rmsnorm(bk, x, p[which])


def _mlp_or_moe(bk, x, p, cfg: ArchConfig, experts=None, layer=0):
    """The layer's MLP: its routed experts are ``experts``' layer
    ``layer`` (the stack held whole) or ``p["moe"]``'s own."""
    if "moe" in p:
        return M.moe_mlp(bk, x, p["moe"], experts, layer,
                         n_experts=cfg.n_experts, top_k=cfg.top_k,
                         act=cfg.act, norm_topk_prob=cfg.norm_topk_prob,
                         routed_scaling=cfg.routed_scaling)
    return L.mlp_gated(bk, x, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], cfg.act)


def softmax_scale(cfg: ArchConfig) -> float:
    """Attention score scale: 1/√(query width), times YaRN's
    mscale(mscale_all_dim)² where the rotary embedding is YaRN's
    (DeepseekV2Attention)."""
    width = cfg.d_nope + cfg.d_rope if cfg.mla else cfg.head_dim
    m = 1.0 if cfg.yarn is None else L.yarn_mscale(cfg.yarn[0], cfg.yarn[5])
    return width ** -0.5 * m * m


def _layer_masks(cfg: ArchConfig, q_len: int, kv_len: int, q_offset=0):
    """(global_mask, local_mask_or_None) as exact booleans."""
    gmask = L.causal_mask(q_len, kv_len, q_offset, cfg.window)
    lmask = None
    if cfg.local_global_period:
        lmask = L.causal_mask(q_len, kv_len, q_offset,
                              cfg.local_global_period)
    return gmask, lmask


def forward(
    bk, params, cfg: ArchConfig, tokens=None, *,
    embeds=None,
    frontend_embeds=None,
    enc_embeds=None,
    enc_out=None,              # precomputed encoder states (decode reuse)
    cache=None,                # stacked per-layer cache pytree or None
    q_offset=0,
) -> Tuple[Any, Any]:
    """Returns (logits, new_cache). ``tokens``: [B, S] int32.

    ``frontend_embeds`` ([B, P, frontend_dim]) come from the modality stub
    (audio frames / vision patches) and are projected+prepended.
    ``enc_embeds`` are the whisper encoder-stub frames.
    """
    # named scopes bound the certified per-scope precision maps: "embed" /
    # "layer{i}" / "head" are the keys mixed/format certificates assign and
    # the serving backends resolve (repro.certify.lm ↔ launch/serve.py)
    with bk.scope("embed"):
        if embeds is None:
            x = L.embed(bk, params["embed"], tokens)
        else:
            x = embeds
        if cfg.embed_scale:
            x = bk.scale(x, math.sqrt(cfg.d_model))

        if frontend_embeds is not None:
            fr = bk.matmul(bk.input(frontend_embeds),
                           bk.param(params["frontend_proj"]))
            x = bk.concat([fr, x], axis=1)

    B, Sq, _ = bk.shape_of(x)
    kv_len = _cache_len(cache) if cache is not None else Sq
    if kv_len < 0:
        kv_len = Sq  # rwkv: O(1) state, no KV buffer
    # ragged decode (continuous batching): q_offset is a [B] vector of
    # per-lane absolute positions — rope tables and masks become per-lane
    ragged = (not isinstance(q_offset, int)
              and getattr(q_offset, "ndim", 0) == 1)
    if ragged and cache is None:
        raise ValueError("per-lane q_offset requires a KV cache")
    if ragged:
        positions = q_offset[:, None] + jnp.arange(Sq)[None, :]  # [B, Sq]
    elif isinstance(q_offset, int):
        positions = jnp.arange(Sq) + q_offset
    else:
        positions = jnp.arange(Sq) + q_offset
    rope_positions = jnp.arange(kv_len) if cache is not None else positions
    cos_full, sin_full = L.rope_tables(rope_positions, _rope_dim(cfg),
                                       cfg.rope_theta, cfg.yarn)
    if ragged:
        cos_q = jnp.take(cos_full, positions, axis=0)   # [B, Sq, half]
        sin_q = jnp.take(sin_full, positions, axis=0)
        gmask = L.lane_causal_mask(Sq, kv_len, q_offset, cfg.window)
        lmask = (L.lane_causal_mask(Sq, kv_len, q_offset,
                                    cfg.local_global_period)
                 if cfg.local_global_period else None)
    else:
        cos_q = cos_full[-Sq:] if cache is None else _take_rows(cos_full, positions, Sq)
        sin_q = sin_full[-Sq:] if cache is None else _take_rows(sin_full, positions, Sq)
        gmask, lmask = _layer_masks(cfg, Sq, kv_len, q_offset)

    # the fused flash-decode hook only sees the plain-causal S==1 step —
    # every masking rule it reproduces in-kernel from the lane lengths
    fused_ok = (cache is not None and Sq == 1 and not cfg.mla
                and not cfg.rwkv and cfg.softcap_attn is None
                and cfg.window is None and cfg.local_global_period is None)

    if cfg.enc_dec and enc_out is None:
        # serve callers precompute this at prefill: re-encoding 1500 frames
        # for every decoded token was a 3300x HLO-flop bug (§Perf)
        enc_out = encode(bk, params, cfg, enc_embeds)

    # routed experts stay whole, out of the scanned layer slices: each
    # layer's products read their [E, K, N] blocks in place, by index
    lp = dict(params["layers"])
    experts = None
    if "moe" in lp:
        moe = dict(lp["moe"])
        experts = {k: moe.pop(k) for k in M.EXPERT_KEYS}
        lp["moe"] = moe
    nd = cfg.first_k_dense

    def layer_fn(p, x, i, aux):
        x, aux_out = _one_layer(bk, p, x, i, aux, cfg, cos_q, sin_q,
                                gmask, lmask, enc_out, q_offset,
                                fused_ok=fused_ok, experts=experts,
                                expert_layer=i - nd)
        return x, aux_out

    if cfg.enc_dec:
        lp["cross"] = params["cross"]
    new_cache = cache
    if nd:
        x, new_cache = bk.layer_loop(layer_fn, params["dense_layers"], x,
                                     nd, aux=new_cache)
    x, new_cache = bk.layer_loop(layer_fn, lp, x, cfg.n_layers - nd,
                                 aux=new_cache, first=nd)

    with bk.scope("head"):
        x = L.rmsnorm(bk, x, params["final_norm"])
        head = params["embed"] if cfg.tie_embeddings else params["head"]
        logits = L.logits_head(bk, x, head, cfg.softcap_final)
        logits = bk.record("logits", logits, kind="head")
    return logits, new_cache


def _rope_dim(cfg: ArchConfig) -> int:
    return cfg.d_rope if cfg.mla else cfg.head_dim


def _take_rows(table, positions, Sq):
    if isinstance(positions, jnp.ndarray) and positions.shape == (Sq,):
        return jnp.take(table, positions, axis=0)
    return table[-Sq:]


def _cache_len(cache) -> int:
    if isinstance(cache, dict) and "k" in cache:
        return int(cache["k"].shape[-2])  # [L, B, (K,) Smax, X]
    return -1


def _one_layer(bk, p, x, i, aux, cfg, cos, sin, gmask, lmask, enc_out,
               q_offset, fused_ok: bool = False, experts=None,
               expert_layer=0):
    h = _norm(bk, x, p, cfg, "ln1")
    aux_out = None

    if cfg.rwkv:
        state = None
        if aux is not None:
            state = S.RwkvState(
                A.layer_of(aux["S"], i),
                bk.value_of(bk.input(A.layer_of(aux["x_tm"], i))))
        out, new_state = S.rwkv_tmix(bk, h, p["tmix"], n_heads=cfg.n_heads,
                                     state=state)
        x = bk.add(x, out)
        h2 = _norm(bk, x, p, cfg, "ln2")
        cm_prev = None if aux is None else A.layer_of(aux["x_cm"], i)
        x = bk.add(x, S.rwkv_cmix(bk, h2, p["cmix"], cm_prev))
        if aux is not None:
            aux_out = {
                "S": A.with_layer(aux["S"], i, new_state.S),
                "x_tm": A.with_layer(aux["x_tm"], i, new_state.x_prev),
                "x_cm": A.with_layer(aux["x_cm"], i,
                                     bk.value_of(h2)[:, -1, :])}
        return x, aux_out

    # pick this layer's mask (gemma2 alternation: even layers local)
    mask = gmask
    if lmask is not None:
        is_local = (i % 2 == 0) if isinstance(i, int) else (i % 2 == 0)
        mask = jnp.where(is_local, lmask, gmask) if not isinstance(is_local, bool) \
            else (lmask if is_local else gmask)

    kv_cache = None
    if aux is not None:
        kv_cache = A.KVCache(aux["k"], aux["v"], A.layer_of(aux["idx"], i),
                             i)

    # named sub-layer scopes: per-scope knobs (formats, range lanes) can
    # resolve layer*/attn and layer*/mlp below per-layer granularity
    with bk.scope("attn"):
        if cfg.mla:
            out, new_kv = A.mla_attention(
                bk, h, p["attn"], n_heads=cfg.n_heads, q_rank=cfg.q_rank,
                kv_rank=cfg.kv_rank, d_nope=cfg.d_nope, d_rope=cfg.d_rope,
                d_v=cfg.d_v, cos=cos, sin=sin, mask=mask, cache=kv_cache,
                q_offset=q_offset, softmax_scale=softmax_scale(cfg))
        else:
            out, new_kv = A.gqa_attention(
                bk, h, p["attn"], n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads,
                d_head=cfg.head_dim, cos=cos, sin=sin, mask=mask,
                softcap=cfg.softcap_attn, qkv_bias=cfg.qkv_bias,
                cache=kv_cache, q_offset=q_offset,
                fused_decode=fused_ok)

    h_ssm_out = None
    if cfg.hybrid:
        h0 = None if aux is None else A.layer_of(aux["h_ssm"], i)
        m_out, h_ssm_out = S.mamba_lite(bk, h, p["mamba"],
                                        d_state=cfg.ssm_state, h0=h0,
                                        return_state=True)
        out = bk.scale(bk.add(out, m_out), 0.5, exact_const=True)

    x = bk.add(x, out)

    if cfg.enc_dec and enc_out is not None:
        hc = _norm(bk, x, p, cfg, "ln1")
        c_out, _ = _cross_attention(bk, hc, enc_out, p["cross"], cfg)
        x = bk.add(x, c_out)

    h2 = _norm(bk, x, p, cfg, "ln2")
    with bk.scope("mlp"):
        mlp_out = _mlp_or_moe(bk, h2, p, cfg, experts, expert_layer)
    x = bk.add(x, mlp_out)

    if new_kv is not None:
        aux_out = {"k": new_kv.k, "v": new_kv.v,
                   "idx": A.with_layer(aux["idx"], i, new_kv.index)}
        if h_ssm_out is not None:
            aux_out["h_ssm"] = A.with_layer(aux["h_ssm"], i, h_ssm_out)
    return x, aux_out


def _cross_attention(bk, x, enc_out, p, cfg: ArchConfig):
    """Decoder→encoder attention (whisper). No mask (full visibility)."""
    B, Sq, _ = bk.shape_of(x)
    Se = bk.shape_of(enc_out)[1]
    mask = jnp.ones((Sq, Se), bool)
    zeros = jnp.zeros(Se, jnp.float32)
    cos = jnp.ones((max(Sq, Se), cfg.head_dim // 2), jnp.float32)
    sin = jnp.zeros((max(Sq, Se), cfg.head_dim // 2), jnp.float32)

    # q from decoder, k/v from encoder — reuse GQA plumbing manually
    q = bk.matmul(x, bk.param(p["wq"]))
    k = bk.matmul(enc_out, bk.param(p["wk"]))
    v = bk.matmul(enc_out, bk.param(p["wv"]))
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    q = bk.reshape(q, (B, Sq, K, G, dh))
    k = bk.reshape(k, (B, Se, K, dh))
    v = bk.reshape(v, (B, Se, K, dh))
    scores = bk.scale(bk.einsum("bqkgd,bskd->bkgqs", q, k), dh ** -0.5)
    probs = bk.softmax(scores, axis=-1)
    out = bk.einsum("bkgqs,bskd->bqkgd", probs, v)
    if bk.is_analysis:
        vlo = jnp.min(v.exact.lo, axis=1)[:, None, :, None, :]
        vhi = jnp.max(v.exact.hi, axis=1)[:, None, :, None, :]
        out = bk.clamp_range(out, vlo, vhi)
    out = bk.reshape(out, (B, Sq, H * dh))
    return bk.matmul(out, bk.param(p["wo"])), None


def encode(bk, params, cfg: ArchConfig, enc_embeds):
    """Whisper encoder stack: bidirectional self-attention over the stub's
    frame embeddings (conv frontend is a stub per the assignment)."""
    x = bk.matmul(bk.input(enc_embeds), bk.param(params["frontend_proj"]))
    Se = bk.shape_of(x)[1]
    cos, sin = L.rope_tables(jnp.arange(Se), cfg.head_dim, cfg.rope_theta)
    mask = jnp.ones((Se, Se), bool)

    def layer_fn(p, x, i, aux):
        h = _norm(bk, x, p, cfg, "ln1")
        out, _ = A.gqa_attention(
            bk, h, p["attn"], n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            d_head=cfg.head_dim, cos=cos, sin=sin, mask=mask)
        x = bk.add(x, out)
        h2 = _norm(bk, x, p, cfg, "ln2")
        x = bk.add(x, _mlp_or_moe(bk, h2, p, cfg))
        return x, None

    x, _ = bk.layer_loop(layer_fn, params["enc_layers"], x, cfg.n_enc_layers)
    return L.rmsnorm(bk, x, params["enc_norm"])


def analytic_params(cfg: ArchConfig, active: bool = False) -> int:
    """Closed-form parameter count (MoE: total vs active) — drives the
    roofline model and the per-arch auto policies (§Perf policy matrix)."""
    d, dh = cfg.d_model, cfg.head_dim
    emb = cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    per_layer = dense_swap = 0
    if cfg.rwkv:
        per_layer += 5 * d * d + d * 64 + 64 * d
        per_layer += d * cfg.d_ff + cfg.d_ff * d + d * d
    else:
        if cfg.mla:
            q_in = d if cfg.q_rank is None else cfg.q_rank
            per_layer += (0 if cfg.q_rank is None else d * cfg.q_rank)
            per_layer += q_in * cfg.n_heads * (cfg.d_nope + cfg.d_rope)
            per_layer += d * (cfg.kv_rank + cfg.d_rope)
            per_layer += cfg.kv_rank * cfg.n_heads * (cfg.d_nope + cfg.d_v)
            per_layer += cfg.n_heads * cfg.d_v * d
        else:
            per_layer += d * cfg.n_heads * dh + 2 * d * cfg.n_kv_heads * dh
            per_layer += cfg.n_heads * dh * d
        if cfg.hybrid:
            di = cfg.mamba_expand * d
            per_layer += 2 * d * di + di * (2 * cfg.ssm_state + 1) + di * d
        if cfg.family == "moe":
            e = cfg.n_experts if not active else cfg.top_k
            ff = cfg.moe_d_ff or cfg.d_ff
            moe_mlp = (d * cfg.n_experts
                       + (e + cfg.n_shared_experts) * 3 * d * ff)
            # leading dense layers: a dense MLP in place of the experts
            dense_swap = cfg.first_k_dense * (3 * d * cfg.d_ff - moe_mlp)
            per_layer += moe_mlp
        else:
            per_layer += 3 * d * cfg.d_ff
    n = emb + cfg.n_layers * per_layer + dense_swap
    if cfg.enc_dec:
        n += cfg.n_enc_layers * (4 * d * dh * cfg.n_heads + 3 * d * cfg.d_ff)
        n += cfg.n_layers * 4 * d * dh * cfg.n_heads
    return n


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, *,
               per_lane_idx: bool = False) -> Dict[str, jax.Array]:
    """Stacked per-layer decode cache. RWKV: O(1) state. MLA: compressed
    latent [L, B, Smax, R] and rope key [L, B, Smax, Dr]. GQA: keys and
    values [L, B, K, Smax, Dh], heads-major so that attention reads a
    layer in place. Positions run along the second-to-last axis.

    ``per_lane_idx=True`` gives each batch lane its own write index
    ([L, B] instead of [L]) — the continuous-batching engine's cache,
    where lanes prefill/decode at independent positions."""
    Lh = cfg.n_layers
    idx = (jnp.zeros((Lh, batch), jnp.int32) if per_lane_idx
           else jnp.zeros((Lh,), jnp.int32))
    if cfg.rwkv:
        C = cfg.d_model // cfg.n_heads
        return {
            "S": jnp.zeros((Lh, batch, cfg.n_heads, C, C), dtype),
            "x_tm": jnp.zeros((Lh, batch, cfg.d_model), dtype),
            "x_cm": jnp.zeros((Lh, batch, cfg.d_model), dtype),
        }
    if cfg.mla:
        return {
            "k": jnp.zeros((Lh, batch, max_seq, cfg.kv_rank), dtype),
            "v": jnp.zeros((Lh, batch, max_seq, cfg.d_rope), dtype),
            "idx": idx,
        }
    out = {
        "k": jnp.zeros((Lh, batch, cfg.n_kv_heads, max_seq, cfg.head_dim), dtype),
        "v": jnp.zeros((Lh, batch, cfg.n_kv_heads, max_seq, cfg.head_dim), dtype),
        "idx": idx,
    }
    if cfg.hybrid:
        out["h_ssm"] = jnp.zeros(
            (Lh, batch, cfg.mamba_expand * cfg.d_model, cfg.ssm_state), dtype
        )
    return out


# --------------------------------------------------------------------------
# losses / steps (jnp-only fast path)
# --------------------------------------------------------------------------

def next_token_loss(bk, params, cfg: ArchConfig, tokens, targets,
                    frontend_embeds=None, enc_embeds=None):
    logits, _ = forward(bk, params, cfg, tokens,
                        frontend_embeds=frontend_embeds,
                        enc_embeds=enc_embeds)
    logits = bk.value_of(logits)
    if frontend_embeds is not None:
        # loss only on the text positions (suffix)
        logits = logits[:, -targets.shape[1]:]
    logits = logits.astype(jnp.float32)

    # Keep the vocab dim model-sharded through the whole loss: a gather (or
    # an unconstrained one-hot) makes XLA replicate the [B,S,V] f32 logits —
    # 67 GiB per copy for the 256k-vocab archs (§Perf train iteration 3).
    def _vshard(t):
        mesh = getattr(bk, "mesh", None)
        if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
            return t
        from jax.sharding import NamedSharding, PartitionSpec as P
        m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        if t.shape[-1] % m:
            return t
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        spec = P(dp or None, *([None] * (t.ndim - 2)), "model")
        return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))

    logits = _vshard(logits)
    onehot = _vshard(jax.nn.one_hot(targets, logits.shape[-1],
                                    dtype=logits.dtype))
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.einsum("bsv,bsv->bs", logits, onehot)
    return (lse - picked).mean()
