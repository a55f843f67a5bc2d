"""The work a result needs, for a decoder of latent attention (MLA, no query
compression) and routed plus shared experts after leading dense layers
(DeepSeek-V2), from shapes and a run's token counts.

The rules are :mod:`chipbench.work`'s: live KV only, emitted rows only, no
padding, each value at the narrowest width that holds its scope's format
(or 32 bits without a map); FLOPs are multiply-adds times two, of the
matrix products only. Besides:

- Routed experts: the FLOPs are exact, rows · k · 3 · 2 · d · f (each row
  through its k experts, nothing dropped). The bytes count the weights of
  every expert the layer holds, at the scope's format width (18 bits at
  k 12, emax 15, emin -24), and each row's activations once per choice.
  A step that leaves an expert untouched needs none of its bytes, so
  this over-counts needed bytes by the share of experts a step leaves
  untouched: at 32 lanes, top-6 of 64, that is (1 - 6/64)^32 = 4.3% of
  the experts on average, and a roofline share still cannot pass 100% by
  more than that.
- Latent attention: the fewer FLOPs of its two forms at the given
  lengths. Non-absorbed: the new token's keys and values expanded from
  the latent (2·R·H·(dn + dv)), then scores and context over the live
  positions (2·H·(dn + dr + dv) per position). Absorbed: the query and
  output taken through the latent (2·H·R·(dn + dv)), then scores over
  latent and rope key and context in the latent (2·H·(2R + dr) per
  position). The latent projection wkv_b runs in attention products of
  float32 XLA, not in the format GEMM.
"""
from __future__ import annotations

from typing import List, Tuple

from chipbench.work import bits, gemm, head_flops


def _dims(arch):
    return (arch["d_model"], arch["n_heads"], arch["kv_rank"],
            arch["d_nope"], arch["d_rope"], arch["d_v"])


def n_dense(arch) -> int:
    return arch.get("first_k_dense", 0)


def attn_gemms(arch) -> List[Tuple[str, int, int]]:
    """(scope, K, N) of each weight product of one layer's attention that
    runs on the format GEMM."""
    d, H, R, dn, dr, dv = _dims(arch)
    return [("attn", d, H * (dn + dr)), ("attn", d, R + dr),
            ("attn", H * dv, d)]


def mlp_gemms(arch, layer: int) -> List[Tuple[str, int, int]]:
    """(scope, K, N) of each dense weight product of layer ``layer``'s MLP:
    the dense MLP of a leading layer; the router and the shared experts
    of an MoE layer."""
    d = arch["d_model"]
    if layer < n_dense(arch):
        ff = arch["d_ff"]
        return [("mlp", d, ff), ("mlp", d, ff), ("mlp", ff, d)]
    fs = arch["n_shared_experts"] * arch["moe_d_ff"]
    out = [("mlp/router", d, arch["n_experts"])]
    if fs:
        out += [("mlp/shared", d, fs), ("mlp/shared", d, fs),
                ("mlp/shared", fs, d)]
    return out


def expert_gemms(arch, fmt_map, layer: int, rows: int
                 ) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of the three routed-expert products of layer
    ``layer`` for ``rows`` tokens: exact FLOPs, every held expert's
    weights."""
    if layer < n_dense(arch):
        return []
    d, f, E, k = (arch["d_model"], arch["moe_d_ff"], arch["n_experts"],
                  arch["top_k"])
    b = bits(fmt_map, [f"layer{layer}", "mlp", "experts"])
    out = []
    for K, N in ((d, f), (d, f), (f, d)):
        out.append((2.0 * rows * k * K * N,
                    (E * K * N + rows * k * (K + N)) * b / 8.0))
    return out


def _path(layer: int, scope: str) -> List[str]:
    return [f"layer{layer}", *scope.split("/")]


def decode_gemms(arch, fmt_map, lanes: int) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each product of one decode step that runs on the
    format GEMM kernel: projections, dense MLP, router, shared and routed
    experts."""
    out = []
    for i in range(arch["n_layers"]):
        for scope, K, N in attn_gemms(arch) + mlp_gemms(arch, i):
            out.append(gemm(lanes, K, N, bits(fmt_map, _path(i, scope))))
        out += expert_gemms(arch, fmt_map, i, lanes)
    return out


def attention_flops(arch, queries: int, kv: int) -> float:
    """Latent attention FLOPs of ``queries`` tokens over ``kv`` live
    positions in all, in the cheaper form."""
    d, H, R, dn, dr, dv = _dims(arch)
    fixed = 2.0 * queries * R * H * (dn + dv)
    return fixed + kv * 2.0 * H * min(dn + dr + dv, 2 * R + dr)


def layer_weight_flops(arch, layer: int, tokens: int) -> float:
    """Weight-product FLOPs of layer ``layer`` for ``tokens`` tokens."""
    f = sum(2.0 * tokens * K * N
            for _, K, N in attn_gemms(arch) + mlp_gemms(arch, layer))
    return f + sum(x for x, _ in expert_gemms(arch, None, layer, tokens))


def decode_flops(arch, lanes: int, kv: int) -> float:
    """Needed FLOPs of one decode step: ``lanes`` tokens over ``kv`` live
    positions in all (the sum over lanes)."""
    return (sum(layer_weight_flops(arch, i, lanes)
                for i in range(arch["n_layers"]))
            + arch["n_layers"] * attention_flops(arch, lanes, kv)
            + head_flops(arch, lanes))


def prefill_flops(arch, prompt: int) -> float:
    """Needed FLOPs of one prompt: its weight products, causal attention
    over the prompt, and the logits of its last row."""
    P = prompt
    return (sum(layer_weight_flops(arch, i, P)
                for i in range(arch["n_layers"]))
            + arch["n_layers"] * attention_flops(arch, P, P * (P + 1) // 2)
            + head_flops(arch, 1))
