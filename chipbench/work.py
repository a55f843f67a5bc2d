"""The work a result needs, from shapes and a run's token counts.

Needed work is what a correct implementation cannot do without, not what
the program does:

- a decode token attends to its lane's live positions, not to
  ``max_seq``; a prompt's attention is causal;
- logits are counted for emitted rows only: every decode row, and the
  last row of each prompt;
- padding (idle lanes, prompt pages) is not counted;
- weights and KV are counted at the narrowest width that holds their
  scope's format (sign, IEEE exponent field, k - 1 stored bits), or at 32
  bits where the configuration serves float32; activations likewise.

FLOPs are multiply-adds times two, of the matrix products only.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

F32 = 32


def exponent_bits(emax: int, emin: int) -> int:
    """Smallest IEEE exponent field e with 2^(e-1) - 1 >= emax and
    2 - 2^(e-1) <= emin."""
    e = 2
    while 2 ** (e - 1) - 1 < emax or 2 - 2 ** (e - 1) > emin:
        e += 1
    return e


def format_bits(fmt: Dict[str, int]) -> int:
    return 1 + exponent_bits(fmt["emax"], fmt["emin"]) + fmt["k"] - 1


def resolve(fmt_map: Dict[str, Dict[str, int]], path: List[str]):
    """The format of scope ``path`` (``["layer3", "attn"]``): the most
    specific key that matches, by (segments, exact segments), later keys
    winning ties; ``layer*`` matches any layer; ``""`` is the default."""
    best, spec = fmt_map[""], (0, -1)
    for key, fmt in fmt_map.items():
        if not key:
            continue
        segs = key.split("/")
        if len(segs) > len(path):
            continue
        if all(s == p or (s == "layer*" and p.startswith("layer"))
               for s, p in zip(segs, path)):
            ks = (len(segs), sum(s != "layer*" for s in segs))
            if ks >= spec:
                best, spec = fmt, ks
    return best


def bits(fmt_map: Optional[Dict], path: List[str]) -> int:
    return F32 if not fmt_map else format_bits(resolve(fmt_map, path))


def layer_gemms(arch) -> List[Tuple[str, int, int]]:
    """(scope, K, N) of each weight product of one decoder layer."""
    d, H, K, D, ff = (arch["d_model"], arch["n_heads"], arch["n_kv_heads"],
                      arch["d_head"], arch["d_ff"])
    return [("attn", d, H * D), ("attn", d, K * D), ("attn", d, K * D),
            ("attn", H * D, d), ("mlp", d, ff), ("mlp", d, ff),
            ("mlp", ff, d)]


def gemm(M: int, K: int, N: int, b: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of an [M, K] @ [K, N] product, every value once."""
    return 2.0 * M * K * N, (K * N + M * K + M * N) * b / 8.0


def decode_gemms(arch, fmt_map, lanes: int) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each layer weight product of one decode step."""
    return [gemm(lanes, K, N, bits(fmt_map, [f"layer{i}", scope]))
            for i in range(arch["n_layers"])
            for scope, K, N in layer_gemms(arch)]


def decode_attention(arch, fmt_map, lanes: int, kv: int
                     ) -> List[Tuple[float, float]]:
    """(FLOPs, bytes) of each layer's decode attention: ``lanes`` queries
    over ``kv`` live positions in all (the sum over lanes)."""
    H, K, D = arch["n_heads"], arch["n_kv_heads"], arch["d_head"]
    out = []
    for i in range(arch["n_layers"]):
        b = bits(fmt_map, [f"layer{i}", "attn"])
        out.append((4.0 * H * D * kv,
                    (2.0 * kv * K * D + 2.0 * lanes * H * D) * b / 8.0))
    return out


def head_flops(arch, rows: int) -> float:
    return 2.0 * rows * arch["d_model"] * arch["vocab"]


def decode_flops(arch, lanes: int, kv: int) -> float:
    """Needed FLOPs of one decode step."""
    return (sum(f for f, _ in decode_gemms(arch, None, lanes))
            + sum(f for f, _ in decode_attention(arch, None, lanes, kv))
            + head_flops(arch, lanes))


def prefill_flops(arch, prompt: int) -> float:
    """Needed FLOPs of one prompt: its weight products, causal attention
    over the prompt, and the logits of its last row."""
    P = prompt
    per_layer = sum(2.0 * P * K * N for _, K, N in layer_gemms(arch))
    attn = 4.0 * arch["n_heads"] * arch["d_head"] * P * (P + 1) / 2
    return arch["n_layers"] * (per_layer + attn) + head_flops(arch, 1)


def roofline_s(work: List[Tuple[float, float]], peak: Dict) -> float:
    """Least time of a list of (FLOPs, bytes) calls, each bound by the
    larger of its compute and its memory time."""
    return sum(max(f / peak["flops"], b / peak["hbm_bytes_s"])
               for f, b in work)
