"""Sharding rule engine: map every tensor in the system onto the mesh.

Strategy (hybrid FSDP × TP × EP, DESIGN.md §5):
  * parameters: greedy largest-divisible-dims assignment — "model" goes to
    the biggest tensor-parallel-friendly dim (d_ff, experts, vocab,
    heads·head_dim), "data" (and "pod" when present and the tensor is
    large) to the next — i.e. fully-sharded (ZeRO-3-like) storage; XLA SPMD
    inserts the per-layer all-gathers;
  * activations/batch: batch over ("pod","data"); fall back to sequence
    sharding when the batch doesn't divide (long_500k has batch 1);
  * KV caches: batch over "data" when divisible else sequence; KV heads
    over "model" when divisible else sequence over "model" (XLA then
    builds the flash-style distributed softmax reductions).

Everything returns NamedShardings so the same rules serve jit in_shardings,
device_put, and the dry-run's ShapeDtypeStruct annotations.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# -- helpers ---------------------------------------------------------------

# parameter subtrees stacked [L, ...] along a leading layer axis
STACKS = ("layers", "dense_layers", "enc_layers", "cross")

def _axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _greedy_param_spec(shape, mesh: Mesh, *, stacked: bool,
                       min_shard_bytes: int = 1 << 20,
                       axes=None) -> P:
    """Assign mesh axes to tensor dims, biggest-first.

    ``stacked``: leading dim is the scanned layer axis — never sharded
    (scan iterates it). Small tensors (< min_shard_bytes) replicate: the
    all-gather latency isn't worth it. ``axes`` restricts which mesh axes
    may be used (serving passes ("model",)).
    """
    dims = list(shape)
    start = 1 if stacked and len(dims) > 1 else 0
    nbytes = int(np.prod(shape)) * 4
    spec = [None] * len(dims)
    if nbytes < min_shard_bytes:
        return P(*spec)
    # order candidate dims by size, largest first
    order = sorted(range(start, len(dims)), key=lambda i: -dims[i])
    cand = axes if axes is not None else ("model", "data", "pod")
    axes_to_place = [a for a in cand if _axis_size(mesh, a) > 1]
    for ax in axes_to_place:
        sz = _axis_size(mesh, ax)
        for i in order:
            if spec[i] is None and dims[i] % sz == 0 and dims[i] >= sz:
                spec[i] = ax
                break
    return P(*spec)


def shard_params(params, mesh: Mesh, *, model_only: bool = False) -> Any:
    """NamedSharding pytree for a parameter tree (stacked layer dicts).

    model_only=True keeps parameters resident on the "model" axis and
    REPLICATED across data/pod — the serving policy (§Perf): a data-axis-
    sharded parameter must be all-gathered on every forward pass, which
    dominates decode's collective term; replication trades HBM capacity
    (P/16 per chip instead of P/256) for zero per-step parameter traffic.
    """
    def one(path, leaf):
        shape = leaf.shape if hasattr(leaf, "shape") else ()
        keys = [getattr(k, "key", str(k)) for k in path]
        stacked = any(k in STACKS for k in keys)
        # expert-parallel weights: shard the expert dim over "model" (the
        # shard_map MoE path requires it); [L, E, d, ff] → P(None,"model",..)
        if "moe" in keys and keys[-1] in ("w_gate", "w_up", "w_down"):
            e_dim = 1 if stacked else 0
            m_sz = _axis_size(mesh, "model")
            if len(shape) > e_dim and shape[e_dim] % m_sz == 0 and m_sz > 1:
                spec = [None] * len(shape)
                spec[e_dim] = "model"
                # remaining big dims may still take data (ZeRO storage)
                if not model_only:
                    d_sz = _axis_size(mesh, "data")
                    for i in sorted(range(e_dim + 1, len(shape)),
                                    key=lambda i: -shape[i]):
                        if shape[i] % d_sz == 0 and d_sz > 1:
                            spec[i] = "data"
                            break
                return NamedSharding(mesh, P(*spec))
        spec = _greedy_param_spec(shape, mesh, stacked=stacked,
                                  axes=("model",) if model_only else None)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def batch_spec(mesh: Mesh, batch: int, seq: int) -> P:
    """[B, S] token batches. Batch goes over every DP axis that divides it;
    axes the batch cannot absorb (e.g. long_500k's batch of 1) move to the
    sequence dim — sequence parallelism as the fallback."""
    dp = [a for a in ("pod", "data") if _axis_size(mesh, a) > 1]
    b_use, s_use = [], []
    rem_b, rem_s = batch, seq
    for a in dp:
        sz = _axis_size(mesh, a)
        if rem_b % sz == 0 and rem_b >= sz:
            b_use.append(a)
            rem_b //= sz
        elif rem_s % sz == 0 and rem_s >= sz:
            s_use.append(a)
            rem_s //= sz
    b_axes = tuple(b_use) if b_use else None
    s_axes = tuple(s_use) if s_use else None
    return P(b_axes, s_axes)


def shard_batch(mesh: Mesh, batch: int, seq: int) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, batch, seq))


def cache_spec(mesh: Mesh, cache_leaf_shape, kind: str) -> P:
    """Decode caches, stacked [L, B, ...]:
      gqa k/v: [L, B, K, S, Dh]; mla: [L, B, S, R]; rwkv S: [L,B,H,C,C].
    """
    shape = list(cache_leaf_shape)
    spec = [None] * len(shape)
    if len(shape) < 3:
        return P(*spec)
    d_sz = _axis_size(mesh, "data")
    m_sz = _axis_size(mesh, "model")
    B = shape[1]
    # batch over data when divisible, else seq over data
    if B % d_sz == 0 and B >= d_sz:
        spec[1] = "data"
        seq_data = False
    else:
        seq_data = True
    if kind == "gqa":  # [L,B,K,S,Dh]
        K, S = shape[2], shape[3]
        if K % m_sz == 0 and K >= m_sz:
            spec[2] = "model"
            if seq_data and S % d_sz == 0:
                spec[3] = "data"
        elif S % (m_sz * (d_sz if seq_data else 1)) == 0:
            spec[3] = ("data", "model") if seq_data else "model"
        elif S % m_sz == 0:
            spec[3] = "model"
    elif kind == "mla":  # [L,B,S,R]
        S = shape[2]
        div = m_sz * (d_sz if seq_data else 1)
        if S % div == 0:
            spec[2] = ("data", "model") if seq_data else "model"
        elif S % m_sz == 0:
            spec[2] = "model"
    elif kind == "rwkv":  # [L,B,H,C,C] or [L,B,d]
        if len(shape) >= 4 and shape[2] % m_sz == 0:
            spec[2] = "model"
        elif len(shape) == 3 and shape[2] % m_sz == 0:
            spec[2] = "model"
    return P(*spec)


def shard_cache(cache, mesh: Mesh, cfg) -> Any:
    def one(path, leaf):
        key = getattr(path[-1], "key", str(path[-1]))
        if key in ("k", "v"):
            kind = "mla" if getattr(cfg, "mla", False) else "gqa"
        elif key in ("S", "h_ssm"):
            kind = "rwkv"
        else:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, cache_spec(mesh, leaf.shape, kind))

    return jax.tree_util.tree_map_with_path(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# -- serving (bitwise-safe) rules ------------------------------------------
#
# The continuous-batching engine asserts BIT-FOR-BIT equality against a
# single-device eager reference, which outlaws any partitioning that splits
# a reduction (partial sums reassociate the accumulation): no contraction-
# dim weight sharding, no sequence-over-"model" KV (the distributed-softmax
# pattern), no batch-matmul contraction splits. What remains is exactly
# Megatron column parallelism (shard each weight's OUTPUT dim over "model")
# plus lane parallelism (shard batch/cache lanes over "data") — every
# collective XLA inserts is then an all-gather/slice of exact values.

def serving_param_spec(path_keys, shape, mesh: Mesh, *,
                       min_shard_bytes: int = 1 << 16) -> P:
    """Column-parallel spec for one serving parameter.

    ``embed``/``head`` tables [vocab, d] shard the vocab dim (the embed
    gather and the head einsum's non-contracting dim); every other ≥2-D
    weight shards its LAST dim (the matmul output dim — never the
    contraction). Stacked [L, ...] tensors skip the scanned leading axis.
    1-D tensors (norm scales, biases) replicate.
    """
    m_sz = _axis_size(mesh, "model")
    spec = [None] * len(shape)
    nbytes = int(np.prod(shape)) * 4 if shape else 0
    if m_sz <= 1 or len(shape) < 2 or nbytes < min_shard_bytes:
        return P(*spec)
    stacked = any(k in STACKS for k in path_keys)
    if path_keys and path_keys[-1] in ("embed", "head"):
        dim = 1 if stacked else 0
    else:
        dim = len(shape) - 1
    if shape[dim] % m_sz == 0 and shape[dim] >= m_sz:
        spec[dim] = "model"
    return P(*spec)


def shard_params_serving(params, mesh: Mesh, *,
                         min_shard_bytes: int = 1 << 16) -> Any:
    """NamedSharding pytree under the bitwise-safe serving rules."""
    def one(path, leaf):
        shape = tuple(leaf.shape) if hasattr(leaf, "shape") else ()
        keys = [getattr(k, "key", str(k)) for k in path]
        return NamedSharding(mesh, serving_param_spec(
            keys, shape, mesh, min_shard_bytes=min_shard_bytes))

    return jax.tree_util.tree_map_with_path(one, params)


def lane_cache_spec(mesh: Mesh, leaf_shape, key: str) -> P:
    """Per-lane KV cache spec, stacked [L, B, ...]: lanes over
    "data" when divisible, KV heads over "model" when divisible — and
    NEVER the sequence dim over "model" (a sequence split makes XLA build
    the distributed softmax, whose reduction order breaks the engine's
    bit-for-bit contract)."""
    shape = list(leaf_shape)
    spec = [None] * len(shape)
    if len(shape) < 2:
        return P(*spec)
    d_sz = _axis_size(mesh, "data")
    m_sz = _axis_size(mesh, "model")
    B = shape[1]
    if d_sz > 1 and B % d_sz == 0 and B >= d_sz:
        spec[1] = "data"
    if key in ("k", "v") and len(shape) == 5:       # gqa [L,B,K,S,Dh]
        K = shape[2]
        if m_sz > 1 and K % m_sz == 0 and K >= m_sz:
            spec[2] = "model"
    return P(*spec)


def shard_cache_serving(cache, mesh: Mesh) -> Any:
    def one(path, leaf):
        key = getattr(path[-1], "key", str(path[-1]))
        return NamedSharding(mesh, lane_cache_spec(mesh, leaf.shape, key))

    return jax.tree_util.tree_map_with_path(one, cache)


def lane_batch_sharding(mesh: Mesh, n_lanes: int) -> NamedSharding:
    """[B] / [B, 1] decode-lane vectors: lanes over "data" when divisible."""
    d_sz = _axis_size(mesh, "data")
    if d_sz > 1 and n_lanes % d_sz == 0 and n_lanes >= d_sz:
        return NamedSharding(mesh, P("data"))
    return NamedSharding(mesh, P())
