"""Gradient compression with error feedback (distributed-optimization trick).

Int8 gradient payloads cut DP all-reduce bytes 4× (the collective-bound term
of the roofline, §Roofline). Error feedback keeps convergence: the residual
(g − dequant(quant(g))) is carried and added to the next step's gradient —
the standard EF-SGD construction, known to preserve AdamW convergence rates.

Under pjit the all-reduce is implicit, so compression is expressed as a
``shard_map`` over the DP axes: quantise the local shard → psum int32 →
dequantise — giving XLA an integer-typed collective. ``compress_tree`` is
the pure (collective-free) codec used both by the shard_map path and by the
tests.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class EFState(NamedTuple):
    residual: Any   # same pytree as grads


def init_ef(grads_like) -> EFState:
    return EFState(jax.tree_util.tree_map(
        lambda g: jnp.zeros_like(g, jnp.float32), grads_like))


def _quant_int8(x: jax.Array, block: int = 256):
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_int8(q, scale, shape, block: int = 256):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape)


def compress_tree(grads, ef: EFState, block: int = 256) -> Tuple[Any, EFState]:
    """Error-feedback int8 round-trip: returns (decompressed grads, new EF).

    What every worker would transmit is the int8 payload; the returned
    gradients are exactly what the receiving side reconstructs, so training
    with these gradients *is* training under compressed communication.
    """
    def one(g, r):
        x = g.astype(jnp.float32) + r
        q, s = _quant_int8(x, block)
        d = _dequant_int8(q, s, g.shape, block)
        return d, x - d

    pairs = jax.tree_util.tree_map(one, grads, ef.residual)
    is2 = lambda t: isinstance(t, tuple) and len(t) == 2
    dec = jax.tree_util.tree_map(lambda t: t[0], pairs, is_leaf=is2)
    res = jax.tree_util.tree_map(lambda t: t[1], pairs, is_leaf=is2)
    return dec, EFState(res)


def compressed_psum_grads(local_grads, mesh, dp_axes=("data",), block: int = 256):
    """shard_map DP all-reduce with int8 payloads.

    The local per-shard gradient is quantised, summed as int32 across the DP
    axes (the wire format a fabric-offload implementation would ship), and
    dequantised with the summed scales upper bound. Bytes on the wire: 1/4
    of f32 (+ 1/block scale overhead).
    """
    from jax import shard_map

    def reduce_one(g):
        def f(x):
            q, s = _quant_int8(x, block)
            qs = jax.lax.psum(q.astype(jnp.int32), dp_axes)
            ss = jax.lax.psum(s, dp_axes)  # conservative: sum of scales
            n = jax.lax.psum(jnp.ones((), jnp.float32), dp_axes)
            return _dequant_int8(qs.astype(jnp.float32) / n, ss / n, x.shape, block)

        return shard_map(f, mesh=mesh, in_specs=P(*[None] * g.ndim),
                         out_specs=P(*[None] * g.ndim))(g)

    return jax.tree_util.tree_map(reduce_one, local_grads)
