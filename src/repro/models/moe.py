"""Mixture-of-Experts MLP: router + top-k dispatch.

Two execution modes:
  dense     — every expert computed for every token, combined by gates.
              Exact, backend-generic (CAA-analysable), O(E) flops: used for
              analysis and smoke tests.
  dropping  — capacity-bounded one-hot dispatch einsums under a scan over
              token chunks (keeps the [Tc, E, C] dispatch tensor small);
              the production path; expert dim shards over the "model" mesh
              axis (expert parallelism → all-to-all under SPMD).

The router's top-k is FP-dependent control flow: under CAA the route is
fixed from reference values and the decision margin recorded (the paper's
argmax treatment, applied to routing — see backend.CaaOps.top_k_mask).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import layers as L


def init_moe(key, d: int, d_ff: int, n_experts: int):
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "w_router": L.dense_init(ks[0], d, n_experts),
        "w_gate": jax.random.normal(ks[1], (n_experts, d, d_ff), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (n_experts, d, d_ff), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (n_experts, d_ff, d), jnp.float32) * s_out,
    }


def moe_mlp(
    bk, x, p, *,
    n_experts: int, top_k: int,
    act: str = "silu",
    capacity_factor: float = 1.25,
    chunk_tokens: int = 4096,
    mode: Optional[str] = None,
):
    """x: [B, S, d] → [B, S, d].

    Mode selection: analysis → dense; a mesh with a "model" axis that
    divides n_experts → expert-parallel shard_map (the production path);
    otherwise chunked capacity dispatch under pjit.
    """
    if mode is None:
        if bk.is_analysis:
            mode = "dense"
        elif _ep_mesh(bk, n_experts) is not None:
            mode = "ep_shard_map"
        else:
            mode = "dropping"
    B, S, d = bk.shape_of(x)

    if mode == "ep_shard_map":
        y = _ep_experts(bk, bk.value_of(x), p, n_experts, top_k, act,
                        capacity_factor, chunk_tokens)
        return bk.input(y)

    xt = bk.reshape(x, (B * S, d))
    logits = bk.matmul(xt, bk.param(p["w_router"]))
    probs = bk.softmax(logits, axis=-1)
    mask = bk.top_k_mask(probs, top_k)                      # [T,E] exact 0/1
    gates = bk.mul(probs, bk.input(mask) if bk.is_analysis else mask)
    denom = bk.sum(gates, axis=-1, keepdims=True)
    gates = bk.div(gates, denom)                            # renormalised

    if mode == "dense":
        y = _dense_experts(bk, xt, gates, p, act)
    else:
        y = _dropping_experts(
            bk, xt, bk.value_of(gates), p, n_experts, top_k, act,
            capacity_factor, chunk_tokens,
        )
        y = bk.input(y)
    return bk.reshape(y, (B, S, d))


def _ep_mesh(bk, n_experts: int):
    mesh = getattr(bk, "mesh", None)
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return None
    m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if m > 1 and n_experts % m == 0:
        return mesh
    return None


def _ep_experts(bk, x, p, n_experts, top_k, act, capacity_factor,
                chunk_tokens):
    """Expert parallelism via shard_map (the production MoE, DESIGN.md §5).

    Tokens are sharded over the DP axes and *replicated* across "model";
    experts are sharded over "model". Every model-rank selects, from its
    replicated token block, the tokens routed to ITS local experts —
    dispatch costs zero inter-chip traffic — runs the local expert GEMMs,
    and the gate-weighted partial outputs are combined with ONE activation-
    sized psum over "model" per layer. Collectives per layer: psum of
    [T_local, d] — versus the pjit chunk-scan path whose global dispatch
    einsums forced XLA into parameter/token-sized all-gathers.
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = bk.mesh
    B, S, d = x.shape
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    m_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    e_loc = n_experts // m_size

    wr = bk.param(p["w_router"])
    wg = bk.param(p["w_gate"])
    wu = bk.param(p["w_up"])
    wd = bk.param(p["w_down"])

    def local(xb, wrb, wgb, wub, wdb):
        # xb: [B_loc, S, d] (replicated across model); w*b: [e_loc, ...]
        Bl = xb.shape[0]
        xt = xb.reshape(Bl * S, d)
        logits = xt @ wrb                                  # full router [T,E]
        probs = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(probs, top_k)
        mask = jax.nn.one_hot(idx, n_experts, dtype=xt.dtype).sum(-2)
        gates = probs * mask
        gates = gates / gates.sum(-1, keepdims=True)
        # this rank's expert slice
        rank = jax.lax.axis_index("model")
        lo = rank * e_loc
        gsel = jax.lax.dynamic_slice_in_dim(gates, lo, e_loc, axis=1)
        msel = jax.lax.dynamic_slice_in_dim(mask, lo, e_loc, axis=1)
        T = xt.shape[0]
        Tc = min(chunk_tokens, T)
        n_chunks = (T + Tc - 1) // Tc
        C = max(1, int(Tc * top_k / n_experts * capacity_factor))

        def one_chunk(_, args):
            xc, gc, mc = args                              # [Tc,d],[Tc,e_loc]
            sel = mc > 0
            pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) * sel - 1
            keep = sel & (pos < C)
            disp = jax.nn.one_hot(jnp.where(keep, pos, -1), C, dtype=xc.dtype)
            disp = disp * keep[..., None].astype(xc.dtype)   # [Tc,e_loc,C]
            xe = jnp.einsum("tec,td->ecd", disp, xc)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wgb))                 * jnp.einsum("ecd,edf->ecf", xe, wub)
            ye = jnp.einsum("ecf,efd->ecd", h, wdb)
            comb = disp * gc[..., None].astype(xc.dtype)
            return None, jnp.einsum("tec,ecd->td", comb, ye)

        pad = n_chunks * Tc - T
        xt_p = jnp.pad(xt, ((0, pad), (0, 0))) if pad else xt
        g_p = jnp.pad(gsel, ((0, pad), (0, 0))) if pad else gsel
        m_p = jnp.pad(msel, ((0, pad), (0, 0))) if pad else msel
        _, ys = jax.lax.scan(
            one_chunk, None,
            (xt_p.reshape(n_chunks, Tc, d),
             g_p.reshape(n_chunks, Tc, e_loc),
             m_p.reshape(n_chunks, Tc, e_loc)))
        y = ys.reshape(-1, d)[:T]
        y = jax.lax.psum(y, "model")                       # combine experts
        return y.reshape(Bl, S, d)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp_axes or None, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(dp_axes or None, None, None),
    )
    return fn(x, wr, wg, wu, wd)


def _dense_experts(bk, xt, gates, p, act):
    """All experts on all tokens; gate-weighted combine. CAA-friendly."""
    h_g = bk.einsum("td,edf->tef", xt, bk.param(p["w_gate"]))
    h_u = bk.einsum("td,edf->tef", xt, bk.param(p["w_up"]))
    h = bk.mul(getattr(bk, act)(h_g), h_u)
    y_e = bk.einsum("tef,efd->ted", h, bk.param(p["w_down"]))
    return bk.einsum("ted,te->td", y_e, gates)


def _dropping_experts(bk, xt, gates, p, n_experts, top_k, act,
                      capacity_factor, chunk_tokens):
    """Capacity dispatch in token chunks (jnp path; runs under JOps only).

    Per chunk of Tc tokens: capacity C = ceil(Tc·top_k/E · cf); tokens beyond
    an expert's capacity are dropped (standard Switch semantics). Dispatch/
    combine are one-hot einsums — they lower to all-to-all when the expert
    dim is sharded.
    """
    xt = bk.value_of(xt)
    w_gate = bk.param(p["w_gate"])
    w_up = bk.param(p["w_up"])
    w_down = bk.param(p["w_down"])
    T, d = xt.shape
    E = n_experts
    Tc = min(chunk_tokens, T)
    n_chunks = (T + Tc - 1) // Tc
    pad = n_chunks * Tc - T
    if pad:
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
    C = max(1, math.ceil(Tc * top_k / E * capacity_factor))

    xs = xt.reshape(n_chunks, Tc, d)
    gs = gates.reshape(n_chunks, Tc, E)

    def one_chunk(_, xg):
        xc, gc = xg                                  # [Tc,d], [Tc,E]
        sel = gc > 0
        pos = jnp.cumsum(sel.astype(jnp.int32), axis=0) * sel - 1
        keep = sel & (pos < C)
        disp = jax.nn.one_hot(jnp.where(keep, pos, -1), C, dtype=xc.dtype)
        disp = disp * keep[..., None].astype(xc.dtype)       # [Tc,E,C]
        xe = jnp.einsum("tec,td->ecd", disp, xc)
        hg = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        hu = jnp.einsum("ecd,edf->ecf", xe, w_up)
        h = getattr(jax.nn, "silu" if act == "silu" else act)(hg) * hu
        ye = jnp.einsum("ecf,efd->ecd", h, w_down)
        comb = disp * gc[..., None].astype(xc.dtype)
        yc = jnp.einsum("tec,ecd->td", comb, ye)
        return None, yc

    _, ys = jax.lax.scan(one_chunk, None, (xs, gs))
    y = ys.reshape(n_chunks * Tc, d)
    return y[:T] if pad else y


def aux_load_balance_loss(gates_probs: jax.Array, mask: jax.Array,
                          n_experts: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    density = mask.mean(axis=0)                 # fraction routed per expert
    router_prob = gates_probs.mean(axis=0)
    return n_experts * jnp.sum(density * router_prob)
