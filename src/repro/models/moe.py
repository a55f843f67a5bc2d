"""Mixture-of-Experts MLP: router, routed experts, shared experts.

Gates: a softmax over all E router logits, the top k kept, renormalised
over those k only where ``norm_topk_prob`` (Mixtral; DeepSeek-V2 does
not), then scaled by ``routed_scaling``. Shared experts (DeepSeek) are one
gated MLP of width n_shared·d_ff, added for every token.

Routed experts run in one of three modes:
  dense        — every expert on every token, combined by the gates (zero
                 off the top k). Exact and backend-generic: the analysis
                 backends run it, so it is the function certification
                 analyses.
  dropless     — the serving path. Each (token, choice) pair is sorted by
                 expert into row tiles that hold one expert each
                 (:func:`route`), and the three products run through
                 ``bk.grouped_matmul`` over the whole stacked [L, E, K, N]
                 expert weights and the layer index: ragged_dot under
                 JOps, the grouped format GEMM under FormatQuantJOps.
                 Nothing is dropped, so a token's result depends on no
                 other token, and it is the dense mode's function.
  ep_shard_map — expert parallelism over a mesh's "model" axis (multi-chip
                 only): capacity-bounded dispatch, which drops the tokens
                 beyond an expert's capacity.

The router's top-k is FP-dependent control flow: under CAA the route is
fixed from reference values and the decision margin recorded (the paper's
argmax treatment, applied to routing — see backend.CaaOps.top_k_mask).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import layers as L

EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def init_moe(key, d: int, d_ff: int, n_experts: int, n_shared: int = 0):
    ks = jax.random.split(key, 5)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {
        "w_router": L.dense_init(ks[0], d, n_experts),
        "w_gate": jax.random.normal(ks[1], (n_experts, d, d_ff), jnp.float32) * s_in,
        "w_up": jax.random.normal(ks[2], (n_experts, d, d_ff), jnp.float32) * s_in,
        "w_down": jax.random.normal(ks[3], (n_experts, d_ff, d), jnp.float32) * s_out,
    }
    if n_shared:
        kg, ku, kd = jax.random.split(ks[4], 3)
        fs = n_shared * d_ff
        p["shared"] = {"w_gate": L.dense_init(kg, d, fs),
                       "w_up": L.dense_init(ku, d, fs),
                       "w_down": L.dense_init(kd, fs, d)}
    return p


def moe_mlp(
    bk, x, p, experts=None, layer=0, *,
    n_experts: int, top_k: int,
    act: str = "silu",
    norm_topk_prob: bool = True,
    routed_scaling: float = 1.0,
    capacity_factor: float = 1.25,
    chunk_tokens: int = 4096,
    mode: Optional[str] = None,
):
    """x: [B, S, d] → [B, S, d].

    ``p`` holds the router (and the shared experts, if any); ``experts``
    the routed experts' stacked ``[L, E, ...]`` weights, whole, and
    ``layer`` the index of this layer in them. Without ``experts`` the
    routed experts are ``p``'s own ``[E, ...]`` weights.

    Mode selection: analysis → dense; a mesh with a "model" axis that
    divides n_experts → expert-parallel shard_map; otherwise dropless.
    """
    if experts is None:
        experts, layer = {k: p[k][None] for k in EXPERT_KEYS}, 0
    if mode is None:
        if bk.is_analysis:
            mode = "dense"
        elif _ep_mesh(bk, n_experts) is not None:
            mode = "ep_shard_map"
        else:
            mode = "dropless"
    B, S, d = bk.shape_of(x)
    xt = bk.reshape(x, (B * S, d))

    if mode == "ep_shard_map":
        if not norm_topk_prob or routed_scaling != 1.0:
            raise NotImplementedError(
                "expert parallelism renormalises the top-k gates")
        w = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
             for k, v in experts.items()}
        y = bk.input(bk.reshape(_ep_experts(
            bk, bk.value_of(x), {**p, **w}, n_experts, top_k, act,
            capacity_factor, chunk_tokens), (B * S, d)))
    else:
        with bk.scope("router"):
            logits = bk.matmul(xt, bk.param(p["w_router"]))
            probs = bk.softmax(logits, axis=-1)
            if mode == "dense":
                mask = bk.top_k_mask(probs, top_k)          # [T,E] exact 0/1
                gates = bk.mul(probs,
                               bk.input(mask) if bk.is_analysis else mask)
            else:                               # [T,k] in top-k order
                gates, idx = jax.lax.top_k(bk.value_of(probs), top_k)
            if norm_topk_prob:
                gates = bk.div(gates, bk.sum(gates, axis=-1, keepdims=True))
            if routed_scaling != 1.0:
                gates = bk.scale(gates, routed_scaling)
        with bk.scope("experts"):
            if mode == "dense":
                y = _dense_experts(bk, xt, gates, experts, layer, act)
            else:
                y = _dropless_experts(bk, xt, idx, gates, experts, layer,
                                      act)
    if "shared" in p:
        with bk.scope("shared"):
            sh = p["shared"]
            y = bk.add(y, L.mlp_gated(bk, xt, sh["w_gate"], sh["w_up"],
                                      sh["w_down"], act))
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


def _dense_experts(bk, xt, gates, experts, layer, act):
    """All experts on all tokens; gate-weighted combine. CAA-friendly."""
    w = {k: bk.param(jax.lax.dynamic_index_in_dim(v, layer, 0,
                                                  keepdims=False))
         for k, v in experts.items()}
    h_g = bk.einsum("td,edf->tef", xt, w["w_gate"])
    h_u = bk.einsum("td,edf->tef", xt, w["w_up"])
    h = bk.mul(getattr(bk, act)(h_g), h_u)
    y_e = bk.einsum("tef,efd->ted", h, w["w_down"])
    return bk.einsum("ted,te->td", y_e, gates)


class Routes(NamedTuple):
    """Where each (token, choice) pair of a batch goes in the row tiles of
    the grouped expert products (:func:`route`). ``R = n_tiles · tm``
    rows: each expert's pairs fill whole tiles of ``tm`` rows from the
    expert's first tile on, experts in order; tiles past ``n_real`` pad
    the grid to its static size. Rows that hold no pair are zero."""
    token: jax.Array        # [R] token of each row; -1 where a row pads
    pos: jax.Array          # [T, k] row of each (token, choice)
    tile_expert: jax.Array  # [n_tiles] expert of each tile (padding
    #                         tiles: the last real tile's expert)
    n_real: jax.Array       # tiles that hold pairs
    group_sizes: jax.Array  # [E] rows of each expert's tiles (the padding
    #                         tiles counted with the last expert)
    tm: int                 # rows per tile (static)


def row_tile(pairs: int, n_experts: int) -> int:
    """Rows per tile: a power of two from 8 (decode: about three rows an
    expert) up to 128, at least the mean rows per expert."""
    tm = 8
    while tm < 128 and tm * n_experts < pairs:
        tm *= 2
    return tm


def route(idx, n_experts: int, tm: Optional[int] = None) -> Routes:
    """The tile layout of the top-k choices ``idx`` [T, k]: pairs sorted
    by expert (stably, so a token's choices keep their order within an
    expert), each expert's group padded to whole tiles. The static bound
    on tiles, Σ_e ceil(n_e / tm) ≤ (T·k + min(E, T·k)·(tm − 1)) / tm,
    fixes the grid whatever the routing."""
    T, k = idx.shape
    P, E = T * k, n_experts
    tm = tm or row_tile(P, E)
    n_tiles = (P + min(E, P) * (tm - 1)) // tm
    R = n_tiles * tm
    i32 = jnp.int32
    flat = idx.reshape(-1).astype(i32)
    counts = jnp.zeros((E,), i32).at[flat].add(1)
    tiles = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles).astype(i32)
    first_row = (tile_end - tiles) * tm
    order = jnp.argsort(flat, stable=True).astype(i32)
    by_expert = flat[order]
    group_start = (jnp.cumsum(counts) - counts).astype(i32)
    rank = jnp.arange(P, dtype=i32) - group_start[by_expert]
    row = first_row[by_expert] + rank
    n_real = tile_end[-1]
    last = jnp.minimum(jnp.arange(n_tiles, dtype=i32), n_real - 1)
    tile_expert = jnp.searchsorted(tile_end, last, side="right").astype(i32)
    return Routes(
        token=jnp.full((R,), -1, i32).at[row].set(order // k),
        pos=jnp.zeros((P,), i32).at[order].set(row).reshape(T, k),
        tile_expert=tile_expert, n_real=n_real,
        group_sizes=(tiles * tm).at[E - 1].add(R - n_real * tm),
        tm=tm)


def _dropless_experts(bk, xt, idx, gates, experts, layer, act):
    """Every (token, choice) pair through its expert, nothing dropped: the
    pairs ``idx`` [T, k] gathered into expert tiles, the three products
    grouped, each token's k results combined by its ``gates`` [T, k] in
    its top-k order."""
    xt, gates = bk.value_of(xt), bk.value_of(gates)
    top_k = idx.shape[1]
    r = route(idx, experts["w_gate"].shape[1])
    x_rows = jnp.where((r.token >= 0)[:, None],
                       jnp.take(xt, jnp.maximum(r.token, 0), axis=0), 0.0)
    x_rows = x_rows.astype(xt.dtype)
    h_g = bk.grouped_matmul(x_rows, experts["w_gate"], layer, r)
    h_u = bk.grouped_matmul(x_rows, experts["w_up"], layer, r)
    h = bk.mul(getattr(bk, act)(h_g), h_u)
    y_rows = bk.grouped_matmul(h, experts["w_down"], layer, r)
    y_pairs = jnp.take(y_rows, r.pos, axis=0)                # [T, k, d]
    y = y_pairs[:, 0] * gates[:, :1]
    for c in range(1, top_k):
        y = y + y_pairs[:, c] * gates[:, c:c + 1]
    return y


def aux_load_balance_loss(gates_probs: jax.Array, mask: jax.Array,
                          n_experts: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    density = mask.mean(axis=0)                 # fraction routed per expert
    router_prob = gates_probs.mean(axis=0)
    return n_experts * jnp.sum(density * router_prob)
