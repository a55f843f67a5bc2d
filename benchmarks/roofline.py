"""Roofline analysis (§Roofline): three terms per (arch × shape), single-pod.

METHOD NOTE (important): XLA's ``compiled.cost_analysis()`` counts a
``while`` loop's body ONCE, and every LM step here iterates layers under
``lax.scan`` (that is what keeps 512-device compiles tractable). The raw
HLO numbers are therefore *per-loop-iteration* quantities. We handle this
honestly:

  * the three roofline terms are computed from ANALYTIC closed forms
    (exact for these GEMM-dominated programs; formulas below), and
  * the HLO-derived numbers are reported as calibration: analytic
    per-layer flops vs HLO per-iteration flops must agree within ~2×
    (asserted in tests/test_roofline.py), and the collective census
    (op kinds/counts from the partitioned HLO) is what the §Perf loop
    watches when it reshards.

Analytic terms (per device, per step), hardware 197 TFLOP/s bf16,
819 GB/s HBM, 50 GB/s/link ICI:

  compute  = (dense_flops + attn_flops) / chips / PEAK
     train:   6·N_act·tokens (+12·L·B·S·W_eff·H·dh attn, W_eff=min(S,window))
     prefill: 2·N_act·tokens (+4·L·B·S·W_eff·H·dh)
     decode:  2·N_act·B     (+4·L·B·S_ctx·H·dh_kv)
  memory   = bytes/device / HBM:
     train:   remat streams params 3× (fwd, recompute, bwd) + optimizer
              update (m,v,p read+write ≈ 16B/param f32 or 4B int8-quant)
              + activation traffic ≈ 12·B·S·d·L bytes
     prefill: params 1× + KV cache write + activations
     decode:  params 1× + KV cache read  (the decode wall)
  collective = bytes on ICI / device / LINK:
     train:   FSDP: all-gather params fwd + bwd re-gather + reduce-scatter
              grads ≈ 3·P_bytes·(n_sh−1)/n_sh, n_sh = axes params shard over
     serve:   TP activation collectives ≈ L·(4·B·S_q·d·2B) + any param
              gathers if weights are data-axis-sharded (a serving
              anti-pattern §Perf removes)
"""
import glob
import json
import os

from repro import configs
from repro.configs import SHAPES

# hardware peaks live in repro.obs.costmodel (single source: the measured
# cost model and these analytic terms must price the same machine)
from repro.obs.costmodel import hardware_for

_HW = hardware_for("TPU v5 lite")     # the v5e pod this model prices

PEAK = _HW.peak_flops
HBM = _HW.hbm_bytes_per_s
LINK = _HW.link_bytes_per_s
CHIPS = 256  # single-pod


# --------------------------------------------------------------------------
# analytic model
# --------------------------------------------------------------------------

from repro.models.transformer import analytic_params as _analytic_params_impl


def analytic_params(cfg, active: bool = False):
    return _analytic_params_impl(cfg, active)


def _analytic_params_unused(cfg, active: bool = False):
    d, dh = cfg.d_model, cfg.head_dim
    emb = cfg.vocab * d * (1 if cfg.tie_embeddings else 2)
    per_layer = 0
    if cfg.rwkv:
        per_layer += 5 * d * d + d * 64 + 64 * d
        per_layer += d * cfg.d_ff + cfg.d_ff * d + d * d
    else:
        if cfg.mla:
            per_layer += d * cfg.q_rank + cfg.q_rank * cfg.n_heads * (cfg.d_nope + cfg.d_rope)
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
            per_layer += d * cfg.n_experts
            per_layer += e * (2 * d * ff + ff * d)
        else:
            per_layer += 3 * d * cfg.d_ff
    n = emb + cfg.n_layers * per_layer
    if cfg.enc_dec:
        n += cfg.n_enc_layers * (4 * d * dh * cfg.n_heads + 3 * d * cfg.d_ff)
        n += cfg.n_layers * 4 * d * dh * cfg.n_heads
    return n


def _attn_flops(cfg, B, S_q, S_kv, backward: bool):
    """QK^T + PV matmul flops (2 GEMMs, 2 flops/MAC), causal ≈ ×1/2 when
    S_q == S_kv; sliding windows cap the effective context."""
    if cfg.rwkv:
        # linear attention: state updates ≈ 2·B·S·H·C² ×2 (two einsums)
        C = cfg.d_model // cfg.n_heads
        f = 4.0 * B * S_q * cfg.n_heads * C * C
        return f * (3.0 if backward else 1.0)
    W = min(S_kv, cfg.window or S_kv)
    if cfg.local_global_period:
        W = (min(S_kv, cfg.local_global_period) + S_kv) / 2  # half local
    causal = 0.5 if S_q == S_kv else 1.0
    f = 4.0 * cfg.n_layers * B * S_q * W * causal * cfg.n_heads * cfg.head_dim
    return f * (3.0 if backward else 1.0) / cfg.n_layers  # per call: caller ×L


DEFAULT_POLICY = {
    # reflects the implemented baseline; §Perf flips these and re-verifies
    # against the dry-run collective census
    "train_fsdp_gather": True,        # params data-axis sharded, gathered/layer
    "serve_params_data_sharded": True,  # greedy sharding also splits over data
    "param_bits": 16,                 # bf16 storage
    "cache_bits": 16,                 # bf16 KV cache
    "quant_moments": None,            # None → auto by size
    "grad_payload_bits": 16,          # int8 compression sets 8
}

D_AX, M_AX = 16, 16  # single-pod mesh


def analytic_terms(cfg, shape, policy=None):
    """Per-DEVICE roofline terms. See module docstring for the formulas."""
    pol = {**DEFAULT_POLICY, **(policy or {})}
    B, S = shape.batch, shape.seq
    N_act = analytic_params(cfg, active=True)
    N_tot = analytic_params(cfg, active=False)
    P_bytes = N_tot * pol["param_bits"] / 8.0
    L, d = cfg.n_layers, cfg.d_model
    toks = B * S

    if shape.kind == "train":
        dense = 6.0 * N_act * toks
        attn = L * _attn_flops(cfg, B, S, S, backward=True)
        flops_dev = (dense + attn) / CHIPS
        # HBM: weights stream 3× per step (fwd, remat recompute, bwd) at the
        # model-parallel shard size; optimizer update on the /chips shard;
        # activation residual traffic for the local tokens
        qm = pol["quant_moments"]
        qm = (_is_big(cfg) if qm is None else qm)
        opt_bytes = N_tot / CHIPS * (6.0 if qm else 16.0)
        w_stream = 3.0 * P_bytes / M_AX
        act = 24.0 * toks / CHIPS * d * L * 2.0 / 16.0  # model-sharded widths
        mem_dev = w_stream + opt_bytes + act
        # ICI: data-axis all-gathers fwd+bwd + grad reduce-scatter + TP acts
        gb = pol["grad_payload_bits"] / 16.0
        coll_dev = (2.0 * P_bytes / M_AX if pol["train_fsdp_gather"] else 0.0)
        coll_dev += P_bytes / M_AX * gb               # grad RS/AR
        coll_dev += L * 8.0 * (toks / D_AX) * d * 2.0 / M_AX  # TP activation
        model = dense
    elif shape.kind == "prefill":
        dense = 2.0 * N_act * toks
        attn = L * _attn_flops(cfg, B, S, S, backward=False)
        flops_dev = (dense + attn) / CHIPS
        cache_dev = _cache_bytes(cfg, B, S) * pol["cache_bits"] / 16.0 / CHIPS
        act = 8.0 * toks / CHIPS * d * L * 2.0 / 16.0
        mem_dev = P_bytes / M_AX + cache_dev + act
        coll_dev = L * 4.0 * (toks / D_AX) * d * 2.0 / M_AX
        if pol["serve_params_data_sharded"]:
            coll_dev += P_bytes / M_AX               # data-axis AG per pass
        model = dense
    else:  # decode
        dense = 2.0 * N_act * B
        attn = L * _attn_flops(cfg, B, 1, S, backward=False)
        flops_dev = (dense + attn) / CHIPS
        cache_dev = _cache_bytes(cfg, B, S) * pol["cache_bits"] / 16.0 / CHIPS
        mem_dev = P_bytes / M_AX * 1.0 + cache_dev
        coll_dev = L * 4.0 * max(B / D_AX, 1.0) * d * 2.0 / M_AX
        if pol["serve_params_data_sharded"]:
            coll_dev += P_bytes / M_AX
        model = dense

    flops = flops_dev * CHIPS
    return {
        "flops": flops, "mem_bytes": mem_dev, "coll_bytes": coll_dev,
        "model_flops": model, "params": N_tot, "active_params": N_act,
        "compute_s": flops_dev / PEAK,
        "memory_s": mem_dev / HBM,
        "collective_s": coll_dev / LINK,
    }


def _cache_bytes(cfg, B, S):
    if cfg.rwkv:
        C = cfg.d_model // cfg.n_heads
        return 2.0 * B * cfg.n_layers * cfg.n_heads * C * C
    if cfg.mla:
        return 2.0 * B * S * cfg.n_layers * (cfg.kv_rank + cfg.d_rope)
    per = 2 * cfg.n_kv_heads * cfg.head_dim
    return 2.0 * B * S * cfg.n_layers * per


def _is_big(cfg):
    return analytic_params(cfg) > 2e10


# --------------------------------------------------------------------------
# assembly: analytic terms + HLO calibration from the dry-run records
# --------------------------------------------------------------------------

def load_cells(out_dir="results/dryrun", mesh="single"):
    from repro.launch.dryrun import effective_shape

    cells = []
    for path in sorted(glob.glob(os.path.join(out_dir, f"*_{mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            cells.append(rec)
            continue
        cfg = configs.get(rec["arch"]).FULL
        shape = effective_shape(cfg, SHAPES[rec["shape"]])
        a = analytic_terms(cfg, shape)
        terms = {k: a[k] for k in ("compute_s", "memory_s", "collective_s")}
        dom = max(terms, key=terms.get).replace("_s", "")
        useful = a["model_flops"] / max(a["flops"], 1.0)
        mfu_bound = (a["model_flops"] / CHIPS / PEAK) / max(max(terms.values()), 1e-30)
        rec["roofline"] = {
            **{k: a[k] for k in ("compute_s", "memory_s", "collective_s",
                                 "model_flops", "flops")},
            "dominant": dom, "usefulness": useful, "mfu_bound": mfu_bound,
            "hlo_flops_per_iter": rec["cost"]["flops"],
            "hlo_coll_bytes_per_iter": rec["collectives"]["total_bytes"],
            "recommendation": _recommend(dom, rec),
        }
        cells.append(rec)
    return cells


def _recommend(dom, rec) -> str:
    if dom == "memory":
        return ("memory-bound: raise arithmetic intensity — bigger per-chip "
                "batch, quantised cache/params (the paper's certified "
                "low-precision serving is exactly this lever)")
    if dom == "collective":
        return ("collective-bound: keep params model-axis-resident (no "
                "data-axis gathers), overlap AG with layer compute, int8 "
                "gradient payloads")
    return "compute-bound: near roofline; tune MXU block shapes / fusion"


def print_table(cells):
    ok = [c for c in cells if c.get("status") == "ok"]
    print("\n== §Roofline (single-pod 16×16; analytic terms, HLO-calibrated) ==")
    print(f"{'arch':<18s}{'shape':<13s}{'compute':>11s}{'memory':>11s}"
          f"{'collect':>11s}{'dom':>8s}{'MFU≤':>7s}")
    rows = []
    for c in ok:
        r = c["roofline"]
        print(f"{c['arch']:<18s}{c['shape']:<13s}"
              f"{r['compute_s']:>11.3e}{r['memory_s']:>11.3e}"
              f"{r['collective_s']:>11.3e}{r['dominant']:>8s}"
              f"{r['mfu_bound']:>7.3f}")
        rows.append((f"roofline_{c['arch']}_{c['shape']}",
                     max(r['compute_s'], r['memory_s'],
                         r['collective_s']) * 1e6,
                     round(r['mfu_bound'], 4)))
    skipped = [c for c in cells if c.get("status") == "skipped"]
    if skipped:
        print(f"({len(skipped)} cells skipped per assignment — see §Dry-run)")
    return rows


def interesting_cells(cells):
    ok = [c for c in cells if c.get("status") == "ok"]
    worst = min(ok, key=lambda c: c["roofline"]["mfu_bound"])
    coll = max(ok, key=lambda c: (c["roofline"]["collective_s"]
                                  / max(c["roofline"]["compute_s"],
                                        c["roofline"]["memory_s"], 1e-30)))
    serving = [c for c in ok if SHAPES[c["shape"]].kind != "train"]
    rep = max(serving, key=lambda c: c["roofline"]["model_flops"])
    return {"worst_mfu": worst, "most_collective": coll, "paper_rep": rep}


def run():
    cells = load_cells()
    rows = print_table(cells)
    picks = interesting_cells(cells)
    print("\nhillclimb candidates:")
    for why, c in picks.items():
        print(f"  {why:16s}: {c['arch']} × {c['shape']} "
              f"(dom={c['roofline']['dominant']}, "
              f"MFU≤{c['roofline']['mfu_bound']:.3f})")
    return rows


if __name__ == "__main__":
    run()
