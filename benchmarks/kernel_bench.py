"""Kernel micro-benchmarks — measured, roofline-anchored, trajectory-kept.

Rebuilt on :mod:`repro.obs.profile` (warmup + median-of-k discipline, one
shared implementation): times the certified serving kernels — baseline
``jnp.matmul``, ``quant_matmul_dynamic_k`` (traced-k), the scalar-prefetch
``quant_matmul_format`` across Pallas block candidates, and
``flash_decode_attention`` — and a micro serving profile (real
``build_serve_steps`` prefill/decode with compile-time and jaxpr-size
gauges, p50/p95/p99 from the log-bucket histograms).

Every run appends ONE entry to the ``BENCH_kernels.json`` trajectory
(repo root, mirrored under ``benchmarks/``): measured rows + achieved
FLOP/s + analytic roofline terms + the serving digest, so each PR records
its perf point and ``python -m repro.obs report --kernels`` /
``python -m repro.obs perfgate`` can render and diff the trajectory.

On this CPU container Pallas runs in interpret mode, so the Pallas rows'
absolute wall-clock is mechanism-true but not TPU-predictive (rows carry
``interpret: true``); the jnp-path rows (baseline, dynamic-k) are real
XLA:CPU timings. The roofline columns are analytic, at the peaks of the
local chip (``repro.obs.costmodel.PEAKS``); off those chips they are left
out.
"""
from __future__ import annotations

import jax


def run(serving: bool = True, reps: int = 3, warmup: int = 1):
    from repro import obs
    from repro.obs import costmodel as CM
    from repro.obs import profile as P

    hw = CM.local_hardware()        # None off the chips in CM.PEAKS
    rows = P.profile_kernels(
        gemm_shapes=((128, 128, 128), (128, 256, 128)),
        ks=(8, 24),
        formats=((4, 8, -6), (8, 15, -14)),
        flash_shapes=((2, 256, 2, 2, 64),),
        reps=reps, warmup=warmup, hw=hw)

    entry = {
        "kind": "kernel_bench",
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "hardware": hw and hw.name,
        "rows": [{k: v for k, v in r.items() if k != "samples"}
                 for r in rows],
    }

    serving_profile = None
    if serving:
        # ≥1 measured serving point per PR, CPU-feasible: 1 layer, tiny
        # batch — compile-time/jaxpr gauges and percentile digests are the
        # signal here, not absolute throughput
        try:
            serving_profile = P.profile_serving(
                arch="qwen2_7b", max_layers=1, batch=2,
                prefill_len=8, decode_steps=6)
            entry["serving"] = serving_profile
        except Exception as e:  # pragma: no cover — keep the bench alive
            print(f"(serving profile skipped: {type(e).__name__}: {e})")

    try:
        model = CM.fit_cost_model(rows)
        entry["cost_model"] = model.to_dict()
    except ValueError:
        model = None

    obs.append_bench("kernels", entry)

    # harness contract: (name, us_per_call, derived) rows for run.py's CSV;
    # derived = fraction of the analytic roofline achieved ("" where the
    # device has no peaks to draw a roofline from)
    out = []
    for r in rows:
        fmt = (f"_k{r['k']}" if r.get("k") is not None else "")
        blk = ("_b" + "x".join(map(str, r["block"]))
               if r.get("block") else "")
        out.append((f"{r['kernel']}_{r['shape']}{fmt}{blk}",
                    r["median_s"] * 1e6,
                    round(r["roofline_frac"], 6) if "roofline_frac" in r
                    else ""))
    if serving_profile:
        pre = serving_profile["prefill"]
        pct = serving_profile["decode"]["percentiles"]
        out.append(("serve_prefill_smoke", pre["latency_s"] * 1e6,
                    pre["jaxpr_eqns"]))
        out.append(("serve_decode_p50", pct["p50"] * 1e6, 0))
        out.append(("serve_decode_p99", pct["p99"] * 1e6, 0))

    print("\n== kernel benches (measured median vs analytic roofline) ==")
    from repro.obs import report as R
    print(R.render_kernel_table(obs.read_bench("kernels")))
    if model is not None:
        print("fitted cost model (achieved rates):")
        for k in sorted(model.alpha):
            print(f"  {k:<26} alpha={model.alpha[k]:.3g} FLOP/s  "
                  f"beta={model.beta[k]:.3g} B/s")
    return out


if __name__ == "__main__":
    run()
