"""Measured kernel/serving profiling: warmup + median-of-k timing against
analytic roofline terms.

Two entry points:

* :func:`profile_kernels` — times the certified kernels
  (``quant_matmul_dynamic_k``, the scalar-prefetch ``quant_matmul_format``,
  a baseline ``jnp.matmul``, and ``flash_decode_attention``) across shapes,
  formats, and Pallas block sizes. Every row carries the measured median
  alongside the ANALYTIC terms (flops, bytes, intensity, roofline time at
  the :class:`repro.obs.costmodel.Hardware` peaks) so achieved-vs-roofline
  is one division, and :func:`repro.obs.costmodel.fit_cost_model` can fit
  achieved (α, β) rates from the same rows.
* :func:`profile_serving` — builds the real serving steps
  (``launch.serve.build_serve_steps``) for a SMOKE arch, AOT-compiles them
  (compile-time + jaxpr-size gauges), runs a prefill + decode loop under
  trace spans, and digests the latencies into p50/p95/p99 via the
  log-bucket histograms in :mod:`repro.obs.metrics`.

Timing discipline: jit/compile fully OUTSIDE the timed region (AOT lower →
compile, or one warmup call), then ``reps`` timed calls each ending in
``jax.block_until_ready``, reported as the median (robust to one GC pause
— the same discipline ``benchmarks/analysis_speed.py`` hand-rolled; this
is the shared implementation). On CPU the Pallas kernels run in interpret
mode — medians are mechanism-true (same code path) but roofline fractions
are only meaningful on real TPUs; rows carry ``interpret`` so readers can
tell.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from .costmodel import Hardware, format_bits, local_hardware

BYTES_F32 = 4  # the emulation's carrier width: everything streams as f32


# ---------------------------------------------------------------------------
# timing + jaxpr primitives
# ---------------------------------------------------------------------------

def measure(fn: Callable, *args, reps: int = 5, warmup: int = 2,
            **kwargs) -> Dict[str, float]:
    """Median-of-``reps`` wall time of ``fn(*args)``, post-warmup.

    The warmup calls absorb jit compilation and first-touch allocation;
    every timed call blocks on the result so async dispatch can't hide
    device time. Returns median/min/mean/max plus the raw samples."""
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn(*args, **kwargs))
    times: List[float] = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    ts = sorted(times)
    n = len(ts)
    median = ts[n // 2] if n % 2 else 0.5 * (ts[n // 2 - 1] + ts[n // 2])
    return {"median_s": median, "min_s": ts[0], "max_s": ts[-1],
            "mean_s": sum(ts) / n, "reps": n, "samples": times}


def _count_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                n += _count_eqns(inner)
            elif isinstance(v, (list, tuple)):
                for w in v:
                    iw = getattr(w, "jaxpr", w)
                    if hasattr(iw, "eqns"):
                        n += _count_eqns(iw)
    return n


def jaxpr_stats(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Total equation count of ``fn``'s jaxpr, descending into sub-jaxprs
    (scan/cond/pjit bodies) — the "program size" gauge: a scan-native
    analysis stays flat in depth, an unrolled one doesn't."""
    import jax

    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return {"eqns": _count_eqns(closed.jaxpr),
            "outvars": len(closed.jaxpr.outvars)}


def time_compile(jitted, *args) -> Dict[str, Any]:
    """AOT lower + compile ``jitted`` for ``args``, separately timed.

    Returns the compiled executable plus ``lower_s``/``compile_s`` — the
    gauges the serving profile records per jit so compile-time regressions
    show up in the trace, not just as mysterious first-call latency."""
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    return {"compiled": compiled, "lower_s": t1 - t0, "compile_s": t2 - t1}


# ---------------------------------------------------------------------------
# analytic terms per kernel invocation
# ---------------------------------------------------------------------------

def _terms(flops: float, bytes_moved: float,
           hw: Optional[Hardware]) -> Dict[str, Any]:
    terms = {"flops": flops, "bytes": bytes_moved,
             "intensity": flops / bytes_moved}
    if hw is not None:          # a roofline needs the chip's peaks
        compute_s = flops / hw.peak_flops
        memory_s = bytes_moved / hw.hbm_bytes_per_s
        terms.update(compute_s=compute_s, memory_s=memory_s,
                     roofline_s=max(compute_s, memory_s),
                     bound="memory" if memory_s >= compute_s else "compute")
    return terms


def gemm_terms(M: int, K: int, N: int, bits: float, *,
               hw: Optional[Hardware]) -> Dict[str, Any]:
    """Analytic terms of one [M,K]@[K,N] GEMM at ``bits``/value storage:
    flops = 2·M·K·N, bytes = operands in + result out (each value touched
    once — the blocked kernel's VMEM residency makes this the floor),
    intensity = flops/bytes; with ``hw``, also the roofline times at its
    peaks and the side of its ridge the GEMM sits on."""
    return _terms(2.0 * M * K * N, (M * K + K * N + M * N) * bits / 8.0, hw)


def flash_decode_terms(B: int, S: int, K: int, G: int, D: int,
                       bits: float, *,
                       hw: Optional[Hardware]) -> Dict[str, Any]:
    """Analytic terms of one flash-decode call: QK^T + PV are 2·2·B·K·G·S·D
    flops; bytes stream the KV cache once (the whole point of the online
    softmax) plus q in / o out."""
    return _terms(4.0 * B * K * G * S * D,
                  (2.0 * B * S * K * D + 2.0 * B * K * G * D) * bits / 8.0,
                  hw)


# ---------------------------------------------------------------------------
# kernel profiling
# ---------------------------------------------------------------------------

#: CPU-feasible default sweep: small enough for interpret-mode Pallas in CI,
#: shaped like real tiles (128-multiples) so TPU runs reuse the same preset
DEFAULT_GEMM_SHAPES: Sequence[tuple] = ((128, 128, 128), (128, 256, 128))
DEFAULT_KS: Sequence[int] = (8, 24)
DEFAULT_FORMATS: Sequence[tuple] = ((4, 8, -6), (8, 15, -14))
DEFAULT_FLASH_SHAPES: Sequence[tuple] = ((2, 256, 2, 2, 64),)

ALL_KERNELS = ("matmul_baseline", "quant_matmul_dynamic_k",
               "quant_matmul_format", "flash_decode")


def _row(kernel: str, terms: Dict[str, Any], timing: Dict[str, float],
         **extra) -> Dict[str, Any]:
    med = timing["median_s"]
    row = {
        "kernel": kernel,
        "median_s": med, "min_s": timing["min_s"], "reps": timing["reps"],
        "flops": terms["flops"], "bytes": terms["bytes"],
        "intensity": terms["intensity"],
        "achieved_flops_per_s": terms["flops"] / med if med > 0 else 0.0,
        "achieved_bytes_per_s": terms["bytes"] / med if med > 0 else 0.0,
        **extra,
    }
    if "roofline_s" in terms:
        row.update(roofline_s=terms["roofline_s"], bound=terms["bound"],
                   roofline_frac=terms["roofline_s"] / med if med > 0
                   else 0.0)
    return row


def profile_kernels(gemm_shapes: Iterable[tuple] = DEFAULT_GEMM_SHAPES,
                    ks: Iterable[int] = DEFAULT_KS,
                    formats: Iterable[tuple] = DEFAULT_FORMATS,
                    blocks: Optional[Iterable[tuple]] = None,
                    flash_shapes: Iterable[tuple] = DEFAULT_FLASH_SHAPES,
                    include: Sequence[str] = ALL_KERNELS,
                    reps: int = 5, warmup: int = 2,
                    interpret: Optional[bool] = None,
                    hw: Optional[Hardware] = None) -> List[Dict[str, Any]]:
    """Time every certified kernel across the sweep; one row per point.

    ``blocks`` — (bm, bn, bk) Pallas tile candidates for the format kernel
    (default: :func:`repro.kernels.quant_matmul.block_candidates` per
    shape, the autotune axis); ``interpret`` default follows the backend
    (interpret off-TPU); ``hw`` defaults to the local chip's peaks, and
    on a device without a :data:`repro.obs.costmodel.PEAKS` entry the rows
    carry no roofline terms (no ``roofline_s``/``bound``/
    ``roofline_frac``). Rows are what ``fit_cost_model`` and the
    ``BENCH_kernels.json`` trajectory consume."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    hw = hw or local_hardware()
    from repro.kernels.quant_matmul import (block_candidates, quant_matmul,
                                            quant_matmul_dynamic_k,
                                            quant_matmul_format)
    from repro.kernels.flash_decode import flash_decode_attention

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows: List[Dict[str, Any]] = []
    key = jax.random.PRNGKey(0)

    for (M, K, N) in gemm_shapes:
        kx, kw = jax.random.split(jax.random.fold_in(key, M * K + N))
        x = jax.random.normal(kx, (M, K), jnp.float32)
        w = jax.random.normal(kw, (K, N), jnp.float32)
        shape = {"M": M, "K": K, "N": N, "shape": f"{M}x{K}x{N}"}
        terms32 = gemm_terms(M, K, N, 32.0, hw=hw)

        if "matmul_baseline" in include:
            f = jax.jit(lambda a, b: jnp.matmul(
                a, b, preferred_element_type=jnp.float32))
            with obs.span("profile.kernel", kernel="matmul_baseline", **{
                    "shape": shape["shape"]}):
                t = measure(f, x, w, reps=reps, warmup=warmup)
            rows.append(_row("matmul_baseline", terms32, t, **shape,
                             interpret=False))

        if "quant_matmul_dynamic_k" in include:
            f = jax.jit(quant_matmul_dynamic_k)
            for k in ks:
                with obs.span("profile.kernel",
                              kernel="quant_matmul_dynamic_k", k=int(k),
                              shape=shape["shape"]):
                    t = measure(f, x, w, jnp.int32(k), reps=reps,
                                warmup=warmup)
                rows.append(_row("quant_matmul_dynamic_k", terms32, t,
                                 **shape, k=int(k), interpret=False,
                                 format_bits=format_bits(k)))

        if "quant_matmul_format" in include:
            cands = list(blocks) if blocks is not None else \
                block_candidates(M, K, N)
            for (bm, bn, bk) in cands:
                f = jax.jit(lambda a, b, fmt, _bm=bm, _bn=bn, _bk=bk:
                            quant_matmul_format(a, b, fmt, block_m=_bm,
                                                block_n=_bn, block_k=_bk,
                                                interpret=interpret))
                for (fk, femax, femin) in formats:
                    fmt = jnp.asarray([fk, femax, femin], jnp.int32)
                    with obs.span("profile.kernel",
                                  kernel="quant_matmul_format",
                                  k=int(fk), block=f"{bm}x{bn}x{bk}",
                                  shape=shape["shape"]):
                        t = measure(f, x, w, fmt, reps=reps, warmup=warmup)
                    rows.append(_row(
                        "quant_matmul_format", terms32, t, **shape,
                        k=int(fk), emax=int(femax), emin=int(femin),
                        block=[bm, bn, bk], interpret=bool(interpret),
                        format_bits=format_bits(fk, femax, femin)))

        if "quant_matmul" in include:  # static-k Pallas kernel (opt-in)
            for k in ks:
                f = jax.jit(lambda a, b, _k=int(k): quant_matmul(
                    a, b, k=_k, interpret=interpret))
                with obs.span("profile.kernel", kernel="quant_matmul",
                              k=int(k), shape=shape["shape"]):
                    t = measure(f, x, w, reps=reps, warmup=warmup)
                rows.append(_row("quant_matmul", terms32, t, **shape,
                                 k=int(k), interpret=bool(interpret)))

    if "flash_decode" in include:
        for (B, S, Kh, G, D) in flash_shapes:
            kq, kk, kv = jax.random.split(jax.random.fold_in(key, S + D), 3)
            q = jax.random.normal(kq, (B, Kh, G, D), jnp.float32)
            kc = jax.random.normal(kk, (B, S, Kh, D), jnp.float32)
            vc = jax.random.normal(kv, (B, S, Kh, D), jnp.float32)
            lengths = jnp.full((B,), S, jnp.int32)
            bs = min(128, S)
            f = jax.jit(lambda *a: flash_decode_attention(
                *a, block_s=bs, interpret=interpret))
            terms = flash_decode_terms(B, S, Kh, G, D, 32.0, hw=hw)
            with obs.span("profile.kernel", kernel="flash_decode",
                          shape=f"B{B}S{S}K{Kh}G{G}D{D}"):
                t = measure(f, q, kc, vc, lengths, reps=reps, warmup=warmup)
            rows.append(_row("flash_decode", terms, t,
                             B=B, S=S, K=Kh, G=G, D=D,
                             shape=f"B{B}S{S}K{Kh}G{G}D{D}",
                             block=[bs], interpret=bool(interpret)))
    return rows


# ---------------------------------------------------------------------------
# serving latency attribution
# ---------------------------------------------------------------------------

def profile_serving(arch: str = "qwen2_7b", max_layers: int = 2,
                    batch: int = 2, prefill_len: int = 8,
                    decode_steps: int = 8,
                    precision_k: Optional[int] = None,
                    registry=None) -> Dict[str, Any]:
    """Profile the real serving path end to end on the host mesh.

    Builds ``launch.serve.build_serve_steps`` for the arch's SMOKE config
    (layer count capped for CI), AOT-compiles prefill and decode with the
    lower/compile phases separately timed, counts jaxpr equations per jit,
    then runs one prefill + ``decode_steps`` decodes under trace spans.
    Latencies land in log-bucket histograms and come back as p50/p95/p99
    digests; compile-time and jaxpr-size gauges go to the active tracer."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs, obs
    from repro.launch import mesh as meshlib
    from repro.launch import serve as S
    from repro.models import transformer as T

    arch_cfg = configs.get(arch).SMOKE
    if max_layers:
        arch_cfg = dc.replace(
            arch_cfg, n_layers=min(arch_cfg.n_layers, int(max_layers)))
    sc = S.ServeConfig(arch=arch, batch=batch,
                       max_seq=prefill_len + decode_steps + 1,
                       prefill_len=prefill_len, precision_k=precision_k)
    from .metrics import MetricsRegistry
    reg = registry if registry is not None else MetricsRegistry()
    reg.meta.update(arch=arch, batch=batch, n_layers=arch_cfg.n_layers,
                    precision_k=precision_k)

    mesh = meshlib.make_host_mesh()
    out: Dict[str, Any] = {"arch": arch, "n_layers": arch_cfg.n_layers,
                           "batch": batch, "prefill_len": prefill_len,
                           "decode_steps": decode_steps,
                           "precision_k": precision_k}
    with mesh:
        prefill, decode, _ = S.build_serve_steps(arch_cfg, sc, mesh)
        params = T.init_params(jax.random.PRNGKey(0), arch_cfg)
        cache = T.init_cache(arch_cfg, sc.batch, sc.max_seq, jnp.float32)
        rng = np.random.RandomState(0)
        batch_in = {"tokens": jnp.asarray(
            rng.randint(0, arch_cfg.vocab, (sc.batch, sc.prefill_len)))}

        # compile-time + program-size gauges, per serving jit
        with obs.span("profile.serve_compile", stage="prefill"):
            pc = time_compile(prefill, params, cache, batch_in)
        js_pre = jaxpr_stats(prefill, params, cache, batch_in)
        obs.gauge("serve.prefill_compile_s", pc["compile_s"])
        obs.gauge("serve.prefill_jaxpr_eqns", js_pre["eqns"])
        reg.gauge("serve.prefill_compile_s", pc["compile_s"])
        reg.gauge("serve.prefill_jaxpr_eqns", js_pre["eqns"])

        db0 = {"tokens": jnp.zeros((sc.batch, 1), jnp.int32),
               "pos": jnp.asarray(sc.prefill_len, jnp.int32)}
        with obs.span("profile.serve_compile", stage="decode"):
            # decode's cache arg is donated; compile from shapes only
            dc_t0 = time.perf_counter()
            dlow = decode.lower(params, jax.eval_shape(lambda: cache), db0)
            dcomp_t = time.perf_counter()
            dlow.compile()
            dcomp = {"lower_s": dcomp_t - dc_t0,
                     "compile_s": time.perf_counter() - dcomp_t}
        js_dec = jaxpr_stats(decode, params, jax.eval_shape(lambda: cache),
                             db0)
        obs.gauge("serve.decode_compile_s", dcomp["compile_s"])
        obs.gauge("serve.decode_jaxpr_eqns", js_dec["eqns"])
        reg.gauge("serve.decode_compile_s", dcomp["compile_s"])
        reg.gauge("serve.decode_jaxpr_eqns", js_dec["eqns"])

        # timed serving loop under spans
        t0 = time.perf_counter()
        with obs.span("serve.prefill", arch=arch, batch=sc.batch,
                      prefill_len=sc.prefill_len):
            logits, cache = prefill(params, cache, batch_in)
            jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        reg.observe("serve.prefill_latency_s", t_prefill)

        tok = jnp.argmax(logits[:, -1, :], axis=-1)
        # one untimed decode absorbs first-dispatch cost (executable load,
        # eager-op compiles) so the percentile digest reflects steady state
        tok, cache = decode(params, cache, {
            "tokens": tok[:, None],
            "pos": jnp.asarray(sc.prefill_len, jnp.int32)})
        jax.block_until_ready(tok)
        for i in range(decode_steps):
            db = {"tokens": tok[:, None],
                  "pos": jnp.asarray(sc.prefill_len + 1 + i, jnp.int32)}
            td = time.perf_counter()
            with obs.span("serve.decode", step=i):
                tok, cache = decode(params, cache, db)
                jax.block_until_ready(tok)
            reg.observe("serve.decode_latency_s",
                        time.perf_counter() - td)

    hp = reg.histograms["serve.decode_latency_s"]
    out.update({
        "prefill": {"latency_s": t_prefill,
                    "compile_s": pc["compile_s"], "lower_s": pc["lower_s"],
                    "jaxpr_eqns": js_pre["eqns"],
                    "tokens_per_s": sc.batch * sc.prefill_len / t_prefill},
        "decode": {"percentiles": hp.percentiles(),
                   "mean_s": hp.mean, "count": hp.count,
                   "compile_s": dcomp["compile_s"],
                   "lower_s": dcomp["lower_s"],
                   "jaxpr_eqns": js_dec["eqns"],
                   "tokens_per_s": (sc.batch * hp.count / hp.sum
                                    if hp.sum > 0 else 0.0)},
    })
    return out
