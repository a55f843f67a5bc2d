#!/usr/bin/env python3
"""Chip smoke: certify -> serve of published-width qwen2_7b on one TPU.

Everything runs in this one process, on the chip JAX finds:

1. qwen2_7b at its published widths, cut in depth to 4 of its 28 layers;
   parameters drawn from a seed on the chip.
2. Seeded requests (8 lanes, prompts of 257-384 tokens, 24 new tokens,
   staggered arrivals) served through ``ContinuousBatchingEngine`` twice:
   uncertified (``JOps``) and under a format map (``FormatQuantJOps``:
   the Pallas ``quant_matmul_format`` and ``flash_decode_certified``
   kernels), and the format map once more through the kernels' eager
   mirrors.
3. Prefill and decode logits compared with the plain f32 reference
   (``repro.models.reference``) under ``default_matmul_precision
   ("highest")``, and the kernels with their mirrors; each maximum
   deviation is printed and held to the tolerance stated below.
4. Certification (``python -m repro.certify``) is left out, with the
   reason printed: XLA:TPU refuses the f64 bit operations of its interval
   arithmetic.

Timings, compile seconds and peak device memory are printed on the way.
The last line is ``{"ok": true, "device": {...}}``; any failure exits
non-zero before it. Without a TPU the script fails at once.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the (data, model) = (2, 2) mesh
                                        # engine against one chip, only

Deviations are max |logits - reference| / max |reference| over the rows
compared: scale-free, since random weights fix no logit scale.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

N_LAYERS = 4            # of 28: 8.1 GB of f32 weights; 8 would be 11.8 GB
LANES = 8
MAX_SEQ = 1024
PAGE = 128              # 257-384-token prompts all pad to 3 pages
N_REQUESTS = 10         # > LANES: two requests wait for a recycled lane
PROMPT_LEN = (257, 384)
MAX_NEW = 24
ARRIVAL_STRIDE = 2      # decode steps between arrivals
REF_LEN = 512           # reference length bucket: prompt + new <= 408

# A per-scope format map in the shape a schema-v3 certificate serves: a
# default, a layer*/attn sub-lane and one per-layer key, so the scanned
# lane machinery resolves three formats in one decode step.
FORMAT_MAP = {
    "": {"k": 12, "emax": 15, "emin": -24},
    "layer*/attn": {"k": 11, "emax": 15, "emin": -24},
    "layer1": {"k": 13, "emax": 15, "emin": -24},
}
K_MIN = min(f["k"] for f in FORMAT_MAP.values())

# Tolerances, as fractions of max |reference logit| (reasons in CHANGES.md):
# f32 against f32 differs only in summation order, ~1e-6; one bf16 pass
# (2^-9 per operand) would exceed 2^-13 many times over.
TOL_F32 = 2.0 ** -13
# each format GEMM rounds operands and result to K_MIN bits (unit 2^-K_MIN);
# 16 units cover the rounding of ~30 GEMMs in a row; 3 fewer bits fail it.
TOL_FORMAT = 2.0 ** (4 - K_MIN)
# kernel and mirror round the same values; f32 summation order differences
# flip a final K_MIN-bit rounding in a few elements only.
TOL_MIRROR = 2.0 ** (2 - K_MIN)

# Certification (python -m repro.certify --arch digits, the fast size)
# does not run on the chip: its f64 interval arithmetic is refused.
CERTIFY_LEFT_OUT = (
    "XLA:TPU cannot compile f64 bitcast-convert (f64 -> u64: 'rewriting "
    "computation to not contain X64 element types ... not implemented'), "
    "which repro/core/interval.py uses in _is_subnormal and _sign_bit and, "
    "through jnp.nextafter, in _down/_up, on every interval it builds; "
    "see ROADMAP.md, Reach")


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, per phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.total += secs

    def since(self, mark: float) -> float:
        return self.total - mark


def peak_gb(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 1e9


def smoke_config():
    from repro import configs
    full = configs.get("qwen2_7b").FULL
    return dataclasses.replace(full, n_layers=N_LAYERS)


def make_requests(cfg, seed: int, n: int = N_REQUESTS,
                  prompt_len=PROMPT_LEN, max_new: int = MAX_NEW):
    from repro.launch.batching import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab, rng.randint(
                        prompt_len[0], prompt_len[1] + 1)).tolist(),
                    max_new_tokens=max_new,
                    arrival_step=i * ARRIVAL_STRIDE)
            for i in range(n)]


def serve(cfg, params, reqs, clock, *, layer_format=None, mesh=None,
          force_kernel=None, max_seq=MAX_SEQ, page=PAGE, lanes=LANES):
    """One engine run; returns ({rid: response}, engine, stats)."""
    from repro import obs
    from repro.launch import serve as S
    from repro.launch.batching import ContinuousBatchingEngine
    sc = S.ServeConfig(arch="qwen2_7b", batch=lanes, max_seq=max_seq,
                       precision_layer_format=layer_format)
    registry = obs.MetricsRegistry()
    eng = ContinuousBatchingEngine(cfg, sc, params, mesh=mesh,
                                   n_lanes=lanes, max_seq=max_seq,
                                   page_size=page,
                                   queue_depth=len(reqs),
                                   registry=registry, keep_logits=True)
    if force_kernel is not None:
        eng.bk.force_kernel = force_kernel
    # the first decode step compiles: note the engine's decode counters
    # after it, so that the rates below leave it out
    first = {}
    engine_step = eng.step

    def step():
        busy = engine_step()
        if eng.steps == 1 and not first:
            first.update(tokens=eng.decode_tokens, s=eng.decode_s)
        return busy

    eng.step = step
    mark, t0 = clock.total, time.perf_counter()
    responses = eng.run(reqs)
    wall = time.perf_counter() - t0
    if len(responses) != len(reqs):
        raise RuntimeError(f"served {len(responses)} of {len(reqs)}")
    steps = eng.steps - 1
    tokens = eng.decode_tokens - first["tokens"]
    secs = eng.decode_s - first["s"]
    stats = {"wall_s": wall, "compile_s": clock.since(mark),
             "decode_steps": eng.steps, "step_ms": 1e3 * secs / steps,
             "lanes_busy": tokens / steps, "tokens_per_s": tokens / secs}
    return {r["id"]: r for r in responses}, eng, stats


def describe(name: str, st) -> str:
    return (f"serve {name}: {st['wall_s']:.2f} s wall ({st['compile_s']:.2f} "
            f"s compiling), {st['decode_steps']} decode steps; after the "
            f"first: {st['step_ms']!r} ms per step, {st['lanes_busy']!r} "
            f"busy lanes per step, {st['tokens_per_s']!r} decode tokens/s")


def decode_hlo_has_kernel(eng) -> bool:
    """Whether the engine's compiled decode step holds a Pallas TPU
    kernel: the dispatches choose the kernels only on a TPU, so this shows
    that they, and not their mirrors, ran."""
    import jax.numpy as jnp
    z = jnp.zeros((eng.n_lanes,), jnp.int32)
    text = eng._decode.lower(eng.params, eng.cache, z, z).compile().as_text()
    return "tpu_custom_call" in text


def reference_rows(cfg, params, reqs, responses, ref_len: int = REF_LEN):
    """{rid: [n_tokens, vocab]} plain-f32 reference logits at the rows the
    engine's logits came from, teacher-forced on the engine's tokens."""
    import jax
    import jax.numpy as jnp
    from repro.models.reference import dense_forward

    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: dense_forward(p, cfg, t)[0])
        out = {}
        for req in reqs:
            toks = responses[req.rid]["tokens"]
            seq = list(req.prompt) + toks[:-1]
            if len(seq) > ref_len:
                raise ValueError(f"request {req.rid} needs {len(seq)} "
                                 f"reference positions (> {ref_len})")
            padded = np.zeros((1, ref_len), np.int32)
            padded[0, :len(seq)] = seq
            P = len(req.prompt)
            logits = fwd(params, jnp.asarray(padded))
            out[req.rid] = np.asarray(logits[P - 1:P - 1 + len(toks)])
    return out


def deviation(reqs, got, want, *, shared_context_only=False):
    """(prefill, decode, rows compared) max deviation, relative to the
    largest |want| logit. ``shared_context_only`` compares a request's
    rows only while both runs had emitted the same tokens (a near-tie can
    flip an argmax, after which the two contexts differ)."""
    pre = dec = scale = 0.0
    rows = 0
    for req in reqs:
        a, b = got[req.rid], want[req.rid]
        la, lb = np.asarray(a["logits"]), np.asarray(b["logits"])
        n = min(len(la), len(lb))
        if shared_context_only:
            ta, tb = a["tokens"], b["tokens"]
            n = next((i + 1 for i in range(n) if ta[i] != tb[i]), n)
        d = np.max(np.abs(la[:n] - lb[:n]), axis=1)
        pre = max(pre, float(d[0]))
        if n > 1:
            dec = max(dec, float(d[1:].max()))
        scale = max(scale, float(np.abs(lb[:n]).max()))
        rows += n
    return pre / scale, dec / scale, rows


def check(name: str, value: float, tol: float):
    verdict = "ok" if value <= tol else "EXCEEDS"
    log(f"{name}: max deviation {value!r} (tolerance {tol!r}) {verdict}")
    if value > tol:
        raise SystemExit(f"chip_smoke: {name} deviation {value!r} > {tol!r}")


def init_params_on_chip(cfg, seed: int):
    import jax
    from repro.models import transformer as T
    params = jax.jit(T.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return jax.block_until_ready(params)


def one_chip(args, dev, clock):
    from repro.models import transformer as T

    cfg = smoke_config()
    full = T.analytic_params(dataclasses.replace(cfg, n_layers=28))
    n = T.analytic_params(cfg)
    log(f"config qwen2_7b FULL widths (d_model {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads}, d_head {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}), cut in depth: n_layers "
        f"{cfg.n_layers} of 28; {n} of {full} parameters, "
        f"{n * 4 / 1e9:.3f} GB of f32 weights")
    t0 = time.perf_counter()
    params = init_params_on_chip(cfg, args.seed)
    log(f"params initialised on {dev.device_kind} in "
        f"{time.perf_counter() - t0:.2f} s; peak memory {peak_gb(dev):.3f} GB")

    reqs = make_requests(cfg, args.seed)
    log(f"{len(reqs)} requests, prompts {[len(r.prompt) for r in reqs]}, "
        f"{MAX_NEW} new tokens each, {LANES} lanes, max_seq {MAX_SEQ}, "
        f"arrivals every {ARRIVAL_STRIDE} steps")

    runs = {}
    for name, kw in (("uncertified", {}),
                     ("format", {"layer_format": FORMAT_MAP}),
                     ("format-mirror", {"layer_format": FORMAT_MAP,
                                        "force_kernel": False})):
        resp, eng, st = serve(cfg, params, reqs, clock, **kw)
        if name == "format" and not decode_hlo_has_kernel(eng):
            raise SystemExit("chip_smoke: the certified decode step holds "
                             "no tpu_custom_call — the kernels did not run")
        runs[name] = resp
        del eng
        log(f"{describe(name, st)}; peak memory {peak_gb(dev):.3f} GB")
    log("format decode step holds tpu_custom_call: the Pallas kernels ran")

    mark, t0 = clock.total, time.perf_counter()
    for name in ("uncertified", "format"):
        ref = reference_rows(cfg, params, reqs, runs[name])
        ref = {rid: {"logits": v, "tokens": runs[name][rid]["tokens"]}
               for rid, v in ref.items()}
        pre, dec, rows = deviation(reqs, runs[name], ref)
        tol = TOL_F32 if name == "uncertified" else TOL_FORMAT
        log(f"{name} vs f32 reference over {rows} rows")
        check(f"{name} prefill vs f32 reference", pre, tol)
        check(f"{name} decode vs f32 reference", dec, tol)
    log(f"f32 reference: {time.perf_counter() - t0:.2f} s "
        f"({clock.since(mark):.2f} s compiling); peak memory "
        f"{peak_gb(dev):.3f} GB")
    pre, dec, rows = deviation(reqs, runs["format"], runs["format-mirror"],
                               shared_context_only=True)
    same = sum(runs["format"][r.rid]["tokens"]
               == runs["format-mirror"][r.rid]["tokens"] for r in reqs)
    log(f"kernels vs eager mirror over {rows} shared-context rows; "
        f"{same}/{len(reqs)} requests token-identical")
    check("format kernels prefill vs eager mirror", pre, TOL_MIRROR)
    check("format kernels decode vs eager mirror", dec, TOL_MIRROR)
    del params, runs
    log(f"certify phase left out: {CERTIFY_LEFT_OUT}")


def four_chips(args, devs, clock):
    """The (2, 2) mesh engine against the one-chip engine: same config,
    requests and format map; tokens and logits compared, and params and
    cache checked to span all four devices."""
    from repro.launch.mesh import make_serving_mesh

    cfg = smoke_config()
    reqs = make_requests(cfg, args.seed)
    params = init_params_on_chip(cfg, args.seed)      # on devs[0]
    one, eng, st = serve(cfg, params, reqs, clock, layer_format=FORMAT_MAP)
    del eng
    log(describe("format on one chip", st))
    mesh = make_serving_mesh(data=2, model=2, devices=devs[:4])
    four, eng, st = serve(cfg, params, reqs, clock, layer_format=FORMAT_MAP,
                          mesh=mesh)
    del params
    log(describe("format on the (2, 2) mesh", st))
    spans = {
        "params": len(eng.params["layers"]["mlp"]["w_gate"].sharding
                      .device_set),
        "cache": len(eng.cache["k"].sharding.device_set),
    }
    log(f"devices spanned: {spans}")
    if min(spans.values()) != 4:
        raise SystemExit(f"chip_smoke: mesh engine spans {spans}, not 4")
    if not decode_hlo_has_kernel(eng):
        raise SystemExit("chip_smoke: the mesh decode step holds no "
                         "tpu_custom_call")
    same = sum(one[r.rid]["tokens"] == four[r.rid]["tokens"] for r in reqs)
    bitwise = all(np.array_equal(one[r.rid]["logits"], four[r.rid]["logits"])
                  for r in reqs)
    pre, dec, rows = deviation(reqs, four, one, shared_context_only=True)
    log(f"mesh vs one chip: {same}/{len(reqs)} requests token-identical, "
        f"logits bitwise equal: {bitwise}, over {rows} shared-context rows")
    check("mesh prefill vs one chip", pre, TOL_MIRROR)
    check("mesh decode vs one chip", dec, TOL_MIRROR)
    for i, d in enumerate(devs[:4]):
        log(f"device {i} peak memory {peak_gb(d):.3f} GB")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (2, 2) mesh engine against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.jitcache import use_compile_cache
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX found {dev.platform!r}")
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, found {len(devs)}")
    cache_dir = use_compile_cache()
    log(f"device {dev.device_kind} x{len(devs)}; jax {jax.__version__}; "
        f"compile cache {cache_dir}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args, devs, clock)
    else:
        one_chip(args, dev, clock)
    log(f"total {time.perf_counter() - t0:.2f} s, of which "
        f"{clock.total:.2f} s compiling")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
