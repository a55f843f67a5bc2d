"""Serving: prefill + decode step builders (the inference shape families).

``build_serve_steps`` returns jitted SPMD (prefill_fn, decode_fn) over the
production mesh with cache shardings from parallel.sharding (KV-heads or
KV-sequence over "model" — the latter makes XLA build the distributed-
softmax flash pattern).

Includes the certified low-precision mode: with ``precision_k`` set, all
matmul-heavy blocks run through the emulated k-bit path (matching what the
CAA analysis certified) — on real low-precision silicon this is where the
speedup cashes in; here it demonstrates the bit-exact pipeline.

With ``--certificates STORE_DIR`` the flag becomes certificate-driven:
``precision_k`` is read from the persisted certificate set for (arch,
exact params) in the :mod:`repro.certify` store — certifying on first use,
loading thereafter — and every response carries the certificate's
(δ̄, ε̄, k) error bars.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, obs
from repro.core.backend import Backend, JOps
from repro.core.backend import UnrolledLayerLoop  # noqa: F401 — the
# unrolled mixin is re-exported here as the serving-side differential
# baseline (compose it in front of a scanned backend; see tests/examples)
from repro.models import transformer as T
from repro.parallel import sharding as sh
from repro.launch import mesh as meshlib
from repro.launch.jitcache import use_compile_cache

log = obs.get_logger("serve")


def _emit_health(bk, out, k, emax=127, emin=-126):
    """Stream per-scope numeric-health stats to the backend's attached
    :class:`repro.obs.ViolationMonitor` (if any) via ``jax.debug.callback``.

    The stats ride alongside the jitted computation as a side effect — the
    returned serving values are untouched bitwise, and with no monitor
    attached (the default) nothing is staged at all, so the certified
    serving differentials are exactly what they were without observability.
    ``k``/``emax``/``emin`` may be traced scalars (the scanned per-layer
    paths)."""
    mon = getattr(bk, "monitor", None)
    if mon is None:
        return
    from repro.core.quantize import numeric_health
    stats = numeric_health(out, k, emax, emin)
    path = list(bk.scope_path)

    def _cb(max_abs, min_nonzero, n_over, n_under, n_nonfinite):
        mon.observe_scope(path, {
            "max_abs": float(max_abs), "min_nonzero": float(min_nonzero),
            "n_over": int(n_over), "n_under": int(n_under),
            "n_nonfinite": int(n_nonfinite)})

    jax.debug.callback(_cb, stats["max_abs"], stats["min_nonzero"],
                       stats["n_over"], stats["n_under"],
                       stats["n_nonfinite"])


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    arch: str = "qwen2_7b"
    batch: int = 8
    max_seq: int = 256
    prefill_len: int = 128
    compute_dtype: str = "float32"
    cache_dtype: str = "float32"     # bf16 on TPU; 'fp8' = certified 8-bit
    param_dtype: str = "same"        # 'fp8' = certified 8-bit storage
    precision_k: Optional[int] = None
    # Per-layer mixed-precision map {layer_scope: k} from a v2 certificate:
    # matmuls inside a mapped scope run at that scope's k, everything else at
    # precision_k. Requires precision_k as the default/fallback.
    precision_layer_k: Optional[Dict[str, int]] = None
    # Per-scope FULL-format map {layer_scope: FpFormat descriptor} from a
    # schema-v3 certificate: matmuls inside a mapped scope run in that
    # scope's custom (k, emax, emin) format (saturating clamp + subnormal
    # emulation); the "" entry is the default for unmapped scopes. Takes
    # precedence over precision_layer_k / precision_k.
    precision_layer_format: Optional[Dict[str, Dict]] = None
    # Certificate-driven precision: path of a repro.certify store; when set,
    # precision_k is taken from the stored CertificateSet for (arch, params)
    # (and precision_layer_k from its mixed map, when certified) and
    # responses carry (δ̄, ε̄, k) error bars.
    certificates: Optional[str] = None
    # §Perf policy matrix: keep params resident on the model axis (no
    # data-axis gathers) — the right call for decode with ≤~70B params.
    # None → auto by param count; False reproduces the greedy-FSDP baseline.
    params_resident: Optional[bool] = None


class QuantJOps(JOps):
    """JOps whose matmuls run in the certified k-bit emulation.

    ``monitor`` (a :class:`repro.obs.ViolationMonitor`, default None)
    receives per-scope numeric-health stats of every matmul product —
    attached by :func:`_backend` when the CLI asked for violation
    monitoring; None stages nothing."""

    monitor = None

    def __init__(self, k: int, *a, **kw):
        super().__init__(*a, **kw)
        self._k = k

    def matmul(self, a, b):
        from repro.core.quantize import _quantize_normal
        aq = _quantize_normal(a.astype(jnp.float32), self._k)
        bq = _quantize_normal(b.astype(jnp.float32), self._k)
        out = jnp.matmul(aq, bq, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
        _emit_health(self, out, self._k)
        return _quantize_normal(out, self._k).astype(self.compute_dtype)

    # routed experts tile by tile through the k-bit matmul above
    grouped_matmul = Backend.grouped_matmul

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        # one traced body serves every layer, so monitor observations from
        # inside the scan carry the stacked wildcard scope JOps.layer_loop
        # pushes (matching the certificate's layer* / layer<i> envelope
        # keys), not an empty path. The span measures TRACE time of the
        # scanned quantize/matmul body (once per compile) — the per-scope
        # attribution of compile cost
        with obs.span("serve.layer_scan", backend=type(self).__name__,
                      layers=n_layers):
            return super().layer_loop(fn, stacked_params, x, n_layers, aux,
                                      first)


class _SuffixLanes:
    """Scan-side sub-layer scope resolution for the quantised serving
    backends.

    Inside the ONE scanned layer body, the current scope suffix (e.g.
    ``("attn",)`` under ``bk.scope("attn")``) picks an ``[L]`` lane built
    by resolving ``outer + [layer{i}, *suffix]`` against the certificate's
    scope map — so ``layer*/attn``-style sub-layer certificate keys apply
    at the right ops instead of being dropped to per-layer granularity.
    With no sub-layer keys in the map, every suffix lane resolves to the
    layer lane (``layer{i}`` matches the longer path), preserving the
    per-layer behavior exactly. Lanes are cached per suffix; ``_dyn``
    holds the gathered per-layer value while tracing the scan body."""

    def _lane_static(self, path):
        raise NotImplementedError

    def _init_lanes(self):
        self._stack_ctx = None
        self._lane_cache: Dict[tuple, Any] = {}
        self._layer_idx = None
        self._dyn = None

    def _suffix_lane(self):
        outer, n_layers, first = self._stack_ctx
        suffix = tuple(self.scope_path[len(outer) + 1:])
        lane = self._lane_cache.get(suffix)
        if lane is None:
            lane = jnp.asarray(
                [self._lane_static(outer + [f"layer{first + i}", *suffix])
                 for i in range(n_layers)], jnp.int32)
            self._lane_cache[suffix] = lane
        return lane

    def _refresh_dyn(self):
        self._dyn = self._suffix_lane()[self._layer_idx]

    def _scope_changed(self):
        super()._scope_changed()
        if (getattr(self, "_stack_ctx", None) is not None
                and getattr(self, "_layer_idx", None) is not None):
            self._refresh_dyn()

    def _lane_loop(self, fn, stacked_params, x, n_layers, aux, super_loop,
                   first: int = 0):
        # super_loop (JOps.layer_loop) pushes the one layer* scope level
        # that the suffix lanes look past
        outer = list(self.scope_path)
        self._stack_ctx = (outer, n_layers, first)
        self._lane_cache = {}

        def scoped_fn(p, carry, i, a):
            self._layer_idx = i - first
            self._refresh_dyn()
            try:
                return fn(p, carry, i, a)
            finally:
                self._layer_idx = None
                self._dyn = None

        try:
            with obs.span("serve.layer_scan", backend=type(self).__name__,
                          layers=n_layers):
                return super_loop(scoped_fn, stacked_params, x,
                                  n_layers, aux, first)
        finally:
            self._stack_ctx = None
            self._lane_cache = {}


class MixedQuantJOps(_SuffixLanes, JOps):
    """JOps whose matmuls run at a per-layer certified precision.

    ``layer_k`` maps scope names (the same bk.scope(...) names the analysis
    gated on) to mantissa precisions; matmuls outside every mapped scope run
    at ``default_k`` — exactly the semantics the mixed certificate proved.
    Outside ``layer_loop`` the current scope path resolves a static Python k;
    inside the scanned layer stack (one traced body for all layers) the
    per-layer k is fetched from a scanned i32 lane by the carry's layer
    index — sub-layer keys resolve through :class:`_SuffixLanes` — and
    flows through :func:`repro.core.quantize.quantize_to_k`, whose traced-k
    rounding is bitwise-identical to the static path — so a single
    compilation serves every layer's precision.
    """

    def __init__(self, layer_k: Dict[str, int], default_k: int, *a, **kw):
        super().__init__(*a, **kw)
        self.layer_k = {str(s): int(v) for s, v in (layer_k or {}).items()}
        self.default_k = int(default_k)
        self._init_lanes()

    def _lane_static(self, path):
        from repro.core.analyze import resolve_scope_value
        return resolve_scope_value(path, self.layer_k, self.default_k)

    def _current_k(self):
        if self._dyn is not None:
            return self._dyn
        return self._lane_static(self.scope_path)

    monitor = None

    def matmul(self, a, b):
        from repro.kernels.quant_matmul import quant_matmul_dynamic_k
        k = self._current_k()
        out = quant_matmul_dynamic_k(a, b, k)
        _emit_health(self, out, k)
        return out.astype(self.compute_dtype)

    grouped_matmul = Backend.grouped_matmul

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        return self._lane_loop(fn, stacked_params, x, n_layers, aux,
                               super().layer_loop, first)


class _FmtTriple:
    """Opaque (k, emax, emin) holder for scope maps — NOT a sequence, so
    :func:`repro.core.scopes.resolve_scope_value` never mistakes it for an
    ``[L]`` per-layer array when a ``layer*`` wildcard key matches."""

    __slots__ = ("triple",)

    def __init__(self, triple):
        self.triple = triple


class FormatQuantJOps(_SuffixLanes, JOps):
    """JOps whose matmuls run in per-scope certified CUSTOM FORMATS.

    ``layer_format`` maps scope names (the bk.scope(...) names the format
    synthesizer certified) to FpFormat descriptor dicts; the ``""`` entry
    (or ``default_format``) covers matmuls outside every mapped scope —
    exactly the semantics a schema-v3 certificate proves: operands and
    result of each matmul rounded into the scope's (k, emax, emin)
    saturating format. Outside ``layer_loop`` the scope resolves a static
    (k, emax, emin) triple; inside the scanned layer stack the per-layer
    triple is fetched from a scanned i32[L, 3] lane (sub-layer keys like
    ``layer*/attn`` resolve through :class:`_SuffixLanes`) — both flow
    through
    :func:`repro.kernels.quant_matmul.quant_matmul_format_ref`, whose
    traced-format rounding is bitwise the static path, so a single
    compilation serves every layer's format.
    """

    def __init__(self, layer_format: Dict[str, Dict],
                 default_format: Optional[Dict] = None, *a, **kw):
        super().__init__(*a, **kw)
        self.layer_format = {str(s): dict(f)
                             for s, f in (layer_format or {}).items()}
        default = default_format or self.layer_format.get("")
        if default is None:
            raise ValueError("layer_format needs a '' default entry (or an "
                             "explicit default_format) for unmapped scopes")
        fmts = list(self.layer_format.values()) + [dict(default)]
        # the (k, emax, emin) triple is per-scope data; the flags must be
        # map-uniform (serving_layer_format guarantees it) because they are
        # compiled statically into the quantisation path — serving a flag
        # the certificate didn't prove would silently change the arithmetic
        flags = {(f.get("has_subnormals", True), f.get("saturating", True))
                 for f in fmts}
        if len(flags) != 1:
            raise ValueError(f"layer_format mixes subnormal/saturation "
                             f"flags {sorted(flags)} — not representable by "
                             "one serving map")
        self.has_subnormals, self.saturating = next(iter(flags))
        if any(f.get("max_finite_override") is not None for f in fmts):
            raise NotImplementedError(
                "encoding-clipped formats (max_finite_override) are not "
                "servable through the (k, emax, emin) triple path")
        self.default_triple = self._triple(default)
        # triples are held in an opaque wrapper: resolve_scope_value
        # layer-indexes tuple values matched through a "layer*" wildcard
        # (the [L]-per-layer map convenience), which would tear a bare
        # (k, emax, emin) apart — wrapped, the triple passes through whole
        self._triples = {s: _FmtTriple(self._triple(f))
                         for s, f in self.layer_format.items() if s}
        self._init_lanes()

    @staticmethod
    def _triple(f: Dict) -> tuple:
        return (int(f["k"]), int(f["emax"]), int(f["emin"]))

    def _lane_static(self, path):
        from repro.core.analyze import resolve_scope_value
        got = resolve_scope_value(path, self._triples,
                                  _FmtTriple(self.default_triple))
        return got.triple

    def _current_fmt(self):
        if self._dyn is not None:
            return self._dyn
        return jnp.asarray(self._lane_static(self.scope_path), jnp.int32)

    monitor = None
    # Certificate-aware flash decode: gqa_attention offers the S==1 decode
    # step to decode_attention below, which quantizes q/k/v tiles into the
    # scope's certified format (resolved through the SAME _SuffixLanes
    # machinery as matmul, so layer*/attn sub-lanes apply). Class-level so
    # tests can force the composed einsum/softmax path off.
    use_flash_decode = True
    # None: the Pallas kernels on a TPU, their eager mirrors elsewhere.
    # A check that compares the two on the chip sets False on an instance.
    force_kernel = None

    def _axis(self, name: str, dim: int):
        """``name`` if that mesh axis splits ``dim`` evenly, else None."""
        size = 1 if self.mesh is None else meshlib.axis_size(self.mesh, name)
        return name if size > 1 and dim % size == 0 else None

    def _per_shard(self, fn, args, in_specs, out_spec):
        """Run ``fn`` once per device of a multi-device mesh. XLA cannot
        partition a Mosaic kernel, so the call goes through shard_map: each
        device takes its lanes ("data") and its output columns or KV heads
        ("model"). No contraction is split, so the bits are one device's."""
        if self.mesh is None or self.mesh.devices.size == 1:
            return fn(*args)
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=tuple(P(*s) for s in in_specs),
                             out_specs=P(*out_spec), check_vma=False)(*args)

    def matmul(self, a, b):
        from repro.kernels.quant_matmul import quant_matmul_format_dispatch
        fmt = self._current_fmt()

        def gemm(a, b, fmt):
            return quant_matmul_format_dispatch(
                a, b, fmt, has_subnormals=self.has_subnormals,
                saturating=self.saturating, force_kernel=self.force_kernel)

        lanes = self._axis("data", a.shape[0])
        cols = self._axis("model", b.shape[-1])
        rest = (None,) * (a.ndim - 2)
        out = self._per_shard(gemm, (a, b, fmt),
                              ((lanes, *rest, None), (None, cols), (None,)),
                              (lanes, *rest, cols))
        _emit_health(self, out, fmt[0], fmt[1], fmt[2])
        return out.astype(self.compute_dtype)

    def grouped_matmul(self, x, w, layer, routes):
        """The routed experts' products through the grouped format GEMM,
        in the scope's format, over the whole stacked expert weights: the
        kernel fetches layer ``layer``'s blocks in place. One device
        only: no layer shares the experts out over a mesh yet, and run on
        each device the product would read every expert there."""
        from repro.kernels.quant_matmul import (
            grouped_quant_matmul_format_dispatch)
        if self.mesh is not None and self.mesh.devices.size > 1:
            raise NotImplementedError(
                "grouped format GEMM on a multi-device mesh: the routed "
                "experts would be replicated on every device")
        fmt = self._current_fmt()
        meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                          jnp.asarray(routes.n_real, jnp.int32)])

        def gemm(x, w, tile_expert, meta, fmt):
            return grouped_quant_matmul_format_dispatch(
                x, w, tile_expert, meta, fmt, tm=routes.tm,
                has_subnormals=self.has_subnormals,
                saturating=self.saturating, force_kernel=self.force_kernel)

        out = gemm(x, w, routes.tile_expert, meta, fmt)
        _emit_health(self, out, fmt[0], fmt[1], fmt[2])
        return out.astype(self.compute_dtype)

    def decode_attention(self, q, k, v, lengths):
        if not self.use_flash_decode:
            return None
        from repro.kernels.flash_decode import certified_decode_attention
        fmt = self._current_fmt()

        def attend(q, k, v, lengths, fmt):
            return certified_decode_attention(
                q, k, v, lengths, fmt, has_subnormals=self.has_subnormals,
                saturating=self.saturating, force_kernel=self.force_kernel)

        lanes = self._axis("data", q.shape[0])
        heads = self._axis("model", q.shape[1])
        out = self._per_shard(
            attend, (q, k, v, lengths, fmt),
            ((lanes, heads, None, None), (lanes, None, heads, None),
             (lanes, None, heads, None), (lanes,), (None,)),
            (lanes, heads, None, None))
        return out.astype(self.compute_dtype)

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        return self._lane_loop(fn, stacked_params, x, n_layers, aux,
                               super().layer_loop, first)


def _backend(sc: ServeConfig, mesh=None, monitor=None):
    # every backend gets the mesh: JOps.shard_hint('act_batch') threads the
    # lane-batch sharding constraint through the scanned layer body (a
    # no-op on 1-device meshes), and MoE expert parallelism reads bk.mesh
    dt = jnp.bfloat16 if sc.compute_dtype == "bfloat16" else jnp.float32
    bk = None
    if sc.precision_layer_format:
        bk = FormatQuantJOps(sc.precision_layer_format, None,
                             dt, jnp.float32, mesh=mesh)
    elif sc.precision_layer_k:
        if sc.precision_k is None:
            raise ValueError("precision_layer_k needs precision_k as the "
                             "default for unmapped scopes")
        bk = MixedQuantJOps(sc.precision_layer_k, sc.precision_k,
                            dt, jnp.float32, mesh=mesh)
    elif sc.precision_k is not None:
        bk = QuantJOps(sc.precision_k, dt, jnp.float32, mesh=mesh)
    if bk is not None:
        bk.monitor = monitor
        return bk
    if monitor is not None:
        raise ValueError("violation monitoring needs a certified quantised "
                         "backend (precision_k / layer map / format map)")
    return JOps(dt, jnp.float32, mesh=mesh)


DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
          "fp8": jnp.float8_e4m3fn}


def build_serve_steps(arch_cfg, sc: ServeConfig, mesh, monitor=None):
    bk = _backend(sc, mesh=mesh, monitor=monitor)
    resident = sc.params_resident
    if resident is None:  # §Perf auto-policy: resident decode ≤ ~70B params
        resident = T.analytic_params(arch_cfg) <= 70e9
    sc = dataclasses.replace(sc, params_resident=bool(resident))
    cache_dtype = DTYPES.get(sc.cache_dtype, jnp.float32)

    def _fwd_kwargs(batch):
        kwargs = {}
        if arch_cfg.frontend == "audio":
            if "enc_out" in batch:          # decode: reuse prefill's encoding
                kwargs["enc_out"] = batch["enc_out"]
            else:
                kwargs["enc_embeds"] = batch["frontend"]
        elif arch_cfg.frontend == "vision" and "frontend" in batch:
            # prefill only: the patch KV lives in the cache afterwards —
            # re-prepending 256 patches per decoded token was a 700x
            # HLO-flop bug caught by the roofline calibration test (§Perf)
            kwargs["frontend_embeds"] = batch["frontend"]
        return kwargs

    def prefill_fn(params, cache, batch):
        kwargs = _fwd_kwargs(batch)
        enc_out = None
        if arch_cfg.enc_dec:
            enc_out = T.encode(bk, params, arch_cfg, batch["frontend"])
            kwargs = {"enc_out": enc_out}
        logits, cache = T.forward(bk, params, arch_cfg, batch["tokens"],
                                  cache=cache, q_offset=0, **kwargs)
        if arch_cfg.enc_dec:
            return logits[:, -1:, :], cache, bk.value_of(enc_out)
        return logits[:, -1:, :], cache

    def decode_fn(params, cache, batch):
        """One token for every sequence at absolute position batch['pos']."""
        logits, cache = T.forward(bk, params, arch_cfg, batch["tokens"],
                                  cache=cache, q_offset=batch["pos"],
                                  **_fwd_kwargs(batch))
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1)
        return next_tok, cache

    # shardings
    key = jax.random.PRNGKey(0)
    pshapes = jax.eval_shape(lambda: T.init_params(key, arch_cfg))
    p_sh = sh.shard_params(pshapes, mesh, model_only=bool(sc.params_resident))
    cache_shapes = jax.eval_shape(
        lambda: T.init_cache(arch_cfg, sc.batch, sc.max_seq, cache_dtype))
    c_sh = sh.shard_cache(cache_shapes, mesh, arch_cfg)
    rep = NamedSharding(mesh, P())
    b_sh_prefill = {"tokens": sh.shard_batch(mesh, sc.batch, sc.prefill_len)}
    b_sh_decode = {"tokens": sh.shard_batch(mesh, sc.batch, 1), "pos": rep}
    if arch_cfg.frontend:
        fsh = NamedSharding(mesh, sh.batch_spec(mesh, sc.batch,
                                                arch_cfg.frontend_seq))
        b_sh_prefill["frontend"] = fsh
        if arch_cfg.enc_dec:
            b_sh_decode["enc_out"] = fsh  # reused encoder states

    prefill_out_sh = (rep, c_sh, rep) if arch_cfg.enc_dec else (rep, c_sh)
    prefill = jax.jit(prefill_fn,
                      in_shardings=(p_sh, c_sh, b_sh_prefill),
                      out_shardings=prefill_out_sh,
                      donate_argnums=(1,))
    decode = jax.jit(decode_fn,
                     in_shardings=(p_sh, c_sh, b_sh_decode),
                     out_shardings=(rep, c_sh),
                     donate_argnums=(1,))
    return prefill, decode, {"params": p_sh, "cache": c_sh}


def apply_certificates(sc: ServeConfig, arch_cfg, params, **certify_kw) -> tuple:
    """Resolve ``sc.certificates`` into a concrete precision_k.

    Loads (or creates, on first use) the certificate set for this exact
    (arch, params) pair from the store and pins ``precision_k`` to its
    ``serving_k``. Returns (updated ServeConfig, CertificateSet) — the set's
    ``error_bars()`` is what gets attached to responses. ``certify_kw``
    (e.g. ``k_max=32``) reaches :func:`repro.certify.certify_lm` — a wider
    range is a *different* store request, so an uncertifiable result at the
    default range never shadows it.
    """
    from repro.certify import serving_certificate

    cs = serving_certificate(sc.arch, arch_cfg, params, sc.certificates,
                             **certify_kw)
    k = cs.serving_k
    if k is None:
        # No usable uniform k across the set (e.g. a v3 format-only
        # certificate whose required_k is None). A complete layer_format
        # map still carries its own "" default, so format serving does not
        # need a uniform fallback k — degrade to format-only serving
        # rather than refusing to serve a certified model.
        lf = cs.serving_layer_format
        if lf is not None and lf.get(""):
            obs.event("serve.format_only_degrade", arch=sc.arch,
                      scopes=len(lf))
            return dataclasses.replace(
                sc, precision_k=None,
                precision_layer_k=None,
                precision_layer_format=lf), cs
        raise RuntimeError(
            f"certificate store holds no certifiable precision for {sc.arch} "
            "— serve at full precision, or widen the search "
            "(--certify-k-max on the CLI)")
    # a v2 certificate with a jointly-certified per-layer map upgrades the
    # uniform k to mixed-precision execution (unmapped scopes stay at k); a
    # v3 certificate further upgrades to full per-scope custom formats
    # (mantissa AND exponent range certified)
    return dataclasses.replace(
        sc, precision_k=k,
        precision_layer_k=cs.serving_layer_k,
        precision_layer_format=cs.serving_layer_format), cs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_7b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prefill-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=16)
    ap.add_argument("--precision-k", type=int, default=None)
    ap.add_argument("--certificates", default=None, metavar="STORE_DIR",
                    help="pick precision_k from the certificate store and "
                         "attach (δ̄, ε̄, k) error bars to responses")
    ap.add_argument("--certify-k-max", type=int, default=None,
                    help="ceiling of the certification search (default 24; "
                         "53 with --certify-mixed/--certify-formats)")
    ap.add_argument("--certify-mixed", action="store_true",
                    help="certify (or load) a per-layer {scope: k} map via "
                         "the scan-native stacked analysis and serve it "
                         "through the scanned per-layer quantisation path")
    ap.add_argument("--certify-formats", action="store_true",
                    help="additionally certify per-scope custom (k, emin, "
                         "emax) formats; an attached map serves through the "
                         "traced-format quantisation path")
    ap.add_argument("--metrics", default=None, metavar="OUT.JSONL",
                    help="append a serving-metrics snapshot (latency "
                         "histograms, tokens/s, occupancy, violation "
                         "counters) as one JSONL object")
    ap.add_argument("--prom", default=None, metavar="OUT.PROM",
                    help="also write the metrics as a Prometheus text "
                         "exposition file (no server; point a scraper/"
                         "node-exporter textfile collector at it)")
    ap.add_argument("--monitor", action="store_true",
                    help="attach certificate-violation monitors: per-scope "
                         "numeric-health checked against the certified "
                         "enclosures, plus one sampled empirical-error "
                         "check against δ̄ (requires --certificates)")
    ap.add_argument("--trace", default=None, metavar="OUT.JSONL",
                    help="record a JSONL trace of the serving run: "
                         "prefill/decode spans, the scanned layer-body "
                         "trace span, per-jit compile-time and jaxpr-size "
                         "gauges; render with `python -m repro.obs report`")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.trace:
        obs.configure(path=args.trace, program="repro.launch.serve",
                      argv=argv)
    if ((args.certify_mixed or args.certify_formats or
         args.certify_k_max is not None) and args.certificates is None):
        ap.error("--certify-mixed/--certify-formats/--certify-k-max require "
                 "--certificates STORE_DIR")
    if args.monitor and args.certificates is None:
        ap.error("--monitor needs --certificates (violations are relative "
                 "to a certificate's bounds)")

    arch_cfg = configs.get(args.arch).SMOKE
    extra = arch_cfg.frontend_seq if arch_cfg.frontend == "vision" else 0
    sc = ServeConfig(arch=args.arch, batch=args.batch,
                     max_seq=args.prefill_len + args.decode_steps + 1 + extra,
                     prefill_len=args.prefill_len,
                     precision_k=args.precision_k,
                     certificates=args.certificates)
    params = T.init_params(jax.random.PRNGKey(0), arch_cfg)
    certset = None
    if sc.certificates is not None:
        kw = {}
        if args.certify_mixed or args.certify_formats:
            # flags map 1:1 onto the certify CLI's --mixed/--formats so the
            # two tools address the same store entry for the same intent
            kw.update(mixed=args.certify_mixed,
                      formats=args.certify_formats,
                      k_max=args.certify_k_max or 53)
        elif args.certify_k_max is not None:
            kw["k_max"] = args.certify_k_max
        sc, certset = apply_certificates(sc, arch_cfg, params, **kw)
        log.info("certificate resolved",
                 k=sc.precision_k,
                 source=("store" if certset.meta.get("from_store")
                         else "fresh analysis (now persisted)"),
                 mixed_scopes=(None if sc.precision_layer_k is None
                               else len(sc.precision_layer_k)),
                 format_scopes=(None if sc.precision_layer_format is None
                                else len(sc.precision_layer_format)),
                 error_bars=certset.error_bars())
    monitor = None
    if args.monitor:
        monitor = obs.ViolationMonitor.from_certificate_set(certset)
        log.info("violation monitor attached",
                 envelopes=len(monitor.envelopes),
                 dbar_u=monitor.dbar_u)
    registry = obs.MetricsRegistry()
    registry.meta.update(arch=args.arch, batch=sc.batch,
                         precision_k=sc.precision_k)
    mesh = meshlib.make_host_mesh()
    with mesh:
        with obs.span("serve.build_steps", arch=args.arch):
            prefill, decode, _ = build_serve_steps(arch_cfg, sc, mesh,
                                                   monitor=monitor)
        cache = T.init_cache(arch_cfg, sc.batch, sc.max_seq, jnp.float32)
        import numpy as np
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(
            rng.randint(0, arch_cfg.vocab, (sc.batch, sc.prefill_len)))}
        if arch_cfg.frontend:
            batch["frontend"] = rng.randn(
                sc.batch, arch_cfg.frontend_seq,
                arch_cfg.frontend_dim).astype("float32")
        if obs.enabled():
            # AOT-compile with the lower/compile phases separately timed so
            # compile cost lands in the trace as gauges (not smeared into
            # the first prefill latency); jaxpr size gauges ride along
            from repro.obs.profile import jaxpr_stats, time_compile
            with obs.span("serve.compile", stage="prefill"):
                pc = time_compile(prefill, params, cache, batch)
            obs.gauge("serve.prefill_compile_s", pc["compile_s"])
            obs.gauge("serve.prefill_lower_s", pc["lower_s"])
            obs.gauge("serve.prefill_jaxpr_eqns",
                      jaxpr_stats(prefill, params, cache, batch)["eqns"])
            registry.gauge("serve.prefill_compile_s", pc["compile_s"])
            # run through the AOT executable — lower().compile() doesn't
            # seed the jit cache, and the compile is already gauged above
            prefill = pc["compiled"]
        t0 = time.perf_counter()
        with obs.span("serve.prefill", arch=args.arch, batch=sc.batch,
                      prefill_len=sc.prefill_len):
            logits, cache = prefill(params, cache, batch)
            jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        registry.observe("serve.prefill_latency_s", t_prefill)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)
        out_toks = [tok]
        prefix = (arch_cfg.frontend_seq
                  if arch_cfg.frontend == "vision" else 0)
        if obs.enabled():
            db0 = {"tokens": tok[:, None],
                   "pos": jnp.asarray(prefix + sc.prefill_len, jnp.int32)}
            if arch_cfg.frontend == "audio":
                db0["frontend"] = batch["frontend"]
            from repro.obs.profile import jaxpr_stats
            obs.gauge("serve.decode_jaxpr_eqns", jaxpr_stats(
                decode, params, jax.eval_shape(lambda: cache), db0)["eqns"])
            with obs.span("serve.compile", stage="decode"):
                tdl = time.perf_counter()
                lowered = decode.lower(params, jax.eval_shape(lambda: cache),
                                       db0)
                tdc = time.perf_counter()
                # lower().compile() doesn't seed the jit's own cache — keep
                # the executable and decode through it, so the percentile
                # digest measures steady-state steps, not a hidden recompile
                decode = lowered.compile()
                obs.gauge("serve.decode_lower_s", tdc - tdl)
                obs.gauge("serve.decode_compile_s",
                          time.perf_counter() - tdc)
                registry.gauge("serve.decode_compile_s",
                               time.perf_counter() - tdc)
        t_decode = 0.0
        for i in range(args.decode_steps):
            db = {"tokens": tok[:, None],
                  "pos": jnp.asarray(prefix + sc.prefill_len + i, jnp.int32)}
            if arch_cfg.frontend == "audio":
                db["frontend"] = batch["frontend"]
            td = time.perf_counter()
            with obs.span("serve.decode", step=i):
                tok, cache = decode(params, cache, db)
                jax.block_until_ready(tok)
            td = time.perf_counter() - td
            t_decode += td
            registry.observe("serve.decode_latency_s", td)
            out_toks.append(tok)
        dt = time.perf_counter() - t0
        toks = jnp.stack(out_toks, axis=1)
        registry.counter("serve.requests", sc.batch)
        registry.counter("serve.tokens", int(toks.size))
        registry.gauge("serve.batch_occupancy", 1.0)  # demo: all slots live
        if t_decode > 0:
            registry.gauge("serve.decode_tokens_per_s",
                           sc.batch * args.decode_steps / t_decode)
        registry.gauge("serve.prefill_tokens_per_s",
                       sc.batch * sc.prefill_len / t_prefill)
        if (monitor is not None and not arch_cfg.frontend
                and not arch_cfg.enc_dec):
            # one sampled empirical-error check: a full-precision reference
            # pass over the same prefill, |Δlogits| in units of the
            # certified u vs δ̄ (gross under-certification detector)
            ref_cache = T.init_cache(arch_cfg, sc.batch, sc.max_seq,
                                     jnp.float32)
            ref_logits, _ = T.forward(JOps(jnp.float32, jnp.float32), params,
                                      arch_cfg, batch["tokens"],
                                      cache=ref_cache, q_offset=0)
            u = certset.error_bars().get("u")
            if u:
                err_u = float(jnp.max(jnp.abs(
                    ref_logits[:, -1:, :].astype(jnp.float64)
                    - logits.astype(jnp.float64)))) / u
                monitor.observe_error(err_u)
        responses = make_responses(toks, certset)
        log.info("served", seqs=sc.batch, decode_steps=args.decode_steps,
                 total_s=round(dt, 2), prefill_s=round(t_prefill, 3),
                 decode_s_per_tok=round(t_decode / max(args.decode_steps, 1),
                                        4),
                 sample=toks[0][:10].tolist())
        dh = registry.histograms.get("serve.decode_latency_s")
        if dh is not None and dh.count:
            pct = dh.percentiles()
            log.info("decode latency percentiles",
                     p50_ms=round(pct["p50"] * 1e3, 3),
                     p95_ms=round(pct["p95"] * 1e3, 3),
                     p99_ms=round(pct["p99"] * 1e3, 3),
                     steps=dh.count)
            for q, v in pct.items():
                registry.gauge(f"serve.decode_latency_{q}_s", v)
        if certset is not None:
            log.info("response metadata",
                     certificate=responses[0]["certificate"])
        if monitor is not None:
            monitor.export(registry)
            ms = monitor.summary()
            log.info("monitor", violations=ms["violations"],
                     observations=ms["counters"]["obs.scope_observations"],
                     worst_err_u=ms["worst_err_u"], dbar_u=ms["dbar_u"],
                     scope_margin_log2={
                         k: round(v, 2)
                         for k, v in ms["scope_margin_log2"].items()})
        if args.metrics:
            registry.write_jsonl(args.metrics)
            log.info("metrics written", path=args.metrics)
        if args.prom:
            registry.write_prometheus(args.prom)
            log.info("prometheus exposition written", path=args.prom)
        if args.trace:
            obs.shutdown()
            log.info("trace written", path=args.trace,
                     hint="render with: python -m repro.obs report "
                          + args.trace)
        return registry, monitor


def make_responses(toks, certset=None):
    """Per-sequence response dicts; with a certificate set attached, every
    response carries the certified (δ̄, ε̄, k) error bars it was served
    under — the contract the certificate pipeline exists to provide."""
    bars = None if certset is None else certset.error_bars()
    responses = []
    for i in range(toks.shape[0]):
        r = {"tokens": toks[i].tolist()}
        if bars is not None:
            r["certificate"] = dict(bars, params_digest=certset.params_digest)
        responses.append(r)
    return responses


if __name__ == "__main__":
    main()
