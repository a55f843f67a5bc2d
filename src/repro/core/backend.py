"""Arithmetic back-ends: one model definition, two executions.

The paper binds its CAA arithmetic into frugally-deep by C++ operator
overloading, so the *same network code* runs either in plain IEEE754 or in
the enhanced analysis arithmetic. We reproduce that design JAX-natively:
every model in :mod:`repro.models` is written against the ``Backend``
interface below, and

  * :class:`JOps` executes it as ordinary jnp (jit/pjit-able, any dtype
    policy — this is the training/serving path), while
  * :class:`CaaOps` executes it on :class:`repro.core.caa.CaaTensor`s,
    producing rigorous absolute/relative error bounds in units of u
    (this is the analysis path), recording a per-layer trace.

``CaaOps`` additionally implements the paper's control-flow handling for
data-dependent routing (MoE top-k): the route is fixed by the reference
values (the paper's "run for one representative per class"), and the margin
between chosen and rejected logits is recorded so routing-flip safety can be
checked against the final error bound.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from . import caa
from . import interval as iv
from .caa import CaaConfig, CaaTensor, DEFAULT_CONFIG
from .scopes import STACK_SCOPE, resolve_scope_value


@dataclasses.dataclass
class TraceRecord:
    name: str
    kind: str
    shape: tuple
    out_mag: float      # sup |exact range|
    max_dbar: float     # units of u
    max_ebar: float     # units of u
    extra: dict = dataclasses.field(default_factory=dict)


class Backend:
    """Interface models are written against. Methods mirror caa.py rules."""

    is_analysis: bool = False

    # -- scoping ------------------------------------------------------------
    # Every backend tracks the model's scope path (layer_loop pushes
    # "layer{i}", models push named blocks). CaaOps uses it for trace names
    # and sensitivity gating; serving backends use it to apply per-scope
    # precision formats (mixed-precision certificates). The default
    # additionally records every distinct path entered (``seen_scopes`` —
    # the raw material scope discovery turns into a layer→k granularity);
    # subclasses react to pushes/pops via the `_scope_changed` hook.

    @property
    def scope_path(self) -> List[str]:
        sp = getattr(self, "_scope", None)
        if sp is None:
            sp = self._scope = []
        return sp

    @property
    def seen_scopes(self) -> List[str]:
        """Every distinct scope path entered, in first-seen order."""
        ss = getattr(self, "_seen_scopes", None)
        if ss is None:
            ss = self._seen_scopes = []
            self._seen_set = set()
        return ss

    def scope(self, name: str):
        """Push ``name`` onto the scope path, and onto JAX's name stack
        (``jax.named_scope``), so every op traced inside it carries the
        scope in its compiled ``op_name`` metadata."""
        ops = self

        class _Scope:
            def __enter__(self):
                self._named = jax.named_scope(name)
                self._named.__enter__()
                ops.scope_path.append(name)
                ops._scope_changed()

            def __exit__(self, *exc):
                ops.scope_path.pop()
                ops._scope_changed()
                return self._named.__exit__(*exc)

        return _Scope()

    def _scope_changed(self):
        """Hook fired after every scope push/pop (see scope_path).

        The base implementation maintains ``seen_scopes``. Membership is
        tested against a companion set — `path not in list` is O(n) per
        push, O(n²) across a deep model's scopes, which is exactly the
        scaling a 56-layer × per-sublayer scope walk would hit."""
        if self.scope_path:
            path = "/".join(self._scope)
            seen = self.seen_scopes          # materialises the set too
            if path not in self._seen_set:
                self._seen_set.add(path)
                seen.append(path)

    # construction
    def param(self, w, exact: bool = False): raise NotImplementedError
    def input(self, x): raise NotImplementedError
    def const(self, c): raise NotImplementedError

    # arithmetic
    def add(self, a, b): raise NotImplementedError
    def sub(self, a, b): raise NotImplementedError
    def mul(self, a, b): raise NotImplementedError
    def div(self, a, b): raise NotImplementedError
    def neg(self, a): raise NotImplementedError
    def scale(self, a, c, exact_const: bool = False): raise NotImplementedError
    def shift(self, a, c): raise NotImplementedError
    def matmul(self, a, b): raise NotImplementedError
    def einsum(self, subscripts, a, b): raise NotImplementedError

    # nonlinearities
    def tanh(self, a): raise NotImplementedError
    def sigmoid(self, a): raise NotImplementedError
    def exp(self, a): raise NotImplementedError
    def log(self, a): raise NotImplementedError
    def sqrt(self, a): raise NotImplementedError
    def rsqrt(self, a): raise NotImplementedError
    def square(self, a): raise NotImplementedError
    def relu(self, a): raise NotImplementedError
    def silu(self, a): raise NotImplementedError
    def gelu(self, a): raise NotImplementedError
    def softmax(self, a, axis: int = -1): raise NotImplementedError
    def softcap(self, a, cap: float):
        """tanh soft-capping (gemma2): cap * tanh(x / cap)."""
        return self.scale(self.tanh(self.scale(a, 1.0 / cap)), cap)

    # reductions
    def sum(self, a, axis, keepdims: bool = False): raise NotImplementedError
    def mean(self, a, axis, keepdims: bool = False): raise NotImplementedError
    def max(self, a, axis, keepdims: bool = False): raise NotImplementedError

    # selection / comparison
    def maximum(self, a, b): raise NotImplementedError
    def where(self, mask, a, b): raise NotImplementedError
    def top_k_mask(self, scores, k: int, name: str = "router"):
        raise NotImplementedError

    # data movement
    def reshape(self, a, shape): raise NotImplementedError
    def transpose(self, a, axes): raise NotImplementedError
    def broadcast_to(self, a, shape): raise NotImplementedError
    def concat(self, parts, axis): raise NotImplementedError
    def take(self, a, idx, axis): raise NotImplementedError
    def slice(self, a, slices): raise NotImplementedError
    def shape_of(self, a) -> tuple: raise NotImplementedError
    def value_of(self, a) -> jax.Array: raise NotImplementedError

    # structure
    def layer_loop(self, fn: Callable, stacked_params, x, n_layers: int,
                   aux=None, first: int = 0):
        """Apply ``fn(layer_params, x, layer_index, aux) -> (x, aux)``
        across layers. Returns (x, aux).

        ``first`` numbers the stack's layers from there: a model whose
        leading layers are a stack of their own (DeepSeek's dense layer
        before its MoE layers) runs them first, then this stack as layers
        ``first .. first + n_layers - 1``. ``layer_index`` and the scope
        names count across both; ``stacked_params`` is indexed from 0.

        ``aux`` is an optional pytree of stacked ``[L, ...]`` state (the
        decode cache), handed whole from layer to layer: layer ``i`` reads
        and writes only its own slice at index ``i``, so a scanned loop
        keeps it in the carry and XLA updates it in place, with no
        per-layer copy in or out. JOps uses lax.scan over stacked
        parameters (O(1) HLO in depth — essential for 512-device compiles
        of 56-layer models); CaaOps unrolls in Python (a static ``i``) so
        per-layer trace records survive."""
        raise NotImplementedError

    def ssm_scan(self, decay, drive, n_steps: int, time_axis: int = 1):
        """h_{t+1} = decay_t ⊙ h_t + drive_t over ``time_axis``."""
        raise NotImplementedError

    def record(self, name: str, a, kind: str = "layer"):
        """Trace hook; identity for JOps."""
        return a

    def clamp_range(self, a, lo, hi):
        """Inject an externally-proven range bound (identity under JOps;
        sound enclosure intersection under CaaOps) — the paper's global-
        insight mechanism for fighting decorrelation."""
        return a

    def shard_hint(self, a, kind: str):
        """Optional sharding annotation (identity by default). Training
        backends use it for sequence-parallel attention (kind='q_seq');
        serving threads kind='act_batch' through the scanned layer body."""
        return a

    def decode_attention(self, q, k, v, lengths):
        """Fused single-token decode attention hook: q [B,K,G,D] against
        the full cache k/v [B,Smax,K,D] with per-lane valid ``lengths``
        [B]. Return the [B,K,G,D] context, or None to use the composed
        einsum/softmax path (the default). Certified serving backends
        override this with the certificate-aware flash decode kernel."""
        return None

    def grouped_matmul(self, x, w, layer, routes):
        """Routed-expert product: rows of ``x`` [R, K] come in tiles of
        ``routes.tm`` rows, tile t all routed to expert
        ``routes.tile_expert[t]``; each row times its expert's weights in
        layer ``layer`` of the whole stacked ``w`` [L, E, K, N]; rows that
        hold no pair are zero and give zero rows. This default runs
        :meth:`matmul` tile by tile (the quantised serving backends'
        arithmetic)."""
        K, N = w.shape[2:]
        w_layer = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)

        def one_tile(args):
            rows, e = args
            return self.matmul(rows, jax.lax.dynamic_index_in_dim(
                w_layer, e, 0, keepdims=False))

        out = jax.lax.map(one_tile, (x.reshape(-1, routes.tm, K),
                                     routes.tile_expert))
        return out.reshape(-1, N)


# ---------------------------------------------------------------------------
# plain-jnp execution
# ---------------------------------------------------------------------------

class JOps(Backend):
    """Straight jnp with a dtype policy — the performance path.

    ``compute_dtype`` is what activations/GEMMs run in (bf16 on TPU);
    ``param_dtype`` what parameters are stored in; accumulation is left to
    XLA (f32 on MXU via preferred_element_type).
    """

    is_analysis = False

    def __init__(self, compute_dtype=jnp.float32, accum_dtype=jnp.float32,
                 mesh=None):
        self.compute_dtype = compute_dtype
        self.accum_dtype = accum_dtype
        self.mesh = mesh  # enables shard_map paths (expert parallelism)

    def param(self, w, exact: bool = False):
        return jnp.asarray(w).astype(self.compute_dtype)

    def input(self, x):
        return jnp.asarray(x).astype(self.compute_dtype)

    def const(self, c):
        return jnp.asarray(c, self.compute_dtype)

    def add(self, a, b): return a + b
    def sub(self, a, b): return a - b
    def mul(self, a, b): return a * b
    def div(self, a, b): return a / b
    def neg(self, a): return -a

    def scale(self, a, c, exact_const: bool = False):
        return a * jnp.asarray(c, a.dtype)

    def shift(self, a, c): return a + jnp.asarray(c, a.dtype)

    # HIGHEST: on a TPU an f32 contraction at default precision runs in
    # bf16 passes; the f32 serving path must compute the f32 it states
    # (a no-op for bf16 operands and on the CPU)
    def matmul(self, a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=self.accum_dtype).astype(
            self.compute_dtype
        )

    def einsum(self, subscripts, a, b):
        return jnp.einsum(
            subscripts, a, b, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=self.accum_dtype
        ).astype(self.compute_dtype)

    def tanh(self, a): return jnp.tanh(a)
    def sigmoid(self, a): return jax.nn.sigmoid(a)
    def exp(self, a): return jnp.exp(a)
    def log(self, a): return jnp.log(a)
    def sqrt(self, a): return jnp.sqrt(a)
    def rsqrt(self, a): return jax.lax.rsqrt(a)
    def square(self, a): return a * a
    def relu(self, a): return jax.nn.relu(a)
    def silu(self, a): return jax.nn.silu(a)
    def gelu(self, a): return jax.nn.gelu(a, approximate=True)

    def softmax(self, a, axis: int = -1):
        return jax.nn.softmax(a.astype(self.accum_dtype), axis=axis).astype(
            self.compute_dtype
        )

    def sum(self, a, axis, keepdims=False): return jnp.sum(a, axis=axis, keepdims=keepdims)
    def mean(self, a, axis, keepdims=False): return jnp.mean(a, axis=axis, keepdims=keepdims)
    def max(self, a, axis, keepdims=False): return jnp.max(a, axis=axis, keepdims=keepdims)

    def maximum(self, a, b): return jnp.maximum(a, b)
    def where(self, mask, a, b): return jnp.where(mask, a, b)

    def top_k_mask(self, scores, k: int, name: str = "router"):
        _, idx = jax.lax.top_k(scores, k)
        return jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype).sum(-2)

    def reshape(self, a, shape): return jnp.reshape(a, shape)
    def transpose(self, a, axes): return jnp.transpose(a, axes)
    def broadcast_to(self, a, shape): return jnp.broadcast_to(a, shape)
    def concat(self, parts, axis): return jnp.concatenate(list(parts), axis=axis)
    def take(self, a, idx, axis): return jnp.take(a, idx, axis=axis)
    def slice(self, a, slices): return a[slices]
    def shape_of(self, a): return tuple(a.shape)
    def value_of(self, a): return a

    def shard_hint(self, a, kind: str):
        """Activation sharding constraints on the mesh (identity without
        one). kind='act_batch' pins the residual stream to batch-over-
        "data", REPLICATED over "model" — threaded through the scanned
        serving body so XLA all-gathers column-parallel matmul outputs
        (exact values) instead of propagating a contraction split (which
        would reassociate the accumulation and break the serving path's
        bit-for-bit contract)."""
        mesh = self.mesh
        if mesh is None or kind != "act_batch":
            return a
        from jax.sharding import NamedSharding, PartitionSpec as P
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if all(s <= 1 for s in sizes.values()):
            return a
        dp = tuple(ax for ax in ("pod", "data")
                   if sizes.get(ax, 1) > 1)
        rem = a.shape[0]
        for ax in dp:
            if rem % sizes[ax]:
                return a
            rem //= sizes[ax]
        spec = P(dp if dp else None, *([None] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        # one traced body serves every layer: it runs under the stacked
        # wildcard scope, which the per-scope maps key as layer*/...
        def body(carry, xs):
            h, a = carry
            p, i = xs
            h, a = fn(p, h, i + first, a)
            return (self.shard_hint(h, "act_batch"), a), None

        idx = jnp.arange(n_layers)
        with self.scope(STACK_SCOPE):
            (out, aux), _ = jax.lax.scan(body, (x, aux),
                                         (stacked_params, idx))
        return out, aux

    # dropless routed experts: the rows of each expert's tiles form one
    # group of a ragged product over that layer's [E, K, N] weights
    def grouped_matmul(self, x, w, layer, routes):
        w_layer = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
        out = jax.lax.ragged_dot(x, w_layer, routes.group_sizes,
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=self.accum_dtype)
        return out.astype(self.compute_dtype)

    def ssm_scan(self, decay, drive, n_steps: int, time_axis: int = 1):
        dec = jnp.moveaxis(decay, time_axis, 0)
        drv = jnp.moveaxis(drive, time_axis, 0)

        def body(h, xs):
            d, b = xs
            h = d * h + b
            return h, h

        h0 = jnp.zeros_like(drv[0])
        _, hs = jax.lax.scan(body, h0, (dec, drv))
        return jnp.moveaxis(hs, 0, time_axis)


# ---------------------------------------------------------------------------
# CAA analysis execution
# ---------------------------------------------------------------------------

class UnrolledLayerLoop:
    """Mixin: the eager per-layer ``layer_loop`` — a Python unroll pushing
    a static ``layer{i}`` scope per layer, so every per-scope knob
    resolves eagerly by name. This single implementation is both the
    analysis-side unroll (CaaOps and its string-scope subclasses) and the
    serving-side differential baseline (compose in front of a scanned
    backend: ``class Ref(UnrolledLayerLoop, MixedQuantJOps)``) — the two
    must never diverge, since certificates are confirmed on the former and
    bit-for-bit checked against the latter."""

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        for i in range(n_layers):
            layer_params = jax.tree_util.tree_map(lambda p: p[i], stacked_params)
            with self.scope(f"layer{first + i}"):
                x, aux = fn(layer_params, x, first + i, aux)
        return x, aux


class CaaOps(UnrolledLayerLoop, Backend):
    """Executes the model on CaaTensors, recording a per-layer trace.

    weights_exact: treat parameters as exactly representable in the target
      format (paper's default: the stored weights *are* the reference) —
      set False to additionally charge the f32→target re-quantisation
      (ε̄ = 1/2 per weight).
    """

    is_analysis = True

    def __init__(self, cfg: CaaConfig = DEFAULT_CONFIG, weights_exact: bool = True):
        self.cfg = cfg
        self.weights_exact = weights_exact
        self.trace: List[TraceRecord] = []
        self._scope: List[str] = []
        # seen_scopes bookkeeping (first-seen order + dedup set) lives on
        # Backend._scope_changed, shared with the serving backends.

    # -- scoping / tracing --
    def _name(self, leaf: str) -> str:
        return "/".join(self._scope + [leaf]) if self._scope else leaf

    @staticmethod
    def _f(x) -> float:
        """Concretise for the trace; NaN placeholder under tracing (scan)."""
        try:
            return float(x)
        except (jax.errors.TracerArrayConversionError, jax.errors.ConcretizationTypeError):
            return float("nan")

    def record(self, name: str, a: CaaTensor, kind: str = "layer", **extra):
        self.trace.append(
            TraceRecord(
                name=self._name(name),
                kind=kind,
                shape=tuple(a.shape),
                out_mag=self._f(jnp.max(iv.mag(a.exact))),
                max_dbar=self._f(jnp.max(a.dbar)),
                max_ebar=self._f(jnp.max(a.ebar)),
                extra=extra,
            )
        )
        return a

    # -- construction --
    def param(self, w, exact: Optional[bool] = None):
        exact = self.weights_exact if exact is None else exact
        return caa.weight(w, self.cfg, exact=exact)

    def input(self, x):
        if isinstance(x, CaaTensor):
            return x
        return caa.make(x)

    def const(self, c):
        return caa.const_exact(c)

    # -- arithmetic --
    def add(self, a, b): return caa.add(a, b, self.cfg)
    def sub(self, a, b): return caa.sub(a, b, self.cfg)
    def mul(self, a, b): return caa.mul(a, b, self.cfg)
    def div(self, a, b): return caa.div(a, b, self.cfg)
    def neg(self, a): return caa.neg(a)

    def scale(self, a, c, exact_const: bool = False):
        return caa.scale_const(a, c, exact_const=exact_const, cfg=self.cfg)

    def shift(self, a, c): return caa.shift_const(a, c, self.cfg)
    def matmul(self, a, b): return caa.matmul(a, b, self.cfg)
    def einsum(self, subscripts, a, b): return caa.einsum(subscripts, a, b, self.cfg)

    def tanh(self, a): return caa.tanh(a, self.cfg)
    def sigmoid(self, a): return caa.sigmoid(a, self.cfg)
    def exp(self, a): return caa.exp(a, self.cfg)
    def log(self, a): return caa.log(a, self.cfg)
    def sqrt(self, a): return caa.sqrt(a, self.cfg)
    def rsqrt(self, a): return caa.rsqrt(a, self.cfg)
    def square(self, a): return caa.square(a, self.cfg)
    def relu(self, a): return caa.relu(a, self.cfg)
    def silu(self, a): return caa.silu(a, self.cfg)
    def gelu(self, a): return caa.gelu(a, self.cfg)
    def softmax(self, a, axis: int = -1): return caa.softmax(a, axis, self.cfg)

    def sum(self, a, axis, keepdims=False): return caa.reduce_sum(a, axis, keepdims, self.cfg)
    def mean(self, a, axis, keepdims=False): return caa.reduce_mean(a, axis, keepdims, self.cfg)
    def max(self, a, axis, keepdims=False): return caa.reduce_max(a, axis, keepdims, self.cfg)

    def maximum(self, a, b): return caa.maximum(a, b, self.cfg)
    def where(self, mask, a, b): return caa.where(mask, a, b)

    def top_k_mask(self, scores: CaaTensor, k: int, name: str = "router"):
        """Fix the route from reference values; record the decision margin.

        The route is safe against rounding iff the gap between the k-th
        chosen and the best rejected logit exceeds twice the logit error
        (in value terms) — recorded for the report (the paper's argmax
        analysis, applied to routing)."""
        vals, idx = jax.lax.top_k(scores.val, k)
        mask = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.val.dtype).sum(-2)
        rejected = jnp.where(mask > 0, -jnp.inf, scores.val)
        margin = jnp.min(vals, -1) - jnp.max(rejected, -1)
        # per-run certified error (finite even when the parametric bound
        # saturates): sup distance from the emulated value to the ideal range
        dist = jnp.maximum(jnp.abs(scores.val - scores.exact.lo),
                           jnp.abs(scores.val - scores.exact.hi))
        err_val = jnp.minimum(
            jnp.max(caa._eff_dbar(scores)) * self.cfg.u_max, jnp.max(dist))
        # _f: concretise for the trace, NaN placeholder under tracing — MoE
        # routing inside a scan-native layer stack traces this path
        self.trace.append(
            TraceRecord(
                name=self._name(name),
                kind="router",
                shape=tuple(scores.shape),
                out_mag=self._f(jnp.max(iv.mag(scores.exact))),
                max_dbar=self._f(jnp.max(scores.dbar)),
                max_ebar=self._f(jnp.max(scores.ebar)),
                extra={
                    "min_margin": self._f(jnp.min(margin)),
                    "flip_safe_if_u_le": self._f(
                        jnp.min(margin) / (2 * err_val + 1e-300)),
                },
            )
        )
        return mask

    def reshape(self, a, shape): return caa.reshape(a, shape)
    def transpose(self, a, axes): return caa.transpose(a, axes)
    def broadcast_to(self, a, shape): return caa.broadcast_to(a, shape)
    def concat(self, parts, axis): return caa.concatenate(list(parts), axis)
    def take(self, a, idx, axis): return caa.take(a, idx, axis)
    def slice(self, a, slices): return caa.slice_(a, slices)
    def shape_of(self, a): return tuple(a.shape)
    def value_of(self, a): return a.val

    def clamp_range(self, a, lo, hi):
        return caa.clamp_exact(a, lo, hi)

    # layer_loop: the eager per-layer unroll from UnrolledLayerLoop —
    # per-layer trace records and string-scope knob gating survive.

    def ssm_scan(self, decay: CaaTensor, drive: CaaTensor, n_steps: int,
                 time_axis: int = 1):
        """Closed-form fixpoint bound (caa.scan_affine_fixpoint) broadcast
        back over time — sound for every step since bounds are monotone in t."""
        dec_w = caa.reduce_max(caa.CaaTensor(
            jnp.abs(decay.val), iv.abs_(decay.exact), decay.dbar, decay.ebar
        ), axis=time_axis, keepdims=True)
        drv_w = caa.CaaTensor(
            drive.val,
            iv.Interval(
                jnp.min(drive.exact.lo, axis=time_axis, keepdims=True),
                jnp.max(drive.exact.hi, axis=time_axis, keepdims=True),
            ),
            jnp.max(jnp.broadcast_to(drive.dbar, drive.shape), axis=time_axis, keepdims=True),
            jnp.max(jnp.broadcast_to(drive.ebar, drive.shape), axis=time_axis, keepdims=True),
        )
        fix = caa.scan_affine_fixpoint(
            caa.CaaTensor(dec_w.val, dec_w.exact, dec_w.dbar, dec_w.ebar),
            caa.CaaTensor(jnp.mean(drive.val, axis=time_axis, keepdims=True),
                          drv_w.exact, drv_w.dbar, drv_w.ebar),
            n_steps, self.cfg,
        )
        # reference values still come from the true scan for val fidelity
        jb = JOps(jnp.float64, jnp.float64)
        vals = jb.ssm_scan(decay.val, drive.val, n_steps, time_axis)
        return caa.CaaTensor(
            vals,
            iv.Interval(jnp.broadcast_to(fix.exact.lo, vals.shape),
                        jnp.broadcast_to(fix.exact.hi, vals.shape)),
            jnp.broadcast_to(fix.dbar, vals.shape),
            jnp.broadcast_to(fix.ebar, vals.shape),
        )


# ---------------------------------------------------------------------------
# per-scope IA magnitude enclosures — the range analysis behind custom
# (k, emin, emax) format certification (repro.certify.formats)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RangeStat:
    """Magnitude enclosure of every FP value a scope produces.

    ``max_abs`` is a rigorous upper bound on |v̂| over every intermediate
    (IA range inflated by the value's own FP error at u_max) — the quantity
    the smallest overflow-free ``emax`` is certified from. ``min_nonzero``
    is the smallest positive element-wise mignitude seen (+inf if none):
    when it clears the format's ``min_normal``, no *provably-nonzero* value
    can go subnormal. ``crosses_zero`` records whether some enclosure
    touches 0 — those values may underflow, which is exactly what the
    λ·2^{emin-(k-1)} absolute term (CaaConfig.round_abs) charges for.
    """

    max_abs: float = 0.0
    min_nonzero: float = math.inf
    crosses_zero: bool = False
    n_ops: int = 0

    def merge(self, other: "RangeStat") -> "RangeStat":
        return RangeStat(
            max_abs=max(self.max_abs, other.max_abs),
            min_nonzero=min(self.min_nonzero, other.min_nonzero),
            crosses_zero=self.crosses_zero or other.crosses_zero,
            n_ops=self.n_ops + other.n_ops,
        )

    def to_dict(self) -> dict:
        return {"max_abs": self.max_abs, "min_nonzero": self.min_nonzero,
                "crosses_zero": self.crosses_zero, "n_ops": self.n_ops}


class RangeCaaOps(CaaOps):
    """CaaOps that additionally accumulates per-scope magnitude enclosures.

    Every op result (and every param/input/const — weights must be
    representable in a scope's format too) updates ``scope_ranges`` at the
    current scope path. The accumulated bounds are concretised floats, so
    this backend is eager-only (under jit the observations would be
    tracers); the format pipeline runs it exactly where PR 1/2 already run
    eager confirmation passes. Observation is side-effect-only — the
    returned tensors are bit-identical to the parent class's, and method
    dispatch goes through ``super()`` so the mixin composes with subclasses
    that redefine scope behaviour (e.g. FormatCaaOps).
    """

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.scope_ranges: Dict[str, RangeStat] = {}

    def _observe(self, out, is_op: bool = True):
        if not isinstance(out, CaaTensor):
            return out
        rng = out.fp_range(self.cfg.u_max)
        lo = jnp.broadcast_to(rng.lo, out.shape)
        hi = jnp.broadcast_to(rng.hi, out.shape)
        import numpy as np
        lo = np.asarray(lo, np.float64).ravel()
        hi = np.asarray(hi, np.float64).ravel()
        mag = np.maximum(np.abs(lo), np.abs(hi))
        mig = np.maximum(np.maximum(lo, -hi), 0.0)
        pos = mig[mig > 0]
        stat = RangeStat(
            max_abs=float(mag.max(initial=0.0)),
            min_nonzero=float(pos.min()) if pos.size else math.inf,
            crosses_zero=bool((mig <= 0).any()),
            n_ops=1 if is_op else 0,
        )
        key = "/".join(self._scope) if self._scope else ""
        prev = self.scope_ranges.get(key)
        self.scope_ranges[key] = stat if prev is None else prev.merge(stat)
        return out


_RANGE_TRACKED_OPS = (
    "param", "input", "const", "add", "sub", "mul", "div", "neg", "scale",
    "shift", "matmul", "einsum", "tanh", "sigmoid", "exp", "log", "sqrt",
    "rsqrt", "square", "relu", "silu", "gelu", "softmax", "sum", "mean",
    "max", "maximum", "where", "concat", "clamp_range", "ssm_scan",
)


def _make_range_wrapper(cls, name: str):
    def method(self, *args, **kwargs):
        out = getattr(super(cls, self), name)(*args, **kwargs)
        # operands cross scope boundaries: a matmul in scope s quantises
        # values produced elsewhere INTO s's format, so every consumed
        # tensor belongs to s's enclosure too (n_ops counts outputs only)
        for a in args:
            if isinstance(a, CaaTensor):
                self._observe(a, is_op=False)
        self._observe(out)
        return out
    method.__name__ = name
    method.__qualname__ = f"{cls.__name__}.{name}"
    return method


def _install_range_wrappers(cls):
    """Wrap every value-producing op of ``cls`` with the `_observe` hook
    (dispatch goes through super(cls), so observation composes with any
    scope/knob behaviour of the base class)."""
    for name in _RANGE_TRACKED_OPS:
        setattr(cls, name, _make_range_wrapper(cls, name))
    return cls


_install_range_wrappers(RangeCaaOps)


# ---------------------------------------------------------------------------
# scan-native (layer-stacked) analysis — one traced body for all L layers
# ---------------------------------------------------------------------------

def _canon_caa(c: CaaTensor) -> CaaTensor:
    """Broadcast every field to val's shape: a lax.scan carry must keep one
    fixed aval across iterations, but CAA rules freely return scalar-
    broadcast dbar/ebar."""
    shape = jnp.shape(c.val)
    b = lambda t: jnp.broadcast_to(jnp.asarray(t, jnp.float64), shape)
    return CaaTensor(c.val, iv.Interval(b(c.exact.lo), b(c.exact.hi)),
                     b(c.dbar), b(c.ebar))


class StackedCaaOps(CaaOps):
    """Scan-native CAA: ``layer_loop`` runs as ONE ``lax.scan`` over the
    stacked parameters — O(1) HLO in depth, the analysis twin of the JOps
    serving path — instead of CaaOps' per-layer Python unroll.

    Scope-dependent knobs become **traced per-layer lanes**: at loop entry
    each layer's ``round_scale``/``round_abs`` is resolved by name against
    ``scope_scales``/``scope_abs`` (static strings, possibly traced values
    — e.g. a probe ladder's scale vector), stacked into ``[L]`` vectors,
    and gathered by the scan carry's layer index inside the one traced
    body. Outside the stack the knobs resolve statically from the scope
    path, exactly like :class:`repro.certify.formats.FormatCaaOps`. With
    empty maps and unit defaults this is the uniform analysis (bounds agree
    with the eager unroll to fp tolerance; the eager path remains the
    reference the pipelines re-confirm against).

    Costs of the scan form: per-layer TraceRecords collapse into one
    ``layer*/...`` record with NaN concretisations, and ``seen_scopes``
    reports the :data:`repro.core.scopes.STACK_SCOPE` wildcard instead of
    concrete layer names (expand with :func:`repro.core.scopes.
    expand_stacked`). Per-layer (δ̄, ε̄) of the carry after every layer is
    emitted as the ``layer_stats`` ``[L]`` arrays instead.
    """

    def __init__(self, cfg: CaaConfig = DEFAULT_CONFIG,
                 scope_scales: Optional[Dict[str, Any]] = None,
                 scope_abs: Optional[Dict[str, Any]] = None,
                 default_scale=1.0, default_abs=None,
                 weights_exact: bool = True):
        self._scales = dict(scope_scales or {})
        self._abs = dict(scope_abs or {})
        self._default_scale = default_scale
        self._default_abs = cfg.round_abs if default_abs is None else default_abs
        self._base_cfg = cfg
        self._in_stack = False
        self._layer_index = None
        self._stack_ctx = None      # (outer_path, n_layers) while scanning
        self._lane_cache: Dict[tuple, tuple] = {}
        self.layer_stats: Optional[Dict[str, jax.Array]] = None
        super().__init__(cfg, weights_exact=weights_exact)
        self._apply_static()

    # -- knob resolution ----------------------------------------------------
    def _apply_static(self):
        s = resolve_scope_value(self._scope, self._scales,
                                self._default_scale)
        ra = resolve_scope_value(self._scope, self._abs, self._default_abs)
        self.cfg = dataclasses.replace(
            self._base_cfg,
            round_scale=self._base_cfg.round_scale * s,
            round_abs=ra)

    def _scope_changed(self):
        super()._scope_changed()
        if not self._in_stack:
            self._apply_static()
        elif self._stack_ctx is not None:
            # inside the one traced body the knobs follow the sub-layer
            # suffix (layer*/attn, layer*/mlp, ...): each distinct suffix
            # gets its own [L] lane, resolved by name exactly like the
            # per-layer lane and gathered at the traced layer index. With
            # no sub-layer keys in the maps every suffix lane equals the
            # per-layer lane, so behaviour is unchanged.
            self._apply_stack_lane()

    def _stack_suffix(self) -> tuple:
        """Scope segments below the stack wildcard (static strings)."""
        outer, _ = self._stack_ctx
        return tuple(self._scope[len(outer) + 1:])

    def _stack_lanes(self, suffix: tuple):
        """[L] knob lanes for one sub-layer suffix, cached per suffix (the
        cache lives on the ops instance, which jit retracing recreates)."""
        cached = self._lane_cache.get(suffix)
        if cached is None:
            outer, n_layers = self._stack_ctx

            def vec(mapping, default):
                vals = [resolve_scope_value(
                    outer + [f"layer{i}", *suffix], mapping, default)
                    for i in range(n_layers)]
                if any(isinstance(v, jax.core.Tracer) for v in vals):
                    return jnp.stack(
                        [jnp.asarray(v, jnp.float64) for v in vals])
                import numpy as np
                return jnp.asarray(np.asarray(vals, np.float64))

            cached = (vec(self._scales, self._default_scale),
                      vec(self._abs, self._default_abs))
            self._lane_cache[suffix] = cached
        return cached

    def _apply_stack_lane(self):
        scale_vec, abs_vec = self._stack_lanes(self._stack_suffix())
        i = self._layer_index
        base = self._base_cfg
        self.cfg = dataclasses.replace(
            base,
            round_scale=base.round_scale * scale_vec[i],
            round_abs=abs_vec[i])

    # -- scan-state hooks (range subclass threads accumulators) -------------
    def _stack_state_init(self, n_layers: int):
        return None

    def _set_stack_state(self, state):
        pass

    def _get_stack_state(self):
        return None

    def _finish_stack_state(self, state):
        pass

    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        if first:
            raise NotImplementedError(
                "the scan-form analyses cover one homogeneous layer stack; "
                "analyse a model with leading layers on the eager unroll")
        if self._in_stack:
            # nested stacks are out of scope for the scan form — fall back
            # to the eager unroll for the inner loop
            return super().layer_loop(fn, stacked_params, x, n_layers, aux)
        outer = list(self._scope)
        self._stack_ctx = (outer, n_layers)
        self._lane_cache = {}

        def body(carry, xs):
            p, i = xs
            cx, state, a = carry
            self._in_stack = True
            self._layer_index = i
            self._set_stack_state(state)
            # per-layer knob lane (suffix ()), resolved by name exactly like
            # the scanned serving backends build their i32 k/format arrays;
            # sub-layer scope pushes inside fn re-pin to their suffix lane
            # via _scope_changed → _apply_stack_lane
            self._apply_stack_lane()
            new_x, a = fn(p, cx, i, a)
            new_x = _canon_caa(new_x)
            stats = (jnp.max(new_x.dbar), jnp.max(new_x.ebar))
            return (new_x, self._get_stack_state(), a), stats

        idx = jnp.arange(n_layers)
        with self.scope(STACK_SCOPE):
            (out, state, aux), stats = jax.lax.scan(
                body, (_canon_caa(x), self._stack_state_init(n_layers), aux),
                (stacked_params, idx))
            self._in_stack = False
            self._layer_index = None
            self._stack_ctx = None
            self._finish_stack_state(state)
        self.layer_stats = {"abs_u": stats[0], "rel_u": stats[1]}
        return out, aux


class StackedRangeCaaOps(StackedCaaOps):
    """Scan-native range analysis: per-scope IA magnitude enclosures as
    ``[L, 4]`` lanes — (max_abs, min_nonzero, crosses_zero, n_ops) —
    accumulated via ``.at[i]`` updates on the scan carry, one lane per
    layer plus one scalar lane for every op outside the stack. Unlike
    :class:`RangeCaaOps` the observations are traced jnp (they live inside
    the one compiled scan body); :meth:`collect_ranges` concretises them to
    the same ``{scope_key: RangeStat}`` shape the eager path produces."""

    _ACC_INIT = (0.0, math.inf, 0.0, 0.0)

    def __init__(self, *args, sublanes: Sequence[str] = (), **kwargs):
        # sublanes: sub-layer scope names (e.g. ("attn", "mlp")) that get
        # their own accumulator lane inside the stack; everything else in a
        # layer lands on lane 0 (the layer-direct lane). With the default
        # () the lanes collapse to the original per-layer shape.
        self._sublanes = tuple(sublanes)
        self._sub_map = {s: j + 1 for j, s in enumerate(self._sublanes)}
        self._outer_accs = None
        self._lane_acc = None
        self._done_lanes: List = []
        super().__init__(*args, **kwargs)
        # outside the stack the scope path is a concrete Python string, so
        # per-path accumulators keep the eager path's key fidelity there
        self._outer_accs: Dict[str, jax.Array] = {}

    def _sub_idx(self) -> int:
        """Static accumulator-lane index of the current sub-layer scope."""
        if self._stack_ctx is None or not self._sub_map:
            return 0
        suffix = self._stack_suffix()
        if suffix:
            return self._sub_map.get(suffix[0], 0)
        return 0

    @staticmethod
    def _merge_acc(acc, stat):
        return jnp.stack([
            jnp.maximum(acc[..., 0], stat[0]),
            jnp.minimum(acc[..., 1], stat[1]),
            jnp.maximum(acc[..., 2], stat[2]),
            acc[..., 3] + stat[3],
        ], axis=-1)

    def _observe(self, out, is_op: bool = True):
        if not isinstance(out, CaaTensor) or self._outer_accs is None:
            return out
        rng = out.fp_range(self.cfg.u_max)
        lo = jnp.broadcast_to(rng.lo, out.shape).ravel()
        hi = jnp.broadcast_to(rng.hi, out.shape).ravel()
        mag = jnp.max(jnp.maximum(jnp.abs(lo), jnp.abs(hi)))
        mig = jnp.maximum(jnp.maximum(lo, -hi), 0.0)
        min_nz = jnp.min(jnp.where(mig > 0, mig, jnp.inf))
        crossed = jnp.any(mig <= 0).astype(jnp.float64)
        stat = (mag, min_nz, crossed,
                jnp.asarray(1.0 if is_op else 0.0, jnp.float64))
        if self._in_stack and self._lane_acc is not None:
            i = self._layer_index
            j = self._sub_idx()
            self._lane_acc = self._lane_acc.at[i, j].set(
                self._merge_acc(self._lane_acc[i, j], stat))
        else:
            key = "/".join(self._scope) if self._scope else ""
            prev = self._outer_accs.get(
                key, jnp.asarray(self._ACC_INIT, jnp.float64))
            self._outer_accs[key] = self._merge_acc(prev, stat)
        return out

    # scan-state plumbing: the [L, S, 4] lanes ride the carry (S = 1 layer-
    # direct lane + one lane per tracked sub-layer scope)
    def _stack_state_init(self, n_layers: int):
        return jnp.broadcast_to(
            jnp.asarray(self._ACC_INIT, jnp.float64),
            (n_layers, 1 + len(self._sublanes), 4))

    def _set_stack_state(self, state):
        self._lane_acc = state

    def _get_stack_state(self):
        return self._lane_acc

    def _finish_stack_state(self, state):
        self._done_lanes.append(state)
        self._lane_acc = None

    def collect_ranges(self) -> Dict[str, RangeStat]:
        """Concretise the lanes: {"layer{i}": RangeStat} per stack lane,
        outside-the-stack paths keyed by their concrete scope string (plus
        ``""`` for unscoped ops) — the same key shape the eager
        :class:`RangeCaaOps` + aggregate_ranges path produces. Stacks from
        repeated layer_loops (e.g. encoder + decoder) merge by layer
        name, matching the eager string-scope aggregation."""
        import numpy as np

        def stat(row) -> RangeStat:
            return RangeStat(
                max_abs=float(row[0]), min_nonzero=float(row[1]),
                crosses_zero=bool(row[2] > 0), n_ops=int(row[3]))

        out: Dict[str, RangeStat] = {}
        for lanes in self._done_lanes:
            arr = np.asarray(lanes, np.float64)
            for i in range(arr.shape[0]):
                for j in range(arr.shape[1]):
                    key = (f"layer{i}" if j == 0
                           else f"layer{i}/{self._sublanes[j - 1]}")
                    s = stat(arr[i, j])
                    if (j > 0 and s.n_ops == 0 and s.max_abs == 0.0
                            and s.min_nonzero == math.inf):
                        continue  # sub-lane never entered

                    out[key] = s if key not in out else out[key].merge(s)
        for key, acc in self._outer_accs.items():
            # the stack wildcard path holds ops observed between scope entry
            # and the scan (none today) — fold it into the default
            key = "" if key.startswith(STACK_SCOPE) else key
            s = stat(np.asarray(acc, np.float64))
            out[key] = s if key not in out else out[key].merge(s)
        out.setdefault("", RangeStat())
        return out


_install_range_wrappers(StackedRangeCaaOps)


# ---------------------------------------------------------------------------
# affine-arithmetic range analysis — finite enclosures where IA saturates
# ---------------------------------------------------------------------------
#
# The IA range pass bounds |v̂| through the CAA error terms: at coarse
# emulated precision the parametric accumulation bounds (CaaConfig.gamma)
# saturate to ∞ and every enclosure downstream is ∞ — which is exactly why
# certify_lm's mixed-mantissa format attempt dies on attention archs. The
# affine pass sidesteps the error terms entirely: it FORWARD-PROPAGATES an
# enclosure of the rounded values themselves, through TWO channels per
# tensor (:class:`AffTensor`):
#
#   * an affine form (interval.AffineForm) — center + noise-symbol terms —
#     that survives elementwise linear ops exactly, so correlated paths
#     (residual adds, gating products) cancel instead of compounding;
#   * a plain interval, advanced by direct outward-rounded interval rules
#     with an operational rounding inflation (1+u/2)^n — this channel keeps
#     the sign/structure facts a symmetric form cannot represent (x² ≥ 0,
#     softmax ∈ [0,1], clamp bounds), so norm denominators never swallow 0.
#
# The enclosure of a tensor is the channels' intersection; both are sound
# for the same rounded-value set. Every rounding charge is the operational
# growth model (1+u/2)^n − 1 plus n·η — finite at EVERY precision, never a
# γ-style closed form whose denominator crosses zero at coarse u (that
# saturation is the bug this pass exists to fix). The pass proves nothing
# about (δ̄, ε̄); it exists solely to tighten RangeStat range evidence, and
# is sound to min-combine with the IA pass.

class AffTensor:
    """Two-channel rounded-value enclosure for the affine range pass.

    Exposes the CaaTensor surface the models (and caa's shape ops) touch
    under ``is_analysis``: ``val`` is the f64 reference value (the form's
    center), ``exact`` the channel intersection — an enclosure of the
    ROUNDED values; unlike CaaTensor, whose ``exact`` holds ideal values
    and whose FP deviation lives in (dbar, ebar), here the deviation is
    inside the enclosure and the error channels read zero."""

    __slots__ = ("form", "ivl")

    def __init__(self, form: iv.AffineForm, ivl: Optional[iv.Interval] = None):
        self.form = form
        self.ivl = iv.aff_interval(form) if ivl is None else ivl

    @property
    def val(self) -> jax.Array:
        return self.form.center

    @property
    def exact(self) -> iv.Interval:
        a = iv.aff_interval(self.form)
        shape = self.form.shape
        lo = jnp.maximum(jnp.broadcast_to(a.lo, shape),
                         jnp.broadcast_to(self.ivl.lo, shape))
        hi = jnp.minimum(jnp.broadcast_to(a.hi, shape),
                         jnp.broadcast_to(self.ivl.hi, shape))
        return iv.Interval(lo, hi)

    @property
    def dbar(self) -> jax.Array:
        return jnp.zeros(self.form.shape, jnp.float64)

    ebar = dbar

    @property
    def shape(self) -> tuple:
        return tuple(self.form.shape)

    @property
    def ndim(self) -> int:
        return len(self.form.shape)


def _aff_struct(f: iv.AffineForm, fn) -> iv.AffineForm:
    """Apply a shape-only op: fn(arr, is_terms) on center/rad and the
    axis-shifted terms."""
    return iv.AffineForm(fn(f.center, False), fn(f.terms, True), f.ids,
                         fn(f.rad, False))


class AffineRangeCaaOps(UnrolledLayerLoop, Backend):
    """Eager affine range pass over per-scope FP formats.

    ``scope_fmts[s]`` is the :class:`repro.core.formats.FpFormat` scope
    ``s`` runs in (resolved with the scopes matcher — ``layer3``,
    ``layer*``, ``layer*/attn`` keys all work); each op charges roundings
    of half-width ``(u_s/2)·|v| + η_s`` at the scope it executes in.
    Observations land in ``scope_ranges`` exactly like
    :class:`RangeCaaOps` (operands observed into the consuming scope,
    enclosures inflated by one re-quantisation into that scope's format),
    so :func:`repro.core.analyze.aggregate_ranges` and the synthesizer
    consume either pass interchangeably."""

    is_analysis = True

    def __init__(self, scope_fmts: Dict[str, Any], default_fmt,
                 budget: int = iv.AFF_DEFAULT_BUDGET,
                 weights_exact: bool = True,
                 condense_rank: str = iv.AFF_DEFAULT_RANK):
        self._fmts = dict(scope_fmts or {})
        self._default_fmt = default_fmt
        self.budget = int(budget)
        self.condense_rank = str(condense_rank)
        self.weights_exact = weights_exact
        self._scope: List[str] = []
        self._knobs: Dict[tuple, tuple] = {}
        self._sym_counter = 1  # 0 marks the empty slot
        self.scope_ranges: Dict[str, RangeStat] = {}

    # -- knobs / symbols -----------------------------------------------------
    def _hu_eta(self):
        """(u_s/2, η_s) of the current scope's format."""
        key = tuple(self._scope)
        got = self._knobs.get(key)
        if got is None:
            fmt = resolve_scope_value(self._scope, self._fmts,
                                      self._default_fmt)
            got = (0.5 * fmt.u, fmt.underflow_unit)
            self._knobs[key] = got
        return got

    def _next_id(self):
        i = self._sym_counter
        self._sym_counter = i + 1
        return i

    # -- lift / rounding charges / observe -----------------------------------
    def _lift(self, x, observe: bool = True) -> AffTensor:
        if isinstance(x, AffTensor):
            t = x
        elif isinstance(x, CaaTensor):
            # a CaaTensor reaching this backend carries exact reference
            # values (inputs built by caa.make) — enclose its fp range at
            # the coarsest unit it may run under (u = 2·hu of this scope)
            hu, _ = self._hu_eta()
            rng = x.fp_range(2.0 * hu)
            form = iv.aff_from_interval(
                rng, self.budget, center=jnp.asarray(x.val, jnp.float64))
            t = AffTensor(form, rng)
        else:
            t = AffTensor(iv.aff_make(x, self.budget))
        if observe:
            self._observe(t, is_op=False)
        return t

    def _round_iv(self, I: iv.Interval, rounds) -> iv.Interval:
        """Widen an ideal-result enclosure by ``rounds`` elementary
        roundings at this scope's format: relative growth (1+u/2)^n − 1
        (plus our own f64 slop) and n·η absolute — the operational model,
        finite at every precision."""
        hu, eta = self._hu_eta()
        grow = (jnp.power(1.0 + hu, float(rounds))
                * (1.0 + 8.0 * iv._gamma_f64(8)) - 1.0)
        add = float(rounds) * eta * (1.0 + grow)
        lo = iv._down(I.lo - (grow * jnp.abs(I.lo) + add))
        hi = iv._up(I.hi + (grow * jnp.abs(I.hi) + add))
        # rounding is monotone with rd(0) = 0: a provably-nonnegative
        # quantity stays nonnegative under FP evaluation (likewise ≤ 0), so
        # the η slop must not push an enclosure across zero — that spurious
        # crossing is what lets mean(x²)+eps reach rsqrt with lo < 0
        lo = jnp.where(I.lo >= 0.0, jnp.maximum(lo, 0.0), lo)
        hi = jnp.where(I.hi <= 0.0, jnp.minimum(hi, 0.0), hi)
        bad = jnp.isnan(lo) | jnp.isnan(hi)
        return iv.Interval(jnp.where(bad, -_AFF_INF, lo),
                           jnp.where(bad, _AFF_INF, hi))

    def _sym(self, f: iv.AffineForm, rounds) -> iv.AffineForm:
        """Charge ``rounds`` output roundings on the form channel as one
        fresh per-element noise symbol."""
        hu, eta = self._hu_eta()
        coeff = float(rounds) * (hu * (jnp.abs(f.center) + iv.aff_tot(f))
                                 + eta)
        return iv.aff_append_symbol(f, coeff, self._next_id(), self.budget,
                                    self.condense_rank)

    def _refit(self, I: iv.Interval, center) -> iv.AffineForm:
        """Terms-free form recentred on the reference value (nonlinear ops
        and contractions drop their symbols; the interval channel carries
        the asymmetric part the form cannot)."""
        c = jnp.asarray(center, jnp.float64)
        return iv.aff_from_interval(I, self.budget,
                                    center=jnp.where(jnp.isfinite(c), c, 0.0))

    def _out(self, f: iv.AffineForm, I: iv.Interval,
             is_op: bool = True) -> AffTensor:
        t = AffTensor(f, I)
        self._observe(t, is_op=is_op)
        return t

    def _requant_interval(self, t: AffTensor) -> iv.Interval:
        """Channel intersection inflated by one re-quantisation into this
        scope's format — the envelope a value must fit when scope s
        consumes or produces it ((1 ± u/2)·v ± η)."""
        return self._round_iv(t.exact, 1)

    def _observe(self, t: AffTensor, is_op: bool):
        import numpy as np
        ivl = self._requant_interval(t)
        lo = np.asarray(jnp.broadcast_to(ivl.lo, t.shape),
                        np.float64).ravel()
        hi = np.asarray(jnp.broadcast_to(ivl.hi, t.shape),
                        np.float64).ravel()
        mag = np.maximum(np.abs(lo), np.abs(hi))
        mig = np.maximum(np.maximum(lo, -hi), 0.0)
        pos = mig[mig > 0]
        stat = RangeStat(
            max_abs=float(mag.max(initial=0.0)),
            min_nonzero=float(pos.min()) if pos.size else math.inf,
            crosses_zero=bool((mig <= 0).any()),
            n_ops=1 if is_op else 0,
        )
        key = "/".join(self._scope) if self._scope else ""
        prev = self.scope_ranges.get(key)
        self.scope_ranges[key] = stat if prev is None else prev.merge(stat)

    # -- construction --------------------------------------------------------
    def param(self, w, exact: Optional[bool] = None):
        exact = self.weights_exact if exact is None else exact
        f = iv.aff_make(w, self.budget)
        if not exact:
            f = self._sym(f, 1)
        return self._out(f, iv.aff_interval(f))

    def input(self, x):
        if isinstance(x, AffTensor):
            self._observe(x, is_op=False)
            return x
        t = self._lift(x, observe=False)
        self._observe(t, is_op=True)
        return t

    def const(self, c):
        f = iv.aff_make(c, self.budget)
        return self._out(f, iv.aff_interval(f))

    # -- elementwise arithmetic (form terms survive — correlations cancel) --
    def add(self, a, b):
        A, B = self._lift(a), self._lift(b)
        f = self._sym(iv.aff_add(A.form, B.form, self.budget,
                                 self.condense_rank), 1)
        I = self._round_iv(iv.add(A.exact, B.exact), 1)
        return self._out(f, I)

    def sub(self, a, b):
        A, B = self._lift(a), self._lift(b)
        f = self._sym(iv.aff_sub(A.form, B.form, self.budget,
                                 self.condense_rank), 1)
        I = self._round_iv(iv.sub(A.exact, B.exact), 1)
        return self._out(f, I)

    def mul(self, a, b):
        A, B = self._lift(a), self._lift(b)
        f = self._sym(iv.aff_mul(A.form, B.form, self.budget,
                                 self.condense_rank), 1)
        I = self._round_iv(iv.mul(A.exact, B.exact), 1)
        return self._out(f, I)

    def neg(self, a):
        A = self._lift(a)
        return self._out(iv.aff_neg(A.form), iv.neg(A.exact))

    def scale(self, a, c, exact_const: bool = False):
        A = self._lift(a)
        f = iv.aff_scale(A.form, c)
        I = iv.scale(A.exact, jnp.asarray(c, jnp.float64))
        if not exact_const:
            f = self._sym(f, 1)
            I = self._round_iv(I, 1)
        return self._out(f, I)

    def shift(self, a, c):
        A = self._lift(a)
        f = self._sym(iv.aff_shift(A.form, c), 1)
        I = self._round_iv(iv.shift(A.exact, jnp.asarray(c, jnp.float64)), 1)
        return self._out(f, I)

    def square(self, a):
        A = self._lift(a)
        f = self._sym(iv.aff_mul(A.form, A.form, self.budget,
                                 self.condense_rank), 1)
        Iq = iv.square(A.exact)
        # squares are exactly nonnegative; iv.square's outward nextafter
        # turns a 0 endpoint into -5e-324, which would defeat _round_iv's
        # sign preservation and ultimately the norm rsqrt guards
        I = self._round_iv(iv.Interval(jnp.maximum(Iq.lo, 0.0), Iq.hi), 1)
        return self._out(f, I)

    def div(self, a, b):
        A, B = self._lift(a), self._lift(b)
        I = self._round_iv(iv.div(A.exact, B.exact), 1)
        return self._out(self._refit(I, A.val / B.val), I)

    # -- nonlinear unaries (interval rule; form refits on the reference) ----
    def _fb_unary(self, a, ivl_fn, val_fn, rounds=1):
        A = self._lift(a)
        I = self._round_iv(ivl_fn(A.exact), rounds)
        return self._out(self._refit(I, val_fn(A.val)), I)

    def tanh(self, a): return self._fb_unary(a, iv.tanh, jnp.tanh)
    def sigmoid(self, a): return self._fb_unary(a, iv.sigmoid,
                                                jax.nn.sigmoid)
    def exp(self, a): return self._fb_unary(a, iv.exp, jnp.exp)
    def log(self, a): return self._fb_unary(a, iv.log, jnp.log)
    def sqrt(self, a): return self._fb_unary(a, iv.sqrt, jnp.sqrt)

    def rsqrt(self, a):
        return self._fb_unary(a, lambda t: iv.recip(iv.sqrt(t)),
                              jax.lax.rsqrt, rounds=2)

    def relu(self, a):
        # exact in FP: selection, no rounding
        A = self._lift(a)
        I = iv.clamp_min(A.exact, 0.0)
        return self._out(self._refit(I, jax.nn.relu(A.val)), I)

    def silu(self, a): return self._fb_unary(a, iv.silu, jax.nn.silu,
                                             rounds=3)

    def gelu(self, a):
        return self._fb_unary(a, iv.gelu_tanh,
                              lambda x: jax.nn.gelu(x, approximate=True),
                              rounds=4)

    def softmax(self, a, axis: int = -1):
        A = self._lift(a)
        # max-shift + exp + sum + div per output: 4 elementary roundings
        I = self._round_iv(iv.softmax_range(A.exact, axis=axis), 4)
        c = jax.nn.softmax(jnp.asarray(A.val, jnp.float64), axis=axis)
        return self._out(self._refit(I, c), I)

    # -- contractions (symbols of distinct elements mix → interval rule) ----
    def matmul(self, a, b):
        A, B = self._lift(a), self._lift(b)
        Ia = self._round_iv(A.exact, 1)   # operand requant into this scope
        Ib = self._round_iv(B.exact, 1)
        n = int(jnp.shape(A.val)[-1])
        I = self._round_iv(iv.matmul(Ia, Ib), n + 2)
        return self._out(self._refit(I, jnp.matmul(A.val, B.val)), I)

    def einsum(self, subscripts, a, b):
        A, B = self._lift(a), self._lift(b)
        Ia = self._round_iv(A.exact, 1)
        Ib = self._round_iv(B.exact, 1)
        n = _einsum_contract_length(subscripts, A.shape, B.shape)
        I = self._round_iv(iv.einsum_ball(subscripts, Ia, Ib), n + 2)
        return self._out(
            self._refit(I, jnp.einsum(subscripts, A.val, B.val)), I)

    def sum(self, a, axis, keepdims: bool = False):
        A = self._lift(a)
        Ia = self._round_iv(A.exact, 1)
        n = _reduced_count(A.shape, axis)
        I = self._round_iv(iv.sum_(Ia, axis=axis, keepdims=keepdims), n + 1)
        return self._out(
            self._refit(I, jnp.sum(A.val, axis=axis, keepdims=keepdims)), I)

    def mean(self, a, axis, keepdims: bool = False):
        # sum-then-scale: the accumulation's n·η absolute slop must be
        # charged on the SUM and divided down with it — charging it on the
        # mean directly is n× too wide, enough to push mean(x²)+eps through
        # zero and blow up every norm's rsqrt
        A = self._lift(a)
        Ia = self._round_iv(A.exact, 1)
        n = _reduced_count(A.shape, axis)
        Is = self._round_iv(iv.sum_(Ia, axis=axis, keepdims=keepdims), n - 1)
        I = self._round_iv(iv.scale(Is, 1.0 / n), 1)
        return self._out(
            self._refit(I, jnp.mean(A.val, axis=axis, keepdims=keepdims)), I)

    def max(self, a, axis, keepdims: bool = False):
        A = self._lift(a)
        I = iv.max_(A.exact, axis=axis, keepdims=keepdims)
        c = jnp.max(jnp.asarray(A.val, jnp.float64), axis=axis,
                    keepdims=keepdims)
        return self._out(self._refit(I, c), I)

    def maximum(self, a, b):
        A, B = self._lift(a), self._lift(b)
        I = iv.maximum(A.exact, B.exact)
        return self._out(self._refit(I, jnp.maximum(A.val, B.val)), I)

    def where(self, mask, a, b):
        m = mask.val if isinstance(mask, (AffTensor, CaaTensor)) else mask
        A, B = self._lift(a), self._lift(b)
        f = iv.aff_where(m, A.form, B.form, self.budget,
                         self.condense_rank)
        Ea, Eb = A.exact, B.exact
        I = iv.Interval(jnp.where(m, Ea.lo, Eb.lo),
                        jnp.where(m, Ea.hi, Eb.hi))
        return self._out(f, I)

    def top_k_mask(self, scores, k: int, name: str = "router"):
        s = self._lift(scores, observe=False)
        _, idx = jax.lax.top_k(s.val, k)
        return jax.nn.one_hot(idx, int(s.shape[-1]),
                              dtype=jnp.float64).sum(-2)

    # -- structure (exact movement: both channels shuffled in place) --------
    def _struct_out(self, a, fn) -> AffTensor:
        A = self._lift(a, observe=False)
        f = iv._aff_broadcast(A.form, A.shape)
        lo = jnp.broadcast_to(A.ivl.lo, A.shape)
        hi = jnp.broadcast_to(A.ivl.hi, A.shape)
        return self._out(_aff_struct(f, fn),
                         iv.Interval(fn(lo, False), fn(hi, False)))

    def reshape(self, a, shape):
        shape = tuple(shape)
        return self._struct_out(a, lambda t, terms: jnp.reshape(
            t, (t.shape[0],) + shape if terms else shape))

    def transpose(self, a, axes):
        axes = tuple(axes)
        taxes = (0,) + tuple(ax + 1 for ax in axes)
        return self._struct_out(a, lambda t, terms: jnp.transpose(
            t, taxes if terms else axes))

    def broadcast_to(self, a, shape):
        A = self._lift(a, observe=False)
        return self._out(
            iv._aff_broadcast(A.form, shape),
            iv.Interval(jnp.broadcast_to(A.ivl.lo, shape),
                        jnp.broadcast_to(A.ivl.hi, shape)))

    def take(self, a, idx, axis):
        tax = axis + 1 if axis >= 0 else axis  # terms lead with the slot dim
        return self._struct_out(a, lambda t, terms: jnp.take(
            t, idx, axis=tax if terms else axis))

    def slice(self, a, slices):
        sl = (tuple(slices) if isinstance(slices, (tuple, list))
              else (slices,))
        return self._struct_out(
            a, lambda t, terms: t[(slice(None),) + sl if terms else sl])

    def concat(self, parts, axis):
        ts = [self._lift(p) for p in parts]
        forms = [iv._aff_broadcast(t.form, t.shape) for t in ts]
        out = forms[0]
        tax = axis + 1 if axis >= 0 else axis
        for f in forms[1:]:
            ids, ta, tb = iv._aff_common(out, f)
            out = iv.aff_condense(iv.AffineForm(
                jnp.concatenate([out.center, f.center], axis=axis),
                jnp.concatenate([ta, tb], axis=tax),
                ids,
                jnp.concatenate([out.rad, f.rad], axis=axis)), self.budget,
                self.condense_rank)
        I = iv.Interval(
            jnp.concatenate([jnp.broadcast_to(t.ivl.lo, t.shape)
                             for t in ts], axis=axis),
            jnp.concatenate([jnp.broadcast_to(t.ivl.hi, t.shape)
                             for t in ts], axis=axis))
        return self._out(out, I)

    def shape_of(self, a):
        return tuple(self._lift(a, observe=False).shape)

    def value_of(self, a):
        return self._lift(a, observe=False).val

    def clamp_range(self, a, lo, hi):
        A = self._lift(a, observe=False)
        lo = jnp.asarray(lo, jnp.float64)
        hi = jnp.asarray(hi, jnp.float64)
        f = iv.aff_intersect(A.form, iv.Interval(lo, hi))
        nlo = jnp.maximum(jnp.broadcast_to(A.ivl.lo, A.shape), lo)
        nhi = jnp.minimum(jnp.broadcast_to(A.ivl.hi, A.shape), hi)
        bad = nlo > nhi   # wrong external bound: keep the original channel
        I = iv.Interval(jnp.where(bad, A.ivl.lo, nlo),
                        jnp.where(bad, A.ivl.hi, nhi))
        return self._out(f, I)

    def record(self, name: str, a, kind: str = "layer"):
        return a

    def ssm_scan(self, decay, drive, n_steps: int, time_axis: int = 1):
        """Interval fixpoint of h' = d⊙h + b under rounded arithmetic:
        with w = max_t |d|, B = max_t |b| and per-step inflation
        (1+u/2)² + 2η, |h| ≤ B'/(1−w') when the rounded decay w' < 1
        (∞ otherwise — still free of saturating γ forms). Reference
        values come from the true f64 scan."""
        D, V = self._lift(decay), self._lift(drive)
        hu, eta = self._hu_eta()
        w = jnp.max(iv.mag(D.exact), axis=time_axis, keepdims=True)
        Bm = jnp.max(iv.mag(V.exact), axis=time_axis, keepdims=True)
        infl = (1.0 + hu) ** 2 * (1.0 + 8.0 * iv._gamma_f64(8))
        wr = iv._up(w * infl)
        Br = iv._up(Bm * infl + 2.0 * eta)
        H = jnp.where(wr < 1.0, Br / jnp.maximum(1.0 - wr, 1e-300),
                      jnp.inf)
        H = iv._up(H * (1.0 + 8.0 * iv._gamma_f64(8)))
        vals = JOps(jnp.float64, jnp.float64).ssm_scan(
            D.val, V.val, n_steps, time_axis)
        I = iv.Interval(jnp.broadcast_to(-H, vals.shape),
                        jnp.broadcast_to(H, vals.shape))
        return self._out(self._refit(I, vals), I)


_AFF_INF = jnp.inf


def _einsum_contract_length(subscripts: str, sa, sb) -> int:
    """Number of products summed per output element of a two-operand
    einsum — the n of the accumulation-rounding charge."""
    ins, out = subscripts.replace(" ", "").split("->")
    A, B = ins.split(",")
    dims = {}
    for ch, d in zip(A, sa):
        dims[ch] = int(d)
    for ch, d in zip(B, sb):
        dims[ch] = int(d)
    n = 1
    for ch, d in dims.items():
        if ch not in out:
            n *= d
    return max(n, 1)


def _reduced_count(shape, axis) -> int:
    if axis is None:
        n = 1
        for d in shape:
            n *= int(d)
        return max(n, 1)
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    n = 1
    for ax in axes:
        n *= int(shape[ax])
    return max(n, 1)


def _canon_aff(t: AffTensor) -> AffTensor:
    """Broadcast every field to center's shape — a scan carry needs one
    fixed aval (the affine twin of :func:`_canon_caa`)."""
    f = t.form
    shape = jnp.shape(f.center)
    form = iv.AffineForm(
        jnp.asarray(f.center, jnp.float64),
        jnp.broadcast_to(jnp.asarray(f.terms, jnp.float64),
                         (f.budget,) + shape),
        jnp.asarray(f.ids, jnp.int32),
        jnp.broadcast_to(jnp.asarray(f.rad, jnp.float64), shape))
    I = iv.Interval(
        jnp.broadcast_to(jnp.asarray(t.ivl.lo, jnp.float64), shape),
        jnp.broadcast_to(jnp.asarray(t.ivl.hi, jnp.float64), shape))
    return AffTensor(form, I)


class StackedAffineRangeCaaOps(AffineRangeCaaOps):
    """Scan-native affine range pass: ``layer_loop`` is ONE ``lax.scan``
    whose carry threads (two-channel enclosure, ``[L, S, 4]`` range lanes,
    noise-symbol counter). The traced i32 counter keeps symbol ids
    distinct across scan iterations — static ids would alias layer i's
    rounding errors with layer i+1's and unsoundly cancel them. Sub-layer
    scopes (``sublanes``, e.g. ``("attn", "mlp")``) get their own
    accumulator lane and their own per-suffix format lane, mirroring
    :class:`StackedRangeCaaOps` / :class:`StackedCaaOps`; ops outside the
    stack run eagerly into ``scope_ranges`` as in the parent class."""

    def __init__(self, scope_fmts: Dict[str, Any], default_fmt,
                 budget: int = iv.AFF_DEFAULT_BUDGET,
                 weights_exact: bool = True,
                 sublanes: Sequence[str] = (),
                 condense_rank: str = iv.AFF_DEFAULT_RANK):
        super().__init__(scope_fmts, default_fmt, budget=budget,
                         weights_exact=weights_exact,
                         condense_rank=condense_rank)
        self._sublanes = tuple(sublanes)
        self._sub_map = {s: j + 1 for j, s in enumerate(self._sublanes)}
        self._in_stack = False
        self._layer_index = None
        self._stack_ctx = None
        self._lane_cache: Dict[tuple, tuple] = {}
        self._lane_acc = None
        self._sym_ctr_traced = None
        self._done_lanes: List = []

    # -- stack plumbing ------------------------------------------------------
    def _stack_suffix(self) -> tuple:
        outer, _ = self._stack_ctx
        return tuple(self._scope[len(outer) + 1:])

    def _sub_idx(self) -> int:
        if self._stack_ctx is None or not self._sub_map:
            return 0
        suffix = self._stack_suffix()
        return self._sub_map.get(suffix[0], 0) if suffix else 0

    def _fmt_lanes(self, suffix: tuple):
        """Per-layer (u/2, η) lanes for one sub-layer suffix (formats are
        static objects, so the lanes are concrete [L] constants)."""
        cached = self._lane_cache.get(suffix)
        if cached is None:
            import numpy as np
            outer, n_layers = self._stack_ctx
            hu, eta = [], []
            for i in range(n_layers):
                fmt = resolve_scope_value(
                    outer + [f"layer{i}", *suffix], self._fmts,
                    self._default_fmt)
                hu.append(0.5 * fmt.u)
                eta.append(fmt.underflow_unit)
            cached = (jnp.asarray(np.asarray(hu, np.float64)),
                      jnp.asarray(np.asarray(eta, np.float64)))
            self._lane_cache[suffix] = cached
        return cached

    def _hu_eta(self):
        if self._in_stack and self._stack_ctx is not None:
            hu_vec, eta_vec = self._fmt_lanes(self._stack_suffix())
            i = self._layer_index
            return hu_vec[i], eta_vec[i]
        return super()._hu_eta()

    def _next_id(self):
        if self._in_stack:
            i = self._sym_ctr_traced
            self._sym_ctr_traced = i + 1
            return i
        return super()._next_id()

    def _observe(self, t: AffTensor, is_op: bool):
        if not self._in_stack:
            return super()._observe(t, is_op)
        ivl = self._requant_interval(t)
        lo = jnp.broadcast_to(ivl.lo, t.shape).ravel()
        hi = jnp.broadcast_to(ivl.hi, t.shape).ravel()
        mag = jnp.max(jnp.maximum(jnp.abs(lo), jnp.abs(hi)))
        mig = jnp.maximum(jnp.maximum(lo, -hi), 0.0)
        min_nz = jnp.min(jnp.where(mig > 0, mig, jnp.inf))
        crossed = jnp.any(mig <= 0).astype(jnp.float64)
        stat = (mag, min_nz, crossed,
                jnp.asarray(1.0 if is_op else 0.0, jnp.float64))
        i, j = self._layer_index, self._sub_idx()
        self._lane_acc = self._lane_acc.at[i, j].set(
            StackedRangeCaaOps._merge_acc(self._lane_acc[i, j], stat))

    # -- the one scan --------------------------------------------------------
    def layer_loop(self, fn, stacked_params, x, n_layers: int, aux=None,
                   first: int = 0):
        if first:
            raise NotImplementedError(
                "the scan-form analyses cover one homogeneous layer stack; "
                "analyse a model with leading layers on the eager unroll")
        if self._in_stack:
            return super().layer_loop(fn, stacked_params, x, n_layers, aux)
        outer = list(self._scope)
        self._stack_ctx = (outer, n_layers)
        self._lane_cache = {}
        x0 = _canon_aff(self._lift(x, observe=False))
        acc0 = jnp.broadcast_to(
            jnp.asarray(StackedRangeCaaOps._ACC_INIT, jnp.float64),
            (n_layers, 1 + len(self._sublanes), 4))
        ctr0 = jnp.asarray(self._sym_counter, jnp.int32)

        def body(carry, xs):
            p, i = xs
            cf, clo, chi, acc, ctr, a = carry
            self._in_stack = True
            self._layer_index = i
            self._lane_acc = acc
            self._sym_ctr_traced = ctr
            cx = AffTensor(cf, iv.Interval(clo, chi))
            new_x, a = fn(p, cx, i, a)
            nt = _canon_aff(self._lift(new_x, observe=False))
            return ((nt.form, nt.ivl.lo, nt.ivl.hi,
                     self._lane_acc, self._sym_ctr_traced, a), None)

        idx = jnp.arange(n_layers)
        with self.scope(STACK_SCOPE):
            carry0 = (x0.form, x0.ivl.lo, x0.ivl.hi, acc0, ctr0, aux)
            (out_f, out_lo, out_hi, acc, ctr, aux), _ = jax.lax.scan(
                body, carry0, (stacked_params, idx))
            self._in_stack = False
            self._layer_index = None
            self._stack_ctx = None
            self._lane_acc = None
        self._done_lanes.append(acc)
        # eager ids must stay ahead of every id the scan consumed
        self._sym_counter = int(ctr)
        return AffTensor(out_f, iv.Interval(out_lo, out_hi)), aux

    def collect_ranges(self) -> Dict[str, RangeStat]:
        """Concretised lanes (``layer{i}`` / ``layer{i}/{sub}`` keys)
        merged with the eager outside-the-stack ``scope_ranges``."""
        import numpy as np
        out: Dict[str, RangeStat] = {}
        for lanes in self._done_lanes:
            arr = np.asarray(lanes, np.float64)
            for i in range(arr.shape[0]):
                for j in range(arr.shape[1]):
                    row = arr[i, j]
                    s = RangeStat(
                        max_abs=float(row[0]), min_nonzero=float(row[1]),
                        crosses_zero=bool(row[2] > 0), n_ops=int(row[3]))
                    if (j > 0 and s.n_ops == 0 and s.max_abs == 0.0
                            and s.min_nonzero == math.inf):
                        continue
                    key = (f"layer{i}" if j == 0
                           else f"layer{i}/{self._sublanes[j - 1]}")
                    out[key] = (s if key not in out
                                else out[key].merge(s))
        for key, s in self.scope_ranges.items():
            key = "" if key.startswith(STACK_SCOPE) else key
            out[key] = s if key not in out else out[key].merge(s)
        out.setdefault("", RangeStat())
        return out
