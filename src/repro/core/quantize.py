"""Emulation of low-precision FP formats on f32/f64 carriers.

This is the *empirical oracle* for the rigorous CAA analysis: we can actually
run a network with every intermediate rounded to a k-bit mantissa (RNE) and
check the measured error against the CAA bound (tests/test_soundness.py), and
run low-precision inference end-to-end to confirm the paper's headline claim
that the predicted precision preserves the top-1 class.

Rounding is performed by bit-twiddling the carrier format (round-to-nearest,
ties-to-even on the retained mantissa), followed by exponent-range handling
(overflow → ±inf or saturate; gradual underflow by re-quantising in a scaled
frame). The same routine, jitted, is what the quantised inference path uses —
and the Pallas ``quant_matmul`` kernel fuses it into the GEMM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .formats import FpFormat, get as get_format


def _round_mantissa_bits(bits, total_mant: int, k: int, uint_t, one):
    """RNE-truncate `bits` (carrier uint) to k mantissa bits (incl. implicit)."""
    s = total_mant - (k - 1)  # bits to drop from the *stored* mantissa
    if s <= 0:
        return bits
    half = one << (s - 1)
    lsb = (bits >> s) & one
    rounded = (bits + (half - one) + lsb) & ~((one << s) - one)
    return rounded.astype(uint_t)


def _quantize_normal(x: jax.Array, k: int) -> jax.Array:
    """Round mantissa of x to k bits (RNE), full carrier exponent range.

    Works for f32 (k<=24) and f64 (k<=53) carriers. NaN/Inf pass through.
    Carry into the exponent on mantissa overflow is handled naturally by the
    integer addition (e.g. 1.111..1 rounds up to 10.0 → exponent += 1).
    """
    dt = x.dtype
    if dt == jnp.float32:
        uint_t, total_mant = jnp.uint32, 23
    elif dt == jnp.float64:
        uint_t, total_mant = jnp.uint64, 52
    else:
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    if k - 1 >= total_mant + 1:
        return x
    one = jnp.asarray(1, uint_t)
    bits = jax.lax.bitcast_convert_type(x, uint_t)
    rounded = _round_mantissa_bits(bits, total_mant, k, uint_t, one)
    out = jax.lax.bitcast_convert_type(rounded, dt)
    # NaN payloads can carry into Inf under the integer trick; restore NaN.
    out = jnp.where(jnp.isnan(x), x, out)
    out = jnp.where(jnp.isinf(x), x, out)
    return out


@functools.partial(jax.jit, static_argnames=("fmt_name",))
def _quantize_impl(x: jax.Array, fmt_name: str) -> jax.Array:
    fmt = get_format(fmt_name)
    k = fmt.k
    y = _quantize_normal(x, k)

    # Exponent-range handling in the carrier.
    max_fin = jnp.asarray(fmt.max_finite, y.dtype)
    min_norm = jnp.asarray(fmt.min_normal, y.dtype)

    # Overflow. Gate on the ORIGINAL value's finiteness: mantissa rounding
    # can overflow the carrier itself (y = ±inf for finite x near carrier
    # max) and a saturating format must still clamp that; for non-saturating
    # formats sign(±inf)·inf reproduces the ±inf unchanged.
    over = jnp.abs(y) > max_fin
    inf_like = jnp.where(
        jnp.asarray(fmt.saturating),
        jnp.sign(y) * max_fin,
        jnp.sign(y) * jnp.asarray(jnp.inf, y.dtype),
    )
    y = jnp.where(over & jnp.isfinite(x), inf_like, y)

    # Underflow: values with magnitude below the smallest normal.
    tiny = (jnp.abs(y) < min_norm) & (y != 0)
    if fmt.has_subnormals:
        # Quantise on the fixed-point grid of spacing 2^{emin-(k-1)} —
        # from the *original* value (single rounding, no double-round)
        step = jnp.asarray(fmt.min_subnormal, y.dtype)
        snapped = jnp.round(x / step) * step  # RNE via jnp.round (banker's)
        y = jnp.where(tiny, snapped, y)
    else:
        # Flush-to-zero below the subnormal midpoint threshold.
        y = jnp.where(tiny & (jnp.abs(y) < min_norm / 2), jnp.zeros_like(y), y)
        y = jnp.where(tiny & (jnp.abs(y) >= min_norm / 2), jnp.sign(y) * min_norm, y)
    return y


def _clip_i32(v, lo: int, hi: int):
    # int32 bounds: Python-int bounds become i64 under x64, and Mosaic
    # cannot lower the i64 -> i32 convert that jnp.clip then inserts
    return jnp.clip(v, jnp.int32(lo), jnp.int32(hi))


def quantize_to_k(x: jax.Array, k) -> jax.Array:
    """Mantissa-only RNE rounding to k bits where ``k`` may be a *traced*
    scalar (jnp int), not just a Python int.

    Bitwise-identical to :func:`_quantize_normal` at the same static k — the
    property tests assert it — but with the dropped-bit count computed in
    integer arithmetic instead of Python control flow, so ONE jit compilation
    serves every k. This is the scalar-k-as-argument path the mixed-precision
    serving backend and the jitted certificate probe ladder rely on: per-layer
    k can come out of a scanned array without recompiling per precision.
    """
    x = jnp.asarray(x)
    dt = x.dtype
    if dt == jnp.float32:
        uint_t, total_mant = jnp.uint32, 23
    elif dt == jnp.float64:
        uint_t, total_mant = jnp.uint64, 52
    else:
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    k = jnp.asarray(k, jnp.int32)
    s = total_mant - (k - 1)               # bits to drop; <= 0 → identity
    eff = _clip_i32(s, 1, total_mant).astype(uint_t)
    one = jnp.asarray(1, uint_t)
    bits = jax.lax.bitcast_convert_type(x, uint_t)
    half = (one << (eff - one)) - one      # 2^{s-1} - 1
    lsb = (bits >> eff) & one
    rounded = (bits + half + lsb) & ~((one << eff) - one)
    out = jax.lax.bitcast_convert_type(rounded.astype(uint_t), dt)
    out = jnp.where(s <= 0, x, out)
    out = jnp.where(jnp.isnan(x) | jnp.isinf(x), x, out)
    return out


def pow2(e, dt) -> jax.Array:
    """Exact 2^e for integer (possibly traced) ``e``, carrier subnormals
    included — by exponent-bit construction, NOT exp2 (XLA lowers exp2
    through exp(x·ln2), which is off by many ulps: unusable where bitwise
    agreement with the static :func:`quantize` path is the contract)."""
    e = jnp.asarray(e, jnp.int32)
    if dt == jnp.float32:
        uint_t, bias, mant, min_e = jnp.uint32, 127, 23, -149
    elif dt == jnp.float64:
        uint_t, bias, mant, min_e = jnp.uint64, 1023, 52, -1074
    else:
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    normal = e >= 1 - bias
    bits_n = _clip_i32(e + bias, 0, 2 * bias).astype(uint_t) << mant
    bits_s = (jnp.asarray(1, uint_t)
              << _clip_i32(e - min_e, 0, mant).astype(uint_t))
    return jax.lax.bitcast_convert_type(jnp.where(normal, bits_n, bits_s), dt)


def quantize_to_format(x: jax.Array, k, emax, emin,
                       has_subnormals: bool = True,
                       saturating: bool = True,
                       max_finite=None) -> jax.Array:
    """Full custom-format rounding where ``k``/``emax``/``emin`` may be
    *traced* scalars — ONE jit compilation serves every certified format.

    Semantics are bitwise-identical to :func:`quantize` at the same static
    format (the property tests assert it): RNE mantissa rounding
    (:func:`quantize_to_k`), overflow beyond ``max_finite`` saturates to
    ±max_finite (or ±inf with ``saturating=False``), magnitudes below
    ``2^emin`` are re-quantised on the subnormal grid of spacing
    ``2^{emin-(k-1)}`` from the *original* value (single rounding), or
    flushed to 0 / ±min_normal without subnormals. NaN/Inf pass through.

    This is the serving-side contract of a schema-v3 format certificate:
    the scalar-prefetch Pallas kernel (:mod:`repro.kernels.quant_matmul`)
    computes exactly this function on its tiles. ``max_finite`` overrides
    the (2−2^{1-k})·2^emax formula for encoding-clipped formats (e4m3).

    Caveat: the identity is stated for carrier-NORMAL inputs (plus 0/±inf/
    NaN). When the emulated format's subnormal grid dips below the
    carrier's own normal range (only possible for emin ≈ the carrier's,
    e.g. bfloat16 emulated on f32), carrier-subnormal inputs hit XLA's
    flush-to-zero inconsistencies in both paths and they may disagree —
    synthesized formats (narrow emin by construction) never get there.
    """
    x = jnp.asarray(x)
    dt = x.dtype
    if dt not in (jnp.float32, jnp.float64):
        raise TypeError(f"carrier must be f32/f64, got {dt}")
    y = quantize_to_k(x, k)
    k = jnp.asarray(k, jnp.int32)
    emax = jnp.asarray(emax, jnp.int32)
    emin = jnp.asarray(emin, jnp.int32)
    if max_finite is None:
        max_fin = (2.0 - pow2(1 - k, dt)) * pow2(emax, dt)
    else:
        max_fin = jnp.asarray(max_finite, dt)
    min_norm = pow2(emin, dt)

    # gate on x, not y: mantissa rounding may overflow the CARRIER (finite x
    # near carrier max → y = ±inf), and saturation must still clamp that
    over = (jnp.abs(y) > max_fin) & jnp.isfinite(x)
    if saturating:
        inf_like = jnp.sign(y) * max_fin
    else:
        inf_like = jnp.sign(y) * jnp.asarray(jnp.inf, dt)
    y = jnp.where(over, inf_like, y)

    tiny = (jnp.abs(y) < min_norm) & (y != 0)
    if has_subnormals:
        step = pow2(emin - (k - 1), dt)
        snapped = jnp.round(x / step) * step   # RNE via jnp.round (banker's)
        y = jnp.where(tiny, snapped, y)
    else:
        y = jnp.where(tiny & (jnp.abs(y) < min_norm / 2), jnp.zeros_like(y), y)
        y = jnp.where(tiny & (jnp.abs(y) >= min_norm / 2),
                      jnp.sign(y) * min_norm, y)
    return jnp.where(jnp.isnan(x) | jnp.isinf(x), x, y)


def numeric_health(x: jax.Array, k, emax, emin) -> dict:
    """Cheap per-tensor numeric-health stats against a (k, emax, emin) format
    whose fields may be *traced* scalars — jit-safe, O(n) elementwise.

    Returns a dict of 0-d arrays:
      max_abs:     largest finite magnitude observed
      min_nonzero: smallest nonzero magnitude observed (+inf if all zero)
      n_over:      elements beyond the format's max_finite (overflow /
                   saturation events under a saturating format)
      n_under:     nonzero elements below the format's min_normal = 2^emin
                   (landing on the subnormal grid / flush region)
      n_nonfinite: NaN/Inf elements (upstream pathology, format-independent)

    This is the runtime observation half of a certificate-violation monitor:
    the certified IA enclosure says where magnitudes *must* lie; these stats
    say where they *did*. The caller compares (on the host, via
    ``jax.debug.callback``) so the jitted serving values stay untouched.
    """
    x = jnp.asarray(x)
    dt = x.dtype
    if dt not in (jnp.float32, jnp.float64):
        x = x.astype(jnp.float32)
        dt = jnp.float32
    k = jnp.asarray(k, jnp.int32)
    max_fin = (2.0 - pow2(1 - k, dt)) * pow2(jnp.asarray(emax, jnp.int32), dt)
    min_norm = pow2(jnp.asarray(emin, jnp.int32), dt)
    a = jnp.abs(x)
    finite = jnp.isfinite(x)
    nonzero = finite & (a > 0)
    inf_dt = jnp.asarray(jnp.inf, dt)
    return {
        "max_abs": jnp.max(jnp.where(finite, a, 0.0)),
        "min_nonzero": jnp.min(jnp.where(nonzero, a, inf_dt)),
        "n_over": jnp.sum((a > max_fin) & finite),
        "n_under": jnp.sum(nonzero & (a < min_norm)),
        "n_nonfinite": jnp.sum(~finite),
    }


def quantize(x: jax.Array, fmt: FpFormat | str | int) -> jax.Array:
    """Round every element of ``x`` to the given format (value kept in carrier).

    ``quantize(x, 'bfloat16')`` on an f32 array returns the f32 array whose
    values are exactly representable in bfloat16 — i.e. an emulated bf16
    storage. ``quantize(x, 8)`` emulates a custom k=8 format.
    """
    fmt = get_format(fmt)
    x = jnp.asarray(x)
    if x.dtype not in (jnp.float32, jnp.float64):
        x = x.astype(jnp.float32)
    return _quantize_impl(x, fmt.name)


def quantized_op(op, fmt: FpFormat | str | int):
    """Wrap a binary/unary op so its *result* is rounded into ``fmt``.

    This is the emulation of 'every FP operation rounds once' from the first
    standard model (paper eq. (5)) at precision k: operands are assumed
    already representable; the op computes in the (much wider) carrier and
    rounds once.
    """
    fmt = get_format(fmt)

    def wrapped(*args):
        return quantize(op(*args), fmt)

    return wrapped


def seq_dot(x: jax.Array, w: jax.Array, fmt: FpFormat | str | int) -> jax.Array:
    """Sequential-order matmul ``x[..., n] @ w[n, m]`` with one rounding per
    FLOP, in ``fmt``.

    The reference semantics of frugally-deep's scalar loop, which the paper
    analyses: acc = fl(acc + fl(x_i * w_i)). Used by the soundness tests as
    the ground-truth low-precision execution for the ``sequential``
    accumulation order.
    """
    fmt = get_format(fmt)
    xq = quantize(x, fmt)
    wq = quantize(w, fmt)

    def body(acc, xw):
        xi, wi = xw  # xi: [...], wi: [m]
        prod = quantize(xi[..., None] * wi, fmt)
        return quantize(acc + prod, fmt), None

    acc0 = jnp.zeros(x.shape[:-1] + (w.shape[-1],), x.dtype)
    acc, _ = jax.lax.scan(body, acc0, (jnp.moveaxis(xq, -1, 0), wq))
    return acc


def pairwise_dot(x: jax.Array, w: jax.Array, fmt: FpFormat | str | int) -> jax.Array:
    """Pairwise(tree)-order matmul ``x[..., n] @ w[n, m]`` with one rounding
    per op, in ``fmt``.

    Models the XLA/TPU reduction tree; error constant γ_{⌈log2 n⌉+1} instead
    of γ_n.
    """
    fmt = get_format(fmt)
    prods = quantize(
        quantize(x, fmt)[..., :, None] * quantize(w, fmt), fmt
    )  # [..., n, m]
    vals = jnp.moveaxis(prods, -2, 0)
    n = vals.shape[0]
    while vals.shape[0] > 1:
        m = vals.shape[0]
        if m % 2:
            carry, vals = vals[-1:], vals[:-1]
        else:
            carry = None
        vals = quantize(vals[0::2] + vals[1::2], fmt)
        if carry is not None:
            vals = jnp.concatenate([vals, carry], axis=0)
    return vals[0]


def kahan_dot(x: jax.Array, w: jax.Array, fmt: FpFormat | str | int) -> jax.Array:
    """Kahan-compensated matmul ``x[..., n] @ w[n, m]`` with one rounding per
    op, in ``fmt`` — the oracle for the 'kahan' accumulation order (the
    paper's future-work codegen hook)."""
    fmt = get_format(fmt)
    xq = quantize(x, fmt)
    wq = quantize(w, fmt)

    def body(carry, xw):
        acc, comp = carry
        xi, wi = xw
        prod = quantize(xi[..., None] * wi, fmt)
        y = quantize(prod - comp, fmt)
        t = quantize(acc + y, fmt)
        comp = quantize(quantize(t - acc, fmt) - y, fmt)
        return (t, comp), None

    z = jnp.zeros(x.shape[:-1] + (w.shape[-1],), x.dtype)
    (acc, _), _ = jax.lax.scan(body, (z, z),
                               (jnp.moveaxis(xq, -1, 0), wq))
    return acc


def measured_error_in_u(exact: jax.Array, approx: jax.Array, fmt) -> tuple[jax.Array, jax.Array]:
    """(absolute, relative) error of ``approx`` vs ``exact``, in units of u."""
    fmt = get_format(fmt)
    u = fmt.u
    abs_err = jnp.abs(approx.astype(jnp.float64) - exact.astype(jnp.float64)) / u
    denom = jnp.abs(exact.astype(jnp.float64))
    rel_err = jnp.where(denom > 0, abs_err / denom, jnp.where(abs_err > 0, jnp.inf, 0.0))
    return abs_err, rel_err
