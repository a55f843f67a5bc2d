"""Tests for the measured-performance layer: ``repro.obs.profile`` (timing
discipline, analytic roofline terms, jaxpr-size gauges), the fitted cost
model (``repro.obs.costmodel``) and its certificate what-if report, the
bench-trajectory plumbing (root emission, session dedupe, soft perf gate),
the Prometheus exposition details the serving digests depend on (label
escaping, cumulative buckets, percentile math), and the ``repro.obs``
CLI views over ``BENCH_kernels.json``.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import costmodel as CM
from repro.obs import profile as P
from repro.obs.report import render_kernel_table

# analytic terms are priced against an explicit chip; the CPU has no peaks
V5E = CM.hardware_for("TPU v5 lite")

from _hyp import given, st


@pytest.fixture(autouse=True)
def _fresh_tracer():
    obs.shutdown()
    yield
    obs.shutdown()


# ---------------------------------------------------------------------------
# timing + jaxpr primitives
# ---------------------------------------------------------------------------


def test_measure_median_within_extremes():
    f = jax.jit(lambda a, b: a + b)
    x = jnp.ones((8, 8))
    t = P.measure(f, x, x, reps=5, warmup=1)
    assert t["reps"] == 5 and len(t["samples"]) == 5
    assert 0 < t["min_s"] <= t["median_s"] <= t["max_s"]
    assert t["min_s"] <= t["mean_s"] <= t["max_s"]


def test_jaxpr_stats_descends_into_scan_body():
    def flat(x):
        return x * 2.0 + 1.0

    def scanned(x):
        def body(c, _):
            return c * 2.0 + 1.0, None
        out, _ = jax.lax.scan(body, x, None, length=4)
        return out

    x = jnp.ones((3,))
    n_flat = P.jaxpr_stats(flat, x)["eqns"]
    n_scan = P.jaxpr_stats(scanned, x)["eqns"]
    # the scan body's equations are counted (scan + body > flat body alone)
    assert n_scan > n_flat >= 2


def test_time_compile_returns_runnable_executable():
    f = jax.jit(lambda a: a @ a)
    x = jnp.eye(4)
    r = P.time_compile(f, x)
    assert r["lower_s"] >= 0 and r["compile_s"] > 0
    np.testing.assert_allclose(np.asarray(r["compiled"](x)), np.eye(4))


def test_peaks_are_keyed_by_device_kind():
    assert V5E.peak_flops == 197e12 and V5E.hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no peak rates"):
        CM.hardware_for("cpu")
    # no silent default: off the chips in the table the rows keep their
    # measured and analytic terms, and report no roofline share
    assert CM.local_hardware() is CM.PEAKS.get(jax.devices()[0].device_kind)
    if CM.local_hardware() is None:
        (row,) = P.profile_kernels(gemm_shapes=((16, 16, 16),),
                                   include=("matmul_baseline",),
                                   reps=1, warmup=1)
        assert row["flops"] == 2.0 * 16 ** 3 and row["median_s"] > 0
        assert not {"roofline_s", "roofline_frac", "bound"} & set(row)
        assert "bound" not in P.gemm_terms(16, 16, 16, 32.0, hw=None)


def test_gemm_terms_math():
    t = P.gemm_terms(128, 256, 64, bits=8.0, hw=V5E)
    assert t["flops"] == 2.0 * 128 * 256 * 64
    assert t["bytes"] == (128 * 256 + 256 * 64 + 128 * 64) * 1.0
    assert t["intensity"] == pytest.approx(t["flops"] / t["bytes"])
    assert t["roofline_s"] == pytest.approx(
        max(t["compute_s"], t["memory_s"]))
    # small GEMMs sit on the memory side of the TPU ridge
    assert t["bound"] == "memory"
    # narrower storage moves the SAME flops with fewer bytes
    assert P.gemm_terms(128, 256, 64, bits=32.0, hw=V5E)["bytes"] == \
        4 * t["bytes"]


def test_flash_decode_terms_math():
    t = P.flash_decode_terms(2, 256, 2, 2, 64, bits=32.0, hw=V5E)
    assert t["flops"] == 4.0 * 2 * 2 * 2 * 256 * 64
    assert t["bytes"] == (2 * 2 * 256 * 2 * 64 + 2 * 2 * 2 * 2 * 64) * 4.0
    assert t["bound"] == "memory"   # decode attention streams the KV cache


def test_block_candidates_respect_divisibility():
    from repro.kernels.quant_matmul import block_candidates

    for (M, K, N) in ((128, 128, 128), (128, 256, 128), (256, 512, 256)):
        cands = block_candidates(M, K, N)
        assert cands and len(cands) <= 4
        assert len(set(cands)) == len(cands)
        for (bm, bn, bk) in cands:
            assert M % bm == 0 and N % bn == 0 and K % bk == 0
    # non-tile-aligned dims fall back to the full dimension
    assert block_candidates(24, 24, 24) == [(24, 24, 24)]


def test_profile_kernels_rows_and_spans():
    tr = obs.configure()
    rows = P.profile_kernels(
        gemm_shapes=((16, 16, 16),), ks=(8,),
        include=("matmul_baseline", "quant_matmul_dynamic_k"),
        reps=2, warmup=1, hw=V5E)
    assert [r["kernel"] for r in rows] == ["matmul_baseline",
                                           "quant_matmul_dynamic_k"]
    for r in rows:
        assert r["median_s"] > 0
        assert r["achieved_flops_per_s"] == pytest.approx(
            r["flops"] / r["median_s"])
        assert r["roofline_frac"] > 0 and r["bound"] in ("memory", "compute")
    assert rows[1]["k"] == 8 and rows[1]["format_bits"] == CM.format_bits(8)
    names = [e["name"] for e in tr.events if e["type"] == "span"]
    assert names.count("profile.kernel") == 2


@pytest.mark.slow
def test_profile_kernels_pallas_format_point():
    (row,) = P.profile_kernels(
        gemm_shapes=((16, 16, 16),), formats=((4, 8, -6),),
        blocks=((16, 16, 16),), include=("quant_matmul_format",),
        reps=1, warmup=1, hw=V5E)
    assert row["kernel"] == "quant_matmul_format"
    assert row["interpret"] == (jax.default_backend() != "tpu")
    assert row["block"] == [16, 16, 16]
    assert row["format_bits"] == CM.format_bits(4, 8, -6)


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


def test_format_bits_and_scope_class():
    assert CM.format_bits(24) == 1 + 8 + 23          # binary32 carrier
    assert CM.format_bits(8) == 1 + 8 + 7
    assert CM.format_bits(4, emax=8, emin=-6) < CM.format_bits(4)
    assert CM.scope_class("") == "default"
    assert CM.scope_class("layer3") == "layer"
    assert CM.scope_class("layer3/attn") == "layer/attn"
    assert CM.scope_class("dense1") == "dense"


def _toy_model(alpha_gemm=1e9, beta_gemm=1e8):
    return CM.CostModel(
        alpha={"quant_matmul_format": alpha_gemm, "flash_decode": 5e8},
        beta={"quant_matmul_format": beta_gemm, "flash_decode": 2e8},
        hardware=V5E)


def test_fit_cost_model_median_rates():
    recs = [
        {"kernel": "g", "median_s": 1e-3, "flops": 1e6, "bytes": 1e5},
        {"kernel": "g", "median_s": 2e-3, "flops": 1e6, "bytes": 1e5},
        {"kernel": "g", "median_s": 4e-3, "flops": 1e6, "bytes": 1e5},
    ]
    m = CM.fit_cost_model(recs)
    assert m.alpha["g"] == pytest.approx(1e6 / 2e-3)   # median point
    assert m.beta["g"] == pytest.approx(1e5 / 2e-3)
    assert m.meta["fit_points"] == {"g": 3}
    with pytest.raises(ValueError):
        CM.fit_cost_model([{"kernel": "g", "median_s": 0.0,
                            "flops": 1.0, "bytes": 1.0}])


def test_fit_cost_model_drops_interpret_rows_when_real_exist():
    # interpret-mode rows time the Python emulator, not the hardware: with
    # a real row present they must not drag the fitted rate down
    recs = [
        {"kernel": "g", "median_s": 1e-3, "flops": 1e6, "bytes": 1e5,
         "interpret": False},
        {"kernel": "g", "median_s": 1.0, "flops": 1e6, "bytes": 1e5,
         "interpret": True},
        {"kernel": "g", "median_s": 2.0, "flops": 1e6, "bytes": 1e5,
         "interpret": True},
    ]
    m = CM.fit_cost_model(recs)
    assert m.alpha["g"] == pytest.approx(1e6 / 1e-3)   # real row only
    assert m.meta["fit_points"] == {"g": 1}
    assert m.meta["interpret_rows_dropped"] == 2
    assert "interpret_only" not in m.meta


def test_fit_cost_model_interpret_only_warns_and_flags():
    recs = [{"kernel": "g", "median_s": 1e-3, "flops": 1e6, "bytes": 1e5,
             "interpret": True}]
    with pytest.warns(RuntimeWarning, match="interpret-mode"):
        m = CM.fit_cost_model(recs)
    assert m.meta["interpret_only"] is True
    assert m.alpha["g"] == pytest.approx(1e6 / 1e-3)   # still fits


def test_predict_two_term_roofline():
    m = _toy_model(alpha_gemm=1e9, beta_gemm=1e8)
    # narrow format: few bytes → compute side; wide: many bytes → memory
    narrow = m.predict("dense1", flops_per_token=1e6, k=4, emax=8, emin=-6)
    wide = m.predict("dense1", flops_per_token=1e6, k=24)
    assert narrow["bits"] < wide["bits"]
    assert narrow["bytes"] < wide["bytes"]
    assert wide["latency_s"] == pytest.approx(
        max(wide["compute_s"], wide["memory_s"]))
    assert wide["latency_s"] >= narrow["latency_s"]
    # attention scopes route to the attention kernel class
    assert m.kernel_for("layer3/attn") == "flash_decode"
    assert m.kernel_for("dense1") == "quant_matmul_format"


def test_cost_model_json_roundtrip(tmp_path):
    m = _toy_model()
    path = str(tmp_path / "cm.json")
    m.save_json(path)
    m2 = CM.CostModel.load_json(path)
    assert m2.alpha == m.alpha and m2.beta == m.beta
    assert m2.hardware.name == m.hardware.name
    d = m.to_dict()
    assert d["schema"] == 1 and "alpha_flops_per_s" in d


def test_cost_report_flags_compute_bound_disagreement():
    # β huge → memory term negligible → every scope compute-bound → the
    # bits objective credits narrowing that buys no predicted latency
    m = CM.CostModel(alpha={"quant_matmul_format": 1e9},
                     beta={"quant_matmul_format": 1e30})
    rep = CM.cost_report(m, layer_flops={"layer0": 1e6, "head": 5e5},
                         layer_k={"layer0": 6, "head": 20})
    assert {r["scope"] for r in rep["scopes"]} == {"layer0", "head"}
    assert sum(r["latency_share"] for r in rep["scopes"]) == pytest.approx(1)
    assert rep["mean_bits_flop_weighted"] < CM.BINARY32_BITS
    notes = [d["note"] for d in rep["disagreements"]]
    assert any("compute-bound" in n for n in notes)
    # memory-bound regime: latency saved tracks bits saved → ranks agree
    m2 = CM.CostModel(alpha={"quant_matmul_format": 1e30},
                      beta={"quant_matmul_format": 1e8})
    rep2 = CM.cost_report(m2, layer_flops={"layer0": 1e6, "head": 5e5},
                          layer_k={"layer0": 6, "head": 20})
    assert rep2["rank_agreement"] == 1.0
    text = CM.render_cost_report(rep)
    assert "scope" in text and "layer0" in text


def test_certificate_cost_report_uses_serving_map():
    class _Set:
        model_id = "m"
        params_digest = "d"
        serving_layer_format = None
        serving_layer_k = {"layer0": 8}
        serving_k = 12

    rep = CM.certificate_cost_report(
        _Set(), {"layer0": 1e6, "head": 1e6}, _toy_model())
    by = {r["scope"]: r for r in rep["scopes"]}
    assert by["layer0"]["k"] == 8          # mixed map wins for layer0
    assert by["head"]["k"] == 12           # uniform fallback elsewhere
    assert rep["serving_map"] == "mixed"
    assert rep["model_id"] == "m"


# ---------------------------------------------------------------------------
# bench trajectory: root emission, dedupe, soft perf gate, CLI views
# ---------------------------------------------------------------------------


def _kernel_entry(median_a=1e-3, median_b=1e-3):
    return {
        "kind": "kernel_bench", "backend": "cpu", "interpret": True,
        "hardware": V5E.to_dict(),
        "rows": [
            {"kernel": "matmul_baseline", "shape": "128x128x128",
             "median_s": median_a, "flops": 2.0 * 128 ** 3,
             "bytes": 3 * 128 * 128 * 4.0, "intensity": 10.7,
             "roofline_s": 2e-7, "roofline_frac": 2e-4, "bound": "memory",
             "achieved_flops_per_s": 2.0 * 128 ** 3 / median_a,
             "achieved_bytes_per_s": 3 * 128 * 128 * 4.0 / median_a,
             "reps": 3, "interpret": False},
            {"kernel": "quant_matmul_format", "shape": "128x128x128",
             "k": 4, "emax": 8, "emin": -6, "block": [128, 128, 128],
             "median_s": median_b, "flops": 2.0 * 128 ** 3,
             "bytes": 3 * 128 * 128 * 4.0, "intensity": 10.7,
             "roofline_s": 2e-7, "roofline_frac": 2e-4, "bound": "memory",
             "achieved_flops_per_s": 2.0 * 128 ** 3 / median_b,
             "achieved_bytes_per_s": 3 * 128 * 128 * 4.0 / median_b,
             "reps": 3, "interpret": True},
        ],
        "serving": {
            "prefill": {"latency_s": 0.3, "compile_s": 0.4, "lower_s": 0.1,
                        "jaxpr_eqns": 176, "tokens_per_s": 53.0},
            "decode": {"percentiles": {"p50": 2e-4, "p95": 3e-4,
                                       "p99": 3e-4},
                       "mean_s": 2e-4, "count": 6, "compile_s": 0.2,
                       "lower_s": 0.05, "jaxpr_eqns": 191,
                       "tokens_per_s": 5000.0},
        },
    }


def test_bench_root_emission_and_mirror(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    obs.append_bench("kernels", _kernel_entry())
    root = tmp_path / "BENCH_kernels.json"
    mirror = tmp_path / "benchmarks" / "BENCH_kernels.json"
    assert root.exists() and mirror.exists()
    assert json.loads(root.read_text()) == json.loads(mirror.read_text())


def test_bench_seeds_from_legacy_location(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    legacy = tmp_path / "benchmarks"
    legacy.mkdir()
    (legacy / "BENCH_kernels.json").write_text(
        json.dumps([{"t": 1.0, "kind": "kernel_bench", "arch": "old",
                     "rows": []}]))
    obs.append_bench("kernels", {**_kernel_entry(), "arch": "new"})
    entries = json.loads((tmp_path / "BENCH_kernels.json").read_text())
    assert len(entries) == 2 and entries[0]["arch"] == "old"


def test_bench_root_is_single_source_of_truth(tmp_path, monkeypatch):
    # once the root file exists it WINS — even when empty — so a stale
    # legacy mirror can never resurrect entries the root dropped
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    legacy = tmp_path / "benchmarks"
    legacy.mkdir()
    (legacy / "BENCH_kernels.json").write_text(
        json.dumps([{"t": 1.0, "kind": "kernel_bench", "arch": "stale",
                     "rows": []}]))
    (tmp_path / "BENCH_kernels.json").write_text("[]")
    assert obs.read_bench("kernels") == []


def test_bench_read_dedupes_by_content(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    e1 = {"t": 1.0, "kind": "kernel_bench", "arch": "a", "rows": []}
    e2 = {"t": 2.0, "kind": "kernel_bench", "arch": "b", "rows": []}
    (tmp_path / "BENCH_kernels.json").write_text(json.dumps([e1, e2, e1]))
    entries = obs.read_bench("kernels")
    assert entries == [e1, e2]                # first-occurrence order


def test_bench_mirror_is_read_only_snapshot(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    obs.append_bench("kernels", {**_kernel_entry(), "arch": "a"})
    mirror = tmp_path / "benchmarks" / "BENCH_kernels.json"
    import stat
    mode = stat.S_IMODE(mirror.stat().st_mode)
    assert not mode & (stat.S_IWUSR | stat.S_IWGRP | stat.S_IWOTH)
    # the read-only snapshot must not break subsequent appends (os.replace
    # renames over it — only directory perms matter)
    obs.append_bench("kernels", {**_kernel_entry(), "arch": "b"})
    entries = json.loads((tmp_path / "BENCH_kernels.json").read_text())
    assert [e["arch"] for e in entries] == ["a", "b"]
    assert json.loads(mirror.read_text()) == entries


def test_check_regressions_flags_only_regressed_rows(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    assert obs.check_regressions("kernels") == []   # nothing to compare
    obs.append_bench("kernels", {**_kernel_entry(1e-3, 1e-3), "arch": "a"})
    obs.append_bench("kernels",
                     {**_kernel_entry(1e-3, 1.5e-3), "arch": "b"})
    findings = obs.check_regressions("kernels", threshold=0.25)
    assert len(findings) == 1
    assert findings[0]["kernel"] == "quant_matmul_format"
    assert findings[0]["ratio"] == pytest.approx(1.5)
    assert obs.check_regressions("kernels", threshold=0.6) == []


def test_render_kernel_table_shows_roofline_and_serving():
    text = render_kernel_table([_kernel_entry()])
    assert "matmul_baseline" in text and "quant_matmul_format" in text
    assert "p50" in text and "p99" in text
    assert "prefill" in text
    # a second entry gets a Δprev column vs the first's matching rows
    text2 = render_kernel_table([_kernel_entry(1e-3, 1e-3),
                                 _kernel_entry(1e-3, 2e-3)])
    assert "+100%" in text2


def test_report_kernels_cli(tmp_path, monkeypatch, capsys):
    from repro.obs.__main__ import main

    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    obs.append_bench("kernels", _kernel_entry())
    assert main(["report", "--kernels"]) == 0
    out = capsys.readouterr().out
    assert "quant_matmul_format" in out and "p99" in out


def test_perfgate_cli_warns_and_exits_zero(tmp_path, monkeypatch, capsys):
    from repro.obs.__main__ import main

    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    assert main(["perfgate"]) == 0                 # empty trajectory: ok
    obs.append_bench("kernels", {**_kernel_entry(1e-3, 1e-3), "arch": "a"})
    obs.append_bench("kernels", {**_kernel_entry(1e-3, 2e-3), "arch": "b"})
    assert main(["perfgate", "--threshold", "0.25"]) == 0   # never fails
    out = capsys.readouterr().out
    assert "::warning::" in out and "quant_matmul_format" in out


def test_perfgate_fail_on_hard_rail(tmp_path, monkeypatch, capsys):
    from repro.obs.__main__ import main

    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    obs.append_bench("kernels", {**_kernel_entry(1e-3, 1e-3), "arch": "a"})
    obs.append_bench("kernels", {**_kernel_entry(1e-3, 2e-3), "arch": "b"})
    # +100% regression beyond the 50% rail → hard failure with ::error::
    assert main(["perfgate", "--threshold", "0.25",
                 "--fail-on", "0.5"]) == 1
    out = capsys.readouterr().out
    assert "::error::" in out and "quant_matmul_format" in out
    # the same regression under a higher rail stays a soft warning
    assert main(["perfgate", "--threshold", "0.25",
                 "--fail-on", "2.0"]) == 0
    out = capsys.readouterr().out
    assert "::warning::" in out and "::error::" not in out


# ---------------------------------------------------------------------------
# metrics details the serving digests rely on
# ---------------------------------------------------------------------------


def test_prometheus_label_escaping_and_labeled_buckets():
    reg = obs.MetricsRegistry()
    reg.counter('serve.requests{arch=qwen2_7b,mode=a"b}', 3)
    reg.observe('serve.decode_latency_s{arch=qwen2_7b}', 0.01)
    reg.observe('serve.decode_latency_s{arch=qwen2_7b}', 0.02)
    text = reg.render_prometheus()
    assert 'serve_requests{arch="qwen2_7b",mode="a\\"b"} 3' in text
    # labeled histogram series keep the _bucket suffix + cumulative counts
    acc = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
           if line.startswith("serve_decode_latency_s_bucket{")]
    assert acc and acc == sorted(acc) and acc[-1] == 2
    assert 'arch="qwen2_7b"' in text and 'le="+Inf"' in text
    assert 'serve_decode_latency_s_count{arch="qwen2_7b"} 2' in text
    # one # TYPE line per base metric name even with many label sets
    reg.observe('serve.decode_latency_s{arch=other}', 0.01)
    text = reg.render_prometheus()
    assert text.count("# TYPE serve_decode_latency_s histogram") == 1


def test_percentiles_clamped_into_observed_range():
    h = obs.Histogram("lat")
    for v in (0.011, 0.012, 0.013):
        h.observe(v)
    p = h.percentiles()
    assert set(p) == {"p50", "p95", "p99"}
    assert h.min <= p["p50"] <= p["p95"] <= p["p99"] <= h.max


@given(st.lists(st.floats(min_value=1e-6, max_value=1e3,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=64))
def test_percentile_digest_order_property(values):
    h = obs.Histogram("lat")
    for v in values:
        h.observe(v)
    p = h.percentiles()
    assert p["p50"] <= p["p95"] <= p["p99"]
    assert h.min <= p["p50"] and p["p99"] <= h.max
    assert math.isfinite(p["p99"])


# ---------------------------------------------------------------------------
# gauges recorded by the certify path
# ---------------------------------------------------------------------------


def test_ladder_compile_gauges_recorded():
    from repro.certify.batch import ProbeLadder, stack_class_ranges
    from repro.models import paper_models as PM

    params = PM.init_digits(jax.random.PRNGKey(0), d_in=12, h1=8, h2=6,
                            n_classes=4)
    x = stack_class_ranges([np.zeros(12)], [np.full(12, 0.1)])
    tr = obs.configure()
    ladder = ProbeLadder(PM.digits_forward, params, x)
    ladder(10)
    ladder(14)
    assert tr.gauges["ladder.uniform_compile_s"] > 0
    assert tr.gauges["ladder.uniform_jaxpr_eqns"] > 0


def test_aff_condense_counts_drops_when_traced():
    from repro.core.interval import AffineForm, aff_condense

    terms = jnp.stack([jnp.full((2,), 0.1 * (i + 1)) for i in range(6)])
    a = AffineForm(center=jnp.zeros((2,)), terms=terms,
                   ids=jnp.arange(1, 7, dtype=jnp.int32),
                   rad=jnp.zeros((2,)))
    tr = obs.configure()
    out = aff_condense(a, budget=2)
    assert out.budget == 2
    assert tr.counters["affine.condense_calls"] == 1
    assert tr.counters["affine.condense_drops"] == 4
    assert tr.gauges["affine.condense_drops"] == 4
