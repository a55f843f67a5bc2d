"""repro.obs: spans, JSONL schema, metrics, violation monitors (ISSUE 6).

The contract under test:

  * spans nest (depth/parent) and time monotonically (a child can never
    outlast its parent; seq reconstructs interleavings without the clock);
  * the JSONL trace round-trips through ``load_events`` and passes
    ``validate_events`` (the CI smoke gate), and malformed traces fail it;
  * all obs calls are no-ops with no tracer configured (the hot paths pay
    nothing by default);
  * the structured logger renders human-readable lines AND mirrors every
    record into the trace stream;
  * metrics: histogram math, Prometheus text exposition (cumulative
    buckets), JSONL snapshots;
  * violation monitors stay silent on in-distribution traffic and FIRE on
    out-of-enclosure input / an empirical error beyond δ̄ — and attaching
    one to a serving backend leaves the served values bitwise untouched;
  * probe ladders (uniform AND stacked scan-native) compile exactly once
    under tracing, and the trace says so.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Never leak a global tracer between tests (or into other modules)."""
    obs.shutdown()
    yield
    obs.shutdown()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_time_monotonically():
    tr = obs.configure()          # in-memory
    with obs.span("outer", stage=1):
        with obs.span("inner"):
            pass
        with obs.span("inner2"):
            pass
    spans = {e["name"]: e for e in tr.events if e["type"] == "span"}
    assert set(spans) == {"outer", "inner", "inner2"}
    assert spans["outer"]["depth"] == 0 and spans["outer"]["parent"] is None
    assert spans["inner"]["depth"] == 1
    assert spans["inner"]["parent"] == "outer"
    assert spans["inner2"]["parent"] == "outer"
    # children close before the parent and can never outlast it
    assert spans["inner"]["dur_s"] >= 0
    assert (spans["inner"]["dur_s"] + spans["inner2"]["dur_s"]
            <= spans["outer"]["dur_s"])
    assert spans["inner"]["seq"] < spans["inner2"]["seq"] < spans["outer"]["seq"]
    seqs = [e["seq"] for e in tr.events]
    assert seqs == sorted(seqs) == list(range(len(seqs)))


def test_span_set_and_rename_before_close():
    tr = obs.configure()
    with obs.span("probe", k=10) as sp:
        sp.set(result=3)
        sp.rename("compile")
    (sp_ev,) = [e for e in tr.events if e["type"] == "span"]
    assert sp_ev["name"] == "compile"
    assert sp_ev["attrs"] == {"k": 10, "result": 3}


def test_disabled_obs_calls_are_noops():
    assert not obs.enabled()
    sp = obs.span("anything", a=1)
    with sp as s:
        s.set(b=2)      # must not raise on the null span
        s.rename("x")
    obs.counter("c")
    obs.gauge("g", 1.0)
    obs.event("e", f=1)
    obs.flush()
    assert obs.get_tracer() is None


def test_span_is_the_null_span_with_no_tracer_and_the_profiler_off():
    from repro.obs import trace
    assert not obs.recording()
    assert obs.span("engine.step", a=1) is trace._NULL_SPAN
    with obs.span("engine.step") as sp:
        sp.set(queue=3)
    assert obs.get_tracer() is None


def _profiled(tmp_path, fn):
    """Host events named ``t.*`` recorded while ``fn`` runs under the JAX
    profiler: [(name, stats, start_ns, end_ns)]."""
    import glob
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(ev.name, dict(ev.stats), ev.start_ns,
             ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path[0]).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("t.")]


def test_spans_are_profiler_events_while_it_collects(tmp_path):
    def run():
        assert obs.recording() and not obs.enabled()
        with obs.span("t.outer", rid=7, n=1) as sp:
            sp.set(tokens=300)
            sp.rename("t.renamed")      # the profiler keeps the first name
            with obs.span("t.inner"):
                jnp.ones(4).block_until_ready()

    ev = {n: (st, s, e) for n, st, s, e in _profiled(tmp_path, run)}
    assert set(ev) == {"t.outer", "t.inner"}
    assert ev["t.outer"][0] == {"rid": 7, "n": 1, "tokens": 300}
    (_, s0, e0), (_, s1, e1) = ev["t.outer"], ev["t.inner"]
    assert s0 <= s1 <= e1 <= e0
    assert not obs.recording()


def test_tracer_spans_also_reach_the_profiler(tmp_path):
    tr = obs.configure()

    def run():
        with obs.span("t.both", k=2) as sp:
            sp.set(hit=1)

    ev = _profiled(tmp_path, run)
    assert [(n, st) for n, st, _, _ in ev] == [("t.both", {"k": 2,
                                                           "hit": 1})]
    (sp_ev,) = [e for e in tr.events if e["type"] == "span"]
    assert sp_ev["name"] == "t.both" and sp_ev["attrs"] == {"k": 2, "hit": 1}


def test_obs_trace_does_not_import_jax_at_load():
    import subprocess
    import sys
    path = os.path.join(os.path.dirname(obs.__file__), "trace.py")
    code = ("import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('t', {path!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "assert 'jax' not in sys.modules, 'jax imported at load'\n"
            "assert m.span('x') is m._NULL_SPAN\n")
    subprocess.run([sys.executable, "-c", code], check=True)


# ---------------------------------------------------------------------------
# JSONL schema round-trip
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_validates(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.configure(path=path, program="test", argv=["--x"])
    with obs.span("stage_a"):
        obs.event("hit", key="abc")
        obs.counter("store.hits", 2)
        obs.gauge("margin", 1.5)
    obs.shutdown()      # flushes counters/gauges and closes the file

    events = obs.load_events(path)
    assert obs.validate_events(events) == []
    assert events[0]["type"] == "meta"
    assert events[0]["schema"] == obs.SCHEMA
    assert events[0]["program"] == "test" and events[0]["argv"] == ["--x"]
    (counters,) = [e for e in events if e["type"] == "counters"]
    assert counters["values"] == {"store.hits": 2}
    (gauges,) = [e for e in events if e["type"] == "gauges"]
    assert gauges["values"] == {"margin": 1.5}


def test_validate_rejects_bad_events():
    assert obs.validate_events([]) == ["empty trace (no events)"]
    errs = obs.validate_events([
        {"type": "nonsense", "seq": 0},
        {"type": "meta", "schema": 99, "seq": 1},
        {"type": "span", "name": "x", "t": 0.0, "dur_s": -1.0,
         "depth": 0, "attrs": {}, "seq": 2},
        {"type": "span", "name": "y", "seq": "not-an-int"},
    ])
    assert any("unknown type" in e for e in errs)
    assert any("schema" in e for e in errs)
    assert any("negative span duration" in e for e in errs)
    assert any("seq" in e for e in errs)


def test_load_events_raises_on_malformed_jsonl(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"type": "meta", "schema": 1, "seq": 0}\n{oops\n')
    with pytest.raises(ValueError, match="malformed"):
        obs.load_events(str(p))


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------


def test_logger_renders_and_mirrors_to_trace(capfd):
    tr = obs.configure()
    log = obs.get_logger("testcomp")
    log.info("model trained", acc=0.93, steps=10)
    err = capfd.readouterr().err
    assert "[testcomp]" in err and "model trained" in err and "acc=0.93" in err
    (ev,) = [e for e in tr.events if e["type"] == "event"]
    assert ev["name"] == "log.testcomp"
    assert ev["fields"]["msg"] == "model trained"
    assert ev["fields"]["acc"] == 0.93


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_histogram_buckets_mean_quantile():
    h = obs.Histogram("lat")
    for v in (0.001, 0.002, 0.004, 0.2):
        h.observe(v)
    assert h.count == 4
    assert h.mean == pytest.approx(0.05175)
    assert h.min == 0.001 and h.max == 0.2
    assert h.quantile(0.5) <= h.quantile(0.99)
    assert sum(h.counts) == 4


def test_prometheus_exposition_cumulative(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("serve.requests", 3)
    reg.gauge("serve.tokens_per_s", 123.5)
    reg.observe("serve.decode_latency_s", 0.01)
    reg.observe("serve.decode_latency_s", 0.02)
    text = reg.render_prometheus()
    assert "# TYPE serve_requests counter\nserve_requests 3" in text
    assert "serve_tokens_per_s 123.5" in text
    assert "serve_decode_latency_s_count 2" in text
    # bucket counts are cumulative and end at +Inf == count
    acc = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
           if line.startswith("serve_decode_latency_s_bucket")]
    assert acc == sorted(acc) and acc[-1] == 2
    assert 'le="+Inf"' in text
    out = tmp_path / "m.prom"
    reg.write_prometheus(str(out))
    assert out.read_text() == text


def test_metrics_jsonl_snapshot(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("n", 1)
    reg.observe("lat", 0.5)
    path = str(tmp_path / "metrics.jsonl")
    reg.write_jsonl(path)
    reg.write_jsonl(path)       # appends — one snapshot per line
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["type"] == "metrics"
    assert lines[0]["counters"] == {"n": 1}
    assert lines[0]["histograms"]["lat"]["count"] == 1


# ---------------------------------------------------------------------------
# violation monitors
# ---------------------------------------------------------------------------


def test_monitor_silent_in_distribution_fires_out_of_enclosure():
    mon = obs.ViolationMonitor({"dense1": {"max_abs": 2.0}}, dbar_u=100.0,
                               u=2.0 ** -10)
    # in-distribution: inside the certified enclosure → no violations
    mon.observe_scope(["dense1"], {"max_abs": 1.5, "n_over": 0,
                                   "n_under": 0, "n_nonfinite": 0})
    assert mon.violations == 0
    assert mon.scope_margin["dense1"] == pytest.approx(math.log2(2.0 / 1.5))
    # under-certified input: observed magnitude above the proven enclosure
    mon.observe_scope(["dense1"], {"max_abs": 8.0, "n_over": 0,
                                   "n_under": 0, "n_nonfinite": 0})
    assert mon.counters["obs.enclosure_violations"] == 1
    assert mon.violations == 1
    assert mon.scope_margin["dense1"] == pytest.approx(math.log2(2.0 / 8.0))
    # overflow events against the certified format are violations by
    # themselves, even at in-enclosure magnitudes
    mon.observe_scope(["dense1"], {"max_abs": 1.0, "n_over": 3,
                                   "n_under": 0, "n_nonfinite": 0})
    assert mon.counters["obs.overflow_events"] == 3
    assert mon.counters["obs.enclosure_violations"] == 2
    # an unmapped scope only counts health events, never false-fires
    mon.observe_scope(["elsewhere"], {"max_abs": 1e9, "n_over": 0,
                                      "n_under": 0, "n_nonfinite": 0})
    assert mon.counters["obs.enclosure_violations"] == 2


def test_monitor_error_sample_against_dbar():
    mon = obs.ViolationMonitor({}, dbar_u=10.0, u=2.0 ** -10)
    mon.observe_error(4.0)
    assert mon.counters["obs.bound_violations"] == 0
    assert mon.error_margin_u() == pytest.approx(6.0)
    mon.observe_error(12.5)
    assert mon.counters["obs.bound_violations"] == 1
    assert mon.error_margin_u() == pytest.approx(-2.5)
    assert mon.worst_err_u == 12.5


def test_monitor_from_certificate_set_folds_layer_wildcard():
    class _CS:
        meta = {"formats": {"applied": True, "scope_ranges": {
            "": {"max_abs": 9.9},          # default scope: not addressable
            "layer0": {"max_abs": 2.0},
            "layer1": {"max_abs": 4.0},
            "head": {"max_abs": 1.0},
        }}}

        @staticmethod
        def error_bars():
            return {"dbar_u": 100.0, "u": 2.0 ** -12}

    mon = obs.ViolationMonitor.from_certificate_set(_CS())
    assert mon.envelopes["layer*"] == {"max_abs": 4.0}   # max over layers
    assert "" not in mon.envelopes
    # the scanned serving path observes under the stacked wildcard scope;
    # the loosest layer's enclosure bounds it (no false positives)
    mon.observe_scope(["layer*"], {"max_abs": 3.0})
    assert mon.violations == 0
    mon.observe_scope(["layer*"], {"max_abs": 40.0})
    assert mon.violations == 1
    # concrete scopes still resolve their own (tighter) envelope
    mon.observe_scope(["head"], {"max_abs": 1.5})
    assert mon.violations == 2


def test_monitor_layer_fold_merges_explicit_wildcard():
    """An explicit (narrow) layer* enclosure must be merge-maxed with the
    concrete layer folds, not trusted alone: the scanned serving path runs
    *every* layer under the wildcard scope, so its envelope has to cover
    the widest certified layer. Concrete layer<i> envelopes must stay
    untouched — neither widened nor shadowed by the fold."""
    class _CS:
        meta = {"formats": {"applied": True, "scope_ranges": {
            "layer0": {"max_abs": 1.0},
            "layer3": {"max_abs": 5.0},
            "layer*": {"max_abs": 2.0},
            "layer3/attn": {"max_abs": 0.5},
        }}}

        @staticmethod
        def error_bars():
            return {"dbar_u": 100.0, "u": 2.0 ** -12}

    mon = obs.ViolationMonitor.from_certificate_set(_CS())
    assert mon.envelopes["layer*"] == {"max_abs": 5.0}   # merge-max, not 2.0
    # observing layer3's certified magnitude under the wildcard path must
    # not false-positive against the stale explicit layer* entry
    mon.observe_scope(["layer*"], {"max_abs": 4.9})
    assert mon.violations == 0
    # the concrete layer3 envelope is not widened by the fold
    mon.observe_scope(["layer3"], {"max_abs": 5.2})
    assert mon.violations == 1
    # sub-layer keys fold into their own layer*/<sub> group
    assert mon.envelopes["layer*/attn"] == {"max_abs": 0.5}
    assert mon.envelopes["layer3/attn"] == {"max_abs": 0.5}
    mon.observe_scope(["layer*", "attn"], {"max_abs": 0.7})
    assert mon.violations == 2


def test_monitor_export_into_registry():
    mon = obs.ViolationMonitor({"blk": {"max_abs": 2.0}}, dbar_u=10.0)
    mon.observe_scope(["blk"], {"max_abs": 1.0})
    mon.observe_error(3.0)
    reg = obs.MetricsRegistry()
    mon.export(reg)
    assert reg.counters["obs.scope_observations"] == 1
    assert reg.counters["obs.enclosure_violations"] == 0
    assert reg.gauges["obs.bound_margin_log2{scope=blk}"] == pytest.approx(1.0)
    assert reg.gauges["obs.error_margin_u"] == pytest.approx(7.0)
    # idempotent re-export: counter deltas, not double counts
    mon.export(reg)
    assert reg.counters["obs.scope_observations"] == 1


def test_monitored_serving_backend_bitwise_identical_and_fires():
    """Attaching a ViolationMonitor must not change a single served bit,
    and must fire on input outside the certified enclosure."""
    from repro.launch.serve import QuantJOps

    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(4, 8), jnp.float32)
    w = jnp.asarray(rng.randn(8, 8) * 0.1, jnp.float32)

    def run(bk):
        with bk.scope("blk"):
            return bk.matmul(a, w)

    # k=12: inside the monitor slack's documented k >= 11 regime (the
    # envelope is measured on the QUANTIZED output; the monitor observes
    # the raw product, up to one ulp above it)
    base = np.asarray(run(QuantJOps(12, jnp.float32, jnp.float32)))
    mon = obs.ViolationMonitor({"blk": {"max_abs": float(np.abs(base).max())}})
    bk = QuantJOps(12, jnp.float32, jnp.float32)
    bk.monitor = mon
    monitored = np.asarray(run(bk))
    np.testing.assert_array_equal(base, monitored)
    assert mon.counters["obs.scope_observations"] == 1
    assert mon.violations == 0
    # inject out-of-enclosure traffic: magnitudes 1000x the certified range
    with bk.scope("blk"):
        bk.matmul(a * 1000.0, w)
    assert mon.counters["obs.enclosure_violations"] >= 1
    assert mon.violations >= 1


# ---------------------------------------------------------------------------
# compile-once under tracing
# ---------------------------------------------------------------------------


def _nano_digits():
    from repro.models import paper_models as PM

    params = PM.init_digits(jax.random.PRNGKey(0), d_in=12, h1=8, h2=6,
                            n_classes=4)
    lo = np.zeros(12)
    hi = np.full(12, 0.1)
    return PM.digits_forward, params, lo, hi


def test_uniform_ladder_compiles_once_under_tracing():
    from repro.certify.batch import ProbeLadder, stack_class_ranges

    forward, params, lo, hi = _nano_digits()
    x = stack_class_ranges([lo], [hi])
    tr = obs.configure()
    ladder = ProbeLadder(forward, params, x)
    for k in (10, 14, 18):
        ladder(k)
    assert ladder.compiles == 1
    assert tr.counters["ladder.compiles"] == 1
    names = [e["name"] for e in tr.events if e["type"] == "span"]
    assert names.count("ladder_compile") == 1
    assert names.count("ladder_probe") == 2
    (comp,) = [e for e in tr.events if e.get("name") == "ladder_compile"]
    assert comp["attrs"]["ladder"] == "uniform"


def test_stacked_mixed_ladder_compiles_once_under_tracing():
    """The scan-native per-layer ladder: every probe of every map — and the
    one-hot sensitivity probes — reuse ONE compiled executable, and the
    trace records exactly one ladder_compile span."""
    from repro.certify.mixed import MixedProbeLadder
    from repro.certify.batch import stack_class_ranges

    rng = np.random.RandomState(0)
    L, d = 2, 4
    params = {
        "layers": {"w": jnp.asarray(rng.randn(L, d, d) * 0.3, jnp.float32),
                   "b": jnp.zeros((L, d), jnp.float32)},
        "head": jnp.asarray(rng.randn(d, 3) * 0.3, jnp.float32),
    }

    def forward(ops, p, x):
        def body(lp, carry, i, aux):
            h = ops.add(ops.matmul(carry, ops.param(lp["w"])),
                        ops.param(lp["b"]))
            return ops.relu(h), None
        h, _ = ops.layer_loop(body, p["layers"], x, L)
        with ops.scope("head"):
            return ops.matmul(h, ops.param(p["head"]))

    x = stack_class_ranges([np.full(d, -0.5)], [np.full(d, 0.5)])
    tr = obs.configure()
    ladder = MixedProbeLadder(forward, params, x,
                              scope_keys=["layer0", "layer1", "head"],
                              stacked=True)
    ladder({"layer0": 12, "layer1": 12, "head": 12}, default_k=12)
    ladder({"layer0": 10, "layer1": 14, "head": 12}, default_k=12)
    ladder.sensitivity("layer1", at_k=12)
    assert ladder.compiles == 1
    assert tr.counters["ladder.compiles"] == 1
    names = [e["name"] for e in tr.events if e["type"] == "span"]
    assert names.count("ladder_compile") == 1
    assert names.count("ladder_probe") == 2


# ---------------------------------------------------------------------------
# report + bench
# ---------------------------------------------------------------------------


def test_report_renders_stage_table():
    from repro.obs import report

    tr = obs.configure()
    with obs.span("certify_run"):
        with obs.span("required_k_search"):
            with obs.span("ladder_probe", scope="dense1"):
                pass
        obs.counter("store.misses")
        obs.gauge("margin", 2.0)
    obs.flush()
    text = report.render(tr.events)
    assert "certify_run" in text and "required_k_search" in text
    assert "store.misses" in text and "margin" in text
    summ = report.summarize(tr.events)
    assert summ["root_total_s"] > 0
    assert summ["spans"]["required_k_search"]["count"] == 1


def test_bench_append_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_BENCH_DIR", str(tmp_path))
    assert obs.read_bench("runs") == []
    obs.append_bench("runs", {"kind": "certify", "arch": "a", "wall_s": 1.5})
    obs.append_bench("runs", {"kind": "certify", "arch": "b", "wall_s": 1.2})
    entries = obs.read_bench("runs")
    assert len(entries) == 2
    assert all("t" in e for e in entries)
    assert entries[1]["wall_s"] == 1.2
    # same identity fields in the same session → replace, not duplicate
    obs.append_bench("runs", {"kind": "certify", "arch": "b", "wall_s": 0.9})
    entries = obs.read_bench("runs")
    assert len(entries) == 2
    assert entries[1]["wall_s"] == 0.9
    # a non-array file is corrupt, not silently accepted
    (tmp_path / "BENCH_bad.json").write_text('{"not": "a list"}')
    with pytest.raises(ValueError):
        obs.read_bench("bad")
