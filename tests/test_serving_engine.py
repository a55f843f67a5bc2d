"""Continuous-batching engine: scheduling, metrics, and the bitwise oracle.

The load-bearing claim: a request served through the mesh-sharded,
continuously-batched engine produces EXACTLY the tokens of running that
request alone through the single-device eager reference (unrolled
per-layer backend, unpadded batch-1 prefill). Staggered arrivals, lane
recycling, page-padded prefills and idle-lane junk must all be invisible
— per-lane rows of every op are bitwise independent of batch composition.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, obs
from repro.launch import mesh as meshlib
from repro.launch import serve
from repro.launch.batching import (ContinuousBatchingEngine, Request,
                                   make_backend, reference_generate)
from repro.models import transformer as T

CFG = configs.get("qwen2_7b").SMOKE


@pytest.fixture(scope="module")
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def _requests(n, seed=0, plen_lo=5, plen_hi=12, max_new=5, stride=1):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, CFG.vocab,
                                       rng.randint(plen_lo, plen_hi + 1)
                                       ).tolist(),
                    max_new_tokens=max_new, arrival_step=i * stride)
            for i in range(n)]


PAGE = 8      # every engine below pages its cache in 8-token pages


def _assert_matches_reference(sc, params, responses, reqs, max_seq):
    for req in reqs:
        got = next(r["tokens"] for r in responses if r["id"] == req.rid)
        want = reference_generate(CFG, sc, params, req.prompt,
                                  req.max_new_tokens, max_seq=max_seq,
                                  page_size=PAGE)
        assert got == want, (req.rid, got, want)


def test_staggered_arrivals_match_reference_plain(params):
    sc = serve.ServeConfig(arch="qwen2_7b", batch=3, max_seq=48)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=3, max_seq=48,
                                   page_size=8, queue_depth=8)
    reqs = _requests(5, stride=2)
    responses = eng.run(reqs)
    assert len(responses) == 5
    _assert_matches_reference(sc, params, responses, reqs, 48)


def test_mixed_certificate_matches_reference(params):
    """Per-layer k map (v2-style) through the scanned lane machinery,
    including a sub-layer key."""
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=48,
                           precision_k=12,
                           precision_layer_k={"layer0": 9,
                                              "layer1/mlp": 10})
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=48,
                                   page_size=8, queue_depth=8)
    reqs = _requests(3, seed=1)
    responses = eng.run(reqs)
    assert len(responses) == 3
    _assert_matches_reference(sc, params, responses, reqs, 48)


def test_format_certificate_matches_reference(params):
    """Per-scope format map (v3-style) — wildcard layer*/attn sub-lane and
    a concrete layer key — served through FormatQuantJOps + the certified
    flash-decode hook; bitwise against the unrolled eager reference."""
    fmt = {"": {"k": 11, "emax": 15, "emin": -14},
           "layer*/attn": {"k": 8, "emax": 15, "emin": -14},
           "layer1": {"k": 9, "emax": 15, "emin": -14}}
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=48,
                           precision_layer_format=fmt)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=48,
                                   page_size=8, queue_depth=8)
    reqs = _requests(3, seed=2)
    responses = eng.run(reqs)
    assert len(responses) == 3
    _assert_matches_reference(sc, params, responses, reqs, 48)


def test_format_fused_decode_actually_engages(params, monkeypatch):
    """The certified flash-decode hook must be exercised, not silently
    skipped: every decode step of a format-certified serve must route
    attention through ``certified_decode_attention`` (prefill, Sq > 1,
    legitimately takes the composed path)."""
    from repro.kernels import flash_decode as fd

    calls = []
    real = fd.certified_decode_attention

    def spy(q, k, v, lengths, fmt, **kw):
        calls.append(q.shape)
        return real(q, k, v, lengths, fmt, **kw)

    monkeypatch.setattr(fd, "certified_decode_attention", spy)
    fmt = {"": {"k": 5, "emax": 15, "emin": -14}}
    sc = serve.ServeConfig(arch="qwen2_7b", batch=1, max_seq=48,
                           precision_layer_format=fmt)
    prompt = list(np.random.RandomState(3).randint(0, CFG.vocab, 6))
    out = reference_generate(CFG, sc, params, prompt, 6, max_seq=48,
                             page_size=PAGE)
    assert len(out) == 6
    # eager unrolled reference: one hook call per layer per decode step
    assert len(calls) == CFG.n_layers * (len(out) - 1)


def test_lane_recycling_and_page_accounting(params):
    """More requests than lanes: lanes recycle, pages return to the pool,
    and every request still completes bit-identically."""
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=32)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=32,
                                   page_size=8, queue_depth=10)
    assert eng.free_pages == eng.total_pages == 8
    reqs = _requests(6, seed=4, max_new=3, stride=0)
    responses = eng.run(reqs)
    assert len(responses) == 6
    assert eng.free_pages == eng.total_pages          # all pages returned
    assert all(l is None for l in eng.lanes)
    _assert_matches_reference(sc, params, responses, reqs, 32)


def test_eos_recycles_lane_early(params):
    sc = serve.ServeConfig(arch="qwen2_7b", batch=1, max_seq=48)
    prompt = list(np.random.RandomState(5).randint(0, CFG.vocab, 6))
    free_run = reference_generate(CFG, sc, params, prompt, 8, max_seq=48,
                                  page_size=PAGE)
    # a token the model will actually emit, first emitted mid-stream
    stop = next(i for i in range(1, len(free_run))
                if free_run[i] not in free_run[:i])
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=1, max_seq=48,
                                   page_size=8, eos_id=free_run[stop])
    [resp] = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=8)])
    assert resp["tokens"] == free_run[:stop + 1]      # stopped AT the eos
    assert len(resp["tokens"]) < 8
    assert eng.free_pages == eng.total_pages


def test_admission_rejection_and_queue_bound(params):
    sc = serve.ServeConfig(arch="qwen2_7b", batch=1, max_seq=32)
    reg = obs.MetricsRegistry()
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=1, max_seq=32,
                                   page_size=8, queue_depth=2, registry=reg)
    # can never fit: prompt + max_new exceeds max_seq
    assert not eng.submit(Request(rid=0, prompt=[1] * 30,
                                  max_new_tokens=10))
    # queue bound: two fit, the third bounces
    assert eng.submit(Request(rid=1, prompt=[1] * 4, max_new_tokens=2))
    assert eng.submit(Request(rid=2, prompt=[1] * 4, max_new_tokens=2))
    assert not eng.submit(Request(rid=3, prompt=[1] * 4, max_new_tokens=2))
    assert reg.counters["serve.requests_rejected{reason=too_long}"] == 1
    assert reg.counters["serve.requests_rejected{reason=queue_full}"] == 1
    responses = eng.run([])
    assert {r["id"] for r in responses} == {1, 2}


def test_gauges_and_per_lane_histograms(params):
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=32)
    reg = obs.MetricsRegistry()
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=32,
                                   page_size=8, registry=reg)
    for r in _requests(2, seed=6, max_new=3, stride=0):
        assert eng.submit(r)
    eng.step()
    assert reg.gauges["serve.batch_occupancy"] == 1.0
    assert reg.gauges["serve.admission_queue_depth"] == 0.0
    eng.run([])
    assert reg.gauges["serve.batch_occupancy"] == 0.0
    # one observation per decode step, not one per lane
    assert reg.histograms["serve.decode_latency_s"].count == eng.steps >= 2
    assert not [k for k in reg.histograms if "lane=" in k]
    assert reg.counters["serve.requests_completed"] == 2
    # a labelled series renders with a proper Prometheus label
    assert not eng.submit(Request(rid=9, prompt=[1] * 40, max_new_tokens=1))
    prom = reg.render_prometheus()
    assert 'serve_requests_rejected{reason="too_long"} 1' in prom


def test_responses_carry_certificate_bars(params):
    class _FakeCertSet:
        params_digest = "deadbeef"

        def error_bars(self):
            return {"dbar": 1.5e-3, "ebar": 2.0e-4, "k": 12}

    sc = serve.ServeConfig(arch="qwen2_7b", batch=1, max_seq=32,
                           precision_k=12)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=1, max_seq=32,
                                   page_size=8, certset=_FakeCertSet())
    responses = eng.run(_requests(2, seed=7, max_new=2, stride=0))
    assert len(responses) == 2
    for r in responses:
        assert r["certificate"]["k"] == 12
        assert r["certificate"]["dbar"] == 1.5e-3
        assert r["certificate"]["params_digest"] == "deadbeef"


def test_pad_contents_do_not_reach_real_rows(params):
    """The linchpin of batched prefill-insert: what sits in the pad columns
    of a page-padded prompt must not reach the real rows' logits nor the
    first P cache positions — causal masking turns pad columns into exact
    zeros, so two paddings of one prompt agree bitwise. Against the
    UNPADDED prompt the agreement is to f32 rounding only: XLA:CPU blocks
    a 6-row and a 16-row GEMM differently, which is why the engine's
    reference prefills at the engine's own padded shape."""
    bk = make_backend(serve.ServeConfig(arch="qwen2_7b", batch=1,
                                        max_seq=32))
    rng = np.random.RandomState(8)
    toks = rng.randint(0, CFG.vocab, 6)
    zero_pad = np.zeros(16, np.int32)
    zero_pad[:6] = toks
    junk_pad = rng.randint(0, CFG.vocab, 16).astype(np.int32)
    junk_pad[:6] = toks
    z = jnp.zeros((1,), jnp.int32)

    def prefill(t):
        c = T.init_cache(CFG, 1, 32, jnp.float32, per_lane_idx=True)
        return T.forward(bk, params, CFG, jnp.asarray(t[None]), cache=c,
                         q_offset=z)

    lg1, c1 = prefill(zero_pad)
    lg2, c2 = prefill(junk_pad)
    assert bool(jnp.array_equal(lg1[0, :6], lg2[0, :6]))
    assert bool(jnp.array_equal(c1["k"][:, :, :, :6], c2["k"][:, :, :, :6]))
    assert bool(jnp.array_equal(c1["v"][:, :, :, :6], c2["v"][:, :, :, :6]))
    lg0, c0 = prefill(toks)
    np.testing.assert_allclose(lg0[0], lg1[0, :6], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c0["k"][:, :, :, :6], c1["k"][:, :, :, :6],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c0["v"][:, :, :, :6], c1["v"][:, :, :, :6],
                               rtol=1e-5, atol=1e-6)


def test_engine_on_explicit_mesh(params):
    """Whatever devices exist, the engine accepts a mesh and the sharded
    run stays bitwise against the meshless eager reference (CI's
    forced-host 4-device job exercises the >1-device case)."""
    mesh = meshlib.make_serving_mesh()
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=32,
                           precision_k=11)
    eng = ContinuousBatchingEngine(CFG, sc, params, mesh=mesh, n_lanes=2,
                                   max_seq=32, page_size=8)
    reqs = _requests(3, seed=9, max_new=3)
    responses = eng.run(reqs)
    assert len(responses) == 3
    _assert_matches_reference(sc, params, responses, reqs, 32)


def test_plain_reference_matches_backend_forward(params):
    """The plain f32 reference the chip smoke compares against computes
    the same function as the backend-generic transformer (no cache)."""
    from repro.models.reference import dense_forward
    toks = jnp.asarray(np.random.RandomState(11).randint(0, CFG.vocab,
                                                         (2, 12)))
    want, _ = T.forward(make_backend(serve.ServeConfig()), params, CFG, toks)
    got = dense_forward(params, CFG, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_kept_logits_match_plain_reference(params):
    """keep_logits: row t of a response's logits is the distribution its
    token t was taken from — prefill row, then decode rows — and agrees
    with the plain reference run teacher-forced on the served tokens."""
    from repro.models.reference import dense_forward
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=48)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=48,
                                   page_size=PAGE, keep_logits=True)
    reqs = _requests(3, seed=12, max_new=4)
    for r in eng.run(reqs):
        req = reqs[r["id"]]
        assert r["logits"].shape == (len(r["tokens"]), CFG.vocab)
        assert list(np.argmax(r["logits"], axis=1)) == r["tokens"]
        seq = list(req.prompt) + r["tokens"][:-1]
        ref = dense_forward(params, CFG, jnp.asarray([seq]))[0]
        P = len(req.prompt)
        np.testing.assert_allclose(r["logits"], np.asarray(ref[P - 1:]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# engine spans and the model's named scopes
# ---------------------------------------------------------------------------

ENGINE_SPANS = {"engine.step", "engine.admit", "engine.prefill",
                "engine.insert", "engine.first_token", "engine.schedule",
                "engine.decode", "engine.decode_wait", "engine.readback",
                "engine.bookkeep"}


def _served(params, traced: bool):
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=48)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=48,
                                   page_size=8, queue_depth=8,
                                   keep_logits=True)
    tracer = obs.configure() if traced else None
    try:
        responses = eng.run(_requests(3, seed=4, max_new=4))
    finally:
        obs.shutdown()
    return responses, tracer


def test_engine_spans_nest_with_their_attributes(params):
    responses, tracer = _served(params, traced=True)
    spans = [e for e in tracer.events if e["type"] == "span"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    assert set(by) == ENGINE_SPANS
    assert all(e["parent"] == "engine.step" for e in by["engine.admit"])
    for child in ("engine.prefill", "engine.insert", "engine.first_token"):
        assert all(e["parent"] == "engine.admit" for e in by[child])
        assert len(by[child]) == len(by["engine.admit"]) == 3
    for name in ("engine.schedule", "engine.decode", "engine.decode_wait",
                 "engine.readback", "engine.bookkeep"):
        assert all(e["parent"] == "engine.step" for e in by[name])
    assert sorted(e["attrs"]["rid"] for e in by["engine.admit"]) == [0, 1, 2]
    for e in by["engine.admit"]:
        a = e["attrs"]
        assert a["n"] == 1 and a["lane"] in (0, 1)
        assert 5 <= a["tokens"] <= 12 and a["padded"] == 8 * -(
            -a["tokens"] // 8)
    assert sum(e["attrs"]["admitted"] for e in by["engine.step"]) == 3
    assert all("queue" in e["attrs"] for e in by["engine.step"])
    # decode attrs: active lanes, and the positions they attend
    first = by["engine.decode"][0]["attrs"]
    assert 1 <= first["lanes"] <= 2 and first["kv"] >= first["lanes"]
    assert (sum(e["attrs"]["lanes"] for e in by["engine.decode"])
            == sum(len(r["tokens"]) - 1 for r in responses))


def test_engine_output_identical_with_tracing_on_and_off(params):
    off, _ = _served(params, traced=False)
    on, _ = _served(params, traced=True)
    assert [r["id"] for r in on] == [r["id"] for r in off]
    for a, b in zip(on, off):
        assert a["tokens"] == b["tokens"]
        np.testing.assert_array_equal(a["logits"], b["logits"])


def test_engine_spans_reach_the_profiler_with_stats(params, tmp_path):
    import glob
    from jax.profiler import ProfileData
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=48)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=48,
                                   page_size=8, queue_depth=8)
    eng.run(_requests(1, seed=5, max_new=2))          # compile outside
    assert not obs.recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.recording() and not obs.enabled()
        eng.run(_requests(2, seed=5, max_new=3))
    finally:
        jax.profiler.stop_trace()
    assert not obs.recording()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [ev for plane in ProfileData.from_file(path[0]).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    assert {ev.name for ev in events} == ENGINE_SPANS
    admits = [dict(ev.stats) for ev in events if ev.name == "engine.admit"]
    assert sorted(a["rid"] for a in admits) == [0, 1]
    assert all(a["n"] == 1 and a["padded"] % 8 == 0 for a in admits)
    dec = [dict(ev.stats) for ev in events if ev.name == "engine.decode"]
    assert all(d["lanes"] >= 1 and d["kv"] >= d["lanes"] for d in dec)


@pytest.mark.parametrize("fmt", [None, {"": {"k": 11, "emax": 15,
                                             "emin": -14}}])
def test_compiled_decode_names_the_model_scopes(params, fmt):
    import re
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=32,
                           precision_layer_format=fmt)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=32,
                                   page_size=8)
    lanes = jnp.zeros((2,), jnp.int32)
    text = eng._decode.lower(eng.params, eng.cache, lanes,
                             lanes).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    segs = [p.split("/") for p in paths]
    for scope in ("embed", "attn", "mlp", "head"):
        assert any(scope in s for s in segs), scope
    # sub-layer scopes sit inside the scanned layer body, under layer*
    for scope in ("attn", "mlp"):
        assert any(s[s.index("layer*"):s.index("layer*") + 3]
                   == ["layer*", "while", "body"]
                   for s in segs if scope in s and "layer*" in s), scope


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested jaxprs included."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    yield from _scans(sub)


@pytest.mark.parametrize("fmt", [None, {"": {"k": 11, "emax": 15,
                                             "emin": -14}}])
def test_decode_threads_the_cache_through_the_scan_carry(params, fmt):
    """The decode program updates the stacked cache in place: its K and V
    enter the layer scan as carry, and no scan output or zero fill has a
    stacked cache leaf's shape. (A cache passed to the scan as input and
    output is sliced per layer, rewritten whole into zero-filled stacked
    outputs and copied out, every step.)"""
    sc = serve.ServeConfig(arch="qwen2_7b", batch=2, max_seq=32,
                           precision_layer_format=fmt)
    eng = ContinuousBatchingEngine(CFG, sc, params, n_lanes=2, max_seq=32,
                                   page_size=8)
    lanes = jnp.zeros((2,), jnp.int32)
    args = (eng.params, eng.cache, lanes, lanes)
    (call,) = jax.make_jaxpr(eng._decode)(*args).jaxpr.eqns
    program = call.params["jaxpr"].jaxpr
    n_params = len(jax.tree_util.tree_leaves(eng.params))
    cache_in = dict(zip(sorted(eng.cache), program.invars[n_params:]))
    stacked = {tuple(eng.cache[n].shape) for n in ("k", "v")}

    scans = list(_scans(program))
    layer_scans = [e for e in scans if e.params["length"] == CFG.n_layers]
    assert len(layer_scans) == 1
    (scan,) = layer_scans
    c0 = scan.params["num_consts"]
    carry = scan.invars[c0:c0 + scan.params["num_carry"]]
    for name in ("k", "v"):
        assert any(v is cache_in[name] for v in carry), name
    carried = {(tuple(v.aval.shape), v.aval.dtype) for v in carry}
    for name, leaf in eng.cache.items():
        assert (tuple(leaf.shape), leaf.dtype) in carried, name
    for e in scans:
        ys = e.outvars[e.params["num_carry"]:]
        assert not {tuple(v.aval.shape) for v in ys} & stacked

    text = eng._decode.lower(*args).as_text()
    for shape in stacked:
        ty = "x".join(map(str, shape))
        assert not [ln for ln in text.splitlines()
                    if "broadcast_in_dim" in ln and f"tensor<{ty}x" in ln]
