"""The program's spans and scopes in a trace (``chipbench/spans.py``): the
model-scope map of compiled text, the proportional split of idle time over
host spans, the ``engine.*`` span sums and the per-step readings, on
hand-made input and on a small chip trace of the engine with its spans
(made by ``chipbench/fixture_spans.py`` into ``data/spans``)."""
import json
import pathlib

import pytest

from chipbench import spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "spans"
ENGINE = {"engine.step", "engine.admit", "engine.prefill", "engine.insert",
          "engine.first_token", "engine.schedule", "engine.decode",
          "engine.decode_wait", "engine.readback", "engine.bookkeep"}

HLO = """\
%layer_body (arg.1: f32[8]) -> f32[8] {
  %arg.1 = f32[8]{0} parameter(0)
  %dot.2 = f32[8]{0} dot(%arg.1, %arg.1), metadata={op_name="jit(decode_step)/layer*/while/body/closed_call/attn/dot_general" source_file="a.py"}
  %dynamic-slice_bitcast_fusion.3 = f32[8]{0} fusion(%arg.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode_step)/layer*/while/body/dynamic_slice"}
  %slice.4 = f32[8]{0} slice(%arg.1), slice={[0:8]}
  ROOT %closed_call.5 = f32[8]{0} custom-call(%slice.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/layer*/while/body/closed_call/mlp/pallas_call"}
}

%scatter_body (arg.6: f32[8]) -> f32[8] {
  %arg.6 = f32[8]{0} parameter(0)
  ROOT %fusion.7 = f32[8]{0} fusion(%arg.6), kind=kLoop, calls=%fused_computation.2
}

ENTRY %main.9 (p.10: f32[8]) -> f32[8] {
  %p.10 = f32[8]{0} parameter(0), metadata={op_name="params['layers']['attn']['wq']"}
  %fusion.11 = f32[8]{0} fusion(%p.10), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(decode_step)/jit(decode_fn)/embed/jit(_take)/gather"}
  %broadcast.12 = f32[8]{0} broadcast(%constant.13), dimensions={}, metadata={op_name="jit(decode_step)/jit(decode_fn)"}
  %while.14 = f32[8]{0} while(%fusion.11), condition=%layer_cond, body=%layer_body, metadata={op_name="jit(decode_step)/jit(decode_fn)/layer*/while"}
  %while.15 = f32[8]{0} while(%broadcast.12), condition=%scatter_cond, body=%scatter_body, metadata={op_name="jit(decode_step)/layer*/while/body/closed_call/attn/vmap(vmap())/scatter"}
  %get-tuple-element.16 = f32[8]{0} get-tuple-element(%while.14), index=0
  %copy.17 = f32[8]{0} copy(%get-tuple-element.16)
  %fusion.18 = f32[8]{0} fusion(%copy.17), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(decode_step)/head/attn_sink/mlp/reduce"}
  ROOT %argmax.19 = s32[] reduce(%fusion.18), to_apply=%region_1, metadata={op_name="jit(decode_step)/argmax"}
}
"""


def test_scope_map_takes_the_innermost_model_scope_of_each_op_name():
    assert spans.scope_map(HLO) == {
        "arg.1": "loop", "dot.2": "attn",
        "dynamic-slice_bitcast_fusion.3": "loop", "slice.4": "loop",
        "closed_call.5": "mlp",
        "arg.6": "attn", "fusion.7": "attn",     # the scatter loop's body
        "p.10": "other", "fusion.11": "embed", "broadcast.12": "other",
        "while.14": "loop", "while.15": "attn",
        "get-tuple-element.16": "loop", "copy.17": "loop",   # scan result
        "fusion.18": "mlp", "argmax.19": "other"}


def test_idle_split_in_proportion_sums_to_the_gaps():
    host = [("step", 0, 100), ("engine.step", 5, 95),
            ("engine.decode", 10, 40), ("engine.readback", 60, 70),
            ("record", 100, 120)]
    seg = trace._innermost(host)
    busy = [(20, 45), (90, 110)]
    lo, hi = 0, 150
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    got = spans.split(seg, gaps)
    assert got == pytest.approx({
        "step": 5, "engine.step": 5 + 15 + 20, "engine.decode": 10,
        "engine.readback": 10, "record": 10, "none": 30})
    assert sum(got.values()) == pytest.approx(
        hi - lo - sum(e - s for s, e in busy))
    # a gap inside one span is that span's whole
    assert spans.split(seg, [(61, 69)]) == {"engine.readback": 8}


def test_engine_spans_sum_seconds_counts_and_numeric_stats():
    events = [("engine.decode", 100, 200, [("lanes", 3), ("kv", 40)]),
              ("engine.decode", 300, 500, [("lanes", 2), ("kv", 30),
                                           ("tag", "x")]),
              ("engine.decode", 50, 90, [("lanes", 9)]),      # before lo
              ("engine.admit", 450, 700, [("n", 1), ("ok", True)]),
              ("step", 100, 600, [])]                        # the harness's
    got = spans.engine_spans(events, 100, 600)
    assert set(got) == {"engine.decode", "engine.admit"}
    assert got["engine.decode"]["s"] == pytest.approx(300e-9)
    assert got["engine.decode"]["count"] == 2
    assert got["engine.decode"]["sums"] == {"lanes": 5, "kv": 70}
    assert got["engine.admit"]["s"] == pytest.approx(150e-9)   # clipped
    assert got["engine.admit"]["sums"] == {"n": 1}


def _reduced(named=True):
    span = lambda s, count, **sums: {"s": s, "count": count, "sums": sums}
    return {
        "window_s": 1.0, "chips": 1, "runs": {"decode_step": 20.0},
        "scopes": ({"decode_step/attn": 0.2, "decode_step/mlp": 0.3,
                    "decode_step/loop": 0.08, "decode_step/other": 0.01}
                   if named else {"decode_step/loop": 0.59,
                                  "decode_step/other": 0.01}),
        "spans": {"engine.step": span(0.9, 20, queue=640, admitted=4),
                  "engine.admit": span(0.2, 4, n=4, tokens=1200),
                  "engine.decode": span(0.01, 20, lanes=640, kv=9000),
                  "engine.decode_wait": span(0.62, 20)},
        "idle": {"engine.readback": 0.05, "engine.schedule": 0.03,
                 "record": 0.1, "step": 0.01, "none": 0.01},
    }


def test_readings_on_a_hand_made_reduction():
    assert spans.readings(_reduced()) == pytest.approx({
        "engine_admit_ms": 1e3 * 0.2 / 4,
        "engine_host_ms_step": 1e3 * (0.9 - 0.2 - 0.01 - 0.62) / 20,
        "engine_idle_ms_step": 1e3 * 0.08 / 20,
        "decode_attn_ms": 10.0, "decode_mlp_ms": 15.0,
        "decode_loop_ms": 4.0})


def test_readings_are_none_for_a_program_without_spans_or_scopes():
    got = spans.readings(dict(_reduced(named=False), spans={}))
    assert got == dict.fromkeys(got, None) and len(got) == 6


def test_a_trace_without_engine_spans_or_scopes_reads_nothing_new():
    # the older chip fixture: harness spans only, no named scopes
    path = str(DATA.parent / "fixture.xplane.pb")
    red, base = spans.reduce(path, {"decode_step": {}}), trace.reduce(path)
    assert red["runs"] == {k: v["n"] for k, v in base["programs"].items()}
    assert set(red["scopes"]) == {"decode_step/other"}
    assert red["spans"] == {}
    assert set(red["idle"]) <= {"none", "step", "decode", "prefill",
                                "insert", "record", "idle_wait"}
    assert sum(red["idle"].values()) + base["busy_s"] == pytest.approx(
        base["window_s"], rel=1e-6)
    assert set(spans.readings(red).values()) == {None}


@pytest.fixture(scope="module")
def recorded():
    path = str(DATA / "fixture.xplane.pb")
    scopes = json.loads((DATA / "fixture_scopes.json").read_text())
    return spans.reduce(path, scopes), trace.reduce(path)


def test_fixture_reduces_as_recorded(recorded):
    red, _ = recorded
    want = json.loads((DATA / "fixture_spans.json").read_text())
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["runs"] == want["runs"]
    assert red["scopes"] == pytest.approx(want["scopes"])
    assert red["idle"] == pytest.approx(want["idle"])
    assert {k: v["count"] for k, v in red["spans"].items()} == {
        k: v["count"] for k, v in want["spans"].items()}


def test_fixture_holds_every_engine_span(recorded):
    sp = recorded[0]["spans"]
    assert set(sp) == ENGINE
    assert sp["engine.decode"]["sums"]["lanes"] >= sp["engine.decode"][
        "count"]
    assert sp["engine.admit"]["sums"]["n"] == sp["engine.admit"]["count"]
    assert sp["engine.admit"]["sums"]["tokens"] > 0
    assert sp["engine.step"]["s"] >= sum(
        sp[k]["s"] for k in spans.NOT_HOST)


def test_fixture_scopes_fit_inside_the_decode_program(recorded):
    red, base = recorded
    decode = {k.split("/", 1)[1]: v for k, v in red["scopes"].items()
              if k.startswith("decode_step/")}
    assert {"attn", "mlp", "head", "loop"} <= set(decode)
    assert sum(decode.values()) <= base["programs"]["decode_step"]["s"]
    assert red["runs"]["decode_step"] == base["programs"]["decode_step"]["n"]
    got = spans.readings(red)
    assert all(v is not None and v > 0 for v in got.values()), got


def test_fixture_idle_is_split_over_engine_spans(recorded):
    red, base = recorded
    idle = red["idle"]
    assert {k for k in idle if k.startswith("engine.")}
    assert sum(idle.values()) + base["busy_s"] == pytest.approx(
        base["window_s"], rel=1e-6)
