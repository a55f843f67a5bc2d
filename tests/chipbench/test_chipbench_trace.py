"""The trace reduction, on a small trace recorded on a TPU v5e (made by
``chipbench/fixture_trace.py``) and on hand-made spans."""
import json
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "fixture.xplane.pb"


def test_innermost_spans_label_the_timeline():
    spans = [("step", 0, 100), ("decode", 10, 40), ("prefill", 50, 60),
             ("record", 100, 120), ("idle_wait", 130, 200)]
    seg = trace._innermost(spans)
    assert trace._labels(seg, [5, 20, 45, 55, 110, 125, 150, 300]) == [
        "step", "decode", "step", "prefill", "record", "none", "idle_wait",
        "none"]


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]


def test_program_names_are_stable():
    assert trace.program_name("jit_decode_step(123)") == "decode_step"
    assert trace.program_name("jit_prefill_step") == "prefill_step"


def test_top_orders_by_seconds():
    assert trace.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                            ["c", 2.0]]


@pytest.fixture(scope="module")
def reduced():
    kernels = json.loads((DATA / "fixture_kernels.json").read_text())
    return trace.reduce(str(FIXTURE), kernels)


def test_fixture_reduces_as_recorded(reduced):
    want = json.loads((DATA / "fixture.json").read_text())
    assert reduced["window_s"] == pytest.approx(want["window_s"])
    assert reduced["busy_s"] == pytest.approx(want["busy_s"])
    assert reduced["kernels"] == pytest.approx(want["kernels"])
    assert {k: v["n"] for k, v in reduced["programs"].items()} == {
        k: v["n"] for k, v in want["programs"].items()}


def test_fixture_busy_time_is_a_union_inside_the_window(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["chips"] == 1
    idle = sum(reduced["idle"].values())
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-6)


def test_fixture_finds_the_programs_and_both_kernels(reduced):
    progs = reduced["programs"]
    assert progs["decode_step"]["n"] > 0
    assert "prefill_step" in progs
    k = reduced["kernels"]
    assert k["decode_step/quant_matmul_format"] > 0
    assert k["decode_step/flash_decode"] > 0
    assert k["decode_step/quant_matmul_format"] <= progs["decode_step"]["s"]


def test_fixture_idle_gaps_are_labelled_by_host_spans(reduced):
    assert set(reduced["idle"]) - {"none"}
    assert set(reduced["idle"]) <= {"none", "step", "decode", "prefill",
                                    "insert", "record", "idle_wait"}
