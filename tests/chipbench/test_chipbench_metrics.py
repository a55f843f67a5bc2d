"""End-to-end metrics are taken over every request and the whole window."""
import types

import numpy as np
import pytest

from chipbench import driver, spec


def _record(loop, reqs, t_open=10.0, t_close=20.0):
    cell = types.SimpleNamespace(traffic={"loop": loop})
    rec = driver.Record(cell=cell, arch={}, fmt_map=None, seconds=0.0)
    rec.t_open, rec.t_close, rec.t_end = t_open, t_close, t_close + 5
    for i, (times, due, measured) in enumerate(reqs):
        rec.reqs[i] = driver.Req(i, [1], 1, measured, due=due,
                                 times=list(times))
    return rec


def read(name, rec):
    return spec.metric_reader(name)(rec)


def test_out_tok_s_counts_every_token_of_the_window_over_its_length():
    rec = _record("backlog", [
        (np.arange(8.0, 22.0, 1.0), None, True),     # 11 in [10, 20]
        (np.arange(15.0, 16.0, 0.1), None, False),   # 10, all in window
    ])
    assert read("out_tok_s", rec) == pytest.approx(21 / 10.0)


def test_itl_tail_is_over_every_gap_not_a_median_of_chunks():
    # 9 requests with 1 ms gaps and one with 100 ms gaps: a median over
    # requests of per-request tails would say 1 ms
    reqs = [(10.0 + 0.001 * np.arange(50), None, True) for _ in range(9)]
    reqs.append((10.0 + 0.1 * np.arange(50), None, True))
    rec = _record("backlog", reqs)
    gaps = np.concatenate([np.diff(r[0]) for r in reqs])
    assert read("itl_p95_ms", rec) == pytest.approx(
        1e3 * np.percentile(gaps, 95))
    assert read("itl_p95_ms", rec) > 50.0


def test_backlog_gaps_are_those_ending_in_the_window():
    rec = _record("backlog", [([9.0, 9.5, 10.5, 21.0], None, True)])
    assert sorted(rec.gaps()) == [1.0]


def test_open_loop_gaps_and_ttft_cover_every_measured_request():
    rec = _record("open", [
        ([11.0, 11.5, 25.0], 10.8, True),   # gaps after the window count
        ([12.0, 12.1], 11.0, True),
        ([5.0, 5.1], 4.0, False),           # lead-in: not measured
        ([], 19.0, True),                   # never served: waits to t_end
    ])
    assert sorted(rec.gaps()) == pytest.approx([0.1, 0.5, 13.5])
    waits = [0.2, 1.0, 25.0 - 19.0]
    assert read("ttft_p90_ms", rec) == pytest.approx(
        1e3 * np.percentile(waits, 90))


def test_host_and_admission_times_are_means_over_the_window():
    rec = _record("backlog", [])
    rec.steps = [driver.Step(11.0, 11.1, 4, 40, 0.06, 0.02, 0),
                 driver.Step(12.0, 12.3, 4, 44, 0.06, 0.2, 0),
                 driver.Step(30.0, 31.0, 4, 48, 0.1, 0.1, 0)]   # after close
    rec.admits = [driver.Admit(12.0, 12.2, 100),
                  driver.Admit(11.0, 11.02, 30)]
    assert read("host_ms_step", rec) == pytest.approx(1e3 * (0.02 + 0.04) / 2)
    assert read("admit_ms", rec) == pytest.approx(1e3 * 0.22 / 2)


def test_trace_metrics_need_a_trace():
    rec = _record("backlog", [])
    for name in ("decode_step_ms", "prefill_us_tok", "idle_share", "mfu",
                 "prefill_mfu", "fmt_gemm_roofline", "fmt_attn_roofline"):
        assert read(name, rec) is None
