"""The traffic generator: every seed carries the same work."""
import itertools
import json
import pathlib

import numpy as np
import pytest

from chipbench import spec, traffic

SEEDS = [0, 1, 2 ** 31 + 7, 2 ** 33 + 5, 987654321]
DATA_MIXES = pathlib.Path(__file__).resolve().parent / "data" / "mixes"


def _mix(name):
    path = spec.HERE / "mixes" / f"{name}.json"
    if not path.exists():
        path = DATA_MIXES / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("seconds", [30.0, 51.0])
def test_open_loop_same_work_every_seed(seconds):
    mix = _mix("tiny-chat")
    plans = [traffic.open_loop(mix, s, 1000, seconds) for s in SEEDS]
    n_win = round(mix["rate_rps"] * seconds)
    for plan in plans:
        win = [p for p in plan if p.measured]
        assert len(win) == n_win
        assert all(0.0 <= p.due < seconds for p in win)
        assert all(-mix["lead_s"] <= p.due < 0 for p in plan
                   if not p.measured)
    sizes = {(tuple(sorted(len(p.prompt) for p in plan if p.measured)),
              tuple(sorted(p.max_new for p in plan if p.measured)))
             for plan in plans}
    assert len(sizes) == 1
    # the gaps between arrivals and the last one's to the window's close
    # are one set, shuffled
    gaps = set()
    for plan in plans:
        due = sorted(p.due for p in plan if p.measured)
        gaps.add(tuple(np.round(np.sort(np.append(np.diff(due),
                                                  seconds - due[-1])), 9)))
    assert len(gaps) == 1
    orders = {tuple(len(p.prompt) for p in plan) for plan in plans}
    assert len(orders) == len(SEEDS)


def test_backlog_blocks_same_work_every_seed():
    mix = _mix("batch")
    block = mix["block"]
    totals = set()
    for s in SEEDS:
        reqs = list(itertools.islice(traffic.backlog(mix, s, 1000),
                                     3 * block))
        for b in range(3):
            chunk = reqs[b * block:(b + 1) * block]
            totals.add((sum(len(p.prompt) for p in chunk),
                        sum(p.max_new for p in chunk),
                        tuple(sorted(len(p.prompt) for p in chunk))))
        assert [p.rid for p in reqs] == list(range(3 * block))
    assert len(totals) == 1


def test_quantile_sets_follow_the_distribution():
    ln = {"dist": "lognormal", "median": 256, "sigma": 0.6,
          "min": 128, "max": 1024}
    v = traffic.quantile_set(ln, 1001)
    assert v.min() >= 128 and v.max() <= 1024
    assert np.median(v) == 256
    u = traffic.quantile_set({"dist": "uniform", "min": 128, "max": 512},
                             64)
    assert u.min() >= 128 and u.max() <= 512
    assert abs(u.mean() - 320) < 1


def test_arrivals_span_the_window():
    due = traffic.arrivals(traffic.exp_gaps(50), 10.0)
    assert due[0] == 0.0 and due[-1] < 10.0
    assert np.all(np.diff(due) > 0)


def test_prefill_pages_cover_the_prompts():
    assert traffic.prefill_pages(_mix("batch"), 256) == [1, 2, 3, 4]
