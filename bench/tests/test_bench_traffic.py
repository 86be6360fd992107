"""The traffic generator and the tail arithmetic."""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np
import pytest

from bench import tails, traffic

MIX = json.loads((pathlib.Path(__file__).resolve().parents[1] / "traffic" /
                  "chat.json").read_text())


def lognormal_cdf(x, median, sigma):
    return 0.5 * (1 + math.erf(math.log(x / median) / (sigma * math.sqrt(2))))


def test_a_seed_reproduces_its_schedule():
    a = traffic.generate(MIX, rate=2.0, seconds=51, seed=2 ** 31 + 77)
    assert a == traffic.generate(MIX, rate=2.0, seconds=51, seed=2 ** 31 + 77)


def test_seeds_reorder_the_same_sizes_and_gaps():
    a = traffic.generate(MIX, rate=2.0, seconds=51, seed=1)
    b = traffic.generate(MIX, rate=2.0, seconds=51, seed=2)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.output_len for r in a) == sorted(r.output_len for r in b)
    # the gaps between due times are the same stratified gaps, reordered
    # (the gap after the last request falls outside the window)
    full = -np.log1p(-traffic.mid_quantiles(len(a)))
    full *= 51 / full.sum()
    for x in (a, b):
        gaps = np.diff([r.due_s for r in x])
        assert np.abs(gaps[:, None] - full[None]).min(1).max() < 1e-9
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_arrivals_fill_the_window_at_the_rate():
    reqs = traffic.generate(MIX, rate=1.6, seconds=51, seed=5)
    due = [r.due_s for r in reqs]
    assert len(reqs) == round(1.6 * 51)
    assert due[0] == 0.0 and due == sorted(due) and due[-1] < 51


@pytest.mark.parametrize("seed", [0, 99, 2 ** 31 + 5])
def test_bucket_and_length_shares_match_the_mix(seed):
    n = 400
    reqs = traffic.generate(MIX, rate=n / 100.0, seconds=100, seed=seed)
    p = MIX["prompt_tokens"]
    lo = 0.0
    for b in p["round_up_to"]:
        hi = 1.0 if b == max(p["round_up_to"]) else lognormal_cdf(
            b, p["median"], p["sigma"])
        share = sum(r.prompt_len == b for r in reqs) / n
        assert abs(share - (hi - lo)) <= 1.0 / n, b
        lo = hi
    o = MIX["output_tokens"]
    outs = np.array([r.output_len for r in reqs])
    assert outs.min() >= o["min"] and outs.max() <= o["max"]
    for x in (64, 129, 300):
        share = float(np.mean(outs <= x))
        assert abs(share - lognormal_cdf(x + 0.5, o["median"], o["sigma"])) \
            <= 2.0 / n


def test_tail_counts_failed_and_unfinished_requests_as_missing():
    ok = [float(i) for i in range(1, 20)]          # 19 served requests
    # one missing of 20: p90 sits at rank 17.1 of 0..19, below it
    assert tails.percentile(ok + [None], 90) == pytest.approx(18.1)
    # two missing of 20: rank 17.1 lies between a served one and a missing
    # one, so p90 is missing too
    assert tails.percentile(ok[:-1] + [None, math.inf], 90) == math.inf
    # a missing request is never below a served one
    assert tails.percentile([None, 1.0], 0) == 1.0
    with pytest.raises(ValueError):
        tails.percentile([], 50)
    assert tails.mean([1.0, 2.0, 6.0]) == 3.0
    assert tails.mean([1.0, None]) == math.inf
    with pytest.raises(ValueError):
        tails.mean([])


def _window(rate, ttft, rise=0.0, failed=0):
    return {"rate_per_s": rate, "ttft_p50_ms": ttft, "failed": failed,
            "backlog_q3": 2.0, "backlog_q4": 2.0 + rise}


@pytest.mark.parametrize("rows,want", [
    # 0.6 grew on one seed: knee 0.45; 0.3 is steady and under 0.36
    ([_window(0.2, 1000), _window(0.2, 1050), _window(0.3, 1100),
      _window(0.3, 1200), _window(0.45, 1500), _window(0.45, 3000),
      _window(0.6, 4000), _window(0.6, 9000, rise=3.5)], (0.45, 0.3)),
    # 0.3 held but its seeds disagree on the median TTFT: the lowest rate
    ([_window(0.2, 1000), _window(0.2, 1100), _window(0.3, 1800),
      _window(0.3, 2900), _window(0.45, 5000, failed=1)], (0.3, 0.2)),
    # nothing held
    ([_window(0.2, 9000, rise=3.0)], (None, 0.2)),
])
def test_the_sweep_picks_the_knee_and_a_steady_rate_below_it(rows, want):
    import importlib

    sweep = importlib.import_module("bench.sweep")
    assert sweep.choose(rows) == want
