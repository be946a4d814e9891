"""The traffic generator: a pure function of the seed, within its clips,
and the same schedule of sizes and arrivals for every seed."""

import json
import os

import numpy as np
import pytest

from chipbench_testlib import BENCH
from harness.stats import percentile
from harness.traffic import Traffic, gamma_gaps, quantile_set


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def items(m, seed, n=120, rate=0.6):
    t = Traffic(m, seed=seed, vocab=92553, seconds=51, rate=rate)
    return t, [t.item(i) for i in range(n)]


@pytest.mark.parametrize("name", ["conv", "code-offline"])
def test_same_seed_same_requests(name):
    _, a = items(mix(name), 2**31 + 11)
    _, b = items(mix(name), 2**31 + 11)
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.n_new == y.n_new
        assert np.array_equal(x.prompt, y.prompt)
    _, c = items(mix(name), 5)
    assert any(not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))


@pytest.mark.parametrize("name", ["conv", "code-offline"])
def test_requests_stay_within_clips(name):
    m = mix(name)
    _, reqs = items(m, 7, n=400)
    for r in reqs:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert 1 <= r.n_new <= m["output"]["max"]
        assert len(r.prompt) + r.n_new <= m["max_total"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 92553


@pytest.mark.parametrize("name", ["conv", "code-offline"])
def test_every_seed_gets_the_same_schedule(name):
    t1, a = items(mix(name), 1)
    _, b = items(mix(name), 2**31 + 5)
    assert [(len(r.prompt), r.n_new, r.due_s) for r in a] == \
        [(len(r.prompt), r.n_new, r.due_s) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the set is in an order of its own, not sorted by size
    lens = [len(r.prompt) for r in a[:t1.n]]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)
    # a second pass through the set (the drain) takes another order
    assert [len(r.prompt) for r in a[t1.n:2 * t1.n]] != lens[:len(a) - t1.n]


def test_open_loop_covers_ramp_and_window_at_its_rate():
    t, reqs = items(mix("conv"), 3, rate=0.5)
    assert t.n == int(np.ceil(0.5 * (t.ramp_s + 51)))
    due = [r.due_s for r in reqs]
    assert all(b > a for a, b in zip(due, due[1:]))
    assert due[t.n - 1] == pytest.approx(gamma_gaps(0.5, 1.0, t.n).sum())
    # cv 1 is the exponential: gaps at its quantiles, -log(1 - u) / rate
    u = (np.arange(t.n) + 0.5) / t.n
    assert gamma_gaps(0.5, 1.0, t.n) == pytest.approx(-np.log1p(-u) / 0.5)


def test_bursty_arrivals_are_a_data_file_only():
    """Gamma gaps with a coefficient of variation of 2: the same mean
    gap, bursts of short gaps and a few long ones."""
    bursty = dict(mix("conv"), name="conv-bursty", arrivals={"gamma_cv": 2.0})
    t = Traffic(bursty, seed=1, vocab=92553, seconds=2000, rate=0.5)
    assert t.gaps.mean() == pytest.approx(2.0, rel=0.02)
    assert t.gaps.std() / t.gaps.mean() == pytest.approx(2.0, rel=0.05)
    poisson = Traffic(mix("conv"), seed=1, vocab=92553, seconds=2000, rate=0.5)
    assert np.median(t.gaps) < 0.3 * np.median(poisson.gaps)


def test_stated_medians():
    conv, code = mix("conv"), mix("code-offline")
    assert np.median(quantile_set(conv["prompt"], 999, 1)) == \
        pytest.approx(1020, rel=0.02)
    assert np.median(quantile_set(conv["output"], 999, 1)) == \
        pytest.approx(129, rel=0.02)
    assert np.median(quantile_set(code["prompt"], 999, 1)) == \
        pytest.approx(1500, rel=0.02)
    assert np.median(quantile_set(code["output"], 999, 1)) == \
        pytest.approx(13, abs=0.5)


def test_unknown_loop_or_arrivals_is_refused():
    with pytest.raises(ValueError):
        Traffic(dict(mix("conv"), loop="bursty"), seed=1, vocab=9,
                seconds=5, rate=1)
    for arrivals in ("poisson", {"gamma_cv": 0}, {"cv": 1.0}):
        with pytest.raises(ValueError):
            Traffic(dict(mix("conv"), arrivals=arrivals), seed=1, vocab=9,
                    seconds=5, rate=1)
    with pytest.raises(ValueError):
        Traffic(mix("conv"), seed=1, vocab=9, seconds=5, rate=None)


def test_percentile_is_nearest_rank_over_all_samples():
    vals = list(range(1, 101))
    assert percentile(vals, 90) == 90
    assert percentile(vals, 95) == 95
    assert percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert percentile([5.0], 99) == 5.0
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 1000], 90) == 10
    with pytest.raises(ValueError):
        percentile([], 90)
    with pytest.raises(ValueError):
        percentile([1.0], 0)

