"""The traffic generator: fixed work per seed, and the two-state MMPP's
empirical rates against its parameters."""
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
from benchmarks.chip import generator as gen  # noqa: E402

MMPP = {"kind": "mmpp2", "rates_rps": [2.0, 12.0], "dwell_s": [3.0, 1.0]}
LENS = {"median": 192, "sigma": 0.8, "min": 32, "max": 1024, "multiple": 64}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_mmpp_mean_rate_matches_parameters(seed):
    seconds = 400.0
    rng = np.random.default_rng(seed)
    t = gen.arrival_times(MMPP, seconds, rng)
    mean = (2.0 * 3.0 + 12.0 * 1.0) / 4.0
    assert len(t) == pytest.approx(mean * seconds, abs=1)
    assert np.all(np.diff(t) > 0) and 0 <= t[0] and t[-1] < seconds
    # each state holds its share of the window and its own rate
    starts, rates = gen.intensity(MMPP, seconds, np.random.default_rng(seed))
    ends = np.append(starts[1:], seconds)
    for r, share in ((2.0, 0.75), (12.0, 0.25)):
        held = [(a, b) for a, b, x in zip(starts, ends, rates) if x == r]
        dur = sum(b - a for a, b in held)
        n = sum(np.sum((t >= a) & (t < b)) for a, b in held)
        assert dur == pytest.approx(share * seconds)
        # the count in a state is random: within four Poisson deviations
        assert abs(n - r * dur) <= 4 * np.sqrt(r * dur)


def test_poisson_mean_and_fixed_work_per_seed():
    traffic = {"arrivals": {"kind": "poisson", "rate_rps": 5.0},
               "prompt": LENS, "output": {"median": 64, "sigma": 0.7,
                                          "min": 8, "max": 256}}
    runs = [gen.make_arrivals(traffic, 40.0, s, 1000) for s in (1, 2, 3)]
    assert {len(r) for r in runs} == {200}
    key = lambda r: (sorted(len(a.prompt) for a in r),
                     sorted(a.max_new_tokens for a in r),
                     np.round(sorted(np.diff([0] + [a.due_s for a in r])), 6)
                     .tolist())
    assert key(runs[0]) == key(runs[1]) == key(runs[2])
    assert [a.due_s for a in runs[0]] != [a.due_s for a in runs[1]]
    assert max(a.due_s for a in runs[0]) < 40.0


def test_lengths_clipped_and_rounded():
    x = gen.lengths(LENS, 1000, np.random.default_rng(0))
    assert x.min() == 64 and x.max() == 1024 and np.all(x % 64 == 0)
    assert np.median(x) == pytest.approx(192, abs=64)
    assert gen.prompt_buckets(LENS) == list(range(64, 1025, 64))


def test_lead_in_arrivals_before_the_window():
    traffic = {"arrivals": {"kind": "poisson", "rate_rps": 5.0},
               "prompt": LENS, "output": {"median": 64, "sigma": 0.7,
                                          "min": 8, "max": 256},
               "lead_in_s": 10.0}
    runs = [gen.make_arrivals(traffic, 40.0, s, 1000) for s in (1, 2)]
    for r in runs:
        due = [a.due_s for a in r]
        assert due == sorted(due) and len({a.uid for a in r}) == len(r)
        lead = [a for a in r if a.due_s < 0]
        assert len(lead) == 50 and min(due) >= -10.0
        assert len(r) - len(lead) == 200
    # the window's arrivals are those of a mix with no lead-in
    plain = gen.make_arrivals(dict(traffic, lead_in_s=0), 40.0, 1, 1000)
    assert [(a.due_s, a.max_new_tokens) for a in runs[0] if a.due_s >= 0] \
        == [(a.due_s, a.max_new_tokens) for a in plain]


@pytest.mark.parametrize("n", [8, 69, 70])
def test_spread_order_keeps_values_and_spreads_them(n):
    x = np.arange(n) * 10
    runs = [gen.spread_order(x, np.random.default_rng(s)) for s in (1, 2)]
    assert sorted(runs[0]) == sorted(x) and list(runs[0]) != list(runs[1])
    bands = np.array_split(np.sort(x), 8)
    band_of = {v: i for i, b in enumerate(bands) for v in b}
    for r in runs:
        # every run of 8 places holds at most one value of each band
        for j in range(0, n, 8):
            seen = [band_of[v] for v in r[j:j + 8]]
            assert len(seen) == len(set(seen))
