"""The traffic generator: every seed gets the same work in its own
order."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

TIERS = [{"tol": 0.01, "weight": 1}, {"tol": 0.001, "weight": 1}]
MIXES = {
    "poisson": {"arrivals": "poisson", "rate_rps": 16.72},
    "backlog": {"arrivals": "backlog", "backlog_rps": 32.0},
    "bursty": {"arrivals": "bursty", "burst_size": 8, "period_s": 0.5,
               "jitter_s": 0.05},
}


@pytest.mark.parametrize("kind", sorted(MIXES))
def test_same_work_for_every_seed(kind):
    mix = dict(MIXES[kind], num_steps=25, tiers=TIERS)
    runs = [traffic.generate(mix, seed, 40.0)
            for seed in (1, 2, 2 ** 40 + 3)]
    for reqs in runs:
        assert len(reqs) == len(runs[0])
        assert sorted(r["tol"] for r in reqs) == \
            sorted(r["tol"] for r in runs[0])
        assert all(0.0 <= r["arrival"] < 40.0 for r in reqs)
        assert len({r["noise_seed"] for r in reqs}) == len(reqs)
        assert all(0 <= r["noise_seed"] < 2 ** 31 for r in reqs)
    assert [r["noise_seed"] for r in runs[0]] != \
        [r["noise_seed"] for r in runs[1]]
    assert traffic.generate(mix, 2, 40.0) == runs[1]


def test_poisson_gaps_are_the_same_set_in_another_order():
    """The gaps between arrivals are the exponential's quantiles at
    (i + 0.5) / n, scaled to the window, for every seed."""
    n = round(16.72 * 40)
    q = (np.arange(n) + 0.5) / n
    want = -np.log1p(-q)
    want = np.sort(want * 40.0 / want.sum())
    mix = dict(MIXES["poisson"], num_steps=25, tiers=TIERS)
    for seed in (5, 6):
        t = np.sort([r["arrival"] for r in traffic.generate(mix, seed, 40.0)])
        gaps = np.sort(np.diff(t))
        idx = np.clip(np.searchsorted(want, gaps), 0, n - 1)
        near = np.minimum(abs(want[idx] - gaps),
                          abs(want[np.maximum(idx - 1, 0)] - gaps))
        assert len(gaps) == n - 1 and near.max() < 1e-9



def test_fixed_gap_order_is_rotated_by_the_seed():
    """With ``order_seed``, every seed's gaps are one fixed sequence,
    rotated."""
    mix = dict(MIXES["poisson"], num_steps=25, tiers=TIERS, order_seed=17)
    gaps = [np.diff([r["arrival"] for r in sorted(
        traffic.generate(mix, seed, 40.0), key=lambda r: r["arrival"])])
        for seed in (5, 6, 2 ** 40 + 3)]
    ring = np.concatenate([gaps[0], gaps[0]])
    n = len(gaps[0])
    for g in gaps[1:]:
        assert not np.allclose(g, gaps[0])
        # a rotation of the first: its head or its tail (whichever does
        # not hold the one gap that wraps, which no run shows) lies in the
        # first's ring at exactly one place
        hits = [i for i in range(n) for part in (g[:20], g[-20:])
                if np.allclose(ring[i:i + 20], part)]
        assert 1 <= len(hits) <= 2
