"""Request schedules: the same sizes and gaps for every run, drawn as
quantiles of the traffic's published distribution."""

import os
import sys

# The benchmark's library, after the paths already there: this directory is
# also named "tests", and must not shadow the repository's own.
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_BENCH, os.path.join(os.path.dirname(os.path.dirname(_BENCH)), "src")):
    if _p not in sys.path:
        sys.path.append(_p)

import collections
import math

import pytest

from chipbench import arrivals
from chipbench.files import load_benchmark, resolve_cell

TRAFFIC = resolve_cell(load_benchmark(), "qwen05.serve").traffic


def test_lengths_round_up_to_the_edges():
    spec = {"median": 100, "sigma": 1.0, "edges": [50, 100, 400]}
    got = arrivals.bucketed_lengths(spec, 10)
    assert got == sorted(got) and set(got) <= {50, 100, 400}
    # quantiles 19, 35 | 51, 68, 88 | 113, 147, 196, 281 and 518, cut to 400
    assert collections.Counter(got) == {50: 2, 100: 3, 400: 5}


def test_schedule_is_fixed_by_the_traffic_alone():
    a = arrivals.schedule(32, 0.25, TRAFFIC["prompt"], TRAFFIC["output"],
                          TRAFFIC["order_seed"])
    b = arrivals.schedule(32, 0.25, TRAFFIC["prompt"], TRAFFIC["output"],
                          TRAFFIC["order_seed"])
    assert a == b
    times = [t for t, _, _ in a]
    assert times == sorted(times)
    # exponential quantiles: the mean gap is close to 1 / rate
    assert times[-1] / 32 == pytest.approx(4.0, rel=0.1)


@pytest.mark.parametrize("order_seed", [1, 2, 3])
def test_order_seed_changes_the_order_not_the_work(order_seed):
    base = arrivals.schedule(32, 0.5, TRAFFIC["prompt"], TRAFFIC["output"],
                             TRAFFIC["order_seed"])
    other = arrivals.schedule(32, 0.5, TRAFFIC["prompt"], TRAFFIC["output"],
                              order_seed)
    assert sorted(p for _, p, _ in base) == sorted(p for _, p, _ in other)
    assert sorted(g for _, _, g in base) == sorted(g for _, _, g in other)
    assert base[-1][0] == pytest.approx(other[-1][0])


def test_published_medians_fall_in_their_buckets():
    n = 32
    for key in ("prompt", "output"):
        spec = TRAFFIC[key]
        lengths = arrivals.bucketed_lengths(spec, n)
        median = sorted(lengths)[n // 2]
        assert median == min(e for e in spec["edges"] if e >= spec["median"])
    assert math.isclose(spec["median"], 13)
