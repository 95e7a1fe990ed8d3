"""Percentiles over every sample, rates over the whole window."""
import os
import sys

import numpy as np
import pytest

# bench/run.py's import paths: the program's src/ and bench/ itself
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(os.path.dirname(_BENCH), "src"), _BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import readers
from lib.loop import Record, Step
from lib.stats import percentile, rate, union_seconds


def test_percentile_is_over_all_samples():
    xs = np.random.default_rng(0).exponential(size=1001).tolist()
    for q in (50, 95, 99):
        assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert percentile([3.0], 95) == 3.0


def test_rate_is_over_the_whole_window():
    assert rate(100, 10.0, 30.0) == 5.0
    with pytest.raises(ValueError):
        rate(1, 2.0, 2.0)


def test_union_merges_overlaps():
    assert union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert union_seconds([]) == 0


class _Loop:
    def __init__(self, records, steps):
        self.records, self.steps = records, steps


class _Ctx:
    def __init__(self, records, steps, w0, w1):
        self.loop, self.w0, self.w1 = _Loop(records, steps), w0, w1


def test_window_samples():
    # a: tokens at 1 (before the window), 3, 4, 6; b: tokens at 5 and 9
    # (after the window)
    a = Record(0, 0, (1,), 4, sent=0.0, times=[1.0, 3.0, 4.0, 6.0])
    b = Record(1, 0, (1,), 4, sent=2.0, times=[5.0, 9.0])
    ctx = _Ctx([a, b], [], w0=2.0, w1=8.0)
    assert sorted(readers.token_gaps(ctx)) == [1.0, 2.0]
    assert readers.out_tokens(ctx) == 4


def test_step_readers_use_window_steps():
    steps = [Step(0.0, 1.0, [5], 0, [], 0),
             Step(2.0, 3.0, [6, 7], 1, [(0, 16)], 1),
             Step(3.0, 5.0, [8], 0, [(16, 8)], 0)]
    ctx = _Ctx([], steps, w0=2.0, w1=5.0)
    ctx.max_batch = 4
    assert readers.step_ms_median(ctx) == pytest.approx(1500.0)
    assert readers.batch_occupancy(ctx) == pytest.approx(100 * 1.5 / 4)
