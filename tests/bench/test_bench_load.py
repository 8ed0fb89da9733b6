"""Seeded arrival schedules of the open loop (bench/traffic/open.py)."""
from __future__ import annotations

import numpy as np
import pytest

import bench_testkit  # noqa: F401  (import paths)
from bench import harness

BIG = 2**31 + 12345          # seeds wider than 32 bits are valid
arrival_offsets = harness.Files().module("traffic", "open").arrival_offsets


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_same_seed_repeats_exactly(seed):
    a = arrival_offsets(38.0, 20.0, seed)
    b = arrival_offsets(38.0, 20.0, seed)
    assert np.array_equal(a, b)


def test_every_seed_gets_the_same_requests_and_gaps_in_another_order():
    a = arrival_offsets(38.0, 20.0, 1)
    b = arrival_offsets(38.0, 20.0, BIG)
    assert len(a) == len(b) == 760
    assert a[0] == b[0] == 0.0
    assert np.all(np.diff(a) >= 0)
    ga, gb = np.diff(a), np.diff(b)
    assert not np.array_equal(ga, gb)
    # both are 759 of the same 760 stratified gaps
    assert len(np.setdiff1d(np.round(ga, 12), np.round(gb, 12))) <= 1
    assert a[-1] == pytest.approx(20.0, rel=0.02)


@pytest.mark.parametrize("rate,seconds", [(50.0, 100.0), (35.0, 20.0)])
def test_mean_gap_is_the_rate(rate, seconds):
    a = arrival_offsets(rate, seconds, 3)
    assert len(a) == round(rate * seconds)
    assert np.mean(np.diff(a)) == pytest.approx(1 / rate, rel=0.02)


@pytest.mark.parametrize("rate,seconds", [(0.0, 1.0), (10.0, -1.0)])
def test_bad_schedules_are_refused(rate, seconds):
    with pytest.raises(ValueError):
        arrival_offsets(rate, seconds, 0)


def test_a_mix_naming_no_loop_file_is_refused():
    from bench import load
    with pytest.raises(ValueError, match="no bench/traffic"):
        load.make_traffic(harness.Files(), None, {}, {"loop": "nowhere"},
                          0, 1.0)
