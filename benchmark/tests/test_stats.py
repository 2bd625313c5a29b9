"""The arithmetic of the end-to-end metrics and of the schedules."""

import math

import pytest

from benchmark import stats


def test_percentile_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_p90_counts_failures_as_misses():
    # 20 requests scheduled at t=0: 17 answered in 1 s, 3 never
    sched = [0.0] * 20
    done = [1.0] * 17 + [None] * 3
    lat = stats.open_loop_latencies(sched, done, give_up=70.0)
    assert stats.percentile(lat, 50) == 1.0
    # 3 misses of 20 lie beyond the 85th percentile: p90 is a miss,
    # counted with the latency it had when the run gave up
    assert stats.percentile(lat, 90) == 70.0
    done = [1.0] * 19 + [None]
    lat = stats.open_loop_latencies(sched, done, give_up=70.0)
    assert stats.percentile(lat, 90) == 1.0


def test_open_loop_latency_runs_from_the_scheduled_arrival():
    # the generator ran late: submitted at 5.0 for a 2.0 arrival; the
    # answer at 6.0 is 4 s late for the user, not 1 s
    assert stats.open_loop_latencies([2.0], [6.0], give_up=99) == [4.0]


def test_tree_nodes_per_s_over_whole_solves():
    trees = [14_561_974, 9_499_307, 13_228_600]
    # first solve starts at 10.0, last ends at 16.0: the gaps between
    # solves count
    assert stats.tree_nodes_per_s(trees, 10.0, 16.0) == pytest.approx(
        sum(trees) / 6.0)
    assert stats.served_per_s(90, 100.0, 145.0) == 2.0


@pytest.mark.parametrize("arrivals", ["regular", "poisson"])
@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7, -5, 2**70 + 3])
def test_open_loop_schedule_fills_the_window_evenly(seed, arrivals):
    rows = [1, 2, 3, 4, 7, 9, 14, 19]
    off, got = stats.open_loop_schedule(2.0, 40, rows, seed, arrivals)
    assert len(off) == 80 == len(got) and off[0] == 0.0
    assert off == sorted(off) and off[-1] < 40
    gaps = [b - a for a, b in zip(off, off[1:])] + [40 - off[-1]]
    assert math.isclose(sum(gaps), 40) and min(gaps) > 0
    if arrivals == "regular":
        assert max(gaps) - min(gaps) < 1e-9
    # every block of len(rows) requests holds each row once
    for b in range(0, 80 - len(rows) + 1, len(rows)):
        assert sorted(got[b:b + len(rows)]) == rows


def test_schedule_is_the_seeds_and_repeats():
    rows = [1, 2, 3, 4, 7, 9, 14, 19]
    a = stats.open_loop_schedule(3.2, 20, rows, 7)
    assert a == stats.open_loop_schedule(3.2, 20, rows, 7)
    b = stats.open_loop_schedule(3.2, 20, rows, 8)
    # another seed: the same arrivals and requests, in another order
    assert a[0] == b[0] and a[1] != b[1]
    assert sorted(a[1]) == sorted(b[1])
    p = stats.open_loop_schedule(3.2, 20, rows, 7, "poisson")
    assert p[0] != stats.open_loop_schedule(3.2, 20, rows, 8, "poisson")[0]
    with pytest.raises(ValueError):
        stats.open_loop_schedule(3.2, 20, rows, 7, "bursty")


def test_closed_cycles_permute_every_row_once():
    rows = [22, 29, 30]
    for cycle in range(5):
        assert sorted(stats.closed_cycle(rows, 99, cycle)) == rows
    orders = {tuple(stats.closed_cycle(rows, s, 0)) for s in range(40)}
    assert len(orders) > 1


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)
