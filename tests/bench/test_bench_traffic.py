"""The traffic generators and the two loops, on a fake clock."""
import numpy as np
import pytest

import bench_testing  # noqa: F401
from bench import traffic

OPEN = {"loop": "open", "rate_qps": 500, "max_batch": 32}


class FakeClock:
    """A clock that moves only when the fake searcher works or the loop
    sleeps."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def fake_search(clock, cost_s, stall_at=None, stall_s=0.0):
    calls = []

    def search(q):
        calls.append(len(q))
        clock.t += cost_s
        if stall_at is not None and len(calls) == stall_at:
            clock.t += stall_s
        return (np.zeros((len(q), 10), np.int64),
                np.zeros((len(q), 10), np.float32))
    return search, calls


def test_same_seed_same_schedule_and_draws():
    a = traffic.open_schedule(OPEN, 5.0, 2**31 + 7)
    b = traffic.open_schedule(OPEN, 5.0, 2**31 + 7)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(traffic.query_draws(10_000, 99, 3),
                                  traffic.query_draws(10_000, 99, 3))
    assert not np.array_equal(traffic.query_draws(10_000, 99, 3),
                              traffic.query_draws(10_000, 99, 4))


def test_seeds_share_the_gaps_not_their_order():
    """Every seed gets the same multiset of gaps: the seed changes which
    query comes when, not how much work the run holds."""
    mix = dict(OPEN, rate_qps=200)
    n = int(np.ceil(200 * 5.0 * 1.5)) + 64
    full = lambda seed: np.cumsum(
        -np.log1p(-(np.arange(n) + 0.5) / n)[
            np.random.default_rng([seed, 2]).permutation(n)]) / 200
    ga, gb = np.diff(full(1), prepend=0), np.diff(full(2), prepend=0)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    due = traffic.open_schedule(mix, 5.0, 1)
    assert np.all(np.diff(due) >= 0) and due[-1] < 5.0
    assert len(due) == pytest.approx(1000, rel=0.1)


@pytest.mark.parametrize("rate", [40, 480, 2000])
def test_open_schedule_keeps_the_mean_rate(rate):
    due = traffic.open_schedule(dict(OPEN, rate_qps=rate), 20.0, 2**31 + 9)
    assert len(due) == pytest.approx(20 * rate, rel=0.05)
    # no stretch of a tenth of the window holds twice its share
    per_tenth = np.histogram(due, bins=10, range=(0, 20.0))[0]
    assert per_tenth.max() < 2 * rate * 2.0


def test_closed_loop_calls_are_exactly_the_clients():
    clock = FakeClock()
    search, calls = fake_search(clock, 0.01)
    pool = np.zeros((50, 4), np.float32)
    win = traffic.closed_loop(search, pool, {"loop": "closed",
                                             "clients": 32}, 1.0, 5,
                              clock=clock)
    assert set(calls) == {32}
    assert len(win.calls) == 100
    assert win.seconds == pytest.approx(1.0)
    assert np.allclose(win.latencies(), 0.01)


def test_open_loop_latency_runs_from_due_time():
    """A stall in one call raises the latency of every query that was due
    while it lasted, because latency counts from the due time."""
    mix = dict(OPEN, rate_qps=100)
    pool = np.zeros((50, 4), np.float32)

    def run(stall_s):
        clock = FakeClock()
        search, _ = fake_search(clock, 0.002, stall_at=10, stall_s=stall_s)
        win = traffic.open_loop(search, pool, mix, 2.0, 11, clock=clock,
                                sleep=clock.sleep)
        return win, np.concatenate([c.due for c in win.calls])

    calm, due = run(0.0)
    stalled, due2 = run(0.3)
    np.testing.assert_array_equal(due, due2)
    assert stalled.attempted == calm.attempted == len(due)
    lat_calm, lat_stall = calm.latencies(), stalled.latencies()
    t_stall = stalled.calls[9].end        # the stalled call's return
    hit = (due2 > stalled.calls[9].start) & (due2 < t_stall - 0.01)
    assert hit.sum() >= 10
    assert np.all(lat_stall[hit] > lat_calm[hit] + 0.01)
    # Those queries waited for the stalled call: in-batch latency alone
    # would miss it.
    assert max(len(c.rows) for c in stalled.calls) > 1
    assert np.median(lat_calm) == pytest.approx(0.002)


def test_open_loop_batches_at_most_max_batch():
    clock = FakeClock()
    search, calls = fake_search(clock, 0.2)   # far slower than arrivals
    pool = np.zeros((50, 4), np.float32)
    win = traffic.open_loop(search, pool, dict(OPEN, rate_qps=1000), 1.0,
                            2, clock=clock, sleep=clock.sleep,
                            drain_s=1.0)
    assert max(calls) == 32
    assert win.unanswered > 0
    assert win.attempted == len(traffic.open_schedule(
        dict(OPEN, rate_qps=1000), 1.0, 2))

