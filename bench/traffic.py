"""Traffic: arrival schedules and query draws from a mix file, and the two
loops that drive the searcher with them on the real clock.

A mix is a JSON file under ``bench/traffic/``. Its ``loop`` key picks the
loop, the rest are that loop's parameters:

- ``{"loop": "closed", "clients": C}``: C clients, each sending its next
  query when its last one returns, so every call is one batch of C.
- ``{"loop": "open", "rate_qps": R, "max_batch": B}``: Poisson arrivals
  at R queries/s. Whenever the searcher is free, every query already due
  (at most B) goes into one call.

Both loops call ``search(queries) -> (ids, dists)`` and record every call
of the window with its due times and answers.

Every seed gets the same multiset of inter-arrival gaps (the quantiles of
the unit exponential), in an order drawn from the seed, so that seeds
change which queries come when, not how much work a run holds.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import numpy as np


def open_schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of the arrivals of an open mix
    over ``seconds``, ascending. The same multiset of unit gaps for every
    seed; the seed picks their order."""
    mean = float(mix["rate_qps"]) * seconds
    n = int(math.ceil(mean * 1.5)) + 64
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)       # unit exponential
    gaps = gaps[np.random.default_rng([seed, 2]).permutation(n)]
    due = np.cumsum(gaps) / float(mix["rate_qps"])
    return due[due < seconds]


def query_draws(pool_size: int, count: int, seed: int) -> np.ndarray:
    """Indices into the query pool, uniform, from the seed."""
    return np.random.default_rng([seed, 1]).integers(0, pool_size, count)


@dataclass
class Call:
    """One searcher call of the window."""
    start: float            # s from the window's start
    end: float
    rows: np.ndarray        # pool index of each query in the call
    due: np.ndarray         # when each was due (s from the window's start)
    ids: np.ndarray
    dists: np.ndarray


@dataclass
class Window:
    calls: list = field(default_factory=list)
    seconds: float = 0.0    # from the window's start to the last return
    lateness: list = field(default_factory=list)  # open loop: s the loop
                            # woke after a due time it slept for
    unanswered: int = 0     # due in the window, never answered

    @property
    def attempted(self) -> int:
        return sum(len(c.rows) for c in self.calls) + self.unanswered

    def latencies(self) -> np.ndarray:
        """Per query, from its due time to its call's return (s)."""
        if not self.calls:
            return np.zeros(0)
        return np.concatenate([c.end - c.due for c in self.calls])


def _serve(search, pool, rows, t0, clock, span):
    with span("bench.search"):
        start = clock() - t0
        ids, dists = search(pool[rows])
        end = clock() - t0
    return start, end, np.asarray(ids), np.asarray(dists)


def closed_loop(search, pool: np.ndarray, mix: dict, seconds: float,
                seed: int, clock=time.perf_counter, span=None) -> Window:
    """C clients, each re-sending as soon as its answer is back: calls of
    exactly C queries, back to back, until ``seconds`` have passed."""
    span = span or _no_span
    c = int(mix["clients"])
    draws = query_draws(len(pool), 1 << 20, seed)
    win, i = Window(), 0
    t0 = clock()
    while clock() - t0 < seconds:
        with span("bench.generate"):
            if i + c > len(draws):
                i = 0
            rows = draws[i:i + c]
            i += c
        start, end, ids, dists = _serve(search, pool, rows, t0, clock, span)
        # A client's query is due when its previous answer came back.
        win.calls.append(Call(start, end, rows, np.full(c, start), ids,
                              dists))
    win.seconds = win.calls[-1].end if win.calls else clock() - t0
    return win


def open_loop(search, pool: np.ndarray, mix: dict, seconds: float,
              seed: int, clock=time.perf_counter, sleep=time.sleep,
              span=None, drain_s: float = 60.0) -> Window:
    """Poisson arrivals on the real clock. Whenever the searcher is free,
    every query already due (at most ``max_batch``) goes into one call.
    Queries due in the window are served to the end; any still waiting
    ``drain_s`` after the window closes count as unanswered."""
    span = span or _no_span
    max_batch = int(mix["max_batch"])
    with span("bench.generate"):
        due = open_schedule(mix, seconds, seed)
        draws = query_draws(len(pool), len(due), seed)
    win, i = Window(), 0
    t0 = clock()
    while i < len(due):
        now = clock() - t0
        if now > seconds + drain_s:
            win.unanswered = len(due) - i
            break
        if due[i] > now:
            with span("bench.wait"):
                sleep(due[i] - now)
            now = clock() - t0
            win.lateness.append(now - due[i])
            now = max(now, due[i])
        j = i + int(np.searchsorted(due[i:i + max_batch], now, "right"))
        start, end, ids, dists = _serve(search, pool, draws[i:j], t0, clock,
                                        span)
        win.calls.append(Call(start, end, draws[i:j], due[i:j], ids, dists))
        i = j
    win.seconds = win.calls[-1].end if win.calls else clock() - t0
    return win


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _no_span(name):
    return _NoSpan()


LOOPS = {"closed": closed_loop, "open": open_loop}

