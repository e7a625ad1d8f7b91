"""The general traffic generator: reads a traffic file and makes a run's
work from ``--seed``.

Two loops:

* ``closed``: self-play games from the empty board, one per slot, each
  refilled as soon as it ends; the file names how many spare games wait
  in the queue.  A game is its RNG key.
* ``open``: best-move queries on a schedule.  The file gives the arrival
  process (``poisson`` at ``rate_per_s``), the range of prefix lengths,
  and a weighted mix of ``sims``.  Each query is a position reached by a
  uniform random legal prefix (no passes) from the empty board, by the
  numpy rules of :mod:`.gorules`, with the side to move, a per-query key
  and a budget.

Every seed gets the same multiset of gaps, prefix lengths and budgets,
in another order: the seed changes which position arrives when,
not how much work a run holds.  The gaps are the quantiles of the
exponential distribution, shuffled (the open-loop Poisson generator of
``benchmarks/bench_load.py``, with the clock moved to the due time).
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

from .gorules import Rules, State


class Query(NamedTuple):
    due_s: float             # offset from the window's start
    prefix: int              # moves played from the empty board
    state: State             # the position, side to move included
    key: np.ndarray          # u32[2]
    sims: int


def _multiset(items, n: int) -> list:
    """``n`` entries in proportion to the ``[value, weight]`` pairs."""
    vals = [v for v, _ in items]
    w = np.asarray([float(x) for _, x in items])
    counts = np.floor(w / w.sum() * n).astype(int)
    for i in np.argsort(-(w / w.sum() * n - counts))[:n - counts.sum()]:
        counts[i] += 1
    return [v for v, c in zip(vals, counts) for _ in range(c)]


def due_times(traffic: dict, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Due offsets of the queries of a window of ``seconds``."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    t = np.cumsum(rng.permutation(gaps))
    return t[t < seconds]


def random_position(rules: Rules, moves: int,
                    rng: np.random.Generator) -> State:
    """``moves`` uniformly random legal moves (no passes) from the empty
    board; stops early if no point is legal."""
    st = rules.initial()
    for _ in range(moves):
        legal = rules.legal(st)[:-1]
        cand = np.flatnonzero(legal)
        if cand.size == 0:
            break
        st = rules.play(st, int(rng.choice(cand)))
    return st


def open_loop(traffic: dict, size: int, seconds: float,
              seed: int) -> List[Query]:
    """The queries due in a window of ``seconds``, in due order."""
    rng = np.random.default_rng(seed)
    rules = Rules(size)
    due = due_times(traffic, seconds, rng)
    n = len(due)
    lo, hi = traffic["prefix_moves"]
    prefixes = rng.permutation(
        [lo + i % (hi - lo + 1) for i in range(n)])
    sims = rng.permutation(_multiset(traffic["sims"], n))
    keys = rng.integers(0, 2 ** 32, (n, 2), dtype=np.uint32)
    return [Query(float(due[i]), int(prefixes[i]),
                  random_position(rules, int(prefixes[i]), rng), keys[i],
                  int(sims[i])) for i in range(n)]


def game_keys(n: int, seed: int) -> np.ndarray:
    """``n`` self-play game keys (u32[n, 2]) from the seed."""
    return np.random.default_rng(seed).integers(0, 2 ** 32, (n, 2),
                                                dtype=np.uint32)
