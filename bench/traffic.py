"""Arrival schedules from a traffic file, and the open-loop source.

A traffic file (``bench/traffic/<name>.json``) describes its arrivals:

* ``{"kind": "poisson", "phases": [{"seconds": s, "rate_qps": r}, ...]}``
  cycles through the phases for the whole window; a phase of rate 0 is
  an off period, so on/off bursts are data, not code.  Inside a phase
  the gaps between arrivals are the ``n`` quantiles of the exponential
  distribution, in an order drawn from the seed: every seed gets the
  same set of gaps, and so the same amount of work, in another order.
* ``{"kind": "backlog"}`` makes every query due at once; the query
  count is the warm-up's rate times the window, so the one ``serve``
  call lasts about as long as the window.

The program's ``WaveScheduler.serve`` takes an array of queries and
admits rows as lanes free up.  :class:`OpenLoopSource` is such an
array whose rows appear only once they are due on the scheduler's own
clock, so the program's refill admits each query when it is due and a
lane is free.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np


def arrival_ms(arrivals: dict, seconds: float,
               rng: np.random.Generator) -> np.ndarray:
    """Due times in ms from the window's start, ascending."""
    if arrivals["kind"] != "poisson":
        raise ValueError(f"no schedule for arrivals {arrivals['kind']!r}")
    out: List[np.ndarray] = []
    t = 0.0
    phases = arrivals["phases"]
    i = 0
    while t < seconds:
        ph = phases[i % len(phases)]
        i += 1
        dur = min(ph["seconds"], seconds - t)
        n = int(round(ph["rate_qps"] * dur))
        if n:
            gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
            gaps = rng.permutation(gaps)
            before = np.cumsum(gaps) - gaps      # each arrival's offset
            out.append(1000.0 * (t + dur * before / gaps.sum()))
        t += dur
    return np.concatenate(out) if out else np.zeros(0)


class OpenLoopSource:
    """Read-only array of queries whose rows become visible when due.

    ``clock`` is handed to ``WaveScheduler(clock=...)``.  A slice
    returns only rows due by the scheduler's latest clock reading, and
    records that reading as each row's admission time."""

    def __init__(self, queries: np.ndarray, due_ms: np.ndarray):
        self._q = queries
        self._due = due_ms
        self.shape = queries.shape
        self.admit_ms = np.full(due_ms.shape[0], np.nan)
        self._t0: Optional[float] = None
        self._last = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def clock(self) -> float:
        self._last = (time.perf_counter() - self._t0) * 1000.0
        return self._last

    @property
    def due_ms(self) -> np.ndarray:
        return self._due

    @property
    def last_ms(self) -> float:
        """The scheduler's latest clock reading: the time its current
        pass admits at."""
        return self._last

    def elapsed_ms(self) -> float:
        """Time since :meth:`start`, read without moving what the next
        slice admits."""
        return (time.perf_counter() - self._t0) * 1000.0

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, sl: slice) -> np.ndarray:
        if not isinstance(sl, slice) or sl.step not in (None, 1):
            raise TypeError("OpenLoopSource supports plain slices only")
        start = sl.start or 0
        due_now = int(np.searchsorted(self._due, self._last, side="right"))
        stop = max(start, min(sl.stop, self.shape[0], due_now))
        self.admit_ms[start:stop] = self._last
        return self._q[start:stop]
