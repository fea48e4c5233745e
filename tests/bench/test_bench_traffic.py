"""Arrival schedules and the open-loop source."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic  # noqa: E402

STEADY = {"kind": "poisson", "phases": [{"seconds": 1.0, "rate_qps": 400}]}


def test_every_seed_gets_the_same_gaps_in_another_order():
    a = traffic.arrival_ms(STEADY, 3.0, np.random.default_rng(1))
    b = traffic.arrival_ms(STEADY, 3.0, np.random.default_rng(2))
    assert a.shape == b.shape == (1200,)
    assert (np.diff(a) >= 0).all() and a[0] == 0.0 and a[-1] < 3000.0
    # per one-second phase, the multiset of gaps is the same
    ga = np.sort(np.diff(np.append(a[:400], 1000.0)))
    gb = np.sort(np.diff(np.append(b[:400], 1000.0)))
    assert np.allclose(ga, gb)
    assert not np.allclose(np.diff(a[:400]), np.diff(b[:400]))


def test_off_phases_send_nothing():
    burst = {"kind": "poisson", "phases": [{"seconds": 0.5, "rate_qps": 200},
                                           {"seconds": 0.5, "rate_qps": 0}]}
    t = traffic.arrival_ms(burst, 2.0, np.random.default_rng(0))
    assert t.shape == (200,)
    assert not ((t % 1000.0) >= 500.0).any()


def test_unknown_arrivals_are_refused():
    with pytest.raises(ValueError):
        traffic.arrival_ms({"kind": "backlog"}, 1.0,
                           np.random.default_rng(0))


def test_open_loop_source_shows_only_due_rows():
    q = np.arange(10, dtype=np.float32).reshape(5, 2)
    src = traffic.OpenLoopSource(q, np.array([0.0, 5.0, 5.0, 20.0, 50.0]))
    src._t0 = 0.0
    src._last = 6.0                 # the scheduler's latest reading
    assert len(src) == 5 and src.shape == (5, 2)
    assert src[0:4].tolist() == q[:3].tolist()
    assert src[3:7].shape == (0, 2)
    assert np.isnan(src.admit_ms[3:]).all()
    src._last = 60.0
    assert src[3:7].tolist() == q[3:].tolist()
    assert src.admit_ms.tolist() == [6.0, 6.0, 6.0, 60.0, 60.0]
