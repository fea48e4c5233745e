"""Readers of the serve loop's own stage times and admission counters
(``ServeReport.stage_ms``, ``admit_calls`` and ``admitted``), shared by
the metric files of each quantity under each traffic.  Each returns
``None`` where the report holds no such field, as in a program that
predates them.

Medians over waves, not means: a process freeze of 110-750 ms lands in
whichever stage runs at the time, and one in a 5 s window would move a
mean by more than a millisecond."""
from __future__ import annotations

from typing import Optional

import numpy as np


def _stage_sums(w, waits: bool) -> Optional[np.ndarray]:
    """Per wave, the summed ms of the stages that wait on the device
    (``waits``) or of the host's own stages."""
    ms = getattr(w.report, "stage_ms", None)
    if ms is None or len(ms) == 0:
        return None
    from repro.core.serving import STAGES, WAIT_STAGES
    cols = np.isin(STAGES, WAIT_STAGES) == waits
    return np.asarray(ms)[:, cols].sum(axis=1)


def host_ms(w) -> Optional[float]:
    """Median over waves of the host stages' ms."""
    s = _stage_sums(w, waits=False)
    return None if s is None else float(np.median(s))


def wait_ms(w) -> Optional[float]:
    """Median over waves of the ms spent waiting on ``_advance`` and
    ``_admit``."""
    s = _stage_sums(w, waits=True)
    return None if s is None else float(np.median(s))


def admit_row_use(w) -> Optional[float]:
    """Of the rows ``_admit`` ranks against every centroid (a wave's
    worth a call), the share that held a query."""
    calls = getattr(w.report, "admit_calls", 0)
    if not calls:
        return None
    return w.report.admitted / (calls * w.cfg["wave_size"])
