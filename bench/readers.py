"""Readers shared by metric files of the same quantity for different
traffic (``wave_ms.steady`` and ``wave_ms.batch`` read alike, but move
different end-to-end metrics).  Each returns ``None`` where the window
holds nothing to read."""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench import yardstick

#: The fused scan+merge kernel as the device trace names it.
KERNEL = "ivf_scan_merge"


def wave_ms(w) -> Optional[float]:
    """Window wall time per wave the scheduler ran."""
    return 1e3 * w.wall_s / w.report.waves if w.report.waves else None


def admit_share(w) -> Optional[float]:
    """Device time of the ``_admit`` program over device busy time, %."""
    if w.trace is None or w.trace.busy_s <= 0:
        return None
    s = w.trace.module_seconds("_admit")
    return 100.0 * s / w.trace.busy_s if s > 0 else None


def scan_merge_roofline(w) -> Optional[float]:
    """Bytes the probes need over the kernel's device time, as a share
    of the chip's HBM bandwidth, %."""
    if w.trace is None or not w.needed_bytes:
        return None
    s = w.trace.op_seconds(KERNEL)
    if s <= 0:
        return None
    bw = yardstick.peak(w.device_kind, "hbm_bytes_per_s")
    return 100.0 * w.needed_bytes / (s * bw)


def idle_share(w) -> Optional[float]:
    """Share of the traced window in which no operation ran, %."""
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share


def percentile(x: Optional[np.ndarray], q: float) -> Optional[float]:
    if x is None or x.size == 0:
        return None
    return float(np.percentile(x, q))
