"""Median ms, on the host clock, of the window's write batches: add,
delete, the log's flush and the publish of the new version (the
``bench.write`` span).  None where the window applied none."""
import numpy as np


def read(w):
    ms = (w.live or {}).get("write_ms")
    return float(np.median(ms)) if ms is not None and ms.size else None
