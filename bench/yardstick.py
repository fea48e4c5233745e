"""What the work needs, and what the chip can give.

* :func:`needed_bytes`: the HBM bytes the probes actually taken need,
  Σ over probes of the probed list's true size × (d × 4 + 4) bytes of
  f32 row and int32 id.  Padding rows, padding slots and slots after an
  exit earn no credit, whatever the implementation streams.  One
  probe's arithmetic is 2·d flops per row against 4·d + 4 bytes, about
  0.5 flop/byte, so HBM bandwidth is the bound.
* :func:`peak`: the chip's published peaks, from ``peaks.json``, keyed
  by ``device_kind``.  A kind missing from the table is an error.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def needed_bytes(ranks: np.ndarray, probes: np.ndarray,
                 sizes: np.ndarray, dim: int) -> float:
    """``ranks`` (Q, N): each query's clusters in probe order;
    ``probes`` (Q,): probes it took; ``sizes`` (C,): true list sizes."""
    taken = np.arange(ranks.shape[1])[None, :] < probes[:, None]
    rows = np.where(taken, sizes[ranks], 0).sum(dtype=np.int64)
    return float(rows) * (dim * 4 + 4)


def peak(kind: str, what: str) -> float:
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}; add "
                       f"its published peaks with their source")
    return float(table[kind][what])
