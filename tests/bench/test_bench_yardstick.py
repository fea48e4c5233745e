"""The bytes the probes need, and the table of peaks."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference, yardstick  # noqa: E402
from repro.core import build_index  # noqa: E402


def test_needed_bytes_by_hand():
    sizes = np.array([3, 5, 0, 7])
    ranks = np.array([[1, 3, 0], [2, 0, 1]])
    probes = np.array([2, 3])
    # query 0 reads lists 1 and 3 (5 + 7 rows), query 1 lists 2, 0, 1
    # (0 + 3 + 5 rows): 20 rows of 4 f32 and one int32 id each
    assert yardstick.needed_bytes(ranks, probes, sizes, 4) == 20 * 20


def test_needed_bytes_on_a_tiny_index():
    rng = np.random.default_rng(0)
    docs = rng.normal(size=(3000, 16)).astype(np.float32)
    ix = build_index(docs, 24, list_pad=256, n_iters=2, seed=0)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    ranks = np.asarray(reference.rank_clusters(q, ix.centroids, n_probe=6))
    probes = np.array([1, 6, 3, 0, 2])
    sizes = np.asarray(ix.cluster_sizes)
    ids = np.asarray(ix.doc_ids)
    offs = np.asarray(ix.cluster_offsets)
    rows = 0
    for qi in range(5):
        for h in range(probes[qi]):
            c = ranks[qi, h]
            rows += int((ids[offs[c]: offs[c] + sizes[c]] >= 0).sum())
    assert yardstick.needed_bytes(ranks, probes, sizes, 16) == rows * 68


def test_peaks_come_from_the_table():
    assert yardstick.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        yardstick.peak("TPU v9 imaginary", "hbm_bytes_per_s")
