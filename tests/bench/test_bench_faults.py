"""The check refuses a broken timed path, a broken build, and the
control.

Each test runs the tiny cell of ``test_bench_harness`` on the CPU with
one fault planted in the program underneath the harness, and sees
``correct`` come out false.  The control (the reference in bf16 in the
program's place) is held against the f32 reference the same way."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from test_bench_harness import COMMON, TINY_CFG, make_root  # noqa: E402

from bench import corpus, harness, reference  # noqa: E402
from repro.core import build_index, serving  # noqa: E402
from repro.core import kmeans as km  # noqa: E402
from repro.kernels import ivf_scan_merge as ism  # noqa: E402

SEED = 4


@pytest.fixture
def fresh_jit():
    """Faults patched into jitted code need a retrace, before and
    after."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(tmp_path, cell="star768-steady"):
    return harness.run(cell, SEED, 1.0, False, root=make_root(tmp_path),
                       platform="cpu")


def _bf16_dot(q, tile):
    return jax.lax.dot_general(
        q.astype(jnp.bfloat16), tile.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _altered_answer(advance):
    def wrapped(index, state, *a, **kw):
        st = advance(index, state, *a, **kw)
        bad = (st.topk_ids[:, 0] + 1) % TINY_CFG["n_docs"]
        return st._replace(topk_ids=st.topk_ids.at[:, 0].set(bad))
    return wrapped


def _patience_off_by_one(advance):
    def wrapped(index, state, *a, lane_delta, **kw):
        return advance(index, state, *a, lane_delta=lane_delta + 1, **kw)
    return wrapped


def _dropped_doc(index_fn):
    def wrapped(*a, **kw):
        ix = index_fn(*a, **kw)
        row = int(np.nonzero(np.asarray(ix.doc_ids) >= 0)[0][0])
        ix.doc_ids = ix.doc_ids.at[row].set(-1)
        return ix
    return wrapped


def _centroids_bf16(index_fn):
    def wrapped(*a, **kw):
        ix = index_fn(*a, **kw)
        ix.centroids = ix.centroids.astype(jnp.bfloat16)
        return ix
    return wrapped


def _one_lloyd_iteration(index_fn):
    def wrapped(*a, **kw):
        return index_fn(*a, **dict(kw, n_iters=1))
    return wrapped


def _last_assignment_skipped(fit):
    """The membership handed on is the one the final centroids were
    averaged from, not a fresh assignment to them."""
    def wrapped(x, init, *, n_clusters, n_iters=10, block=4096):
        cen, assign = fit(x, init, n_clusters=n_clusters,
                          n_iters=n_iters - 1, block=block)
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        cnt = jax.ops.segment_sum(jnp.ones(x.shape[0], x.dtype), assign,
                                  num_segments=n_clusters)
        new = sums / jnp.maximum(cnt, 1.0)[:, None]
        return jnp.where((cnt > 0)[:, None], new, cen), assign
    return wrapped


FAULTS = {
    # the control in the program's place: the kernel scores in bf16
    "kernel_bf16": (ism, "_dot", lambda _: _bf16_dot, "queries_differ"),
    # an answer altered where it is produced
    "answer_altered": (serving, "_advance", _altered_answer, "queries_differ"),
    # the exit evaluation broken: patience counted one probe too long
    "patience_off_by_one": (serving, "_advance", _patience_off_by_one,
                            "queries_differ"),
    # centroid ranking reversed
    "ranking_reversed": (serving, "centroid_sims",
                         lambda f: (lambda q, c: -f(q, c)), "queries_differ"),
    # the index loses a document
    "doc_dropped": (harness, "build_index", _dropped_doc, "index_faults"),
    # the build's shortcuts: centroids held in bf16, k-means stopped
    # after one iteration, the last assignment step skipped
    "centroids_bf16": (harness, "build_index", _centroids_bf16,
                       "index_faults"),
    "one_lloyd_iteration": (harness, "build_index", _one_lloyd_iteration,
                            "centroid_drift"),
    "last_assignment_skipped": (km, "kmeans_fit", _last_assignment_skipped,
                                "assign_excess"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_makes_the_run_incorrect(tmp_path, monkeypatch,
                                               fresh_jit, fault):
    mod, name, make, number = FAULTS[fault]
    monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    out = _run(tmp_path)
    assert out["correct"] is False
    chk = out["checks"][number]
    assert chk["value"] > chk["limit"]


def _one_lane_altered(advance):
    def wrapped(index, state, *a, **kw):
        st = advance(index, state, *a, **kw)
        bad = (st.topk_ids[0, 0] + 1) % TINY_CFG["n_docs"]
        return st._replace(topk_ids=st.topk_ids.at[0, 0].set(bad))
    return wrapped


def test_fault_in_one_lane_of_the_wave_fails_the_chip_limit(
        tmp_path, monkeypatch, fresh_jit):
    """A fault confined to one lane of a full wave of 64 spoils about
    one query in 64; the chip configurations' ``queries_differ`` limit
    lies below that share.  The run checks every query it served."""
    limit = harness.load("configs", "msmarco-star-2m")["limits"][
        "queries_differ"]
    assert limit == harness.load("configs", "bigann-10m")["limits"][
        "queries_differ"]
    cfg = dict(TINY_CFG, wave_size=64,
               limits=dict(TINY_CFG["limits"], queries_differ=limit))
    root = make_root(tmp_path, cfg=cfg,
                     common=dict(COMMON, check_queries=4096))
    monkeypatch.setattr(serving, "_advance",
                        _one_lane_altered(serving._advance))
    out = harness.run("bigann128-batch", SEED, 1.0, False, root=root,
                      platform="cpu")
    chk = out["checks"]["queries_differ"]
    assert out["correct"] is False
    assert limit < chk["value"] < 4 / 64


def test_control_in_bf16_fails_the_limits():
    """The bf16 reference against the f32 one, at the tiny size: its
    numbers exceed the configuration's limits."""
    cfg = TINY_CFG
    docs, q, _ = corpus.generate(SEED, cfg, 256,
                                 {"hard_frac": 0.35, "easy_noise": 0.15})
    ix = build_index(docs, cfg["n_clusters"], list_pad=cfg["list_pad"],
                     n_iters=cfg["kmeans_iters"], seed=SEED)
    part = reference.partition_of(
        np.asarray(ix.centroids), np.asarray(ix.doc_ids),
        np.asarray(ix.cluster_offsets), np.asarray(ix.cluster_sizes),
        ix.list_pad)
    d = jnp.asarray(docs)
    ids, probes = reference.search_blocks(q, part, d, cfg)
    c_ids, c_probes = reference.search_blocks(q, part, d, cfg, lowp=True)
    numbers = reference.disagreement(c_ids, c_probes, ids, probes)
    assert numbers["queries_differ"] > cfg["limits"]["queries_differ"]
