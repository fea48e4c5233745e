"""The plain reference and the comparison that decides ``correct``.

Nothing here imports the program.  The reference is the paper's
per-probe patience search written out in ``jax.numpy``: rank every
centroid by inner product, walk the first N clusters in that order,
score each list's docs, merge into a running top-k, and stop once the
top-k has stayed >= phi percent unchanged for Delta probes in a row.
It scores the benchmark's own copy of the generated docs; from the
program's index it takes only what defines the partition (centroids
and list membership), and only after holding that partition to the
configuration by its own arithmetic:

* :func:`index_faults`: every generated doc once, unchanged, under its
  id, in one list of at most ``list_pad`` rows; the rows and centroids
  the search reads in the configuration's storage type;
* :func:`kmeans_excess`: how far the centroids and the membership lie
  from a k-means fixed point, measured on the generated docs in f32:
  the centroids against their lists' own means (``centroid_drift``)
  and each doc's list against its nearest centroid
  (``assign_excess``), each as a share of the quantization error.

A live index (``bench/live.py``) is searched the same way over the
union of the built lists and the added docs, each added doc placed in
its nearest centroid's list by this module's own f32 arithmetic
(:func:`nearest_lists`, :func:`with_members`); each query sees only
the ids live in the version it was served on.

Precision: by default every product is f32 (``Precision.HIGHEST``),
as the configuration states.  ``lowp=True`` rounds queries, docs and
centroids to bfloat16 and accumulates in f32: the control, which the
comparison has to refuse.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Partition(NamedTuple):
    """The index's clustering, as host arrays."""
    centroids: np.ndarray   # (C, d) f32
    table: np.ndarray       # (C, list_pad) int32 member ids, -1 pad


def _dot(a, b, lowp: bool, spec: str):
    if lowp:
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def index_faults(n_docs: int, prints: np.ndarray, ids: np.ndarray,
                 row_prints: np.ndarray, offsets: np.ndarray,
                 sizes: np.ndarray, *, list_pad: int,
                 dtypes: Dict[str, str], storage: str) -> Dict[str, int]:
    """How the index departs from the generated corpus and the
    configuration.

    ``ids``/``row_prints``: per stored row, its doc id (-1 = padding)
    and the fingerprint of its vector; ``offsets``/``sizes``: each
    inverted list's first row and length; ``dtypes``: the type of each
    float array the search reads (rows, centroids).  Every count is 0
    for an index that holds each generated doc exactly once, unchanged,
    under its own id, inside exactly one list of at most ``list_pad``
    rows, with every float array in the ``storage`` type."""
    valid = ids >= 0
    vid = ids[valid]
    out_of_range = int((vid >= n_docs).sum())
    vid = vid[vid < n_docs]
    counts = np.bincount(vid, minlength=n_docs)
    in_list = np.zeros(ids.shape[0] + 1, np.int64)
    np.add.at(in_list, offsets, 1)
    np.add.at(in_list, offsets + sizes, -1)
    in_list = np.cumsum(in_list)[:-1]
    ok_rows = valid & (ids < n_docs)
    return {
        "missing": int((counts == 0).sum()),
        "duplicated": int((counts > 1).sum()),
        "out_of_range": out_of_range,
        "changed": int((row_prints[ok_rows] != prints[ids[ok_rows]]).sum()),
        "outside_lists": int((valid & (in_list != 1)).sum()),
        "padding_in_lists": int((~valid & (in_list > 0)).sum()),
        "oversized": int((sizes > list_pad).sum()),
        "storage": sum(dt != storage for dt in dtypes.values()),
    }


def list_of(n_docs: int, ids: np.ndarray, offsets: np.ndarray,
            sizes: np.ndarray) -> np.ndarray:
    """(n_docs,) int32: the list that holds each doc, -1 for none."""
    sizes = sizes.astype(np.int64)
    starts = np.cumsum(sizes) - sizes
    rows = (np.arange(int(sizes.sum())) - np.repeat(starts, sizes)
            + np.repeat(offsets.astype(np.int64), sizes))
    lists = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    doc = ids[rows]
    ok = (doc >= 0) & (doc < n_docs)
    out = np.full(n_docs, -1, np.int32)
    out[doc[ok]] = lists[ok]
    return out


def partition_of(centroids: np.ndarray, ids: np.ndarray,
                 offsets: np.ndarray, sizes: np.ndarray,
                 list_pad: int) -> Partition:
    """Member ids of every list, padded with -1 to ``list_pad``."""
    lane = np.arange(list_pad)
    rows = np.minimum(offsets[:, None] + lane[None, :], ids.shape[0] - 1)
    table = np.where(lane[None, :] < sizes[:, None], ids[rows], -1)
    return Partition(np.asarray(centroids, np.float32),
                     table.astype(np.int32))


def with_members(part: Partition, ids: np.ndarray, lists: np.ndarray,
                 align: int = 128) -> Partition:
    """``part`` with ``ids`` appended to ``lists`` (one list per id,
    after the list's own members, in the order given); the table grows
    to a multiple of ``align`` that holds the longest list."""
    n_main = (part.table >= 0).sum(1)
    add = np.bincount(lists, minlength=n_main.size)
    width = max(part.table.shape[1], int((n_main + add).max(initial=0)))
    width = -(-width // align) * align
    table = np.full((n_main.size, width), -1, np.int32)
    table[:, :part.table.shape[1]] = part.table
    order = np.argsort(lists, kind="stable")
    ls = lists[order]
    first = np.searchsorted(ls, ls)                # first of its list
    table[ls, n_main[ls] + np.arange(ls.size) - first] = ids[order]
    return Partition(part.centroids, table)


@jax.jit
def _nearest(x, centroids):
    s = _dot(x, centroids, False, "bd,cd->bc") \
        - 0.5 * jnp.sum(centroids * centroids, 1)[None, :]
    return jnp.argmax(s, 1).astype(jnp.int32)


def nearest_lists(vecs: np.ndarray, centroids: np.ndarray,
                  block: int = 1024) -> np.ndarray:
    """Each vector's nearest centroid in squared L2, in f32 at
    ``HIGHEST`` (argmax of x.c - |c|^2 / 2), in blocks of one shape."""
    c = jnp.asarray(np.asarray(centroids, np.float32))
    out = []
    for lo in range(0, vecs.shape[0], block):
        x = np.zeros((block, vecs.shape[1]), np.float32)
        m = min(block, vecs.shape[0] - lo)
        x[:m] = vecs[lo: lo + m]
        out.append(np.asarray(_nearest(jnp.asarray(x), c))[:m])
    return np.concatenate(out) if out else np.zeros(0, np.int32)


@functools.partial(jax.jit, static_argnames=("block",))
def _quantization(docs, centroids, own, *, block: int):
    """Per block of docs: Σ ||x - c_own||² and Σ (||x - c_own||² -
    min_j ||x - c_j||²), f32 at ``HIGHEST``; docs with ``own`` -1 count
    nothing.  The second sum takes both distances from one row of
    scores, so each term is >= 0 exactly."""
    n, d = docs.shape
    half = 0.5 * jnp.sum(centroids * centroids, 1)
    n_blocks = -(-n // block)

    def body(i, acc):
        lo = i * block
        start = jnp.minimum(lo, n - block)
        x = jax.lax.dynamic_slice_in_dim(docs, start, block)
        a = jax.lax.dynamic_slice_in_dim(own, start, block)
        valid = ((start + jnp.arange(block)) >= lo) & (a >= 0)
        a = jnp.maximum(a, 0)
        s = _dot(x, centroids, False, "bd,cd->bc") - half[None, :]
        mine = jnp.take_along_axis(s, a[:, None], 1)[:, 0]
        gap = 2.0 * (jnp.max(s, 1) - mine)
        diff = x - centroids[a]
        err = jnp.sum(diff * diff, 1)
        part = jnp.stack([jnp.sum(jnp.where(valid, err, 0.0)),
                          jnp.sum(jnp.where(valid, gap, 0.0)),
                          jnp.sum((valid & (gap > 0)).astype(jnp.float32))])
        return acc.at[i].set(part)

    return jax.lax.fori_loop(0, n_blocks, body,
                             jnp.zeros((n_blocks, 3), jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_lists",))
def _member_means(docs, own, *, n_lists: int):
    """(C, d) mean of each list's docs, (C,) its size."""
    seg = jnp.where(own >= 0, own, n_lists)
    sums = jax.ops.segment_sum(docs, seg, num_segments=n_lists + 1)
    cnt = jax.ops.segment_sum(jnp.ones(own.shape, jnp.float32), seg,
                              num_segments=n_lists + 1)
    return sums[:n_lists] / jnp.maximum(cnt[:n_lists], 1.0)[:, None], \
        cnt[:n_lists]


def kmeans_excess(docs_dev, centroids: np.ndarray, own: np.ndarray
                  ) -> Dict[str, float]:
    """How far a partition lies from a k-means fixed point, on the
    generated docs.  With J = Σ ||x - c_own(x)||² (the quantization
    error of the stored centroids and membership):

    * ``centroid_drift`` = Σ_j n_j ||c_j - m_j||² / (J - that sum):
      the error the centroids add over their lists' own means m_j,
      as a share of the error of those means.  0 after an update step.
    * ``assign_excess`` = Σ_x (||x - c_own||² - min_j ||x - c_j||²)
      / (J - that sum): the error the membership adds over nearest-
      centroid assignment, as a share of the error of that assignment.
      0 after an assignment step, but for near-ties.

    Lloyd's k-means ends with both small; fewer iterations, centroids
    that are not their lists' means, or a membership that is not the
    nearest centroid's raise them."""
    c = jnp.asarray(np.asarray(centroids, np.float32))
    own_dev = jnp.asarray(own.astype(np.int32))
    n_lists = int(c.shape[0])
    block = max(128, min(4096, 1 << int(np.log2(max(1, (1 << 26)
                                                     // n_lists)))))
    block = min(block, int(docs_dev.shape[0]))
    q = np.asarray(_quantization(docs_dev, c, own_dev, block=block),
                   np.float64).sum(0)
    means, cnt = _member_means(docs_dev, own_dev, n_lists=n_lists)
    off = np.asarray(centroids, np.float64) - np.asarray(means, np.float64)
    drift = float((np.asarray(cnt, np.float64) * (off * off).sum(1)).sum())
    err, gap, n_gap = float(q[0]), float(q[1]), float(q[2])
    return {"centroid_drift": drift / max(err - drift, 1e-30),
            "assign_excess": gap / max(err - gap, 1e-30),
            "quantization_error": err,
            "docs_off_nearest": n_gap / max(1, int((own >= 0).sum()))}


@functools.partial(jax.jit, static_argnames=("n_probe", "lowp"))
def rank_clusters(queries, centroids, *, n_probe: int, lowp: bool = False):
    """(B, n_probe) cluster ids in descending inner product."""
    sims = _dot(queries, centroids, lowp, "bd,cd->bc")
    return jax.lax.top_k(sims, n_probe)[1].astype(jnp.int32)


def _rows(docs, extra, ids):
    """The vectors of ``ids``: rows of ``docs``, and from
    ``docs.shape[0]`` on rows of ``extra``."""
    if extra is None:
        return docs[jnp.maximum(ids, 0)]
    n = docs.shape[0]
    main = docs[jnp.clip(ids, 0, n - 1)]
    added = extra[jnp.clip(ids - n, 0, extra.shape[0] - 1)]
    return jnp.where((ids >= n)[..., None], added, main)


@functools.partial(jax.jit, static_argnames=("k", "n_probe", "delta",
                                             "lowp"))
def patience_search(queries, centroids, table, docs, phi, extra=None,
                    born=None, death=None, ver=None, *, k: int,
                    n_probe: int, delta: int, lowp: bool = False):
    """Per-probe patience search.  Returns (B, k) ids in descending
    score, (B, k) scores and (B,) probes taken.

    For a live index: ``extra`` holds the vectors of the ids from
    ``docs.shape[0]`` on (docs added after the build), and query b
    searches an id only where ``born[id] <= ver[b] < death[id]``: the
    versions of the index in which that id was live."""
    b = queries.shape[0]
    rank = rank_clusters(queries, centroids, n_probe=n_probe, lowp=lowp)

    def cond(s):
        h, _, _, _, active, _ = s
        return (h < n_probe) & jnp.any(active)

    def body(s):
        h, ts, ti, ctr, active, probes = s
        ids = table[rank[:, h]]                                # (B, L)
        if born is not None:
            i = jnp.maximum(ids, 0)
            v = ver[:, None]
            ids = jnp.where((born[i] <= v) & (death[i] > v), ids, -1)
        vecs = _rows(docs, extra, ids)                         # (B, L, d)
        sc = _dot(vecs, queries, lowp, "bld,bd->bl")
        sc = jnp.where(ids >= 0, sc, -jnp.inf)
        ns, pos = jax.lax.top_k(jnp.concatenate([ts, sc], 1), k)
        ni = jnp.take_along_axis(jnp.concatenate([ti, ids], 1), pos, 1)
        # phi: share of the previous top-k (real entries) still in it
        kept = ((ti[:, :, None] == ni[:, None, :])
                & (ti[:, :, None] >= 0)).sum((1, 2))
        phi_v = 100.0 * kept.astype(jnp.float32) / k
        ts = jnp.where(active[:, None], ns, ts)
        ti = jnp.where(active[:, None], ni, ti)
        ctr = jnp.where(active & (h >= 1) & (phi_v >= phi), ctr + 1, 0)
        probes = jnp.where(active, h + 1, probes)
        done = (ctr >= delta) | (h + 1 >= n_probe)
        return h + 1, ts, ti, ctr, active & ~done, probes

    init = (jnp.int32(0), jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), bool), jnp.zeros((b,), jnp.int32))
    _, ts, ti, _, _, probes = jax.lax.while_loop(cond, body, init)
    return ti, ts, probes


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_topk(queries, docs, *, k: int, block: int = 65536):
    """Exact top-k ids over all docs, blocked over doc rows."""
    n = docs.shape[0]
    block = min(block, n)
    b = queries.shape[0]

    def body(j, carry):
        lo = j * block
        start = jnp.minimum(lo, n - block)
        rows = start + jnp.arange(block)
        sc = _dot(queries, jax.lax.dynamic_slice_in_dim(docs, start, block),
                  False, "bd,nd->bn")
        sc = jnp.where((rows >= lo)[None, :], sc, -jnp.inf)
        bs, bi = jax.lax.top_k(sc, k)
        cs = jnp.concatenate([carry[0], bs], 1)
        ci = jnp.concatenate([carry[1], start + bi.astype(jnp.int32)], 1)
        s, p = jax.lax.top_k(cs, k)
        return s, jnp.take_along_axis(ci, p, 1)

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // block), body, init)[1]


def search_blocks(queries: np.ndarray, part: Partition, docs_dev, cfg: dict,
                  *, lowp: bool = False, q_block: int = 128, extra=None,
                  born=None, death=None, ver: Optional[np.ndarray] = None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference ids and probes for ``queries`` (host), in query blocks
    of one compiled shape.  ``extra``/``born``/``death`` (device) and
    ``ver`` (host, one per query): a live index, as
    :func:`patience_search` takes it."""
    n = queries.shape[0]
    cen = jnp.asarray(part.centroids)
    table = jnp.asarray(part.table)
    n_probe = min(cfg["n_probe"], part.centroids.shape[0])
    ids, probes = [], []
    for lo in range(0, n, q_block):
        q = np.zeros((q_block, queries.shape[1]), np.float32)
        m = min(q_block, n - lo)
        q[:m] = queries[lo: lo + m]
        v = None
        if ver is not None:
            v = np.zeros(q_block, np.int32)
            v[:m] = ver[lo: lo + m]
            v = jnp.asarray(v)
        i, _, p = patience_search(
            jnp.asarray(q), cen, table, docs_dev,
            jnp.float32(cfg["patience_phi"]), extra, born, death, v,
            k=cfg["k"], n_probe=n_probe, delta=cfg["patience_delta"],
            lowp=lowp)
        ids.append(np.asarray(i)[:m])
        probes.append(np.asarray(p)[:m])
    return np.concatenate(ids), np.concatenate(probes)


def exact_blocks(queries: np.ndarray, docs_dev, k: int,
                 q_block: int = 128) -> np.ndarray:
    out = []
    for lo in range(0, queries.shape[0], q_block):
        q = np.zeros((q_block, queries.shape[1]), np.float32)
        m = min(q_block, queries.shape[0] - lo)
        q[:m] = queries[lo: lo + m]
        out.append(np.asarray(exact_topk(jnp.asarray(q), docs_dev,
                                         k=k))[:m])
    return np.concatenate(out)


def disagreement(ids: np.ndarray, probes: np.ndarray, ref_ids: np.ndarray,
                 ref_probes: np.ndarray) -> Dict[str, float]:
    """Share of queries whose top-k id set or probe count differs from
    the reference's (``queries_differ``, the number compared), and the
    two parts apart."""
    ids_d = ~(np.sort(ids, 1) == np.sort(ref_ids, 1)).all(1)
    probes_d = probes != ref_probes
    return {"queries_differ": float((ids_d | probes_d).mean()),
            "ids_differ": float(ids_d.mean()),
            "probes_differ": float(probes_d.mean())}


def r_star(ids: np.ndarray, exact: np.ndarray) -> Dict[str, float]:
    """R*@1 and R*@k against the exact top-k (copied from the program's
    ``core/metrics.py``: share of exact top-1 found at rank 0, mean
    top-k overlap)."""
    k = exact.shape[1]
    inter = (ids[:, :, None] == exact[:, None, :]).any(-1)
    return {"r_star_at_1": float(np.mean(ids[:, 0] == exact[:, 0])),
            "r_star_at_k": float(np.mean(inter.sum(1) / k))}
