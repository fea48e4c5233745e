"""Batched Lloyd k-means in JAX (the IVF coarse quantizer).

FAISS trains the IVF coarse quantizer with k-means on a sample of the
corpus; we do the same. The assignment step is a blocked matmul (MXU
friendly); the update step is a segment_sum. A shard_map variant
distributes the assignment over the `data` mesh axis for corpus-scale
builds (used by the ivf_build dry-run cell).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _assign_block(x: jnp.ndarray, centroids: jnp.ndarray,
                  block: int = 4096) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Nearest-centroid assignment by inner product, blocked over rows.

    A ragged tail is covered by one last block that ends at row n and
    overlaps the one before it (overlapping rows are recomputed to the
    same values), so the corpus is never padded or copied."""
    n = x.shape[0]
    block = min(block, n)
    c_sq = jnp.sum(centroids * centroids, axis=1)  # (C,)

    def body(i, carry):
        assign, best = carry
        start = jnp.minimum(i * block, n - block)
        xb = jax.lax.dynamic_slice_in_dim(x, start, block, axis=0)
        # squared L2 = |x|^2 - 2 x.c + |c|^2 ; |x|^2 constant per row
        sims = xb @ centroids.T - 0.5 * c_sq[None, :]
        a = jnp.argmax(sims, axis=1).astype(jnp.int32)
        s = jnp.max(sims, axis=1)
        assign = jax.lax.dynamic_update_slice_in_dim(assign, a, start, 0)
        best = jax.lax.dynamic_update_slice_in_dim(best, s, start, 0)
        return assign, best

    assign = jnp.zeros((n,), jnp.int32)
    best = jnp.zeros((n,), x.dtype)
    return jax.lax.fori_loop(0, -(-n // block), body, (assign, best),
                             unroll=False)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters", "block"))
def kmeans_fit(x: jnp.ndarray, init: jnp.ndarray, *, n_clusters: int,
               n_iters: int = 10, block: int = 4096) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Lloyd iterations from ``init`` centroids. Returns (centroids, assign)."""

    def step(carry, _):
        centroids = carry
        assign, _ = _assign_block(x, centroids, block)
        sums = jax.ops.segment_sum(x, assign, num_segments=n_clusters)
        counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), assign,
                                     num_segments=n_clusters)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # keep old centroid for empty clusters
        centroids = jnp.where((counts > 0)[:, None], new, centroids)
        return centroids, counts

    centroids, _ = jax.lax.scan(step, init, None, length=n_iters)
    assign, _ = _assign_block(x, centroids, block)
    return centroids, assign


def kmeans(x: np.ndarray, n_clusters: int, *, n_iters: int = 10,
           seed: int = 0, block: int = 4096) -> Tuple[np.ndarray, np.ndarray]:
    """Host entry point: random-sample init (FAISS default) + Lloyd."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    idx = rng.choice(n, size=min(n_clusters, n), replace=False)
    init = np.asarray(x[idx], dtype=np.float32)
    if init.shape[0] < n_clusters:  # corpus smaller than C: jitter duplicates
        extra = init[rng.integers(0, init.shape[0], n_clusters - init.shape[0])]
        extra = extra + rng.normal(0, 1e-3, extra.shape).astype(np.float32)
        init = np.concatenate([init, extra], 0)
    centroids, assign = kmeans_fit(jnp.asarray(x, jnp.float32),
                                   jnp.asarray(init), n_clusters=n_clusters,
                                   n_iters=n_iters, block=block)
    return np.asarray(centroids), np.asarray(assign)


def retrain(x: np.ndarray, centroids: np.ndarray, *, n_iters: int = 4,
            block: int = 4096) -> Tuple[np.ndarray, np.ndarray]:
    """Warm-start Lloyd re-fit for background re-clustering.

    Starts from the serving centroids (already near the corpus modes,
    so a few iterations suffice) and keeps the cluster COUNT fixed —
    no ``split_oversized`` — so the rebuilt index keeps its compiled
    search shapes; entries overflowing ``list_pad`` under the new
    assignment spill into the rebuild candidate's delta buffer exactly
    like ``merge_delta`` spill-back.  Deterministic: same (corpus,
    centroids, n_iters) always yields the same result, which is what
    lets the rebuild chaos drill demand bit-identical recovery.

    Returns ``(new_centroids, assign)`` as host arrays.  An empty
    corpus returns the input centroids unchanged.
    """
    centroids = np.asarray(centroids, np.float32)
    x = np.asarray(x, np.float32)
    if x.shape[0] == 0:
        return centroids.copy(), np.zeros(0, np.int32)
    new, assign = kmeans_fit(jnp.asarray(x), jnp.asarray(centroids),
                             n_clusters=centroids.shape[0],
                             n_iters=n_iters, block=block)
    return np.asarray(new), np.asarray(assign)


def sharded_assign_step(mesh, data_axis: str = "data"):
    """shard_map'd assignment+partial-stats step for corpus-scale k-means.

    Each data shard computes assignments for its rows and the *partial*
    (sum, count) statistics; a psum over the data axis yields the global
    Lloyd update. Used by the ``ivf_build`` dry-run cell.
    """
    from jax.sharding import PartitionSpec as P

    def local_step(x, centroids):
        assign, _ = _assign_block(x, centroids, 4096)
        nc = centroids.shape[0]
        sums = jax.ops.segment_sum(x, assign, num_segments=nc)
        counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), x.dtype), assign,
                                     num_segments=nc)
        sums = jax.lax.psum(sums, data_axis)
        counts = jax.lax.psum(counts, data_axis)
        new = jnp.where((counts > 0)[:, None],
                        sums / jnp.maximum(counts, 1.0)[:, None], centroids)
        return new, assign

    return jax.shard_map(local_step, mesh=mesh,
                         in_specs=(P(data_axis, None), P()),
                         out_specs=(P(), P(data_axis)),
                         check_vma=False)


def split_oversized(x: np.ndarray, centroids: np.ndarray, assign: np.ndarray,
                    max_size: int, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Recursively 2-means-split clusters larger than ``max_size``.

    Keeps every inverted list <= list_pad so a probe is exactly one
    contiguous (list_pad, d) tile (DESIGN §2: balanced IVF layout).
    """
    rng = np.random.default_rng(seed)
    centroids = list(np.asarray(centroids))
    assign = np.asarray(assign).copy()
    # member rows of every oversized cluster, ascending, from one sort
    sizes = np.bincount(assign, minlength=len(centroids))
    starts = np.cumsum(sizes) - sizes
    order = np.argsort(assign, kind="stable")
    queue = [int(c) for c in np.nonzero(sizes > max_size)[0]]
    members_of = {c: order[starts[c]: starts[c] + sizes[c]] for c in queue}
    while queue:
        c = queue.pop()
        members = members_of[c]
        if members.size <= max_size:
            continue
        pts = x[members]
        # cheap 2-means: two random seeds, 4 Lloyd iterations
        seeds = pts[rng.choice(pts.shape[0], 2, replace=False)].copy()
        for _ in range(4):
            d0 = ((pts - seeds[0]) ** 2).sum(1)
            d1 = ((pts - seeds[1]) ** 2).sum(1)
            m1 = d1 < d0
            if m1.all() or (~m1).all():   # degenerate: split in half
                m1 = np.zeros(pts.shape[0], bool)
                m1[: pts.shape[0] // 2] = True
            seeds[0] = pts[~m1].mean(0)
            seeds[1] = pts[m1].mean(0)
        new_id = len(centroids)
        centroids[c] = seeds[0]
        centroids.append(seeds[1])
        assign[members[m1]] = new_id
        members_of[c], members_of[new_id] = members[~m1], members[m1]
        for cc in (c, new_id):
            if members_of[cc].size > max_size:
                queue.append(cc)
    return np.stack(centroids).astype(np.float32), assign
