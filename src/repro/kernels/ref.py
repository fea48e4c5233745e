"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True) -> jnp.ndarray:
    """q,k,v: (BH, S, hd) -> (BH, S, hd). Plain masked softmax."""
    s = q.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqh,bkh->bqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask[None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bqk,bkh->bqh", p, v.astype(jnp.float32)) \
        .astype(q.dtype)


def ivf_scan_ref(queries: jnp.ndarray, docs: jnp.ndarray,
                 offsets: jnp.ndarray, sizes: jnp.ndarray,
                 list_pad: int) -> jnp.ndarray:
    """(B,d) x cluster-major (n,d) rows [offset, offset+size) ->
    (B, list_pad) scores, -inf outside the list."""
    tiles = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(
        docs, o, list_pad, 0))(offsets)
    sc = jnp.einsum("bld,bd->bl", tiles.astype(jnp.float32),
                    queries.astype(jnp.float32), precision=_HIGHEST)
    mask = jnp.arange(list_pad)[None] < sizes[:, None]
    return jnp.where(mask, sc, -jnp.inf)


def topk_merge_ref(scores: jnp.ndarray, ids: jnp.ndarray,
                   new_scores: jnp.ndarray, new_ids: jnp.ndarray,
                   k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    cat_s = jnp.concatenate([scores, new_scores], 1)
    cat_i = jnp.concatenate([ids, new_ids], 1)
    ts, idx = jax.lax.top_k(cat_s, k)
    return ts, jnp.take_along_axis(cat_i, idx, 1)


def ivf_scan_merge_ref(queries: jnp.ndarray, docs: jnp.ndarray,
                       doc_ids: jnp.ndarray, offsets: jnp.ndarray,
                       sizes: jnp.ndarray, run_scores: jnp.ndarray,
                       run_ids: jnp.ndarray, k: int, list_pad: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused multi-probe scan+merge oracle.

    offsets/sizes: (B, chunk) row offsets / true list sizes of each
    query's probed clusters.  run_scores/run_ids: (B, k) incoming
    running top-k (-inf / -1 empty slots).  Returns per-probe top-k
    snapshots (B, chunk, k) scores / ids and (B, chunk) int32
    new-entry counts, where count = k - |prev_topk ∩ new_topk|
    (invalid slots count as new), so
    phi = 100 * (k - count) / k == intersection_pct(prev, new).
    """
    chunk = offsets.shape[1]
    s, i = run_scores.astype(jnp.float32), run_ids
    snap_s, snap_i, cnts = [], [], []
    for t in range(chunk):
        tiles = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(
            docs, o, list_pad, 0))(offsets[:, t])
        tids = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(
            doc_ids, o, list_pad, 0))(offsets[:, t])
        sc = jnp.einsum("bld,bd->bl", tiles.astype(jnp.float32),
                        queries.astype(jnp.float32), precision=_HIGHEST)
        mask = jnp.arange(list_pad)[None] < sizes[:, t][:, None]
        tids = jnp.where(mask, tids, -1)
        # id < 0 == padding or tombstoned row: never a candidate
        sc = jnp.where(mask & (tids >= 0), sc, -jnp.inf)
        ns, ni = topk_merge_ref(s, i, sc, tids, k)
        inter = jnp.sum((i[:, :, None] == ni[:, None, :])
                        & (i[:, :, None] >= 0), axis=(1, 2))
        cnts.append(k - inter.astype(jnp.int32))
        snap_s.append(ns)
        snap_i.append(ni)
        s, i = ns, ni
    return (jnp.stack(snap_s, axis=1), jnp.stack(snap_i, axis=1),
            jnp.stack(cnts, axis=1))


def delta_scan_ref(queries: jnp.ndarray, vecs: jnp.ndarray) -> jnp.ndarray:
    """queries (B,d) x delta vecs (cap,d) -> (B,cap) raw f32 scores."""
    return jnp.matmul(queries.astype(jnp.float32),
                      vecs.astype(jnp.float32).T, precision=_HIGHEST)


def embedding_bag_ref(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """table (R,D), ids (B,F) -> (B,D) sum-bag."""
    return jnp.take(table, ids, axis=0).sum(axis=1)
