"""IVF two-level index + batched adaptive (early-exit) A-kNN search.

TPU-native layout (DESIGN §2): document embeddings are stored
cluster-major and every inverted list is <= ``list_pad`` rows (oversized
k-means clusters are 2-means split at build time), so one probe ==
streaming one contiguous ``(list_pad, d)`` tile per query + one MXU
scoring matmul + one vectorised top-k merge. Early exit is a per-query
*active mask* inside a ``lax.while_loop``; the loop terminates when all
queries exited or N probes were spent.

The adaptive policies (Patience / REG / Classifier / Cascade) are
described in the paper §2 and implemented in ``repro.core.policies``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as km
from repro.core.policies import Policy, PolicyDecision, policy_step
from repro.core.features import FeatureExtras

#: Matmul precision of every XLA scoring path.  On a TPU the default is
#: one bf16 pass; the fused kernel scores in f32, so the reference pins
#: f32 too.  On the CPU both are exact f32 already.
HIGHEST = jax.lax.Precision.HIGHEST


def centroid_sims(queries: jnp.ndarray, centroids: jnp.ndarray
                  ) -> jnp.ndarray:
    """(B, d) x (C, d) -> (B, C) inner products, the probe-order key."""
    return jnp.matmul(queries, centroids.T, precision=HIGHEST)


def tile_scores(tiles: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """(B, L, d) probed tiles x (B, d) queries -> (B, L) scores."""
    return jnp.einsum("bld,bd->bl", tiles, queries, precision=HIGHEST)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IVFIndex:
    """Cluster-major IVF index (all arrays device-ready)."""

    centroids: jnp.ndarray        # (C, d) f32
    docs: jnp.ndarray             # (n_pad, d) cluster-major, zero padded tail
    doc_ids: jnp.ndarray          # (n_pad,) int32, -1 on padding
    cluster_offsets: jnp.ndarray  # (C,) int32 row offset of each list
    cluster_sizes: jnp.ndarray    # (C,) int32
    list_pad: int                 # static: tile rows streamed per probe

    def tree_flatten(self):
        return ((self.centroids, self.docs, self.doc_ids,
                 self.cluster_offsets, self.cluster_sizes), self.list_pad)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]


class DeltaView(NamedTuple):
    """Device view of the live-mutation delta buffer (``repro.index``).

    Fixed-capacity arrays; empty (or tombstoned) slots carry id -1.
    ``assign`` is the nearest-centroid cluster each buffered vector
    will be merged into, which gates *when* it becomes visible to a
    query: a delta vector is merged into the running top-k at the
    probe of its assigned cluster, so results are bit-identical to a
    rebuilt index holding the same net corpus for every exit policy.
    """
    vecs: jnp.ndarray     # (cap, d) f32
    ids: jnp.ndarray      # (cap,) int32 external doc ids, -1 empty
    assign: jnp.ndarray   # (cap,) int32 assigned cluster, -1 empty


def validate_alignment(index: IVFIndex, *, blk_l: int = 128) -> None:
    """Eagerly enforce the fused-kernel layout contract.

    The Pallas scan kernels stream ``(blk_l, d)`` tiles addressed by
    scalar-prefetched *block* offsets, so every inverted-list offset
    must be a ``blk_l`` multiple and ``list_pad`` must be divisible by
    ``blk_l`` — otherwise the kernel would silently score the wrong
    rows.  Raises ``ValueError`` with a pointer at ``build_index``
    instead.  No-op for abstract (ShapeDtypeStruct) indexes.
    """
    if blk_l <= 0:
        raise ValueError(f"blk_l must be positive, got {blk_l}")
    if index.list_pad % blk_l:
        raise ValueError(
            f"list_pad={index.list_pad} is not a multiple of blk_l="
            f"{blk_l}; rebuild with build_index(list_pad=<{blk_l}"
            f"-multiple>) or pass a compatible blk_l")
    offs = index.cluster_offsets
    if not hasattr(offs, "__array__"):          # abstract dry-run index
        return
    offs = np.asarray(offs)
    bad = np.nonzero(offs % blk_l)[0]
    if bad.size:
        raise ValueError(
            f"{bad.size} inverted-list offsets are not blk_l={blk_l} "
            f"aligned (first bad cluster {int(bad[0])}, offset "
            f"{int(offs[bad[0]])}); the fused scan kernel would stream "
            f"misaligned tiles and compute garbage. Rebuild the index "
            f"with build_index(align={blk_l}) (or a multiple).")


def build_index(docs: np.ndarray, n_clusters: int, *, list_pad: int = 256,
                n_iters: int = 10, seed: int = 0,
                align: int = 128) -> IVFIndex:
    """k-means -> oversize split -> cluster-major re-layout.

    ``align``: every inverted list starts at a multiple of ``align``
    rows (gap rows id=-1), so the Pallas scan kernel can stream
    (align, d) tiles with block-aligned scalar-prefetch offsets.
    """
    if align <= 0:
        raise ValueError(f"align must be positive, got {align}")
    if list_pad % align:
        raise ValueError(
            f"list_pad={list_pad} must be a multiple of align={align} "
            f"so list offsets stay tile-aligned for the scan kernels")
    docs = np.asarray(docs, np.float32)
    centroids, assign = km.kmeans(docs, n_clusters, n_iters=n_iters, seed=seed)
    centroids, assign = km.split_oversized(docs, centroids, assign, list_pad,
                                           seed=seed)
    c = centroids.shape[0]
    d = docs.shape[1]
    sizes = np.bincount(assign, minlength=c).astype(np.int32)
    aligned = ((sizes + align - 1) // align) * align
    offsets = np.zeros(c, np.int32)
    offsets[1:] = np.cumsum(aligned)[:-1].astype(np.int32)
    total = int(aligned.sum()) + list_pad
    sorted_docs = np.zeros((total, d), np.float32)
    sorted_ids = np.full(total, -1, np.int32)
    order = np.argsort(assign, kind="stable")
    row = 0
    pos = 0
    srt = assign[order]
    for cid in range(c):
        sz = int(sizes[cid])
        sel = order[pos: pos + sz]
        sorted_docs[offsets[cid]: offsets[cid] + sz] = docs[sel]
        sorted_ids[offsets[cid]: offsets[cid] + sz] = sel
        pos += sz
    return IVFIndex(jnp.asarray(centroids), jnp.asarray(sorted_docs),
                    jnp.asarray(sorted_ids), jnp.asarray(offsets),
                    jnp.asarray(sizes), list_pad)


def abstract_index(n_docs: int, dim: int, n_clusters: int,
                   list_pad: int) -> IVFIndex:
    """ShapeDtypeStruct stand-in for dry-runs (no allocation)."""
    sd = jax.ShapeDtypeStruct
    return IVFIndex(sd((n_clusters, dim), jnp.float32),
                    sd((n_docs + list_pad, dim), jnp.float32),
                    sd((n_docs + list_pad,), jnp.int32),
                    sd((n_clusters,), jnp.int32),
                    sd((n_clusters,), jnp.int32), list_pad)


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------


class SearchState(NamedTuple):
    h: jnp.ndarray                # () int32 — probes done so far
    topk_scores: jnp.ndarray      # (B, k)
    topk_ids: jnp.ndarray         # (B, k)
    rs1_ids: jnp.ndarray          # (B, k) result set after first probe
    phi_hist: jnp.ndarray         # (B, tau-1) consecutive intersections (%)
    phi1_hist: jnp.ndarray        # (B, tau-1) intersection with RS_1 (%)
    centroid_sims: jnp.ndarray    # (B, tau)
    patience_ctr: jnp.ndarray     # (B,) int32
    target: jnp.ndarray           # (B,) int32 probes budget (REG/cascade)
    active: jnp.ndarray           # (B,) bool
    probes: jnp.ndarray           # (B,) int32 probes actually used


class SearchResult(NamedTuple):
    topk_scores: jnp.ndarray
    topk_ids: jnp.ndarray
    probes: jnp.ndarray           # (B,) int32
    phi_hist: jnp.ndarray         # (B, tau-1) — for diagnostics/benchmarks


def intersection_pct(a_ids: jnp.ndarray, b_ids: jnp.ndarray) -> jnp.ndarray:
    """100*|A ∩ B|/k for padded id sets (-1 = empty slot). (B,k)x(B,k)->(B,)"""
    k = a_ids.shape[-1]
    eq = (a_ids[..., :, None] == b_ids[..., None, :]) & (a_ids[..., :, None] >= 0)
    return 100.0 * jnp.sum(eq, axis=(-2, -1)).astype(jnp.float32) / k


def _probe_tiles(index: IVFIndex, cids: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stream each query's cluster tile: (B,L,d) docs, (B,L) ids, (B,L) mask."""
    lp = index.list_pad
    offs = jnp.take(index.cluster_offsets, cids)
    sizes = jnp.take(index.cluster_sizes, cids)
    tiles = jax.vmap(
        lambda o: jax.lax.dynamic_slice_in_dim(index.docs, o, lp, axis=0))(offs)
    ids = jax.vmap(
        lambda o: jax.lax.dynamic_slice_in_dim(index.doc_ids, o, lp, axis=0))(offs)
    mask = jnp.arange(lp)[None, :] < sizes[:, None]
    ids = jnp.where(mask, ids, -1)
    # stored id -1 inside a list == tombstoned doc: mask it like padding
    return tiles, ids, mask & (ids >= 0)


def _scrub_dead(scores: jnp.ndarray, ids: jnp.ndarray, dead: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Mask candidates whose external id is tombstoned.

    ``dead`` is the cumulative (id_capacity,) bool lookup from
    ``repro.index``; needed when a running top-k can carry ids that
    were deleted *after* they were merged (version swaps mid-query)."""
    gone = jnp.take(dead, jnp.clip(ids, 0, dead.shape[0] - 1)) & (ids >= 0)
    return (jnp.where(gone, -jnp.inf, scores), jnp.where(gone, -1, ids))


def _merge_topk(scores: jnp.ndarray, ids: jnp.ndarray, new_scores: jnp.ndarray,
                new_ids: jnp.ndarray, k: int, use_kernel: bool = False,
                dead: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if dead is not None:
        scores, ids = _scrub_dead(scores, ids, dead)
        new_scores, new_ids = _scrub_dead(new_scores, new_ids, dead)
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.topk_merge(scores, ids, new_scores, new_ids, k)
    cat_s = jnp.concatenate([scores, new_scores], axis=1)
    cat_i = jnp.concatenate([ids, new_ids], axis=1)
    top_s, idx = jax.lax.top_k(cat_s, k)
    top_i = jnp.take_along_axis(cat_i, idx, axis=1)
    return top_s, top_i


def search(index: IVFIndex, queries: jnp.ndarray, policy: Policy, *,
           delta: Optional[DeltaView] = None,
           use_scan_kernel: bool = False, use_topk_kernel: bool = False,
           use_fused_kernel: bool = False, chunk: int = 1,
           blk_l: int = 128) -> SearchResult:
    """Batched adaptive A-kNN: probe clusters in similarity order with
    per-query early exit.

    ``policy`` is a static (hashable) Policy; tree ensembles used by
    REG/Classifier live in ``policy.reg``/``policy.clf`` as numpy-backed
    constants baked into the jaxpr.

    ``chunk`` probes are advanced per ``while_loop`` iteration (the
    per-probe slots are unrolled in the body), cutting dispatch
    overhead ``chunk``-fold.  The exit policy is still evaluated at
    per-probe granularity from per-probe top-k snapshots, so results
    and probe counts are bit-identical to ``chunk=1`` for every policy.

    ``use_fused_kernel`` routes the whole chunk through the fused
    scan+merge Pallas kernel (``kernels/ivf_scan_merge.py``): one
    dispatch per chunk, raw scores never leave VMEM, and the patience
    signal phi is recovered from the kernel's per-probe new-entry
    counts instead of re-running ``intersection_pct``.

    ``delta`` (live-mutation subsystem, ``repro.index``): a fixed-
    capacity buffer of recently added vectors.  It is brute-force
    scored once per query — by ``ops.delta_scan`` on the per-probe
    path, or *inside* the fused kernel as a second prefetch stream —
    and each entry is merged into the running top-k at the probe of
    its *assigned* cluster, so phi/patience accounting — and therefore
    the result — is bit-identical to searching a rebuilt index that
    physically contains the delta docs in those lists.  Tombstoned
    docs carry stored id -1 and are masked on every path.
    """
    if use_fused_kernel or use_scan_kernel:
        # the kernels trust blk_l-aligned offsets: fail loudly up front
        validate_alignment(index, blk_l=blk_l)
    return _search(index, queries, policy, delta,
                   use_scan_kernel=use_scan_kernel,
                   use_topk_kernel=use_topk_kernel,
                   use_fused_kernel=use_fused_kernel, chunk=chunk,
                   blk_l=blk_l)


@functools.partial(
    jax.jit, static_argnames=("use_scan_kernel", "use_topk_kernel",
                              "use_fused_kernel", "chunk", "blk_l"))
def _search(index: IVFIndex, queries: jnp.ndarray, policy: Policy,
            delta: Optional[DeltaView], *, use_scan_kernel: bool,
            use_topk_kernel: bool, use_fused_kernel: bool, chunk: int,
            blk_l: int) -> SearchResult:
    B, d = queries.shape
    k, N, tau = policy.k, policy.n_probe, policy.tau
    nc = index.n_clusters
    n_rank = min(N, nc)
    chunk = max(1, min(chunk, n_rank))
    # phi1 (vs RS_1) only feeds the learned-policy feature matrix
    needs_phi1 = policy.use_classifier or policy.use_reg

    csims = centroid_sims(queries, index.centroids)           # (B, C)
    rank_sims, cluster_rank = jax.lax.top_k(csims, n_rank)    # (B, N)

    if delta is not None and not use_fused_kernel:
        from repro.kernels import ops as kops
        # probe-0 brute-force scan of the whole delta buffer; each
        # entry is *merged* only at the probe of its assigned cluster.
        # (The fused path scores the buffer inside the kernel instead —
        # a second prefetch stream — so it skips this dispatch.)
        d_sc = kops.delta_scan(queries, delta.vecs)           # (B, cap)
        d_valid = (delta.ids >= 0)[None, :]                   # (1, cap)
        d_ids = jnp.broadcast_to(delta.ids[None, :], d_sc.shape)

    def delta_candidates(gate):
        """(B, cap) gated delta candidates: -inf / -1 outside gate."""
        return (jnp.where(gate, d_sc, -jnp.inf),
                jnp.where(gate, d_ids, -1))

    def probe_scores(cids):
        if use_scan_kernel:
            from repro.kernels import ops as kops
            lp = index.list_pad
            offs = jnp.take(index.cluster_offsets, cids)
            sizes = jnp.take(index.cluster_sizes, cids)
            sc = kops.ivf_scan(queries, index.docs, offs, sizes,
                               list_pad=lp, blk_l=blk_l)
            ids = jax.vmap(lambda o: jax.lax.dynamic_slice_in_dim(
                index.doc_ids, o, lp, axis=0))(offs)
            mask = jnp.arange(lp)[None, :] < sizes[:, None]
            ids = jnp.where(mask, ids, -1)
            return jnp.where(ids >= 0, sc, -jnp.inf), ids
        tiles, ids, mask = _probe_tiles(index, cids)
        sc = tile_scores(tiles, queries)
        return jnp.where(mask, sc, -jnp.inf), ids

    init = SearchState(
        h=jnp.zeros((), jnp.int32),
        topk_scores=jnp.full((B, k), -jnp.inf, queries.dtype),
        topk_ids=jnp.full((B, k), -1, jnp.int32),
        rs1_ids=jnp.full((B, k), -1, jnp.int32),
        phi_hist=jnp.zeros((B, max(tau - 1, 1)), jnp.float32),
        phi1_hist=jnp.zeros((B, max(tau - 1, 1)), jnp.float32),
        centroid_sims=rank_sims[:, :tau].astype(jnp.float32),
        patience_ctr=jnp.zeros((B,), jnp.int32),
        target=jnp.full((B,), N, jnp.int32),
        active=jnp.ones((B,), bool),
        probes=jnp.zeros((B,), jnp.int32),
    )

    def cond(s: SearchState):
        return (s.h < n_rank) & jnp.any(s.active)

    def slot_update(s: SearchState, m_s, m_i, phi_pre) -> SearchState:
        """One probe's state transition given its merged top-k
        (snapshot or freshly merged) and, on the fused path, the
        kernel-derived phi (None -> recompute via intersection_pct)."""
        h = s.h
        act = s.active[:, None]
        topk_scores = jnp.where(act, m_s, s.topk_scores)
        topk_ids = jnp.where(act, m_i, s.topk_ids)

        phi = intersection_pct(s.topk_ids, topk_ids) \
            if phi_pre is None else phi_pre               # vs previous
        rs1_ids = jnp.where((h == 0)[None, None] & act, topk_ids, s.rs1_ids)

        # record stability history rows h-1 in [0, tau-2]
        hist_col = jnp.clip(h - 1, 0, max(tau - 2, 0))
        col_mask = (jnp.arange(s.phi_hist.shape[1]) == hist_col)[None, :]
        in_window = (h >= 1) & (h <= tau - 1)
        upd = col_mask & in_window & s.active[:, None]
        phi_hist = jnp.where(upd, phi[:, None], s.phi_hist)
        if needs_phi1:
            phi1 = intersection_pct(rs1_ids, topk_ids)
            phi1_hist = jnp.where(upd, phi1[:, None], s.phi1_hist)
        else:
            phi1_hist = s.phi1_hist

        extras = FeatureExtras(
            queries=queries, centroid_sims=s.centroid_sims,
            topk_scores=topk_scores, phi_hist=phi_hist, phi1_hist=phi1_hist)

        dec: PolicyDecision = policy_step(
            policy, h=h, phi=phi, patience_ctr=s.patience_ctr,
            target=s.target, extras=extras)

        exit_now = s.active & dec.exit & (h + 1 >= policy.min_probes)
        probes = jnp.where(s.active, h + 1, s.probes)
        active = s.active & ~exit_now & (h + 1 < n_rank)
        return SearchState(h + 1, topk_scores, topk_ids, rs1_ids, phi_hist,
                           phi1_hist, s.centroid_sims, dec.patience_ctr,
                           dec.target, active, probes)

    def body(s: SearchState) -> SearchState:
        if use_fused_kernel:
            from repro.kernels import ops as kops
            # one fused dispatch scores+merges the whole probe chunk;
            # slots past n_rank get size 0 so they merge nothing
            rel = jnp.arange(chunk, dtype=jnp.int32)
            idx = jnp.clip(s.h + rel, 0, n_rank - 1)
            cids = jnp.take(cluster_rank, idx, axis=1)        # (B, chunk)
            offs = jnp.take(index.cluster_offsets, cids)
            slot_ok = (s.h + rel < n_rank)[None, :]
            sizes = jnp.where(slot_ok,
                              jnp.take(index.cluster_sizes, cids), 0)
            if delta is not None:
                # delta buffer rides the kernel as a second prefetch
                # stream; each entry merges at its assigned cluster's
                # probe slot.  Slots past the budget gate on -2 (an
                # empty slot's assign is -1, a real cluster id >= 0).
                gates = jnp.where(slot_ok, cids, -2)
                snap_s, snap_i, cnts = kops.ivf_scan_merge(
                    queries, index.docs, index.doc_ids, offs, sizes,
                    s.topk_scores, s.topk_ids, delta.vecs, delta.ids,
                    delta.assign, gates, k=k,
                    list_pad=index.list_pad, chunk=chunk, blk_l=blk_l)
            else:
                snap_s, snap_i, cnts = kops.ivf_scan_merge(
                    queries, index.docs, index.doc_ids, offs, sizes,
                    s.topk_scores, s.topk_ids, k=k,
                    list_pad=index.list_pad, chunk=chunk, blk_l=blk_l)
        st = s
        for t in range(chunk):
            if use_fused_kernel:
                phi_pre = (100.0
                           * (k - cnts[:, t]).astype(jnp.float32) / k)
                st = slot_update(st, snap_s[:, t], snap_i[:, t],
                                 phi_pre)
            else:
                probe_idx = jnp.broadcast_to(
                    jnp.minimum(st.h, n_rank - 1), (B,))
                cids = jnp.take_along_axis(
                    cluster_rank, probe_idx[:, None], axis=1)[:, 0]
                new_scores, new_ids = probe_scores(cids)
                if delta is not None:
                    gate = d_valid & (delta.assign[None, :]
                                      == cids[:, None])
                    e_s, e_i = delta_candidates(gate)
                    new_scores = jnp.concatenate([new_scores, e_s], 1)
                    new_ids = jnp.concatenate([new_ids, e_i], 1)
                m_s, m_i = _merge_topk(st.topk_scores, st.topk_ids,
                                       new_scores, new_ids, k,
                                       use_topk_kernel)
                st = slot_update(st, m_s, m_i, None)
        return st

    final = jax.lax.while_loop(cond, body, init)
    return SearchResult(final.topk_scores, final.topk_ids, final.probes,
                        final.phi_hist)


@functools.partial(jax.jit, static_argnames=("tau", "k", "with_intersections"))
def extract_features(index: IVFIndex, queries: jnp.ndarray, *, tau: int,
                     k: int, with_intersections: bool = True) -> jnp.ndarray:
    """Run exactly ``tau`` probes and build the Table-1 feature matrix.

    This is the same code path the jitted search uses at h == tau, so
    offline (training) and online (serving) features match bit-for-bit.
    """
    from repro.core.features import FeatureExtras as FE, feature_matrix
    B = queries.shape[0]
    csims = centroid_sims(queries, index.centroids)
    rank_sims, cluster_rank = jax.lax.top_k(csims, min(tau, index.n_clusters))

    def step(carry, h):
        scores, ids, rs1, phi_h, phi1_h = carry
        tiles, tids, mask = _probe_tiles(index, cluster_rank[:, h])
        sc = jnp.where(mask, tile_scores(tiles, queries), -jnp.inf)
        ns, ni = _merge_topk(scores, ids, sc, tids, k)
        phi = intersection_pct(ids, ni)
        rs1 = jnp.where(h == 0, ni, rs1)
        phi1 = intersection_pct(rs1, ni)
        col = jnp.clip(h - 1, 0, max(tau - 2, 0))
        colm = (jnp.arange(max(tau - 1, 1)) == col)[None, :] & (h >= 1)
        phi_h = jnp.where(colm, phi[:, None], phi_h)
        phi1_h = jnp.where(colm, phi1[:, None], phi1_h)
        return (ns, ni, rs1, phi_h, phi1_h), None

    init = (jnp.full((B, k), -jnp.inf, queries.dtype),
            jnp.full((B, k), -1, jnp.int32),
            jnp.full((B, k), -1, jnp.int32),
            jnp.zeros((B, max(tau - 1, 1)), jnp.float32),
            jnp.zeros((B, max(tau - 1, 1)), jnp.float32))
    (scores, ids, rs1, phi_h, phi1_h), _ = jax.lax.scan(
        step, init, jnp.arange(min(tau, index.n_clusters)))
    extras = FE(queries=queries, centroid_sims=rank_sims.astype(jnp.float32),
                topk_scores=scores, phi_hist=phi_h, phi1_hist=phi1_h)
    return feature_matrix(extras, with_intersections=with_intersections)


@functools.partial(jax.jit, static_argnames=("k",))
def brute_force(docs: jnp.ndarray, queries: jnp.ndarray, k: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact kNN oracle (id space = row index)."""
    sims = jnp.matmul(queries, docs.T, precision=HIGHEST)
    s, i = jax.lax.top_k(sims, k)
    return s, i.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def _exact_block(index: IVFIndex, queries: jnp.ndarray, *, k: int,
                 block: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = index.docs.shape[0]
    block = min(block, n)
    b = queries.shape[0]

    def body(j, carry):
        lo = j * block
        # the last block ends at row n and overlaps the one before it;
        # rows below ``lo`` were already scored there
        start = jnp.minimum(lo, n - block)
        docs = jax.lax.dynamic_slice_in_dim(index.docs, start, block)
        ids = jax.lax.dynamic_slice_in_dim(index.doc_ids, start, block)
        rows = start + jnp.arange(block)
        sc = jnp.matmul(queries, docs.T, precision=HIGHEST)
        sc = jnp.where(((ids >= 0) & (rows >= lo))[None, :], sc, -jnp.inf)
        bs, bi = jax.lax.top_k(sc, k)
        return _merge_topk(*carry, bs, jnp.take(ids, bi), k)

    init = (jnp.full((b, k), -jnp.inf, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    return jax.lax.fori_loop(0, -(-n // block), body, init)


def exact_topk(index: IVFIndex, queries: jnp.ndarray, k: int, *,
               q_block: int = 256, block: int = 65536
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact kNN oracle over the index's own device docs.

    Blocked over queries and over doc rows; returns external doc ids
    (rows mapped through ``doc_ids``, padding and tombstones skipped),
    so it needs no second copy of the corpus and no (B, n) score
    matrix."""
    out_s, out_i = [], []
    for lo in range(0, queries.shape[0], q_block):
        s, i = _exact_block(index, jnp.asarray(queries[lo: lo + q_block]),
                            k=k, block=block)
        out_s.append(np.asarray(s))
        out_i.append(np.asarray(i))
    return np.concatenate(out_s), np.concatenate(out_i)


def probe_trace(index: IVFIndex, queries: jnp.ndarray, n_probe: int, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Reference (non-exiting) scan returning the full top-k trajectory:
    ids after every probe h=1..N. Used for C(q) labels, Figure 1 and
    policy oracles. Returns (ids_traj (N,B,k), phi (N-1,B))."""
    B = queries.shape[0]
    csims = centroid_sims(queries, index.centroids)
    _, cluster_rank = jax.lax.top_k(csims, min(n_probe, index.n_clusters))

    def step(carry, h):
        scores, ids = carry
        cids = cluster_rank[:, h]
        tiles, tids, mask = _probe_tiles(index, cids)
        sc = jnp.where(mask, tile_scores(tiles, queries), -jnp.inf)
        ns, ni = _merge_topk(scores, ids, sc, tids, k)
        return (ns, ni), ni

    init = (jnp.full((B, k), -jnp.inf, queries.dtype),
            jnp.full((B, k), -1, jnp.int32))
    _, traj = jax.lax.scan(step, init,
                           jnp.arange(min(n_probe, index.n_clusters)))
    traj = np.asarray(traj)
    phi = np.stack([np.asarray(intersection_pct(jnp.asarray(traj[h - 1]),
                                                jnp.asarray(traj[h])))
                    for h in range(1, traj.shape[0])])
    return traj, phi


def min_probes_labels(traj_ids: np.ndarray, exact_top1: np.ndarray,
                      n_probe: int) -> np.ndarray:
    """C(q): minimal h such that RS_h contains the exact 1-NN (else N)."""
    n, b, _ = traj_ids.shape
    found = (traj_ids == exact_top1[None, :, None]).any(-1)  # (N, B)
    any_found = found.any(0)
    first = np.argmax(found, axis=0) + 1
    return np.where(any_found, first, n_probe).astype(np.int32)
