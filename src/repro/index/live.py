"""LiveIndex: streaming mutations over a frozen cluster-major IVF index.

Write path (host-coordinated, cheap):
  * ``add``    -> vectors land in the :class:`DeltaBuffer`, pre-assigned
                  to their nearest centroid.
  * ``delete`` -> main-index docs get their stored id burned to -1
                  (the tombstone every scan path masks); buffered docs
                  get their slot cleared.  The external id is recorded
                  in the cumulative :class:`Tombstones` set.
  * ``merge_delta`` -> background compaction: re-layout the net corpus
                  (survivors + buffered adds) into a fresh immutable
                  ``IVFIndex`` with the SAME centroids, respecting the
                  ``align`` padding contract.  Entries that would
                  overflow a full list spill back into the buffer.

Read path: ``live.search(...)`` == ``core.search(index, ..., delta=
view)``.  The key invariant (tested): the overlay view returns
bit-identical top-k, probe counts and phi history to a freshly
rebuilt index holding the net corpus, for every exit policy, on both
the per-probe and fused kernel paths.  Centroids never change under
mutation within an *epoch* (``merge_delta`` keeps them fixed), which
is what keeps probe order — and mid-flight lane state — valid across
``merge_delta`` version swaps.  Only a background re-clustering
(``repro.index.rebuild``) retrains them, bumping ``epoch`` so readers
drain in-flight lanes before adopting the new centroid generation.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.ivf import (DeltaView, IVFIndex, search as core_search,
                            validate_alignment)
from repro.index.delta import (DeltaBuffer, DeltaFull, Tombstones,
                               assign_clusters)
from repro.index.wal import OP_ADD, OP_DELETE, OP_MERGE


def relayout(vecs: np.ndarray, ids: np.ndarray, assign: np.ndarray,
             centroids, *, list_pad: int, align: int = 128,
             round_total_to: Optional[int] = None) -> IVFIndex:
    """Cluster-major re-layout of an already-assigned corpus.

    Same physical format as ``build_index`` (``align``-aligned list
    offsets, ``list_pad`` slack tail) but with fixed centroids and
    caller-provided assignments — the primitive under ``merge_delta``
    and the rebuild-equivalence oracle.  The within-cluster order of
    ``vecs`` is preserved (stable sort), so ties resolve like the
    insertion order the live overlay sees.  ``round_total_to`` pads the
    total row count up to a multiple, so repeated merges reuse compiled
    search executables instead of re-tracing per merge.
    """
    return _upload(_layout(vecs, ids, assign, centroids,
                           list_pad=list_pad, align=align,
                           round_total_to=round_total_to), list_pad)


def _layout(vecs, ids, assign, centroids, *, list_pad: int, align: int,
            round_total_to: Optional[int]) -> Tuple[np.ndarray, ...]:
    """Host half of :func:`relayout`: (centroids, docs, doc_ids,
    offsets, sizes) as numpy arrays."""
    if align <= 0:
        raise ValueError(f"align must be positive, got {align}")
    if list_pad % align:
        raise ValueError(
            f"list_pad={list_pad} must be a multiple of align={align}")
    vecs = np.asarray(vecs, np.float32)
    ids = np.asarray(ids, np.int32)
    assign = np.asarray(assign, np.int32)
    centroids_np = np.asarray(centroids, np.float32)
    c, d = centroids_np.shape
    sizes = np.bincount(assign, minlength=c).astype(np.int32)
    over = np.nonzero(sizes > list_pad)[0]
    if over.size:
        raise ValueError(
            f"cluster {int(over[0])} would hold {int(sizes[over[0]])} "
            f"docs > list_pad={list_pad}; spill the overflow back to "
            f"the delta buffer (merge_delta does) or rebuild offline")
    aligned = ((sizes + align - 1) // align) * align
    offsets = np.zeros(c, np.int32)
    offsets[1:] = np.cumsum(aligned)[:-1].astype(np.int32)
    total = int(aligned.sum()) + list_pad
    if round_total_to:
        total = -(-total // round_total_to) * round_total_to
    sorted_docs = np.zeros((total, d), np.float32)
    sorted_ids = np.full(total, -1, np.int32)
    order = np.argsort(assign, kind="stable")
    pos = 0
    for cid in range(c):
        sz = int(sizes[cid])
        sel = order[pos: pos + sz]
        sorted_docs[offsets[cid]: offsets[cid] + sz] = vecs[sel]
        sorted_ids[offsets[cid]: offsets[cid] + sz] = ids[sel]
        pos += sz
    return centroids_np, sorted_docs, sorted_ids, offsets, sizes


def _upload(layout: Tuple[np.ndarray, ...], list_pad: int) -> IVFIndex:
    return IVFIndex(*(jnp.asarray(a) for a in layout), list_pad)


class LiveIndex:
    """Mutable front over an immutable IVFIndex + delta + tombstones.

    ``wal`` (optional :class:`repro.index.wal.MutationWAL`): every
    mutation appends one fsync'd record *before* touching in-memory
    state (classic write-ahead ordering; arguments are validated first
    so a logged record can always be replayed).  Combined with
    ``IndexRegistry`` snapshots this makes the index crash-safe:
    ``IndexRegistry.recover(manager, wal)`` rebuilds a bit-identical
    LiveIndex from the latest snapshot plus log replay.
    """

    def __init__(self, index: IVFIndex, *, delta_cap: int = 1024,
                 align: int = 128, round_total_to: int = 4096, wal=None):
        validate_alignment(index, blk_l=align)
        self.index = index
        self.align = align
        self.round_total_to = round_total_to
        self._centroids = np.asarray(index.centroids)
        self._refresh_mirrors()
        self.next_id = int(self._doc_ids.max(initial=-1)) + 1
        self.delta = DeltaBuffer(index.dim, delta_cap)
        self.tombs = Tombstones(self.next_id)
        self.version = 0                 # bumped by merge_delta
        self.seq = 0                     # bumped by every mutation
        self.epoch = 0                   # bumped by a rebuild publish
        self.wal = wal
        self._replaying = False

    @classmethod
    def from_version(cls, ver, *, align: int = 128,
                     round_total_to: int = 4096, wal=None) -> "LiveIndex":
        """Rebuild a LiveIndex from a published/restored snapshot
        (``repro.index.registry.IndexVersion``).  The delta buffer and
        tombstone set are reconstructed slot-for-slot, so replaying the
        same mutations yields the same state as the original instance."""
        self = cls.__new__(cls)
        self.index = ver.index
        self.align = align
        self.round_total_to = round_total_to
        self._centroids = np.asarray(ver.index.centroids)
        self._refresh_mirrors()
        self.next_id = int(ver.next_id)
        dvecs = np.asarray(ver.delta.vecs)
        dids = np.asarray(ver.delta.ids)
        dassign = np.asarray(ver.delta.assign)
        buf = DeltaBuffer(dvecs.shape[1], dvecs.shape[0])
        buf.vecs[: dvecs.shape[0]] = dvecs
        buf.ids[: dids.shape[0]] = dids
        buf.assign[: dassign.shape[0]] = dassign
        # assign >= 0 marks every consumed slot (delete burns only the
        # id; compact_keep resets assign) -> append pointer position
        buf.count = int((dassign >= 0).sum())
        buf._slot_of = {int(i): s for s, i in enumerate(dids) if i >= 0}
        self.delta = buf
        dead = np.asarray(ver.dead)
        tombs = Tombstones(dead.shape[0])
        tombs._dead[: dead.shape[0]] = dead
        tombs.count = int(dead.sum())
        self.tombs = tombs
        self.version = int(getattr(ver, "merges", 0))
        self.seq = int(ver.seq) if getattr(ver, "seq", -1) >= 0 \
            else int(ver.version)
        self.epoch = int(getattr(ver, "epoch", 0))
        self.wal = wal
        self._replaying = False
        return self

    def _log(self, op: int, payload: Optional[np.ndarray] = None) -> None:
        if self.wal is not None and not self._replaying:
            # merge is a compaction boundary: force the group-commit
            # batch to disk so the record (and everything before it)
            # is durable before the expensive re-layout runs
            self.wal.append(op, self.seq + 1, payload,
                            force=(op == OP_MERGE))

    # -- host mirrors -------------------------------------------------------
    def _refresh_mirrors(self) -> None:
        self._doc_ids = np.asarray(self.index.doc_ids)
        self._offsets = np.asarray(self.index.cluster_offsets)
        rows = np.nonzero(self._doc_ids >= 0)[0]
        self._row_of = dict(
            zip(self._doc_ids[rows].tolist(), rows.tolist()))

    def _main_assignments(self, rows: np.ndarray) -> np.ndarray:
        """Recover row -> cluster from the layout (offsets are sorted;
        empty clusters share the next offset and own no rows)."""
        return (np.searchsorted(self._offsets, rows, side="right") - 1
                ).astype(np.int32)

    # -- mutations ----------------------------------------------------------
    @property
    def n_live(self) -> int:
        return len(self._row_of) + len(self.delta)

    def add(self, vecs: np.ndarray) -> np.ndarray:
        """Stage new vectors; returns their external doc ids.
        Raises :class:`DeltaFull` when the buffer is out of slots."""
        vecs = np.asarray(vecs, np.float32).reshape(-1, self.index.dim)
        m = vecs.shape[0]
        self.delta.ensure_room(m)        # validate BEFORE logging
        self._log(OP_ADD, vecs)
        ids = np.arange(self.next_id, self.next_id + m, dtype=np.int32)
        assign = assign_clusters(vecs, self._centroids)
        self.delta.add(vecs, ids, assign)
        self.next_id += m
        self.tombs.ensure_capacity(self.next_id)
        self.seq += 1
        return ids

    def delete(self, ids) -> None:
        """Tombstone documents by external id (idempotent)."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        bad = ids[(ids < 0) | (ids >= self.next_id)]
        if bad.size:                     # validate BEFORE logging
            raise ValueError(f"doc id {int(bad[0])} was never allocated")
        self._log(OP_DELETE, ids)
        burn_rows = []
        for i in ids:
            i = int(i)
            if i in self.tombs:
                continue
            self.tombs.add((i,))
            if not self.delta.delete(i):
                burn_rows.append(self._row_of.pop(i))
        if burn_rows:
            rows = np.asarray(burn_rows)
            self._doc_ids = self._doc_ids.copy()
            self._doc_ids[rows] = -1
            self.index = IVFIndex(
                self.index.centroids, self.index.docs,
                self.index.doc_ids.at[jnp.asarray(rows)].set(-1),
                self.index.cluster_offsets, self.index.cluster_sizes,
                self.index.list_pad)
        self.seq += 1

    def merge_delta(self) -> int:
        """Fold the delta buffer into a fresh immutable main index.

        Buffered entries are appended to their assigned cluster's list
        after the surviving docs; entries that would push a list past
        ``list_pad`` spill back into the buffer (newest first out).
        Returns the new version number.
        """
        self._log(OP_MERGE)
        lp = self.index.list_pad
        rows = np.nonzero(self._doc_ids >= 0)[0]
        assign_main = self._main_assignments(rows)
        c = self.index.n_clusters
        fill = np.bincount(assign_main, minlength=c).astype(np.int64)
        slots = self.delta.live_slots()
        take = np.ones(slots.size, bool)
        for j, s in enumerate(slots):
            cl = int(self.delta.assign[s])
            if fill[cl] >= lp:
                take[j] = False          # spill: stays buffered
            else:
                fill[cl] += 1
        merged = slots[take]
        net_vecs = np.concatenate([np.asarray(self.index.docs)[rows],
                                   self.delta.vecs[merged]])
        net_ids = np.concatenate(
            [self._doc_ids[rows], self.delta.ids[merged]])
        net_assign = np.concatenate(
            [assign_main, self.delta.assign[merged]])
        layout = _layout(net_vecs, net_ids, net_assign, self._centroids,
                         list_pad=lp, align=self.align,
                         round_total_to=self.round_total_to)
        del net_vecs
        # drop this instance's hold on the old device arrays before the
        # upload: an index that fills more than half the device can then
        # be merged once no reader pins the old version
        self.index = None
        self.index = _upload(layout, lp)
        self.delta.compact_keep(slots[~take])
        self._refresh_mirrors()
        self.version += 1
        self.seq += 1
        return self.version

    # -- read path ----------------------------------------------------------
    def delta_view(self) -> DeltaView:
        return self.delta.view()

    def dead_lookup(self) -> jnp.ndarray:
        return self.tombs.lookup()

    def search(self, queries, policy, **kwargs):
        """Adaptive search over (main index + delta + tombstones)."""
        return core_search(self.index, jnp.asarray(queries), policy,
                           delta=self.delta_view(), **kwargs)

    # -- oracles (tests / offline maintenance) ------------------------------
    def net_corpus(self) -> Tuple[np.ndarray, np.ndarray]:
        """(vecs, external ids) of every live doc: main survivors in
        corpus order, then buffered adds in insertion order."""
        rows = np.nonzero(self._doc_ids >= 0)[0]
        rows = rows[np.argsort(self._doc_ids[rows], kind="stable")]
        slots = self.delta.live_slots()
        vecs = np.concatenate(
            [np.asarray(self.index.docs)[rows], self.delta.vecs[slots]])
        ids = np.concatenate([self._doc_ids[rows], self.delta.ids[slots]])
        return vecs, ids

    def rebuild_equivalent(self) -> IVFIndex:
        """Fresh from-scratch re-layout of the net corpus with the same
        centroids: the rebuild-equivalence oracle.  Searching it must be
        bit-identical to the live overlay view for every policy."""
        rows = np.nonzero(self._doc_ids >= 0)[0]
        assign_main = self._main_assignments(rows)
        slots = self.delta.live_slots()
        vecs = np.concatenate(
            [np.asarray(self.index.docs)[rows], self.delta.vecs[slots]])
        ids = np.concatenate([self._doc_ids[rows], self.delta.ids[slots]])
        assign = np.concatenate([assign_main, self.delta.assign[slots]])
        # spilled entries can push a logical cluster past list_pad (that
        # is what spilling is for); the oracle grows the tile so the
        # rebuilt index can hold them.  Extra rows are masked padding,
        # so per-probe candidate sets — and results — are unchanged.
        sizes = np.bincount(assign, minlength=self.index.n_clusters)
        biggest = int(sizes.max(initial=0))
        lp = max(self.index.list_pad,
                 -(-biggest // self.align) * self.align)
        return relayout(vecs, ids, assign, self._centroids,
                        list_pad=lp, align=self.align)
