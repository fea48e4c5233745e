"""Versioned snapshot registry: atomic publish/subscribe of index state.

``search()``/``WaveScheduler`` read an :class:`IndexVersion` (immutable
snapshot of main index + delta view + dead lookup); the mutation path
publishes a fresh one whenever state changes.  Readers pick up the new
version between waves — never mid-wave — so every in-flight probe loop
sees one coherent (index, delta, tombstones) triple.

Snapshots round-trip through ``checkpoint.CheckpointManager`` (atomic
dir-rename publish, one .npy per array).  With a mutation WAL
(``repro.index.wal``) the pair is crash-safe: ``recover()`` loads the
latest snapshot and replays every logged mutation past it, rebuilding
a LiveIndex bit-identical to the one that crashed.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointError
from repro.core.ivf import DeltaView, IVFIndex

_SNAPSHOT_KEYS = ("centroids", "docs", "doc_ids", "offsets", "sizes",
                  "dvecs", "dids", "dassign", "dead", "meta")


class StaleEpochError(RuntimeError):
    """A publish carried an epoch older than the registry's current one.

    Raised when a ``merge_delta`` (or any publisher) computed against a
    pre-rebuild index races a background rebuild's epoch-bumped
    publish: the stale version must NOT clobber the re-clustered one.
    The loser re-reads ``registry.current()`` and redoes its work
    against the new epoch (its mutations are safe — they are in the
    WAL and were replayed onto the rebuild candidate during catch-up).
    """


@dataclass(frozen=True)
class IndexVersion:
    """One immutable, publishable snapshot of the live index."""
    version: int
    index: IVFIndex
    delta: DeltaView
    dead: jnp.ndarray          # (id_capacity,) bool tombstone lookup
    next_id: int
    seq: int = -1              # LiveIndex mutation counter at snapshot
    merges: int = 0            # LiveIndex merge counter at snapshot
    epoch: int = 0             # centroid generation (bumped by rebuild)


def version_of(live, *, version: Optional[int] = None) -> IndexVersion:
    """Snapshot a :class:`repro.index.live.LiveIndex`."""
    return IndexVersion(
        version=live.seq if version is None else version,
        index=live.index,
        delta=live.delta_view(),
        dead=live.dead_lookup(),
        next_id=live.next_id,
        seq=live.seq,
        merges=live.version,
        epoch=int(getattr(live, "epoch", 0)))


class IndexRegistry:
    """Thread-safe single-slot publish/subscribe for IndexVersions."""

    def __init__(self, initial: Optional[IndexVersion] = None):
        self._lock = threading.Lock()
        self._current: Optional[IndexVersion] = None
        self.swaps = 0
        if initial is not None:
            self.publish(initial)

    def publish(self, ver: IndexVersion) -> IndexVersion:
        with self._lock:
            if self._current is not None and \
                    ver.epoch < self._current.epoch:
                raise StaleEpochError(
                    f"publish of version {ver.version} carries epoch "
                    f"{ver.epoch} but the registry is at epoch "
                    f"{self._current.epoch} — a background rebuild "
                    f"published first; re-read current() and redo the "
                    f"mutation against the new index")
            if self._current is not None and \
                    ver.version <= self._current.version:
                ver = IndexVersion(self._current.version + 1, ver.index,
                                   ver.delta, ver.dead, ver.next_id,
                                   ver.seq, ver.merges, ver.epoch)
            self._current = ver
            self.swaps += 1
            return ver

    def current(self) -> IndexVersion:
        with self._lock:
            if self._current is None:
                raise RuntimeError("registry holds no published version")
            return self._current

    # -- persistence ---------------------------------------------------------
    def save(self, manager) -> str:
        """Write the current version through a CheckpointManager."""
        ver = self.current()
        ix = ver.index
        tree = {
            "centroids": ix.centroids, "docs": ix.docs,
            "doc_ids": ix.doc_ids, "offsets": ix.cluster_offsets,
            "sizes": ix.cluster_sizes,
            "dvecs": ver.delta.vecs, "dids": ver.delta.ids,
            "dassign": ver.delta.assign, "dead": ver.dead,
            "meta": np.asarray(
                [ix.list_pad, ver.version, ver.next_id, ver.seq,
                 ver.merges, ver.epoch], np.int64),
        }
        return manager.save(ver.version, tree)

    @staticmethod
    def restore(manager, step: Optional[int] = None
                ) -> Tuple["IndexRegistry", IndexVersion]:
        step, arrs = manager.load_arrays(step)
        missing = [k for k in _SNAPSHOT_KEYS if k not in arrs]
        if missing:
            raise CheckpointError(
                f"index snapshot at step {step} under {manager.root!r} "
                f"is missing arrays {missing} — expected the schema "
                f"written by IndexRegistry.save: {list(_SNAPSHOT_KEYS)} "
                f"(was this checkpoint written by a different tree?)")
        meta = np.asarray(arrs["meta"]).ravel()
        if meta.size < 3:
            raise CheckpointError(
                f"index snapshot at step {step} under {manager.root!r} "
                f"has a malformed 'meta' array of size {meta.size} — "
                f"expected >= 3 entries [list_pad, version, next_id"
                f"(, seq, merges)]")
        list_pad, version, next_id = (int(x) for x in meta[:3])
        seq = int(meta[3]) if meta.size > 3 else version
        merges = int(meta[4]) if meta.size > 4 else 0
        epoch = int(meta[5]) if meta.size > 5 else 0
        ver = IndexVersion(
            version=version,
            index=IVFIndex(jnp.asarray(arrs["centroids"]),
                           jnp.asarray(arrs["docs"]),
                           jnp.asarray(arrs["doc_ids"]),
                           jnp.asarray(arrs["offsets"]),
                           jnp.asarray(arrs["sizes"]), list_pad),
            delta=DeltaView(jnp.asarray(arrs["dvecs"]),
                            jnp.asarray(arrs["dids"]),
                            jnp.asarray(arrs["dassign"])),
            dead=jnp.asarray(arrs["dead"]),
            next_id=next_id,
            seq=seq,
            merges=merges,
            epoch=epoch)
        return IndexRegistry(ver), ver

    @staticmethod
    def recover(manager, wal=None, *, step: Optional[int] = None,
                align: int = 128, round_total_to: int = 4096):
        """Crash recovery: latest snapshot + WAL replay past it.

        Returns ``(registry, live, replay_report)`` where ``live`` is a
        :class:`repro.index.live.LiveIndex` bit-identical (top-k ids,
        φ history, probe counts) to the instance that crashed, and the
        registry holds its freshly published current version.
        ``replay_report`` is None when no WAL is given.

        If the WAL shows a background rebuild in flight at crash time,
        the two-phase protocol is resolved first: a durable
        ``REBUILD_COMMIT`` whose staged snapshot was not yet promoted
        gets its promote redone (the commit record *is* the publish);
        an open epoch (``BEGIN`` without ``COMMIT``/``ABORT``) is
        aborted and its staging cleaned, so recovery lands on the
        pre-rebuild snapshot + full replay — bit-identical either way.
        """
        from repro.index.live import LiveIndex
        from repro.index.rebuild import resolve_pending_rebuild
        promoted = aborted = False
        if wal is not None:
            promoted, aborted = resolve_pending_rebuild(manager, wal)
        _, ver = IndexRegistry.restore(manager, step)
        live = LiveIndex.from_version(ver, align=align,
                                      round_total_to=round_total_to,
                                      wal=wal)
        if wal is not None:
            wal.note_durable(live.seq)   # restored snapshot is durable
        report = wal.replay_into(live) if wal is not None else None
        if report is not None:
            report.rebuild_promoted = promoted
            report.rebuild_aborted = aborted
        reg = IndexRegistry(version_of(live))
        return reg, live, report
