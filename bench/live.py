"""The live traffic: a seeded write driver over the program's live
index, and the checks that hold a live run to its guarantees.

A traffic file with ``"kind": "live"`` serves its open-loop queries
(``arrivals``, as any traffic file) through the program's live path:

* set-up wraps the built index in ``repro.index.LiveIndex`` with a
  ``MutationWAL`` (``mutations.wal`` in a directory under ``TMPDIR``,
  ``group_commit_n`` records to an fsync), fills the delta buffer with
  ``writes.fill_adds`` adds, applies one batch shaped as the window's
  (so that its device programs are compiled in set-up), publishes the
  live index's version in an ``IndexRegistry`` and serves through a
  ``WaveScheduler`` that reads that registry;
* in the window, ``serve``'s ``on_wave`` applies every batch that is
  due on the traffic's clock, one every ``writes.every_ms``:
  ``writes.adds`` adds, then deletes of ``writes.delete_adds`` earlier
  adds and ``writes.delete_main`` built docs, then
  ``MutationWAL.flush()`` and a publish.  Only then is the batch
  acknowledged.  Versions change between waves, as the program's
  contract has it.

Every write is drawn from the run's seed.  An add is a unit vector, a
noisy copy (noise ``easy_noise`` x ``spread``) of a doc: every other
one of a doc that a later easy query of the window copies, so that
added docs enter answers and the delta merge runs; the rest of a doc
drawn at random.  A deleted add is one drawn at a later query where
there is one; a deleted built doc is one that a later easy query
copies, so that a version older than a query's admission would show.

:func:`check` imports nothing of the program.  The numbers it compares:

* ``writes_lost``: after the window, untimed, one query per
  acknowledged add that is still live, the added vector itself, served
  by a ``WaveScheduler`` on the final version: its id must be in the
  answer.  Also counted: each acknowledged delete whose id is in an
  answer of the window admitted after the version that carried it, or
  in any of those answers.
* ``phantom_ids``: ids in answers of the window that were live in no
  version current during the query's life (admission to result).
* ``wal_faults``: the log read back by this module's own reader of the
  format ``repro.index.wal`` documents: every acknowledged record
  once, in order, numbered 1, 2, ..., its payload as sent and its CRC
  valid, and nothing else; and each acknowledged batch that the file
  had not been fsync'd through (``os.fsync`` is watched).
* ``queries_differ``: as the static check, on answers whose whole life
  saw one version, against the reference over that version's net
  partition: the built lists less the docs deleted by then, and the
  adds live then in the list of their nearest centroid, computed in
  f32 at ``HIGHEST``.
* ``index_faults``, ``centroid_drift``, ``assign_excess``, ``failed``:
  as the static check, with the built docs the harness deleted
  expected as rows burned to -1 and the lists otherwise unchanged.
"""
from __future__ import annotations

import dataclasses
import gc
import io
import os
import shutil
import struct
import tempfile
import time
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus, harness, reference, traffic
from bench.harness import emit

#: the death version of an id that was never deleted
NEVER = np.iinfo(np.int32).max

OP_ADD, OP_DELETE = 1, 2          # record ops of the mutation log


@dataclasses.dataclass
class Batch:
    """One write batch: adds, then deletes, then flush and publish."""
    vecs: np.ndarray            # (m, d) unit f32 vectors to add
    del_adds: List[int]         # ordinals of earlier adds to delete
    del_main: List[int]         # ids of built docs to delete


class Planner:
    """Draws write batches, in order, from one generator per phase."""

    def __init__(self, docs: np.ndarray, sources: np.ndarray,
                 noise: float, writes: dict):
        self.docs = docs            # the built docs, host
        self.src = sources          # per pool query: the doc it copies
        self.noise = noise
        self.w = writes
        self.n_adds = 0             # adds drawn so far: the next ordinal
        self.live: List[int] = []   # ordinals of adds not deleted
        self.target: Dict[int, float] = {}   # ordinal -> its query's due
        self.dead_main = set()

    def _later_easy(self, rng, due: np.ndarray, t: float) -> int:
        """A random easy query of the window due after ``t`` (any easy
        query of the window where none is)."""
        lo = int(np.searchsorted(due, t, side="right"))
        lo = 0 if lo >= due.size else lo
        for _ in range(100000):
            q = int(rng.integers(lo, due.size))
            if self.src[q] >= 0:
                return q
        raise RuntimeError("the window holds no easy query to copy")

    def _adds(self, rng, due: np.ndarray, t: float, m: int) -> np.ndarray:
        d = self.docs.shape[1]
        vecs = np.empty((m, d), np.float32)
        for i in range(m):
            if i % 2 == 0:
                q = self._later_easy(rng, due, t)
                base, self.target[self.n_adds] = int(self.src[q]), due[q]
            else:
                base = int(rng.integers(self.docs.shape[0]))
                self.target[self.n_adds] = -np.inf
            x = self.docs[base] + self.noise * rng.standard_normal(
                d, dtype=np.float32)
            vecs[i] = x / np.linalg.norm(x)
            self.live.append(self.n_adds)
            self.n_adds += 1
        return vecs

    def _del_add(self, rng, t: float) -> int:
        later = [o for o in self.live if self.target[o] > t]
        pool = later or self.live
        o = pool[int(rng.integers(len(pool)))]
        self.live.remove(o)
        return o

    def _del_main(self, rng, due: np.ndarray, t: float) -> int:
        """A built doc not yet deleted that a later easy query copies;
        where none is left, one that any query of the window copies,
        and then any built doc."""
        for _ in range(64):
            s = int(self.src[self._later_easy(rng, due, t)])
            if s not in self.dead_main:
                break
        else:
            lo = int(np.searchsorted(due, t, side="right"))
            s = -1
            for first in (lo, 0):
                cand = np.unique(self.src[first:due.size])
                cand = cand[(cand >= 0) & ~np.isin(cand, list(
                    self.dead_main))]
                if cand.size:
                    s = int(cand[rng.integers(cand.size)])
                    break
            while s < 0 or s in self.dead_main:
                s = int(rng.integers(self.docs.shape[0]))
        self.dead_main.add(s)
        return s

    def fill(self, rng, due: np.ndarray) -> List[Batch]:
        """The set-up's adds, in batches of ``fill_batch``."""
        n, step = self.w["fill_adds"], self.w["fill_batch"]
        return [Batch(self._adds(rng, due, -np.inf, min(step, n - lo)),
                      [], []) for lo in range(0, n, step)]

    def batch(self, rng, due: np.ndarray, t: float) -> Batch:
        """A window's batch due at ``t`` ms: its deletes are drawn
        before its adds, so it never deletes its own."""
        dels = [self._del_add(rng, t) for _ in range(self.w["delete_adds"])]
        main = [self._del_main(rng, due, t)
                for _ in range(self.w["delete_main"])]
        return Batch(self._adds(rng, due, t, self.w["adds"]), dels, main)


class Book:
    """What the harness wrote and had acknowledged, in order."""

    def __init__(self, n_docs: int):
        self.n_docs = n_docs
        self.add_ids: List[int] = []         # by add ordinal
        self.add_vecs: Dict[int, np.ndarray] = {}
        self.born: Dict[int, int] = {}       # add id -> first version
        self.death: Dict[int, int] = {}      # deleted id -> its version
        self.sent: List[Tuple[int, np.ndarray]] = []   # log records
        self.unsynced = 0       # acknowledged batches not yet durable

    def ack(self, b: Batch, ids: np.ndarray, dels: np.ndarray, ver: int,
            durable: bool) -> None:
        self.sent.append((OP_ADD, b.vecs))
        for i, v in zip(ids.tolist(), b.vecs):
            self.add_ids.append(i)
            self.add_vecs[i] = v
            self.born[i] = ver
        if dels.size:
            self.sent.append((OP_DELETE, dels))
            for i in dels.tolist():
                self.death[i] = ver
        self.unsynced += int(not durable)

    def arrays(self, dim: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(n_ids,) first and last-plus-one version of each id, and the
        vectors of the added ids (row = id - n_docs)."""
        n = max([self.n_docs] + [i + 1 for i in self.add_vecs])
        born = np.zeros(n, np.int32)
        death = np.full(n, NEVER, np.int32)
        extra = np.zeros((n - self.n_docs, dim), np.float32)
        for i, v in self.add_vecs.items():
            born[i] = self.born[i]
            extra[i - self.n_docs] = v
        for i, v in self.death.items():
            if 0 <= i < n:
                death[i] = v
        return born, death, extra


class FsyncWatch:
    """``os.fsync`` wrapped to note, per file (inode), the size it was
    called at: what was durable, and when."""

    def __init__(self):
        self.synced: Dict[int, int] = {}
        self._real = None

    def start(self) -> None:
        self._real = real = os.fsync

        def fsync(fd):
            st = os.fstat(fd)
            real(fd)
            self.synced[st.st_ino] = max(self.synced.get(st.st_ino, 0),
                                         st.st_size)
        os.fsync = fsync

    def stop(self) -> None:
        if self._real is not None:
            os.fsync, self._real = self._real, None

    def covers(self, path: str) -> bool:
        """Was the file fsync'd at its present size or later?"""
        st = os.stat(path)
        return self.synced.get(st.st_ino, -1) >= st.st_size


class LiveMarks(harness.WaveMarks):
    """The live window's ``on_wave``: the wave marks, the clock
    reading and version of each wave, and the writes that are due."""

    def __init__(self, traced: bool, dep: "LiveDeployment", source,
                 seconds: float, rng):
        super().__init__(traced)
        self.source = source
        self._dep, self._rng = dep, rng
        every = dep.w["every_ms"]
        self._due = every * np.arange(int(np.ceil(1000 * seconds / every)))
        self._next = 0
        self.pass_ms: List[float] = []   # per wave: its pass's clock
        self.wave_ver: List[int] = []    # per wave: the version it ran
        self.write_ms: List[float] = []  # per batch of the window

    @property
    def planned_adds(self) -> int:
        return self._due.size * self._dep.w["adds"]

    def __call__(self, wave: int) -> None:
        super().__call__(wave)
        self.pass_ms.append(self.source.last_ms)
        self.wave_ver.append(self._dep.ver)
        now = self.source.elapsed_ms()
        while self._next < self._due.size and self._due[self._next] <= now:
            b = self._dep.planner.batch(self._rng, self.source.due_ms,
                                        float(self._due[self._next]))
            self.write_ms.append(self._dep.write(b, window=True))
            self._next += 1


class LiveDeployment(harness.Deployment):
    """A configuration served as a live index under the traffic's
    writes; see the module's docstring."""

    def __init__(self, cfg: dict, seed: int, tr: dict, seconds: float,
                 n_pool: Optional[int] = None):
        from repro.index import (IndexRegistry, LiveIndex, MutationWAL,
                                 version_of)
        super().__init__(cfg, seed, n_pool or tr["pool_queries"],
                         tr["queries"])
        t = time.perf_counter()
        self.w = tr["writes"]
        self.seed = seed
        ix = self.index
        self.built = {"ids": np.asarray(ix.doc_ids),
                      "offsets": np.asarray(ix.cluster_offsets),
                      "sizes": np.asarray(ix.cluster_sizes)}
        src = corpus.query_sources(seed, cfg, self.queries.shape[0],
                                   tr["queries"])
        self.planner = Planner(self.docs, src, tr["queries"]["easy_noise"]
                               * cfg["generator"]["spread"], self.w)
        self.book = Book(cfg["n_docs"])
        self.ver = 0                  # window batches published so far
        self.fill: Dict[int, float] = {}   # version -> delta occupancy
        self.marks: Optional[LiveMarks] = None
        self._windows = 0
        self._version_of = version_of
        self._dir = tempfile.mkdtemp(prefix="bench-live-")
        self.wal_path = os.path.join(self._dir, "mutations.wal")
        self.fsyncs = FsyncWatch()
        self.fsyncs.start()
        self.live = LiveIndex(ix, delta_cap=self.w["delta_cap"],
                              wal=MutationWAL(
                                  self.wal_path,
                                  group_commit_n=self.w["group_commit_n"]))
        self.reg = IndexRegistry(version_of(self.live))
        due = traffic.arrival_ms(tr["arrivals"], seconds,
                                 np.random.default_rng([seed % (1 << 64), 2]))
        rng = np.random.default_rng([seed % (1 << 64), 4])
        for b in self.planner.fill(rng, due):
            self.write(b)
        self.write(self.planner.batch(rng, due, -np.inf))
        jax.block_until_ready(self.reg.current().index.doc_ids)
        emit(phase="live", seconds=time.perf_counter() - t,
             adds=len(self.book.add_ids), deletes=len(self.book.death),
             delta_fill=self.live.delta.occupancy(),
             wal_bytes=os.path.getsize(self.wal_path))

    def scheduler(self, clock=None):
        from repro.core.serving import WaveScheduler
        c = self.cfg
        return WaveScheduler(self.reg.current().index,
                             wave_size=c["wave_size"], k=c["k"],
                             n_probe=c["n_probe"],
                             delta=c["patience_delta"],
                             phi=c["patience_phi"], registry=self.reg,
                             clock=clock)

    def wave_marks(self, traced: bool, source, seconds: float,
                   seed: int) -> LiveMarks:
        self._windows += 1
        marks = LiveMarks(traced, self, source, seconds,
                          np.random.default_rng([seed % (1 << 64), 5,
                                                 self._windows]))
        room = self.live.delta.capacity - self.live.delta.count
        if marks.planned_adds > room:
            raise ValueError(f"the window's {marks.planned_adds} adds do "
                             f"not fit the delta buffer's {room} free "
                             f"slots")
        self.marks = marks
        return marks

    def write(self, b: Batch, window: bool = False) -> float:
        """Apply, make durable and publish one batch, then acknowledge
        it; returns its ms on the host clock."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.write"):
            ids = self.live.add(b.vecs)
            dels = np.asarray([self.book.add_ids[o] for o in b.del_adds]
                              + b.del_main, np.int64)
            if dels.size:
                self.live.delete(dels)
            self.live.wal.flush()
            self.ver += int(window)
            self.reg.publish(self._version_of(self.live))
        ms = 1e3 * (time.perf_counter() - t)
        self.book.ack(b, np.asarray(ids), dels, self.ver,
                      self.fsyncs.covers(self.wal_path))
        self.fill[self.ver] = self.live.delta.occupancy()
        return ms

    def window_stats(self) -> dict:
        """What the ``.live`` readers read: the window's write batches'
        ms and, per wave, the delta occupancy of the version it ran."""
        m = self.marks
        return {"write_ms": np.asarray(m.write_ms),
                "delta_fill": np.asarray([self.fill[v] for v in
                                          m.wave_ver])}

    def release(self) -> dict:
        held = harness.held_of(self.live.index)
        self.live.wal.close()
        self.fsyncs.stop()
        self.live = self.reg = self.index = None
        gc.collect()
        return held

    def close(self) -> None:
        self.fsyncs.stop()
        shutil.rmtree(self._dir, ignore_errors=True)


def read_log(path: str) -> Tuple[List[Tuple[int, int, bytes]], int]:
    """The records of a mutation log, by the on-disk format that
    ``repro.index.wal`` documents: file magic ``EEWAL001``, then per
    record ``\\xa5Z`` | op u8 | seq u64 | payload length u32 | crc32
    u32 | payload, little-endian.  Returns (op, seq, payload) of each
    record and how many were damaged: a bad header, a short record, a
    CRC that does not match."""
    with open(path, "rb") as f:
        raw = f.read()
    if raw[:8] != b"EEWAL001":
        return [], 1
    hdr = struct.Struct("<2sBQII")
    out, damaged, off = [], 0, 8
    while off < len(raw):
        if off + hdr.size > len(raw):
            return out, damaged + 1
        magic, op, seq, n, crc = hdr.unpack_from(raw, off)
        body = raw[off + hdr.size: off + hdr.size + n]
        if magic != b"\xa5Z" or len(body) < n:
            return out, damaged + 1
        if zlib.crc32(body) != crc:
            damaged += 1
        out.append((op, seq, body))
        off += hdr.size + n
    return out, damaged


def _payload_is(raw: bytes, want: np.ndarray) -> bool:
    try:
        got = np.load(io.BytesIO(raw), allow_pickle=False)
    except (ValueError, EOFError, OSError):
        return False
    return (got.dtype == want.dtype and got.shape == want.shape
            and got.tobytes() == want.tobytes())


def log_faults(path: str, sent: List[Tuple[int, np.ndarray]]
               ) -> Dict[str, int]:
    """How the log departs from the records acknowledged to the
    harness: damaged records, and positions whose record is missing,
    extra, or not the one sent (op, number, payload)."""
    got, damaged = read_log(path)
    wrong = 0
    for i in range(max(len(got), len(sent))):
        if i >= len(got) or i >= len(sent):
            wrong += 1
            continue
        op, seq, raw = got[i]
        want_op, want = sent[i]
        wrong += int(op != want_op or seq != i + 1
                     or not _payload_is(raw, want))
    return {"records": len(got), "sent": len(sent), "damaged": damaged,
            "wrong": wrong}


def lives(rep, marks: LiveMarks, done: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """The first and last version each completed query's life saw:
    the versions of the waves from the one after its admission to the
    last before its result, found by the pass clock of each wave."""
    pass_ms = np.asarray(marks.pass_ms)
    ver = np.asarray(marks.wave_ver)
    admit = marks.source.admit_ms[done]
    out = admit + np.asarray([rep.latency_ms[i] for i in done])
    first = np.searchsorted(pass_ms, admit - 1e-6, side="left")
    last = np.searchsorted(pass_ms, out - 1e-6, side="left") - 1
    first = np.clip(first, 0, ver.size - 1)
    last = np.clip(np.maximum(last, first), 0, ver.size - 1)
    return ver[first], ver[last]


def check(dep: LiveDeployment, rep, attempted: int, tr: dict, seed: int,
          *, control: bool = False) -> dict:
    """The live run's numbers compared (name -> value); with
    ``control`` the bf16 reference's ``queries_differ`` too."""
    try:
        return _check(dep, rep, attempted, tr, seed, control)
    finally:
        dep.close()


def _check(dep, rep, attempted, tr, seed, control) -> dict:
    cfg, book = dep.cfg, dep.book
    t = time.perf_counter()
    born, death, extra = book.arrays(cfg["dim"])
    n_ids = born.size
    # -- the window's answers against the versions of their lives ------
    done = np.asarray(sorted(rep.results), np.int64)
    ans = np.stack([rep.results[i] for i in done]) if done.size \
        else np.zeros((0, cfg["k"]), np.int32)
    lo, hi = lives(rep, dep.marks, done)
    known = (ans >= 0) & (ans < n_ids)
    i = np.where(known, ans, 0)
    alive = known & (born[i] <= hi[:, None]) & (death[i] > lo[:, None])
    phantom = int(((ans != -1) & ~alive).sum())
    seen_after_delete = int((known & (death[i] <= lo[:, None])).sum())
    # -- one query per acknowledged add still live, on the final version
    probe = np.asarray([x for x in book.add_ids if death[x] == NEVER],
                       np.int64)
    p_rep = dep.scheduler().serve(extra[probe - cfg["n_docs"]])
    p_ans = np.stack([p_rep.results.get(j, np.full(cfg["k"], -1))
                      for j in range(probe.size)]) if probe.size \
        else np.zeros((0, cfg["k"]), np.int64)
    missed = int((~(p_ans == probe[:, None]).any(1)).sum())
    p_known = (p_ans >= 0) & (p_ans < n_ids)
    dead_served = int((p_known & (death[np.where(p_known, p_ans, 0)]
                                  != NEVER)).sum())
    t_probe = time.perf_counter()
    held = dep.release()
    log = log_faults(dep.wal_path, book.sent)
    # -- the index: built docs less the deletes, burned in place -------
    built = dep.built
    deleted = np.asarray([x for x in book.death if x < cfg["n_docs"]],
                         np.int64)
    if held["ids"].shape == built["ids"].shape \
            and held["offsets"].shape == built["offsets"].shape:
        faults = reference.index_faults(
            cfg["n_docs"], dep.prints, built["ids"], held["row_prints"],
            built["offsets"], built["sizes"], list_pad=cfg["list_pad"],
            dtypes=held["dtypes"], storage=cfg["storage"])
        expect = np.where(np.isin(built["ids"], deleted), -1, built["ids"])
        faults["burned"] = int((held["ids"] != expect).sum())
        faults["relaid"] = int((held["offsets"] != built["offsets"]).sum()
                               + (held["sizes"] != built["sizes"]).sum())
    else:
        faults = {"relaid": 1}
    # -- the sample: answers whose whole life saw one version ----------
    one = lo == hi
    probes = np.asarray([rep.probes[j] for j in done])
    sample = harness.draw_sample(done[one], probes[one], tr, seed)
    pos = np.searchsorted(done, sample)
    q = dep.queries[sample]
    t_up = time.perf_counter()
    docs_dev = corpus.docs_on_device(cfg)[0]
    if not np.array_equal(np.asarray(corpus.fingerprint(docs_dev)),
                          dep.prints):
        raise RuntimeError("the corpus drawn again differs from the one "
                           "indexed")
    km = reference.kmeans_excess(
        docs_dev, held["centroids"],
        reference.list_of(cfg["n_docs"], built["ids"], built["offsets"],
                          built["sizes"]))
    t_ref = time.perf_counter()
    added = np.asarray(sorted(book.add_vecs), np.int64)
    part = reference.with_members(
        reference.partition_of(held["centroids"], built["ids"],
                               built["offsets"], built["sizes"],
                               held["list_pad"]),
        added.astype(np.int32),
        reference.nearest_lists(extra[added - cfg["n_docs"]],
                                held["centroids"]))
    live_args = dict(extra=jnp.asarray(extra) if extra.size else None,
                     born=jnp.asarray(born), death=jnp.asarray(death),
                     ver=lo[pos])
    ref_ids, ref_probes = reference.search_blocks(q, part, docs_dev, cfg,
                                                  **live_args)
    parts = reference.disagreement(ans[pos], probes[pos], ref_ids,
                                   ref_probes)
    numbers = {"failed": attempted - len(rep.results),
               "index_faults": sum(faults.values()),
               "centroid_drift": km["centroid_drift"],
               "assign_excess": km["assign_excess"],
               "queries_differ": parts["queries_differ"],
               "writes_lost": missed + seen_after_delete + dead_served,
               "phantom_ids": phantom,
               "wal_faults": log["damaged"] + log["wrong"] + book.unsynced}
    diag = {"sampled": int(sample.size), "differ": parts,
            "spanned_swap": int((~one).sum()), "versions": dep.ver,
            "adds": len(book.add_ids), "deletes": len(book.death),
            "probe_queries": int(probe.size), "probe_missed": missed,
            "probe_dead_served": dead_served,
            "seen_after_delete": seen_after_delete, "log": log,
            "unsynced": book.unsynced, "index": faults, "kmeans": km,
            "mean_probes_sample": float(probes[pos].mean())
            if pos.size else None,
            "probe_s": t_probe - t, "reference_s": time.perf_counter()
            - t_ref, "redraw_kmeans_s": t_ref - t_up}
    if control:
        c_ids, c_probes = reference.search_blocks(q, part, docs_dev, cfg,
                                                  lowp=True, **live_args)
        c_parts = reference.disagreement(c_ids, c_probes, ref_ids,
                                         ref_probes)
        numbers["control.queries_differ"] = c_parts["queries_differ"]
        diag["control_differ"] = c_parts
    del docs_dev
    emit(phase="check", seconds=time.perf_counter() - t, **diag)
    return numbers
