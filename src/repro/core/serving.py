"""Wave-scheduled serving: turning per-query early exit into TPU
throughput (beyond-paper, DESIGN §2).

On a SIMD batch, an exited query's lane otherwise idles until the whole
batch finishes. The wave scheduler advances lane states by fixed probe
chunks, then *compacts*: exited lanes are refilled with queued queries.
Effective cost per query approaches the paper's C̄ instead of max-C of
the batch.

Lane state is a pytree of (W, ...) arrays; admission/compaction are
gather/scatters on device; the host loop only moves query ids.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ivf import (DeltaView, IVFIndex, _merge_topk, _probe_tiles,
                            _scrub_dead, centroid_sims, intersection_pct,
                            tile_scores, validate_alignment)
from repro.core.policies import (RUNG_CAP, RUNG_FORCE, RUNG_NONE,
                                 RUNG_TIGHTEN, DegradationLadder)


class LaneState(NamedTuple):
    qvec: jnp.ndarray         # (W, d) admitted query vectors
    cluster_rank: jnp.ndarray # (W, N)
    h: jnp.ndarray            # (W,) per-lane next probe rank
    topk_scores: jnp.ndarray  # (W, k)
    topk_ids: jnp.ndarray     # (W, k)
    patience: jnp.ndarray     # (W,)
    active: jnp.ndarray       # (W,) bool — lane holds a live query
    qid: jnp.ndarray          # (W,) int32 external id, -1 empty


def _empty_state(w: int, d: int, n: int, k: int) -> LaneState:
    return LaneState(
        qvec=jnp.zeros((w, d), jnp.float32),
        cluster_rank=jnp.zeros((w, n), jnp.int32),
        h=jnp.zeros((w,), jnp.int32),
        topk_scores=jnp.full((w, k), -jnp.inf, jnp.float32),
        topk_ids=jnp.full((w, k), -1, jnp.int32),
        patience=jnp.zeros((w, ), jnp.int32),
        active=jnp.zeros((w,), bool),
        qid=jnp.full((w,), -1, jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_probe",),
                   donate_argnames=("state",))
def _admit(state: LaneState, centroids: jnp.ndarray, new_q: jnp.ndarray,
           new_qid: jnp.ndarray, n_probe: int) -> LaneState:
    """Fill empty lanes with the queries of ``new_q`` whose ``new_qid``
    is not -1 (vectorised).  Callers pad both to a fixed row count, so
    one compiled program admits batches of every size.  The valid rows
    come first, and row j lands in the j-th free lane, so the host
    knows the filled lanes without reading them back
    (:func:`_admitted_lanes`)."""
    free = ~state.active                                  # (W,)
    # slot j of new_q goes to the j-th free lane
    free_rank = jnp.cumsum(free) - 1                      # rank among free
    take = free & (free_rank < jnp.sum(new_qid >= 0))
    src = jnp.clip(free_rank, 0, new_q.shape[0] - 1)
    csims = centroid_sims(new_q, centroids)
    _, rank = jax.lax.top_k(csims, n_probe)
    def fill(old, new_full, extra_dims):
        newv = jnp.take(new_full, src, axis=0)
        m = take.reshape((-1,) + (1,) * extra_dims)
        return jnp.where(m, newv, old)
    return LaneState(
        qvec=fill(state.qvec, new_q, 1),
        cluster_rank=fill(state.cluster_rank, rank.astype(jnp.int32), 1),
        h=jnp.where(take, 0, state.h),
        topk_scores=jnp.where(take[:, None], -jnp.inf, state.topk_scores),
        topk_ids=jnp.where(take[:, None], -1, state.topk_ids),
        patience=jnp.where(take, 0, state.patience),
        active=state.active | take,
        qid=jnp.where(take, jnp.take(new_qid, src), state.qid))


def _admitted_lanes(active: np.ndarray, m: int) -> np.ndarray:
    """The lanes :func:`_admit` fills with ``m`` valid rows, given the
    host's copy of ``state.active``: the first ``m`` free ones."""
    return np.flatnonzero(~active)[:m]


@functools.partial(jax.jit,
                   static_argnames=("chunk", "k", "n_probe", "phi",
                                    "use_fused"),
                   donate_argnames=("state",))
def _advance(index: IVFIndex, state: LaneState,
             dview: Optional[DeltaView] = None,
             dead: Optional[jnp.ndarray] = None, *,
             lane_delta: jnp.ndarray, lane_cap: jnp.ndarray, chunk: int,
             k: int, n_probe: int, phi: float,
             use_fused: bool = True) -> LaneState:
    """Advance every active lane by up to ``chunk`` probes.

    ``lane_delta``/``lane_cap`` are per-lane (W,) exit knobs: the
    patience threshold and the probe budget.  Without a deadline both
    are constant (the scheduler's ``delta``/``n_probe``); under
    deadline pressure the degradation ladder lowers them per lane, so
    a struggling lane exits earlier while its neighbours run the full
    policy.  Exit granularity stays per-probe either way.

    The fused path issues ONE ``ivf_scan_merge`` dispatch for the whole
    chunk — lanes stop materializing ``(W, list_pad, d)`` doc gathers,
    raw scores stay in VMEM, and the per-probe patience signal comes
    from the kernel's new-entry counts.  Exit granularity is unchanged:
    lane state is rolled forward slot by slot from the kernel's
    per-probe top-k snapshots, so mid-chunk exits land on the exact
    probe they would have on the unfused path.

    ``dview``/``dead`` (live-mutation overlay, ``repro.index``): delta
    entries are brute-force scored once per wave and merged into a
    lane's running top-k at the probe of their assigned cluster (same
    bit-identity rule as ``core.search``); ``dead`` is the cumulative
    tombstone lookup, scrubbing running top-k entries that were deleted
    after they were merged — required for mid-flight lanes that span an
    index version swap.

    ``state`` is donated (its buffers hold the result) and ``phi`` is
    static, so a dispatch neither allocates lane state nor copies a
    scalar to the device.
    """

    if dead is not None:
        # scrub once per wave: a lane's carry may predate a deletion
        ts0, ti0 = _scrub_dead(state.topk_scores, state.topk_ids, dead)
        state = state._replace(topk_scores=ts0, topk_ids=ti0)

    if dview is not None:
        # burn tombstoned buffer entries to id -1 up front (cheap
        # elementwise op): both kernel paths then mask them exactly
        # like empty slots, with no per-slot re-merge
        d_ids_eff = dview.ids
        if dead is not None:
            gone = jnp.take(dead, jnp.clip(dview.ids, 0,
                                           dead.shape[0] - 1)) \
                & (dview.ids >= 0)
            d_ids_eff = jnp.where(gone, -1, dview.ids)
        if not use_fused:
            from repro.kernels import ops as kops
            d_sc = kops.delta_scan(state.qvec, dview.vecs)  # (W, cap)
            d_valid = (d_ids_eff >= 0)[None, :]
            d_ids = jnp.broadcast_to(d_ids_eff[None, :], d_sc.shape)

    def delta_cands(gate):
        return (jnp.where(gate, d_sc, -jnp.inf),
                jnp.where(gate, d_ids, -1))

    def slot(st: LaneState, ms, mi, phi_v) -> LaneState:
        act = st.active[:, None]
        ts = jnp.where(act, ms, st.topk_scores)
        ti = jnp.where(act, mi, st.topk_ids)
        ctr = jnp.where(st.active & (st.h >= 1) & (phi_v >= phi),
                        st.patience + 1, 0)
        h = jnp.where(st.active, st.h + 1, st.h)
        exited = st.active & ((ctr >= lane_delta) | (h >= lane_cap))
        return LaneState(st.qvec, st.cluster_rank, h, ts, ti, ctr,
                         st.active & ~exited, st.qid)

    if use_fused:
        from repro.kernels import ops as kops
        rel = jnp.arange(chunk, dtype=jnp.int32)[None, :]
        idx = jnp.clip(state.h[:, None] + rel, 0, n_probe - 1)
        cids = jnp.take_along_axis(state.cluster_rank, idx, axis=1)
        offs = jnp.take(index.cluster_offsets, cids)
        # inactive lanes and slots past the probe budget merge nothing
        slot_ok = ((state.h[:, None] + rel) < n_probe) \
            & state.active[:, None]
        sizes = jnp.where(slot_ok, jnp.take(index.cluster_sizes, cids), 0)
        if dview is not None:
            # delta buffer rides the kernel as a second prefetch
            # stream, gated per slot on the assigned cluster id
            # (see core.ivf._search): still ONE dispatch per chunk
            gates = jnp.where(slot_ok, cids, -2)
            snap_s, snap_i, cnts = kops.ivf_scan_merge(
                state.qvec, index.docs, index.doc_ids, offs, sizes,
                state.topk_scores, state.topk_ids, dview.vecs,
                d_ids_eff, dview.assign, gates, k=k,
                list_pad=index.list_pad, chunk=chunk)
        else:
            snap_s, snap_i, cnts = kops.ivf_scan_merge(
                state.qvec, index.docs, index.doc_ids, offs, sizes,
                state.topk_scores, state.topk_ids, k=k,
                list_pad=index.list_pad, chunk=chunk)
        st = state
        for t in range(chunk):
            phi_v = 100.0 * (k - cnts[:, t]).astype(jnp.float32) / k
            st = slot(st, snap_s[:, t], snap_i[:, t], phi_v)
        return st

    def body(_, st: LaneState) -> LaneState:
        hv = jnp.minimum(st.h, n_probe - 1)
        cids = jnp.take_along_axis(st.cluster_rank, hv[:, None], 1)[:, 0]
        tiles, ids, mask = _probe_tiles(index, cids)
        sc = tile_scores(tiles, st.qvec)
        sc = jnp.where(mask, sc, -jnp.inf)
        if dview is not None:
            gate = d_valid & (dview.assign[None, :] == cids[:, None])
            e_s, e_i = delta_cands(gate)
            sc = jnp.concatenate([sc, e_s], axis=1)
            ids = jnp.concatenate([ids, e_i], axis=1)
        ms, mi = _merge_topk(st.topk_scores, st.topk_ids, sc, ids, k)
        ti = jnp.where(st.active[:, None], mi, st.topk_ids)
        return slot(st, ms, mi, intersection_pct(st.topk_ids, ti))

    return jax.lax.fori_loop(0, chunk, body, state)


#: ordering of degradation reasons — a stronger rung overwrites a weaker
_REASON_RANK = {"tightened_patience": 1, "capped_probes": 2,
                "forced_exit": 3, "shed": 4}

#: The serve loop's stages in loop order: the columns of
#: ``ServeReport.stage_ms`` and, prefixed ``serve.``, the names of their
#: profiler spans.  The ``WAIT_STAGES`` block on the device; the rest is
#: host work.  ``wait_advance`` is the loop's one blocking pull a wave
#: (:data:`_PULLED` of ``_advance``'s result).  ``wait_admit`` reads 0:
#: the host knows the lanes ``_admit`` fills and waits for nothing.
STAGES = ("wait_advance", "harvest", "pin", "ladder", "admit",
          "wait_admit", "advance", "rebuild")
WAIT_STAGES = ("wait_advance", "wait_admit")

#: The lane-state fields the host reads after each ``_advance``: copied
#: asynchronously from its dispatch on, fetched together at the top of
#: the next pass.
_PULLED = ("active", "qid", "h", "topk_ids")


class _StageClock:
    """Per-wave host time of each stage in :data:`STAGES`.

    ``with stage("admit"):`` opens a profiler span ``serve.admit``, on
    the host plane of the trace and the device planes' clock (so a
    device idle gap inside it is put down to that stage), and adds the
    block's ``time.perf_counter`` ms to the current wave's row.  It is
    the host's real clock, not the scheduler's injectable ``clock``,
    which is the traffic's.  With the profiler off a stage costs a few
    microseconds: one inactive ``TraceMe`` and two clock reads."""

    def __init__(self):
        self.rows: List[List[float]] = []
        self.row = [0.0] * len(STAGES)

    @contextlib.contextmanager
    def __call__(self, stage: str):
        with jax.profiler.TraceAnnotation("serve." + stage):
            t = time.perf_counter()
            yield
            self.row[STAGES.index(stage)] += 1e3 * (time.perf_counter() - t)

    def end_wave(self) -> None:
        self.rows.append(self.row)
        self.row = [0.0] * len(STAGES)

    def ms(self) -> np.ndarray:
        """(waves, len(STAGES)) ms; the loop's last pass, which
        dispatches nothing, adds into the last wave's row."""
        out = np.zeros((len(self.rows), len(STAGES)))
        if self.rows:
            out[:] = self.rows
            out[-1] += self.row
        return out


@dataclasses.dataclass
class ServeReport:
    results: Dict[int, np.ndarray]
    probes: Dict[int, int]
    waves: int
    occupancy: float            # mean fraction of busy lanes per wave
    lane_steps: int             # total lane-probe slots spent
    # -- deadline/degradation accounting (empty when deadline_ms unset) --
    degraded: Dict[int, str] = dataclasses.field(default_factory=dict)
    latency_ms: Dict[int, float] = dataclasses.field(default_factory=dict)
    deadline_ms: Optional[float] = None
    wave_cost_ms: float = 0.0   # final EMA of per-wave cost
    # -- background rebuild accounting (zero without a rebuilder) --
    epoch_swaps: int = 0        # higher-epoch versions adopted (drained)
    drain_waves: int = 0        # waves spent draining before a swap
    rebuild_ticks: int = 0      # rebuild stages run between waves
    rebuild_throttled: int = 0  # ticks skipped under deadline pressure
    # -- the loop's own stages and admission counters --
    stage_ms: np.ndarray = dataclasses.field(       # (waves, len(STAGES))
        default_factory=lambda: np.zeros((0, len(STAGES))))
    empty_waves: int = 0        # waves dispatched with no active lane
    admit_calls: int = 0        # _admit dispatches
    admitted: int = 0           # queries placed in a lane
    # passes through the loop's one blocking read, waves + 1: counted
    # at that read, so a second read elsewhere does not show here
    host_pulls: int = 0

    @property
    def degraded_fraction(self) -> float:
        return len(self.degraded) / max(len(self.results), 1)

    def shed_ids(self) -> List[int]:
        return [q for q, r in self.degraded.items() if r == "shed"]


class WaveScheduler:
    """Throughput-oriented serving loop over the adaptive search.

    ``registry`` (optional, ``repro.index.IndexRegistry``): between
    waves the scheduler re-reads ``registry.current()`` and advances
    against that version's (index, delta view, tombstones) — an atomic
    swap point.  Mid-flight lanes stay correct across swaps: probes
    already taken saw buffered docs through the delta overlay, probes
    still to come see them inside the merged lists (centroids are fixed
    under mutation, so each lane's cluster_rank stays valid), and the
    per-wave tombstone scrub evicts results deleted after they were
    merged.

    **Epoch-fenced swaps** (background re-clustering,
    ``repro.index.rebuild``): a version whose ``epoch`` is HIGHER than
    the one lanes are probing carries re-trained centroids, so every
    in-flight ``cluster_rank`` would be meaningless against it.  The
    scheduler therefore *drains*: it pins the old version, stops
    admitting, finishes in-flight lanes against the pinned epoch
    (their results are correct for the corpus they were admitted
    under — mutation catch-up means no document is missing), and
    adopts the new epoch only once no lane is active.  Same-epoch
    version swaps (``merge_delta``) keep the old wave-granular
    behavior.

    ``rebuilder`` (optional, ``repro.index.rebuild.Rebuilder``): when
    armed, the scheduler runs ONE rebuild pipeline stage between waves
    — unless the degradation ladder's ``throttle_rebuild`` says a lane
    is too close to its deadline to absorb the stall.
    """

    def __init__(self, index: IVFIndex, *, wave_size: int = 64,
                 chunk: int = 8, k: int = 100, n_probe: int = 80,
                 delta: int = 7, phi: float = 95.0,
                 use_fused: bool = True, registry=None,
                 deadline_ms: Optional[float] = None,
                 ladder: Optional[DegradationLadder] = None,
                 clock: Optional[Callable[[], float]] = None,
                 rebuilder=None):
        """``deadline_ms``: per-query latency budget, counted from lane
        admission.  When set, the scheduler walks the
        :class:`repro.core.policies.DegradationLadder` instead of
        blowing the budget: tighten patience -> cap remaining probes ->
        force-exit with the partial top-k -> shed admissions.  Every
        affected query carries a reason in ``ServeReport.degraded``.

        ``clock``: ms-resolution monotonic clock (injectable for
        deterministic tests and the chaos harness); defaults to
        ``time.monotonic() * 1000``.
        """
        if use_fused:
            validate_alignment(index)
        self.index = index
        self.w = wave_size
        self.chunk = chunk
        self.k = k
        self.n = min(n_probe, index.n_clusters)
        self.delta = delta
        self.phi = phi
        self.use_fused = use_fused
        self.registry = registry
        self.deadline_ms = deadline_ms
        self.ladder = ladder or DegradationLadder()
        self._now = clock or (lambda: time.monotonic() * 1000.0)
        self.rebuilder = rebuilder
        self._pinned = None        # version lanes are probing against

    def _refresh_pin(self, active_any: bool) -> Tuple[bool, bool]:
        """Adopt the registry's current version if lanes allow it.

        Same-epoch updates (merge_delta) adopt immediately — the
        wave-granular swap that mid-flight lanes tolerate.  A
        higher-epoch version (rebuild: new centroids) only lands once
        no lane is active; until then the scheduler reports *drain*
        and the caller stops admitting.  Returns ``(draining,
        swapped)``.
        """
        if self.registry is None:
            return False, False
        cur = self.registry.current()
        if self._pinned is None:
            self._pinned = cur
            return False, False
        cur_epoch = getattr(cur, "epoch", 0)
        pin_epoch = getattr(self._pinned, "epoch", 0)
        if cur_epoch == pin_epoch:
            self._pinned = cur
            return False, False
        if active_any:
            return True, False     # drain: finish lanes on old epoch
        self._pinned = cur
        return False, True

    def _version(self):
        if self.registry is None:
            return self.index, None, None
        ver = self._pinned if self._pinned is not None \
            else self.registry.current()
        return ver.index, ver.delta, ver.dead

    def _centroids(self):
        """Centroids new admissions rank clusters against — must match
        the epoch their lanes will probe."""
        ix, _, _ = self._version()
        return ix.centroids

    @staticmethod
    def _flag(degraded: Dict[int, str], qid: int, reason: str) -> None:
        old = degraded.get(qid)
        if old is None or _REASON_RANK[reason] > _REASON_RANK[old]:
            degraded[qid] = reason

    def serve(self, queries: np.ndarray, *, compact: bool = True,
              on_wave=None) -> ServeReport:
        d = queries.shape[1]
        state = _empty_state(self.w, d, self.n, self.k)
        next_q = 0
        results: Dict[int, np.ndarray] = {}
        probes: Dict[int, int] = {}
        degraded: Dict[int, str] = {}
        latency: Dict[int, float] = {}
        waves = 0
        occ = []
        lane_steps = 0
        nq = queries.shape[0]
        prev_active = np.zeros(self.w, bool)
        lane_admit = np.zeros(self.w, np.float64)   # admit timestamp, ms
        full_delta = jnp.full((self.w,), self.delta, jnp.int32)
        full_cap = jnp.full((self.w,), self.n, jnp.int32)
        wave_cost = 0.0                              # EMA of wave ms
        last_read = stall = 0.0       # last read's time; rebuild ms since
        epoch_swaps = drain_waves = 0
        rebuild_ticks = rebuild_throttled = 0
        empty_waves = admit_calls = admitted = host_pulls = 0
        stage = _StageClock()
        self._pinned = None if self.registry is None \
            else self.registry.current()
        while True:
            # the wave's one blocking read: everything below works on
            # these host copies (``_advance`` leaves ``qid`` as it was)
            with stage("wait_advance"):
                active, qids, h_np, tid = jax.device_get(
                    tuple(getattr(state, f) for f in _PULLED))
            host_pulls += 1
            with stage("harvest"):
                now = self._now()
                if waves:
                    # the last wave's whole period, read to read: the
                    # device time waited out in the read included, the
                    # rebuild stall left out of the EMA the ladder
                    # budgets against
                    sample = now - last_read - stall
                    wave_cost = sample if waves == 1 \
                        else 0.5 * wave_cost + 0.5 * sample
                last_read, stall = now, 0.0
                # harvest exits: lanes that flipped active->inactive
                for lane in np.nonzero(prev_active & ~active)[0]:
                    qid = int(qids[lane])
                    results[qid] = tid[lane]
                    probes[qid] = int(h_np[lane])
                    latency[qid] = now - lane_admit[lane]
            # -- epoch-fenced version adoption ------------------------------
            with stage("pin"):
                draining, swapped = self._refresh_pin(bool(active.any()))
            if swapped:
                epoch_swaps += 1
            if draining:
                drain_waves += 1
            # -- degradation ladder (deadline-budgeted serving) -------------
            lane_delta, lane_cap = full_delta, full_cap
            if self.deadline_ms is not None:
                with stage("ladder"):
                    remaining = self.deadline_ms - (now - lane_admit)
                    rungs = self.ladder.rungs(remaining,
                                              max(wave_cost, 1e-9))
                    rungs = np.where(active, rungs, RUNG_NONE)
                    force = active & (rungs == RUNG_FORCE)
                    if force.any():
                        for lane in np.nonzero(force)[0]:
                            qid = int(qids[lane])
                            results[qid] = tid[lane]
                            probes[qid] = int(h_np[lane])
                            latency[qid] = now - lane_admit[lane]
                            self._flag(degraded, qid, "forced_exit")
                        active = active & ~force
                        state = state._replace(active=jnp.asarray(active))
                    for lane in np.nonzero(active
                                           & (rungs >= RUNG_TIGHTEN))[0]:
                        self._flag(degraded, int(qids[lane]),
                                   "capped_probes"
                                   if rungs[lane] >= RUNG_CAP
                                   else "tightened_patience")
                    if (rungs > RUNG_NONE).any():
                        afford = np.floor(
                            np.maximum(remaining, 0.0)
                            / max(wave_cost, 1e-9)).astype(np.int64) \
                            * self.chunk
                        cap_np = np.where(rungs >= RUNG_CAP, h_np + afford,
                                          self.n)
                        cap_np = np.minimum(cap_np, self.n).astype(np.int32)
                        tight = min(self.ladder.tight_delta, self.delta)
                        delta_np = np.where(rungs >= RUNG_TIGHTEN, tight,
                                            self.delta).astype(np.int32)
                        lane_delta = jnp.asarray(delta_np)
                        lane_cap = jnp.asarray(cap_np)
            # -- admission (with overload shedding) -------------------------
            if (compact or not active.any()) and not draining:
                if next_q < nq and (~active).any():
                    room = int((~active).sum())
                    if self.deadline_ms is not None \
                            and wave_cost > self.deadline_ms:
                        # even a fresh query cannot meet the deadline:
                        # shed instead of admitting to certain death
                        with stage("admit"):
                            for qid in range(next_q,
                                             min(nq, next_q + room)):
                                results[qid] = np.full(self.k, -1,
                                                       np.int32)
                                probes[qid] = 0
                                latency[qid] = 0.0
                                self._flag(degraded, qid, "shed")
                            next_q = min(nq, next_q + room)
                    else:
                        with stage("admit"):
                            batch = queries[next_q: next_q + room]
                            m = batch.shape[0]
                            qpad = np.zeros((self.w, d), np.float32)
                            qpad[:m] = batch
                            ids = np.full(self.w, -1, np.int32)
                            ids[:m] = np.arange(next_q, next_q + m)
                            state = _admit(state, self._centroids(),
                                           jnp.asarray(qpad),
                                           jnp.asarray(ids), self.n)
                            filled = _admitted_lanes(active, m)
                            active = active.copy()
                            active[filled] = True
                        admit_calls += 1
                        next_q += m
                        lane_admit[filled] = now
                        admitted += m
            if not active.any() and next_q >= nq:
                break
            empty_waves += int(not active.any())
            occ.append(active.mean())
            lane_steps += self.w * self.chunk
            prev_active = active
            with stage("advance"):
                index, dview, dead = self._version()
                state = _advance(index, state, dview, dead,
                                 lane_delta=lane_delta, lane_cap=lane_cap,
                                 chunk=self.chunk, k=self.k,
                                 n_probe=self.n, phi=self.phi,
                                 use_fused=self.use_fused)
                for f in _PULLED:
                    getattr(state, f).copy_to_host_async()
            waves += 1
            if on_wave is not None:
                on_wave(waves)
            # -- background rebuild tick (throttled under pressure) ---------
            # timed, so the stall never inflates the wave-cost EMA
            if self.rebuilder is not None and self.rebuilder.active:
                with stage("rebuild"):
                    t0 = self._now()
                    throttle = False
                    if self.deadline_ms is not None:
                        # the lanes this wave runs, from the host's copy
                        rem = (self.deadline_ms - (t0 - lane_admit))[active]
                        throttle = self.ladder.throttle_rebuild(
                            rem, max(wave_cost, 1e-9))
                    if throttle:
                        rebuild_throttled += 1
                    else:
                        self.rebuilder.tick()
                        rebuild_ticks += 1
                    stall = self._now() - t0
            stage.end_wave()
        return ServeReport(results, probes, waves,
                           float(np.mean(occ)) if occ else 0.0,
                           lane_steps, degraded=degraded,
                           latency_ms=latency,
                           deadline_ms=self.deadline_ms,
                           wave_cost_ms=wave_cost,
                           epoch_swaps=epoch_swaps,
                           drain_waves=drain_waves,
                           rebuild_ticks=rebuild_ticks,
                           rebuild_throttled=rebuild_throttled,
                           stage_ms=stage.ms(), empty_waves=empty_waves,
                           admit_calls=admit_calls, admitted=admitted,
                           host_pulls=host_pulls)
