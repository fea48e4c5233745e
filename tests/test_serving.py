"""Wave-scheduled serving (beyond-paper throughput layer)."""
import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.core import brute_force, metrics, policies, search
from repro.core import serving
from repro.core.serving import STAGES, WaveScheduler

pytestmark = pytest.mark.slow   # full serve loops: ~15s total


def test_wave_scheduler_serves_everything(tiny_index, tiny_corpus):
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0)
    rep = ws.serve(tiny_corpus.queries[:100])
    assert len(rep.results) == 100
    assert all(p >= 1 for p in rep.probes.values())


def test_compaction_improves_occupancy(tiny_index, tiny_corpus):
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0)
    with_c = ws.serve(tiny_corpus.queries[:128], compact=True)
    without = ws.serve(tiny_corpus.queries[:128], compact=False)
    assert with_c.occupancy > without.occupancy
    assert with_c.lane_steps <= without.lane_steps


def test_wave_scheduler_swaps_versions_mid_stream(tiny_index, tiny_corpus):
    """Mutations + merge_delta publishing new IndexVersions *while* a
    query stream is in flight must not corrupt lanes: every query
    still completes exactly once, results carry no tombstoned or
    duplicate ids, and docs added before serving started are findable.
    """
    from repro.index import IndexRegistry, LiveIndex, version_of

    live = LiveIndex(tiny_index, delta_cap=512)
    rng = np.random.default_rng(5)
    pre = live.add(tiny_corpus.docs[:32]
                   + rng.normal(scale=1e-4, size=(32, 24)).astype(np.float32))
    reg = IndexRegistry(version_of(live))
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0, registry=reg)
    deleted = []

    def mutate(wave):
        if wave % 2 == 0:
            live.add(rng.normal(size=(8, 24)).astype(np.float32))
        doomed = rng.integers(0, 8000, 4)
        live.delete(doomed)
        deleted.extend(int(i) for i in doomed)
        if wave == 4:
            live.merge_delta()
        reg.publish(version_of(live))

    rep = ws.serve(tiny_corpus.queries[:100], on_wave=mutate)
    assert len(rep.results) == 100
    assert reg.swaps > 1 and live.version >= 1
    dead = set(deleted)
    hits_pre = 0
    for qid, ids in rep.results.items():
        real = ids[ids >= 0]
        assert len(set(real.tolist())) == len(real)       # no dups
        # the final scrub ran against the last version this lane saw;
        # docs deleted *before* that are guaranteed gone
        hits_pre += int(np.isin(ids, pre).any())
    assert hits_pre > 0          # pre-serve adds are findable via overlay
    # queries identical to a pre-added doc must retrieve it
    probe_q = tiny_corpus.docs[:8].astype(np.float32)
    rep2 = ws.serve(probe_q)
    for qid in range(8):
        assert int(pre[qid]) in rep2.results[qid].tolist() \
            or int(qid) in rep2.results[qid].tolist()


def test_wave_results_match_plain_search(tiny_index, tiny_corpus,
                                         tiny_exact):
    """Same policy, same index -> same effectiveness ballpark (wave
    chunking quantises probe counts, so compare recall not ids)."""
    q = tiny_corpus.queries[:128]
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=1, k=10,
                       n_probe=24, delta=3, phi=90.0)
    rep = ws.serve(q)
    ids = np.stack([rep.results[i] for i in range(128)])
    r_wave = metrics.r_star_at_1(ids, tiny_exact[1][:128, 0])
    res = search(tiny_index, jnp.asarray(q),
                 policies.patience(24, 3, 90.0, k=10, tau=3))
    r_plain = metrics.r_star_at_1(np.asarray(res.topk_ids),
                                  tiny_exact[1][:128, 0])
    assert abs(r_wave - r_plain) < 0.08


def _col(stage):
    return STAGES.index(stage)


def _check_stage_rows(rep):
    """Shape and the stages every wave runs; ``admit`` positive exactly
    on the waves that dispatched ``_admit``; ``wait_admit`` 0 on every
    wave, since the host knows the lanes ``_admit`` fills."""
    ms = rep.stage_ms
    assert ms.shape == (rep.waves, len(STAGES))
    assert (ms >= 0).all()
    for s in ("wait_advance", "harvest", "pin", "advance"):
        assert (ms[:, _col(s)] > 0).all(), s
    admitted = ms[:, _col("admit")] > 0
    assert admitted.sum() == rep.admit_calls
    # no deadline, no rebuilder: those stages never run
    assert (ms[:, [_col("wait_admit"), _col("ladder"),
                   _col("rebuild")]] == 0).all()


def test_stage_times_and_counters_of_a_plain_serve(tiny_index,
                                                   tiny_corpus):
    q = tiny_corpus.queries[:100]
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0)
    rep = ws.serve(q)
    _check_stage_rows(rep)
    assert rep.empty_waves == 0
    assert rep.admitted == len(rep.results) == 100
    # 100 queries over 32 lanes: at least four admissions
    assert 4 <= rep.admit_calls < rep.waves
    # the spans change nothing the loop serves
    ids = np.stack([rep.results[i] for i in range(100)])
    probes = np.asarray([rep.probes[i] for i in range(100)])
    ref = search(tiny_index, jnp.asarray(q),
                 policies.patience(24, 3, 90.0, k=10))
    np.testing.assert_array_equal(ids, np.asarray(ref.topk_ids))
    np.testing.assert_array_equal(probes, np.asarray(ref.probes))


class _HeldBack:
    """Query rows of which the first ``n_now`` are due at once and the
    rest once the scheduler's clock (one tick a reading) reaches
    ``at``: the first lanes drain meanwhile, and the loop runs waves
    with no active lane."""

    def __init__(self, queries, n_now, at):
        self._q, self._n_now, self._at = queries, n_now, at
        self.shape = queries.shape
        self.t = 0.0

    def clock(self):
        self.t += 1.0
        return self.t

    def __getitem__(self, sl):
        stop = sl.stop if self.t >= self._at else min(sl.stop, self._n_now)
        return self._q[sl.start: max(sl.start, stop)]


def test_empty_waves_and_zero_row_admits_are_counted(tiny_index,
                                                     tiny_corpus):
    src = _HeldBack(tiny_corpus.queries[:48], 16, at=40.0)
    ws = WaveScheduler(tiny_index, wave_size=16, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0, clock=src.clock)
    rep = ws.serve(src)
    assert len(rep.results) == rep.admitted == 48
    assert rep.empty_waves > 0
    _check_stage_rows(rep)
    # while nothing is due, free lanes still dispatch _admit with no row
    assert rep.admit_calls * 16 > rep.admitted


def test_stage_spans_reach_the_profiler(tmp_path, tiny_index, tiny_corpus):
    """Each stage is a ``serve.<stage>`` span on the trace's host plane:
    one ``serve.advance`` per wave, and one ``serve.wait_advance`` per
    pass of the loop (the waves and the last pass, which only waits
    and harvests)."""
    ws = WaveScheduler(tiny_index, wave_size=32, chunk=4, k=10,
                       n_probe=24, delta=3, phi=90.0)
    q = tiny_corpus.queries[:40]
    ws.serve(q)                                  # compiled outside
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        rep = ws.serve(q)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("serve.advance") == rep.waves
    assert names.count("serve.wait_advance") == rep.waves + 1
    assert names.count("serve.admit") == rep.admit_calls
    assert {n for n in names if n.startswith("serve.")} \
        == {f"serve.{s}" for s in STAGES if s not in ("wait_admit",
                                                      "ladder",
                                                      "rebuild")}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("share", [0.0, 0.25, 0.5, 1.0])
def test_host_knows_the_lanes_admit_fills(tiny_index, seed, share):
    """``_admitted_lanes`` on the host's ``active`` names exactly the
    lanes the device's ``_admit`` fills, for any mask and any row count
    from none to every free lane."""
    w, d = 16, tiny_index.docs.shape[1]
    rng = np.random.default_rng(seed)
    active = rng.random(w) < 0.5
    m = int(round(share * (~active).sum()))
    state = serving._empty_state(w, d, 8, 10)._replace(
        active=jnp.asarray(active),
        qid=jnp.asarray(np.where(active, 1000 + np.arange(w), -1),
                        jnp.int32))
    ids = np.full(w, -1, np.int32)
    ids[:m] = 7 + np.arange(m)
    q = rng.normal(size=(w, d)).astype(np.float32)
    out = serving._admit(state, tiny_index.centroids, jnp.asarray(q),
                         jnp.asarray(ids), 8)
    new_active, qid = jax.device_get((out.active, out.qid))
    filled = serving._admitted_lanes(active, m)
    assert len(filled) == m
    np.testing.assert_array_equal(np.flatnonzero(new_active & ~active),
                                  filled)
    np.testing.assert_array_equal(qid[filled], ids[:m])


def _ladder_scheduler(index):
    """A deadline-ladder scheduler and its ``on_wave`` clock: 1 ms
    waves, a 20 ms stall every fourth, forcing exits."""
    from repro.runtime.chaos import SimClock
    clock = SimClock()
    ws = WaveScheduler(index, wave_size=16, chunk=1, k=10, n_probe=16,
                       delta=3, phi=90.0, deadline_ms=5.0, clock=clock)

    def tick(wave):
        clock.advance(20.0 if wave % 4 == 0 else 1.0)
    return ws, tick


def _registry_scheduler(index):
    from repro.index import IndexRegistry, LiveIndex, version_of
    reg = IndexRegistry(version_of(LiveIndex(index, delta_cap=256)))
    return WaveScheduler(index, wave_size=32, chunk=4, k=10, n_probe=24,
                         delta=3, phi=90.0, registry=reg), None


def _host_reads(monkeypatch):
    """From here on, note each read of a ``jax.Array``'s value on the
    host: through ``_value`` (``device_get``, ``int()``, ``bool()``,
    ``tolist()``) and through the buffer protocol, which is how
    ``np.asarray`` reads an array on the CPU."""
    from jax._src.array import ArrayImpl
    reads = []
    value, buffer = ArrayImpl._value, ArrayImpl.__buffer__

    def counted_value(self):
        reads.append(self.shape)
        return value.fget(self)

    def counted_buffer(self, flags):
        reads.append(self.shape)
        return buffer(self, flags)
    monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
    monkeypatch.setattr(ArrayImpl, "__buffer__", counted_buffer)
    return reads


@pytest.mark.parametrize("how", ["plain", "ladder", "registry",
                                 "planted"])
def test_one_blocking_pull_a_wave(tiny_index, tiny_corpus, monkeypatch,
                                  how):
    """The loop reads the device once a pass, ``waves`` + 1 times, and
    nothing else: every host read of an array during the serve is one of
    the :data:`serving._PULLED` fields of that read.  The deadline
    ladder's forced exits and the registry's pin included; and what it
    reads is the state ``_advance`` hands back, fault planted in it as
    the benchmark's checks plant one."""
    q, mark = tiny_corpus.queries[:64], 999_999
    if how == "ladder":
        ws, on_wave = _ladder_scheduler(tiny_index)
    elif how == "registry":
        ws, on_wave = _registry_scheduler(tiny_index)
    else:
        ws, on_wave = WaveScheduler(tiny_index, wave_size=32, chunk=4,
                                    k=10, n_probe=24, delta=3,
                                    phi=90.0), None
    if how == "planted":
        advance = serving._advance

        def altered(index, state, *a, **kw):
            st = advance(index, state, *a, **kw)
            return st._replace(topk_ids=st.topk_ids.at[:, 0].set(mark))
        monkeypatch.setattr(serving, "_advance", altered)
    get, gets = jax.device_get, []

    def counted(x):
        gets.append(1)
        return get(x)
    monkeypatch.setattr(jax, "device_get", counted)
    reads = _host_reads(monkeypatch)
    rep = ws.serve(q, on_wave=on_wave)
    monkeypatch.undo()
    assert set(rep.results) == set(range(64))
    assert rep.host_pulls == len(gets) == rep.waves + 1
    assert len(reads) == len(serving._PULLED) * rep.host_pulls
    if how == "ladder":
        assert "forced_exit" in rep.degraded.values()
    if how == "planted":
        assert all(ids[0] == mark for ids in rep.results.values())


@pytest.mark.parametrize("deadline_ms", [5.0, 2.0])
def test_ladder_budgets_the_time_spent_in_the_read(tiny_index, tiny_corpus,
                                                   monkeypatch,
                                                   deadline_ms):
    """The wave cost the deadline ladder budgets against is the whole
    wave, the device time the blocking read waits out included: a serve
    whose 3 ms waves are all spent in the read degrades, sheds and
    reports exactly as one whose waves are spent in host code, and no
    query overshoots its budget by more than one wave."""
    from repro.runtime.chaos import SimClock
    wave_ms, q = 3.0, tiny_corpus.queries[:64]

    def serve(in_read):
        clock = SimClock()
        ws = WaveScheduler(tiny_index, wave_size=16, chunk=1, k=10,
                           n_probe=16, delta=3, phi=90.0,
                           deadline_ms=deadline_ms, clock=clock)
        get = jax.device_get

        def slow_get(x):
            clock.advance(wave_ms)
            return get(x)
        with monkeypatch.context() as mp:
            if in_read:
                mp.setattr(jax, "device_get", slow_get)
            return ws.serve(q, on_wave=None if in_read
                            else lambda w: clock.advance(wave_ms))
    rep, host = serve(True), serve(False)
    assert rep.wave_cost_ms == pytest.approx(wave_ms)
    assert rep.degraded == host.degraded
    assert rep.latency_ms == host.latency_ms
    reasons = set(rep.degraded.values())
    if deadline_ms > wave_ms:
        assert "capped_probes" in reasons
    else:
        assert "shed" in reasons
    for qid, lat in rep.latency_ms.items():
        assert lat <= deadline_ms + wave_ms + 1e-9, qid
