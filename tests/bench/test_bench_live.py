"""The live traffic (``bench/live.py``) at a tiny size on the CPU: the
write schedule repeats from its seed, a sound run is correct and
reports the ``.live`` metrics, and each fault of the live path that the
check is there to catch makes the run incorrect."""
import json
import os
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
from test_bench_faults import _altered_answer  # noqa: E402
from test_bench_harness import COMMON, TINY_CFG, make_root  # noqa: E402

from bench import corpus, harness, live  # noqa: E402
from repro.core import serving  # noqa: E402
from repro.index import delta as delta_mod  # noqa: E402
from repro.index import live as live_mod  # noqa: E402
from repro.index import wal as wal_mod  # noqa: E402

TINY_LIVE = dict(
    COMMON, kind="live",
    arrivals={"kind": "poisson", "phases": [{"seconds": 1.0,
                                             "rate_qps": 48}]},
    writes={"delta_cap": 128, "fill_adds": 32, "fill_batch": 16,
            "every_ms": 250, "adds": 4, "delete_adds": 1, "delete_main": 1,
            "group_commit_n": 1},
    limits={"writes_lost": 0, "phantom_ids": 0, "wal_faults": 0})
SEED = 2**31 + 23
# the live cell's per-layer metrics, as a benchmark that serves the
# live traffic lists them
LIVE_METRICS = [
    dict(name=f"{n}.live", unit=u, better="lower", source=src,
         layer=layer, moves="p95_ms", workloads=["star768-live"])
    for n, u, src, layer in (
        ("wave_ms", "ms", "host_clock", "serve loop"),
        ("host_ms", "ms", "program_span", "serve loop"),
        ("wait_ms", "ms", "program_span", "serve loop"),
        ("idle_share", "%", "device_trace", "device"),
        ("write_ms", "ms", "host_clock", "live index"),
        ("delta_fill", "ratio", "program_counter", "live index"))]
LIVE_NUMBERS = ("writes_lost", "phantom_ids", "wal_faults")


def make_live_root(tmp: Path) -> Path:
    """The tiny root of ``test_bench_harness`` with a live cell."""
    root = make_root(tmp)
    (root / "bench" / "traffic" / "live.json").write_text(
        json.dumps(TINY_LIVE))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "star768-live", "config": "tiny",
                            "traffic": "live", "chips": 1, "why": "tiny"})
    for m in bm["end_to_end"]:
        if m["name"] == "p95_ms":
            m["workloads"].append("star768-live")
    bm["per_layer"] += LIVE_METRICS
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def _run(tmp_path, traced=False, seed=SEED):
    return harness.run("star768-live", seed, 1.0, traced,
                       root=make_live_root(tmp_path), platform="cpu")


def _plan(seed):
    """The set-up's batches and four window batches, as drawn."""
    docs, _, _ = corpus.generate(seed, TINY_CFG, 512, TINY_LIVE["queries"])
    src = corpus.query_sources(seed, TINY_CFG, 512, TINY_LIVE["queries"])
    due = np.linspace(0.0, 990.0, 48)
    p = live.Planner(docs, src, 0.015, TINY_LIVE["writes"])
    rng = np.random.default_rng([seed, 4])
    out = p.fill(rng, due) + [p.batch(rng, due, -np.inf)]
    wrng = np.random.default_rng([seed, 5, 1])
    out += [p.batch(wrng, due, t) for t in (0.0, 250.0, 500.0, 750.0)]
    return out, docs, src


def test_write_schedule_repeats_from_its_seed():
    a, docs, src = _plan(SEED)
    b, _, _ = _plan(SEED)
    c, _, _ = _plan(SEED + 1)
    assert len(a) == len(b) == len(c) == 2 + 1 + 4
    for x, y in zip(a, b):
        assert np.array_equal(x.vecs, y.vecs)
        assert x.del_adds == y.del_adds and x.del_main == y.del_main
    assert not np.array_equal(a[-1].vecs, c[-1].vecs)
    # every batch of the window has its shape: 4 unit adds, 1 + 1 deletes
    for x in a[2:]:
        assert x.vecs.shape == (4, TINY_CFG["dim"])
        assert np.allclose(np.linalg.norm(x.vecs, axis=1), 1.0, atol=1e-5)
        assert len(x.del_adds) == len(x.del_main) == 1
    # the set-up's adds fill 32 slots; no add or doc is deleted twice
    assert sum(x.vecs.shape[0] for x in a) == 32 + 5 * 4
    dels = [d for x in a for d in x.del_adds]
    main = [d for x in a for d in x.del_main]
    assert len(set(dels)) == len(dels) and len(set(main)) == len(main)
    # an add is deleted only by a later batch
    assert all(0 <= d < 32 + 4 * (j - 2)
               for j, x in enumerate(a) for d in x.del_adds)
    # deleted built docs are docs that queries of the pool copy
    assert np.isin(main, src[src >= 0]).all()


def test_query_sources_are_the_docs_easy_queries_copy():
    mix = TINY_LIVE["queries"]
    docs, q, _ = corpus.generate(5, TINY_CFG, 256, mix)
    src = corpus.query_sources(5, TINY_CFG, 256, mix)
    easy = np.nonzero(src >= 0)[0]
    assert easy.size == 256 - round(256 * mix["hard_frac"])
    best = np.argmax(q[easy] @ docs.T, axis=1)
    assert (best == src[easy]).mean() > 0.95


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_tiny_live_run_is_correct(tmp_path, traced):
    out = _run(tmp_path, traced)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(LIVE_NUMBERS) <= set(out["checks"])
    assert list(out)[-1] == "checks"
    m = out["metrics"]
    if traced:
        # no device trace on the CPU, so no idle share
        assert {"wave_ms.live", "host_ms.live", "wait_ms.live",
                "write_ms.live", "delta_fill.live"} <= set(m)
        assert m["write_ms.live"]["value"] > 0
        # 36 of 128 slots filled in set-up, then 4 adds a batch
        assert 36 / 128 <= m["delta_fill.live"]["value"] <= 52 / 128
    else:
        assert set(m) == {"setup_s", "p95_ms"}


def _acked_but_dead(add):
    """The second call's entries take slots but are never live."""
    calls = []

    def wrapped(self, vecs, ids, assign):
        add(self, vecs, ids, assign)
        calls.append(1)
        if len(calls) == 2:
            self.ids[self.count - len(ids): self.count] = -1
            self._view = None
    return wrapped


def _delete_ignored(_delete):
    def wrapped(self, ids):
        self._log(wal_mod.OP_DELETE,
                  np.atleast_1d(np.asarray(ids, np.int64)))
        self.seq += 1
    return wrapped


def _third_record_lost(append):
    calls = []

    def wrapped(self, op, seq, payload=None, *, force=False):
        calls.append(1)
        if len(calls) != 3:
            append(self, op, seq, payload, force=force)
    return wrapped


def _never_synced(_sync):
    def wrapped(self):
        self._f.flush()
        self._pending = 0
        self._group_t0 = None
    return wrapped


def _pin_kept(_refresh):
    """Lanes keep the version the serve call started with."""
    def wrapped(self, active_any):
        return False, False
    return wrapped


FAULTS = {
    # an add acknowledged and never applied
    "add_not_applied": (delta_mod.DeltaBuffer, "add", _acked_but_dead,
                        ("writes_lost",)),
    # a delete acknowledged and ignored
    "delete_ignored": (live_mod.LiveIndex, "delete", _delete_ignored,
                       ("writes_lost", "phantom_ids")),
    # an acknowledged batch missing from the log
    "record_missing": (wal_mod.MutationWAL, "append", _third_record_lost,
                       ("wal_faults",)),
    # acknowledged batches never fsync'd
    "not_fsynced": (wal_mod.MutationWAL, "_sync", _never_synced,
                    ("wal_faults",)),
    # an answer altered where it is produced
    "answer_altered": (serving, "_advance", _altered_answer,
                       ("queries_differ", "writes_lost")),
    # answers served from a version older than their admission
    "stale_version": (serving.WaveScheduler, "_refresh_pin", _pin_kept,
                      ("writes_lost", "phantom_ids", "queries_differ")),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_live_fault_makes_the_run_incorrect(tmp_path, monkeypatch,
                                                    fault):
    cls, name, make, numbers = FAULTS[fault]
    monkeypatch.setattr(cls, name, make(getattr(cls, name)))
    out = _run(tmp_path)
    assert out["correct"] is False
    over = [n for n in numbers
            if out["checks"][n]["value"] > out["checks"][n]["limit"]]
    assert over, out["checks"]


def test_control_in_bf16_fails_the_live_limit(tmp_path):
    """The bf16 reference against the f32 one over the same versions of
    a live run's sample."""
    root = make_live_root(tmp_path)
    tr = harness.load("traffic", "live", root)
    dep = harness.deployment(TINY_CFG, SEED, tr, 1.0)
    rate = harness.warm_up_rate(dep, tr)
    rep, _, attempted, _, _, _ = harness.measure(
        dep, tr, 1.0, SEED, harness.window_queries(dep, tr, rate, 1.0),
        False)
    numbers = harness.check_window(dep, rep, attempted, tr, SEED,
                                   control=True)
    assert numbers["queries_differ"] <= TINY_CFG["limits"]["queries_differ"]
    assert numbers["control.queries_differ"] > \
        TINY_CFG["limits"]["queries_differ"]


def test_log_reader_finds_damage(tmp_path):
    path = str(tmp_path / "m.wal")
    sent = [(1, np.ones((2, 4), np.float32)),
            (2, np.asarray([3, 5], np.int64))]
    with wal_mod.MutationWAL(path) as w:
        for i, (op, arr) in enumerate(sent):
            w.append(op, i + 1, arr)
    assert live.log_faults(path, sent) == {"records": 2, "sent": 2,
                                           "damaged": 0, "wrong": 0}
    assert live.log_faults(path, sent + sent[:1])["wrong"] == 1
    assert live.log_faults(path, [sent[1], sent[0]])["wrong"] == 2
    raw = bytearray(Path(path).read_bytes())
    raw[-1] ^= 0xFF                                   # last payload byte
    Path(path).write_bytes(bytes(raw))
    got = live.log_faults(path, sent)
    assert got["damaged"] == 1 and got["wrong"] == 1


def test_fsync_watch_sees_what_is_durable(tmp_path):
    path = tmp_path / "f"
    watch = live.FsyncWatch()
    watch.start()
    try:
        with open(path, "wb") as f:
            f.write(b"abc")
            f.flush()
            os.fsync(f.fileno())
            assert watch.covers(str(path))
            f.write(b"de")
            f.flush()
            assert not watch.covers(str(path))
    finally:
        watch.stop()
    assert watch._real is None


@pytest.mark.parametrize("name", ["wave_ms.live", "host_ms.live",
                                  "wait_ms.live", "idle_share.live",
                                  "write_ms.live", "delta_fill.live"])
def test_live_readers_are_silent_without_their_fields(name):
    read = harness.reader(name)
    rep = types.SimpleNamespace(waves=0, probes={}, lane_steps=0)
    w = types.SimpleNamespace(report=rep, cfg={"wave_size": 16}, trace=None,
                              live=None, wall_s=1.0)
    assert read(w) is None
    if name in ("write_ms.live", "delta_fill.live"):
        w.live = {"write_ms": np.asarray([3.0, 1.0, 2.0]),
                  "delta_fill": np.asarray([0.5, 0.25])}
        assert read(w) == pytest.approx(2.0 if name == "write_ms.live"
                                        else 0.375)
        w.live = {"write_ms": np.zeros(0), "delta_fill": np.zeros(0)}
        assert read(w) is None


def test_static_cells_take_the_static_path(tmp_path, monkeypatch):
    """The steady and batch cells build, serve and check as before: one
    ``build_index`` with the configuration's arguments, schedulers with
    no registry, plain wave marks, and no live index or log."""
    calls = {"build": [], "sched": [], "marks": [], "live": 0}
    build = harness.build_index
    sched = harness.WaveScheduler

    def spy_build(*a, **kw):
        calls["build"].append((a[1:], kw))
        return build(*a, **kw)

    class SpySched(sched):
        def __init__(self, *a, **kw):
            calls["sched"].append(kw)
            super().__init__(*a, **kw)

        def serve(self, queries, **kw):
            calls["marks"].append(type(kw.get("on_wave")))
            return super().serve(queries, **kw)

    def spy_live(*a, **kw):
        calls["live"] += 1
        raise AssertionError("a static cell made a live index")

    monkeypatch.setattr(harness, "build_index", spy_build)
    monkeypatch.setattr(harness, "WaveScheduler", SpySched)
    monkeypatch.setattr(live_mod.LiveIndex, "__init__", spy_live)
    root = make_root(tmp_path)
    for cell in ("star768-steady", "bigann128-batch"):
        out = harness.run(cell, 7, 1.0, False, root=root, platform="cpu")
        assert out["correct"] is True, out["checks"]
    c = TINY_CFG
    assert calls["build"] == [((c["n_clusters"],), dict(
        list_pad=c["list_pad"], n_iters=c["kmeans_iters"],
        seed=c["generator"]["seed"]))] * 2
    keys = {"wave_size", "k", "n_probe", "delta", "phi", "clock"}
    assert calls["sched"] and all(set(kw) == keys for kw in calls["sched"])
    # warm-up serves without marks; each window with plain wave marks
    assert [m for m in calls["marks"] if m is not type(None)] \
        == [harness.WaveMarks] * 2
    assert calls["live"] == 0
