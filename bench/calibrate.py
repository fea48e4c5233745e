#!/usr/bin/env python3
"""The measurements a cell is defined from, each run once on the chip.

    python3 bench/calibrate.py spread --config <name> --spreads a,b,.. --seed n
    python3 bench/calibrate.py build --config <name> --seed n
    python3 bench/calibrate.py stall --workload <cell> --seconds s --seed n
    python3 bench/calibrate.py freeze --seconds s
    python3 bench/calibrate.py knee --workload <cell> --rates r1,r2,.. \
        --seconds s --seed n
    python3 bench/calibrate.py limits --workload <cell> --seeds a,b,.. \
        --seconds s
    python3 bench/calibrate.py length --workload <cell> --seeds a,b,.. \
        --lengths l1,l2,..

* ``spread``: for each generator spread, the recall that fixed N
  (patience off) and the patience policy reach on the generated corpus,
  against the exact top-k, and the mean probes.  The configuration's
  spread is the one whose fixed-N R*@1 is nearest the paper's 0.95.
* ``build``: the numbers the check holds the program's partition to
  (``centroid_drift``, ``assign_excess``) and the recall it gives, for
  the program's build and for builds with one shortcut planted: one
  Lloyd iteration, the last assignment step skipped, k-means trained
  on a sample, centroids rounded to bf16.  The limits are set between
  the program's readings and the shortcuts'.
* ``stall``: where long gaps between waves come from (a watchdog on
  the serving thread, then one traced window); ``freeze``: whether
  such gaps come without the serving loop, on the host alone or with
  small device reads.
* ``knee``: one set-up, then one open-loop window per rate.  The knee
  is the highest rate whose admission queue does not grow over the
  window; the steady cell's rate is fixed at about 0.8 of it.  Under
  live traffic each window also applies its writes, so the delta
  buffer fills from window to window.
* ``limits``: per seed a full set-up and a window at the cell's own
  load, then the numbers the check compares, for the program and for
  the control (the reference in bf16 in the program's place).  The
  check's limits are set between the two readings.
* ``length``: per seed one set-up and one window of each length: the
  spread of the end-to-end metrics across seeds at each length, from
  which ``run_seconds`` is chosen.

Every line of output is one JSON object.  Needs a TPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import corpus, harness, reference  # noqa: E402
from bench.harness import emit  # noqa: E402


def spread(a) -> None:
    base = harness.load("configs", a.config)
    mix = {"hard_frac": a.hard_frac, "easy_noise": a.easy_noise}
    for s in (float(x) for x in a.spreads.split(",")):
        cfg = copy.deepcopy(base)
        cfg["generator"].update(spread=s, seed=a.seed)
        dep = harness.Deployment(cfg, a.seed, a.queries, mix)
        held = dep.release()
        part = reference.partition_of(held["centroids"], held["ids"],
                                      held["offsets"], held["sizes"],
                                      held["list_pad"])
        docs = jnp.asarray(dep.docs)
        q = dep.queries
        exact = reference.exact_blocks(q, docs, cfg["k"])
        fixed = dict(cfg, patience_delta=1 << 20)
        f_ids, _ = reference.search_blocks(q, part, docs, fixed)
        p_ids, p_probes = reference.search_blocks(q, part, docs, cfg)
        emit(mode="spread", config=a.config, spread=s,
             clusters=int(part.centroids.shape[0]),
             rows=int(held["ids"].shape[0]),
             fixed_n=reference.r_star(f_ids, exact),
             fixed_n_1nn_in_topk=float((f_ids == exact[:, :1]).any(1).mean()),
             patience=reference.r_star(p_ids, exact),
             patience_1nn_in_topk=float(
                 (p_ids == exact[:, :1]).any(1).mean()),
             mean_probes=float(p_probes.mean()),
             probes_quartiles=[float(x) for x in
                               np.percentile(p_probes, [25, 50, 75])])
        del docs, dep


def _setup(workload: str, seed: int, n_pool: int = 0,
           seconds: float = 5.0):
    """The cell's configuration and traffic, and a warmed deployment
    with at least ``n_pool`` queries (a live one planned for a window
    of ``seconds``); returns (cfg, tr, dep, rate)."""
    bm = harness.benchmark()
    cell = harness.cell_of(bm, workload)
    cfg = harness.load("configs", cell["config"])
    tr = harness.load("traffic", cell["traffic"])
    dep = harness.deployment(cfg, seed, tr, seconds,
                             max(n_pool, tr["pool_queries"]))
    return cfg, tr, dep, harness.warm_up_rate(dep, tr)


def _window(dep, tr, seconds: float, seed: int, rate: float):
    """One window at the traffic's load, as the readers see it."""
    rep, wall, attempted, due, admit, _ = harness.measure(
        dep, tr, seconds, seed,
        harness.window_queries(dep, tr, rate, seconds), False)
    return harness.Window(dep.cfg, rep, wall, attempted, 0.0, 0, "", due,
                          admit)


def knee(a) -> None:
    rates = [float(x) for x in a.rates.split(",")]
    n_pool = int(max(rates) * a.seconds * 1.05) + 4096
    _, tr, dep, _ = _setup(a.workload, a.seed, n_pool, a.seconds)
    for r in rates:
        arr = {"kind": "poisson", "phases": [{"seconds": 1.0,
                                              "rate_qps": r}]}
        w = _window(dep, dict(tr, arrivals=arr), a.seconds, a.seed, 0.0)
        queue = w.queue_ms()
        fifth = max(1, queue.size // 5)
        emit(mode="knee", rate_qps=r, requests=int(queue.size),
             wall_s=w.wall_s, served_qps=queue.size / w.wall_s,
             waves=w.report.waves, occupancy=w.report.occupancy,
             p50_ms=float(np.percentile(w.latency_ms(), 50)),
             p99_ms=float(np.percentile(w.latency_ms(), 99)),
             queue_p99_first_fifth_ms=float(
                 np.percentile(queue[:fifth], 99)),
             queue_p99_last_fifth_ms=float(
                 np.percentile(queue[-fifth:], 99)),
             mean_probes=float(np.mean(list(w.report.probes.values()))))


def limits(a) -> None:
    for seed in (int(x) for x in a.seeds.split(",")):
        _, tr, dep, rate = _setup(a.workload, seed, seconds=a.seconds)
        w = _window(dep, tr, a.seconds, seed, rate)
        numbers = harness.check_window(dep, w.report, w.attempted, tr,
                                       seed, control=True)
        emit(mode="limits", workload=a.workload, seed=seed, **numbers)
        del dep


def length(a) -> None:
    """Per seed one set-up, then one window of each length, each read
    by the cell's end-to-end readers: the spread across seeds at each
    length is what sets ``run_seconds``."""
    lengths = [float(x) for x in a.lengths.split(",")]
    metrics = harness.cell_metrics(harness.benchmark(), a.workload, False)
    for seed in (int(x) for x in a.seeds.split(",")):
        _, tr, dep, rate = _setup(a.workload, seed, seconds=sum(lengths))
        for secs in lengths:
            w = _window(dep, tr, secs, seed, rate)
            vals = {m["name"]: harness.reader(m["name"])(w)
                    for m in metrics
                    if m["name"] not in ("setup_s", "hbm_bytes_per_doc")}
            emit(mode="length", workload=a.workload, seed=seed,
                 seconds=secs, **vals)
        del dep


def _variants(cfg: dict, docs: np.ndarray):
    """The program's k-means with one shortcut planted in each variant,
    as (name, centroids, per-doc list) after the program's split."""
    from repro.core import kmeans as km
    c, it, seed = cfg["n_clusters"], cfg["kmeans_iters"], \
        cfg["generator"]["seed"]

    def split(cen, assign):
        return km.split_oversized(docs, np.asarray(cen), np.asarray(assign),
                                  cfg["list_pad"], seed=seed)

    # k-means stopped after one Lloyd iteration
    yield ("one_iteration", *split(*km.kmeans(docs, c, n_iters=1,
                                              seed=seed)))
    # the last assignment step skipped: the membership is the one the
    # final centroids were averaged from
    cen, assign = km.kmeans(docs, c, n_iters=it - 1, seed=seed)
    x = jnp.asarray(docs)
    sums = jax.ops.segment_sum(x, jnp.asarray(assign), num_segments=c)
    cnt = jax.ops.segment_sum(jnp.ones(docs.shape[0], jnp.float32),
                              jnp.asarray(assign), num_segments=c)
    cen = np.where(np.asarray(cnt)[:, None] > 0,
                   np.asarray(sums / jnp.maximum(cnt, 1.0)[:, None]), cen)
    del x, sums
    yield ("stale_assignment", *split(cen, assign))
    # trained on one doc in eight, then every doc assigned
    rng = np.random.default_rng(seed)
    sub = np.sort(rng.choice(docs.shape[0], docs.shape[0] // 8,
                             replace=False))
    cen, _ = km.kmeans(docs[sub], c, n_iters=it, seed=seed)
    assign = np.asarray(jax.jit(km._assign_block)(jnp.asarray(docs),
                                                  jnp.asarray(cen))[0])
    yield ("sample_trained", *split(cen, assign))


def build(a) -> None:
    """The numbers the check holds the program's partition to, for the
    program's own build and for builds with a shortcut planted, with
    the recall each partition gives."""
    cfg = harness.load("configs", a.config)
    mix = {"hard_frac": a.hard_frac, "easy_noise": a.easy_noise}
    dep = harness.Deployment(cfg, a.seed, a.queries, mix)
    held = dep.release()
    own = reference.list_of(cfg["n_docs"], held["ids"], held["offsets"],
                            held["sizes"])
    docs, q = dep.docs, dep.queries
    del dep

    def report(name, cen, own):
        sizes = np.bincount(own, minlength=cen.shape[0])
        order = np.argsort(own, kind="stable")
        part = reference.partition_of(cen, order,
                                      np.cumsum(sizes) - sizes, sizes,
                                      cfg["list_pad"])
        d = corpus.docs_on_device(cfg)[0]
        t = time.perf_counter()
        km = reference.kmeans_excess(d, cen, own)
        km_s = time.perf_counter() - t
        ids, probes = reference.search_blocks(q, part, d, cfg)
        exact = reference.exact_blocks(q, d, cfg["k"])
        emit(mode="build", config=a.config, variant=name,
             lists=int(cen.shape[0]), oversized=int((sizes >
                                                     cfg["list_pad"]).sum()),
             kmeans=km, kmeans_s=km_s, recall=reference.r_star(ids, exact),
             mean_probes=float(probes.mean()))
        del d

    report("program", held["centroids"], own)
    rounded = np.asarray(jnp.asarray(held["centroids"]).astype(
        jnp.bfloat16).astype(jnp.float32))
    report("centroids_rounded_to_bf16", rounded, own)
    for name, cen, assign in _variants(cfg, docs):
        report(name, np.asarray(cen, np.float32), np.asarray(assign))


class _Watchdog:
    """A thread that sleeps 1 ms at a time and notes how late each sleep
    wakes: a wake far later than the sleep means no Python thread of
    the process ran meanwhile.  ``probe`` runs after every wake."""

    def __init__(self, probe=None):
        self.late = []
        self._probe = probe
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            t = time.perf_counter()
            time.sleep(0.001)
            self.late.append(time.perf_counter() - t - 0.001)
            if self._probe is not None:
                self._probe()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def summary(self, over_ms: float) -> dict:
        late = 1e3 * np.asarray(self.late)
        return {"watchdog_late_max_ms": float(late.max()),
                f"watchdog_late_over_{over_ms:g}ms": int((late > over_ms)
                                                         .sum())}


def stall(a) -> None:
    """Where the long gaps between waves come from.  Windows at the
    cell's load with a :class:`_Watchdog` that, while no wave has
    started for ``--gap-ms``, samples the serving thread's stack; then
    one traced window whose longest waves are listed with the device
    operations and host events inside them."""
    import tempfile
    import traceback
    from collections import Counter
    _, tr, dep, rate = _setup(a.workload, a.seed)
    main_id = threading.get_ident()

    for i in range(a.windows):
        marks = harness.WaveMarks(False)
        stacks = Counter()

        def sample():
            if time.perf_counter() - marks.times[-1] > a.gap_ms / 1e3:
                fr = sys._current_frames().get(main_id)
                if fr is not None:
                    stacks["".join(traceback.format_stack(fr)[-6:])] += 1

        with _Watchdog(sample) as dog:
            rep = harness.measure(dep, tr, a.seconds, a.seed + i,
                                  harness.window_queries(dep, tr, rate,
                                                         a.seconds),
                                  False, marks)[0]
        gaps = marks.gaps_ms()
        emit(mode="stall", window=i, waves=rep.waves,
             gaps_over_ms={str(g): int((gaps > g).sum())
                           for g in (30, 60, 100)},
             longest_gaps_ms=sorted(gaps.tolist())[-5:], **dog.summary(20),
             stacks=[[n, st] for st, n in stacks.most_common(4)])

    from jax.profiler import ProfileData
    tdir = tempfile.mkdtemp(prefix="bench-stall-")
    jax.profiler.start_trace(tdir)
    harness.measure(dep, tr, a.seconds, a.seed + 99,
                    harness.window_queries(dep, tr, rate, a.seconds),
                    False, harness.WaveMarks(True))
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    waves, host, dev = [], [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                ev = (plane.name + "|" + line.name, e.name[:100],
                      e.start_ns, e.start_ns + e.duration_ns)
                if plane.name.startswith("/device:"):
                    dev.append(ev)
                elif e.name == "bench.wave":
                    waves.append(ev)
                elif e.duration_ns > 2e6:
                    host.append(ev)
    waves.sort(key=lambda w: w[2] - w[3])
    for w in waves[:a.top]:
        lo, hi = w[2], w[3]
        inside = [e for e in dev if e[2] < hi and e[3] > lo]
        emit(mode="stall_trace", wave_ms=(hi - lo) / 1e6,
             device_ops=len(inside),
             device_longest=[[e[0], e[1], (e[3] - e[2]) / 1e6,
                              (e[2] - lo) / 1e6] for e in sorted(
                                  inside, key=lambda e: e[2] - e[3])[:8]],
             host=[[e[0], e[1], (e[3] - e[2]) / 1e6, (e[2] - lo) / 1e6]
                   for e in host if e[2] < hi and e[3] > lo][:20])
    shutil.rmtree(tdir, ignore_errors=True)


def freeze(a) -> None:
    """Do long gaps come with the device?  Two loops of ``--seconds``
    each, with a :class:`_Watchdog` beside them: first the host alone
    (2 ms sleeps, the device idle), then a small jitted call read back
    to the host 100 times a second.  A gap in the first is the host's;
    a gap only in the second is the runtime's."""
    def watched(step):
        gaps = []
        with _Watchdog() as dog:
            end = time.perf_counter() + a.seconds
            last = time.perf_counter()
            while last < end:
                step()
                now = time.perf_counter()
                gaps.append(now - last)
                last = now
        g = 1e3 * np.asarray(gaps)
        return {"steps": int(g.size), "gaps_over_60ms": int((g > 60).sum()),
                "longest_gaps_ms": sorted(g.tolist())[-3:],
                **dog.summary(60)}

    emit(mode="freeze", loop="host", **watched(lambda: time.sleep(0.002)))
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((64,), jnp.int32)
    np.asarray(f(x))

    def device_step():
        np.asarray(f(x))
        time.sleep(0.01)

    emit(mode="freeze", loop="device", **watched(device_step))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    p = sub.add_parser("spread")
    p.add_argument("--config", required=True)
    p.add_argument("--spreads", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--queries", type=int, default=512)
    p.add_argument("--hard-frac", type=float, default=0.35)
    p.add_argument("--easy-noise", type=float, default=0.15)
    p = sub.add_parser("build")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--queries", type=int, default=512)
    p.add_argument("--hard-frac", type=float, default=0.35)
    p.add_argument("--easy-noise", type=float, default=0.15)
    p = sub.add_parser("stall")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--windows", type=int, default=4)
    p.add_argument("--gap-ms", type=float, default=40.0)
    p.add_argument("--top", type=int, default=4)
    p = sub.add_parser("freeze")
    p.add_argument("--seconds", type=float, default=60.0)
    p = sub.add_parser("knee")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    p = sub.add_parser("limits")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p = sub.add_parser("length")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--lengths", required=True)
    a = ap.parse_args()
    harness.require_devices(1, "tpu")
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    {"spread": spread, "build": build, "stall": stall, "freeze": freeze,
     "knee": knee, "limits": limits, "length": length}[a.mode](a)
    emit(mode="done", seconds=time.perf_counter() - T0)


if __name__ == "__main__":
    main()
