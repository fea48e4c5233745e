"""One run of one cell: set-up, a measured window, the metrics, the check.

Everything that belongs to one cell is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic, and
  the metrics, each with the cells it applies to;
* ``bench/configs/<config>.json`` holds the deployment's sizes, the
  exit policy, the generator's settings and the check's limits;
* ``bench/traffic/<traffic>.json`` holds the arrivals and query mix;
  one with ``"kind": "live"`` also holds writes, served through the
  program's live index (``bench/live.py``), and limits of its own;
* ``bench/metrics/<metric>.py`` holds a ``read(window)`` that returns
  the metric's value from a :class:`Window`, or ``None`` where it
  finds nothing to read.

So a later cell, configuration or metric is new files plus new
entries in ``BENCHMARK.json``, and no edit here.

The window drives ``repro.core.serving.WaveScheduler.serve`` as the
program ships it, on an index built by the program's ``build_index``
over the configuration's corpus, drawn on the device from its data
seed; the queries, their arrivals and the check's sample are drawn
from the run's ``--seed``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus, reference, trace, traffic, yardstick
from repro.core import build_index
from repro.core.serving import ServeReport, WaveScheduler

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or too few of them."""


def load(kind: str, name: str, root: Path = ROOT) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads((root / "bench" / kind / f"{name}.json").read_text())


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_of(bm: dict, name: str) -> dict:
    for c in bm["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bm: dict, cell: str, per_layer: bool) -> List[dict]:
    """The metrics this cell reports: end-to-end ones untraced, the
    per-layer ones traced; an entry without ``workloads`` applies to
    every cell."""
    group = bm["per_layer"] if per_layer else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def require_devices(chips: int, platform: str) -> List:
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise NoChip(f"this cell needs {chips} {platform} device(s); JAX "
                     f"found {len(devs)} {devs[0].platform} "
                     f"({devs[0].device_kind})")
    return devs


def emit(**fields) -> None:
    """An earlier line of standard output: one JSON object."""
    print(json.dumps(fields), flush=True)


class CompileCount:
    """Backend compiles and persistent-cache hits while it is open."""

    def __init__(self):
        self.compiles = 0          # compile requests, cache hits included
        self.cache_hits = 0
        self.cache_misses = 0

    def _dur(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._evt)


@dataclasses.dataclass
class Window:
    """What one measured window leaves for the metric readers."""
    cfg: dict
    report: ServeReport
    wall_s: float
    attempted: int
    setup_s: float
    bytes_in_use: int
    device_kind: str
    due_ms: Optional[np.ndarray] = None      # open loop only
    admit_ms: Optional[np.ndarray] = None    # open loop only
    trace: Optional[trace.Summary] = None    # traced runs only
    needed_bytes: Optional[float] = None     # traced runs only
    live: Optional[dict] = None              # live traffic only

    def completed(self) -> np.ndarray:
        return np.asarray(sorted(self.report.results), np.int64)

    def latency_ms(self) -> Optional[np.ndarray]:
        """Per completed request, from when it was due to when ``serve``
        returned its result: (admission - due) + the scheduler's own
        admission-to-result latency."""
        if self.due_ms is None:
            return None
        q = self.completed()
        lat = np.asarray([self.report.latency_ms[i] for i in q])
        return self.admit_ms[q] - self.due_ms[q] + lat

    def queue_ms(self) -> Optional[np.ndarray]:
        """Per completed request, from when it was due to admission."""
        if self.due_ms is None:
            return None
        q = self.completed()
        return self.admit_ms[q] - self.due_ms[q]


class Deployment:
    """A configuration made real: generated data and the program's
    index over it, held until :meth:`release`."""

    def __init__(self, cfg: dict, seed: int, n_queries: int,
                 query_mix: dict):
        self.cfg = cfg
        t = time.perf_counter()
        self.docs, self.queries, self.prints = corpus.generate(
            seed, cfg, n_queries, query_mix)
        emit(phase="generate", seconds=time.perf_counter() - t,
             docs=list(self.docs.shape), queries=list(self.queries.shape))
        t = time.perf_counter()
        self.index = build_index(
            self.docs, cfg["n_clusters"], list_pad=cfg["list_pad"],
            n_iters=cfg["kmeans_iters"], seed=cfg["generator"]["seed"])
        jax.block_until_ready(self.index.docs)
        emit(phase="build_index", seconds=time.perf_counter() - t,
             clusters=int(self.index.n_clusters),
             rows=int(self.index.docs.shape[0]))

    def scheduler(self, clock=None) -> WaveScheduler:
        c = self.cfg
        return WaveScheduler(self.index, wave_size=c["wave_size"], k=c["k"],
                             n_probe=c["n_probe"],
                             delta=c["patience_delta"],
                             phi=c["patience_phi"], clock=clock)

    def wave_marks(self, traced: bool, source, seconds: float,
                   seed: int) -> "WaveMarks":
        """``serve``'s ``on_wave`` for a window."""
        return WaveMarks(traced)

    def release(self) -> dict:
        """Take what the check needs from the index to the host, then
        drop the program's hold on the device."""
        held = held_of(self.index)
        self.index = None
        gc.collect()
        return held


def held_of(ix) -> dict:
    """What the check takes from an index, as host arrays."""
    return {"ids": np.asarray(ix.doc_ids),
            "row_prints": np.asarray(corpus.fingerprint(ix.docs)),
            "offsets": np.asarray(ix.cluster_offsets),
            "sizes": np.asarray(ix.cluster_sizes),
            "centroids": np.asarray(ix.centroids),
            "dtypes": {"docs": str(ix.docs.dtype),
                       "centroids": str(ix.centroids.dtype)},
            "list_pad": ix.list_pad}


def is_live(tr: dict) -> bool:
    return tr.get("kind") == "live"


def deployment(cfg: dict, seed: int, tr: dict, seconds: float,
               n_pool: Optional[int] = None) -> Deployment:
    """The configuration made real for this traffic."""
    if is_live(tr):
        from bench import live
        return live.LiveDeployment(cfg, seed, tr, seconds, n_pool)
    return Deployment(cfg, seed, n_pool or tr["pool_queries"],
                      tr["queries"])


def warm_up(dep: Deployment, n: int) -> float:
    """Serve the last ``n`` queries of the pool (never the window's):
    compiles ``_admit`` and ``_advance`` for this index.  Returns the
    queries per second of the call."""
    t = time.perf_counter()
    rep = dep.scheduler().serve(dep.queries[-n:])
    return len(rep.results) / (time.perf_counter() - t)


def warm_up_rate(dep: Deployment, tr: dict) -> float:
    """Warm up the traffic's shapes; returns the rate that sizes a
    backlog window.  A backlog serves once more after the compile, so
    that its rate is that of compiled code."""
    rate = warm_up(dep, tr["warmup_queries"])
    if tr["arrivals"]["kind"] == "backlog":
        rate = warm_up(dep, tr["warmup_queries"])
    return rate


def window_queries(dep: Deployment, tr: dict, rate: float,
                   seconds: float) -> Optional[int]:
    """How many queries a backlog window serves (None: open loop)."""
    if tr["arrivals"]["kind"] != "backlog":
        return None
    n = int(rate * seconds)
    if n > dep.queries.shape[0] - tr["warmup_queries"]:
        raise ValueError(f"pool_queries {tr['pool_queries']} is too "
                         f"small for {n} queries")
    return n


class WaveMarks:
    """``serve``'s ``on_wave``: the time between waves, and in a traced
    window a host span around each wave's host work, for labelling
    the device's idle gaps."""

    def __init__(self, traced: bool):
        self._traced = traced
        self._open = None
        self.times = [time.perf_counter()]

    def __call__(self, _wave: int) -> None:
        self.times.append(time.perf_counter())
        if self._traced:
            self.close()
            self._open = jax.profiler.TraceAnnotation("bench.wave")
            self._open.__enter__()

    def close(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def gaps_ms(self) -> np.ndarray:
        return 1e3 * np.diff(np.asarray(self.times))


def measure(dep: Deployment, tr: dict, seconds: float, seed: int,
            n_window: Optional[int], traced: bool,
            marks: Optional[WaveMarks] = None):
    """The window.  Returns (report, wall_s, attempted, due, admit,
    trace summary or None).  ``marks`` replaces the window's own
    :class:`WaveMarks`."""
    arrivals = tr["arrivals"]
    if arrivals["kind"] == "backlog":
        source, due = dep.queries[:n_window], None
        clock = None
    else:
        rng = np.random.default_rng([seed % (1 << 64), 2])
        due = traffic.arrival_ms(arrivals, seconds, rng)
        if due.shape[0] > dep.queries.shape[0]:
            raise ValueError(f"the schedule has {due.shape[0]} arrivals, "
                             f"the query pool {dep.queries.shape[0]}")
        source = traffic.OpenLoopSource(dep.queries[:due.shape[0]], due)
        clock = source.clock
    ws = dep.scheduler(clock)
    marks = dep.wave_marks(traced, source, seconds, seed) if marks is None \
        else marks
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            jax.profiler.start_trace(tdir)
        span = (jax.profiler.TraceAnnotation("bench.window") if traced
                else contextlib.nullcontext())
        with CompileCount() as cc:
            with span:
                if clock is not None:
                    source.start()
                t = time.perf_counter()
                rep = ws.serve(source, on_wave=marks)
                wall = time.perf_counter() - t
                marks.close()
        summary = None
        if traced:
            jax.profiler.stop_trace()
            tr_obj = trace.load(tdir)
            summary = trace.summarize(tr_obj) if tr_obj.ops else None
            if summary is not None:
                emit(phase="trace", window_s=summary.window_s,
                     busy_s=summary.busy_s,
                     modules=summary.top(summary.module_s, 8),
                     ops=summary.top(summary.op_s, 20))
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    gaps = marks.gaps_ms()
    emit(phase="window", seconds=wall, waves=rep.waves,
         occupancy=rep.occupancy, completed=len(rep.results),
         compiles_in_window=cc.compiles,
         wave_gap_p50_ms=float(np.median(gaps)) if gaps.size else None,
         wave_gap_max_ms=float(gaps.max()) if gaps.size else None,
         wave_gaps_over_30ms=int((gaps > 30).sum()))
    admit = source.admit_ms if due is not None else None
    return rep, wall, source.shape[0], due, admit, summary


def draw_sample(done: np.ndarray, probes: np.ndarray, tr: dict,
                seed: int) -> np.ndarray:
    """The check's sample of the completed queries ``done`` (ascending,
    ``probes`` each): the traffic's ``check_longest`` of most probes and
    the rest of ``check_queries`` drawn from the seed, ascending."""
    rng = np.random.default_rng([seed % (1 << 64), 3])
    n_long = min(tr["check_longest"], done.size)
    longest = done[np.argsort(-probes, kind="stable")[:n_long]]
    rest = np.setdiff1d(done, longest)
    n_rand = min(tr["check_queries"] - n_long, rest.size)
    return np.sort(np.concatenate(
        [longest, rng.choice(rest, n_rand, replace=False)]))


def check(dep: Deployment, held: dict, rep: ServeReport, attempted: int,
          tr: dict, seed: int, *, control: bool = False) -> dict:
    """Compare what the window served with the plain reference.

    Returns the numbers compared (name -> value); with ``control`` the
    bf16 reference's numbers too, under ``control.<name>``."""
    cfg = dep.cfg
    t = time.perf_counter()
    faults = reference.index_faults(
        cfg["n_docs"], dep.prints, held["ids"], held["row_prints"],
        held["offsets"], held["sizes"], list_pad=cfg["list_pad"],
        dtypes=held["dtypes"], storage=cfg["storage"])
    part = reference.partition_of(held["centroids"], held["ids"],
                                  held["offsets"], held["sizes"],
                                  held["list_pad"])
    done = np.asarray(sorted(rep.results), np.int64)
    probes = np.asarray([rep.probes[i] for i in done])
    sample = draw_sample(done, probes, tr, seed)
    ids = np.stack([rep.results[i] for i in sample])
    prb = np.asarray([rep.probes[i] for i in sample])
    q = dep.queries[sample]
    t_up = time.perf_counter()
    # drawn again on the device: the same program and data seed give
    # the same bits as the copy that was indexed, which the prints show
    docs_dev = corpus.docs_on_device(cfg)[0]
    if not np.array_equal(np.asarray(corpus.fingerprint(docs_dev)),
                          dep.prints):
        raise RuntimeError("the corpus drawn again differs from the one "
                           "indexed")
    t_km = time.perf_counter()
    km = reference.kmeans_excess(
        docs_dev, held["centroids"],
        reference.list_of(cfg["n_docs"], held["ids"], held["offsets"],
                          held["sizes"]))
    t_ref = time.perf_counter()
    ref_ids, ref_probes = reference.search_blocks(q, part, docs_dev, cfg)
    t_ex = time.perf_counter()
    parts = reference.disagreement(ids, prb, ref_ids, ref_probes)
    numbers = {"failed": attempted - len(rep.results),
               "index_faults": sum(faults.values()),
               "centroid_drift": km["centroid_drift"],
               "assign_excess": km["assign_excess"],
               "queries_differ": parts["queries_differ"]}
    exact = reference.exact_blocks(q, docs_dev, cfg["k"])
    diag = {"sampled": int(sample.size), "differ": parts,
            "served": reference.r_star(ids, exact),
            "reference": reference.r_star(ref_ids, exact),
            "mean_probes_window": float(probes.mean()),
            "mean_probes_sample": float(prb.mean()),
            "index": faults, "kmeans": km,
            "redraw_s": t_km - t_up, "kmeans_s": t_ref - t_km,
            "reference_s": t_ex - t_ref,
            "exact_s": time.perf_counter() - t_ex}
    if control:
        c_ids, c_probes = reference.search_blocks(q, part, docs_dev, cfg,
                                                  lowp=True)
        c_parts = reference.disagreement(c_ids, c_probes, ref_ids,
                                         ref_probes)
        numbers["control.queries_differ"] = c_parts["queries_differ"]
        diag["control_differ"] = c_parts
        diag["control"] = reference.r_star(c_ids, exact)
    del docs_dev
    emit(phase="check", seconds=time.perf_counter() - t, **diag)
    return numbers


def check_window(dep: Deployment, rep: ServeReport, attempted: int,
                 tr: dict, seed: int, *, control: bool = False) -> dict:
    """Release the program's state and compare what the window served
    with the plain reference (a live run: see ``bench/live.py``)."""
    if is_live(tr):
        from bench import live
        return live.check(dep, rep, attempted, tr, seed, control=control)
    held = dep.release()
    return check(dep, held, rep, attempted, tr, seed, control=control)


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        root: Path = ROOT, platform: str = "tpu",
        t0: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t0 = time.perf_counter() if t0 is None else t0
    bm = benchmark(root)
    cell = cell_of(bm, workload)
    cfg = load("configs", cell["config"], root)
    tr = load("traffic", cell["traffic"], root)
    devs = require_devices(cell["chips"], platform)
    dev = devs[0]
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         count=len(devs), workload=workload, seed=seed, seconds=seconds,
         trace=int(traced))

    win_s = min(seconds, tr["trace_seconds"]) if traced else seconds
    with CompileCount() as cc:
        dep = deployment(cfg, seed, tr, win_s)
        t = time.perf_counter()
        rate = warm_up_rate(dep, tr)
        n_window = window_queries(dep, tr, rate, win_s)
        setup_s = time.perf_counter() - t0
    emit(phase="setup", seconds=setup_s, warm_up_s=time.perf_counter() - t,
         warm_up_qps=rate, compiles=cc.compiles, cache_hits=cc.cache_hits,
         cache_misses=cc.cache_misses)

    rep, wall, attempted, due, admit, summary = measure(
        dep, tr, win_s, seed, n_window, traced)
    if traced and summary is None and platform != "cpu":
        raise RuntimeError("the traced window holds no device operation")
    stats = dev.memory_stats() or {}
    in_use = int(stats.get("bytes_in_use", 0))
    peak = int(stats.get("peak_bytes_in_use", 0))

    needed = None
    if traced:
        done = np.asarray(sorted(rep.results), np.int64)
        n_probe = min(cfg["n_probe"], dep.index.n_clusters)
        ranks = []
        for i in range(0, done.size, 1024):      # one compiled shape
            q = np.zeros((1024, cfg["dim"]), np.float32)
            part = dep.queries[done[i: i + 1024]]
            q[:part.shape[0]] = part
            ranks.append(np.asarray(reference.rank_clusters(
                jnp.asarray(q), dep.index.centroids,
                n_probe=n_probe))[:part.shape[0]])
        needed = yardstick.needed_bytes(
            np.concatenate(ranks),
            np.asarray([rep.probes[i] for i in done]),
            np.asarray(dep.index.cluster_sizes), cfg["dim"])
    w = Window(cfg, rep, wall, attempted, setup_s, in_use,
               dev.device_kind, due, admit, summary, needed)
    if is_live(tr):
        w.live = dep.window_stats()
    metrics = {}
    for m in cell_metrics(bm, workload, traced):
        v = reader(m["name"], root)(w)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    lat = w.latency_ms()
    if lat is not None and lat.size:
        emit(phase="latency", requests=int(lat.size),
             mean_ms=float(lat.mean()),
             **{f"p{q}_ms": float(np.percentile(lat, q))
                for q in (50, 90, 95, 99)},
             max_ms=float(lat.max()),
             queue_p99_ms=float(np.percentile(w.queue_ms(), 99)))

    numbers = check_window(dep, rep, attempted, tr, seed)
    limits = dict(cfg["limits"], **tr.get("limits", {}))
    correct = all(numbers[k] <= limits[k] for k in limits)
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(numbers["failed"]), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devs), "memory_peak_bytes": peak}}
    if summary is not None:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top(summary.op_s),
                            "idle_gaps": summary.top(summary.gap_s)}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    return out


def main(argv: List[str], t0: float) -> int:
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        out = run(a.workload, a.seed, a.seconds, bool(a.trace), t0=t0)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0

