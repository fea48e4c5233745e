"""Retrieval serving driver: build an IVF index over a corpus, pick a
policy, stream a query log through the wave scheduler and report the
paper's effectiveness/efficiency metrics.

    PYTHONPATH=src python -m repro.launch.serve --policy patience \
        --n-docs 50000 --queries 1024

Live mutation (``repro.index``): ``--mutation-rate R`` injects R
document adds per wave (plus R//4 deletes of previously added docs)
*while the query stream is in flight*, through a ``LiveIndex`` +
``IndexRegistry`` pair; ``--merge-every M`` folds the delta buffer
into a fresh immutable index version every M waves.  The driver then
reports live-vs-static recall so regressions in the overlay path are
visible at the CLI.

Background re-clustering (``repro.index.rebuild``):
``--rebuild-every N`` requests a crash-safe centroid rebuild every N
waves; ``--rebuild-drift R`` instead arms a :class:`DriftTracker`
that requests one when added docs drift R× off the build-time
baseline.  Rebuild stages interleave with serving waves (throttled
under deadline pressure) and the swap is epoch-fenced: in-flight
lanes drain on the pinned version before the scheduler adopts the
re-clustered index.

Chaos mode (``repro.runtime.chaos``): ``--chaos`` runs the seeded
resilience drills — crash + WAL recovery over a mutation stream,
recall-vs-deadline curve under latency spikes, and shard-fault
retry/skip — and writes ``artifacts/BENCH_resilience.json``:

    PYTHONPATH=src python -m repro.launch.serve --chaos \
        --n-docs 4000 --queries 64 --clusters 32

``--deadline-ms`` (without ``--chaos``) serves the stream under a real
per-query latency budget through the degradation ladder.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax.numpy as jnp
import numpy as np

from repro.core import build_index, exact_topk, metrics, policies, search
from repro.core.serving import STAGES, WaveScheduler
from repro.data.synthetic import clustered_corpus
from repro.index import DeltaFull, IndexRegistry, LiveIndex, version_of
from repro.launch import compile_cache


def _serve(ws, queries, *, compact, on_wave=None):
    t1 = time.time()
    rep = ws.serve(queries, compact=compact, on_wave=on_wave)
    wall = (time.time() - t1) * 1000
    n = queries.shape[0]
    ids = np.stack([rep.results[i] for i in range(n)])
    probes = np.array([rep.probes[i] for i in range(n)])
    return rep, ids, probes, wall


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="patience",
                    choices=["fixed", "patience"])
    ap.add_argument("--n-docs", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--clusters", type=int, default=256)
    ap.add_argument("--queries", type=int, default=1024)
    ap.add_argument("--n-probe", type=int, default=48)
    ap.add_argument("--k", type=int, default=50)
    ap.add_argument("--delta", type=int, default=5)
    ap.add_argument("--phi", type=float, default=95.0)
    ap.add_argument("--wave-size", type=int, default=128)
    ap.add_argument("--no-compact", action="store_true")
    ap.add_argument("--mutation-rate", type=int, default=0,
                    help="doc adds per wave (deletes at rate//4) "
                         "streamed against the live index")
    ap.add_argument("--merge-every", type=int, default=16,
                    help="fold the delta buffer into a new index "
                         "version every N waves")
    ap.add_argument("--delta-cap", type=int, default=4096,
                    help="delta buffer capacity (slots)")
    ap.add_argument("--rebuild-every", type=int, default=0,
                    help="request a background centroid rebuild every "
                         "N waves of the live stream (0 = off); stages "
                         "interleave with serving waves and the swap "
                         "is epoch-fenced")
    ap.add_argument("--rebuild-drift", type=float, default=0.0,
                    help="drift-ratio threshold that triggers a "
                         "rebuild (0 = off): mean nearest-centroid "
                         "distance of added docs vs the build-time "
                         "baseline")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-query latency budget; under pressure the "
                         "scheduler walks the degradation ladder "
                         "instead of blowing it")
    ap.add_argument("--chaos", action="store_true",
                    help="run the seeded resilience drills and write "
                         "artifacts/BENCH_resilience.json")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-crash-every", type=int, default=7,
                    help="inject a crash at every Nth mutation "
                         "boundary (0 = off)")
    ap.add_argument("--chaos-shard-fault-rate", type=float, default=0.3)
    ap.add_argument("--chaos-spike-rate", type=float, default=0.15)
    ap.add_argument("--chaos-deadlines", default="2,5,10,25",
                    help="comma-separated deadline_ms sweep")
    ap.add_argument("--chaos-out", default=None,
                    help="output JSON path (default "
                         "artifacts/BENCH_resilience.json)")
    args = ap.parse_args()
    compile_cache.enable()

    t0 = time.time()
    c = clustered_corpus(n_docs=args.n_docs, dim=args.dim,
                         n_components=args.clusters,
                         n_queries=args.queries, seed=0)
    index = build_index(c.docs, args.clusters, list_pad=256, n_iters=6)
    print(f"index built: {index.n_clusters} clusters "
          f"({time.time() - t0:.1f}s)")

    # exact oracle over the index's own device docs: no second copy
    _, exact = exact_topk(index, c.queries, args.k)

    if args.chaos:
        from repro.runtime.chaos import ChaosConfig, run_chaos
        cfg = ChaosConfig(seed=args.chaos_seed,
                          crash_every=args.chaos_crash_every,
                          shard_fault_rate=args.chaos_shard_fault_rate,
                          spike_rate=args.chaos_spike_rate)
        deadlines = [float(x) for x in
                     args.chaos_deadlines.split(",") if x]
        with tempfile.TemporaryDirectory(prefix="chaos_") as workdir:
            payload = run_chaos(index, c.docs, c.queries, exact, cfg,
                                workdir, k=args.k,
                                n_probe=args.n_probe,
                                deadlines_ms=deadlines)
        out = args.chaos_out or os.path.join("artifacts",
                                             "BENCH_resilience.json")
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
        print(json.dumps({"recovery": payload["recovery"],
                          "shard_faults": payload["shard_faults"]},
                         indent=2))
        print(f"wrote {out}")
        return

    if args.policy == "fixed":
        pol = policies.fixed(args.n_probe, k=args.k)
        res = search(index, jnp.asarray(c.queries), pol)
        ids, probes = np.asarray(res.topk_ids), np.asarray(res.probes)
        print(metrics.summarize(ids, probes, exact, c.relevant))
        return

    ws = WaveScheduler(index, wave_size=args.wave_size, chunk=4,
                       k=args.k, n_probe=args.n_probe, delta=args.delta,
                       phi=args.phi, deadline_ms=args.deadline_ms)
    rep, ids, probes, wall = _serve(ws, c.queries,
                                    compact=not args.no_compact)
    summ = metrics.summarize(ids, probes, exact, c.relevant, wall)
    summ["occupancy"] = round(rep.occupancy, 3)
    summ["waves"] = rep.waves
    summ["empty_waves"] = rep.empty_waves
    summ["admit_calls"] = rep.admit_calls
    summ["host_pulls"] = rep.host_pulls
    if rep.waves:       # per stage: median and max ms over waves
        summ["stage_ms"] = {
            s: (round(float(np.median(c)), 3), round(float(c.max()), 3))
            for s, c in zip(STAGES, rep.stage_ms.T)}
    if args.deadline_ms is not None:
        summ["degraded_fraction"] = round(rep.degraded_fraction, 4)
        summ["wave_cost_ms"] = round(rep.wave_cost_ms, 3)
    print({k: round(v, 4) if isinstance(v, float) else v
           for k, v in summ.items()})

    if args.mutation_rate <= 0:
        return

    # --- mixed query/mutation stream over the live index ------------------
    rebuild_on = args.rebuild_every > 0 or args.rebuild_drift > 0
    rebuilder = tracker = rb_tmp = None
    if rebuild_on:
        # a durable rebuild needs a WAL (catch-up across stages) and a
        # snapshot root (two-phase publish); both are scratch here
        from repro.checkpoint.manager import CheckpointManager
        from repro.index import DriftTracker, MutationWAL, Rebuilder
        rb_tmp = tempfile.TemporaryDirectory(prefix="serve_rebuild_")
        wal = MutationWAL(os.path.join(rb_tmp.name, "mutations.wal"),
                          group_commit_n=8, group_commit_ms=50.0)
        live = LiveIndex(index, delta_cap=args.delta_cap, wal=wal)
        mgr = CheckpointManager(os.path.join(rb_tmp.name, "snapshots"),
                                async_save=False)
    else:
        live = LiveIndex(index, delta_cap=args.delta_cap)
        mgr = None
    reg = IndexRegistry(version_of(live))
    if rebuild_on:
        reg.save(mgr)
        live.wal.note_durable(live.seq)

        def on_publish(new_live, report):
            nonlocal live
            live = new_live          # rebind the mutation stream
            if tracker is not None:
                tracker.rebase(new_live._centroids)

        rebuilder = Rebuilder(live, reg, mgr, on_publish=on_publish)
        if args.rebuild_drift > 0:
            tracker = DriftTracker(live._centroids, c.docs,
                                   threshold=args.rebuild_drift)
    ws_live = WaveScheduler(index, wave_size=args.wave_size, chunk=4,
                            k=args.k, n_probe=args.n_probe,
                            delta=args.delta, phi=args.phi, registry=reg,
                            deadline_ms=args.deadline_ms,
                            rebuilder=rebuilder)
    rng = np.random.default_rng(1)
    added: list[int] = []
    stats = {"adds": 0, "deletes": 0, "merges": 0}

    def mutate(wave: int) -> None:
        # corpus-like churn: noisy copies of existing docs, so added
        # vectors score on the same scale as the static corpus
        src = rng.integers(0, args.n_docs, args.mutation_rate)
        new = (c.docs[src]
               + rng.normal(scale=0.05, size=(args.mutation_rate,
                                              args.dim))
               ).astype(np.float32)
        try:
            added.extend(int(i) for i in live.add(new))
            stats["adds"] += args.mutation_rate
            if tracker is not None:
                tracker.observe(new)
        except DeltaFull:
            live.merge_delta()
            stats["merges"] += 1
        n_del = args.mutation_rate // 4
        if n_del and len(added) > n_del:
            doomed = [added.pop(rng.integers(len(added)))
                      for _ in range(n_del)]
            live.delete(doomed)
            stats["deletes"] += n_del
        if args.merge_every and wave % args.merge_every == 0 \
                and len(live.delta):
            live.merge_delta()
            stats["merges"] += 1
        reg.publish(version_of(live))
        if rebuilder is not None and not rebuilder.active:
            if args.rebuild_every and wave % args.rebuild_every == 0:
                rebuilder.request(f"every-{args.rebuild_every}")
            elif tracker is not None and tracker.triggered:
                rebuilder.request(f"drift>{args.rebuild_drift}")

    rep_l, ids_l, probes_l, wall_l = _serve(
        ws_live, c.queries, compact=not args.no_compact, on_wave=mutate)
    r_static = metrics.r_star_at_k(ids, exact)
    r_live = metrics.r_star_at_k(ids_l, exact)
    row = {"mode": "live", "mutation_rate": args.mutation_rate,
           "merge_every": args.merge_every, **stats,
           "versions": live.version, "swaps": reg.swaps,
           "delta_occupancy": round(live.delta.occupancy(), 3),
           "recall_static": round(r_static, 4),
           "recall_live": round(r_live, 4),
           "recall_gap": round(abs(r_static - r_live), 4),
           "latency_ms": round(wall_l, 1),
           "mean_probes": round(float(probes_l.mean()), 2)}
    if rebuilder is not None:
        row.update({"rebuilds": rebuilder.epochs_published,
                    "epoch": live.epoch,
                    "epoch_swaps": rep_l.epoch_swaps,
                    "drain_waves": rep_l.drain_waves,
                    "rebuild_ticks": rep_l.rebuild_ticks,
                    "rebuild_throttled": rep_l.rebuild_throttled})
        if tracker is not None:
            row["drift_ratio"] = round(tracker.ratio, 3)
    print(row)
    if rb_tmp is not None:
        live.wal.close()
        rb_tmp.cleanup()


if __name__ == "__main__":
    main()
