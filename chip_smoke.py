#!/usr/bin/env python3
"""One-chip smoke run of the served early-exit path at deployment size.

    python3 chip_smoke.py [--seed 0] [--delta-cap 4096]

Needs a TPU: on any other backend it exits non-zero before it builds
anything.  In one process it

1. builds a 2,097,152 x 768 f32 IVF index (16,384 clusters, list_pad
   256) from ``data.synthetic.clustered_corpus`` and prints the
   device's bytes in use once the index is resident;
2. computes the exact top-100 over the index's own device docs;
3. serves 256 queries through ``WaveScheduler`` (fused Mosaic kernel,
   wave 64, the scheduler's chunk), printing compile and serve seconds,
   R*@100, and the share of queries whose ids or probe counts differ
   from the per-probe XLA reference (``search(use_fused_kernel=False)``);
4. checks that the compiled ``_advance`` program holds a
   ``tpu_custom_call``;
5. runs the live path on the same index: adds and deletes through a
   ``LiveIndex`` delta buffer, served through an ``IndexRegistry``
   (the in-kernel delta stream), then one ``merge_delta``, each held to
   the same agreement rule.

Phase lines go to stdout as JSON objects; the last line is
``{"ok": true, "device": {...}}``.  A failed phase raises and the
script exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import build_index, exact_topk, metrics, policies, search  # noqa: E402
from repro.core import serving  # noqa: E402
from repro.data.synthetic import clustered_corpus  # noqa: E402
from repro.index import IndexRegistry, LiveIndex, version_of  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Deployment of ``configs/msmarco_ivf.py`` at its published widths
    (d, k, N, patience, list_pad), with the corpus cut to 2^21 docs and
    the clusters to 2^14 so the f32 index fits one 16 GB chip."""
    n_docs: int = 1 << 21
    dim: int = 768
    n_clusters: int = 1 << 14
    n_components: int = 1024
    list_pad: int = 256
    k: int = 100
    n_probe: int = 80
    delta: int = 7
    phi: float = 95.0
    kmeans_iters: int = 6
    n_queries: int = 256
    wave_size: int = 64
    delta_cap: int = 4096
    n_adds: int = 384
    n_deletes: int = 192
    max_differing: float = 0.01
    seed: int = 0


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _bytes_in_use():
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_in_use") if stats else "not reported"


def _agreement(phase, ids, probes, ref, max_differing):
    """Share of queries whose top-k ids or probe counts differ from the
    per-probe reference; fails the phase at ``max_differing``."""
    ref_ids = np.asarray(ref.topk_ids)
    ref_probes = np.asarray(ref.probes)
    differ = (ids != ref_ids).any(axis=1) | (probes != ref_probes)
    share = float(differ.mean())
    _emit(phase=phase, check="agreement", queries=int(differ.size),
          differing=int(differ.sum()), share=share,
          ids_identical=bool((ids == ref_ids).all()),
          probes_identical=bool((probes == ref_probes).all()))
    if share >= max_differing:
        raise AssertionError(
            f"{phase}: {share:.4f} of queries differ from the per-probe "
            f"reference (limit {max_differing})")


def _serve(ws, queries):
    """Warm-up serve of one wave (compiles), then the timed serve."""
    t0 = time.perf_counter()
    ws.serve(queries[: ws.w])
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep = ws.serve(queries)
    n = queries.shape[0]
    ids = np.stack([rep.results[i] for i in range(n)])
    probes = np.asarray([rep.probes[i] for i in range(n)])
    # results are host arrays: the serve loop synced on every wave
    return ids, probes, rep, compile_s, time.perf_counter() - t0


def _reference(search_fn, queries):
    t0 = time.perf_counter()
    ref = search_fn(jnp.asarray(queries))
    jax.block_until_ready(ref.topk_ids)
    return ref, time.perf_counter() - t0


def _advance_has_kernel(ws, index, d) -> bool:
    """Is the Mosaic kernel inside the compiled ``_advance`` step?"""
    state = serving._empty_state(ws.w, d, ws.n, ws.k)
    lane = jnp.zeros((ws.w,), jnp.int32)
    text = serving._advance.lower(
        index, state, lane_delta=lane, lane_cap=lane, chunk=ws.chunk,
        k=ws.k, n_probe=ws.n, phi=ws.phi).compile().as_text()
    return "tpu_custom_call" in text


def _live_round(phase, live, queries, cfg, pol):
    """Serve through a registry over ``live`` and compare with the
    per-probe path with delta.  Readers are local, so the old version
    is unpinned once this returns."""
    reg = IndexRegistry(version_of(live))
    ws = serving.WaveScheduler(live.index, wave_size=cfg.wave_size,
                               k=cfg.k, n_probe=cfg.n_probe,
                               delta=cfg.delta, phi=cfg.phi, registry=reg)
    ids, probes, rep, compile_s, serve_s = _serve(ws, queries)
    ref, ref_s = _reference(
        lambda q: live.search(q, pol, use_fused_kernel=False), queries)
    _emit(phase=phase, delta_live=len(live.delta),
          tombstones=live.tombs.count, waves=rep.waves,
          compile_s=compile_s, serve_s=serve_s, reference_s=ref_s,
          mean_probes=float(probes.mean()))
    _agreement(phase, ids, probes, ref, cfg.max_differing)


def run(cfg: SmokeConfig, *, platform: str = "tpu") -> dict:
    """Every phase, in this process.  ``platform`` is the backend the
    run must find; on TPU the compiled step must hold the kernel."""
    dev = jax.devices()[0]
    if dev.platform != platform:
        raise SystemExit(f"chip_smoke: needs a {platform} device, JAX "
                         f"found {dev.platform} ({dev.device_kind})")
    _emit(phase="device", platform=dev.platform, kind=dev.device_kind,
          count=len(jax.devices()), config=dataclasses.asdict(cfg))

    t0 = time.perf_counter()
    # spread is scaled so that the per-doc noise norm matches the
    # generator's 128-d design point at any width
    corpus = clustered_corpus(
        n_docs=cfg.n_docs, dim=cfg.dim, n_components=cfg.n_components,
        n_queries=cfg.n_queries, spread=0.25 * np.sqrt(128 / cfg.dim),
        seed=cfg.seed)
    queries = corpus.queries
    _emit(phase="corpus", seconds=time.perf_counter() - t0,
          docs=list(corpus.docs.shape), queries=list(queries.shape))

    t0 = time.perf_counter()
    index = build_index(corpus.docs, cfg.n_clusters,
                        list_pad=cfg.list_pad, n_iters=cfg.kmeans_iters,
                        seed=cfg.seed)
    del corpus
    jax.block_until_ready(index.docs)
    _emit(phase="index", seconds=time.perf_counter() - t0,
          clusters=index.n_clusters, rows=int(index.docs.shape[0]),
          index_bytes=int(index.docs.nbytes + index.doc_ids.nbytes),
          device_bytes_in_use=_bytes_in_use())

    t0 = time.perf_counter()
    _, exact = exact_topk(index, queries, cfg.k)
    _emit(phase="exact_oracle", seconds=time.perf_counter() - t0)

    # -- static path ------------------------------------------------------
    pol = policies.patience(cfg.n_probe, cfg.delta, cfg.phi, k=cfg.k)
    ws = serving.WaveScheduler(index, wave_size=cfg.wave_size, k=cfg.k,
                               n_probe=cfg.n_probe, delta=cfg.delta,
                               phi=cfg.phi)
    ids, probes, rep, compile_s, serve_s = _serve(ws, queries)
    ref, ref_s = _reference(lambda q: search(index, q, pol), queries)
    has_kernel = _advance_has_kernel(ws, index, cfg.dim)
    _emit(phase="static", chunk=ws.chunk, waves=rep.waves,
          occupancy=rep.occupancy, compile_s=compile_s, serve_s=serve_s,
          reference_s=ref_s, mean_probes=float(probes.mean()),
          r_star_at_k=metrics.r_star_at_k(ids, exact),
          r_star_at_k_reference=metrics.r_star_at_k(
              np.asarray(ref.topk_ids), exact),
          tpu_custom_call=has_kernel)
    if platform == "tpu" and not has_kernel:
        raise AssertionError("compiled _advance holds no tpu_custom_call")
    _agreement("static", ids, probes, ref, cfg.max_differing)
    del ws, ref

    # -- live path: delta stream, tombstones, one merge -------------------
    live = LiveIndex(index, delta_cap=cfg.delta_cap)
    del index
    rng = np.random.default_rng(cfg.seed + 1)
    near = queries[rng.integers(0, queries.shape[0], cfg.n_adds)]
    adds = near + rng.normal(scale=0.02, size=near.shape)
    adds = (adds / np.linalg.norm(adds, axis=1, keepdims=True)
            ).astype(np.float32)
    added = live.add(adds)
    # tombstone half in the buffer, half among the queries' exact hits
    live.delete(rng.choice(added, cfg.n_deletes // 2, replace=False))
    hits = np.unique(exact[:, :4])
    live.delete(rng.choice(hits[hits >= 0], cfg.n_deletes // 2,
                           replace=False))
    _live_round("live", live, queries, cfg, pol)

    gc.collect()          # no reader may pin the pre-merge arrays
    t0 = time.perf_counter()
    live.merge_delta()
    jax.block_until_ready(live.index.docs)
    _emit(phase="merge", seconds=time.perf_counter() - t0,
          version=live.version, delta_left=len(live.delta),
          device_bytes_in_use=_bytes_in_use())
    _live_round("merged", live, queries, cfg, pol)
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--delta-cap", type=int, default=4096)
    args = ap.parse_args()
    compile_cache.enable()
    result = run(SmokeConfig(seed=args.seed, delta_cap=args.delta_cap))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
