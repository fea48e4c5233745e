"""Kernel micro-benchmarks: correctness vs oracle + timing of both the
jnp/XLA ref path and the ``ops.*`` dispatch path.

CPU interpret-mode timings of the Pallas bodies are not meaningful
hardware numbers; what we measure here is (a) allclose vs the ref and
(b) wall time of each path on this backend — the ``*_ref_xla`` rows are
the CPU baseline the TPU kernels replace, the ``*_ops`` rows catch
dispatch-path regressions. Printed as name,us_per_call,max_err CSV.

``main`` returns the BENCH_kernels.json artifact: the legacy ``rows``
plus a ``fused_sweep`` (chunk × blk_l, with and without the in-kernel
delta stream), a ``sort`` section timing the packed (score,id) network
against the legacy three-lane tagged network, and backend metadata.
The fused kernel runs one body on both backends (grid-streamed tiles),
so every row is measured on whatever backend runs the bench.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref, sort


def _time(fn, *args, reps: int = 5) -> float:
    jax.block_until_ready(fn(*args))        # single warmup / compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _err(a, b) -> float:
    """max abs err with -inf/-inf treated as equal."""
    return float(jnp.max(jnp.abs(jnp.nan_to_num(
        jnp.asarray(a) - jnp.asarray(b), neginf=0.0, posinf=0.0))))


def _bitonic_desc_tagged_legacy(s, i, t):
    """The fused kernel's pre-packed three-lane sort (score f32, id
    i32, tag i32 — three shuffles + three selects per pass), kept here
    ONLY as the packed-vs-tagged benchmark baseline."""
    r, m = s.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    stages = int(np.log2(m))

    def partner(x, jj):
        x3 = x.reshape(r, m // (2 * jj), 2, jj)
        return jnp.flip(x3, axis=2).reshape(r, m)

    for stage in range(1, stages + 1):
        kk = 1 << stage
        for jj in (1 << p for p in range(stage - 1, -1, -1)):
            keep_max = jnp.where((idx & kk) == 0,
                                 (idx & jj) == 0,
                                 (idx & jj) != 0)
            ps, pi, pt = partner(s, jj), partner(i, jj), partner(t, jj)
            take_p = jnp.where(keep_max, ps > s, ps < s)
            s = jnp.where(take_p, ps, s)
            i = jnp.where(take_p, pi, i)
            t = jnp.where(take_p, pt, t)
    return s, i, t


def _sort_section(reps: int, smoke: bool) -> Dict:
    """Packed (2-word record) vs legacy tagged (3-lane) network."""
    rng = np.random.default_rng(17)
    r, m = (64, 512) if not smoke else (16, 512)
    sc = jnp.asarray(rng.normal(size=(r, m)).astype(np.float32))
    ids = jnp.asarray(rng.integers(0, 1 << 29, (r, m)).astype(np.int32))
    tags = jnp.zeros((r, m), jnp.int32)

    packed = jax.jit(lambda s, i: sort.bitonic_desc_packed(
        sort.pack(sort.score_to_key(s), i)))
    tagged = jax.jit(_bitonic_desc_tagged_legacy)
    out_p = packed(sc, ids)
    out_t = tagged(sc, ids, tags)
    np.testing.assert_array_equal(
        np.asarray(sort.key_to_score(out_p[:, 0])), np.asarray(out_t[0]))
    packed_us = _time(packed, sc, ids, reps=reps)
    tagged_us = _time(tagged, sc, ids, tags, reps=reps)
    return {"rows": r, "m": m, "packed_us": packed_us,
            "tagged_us": tagged_us,
            "speedup": tagged_us / max(packed_us, 1e-9)}


def main(smoke: bool = False) -> Dict:
    rows = []
    reps = 2 if smoke else 5

    def add(name, us, err):
        rows.append({"name": name, "us": us, "err": err})

    r = jax.random
    # flash attention
    q = r.normal(r.PRNGKey(0), (4, 512, 64))
    k = r.normal(r.PRNGKey(1), (4, 512, 64))
    v = r.normal(r.PRNGKey(2), (4, 512, 64))
    jref = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
    fa_ops = ops.flash_attention(q, k, v, blk_q=128, blk_k=128)
    err = _err(fa_ops, jref(q, k, v))
    add("flash_attention_ref_xla", _time(jref, q, k, v, reps=reps), err)
    add("flash_attention_ops",
        _time(lambda: ops.flash_attention(q, k, v, blk_q=128, blk_k=128), reps=reps),
        err)

    # ivf scan
    docs = r.normal(r.PRNGKey(3), (65536, 64))
    qs = r.normal(r.PRNGKey(4), (64, 64))
    offs = jnp.arange(64, dtype=jnp.int32) * 256
    szs = jnp.full((64,), 250, jnp.int32)
    jscan = jax.jit(lambda a, b, c, d: ref.ivf_scan_ref(a, b, c, d, 256))
    err = _err(ops.ivf_scan(qs, docs, offs, szs, list_pad=256),
               jscan(qs, docs, offs, szs))
    add("ivf_scan_ref_xla", _time(jscan, qs, docs, offs, szs, reps=reps), err)
    add("ivf_scan_ops",
        _time(lambda: ops.ivf_scan(qs, docs, offs, szs, list_pad=256), reps=reps), err)

    # topk merge
    s = r.normal(r.PRNGKey(5), (256, 50))
    i = r.randint(r.PRNGKey(6), (256, 50), 0, 10 ** 6)
    ns = r.normal(r.PRNGKey(7), (256, 256))
    ni = r.randint(r.PRNGKey(8), (256, 256), 0, 10 ** 6)
    jmerge = jax.jit(lambda a, b, c, d: ref.topk_merge_ref(a, b, c, d, 50))
    err = _err(ops.topk_merge(s, i, ns, ni, 50)[0],
               jmerge(s, i, ns, ni)[0])
    add("topk_merge_ref_xla", _time(jmerge, s, i, ns, ni, reps=reps), err)
    add("topk_merge_ops",
        _time(lambda: ops.topk_merge(s, i, ns, ni, 50), reps=reps), err)

    # fused multi-probe scan -> merge: chunk sweep (total probes per
    # query fixed at 8, so rows compare dispatch granularity — how many
    # probes amortise one kernel launch — not total work)
    B, n_pr, lp, kk = 16, 8, 256, 50
    fdocs = r.normal(r.PRNGKey(11), (B * n_pr * lp, 64))
    fids = jnp.arange(B * n_pr * lp, dtype=jnp.int32)
    all_offs = (jnp.arange(B * n_pr, dtype=jnp.int32) * lp).reshape(B, n_pr)
    fq = r.normal(r.PRNGKey(12), (B, 64))
    rs = jnp.full((B, kk), -jnp.inf, jnp.float32)
    ri = jnp.full((B, kk), -1, jnp.int32)
    chunk4 = all_offs[:, :4]
    fszs4 = jnp.full((B, 4), lp - 6, jnp.int32)
    jfused = jax.jit(lambda: ref.ivf_scan_merge_ref(
        fq, fdocs, fids, chunk4, fszs4, rs, ri, kk, lp))
    o_ops = ops.ivf_scan_merge(fq, fdocs, fids, chunk4, fszs4, rs, ri,
                               k=kk, list_pad=lp, chunk=4)
    o_ref = jfused()
    err = max(_err(o_ops[0], o_ref[0]),
              float(jnp.max(jnp.abs(o_ops[2] - o_ref[2]))))
    add("ivf_scan_merge_ref_xla", _time(jfused, reps=reps), err)

    def sweep_chunk(chunk: int, blk_l: int = 128) -> float:
        """us for the full n_pr probes issued as n_pr/chunk dispatches."""
        offs = all_offs.reshape(B, n_pr // chunk, chunk)
        szs = jnp.full((B, chunk), lp - 6, jnp.int32)

        def run():
            s, i = rs, ri
            for j in range(n_pr // chunk):
                snap_s, snap_i, _ = ops.ivf_scan_merge(
                    fq, fdocs, fids, offs[:, j], szs, s, i,
                    k=kk, list_pad=lp, chunk=chunk, blk_l=blk_l)
                s, i = snap_s[:, -1], snap_i[:, -1]
            return s, i

        return _time(run, reps=reps)

    for chunk in ([4] if smoke else [1, 2, 4, 8]):
        add(f"ivf_scan_merge_ops_c{chunk}", sweep_chunk(chunk), err)

    # chunk × blk_l sweep: dispatch granularity vs tile height (tiles
    # are written at blk_l-multiple lane offsets, so blk_l >= 128 on TPU)
    fused_sweep = []
    for chunk in ([4] if smoke else [2, 4, 8]):
        for blk_l in ([128] if smoke else [128, 256]):
            fused_sweep.append({
                "chunk": chunk, "blk_l": blk_l, "delta": False,
                "us": sweep_chunk(chunk, blk_l), "err": err})

    # in-kernel delta stream: same probes plus a 256-entry buffer
    # (second prefetch stream + per-slot gated merge, one dispatch)
    dcap = 256
    dl_vecs = r.normal(r.PRNGKey(14), (dcap, 64))
    dl_ids = jnp.arange(dcap, dtype=jnp.int32) + 10 ** 7
    dl_assign = jnp.zeros((dcap,), jnp.int32)     # never probed here
    szs4 = jnp.full((B, 4), lp - 6, jnp.int32)
    gates = jnp.full((B, 4), -2, jnp.int32)

    def run_delta():
        return ops.ivf_scan_merge(
            fq, fdocs, fids, all_offs[:, :4], szs4, rs, ri,
            dl_vecs, dl_ids, dl_assign, gates,
            k=kk, list_pad=lp, chunk=4)

    fused_sweep.append({
        "chunk": 4, "blk_l": 128, "delta": True,
        "us": _time(run_delta, reps=reps), "err": err})
    for row in fused_sweep:
        tag = "_delta" if row["delta"] else ""
        add(f"fused_c{row['chunk']}_blk{row['blk_l']}{tag}",
            row["us"], row["err"])

    # delta scan (live-mutation buffer brute force)
    dvecs = r.normal(r.PRNGKey(13), (1024, 64))
    dref = jax.jit(ref.delta_scan_ref)
    err = _err(ops.delta_scan(fq, dvecs), dref(fq, dvecs))
    add("delta_scan_ref_xla", _time(dref, fq, dvecs, reps=reps), err)
    add("delta_scan_ops",
        _time(lambda: ops.delta_scan(fq, dvecs), reps=reps), err)

    # embedding bag
    table = r.normal(r.PRNGKey(9), (100_000, 16))
    ids = r.randint(r.PRNGKey(10), (1024, 26), 0, 100_000)
    jbag = jax.jit(ref.embedding_bag_ref)
    err = _err(ops.embedding_bag(table, ids), jbag(table, ids))
    add("embedding_bag_ref_xla", _time(jbag, table, ids, reps=reps), err)
    # embedding_bag's interpret-mode gather costs ~30s/call on CPU;
    # the single err check above already exercises the ops path

    for row in rows:
        print(f"{row['name']},{row['us']:.1f},{row['err']:.2e}")
    return {
        "rows": rows,
        "fused_sweep": fused_sweep,
        "sort": _sort_section(reps, smoke),
        "backend": jax.default_backend(),
    }


if __name__ == "__main__":
    main()
