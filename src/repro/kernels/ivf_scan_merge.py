"""Fused multi-probe IVF scan -> top-k merge kernel (DESIGN §2).

Memory / dispatch model
-----------------------
The unfused hot loop costs two ``pallas_call`` dispatches per probe and
round-trips the raw ``(B, list_pad)`` score tile through HBM between
the scan (``ivf_scan.py``) and the merge (``topk_merge.py``).  This
kernel fuses the paper's whole inner loop — probe -> score -> merge —
over a *chunk* of probes in a single launch, and (optionally) folds the
live-mutation delta-buffer scan in as a second stream:

* grid ``(B, chunk, list_pad // blk_l)``: for each query ``i`` the
  kernel walks its ``chunk`` probed clusters, one ``(blk_l, d)`` tile
  per step.  The docs / ids BlockSpecs index the tile through the
  scalar-prefetched ``blk_l``-aligned list offset of slot ``(i, j)``
  (``build_index(align=...)`` guarantees alignment), so Pallas fetches
  tile ``t+1`` while the MXU scores tile ``t`` — the same kernel body
  on the TPU and in interpret mode.
* raw scores NEVER touch HBM: each ``(1, blk_l)`` row lands in a VMEM
  strip at lane offset ``t * blk_l`` (a 128-multiple, which Mosaic
  needs for the dynamic store); once a probe's ``list_pad`` strip is
  complete it is masked by the true list size and merged into the
  packed running top-k via the shared bitonic network (``kernels/sort.py``): score
  keys in one int32 word, the doc id in the other, so every
  compare-exchange moves one stacked record instead of three lanes.
* the per-probe *new-entry count* — and therefore the patience signal
  ``phi = 100 * (k - new_entries) / k`` — falls out of the merge for
  free: entering candidates carry ``sort.NEW_MARK`` in their id word,
  survivors still marked after the sort are this probe's new entries.
  Marks are stripped before the snapshot is written.
* **delta stream** (live mutation, ``repro.index``): the fixed-capacity
  buffer of freshly added vectors is scored ONCE per query (at the
  chunk's first step) from HBM through a two-slot DMA buffer into a
  VMEM strip, then each entry is merged exactly at the probe slot of its
  *assigned* cluster (scalar-prefetched ``gate_cids``; slots past the
  probe budget gate on ``-2`` so they can never match an empty slot's
  ``assign == -1``).  Because the running top-k already carries every
  earlier merge, gating each entry once at its own probe reproduces the
  sequential per-probe reference bit-for-bit — no host-side re-merge.

Outputs per launch: per-probe top-k snapshots ``(B, chunk, k)`` scores
and doc ids (so the caller can evaluate the exit policy at per-probe
granularity and roll a query back to its exact exit probe) plus the
``(B, chunk)`` int32 new-entry counts.  HBM write traffic per probe is
``k`` lanes instead of ``list_pad`` — and the merge reads come from
VMEM instead of HBM.

Scores use the ``-1e30`` sentinel in place of ``-inf`` inside the sort
network; ``ops.ivf_scan_merge`` maps sentinels back to ``-inf`` on the
way out so callers see the same empty-slot convention as the XLA path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import sort

NEG = -1e30          # finite stand-in for -inf inside the sort network
VALID_MIN = -1e29    # scores above this are real candidates
KEY_NEG = sort.key_of(NEG)
KEY_VALID = sort.key_of(VALID_MIN)


def _dot(q, tile):
    """(1, d) x (rows, d)^T -> (1, rows) at full f32 precision."""
    return jax.lax.dot_general(
        q, tile.astype(jnp.float32), (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _score_delta(dvec_hbm, dbuf, dsem, dsc, q, *, nblk_d: int,
                 blk_dl: int) -> None:
    """Second stream: score the whole delta buffer into the (1, cap_pad)
    VMEM strip ``dsc`` (once per query, at the chunk's first step).

    The buffer stays in HBM; ``(blk_dl, d)`` tiles are copied into a
    two-slot VMEM buffer so tile ``t + 1`` is in flight while tile ``t``
    is scored."""
    def copy(t, slot):
        return pltpu.make_async_copy(
            dvec_hbm.at[pl.ds(pl.multiple_of(t * blk_dl, blk_dl), blk_dl)],
            dbuf.at[slot], dsem.at[slot])

    copy(0, 0).start()

    def body(t, carry):
        slot = t % 2

        @pl.when(t + 1 < nblk_d)
        def _prefetch():
            copy(t + 1, 1 - slot).start()

        copy(t, slot).wait()
        lane0 = pl.multiple_of(t * blk_dl, blk_dl)
        dsc[:, pl.ds(lane0, blk_dl)] = _dot(q, dbuf[slot])
        return carry

    jax.lax.fori_loop(0, nblk_d, body, 0)


def _kernel(*refs, k: int, chunk: int, blk_l: int, nblk: int,
            m_pad: int, has_delta: bool, blk_dl: int, nblk_d: int,
            m2_pad: int):
    if has_delta:
        (boffs_ref, sizes_ref, gates_ref, q_ref, docs_ref, ids_ref,
         ins_ref, ini_ref, dvec_hbm, did_ref, das_ref, outs_ref,
         outi_ref, cnt_ref, sbuf, ibuf, run_p, dsc, dbuf, dsem) = refs
    else:
        (boffs_ref, sizes_ref, q_ref, docs_ref, ids_ref, ins_ref,
         ini_ref, outs_ref, outi_ref, cnt_ref, sbuf, ibuf, run_p) = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32)          # (1, d)

    # a query's first step: load its incoming running top-k into the
    # packed scratch, and score the delta buffer once
    @pl.when((j == 0) & (t == 0))
    def _load_running():
        s0 = jnp.maximum(ins_ref[...], NEG)     # clamp -inf empty slots
        run_p[0:1] = sort.score_to_key(s0)
        run_p[1:2] = ini_ref[...]
        if has_delta:
            _score_delta(dvec_hbm, dbuf, dsem, dsc, q, nblk_d=nblk_d,
                         blk_dl=blk_dl)

    # score this step's (blk_l, d) tile of the probed list into the
    # VMEM strip; the grid pipeline has already fetched the next one
    lane0 = pl.multiple_of(t * blk_l, blk_l)
    sbuf[:, pl.ds(lane0, blk_l)] = _dot(q, docs_ref[...])
    ibuf[:, pl.ds(lane0, blk_l)] = ids_ref[...]

    @pl.when(t == nblk - 1)
    def _merge():
        # merge A: the probe's list, masked by true size, NEW-marked
        size = sizes_ref[i * chunk + j]
        new_s = sbuf[...]
        new_i = ibuf[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, new_i.shape, 1)
        # tombstones: deleted rows keep their vector but their stored id
        # is burned to -1 (repro.index.live), so masking id < 0 hides
        # both padding and deleted docs without an extra input stream
        alive = (lane < size) & (new_i >= 0)
        cand = jnp.concatenate(
            [jnp.where(alive, sort.score_to_key(new_s), KEY_NEG),
             jnp.where(alive, new_i | sort.NEW_MARK, -1)], axis=0)
        run_p[...] = sort.merge_packed(run_p[...], cand, m_pad,
                                       pad_key=KEY_NEG)[:, :k]

        if has_delta:
            # merge B: delta entries whose assigned cluster is THIS
            # probe.  Each entry is offered exactly once (its own slot);
            # the running top-k already holds every earlier merge, so
            # this reproduces the sequential per-probe reference.
            gate_cid = gates_ref[i * chunk + j]
            dio = did_ref[...]                   # (1, cap_pad)
            gate = (das_ref[...] == gate_cid) & (dio >= 0)

            @pl.when(jnp.max(gate.astype(jnp.int32)) > 0)
            def _merge_delta():
                dcand = jnp.concatenate(
                    [jnp.where(gate, sort.score_to_key(dsc[...]), KEY_NEG),
                     jnp.where(gate, dio | sort.NEW_MARK, -1)], axis=0)
                run_p[...] = sort.merge_packed(run_p[...], dcand, m2_pad,
                                               pad_key=KEY_NEG)[:, :k]

        # lanes still NEW-marked survived this probe's merge(s):
        # phi = 100 * kept / k = 100 * (k - new_entries) / k
        keys = run_p[0:1, :]
        idw = run_p[1:2, :]
        kept = jnp.sum(((keys > KEY_VALID) & ~sort.is_marked(idw))
                       .astype(jnp.int32), axis=1, keepdims=True)
        cnt_ref[...] = k - kept
        clean = sort.strip_marks(idw)
        run_p[1:2] = clean
        outs_ref[...] = sort.key_to_score(keys)
        outi_ref[...] = clean


def ivf_scan_merge(queries: jnp.ndarray, docs: jnp.ndarray,
                   ids3d: jnp.ndarray, block_offsets: jnp.ndarray,
                   sizes: jnp.ndarray, run_scores: jnp.ndarray,
                   run_ids: jnp.ndarray, *, k: int, list_pad: int,
                   chunk: int, blk_l: int = 128,
                   delta_vecs: Optional[jnp.ndarray] = None,
                   delta_ids: Optional[jnp.ndarray] = None,
                   delta_assign: Optional[jnp.ndarray] = None,
                   gate_cids: Optional[jnp.ndarray] = None,
                   blk_dl: int = 128, interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """queries (B,d); docs (n,d) cluster-major; ids3d (n//blk_l, 1,
    blk_l) doc ids, row-blocked; block_offsets/sizes (B*chunk,) int32
    (offsets in blk_l units); run_scores/run_ids (B,k) incoming top-k.

    Optional delta stream: delta_vecs (cap_pad, d) with cap_pad a
    ``blk_dl`` multiple, delta_ids/delta_assign (1, cap_pad) int32
    (id -1 = empty slot, assign -2 on padding), gate_cids (B*chunk,)
    int32 — the probed cluster of each slot, or -2 for slots past the
    probe budget.

    Returns per-probe snapshots (B, chunk, k) scores (NEG sentinel for
    empty slots) / ids, and (B, chunk) int32 new-entry counts.
    """
    b, d = queries.shape
    assert list_pad % blk_l == 0, "list_pad must be a blk_l multiple"
    has_delta = delta_vecs is not None
    nblk = list_pad // blk_l
    m_pad = 1 << int(np.ceil(np.log2(k + list_pad)))
    if has_delta:
        cap_pad = delta_vecs.shape[0]
        assert cap_pad % blk_dl == 0, "delta cap must be blk_dl-padded"
        nblk_d = cap_pad // blk_dl
        m2_pad = 1 << int(np.ceil(np.log2(k + cap_pad)))
    else:
        cap_pad, nblk_d, m2_pad = 0, 0, 0

    # Every per-query array carries a unit sublane dim behind a
    # squeezed leading one: a (1, w) block over (B, w) would break the
    # TPU's (8, 128) tiling rule, a (None, 1, w) block over (B, 1, w)
    # does not.
    def at_query(i, j, t, *_):
        return (i, 0, 0)

    def tile_of(i, j, t, boffs, *_):
        return (boffs[i * chunk + j] + t, 0)

    def ids_of(i, j, t, boffs, *_):
        return (boffs[i * chunk + j] + t, 0, 0)

    in_specs = [
        pl.BlockSpec((None, 1, d), at_query),          # queries
        pl.BlockSpec((blk_l, d), tile_of),             # docs
        pl.BlockSpec((None, 1, blk_l), ids_of),        # ids
        pl.BlockSpec((None, 1, k), at_query),          # run_scores
        pl.BlockSpec((None, 1, k), at_query),          # run_ids
    ]
    inputs = [queries[:, None, :], docs, ids3d, run_scores[:, None, :],
              run_ids[:, None, :]]
    scratch = [
        pltpu.VMEM((1, list_pad), jnp.float32),   # probe score strip
        pltpu.VMEM((1, list_pad), jnp.int32),     # probe id strip
        pltpu.VMEM((2, k), jnp.int32),            # packed running top-k
    ]
    if has_delta:
        whole = lambda *_: (0, 0)
        in_specs += [
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # vecs
            pl.BlockSpec((1, cap_pad), whole),
            pl.BlockSpec((1, cap_pad), whole),
        ]
        inputs += [delta_vecs, delta_ids.reshape(1, cap_pad),
                   delta_assign.reshape(1, cap_pad)]
        scratch += [
            pltpu.VMEM((1, cap_pad), jnp.float32),     # delta scores
            pltpu.VMEM((2, blk_dl, d), delta_vecs.dtype),  # tile slots
            pltpu.SemaphoreType.DMA((2,)),
        ]

    def per_slot(i, j, t, *_):
        return (i, j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if has_delta else 2,
        grid=(b, chunk, nblk),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, None, 1, k), per_slot),
                   pl.BlockSpec((None, None, 1, k), per_slot),
                   pl.BlockSpec((None, None, 1, 1), per_slot)],
        scratch_shapes=scratch,
    )
    kern = functools.partial(
        _kernel, k=k, chunk=chunk, blk_l=blk_l, nblk=nblk, m_pad=m_pad,
        has_delta=has_delta, blk_dl=blk_dl, nblk_d=nblk_d, m2_pad=m2_pad)
    prefetch = [block_offsets.astype(jnp.int32), sizes.astype(jnp.int32)]
    if has_delta:
        prefetch.append(gate_cids.astype(jnp.int32))
    out_s, out_i, cnt = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, chunk, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, chunk, 1, k), jnp.int32),
                   jax.ShapeDtypeStruct((b, chunk, 1, 1), jnp.int32)],
        interpret=interpret,
        name="ivf_scan_merge",      # the op name a device trace shows
    )(*prefetch, *inputs)
    return out_s[:, :, 0], out_i[:, :, 0], cnt[:, :, 0, 0]
