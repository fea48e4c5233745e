"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) every kernel runs in interpret mode — the
kernel body executes eagerly in Python for correctness validation
against ref.py. On a TPU backend the same call sites compile the real
Mosaic kernels.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import delta_scan as _ds
from repro.kernels import embedding_bag as _eb
from repro.kernels import flash_attention as _fa
from repro.kernels import ivf_scan as _scan
from repro.kernels import ivf_scan_merge as _sm
from repro.kernels import topk_merge as _tm


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k"))
def flash_attention(q, k, v, *, causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128):
    return _fa.flash_attention(q, k, v, causal=causal, blk_q=blk_q,
                               blk_k=blk_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("list_pad", "blk_l"))
def ivf_scan(queries, docs, offsets, sizes, *, list_pad: int,
             blk_l: int = 128):
    """Fused cluster-tile scoring; -inf outside each true list size."""
    raw = _scan.ivf_scan(queries, docs, offsets, list_pad=list_pad,
                         blk_l=blk_l, interpret=_interpret())
    mask = jnp.arange(list_pad)[None, :] < sizes[:, None]
    return jnp.where(mask, raw, -jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("k", "list_pad", "chunk", "blk_l",
                              "blk_dl"))
def ivf_scan_merge(queries, docs, doc_ids, offsets, sizes, run_scores,
                   run_ids, delta_vecs=None, delta_ids=None,
                   delta_assign=None, gate_cids=None, *, k: int,
                   list_pad: int, chunk: int, blk_l: int = 128,
                   blk_dl: int = 128
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused multi-probe scan -> running top-k merge (one dispatch per
    ``chunk`` probes; see ivf_scan_merge.py for the memory model).

    offsets/sizes: (B, chunk) row offsets (blk_l aligned) and true list
    sizes per probed cluster; run_scores/run_ids: (B, k) incoming
    running top-k.  Returns ((B, chunk, k) snapshot scores with -inf
    empty slots, (B, chunk, k) snapshot ids, (B, chunk) new-entry
    counts with phi = 100 * (k - count) / k).

    Live-mutation overlay (all four together or none): delta_vecs
    (cap, d) / delta_ids / delta_assign (cap,) — the delta buffer, id
    -1 on empty or tombstoned slots — and gate_cids (B, chunk), the
    probed cluster id of each slot or -2 for slots past the probe
    budget.  The buffer is scored in-kernel as a second prefetch
    stream and each entry merges at its assigned cluster's probe slot,
    so the counts (and phi) stay exact — one Pallas dispatch per
    chunk, no host-side re-merge.
    """
    n = doc_ids.shape[0]
    tail = (-n) % blk_l
    ids3d = jnp.pad(doc_ids, (0, tail),
                    constant_values=-1).reshape(-1, 1, blk_l)
    kw = {}
    if delta_vecs is not None:
        cap = delta_vecs.shape[0]
        blk_dl = min(blk_dl, 1 << int(np.ceil(np.log2(max(cap, 1)))))
        dtail = (-cap) % blk_dl
        kw = dict(
            delta_vecs=jnp.pad(delta_vecs, ((0, dtail), (0, 0))),
            delta_ids=jnp.pad(delta_ids, (0, dtail),
                              constant_values=-1),
            delta_assign=jnp.pad(delta_assign, (0, dtail),
                                 constant_values=-2),
            gate_cids=gate_cids.reshape(-1), blk_dl=blk_dl)
    out_s, out_i, cnt = _sm.ivf_scan_merge(
        queries, docs, ids3d,
        (offsets // blk_l).reshape(-1), sizes.reshape(-1),
        run_scores, run_ids, k=k, list_pad=list_pad, chunk=chunk,
        blk_l=blk_l, interpret=_interpret(), **kw)
    # sentinel -> -inf so empty slots match the XLA merge convention
    out_s = jnp.where(out_s > _sm.VALID_MIN, out_s, -jnp.inf)
    return out_s, out_i, cnt


@functools.partial(jax.jit, static_argnames=("blk_b", "blk_c"))
def delta_scan(queries, vecs, *, blk_b: int = 8, blk_c: int = 128):
    """Brute-force scan of the live-mutation delta buffer: (B,d) x
    (cap,d) -> (B,cap) raw scores (callers mask empty/tombstoned slots
    by ``ids >= 0``)."""
    return _ds.delta_scan(queries, vecs, blk_b=blk_b, blk_c=blk_c,
                          interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("k", "blk_b"))
def topk_merge(scores, ids, new_scores, new_ids, k: int, *,
               blk_b: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return _tm.topk_merge(scores, ids, new_scores, new_ids, k,
                          blk_b=blk_b, interpret=_interpret())


@jax.jit
def embedding_bag(table, ids):
    return _eb.embedding_bag(table, ids, interpret=_interpret())
