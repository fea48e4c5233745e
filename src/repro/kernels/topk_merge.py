"""Top-k merge kernel: packed bitonic network over (running-k ++ new-L).

The A-kNN inner loop merges each query's running top-k with list_pad
fresh scores every probe.  The network is the shared packed sort
(``kernels/sort.py``): scores are monotone-mapped into int32 keys and
ride stacked with their doc ids through a static XOR-partner
compare-exchange network — one shuffle + one select per pass for the
whole (score, id) record, no data-dependent control flow.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import sort

NEG_INF = -jnp.inf
_KEY_NEG = sort.key_of(-1e30)


def _kernel(s_ref, i_ref, ns_ref, ni_ref, os_ref, oi_ref, *, k: int,
            m_pad: int):
    # every ref is (blk_b, 1, width): the unit sublane row keeps the
    # (key, id) pack a sublane concat, with no in-kernel shape cast
    s = jnp.concatenate([s_ref[...], ns_ref[...]], axis=-1)
    i = jnp.concatenate([i_ref[...], ni_ref[...]], axis=-1)
    # NaN/±inf clamp BEFORE the key map: every non-finite score becomes
    # the -1e30 sentinel, so NaNs cannot leak above +inf in key space
    s = jnp.where(jnp.isfinite(s), s, -1e30)
    cand = jnp.concatenate([sort.score_to_key(s), i], axis=-2)
    out = sort.bitonic_desc_packed(
        sort.pad_lanes(cand, m_pad, pad_key=_KEY_NEG))
    os_ref[...] = sort.key_to_score(out[:, 0:1, :k])
    oi_ref[...] = out[:, 1:2, :k]


def topk_merge(scores: jnp.ndarray, ids: jnp.ndarray,
               new_scores: jnp.ndarray, new_ids: jnp.ndarray, k: int,
               *, blk_b: int = 8, interpret: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b = scores.shape[0]
    total = scores.shape[1] + new_scores.shape[1]
    m_pad = 1 << int(np.ceil(np.log2(total)))
    blk_b = min(blk_b, b)
    bp = -(-b // blk_b) * blk_b
    kern = functools.partial(_kernel, k=k, m_pad=m_pad)

    def rows(x, fill):
        x = jnp.pad(x, ((0, bp - b), (0, 0)), constant_values=fill)
        return x[:, None, :]

    spec = lambda w: pl.BlockSpec((blk_b, 1, w), lambda bi: (bi, 0, 0))
    out_s, out_i = pl.pallas_call(
        kern, grid=(bp // blk_b,),
        in_specs=[spec(scores.shape[1]), spec(ids.shape[1]),
                  spec(new_scores.shape[1]), spec(new_ids.shape[1])],
        out_specs=[spec(k), spec(k)],
        out_shape=[jax.ShapeDtypeStruct((bp, 1, k), jnp.float32),
                   jax.ShapeDtypeStruct((bp, 1, k), ids.dtype)],
        interpret=interpret,
    )(rows(scores, -jnp.inf), rows(ids, -1), rows(new_scores, -jnp.inf),
      rows(new_ids, -1))
    out_s, out_i = out_s[:b, 0], out_i[:b, 0]
    # the kernel clamps -inf to -1e30 for the sort network; map the
    # sentinel back so empty slots match the XLA merge (-inf) exactly
    out_s = jnp.where(out_s > -1e29, out_s, NEG_INF)
    return out_s.astype(scores.dtype), out_i
