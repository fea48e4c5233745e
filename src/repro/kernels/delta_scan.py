"""Brute-force delta-buffer scan kernel (live-mutation subsystem).

The delta buffer holds at most a few thousand recently-added vectors,
so scanning it is one small ``(B, d) x (d, cap)`` matmul.  It still
goes through Pallas so the TPU serving path keeps a single dispatch
discipline: queries and delta tiles stream HBM -> VMEM block by block
and the MXU scores a ``(blk_b, blk_c)`` output tile per grid step.

The kernel returns *raw* scores for every slot (including empty or
tombstoned ones); callers mask by ``DeltaView.ids >= 0`` and by the
per-probe cluster-assignment gate (see ``repro.index``), which is what
keeps live-search results bit-identical to a rebuilt index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, v_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # (blk_b, d)
    v = v_ref[...].astype(jnp.float32)          # (blk_c, d)
    o_ref[...] = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (blk_b, blk_c)


def delta_scan(queries: jnp.ndarray, vecs: jnp.ndarray, *,
               blk_b: int = 8, blk_c: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """queries (B, d) x delta vecs (cap, d) -> (B, cap) f32 scores."""
    b, d = queries.shape
    cap = vecs.shape[0]
    blk_b = min(blk_b, b)
    blk_c = min(blk_c, cap)
    bp = -(-b // blk_b) * blk_b
    cp = -(-cap // blk_c) * blk_c
    if bp != b:
        queries = jnp.pad(queries, ((0, bp - b), (0, 0)))
    if cp != cap:
        vecs = jnp.pad(vecs, ((0, cp - cap), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=(bp // blk_b, cp // blk_c),
        in_specs=[
            pl.BlockSpec((blk_b, d), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((blk_b, blk_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, cp), jnp.float32),
        interpret=interpret,
    )(queries, vecs)
    return out[:b, :cap]
