"""IVF cluster-scan kernel: the paper's hot loop on TPU (DESIGN §2).

Each query streams its probed cluster's contiguous (list_pad, d) tile
from the cluster-major doc matrix straight into VMEM — the per-query row
offset rides in scalar-prefetch (pltpu.PrefetchScalarGridSpec), so the
DMA pipeline can prefetch the next tile while the MXU scores the current
one. Offsets must be aligned to ``blk_l`` rows (build_index(align=...)
guarantees this); masking by true list size happens in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(offs_ref, q_ref, docs_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # (1, d)
    tile = docs_ref[...].astype(jnp.float32)    # (blk_l, d)
    o_ref[...] = jax.lax.dot_general(
        q, tile, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (1, blk_l)


def ivf_scan(queries: jnp.ndarray, docs: jnp.ndarray,
             offsets: jnp.ndarray, *, list_pad: int, blk_l: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """queries (B,d) f32; docs (n,d) cluster-major; offsets (B,) int32
    (aligned to blk_l) -> raw scores (B, list_pad)."""
    b, d = queries.shape
    assert list_pad % blk_l == 0
    nblk = list_pad // blk_l
    # per-query rows ride a unit sublane dim behind a squeezed leading
    # one, which keeps every block inside the TPU's (8, 128) tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nblk),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, j, offs: (i, 0, 0)),
            pl.BlockSpec((blk_l, d),
                         lambda i, j, offs: (offs[i] // blk_l + j, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, blk_l),
                               lambda i, j, offs: (i, 0, j)),
    )
    block_offsets = offsets.astype(jnp.int32)
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, list_pad), jnp.float32),
        interpret=interpret,
    )(block_offsets, queries[:, None, :], docs)
    return out[:, 0]
