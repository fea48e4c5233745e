"""Packed (score, id) bitonic sort — single source of truth for every
top-k merge network in the kernels layer.

Scores are monotone-mapped into int32 *keys* (``score_to_key``): the
IEEE-754 bit pattern of a float, with the magnitude bits flipped for
negatives, compares in the same order as the float itself under signed
integer comparison.  The map is an exact involution, so scores
round-trip bit-for-bit (``key_to_score``) — including negatives,
denormals and ±inf.  NaNs map above +inf; callers that may see NaN
clamp it first (``topk_merge`` maps every non-finite score to the
``-1e30`` sentinel).

The sort then runs on a single stacked ``(..., 2, M)`` int32 array —
key word in sublane 0, id word in sublane 1 — instead of separate f32
score / i32 id / i32 tag lanes: each compare-exchange pass moves the
whole record with two lane rotations and one select (plus one
lexicographic compare), where the tagged three-lane network paid three
shuffles and three selects.  On a TPU the two words share one vreg per
128 lanes, so a pass costs what a one-word network would.

Ties: descending lexicographic on (key, id-word), so equal scores are
broken by the *higher* id word deterministically.  The per-probe
reference (``jax.lax.top_k``) breaks exact-score ties by position
instead; bit-identity between the two therefore assumes tie-free
scores (true for the float workloads in the test batteries — exact
duplicate dot products across distinct docs).

The tag lane of the old fused kernel is replaced by one *mark bit* in
the id word (``NEW_MARK``): candidates entering a merge are marked,
survivors still marked afterwards are this probe's new entries.  Doc
ids must stay below 2**30.  The tombstone/empty id ``-1`` is never
marked and never unmarked — ``strip_marks`` masks only non-negative
words, so the sentinel survives untouched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

_SIGN_FLIP = 0x7FFFFFFF          # flips magnitude bits of negatives
NEW_MARK = 1 << 30               # id-word bit: entered on this probe


def score_to_key(s: jnp.ndarray) -> jnp.ndarray:
    """f32 -> i32, strictly order-preserving under signed compare."""
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(bits < 0, bits ^ _SIGN_FLIP, bits)


def key_to_score(key: jnp.ndarray) -> jnp.ndarray:
    """Exact inverse of :func:`score_to_key` (it is an involution)."""
    bits = jnp.where(key < 0, key ^ _SIGN_FLIP, key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def key_of(x: float) -> int:
    """Host-side key of a python float (for sentinel constants)."""
    b = int(np.float32(x).view(np.int32))
    return b ^ _SIGN_FLIP if b < 0 else b


def mark_new(ids: jnp.ndarray) -> jnp.ndarray:
    """Set the new-entry bit on real ids; -1 sentinels pass through."""
    return jnp.where(ids >= 0, ids | NEW_MARK, ids)


def strip_marks(idw: jnp.ndarray) -> jnp.ndarray:
    """Clear the mark bit.  Guarded on sign so ``-1`` stays ``-1``
    (a bare ``& ~NEW_MARK`` would corrupt the sentinel)."""
    return jnp.where(idw >= 0, idw & ~NEW_MARK, idw)


def is_marked(idw: jnp.ndarray) -> jnp.ndarray:
    return (idw >= 0) & ((idw & NEW_MARK) != 0)


def pack(keys: jnp.ndarray, idw: jnp.ndarray) -> jnp.ndarray:
    """Stack (..., M) key / id-word lanes into the (..., 2, M) sort form."""
    return jnp.stack([keys, idw], axis=-2)


@jax.jit
def bitonic_desc_packed(x: jnp.ndarray) -> jnp.ndarray:
    """Sort a packed (..., 2, M) array descending by (key, id word).

    Jitted because ``pltpu.roll`` has no eager rule; inside a kernel
    or another jit the call is inlined.

    M must be a power of two.  The lane ^ jj partner of each
    compare-exchange pass comes from two lane rotations by ``jj``
    (``pltpu.roll``: an XLU rotate under Mosaic, ``jnp.roll`` under
    XLA and in interpret mode), selected by bit ``jj`` of the lane
    index.  Both words ride the same rotation and the same ``take_p``
    mask: two rotates + one select per pass for the whole record.
    """
    *lead, two, m = x.shape
    assert two == 2 and m & (m - 1) == 0, x.shape
    axis = x.ndim - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, (1,) * len(lead) + (1, m),
                                   axis)
    stages = int(np.log2(m))

    def partner(v, jj):
        # lane i reads lane i + jj when bit jj of i is clear, else i - jj
        return jnp.where((idx & jj) == 0, pltpu.roll(v, m - jj, axis),
                         pltpu.roll(v, jj, axis))

    for stage in range(1, stages + 1):
        kk = 1 << stage
        for jj in (1 << p for p in range(stage - 1, -1, -1)):
            # keep the max in descending blocks' low lanes and
            # ascending blocks' high lanes
            keep_max = ((idx & kk) == 0) ^ ((idx & jj) != 0)
            p = partner(x, jj)
            pk, pi = p[..., 0:1, :], p[..., 1:2, :]
            xk, xi = x[..., 0:1, :], x[..., 1:2, :]
            k_eq = pk == xk
            p_gt = (pk > xk) | (k_eq & (pi > xi))
            p_lt = (pk < xk) | (k_eq & (pi < xi))
            take_p = (keep_max & p_gt) | (~keep_max & p_lt)
            x = jnp.where(take_p, p, x)
    return x


def pad_lanes(x: jnp.ndarray, m_pad: int, *, pad_key: int) -> jnp.ndarray:
    """Pad a packed (..., 2, M) array to ``m_pad`` lanes with
    (pad_key, -1) records, which sink below every real candidate."""
    pad = m_pad - x.shape[-1]
    if not pad:
        return x
    shape = x.shape[:-1] + (pad,)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, x.ndim - 2)
    fill = jnp.where(row == 0, jnp.int32(pad_key), jnp.int32(-1))
    return jnp.concatenate([x, fill], axis=-1)


def merge_packed(run: jnp.ndarray, cand: jnp.ndarray, m_pad: int,
                 *, pad_key: int) -> jnp.ndarray:
    """Merge a packed running (..., 2, K) state with packed (..., 2, M)
    candidates.

    Returns the full sorted (..., 2, m_pad) network output; callers
    slice the leading K lanes back into their running state.
    """
    return bitonic_desc_packed(pad_lanes(
        jnp.concatenate([run, cand], axis=-1), m_pad, pad_key=pad_key))
