"""Corpus and query generation on the device.

The design is that of ``repro.data.synthetic.clustered_corpus``: an
anisotropic Gaussian mixture on the unit sphere whose component sizes
follow a Zipf law, and queries that mix *easy* ones (noisy copies of
docs) with *hard* ones (interpolations between two components plus
noise).  Here every array is drawn on the device in one jitted call,
so a multi-gigabyte corpus costs seconds of set-up, not a minute of
host numpy; the benchmark keeps its own copy so that no program change
can alter the data it is measured on.

Also here: an exact integer fingerprint of f32 rows, by which the check
confirms that the program's index holds every generated doc unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _unit(x: jnp.ndarray) -> jnp.ndarray:
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_docs", "dim",
                                             "n_components", "block"))
def make_docs(key, spread, zipf_s, *, n_docs: int, dim: int,
              n_components: int, block: int = 65536):
    """(n_docs, dim) unit docs, (C, dim) component centers, (C,) scales.

    Rows are drawn in blocks inside one ``fori_loop`` so that the only
    full-size buffer is the output; a ragged tail is covered by a last
    block that overlaps the one before it."""
    kc, ks, kd = jax.random.split(key, 3)
    centers = _unit(jax.random.normal(kc, (n_components, dim), F32))
    scales = (0.5 + jax.random.uniform(ks, (n_components,), F32)) * spread
    w = jnp.arange(1, n_components + 1, dtype=F32) ** (-zipf_s)
    cdf = jnp.cumsum(w / jnp.sum(w))
    block = min(block, n_docs)

    def body(i, out):
        start = jnp.minimum(i * block, n_docs - block)
        k1, k2 = jax.random.split(jax.random.fold_in(kd, i))
        u = jax.random.uniform(k1, (block,), F32)
        comp = jnp.minimum(jnp.searchsorted(cdf, u), n_components - 1)
        noise = jax.random.normal(k2, (block, dim), F32)
        pts = centers[comp] + noise * scales[comp][:, None]
        return jax.lax.dynamic_update_slice_in_dim(out, _unit(pts), start,
                                                   0)

    docs = jax.lax.fori_loop(0, -(-n_docs // block), body,
                             jnp.zeros((n_docs, dim), F32))
    return docs, centers, scales


@functools.partial(jax.jit, static_argnames=("n_queries", "n_hard"))
def make_queries(key, docs, centers, spread, easy_noise, *, n_queries: int,
                 n_hard: int):
    """(n_queries, dim) unit queries: ``n_hard`` interpolations between
    two components plus noise of ``spread``, the rest docs plus noise
    of ``easy_noise * spread``, in an order drawn from ``key``."""
    n_easy = n_queries - n_hard
    n_docs, dim = docs.shape
    n_comp = centers.shape[0]
    ks = jax.random.split(key, 6)
    src = jax.random.randint(ks[0], (n_easy,), 0, n_docs)
    easy = docs[src] + jax.random.normal(ks[1], (n_easy, dim), F32) \
        * (easy_noise * spread)
    c1 = jax.random.randint(ks[2], (n_hard,), 0, n_comp)
    c2 = jax.random.randint(ks[3], (n_hard,), 0, n_comp)
    t = jax.random.uniform(ks[4], (n_hard, 1), F32)
    hard = centers[c1] * t + centers[c2] * (1 - t) \
        + jax.random.normal(jax.random.fold_in(ks[4], 1), (n_hard, dim),
                            F32) * spread
    q = _unit(jnp.concatenate([easy, hard]))
    return q[jax.random.permutation(ks[5], n_queries)]


@functools.partial(jax.jit, static_argnames=("n_docs", "n_queries",
                                             "n_hard"))
def _query_sources(key, *, n_docs: int, n_queries: int, n_hard: int):
    n_easy = n_queries - n_hard
    ks = jax.random.split(key, 6)
    src = jax.random.randint(ks[0], (n_easy,), 0, n_docs)
    src = jnp.concatenate([src, jnp.full((n_hard,), -1, src.dtype)])
    return src[jax.random.permutation(ks[5], n_queries)]


def query_sources(seed: int, cfg: dict, n_queries: int,
                  query_mix: dict) -> np.ndarray:
    """(n_queries,) int32: the doc each easy query of :func:`generate`'s
    pool is a noisy copy of, -1 for a hard query.  The same draws from
    the same key as :func:`make_queries`."""
    return np.asarray(_query_sources(
        jax.random.fold_in(base_key(seed), 1), n_docs=cfg["n_docs"],
        n_queries=n_queries,
        n_hard=int(round(n_queries * query_mix["hard_frac"]))))


@jax.jit
def fingerprint(rows: jnp.ndarray) -> jnp.ndarray:
    """(n, d) f32 -> (n,) uint32: the bits of each row times fixed odd
    multipliers, summed mod 2**32.  Integer arithmetic, so the value
    does not depend on the order of the sum: equal rows give equal
    prints on any device and shape, and a changed bit changes it."""
    bits = jax.lax.bitcast_convert_type(rows, jnp.uint32)
    lane = jnp.arange(rows.shape[1], dtype=jnp.uint32)
    mult = (lane * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9)) \
        | jnp.uint32(1)
    return jnp.sum(bits * mult[None, :], axis=1, dtype=jnp.uint32)


def docs_on_device(cfg: dict):
    """The configuration's corpus, (n_docs, dim) on the device, and its
    component centers.  Drawn from the configuration's own data seed:
    a deployment serves one corpus, so the index and every compiled
    program over it repeat from run to run."""
    gen = cfg["generator"]
    return make_docs(
        jax.random.fold_in(base_key(gen["seed"]), 0), F32(gen["spread"]),
        F32(gen["zipf_s"]), n_docs=cfg["n_docs"], dim=cfg["dim"],
        n_components=gen["n_components"])


def generate(seed: int, cfg: dict, n_queries: int, query_mix: dict):
    """Docs and the traffic's queries for one run.

    Returns ``(docs, queries, prints)`` as host arrays: the docs the
    program indexes, the query pool, drawn from ``seed``, and the docs'
    fingerprints.  The device copy of the docs is dropped before this
    returns, so the program's own upload finds the memory free."""
    docs, centers, _ = docs_on_device(cfg)
    n_hard = int(round(n_queries * query_mix["hard_frac"]))
    queries = make_queries(
        jax.random.fold_in(base_key(seed), 1), docs, centers,
        F32(cfg["generator"]["spread"]), F32(query_mix["easy_noise"]),
        n_queries=n_queries, n_hard=n_hard)
    prints = np.asarray(fingerprint(docs))
    host_docs = np.asarray(docs)
    host_q = np.asarray(queries)
    del docs, centers, queries
    return host_docs, host_q, prints
