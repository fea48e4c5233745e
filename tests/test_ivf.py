"""IVF index + search behaviour (the paper's data plane)."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (brute_force, build_index, exact_topk, metrics,
                        policies, probe_trace, min_probes_labels, search)


def test_index_layout(tiny_index, tiny_corpus):
    offs = np.asarray(tiny_index.cluster_offsets)
    sizes = np.asarray(tiny_index.cluster_sizes)
    ids = np.asarray(tiny_index.doc_ids)
    assert (sizes <= tiny_index.list_pad).all()
    assert (offs % 128 == 0).all()                # kernel alignment
    seen = []
    for c in range(len(offs)):
        sl = ids[offs[c]: offs[c] + sizes[c]]
        assert (sl >= 0).all()
        seen.append(sl)
    seen = np.concatenate(seen)
    assert len(np.unique(seen)) == tiny_corpus.docs.shape[0]


def test_docs_match_source(tiny_index, tiny_corpus):
    offs = np.asarray(tiny_index.cluster_offsets)
    sizes = np.asarray(tiny_index.cluster_sizes)
    ids = np.asarray(tiny_index.doc_ids)
    docs = np.asarray(tiny_index.docs)
    c = 3
    sl = slice(offs[c], offs[c] + sizes[c])
    np.testing.assert_allclose(docs[sl], tiny_corpus.docs[ids[sl]],
                               rtol=1e-6)


def test_fixed_recall_increases_with_n(tiny_index, tiny_corpus,
                                       tiny_exact):
    q = jnp.asarray(tiny_corpus.queries)
    recalls = []
    for n in (2, 8, 32):
        res = search(tiny_index, q, policies.fixed(n, k=10, tau=3))
        recalls.append(metrics.r_star_at_1(np.asarray(res.topk_ids),
                                           tiny_exact[1][:, 0]))
        assert (np.asarray(res.probes) == n).all()
    assert recalls[0] <= recalls[1] <= recalls[2]
    assert recalls[2] > 0.85


def test_full_probe_equals_brute_force(tiny_index, tiny_corpus,
                                       tiny_exact):
    q = jnp.asarray(tiny_corpus.queries)
    n = tiny_index.n_clusters
    res = search(tiny_index, q, policies.fixed(n, k=10, tau=3))
    assert metrics.r_star_at_1(np.asarray(res.topk_ids),
                               tiny_exact[1][:, 0]) == 1.0


@pytest.mark.parametrize("block", [65536, 1000], ids=["one_block", "ragged"])
def test_exact_topk_matches_brute_force(tiny_index, tiny_corpus, block):
    """The blocked oracle over the index's own docs skips padding rows
    and tombstoned ids, counts no row of a ragged last block twice, and
    returns ids in the corpus's id space."""
    q = tiny_corpus.queries[:40]
    _, ref = brute_force(jnp.asarray(tiny_corpus.docs), jnp.asarray(q), 30)
    ref = np.asarray(ref)
    dead = np.unique(ref[:, 0])
    ids = np.asarray(tiny_index.doc_ids)
    index = dataclasses.replace(
        tiny_index, doc_ids=jnp.asarray(np.where(np.isin(ids, dead), -1,
                                                 ids)))
    _, got = exact_topk(index, q, 10, q_block=16, block=block)
    want = np.stack([row[~np.isin(row, dead)][:10] for row in ref])
    np.testing.assert_array_equal(got, want)


def test_scores_sorted_and_ids_unique(tiny_index, tiny_corpus):
    q = jnp.asarray(tiny_corpus.queries)
    res = search(tiny_index, q, policies.fixed(16, k=10, tau=3))
    s = np.asarray(res.topk_scores)
    ids = np.asarray(res.topk_ids)
    assert (np.diff(s, axis=1) <= 1e-6).all()
    for row in ids:
        valid = row[row >= 0]
        assert len(np.unique(valid)) == len(valid)


def test_kernel_paths_match(tiny_index, tiny_corpus):
    q = jnp.asarray(tiny_corpus.queries[:64])
    pol = policies.patience(24, delta=3, phi=90.0, k=10, tau=3)
    a = search(tiny_index, q, pol)
    b = search(tiny_index, q, pol, use_scan_kernel=True,
               use_topk_kernel=True)
    assert (np.asarray(a.topk_ids) == np.asarray(b.topk_ids)).all()
    assert (np.asarray(a.probes) == np.asarray(b.probes)).all()


def test_labels_power_law(tiny_index, tiny_corpus, tiny_exact):
    """Paper §Classification: ~50% of queries need 1 probe; the
    distribution is heavy-tailed."""
    q = jnp.asarray(tiny_corpus.queries)
    traj, _ = probe_trace(tiny_index, q, 32, 10)
    lab = min_probes_labels(traj, tiny_exact[1][:, 0], 32)
    frac1 = float(np.mean(lab == 1))
    assert frac1 > 0.25                     # mass at C(q)=1
    assert float(np.mean(lab <= 10)) > frac1 + 0.1


def test_phi_saturates(tiny_index, tiny_corpus):
    """Paper Figure 1: mean intersection climbs toward 100%."""
    q = jnp.asarray(tiny_corpus.queries[:128])
    _, phi = probe_trace(tiny_index, q, 32, 10)
    mean = phi.mean(axis=1)
    assert mean[-1] > 85.0
    assert mean[-1] > mean[0]
