"""Multi-device semantics via subprocess (XLA_FLAGS must be set before
jax import, so these run in worker processes with 8 fake devices)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow   # subprocess-per-test: ~1 min total

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        "--xla_disable_hlo_passes=all-reduce-promotion")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"     # a child never contends for a chip
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


PRELUDE = textwrap.dedent("""
    import json
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
""")


def test_distributed_ivf_matches_local():
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.data.synthetic import clustered_corpus
        from repro.core import build_index, brute_force, metrics
        from repro.core.distributed_ivf import (shard_index,
                                                make_distributed_search)
        c = clustered_corpus(n_docs=8000, dim=24, n_components=64,
                             n_queries=64, seed=0)
        idx = build_index(c.docs, 64, list_pad=256, n_iters=4)
        sh = shard_index(idx, 4)
        fn = make_distributed_search(mesh, n_probe=64, k=10,
                                     patience_delta=None, list_pad=256)
        with mesh:
            res = fn(*map(jnp.asarray, (sh.centroids, sh.docs,
                                        sh.doc_ids, sh.offsets,
                                        sh.sizes)), jnp.asarray(c.queries))
        _, exact = brute_force(jnp.asarray(c.docs),
                               jnp.asarray(c.queries), 10)
        r = metrics.r_star_at_1(np.asarray(res.topk_ids),
                                np.asarray(exact)[:, 0])
        print(json.dumps({"recall": r}))
    """))
    # probing every cluster distributed == exhaustive
    assert out["recall"] == 1.0


def test_sharded_embedding_lookup_matches_dense():
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.distributed.embedding import make_sharded_lookup
        rows, d = 64, 8
        table = jnp.asarray(
            np.random.default_rng(0).normal(0, 1, (rows, d))
            .astype(np.float32))
        ids = jnp.asarray(
            np.random.default_rng(1).integers(0, rows, (16, 5))
            .astype(np.int32))
        fn = make_sharded_lookup(mesh, rows)
        with mesh:
            out = fn(table, ids)
        exp = np.asarray(table)[np.asarray(ids)]
        err = float(np.max(np.abs(np.asarray(out) - exp)))
        print(json.dumps({"err": err}))
    """))
    assert out["err"] < 1e-5


def test_ring_all_gather_matches_xla():
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.distributed.collectives import ring_all_gather
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

        def local(xs):
            return ring_all_gather(xs, "model", 4)

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=P(None, "model"),
                           out_specs=P(None, None, "model"),
                           check_vma=False)
        with mesh:
            got = fn(x)                      # (4, 8, 1) chunks stacked
        chunks = [np.asarray(x)[:, i:i+1] for i in range(4)]
        exp = np.stack(chunks)
        err = float(np.max(np.abs(np.asarray(got) - exp)))
        print(json.dumps({"err": err}))
    """))
    assert out["err"] < 1e-6


def test_compressed_psum_approximates_mean():
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.distributed.collectives import compressed_psum
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 1, (8, 64)).astype(np.float32))

        def local(gs):
            out, _ = compressed_psum(gs[0], jnp.zeros_like(gs[0]),
                                     "data")
            return out[None]

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=P("data", None),
                           out_specs=P("data", None), check_vma=False)
        with mesh:
            got = fn(g.reshape(2, 4, 64)[:, 0])   # 2 dp shards
        exp = np.asarray(g.reshape(2, 4, 64)[:, 0]).mean(0)
        err = float(np.max(np.abs(np.asarray(got)[0] - exp)))
        scale = float(np.abs(exp).max())
        print(json.dumps({"rel": err / (scale + 1e-9)}))
    """))
    assert out["rel"] < 0.02    # one int8 quantization step


def test_moe_sharded_matches_single_device():
    out = _run(PRELUDE + textwrap.dedent("""
        import dataclasses, functools
        from repro.configs import get_arch, reduced
        from repro.models import moe as moe_lib
        from repro.distributed.context import activation_mesh
        cfg = reduced(get_arch("dbrx-132b")).model
        p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32)
        ref_out, ref_aux = moe_lib.moe_forward(p, x, cfg)   # no mesh
        with mesh, activation_mesh(mesh):
            out, aux = jax.jit(
                lambda p_, x_: moe_lib.moe_forward(p_, x_, cfg))(p, x)
        err = float(jnp.max(jnp.abs(out - ref_out)))
        print(json.dumps({"err": err, "aux_err":
                          abs(float(aux) - float(ref_aux))}))
    """))
    assert out["err"] < 2e-2
    assert out["aux_err"] < 1e-3


def test_smoke_dryrun_cell_small_mesh():
    """dryrun machinery end-to-end on a small mesh (fast cell)."""
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.launch import cells as cells_lib
        from repro.distributed.context import activation_mesh
        with mesh, activation_mesh(mesh):
            cell = cells_lib.build_cell("gat-cora", "molecule", mesh)
            compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                               out_shardings=cell.out_shardings,
                               donate_argnums=cell.donate_argnums
                               ).lower(*cell.args).compile()
            ca = compiled.cost_analysis()
        print(json.dumps({"flops": float(ca["flops"])}))
    """))
    assert out["flops"] > 0


def test_int8_doc_storage_matches_f32():
    out = _run(PRELUDE + textwrap.dedent("""
        from repro.data.synthetic import clustered_corpus
        from repro.core import build_index, brute_force, metrics
        from repro.core.distributed_ivf import (shard_index,
                                                quantize_sharded,
                                                make_distributed_search)
        c = clustered_corpus(n_docs=6000, dim=24, n_components=64,
                             n_queries=64, seed=3)
        idx = build_index(c.docs, 64, list_pad=256, n_iters=4)
        sh = quantize_sharded(shard_index(idx, 4))
        fn = make_distributed_search(mesh, n_probe=64, k=10,
                                     patience_delta=None, list_pad=256,
                                     int8_docs=True)
        with mesh:
            res = fn(*map(jnp.asarray, (sh.centroids, sh.docs,
                                        sh.doc_ids, sh.offsets,
                                        sh.sizes)),
                     jnp.asarray(c.queries),
                     jnp.asarray(sh.doc_scales))
        _, exact = brute_force(jnp.asarray(c.docs),
                               jnp.asarray(c.queries), 10)
        r = metrics.r_star_at_1(np.asarray(res.topk_ids),
                                np.asarray(exact)[:, 0])
        print(json.dumps({"recall": r}))
    """))
    assert out["recall"] >= 0.98    # int8 rounding can flip rare ties
