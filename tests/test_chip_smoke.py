"""chip_smoke.py's phases end to end at a tiny size on the CPU.

The script itself insists on a TPU; here the sizes and the expected
platform are passed to its ``run`` so its control flow cannot rot
between chip runs.  Kernels run in interpret mode, where the fused
path must agree with the per-probe reference exactly.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_chip_smoke_phases_agree_on_cpu(capsys):
    cfg = chip_smoke.SmokeConfig(
        n_docs=4096, dim=32, n_clusters=32, n_components=16, list_pad=256,
        k=10, n_probe=8, delta=2, phi=90.0, kmeans_iters=3,
        n_queries=40, wave_size=16, delta_cap=256, n_adds=48,
        n_deletes=16, max_differing=1e-9, seed=3)
    out = chip_smoke.run(cfg, platform="cpu")
    assert out == {"ok": True, "device": {"platform": "cpu",
                                          "kind": "cpu", "count": 1}}
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    phases = [x["phase"] for x in lines]
    for p in ("device", "corpus", "index", "exact_oracle", "static",
              "live", "merge", "merged"):
        assert p in phases
    checks = [x for x in lines if x.get("check") == "agreement"]
    assert [c["phase"] for c in checks] == ["static", "live", "merged"]
    assert all(c["differing"] == 0 for c in checks)
    static = next(x for x in lines if x["phase"] == "static"
                  and "r_star_at_k" in x)
    assert 0.0 < static["r_star_at_k"] <= 1.0


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO,
                                                       "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a tpu device" in out.stderr


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_goes_where_the_environment_says(monkeypatch,
                                                       tmp_path, from_env):
    """Entry points keep JAX's persistent cache in
    JAX_COMPILATION_CACHE_DIR when it is set, else at <repo>/.jax_cache;
    importing repro sets nothing."""
    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert before in (None, "") or before == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        where = compile_cache.enable()
        if from_env:
            assert where == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert where == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == where
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
