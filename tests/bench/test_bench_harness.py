"""The benchmark's harness end to end at a tiny size on the CPU.

Each test builds a throwaway benchmark root under ``tmp_path``: the
real metric readers and a tiny configuration and traffic mix, found by
name as the chip runs find theirs.  Kernels run in interpret mode,
where the served path must agree with the plain reference exactly.
"""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402

TINY_CFG = {
    "name": "tiny", "n_docs": 4096, "dim": 32, "n_clusters": 32,
    "list_pad": 256, "kmeans_iters": 3, "storage": "float32", "k": 10,
    "n_probe": 8, "patience_delta": 2, "patience_phi": 90.0,
    "wave_size": 16,
    "generator": {"n_components": 8, "zipf_s": 1.1, "spread": 0.1,
                  "seed": 3},
    # the tiny build reads 0.0025 and 0.0013 on the CPU; one Lloyd
    # iteration reads 0.067 drift, a skipped last assignment 0.0042
    "limits": {"failed": 0, "index_faults": 0, "centroid_drift": 0.01,
               "assign_excess": 0.003, "queries_differ": 0.0},
}

TINY_TRAFFIC = {
    "steady": {"arrivals": {"kind": "poisson",
                            "phases": [{"seconds": 1.0, "rate_qps": 48}]}},
    "batch": {"arrivals": {"kind": "backlog"}},
}
COMMON = {"queries": {"hard_frac": 0.35, "easy_noise": 0.15},
          "pool_queries": 4096, "warmup_queries": 32, "trace_seconds": 1,
          "check_queries": 40, "check_longest": 8}


def make_root(tmp: Path, extra_metrics=(), cfg=None, common=None) -> Path:
    """A benchmark root holding the real readers, a tiny config and the
    two tiny traffic mixes, with one cell of each."""
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(REPO / "bench" / "metrics", tmp / "bench" / "metrics")
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg or TINY_CFG))
    for name, tr in TINY_TRAFFIC.items():
        (tmp / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(common or COMMON, **tr)))
    bm["workloads"] = [
        {"name": "star768-steady", "config": "tiny", "traffic": "steady",
         "chips": 1, "why": "tiny"},
        {"name": "bigann128-batch", "config": "tiny", "traffic": "batch",
         "chips": 1, "why": "tiny"}]
    bm["per_layer"] += list(extra_metrics)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp


@pytest.mark.parametrize("cell,e2e", [
    ("star768-steady", {"setup_s", "p95_ms", "hbm_bytes_per_doc"}),
    ("bigann128-batch", {"setup_s", "qps", "hbm_bytes_per_doc"}),
])
def test_tiny_run_prints_a_correct_contract_line(tmp_path, cell, e2e):
    root = make_root(tmp_path)
    out = harness.run(cell, 2**31 + 5, 1.0, False, root=root,
                      platform="cpu")
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # hbm_bytes_per_doc needs memory stats, which the CPU does not give
    assert set(out["metrics"]) | {"hbm_bytes_per_doc"} == e2e
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_new_metric_is_found_by_name(tmp_path):
    """A metric added as one file plus one BENCHMARK.json entry is read
    in the cells it names, with no edit to the harness."""
    entry = {"name": "probes_per_query.tmp", "unit": "probes",
             "better": "lower", "source": "program_counter",
             "layer": "serve loop", "moves": "p95_ms",
             "workloads": ["star768-steady"]}
    root = make_root(tmp_path, [entry])
    (root / "bench" / "metrics" / "probes_per_query.tmp.py").write_text(
        "def read(w):\n"
        "    r = w.report\n"
        "    return sum(r.probes.values()) / len(r.probes)\n")
    out = harness.run("star768-steady", 9, 1.0, True, root=root,
                      platform="cpu")
    assert out["correct"] is True
    assert out["metrics"]["probes_per_query.tmp"]["value"] >= 1.0
    assert out["metrics"]["probes_per_query.tmp"]["unit"] == "probes"
    # traced runs report per-layer metrics only
    assert "p95_ms" not in out["metrics"]


def test_metrics_apply_to_the_cells_they_name():
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    steady = {m["name"] for m in harness.cell_metrics(bm, "star768-steady",
                                                      False)}
    batch = {m["name"] for m in harness.cell_metrics(bm, "bigann128-batch",
                                                     False)}
    assert steady == {"setup_s", "p95_ms", "hbm_bytes_per_doc"}
    assert batch == {"setup_s", "qps", "hbm_bytes_per_doc"}
    for m in bm["per_layer"] + bm["end_to_end"]:
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").exists()


def _run_cli(cwd: Path):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "star768-steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_a_cpu_backend():
    out = _run_cli(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == "" or '"correct"' not in out.stdout
    assert "needs 1 tpu device" in out.stderr


def test_cli_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's own
    files has no system to measure."""
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    for p in bm["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
