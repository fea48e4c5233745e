"""Benchmark entry point: one section per paper table/figure + the
beyond-paper serving table and kernel CSV.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke]

--full: 3x timing reps + bigger forests in Table 2 (slower). The
roofline table is produced separately from the dry-run artifacts via
``python -m benchmarks.roofline`` (it needs launch/dryrun.py output).
--smoke: minutes-scale CI mode — tiny substrate, one encoder, skips
the distribution/figure sections, but still writes (and therefore
validates) every JSON artifact: BENCH_kernels.json, BENCH_table2.json,
BENCH_serving.json.
"""
from __future__ import annotations

import json
import os
import sys
import time

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "artifacts")


def _write(name: str, payload) -> None:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {os.path.relpath(path)}")


def main() -> None:
    full = "--full" in sys.argv
    smoke = "--smoke" in sys.argv
    from repro.launch import compile_cache
    compile_cache.enable()
    t0 = time.time()
    print("=" * 72)
    print("## Kernel micro-benchmarks (name,us_per_call,max_err)")
    from benchmarks import kernel_bench
    _write("BENCH_kernels.json", kernel_bench.main(smoke=smoke))

    if not smoke:
        print("=" * 72)
        print("## Paper §Classification: C(q) power law")
        from benchmarks import clabel_dist
        clabel_dist.main("star-like")

        print("=" * 72)
        print("## Paper Figure 1: phi_h saturation + Exit/Continue split")
        from benchmarks import figure1
        figure1.main("star-like")

    print("=" * 72)
    print("## Paper Table 2: early-exit strategies x 3 encoders")
    from benchmarks import table2
    _write("BENCH_table2.json", table2.main(quick=not full, smoke=smoke))

    print("=" * 72)
    print("## Beyond-paper: wave scheduler + live-mutation serving")
    from benchmarks import serving_bench
    _write("BENCH_serving.json", serving_bench.main("star-like",
                                                    smoke=smoke))

    if not smoke:
        print("=" * 72)
        try:
            from benchmarks import roofline
            rows = roofline.load_records("single")
            if rows:
                print("## Roofline (single-pod dry-run artifacts)")
                roofline.main("single")
            else:
                print("## Roofline: no dry-run artifacts yet "
                      "(run python -m repro.launch.dryrun --all)")
        except Exception as e:  # noqa: BLE001
            print(f"## Roofline skipped: {e}")
    print(f"\ntotal bench time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
