"""The serve loop's pull counter (``bench/metrics/pulls_per_wave.*``):
one blocking device-to-host read a wave, and silence on a program
without the counter."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from test_bench_harness import make_root  # noqa: E402

SUFFIXES = ("steady", "batch")


@pytest.mark.parametrize("suffix", SUFFIXES)
def test_pull_readers_on_made_up_reports(suffix):
    read = harness.reader(f"pulls_per_wave.{suffix}")
    old = types.SimpleNamespace(waves=3, probes={0: 1}, lane_steps=8)
    assert read(types.SimpleNamespace(report=old)) is None
    none = types.SimpleNamespace(waves=0, host_pulls=1)
    assert read(types.SimpleNamespace(report=none)) is None
    rep = types.SimpleNamespace(waves=40, host_pulls=41)
    assert read(types.SimpleNamespace(report=rep)) == pytest.approx(1.025)


@pytest.mark.parametrize("cell,suffix", [("star768-steady", "steady"),
                                         ("bigann128-batch", "batch")])
def test_tiny_cells_report_one_pull_a_wave(tmp_path, cell, suffix):
    root = make_root(tmp_path)
    out = harness.run(cell, 2**31 + 17, 1.0, True, root=root,
                      platform="cpu")
    assert out["correct"] is True, out["checks"]
    # one read a wave and one more: (waves + 1) / waves, exactly, for
    # the few waves a loaded CPU runs in a second
    v = out["metrics"][f"pulls_per_wave.{suffix}"]["value"]
    assert 1.0 < v < 2.0
    assert v == pytest.approx(1 + 1 / round(1 / (v - 1)))
