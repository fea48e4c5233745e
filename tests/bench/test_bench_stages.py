"""The serve loop's stage readers (``bench/stages.py``): idle gaps put
down to ``serve.*`` spans, the tiny cells reporting the stage metrics,
and silence on a program without the stages."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, stages, trace  # noqa: E402
from test_bench_live import make_live_root  # noqa: E402

WAVE = [trace.Event("bench.window", 0, 100),
        trace.Event("bench.wave", 10, 60)]
SERVE = [trace.Event("serve.wait_advance", 10, 20),
         trace.Event("serve.harvest", 20, 30),
         trace.Event("serve.admit", 30, 50),
         trace.Event("serve.advance", 50, 55)]


@pytest.mark.parametrize("serve_spans", [False, True],
                         ids=["bench-only", "with-serve"])
def test_idle_gaps_go_to_the_innermost_serve_stage(serve_spans):
    """With ``serve.*`` spans inside a ``bench.wave``, a gap inside
    ``serve.admit`` is put down to it; without them the labels and
    seconds are those of the ``bench.*`` spans alone.  A gap is cut at
    the span edges inside it, each piece going to its own span."""
    spans = WAVE + (SERVE if serve_spans else [])
    ix = trace.SpanIndex(spans)
    assert ix.label(40) == ("serve.admit" if serve_spans else "bench.wave")
    assert ix.label(57) == "bench.wave"
    assert ix.label(80) == "bench.window"
    # device busy [12, 18] and [52, 54]: idle [0,12) [18,52) [54,100]
    ops = [trace.Event("%ivf_scan_merge.1 = () custom-call()", 12, 18),
           trace.Event("%fusion.2 = () fusion()", 52, 54)]
    mods = [trace.Event("jit__advance(1)", 12, 18),
            trace.Event("jit__admit(2)", 52, 54)]
    s = trace.summarize(trace.Trace({"/device:TPU:0": ops},
                                    {"/device:TPU:0": mods}, spans))
    assert sum(s.gap_s.values()) == pytest.approx(92e-9)
    if serve_spans:
        # [0,12) -> window 10, wait_advance 2; [18,52) -> wait_advance
        # 2, harvest 10, admit 20, advance 2; [54,100] -> advance 1,
        # wave 5, window 40
        assert s.gap_s == pytest.approx({
            "bench.window": 50e-9, "serve.wait_advance": 4e-9,
            "serve.harvest": 10e-9, "serve.admit": 20e-9,
            "serve.advance": 3e-9, "bench.wave": 5e-9})
    else:
        # [0,12) -> window 10, wave 2; [18,52) -> wave 34; [54,100] ->
        # wave 6, window 40
        assert s.gap_s == pytest.approx({"bench.window": 50e-9,
                                         "bench.wave": 42e-9})


def test_stage_readers_are_silent_without_the_stages():
    """A program that predates the stages leaves the metrics out."""
    rep = types.SimpleNamespace(waves=3, probes={0: 1}, lane_steps=8)
    w = types.SimpleNamespace(report=rep, cfg={"wave_size": 16})
    assert stages.host_ms(w) is None
    assert stages.wait_ms(w) is None
    assert stages.admit_row_use(w) is None


def test_stage_readers_on_a_made_up_report():
    from repro.core.serving import STAGES
    ms = np.zeros((3, len(STAGES)))
    ms[:, STAGES.index("wait_advance")] = [4.0, 5.0, 6.0]
    ms[:, STAGES.index("harvest")] = [0.5, 900.0, 0.5]   # a freeze
    ms[:, STAGES.index("advance")] = [1.0, 1.0, 1.0]
    rep = types.SimpleNamespace(stage_ms=ms, admit_calls=4, admitted=16)
    w = types.SimpleNamespace(report=rep, cfg={"wave_size": 16})
    assert stages.wait_ms(w) == pytest.approx(5.0)
    assert stages.host_ms(w) == pytest.approx(1.5)
    assert stages.admit_row_use(w) == pytest.approx(0.25)


@pytest.mark.parametrize("cell,suffix", [("star768-steady", "steady"),
                                         ("bigann128-batch", "batch"),
                                         ("star768-live", "live")])
def test_tiny_cells_report_the_stage_metrics(tmp_path, cell, suffix):
    root = make_live_root(tmp_path)
    out = harness.run(cell, 2**31 + 11, 1.0, True, root=root,
                      platform="cpu")
    assert out["correct"] is True, out["checks"]
    # the live cell has no admit_row_use of its own
    names = ("host_ms", "wait_ms") + (() if suffix == "live"
                                      else ("admit_row_use",))
    got = {n: out["metrics"][f"{n}.{suffix}"]["value"] for n in names}
    assert got["host_ms"] > 0 and got["wait_ms"] > 0
    assert suffix == "live" or 0 < got["admit_row_use"] <= 1
