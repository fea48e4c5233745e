"""The reduction from a profiler trace to busy time, idle gaps and
kernel time by name, on a trace recorded here with hand-placed device
operations inside its window."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402


def test_merged_busy_and_gaps_clip_to_the_window():
    iv = [(5, 15), (10, 20), (30, 40), (38, 45), (90, 120)]
    assert trace.merged(iv, 0, 100) == [(5, 20), (30, 45), (90, 100)]
    assert trace.busy_ns(iv, 0, 100) == 15 + 15 + 10
    assert trace.idle_gaps(iv, 0, 100) == [(0, 5), (20, 30), (45, 90)]
    assert trace.idle_gaps([], 0, 10) == [(0, 10)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A real trace: a window span holding two wave spans."""
    d = tmp_path_factory.mktemp("xplane")
    f = jax.jit(lambda x: x * 2 + 1)
    x = jax.numpy.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.wave"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.load(str(d))


def test_recorded_trace_holds_the_bench_spans(recorded):
    names = sorted(s.name for s in recorded.spans)
    assert names == ["bench.wave", "bench.wave", "bench.window"]
    lo, hi = trace.window(recorded)
    waves = [s for s in recorded.spans if s.name == "bench.wave"]
    assert all(lo <= s.start < s.end <= hi for s in waves)


def test_summary_of_hand_placed_device_ops(recorded):
    lo, hi = trace.window(recorded)
    span = hi - lo
    wave = min((s for s in recorded.spans if s.name == "bench.wave"),
               key=lambda s: s.start)
    ops = [  # start/end as fractions of the window; TPU names
        trace.Event("%ivf_scan_merge.1 = (f32[64,8,1,100]) custom-call()",
                    lo + 0.10 * span, lo + 0.30 * span),
        trace.Event("%fusion.2 = s32[512] fusion()", lo + 0.25 * span,
                    lo + 0.40 * span),
        trace.Event("%fusion.2 = f32[64] fusion()", lo - 0.10 * span,
                    lo + 0.05 * span),
    ]
    mods = [trace.Event("jit__admit(123)", lo - 0.10 * span,
                        lo + 0.05 * span),
            trace.Event("jit__advance(456)", lo + 0.10 * span,
                        lo + 0.40 * span)]
    t = trace.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": mods},
                    recorded.spans)
    s = trace.summarize(t)
    assert s.window_s == pytest.approx(span / 1e9)
    # busy: [0, .05] and [.10, .40] of the window
    assert s.busy_s == pytest.approx(0.35 * span / 1e9)
    assert s.idle_share == pytest.approx(0.65)
    assert s.op_seconds("ivf_scan_merge") == pytest.approx(0.2 * span / 1e9)
    assert s.module_seconds("_admit") == pytest.approx(0.05 * span / 1e9)
    # ops of one name in two programs stay apart
    assert set(s.op_s) == {"jit__advance/ivf_scan_merge.1",
                           "jit__advance/fusion.2", "jit__admit/fusion.2"}
    assert sum(s.gap_s.values()) == pytest.approx(0.65 * span / 1e9)
    assert trace.SpanIndex(recorded.spans).label(
        0.5 * (wave.start + wave.end)) == "bench.wave"
    assert len(s.top(s.op_s, 2)) == 2


def test_span_index_prefers_the_innermost_span():
    spans = [trace.Event("bench.window", 0, 100),
             trace.Event("bench.wave", 10, 20),
             trace.Event("bench.wave", 30, 60)]
    ix = trace.SpanIndex(spans)
    assert ix.label(15) == "bench.wave"
    assert ix.label(25) == "bench.window"
    assert ix.label(59) == "bench.wave"
    assert ix.label(150) == "outside bench spans"


@pytest.fixture(scope="module")
def recorded_stages(tmp_path_factory):
    """A real trace whose waves hold the serve loop's stage spans."""
    d = tmp_path_factory.mktemp("xplane_stages")
    f = jax.jit(lambda x: x * 3 - 1)
    x = jax.numpy.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.wave"):
                with jax.profiler.TraceAnnotation("serve.wait_advance"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("serve.admit"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench.write"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.load(str(d))


def test_load_collects_the_serve_stages(recorded_stages):
    names = sorted(s.name for s in recorded_stages.spans)
    assert names == ["bench.wave"] * 2 + ["bench.window"] \
        + ["bench.write"] * 2 + ["serve.admit"] * 2 \
        + ["serve.wait_advance"] * 2


@pytest.mark.parametrize("reader", ["idle_share.steady", "idle_share.batch",
                                    "idle_share.live"])
def test_stage_spans_leave_busy_and_idle_unchanged(recorded_stages, reader):
    """Labelling idle gaps by the serve loop's stages moves idle time
    between labels only: on one recorded event list, with and without
    the ``serve.*`` spans, the window, the busy time, the idle total and
    every ``idle_share.*`` read the same."""
    import types
    from bench import harness
    lo, hi = trace.window(recorded_stages)
    span = hi - lo
    stage = [s for s in recorded_stages.spans if s.name.startswith("serve.")]
    ops = [trace.Event("%ivf_scan_merge.1 = () custom-call()",
                       s.start + 0.1 * (s.end - s.start),
                       s.start + 0.6 * (s.end - s.start)) for s in stage]
    ops.append(trace.Event("%fusion.2 = () fusion()", lo + 0.9 * span,
                           lo + 0.95 * span))
    bench_only = [s for s in recorded_stages.spans
                  if s.name.startswith("bench.")]
    sums = [trace.summarize(trace.Trace({"/device:TPU:0": ops},
                                        {"/device:TPU:0": []}, sp))
            for sp in (recorded_stages.spans, bench_only)]
    read = harness.reader(reader)
    with_stages, without = sums
    assert with_stages.window_s == without.window_s
    assert with_stages.busy_s == without.busy_s
    assert with_stages.idle_share == without.idle_share
    assert sum(with_stages.gap_s.values()) == pytest.approx(
        sum(without.gap_s.values()))
    assert read(types.SimpleNamespace(trace=with_stages)) == \
        read(types.SimpleNamespace(trace=without))
    assert {"serve.wait_advance", "serve.admit"} <= set(with_stages.gap_s)
    assert not any(k.startswith("serve.") for k in without.gap_s)
