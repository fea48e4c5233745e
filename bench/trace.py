"""Reduction of a JAX profiler trace to busy time, kernel time by name,
the top device operations and the idle gaps labelled by host spans.

The profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain
interval lists; everything after that is arithmetic on those lists, so
the tests check it on small recorded or hand-made traces.

* Device operations are the events of the ``XLA Ops`` line of every
  ``/device:`` plane; programs are those of its ``XLA Modules`` line.
* Host spans are the benchmark's own ``TraceAnnotation``s, whose names
  start with ``bench.``; the window is the ``bench.window`` span.
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices; idle is the rest of the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    stats: Dict[str, str] = dataclasses.field(default_factory=dict)

    def matches(self, needle: str) -> bool:
        """Does the op's own name hold ``needle``?  (Not its operands:
        an op that reads the kernel's output names it too.)"""
        return needle in self.short

    @property
    def short(self) -> str:
        """The op's own name: a TPU trace names an op by its whole HLO
        line (``%fusion.6 = f32[...] fusion(...)``)."""
        return self.name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> its operations
    modules: Dict[str, List[Event]]    # device plane -> its programs
    spans: List[Event]                 # host spans named bench.*


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        stats = {str(k): str(v) for k, v in e.stats}
        out.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    return out


def program_of(ops: List[Event], modules: List[Event]) -> List[str]:
    """``<program>/<op>`` for each op, the program being the module
    event that holds the op's start on the same device (op names such
    as ``fusion.2`` repeat across programs)."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < mods[i].end:
            prog = mods[i].name.split("(", 1)[0]
        else:
            prog = e.stats.get("hlo_module", "?")
        out.append(f"{prog}/{e.short}")
    return out


def load(trace_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` the profiler wrote under the dir."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
                elif line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    return Trace(ops, modules, spans)


def merged(intervals: Iterable[Interval], lo: float, hi: float
           ) -> List[Interval]:
    """Union of intervals clipped to [lo, hi], as disjoint sorted runs."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_gaps(intervals: Iterable[Interval], lo: float, hi: float
              ) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


class SpanIndex:
    """Innermost host span around a time.  Spans of one name do not
    overlap each other (each is a phase, a window or a wave), so one
    bisection per name finds every candidate."""

    def __init__(self, spans: Sequence[Event]):
        by_name: Dict[str, List[Event]] = defaultdict(list)
        for sp in spans:
            by_name[sp.name].append(sp)
        self._by_name = {n: (sorted(v, key=lambda e: e.start))
                         for n, v in by_name.items()}
        self._starts = {n: [e.start for e in v]
                        for n, v in self._by_name.items()}

    def label(self, t: float) -> str:
        best: Optional[Event] = None
        for name, evs in self._by_name.items():
            i = bisect.bisect_right(self._starts[name], t) - 1
            if i >= 0 and t < evs[i].end and (
                    best is None
                    or evs[i].end - evs[i].start < best.end - best.start):
                best = evs[i]
        return best.name if best is not None else "outside bench spans"


def window(trace: Trace) -> Interval:
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(w) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(w)}")
    return w[0].start, w[0].end


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    op_s: Dict[str, float]             # op name -> seconds, all devices
    module_s: Dict[str, float]         # program name -> seconds
    gap_s: Dict[str, float]            # host span label -> idle seconds
    n_devices: int
    _trace: Trace = dataclasses.field(repr=False, default=None)
    _lo: float = 0.0
    _hi: float = 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, needle: str) -> float:
        """Seconds of device operations whose name or stats hold
        ``needle``, summed over devices, inside the window."""
        return _clipped_s(self._trace.ops, needle, self._lo, self._hi)

    def module_seconds(self, needle: str) -> float:
        return _clipped_s(self._trace.modules, needle, self._lo, self._hi)

    def top(self, table: Dict[str, float], n: int = 10
            ) -> List[List[object]]:
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _clipped_s(per_dev: Dict[str, List[Event]], needle: str, lo: float,
               hi: float) -> float:
    tot = 0.0
    for evs in per_dev.values():
        tot += sum(max(0.0, min(e.end, hi) - max(e.start, lo))
                   for e in evs if e.matches(needle))
    return tot / 1e9


def summarize(trace: Trace) -> Summary:
    lo, hi = window(trace)
    if not trace.ops:
        raise RuntimeError("the trace holds no device operations")
    op_s: Dict[str, float] = defaultdict(float)
    mod_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    busy = []
    spans = SpanIndex(trace.spans)
    for dev, evs in trace.ops.items():
        iv = [(e.start, e.end) for e in evs]
        busy.append(busy_ns(iv, lo, hi))
        for e, lab in zip(evs, program_of(evs, trace.modules.get(dev, []))):
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                op_s[lab] += d / 1e9
        for g0, g1 in idle_gaps(iv, lo, hi):
            gap_s[spans.label(0.5 * (g0 + g1))] += \
                (g1 - g0) / 1e9 / len(trace.ops)
    for evs in trace.modules.values():
        for e in evs:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                mod_s[e.name] += d / 1e9
    return Summary((hi - lo) / 1e9, sum(busy) / len(busy) / 1e9,
                   dict(op_s), dict(mod_s), dict(gap_s), len(trace.ops),
                   trace, lo, hi)
