"""The program's own spans in the profiler trace of a window, and the
device's idle time put down to them.

The program writes a ``jax.profiler.TraceAnnotation`` at each layer
boundary of the transcode path (``repro.*``, named in
``src/repro/spans.py``).  They land on the host thread that made the
call, beside the harness's ``bench.*`` spans and JAX's own dispatch
events (``PjitFunction(<program>)``, each the host side of one jitted
program's launch), on the clock of the device's ops.  ``devtrace``
keeps only the harness's spans; this module reads the run's trace file
again for the rest:

- the ``repro.*`` and ``bench.*`` spans of the window's thread that
  start inside the window;
- the outermost dispatch events there (the trace nests each one in
  another of the same name);
- each device-idle interval put down to the innermost span covering it,
  with the spans that enclose that one (its path).

One caller on one thread makes one call at a time, so a call's spans are
tied together by nesting in time.  A run of a program without these
spans (an older checkout) reads nothing: ``for_run`` returns None.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import pathlib

import devtrace

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / ".bench_trace"

PROGRAM_PREFIX = "repro."
DISPATCH_EVENT = "PjitFunction("
CALL_SPAN = "bench.call"
# The program's entry points: one span per call of the cell.
ENTRY_SPANS = ("repro.transcode", "repro.ragged_transcode")
# From argument conversion to the return of the jitted program's call.
DISPATCH_SPANS = ("repro.transcode.dispatch", "repro.ragged.dispatch")
LAYOUT_CHECK_SPAN = "repro.ragged.check_layout"
PACK_SPAN = "repro.pack_documents"


@dataclasses.dataclass(frozen=True)
class HostTrace:
    """The window's thread: its spans and its outermost dispatches."""

    spans: tuple        # devtrace.Event, repro.* and bench.*, by start
    dispatches: tuple   # devtrace.Event, outermost PjitFunction(...)

    def named(self, *names) -> list:
        return [e for e in self.spans if e.name in names]

    def calls(self) -> int:
        """The harness's calls in the window."""
        return len(self.named(CALL_SPAN))

    def mean_ms(self, *names) -> float | None:
        """Mean duration of the spans of these names, in ms."""
        evs = self.named(*names)
        if not evs:
            return None
        return sum(e.end - e.start for e in evs) / len(evs) / 1e6

    def dispatches_per_call(self) -> float | None:
        """Outermost dispatch events inside an entry span, per entry
        span."""
        entries = self.named(*ENTRY_SPANS)
        if not entries:
            return None
        starts = [e.start for e in entries]
        inside = 0
        for d in self.dispatches:
            k = bisect.bisect_right(starts, d.start) - 1
            inside += k >= 0 and d.end <= entries[k].end
        return inside / len(entries)

    def idle_by_path(self, trace: devtrace.Trace) -> dict:
        """Seconds of device-idle time in the window under each path of
        spans (outermost first; ``()`` where none covers it), averaged
        over the chips."""
        segments = innermost(self.spans, trace.window)
        out: dict = {}
        for evs in trace.ops.values():
            g = devtrace.gaps([(e.start, e.end) for e in evs], trace.window)
            for path, s in overlap(g, segments).items():
                out[path] = out.get(path, 0.0) + s / len(trace.ops)
        return out


def innermost(spans, window) -> list:
    """``(start, end, path)`` for each stretch of ``window``, in order:
    the names of the spans covering it, outermost first.  The spans come
    from one thread, so two of them are nested or disjoint."""
    out: list = []
    stack: list = []          # [(end, name)]
    at = window[0]

    def emit(upto):
        nonlocal at
        if upto > at:
            out.append((at, upto, tuple(n for _, n in stack)))
            at = upto

    for ev in sorted(spans, key=lambda e: (e.start, -e.end)):
        s, e = max(ev.start, window[0]), min(ev.end, window[1])
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, ev.name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(window[1])
    return out


def overlap(intervals, segments) -> dict:
    """Length (s) of the sorted, disjoint ``intervals`` lying in each
    path of ``segments`` (``innermost``'s output)."""
    out: dict = {}
    j = 0
    for lo, hi in intervals:
        while j < len(segments) and segments[j][1] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < hi:
            o = min(segments[k][1], hi) - max(segments[k][0], lo)
            if o > 0:
                path = segments[k][2]
                out[path] = out.get(path, 0.0) + o / 1e9
            k += 1
    return out


def _outermost(events) -> list:
    out: list = []
    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        if out and ev.end <= out[-1].end:
            continue
        out.append(ev)
    return out


def from_profile(profile, window) -> HostTrace | None:
    """The spans and dispatches of the thread whose ``bench.window`` span
    is ``window``, or None when that thread holds no ``repro.*`` span
    inside it."""
    lo, hi = window
    for plane in profile.planes:
        if plane.name != devtrace.HOST_PLANE:
            continue
        for line in plane.lines:
            events = [devtrace.Event(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                      for e in line.events]
            if not any(e.name == devtrace.WINDOW_SPAN
                       and (e.start, e.end) == tuple(window)
                       for e in events):
                continue
            inside = [e for e in events if lo <= e.start < hi]
            spans = sorted((e for e in inside
                            if e.name.startswith(PROGRAM_PREFIX)
                            or (e.name.startswith(devtrace.SPAN_PREFIX)
                                and e.name != devtrace.WINDOW_SPAN)),
                           key=lambda e: (e.start, -e.end))
            if not any(e.name.startswith(PROGRAM_PREFIX) for e in spans):
                return None
            return HostTrace(tuple(spans), tuple(_outermost(
                e for e in inside if e.name.startswith(DISPATCH_EVENT))))
    return None


@functools.lru_cache(maxsize=1)
def _load(window: tuple) -> HostTrace | None:
    """The host side of the trace file under ``TRACE_DIR`` whose window
    is ``window``, newest file first; None when none is."""
    import jax

    files = sorted(TRACE_DIR.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for f in files:
        try:
            profile = jax.profiler.ProfileData.from_file(str(f))
        except (OSError, RuntimeError, ValueError):
            continue
        if _window_of(profile) == window:
            return from_profile(profile, window)
    return None


def _window_of(profile):
    for plane in profile.planes:
        if plane.name != devtrace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == devtrace.WINDOW_SPAN:
                    return (e.start_ns, e.start_ns + e.duration_ns)
    return None


def for_run(obs) -> HostTrace | None:
    """The program's spans in this run's traced window, or None: no
    trace, no chip in it (a host time off the chip is no device-trace
    number), no file, or a program without spans."""
    if obs.trace is None or not obs.trace.ops:
        return None
    return _load(tuple(obs.trace.window))
