"""The reduction from a profiler trace of the window to device numbers.

JAX's profiler writes one XSpace (``*.xplane.pb``).  On a TPU each chip
has a plane ``/device:TPU:<id>`` whose line ``XLA Ops`` holds every
operation that ran on it, nested where one op runs others (a
``conditional`` encloses the branch it takes).  Host threads share the
plane ``/host:CPU``; the harness's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``) are events there, on the same clock.

- Busy time is the union of a chip's op intervals inside the window,
  so nested ops count once; the idle share is 1 minus busy over the
  window, averaged over the chips.
- A Pallas kernel is an op whose HLO is a ``tpu_custom_call``.  The trace
  names an op by its HLO text (``%branch_0_fun.1 = ... custom-call(...),
  custom_call_target="tpu_custom_call", ...``), and the line ``XLA
  Modules`` says which jitted program (``jit__transcode_impl(...)``) it
  ran in; an op is labelled ``<program>/<instruction>``.
- Each idle gap is shared out among the harness spans that cover it,
  which says what the host was doing meanwhile.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
TPU_KERNEL = 'custom_call_target="tpu_custom_call"'
NO_SPAN = "outside any bench span"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str             # for an op, its HLO text
    start: float          # ns, on the trace's clock
    end: float
    module: str = ""      # for an op, the program it ran in

    @property
    def label(self) -> str:
        """``<program>/<HLO instruction>``, e.g.
        ``jit__transcode_impl/%branch_0_fun.1``."""
        instr = self.name.split(" = ", 1)[0]
        return f"{self.module}/{instr}" if self.module else instr

    @property
    def is_kernel(self) -> bool:
        return TPU_KERNEL in self.name


# ---------------------------------------------------------------------------
# Interval arithmetic.


def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` intervals covering the same
    points as ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals, window) -> float:
    """Length of ``window`` covered by ``intervals``."""
    return sum(e - s for s, e in union(clip(intervals, window)))


def gaps(intervals, window) -> list:
    """The parts of ``window`` that ``intervals`` leave uncovered."""
    out, at = [], window[0]
    for s, e in union(clip(intervals, window)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def idle_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def roofline_pct(needed_bytes: float, peak_bytes_per_s: float,
                 kernel_s: float) -> float:
    """Share of the memory roofline: the least time the bytes need at the
    peak, over the time the kernels took."""
    return 100.0 * needed_bytes / peak_bytes_per_s / kernel_s


def self_times(events) -> dict:
    """Seconds per op label, each op less the ops nested in it, so that a
    ``conditional`` is not counted again for the branch it ran."""
    out: dict = {}
    stack: list = []          # [end, label, start, time of children]

    def close(item):
        end, label, start, child = item
        out[label] = out.get(label, 0.0) + (end - start - child) / 1e9
        if stack:
            stack[-1][3] += end - start

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= ev.start:
            close(stack.pop())
        stack.append([ev.end, ev.label, ev.start, 0.0])
    while stack:
        close(stack.pop())
    return out


# ---------------------------------------------------------------------------
# The trace of one window.


@dataclasses.dataclass
class Trace:
    ops: dict                 # device id -> [Event] of its XLA Ops line
    spans: list               # the harness's bench.* spans
    window: tuple             # (start, end) ns of the bench.window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds of the window in which an op ran, averaged over the
        chips."""
        per = [covered([(e.start, e.end) for e in evs], self.window)
               for evs in self.ops.values()]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def in_window(self, events) -> list:
        lo, hi = self.window
        return [e for e in events if lo <= e.start < hi]

    def kernels(self) -> list:
        """The Pallas kernel ops of every chip in the window."""
        return [e for evs in self.ops.values() for e in self.in_window(evs)
                if e.is_kernel]

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, and the idle time by what
        the host was doing, ``top`` of each, in seconds."""
        ops: dict = {}
        for evs in self.ops.values():
            for label, s in self_times(self.in_window(evs)).items():
                ops[label] = ops.get(label, 0.0) + s / len(self.ops)
        idle: dict = {}
        for evs in self.ops.values():
            g = gaps([(e.start, e.end) for e in evs], self.window)
            for label, s in self.host_during(g).items():
                idle[label] = idle.get(label, 0.0) + s / len(self.ops)
        return {"device_ops": _top(ops, top), "idle_gaps": _top(idle, top)}

    def host_during(self, intervals) -> dict:
        """Seconds of the sorted, disjoint ``intervals`` that each harness
        span covers; the rest under ``NO_SPAN``.  The spans come from one
        thread, so they do not overlap one another."""
        spans = sorted(self.spans, key=lambda e: e.start)
        out: dict = {}
        j = 0
        for lo, hi in intervals:
            while j < len(spans) and spans[j].end <= lo:
                j += 1
            rest = hi - lo
            k = j
            while k < len(spans) and spans[k].start < hi:
                o = min(spans[k].end, hi) - max(spans[k].start, lo)
                if o > 0:
                    out[spans[k].name] = out.get(spans[k].name, 0.0) + o / 1e9
                    rest -= o
                k += 1
            if rest > 0:
                out[NO_SPAN] = out.get(NO_SPAN, 0.0) + rest / 1e9
        return out


def _top(d: dict, n: int) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _ops(plane) -> list:
    """The plane's ops, each with the program it ran in."""
    lines = {line.name: line for line in plane.lines}
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                   e.name.split("(", 1)[0])
                  for e in (lines[MODULES_LINE].events
                            if MODULES_LINE in lines else []))
    starts = [m[0] for m in mods]
    out = []
    for e in (lines[OPS_LINE].events if OPS_LINE in lines else []):
        k = bisect.bisect_right(starts, e.start_ns) - 1
        mod = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] else ""
        out.append(Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         mod))
    return out


def from_profile(profile, device_ids=None) -> Trace:
    """A ``jax.profiler.ProfileData`` as a ``Trace`` of the chips in
    ``device_ids`` (all chips when None)."""
    ops, spans = {}, []
    window = None
    for plane in profile.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX):
            dev = int(name[len(DEVICE_PREFIX):])
            if device_ids is not None and dev not in device_ids:
                continue
            ops[dev] = _ops(plane)
        elif name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    ev = Event(e.name, e.start_ns,
                               e.start_ns + e.duration_ns)
                    if e.name == WINDOW_SPAN:
                        window = (ev.start, ev.end)
                    else:
                        spans.append(ev)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    return Trace(ops, spans, window)


def load(log_dir, device_ids=None) -> Trace:
    """The trace ``jax.profiler`` wrote under ``log_dir``."""
    import jax

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(str(files[-1])),
                        device_ids)
