"""Share of the window in which no operation ran on the chip, from the
profiler trace: 1 minus the union of the chip's op intervals over the
window, averaged over the cell's chips."""

import devtrace


def read(obs):
    if obs.trace is None or not obs.trace.ops:
        return None
    return devtrace.idle_pct(obs.trace.busy_s(), obs.trace.window_s)
