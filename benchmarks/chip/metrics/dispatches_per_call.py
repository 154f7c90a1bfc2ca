"""Jitted programs launched per call of the program's entry point: the
outermost ``PjitFunction(...)`` dispatch events inside the
``repro.transcode`` / ``repro.ragged_transcode`` spans, over the number
of those spans, from the profiler trace.  Each program is one more host
dispatch and one more device launch."""

import progspans


def read(obs):
    host = progspans.for_run(obs)
    return None if host is None else host.dispatches_per_call()
