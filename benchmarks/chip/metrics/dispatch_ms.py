"""Mean milliseconds per call of the program's dispatch span,
``repro.transcode.dispatch`` (bulk) or ``repro.ragged.dispatch``
(ingest): converting the arguments and launching the jitted program, up
to the return of its call (not to its result), from the profiler
trace."""

import progspans


def read(obs):
    host = progspans.for_run(obs)
    return None if host is None else host.mean_ms(*progspans.DISPATCH_SPANS)
