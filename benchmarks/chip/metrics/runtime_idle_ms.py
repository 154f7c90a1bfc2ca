"""Mean milliseconds per call of the device's idle time inside the
harness's ``bench.call`` span that no ``repro.*`` span covers, from the
profiler trace: after the program has returned, the wait for the device
to start the launched program and, once it is done, for the runtime to
report the result ready."""

import progspans


def read(obs):
    host = progspans.for_run(obs)
    n = 0 if host is None else host.calls()
    if not n:
        return None
    idle = host.idle_by_path(obs.trace)
    return idle.get((progspans.CALL_SPAN,), 0.0) / n * 1e3
