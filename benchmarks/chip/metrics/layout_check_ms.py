"""Mean milliseconds per batch of the program's ``repro.ragged.check_layout``
span: converting the packed arguments and checking their layout on the
host, which reads ``offsets`` and ``lengths`` back from the device, from
the profiler trace."""

import progspans


def read(obs):
    host = progspans.for_run(obs)
    return None if host is None else host.mean_ms(progspans.LAYOUT_CHECK_SPAN)
