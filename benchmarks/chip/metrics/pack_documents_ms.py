"""Mean milliseconds per batch of the program's ``repro.pack_documents``
span: the program's share of the harness's ``bench.pack``, without the
harness's copy into the budget-sized buffer, from the profiler trace."""

import progspans


def read(obs):
    host = progspans.for_run(obs)
    return None if host is None else host.mean_ms(progspans.PACK_SPAN)
