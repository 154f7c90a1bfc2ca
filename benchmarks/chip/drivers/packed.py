"""Training-data ingest on one chip: one packed launch per batch.

Each call takes the next batch of documents from the pool (``ingest``),
packs it with the program's ``packing.pack_documents`` (in the window:
packing is the program's work), copies it to the chip, and runs the
configuration's ragged entry point.  The output stays on the device, as
an ingest step would hand it on; the call is complete once it is ready
there.
"""

from __future__ import annotations

import common
import ingest


def setup(config, traffic, seed, devices, reference):
    st = ingest.make_state(config, traffic, seed, devices, reference)
    # Every batch has one shape, so two calls load every program.
    for _ in range(2):
        step(st, common.NoSpans())
    st.restart()
    return st


def step(st: ingest.State, spans) -> common.Record:
    import jax

    idx = st.batches.next()
    with spans.span("bench.pack"):
        packed = ingest.pack(st, idx)
    with spans.span("bench.h2d"):
        args = jax.device_put(packed, st.devices[0])
        jax.block_until_ready(args)
    with spans.span("bench.call"):
        r = st.fn(*args, **st.kwargs)
        jax.block_until_ready(r)
    st.kept.offer((idx, r))
    return ingest.record(st, idx)


check = ingest.check
