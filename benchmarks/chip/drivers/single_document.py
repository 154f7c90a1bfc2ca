"""Bulk conversion of documents held on the host: one buffer per call.

Each call copies one host buffer to the chip, runs the configuration's
single-document entry point on it, and reads the result back whole
(``buffer``, ``count``, ``status``); the caller slices the buffer to
``count`` on the host.  The traffic's pool of buffers is made from the
seed in set-up and cycled in a seeded order: the transcoder keeps no
state between calls, so a repeated buffer flatters nothing.

A mix (``traffic/<name>.json``) gives the Table 4 ``table`` and
``profiles`` and the ``pool`` size.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import common
import corpus


@dataclass
class State:
    fn: object
    kwargs: dict
    device: object
    reference: object
    config: dict
    bufs: list             # pool slot -> host uint8 buffer
    chars: list            # pool slot -> characters
    kbytes: list           # pool slot -> bytes its launch needs, 0: none
    kept: list = field(default_factory=list)   # slot -> Reservoir
    i: int = 0             # calls made; call i uses slot i % len(bufs)


def make_pool(config: dict, traffic: dict, seed: int, dst_unit: int):
    """The seeded pool: one buffer per profile, cycled over the pool's
    slots in a seeded order."""
    size = config["call_bytes"]
    base = {p: corpus.text(traffic["table"], p, size, seed, "bulk")[0]
            for p in traffic["profiles"]}
    rng = corpus.rng_for(seed, "bulk-order", *traffic["profiles"])
    n = traffic["pool"]
    slots = [traffic["profiles"][k % len(base)] for k in range(n)]
    bufs = [base[slots[k]] for k in rng.permutation(n)]
    counts = {}                  # per distinct buffer
    for b in bufs:
        if id(b) not in counts:
            c, units = common.utf8_counts(b)
            ascii_only = int(b.max()) < 0x80
            counts[id(b)] = (c, 0 if ascii_only else common.kernel_bytes(
                b.shape[0], units, dst_unit))
    return (bufs, [counts[id(b)][0] for b in bufs],
            [counts[id(b)][1] for b in bufs])


def setup(config, traffic, seed, devices, reference):
    fn = common.entry(config)
    kwargs = dict(src_format=config["src_format"],
                  strategy=config["strategy"][str(len(devices))],
                  validate=config["validate"], errors=config["errors"])
    unit = np.dtype(reference.CODECS[config["dst_format"]][2]).itemsize
    bufs, chars, kbytes = make_pool(config, traffic, seed, unit)
    st = State(fn, kwargs, devices[0], reference, config, bufs, chars,
               kbytes)
    # Warm-up: every distinct buffer once through the whole call, so every
    # program the window runs is compiled and loaded.
    for slot in {id(b): k for k, b in enumerate(bufs)}.values():
        _call(st, slot, common.NoSpans())
    rng = corpus.rng_for(seed, "bulk-sample")
    st.kept = [common.Reservoir(1, rng) for _ in bufs]
    st.i = 0
    return st


def _call(st: State, slot: int, spans):
    import jax

    with spans.span("bench.h2d"):
        x = jax.device_put(st.bufs[slot], st.device)
        x.block_until_ready()
    with spans.span("bench.call"):
        r = st.fn(x, st.config["dst_format"], **st.kwargs)
        jax.block_until_ready(r)
    with spans.span("bench.d2h"):
        buf, count, status = jax.device_get((r.buffer, r.count, r.status))
    return buf, int(count), int(status)


def step(st: State, spans) -> common.Record:
    slot = st.i % len(st.bufs)
    st.i += 1
    out = _call(st, slot, spans)
    st.kept[slot].offer(out)
    return common.Record(st.chars[slot], st.kbytes[slot])


def check(st: State) -> dict:
    """Each slot's sampled result against the reference."""
    tally = common.Tally()
    for slot, res in enumerate(st.kept):
        for buf, count, status in res.items:
            units, want = st.reference.transcode(
                st.bufs[slot].tobytes(), st.config["src_format"],
                st.config["dst_format"], st.config["errors"])
            tally.document(units, want, buf, count, status)
    return tally.checks()
