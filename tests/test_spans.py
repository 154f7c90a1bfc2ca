"""The program's profiler spans: under a profiler session each entry point
writes the spans ``repro.spans`` names, once per call and nested as the
call is, on the host thread that made the call."""

import pathlib

import numpy as np
import pytest

import jax

from repro import spans
from repro.core import packing
from repro.core import transcode as tc

NAMES = sorted(v for k, v in vars(spans).items() if k.isupper())


def _traced(tmp_path, fn):
    """``fn()`` once to compile, then once under a profiler session: the
    host events of the traced call, as ``(name, start, end)`` by start."""
    jax.block_until_ready(fn())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    prof = jax.profiler.ProfileData.from_file(str(path))
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in prof.planes if p.name == "/host:CPU"
           for line in p.lines for e in line.events]
    return sorted(evs, key=lambda e: (e[1], -e[2]))


def _only(evs, name):
    got = [e for e in evs if e[0] == name]
    assert len(got) == 1, f"{name}: {len(got)} spans"
    return got[0]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_span_names_are_the_programs_own():
    assert NAMES and len(NAMES) == len(set(NAMES))
    assert all(n.startswith("repro.") for n in NAMES)


def test_transcode_writes_its_spans(tmp_path):
    x = np.frombuffer("abc é一\U0001F600 ".encode() * 40, np.uint8)
    evs = _traced(tmp_path, lambda: tc.transcode(x, "utf16",
                                                 src_format="utf8"))
    outer = _only(evs, spans.TRANSCODE)
    inner = _only(evs, spans.TRANSCODE_DISPATCH)
    assert _inside(inner, outer)
    # The jitted program's dispatch happens inside the dispatch span.
    assert any(n == "PjitFunction(_transcode_impl)" and _inside((n, s, e),
                                                                inner)
               for n, s, e in evs)
    assert not {spans.RAGGED_TRANSCODE, spans.PACK_DOCUMENTS} & {
        e[0] for e in evs}


def test_ragged_transcode_and_packing_write_their_spans(tmp_path):
    docs = [np.frombuffer(b"hello \xff world", np.uint8),
            np.frombuffer("Ж一 ".encode() * 300, np.uint8)]

    def call():
        pk = packing.pack_documents(docs)
        return tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                                   errors="replace")

    evs = _traced(tmp_path, call)
    pack = _only(evs, spans.PACK_DOCUMENTS)
    outer = _only(evs, spans.RAGGED_TRANSCODE)
    check = _only(evs, spans.RAGGED_CHECK_LAYOUT)
    dispatch = _only(evs, spans.RAGGED_DISPATCH)
    assert pack[2] <= outer[1]
    assert _inside(check, outer) and _inside(dispatch, outer)
    assert check[2] <= dispatch[1]
    assert spans.TRANSCODE not in {e[0] for e in evs}


def test_unknown_ragged_strategy_is_still_refused():
    pk = packing.pack_documents([np.frombuffer(b"abc", np.uint8)])
    with pytest.raises(ValueError, match="unknown strategy"):
        tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                            strategy="nope")
