"""Differential fuzz harness: every transcoder cell vs CPython codecs.

Seeded random + mutated corpora are checked **byte-exactly** against
CPython's ``codecs`` machinery across every
(direction x strategy x errors) cell — the single-launch ``onepass``
kernel (DESIGN.md §9) rides every sweep next to the pure-jnp
``blockparallel`` reference — including the ragged packed-batch path
(one device and sharded) with per-document statuses:

  * valid streams: ``buffer[:count]`` must equal the CPython transcode
    bit for bit, ``status`` must be -1;
  * invalid streams under ``errors="strict"``: ``status`` must equal
    Python's ``UnicodeDecodeError.start`` (unit-relative for UTF-16),
    and every strategy must agree with the blockparallel reference on
    (buffer, count) — the speculative output is defined cross-strategy,
    not by CPython;
  * invalid streams under ``errors="replace"``: the output must equal
    CPython's ``errors="replace"`` transcode bit for bit (U+FFFD per
    maximal subpart) and ``status`` the first substitution offset.

The seed is fixed (override with ``REPRO_FUZZ_SEED``) so CI runs are
reproducible; the boundary-adversarial generators place truncated leads
and surrogate pairs so they straddle VMEM-tile boundaries AND packed
document boundaries, with empty and all-ASCII documents mixed into the
same ragged batch.

The ``parity`` tests are the interpret-vs-compiled gate: on CPU they pin
the Pallas interpreter kernels to the XLA-compiled blockparallel
reference; on a TPU backend the same tests additionally run the
Mosaic-compiled kernels against the interpreter.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core import transcode as tc
from repro.data import synthetic
from repro.kernels import onepass_transcode as op
from repro.kernels import ragged_transcode as rt
from repro.kernels import runtime

SEED = int(os.environ.get("REPRO_FUZZ_SEED", "20260801"))

CAP8 = 1536    # fixed single-doc capacities: one compilation per cell
CAP16 = 1280

LANGS = ["latin", "arabic", "chinese", "emoji", "korean", "hebrew"]

# Bytes that exercise every UTF-8 error class: continuations, C0/C1
# (never valid leads), constrained-second-byte leads (E0/ED/F0/F4), F5+.
ADVERSARIAL8 = np.array([0x41, 0x7F, 0x80, 0x9F, 0xA0, 0xBF, 0xC0, 0xC1,
                         0xC2, 0xDF, 0xE0, 0xED, 0xEE, 0xF0, 0xF4, 0xF5,
                         0xFF, 0x90, 0x8F, 0x20], np.uint8)


# ---------------------------------------------------------------------------
# CPython oracles


def _py8(raw: bytes):
    """(utf16_units, exc.start) for a UTF-8 stream (-1 when valid)."""
    try:
        return (np.frombuffer(raw.decode("utf-8").encode("utf-16-le"),
                              np.uint16), -1)
    except UnicodeDecodeError as e:
        return None, e.start


def _py8_replace(raw: bytes):
    return np.frombuffer(
        raw.decode("utf-8", "replace").encode("utf-16-le"), np.uint16)


def _py16(units: np.ndarray):
    """(utf8_bytes, exc.start // 2) for a UTF-16LE stream."""
    try:
        return (np.frombuffer(
            units.astype(np.uint16).tobytes().decode("utf-16-le")
            .encode("utf-8"), np.uint8), -1)
    except UnicodeDecodeError as e:
        return None, e.start // 2


def _py16_replace(units: np.ndarray):
    return np.frombuffer(
        units.astype(np.uint16).tobytes().decode("utf-16-le", "replace")
        .encode("utf-8"), np.uint8)


# ---------------------------------------------------------------------------
# Corpus generators


def _utf8_case(rng, trial, cap=CAP8):
    """One seeded UTF-8 stream: valid, mutated-valid, pure-random or
    adversarial-alphabet, in rotation."""
    buf = np.zeros(cap, np.uint8)
    kind = trial % 4
    if kind in (0, 1):
        b = synthetic.utf8_array(LANGS[trial % len(LANGS)], 400,
                                 seed=SEED + trial)[:cap]
        buf[: len(b)] = b
        n = len(b)
        if kind == 1:       # mutate 1-4 bytes of a valid stream
            k = int(rng.integers(1, 5))
            buf[rng.integers(0, max(n, 1), k)] = rng.integers(0, 256, k)
    elif kind == 2:
        n = int(rng.integers(1, cap))
        buf[:n] = rng.integers(0, 256, n)
    else:
        n = int(rng.integers(1, 96))
        buf[:n] = rng.choice(ADVERSARIAL8, n)
    return buf, n


def _utf16_case(rng, trial, cap=CAP16):
    buf = np.zeros(cap, np.uint16)
    kind = trial % 3
    if kind == 0:
        u = synthetic.utf16_units(LANGS[trial % len(LANGS)], 400,
                                  seed=SEED + trial)[:cap]
        buf[: len(u)] = u
        n = len(u)
    elif kind == 1:
        u = synthetic.utf16_units("emoji", 300, seed=SEED + trial)[:cap]
        buf[: len(u)] = u
        n = len(u)
        k = int(rng.integers(1, 4))   # surrogate-heavy corruption
        buf[rng.integers(0, max(n, 1), k)] = rng.integers(0xD800, 0xE000, k)
    else:
        n = int(rng.integers(1, cap))
        buf[:n] = rng.integers(0, 1 << 16, n)
    return buf, n


def boundary_documents8():
    """UTF-8 documents engineered so multi-byte characters and truncated
    leads straddle (a) the 1024-byte VMEM tile boundary inside one
    document and (b) the packed document boundary — plus empty and
    all-ASCII documents mixed in, per the ragged batch contract."""
    docs = []
    probes = [b"\xf0\x9f\x92\xa9", b"\xe4\xb8\xad", b"\xc3\xa9",
              b"\xf0\x9f\x92", b"\xe4\xb8", b"\xc3", b"\xed\xa0\x80"]
    tile = packing.TILE
    for k, probe in enumerate(probes):
        # (a) straddle this doc's own internal tile boundary
        pos = tile - 2 + (k % 4)
        doc = np.full(tile + 64, 0x41, np.uint8)
        doc[pos: pos + len(probe)] = np.frombuffer(probe, np.uint8)
        docs.append(doc)
        # (b) end the document EXACTLY at its tile boundary with the
        # probe's tail truncated by the document end: the next packed
        # document starts in the adjacent tile, and its leading bytes
        # must never complete this document's sequence.
        doc = np.full(tile, 0x41, np.uint8)
        doc[tile - len(probe):] = np.frombuffer(probe, np.uint8)
        docs.append(doc)
        # ...followed by a document that BEGINS with continuation bytes
        # (the exact bytes that would complete the truncated lead).
        docs.append(np.frombuffer(b"\xa9\x80\x80 tail", np.uint8))
    docs.append(np.zeros(0, np.uint8))                       # empty
    docs.append(np.full(200, 0x2E, np.uint8))                # all-ASCII
    docs.append(np.zeros(0, np.uint8))                       # empty again
    return docs


def boundary_documents16():
    """UTF-16 analogue: surrogate pairs straddling tile boundaries and
    lone high surrogates truncated at a document end whose packed
    neighbour starts with a low surrogate."""
    tile = packing.TILE
    docs = []
    # pair straddles the doc's internal tile boundary
    doc = np.full(tile + 32, 0x41, np.uint16)
    doc[tile - 1: tile + 1] = [0xD83C, 0xDF89]
    docs.append(doc)
    # doc ends at its tile boundary on a lone high surrogate...
    doc = np.full(tile, 0x41, np.uint16)
    doc[-1] = 0xD800
    docs.append(doc)
    # ...next doc starts with the low half that must NOT pair with it.
    docs.append(np.frombuffer(
        np.array([0xDC00, 0x42, 0x43], np.uint16).tobytes(),
        np.uint16))
    docs.append(np.zeros(0, np.uint16))                      # empty
    docs.append(np.full(100, 0x41, np.uint16))               # all-ASCII
    return docs


# ---------------------------------------------------------------------------
# Single-document cells: (strategy x errors) vs CPython


def _check8_strict(buf, n, strategy):
    want, want_pos = _py8(bytes(buf[:n]))
    x = jnp.asarray(buf if strategy == "onepass"
                    else buf.astype(np.int32))
    out, cnt, status = tc.transcode_utf8_to_utf16(x, n, strategy=strategy)
    assert int(status) == want_pos
    got = np.asarray(out)[: min(int(cnt), out.shape[0])]
    if want_pos < 0:
        assert int(cnt) == len(want)
        assert np.array_equal(got, want)
    elif strategy != "windowed":
        # The speculative output on an invalid stream is defined
        # cross-strategy for the block-parallel family; the serial
        # windowed walker resynchronizes differently and only pins
        # ``status`` there.
        ref = tc.utf8_to_utf16(jnp.asarray(buf.astype(np.int32)), n)
        assert int(cnt) == int(ref.count)
        assert np.array_equal(got, np.asarray(ref.buffer)[: len(got)])


def _check8_replace(buf, n, strategy):
    want = _py8_replace(bytes(buf[:n]))
    _, want_pos = _py8(bytes(buf[:n]))
    x = jnp.asarray(buf if strategy == "onepass"
                    else buf.astype(np.int32))
    out, cnt, status = tc.transcode_utf8_to_utf16(x, n, strategy=strategy,
                                                  errors="replace")
    assert int(status) == want_pos
    assert int(cnt) == len(want)
    assert np.array_equal(np.asarray(out)[: int(cnt)], want)


@pytest.mark.parametrize("strategy", ["onepass", "blockparallel"])
def test_differential_utf8_to_utf16(strategy):
    rng = np.random.default_rng(SEED)
    for trial in range(20):
        buf, n = _utf8_case(rng, trial)
        _check8_strict(buf, n, strategy)
        _check8_replace(buf, n, strategy)


def test_differential_utf8_to_utf16_windowed():
    """The serial paper baseline: strict-only cell of the matrix."""
    rng = np.random.default_rng(SEED + 1)
    for trial in range(8):
        buf, n = _utf8_case(rng, trial)
        _check8_strict(buf, n, "windowed")


def _check16_strict(buf, n, strategy):
    want, want_pos = _py16(buf[:n])
    x = jnp.asarray(buf if strategy == "onepass"
                    else buf.astype(np.int32))
    out, cnt, status = tc.transcode_utf16_to_utf8(x, n, strategy=strategy)
    assert int(status) == want_pos
    got = np.asarray(out)[: min(int(cnt), out.shape[0])]
    if want_pos < 0:
        assert int(cnt) == len(want)
        assert np.array_equal(got, want)
    elif strategy != "windowed":
        ref = tc.utf16_to_utf8(jnp.asarray(buf.astype(np.int32)), n)
        assert int(cnt) == int(ref.count)
        assert np.array_equal(got, np.asarray(ref.buffer)[: len(got)])


def _check16_replace(buf, n, strategy):
    want = _py16_replace(buf[:n])
    _, want_pos = _py16(buf[:n])
    x = jnp.asarray(buf if strategy == "onepass"
                    else buf.astype(np.int32))
    out, cnt, status = tc.transcode_utf16_to_utf8(x, n, strategy=strategy,
                                                  errors="replace")
    assert int(status) == want_pos
    assert int(cnt) == len(want)
    assert np.array_equal(np.asarray(out)[: int(cnt)], want)


@pytest.mark.parametrize("strategy", ["onepass", "blockparallel"])
def test_differential_utf16_to_utf8(strategy):
    rng = np.random.default_rng(SEED + 2)
    for trial in range(16):
        buf, n = _utf16_case(rng, trial)
        _check16_strict(buf, n, strategy)
        _check16_replace(buf, n, strategy)


def test_differential_utf16_to_utf8_windowed():
    rng = np.random.default_rng(SEED + 3)
    for trial in range(6):
        buf, n = _utf16_case(rng, trial)
        _check16_strict(buf, n, "windowed")


# ---------------------------------------------------------------------------
# Ragged packed-batch cells: per-document statuses vs CPython


def _check_ragged8(docs, errors, strategy="onepass"):
    pk = packing.pack_documents(docs, dtype=np.uint8)
    res = tc.ragged_utf8_to_utf16(pk.data, pk.offsets, pk.lengths,
                                  errors=errors, strategy=strategy)
    for d, doc in enumerate(docs):
        raw = bytes(np.asarray(doc, np.uint8))
        _, want_pos = _py8(raw)
        assert int(res.statuses[d]) == want_pos, d
        lo = int(res.offsets[d])
        got = np.asarray(res.buffer)[lo: lo + int(res.counts[d])]
        if errors == "replace":
            want = _py8_replace(raw)
            assert int(res.counts[d]) == len(want), d
            assert np.array_equal(got, want), d
        elif want_pos < 0:
            want, _ = _py8(raw)
            assert int(res.counts[d]) == len(want), d
            assert np.array_equal(got, want), d
        # Acceptance: bit-identical to the single-document transcoder
        # (buffer, count, status) on the fuzz corpus.  Capacity = the
        # doc's tile span, so single-doc compilations are shared.
        span = max(int(pk.offsets[d + 1] - pk.offsets[d]), 1)
        buf = np.zeros(span, np.uint8)
        buf[: len(raw)] = np.frombuffer(raw, np.uint8)
        single = op.transcode_onepass(jnp.asarray(buf), len(raw),
                                      src="utf8", dst="utf16",
                                      errors=errors)
        assert int(res.counts[d]) == int(single.count), d
        assert int(res.statuses[d]) == int(single.status), d
        k = min(int(single.count), span)
        assert np.array_equal(got[:k], np.asarray(single.buffer)[:k]), d


def _check_ragged16(docs, errors, strategy="onepass"):
    pk = packing.pack_documents(docs, dtype=np.uint16)
    res = tc.ragged_utf16_to_utf8(pk.data, pk.offsets, pk.lengths,
                                  errors=errors, strategy=strategy)
    for d, doc in enumerate(docs):
        u = np.asarray(doc, np.uint16)
        _, want_pos = _py16(u)
        assert int(res.statuses[d]) == want_pos, d
        lo = int(res.offsets[d])
        got = np.asarray(res.buffer)[lo: lo + int(res.counts[d])]
        if errors == "replace":
            want = _py16_replace(u)
            assert int(res.counts[d]) == len(want), d
            assert np.array_equal(got, want), d
        elif want_pos < 0:
            want, _ = _py16(u)
            assert int(res.counts[d]) == len(want), d
            assert np.array_equal(got, want), d
        span = max(int(pk.offsets[d + 1] - pk.offsets[d]), 1)
        buf = np.zeros(span, np.uint16)
        buf[: len(u)] = u
        single = op.transcode_onepass(jnp.asarray(buf), len(u),
                                      src="utf16", dst="utf8",
                                      errors=errors)
        assert int(res.counts[d]) == int(single.count), d
        assert int(res.statuses[d]) == int(single.status), d
        k = min(int(single.count), 3 * span)
        assert np.array_equal(got[:k], np.asarray(single.buffer)[:k]), d


@pytest.mark.parametrize("strategy", tc.RAGGED_STRATEGIES)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_differential_ragged_utf8_fuzz(errors, strategy):
    rng = np.random.default_rng(SEED + 4)
    for batch in range(4):
        docs = []
        for t in range(6):
            buf, n = _utf8_case(rng, batch * 6 + t, cap=1400)
            docs.append(buf[:n])
        docs.insert(2, np.zeros(0, np.uint8))            # empty mixed in
        docs.insert(4, np.full(77, 0x41, np.uint8))      # all-ASCII
        _check_ragged8(docs, errors, strategy)


@pytest.mark.parametrize("strategy", tc.RAGGED_STRATEGIES)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_differential_ragged_utf16_fuzz(errors, strategy):
    rng = np.random.default_rng(SEED + 5)
    for batch in range(3):
        docs = []
        for t in range(5):
            buf, n = _utf16_case(rng, batch * 5 + t, cap=1200)
            docs.append(buf[:n])
        docs.insert(1, np.zeros(0, np.uint16))
        _check_ragged16(docs, errors, strategy)


@pytest.mark.parametrize("strategy", tc.RAGGED_STRATEGIES)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_differential_ragged_boundary_adversarial_utf8(errors, strategy):
    _check_ragged8(boundary_documents8(), errors, strategy)


@pytest.mark.parametrize("strategy", tc.RAGGED_STRATEGIES)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_differential_ragged_boundary_adversarial_utf16(errors, strategy):
    _check_ragged16(boundary_documents16(), errors, strategy)


def test_boundary_probes_also_hit_single_doc_strategies():
    """The tile-straddling probes, replayed through every single-doc
    strategy (padding each doc to the shared fixed capacity)."""
    for doc in boundary_documents8():
        n = len(doc)
        if n == 0 or n > CAP8:
            continue
        buf = np.zeros(CAP8, np.uint8)
        buf[:n] = doc
        for strategy in ("onepass", "blockparallel"):
            _check8_strict(buf, n, strategy)
            _check8_replace(buf, n, strategy)


# ---------------------------------------------------------------------------
# Codec-matrix cells: every new (src, dst) pair vs CPython codecs.
#
# The oracle is CPython end to end: decode with the source codec, encode
# with the destination codec.  Status semantics: the first input-element
# offset where a substitution would occur — a decode error
# (``UnicodeDecodeError.start`` scaled to units) or, for Latin-1 egress,
# an unencodable code point (``UnicodeEncodeError.start`` mapped back to
# source elements through the strictly-decodable prefix).

_CODEC = {"utf8": "utf-8", "utf16": "utf-16-le", "utf32": "utf-32-le",
          "latin1": "latin-1"}
# Explicit little-endian dtypes: the oracle's wire form must not depend
# on host endianness.
_WIRE_DT = {"utf8": np.dtype(np.uint8), "utf16": np.dtype("<u2"),
            "utf32": np.dtype("<u4"), "latin1": np.dtype(np.uint8)}

MATRIX_NEW_PAIRS = [("utf8", "utf32"), ("utf32", "utf8"),
                    ("utf16", "utf32"), ("utf32", "utf16"),
                    ("latin1", "utf8"), ("utf8", "latin1")]


def _wire_bytes(src, arr):
    return np.ascontiguousarray(arr).astype(_WIRE_DT[src]).tobytes()


def _from_text(fmt, text):
    return np.frombuffer(text.encode(_CODEC[fmt]), _WIRE_DT[fmt])


def _expected_status(src, dst, arr):
    """Our status semantics from CPython oracles (see section comment)."""
    raw = _wire_bytes(src, arr)
    width = _WIRE_DT[src].itemsize
    try:
        text = raw.decode(_CODEC[src])
        dec_pos = -1
    except UnicodeDecodeError as e:
        text = raw[: e.start].decode(_CODEC[src])  # strictly-valid prefix
        dec_pos = e.start // width
    if dst == "latin1":
        for j, ch in enumerate(text):
            if ord(ch) > 0xFF:
                return len(text[:j].encode(_CODEC[src])) // width
    return dec_pos


CAPM = 1280   # fixed matrix-cell capacity: one compilation per cell


def _matrix_transcode(src, dst, arr, strategy, errors):
    buf = np.zeros(max(CAPM, len(arr)), _WIRE_DT[src])
    buf[: len(arr)] = arr
    x = jnp.asarray(buf) if strategy == "onepass" \
        else jnp.asarray(buf.astype(np.int64).astype(np.int32))
    return tc.transcode(x, dst, src_format=src, n_valid=len(arr),
                        strategy=strategy, errors=errors)


def _check_matrix_cell(src, dst, arr, strategy):
    raw = _wire_bytes(src, arr)
    want_pos = _expected_status(src, dst, arr)

    # strict: byte-exact on valid streams, status always; the
    # speculative invalid-stream output is defined cross-strategy.
    out, cnt, status = _matrix_transcode(src, dst, arr, strategy, "strict")
    assert int(status) == want_pos, (src, dst, strategy, int(status))
    got = np.asarray(out)[: min(int(cnt), out.shape[0])]
    if want_pos < 0:
        want = _from_text(dst, raw.decode(_CODEC[src]))
        assert int(cnt) == len(want), (src, dst, strategy)
        assert np.array_equal(got.astype(np.int64), want), \
            (src, dst, strategy)
    else:
        ref = _matrix_transcode(src, dst, arr, "blockparallel", "strict")
        assert int(cnt) == int(ref.count), (src, dst, strategy)
        assert np.array_equal(
            got.astype(np.int64),
            np.asarray(ref.buffer)[: len(got)].astype(np.int64)), \
            (src, dst, strategy)

    # replace: byte-exact vs CPython's chained replace semantics.
    want = _from_text(dst, raw.decode(_CODEC[src], "replace")) \
        if dst != "latin1" else np.frombuffer(
            raw.decode(_CODEC[src], "replace")
            .encode("latin-1", "replace"), np.uint8)
    out, cnt, status = _matrix_transcode(src, dst, arr, strategy, "replace")
    assert int(status) == want_pos, (src, dst, strategy)
    assert int(cnt) == len(want), (src, dst, strategy)
    assert np.array_equal(
        np.asarray(out)[: int(cnt)].astype(np.int64), want), \
        (src, dst, strategy)


def _matrix_case(src, rng, trial, cap):
    """One seeded source buffer for a matrix-cell fuzz trial."""
    if src == "utf8":
        buf, n = _utf8_case(rng, trial, cap=cap)
        return buf[:n]
    if src == "utf16":
        buf, n = _utf16_case(rng, trial, cap=cap)
        return buf[:n]
    if src == "utf32":
        n = int(rng.integers(1, cap))
        kind = trial % 3
        if kind == 0:   # valid code points from a corpus
            text = bytes(synthetic.utf8_array(
                LANGS[trial % len(LANGS)], 400,
                seed=SEED + trial)).decode("utf-8")[:n]
            return np.array([ord(c) for c in text], np.uint32)
        cps = rng.integers(0, 0x110000, n).astype(np.uint32)
        if kind == 2:   # sprinkle surrogates / too-large / huge garbage
            k = int(rng.integers(1, 6))
            where = rng.integers(0, n, k)
            cps[where] = rng.choice(
                np.array([0xD800, 0xDFFF, 0x110000, 0xFFFFFFFF, 0xDC00],
                         np.uint32), k)
        return cps
    # latin1: any byte stream is valid
    n = int(rng.integers(1, cap))
    return rng.integers(0, 256, n).astype(np.uint8)


@pytest.mark.parametrize("src,dst", MATRIX_NEW_PAIRS)
@pytest.mark.parametrize("strategy", ["onepass", "blockparallel"])
def test_differential_matrix_cells(src, dst, strategy):
    rng = np.random.default_rng(SEED + 8)
    for trial in range(8):
        arr = _matrix_case(src, rng, trial, cap=CAPM)
        _check_matrix_cell(src, dst, arr, strategy)


def test_differential_matrix_boundary_adversarial():
    """Matrix cells with errors engineered to straddle the 1024-lane
    VMEM tile boundary (the cross-tile claimed-byte chain must agree
    with CPython at every offset, for every endpoint)."""
    probes8 = [b"\xf0\x9f\x92", b"\xc3", b"\xed\xa0\x80", b"\xc3\xa9"]
    for probe in probes8:
        for pos in (1021, 1022, 1023, 1024):
            buf = np.full(2048, 0x41, np.uint8)
            buf[pos: pos + len(probe)] = np.frombuffer(probe, np.uint8)
            for dst in ("utf32", "latin1"):
                _check_matrix_cell("utf8", dst, buf, "onepass")
    # utf32 source: a bad scalar at the tile boundary
    for bad in (0xD800, 0x110000):
        cps = np.full(1100, 0x41, np.uint32)
        cps[1023] = bad
        for dst in ("utf8", "utf16"):
            _check_matrix_cell("utf32", dst, cps, "onepass")
    # latin1 source: high bytes straddling the boundary widen to 2-byte
    # UTF-8 sequences across it
    b = np.full(1100, 0x41, np.uint8)
    b[1020:1028] = 0xE9
    _check_matrix_cell("latin1", "utf8", b, "onepass")
    # utf8 -> latin1: an unencodable (but valid UTF-8) char at the
    # boundary must locate at its lead byte
    s = "A" * 1022 + "中" + "B" * 64
    arr = np.frombuffer(s.encode("utf-8"), np.uint8)
    _check_matrix_cell("utf8", "latin1", arr, "onepass")


@pytest.mark.parametrize("src,dst", [("utf8", "utf32"), ("latin1", "utf8"),
                                     ("utf8", "latin1")])
@pytest.mark.parametrize("strategy", tc.RAGGED_STRATEGIES)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_differential_matrix_ragged(src, dst, errors, strategy):
    """Ragged matrix cells: per-document parity with the single-document
    transcoder and with the CPython oracle."""
    rng = np.random.default_rng(SEED + 9)
    docs = [_matrix_case(src, rng, t, cap=1200) for t in range(5)]
    docs.insert(1, np.zeros(0, _WIRE_DT[src]))           # empty mixed in
    docs.insert(3, np.full(80, 0x41, _WIRE_DT[src]))     # all-ASCII
    pk = packing.pack_documents(docs, dtype=_WIRE_DT[src])
    res = tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                              src_format=src, dst_format=dst,
                              errors=errors, strategy=strategy)
    factor = tc.CAP_FACTOR[(src, dst)]
    for d, doc in enumerate(docs):
        want_pos = _expected_status(src, dst, doc)
        assert int(res.statuses[d]) == want_pos, d
        lo = int(res.offsets[d])
        got = np.asarray(res.buffer)[lo: lo + int(res.counts[d])]
        span = max(int(pk.offsets[d + 1] - pk.offsets[d]), 1)
        buf = np.zeros(span, _WIRE_DT[src])
        buf[: len(doc)] = doc
        single = op.transcode_onepass(jnp.asarray(buf), len(doc), src=src,
                                      dst=dst, errors=errors)
        assert int(res.counts[d]) == int(single.count), d
        assert int(res.statuses[d]) == int(single.status), d
        k = min(int(single.count), factor * span)
        assert np.array_equal(got[:k], np.asarray(single.buffer)[:k]), d


# ---------------------------------------------------------------------------
# Interpret-vs-compiled parity (the CI parity job runs `-k parity`).
#
# On CPU there is no Mosaic: parity means the Pallas INTERPRETER kernels
# against the XLA-COMPILED blockparallel reference (both jitted).  On a
# TPU backend the same tests additionally pin the Mosaic-compiled
# kernels (interpret=False) to the interpreter (interpret=True).


def _on_tpu():
    return jax.default_backend() == "tpu"


def test_parity_resolution_matches_backend():
    assert runtime.resolve_interpret(None) == (not _on_tpu())


def _parity_case(direction, rng, trial):
    """``(buffer, n, src, dst, capacity)`` of one parity trial."""
    if direction == "utf8-utf16":
        buf, n = _utf8_case(rng, trial)
        return buf, n, "utf8", "utf16", CAP8
    buf, n = _utf16_case(rng, trial)
    return buf, n, "utf16", "utf8", 3 * CAP16


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("direction", ["utf8-utf16", "utf16-utf8"])
def test_parity_onepass_interpret_vs_compiled(direction, errors):
    """One-pass kernels (single launch, SMEM carry): interpreter vs the
    XLA-compiled blockparallel reference on CPU; on a TPU backend the
    same test additionally pins the Mosaic-compiled kernel — whose
    sequential-grid carry is the §9 correctness assumption — to the
    interpreter."""
    rng = np.random.default_rng(SEED + 11)
    for trial in range(8):
        buf, n, src, dst, cap = _parity_case(direction, rng, trial)
        interp = op.transcode_onepass(jnp.asarray(buf), n, src=src,
                                      dst=dst, errors=errors,
                                      interpret=True)
        # Compiled reference: the pure-jnp strategy under jit.
        ref = tc.transcode(jnp.asarray(buf.astype(np.int32)), dst,
                           src_format=src, n_valid=n,
                           strategy="blockparallel", errors=errors)
        assert int(interp.count) == int(ref.count), trial
        assert int(interp.status) == int(ref.status), trial
        k = min(int(interp.count), cap)
        assert np.array_equal(np.asarray(interp.buffer)[:k],
                              np.asarray(ref.buffer)[:k]), trial
        if _on_tpu():   # pragma: no cover - TPU-only branch
            comp = op.transcode_onepass(jnp.asarray(buf), n, src=src,
                                        dst=dst, errors=errors,
                                        interpret=False)
            assert int(comp.count) == int(interp.count)
            assert int(comp.status) == int(interp.status)
            assert np.array_equal(np.asarray(comp.buffer),
                                  np.asarray(interp.buffer))


@pytest.mark.parametrize("src,dst", MATRIX_NEW_PAIRS)
def test_parity_onepass_matrix_interpret_vs_compiled(src, dst):
    """Matrix cells through the one-pass kernel: interpreter vs the
    compiled blockparallel reference (and Mosaic vs interpreter on
    TPU)."""
    rng = np.random.default_rng(SEED + 12)
    for trial in range(4):
        arr = _matrix_case(src, rng, trial, cap=1280)
        interp = op.transcode_onepass(jnp.asarray(arr), len(arr), src=src,
                                      dst=dst, interpret=True)
        ref = _matrix_transcode(src, dst, arr, "blockparallel", "strict")
        assert int(interp.count) == int(ref.count), (src, dst, trial)
        assert int(interp.status) == int(ref.status), (src, dst, trial)
        k = int(interp.count)
        assert np.array_equal(
            np.asarray(interp.buffer)[:k].astype(np.int64),
            np.asarray(ref.buffer)[:k].astype(np.int64)), (src, dst, trial)
        if _on_tpu():   # pragma: no cover - TPU-only branch
            comp = op.transcode_onepass(jnp.asarray(arr), len(arr),
                                        src=src, dst=dst, interpret=False)
            assert int(comp.count) == int(interp.count)
            assert int(comp.status) == int(interp.status)
            assert np.array_equal(np.asarray(comp.buffer),
                                  np.asarray(interp.buffer))


@pytest.mark.parametrize("src,dst,docs", [
    ("utf8", "utf16", boundary_documents8),
    ("utf16", "utf8", boundary_documents16)], ids=["utf8", "utf16"])
def test_parity_ragged_onepass_interpret_vs_compiled(src, dst, docs):
    """Ragged one-pass launch: interpreter vs the per-document compiled
    reference (and Mosaic vs interpreter on TPU)."""
    docs = docs()
    dtype = _WIRE_DT[src]
    pk = packing.pack_documents(docs, dtype=dtype)
    interp = rt.transcode_ragged(pk.data, pk.offsets, pk.lengths, src=src,
                                 dst=dst, interpret=True)
    for d, doc in enumerate(docs):
        n = len(doc)
        buf = np.zeros(max(n, 1), dtype)
        buf[:n] = doc
        ref = tc.transcode(jnp.asarray(buf.astype(np.int32)), dst,
                           src_format=src, n_valid=n,
                           strategy="blockparallel")
        assert int(interp.counts[d]) == int(ref.count), d
        assert int(interp.statuses[d]) == int(ref.status), d
    if _on_tpu():   # pragma: no cover - TPU-only branch
        comp = rt.transcode_ragged(pk.data, pk.offsets, pk.lengths,
                                   src=src, dst=dst, interpret=False)
        assert np.array_equal(np.asarray(comp.buffer),
                              np.asarray(interp.buffer))
        assert np.array_equal(np.asarray(comp.counts),
                              np.asarray(interp.counts))
        assert np.array_equal(np.asarray(comp.statuses),
                              np.asarray(interp.statuses))


# ---------------------------------------------------------------------------
# Streaming vs CPython's INCREMENTAL codecs (resumable transcode,
# DESIGN.md §10): the chunked stream at adversarial split points —
# mid-sequence, mid-surrogate-pair, empty chunks, 1-byte chunks — must
# reproduce what ``codecs.getincrementaldecoder`` sees chunk by chunk.


def _random_splits(rng, n, n_cuts):
    """Random cut points, with empties (duplicate cuts) mixed in."""
    cuts = np.sort(rng.integers(0, n + 1, n_cuts))
    bounds = np.concatenate([[0], cuts, [n]])
    return [(int(bounds[i]), int(bounds[i + 1]))
            for i in range(len(bounds) - 1)]


def test_stream_incremental_utf8_replace_fuzz():
    """utf8 -> utf16, errors="replace": each chunk's emission must equal
    the incremental decoder's per-chunk output, encoded to UTF-16LE —
    CPython's own holdback is the oracle for ours."""
    import codecs
    from repro.core import stream as cs
    rng = np.random.default_rng(SEED + 71)
    for trial in range(8):
        b = synthetic.utf8_array(LANGS[trial % len(LANGS)], 200,
                                 seed=SEED + trial).copy()
        bad = rng.integers(0, len(b), 6)
        b[bad] = rng.integers(0x80, 0x100, 6)       # random dirt
        st = cs.stream_init("utf8", "utf16", errors="replace")
        dec = codecs.getincrementaldecoder("utf-8")("replace")
        for lo, hi in _random_splits(rng, len(b), 6):
            res, st = cs.transcode_stream_chunk(st, b[lo:hi])
            want = np.frombuffer(
                dec.decode(b[lo:hi].tobytes()).encode("utf-16-le"),
                np.uint16)
            got = np.asarray(res.buffer)[: int(res.count)]
            np.testing.assert_array_equal(got, want, err_msg=f"t{trial}")
        res, st = cs.finalize(st)
        want = np.frombuffer(
            dec.decode(b"", final=True).encode("utf-16-le"), np.uint16)
        np.testing.assert_array_equal(
            np.asarray(res.buffer)[: int(res.count)], want)


def test_stream_incremental_utf16_replace_fuzz():
    """utf16 -> utf8 with surrogate pairs straddling random splits,
    including single-unit chunks."""
    import codecs
    from repro.core import stream as cs
    rng = np.random.default_rng(SEED + 72)
    for trial in range(8):
        u = synthetic.utf16_units("emoji", 120, seed=SEED + trial).copy()
        u[rng.integers(0, len(u), 3)] = 0xD800      # lone surrogates
        st = cs.stream_init("utf16", "utf8", errors="replace")
        dec = codecs.getincrementaldecoder("utf-16-le")("replace")
        splits = _random_splits(rng, len(u), 10) if trial % 2 else \
            [(i, i + 1) for i in range(len(u))]     # 1-unit chunks
        for lo, hi in splits:
            res, st = cs.transcode_stream_chunk(st, u[lo:hi])
            want = np.frombuffer(
                dec.decode(u[lo:hi].astype("<u2").tobytes())
                .encode("utf-8"), np.uint8)
            got = np.asarray(res.buffer)[: int(res.count)]
            np.testing.assert_array_equal(got, want, err_msg=f"t{trial}")
        res, _ = cs.finalize(st)
        want = np.frombuffer(
            dec.decode(b"", final=True).encode("utf-8"), np.uint8)
        np.testing.assert_array_equal(
            np.asarray(res.buffer)[: int(res.count)], want)


def test_stream_incremental_strict_status_fuzz():
    """errors="strict": the final sticky status must equal the
    whole-buffer ``UnicodeDecodeError.start`` regardless of chunking."""
    from repro.core import stream as cs
    rng = np.random.default_rng(SEED + 73)
    for trial in range(10):
        b = synthetic.utf8_array(LANGS[trial % len(LANGS)], 150,
                                 seed=SEED + trial).copy()
        k = int(rng.integers(1, 5))
        b[rng.integers(0, max(len(b), 1), k)] = rng.integers(0, 256, k)
        try:
            b.tobytes().decode("utf-8")
            want = -1
        except UnicodeDecodeError as e:
            want = e.start
        st = cs.stream_init("utf8", "utf16", errors="strict")
        for lo, hi in _random_splits(rng, len(b), 5):
            _, st = cs.transcode_stream_chunk(st, b[lo:hi])
        _, st = cs.finalize(st)
        assert st.status == want, (trial, st.status, want)
