"""Ragged packed-batch transcode: packing layout, device ownership map,
bit-identity with the per-document block-parallel reference, and the
batch entry points."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core import transcode as tc
from repro.data import pipeline, synthetic
from repro.kernels import ragged_transcode as rt
from repro.kernels import window

TILE = packing.TILE


def _docs_mixed():
    """The adversarial batch shape: empty, all-ASCII, sub-tile,
    multi-tile and malformed documents in one ragged batch."""
    return [
        synthetic.utf8_array("latin", 200, seed=1),          # all-ASCII
        np.zeros(0, np.uint8),                               # empty
        synthetic.utf8_array("emoji", 700, seed=2),          # multi-tile
        synthetic.utf8_array("chinese", 1500, seed=3),       # multi-tile
        np.frombuffer(b"hi \xe4\xb8 there", np.uint8),       # malformed
        synthetic.utf8_array("arabic", 40, seed=4),          # sub-tile
    ]


# ---------------------------------------------------------------------------
# Packing layout


def test_pack_documents_layout():
    docs = _docs_mixed()
    pk = packing.pack_documents(docs)
    assert pk.n_docs == len(docs)
    assert pk.offsets[0] == 0
    assert np.all(pk.offsets % TILE == 0)          # tile-aligned starts
    assert np.all(np.diff(pk.offsets) >= 0)
    for d, doc in enumerate(docs):
        n = len(doc)
        assert pk.lengths[d] == n
        lo, hi = pk.offsets[d], pk.offsets[d + 1]
        assert hi - lo == -(-n // TILE) * TILE     # exact tile span
        assert np.array_equal(pk.data[lo: lo + n], np.asarray(doc))
        assert not pk.data[lo + n: hi].any()       # zero-filled slack


def test_pack_documents_fixed_geometry():
    docs = [b"ab", b""]
    pk = packing.pack_documents(docs, doc_tiles=2, pad_to_docs=4)
    assert pk.n_docs == 4
    assert np.array_equal(pk.offsets, np.arange(5) * 2 * TILE)
    assert np.array_equal(pk.lengths, [2, 0, 0, 0])
    with pytest.raises(ValueError):
        packing.pack_documents([np.zeros(TILE + 1, np.uint8)], doc_tiles=1)
    with pytest.raises(ValueError):
        packing.pack_documents(docs, pad_to_docs=1)


def test_pack_documents_bytes_and_dtype():
    pk = packing.pack_documents([b"abc"], dtype=np.uint8)
    assert pk.data.dtype == np.uint8 and pk.lengths[0] == 3
    pk16 = packing.pack_documents([np.array([0x41], np.uint16)])
    assert pk16.data.dtype == np.uint16


def test_unpack_results_clamps_to_capacity():
    buf = np.arange(8, dtype=np.uint16)
    docs = packing.unpack_results(buf, np.array([0, 4, 8]),
                                  np.array([4, 100]))
    assert np.array_equal(docs[0], [0, 1, 2, 3])
    assert np.array_equal(docs[1], [4, 5, 6, 7])   # clamped, no IndexError


# ---------------------------------------------------------------------------
# Device ownership map


def test_tile_ownership_map():
    # docs: 1 tile, EMPTY, 2 tiles, 1 tile  ->  offsets in tiles: 0,1,1,3,4
    offsets = np.array([0, 1, 1, 3, 4]) * TILE
    lengths = np.array([TILE, 0, TILE + 5, 7], np.int32)
    tile_doc, tile_end, same_prev, same_next = packing.tile_ownership(
        jnp.asarray(offsets), jnp.asarray(lengths), nblk=4, block=TILE)
    assert np.array_equal(tile_doc, [0, 2, 2, 3])  # empty doc owns no tile
    assert np.array_equal(tile_end,
                          [TILE, 2 * TILE + 5, 2 * TILE + 5, 3 * TILE + 7])
    # Neighbour flags: only the two tiles of doc 2 see each other.
    assert np.array_equal(same_prev, [0, 0, 1, 0])
    assert np.array_equal(same_next, [0, 1, 0, 0])


def test_tile_ownership_trailing_pad_tile_is_dead():
    # A pad tile past the last document clamps to the last doc but its
    # tile_end precedes it: no lane can be live.
    offsets = np.array([0, TILE])
    lengths = np.array([10], np.int32)
    tile_doc, tile_end, _, _ = packing.tile_ownership(
        jnp.asarray(offsets), jnp.asarray(lengths), nblk=2, block=TILE)
    assert int(tile_doc[1]) == 0
    assert int(tile_end[1]) == 10 < TILE  # every lane of tile 1 is dead


# ---------------------------------------------------------------------------
# Bit-identity with the per-document block-parallel reference


@functools.partial(jax.jit, static_argnames=("src", "dst", "errors"))
def _reference(buf, n, src, dst, errors):
    """One document through the pure-jnp block-parallel reference."""
    return tc.transcode(buf, dst, src_format=src, n_valid=n,
                        strategy="blockparallel", errors=errors)


def _assert_doc_equal(res, d, single, span):
    """Ragged doc d must reproduce the single-doc TranscodeResult:
    same count, same status, same buffer prefix (the single-doc buffer is
    capacity-clamped, so compare min(count, span) elements)."""
    assert int(res.counts[d]) == int(single.count), d
    assert int(res.statuses[d]) == int(single.status), d
    k = min(int(single.count), span)
    lo = int(res.offsets[d])
    got = np.asarray(res.buffer)[lo: lo + k]
    want = np.asarray(single.buffer)[:k]
    assert np.array_equal(got, want), d


@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_ragged_utf8_matches_per_doc_blockparallel(errors):
    docs = _docs_mixed()
    pk = packing.pack_documents(docs)
    res = tc.ragged_utf8_to_utf16(pk.data, pk.offsets, pk.lengths,
                                  errors=errors)
    # Dense output: offsets are the cumsum of counts.
    assert np.array_equal(np.asarray(res.offsets),
                          np.concatenate([[0], np.cumsum(res.counts)]))
    for d, doc in enumerate(docs):
        n = len(doc)
        buf = np.zeros(max(n, 1), np.uint8)
        buf[:n] = doc
        single = _reference(buf, n, "utf8", "utf16", errors)
        _assert_doc_equal(res, d, single, max(n, 1))


@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_ragged_utf16_matches_per_doc_blockparallel(errors):
    docs = [
        synthetic.utf16_units("korean", 400, seed=1),
        np.zeros(0, np.uint16),
        # surrogate pair straddling the doc's own tile boundary
        np.concatenate([np.full(1023, 0xE000, np.uint16),
                        np.array([0xD800, 0xDC00], np.uint16),
                        np.full(50, 0x41, np.uint16)]),
        np.array([0x41, 0xD800, 0x42], np.uint16),   # lone surrogate
        synthetic.utf16_units("emoji", 700, seed=5),
    ]
    pk = packing.pack_documents(docs, dtype=np.uint16)
    res = tc.ragged_utf16_to_utf8(pk.data, pk.offsets, pk.lengths,
                                  errors=errors)
    for d, doc in enumerate(docs):
        n = len(doc)
        buf = np.zeros(max(n, 1), np.uint16)
        buf[:n] = doc
        single = _reference(buf, n, "utf16", "utf8", errors)
        _assert_doc_equal(res, d, single, 3 * max(n, 1))


def test_ragged_scan_matches_strict_transcode():
    docs = _docs_mixed()
    pk = packing.pack_documents(docs)
    res = tc.ragged_utf8_to_utf16(pk.data, pk.offsets, pk.lengths)
    counts, statuses = tc.ragged_scan_utf8(pk.data, pk.offsets, pk.lengths)
    assert np.array_equal(np.asarray(counts), np.asarray(res.counts))
    assert np.array_equal(np.asarray(statuses), np.asarray(res.statuses))
    u16docs = [synthetic.utf16_units("latin", 100, seed=1),
               np.array([0xDC00], np.uint16)]
    pk16 = packing.pack_documents(u16docs, dtype=np.uint16)
    res16 = tc.ragged_utf16_to_utf8(pk16.data, pk16.offsets, pk16.lengths)
    c16, s16 = tc.ragged_scan_utf16(pk16.data, pk16.offsets, pk16.lengths)
    assert np.array_equal(np.asarray(c16), np.asarray(res16.counts))
    assert np.array_equal(np.asarray(s16), np.asarray(res16.statuses))


def test_ragged_garbage_beyond_length_is_masked():
    """Bytes past a document's logical length must not leak into its own
    or its neighbour's analysis (the packed analogue of n_valid)."""
    pk = packing.pack_documents([b"ok", b"fine"])
    data = np.asarray(pk.data).copy()
    data[2: TILE] = 0xFF            # garbage in doc 0's slack
    res = tc.ragged_utf8_to_utf16(jnp.asarray(data), pk.offsets, pk.lengths)
    assert np.array_equal(np.asarray(res.statuses), [-1, -1])
    assert np.array_equal(np.asarray(res.counts), [2, 4])


def test_ragged_rejects_malformed_batch_args():
    data = jnp.zeros((2 * TILE,), jnp.uint8)
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.zeros((1,), jnp.int32),
                                jnp.zeros((0,), jnp.int32))
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([0, TILE]),
                                jnp.asarray([5, 5]))
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([0, TILE]),
                                jnp.asarray([5]), errors="ignore")
    # Layout invariants (silently wrong results otherwise): mid-tile
    # start, nonzero first offset, decreasing offsets, oversize length.
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([0, 100, 2 * TILE]),
                                jnp.asarray([100, 1900]))
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([TILE, 2 * TILE]),
                                jnp.asarray([5]))
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([0, 2 * TILE, TILE]),
                                jnp.asarray([5, 5]))
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data, jnp.asarray([0, TILE, 2 * TILE]),
                                jnp.asarray([TILE + 1, 5]))
    # Truncated data buffer: trailing docs would silently read as empty.
    with pytest.raises(ValueError):
        tc.ragged_utf8_to_utf16(data[:TILE],
                                jnp.asarray([0, TILE, 2 * TILE]),
                                jnp.asarray([5, 50]))


def test_ragged_single_launch_per_pass_jaxpr():
    """The whole batch must transcode in ONE launch, and scan in ONE
    launch, however many documents it holds."""
    from _jaxpr import pallas_eqns
    pk = packing.pack_documents(_docs_mixed())
    args = (jnp.asarray(pk.data), jnp.asarray(pk.offsets),
            jnp.asarray(pk.lengths))
    jaxpr = jax.make_jaxpr(
        lambda d, o, l: tc.ragged_utf8_to_utf16(d, o, l))(*args).jaxpr
    assert len(pallas_eqns(jaxpr)) == 1      # one-pass, batch-wide
    jaxpr_scan = jax.make_jaxpr(
        lambda d, o, l: tc.ragged_scan_utf8(d, o, l))(*args).jaxpr
    assert len(pallas_eqns(jaxpr_scan)) == 1  # count pass only


# ---------------------------------------------------------------------------
# Blocks of tiles: the one-pass kernel steps over blocks of G tiles, and a
# block holds tiles of several documents.  Every batch here has one shape
# (NT tiles, NDOCS document rows), so the cases share their programs.

NT, NDOCS = 12, 12
_KINDS = {"ascii": (0x20, 0x7E), "two": (0x0621, 0x064A),
          "cjk": (0x4E00, 0x9FA5), "emoji": (0x1F300, 0x1F6FF)}
_MALFORMED = [b"\x80", b"\xff", b"\xc3", b"\xe4\xb8", b"\xf0\x9f\x92",
              b"\xed\xa0\x80", b"\xc0\xaf", b"\xf8\x88\x80\x80\x80"]


def _edge():
    """Tiles per block of an NT-tile batch: the first block edge."""
    g = window.tiles_per_step(NT * TILE, rt.RAGGED_G_MAX)
    assert 1 < g < NT
    return g


def _text(kind, nbytes, seed=0):
    """Exactly ``nbytes`` of valid UTF-8: whole characters of one kind,
    topped up with ASCII."""
    lo, hi = _KINDS[kind]
    k = nbytes // len(chr(lo).encode())
    cps = np.random.default_rng(seed).integers(lo, hi + 1, k)
    raw = "".join(map(chr, cps)).encode()
    return raw + b"a" * (nbytes - len(raw))


def _splice(raw, at, piece):
    return raw[:at] + piece + raw[at + len(piece):]


def _check_block_batch(docs, errors):
    """One NT-tile batch: every document bit-exact against the
    block-parallel reference on that document alone, and against
    CPython."""
    pk = packing.pack_documents(docs, dtype=np.uint8, pad_to_docs=NDOCS)
    assert pk.data.shape[0] <= NT * TILE
    data = np.zeros(NT * TILE, np.uint8)
    data[:pk.data.shape[0]] = pk.data
    one = rt.transcode_ragged(data, pk.offsets, pk.lengths, src="utf8",
                              dst="utf16", errors=errors)
    assert np.array_equal(np.asarray(one.offsets),
                          np.concatenate([[0], np.cumsum(one.counts)]))
    buf = np.asarray(one.buffer)
    assert not buf[int(one.offsets[-1]):].any()
    for d, raw in enumerate(docs):
        # One buffer shape for every document, so the cases share the
        # reference's programs.
        doc = np.zeros(NT * TILE, np.uint8)
        doc[:len(raw)] = np.frombuffer(raw, np.uint8)
        _assert_doc_equal(one, d, _reference(doc, len(raw), "utf8",
                                             "utf16", errors), NT * TILE)
    for d, raw in enumerate(docs):
        try:
            want, pos = raw.decode("utf-8").encode("utf-16-le"), -1
        except UnicodeDecodeError as e:
            want, pos = None, e.start
        if errors == "replace":
            want = raw.decode("utf-8", "replace").encode("utf-16-le")
        assert int(one.statuses[d]) == pos, d
        if want is not None:
            want = np.frombuffer(want, np.uint16)
            lo = int(one.offsets[d])
            assert int(one.counts[d]) == len(want), d
            assert np.array_equal(buf[lo: lo + len(want)], want), d
    pad = slice(len(docs), NDOCS)
    assert not np.asarray(one.counts)[pad].any()
    assert (np.asarray(one.statuses)[pad] == -1).all()


def _doc_ends_on_block_edge(e):
    # Doc 0 fills block 0 and ends on its last byte with a truncated
    # lead; doc 1 starts block 1 with the continuation bytes that would
    # complete it, were inflow to cross documents.
    return [_text("two", e * TILE - 1, 1) + b"\xe4",
            b"\xb8\xad" + _text("cjk", 600, 2),
            _text("cjk", 1500, 3)]


def _character_across_block_edge(e):
    # Doc 1 runs from tile 1 across the block edge: a 4-byte character
    # straddles the block edge, a 3-byte one a tile edge inside block 0,
    # and a truncated 4-byte lead a tile edge inside block 1.
    doc = _text("ascii", e * TILE + 500, 4)
    doc = _splice(doc, (e - 1) * TILE - 2, "\U0001F600".encode())
    doc = _splice(doc, 2 * TILE - 1, "中".encode())
    doc = _splice(doc, e * TILE - 1, b"\xf0\x9f\x92")
    return [_text("ascii", 100, 5), doc]


def _errors_in_documents_sharing_a_block(e):
    # Block 0: doc 0's error late in its tile, doc 1's early in the next,
    # then a valid doc.  Doc 3 is valid and runs into block 1, where doc
    # 4's error is the first error of the block.
    return [_splice(_text("ascii", 1000, 6), 900, b"\xff"),
            _splice(_text("two", 800, 7), 5, b"\xc3"),
            _text("two", 700, 8),
            _text("cjk", (e - 4) * TILE + 10, 9),
            b"ab\xed\xa0\x80" + _text("ascii", 300, 10)]


def _empty_documents_in_a_block(e):
    return [b"", _text("ascii", 300, 11), b"", b"", _text("cjk", 1500, 12),
            b"", _text("two", (e - 3) * TILE, 13), b"",
            _text("emoji", 200, 14), b""]


def _ascii_and_cjk_in_one_block(e):
    # Block 0 mixes ASCII documents with a CJK one (the general body);
    # block 1 holds ASCII only, after a document ending in a 3-byte
    # character.
    return [_text("ascii", 2 * TILE, 15), _text("cjk", TILE - 3, 16)
            + "中".encode(), _text("ascii", TILE - 1, 17),
            _text("cjk", (e - 4) * TILE, 18),
            _text("ascii", 3 * TILE + 17, 19)]


BLOCK_CASES = {
    "doc-ends-on-block-edge": _doc_ends_on_block_edge,
    "character-across-block-edge": _character_across_block_edge,
    "errors-in-documents-sharing-a-block":
        _errors_in_documents_sharing_a_block,
    "empty-documents-in-a-block": _empty_documents_in_a_block,
    "ascii-and-cjk-in-one-block": _ascii_and_cjk_in_one_block,
}


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("case", BLOCK_CASES)
def test_ragged_block_edges(case, errors):
    _check_block_batch(BLOCK_CASES[case](_edge()), errors)


@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_ragged_blocks_fuzz(errors):
    """Seeded batches of documents of every kind and length, a malformed
    sequence in about half of them, packed up to NT tiles."""
    rng = np.random.default_rng(1616)
    for _ in range(3):
        docs, tiles = [], 0
        while len(docs) < NDOCS:
            n = int(rng.integers(0, 2500))
            raw = _text(str(rng.choice(list(_KINDS))), n,
                        int(rng.integers(1 << 30)))
            if raw and rng.random() < 0.5:
                at = int(rng.integers(0, len(raw)))
                raw = raw[:at] + _MALFORMED[rng.integers(len(_MALFORMED))] \
                    + raw[at:]
            tiles += -(-len(raw) // TILE)
            if tiles > NT:
                break
            docs.append(raw)
        _check_block_batch(docs, errors)


# ---------------------------------------------------------------------------
# Batch entry points (strategy="packed" vs a per-document transcode loop)


def _per_doc(docs, lens, src, dst):
    """The batch contract, one ``transcode`` call per document."""
    rows = [tc.transcode(jnp.asarray(docs[i]), dst, src_format=src,
                         n_valid=int(lens[i])) for i in range(len(docs))]
    return tuple(np.stack([np.asarray(r[k]) for r in rows])
                 for k in range(3))


def test_batch_entries_packed_equals_per_doc_transcode():
    L = 1536
    docs = np.zeros((4, L), np.uint8)
    lens = []
    for i, lang in enumerate(["latin", "chinese", "emoji", "arabic"]):
        d = synthetic.utf8_array(lang, 300, seed=i)[:L]
        docs[i, : len(d)] = d
        lens.append(len(d))
    lens = np.asarray(lens, np.int32)
    pk = pipeline.batch_utf8_to_utf16(docs, lens)              # packed
    buffer, count, status = _per_doc(docs, lens, "utf8", "utf16")
    assert pk.buffer.shape == buffer.shape == (4, L)
    assert np.array_equal(np.asarray(pk.buffer), buffer)
    assert np.array_equal(np.asarray(pk.count), count)
    assert np.array_equal(np.asarray(pk.status), status)

    units = np.zeros((2, 1024), np.uint16)
    ulens = []
    for i, lang in enumerate(["korean", "latin"]):
        d = synthetic.utf16_units(lang, 300, seed=i)[:1024]
        units[i, : len(d)] = d
        ulens.append(len(d))
    ulens = np.asarray(ulens, np.int32)
    pk = pipeline.batch_utf16_to_utf8(units, ulens)
    buffer, count, status = _per_doc(units, ulens, "utf16", "utf8")
    assert pk.buffer.shape == buffer.shape == (2, 3 * 1024)
    assert np.array_equal(np.asarray(pk.buffer), buffer)
    assert np.array_equal(np.asarray(pk.count), count)
    assert np.array_equal(np.asarray(pk.status), status)


def test_batch_entries_replace_policy_threads_through():
    docs = np.zeros((2, 1024), np.uint8)
    docs[0, :3] = [0x61, 0xFF, 0x62]     # a <bad> b
    docs[1, :2] = [0xC3, 0xA9]           # é
    lens = np.asarray([3, 2], np.int32)
    res = pipeline.batch_utf8_to_utf16(docs, lens, errors="replace")
    want0 = np.frombuffer(
        b"a\xffb".decode("utf-8", "replace").encode("utf-16-le"), np.uint16)
    assert np.array_equal(np.asarray(res.buffer[0])[:3], want0)
    assert int(res.status[0]) == 1 and int(res.status[1]) == -1
