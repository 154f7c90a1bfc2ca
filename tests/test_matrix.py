"""Codec-matrix refactor: stage composition, generic-driver sharing,
derived stage widths, byte helpers, and the matrix surfaces of the data
pipeline and serving engine (DESIGN.md §8).

Named ``test_matrix`` so the CI matrix-parity job (``-k "matrix or
parity"``) picks the whole module up alongside the differential suite's
matrix cells.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _jaxpr import iter_eqns, pallas_eqns

from repro.core import transcode as tc
from repro.data import pipeline, synthetic
from repro.kernels import onepass_transcode as op
from repro.kernels import stages
from repro.kernels.stages import driver as sdrv


# ---------------------------------------------------------------------------
# Registry / dispatch


def test_matrix_formats_and_aliases():
    assert tc.normalize_format("utf-8") == "utf8"
    assert tc.normalize_format("UTF-16-LE") == "utf16"
    assert tc.normalize_format("utf-32-le") == "utf32"
    assert tc.normalize_format("latin-1") == "latin1"
    assert tc.normalize_format("iso-8859-1") == "latin1"
    with pytest.raises(ValueError):
        tc.normalize_format("ebcdic")
    # every (src != dst) pair is a supported cell
    assert len(tc.PAIRS) == 12
    with pytest.raises(ValueError):
        tc.transcode(jnp.zeros(8, jnp.uint8), "utf8", src_format="utf8")


def test_matrix_registry_shares_cap_factors():
    """The kernel registry and the public dispatch must agree on the
    static capacity conventions (one source of truth)."""
    assert stages.CAP_FACTOR is tc.CAP_FACTOR
    for (s, d), f in tc.CAP_FACTOR.items():
        codec_s, codec_d, factor = stages.get_pair(s, d)
        assert factor == f
        assert codec_s.name == s and codec_d.name == d


def test_matrix_stage_widths_are_derived():
    """Stage windows come from the destination's unit length at the
    source's largest fabricable code point — including the surrogate-
    flood worst case that the old hand-sized UTF-16→UTF-8 bound missed."""
    u = stages
    assert stages.stage_units(u.UTF8, u.UTF16) == 2
    assert stages.stage_units(u.UTF8, u.UTF32) == 1
    assert stages.stage_units(u.UTF16, u.UTF8) == 4   # was 3 (+1) — bug
    assert stages.stage_units(u.UTF32, u.UTF8) == 4
    assert stages.stage_units(u.UTF32, u.UTF16) == 2
    assert stages.stage_units(u.LATIN1, u.UTF8) == 2
    for (s, d) in stages.PAIRS:
        assert stages.stage_width(*stages.get_pair(s, d)[:2]) \
            == stages.BLOCK * stages.stage_units(*stages.get_pair(s, d)[:2])


def test_matrix_stage_overflow_regression_surrogate_flood():
    """A tile of 0xDBFF units folds EVERY lane to a supplementary pair
    code point (4 speculative UTF-8 bytes each, 4*BLOCK per tile) — the
    old 3*BLOCK+1 stage silently dropped the tail and the kernel output
    diverged from blockparallel.  Pin the fix."""
    for unit in (0xDBFF, 0xDBFF):
        u = np.full(2048, unit, np.uint16)
        f = op.transcode_onepass(jnp.asarray(u), len(u), src="utf16",
                                 dst="utf8")
        b = tc.utf16_to_utf8(jnp.asarray(u.astype(np.int32)), len(u))
        assert int(f.count) == int(b.count)
        k = int(f.count)
        assert np.array_equal(np.asarray(f.buffer)[:k],
                              np.asarray(b.buffer)[:k].astype(np.uint8))
    # alternating DBFF/FFFF: 4-byte and 3-byte speculative lanes mixed
    u = np.tile(np.array([0xDBFF, 0xFFFF], np.uint16), 1024)
    f = op.transcode_onepass(jnp.asarray(u), len(u), src="utf16",
                             dst="utf8")
    b = tc.utf16_to_utf8(jnp.asarray(u.astype(np.int32)), len(u))
    assert int(f.count) == int(b.count)
    k = int(f.count)
    assert np.array_equal(np.asarray(f.buffer)[:k],
                          np.asarray(b.buffer)[:k].astype(np.uint8))


# ---------------------------------------------------------------------------
# ONE generic driver serves every cell (no per-pair kernel duplication).


def test_matrix_cells_share_one_generic_driver(monkeypatch):
    """Tracing ANY matrix cell must invoke the stages package's single
    ``onepass_tile`` (transcode) / ``count_tile`` (scan) driver — no
    per-pair kernel bodies.  UTF-8→UTF-16 (the classic cell) and
    UTF-8→UTF-32 / latin1→utf8 (new cells) are counted through the same
    monkeypatched entry points."""
    calls = {"count": [], "transcode": []}
    real_count, real_onepass = sdrv.count_tile, sdrv.onepass_tile

    def spy_count(src, dst, *a, **k):
        calls["count"].append((src.name, dst.name))
        return real_count(src, dst, *a, **k)

    def spy_onepass(src, dst, *a, **k):
        calls["transcode"].append((src.name, dst.name))
        return real_onepass(src, dst, *a, **k)

    monkeypatch.setattr(sdrv, "count_tile", spy_count)
    monkeypatch.setattr(sdrv, "onepass_tile", spy_onepass)
    # A program traced earlier in this process would not run the spies.
    jax.clear_caches()

    cap = 2048
    for src, dst, dt in (("utf8", "utf16", jnp.uint8),
                         ("utf8", "utf32", jnp.uint8),
                         ("latin1", "utf8", jnp.uint8)):
        jax.make_jaxpr(
            lambda x, s=src, d=dst: op.transcode_onepass(
                x, cap - 5, src=s, dst=d, ascii_fastpath=False)
        )(jnp.zeros((cap,), dt))
        jax.make_jaxpr(
            lambda x, s=src, d=dst: op.scan_onepass(x, cap - 5, src=s,
                                                    dst=d)
        )(jnp.zeros((cap,), dt))
        assert (src, dst) in calls["count"], (src, dst, calls["count"])
        assert (src, dst) in calls["transcode"], \
            (src, dst, calls["transcode"])


@pytest.mark.parametrize("src,dst,dt", [("utf8", "utf16", jnp.uint8),
                                        ("utf8", "utf32", jnp.uint8),
                                        ("utf32", "utf8", jnp.uint32),
                                        ("latin1", "utf8", jnp.uint8)])
def test_matrix_jaxpr_one_launch_narrow_io(src, dst, dt):
    """Every matrix cell is the same one-launch shape (count and write
    off one decode, nothing else), with narrow-dtype large operands."""
    cap = 2048
    itemsize = stages.get_codec(src).itemsize
    jaxpr = jax.make_jaxpr(
        lambda x: op.transcode_onepass(x, cap - 5, src=src, dst=dst,
                                       ascii_fastpath=False)
    )(jnp.zeros((cap,), dt)).jaxpr
    kernels = pallas_eqns(jaxpr)
    assert len(kernels) == 1, (src, dst, len(kernels))
    for eqn in kernels:
        for v in eqn.invars:
            if v.aval.size >= cap:
                assert v.aval.dtype.itemsize <= itemsize, (src, dst, v.aval)
    names = {e.primitive.name for e in iter_eqns(jaxpr)}
    assert not any("scatter" in n for n in names), names


# ---------------------------------------------------------------------------
# Latin-1 semantics (the asymmetric corner of the matrix)


def test_matrix_latin1_roundtrip_and_substitution():
    t = "café ÿ þ £"
    l1 = np.frombuffer(t.encode("latin-1"), np.uint8)
    for strat in ("onepass", "blockparallel"):
        r = tc.latin1_to_utf8(jnp.asarray(l1), len(l1), strategy=strat)
        assert int(r.status) == -1
        assert bytes(np.asarray(r.buffer)[: int(r.count)].astype(np.uint8)) \
            == t.encode("utf-8")
        r = tc.latin1_to_utf16(jnp.asarray(l1), len(l1), strategy=strat)
        assert np.array_equal(
            np.asarray(r.buffer)[: int(r.count)].astype(np.uint16),
            np.frombuffer(t.encode("utf-16-le"), np.uint16))
    # utf8 -> latin1 with an unencodable char: status at its lead byte,
    # replace output matches CPython's chained replace ('?')
    s = "ab 中 é"
    b = np.frombuffer(s.encode("utf-8"), np.uint8)
    want_pos = len("ab ".encode("utf-8"))
    for strat in ("onepass", "blockparallel"):
        r = tc.utf8_to_latin1(jnp.asarray(b), len(b), strategy=strat)
        assert int(r.status) == want_pos, strat
        r = tc.utf8_to_latin1(jnp.asarray(b), len(b), errors="replace",
                              strategy=strat)
        assert int(r.status) == want_pos, strat
        assert bytes(np.asarray(r.buffer)[: int(r.count)].astype(np.uint8)) \
            == s.encode("latin-1", "replace"), strat


def test_matrix_utf32_strict_substitutes_but_locates():
    cps = np.array([0x41, 0xD800, 0x1F389, 0x110000, 0x42], np.uint32)
    for strat in ("onepass", "blockparallel"):
        out, cnt, status = tc.utf32_to_utf8(jnp.asarray(cps), len(cps),
                                            strategy=strat)
        assert int(status) == 1, strat
        # the buffer is the replace-form output (well-defined narrow
        # values) even under strict; status lets callers reject.
        want = "A�🎉�B".encode("utf-8")
        assert bytes(np.asarray(out)[: int(cnt)].astype(np.uint8)) == want, \
            strat


def test_matrix_ascii_fastpath_rejects_wrapped_negative_utf32():
    """A garbage UTF-32 scalar (0xFFFFFFFF wraps to int32 -1) inside an
    otherwise-ASCII buffer must NOT ride the ASCII fast path: both
    strategies locate it and substitute U+FFFD (review regression)."""
    cps = np.array([0x41, 0xFFFFFFFF, 0x42], np.uint32)
    want = "A�B".encode("utf-8")
    for strat in ("onepass", "blockparallel"):
        out, cnt, status = tc.utf32_to_utf8(jnp.asarray(cps), len(cps),
                                            strategy=strat)
        assert int(status) == 1, strat
        assert int(cnt) == len(want), strat
        assert bytes(np.asarray(out)[: int(cnt)].astype(np.uint8)) == want, \
            strat


def test_matrix_scan_counts_destination_units():
    s = "naïve 中文 🎉"
    b = np.frombuffer(s.encode("utf-8"), np.uint8)
    for strat in ("onepass", "blockparallel"):
        cnt, status = tc.scan(jnp.asarray(b), "utf32", src_format="utf8",
                              n_valid=len(b), strategy=strat)
        assert int(status) == -1
        assert int(cnt) == len(s), strat
        cnt16, _ = tc.scan(jnp.asarray(b), "utf16", src_format="utf8",
                           n_valid=len(b), strategy=strat)
        assert int(cnt16) == len(s.encode("utf-16-le")) // 2, strat


_GEN_HI = {1: 256, 2: 1 << 16, 4: 0x110000}


@pytest.mark.parametrize("src,dst", tc.PAIRS)
def test_matrix_scan_matches_blockparallel_all_cells(src, dst):
    """The counting kernel over every matrix cell: ``(count, status)``
    equal the pure-jnp count and the strict transcode's, on garbage
    across the source's whole range with ``n_valid`` inside the last
    tile."""
    rng = np.random.default_rng(20261021)
    codec = stages.get_codec(src)
    cap = 2 * stages.BLOCK
    for trial in range(2):
        arr = rng.integers(0, _GEN_HI[codec.itemsize], cap)
        if trial:       # mostly ASCII, a few wide values
            arr = np.where(rng.random(cap) < 0.9, arr % 0x80, arr)
        arr = jnp.asarray(arr.astype(codec.dtype))
        n = int(rng.integers(stages.BLOCK, cap))
        cnt, status = op.scan_onepass(arr, n, src=src, dst=dst)
        ref = tc.scan(arr, dst, src_format=src, n_valid=n,
                      strategy="blockparallel")
        assert (int(cnt), int(status)) == (int(ref[0]), int(ref[1])), \
            (src, dst, trial)
        res = op.transcode_onepass(arr, n, src=src, dst=dst)
        assert (int(cnt), int(status)) == (int(res.count),
                                           int(res.status)), \
            (src, dst, trial)


# ---------------------------------------------------------------------------
# Endianness-explicit byte helpers


def test_matrix_le_byte_helpers_roundtrip():
    s = "héllo 🎉 中"
    raw16 = np.frombuffer(s.encode("utf-16-le"), np.uint8)
    units = tc.utf16le_bytes_to_units(jnp.asarray(raw16.astype(np.int32)))
    assert np.array_equal(np.asarray(units),
                          np.frombuffer(s.encode("utf-16-le"), "<u2")
                          .astype(np.int32))
    back = tc.units_to_utf16le_bytes(units)
    assert np.array_equal(np.asarray(back), raw16.astype(np.int32))

    raw32 = np.frombuffer(s.encode("utf-32-le"), np.uint8)
    cps = tc.utf32le_bytes_to_cps(jnp.asarray(raw32.astype(np.int32)))
    assert np.array_equal(np.asarray(cps),
                          np.array([ord(c) for c in s], np.int32))
    back = tc.cps_to_utf32le_bytes(cps)
    assert np.array_equal(np.asarray(back), raw32.astype(np.int32))


def test_matrix_le_byte_helpers_reject_ragged_length():
    with pytest.raises(ValueError):
        tc.utf16le_bytes_to_units(jnp.zeros(3, jnp.int32))
    with pytest.raises(ValueError):
        tc.utf32le_bytes_to_cps(jnp.zeros(6, jnp.int32))


# ---------------------------------------------------------------------------
# Pipeline: matrix batch entries + device-side codepoint emission


def test_matrix_pipeline_batch_transcode_utf32():
    L = 1536
    langs = ["latin", "chinese", "emoji"]
    docs = np.zeros((3, L), np.uint8)
    lens = []
    for i, lang in enumerate(langs):
        d = synthetic.utf8_array(lang, 300, seed=i)[:L]
        docs[i, : len(d)] = d
        lens.append(len(d))
    lens = np.asarray(lens, np.int32)
    res = pipeline.batch_transcode(docs, lens, in_encoding="utf8",
                                   out_encoding="utf32")
    assert res.buffer.shape == (3, L)
    for i in range(3):
        # The packed batch against the per-document transcode and CPython.
        one = tc.transcode(jnp.asarray(docs[i]), "utf32", src_format="utf8",
                           n_valid=int(lens[i]))
        assert np.array_equal(np.asarray(res.buffer[i]),
                              np.asarray(one.buffer)), i
        text = bytes(docs[i, : lens[i]]).decode("utf-8")
        assert int(res.status[i]) == int(one.status) == -1, i
        assert int(res.count[i]) == int(one.count) == len(text), i
        assert np.array_equal(
            np.asarray(res.buffer[i])[: len(text)].astype(np.int64),
            np.array([ord(c) for c in text], np.int64)), i


def test_matrix_pipeline_emits_codepoints_on_device():
    cfg = pipeline.PipelineConfig(seq_len=512, global_batch=2,
                                  emit="codepoints")
    p = pipeline.TextPipeline(cfg)
    batch = p.next_batch()
    assert "codepoints" in batch and "cp_counts" in batch
    assert batch["codepoints"].shape[0] == 2
    # cross-check one document against the host decode
    doc = p._doc_bytes(0, 0)
    text = bytes(doc).decode("utf-8")
    assert int(batch["cp_counts"][0]) == len(text)
    assert np.array_equal(
        np.asarray(batch["codepoints"][0])[: len(text)].astype(np.int64),
        np.array([ord(c) for c in text], np.int64))


def test_matrix_pipeline_rejects_unknown_pair():
    with pytest.raises(ValueError):
        pipeline.batch_transcode(np.zeros((1, 8), np.uint8),
                                 np.array([4], np.int32),
                                 in_encoding="utf8", out_encoding="utf8")
