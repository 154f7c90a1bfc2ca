"""Single-pass pipeline tests (strategy="onepass", DESIGN.md §9).

Three claims are pinned here:

  1. **One launch.**  A one-pass transcode — single stream or a whole
     ragged packed batch — traces to exactly ONE ``pallas_call``: the
     SMEM offset carry replaces a count-launch / cumsum / write-launch
     split.
  2. **Bit identity.**  (buffer, count, status) are bit-identical to the
     pure-jnp ``strategy="blockparallel"`` reference across every matrix
     cell × ``errors=`` policy, including boundary-adversarial streams
     straddling VMEM tile, block and packed-document boundaries (the
     carry must advance by exactly each tile's total for the bases to
     agree).
  3. **Per-tile ASCII skip.**  Mixed ASCII/multibyte documents where only
     some tiles are non-ASCII stay correct (the skip may only fire on
     tiles whose boundary inflow is clean), including a pure-ASCII tile
     whose previous tile ends in lead/continuation bytes.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _jaxpr import pallas_eqns

from repro.core import packing
from repro.core import transcode as tc
from repro.data import synthetic
from repro.kernels import onepass_transcode as op
from repro.kernels import ragged_transcode as rt
from repro.kernels import stages
from repro.kernels import window

BLOCK = stages.BLOCK


def _u8(x, n_valid=None, **kw):
    """UTF-8 -> UTF-16 through the onepass kernel."""
    return op.transcode_onepass(x, n_valid, src="utf8", dst="utf16", **kw)


@functools.partial(jax.jit, static_argnames=("src", "dst", "errors",
                                             "validate"))
def _blockparallel(x, n, src, dst, errors, validate):
    return tc.transcode(x, dst, src_format=src, n_valid=n,
                        strategy="blockparallel", errors=errors,
                        validate=validate)


def _reference(x, n, src="utf8", dst="utf16", errors="strict",
               validate=True):
    """The same call through the pure-jnp block-parallel reference."""
    return _blockparallel(jnp.asarray(x), n, src, dst, errors, validate)


# ---------------------------------------------------------------------------
# Claim 1: one launch.


@pytest.mark.parametrize("src,dst,dt", [("utf8", "utf16", jnp.uint8),
                                        ("utf16", "utf8", jnp.uint16),
                                        ("utf32", "utf8", jnp.uint32),
                                        ("latin1", "utf8", jnp.uint8)])
def test_onepass_traces_to_one_pallas_call(src, dst, dt):
    cap = 4096
    for fastpath in (True, False):
        jaxpr = jax.make_jaxpr(
            lambda x, s=src, d=dst, a=fastpath: op.transcode_onepass(
                x, cap - 5, src=s, dst=d, ascii_fastpath=a)
        )(jnp.zeros((cap,), dt)).jaxpr
        kernels = pallas_eqns(jaxpr)
        assert len(kernels) == 1, (src, dst, fastpath, len(kernels))


def test_onepass_ragged_traces_to_one_pallas_call():
    docs = [np.full(1500, 0x41, np.uint8), np.full(700, 0x41, np.uint8)]
    pk = packing.pack_documents(docs, dtype=np.uint8)
    jaxpr = jax.make_jaxpr(
        lambda d, o, l: rt.transcode_ragged(d, o, l, src="utf8",
                                            dst="utf16")
    )(jnp.asarray(pk.data), jnp.asarray(pk.offsets),
      jnp.asarray(pk.lengths)).jaxpr
    assert len(pallas_eqns(jaxpr)) == 1


def test_onepass_shares_the_generic_driver(monkeypatch):
    """Tracing a one-pass cell must go through the stages package's
    single ``onepass_tile`` body (itself composed of the same
    decode_once/count_decoded/stage_decoded primitives count_tile
    wraps) — no per-pair kernel duplication."""
    from repro.kernels.stages import driver as sdrv
    calls = []
    real = sdrv.onepass_tile

    def spy(src, dst, *a, **k):
        calls.append((src.name, dst.name))
        return real(src, dst, *a, **k)

    monkeypatch.setattr(sdrv, "onepass_tile", spy)
    cap = 2048
    for src, dst, dt in (("utf8", "utf16", jnp.uint8),
                         ("utf32", "utf8", jnp.uint32)):
        jax.make_jaxpr(
            lambda x, s=src, d=dst: op.transcode_onepass(
                x, cap - 5, src=s, dst=d, ascii_fastpath=False)
        )(jnp.zeros((cap,), dt))
        assert (src, dst) in calls, (src, dst, calls)


# ---------------------------------------------------------------------------
# Claim 2: bit identity with the block-parallel reference.


def _assert_identical(a, f, ctx):
    assert int(a.count) == int(f.count), ctx
    assert int(a.status) == int(f.status), ctx
    assert np.array_equal(np.asarray(a.buffer), np.asarray(f.buffer)), ctx


_GEN_HI = {1: 256, 2: 1 << 16, 4: 0x110000}


@pytest.mark.parametrize("src,dst", tc.PAIRS)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_onepass_bit_identical_to_blockparallel_all_cells(src, dst, errors):
    rng = np.random.default_rng(20260801)
    dt = stages.get_codec(src).dtype
    cap = 2 * BLOCK
    for trial in range(4):
        n = int(rng.integers(1, cap))
        arr = rng.integers(0, _GEN_HI[stages.get_codec(src).itemsize],
                           cap).astype(dt)
        a = op.transcode_onepass(jnp.asarray(arr), n, src=src, dst=dst,
                                 errors=errors)
        f = _reference(arr, n, src, dst, errors=errors)
        _assert_identical(a, f, (src, dst, errors, trial))


@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_onepass_boundary_straddling_characters(errors):
    """Multi-byte characters and truncated leads at VMEM tile boundaries:
    the SMEM carry's base must agree with the reference's global
    compaction at every tile, or outputs shear at exactly these
    positions."""
    probes = [b"\xf0\x9f\x92\xa9", b"\xe4\xb8\xad", b"\xc3\xa9",
              b"\xf0\x9f\x92", b"\xc3", b"\xed\xa0\x80"]
    for probe in probes:
        for pos in (BLOCK - 3, BLOCK - 2, BLOCK - 1, BLOCK, 2 * BLOCK - 1):
            buf = np.full(3 * BLOCK, 0x41, np.uint8)
            buf[pos: pos + len(probe)] = np.frombuffer(probe, np.uint8)
            a = _u8(jnp.asarray(buf), len(buf), errors=errors)
            f = _reference(buf, len(buf), errors=errors)
            _assert_identical(a, f, (probe, pos, errors))


@pytest.mark.parametrize("validate", [True, False])
def test_onepass_validate_flag_and_scan(validate):
    b = synthetic.utf8_array("arabic", 2000, seed=7)
    buf = np.zeros(8192, np.uint8)
    buf[: len(b)] = b
    a = _u8(jnp.asarray(buf), len(b), validate=validate)
    f = _reference(buf, len(b), validate=validate)
    _assert_identical(a, f, validate)
    # scan: the counting kernel against the pure-jnp count.
    c1, s1 = tc.scan_utf8(jnp.asarray(buf), len(b), strategy="onepass")
    c2, s2 = tc.scan_utf8(jnp.asarray(buf), len(b),
                          strategy="blockparallel")
    assert int(c1) == int(c2) and int(s1) == int(s2)


@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_onepass_ragged_bit_identical_to_per_doc_reference(errors):
    rng = np.random.default_rng(20260801 + 1)
    docs = [synthetic.utf8_array(lang, n, seed=i) for i, (lang, n) in
            enumerate([("latin", 1500), ("chinese", 900), ("emoji", 40),
                       ("arabic", 2100), ("korean", 1024)])]
    docs.insert(1, np.zeros(0, np.uint8))                  # empty
    docs.insert(3, np.full(77, 0x41, np.uint8))            # all-ASCII
    mutated = docs[4].copy()
    mutated[rng.integers(0, len(mutated), 3)] = 0xFF       # invalid doc
    docs[4] = mutated
    pk = packing.pack_documents(docs, dtype=np.uint8)
    a = rt.transcode_ragged(pk.data, pk.offsets, pk.lengths, src="utf8",
                            dst="utf16", errors=errors)
    # The dense stream is each document's reference output in order,
    # then zeros.
    want, offsets = [], [0]
    for d, doc in enumerate(docs):
        f = _reference(doc, len(doc), errors=errors)
        want.append(np.asarray(f.buffer)[: int(f.count)])
        offsets.append(offsets[-1] + int(f.count))
        assert int(a.counts[d]) == int(f.count), (d, errors)
        assert int(a.statuses[d]) == int(f.status), (d, errors)
    want = np.concatenate(want)
    buf = np.asarray(a.buffer)
    assert np.array_equal(np.asarray(a.offsets), offsets)
    assert np.array_equal(buf[: len(want)], want)
    assert not buf[len(want):].any()


def test_onepass_ragged_doc_pack_boundaries():
    """Truncated leads ending EXACTLY at a packed document boundary whose
    neighbour starts with the completing continuation bytes: the carry +
    ownership resets must keep the documents independent."""
    tile = packing.TILE
    docs = []
    for probe in (b"\xf0\x9f\x92", b"\xc3", b"\xe4\xb8"):
        doc = np.full(tile, 0x41, np.uint8)
        doc[tile - len(probe):] = np.frombuffer(probe, np.uint8)
        docs.append(doc)
        docs.append(np.frombuffer(b"\xa9\x80\x80 tail", np.uint8))
    docs.append(np.zeros(0, np.uint8))
    pk = packing.pack_documents(docs, dtype=np.uint8)
    for errors in ("strict", "replace"):
        res = rt.transcode_ragged(pk.data, pk.offsets, pk.lengths,
                                  src="utf8", dst="utf16", errors=errors)
        for d, doc in enumerate(docs):
            span = max(int(pk.offsets[d + 1] - pk.offsets[d]), 1)
            buf = np.zeros(span, np.uint8)
            buf[: len(doc)] = doc
            single = _u8(jnp.asarray(buf), len(doc), errors=errors)
            assert int(res.counts[d]) == int(single.count), (d, errors)
            assert int(res.statuses[d]) == int(single.status), (d, errors)
            lo = int(res.offsets[d])
            got = np.asarray(res.buffer)[lo: lo + int(res.counts[d])]
            k = min(int(single.count), span)
            assert np.array_equal(got[:k],
                                  np.asarray(single.buffer)[:k]), (d, errors)


def test_onepass_zero_length_and_n_valid_zero():
    z = _u8(jnp.zeros((0,), jnp.uint8))
    assert int(z.count) == 0 and int(z.status) == -1
    b = synthetic.utf8_array("latin", 100, seed=0)
    buf = np.zeros(2048, np.uint8)
    buf[: len(b)] = b
    r = _u8(jnp.asarray(buf), 0)
    assert int(r.count) == 0 and int(r.status) == -1


# ---------------------------------------------------------------------------
# Claim 3: the per-tile ASCII skip.


def test_onepass_single_nonascii_tile():
    """A document where exactly ONE tile holds multibyte characters: the
    whole-buffer cond fails, the skip fires on every other tile, and the
    result is still bit-identical to the reference and to the CPython
    oracle."""
    n = 8 * BLOCK
    buf = np.full(n, 0x61, np.uint8)
    cjk = "中文データ処理".encode("utf-8")
    pos = 3 * BLOCK + 100                    # interior of tile 3 only
    buf[pos: pos + len(cjk)] = np.frombuffer(cjk, np.uint8)
    a = _u8(jnp.asarray(buf), n)
    f = _reference(buf, n)
    _assert_identical(a, f, "single-nonascii-tile")
    want = np.frombuffer(bytes(buf).decode("utf-8").encode("utf-16-le"),
                         np.uint16)
    assert int(a.count) == len(want)
    assert np.array_equal(np.asarray(a.buffer)[: len(want)], want)


@pytest.mark.parametrize("tail", [b"\xc3", b"\xf0\x9f\x92", b"\x80",
                                  b"\xc3\xa9"])
def test_onepass_ascii_tile_after_multibyte_inflow(tail):
    """A pure-ASCII tile whose PREVIOUS tile ends in lead / continuation
    bytes (the boundary-inflow cases that must NOT take the skip): the
    conservative inflow guard sends the tile down the general path and
    the result stays bit-identical to the reference — including the
    error located in the previous tile for the truncated leads."""
    for errors in ("strict", "replace"):
        buf = np.full(3 * BLOCK, 0x61, np.uint8)
        buf[BLOCK - len(tail): BLOCK] = np.frombuffer(tail, np.uint8)
        a = _u8(jnp.asarray(buf), len(buf), errors=errors)
        f = _reference(buf, len(buf), errors=errors)
        _assert_identical(a, f, (tail, errors))


def test_onepass_ascii_skip_on_off_equivalence():
    """ascii_fastpath=True (whole-buffer cond + per-tile skip) and False
    (general path for every tile) must agree bit for bit on mixed and on
    pure-ASCII buffers."""
    mixed = np.full(4 * BLOCK, 0x61, np.uint8)
    mixed[BLOCK + 5: BLOCK + 8] = np.frombuffer("中".encode("utf-8"),
                                                np.uint8)
    pure = np.full(4 * BLOCK, 0x41, np.uint8)
    for buf in (mixed, pure):
        for errors in ("strict", "replace"):
            on = _u8(jnp.asarray(buf), len(buf) - 9, errors=errors,
                     ascii_fastpath=True)
            off = _u8(jnp.asarray(buf), len(buf) - 9, errors=errors,
                      ascii_fastpath=False)
            _assert_identical(on, off, errors)


def test_onepass_ascii_skip_other_sources():
    """The skip is format-generic: UTF-16/UTF-32/Latin-1 sources with
    mostly-ASCII content and one contaminated tile."""
    cases = [
        ("utf16", "utf8", np.full(3 * BLOCK, 0x41, np.uint16)),
        ("utf32", "utf8", np.full(3 * BLOCK, 0x41, np.uint32)),
        ("latin1", "utf8", np.full(3 * BLOCK, 0x41, np.uint8)),
    ]
    cases[0][2][BLOCK + 3: BLOCK + 5] = [0xD83C, 0xDF89]   # surrogate pair
    cases[1][2][BLOCK + 3] = 0x1F389                        # astral cp
    cases[2][2][BLOCK + 3] = 0xE9                           # é high byte
    for src, dst, arr in cases:
        a = op.transcode_onepass(jnp.asarray(arr), len(arr), src=src,
                                 dst=dst)
        f = _reference(arr, len(arr), src, dst)
        _assert_identical(a, f, (src, dst))


def test_onepass_utf32_garbage_does_not_ride_the_skip():
    """int32-wrapped garbage (0xFFFFFFFF reads negative inside the
    kernel) must not pass the per-tile ASCII predicate."""
    arr = np.full(2 * BLOCK, 0x41, np.uint32)
    arr[BLOCK + 1] = 0xFFFFFFFF
    a = op.transcode_onepass(jnp.asarray(arr), len(arr), src="utf32",
                             dst="utf8")
    f = _reference(arr, len(arr), "utf32", "utf8")
    _assert_identical(a, f, "utf32-garbage")
    assert int(a.status) == BLOCK + 1


# ---------------------------------------------------------------------------
# Tile-class dispatch: the ≤2-byte class (DESIGN.md §9).


def test_onepass_two_byte_straddling_class_transitions():
    """2-byte sequences straddling every (ASCII | ≤2-byte | general)
    class transition: tile 0 is pure ASCII, tile 1 pure 2-byte (the
    ≤2-byte class), tile 2 holds 3-byte CJK (general), with a 2-byte
    character split across BOTH tile boundaries.  The per-tile classes
    differ but the compact output must stay bit-identical to the
    reference."""
    buf = np.full(3 * BLOCK, 0x61, np.uint8)
    two = np.frombuffer("ب".encode("utf-8"), np.uint8)        # 0xD8 0xA8
    buf[BLOCK + 2: 2 * BLOCK - 2: 2] = two[0]
    buf[BLOCK + 3: 2 * BLOCK - 1: 2] = two[1]
    cjk = np.frombuffer("中".encode("utf-8"), np.uint8)
    buf[2 * BLOCK + 10: 2 * BLOCK + 13] = cjk
    # Straddle ASCII->class2: lead at BLOCK-1, continuation at BLOCK.
    buf[BLOCK - 1], buf[BLOCK] = two[0], two[1]
    # Straddle class2->general: lead at 2*BLOCK-1, continuation after.
    buf[2 * BLOCK - 1], buf[2 * BLOCK] = two[0], two[1]
    for errors in ("strict", "replace"):
        a = _u8(jnp.asarray(buf), len(buf), errors=errors)
        f = _reference(buf, len(buf), errors=errors)
        _assert_identical(a, f, errors)
    want = np.frombuffer(bytes(buf).decode("utf-8").encode("utf-16-le"),
                         np.uint16)
    assert int(a.count) == len(want)
    assert np.array_equal(np.asarray(a.buffer)[: len(want)], want)


@pytest.mark.parametrize("tail", [b"\xe4\xb8", b"\xf0\x9f\x92", b"\xe4",
                                  b"\xf0"])
def test_onepass_class2_tile_with_wide_lead_inflow(tail):
    """A tile of pure 2-byte content whose PREVIOUS tile ends in a
    truncated 3-/4-byte lead: the inflow window disqualifies the ≤2-byte
    class (its 1-lane claim logic cannot represent the wide lead's
    claim), so the general path must handle the boundary — bit-identical
    to the reference, with the truncated lead's error located in its own
    tile."""
    two = np.frombuffer("ب".encode("utf-8"), np.uint8)
    for errors in ("strict", "replace"):
        buf = np.full(3 * BLOCK, 0x61, np.uint8)
        buf[BLOCK + 2: 2 * BLOCK - 2: 2] = two[0]
        buf[BLOCK + 3: 2 * BLOCK - 1: 2] = two[1]
        buf[BLOCK - len(tail): BLOCK] = np.frombuffer(tail, np.uint8)
        a = _u8(jnp.asarray(buf), len(buf), errors=errors)
        f = _reference(buf, len(buf), errors=errors)
        _assert_identical(a, f, (tail, errors))


def test_onepass_surrogate_flood_not_claimed_by_class2():
    """UTF-16 surrogate-flood garbage (every lane a lone or paired
    surrogate half) sits entirely OUTSIDE the ≤2-byte class predicate:
    the general path must classify it, and the first unpaired half's
    offset must match the reference — with a clean ≤2-byte tile right
    after the
    flood taking the class without inheriting any claim."""
    rng = np.random.default_rng(20260809)
    arr = np.full(3 * BLOCK, 0x41, np.uint16)
    arr[BLOCK: 2 * BLOCK] = rng.integers(0xD800, 0xE000,
                                         BLOCK).astype(np.uint16)
    arr[2 * BLOCK:] = rng.integers(0x80, 0x800, BLOCK).astype(np.uint16)
    for errors in ("strict", "replace"):
        a = op.transcode_onepass(jnp.asarray(arr), len(arr), src="utf16",
                                 dst="utf8", errors=errors)
        f = _reference(arr, len(arr), "utf16", "utf8", errors=errors)
        _assert_identical(a, f, errors)


@pytest.mark.parametrize("src,dst", tc.PAIRS)
@pytest.mark.parametrize("errors", ["strict", "replace"])
def test_onepass_class_dispatch_on_off_bit_identity_fuzz(src, dst, errors):
    """Class-on (ascii_fastpath=True: three-way dispatch) vs class-off
    (False: general path only) bit-identity fuzz across all 12 cells ×
    errors policies, with values biased INTO the ≤2-byte class (plus
    out-of-class contamination at tile granularity) so the class-2
    branch actually fires and disagreements cannot hide in the general
    path."""
    rng = np.random.default_rng(20260808)
    codec = stages.get_codec(src)
    cap = 4 * BLOCK
    for trial in range(3):
        if codec.itemsize == 1:
            # Bytes below 0xE0: ASCII + 2-byte leads + continuations.
            arr = rng.integers(0, 0xE0, cap)
        else:
            arr = rng.integers(0, 0x800, cap)
        # Contaminate one tile with full-range garbage and one with pure
        # ASCII so all three classes appear in one buffer.
        arr[BLOCK: 2 * BLOCK] = rng.integers(
            0, _GEN_HI[codec.itemsize], BLOCK)
        arr[2 * BLOCK: 3 * BLOCK] = rng.integers(0x20, 0x7F, BLOCK)
        arr = arr.astype(codec.dtype)
        n = int(rng.integers(3 * BLOCK, cap))
        on = op.transcode_onepass(jnp.asarray(arr), n, src=src, dst=dst,
                                  errors=errors, ascii_fastpath=True)
        off = op.transcode_onepass(jnp.asarray(arr), n, src=src, dst=dst,
                                   errors=errors, ascii_fastpath=False)
        _assert_identical(on, off, (src, dst, errors, trial, "on/off"))
        f = _reference(arr, n, src, dst, errors=errors)
        _assert_identical(on, f, (src, dst, errors, trial, "vs-reference"))


# ---------------------------------------------------------------------------
# Dispatch plumbing.


def test_default_strategy_is_onepass():
    assert tc.DEFAULT_STRATEGY == "onepass"
    assert "onepass" in tc.STRATEGIES
    b = synthetic.utf8_array("arabic", 500, seed=3)
    d = tc.transcode_utf8_to_utf16(jnp.asarray(b), len(b))
    e = tc.transcode(jnp.asarray(b), "utf16", src_format="utf8",
                     n_valid=len(b), strategy="onepass")
    _assert_identical(d, e, "default-dispatch")


def test_ragged_strategy_rejects_unknown():
    docs = [np.full(10, 0x41, np.uint8)]
    pk = packing.pack_documents(docs, dtype=np.uint8)
    with pytest.raises(ValueError, match="strategy"):
        tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                            strategy="windowed")
    with pytest.raises(ValueError, match="strategy"):
        tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                            strategy="blockparallel")


# ---------------------------------------------------------------------------
# Block geometry: one grid step handles a block of up to ``op.G_MAX``
# consecutive tiles (DESIGN.md §9).  Every case below runs at inputs of
# one tile, of fewer tiles than a block, of whole blocks, and of whole
# blocks plus a partial last one.

G_MAX = op.G_MAX
GEOMETRIES = {"one-tile": 1, "sub-block": 3,
              "partial-last-block": 2 * G_MAX + 3, "full-blocks": 3 * G_MAX}


def _block_shapes(eqn):
    """``(grid, [block shape of each 3-D operand])`` of a pallas_call."""
    gm = eqn.params["grid_mapping"]
    shapes = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in gm.block_mappings]
    return gm.grid, [s for s in shapes if len(s) == 3]


def _edges(ntiles):
    """Tile edges worth a probe: the first inside a block, the first
    between blocks, and the last one."""
    g = window.tiles_per_step(ntiles * BLOCK, G_MAX)
    edges = {BLOCK if g > 1 else None,
             g * BLOCK if g < ntiles else None,
             (ntiles - 1) * BLOCK or None}
    return sorted(e for e in edges if e)


def _vs_reference(buf, n, errors, src="utf8", dst="utf16"):
    a = op.transcode_onepass(jnp.asarray(buf), n, src=src, dst=dst,
                             errors=errors)
    f = _reference(buf, n, src, dst, errors=errors)
    _assert_identical(a, f, (len(buf), n, errors, src, dst))
    return a


def test_tiles_per_step_is_the_largest_power_of_two_that_fits():
    assert op.G_MAX & (op.G_MAX - 1) == 0
    for n in (0, 1, BLOCK, BLOCK + 1, 3 * BLOCK, 8 * BLOCK - 1,
              G_MAX * BLOCK, 100 * G_MAX * BLOCK):
        g = window.tiles_per_step(n, G_MAX)
        ntiles = max(1, -(-n // BLOCK))
        assert g & (g - 1) == 0 and g <= min(G_MAX, ntiles), (n, g)
        assert 2 * g > min(G_MAX, ntiles), (n, g)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_onepass_steps_over_blocks_of_tiles(geometry):
    """The launch's grid walks blocks of ``tiles_per_step`` tiles, with
    the tiles before and after each block as single-tile operands."""
    n = GEOMETRIES[geometry] * BLOCK - 7
    g = window.tiles_per_step(n, G_MAX)
    jaxpr = jax.make_jaxpr(lambda x: op.transcode_onepass(
        x, src="utf8", dst="utf16", ascii_fastpath=False))(
            jnp.zeros((n,), jnp.uint8)).jaxpr
    (eqn,) = pallas_eqns(jaxpr)
    grid, blocks = _block_shapes(eqn)
    assert grid == (-(-GEOMETRIES[geometry] // g),)
    assert blocks[:3] == [(1, 8, 128), (g, 8, 128), (1, 8, 128)]


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_ragged_steps_over_blocks_of_tiles(geometry):
    """The ragged kernel's grid walks blocks of ``tiles_per_step`` tiles
    up to ``RAGGED_G_MAX`` as the onepass kernel's does, whatever
    documents the tiles belong to."""
    ntiles = GEOMETRIES[geometry]
    docs = [np.full(1500, 0x41, np.uint8)] * (ntiles // 2) \
        + [np.full(7, 0x41, np.uint8)] * (ntiles % 2)
    pk = packing.pack_documents(docs, dtype=np.uint8)
    assert pk.data.shape[0] == ntiles * BLOCK
    g = window.tiles_per_step(ntiles * BLOCK, rt.RAGGED_G_MAX)
    jaxpr = jax.make_jaxpr(
        lambda d, o, l: rt.transcode_ragged(d, o, l, src="utf8",
                                            dst="utf16")
    )(jnp.asarray(pk.data), jnp.asarray(pk.offsets),
      jnp.asarray(pk.lengths)).jaxpr
    (eqn,) = pallas_eqns(jaxpr)
    grid, blocks = _block_shapes(eqn)
    assert grid == (-(-ntiles // g),)
    assert blocks[:3] == [(1, 8, 128), (g, 8, 128), (1, 8, 128)]


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_onepass_block_boundary_straddling(geometry, errors):
    """Characters and truncated leads straddling tile edges inside a
    block and between blocks: the per-tile prefix inside a block and
    the carry between blocks must put every window where the reference
    does."""
    ntiles = GEOMETRIES[geometry]
    probes = [b"\xf0\x9f\x92\xa9", b"\xe4\xb8\xad", b"\xc3\xa9",
              b"\xf0\x9f\x92", b"\xc3", b"\xed\xa0\x80"]
    for probe in probes:
        for back in range(1, len(probe) + 1):
            buf = np.full(ntiles * BLOCK, 0x41, np.uint8)
            # One tile has no edge: the probe runs off the stream's end.
            for e in _edges(ntiles) or [BLOCK]:
                lo = e - back
                piece = probe[: len(buf) - lo]
                buf[lo: lo + len(piece)] = np.frombuffer(piece, np.uint8)
            _vs_reference(buf, len(buf), errors)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_onepass_block_class_transitions(geometry):
    """Tiles cycling ASCII, ≤2-byte and general content, with 2-byte
    characters across every tile edge: blocks whose tiles mix classes
    take the general body, and the result stays bit-identical to the
    reference and to CPython."""
    ntiles = GEOMETRIES[geometry]
    two = np.frombuffer("ب".encode("utf-8"), np.uint8)
    cjk = np.frombuffer("中".encode("utf-8"), np.uint8)
    buf = np.full(ntiles * BLOCK, 0x61, np.uint8)
    for t in range(ntiles):
        lo = t * BLOCK
        if t % 3 == 1:
            buf[lo + 2: lo + BLOCK - 2: 2] = two[0]
            buf[lo + 3: lo + BLOCK - 1: 2] = two[1]
        elif t % 3 == 2:
            buf[lo + 10: lo + 13] = cjk
        if t:
            buf[lo - 1], buf[lo] = two
    for errors in ("strict", "replace"):
        a = _vs_reference(buf, len(buf), errors)
    want = np.frombuffer(bytes(buf).decode("utf-8").encode("utf-16-le"),
                         np.uint16)
    assert int(a.count) == len(want)
    assert np.array_equal(np.asarray(a.buffer)[: len(want)], want)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_onepass_block_ascii_after_multibyte_inflow(geometry):
    """Pure-ASCII tiles whose previous tile ends in lead or continuation
    bytes, at an edge inside a block and at one between blocks."""
    ntiles = GEOMETRIES[geometry]
    tails = [b"\xc3", b"\xf0\x9f\x92", b"\x80", b"\xc3\xa9"]
    for k, tail in enumerate(tails):
        buf = np.full(ntiles * BLOCK, 0x61, np.uint8)
        for e in _edges(ntiles) or [BLOCK]:
            buf[e - len(tail): e] = np.frombuffer(tail, np.uint8)
        for errors in ("strict", "replace"):
            _vs_reference(buf, len(buf), errors)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_onepass_block_n_valid_and_zero(geometry):
    """``n_valid`` ending inside the last tile, on a tile edge, and
    inside an earlier block; and ``n_valid=0`` at every geometry."""
    ntiles = GEOMETRIES[geometry]
    b = synthetic.utf8_array("chinese", ntiles * BLOCK, seed=11)
    buf = np.zeros(ntiles * BLOCK, np.uint8)
    buf[:] = b[: len(buf)] if len(b) >= len(buf) else np.pad(
        b, (0, len(buf) - len(b)))
    g = window.tiles_per_step(len(buf), G_MAX)
    for n in sorted({len(buf) - 5, (ntiles - 1) * BLOCK,
                     max(1, (ntiles - g) * BLOCK - 3), 0}):
        r = _vs_reference(buf, n, "strict")
        if n == 0:
            assert int(r.count) == 0 and int(r.status) == -1


def test_onepass_mixed_class_block_dispatches_general():
    """A block takes a class only when every tile of it would: ASCII next
    to ≤2-byte tiles takes the ≤2-byte body, and one wide tile sends the
    whole block to the general body — bit-identical to the reference
    either way."""
    from repro.kernels.stages import driver as sdrv
    utf8 = stages.get_codec("utf8")
    two = np.frombuffer("ب".encode("utf-8"), np.uint8)
    buf = np.full(4 * BLOCK, 0x61, np.uint8)
    buf[BLOCK: 2 * BLOCK: 2], buf[BLOCK + 1: 2 * BLOCK: 2] = two
    blk = jnp.asarray(buf.reshape(4, 8, 128).astype(np.int32))
    prev = jnp.concatenate([jnp.zeros((1, 8, 128), jnp.int32), blk[:-1]])
    assert bool(sdrv.ascii_tile_pred(blk[0], prev[0]))
    assert not bool(sdrv.ascii_tile_pred(blk, prev))
    assert bool(utf8.class2_pred(blk, prev))
    _vs_reference(buf, len(buf), "strict")
    buf[3 * BLOCK + 9: 3 * BLOCK + 12] = np.frombuffer(
        "中".encode("utf-8"), np.uint8)
    blk = jnp.asarray(buf.reshape(4, 8, 128).astype(np.int32))
    prev = jnp.concatenate([jnp.zeros((1, 8, 128), jnp.int32), blk[:-1]])
    assert bool(utf8.class2_pred(blk[:3], prev[:3]))
    assert not bool(utf8.class2_pred(blk, prev))
    for errors in ("strict", "replace"):
        _vs_reference(buf, len(buf), errors)


@pytest.mark.parametrize("errors", ["strict", "replace"])
@pytest.mark.parametrize("src,dst", [("utf8", "utf16"), ("utf16", "utf8"),
                                     ("utf8", "latin1"), ("utf32", "utf16")])
def test_onepass_block_fuzz_vs_reference(src, dst, errors):
    """Garbage and class-biased content over whole blocks plus a partial
    one: onepass (blocks of tiles) against the block-parallel reference
    (no tiles at all)."""
    rng = np.random.default_rng(20261018)
    codec = stages.get_codec(src)
    ntiles = GEOMETRIES["partial-last-block"]
    hi = _GEN_HI[codec.itemsize]
    for trial in range(2):
        arr = rng.integers(0, 0xE0 if codec.itemsize == 1 else 0x800,
                           ntiles * BLOCK)
        for t in rng.choice(ntiles, ntiles // 2, replace=False):
            kind = rng.integers(3)
            lo, hi_t = t * BLOCK, (t + 1) * BLOCK
            arr[lo:hi_t] = (rng.integers(0, hi, BLOCK) if kind == 0 else
                            rng.integers(0x20, 0x7F, BLOCK) if kind == 1
                            else arr[lo:hi_t])
        arr = arr.astype(codec.dtype)
        n = int(rng.integers((ntiles - 2) * BLOCK, ntiles * BLOCK))
        _vs_reference(arr, n, errors, src, dst)
