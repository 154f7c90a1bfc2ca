"""The transcode kernels (``transcode_onepass`` / ``scan_onepass``):
equivalence with the pure-jnp ``blockparallel`` and paper ``windowed``
references and CPython ``codecs``, edge cases, and HBM-traffic shape
checks (narrow ingress, no full-capacity int32 between decode and
compaction)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from _jaxpr import iter_eqns, pallas_eqns

from repro.core import transcode as tc
from repro.data import pipeline, synthetic
from repro.kernels import onepass_transcode as op
from repro.kernels import ops, runtime

LIPSUM_LANGS = ["arabic", "chinese", "emoji", "hebrew", "hindi",
                "japanese", "korean", "latin", "russian"]


def _u8(x, n_valid=None, **kw):
    """UTF-8 -> UTF-16 through the kernel."""
    return op.transcode_onepass(x, n_valid, src="utf8", dst="utf16", **kw)


def _u16(x, n_valid=None, **kw):
    """UTF-16 -> UTF-8 through the kernel."""
    return op.transcode_onepass(x, n_valid, src="utf16", dst="utf8", **kw)


def _utf8(lang, n_chars, seed=0):
    return synthetic.utf8_array(lang, n_chars, seed)


def _utf16(lang, n_chars, seed=0):
    return synthetic.utf16_units(lang, n_chars, seed)


def _unpack(res):
    out, cnt, status = res
    return np.asarray(out)[: int(cnt)], int(cnt), int(status)


def _py_err_start(raw: bytes):
    """Python's exc.start for a UTF-8 stream, -1 when valid."""
    try:
        raw.decode("utf-8")
        return -1
    except UnicodeDecodeError as e:
        return e.start


def _py_err_start16(units: np.ndarray):
    """Python's exc.start // 2 for a UTF-16LE stream, -1 when valid."""
    try:
        units.astype(np.uint16).tobytes().decode("utf-16-le")
        return -1
    except UnicodeDecodeError as e:
        return e.start // 2


# ---------------------------------------------------------------------------
# Equivalence on every benchmark corpus


@pytest.mark.parametrize("lang", LIPSUM_LANGS)
def test_kernel_equals_blockparallel_and_windowed_utf8_to_utf16(lang):
    b = _utf8(lang, 1200, seed=11)
    n = len(b)
    got_f = _unpack(tc.transcode_utf8_to_utf16(
        jnp.asarray(b), n, strategy="onepass"))
    got_b = _unpack(tc.transcode_utf8_to_utf16(
        jnp.asarray(b.astype(np.int32)), n, strategy="blockparallel"))
    got_w = _unpack(tc.transcode_utf8_to_utf16(
        jnp.asarray(b.astype(np.int32)), n, strategy="windowed"))
    assert got_f[1] == got_b[1] == got_w[1]
    assert np.array_equal(got_f[0], got_b[0])
    assert np.array_equal(got_f[0], got_w[0])
    assert got_f[2] == got_b[2] == got_w[2] == -1
    # python oracle
    want = np.frombuffer(bytes(b).decode("utf-8").encode("utf-16-le"),
                         np.uint16)
    assert np.array_equal(got_f[0], want)


@pytest.mark.parametrize("lang", LIPSUM_LANGS)
def test_kernel_equals_blockparallel_and_windowed_utf16_to_utf8(lang):
    u = _utf16(lang, 1200, seed=11)
    n = len(u)
    got_f = _unpack(tc.transcode_utf16_to_utf8(
        jnp.asarray(u), n, strategy="onepass"))
    got_b = _unpack(tc.transcode_utf16_to_utf8(
        jnp.asarray(u.astype(np.int32)), n, strategy="blockparallel"))
    got_w = _unpack(tc.transcode_utf16_to_utf8(
        jnp.asarray(u.astype(np.int32)), n, strategy="windowed"))
    assert got_f[1] == got_b[1] == got_w[1]
    assert np.array_equal(got_f[0], got_b[0])
    assert np.array_equal(got_f[0], got_w[0])
    assert got_f[2] == got_b[2] == got_w[2] == -1
    want = np.frombuffer(
        u.tobytes().decode("utf-16-le").encode("utf-8"), np.uint8)
    assert np.array_equal(got_f[0], want)


# ---------------------------------------------------------------------------
# Property test: random valid + mutated-invalid streams


def test_kernel_equals_blockparallel_on_mutated_streams():
    rng = np.random.default_rng(7)
    langs = ["latin", "arabic", "chinese", "emoji"]
    fixed = 1536  # fixed buffer so all cases share one compilation
    for trial in range(24):
        b = _utf8(langs[trial % 4], 400, seed=trial)[:fixed]
        buf = np.zeros(fixed, np.uint8)
        buf[: len(b)] = b
        n = len(b)
        if trial % 3:  # two thirds of cases: corrupt 1-3 random bytes
            k = rng.integers(1, 4)
            buf[rng.integers(0, max(n, 1), k)] = rng.integers(0, 256, k)
        want_pos = _py_err_start(bytes(buf[:n]))
        got_f = _unpack(_u8(jnp.asarray(buf), n))
        got_b = _unpack(tc.utf8_to_utf16(
            jnp.asarray(buf.astype(np.int32)), n))
        assert got_f[1] == got_b[1], trial
        assert np.array_equal(got_f[0], got_b[0]), trial
        # single-scan status: kernel == blockparallel == Python exc.start
        assert got_f[2] == got_b[2] == want_pos, trial


def test_kernel_equals_blockparallel_on_mutated_utf16_streams():
    rng = np.random.default_rng(9)
    fixed = 1280
    for trial in range(16):
        u = _utf16(["latin", "emoji", "korean", "russian"][trial % 4],
                   400, seed=trial)[:fixed]
        buf = np.zeros(fixed, np.uint16)
        buf[: len(u)] = u
        n = len(u)
        if trial % 2:  # half the cases: corrupt 1-2 random units
            k = rng.integers(1, 3)
            buf[rng.integers(0, max(n, 1), k)] = \
                rng.integers(0, 1 << 16, k)
        want_pos = _py_err_start16(buf[:n])
        got_f = _unpack(_u16(jnp.asarray(buf), n))
        got_b = _unpack(tc.utf16_to_utf8(
            jnp.asarray(buf.astype(np.int32)), n))
        assert got_f[1] == got_b[1], trial
        assert np.array_equal(got_f[0], got_b[0]), trial
        assert got_f[2] == got_b[2] == want_pos, trial


# ---------------------------------------------------------------------------
# Edge cases


def test_kernel_speculative_worst_case_stage_width():
    """Invalid input dense in 4-byte leads makes EVERY byte of a tile a
    speculative 2-unit lead (2*BLOCK units per tile) — the per-tile stage
    must absorb that or base offsets desynchronize from blockparallel."""
    b = np.concatenate([np.full(1024, 0xF4, np.uint8),
                        np.full(1024, 0xF1, np.uint8)])
    got_f = _unpack(_u8(jnp.asarray(b), len(b)))
    got_b = _unpack(tc.utf8_to_utf16(jnp.asarray(b.astype(np.int32)),
                                     len(b)))
    assert got_f[1] == got_b[1]
    assert np.array_equal(got_f[0], got_b[0])
    assert got_f[2] == got_b[2] == 0  # invalid from the first byte
    # UTF-16 side: every unit a speculative 3-byte lane (valid stream of
    # U+E000) exactly fills the 3*BLOCK stage.
    u = np.full(2048, 0xE000, np.uint16)
    got_f = _unpack(_u16(jnp.asarray(u), len(u)))
    got_b = _unpack(tc.utf16_to_utf8(jnp.asarray(u.astype(np.int32)),
                                     len(u)))
    assert got_f[1] == got_b[1] == 3 * 2048
    assert np.array_equal(got_f[0], got_b[0])
    # VALID input overflow: a surrogate pair straddling the tile boundary
    # gives tile 0 a 4-byte lane with no compensating 0-lane in-tile, so
    # its total is 3*BLOCK + 1 — one past the naive stage bound.
    u = np.concatenate([np.full(1023, 0xE000, np.uint16),
                        np.asarray([0xD800, 0xDC00], np.uint16),
                        np.full(1023, 0x41, np.uint16)])
    got_f = _unpack(_u16(jnp.asarray(u), len(u)))
    got_b = _unpack(tc.utf16_to_utf8(jnp.asarray(u.astype(np.int32)),
                                     len(u)))
    want = np.frombuffer(
        u.tobytes().decode("utf-16-le").encode("utf-8"), np.uint8)
    assert got_f[1] == got_b[1] == len(want)
    assert np.array_equal(got_f[0], want)
    assert np.array_equal(got_b[0], want)
    assert got_f[2] == got_b[2] == -1
    # and the unpaired-high-surrogate flood (mixed 3-byte/4-byte lanes)
    u = np.full(2048, 0xD800, np.uint16)
    got_f = _unpack(_u16(jnp.asarray(u), len(u)))
    got_b = _unpack(tc.utf16_to_utf8(jnp.asarray(u.astype(np.int32)),
                                     len(u)))
    assert got_f[1] == got_b[1]
    assert np.array_equal(got_f[0], got_b[0])
    assert got_f[2] == got_b[2] == 0


def test_kernel_zero_length():
    out, cnt, status = _u8(jnp.zeros((0,), jnp.uint8), 0)
    assert out.shape == (0,) and int(cnt) == 0 and int(status) == -1
    out, cnt, status = _u16(jnp.zeros((0,), jnp.uint16), 0)
    assert out.shape == (0,) and int(cnt) == 0 and int(status) == -1


def test_kernel_n_valid_zero():
    b = jnp.asarray(np.full(64, 0xFF, np.uint8))  # garbage beyond n
    out, cnt, status = _u8(b, 0)
    assert int(cnt) == 0 and int(status) == -1


def test_kernel_tile_aligned_trailing_truncation():
    b = np.full(2048, 0x41, np.uint8)
    b[-1] = 0xC3  # lead byte truncated exactly at a tile boundary
    _, _, status = _u8(jnp.asarray(b), 2048)
    assert int(status) == 2047  # located at the truncated lead
    u = np.full(1024, 0x41, np.uint16)
    u[-1] = 0xD800  # lone high surrogate at the tile boundary
    _, _, status = _u16(jnp.asarray(u), 1024)
    assert int(status) == 1023


def test_kernel_cross_tile_characters():
    s = "A" * 1022 + "🎉" + "B" * 100  # 4-byte char straddles the boundary
    b = np.frombuffer(s.encode("utf-8"), np.uint8)
    out, cnt, status = _u8(jnp.asarray(b), len(b))
    want = np.frombuffer(s.encode("utf-16-le"), np.uint16)
    assert int(status) == -1
    assert np.array_equal(np.asarray(out)[: int(cnt)], want)

    u = np.full(2048, 0x41, np.int32)
    u[1023], u[1024] = 0xD83C, 0xDF89  # pair straddles the boundary
    out, cnt, status = _u16(jnp.asarray(u), 2048)
    want = np.frombuffer(
        u.astype(np.uint16).tobytes().decode("utf-16-le").encode("utf-8"),
        np.uint8)
    assert int(status) == -1
    assert np.array_equal(np.asarray(out)[: int(cnt)], want)


def test_kernel_ascii_fastpath_agrees_with_general():
    b = _utf8("latin", 500, seed=3)
    n = len(b)
    fast = _unpack(_u8(jnp.asarray(b), n))
    slow = _unpack(_u8(jnp.asarray(b), n,
                                          ascii_fastpath=False))
    assert fast[1] == slow[1] and fast[2] == slow[2]
    assert np.array_equal(fast[0], slow[0])


# ---------------------------------------------------------------------------
# HBM-traffic shape checks (acceptance: narrow ingress, nothing
# full-capacity int32 between decode and compaction)


def test_kernel_utf8_jaxpr_has_narrow_io_and_no_global_scatter():
    cap = 4096
    b = jnp.zeros((cap,), jnp.uint8)
    jaxpr = jax.make_jaxpr(
        lambda x: _u8(x, cap - 5, ascii_fastpath=False)
    )(b).jaxpr
    kernels = pallas_eqns(jaxpr)
    assert len(kernels) == 1  # one launch: count and write off one decode
    for eqn in kernels:
        # Ingress <= 1 byte/element: every large operand is uint8.
        for v in eqn.invars:
            if v.aval.size >= cap:
                assert v.aval.dtype.itemsize == 1, (v.aval,)
        # Between decode and compaction nothing full-capacity and int32
        # leaves the kernel: outputs are per-tile scalars or narrow lanes.
        for v in eqn.outvars:
            assert v.aval.dtype.itemsize <= 2 or v.aval.size < cap // 256, \
                (v.aval,)
    # Global compaction is gone: no scatter outside the kernels.
    names = {e.primitive.name for e in iter_eqns(jaxpr)}
    assert not any("scatter" in n for n in names), names


def test_kernel_utf16_jaxpr_has_narrow_io_and_no_global_scatter():
    cap_in = 2048
    u = jnp.zeros((cap_in,), jnp.uint16)
    jaxpr = jax.make_jaxpr(
        lambda x: _u16(x, cap_in - 5, ascii_fastpath=False)
    )(u).jaxpr
    kernels = pallas_eqns(jaxpr)
    assert len(kernels) == 1
    for eqn in kernels:
        for v in eqn.invars:
            if v.aval.size >= cap_in:
                assert v.aval.dtype.itemsize <= 2, (v.aval,)
        for v in eqn.outvars:
            assert v.aval.dtype.itemsize <= 2 or v.aval.size < cap_in // 256, \
                (v.aval,)
    names = {e.primitive.name for e in iter_eqns(jaxpr)}
    assert not any("scatter" in n for n in names), names


def test_blockparallel_kernel_path_is_the_contrast():
    """The pre-fusion kernel path DOES ship full-capacity int32 decode
    outputs through HBM — the discriminating contrast for the test above."""
    cap = 4096
    b = jnp.zeros((cap,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda x: ops.utf8_to_utf16(x, cap - 5, validate=False))(b).jaxpr
    wide = [
        v for e in pallas_eqns(jaxpr) for v in e.outvars
        if v.aval.dtype.itemsize == 4 and v.aval.size >= cap
    ]
    assert wide, "expected full-capacity int32 outputs on the legacy path"


# ---------------------------------------------------------------------------
# Validation fusion (acceptance): the kernel with validation makes exactly
# ONE scan over the input bytes — no standalone validate read — and the
# first_error_index matches Python's bytes.decode position.


def test_kernel_validation_is_single_scan_jaxpr():
    """Validation must ride along with the decode: turning validate on
    adds NO kernel launch and NO out-of-kernel read of the input bytes
    (the old standalone validate_kl pass showed up as full-capacity
    gathers outside pallas)."""
    cap = 4096
    b = jnp.zeros((cap,), jnp.uint8)
    jaxprs = {}
    for validate in (True, False):
        jaxprs[validate] = jax.make_jaxpr(
            lambda x, v=validate: _u8(
                x, cap - 5, validate=v, ascii_fastpath=False))(b).jaxpr
    for validate, jaxpr in jaxprs.items():
        kernels = pallas_eqns(jaxpr)
        # one launch, nothing else — validation adds no scan.
        assert len(kernels) == 1, (validate, len(kernels))
        # No out-of-kernel gather touches a capacity-sized operand (the
        # nibble-table lookups run inside the kernel).
        for eqn in iter_eqns(jaxpr):
            if "gather" in eqn.primitive.name:
                assert all(v.aval.size < cap for v in eqn.invars), \
                    (validate, eqn)


def test_kernel_scan_is_count_pass_only():
    """scan_onepass: validation + capacity in ONE pallas call."""
    cap = 2048
    jaxpr = jax.make_jaxpr(
        lambda x: op.scan_onepass(x, cap - 3, src="utf8", dst="utf16"))(
            jnp.zeros((cap,), jnp.uint8)).jaxpr
    assert len(pallas_eqns(jaxpr)) == 1
    jaxpr16 = jax.make_jaxpr(
        lambda x: op.scan_onepass(x, cap - 3, src="utf16", dst="utf8"))(
            jnp.zeros((cap,), jnp.uint16)).jaxpr
    assert len(pallas_eqns(jaxpr16)) == 1


def test_first_error_index_matches_python_on_fuzzed_corpus():
    """Acceptance: status == Python UnicodeDecodeError.start across a
    fuzzed corpus (valid, mutated, and adversarial-alphabet streams)."""
    rng = np.random.default_rng(42)
    fixed = 1536
    adversarial = np.array([0x41, 0x80, 0x9F, 0xA0, 0xBF, 0xC0, 0xC2,
                            0xE0, 0xED, 0xEE, 0xF0, 0xF4, 0xF5, 0xFF,
                            0x90, 0x8F], np.uint8)
    for trial in range(30):
        buf = np.zeros(fixed, np.uint8)
        if trial % 3 == 0:
            b = _utf8(["emoji", "chinese", "hebrew"][(trial // 3) % 3], 400,
                      seed=trial)[:fixed]
            buf[: len(b)] = b
            n = len(b)
            k = rng.integers(0, 4)
            if k:
                buf[rng.integers(0, n, k)] = rng.integers(0, 256, k)
        elif trial % 3 == 1:
            n = int(rng.integers(1, fixed))
            buf[:n] = rng.integers(0, 256, n)
        else:
            n = int(rng.integers(1, 64))
            buf[:n] = rng.choice(adversarial, n)
        want = _py_err_start(bytes(buf[:n]))
        _, _, status = _u8(jnp.asarray(buf), n)
        assert int(status) == want, (trial, bytes(buf[:n])[:20])
        count, sstatus = op.scan_onepass(jnp.asarray(buf), n, src="utf8",
                                          dst="utf16")
        assert int(sstatus) == want, trial
        bcount, bstatus = tc.scan_utf8(jnp.asarray(buf), n,
                                       strategy="blockparallel")
        assert int(sstatus) == int(bstatus) and int(count) == int(bcount)


def test_utf16_scan_status_matches_python():
    rng = np.random.default_rng(17)
    fixed = 1024
    for trial in range(12):
        buf = np.zeros(fixed, np.uint16)
        n = int(rng.integers(1, fixed))
        buf[:n] = rng.integers(0, 1 << 16, n)
        try:
            buf[:n].tobytes().decode("utf-16-le")
            want = -1
        except UnicodeDecodeError as e:
            want = e.start // 2
        count, status = op.scan_onepass(jnp.asarray(buf), n, src="utf16",
                                       dst="utf8")
        assert int(status) == want, trial
        bcount, bstatus = tc.scan_utf16(jnp.asarray(buf), n,
                                        strategy="blockparallel")
        assert int(status) == int(bstatus) and int(count) == int(bcount)


# ---------------------------------------------------------------------------
# errors="replace": U+FFFD per maximal subpart, CPython semantics


def test_kernel_replace_matches_python_utf8():
    rng = np.random.default_rng(5)
    fixed = 1536
    for trial in range(20):
        buf = np.zeros(fixed, np.uint8)
        if trial % 2:
            b = _utf8(["latin", "emoji", "arabic", "korean"][trial % 4],
                      400, seed=trial)[:fixed]
            buf[: len(b)] = b
            n = len(b)
            k = rng.integers(1, 5)
            buf[rng.integers(0, n, k)] = rng.integers(0, 256, k)
        else:
            n = int(rng.integers(1, fixed))
            buf[:n] = rng.integers(0, 256, n)
        want = np.frombuffer(
            bytes(buf[:n]).decode("utf-8", "replace").encode("utf-16-le"),
            np.uint16)
        got_f = _unpack(_u8(jnp.asarray(buf), n,
                                               errors="replace"))
        got_b = _unpack(tc.utf8_to_utf16(jnp.asarray(buf.astype(np.int32)),
                                         n, errors="replace"))
        assert np.array_equal(got_f[0], want), trial
        assert got_f[1] == got_b[1] == len(want), trial
        assert np.array_equal(got_b[0], want), trial
        assert got_f[2] == got_b[2] == _py_err_start(bytes(buf[:n])), trial


def test_kernel_replace_matches_python_utf16():
    rng = np.random.default_rng(23)
    fixed = 1280
    for trial in range(16):
        buf = np.zeros(fixed, np.uint16)
        if trial % 2:
            u = _utf16(["latin", "emoji"][trial % 2], 400, seed=trial)[:fixed]
            buf[: len(u)] = u
            n = len(u)
            k = rng.integers(1, 4)
            # surrogate-heavy corruption: the interesting class here
            buf[rng.integers(0, n, k)] = rng.integers(0xD800, 0xE000, k)
        else:
            n = int(rng.integers(1, fixed))
            buf[:n] = rng.integers(0, 1 << 16, n)
        want = np.frombuffer(
            buf[:n].tobytes().decode("utf-16-le", "replace").encode("utf-8"),
            np.uint8)
        got_f = _unpack(_u16(jnp.asarray(buf), n,
                                               errors="replace"))
        got_b = _unpack(tc.utf16_to_utf8(jnp.asarray(buf.astype(np.int32)),
                                         n, errors="replace"))
        assert np.array_equal(got_f[0], want), trial
        assert got_f[1] == got_b[1] == len(want), trial
        assert np.array_equal(got_b[0], want), trial
        assert got_f[2] == got_b[2] == _py_err_start16(buf[:n]), trial


def test_error_location_and_replace_across_tile_boundary():
    """Maximal subparts straddling the 1024-byte tile boundary: the
    claimed-byte chain reads the previous tile, the continuation checks
    read the next — both must agree with Python at every offset."""
    probes = [b"\xf0\x9f\x92", b"\xe4\xb8", b"\xc3", b"\x80\x80",
              b"\xed\xa0\x80", b"\xf4\x90\x80\x80"]
    for probe in probes:
        for pos in (1019, 1021, 1022, 1023, 1024, 1025):
            buf = np.full(2048, 0x41, np.uint8)
            buf[pos: pos + len(probe)] = np.frombuffer(probe, np.uint8)
            raw = bytes(buf)
            _, _, status = _u8(jnp.asarray(buf), 2048)
            assert int(status) == _py_err_start(raw), (probe, pos)
            got = _unpack(_u8(jnp.asarray(buf), 2048,
                                                 errors="replace"))
            want = np.frombuffer(
                raw.decode("utf-8", "replace").encode("utf-16-le"),
                np.uint16)
            assert np.array_equal(got[0], want), (probe, pos)


def test_replace_on_valid_input_equals_strict():
    b = _utf8("japanese", 800, seed=9)
    n = len(b)
    strict = _unpack(_u8(jnp.asarray(b), n))
    rep = _unpack(_u8(jnp.asarray(b), n,
                                         errors="replace"))
    assert strict[1] == rep[1] and strict[2] == rep[2] == -1
    assert np.array_equal(strict[0], rep[0])


def test_unknown_errors_policy_rejected():
    b = jnp.zeros((8,), jnp.uint8)
    with pytest.raises(ValueError):
        _u8(b, 8, errors="ignore")
    with pytest.raises(ValueError):
        tc.transcode_utf8_to_utf16(b, 8, strategy="windowed",
                                   errors="replace")


# ---------------------------------------------------------------------------
# Batched entry + interpret auto-detection


def test_batched_entry_matches_per_doc():
    L = 1536
    langs = ["latin", "chinese", "emoji"]
    docs = np.zeros((3, L), np.uint8)
    lens = []
    for i, lang in enumerate(langs):
        d = _utf8(lang, 300, seed=i)[:L]
        docs[i, : len(d)] = d
        lens.append(len(d))
    lens = np.asarray(lens, np.int32)
    out, cnt, status = pipeline.batch_utf8_to_utf16(docs, lens)
    assert out.shape == (3, L)
    for i in range(3):
        o, c, s = _u8(jnp.asarray(docs[i]), int(lens[i]))
        assert int(cnt[i]) == int(c) and int(status[i]) == int(s)
        assert np.array_equal(np.asarray(out[i])[: int(c)],
                              np.asarray(o)[: int(c)])

    units = np.zeros((2, 1024), np.uint16)
    ulens = []
    for i, lang in enumerate(["korean", "latin"]):
        d = _utf16(lang, 300, seed=i)[:1024]
        units[i, : len(d)] = d
        ulens.append(len(d))
    out, cnt, status = pipeline.batch_utf16_to_utf8(units, np.asarray(ulens))
    assert out.shape == (2, 3 * 1024)
    for i in range(2):
        o, c, s = _u16(jnp.asarray(units[i]), ulens[i])
        assert int(cnt[i]) == int(c) and int(status[i]) == int(s)
        assert np.array_equal(np.asarray(out[i])[: int(c)],
                              np.asarray(o)[: int(c)])


def test_interpret_autodetect():
    # This container has no TPU: kernels must auto-select interpret mode
    # and still execute (interpret=None throughout the public wrappers).
    assert runtime.default_interpret() == (jax.default_backend() != "tpu")
    assert runtime.resolve_interpret(None) == runtime.default_interpret()
    assert runtime.resolve_interpret(False) is False
    b = np.frombuffer("héllo wörld".encode("utf-8"), np.uint8)
    assert bool(ops.validate_utf8(jnp.asarray(b.astype(np.int32)), len(b)))
    out, cnt, err = ops.utf8_to_utf16(jnp.asarray(b.astype(np.int32)), len(b))
    assert not bool(err)
