"""Property-based tests: the transcoding core vs Python's certified codec.

Python's str.encode/bytes.decode is the oracle.  Hypothesis generates
arbitrary Unicode strings (all planes) and adversarial byte mutations;
both strategies (blockparallel + windowed) and both directions must agree
byte-exactly with the oracle, and must flag every invalid input the
oracle rejects.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import transcode as tc

SETTINGS = dict(max_examples=60, deadline=None)

text = st.text(
    alphabet=st.characters(min_codepoint=0, max_codepoint=0x10FFFF,
                           exclude_categories=("Cs",)),  # no lone surrogates
    max_size=80)


def _u8(s):
    return np.frombuffer(s.encode("utf-8"), np.uint8).astype(np.int32)


def _u16(s):
    return np.frombuffer(s.encode("utf-16-le"), np.uint16).astype(np.int32)


def _pad(a, n=8):
    out = np.zeros(max(len(a), n), np.int32)
    out[: len(a)] = a
    return out


@settings(**SETTINGS)
@given(text, st.sampled_from(["blockparallel", "windowed"]))
def test_utf8_to_utf16_matches_python(s, strategy):
    b, u = _u8(s), _u16(s)
    out, cnt, status = tc.transcode_utf8_to_utf16(
        jnp.asarray(_pad(b)), len(b), strategy=strategy)
    assert int(status) == -1, s
    got = np.asarray(out)[: int(cnt)]
    assert np.array_equal(got, u), (s, got[:10], u[:10])


@settings(**SETTINGS)
@given(text, st.sampled_from(["blockparallel", "windowed"]))
def test_utf16_to_utf8_matches_python(s, strategy):
    b, u = _u8(s), _u16(s)
    out, cnt, status = tc.transcode_utf16_to_utf8(
        jnp.asarray(_pad(u)), len(u), strategy=strategy)
    assert int(status) == -1, s
    got = np.asarray(out)[: int(cnt)]
    assert np.array_equal(got, b), s


@settings(**SETTINGS)
@given(text)
def test_utf8_to_utf32_roundtrip(s):
    b = _u8(s)
    cps = np.array([ord(c) for c in s], np.int32)
    out, cnt, status = tc.utf8_to_utf32(jnp.asarray(_pad(b)), len(b))
    assert int(status) == -1
    assert np.array_equal(np.asarray(out)[: int(cnt)], cps)
    # egress back to utf-8
    out8, cnt8, status8 = tc.utf32_to_utf8(jnp.asarray(_pad(cps)), len(cps))
    assert int(status8) == -1
    assert np.array_equal(np.asarray(out8)[: int(cnt8)], b)


@settings(**SETTINGS)
@given(st.binary(max_size=64))
def test_validation_agrees_with_python(raw):
    """Arbitrary bytes: validate_utf8 == python's decodability."""
    try:
        raw.decode("utf-8")
        valid = True
    except UnicodeDecodeError:
        valid = False
    b = _pad(np.frombuffer(raw, np.uint8).astype(np.int32))
    got = bool(tc.validate_utf8(jnp.asarray(b), len(raw)))
    assert got == valid, raw


@settings(**SETTINGS)
@given(st.binary(max_size=48))
def test_invalid_bytes_flagged_by_transcoder(raw):
    """Arbitrary bytes: status == Python's UnicodeDecodeError.start."""
    try:
        raw.decode("utf-8")
        want = -1
    except UnicodeDecodeError as e:
        want = e.start
    b = _pad(np.frombuffer(raw, np.uint8).astype(np.int32))
    _, _, status = tc.utf8_to_utf16(jnp.asarray(b), len(raw))
    assert int(status) == want, raw


@settings(**SETTINGS)
@given(st.binary(max_size=48))
def test_replace_matches_python_utf8(raw):
    """Arbitrary bytes: errors='replace' output == Python's, byte-exact,
    and the onepass kernel's status equals the blockparallel one."""
    want = np.frombuffer(
        raw.decode("utf-8", "replace").encode("utf-16-le"), np.uint16)
    cap = 128  # fixed capacity: all examples share one compilation
    buf = np.zeros(cap, np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, np.uint8)
    out, cnt, status = tc.utf8_to_utf16(
        jnp.asarray(buf.astype(np.int32)), len(raw), errors="replace")
    got = np.asarray(out)[: int(cnt)].astype(np.uint16)
    assert np.array_equal(got, want), raw
    fout, fcnt, fstatus = tc.transcode_utf8_to_utf16(
        jnp.asarray(buf), len(raw), strategy="onepass", errors="replace")
    assert int(fcnt) == int(cnt) and int(fstatus) == int(status), raw
    assert np.array_equal(np.asarray(fout)[: int(fcnt)], got), raw


@settings(**SETTINGS)
@given(st.lists(st.integers(0, 0xFFFF), max_size=40))
def test_replace_matches_python_utf16(units):
    raw = np.array(units, np.uint16)
    want = np.frombuffer(
        raw.tobytes().decode("utf-16-le", "replace").encode("utf-8"),
        np.uint8)
    cap = 64
    buf = np.zeros(cap, np.uint16)
    buf[: len(units)] = raw
    out, cnt, status = tc.utf16_to_utf8(
        jnp.asarray(buf.astype(np.int32)), len(units), errors="replace")
    got = np.asarray(out)[: int(cnt)].astype(np.uint8)
    assert np.array_equal(got, want), units
    fout, fcnt, fstatus = tc.transcode_utf16_to_utf8(
        jnp.asarray(buf), len(units), strategy="onepass", errors="replace")
    assert int(fcnt) == int(cnt) and int(fstatus) == int(status), units
    assert np.array_equal(np.asarray(fout)[: int(fcnt)], got), units


@settings(**SETTINGS)
@given(st.lists(st.integers(0, 0xFFFF), max_size=40))
def test_utf16_validation_agrees_with_python(units):
    raw = np.array(units, np.uint16).tobytes()
    try:
        raw.decode("utf-16-le")
        valid = True
    except UnicodeDecodeError:
        valid = False
    u = _pad(np.array(units, np.int32))
    got = bool(tc.validate_utf16(jnp.asarray(u), len(units)))
    assert got == valid, units


@settings(**SETTINGS)
@given(text)
def test_length_counting(s):
    b, u = _u8(s), _u16(s)
    assert int(tc.utf16_length_from_utf8(jnp.asarray(_pad(b)), len(b))) == len(u)
    assert int(tc.utf8_length_from_utf16(jnp.asarray(_pad(u)), len(u))) == len(b)
    assert int(tc.count_utf8_chars(jnp.asarray(_pad(b)), len(b))) == len(s)
