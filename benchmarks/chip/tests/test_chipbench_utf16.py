"""The UTF-16 bulk cell's traffic source (``drivers/single_document_utf16``)
and its control (``controls/cesu8``): seeded pools of whole characters,
counted as CPython counts them, and a control that breaks only
surrogate pairs."""

import hashlib
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import run  # noqa: E402

SEED = 2**31 + 4242
CONFIG_FILE = CHIP / "configs" / "bulk-utf16-utf8.json"
TRAFFIC_FILE = CHIP / "traffic" / "lipsum-multibyte.json"
CONFIG = json.loads(CONFIG_FILE.read_text())
TRAFFIC = json.loads(TRAFFIC_FILE.read_text())
CALL_BYTES = 1 << 16


def _driver():
    return run.load_module(CHIP / "drivers" / "single_document_utf16.py")


def _pool(seed=SEED, call_bytes=CALL_BYTES):
    config = dict(CONFIG, call_bytes=call_bytes)
    traffic = dict(TRAFFIC, pool=2 * len(TRAFFIC["profiles"]))
    return _driver().make_pool(config, traffic, seed)


def test_every_buffer_is_exactly_half_the_call_in_units():
    bufs, chars, kbytes = _pool()
    assert len(bufs) == len(chars) == len(kbytes) == 16
    for b in bufs:
        assert b.dtype == np.dtype("<u2")
        assert b.shape == (CALL_BYTES // 2,)


def test_valid_utf16_with_no_pair_cut_at_the_pad():
    bufs, _, _ = _pool()
    for b in bufs:
        b.tobytes().decode("utf-16-le")          # raises if a pair is cut
    # An odd length of emoji (all pairs): the last pair that fits, then
    # one space where half a pair would not fit.
    units, chars, utf8 = _driver().text("lipsum", "emoji", 4097, SEED)
    assert units.shape == (4097,) and units[-1] == 0x20
    assert 0xDC00 <= units[-2] < 0xE000
    assert (chars, utf8) == (2048 + 1, 4 * 2048 + 1)
    assert len(units.tobytes().decode("utf-16-le")) == chars


def test_counts_and_kernel_bytes_are_cpythons():
    bufs, chars, kbytes = _pool()
    for b, c, k in zip(bufs, chars, kbytes):
        text = b.tobytes().decode("utf-16-le")
        assert c == len(text)
        assert k == CALL_BYTES + len(text.encode("utf-8"))
    # The emoji mix is all pairs: half the units are characters.
    assert CALL_BYTES // 4 in chars


def test_profiles_cycle_over_the_pool_in_a_seeded_order():
    bufs, _, _ = _pool()
    distinct = {id(b) for b in bufs}
    assert len(distinct) == len(TRAFFIC["profiles"])
    assert all(sum(id(b) == i for b in bufs) == 2 for i in distinct)


DIGEST = """
import hashlib, json, sys
sys.path.insert(0, {chip!r})
import run
d = run.load_module(run.HERE / "drivers" / "single_document_utf16.py")
config = dict(json.load(open({config!r})), call_bytes={call})
bufs, chars, kbytes = d.make_pool(config, json.load(open({traffic!r})),
                                  {seed})
h = hashlib.md5()
for b in bufs:
    h.update(b.tobytes())
h.update(repr((chars, kbytes)).encode())
print(h.hexdigest())
"""


def test_same_units_whatever_the_hash_seed():
    code = DIGEST.format(chip=str(CHIP), seed=SEED, call=CALL_BYTES,
                         config=str(CONFIG_FILE), traffic=str(TRAFFIC_FILE))
    out = []
    for hs in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=hs)
        out.append(subprocess.run([sys.executable, "-c", code], env=env,
                                  capture_output=True, text=True,
                                  check=True, timeout=120).stdout.strip())
    assert out[0] == out[1] and len(out[0]) == 32


def test_other_seed_other_units():
    a, _, _ = _pool(SEED)
    b, _, _ = _pool(SEED + 1)
    digest = [hashlib.md5(b"".join(x.tobytes() for x in p)).hexdigest()
              for p in (a, b)]
    assert digest[0] != digest[1]
    for p in ("hindi", "emoji"):
        x = _driver().text("lipsum", p, 4096, SEED)[0]
        y = _driver().text("lipsum", p, 4096, SEED + 1)[0]
        assert not np.array_equal(x, y)


def _utf16(text):
    return np.frombuffer(text.encode("utf-16-le"), np.uint16)


def test_the_cesu8_control_narrows_only_supplementary_characters():
    cesu8 = run.load_module(CHIP / "controls" / "cesu8.py")
    text = "aЖ一\U0001F600z"
    r = cesu8.transcode(_utf16(text), "utf8", src_format="utf16",
                        errors="strict")
    assert (int(r.count), int(r.status)) == (13, -1)
    buf = np.asarray(r.buffer)
    assert buf.shape == (3 * 6,)
    assert buf[:13].tobytes() == (b"a\xd0\x96\xe4\xb8\x80"
                                  b"\xed\xa0\xbd\xed\xb8\x80z")
    # Inside the Basic Multilingual Plane it is UTF-8.
    bmp = "aЖ一z"
    r = cesu8.transcode(_utf16(bmp), "utf8", src_format="utf16",
                        errors="strict")
    assert np.asarray(r.buffer)[:int(r.count)].tobytes() == bmp.encode()
    lone = np.asarray([0x61, 0x62, 0xDC00], np.uint16)
    bad = cesu8.transcode(lone, "utf8", src_format="utf16", errors="strict")
    assert (int(bad.count), int(bad.status)) == (0, 2)


@pytest.mark.parametrize("pair", [("utf8", "utf16"), ("utf16", "utf16")])
def test_the_cesu8_control_refuses_other_directions(pair):
    cesu8 = run.load_module(CHIP / "controls" / "cesu8.py")
    with pytest.raises(ValueError, match="UTF-16 to UTF-8 only"):
        cesu8.transcode(_utf16("a"), pair[1], src_format=pair[0],
                        errors="strict")
