"""Seeded synthetic text after the paper's Table 4 corpora.

Lemire & Muła (arXiv:2109.10433) benchmark on lipsum files (Table 4a)
and on Wikipedia "Mars" articles (Table 4b).  What the transcoder's
speed depends on is each file's mix of UTF-8 sequence lengths, so a
profile here is that mix, as a percentage of characters per UTF-8
length (1, 2, 3, 4 bytes), plus the Unicode blocks its multi-byte
characters come from.

Every draw is seeded from ``(seed, crc32(table/profile))``: the same
seed gives the same bytes in every process, whatever ``PYTHONHASHSEED``
says.  Encoding is vectorised numpy, so a 4 MiB buffer takes tens of
milliseconds.
"""

from __future__ import annotations

import zlib

import numpy as np

# Code-point blocks per UTF-8 length class.
ASCII = (0x20, 0x7E)
BLOCKS = {
    "arabic2": (0x0621, 0x064A),
    "hebrew2": (0x05D0, 0x05EA),
    "cyrillic2": (0x0410, 0x044F),
    "latin2": (0x00C0, 0x00FF),
    "greek2": (0x0391, 0x03C9),
    "cjk3": (0x4E00, 0x9FA5),
    "kana3": (0x3041, 0x30FE),
    "hangul3": (0xAC00, 0xD7A3),
    "devanagari3": (0x0901, 0x0963),
    "thai3": (0x0E01, 0x0E5B),
    "emoji4": (0x1F300, 0x1F6FF),
}

# name: ((1-byte, 2-byte, 3-byte, 4-byte) % of characters, 2-byte block,
#        3-byte block)
TABLES = {
    # Table 4 (a): lipsum.
    "lipsum": {
        "arabic": ((22, 78, 0, 0), "arabic2", "cjk3"),
        "chinese": ((1, 0, 99, 0), "latin2", "cjk3"),
        "emoji": ((0, 0, 0, 100), "latin2", "cjk3"),
        "hebrew": ((22, 78, 0, 0), "hebrew2", "cjk3"),
        "hindi": ((16, 0, 84, 0), "latin2", "devanagari3"),
        "japanese": ((5, 0, 95, 0), "latin2", "kana3"),
        "korean": ((27, 1, 72, 0), "latin2", "hangul3"),
        "latin": ((100, 0, 0, 0), "latin2", "cjk3"),
        "russian": ((19, 81, 0, 0), "cyrillic2", "cjk3"),
    },
    # Table 4 (b): Wikipedia-Mars.
    "wiki": {
        "arabic": ((75, 25, 0, 0), "arabic2", "cjk3"),
        "chinese": ((84, 1, 15, 0), "latin2", "cjk3"),
        "czech": ((95, 5, 0, 0), "latin2", "cjk3"),
        "english": ((100, 0, 0, 0), "latin2", "cjk3"),
        "french": ((98, 2, 0, 0), "latin2", "cjk3"),
        "greek": ((74, 26, 0, 0), "greek2", "cjk3"),
        "hebrew": ((71, 29, 0, 0), "hebrew2", "cjk3"),
        "hindi": ((78, 0, 22, 0), "latin2", "devanagari3"),
        "japanese": ((80, 1, 19, 0), "latin2", "kana3"),
        "korean": ((82, 1, 17, 0), "latin2", "hangul3"),
        "russian": ((70, 30, 0, 0), "cyrillic2", "cjk3"),
        "thai": ((77, 0, 23, 0), "latin2", "thai3"),
    },
}

def rng_for(seed: int, *labels: str) -> np.random.Generator:
    """A generator keyed by the seed and a stable digest of ``labels``."""
    key = zlib.crc32("/".join(labels).encode())
    return np.random.default_rng([int(seed) % (1 << 64), key])


def codepoints(table: str, profile: str, n_chars: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n_chars`` code points with the profile's length mix (int32)."""
    pct, block2, block3 = TABLES[table][profile]
    p = np.asarray(pct, np.float64) / sum(pct)
    cls = rng.choice(4, size=n_chars, p=p)
    blocks = (ASCII, BLOCKS[block2], BLOCKS[block3], BLOCKS["emoji4"])
    lo = np.asarray([b[0] for b in blocks], np.int64)[cls]
    span = np.asarray([b[1] - b[0] + 1 for b in blocks], np.int64)[cls]
    return (lo + rng.integers(0, 1 << 30, n_chars) % span).astype(np.int32)


def utf8_lengths(cp: np.ndarray) -> np.ndarray:
    """UTF-8 sequence length of each code point (1..4)."""
    return (1 + (cp >= 0x80).astype(np.int32) + (cp >= 0x800)
            + (cp >= 0x10000))


def utf8_encode(cp: np.ndarray) -> np.ndarray:
    """UTF-8 bytes of ``cp`` (no surrogates among them) as uint8."""
    cp = cp.astype(np.uint32)
    n = utf8_lengths(cp)
    out = np.zeros((cp.shape[0], 4), np.uint8)
    lead_mark = np.asarray([0, 0x00, 0xC0, 0xE0, 0xF0], np.uint32)[n]
    shift = 6 * (n - 1).astype(np.uint32)
    out[:, 0] = (lead_mark | (cp >> shift)).astype(np.uint8)
    for k in range(1, 4):
        s = 6 * np.maximum(n - 1 - k, 0).astype(np.uint32)
        out[:, k] = (0x80 | ((cp >> s) & 0x3F)).astype(np.uint8)
    return out[np.arange(4)[None, :] < n[:, None]]


def text(table: str, profile: str, n_bytes: int, seed: int,
         *labels: str) -> tuple[np.ndarray, int]:
    """Exactly ``n_bytes`` of UTF-8 in the profile's mix: characters up to
    the last whole one that fits, then ASCII spaces.  Returns ``(bytes as
    uint8, characters)``."""
    pct = TABLES[table][profile][0]
    mean = sum((k + 1) * p for k, p in enumerate(pct)) / sum(pct)
    rng = rng_for(seed, table, profile, *labels)
    cp = codepoints(table, profile, int(n_bytes / mean * 1.02) + 16, rng)
    ends = np.cumsum(utf8_lengths(cp))
    keep = int(np.searchsorted(ends, n_bytes, side="right"))
    body = utf8_encode(cp[:keep])
    pad = n_bytes - body.shape[0]
    return (np.concatenate([body, np.full(pad, 0x20, np.uint8)]),
            keep + pad)


def cut_documents(stream: np.ndarray, lengths: np.ndarray) -> list:
    """Split a UTF-8 stream into documents of about ``lengths`` bytes,
    each cut back to a character boundary.  Returns byte views."""
    docs, lo = [], 0
    for n in lengths:
        hi = min(lo + int(n), stream.shape[0])
        while hi < stream.shape[0] and (stream[hi] & 0xC0) == 0x80:
            hi -= 1
        docs.append(stream[lo:hi])
        lo = hi
    return docs


def malform(buf: np.ndarray, rng: np.random.Generator,
            sequences: list) -> np.ndarray:
    """A copy of ``buf`` with one malformed sequence, drawn from
    ``sequences`` (hex strings such as ``"ed a0 80"``), inserted at a
    character boundary drawn from ``rng``; the copy is invalid UTF-8."""
    seq = np.frombuffer(bytes.fromhex(
        sequences[int(rng.integers(0, len(sequences)))]), np.uint8)
    starts = np.flatnonzero((buf & 0xC0) != 0x80)
    at = int(rng.choice(np.append(starts, buf.shape[0])))
    return np.concatenate([buf[:at], seq, buf[at:]])
