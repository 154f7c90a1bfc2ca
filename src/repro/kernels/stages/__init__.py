"""Codec stages: per-format decode/encode tile bodies + the generic
driver (DESIGN.md §8).

The registry below is the single source of truth for which formats the
single-stream and ragged Pallas kernels speak.  Every (src, dst) pair
with ``src != dst`` is a valid composition of :func:`driver.onepass_tile`
(transcode) and :func:`driver.count_tile` (scan); the classic
UTF-8→UTF-16 and UTF-16→UTF-8 kernels are just two cells of this matrix.
"""

from __future__ import annotations

from repro.kernels.stages import driver
from repro.kernels.stages import latin1 as s_latin1
from repro.kernels.stages import utf16 as s_utf16
from repro.kernels.stages import utf32 as s_utf32
from repro.kernels.stages import utf8 as s_utf8
from repro.kernels.stages.driver import (  # noqa: F401  (re-export)
    BLOCK, LANES, ROWS, Codec, ascii_tile_pred, count_decoded,
    count_decoded2, count_tile, decode_once, decode_once2, onepass_tile,
    stage_decoded, stage_decoded2, stage_units, stage_units2, stage_width)

import jax.numpy as jnp


UTF8 = Codec(
    name="utf8",
    dtype=jnp.uint8,
    itemsize=1,
    decode=s_utf8.speculative_decode,
    analyze=s_utf8.analyze_tile,
    unit_len=s_utf8.unit_len,
    encode=s_utf8.encode_units,
    max_speculative_cp=s_utf8.MAX_SPECULATIVE_CP,
    py_unit_len=s_utf8.py_unit_len,
    # Keiser-Lemire nibble-table detector: rides along with the
    # maximal-subpart locator in the count pass's validation.
    extra_err=s_utf8.kl_error_tile,
    max_lookback=3,
    class2_pred=s_utf8.class2_pred,
    decode2=s_utf8.decode2,
    analyze2=s_utf8.analyze2,
    # UTF-8's class-2 analysis substitutes U+FFFD for in-class garbage
    # (stray continuations, truncated 2-byte sequences, C0/C1), so the
    # class stage window must cover the replacement character's encoding.
    class2_replaces=True,
)

UTF16 = Codec(
    name="utf16",
    dtype=jnp.uint16,
    itemsize=2,
    decode=s_utf16.speculative_decode,
    analyze=s_utf16.analyze_tile,
    unit_len=s_utf16.unit_len,
    encode=s_utf16.encode_units,
    max_speculative_cp=s_utf16.MAX_SPECULATIVE_CP,
    py_unit_len=s_utf16.py_unit_len,
    # Only a trailing high surrogate can reach across a tile boundary.
    max_lookback=1,
    class2_pred=s_utf16.class2_pred,
    decode2=s_utf16.decode2,
    analyze2=s_utf16.analyze2,
    class2_lanes_lead=True,
)

UTF32 = Codec(
    name="utf32",
    dtype=jnp.uint32,
    itemsize=4,
    decode=s_utf32.speculative_decode,
    analyze=s_utf32.analyze_tile,
    unit_len=s_utf32.unit_len,
    encode=s_utf32.encode_units,
    max_speculative_cp=s_utf32.MAX_SPECULATIVE_CP,
    py_unit_len=s_utf32.py_unit_len,
    # Fixed-width source: characters never span a tile boundary.
    max_lookback=0,
    class2_pred=s_utf32.class2_pred,
    decode2=s_utf32.decode2,
    analyze2=s_utf32.analyze2,
    lanes_lead=True,
    class2_lanes_lead=True,
)

LATIN1 = Codec(
    name="latin1",
    dtype=jnp.uint8,
    itemsize=1,
    decode=s_latin1.speculative_decode,
    analyze=s_latin1.analyze_tile,
    unit_len=s_latin1.unit_len,
    encode=s_latin1.encode_units,
    max_speculative_cp=s_latin1.MAX_SPECULATIVE_CP,
    py_unit_len=s_latin1.py_unit_len,
    encode_bad=s_latin1.encode_bad,
    # Fixed-width source; the general path is already 2-byte-max work,
    # so the ≤2-byte class is disabled (class2_pred=None).
    max_lookback=0,
    lanes_lead=True,
)

CODECS = {c.name: c for c in (UTF8, UTF16, UTF32, LATIN1)}

# Output capacity per input element: the single definition lives next to
# the public dispatch (``repro.core.transcode``); the kernel registry and
# the block-parallel reference share it so their static buffer
# conventions can never drift apart.
from repro.core.transcode import CAP_FACTOR, PAIRS  # noqa: E402,F401


def get_codec(name: str) -> Codec:
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(
            f"unknown format {name!r}; supported: {sorted(CODECS)}")


def get_pair(src: str, dst: str):
    """Resolve a (src, dst) format pair to ``(src_codec, dst_codec,
    cap_factor)``; rejects src == dst and unknown names."""
    if (src, dst) not in CAP_FACTOR:
        raise ValueError(
            f"unsupported format pair {src!r} -> {dst!r}; "
            f"supported pairs: {list(PAIRS)}")
    return CODECS[src], CODECS[dst], CAP_FACTOR[(src, dst)]
