"""A control for UTF-16 output: the plain reference in the program's
place, one step below UTF-16.  UCS-2 gives every code point one 16-bit
unit (a code point above U+FFFF keeps its low 16 bits) where UTF-16
writes a surrogate pair.  Valid text with supplementary characters,
such as the lipsum emoji mix, comes out wrong; text inside the Basic
Multilingual Plane comes out right, so a cell of such text needs
another control."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Result(NamedTuple):
    buffer: object
    count: object
    status: object


def transcode(x, dst_format: str, *, src_format: str, errors: str, **_):
    """UTF-8 ``x`` to UCS-2, in the program's result layout (a buffer of
    twice the input's length, its count and its status)."""
    import jax

    if (src_format, dst_format) != ("utf8", "utf16"):
        raise ValueError("the UCS-2 control converts UTF-8 to UTF-16 only")
    raw = np.asarray(jax.device_get(x)).tobytes()
    try:
        text, status = raw.decode("utf-8"), -1
    except UnicodeDecodeError as e:
        status = e.start
        text = "" if errors == "strict" else raw.decode("utf-8", errors)
    units = np.frombuffer(text.encode("utf-32-le"), np.uint32).astype(
        np.uint16)
    buf = np.zeros(2 * len(raw), np.uint16)
    buf[:units.shape[0]] = units
    return Result(*jax.device_put((buf, np.int32(units.shape[0]),
                                   np.int32(status))))
