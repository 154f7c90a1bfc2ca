"""A control for UTF-16 to UTF-8: the plain reference in the program's
place, one step below UTF-8.  CESU-8 encodes each 16-bit unit on its
own, so a supplementary character (a surrogate pair) becomes two 3-byte
sequences where UTF-8 writes one 4-byte sequence.  Valid text with
supplementary characters, such as the lipsum emoji mix, comes out
wrong; text inside the Basic Multilingual Plane comes out right."""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Result(NamedTuple):
    buffer: object
    count: object
    status: object


def transcode(x, dst_format: str, *, src_format: str, errors: str, **_):
    """UTF-16LE ``x`` to CESU-8, in the program's result layout (a uint8
    buffer of three times the input's units, its count and its status)."""
    import jax

    if (src_format, dst_format) != ("utf16", "utf8"):
        raise ValueError("the CESU-8 control converts UTF-16 to UTF-8 only")
    u = np.asarray(jax.device_get(x)).astype("<u2")
    raw = u.tobytes()
    try:
        text, status = raw.decode("utf-16-le"), -1
    except UnicodeDecodeError as e:
        status = e.start // 2
        text = "" if errors == "strict" else raw.decode("utf-16-le", errors)
    # Each unit alone: UTF-32 keeps surrogate halves apart, and
    # surrogatepass writes each as its own 3-byte sequence.
    units = np.frombuffer(text.encode("utf-16-le"), "<u2").astype("<u4")
    out = np.frombuffer(units.tobytes().decode("utf-32-le", "surrogatepass")
                        .encode("utf-8", "surrogatepass"), np.uint8)
    buf = np.zeros(3 * u.shape[0], np.uint8)
    buf[:out.shape[0]] = out
    return Result(*jax.device_put((buf, np.int32(out.shape[0]),
                                   np.int32(status))))
