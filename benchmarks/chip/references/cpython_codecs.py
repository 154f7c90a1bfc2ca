"""The plain reference: CPython's own codecs.

Given the input bytes of one document and the configuration's formats
and ``errors=`` policy, returns what a correct transcoder produces:
the output code units, and the status, which is -1 for a valid input
and otherwise ``UnicodeDecodeError.start`` in source code units.  Under
``errors="strict"`` an invalid input has no defined output, only its
status, so the units are ``None`` there.
"""

from __future__ import annotations

import numpy as np

CODECS = {"utf8": ("utf-8", 1, np.uint8), "utf16": ("utf-16-le", 2, np.uint16),
          "utf32": ("utf-32-le", 4, np.uint32)}


def transcode(raw: bytes, src: str, dst: str, errors: str):
    """``(units or None, status)`` for one document."""
    s_name, s_width, _ = CODECS[src]
    d_name, _, d_dtype = CODECS[dst]
    try:
        text = raw.decode(s_name)
        status = -1
    except UnicodeDecodeError as e:
        status = e.start // s_width
        if errors == "strict":
            return None, status
        text = raw.decode(s_name, errors)
    return np.frombuffer(text.encode(d_name), d_dtype), status
