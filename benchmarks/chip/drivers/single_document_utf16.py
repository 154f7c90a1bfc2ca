"""Bulk conversion of UTF-16LE documents held on the host: one buffer per
call.

The calls, the readback and the comparison are ``single_document``'s
(its ``State``, ``_call``, ``step`` and ``check``); this driver brings
the pool alone.  Each buffer is ``call_bytes // 2`` UTF-16LE units of a
Table 4 profile's code points: whole characters (a surrogate pair is
never cut), then U+0020 units up to the exact length, so every call has
one shape.

A mix (``traffic/<name>.json``) gives the Table 4 ``table`` and
``profiles`` and the ``pool`` size.
"""

from __future__ import annotations

import importlib.util
import pathlib
import sys

import numpy as np

import common
import corpus


def _sibling(stem: str):
    """A driver beside this one, imported once by its path (the name the
    harness gives it), whatever else is on ``sys.path``."""
    name = f"bench_drivers_{stem}"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, pathlib.Path(__file__).with_name(f"{stem}.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


_sd = _sibling("single_document")
State, _call, step, check = _sd.State, _sd._call, _sd.step, _sd.check


def utf16_units(cp: np.ndarray) -> np.ndarray:
    """UTF-16 code units per code point (1 or 2)."""
    return 1 + (cp >= 0x10000).astype(np.int64)


def utf16_encode(cp: np.ndarray) -> np.ndarray:
    """UTF-16LE units of ``cp`` (no surrogates among them) as uint16."""
    cp = cp.astype(np.int64)
    n = utf16_units(cp)
    v = cp - 0x10000
    out = np.stack([np.where(n == 2, 0xD800 | (v >> 10), cp),
                    0xDC00 | (v & 0x3FF)], axis=1).astype("<u2")
    return out[np.arange(2)[None, :] < n[:, None]]


def text(table: str, profile: str, n_units: int,
         seed: int) -> tuple[np.ndarray, int, int]:
    """Exactly ``n_units`` of UTF-16LE in the profile's mix: characters up
    to the last whole one that fits, then U+0020.  Returns ``(units as
    uint16, characters, UTF-8 bytes of the same text)``."""
    pct = corpus.TABLES[table][profile][0]
    mean = 1 + pct[3] / sum(pct)
    rng = corpus.rng_for(seed, table, profile, "bulk-utf16")
    cp = corpus.codepoints(table, profile, int(n_units / mean * 1.02) + 16,
                           rng)
    keep = int(np.searchsorted(np.cumsum(utf16_units(cp)), n_units,
                               side="right"))
    body = utf16_encode(cp[:keep])
    pad = n_units - body.shape[0]
    utf8 = int(corpus.utf8_lengths(cp[:keep]).sum()) + pad
    return (np.concatenate([body, np.full(pad, 0x20, "<u2")]), keep + pad,
            utf8)


def make_pool(config: dict, traffic: dict, seed: int):
    """The seeded pool: one buffer per profile, cycled over the pool's
    slots in a seeded order.  Returns ``(buffers, characters, kernel
    bytes)`` per slot."""
    n_units = config["call_bytes"] // 2
    base = {p: text(traffic["table"], p, n_units, seed)
            for p in traffic["profiles"]}
    rng = corpus.rng_for(seed, "bulk-utf16-order", *traffic["profiles"])
    n = traffic["pool"]
    slots = [traffic["profiles"][k % len(base)] for k in range(n)]
    order = [slots[k] for k in rng.permutation(n)]
    bufs, chars, kbytes = [], [], []
    for p in order:
        buf, c, utf8 = base[p]
        bufs.append(buf)
        chars.append(c)
        # An all-ASCII buffer skips the launch (the whole-buffer cond).
        kbytes.append(0 if int(buf.max()) < 0x80 else common.kernel_bytes(
            config["call_bytes"], utf8, 1))
    return bufs, chars, kbytes


def setup(config, traffic, seed, devices, reference):
    fn = common.entry(config)
    kwargs = dict(src_format=config["src_format"],
                  strategy=config["strategy"][str(len(devices))],
                  validate=config["validate"], errors=config["errors"])
    bufs, chars, kbytes = make_pool(config, traffic, seed)
    st = State(fn, kwargs, devices[0], reference, config, bufs, chars,
               kbytes)
    # Warm-up: every distinct buffer once through the whole call.
    for slot in {id(b): k for k, b in enumerate(bufs)}.values():
        _call(st, slot, common.NoSpans())
    rng = corpus.rng_for(seed, "bulk-utf16-sample")
    st.kept = [common.Reservoir(1, rng) for _ in bufs]
    st.i = 0
    return st
