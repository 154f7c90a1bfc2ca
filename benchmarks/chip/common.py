"""Pieces every driver shares: the per-call record, the seeded sample of
results kept for the comparison, and the comparison itself."""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import pathlib
from typing import NamedTuple

import numpy as np


class Record(NamedTuple):
    """What one call of the window did."""

    chars: int          # characters of its input, counted at generation
    kernel_bytes: int   # bytes its kernel launch needs (0: no launch)


HERE = pathlib.Path(__file__).resolve().parent


def entry(config: dict):
    """The function the configuration drives: ``module:name`` of the
    program, or ``path.py:name`` of a file of the benchmark (a control)."""
    target, name = config["entry"].split(":")
    if not target.endswith(".py"):
        return getattr(importlib.import_module(target), name)
    spec = importlib.util.spec_from_file_location(
        "bench_entry_" + pathlib.Path(target).stem, HERE / target)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, name)


def kernel_bytes(in_bytes: int, out_units: int, unit_bytes: int) -> int:
    """Bytes one launch must move through HBM at the least: its live input
    read once and the output units of its result written once.  Tile
    padding and capacity slack are not counted."""
    return int(in_bytes) + int(out_units) * int(unit_bytes)


def utf8_counts(buf: np.ndarray) -> tuple[int, int]:
    """``(characters, UTF-16 units)`` of a UTF-8 buffer, as CPython
    decodes it with ``errors="replace"``."""
    try:
        buf.tobytes().decode("utf-8")
    except UnicodeDecodeError:
        text = buf.tobytes().decode("utf-8", "replace")
        return len(text), len(text.encode("utf-16-le")) // 2
    # Valid: each non-continuation byte starts one character, and each
    # 4-byte one becomes a surrogate pair.
    chars = int(np.count_nonzero((buf & 0xC0) != 0x80))
    return chars, chars + int(np.count_nonzero(buf >= 0xF0))


class NoSpans:
    """Spans that record nothing (warm-up calls)."""

    @contextlib.contextmanager
    def span(self, _name: str):
        yield


class Reservoir:
    """A uniform sample of ``size`` items out of a stream, drawn from the
    seed (Algorithm R), so that the comparison covers the whole window
    without keeping every result."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = size, rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        k = int(self.rng.integers(0, self.seen))
        if k < self.size:
            self.items[k] = item


class Tally:
    """Mismatches against the reference, by kind; each has the limit 0:
    the comparison is exact."""

    KINDS = ("wrong_status", "wrong_count", "wrong_units")

    def __init__(self):
        self.n = dict.fromkeys(self.KINDS, 0)
        self.compared = 0

    def document(self, want_units, want_status: int, got: np.ndarray,
                 count: int, status: int) -> None:
        """One document: ``got`` is its output buffer from its start, at
        least ``count`` units long where the program kept them."""
        self.compared += 1
        self.n["wrong_status"] += int(status != want_status)
        if want_units is None:           # strict and invalid: no output
            return
        n = len(want_units)
        self.n["wrong_count"] += int(count != n)
        got = got[:min(count, n)]
        self.n["wrong_units"] += int(
            np.count_nonzero(got != want_units[:got.shape[0]])
            + abs(n - got.shape[0]))

    def checks(self) -> dict:
        out = {k: (v, 0) for k, v in self.n.items()}
        # A run that compared nothing proves nothing.
        out["uncompared"] = (int(self.compared == 0), 0)
        return out
