"""Bulk UTF-16LE -> UTF-8 through ``core.transcode.transcode`` with
``strategy="onepass"``, against CPython, at three blocks of ``G_MAX``
tiles: the shape of the bulk UTF-16 benchmark call, cut to what the
interpreter runs quickly.

Each Table 4a lipsum mix other than Latin fills the document, with a
surrogate pair across a tile edge inside the first block and another
across the edge between the two blocks: the pair's high half is the
last unit of one tile, its low half the first of the next, so the
decode folds it with one unit of lookahead across the edge.  The third
block holds the mix alone, so the Arabic, Hebrew and Russian mixes take
the ≤2-byte body there.  Lone surrogates at the same edges must give
CPython's status, in units.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import transcode as tc
from repro.data import synthetic
from repro.kernels import onepass_transcode as op
from repro.kernels import stages
from repro.kernels import window

BLOCK = stages.BLOCK
N = 3 * op.G_MAX * BLOCK               # three whole blocks of units
TILE_EDGE = BLOCK                      # inside the first block
BLOCK_EDGE = op.G_MAX * BLOCK          # between the first two blocks
PAIR = [0xD83D, 0xDE00]                # U+1F600
MIXES = ["arabic", "chinese", "emoji", "hebrew", "hindi", "japanese",
         "korean", "russian"]


def _units(lang, n, seed):
    """Exactly ``n`` UTF-16 units of the mix: whole characters, then
    spaces (a pair is never cut)."""
    cp = synthetic._sample_codepoints(synthetic.LANG_PROFILES[lang], n,
                                      np.random.default_rng(seed))
    u = np.frombuffer("".join(map(chr, cp)).encode("utf-16-le"), np.uint16)
    ends = np.cumsum(1 + (cp >= 0x10000))
    k = int(np.searchsorted(ends, n, side="right"))
    keep = int(ends[k - 1]) if k else 0
    return np.concatenate([u[:keep], np.full(n - keep, 0x20, np.uint16)])


def _document(lang, seed, at_edges):
    """``N`` units of ``lang`` with ``at_edges[k]`` written so that it
    ends at edge k's first unit (``[hi, lo]`` straddles the edge)."""
    edges = (TILE_EDGE, BLOCK_EDGE)
    parts, lo = [], 0
    for k, (edge, seq) in enumerate(zip(edges, at_edges)):
        start = edge - len(seq) + 1
        parts += [_units(lang, start - lo, seed + k),
                  np.asarray(seq, np.uint16)]
        lo = edge + 1
    parts.append(_units(lang, N - lo, seed + 7))
    doc = np.concatenate(parts)
    assert doc.shape == (N,)
    return doc


def _run(doc):
    r = tc.transcode(jnp.asarray(doc), "utf8", src_format="utf16",
                     strategy="onepass", validate=True, errors="strict")
    return np.asarray(r.buffer), int(r.count), int(r.status)


def test_the_call_runs_blocks_of_g_max_tiles():
    assert window.tiles_per_step(N, op.G_MAX) == op.G_MAX
    assert N // BLOCK == 3 * op.G_MAX


@pytest.mark.parametrize("lang", MIXES)
def test_lipsum_mix_matches_cpython(lang):
    doc = _document(lang, 1500 + MIXES.index(lang), [PAIR, PAIR])
    assert doc[TILE_EDGE - 1] == PAIR[0] and doc[TILE_EDGE] == PAIR[1]
    assert doc[BLOCK_EDGE - 1] == PAIR[0] and doc[BLOCK_EDGE] == PAIR[1]
    want = doc.tobytes().decode("utf-16-le").encode("utf-8")
    buf, count, status = _run(doc)
    assert status == -1
    assert count == len(want)
    assert buf.shape == (3 * N,)
    assert buf[:count].tobytes() == want


@pytest.mark.parametrize("lone,edge", [
    (0xD83D, "tile"),      # a high half that ends a tile, no low after it
    (0xDE00, "block"),     # a low half that starts a block, no high before
])
def test_lone_surrogate_status_is_cpythons(lone, edge):
    # The lone half sits at the edge's last unit (high) or first (low),
    # with a space on the other side.
    seq = [lone, 0x20] if lone < 0xDC00 else [0x20, lone]
    at = {"tile": [seq, [0x20]], "block": [[0x20], seq]}[edge]
    doc = _document("chinese", 77, at)
    with pytest.raises(UnicodeDecodeError) as e:
        doc.tobytes().decode("utf-16-le")
    want = e.value.start // 2
    assert want == {"tile": TILE_EDGE - 1, "block": BLOCK_EDGE}[edge]
    _buf, _count, status = _run(doc)
    assert status == want
