"""Training-data ingest traffic: a seeded pool of documents with
heavy-tailed lengths, cut into fixed-budget packed batches.

A mix (``traffic/<name>.json``) gives the Table 4 ``table`` and
``profiles``, ``pool_docs``, the ``length`` law, the ``invalid_share``
of documents that get one of the ``malformed`` sequences, and how many
batches the comparison samples (``sample_batches``).

The pool is made in set-up.  Each batch takes documents from a seeded
sequence of permutations of the pool until one more would overflow the
tile budget once every document starts on a tile (``packing.TILE``
elements), so a batch holds whole documents only, and batches differ in
their documents but not in their budget.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import common
import corpus

TILE = 1024


@dataclass
class Pool:
    docs: list            # uint8 UTF-8 documents
    chars: np.ndarray     # characters per document (CPython, "replace")
    units: np.ndarray     # UTF-16 units per document (CPython, "replace")
    nbytes: np.ndarray    # UTF-8 bytes per document
    tiles: np.ndarray     # tiles per document when packed


def doc_lengths(traffic: dict, rng: np.random.Generator, n: int):
    """Document lengths in UTF-8 bytes: Pareto(alpha) times ``scale``,
    capped."""
    law = traffic["length"]
    raw = (rng.pareto(law["alpha"], n) + 1.0) * law["scale"]
    return np.minimum(raw, law["cap"]).astype(np.int64)


def make_pool(traffic: dict, seed: int) -> Pool:
    rng = corpus.rng_for(seed, "ingest-pool")
    n = traffic["pool_docs"]
    profiles = list(traffic["profiles"])
    lengths = doc_lengths(traffic, rng, n)
    which = rng.integers(0, len(profiles), n)
    docs = [None] * n
    for p, prof in enumerate(profiles):
        idx = np.flatnonzero(which == p)
        total = int(lengths[idx].sum()) + 4
        stream, _ = corpus.text(traffic["table"], prof, total, seed,
                                "ingest")
        for k, d in zip(idx, corpus.cut_documents(stream, lengths[idx])):
            docs[k] = d
    bad = rng.choice(n, int(round(n * traffic["invalid_share"])),
                     replace=False)
    for k in np.sort(bad):
        docs[k] = corpus.malform(docs[k], rng, traffic["malformed"])
    counts = np.asarray([common.utf8_counts(d) for d in docs], np.int64)
    nbytes = np.asarray([d.shape[0] for d in docs], np.int64)
    return Pool(docs, counts[:, 0], counts[:, 1], nbytes, -(-nbytes // TILE))


class Batches:
    """Batches of whole documents under a tile budget, in a seeded order
    that never runs out: each pass is a fresh permutation of the pool."""

    def __init__(self, pool: Pool, budget_tiles: int, seed: int):
        if int(pool.tiles.max()) > budget_tiles:
            raise ValueError("a document is larger than the batch budget")
        self.pool, self.budget = pool, budget_tiles
        self.rng = corpus.rng_for(seed, "ingest-order")
        self.order = np.zeros(0, np.int64)
        self.pos = 0

    def next(self) -> np.ndarray:
        """Document indices of the next batch."""
        while True:
            ends = np.cumsum(self.pool.tiles[self.order[self.pos:]])
            k = int(np.searchsorted(ends, self.budget, side="right"))
            if k < ends.shape[0]:
                break
            self.order = np.concatenate(
                [self.order[self.pos:],
                 self.rng.permutation(len(self.pool.docs))])
            self.pos = 0
        idx = self.order[self.pos:self.pos + k]
        self.pos += k
        return idx


@dataclass
class State:
    fn: object
    kwargs: dict
    devices: list
    reference: object
    config: dict
    traffic: dict
    seed: int
    pool: Pool
    budget_bytes: int
    unit: int
    batches: Batches = None
    kept: common.Reservoir = None

    def restart(self):
        """Back to the first batch, with nothing kept: the window starts
        where set-up started."""
        self.batches = Batches(self.pool, self.budget_bytes // TILE,
                               self.seed)
        self.kept = common.Reservoir(
            self.traffic["sample_batches"],
            corpus.rng_for(self.seed, "ingest-sample"))


def make_state(config, traffic, seed, devices, reference) -> State:
    """The pool and the call of a packed ingest cell, before warm-up."""
    kwargs = dict(src_format=config["src_format"],
                  dst_format=config["dst_format"],
                  validate=config["validate"], errors=config["errors"],
                  strategy=config["strategy"][str(len(devices))])
    unit = np.dtype(reference.CODECS[config["dst_format"]][2]).itemsize
    st = State(common.entry(config), kwargs, devices, reference, config,
               traffic, seed, make_pool(traffic, seed),
               config["batch_bytes_per_chip"] * len(devices), unit)
    st.restart()
    return st


def pack(st: State, idx: np.ndarray):
    """The batch in the program's packed layout (``pack_documents``),
    padded to the budget and to one document per tile, so that every
    batch has one shape."""
    from repro.core import packing

    pk = packing.pack_documents([st.pool.docs[d] for d in idx],
                                pad_to_docs=st.budget_bytes // TILE)
    data = np.zeros(st.budget_bytes, np.uint8)
    data[:pk.data.shape[0]] = pk.data
    return data, pk.offsets, pk.lengths


def record(st: State, idx: np.ndarray) -> common.Record:
    return common.Record(
        int(st.pool.chars[idx].sum()),
        common.kernel_bytes(st.pool.nbytes[idx].sum(),
                            st.pool.units[idx].sum(), st.unit))


def check(st: State) -> dict:
    """Every document of each sampled batch against the reference; the
    padding rows must come out empty and valid."""
    import jax

    tally = common.Tally()
    for idx, r in st.kept.items:
        buf, offs, counts, statuses = jax.device_get(
            (r.buffer, r.offsets, r.counts, r.statuses))
        for row, d in enumerate(idx):
            units, want = st.reference.transcode(
                st.pool.docs[d].tobytes(), st.config["src_format"],
                st.config["dst_format"], st.config["errors"])
            tally.document(units, want, buf[offs[row]:], int(counts[row]),
                           int(statuses[row]))
        pad = slice(len(idx), counts.shape[0])
        tally.n["wrong_count"] += int(np.count_nonzero(counts[pad]))
        tally.n["wrong_status"] += int(np.count_nonzero(statuses[pad] != -1))
    return tally.checks()
