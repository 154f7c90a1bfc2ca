"""Sharded data pipeline: raw UTF-8 -> validated, packed token batches.

Design (DESIGN.md §2): the host ships **raw UTF-8 bytes** to the device —
2–4x less host-to-device bandwidth than pre-decoded UTF-32 — and the device
runs the paper's validation/transcoding as the first stage of the jitted
input program.  This is precisely the paper's system claim (transcoding at
line rate so ingest is never the bottleneck) applied to an accelerator.

Fault-tolerance properties (system prompt: straggler mitigation, elastic
restart):

  * **Deterministic sharding**: document k of global step s belongs to host
    ``(s * global_batch + k) % n_hosts``; any host can recompute any shard,
    so a restarted/replaced host rejoins at a global step boundary with
    ``skip_to(step)`` and no coordination.
  * **Stateless generators**: the synthetic corpus is a pure function of
    (seed, step, slot), so skip-ahead is O(1) — no replaying of the stream.
  * **Elastic re-shard**: changing ``n_hosts`` re-partitions the same
    global document sequence; the global batch content at a given step is
    invariant to the host count.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core import transcode as tc
from repro.data import synthetic
from repro.testing import faults
from repro.data.tokenizer import BOS_ID, EOS_ID, PAD_ID, ByteTokenizer


# ---------------------------------------------------------------------------
# Batched transcoding entry points.
#
# Inputs are fixed-capacity [B, L] buffers of narrow dtype plus a [B]
# vector of logical lengths; outputs are a TranscodeResult of batched
# arrays — [B, cap] buffers, [B] counts, [B] statuses, per-document
# first-error offsets, -1 where valid.  The [B, L] buffer is
# reinterpreted as ONE tile-aligned packed stream (row-major flattening
# IS the packed layout once L is padded to a tile multiple) and the
# ragged single-pass kernel runs as ONE grid launch for the whole batch
# (DESIGN.md §7/§9: one read + one decode, segment scan carried in
# SMEM), on one device ("packed") or split across a device mesh's data
# axis ("sharded", DESIGN.md §12); the dense ragged output is re-padded
# to the [B, cap] contract with one gather.  Callers that can consume
# the dense layout directly should use ``tc.ragged_transcode`` on a
# ``packing.pack_documents`` batch and skip both the padding and the
# re-pad gather.
#
# The ``errors=`` policy threads through both, so a batch of partially-
# malformed documents can ingest losslessly (errors="replace": U+FFFD
# per maximal subpart) without a host round trip.

BATCH_STRATEGIES = ("packed", "sharded")

_TILE = packing.TILE


def _rows_as_packed(docs):
    """[B, L] row buffers -> tile-aligned packed stream (zero repack).

    Pads the capacity axis to a tile multiple; row-major flattening then
    satisfies the packed-layout invariant (tile-aligned starts), so the
    offsets vector is just ``arange(B+1) * Lp``.
    """
    b, cap = docs.shape
    cap_p = -(-cap // _TILE) * _TILE
    if cap_p != cap:
        docs = jnp.pad(docs, ((0, 0), (0, cap_p - cap)))
    offsets = jnp.arange(b + 1, dtype=jnp.int32) * cap_p
    return docs.reshape(-1), offsets


def _repad(res, out_cap: int):
    """Dense ragged output -> the padded [B, cap] batch contract."""
    j = jnp.arange(out_cap, dtype=jnp.int32)[None, :]
    src = res.offsets[:-1, None] + j
    valid = j < res.counts[:, None]
    src = jnp.clip(src, 0, res.buffer.shape[0] - 1)
    out = jnp.where(valid, res.buffer[src], 0)
    return tc.TranscodeResult(out, res.counts, res.statuses)


@functools.partial(jax.jit, static_argnames=("src", "dst", "validate",
                                             "errors", "out_cap"))
def _packed_batch(docs, lengths, src, dst, validate, errors, out_cap):
    data, offsets = _rows_as_packed(docs)
    res = tc.ragged_transcode(data, offsets, lengths, src_format=src,
                              dst_format=dst, validate=validate,
                              errors=errors)
    return _repad(res, out_cap)


def batch_transcode(docs, lengths, *, in_encoding: str = "utf8",
                    out_encoding: str = "utf16", strategy: str = "packed",
                    validate: bool = True, errors: str = "strict",
                    n_shards=None):
    """Batched transcode for any matrix cell: [B, L] narrow buffers ->
    TranscodeResult([B, cap_factor*L], [B], [B]).

    ``strategy="packed"`` (default) reinterprets the row-major batch as
    ONE tile-aligned packed stream and runs a single ragged one-pass
    launch; ``strategy="sharded"`` splits that same packed stream across
    the data axis of a device mesh — one onepass launch per shard,
    bit-identical gather (DESIGN.md §12; ``n_shards`` applies only
    here).
    """
    faults.fire(faults.PIPELINE_BATCH)   # chaos-suite hook (no-op in prod)
    src = tc.normalize_format(in_encoding)
    dst = tc.normalize_format(out_encoding)
    if (src, dst) not in tc.CAP_FACTOR:
        raise ValueError(f"unsupported format pair {src!r} -> {dst!r}")
    factor = tc.CAP_FACTOR[(src, dst)]
    if strategy not in BATCH_STRATEGIES:
        raise ValueError(
            f"batch_transcode: unknown strategy {strategy!r} "
            f"(supported: {list(BATCH_STRATEGIES)})")
    if n_shards is not None and strategy != "sharded":
        raise ValueError("n_shards requires strategy='sharded'")
    docs = jnp.asarray(docs)
    lengths = jnp.asarray(lengths)
    if strategy == "sharded":
        # The host-side splitter needs concrete arrays, so this path is
        # eager end-to-end (the shard_map launch itself is jitted and
        # cached inside repro.core.shard).
        from repro.kernels import stages
        narrow = np.asarray(docs).astype(stages.get_codec(src).dtype)
        data, offsets = _rows_as_packed(jnp.asarray(narrow))
        res = tc.ragged_transcode(
            np.asarray(data), np.asarray(offsets), np.asarray(lengths),
            src_format=src, dst_format=dst, validate=validate,
            errors=errors, strategy="sharded", n_shards=n_shards)
        return _repad(res, factor * docs.shape[1])
    from repro.kernels import stages
    narrow = docs.astype(stages.get_codec(src).dtype)
    return _packed_batch(narrow, lengths, src, dst, validate, errors,
                         factor * docs.shape[1])


def batch_utf8_to_utf16(docs, lengths, *, strategy: str = "packed",
                        validate: bool = True, errors: str = "strict"):
    """Batched UTF-8 -> UTF-16: [B, L] byte buffers -> ([B, L], [B], [B])."""
    return batch_transcode(docs, lengths, in_encoding="utf8",
                           out_encoding="utf16", strategy=strategy,
                           validate=validate, errors=errors)


def batch_utf16_to_utf8(units, lengths, *, strategy: str = "packed",
                        validate: bool = True, errors: str = "strict"):
    """Batched UTF-16 -> UTF-8: [B, L] unit buffers -> ([B, 3L], [B], [B])."""
    return batch_transcode(units, lengths, in_encoding="utf16",
                           out_encoding="utf8", strategy=strategy,
                           validate=validate, errors=errors)


def batch_utf8_to_codepoints(docs, lengths, *, strategy: str = "packed",
                             validate: bool = True,
                             errors: str = "strict"):
    """Batched UTF-8 -> UTF-32 code points: the device-side decode the
    codepoint-consuming models ingest (one ragged launch, not the
    host-side ``core/utf32.py`` helpers)."""
    return batch_transcode(docs, lengths, in_encoding="utf8",
                           out_encoding="utf32", strategy=strategy,
                           validate=validate, errors=errors)


@dataclasses.dataclass
class PipelineConfig:
    seq_len: int = 1024
    global_batch: int = 8
    langs: tuple = ("latin", "arabic", "chinese", "emoji")
    seed: int = 0
    host_id: int = 0
    n_hosts: int = 1
    validate: bool = True
    # "tokens" (default): byte-tokenized BOS/doc/EOS frames.
    # "codepoints": the batch additionally carries per-document UTF-32
    # code points, decoded ON DEVICE through the ragged
    # UTF-8 -> UTF-32 matrix cell (one packed launch per batch — not the
    # host-side core/utf32.py helpers).
    emit: str = "tokens"


class TextPipeline:
    """Deterministic, restartable synthetic-text pipeline."""

    def __init__(self, cfg: PipelineConfig):
        if cfg.global_batch % cfg.n_hosts:
            raise ValueError("global_batch must divide evenly across hosts")
        self.cfg = cfg
        self.step = 0
        self._tok = ByteTokenizer()
        # Device ingest program: bytes -> validated token sequence.
        self._ingest = jax.jit(self._ingest_fn)

    # ------------------------------------------------------------------
    def skip_to(self, step: int) -> None:
        """O(1) restart at a global step boundary (fault tolerance)."""
        self.step = step

    @property
    def local_batch(self) -> int:
        return self.cfg.global_batch // self.cfg.n_hosts

    # ------------------------------------------------------------------
    def _doc_bytes(self, step: int, slot: int) -> np.ndarray:
        """Raw UTF-8 for global slot ``slot`` of global step ``step``."""
        cfg = self.cfg
        lang = cfg.langs[(step + slot) % len(cfg.langs)]
        # seq_len bytes of budget; CJK characters are 3 bytes, so ask for
        # seq_len chars and truncate at a character boundary below.
        doc = synthetic.utf8_array(
            lang, cfg.seq_len, seed=cfg.seed + step * cfg.global_batch + slot)
        doc = doc[: cfg.seq_len - 2]  # room for BOS/EOS
        # Truncate to a character boundary: drop trailing continuation
        # bytes and a trailing incomplete lead.
        end = len(doc)
        while end > 0 and (doc[end - 1] & 0xC0) == 0x80:
            end -= 1
        if end > 0 and doc[end - 1] >= 0xC0:
            end -= 1
        return doc[:end]

    def _ingest_fn(self, raw: jnp.ndarray, n_valid: jnp.ndarray):
        """Jitted device ingest: validate UTF-8, tokenize, frame, label."""
        cfg = self.cfg
        ok = tc.validate_utf8(raw, n_valid) if cfg.validate else jnp.bool_(True)
        ids = self._tok.encode(raw)
        pos = jnp.arange(cfg.seq_len)
        # [BOS] doc [EOS] [PAD...]
        tokens = jnp.where(
            pos == 0, BOS_ID,
            jnp.where(pos - 1 < n_valid, jnp.roll(ids, 1),
                      jnp.where(pos == n_valid + 1, EOS_ID, PAD_ID)))
        labels = jnp.roll(tokens, -1)
        labels = jnp.where(pos >= n_valid + 1, -1, labels)  # -1 = no loss
        return tokens, labels, ok

    # ------------------------------------------------------------------
    def next_batch(self):
        """Local (per-host) batch for the current global step."""
        cfg = self.cfg
        toks, labs, raws, lens = [], [], [], []
        # Deterministic host sharding, without touching other hosts'
        # slots: host h owns exactly the slots h, h+n_hosts, ... — the
        # stride iteration IS the shard, so host k never materializes
        # (or even names) host j's documents.
        for k in range(cfg.host_id, cfg.global_batch, cfg.n_hosts):
            doc = self._doc_bytes(self.step, k)
            raw = np.zeros(cfg.seq_len, np.uint8)
            raw[: len(doc)] = doc
            t, l, ok = self._ingest(jnp.asarray(raw), jnp.int32(len(doc)))
            if cfg.validate and not bool(ok):  # pragma: no cover
                raise ValueError(f"invalid UTF-8 document at step={self.step}")
            toks.append(t)
            labs.append(l)
            raws.append(raw)
            lens.append(len(doc))
        self.step += 1
        batch = {
            "tokens": jnp.stack(toks),
            "labels": jnp.stack(labs),
        }
        if cfg.emit == "codepoints":
            # Device-side decode to the UTF-32 interchange format: ONE
            # ragged packed launch for the whole local batch through the
            # UTF-8 -> UTF-32 matrix cell.
            res = batch_utf8_to_codepoints(
                np.stack(raws), np.asarray(lens, np.int32),
                validate=cfg.validate)
            batch["codepoints"] = res.buffer
            batch["cp_counts"] = res.count
        return batch

    def __iter__(self):
        while True:
            yield self.next_batch()
