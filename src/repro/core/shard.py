"""Mesh-sharded ragged transcode: one onepass launch per device shard.

The single-device ragged path (``repro.kernels.ragged_transcode``) runs
a whole packed batch as ONE grid launch — aggregate ingest is therefore
bounded by one device and one host->device link.  This module splits a
packed batch across the ``data`` axis of a 1-D device mesh with
``shard_map``: each shard runs the UNCHANGED ragged onepass launch on
its own tile-aligned sub-stream, and the per-fragment results are
gathered back with the same segment-reduction machinery the kernel's
per-document reduce uses, so the assembled result is bit-identical to
the single-device path (buffer, per-document counts, statuses).

Shard-cut rules (DESIGN.md §12):

  * The host-side splitter balances by BYTES, not document count: the
    k-th cut targets ``k * total_live / n_shards`` and snaps to the
    nearest document boundary of the ``core/packing`` row-offset vector.
  * A document larger than the shard chunk budget (default: the balanced
    per-shard target) cannot wait for a boundary — the cut lands inside
    it, walked back by the per-codec holdback rule of
    :func:`repro.core.stream.holdback_units` (``Codec.max_lookback``:
    3 for UTF-8, 1 for UTF-16, 0 for the fixed-width formats) so every
    fragment starts at a unit boundary and the per-fragment counts /
    statuses / replace-substitutions compose chunk-wise, exactly like
    the resumable stream chunks of DESIGN.md §10.
  * Every fragment is re-packed tile-aligned per shard (the kernels'
    packed-layout invariant), so fragment order — shard-major, then
    slot-major — IS global document order, and the dense global output
    is the fragment emissions concatenated in that order.

Strict-policy caveat (same as the streaming layer): for a document that
contains an error AND is split across shards, the speculative buffer
content AFTER the first error is launch-geometry-defined; counts and
statuses still compose exactly.  Documents left whole (the splitter
default for anything under the chunk budget) are bit-identical under
every policy.

``jax.shard_map`` needs ``check_vma=False`` here: ``pallas_call`` has no
varying-manual-axes rule, and every output is genuinely per-shard
anyway.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import packing
from repro.core import result as R
from repro.core import stream
from repro.testing import faults

TILE = packing.TILE

_IMAX = R.NO_ERR_SENTINEL


def _round_up(n: int, block: int = TILE) -> int:
    return -(-int(n) // block) * block


class ShardPlan(NamedTuple):
    """Host-side split of one packed batch into per-shard sub-streams.

    ``data``/``offsets``/``lengths`` are the per-shard packed layouts
    stacked on a leading shard axis (every shard shares one geometry so
    the ``shard_map`` body compiles once).  ``frag_doc``/``frag_base``
    map each per-shard document slot back to (global document, start
    offset within that document); padding slots carry ``frag_doc ==
    n_docs`` (one past the last document — the sentinel segment the
    gather drops).
    """

    n_shards: int
    n_docs: int
    data: np.ndarray       # [n_shards, shard_len]   codec dtype
    offsets: np.ndarray    # [n_shards, Bs+1] int32  tile-aligned starts
    lengths: np.ndarray    # [n_shards, Bs]   int32  fragment lengths
    frag_doc: np.ndarray   # [n_shards, Bs]   int32  global doc (n_docs=pad)
    frag_base: np.ndarray  # [n_shards, Bs]   int32  fragment start in doc

    @property
    def shard_len(self) -> int:
        return self.data.shape[1]

    @property
    def docs_per_shard(self) -> int:
        return self.lengths.shape[1]


def _normalize_cut(d: int, e: int, lengths: np.ndarray) -> tuple:
    """Canonical (doc, elem) cut: a cut at a document's live end is the
    next document's start, so boundary cuts compare equal regardless of
    which side produced them."""
    n_docs = lengths.shape[0]
    if d >= n_docs:
        return (n_docs, 0)
    e = int(min(max(e, 0), lengths[d]))
    if e > 0 and e == int(lengths[d]):
        return (d + 1, 0)
    return (int(d), e)


def plan_shards(data, offsets, lengths, n_shards: int, *,
                src: str = "utf8",
                chunk_budget: Optional[int] = None) -> ShardPlan:
    """Split a packed batch into ``n_shards`` tile-aligned sub-streams.

    Cuts are balanced by live bytes and land on document boundaries;
    documents larger than ``chunk_budget`` (default: the balanced
    per-shard target) are split mid-document with the per-codec holdback
    walk-back so the fragment boundary is a unit boundary.  Host-side
    only — the splitter needs concrete values.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if isinstance(data, jax.core.Tracer) or \
            isinstance(offsets, jax.core.Tracer):
        raise TypeError(
            "plan_shards is a host-side splitter and needs concrete "
            "arrays, not tracers (call it outside jit)")
    data = np.asarray(data)
    offsets = np.asarray(offsets, np.int64)
    lengths = np.asarray(lengths, np.int64)
    n_docs = offsets.shape[0] - 1
    if n_docs < 1:
        raise ValueError("plan_shards: offsets must be [B+1] with B >= 1")
    live = np.cumsum(np.concatenate([[0], lengths]))
    total = int(live[-1])
    target = max(TILE, _round_up(-(-total // max(n_shards, 1))))
    budget = target if chunk_budget is None else int(chunk_budget)
    if budget < TILE:
        raise ValueError(f"chunk_budget must be >= {TILE}, got {budget}")

    # Cut points in (doc, elem-within-doc) space; cuts[k] starts shard k.
    cuts = [(0, 0)]
    for k in range(1, n_shards):
        g = (k * total) // n_shards           # ideal cut, in LIVE bytes
        dd = int(np.clip(np.searchsorted(live[1:], g, side="right"),
                         0, max(n_docs - 1, 0)))
        if n_docs and int(lengths[dd]) > budget:
            # Oversize document: cut inside it, walked back to a unit
            # boundary (the stream layer's holdback rule).
            e = int(g - live[dd])
            lo = int(offsets[dd])
            tail = data[lo + max(e - 4, 0): lo + e]
            e -= stream.holdback_units(src, tail)
            cut = _normalize_cut(dd, e, lengths)
        else:
            # Snap to the nearest document boundary (in live bytes).
            b = dd if (g - int(live[dd])) <= (int(live[dd + 1]) - g) \
                else dd + 1
            cut = _normalize_cut(b, 0, lengths)
        cuts.append(max(cut, cuts[-1]))
    cuts.append((n_docs, 0))

    # Fragment lists per shard: (global doc, base-within-doc, length).
    frags = []
    for k in range(n_shards):
        (d0, e0), (d1, e1) = cuts[k], cuts[k + 1]
        fl = []
        if (d0, e0) < (d1, e1):
            if d0 == d1:
                fl.append((d0, e0, e1 - e0))
            else:
                fl.append((d0, e0, int(lengths[d0]) - e0))
                for d in range(d0 + 1, d1):
                    fl.append((d, 0, int(lengths[d])))
                if e1 > 0:
                    fl.append((d1, 0, e1))
        frags.append(fl)

    bs = max(1, max(len(fl) for fl in frags))
    shard_len = max(TILE, max(
        sum(_round_up(n) for _, _, n in fl) for fl in frags))
    sh_data = np.zeros((n_shards, shard_len), data.dtype)
    sh_off = np.zeros((n_shards, bs + 1), np.int32)
    sh_len = np.zeros((n_shards, bs), np.int32)
    fr_doc = np.full((n_shards, bs), n_docs, np.int32)   # pad sentinel
    fr_base = np.zeros((n_shards, bs), np.int32)
    for k, fl in enumerate(frags):
        lo = 0
        for j, (d, base, n) in enumerate(fl):
            src_lo = int(offsets[d]) + base
            sh_data[k, lo: lo + n] = data[src_lo: src_lo + n]
            sh_off[k, j] = lo
            sh_len[k, j] = n
            fr_doc[k, j] = d
            fr_base[k, j] = base
            lo += _round_up(n)
        sh_off[k, len(fl):] = lo
    return ShardPlan(n_shards, n_docs, sh_data, sh_off, sh_len,
                     fr_doc, fr_base)


# ---------------------------------------------------------------------------
# shard_map execution: one UNCHANGED ragged onepass launch per shard.

# Jitted shard_map callables, keyed per (mesh devices, cell, policy,
# donate), LRU-bounded (shapes re-key inside jit).
_CALL_CACHE: dict = {}
_CALL_CACHE_MAX = 16


def _cache_get(key, build):
    fn = _CALL_CACHE.get(key)
    if fn is None:
        fn = build()
        while len(_CALL_CACHE) >= _CALL_CACHE_MAX:
            _CALL_CACHE.pop(next(iter(_CALL_CACHE)))
        _CALL_CACHE[key] = fn
    else:
        _CALL_CACHE.pop(key)
        _CALL_CACHE[key] = fn
    return fn


def _mesh_key(mesh: Mesh) -> tuple:
    return tuple(d.id for d in mesh.devices.flat)


def sharded_call(mesh: Mesh, src: str, dst: str, validate: bool,
                 errors: str, interpret, *, donate: bool = False):
    """Jitted ``shard_map`` wrapper around the ragged onepass launch:
    ``(data, offsets, lengths)`` stacked per shard -> per-shard
    ``(buffer, out_offsets, counts, statuses)``.

    With ``donate=True`` the staged input buffers are donated to XLA
    (the double-buffered feeder's waves are single-use, so their device
    memory is reused for the outputs).
    """
    from repro.kernels import ragged_transcode as rt

    key = (_mesh_key(mesh), src, dst, bool(validate), errors,
           interpret, bool(donate))

    def build():
        def body(d, o, l):
            res = rt._ragged_onepass_impl(d[0], o[0], l[0], src, dst,
                                          validate, interpret, errors)
            return (res.buffer[None], res.offsets[None],
                    res.counts[None], res.statuses[None])

        # check_vma=False: pallas_call has no varying-axes rule, and
        # every output here is genuinely per-shard.
        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=(P("data"),) * 4, check_vma=False)
        return jax.jit(sm, donate_argnums=(0, 1, 2) if donate else ())

    return _cache_get(key, build)


def sharded_scan_call(mesh: Mesh, src: str, dst: str, interpret):
    """Jitted ``shard_map`` wrapper around the ragged counting scan:
    per-shard ``(counts, statuses)`` — the ingress-boundary query."""
    from repro.kernels import ragged_transcode as rt

    key = (_mesh_key(mesh), "scan", src, dst, interpret)

    def build():
        def body(d, o, l):
            counts, statuses = rt._ragged_scan_impl(
                d[0], o[0], l[0], src, dst, interpret)
            return counts[None], statuses[None]

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=(P("data"),) * 2, check_vma=False)
        return jax.jit(sm)

    return _cache_get(key, build)


# ---------------------------------------------------------------------------
# Gather: per-fragment results -> the single-device result, with the
# kernel's own segment-reduction machinery over the fragment -> document
# map.


def _doc_counts_statuses(plan: ShardPlan, counts, statuses, validate):
    """Fragment (counts, statuses) -> per-document, composing first-error
    offsets through each fragment's base (min over fragments = global
    first error, since fragments partition a document in order)."""
    n_docs = plan.n_docs
    fd = jnp.asarray(plan.frag_doc.reshape(-1))
    fb = jnp.asarray(plan.frag_base.reshape(-1))
    cf = jnp.asarray(counts).reshape(-1)
    # Padding slots (frag_doc == n_docs) reduce into the dropped
    # sentinel segment — segment_sum/min fills empty documents with
    # 0 / NO_ERR_SENTINEL exactly like the kernel's per-doc reduce.
    doc_counts = jax.ops.segment_sum(cf, fd, num_segments=n_docs + 1)[
        :n_docs].astype(jnp.int32)
    if validate:
        sf = jnp.asarray(statuses).reshape(-1)
        adj = jnp.where(sf < 0, _IMAX, sf + fb)
        first = jax.ops.segment_min(adj, fd, num_segments=n_docs + 1)[
            :n_docs]
        doc_statuses = jnp.where(first == _IMAX, R.STATUS_OK,
                                 first).astype(jnp.int32)
    else:
        doc_statuses = jnp.full((n_docs,), R.STATUS_OK, jnp.int32)
    return doc_counts, doc_statuses


def _gather_result(plan: ShardPlan, cap: int, dst_dtype, bufs, oos,
                   counts, statuses, validate) -> R.RaggedTranscodeResult:
    """Reassemble the dense global output: fragment order (shard-major,
    slot-major) is global document order, so the global stream is the
    fragment emissions concatenated — ONE searchsorted gather."""
    doc_counts, doc_statuses = _doc_counts_statuses(
        plan, counts, statuses, validate)
    out_offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(doc_counts).astype(jnp.int32)])

    bufs = jnp.asarray(bufs)
    cf = jnp.asarray(counts).reshape(-1)
    bs = plan.docs_per_shard
    frag_ends = jnp.cumsum(cf)
    total = frag_ends[-1]
    frag_starts = frag_ends - cf
    # Local output start of each fragment inside its shard's dense
    # buffer: the per-shard out_offsets vector, last entry dropped.
    local = jnp.asarray(oos)[:, :bs].reshape(-1)
    i = jnp.arange(cap, dtype=jnp.int32)
    f = jnp.clip(jnp.searchsorted(frag_ends, i, side="right"),
                 0, cf.shape[0] - 1)
    src_idx = jnp.clip(local[f] + (i - frag_starts[f]),
                       0, bufs.shape[1] - 1)
    out = jnp.where(i < total, bufs[f // bs, src_idx],
                    jnp.zeros((), dst_dtype))
    return R.RaggedTranscodeResult(out, out_offsets, doc_counts,
                                   doc_statuses)


# ---------------------------------------------------------------------------
# Public entry points.


def _resolve_mesh(mesh: Optional[Mesh], n_shards: Optional[int]) -> Mesh:
    from repro.launch import mesh as launch_mesh
    if mesh is not None:
        if "data" not in mesh.axis_names:
            raise ValueError(
                f"sharded transcode needs a mesh with a 'data' axis, "
                f"got axes {mesh.axis_names}")
        return mesh
    return launch_mesh.make_transcode_mesh(n_shards)


def ragged_transcode_sharded(data, offsets, lengths, *,
                             src_format: str = "utf8",
                             dst_format: str = "utf16",
                             validate: bool = True,
                             errors: str = "strict",
                             n_shards: Optional[int] = None,
                             mesh: Optional[Mesh] = None,
                             chunk_budget: Optional[int] = None,
                             interpret=None) -> R.RaggedTranscodeResult:
    """Mesh-sharded ragged transcode, bit-identical to the single-device
    onepass path (module docstring: shard-cut rules and the strict
    split-document caveat).

    ``n_shards`` defaults to the mesh's data-axis size (or every host
    platform device when neither is given).
    """
    from repro.core import transcode as tc
    from repro.kernels import ragged_transcode as rt
    from repro.kernels import runtime
    from repro.kernels import stages

    R.check_errors_policy(errors)
    src = tc.normalize_format(src_format)
    dst = tc.normalize_format(dst_format)
    codec_s, codec_d, factor = stages.get_pair(src, dst)
    data, offsets, lengths = rt._as_packed(data, offsets, lengths,
                                           codec_s.dtype)
    mesh = _resolve_mesh(mesh, n_shards)
    n = int(mesh.shape["data"])
    plan = plan_shards(np.asarray(data), np.asarray(offsets),
                       np.asarray(lengths), n, src=src,
                       chunk_budget=chunk_budget)
    fn = sharded_call(mesh, src, dst, bool(validate), errors,
                      runtime.resolve_interpret(interpret))
    # Host-side chaos hook: fires per CALL (a cache-hot jitted
    # executable skips the kernel wrappers' trace-time hooks) — the
    # supervised-launch layer (core.recovery) retries/replans around it.
    faults.fire(faults.SHARD_LAUNCH)
    bufs, oos, counts, statuses = fn(plan.data, plan.offsets, plan.lengths)
    # Same capacity budget as the single-device launch on this data
    # buffer (factor x its tile span) — the bit-identity contract.
    cap = factor * max(1, -(-int(data.shape[0]) // TILE)) * TILE
    return _gather_result(plan, cap, codec_d.dtype,
                          np.asarray(bufs), np.asarray(oos),
                          np.asarray(counts), np.asarray(statuses),
                          bool(validate))


def scan_ragged_sharded(data, offsets, lengths, *,
                        src_format: str = "utf8",
                        dst_format: str = "utf16",
                        n_shards: Optional[int] = None,
                        mesh: Optional[Mesh] = None,
                        chunk_budget: Optional[int] = None,
                        interpret=None):
    """Mesh-sharded counting scan: per-document ``(counts, statuses)``,
    bit-identical to :func:`repro.core.transcode.ragged_scan`."""
    from repro.core import transcode as tc
    from repro.kernels import ragged_transcode as rt
    from repro.kernels import runtime
    from repro.kernels import stages

    src = tc.normalize_format(src_format)
    dst = tc.normalize_format(dst_format)
    codec_s, _codec_d, _f = stages.get_pair(src, dst)
    data, offsets, lengths = rt._as_packed(data, offsets, lengths,
                                           codec_s.dtype)
    mesh = _resolve_mesh(mesh, n_shards)
    n = int(mesh.shape["data"])
    plan = plan_shards(np.asarray(data), np.asarray(offsets),
                       np.asarray(lengths), n, src=src,
                       chunk_budget=chunk_budget)
    fn = sharded_scan_call(mesh, src, dst,
                           runtime.resolve_interpret(interpret))
    faults.fire(faults.SHARD_LAUNCH)   # per-call chaos hook (see above)
    counts, statuses = fn(plan.data, plan.offsets, plan.lengths)
    return _doc_counts_statuses(plan, np.asarray(counts),
                                np.asarray(statuses), True)
