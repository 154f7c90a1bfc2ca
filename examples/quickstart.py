"""Quickstart: the transcoding core as a library (paper's public API).

    PYTHONPATH=src python examples/quickstart.py

The supported surface is the GENERIC entry points (``repro.transcode`` /
``scan`` / ``ragged_transcode`` / ``ragged_scan``); the per-pair wrappers
are deprecated shims that warn (DESIGN.md §11).  Migration table:

    deprecated wrapper                  generic call
    ----------------------------------  --------------------------------
    transcode_utf8_to_utf16(b, n)       transcode(b, "utf16", src_format="utf8", n_valid=n)
    transcode_utf16_to_utf8(u, n)       transcode(u, "utf8", src_format="utf16", n_valid=n)
    utf8_to_utf16(b, n)                 transcode(b, "utf16", src_format="utf8", n_valid=n, strategy="blockparallel")
    utf16_to_utf8(u, n)                 transcode(u, "utf8", src_format="utf16", n_valid=n, strategy="blockparallel")
    utf8_to_utf32 / utf16_to_utf32      transcode(x, "utf32", src_format=..., strategy="blockparallel")
    utf32_to_utf8 / utf32_to_utf16      transcode(cp, ..., src_format="utf32", strategy="blockparallel")
    utf8_to_latin1 / latin1_to_*        transcode(x, ..., strategy="onepass")
    scan_utf8(b, n)                     scan(b, "utf16", src_format="utf8", n_valid=n)
    scan_utf16(u, n)                    scan(u, "utf8", src_format="utf16", n_valid=n)
    ragged_utf8_to_utf16(d, o, l)       ragged_transcode(d, o, l, src_format="utf8", dst_format="utf16")
    ragged_utf16_to_utf8(d, o, l)       ragged_transcode(d, o, l, src_format="utf16", dst_format="utf8")
    ragged_scan_utf8 / ragged_scan_utf16  ragged_scan(d, o, l, src_format=..., dst_format=...)

(The ``strategy=`` column records each wrapper's default; the generic
default is ``"onepass"``.)
"""

import numpy as np

import jax.numpy as jnp

from repro.core import transcode as tc
from repro.kernels import ops as kops


def show(title, value):
    print(f"{title:<46s} {value}")


def main():
    s = "naïve 中文 🎉 — transcoding demo"
    utf8 = np.frombuffer(s.encode("utf-8"), np.uint8).astype(np.int32)
    utf16 = np.frombuffer(s.encode("utf-16-le"), np.uint16).astype(np.int32)

    # --- validation (Keiser-Lemire, vectorized) -------------------------
    show("validate_utf8(valid text)",
         bool(tc.validate_utf8(jnp.asarray(utf8), len(utf8))))
    bad = jnp.asarray(np.array([0xED, 0xA0, 0x80, 0, 0, 0, 0, 0], np.int32))
    show("validate_utf8(surrogate U+D800)", bool(tc.validate_utf8(bad, 3)))

    # --- UTF-8 -> UTF-16 (all strategies) -------------------------------
    # "onepass" (the default) is the single-launch pipeline: one read +
    # one decode of the input, inter-tile offsets carried in SMEM
    # (DESIGN.md §9); "blockparallel" (pure jnp) and "windowed" (the
    # paper's algorithm) are the references it is pinned against.
    for strat in ("onepass", "blockparallel", "windowed"):
        out, cnt, err = tc.transcode(
            jnp.asarray(utf8), "utf16", src_format="utf8",
            n_valid=len(utf8), strategy=strat)
        got = np.asarray(out)[: int(cnt)].astype(np.uint16)
        ok = np.array_equal(got, utf16.astype(np.uint16))
        show(f"utf8->utf16 [{strat}] matches python", ok)

    # Explicit one-pass call on a mixed mostly-ASCII document: the
    # per-tile class dispatch (DESIGN.md §9) keeps clean tiles on the
    # ASCII copy path even though the buffer as a whole is not ASCII —
    # and tiles of dense 2-byte scripts (Arabic, Hebrew, Russian, ...)
    # take a narrowed ≤2-byte fast path: no 3-/4-byte candidate
    # assembly, half the staging window, uint16 intermediates.
    mixed = ("The quick brown fox. " * 120 + "速い茶色の狐。").encode("utf-8")
    out, cnt, status = tc.transcode(
        jnp.asarray(np.frombuffer(mixed, np.uint8)), "utf16",
        src_format="utf8", strategy="onepass")
    show("transcode(..., strategy='onepass') round-trips",
         bytes(np.asarray(out)[: int(cnt)].astype(np.uint16).tobytes())
         .decode("utf-16-le") == mixed.decode("utf-8"))

    # --- UTF-16 -> UTF-8 ------------------------------------------------
    out, cnt, err = tc.transcode(jnp.asarray(utf16), "utf8",
                                 src_format="utf16", n_valid=len(utf16))
    got = bytes(np.asarray(out)[: int(cnt)].astype(np.uint8))
    show("utf16->utf8 round-trips", got.decode("utf-8") == s)

    # --- Pallas kernel path (interpret=True on CPU, same API) -----------
    out, cnt, err = kops.utf8_to_utf16(jnp.asarray(utf8), len(utf8))
    got = np.asarray(out)[: int(cnt)].astype(np.uint16)
    show("Pallas kernel utf8->utf16 matches", np.array_equal(
        got, utf16.astype(np.uint16)))

    # --- error location + replacement (simdutf-style result) ------------
    broken = np.frombuffer("héllo".encode("utf-8"), np.uint8).copy()
    broken[1] = 0xFF  # corrupt the é lead byte
    count, status = tc.scan(jnp.asarray(broken), "utf16",
                            src_format="utf8", n_valid=len(broken))
    show("scan: first invalid byte offset", int(status))
    out, cnt, status = tc.transcode(
        jnp.asarray(broken), "utf16", src_format="utf8",
        n_valid=len(broken), errors="replace")
    fixed = np.asarray(out)[: int(cnt)].astype(np.uint16).tobytes()
    show("errors='replace' output", fixed.decode("utf-16-le"))

    # --- the codec matrix (DESIGN.md §8): any (src, dst) format pair ----
    legacy = "café ÿ £".encode("latin-1")   # a Latin-1 wire buffer
    out, cnt, status = tc.transcode(
        jnp.asarray(np.frombuffer(legacy, np.uint8)), "utf8",
        src_format="latin1")
    show("transcode(latin1 -> utf8) round-trips",
         bytes(np.asarray(out)[: int(cnt)].astype(np.uint8))
         == "café ÿ £".encode("utf-8"))
    out, cnt, status = tc.transcode(
        jnp.asarray(utf8), "utf32", src_format="utf8", n_valid=len(utf8))
    show("utf8 -> utf32 code points (onepass cell)",
         np.array_equal(np.asarray(out)[: int(cnt)].astype(np.int64),
                        np.array([ord(c) for c in s])))
    out, cnt, status = tc.transcode(
        jnp.asarray(utf8), "latin1", src_format="utf8", errors="replace")
    show("utf8 -> latin1 (replace: '?' for cp > U+00FF)",
         bytes(np.asarray(out)[: int(cnt)].astype(np.uint8)))

    # --- capacity planning (simdutf-style length queries) ---------------
    show("utf16 units needed",
         int(tc.utf16_length_from_utf8(jnp.asarray(utf8), len(utf8))))
    show("utf8 bytes needed",
         int(tc.utf8_length_from_utf16(jnp.asarray(utf16), len(utf16))))
    show("characters", int(tc.count_utf8_chars(jnp.asarray(utf8), len(utf8))))

    # --- mesh-sharded ragged batches (DESIGN.md §12) ---------------------
    # A packed batch split across the mesh "data" axis: each shard runs
    # the one-launch ragged kernel locally and the per-document results
    # gather back bit-identical to the single-device path.  n_shards=1
    # runs anywhere; on a multi-device host (or CPU with
    # XLA_FLAGS=--xla_force_host_platform_device_count=8) raise n_shards
    # — document-boundary cuts balance live bytes across shards.
    from repro.core import packing
    docs = [s.encode("utf-8"), b"second document", b"", b"third"]
    pk = packing.pack_documents(docs)
    res = tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                              src_format="utf8", dst_format="utf16",
                              strategy="sharded", n_shards=1)
    ref = tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                              src_format="utf8", dst_format="utf16")
    show("sharded == single-device (counts)",
         np.array_equal(np.asarray(res.counts), np.asarray(ref.counts)))
    show("sharded == single-device (buffer)",
         np.array_equal(np.asarray(res.buffer), np.asarray(ref.buffer)))

    # --- supervised launch (DESIGN.md §10: shard fault tolerance) --------
    # The same call under the retry / watchdog / degraded-mesh-replan
    # supervisor: transient failures retry with backoff, persistent ones
    # replan onto fewer devices (bit-identical result — same cut rules
    # at every mesh size), and only a fully exhausted ladder raises a
    # typed DegradedMeshExhausted.  See examples/serve_demo.py for the
    # serve engine's circuit breaker riding the same layer.
    from repro.core import recovery
    log = recovery.SupervisionLog()
    sup = recovery.supervised_ragged_transcode(
        pk.data, pk.offsets, pk.lengths, src_format="utf8",
        dst_format="utf16", n_shards=1, log=log)
    show("supervised == single-device (buffer)",
         np.array_equal(np.asarray(sup.buffer), np.asarray(ref.buffer)))
    show("supervision log", log.attempts)


if __name__ == "__main__":
    main()
