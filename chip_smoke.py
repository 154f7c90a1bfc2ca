"""Bring-up smoke of the transcode path on the TPU.

    python chip_smoke.py [--seed 0]     # one chip: the main path
    python chip_smoke.py --chips 4      # four chips: the sharded path only

Runs the library's main path end to end through the public entry points
and compares every output with CPython ``codecs``:

  single     ``transcode`` (default strategy) UTF-8 -> UTF-16 and back,
             validating, on one 128k-character document per lipsum
             profile, plus a mutated document under ``errors="strict"``
             and ``"replace"``, and ``scan`` on the same inputs.  One
             call's compiled HLO must hold the Pallas kernel
             (``tpu_custom_call``).
  packed     ``ragged_transcode`` / ``ragged_scan`` on a packed batch of
             64 documents: heavy-tailed lengths, mixed profiles, a few
             empty, one invalid.
  serve      ``serve.Engine`` with bytelm-100m at its published widths
             (random parameters from ``--seed``) answers 8 requests with
             mixed UTF-8 / UTF-16-LE ingress; no retry, no host fallback,
             no breaker event.

With ``--chips 4`` only the sharded ragged path runs: the packed batch
on a 4-device transcode mesh, through ``strategy="sharded"`` and through
the double-buffered feeder's waves, compared with the single-device
ragged launch and checked to sit on four distinct devices.

Each phase prints its name, compile seconds and wall seconds (set-up
facts, not speed figures).  Any failed check raises.  The last line of
standard output is ``{"ok": true, "device": {...}}``.  Without a TPU it
exits non-zero and prints no result.  Inputs are made from ``--seed``;
nothing is downloaded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# The nine lipsum profiles of the paper's Table 4a (Lemire & Muła,
# arXiv:2109.10433; ``repro.data.synthetic``), one document of N_CHARS
# characters each.
LIPSUM_LANGS = ["arabic", "chinese", "emoji", "hebrew", "hindi",
                "japanese", "korean", "latin", "russian"]
N_CHARS = 1 << 17
N_DOCS = 64            # packed-batch size
SERVE_REQUESTS = 8


def _fail(msg: str) -> int:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    return 1


class _CompileClock:
    """Seconds of backend (XLA and Mosaic) compilation, from JAX's own
    monitoring events.  Tracing is left out: its events nest and would
    count twice."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.total += duration


def _phase(clock, name, fn):
    c0, t0 = clock.total, time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    print(f"phase {name}: compile_s={clock.total - c0:.3f} "
          f"wall_s={wall:.3f}", flush=True)


def _expect(cond, what):
    if not cond:
        raise AssertionError(what)


def _decode_start(raw: bytes, encoding: str) -> int:
    """CPython's first-error offset in code units (-1 when valid)."""
    unit = 2 if encoding == "utf-16-le" else 1
    try:
        raw.decode(encoding)
        return -1
    except UnicodeDecodeError as e:
        return e.start // unit


def _mutate(b, rng):
    """A copy of UTF-8 bytes with a few bytes replaced by garbage that
    CPython rejects."""
    b = b.copy()
    for p in rng.choice(len(b), 4, replace=False):
        b[p] = rng.choice([0x80, 0xC0, 0xED, 0xF5, 0xFF])
    _expect(_decode_start(bytes(b), "utf-8") >= 0, "mutation left doc valid")
    return b


# ---------------------------------------------------------------------------
# Phases.


def phase_single(docs, rng):
    import jax
    import jax.numpy as jnp

    from repro.core import transcode as tc

    for lang, b in docs.items():
        text = bytes(b).decode("utf-8")
        u16 = np.frombuffer(text.encode("utf-16-le"), np.uint16)
        r = tc.transcode(jnp.asarray(b), "utf16", src_format="utf8")
        _expect(int(r.status) == -1 and int(r.count) == len(u16)
                and np.array_equal(np.asarray(r.buffer)[:len(u16)], u16),
                f"utf8->utf16 {lang}")
        r = tc.transcode(jnp.asarray(u16), "utf8", src_format="utf16")
        _expect(int(r.status) == -1 and int(r.count) == len(b)
                and np.array_equal(np.asarray(r.buffer)[:len(b)], b),
                f"utf16->utf8 {lang}")
        count, status = tc.scan(jnp.asarray(b), "utf16", src_format="utf8")
        _expect((int(count), int(status)) == (len(u16), -1),
                f"scan utf8 {lang}")
        count, status = tc.scan(jnp.asarray(u16), "utf8", src_format="utf16")
        _expect((int(count), int(status)) == (len(b), -1),
                f"scan utf16 {lang}")

    bad = _mutate(docs["arabic"], rng)
    start = _decode_start(bytes(bad), "utf-8")
    r = tc.transcode(jnp.asarray(bad), "utf16", src_format="utf8")
    _expect(int(r.status) == start, f"strict status {int(r.status)} "
            f"!= UnicodeDecodeError.start {start}")
    want = np.frombuffer(bytes(bad).decode("utf-8", "replace")
                         .encode("utf-16-le"), np.uint16)
    r = tc.transcode(jnp.asarray(bad), "utf16", src_format="utf8",
                     errors="replace")
    _expect(int(r.status) == start and int(r.count) == len(want)
            and np.array_equal(np.asarray(r.buffer)[:len(want)], want),
            "replace output != codecs 'replace'")
    _count, status = tc.scan(jnp.asarray(bad), "utf16", src_format="utf8")
    _expect(int(status) == start, "scan status on the mutated document")

    # The result above came from the Pallas kernel, not from the
    # whole-buffer ASCII branch or a reference: its HLO holds the
    # Mosaic custom call.
    x = jnp.asarray(docs["chinese"])
    hlo = jax.jit(lambda x: tc.transcode(
        x, "utf16", src_format="utf8")).lower(x).compile().as_text()
    _expect("tpu_custom_call" in hlo, "no tpu_custom_call in the HLO")


def packed_batch(rng, langs):
    """64 documents, heavy-tailed lengths, mixed profiles, three empty,
    one invalid.  Returns the UTF-8 documents."""
    from repro.data import synthetic

    lengths = np.minimum((rng.pareto(1.2, N_DOCS) + 1) * 300,
                         1 << 16).astype(int)
    lengths[[5, 17, 40]] = 0
    docs = []
    for i, n in enumerate(lengths):
        lang = langs[i % len(langs)]
        docs.append(synthetic.utf8_array(lang, int(n), seed=1000 + i)
                    if n else np.zeros(0, np.uint8))
    docs[23] = _mutate(docs[23], rng)
    return docs


def check_ragged(res, docs, errors):
    buf = np.asarray(res.buffer)
    offs, counts = np.asarray(res.offsets), np.asarray(res.counts)
    statuses = np.asarray(res.statuses)
    for k, b in enumerate(docs):
        raw = bytes(b)
        start = _decode_start(raw, "utf-8")
        _expect(int(statuses[k]) == start, f"doc {k} status")
        if start >= 0 and errors == "strict":
            continue            # codecs raises; only the offset is defined
        want = np.frombuffer(raw.decode("utf-8", errors)
                             .encode("utf-16-le"), np.uint16)
        got = buf[offs[k]:offs[k] + counts[k]]
        _expect(int(counts[k]) == len(want) and np.array_equal(got, want),
                f"doc {k} ({errors}) differs from codecs")


def phase_packed(docs):
    from repro.core import packing
    from repro.core import transcode as tc

    pk = packing.pack_documents(docs)
    for errors in ("strict", "replace"):
        res = tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                                  src_format="utf8", dst_format="utf16",
                                  errors=errors)
        check_ragged(res, docs, errors)
    counts, statuses = tc.ragged_scan(pk.data, pk.offsets, pk.lengths,
                                      src_format="utf8", dst_format="utf16")
    for k, b in enumerate(docs):
        raw = bytes(b)
        start = _decode_start(raw, "utf-8")
        _expect(int(statuses[k]) == start, f"scan doc {k} status")
        if start < 0:
            _expect(int(counts[k]) == len(raw.decode("utf-8")
                                          .encode("utf-16-le")) // 2,
                    f"scan doc {k} count")


def phase_serve(seed, rng):
    import jax

    from repro.data import synthetic
    from repro.models import registry
    from repro.serve.engine import Engine, Request

    fam, cfg, model = registry.get("bytelm-100m")
    params = model.init(jax.random.PRNGKey(seed))
    eng = Engine(model, cfg, fam, params, max_batch=4, max_prompt=128,
                 max_new=6)
    reqs = []
    for i, lang in enumerate(["latin", "arabic", "chinese", "emoji",
                              "hindi", "russian", "korean"]):
        text = bytes(synthetic.utf8_array(lang, 12, seed=seed + i)) \
            .decode("utf-8")
        if i % 2:
            reqs.append(Request(text.encode("utf-16-le"),
                                in_encoding="utf-16-le", max_new=4))
        else:
            reqs.append(Request(text.encode("utf-8"), max_new=4))
    bad = _mutate(synthetic.utf8_array("latin", 40, seed=seed), rng)
    reqs.append(Request(bytes(bad), errors="replace", max_new=4))
    _expect(len(reqs) == SERVE_REQUESTS, "request count")

    results = eng.serve(reqs)
    for k, (req, res) in enumerate(zip(reqs, results)):
        _expect(res.ok, f"request {k} not served: {res.code} {res.error}")
        start = _decode_start(req.prompt_bytes, req.in_encoding)
        _expect(res.error_offset == start, f"request {k} error_offset")
        want = (req.prompt_bytes.decode(req.in_encoding, "replace")
                .encode("utf-8") if start >= 0 else b"")
        _expect(res.sanitized_prompt == want,
                f"request {k} sanitized prompt != CPython decode")
    _expect(eng.counters["retries"] == 0, "serve retried a launch")
    _expect(eng.counters["fallback"] == 0, "serve fell back to the host")
    breaker = [e for e in eng.events if e[0].startswith("breaker_")]
    _expect(not breaker, f"breaker events: {breaker}")


def phase_sharded(docs):
    from repro.core import packing, shard
    from repro.core import transcode as tc
    from repro.data import shard_feed
    from repro.launch import mesh as launch_mesh

    mesh = launch_mesh.make_transcode_mesh(4)
    waves = [docs, docs[::-1], docs[1::2] + docs[::2]]
    packed = [packing.pack_documents(w) for w in waves]
    refs = [tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                                src_format="utf8", dst_format="utf16")
            for pk in packed]

    def same(a, b, what):
        total = int(np.asarray(a.offsets)[-1])
        _expect(np.array_equal(np.asarray(a.offsets), np.asarray(b.offsets))
                and np.array_equal(np.asarray(a.counts),
                                   np.asarray(b.counts))
                and np.array_equal(np.asarray(a.statuses),
                                   np.asarray(b.statuses))
                and np.array_equal(np.asarray(a.buffer)[:total],
                                   np.asarray(b.buffer)[:total]),
                f"{what}: sharded result differs from single-device")

    for w, (pk, ref) in enumerate(zip(packed, refs)):
        check_ragged(ref, waves[w], "strict")
        res = tc.ragged_transcode(pk.data, pk.offsets, pk.lengths,
                                  src_format="utf8", dst_format="utf16",
                                  strategy="sharded", shard_mesh=mesh)
        same(res, ref, f"strategy=sharded wave {w}")

    plans = [shard.plan_shards(pk.data, pk.offsets, pk.lengths, 4)
             for pk in packed]
    outs, _stats = shard_feed.run_sharded_waves(mesh, plans, src="utf8",
                                                dst="utf16",
                                                isolate=False)
    for w, (plan, pk, ref, out) in enumerate(zip(plans, packed, refs,
                                                  outs)):
        bufs, oos, counts, statuses = out
        held = {s.device for s in bufs.addressable_shards}
        _expect(len(held) == 4 and held == set(mesh.devices.flat),
                f"wave {w} output on {len(held)} devices, not 4")
        cap = int(np.asarray(ref.buffer).shape[0])
        res = shard._gather_result(plan, cap, np.uint16, np.asarray(bufs),
                                   np.asarray(oos), np.asarray(counts),
                                   np.asarray(statuses), True)
        same(res, ref, f"feeder wave {w}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path on four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    sys.path.insert(0, HERE)
    try:
        from repro import compile_cache
    except ImportError as e:
        return _fail(f"the repro package is not next to this script ({e})")
    compile_cache.enable()

    import jax

    from repro.data import synthetic
    from repro.kernels import runtime

    dev = jax.devices()
    if dev[0].platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev[0].platform!r} "
                     f"({dev[0].device_kind}); this smoke needs a TPU chip")
    if runtime.resolve_interpret(None) is not False:
        return _fail("Pallas kernels would run interpreted on this backend")
    if len(dev) < args.chips:
        return _fail(f"--chips {args.chips} needs {args.chips} TPU chips; "
                     f"JAX sees {len(dev)}")
    print(f"device: {dev[0].device_kind} x{len(dev)}", flush=True)

    clock = _CompileClock()
    rng = np.random.default_rng(args.seed)
    batch = packed_batch(rng, LIPSUM_LANGS)
    if args.chips == 4:
        _phase(clock, "sharded", lambda: phase_sharded(batch))
    else:
        docs = {lang: synthetic.utf8_array(lang, N_CHARS, seed=args.seed)
                for lang in ("latin", "arabic", "chinese", "emoji",
                             "hindi")}
        _phase(clock, "single", lambda: phase_single(docs, rng))
        _phase(clock, "packed", lambda: phase_packed(batch))
        _phase(clock, "serve", lambda: phase_serve(args.seed, rng))
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
