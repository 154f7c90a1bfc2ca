"""Chaos suite: deterministic fault injection against every robustness
path (ISSUE: every injected fault class must end in a retried success or
a TYPED error — never a hang, never silent corruption, never one
request's fault contaminating its wave-mates).

The harness (``repro.testing.faults``) arms faults by *point* name and
*call index*; unarmed, every hook is a no-op passthrough, which the
first test pins.  Serve-engine tests inject at the ragged-kernel hooks
the engine's ingress actually launches through and assert against a
clean-run baseline from the same engine.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import transcode as tc
from repro.core.stream import finalize, stream_init, transcode_stream_chunk
from repro.kernels import onepass_transcode as op
from repro.models import registry
from repro.serve import engine as eng
from repro.serve.engine import Engine, Request
from repro.testing import faults

# ---------------------------------------------------------------------------
# Harness mechanics.


def test_unarmed_hooks_are_noops():
    assert faults.active() is None
    payload = np.arange(5)
    assert faults.fire(faults.KERNEL_ONEPASS, payload) is payload
    assert faults.fire(faults.STREAM_CHUNK) is None


def test_harness_counts_and_times():
    boom = faults.Fault(faults.KERNEL_ONEPASS, times=(2,))
    with faults.harness(boom) as h:
        faults.fire(faults.KERNEL_ONEPASS)          # call 1: clean
        with pytest.raises(faults.FaultInjected):
            faults.fire(faults.KERNEL_ONEPASS)      # call 2: armed
        faults.fire(faults.KERNEL_ONEPASS)          # call 3: clean again
    assert h.calls[faults.KERNEL_ONEPASS] == 3
    assert h.fired == [(faults.KERNEL_ONEPASS, "error", 2)]
    assert faults.active() is None                  # restored on exit


def test_harness_nesting_restores_outer():
    outer = faults.Fault(faults.PIPELINE_BATCH, times=None)
    with faults.harness(outer) as ho:
        with faults.harness() as hi:                # inner: no faults
            faults.fire(faults.PIPELINE_BATCH)      # must NOT raise
        assert hi.calls[faults.PIPELINE_BATCH] == 1
        assert faults.active() is ho                # outer re-armed
        with pytest.raises(faults.FaultInjected):
            faults.fire(faults.PIPELINE_BATCH)


def test_truncate_and_latency_faults():
    tr = faults.Fault(faults.STREAM_CHUNK, kind="truncate", truncate_to=2)
    lat = faults.Fault(faults.PIPELINE_BATCH, kind="latency",
                       latency_s=0.01)
    with faults.harness(tr, lat) as h:
        out = faults.fire(faults.STREAM_CHUNK, np.arange(6))
        np.testing.assert_array_equal(out, [0, 1])
        t0 = time.monotonic()
        faults.fire(faults.PIPELINE_BATCH)
        assert time.monotonic() - t0 >= 0.01
    assert {k for k, _, _ in h.fired} == {faults.STREAM_CHUNK,
                                          faults.PIPELINE_BATCH}


def test_bad_fault_kind_rejected():
    with pytest.raises(ValueError):
        faults.Fault(faults.KERNEL_ONEPASS, kind="explode")


# ---------------------------------------------------------------------------
# Kernel wrappers: faults surface as exceptions, never hangs/corruption.


def test_kernel_fault_surfaces_and_recovers():
    x = jnp.asarray(np.frombuffer(b"hello", np.uint8))
    with faults.harness(faults.Fault(faults.KERNEL_ONEPASS)):
        with pytest.raises(faults.FaultInjected):
            op.transcode_onepass(x, src="utf8", dst="utf16")
    # The failure is stateless: the very next call is clean.
    res = op.transcode_onepass(x, src="utf8", dst="utf16")
    assert int(res.count) == 5 and int(res.status) == -1


def test_stream_truncation_fault_keeps_accounting_consistent():
    """A truncated chunk loses data but must never corrupt the stream:
    the state's counts stay consistent with what was ACTUALLY processed
    (the truncated stream equals a clean stream of the truncated data)."""
    data = np.frombuffer("héllo wörld".encode("utf-8"), np.uint8)
    tr = faults.Fault(faults.STREAM_CHUNK, kind="truncate", truncate_to=3,
                      times=(2,))
    st = stream_init("utf8", "utf16")
    parts = []
    with faults.harness(tr):
        for i in range(0, len(data), 5):
            r, st = transcode_stream_chunk(st, data[i: i + 5])
            parts.append(np.asarray(r.buffer)[: int(r.count)])
    r, st = finalize(st)
    parts.append(np.asarray(r.buffer)[: int(r.count)])
    # Oracle: the same stream minus the dropped tail of chunk 2.
    seen = np.concatenate([data[:5], data[5:8], data[10:]])
    st2 = stream_init("utf8", "utf16")
    parts2 = []
    for i in range(0, len(seen), 5):
        r2, st2 = transcode_stream_chunk(st2, seen[i: i + 5])
        parts2.append(np.asarray(r2.buffer)[: int(r2.count)])
    r2, st2 = finalize(st2)
    parts2.append(np.asarray(r2.buffer)[: int(r2.count)])
    assert st.out_count == st2.out_count
    assert st.status == st2.status
    np.testing.assert_array_equal(np.concatenate(parts),
                                  np.concatenate(parts2))


def test_latency_fault_leaves_results_identical():
    x = jnp.asarray(np.frombuffer("café".encode(), np.uint8))
    clean = op.transcode_onepass(x, src="utf8", dst="utf16")
    lat = faults.Fault(faults.KERNEL_ONEPASS, kind="latency",
                       latency_s=0.01, times=None)
    with faults.harness(lat) as h:
        slow = op.transcode_onepass(x, src="utf8", dst="utf16")
    assert h.fires_at(faults.KERNEL_ONEPASS)
    assert int(slow.count) == int(clean.count)
    assert int(slow.status) == int(clean.status)
    np.testing.assert_array_equal(np.asarray(slow.buffer),
                                  np.asarray(clean.buffer))


def test_pipeline_batch_fault_surfaces_and_recovers():
    from repro.data import pipeline
    docs = np.zeros((2, 8), np.uint8)
    docs[:, :5] = np.frombuffer(b"hello", np.uint8)
    lengths = np.array([5, 5], np.int32)
    with faults.harness(faults.Fault(faults.PIPELINE_BATCH)):
        with pytest.raises(faults.FaultInjected):
            pipeline.batch_transcode(docs, lengths)
    res = pipeline.batch_transcode(docs, lengths)
    assert list(np.asarray(res.count)) == [5, 5]


# ---------------------------------------------------------------------------
# Capacity-overflow sentinel (satellite): speculative garbage beyond
# CAP_FACTOR capacity drops at capacity with a non-(-1) status.


@pytest.mark.parametrize("src,dst", tc.PAIRS)
def test_capacity_overflow_drops_at_capacity(src, dst):
    n = 1024
    x = faults.capacity_overflow_input(src, n)
    res = tc.transcode(jnp.asarray(x), dst, src_format=src, n_valid=n,
                       strategy="onepass", errors="strict")
    cap = tc.CAP_FACTOR[(src, dst)] * n
    assert len(res.buffer) == cap            # output clipped AT capacity
    if (src, dst) in faults.OVERFLOW_PAIRS:
        # The flood's speculative unit count exceeds capacity — the
        # write must drop at cap, flagged by a real (>= 0) status.
        assert int(res.count) > cap
        assert int(res.status) >= 0
    elif src == "latin1":
        assert int(res.status) == -1         # latin1 is never invalid
        assert int(res.count) <= cap
    else:
        assert int(res.status) >= 0          # flood is invalid input
        assert int(res.count) <= cap


# ---------------------------------------------------------------------------
# Serve engine under injected faults.


@pytest.fixture(scope="module")
def served():
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    return Engine(model, cfg, fam, params, max_batch=4, max_prompt=64,
                  max_new=8, backoff_base_s=0.0)


CLEAN = b"hello"
POISON = b"bad \xff byte"


def test_serve_transient_fault_retried_to_success(served):
    baseline = served.serve([Request(CLEAN)])[0]
    r0 = served.counters["retries"]
    # First ragged-scan launch fails once; the retry must succeed and
    # the result must be byte-identical to the clean run.
    with faults.harness(faults.Fault(faults.KERNEL_RAGGED_SCAN,
                                     times=(1,))):
        res = served.serve([Request(CLEAN)])[0]
    assert res.ok and res.code == eng.OK
    assert res.text_bytes == baseline.text_bytes
    assert served.counters["retries"] == r0 + 1


def test_serve_persistent_fault_degrades_to_host_fallback(served):
    baseline = served.serve([Request(CLEAN)])[0]
    f0 = served.counters["fallback"]
    # EVERY ragged launch fails: the wave must degrade per-document to
    # the host codecs path — clean prompts still serve (same bytes),
    # poison prompts get their typed per-document rejection with the
    # right offset, and neither contaminates the other.
    with faults.harness(
            faults.Fault(faults.KERNEL_RAGGED_SCAN, times=None),
            faults.Fault(faults.KERNEL_RAGGED, times=None),
            faults.Fault(faults.KERNEL_ONEPASS, times=None)):
        res = served.serve([Request(CLEAN), Request(POISON),
                            Request(POISON, errors="replace")])
    assert res[0].ok and res[0].text_bytes == baseline.text_bytes
    assert not res[1].ok and res[1].code == eng.REJECTED_INVALID
    assert res[1].error_offset == POISON.index(0xFF)
    assert res[2].ok
    assert res[2].sanitized_prompt == POISON.decode(
        "utf-8", "replace").encode("utf-8")
    assert served.counters["fallback"] >= f0 + 3
    assert served.counters["retries"] > 0


@pytest.mark.parametrize("point", [faults.KERNEL_RAGGED_SCAN,
                                   faults.KERNEL_ONEPASS,
                                   faults.KERNEL_RAGGED])
def test_serve_lowering_refusal_propagates(point):
    """A kernel the compiler refuses fails the same way on every launch:
    it leaves the engine at once, with no retry, no host fallback and no
    breaker event (its own engine: the refusal abandons the wave)."""
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    e = Engine(model, cfg, fam, model.init(jax.random.PRNGKey(0)),
               max_batch=4, max_prompt=64, max_new=8, backoff_base_s=0.0)
    refusal = faults.Fault(point, times=None, exc=lambda: NotImplementedError(
        "Unimplemented primitive in Pallas TPU lowering: cumsum"))
    with faults.harness(refusal):
        with pytest.raises(NotImplementedError, match="TPU lowering"):
            e.serve([Request(CLEAN), Request(POISON, errors="replace"),
                     Request("héllo".encode("utf-16-le"),
                             in_encoding="utf-16-le")])
    assert e.counters["retries"] == 0
    assert e.counters["fallback"] == 0
    assert not [ev for ev in e.events if ev[0].startswith("breaker_")]


def test_serve_unit_group_fallback_matches_device_semantics(served):
    prompt16 = "héllo".encode("utf-16-le")
    lone = np.array([0xD800], "<u2").tobytes() + prompt16
    baseline = served.serve([Request(prompt16, in_encoding="utf-16-le")])[0]
    with faults.harness(faults.Fault(faults.KERNEL_RAGGED, times=None),
                        faults.Fault(faults.KERNEL_RAGGED_SCAN,
                                     times=None)):
        res = served.serve([
            Request(prompt16, in_encoding="utf-16-le"),
            Request(lone, in_encoding="utf-16-le"),
            Request(lone, in_encoding="utf-16-le", errors="replace")])
    assert res[0].ok and res[0].text_bytes == baseline.text_bytes
    assert not res[1].ok and res[1].code == eng.REJECTED_INVALID
    assert res[1].error_offset == 0          # unit-relative offset
    assert res[2].ok and res[2].sanitized_prompt.startswith(
        "�".encode("utf-8"))


def test_serve_poison_wave_isolation_device_path(served):
    """No faults armed: one poison document in a packed wave must
    degrade to ITS error only — wave-mates before and after serve."""
    res = served.serve([Request(CLEAN), Request(POISON), Request(b"world")])
    assert res[0].ok and res[2].ok
    assert not res[1].ok and res[1].code == eng.REJECTED_INVALID


def test_serve_bad_out_encoding_isolated(served):
    """Egress poison: an unknown out_encoding yields a typed
    per-document failure, not an exception that eats the wave."""
    res = served.serve([Request(CLEAN), Request(b"ok", out_encoding="ebcdic")])
    assert res[0].ok
    assert not res[1].ok and res[1].code == eng.FAILED_TRANSCODE
    assert "out_encoding" in res[1].error


def test_serve_overload_sheds_typed(served):
    n = served.queue_limit + 3
    res = served.serve([Request(CLEAN) for _ in range(n)])
    shed = [r for r in res if r.code == eng.REJECTED_OVERLOAD]
    assert len(shed) == 3
    assert all(not r.ok and "queue full" in r.error for r in shed)
    assert all(r.ok for r in res[: served.queue_limit])
    assert served.counters["shed"] >= 3


def test_serve_deadline_expiry_typed():
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    now = [0.0]
    e = Engine(model, cfg, fam, params, max_batch=4, max_prompt=64,
               max_new=8, clock=lambda: now[0], sleep=lambda s: None)

    res = e.serve([Request(CLEAN, deadline_s=10.0)])[0]
    assert res.ok                            # generous deadline: serves

    orig = e._ingress_chunk

    def slow_ingress(group, bound, take):
        now[0] += 5.0                        # ingress "takes" 5s
        return orig(group, bound, take)

    e._ingress_chunk = slow_ingress
    res = e.serve([Request(CLEAN, deadline_s=1.0),
                   Request(CLEAN, deadline_s=60.0)])
    assert not res[0].ok and res[0].code == eng.REJECTED_DEADLINE
    assert res[1].ok
    assert e.counters["deadline"] == 1


# ---------------------------------------------------------------------------
# Fault-point registry completeness (satellite): every ``faults.POINTS``
# entry must have (a) a ``faults.fire()`` call site under ``src/repro``
# and (b) a test-reachable code path that actually drives a call through
# it — so a new kernel or subsystem can't ship a registry entry without
# chaos coverage (mirrors PR 7's ``tc.DEPRECATED`` completeness sweep).


def _point_constants():
    """point value -> module constant name (e.g. "kernel.onepass" ->
    "KERNEL_ONEPASS"), built from the module itself so a new POINTS
    entry is covered without editing this test."""
    names = {v: k for k, v in vars(faults).items()
             if k.isupper() and isinstance(v, str) and v in faults.POINTS}
    assert set(names) == set(faults.POINTS)
    return names


def test_every_fault_point_has_a_src_call_site():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src", "repro")
    blobs = []
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    blobs.append(fh.read())
    text = "\n".join(blobs)
    for point, const in sorted(_point_constants().items()):
        assert f"faults.fire(faults.{const}" in text, (
            f"fault point {point!r} has no faults.fire(faults.{const}...) "
            f"call site under src/repro — a registry entry nothing can "
            f"inject into")


def _packed_docs():
    from repro.core import packing
    return packing.pack_documents(
        [np.frombuffer(b"hello", np.uint8),
         np.frombuffer(b"world!", np.uint8)], dtype=np.uint8)


def _x_onepass():
    op.transcode_onepass(jnp.asarray(np.frombuffer(b"hello", np.uint8)),
                         src="utf8", dst="utf16")


def _x_scan():
    op.scan_onepass(jnp.asarray(np.frombuffer(b"hello", np.uint8)),
                    src="utf8", dst="utf16")


def _x_ragged():
    from repro.kernels import ragged_transcode as rt
    p = _packed_docs()
    rt.transcode_ragged(p.data, p.offsets, p.lengths,
                        src="utf8", dst="utf16")


def _x_ragged_scan():
    from repro.kernels import ragged_transcode as rt
    p = _packed_docs()
    rt.scan_ragged(p.data, p.offsets, p.lengths, src="utf8", dst="utf16")


def _x_stream():
    st = stream_init("utf8", "utf16")
    transcode_stream_chunk(st, np.frombuffer(b"hello", np.uint8))


def _x_pipeline():
    from repro.data import pipeline
    docs = np.zeros((1, 8), np.uint8)
    docs[0, :5] = np.frombuffer(b"hello", np.uint8)
    pipeline.batch_transcode(docs, np.array([5], np.int32))


def _x_shard_launch():
    from repro.core import shard
    p = _packed_docs()
    shard.ragged_transcode_sharded(p.data, p.offsets, p.lengths,
                                   src_format="utf8", dst_format="utf16",
                                   n_shards=1)


def _x_feed_stage():
    from jax.sharding import Mesh
    from repro.data.shard_feed import DoubleBufferedFeeder
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    feeder = DoubleBufferedFeeder(mesh, stage_fn=lambda arrays: arrays)
    try:
        feeder.run([("w0",)], lambda *staged: staged)
    finally:
        feeder.close()


def _x_engine_probe():
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    e = Engine(model, cfg, fam, params, max_batch=2, max_prompt=64,
               max_new=4, backoff_base_s=0.0, sleep=lambda s: None,
               breaker_threshold=1, breaker_cooldown_s=0.0)
    e.serve([Request(CLEAN)])            # pre-warm the utf-8 cells
    # Trip the breaker under a NESTED harness so the failure injection
    # is invisible to the outer (counting) harness.
    with faults.harness(faults.Fault(faults.KERNEL_RAGGED_SCAN,
                                     times=None)):
        e.serve([Request(CLEAN)])        # retries exhaust -> breaker opens
    e.serve([Request(CLEAN)])            # cooldown 0 -> half-open probe


_EXERCISERS = {
    faults.KERNEL_ONEPASS: _x_onepass,
    faults.KERNEL_SCAN: _x_scan,
    faults.KERNEL_RAGGED: _x_ragged,
    faults.KERNEL_RAGGED_SCAN: _x_ragged_scan,
    faults.STREAM_CHUNK: _x_stream,
    faults.PIPELINE_BATCH: _x_pipeline,
    faults.SHARD_LAUNCH: _x_shard_launch,
    faults.FEED_STAGE: _x_feed_stage,
    faults.ENGINE_PROBE: _x_engine_probe,
}


def test_exerciser_registry_covers_every_point():
    assert set(_EXERCISERS) == set(faults.POINTS), (
        "a new faults.POINTS entry needs an exerciser here — otherwise "
        "it can ship without any test able to reach its fire() call")


@pytest.mark.parametrize("point", faults.POINTS)
def test_every_fault_point_reachable_from_tests(point):
    with faults.harness() as h:          # no faults armed: count only
        _EXERCISERS[point]()
    assert h.calls.get(point, 0) >= 1, (
        f"exerciser for {point!r} never drove a call through its "
        f"fire() site")
