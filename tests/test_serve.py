"""Serving engine: ingress validation, batched decode, egress encodings,
continuous-batching scheduler and the submit/poll surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.models import registry
from repro.serve import kvcache
from repro.serve.engine import Engine, Request, ResultCode


@pytest.fixture(scope="module")
def engine():
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    return Engine(model, cfg, fam, params, max_batch=4, max_prompt=64,
                  max_new=8)


def test_valid_prompts_served(engine):
    res = engine.serve([Request(b"hello"), Request("café 中".encode())])
    assert all(r.ok for r in res)


def test_invalid_utf8_rejected(engine):
    res = engine.serve([Request(b"\xff\xfe bad \x80")])
    assert not res[0].ok and "invalid" in res[0].error
    assert res[0].error_offset == 0  # 0xFF is the first bad byte


def test_truncated_multibyte_strict_reports_offset(engine):
    """errors='strict' (default): truncated sequences reject with the
    first-error offset, matching Python's UnicodeDecodeError.start."""
    for prompt in [b"hi \xe4\xb8", b"abc\xc3", b"xy\xf0\x9f\x98"]:
        try:
            prompt.decode("utf-8")
            raise AssertionError("expected invalid prompt")
        except UnicodeDecodeError as e:
            want = e.start
        res = engine.serve([Request(prompt)])[0]
        assert not res.ok and "invalid" in res.error
        assert res.error_offset == want, (prompt, res.error_offset, want)


def test_truncated_multibyte_replace_served(engine):
    """errors='replace': malformed prompts are sanitized (U+FFFD per
    maximal subpart) and served, with the substitution offset surfaced."""
    prompt = b"hi \xe4\xb8 there"
    res = engine.serve([Request(prompt, errors="replace")])[0]
    assert res.ok
    assert res.error_offset == 3
    assert res.sanitized_prompt == prompt.decode(
        "utf-8", "replace").encode("utf-8")
    assert b"\xef\xbf\xbd" in res.sanitized_prompt  # U+FFFD in output
    # A clean prompt under replace carries no substitution report.
    res = engine.serve([Request(b"clean", errors="replace")])[0]
    assert res.ok and res.error_offset == -1 and res.sanitized_prompt == b""


def test_lone_surrogate_utf16_strict_reports_offset(engine):
    units = np.array([0x41, 0xD800, 0x42], np.uint16)  # A, lone hi, B
    res = engine.serve([Request(units.tobytes(),
                                in_encoding="utf-16-le")])[0]
    assert not res.ok and "invalid" in res.error
    assert res.error_offset == 1  # unit offset, exc.start // 2
    # trailing lone surrogate (truncated pair)
    units = np.array([0x41, 0xD83C], np.uint16)
    res = engine.serve([Request(units.tobytes(),
                                in_encoding="utf-16-le")])[0]
    assert not res.ok and res.error_offset == 1


def test_lone_surrogate_utf16_replace_served(engine):
    units = np.array([0x41, 0xDC00, 0x42], np.uint16)  # lone low half
    res = engine.serve([Request(units.tobytes(), in_encoding="utf-16-le",
                                errors="replace")])[0]
    assert res.ok
    assert res.error_offset == 1
    want = units.tobytes().decode("utf-16-le", "replace").encode("utf-8")
    assert res.sanitized_prompt == want
    assert b"\xef\xbf\xbd" in res.sanitized_prompt


def test_valid_utf16_prompt_equals_utf8_prompt(engine):
    """A valid UTF-16LE prompt tokenizes identically to its UTF-8 twin
    (the device transcode is the ingress tokenizer's source)."""
    s = "hé🎉"
    r8 = engine.serve([Request(s.encode("utf-8"))])[0]
    r16 = engine.serve([Request(s.encode("utf-16-le"),
                                in_encoding="utf-16-le")])[0]
    assert r8.ok and r16.ok
    assert r8.text_bytes == r16.text_bytes


def test_odd_utf16_byte_length_rejected(engine):
    res = engine.serve([Request(b"\x41\x00\x42", in_encoding="utf-16-le")])[0]
    assert not res.ok and "odd" in res.error


def test_oversize_rejected(engine):
    res = engine.serve([Request(b"x" * 1000)])
    assert not res[0].ok


def test_utf16_egress_consistent(engine):
    """Same generation, both encodings: UTF-16 output must transcode back
    to the UTF-8 output (egress goes through the paper's encoder)."""
    r8 = engine.serve([Request(b"abc")])[0]
    r16 = engine.serve([Request(b"abc", out_encoding="utf-16-le")])[0]
    assert r8.ok and r16.ok
    if r8.text_bytes:
        try:
            s8 = r8.text_bytes.decode("utf-8")
            s16 = r16.text_bytes.decode("utf-16-le")
            assert s8 == s16
        except UnicodeDecodeError:
            pass  # untrained byte model may emit invalid sequences


def test_batch_equals_individual(engine):
    """Batched serving must give the same tokens as one-at-a-time."""
    prompts = [b"aa", b"bbbb", b"c"]
    batched = engine.serve([Request(p) for p in prompts])
    single = [engine.serve([Request(p)])[0] for p in prompts]
    for b, s in zip(batched, single):
        assert b.text_bytes == s.text_bytes


def test_ring_cache_wraps():
    """SWA ring cache: decoding past the window stays finite & bounded."""
    fam, cfg, model = registry.get("h2o-danube-1.8b", reduced=True)
    params = model.init(jax.random.PRNGKey(1))
    cap = kvcache.capacity_for(cfg, 1000)
    assert cap == cfg.window  # ring buffer, not full context
    state = kvcache.init_state(model, cfg, 1, 1000)
    from repro.serve import serve_step
    dec = jax.jit(serve_step.make_decode(model, fam))
    tok = jnp.array([[5]], jnp.int32)
    key = jax.random.PRNGKey(0)
    for pos in range(0, 40):  # window is 16 in reduced config
        nxt, logits, state = dec(params, tok, jnp.array([pos]), state, key)
        assert bool(jnp.isfinite(logits).all())
    # cache capacity never grew (check the 5-D stacked K/V leaves)
    kv_leaves = [l for l in jax.tree.leaves(state) if l.ndim == 5]
    assert kv_leaves and all(l.shape[2] == cfg.window for l in kv_leaves)


def test_state_bytes_planner():
    _, cfg_full, _ = registry.get("qwen2.5-32b")
    full = kvcache.state_bytes(cfg_full, batch=128, context_len=32768)
    assert full > 1e11   # ~1.1 TB global KV for decode_32k
    _, cfg_mamba, _ = registry.get("falcon-mamba-7b")
    m = kvcache.state_bytes(cfg_mamba, batch=1, context_len=524288)
    assert m < 1e9       # SSM state independent of context


def test_matrix_utf32le_ingress(engine):
    """UTF-32LE prompts: validated/transcoded through the (utf32, utf8)
    matrix cell; identical tokens to the UTF-8 twin."""
    s = "hé🎉"
    r8 = engine.serve([Request(s.encode("utf-8"))])[0]
    r32 = engine.serve([Request(s.encode("utf-32-le"),
                                in_encoding="utf-32-le")])[0]
    assert r8.ok and r32.ok
    assert r8.text_bytes == r32.text_bytes
    # invalid scalar (lone surrogate) rejects with its code-point offset
    bad = np.array([0x41, 0xD800, 0x42], "<u4").tobytes()
    res = engine.serve([Request(bad, in_encoding="utf-32-le")])[0]
    assert not res.ok and "invalid" in res.error
    assert res.error_offset == 1
    # ...and serves sanitized under errors="replace"
    res = engine.serve([Request(bad, in_encoding="utf-32-le",
                                errors="replace")])[0]
    assert res.ok and res.error_offset == 1
    assert res.sanitized_prompt == "A�B".encode("utf-8")
    # ragged byte count rejects
    res = engine.serve([Request(b"\x41\x00\x00", in_encoding="utf-32-le")])[0]
    assert not res.ok and "multiple of 4" in res.error


def test_matrix_latin1_ingress(engine):
    """Latin-1 prompts can never be invalid; bytes >= 0x80 widen to
    2-byte UTF-8 sequences before tokenization."""
    s = "café ÿ"
    r8 = engine.serve([Request(s.encode("utf-8"))])[0]
    rl1 = engine.serve([Request(s.encode("latin-1"),
                                in_encoding="latin-1")])[0]
    assert r8.ok and rl1.ok
    assert r8.text_bytes == rl1.text_bytes
    # arbitrary bytes are a valid latin-1 prompt (incl. 0x80..0x9F)
    res = engine.serve([Request(bytes(range(1, 40)) + b"\x80\xff",
                                in_encoding="latin-1")])[0]
    assert res.ok and res.error_offset == -1


def _fresh_engine(**kw):
    fam, cfg, model = registry.get("bytelm-100m", reduced=True)
    params = model.init(jax.random.PRNGKey(0))
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_prompt", 64)
    kw.setdefault("max_new", 8)
    return Engine(model, cfg, fam, params, **kw)


def test_submit_poll_lifecycle(engine):
    t = engine.submit(Request(b"hello"))
    assert isinstance(t, int)
    assert engine.poll(t) is None          # queued, not yet drained
    engine.drain()
    res = engine.poll(t)
    assert res is not None and res.ok and res.code is ResultCode.OK
    assert engine.poll(t) is None          # poll consumes the result
    assert t in engine.latencies and engine.latencies[t] >= 0.0


def test_submit_invalid_settles_before_drain(engine):
    t = engine.submit(Request(b""))        # empty prompt: field check
    res = engine.poll(t)                   # no drain() needed
    assert res is not None and not res.ok
    assert res.code is ResultCode.REJECTED_INVALID


def test_serve_shim_matches_submit_poll(engine):
    prompts = [b"aa", b"bbbb", b"c"]
    shim = engine.serve([Request(p) for p in prompts])
    tickets = [engine.submit(Request(p)) for p in prompts]
    engine.drain()
    direct = [engine.poll(t) for t in tickets]
    for s, d in zip(shim, direct):
        assert s.ok and d.ok and s.text_bytes == d.text_bytes


def test_scheduler_param_validated():
    with pytest.raises(ValueError, match="scheduler"):
        _fresh_engine(scheduler="batch")


def test_bucket_boundaries():
    bounds = packing.bucket_boundaries(64)
    assert bounds == (8, 12, 18, 27, 40, 60, 64)
    assert bounds == tuple(sorted(set(bounds)))    # strictly increasing
    assert packing.bucket_boundaries(4) == (4,)
    assert packing.bucket_boundaries(9, min_length=8) == (8, 9)
    with pytest.raises(ValueError):
        packing.bucket_boundaries(0)
    with pytest.raises(ValueError):
        packing.bucket_boundaries(64, step=1.0)


def test_continuous_refill_mid_wave():
    """THE continuous-batching pin: with both slots taken and one request
    queued, the slot whose request finishes first must re-admit the
    queued request mid-wave, while its batch-mate is still decoding."""
    e = _fresh_engine(scheduler="continuous")
    ta = e.submit(Request(b"aaaa", max_new=2))     # finishes early
    tb = e.submit(Request(b"bbbb", max_new=8))     # decodes the tail
    tc_ = e.submit(Request(b"cccc", max_new=2))    # queued: both slots busy
    e.drain()
    assert all(e.poll(t).ok for t in (ta, tb, tc_))
    ev = {(kind, t): (slot, step)
          for kind, t, slot, step, _wall in e.events}
    assert ev[("admit", ta)][1] == ev[("admit", tb)][1] == 0
    finish_a = ev[("finish", ta)]
    finish_b = ev[("finish", tb)]
    admit_c = ev[("admit", tc_)]
    assert finish_a[1] < finish_b[1]               # a really is shorter
    # Mid-wave: c admitted BEFORE b finished, into a's freed slot.
    assert admit_c[1] < finish_b[1]
    assert admit_c[0] == finish_a[0]


def test_deadline_expiry_during_refill_ingress_frees_slot():
    """A queued request whose deadline expires BETWEEN its queue pop and
    its ingress completing (ingress is the slow path: retries, host
    fallback) must settle ``rejected_deadline`` from the post-ingress
    re-check WITHOUT consuming the freed slot — the slot goes to the
    next queued request, mid-wave, while the batch-mate still decodes."""
    now = [0.0]
    e = _fresh_engine(scheduler="continuous", clock=lambda: now[0],
                      sleep=lambda s: None)
    calls = [0]
    orig = e._ingress_chunk

    def slow_after_first(group, bound, take):
        calls[0] += 1
        if calls[0] > 1:
            now[0] += 5.0              # refill ingress "takes" 5s
        return orig(group, bound, take)

    e._ingress_chunk = slow_after_first
    ta = e.submit(Request(b"aaaa", max_new=2))    # frees its slot early
    tb = e.submit(Request(b"bbbb", max_new=8))    # still decoding then
    tc_ = e.submit(Request(b"cccc", max_new=2, deadline_s=2.0))
    td = e.submit(Request(b"dddd", max_new=2))    # should get a's slot
    e.drain()
    assert e.poll(ta).ok and e.poll(tb).ok and e.poll(td).ok
    rc = e.poll(tc_)
    assert not rc.ok and rc.code is ResultCode.REJECTED_DEADLINE
    assert e.counters["deadline"] == 1
    ev = {(kind, t): (slot, step)
          for kind, t, slot, step, _wall in e.events}
    assert ("admit", tc_) not in ev               # never took a slot
    reject_c = ev[("reject", tc_)]
    assert reject_c[0] == -1                      # slotless rejection
    finish_a, finish_b = ev[("finish", ta)], ev[("finish", tb)]
    admit_d = ev[("admit", td)]
    # Ordering pin: a frees its slot, c's pop+ingress expires it, then d
    # is admitted into THAT slot — all while b is still mid-decode.
    assert finish_a[1] <= reject_c[1] <= admit_d[1] < finish_b[1]
    assert admit_d[0] == finish_a[0]


def test_wave_scheduler_defers_refill():
    """The wave reference: the queued request is only admitted once the
    WHOLE wave drained — pinning that the schedulers actually differ."""
    e = _fresh_engine(scheduler="wave")
    ta = e.submit(Request(b"aaaa", max_new=2))
    tb = e.submit(Request(b"bbbb", max_new=8))
    tc_ = e.submit(Request(b"cccc", max_new=2))
    e.drain()
    assert all(e.poll(t).ok for t in (ta, tb, tc_))
    ev = {(kind, t): (slot, step)
          for kind, t, slot, step, _wall in e.events}
    assert ev[("admit", tc_)][1] >= ev[("finish", tb)][1]


def test_refilled_slot_inherits_nothing():
    """A request served through a refilled slot must generate the same
    tokens as the same request served alone — full-row state replacement
    leaves nothing of the predecessor behind."""
    alone = _fresh_engine(scheduler="continuous")
    want = alone.serve([Request(b"cccc", max_new=4)])[0]
    e = _fresh_engine(scheduler="continuous")
    res = e.serve([Request(b"aaaa", max_new=2),
                   Request(b"bbbb", max_new=8),
                   Request(b"cccc", max_new=4)])
    assert res[2].ok and res[2].text_bytes == want.text_bytes


def test_bucketed_prefill_shares_compile_cell():
    """Prompts in the same length bucket pad to the bucket bound: one
    prefill cell, not one per distinct prompt length."""
    e = _fresh_engine()
    res = e.serve([Request(b"abc"), Request(b"abcdefg")])   # both <= 8
    assert all(r.ok for r in res)
    prefill_cells = [k for k in e._cells if k[0] == "prefill"]
    assert prefill_cells == [("prefill", 8)]


def test_compile_cache_lru_bounded():
    e = _fresh_engine(compile_cache_size=2)
    res = e.serve([Request(b"ab"), Request(b"x" * 20), Request(b"y" * 35)])
    assert all(r.ok for r in res)
    assert len(e._cells) <= 2


def test_matrix_egress_encodings(engine):
    """Same generation in all four egress encodings: each wire form must
    decode back to the same text (latin-1 may substitute '?')."""
    res = {enc: engine.serve([Request(b"abc", out_encoding=enc)])[0]
           for enc in ("utf-8", "utf-16-le", "utf-32-le", "latin-1")}
    assert all(r.ok for r in res.values())
    if res["utf-8"].text_bytes:
        try:
            s8 = res["utf-8"].text_bytes.decode("utf-8")
        except UnicodeDecodeError:
            return  # untrained byte model may emit invalid sequences
        assert res["utf-16-le"].text_bytes.decode("utf-16-le") == s8
        assert res["utf-32-le"].text_bytes.decode("utf-32-le") == s8
        want_l1 = s8.encode("latin-1", "replace")
        assert res["latin-1"].text_bytes == want_l1
