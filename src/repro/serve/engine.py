"""Continuously-batched serving engine with transcode ingress/egress.

Requests arrive as raw UTF-8, UTF-16LE, UTF-32LE or Latin-1 byte strings
(the full codec matrix, DESIGN.md §8) through a submit/poll surface:

  * :meth:`Engine.submit` — cheap host-side field validation, bounded
    admission (overload shed beyond ``queue_limit``), then the request is
    enqueued into a **length-bucketed** admission queue (tensor2tensor
    ``bucket_by_sequence_length``-style multiplicative boundaries,
    :func:`repro.core.packing.bucket_boundaries`) keyed by
    ``(encoding, errors)`` group.  Returns an int ticket.
  * :meth:`Engine.drain` — the slot-level decode loop.  Each of
    ``max_batch`` decode slots is refilled **the moment it frees** (EOS /
    token budget), mid-wave, from the queue whose head ticket is oldest:
    continuous batching, not wave batching.  A refilled slot inherits
    NOTHING from its predecessor — its KV-cache row is replaced
    wholesale by the freshly prefilled row, and deadlines, retry
    counters, poison isolation and typed :class:`ResultCode` outcomes
    all hold per-slot.
  * :meth:`Engine.poll` — settled :class:`Result` by ticket (or ``None``
    while queued / in flight).

The old ``Engine.serve(list) -> list`` survives as a thin synchronous
shim (submit all, drain, poll each) — continuous batching is not
expressible through a batch-in/batch-out call.

**Ingress** stays packed multi-request (the paper's validation running
at the API boundary): each refill takes up to ``max_batch`` same-bucket
prompts and runs ONE ragged launch — a counting scan
(fused validation + per-document error location) for UTF-8, a ragged
transcode to UTF-8 through the matrix cell for unit encodings — padded
to the bucket's geometry, so there is **one compilation per (bucket,
errors-policy) cell**, held in an LRU-bounded compile cache.  Prefill
likewise pads to the bucket bound, one compiled cell per bucket instead
of one per distinct prompt length.  The deadline/retry/shed/fallback machinery
rides the slot loop: transient launch failures retry with backoff, a
persistently failing group degrades per-document to the host ``codecs``
path, expired deadlines free their queue position with a typed
rejection, and egress failures poison only their own slot.

**Egress** detokenizes to any matrix format (UTF-8 / UTF-16LE /
UTF-32LE / Latin-1) through the vectorized encoders.

A per-ingress-group **circuit breaker** (:class:`_Breaker`) sits above
the retry ladder: ``breaker_threshold`` consecutive chunk-launch
failures open the group, open chunks route launch-free to the host
fallback, and after ``breaker_cooldown_s`` a half-open probe (one
launch, real traffic, no retries) decides between closing the breaker
and another cooldown — a persistently-down device path costs one probe
per cooldown instead of a retry+backoff storm per chunk.

Scheduling observability: ``Engine.events`` records the slot lifecycle
of the most recent :meth:`drain` as ``(kind, ticket, slot, step, wall)``
tuples (``kind`` in ``"admit"`` / ``"finish"`` / ``"reject"``, ``step``
the global decode-step counter) — the continuous-vs-wave benchmark and
the mid-wave-refill test both read it.  Breaker transitions append
``("breaker_open" | "breaker_half_open" | "breaker_closed", group,
-1, step, wall)`` to the same log (cleared per drain, so transition
assertions must read the drain that caused them).  ``Engine.latencies`` maps
recently settled tickets to their submit→settle wall time.  Both are
**bounded** — ``events`` is a ring buffer (``event_limit`` newest
entries) and ``latencies`` an insertion-ordered window (``latency_window``
newest settles, oldest evicted like the compile-cache LRU) — so a
long-running engine's memory footprint is flat no matter how many
tickets it serves.  Rolling nearest-rank percentiles over the latency
window are published as ``counters["latency_p50_ms"]`` /
``counters["latency_p99_ms"]`` on every settle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import enum
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core import transcode as tc
from repro.data.tokenizer import BOS_ID, EOS_ID, N_SPECIAL, ByteTokenizer
from repro.kernels import runtime
from repro.serve import kvcache, serve_step
from repro.testing import faults


class ResultCode(str, enum.Enum):
    """Typed result codes (``Result.code``; failure-mode table in
    DESIGN.md §10).  ``ok`` stays the boolean verdict; the code names WHY
    a request did not serve — load-shedding and deadline misses are not
    the same failure as an invalid prompt, and callers (and the chaos
    suite) need to tell them apart without parsing message strings.

    String-valued for backward compatibility: every member compares equal
    to (and serializes as) the bare string literal it replaced, so
    ``result.code == "rejected_overload"`` keeps working.
    """

    OK = "ok"
    REJECTED_INVALID = "rejected_invalid"     # bad prompt/field (permanent)
    REJECTED_OVERLOAD = "rejected_overload"   # admission queue full (shed)
    REJECTED_DEADLINE = "rejected_deadline"   # per-request deadline expired
    FAILED_TRANSCODE = "failed_transcode"     # device path down, no fallback

    __str__ = str.__str__    # render the wire value, not the member name


# Backward-compatible module aliases (``eng.OK`` etc. predate the enum).
OK = ResultCode.OK
REJECTED_INVALID = ResultCode.REJECTED_INVALID
REJECTED_OVERLOAD = ResultCode.REJECTED_OVERLOAD
REJECTED_DEADLINE = ResultCode.REJECTED_DEADLINE
FAILED_TRANSCODE = ResultCode.FAILED_TRANSCODE


@dataclasses.dataclass
class Request:
    prompt_bytes: bytes
    # Per-request generation budget, clamped to the engine's ``max_new``.
    max_new: int = 32
    # "utf-8" | "utf-16-le" | "utf-32-le" | "latin-1" (full codec matrix)
    out_encoding: str = "utf-8"
    in_encoding: str = "utf-8"
    errors: str = "strict"          # "strict" | "replace"
    # Per-request deadline, in seconds from ``submit()`` (None = no
    # deadline).  A request whose deadline expires before its slot
    # admission is rejected with ``REJECTED_DEADLINE`` instead of holding
    # a slot — late answers are dropped work, not service.
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class Result:
    ok: bool
    text_bytes: bytes = b""
    error: str = ""
    # Offset of the first invalid element in the prompt (bytes for utf-8,
    # code units for utf-16-le; Python ``UnicodeDecodeError.start``
    # semantics), -1 when the prompt was well-formed.  Populated for
    # strict rejections AND for replace-mode substitutions.
    error_offset: int = -1
    # Under errors="replace": the prompt actually served, as UTF-8, with
    # U+FFFD substituted per maximal subpart (empty otherwise).
    sanitized_prompt: bytes = b""
    # Typed outcome: OK for served requests, else which failure mode
    # rejected the request.
    code: ResultCode = ResultCode.OK


@dataclasses.dataclass
class _Slot:
    """One live decode slot (private): the request it serves, its prompt
    provenance, and the tokens generated so far."""

    ticket: int
    req: Request
    error_offset: int
    sanitized: bytes
    budget: int
    tokens: List[int] = dataclasses.field(default_factory=list)


class _Breaker:
    """Per-ingress-group circuit breaker (closed / open / half-open).

    The retry+backoff ladder is the right answer to a TRANSIENT launch
    failure; against a persistently-down device path it becomes a
    storm — every chunk pays ``max_retries`` launches plus backoff
    sleeps before falling back.  The breaker remembers: after
    ``threshold`` consecutive chunk-level failures the group goes
    **open** and chunks route straight to the host ``codecs`` fallback
    with **zero** device launches.  After ``cooldown_s`` on the
    injectable clock the next chunk is a **half-open probe**: ONE
    launch, no retries, carrying that chunk's real traffic — success
    closes the breaker (full service resumes), failure re-opens it for
    another cooldown.  Any full-path success resets the failure count.
    """

    __slots__ = ("threshold", "cooldown_s", "_clock", "state",
                 "failures", "opened_at")

    def __init__(self, threshold: int, cooldown_s: float, clock):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self.state = "closed"
        self.failures = 0
        self.opened_at: Optional[float] = None

    def route(self) -> str:
        """How the next chunk launch should run: ``"full"`` (closed —
        retry+backoff), ``"probe"`` (half-open — one launch, no
        retries) or ``"skip"`` (open — host fallback, no launch).
        Moves open -> half_open when the cooldown has elapsed."""
        if self.state == "open":
            if self._clock() - self.opened_at >= self.cooldown_s:
                self.state = "half_open"
                return "probe"
            return "skip"
        if self.state == "half_open":
            return "probe"
        return "full"

    def record(self, ok: bool) -> Optional[str]:
        """Record a routed launch outcome; returns the new state name
        when this outcome caused a transition, else ``None``."""
        if ok:
            self.failures = 0
            if self.state != "closed":
                self.state = "closed"
                self.opened_at = None
                return "closed"
            return None
        self.failures += 1
        if self.state == "half_open" or self.failures >= self.threshold:
            self.state = "open"
            self.opened_at = self._clock()
            return "open"
        return None


class Engine:
    def __init__(self, model, cfg, family: str, params, max_batch: int = 8,
                 max_prompt: int = 512, max_new: int = 128,
                 temperature: float = 0.0, queue_limit: Optional[int] = None,
                 max_retries: int = 2, backoff_base_s: float = 0.05,
                 clock=time.monotonic, sleep=time.sleep,
                 scheduler: str = "continuous",
                 bucket_min: int = 8, bucket_step: float = 1.5,
                 compile_cache_size: int = 32,
                 latency_window: int = 1024, event_limit: int = 4096,
                 ingress_shards: int = 1,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0):
        if scheduler not in ("continuous", "wave"):
            raise ValueError(
                f"scheduler must be 'continuous' or 'wave', got {scheduler!r}")
        if ingress_shards < 1:
            raise ValueError(
                f"ingress_shards must be >= 1, got {ingress_shards}")
        self.model, self.cfg, self.family = model, cfg, family
        self.params = params
        self.max_batch, self.max_prompt, self.max_new = (
            max_batch, max_prompt, max_new)
        # Admission bound: at most this many requests queued; the tail is
        # shed with REJECTED_OVERLOAD instead of growing an unbounded
        # work list (DESIGN.md §10).
        self.queue_limit = (4 * max_batch if queue_limit is None
                            else queue_limit)
        # Transient-failure policy: a failed transcode launch is retried
        # ``max_retries`` times with exponential backoff (base doubles
        # per attempt) before the group degrades to the host fallback.
        self.max_retries = max_retries
        self.backoff_base_s = backoff_base_s
        # Injectable for deterministic chaos tests — production uses the
        # monotonic clock and real sleep.
        self._clock, self._sleep = clock, sleep
        # Circuit breakers, one per ingress group, created lazily on
        # first use (see _Breaker): ``breaker_threshold`` consecutive
        # chunk failures open a group; ``breaker_cooldown_s`` (on the
        # injectable clock) gates the half-open probe.
        if breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {breaker_threshold}")
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown_s = breaker_cooldown_s
        self._breakers: Dict[str, _Breaker] = {}
        # "continuous": a freed slot refills immediately, mid-wave.
        # "wave": refill only once ALL slots drain — the wave-batching
        # reference.
        self.scheduler = scheduler
        # Observability: how often the robustness paths actually fired.
        #   retries   — transient launch failures retried
        #   fallback  — prompts served via the host ``codecs`` path
        #   shed      — requests rejected at admission (overload)
        #   deadline  — requests expired before their slot admission
        #   breaker_open / breaker_half_open / breaker_closed — breaker
        #               state TRANSITIONS (not states); breaker_skip
        #               counts chunks routed to fallback launch-free
        #               while open, breaker_probe the half-open probes.
        self.counters = collections.Counter()
        # Length-bucket upper bounds (inclusive), shared by the admission
        # queues, the ingress pack geometry and the prefill padding.
        self._bounds = packing.bucket_boundaries(
            max_prompt, min_length=bucket_min, step=bucket_step)
        # Admission queues: (group, bucket_bound) -> deque of
        # (ticket, request, units).  ``group`` is "utf-8" or the
        # (encoding, errors) pair — the unit that shares one ragged
        # ingress launch.
        self._queues: Dict[tuple, collections.deque] = {}
        self._pending = 0
        self._next_ticket = 0
        self._results: Dict[int, Result] = {}
        self._submit_t: Dict[int, float] = {}
        self._deadlines: Dict[int, float] = {}
        # Settled-ticket latency (submit -> settle, seconds) and the slot
        # lifecycle of the most recent drain() (see module docstring).
        # Both are bounded: a long-running engine settles unboundedly many
        # tickets, so `latencies` keeps only the newest `latency_window`
        # entries (insertion-ordered eviction, like the compile cache) and
        # `events` is a ring buffer of the newest `event_limit` tuples.
        self.latencies: "collections.OrderedDict[int, float]" = \
            collections.OrderedDict()
        self._latency_window = latency_window
        # Sorted view of the latency window for O(1) rolling percentiles.
        self._lat_sorted: List[float] = []
        self.events: collections.deque = collections.deque(
            maxlen=event_limit)
        self._step = 0
        # LRU-bounded compile cache, one jitted cell per (kind, bucket,
        # errors-policy): hit refreshes recency, insert beyond capacity
        # evicts the coldest executable.
        self._cells: "collections.OrderedDict[tuple, object]" = \
            collections.OrderedDict()
        self._cell_limit = compile_cache_size
        self.tok = ByteTokenizer()
        self._decode = jax.jit(serve_step.make_decode(model, family,
                                                      temperature))
        self._ctx = max_prompt + max_new
        # Sharded ingress (DESIGN.md §12): with ingress_shards > 1 a
        # drain wave's ragged ingress launches fan out across a 1-D
        # data mesh — one onepass launch per shard — instead of one
        # single-device launch per chunk.  The shard_map executables are
        # cached inside repro.core.shard, not in self._cells.
        self.ingress_shards = ingress_shards
        self._ingress_mesh = None
        if ingress_shards > 1:
            from repro.launch import mesh as launch_mesh
            self._ingress_mesh = launch_mesh.make_transcode_mesh(
                ingress_shards)

    # ------------------------------------------------------------------
    # Compile cache.

    def _cell(self, key, build):
        """Jitted cell for ``key``, LRU-refreshed; built (and compiled on
        first call) at most once while it stays resident."""
        if key in self._cells:
            self._cells[key] = self._cells.pop(key)
            return self._cells[key]
        fn = build()
        self._cells[key] = fn
        while len(self._cells) > self._cell_limit:
            self._cells.popitem(last=False)
        return fn

    def _launch_with_retry(self, fn):
        """Run a transcode-launch thunk, retrying transient failures with
        exponential backoff; the final failure propagates to the caller
        (which degrades to the host fallback).  A refusal to lower or
        compile the kernel (``runtime.refused``) is not transient: it
        propagates at once, and the callers re-raise it too."""
        delay = self.backoff_base_s
        for attempt in range(self.max_retries + 1):
            try:
                return fn()
            except Exception as e:
                if attempt == self.max_retries or runtime.refused(e):
                    raise
                self.counters["retries"] += 1
                self._sleep(delay)
                delay *= 2

    # ------------------------------------------------------------------
    # Circuit breaker (one per ingress group; DESIGN.md §10).

    @staticmethod
    def _group_name(group) -> str:
        """Stable string key/event label for an ingress group ("utf-8"
        or an (encoding, errors) pair)."""
        return group if isinstance(group, str) else ":".join(group)

    def _breaker_route(self, group):
        """The group's breaker and its routing verdict for the next
        chunk launch ("full" / "probe" / "skip"); emits the open ->
        half_open transition and counts launch-free skips."""
        name = self._group_name(group)
        br = self._breakers.get(name)
        if br is None:
            br = self._breakers[name] = _Breaker(
                self.breaker_threshold, self.breaker_cooldown_s,
                self._clock)
            return br, "full"
        before = br.state
        mode = br.route()
        if br.state != before:          # open -> half_open (cooldown up)
            self._breaker_event(name, br.state)
        if mode == "skip":
            self.counters["breaker_skip"] += 1
        return br, mode

    def _breaker_record(self, group, br: _Breaker, ok: bool):
        transition = br.record(ok)
        if transition is not None:
            self._breaker_event(self._group_name(group), transition)

    def _breaker_event(self, name: str, state: str):
        self.counters[f"breaker_{state}"] += 1
        self.events.append((f"breaker_{state}", name, -1, self._step,
                            self._clock()))

    def _probe_launch(self, fn):
        """Half-open probe: exactly ONE launch, no retry, no backoff —
        the probe either closes the breaker or re-opens it.  It carries
        the chunk's real traffic, so a success IS served work."""
        self.counters["breaker_probe"] += 1
        faults.fire(faults.ENGINE_PROBE)
        return fn()

    # ------------------------------------------------------------------
    # Admission (submit / poll / drain / serve).

    # Unit widths and packed dtypes per non-UTF-8 ingress encoding; the
    # wire bytes split into units with an EXPLICIT little-endian dtype
    # ('<u2'/'<u4', host-endianness-independent — unlike a native-order
    # ``.view(np.uint16)``, whose meaning flips on a big-endian host).
    # The jnp byte-math twins (``tc.utf16le_bytes_to_units`` /
    # ``tc.utf32le_bytes_to_cps``) serve device-resident buffers; this
    # is the host-side pre-pack path, where a device round trip per
    # prompt would be pure overhead.
    _UNIT_INGRESS = {
        "utf-16-le": (2, np.uint16, "utf16", "unit"),
        "utf-32-le": (4, np.uint32, "utf32", "code point"),
        "latin-1": (1, np.uint8, "latin1", "byte"),
    }

    @staticmethod
    def _wire_units(raw: np.ndarray, width: int, np_dtype) -> np.ndarray:
        if width == 1:
            return raw.astype(np_dtype)
        le = np.frombuffer(raw.tobytes(), np.dtype(f"<u{width}"))
        return le.astype(np_dtype)

    def _bound(self, n: int) -> int:
        """Bucket upper bound for a sequence of ``n`` elements."""
        return self._bounds[min(bisect.bisect_left(self._bounds, n),
                                len(self._bounds) - 1)]

    def _settle(self, ticket: int, result: Result):
        self._results[ticket] = result
        self._deadlines.pop(ticket, None)
        t0 = self._submit_t.pop(ticket, None)
        if t0 is not None:
            lat = self._clock() - t0
            # Self-heal the sorted view if a consumer cleared/mutated the
            # public window externally (the serve benchmark does).
            if len(self._lat_sorted) != len(self.latencies):
                self._lat_sorted = sorted(self.latencies.values())
            self.latencies[ticket] = lat
            bisect.insort(self._lat_sorted, lat)
            while len(self.latencies) > self._latency_window:
                _t, old = self.latencies.popitem(last=False)
                del self._lat_sorted[bisect.bisect_left(self._lat_sorted,
                                                        old)]
            # Rolling nearest-rank percentiles over the bounded window.
            s = self._lat_sorted
            self.counters["latency_p50_ms"] = s[(len(s) - 1) // 2] * 1e3
            self.counters["latency_p99_ms"] = \
                s[(len(s) - 1) * 99 // 100] * 1e3

    def submit(self, request: Request) -> int:
        """Admit one request; returns its ticket (an int).

        Host-side field validation and overload shedding happen here,
        synchronously — a rejected request settles immediately and its
        result is already pollable.  Valid requests enter the
        length-bucketed admission queue and settle during :meth:`drain`.
        """
        ticket = self._next_ticket
        self._next_ticket += 1
        now = self._clock()
        self._submit_t[ticket] = now
        if request.deadline_s is not None:
            self._deadlines[ticket] = now + request.deadline_s

        def reject(error: str) -> int:
            self._settle(ticket, Result(ok=False, code=REJECTED_INVALID,
                                        error=error))
            return ticket

        if request.errors not in ("strict", "replace"):
            return reject(f"unknown errors policy: {request.errors}")
        raw = np.frombuffer(request.prompt_bytes, np.uint8)
        if request.in_encoding in self._UNIT_INGRESS:
            width, np_dtype, _src, _noun = \
                self._UNIT_INGRESS[request.in_encoding]
            if len(raw) % width:
                return reject(
                    f"odd {request.in_encoding} prompt byte length"
                    if width == 2 else
                    f"{request.in_encoding} prompt byte length not a "
                    f"multiple of {width}")
            units = self._wire_units(raw, width, np_dtype)
            if len(units) == 0 or len(units) > self.max_prompt:
                return reject("empty or oversize prompt")
            group = (request.in_encoding, request.errors)
        elif request.in_encoding == "utf-8":
            if len(raw) == 0 or len(raw) > self.max_prompt - 1:
                return reject("empty or oversize prompt")
            units, group = raw, "utf-8"
        else:
            return reject(f"unknown in_encoding: {request.in_encoding}")

        if self._pending >= self.queue_limit:
            self.counters["shed"] += 1
            self._settle(ticket, Result(
                ok=False, code=REJECTED_OVERLOAD,
                error=(f"admission queue full ({self.queue_limit} slots); "
                       f"request shed")))
            return ticket
        qkey = (group, self._bound(len(units)))
        self._queues.setdefault(qkey, collections.deque()).append(
            (ticket, request, units))
        self._pending += 1
        return ticket

    def poll(self, ticket: int) -> Optional[Result]:
        """Settled :class:`Result` for ``ticket`` (removing it), or
        ``None`` while the request is still queued / in flight."""
        return self._results.pop(ticket, None)

    def serve(self, requests: List[Request]) -> List[Result]:
        """Synchronous shim over submit/drain/poll (the legacy batch
        API): every request settles before this returns, in order."""
        tickets = [self.submit(r) for r in requests]
        self.drain()
        return [self.poll(t) for t in tickets]  # type: ignore[misc]

    # ------------------------------------------------------------------
    # The slot-level decode loop.

    def drain(self) -> None:
        """Run the continuous-batching loop until every queued request
        settles.  Resets :attr:`events` and the step counter."""
        B = self.max_batch
        self.events.clear()
        self._step = 0
        if not self._pending:
            return
        state = kvcache.init_state(self.model, self.cfg, B, self._ctx)
        slots: List[Optional[_Slot]] = [None] * B
        cur = np.zeros(B, np.int32)
        pos = np.zeros(B, np.int32)
        key = jax.random.PRNGKey(0)
        while self._pending or any(s is not None for s in slots):
            free = [j for j in range(B) if slots[j] is None]
            # Refill round: continuous mode refills any free slot the
            # moment one exists; wave mode only once the whole wave
            # drained.  Either way the round fills greedily.
            if free and self._pending and (self.scheduler == "continuous"
                                           or len(free) == B):
                while free and self._pending:
                    state = self._refill_once(free, slots, state, cur, pos)
            live = [j for j in range(B) if slots[j] is not None]
            if not live:
                continue
            # One decode step for the whole batch; free slots carry
            # garbage rows that the next refill replaces wholesale.
            self._step += 1
            key, sub = jax.random.split(key)
            nxt, _, state = self._decode(
                self.params, jnp.asarray(cur)[:, None], jnp.asarray(pos),
                state, sub)
            nxt = np.asarray(nxt)
            for j in live:
                pos[j] += 1
                cur[j] = nxt[j]
                self._push_token(slots, j, int(nxt[j]))

    def _refill_once(self, free, slots, state, cur, pos):
        """Admit up to ``len(free)`` requests from ONE (group, bucket)
        queue — one ragged ingress launch, one (or few) bucket-padded
        prefills — and scatter the prefilled rows into the free slots.
        Returns the updated batch state; ``free``/``slots``/``cur``/
        ``pos`` are updated in place."""
        ready = [k for k, q in self._queues.items() if q]
        if not ready:
            self._pending = 0      # defensive: counter out of sync
            return state
        # FIFO fairness across cells: serve the oldest head ticket.
        qkey = min(ready, key=lambda k: self._queues[k][0][0])
        group, bound = qkey
        q = self._queues[qkey]
        take = []
        while q and len(take) < len(free):
            ticket, req, units = q.popleft()
            self._pending -= 1
            if self._expired(ticket, req):
                continue
            take.append((ticket, req, units))
        if not q:
            del self._queues[qkey]
        if not take:
            return state
        admitted = self._ingress_chunk(group, bound, take)
        # Deadline re-check: ingress (retries, host fallback) can be the
        # slow path; an entry that expired during it must not take a slot.
        admitted = [e for e in admitted if not self._expired(e[0], e[1])]
        if not admitted:
            return state
        # Group by prefill bucket of the ACTUAL token length (replace-
        # sanitization and unit->UTF-8 expansion can cross input-bucket
        # bounds), prefill each group padded to its bound, and merge the
        # prefilled rows into the free slots.
        by_bucket: Dict[int, list] = {}
        for entry in admitted:
            by_bucket.setdefault(self._bound(len(entry[2])), []).append(entry)
        for pb in sorted(by_bucket):
            grp = by_bucket[pb]
            toks = np.zeros((self.max_batch, pb), np.int32)
            toks[:, 0] = BOS_ID          # dummy rows: one BOS token
            lens = np.ones(self.max_batch, np.int32)
            for r, (_t, _req, ids, _off, _san) in enumerate(grp):
                toks[r, : len(ids)] = ids
                lens[r] = len(ids)
            last_logits, pstate = self._prefill_call(toks, lens)
            first = np.asarray(jnp.argmax(last_logits, -1)).astype(np.int32)
            slot_idx = [free.pop(0) for _ in grp]
            state = self._merge_rows(state, pstate, slot_idx)
            wall = self._clock()
            for r, (ticket, req, ids, off, sanitized) in enumerate(grp):
                j = slot_idx[r]
                slots[j] = _Slot(ticket=ticket, req=req, error_offset=off,
                                 sanitized=sanitized,
                                 budget=max(1, min(req.max_new,
                                                   self.max_new)))
                cur[j] = first[r]
                pos[j] = lens[r]
                self.events.append(("admit", ticket, j, self._step, wall))
                # The prefill's argmax is the first generated token; a
                # 1-token budget (or an immediate EOS) finishes here,
                # before any decode step.
                self._push_token(slots, j, int(first[r]))
        return state

    def _expired(self, ticket: int, req: Request) -> bool:
        dl = self._deadlines.get(ticket)
        if dl is None or self._clock() < dl:
            return False
        self.counters["deadline"] += 1
        self._settle(ticket, Result(
            ok=False, code=REJECTED_DEADLINE,
            error=f"deadline of {req.deadline_s:g}s expired before decode"))
        self.events.append(("reject", ticket, -1, self._step, self._clock()))
        return True

    def _prefill_call(self, toks: np.ndarray, lens: np.ndarray):
        """Bucket-padded prefill into a FRESH full-batch scratch state
        (one compiled cell per bucket bound — the geometry is always
        ``(max_batch, bound)``)."""
        fn = self._cell(
            ("prefill", toks.shape[1]),
            lambda: jax.jit(serve_step.make_prefill(self.model, self.family)))
        scratch = kvcache.init_state(self.model, self.cfg, self.max_batch,
                                     self._ctx)
        return fn(self.params, jnp.asarray(toks), jnp.asarray(lens), scratch)

    def _merge_rows(self, state, pstate, slot_idx):
        """Scatter prefilled rows ``0..k-1`` of ``pstate`` into batch
        rows ``slot_idx`` of the live state.  Every state leaf carries
        the batch on axis 1 (``(stack, batch, ...)``), and rows are
        independent (per-row cursors/positions), so full-row replacement
        is exact — the refilled slot inherits nothing."""
        k = len(slot_idx)
        # Jitted per row count: the eager per-leaf ``.at[].set`` dispatch
        # costs more than a prefill, and refills are the continuous
        # scheduler's hot path.
        fn = self._cell(("merge", k), lambda: jax.jit(
            lambda big, small, sl: jax.tree.map(
                lambda b, s: b.at[:, sl].set(s[:, :k]), big, small)))
        return fn(state, pstate, jnp.asarray(np.asarray(slot_idx, np.int32)))

    def _push_token(self, slots, j: int, token: int):
        """Record one generated token for slot ``j``; finish the slot on
        EOS or budget exhaustion (egress + settle + free)."""
        s = slots[j]
        s.tokens.append(token)
        if token == EOS_ID or len(s.tokens) >= s.budget:
            self._finish_slot(slots, j)

    def _finish_slot(self, slots, j: int):
        s = slots[j]
        gen = np.asarray(s.tokens, np.int64)
        gen = gen[(gen >= 0) & (gen != EOS_ID)]
        # Per-slot poison isolation on egress: one request with a bad
        # out_encoding (or an egress-transcode failure) must not throw
        # away its batch-mates' finished generations.
        try:
            wire = self._egress(gen, s.req.out_encoding)
        except Exception as e:
            self._settle(s.ticket, Result(
                ok=False, code=FAILED_TRANSCODE,
                error=f"egress transcode failed: {e}",
                error_offset=s.error_offset, sanitized_prompt=s.sanitized))
        else:
            self._settle(s.ticket, Result(
                ok=True, text_bytes=wire,
                error_offset=s.error_offset, sanitized_prompt=s.sanitized))
        self.events.append(("finish", s.ticket, j, self._step,
                            self._clock()))
        slots[j] = None

    # ------------------------------------------------------------------
    # Packed chunk ingress (one ragged launch per refill chunk).

    def _ingress_chunk(self, group, bound: int, take):
        """Validate/transcode one same-bucket chunk of ``(ticket, req,
        units)``; rejections settle here, admitted entries return as
        ``(ticket, req, ids, error_offset, sanitized)``."""
        if group == "utf-8":
            return self._ingress_utf8_chunk(bound, take)
        encoding, policy = group
        return self._ingress_unit_chunk(encoding, policy, bound, take)

    def _doc_tiles(self, bound: int) -> int:
        """Tiles per packed ingress slot for a bucket bound."""
        return max(1, -(-bound // packing.TILE))

    def _ingress_utf8_chunk(self, bound: int, take):
        """ONE ragged counting-scan launch for the chunk: fused
        validation + per-document error location, no write pass — clean
        prompts (the common case) pay one packed read per chunk instead
        of one kernel dispatch per request."""
        dt = self._doc_tiles(bound)
        if self._ingress_mesh is not None:
            # Sharded fan-out: the wave's packed chunk splits across the
            # ingress mesh, one counting launch per shard (the shard_map
            # executable caches inside repro.core.shard).
            from repro.core import shard as shard_mod

            def _scan():
                faults.fire(faults.KERNEL_RAGGED_SCAN)
                pk = packing.pack_documents(
                    [u for _, _, u in take], dtype=np.uint8, doc_tiles=dt,
                    pad_to_docs=self.max_batch)
                return shard_mod.scan_ragged_sharded(
                    pk.data, pk.offsets, pk.lengths, src_format="utf8",
                    dst_format="utf16", mesh=self._ingress_mesh)
        else:
            cell = self._cell(
                ("scan_utf8", dt),
                lambda: jax.jit(lambda d, o, l: tc.ragged_scan(
                    d, o, l, src_format="utf8", dst_format="utf16")))

            def _scan():
                # The chaos hook fires HERE, per call: the jitted cell
                # body below only reaches the kernel wrapper's own hook
                # while tracing, and cached executables skip it entirely.
                faults.fire(faults.KERNEL_RAGGED_SCAN)
                pk = packing.pack_documents(
                    [u for _, _, u in take], dtype=np.uint8, doc_tiles=dt,
                    pad_to_docs=self.max_batch)
                return cell(pk.data, pk.offsets, pk.lengths)

        br, mode = self._breaker_route("utf-8")
        if mode == "skip":
            # Breaker open: the device path is known-down, so the chunk
            # routes straight to the host fallback — no launch, no
            # retry storm.
            return self._host_fallback_utf8(take)
        try:
            _counts, statuses = (self._probe_launch(_scan)
                                 if mode == "probe"
                                 else self._launch_with_retry(_scan))
        except Exception as e:
            if runtime.refused(e):
                raise
            # Device path down for this chunk after retries (or the
            # half-open probe failed): feed the breaker and degrade
            # per-document to the host ``codecs`` path so clean prompts
            # still serve and poison ones get typed errors.
            self._breaker_record("utf-8", br, ok=False)
            return self._host_fallback_utf8(take)
        self._breaker_record("utf-8", br, ok=True)
        statuses = np.asarray(statuses)
        admitted = []
        for k, (ticket, req, raw) in enumerate(take):
            off = int(statuses[k])
            if off < 0:
                ids = np.concatenate(
                    [[BOS_ID], raw.astype(np.int32) + N_SPECIAL])
                admitted.append((ticket, req, ids, -1, b""))
            elif req.errors != "replace":
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error=f"invalid UTF-8 prompt at byte {off}",
                    error_offset=off))
                self.events.append(("reject", ticket, -1, self._step,
                                    self._clock()))
            else:
                entry = self._sanitize_utf8(ticket, req, raw, off)
                if isinstance(entry, Result):
                    self._settle(ticket, entry)
                    self.events.append(("reject", ticket, -1, self._step,
                                        self._clock()))
                else:
                    admitted.append(entry)
        return admitted

    def _host_fallback_utf8(self, take):
        """Graceful degradation: validate/sanitize each UTF-8 prompt with
        CPython's codec machinery (bit-compatible semantics — the device
        kernels are pinned against it by the differential fuzz).  Slow
        path, but one flaky launch must not fail a whole packed chunk."""
        admitted = []
        for ticket, req, raw in take:
            self.counters["fallback"] += 1
            data = raw.tobytes()
            try:
                data.decode("utf-8")
                off = -1
            except UnicodeDecodeError as e:
                off = e.start
            if off < 0:
                ids = np.concatenate(
                    [[BOS_ID], raw.astype(np.int32) + N_SPECIAL])
                admitted.append((ticket, req, ids, -1, b""))
            elif req.errors != "replace":
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error=f"invalid UTF-8 prompt at byte {off}",
                    error_offset=off))
            else:
                clean = np.frombuffer(
                    data.decode("utf-8", "replace").encode("utf-8"),
                    np.uint8)
                if len(clean) == 0 or len(clean) > self.max_prompt - 1:
                    self._settle(ticket, Result(
                        ok=False, code=REJECTED_INVALID,
                        error="empty or oversize prompt after replacement",
                        error_offset=off))
                else:
                    ids = np.concatenate(
                        [[BOS_ID], clean.astype(np.int32) + N_SPECIAL])
                    admitted.append((ticket, req, ids, off, bytes(clean)))
        return admitted

    def _sanitize_utf8(self, ticket, req, raw, off):
        """Dirty prompt under replace: sanitize via a single-pass
        replace-transcode to UTF-16 (the default strategy), then encode
        the now-valid units back to UTF-8 for the byte tokenizer (dirty
        prompts are the rare case, so this stays per-request)."""
        buf = np.zeros(self.max_prompt, np.uint8)
        buf[: len(raw)] = raw

        def _device():
            u16, cu, _status = tc.transcode(
                jnp.asarray(buf), "utf16", src_format="utf8",
                n_valid=len(raw), errors="replace")
            # The units are valid by construction — skip the
            # re-validation scan on the way back to bytes.
            b8, cb, _ = tc.transcode(u16, "utf8", src_format="utf16",
                                     n_valid=cu, validate=False)
            return np.asarray(b8)[: int(cb)].astype(np.uint8)

        try:
            clean = self._launch_with_retry(_device)
        except Exception as e:
            if runtime.refused(e):
                raise
            self.counters["fallback"] += 1
            clean = np.frombuffer(
                raw.tobytes().decode("utf-8", "replace").encode("utf-8"),
                np.uint8)
        if len(clean) == 0 or len(clean) > self.max_prompt - 1:
            return Result(
                ok=False, code=REJECTED_INVALID,
                error="empty or oversize prompt after replacement",
                error_offset=off)
        ids = np.concatenate([[BOS_ID], clean.astype(np.int32) + N_SPECIAL])
        return (ticket, req, ids, off, bytes(clean))

    def _ingress_unit_chunk(self, encoding, policy, bound: int, take):
        """ONE ragged single-pass launch for a chunk of unit-encoded
        prompts (the (encoding, ``errors=``) pair is the compile cell):
        the launch validates + locates per document through that matrix
        cell AND produces the UTF-8 the byte tokenizer consumes, off one
        decode of the packed chunk.  Covers utf-16-le, utf-32-le and
        latin-1 ingress (latin-1 can never reject — every byte is a
        code point)."""
        width, np_dtype, src, noun = self._UNIT_INGRESS[encoding]
        dt = self._doc_tiles(bound)
        if self._ingress_mesh is not None:
            # Sharded fan-out, one onepass launch per shard; the gather
            # is bit-identical to the single-device cell, so everything
            # below consumes the result unchanged.
            def _launch():
                faults.fire(faults.KERNEL_RAGGED)   # per-call chaos hook
                pk = packing.pack_documents(
                    [u for _, _, u in take], dtype=np_dtype, doc_tiles=dt,
                    pad_to_docs=self.max_batch)
                return tc.ragged_transcode(
                    pk.data, pk.offsets, pk.lengths, src_format=src,
                    dst_format="utf8", errors=policy, strategy="sharded",
                    shard_mesh=self._ingress_mesh)
        else:
            cell = self._cell(
                ("unit", src, policy, dt),
                lambda: jax.jit(lambda d, o, l: tc.ragged_transcode(
                    d, o, l, src_format=src, dst_format="utf8",
                    errors=policy)))

            def _launch():
                faults.fire(faults.KERNEL_RAGGED)   # per-call chaos hook
                pk = packing.pack_documents(
                    [u for _, _, u in take], dtype=np_dtype, doc_tiles=dt,
                    pad_to_docs=self.max_batch)
                return cell(pk.data, pk.offsets, pk.lengths)

        group = (encoding, policy)
        br, mode = self._breaker_route(group)
        if mode == "skip":
            return self._host_fallback_unit(encoding, policy, take)
        try:
            res = (self._probe_launch(_launch) if mode == "probe"
                   else self._launch_with_retry(_launch))
        except Exception as e:
            if runtime.refused(e):
                raise
            self._breaker_record(group, br, ok=False)
            return self._host_fallback_unit(encoding, policy, take)
        self._breaker_record(group, br, ok=True)
        outs = packing.unpack_results(res.buffer, res.offsets, res.counts)
        statuses = np.asarray(res.statuses)
        admitted = []
        for k, (ticket, req, units) in enumerate(take):
            off = int(statuses[k])
            if policy != "replace" and off >= 0:
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error=f"invalid {encoding} prompt at {noun} {off}",
                    error_offset=off))
                self.events.append(("reject", ticket, -1, self._step,
                                    self._clock()))
                continue
            b8 = np.asarray(outs[k]).astype(np.uint8)
            if len(b8) == 0 or len(b8) > self.max_prompt - 1:
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error="empty or oversize prompt"))
                self.events.append(("reject", ticket, -1, self._step,
                                    self._clock()))
                continue
            ids = np.concatenate([[BOS_ID], b8.astype(np.int32) + N_SPECIAL])
            sanitized = bytes(b8) if (policy == "replace" and off >= 0) \
                else b""
            admitted.append((ticket, req, ids, off, sanitized))
        return admitted

    def _host_fallback_unit(self, encoding, policy, take):
        """Host ``codecs`` degradation for a unit-encoded chunk whose
        ragged launch failed after retries (mirrors the device cell's
        CPython-pinned semantics, including the first-error offset in
        source units)."""
        width, _np_dtype, _src, noun = self._UNIT_INGRESS[encoding]
        admitted = []
        for ticket, req, units in take:
            self.counters["fallback"] += 1
            wire = (units.astype(np.uint8).tobytes() if width == 1
                    else units.astype(f"<u{width}").tobytes())
            try:
                wire.decode(encoding)
                off = -1
            except UnicodeDecodeError as e:
                off = e.start // width
            if policy != "replace" and off >= 0:
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error=f"invalid {encoding} prompt at {noun} {off}",
                    error_offset=off))
                continue
            text = wire.decode(encoding, "replace" if off >= 0 else "strict")
            b8 = np.frombuffer(text.encode("utf-8"), np.uint8)
            if len(b8) == 0 or len(b8) > self.max_prompt - 1:
                self._settle(ticket, Result(
                    ok=False, code=REJECTED_INVALID,
                    error="empty or oversize prompt"))
                continue
            ids = np.concatenate([[BOS_ID], b8.astype(np.int32) + N_SPECIAL])
            sanitized = bytes(b8) if (policy == "replace" and off >= 0) \
                else b""
            admitted.append((ticket, req, ids, off, sanitized))
        return admitted

    # ------------------------------------------------------------------
    # Egress.

    def _egress(self, token_ids: np.ndarray, encoding: str) -> bytes:
        byte_vals = token_ids - N_SPECIAL
        byte_vals = byte_vals[(byte_vals >= 0) & (byte_vals < 256)]
        if encoding == "utf-8" or len(byte_vals) == 0:
            return bytes(byte_vals.astype(np.uint8))
        b = jnp.asarray(byte_vals.astype(np.int32))
        # Pinned to the eager pure-jnp strategy: egress buffers have a
        # new length per response, and the onepass Pallas pipeline would
        # recompile per distinct shape.  Wire bytes come from the
        # explicit-LE jnp helpers, never a host ``.view()``.
        if encoding == "utf-16-le":
            out, count, _status = tc.transcode(
                b, "utf16", src_format="utf8", n_valid=len(byte_vals),
                strategy="blockparallel")
            wire = tc.units_to_utf16le_bytes(out[: int(count)])
        elif encoding == "utf-32-le":
            out, count, _status = tc.transcode(
                b, "utf32", src_format="utf8", n_valid=len(byte_vals),
                strategy="blockparallel")
            wire = tc.cps_to_utf32le_bytes(out[: int(count)])
        elif encoding == "latin-1":
            # A byte-LM can emit code points above U+00FF: substitute
            # CPython-style ('?') rather than fail the response.
            out, count, _status = tc.transcode(
                b, "latin1", src_format="utf8", n_valid=len(byte_vals),
                strategy="blockparallel", errors="replace")
            wire = out[: int(count)]
        else:
            raise ValueError(f"unknown out_encoding: {encoding}")
        return bytes(np.asarray(wire).astype(np.uint8))
