"""Slot-pool continuous-batching scheduler.

The serving core: a fixed number of decode *slots* sharing ONE
`KVDecoder` batch.  Every engine tick runs one jitted decode step over
all slots (`KVDecoder.step_slots` — a single XLA program regardless of
which slots are live); a request that finishes (eos / token budget /
cache capacity / deadline) frees its slot **mid-flight**, and queued
requests are admitted into free slots at the next iteration without
recompiling anything: admission is a bucketed-length prefill
(`prefill_padded`, one program per bucket, warmed after the first
request of each bucket) plus one traced-slot-index cache write
(`adopt_row`).  The decode jits live in the same process as the PR-2
program cache, so a warm server performs ZERO traces per tick —
asserted via `executor_compile_total{kind=decode_*}` by
tests/test_serving.py.

Host/device split follows the training hot loop's rule: per-slot
``start``/``cursor`` windows, queued requests, and sampling live on the
HOST (numpy); no tick reads device state except the one (B, V) logits
fetch that sampling needs anyway.  Per-request sampling params
(temperature / top_k / seed) are host-side, so heterogeneous requests
co-batch freely.

A *block decoder* (``decoder.block_length`` = n > 1: ``models/sdar.py``,
generation by diffusion over blocks) does not yield one token a slot a
tick.  A slot holds a block under denoising -- ``n`` ids, some of them
the mask id -- and a tick is a *forward* that hands the backend ``(B,
n)`` ids and gets back, per row, the most probable token and its
probability (reduced on the device; no logits come to the host).  Per
slot a forward is either a DENOISE forward, which fixes the ``k``
masked positions of highest probability by the request's schedule
(``denoising_steps``: ``k = n // steps``, one more in the first ``n %
steps`` forwards) and so yields 1..n tokens, or, once the block is
whole and the request goes on, the COMMIT forward, which yields none:
it writes the block's final K/V and moves the slot's cursor by ``n``
(``PagedSlots.step(..., commit)``).  Slots in either phase share one
forward.  A block's tokens are delivered, and stamped, when the block
is whole; a request ends only there (its last block needs no commit),
``eos`` is looked for there, and ``ttft`` is the first block's.  An
admission prefills the prompt's whole blocks and samples nothing; the
prompt's remainder rides, fixed, in the first block.  Greedy only: a
temperature is refused at ``submit``.

Backpressure is explicit: the admission queue is bounded
(``MXTPU_SERVE_QUEUE``); a full queue raises
:class:`AdmissionQueueFull`, which the HTTP layer maps to 429.
Deadlines (``MXTPU_SERVE_DEADLINE_MS`` default, per-request override)
are enforced both while queued and mid-generation.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from ..telemetry import tracing as _tracing
from . import paged_kv as _paged_kv

__all__ = ["Request", "SlotScheduler", "AdmissionQueueFull"]

# --- serving metric families (docs/telemetry.md, serving section) ----------
_TM_REQS = _tm.counter(
    "serve_requests_total",
    "requests by terminal outcome: ok (completed), rejected (admission "
    "queue full), timeout (deadline while queued or generating), error, "
    "shutdown", labels=("outcome",))
_TM_TOKENS = _tm.counter(
    "serve_tokens_total", "tokens generated and delivered to requests")
_TM_QUEUE = _tm.gauge(
    "serve_queue_depth", "requests waiting in the bounded admission queue")
_TM_OCCUPANCY = _tm.gauge(
    "serve_slot_occupancy", "decode slots currently running a request")
_TM_TTFT = _tm.histogram(
    "serve_ttft_seconds",
    "time-to-first-token: request ARRIVAL (HTTP receipt, before "
    "parse/queue — the server passes its receipt stamp into Request) "
    "to the first sampled token: queue wait + admission prefill")
_TM_QWAIT = _tm.histogram(
    "serve_queue_wait_seconds",
    "time a request spent in the bounded admission queue before a "
    "slot freed up — the queueing component of serve_ttft_seconds, "
    "reported separately so saturation (queue wait) and compute "
    "(prefill) are tellable apart at the replica")
_TM_REQ_SEC = _tm.histogram(
    "serve_request_seconds", "request latency: arrival to terminal outcome")
_TM_REUSE = _tm.counter(
    "serve_slot_reuse_total",
    "admissions into a slot that already served an earlier request — "
    "continuous batching in action (0 means every request got a cold slot)")
_TM_TICK = _tm.histogram(
    "serve_tick_seconds",
    "one engine tick: a fused decode step over all slots + host sampling")


class SchedulerDraining(MXNetError):
    """Submitted while draining (POST /admin/drain): the server is
    finishing in-flight work before a restart — resubmit elsewhere."""


class AdmissionQueueFull(MXNetError):
    """The bounded admission queue is full — shed load (HTTP 429)."""


def _ms(seconds):
    return None if seconds is None else round(seconds * 1000.0, 3)


def _env_int(name, default):
    v = os.environ.get(name)
    return default if not v else int(v)


class Request:
    """One generation request and its (thread-safe) result slot.

    ``wait(timeout)`` blocks until a terminal outcome; ``tokens`` then
    holds everything generated (possibly partial on ``timeout``).
    """

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens=16, temperature=0.0,
                 top_k=None, eos_id=None, deadline_ms=None, seed=0,
                 arrival=None, trace=None, parent=None, sampled=False,
                 denoising_steps=None):
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise MXNetError(
                f"prompt must be a non-empty 1-D token-id sequence, got "
                f"shape {prompt.shape}")
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        temperature = float(temperature)
        if not np.isfinite(temperature) or temperature < 0:
            raise MXNetError(
                f"temperature must be a finite number >= 0, got "
                f"{temperature!r}")
        if top_k is not None:
            top_k = int(top_k)
            if top_k < 1:
                raise MXNetError(f"top_k must be >= 1, got {top_k}")
        if deadline_ms is not None and not (
                np.isfinite(deadline_ms) and deadline_ms >= 0):
            raise MXNetError(
                f"deadline_ms must be a finite number >= 0, got "
                f"{deadline_ms!r}")
        seed = int(seed)
        if not 0 <= seed < 2 ** 32:
            raise MXNetError(f"seed must be in [0, 2**32), got {seed}")
        if denoising_steps is not None and int(denoising_steps) < 1:
            raise MXNetError(
                f"denoising_steps must be >= 1, got {denoising_steps}")
        self.id = next(Request._ids)
        self.prompt = prompt.astype(np.int64)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        # TTFT origin (ISSUE 16): the server stamps monotonic receipt
        # time BEFORE reading/parsing the body and passes it here, so
        # serve_ttft_seconds includes the full queue wait
        self.arrival = (time.monotonic() if arrival is None
                        else float(arrival))
        self.deadline = (self.arrival + deadline_ms / 1000.0
                         if deadline_ms else None)
        # trace context (telemetry/tracing.py): the W3C traceparent the
        # router minted; spans are recorded only when `sampled` rode in
        # on the flags byte AND tracing is on in this process
        self.trace = trace
        self.parent = parent
        self.sampled = bool(sampled)
        self.queue_wait = None
        self.tokens = []
        # one time.monotonic() stamp per token, on arrival's clock: the
        # reply's token_ms and the terminal span's gaps_ms come from here
        self.token_times = []
        # a block decoder's: forwards a block of this request takes to
        # unmask (None: the decoder's default), and per token the
        # ordinal of the forward of its block that fixed it
        self.denoising_steps = (None if denoising_steps is None
                                else int(denoising_steps))
        self.unmask_step = None
        self.outcome = None   # ok | timeout | error | shutdown
        self.error = None
        self.ttft = None
        self._rng = np.random.RandomState(seed)
        self._event = threading.Event()

    def wait(self, timeout=None):
        """Block until the request reaches a terminal outcome (or the
        wait times out — ``outcome`` is then still None)."""
        self._event.wait(timeout)
        return self

    @property
    def done(self):
        return self._event.is_set()


class _WholePrompt:
    """The contiguous pool's record of an admission (the paged pool's
    is :class:`~mxnet_tpu.serving.paged_kv._Admission`): one program,
    ``pending`` until it has run, never in chunks."""
    chunked = False

    def __init__(self, slot, prompt):
        self.slot, self.prompt, self.pending = slot, prompt, True


class _ContiguousSlots:
    """The PR-6 contiguous slot pool behind the backend interface the
    scheduler drives: one ``(L, slots, H, max_len, dh)`` cache pair,
    left-padded bucketed prefill + ``adopt_row`` admission, per-slot
    ``[start, cursor]`` windows.  The paged twin is
    :class:`~mxnet_tpu.serving.paged_kv.PagedSlots`."""

    paged = False

    def __init__(self, decoder, num_slots, prefill_buckets):
        self.decoder = decoder
        self.num_slots = num_slots
        self.prefill_buckets = prefill_buckets
        self.cache = decoder.init_slot_state(num_slots)
        self.start = np.zeros(num_slots, np.int32)
        self.cursor = np.zeros(num_slots, np.int32)

    def stats(self):
        return None

    @property
    def max_prompt(self):
        return self.prefill_buckets[-1]

    def begin_admit(self, slot, prompt, trace=None):
        """The first half of an admission, as the paged pool has it:
        here nothing is looked up or allocated, and the one program of
        :meth:`admit_chunk` is the whole of it.  ``trace`` is accepted
        for parity with the paged pool (which records kv_admit/kv_evict
        spans); the contiguous pool has no per-admit KV events to
        attribute."""
        return _WholePrompt(slot, np.asarray(prompt))

    def admit_chunk(self, adm):
        """Bucketed left-padded prefill + one traced-slot cache write;
        returns the next-token logits row of the last prompt token."""
        slot, prompt = adm.slot, adm.prompt
        plen = int(prompt.size)
        bucket = next(b for b in self.prefill_buckets if b >= plen)
        padded = np.zeros((1, bucket), np.int64)
        padded[0, bucket - plen:] = prompt
        row, logits = self.decoder.prefill_padded(padded, [plen])
        self.cache = self.decoder.adopt_row(self.cache, row, slot)
        self.start[slot] = bucket - plen
        self.cursor[slot] = bucket
        adm.pending = False
        return logits[0, -1]

    def admit(self, slot, prompt, trace=None):
        """A whole admission at once."""
        return self.admit_chunk(self.begin_admit(slot, prompt, trace))

    def step(self, tokens, occupied):
        """ONE jitted decode step over the whole pool; advances the
        occupied rows' windows.  Never starves (each slot owns its full
        max_len row) — the empty second return keeps the interface."""
        tokens = np.asarray(tokens).copy()
        start = self.start.copy()
        cursor = self.cursor.copy()
        free = ~occupied
        # free rows ride along; pin their write to position 0 —
        # adopt_row overwrites the whole row on admission
        tokens[free] = 0
        start[free] = 0
        cursor[free] = 0
        self.cache, logits = self.decoder.step_slots(
            self.cache, tokens, start, cursor)
        self.cursor[occupied] += 1
        return logits, []

    def exhausted(self, slot):
        return self.cursor[slot] >= self.decoder.max_len

    def release(self, slot):
        self.start[slot] = 0
        self.cursor[slot] = 0


class SlotScheduler:
    """Continuous batching over one :class:`~mxnet_tpu.models.decode.
    KVDecoder`.

    ``prefill_buckets``: padded prompt lengths the admission prefill
    compiles for (default: powers of two from 8 up to the decoder's
    ``max_len``).  A request's prompt is left-padded to the smallest
    bucket that fits, so the number of prefill programs is
    O(log max_len) and a warm server admits without tracing.

    ``paged``/``kv_block``/``num_pages``/``prefix_cache`` select the
    paged KV backend (`serving/paged_kv.py`): block-table indirection
    over a shared page pool with prompt-prefix reuse.  Default follows
    ``MXTPU_KV_BLOCK`` (0/unset = contiguous).  ``paged_kernel``
    overrides ``MXTPU_PAGED_KERNEL`` — the paged step's attention
    lowering (gather / Pallas page-walk kernel; ISSUE
    18), resolved once at construction through ``mxnet_tpu.autotune``.
    """

    def __init__(self, decoder, num_slots=None, queue_size=None,
                 default_deadline_ms=None, prefill_buckets=None,
                 idle_wait=0.05, paged=None, kv_block=None,
                 num_pages=None, prefix_cache=None, paged_kernel=None):
        self.decoder = decoder
        # `is not None` (not truthiness): an explicit 0 must reach the
        # guards below, not silently become the env/default value
        self.num_slots = int(
            num_slots if num_slots is not None
            else _env_int("MXTPU_SERVE_SLOTS", 4))
        self.queue_size = int(
            queue_size if queue_size is not None
            else _env_int("MXTPU_SERVE_QUEUE", 16))
        self.default_deadline_ms = (
            default_deadline_ms
            if default_deadline_ms is not None
            else _env_int("MXTPU_SERVE_DEADLINE_MS", 30000))
        if self.num_slots < 1:
            raise MXNetError("need at least one decode slot")
        if self.queue_size < 0:
            raise MXNetError("queue_size must be >= 0 (0 disables "
                             "queueing: every submit sheds load)")
        if prefill_buckets is None:
            prefill_buckets, b = [], 8
            while b < decoder.max_len:
                prefill_buckets.append(b)
                b *= 2
            prefill_buckets.append(decoder.max_len)
        self.prefill_buckets = tuple(sorted(set(prefill_buckets)))
        if self.prefill_buckets[-1] > decoder.max_len:
            raise MXNetError(
                f"prefill bucket {self.prefill_buckets[-1]} exceeds the "
                f"decoder's max_len {decoder.max_len}")

        blk = kv_block if kv_block is not None else _paged_kv.kv_block()
        if paged is None:
            paged = blk > 0
        # a block decoder's n (module docstring); 1: a token a tick
        self._block_n = n = int(getattr(decoder, "block_length", 1))
        if n > 1 and not paged:
            raise MXNetError(
                "a block decoder is served paged: give kv_block")
        if paged:
            self.backend = _paged_kv.PagedSlots(
                decoder, self.num_slots, block=(blk or None),
                num_pages=num_pages, prefix_cache=prefix_cache,
                prefill_buckets=self.prefill_buckets,
                kernel=paged_kernel)
        else:
            self.backend = _ContiguousSlots(
                decoder, self.num_slots, self.prefill_buckets)
        # the block each slot holds: its ids, which of them are fixed
        # (all: it is whole and its next forward is its commit), the
        # forward that fixed each (-1: the prompt's), and the ordinal
        # of its next denoise forward
        self._blk_ids = np.zeros((self.num_slots, n), np.int64)
        self._blk_fixed = np.zeros((self.num_slots, n), bool)
        self._blk_at = np.full((self.num_slots, n), -1, np.int64)
        self._blk_step = np.zeros(self.num_slots, np.int64)
        self.slots = [None] * self.num_slots
        self._next_tok = np.zeros(self.num_slots, np.int64)
        self._slot_used = [False] * self.num_slots
        self._queue = deque()
        # (slot, request, the backend's admission) of a prompt that goes
        # in chunks and is not whole yet; the engine thread's alone
        self._admitting = None
        self._cond = threading.Condition()
        self._stop = False
        self._draining = False
        self._idle_wait = float(idle_wait)
        # rolled-up engine stats (bench + /healthz): mean slot occupancy
        # = slot_ticks / ticks.  A tick is a forward, a slot-tick an
        # occupied slot in one; the last three move only under a block
        # decoder: slot-forwards that were commits, positions unmasked,
        # blocks whose cursor moved
        self.stats = {"ticks": 0, "slot_ticks": 0, "admitted": 0,
                      "completed": 0, "commit_forwards": 0,
                      "tokens_unmasked": 0, "blocks_committed": 0}
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name="mxtpu-serve-engine-%d" % id(self))
        self._thread.start()

    # ------------------------------------------------------------ client API
    def submit(self, prompt, **kwargs):
        """Enqueue a generation request; returns the :class:`Request`.
        Raises :class:`AdmissionQueueFull` when the bounded queue is full
        and :class:`MXNetError` for requests that can never be served
        (prompt longer than ``backend.max_prompt``)."""
        kwargs.setdefault("deadline_ms", self.default_deadline_ms or None)
        req = Request(prompt, **kwargs)
        if self._block_n > 1:
            # the head hands over a top-1 token, not a distribution
            if req.temperature > 0:
                _TM_REQS.inc(outcome="rejected")
                raise MXNetError(
                    "this decoder's head returns its most probable token "
                    "alone: temperature must be 0, got "
                    f"{req.temperature}")
            if req.denoising_steps is None:
                req.denoising_steps = int(self.decoder.denoising_steps)
            req.denoising_steps = min(req.denoising_steps, self._block_n)
            req.unmask_step = []
        elif req.denoising_steps is not None:
            _TM_REQS.inc(outcome="rejected")
            raise MXNetError(
                "denoising_steps is a block decoder's; this decoder "
                "yields one token a forward")
        vocab = getattr(self.decoder, "vocab", None)
        if req.top_k is not None and vocab and req.top_k > vocab:
            _TM_REQS.inc(outcome="rejected")
            raise MXNetError(
                f"top_k {req.top_k} exceeds the vocab size {vocab}")
        if req.prompt.size > self.backend.max_prompt:
            _TM_REQS.inc(outcome="rejected")
            raise MXNetError(
                f"prompt length {req.prompt.size} exceeds what an "
                f"admission takes, {self.backend.max_prompt} (the largest "
                "prefill bucket, or the cache window where a prompt may "
                "go in chunks)")
        with self._cond:
            if self._stop:
                raise MXNetError("scheduler is shut down")
            if self._draining:
                _TM_REQS.inc(outcome="rejected")
                raise SchedulerDraining(
                    "scheduler is draining: not admitting new requests "
                    "(in-flight and queued requests will finish)")
            if len(self._queue) >= self.queue_size:
                _TM_REQS.inc(outcome="rejected")
                raise AdmissionQueueFull(
                    f"admission queue full ({self.queue_size} waiting)")
            self._queue.append(req)
            _TM_QUEUE.set(len(self._queue))
            self._cond.notify()
        return req

    def generate(self, prompt, timeout=None, **kwargs):
        """submit() + wait(): returns the finished :class:`Request`."""
        req = self.submit(prompt, **kwargs)
        limit = timeout
        if limit is None and req.deadline is not None:
            limit = max(req.deadline - time.monotonic(), 0.0) + 5.0
        return req.wait(limit)

    # ------------------------------------------------------------- draining
    def drain(self):
        """Stop admitting new requests; queued and in-flight requests
        finish normally (the rolling-restart half of the survival
        layer: an orchestrator drains a replica, waits for
        :attr:`drained`, then restarts it under live traffic).
        Idempotent; ``submit`` raises :class:`SchedulerDraining` until
        shutdown or :meth:`undrain`."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()

    def undrain(self):
        """Re-open admission (a drain that was cancelled)."""
        with self._cond:
            self._draining = False
            self._cond.notify_all()

    @property
    def draining(self):
        return self._draining

    @property
    def drained(self):
        """True when a draining scheduler has no queued or in-flight
        work left — safe to restart."""
        with self._cond:
            return (self._draining and not self._queue
                    and self._admitting is None
                    and all(r is None for r in self.slots))

    @property
    def paged(self):
        return self.backend.paged

    def paged_stats(self):
        """Page-pool occupancy for ``/healthz`` (None when running the
        contiguous backend): {block, pages_total, pages_free,
        prefix_pages}."""
        return self.backend.stats()

    @property
    def occupied(self):
        return sum(1 for r in self.slots if r is not None)

    @property
    def queue_depth(self):
        with self._cond:
            return len(self._queue)

    def close(self, timeout=10.0):
        """Stop the engine thread; queued and in-flight requests finish
        with outcome ``shutdown``."""
        with self._cond:
            if self._stop:
                return
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout)
        with self._cond:
            queued = list(self._queue)
            self._queue.clear()
        _TM_QUEUE.set(0)
        # the engine never touches the queue after _stop, so queued
        # requests are safe to terminate here either way
        for req in queued:
            self._terminal(req, "shutdown")
        if self._thread.is_alive():
            # engine wedged past the join timeout (e.g. blocked inside a
            # jitted call): leave the slots to it — it may still finish
            # them, and _terminal is idempotent if it does so later; the
            # in-flight clients' own wait() deadlines bound their hang
            return
        for req in self.slots:
            if req is not None:
                self._terminal(req, "shutdown")
        if self._admitting is not None:
            self._terminal(self._admitting[1], "shutdown")
        # race-ok: reached only after _thread.join() proved the engine
        # thread dead (is_alive() returns above otherwise) — the join is
        # the happens-before edge static analysis can't see
        self.slots = [None] * self.num_slots
        _TM_OCCUPANCY.set(0)

    # ---------------------------------------------------------- engine loop
    def _run(self):
        while True:
            with self._cond:
                if self._nothing_to_do():
                    with _tracing.phase("engine.idle", "engine"):
                        while self._nothing_to_do():
                            self._cond.wait(self._idle_wait)
                if self._stop:
                    return
            # the engine thread must OUTLIVE any single bad request: an
            # exception anywhere in an iteration terminates the affected
            # requests with outcome `error` and the loop keeps serving —
            # a dead engine would hang every in-flight and future client
            try:
                now = time.monotonic()
                self._expire_queued(now)
                self._admit(now)
                if any(r is not None for r in self.slots):
                    self._tick()
            except Exception as exc:  # noqa: BLE001 — requests must
                #                       terminate, not hang their clients
                for i, req in enumerate(self.slots):
                    if req is not None:
                        req.error = exc
                        self._finish_slot(i, "error")

    def _nothing_to_do(self):
        """Nothing queued, no slot busy, not stopping (under _cond)."""
        return (not self._stop and not self._queue
                and self._admitting is None
                and all(r is None for r in self.slots))

    def _expire_queued(self, now):
        with self._cond:
            keep = deque()
            for req in self._queue:
                if req.deadline is not None and now > req.deadline:
                    self._terminal(req, "timeout")
                else:
                    keep.append(req)
            if len(keep) != len(self._queue):
                self._queue = keep
                _TM_QUEUE.set(len(keep))

    def _admit(self, now):
        """Move queued requests into free slots: bucketed prefill + one
        traced-slot cache write each; the first token is sampled straight
        from the prefill logits (that fetch IS the TTFT).  A prompt that
        goes in chunks (its tail is longer than the largest bucket; a
        paged backend whose decoder's prefill takes history) gets ONE
        chunk a call: the occupied slots tick between two chunks, its
        own slot is not occupied until the last, and no other request
        is admitted meanwhile."""
        if self._admitting is not None:
            free, req, adm = self._admitting
            if req.deadline is not None and now > req.deadline:
                self._admitting = None
                self.backend.release(free)
                self._terminal(req, "timeout")
            else:
                self._admit_one(free, req, adm)
        while self._admitting is None:
            free = next((i for i, r in enumerate(self.slots) if r is None),
                        None)
            if free is None:
                return
            with self._cond:
                if not self._queue:
                    return
                req = self._queue.popleft()
                _TM_QUEUE.set(len(self._queue))
            self._admit_one(free, req)

    def _to_host(self, out, program):
        """What a backend returned, as the host array(s) the sampler
        reads -- the float32 logits, or a block decoder's ``(token,
        probability)`` pair: the ONE host sync of a tick and of an
        admission.  While someone is looking it is two leaf spans:
        ``engine.wait``, the device finishing, and ``engine.fetch``,
        the conversion and the copy."""
        block = self._block_n > 1

        def arrays():
            if block:
                return tuple(np.asarray(a) for a in out)
            return np.asarray(out, np.float32)

        if not _tracing.recording():
            return arrays()
        import jax

        with _tracing.phase("engine.wait", "engine", program=program):
            jax.block_until_ready(out)
        nbytes = sum(a.nbytes for a in out) if block else 4 * out.size
        with _tracing.phase("engine.fetch", "engine", program=program,
                            bytes=nbytes):
            return arrays()

    def _prefill(self, req, adm, bucket):
        """One prefill program of ``adm`` and, for the sampler, its
        logits on the host, under one span: ``engine.prefill`` for an
        admission of one program, ``engine.prefill_chunk``, with the
        chunk's place in the prompt, for each of several.  Returns the
        span and the logits (None from a block decoder, whose admission
        fetches nothing)."""
        name, program = "engine.prefill", "prefill"
        attrs = {"request": req.id, "bucket": bucket}
        if adm.chunked:
            tokens, bucket = self.backend.next_chunk(adm)
            name, program = "engine.prefill_chunk", "chunk"
            attrs = {"request": req.id, "hist": adm.hist + adm.done,
                     "tokens": tokens, "bucket": bucket,
                     "last": adm.done + tokens == adm.tail.size}
        with _tracing.phase(name, "engine", **attrs) as pf:
            logits = None
            # nothing is pending of a block decoder's prompt that is
            # shorter than one block
            if adm.pending:
                # a backend returns a device array without waiting
                with _tracing.phase("engine.launch", "engine",
                                    program=program, bucket=bucket):
                    logits = self.backend.admit_chunk(adm)
            if self._block_n == 1:
                logits = self._to_host(logits, program)
        return pf, logits

    def _admit_one(self, free, req, adm=None):
        """One visit to a request's admission, as the span
        ``engine.admit``: for most the whole of it -- prefill, first
        sample, cache write; for a prompt that goes in chunks one
        chunk, ``adm`` being the backend's record of the chunks before
        (the last visit samples).  It fails only this request: the slot
        stays free and the engine moves on.  A block decoder's
        admission samples nothing and fetches nothing: its
        ``engine.prefill`` times the dispatch alone, and the first
        forward waits for the prefill."""
        from .. import faults as _faults

        traced = req.sampled and _tracing.trace_on()
        if adm is None:
            req.queue_wait = time.monotonic() - req.arrival
            _TM_QWAIT.observe(req.queue_wait)
            if traced:
                _tracing.record_span(
                    "queue_wait", "replica", req.trace, req.queue_wait,
                    parent=req.parent, request=req.id)
        self._admitting = None
        plen = int(req.prompt.size)
        bucket = next((b for b in self.prefill_buckets if b >= plen),
                      self.prefill_buckets[-1])
        progressed = admitted = False
        with _tracing.phase(
                "engine.admit", "engine", request=req.id, slot=free,
                prompt_len=plen, bucket=bucket,
                queue_wait_ms=_ms(req.queue_wait)) as span:
            try:
                if adm is None:
                    _faults.maybe_fail("serve_admit")
                    adm = self.backend.begin_admit(
                        free, req.prompt,
                        trace=(req.trace if traced else None))
                # the fetch belongs to the prefill, not to sampling
                pf, logits = self._prefill(req, adm, bucket)
                if adm.pending:
                    self._admitting = (free, req, adm)
                elif self._block_n == 1:
                    first = self._sample(req, logits)
            except Exception as exc:  # noqa: BLE001
                self.backend.release(free)
                req.error = exc
                self._terminal(req, "error")
            else:
                progressed = True
                admitted = self._admitting is None
            if admitted:
                if self._slot_used[free]:
                    _TM_REUSE.inc()
                self._slot_used[free] = True
                self.slots[free] = req
                self.stats["admitted"] += 1
                _TM_OCCUPANCY.set(self.occupied)
                if self._block_n > 1:
                    # the prompt's remainder, fixed, opens the first block
                    n = self._block_n
                    self._new_block(
                        free, req.prompt[req.prompt.size // n * n:])
                else:
                    self._next_tok[free] = first
                    self._deliver(req, [first], time.monotonic())
                    self._maybe_finish(free, req.token_times[-1])
        if traced and progressed and span.t1 is not None:
            # the per-request records of a router-sampled request, from
            # the phases' own stamps (traced implies they were live):
            # one ``prefill`` a program, ``admit`` when it is whole
            _tracing.record_span(
                "prefill", "replica", req.trace, pf.t1 - pf.t0,
                parent=req.parent, bucket=bucket, prompt_len=plen,
                request=req.id)
            if admitted:
                _tracing.record_span(
                    "admit", "replica", req.trace, span.t1 - span.t0,
                    parent=req.parent, slot=free, request=req.id)

    def _tick(self):
        """ONE jitted decode step over the whole pool + host sampling."""
        from .. import faults as _faults

        # SIGKILL-shaped chaos: MXTPU_FAULT_PLAN="replica_kill:
        # crash_after:n" dies mid-decode — the death the router's
        # re-route/502 paths must survive (tests/test_serving_fleet.py)
        _faults.fire("replica_kill")
        # injected slow replica (MXTPU_FAULT_PLAN="serve_slow:drop:1"):
        # park the engine thread so queue wait and TTFT genuinely
        # inflate — the SLO plane's violation paths ride this in tests
        if _faults.active() and _faults.should_drop("serve_slow"):
            time.sleep(_tm.health._fault_slow_s())
        occupied = [i for i, r in enumerate(self.slots) if r is not None]
        n = self.stats["ticks"]
        occ_mask = np.array([r is not None for r in self.slots])
        block = self._block_n > 1
        phases = {}
        if block:
            # slots whose block is whole commit it in this forward
            commit = self._blk_fixed.all(axis=1) & occ_mask
            phases = {"commit": int(commit.sum()),
                      "denoise": len(occupied) - int(commit.sum())}
        with _tracing.phase("engine.tick", "engine", tick=n,
                            occupied=len(occupied), **phases) as tick:
            # each stamp is read once: from the phase when someone is
            # looking, else here (the tick histogram needs it always)
            t0 = tick.t0 or time.perf_counter()
            # sampled decode-tick spans (ISSUE 16): every TICK_EVERY-th
            # tick records one span per sampled live request — pure host
            # dict writes after the tick, so the zero-host-sync invariant
            # holds; requests are captured NOW because _finish_slot
            # clears slots
            tick_reqs = ()
            if _tracing.trace_on() and n % _tracing.TICK_EVERY == 0:
                tick_reqs = [(i, self.slots[i]) for i in occupied
                             if self.slots[i].sampled]
            with _tracing.phase("engine.step", "engine", tick=n):
                with _tracing.phase("engine.launch", "engine",
                                    program="step"):
                    out, starved = self.backend.step(
                        *((self._blk_ids, occ_mask, commit) if block
                          else (self._next_tok, occ_mask)))
                # the ONE host sync/tick: the logits, or a block
                # decoder's (token, probability) a row
                if block:
                    toks, probs = self._to_host(out, "step")
                else:
                    logits = self._to_host(out, "step")
            with _tracing.phase("engine.sample", "engine",
                                tick=n) as sample:
                now = time.monotonic()
                for i in occupied:
                    if i in starved:
                        # page pool exhausted mid-generation: deliver
                        # what was generated so far (the paged analog of
                        # the contiguous cache-window truncation —
                        # documented in serving.md)
                        self._finish_slot(i, "ok")
                        continue
                    req = self.slots[i]
                    if block:
                        self._advance_block(i, toks[i], probs[i],
                                            bool(commit[i]), now)
                        continue
                    nxt = self._sample(req, logits[i])
                    self._next_tok[i] = nxt
                    self._deliver(req, [nxt], now)
                    self._maybe_finish(i, now)
                self.stats["ticks"] += 1
                self.stats["slot_ticks"] += len(occupied)
            tick_dur = (sample.t1 or time.perf_counter()) - t0
            _TM_TICK.observe(tick_dur)
            for i, req in tick_reqs:
                _tracing.record_span(
                    "decode_tick", "replica", req.trace, tick_dur,
                    parent=req.parent, slot=i, tick=n,
                    tokens=len(req.tokens), request=req.id)

    @staticmethod
    def _deliver(req, tokens, now):
        """``tokens`` leave for the request, stamped ``now``; the first
        ever is its TTFT."""
        if not req.tokens:
            req.ttft = now - req.arrival
            _TM_TTFT.observe(req.ttft)
        req.tokens.extend(tokens)
        req.token_times.extend([now] * len(tokens))
        _TM_TOKENS.inc(len(tokens))

    def _new_block(self, slot, fixed=()):
        """A fresh block for ``slot``: ``fixed`` ids (the prompt's
        remainder) in front, the mask id behind."""
        k = len(fixed)
        self._blk_ids[slot, :k] = fixed
        self._blk_ids[slot, k:] = self.decoder.mask_id
        self._blk_fixed[slot] = np.arange(self._block_n) < k
        self._blk_at[slot] = -1
        self._blk_step[slot] = 0

    def _advance_block(self, slot, toks, probs, committed, now):
        """What one forward did to the block of ``slot``: ``toks``,
        ``probs`` ``(n,)`` the most probable token and its probability
        a position.  A commit forward moved the cursor (the backend's
        doing): a fresh block.  A denoise forward fixes the request's
        ``k`` most probable masked positions (ties to the lower
        position); a block without a mask left is whole: its tokens are
        delivered, and its next forward, if the request goes on, is its
        commit."""
        req = self.slots[slot]
        if req.deadline is not None and now > req.deadline:
            self._finish_slot(slot, "timeout")
            return
        if committed:
            self.stats["commit_forwards"] += 1
            self.stats["blocks_committed"] += 1
            if self.backend.exhausted(slot):
                # cache window exhausted: deliver what fits
                self._finish_slot(slot, "ok")
            else:
                self._new_block(slot)
            return
        n, steps = self._block_n, req.denoising_steps
        s = int(self._blk_step[slot])
        masked = np.flatnonzero(~self._blk_fixed[slot])
        k = n // steps + (1 if s < n % steps else 0)
        now_fixed = masked[np.argsort(-probs[masked], kind="stable")[:k]]
        self._blk_ids[slot, now_fixed] = toks[now_fixed]
        self._blk_fixed[slot, now_fixed] = True
        self._blk_at[slot, now_fixed] = s
        self._blk_step[slot] = s + 1
        self.stats["tokens_unmasked"] += len(now_fixed)
        if len(now_fixed) < len(masked):
            return
        # whole: what is the request's of it (not the prompt's
        # remainder, not beyond its budget, nothing behind an eos)
        mine = self._blk_at[slot] >= 0
        new = [int(t) for t in self._blk_ids[slot, mine]][
            :req.max_new_tokens - len(req.tokens)]
        ended = req.eos_id is not None and req.eos_id in new
        if ended:
            new = new[:new.index(req.eos_id) + 1]
        req.unmask_step.extend(
            int(a) for a in self._blk_at[slot, mine][:len(new)])
        self._deliver(req, new, now)
        if ended or len(req.tokens) >= req.max_new_tokens:
            self._finish_slot(slot, "ok")

    def _maybe_finish(self, slot, now):
        req = self.slots[slot]
        if req.deadline is not None and now > req.deadline:
            self._finish_slot(slot, "timeout")
        elif (req.eos_id is not None and req.tokens
              and req.tokens[-1] == req.eos_id):
            self._finish_slot(slot, "ok")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish_slot(slot, "ok")
        elif self.backend.exhausted(slot):
            # cache window exhausted: the checkpoint's positional table
            # ends here — deliver what fits (documented truncation)
            self._finish_slot(slot, "ok")

    def _finish_slot(self, slot, outcome):
        req = self.slots[slot]
        self.slots[slot] = None
        self.backend.release(slot)
        self._next_tok[slot] = 0
        self.stats["completed"] += 1
        _TM_OCCUPANCY.set(self.occupied)
        self._terminal(req, outcome)

    def _terminal(self, req, outcome):
        if req.outcome is not None:   # idempotent: first outcome wins
            return
        req.outcome = outcome
        _TM_REQS.inc(outcome=outcome)
        wall = time.monotonic() - req.arrival
        _TM_REQ_SEC.observe(wall)
        if _tracing.recording():
            # the terminal span covers the whole request (arrival →
            # outcome), router-sampled or not, and carries what the
            # request waited for: the queue, its first token, and each
            # gap between two tokens
            times = req.token_times
            _tracing.record_span(
                "request", "replica", req.trace, wall,
                parent=req.parent, outcome=outcome,
                tokens=len(req.tokens), request=req.id,
                queue_wait_ms=_ms(req.queue_wait), ttft_ms=_ms(req.ttft),
                gaps_ms=[_ms(b - a) for a, b in zip(times, times[1:])])
            if req.sampled and _tracing.trace_on():
                # mirrored into the PR-5 flight ring so post-mortem
                # dumps carry the trace id
                _tm.record_step(
                    loop="serve", trace=req.trace, outcome=outcome,
                    wall_s=wall, ttft_s=req.ttft)
        req._event.set()

    @staticmethod
    def _sample(req, logits):
        """Host-side per-request sampling — same math as
        KVDecoder.generate, but with each request's own params/rng so
        heterogeneous requests co-batch."""
        if req.temperature <= 0:
            return int(logits.argmax())
        lg = logits / req.temperature
        if req.top_k:
            # clamp to the vocab: submit() validates against the
            # decoder's vocab when known, this keeps np.partition safe
            # for decoders that don't expose one
            k = min(req.top_k, lg.shape[-1])
            kth = np.partition(lg, -k)[-k]
            lg = np.where(lg < kth, -np.inf, lg)
        z = lg - lg.max()
        prob = np.exp(z)
        prob /= prob.sum()
        return int(req._rng.choice(lg.shape[-1], p=prob))
