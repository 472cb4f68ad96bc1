"""Autoregressive generation serving: continuous micro-batching + streaming.

The one-shot serving path (engine.py) batches *requests*; generation traffic
batches *tokens*. This module is the decode-side engine on top of the paged
KV cache (:mod:`analytics_zoo_tpu.ops.kv_cache`) and the cached entry
points of a decoder (:class:`~analytics_zoo_tpu.models.decoder.
CachedDecoder`: ``prefill()``, ``decode_step()``, ...):

* :class:`ContinuousBatcher` — ``n_slots`` concurrent decode sequences
  sharing ONE fixed-shape compiled decode step. New requests are admitted
  into free slots and finished ones retired *per decode step*, so aggregate
  throughput tracks active tokens instead of the slowest request in a batch
  (the reference's run-to-completion Flink batches are exactly the
  anti-pattern).
* :class:`GenerationEngine` — the broker-facing job: consumes generation
  requests from ``generation_stream`` (XREADGROUP, same consumer-group
  semantics as the one-shot engine) and streams frame-per-chunk token deltas
  onto a per-request broker stream (``genout:<uri>``) with a final-frame
  marker (token ids as plain ints: small frames are cheapest as JSON).
* :class:`GenerationClient` — ``submit()`` + ``stream()``: the token-delta
  consumer (XREAD cursor reads; broker.py grew the verb for this).

Trace spans: a client ``submit`` parents ``serving.gen.prefill`` and the
per-request ``serving.gen.stream`` span on the engine side, same propagation
rules as the one-shot path. Telemetry: ``zoo_gen_tokens_total``,
``zoo_gen_inter_token_seconds``, ``zoo_gen_requests_total{outcome}``, and
active-slots / free-pages gauges.

The path accounts for its own time (docs/observability.md has the tables):
``zoo_gen_loop_seconds_total{phase}`` splits every second of the decode loop's
thread into exclusive phases (:class:`_LoopClock`; each phase is also a
``serving.gen.loop.<phase>`` profiler region, on the device trace's clock);
the engine's sink and source threads keep the same books
(``zoo_gen_sink_seconds_total``, ``zoo_gen_source_seconds_total``; their
working phases are regions too, their waits are not), and
``zoo_gen_cpu_seconds_total{thread,phase}`` holds each thread's own CPU time
beside its wall time. A request's legs are histograms on one fine ladder:
ingress (client ``submit`` to the engine's source), queue wait (``submit`` to
leaving the backlog), prefill (to the first token on the host; the two sum to
``zoo_gen_ttft_seconds``) and egress (a frame handed to the sink until the
round trip that carried it, one ``XADDM`` for all that waited, returned; ``zoo_gen_egress_queued_seconds{frame}`` is the part of it
spent in the sink's queue).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import logging
import queue
import threading
import time
import uuid
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common import memwitness as _mw
from ..common import telemetry as _tm
from ..common.chaos import WorkerKilled, chaos_point
from ..common.locks import traced_lock
from ..common.resilience import HealthRegistry, RetryAbortedError, RetryPolicy
from ..observability import events as _events
from ..observability import recorder as _flight
from ..ops.kv_cache import (OutOfPages, PagePool, PrefixCache, SCRATCH_PAGE,
                            copy_page)
from . import qos as _qos
from .client import _Conn
from .config import ServingConfig
from .schema import (DEADLINE_KEY, PRIORITY_KEY, SENT_KEY, TRACE_KEY,
                     payload_deadline, payload_priority, payload_sent_at,
                     payload_trace)

logger = logging.getLogger("analytics_zoo_tpu.serving.generation")

GEN_STREAM = "generation_stream"
GEN_OUT_PREFIX = "genout:"
# broker-side stats hash (per consumer group): the engine's source loop
# republishes GenerationEngine.stats() here ~1/s so `cli info` can show
# decode occupancy + prefix-cache hit rate without reaching into the
# serving process
GEN_STATS_PREFIX = "gen:stats:"

_GEN_TOKENS = _tm.counter("zoo_gen_tokens_total",
                          "Tokens processed by generation serving, by phase "
                          "(prefill = prompt tokens, decode = generated)",
                          labels=("phase",))
_GEN_REQS = _tm.counter("zoo_gen_requests_total",
                        "Generation requests finished, by outcome",
                        labels=("outcome",))
_GEN_STEPS = _tm.counter("zoo_gen_decode_steps_total",
                         "Multi-slot decode steps executed (counted when the "
                         "step is collected: read and emitted)")
_GEN_SLOT_STEPS = _tm.counter(
    "zoo_gen_decode_slot_steps_total",
    "Live slots summed over the single-token decode steps dispatched (a "
    "step of 38 live slots adds 38): the rows a step computed, which is what "
    "a per-slot state update costs by")
_GEN_LINEAR_PREFILL = _tm.counter(
    "zoo_gen_linear_prefill_tokens_total",
    "True prompt tokens (no bucket padding) prefilled into the per-slot "
    "recurrent state of a model that has such layers (HybridLM's chunked "
    "scan); stays 0 for a model whose layers all hold pages")
_GEN_LAUNCHES = _tm.counter(
    "zoo_gen_decode_launches_total",
    "Single-token decode steps dispatched, by order: ahead = launched from "
    "the ids the step before it left on the device, before that step was "
    "read (the host's work of the pass runs under a step); drained = "
    "launched from ids on the host, the step in flight collected first, "
    "with the reason (ahead has reason=\"\")",
    labels=("order", "reason"))
_GEN_ITL = _tm.histogram("zoo_gen_inter_token_seconds",
                         "Per-stream time between consecutive emitted tokens",
                         buckets=_tm.LATENCY_LADDER)
_GEN_TTFT = _tm.histogram(
    "zoo_gen_ttft_seconds",
    "Per-stream time from submit to the first emitted token, by priority "
    "class: zoo_gen_queue_wait_seconds + zoo_gen_prefill_seconds of the same "
    "request (chunked prefill makes this a scheduling outcome: the budget "
    "trades running streams' ITL against new streams' TTFT)",
    labels=("priority",), buckets=_tm.LATENCY_LADDER)
_GEN_INGRESS = _tm.histogram(
    "zoo_gen_ingress_seconds",
    "GenerationClient.submit (a wall-clock stamp in the payload; absent "
    "from old clients, then not observed) to the engine's source thread "
    "taking the entry off the broker stream", buckets=_tm.LATENCY_LADDER)
_GEN_QUEUE_WAIT = _tm.histogram(
    "zoo_gen_queue_wait_seconds",
    "Per-stream time from submit to leaving the backlog for a decode slot, "
    "by priority class (the first leg of zoo_gen_ttft_seconds)",
    labels=("priority",), buckets=_tm.LATENCY_LADDER)
_GEN_PREFILL = _tm.histogram(
    "zoo_gen_prefill_seconds",
    "Per-stream time from leaving the backlog to the first token on the "
    "host, by prefill bucket ('chunked' under chunked prefill, the waits "
    "between chunks included): the second leg of zoo_gen_ttft_seconds",
    labels=("bucket",), buckets=_tm.LATENCY_LADDER)
_GEN_EGRESS = _tm.histogram(
    "zoo_gen_egress_seconds",
    "A frame handed to the engine's sink queue until its XADD to the "
    "broker returned", buckets=_tm.LATENCY_LADDER)
_GEN_EGRESS_QUEUED = _tm.histogram(
    "zoo_gen_egress_queued_seconds",
    "The first leg of zoo_gen_egress_seconds: a frame handed to the engine's "
    "sink queue until the sink thread took it off, by kind of frame (first = "
    "a stream's seq 0, final = its last frame, next = the others; one "
    "observation a frame, as zoo_gen_egress_seconds)",
    labels=("frame",), buckets=_tm.LATENCY_LADDER)
_EGRESS_QUEUED = {kind: _GEN_EGRESS_QUEUED.labels(frame=kind)
                  for kind in ("first", "next", "final")}
_GEN_EMIT_BLOCKED = _tm.counter(
    "zoo_gen_emit_blocked_seconds_total",
    "Seconds the decode loop's emit stood blocked because the engine's sink "
    "queue was full (0 while the sink keeps up; rising: the sink is the "
    "knee)")
#: the exclusive phases of the decode loop's thread (docs/observability.md)
LOOP_PHASES = ("swap", "admit", "prefill_host", "prefill_wait", "decode_host",
               "decode_wait", "emit", "idle", "other")
_GEN_LOOP_SECONDS = _tm.counter(
    "zoo_gen_loop_seconds_total",
    "Seconds of the continuous batcher's loop thread by exclusive phase; "
    "the phases sum to the thread's wall time (the *_wait phases are the "
    "host waiting for device work, idle is the wait for a request)",
    labels=("phase",))
#: ... of the engine's sink thread: idle is the wait for a frame
SINK_PHASES = ("idle", "build", "xadd", "ack", "other")
_GEN_SINK_SECONDS = _tm.counter(
    "zoo_gen_sink_seconds_total",
    "Seconds of the generation engine's sink thread by exclusive phase, "
    "summing to the thread's wall time: idle (blocked on its queue), build "
    "(the frame), xadd and ack (the broker calls), other",
    labels=("phase",))
#: ... and of its source thread: poll is the blocking read for a request
SOURCE_PHASES = ("poll", "admit", "stats", "other")
_GEN_SOURCE_SECONDS = _tm.counter(
    "zoo_gen_source_seconds_total",
    "Seconds of the generation engine's source thread by exclusive phase, "
    "summing to the thread's wall time: poll (blocked in XREADGROUP), admit "
    "(an entry parsed and submitted to the batcher), stats (the HSET once a "
    "second), other",
    labels=("phase",))
_GEN_CPU_SECONDS = _tm.counter(
    "zoo_gen_cpu_seconds_total",
    "The thread's own CPU seconds (time.thread_time) by thread (loop, sink, "
    "source) and by the phase of its zoo_gen_<thread>_seconds_total, "
    "estimated from one pass in seventeen. Wall less CPU of a phase that makes "
    "no blocking call is time the thread was runnable and did not run: the "
    "wait for the interpreter or the host",
    labels=("thread", "phase"))
_GEN_PREFILL_CHUNKS = _tm.counter(
    "zoo_gen_prefill_chunks_total",
    "Chunked-prefill dispatches executed (each fills at most "
    "prefill_chunk_tokens positions of one stream's prompt)")
_GEN_SHED = _tm.counter("zoo_gen_shed_total",
                        "Generation requests shed by the continuous batcher "
                        "instead of decoded, by overload class",
                        labels=("reason",))
_GEN_PREEMPT = _tm.counter(
    "zoo_gen_preemptions_total",
    "Bulk decode slots preempted for latency-critical requests (the "
    "preempted stream keeps its KV pages and resumes in a later slot)")
_GEN_SPEC_STEPS = _tm.counter(
    "zoo_gen_spec_steps_total",
    "Speculative verify steps executed (each scores spec_k tokens per slot "
    "in one dispatch)")
_GEN_SPEC_TOKENS = _tm.counter(
    "zoo_gen_spec_tokens_total",
    "Speculative-decode draft accounting: drafted = k-1 proposals per slot "
    "per verify step, accepted = drafts the target confirmed (acceptance "
    "rate = accepted/drafted)", labels=("kind",))
_GEN_SPEC_ACCEPT_PROB = _tm.histogram(
    "zoo_gen_spec_accept_prob",
    "Per-draft acceptance probability under the target distribution "
    "(pi(draft) from the verify step — the expected-acceptance signal)",
    buckets=(.01, .05, .1, .25, .5, .75, .9, .99))
_GEN_SWAPS = _tm.counter(
    "zoo_gen_swaps_total",
    "Atomic (target params, draft schedule) hot-swap pairs applied by live "
    "continuous batchers between decode steps")
_GEN_PREFIX_HITS = _tm.counter(
    "zoo_gen_prefix_hits_total",
    "Prefills that matched at least one published prefix block in the "
    "shared-prefix KV cache (matched pages mapped read-only, zero compute)")
_GEN_PREFIX_MISSES = _tm.counter(
    "zoo_gen_prefix_misses_total",
    "Prefills that matched no published prefix block (full cold prefill)")
_GEN_PREFIX_TOKENS_SAVED = _tm.counter(
    "zoo_gen_prefix_tokens_saved_total",
    "Prompt tokens NOT recomputed because their KV pages came from the "
    "shared-prefix cache (per warm prefill: tokens before the divergence "
    "point)")
_GEN_PREFIX_EVICTED = _tm.counter(
    "zoo_gen_prefix_evicted_pages_total",
    "KV pages released by prefix-cache eviction sweeps (LRU over entries "
    "no live stream is matched through: budget overflow + pool-pressure "
    "reclaims)")
_LIVE_GENERATORS: "weakref.WeakSet[ContinuousBatcher]" = weakref.WeakSet()
_tm.collector("zoo_gen_active_slots",
              "Occupied decode slots summed over live continuous batchers",
              lambda: [((), float(sum(g.active_slots()
                                      for g in list(_LIVE_GENERATORS))))])
_tm.collector("zoo_gen_free_pages",
              "Free KV-cache pages summed over live continuous batchers",
              lambda: [((), float(sum(g.pool.free_count()
                                      for g in list(_LIVE_GENERATORS))))])
_tm.collector("zoo_gen_param_bytes",
              "Bytes of the parameter tree live continuous batchers serve "
              "from (the served tree: leaves the forward casts at use held "
              "in the compute dtype), by leaf dtype",
              lambda: [((dtype,), float(n)) for dtype, n in sorted(sum(
                  (g.param_bytes for g in list(_LIVE_GENERATORS)),
                  collections.Counter()).items())],
              labels=("dtype",))
_tm.collector("zoo_gen_cache_bytes",
              "Bytes of decode cache live continuous batchers hold on the "
              "device, by kind: pages (the K and V pools), and for a model "
              "with per-slot state one kind a leaf: recurrent (the "
              "linear-attention layers' matrix states) or ssm (the "
              "state-space layers'), and conv (their convolution tails)",
              lambda: [((kind,), float(n)) for kind, n in sorted(sum(
                  (collections.Counter(g.cfg.bytes_by_kind())
                   for g in list(_LIVE_GENERATORS)),
                  collections.Counter()).items())],
              labels=("kind",))
_LIVE_ENGINES: "weakref.WeakSet[GenerationEngine]" = weakref.WeakSet()
_tm.collector("zoo_gen_sink_queue_depth",
              "Frames waiting in the sink queues of live generation engines "
              "(a queue holds 1,024; a full one blocks the decode loop's "
              "emit)",
              lambda: [((), float(sum(e._sink_q.qsize()
                                      for e in list(_LIVE_ENGINES))))])
_tm.collector("zoo_gen_prefix_reclaimable_pages",
              "Prefix-cache pages whose only reference is the cache's own "
              "(no live stream attached) — HBM an eviction sweep would "
              "return to the free list, distinguishing 'held but "
              "reclaimable' from truly occupied pages",
              lambda: [((), float(sum(
                  g.prefix_cache.reclaimable_pages()
                  for g in list(_LIVE_GENERATORS)
                  if g.prefix_cache is not None)))])


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class _Phase:
    """``with clock.phase(name):`` (see :class:`_LoopClock`)."""

    __slots__ = ("_clock", "_name")

    def __init__(self, clock: "_LoopClock", name: str):
        self._clock = clock
        self._name = name

    def __enter__(self):
        self._clock._push(self._name)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._clock._pop()
        return False


_NO_PHASE = contextlib.nullcontext()


class _Sampled:
    """A CPU child that is fed on one pass in ``clock.CPU_EVERY``: what it is
    given counts that many times (see :class:`_LoopClock`)."""

    __slots__ = ("_child", "_clock")

    def __init__(self, child, clock: "_LoopClock"):
        self._child = child
        self._clock = clock

    def inc(self, seconds: float) -> None:
        self._child.inc(seconds * self._clock.CPU_EVERY)


class _LoopClock:
    """Closed accounting of one thread's wall time: the decode loop's (the
    defaults), the engine's sink's, its source's.

    The thread is in exactly one phase at a time: entering a phase suspends
    the one around it and leaving resumes it, so the phases are exclusive,
    and what no phase claims of a pass goes to ``other`` when the pass
    closes. Their sum is the thread's wall time, to the clock's resolution.
    Each stretch is a :func:`telemetry.region` ``<prefix><phase>``:
    flat, never nested, so a profiler trace shows them side by side on the
    thread's line; the phases of ``quiet`` (a thread's waits) feed their
    counters and enter no annotation. A thread other than the clock's
    (``close()`` failing the streams left) gets a no-op.

    The thread's CPU seconds by the same phases
    (``zoo_gen_cpu_seconds_total{thread,phase}``) are an estimate from one
    pass in :attr:`CPU_EVERY`, counted that many times: ``time.thread_time``
    is a system call, 5.7 us where the benchmark runs against 0.09 for
    ``perf_counter`` (PERF.md, PR 41), and a pass of the decode loop would
    make thirteen of them, a frame of the sink seven, with the interpreter
    held."""

    #: the thread's CPU clock is read on every pass of this many: a prime,
    #: so that the passes read do not fall in step with a period of the work
    #: (a stream takes a new page every ``page_size`` = 16 steps)
    CPU_EVERY = 17

    def __init__(self, family=_GEN_LOOP_SECONDS,
                 phases: Sequence[str] = LOOP_PHASES,
                 prefix: str = "serving.gen.loop.", thread: str = "loop",
                 quiet: Sequence[str] = ()):
        self._children = {p: family.labels(phase=p) for p in phases}
        self._cpu_children = {
            p: _Sampled(_GEN_CPU_SECONDS.labels(thread=thread, phase=p), self)
            for p in phases}
        # resolved once: a phase is entered thousands of times a second
        self._phases = {p: _Phase(self, p) for p in phases}
        self._regions = {p: (prefix + p, self._children[p],
                             self._cpu_children[p], p not in quiet)
                         for p in phases}
        #: this clock's own seconds by phase (the counter family is shared
        #: by every batcher or engine of the process); read by ``stats()``
        self.seconds: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self._tid: Optional[int] = None
        self._stack: List[str] = []     # phases entered, innermost last
        self._open = None               # the innermost phase's running region
        self._passes = 0                # closed since begin()
        self._cpu_pass = False          # this pass reads the CPU clock
        self._pass_t0 = self._pass_c0 = 0.0
        # seconds of this pass in some phase: wall, and the thread's CPU
        self._accounted = self._accounted_cpu = 0.0

    def begin(self) -> None:
        """The calling thread is the loop from now on (a respawn too)."""
        self._tid = threading.get_ident()
        self._stack.clear()
        self._open = None
        self._accounted = self._accounted_cpu = 0.0
        self._passes = 0
        self._cpu_pass = True           # the first pass is one of them
        self._pass_c0 = time.thread_time()
        self._pass_t0 = time.perf_counter()

    def phase(self, name: str):
        if threading.get_ident() != self._tid:
            return _NO_PHASE
        return self._phases[name]

    def _start(self, name: str) -> None:
        region_name, child, cpu_child, annotate = self._regions[name]
        self._open = _tm.region(region_name, child,
                                cpu_child if self._cpu_pass else None,
                                annotate)
        self._open.__enter__()

    def _stop(self, name: str) -> None:
        self._open.__exit__(None, None, None)
        dt = self._open.seconds
        self.seconds[name] += dt
        self._accounted += dt
        self._accounted_cpu += self._open.cpu_seconds

    def _push(self, name: str) -> None:
        outer = self._stack[-1] if self._stack else None
        self._stack.append(name)
        if outer == name:               # the same phase goes on
            return
        if outer is not None:
            self._stop(outer)
        self._start(name)

    def _pop(self) -> None:
        name = self._stack.pop()
        outer = self._stack[-1] if self._stack else None
        if outer == name:
            return
        self._stop(name)
        if outer is not None:
            self._start(outer)

    def close_pass(self) -> None:
        """End of a pass, no phase open: the pass's remainder is ``other``,
        and the next pass starts at the same readings."""
        now = time.perf_counter()
        other = max(0.0, now - self._pass_t0 - self._accounted)
        self._children["other"].inc(other)
        self.seconds["other"] += other
        if self._cpu_pass:
            self._cpu_children["other"].inc(max(
                0.0, time.thread_time() - self._pass_c0 - self._accounted_cpu))
        self._passes += 1
        self._cpu_pass = self._passes % self.CPU_EVERY == 0
        if self._cpu_pass:
            self._pass_c0 = time.thread_time()
        self._pass_t0 = now
        self._accounted = self._accounted_cpu = 0.0


class _Request:
    """One generation request's host-side state."""

    __slots__ = ("uri", "prompt", "max_new_tokens", "temperature", "seed",
                 "eos_id", "on_chunk", "ctx", "submitted_t", "dequeued_t",
                 "first_token_t", "prefill_bucket", "cancelled",
                 "last_emit_t", "priority", "deadline", "seq",
                 "cached_prefix_tokens")

    def __init__(self, uri, prompt, max_new_tokens, temperature, seed,
                 eos_id, on_chunk, ctx, priority=None, deadline=None,
                 seq=0):
        self.uri = uri
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed) & 0xFFFFFFFF
        self.eos_id = eos_id
        self.on_chunk = on_chunk
        self.ctx = ctx
        # the server-side timeline, on time.perf_counter(): submitted,
        # taken from the backlog for a slot (the latest time, if a dry pool
        # sent it back), first token on the host, latest token
        self.submitted_t = time.perf_counter()
        self.dequeued_t = self.submitted_t
        self.first_token_t: Optional[float] = None
        self.last_emit_t: Optional[float] = None
        self.prefill_bucket = ""        # zoo_gen_prefill_seconds' label
        self.cancelled = False
        # overload QoS (serving/qos.py): admission runs in (priority,
        # deadline) order; critical requests may preempt bulk decode slots
        self.priority = _qos.normalize_priority(priority)
        self.deadline = _qos.normalize_deadline(deadline)
        self.seq = seq
        # prompt tokens served from the shared-prefix cache instead of
        # recomputed (set at admission; rides the final frame's meta)
        self.cached_prefix_tokens = 0

    @property
    def order_key(self) -> Tuple:
        return _qos.order_key(self.priority, self.deadline, self.seq)


class StreamHandle:
    """In-process consumer for one stream: iterate :meth:`tokens` for chunk
    deltas, or :meth:`result` for the whole sequence. ``cancel()`` retires
    the request at the next decode step."""

    def __init__(self, request: _Request):
        self._request = request
        self._q: "queue.Queue[Tuple[List[int], bool, Dict[str, Any]]]" = \
            queue.Queue()
        self.uri = request.uri

    def _push(self, tokens: List[int], final: bool, meta: Dict[str, Any]):
        self._q.put((tokens, final, meta))

    def cancel(self):
        self._request.cancelled = True

    def frames(self, timeout_s: float = 60.0):
        """Yield raw ``(tokens, final, meta)`` frames until (and including)
        the final one — the HTTP frontend's chunked-response source. Raises
        :class:`TimeoutError` (not a bare ``queue.Empty``) when the decode
        loop stalls past ``timeout_s``."""
        while True:
            try:
                tokens, final, meta = self._q.get(timeout=timeout_s)
            except queue.Empty:
                raise TimeoutError(
                    f"no generation frame for {self.uri!r} within "
                    f"{timeout_s}s") from None
            yield tokens, final, meta
            if final:
                return

    def tokens(self, timeout_s: float = 60.0):
        """Yield token-chunk lists until the final frame; raises on an
        errored stream."""
        for tokens, final, meta in self.frames(timeout_s=timeout_s):
            if tokens:
                yield tokens
            if final and meta.get("outcome") == "shed":
                raise _qos.ShedError(
                    f"generation request {self.uri!r} shed: "
                    f"{meta.get('error', 'overloaded')}",
                    retry_after_s=float(meta.get("retry_after_s", 1.0)),
                    reason="deadline")
            if final and meta.get("error"):
                raise RuntimeError(
                    f"generation failed for {self.uri!r}: {meta['error']}")

    def result(self, timeout_s: float = 60.0) -> List[int]:
        out: List[int] = []
        for chunk in self.tokens(timeout_s=timeout_s):
            out.extend(chunk)
        return out


class _Slot:
    """One decode slot's host-side state (device state lives in the cache)."""

    __slots__ = ("request", "length", "generated", "last_token", "pages",
                 "handle", "history", "pending_drafts", "prefix_keys",
                 "prefilling", "prefill_done", "chunks")

    def __init__(self, request: _Request, length: int, last_token: int,
                 pages: List[int], history: Optional[List[int]] = None,
                 prefix_keys: Optional[List[str]] = None):
        self.request = request
        self.length = length            # tokens already in the cache
        self.generated = 1              # prefill samples token 0
        self.last_token = last_token    # sampled, not yet cached
        self.pages = pages              # owned page ids (freed on retire)
        # chunked-prefill phase (ISSUE 20): a prefilling slot owns its pages
        # and table row but is masked out of every decode/verify dispatch
        # until _finalize_prefill samples token 0 and flips it live
        self.prefilling = False
        self.prefill_done = 0           # prompt tokens already in the cache
        self.chunks = 0                 # chunk dispatches spent on this slot
        # full token sequence (prompt + emitted) — the self-drafting k-gram
        # proposer's corpus; maintained in plain mode too so a hot-swap into
        # speculative mode can draft for in-flight streams immediately
        self.history: List[int] = history if history is not None else []
        # drafted-but-not-yet-verified tokens: proposed right after a step
        # so a PREEMPTED slot parks carrying its pending draft state and
        # resumes without re-drafting (PR-13 composition)
        self.pending_drafts: Optional[List[int]] = None
        # prefix-cache entry keys this stream matched through at admission;
        # released (stream-active decrement) when the slot retires. The
        # PAGE references ride slot.pages and release with them.
        self.prefix_keys: List[str] = prefix_keys or []


class _Flight:
    """A decode step that was dispatched and has not been read: the step in
    flight. ``next_ids`` is the device array the step returns (the next
    launch takes it in the place of ``ids``; the collect reads it), ``rows``
    the ``(row, slot)`` pairs it stepped: slot OBJECTS, so a row retired and
    refilled between the launch and the collect gets nothing of it."""

    __slots__ = ("next_ids", "rows", "t_launch")

    def __init__(self, next_ids, rows: List[Tuple[int, _Slot]]):
        self.next_ids = next_ids
        self.rows = rows
        self.t_launch = time.monotonic()


#: why a decode launch took its ids from the host (the ``reason`` label of
#: ``zoo_gen_decode_launches_total{order="drained"}``)
DRAIN_REASONS = ("first", "admit", "chunk", "preempt", "swap", "spec")


class ContinuousBatcher:
    """Continuous micro-batching decode loop over a paged KV cache.

    ``model`` is a :class:`~analytics_zoo_tpu.models.decoder.CachedDecoder`
    (``TransformerLM``, ``HybridLM``: one contract, ``init_kv_cache`` /
    ``prefill`` / ``prefill_from`` / ``prefill_chunk`` / ``decode_step`` /
    ``verify_step``), ``params`` its pytree. One daemon loop thread admits
    pending requests into free slots, runs one fixed-shape decode step over
    all slots, emits per-stream token deltas, and retires finished sequences
    — all per step. A chaos-killed loop is respawned by a supervisor with
    cache/slot state intact, so in-flight streams survive (kill-the-engine
    drill in tests/test_generation.py).

    **One decode step stays in flight.** A pass of plain decode is *launch,
    then collect* (:meth:`_launch`, :meth:`_collect`): step n+1 is dispatched
    with the ids step n left ON THE DEVICE in the place of ``ids``, and only
    then is step n read, emitted and finished, while the device runs n+1.
    What step n+1 needs besides, the host knows before it has read step n
    (lengths and token ordinals are step n's plus one; a row that ends with
    step n by ``max_new_tokens`` or ``max_seq_len`` is masked to scratch).
    What is found only at the read (EOS, a cancellation) retires the slot
    then, and the token step n+1 computed for that row is thrown away.
    ``self._flight`` is that step: the one piece of state between the two.
    A pass *drains* (collects the step in flight, then launches from ids on
    the host: the serial order) when a row it has to step was not stepped by
    the step in flight, so its token is on the host (an admission, a resume,
    a chunked prefill finalised), and before what needs every slot's true
    state: a preemption, a hot swap, speculation's steps, the loop's end.
    Nothing selects the order; ``stats()["decode_launches"]`` and
    ``zoo_gen_decode_launches_total{order,reason}`` say how often each ran.

    **What is held on the device.** The batcher reads the compute dtype of
    the precision policy ONCE, when it is built, and every executable it owns
    traces under that dtype on whatever thread and under whatever policy the
    trace happens later. ``self.params`` is the *served tree* of the tree it
    was given: each leaf the model declares as cast at use
    (``model.cast_at_use(params)``: a block's matmul kernels and biases, the
    head) and that is wider than the compute dtype is cast once, in one
    jitted call; every other leaf is the given array itself. Under a bf16
    policy a decode step so streams half the bytes of f32 weights, and its
    arithmetic is the same bit for bit (the cast at use becomes the
    identity). Where no leaf is wider than the compute dtype the served tree
    IS the given tree and nothing is allocated. The batcher keeps no
    reference to the given tree: it is the caller's to drop.
    ``stats()["param_bytes"]`` (``{dtype: bytes}``; ``cli info``;
    ``zoo_gen_param_bytes{dtype}``) says what is being served.

    **A model with per-slot state** (``cfg.slot_state``, the one thing the
    batcher asks about the model it holds: its cache description,
    :class:`~analytics_zoo_tpu.ops.kv_cache.KVCacheConfig`, names layers that
    keep a fixed-size recurrent state a slot instead of pages, as a
    ``HybridLM`` with linear layers does). The loop is the same; three
    things follow from the state being addressed by slot and not through the
    page table. Every prefill is told the slot it fills
    (``model.prefill(..., slots=)``; a model of pages alone ignores it), and
    such a model writes that slot's whole state, so a reused slot starts
    from its prompt and from nothing of the stream before;
    that write is dispatched after any decode step still in flight on the
    row, so a step launched ahead for a stream that has since ended cannot
    reach the next stream's state. A row a step does not step (it ended, it
    sits a step out for want of a page) is masked to scratch in the
    dispatched table, and the model leaves such a row's state as it was.
    And what would need a snapshot or a resume of that state is refused, in
    words, when the batcher is built: a prefix cache, speculation, chunked
    prefill; a critical request does not preempt a bulk slot (parked pages
    would resume in another slot, without the state) and waits for a
    retirement. ``stats()["cache_bytes"]`` and ``zoo_gen_cache_bytes{kind}``
    count the state beside the pages.
    """

    def __init__(self, model, params, *, n_slots: int = 8,
                 page_size: int = 16, max_seq_len: Optional[int] = None,
                 n_pages: Optional[int] = None, top_k: int = 0,
                 spec_k: int = 0, spec_ngram: int = 3,
                 prefix_cache_pages: int = 0,
                 prefix_block_tokens: int = 0,
                 prefill_chunk_tokens: int = 0,
                 prefill_token_budget: int = 0,
                 prefill_slo_itl_s: Optional[float] = None,
                 graph_checks: Optional[str] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 donate_cache: bool = True,
                 registry: Optional[HealthRegistry] = None,
                 autostart: bool = True):
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two, got "
                             f"{page_size} (prefill buckets are pow2 and "
                             f"must tile by pages)")
        if prefill_chunk_tokens < 0 or (prefill_chunk_tokens
                                        and prefill_chunk_tokens % page_size):
            raise ValueError(f"prefill_chunk_tokens must be 0 (whole-prompt "
                             f"prefill) or a positive multiple of page_size "
                             f"{page_size}, got {prefill_chunk_tokens}")
        if prefill_token_budget < 0:
            raise ValueError(f"prefill_token_budget must be >= 0, got "
                             f"{prefill_token_budget}")
        if prefill_token_budget and not prefill_chunk_tokens:
            raise ValueError("prefill_token_budget requires "
                             "prefill_chunk_tokens > 0 (the budget is spent "
                             "in whole chunks)")
        import jax
        import jax.numpy as jnp

        from ..nn.module import compute_dtype

        self.model = model
        # the ONE reading of the precision policy: the served tree, the page
        # pool and every trace below are made for this dtype
        self.compute_dtype = dtype = jnp.dtype(compute_dtype())
        self._cast = jax.jit(lambda leaves: [x.astype(dtype) for x in leaves])
        self.params = self._serve_view(params)
        self.n_slots = int(n_slots)
        # clamp to the vocabulary: lax.top_k with k > V fails at trace time
        self.top_k = min(int(top_k), getattr(model, "vocab", int(top_k)))
        self.cfg, pool = self._pinned(
            model.init_kv_cache, n_slots, page_size=page_size,
            max_seq_len=max_seq_len, n_pages=n_pages)
        # per-slot state (class docstring): what cannot snapshot it is refused
        self._refuse_for_slot_state(
            prefix_cache_pages=prefix_cache_pages, spec_k=spec_k,
            prefill_chunk_tokens=prefill_chunk_tokens)
        # the pool is COMMITTED to the device the served tree lies on (the
        # same buffers, no copy). An executable that holds a shard_map (the
        # flash prefill) returns committed arrays, and everything the pool is
        # then threaded through does: a pool uncommitted until its first such
        # prefill made that bucket lower a second time on its second use, and
        # would make the decode step lower once for ids put from the host and
        # once for the committed ids a step returns
        device = next(iter(
            jax.tree_util.tree_leaves(self.params)[0].devices()))
        self.cache = jax.device_put(pool, device)
        del pool
        # a decode step's ids from the host, as the kind of array a step
        # returns (committed, as the pool is): _decode sees one signature
        # whichever it is given
        self._put_ids = lambda ids: jax.device_put(ids, device)
        ids_on_device = jax.sharding.SingleDeviceSharding(device)
        self.pool = PagePool(self.cfg)
        # shared-prefix KV cache (ISSUE 17): 0 pages disables sharing
        # entirely (the cold baseline); the budget counts CACHE-held pages
        # inside the one pool, reclaimed under pool pressure before any
        # stream is ever truncated for pages the cache is sitting on
        self.prefix_cache: Optional[PrefixCache] = None
        if int(prefix_cache_pages) > 0:
            self.prefix_cache = PrefixCache(
                self.pool,
                block_tokens=int(prefix_block_tokens) or page_size,
                page_size=page_size, max_pages=int(prefix_cache_pages))
        # the paged kernel runs these widths as its query length (a prefill
        # chunk; a prefix-hit suffix bucket, pow2 up to the cache cap):
        # refuse here a width it cannot tile, not at the first long prompt
        from ..ops.paged_attention import query_block

        for width in (int(prefill_chunk_tokens),
                      self.cfg.max_seq_len if self.prefix_cache else 0):
            if width:
                query_block(width, self.cfg.n_heads, self.cfg.head_dim,
                            self.cfg.dtype)
        self.prefix_tokens_saved = 0
        self.peak_pages_in_use = 0
        self.registry = registry
        # host-side mirrors of the traced arrays (fixed shapes)
        self._table = np.full((self.n_slots, self.cfg.pages_per_slot),
                              SCRATCH_PAGE, np.int32)
        self._slots: List[Optional[_Slot]] = [None] * self.n_slots
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        # (priority, deadline)-ordered staging area between the submit queue
        # and slot admission; owned by the loop thread. Preempted bulk slots
        # park here-adjacent with their KV pages INTACT until a slot frees
        self._backlog: List[_Request] = []
        self._preempted: List[_Slot] = []
        self._seq = 0
        # measured per-decode-step service time: the shed proof for queued
        # generation requests (a request whose deadline cannot even absorb
        # one step is hopeless) and the computed Retry-After
        self.step_ema = _qos.ServiceTimeEMA()
        # chunked prefill (ISSUE 20): chunk_tokens > 0 routes EVERY prefill
        # through the fixed-shape chunk executable, interleaved with decode
        # under a per-loop-pass token budget (static YAML budget, or derived
        # from the ITL SLO headroom when prefill_slo_itl_s is declared)
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.prefill_token_budget = int(prefill_token_budget)
        self.prefill_slo_itl_s = (float(prefill_slo_itl_s)
                                  if prefill_slo_itl_s else None)
        self.chunk_ema = _qos.ServiceTimeEMA()
        self._last_budget: Optional[Dict[str, Any]] = None
        # uris cancelled while still queued (bounded: unknown uris age out)
        self._cancelled_uris: "collections.deque[str]" = \
            collections.deque(maxlen=1024)
        self._wake = threading.Event()
        self._stop = threading.Event()
        # slots/table vs stats readers; final-frame callbacks run OUTSIDE it
        # (the PR-8 fix) — the hold-hazard rule keeps that true
        # zoo-lock: guards(_slots, _table, _seq, _preempted)
        self._lock = traced_lock("ContinuousBatcher._lock")
        # speculative decode (ISSUE 14): spec_k >= 2 switches the loop to
        # the k-token verify executable; 0/1 is the classic one-token step.
        # k and the drafter schedule are swappable at runtime as one pair
        # with the params (swap_params — the hot-swap manifest contract)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        if self.spec_k == 1:
            self.spec_k = 0             # k=1 is definitionally plain decode
        self._pending_swap: Optional[Tuple] = None
        self._preempt_refused = False
        self.version: Optional[str] = None
        self.swaps = 0
        # accounting
        self.steps = 0
        self.tokens_generated = 0
        self.requests_finished: Dict[str, int] = {}
        self.loop_respawns = 0
        self.prefill_buckets: set = set()
        self.decode_shapes: set = set()
        self.chunk_shapes: set = set()
        self.prefill_chunks_total = 0
        # spec accounting (acceptance rate = accepted/drafted)
        self.spec_steps = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        # slot-occupancy integral: sum over steps of active-slot count —
        # occupancy = _occupied_slot_steps / (steps * n_slots), the bench's
        # per-entry utilization field
        self._occupied_slot_steps = 0
        self._decode_tokens = 0          # decode-phase tokens (excl prefill)
        self._clock = _LoopClock()
        # the decode step that is dispatched and not read (class docstring).
        # State of the batcher, not of a pass: a loop killed between a launch
        # and its collect leaves it here for the respawned loop
        self._flight: Optional[_Flight] = None
        self._drained_for: Optional[str] = None   # why, until the next launch
        self._collected_t = 0.0                   # time.monotonic() of a collect
        self.launches_ahead = 0
        self.launches_drained: Dict[str, int] = dict.fromkeys(DRAIN_REASONS, 0)

        cfg = self.cfg
        # Donate the KV page pool into both dispatches (the cache-alias
        # rule's invariant): the loop rebinds self.cache to each call's
        # output, so the input pool is dead the moment the step runs — with
        # donation XLA updates the pool in place instead of materializing a
        # second pool-sized buffer and copying every decode step.
        # ``donate_cache=False`` exists for the rule's negative polarity
        # (tests) and for backends where donation misbehaves.
        self.donate_cache = bool(donate_cache)
        self.hbm_budget_bytes = hbm_budget_bytes
        donate = (1,) if donate_cache else ()
        pinned = self._pinned       # each trace under self.compute_dtype
        # ids are declared where every form of them lies, so that host ids a
        # caller passes as NumPy (the benchmark's logit probe) run the
        # executable that serves, not a copy lowered for unplaced ids
        self._decode = jax.jit(
            lambda p, c, ids, ln, tb, sd, ti, tp: pinned(
                model.decode_step,
                p, c, ids, ln, tb, sd, ti, tp, page_size=cfg.page_size,
                top_k=self.top_k),
            in_shardings=(None, None, ids_on_device) + (None,) * 5,
            donate_argnums=donate)
        prefill = jax.jit(
            lambda p, c, ids, ln, tb, slots: pinned(
                model.prefill, p, c, ids, ln, tb, slots=slots,
                page_size=cfg.page_size),
            donate_argnums=donate)

        @functools.wraps(prefill)
        def prefill_at(p, c, ids, ln, tb, slots=None):
            # told which slots it fills (a model of pages alone ignores
            # them); without them (the benchmark's logit probe) slots
            # 0 .. B-1, through the one executable a bucket that serves
            return prefill(p, c, ids, ln, tb,
                           np.arange(len(ln), dtype=np.int32)
                           if slots is None else slots)

        self._prefill = prefill_at
        # suffix prefill from the divergence point of a prefix hit (one
        # executable per pow2 suffix bucket, same ladder as _prefill) and
        # the COW boundary-page copy (ONE executable: src/dst are traced)
        self._prefill_from = jax.jit(
            lambda p, c, ids, st, ln, tb: pinned(
                model.prefill_from,
                p, c, ids, st, ln, tb, page_size=cfg.page_size),
            donate_argnums=donate)
        self._copy_page = jax.jit(
            copy_page, donate_argnums=(0,) if donate_cache else ())
        # chunked prefill: ONE executable per chunk_tokens (B=1, fixed ids
        # width, fixed WIDE table — pages_per_slot + chunk_tokens/page_size
        # entries so the final chunk of a max-length prompt never indexes
        # past the row; overflow entries are scratch, bit-neutral)
        self._prefill_chunk = None
        if self.prefill_chunk_tokens:
            self._prefill_chunk = jax.jit(
                lambda p, c, ids, nd, nv, tb: pinned(
                    model.prefill_chunk,
                    p, c, ids, nd, nv, tb, page_size=cfg.page_size),
                donate_argnums=donate)
        # one compiled verify executable per k ever used (lazily jitted; a
        # spec-schedule hot-swap to a new k compiles exactly one more — the
        # per-(k, slot-count) executable invariant the lint gate asserts)
        self._verify_fns: Dict[int, Any] = {}
        self._donate = donate
        from ..ops.kv_cache import sample_tokens

        self._sample = jax.jit(
            lambda lg, sd, ti, tp: sample_tokens(lg, sd, ti, tp,
                                                 top_k=self.top_k))
        if graph_checks and graph_checks != "off":
            self.check_decode_stability(graph_checks)
        _LIVE_GENERATORS.add(self)
        self._threads: List[threading.Thread] = []
        if autostart:
            self.start()

    def _refuse_for_slot_state(self, *, prefix_cache_pages=0, spec_k=0,
                               prefill_chunk_tokens=0) -> None:
        """Raise for an option that assumes the whole cache is pages, for a
        model that also keeps a recurrent state a slot
        (``cfg.slot_state``)."""
        if not self.cfg.slot_state:
            return
        kind = type(self.model).__name__
        state = ", ".join(name for name, _, _ in self.cfg.slot_state)
        if int(prefix_cache_pages) > 0:
            raise ValueError(
                f"prefix_cache_pages={prefix_cache_pages}: {kind} keeps "
                f"per-slot state ({state}) beside its pages, and a shared "
                f"prefix's pages do not hold the recurrent state at the "
                f"prefix's end; prefix reuse needs a snapshot of that state "
                f"a block, which is not built. Serve it with "
                f"prefix_cache_pages=0")
        if int(spec_k) >= 2:
            raise ValueError(
                f"spec_k={spec_k}: {kind} keeps per-slot state ({state}); a "
                f"rejected draft would have to roll that state back. Serve "
                f"it with spec_k=0")
        if int(prefill_chunk_tokens) > 0:
            raise ValueError(
                f"prefill_chunk_tokens={prefill_chunk_tokens}: chunked "
                f"prefill needs a prefill_chunk() that resumes the per-slot "
                f"state ({state}) where the last chunk left it; {kind} has "
                f"none. Serve it with prefill_chunk_tokens=0")

    # ------------------------------------------------------------- served tree

    def _pinned(self, fn, *args, **kw):
        """``fn(*args, **kw)`` with this thread's compute dtype held to the
        one the batcher was built with: what a jitted dispatch's trace, the
        pool's dtype and the graph checks all go through, so a policy
        changed afterwards cannot pair a served tree in one dtype with a
        trace in another."""
        from ..nn.module import pinned_compute_dtype

        with pinned_compute_dtype(self.compute_dtype):
            return fn(*args, **kw)

    def _serve_view(self, params):
        """The served tree of ``params`` (class docstring):
        on the device, the leaves ``model.cast_at_use`` names that are wider
        than the compute dtype cast in one jitted call, every other leaf the
        array ``jax.device_put`` gave. A narrower leaf stays as it is:
        casting it up once would only make the step read more."""
        import jax
        import jax.numpy as jnp

        served = jax.device_put(params)
        leaves, treedef = jax.tree_util.tree_flatten(served)
        declare = getattr(self.model, "cast_at_use", None)
        if declare is not None:
            flags = treedef.flatten_up_to(declare(served))
            wide = [i for i, (leaf, flag) in enumerate(zip(leaves, flags))
                    if flag and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and leaf.dtype.itemsize > self.compute_dtype.itemsize]
            if wide:
                for i, cast in zip(wide, self._cast([leaves[i]
                                                     for i in wide])):
                    leaves[i] = cast
                served = treedef.unflatten(leaves)
        return served

    @property
    def param_bytes(self) -> "collections.Counter[str]":
        """Bytes of the served tree by leaf dtype (``stats()``, ``cli info``,
        ``zoo_gen_param_bytes{dtype}``)."""
        import jax

        nbytes: "collections.Counter[str]" = collections.Counter()
        for leaf in jax.tree_util.tree_leaves(self.params):
            nbytes[str(leaf.dtype)] += int(leaf.nbytes)
        return nbytes

    # ------------------------------------------------------------------ control

    def start(self) -> "ContinuousBatcher":
        running = getattr(self, "_loop_thread", None)
        if running is not None and running.is_alive():
            return self          # idempotent: already running
        self._stop.clear()
        self._loop_thread = self._spawn_loop()
        sup = threading.Thread(target=self._supervise, daemon=True,
                               name="zoo-gen-supervisor")
        sup.start()
        self._threads = [self._loop_thread, sup]
        return self

    def _spawn_loop(self) -> threading.Thread:
        t = threading.Thread(target=self._loop, daemon=True,
                             name="zoo-gen-batcher")
        t.start()
        return t

    def _supervise(self):
        """Respawn a dead decode loop (chaos kill, model error) with slot and
        cache state intact — in-flight streams continue where they stopped."""
        while not self._stop.is_set():
            if not self._loop_thread.is_alive() and not self._stop.is_set():
                logger.warning("respawning dead generation decode loop")
                self.loop_respawns += 1
                self._loop_thread = self._spawn_loop()
            self._stop.wait(0.05)

    def close(self):
        self._stop.set()
        self._wake.set()
        for t in self._threads:
            t.join(timeout=2.0)
        # fail queued-but-never-admitted requests instead of stranding readers
        while True:
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            self._finish_cb(req, [], "error",
                            error="generator closed before admission")
        backlog, self._backlog = self._backlog, []
        for req in backlog:
            self._finish_cb(req, [], "error",
                            error="generator closed before admission")
        parked, self._preempted = self._preempted, []
        for slot in parked:
            self.pool.release(slot.pages)
            slot.pages = []
            if slot.prefix_keys and self.prefix_cache is not None:
                self.prefix_cache.release_stream(slot.prefix_keys)
                slot.prefix_keys = []
            self._finish_cb(slot.request, [], "error",
                            error="generator closed mid-stream",
                            n_tokens=slot.generated)
        self._fail_all_active("generator closed mid-stream")
        # leak accounting: drop the cache's own page references so a closed
        # batcher's pool sums back to capacity
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate()

    # ------------------------------------------------------------------- client

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None, uri: Optional[str] = None,
               on_chunk: Optional[Callable] = None,
               ctx=None, priority: Optional[str] = None,
               deadline: Optional[float] = None) -> StreamHandle:
        """Enqueue one generation request; returns a :class:`StreamHandle`.
        ``on_chunk(tokens, final, meta)`` additionally mirrors every frame
        (the broker engine rides this). ``priority`` (critical/normal/bulk)
        and ``deadline`` (absolute epoch seconds) order admission; a
        critical request may preempt a bulk slot, and a request whose
        deadline provably cannot be met finishes with outcome ``shed``."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        limit = self.cfg.max_seq_len
        if prompt.size >= limit:
            raise ValueError(f"prompt of {prompt.size} tokens exceeds the "
                             f"cache's max_seq_len {limit}")
        with self._lock:
            self._seq += 1
            seq = self._seq
        req = _Request(uri or uuid.uuid4().hex, prompt, max_new_tokens,
                       temperature, seed, eos_id, on_chunk, ctx,
                       priority=priority, deadline=deadline, seq=seq)
        handle = StreamHandle(req)

        def fanout(tokens, final, meta, _h=handle, _cb=on_chunk):
            _h._push(tokens, final, meta)
            if _cb is not None:
                _cb(tokens, final, meta)

        req.on_chunk = fanout
        self._pending.put(req)
        self._wake.set()
        return handle

    def generate(self, prompt, **kw) -> List[int]:
        """Blocking convenience: submit + drain the stream."""
        timeout_s = kw.pop("timeout_s", 120.0)
        return self.submit(prompt, **kw).result(timeout_s=timeout_s)

    def cancel_uri(self, uri: str) -> None:
        """Cancel by stream id — the remote-cancel entry point (an abandoned
        HTTP client, a client-sent cancel frame). Marks an active slot's
        request cancelled, or remembers the uri (bounded) so a still-queued
        request is dropped at admission."""
        with self._lock:
            for slot in self._slots:
                if slot is not None and slot.request.uri == uri:
                    slot.request.cancelled = True
                    return
            for slot in self._preempted:
                if slot.request.uri == uri:
                    slot.request.cancelled = True
                    return
            self._cancelled_uris.append(uri)

    # ------------------------------------------------------------------- loop

    def active_slots(self) -> int:
        with self._lock:
            return sum(s is not None for s in self._slots)

    def _loop(self):
        clock = self._clock
        clock.begin()
        try:
            while not self._stop.is_set():
                # deterministic fault site: the kill-the-engine-mid-stream
                # drill severs the loop here; the supervisor respawns it
                chaos_point("serving.generate")
                try:
                    self._loop_pass()
                except Exception as e:
                    # a DETERMINISTIC step failure (XLA error, poisoned
                    # cache state) must fail the in-flight streams, not
                    # die and let the supervisor respawn into the same
                    # failure at 20 Hz forever (WorkerKilled — a simulated
                    # crash — still exits to the supervisor below)
                    logger.exception("decode step failed; failing the "
                                     "active streams")
                    self._fail_all_active(f"decode step failed: {e}")
                clock.close_pass()
            try:
                self._drain()           # its tokens belong to the streams
            except Exception:
                logger.exception("the last decode step could not be read")
        except WorkerKilled:
            logger.warning("generation decode loop killed mid-stream; "
                           "slots/cache intact, awaiting respawn")
        finally:
            clock.close_pass()

    def _loop_pass(self):
        clock = self._clock
        if self._pending_swap is not None:
            with clock.phase("swap"):
                self._apply_pending_swap()
        with clock.phase("admit"):
            self._admit()
            if self.prefill_chunk_tokens:
                # spend at most one budget of prefill chunks, THEN decode:
                # running streams advance every loop pass no matter how deep
                # the prefill backlog (starvation-free by construction)
                self._prefill_chunks()
        if self._flight is None and self.active_slots() == 0:
            if (self._pending.empty() and not self._backlog
                    and not self._preempted):
                with clock.phase("idle"):
                    self._wake.wait(timeout=0.05)
                self._wake.clear()
            return
        self._step()

    def _fail_all_active(self, error: str):
        # a step that failed takes the one chained on it with it: whether the
        # launch raised or the collect, the streams below fail here, once
        self._flight = None
        with self._lock:
            finishes = [self._retire_locked(i, "error", error=error)
                        for i, s in enumerate(self._slots) if s is not None]
        for fin in finishes:
            self._finish_cb(*fin)

    # admission ---------------------------------------------------------------

    def _drain_pending(self) -> None:
        """Move submitted requests into the (priority, deadline)-ordered
        backlog, dropping cancelled ones and SHEDDING every request whose
        deadline provably cannot be met — the measured per-decode-step time
        is the proof — before any slot or KV page is spent on it."""
        while True:
            try:
                self._backlog.append(self._pending.get_nowait())
            except queue.Empty:
                break
        if not self._backlog:
            return
        ema = self.step_ema.value()
        now = time.time()
        keep: List[_Request] = []
        for req in sorted(self._backlog, key=lambda r: r.order_key):
            if req.uri in self._cancelled_uris:
                self._cancelled_uris.remove(req.uri)
                req.cancelled = True
            if req.cancelled:
                self._finish_cb(req, [], "cancelled")
                continue
            rec = _flight.get()
            # no recorder (the common case): bare predicate on the admit
            # hot path — every backlog entry is re-judged each decode step.
            # Recorded decisions go through the full pure function so live
            # and replay stay identical; the predicates agree by definition
            if rec is None and not _qos.cannot_meet(req.deadline, 0.0, ema,
                                                    now=now):
                keep.append(req)
                continue
            inputs = {"now": now, "deadline": req.deadline,
                      "est_wait_s": 0.0, "service_ema_s": ema,
                      "depth": len(self._backlog),
                      "concurrency": self.n_slots,
                      "priority": req.priority}
            decision = _qos.admission_decision(inputs)
            if rec is not None:
                rec.record("admission.generation", inputs, decision)
            if decision["action"] == "shed":
                chaos_point("overload.shed", tag="generation")
                _GEN_SHED.labels(reason="deadline").inc()
                self._finish_cb(
                    req, [], "shed",
                    error="deadline cannot be met by the decode loop",
                    retry_after_s=decision["retry_after_s"])
                continue
            keep.append(req)
        self._backlog = keep

    def _admission_open(self) -> bool:
        return any(s is None for s in self._slots) or bool(
            self._backlog and self._backlog[0].priority == "critical")

    def _preempt_for(self, req: _Request) -> bool:
        """Make room for a critical request by preempting a BULK slot: the
        victim's host state (pages included — its KV cache contents stay
        exactly where they are) parks on the preempted list and resumes in
        a later free slot with nothing recomputed. Returns True when a slot
        was freed."""
        if req.priority != "critical":
            return False
        if self.cfg.slot_state:
            # a parked stream keeps its pages and resumes in ANOTHER slot,
            # where its recurrent state is not: the request waits for a
            # retirement (class docstring)
            if not self._preempt_refused:
                self._preempt_refused = True
                logger.warning(
                    "generation: %s keeps per-slot state; a critical "
                    "request does not preempt a bulk slot (its state cannot "
                    "be parked) and waits for a retirement",
                    type(self.model).__name__)
            return False
        with self._lock:
            if not any(s is not None and s.request.priority == "bulk"
                       for s in self._slots):
                return False
        # the victim parks with its true last_token, length and ordinal
        self._drain("preempt")
        with self._lock:
            if any(s is None for s in self._slots):
                return True             # the collect retired a stream
            victims = [(s.request.order_key, i) for i, s in
                       enumerate(self._slots)
                       if s is not None and s.request.priority == "bulk"]
            if not victims:
                return False
            # preempt the LEAST urgent bulk stream (max order key)
            _, idx = max(victims)
            slot = self._slots[idx]
            self._slots[idx] = None
            self._table[idx, :] = SCRATCH_PAGE
            self._preempted.append(slot)
        _GEN_PREEMPT.inc()
        logger.info("generation: preempted bulk stream %s for critical %s",
                    slot.request.uri, req.uri)
        return True

    def _resume_slot(self, parked: _Slot) -> None:
        """Re-install a preempted stream into a free slot: restore its page
        table row from the pages it kept and continue decoding — no
        prefill, no token loss."""
        if parked.request.cancelled:
            with self._lock:
                self.pool.release(parked.pages)
                parked.pages = []
                if parked.prefix_keys and self.prefix_cache is not None:
                    self.prefix_cache.release_stream(parked.prefix_keys)
                    parked.prefix_keys = []
            self._finish_cb(parked.request, [], "cancelled")
            return
        with self._lock:
            idx = self._slots.index(None)
            self._table[idx, :] = SCRATCH_PAGE
            self._table[idx, :len(parked.pages)] = parked.pages
            self._slots[idx] = parked

    def _admit(self):
        self._drain_pending()
        # the gate is asked ONCE per loop pass; the pass then fills every
        # free slot
        if not self._admission_open():
            return
        while not self._stop.is_set():
            # next admission candidate: preempted streams compete with the
            # backlog under the same (priority, deadline) order — a parked
            # bulk stream does not jump a queued critical request
            with self._lock:
                cand_resume = min(self._preempted,
                                  key=lambda s: s.request.order_key,
                                  default=None)
            cand_new: Optional[_Request] = \
                self._backlog[0] if self._backlog else None
            if cand_resume is not None and (
                    cand_new is None
                    or cand_resume.request.order_key <= cand_new.order_key):
                if not any(s is None for s in self._slots):
                    return
                with self._lock:
                    self._preempted.remove(cand_resume)
                self._resume_slot(cand_resume)
                continue
            if cand_new is None:
                return
            if not any(s is None for s in self._slots):
                # full house: a critical head may evict a bulk slot (pages
                # intact); anything else waits for a retirement
                if not self._preempt_for(cand_new):
                    return
            req = self._backlog.pop(0)
            req.dequeued_t = time.perf_counter()
            if req.uri in self._cancelled_uris:
                self._cancelled_uris.remove(req.uri)
                req.cancelled = True
            if req.cancelled:
                self._finish_cb(req, [], "cancelled")
                continue
            try:
                self._prefill_into_slot(req)
            except OutOfPages:
                n_need = -(-req.prompt.size // self.cfg.page_size)
                if n_need > self.pool.capacity:
                    self._finish_cb(req, [], "error",
                                    error=f"prompt needs {n_need} pages, "
                                          f"pool capacity "
                                          f"{self.pool.capacity}")
                    continue
                # pool temporarily dry: park at the backlog head (ordered
                # admission keeps it first in class) and wait for retirements
                self._backlog.insert(0, req)
                if self.active_slots() == 0 and self._preempted:
                    # every page is held by PARKED streams (preempt took the
                    # victims' slots but not their pages): resume one so the
                    # pool can ever drain — otherwise the critical head and
                    # the parked bulk would deadlock each other
                    with self._lock:
                        parked = min(self._preempted,
                                     key=lambda s: s.request.order_key)
                        self._preempted.remove(parked)
                    self._resume_slot(parked)
                return
            except WorkerKilled:
                # chaos kill mid-prefill: the request lost nothing (every
                # page/cache reference was handed back above) — requeue it
                # at the backlog head so the respawned loop re-admits it,
                # then let the kill reach the supervisor
                self._backlog.insert(0, req)
                raise
            except Exception as e:   # a bad request must not kill the loop
                logger.exception("prefill failed for %s", req.uri)
                self._finish_cb(req, [], "error", error=str(e))

    def _alloc_pages(self, n: int) -> List[int]:
        """``pool.alloc`` with the prefix cache as a pressure valve: a dry
        pool first LRU-evicts cache-held-but-unreferenced entries (that HBM
        is reclaimable, not occupied) before :class:`OutOfPages` ever
        reaches a stream."""
        try:
            return self.pool.alloc(n)
        except OutOfPages:
            if self.prefix_cache is None:
                raise
            freed = self.prefix_cache.reclaim_pages(n)
            if not freed:
                raise
            _GEN_PREFIX_EVICTED.inc(freed)
            _events.emit("gen.prefix.evicted", severity="info",
                         reason="pool_pressure", pages=freed)
            return self.pool.alloc(n)

    def _note_pool_peak(self) -> None:
        used = self.pool.capacity - self.pool.free_count()
        if used > self.peak_pages_in_use:
            self.peak_pages_in_use = used

    def _prefill_into_slot(self, req: _Request):
        if self.prefill_chunk_tokens:
            # chunked mode routes EVERY prefill through the chunk executable
            # (short prompts take one chunk) — one code path, one identity
            return self._begin_chunked_prefill(req)
        clock = self._clock
        slot_idx = self._slots.index(None)
        cfg = self.cfg
        n_prompt = int(req.prompt.size)
        n_pg = -(-n_prompt // cfg.page_size)
        # shared-prefix lookup FIRST: matched blocks arrive as read-only
        # pages (lookup already took this stream's pool references on them)
        match = None
        if self.prefix_cache is not None:
            match = self.prefix_cache.lookup(req.prompt)
            if match is None:
                _GEN_PREFIX_MISSES.inc()
            else:
                _GEN_PREFIX_HITS.inc()
        keys: List[str] = [] if match is None else match.keys
        row: List[int] = [] if match is None else list(match.pages)
        held: List[int] = list(row)     # pages this stream holds refs on
        start = 0 if match is None else match.n_tokens
        try:
            if match is not None and start >= n_prompt:
                # the WHOLE (block-aligned) prompt is cached, but sampling
                # token 0 still needs the last position's logits — recompute
                # just that token, copy-on-writing the boundary page so its
                # K/V write never lands in a shared page
                start = n_prompt - 1
                bp = start // cfg.page_size
                (cow,) = self._alloc_pages(1)
                held.append(cow)
                self.cache = self._copy_page(
                    self.cache, np.int32(row[bp]), np.int32(cow))
                self.pool.release([row[bp]])
                held.remove(row[bp])
                row[bp] = cow
            if len(row) < n_pg:
                fresh = self._alloc_pages(n_pg - len(row))
                row.extend(fresh)
                held.extend(fresh)
            self._note_pool_peak()
            n_suffix = n_prompt - start
            bucket = min(max(_next_pow2(n_suffix), cfg.page_size),
                         cfg.max_seq_len)
            if bucket % cfg.page_size:
                bucket = -(-bucket // cfg.page_size) * cfg.page_size
            if start:
                # refcount-aliasing write isolation: every page the suffix
                # dispatch can write must be exclusively this stream's
                from ..analysis.rules.decode import lint_prefix_write_isolation

                findings = lint_prefix_write_isolation(
                    self.pool, row, start, page_size=cfg.page_size)
                if findings:
                    raise RuntimeError(
                        "prefix-share write isolation violated: "
                        + "; ".join(f.message for f in findings))
            with _tm.span("serving.gen.prefill", remote=req.ctx, uri=req.uri,
                          bucket=bucket, cached_tokens=start,
                          slot=slot_idx), \
                    clock.phase("prefill_host"):
                ids = np.zeros((1, bucket), np.int32)
                ids[0, :n_suffix] = req.prompt[start:]
                table = np.full((1, cfg.pages_per_slot), SCRATCH_PAGE,
                                np.int32)
                table[0, :len(row)] = row
                if start:
                    logits, self.cache = self._prefill_from(
                        self.params, self.cache, ids,
                        np.array([start], np.int32),
                        np.array([n_prompt], np.int32), table)
                else:
                    logits, self.cache = self._prefill(
                        self.params, self.cache, ids,
                        np.array([n_prompt], np.int32), table,
                        np.array([slot_idx], np.int32))
                first = self._sample(
                    logits, np.array([req.seed], np.uint32),
                    np.array([0], np.uint32),
                    np.array([req.temperature], np.float32))
                with clock.phase("prefill_wait"):
                    tok = int(np.asarray(first)[0])
            if self.prefix_cache is not None:
                # deterministic fault site: the chaos drill kills the loop
                # HERE — after compute, before publish. The handler below
                # releases every reference this stream took; publish itself
                # is all-or-nothing under the cache lock, so a respawn can
                # never observe a torn chain
                chaos_point("prefix.publish")
                self.prefix_cache.publish(req.prompt, n_prompt, row)
                sweep = self.prefix_cache.evict_to_budget()
                if sweep["pages"]:
                    _GEN_PREFIX_EVICTED.inc(sweep["pages"])
                    _events.emit("gen.prefix.evicted", severity="info",
                                 reason="budget", entries=sweep["entries"],
                                 pages=sweep["pages"],
                                 held_pages=sweep["held_pages"])
        except BaseException:
            # a failed prefill must hand back EVERYTHING it acquired —
            # shared-page references included — or repeated failures would
            # drain the pool permanently
            if keys and self.prefix_cache is not None:
                self.prefix_cache.release_stream(keys)
            self.pool.release(held)
            raise
        self.prefill_buckets.add(bucket)
        req.prefill_bucket = str(bucket)
        _GEN_TOKENS.labels(phase="prefill").inc(n_suffix)
        if self.cfg.slot_state:
            _GEN_LINEAR_PREFILL.inc(n_prompt)
        if start:
            req.cached_prefix_tokens = start
            self.prefix_tokens_saved += start
            _GEN_PREFIX_TOKENS_SAVED.inc(start)
        slot = _Slot(req, n_prompt, tok, list(row),
                     history=req.prompt.tolist() + [tok],
                     prefix_keys=keys)
        if self.spec_k >= 2:
            from ..ops.speculative import propose_kgram

            slot.pending_drafts = propose_kgram(
                slot.history, self.spec_k - 1, self.spec_ngram)
        with self._lock:
            self._table[slot_idx, :] = SCRATCH_PAGE
            self._table[slot_idx, :n_pg] = row
            self._slots[slot_idx] = slot
        with clock.phase("emit"):
            self._emit(slot, [tok])
            self._maybe_finish(slot_idx)

    # chunked prefill (ISSUE 20) ----------------------------------------------

    def _begin_chunked_prefill(self, req: _Request):
        """Admit a request into the ``prefilling`` phase: claim its pages
        (warm prefix blocks arrive from the cache first, so a warm stream
        skips straight to its suffix chunks), install the slot MASKED out of
        every decode dispatch, and let :meth:`_prefill_chunks` fill the
        prompt chunk by chunk under the loop's token budget. Nothing is
        dispatched here — admission stays O(host work).

        Error contract (same as whole-prompt prefill): any failure before
        the slot installs hands back every page and prefix reference this
        request acquired; after install, :meth:`_retire_locked` owns that
        release exactly once."""
        slot_idx = self._slots.index(None)
        cfg = self.cfg
        n_prompt = int(req.prompt.size)
        n_pg = -(-n_prompt // cfg.page_size)
        match = None
        if self.prefix_cache is not None:
            match = self.prefix_cache.lookup(req.prompt)
            if match is None:
                _GEN_PREFIX_MISSES.inc()
            else:
                _GEN_PREFIX_HITS.inc()
        keys: List[str] = [] if match is None else match.keys
        row: List[int] = [] if match is None else list(match.pages)
        held: List[int] = list(row)     # pages this stream holds refs on
        start = 0 if match is None else match.n_tokens
        try:
            if match is not None and start >= n_prompt:
                # whole (block-aligned) prompt cached: only the last token
                # needs recomputing for its logits — copy-on-write the
                # boundary page so the chunk's K/V write never lands in a
                # shared page, then prefill a single 1-token chunk
                start = n_prompt - 1
                bp = start // cfg.page_size
                (cow,) = self._alloc_pages(1)
                held.append(cow)
                self.cache = self._copy_page(
                    self.cache, np.int32(row[bp]), np.int32(cow))
                self.pool.release([row[bp]])
                held.remove(row[bp])
                row[bp] = cow
            if len(row) < n_pg:
                fresh = self._alloc_pages(n_pg - len(row))
                row.extend(fresh)
                held.extend(fresh)
            self._note_pool_peak()
            if start:
                # refcount-aliasing write isolation: every page the suffix
                # chunks can write must be exclusively this stream's
                from ..analysis.rules.decode import lint_prefix_write_isolation

                findings = lint_prefix_write_isolation(
                    self.pool, row, start, page_size=cfg.page_size)
                if findings:
                    raise RuntimeError(
                        "prefix-share write isolation violated: "
                        + "; ".join(f.message for f in findings))
        except BaseException:
            # a failed admission must hand back EVERYTHING it acquired —
            # shared-page references included — or repeated failures would
            # drain the pool permanently
            if keys and self.prefix_cache is not None:
                self.prefix_cache.release_stream(keys)
            self.pool.release(held)
            raise
        if start:
            req.cached_prefix_tokens = start
            self.prefix_tokens_saved += start
            _GEN_PREFIX_TOKENS_SAVED.inc(start)
        req.prefill_bucket = "chunked"
        slot = _Slot(req, n_prompt, -1, list(row), prefix_keys=keys)
        slot.generated = 0              # token 0 samples at finalize
        slot.prefilling = True
        slot.prefill_done = start
        with self._lock:
            self._table[slot_idx, :] = SCRATCH_PAGE
            self._table[slot_idx, :n_pg] = row
            self._slots[slot_idx] = slot

    def _prefill_budget(self) -> int:
        """Tokens this loop pass may spend on prefill chunks, through the
        pure decision function (recorded on the flight recorder whenever the
        verdict changes — live and replay stay identical)."""
        inputs = {"chunk_tokens": self.prefill_chunk_tokens,
                  "static_budget": self.prefill_token_budget,
                  "itl_target_s": self.prefill_slo_itl_s,
                  "decode_ema_s": round(self.step_ema.value(), 6),
                  "chunk_ema_s": round(self.chunk_ema.value(), 6)}
        decision = _qos.prefill_budget_decision(inputs)
        if decision != self._last_budget:
            rec = _flight.get()
            if rec is not None:
                rec.record("gen.prefill.budget", inputs, decision)
            _events.emit("gen.prefill.budget", severity="info",
                         budget_tokens=decision["budget_tokens"],
                         chunks=decision["chunks"],
                         source=decision["source"])
            self._last_budget = decision
        return int(decision["budget_tokens"])

    def _prefill_chunks(self):
        """Spend at most one token budget on pending prefill chunks, in
        (priority, deadline) order. The FIRST chunk always runs (progress
        floor: a prefilling stream must advance even when the budget is
        below one chunk), then chunks run while they fit."""
        budget: Optional[int] = None
        spent = 0
        while True:
            with self._lock:
                cands = [(s.request.order_key, i)
                         for i, s in enumerate(self._slots)
                         if s is not None and s.prefilling]
            if not cands:
                return
            if budget is None:
                budget = self._prefill_budget()
            if spent and spent + self.prefill_chunk_tokens > budget:
                return
            _, idx = min(cands)
            spent += self._prefill_one_chunk(idx)

    def _prefill_one_chunk(self, idx: int) -> int:
        """Run ONE chunk of slot ``idx``'s prompt through the fixed-shape
        chunk executable; finalize the stream when the prompt completes.
        Returns the chunk tokens spent (0 when the slot retired instead)."""
        cfg = self.cfg
        ct = self.prefill_chunk_tokens
        fin = None
        with self._lock:
            slot = self._slots[idx]
            if slot is None or not slot.prefilling:
                return 0
            if slot.request.cancelled:
                fin = self._retire_locked(idx, "cancelled")
        if fin is not None:
            self._finish_cb(*fin)
            return 0
        req = slot.request
        n_prompt = int(req.prompt.size)
        n_done = slot.prefill_done
        n_valid = min(ct, n_prompt - n_done)
        # deterministic fault site BEFORE the dispatch: a kill here leaves
        # the slot's state untouched, so the respawned loop re-runs exactly
        # this chunk — idempotent (same K/V rewritten into exclusively-owned
        # pages; the token sample happens only once, at finalize)
        chaos_point("prefill.chunk")
        try:
            with _tm.span("serving.gen.prefill.chunk", remote=req.ctx,
                          uri=req.uri, n_done=n_done, n_valid=n_valid), \
                    self._clock.phase("prefill_host"):
                ids = np.zeros((1, ct), np.int32)
                ids[0, :n_valid] = req.prompt[n_done:n_done + n_valid]
                # WIDE table: a chunk ending at position n_done+ct-1 can
                # index page (pages_per_slot - 1) + ct/page_size; overflow
                # entries stay scratch (masked lanes, bit-neutral)
                wide = cfg.pages_per_slot + ct // cfg.page_size
                table = np.full((1, wide), SCRATCH_PAGE, np.int32)
                table[0, :len(slot.pages)] = slot.pages
                t0 = time.monotonic()
                logits, self.cache = self._prefill_chunk(
                    self.params, self.cache, ids,
                    np.array([n_done], np.int32),
                    np.array([n_valid], np.int32), table)
                self.chunk_ema.observe(time.monotonic() - t0)
        except Exception as e:
            # a deterministic chunk failure (bad state, XLA error) fails
            # THIS stream, not the loop; WorkerKilled (BaseException)
            # still propagates to the supervisor with slot state intact
            logger.exception("prefill chunk failed for %s", req.uri)
            with self._lock:
                if self._slots[idx] is slot:
                    fin = self._retire_locked(
                        idx, "error", error=f"prefill chunk failed: {e}")
            if fin is not None:
                self._finish_cb(*fin)
            return ct
        slot.prefill_done = n_done + n_valid
        slot.chunks += 1
        self.prefill_chunks_total += 1
        self.chunk_shapes.add((ct, wide))
        _GEN_PREFILL_CHUNKS.inc()
        _GEN_TOKENS.labels(phase="prefill").inc(n_valid)
        if slot.prefill_done >= n_prompt:
            self._finalize_prefill(idx, slot, logits)
        return ct

    def _finalize_prefill(self, idx: int, slot: _Slot, logits) -> None:
        """Flip a fully-prefilled slot live: sample token 0 (same seed,
        same ordinal-0 sample whole-prompt prefill takes — chunking never
        changes a stream's tokens), THEN publish to the prefix cache. The
        order matters: a chaos kill at the publish site leaves a clean
        decoding slot that merely never published — nothing to unwind."""
        req = slot.request
        clock = self._clock
        with clock.phase("prefill_host"):
            first = self._sample(
                logits, np.array([req.seed], np.uint32),
                np.array([0], np.uint32),
                np.array([req.temperature], np.float32))
            with clock.phase("prefill_wait"):
                tok = int(np.asarray(first)[0])
        slot.last_token = tok
        slot.generated = 1
        slot.history = req.prompt.tolist() + [tok]
        slot.prefilling = False
        if self.spec_k >= 2:
            from ..ops.speculative import propose_kgram

            slot.pending_drafts = propose_kgram(
                slot.history, self.spec_k - 1, self.spec_ngram)
        if self.prefix_cache is not None:
            chaos_point("prefix.publish")
            self.prefix_cache.publish(req.prompt, int(req.prompt.size),
                                      slot.pages)
            sweep = self.prefix_cache.evict_to_budget()
            if sweep["pages"]:
                _GEN_PREFIX_EVICTED.inc(sweep["pages"])
                _events.emit("gen.prefix.evicted", severity="info",
                             reason="budget", entries=sweep["entries"],
                             pages=sweep["pages"],
                             held_pages=sweep["held_pages"])
        with clock.phase("emit"):
            self._emit(slot, [tok])
            self._maybe_finish(idx)

    # decode ------------------------------------------------------------------

    def _verify_fn(self, k: int):
        """The compiled k-token verify executable (lazily jitted, cached
        per k — exactly one executable per (k, slot-count))."""
        fn = self._verify_fns.get(k)
        if fn is None:
            import jax

            cfg = self.cfg
            fn = jax.jit(
                lambda p, c, ids, ln, tb, sd, ti, tp: self._pinned(
                    self.model.verify_step,
                    p, c, ids, ln, tb, sd, ti, tp, page_size=cfg.page_size,
                    top_k=self.top_k), donate_argnums=self._donate)
            self._verify_fns[k] = fn
        return fn

    def _apply_pending_swap(self):
        """Land a staged (params, spec schedule) pair between decode steps:
        the loop thread is the only dispatcher, so no step ever sees a
        mixed (old params, new drafter) — the atomic manifest-pair flip
        (see :meth:`swap_params`)."""
        pend = self._pending_swap
        if pend is None:
            return
        self._drain("swap")     # no step spans the flip of (params, schedule)
        self._pending_swap = None
        params, version, spec = pend
        self.params = params
        self.version = version
        if spec is not None:
            self.spec_k = 0 if spec.k == 1 else int(spec.k)
            self.spec_ngram = int(spec.max_ngram)
        with self._lock:
            parked = list(self._preempted)
        for slot in list(self._slots) + parked:
            if slot is not None:
                # proposals drafted under the OLD target die with it; the
                # k-gram corpus (history) is model-independent and survives
                slot.pending_drafts = None
        if self.prefix_cache is not None:
            # published K/V was computed under the OLD weights — one atomic
            # invalidate between steps. In-flight warm streams keep their
            # own page references and stay token-exact; only the index dies
            dropped = self.prefix_cache.invalidate()
            if dropped:
                _events.emit("gen.prefix.invalidated", severity="info",
                             reason="hot_swap", pages=dropped,
                             version=str(version))
        self.swaps += 1
        _GEN_SWAPS.inc()
        logger.info("generation batcher swapped to version=%s spec_k=%d",
                    version, self.spec_k)

    def _step(self):
        if self.spec_k >= 2:
            return self._step_spec()
        self._step_plain()

    def _step_plain(self, rows: Optional[List[int]] = None):
        """One pass of single-token decode: launch a step, then collect the
        one launched a pass ago, which the device finished while the host
        emitted, admitted and built this one (class docstring). The pass
        drains first when the step in flight cannot give the next one its
        ids. ``rows=None`` steps every occupied slot (classic mode); a row
        subset steps only those slots, with every other row masked to
        scratch in the dispatched table copy — speculative mode's tail
        regime (slots within k of the cache cap, or squeezed out of the
        k-page lookahead by a dry pool) rides the SAME single-token
        executable plain decode uses, in the serial order (launched from the
        host, collected at once), so those streams emit and truncate exactly
        as the non-speculative loop would."""
        stepped = self._flight
        if stepped is not None:
            why = self._why_drain(stepped, rows)
            if why is not None:
                self._drain(why)
                stepped = None
        self._flight = self._launch(rows, stepped)
        if rows is not None:
            stepped, self._flight = self._flight, None
        if stepped is not None:
            self._collect(stepped)

    def _why_drain(self, flight: _Flight,
                   rows: Optional[List[int]]) -> Optional[str]:
        """None when the next step can take its ids from ``flight`` on the
        device: every row it has to step was stepped by ``flight``. Else the
        reason its ids have to come from the host: a row joined whose token
        is there (admitted, resumed, or left out of a step for want of a
        page: ``admit``; its prefill finalised in chunks: ``chunk``), or the
        rows are speculation's tail."""
        if rows is not None:
            return "spec"
        stepped = dict(flight.rows)
        with self._lock:
            for i, slot in enumerate(self._slots):
                if (slot is None or slot.prefilling or slot.request.cancelled
                        or stepped.get(i) is slot):
                    continue
                return "chunk" if slot.chunks else "admit"
        return None

    def _drain(self, reason: Optional[str] = None) -> None:
        """Collect the step in flight, if there is one, before something that
        needs every slot's true state; the next launch is ``reason``'s."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._drained_for = reason
            self._collect(flight)

    def _launch(self, rows: Optional[List[int]],
                chain: Optional[_Flight]) -> Optional[_Flight]:
        """Dispatch one single-token decode step and return it unread, or
        None when no row has a token to compute. With ``chain`` (the step in
        flight, which stepped every row this one steps: :meth:`_why_drain`)
        the step's ``ids`` are ``chain``'s on the device and every row is one
        step ahead of its slot's host state; without, they are the slots'
        ``last_token``, put on the device the same way (one executable
        whichever it is)."""
        clock = self._clock
        with clock.phase("decode_host"):
            cfg = self.cfg
            b = self.n_slots
            d = 0 if chain is None else 1   # steps each row has in flight
            ids = np.zeros(b, np.int32)     # unused under a chain
            lengths = np.zeros(b, np.int32)
            seeds = np.zeros(b, np.uint32)
            tok_idx = np.zeros(b, np.uint32)
            temps = np.zeros(b, np.float32)
            masked = np.ones(b, bool)
            finishes = []
            live: List[Tuple[int, _Slot]] = []
            with self._lock:
                for i in (range(b) if rows is None else rows):
                    slot = self._slots[i]
                    if slot is None:
                        continue
                    req = slot.request
                    if req.cancelled:
                        finishes.append(self._retire_locked(i, "cancelled"))
                        continue
                    if slot.prefilling:
                        # mid-prefill: masked out of the dispatch below — an
                        # unmasked row would take a position-0 K/V write into
                        # its REAL first page (silent prompt corruption)
                        continue
                    length = slot.length + d
                    generated = slot.generated + d
                    if d and (generated >= req.max_new_tokens
                              or length + 1 > cfg.max_seq_len):
                        continue    # ends with the step in flight: its collect
                    # grow: the position being written this step needs its page
                    p = length // cfg.page_size
                    if self._table[i, p] == SCRATCH_PAGE:
                        try:
                            (pg,) = self._alloc_pages(1)
                        except OutOfPages:
                            if d:
                                # its token in flight is still owed: sit this
                                # step out; the next pass drains and decides
                                continue
                            finishes.append(self._retire_locked(
                                i, "truncated",
                                error="kv page pool exhausted"))
                            continue
                        self._table[i, p] = pg
                        slot.pages.append(pg)
                        self._note_pool_peak()
                    ids[i] = slot.last_token
                    lengths[i] = length
                    seeds[i] = req.seed
                    tok_idx[i] = generated
                    temps[i] = req.temperature
                    masked[i] = False
                    live.append((i, slot))
                table = self._table.copy()
            # every row not stepped writes to scratch: free rows are scratch
            # already; prefilling, ending and non-member rows are not
            table[masked] = SCRATCH_PAGE
            for fin in finishes:       # final-frame callbacks OUTSIDE the lock
                self._finish_cb(*fin)
            if not live:
                return None
            if chain is not None:
                order, reason = "ahead", ""
                self.launches_ahead += 1
            else:
                order = "drained"
                reason = ("spec" if rows is not None
                          else self._drained_for or "first")
                self.launches_drained[reason] += 1
            self._drained_for = None
            _GEN_LAUNCHES.labels(order, reason).inc()
            _GEN_SLOT_STEPS.inc(len(live))
            self.decode_shapes.add((b, cfg.pages_per_slot, cfg.page_size))
            next_ids, _logits, self.cache = self._decode(
                self.params, self.cache,
                self._put_ids(ids) if chain is None else chain.next_ids,
                lengths, table, seeds, tok_idx, temps)
            # the copy to the host starts when the step ends, not when the
            # collect asks: the read finds the ids there (a step wrapped by a
            # test may hand back host ids, which have nothing to start)
            start_copy = getattr(next_ids, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
            return _Flight(next_ids, live)

    def _collect(self, flight: _Flight) -> None:
        """Read a launched step and give its rows their tokens: emit, finish.
        A row whose slot was retired since the launch (EOS or a cancellation
        found at the collect before, a failure) gets nothing, whoever holds
        the row now."""
        clock = self._clock
        with clock.phase("decode_host"):
            with clock.phase("decode_wait"):
                next_ids = np.asarray(flight.next_ids).tolist()
            # what a stream waits for a token: since the collect before, or
            # since the launch where the loop had nothing in flight
            now = time.monotonic()
            self.step_ema.observe(now - max(flight.t_launch,
                                            self._collected_t))
            self._collected_t = now
            self.steps += 1
            _GEN_STEPS.inc()
            _mw.sample("serving.decode")
        with clock.phase("emit"):
            for i, slot in flight.rows:
                with self._lock:
                    if self._slots[i] is not slot:
                        continue
                tok = next_ids[i]
                slot.length += 1           # last_token is now cached
                slot.last_token = tok
                slot.generated += 1
                slot.history.append(tok)
                self._decode_tokens += 1
                self._occupied_slot_steps += 1
                self._emit(slot, [tok])
                self._maybe_finish(i)

    def _step_spec(self):
        """One speculative verify step: draft k-1 tokens per slot (k-gram
        self-draft), score all k positions in ONE dispatch, and advance each
        slot by its accepted run + the target's correction/bonus token —
        1..k tokens per stream per dispatch.

        Slots that cannot take a whole verify step — within k of the cache
        cap (including in-flight streams a hot-swap just raised k under),
        or unable to claim the k-page lookahead from a dry pool — fall
        back to the single-token executable (:meth:`_step_plain` over just
        those rows) for this pass, so speculation NEVER changes what a
        stream emits: not its tokens, and not its truncation point."""
        from ..ops.speculative import propose_kgram

        clock = self._clock
        with clock.phase("decode_host"):
            cfg = self.cfg
            b = self.n_slots
            k = self.spec_k
            ids = np.zeros((b, k), np.int32)
            lengths = np.zeros(b, np.int32)
            seeds = np.zeros(b, np.uint32)
            tok_idx = np.zeros(b, np.uint32)
            temps = np.zeros(b, np.float32)
            finishes = []
            tail: List[int] = []
            prefilling: List[int] = []
            with self._lock:
                for i, slot in enumerate(self._slots):
                    if slot is None:
                        continue
                    if slot.request.cancelled:
                        finishes.append(self._retire_locked(i, "cancelled"))
                        continue
                    if slot.prefilling:
                        # mid-prefill: masked out of the verify dispatch (and
                        # NOT a tail row — nothing decodes until finalize)
                        prefilling.append(i)
                        continue
                    if slot.length + k > cfg.max_seq_len:
                        # tail regime: fewer than k positions remain (or a swap
                        # raised k mid-stream) — single-token path below; this
                        # row is masked out of the verify dispatch
                        tail.append(i)
                        continue
                    # grow: the verify step writes positions
                    # length .. length+k-1; allocate every page they span.
                    # A dry pool mid-lookahead is NOT a truncation — plain
                    # decode would only need the first of these pages — so the
                    # slot takes the single-token path this pass instead
                    # (pages already claimed stay; they back later positions)
                    first_pg = slot.length // cfg.page_size
                    last_pg = (slot.length + k - 1) // cfg.page_size
                    dry = False
                    for p in range(first_pg, last_pg + 1):
                        if self._table[i, p] != SCRATCH_PAGE:
                            continue
                        try:
                            (pg,) = self._alloc_pages(1)
                        except OutOfPages:
                            tail.append(i)
                            dry = True
                            break
                        self._table[i, p] = pg
                        slot.pages.append(pg)
                        self._note_pool_peak()
                    if dry:
                        continue
                    drafts = slot.pending_drafts
                    if drafts is None or len(drafts) != k - 1:
                        drafts = propose_kgram(slot.history, k - 1,
                                               self.spec_ngram)
                        slot.pending_drafts = drafts
                    ids[i, 0] = slot.last_token
                    ids[i, 1:] = drafts
                    lengths[i] = slot.length
                    seeds[i] = slot.request.seed
                    tok_idx[i] = slot.generated
                    temps[i] = slot.request.temperature
                table = self._table.copy()
                active = [i for i, s in enumerate(self._slots)
                          if s is not None and not s.prefilling]
            spec_rows = [i for i in active if i not in tail]
            for i in tail + prefilling:
                # scratch these rows' tables in the COPY: their verify-step
                # writes land in scratch, never past their table's end (tail)
                # and never into a half-prefilled prompt (prefilling)
                table[i, :] = SCRATCH_PAGE
            for fin in finishes:       # final-frame callbacks OUTSIDE the lock
                self._finish_cb(*fin)
            if not spec_rows:
                if tail:
                    self._step_plain(rows=tail)
                return
            self.decode_shapes.add((b, cfg.pages_per_slot, cfg.page_size, k))
            t0 = time.monotonic()
            accepted, tokens, draft_probs, self.cache = self._verify_fn(k)(
                self.params, self.cache, ids, lengths, table, seeds, tok_idx,
                temps)
            with clock.phase("decode_wait"):
                accepted = np.asarray(accepted)
                tokens = np.asarray(tokens)
                draft_probs = np.asarray(draft_probs)
            self.step_ema.observe(time.monotonic() - t0)
            self.steps += 1
            self.spec_steps += 1
            self._occupied_slot_steps += len(spec_rows)
            _GEN_STEPS.inc()
            _GEN_SPEC_STEPS.inc()
            _mw.sample("serving.decode")
        with clock.phase("emit"):
            for i in spec_rows:
                with self._lock:
                    slot = self._slots[i]
                if slot is None:
                    continue
                req = slot.request
                a = int(accepted[i])
                # emit the confirmed run + the correction/bonus, clipped at the
                # request budget / eos (any clip also satisfies _maybe_finish,
                # so a partially-consumed run always retires)
                emit: List[int] = []
                for tok in (int(tokens[i, j]) for j in range(a + 1)):
                    emit.append(tok)
                    if req.eos_id is not None and tok == req.eos_id:
                        break
                    if slot.generated + len(emit) >= req.max_new_tokens:
                        break
                slot.length += a + 1       # certain token + accepted drafts
                slot.last_token = emit[-1]
                slot.generated += len(emit)
                slot.history.extend(emit)
                slot.pending_drafts = None
                self._decode_tokens += len(emit)
                self.spec_drafted += k - 1
                self.spec_accepted += a
                _GEN_SPEC_TOKENS.labels(kind="drafted").inc(k - 1)
                _GEN_SPEC_TOKENS.labels(kind="accepted").inc(a)
                for j in range(min(a + 1, k - 1)):
                    _GEN_SPEC_ACCEPT_PROB.observe(float(draft_probs[i, j]))
                self._emit(slot, emit)
                self._maybe_finish(i)
                with self._lock:
                    slot = self._slots[i]
                if slot is not None:
                    # draft the NEXT proposals now: a slot preempted before its
                    # next verify parks carrying this pending draft state
                    with clock.phase("decode_host"):
                        slot.pending_drafts = propose_kgram(
                            slot.history, k - 1, self.spec_ngram)
        if tail:
            self._step_plain(rows=tail)

    def _emit(self, slot: _Slot, tokens: List[int]):
        now = time.perf_counter()
        req = slot.request
        meta: Dict[str, Any] = {"uri": req.uri}
        if req.last_emit_t is not None:
            _GEN_ITL.observe(now - req.last_emit_t)
        else:
            # first token of the stream: TTFT and its two legs (submit ->
            # leaving the backlog -> first token on the host), which also
            # ride the first frame's meta with the chunks spent (the bench's
            # drive() reads them there)
            req.first_token_t = now
            queue_s = req.dequeued_t - req.submitted_t
            prefill_s = now - req.dequeued_t
            _GEN_QUEUE_WAIT.labels(priority=req.priority).observe(queue_s)
            _GEN_PREFILL.labels(bucket=req.prefill_bucket).observe(prefill_s)
            _GEN_TTFT.labels(priority=req.priority).observe(
                queue_s + prefill_s)
            _tm.record_span("serving.gen.queue", req.submitted_t,
                            req.dequeued_t, remote=req.ctx, uri=req.uri,
                            priority=req.priority)
            meta["ttft_s"] = round(queue_s + prefill_s, 6)
            meta["chunks"] = slot.chunks
            meta["queue_wait_ms"] = round(queue_s * 1e3, 3)
            meta["prefill_wait_ms"] = round(prefill_s * 1e3, 3)
        req.last_emit_t = now
        self.tokens_generated += len(tokens)
        _GEN_TOKENS.labels(phase="decode").inc(len(tokens))
        cb = req.on_chunk
        if cb is not None:
            try:
                cb(tokens, False, meta)
            except Exception:   # a consumer bug must not poison the loop
                logger.exception("token-chunk callback failed for %s",
                                 req.uri)

    def _maybe_finish(self, slot_idx: int):
        fin = None
        with self._lock:
            slot = self._slots[slot_idx]
            if slot is None:
                return
            req = slot.request
            done = (req.cancelled
                    or slot.generated >= req.max_new_tokens
                    or (req.eos_id is not None
                        and slot.last_token == req.eos_id)
                    or slot.length + 1 > self.cfg.max_seq_len)
            if done:
                outcome = ("cancelled" if req.cancelled else
                           "truncated"
                           if (slot.generated < req.max_new_tokens
                               and (req.eos_id is None
                                    or slot.last_token != req.eos_id))
                           else "ok")
                fin = self._retire_locked(slot_idx, outcome)
        if fin is not None:
            self._finish_cb(*fin)

    def _retire_locked(self, slot_idx: int, outcome: str,
                       error: Optional[str] = None):
        """Free the slot's pages. Caller holds ``_lock`` and MUST invoke
        ``_finish_cb(*returned)`` after releasing it — the final-frame
        callback can block on broker backpressure, and blocking inside the
        lock would wedge ``active_slots()``/stats/metrics collectors."""
        slot = self._slots[slot_idx]
        self._slots[slot_idx] = None
        self._table[slot_idx, :] = SCRATCH_PAGE
        # refcounted release: exclusively-owned pages return to the free
        # list; shared prefix pages just drop this stream's reference (the
        # cache and/or sibling streams keep them alive)
        self.pool.release(slot.pages)
        slot.pages = []
        if slot.prefix_keys and self.prefix_cache is not None:
            self.prefix_cache.release_stream(slot.prefix_keys)
            slot.prefix_keys = []
        return (slot.request, [], outcome, error, slot.generated)

    def _finish_cb(self, req: _Request, tokens: List[int], outcome: str,
                   error: Optional[str] = None, n_tokens: int = 0,
                   retry_after_s: Optional[float] = None):
        self.requests_finished[outcome] = \
            self.requests_finished.get(outcome, 0) + 1
        _GEN_REQS.labels(outcome=outcome).inc()
        meta = {"uri": req.uri, "outcome": outcome, "n_tokens": n_tokens}
        if error:
            meta["error"] = error
        if retry_after_s is not None:
            # shed outcomes: the computed backoff rides the final frame so
            # HTTP/broker consumers can relay an honest Retry-After
            meta["retry_after_s"] = round(retry_after_s, 4)
        if req.first_token_t is not None:
            # the request's server-side timeline, retired now
            meta["timeline_s"] = {
                "queue": round(req.dequeued_t - req.submitted_t, 6),
                "prefill": round(req.first_token_t - req.dequeued_t, 6),
                "decode": round(req.last_emit_t - req.first_token_t, 6),
                "total": round(time.perf_counter() - req.submitted_t, 6)}
        if req.on_chunk is not None:
            with self._clock.phase("emit"):
                try:
                    req.on_chunk(tokens, True, meta)
                except Exception:   # a consumer bug must not poison the loop
                    logger.exception("final-frame callback failed for %s",
                                     req.uri)

    # ------------------------------------------------------------- hot swap

    def swap_params(self, params, version: Optional[str] = None,
                    spec=None) -> None:
        """Stage an atomic (target params, draft schedule) flip — the
        generation side of the PR-10 hot-swap contract: a publish carrying
        both new weights AND a new speculative schedule (``spec`` — a
        :class:`~analytics_zoo_tpu.ops.speculative.SpecDecodeConfig` or its
        dict form, e.g. the manifest's ``spec`` field) lands as ONE pair
        between decode steps; no step ever verifies new-model drafts with
        old weights or vice versa. In-flight streams continue (their
        pending proposals are re-drafted; the k-gram corpus survives). A
        spec flip to a new ``k`` lazily compiles exactly one more verify
        executable — the per-(k, slot-count) invariant holds.

        ``params`` is the tree as published; what is flipped in is its
        served tree (class docstring), built here, on the caller's (the
        staging) thread, so the loop's ``swap`` phase stays a pointer flip."""
        if spec is not None:
            from ..ops.speculative import SpecDecodeConfig

            if isinstance(spec, dict):
                spec = SpecDecodeConfig(**spec)
            elif not isinstance(spec, SpecDecodeConfig):
                raise TypeError(f"spec must be a SpecDecodeConfig or dict, "
                                f"got {type(spec).__name__}")
            self._refuse_for_slot_state(spec_k=spec.k)
        self._pending_swap = (self._serve_view(params), version, spec)
        self._wake.set()

    def host_params(self):
        """The tree being served, as host arrays — the retention hook
        :class:`~.hotswap.ModelSwapper` snapshots before a swap so
        ``rollback()`` can restore the pre-swap pair. It is the SERVED tree
        (class docstring), not the tree that was given: under a bf16 policy
        its matmul weights are bf16. Handing it back to :meth:`swap_params`
        serves it as it is (no leaf is wider than the compute dtype any
        more), so a swap then a rollback is stream-exact; it is not a copy
        of the published f32 weights."""
        import jax

        return jax.device_get(self.params)

    # ------------------------------------------------------------- diagnostics

    def check_decode_stability(self, mode: str = "warn",
                               hbm_budget_bytes: Optional[int] = None):
        """Run the decode graph checks over the traced decode step (no
        compile): ``decode-shape-stability`` (cache threads through with
        identical shapes, no host transfers, no per-step growth) plus the
        memory tier — ``cache-alias`` (the pool must be donated into the
        dispatch; tripped by ``donate_cache=False``) and, when a budget is
        declared, ``hbm-budget`` over the donation-aware static peak. Wired
        into ``ServingConfig.graph_checks`` warmup by
        :class:`GenerationEngine` alongside the fused-int8 check; the static
        peak is also noted into the memory witness so the CI gate can
        cross-check measured decode bytes against it."""
        import logging as _logging

        from ..analysis import enforce
        from ..analysis.rules.decode import lint_decode_stability

        budget = (hbm_budget_bytes if hbm_budget_bytes is not None
                  else self.hbm_budget_bytes)
        findings = self._pinned(
            lint_decode_stability,
            self.model, self.params, self.cfg, self.cache,
            top_k=self.top_k, spec_k=self.spec_k,
            chunk_tokens=self.prefill_chunk_tokens,
            where="serving.generation",
            donate_cache=self.donate_cache, hbm_budget_bytes=budget,
            note_static_site="serving.decode")
        return enforce(findings, mode,
                       _logging.getLogger("analytics_zoo_tpu.serving"))

    def _decode_args(self):
        """Avals of the ONE decode (or k-token verify) dispatch."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        b = self.n_slots
        sds = jax.ShapeDtypeStruct
        ids_aval = (sds((b, self.spec_k), jnp.int32) if self.spec_k >= 2
                    else sds((b,), jnp.int32))
        return (self.params, self.cache, ids_aval,
                sds((b,), jnp.int32), sds((b, cfg.pages_per_slot), jnp.int32),
                sds((b,), jnp.uint32), sds((b,), jnp.uint32),
                sds((b,), jnp.float32))

    def lower_decode(self):
        """``jax.stages.Lowered`` of the decode (or verify) dispatch the loop
        runs: for a caller that wants to read the program (StableHLO text
        and the kernels in it, memory analysis) rather than trust the
        routing."""
        dispatch = (self._verify_fn(self.spec_k) if self.spec_k >= 2
                    else self._decode)
        return dispatch.lower(*self._decode_args())

    def decode_memory(self) -> Dict[str, Any]:
        """Memory picture of the ONE decode executable, for the bench gate:
        the compiled buffer table (``alias_size_in_bytes`` is the donated
        pool showing up as an input→output alias) plus the static live-range
        peak under the actual donation flags AND with donation disabled —
        their difference is the second pool-sized buffer the ``cache-alias``
        rule exists to prevent."""
        import jax
        import jax.tree_util as jtu

        from ..analysis.memory import memory_fields, profile_jaxpr

        cfg = self.cfg
        spec = self.spec_k >= 2
        args = self._decode_args()
        fields = memory_fields(self.lower_decode().compile())
        step = (self.model.verify_step if spec else self.model.decode_step)
        closed = jax.make_jaxpr(
            lambda p, c, ids, ln, tb, sd, ti, tp: self._pinned(
                step,
                p, c, ids, ln, tb, sd, ti, tp, page_size=cfg.page_size,
                top_k=self.top_k))(*args)
        n_params = len(jtu.tree_leaves(self.params))
        cache_leaves = jtu.tree_leaves(self.cache)
        donated = ([False] * n_params
                   + [self.donate_cache] * len(cache_leaves) + [False] * 6)
        prof = profile_jaxpr(closed, donated_invars=donated)
        prof_undonated = profile_jaxpr(closed)
        return {
            "compiled": fields,
            "donate_cache": self.donate_cache,
            "cache_bytes": int(sum(int(l.nbytes) for l in cache_leaves)),
            "static_peak_bytes": prof.peak_live_bytes,
            "static_peak_bytes_undonated": prof_undonated.peak_live_bytes,
            "aliased_bytes": prof.aliased_out_bytes,
        }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            active = sum(s is not None for s in self._slots)
            prefilling = sum(s is not None and s.prefilling
                             for s in self._slots)
            preempted = len(self._preempted)
        out = {
            "slots": self.n_slots,
            "active_slots": active,
            "prefilling": prefilling,
            "preempted_parked": preempted,
            "backlog": len(self._backlog),
            "step_ema_s": round(self.step_ema.value(), 6),
            # the loop thread's seconds by exclusive phase since start (this
            # batcher's share of zoo_gen_loop_seconds_total)
            "loop_seconds": {p: round(v, 6)
                             for p, v in self._clock.seconds.items()},
            "free_pages": self.pool.free_count(),
            "page_capacity": self.pool.capacity,
            "steps": self.steps,
            # single-token decode launches: ahead = from the step in flight's
            # ids on the device, drained = from the host, by reason;
            # ahead / (ahead + drained) is the share that hid the host
            "decode_launches": {
                "ahead": self.launches_ahead,
                "drained": sum(self.launches_drained.values()),
                "drained_by": {r: n for r, n in self.launches_drained.items()
                               if n}},
            "tokens_generated": self.tokens_generated,
            "requests": dict(self.requests_finished),
            "loop_respawns": self.loop_respawns,
            "prefill_buckets": sorted(self.prefill_buckets),
            # bucket invariant: ONE decode shape ever traced (per spec k —
            # a schedule hot-swap legitimately adds its own entry)
            "distinct_decode_shapes": len(self.decode_shapes),
            # slot-occupancy: mean fraction of slots active per decode step
            # (the queue-wait-vs-decode-rate disambiguator in the bench)
            "slot_occupancy": round(
                self._occupied_slot_steps / (self.steps * self.n_slots), 4)
            if self.steps else 0.0,
            # decode tokens advanced per OCCUPIED slot-step: the dispatch-
            # amortization factor speculative decode multiplies (1.0 for
            # plain decode by construction; ~1 + acceptance*(k-1) in spec
            # mode), independent of host speed and stream-tail scheduling
            "tokens_per_slot_step": round(
                self._decode_tokens / max(self._occupied_slot_steps, 1), 4)
            if self._occupied_slot_steps else 0.0,
            "model_version": self.version,
            "swaps": self.swaps,
            # the served tree by leaf dtype: under a bf16 policy the matmul
            # weights read bfloat16 here, the rest float32
            "param_bytes": dict(self.param_bytes),
            # the decode cache by kind: pages, and a model's per-slot state
            # (recurrent, conv) where it keeps one
            "cache_bytes": self.cfg.bytes_by_kind(),
            # high-water mark of allocated (non-free) pool pages — the
            # sublinearity evidence for prefix sharing in the bench
            "peak_pages_in_use": self.peak_pages_in_use,
        }
        if self.prefix_cache is not None:
            out["prefix"] = dict(self.prefix_cache.stats(),
                                 tokens_saved=self.prefix_tokens_saved,
                                 shared_pages=self.pool.shared_count())
        if self.prefill_chunk_tokens:
            out["prefill"] = {
                "chunk_tokens": self.prefill_chunk_tokens,
                "chunks": self.prefill_chunks_total,
                # chunk-shape invariant: ONE compiled chunk executable per
                # (chunk_tokens, slot) — the bench/lint gate's counterpart
                # of distinct_decode_shapes
                "distinct_chunk_shapes": len(self.chunk_shapes),
                "chunk_ema_s": round(self.chunk_ema.value(), 6),
                "budget": (dict(self._last_budget)
                           if self._last_budget else None),
            }
        if self.spec_k >= 2 or self.spec_steps:
            out["spec"] = {
                "k": self.spec_k,
                "ngram": self.spec_ngram,
                "steps": self.spec_steps,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "acceptance_rate": round(
                    self.spec_accepted / self.spec_drafted, 4)
                if self.spec_drafted else 0.0,
                "tokens_per_step": round(
                    self.tokens_generated / self.steps, 3)
                if self.steps else 0.0,
            }
        return out


# ---------------------------------------------------------------------------
# broker-facing engine + client
# ---------------------------------------------------------------------------

def _itl_objective_target_s(cfg) -> Optional[float]:
    """The declared inter-token-latency objective's threshold (seconds), if
    any: a latency-type SLO objective whose name mentions ``itl`` arms the
    SLO-derived prefill budget (``qos.prefill_budget_from_slo``)."""
    for obj in getattr(cfg, "slo_objectives", ()) or ():
        if (str(obj.get("type", "")).lower() == "latency"
                and "itl" in str(obj.get("name", "")).lower()):
            return float(obj.get("threshold_ms", 1000.0)) / 1e3
    return None


class GenerationEngine:
    """Streaming generation job over the broker fabric.

    Consumes request payloads from ``generation_stream`` and streams token
    deltas as frame-per-chunk entries on ``genout:<uri>``:

        {"sid": uri, "seq": n, "tokens": [id, ...], "final": false}
        ...
        {"sid": uri, "seq": n, "tokens": [], "final": true,
         "outcome": "ok"|"error"|"cancelled"|"truncated", "n_tokens": N}

    Chunk writes ride a sink thread so the decode loop never blocks on a
    broker RTT; the sink sends all the frames that wait (a decode step hands
    over one a live stream) in one round trip (``XADDM``); a request is
    XACKed only after its final frame is durably in the broker
    (at-least-once, like the one-shot engine).

    ``params`` is handed to a :class:`ContinuousBatcher`, which serves from
    its *served tree* (there: matmul weights cast once to the policy's
    compute dtype) and keeps no reference to ``params`` itself: drop it after
    construction and the device holds the served tree and the page pool.
    ``stats()["param_bytes"]`` (republished to ``cli info``) says what that
    is, by dtype.
    """

    def __init__(self, model, params=None,
                 config: Optional[ServingConfig] = None,
                 group: str = "generation",
                 registry: Optional[HealthRegistry] = None,
                 stream: Optional[str] = None):
        self.config = config or ServingConfig()
        self.group = group
        # fleet mode: a replica consumes its own routed dispatch stream
        # (serving/fleet.py ReplicaRouter) instead of the shared one; the
        # per-request genout:* reply streams are unaffected by routing
        self._routed = stream is not None
        self.stream = stream or GEN_STREAM
        self.registry = registry if registry is not None else HealthRegistry(
            default_timeout_s=self.config.heartbeat_timeout_s)
        cfg = self.config
        if isinstance(model, ContinuousBatcher):
            self.batcher = model
        else:
            budget_mb = getattr(cfg, "hbm_budget_mb", None)
            self.batcher = ContinuousBatcher(
                model, params, n_slots=cfg.gen_slots,
                page_size=cfg.gen_page_size, max_seq_len=cfg.gen_max_seq_len,
                n_pages=cfg.gen_pages or None, top_k=cfg.gen_top_k,
                spec_k=getattr(cfg, "gen_spec_k", 0),
                spec_ngram=getattr(cfg, "gen_spec_ngram", 3),
                prefix_cache_pages=getattr(cfg, "gen_prefix_cache_pages", 0),
                prefix_block_tokens=getattr(cfg, "gen_prefix_block_tokens",
                                            0),
                prefill_chunk_tokens=getattr(cfg, "gen_prefill_chunk_tokens",
                                             0),
                prefill_token_budget=getattr(cfg,
                                             "gen_prefill_token_budget", 0),
                prefill_slo_itl_s=_itl_objective_target_s(cfg),
                hbm_budget_bytes=int(budget_mb * 2 ** 20) if budget_mb
                else None,
                graph_checks=None, autostart=False)
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._sink_q: "queue.Queue" = queue.Queue(maxsize=1024)
        self._sink_clock = _LoopClock(_GEN_SINK_SECONDS, SINK_PHASES,
                                      "serving.gen.sink.", "sink",
                                      quiet=("idle",))
        self._source_clock = _LoopClock(_GEN_SOURCE_SECONDS, SOURCE_PHASES,
                                        "serving.gen.source.", "source",
                                        quiet=("poll", "stats"))
        self.served_streams = 0
        self.frames_written = 0
        self.emit_blocked_s = 0.0
        _LIVE_ENGINES.add(self)

    def _connect(self, tag: str) -> _Conn:
        policy = RetryPolicy(max_attempts=None, base_delay_s=0.05,
                             max_delay_s=0.5, attempt_timeout_s=5.0,
                             retryable=(ConnectionError, OSError))
        return _Conn(self.config.queue_host, self.config.queue_port,
                     policy=policy, abort=self._stop.is_set, tag=tag)

    def _warm(self):
        """Startup decode-graph check (``ServingConfig.graph_checks``): the
        traced decode step must be shape-stable, host-transfer-free, and
        pool-donating (``cache-alias``; plus ``hbm-budget`` under a declared
        ``hbm_budget_mb``) BEFORE the job takes traffic — the decode analog
        of the one-shot engine's fused-int8 warmup check."""
        checks = getattr(self.config, "graph_checks", "warn")
        if not checks or checks == "off":
            return
        try:
            self.batcher.check_decode_stability(checks)
        except Exception:
            if checks == "raise":
                raise
            logger.exception("decode-shape-stability check failed; "
                             "serving anyway (graph_checks=warn)")

    def start(self) -> "GenerationEngine":
        self._stop.clear()
        self._warm()
        self.batcher.start()
        conn = self._connect("gen.control")
        try:
            # shared stream: tail semantics (see ClusterServing.start). A
            # routed per-replica stream is private to this engine and the
            # router may have forwarded before this call lands — replay
            # from '0' so nothing dispatched early is skipped
            conn.call("XGROUPCREATE", self.stream, self.group,
                      "0" if self._routed else "$")
        except RetryAbortedError:
            pass
        finally:
            conn.close()
        for name, fn in (("source", self._source_loop),
                         ("sink", self._sink_loop)):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"zoo-gen-{name}")
            t.start()
            self._threads.append(t)
        return self

    def _source_loop(self):
        conn = self._connect("gen.source")
        hb = self.registry.register("serving.gen.source")
        clock = self._source_clock
        clock.begin()
        stats_pub = 0.0
        try:
            while not self._stop.is_set():
                hb.beat()
                now = time.time()
                try:
                    if now - stats_pub >= 1.0:
                        stats_pub = now
                        with clock.phase("stats"):
                            conn.call("HSET", GEN_STATS_PREFIX + self.group,
                                      dict(self.stats(), ts=now))
                    with clock.phase("poll"):
                        entries = conn.call("XREADGROUP", self.stream,
                                            self.group, 8, 200)
                except RetryAbortedError:
                    break
                for entry_id, payload in entries or ():
                    with clock.phase("admit"):
                        self._admit_entry(entry_id, payload)
                clock.close_pass()
        finally:
            clock.close_pass()
            hb.stop()
            conn.close()

    def _admit_entry(self, entry_id: str, payload: Any):
        ctx = payload_trace(payload)
        sent_at = payload_sent_at(payload)
        if sent_at is not None:
            # across machines the two wall clocks may disagree: a negative
            # reading is no reading
            ingress_s = time.time() - sent_at
            if ingress_s >= 0:
                _GEN_INGRESS.observe(ingress_s)
        # resolve the reply stream FIRST: a payload with a good uri but a
        # bad field (max_new_tokens="abc") must get its error frame on the
        # stream the client is actually polling
        uri = (payload.get("uri") if isinstance(payload, dict) else None) \
            or str(payload)[:64]
        if isinstance(payload, dict) and payload.get("cancel"):
            # client-sent cancel frame: stop decoding for an abandoned
            # stream (the stream's own final frame reports "cancelled");
            # the cancel entry itself just needs acking
            self.batcher.cancel_uri(uri)
            self._sink_q.put(("ack", entry_id, uri, 0, [], {}, False, None,
                              None))
            return
        try:
            prompt = np.asarray(payload["prompt"], np.int32).reshape(-1)
            kw = dict(
                max_new_tokens=int(payload.get("max_new_tokens", 32)),
                temperature=float(payload.get("temperature", 0.0)),
                seed=int(payload.get("seed", 0)),
                eos_id=(int(payload["eos_id"])
                        if payload.get("eos_id") is not None else None),
                # overload QoS rides the payload (durable across AOF replay
                # and failover requeue); absent from old clients
                priority=payload_priority(payload),
                deadline=payload_deadline(payload))
        except Exception as e:
            logger.exception("malformed generation request %s", entry_id)
            self._sink_q.put(("chunk", entry_id, uri, 0, [],
                              {"outcome": "error",
                               "error": f"malformed request: {e}"}, True,
                              ctx, time.perf_counter()))
            return
        seq_counter = [0]
        t0 = time.perf_counter()

        def on_chunk(tokens, final, meta, _uri=uri, _eid=entry_id, _ctx=ctx):
            seq = seq_counter[0]
            seq_counter[0] += 1
            if final:
                meta = dict(meta)
                meta.setdefault("outcome", "ok")
                _tm.record_span("serving.gen.stream", t0, time.perf_counter(),
                                remote=_ctx, uri=_uri,
                                n_tokens=meta.get("n_tokens", 0))
            # the last field is the instant the frame was handed over:
            # zoo_gen_egress_seconds runs from here to its XADD returning
            item = ("chunk", _eid, _uri, seq, list(tokens),
                    meta if final else {}, final, _ctx, time.perf_counter())
            try:
                self._sink_q.put_nowait(item)
            except queue.Full:          # back-pressure: the sink is behind
                self._sink_q.put(item)
                blocked = time.perf_counter() - item[-1]
                _GEN_EMIT_BLOCKED.inc(blocked)
                self.emit_blocked_s += blocked

        try:
            self.batcher.submit(prompt, uri=uri, on_chunk=on_chunk,
                                ctx=ctx, **kw)
        except Exception as e:   # invalid prompt (too long, empty)
            self._sink_q.put(("chunk", entry_id, uri, 0, [],
                              {"outcome": "error", "error": str(e)}, True,
                              ctx, time.perf_counter()))

    def _sink_loop(self):
        conn = self._connect("gen.sink")
        hb = self.registry.register("serving.gen.sink")
        clock = self._sink_clock
        clock.begin()
        try:
            while True:
                hb.beat()
                try:
                    try:
                        items = [self._sink_q.get_nowait()]
                    except queue.Empty:     # only a wait is idle time
                        with clock.phase("idle"):
                            items = [self._sink_q.get(timeout=0.1)]
                    # a decode step hands over a frame a live stream at
                    # once: whatever waits goes out in the same round trip
                    while len(items) < self._sink_q.maxsize:
                        try:
                            items.append(self._sink_q.get_nowait())
                        except queue.Empty:
                            break
                    self._write(conn, items)
                except queue.Empty:
                    if self._stop.is_set():
                        break
                except RetryAbortedError:
                    break
                clock.close_pass()
        finally:
            clock.close_pass()
            hb.stop()
            conn.close()

    def _write(self, conn: _Conn, items: List[Tuple]) -> None:
        """One turn of the sink: the frames of ``items``, just taken off the
        queue in order, to their reply streams in ONE round trip (``XADDM``),
        and the requests acknowledged whose final frame is then in the
        broker. A round trip a frame is a millisecond of the interpreter a
        token a stream, and a wake-up of every blocked reader: with most
        slots live that, and not the chip, bounds what a deployment carries
        (PERF.md section 6, PR 43)."""
        t_taken = time.perf_counter()
        clock = self._sink_clock
        frames, acks = [], []
        with clock.phase("build"):
            for kind, entry_id, uri, seq, tokens, meta, final, ctx, _ in items:
                if kind == "ack":       # cancel frames carry no reply
                    acks.append(entry_id)
                    continue
                # token ids as plain ints: a frame without arrays rides the
                # wire as JSON, which costs the broker and the reader far
                # less than a binary frame of a token; the client makes the
                # array
                frame = {"sid": uri, "seq": seq,
                         "tokens": np.asarray(tokens, np.int32).tolist(),
                         "final": bool(final)}
                if final:
                    frame.update({k: v for k, v in meta.items()
                                  if k in ("outcome", "error", "n_tokens",
                                           "retry_after_s")})
                    acks.append(entry_id)
                if ctx is not None:
                    frame[TRACE_KEY] = ctx
                frames.append((GEN_OUT_PREFIX + uri, frame))
        if frames:
            with clock.phase("xadd"):
                conn.call("XADDM", frames)
            t_done = time.perf_counter()
            for kind, _, uri, seq, _, _, final, ctx, t_handed in items:
                if kind == "ack":
                    continue
                which = "first" if seq == 0 else "final" if final else "next"
                _GEN_EGRESS.observe(t_done - t_handed)
                _EGRESS_QUEUED[which].observe(t_taken - t_handed)
                if which != "next":
                    # the two frames a client waits for close the request's
                    # trace; a span a token would push the traces out of the
                    # recorder
                    _tm.record_span("serving.gen.egress", t_handed, t_done,
                                    remote=ctx, uri=uri, frame=which,
                                    queued_s=round(t_taken - t_handed, 6))
                self.served_streams += bool(final)
            self.frames_written += len(frames)
        if acks:
            with clock.phase("ack"):
                conn.call("XACK", self.stream, self.group, acks)

    def stats(self) -> Dict[str, Any]:
        out = {"served_streams": self.served_streams,
               # the sink thread's seconds by exclusive phase since start
               # (this engine's share of zoo_gen_sink_seconds_total), the
               # frames they wrote, and the back-pressure pair: frames
               # waiting now, and how long a full queue has blocked emit
               "sink": {"seconds": {p: round(v, 6) for p, v in
                                    self._sink_clock.seconds.items()},
                        "frames": self.frames_written,
                        "queue_depth": self._sink_q.qsize(),
                        "emit_blocked_s": round(self.emit_blocked_s, 6)}}
        out.update(self.batcher.stats())
        return out

    def stop(self, drain_s: float = 1.0):
        deadline = time.time() + drain_s
        while time.time() < deadline and (self.batcher.active_slots()
                                          or not self._sink_q.empty()):
            time.sleep(0.01)
        # close the batcher BEFORE signalling stop: closing fails whatever is
        # still pending/active, and those final error frames must land on
        # _sink_q while the sink loop is still guaranteed to drain it (the
        # sink only exits on stop-AND-empty)
        self.batcher.close()
        drain2 = time.time() + drain_s
        while time.time() < drain2 and not self._sink_q.empty():
            time.sleep(0.01)
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)
        self._threads.clear()


class GenerationClient:
    """Producer/consumer for broker-backed generation streams."""

    def __init__(self, host: str = "127.0.0.1", port: int = 6380,
                 policy: Optional[RetryPolicy] = None):
        from .client import default_conn_policy

        self._conn = _Conn(host, port,
                           policy=policy or default_conn_policy(),
                           tag="client.gen")

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               uri: Optional[str] = None,
               priority: Optional[str] = None,
               deadline_ms: Optional[float] = None,
               deadline: Optional[float] = None) -> str:
        """Enqueue one generation request; returns its stream id.
        ``priority``/``deadline_ms`` (or absolute ``deadline``) arm
        (priority, deadline)-ordered admission and deadline shedding at the
        decode tier — a shed stream's final frame reports outcome ``shed``
        with a computed ``retry_after_s``."""
        uri = uri or uuid.uuid4().hex
        dl = _qos.normalize_deadline(deadline)
        if dl is None:
            dl = _qos.deadline_from_ms(deadline_ms)
        with _tm.span("serving.gen.send", uri=uri) as sp:
            payload = {"uri": uri, TRACE_KEY: sp.wire_context(),
                       SENT_KEY: time.time(),
                       "prompt": np.asarray(prompt, np.int32).reshape(-1),
                       "max_new_tokens": int(max_new_tokens),
                       "temperature": float(temperature), "seed": int(seed),
                       "eos_id": int(eos_id) if eos_id is not None else None}
            if priority is not None:
                payload[PRIORITY_KEY] = _qos.normalize_priority(priority)
            if dl is not None:
                payload[DEADLINE_KEY] = dl
            self._conn.call("XADD", GEN_STREAM, payload)
        return uri

    def cancel(self, uri: str) -> None:
        """Ask the engine to stop decoding ``uri`` (abandoned stream): the
        request's own final frame will report outcome ``cancelled``."""
        self._conn.call("XADD", GEN_STREAM, {"uri": uri, "cancel": True})

    def stream(self, uri: str, timeout_s: float = 60.0):
        """Yield token chunks (int32 ndarrays) for ``uri`` until the final
        frame; raises on an errored stream. Frame-per-chunk; chunks
        reassemble in ``seq`` order (the broker stream is ordered). The per-request broker stream is deleted after its
        terminal frame is consumed (the streaming twin of OutputQueue's
        HDEL-after-query), so finished streams don't accumulate broker
        state."""
        cursor = 0
        deadline = time.monotonic() + timeout_s
        stream_key = GEN_OUT_PREFIX + uri
        while True:
            block = max(1, min(500, int((deadline - time.monotonic()) * 1e3)))
            cursor, entries = self._conn.call("XREAD", stream_key, cursor,
                                              64, block)
            for _id, frame in entries:
                toks = np.asarray(frame.get("tokens", ()), np.int32)
                if toks.size:
                    yield toks
                if frame.get("final"):
                    try:
                        self._conn.call("XDELSTREAM", stream_key)
                    except Exception:   # cleanup is best-effort
                        pass
                    if frame.get("outcome") == "shed":
                        raise _qos.ShedError(
                            f"generation request {uri!r} shed: "
                            f"{frame.get('error', 'overloaded')}",
                            retry_after_s=float(
                                frame.get("retry_after_s", 1.0)),
                            reason="deadline")
                    if frame.get("error") or frame.get("outcome") == "error":
                        raise RuntimeError(
                            f"generation failed for {uri!r}: "
                            f"{frame.get('error', 'unknown error')}")
                    return
            if time.monotonic() >= deadline:
                raise TimeoutError(f"no final frame for {uri!r} within "
                                   f"{timeout_s}s")

    def generate(self, prompt, timeout_s: float = 60.0, **kw) -> List[int]:
        uri = self.submit(prompt, **kw)
        out: List[int] = []
        for chunk in self.stream(uri, timeout_s=timeout_s):
            out.extend(chunk.tolist())
        return out

    def close(self):
        self._conn.close()


__all__ = ["ContinuousBatcher", "GenerationClient", "GenerationEngine",
           "GEN_OUT_PREFIX", "GEN_STATS_PREFIX", "GEN_STREAM",
           "StreamHandle"]
