"""Iteration-level continuous batching: paged KV-cache pool, admit/retire
scheduler, and replica fan-out.

PR 8's :class:`~deeplearning4j_tpu.remote.serving.BucketedExecutor` runs a
whole ``generate()`` per coalesced group — one slow long prompt holds its
batch hostage and occupancy collapses under ragged arrivals (ROADMAP
item 1).  This module schedules at the DECODE-STEP boundary instead, the
way ``SharedTrainingMaster``'s gradient sharing kept every training
replica busy:

- :class:`KVCachePool` — fixed-size pages over ONE preallocated device
  buffer per model, with per-slot page tables.  Admitting or retiring a
  sequence is a host-side free-list edit; the decode executable's shapes
  (slots x page-table width x pool) never change, so churn never
  re-traces (``nn/conf/attention.py paged_attention`` is the device-side
  math: on a TPU a kernel that reads each slot's live pages where they
  lie, elsewhere a gather of every slot's capacity under a mask).
- :class:`ContinuousBatcher` — the iteration-level scheduler: a fixed
  slot array steps through ONE shared decode executable; finished
  sequences retire and queued ones admit BETWEEN steps (strict-FIFO
  admission, so no request starves behind later arrivals), each new
  token streams back to the waiting client as its step completes, and a
  pool squeeze preempts the youngest slot (restart-with-skip) instead of
  wedging.  What it calls of a model is what
  :class:`~deeplearning4j_tpu.nlp.served.ServedLM` builds: ``prefillRaw``,
  the step, the admission write.
- :class:`ReplicaSet` — fan-out behind one
  :class:`~deeplearning4j_tpu.remote.serving.ModelRegistry` route:
  each replica is its own executor whose weights are placed by
  ``parallel.meshtrainer.apply_inference_plan`` (TP-serve a model
  partitioned over several chips, per arXiv:2004.13336's sharded-state
  discipline) or ``place_replica`` (DP-serve small ones, one chip
  each); requests route to the least-loaded replica, and
  ``armAutoscale`` scales the set one replica up/down on the
  ``serving_queue_depth`` alert's firing/resolved edges.

Compile discipline: every executable (per-bucket prefill + pool write,
the decode step) is warmed at ``start()``; admit/retire churn in steady
state must hold the jit-miss counter FLAT.  Pool or plan changes pop
every cached step fn and rebuild fresh closures — JAX's jaxpr cache keys
on function identity + avals, so a reused closure could resurrect the old
layout's traced constraints.
"""
from __future__ import annotations

import functools
import math
import queue as _stdqueue
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# injection registries only — fault/chaos.py imports THIS module lazily,
# so the package-level import here cannot cycle
from deeplearning4j_tpu.fault import injection as _inj
from deeplearning4j_tpu.nn.conf.attention import (CacheSpec,
                                                  paged_kernel_kv_passes,
                                                  paged_kernel_lowerings,
                                                  sparse_in_place_lowerings)
from deeplearning4j_tpu.nlp.mamba import ssd_step_kernel_lowerings
from deeplearning4j_tpu.parallel.moe import (moe_grouped_kernel_lowerings,
                                             moe_step_kernel_lowerings)
from deeplearning4j_tpu.remote.serving import (AdmissionControl,
                                               BucketLadder,
                                               DeadlineExceeded,
                                               NoHealthyReplicas,
                                               ServiceOverloaded)
from deeplearning4j_tpu.telemetry import (SERVING_LOOP_PHASES,
                                          RequestContext, ThresholdRule,
                                          current_context, flight_recorder,
                                          gc_pause_seconds, get_registry,
                                          observe_exemplar,
                                          register_thread_role,
                                          serving_metrics, timeline_store,
                                          tracer)

__all__ = ["KVCachePool", "ContinuousBatcher", "ReplicaSet"]


_PROBE_FN = None

#: an idle stretch of the device this long is also a Chrome event
#: (``serving.device.idle``); a loop phase, or a stretch the device was
#: starved for, this long is a stall and leaves ``serving.loop.stall``
_IDLE_EVENT_SECONDS = 0.001
_STALL_SECONDS = 0.1
# a streamed token that waited this long between the loop's put and its
# consumer's get is also a Chrome event (at 64 slots a lower bar would put
# an event a token into the tracer's ring, under its lock, from 64 threads)
_QUEUED_EVENT_SECONDS = 0.01
# a stream's consumer adds its hand-off sums to the registry this often
_HANDOFF_FLUSH_TOKENS = 32
# the roles dl4j_tpu_process_thread_cpu_seconds_total books this module's
# threads under: the loop (cbatch-<name>) and the replicas' health probes
register_thread_role("cbatch-", "serving_loop")
register_thread_role("replica-probe-", "telemetry")
register_thread_role("probe-once-", "telemetry")


def _cpu_clock_read_seconds() -> float:
    """What one read of the calling thread's CPU clock costs on this
    host: the least of five batches of twenty (a batch that was preempted
    reads high)."""
    best = math.inf
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(20):
            time.thread_time()
        best = min(best, (time.perf_counter() - t0) / 20)
    return best


class _LoopClock:
    """The loop thread's second clock: when its phases read the thread's
    CPU clock, and what is made of the readings.

    A read costs 0.3 us where the kernel serves it and 6 us alone, 17
    among the server's threads, behind a sandbox (gVisor, the benchmark's
    host, PR 50), where sixteen reads an iteration cost a 64-slot loop
    0.27 ms a step: so the clock is read in one iteration of ``every``,
    chosen from what a read costs here so that the reads come to about a
    microsecond an iteration (every iteration where a read costs that or
    less); the phases of the other iterations observe their wall time
    alone.

    Where the CPU clock moves a scheduler tick at a time (10 ms on that
    host), a phase of a millisecond reads 0 or a whole tick: what a phase
    read beyond its own wall time is kept as that phase's ``credit`` and
    set against its next observations, so that a phase's wall less its
    off-CPU seconds add up to the CPU seconds read in it.  With a clock
    that counts nanoseconds the credit stays 0."""

    def __init__(self):
        self.every = min(16, max(1, math.ceil(
            _cpu_clock_read_seconds() / 1e-6)))
        self.on = True
        self._iterations = 0
        self._credit = dict.fromkeys(SERVING_LOOP_PHASES, 0.0)

    def iteration(self) -> None:
        self._iterations += 1
        self.on = self._iterations % self.every == 0

    def offcpu(self, phase: str, seconds: float, cpu: float) -> float:
        """Seconds of a phase of ``seconds`` that its thread did not run
        for, given that its CPU clock gained ``cpu`` in it."""
        credit = self._credit[phase] + cpu
        ran = min(seconds, credit)
        self._credit[phase] = credit - ran
        return seconds - ran


def _probe_fn():
    """Process-wide tiny jitted dispatch for replica health probes —
    compiled ONCE outside every batcher's ``compileCacheSize``
    accounting, so probing never moves the steady-state jit-miss
    counter (the flat-across-churn invariant)."""
    global _PROBE_FN
    if _PROBE_FN is None:
        _PROBE_FN = jax.jit(lambda x: x + 1)
    return _PROBE_FN


class KVCachePool:
    """The cache memory of one served model, as its ``cacheSpec()``
    (:class:`~deeplearning4j_tpu.nn.conf.attention.CacheSpec`) names it:
    three kinds of state side by side, device buffers in ``arrays`` and
    the host-side bookkeeping here.

    - *paged*: ``k``/``v`` ``(pagedLayers, numPages, pageSize,
      spec.rowWidth)`` plus a free list and per-slot page tables.
      Pages are token-major (a row is one position, all heads side by
      side): the two minor dimensions are what the TPU tiles without a
      re-layout, so the decode step and the prefill write update them in
      place and the step's kernel reads a page as it lies, all heads of
      a row at once (``paged_attention``).  Only the layers that own
      pages have any: 48 of 48 for GPT-2 XL, one of 32 for a SambaY
      stack.  A model whose rows are latent (``spec.latentWidth``) has
      ONE such array and no ``v``: keys and values are read from the same
      row (``paged_latent_attention``).
    - *ring*: ``ringK``/``ringV`` ``(ringLayers, maxSlots, ringRows,
      spec.rowWidth)``, a slot's last ``ringRows`` positions written
      modulo ``ringRows``, counted from the sequence's first real token
      (``(p - start) % ringRows``), so that the live rows are always the
      first ``min(pos - start + 1, ringRows)`` and a step can read a ring
      as fixed pages of its slot.
    - *recurrent*: one array ``(layers, maxSlots, ...)`` for each entry
      of ``spec.slotState``, overwritten every step.

    ``arrays`` is the tuple the step and the admission write take and
    return, in the order of ``spec.arrayKinds``: ``(k, v[, index][,
    ringK, ringV][, *slotState])`` — ``(k, v)`` when every layer is paged,
    ``(rows[, *slotState])`` for latent rows.  ``index``
    ``(pagedLayers, numPages, pageSize, spec.indexRowWidth)`` is there
    for a model whose attention selects its rows (``spec.indexWidth``):
    the same pages, free list and page tables address it, so allocating,
    freeing and replaying a sequence cover it with nothing added.

    Page 0 is the SCRATCH page: inactive slots' table entries point at
    it, so the fixed-shape decode step can write their (ignored) K/V
    somewhere harmless without a gather/scatter shape ever depending on
    how many slots are live.  Ring rows and recurrent state have no
    scratch: the step leaves them as they are for a slot whose ``pos``
    is 0, admission overwrites the recurrent state whole, and which
    ring rows are live follows from ``pos`` and ``start`` alone, so a
    reused slot's stale rows are never valid.  ``ensure``/``release`` are
    plain list edits — allocation never reallocates device memory and
    never changes an executable shape.
    """

    def __init__(self, nLayers: int, nHeads: int, headSize: int,
                 pageSize: int = 8, numPages: int = 64, maxSlots: int = 4,
                 maxPagesPerSeq: int = 8, dtype=jnp.float32,
                 sharding=None, spec: Optional[CacheSpec] = None,
                 slotSharding=None):
        self.spec = spec if spec is not None else CacheSpec(
            int(nLayers), int(nHeads), int(headSize), dtype)
        spec = self.spec
        self.pageSize = int(pageSize)
        self.numPages = int(numPages)
        self.maxSlots = int(maxSlots)
        self.maxPagesPerSeq = int(maxPagesPerSeq)
        if self.numPages < self.maxPagesPerSeq + 1:
            # invariant the preemption path relies on: a LONE sequence
            # always fits once everything else is evicted
            raise ValueError(
                f"numPages={self.numPages} must exceed maxPagesPerSeq="
                f"{self.maxPagesPerSeq} (page 0 is reserved scratch)")

        def zeros(shape, dt, sh):
            a = jnp.zeros(shape, dt)
            return a if sh is None else jax.device_put(a, sh)
        paged = (spec.pagedLayers, self.numPages, self.pageSize,
                 spec.rowWidth)
        rows = {"paged": paged,
                # one key for every index head: no head to split it by
                "index": paged[:3] + (spec.indexRowWidth,),
                "ring": (spec.ringLayers, self.maxSlots, spec.ringRows,
                         spec.rowWidth)}
        kinds = spec.arrayKinds             # the slot state comes last
        shapes = [(rows[k], spec.dtype) for k in kinds if k != "slot"] + [
            ((shape[0], self.maxSlots) + tuple(shape[1:]), dt)
            for _name, shape, dt in spec.slotState]
        self._arrays = tuple(
            zeros(shape, dt, sharding if kind == "paged" else slotSharding)
            for kind, (shape, dt) in zip(kinds, shapes))
        self.pageTable = np.zeros((self.maxSlots, self.maxPagesPerSeq),
                                  np.int32)
        self._free = deque(range(1, self.numPages))
        self._held: List[List[int]] = [[] for _ in range(self.maxSlots)]
        itemsize = jnp.dtype(spec.dtype).itemsize
        rowBytes = spec.rowWidth * itemsize
        #: bytes of each kind per live unit: a page (K and V, or the one
        #: latent row), a page's index rows, a ring row (all ring layers,
        #: K and V), a slot's recurrent state
        self.pageBytes = spec.pagedLayers * self.pageSize \
            * spec.pagedPools * rowBytes
        self.indexPageBytes = spec.pagedLayers * self.pageSize \
            * spec.indexRowWidth * itemsize
        self.ringRowBytes = spec.ringLayers * 2 * rowBytes
        self.slotStateBytes = sum(
            int(np.prod(shape)) * jnp.dtype(dt).itemsize
            for _name, shape, dt in spec.slotState)

    @classmethod
    def forSpec(cls, spec: CacheSpec, pageSize: int, numPages: int,
                maxSlots: int, maxPagesPerSeq: int, sharding=None,
                slotSharding=None) -> "KVCachePool":
        return cls(spec.pagedLayers, spec.kvHeads, spec.headSize, pageSize,
                   numPages, maxSlots, maxPagesPerSeq, spec.dtype, sharding,
                   spec=spec, slotSharding=slotSharding)

    @property
    def arrays(self) -> tuple:
        return self._arrays

    @arrays.setter
    def arrays(self, arrays) -> None:
        self._arrays = tuple(arrays)

    # the paged buffers under the names they have always had
    @property
    def k(self):
        return self.arrays[0]

    @k.setter
    def k(self, a) -> None:
        self.arrays = (a,) + self.arrays[1:]

    @property
    def v(self):
        return self.arrays[1]

    @v.setter
    def v(self, a) -> None:
        self.arrays = self.arrays[:1] + (a,) + self.arrays[2:]

    def stateSlots(self) -> int:
        """Slots that hold a sequence's state (pages, ring rows,
        recurrent state): from ``ensure`` to ``release``."""
        return sum(1 for held in self._held if held)

    def ringRowsFor(self, lengths) -> int:
        """Ring rows that are live for sequences of ``lengths``
        positions: each holds its last ``ringRows`` at most."""
        # jaxlint: disable=host-sync -- lengths are the scheduler's host-side slot positions
        return int(np.minimum(lengths, self.spec.ringRows).sum())

    def freePages(self) -> int:
        return len(self._free)

    def usedPages(self) -> int:
        return (self.numPages - 1) - len(self._free)

    def pagesFor(self, tokens: int) -> int:
        # jaxlint: disable=host-sync -- token counts are Python ints (host bookkeeping), never device scalars
        return -(-int(tokens) // self.pageSize)

    def capacityTokens(self) -> int:
        return self.maxPagesPerSeq * self.pageSize

    def heldIds(self, slot: int) -> List[int]:
        return list(self._held[slot])

    def ensure(self, slot: int, upTo: int) -> bool:
        """Grow ``slot``'s allocation to cover positions ``[0, upTo)``.
        False when the free list (or the per-sequence table width)
        can't — the scheduler then preempts or defers."""
        want = self.pagesFor(upTo)
        if want > self.maxPagesPerSeq:
            return False
        held = self._held[slot]
        need = want - len(held)
        if need <= 0:
            return True
        if need > len(self._free):
            return False
        for _ in range(need):
            pid = self._free.popleft()
            self.pageTable[slot, len(held)] = pid
            held.append(pid)
        return True

    def release(self, slot: int) -> int:
        """Free every page ``slot`` holds; returns how many."""
        held = self._held[slot]
        n = len(held)
        self._free.extend(held)
        held.clear()
        self.pageTable[slot, :] = 0
        return n


class _Pending:
    """One client request: its rows fan out to sequences; results
    reassemble when the last row retires.  Completion bookkeeping uses a
    PER-REQUEST lock, not a per-batcher one: after a failover the rows
    of one request can retire on DIFFERENT replicas concurrently."""
    __slots__ = ("rows", "quota", "doneRows", "error", "event", "t0",
                 "deadline", "lock", "ctx", "firstTokenAt")

    def __init__(self, rows: int, quota: int,
                 deadline: Optional[float] = None,
                 ctx: Optional[RequestContext] = None):
        self.rows = int(rows)
        self.quota = int(quota)
        self.doneRows = 0
        self.error: Optional[BaseException] = None
        self.event = threading.Event()
        self.t0 = time.perf_counter()
        self.deadline = deadline        # absolute time.monotonic(), or None
        self.lock = threading.Lock()
        # request-scoped observability: ONE context for the request's
        # whole life, shared by every row and surviving failover hops
        self.ctx = ctx
        self.firstTokenAt: Optional[float] = None   # TTFT observed once


class _Seq:
    """One sequence of a request: queued, then bound to a decode slot."""
    __slots__ = ("tokens", "realLen", "bucket", "quota", "pages", "parent",
                 "row", "emitted", "streamQ", "streamed", "streamSkip",
                 "cancelled", "restarts", "deadline", "forced", "ctx",
                 "enqT", "lastTokT")

    def __init__(self, tokens: np.ndarray, bucket: int, quota: int,
                 pages: int, parent: _Pending, row: int,
                 deadline: Optional[float] = None):
        self.tokens = tokens            # (1, realLen) int32
        self.realLen = int(tokens.shape[1])
        self.bucket = int(bucket)
        self.quota = int(quota)
        self.pages = int(pages)
        self.parent = parent
        self.row = int(row)
        self.emitted: List[int] = []
        self.streamQ: Optional[_stdqueue.SimpleQueue] = None
        self.streamed = 0               # tokens pushed to the stream, ever
        self.streamSkip = 0             # re-emissions to swallow after a preempt
        self.cancelled = False
        self.restarts = 0
        self.deadline = deadline        # absolute time.monotonic(), or None
        # the already-computed token prefix, teacher-forced during a
        # replay so the prefix a client sees never depends on bit-wise
        # reproducibility across the replica that adopts the sequence
        self.forced: List[int] = []
        self.ctx = parent.ctx           # the request's one trace context
        self.enqT: Optional[float] = None    # perf_counter at last enqueue
        self.lastTokT: Optional[float] = None  # last FRESH token's time


class _Flight:
    """One dispatched decode step whose tokens the host has not read yet:
    the step's output on the device, the slots that took part, the
    sequence each of them held at dispatch and its length after the
    step.  A token is delivered by that identity, never by slot number:
    by the time it is read the slot may be empty or hold a newcomer."""
    __slots__ = ("greedy", "slots", "seqs", "lengths")

    def __init__(self, greedy, slots: List[int],
                 seqs: List[Optional[_Seq]], lengths: np.ndarray):
        self.greedy = greedy            # (S, tq) int32, on the device
        self.slots = slots
        self.seqs = seqs                # by slot; None where it sat out
        self.lengths = lengths          # by position in ``slots``


def _finish_seq(seq: _Seq, error: Optional[BaseException],
                model: str) -> None:
    """Deliver a sequence's final verdict to its request.  Module-level
    (not a batcher method) because after a failover the finishing
    replica is not the admitting one — and the replica set itself
    finishes orphans when no survivor can adopt them."""
    parent = seq.parent
    if seq.streamQ is not None:
        seq.streamQ.put(error)          # None = clean end sentinel
    with parent.lock:
        parent.doneRows += 1
        if error is not None and parent.error is None:
            parent.error = error
        last = parent.doneRows >= parent.rows
    tid = parent.ctx.traceId if parent.ctx is not None else None
    timeline_store().note(tid, "serving.retire", replica=model,
                          row=seq.row, tokens=len(seq.emitted),
                          error=type(error).__name__ if error else None)
    if last:
        sm = serving_metrics()
        sm.request_seconds().observe(time.perf_counter() - parent.t0,
                                     model=model)
        sm.requests().inc(model=model,
                          outcome="error" if parent.error else "ok")
        if parent.error is not None and tid is not None:
            # a failed request's whole timeline lands in the crash ring
            # so the post-mortem has the trace without racing eviction
            flight_recorder().record(
                kind="serving_request_failure", trace_id=tid, model=model,
                error=f"{type(parent.error).__name__}: {parent.error}",
                timeline=timeline_store().events(tid))
        parent.event.set()


class ContinuousBatcher:
    """The iteration-level scheduler: one shared fixed-slot decode batch,
    admit/retire between steps, token streaming, paged KV memory.

    Registry-compatible executor surface (``start``/``submit``/
    ``submitStream``/``queuedRows``/``shutdown``), so it hosts behind
    ``POST /v1/serving/<name>`` exactly like a
    :class:`~deeplearning4j_tpu.remote.serving.BucketedExecutor` —
    ``{"tokens": [...], "maxNewTokens": n}`` payloads, plus
    ``{"stream": true}`` for per-token NDJSON streaming.

    The loop runs ONE step ahead of the device: an iteration dispatches
    step k and only then reads step k-1's tokens, so the device computes
    while the host emits, does its books and prepares the next step.  A
    slot that took part in the unread step takes its input token from
    that step's output on the device (``paged_step_tokens``); a slot
    admitted since, resumed after a deferred round or under
    teacher-forced replay takes the host's.  ``pos`` advances at
    dispatch.  A sequence that ends by its quota is known ahead: the
    dispatch of the step that computes its last token frees its slot and
    pages for the next admission, and the tokens still unread reach it
    by the flight's record of who held the slot (``_parted``).  One whose
    end the host learns from the token itself or from outside (EOS,
    cancel, deadline, preemption) has one more token computed, which is
    discarded by that same identity
    (``dl4j_tpu_serving_decode_tokens_discarded_total``).  Either way
    the last K/V row went into a page the sequence held at dispatch, and
    device order puts the next occupant's prefill write after it.  At
    most one step is ever unread, and it is read before the loop waits
    and dropped when the loop exits or a batch fails.

    The price is paid by an arrival: its prefill queues on the device
    behind the step just dispatched, so the first token comes up to one
    step later than when the device stood idle between steps (GPT-2 XL
    on a v5e: TTFT p50 26-30 -> 36-43 ms at 1.3 req/s, PERF.md s6), in
    exchange for every later token coming sooner.
    """

    def __init__(self, lm, name: str = "default", pageSize: int = 8,
                 numPages: Optional[int] = None, maxSlots: int = 4,
                 ladder: Optional[BucketLadder] = None,
                 admission: Optional[AdmissionControl] = None,
                 eosToken: Optional[int] = None, plan=None, device=None,
                 retireLogSize: int = 64):
        self.lm = lm
        self.name = str(name)
        cfg = lm.config
        self.pageSize = int(pageSize)
        self._maxPagesPerSeq = -(-cfg.maxLen // self.pageSize)
        self._numPages = int(numPages) if numPages is not None else \
            1 + int(maxSlots) * self._maxPagesPerSeq
        self.maxSlots = int(maxSlots)
        self.eosToken = int(eosToken) if eosToken is not None else None
        self.admission = admission or AdmissionControl()
        if ladder is None:
            ladder = BucketLadder(
                batchSizes=(self.maxSlots,),
                seqLens=tuple(
                    s for s in (16, 32, 64, 128, 256, 512, 1024)
                    if s <= max(cfg.maxLen // 2, self.pageSize)
                    and s % self.pageSize == 0) or (self.pageSize,))
        for s in ladder.seqLens:
            if s % self.pageSize:
                raise ValueError(
                    f"prompt bucket {s} is not a multiple of the page "
                    f"size {self.pageSize} (prefill copies whole pages)")
            if s >= cfg.maxLen:
                raise ValueError(
                    f"prompt bucket {s} leaves no room to generate "
                    f"within the capacity {cfg.maxLen}")
        self.ladder = ladder
        self.plan = None
        self._device = device
        # slot state — owned by the loop thread
        self._slotSeq: List[Optional[_Seq]] = [None] * self.maxSlots
        self._pos = np.zeros(self.maxSlots, np.int32)
        self._start = np.zeros(self.maxSlots, np.int32)
        self._tok = np.zeros(self.maxSlots, np.int32)
        self._admitOrder: deque = deque()   # slots, oldest admission first
        # the dispatched step whose tokens are still on the device, and
        # what stands in for its output when there is none (``warm``)
        self._inflight: Optional[_Flight] = None
        self._noPrev = None
        #: the last step's small inputs, host copies and device arrays
        self._uploaded: Tuple[tuple, list] = ((), [])
        # sequences that have left their slot, and given back their
        # pages, with tokens still unread on the device: the step that
        # computes a quota's last token is known at its dispatch
        self._parted: List[_Seq] = []
        # the drain clock, the loop thread's own (it is the only
        # dispatcher of device work here): what it last gave the device,
        # the last instant it knew the device to hold work (every
        # dispatch), the instant it learned the device had run out (a
        # blocking read of that last thing returned; None while unknown)
        # and what it has done since, which names the idle stretch
        self._given = None
        self._busyAt = 0.0
        self._drainedAt: Optional[float] = None
        self._idleCause = "loop"
        # request queue — guarded by _cv
        self._queue: deque = deque()
        self._queuedRows = 0
        self._queuedPages = 0
        self._cv = threading.Condition()
        self._running = False
        self._warmed = False
        self._thread: Optional[threading.Thread] = None
        # bounded ring of (ts, pages freed): _retireRate() only ever
        # needs the recent window, and an unbounded log on a long-lived
        # replica would grow its Retry-After bookkeeping forever
        self._retireLog: deque = deque(maxlen=max(2, int(retireLogSize)))
        # set by ReplicaSet: called with (batcher, seqs, error) when a
        # shared step fails with sequences in flight — the failover
        # path.  None (standalone batcher) errors the sequences instead.
        self.onSequenceFailure = None
        self._stepFns: Dict[str, object] = {}
        # what the model's step counts on the device and returns in the
        # columns behind its tokens (row 0): ``(metric, labels)`` each, or
        # ``(metric, labels, unit)`` for a column worth ``unit`` a count
        self._stepCounters = tuple(lm.stepCounters)
        self._cacheSeen: Optional[int] = None
        self._busySteps = 0.0
        self._steps = 0
        self._phaseObservers = {
            p: functools.partial(self._observePhase, p)
            for p in SERVING_LOOP_PHASES}
        self._clock = _LoopClock()
        self._phaseHeld: dict = {}
        if plan is not None:
            self.applyPlan(plan)            # shards params, builds pools
        else:
            if device is not None:
                from deeplearning4j_tpu.parallel.meshtrainer import \
                    place_replica
                place_replica(lm, device)
            self._buildPools()

    # -- placement ------------------------------------------------------
    def _poolSharding(self, nHeads: int):
        if self.plan is None:
            if self._device is not None:
                return jax.sharding.SingleDeviceSharding(self._device)
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self.plan.mesh
        if mesh.modelSize > 1 and nHeads % mesh.modelSize == 0:
            # the merged heads*headSize dimension splits into whole
            # heads, beside their TP-sharded projection columns
            return NamedSharding(mesh.mesh, P(None, None, None,
                                              self.plan.modelAxis))
        return NamedSharding(mesh.mesh, P())

    def _buildPools(self) -> None:
        """The model's pool, shaped by what the model says its layers
        keep (``cacheSpec()``).  On a TP mesh the paged buffers split by
        heads; ring and recurrent state replicate."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = self.lm.cacheSpec()
        whole = NamedSharding(self.plan.mesh.mesh, P()) \
            if self.plan is not None else self._poolSharding(1)
        self.pool = KVCachePool.forSpec(
            spec, self.pageSize, self._numPages, self.maxSlots,
            self._maxPagesPerSeq,
            sharding=self._poolSharding(spec.splitHeads),
            slotSharding=whole)

    def applyPlan(self, plan) -> None:
        """Inference-mode :class:`~deeplearning4j_tpu.parallel.
        meshtrainer.ShardingPlan` application — the TP replica path:
        shard the model's weights over the plan's model axis, rebuild
        the pool ON the mesh, and pop every cached step executable so
        the next warm traces fresh closures against the new placement."""
        from deeplearning4j_tpu.parallel.meshtrainer import \
            apply_inference_plan
        apply_inference_plan(self.lm, plan)
        self.plan = plan
        self._buildPools()
        self._invalidateFns()

    # -- executables ----------------------------------------------------
    def _invalidateFns(self) -> None:
        """Pool or plan changed: drop every cached step fn (and the
        model's cached jits) so nothing re-dispatches a trace whose
        constraints belong to the old layout."""
        self._stepFns.clear()
        self.lm.dropCompiled()
        self._warmed = False
        self._cacheSeen = None

    def _ensureFns(self) -> None:
        if "step" in self._stepFns:
            return
        self._stepFns["step"] = self.lm.buildPagedDecodeFn()
        self._stepFns["write"] = self.lm.buildPagedPrefillWriteFn()

    def compileCacheSize(self) -> int:
        """Executable-cache entries across the model's and the
        scheduler's fns — the flat-across-churn acceptance probe."""
        return self.lm.compileCacheSize() + sum(
            int(fn._cache_size()) for fn in self._stepFns.values()
            if hasattr(fn, "_cache_size"))

    def warm(self) -> float:
        """Compile every steady-state executable BEFORE traffic: one
        prefill + pool write per prompt bucket (scratch pages take the
        dummy writes) and the decode step."""
        if self._warmed:
            return 0.0
        sm = serving_metrics()
        t0 = time.perf_counter()
        before = self.compileCacheSize()
        self._ensureFns()
        S = self.maxSlots
        zeros = jnp.zeros(S, jnp.int32)
        tok0 = jnp.zeros((S, 1), jnp.int32)
        pt = jnp.asarray(self.pool.pageTable)
        step = self._stepFns["step"]
        lowered, experts, inPlace, ssd = paged_kernel_lowerings(), \
            moe_step_kernel_lowerings(), sparse_in_place_lowerings(), \
            ssd_step_kernel_lowerings()
        prev, *self.pool.arrays = step(
            self.lm.params, *self.pool.arrays, tok0, tok0, pt, zeros, zeros)
        # one kernel lowering for each layer whose rows the step read
        # through it (``paged_kernel_lowerings``): every paged layer and,
        # where the model reads them the same way, every ring layer
        read = paged_kernel_lowerings() - lowered
        kernel = read > 0
        spec = self.pool.spec
        sm.paged_attention_kernel().set(1 if kernel else 0, model=self.name)
        sm.paged_attention_kv_passes().set(
            paged_kernel_kv_passes() if kernel else 0, model=self.name)
        sm.ring_attention_kernel().set(
            1 if spec.ringLayers
            and read >= spec.pagedLayers + spec.ringLayers else 0,
            model=self.name)
        sm.moe_step_kernel().set(
            1 if moe_step_kernel_lowerings() > experts else 0,
            model=self.name)
        sm.ssd_step_kernel().set(
            1 if ssd_step_kernel_lowerings() > ssd else 0, model=self.name)
        sm.sparse_read_in_place().set(
            1 if spec.indexWidth and sparse_in_place_lowerings() - inPlace
            >= spec.pagedLayers else 0, model=self.name)
        emb = self.lm.params.get("emb")
        if emb is not None:
            sm.tied_table_lane_aligned().set(
                1 if emb.shape[-1] % 128 == 0 else 0, model=self.name)
        # and with a step's own output for ``prev``, as every later call
        # has it: beside committed params that is another entry of the
        # jit's cache than fresh zeros.  It stands in wherever no step is
        # unread.
        self._noPrev, *self.pool.arrays = step(
            self.lm.params, *self.pool.arrays, tok0, prev, pt, zeros, zeros)
        slot0 = jnp.zeros((), jnp.int32)
        grouped = moe_grouped_kernel_lowerings()
        for Tp in self.ladder.seqLens:
            dummy = np.zeros((1, Tp), np.int32)
            ids = jnp.zeros(Tp // self.pageSize, jnp.int32)   # scratch
            # slot 0's ring rows and recurrent state take the dummy
            # writes: an admission overwrites them before any step reads
            _l, *state = self.lm.prefillRaw(dummy, lengths=[1])
            self._writeState(state, ids, slot0)
        sm.moe_grouped_kernel().set(
            1 if moe_grouped_kernel_lowerings() > grouped else 0,
            model=self.name)
        jax.block_until_ready(self.pool.arrays)  # jaxlint: sync-ok -- warm-up fence: compile cost must land in warmup_seconds, not the first request
        self._atRest()
        self._warmed = True
        dt = time.perf_counter() - t0
        sm.warmup_seconds().observe(dt, model=self.name)
        sm.warmup_compiles().inc(max(0, self.compileCacheSize() - before),
                                 model=self.name)
        return dt

    # -- lifecycle ------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        if self._running:
            return self
        sm = serving_metrics()
        self.admission.bind(self.name)
        sm.queue_depth().set(0, model=self.name)
        sm.compile_hits().inc(0, model=self.name)
        sm.compile_misses().inc(0, model=self.name)
        sm.decode_steps_overlapped().inc(0, model=self.name)
        sm.decode_tokens_discarded().inc(0, model=self.name)
        # register the latency-decomposition histograms up front so the
        # hot path's observe_exemplar() finds them already constructed
        sm.ttft_seconds()
        sm.inter_token_seconds()
        sm.queue_wait_seconds()
        sm.prefill_seconds()
        sm.loop_phase_seconds()
        sm.loop_phase_offcpu_seconds()
        # the ring series exist from the start and read 0 until a step
        # updates them: never, for a model without window layers, so a
        # reader of every kind of cache state finds all of them
        sm.ring_rows_in_use().set(0, model=self.name)
        sm.cache_bytes().set(0, model=self.name, kind="ring")
        sm.device_idle_seconds()
        self.warm()
        self._atRest()
        self._updatePageGauges()
        self._cacheSeen = self.compileCacheSize()
        self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"cbatch-{self.name}")
        self._thread.start()
        return self

    def shutdown(self) -> None:
        err = RuntimeError(f"continuous batcher {self.name!r} shut down")
        with self._cv:
            if not self._running:
                return
            self._running = False
            drained = list(self._queue)
            self._queue.clear()
            self._queuedRows = 0
            self._queuedPages = 0
            self._cv.notify_all()
        # registry/metric locks are only ever taken AFTER _cv is released
        # (one scheduler -> registry lock order on every path)
        for seq in drained:
            self._finishSeq(seq, err)
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None
        # the loop has exited: slot state is safe to touch from here
        for slot, seq in enumerate(self._slotSeq):
            if seq is not None:
                self._retireSlot(slot, error=err)
        for seq in self._takeParted():
            self._finishSeq(seq, err)
        serving_metrics().queue_depth().set(0, model=self.name)

    def busy(self) -> bool:
        """A sequence holds a slot, or has parted from one and is still
        owed its last token."""
        return bool(self._parted) or \
            any(s is not None for s in self._slotSeq)

    def queuedRows(self) -> int:
        with self._cv:
            return self._queuedRows

    def occupancy(self) -> Optional[float]:
        """Mean active-slots fraction over every decode step so far."""
        return self._busySteps / self._steps if self._steps else None

    # -- request path ---------------------------------------------------
    def _makeSeqs(self, payload) -> Tuple[List[_Seq], _Pending]:
        """Validate and split one request into per-row sequences.  Every
        condition that could wedge or poison the shared decode batch is
        rejected HERE (HTTP 400), never mid-flight: prompts above the
        top bucket, quotas past the positional capacity, and quotas
        whose pages can never fit the per-sequence KV budget."""
        if not isinstance(payload, dict) or "tokens" not in payload:
            raise ValueError('generative request needs {"tokens": [...]}')
        # jaxlint: sync-ok -- request decode: token ids arrive as host JSON
        toks = np.asarray(payload["tokens"], np.int32)
        if toks.ndim == 1:
            toks = toks[None, :]
        if toks.ndim != 2 or toks.shape[0] < 1 or toks.shape[1] < 1:
            raise ValueError(
                f"tokens must be (t,) or (b, t) with b >= 1 and t >= 1; "
                f"got shape {toks.shape}")
        vocab = self.lm.config.vocabSize
        if toks.min() < 0 or toks.max() >= vocab:
            raise ValueError(f"token ids must be in [0, {vocab})")
        n = int(payload.get("maxNewTokens", 16))
        if n < 1:
            raise ValueError("maxNewTokens must be >= 1")
        Tp = self.ladder.seqBucket(toks.shape[1])    # 400 above top bucket
        cap = self.lm.config.maxLen
        if Tp + n > cap:
            raise ValueError(
                f"prompt bucket {Tp} + maxNewTokens {n} exceeds the "
                f"positional capacity {cap}")
        pages = self.pool.pagesFor(Tp + n)
        if pages > self.pool.maxPagesPerSeq:
            raise ValueError(
                f"prompt bucket {Tp} + maxNewTokens {n} can never fit "
                f"the KV page budget ({pages} pages > "
                f"{self.pool.maxPagesPerSeq} per sequence)")
        deadline = None
        dl = payload.get("deadlineSeconds")
        if dl is not None:
            dl = float(dl)  # jaxlint: sync-ok -- deadlineSeconds arrives as host JSON, not a device scalar
            if not dl >= 0.0:           # also rejects NaN
                raise ValueError("deadlineSeconds must be >= 0")
            deadline = time.monotonic() + dl
        # adopt the ingress-thread's ambient trace context (the HTTP
        # handler parsed/minted it and enqueues synchronously on this
        # same thread); a direct caller without one gets a fresh trace
        ctx = current_context()
        if ctx is None:
            ctx = RequestContext.new(deadline=deadline)
        parent = _Pending(toks.shape[0], n, deadline=deadline, ctx=ctx)
        seqs = [_Seq(toks[i:i + 1], Tp, n, pages, parent, i,
                     deadline=deadline)
                for i in range(toks.shape[0])]
        return seqs, parent

    def _admitGate(self, rows: int, pages: int,
                   singleStep: bool = False,
                   deadline: Optional[float] = None,
                   ctx: Optional[RequestContext] = None) -> None:
        sm = serving_metrics()
        tid = ctx.traceId if ctx is not None else None
        if deadline is not None and time.monotonic() >= deadline:
            # end-to-end deadline already spent (queueing upstream, a
            # slow hop): shed NOW rather than burn a decode slot on a
            # response nobody is waiting for (tail-at-scale discipline)
            sm.deadline_sheds().inc(model=self.name, stage="admission")
            sm.requests().inc(model=self.name, outcome="deadline")
            timeline_store().note(tid, "serving.shed", replica=self.name,
                                  stage="admission")
            raise DeadlineExceeded(
                "end-to-end deadline expired before admission")
        queued = self.queuedRows()
        sm.queue_depth().set(queued, model=self.name)
        fired = self.admission.check(queued)
        retryAfter = self.admission.retryAfter
        if fired is None:
            # page-headroom shed is about WEDGE risk, not backlog: a
            # queued sequence holds no pages, so only a request that
            # cannot fit the CURRENT free list sheds (backlog depth is
            # the queue-depth rule's job).  Single-step retrieval
            # sequences (quota == 1) emit at admission and retire before
            # any decode step — they never hold pages, so the deficit
            # shed does not apply to them.
            kv = self.admission.checkKv(self.pool.freePages(), pages,
                                        self._retireRate(),
                                        holdsPages=not singleStep)
            if kv is not None:
                fired, retryAfter = kv[:2], kv[2]
        if fired is not None:
            rule, detail = fired
            sm.shed().inc(model=self.name, rule=rule)
            sm.requests().inc(model=self.name, outcome="shed")
            timeline_store().note(tid, "serving.shed", replica=self.name,
                                  stage="admission", rule=rule)
            raise ServiceOverloaded(detail, retryAfter)

    def _enqueue(self, seqs: Sequence[_Seq], front: bool = False) -> None:
        now = time.perf_counter()
        with self._cv:
            if not self._running:
                raise RuntimeError(
                    f"continuous batcher {self.name!r} is not running")
            if front:
                # failed-over sequences adopt the survivor's FIFO head:
                # they already waited their turn on the dead replica
                for s in reversed(list(seqs)):
                    self._queue.appendleft(s)
            else:
                for s in seqs:
                    self._queue.append(s)
            for s in seqs:
                s.enqT = now        # queue wait restarts on every hop
            self._queuedRows += len(seqs)
            self._queuedPages += sum(s.pages for s in seqs)
            depth = self._queuedRows
            self._cv.notify()
        serving_metrics().queue_depth().set(depth, model=self.name)
        ts = timeline_store()
        for s in seqs:
            ts.note(s.ctx.traceId if s.ctx is not None else None,
                    "serving.enqueue", replica=self.name, row=s.row,
                    front=front, restarts=s.restarts)

    def submit(self, payload, timeout: Optional[float] = None):
        """Validate, admit, enqueue, block until every row finished.
        Returns (b, maxNewTokens) int32 (rows that hit ``eosToken``
        early are padded with it).  Raises ``ValueError`` (HTTP 400) for
        malformed payloads, :class:`ServiceOverloaded` (429) when
        admission sheds."""
        seqs, parent = self._makeSeqs(payload)
        self._admitGate(len(seqs), sum(s.pages for s in seqs),
                        singleStep=(parent.quota == 1),
                        deadline=parent.deadline, ctx=parent.ctx)
        self._enqueue(seqs)
        if not parent.event.wait(timeout):
            # reap still-QUEUED rows now — left behind they would keep
            # inflating _queuedRows (phantom backlog shedding live
            # traffic) until each crawled to the FIFO head; rows already
            # in a slot retire at the loop's next boundary
            depth = None
            with self._cv:
                for s in seqs:
                    s.cancelled = True
                    if s in self._queue:
                        self._queue.remove(s)
                        self._queuedRows -= 1
                        self._queuedPages -= s.pages
                depth = self._queuedRows
                self._cv.notify()
            serving_metrics().queue_depth().set(depth, model=self.name)
            raise TimeoutError(
                f"continuous-batching request timed out after {timeout}s")
        if parent.error is not None:
            raise parent.error
        pad = self.eosToken if self.eosToken is not None else 0
        out = np.full((parent.rows, parent.quota), pad, np.int32)
        for s in seqs:
            # jaxlint: sync-ok -- response assembly from host-side emitted-token lists (already D2H'd per step)
            row = np.asarray(s.emitted[:parent.quota], np.int32)
            out[s.row, :len(row)] = row
        return out

    def submitStream(self, payload):
        """Single-sequence streaming submit: validates + enqueues NOW
        (so 400/429 surface before any token), returns a generator
        yielding each token as its decode step completes.  Closing the
        generator early cancels the sequence at the next step
        boundary."""
        seqs, parent = self._makeSeqs(payload)
        if len(seqs) != 1:
            raise ValueError("streaming serves a single sequence per "
                             "request")
        seq = seqs[0]
        seq.streamQ = _stdqueue.SimpleQueue()
        self._admitGate(1, seq.pages, singleStep=(seq.quota == 1),
                        deadline=parent.deadline, ctx=parent.ctx)
        heartbeat = payload.get("keepAliveSeconds")
        if heartbeat is not None:
            heartbeat = float(heartbeat)  # jaxlint: sync-ok -- keepAliveSeconds arrives as host JSON, not a device scalar
            if not heartbeat > 0.0:
                raise ValueError("keepAliveSeconds must be > 0")
        self._enqueue(seqs)

        def book(queued: float, write: float, tokens: int) -> None:
            sm = serving_metrics()
            sm.stream_token_seconds().inc(queued, model=self.name,
                                          stage="queued")
            sm.stream_token_seconds().inc(write, model=self.name,
                                          stage="write")
            sm.stream_tokens_delivered().inc(tokens, model=self.name)

        def gen():
            from deeplearning4j_tpu.remote.server import KEEPALIVE
            # the token's hand-off, on the consumer's thread: seconds
            # from the loop's put to this get's return (queued) and from
            # there to the consumer asking for the next item (write),
            # summed here and added to the registry every
            # _HANDOFF_FLUSH_TOKENS tokens and at the end, never a token
            queued = write = 0.0
            tokens = 0
            try:
                while True:
                    try:
                        item = seq.streamQ.get(timeout=heartbeat)
                    except _stdqueue.Empty:
                        # decode gap (big batch, failover replay, a slow
                        # replica): yield the sentinel so the transport
                        # writes a comment line — a client that hung up
                        # fails THAT write and cancels the sequence just
                        # like a failed token write would
                        yield KEEPALIVE
                        continue
                    if item is None:
                        return
                    if isinstance(item, BaseException):
                        raise item
                    tok, putAt = item
                    got = time.perf_counter()
                    lay = got - putAt
                    queued += lay
                    tokens += 1
                    if lay >= _QUEUED_EVENT_SECONDS:
                        # on this thread's track; no profiler annotation:
                        # the stretch lies in the past
                        tracer().record_complete(
                            "serving.stream.queued", putAt, lay,
                            args={"replica": self.name, "row": seq.row})
                    # jaxlint: disable=host-sync -- stream items are host ints pushed by _emit
                    yield int(tok)
                    write += time.perf_counter() - got
                    if tokens >= _HANDOFF_FLUSH_TOKENS:
                        book(queued, write, tokens)
                        queued = write = 0.0
                        tokens = 0
            finally:
                if tokens:
                    book(queued, write, tokens)
                if not seq.parent.event.is_set():
                    seq.cancelled = True
        return gen()

    def _retireRate(self) -> float:
        """Mean page-retire rate (pages/sec) over the recent retire log
        — the denominator of the KV-headroom Retry-After."""
        log = list(self._retireLog)
        if len(log) < 2:
            return 0.0
        dt = log[-1][0] - log[0][0]
        if dt <= 0:
            return 0.0
        return sum(p for _, p in log[1:]) / dt

    # -- scheduler loop -------------------------------------------------
    def _phase(self, phase: str):
        """One phase of the loop thread (``SERVING_LOOP_PHASES``): the
        span ``serving.loop.<phase>``, its profiler annotation and one
        observation of ``dl4j_tpu_serving_loop_phase_seconds`` and, in
        the iterations that read the thread's CPU clock (``_LoopClock``:
        every one where a read is cheap), of
        ``..._loop_phase_offcpu_seconds``, all from the same two reads
        of each clock.  Loop thread only; never entered while ``_cv`` is
        held (scheduler -> registry lock order)."""
        return tracer().span("serving.loop." + phase, cpu=self._clock.on,
                             observe=self._phaseObservers[phase])

    def _phaseCells(self, phase: str):
        """Both phase histograms and this model's cell of each for
        ``phase``, looked up once and held while the process's registry
        holds the first: an observation then costs one registry lookup
        for the pair, where two histograms by their accessors cost two
        and a label lookup each."""
        held = self._phaseHeld.get(phase)
        if held is None or get_registry().get(held[0].name) is not held[0]:
            sm = serving_metrics()
            wall, off = sm.loop_phase_seconds(), sm.loop_phase_offcpu_seconds()
            held = self._phaseHeld[phase] = (
                wall, wall.cell(model=self.name, phase=phase),
                off, off.cell(model=self.name, phase=phase))
        return held

    def _observePhase(self, phase: str, seconds: float,
                      cpu: Optional[float] = None) -> None:
        """``cpu``: what the thread's CPU clock gained in the phase, in
        the iterations that read it (``_LoopClock``)."""
        wall, wallCell, off, offCell = self._phaseCells(phase)
        wall.observe_cell(wallCell, seconds)
        offcpu = None
        if cpu is not None:
            offcpu = self._clock.offcpu(phase, seconds, cpu)
            off.observe_cell(offCell, offcpu)
        if seconds >= _STALL_SECONDS and phase != "wait":
            self._stall(phase, seconds, offcpu)

    def _stall(self, what: str, seconds: float,
               offcpu: Optional[float] = None) -> None:
        """A loop phase, or a stretch the device was starved for, of
        ``_STALL_SECONDS`` or more has just ended: leave what a process
        can cheaply know it coincided with (``offcpu_seconds``: of a
        phase, what the loop thread did not run for; None where the CPU
        clock was not read in it, and for a starved stretch, which is no
        one phase's)."""
        tracer().instant(
            "serving.loop.stall", replica=self.name, phase=what,
            seconds=round(seconds, 6),
            gc_seconds=round(
                gc_pause_seconds(time.perf_counter() - seconds), 6),
            offcpu_seconds=None if offcpu is None else round(offcpu, 6),
            threads=threading.active_count(), queued=self._queuedRows)

    # -- the drain clock ------------------------------------------------
    def _atRest(self) -> None:
        """Nothing the loop gave the device is unfinished (the warm-up's
        fence; a start): the device's idle time counts from here."""
        self._given, self._idleCause = None, "loop"
        self._drainedAt = time.perf_counter()

    def _starved(self) -> Optional[Tuple[float, bool]]:
        """Before every dispatch onto the device (a prefill, a step):
        since when it has stood idle, and whether that
        instant is an upper bound; None while it holds work.  From a
        drain instant on record the stretch is exact; where none is on
        record and what was dispatched last ``is_ready()``, the device
        ran dry at an unknown instant since the loop last knew it to hold
        work (the return of the dispatch before), and the stretch since
        THAT instant is an upper bound."""
        if self._drainedAt is not None:
            return self._drainedAt, False
        if self._given is not None and self._given.is_ready():
            return self._busyAt, True
        return None

    def _fed(self, starved: Optional[Tuple[float, bool]], given) -> None:
        """The dispatch of ``given`` has returned: the device holds work
        from here, and a stretch it stood idle for (``_starved()``, asked
        before the call) ends here, the call included: the device waits
        through it for the launch.  With ``_starved`` one ``is_ready()``
        and one clock read a dispatch; the one site that feeds
        ``dl4j_tpu_serving_device_idle_seconds``."""
        now = time.perf_counter()
        if starved is not None:
            (since, bound), cause = starved, self._idleCause
            seconds = now - since
            serving_metrics().device_idle_seconds().observe(
                seconds, model=self.name, cause=cause)
            if seconds >= _IDLE_EVENT_SECONDS:
                tracer().record_complete(
                    "serving.device.idle", since, seconds,
                    args={"replica": self.name, "cause": cause,
                          "bound": bound})
                if seconds >= _STALL_SECONDS and cause != "wait":
                    self._stall("device.idle." + cause, seconds)
            self._drainedAt = None
        self._given, self._busyAt, self._idleCause = given, now, "loop"

    def _idle(self) -> bool:
        return self._queuedRows == 0 and self._inflight is None and \
            not any(s is not None for s in self._slotSeq)

    def _loop(self) -> None:
        while True:
            with self._cv:
                if not self._running:
                    # the unread step is dropped: whoever stopped the
                    # loop fails or replays its sequences from what they
                    # had emitted
                    self._inflight = None
                    return
                idle = self._idle()
            if idle:
                # one slice of waiting per iteration, so that an idle
                # stretch is on the record (and in a capture) while it
                # lasts; the condition is looked at again under the lock,
                # so an enqueue between the two looks is not slept through
                self._idleCause = "wait"
                with self._phase("wait"), self._cv:
                    if self._running and self._idle():
                        self._cv.wait(0.1)
                continue
            try:
                if _inj.replica_dead(self.name):
                    # a crashed replica's loop idles instead of serving:
                    # the health probe (not this thread) is what removes
                    # it from routing
                    time.sleep(0.02)
                    continue
                if _inj.check_replica_crash(self.name):
                    raise _inj.InjectedReplicaCrash(self.name)
                if not self._warmed:
                    # a prior failure rebuilt the pools: re-warm before
                    # serving (fresh fns against the fresh buffers)
                    self.warm()
                    self._cacheSeen = self.compileCacheSize()
                self._iterate()
            except Exception as e:
                # the scheduler thread must survive ANY dispatch failure
                # (device OOM, a jit error): fail the affected work, not
                # every future request (cf. BucketedExecutor._loop)
                self._failBatch(e)

    def _iterate(self) -> None:
        """One busy iteration: admit, then the decode work.  The span is
        the parent of everything in it: what a span costs between two
        phases is then inside a span too, so no instant of the loop
        thread is without a name."""
        self._clock.iteration()
        with tracer().span("serving.loop.iteration"):
            with self._phase("admit"):
                self._admit()
            if self.busy() or self._inflight is not None:
                self._stepOnce()

    def _failBatch(self, error: BaseException) -> None:
        """Last-resort recovery for a failed shared step: hand every
        in-flight sequence to the replica set's failover handler when
        one is wired (reset for a from-prompt replay on a survivor),
        else error it; then rebuild pools and step fns — a dispatch
        that raised may already have CONSUMED the donated pool buffers,
        so the old arrays cannot be trusted (or even alive)."""
        handler = self.onSequenceFailure
        handed: List[_Seq] = []
        self._inflight = None           # its tokens are never delivered
        # nor is what the device holds known any more (the pools are
        # rebuilt below; the warm-up that follows ends in a fence)
        self._given = self._drainedAt = None
        for slot, seq in enumerate(self._slotSeq):
            if seq is None:
                continue
            if handler is not None and not seq.cancelled:
                self._vacate(slot)
                self._resetForReplay(seq)
                handed.append(seq)
            else:
                self._retireSlot(slot, error=error)
        for seq in self._takeParted():
            if handler is not None and not seq.cancelled:
                self._resetForReplay(seq)
                handed.append(seq)
            else:
                self._finishSeq(seq, error)
        self._buildPools()
        self._invalidateFns()
        self._updatePageGauges()
        if handed:
            ts = timeline_store()
            for seq in handed:
                ts.note(seq.ctx.traceId if seq.ctx is not None else None,
                        "serving.evacuate", replica=self.name,
                        reason=f"{type(error).__name__}: {error}")
            handler(self, handed, error)

    def _admit(self) -> None:
        """Fill free slots from the queue head — strict FIFO, so a large
        request defers later arrivals instead of being starved by them;
        admission stops when the head's prefill pages don't fit yet."""
        while True:
            free = next((i for i, s in enumerate(self._slotSeq)
                         if s is None), None)
            seq = None
            with self._cv:
                if not self._queue:
                    return
                head = self._queue[0]
                expired = head.deadline is not None and \
                    time.monotonic() >= head.deadline
                if not head.cancelled and not expired:
                    if free is None:
                        return
                    if self.pool.freePages() < \
                            self.pool.pagesFor(head.bucket):
                        return
                self._queue.popleft()
                self._queuedRows -= 1
                self._queuedPages -= head.pages
                depth = self._queuedRows
                seq = head
            serving_metrics().queue_depth().set(depth, model=self.name)
            if seq.cancelled:
                self._finishSeq(seq, None)
                continue
            if expired:
                # its deadline ran out while it waited in line: it never
                # gets a slot, never holds a page
                serving_metrics().deadline_sheds().inc(model=self.name,
                                                       stage="queued")
                timeline_store().note(
                    seq.ctx.traceId if seq.ctx is not None else None,
                    "serving.shed", replica=self.name, stage="queued")
                self._finishSeq(seq, DeadlineExceeded(
                    "end-to-end deadline expired while queued"))
                continue
            try:
                self._admitSeq(free, seq)
            except Exception as e:
                # an admission that blows up (bad prefill, device error)
                # fails ITS sequence only — free whatever the slot
                # already holds and keep admitting
                self.pool.release(free)
                if self._slotSeq[free] is seq:
                    self._retireSlot(free, error=e)
                else:
                    self._finishSeq(seq, e)

    def _admitSeq(self, slot: int, seq: _Seq) -> None:
        sm = serving_metrics()
        tid = seq.ctx.traceId if seq.ctx is not None else None
        admitT = time.perf_counter()
        queueWait = admitT - seq.enqT if seq.enqT is not None else None
        if queueWait is not None:
            observe_exemplar("dl4j_tpu_serving_queue_wait_seconds",
                             queueWait, trace_id=tid, model=self.name)
        Tp = seq.bucket
        self.pool.ensure(slot, Tp)
        padded = seq.tokens if seq.realLen == Tp else np.concatenate(
            [np.zeros((1, Tp - seq.realLen), np.int32), seq.tokens],
            axis=1)
        nP = Tp // self.pageSize
        # a restart (preemption OR failover onto this replica) goes
        # through the model's restart hook — same executable + bucket as
        # a first admission, but the hook is the seam a survivor with
        # different numerics can override
        prefill = self.lm.restartFromPrompt if seq.restarts > 0 \
            else self.lm.prefillRaw
        span = tracer().span(
            "serving.prefill",
            observe=lambda dt: observe_exemplar(
                "dl4j_tpu_serving_prefill_seconds", dt, trace_id=tid,
                model=self.name),
            replica=self.name, slot=slot, bucket=Tp, trace_id=tid)
        with span:
            slotA = jnp.asarray(slot, jnp.int32)
            ids = jnp.asarray(self.pool.heldIds(slot)[:nP], jnp.int32)
            starved = self._starved()
            logits, *state = prefill(padded, lengths=[seq.realLen])
            self._fed(starved, logits)
            with tracer().span("serving.state.write", replica=self.name,
                               slot=slot):
                self._writeState(state, ids, slotA)
            # the row's slice is the last thing this admission gives the
            # device, behind the prefill and the state writes; a device
            # runs what it is given in order, so when its read returns
            # the device has run out of work, and stands idle from here
            # to the next dispatch: the gap an admission leaves
            # (ROADMAP S2)
            self._given = logits[0]
            # jaxlint: sync-ok -- the prefill's greedy token seeds the host-side slot state
            first = int(np.argmax(np.asarray(self._given)))
            self._drainedAt, self._idleCause = time.perf_counter(), "admit"
            if seq.forced and len(seq.emitted) < len(seq.forced):
                # teacher-forced replay: the first token was already
                # computed (and maybe delivered) before the move — force
                # it so the delivered prefix survives any cross-replica
                # numeric drift, and so the KV the step writes next is
                # conditioned on the prefix the client actually saw
                first = int(seq.forced[0])
        prefillDt = span.seconds
        sm.prefill_positions().inc(Tp, model=self.name, bucket=str(Tp))
        sm.prefill_prompt_tokens().inc(seq.realLen, model=self.name)
        self._slotSeq[slot] = seq
        self._pos[slot] = Tp
        self._start[slot] = Tp - seq.realLen
        self._tok[slot] = first
        self._admitOrder.append(slot)
        sm.sequences_admitted().inc(model=self.name)
        timeline_store().note(
            tid, "serving.admit", replica=self.name, slot=slot, row=seq.row,
            restarts=seq.restarts,
            queue_wait_s=round(queueWait, 6) if queueWait is not None
            else None,
            prefill_s=round(prefillDt, 6))
        self._updatePageGauges()
        if self._emit(seq, first):
            self._retireSlot(slot)

    def _writeState(self, state, pageIds, slot) -> None:
        """Write what one sequence's prefill leaves behind (``state``:
        ``prefillRaw``'s parts after the logits, batch row 0) — pages,
        ring rows, recurrent state — into the pool, at ``pageIds`` and
        ``slot``."""
        self.pool.arrays = self._stepFns["write"](
            *self.pool.arrays, *(part[:, 0] for part in state), pageIds,
            slot)

    def _emit(self, seq: _Seq, tok: int) -> bool:
        """Deliver one token; True when the sequence is finished.  After
        a preemption the regenerated prefix is swallowed
        (``streamSkip``) so a streaming client never sees a token
        twice."""
        seq.emitted.append(tok)
        serving_metrics().decode_tokens().inc(model=self.name)
        now = time.perf_counter()
        # latency decomposition observes FRESH tokens only: a replayed
        # prefix (len(emitted) <= len(forced)) was already delivered, so
        # re-observing it would double-count.  lastTokT deliberately
        # survives the replay — the first fresh post-failover token's
        # inter-token gap then CONTAINS the failover, which is exactly
        # what the client experienced.
        if len(seq.emitted) > len(seq.forced):
            tid = seq.ctx.traceId if seq.ctx is not None else None
            parent = seq.parent
            if parent.firstTokenAt is None:
                with parent.lock:
                    isFirst = parent.firstTokenAt is None
                    if isFirst:
                        parent.firstTokenAt = now
                if isFirst:
                    observe_exemplar("dl4j_tpu_serving_ttft_seconds",
                                     now - parent.t0, trace_id=tid,
                                     model=self.name)
                    timeline_store().note(
                        tid, "serving.first_token", replica=self.name,
                        row=seq.row, ttft_s=round(now - parent.t0, 6))
            if seq.lastTokT is not None:
                observe_exemplar("dl4j_tpu_serving_inter_token_seconds",
                                 now - seq.lastTokT, trace_id=tid,
                                 model=self.name)
            seq.lastTokT = now
        if seq.streamQ is not None:
            if seq.streamSkip > 0:
                seq.streamSkip -= 1
            else:
                # the put's instant rides with the token: its consumer
                # books what lay between here and its own get
                seq.streamQ.put((tok, now))
                seq.streamed += 1
        if len(seq.emitted) >= seq.quota:
            return True
        return self.eosToken is not None and tok == self.eosToken

    def _stepOnce(self) -> None:
        """One iteration's decode work: dispatch the next step, then read
        the one that was dispatched an iteration ago."""
        delay = _inj.replica_slowdown(self.name)
        if delay:
            time.sleep(delay)           # injected brownout (SlowReplica)
        with tracer().span("serving.decode.step",
                           replica=self.name) as stepArgs:
            with self._phase("grow"):
                active, deferred = self._growPages()
            stepArgs["active"] = len(active)
            flight = self._dispatch(active, deferred) if active else None
            # one step ahead: what was just dispatched stays unread
            # while the host delivers the step before it
            flight, self._inflight = self._inflight, flight
            if flight is not None:
                self._land(flight)

    def _dispatch(self, active: List[int], deferred: set) -> _Flight:
        """Upload the slots' state and dispatch one step for ``active``;
        the host's ``pos`` moves on by what was dispatched, and a
        sequence whose quota this step fills leaves its slot."""
        ahead = self._inflight
        with self._phase("upload"):
            # copies: the slots' state moves on while the step runs, and
            # on the CPU a device array may alias the numpy buffer it
            # was made from
            ptH = self.pool.pageTable.copy()
            posH = self._pos.copy()
            startH = self._start.copy()
            tokH = self._tok.copy()
            # deferred rows go to the scratch page with zeroed state: the
            # fixed-shape step still computes them, but their writes land
            # in scratch and their REAL page tables / slot state stay
            # untouched for the next round
            for s in deferred:
                ptH[s, :] = 0
                posH[s] = startH[s] = tokH[s] = 0
            seqs: List[Optional[_Seq]] = [None] * self.maxSlots
            for s in active:
                seq = seqs[s] = self._slotSeq[s]
                if ahead is not None and ahead.seqs[s] is seq:
                    # its input is the unread step's output: on the
                    # device, unless a replay forces it (known ahead)
                    n = len(seq.emitted)
                    tokH[s] = seq.forced[n] if n < len(seq.forced) else -1
            pt, pos, startA, tokA = self._upload(ptH, posH, startH,
                                                 tokH[:, None])
        step = self._stepFns["step"]
        with self._phase("dispatch"):
            prev = self._noPrev if ahead is None else ahead.greedy
            starved = self._starved()
            greedy, *self.pool.arrays = step(
                self.lm.params, *self.pool.arrays, tokA, prev, pt, pos,
                startA)
            self._fed(starved, greedy)
            # the step's inputs die here and not at the return, so that
            # freeing them is inside a phase (tens of microseconds: the
            # loop's time is to be accounted for)
            del tokA, prev, pt, pos, startA
            if ahead is not None:
                serving_metrics().decode_steps_overlapped().inc(
                    model=self.name)
            self._pos[active] += 1
            flight = _Flight(greedy, active, seqs,
                             (self._pos - self._start)[active])
            for s in active:
                seq = seqs[s]
                unread = ahead is not None and ahead.seqs[s] is seq
                if len(seq.emitted) + unread + 1 >= seq.quota:
                    # this step computes its last token: known ahead, so
                    # the slot and its pages are free for the next
                    # admission now (device order keeps that one's
                    # prefill write behind this step's K/V row); the
                    # token finds the sequence by the flight's record
                    self._retireSlot(s, parting=True)
            return flight

    def _upload(self, *host: np.ndarray) -> list:
        """The step's small inputs on the device: ONE transfer for those
        whose values differ from the step before's, and that step's
        device arrays for the rest.  ``pos`` moves every step; a page
        table only when a slot takes a page, ``start`` at an admission,
        the tokens when one is new or forced (a slot that goes on reads
        -1: its input is on the device)."""
        hostWas, dev = self._uploaded
        fresh = [i for i, h in enumerate(host)
                 if i >= len(hostWas) or not np.array_equal(h, hostWas[i])]
        dev = list(dev) + [None] * (len(host) - len(dev))
        for i, a in zip(fresh, jax.device_put([host[i] for i in fresh])):
            dev[i] = a
        # the host copies are never written again (the caller made them
        # for this step), so a device array that aliases one stays true
        self._uploaded = (host, dev)
        return dev

    def _land(self, flight: _Flight) -> None:
        """Read a dispatched step's tokens, deliver them, do the books."""
        with self._phase("fetch"):
            # jaxlint: sync-ok -- greedy tokens ARE the response payload (streamed per step)
            g = np.asarray(flight.greedy)
            if flight.greedy is self._given:
                # nothing was dispatched behind this step (the last
                # before the loop waits): the device ran out of work here
                self._drainedAt = time.perf_counter()
        with self._phase("emit"):
            self._emitStep(flight, g)
        with self._phase("bookkeep"):
            flight.greedy = None        # freed inside a phase, as above
            sm = serving_metrics()
            if self._stepCounters:
                counted = g[0, g.shape[1] - len(self._stepCounters):]
                for (metric, labels, *unit), n in zip(self._stepCounters,
                                                      counted):
                    if n:
                        # a column may count in units of more than one
                        # (a count too large for one int32 rides in two)
                        # jaxlint: disable=host-sync -- counted is a slice of g, the already-fetched host copy of this step's output
                        n = int(n) * (unit[0] if unit else 1)
                        getattr(sm, metric)().inc(n, model=self.name,
                                                  **labels)
            occupied = len(flight.slots) / self.maxSlots
            self._steps += 1
            self._busySteps += occupied
            sm.decode_steps().inc(model=self.name)
            sm.slot_occupancy().set(occupied, model=self.name)
            if self.pool.spec.ringLayers:
                self._updateRingGauges(flight.lengths)
            after = self.compileCacheSize()
            if self._cacheSeen is not None and after > self._cacheSeen:
                sm.compile_misses().inc(after - self._cacheSeen,
                                        model=self.name)
                self._cacheSeen = after
            else:
                sm.compile_hits().inc(model=self.name)

    def _growPages(self) -> Tuple[List[int], set]:
        """Before a step: retire what ran out of time, then give every
        slot the pages its next step writes; returns the slots that step
        and those deferred a round."""
        now = time.monotonic()
        for s, seq in enumerate(self._slotSeq):
            # deadline sweep BETWEEN steps: an expired sequence's pages
            # go back to the free list before the next dispatch
            if seq is not None and seq.deadline is not None and \
                    now >= seq.deadline:
                serving_metrics().deadline_sheds().inc(model=self.name,
                                                       stage="decode")
                self._retireSlot(s, error=DeadlineExceeded(
                    "end-to-end deadline expired mid-decode"))
        # page growth in ADMISSION-AGE order: a slot may only preempt
        # YOUNGER slots, and when none are left it DEFERS one step
        # instead — the oldest sequence therefore always progresses and
        # finishes, so a pool squeeze degrades to serial service rather
        # than two big sequences preempting each other forever
        deferred = set()
        for s in list(self._admitOrder):
            if self._slotSeq[s] is None:
                continue
            while not self.pool.ensure(s, int(self._pos[s]) + 1):
                order = list(self._admitOrder)
                younger = order[order.index(s) + 1:]
                victim = next((v for v in reversed(younger)
                               if self._slotSeq[v] is not None), None)
                if victim is None:
                    deferred.add(s)
                    break
                self._preempt(victim)
        active = [i for i, s in enumerate(self._slotSeq)
                  if s is not None and i not in deferred]
        return active, deferred

    def _emitStep(self, flight: _Flight, g) -> None:
        """Deliver one step's tokens, slot by slot: emission, slot state,
        timeline note, retirement."""
        sm = serving_metrics()
        for s in flight.slots:
            seq = flight.seqs[s]
            held = self._slotSeq[s] is seq
            if not held and seq not in self._parted:
                # it ended after the dispatch (EOS, cancelled, expired,
                # preempted): this token is nobody's
                sm.decode_tokens_discarded().inc(model=self.name)
                continue
            if seq.cancelled:
                self._retire(s, seq)
                continue
            if len(seq.emitted) < len(seq.forced):
                # teacher-forced replay: override the computed token
                # with the one the sequence already produced before the
                # move
                tok = int(seq.forced[len(seq.emitted)])
            else:
                # jaxlint: disable=host-sync -- g is the already-materialized host copy of this step's greedy tokens
                tok = int(g[s, 0])
            done = self._emit(seq, tok)
            if held:
                self._tok[s] = tok
            timeline_store().note(
                seq.ctx.traceId if seq.ctx is not None else None,
                "serving.decode.step", replica=self.name, slot=s,
                tokens=len(seq.emitted))
            if done:
                self._retire(s, seq)

    def _retire(self, slot: int, seq: _Seq) -> None:
        """Finish ``seq`` at the read of a step it took part in in
        ``slot``: it still holds the slot, or it has parted from it."""
        if seq in self._parted:
            self._parted.remove(seq)
            self._finishSeq(seq, None)
        else:
            self._retireSlot(slot)

    def _preempt(self, slot: int) -> None:
        """Evict the youngest slot to free pages: release everything it
        holds and requeue it at the FRONT.  Greedy decode is
        deterministic, so the restart regenerates the identical prefix;
        ``streamSkip`` swallows the re-emissions."""
        seq, _freed = self._vacate(slot)
        self._resetForReplay(seq)
        with self._cv:
            self._queue.appendleft(seq)
            self._queuedRows += 1
            self._queuedPages += seq.pages
        sm = serving_metrics()
        sm.preemptions().inc(model=self.name)
        timeline_store().note(
            seq.ctx.traceId if seq.ctx is not None else None,
            "serving.preempt", replica=self.name, slot=slot,
            tokens_kept=len(seq.forced))
        self._updatePageGauges()

    @staticmethod
    def _resetForReplay(seq: _Seq) -> None:
        """Rewind a sequence to restart-from-prompt state (preemption or
        failover): record the computed prefix for teacher-forcing, arm
        ``streamSkip`` so the re-emission is swallowed, clear the
        emitted list.  Exactly-once delivery follows: every token a
        client saw is in ``forced`` and will be re-emitted (skipped) in
        the same order; every token it hasn't seen streams once."""
        if len(seq.emitted) > len(seq.forced):
            seq.forced = list(seq.emitted)
        seq.restarts += 1
        seq.streamSkip = seq.streamed
        seq.emitted = []

    def probe(self) -> bool:
        """Replica liveness check for the health prober: the injected
        fault registries (a chaos schedule's crash/brownout), the loop
        thread's liveness, and one tiny REAL device dispatch.  Runs a
        module-level jitted fn compiled once per process — NOT counted
        by ``compileCacheSize`` — so probing keeps the steady-state
        jit-miss counter flat.  Decode-path health is covered
        separately: a crashed step raises into ``_failBatch`` and the
        failover handler, it doesn't wait for a probe."""
        if _inj.replica_dead(self.name):
            return False
        if _inj.check_replica_crash(self.name):
            # an armed crash with no traffic to trip it: an IDLE crashed
            # replica must still go unhealthy (the loop's check only
            # runs when there is work)
            return False
        delay = _inj.replica_slowdown(self.name)
        if delay:
            time.sleep(delay)           # a browned-out replica probes slow
        if self._thread is not None and not self._thread.is_alive():
            return False
        x = jax.device_put(1, self._device) \
            if self._device is not None else 1
        # jaxlint: sync-ok -- the probe EXISTS to synchronize: its round-trip latency is the health signal
        out = jax.block_until_ready(_probe_fn()(x))
        # jaxlint: sync-ok -- probe verdict readback, off the decode path
        return int(out) == 2

    def evacuate(self) -> List[_Seq]:
        """Pull every queued AND in-flight sequence off this replica for
        failover, stopping the loop.  Returns the sequences reset for a
        from-prompt replay (cancelled ones are finished here instead).
        In-flight slots are stolen only after the loop thread actually
        JOINED — a wedged thread mid-``_stepOnce`` still owns its slot
        state, so a reaper thread waits it out and errors the leftovers
        (exactly-once beats availability: a maybe-double-delivered
        sequence is worse than a failed one)."""
        with self._cv:
            self._running = False
            queued = list(self._queue)
            self._queue.clear()
            self._queuedRows = 0
            self._queuedPages = 0
            self._cv.notify_all()
        inflight: List[_Seq] = []
        joined = True
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            joined = not self._thread.is_alive()
        if joined:
            self._thread = None
            for slot in list(self._admitOrder):
                if self._slotSeq[slot] is not None:
                    inflight.append(self._vacate(slot)[0])
            inflight.extend(self._takeParted())
            self._updatePageGauges()
        else:
            # wedged mid-step: its slots cannot be failed over safely
            # (the step may still emit).  A reaper outlives the wedge
            # and errors whatever is left.
            wedged = self._thread

            def reap():
                wedged.join()
                err = RuntimeError(f"replica {self.name!r} evacuated "
                                   f"while wedged mid-step")
                for slot, seq in enumerate(self._slotSeq):
                    if seq is not None:
                        self._retireSlot(slot, error=err)
                for seq in self._takeParted():
                    self._finishSeq(seq, err)
            threading.Thread(target=reap, daemon=True,
                             name=f"wedge-reap-cbatch-{self.name}"
                             ).start()
        out: List[_Seq] = []
        ts = timeline_store()
        for seq in inflight + queued:
            if seq.cancelled:
                self._finishSeq(seq, None)
                continue
            self._resetForReplay(seq)
            ts.note(seq.ctx.traceId if seq.ctx is not None else None,
                    "serving.evacuate", replica=self.name,
                    reason="replica evacuated")
            out.append(seq)
        serving_metrics().queue_depth().set(0, model=self.name)
        return out

    def _retireSlot(self, slot: int, error: Optional[BaseException] = None,
                    parting: bool = False) -> None:
        seq, freed = self._vacate(slot)
        self._retireLog.append((time.monotonic(), freed))
        sm = serving_metrics()
        sm.sequences_retired().inc(model=self.name)
        self._updatePageGauges()
        if parting:
            # its last token is still on the device: the verdict waits
            # for the read of that step
            self._parted.append(seq)
        else:
            self._finishSeq(seq, error)

    def _vacate(self, slot: int) -> Tuple[Optional[_Seq], int]:
        """Take the sequence out of ``slot`` and give back its pages;
        returns it and the number of pages freed."""
        seq = self._slotSeq[slot]
        freed = self.pool.release(slot)
        self._slotSeq[slot] = None
        self._pos[slot] = self._start[slot] = self._tok[slot] = 0
        if slot in self._admitOrder:
            self._admitOrder.remove(slot)
        return seq, freed

    def _takeParted(self) -> List[_Seq]:
        """The sequences that parted from their slots and were not read
        to their end, when the step that owes them a token is dropped."""
        parted, self._parted = self._parted, []
        return parted

    def _finishSeq(self, seq: _Seq, error: Optional[BaseException]) -> None:
        _finish_seq(seq, error, self.name)

    def _updateRingGauges(self, lengths) -> None:
        """After a step is read: the ring rows that are live now (they
        grow with a sequence until it passes the window) and the rings
        that wrapped in that step, whose slots then held ``lengths``."""
        sm = serving_metrics()
        rows = self.pool.ringRowsFor(self._pos - self._start)
        sm.ring_rows_in_use().set(rows, model=self.name)
        sm.cache_bytes().set(rows * self.pool.ringRowBytes,
                             model=self.name, kind="ring")
        wraps = int(np.count_nonzero(
            lengths % self.pool.spec.ringRows == 0))
        if wraps:
            sm.ring_wraps().inc(wraps, model=self.name)

    def _updatePageGauges(self) -> None:
        sm = serving_metrics()
        sm.kv_pages_in_use().set(self.pool.usedPages(), model=self.name,
                                 pool="target")
        sm.kv_pages_free().set(self.pool.freePages(), model=self.name,
                               pool="target")
        slots = self.pool.stateSlots()
        sm.state_slots_in_use().set(slots, model=self.name)
        sm.cache_bytes().set(self.pool.usedPages() * self.pool.pageBytes,
                             model=self.name, kind="paged")
        sm.cache_bytes().set(slots * self.pool.slotStateBytes,
                             model=self.name, kind="recurrent")
        if self.pool.indexPageBytes:
            sm.index_rows_bytes().set(
                self.pool.usedPages() * self.pool.indexPageBytes,
                model=self.name)


class _ReplicaQueueDepthRule(ThresholdRule):
    """``serving_queue_depth`` rule evaluating the replica set's LIVE
    queued rows (summed across replicas) and publishing them to the
    set-level gauge.  The gauge alone is written when a submit
    COMPLETES — during a cold burst every submit is still blocked (and
    streaming submits never write it), so a gauge-only rule would read
    0 at exactly the moment the autoscaler is needed."""

    def __init__(self, rs: "ReplicaSet", threshold: float):
        super().__init__("serving_queue_depth_high",
                         "dl4j_tpu_serving_queue_depth", ">=", threshold,
                         model=rs.name)
        self._rs = rs

    def evaluate(self, registry, now):
        depth = float(self._rs.queuedRows())
        serving_metrics().queue_depth().set(depth, model=self._rs.name)
        if depth >= self.threshold:
            return (f"dl4j_tpu_serving_queue_depth{{model="
                    f"{self._rs.name!r}}} = {depth:g} >= "
                    f"{self.threshold:g} (live replica-set backlog)")
        return None


class ReplicaSet:
    """Fan one registry route out over N executor replicas.

    ``factory(idx)`` builds replica ``idx`` (a
    :class:`ContinuousBatcher` or ``BucketedExecutor`` whose weights the
    factory has already placed — ``place_replica`` for one-chip DP
    copies, ``apply_inference_plan`` for a TP-sharded replica spanning
    several chips).  Requests route to the least-loaded live replica.
    ``scaleUp``/``scaleDown`` move the set by one replica;
    :meth:`armAutoscale` wires them to the ``serving_queue_depth``
    alert's firing/resolved edges through
    ``HealthMonitor.registerAction`` (counted in
    ``dl4j_tpu_health_actions_total``)."""

    def __init__(self, factory, name: str = "default", replicas: int = 1,
                 minReplicas: int = 1, maxReplicas: int = 8,
                 drainTimeout: float = 30.0, probeInterval: float = 0.5,
                 probeTimeout: float = 2.0, probeFailThreshold: int = 2,
                 submitRetries: int = 2, retryBackoff: float = 0.05,
                 retryMaxBackoff: float = 1.0, retryJitter: float = 0.5,
                 retryAfter: float = 1.0, seed: Optional[int] = None):
        self._factory = factory
        self.name = str(name)
        self.minReplicas = max(1, int(minReplicas))
        self.maxReplicas = max(self.minReplicas, int(maxReplicas))
        self._initial = max(self.minReplicas, int(replicas))
        self.drainTimeout = float(drainTimeout)
        # health probing (0 disables): a replica failing
        # probeFailThreshold CONSECUTIVE probes — each bounded by
        # probeTimeout on its own thread, so a wedged probe can't wedge
        # the prober — leaves routing; one healthy pass resets the run
        self.probeInterval = float(probeInterval)
        self.probeTimeout = float(probeTimeout)
        self.probeFailThreshold = max(1, int(probeFailThreshold))
        # submit retry-against-another-replica policy: exponential
        # backoff with seeded jitter, bounded by the request's
        # remaining deadline budget
        self.submitRetries = max(0, int(submitRetries))
        self.retryBackoff = float(retryBackoff)
        self.retryMaxBackoff = float(retryMaxBackoff)
        self.retryJitter = float(retryJitter)
        self.retryAfter = float(retryAfter)
        self._rng = random.Random(seed)
        self._replicas: List = []
        self._nextIdx = 0
        self._pendingAdds = 0
        self._lock = threading.Lock()
        self._running = False
        self._reapers: List[threading.Thread] = []
        self._probes: List[threading.Thread] = []

    def start(self) -> "ReplicaSet":
        with self._lock:
            if self._running:
                return self
            self._running = True
        while self.replicaCount() < self._initial:
            if self._addReplica() is None:
                break
        return self

    def _addReplica(self, force: bool = False):
        """Build + start one replica.  The slow factory/warm work runs
        OUTSIDE the lock; admission into the routing set re-checks
        ``_running``/``maxReplicas`` under it, so a racing shutdown (or
        a second concurrent scaleUp) can never leak a live replica or
        overshoot the cap — a replica that loses the re-check is shut
        down, not stranded.  ``force`` lifts the cap check for
        :meth:`swap`, which adds the green replica BEFORE removing the
        blue one (momentarily maxReplicas + 1)."""
        with self._lock:
            if not self._running or (
                    not force and
                    len(self._replicas) + self._pendingAdds >=
                    self.maxReplicas):
                return None
            self._pendingAdds += 1
            idx = self._nextIdx
            self._nextIdx += 1
        ex = None
        started = False
        try:
            ex = self._factory(idx)
            if getattr(ex, "name", None) in (None, "default"):
                ex.name = f"{self.name}/{idx}"
            # ex.start() warms every executable BEFORE the replica can
            # be routed to — a swapped-in replica never serves cold
            ex.start()
            started = True
        finally:
            with self._lock:
                self._pendingAdds -= 1
                admitted = started and self._running and (
                    force or len(self._replicas) < self.maxReplicas)
                if admitted:
                    self._replicas.append(ex)
                    n = len(self._replicas)
        if not admitted:
            if ex is not None:
                ex.shutdown()
            return None
        if hasattr(ex, "onSequenceFailure"):
            # the in-flight failover seam: a failed shared step hands
            # its live sequences here instead of erroring them
            ex.onSequenceFailure = self._onBatchFailure
        sm = serving_metrics()
        sm.replicas().set(n, model=self.name)
        sm.replica_health().set(1, model=self.name,
                                replica=getattr(ex, "name", str(idx)))
        self._startProbe(ex)
        return ex

    # -- health probing -------------------------------------------------
    def _startProbe(self, ex) -> None:
        if self.probeInterval <= 0 or not hasattr(ex, "probe"):
            return
        th = threading.Thread(
            target=self._probeLoop, args=(ex,), daemon=True,
            name=f"replica-probe-{getattr(ex, 'name', '?')}")
        th.start()
        with self._lock:
            self._probes.append(th)

    def _probeOnce(self, ex) -> bool:
        """One probe attempt, bounded by ``probeTimeout`` on its OWN
        short-lived thread — a wedged device dispatch hangs that thread,
        not the prober (the DeviceHealthProbe discipline)."""
        result: List[bool] = []

        def attempt():
            try:
                result.append(bool(ex.probe()))
            except Exception:
                result.append(False)
        t = threading.Thread(target=attempt, daemon=True,
                             name=f"probe-once-{getattr(ex, 'name', '?')}")
        t.start()
        t.join(self.probeTimeout)
        return bool(result) and result[0]

    def _probeLoop(self, ex) -> None:
        fails = 0
        sm = serving_metrics()
        rname = getattr(ex, "name", "?")
        while True:
            with self._lock:
                if not self._running or ex not in self._replicas:
                    return
            if self._probeOnce(ex):
                fails = 0
                sm.replica_health().set(1, model=self.name,
                                        replica=rname)
            else:
                fails += 1
                if fails >= self.probeFailThreshold:
                    sm.replica_health().set(0, model=self.name,
                                            replica=rname)
                    self._retireReplica(
                        ex, reason=f"{fails} consecutive probe failures")
                    return
            time.sleep(self.probeInterval)

    def _retireReplica(self, ex, reason: str = "") -> None:
        """Remove an UNHEALTHY replica from routing and fail its work
        over to survivors.  Health retirement ignores ``minReplicas`` —
        keeping a dead replica in the route to satisfy a floor just
        converts every Nth request into an error."""
        with self._lock:
            if ex not in self._replicas:
                return
            self._replicas.remove(ex)
            n = len(self._replicas)
        sm = serving_metrics()
        sm.replicas().set(n, model=self.name)
        sm.replica_health().set(0, model=self.name,
                                replica=getattr(ex, "name", "?"))
        if hasattr(ex, "evacuate"):
            seqs = ex.evacuate()
            if seqs:
                self._failover(seqs, note=reason)
        # the dead replica's shutdown can block (a wedged loop thread):
        # reap it off-path so retirement itself never wedges
        th = threading.Thread(target=ex.shutdown, daemon=True,
                              name=f"replica-reaper-{self.name}")
        th.start()
        with self._lock:
            self._reapers.append(th)

    def _failover(self, seqs: Sequence[_Seq], note: str = "",
                  exclude=None) -> None:
        """Re-home evacuated sequences on survivors: each lands at a
        survivor's FIFO head (it already waited its turn) and replays
        from the prompt, ``streamSkip``/``forced`` making the move
        invisible to the client.  A sequence whose deadline already
        expired — or with no survivor to take it — finishes with the
        error instead."""
        sm = serving_metrics()
        ts = timeline_store()
        for seq in seqs:
            tid = seq.ctx.traceId if seq.ctx is not None else None
            if seq.deadline is not None and \
                    time.monotonic() >= seq.deadline:
                sm.deadline_sheds().inc(model=self.name, stage="failover")
                ts.note(tid, "serving.shed", replica=self.name,
                        stage="failover")
                _finish_seq(seq, DeadlineExceeded(
                    "end-to-end deadline expired during failover"),
                    self.name)
                continue
            with self._lock:
                live = list(self._replicas)
            cands = [e for e in live
                     if hasattr(e, "_enqueue") and e is not exclude] or \
                    [e for e in live if hasattr(e, "_enqueue")]
            target = min(cands, key=lambda e: e.queuedRows()) \
                if cands else None
            if target is None:
                _finish_seq(seq, NoHealthyReplicas(
                    f"no survivor to adopt sequence after failover"
                    f"{' (' + note + ')' if note else ''}",
                    retryAfter=self.retryAfter), self.name)
                continue
            try:
                target._enqueue([seq], front=True)
                sm.failovers().inc(model=self.name)
                ts.note(tid, "serving.failover",
                        to=getattr(target, "name", "?"),
                        note=note or None)
            except Exception as e:
                _finish_seq(seq, e, self.name)

    def _onBatchFailure(self, source, seqs, error) -> None:
        self._failover(seqs,
                       note=f"{type(error).__name__}: {error}",
                       exclude=source)

    def replicaCount(self) -> int:
        with self._lock:
            return len(self._replicas)

    def scaleUp(self) -> Optional[str]:
        """One replica up (the queue-depth alert's firing-edge
        remediation); None when already at ``maxReplicas`` or shut
        down."""
        if self._addReplica() is None:
            return None
        return f"scaled {self.name} up to {self.replicaCount()} replicas"

    def scaleDown(self) -> Optional[str]:
        """One replica down (the resolved-edge remediation): the replica
        leaves the routing set immediately and a reaper thread drains
        its backlog before shutdown; None at ``minReplicas``."""
        with self._lock:
            if not self._running or len(self._replicas) <= self.minReplicas:
                return None
            ex = self._replicas.pop()       # stops routing to it NOW
            n = len(self._replicas)
        sm = serving_metrics()
        sm.replicas().set(n, model=self.name)
        sm.replica_health().set(0, model=self.name,
                                replica=getattr(ex, "name", "?"))
        th = threading.Thread(target=self._drainStop, args=(ex,),
                              daemon=True,
                              name=f"replica-reaper-{self.name}")
        th.start()
        with self._lock:
            self._reapers.append(th)
        return f"scaled {self.name} down to {n} replicas"

    def _drainStop(self, ex) -> None:
        """Graceful drain: the replica is already out of routing, so its
        backlog only shrinks — let every in-flight sequence finish,
        bounded by ``drainTimeout``; stragglers past the bound are
        evacuated and failed over to survivors (not dropped)."""
        t0 = time.monotonic()
        deadline = t0 + self.drainTimeout
        busy = getattr(ex, "busy", None)
        while time.monotonic() < deadline and (
                ex.queuedRows() > 0 or (busy is not None and busy())):
            time.sleep(0.05)
        if hasattr(ex, "evacuate") and (
                ex.queuedRows() > 0 or (busy is not None and busy())):
            stragglers = ex.evacuate()
            if stragglers:
                self._failover(stragglers, note="drain timeout",
                               exclude=ex)
        ex.shutdown()
        serving_metrics().drain_seconds().observe(
            time.monotonic() - t0, model=self.name)

    def swap(self, factory=None) -> Optional[str]:
        """Blue/green rollover (ROADMAP item 4's serving primitive):
        for each current replica, build + WARM a replacement from
        ``factory`` (default: the current one), route to it, then drain
        and retire the old replica through the ``scaleDown`` reaper
        path.  In-flight streams on the old replica finish (or fail
        over past ``drainTimeout``); new requests land on the
        replacement, which entered the route fully warmed from the AOT
        cache — no cold-compile window."""
        if factory is not None:
            self._factory = factory
        with self._lock:
            olds = list(self._replicas)
        swapped = 0
        for old in olds:
            new = self._addReplica(force=True)
            if new is None:
                break
            with self._lock:
                if old not in self._replicas:   # crashed/retired already
                    continue
                self._replicas.remove(old)
                n = len(self._replicas)
            sm = serving_metrics()
            sm.replicas().set(n, model=self.name)
            sm.replica_health().set(0, model=self.name,
                                    replica=getattr(old, "name", "?"))
            th = threading.Thread(target=self._drainStop, args=(old,),
                                  daemon=True,
                                  name=f"replica-reaper-{self.name}")
            th.start()
            with self._lock:
                self._reapers.append(th)
            swapped += 1
        if swapped == 0:
            return None
        return f"swapped {swapped} replica(s) behind {self.name}"

    def _pick(self):
        with self._lock:
            if not self._replicas:
                raise NoHealthyReplicas(
                    f"replica set {self.name!r} has no live replicas",
                    retryAfter=self.retryAfter)
            return min(self._replicas, key=lambda e: e.queuedRows())

    def _retryDelay(self, attempt: int,
                    deadline: Optional[float]) -> float:
        """Bounded exponential backoff with seeded jitter, clipped to
        the request's remaining deadline budget (raises when none is
        left — retrying past the deadline only wastes a survivor's
        slot)."""
        delay = min(self.retryBackoff * (2 ** attempt),
                    self.retryMaxBackoff)
        delay *= 1.0 + self.retryJitter * self._rng.random()
        if deadline is not None and \
                time.monotonic() + delay >= deadline:
            sm = serving_metrics()
            sm.deadline_sheds().inc(model=self.name, stage="retry")
            raise DeadlineExceeded(
                "end-to-end deadline leaves no budget for a retry")
        return delay

    @staticmethod
    def _requestDeadline(payload) -> Optional[float]:
        dl = payload.get("deadlineSeconds") \
            if isinstance(payload, dict) else None
        if dl is None:
            return None
        dl = float(dl)  # jaxlint: sync-ok -- deadlineSeconds arrives as host JSON, not a device scalar
        if not dl >= 0.0:
            raise ValueError("deadlineSeconds must be >= 0")
        return time.monotonic() + dl

    def submit(self, payload, timeout: Optional[float] = None):
        """Route to the least-loaded replica; a replica-side FAILURE
        (not a client error, not an admission shed, not a deadline)
        retries against another replica with backoff + jitter, honoring
        the remaining deadline budget."""
        deadline = self._requestDeadline(payload)
        attempt = 0
        while True:
            ex = self._pick()
            try:
                out = ex.submit(payload, timeout)
            except (ServiceOverloaded, NoHealthyReplicas,
                    DeadlineExceeded, TimeoutError, ValueError,
                    TypeError):
                raise               # deterministic / client-owned: no retry
            except Exception:
                if attempt >= self.submitRetries:
                    raise
                time.sleep(self._retryDelay(attempt, deadline))
                attempt += 1
                continue
            serving_metrics().queue_depth().set(self.queuedRows(),
                                                model=self.name)
            return out

    def submitStream(self, payload):
        """Streaming route with the same retry policy around CREATION
        (validate + enqueue happen eagerly, before any token, so a
        failed submit here never half-delivered anything)."""
        deadline = self._requestDeadline(payload)
        attempt = 0
        while True:
            ex = self._pick()
            if not hasattr(ex, "submitStream"):
                raise ValueError(
                    f"replica set {self.name!r} does not stream")
            try:
                return ex.submitStream(payload)
            except (ServiceOverloaded, NoHealthyReplicas,
                    DeadlineExceeded, TimeoutError, ValueError,
                    TypeError):
                raise
            except Exception:
                if attempt >= self.submitRetries:
                    raise
                time.sleep(self._retryDelay(attempt, deadline))
                attempt += 1

    def queuedRows(self) -> int:
        with self._lock:
            return sum(e.queuedRows() for e in self._replicas)

    def shutdown(self) -> None:
        with self._lock:
            self._running = False
            reps, self._replicas = self._replicas, []
        for ex in reps:
            ex.shutdown()
        for pth in self._probes:
            pth.join(timeout=max(5.0, self.probeTimeout +
                                 self.probeInterval + 1.0))
        for rth in self._reapers:
            rth.join(timeout=35.0)
        self._probes = []
        self._reapers = []

    def armAutoscale(self, monitor, highQueueRows: int = 64,
                     rule: Optional[ThresholdRule] = None) -> ThresholdRule:
        """Wire the self-healing loop (ROADMAP item 5's serving
        remainder): a ``serving_queue_depth`` rule on ``monitor`` whose
        FIRING edge scales one replica up and whose RESOLVED edge
        scales one back down.  The default rule reads the set's LIVE
        backlog (see :class:`_ReplicaQueueDepthRule`); pass ``rule`` to
        watch something else."""
        rule = rule or _ReplicaQueueDepthRule(self, highQueueRows)
        monitor.rules.append(rule)

        def scale_up(_rule, _detail):
            return self.scaleUp()

        def scale_down(_rule, _detail):
            return self.scaleDown()

        monitor.registerAction(rule.name, scale_up)
        monitor.registerAction(rule.name, scale_down, on="resolved")
        return rule
