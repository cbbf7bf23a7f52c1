"""Sharded multi-process input pipeline with double-buffered async H2D.

The streaming gap this closes: a device-resident pipeline keeps the chip
fed while a single-process streaming path feeds it a small fraction of
that — the chip starves the moment data doesn't already live on device
(today's ratio on the chip: not measured).  Two serial bottlenecks cause
it: Python decode/augment runs on
one GIL, and every batch's host->device copy blocks the step that needs
it.  This module splits both out of the training loop:

1. **Producer pool** — ``numWorkers`` OS processes (``multiprocessing``,
   fork by default so the decode code needs no re-import; the parent may
   hold the chip, which belongs to one process, so workers stay
   numpy-only — ``chip_smoke.py`` runs exactly this on the TPU), each
   handed a deterministic :class:`ShardSpec`.  The record source shards per
   worker — per-host first (the ``SharedTrainingMaster`` /
   ``jax.process_index()`` convention, the per-host data sharding of
   Spark DataVec in the source paper), then per-worker within the host —
   so no record is decoded twice anywhere in the pod.  Workers assemble
   fixed-shape batches directly into **shared-memory slots** (one
   memcpy, no pickle of the pixel payload) and post slot metadata on a
   queue; slot recycling is the pool's backpressure.
2. **Double-buffered async H2D** — the consumer stages each assembled
   batch onto the device immediately (``jax.device_put``, asynchronous)
   into a ``stagingDepth``-deep ring (default 2): the transfer of batch
   N+1 overlaps the device step on batch N, and retiring a ring entry
   drops the previous device buffer so the allocator reuses it (the
   buffer-donation discipline of the fused train step, applied to input
   staging).

Crash discipline mirrors ``AsyncDataSetIterator``'s sentinel contract: a
worker that dies — exception (pickled through the queue) or hard kill
(detected by liveness polling, since a SIGKILLed producer can post no
sentinel) — surfaces as :class:`ProducerWorkerError` in the consumer, so
a truncated epoch can never look like a clean end.

Telemetry reports through the shared ``dl4j_tpu_etl_*`` namespace
(:func:`deeplearning4j_tpu.telemetry.etl_metrics`): queue depth,
consumers-waiting and producer-active gauges keep the watchdog's
``etl_starvation`` rule working unchanged, and the new
``dl4j_tpu_etl_h2d_bytes_total`` / ``dl4j_tpu_etl_h2d_seconds`` series
measure the transfer stage itself (bytes put on the device, and the
seconds the put took).

The fit paths (``MultiLayerNetwork.fit``, ``ParallelWrapper.fit``,
``FaultTolerantTrainer``) engage this automatically via
:func:`maybe_prefetch` whenever the wrapped iterator reports
``streaming() == True``; tune with ``DL4J_TPU_ETL_WORKERS`` (0 disables)
or construct :class:`PrefetchingDataSetIterator` directly.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import multiprocessing as _mp
import os
import pickle
import queue as _queue
import threading
import time
import weakref
from multiprocessing import shared_memory as _shm
from typing import Dict, Optional

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterator import DataSetIterator

log = logging.getLogger(__name__)

__all__ = ["ShardSpec", "PrefetchingDataSetIterator", "ProducerWorkerError",
           "RaggedFeatureReader", "hash_feature", "maybe_prefetch",
           "default_host_spec", "stage_batch"]

_FIELDS = ("features", "labels", "featuresMask", "labelsMask")

# every array a batch carries across the process/device boundary: the
# DL4J quadruple plus the ragged-batch offsets sidecar.  Workers and the
# staging ring must transfer ALL of these — the queue-pickle fallback
# for oversized batches once serialized only _FIELDS and silently
# dropped the offsets a RaggedFeatureReader attaches.
_XFER_FIELDS = _FIELDS + ("offsets",)


# ----------------------------------------------------------- sharding ----

@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Deterministic shard assignment for one producer worker.

    The global shard index flattens host-major — host h, worker w of W
    owns shard ``h*W + w`` of ``H*W`` — matching the
    ``SharedTrainingMaster`` host-index convention
    (``jax.process_index()``), so a pod-wide run reads every record
    exactly once with no coordination beyond the spec itself.
    """

    hostIndex: int = 0
    hostCount: int = 1
    workerIndex: int = 0
    workerCount: int = 1
    # epoch generation of this worker pool start: the pickled source
    # blob is frozen, so per-epoch variation (augmentation RNG,
    # factory-side shuffling) must key off this — see ``setEpoch``
    epoch: int = 0

    @property
    def shardIndex(self) -> int:
        return self.hostIndex * self.workerCount + self.workerIndex

    @property
    def shardCount(self) -> int:
        return self.hostCount * self.workerCount

    def owns(self, recordIndex: int) -> bool:
        return recordIndex % self.shardCount == self.shardIndex


def default_host_spec() -> tuple:
    """(hostIndex, hostCount) from the JAX distributed runtime when one
    is initialized (the ``SharedTrainingMaster.connect`` path), else
    (0, 1)."""
    try:
        import jax
        return int(jax.process_index()), int(jax.process_count())
    except Exception:
        return 0, 1


def _resolve_shard(source, spec: ShardSpec):
    """Shard ``source`` for one worker.

    - a callable is a batch factory: ``source(spec)`` returns the
      worker's iterable of DataSets (full control, e.g. synthetic
      sources);
    - an iterator exposing ``shard(index, count)`` (the RecordReader
      iterators) shards at RECORD granularity — each worker decodes only
      its slice;
    - anything else falls back to batch-granularity ownership: every
      worker drains the full source but emits only batches
      ``i % shardCount == shardIndex`` (correct, but decode is not
      parallelized — sources that matter should implement ``shard``).
    """
    if callable(source) and not isinstance(source, DataSetIterator):
        return source(spec)
    shard = getattr(source, "shard", None)
    if shard is not None:
        try:
            return shard(spec.shardIndex, spec.shardCount)
        except NotImplementedError:
            pass
    return _ModuloBatches(source, spec)


class _ModuloBatches:
    def __init__(self, source, spec: ShardSpec):
        self.source, self.spec = source, spec

    def __iter__(self):
        for i, ds in enumerate(_iter_batches(self.source)):
            if self.spec.owns(i):
                yield ds


def _iter_batches(src):
    if hasattr(src, "hasNext") and hasattr(src, "next"):
        # manual drain of the DataSetIterator SPI (duck-typed: bench /
        # user sources need not subclass), not the python protocol —
        # __next__ routes through the parent-process telemetry helpers,
        # which a pool worker must not touch
        if hasattr(src, "reset"):
            src.reset()
        while src.hasNext():
            yield src.next()
    else:
        yield from src


# ------------------------------------------------------- worker process ----

def _to_np(x) -> Optional[np.ndarray]:
    if x is None:
        return None
    if hasattr(x, "numpy"):
        # jaxlint: sync-ok -- producer worker is host-side by design (decode into shm, never jax)
        x = x.numpy()
    # jaxlint: sync-ok -- contiguous host copy is what the shm slot memcpy requires
    return np.ascontiguousarray(np.asarray(x))


def _untrack(seg, untrack: bool) -> None:
    """Drop the attach-side resource_tracker registration — but ONLY in a
    spawn-started worker, whose own fresh tracker would otherwise unlink
    the parent's live segments when the worker exits.  A fork-started
    worker shares the parent's tracker (register is a dedup no-op there),
    and unregistering would corrupt the parent's cache instead."""
    if not untrack:
        return
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister(seg._name, "shared_memory")
    except Exception:
        pass


def _worker_main(sourceBlob: bytes, spec: ShardSpec, shmNames, shmBytes: int,
                 freeQ, metaQ, stopEvt, untrack: bool = False) -> None:
    """Producer-pool worker body.  Runs in a child process: numpy decode
    only — it must never import jax or touch the parent's telemetry.
    Exits through the sentinel discipline: exactly one terminal message,
    ``("err", ...)`` then ``("end", ...)`` on crash, bare ``("end", ...)``
    on a clean drain."""
    segs = {}
    try:
        # FIRST: pin this process to host-only arrays.  A fork child
        # inherits the parent's XLA runtime mid-whatever-it-was-doing;
        # one jnp.asarray from DataSet construction here can deadlock on
        # a mutex some parent thread held at fork time.
        from deeplearning4j_tpu.ops.ndarray import set_host_only_arrays
        set_host_only_arrays(True)
        source = pickle.loads(sourceBlob)
        # the blob is the SAME bytes every epoch — without an epoch
        # signal, augmentation RNG would replay byte-identically each
        # generation (the inline path's reader RNG advances instead)
        setEpoch = getattr(source, "setEpoch", None)
        if setEpoch is not None:
            setEpoch(spec.epoch)
        it = _resolve_shard(source, spec)
        for ds in _iter_batches(it):
            if stopEvt.is_set():
                break
            fields = [_to_np(getattr(ds, f, None)) for f in _XFER_FIELDS]
            nbytes = sum(a.nbytes for a in fields if a is not None)
            if nbytes > shmBytes:
                # oversized batch: pickle through the queue (slower, but
                # the contract survives any shape)
                metaQ.put(("inline", spec.workerIndex, fields))
                continue
            slot = None
            while slot is None and not stopEvt.is_set():
                try:
                    slot = freeQ.get(timeout=0.1)
                except _queue.Empty:
                    pass
            if slot is None:        # stopping while blocked on a slot
                break
            seg = segs.get(slot)
            if seg is None:
                seg = segs[slot] = _shm.SharedMemory(name=shmNames[slot])
                _untrack(seg, untrack)
            off, metas = 0, []
            for a in fields:
                if a is None:
                    metas.append(None)
                    continue
                np.ndarray(a.shape, dtype=a.dtype, buffer=seg.buf,
                           offset=off)[...] = a
                metas.append((a.shape, str(a.dtype), off))
                off += a.nbytes
            metaQ.put(("batch", spec.workerIndex, slot, metas))
    except BaseException as e:
        import traceback
        metaQ.put(("err", spec.workerIndex, type(e).__name__, str(e),
                   traceback.format_exc()))
    finally:
        metaQ.put(("end", spec.workerIndex))
        for seg in segs.values():
            try:
                seg.close()
            except Exception:
                pass


# ------------------------------------------------------------ H2D ring ----

def _device_put(a, device):
    """``device`` may be a Device OR a Sharding — a MeshTrainer plan's
    batch NamedSharding routes here so sharded inputs land DIRECTLY on
    their mesh shards instead of replicated-then-resharded inside the
    step.  A batch the sharding rejects (ragged tail not divisible by
    the data axis) falls back to default placement — the step's own
    ``_place_batch`` handles it the same way."""
    if a is None:
        return None
    try:
        import jax
        if device is None:
            return jax.device_put(a)
        try:
            return jax.device_put(a, device)
        except ValueError:
            return jax.device_put(a)
    except Exception:
        return a        # no backend: hand the host array through


class _StagedBatch:
    """One in-flight H2D transfer.  ``device_put`` is asynchronous: the
    copy engine runs while the consumer's device step executes, and
    :meth:`materialize` only pays whatever tail hasn't completed yet —
    near zero once the ring is warm."""

    __slots__ = ("dev", "nbytes", "issueSeconds", "issuedAt")

    def __init__(self, fields, device):
        from deeplearning4j_tpu.telemetry import etl_metrics
        self.nbytes = sum(a.nbytes for a in fields if a is not None)
        t0 = time.perf_counter()
        self.dev = [_device_put(a, device) for a in fields]
        self.issuedAt = t0
        self.issueSeconds = time.perf_counter() - t0
        etl_metrics().h2d_bytes().inc(self.nbytes)

    def materialize(self) -> DataSet:
        from deeplearning4j_tpu.telemetry import etl_metrics, tracer
        t0 = time.perf_counter()
        for a in self.dev:
            if a is not None and hasattr(a, "block_until_ready"):
                try:
                    # jaxlint: sync-ok -- the sync IS the H2D completion fence of the staging ring
                    a.block_until_ready()
                except AttributeError:  # pragma: no cover
                    pass
        wait = time.perf_counter() - t0
        etl_metrics().h2d_seconds().observe(self.issueSeconds + wait)
        from deeplearning4j_tpu.telemetry.instrument import \
            observe_step_phase
        observe_step_phase("h2d", self.issueSeconds + wait)
        tracer().record_complete(
            "h2d_stage", self.issuedAt, self.issueSeconds + wait,
            # jaxlint: disable=host-sync -- nbytes is a Python int, not a device scalar
            args={"bytes": int(self.nbytes)})
        return DataSet(*self.dev)


def stage_batch(ds, device) -> _StagedBatch:
    """Stage a DataSet's arrays onto ``device`` (a Device or a mesh
    batch Sharding) asynchronously; ``.materialize()`` later returns the
    on-device DataSet after the completion fence.  Used by
    ``AsyncDataSetIterator`` so its thread-prefetch path gets the same
    direct-to-shard H2D routing as the producer pool."""
    fields = []
    for name in _XFER_FIELDS:
        a = getattr(ds, name, None)
        fields.append(None if a is None
                      else (a.jax if hasattr(a, "jax") else a))
    return _StagedBatch(fields, device)


# ------------------------------------------------------------- consumer ----

class ProducerWorkerError(RuntimeError):
    """A producer-pool worker died — either with an exception (original
    type/message/traceback attached) or without a sentinel (killed)."""

    def __init__(self, workerIndex: int, message: str,
                 childTraceback: str = ""):
        super().__init__(f"ETL producer worker {workerIndex}: {message}")
        self.workerIndex = workerIndex
        self.childTraceback = childTraceback


class PrefetchingDataSetIterator(DataSetIterator):
    """Drop-in DataSetIterator over a sharded producer pool + H2D ring.

    ``source`` is either a picklable :class:`DataSetIterator` (sharded
    per worker through its ``shard()`` when available) or a callable
    ``factory(spec: ShardSpec) -> iterable[DataSet]``.  The pool starts
    lazily on first ``hasNext()`` and restarts on ``reset()`` (one
    worker generation per epoch — the pool analogue of
    ``AsyncDataSetIterator``'s producer restart).  ``close()`` releases
    the shared-memory slots; the fit paths that auto-engage the pool
    call it, and a finalizer covers leaked instances.

    Tuning knobs: ``numWorkers`` (decode parallelism), ``queueDepth``
    (shared-memory slots = in-flight assembled batches = producer
    backpressure), ``stagingDepth`` (device-side ring, 2 = double
    buffered), ``shmBytes`` (per-slot capacity; oversized batches fall
    back to queue pickling).
    """

    def __init__(self, source, numWorkers: int = 2, queueDepth: int = 4,
                 stagingDepth: int = 2, shmBytes: int = 32 << 20,
                 hostIndex: Optional[int] = None,
                 hostCount: Optional[int] = None,
                 device=None, startMethod: Optional[str] = None):
        if numWorkers < 1:
            raise ValueError("numWorkers must be >= 1")
        # pickle NOW: an unpicklable source must fail at construction
        # (where maybe_prefetch can fall back), not inside the first fit
        self._sourceBlob = pickle.dumps(source)
        self._wrapped = source if isinstance(source, DataSetIterator) \
            else None
        self.numWorkers = int(numWorkers)
        self.queueDepth = max(2, int(queueDepth))
        self.stagingDepth = max(1, int(stagingDepth))
        self.shmBytes = int(shmBytes)
        h, n = default_host_spec()
        self.hostIndex = h if hostIndex is None else int(hostIndex)
        self.hostCount = n if hostCount is None else int(hostCount)
        self.device = device
        method = startMethod or os.environ.get("DL4J_TPU_ETL_START_METHOD")
        if method is None:
            method = "fork" if "fork" in _mp.get_all_start_methods() \
                else "spawn"
        self._ctx = _mp.get_context(method)
        self._segs = []
        self._procs = []
        self._metaQ = self._freeQ = self._stopEvt = None
        self._ring = collections.deque()
        self._started = False
        self._exhausted = False
        self._endsSeen: set = set()
        self._liveProducers = 0
        self._closed = False
        self._epoch = -1
        self._pendingError: Optional[ProducerWorkerError] = None
        # health-remediation restart: the etl_starvation action sets the
        # event from the watchdog thread; the CONSUMER thread (which owns
        # the pool) notices at its next poll and restarts the workers,
        # fast-forwarding the new generation past the batches it already
        # delivered this epoch (numWorkers=1 supervised streams are
        # deterministic, so the skip is exact)
        self._restartReq = threading.Event()
        self._delivered = 0
        self._skip = 0
        # state the leak finalizer can reach without holding self: a
        # dropped-without-close() iterator must stop its workers (they
        # block on freeQ forever once the consumer is gone), not just
        # unlink the shm segments
        self._live = {"segs": self._segs, "procs": [], "stop": None}
        self._finalizer = weakref.finalize(
            self, PrefetchingDataSetIterator._cleanup_leaked, self._live)

    # -- lifecycle ------------------------------------------------------

    @staticmethod
    def _cleanup_leaked(state) -> None:
        stop = state.get("stop")
        if stop is not None:
            try:
                stop.set()
            except Exception:
                pass
        for p in state.get("procs", ()):
            try:
                if p.is_alive():
                    p.terminate()
            except Exception:
                pass
        PrefetchingDataSetIterator._cleanup_segments(state["segs"])

    @staticmethod
    def _cleanup_segments(segs) -> None:
        for seg in segs:
            try:
                seg.close()
                seg.unlink()
            except Exception:
                pass
        segs.clear()

    def _ensure_segments(self) -> None:
        while len(self._segs) < self.queueDepth:
            self._segs.append(_shm.SharedMemory(create=True,
                                                size=self.shmBytes))

    def _start(self) -> None:
        from deeplearning4j_tpu.telemetry import etl_metrics, tracer
        if self._closed:
            raise RuntimeError("iterator is closed")
        self._ensure_segments()
        self._metaQ = self._ctx.Queue()
        self._freeQ = self._ctx.Queue()
        for i in range(len(self._segs)):
            self._freeQ.put(i)
        self._stopEvt = self._ctx.Event()
        self._endsSeen = set()
        self._exhausted = False
        self._epoch += 1
        names = [seg.name for seg in self._segs]
        untrack = self._ctx.get_start_method() != "fork"
        self._procs = []
        with tracer().span("etl_pool_start", workers=self.numWorkers,
                           epoch=self._epoch):
            import warnings
            with warnings.catch_warnings():
                # py3.12+'s os.fork()-with-threads warning: the workers
                # run numpy decode only and never re-enter jax or its
                # thread pools, so the fork is safe here
                warnings.simplefilter("ignore", RuntimeWarning)
                for w in range(self.numWorkers):
                    spec = ShardSpec(self.hostIndex, self.hostCount, w,
                                     self.numWorkers, epoch=self._epoch)
                    p = self._ctx.Process(
                        target=_worker_main,
                        args=(self._sourceBlob, spec, names, self.shmBytes,
                              self._freeQ, self._metaQ, self._stopEvt,
                              untrack),
                        daemon=True)
                    p.start()
                    self._procs.append(p)
        self._live["procs"] = list(self._procs)
        self._live["stop"] = self._stopEvt
        self._liveProducers = self.numWorkers
        em = etl_metrics()
        em.producer_active().inc(self.numWorkers)
        em.pool_workers().set(self.numWorkers)
        self._started = True

    def _producer_done(self) -> None:
        if self._liveProducers > 0:
            self._liveProducers -= 1
            from deeplearning4j_tpu.telemetry import etl_metrics
            etl_metrics().producer_active().dec()

    def _shutdown(self) -> Optional[ProducerWorkerError]:
        """Stop the pool (keeps the shm slots for the next epoch).
        Returns the first worker error found while draining — a crash
        whose message was still queued must not be thrown away with the
        drain (``reset()`` re-raises it, mirroring the
        ``AsyncDataSetIterator.reset`` contract)."""
        if not self._started:
            return None
        from deeplearning4j_tpu.telemetry import etl_metrics
        err = None
        self._stopEvt.set()
        # drain pending metadata so worker feeder threads can flush and
        # exit; slots referenced by drained messages are simply unused
        try:
            while True:
                msg = self._metaQ.get_nowait()
                if err is None and msg and msg[0] == "err":
                    _, w, tname, text, tb = msg
                    err = ProducerWorkerError(w, f"{tname}: {text}", tb)
        except (_queue.Empty, OSError):
            pass
        deadline = time.monotonic() + 5.0
        for p in self._procs:
            p.join(timeout=max(0.0, deadline - time.monotonic()))
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=1.0)
        for q in (self._metaQ, self._freeQ):
            try:
                q.close()
                q.cancel_join_thread()
            except Exception:
                pass
        while self._liveProducers > 0:
            self._producer_done()
        etl_metrics().pool_workers().set(0)
        self._procs = []
        self._live["procs"] = []
        self._started = False
        return err

    def requestRestart(self) -> None:
        """Thread-safe producer-pool restart request — the
        ``etl_starvation`` alert remediation.  Callable from any thread
        (the watchdog fires it); the CONSUMER thread, which owns the
        pool, performs the actual teardown/restart at its next poll —
        including while it is blocked on the starved queue — and
        fast-forwards the fresh worker generation past the batches it
        already delivered this epoch, so no example is double-trained.

        The replay skip is EXACT only for ``numWorkers=1`` (the
        supervised default — multi-worker pools interleave shards
        scheduling-dependently, so a mid-epoch restart there is
        at-least-once, not exactly-once; the supervisor's remediation
        declines to restart those)."""
        self._restartReq.set()

    def _restart_pool(self) -> None:
        """Consumer-thread only: tear the pool down and restart the same
        ShardSpec epoch, skipping the already-delivered prefix on
        replay.  Staged-but-undelivered ring batches are dropped — the
        new generation reproduces them (they are NOT in the skip count),
        so delivery stays exactly-once."""
        from deeplearning4j_tpu.telemetry import etl_metrics
        log.warning("restarting ETL producer pool (epoch %d): replay "
                    "will skip the %d batch(es) already delivered",
                    max(self._epoch, 0), self._delivered)
        err = self._shutdown()
        if err is not None and self._pendingError is None:
            self._pendingError = err
        self._ring.clear()
        self._skip = self._delivered
        self._epoch -= 1    # same ShardSpec epoch: identical stream order
        self._start()
        etl_metrics().pool_restarts().inc()
        from deeplearning4j_tpu.telemetry.runlog import record_event
        record_event("etl.restart", delivered=self._delivered,
                     epoch=max(self._epoch, 0))

    def close(self) -> None:
        """Full teardown: pool + shared-memory slots.  Idempotent.
        Unlike ``reset()``, explicit teardown does not re-raise pending
        worker errors."""
        self._shutdown()
        self._pendingError = None
        self._ring.clear()
        self._restartReq.clear()
        self._delivered = self._skip = 0
        self._cleanup_segments(self._segs)
        self._closed = True

    # -- consumption ----------------------------------------------------

    def _dead_without_sentinel(self):
        for w, p in enumerate(self._procs):
            if w not in self._endsSeen and not p.is_alive():
                return w, p
        return None

    def _fail(self, exc: ProducerWorkerError) -> None:
        try:
            self._shutdown()
        finally:
            self._ring.clear()
            self._exhausted = True
        raise exc

    def _get_msg(self, block: bool):
        from deeplearning4j_tpu.telemetry import etl_metrics, note_etl_wait
        em = etl_metrics()
        try:
            depth = self._metaQ.qsize()
        except (NotImplementedError, OSError):  # pragma: no cover
            depth = -1
        if depth >= 0:
            em.queue_depth().set(depth)
        em.pool_workers().set(sum(p.is_alive() for p in self._procs))
        if not block:
            try:
                return self._metaQ.get_nowait()
            except _queue.Empty:
                return None
        waiting = None
        if depth == 0:
            # same starvation discipline as AsyncDataSetIterator: the
            # live waiting gauge is what EtlStarvationRule watches
            em.empty_polls().inc()
            waiting = em.consumers_waiting()
            waiting.inc()
        t0 = time.perf_counter()
        try:
            while True:
                try:
                    msg = self._metaQ.get(timeout=0.2)
                    break
                except _queue.Empty:
                    if self._restartReq.is_set():
                        # the starvation remediation: we ARE the blocked
                        # consumer the alert is about — restart the pool
                        # right here and resume polling the new queue
                        self._restartReq.clear()
                        self._restart_pool()
                        continue
                    dead = self._dead_without_sentinel()
                    if dead is None:
                        continue
                    # grace get: a cleanly-exited worker's ("end", w)
                    # can still be in the pipe when is_alive() first
                    # reads False — only a queue that stays empty past
                    # the grace window proves a sentinel-less death
                    try:
                        msg = self._metaQ.get(timeout=1.0)
                        break
                    except _queue.Empty:
                        w, p = dead
                        self._fail(ProducerWorkerError(
                            w, "died without sentinel "
                               f"(exitcode {p.exitcode})"))
        finally:
            if waiting is not None:
                waiting.dec()
        wait = time.perf_counter() - t0
        em.prefetch_wait().set(wait)
        note_etl_wait(wait, self)       # folds into the next etl_fetch
        return msg

    def _fill(self, block: bool) -> None:
        """Pull pool messages, staging up to ``stagingDepth`` batches on
        the device.  ``block`` only applies while the ring is empty —
        topping up never stalls the caller."""
        from deeplearning4j_tpu.telemetry import etl_metrics, tracer
        em = etl_metrics()
        while not self._exhausted and len(self._ring) < self.stagingDepth:
            if self._restartReq.is_set():
                self._restartReq.clear()
                if self._started:
                    self._restart_pool()
            msg = self._get_msg(block and not self._ring)
            if msg is None:
                return
            kind = msg[0]
            if kind == "batch":
                _, w, slot, metas = msg
                if self._skip > 0:
                    # replay fast-forward after a pool restart: recycle
                    # the slot without assembling the batch
                    self._skip -= 1
                    self._freeQ.put(slot)
                    continue
                t0 = time.perf_counter()
                fields = []
                for meta in metas:
                    if meta is None:
                        fields.append(None)
                        continue
                    shape, dtype, off = meta
                    view = np.ndarray(shape, dtype=dtype,
                                      buffer=self._segs[slot].buf,
                                      offset=off)
                    # private copy so the slot recycles immediately; the
                    # async device transfer then reads stable memory
                    # jaxlint: sync-ok -- host-to-host copy out of the shm slot, no device involved
                    fields.append(np.array(view, copy=True))
                self._freeQ.put(slot)
                tracer().record_complete("etl_assemble", t0,
                                         time.perf_counter() - t0)
                em.pool_batches().inc()
                self._ring.append(_StagedBatch(fields, self.device))
            elif kind == "inline":
                _, w, fields = msg
                if self._skip > 0:
                    self._skip -= 1
                    continue
                em.pool_batches().inc()
                em.pool_inline_batches().inc()
                self._ring.append(_StagedBatch(fields, self.device))
            elif kind == "end":
                self._endsSeen.add(msg[1])
                self._producer_done()
                if len(self._endsSeen) >= self.numWorkers:
                    self._exhausted = True
                    self._shutdown()
            else:   # ("err", worker, typename, message, traceback)
                _, w, tname, text, tb = msg
                self._producer_done()
                self._fail(ProducerWorkerError(w, f"{tname}: {text}", tb))

    def _raise_pending(self) -> None:
        if self._pendingError is not None:
            exc = self._pendingError
            self._pendingError = None
            raise exc

    def hasNext(self) -> bool:
        self._raise_pending()
        if not self._started and not self._exhausted:
            self._start()
        self._fill(block=True)
        return bool(self._ring)

    def next(self, num: int = 0) -> DataSet:
        if not self.hasNext():
            raise StopIteration
        staged = self._ring.popleft()
        self._delivered += 1
        ds = staged.materialize()
        # double buffering: issue the NEXT transfer before the caller
        # starts the step on this batch (non-blocking top-up).  A crash
        # surfacing during the top-up must not discard the good batch
        # already materialized — defer it to the next fetch.
        try:
            self._fill(block=False)
        except ProducerWorkerError as e:
            self._pendingError = e
        return self._applyPre(ds)

    def setDevice(self, device) -> None:
        """Retarget the H2D staging ring (elastic re-mesh: the plan's
        batch sharding changed mesh).  Applies from the NEXT staged
        batch; already-staged batches keep their old placement — the
        step's own ``_place_batch`` reconciles those stragglers."""
        self.device = device

    def reassign(self, hostIndex: Optional[int] = None,
                 hostCount: Optional[int] = None) -> None:
        """Re-assign this consumer's ShardSpec host slot (elastic
        re-mesh: a host left or joined the pod, so record ownership
        must repartition or records get double-read/dropped).  Stops
        the pool; the next ``hasNext()`` restarts it with the new spec
        FROM THE STREAM'S START — callers realign mid-epoch position
        via the supervisor's checkpoint skip fast-forward, exactly like
        a resume."""
        err = self._shutdown()
        if hostIndex is not None:
            # jaxlint: sync-ok -- host slot indices are Python ints, not device scalars
            self.hostIndex = int(hostIndex)
        if hostCount is not None:
            # jaxlint: sync-ok -- host slot indices are Python ints, not device scalars
            self.hostCount = int(hostCount)
        self._ring.clear()
        self._delivered = self._skip = 0
        self._exhausted = False
        if err is not None:
            self._pendingError = err

    def reset(self) -> None:
        err = self._shutdown()
        if err is None:
            err = self._pendingError
        self._pendingError = None
        self._ring.clear()
        self._restartReq.clear()
        self._delivered = self._skip = 0
        self._exhausted = False     # lazy restart on the next hasNext()
        if err is not None:
            # a crash drained away (or deferred from a next() top-up)
            # must not vanish in a reset: the prior epoch was truncated.
            # State is already clean — a follow-up reset()/hasNext()
            # restarts the pool normally.
            raise err

    # -- SPI delegation -------------------------------------------------

    def batch(self) -> int:
        return self._wrapped.batch() if self._wrapped is not None else -1

    def totalOutcomes(self) -> int:
        return self._wrapped.totalOutcomes() \
            if self._wrapped is not None else -1

    def inputColumns(self) -> int:
        return self._wrapped.inputColumns() \
            if self._wrapped is not None else -1

    def streaming(self) -> bool:
        return False        # already prefetched: never wrap twice


# ------------------------------------------------ ragged ingestion ----

# Knuth multiplicative hash constants (golden-ratio / 2^64 + the
# splitmix64 finalizer) — cheap, stateless, and identical across
# processes, so ETL workers and the serving tier hash raw feature
# values to the same embedding-table rows.
_HASH_MULT = 0x9E3779B97F4A7C15
_HASH_MIX = 0xBF58476D1CE4E5B9


def hash_feature(values, numEmbeddings: int) -> np.ndarray:
    """Hash raw categorical feature values into ``[0, numEmbeddings)``.

    Pure numpy (ETL workers must never import jax).  Accepts any
    integer array-like; returns int64 hashed ids of the same shape.
    """
    v = np.asarray(values, dtype=np.uint64)  # jaxlint: sync-ok -- host-side ETL hashing of raw python/numpy ids, no device buffers
    with np.errstate(over="ignore"):    # wraparound IS the hash
        h = (v + np.uint64(1)) * np.uint64(_HASH_MULT)
        h ^= h >> np.uint64(29)
        h *= np.uint64(_HASH_MIX)
        h ^= h >> np.uint64(32)
    return (h % np.uint64(numEmbeddings)).astype(np.int64)


class RaggedFeatureReader(DataSetIterator):
    """Streaming ragged/hashed-feature ingestion for the recommender
    tier (feeds ``ShardedEmbeddingBag``).

    Records are ``(values, label)`` pairs where ``values`` is one
    ragged list of raw categorical ids (``numFields == 1``) or a tuple
    of ``numFields`` such lists.  Each batch:

    - hashes raw ids into ``[0, numEmbeddings)`` (:func:`hash_feature`),
    - dedups ids PER ROW host-side (phase 1 of the two-phase sparse
      lookup: ``np.unique`` with counts — the duplicate multiplicity
      moves into the ``featuresMask`` weights, so sum-pooling is
      unchanged and only unique ids cross the interconnect),
    - pads every bag to the smallest bucket in ``bagBuckets`` that fits
      the batch's longest bag (id 0 / weight 0).  Raggedness therefore
      maps to a FINITE set of batch shapes — the fused train step
      compiles one executable per bucket and never re-traces on
      per-batch raggedness.

    The emitted DataSet carries features ``(b, numFields*bucket)``
    (float-encoded ids), featuresMask weights of the same shape,
    one-hot labels, and an ``offsets`` sidecar — the CSR row offsets of
    the PRE-dedup ragged values (``numFields*b + 1`` int64) used for
    exactly-once accounting across pool restarts.  Deterministic:
    record order fully determines every batch, which is what the pool's
    replay fast-forward needs.
    """

    def __init__(self, records, batchSize: int, numEmbeddings: int,
                 numClasses: int, bagBuckets=(4, 8, 16, 32, 64, 128),
                 numFields: int = 1, hashInputs: bool = True,
                 collisionSampleEvery: int = 8,
                 collisionSampleSize: int = 4096):
        self.records = list(records)
        self.batchSize = int(batchSize)
        self.numEmbeddings = int(numEmbeddings)
        self.numClasses = int(numClasses)
        self.bagBuckets = tuple(sorted(int(b) for b in bagBuckets))
        self.numFields = int(numFields)
        self.hashInputs = bool(hashInputs)
        # sampled collision estimator: hashed rows whose id falls on
        # the sample stride remember the FIRST raw value seen; a later
        # DIFFERENT raw value on the same row is a witnessed collision
        # (counted once per distinct pair).  Both dicts are bounded —
        # the estimator must never grow with stream length.
        # 0 disables sampling entirely.
        self.collisionSampleEvery = int(collisionSampleEvery)
        self.collisionSampleSize = int(collisionSampleSize)
        self._collisionSeen: Dict[int, int] = {}
        self._collisionHits: set = set()
        self._i = 0

    # -- SPI ------------------------------------------------------------
    def hasNext(self) -> bool:
        return self._i < len(self.records)

    def next(self, num: int = 0) -> DataSet:
        n = num or self.batchSize
        rows = self.records[self._i:self._i + n]
        if not rows:
            raise StopIteration("reader exhausted: call reset() first")
        self._i += len(rows)
        bags, labels, rawLens = [], [], []
        collisions = 0
        for values, label in rows:
            fields = values if self.numFields > 1 else (values,)
            if len(fields) != self.numFields:
                raise ValueError(
                    f"record has {len(fields)} fields, expected "
                    f"{self.numFields}")
            for vals in fields:
                if self.hashInputs:
                    ids = hash_feature(vals, self.numEmbeddings)
                    if self.collisionSampleEvery > 0:
                        collisions += self._sampleCollisions(
                            ids,
                            np.asarray(vals, dtype=np.int64))  # jaxlint: sync-ok -- host-side raw record ids
                else:
                    ids = np.asarray(vals, dtype=np.int64)  # jaxlint: sync-ok -- host-side ingestion of raw record ids
                uniq, counts = np.unique(ids, return_counts=True)
                bags.append((uniq, counts.astype(np.float32)))
                rawLens.append(len(ids))
            labels.append(label)
        bucket = self._bucket_for(max(len(u) for u, _ in bags))
        b = len(rows)
        f = np.zeros((b, self.numFields * bucket), dtype=np.float32)
        w = np.zeros((b, self.numFields * bucket), dtype=np.float32)
        for j, (uniq, counts) in enumerate(bags):
            row, field = divmod(j, self.numFields)
            off = field * bucket
            f[row, off:off + len(uniq)] = uniq
            w[row, off:off + len(uniq)] = counts
        l = np.zeros((b, self.numClasses), dtype=np.float32)
        l[np.arange(b), np.asarray(labels, dtype=np.int64)] = 1.0  # jaxlint: sync-ok -- host-side one-hot of python record labels
        offsets = np.zeros(len(bags) + 1, dtype=np.int64)
        np.cumsum(rawLens, out=offsets[1:])
        self._note_batch(int(offsets[-1]), sum(len(u) for u, _ in bags),
                         collisions)
        return self._applyPre(
            DataSet(f, l, featuresMask=w, offsets=offsets))

    def _sampleCollisions(self, hashed: np.ndarray,
                          raw: np.ndarray) -> int:
        """Count NEWLY witnessed hash collisions among the sampled
        stride of this bag.  A collision is two distinct raw ids on one
        hashed row — silent by construction (the lookup math is
        perfectly happy serving both users one embedding), so witnessing
        is the only detection there is.  Sampling ``1/sampleEvery`` of
        rows keeps the memory and per-batch cost bounded; scale the
        counter by ``sampleEvery`` for a population estimate."""
        sel = hashed % self.collisionSampleEvery == 0
        if not sel.any():
            return 0
        count = 0
        seen = self._collisionSeen
        for h, r in zip(hashed[sel].tolist(), raw[sel].tolist()):
            first = seen.get(h)
            if first is None:
                if len(seen) < self.collisionSampleSize:
                    seen[h] = r
            elif first != r:
                key = (h, r)
                if key not in self._collisionHits and \
                        len(self._collisionHits) < \
                        self.collisionSampleSize:
                    self._collisionHits.add(key)
                    count += 1
        return count

    def _bucket_for(self, longest: int) -> int:
        for bkt in self.bagBuckets:
            if longest <= bkt:
                return bkt
        raise ValueError(
            f"bag of {longest} unique ids exceeds the largest bucket "
            f"{self.bagBuckets[-1]} — raise bagBuckets (silent "
            "truncation would violate exactly-once ingestion)")

    def _note_batch(self, raw: int, stored: int,
                    collisions: int = 0) -> None:
        # ingestion telemetry — but ONLY in the parent process: a pool
        # worker must not import jax-adjacent modules, and its registry
        # would be discarded anyway
        from deeplearning4j_tpu.ops.ndarray import host_only_arrays
        if host_only_arrays():
            return
        from deeplearning4j_tpu.telemetry import recsys_metrics
        rm = recsys_metrics()
        rm.lookup_rows().inc(raw, phase="raw")
        rm.lookup_rows().inc(stored, phase="stored")
        rm.dedup_ratio().set(stored / max(raw, 1))
        if collisions:
            rm.hash_collisions().inc(collisions)

    def reset(self) -> None:
        self._i = 0

    def batch(self) -> int:
        return self.batchSize

    def totalOutcomes(self) -> int:
        return self.numClasses

    def inputColumns(self) -> int:
        return self.numFields

    def streaming(self) -> bool:
        return True         # per-record hash+dedup is real host work

    def setEpoch(self, epoch: int) -> None:
        pass                # deterministic: no per-epoch randomness

    def shard(self, index: int, count: int) -> "RaggedFeatureReader":
        """Deterministic 1-of-``count`` record shard (producer-pool
        worker contract)."""
        out = RaggedFeatureReader(
            self.records[index::count], self.batchSize,
            self.numEmbeddings, self.numClasses,
            bagBuckets=self.bagBuckets, numFields=self.numFields,
            hashInputs=self.hashInputs,
            collisionSampleEvery=self.collisionSampleEvery,
            collisionSampleSize=self.collisionSampleSize)
        if self.getPreProcessor() is not None:
            out.setPreProcessor(self.getPreProcessor())
        return out


# ------------------------------------------------------- auto-selection ----

def maybe_prefetch(iterator, numWorkers: Optional[int] = None,
                   hostShard: bool = True, **kw):
    """Wrap ``iterator`` in the producer pool when it is a streaming
    source (``iterator.streaming()``) and the pool is enabled
    (``DL4J_TPU_ETL_WORKERS`` > 0, default 2).  Falls back to the
    iterator unchanged when the source is not streaming, not picklable,
    or the pool can't start — the inline path always works.

    ``DL4J_TPU_ETL_WORKERS=0`` is a kill-switch that wins even over an
    explicit ``numWorkers`` (a caller pinning worker COUNT must not
    override the operator disabling forked workers outright).

    ``hostShard=False`` pins the spec to (0, 1) hosts: callers whose
    fit semantics are per-process (bare ``MultiLayerNetwork.fit`` with
    no mesh/all-reduce) must each see the FULL stream under
    ``jax.distributed``; the data-parallel paths (``ParallelWrapper``,
    ``SharedTrainingMaster``) keep the per-host shard convention.

    The fit loops call this; callers that get a NEW object back own its
    ``close()``.
    """
    if not isinstance(iterator, DataSetIterator):
        return iterator
    try:
        if not iterator.streaming():
            return iterator
    except Exception:
        return iterator
    try:
        env = int(os.environ.get("DL4J_TPU_ETL_WORKERS", "2"))
    except ValueError:
        env = 2
    if env <= 0:
        return iterator
    if numWorkers is None:
        numWorkers = env
    if numWorkers <= 0:
        return iterator
    if not hostShard:
        kw.setdefault("hostIndex", 0)
        kw.setdefault("hostCount", 1)
    try:
        return PrefetchingDataSetIterator(iterator, numWorkers=numWorkers,
                                          **kw)
    except Exception as e:
        # visible degradation: the operator asked for the pool (env or
        # default) and is getting the slow inline path instead — a
        # debug-level whisper would hide an ~Nx throughput loss
        log.warning(
            "ETL producer pool unavailable for %s (%s: %s); falling back "
            "to the inline single-process path",
            type(iterator).__name__, type(e).__name__, e)
        return iterator
