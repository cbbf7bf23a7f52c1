"""Hot-path instrumentation helpers shared by the model/fault/parallel
layers.

Everything here is designed to be safe in the fused-step hot loop:

- metric lookups are dict-gets under a lock (no allocation churn);
- the step timer measures HOST wall time around the jitted call — with
  donated param buffers the next dispatch backpressures on the previous
  step, so over a window the dispatch rate converges to true device
  throughput without forcing a per-step ``block_until_ready`` round-trip
  (the listener-level throughput in
  :class:`~deeplearning4j_tpu.optimize.listeners.PerformanceListener`
  DOES block, and is the accurate samples/sec surface);
- jit cache misses are detected exactly via the jitted function's
  ``_cache_size()`` delta, so recompiles (new shape, dropped mesh trace)
  show up as ``dl4j_tpu_train_jit_cache_misses_total`` plus their wall
  time in ``dl4j_tpu_train_compile_seconds_total`` and a ``compile``
  span in the merged Chrome trace.
"""
from __future__ import annotations

import bisect
import contextlib
import functools
import threading
import time
from typing import Optional, Sequence

from deeplearning4j_tpu.telemetry.flight import flight_recorder
from deeplearning4j_tpu.telemetry.registry import (DEFAULT_BUCKETS,
                                                   get_registry)
from deeplearning4j_tpu.telemetry.runlog import (current_run, record_event,
                                                 run_span_attrs)
from deeplearning4j_tpu.telemetry.tracing import tracer

__all__ = ["train_step_span", "record_crash", "etl_fetch", "note_etl_wait",
           "supervised_scope", "microbatch_scope", "in_microbatch",
           "record_logical_step", "ReplicaTimingListener", "etl_metrics",
           "EtlMetrics", "ServingMetrics", "serving_metrics",
           "MeshMetrics", "mesh_metrics", "ElasticMetrics",
           "elastic_metrics", "CoordMetrics", "coord_metrics",
           "AotCacheMetrics", "aot_metrics", "replica_step_gauge",
           "observe_exemplar", "exemplar_for", "latency_exemplars",
           "clear_exemplars", "STEP_PHASES", "StepPhaseMetrics",
           "step_phase_metrics", "observe_step_phase", "h2d_span",
           "SERVING_LOOP_PHASES"]

# set while a fault supervisor owns the step: a step-level
# InvalidStepException/panic is then a RECOVERABLE divergence (the
# supervisor rolls back), not a crash — no dump, no crash counter.
# The supervisor itself dumps exactly once if recovery finally fails.
_scope = threading.local()


@contextlib.contextmanager
def supervised_scope():
    prev = getattr(_scope, "supervised", False)
    _scope.supervised = True
    try:
        yield
    finally:
        _scope.supervised = prev


@contextlib.contextmanager
def microbatch_scope():
    """Active during OOM micro-batch retries: half-batch step times must
    not enter the replica step-time/spread gauges (a recovered OOM would
    read as sustained contention for a whole window)."""
    prev = getattr(_scope, "microbatch", False)
    _scope.microbatch = True
    try:
        yield
    finally:
        _scope.microbatch = prev


def _jit_cache_size(model) -> Optional[int]:
    # _trainStep is a cached_property: reading model.__dict__ avoids
    # triggering the jit-wrapper build just to measure it
    fn = model.__dict__.get("_trainStep")
    if fn is None:
        return 0
    try:
        return int(fn._cache_size())
    except Exception:
        return None


def _report_step(model, seconds: float, batch_size: int,
                 **flight_extra) -> None:
    """The one reporting tail every logical step goes through — normal
    steps and OOM micro-batch splits must land in the SAME series."""
    reg = get_registry()
    reg.counter("dl4j_tpu_train_steps_total",
                "Logical train steps dispatched").inc()
    reg.histogram("dl4j_tpu_train_step_seconds",
                  "Host wall time per logical train step",
                  buckets=DEFAULT_BUCKETS).observe(seconds)
    if seconds > 0:
        reg.gauge(
            "dl4j_tpu_train_examples_per_second",
            "Dispatch-rate examples/sec (see PerformanceListener for the "
            "blocked, device-accurate rate)").set(batch_size / seconds)
    observe_step_phase("compute", seconds, step=model.iterationCount)
    record_event("train.step", step=int(model.iterationCount),
                 epoch=int(model.epochCount),
                 seconds=round(seconds, 6))
    flight_recorder().record(
        iteration=model.iterationCount, epoch=model.epochCount,
        step_seconds=round(seconds, 6), batch_size=int(batch_size),
        **flight_extra)


@contextlib.contextmanager
def train_step_span(model, batch_size: int):
    """Wrap one logical train step (fused step / TBPTT chunk loop / legacy
    solver iteration): step counter + step-time histogram + examples/sec
    gauge + jit-compile accounting + a ``step`` span + a FlightRecorder
    record.  Crashes inside the step dump the flight ring (see
    :func:`record_crash`) and re-raise."""
    if getattr(_scope, "microbatch", False):
        # OOM-retry half-batches are not logical steps: the supervisor
        # keeps iterationCount at ONE step for the whole split, so the
        # step counter/histogram/throughput must not see the halves —
        # only a trace span marking the retry work
        with tracer().span("microbatch_step", batch=int(batch_size)):
            yield
        return
    reg = get_registry()
    before = _jit_cache_size(model)
    t0 = time.perf_counter()
    try:
        with tracer().span("step", iteration=model.iterationCount,
                           epoch=model.epochCount, batch=int(batch_size),
                           **run_span_attrs()):
            yield
    except Exception as e:
        from deeplearning4j_tpu.optimize.solvers import InvalidStepException
        if isinstance(e, (InvalidStepException, FloatingPointError)):
            if getattr(_scope, "supervised", False):
                # the supervisor will roll back and retry — log the event
                # in the ring but don't report a crash for a recoverable
                # divergence (it dumps once itself if recovery fails)
                flight_recorder().record(
                    event="invalid_step", reason=f"{type(e).__name__}: {e}",
                    iteration=model.iterationCount)
            else:
                record_crash(f"{type(e).__name__}: {e}", model=model)
        raise
    dt = time.perf_counter() - t0
    after = _jit_cache_size(model)
    if before is not None and after is not None and after > before:
        reg.counter(
            "dl4j_tpu_train_jit_cache_misses_total",
            "Fused-step executable cache misses (recompiles)").inc(
                after - before)
        reg.counter(
            "dl4j_tpu_train_compile_seconds_total",
            "Wall seconds of steps that included an XLA compile").inc(dt)
        tracer().record_complete("compile", t0, dt,
                                 args={"iteration": model.iterationCount})
    _report_step(model, dt, batch_size, jit_cache_size=after)


def in_microbatch() -> bool:
    """True inside an OOM micro-batch retry (see :func:`microbatch_scope`);
    the model train loops use this to defer per-step listener/metric
    reporting to the supervisor's logical-step boundary."""
    return getattr(_scope, "microbatch", False)


def record_logical_step(model, seconds: float, batch_size: int) -> None:
    """Count one LOGICAL step completed via micro-batch OOM retry: the
    halves themselves are skipped (``microbatch_scope``), so the
    supervisor reports the whole split here — without this the step
    counter would drift below ``iterationCount`` and the step-time
    histogram would be missing exactly the slowest steps."""
    _report_step(model, seconds, batch_size, oom_split=True)


def record_crash(reason: str, model=None) -> str:
    """Append a crash record, mark the trace, and dump the flight ring to
    JSON (the ``CrashReportingUtil`` analogue).  Returns the dump path."""
    fr = flight_recorder()
    rec = {"event": "crash", "reason": reason}
    if model is not None:
        rec["iteration"] = getattr(model, "iterationCount", None)
        rec["epoch"] = getattr(model, "epochCount", None)
    fr.record(**rec)
    tracer().instant("crash", reason=reason)
    get_registry().counter("dl4j_tpu_train_crash_dumps_total",
                           "FlightRecorder crash dumps written").inc()
    return fr.dump(reason=reason)


class EtlMetrics:
    """The ``dl4j_tpu_etl_*`` metric namespace, registered from ONE site.

    Both input pipelines report here — the thread-prefetch
    ``AsyncDataSetIterator`` and the process-pool
    ``datavec.pipeline.PrefetchingDataSetIterator`` — so the watchdog's
    ``etl_starvation`` rule and the federated dashboards see one coherent
    series no matter which pipeline feeds the loop (and the telemetry
    lint's one-registering-module rule stays satisfiable).  Accessors
    re-resolve through :func:`get_registry` on every call: tests swap the
    registry, and a cached metric would silently write into the old one.
    """

    def queue_depth(self):
        return get_registry().gauge(
            "dl4j_tpu_etl_queue_depth",
            "Prefetch-queue depth observed by the consumer")

    def consumers_waiting(self):
        return get_registry().gauge(
            "dl4j_tpu_etl_consumers_waiting",
            "Consumers currently blocked on an empty prefetch queue")

    def empty_polls(self):
        return get_registry().counter(
            "dl4j_tpu_etl_queue_empty_polls_total",
            "Consumer polls that found the prefetch queue empty")

    def producer_active(self):
        return get_registry().gauge(
            "dl4j_tpu_etl_producer_active",
            "Prefetch producers (threads or pool processes) currently "
            "running")

    def prefetch_wait(self):
        return get_registry().gauge(
            "dl4j_tpu_etl_prefetch_wait_seconds",
            "Consumer block time on the last prefetch-queue get")

    def h2d_bytes(self):
        return get_registry().counter(
            "dl4j_tpu_etl_h2d_bytes_total",
            "Bytes moved host->device by the ETL staging ring")

    def h2d_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_etl_h2d_seconds",
            "Per-batch host->device transfer wall time (issue + "
            "completion wait) in the ETL staging ring",
            buckets=DEFAULT_BUCKETS)

    def pool_workers(self):
        return get_registry().gauge(
            "dl4j_tpu_etl_pool_workers",
            "Producer processes alive in the sharded ETL pool")

    def pool_batches(self):
        return get_registry().counter(
            "dl4j_tpu_etl_pool_batches_total",
            "Batches delivered by the sharded ETL producer pool")

    def pool_inline_batches(self):
        return get_registry().counter(
            "dl4j_tpu_etl_pool_inline_batches_total",
            "Pool batches that bypassed shared memory (oversized or "
            "partial: pickled through the queue instead)")

    def pool_restarts(self):
        return get_registry().counter(
            "dl4j_tpu_etl_pool_restarts_total",
            "Producer-pool restarts (etl_starvation remediation or an "
            "explicit requestRestart) — the stream position is "
            "preserved by the consumer's skip fast-forward")


_ETL_METRICS = EtlMetrics()


def etl_metrics() -> EtlMetrics:
    """Accessor for the shared ETL metric namespace (see
    :class:`EtlMetrics`)."""
    return _ETL_METRICS


#: serving latency spans sub-ms (warm MLP on-host) to tens of seconds
#: (long-context decode) — finer low end than DEFAULT_BUCKETS so a p99
#: read off the bucket bounds stays meaningful at serving speeds
SERVING_LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0)

#: the decode loop's host phases run from ten microseconds (bookkeep) to
#: one device step (fetch): finer at the low end than any latency above
SERVING_LOOP_PHASE_BUCKETS = (
    0.00001, 0.000025, 0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025,
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0)

#: what the continuous batcher's loop thread is doing, in loop order
#: (``remote/scheduler.py``): every phase is a span
#: ``serving.loop.<phase>`` and one observation of
#: ``dl4j_tpu_serving_loop_phase_seconds`` from the same two clock reads
SERVING_LOOP_PHASES = ("wait", "admit", "grow", "upload", "dispatch",
                       "fetch", "emit", "bookkeep")

#: a ladder warm-up spans "every bucket loads from the AOT cache" (ms)
#: to "a deep generative ladder compiles from scratch" (minutes) —
#: DEFAULT_BUCKETS can't resolve both ends
SERVING_WARMUP_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0, 120.0, 300.0)


class ServingMetrics:
    """The ``dl4j_tpu_serving_*`` namespace, registered from ONE site.

    The continuous-batching tier (``remote/serving.py``) reports here;
    admission control reads the same registry back through
    ``ThresholdRule``s, so the shed decision and the dashboards see one
    coherent series.  Accessors re-resolve through :func:`get_registry`
    on every call (tests swap the registry).  Every per-model series
    carries a ``model`` label — one serving process hosts many models.
    """

    def request_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_request_seconds",
            "End-to-end request latency inside the serving tier "
            "(enqueue to response ready), per model",
            labelnames=("model",), buckets=SERVING_LATENCY_BUCKETS)

    def requests(self):
        return get_registry().counter(
            "dl4j_tpu_serving_requests_total",
            "Requests completed by the bucketed executor, by model and "
            "outcome (ok/error/shed)",
            labelnames=("model", "outcome"))

    def queue_depth(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_queue_depth",
            "Feature rows currently queued ahead of the scheduler, per "
            "model (the admission controller's primary signal)",
            labelnames=("model",))

    def shed(self):
        return get_registry().counter(
            "dl4j_tpu_serving_shed_total",
            "Requests rejected by admission control (HTTP 429), by model "
            "and the rule that fired",
            labelnames=("model", "rule"))

    def compile_hits(self):
        return get_registry().counter(
            "dl4j_tpu_serving_compile_cache_hits_total",
            "Dispatches that hit a warm executable (no fresh XLA trace)",
            labelnames=("model",))

    def compile_misses(self):
        return get_registry().counter(
            "dl4j_tpu_serving_compile_cache_misses_total",
            "Dispatches that triggered a fresh XLA trace after warmup "
            "(steady state should hold this at zero)",
            labelnames=("model",))

    def warmup_compiles(self):
        return get_registry().counter(
            "dl4j_tpu_serving_warmup_compiles_total",
            "Executables compiled eagerly by BucketedExecutor.start() "
            "over the bucket ladder",
            labelnames=("model",))

    def p99_seconds(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_p99_seconds",
            "p99 request latency read off the request histogram after "
            "each dispatch (admission control's latency signal)",
            labelnames=("model",))

    def batch_occupancy(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_batch_occupancy",
            "Real rows / padded rows of the last dispatched bucket "
            "(1.0 = no padding waste)",
            labelnames=("model",))

    def pad_rows(self):
        return get_registry().counter(
            "dl4j_tpu_serving_pad_rows_total",
            "Padding rows dispatched to round batches up to a bucket",
            labelnames=("model",))

    def decode_tokens(self):
        return get_registry().counter(
            "dl4j_tpu_serving_decode_tokens_total",
            "Tokens generated through the KV-cache decode path",
            labelnames=("model",))

    def warmup_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_warmup_seconds",
            "Wall time of one BucketedExecutor ladder warm-up (compile "
            "on a cold AOT cache, executable loads on a warm one) — the "
            "server-start-to-ready cost, per model",
            labelnames=("model",), buckets=SERVING_WARMUP_BUCKETS)

    # -- continuous batching (remote/scheduler.py) -----------------------
    def slot_occupancy(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_slot_occupancy",
            "Active decode slots / total slots of the continuous "
            "batcher's shared step (1.0 = every slot busy; the "
            "iteration-level scheduler's primary efficiency signal)",
            labelnames=("model",))

    def kv_pages_in_use(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_kv_pages_in_use",
            "KV-cache pages currently allocated to admitted sequences, "
            "per model and pool (a batcher has one, \"target\")",
            labelnames=("model", "pool"))

    def paged_attention_kernel(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_paged_attention_kernel",
            "1 when the batcher's decode step was built with the kernel "
            "that reads the live KV pages where they lie (lowered for one "
            "TPU), 0 when it gathers every slot's whole capacity (the "
            "CPU, a pool split over devices, a model with its own step); "
            "the share of capacity a kernel step reads is "
            "kv_pages_in_use / (maxSlots x maxPagesPerSeq)",
            labelnames=("model",))

    def paged_attention_kv_passes(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_paged_attention_kv_passes",
            "MXU passes over one K (and one V) lane tile a chunk a query "
            "in the batcher's decode step as it was lowered: 1 for a "
            "bfloat16 pool (K and V enter the MXU as stored), 3 for a "
            "float32 pool (its high, middle and low bits), 0 where the "
            "step gathers (paged_attention_kernel 0)",
            labelnames=("model",))

    def ring_attention_kernel(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_ring_attention_kernel",
            "1 when the batcher's decode step read its window layers' "
            "rings through the same kernel as its pages, each ring as "
            "its slot's fixed pages where it lies (lowered for one TPU: "
            "as many kernel lowerings as the model has paged and ring "
            "layers), 0 when it gathers them (the CPU, several devices) "
            "and for a model without ring layers",
            labelnames=("model",))

    def sparse_read_in_place(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_sparse_read_in_place",
            "1 when every layer of the batcher's decode step whose "
            "attention selects its rows reads the chosen K and V rows "
            "where they lie: the paged-attention kernel's pass over the "
            "slot's live pages under the selection's mask (lowered for "
            "one TPU, the slot's capacity under the crossover), 0 when "
            "it sorts the scores and gathers the chosen rows (a larger "
            "capacity, the CPU, several devices) and for a model "
            "without a selector",
            labelnames=("model",))

    def moe_step_kernel(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_moe_step_kernel",
            "1 when the batcher's decode step was built with the kernel "
            "that reads only the held experts a live slot's token chose "
            "(an expert layer that holds a share, lowered for one TPU), 0 "
            "when it multiplies every held expert over every slot (the "
            "CPU, several devices) and for a model without such a layer; "
            "the share of the held experts' weights a kernel step reads "
            "is moe_experts_hit_total{phase=\"step\"} / (experts held x "
            "expert layers x decode steps)",
            labelnames=("model",))

    def moe_grouped_kernel(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_moe_grouped_kernel",
            "1 when a prefill of the batcher's ladder was built with the "
            "kernel that streams the weights of each held expert with a "
            "pair in the pass once, under its rows' matmuls "
            "(parallel/moe.py:moe_share_grouped, lowered for one TPU), 0 "
            "when its grouped experts are the compiler's ragged dot, "
            "which streams every held expert's weights at a third of the "
            "memory's pace or less (the CPU, several devices) and for a model "
            "without such a layer",
            labelnames=("model",))

    def ssd_step_kernel(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_ssd_step_kernel",
            "1 when the batcher's decode step was built with the kernel "
            "that reads every Mamba-2 (SSD) state once and writes it once "
            "in place (nlp/mamba.py:ssd_state_step, lowered for one TPU), "
            "0 when it runs the recurrence as jax.numpy (the CPU, several "
            "devices) and for a model without such a layer",
            labelnames=("model",))

    def tied_table_lane_aligned(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_tied_table_lane_aligned",
            "1 when the embedding table the batcher's decode step is "
            "given (params[\"emb\"]) is whole lane tiles of 128 columns "
            "wide, so that the lookup and a tied head read it as it lies "
            "(TransformerLM pads its table with zero columns to that "
            "width when it is given a tree), 0 when not: a width that is "
            "not whole tiles makes the TPU's compiler copy the whole "
            "table inside every step; no series for a model with no "
            "params[\"emb\"]",
            labelnames=("model",))

    def kv_pages_free(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_kv_pages_free",
            "KV-cache pages on the free list, per model and pool — the "
            "admission controller's page-headroom signal",
            labelnames=("model", "pool"))

    def state_slots_in_use(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_state_slots_in_use",
            "Decode slots whose cache state (pages, ring rows, recurrent "
            "state) belongs to an admitted sequence, per model",
            labelnames=("model",))

    def ring_rows_in_use(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_ring_rows_in_use",
            "Live rows of the window layers' K/V rings, summed over "
            "slots: a sequence holds its last ringRows positions at most",
            labelnames=("model",))

    def cache_bytes(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_cache_bytes",
            "Bytes of live cache state by kind: paged (allocated pages of "
            "the layers that own pages), ring (live ring rows of every "
            "window layer), recurrent (fixed state of the slots in use)",
            labelnames=("model", "kind"))

    def index_rows_bytes(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_index_rows_bytes",
            "Bytes of the third paged pool, the index rows of a model "
            "whose attention selects its rows (CacheSpec.indexWidth), in "
            "the pages in use: what a decode step's selector reads, "
            "beside cache_bytes{kind=\"paged\"}, which counts K and V; "
            "absent for a model without a selector",
            labelnames=("model",))

    def ring_wraps(self):
        return get_registry().counter(
            "dl4j_tpu_serving_ring_wraps_total",
            "Times a sequence's length passed a multiple of the window, "
            "so that its rings began to overwrite their oldest rows",
            labelnames=("model",))

    def preemptions(self):
        return get_registry().counter(
            "dl4j_tpu_serving_preemptions_total",
            "Decode slots evicted mid-generation to free KV pages "
            "(restart-with-skip; the sequence requeues at the front)",
            labelnames=("model",))

    def sequences_admitted(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sequences_admitted_total",
            "Sequences admitted into a decode slot between steps",
            labelnames=("model",))

    def sequences_retired(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sequences_retired_total",
            "Sequences retired from a decode slot (finished, errored "
            "or cancelled) with all their pages freed",
            labelnames=("model",))

    def decode_steps(self):
        return get_registry().counter(
            "dl4j_tpu_serving_decode_steps_total",
            "Shared decode steps dispatched by the continuous batcher "
            "(one fixed-shape executable call per step)",
            labelnames=("model",))

    def decode_steps_overlapped(self):
        return get_registry().counter(
            "dl4j_tpu_serving_decode_steps_overlapped_total",
            "Decode steps dispatched while the step before was still "
            "unread on the device, so that the device ran while the host "
            "emitted and prepared (over decode_steps_total: the share of "
            "steps for which the loop was one step ahead)",
            labelnames=("model",))

    def decode_tokens_discarded(self):
        return get_registry().counter(
            "dl4j_tpu_serving_decode_tokens_discarded_total",
            "Tokens computed for a sequence that had left its slot by "
            "the time they were read (EOS, cancel, deadline or preemption "
            "learnt one step late): the price of running one step ahead",
            labelnames=("model",))

    def replicas(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_replicas",
            "Live executor replicas behind the named registry route "
            "(scaled by the serving_queue_depth remediation)",
            labelnames=("model",))

    def failovers(self):
        return get_registry().counter(
            "dl4j_tpu_serving_failovers_total",
            "Sequences failed over from an unhealthy/crashed replica to "
            "a survivor, replayed from the prompt with streamSkip hiding "
            "the re-emission (exactly-once delivery across the move)",
            labelnames=("model",))

    def deadline_sheds(self):
        return get_registry().counter(
            "dl4j_tpu_serving_deadline_sheds_total",
            "Requests shed because their end-to-end deadline expired — "
            "stage=admission never entered a decode slot (HTTP 504); "
            "stage=queued/decode were cancelled between steps with their "
            "KV pages freed",
            labelnames=("model", "stage"))

    def drain_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_drain_seconds",
            "Graceful-drain duration when a replica leaves the route "
            "(scaleDown/swap): admission stopped, in-flight sequences "
            "run to completion bounded by drainTimeout, stragglers "
            "failed over to survivors",
            buckets=SERVING_WARMUP_BUCKETS, labelnames=("model",))

    def replica_health(self):
        return get_registry().gauge(
            "dl4j_tpu_serving_replica_health",
            "Per-replica probe verdict: 1 healthy (probe within timeout "
            "under the consecutive-failure threshold), 0 removed from "
            "routing — surfaced in /healthz",
            labelnames=("model", "replica"))

    # -- per-stage latency decomposition (request-scoped observability) --
    def ttft_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_ttft_seconds",
            "Time to first token: request enqueue to the first token "
            "emitted to the client, per model (queue wait + prefill + "
            "first sampling step; failover restarts extend it)",
            labelnames=("model",), buckets=SERVING_LATENCY_BUCKETS)

    def inter_token_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_inter_token_seconds",
            "Gap between consecutive NEW tokens of one sequence "
            "(replayed tokens hidden by streamSkip do not observe; a "
            "failover's replay gap lands here by design), per model",
            labelnames=("model",), buckets=SERVING_LATENCY_BUCKETS)

    def queue_wait_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_queue_wait_seconds",
            "Enqueue to decode-slot admission, per model — the queueing "
            "share of TTFT (attributes p99 regressions to queueing vs "
            "compute)",
            labelnames=("model",), buckets=SERVING_LATENCY_BUCKETS)

    def prefill_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_prefill_seconds",
            "Prompt prefill wall time (bucketed forward + KV pool write "
            "+ first-token argmax) inside slot admission, per model",
            labelnames=("model",), buckets=SERVING_LATENCY_BUCKETS)

    def prefill_positions(self):
        return get_registry().counter(
            "dl4j_tpu_serving_prefill_positions_total",
            "Positions prefilled at admission, padding included: the "
            "prompt bucket of every prefill, per model and bucket (over "
            "prefill_prompt_tokens_total it is what the bucket ladder "
            "wastes; over the bucket, the prefills of that shape)",
            labelnames=("model", "bucket"))

    def prefill_prompt_tokens(self):
        return get_registry().counter(
            "dl4j_tpu_serving_prefill_prompt_tokens_total",
            "Real prompt tokens prefilled at admission, per model",
            labelnames=("model",))

    # routing of a served expert layer that holds a share of the experts
    # (parallel/moe.py): counted on the device inside the step and the
    # prefill, returned in the columns behind the step's tokens and read
    # with them (``stepCounters`` of the served model)
    def moe_pairs_routed(self):
        return get_registry().counter(
            "dl4j_tpu_serving_moe_pairs_routed_total",
            "Token-expert pairs whose expert this replica holds, and so "
            "computed here, summed over the expert layers; per model and "
            "phase (step: a decode step's live slots; prefill: a "
            "prompt's real positions)",
            labelnames=("model", "phase"))

    def moe_pairs_absent(self):
        return get_registry().counter(
            "dl4j_tpu_serving_moe_pairs_absent_total",
            "Token-expert pairs the router chose whose expert another "
            "chip of the deployment holds: their part of the layer's "
            "output is left out here; routed + absent = experts a token x "
            "tokens x expert layers",
            labelnames=("model", "phase"))

    def moe_experts_hit(self):
        return get_registry().counter(
            "dl4j_tpu_serving_moe_experts_hit_total",
            "Held experts with at least one token, summed over the expert "
            "layers and the steps (or prefills): over experts held x "
            "layers x steps it is the share of the held experts' weights "
            "a step has to read, and the share it DOES read where "
            "moe_step_kernel is 1 (elsewhere the step reads them all)",
            labelnames=("model", "phase"))

    # the selector of a sparse attention (paged_sparse_attention): counted
    # like the routing above
    def sparse_rows_scored(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sparse_rows_scored_total",
            "Live index rows the selector scored, summed over the layers "
            "and the slots (step) or the real queries (prefill: a query "
            "scores the real positions up to its own)",
            labelnames=("model", "phase"))

    def sparse_rows_selected(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sparse_rows_selected_total",
            "K/V rows attended after selection, summed as the rows "
            "scored: min(live rows, topk) a query a layer; selected / "
            "scored is the share of the live rows a query reads",
            labelnames=("model", "phase"))

    # the tiles of a sparse prefill's two kernels (nlp/keye_vl.py
    # sparse_attend_full): computed from the bucket and the prompt's first
    # real position beside the prefill, and carried as the counts above
    def sparse_prefill_tiles_causal(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sparse_prefill_tiles_causal_total",
            "(query block, key block) tiles at or under the diagonal of "
            "the prompt buckets prefilled, summed over the layers: what "
            "selection and attention would visit if every position of a "
            "bucket were real",
            labelnames=("model", "phase"))

    def sparse_prefill_tiles_visited(self):
        return get_registry().counter(
            "dl4j_tpu_serving_sparse_prefill_tiles_visited_total",
            "Those of the causal tiles that hold a real key under a real "
            "query, which the prefill's kernels score and attend (the "
            "left pads' tiles are skipped); visited / causal is the share "
            "of a bucket's quadratic work its prompts need, 1 for a full "
            "bucket",
            labelnames=("model", "phase"))

    def loop_phase_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_loop_phase_seconds",
            "Host wall time of the continuous batcher's loop thread, by "
            "phase: wait (idle, nothing queued, at most 0.1 s a slice), "
            "admit (queue head to slot: prefill, pool write, first "
            "token), grow (deadline sweep, page growth, preemption), "
            "upload (host slot state to device arrays), dispatch (the "
            "step executable's call until it returns), fetch (the wait for "
            "the step dispatched an iteration earlier and its tokens' "
            "D2H: what is left of the device step once the other phases "
            "ran beside it), emit (delivery, timeline, retire), bookkeep "
            "(counters, gauges, compile-cache size); one observation a "
            "phase a loop iteration, per model",
            labelnames=("model", "phase"),
            buckets=SERVING_LOOP_PHASE_BUCKETS)

    def loop_phase_offcpu_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_loop_phase_offcpu_seconds",
            "Seconds of a loop phase in which the loop thread did NOT "
            "run: the phase's wall time (loop_phase_seconds, the same "
            "two reads) less what the thread's own CPU clock "
            "(time.thread_time()) gained between them; one observation "
            "a phase in every loop iteration that reads that clock: all "
            "of them where a read costs a microsecond or less, one in "
            "up to sixteen where it costs more (whole iterations, so a "
            "sum over phases over the count of fetch is a mean a step "
            "either way).  Where the clock moves a scheduler tick at a "
            "time, what a phase reads beyond its wall time is set "
            "against its next observations: sums hold, single "
            "observations do not.  In "
            "wait and fetch the thread blocks by design and this is the "
            "wait; in grow, emit and bookkeep nothing blocks by design "
            "and this is the time the thread waited for the interpreter "
            "or for a core; upload, dispatch and admit call into the "
            "runtime and may hold either; per model",
            labelnames=("model", "phase"),
            buckets=SERVING_LOOP_PHASE_BUCKETS)

    # the token's hand-off from the loop thread to the thread that
    # writes it to the client (submitStream's generator keeps the sums
    # in locals and adds them here every 32 tokens and at the end)
    def stream_token_seconds(self):
        return get_registry().counter(
            "dl4j_tpu_serving_stream_token_seconds_total",
            "Seconds streamed tokens spent between the loop thread and "
            "the client's socket, by stage: queued (from the loop's put "
            "to the consumer's get returning: the handler thread's "
            "wake-up and its wait for the interpreter, and with a "
            "consumer slower than the decode step the time the token "
            "lay in the queue), write (from there to the consumer "
            "asking for the next token: json.dumps, the chunk's bytes, "
            "write, flush; a client that reads slowly shows here once "
            "the socket's buffer is full); over "
            "stream_tokens_delivered_total it is seconds a token; per "
            "model",
            labelnames=("model", "stage"))

    def stream_tokens_delivered(self):
        return get_registry().counter(
            "dl4j_tpu_serving_stream_tokens_delivered_total",
            "Tokens a stream's consumer took off its queue (sentinels "
            "and keep-alives count nothing; a replayed prefix is "
            "swallowed before the queue and so not counted twice; a "
            "token put for a consumer that had hung up is never "
            "delivered), per model",
            labelnames=("model",))

    def device_idle_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_serving_device_idle_seconds",
            "Stretches in which the device had nothing to run, as the "
            "continuous batcher's loop thread (its only dispatcher) "
            "knows them, each booked when the dispatch that ended it "
            "returned, by cause: wait (the loop slept in it: nothing "
            "queued, nothing in a slot, the traffic's pause), admit "
            "(from an admission's first-token read to the next dispatch: "
            "the loop had stopped for a prefill), loop (neither: "
            "sequences held slots and the device ran dry between two "
            "dispatches, the host slower than the step).  A stretch that "
            "opens where a blocking read of the last thing dispatched "
            "returned reads the device's gap FROM BELOW: it leaves out "
            "that read's D2H and wake-up at its front and the launch "
            "behind the dispatch call at its back (together 1-2 ms on a "
            "TPU v5e); so every wait and admit stretch.  A loop "
            "stretch that is_ready() alone found, at a dispatch with the "
            "step before unread, is an UPPER BOUND: the time since the "
            "dispatch before returned, of which the device worked one "
            "step at most.  sum = idle seconds, count = dispatches that "
            "found the device idle, the buckets from 0.1 s up = stalls; "
            "per model",
            labelnames=("model", "cause"),
            buckets=SERVING_LOOP_PHASE_BUCKETS)


_SERVING_METRICS = ServingMetrics()


def serving_metrics() -> ServingMetrics:
    """Accessor for the shared serving metric namespace (see
    :class:`ServingMetrics`)."""
    return _SERVING_METRICS


# -- histogram exemplars --------------------------------------------------
# Prometheus-style exemplars: each (histogram, label set) remembers the
# trace id of the observation that landed in its highest bucket so far,
# so a p99 spike on a latency dashboard links DIRECTLY to one request's
# timeline (`/v1/requests/<traceId>`).  The store is tiny (one record
# per cell) and updated under one lock — hot-loop safe.
_EXEMPLARS: dict = {}
_EXEMPLAR_LOCK = threading.Lock()


def observe_exemplar(name, value, trace_id=None, attrs=None, **labels):
    """Observe ``value`` into the ALREADY-REGISTERED histogram ``name``
    and attach ``trace_id`` as the exemplar when this observation is as
    slow as (or slower than) the cell's current exemplar.  A literal,
    registered metric name is required — jaxlint's telemetry-exemplar
    rule cross-checks call sites against registration sites.  ``attrs``
    rides along on the exemplar record WITHOUT becoming histogram labels
    (step-phase exemplars carry unbounded (generation, step) coordinates
    this way — pointing at one step without a cardinality explosion)."""
    hist = get_registry().get(name)
    if hist is None or not hasattr(hist, "buckets"):
        return
    hist.observe(value, **labels)
    if not trace_id:
        return
    bucket = bisect.bisect_left(hist.buckets, value)
    key = (name, tuple(sorted(labels.items())))
    with _EXEMPLAR_LOCK:
        cur = _EXEMPLARS.get(key)
        if cur is None or bucket >= cur["bucket"]:
            rec = {"trace_id": trace_id, "value": value, "bucket": bucket}
            if attrs:
                rec["attrs"] = dict(attrs)
            _EXEMPLARS[key] = rec


def exemplar_for(name, **labels):
    """The slowest-bucket exemplar recorded for one histogram cell:
    ``{"trace_id", "value", "bucket"}`` or None."""
    key = (name, tuple(sorted(labels.items())))
    with _EXEMPLAR_LOCK:
        got = _EXEMPLARS.get(key)
        return dict(got) if got else None


def latency_exemplars():
    """Every recorded exemplar, keyed ``{metric: {label tuple: record}}``
    — what the README's worked example walks from a p99 spike to a
    trace id."""
    with _EXEMPLAR_LOCK:
        out: dict = {}
        for (name, lkey), rec in _EXEMPLARS.items():
            out.setdefault(name, {})[lkey] = dict(rec)
        return out


def clear_exemplars():
    with _EXEMPLAR_LOCK:
        _EXEMPLARS.clear()


#: The five seams one logical train step decomposes into — instrumented
#: at etl_fetch (data_wait), the prefetcher's staged-batch materialize and
#: ``_fitBatch``'s placement / re-shard (h2d), the host wall around the
#: ASYNCHRONOUS dispatch of the jitted step (compute: enqueue time, not
#: the device's compute, which the host does not wait for), the
#: supervisor's sealed save (checkpoint) and the pod barrier (barrier).
STEP_PHASES = ("data_wait", "h2d", "compute", "checkpoint", "barrier")


class StepPhaseMetrics:
    """The ``dl4j_tpu_step_*`` step-time decomposition namespace,
    registered from ONE site.

    Splits step wall time into the phases that answer "why did step time
    double at generation 3": input wait vs host-to-device staging vs
    fused-step compute vs checkpoint stall vs barrier wait.  Every
    histogram takes exemplars (via :func:`observe_step_phase`) pointing
    at the (trace id, generation, step) of the slowest observation, so a
    p99 spike on any phase links straight to one step of one run.
    Accessors re-resolve through :func:`get_registry` on every call
    (tests swap the registry).
    """

    def data_wait_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_step_data_wait_seconds",
            "Step time waiting on the input pipeline (batch fetch, "
            "prefetch stalls)", buckets=DEFAULT_BUCKETS)

    def h2d_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_step_h2d_seconds",
            "Step time staging batches host-to-device: the prefetcher's "
            "issue + materialize wait, and the fit loop's placement or "
            "re-shard of the batch before the step is enqueued",
            buckets=DEFAULT_BUCKETS)

    def compute_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_step_compute_seconds",
            "Host wall time around the asynchronous dispatch of the "
            "jitted fused step (enqueue; the device's compute is not "
            "waited for, so this is NOT device time)",
            buckets=DEFAULT_BUCKETS)

    def checkpoint_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_step_checkpoint_seconds",
            "Step time blocked on a sealed checkpoint save",
            buckets=DEFAULT_BUCKETS)

    def barrier_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_step_barrier_seconds",
            "Step time blocked on the pod coordination barrier",
            buckets=DEFAULT_BUCKETS)


_STEP_PHASE_METRICS = StepPhaseMetrics()


def step_phase_metrics() -> StepPhaseMetrics:
    """Accessor for the shared step-phase namespace (see
    :class:`StepPhaseMetrics`)."""
    return _STEP_PHASE_METRICS


def observe_step_phase(phase: str, seconds: float,
                       step: Optional[int] = None) -> None:
    """Observe one step-phase duration with a run-scoped exemplar: the
    active :class:`~deeplearning4j_tpu.telemetry.runlog.RunContext`
    supplies the trace id and generation, so the slowest-bucket exemplar
    on each phase histogram resolves to (trace id, generation, step)."""
    rc = current_run()
    tid = rc.runId if rc is not None else None
    attrs = None
    if rc is not None:
        attrs = {"generation": int(rc.generation)}
        if step is not None:
            attrs["step"] = int(step)
    spm = _STEP_PHASE_METRICS
    if phase == "data_wait":
        spm.data_wait_seconds()
        observe_exemplar("dl4j_tpu_step_data_wait_seconds", seconds,
                         tid, attrs=attrs)
    elif phase == "h2d":
        spm.h2d_seconds()
        observe_exemplar("dl4j_tpu_step_h2d_seconds", seconds,
                         tid, attrs=attrs)
    elif phase == "compute":
        spm.compute_seconds()
        observe_exemplar("dl4j_tpu_step_compute_seconds", seconds,
                         tid, attrs=attrs)
    elif phase == "checkpoint":
        spm.checkpoint_seconds()
        observe_exemplar("dl4j_tpu_step_checkpoint_seconds", seconds,
                         tid, attrs=attrs)
    elif phase == "barrier":
        spm.barrier_seconds()
        observe_exemplar("dl4j_tpu_step_barrier_seconds", seconds,
                         tid, attrs=attrs)
    else:
        raise ValueError(f"unknown step phase {phase!r}; "
                         f"expected one of {STEP_PHASES}")


def h2d_span():
    """``_fitBatch``'s placement of one batch (host-to-device, or the
    re-shard over a mesh) as the ``h2d`` phase: span, profiler annotation
    and ``dl4j_tpu_step_h2d_seconds`` from the same two clock reads."""
    return tracer().span(
        "h2d", observe=functools.partial(observe_step_phase, "h2d"))


class MeshMetrics:
    """The ``dl4j_tpu_mesh_*`` namespace, registered from ONE site.

    ``parallel.meshtrainer.MeshTrainer`` — the unified GSPMD stepping
    path every parallel facade (ParallelWrapper, SharedTrainingMaster,
    ZeRO, MoE, pipeline) executes through — reports here: step time,
    per-axis collective traffic estimated statically from the
    ShardingPlan, and executable cache misses (the steady-state
    acceptance bar is this counter staying FLAT after step 1).
    Accessors re-resolve through :func:`get_registry` on every call
    (tests swap the registry).
    """

    def steps(self):
        return get_registry().counter(
            "dl4j_tpu_mesh_steps_total",
            "Train steps dispatched through the MeshTrainer unified "
            "sharded step (all parallel facades step here)")

    def step_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_mesh_step_seconds",
            "Host wall time per MeshTrainer step (lockstep across the "
            "mesh: one executable, GSPMD collectives inside)",
            buckets=DEFAULT_BUCKETS)

    def jit_cache_misses(self):
        return get_registry().counter(
            "dl4j_tpu_mesh_jit_cache_misses_total",
            "Sharded-step executable cache misses (steady state must "
            "hold this flat after the first step)")

    def collective_bytes(self):
        return get_registry().counter(
            "dl4j_tpu_mesh_collective_bytes_total",
            "Estimated bytes moved per mesh axis and collective "
            "(all_reduce / reduce_scatter / all_gather), priced "
            "statically from the ShardingPlan",
            labelnames=("axis", "collective"))

    def axis_size(self):
        return get_registry().gauge(
            "dl4j_tpu_mesh_axis_size",
            "Device count per named mesh axis of the active "
            "ShardingPlan", labelnames=("axis",))


_MESH_METRICS = MeshMetrics()


def mesh_metrics() -> MeshMetrics:
    """Accessor for the shared mesh metric namespace (see
    :class:`MeshMetrics`)."""
    return _MESH_METRICS


class ElasticMetrics:
    """The ``dl4j_tpu_elastic_*`` namespace, registered from ONE site.

    ``fault.elastic.ElasticSupervisor`` reports here: re-mesh events by
    direction (shrink on device loss, grow on recovered capacity, evict
    on a chronic straggler), re-mesh latency (mesh rebuild + plan-to-plan
    reshard + iterator realignment), the current device count, and the
    raw loss/eviction counters the ops dashboards alert on.  Accessors
    re-resolve through :func:`get_registry` on every call (tests swap
    the registry).
    """

    def remeshes(self):
        return get_registry().counter(
            "dl4j_tpu_elastic_remesh_total",
            "Elastic re-mesh events by direction (shrink = device loss, "
            "grow = capacity returned, evict = straggler host removed)",
            labelnames=("direction",))

    def remesh_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_elastic_remesh_seconds",
            "Wall time of one elastic re-mesh: mesh rebuild + "
            "plan-to-plan reshard (or resharded checkpoint restore) + "
            "input-pipeline realignment",
            buckets=DEFAULT_BUCKETS)

    def mesh_devices(self):
        return get_registry().gauge(
            "dl4j_tpu_elastic_mesh_devices",
            "Devices in the currently active elastic mesh")

    def device_losses(self):
        return get_registry().counter(
            "dl4j_tpu_elastic_device_losses_total",
            "Permanent device losses detected by the elastic supervisor")

    def evictions(self):
        return get_registry().counter(
            "dl4j_tpu_elastic_straggler_evictions_total",
            "Hosts/replicas evicted from the mesh because the "
            "replica-straggler condition held past its patience")


_ELASTIC_METRICS = ElasticMetrics()


def elastic_metrics() -> ElasticMetrics:
    """Accessor for the shared elastic metric namespace (see
    :class:`ElasticMetrics`)."""
    return _ELASTIC_METRICS


#: a coordinated barrier spans "peers already at their boundary" (ms) to
#: "the slowest participant is a full checkpoint period away" (tens of
#: seconds) — DEFAULT_BUCKETS tops out too early for the long tail an
#: operator needs to see before raising barrierTimeout
COORD_BARRIER_BUCKETS = (
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    30.0, 60.0, 120.0)


class CoordMetrics:
    """The ``dl4j_tpu_coord_*`` namespace, registered from ONE site.

    ``fault.coordination`` reports here: the mesh generation this
    process has adopted, barrier latency, leader-side dead-lease
    detections, fenced (stale-generation) writes rejected by the
    checkpoint fence, and host re-admissions.  Accessors re-resolve
    through :func:`get_registry` on every call (tests swap the
    registry).
    """

    def generation(self):
        return get_registry().gauge(
            "dl4j_tpu_coord_generation",
            "Mesh generation this process has adopted (bumps on every "
            "agreed pod-wide re-mesh)")

    def barrier_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_coord_barrier_seconds",
            "Wall time spent in the pod-wide re-mesh barrier (ack "
            "published to all participants acked)",
            buckets=COORD_BARRIER_BUCKETS)

    def heartbeats_missed(self):
        return get_registry().counter(
            "dl4j_tpu_coord_heartbeats_missed_total",
            "Hosts whose heartbeat lease expired (leader-side dead-host "
            "detections, one per live->dead transition)")

    def fenced_writes_rejected(self):
        return get_registry().counter(
            "dl4j_tpu_coord_fenced_writes_rejected_total",
            "Checkpoint seals/manifest publishes rejected by the "
            "generation fence (stale or evicted writer)")

    def readmissions(self):
        return get_registry().counter(
            "dl4j_tpu_coord_readmissions_total",
            "Evicted hosts/devices re-admitted to the mesh after "
            "passing the probation policy")

    def leader_failovers(self):
        return get_registry().counter(
            "dl4j_tpu_coord_leader_failovers_total",
            "In-flight plans orphaned by a proposer dying mid-barrier "
            "and adopted by the next-lowest live participant (same "
            "generation, same digest)")

    def eviction_votes(self):
        return get_registry().counter(
            "dl4j_tpu_coord_eviction_votes_total",
            "Straggler-eviction vote-count transitions tallied by the "
            "leader, by replica and verdict (evict = quorum reached, "
            "hold = below quorum)",
            labelnames=("replica", "verdict"))

    def chaos_events(self):
        return get_registry().counter(
            "dl4j_tpu_coord_chaos_events_total",
            "Fault events fired by the deterministic chaos-soak "
            "harness, by event kind",
            labelnames=("event",))


_COORD_METRICS = CoordMetrics()


def coord_metrics() -> CoordMetrics:
    """Accessor for the shared coordination metric namespace (see
    :class:`CoordMetrics`)."""
    return _COORD_METRICS


#: an executable load is a disk read + runtime deserialize: sub-ms to a
#: few hundred ms for a big multi-device program — DEFAULT_BUCKETS has
#: no resolution below 5 ms where most loads land
AOT_LOAD_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0)

#: a bake is a full XLA compile: tens of ms for a toy step to minutes
#: for a big sharded program
AOT_BAKE_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    60.0, 120.0, 300.0)


class AotCacheMetrics:
    """The ``dl4j_tpu_aot_cache_*`` namespace, registered from ONE site.

    ``compile.aotcache`` reports here: executable-cache hits/misses by
    executable kind (mesh_step / train_step / output / prefill /
    decode), load and bake latency, LRU evictions and quarantined
    (corrupt) entries.  The warm-boot acceptance bar reads as: hits > 0
    while ``dl4j_tpu_train_compile_seconds_total`` and the serving
    compile-miss counters stay ~0.  Accessors re-resolve through
    :func:`get_registry` on every call (tests swap the registry).
    """

    def hits(self):
        return get_registry().counter(
            "dl4j_tpu_aot_cache_hits_total",
            "Serialized executables loaded from the persistent AOT "
            "cache instead of compiled, by executable kind",
            labelnames=("kind",))

    def misses(self):
        return get_registry().counter(
            "dl4j_tpu_aot_cache_misses_total",
            "AOT cache lookups that found no loadable entry (fresh "
            "XLA compile follows), by executable kind",
            labelnames=("kind",))

    def load_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_aot_cache_load_seconds",
            "Wall time to read + deserialize one cached executable",
            buckets=AOT_LOAD_BUCKETS)

    def bake_seconds(self):
        return get_registry().histogram(
            "dl4j_tpu_aot_cache_bake_seconds",
            "Wall time of the fresh XLA compile behind one cache miss "
            "(the cost the next boot skips)",
            buckets=AOT_BAKE_BUCKETS)

    def evictions(self):
        return get_registry().counter(
            "dl4j_tpu_aot_cache_evictions_total",
            "Cache entries removed by LRU eviction to hold the "
            "configured size bound")

    def quarantined(self):
        return get_registry().counter(
            "dl4j_tpu_aot_cache_quarantined_total",
            "Corrupt or stale cache entries moved to quarantine "
            "(checksum/unpickle/deserialize failure; the caller "
            "compiled fresh)")


_AOT_METRICS = AotCacheMetrics()


def aot_metrics() -> AotCacheMetrics:
    """Accessor for the shared AOT-cache metric namespace (see
    :class:`AotCacheMetrics`)."""
    return _AOT_METRICS


#: a top-k retrieval request is one prefill (+ k-1 fixed-shape decode
#: steps): sub-ms warm on the CPU proxy to tens of ms under queueing —
#: resolution concentrated under 100 ms where the serving SLO lives
RECSYS_TOPK_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5)


class RecsysMetrics:
    """The ``dl4j_tpu_recsys_*`` namespace, registered from ONE site.

    The recommender tier reports here: ingestion volume and dedup
    effectiveness from ``RaggedFeatureReader`` (host-side per-row
    unique of hashed ids), the interconnect bytes a table-parallel
    lookup moves (computed statically from the exchange shapes — no
    device sync), and end-to-end top-k retrieval latency through
    ``ContinuousBatcher``.  Accessors re-resolve through
    :func:`get_registry` on every call (tests swap the registry).
    """

    def lookup_rows(self):
        return get_registry().counter(
            "dl4j_tpu_recsys_lookup_rows_total",
            "Embedding ids ingested for lookup, by pipeline phase "
            "(raw = before host-side dedup, stored = after)",
            labelnames=("phase",))

    def alltoall_bytes(self):
        return get_registry().counter(
            "dl4j_tpu_recsys_alltoall_bytes_total",
            "Interconnect bytes moved by table-parallel sparse "
            "lookups (id requests + resolved rows + row all-gather), "
            "computed from static exchange shapes")

    def dedup_ratio(self):
        return get_registry().gauge(
            "dl4j_tpu_recsys_dedup_ratio",
            "stored/raw id ratio of the last ingested ragged batch "
            "(1.0 = no duplicates; lower is better)")

    def topk_latency(self):
        return get_registry().histogram(
            "dl4j_tpu_recsys_topk_latency_seconds",
            "End-to-end top-k retrieval latency through the "
            "continuous batcher (submit to ranked ids)",
            buckets=RECSYS_TOPK_BUCKETS)

    def hash_collisions(self):
        return get_registry().counter(
            "dl4j_tpu_recsys_hash_collisions_total",
            "Distinct raw feature values observed mapping to the same "
            "hashed embedding row (sampled estimator in "
            "RaggedFeatureReader; silent collisions degrade ranking "
            "quality without ever erroring)")


_RECSYS_METRICS = RecsysMetrics()


def recsys_metrics() -> RecsysMetrics:
    """Accessor for the shared recommender-tier metric namespace (see
    :class:`RecsysMetrics`)."""
    return _RECSYS_METRICS


def note_etl_wait(seconds: float, owner) -> None:
    """Record blocking ETL wait incurred outside ``next()``
    (AsyncDataSetIterator blocks in ``hasNext()`` to populate its peek),
    charged to ``owner`` — the iterator that blocked — and folded into the
    next :func:`etl_fetch` ON THAT ITERATOR.  Keying by iterator (not a
    bare thread-local) keeps a drain that never calls ``etl_fetch`` (a
    normalizer ``fit`` pass) from leaking its waits into an unrelated
    fetch; the iterator zeroes its pending on reset."""
    owner._telemetry_pending_wait = getattr(
        owner, "_telemetry_pending_wait", 0.0) + float(seconds)


def etl_fetch(iterator):
    """One batch fetch timed as the ETL phase: an ``etl`` trace event, the
    last-fetch stall gauge, and cumulative stall seconds.  Used by every
    training loop that drains an iterator, so a slow input pipeline is
    visible as ``dl4j_tpu_etl_stall_seconds`` regardless of which loop
    drives it — including async iterators whose blocking happens in
    ``hasNext`` (handed over via :func:`note_etl_wait`)."""
    pending = getattr(iterator, "_telemetry_pending_wait", 0.0)
    if pending:
        iterator._telemetry_pending_wait = 0.0

    def observe(seconds: float) -> None:
        # the hasNext wait handed over is part of the time the loop stood
        # still for data: in the histogram and gauges, and in the span's
        # args (the span itself is the real time inside next())
        dt = seconds + pending
        reg = get_registry()
        observe_step_phase("data_wait", dt)
        reg.gauge("dl4j_tpu_etl_stall_seconds",
                  "Host wall time the train loop spent waiting on the "
                  "last batch fetch (async prefetch waits included)").set(dt)
        reg.counter("dl4j_tpu_etl_stall_seconds_total",
                    "Cumulative seconds the train loop waited on batch "
                    "fetches").inc(dt)

    with tracer().span("etl", observe=observe,
                       waited_before_s=round(pending, 6)):
        return iterator.next()


def replica_step_gauge():
    """The per-replica lockstep step-time gauge — registered HERE (one
    module) and shared by :class:`ReplicaTimingListener`, the straggler
    watchdog rule, and the fault-injection straggler stand-in."""
    return get_registry().gauge(
        "dl4j_tpu_parallel_replica_step_seconds",
        "Lockstep per-replica step wall time",
        labelnames=("replica",))


class ReplicaTimingListener:
    """Per-replica step-time gauges + timing-spread gauge for data-parallel
    fits (attached internally by ``ParallelWrapper``).

    Under GSPMD the step is ONE executable synchronous across replicas, so
    each replica's step time IS the lockstep wall time; the straggler /
    contention signal is the max/min ratio over a rolling window of
    those lockstep times — a contended window reads as spread, not as a
    uniform regression."""

    def __init__(self, devices: Sequence, window: int = 20):
        self._device_ids = [str(getattr(d, "id", i))
                            for i, d in enumerate(devices)]
        self._window = max(2, int(window))
        self._times = []
        self._last = None
        self._etl_mark = None

    def _etl_total(self) -> float:
        c = get_registry().get("dl4j_tpu_etl_stall_seconds_total")
        return c.value() if c is not None else 0.0

    # TrainingListener duck-typed surface (only the hooks it needs)
    def onEpochStart(self, model):
        # epoch boundaries (iterator reset, async-producer drain/join) are
        # not step time — restart the inter-iteration clock so the gap
        # can't masquerade as a straggler in the spread gauge
        self._last = None

    def onEpochEnd(self, model):
        self._last = None

    def onForwardPass(self, model, activations=None):
        pass

    def onBackwardPass(self, model):
        pass

    def onGradientCalculation(self, model):
        pass

    def iterationDone(self, model, iteration, epoch):
        now = time.perf_counter()
        etl_now = self._etl_total()
        if self._last is None:
            self._last, self._etl_mark = now, etl_now
            return
        # the inter-iteration interval contains one batch fetch — subtract
        # the ETL counter's delta so a slow fetch (cold cache, starved
        # prefetcher) doesn't read as device contention in the spread;
        # this keeps one semantics with the fitDataSet path, which times
        # the step call alone
        dt = max(now - self._last - (etl_now - (self._etl_mark or 0.0)),
                 0.0)
        self._last, self._etl_mark = now, etl_now
        if dt > 0:
            self.record(dt)

    def record(self, dt: float) -> None:
        """Feed one lockstep step time directly (the per-batch
        ``fitDataSet`` path times the step call itself so supervisor
        overhead between batches doesn't pollute the gauge)."""
        if getattr(_scope, "microbatch", False):
            return      # OOM half-batches are not representative steps
        reg = get_registry()
        g = replica_step_gauge()
        for rid in self._device_ids:
            g.set(dt, replica=rid)
        self._times.append(dt)
        if len(self._times) > self._window:
            self._times.pop(0)
        if len(self._times) >= 2:
            lo = min(self._times)
            if lo > 0:
                reg.gauge(
                    "dl4j_tpu_parallel_step_time_spread",
                    "max/min step time over a rolling window (above 2.0 "
                    "the window was contended)").set(
                        max(self._times) / lo)
