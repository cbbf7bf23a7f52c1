"""Unified telemetry spine: metrics registry, span tracing, flight
recorder.

One place every layer reports through (SURVEY.md §5.1's ``OpProfiler`` /
``PerformanceListener`` / ``StatsListener`` fragments, unified):

- :mod:`.registry` — counters/gauges/histograms with labels, thread-safe,
  process-global default; Prometheus text exposition served from
  ``/metrics`` on both ``remote.JsonModelServer`` and ``ui.UIServer``.
- :mod:`.tracing` — nested ``span(name, **attrs)`` contexts merged with
  the ``OpProfiler`` Chrome-trace events into ONE trace file;
  every span is also a ``jax.profiler.TraceAnnotation`` (``dl4j.<name>``)
  in any profiler capture.
- :mod:`.flight` — ring buffer of the last N step records, dumped to JSON
  on ``InvalidStepException``/divergence/crash (``CrashReportingUtil``
  analogue).
- :mod:`.instrument` — the hot-path helpers the model/fault/parallel/ETL
  layers call.
- :mod:`.federation` — cross-process snapshot writers + the aggregator
  behind ``/metrics/federated`` (counters sum across hosts,
  gauges/histograms gain a ``host`` label).
- :mod:`.health` — watchdog alert rules + :class:`HealthMonitor`
  (firing/resolved transitions to a JSON event log and the
  ``dl4j_tpu_health_alerts_firing`` gauge); ``/healthz`` liveness.
- :mod:`.export` — durable final-snapshot flush on atexit/SIGTERM for
  scrape-less batch jobs (plus the FlightRecorder ring, so preempted
  jobs leave a crash record).

Metric naming convention (linted by ``tools/lint_telemetry.py``):
``dl4j_tpu_<subsystem>_<name>``; counters end ``_total``.
"""
from deeplearning4j_tpu.telemetry.context import (  # noqa: F401
    RequestContext, TimelineStore, current_context, parse_traceparent,
    request_context, set_timeline_store, timeline_store)
from deeplearning4j_tpu.telemetry.export import (  # noqa: F401
    install_export_handlers, uninstall_export_handlers,
    write_final_snapshot)
from deeplearning4j_tpu.telemetry.federation import (  # noqa: F401
    SnapshotWriter, TelemetryAggregator, federated_exposition,
    get_federation_dir, host_id, set_federation_dir)
from deeplearning4j_tpu.telemetry.flight import (  # noqa: F401
    FlightRecorder, flight_recorder, set_flight_recorder)
from deeplearning4j_tpu.telemetry.health import (  # noqa: F401
    AlertRule, DivergencePrecursorRule, EtlStarvationRule, HealthMonitor,
    ReplicaStragglerRule, ThresholdRule, TrainingStallRule, default_rules,
    health_summary, recsys_hash_collision_rule)
from deeplearning4j_tpu.telemetry.instrument import (  # noqa: F401
    SERVING_LOOP_PHASES, STEP_PHASES, AotCacheMetrics, CoordMetrics, ElasticMetrics, EtlMetrics,
    MeshMetrics, RecsysMetrics, ReplicaTimingListener, ServingMetrics,
    StepPhaseMetrics, aot_metrics, clear_exemplars, coord_metrics,
    elastic_metrics, etl_fetch, etl_metrics, exemplar_for, h2d_span,
    in_microbatch,
    latency_exemplars, mesh_metrics, microbatch_scope, note_etl_wait,
    observe_exemplar, observe_step_phase, record_crash, record_logical_step,
    recsys_metrics, replica_step_gauge, serving_metrics, step_phase_metrics,
    supervised_scope, train_step_span)
from deeplearning4j_tpu.telemetry.otlp import (  # noqa: F401
    OtlpExporter, ensure_otlp_exporter, otlp_exporter, set_otlp_exporter)
from deeplearning4j_tpu.telemetry.runlog import (  # noqa: F401
    TIMELINE_EVENT_KINDS, FleetTimeline, HybridLogicalClock, RunContext,
    current_run, current_run_id, fleet_timeline, merge_timelines,
    record_event, run_scope, run_span_attrs, set_fleet_timeline)
from deeplearning4j_tpu.telemetry.registry import (  # noqa: F401
    DEFAULT_BUCKETS, Counter, Gauge, Histogram, MetricsRegistry,
    gc_pause_seconds, get_registry, register_thread_role, set_registry,
    thread_role)
from deeplearning4j_tpu.telemetry.timeseries import (  # noqa: F401
    MetricsRetention, ensure_retention, retention, set_retention)
from deeplearning4j_tpu.telemetry.tracing import (  # noqa: F401
    Tracer, set_tracer, tracer)
