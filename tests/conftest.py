"""Test configuration.

By default all tests run on CPU with 8 virtual XLA devices so the
multi-chip sharding path is exercised without TPU hardware (the reference's
analogue is DummyTransport / local[N] Spark masters — SURVEY.md §4):
``JAX_PLATFORMS=cpu`` plus the device-count flag, both set before jax is
imported, is all that takes.

``pytest -m tpu tests/`` instead leaves the platform alone and runs ONLY
the ``@pytest.mark.tpu`` tests, on the chip, in this one process.  Without
a TPU they FAIL (a device check that skips hides the device).  They cover
what ``chip_smoke.py`` does not; the end-to-end "does it start on the chip"
proof is ``python chip_smoke.py``.
"""
import os
import sys

_TPU_RUN = "tpu" in os.environ.get("PYTEST_ADDOPTS", "") or \
    any(a == "tpu" for i, a in enumerate(sys.argv)
        if i and sys.argv[i - 1] == "-m")

if not _TPU_RUN:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: tests that need the real TPU chip (run with "
        "`pytest -m tpu`: they fail without one; skipped on the CPU "
        "mesh)")
    config.addinivalue_line(
        "markers", "slow: long-running tests (multi-process spawns)")
    config.addinivalue_line(
        "markers", "fault: fault-tolerance tests (supervisor recovery "
        "paths driven by the deterministic injection harness)")
    config.addinivalue_line(
        "markers", "telemetry: telemetry-spine tests (metrics registry, "
        "/metrics exposition, span tracing, flight recorder)")
    config.addinivalue_line(
        "markers", "etl: input-pipeline tests (sharded producer pool, "
        "shared-memory batch assembly, H2D staging ring)")
    config.addinivalue_line(
        "markers", "serving: continuous-batching serving-tier tests "
        "(bucketed warm executables, KV-cache decode, admission control)")
    config.addinivalue_line(
        "markers", "lint: static-analysis tests (the jaxlint AST "
        "framework, its rule fixtures, and the repo-is-clean smoke "
        "gate)")
    config.addinivalue_line(
        "markers", "mesh: unified GSPMD mesh tests (MeshTrainer single "
        "sharded step: DP/TP/ZeRO/EP equivalence, steady-state "
        "compile-cache discipline, fault supervision across mesh "
        "shapes)")
    config.addinivalue_line(
        "markers", "elastic: elastic re-mesh tests (plan-to-plan "
        "resharding, shrink-on-device-loss, grow-on-recovery, "
        "straggler eviction, async checkpoint sealing)")
    config.addinivalue_line(
        "markers", "coord: pod-level coordination tests (heartbeat "
        "leases, mesh-generation consensus and barrier, checkpoint "
        "generation fencing, re-admission policy, device-health "
        "probe, alert-driven remediation)")
    config.addinivalue_line(
        "markers", "aot: AOT compile + persistent executable cache "
        "tests (content-addressed store, warm-boot preload, "
        "corrupt-entry quarantine, re-mesh re-keying, cross-process "
        "reuse)")
    config.addinivalue_line(
        "markers", "chaos: deterministic chaos-soak tests (seeded "
        "fault schedules over a coordinated training run: leader "
        "failover, barrier deaths, partitions, corrupt/torn state — "
        "with the standing lineage/trajectory/delivery/jit invariants)")
    config.addinivalue_line(
        "markers", "cbatch: iteration-level continuous-batching tests "
        "(paged KV pool, admit/retire scheduler, token streaming, "
        "replica fan-out)")
    config.addinivalue_line(
        "markers", "recsys: recommender-tier tests (sharded embedding "
        "tables, two-phase dedup'd sparse lookup, ragged ingestion "
        "exactly-once, elastic re-mesh of a row-sharded table, top-k "
        "retrieval serving through the continuous batcher)")
    config.addinivalue_line(
        "markers", "servfault: serving fault-tolerance tests (replica "
        "health probing, in-flight failover with exactly-once token "
        "delivery, end-to-end deadlines, graceful drain/swap, the "
        "serving chaos soak)")
    config.addinivalue_line(
        "markers", "obsreq: request-scoped observability tests (trace "
        "propagation across failover, TTFT/ITL decomposition, the "
        "request timeline endpoint, metrics retention queries, OTLP "
        "export, the NDJSON access log)")
    config.addinivalue_line(
        "markers", "trainobs: training-plane observability tests "
        "(run-scoped trace ids on step/checkpoint/barrier spans, the "
        "cross-host fleet timeline with hybrid-logical-clock merge, "
        "the run timeline endpoint, step-time decomposition "
        "histograms with (generation, step) exemplars)")


def pytest_collection_modifyitems(config, items):
    import pytest
    if _TPU_RUN:
        return
    skip = pytest.mark.skip(reason="needs real TPU (run: pytest -m tpu)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
