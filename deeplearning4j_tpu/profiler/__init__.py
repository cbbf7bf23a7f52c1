"""Profiling + panic modes + Chrome trace emission.

Reference: nd4j-api ``org/nd4j/linalg/profiler/{OpProfiler,ProfilerConfig,
PerformanceTracker}.java`` (per-op timings, NAN_PANIC/INF_PANIC scanning op
outputs) and the SameDiff ``ProfilingListener`` writing chrome://tracing
JSON (SURVEY.md §5.1).

TPU-native mapping: there is no per-op dispatch to time — XLA fuses the
whole step — so the unit of profiling is the EXECUTABLE (train step, output
fn) plus host phases (ETL, transfer).  ``OpProfiler`` times those;
NAN/INF panic checks the step's loss (the reference scans every op output —
under one fused executable the loss is the observable surface); for
kernel-level depth, :func:`start_trace`/:func:`stop_trace` wrap
``jax.profiler`` and produce TensorBoard/XPlane traces.
"""
from __future__ import annotations

import contextlib
import json
import time
from collections import defaultdict
from typing import Dict, List, Optional


class ProfilerConfig:
    """Reference: ProfilerConfig.java — build with the modes you want."""

    def __init__(self, checkForNAN: bool = False, checkForINF: bool = False,
                 stackTrace: bool = False, nativeStatistics: bool = False):
        self.checkForNAN = checkForNAN
        self.checkForINF = checkForINF
        self.stackTrace = stackTrace
        self.nativeStatistics = nativeStatistics


class OpProfiler:
    """Singleton phase timer + panic checks (reference: OpProfiler.java)."""

    _instance: Optional["OpProfiler"] = None

    def __init__(self):
        self.config = ProfilerConfig()
        self._times: Dict[str, float] = defaultdict(float)
        self._counts: Dict[str, int] = defaultdict(int)
        self._events: List[dict] = []
        self._t0 = time.perf_counter()

    @classmethod
    def getInstance(cls) -> "OpProfiler":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def setConfig(self, config: ProfilerConfig) -> None:
        self.config = config

    # -- timing -----------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            dur = time.perf_counter() - start
            self._times[name] += dur
            self._counts[name] += 1
            self._events.append({
                "name": name, "ph": "X", "pid": 1, "tid": 1,
                "ts": (start - self._t0) * 1e6, "dur": dur * 1e6})

    def timeSpent(self, name: str) -> float:
        return self._times[name]

    def invocations(self, name: str) -> int:
        return self._counts[name]

    def reset(self) -> None:
        self._times.clear()
        self._counts.clear()
        self._events.clear()
        self._t0 = time.perf_counter()

    def printOutDashboard(self) -> str:
        lines = [f"{'phase':<30} {'count':>8} {'total_s':>10} {'avg_ms':>10}"]
        for name in sorted(self._times, key=lambda n: -self._times[n]):
            t, c = self._times[name], self._counts[name]
            lines.append(f"{name:<30} {c:>8} {t:>10.3f} {1e3 * t / c:>10.2f}")
        out = "\n".join(lines)
        print(out)
        return out

    # -- chrome trace ------------------------------------------------------
    def writeChromeTrace(self, path: str) -> None:
        """chrome://tracing-format JSON (reference: ProfilingListener)."""
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": self._events}, f)

    # -- panic -------------------------------------------------------------
    def hookOut(self, value: float, where: str = "loss") -> None:
        """Reference: DefaultOpExecutioner.profilingConfigurableHookOut —
        throw on the first NaN/Inf when panic mode is on."""
        import math
        v = float(value)
        if self.config.checkForNAN and math.isnan(v):
            raise FloatingPointError(f"NAN_PANIC: NaN detected in {where}")
        if self.config.checkForINF and math.isinf(v):
            raise FloatingPointError(f"INF_PANIC: Inf detected in {where}")


def check_panic(value: float, where: str = "loss") -> None:
    """Cheap global hook used by the train loops."""
    prof = OpProfiler._instance
    if prof is not None and (prof.config.checkForNAN or
                             prof.config.checkForINF):
        prof.hookOut(value, where)


def panic_enabled() -> bool:
    """True when NAN/INF panic mode is on — train loops use this to decide
    whether the per-step loss must be synced to host (panic needs the value
    NOW; otherwise the loss stays an async device scalar and dispatch never
    blocks on the host round-trip)."""
    prof = OpProfiler._instance
    return prof is not None and (prof.config.checkForNAN or
                                 prof.config.checkForINF)


# -- device-level traces (TensorBoard) --------------------------------------

def start_trace(log_dir: str) -> None:
    """XLA-level profiling via jax.profiler (kernel timings on the chip).
    Every ``telemetry.tracer().span(name)`` is a
    ``jax.profiler.TraceAnnotation`` named ``dl4j.<name>``, so host spans
    line up with the kernel timeline in this capture as in any other
    ``jax.profiler`` session."""
    import jax
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    import jax
    jax.profiler.stop_trace()


class ProfilingListener:
    """TrainingListener emitting one Chrome-trace slice per iteration
    (reference: autodiff/listeners/profiler/ProfilingListener.java).

    Registry-backed: iteration slices are recorded through the process
    telemetry :func:`~deeplearning4j_tpu.telemetry.tracer`, so the flushed
    file is the MERGED trace — the train loop's nested step/h2d/etl/
    compile spans and the OpProfiler's phase events, one file.

    The trace file flushes every ``flushEveryNIterations`` (and on epoch
    end) — a per-iteration rewrite of the cumulative JSON would be O(n²)
    host IO in the training hot loop.
    """

    def __init__(self, outputPath: str, flushEveryNIterations: int = 100):
        self.outputPath = outputPath
        self.flushEvery = max(1, flushEveryNIterations)
        self._iter_start = None

    #: newest tracer events kept by the cheap PERIODIC flush (epoch end
    #: writes the full ring) — bounds the hot-loop serialization cost
    PERIODIC_FLUSH_TAIL = 10_000

    def onEpochStart(self, model):
        pass

    def onEpochEnd(self, model):
        self._flush()

    def onForwardPass(self, model, activations=None):
        pass

    def onBackwardPass(self, model):
        pass

    def onGradientCalculation(self, model):
        pass

    def _flush(self, tail=None):
        from deeplearning4j_tpu.telemetry import tracer
        tracer().write_chrome_trace(self.outputPath, tail=tail)

    def iterationDone(self, model, iteration, epoch):
        from deeplearning4j_tpu.telemetry import tracer
        now = time.perf_counter()
        if self._iter_start is not None:
            tracer().record_complete(
                f"iteration_{iteration}", self._iter_start,
                now - self._iter_start, args={"score": model.score()})
        self._iter_start = now
        if iteration % self.flushEvery == 0:
            # tail-bounded: the periodic flush exists so the file is fresh
            # if the run dies, not to re-serialize the entire shared ring
            # every N iterations in the hot loop
            self._flush(tail=self.PERIODIC_FLUSH_TAIL)
