"""The chip: refuse to run without it, say what it is, keep its compile
cache, count compilations, read its peak memory.

``CompileCounter`` and the refusal follow ``chip_smoke.py`` (patterns
copied, file untouched).
"""
from __future__ import annotations

import collections
import os
import sys


class NoChip(Exception):
    """The machine does not hold what the cell asks for."""


def require_chips(chips: int, peaks_for) -> dict:
    """The device description of the result line, or :class:`NoChip` when
    JAX finds no accelerator of a kind in the peaks table, or fewer chips
    than the cell asks for."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU, found platform {dev.platform!r} "
                     f"({dev.device_kind}); there is no CPU mode")
    try:
        peaks_for(dev.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def enable_cache() -> str:
    """The persistent compile cache exactly where the program keeps it
    (``compile/jaxcache.py``: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``), and every program written to it — the
    default leaves out compiles under a second, and the serving ladder is
    made of those."""
    import jax

    from deeplearning4j_tpu.compile import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def memory_peak_bytes(devices) -> int:
    """Peak bytes held on the fullest of ``devices``, as JAX's
    ``memory_stats()`` reports them: the peak of the arrays in use plus
    the peak of what the runtime reserved for the programs' temporaries.
    (On this TPU runtime the two are apart: a ResNet-50 step's 8.9 GB of
    activations show under ``peak_bytes_reserved`` and not under
    ``peak_bytes_in_use``, which held 1.15 GB; my chip run, PR 23.)"""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def seed_key(seed: int):
    """A PRNG key from any whole number ``--seed`` may be (the driver's
    run past 2**31): low 31 bits seed the key, the rest is folded in."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits and misses
    off ``jax.monitoring``: a request served from the cache still counts
    as a compilation, with a hit beside it."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.counts = collections.Counter()
        self.compile_seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def _duration(self, event, seconds, **_kw):
        if event == self._COMPILE:
            self.counts["compilations"] += 1
            self.compile_seconds += seconds

    def snapshot(self) -> dict:
        return {"compilations": self.counts["compilations"],
                "compile_seconds": self.compile_seconds,
                "cache_hits": self.counts["cache_hits"],
                "cache_misses": self.counts["cache_misses"]}


def say(*parts) -> None:
    """An earlier line of standard output (anything but the result)."""
    print(*parts, flush=True)


def fail(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)
