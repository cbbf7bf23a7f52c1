"""Ahead-of-time compilation: persistent executable cache (ROADMAP item 2).

Everything hot in this repo is jitted, but a fresh process still re-pays
trace+compile on boot.  :mod:`.aotcache` makes compilation a persistent,
content-addressed artifact (the TVM / nGraph ahead-of-time lineage,
PAPERS arXiv:1802.04799 / arXiv:1801.08058): serialized XLA executables
keyed by (model topology, input avals, ShardingPlan + device set,
jax/XLA version) on disk, preloaded at boot by the train/serving paths.
"""
from deeplearning4j_tpu.compile.aotcache import (  # noqa: F401
    AotCache, AotDispatch, aot_cache, set_aot_cache, device_fingerprint,
    model_digest, plan_digest, preload_model, version_fingerprint,
    wrap_jit, wrap_serving_model)
from deeplearning4j_tpu.compile.jaxcache import (  # noqa: F401
    DEFAULT_CACHE_DIR, enable_compile_cache)
