"""AOT compile + persistent executable cache: zero cold starts.

Every hot path in this repo is jitted, yet every PROCESS still re-paid
trace+compile on boot: serving warm-up eagerly compiles the whole bucket
ladder, preemption resume and elastic re-mesh re-trace the fused step
after every restart, and CI re-burns identical XLA work on each run.
Per the compiler-stack lineage in PAPERS (TVM arXiv:1802.04799, nGraph
arXiv:1801.08058) compilation should be an ahead-of-time, persistent,
content-addressed artifact — this module is that artifact store:

- :class:`AotCache` — on-disk content-addressed cache of serialized XLA
  executables (``jax.experimental.serialize_executable``).  Entries are
  keyed by a sha256 over (kind, model topology digest, input avals,
  ShardingPlan digest + device-set fingerprint, jax/jaxlib/backend
  version); written atomically (tmp + ``os.replace`` + checksum header);
  corrupt or stale entries are QUARANTINED (moved aside, never trusted
  again) and the caller falls back to a fresh compile; total size is
  bounded with LRU eviction.
- :class:`AotDispatch` — the callable installed in place of a bare
  ``jax.jit`` wrapper on the boot paths: per input-signature it loads
  the executable from the cache (a few ms) or compiles once via
  ``jitted.lower(*args).compile()`` and bakes the result back.  Its
  ``_cache_size()`` counts FRESH XLA compiles only — a disk load is not
  a recompile, so ``dl4j_tpu_train_compile_seconds_total`` and the
  serving compile-miss counters stay ~0 on a warm boot, which is the
  acceptance bar.
- per-group shape LADDERS — the cache remembers which input signatures
  a (model, plan) group has compiled, so ``preload()`` can load the
  whole ladder at boot before the first batch arrives.

Keying correctness: the ShardingPlan digest + device-set fingerprint is
part of every key, so after an elastic ``remesh`` the new install can
NEVER load a stale old-mesh executable — the old plan hashes to a
different group (the same discipline as popping the ``_stepFn``
cached_property for JAX's fun-identity jaxpr cache).

The cache is OFF unless configured: set ``DL4J_TPU_AOT_CACHE_DIR`` (or
call :func:`set_aot_cache`) to enable; ``DL4J_TPU_AOT_CACHE=0`` is the
kill switch; ``DL4J_TPU_AOT_CACHE_MAX_BYTES`` bounds the LRU size.
``tools/aotc`` pre-bakes a model's full ladder for fleet rollout.

Telemetry: the ``dl4j_tpu_aot_cache_*`` namespace (registered once in
``telemetry.instrument.AotCacheMetrics``) — hits/misses by kind, load
and bake latency, evictions, quarantined entries.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["AotCache", "AotDispatch", "aot_cache", "set_aot_cache",
           "model_digest", "plan_digest", "device_fingerprint",
           "version_fingerprint", "wrap_jit", "wrap_serving_model",
           "preload_model"]

log = logging.getLogger(__name__)

_ENTRY_SUFFIX = ".aotx"
_DEFAULT_MAX_BYTES = 4 << 30
_QUARANTINE_KEEP = 20


# ---------------------------------------------------------------------------
# digests
# ---------------------------------------------------------------------------

def _digest(obj: Any) -> str:
    """sha256 over the canonical JSON of ``obj`` (tuples/sets coerced so
    the same logical key always hashes identically across processes)."""
    return hashlib.sha256(json.dumps(
        _canon(obj), sort_keys=True, separators=(",", ":"))
        .encode("utf-8")).hexdigest()


def _canon(obj: Any):
    if isinstance(obj, dict):
        return {str(k): _canon(v) for k, v in sorted(obj.items(),
                                                     key=lambda kv:
                                                     str(kv[0]))}
    if isinstance(obj, (list, tuple)):
        return [_canon(v) for v in obj]
    if isinstance(obj, (set, frozenset)):
        return sorted(_canon(v) for v in obj)
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def _obj_desc(v: Any, depth: int = 3):
    """Deterministic, address-free description of a config object: class
    name + primitive attributes, recursively (bounded).  ``repr`` alone
    is NOT usable — default object reprs embed memory addresses, which
    would make the digest differ across processes for identical
    topologies."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_obj_desc(x, depth - 1) for x in v] if depth > 0 else len(v)
    if isinstance(v, dict):
        return {str(k): _obj_desc(x, depth - 1) for k, x in v.items()} \
            if depth > 0 else sorted(str(k) for k in v)
    name = type(v).__name__
    if depth <= 0:
        return name
    attrs = getattr(v, "__dict__", None)
    if not attrs:
        return name
    return {"__class__": name,
            **{k: _obj_desc(x, depth - 1) for k, x in sorted(attrs.items())
               if not k.startswith("_")}}


def model_digest(model) -> str:
    """Topology digest of a model: layer/node types + config + per-leaf
    param shapes/dtypes.  Values are deliberately EXCLUDED — an
    executable depends on shapes and the traced math, never on weights —
    so two processes that build the same architecture (any seed) share
    cache entries."""
    desc: Dict[str, Any] = {"class": type(model).__name__}
    conf = getattr(model, "conf", None)
    if conf is not None:                    # MultiLayerNetwork / graph
        if hasattr(conf, "layers"):
            desc["layers"] = [_obj_desc(layer) for layer in conf.layers]
        elif hasattr(conf, "nodes"):
            desc["nodes"] = {name: _obj_desc(conf.nodes[name][0])
                             for name in conf.topoOrder}
        desc["globalConf"] = _obj_desc(getattr(conf, "globalConf", {}))
        desc["computeDtype"] = str(getattr(model, "_computeDtype", ""))
    cfg = getattr(model, "config", None)
    if cfg is not None:                     # TransformerLM-style config
        desc["config"] = _obj_desc(cfg)
    params = getattr(model, "params_", None)
    if params is None:
        params = getattr(model, "params", None)
    if params is not None:
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(params)
        desc["params"] = [[str(treedef)]] + [
            [list(getattr(v, "shape", ())), str(getattr(v, "dtype", ""))]
            for v in leaves]
    return _digest(desc)


def plan_digest(plan) -> str:
    """Digest of a ShardingPlan: axis factorization, TP/ZeRO flags AND
    the exact ordered device set.  Keying on this is what guarantees a
    re-meshed trainer can never load a pre-remesh executable — any plan
    or device-set change hashes to a different group."""
    mesh = plan.mesh
    return _digest({
        "axes": plan.axis_sizes(),
        "tensorParallel": plan.tensorParallel,
        "zero1": plan.zero1,
        "dataAxis": plan.dataAxis, "modelAxis": plan.modelAxis,
        "zeroAxis": plan.zeroAxis,
        "devices": device_fingerprint(list(mesh.mesh.devices.flat)),
    })


def device_fingerprint(devices: Optional[Sequence] = None) -> List:
    """Ordered (id, kind, process) description of the device set an
    executable is loaded for — a deserialized executable replays its
    baked device assignment, so a different set must be a cache miss."""
    import jax
    if devices is None:
        devices = jax.devices()
    return [[int(getattr(d, "id", i)),
             str(getattr(d, "device_kind", "")),
             int(getattr(d, "process_index", 0))]
            for i, d in enumerate(devices)]


def version_fingerprint() -> Dict[str, str]:
    """Everything that changes the traced math without changing the
    model CONFIG: jax/jaxlib/backend versions, THIS package's version
    (an upgrade can fix layer/gradient math — a shared fleet cache must
    never serve the old trace), and numerics-relevant jax config."""
    import jax
    import jaxlib

    import deeplearning4j_tpu
    fp = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
          "backend": jax.default_backend(),
          "dl4j_tpu": getattr(deeplearning4j_tpu, "__version__", "?"),
          "x64": str(bool(jax.config.jax_enable_x64)),
          "matmul_precision": str(getattr(
              jax.config, "jax_default_matmul_precision", None))}
    try:
        from jax.extend import backend as jex_backend
        fp["platform_version"] = str(
            jex_backend.get_backend().platform_version)
    except Exception:
        pass
    return fp


def _sig_key(args: tuple) -> tuple:
    """Hashable input-signature key for the per-CALL dispatch dict:
    (treedef, per-leaf (shape, dtype, weak_type)).  Deliberately cheap —
    this runs on every step, so it must stay a tree_flatten plus small
    tuples, no string formatting (PyTreeDefs hash and compare by
    structure, so the tuple is a stable dict key)."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype),
                        bool(getattr(leaf, "weak_type", False))))
        else:
            sig.append(("py", repr(leaf)))
    return (treedef, tuple(sig))


def _sig_str(key: tuple) -> str:
    """Stable STRING form of a signature key — what the content digest
    and the on-disk ladder record (computed only on miss/preload, never
    per step).  Non-array leaves carry their repr, so a static-arg flip
    is its own executable."""
    treedef, sig = key
    parts = [str(treedef)]
    for entry in sig:
        if entry[0] == "py":
            parts.append(f"py:{entry[1]}")
        else:
            shape, dtype, weak = entry
            parts.append(f"{shape}:{dtype}:{1 if weak else 0}")
    return ";".join(parts)


def _pack_executable(compiled) -> Dict[str, Any]:
    """``serialize_executable.serialize`` + a registry-local treedef
    form.

    ``serialize`` returns the XLA payload plus two ``PyTreeDef``s.
    Rather than pickling PyTreeDef objects (C-extension internals whose
    pickle support is version-fragile, especially for custom registered
    nodes), persist a structural SKELETON — the treedef unflattened
    over integer leaves, i.e. plain dicts/tuples/registered node
    instances, which pickle natively — and rebuild fresh PyTreeDefs
    from the LOADING process's own registry at load time."""
    import jax
    from jax.experimental import serialize_executable
    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    return {"payload": payload,
            "in_skel": jax.tree_util.tree_unflatten(
                in_tree, list(range(in_tree.num_leaves))),
            "out_skel": jax.tree_util.tree_unflatten(
                out_tree, list(range(out_tree.num_leaves))),
            # the devices it was compiled for, in assignment order:
            # loading onto anything else (the default is EVERY device of
            # the backend) breaks a one-device executable on a
            # multi-device host
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()]}


def _unpack_executable(exe: Dict[str, Any]):
    import jax
    from jax.experimental import serialize_executable
    if "in_skel" not in exe or "device_ids" not in exe:
        raise ValueError("legacy executable entry format")
    in_tree = jax.tree_util.tree_structure(exe["in_skel"])
    out_tree = jax.tree_util.tree_structure(exe["out_skel"])
    byId = {d.id: d for d in jax.devices()}
    return serialize_executable.deserialize_and_load(
        exe["payload"], in_tree, out_tree,
        execution_devices=[byId[i] for i in exe["device_ids"]])


# ---------------------------------------------------------------------------
# the on-disk cache
# ---------------------------------------------------------------------------

class AotCache:
    """Content-addressed on-disk store of serialized XLA executables.

    Layout (all writes atomic: tmp + ``os.replace``)::

        <dir>/<entry-digest>.aotx      sha256 header + pickled payload
        <dir>/ladder-<group>.json      input signatures seen per group
        <dir>/quarantine/...           corrupt entries, moved aside

    An entry file is ``64 hex chars of sha256(body) + body`` where body
    is the pickle of ``{"key": <full key json>, "exe": (payload,
    in_tree, out_tree)}`` from ``serialize_executable.serialize``.  The
    checksum makes a torn or bit-rotted write deterministically
    detectable: it is quarantined and the caller compiles fresh.
    """

    def __init__(self, directory: str,
                 maxBytes: Optional[int] = None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        if maxBytes is None:
            env = os.environ.get("DL4J_TPU_AOT_CACHE_MAX_BYTES")
            maxBytes = int(env) if env else _DEFAULT_MAX_BYTES
        self.maxBytes = int(maxBytes)

    # -- paths ----------------------------------------------------------
    def entryPath(self, digest: str) -> str:
        return os.path.join(self.directory, digest + _ENTRY_SUFFIX)

    def _ladderDir(self, group: str) -> str:
        return os.path.join(self.directory, f"ladder-{group}")

    def _quarantineDir(self) -> str:
        return os.path.join(self.directory, "quarantine")

    # -- metrics --------------------------------------------------------
    @staticmethod
    def _metrics():
        from deeplearning4j_tpu.telemetry import aot_metrics
        return aot_metrics()

    # -- read path ------------------------------------------------------
    def get(self, digest: str, kind: str = "unknown"):
        """Load the executable for ``digest``; None on miss.  Any
        corruption (bad checksum, unpicklable, runtime rejects the
        deserialize — e.g. a stale entry from another device topology
        that slipped past the key) quarantines the entry and returns
        None so the caller falls back to a fresh compile."""
        m = self._metrics()
        path = self.entryPath(digest)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as fh:
                blob = fh.read()
        except OSError:
            m.misses().inc(kind=kind)
            return None
        try:
            head, body = blob[:64], blob[64:]
            if hashlib.sha256(body).hexdigest().encode("ascii") != head:
                raise ValueError("checksum mismatch")
            entry = pickle.loads(body)
            loaded = _unpack_executable(entry["exe"])
        except Exception as e:
            log.warning("quarantining corrupt/stale AOT cache entry %s "
                        "(%s: %s)", os.path.basename(path),
                        type(e).__name__, e)
            self._quarantine(path)
            m.misses().inc(kind=kind)
            return None
        # touch: the LRU clock is file mtime
        try:
            os.utime(path)
        except OSError:
            pass
        m.hits().inc(kind=kind)
        m.load_seconds().observe(time.perf_counter() - t0)
        return loaded

    def _quarantine(self, path: str) -> None:
        qdir = self._quarantineDir()
        try:
            os.makedirs(qdir, exist_ok=True)
            dst = os.path.join(
                qdir, f"{os.path.basename(path)}.{os.getpid()}."
                      f"{time.time_ns()}")
            os.replace(path, dst)
        except OSError:
            try:
                os.remove(path)
            except OSError:
                pass
        self._metrics().quarantined().inc()
        # bound the graveyard in COUNT and BYTES: forensics are worth a
        # few files, never multiples of the cache's own size bound (a
        # quarantined sharded-mesh executable can be hundreds of MB)
        try:
            aged = []
            for fn in os.listdir(qdir):
                fp = os.path.join(qdir, fn)
                aged.append((os.path.getmtime(fp), os.path.getsize(fp),
                             fp))
            aged.sort(reverse=True)         # newest first
            budget = self.maxBytes // 8
            kept = 0
            for i, (_m, size, fp) in enumerate(aged):
                kept += size
                if i >= _QUARANTINE_KEEP or kept > budget:
                    os.remove(fp)
        except OSError:
            pass

    # -- write path -----------------------------------------------------
    def put(self, digest: str, compiled, key: Dict[str, Any],
            group: str, signature: str,
            bakeSeconds: Optional[float] = None) -> bool:
        """Serialize + atomically publish one executable, record its
        signature on the group's ladder, then enforce the LRU bound.
        Returns False — entry skipped, run unaffected — when the
        backend cannot serialize this executable OR the cache media
        rejects the write (full/read-only disk): the caller already
        holds the compiled executable, so a cache write failure must
        never take the step down."""
        try:
            exe = _pack_executable(compiled)
            body = pickle.dumps({"key": _canon(key), "exe": exe},
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            log.warning("AOT cache: executable not serializable on this "
                        "backend (%s: %s); entry skipped",
                        type(e).__name__, e)
            return False
        blob = hashlib.sha256(body).hexdigest().encode("ascii") + body
        path = self.entryPath(digest)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            with open(tmp, "wb") as fh:
                fh.write(blob)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
        except OSError as e:
            log.warning("AOT cache: entry write failed (%s: %s); "
                        "continuing uncached", type(e).__name__, e)
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        try:
            # independent of the entry publish: the entry above is
            # live and lazily loadable by digest even if the ladder
            # record fails — only boot PRELOAD misses it
            self._recordLadder(group, signature, digest)
        except OSError as e:
            log.warning("AOT cache: ladder record failed (%s: %s); "
                        "entry stays loadable by digest",
                        type(e).__name__, e)
        if bakeSeconds is not None:
            self._metrics().bake_seconds().observe(bakeSeconds)
        self._evict()
        return True

    def _recordLadder(self, group: str, signature: str,
                      digest: str) -> None:
        """Record (signature, digest) on the group's ladder so a later
        boot can preload every executable this group ever compiled.
        One atomic file PER ENTRY (``ladder-<group>/<digest>.json``):
        concurrent bakers — N fleet workers sharing one cache dir —
        each publish their own file, so there is no read-modify-write
        to lose entries to."""
        ldir = self._ladderDir(group)
        path = os.path.join(ldir, f"{digest}.json")
        if os.path.exists(path):
            return
        os.makedirs(ldir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with open(tmp, "w") as fh:
            json.dump({"signature": signature, "digest": digest}, fh)
        os.replace(tmp, path)

    def ladder(self, group: str) -> List[Dict[str, str]]:
        ldir = self._ladderDir(group)
        out: List[Dict[str, str]] = []
        try:
            names = sorted(os.listdir(ldir))
        except OSError:
            return out
        for fn in names:
            if not fn.endswith(".json"):
                continue
            try:
                with open(os.path.join(ldir, fn)) as fh:
                    out.append(json.load(fh))
            except (OSError, json.JSONDecodeError, ValueError):
                continue
        return out

    # -- bounds ---------------------------------------------------------
    def entries(self) -> List[Tuple[str, int, float]]:
        """(digest, bytes, mtime) for every entry on disk."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return out
        for fn in names:
            if not fn.endswith(_ENTRY_SUFFIX):
                continue
            fp = os.path.join(self.directory, fn)
            try:
                st = os.stat(fp)
            except OSError:
                continue
            out.append((fn[:-len(_ENTRY_SUFFIX)], st.st_size, st.st_mtime))
        return out

    def totalBytes(self) -> int:
        return sum(size for _d, size, _m in self.entries())

    def _dropLadderRecords(self, digest: str) -> None:
        """Remove a deleted entry's ladder record(s) so later boots
        don't preload a digest that no longer exists (each stale record
        would read as a permanent cache miss)."""
        try:
            groups = [fn for fn in os.listdir(self.directory)
                      if fn.startswith("ladder-")]
        except OSError:
            return
        for g in groups:
            try:
                os.remove(os.path.join(self.directory, g,
                                       f"{digest}.json"))
            except OSError:
                pass

    def _sweepTmp(self) -> None:
        """Delete orphaned ``*.tmp`` blobs a killed writer left behind
        (preemption mid-``put``/mid-ladder-record is a first-class
        scenario here), in the cache root AND the ladder dirs.  Age-
        gated so a LIVE concurrent writer's in-flight tmp survives."""
        cutoff = time.time() - 3600.0
        try:
            names = os.listdir(self.directory)
        except OSError:
            return
        dirs = [self.directory] + [
            os.path.join(self.directory, fn) for fn in names
            if fn.startswith("ladder-")]
        for d in dirs:
            try:
                files = os.listdir(d)
            except OSError:
                continue
            for fn in files:
                if not fn.endswith(".tmp"):
                    continue
                fp = os.path.join(d, fn)
                try:
                    if os.path.getmtime(fp) < cutoff:
                        os.remove(fp)
                except OSError:
                    pass

    def _evict(self) -> None:
        """LRU: drop least-recently-used entries (and their ladder
        records) until under the size bound; also sweeps aged orphan
        tmp files."""
        self._sweepTmp()
        entries = self.entries()
        total = sum(size for _d, size, _m in entries)
        if total <= self.maxBytes:
            return
        m = self._metrics()
        for digest, size, _mtime in sorted(entries, key=lambda e: e[2]):
            if total <= self.maxBytes:
                break
            try:
                os.remove(self.entryPath(digest))
            except OSError:
                continue
            self._dropLadderRecords(digest)
            total -= size
            m.evictions().inc()

    def clear(self) -> None:
        import shutil
        for digest, _size, _m in self.entries():
            try:
                os.remove(self.entryPath(digest))
            except OSError:
                pass
        try:
            for fn in os.listdir(self.directory):
                if fn.startswith("ladder-"):
                    shutil.rmtree(os.path.join(self.directory, fn),
                                  ignore_errors=True)
        except OSError:
            pass


# ---------------------------------------------------------------------------
# process-global configuration
# ---------------------------------------------------------------------------

_CACHE: Optional[AotCache] = None
_CACHE_EXPLICIT = False


def set_aot_cache(cache) -> None:
    """Install the process-global cache: an :class:`AotCache`, a
    directory path, or None to disable.  An explicit install (including
    None) takes precedence over ``DL4J_TPU_AOT_CACHE_DIR``."""
    global _CACHE, _CACHE_EXPLICIT
    _CACHE = AotCache(cache) if isinstance(cache, str) else cache
    _CACHE_EXPLICIT = True


def aot_cache() -> Optional[AotCache]:
    """The process-global cache, or None when AOT caching is off.
    Resolution order: the kill switch ``DL4J_TPU_AOT_CACHE=0`` wins,
    then :func:`set_aot_cache`, then ``DL4J_TPU_AOT_CACHE_DIR``."""
    global _CACHE
    if os.environ.get("DL4J_TPU_AOT_CACHE") == "0":
        return None
    if _CACHE_EXPLICIT:
        return _CACHE
    env = os.environ.get("DL4J_TPU_AOT_CACHE_DIR")
    if env and (_CACHE is None or
                _CACHE.directory != os.path.abspath(env)):
        _CACHE = AotCache(env)
    return _CACHE


# ---------------------------------------------------------------------------
# the dispatch wrapper
# ---------------------------------------------------------------------------

class AotDispatch:
    """Drop-in callable for a ``jax.jit`` wrapper on a boot path.

    Per input signature: in-memory executable -> call; else disk cache
    load (a few ms); else ONE fresh ``lower().compile()`` baked back to
    disk.  ``_cache_size()`` counts fresh XLA compiles ONLY — the
    telemetry layers (``train_step_span``, ``MeshTrainer``,
    ``BucketedExecutor``) read it as "recompiles", and a disk load is
    not a recompile; this is exactly what makes
    ``dl4j_tpu_train_compile_seconds_total`` ~0 on a warm boot.
    """

    def __init__(self, jitted, cache: AotCache, keyBase: Dict[str, Any],
                 kind: str):
        self._jitted = jitted
        self._cache = cache
        self._keyBase = keyBase
        self.kind = kind
        self.group = _digest(keyBase)
        # two-tier lookup: the hot dict is keyed by the cheap tuple
        # signature computed per call; preloaded executables sit keyed
        # by their on-disk STRING signature until the first call
        # promotes them (string rendering is miss/boot cost, not
        # per-step cost)
        self._loaded: Dict[tuple, Any] = {}
        self._preloaded: Dict[str, Any] = {}
        self._promoted: set = set()     # string sigs already in _loaded
        self._fresh = 0
        self._lock = threading.Lock()

    # the jit-cache-accounting probe every telemetry layer reads
    def _cache_size(self) -> int:
        return self._fresh

    def loadedCount(self) -> int:
        return len(self._loaded) + len(self._preloaded)

    def entryDigest(self, signature: str) -> str:
        return _digest({"base": self._keyBase, "signature": signature})

    def preload(self) -> int:
        """Load every executable on this group's ladder (boot-path hook:
        MeshTrainer install, supervisor resume, serving warm).  Returns
        the number loaded."""
        n = 0
        for entry in self._cache.ladder(self.group):
            sig = entry.get("signature")
            digest = entry.get("digest")
            if not sig or not digest or sig in self._preloaded \
                    or sig in self._promoted:
                continue
            exe = self._cache.get(digest, kind=self.kind)
            if exe is not None:
                self._preloaded[sig] = exe
                n += 1
        return n

    def __call__(self, *args):
        key = _sig_key(args)
        exe = self._loaded.get(key)
        if exe is not None:
            return exe(*args)
        with self._lock:
            exe = self._loaded.get(key)
            if exe is None:
                sig = _sig_str(key)
                exe = self._preloaded.pop(sig, None)
                if exe is None:
                    exe = self._miss(sig, args)
                self._loaded[key] = exe
                self._promoted.add(sig)
        return exe(*args)

    def _miss(self, sig: str, args: tuple):
        digest = self.entryDigest(sig)
        exe = self._cache.get(digest, kind=self.kind)
        if exe is None:
            t0 = time.perf_counter()
            exe = self._jitted.lower(*args).compile()
            dt = time.perf_counter() - t0
            self._fresh += 1
            self._cache.put(digest, exe,
                            key={"base": self._keyBase, "signature": sig},
                            group=self.group, signature=sig,
                            bakeSeconds=dt)
        return exe


# ---------------------------------------------------------------------------
# boot-path wiring helpers
# ---------------------------------------------------------------------------

def wrap_jit(jitted, *, kind: str, model=None, plan=None,
             preload: bool = True):
    """Wrap a ``jax.jit`` object in an :class:`AotDispatch` when the
    process-global cache is configured; otherwise return it UNCHANGED
    (zero behavior change with the cache off).  ``model``/``plan``
    contribute their digests to the key — a plan is what scopes mesh
    executables to one exact (layout, device set) so a re-mesh re-keys."""
    cache = aot_cache()
    if cache is None:
        return jitted
    keyBase: Dict[str, Any] = {"kind": kind,
                               "versions": version_fingerprint()}
    try:
        # the wrapped function's import identity is always part of the
        # key: without it, two DIFFERENT functions wrapped with the
        # same kind/model/avals would collide on one entry and silently
        # serve each other's math
        wrapped = getattr(jitted, "__wrapped__", jitted)
        keyBase["fn"] = (f"{getattr(wrapped, '__module__', '?')}."
                         f"{getattr(wrapped, '__qualname__', '?')}")
        if model is not None:
            keyBase["model"] = model_digest(model)
        if plan is not None:
            keyBase["plan"] = plan_digest(plan)
        else:
            keyBase["devices"] = device_fingerprint()
    except Exception as e:
        # an undigestable model/plan must degrade to plain jit, never
        # take the step down
        log.warning("AOT cache: could not key %s (%s: %s); falling back "
                    "to plain jit", kind, type(e).__name__, e)
        return jitted
    disp = AotDispatch(jitted, cache, keyBase, kind)
    if preload:
        n = disp.preload()
        if n:
            log.info("AOT cache: preloaded %d %s executable(s) for "
                     "group %s", n, kind, disp.group[:12])
    return disp


def wrap_serving_model(model) -> bool:
    """AOT-wrap a forward model's ``_outputFn`` in place (the
    ``BucketedExecutor.warm()`` hook).  No-op (False) with the cache off
    or for a model without one."""
    if aot_cache() is None or not hasattr(model, "_outputFn"):
        return False
    fn = model._outputFn              # builds the cached_property jit
    if not isinstance(fn, AotDispatch):
        model.__dict__["_outputFn"] = wrap_jit(
            fn, kind="output", model=model)
    return True


def preload_model(model) -> int:
    """Preload the train-step ladder for ``model`` (the supervisor's
    resume hook): forces the step install NOW — outside the first
    step's timed span — so restart-to-first-step pays executable LOADS
    here, not inside the step.  For a mesh facade (ParallelWrapper)
    that means driving ``MeshTrainer._ensure_ready`` (its install path
    wraps + preloads against the current plan); for a bare net it
    touches the ``_trainStep`` cached_property.  Returns executables
    now loaded; 0 with the cache off."""
    if aot_cache() is None or model is None:
        return 0
    net = getattr(model, "model", model)     # unwrap a ParallelWrapper
    trainer = getattr(model, "trainer", None)
    if callable(trainer):
        try:
            trainer()._ensure_ready()
        except Exception as e:
            # the next step installs anyway — never break resume here
            log.warning("AOT cache: mesh preload at resume failed "
                        "(%s: %s); first step will install instead",
                        type(e).__name__, e)
    fn = getattr(net, "_trainStep", None)
    if isinstance(fn, AotDispatch):
        fn.preload()                # idempotent top-up
        return fn.loadedCount()
    return 0
