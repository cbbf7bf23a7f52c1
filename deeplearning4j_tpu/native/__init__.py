"""Python binding for the dl4j_native C++ runtime.

The TPU analogue of the reference's backend loading layer (reference:
nd4j-native-api ``NativeOpsHolder`` + JavaCPP presets): locate or build
``libdl4j_native.so`` (sources in ``native/``), expose its flat C ABI via
ctypes, and degrade to pure-NumPy fallbacks when no toolchain is available —
functional parity either way, the native path is the fast one.

Public surface:

- :func:`available` / :func:`backend` — which implementation is live.
- :func:`parallel_for`, :func:`num_threads`, :func:`set_num_threads`
- :func:`threshold_encode` / :func:`threshold_decode` /
  :func:`bitmap_encode` / :func:`bitmap_decode` — gradient compression with
  residual semantics (reference: encodeThresholdP1..P3 / encodeBitmap).
- :func:`philox_uniform` / :func:`philox_gaussian` — counter-addressed RNG.
- :class:`Workspace` — host arena allocator (reference: MemoryWorkspace).
- :func:`csv_parse` — native text→float32 matrix fast path for datavec.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_BUILD_DIR = _NATIVE_DIR / "build"
_LIB_NAME = "libdl4j_native.so"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _compile() -> Optional[Path]:
    """Build the shared library; cmake+ninja preferred, bare g++ fallback."""
    out = _BUILD_DIR / _LIB_NAME
    srcs = sorted((_NATIVE_DIR / "src").glob("*.cpp"))
    if not srcs:
        return None
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # one builder at a time ACROSS processes: on a fresh checkout every
    # xdist worker gets here together, and two cmake runs in one directory
    # (or a g++ writing the library another process is loading) fail one
    # of them.  The lock is the build directory's; closing it releases it.
    fd = os.open(_BUILD_DIR, os.O_RDONLY)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        if out.exists() and not _stale(out):
            return out                  # built while this process waited
        return _compile_locked(out, srcs)
    finally:
        os.close(fd)


def _compile_locked(out: Path, srcs) -> Optional[Path]:
    try:
        subprocess.run(
            ["cmake", "-G", "Ninja", "-S", str(_NATIVE_DIR), "-B", str(_BUILD_DIR)],
            check=True, capture_output=True, timeout=120)
        subprocess.run(["cmake", "--build", str(_BUILD_DIR)],
                       check=True, capture_output=True, timeout=300)
        if out.exists():
            return out
    except (OSError, subprocess.SubprocessError):
        pass
    try:  # toolchain without cmake/ninja: single g++ invocation
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
             "-I", str(_NATIVE_DIR / "include"),
             *[str(s) for s in srcs], "-o", str(out)],
            check=True, capture_output=True, timeout=300)
        return out if out.exists() else None
    except (OSError, subprocess.SubprocessError):
        return None


def _stale(so_path: Path) -> bool:
    """True when any C++ source/header is newer than the built library."""
    try:
        built = so_path.stat().st_mtime
        srcs = list((_NATIVE_DIR / "src").glob("*.cpp")) + \
            list((_NATIVE_DIR / "include").glob("*.h"))
        return any(s.stat().st_mtime > built for s in srcs)
    except OSError:
        return True


def _declare(lib: ctypes.CDLL) -> None:
    i32, i64, u32, u64 = (ctypes.c_int32, ctypes.c_int64, ctypes.c_uint32,
                          ctypes.c_uint64)
    f32 = ctypes.c_float
    pf32 = ctypes.POINTER(ctypes.c_float)
    pi32 = ctypes.POINTER(ctypes.c_int32)
    pu32 = ctypes.POINTER(ctypes.c_uint32)
    void_p = ctypes.c_void_p

    lib.dl4j_abi_version.restype = i64
    lib.dl4j_num_threads.restype = i32
    lib.dl4j_set_num_threads.argtypes = [i32]
    lib.dl4j_parallel_for.argtypes = [void_p, void_p, i64, i64, i64]

    lib.dl4j_threshold_count.restype = i64
    lib.dl4j_threshold_count.argtypes = [pf32, i64, f32]
    lib.dl4j_threshold_encode.restype = i64
    lib.dl4j_threshold_encode.argtypes = [pf32, i64, f32, pi32, i64]
    lib.dl4j_threshold_decode.argtypes = [pi32, i64, f32, pf32, i64]
    lib.dl4j_bitmap_encode.restype = i64
    lib.dl4j_bitmap_encode.argtypes = [pf32, i64, f32, pu32]
    lib.dl4j_bitmap_decode.argtypes = [pu32, i64, f32, pf32]

    lib.dl4j_philox_uniform.argtypes = [u64, u64, pf32, i64]
    lib.dl4j_philox_gaussian.argtypes = [u64, u64, pf32, i64]
    lib.dl4j_philox_uint32.argtypes = [u64, u64, pu32, i64]

    lib.dl4j_workspace_create.restype = void_p
    lib.dl4j_workspace_create.argtypes = [i64]
    lib.dl4j_workspace_alloc.restype = void_p
    lib.dl4j_workspace_alloc.argtypes = [void_p, i64]
    lib.dl4j_workspace_reset.argtypes = [void_p]
    lib.dl4j_workspace_destroy.argtypes = [void_p]
    for fn in ("capacity", "used", "spilled"):
        getattr(lib, f"dl4j_workspace_{fn}").restype = i64
        getattr(lib, f"dl4j_workspace_{fn}").argtypes = [void_p]

    lib.dl4j_csv_count_rows.restype = i64
    lib.dl4j_csv_count_rows.argtypes = [ctypes.c_char_p, i64]
    lib.dl4j_csv_parse_f32.restype = i64
    lib.dl4j_csv_parse_f32.argtypes = [ctypes.c_char_p, i64, ctypes.c_char,
                                       i32, pf32, i64, pi32]


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("DL4J_TPU_DISABLE_NATIVE"):
            return None
        path = _BUILD_DIR / _LIB_NAME
        if not path.exists() or _stale(path):
            built = _compile()
            if built is None:
                return None
            path = built
        try:
            lib = ctypes.CDLL(str(path))
            _declare(lib)
            if lib.dl4j_abi_version() != 1:
                return None
            _lib = lib
        except OSError:
            _lib = None
        return _lib


def available() -> bool:
    """True when the C++ runtime is loaded (vs NumPy fallback)."""
    return _load() is not None


def backend() -> str:
    return "native" if available() else "numpy"


# ---------------------------------------------------------------- threads

def num_threads() -> int:
    lib = _load()
    return int(lib.dl4j_num_threads()) if lib else 1


def set_num_threads(n: int) -> None:
    lib = _load()
    if lib:
        lib.dl4j_set_num_threads(int(n))


_KERNEL_FN = ctypes.CFUNCTYPE(None, ctypes.c_int64, ctypes.c_int64,
                              ctypes.c_void_p)


def parallel_for(fn, start: int, stop: int, min_chunk: int = 1) -> None:
    """Run ``fn(lo, hi)`` over chunks of [start, stop) on the native pool."""
    lib = _load()
    if lib is None:
        fn(start, stop)
        return
    cb = _KERNEL_FN(lambda lo, hi, _arg: fn(lo, hi))
    lib.dl4j_parallel_for(ctypes.cast(cb, ctypes.c_void_p), None,
                          start, stop, min_chunk)


# ---------------------------------------------------------- compression

def _f32ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _require_f32_inplace(grad: np.ndarray, fn: str) -> np.ndarray:
    """In-place residual semantics only work on the caller's own buffer —
    a silent ascontiguousarray copy would mutate the copy and re-send the
    same gradient mass every step."""
    if not (isinstance(grad, np.ndarray) and grad.dtype == np.float32
            and grad.flags.c_contiguous):
        raise TypeError(f"{fn} mutates its input in place and requires a "
                        "C-contiguous float32 ndarray; got "
                        f"{type(grad).__name__}"
                        f"{'/' + str(grad.dtype) if isinstance(grad, np.ndarray) else ''}")
    return grad


def threshold_encode(grad: np.ndarray, threshold: float) -> np.ndarray:
    """Sparse-encode ``grad`` in place (residual semantics).

    Returns int32 signed indices: ``index+1`` carrying the update sign.
    ``grad`` must be a C-contiguous float32 vector (enforced); encoded mass
    is subtracted from it so the caller keeps the residual.
    """
    grad = _require_f32_inplace(grad, "threshold_encode")
    lib = _load()
    if lib is None:
        mask = np.abs(grad) >= threshold
        idx = np.nonzero(mask)[0].astype(np.int32)
        signs = np.sign(grad[idx]).astype(np.int32)
        grad[idx] -= signs * np.float32(threshold)
        return (idx.astype(np.int32) + 1) * signs
    cap = lib.dl4j_threshold_count(_f32ptr(grad), grad.size,
                                   ctypes.c_float(threshold))
    out = np.empty(int(cap), dtype=np.int32)
    n = lib.dl4j_threshold_encode(
        _f32ptr(grad), grad.size, ctypes.c_float(threshold),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), out.size)
    return out[:int(n)]


def threshold_decode(idx: np.ndarray, threshold: float,
                     target: np.ndarray) -> np.ndarray:
    """Apply a sparse message onto ``target`` (float32 vector) in place."""
    idx = np.ascontiguousarray(idx, dtype=np.int32)
    assert target.dtype == np.float32 and target.flags.c_contiguous
    lib = _load()
    if lib is None:
        pos = np.abs(idx) - 1
        np.add.at(target, pos, np.sign(idx).astype(np.float32)
                  * np.float32(threshold))
        return target
    lib.dl4j_threshold_decode(
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), idx.size,
        ctypes.c_float(threshold), _f32ptr(target), target.size)
    return target


def bitmap_encode(grad: np.ndarray, threshold: float) -> Tuple[np.ndarray, int]:
    """Dense 2-bit encode of ``grad`` in place; returns (bitmap words, count).
    ``grad`` must be a C-contiguous float32 vector (enforced)."""
    grad = _require_f32_inplace(grad, "bitmap_encode")
    words = np.zeros((grad.size + 15) // 16, dtype=np.uint32)
    lib = _load()
    if lib is None:
        codes = np.where(grad >= threshold, 1,
                         np.where(grad <= -threshold, 2, 0)).astype(np.uint32)
        signs = np.where(codes == 1, 1.0, np.where(codes == 2, -1.0, 0.0))
        grad -= signs.astype(np.float32) * np.float32(threshold)
        idx = np.arange(grad.size)
        np.bitwise_or.at(words, idx >> 4, codes << ((idx & 15) << 1))
        return words, int(np.count_nonzero(codes))
    n = lib.dl4j_bitmap_encode(
        _f32ptr(grad), grad.size, ctypes.c_float(threshold),
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
    return words, int(n)


def bitmap_decode(words: np.ndarray, n: int, threshold: float,
                  target: np.ndarray) -> np.ndarray:
    words = np.ascontiguousarray(words, dtype=np.uint32)
    assert target.dtype == np.float32 and target.flags.c_contiguous
    lib = _load()
    if lib is None:
        idx = np.arange(n)
        codes = (words[idx >> 4] >> ((idx & 15) << 1)) & 3
        target += np.where(codes == 1, threshold,
                           np.where(codes == 2, -threshold, 0.0)
                           ).astype(np.float32)
        return target
    lib.dl4j_bitmap_decode(
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), n,
        ctypes.c_float(threshold), _f32ptr(target))
    return target


# ------------------------------------------------------------------- rng

def philox_uniform(seed: int, offset: int, n: int) -> np.ndarray:
    """U[0,1) float32 stream addressed by (seed, offset) — slicing-stable."""
    out = np.empty(n, dtype=np.float32)
    lib = _load()
    if lib is None:
        # NumPy Philox with the same counter discipline (values differ from
        # the native kernel; both are valid streams — determinism is per
        # backend, matching the reference's per-backend RNG contract).
        bits = np.random.Philox(key=seed, counter=offset)
        out[:] = np.random.Generator(bits).random(n, dtype=np.float32)
        return out
    lib.dl4j_philox_uniform(seed, offset, _f32ptr(out), n)
    return out


def philox_gaussian(seed: int, offset: int, n: int) -> np.ndarray:
    out = np.empty(n, dtype=np.float32)
    lib = _load()
    if lib is None:
        bits = np.random.Philox(key=seed, counter=offset)
        out[:] = np.random.Generator(bits).standard_normal(n, dtype=np.float32)
        return out
    lib.dl4j_philox_gaussian(seed, offset, _f32ptr(out), n)
    return out


# ------------------------------------------------------------- workspace

class Workspace:
    """Host arena allocator with LEARNING-policy growth.

    (reference: org.nd4j.linalg.api.memory.MemoryWorkspace /
    libnd4j memory::Workspace).  ``alloc`` returns a NumPy float32 view over
    arena memory valid until the next ``reset``.
    """

    def __init__(self, initial_bytes: int = 1 << 20):
        self._lib = _load()
        self._arrays = []  # fallback: retain allocations for the cycle
        if self._lib is not None:
            self._ptr = self._lib.dl4j_workspace_create(int(initial_bytes))
        else:
            self._ptr = None
            self._capacity = int(initial_bytes)
            self._used = 0
            self._spilled = 0

    def alloc_f32(self, n: int) -> np.ndarray:
        nbytes = int(n) * 4
        if self._lib is not None:
            p = self._lib.dl4j_workspace_alloc(self._ptr, nbytes)
            if not p:  # NULL: allocation failure or destroyed workspace —
                # from_address would segfault instead of raising
                raise MemoryError(
                    f"workspace alloc of {nbytes} bytes failed")
            buf = (ctypes.c_float * int(n)).from_address(p)
            return np.frombuffer(buf, dtype=np.float32)
        a = np.empty(int(n), dtype=np.float32)
        self._arrays.append(a)
        if self._used + nbytes <= self._capacity:
            self._used += nbytes
        else:
            self._spilled += nbytes
        return a

    def reset(self) -> None:
        if self._lib is not None:
            self._lib.dl4j_workspace_reset(self._ptr)
        else:
            self._arrays.clear()
            if self._spilled:
                self._capacity += self._spilled
            self._used = 0
            self._spilled = 0

    @property
    def capacity(self) -> int:
        if self._lib is not None:
            return int(self._lib.dl4j_workspace_capacity(self._ptr))
        return self._capacity

    @property
    def spilled(self) -> int:
        if self._lib is not None:
            return int(self._lib.dl4j_workspace_spilled(self._ptr))
        return self._spilled

    def close(self) -> None:
        if self._lib is not None and self._ptr:
            self._lib.dl4j_workspace_destroy(self._ptr)
            self._ptr = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ------------------------------------------------------------------ csv

def csv_parse(text: bytes | str, delim: str = ",",
              skip_rows: int = 0) -> np.ndarray:
    """Parse numeric delimiter-separated text into a float32 matrix."""
    if isinstance(text, str):
        text = text.encode("utf-8")
    lib = _load()
    if lib is None:
        rows = [ln for ln in text.decode("utf-8").splitlines() if ln.strip()]
        rows = rows[skip_rows:]
        if not rows:
            return np.zeros((0, 0), dtype=np.float32)
        data = [[float(v) for v in ln.split(delim)] for ln in rows]
        return np.asarray(data, dtype=np.float32)
    nrows = lib.dl4j_csv_count_rows(text, len(text)) - skip_rows
    if nrows <= 0:
        return np.zeros((0, 0), dtype=np.float32)
    # One probe pass sizes the buffer: columns from the first data line
    # (same non-empty-line indexing as the C side, which trims ' ' and '\r'
    # only — stripping other whitespace here would desynchronise the two).
    nonempty = [ln for ln in text.split(b"\n") if ln.strip(b" \r")]
    first = nonempty[skip_rows] if len(nonempty) > skip_rows else b""
    ncols = first.count(delim.encode()) + 1
    out = np.empty(int(nrows) * ncols, dtype=np.float32)
    cols = ctypes.c_int32(0)
    got = lib.dl4j_csv_parse_f32(
        text, len(text), ctypes.c_char(delim.encode()), skip_rows,
        _f32ptr(out), out.size, ctypes.byref(cols))
    if got < 0:
        raise ValueError("malformed or ragged numeric CSV")
    return out[:int(got) * cols.value].reshape(int(got), cols.value)
