"""Python binding for the native C++ dataplane library.

The reference implements its dataplane in native code (HLS C++ reduce_ops /
hp_compression kernels, C firmware); our equivalent hot paths live in
``native/src/dataplane.cpp`` (built into ``libaccl_dataplane.so`` by
``native/Makefile``) and are loaded here via ctypes, with numpy fallbacks in
``backends/emulator/dataplane.py`` when the library is unavailable.  Every
process that wants a library first runs ``make -C native`` (:func:`build`)
and lets make's timestamps decide: ``native/build/`` is not under git, so a
library found there may be older than the sources beside it, or built on
another machine.  No toolchain means the numpy path; a build that FAILS says
so in a warning and also means the numpy path, never a stale library.
"""

from __future__ import annotations

import ctypes
import pathlib
import shutil
import subprocess
import warnings

import numpy as np

from ..constants import ReduceFunction

_LIB = None
_LOAD_ATTEMPTED = False

_NATIVE_DIR = pathlib.Path(__file__).resolve().parent.parent.parent / "native"
_SO_PATH = _NATIVE_DIR / "build" / "libaccl_dataplane.so"
_ENGINE_SO_PATH = _NATIVE_DIR / "build" / "libaccl_engine.so"
_DATALOADER_SO_PATH = _NATIVE_DIR / "build" / "libaccl_dataloader.so"


_BUILD_OK = None  # None until build() ran in this process


def build() -> bool:
    """``make -C native`` once a process, serialized across processes with
    a file lock so N spawn-launched ranks don't race on the same output
    file.  True when make left the libraries up to date."""
    global _BUILD_OK
    if _BUILD_OK is not None:
        return _BUILD_OK
    _BUILD_OK = False
    if shutil.which("make") is None or not _NATIVE_DIR.is_dir():
        return False  # no toolchain: the documented numpy path
    import fcntl

    with open(_NATIVE_DIR / ".build.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            proc = subprocess.run(
                ["make", "-C", str(_NATIVE_DIR)],
                capture_output=True, text=True, timeout=300,
            )
        except subprocess.TimeoutExpired:
            warnings.warn("native build timed out: `make -C native`",
                          RuntimeWarning)
            return False
    if proc.returncode != 0:
        warnings.warn(
            "native build failed (`make -C native`), using the numpy "
            "path:\n" + proc.stderr[-2000:],
            RuntimeWarning,
        )
        return False
    _BUILD_OK = True
    return True


def _bind(lib):
    c = ctypes
    lib.accl_reduce_inplace.restype = c.c_int
    lib.accl_reduce_inplace.argtypes = [
        c.c_int, c.c_int, c.c_void_p, c.c_void_p, c.c_size_t,
    ]
    for name in (
        "accl_f32_to_f16", "accl_f32_to_bf16", "accl_f16_to_f32",
        "accl_bf16_to_f32", "accl_f32_to_f8e4m3", "accl_f8e4m3_to_f32",
        "accl_f32_to_f8e5m2", "accl_f8e5m2_to_f32",
    ):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [c.c_void_p, c.c_void_p, c.c_size_t]
    lib.accl_rxpool_create.restype = c.c_int
    lib.accl_rxpool_create.argtypes = [c.c_int]
    lib.accl_rxpool_fill.restype = c.c_int
    lib.accl_rxpool_fill.argtypes = [
        c.c_int, c.c_uint32, c.c_uint32, c.c_uint32, c.c_uint64,
    ]
    lib.accl_rxpool_seek.restype = c.c_int
    lib.accl_rxpool_seek.argtypes = lib.accl_rxpool_fill.argtypes
    lib.accl_rxpool_release.restype = None
    lib.accl_rxpool_release.argtypes = [c.c_int, c.c_int]
    lib.accl_rxpool_occupancy.restype = c.c_int
    lib.accl_rxpool_occupancy.argtypes = [c.c_int]
    lib.accl_rxpool_destroy.restype = None
    lib.accl_rxpool_destroy.argtypes = [c.c_int]


def _load():
    global _LIB, _LOAD_ATTEMPTED
    if _LOAD_ATTEMPTED:
        return _LIB
    _LOAD_ATTEMPTED = True
    if not build():
        return None
    lib = ctypes.CDLL(str(_SO_PATH))
    _bind(lib)
    _LIB = lib
    return _LIB


# dtype codes shared with native/src/dataplane.cpp
_DTYPE_CODE = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    np.dtype(np.float16): 4,
}


def available() -> bool:
    return _load() is not None


def reduce_inplace(fn: ReduceFunction, dst: np.ndarray, src: np.ndarray) -> bool:
    """Returns True if the native path handled the reduction."""
    lib = _load()
    if lib is None:
        return False
    code = _DTYPE_CODE.get(dst.dtype)
    if code is None or not dst.flags.c_contiguous or not src.flags.c_contiguous:
        return False
    rc = lib.accl_reduce_inplace(
        int(fn), code, dst.ctypes.data, src.ctypes.data, dst.size
    )
    return rc == 0


_CAST_FNS = {
    "float16": ("accl_f32_to_f16", "accl_f16_to_f32", np.uint16),
    "bfloat16": ("accl_f32_to_bf16", "accl_bf16_to_f32", np.uint16),
    "float8_e4m3": ("accl_f32_to_f8e4m3", "accl_f8e4m3_to_f32", np.uint8),
    "float8_e5m2": ("accl_f32_to_f8e5m2", "accl_f8e5m2_to_f32", np.uint8),
}


def cast_f32(src: np.ndarray, wire: str) -> np.ndarray:
    """f32 -> f16/bf16/fp8 wire compression (returns the wire's bit
    patterns: uint16 for the 16-bit lanes, uint8 for fp8)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    name, _, bits = _CAST_FNS[wire]
    src = np.ascontiguousarray(src, np.float32)
    out = np.empty(src.size, bits)
    getattr(lib, name)(src.ctypes.data, out.ctypes.data, src.size)
    return out


def uncast_f32(src: np.ndarray, wire: str) -> np.ndarray:
    """Wire bit patterns -> f32."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    _, name, bits = _CAST_FNS[wire]
    src = np.ascontiguousarray(src, bits)
    out = np.empty(src.size, np.float32)
    getattr(lib, name)(src.ctypes.data, out.ctypes.data, src.size)
    return out


class NativeRxMatcher:
    """C++-backed RX signature pool (the rxbuf_seek role); payloads stay in
    Python, indexed by slot id."""

    def __init__(self, nslots: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._pool = lib.accl_rxpool_create(nslots)
        self.nslots = nslots

    def fill(self, comm: int, src: int, tag: int, seqn: int) -> int:
        return self._lib.accl_rxpool_fill(self._pool, comm, src, tag, seqn)

    def seek(self, comm: int, src: int, tag: int, seqn: int) -> int:
        return self._lib.accl_rxpool_seek(self._pool, comm, src, tag, seqn)

    def release(self, slot: int) -> None:
        self._lib.accl_rxpool_release(self._pool, slot)

    def occupancy(self) -> int:
        return self._lib.accl_rxpool_occupancy(self._pool)

    def close(self) -> None:
        if self._pool is not None:
            self._lib.accl_rxpool_destroy(self._pool)
            self._pool = None

    def __del__(self):  # pragma: no cover - GC timing
        try:
            self.close()
        except Exception:
            pass
