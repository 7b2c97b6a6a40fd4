"""Token-stream input pipeline over the native C++ prefetching loader.

The reference keeps its host runtime native (the C++ driver under
``driver/xrt``); the training input pipeline gets the same treatment:
``native/src/dataloader.cpp`` mmaps a binary token file and assembles
``(batch, seq+1)`` windows on a background thread into a bounded ring, so
the Python step loop only copies a ready batch while the next one is
being built.  Sampling is stateless and deterministic (splitmix64 of
``seed ^ step ^ row`` into this shard's stripe), which gives:

* exact checkpoint resume — ``seek(step)`` repositions without replay;
* disjoint dp shards — each rank draws windows from its own stripe;
* reproducibility — same (file, seed, step) is the same batch anywhere.

File format ``ACCLTOK1``: 8-byte magic, u32 dtype code (2 = uint16,
4 = uint32), u64 token count, raw little-endian ids.
:func:`write_token_file` produces it.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional, Tuple

import numpy as np

_MAGIC = b"ACCLTOK1"

_ERRORS = {
    -1: "cannot open file",
    -2: "bad magic/header (not an ACCLTOK1 file?)",
    -3: "file too small for one window (need seq+2 tokens per shard)",
    -4: "invalid arguments",
    -5: "loader closed",
}


def write_token_file(path: str, tokens: np.ndarray) -> None:
    """Write a 1-D integer token array in the ``ACCLTOK1`` format
    (uint16 when every id fits, else uint32)."""
    tokens = np.ascontiguousarray(np.asarray(tokens).reshape(-1))
    if not np.issubdtype(tokens.dtype, np.integer):
        raise ValueError(f"token ids must be integers, got {tokens.dtype}")
    if tokens.size and int(tokens.min()) < 0:
        raise ValueError("token ids must be non-negative")
    wide = tokens.size and int(tokens.max()) > 0xFFFF
    arr = tokens.astype(np.uint32 if wide else np.uint16)
    header = _MAGIC + np.uint32(arr.itemsize).tobytes() + np.uint64(
        arr.size
    ).tobytes()
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(header)
        f.write(arr.tobytes())
    os.replace(tmp, path)  # atomic publish


def _load_lib():
    from .native import _DATALOADER_SO_PATH, build

    if not build():
        raise RuntimeError(
            "libaccl_dataloader.so unavailable (no C++ toolchain?); "
            "run `make -C native`"
        )
    lib = ctypes.CDLL(str(_DATALOADER_SO_PATH))
    c = ctypes
    lib.accl_dl_open.restype = c.c_int
    lib.accl_dl_open.argtypes = [
        c.c_char_p, c.c_uint64, c.c_uint64, c.c_uint64, c.c_uint64,
        c.c_uint64, c.c_uint64, c.c_uint64, c.POINTER(c.c_void_p),
    ]
    lib.accl_dl_next.restype = c.c_int
    lib.accl_dl_next.argtypes = [
        c.c_void_p, c.POINTER(c.c_uint32), c.POINTER(c.c_uint64),
    ]
    lib.accl_dl_seek.restype = c.c_int
    lib.accl_dl_seek.argtypes = [c.c_void_p, c.c_uint64]
    lib.accl_dl_token_count.restype = c.c_int
    lib.accl_dl_token_count.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
    lib.accl_dl_close.restype = c.c_int
    lib.accl_dl_close.argtypes = [c.c_void_p]
    return lib


_lib = None


def _check(rc: int, what: str) -> None:
    if rc != 0:
        raise RuntimeError(f"{what}: {_ERRORS.get(rc, f'error {rc}')}")


class TokenLoader:
    """Prefetching reader of an ``ACCLTOK1`` token file.

    Each :meth:`next` returns ``(tokens, targets)`` int32 arrays of shape
    ``(batch, seq)`` — targets are the one-position shift of the same
    window (the LM objective this repo's trainers use) — plus the step
    index the window was drawn for.
    """

    def __init__(
        self,
        path: str,
        batch: int,
        seq: int,
        *,
        shard: int = 0,
        num_shards: int = 1,
        seed: int = 0,
        start_step: int = 0,
        prefetch_depth: int = 2,
    ):
        global _lib
        if _lib is None:
            _lib = _load_lib()
        self._lib = _lib
        self.batch, self.seq = int(batch), int(seq)
        handle = ctypes.c_void_p()
        rc = self._lib.accl_dl_open(
            str(path).encode(), self.batch, self.seq, shard, num_shards,
            seed, start_step, prefetch_depth, ctypes.byref(handle),
        )
        _check(rc, f"open {path}")
        self._handle = handle
        self._buf = np.empty(self.batch * (self.seq + 1), np.uint32)

    @property
    def token_count(self) -> int:
        out = ctypes.c_uint64()
        _check(
            self._lib.accl_dl_token_count(self._handle, ctypes.byref(out)),
            "token_count",
        )
        return int(out.value)

    def next(self) -> Tuple[np.ndarray, np.ndarray, int]:
        step = ctypes.c_uint64()
        rc = self._lib.accl_dl_next(
            self._handle,
            self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            ctypes.byref(step),
        )
        _check(rc, "next")
        win = self._buf.reshape(self.batch, self.seq + 1).astype(np.int32)
        return win[:, :-1].copy(), win[:, 1:].copy(), int(step.value)

    def seek(self, step: int) -> None:
        """Reposition at ``step`` (checkpoint resume): prefetched batches
        are dropped and production restarts there."""
        _check(self._lib.accl_dl_seek(self._handle, int(step)), "seek")

    def close(self) -> None:
        if self._handle is not None:
            self._lib.accl_dl_close(self._handle)
            self._handle = None

    def __enter__(self) -> "TokenLoader":
        return self

    def __exit__(self, *exc) -> Optional[bool]:
        self.close()
        return None

    def __del__(self):  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:
            pass
