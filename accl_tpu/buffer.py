"""Buffer abstraction: host/device arrays the engine moves data between.

Role model: ``driver/xrt/include/accl/buffer.hpp:32-141`` (``BaseBuffer`` with
``sync_to_device`` / ``sync_from_device`` / ``slice`` / ``address`` /
``is_host_only``) and its backend implementations (XRTBuffer / SimBuffer /
DummyBuffer).  TPU-natively, "device memory" is TPU HBM addressed through JAX
arrays; on the emulator tier the device side is a distinct host allocation so
that sync semantics stay observable (a test can detect a missing sync exactly
like the reference suite does).
"""

from __future__ import annotations

import functools
import threading
from typing import Optional

import numpy as np

from .constants import DataType, dtype_to_numpy, numpy_to_dtype


@functools.lru_cache(maxsize=512)
def _zeros_program(shape: tuple, npdt, device):
    """Jitted on-device zeros initializer, cached per (shape, dtype, device)
    so repeated buffer creation reuses the compiled program.  Shared with
    the XLA engine's dummy-operand shards."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    return jax.jit(
        lambda: jnp.zeros(shape, npdt),
        out_shardings=SingleDeviceSharding(device),
    )


def dev_zeros(shape: tuple, npdt, device):
    """A zeros array committed to ``device`` without touching the host."""
    return _zeros_program(tuple(shape), np.dtype(npdt), device)()


def make_buffer(device, count: int, dtype, host_only: bool = False,
                data=None):
    """Backend-appropriate buffer for a device-tier engine: an HBM-resident
    :class:`DeviceBuffer` on ``device``, or an :class:`EmuBuffer` when
    host-only (or no device is available).  ``data`` seeds the buffer —
    the host side ALIASES it and the device side is synced on return."""
    if host_only or device is None:
        if data is not None:
            buf = EmuBuffer.from_array(data, host_only=host_only)
            buf.sync_to_device()
            return buf
        return EmuBuffer(count, dtype, host_only=host_only)
    if data is not None:
        import jax

        arr = jax.device_put(data, device)
        return DeviceBuffer(count, dtype, device, array=arr, host=data)
    return DeviceBuffer(count, dtype, device)


# Slicing and scatter-writeback run as cached jitted programs, not eager
# ops: eager indexing dispatches its index scalars host->device, which
# would violate the zero-host-copy contract (and trip transfer guards).
@functools.lru_cache(maxsize=2048)
def _slice_program(start: int, stop: int):
    import jax

    return jax.jit(lambda a: a[start:stop])


@functools.lru_cache(maxsize=2048)
def _writeback_program(start: int, n: int):
    import jax

    return jax.jit(lambda base, a: base.at[start : start + n].set(a[:n]))


class BaseBuffer:
    """A typed 1-D region with a host view and a device residence."""

    def __init__(self, count: int, dtype: DataType):
        self._count = int(count)
        self._dtype = DataType(dtype)

    # -- introspection ------------------------------------------------------
    @property
    def count(self) -> int:
        return self._count

    @property
    def dtype(self) -> DataType:
        return self._dtype

    @property
    def nbytes(self) -> int:
        return self._count * dtype_to_numpy(self._dtype).itemsize

    @property
    def is_dummy(self) -> bool:
        return False

    @property
    def is_host_only(self) -> bool:
        return False

    # -- data movement ------------------------------------------------------
    def sync_to_device(self) -> None:
        raise NotImplementedError

    def sync_from_device(self) -> None:
        raise NotImplementedError

    def free_buffer(self) -> None:
        pass

    # -- views --------------------------------------------------------------
    def slice(self, start: int, stop: int) -> "BaseBuffer":
        raise NotImplementedError

    def host_view(self) -> np.ndarray:
        """Host-side numpy view (mutating it mutates host memory)."""
        raise NotImplementedError

    def device_view(self) -> np.ndarray:
        """Engine-side view of device memory (emulator tiers only)."""
        raise NotImplementedError


class EmuBuffer(BaseBuffer):
    """Emulator-tier buffer: host and 'device' are separate host allocations.

    The engine dataplane only ever touches ``device_view()``; user code writes
    ``host_view()`` (or the ``data`` property) and must ``sync_to_device`` —
    exactly the contract the reference tests rely on.  Slices alias the parent
    storage on both sides.
    """

    def __init__(
        self,
        count: int,
        dtype: DataType,
        host: Optional[np.ndarray] = None,
        dev: Optional[np.ndarray] = None,
        host_only: bool = False,
    ):
        super().__init__(count, dtype)
        npdt = dtype_to_numpy(dtype)
        self._host = host if host is not None else np.zeros(count, npdt)
        if host_only:
            self._dev = self._host
        else:
            self._dev = dev if dev is not None else np.zeros(count, npdt)
        self._host_only = host_only

    @classmethod
    def from_array(cls, arr: np.ndarray, host_only: bool = False) -> "EmuBuffer":
        arr = np.ascontiguousarray(arr).reshape(-1)
        return cls(arr.size, numpy_to_dtype(arr.dtype), host=arr, host_only=host_only)

    @property
    def is_host_only(self) -> bool:
        return self._host_only

    @property
    def data(self) -> np.ndarray:
        return self._host

    def sync_to_device(self) -> None:
        if not self._host_only:
            np.copyto(self._dev, self._host)

    def sync_from_device(self) -> None:
        if not self._host_only:
            np.copyto(self._host, self._dev)

    def slice(self, start: int, stop: int) -> "EmuBuffer":
        if not (0 <= start <= stop <= self._count):
            raise IndexError(f"slice [{start}:{stop}) out of range 0..{self._count}")
        return EmuBuffer(
            stop - start,
            self._dtype,
            host=self._host[start:stop],
            dev=self._dev[start:stop],
            host_only=self._host_only,
        )

    def host_view(self) -> np.ndarray:
        return self._host

    def device_view(self) -> np.ndarray:
        return self._dev


class DeviceBuffer(BaseBuffer):
    """HBM-resident buffer: the device side is a committed ``jax.Array``.

    Role model: ``XRTBuffer`` (``driver/xrt/include/accl/xrtbuffer.hpp``) —
    a device BO with a host shadow and ``sync_to/from_device``.  On TPU the
    BO is a single-device ``jax.Array`` pinned to one chip's HBM; the
    collective engine assembles per-rank device arrays into one sharded
    global array with ``jax.make_array_from_single_device_arrays`` (zero
    copy) and adopts result shards back — the host never touches the data
    path, matching the reference's "no host in the loop" contract
    (``README.md:7-14``, hot path ``accl.cpp:780-826``).

    jax.Arrays are immutable, so "writes" replace the underlying array
    (``store``) — a device-side computation, never a host transfer.  Slices
    carry a parent link and write back with ``.at[...].set`` on store,
    preserving the reference's aliasing semantics.
    """

    def __init__(
        self,
        count: int,
        dtype: DataType,
        device,
        array=None,
        parent: Optional["DeviceBuffer"] = None,
        offset: int = 0,
        host: Optional[np.ndarray] = None,
    ):
        super().__init__(count, dtype)
        self.device = device
        self._parent = parent
        self._offset = int(offset)
        # lazy result adoption (single-interaction dispatch): an engine
        # may park the device program that places a result into this
        # buffer (writeback/trim — one program dispatch each) as a pending
        # thunk; any data access resolves it first, so fire-and-forget
        # callers never pay the result leg and readers never see stale
        # bytes.  Lives on the ROOT buffer (stores write through parents);
        # the REENTRANT lock makes park/resolve atomic AND ordered: a
        # concurrent resolver that loses the race blocks until the
        # winner's thunk has fully landed (so no reader can observe the
        # pre-store _dev), while the thunk's own store()/device_array()
        # re-entering resolve_pending on the same thread cannot deadlock.
        self._pending: Optional[object] = None
        self._plock = threading.RLock()
        # monotone defer counter: every parked thunk bumps it, so a
        # writer that wants to COLLAPSE successive whole-result stores
        # (the command ring's window adoption) can prove no other
        # deferred write slipped in between (buffer.py stays policy-
        # free: chaining remains the default — partial writes must
        # layer in issue order)
        self._defer_seq = 0
        npdt = dtype_to_numpy(dtype)
        self._host = host if host is not None else np.zeros(count, npdt)
        if parent is not None:
            self._dev = None  # storage lives in the root buffer
        elif array is not None:
            self._dev = array
        else:
            # allocate by committing the freshly-zeroed host shadow: one
            # H2D put, NO compile.  dev_zeros would jit a zeros program
            # per distinct count — a workload sweeping sizes (the soak)
            # pays a fresh XLA compile per allocation, which dominated
            # the round-4 dist soak.  Allocation is not the data path:
            # the zero-host-copy contract (transfer-guard-tested) covers
            # the collective between creation and sync, not creation.
            import jax

            self._dev = jax.device_put(self._host, device)

    # -- introspection ------------------------------------------------------
    @property
    def data(self) -> np.ndarray:
        return self._host

    def _root(self) -> "DeviceBuffer":
        buf = self
        while buf._parent is not None:
            buf = buf._parent
        return buf

    def _root_offset(self) -> int:
        buf, off = self, 0
        while buf._parent is not None:
            off += buf._offset
            buf = buf._parent
        return off

    def defer_store(self, thunk) -> None:
        """Park a result-placement thunk (engine side).  Chains with any
        earlier pending store so partial writes land in issue order when
        the buffer is finally resolved."""
        root = self._root()
        with root._plock:
            root._defer_seq += 1
            prev = root._pending
            if prev is None:
                root._pending = thunk
            else:
                def chained(prev=prev, thunk=thunk):
                    prev()
                    thunk()

                root._pending = chained

    def resolve_pending(self) -> None:
        """Run any parked result placement (idempotent; re-entrancy safe:
        the thunk's own ``store()`` sees the slot already cleared).  The
        thunk runs INSIDE the reentrant lock so a concurrent resolver
        that loses the swap cannot proceed to read ``_dev`` until the
        winner's store has landed."""
        root = self._root()
        with root._plock:
            thunk, root._pending = root._pending, None
            if thunk is not None:
                thunk()

    def device_array(self):
        """The committed ``jax.Array`` (sliced view for child buffers —
        a device-side computation, not a transfer)."""
        self.resolve_pending()
        root = self._root()
        if root is self:
            return self._dev
        off = self._root_offset()
        return _slice_program(off, off + self._count)(root._dev)

    def store(self, array, count: Optional[int] = None) -> bool:
        """Engine-side result placement: replace the first ``count`` device
        elements with ``array`` (a jax.Array already on this device).
        Whole-buffer stores on root buffers are free (pointer swap); partial
        or sliced stores write back with ``.at[...].set``.  Returns True
        when a writeback program was dispatched (a device interaction),
        False for the free pointer swap — the engines' interaction
        counters key off this."""
        self.resolve_pending()
        n = self._count if count is None else int(count)
        if getattr(array, "ndim", 1) != 1 or array.shape[0] < n:
            raise ValueError(
                f"store of shape {getattr(array, 'shape', '?')} into {n} "
                f"elements of a {self._count}-element buffer"
            )
        if array.dtype != dtype_to_numpy(self._dtype):
            raise TypeError(
                f"store dtype {array.dtype} != buffer dtype {self._dtype.name}"
            )
        root = self._root()
        off = self._root_offset()
        if root is self and n == self._count and array.shape[0] == n:
            root._dev = array
            return False
        root._dev = _writeback_program(off, n)(root._dev, array)
        return True

    # -- data movement ------------------------------------------------------
    def sync_to_device(self) -> None:
        import jax

        arr = jax.device_put(self._host, self.device)
        self.store(arr)

    def sync_from_device(self) -> None:
        np.copyto(self._host, np.asarray(self.device_array()))

    def free_buffer(self) -> None:
        root = self._root()
        if root is self:
            # only the ROOT free drops parked results (they are moot once
            # the storage dies); freeing a child slice must not discard a
            # deferred store destined for the root or a sibling slice
            with root._plock:
                root._pending = None
            if self._dev is not None:
                self._dev.delete()
                self._dev = None

    # -- views --------------------------------------------------------------
    def slice(self, start: int, stop: int) -> "DeviceBuffer":
        if not (0 <= start <= stop <= self._count):
            raise IndexError(f"slice [{start}:{stop}) out of range 0..{self._count}")
        return DeviceBuffer(
            stop - start,
            self._dtype,
            self.device,
            parent=self,
            offset=start,
            host=self._host[start:stop],
        )

    def host_view(self) -> np.ndarray:
        return self._host

    def device_view(self) -> np.ndarray:
        """Host copy of device memory — the generic fallback path for mixed
        emulator/device operands.  The zero-copy engine path never calls
        this (it uses :meth:`device_array`)."""
        return np.asarray(self.device_array())


class DummyBuffer(BaseBuffer):
    """Placeholder operand for ranks that contribute no data to a collective
    (ref ``driver/xrt/include/accl/dummybuffer.hpp``)."""

    def __init__(self, count: int = 0, dtype: DataType = DataType.FLOAT32):
        super().__init__(count, dtype)

    @property
    def is_dummy(self) -> bool:
        return True

    def sync_to_device(self) -> None:
        pass

    def sync_from_device(self) -> None:
        pass

    def slice(self, start: int, stop: int) -> "DummyBuffer":
        return DummyBuffer(stop - start, self._dtype)

    def host_view(self) -> np.ndarray:
        raise RuntimeError("dummy buffer has no storage")

    def device_view(self) -> np.ndarray:
        raise RuntimeError("dummy buffer has no storage")
