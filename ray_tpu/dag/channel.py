"""Mutable shared-memory channels — the transport under compiled graphs.

Reference: ``src/ray/core_worker/experimental_mutable_object_manager.h:48``
and ``python/ray/experimental/channel/shared_memory_channel.py`` — mutable
(versioned) shm objects with writer/reader acquire semantics and timeouts,
reused across DAG executions so the per-execution cost is a memcpy + a
version bump instead of an object-store allocation and RPC.

TPU-native redesign: one POSIX shm segment per channel holding a small
ring of slots (seqlock-style versioning, per-reader consume cursors in the
header). Writers block when the ring is full (backpressure = ring depth);
readers block on the slot version. All coordination is in shared memory —
zero RPCs on the steady-state path. Cross-host channels are intentionally
NOT built on this layer: on TPU the inter-host data path belongs to the
in-program ICI collectives (``parallel/``), not the actor channel layer.

Layout (little-endian):
    [u32 magic][u32 num_slots][u64 slot_size][u32 num_readers][u32 pad]
    [u64 reader_cursor] * num_readers        # next seq each reader wants
    slot * num_slots, each:
        [u64 version]    # seq+1 once the write of that seq is complete
        [u64 length]
        [payload bytes]

A value is framed with a 1-byte kind: 0=value, 1=error (pickled
exception), 2=close (teardown sentinel).
"""

from __future__ import annotations

import ctypes
import pickle
import struct
import time
from typing import List, Optional, Tuple

_MAGIC = 0x52544348  # "RTCH"
_HDR = struct.Struct("<IIQII")
_U64 = struct.Struct("<Q")
_SLOT_HDR = struct.Struct("<QQ")

KIND_VALUE = 0
KIND_ERROR = 1
KIND_CLOSE = 2


# ---------------------------------------------------------------------------
# POSIX named semaphores (ctypes): the cross-process wakeup primitive.
# Sleep-polling costs ~0.5-2ms per handoff on a loaded host; sem_post/
# sem_timedwait make channel handoffs kernel-scheduled. glibc puts named
# semaphores in /dev/shm as ``sem.<name>`` — same namespace discipline as
# the channel segments, so orphan sweeps can reap both.

_libc = ctypes.CDLL(None, use_errno=True)
_SEM_FAILED = ctypes.c_void_p(-1).value
_O_CREAT = 0o100


class _timespec(ctypes.Structure):
    _fields_ = [("tv_sec", ctypes.c_long), ("tv_nsec", ctypes.c_long)]


try:
    _libc.sem_open.restype = ctypes.c_void_p
    _libc.sem_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_uint, ctypes.c_uint]
    _libc.sem_post.argtypes = [ctypes.c_void_p]
    _libc.sem_timedwait.argtypes = [ctypes.c_void_p, ctypes.POINTER(_timespec)]
    _libc.sem_trywait.argtypes = [ctypes.c_void_p]
    _libc.sem_close.argtypes = [ctypes.c_void_p]
    _HAVE_SEM = True
except AttributeError:  # non-glibc platform: fall back to pure polling
    _HAVE_SEM = False


class _Sem:
    """A named semaphore used as a wakeup HINT — shm versions/cursors stay
    authoritative, so lost or extra posts are harmless."""

    def __init__(self, name: str):
        self.name = name
        self._h = None
        if not _HAVE_SEM:
            return
        h = _libc.sem_open(("/" + name).encode(), _O_CREAT, 0o600, 0)
        if h != _SEM_FAILED:
            self._h = h

    def post(self) -> None:
        if self._h is not None:
            _libc.sem_post(self._h)

    def wait(self, timeout_s: float) -> None:
        """Block up to ``timeout_s`` for a post (spurious returns fine)."""
        if self._h is None:
            time.sleep(min(timeout_s, 0.0005))
            return
        now = time.time() + timeout_s
        ts = _timespec(int(now), int((now % 1.0) * 1e9))
        _libc.sem_timedwait(self._h, ctypes.byref(ts))

    def drain(self) -> None:
        if self._h is None:
            return
        while _libc.sem_trywait(self._h) == 0:
            pass

    def close(self) -> None:
        if self._h is not None:
            _libc.sem_close(self._h)
            self._h = None

    @staticmethod
    def unlink(name: str) -> None:
        if _HAVE_SEM:
            _libc.sem_unlink(("/" + name).encode())


class ChannelTimeoutError(TimeoutError):
    """A channel read/write did not complete within the timeout
    (reference ``RayChannelTimeoutError``)."""


class ChannelClosedError(RuntimeError):
    """The peer tore the compiled graph down."""


# one tracker-workaround implementation, shared with the object store
from ray_tpu.core.object_store import _attach, _create  # noqa: E402


def _check_deadline(deadline: Optional[float]) -> None:
    if deadline is not None and time.monotonic() > deadline:
        raise ChannelTimeoutError("channel operation timed out")


class ShmChannel:
    """One ring-buffer channel. The creator (driver) owns the segment
    lifetime; actors attach by name."""

    def __init__(
        self,
        name: str,
        *,
        create: bool = False,
        slot_size: int = 1 << 20,
        num_slots: int = 8,
        num_readers: int = 1,
    ):
        self.name = name
        if create:
            total = _HDR.size + 8 * num_readers + num_slots * (_SLOT_HDR.size + slot_size)
            self._seg = _create(name, total)
            self._buf = memoryview(self._seg.buf)
            _HDR.pack_into(self._buf, 0, _MAGIC, num_slots, slot_size, num_readers, 0)
            for i in range(num_readers):
                _U64.pack_into(self._buf, _HDR.size + 8 * i, 0)
            for s in range(num_slots):
                _SLOT_HDR.pack_into(self._buf, self._slot_off_static(s, num_readers, slot_size), 0, 0)
        else:
            self._seg = _attach(name)
            self._buf = memoryview(self._seg.buf)
            magic, num_slots, slot_size, num_readers, _ = _HDR.unpack_from(self._buf, 0)
            if magic != _MAGIC:
                raise ValueError(f"{name} is not a channel segment")
        self.slot_size = slot_size
        self.num_slots = num_slots
        self.num_readers = num_readers
        self._cursor_base = _HDR.size
        self._slots_base = _HDR.size + 8 * num_readers
        # wakeup hints: one sem per reader (posted on write), one for the
        # writer (posted on advance)
        self._reader_sems: List[_Sem] = [
            _Sem(f"{name}-r{i}") for i in range(num_readers)
        ]
        self._writer_sem = _Sem(f"{name}-w")

    @staticmethod
    def _slot_off_static(slot: int, num_readers: int, slot_size: int) -> int:
        return _HDR.size + 8 * num_readers + slot * (_SLOT_HDR.size + slot_size)

    def _slot_off(self, slot: int) -> int:
        return self._slots_base + slot * (_SLOT_HDR.size + self.slot_size)

    # -- writer ----------------------------------------------------------
    def _min_cursor(self) -> int:
        lo = None
        for i in range(self.num_readers):
            (c,) = _U64.unpack_from(self._buf, self._cursor_base + 8 * i)
            lo = c if lo is None else min(lo, c)
        return lo or 0

    def write(self, seq: int, kind: int, payload: bytes, timeout: Optional[float] = None) -> None:
        """Publish ``payload`` as execution ``seq``. Blocks while the slot
        still holds an unconsumed previous value (ring backpressure)."""
        if len(payload) + 1 > self.slot_size:
            raise ValueError(
                f"value of {len(payload)} bytes exceeds channel slot size "
                f"{self.slot_size}; recompile with a larger _buffer_size_bytes"
            )
        deadline = None if timeout is None else time.monotonic() + timeout
        # slot is free once every reader has consumed its previous tenant
        # (seq - num_slots); i.e. all cursors are past it
        while self._min_cursor() < seq - self.num_slots + 1:
            _check_deadline(deadline)
            self._writer_sem.wait(0.05)
        off = self._slot_off(seq % self.num_slots)
        body_off = off + _SLOT_HDR.size
        self._buf[body_off] = kind
        self._buf[body_off + 1 : body_off + 1 + len(payload)] = payload
        # Length then version: the version word is what readers poll.
        # ORDERING CAVEAT: these are plain memoryview stores with no
        # explicit release fence — correctness relies on x86-TSO (stores
        # retire in program order). On a weakly-ordered host (ARM) a
        # reader could observe version==seq+1 before the payload stores
        # and deserialize torn data; porting there needs an atomic
        # release write (or a payload checksum in the slot header).
        # TPU-host fleets are x86, so this build documents rather than
        # pays the fence cost.
        _SLOT_HDR.pack_into(self._buf, off, 0, len(payload) + 1)
        _U64.pack_into(self._buf, off, seq + 1)
        for sem in self._reader_sems:
            sem.post()

    def write_value(self, seq: int, value, timeout: Optional[float] = None) -> None:
        from ray_tpu.core import serialization

        self.write(seq, KIND_VALUE, serialization.serialize(value).to_bytes(), timeout)

    def write_error(self, seq: int, error: BaseException, timeout: Optional[float] = None) -> None:
        self.write(seq, KIND_ERROR, pickle.dumps(error), timeout)

    def write_close(self, seq: int, timeout: Optional[float] = None) -> None:
        self.write(seq, KIND_CLOSE, b"", timeout)

    # -- reader ----------------------------------------------------------
    def read(self, reader: int, seq: int, timeout: Optional[float] = None) -> Tuple[int, memoryview]:
        """Return (kind, payload_view) for ``seq``. The view aliases the
        slot — call :meth:`advance` only after the value is consumed (the
        slot is never overwritten before every cursor passes it)."""
        off = self._slot_off(seq % self.num_slots)
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            (version,) = _U64.unpack_from(self._buf, off)
            if version == seq + 1:
                break
            _check_deadline(deadline)
            self._reader_sems[reader].wait(0.05)
        (_, length) = _SLOT_HDR.unpack_from(self._buf, off)
        body_off = off + _SLOT_HDR.size
        kind = self._buf[body_off]
        return kind, self._buf[body_off + 1 : body_off + length]

    def read_value(self, reader: int, seq: int, timeout: Optional[float] = None):
        """Read + decode ``seq``; raises on error/close markers. The
        decoded value may alias slot memory — consume before advance."""
        from ray_tpu.core import serialization

        kind, view = self.read(reader, seq, timeout)
        if kind == KIND_CLOSE:
            raise ChannelClosedError("channel closed")
        if kind == KIND_ERROR:
            raise pickle.loads(view)
        return serialization.deserialize_bytes(view)

    def advance(self, reader: int, seq: int) -> None:
        """Mark ``seq`` consumed by ``reader`` — frees the slot for reuse
        once all readers pass it."""
        _U64.pack_into(self._buf, self._cursor_base + 8 * reader, seq + 1)
        self._writer_sem.post()

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        for sem in self._reader_sems:
            sem.close()
        self._writer_sem.close()
        try:
            self._buf.release()
        except Exception:
            pass
        try:
            self._seg.close()
        except Exception:
            pass

    def unlink(self) -> None:
        for i in range(self.num_readers):
            _Sem.unlink(f"{self.name}-r{i}")
        _Sem.unlink(f"{self.name}-w")
        try:
            self._seg.unlink()
        except Exception:
            pass
