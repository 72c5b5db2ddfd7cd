"""Shared-memory object store (plasma equivalent).

Reference: ``src/ray/object_manager/plasma/`` — an immutable shm store
owned by the node daemon (``store.h:55``), LRU eviction
(``eviction_policy.h``), disk fallback/spilling
(``raylet/local_object_manager.h:110``), client attach by FD-passing.

TPU-native redesign: each object is one POSIX shm segment named after its
ObjectID, created and written *by the producing worker* (zero-copy create;
no FD passing needed — the name is the capability) then *adopted* by the
node daemon, which owns lifetime: capacity accounting, LRU spill-to-disk,
restore, delete. POSIX unlink semantics make eviction safe: readers that
already attached keep valid mappings; only the name disappears.

Three pieces:
  * ``ShmStore``     — daemon-side authority (runs inside the node daemon).
  * ``StoreClient``  — worker-side: create/write and attach/read segments.
  * ``MemoryStore``  — per-worker in-process store for small/inline objects
                       (reference ``CoreWorkerMemoryStore``).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from multiprocessing import shared_memory, resource_tracker
from typing import Dict, List, Optional, Tuple

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.core.ids import ObjectID

logger = logging.getLogger(__name__)


def segment_name(object_id: ObjectID) -> str:
    return "rt-" + object_id.hex()


def _attach(name: str) -> shared_memory.SharedMemory:
    """Attach to an existing segment without the resource tracker claiming
    it (py3.12's tracker would unlink segments it never created when this
    process exits)."""
    try:
        return shared_memory.SharedMemory(name=name, create=False, track=False)  # py>=3.13
    except TypeError:
        seg = shared_memory.SharedMemory(name=name, create=False)
        try:
            resource_tracker.unregister(seg._name, "shared_memory")  # noqa: SLF001
        except Exception:
            pass
        return seg


# MADV_POPULATE_WRITE (Linux 5.14+; mmap module may predate the constant):
# pre-fault a fresh segment's pages in ONE syscall before the bulk copy.
# Per-page fault-on-write costs ~10× the copy itself on virtualized hosts
# (measured 0.6 vs 3.4+ GB/s on the bench box for 64 MiB puts).
_MADV_POPULATE_WRITE = getattr(__import__("mmap"), "MADV_POPULATE_WRITE", 23)


def _prefault(seg: shared_memory.SharedMemory) -> None:
    try:
        seg._mmap.madvise(_MADV_POPULATE_WRITE)  # noqa: SLF001
    except Exception:
        pass  # old kernel / unsupported — the copy still works, just slower


def _create(name: str, size: int) -> shared_memory.SharedMemory:
    try:
        seg = shared_memory.SharedMemory(name=name, create=True, size=size, track=False)
    except TypeError:
        seg = shared_memory.SharedMemory(name=name, create=True, size=size)
        try:
            resource_tracker.unregister(seg._name, "shared_memory")  # noqa: SLF001
        except Exception:
            pass
    if size >= (1 << 20):  # syscall not worth it for small segments
        _prefault(seg)
    return seg


class ObjectStoreFull(Exception):
    pass


class SegmentWindow:
    """A memoryview over (a range of) a store segment plus the attachment
    keeping it valid — the zero-copy unit of the data plane. Senders get
    read windows (``read_window``) and write them straight to the socket;
    receivers get the writable window into an UNSEALED entry
    (``receive_window``) and read chunk payloads directly into it.

    ``close()`` releases the view then the mapping; it tolerates live
    sub-views (a late in-flight receive still holding a slice) by leaving
    the mapping open — the process-lifetime leak of one mapping beats a
    BufferError masking a transfer result."""

    __slots__ = ("_seg", "view")

    def __init__(self, seg: shared_memory.SharedMemory, view: memoryview):
        self._seg = seg
        self.view = view

    def __len__(self) -> int:
        return len(self.view)

    def close(self) -> None:
        try:
            self.view.release()
            self._seg.close()
        except BufferError:
            logger.debug("segment window closed with live sub-views; mapping kept")
        except Exception:
            pass


@dataclass
class _Entry:
    size: int
    sealed: bool = True
    pinned: int = 0
    spilled_path: Optional[str] = None
    in_shm: bool = True
    created_at: float = field(default_factory=time.monotonic)
    # crc32 content digest, computed lazily on first object_info serve
    # (or recorded at seal time by the pull manager) — the end-to-end
    # integrity token carried with transfer metadata
    digest: Optional[int] = None
    # False when a streaming receive ATTACHED to a pre-existing inode
    # (simulated multi-node: the "remote" source shares this /dev/shm, so
    # the segment already exists with identical immutable content) — an
    # aborted receive must then NOT unlink it out from under the source
    inode_owner: bool = True
    # True once ANY reader resolved this object through the daemon
    # (get_object_meta / transfer). Gates segment recycling: an inode no
    # process ever attached can be renamed+rewritten by its creator with
    # warm pages; one that was read may back live zero-copy views.
    read_by_any: bool = False
    # True for copies CREATED on this node (worker put/task output via
    # adopt); False for transfer-received replicas (create_with_data).
    # The drain flush replicates only primaries — secondaries already
    # live elsewhere.
    primary: bool = True


class ShmStore:
    """Daemon-side store authority. Thread-safe; no asyncio dependency."""

    def __init__(self, capacity_bytes: Optional[int] = None, spill_dir: Optional[str] = None):
        self.capacity = capacity_bytes or GLOBAL_CONFIG.object_store_memory_bytes
        self.spill_dir = spill_dir or GLOBAL_CONFIG.object_spilling_dir or "/tmp/ray_tpu_spill"
        self._entries: "OrderedDict[ObjectID, _Entry]" = OrderedDict()  # LRU order
        self._used = 0
        self._lock = threading.RLock()
        self.num_spilled = 0
        self.num_restored = 0
        self.num_evicted = 0
        # worker reuse pools hold real tmpfs pages the entry table no
        # longer tracks; admission control reads their size from the
        # filesystem (the one source of truth that survives worker
        # death/shutdown), cached briefly
        self._pool_debt = 0
        self._pool_debt_ts = 0.0
        # daemon-side receive-segment reuse pool (KV-migration satellite):
        # transfer-received segments deleted with ``recycle_receive`` (and
        # aborted receives this store created) keep their warm inode here
        # — pool file name -> byte size, oldest first — and the next
        # allocate_receive of a fitting size RENAMES one back instead of
        # paying segment create + zero-fill (no MADV_POPULATE on this
        # kernel; warm pages are the substitute)
        self._recv_pool: "OrderedDict[str, int]" = OrderedDict()
        self._recv_pool_bytes = 0
        self._recv_pool_seq = 0
        self.num_recv_pool_hits = 0
        self.num_recv_pool_puts = 0

    # -- accounting ------------------------------------------------------
    @property
    def used_bytes(self) -> int:
        return self._used

    def contains(self, object_id: ObjectID) -> bool:
        with self._lock:
            e = self._entries.get(object_id)
            # unsealed (mid-receive) entries are invisible to readers
            return e is not None and e.sealed

    def list_entries(self) -> List[Dict[str, object]]:
        """State-API view of every tracked object (``ray list objects``)."""
        with self._lock:
            return [
                {
                    "object_id": oid.hex(),
                    "size": e.size,
                    "in_shm": e.in_shm,
                    "pinned": e.pinned,
                    "spilled": e.spilled_path is not None,
                    "primary": e.primary,
                    "sealed": e.sealed,
                }
                for oid, e in self._entries.items()
            ]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "used_bytes": self._used,
                "capacity_bytes": self.capacity,
                "num_objects": len(self._entries),
                "num_spilled": self.num_spilled,
                "num_restored": self.num_restored,
                "num_evicted": self.num_evicted,
                "recv_pool_bytes": self._recv_pool_bytes,
                "recv_pool_segments": len(self._recv_pool),
                "recv_pool_hits": self.num_recv_pool_hits,
                "recv_pool_puts": self.num_recv_pool_puts,
            }

    # -- receive-segment reuse pool --------------------------------------
    def _pool_receive_segment_locked(self, object_id: ObjectID, size: int) -> bool:
        """Move a private receive segment's inode into the reuse pool
        instead of unlinking it. Caller must hold the lock and must have
        already dropped the entry. Returns False (caller unlinks) when
        pooling is off, full, or the rename fails."""
        limit = GLOBAL_CONFIG.receive_segment_pool_bytes
        if limit <= 0 or size <= 0:
            return False
        self._recv_pool_seq += 1
        pool_name = f"rt-rpool-{os.getpid()}-{self._recv_pool_seq}"
        try:
            os.rename(
                os.path.join(_SHM_DIR, segment_name(object_id)),
                os.path.join(_SHM_DIR, pool_name),
            )
        except OSError:
            return False
        try:
            # physical size, not the entry's logical size: a segment that
            # was itself a pool hit can be larger than the object it held
            size = os.path.getsize(os.path.join(_SHM_DIR, pool_name))
        except OSError:
            pass
        self._recv_pool[pool_name] = size
        self._recv_pool_bytes += size
        self.num_recv_pool_puts += 1
        while self._recv_pool_bytes > limit and self._recv_pool:
            victim, vsize = self._recv_pool.popitem(last=False)
            self._recv_pool_bytes -= vsize
            try:
                os.unlink(os.path.join(_SHM_DIR, victim))
            except OSError:
                pass
        return True

    def _take_recv_pooled_locked(self, object_id: ObjectID, size: int) -> bool:
        """Claim a pooled receive segment that fits ``size`` without
        gross waste (same tight-fit rule as the worker pool: slack is
        invisible to accounting, bound it) and rename it to the object's
        segment name. Never overwrites an existing inode — on simulated
        shared-/dev/shm clusters the target name may BE the source's
        live copy, and a rename-over would destroy it (the ``forget()``
        hazard class); the plain create path handles that case."""
        target = os.path.join(_SHM_DIR, segment_name(object_id))
        if os.path.exists(target):
            return False
        for name, psize in self._recv_pool.items():
            if psize >= size and psize <= size + max(size >> 3, 1 << 20):
                del self._recv_pool[name]
                self._recv_pool_bytes -= psize
                try:
                    os.rename(os.path.join(_SHM_DIR, name), target)
                except OSError:
                    try:
                        os.unlink(os.path.join(_SHM_DIR, name))
                    except OSError:
                        pass
                    return False
                self.num_recv_pool_hits += 1
                return True
        return False

    # -- create/adopt ----------------------------------------------------
    def adopt(self, object_id: ObjectID, size: int) -> None:
        """Take ownership of a worker-created, already-written segment."""
        with self._lock:
            if object_id in self._entries:
                return
            self._make_room(size)
            self._entries[object_id] = _Entry(size=size)
            self._used += size

    def create_with_data(self, object_id: ObjectID, data: memoryview) -> None:
        """Daemon-side create (object transfer receive path)."""
        size = len(data)
        with self._lock:
            if object_id in self._entries:
                return
            self._make_room(size)
            try:
                seg = _create(segment_name(object_id), size)
                seg.buf[:size] = data
                seg.close()
            except FileExistsError:
                # Simulated multi-node: the "remote" node shares this
                # machine's /dev/shm, so the segment already exists with
                # identical content (objects are immutable) — adopt as-is.
                pass
            self._entries[object_id] = _Entry(size=size, primary=False)
            self._used += size

    # -- streaming receive (pull manager) --------------------------------
    # The destination segment is allocated UP FRONT and chunks are
    # written directly into it (no whole-object heap buffer). The entry
    # exists unsealed for the duration — invisible to every reader path
    # (contains/ensure_local/read_*) — and is either sealed atomically
    # once the content digest verifies, or aborted without a trace.

    def begin_receive(self, object_id: ObjectID) -> bool:
        """Reserve an unsealed entry for an incoming transfer. Returns
        False if the object is already present (sealed) — the pull is a
        no-op. A stale unsealed entry (aborted transfer that lost the
        race to clean up) is replaced."""
        with self._lock:
            e = self._entries.get(object_id)
            if e is not None:
                if e.sealed:
                    return False
                self._abort_receive_locked(object_id, e)
            return True

    def allocate_receive(self, object_id: ObjectID, size: int) -> str:
        """Create the destination segment for a begin_receive'd transfer
        (separate from begin_receive so admission control can run between
        the reservation and the allocation). Returns the segment name;
        the caller attaches and writes chunks into it."""
        with self._lock:
            self._make_room(size)
            inode_owner = True
            if not self._take_recv_pooled_locked(object_id, size):
                try:
                    seg = _create(segment_name(object_id), size)
                    seg.close()
                except FileExistsError:
                    # simulated multi-node: the source shares this
                    # /dev/shm, the inode already holds the (immutable)
                    # content — write over it with identical bytes, but
                    # never unlink it on abort (the source still serves
                    # from it)
                    inode_owner = False
            self._entries[object_id] = _Entry(
                size=size, sealed=False, primary=False, inode_owner=inode_owner
            )
            self._used += size
            return segment_name(object_id)

    def seal_receive(self, object_id: ObjectID, digest: Optional[int] = None) -> None:
        """Atomically publish a fully-received, digest-verified object:
        only now do readers see it."""
        with self._lock:
            e = self._entries.get(object_id)
            if e is None:
                return
            e.sealed = True
            e.digest = digest

    def abort_receive(self, object_id: ObjectID) -> None:
        """Tear down a failed transfer: the uncommitted segment is
        dropped; readers never saw the entry."""
        with self._lock:
            e = self._entries.get(object_id)
            if e is None or e.sealed:
                return  # sealed entries are never aborted
            self._abort_receive_locked(object_id, e)

    def _abort_receive_locked(self, object_id: ObjectID, e: _Entry) -> None:
        self._entries.pop(object_id, None)
        self._used -= e.size
        if e.inode_owner:
            # no reader ever saw an unsealed entry, so the inode is
            # private: recycle it into the receive pool (a failed
            # transfer's retry is exactly the repeat case the pool is
            # for); unlink only when pooling declines it
            if self._pool_receive_segment_locked(object_id, e.size):
                return
            try:
                seg = _attach(segment_name(object_id))
                seg.unlink()
                seg.close()
            except FileNotFoundError:
                pass

    def receive_window(self, object_id: ObjectID) -> SegmentWindow:
        """The writable window into an UNSEALED entry (an in-flight
        transfer's destination segment): the pull manager reads verified
        chunk payloads straight into it — zero intermediate copies. Only
        the receiving transfer may hold this window; every reader path
        still denies the object until ``seal_receive``. Raises KeyError
        when no unsealed entry exists (never exposes sealed objects as
        writable)."""
        with self._lock:
            e = self._entries.get(object_id)
            if e is None or e.sealed:
                raise KeyError(
                    f"no unsealed receive entry for {object_id.hex()[:12]}"
                )
            size = e.size
        seg = _attach(segment_name(object_id))
        return SegmentWindow(seg, memoryview(seg.buf)[:size])

    def peek_digest(self, object_id: ObjectID) -> Optional[int]:
        """Cached digest only — never computes (cheap probe-path check)."""
        with self._lock:
            e = self._entries.get(object_id)
            return None if e is None else e.digest

    def digest_of(self, object_id: ObjectID) -> Optional[int]:
        """crc32 content digest, computed lazily and cached on the entry
        (the transfer-metadata integrity token). None if absent."""
        import zlib

        meta = self.ensure_local(object_id)
        if meta is None:
            return None
        with self._lock:
            e = self._entries.get(object_id)
            if e is None:
                return None
            if e.digest is not None:
                return e.digest
        name, size = meta
        try:
            seg = _attach(name)
        except FileNotFoundError:
            return None  # raced a spill/delete; caller retries via ensure_local
        try:
            digest = zlib.crc32(seg.buf[:size])
        finally:
            seg.close()
        with self._lock:
            e = self._entries.get(object_id)
            if e is not None:
                e.digest = digest
        return digest

    def _recycle_pool_debt(self) -> int:
        """Bytes held by worker segment-reuse pools (``rt-pool-*`` files):
        real tmpfs usage invisible to the entry table."""
        now = time.monotonic()
        if now - self._pool_debt_ts > 1.0:
            import glob

            debt = 0
            for path in glob.glob("/dev/shm/rt-pool-*"):
                try:
                    debt += os.path.getsize(path)
                except OSError:
                    pass
            self._pool_debt = debt
            self._pool_debt_ts = now
        return self._pool_debt

    def _make_room(self, size: int) -> None:
        if size > self.capacity:
            raise ObjectStoreFull(
                f"object of {size} bytes exceeds store capacity {self.capacity}"
            )
        threshold = int(self.capacity * GLOBAL_CONFIG.object_spilling_threshold)
        debt = self._recycle_pool_debt()
        # the receive pool holds real tmpfs pages too — drain it before
        # spilling live objects (pool entries are pure cache)
        while (
            self._used + debt + self._recv_pool_bytes + size > threshold
            and self._recv_pool
        ):
            victim, vsize = self._recv_pool.popitem(last=False)
            self._recv_pool_bytes -= vsize
            try:
                os.unlink(os.path.join(_SHM_DIR, victim))
            except OSError:
                pass
        debt += self._recv_pool_bytes
        while self._used + debt + size > threshold and self._spill_one():
            pass
        if self._used + debt + size > self.capacity:
            raise ObjectStoreFull(
                f"store full: used={self._used}, pool_debt={debt}, "
                f"requested={size}, capacity={self.capacity} and nothing spillable"
            )

    def _spill_one(self) -> bool:
        """Spill the least-recently-used unpinned in-shm object to disk."""
        victim = None
        for oid, e in self._entries.items():
            if e.in_shm and e.pinned == 0 and e.sealed:
                victim = (oid, e)
                break
        if victim is None:
            return False
        oid, e = victim
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, segment_name(oid))
        try:
            seg = _attach(segment_name(oid))
        except FileNotFoundError:
            # segment vanished (daemon restart); drop the entry
            self._drop(oid)
            return True
        try:
            with open(path, "wb") as f:
                f.write(seg.buf)
            seg.unlink()
        finally:
            seg.close()
        e.in_shm = False
        e.spilled_path = path
        self._used -= e.size
        self.num_spilled += 1
        logger.debug("spilled %s (%d bytes) to %s", oid.hex()[:12], e.size, path)
        return True

    def ensure_local(self, object_id: ObjectID) -> Optional[Tuple[str, int]]:
        """Return (segment_name, size) if present, restoring from spill if
        needed; None if unknown."""
        with self._lock:
            e = self._entries.get(object_id)
            if e is None or not e.sealed:
                # unsealed = a transfer in flight: readers must never see
                # a partially-written segment
                return None
            self._entries.move_to_end(object_id)  # LRU touch
            e.read_by_any = True
            if not e.in_shm:
                self._restore(object_id, e)
            return segment_name(object_id), e.size

    def _restore(self, object_id: ObjectID, e: _Entry) -> None:
        self._make_room(e.size)
        seg = _create(segment_name(object_id), e.size)
        with open(e.spilled_path, "rb") as f:
            f.readinto(seg.buf)
        seg.close()
        e.in_shm = True
        self._used += e.size
        self.num_restored += 1

    def read_bytes(self, object_id: ObjectID) -> Optional[bytes]:
        """Copy out an object's bytes (transfer send path)."""
        meta = self.ensure_local(object_id)
        if meta is None:
            return None
        name, size = meta
        seg = _attach(name)
        try:
            return bytes(seg.buf[:size])
        finally:
            seg.close()

    def read_range(self, object_id: ObjectID, offset: int, length: int) -> Optional[bytes]:
        """Copy one chunk (transfer send path — avoids copying the whole
        object per chunk request)."""
        meta = self.ensure_local(object_id)
        if meta is None:
            return None
        name, size = meta
        seg = _attach(name)
        try:
            end = min(size, offset + length)
            return bytes(seg.buf[offset:end])
        finally:
            seg.close()

    def read_window(
        self, object_id: ObjectID, offset: int, length: int
    ) -> Optional[SegmentWindow]:
        """Zero-copy chunk view (transfer send path): the returned window
        is written to the socket straight from the mapped segment — no
        per-chunk ``bytes`` copy. The caller closes it once the transport
        has consumed the buffer (RawPayload's close hook). Restores from
        spill like :meth:`read_range`; None if unknown."""
        meta = self.ensure_local(object_id)
        if meta is None:
            return None
        name, size = meta
        try:
            seg = _attach(name)
        except FileNotFoundError:
            return None  # raced a spill/delete; caller retries
        end = min(size, offset + length)
        return SegmentWindow(seg, memoryview(seg.buf)[offset:end])

    def pin(self, object_id: ObjectID) -> None:
        with self._lock:
            e = self._entries.get(object_id)
            if e:
                e.pinned += 1

    def unpin(self, object_id: ObjectID) -> None:
        with self._lock:
            e = self._entries.get(object_id)
            if e and e.pinned > 0:
                e.pinned -= 1

    def delete(
        self,
        object_id: ObjectID,
        allow_recycle: bool = False,
        recycle_receive: bool = False,
    ) -> bool:
        """Drop an object. With ``allow_recycle`` (sent by the deleting
        OWNER, who created the segment and keeps it mapped), a segment no
        reader ever resolved is released *without unlinking*: the caller
        takes ownership of the inode for its reuse pool. Returns True in
        exactly that case.

        ``recycle_receive`` is the DAEMON-side analogue for
        transfer-received objects (KV migration): the caller asserts it
        was the object's only consumer and has released its mapping, so
        the inode goes into this store's receive-segment reuse pool
        instead of being unlinked. The store can't verify the assertion
        — a caller that lies hands a still-mapped inode to a future
        transfer, which would scribble over the liar's view — so only
        transfer-private objects (like migration payloads) may use it.
        Restricted to in-shm, unpinned, inode-owning entries."""
        with self._lock:
            if recycle_receive:
                e = self._entries.get(object_id)
                if (
                    e is not None
                    and e.in_shm
                    and e.pinned == 0
                    and e.inode_owner
                    and e.spilled_path is None
                ):
                    self._entries.pop(object_id)
                    self._used -= e.size
                    if self._pool_receive_segment_locked(object_id, e.size):
                        return True
                    # pooling declined: fall through to a plain unlink
                    try:
                        seg = _attach(segment_name(object_id))
                        seg.unlink()
                        seg.close()
                    except FileNotFoundError:
                        pass
                    return False
            if allow_recycle:
                e = self._entries.get(object_id)
                if (
                    e is not None
                    and e.in_shm
                    and not e.read_by_any
                    and e.spilled_path is None
                    and e.pinned == 0
                ):
                    self._entries.pop(object_id)
                    self._used -= e.size
                    return True
            self._drop(object_id)
            return False

    def forget(self, object_id: ObjectID) -> None:
        """Drop the entry WITHOUT unlinking the segment. Drain handoff:
        once a peer holds the replica, this store must stop claiming the
        object — but on a simulated (shared-/dev/shm) cluster the peer's
        "copy" is the SAME inode, so unlinking here (shutdown would)
        destroys the replica too. A real preempted host dies seconds
        later and takes the unreferenced inode with it."""
        with self._lock:
            e = self._entries.pop(object_id, None)
            if e is None:
                return
            if e.in_shm:
                self._used -= e.size
            if e.spilled_path:
                try:
                    os.remove(e.spilled_path)
                except OSError:
                    pass

    def _drop(self, object_id: ObjectID) -> None:
        e = self._entries.pop(object_id, None)
        if e is None:
            return
        if e.in_shm:
            self._used -= e.size
            try:
                seg = _attach(segment_name(object_id))
                seg.unlink()
                seg.close()
            except FileNotFoundError:
                pass
        if e.spilled_path:
            try:
                os.remove(e.spilled_path)
            except OSError:
                pass

    def shutdown(self) -> None:
        with self._lock:
            for oid in list(self._entries):
                self._drop(oid)
            for name in self._recv_pool:
                try:
                    os.unlink(os.path.join(_SHM_DIR, name))
                except OSError:
                    pass
            self._recv_pool.clear()
            self._recv_pool_bytes = 0


_SHM_DIR = "/dev/shm"


class StoreClient:
    """Worker-side shm access. Keeps attachments cached so zero-copy views
    (numpy arrays backed by shm) stay valid for the process lifetime.

    Segment recycling (the plasma-arena insight, ``plasma/store.h:55``):
    page faults on a fresh mmap cost ~10× the copy on virtualized hosts,
    so segments whose objects were freed *without ever being read by
    another process* (daemon-confirmed) are renamed into a small pool —
    same inode, warm PTEs — and the next put of a fitting size reuses
    them at memcpy speed."""

    def __init__(self):
        self._attached: Dict[ObjectID, shared_memory.SharedMemory] = {}
        self._created: Dict[ObjectID, shared_memory.SharedMemory] = {}
        # reuse pool: (current_file_name, still-mapped segment)
        self._pool: List[Tuple[str, shared_memory.SharedMemory]] = []
        self._pool_bytes = 0
        self._pool_seq = 0
        self._lock = threading.Lock()

    def has_created(self, object_id: ObjectID) -> bool:
        with self._lock:
            return object_id in self._created

    def recycle(self, object_id: ObjectID) -> None:
        """Owner freed the object and the daemon confirmed no reader ever
        resolved it: keep the (still warm) segment for reuse. Caller owns
        the inode now — rename it out of the object namespace."""
        with self._lock:
            seg = self._created.pop(object_id, None)
            self._attached.pop(object_id, None)
            if seg is None:
                return
            limit = min(
                GLOBAL_CONFIG.object_store_recycle_bytes,
                GLOBAL_CONFIG.object_store_memory_bytes // 4,
            )
            size = seg.size
            if size < (1 << 20) or self._pool_bytes + size > limit:
                # Reject: unlink by the object's CURRENT file name —
                # seg.unlink() would use the original creation name, which
                # is stale for pool-reused segments (leak, or worse:
                # unlinking a re-produced object's live segment).
                try:
                    os.unlink(os.path.join(_SHM_DIR, segment_name(object_id)))
                except OSError:
                    pass
                try:
                    seg.close()
                except Exception:
                    pass
                return
            self._pool_seq += 1
            pool_name = f"rt-pool-{os.getpid()}-{self._pool_seq}"
            try:
                # NOTE: the file is named after the OBJECT (rename on reuse
                # keeps segment_name(oid) current); seg.name still holds
                # the segment's original creation name — don't use it.
                os.rename(
                    os.path.join(_SHM_DIR, segment_name(object_id)),
                    os.path.join(_SHM_DIR, pool_name),
                )
            except OSError:
                try:
                    seg.close()
                except Exception:
                    pass
                return
            self._pool.append((pool_name, seg))
            self._pool_bytes += size

    def _take_pooled(
        self, object_id: ObjectID, size: int
    ) -> Optional[shared_memory.SharedMemory]:
        """Claim a pooled segment that fits (without gross waste) and
        rename it to the new object's name. Same inode → warm pages."""
        with self._lock:
            for i, (name, seg) in enumerate(self._pool):
                # Tight fit only: physical slack beyond the logical size is
                # invisible to the daemon's accounting (entries record the
                # logical size), so bound it at 12.5% / 1 MiB.
                if seg.size >= size and seg.size <= size + max(size >> 3, 1 << 20):
                    del self._pool[i]
                    self._pool_bytes -= seg.size
                    try:
                        os.rename(
                            os.path.join(_SHM_DIR, name),
                            os.path.join(_SHM_DIR, segment_name(object_id)),
                        )
                    except OSError:
                        try:
                            seg.close()
                        except Exception:
                            pass
                        return None
                    return seg
        return None

    def create_and_write(self, object_id: ObjectID, ser) -> int:
        """Write a SerializedValue into a fresh segment; returns size.

        Serialized bytes go straight into the mapped segment (one copy) —
        the put-GB/s hot path."""
        size = ser.total_bytes
        seg = self._take_pooled(object_id, size)
        if seg is not None:
            ser.write_into_view(memoryview(seg.buf))
            with self._lock:
                stale = [
                    s
                    for s in (
                        self._created.pop(object_id, None),
                        self._attached.pop(object_id, None),
                    )
                    if s is not None and s is not seg
                ]
                self._created[object_id] = seg
            for s in stale:
                try:
                    s.close()
                except Exception:
                    pass
            return size
        try:
            seg = _create(segment_name(object_id), size)
        except FileExistsError:
            # Same object re-produced (task retry / simulated multi-node).
            # Re-serialization (cloudpickle) is not guaranteed byte-identical:
            # if the new payload is larger than the old segment, unlink and
            # recreate — POSIX unlink keeps existing readers' mappings valid.
            seg = _attach(segment_name(object_id))
            if len(seg.buf) < size:
                try:
                    seg.unlink()
                finally:
                    seg.close()
                seg = _create(segment_name(object_id), size)
        ser.write_into_view(memoryview(seg.buf))
        with self._lock:
            # Drop stale cached mappings (both caches): after a re-produce
            # the old unlinked inode must not win future read()s.
            stale = [
                s
                for s in (
                    self._created.pop(object_id, None),
                    self._attached.pop(object_id, None),
                )
                if s is not None and s is not seg
            ]
            self._created[object_id] = seg
        for s in stale:
            try:
                s.close()
            except Exception:
                pass
        return size

    def read(self, object_id: ObjectID, size: int) -> memoryview:
        with self._lock:
            seg = self._attached.get(object_id) or self._created.get(object_id)
            if seg is None:
                seg = _attach(segment_name(object_id))
                self._attached[object_id] = seg
        return memoryview(seg.buf)[:size]

    def release(self, object_id: ObjectID) -> None:
        with self._lock:
            seg = self._attached.pop(object_id, None) or self._created.pop(object_id, None)
        if seg is not None:
            try:
                seg.close()
            except Exception:
                pass

    def close_all(self) -> None:
        with self._lock:
            segs = list(self._attached.values()) + list(self._created.values())
            pool = self._pool
            self._attached.clear()
            self._created.clear()
            self._pool = []
            self._pool_bytes = 0
        for seg in segs:
            try:
                seg.close()
            except Exception:
                pass
        for name, seg in pool:
            try:
                os.unlink(os.path.join(_SHM_DIR, name))
            except OSError:
                pass
            try:
                seg.close()
            except Exception:
                pass


class MemoryStore:
    """In-process store for small objects; supports blocking waits.

    Reference: ``core_worker/store_provider/memory_store/``."""

    def __init__(self):
        self._data: Dict[ObjectID, bytes] = {}
        self._events: Dict[ObjectID, threading.Event] = {}
        self._lock = threading.Lock()

    def put(self, object_id: ObjectID, data: bytes) -> None:
        with self._lock:
            self._data[object_id] = data
            ev = self._events.pop(object_id, None)
        if ev:
            ev.set()

    # Reads are lock-free: dict.get on a key is atomic under the GIL and
    # this store is the owner-side INLINE CACHE — every get() on a small
    # task result goes through here, so a lock acquire per read is pure
    # hot-path overhead. Mutation (put/delete) stays locked for the
    # event bookkeeping.
    def get(self, object_id: ObjectID) -> Optional[bytes]:
        return self._data.get(object_id)

    def contains(self, object_id: ObjectID) -> bool:
        return object_id in self._data

    def wait_for(self, object_id: ObjectID, timeout: Optional[float]) -> Optional[bytes]:
        with self._lock:
            if object_id in self._data:
                return self._data[object_id]
            ev = self._events.get(object_id)
            if ev is None:
                ev = self._events[object_id] = threading.Event()
        if not ev.wait(timeout):
            return None
        with self._lock:
            return self._data.get(object_id)

    def delete(self, object_id: ObjectID) -> None:
        with self._lock:
            self._data.pop(object_id, None)
