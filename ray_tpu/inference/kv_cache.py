"""Paged KV cache management: the host-side block allocator.

vLLM-style paging (PAPERS.md: TPU serving stacks win by packing many
requests into one fixed-shape KV cache): the device holds what the model's
cache description says a token leaves in each layer
(``models/interface.py::CacheLayout``: ``[n_layers, num_blocks, block_size,
n_kv_heads, head_dim]`` K and V tensors, or one latent row a token); this
module owns the *accounting* — which request holds which block ids, what is free, and
when a new request must wait in the admission queue instead.

Block id 0 is reserved as the NULL block: padding positions in the
fixed-shape prefill/decode steps write their trash there, so it is never
handed to a request. A block id covers ``block_size`` token positions in
every layer of ONE layer group (``models/interface.py::LayerGroup``). A
model whose layers all keep a sequence whole has one group, every layer in
it, and the allocator deals in tokens, not layer-tokens. A model with
window layers has a second group (or more), a pool beside the first:
:class:`_WindowPool`, a free list, a table a request and counters of its
own. :meth:`PagedBlockManager.grow_to` grows every group and, in a group
that keeps the last ``W`` positions, RELEASES the blocks that lie wholly
behind the window: the released entry of the request's table reads the null
block. Every way out (:meth:`free`, :meth:`evict`) gives back every group's
blocks. What cannot run over a window group yet (prefix reuse, export /
import, the tier, a verify window) is refused where the engine is made.

Prefix caching (the warm-TTFT tentpole): a FULL block whose token
content has been completely written is immutable from then on — decode
only ever writes positions past it. The manager therefore indexes full
blocks by a *chain digest* (hash of the block's tokens chained with the
previous block's digest — the path-compressed radix tree of vLLM's
automatic prefix caching, stored flat because every node is uniquely
named by its prefix digest). A new request whose prompt prefix matches
cached digests ACQUIRES those blocks (refcounted sharing instead of
re-prefilling) and only the uncached tail goes through prefill.

Copy-on-write covers the one case where a sharer must write into a
shared block: a *full-prompt* hit still needs the last prompt token's
logits, so the final hit block is duplicated device-side
(``model_runner.copy_blocks``) and a 1-token prefill recomputes just
that position into the private copy — the shared original stays
immutable for every other reader.

Eviction: blocks whose refcount drops to zero stay cached (they cost
nothing until the pool is short) on an LRU list; allocation drains the
free list first, then reclaims the oldest unreferenced cached block.
``free_blocks``/``used_blocks`` count cached-but-unreferenced capacity
as free — it is reclaimable at zero cost, and admission control must
see it that way or a warm cache would wedge the queue.

State slots (a model with recurrent layers, ``models/interface.py::
StateLayout``): beside its blocks a running request holds ONE slot of a
second pool, the index of its fixed-size per-sequence arrays on the device.
The same manager hands it out (:meth:`assign_slot`, at admission, after the
blocks) and takes it back in :meth:`free`, which every way out of the
running set goes through (finish, cancel, preemption's evict, the engine's
fail-all), so a slot cannot outlive its request's blocks. Slot 0 is the
null slot, as block 0 is the null block. ``state_slots`` 0: no such pool.

Pure host-side python with no jax dependency: unit-testable without an
accelerator, and cheap enough to run under the engine lock.
"""

from __future__ import annotations

import hashlib
import struct
import threading
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: digest width for the chain hash. 16 bytes: block-content collisions
#: would silently serve wrong KV, so this is sized for "never", not for
#: compactness — the gossip digest truncates to 8-byte ints instead.
_DIGEST_SIZE = 16


def _chain_digest(prev: bytes, block_tokens) -> bytes:
    """Digest naming the prefix that ends with ``block_tokens``."""
    h = hashlib.blake2b(prev, digest_size=_DIGEST_SIZE)
    h.update(struct.pack(f"<{len(block_tokens)}q", *block_tokens))
    return h.digest()


def prefix_block_hashes(tokens, block_size: int) -> List[int]:
    """Chain digests of every FULL block of ``tokens``, truncated to
    signed 64-bit ints — the compact form replicas gossip to routers and
    routers recompute per request for affinity scoring. Must stay in
    lockstep with the manager's internal chain (same function, truncated
    view), or affinity would never match."""
    out: List[int] = []
    prev = b""
    for end in range(block_size, len(tokens) + 1, block_size):
        prev = _chain_digest(prev, tokens[end - block_size : end])
        out.append(struct.unpack("<q", prev[:8])[0])
    return out


class _WindowPool:
    """The blocks of one layer group that keeps the last ``keeps`` positions
    of a sequence: a free list, a table a request (as long as the sequence
    has run, a released entry 0) and counters. No block is shared, indexed or
    copied: what could share one is refused where the engine is made. Not
    locked: :class:`PagedBlockManager` calls it under its own lock."""

    def __init__(self, name: str, num_blocks: int, block_size: int, keeps: int):
        if num_blocks < 2:
            raise ValueError(f"group {name!r} needs >= 2 blocks (block 0 is the null block)")
        if keeps < 1:
            raise ValueError(f"group {name!r} keeps the last {keeps} positions: need >= 1")
        self.name, self.num_blocks, self.block_size, self.keeps = name, num_blocks, block_size, keeps
        self.free: deque = deque(range(1, num_blocks))
        #: request -> its table so far: entry ``i`` the block of positions
        #: ``[i * bs, (i + 1) * bs)``, 0 once released (or never needed)
        self.tables: Dict[str, List[int]] = {}
        #: request -> the first entry that may still hold a block
        self.first: Dict[str, int] = {}
        self.peak_in_use = 0
        self.taken = 0
        #: blocks given back by sliding, not by a request's end
        self.released_behind = 0

    @property
    def in_use(self) -> int:
        return self.num_blocks - 1 - len(self.free)

    def plan(self, request_id: str, first_query: int, num_tokens: int) -> Tuple[int, int, int]:
        """``(first live entry, entries needed, blocks to take beyond what
        sliding gives back)`` for queries from position ``first_query`` on
        over ``num_tokens`` positions: the first key any of them sees is
        ``first_query - keeps + 1``."""
        bs = self.block_size
        lo = max(0, first_query - self.keeps + 1) // bs
        hi = max(lo + 1, -(-num_tokens // bs))
        table = self.tables.get(request_id, ())
        first = self.first.get(request_id, 0)
        gives = sum(1 for blk in table[first:lo] if blk)
        have = sum(1 for blk in table[lo:hi] if blk)
        return lo, hi, (hi - lo) - have - gives

    def slide(self, request_id: str, lo: int, hi: int) -> None:
        """Release the entries behind ``lo``, then cover ``[lo, hi)``. The
        caller checked :meth:`plan` against the free list."""
        table = self.tables.setdefault(request_id, [])
        for i in range(self.first.get(request_id, 0), min(lo, len(table))):
            if table[i]:
                self.free.append(table[i])
                table[i] = 0
                self.released_behind += 1
        self.first[request_id] = lo
        table.extend([0] * (hi - len(table)))
        for i in range(lo, hi):
            if not table[i]:
                table[i] = self.free.popleft()
                self.taken += 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)

    def trim(self, request_id: str, num_tokens: int) -> None:
        """Hand back the entries past ``num_tokens`` positions."""
        table = self.tables.get(request_id)
        keep = max(1, -(-num_tokens // self.block_size))
        while table and len(table) > keep:
            blk = table.pop()
            if blk:
                self.free.append(blk)

    def release(self, request_id: str) -> int:
        table = self.tables.pop(request_id, ())
        self.first.pop(request_id, None)
        held = [blk for blk in table if blk]
        self.free.extend(held)
        return len(held)

    def stats(self) -> Dict[str, int]:
        return {
            "blocks": self.num_blocks, "keeps": self.keeps, "in_use": self.in_use,
            "peak_in_use": self.peak_in_use, "taken": self.taken,
            "released_behind": self.released_behind,
        }


class PagedBlockManager:
    """Allocation / free / eviction accounting for the shared block pool."""

    def __init__(
        self,
        num_blocks: int,
        block_size: int,
        *,
        prefix_cache_enabled: bool = False,
        prefix_cache_max_blocks: int = 0,
        state_slots: int = 0,
        group: str = "all",
        windows: Sequence[Tuple[str, int, int]] = (),
    ):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        if windows and prefix_cache_enabled:
            raise ValueError(
                "prefix reuse cannot run over a layer group that keeps a window: a hit would skip "
                "prefill over positions whose window rows were released"
            )
        #: the name of the group these blocks belong to (the one that keeps a
        #: sequence whole) and, beside it, the pools of the groups that keep a
        #: window: ``(name, num_blocks, keeps)`` each, in the layout's order
        self.group = group
        self.windows: Tuple[_WindowPool, ...] = tuple(
            _WindowPool(name, n, block_size, keeps) for name, n, keeps in windows
        )
        self.peak_in_use = 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.prefix_cache_enabled = prefix_cache_enabled
        #: cap on indexed blocks (0 = bounded only by the pool itself)
        self.prefix_cache_max_blocks = prefix_cache_max_blocks
        # block 0 = null: never allocated
        self._free: deque = deque(range(1, num_blocks))
        self._owned: Dict[str, List[int]] = {}
        #: block -> number of requests referencing it (shared prefix
        #: blocks count every sharer; COW sources count their pin)
        self._ref: Dict[int, int] = {}
        #: block -> chain digest, for FULL (immutable) cached blocks
        self._block_hash: Dict[int, bytes] = {}
        #: chain digest -> block (the flat radix index). Ordered by
        #: RECENCY OF USE (insertion + move-to-end on every hit): the
        #: gossip digest truncates to the most recent entries, and a hot
        #: shared system prompt must stay inside that window no matter
        #: how long ago it was first indexed.
        self._index: "OrderedDict[bytes, int]" = OrderedDict()
        #: chain digest -> PARENT chain digest (b"" at the root): the
        #: radix-path structure the flat index erases. Gossip export
        #: walks these so every exported digest ships with its whole
        #: ancestor spine — a consecutive-prefix matcher (the router)
        #: can't use an orphan digest whose ancestors were truncated out.
        self._parent: Dict[bytes, bytes] = {}
        #: unreferenced cached blocks, oldest first (block -> digest)
        self._lru: "OrderedDict[int, bytes]" = OrderedDict()
        #: request -> COW source blocks pinned until the device copy ran
        self._cow_src: Dict[str, List[int]] = {}
        #: chain digest -> acquire_prefix hit count (the popularity
        #: signal the spill-vs-drop policy reads at eviction)
        self._hits: Dict[bytes, int] = {}
        #: spill-vs-drop policy hook: ``fn(digest, block, hits) -> bool``
        #: consulted at EVERY indexed-block eviction (allocation-pressure
        #: LRU reclaim and the register cap-eviction — one policy point,
        #: not two divergent code paths). True = the block's content was
        #: spilled somewhere recoverable (the cluster KV tier), False =
        #: dropped. Runs under the manager lock on the step thread: the
        #: hook may read the device (the content dies with the return)
        #: but MUST NOT re-enter locked manager methods or block on IO.
        self._spill_hook = None
        #: usable state slots (ids 1..state_slots; 0 = the model has no
        #: per-sequence state and nobody asks)
        self.state_slots = state_slots
        self._free_slots: deque = deque(range(1, state_slots + 1))
        self._slot: Dict[str, int] = {}
        self.slots_peak_in_use = 0
        self.slots_assigned_total = 0
        self.slots_released_total = 0
        #: requests that waited at the head of the admission queue for a
        #: slot at least once (the scheduler counts each once)
        self.slot_admission_waits = 0
        self._lock = threading.Lock()
        # lifetime accounting (engine /metrics + stats())
        self.total_allocs = 0
        self.total_frees = 0
        self.total_evictions = 0
        self.prefix_queries_total = 0
        self.prefix_hits_total = 0
        self.prefix_tokens_saved_total = 0
        self.cow_copies_total = 0
        self.prefix_evictions_total = 0
        #: books-balance split of prefix_evictions_total: every evicted
        #: indexed block is EXACTLY one of spilled (content preserved in
        #: the tier) or dropped (gone) — evictions == spilled + dropped
        self.prefix_spilled_total = 0
        self.prefix_dropped_total = 0

    def set_spill_hook(self, hook) -> None:
        """Install the spill-vs-drop policy (see ``_spill_hook``)."""
        with self._lock:
            self._spill_hook = hook

    # -- capacity ---------------------------------------------------------
    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1  # minus the null block

    @property
    def free_blocks(self) -> int:
        """Immediately-allocatable capacity: the free list plus cached
        blocks no live request references (reclaimed on demand)."""
        with self._lock:
            return len(self._free) + len(self._lru)

    @property
    def cached_blocks(self) -> int:
        """Unreferenced blocks held only by the prefix cache."""
        with self._lock:
            return len(self._lru)

    @property
    def used_blocks(self) -> int:
        return self.usable_blocks - self.free_blocks

    def utilization(self) -> float:
        return self.used_blocks / max(1, self.usable_blocks)

    def blocks_for_tokens(self, num_tokens: int) -> int:
        return max(1, -(-num_tokens // self.block_size))  # ceil

    # -- allocation -------------------------------------------------------
    def owned(self, request_id: str) -> List[int]:
        with self._lock:
            return list(self._owned.get(request_id, ()))

    def _evict_indexed_locked(self, blk: int, digest: bytes) -> None:
        """Retire one indexed block from the radix structure — the ONE
        spill-vs-drop policy decision point (ISSUE 17's unlocking
        refactor: LRU eviction and the kv_transfer export path used to
        be unrelated, so KV pressure silently destroyed reusable state).
        The hook sees the block while its device content is still valid
        and decides: spill (preserve in the tier) or drop."""
        del self._index[digest]
        del self._block_hash[blk]
        self._parent.pop(digest, None)
        hits = self._hits.pop(digest, 0)
        self.prefix_evictions_total += 1
        spilled = False
        if self._spill_hook is not None:
            try:
                spilled = bool(self._spill_hook(digest, blk, hits))
            except Exception:
                spilled = False  # a broken policy degrades to drop
        if spilled:
            self.prefix_spilled_total += 1
        else:
            self.prefix_dropped_total += 1

    def _take_block_locked(self) -> Optional[int]:
        """One free block, reclaiming the LRU cached block if needed."""
        if self._free:
            return self._free.popleft()
        if self._lru:
            blk, digest = self._lru.popitem(last=False)
            self._evict_indexed_locked(blk, digest)
            return blk
        return None

    def _release_block_locked(self, blk: int) -> None:
        """Drop one reference; park the block on the LRU (still cached)
        or the free list once nobody references it."""
        n = self._ref.get(blk, 1) - 1
        if n > 0:
            self._ref[blk] = n
            return
        self._ref.pop(blk, None)
        digest = self._block_hash.get(blk)
        if digest is not None:
            self._lru[blk] = digest
            self._lru.move_to_end(blk)
        else:
            self._free.append(blk)

    def can_grow_to(self, request_id: str, num_tokens: int) -> bool:
        """Whether the pool can extend ``request_id`` to cover
        ``num_tokens`` total positions (no allocation happens)."""
        need = self.blocks_for_tokens(num_tokens)
        with self._lock:
            have = len(self._owned.get(request_id, ()))
            return need - have <= len(self._free) + len(self._lru)

    def grow_to(self, request_id: str, num_tokens: int, chunk: Optional[Tuple[int, int]] = None) -> bool:
        """Extend the request's block list to cover ``num_tokens`` total
        positions. All-or-nothing: returns False (nothing allocated, nothing
        released) when the free pool of ANY group can't cover the extension.

        A group that keeps the last ``W`` positions covers what the step's
        queries see and no more: for a decode step (``chunk`` None: the query
        stands at ``num_tokens - 1``) the positions from ``num_tokens - W`` on,
        for a prefill chunk ``chunk = (start, end)`` those from ``start - W +
        1`` up to ``end``; the blocks that lie WHOLLY behind that are given
        back first, and their entries of the table read the null block."""
        need = self.blocks_for_tokens(num_tokens)
        with self._lock:
            blocks = self._owned.setdefault(request_id, [])
            missing = max(0, need - len(blocks))
            first_query, upto = chunk if chunk is not None else (num_tokens - 1, num_tokens)
            plans = [w.plan(request_id, first_query, upto) for w in self.windows]
            if missing > len(self._free) + len(self._lru) or any(
                take > len(w.free) for w, (_, _, take) in zip(self.windows, plans)
            ):
                if not blocks:
                    self._owned.pop(request_id, None)
                return False
            for w, (lo, hi, _) in zip(self.windows, plans):
                w.slide(request_id, lo, hi)
            for _ in range(missing):
                blk = self._take_block_locked()
                blocks.append(blk)
                self._ref[blk] = 1
            self.total_allocs += missing
            if missing:
                self.peak_in_use = max(
                    self.peak_in_use, self.num_blocks - 1 - len(self._free) - len(self._lru)
                )
            return True

    def trim_to(self, request_id: str, num_tokens: int) -> int:
        """Shrink the request's block list back to exactly cover
        ``num_tokens`` total positions — the speculative-decode rollback:
        blocks grown for rejected draft positions are handed back before
        any other request could observe them. Refcount-aware like
        :meth:`free` (a trimmed block some other holder still references
        just drops this request's pin), though in the speculative path
        trimmed tails are always freshly grown (ref==1, never indexed)
        so they go straight back to the free list. Returns the number of
        block references released."""
        keep = self.blocks_for_tokens(num_tokens)
        with self._lock:
            blocks = self._owned.get(request_id)
            if not blocks or len(blocks) <= keep:
                return 0
            released = 0
            while len(blocks) > keep:
                self._release_block_locked(blocks.pop())
                released += 1
            for w in self.windows:
                w.trim(request_id, num_tokens)
            self.total_frees += released
            return released

    # -- state slots ------------------------------------------------------
    def has_free_slot(self) -> bool:
        """Whether a request could be given a state slot now (always, for a
        model without per-sequence state)."""
        with self._lock:
            return not self.state_slots or bool(self._free_slots)

    def assign_slot(self, request_id: str) -> int:
        """Hand ``request_id`` a free state slot (the one it holds, if any);
        0 without a pool. The caller checked :meth:`has_free_slot`."""
        with self._lock:
            if not self.state_slots:
                return 0
            slot = self._slot.get(request_id)
            if slot is None:
                slot = self._slot[request_id] = self._free_slots.popleft()
                self.slots_assigned_total += 1
                self.slots_peak_in_use = max(self.slots_peak_in_use, len(self._slot))
            return slot

    def slot_of(self, request_id: str) -> int:
        """The state slot the request holds; 0 (the null slot) if none."""
        with self._lock:
            return self._slot.get(request_id, 0)

    def slot_stats(self) -> Dict[str, int]:
        """What ``engine_stats()["state_pool"]`` says."""
        with self._lock:
            return {
                "slots": self.state_slots,
                "in_use": len(self._slot),
                "peak_in_use": self.slots_peak_in_use,
                "assigned": self.slots_assigned_total,
                "released": self.slots_released_total,
                "admission_waits": self.slot_admission_waits,
            }

    def free(self, request_id: str) -> int:
        """Release every block the request holds (refcount-aware: shared
        blocks survive for their other holders) and its state slot. Returns
        the number of block references released."""
        with self._lock:
            slot = self._slot.pop(request_id, None)
            if slot is not None:
                self._free_slots.append(slot)
                self.slots_released_total += 1
            blocks = self._owned.pop(request_id, [])
            for blk in blocks:
                self._release_block_locked(blk)
            for w in self.windows:
                w.release(request_id)
            # a pending COW that never executed releases its source pin
            for blk in self._cow_src.pop(request_id, ()):
                self._release_block_locked(blk)
            self.total_frees += len(blocks)
            return len(blocks)

    def evict(self, request_id: str) -> int:
        """Free-with-attitude: same as :meth:`free` but counted as a
        preemption eviction (the scheduler took the blocks away; the
        request re-prefills on readmission)."""
        n = self.free(request_id)
        if n:
            self.total_evictions += 1
        return n

    # -- prefix cache -----------------------------------------------------
    def acquire_prefix(
        self, request_id: str, tokens
    ) -> Tuple[int, List[Tuple[int, int]]]:
        """Attach cached blocks covering the longest indexed prefix of
        ``tokens`` to ``request_id``; returns ``(cached_tokens,
        cow_pairs)``. The request's prefill then starts at
        ``cached_tokens`` instead of 0.

        A FULL-prompt hit keeps the final block shared but pairs it with
        a freshly allocated private copy target: ``cow_pairs`` =
        ``[(src, dst)]`` for the engine to execute device-side before
        the 1-token tail prefill writes position ``len(tokens)-1`` into
        ``dst``. The source stays refcount-pinned until
        :meth:`cow_copied` (or :meth:`free`) — without the pin, another
        admission in the same scheduling pass could reclaim it before
        the copy ran. Does NOT bump the hit counters — the scheduler
        commits them via :meth:`note_prefix_hit` only once admission
        (block growth for the tail) actually succeeds, so a stuck queue
        head retrying every tick doesn't inflate the stats.
        """
        if not self.prefix_cache_enabled:
            return 0, []
        with self._lock:
            if self._owned.get(request_id):
                return 0, []  # mid-flight request: table already live
            hits: List[int] = []
            prev = b""
            bs = self.block_size
            for end in range(bs, len(tokens) + 1, bs):
                prev = _chain_digest(prev, tokens[end - bs : end])
                blk = self._index.get(prev)
                if blk is None:
                    break
                # refresh use-recency so hot prefixes stay in the
                # truncated gossip digest window
                self._index.move_to_end(prev)
                # popularity signal for the eviction-time spill-vs-drop
                # decision ("spill popular, drop cold")
                self._hits[prev] = self._hits.get(prev, 0) + 1
                hits.append(blk)
            if not hits:
                return 0, []
            # pin every hit FIRST: an unreferenced hit sits on the LRU
            # and a subsequent allocation in this same pass could
            # otherwise reclaim it out from under us
            for blk in hits:
                self._ref[blk] = self._ref.get(blk, 0) + 1
                self._lru.pop(blk, None)
            cow: List[Tuple[int, int]] = []
            cached_tokens = len(hits) * bs
            if cached_tokens >= len(tokens):
                # full-prompt hit: the first sampled token needs the last
                # prompt token's logits, so ONE token must still prefill
                # — and its K/V write lands inside the final (shared)
                # block. COW that block to a private copy; the tail
                # prefill recomputes position len-1 into the copy.
                dst = self._take_block_locked()
                if dst is None:
                    # pool dry: fall back to recomputing the last block
                    self._release_block_locked(hits.pop())
                    cached_tokens -= bs
                else:
                    src = hits[-1]
                    hits[-1] = dst
                    self._ref[dst] = 1
                    # src keeps the pin taken above, now owned by the
                    # pending-copy record instead of the block table
                    self._cow_src.setdefault(request_id, []).append(src)
                    cow.append((src, dst))
                    self.cow_copies_total += 1
                    self.total_allocs += 1
                    cached_tokens = len(tokens) - 1
            if not hits:
                return 0, []
            self._owned[request_id] = hits
            return cached_tokens, cow

    def note_prefix_hit(self, cached_tokens: int) -> None:
        """Commit hit accounting once the request actually ADMITTED —
        one query per admission, not per acquire attempt (a queue head
        stuck behind block pressure re-acquires every scheduler tick and
        would otherwise drown the hit rate in retry noise). No-op with
        the cache disabled: queries_total must read as "admissions with
        the cache ON", not tick up under a 0.0 hit rate."""
        if not self.prefix_cache_enabled:
            return
        with self._lock:
            self.prefix_queries_total += 1
            if cached_tokens <= 0:
                return
            self.prefix_hits_total += 1
            self.prefix_tokens_saved_total += cached_tokens

    def cow_copied(self, request_id: str) -> None:
        """The engine executed the pending device copies: release the
        source pins (the private copies live in the block table now)."""
        with self._lock:
            for blk in self._cow_src.pop(request_id, ()):
                self._release_block_locked(blk)

    def register_prefix(self, request_id: str, tokens) -> int:
        """Index the request's fully-written blocks: ``tokens`` must be
        the positions whose K/V are actually in the cache (the prompt at
        prefill completion; prompt+generated-minus-one at finish — the
        final sampled token's K/V is never written). Full blocks are
        immutable from here on, so indexing them is safe for any future
        reader. Returns how many new blocks were indexed."""
        if not self.prefix_cache_enabled:
            return 0
        with self._lock:
            blocks = self._owned.get(request_id)
            if not blocks:
                return 0
            bs = self.block_size
            n_full = min(len(tokens) // bs, len(blocks))
            added = 0
            prev = b""
            for i in range(n_full):
                parent = prev
                prev = _chain_digest(prev, tokens[i * bs : (i + 1) * bs])
                blk = blocks[i]
                if blk in self._block_hash:
                    continue  # already indexed (e.g. acquired via a hit)
                if prev in self._index:
                    continue  # another block already serves this prefix
                if self.prefix_cache_max_blocks > 0 and (
                    len(self._index) >= self.prefix_cache_max_blocks
                ):
                    if not self._lru:
                        break  # cap reached, nothing evictable
                    old_blk, old_digest = self._lru.popitem(last=False)
                    self._evict_indexed_locked(old_blk, old_digest)
                    self._free.append(old_blk)
                self._block_hash[blk] = prev
                self._index[prev] = blk
                self._parent[prev] = parent
                added += 1
            return added

    def prefix_digest(self, max_entries: int = 256) -> List[int]:
        """Compact cache summary for router gossip: a bounded
        RADIX-PATH export instead of the old flat recent-N slice.

        The router's affinity scorer matches consecutively from block 0
        and stops at the first miss, so an exported digest is only
        usable when its entire ancestor chain is exported with it. The
        flat MRU slice broke exactly that once the index outgrew the
        budget: it kept the N most-recently-used blocks as arbitrary
        points, truncating the ancestors a deep hot path needs. Here we
        walk the index MRU-first and export whole root-anchored SPINES
        (each digest plus every ancestor still indexed), skipping spines
        that don't fit the remaining budget or whose chain is broken by
        eviction (their descendants can never match anyway) — so with
        >10k indexed blocks the gossip covers the hottest complete
        paths, not a useless frontier of orphans.

        Truncation contract (unchanged): entries are the first 8 bytes
        of the 16-byte chain digest as signed 64-bit ints. A router-side
        collision is a FALSE POSITIVE ONLY — it routes a request to a
        replica that turns out cold, costing one suboptimal placement;
        correctness never depends on this digest (the engine re-derives
        full 16-byte digests at admission)."""
        out: List[bytes] = []
        with self._lock:
            seen = set()
            for digest in reversed(self._index):
                if len(out) >= max_entries:
                    break
                if digest in seen:
                    continue  # already exported as an ancestor
                spine: List[bytes] = []
                d = digest
                complete = True
                while d:
                    if d in seen:
                        break  # ancestors already in the export
                    if d not in self._index:
                        complete = False  # evicted mid-chain: orphan path
                        break
                    spine.append(d)
                    d = self._parent.get(d, b"")
                if not complete or len(out) + len(spine) > max_entries:
                    continue
                seen.update(spine)
                out.extend(spine)
        return [struct.unpack("<q", d[:8])[0] for d in out]

    # -- KV-cache migration (disaggregated serving) -----------------------
    def reserve_import(self, num_blocks: int) -> Optional[List[int]]:
        """Allocate blocks for migrated KV content, each pinned (ref=1)
        until :meth:`commit_import` or :meth:`abort_import` — the device
        scatter runs between reserve and commit, and an unpinned block
        could be reclaimed out from under it. Returns None (nothing
        taken) when the pool can't cover the import — the caller falls
        back to a plain prefill instead of wedging admission."""
        with self._lock:
            if num_blocks <= 0:
                return []
            if num_blocks > len(self._free) + len(self._lru):
                return None
            out: List[int] = []
            for _ in range(num_blocks):
                blk = self._take_block_locked()
                self._ref[blk] = 1
                out.append(blk)
            self.total_allocs += num_blocks
            return out

    def commit_import(self, blocks: List[int], tokens) -> int:
        """Index scattered import blocks in the radix structure so later
        admissions (the migrated request first of all) acquire them as
        prefix hits. Block i must hold the K/V of
        ``tokens[i*bs:(i+1)*bs]`` — the chain digest is recomputed here
        from the tokens, never trusted from the wire. Blocks whose
        prefix another local block already serves are redundant copies:
        released straight back to the free list. Every committed block
        drops its import pin and parks cached-unreferenced (LRU), i.e.
        imported KV costs nothing until someone uses or evicts it.
        Returns the number of blocks actually indexed."""
        bs = self.block_size
        n = min(len(blocks), len(tokens) // bs)
        added = 0
        with self._lock:
            prev = b""
            for i in range(n):
                parent = prev
                prev = _chain_digest(prev, tokens[i * bs : (i + 1) * bs])
                blk = blocks[i]
                if prev in self._index or blk in self._block_hash:
                    # an equivalent block is already indexed locally:
                    # drop the imported copy (no digest -> free list)
                    self._release_block_locked(blk)
                    continue
                self._block_hash[blk] = prev
                self._index[prev] = blk
                self._parent[prev] = parent
                # pin released WITH the digest set: lands on the LRU as
                # a cached-unreferenced block
                self._release_block_locked(blk)
                added += 1
            # surplus reserve (shouldn't happen: caller sizes exactly)
            for blk in blocks[n:]:
                self._release_block_locked(blk)
        return added

    def abort_import(self, blocks: List[int]) -> None:
        """Scatter failed: return reserved (never-indexed) blocks."""
        with self._lock:
            for blk in blocks:
                self._release_block_locked(blk)

    def prefix_stats(self) -> Dict[str, float]:
        with self._lock:
            indexed = len(self._index)
            cached_free = len(self._lru)
            queries = self.prefix_queries_total
            hits = self.prefix_hits_total
        return {
            "enabled": self.prefix_cache_enabled,
            "indexed_blocks": indexed,
            "cached_unreferenced_blocks": cached_free,
            # queries = ADMISSIONS with the cache enabled (see
            # note_prefix_hit), so hit_rate reads as "fraction of
            # admitted requests that reused cached blocks"
            "queries_total": queries,
            "hits_total": hits,
            "hit_rate": hits / queries if queries else 0.0,
            "tokens_saved_total": self.prefix_tokens_saved_total,
            "cow_copies_total": self.cow_copies_total,
            "evictions_total": self.prefix_evictions_total,
            # spill-vs-drop books: evictions == spilled + dropped, always
            "spilled_total": self.prefix_spilled_total,
            "dropped_total": self.prefix_dropped_total,
        }

    # -- introspection ----------------------------------------------------
    def refcount(self, block_id: int) -> int:
        with self._lock:
            return self._ref.get(block_id, 0)

    def table_row(self, request_id: str, max_blocks: int):
        """The request's block-table row, right-padded with the null
        block to the fixed ``max_blocks`` width the jitted steps expect.
        With window groups: one such row a group, an int32 array ``[groups,
        max_blocks]``, this pool's first (a released entry reads the null
        block; an array, because at a table of a thousand entries the rows of
        a decode batch cost milliseconds to convert from lists)."""
        with self._lock:
            rows = [self._owned.get(request_id, ())]
            rows += [w.tables.get(request_id, ()) for w in self.windows]
            if any(len(blocks) > max_blocks for blocks in rows):
                raise ValueError(
                    f"request {request_id!r} holds {max(len(blocks) for blocks in rows)} blocks > "
                    f"max_blocks_per_seq {max_blocks}"
                )
            if not self.windows:
                return list(rows[0]) + [0] * (max_blocks - len(rows[0]))
            out = np.zeros((len(rows), max_blocks), np.int32)
            for g, blocks in enumerate(rows):
                out[g, : len(blocks)] = blocks
            return out

    def held_blocks(self, request_id: str) -> List[int]:
        """Blocks the request holds now, a group (this pool's first)."""
        with self._lock:
            return [len(self._owned.get(request_id, ()))] + [
                sum(1 for blk in w.tables.get(request_id, ()) if blk) for w in self.windows
            ]

    def blocks_in_use(self) -> List[int]:
        """Blocks out of each group's pool now (this pool's first): what a
        decode launch adds to the engine's ``kv_held``, two ints and no walk
        over the requests."""
        with self._lock:
            return [self.num_blocks - 1 - len(self._free) - len(self._lru)] + [
                w.in_use for w in self.windows
            ]

    def pool_stats(self) -> Dict[str, Dict[str, int]]:
        """What ``engine_stats()["kv_pools"]`` says: a group's blocks, those
        in use now and at the peak, blocks taken in all, and blocks given
        back by sliding (a group that keeps everything gives none back so)."""
        with self._lock:
            in_use = self.num_blocks - 1 - len(self._free) - len(self._lru)
            return {
                self.group: {
                    "blocks": self.num_blocks, "keeps": "all", "in_use": in_use,
                    "peak_in_use": max(self.peak_in_use, in_use), "taken": self.total_allocs,
                    "released_behind": 0,
                },
                **{w.name: w.stats() for w in self.windows},
            }

    def stats(self) -> Dict[str, float]:
        with self._lock:
            free = len(self._free) + len(self._lru)
            cached = len(self._lru)
            holders = len(self._owned)
        used = self.usable_blocks - free
        return {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "used_blocks": used,
            "free_blocks": free,
            "prefix_cached_blocks": cached,
            "holders": holders,
            "utilization": used / max(1, self.usable_blocks),
            "total_allocs": self.total_allocs,
            "total_frees": self.total_frees,
            "total_evictions": self.total_evictions,
        }
