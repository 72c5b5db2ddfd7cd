"""Speculative decoding: draft proposers for the paged-KV engine.

The engine's decode loop amortizes per-token step overhead by letting a
cheap *proposer* guess k tokens ahead, then verifying all k+1 positions
in ONE bucketed jitted target step (the model's ``paged_verify_step`` —
chunked-prefill-shaped, all-position logits). Because PR 10's
(request_seed, absolute-position) RNG pins the whole output stream given
(seed, prompt), acceptance is **exact-match**: the target's
deterministically-realized token at each position is computed from the
verify logits with the engine's own sampler, drafts are accepted while
they match it, and the first mismatch position emits the target's token
instead (the "bonus/correction" token) — so every speculative step emits
at least one token and the emitted stream is byte-identical to plain
decode by construction, for greedy AND seeded temperature>0 sampling.
The proposer therefore only affects THROUGHPUT (acceptance rate), never
content: any drafting strategy is sound.

Three proposers, and where each runs:

* :class:`NgramProposer` — model-free prompt-lookup decoding: find the
  most recent previous occurrence of the context's trailing n-gram and
  propose the tokens that followed it. Zero device cost, no extra
  compile footprint; wins exactly on repetitive continuations (code,
  structured text, resumed prefixes).
* :class:`DraftModelProposer` — a scaled-down same-tokenizer draft
  model running greedy decode on its OWN paged runner + block pool.
  Catch-up is incremental: the proposer tracks which token history its
  draft cache actually holds and re-feeds only the diverged tail
  (rejected drafts overwrite in place — the paged layout addresses K/V
  purely by position, so stale slots past the committed context are
  inert until rewritten).

* :class:`MtpDrafts` — the TARGET's own drafter (a multi-token-prediction
  module kept after training: ``models/interface.py::Drafter``), which runs
  inside the target's programs over the target's cache, for the whole batch at
  once: the verify window, the acceptance, the drafter over what was
  committed and the next drafts are ONE launch a step for an all-greedy
  batch, two around the host's sampler otherwise
  (``model_runner.py::launch_mtp_step``). Nothing is proposed on the host:
  this class only keeps each request's next draft between steps.

The first two run on the HOST, a slot at a time, inside the engine's
``schedule`` phase (``propose(ctx, k)``; the draft model on its own runner,
batch-1 launches a slot); the third on the device in the step itself. All
expose ``release(request_id)`` and ``compile_count()`` /
``recompiles_after_warmup()`` for the zero-recompile gate.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from ray_tpu.inference.kv_cache import PagedBlockManager


class NgramProposer:
    """Prompt-lookup drafting: propose the continuation of the most
    recent previous occurrence of the context's trailing n-gram.

    Tries the longest configured n-gram first (stronger evidence) and
    falls back to shorter ones; returns ``[]`` when nothing in the
    context repeats — the engine then runs that slot as plain decode.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if min_ngram < 1 or max_ngram < min_ngram:
            raise ValueError("need max_ngram >= min_ngram >= 1")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(
        self, ctx: Sequence[int], k: int, request_id: str = ""
    ) -> List[int]:
        L = len(ctx)
        if k <= 0 or L < self.min_ngram + 1:
            return []
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            pattern = tuple(ctx[L - n :])
            # scan right-to-left for the most recent PRIOR occurrence
            # (the trailing occurrence itself is excluded)
            for i in range(L - n - 1, -1, -1):
                if tuple(ctx[i : i + n]) == pattern:
                    return list(ctx[i + n : i + n + k])
        return []

    def release(self, request_id: str) -> None:  # stateless
        pass

    def compile_count(self) -> int:
        return 0

    def recompiles_after_warmup(self) -> int:
        return 0


class DraftModelProposer:
    """Greedy k-step drafting with a scaled-down model on its own paged
    runner. Per-request draft-cache state is tracked host-side as the
    exact token history whose K/V the draft cache holds; every propose
    call re-feeds only the diverged tail (after a rollback that is the
    rejected drafts' positions, overwritten in place)."""

    def __init__(
        self,
        cfg,
        params,
        *,
        num_blocks: int,
        block_size: int,
        prefill_buckets: Sequence[int],
        decode_buckets: Sequence[int] = (1,),
        cache_dtype=None,
    ):
        from ray_tpu.inference.model_runner import PagedModelRunner

        self.cfg = cfg
        self.runner = PagedModelRunner(
            cfg,
            params,
            num_blocks=num_blocks,
            block_size=block_size,
            prefill_buckets=prefill_buckets,
            decode_buckets=decode_buckets,
            cache_dtype=cache_dtype,
        )
        self.blocks = PagedBlockManager(num_blocks, block_size)
        #: request -> tokens whose K/V the draft cache holds at
        #: positions 0..len-1 (includes stale speculative tails until
        #: the next propose overwrites them)
        self._written: Dict[str, List[int]] = {}

    # -- warmup / compile accounting (ride the engine's gates) ----------
    def warmup(self) -> None:
        self.runner.warmup()

    def mark_warm(self) -> None:
        self.runner.mark_warm()

    def compile_count(self) -> int:
        return self.runner.compile_count()

    def recompiles_after_warmup(self) -> int:
        return self.runner.recompiles_after_warmup()

    # -- drafting -------------------------------------------------------
    def propose(self, ctx: Sequence[int], k: int, request_id: str = "") -> List[int]:
        import numpy as np

        L = len(ctx)
        if k <= 0 or L < 1:
            return []
        k = min(k, self.cfg.max_seq_len - L)
        if k <= 0:
            return []
        rid = request_id or "draft"
        held = self._written.get(rid, [])
        # longest prefix of the draft cache that is still the truth
        p = 0
        limit = min(len(held), L - 1)
        while p < limit and held[p] == ctx[p]:
            p += 1
        # decode writes K/V at positions L-1 .. L+k-2: need L-1+k covered
        if not self.blocks.grow_to(rid, L - 1 + k):
            return []  # draft pool dry: skip speculation this step
        row = self.blocks.table_row(rid, self.runner.max_blocks_per_seq)
        # catch-up prefill of the diverged tail ctx[p:L-1], bucketed
        max_chunk = self.runner.prefill_buckets[-1]
        pos = p
        while pos < L - 1:
            chunk = list(ctx[pos : min(pos + max_chunk, L - 1)])
            self.runner.prefill_chunk(chunk, row, pos)
            pos += len(chunk)
        # greedy draft decode from the last committed token
        drafts: List[int] = []
        tok = int(ctx[L - 1])
        for i in range(k):
            cur = L - 1 + i
            logits = self.runner.decode([tok], [cur], [row], [cur + 1])
            tok = int(np.argmax(logits[0]))
            drafts.append(tok)
        # cache now holds ctx[:L] plus all drafts except the last (whose
        # K/V was never written)
        self._written[rid] = list(ctx[:L]) + drafts[:-1]
        return drafts

    def release(self, request_id: str) -> None:
        rid = request_id or "draft"
        self._written.pop(rid, None)
        self.blocks.free(rid)


class MtpDrafts:
    """The next draft of each request of an engine whose model drafts for
    itself: what the step's program handed back, kept until the next step
    puts it into that slot's verify window. A request without one (its
    prefill just ended, or it was preempted and prefilled again) has no row
    of the drafter's at its last position yet: the engine then verifies the
    window one position earlier, both tokens committed."""

    def __init__(self) -> None:
        self._next: Dict[str, int] = {}

    def draft_of(self, request_id: str) -> Optional[int]:
        return self._next.get(request_id)

    def keep(self, request_id: str, draft: int) -> None:
        self._next[request_id] = int(draft)

    def release(self, request_id: str) -> None:
        self._next.pop(request_id, None)

    # the drafter's programs are the target runner's own, counted there
    def compile_count(self) -> int:
        return 0

    def recompiles_after_warmup(self) -> int:
        return 0
