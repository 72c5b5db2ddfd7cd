"""InferenceEngine: continuous-batching autoregressive generation.

One engine per replica/process. A background step-loop thread drives
``step()``: each step runs at most one prefill chunk plus the standing
decode batch (``scheduler.StepPlan``), samples the new tokens host-side,
and pushes them into per-request queues that :meth:`generate` drains —
so tokens stream to the caller WHILE other requests keep decoding. The
push waits until the NEXT launch is on its way (``_hold`` /
``_deliver_held``): the streams it wakes then run while the device does.
Where an arrival could not have had the next step's place anyway (the slots
are full or requests wait; or a running request's next prefill chunk is
already due there) the loop leaves an all-greedy decode launch unread
while it plans and launches the next step (all its programs before it waits
for any), whose rows take their tokens from that launch's result on the
device (``_stays_unread``): a plain batch's picks, or, where the model drafts
for itself, the ONE-program step's new tokens, accepted count and next draft,
which are the next window. The host's part of a step then runs beside the
device's.

Request lifecycle hooks the rest of the runtime:

* **deadlines** — ``submit`` captures the ambient ``core.deadline``
  budget (propagated onto TaskSpecs by the runtime, so a serve caller's
  timeout reaches the replica); the scheduler fails requests the step
  after their budget expires instead of decoding dead tokens.
* **drain** — ``begin_drain()`` stops admission and lets in-flight work
  finish inside ``drain_grace_s``; wired to the node DRAINING push via
  :meth:`attach_node_drain_listener` so a preemption warning on the
  replica's node stops new work without erroring live streams.
* **observability** — TTFT / tokens-per-second / cache-utilization /
  queue-depth gauges through ``observability.metrics`` and a per-step
  ``timeline`` profile event (chrome://tracing shows prefill/decode
  interleave per step).
* **deterministic continuation** — sampling is keyed on
  ``(request seed, absolute position)`` (:meth:`_sample`), so a request
  resubmitted with ``prompt + generated[:k]`` continues the identical
  token stream on ANY engine with the same params. That property is
  what the serve router's resumable-stream protocol (exactly-once token
  delivery across replica death) is built on.
* **chaos + health** — ``testing_replica_chaos`` installs a seeded
  :class:`util.chaos.ReplicaFaultPlan` consulted at the step boundary
  (kill mid-prefill/mid-decode, stall); :meth:`healthy` exposes a
  wedged-step-loop detector the serve controller polls through
  ``replica.health()``.
"""

from __future__ import annotations

import hashlib
import logging
import math
import os
import queue
import signal
import threading
import time
import uuid
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Sequence

import numpy as np

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.core.deadline import Deadline, remaining as deadline_remaining
from ray_tpu.inference.kv_cache import PagedBlockManager, _chain_digest
from ray_tpu.inference.scheduler import (
    CANCELLED,
    DECODE,
    FAILED,
    FINISHED,
    ContinuousBatchingScheduler,
    Request,
)
from ray_tpu.observability import timeline
from ray_tpu.observability import tracing as _tracing

_END = object()  # stream sentinel
#: streams a step's deliveries wake at most (a first token, an end and an
#: error go out whatever the count). A wake-up is a stream item: its consumer
#: takes the GIL to hand it to the RPC loop, which takes it to write it, and
#: the owner's acknowledgement takes it again. 32 a step cost the step thread
#: nothing it can see (8.9 ms of host under a 12 ms device step); 128 a step
#: made its 10 ms of work last 35 (PERF.md, PR 49): it queued for the GIL
#: behind the streams, and the device waited for it.
_WAKES_PER_STEP = 32

#: the step account's leaf phases (``stats()["step_phases"]["<phase>_s"]``,
#: profiler annotation ``engine.<phase>``); README "Observability". The
#: step thread's OFF-CPU seconds, what it waited (the GIL, a blocking call
#: into the runtime, the OS), are an account beside the wall clock's, the
#: thread's own and each phase's and part's under the same names:
#: ``stats()["step_offcpu"]``
STEP_PHASES = (
    "schedule", "launch", "device_wait", "readback", "sample", "emit",
    "bookkeeping", "loop_wait",
)

#: the parts of those phases (``stats()["step_parts"]["<phase>_<part>_s"]``,
#: annotation ``engine.<phase>.<part>`` nested in the phase's): accounts of
#: their own, never in a leaf; what they leave of a phase is its self time
STEP_PARTS = (
    "schedule.drain", "schedule.admit", "schedule.plan",
    "launch.rows", "launch.inputs", "launch.call",
    "readback.logits", "readback.loads",
    "emit.commit", "emit.deliver",
)

#: the blocks that call nothing which blocks below Python: their off-CPU
#: seconds can only be the GIL (or another Python thread's lock) and the OS
#: (``stats()["step_offcpu"]["unblocked_s"]``). Not ``emit.commit``: with
#: ``kv_tier_enabled``, and for a prefill export, it gathers the request's
#: blocks from the device (``_tier_writeback_full_blocks``,
#: ``_complete_prefill_export``), a transfer the thread waits for
UNBLOCKED = ("schedule.admit", "schedule.plan", "launch.rows", "launch.inputs", "sample")

logger = logging.getLogger(__name__)

# -- replica chaos (util/chaos.py::ReplicaFaultPlan) -------------------------
_RPLAN_CACHE = None
_RPLAN_CACHE_LOCK = threading.Lock()


def active_replica_fault_plan():
    """The process-wide seeded replica fault plan for
    ``testing_replica_chaos`` (or None); seed logged at activation
    (util/chaos.py::SeededPlanCache)."""
    global _RPLAN_CACHE
    if _RPLAN_CACHE is None:
        from ray_tpu.util.chaos import ReplicaFaultPlan, SeededPlanCache

        with _RPLAN_CACHE_LOCK:
            if _RPLAN_CACHE is None:
                _RPLAN_CACHE = SeededPlanCache(
                    ReplicaFaultPlan, "replica",
                    "testing_replica_chaos", "testing_replica_chaos_seed",
                    logger,
                )
    return _RPLAN_CACHE.active()


def _model_kv_namespace(model_cfg, params) -> str:
    """Model-identity namespace for cluster KV tier keys. A chain
    digest names a TOKEN prefix, not the model that computed the KV —
    and the daemon tier registry is node-global — so tier keys are
    scoped by a fingerprint of (config, weights): the model config's
    repr plus, per weight leaf, its path, shape, dtype and a
    first-elements value sample. Two deployments of the same
    architecture with different weights therefore can never serve each
    other's KV (their shapes/dtypes are identical — only the values
    differ, which is exactly what the sample catches). Replicas of ONE
    deployment agree because param init is bit-deterministic (fixed
    seed; PR 14) and checkpoint loads share bytes."""
    h = hashlib.blake2b(digest_size=8)
    h.update(repr(model_cfg).encode())
    try:
        import jax

        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
            sample = np.ascontiguousarray(np.asarray(leaf.reshape(-1)[:4]))
            h.update(str(path).encode())
            h.update(str(np.shape(leaf)).encode())
            h.update(str(sample.dtype).encode())
            h.update(sample.tobytes())
    except Exception:  # noqa: BLE001 — the config repr alone still scopes
        pass
    return h.hexdigest()


def _stable_request_seed(request_id: str) -> int:
    """Process-independent sampling seed derived from a request id.
    ``hash()`` is salted per interpreter (PYTHONHASHSEED), which would
    make a request resumed on another replica sample a DIFFERENT stream
    — breaking exactly-once token delivery for unseeded requests."""
    return int.from_bytes(
        hashlib.blake2b(request_id.encode(), digest_size=8).digest(), "little"
    )


class EngineDrainingError(RuntimeError):
    """New request rejected because the engine is draining."""


class RequestFailedError(RuntimeError):
    """The engine gave up on a request (deadline expiry, drain cutoff)."""


#: string marker the serve router's resumable-stream failover matches on
#: (the exception type itself may be re-raised under a different class
#: after crossing the actor boundary — the message survives any wrapper);
#: defined in jax-free kv_transfer so routers can match it without
#: importing the engine
from ray_tpu.inference.kv_transfer import KV_MIGRATION_MARKER  # noqa: E402


class KvMigrationHandoff(RequestFailedError):
    """A draining replica flushed this in-flight request's FULL KV
    (prompt + generated) into the cluster tier and handed the stream
    back: the router resumes it on a survivor, which faults the KV in
    instead of re-prefilling — client-invisible through the SeqGate."""


@dataclass
class _Windows:
    """What the host knows of the rows of a drafter's step, in row order."""

    #: each window as it was launched: ``[x, d]``, ``[x]`` where no draft
    #: rides, ``[-1 - j, ..]`` where it is what row ``j`` of the unread step
    #: before leaves on the device
    tokens: List[List[int]]
    #: how many of a window's tokens are committed (2: a slot without a draft yet)
    known: List[int]
    #: the drafts that ride each window; None: ONE rides whose value was on
    #: the device at the launch (a named row), known once that step is read
    drafts: List[Optional[List[int]]]
    #: each row's block table and the context length its window starts at (of a
    #: named row the least: the program adds what the step before accepted)
    rows: List[Any]
    ctxs: List[int]

    def riding(self, row: int) -> int:
        drafts = self.drafts[row]
        return 1 if drafts is None else len(drafts)


@dataclass
class _DecodeBatch:
    """A decode launch and the requests of its rows, in row order: a plain
    decode batch, or the step of a model that drafts for itself."""

    reqs: List[Request]
    #: the runner's handle (``model_runner.Launched``): what a read waits for
    launched: Any
    #: every request greedy: the read gives the device's picks, not logits
    #: (a drafter's step: its ONE program ran, not the two around the sampler)
    greedy: bool
    #: a drafter's step: its rows' windows (None: a plain decode launch)
    windows: Optional[_Windows] = None
    #: left unread for the chunk due in the next step alone: the engine had
    #: room (:meth:`InferenceEngine._next_step_taken_by`)
    chunk_due: bool = False


@dataclass
class EngineConfig:
    """Knobs for the paged-KV continuous-batching engine (see README
    "inference" section)."""

    #: device block pool size (block 0 is the reserved null block): the pool
    #: of the layer group that keeps a sequence whole. A group that keeps a
    #: window (``models/interface.py::LayerGroup``) has a pool beside it whose
    #: size follows from the fields here (``InferenceEngine._window_pools``)
    num_blocks: int = 128
    #: token positions per block
    block_size: int = 16
    #: prefill chunk-length buckets; one XLA program compiles per bucket.
    #: None → derived from the model's max_seq_len (powers of two).
    prefill_buckets: Optional[Sequence[int]] = None
    #: decode batch-size buckets; None → (1, 2, 4, ..., max_decode_batch)
    decode_buckets: Optional[Sequence[int]] = None
    max_decode_batch: int = 8
    #: prefill chunks per engine step (prefill rides WITH the decode batch)
    max_prefills_per_step: int = 1
    #: admission queue bound: submits beyond this fail fast
    max_queue_depth: int = 128
    #: compile every bucket at startup so serving never eats a compile
    warmup: bool = True
    #: default cap on generated tokens per request
    max_new_tokens_default: int = 64
    #: KV cache dtype override (None → model dtype)
    cache_dtype: Any = None
    #: reap finished-but-never-drained token streams after this long; a
    #: caller that submits and walks away (own deadline hit, gave up
    #: after a tokens() timeout without cancel()) would otherwise pin its
    #: queue in the replica forever. <= 0 disables.
    finished_stream_ttl_s: float = 300.0
    #: healthy() reports False once there is pending work but the step
    #: loop hasn't completed an iteration for this long — a wedged step
    #: thread (stuck device call, injected stall) in a replica whose
    #: actor loop still answers RPCs. The serve controller polls this
    #: through replica.health() and restarts the replica. <= 0 disables
    #: the staleness check (thread liveness is still checked).
    step_stall_unhealthy_s: float = 10.0
    #: prefix caching (kv_cache.py): full blocks are indexed by token
    #: chain-hash and SHARED with later requests whose prompt prefix
    #: matches — those skip the covered prefill chunks entirely (the
    #: warm-TTFT path for fleets of conversations sharing one system
    #: prompt). Numerically inert: shared KV values are exactly what an
    #: uncached prefill would have written.
    prefix_cache_enabled: bool = True
    #: cap on indexed blocks (0 = bounded only by the pool; unreferenced
    #: cached blocks are reclaimed LRU-first whenever allocation needs
    #: them, so the cache never starves admission)
    prefix_cache_max_blocks: int = 0
    #: KV-cache migration (disaggregated prefill/decode serving): opts
    #: this engine into the block gather/scatter programs — compiled at
    #: warmup so migrations never recompile — and the export/import
    #: request modes (prefill_kv / import_kv_blocks). Off by default so
    #: plain deployments keep their exact compile count.
    kv_transfer_enabled: bool = False
    #: cluster-wide KV prefix tier (kv_transfer.py tier layer): write
    #: popular full prefix blocks back into daemon-owned shm storage
    #: (explicitly at prefill/decode block boundaries, and as the SPILL
    #: half of the eviction spill-vs-drop policy), advertise them
    #: through the routing gossip, and serve warm recovery — resume via
    #: fault-in, warm replica restart, drain-time live migration.
    #: Implies the gather/scatter programs (kv_transfer warmup). Off by
    #: default so plain deployments keep their exact compile count.
    kv_tier_enabled: bool = False
    #: speculative decoding (inference/speculative.py): drafts proposed
    #: per decode slot and verified in ONE bucketed jitted target step
    #: (models.llama.paged_verify_step). 0 disables — plain deployments
    #: keep their exact compile count (no verify bucket, no draft
    #: runner). Acceptance is exact-match against the engine's own
    #: deterministic (seed, absolute-position) sampler, so the emitted
    #: stream is byte-identical to non-speculative decode and the
    #: resumable-stream contract survives unchanged.
    speculative_k: int = 0
    #: draft mode, three proposers: "ngram" (model-free prompt-lookup
    #: decoding on the host, a slot at a time, zero device cost), "model" (a
    #: scaled-down same-tokenizer draft model on its OWN paged runner and
    #: pool, batch-1 launches a slot; requires draft_config) or "mtp" (the
    #: target's own multi-token-prediction module, ``Model.drafter``: it runs
    #: inside the TARGET's step program for the whole batch, and decode is
    #: that program; speculative_k must be the module's depth)
    speculative_draft: str = "ngram"
    #: LlamaConfig for speculative_draft="model" (same vocab as the
    #: target); ignored for "ngram"
    draft_config: Any = None
    #: draft model params (None → deterministic init from draft_config
    #: with draft_seed)
    draft_params: Any = None
    draft_seed: int = 0
    #: draft runner pool/buckets (0/None → scaled from the engine's own)
    draft_num_blocks: int = 0
    draft_prefill_buckets: Optional[Sequence[int]] = None
    #: adaptive k: the 4 Hz gauge refresh shrinks the live draft budget
    #: toward 1 while the windowed acceptance rate sits below the floor,
    #: and grows it back toward speculative_k while acceptance is high —
    #: the verify bucket stays fixed at speculative_k+1 (shorter windows
    #: pad via true_len), so adaptation never recompiles
    speculative_adaptive: bool = True
    speculative_accept_floor: float = 0.35
    #: prompt-lookup n-gram sizes for speculative_draft="ngram"
    ngram_max: int = 3
    ngram_min: int = 1

    def resolved_verify_buckets(self) -> Sequence[int]:
        """One verify bucket, sized for the full draft budget: k+1
        window positions (last committed token + k drafts); shorter
        windows (adaptive shrink, tail-of-request clamps) pad into it
        via true_len instead of compiling new shapes."""
        if self.speculative_k <= 0:
            return ()
        return (self.speculative_k + 1,)

    def resolved_prefill_buckets(self, max_seq_len: int) -> Sequence[int]:
        if self.prefill_buckets is not None:
            return tuple(sorted(self.prefill_buckets))
        out, b = [], 16
        while b < max_seq_len:
            out.append(b)
            b *= 2
        out.append(max_seq_len)
        return tuple(out)

    def resolved_decode_buckets(self) -> Sequence[int]:
        if self.decode_buckets is not None:
            return tuple(sorted(self.decode_buckets))
        out, b = [], 1
        while b < self.max_decode_batch:
            out.append(b)
            b *= 2
        out.append(self.max_decode_batch)
        return tuple(sorted(set(out)))


# -- engine metrics (registered once per process; re-registration of the
# same names returns the shared underlying metric) --------------------------


def _engine_metrics():
    from ray_tpu.observability.metrics import Counter, Gauge
    from ray_tpu.observability.slo import slo_metrics
    from ray_tpu.observability import rpc_metrics

    slo = slo_metrics()
    return {
        # SLO-ledger sinks (observability/slo.py): aggregatable
        # log-bucket histograms + goodput/fault-cost counters, labeled
        # {deployment, tenant_class}. raytpu_llm_ttft_seconds used to be
        # a per-engine quantile GAUGE — mathematically un-aggregatable
        # across a /federate scrape; the histogram replaces it.
        "ttft": slo["ttft"],
        "itl": slo["itl"],
        "e2e": slo["e2e"],
        "goodput": slo["goodput"],
        "fault": slo["fault"],
        "deadline": slo["deadline"],
        "tps": Gauge(
            "raytpu_llm_tokens_per_s",
            "decode throughput over the trailing window",
        ),
        "cache_util": Gauge(
            "raytpu_llm_kv_cache_utilization",
            "fraction of usable KV blocks currently allocated",
        ),
        "queue_depth": Gauge(
            "raytpu_llm_queue_depth", "requests waiting for admission"
        ),
        "active": Gauge("raytpu_llm_active_requests", "admitted, unfinished"),
        "decode_batch": Gauge(
            "raytpu_llm_decode_batch_size", "slots in the last decode step"
        ),
        "tokens_total": Counter(
            "raytpu_llm_tokens_generated_total", "tokens sampled"
        ),
        "requests_total": Counter(
            "raytpu_llm_requests_total", "requests by terminal state", ("outcome",)
        ),
        "preemptions_total": Counter(
            "raytpu_llm_preemptions_total", "requests evicted for blocks"
        ),
        "prefix_hits_total": Counter(
            "raytpu_llm_prefix_hits_total",
            "admissions that reused cached prefix blocks",
        ),
        "prefix_tokens_saved_total": Counter(
            "raytpu_llm_prefix_tokens_saved_total",
            "prompt tokens whose prefill was skipped via the prefix cache",
        ),
        "cow_copies_total": Counter(
            "raytpu_llm_cow_copies_total",
            "copy-on-write block duplications (full-prompt cache hits)",
        ),
        # speculative decoding (defined in rpc_metrics so every process
        # that imports the transport layer exports consistent help text;
        # referencing them here puts them on the engine /metrics path
        # and under the catalog lint)
        "spec_proposed": rpc_metrics.LLM_SPEC_PROPOSED,
        "spec_accepted": rpc_metrics.LLM_SPEC_ACCEPTED,
        "spec_rollbacks": rpc_metrics.LLM_SPEC_ROLLBACKS,
        "spec_acceptance": rpc_metrics.LLM_SPEC_ACCEPTANCE,
    }


class InferenceEngine:
    def __init__(self, model_cfg, params, engine_cfg: Optional[EngineConfig] = None):
        from ray_tpu.inference.model_runner import PagedModelRunner

        self.cfg = model_cfg
        self.engine_cfg = ec = engine_cfg or EngineConfig()
        decode_buckets = ec.resolved_decode_buckets()
        if ec.max_decode_batch > max(decode_buckets):
            # catching this at runtime instead means _round_up_bucket
            # raises inside step() and _fail_all errors every in-flight
            # request, repeatedly — fail loud at init instead
            raise ValueError(
                f"max_decode_batch={ec.max_decode_batch} exceeds the largest "
                f"decode bucket {max(decode_buckets)}; add a bucket >= the "
                "batch cap or lower max_decode_batch"
            )
        #: model-identity namespace scoping this engine's tier keys
        #: (REVIEW: the digest names tokens, the daemon registry is
        #: node-global — unscoped, one model could serve another's KV).
        #: Computed BEFORE runner construction: donation may invalidate
        #: the params tree the fingerprint samples.
        self._tier_ns = ""
        if ec.kv_tier_enabled:
            self._tier_ns = GLOBAL_CONFIG.kv_tier_namespace or _model_kv_namespace(
                model_cfg, params
            )
        #: the step account: where the step-loop thread's time goes, phase
        #: by phase; _step() hands it to the runner's calls, which add
        #: their launch / device_wait / readback
        self._clock = timeline.PhaseClock("engine", STEP_PHASES, STEP_PARTS)
        state_slots = self._state_slots(model_cfg, ec)
        self._refuse_payloads(model_cfg, ec)
        #: whether the model drafts for itself (speculative_draft "mtp")
        self._mtp = self._drafts_for_itself(model_cfg, ec)
        #: the pools beside the first: ``(name, num_blocks, keeps)`` of each
        #: layer group that keeps a window (none for a model of one group)
        windows = self._window_pools(model_cfg, ec)
        self.runner = PagedModelRunner(
            model_cfg,
            params,
            num_blocks=(ec.num_blocks, *(n for _, n, _ in windows)),
            block_size=ec.block_size,
            prefill_buckets=ec.resolved_prefill_buckets(model_cfg.max_seq_len),
            decode_buckets=decode_buckets,
            verify_buckets=ec.resolved_verify_buckets(),
            cache_dtype=ec.cache_dtype,
            state_slots=state_slots,
            drafter=self._mtp,
        )
        #: the start-up account, written once while the replica comes up
        #: (LLMServer adds what it alone sees: the weights, and the whole
        #: of its __init__)
        self.startup: Dict[str, Any] = {
            "cache_alloc_s": self.runner.cache_alloc_s,
            "warmup_s": 0.0,
            "warmup_programs": self.runner.warmup_programs,
        }
        #: the request account: at each first token, the engine's own TTFT
        #: split into its three waits (sums over requests; the reader
        #: differences them and divides by first_tokens)
        self._request_stages = {
            "first_tokens": 0, "queue_s": 0.0, "prefill_wait_s": 0.0,
            "prefill_run_s": 0.0,
        }
        self.blocks = PagedBlockManager(
            ec.num_blocks,
            ec.block_size,
            # no state snapshot a block yet: a model with per-sequence state
            # takes no prefix hit (:meth:`_state_slots`)
            prefix_cache_enabled=ec.prefix_cache_enabled and not state_slots and not windows,
            prefix_cache_max_blocks=ec.prefix_cache_max_blocks,
            state_slots=state_slots,
            group=self.runner.cache_layout.groups[0].name,
            windows=windows,
        )
        #: running sums a decode launch: the block-layers out of the pools, and
        #: those ONE table a request would hold for the same sequences (every
        #: layer, every position: the first group's blocks times every layer)
        self._kv_held = dict.fromkeys(("launches", "held_block_layers", "one_table_block_layers"), 0)
        self.scheduler = ContinuousBatchingScheduler(
            self.blocks,
            max_decode_batch=ec.max_decode_batch,
            max_prefill_chunk=max(ec.resolved_prefill_buckets(model_cfg.max_seq_len)),
            max_prefills_per_step=ec.max_prefills_per_step,
            max_queue_depth=ec.max_queue_depth,
        )
        self._out: Dict[str, queue.Queue] = {}
        #: emit commits, the wake-ups wait: every item the step thread has
        #: for a request's out-queue goes here as (queue, item, committed
        #: at), in order, and :meth:`_deliver_held` puts them once the next
        #: launch is on its way (or there is none to wait for). The woken
        #: consumers then want the GIL while the device runs and this
        #: thread waits, not while it launches.
        self._held: List[tuple] = []
        self._wakes_left = _WAKES_PER_STEP
        #: orders appends and deliveries: cancel() and stop() finish
        #: requests from their callers' threads
        self._held_lock = threading.Lock()
        #: every item delivered, by where (monotonic; stats()["wakes"]):
        #: after a launch, at a step with nothing to launch, or directly (a
        #: step() from outside the loop, cancel, stop, a failure); held_s
        #: sums delivery instant - commit instant over the items
        self._wakes = {
            "items": 0, "after_launch": 0, "at_idle": 0, "direct": 0, "held_s": 0.0,
        }
        #: the one decode launch the step loop has not read yet (None: every
        #: launch is read), a plain batch or a drafter's step: its rows' tokens
        #: are in flight while the next step is planned and launched
        #: (:meth:`_stays_unread`)
        self._unread: Optional[_DecodeBatch] = None
        #: plain decode launches, those launched while the one before was
        #: unread, and results dropped before they reached a stream
        #: (monotonic; stats()["decode_ahead"])
        self._decode_ahead = {"launches": 0, "ahead": 0, "dropped": 0}
        #: of ``ahead``, the launches behind one that a due chunk alone left
        #: unread: the engine had room (stats()["decode_ahead_chunk_due"])
        self._ahead_chunk_due = 0
        # request id -> submitter's (trace_id, span_id): the step-loop
        # thread stamps per-request spans (admission→first-token,
        # admission→finish) under the serve caller's trace
        self._trace_ctx: Dict[str, tuple] = {}
        self._submitted_at: Dict[str, float] = {}
        self._first_token_at: Dict[str, float] = {}
        self._finished_at: Dict[str, float] = {}
        self._next_stream_reap = 0.0
        self._next_gauge_refresh = 0.0
        self._lock = threading.RLock()
        self._work = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._draining = False
        self._drain_deadline: Optional[Deadline] = None
        self._listener_backend = None
        self._node_listener = None
        #: step-loop heartbeat consumed by healthy(): stamped once per
        #: loop iteration, so a step wedged inside device code (or an
        #: injected stall) goes stale while the actor loop stays live
        self._last_beat = time.monotonic()
        #: per-engine fault-plan override (tests arm ONE replica
        #: surgically); None falls through to the env/config plan
        self.testing_fault_plan = None
        self.metrics = _engine_metrics()
        from ray_tpu.observability.slo import BucketCounts

        #: per-ENGINE TTFT tape (the process-registry histogram is shared
        #: by every engine in the process — tests host several): backs
        #: the stats()["ttft"] p50/p99 back-compat shape
        self._ttft_tape = BucketCounts()
        #: deployment label for the SLO series; serve/replica.py stamps
        #: it via LLMServer.set_deployment_name ("" for bare engines)
        self.slo_deployment = ""
        #: intake books — with the scheduler's queued/running counts,
        #: submitted == finished + failed + cancelled + in_flight holds
        #: exactly at quiesce (slo.books_balanced), the conservation gate
        #: fault paths are reconciled against
        self._books = {"submitted": 0, "finished": 0, "failed": 0, "cancelled": 0}
        self._token_times: deque = deque(maxlen=2048)
        #: recent (monotonic, value) latency samples backing the gossiped
        #: closed-loop signals (routing_stats ttft_p99_s / itl_p99_s).
        #: The ledger tapes above are LIFETIME histograms — an autopilot
        #: steering on them would barely feel current burn, so the
        #: control signals come from a sliding window instead.
        self._recent_ttfts: deque = deque(maxlen=512)
        self._recent_itls: deque = deque(maxlen=2048)
        #: (monotonic, n_tokens) per prefill pass — windowed prefill
        #: throughput for the disagg pool-ratio adaptation
        self._prefill_token_times: deque = deque(maxlen=2048)
        self._preempt_seen = 0
        self._replay_seen = 0
        self._prefix_seen: Dict[str, int] = {}
        #: queued KV-import jobs, executed BY the step thread at the top
        #: of each step — device cache mutation must never race the step
        #: loop's own cache swaps (donation on TPU invalidates the buffer
        #: a concurrent reader grabbed)
        self._kv_imports: "queue.Queue" = queue.Queue()
        # -- cluster KV tier (PR 17) --
        #: tier adverts this replica gossips: digest hex -> routable
        #: descriptor, MRU-capped at kv_tier_max_adverts. Dropping an
        #: entry here IS the retraction signal — routers diff advert
        #: sets per report and purge in one gossip hop.
        self._tier_adverts: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        #: guards _tier_adverts and _tier_pending: mutated by the step
        #: thread AND the tier publisher thread, snapshotted by
        #: routing_stats on the actor thread — an unlocked OrderedDict
        #: move_to_end/popitem races "mutated during iteration" there
        self._tier_lock = threading.Lock()
        #: digests queued for background publish (dedup vs re-enqueue)
        self._tier_pending: set = set()
        #: (digest, host kv, trigger) handed to the tier publisher
        #: thread. Gathers stay ON the step thread (device cache reads
        #: must not race donation) but the publish — shm write + daemon
        #: RPC with a 10s timeout — must come OFF it: a wedged daemon
        #: would otherwise stall token emission for the whole batch at
        #: every block boundary. Bounded: overflow drops the write-back
        #: (best-effort warmth, never backpressure on decode).
        self._tier_pub_q: "queue.Queue" = queue.Queue(maxsize=256)
        self._tier_pub_thread: Optional[threading.Thread] = None
        #: (digest, host kv) spills gathered under the block-manager
        #: lock, published by the step thread OUTSIDE it (publish does
        #: shm writes + daemon RPC — too heavy for an allocation path)
        self._tier_spill_pending: List[tuple] = []
        #: drain-with-migration latch (begin_drain(migrate=True))
        self._migrate_on_drain = False
        if ec.kv_tier_enabled:
            self.blocks.set_spill_hook(self._tier_spill)
        # -- speculative decoding (PR 19) --
        #: the draft proposer (None when disabled). Only constructed for
        #: speculative_k > 0, so plain engines keep their exact compile
        #: count — the verify jit exists but holds zero cache entries.
        self.spec = None
        #: lifetime propose/accept/rollback books (stats() + adaptive k)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rollbacks = 0
        #: a model that drafts for itself: slots x steps through its step that
        #: were read AND committed, the tokens they committed (1 to 2 a
        #: slot-step), the steps, which of them took the ONE program (an
        #: all-greedy batch) or the two around the sampler, those launched while
        #: the step before was unread (its result handed over on the device),
        #: and the rows dropped before they reached a stream
        self._mtp_counts = {
            "slot_steps": 0, "committed_tokens": 0, "step_launches": 0, "launches_fused": 0,
            "launches_split": 0, "launches_ahead": 0, "rows_dropped": 0,
        }
        #: (proposed, accepted) snapshot at the last gauge refresh — the
        #: adaptive controller steers on the window delta, not lifetime
        self._spec_window_seen = (0, 0)
        self._spec_acceptance = 0.0
        if ec.speculative_k > 0:
            from ray_tpu.inference.speculative import (
                DraftModelProposer,
                MtpDrafts,
                NgramProposer,
            )

            if self._mtp:
                self.spec = MtpDrafts()
            elif ec.speculative_draft == "model":
                if ec.draft_config is None:
                    raise ValueError(
                        "speculative_draft='model' requires draft_config"
                    )
                draft_params = ec.draft_params
                if draft_params is None:
                    import jax

                    from ray_tpu.models.interface import model_of

                    draft_params = model_of(ec.draft_config).init_params(
                        ec.draft_config, jax.random.PRNGKey(ec.draft_seed)
                    )
                self.spec = DraftModelProposer(
                    ec.draft_config,
                    draft_params,
                    num_blocks=ec.draft_num_blocks or ec.num_blocks,
                    block_size=ec.block_size,
                    prefill_buckets=(
                        tuple(ec.draft_prefill_buckets)
                        if ec.draft_prefill_buckets is not None
                        else ec.resolved_prefill_buckets(
                            ec.draft_config.max_seq_len
                        )
                    ),
                    cache_dtype=ec.cache_dtype,
                )
            elif ec.speculative_draft == "ngram":
                self.spec = NgramProposer(
                    max_ngram=ec.ngram_max, min_ngram=ec.ngram_min
                )
            else:
                raise ValueError(
                    f"unknown speculative_draft {ec.speculative_draft!r} "
                    "(expected 'ngram', 'model' or 'mtp')"
                )
            self.scheduler.spec_max_context = model_cfg.max_seq_len
            self.scheduler.spec_k_live = ec.speculative_k
            self.scheduler.spec_drafts_on_device = self._mtp
        self.total_steps = 0
        if ec.warmup:
            t0 = time.perf_counter()
            self.runner.warmup(kv_io=ec.kv_transfer_enabled or ec.kv_tier_enabled)
            if self.spec is not None and hasattr(self.spec, "warmup"):
                self.spec.warmup()
            self.startup["warmup_s"] = time.perf_counter() - t0
        else:
            self.runner.mark_warm()
            if self.spec is not None and hasattr(self.spec, "mark_warm"):
                self.spec.mark_warm()

    # -- lifecycle --------------------------------------------------------
    @staticmethod
    def _state_slots(model_cfg, ec: "EngineConfig") -> int:
        """State slots this engine's pool holds: 0 for a model whose layers
        all attend. A model with recurrent layers (``Model.state_layout``)
        gets ``max_decode_batch`` of them (every running sequence holds one
        from admission on, and no more run at a time), and every feature
        that would move a sequence's rows WITHOUT its state (export / import,
        the tier, a verify window) is refused here, with the reason, instead
        of answering wrongly. Prefix reuse, which is on by default, is
        switched OFF for such a model where the block manager is made (no
        block is indexed, no hit is taken: ``stats()["prefix_cache"]
        ["enabled"]`` says so), because a hit would skip prefill over tokens
        whose state nobody kept."""
        from ray_tpu.models.interface import model_of

        model = model_of(model_cfg)
        if model.state_layout is None:
            return 0
        why = (
            f"a {model.name} model keeps a per-sequence state in its recurrent layers beside "
            "the rows a token: "
        )
        refused = {
            "kv_transfer_enabled": (ec.kv_transfer_enabled,
                "an exported prompt's blocks carry no state, so the importer would decode from zeros"),
            "kv_tier_enabled": (ec.kv_tier_enabled,
                "tier write-back and resume move blocks by their tokens' digest, without the state"),
            "speculative_k": (ec.speculative_k > 0,
                "a verify window needs the state after each of its positions for the roll-back"),
        }
        for field, (on, reason) in refused.items():
            if on:
                raise ValueError(f"{field} cannot run here: {why}{reason}")
        return ec.max_decode_batch

    @staticmethod
    def _window_pools(model_cfg, ec: "EngineConfig") -> tuple:
        """``(name, num_blocks, keeps)`` of each layer group of the model's
        cache that keeps a window (``CacheLayout.groups`` after the first):
        none for a model of one group. Every feature that names a block by
        its tokens, or rolls a sequence back (export / import, the tier, a
        verify window), is refused here with the reason instead of answering
        wrongly: blocks behind a window are given back while the sequence
        runs, so the tokens of a prefix no longer say which rows are held.
        Prefix reuse, which is on by default, is switched OFF for such a model
        where the block manager is made, as for a model with a state pool (a
        hit would skip prefill over positions whose window rows were
        released; the manager itself refuses the two together). The pool's
        size is no option: it follows from the decode batch, the window, the
        largest chunk and the block size."""
        from ray_tpu.models.interface import model_of

        model = model_of(model_cfg)
        groups = model.cache_layout(model_cfg, ec.block_size, ec.cache_dtype).groups
        if len(groups) < 2:
            return ()
        if groups[0].keeps or not all(g.keeps for g in groups[1:]):
            raise ValueError(
                f"a {model.name} model's cache groups {[(g.name, g.keeps) for g in groups]} cannot be "
                "run: the first group keeps a sequence whole, every other a window"
            )
        why = (
            f"a {model.name} model of this configuration has layers that keep the last "
            f"{[g.keeps for g in groups[1:]]} positions alone and give back the blocks behind: "
        )
        refused = {
            "kv_transfer_enabled": (ec.kv_transfer_enabled,
                "a block's digest says nothing of the window rows no longer held, so the importer would attend over nulls"),
            "kv_tier_enabled": (ec.kv_tier_enabled,
                "tier write-back and resume move blocks by their tokens' digest, whatever was released"),
            "speculative_k": (ec.speculative_k > 0,
                "a rejected tail would have to come back from behind a released block"),
        }
        for field, (on, reason) in refused.items():
            if on:
                raise ValueError(f"{field} cannot run here: {why}{reason}")
        # what the pool must hold so that no step of a full decode batch waits
        # on it: every slot its window (and a block where the window starts
        # inside one), and a largest chunk for each request between its chunks:
        # the ones this step prefills, and the one whose last chunk ran and
        # whose first decode step has not slid it yet
        bs = ec.block_size
        chunk = -(-max(ec.resolved_prefill_buckets(model_cfg.max_seq_len)) // bs)
        return tuple(
            (g.name, 1 + ec.max_decode_batch * (-(-g.keeps // bs) + 1) + (ec.max_prefills_per_step + 1) * chunk,
             g.keeps)
            for g in groups[1:]
        )

    @staticmethod
    def _refuse_payloads(model_cfg, ec: "EngineConfig") -> None:
        """A model whose token leaves rows of DIFFERENT widths in a layer (a
        latent row and an indexer's key: ``CacheLayout.one_payload`` false)
        has no stacked payload of a block: what would ship one (export and
        import, the tier) is refused here, with the reason."""
        from ray_tpu.models.interface import model_of

        model = model_of(model_cfg)
        layout = model.cache_layout(model_cfg, ec.block_size, ec.cache_dtype)
        if layout.one_payload:
            return
        rows = ", ".join(f"{name} {math.prod(shape)}" for name, shape in layout.arrays)
        for field in ("kv_transfer_enabled", "kv_tier_enabled"):
            if getattr(ec, field):
                raise ValueError(
                    f"{field} cannot run here: a token of a {model.name} model leaves rows of different "
                    f"widths in a layer ({rows}), and a block's payload is ONE stacked array"
                )

    @staticmethod
    def _drafts_for_itself(model_cfg, ec: "EngineConfig") -> bool:
        """Whether decode is the model's own drafter's step (``speculative_draft``
        ``"mtp"`` with ``speculative_k`` > 0). What that drafter cannot carry
        yet is refused here, with the reason, instead of answering wrongly or
        drafting from rows nobody wrote: its row at a position is made with
        the token AFTER it, so a block shared through the prefix cache ends
        on a row made with another request's continuation; an exported or
        tiered prompt leaves before its last position's row exists (that row
        waits for the first output token), and the importer's first step
        would not know."""
        if ec.speculative_k <= 0 or ec.speculative_draft != "mtp":
            return False
        from ray_tpu.models.interface import model_of

        model = model_of(model_cfg)
        drafter = model.drafter(model_cfg) if model.drafter else None
        if drafter is None:
            raise ValueError(
                f"speculative_draft='mtp' needs a model with a drafter of its own: a {model.name} "
                "model of this configuration keeps none"
            )
        if ec.speculative_k != drafter.window - 1:
            raise ValueError(
                f"speculative_k={ec.speculative_k} cannot run here: the {drafter.kind} drafter of this "
                f"model drafts {drafter.window - 1} token(s) a step"
            )
        why = f"a {model.name} model's {drafter.kind} drafter writes a row a token beside the model's own, made with the NEXT token: "
        refused = {
            "prefix_cache_enabled": (ec.prefix_cache_enabled,
                "a shared block's last row was made with another request's continuation (set it to False)"),
            "kv_transfer_enabled": (ec.kv_transfer_enabled,
                "an exported prompt leaves before its last position's row is written"),
            "kv_tier_enabled": (ec.kv_tier_enabled,
                "tier write-back and resume move blocks by their tokens' digest, whatever followed them"),
        }
        for field, (on, reason) in refused.items():
            if on:
                raise ValueError(f"{field} cannot run here: {why}{reason}")
        return True

    def start(self) -> "InferenceEngine":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="llm-engine-step"
            )
            self._thread.start()
        if self.engine_cfg.kv_tier_enabled:
            if self._tier_pub_thread is None or not self._tier_pub_thread.is_alive():
                self._tier_pub_thread = threading.Thread(
                    target=self._tier_publish_loop,
                    daemon=True,
                    name="llm-engine-tier-pub",
                )
                self._tier_pub_thread.start()
            self._tier_recover()
        return self

    def _tier_recover(self) -> None:
        """Warm-restart half of the tier: the local daemon's registry
        survived whatever killed the previous replica process — re-adopt
        its entries as OUR adverts so the very next gossip beat makes
        this replacement routable as prefix-warm. Failover stall then
        ≈ fault-in pull latency, not a cold prefill. Filtered to OUR
        model namespace: the registry is node-global, and re-adverting
        another deployment's entries would route its KV to our model."""
        try:
            from ray_tpu.inference import kv_transfer

            entries = kv_transfer.tier_list(ns=self._tier_ns)
        except Exception:  # noqa: BLE001 — recovery is best-effort
            return
        cap = max(1, GLOBAL_CONFIG.kv_tier_max_adverts)
        with self._tier_lock:
            for digest_hex, desc in entries.items():
                if len(self._tier_adverts) >= cap:
                    break
                self._tier_adverts[digest_hex] = desc

    def stop(self) -> None:
        self._stop.set()
        self._work.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._tier_pub_thread is not None:
            self._tier_pub_thread.join(timeout=10)
            self._tier_pub_thread = None
        # the step loop is dead: queued/running requests can never emit
        # another token — fail them so callers blocked in tokens() wake
        # instead of hanging on q.get() forever
        self._fail_all(RequestFailedError("engine stopped"))
        # parked KV importers would likewise wait on a thread that will
        # never run their job again
        while True:
            try:
                _tokens, _kv, reply = self._kv_imports.get_nowait()
            except queue.Empty:
                break
            reply.put((False, RequestFailedError("engine stopped")))
        self.detach_node_drain_listener()

    def _loop(self) -> None:
        clock = self._clock
        while not self._stop.is_set():
            self._last_beat = time.monotonic()
            did_work = False
            try:
                # settles its own part of the account; what it commits is
                # woken after the next step's first launch
                did_work = self.step(hold_wakes=True)
            except Exception as e:  # noqa: BLE001 — fail in-flight, keep serving
                self._fail_all(e)
                if any(a.is_deleted() for a in self.runner.cache.values()):
                    # the step died AFTER its jit call consumed the donated
                    # cache: every later step would fail on a deleted
                    # buffer. Stop the loop — healthy() turns False and the
                    # serve controller replaces the replica.
                    logger.exception("engine step lost the donated KV cache; stopping")
                    self._stop.set()
                    return
            with clock.phase("bookkeeping"):
                self._reap_abandoned_streams()
            if not did_work:
                with clock.phase("loop_wait"):
                    self._work.wait(timeout=0.005)
                    self._work.clear()
            # from where step() settled to here: the loop's own overhead
            clock.settle(
                clock.settled_at, "bookkeeping" if did_work else "loop_wait", rated=False
            )

    # -- submission -------------------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int] = None,
        temperature: float = 0.0,
        priority: int = 0,
        eos_token: Optional[int] = None,
        request_id: Optional[str] = None,
        seed: Optional[int] = None,
        timeout_s: Optional[float] = None,
        prefill_only: bool = False,
        tenant_class: str = "",
        ledger_stages: Optional[Dict[str, float]] = None,
        record_slo: bool = True,
        speculative: Optional[bool] = None,
    ) -> str:
        """Enqueue a generation request; returns its id. The ambient
        ``core.deadline`` budget (or explicit ``timeout_s``, whichever is
        tighter) bounds the request end to end. ``prefill_only`` is the
        KV-migration export mode (use :meth:`prefill_kv`, which also
        drains the payload). ``tenant_class`` labels the SLO histograms;
        ``ledger_stages`` carries stage durations measured upstream
        (e.g. the KV import that ran before this submit);
        ``record_slo=False`` keeps a resume attempt's warm-replay
        latencies out of the SLO histograms (see Request.record_slo).
        ``speculative`` is the per-request off-switch: False forces
        plain decode for this request even on a speculative engine
        (True/None follow the engine config — output bytes are
        identical either way, only throughput changes)."""
        if self._draining or not self.scheduler.admitting:
            raise EngineDrainingError("engine is draining: not admitting requests")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens is None:
            max_new = self.engine_cfg.max_new_tokens_default
        else:
            max_new = int(max_new_tokens)
            if max_new < 1:
                raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        # clamp so prompt + generation always fits the block-table width
        room = self.cfg.max_seq_len - len(prompt)
        if room < 1:
            raise ValueError(
                f"prompt of {len(prompt)} tokens >= max_seq_len {self.cfg.max_seq_len}"
            )
        max_new = min(max_new, room)
        rid = request_id or uuid.uuid4().hex[:16]
        if temperature > 0.0 and seed is None:
            # resolve ONCE, stably: sampling is keyed on (seed, position)
            # so a resumed/replayed request re-derives the identical
            # stream from its id alone (see _sample / _stable_request_seed)
            seed = _stable_request_seed(rid)
        budget = deadline_remaining()
        if timeout_s is not None:
            budget = timeout_s if budget is None else min(budget, timeout_s)
        req = Request(
            request_id=rid,
            prompt=prompt,
            max_new_tokens=max_new,
            priority=priority,
            temperature=temperature,
            eos_token=eos_token,
            deadline=Deadline.after(budget) if budget is not None else None,
            seed=seed,
            prefill_only=prefill_only,
            tenant_class=str(tenant_class or ""),
            ledger_stages=dict(ledger_stages or {}),
            record_slo=bool(record_slo),
            spec_k=(
                self.engine_cfg.speculative_k
                if self.spec is not None
                and speculative is not False
                and not prefill_only
                else 0
            ),
        )
        trace_wire = _tracing.current_wire()
        with self._lock:
            if rid in self._out:
                raise ValueError(f"duplicate request_id {rid!r}")
            self._out[rid] = queue.Queue()
            if trace_wire is not None:
                self._trace_ctx[rid] = trace_wire
            self._submitted_at[rid] = time.monotonic()
        try:
            self.scheduler.add(req)
        except Exception:
            with self._lock:
                self._out.pop(rid, None)
                self._trace_ctx.pop(rid, None)
                self._submitted_at.pop(rid, None)
            raise
        with self._lock:
            # counted only AFTER scheduler.add succeeded: a rejected
            # submit (queue full, draining) never entered the books
            self._books["submitted"] += 1
        self._work.set()
        return rid

    def generate(
        self,
        prompt: Sequence[int],
        *,
        max_new_tokens: Optional[int] = None,
        temperature: float = 0.0,
        priority: int = 0,
        eos_token: Optional[int] = None,
        request_id: Optional[str] = None,
        seed: Optional[int] = None,
        timeout_s: Optional[float] = None,
        tenant_class: str = "",
        ledger_stages: Optional[Dict[str, float]] = None,
        record_slo: bool = True,
        speculative: Optional[bool] = None,
    ) -> Iterator[int]:
        """Submit and stream tokens as they decode. Closing/abandoning
        the iterator cancels the request and frees its blocks."""
        rid = self.submit(
            prompt,
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            priority=priority,
            eos_token=eos_token,
            request_id=request_id,
            seed=seed,
            timeout_s=timeout_s,
            tenant_class=tenant_class,
            ledger_stages=ledger_stages,
            record_slo=record_slo,
            speculative=speculative,
        )
        try:
            yield from self.tokens(rid)
        finally:
            self.cancel(rid)  # no-op when already finished

    def generate_chunks(self, prompt: Sequence[int], **kw) -> Iterator[List[int]]:
        """:meth:`generate`, coalesced: yields LISTS — each the full
        burst of tokens available at wake-up. Speculative decoding
        commits up to k+1 tokens per verify step; draining the burst in
        one item lets the serve streaming path pay its per-item cost
        once per STEP instead of once per token (the router flattens, so
        clients still see a per-token stream)."""
        rid = self.submit(prompt, **kw)
        try:
            yield from self.tokens_chunked(rid)
        finally:
            self.cancel(rid)  # no-op when already finished

    def tokens_chunked(
        self, request_id: str, timeout: Optional[float] = None
    ) -> Iterator[List[int]]:
        """Chunked variant of :meth:`tokens`: one blocking wait per
        burst, then a non-blocking drain of everything already queued.
        Timeout/resume semantics match :meth:`tokens` (the timeout
        bounds the wait for the NEXT burst)."""
        q = self._out.get(request_id)
        if q is None:
            raise KeyError(f"unknown request {request_id!r}")
        drop = True
        try:
            while True:
                try:
                    item = q.get(timeout=timeout) if timeout is not None else q.get()
                except queue.Empty:
                    drop = False
                    raise TimeoutError(
                        f"no token within {timeout}s for request {request_id!r}; "
                        "still running — retry tokens_chunked() or cancel()"
                    ) from None
                terminal = None
                chunk: List[int] = []
                while True:
                    if item is _END or isinstance(item, Exception):
                        terminal = item
                        break
                    chunk.append(item)
                    try:
                        item = q.get_nowait()
                    except queue.Empty:
                        break
                if chunk:
                    yield chunk
                if terminal is _END:
                    return
                if terminal is not None:
                    raise terminal
        finally:
            # same queue-drop rule as tokens(): keep it on inter-token
            # timeout so a retry can pick the stream back up
            if drop:
                with self._lock:
                    self._out.pop(request_id, None)
                    self._finished_at.pop(request_id, None)

    def tokens(self, request_id: str, timeout: Optional[float] = None) -> Iterator[int]:
        """Drain a submitted request's token stream. ``timeout`` bounds
        each inter-token gap: on expiry a :class:`TimeoutError` is raised
        but the request keeps running and the stream stays resumable —
        call ``tokens()`` again to continue, or ``cancel()`` to give up."""
        q = self._out.get(request_id)
        if q is None:
            raise KeyError(f"unknown request {request_id!r}")
        drop = True
        try:
            while True:
                try:
                    item = q.get(timeout=timeout) if timeout is not None else q.get()
                except queue.Empty:
                    drop = False
                    raise TimeoutError(
                        f"no token within {timeout}s for request {request_id!r}; "
                        "still running — retry tokens() or cancel()"
                    ) from None
                if item is _END:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            # stream consumed (or abandoned): drop the queue — except on
            # inter-token timeout, where the request is still decoding and
            # a retry must find the queue (popping here would silently
            # drop every later token and KeyError the retry)
            if drop:
                with self._lock:
                    self._out.pop(request_id, None)
                    self._finished_at.pop(request_id, None)

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued/running request; frees its blocks. Returns
        True if something was actually cancelled."""
        req = self.scheduler.cancel(request_id)
        if req is None:
            # already finished (or unknown). The finish may still be
            # mid-flight on the step thread — scheduler.finish() done but
            # _finish_request() not yet run — so popping the queue alone
            # could strand a consumer blocked in q.get() with no _END
            # ever arriving. Wake it, then drop the dict entry.
            with self._lock:
                q = self._out.pop(request_id, None)
                self._finished_at.pop(request_id, None)
            if q is not None:
                q.put(_END)
            return False
        self._finish_request(req, CANCELLED, error=None)
        # the caller is not the step loop: nothing to launch before its _END
        self._deliver_held("direct")
        return True

    # -- drain ------------------------------------------------------------
    def begin_drain(
        self, grace_s: Optional[float] = None, *, migrate: bool = False
    ) -> None:
        """Stop admitting; in-flight (queued + running) requests keep
        decoding until done or the grace window closes, after which the
        stragglers fail with :class:`RequestFailedError`.

        ``migrate=True`` (tier deployments): instead of letting
        in-flight decodes run the grace window out, the next step
        flushes each one's FULL KV (prompt + generated — closing the
        disagg gap where export covers prompt KV only) into the cluster
        tier and fails it with :class:`KvMigrationHandoff`, which the
        router treats as resumable — the stream continues on a survivor
        via tier fault-in, client-invisible."""
        grace = GLOBAL_CONFIG.drain_grace_s if grace_s is None else grace_s
        with self._lock:
            self._draining = True
            self.scheduler.admitting = False
            self._drain_deadline = Deadline.after(grace)
            if migrate and self.engine_cfg.kv_tier_enabled:
                self._migrate_on_drain = True
        self._work.set()

    @property
    def draining(self) -> bool:
        return self._draining

    def attach_node_drain_listener(self) -> None:
        """Subscribe to node DRAINING pushes: a preemption warning on OUR
        node triggers ``begin_drain`` (serve unroutes the replica at the
        same time, so live streams finish and nothing new arrives)."""
        try:
            import ray_tpu
            from ray_tpu.core.api import _global_worker

            my_node = ray_tpu.get_runtime_context().get_node_id()
            backend = _global_worker().backend
        except Exception:
            return  # local mode / no cluster: explicit begin_drain() only

        def _on_node_event(msg: Dict[str, Any]) -> None:
            nid = msg.get("node_id")
            nid = nid.hex() if isinstance(nid, bytes) else nid
            if msg.get("state") == "DRAINING" and nid == my_node:
                self.begin_drain()

        try:
            backend.add_node_event_listener(_on_node_event)
        except Exception:
            return
        self._listener_backend = backend
        self._node_listener = _on_node_event

    def detach_node_drain_listener(self) -> None:
        if self._listener_backend is not None and self._node_listener is not None:
            try:
                self._listener_backend.remove_node_event_listener(self._node_listener)
            except Exception:
                pass
        self._listener_backend = None
        self._node_listener = None

    # -- the step ---------------------------------------------------------
    def step(self, hold_wakes: bool = False) -> bool:
        """One engine step: ≤N prefill chunks + the decode batch. Returns
        whether any work ran. Every second of it lands in one phase of
        the step account (``stats()["step_phases"]``): what no phase
        claimed is this step's bookkeeping.

        What the step commits reaches the requests' queues before it
        returns, and every program it launched has been read, unless
        ``hold_wakes``: the step loop's own calls leave the last commits'
        items held, for the next step to deliver after ITS launches, and,
        where no arrival could have had the next step's slot or chunk, the
        decode launch unread, for the next step to read after them
        (:meth:`_stays_unread`)."""
        since = time.perf_counter()
        # timeline timestamps share the module's wall-clock epoch so
        # engine_step events merge with every other process's trace
        t0_us = timeline._now_us()
        did_work = False
        try:
            did_work = self._step(t0_us, in_loop=hold_wakes)
            return did_work
        finally:
            if not hold_wakes:
                self._wake("direct")
            # a step that found nothing to do is no lap to rate another against
            self._clock.settle(since, "bookkeeping" if did_work else "schedule", rated=did_work)

    def _step(self, t0_us: float, in_loop: bool) -> bool:
        clock = self._clock
        self._wakes_left = _WAKES_PER_STEP
        with clock.phase("schedule", step=self.total_steps):
            with clock.part("drain"):
                if self._draining and self._drain_deadline is not None and self._drain_deadline.expired:
                    self._fail_all(
                        RequestFailedError("engine drain grace expired mid-generation")
                    )
                if self._migrate_on_drain:
                    self._migrate_inflight()
                did_import = self._drain_kv_imports()
                self._drain_tier_spills()
            plan = self.scheduler.schedule(clock)
            for req in plan.reaped:
                # every reap here is a deadline expiry (queued or running) —
                # a fault-cost class the SLO report breaks out explicitly
                self.metrics["deadline"].inc(
                    labels={"deployment": self.slo_deployment}
                )
                self._finish_request(
                    req,
                    req.state,
                    error=RequestFailedError(
                        f"request {req.request_id} deadline expired before completion"
                    ),
                )
            idle = not plan.prefills and not plan.decodes
            if not idle:
                self._consult_replica_chaos(plan)
        if idle:
            # nothing to launch: what the last step left unread is read now,
            # and nothing stays held
            read = self._read_unread()
            self._wake("at_idle")
            return did_import or read or not plan.empty
        wake = self._wake_after_launch

        # While the last step's decode launch is unread the device has work
        # queued, and every program of this step is launched before the
        # thread waits for any: the chunk, then the decode batch right behind
        # it (its plan never depended on the chunk's result; the device works
        # through its queue in the order of the launches). With nothing
        # unread the step runs in the order it always had, a chunk read
        # before the decode batch goes out: an engine that is not looking
        # ahead behaves as it did.
        ahead = self._unread is not None
        chunks: List[tuple] = []
        for req, start, chunk in plan.prefills:
            if req.pending_cow:
                # prefix-cache COW: duplicate the shared block(s) BEFORE
                # this chunk writes into the private copies, then drop
                # the source pins (the copies are live in the table now)
                with clock.phase("schedule"):
                    self.runner.copy_blocks(req.pending_cow)
                    self.blocks.cow_copied(req.request_id)
                    req.pending_cow = []
            if req.prefill_started_at is None:
                req.prefill_started_at = time.monotonic()
            with clock.phase("launch"), clock.part("rows"):
                row = self.blocks.table_row(
                    req.request_id, self.runner.max_blocks_per_seq
                )
                prompt = req.effective_prompt
                tokens = prompt[start : start + chunk]
                # what a model that drafts for itself runs its drafter with
                follows = prompt[start + chunk] if start + chunk < len(prompt) else -1
            launched = self.runner.launch_prefill(
                tokens, row, start, clock, slot=self.blocks.slot_of(req.request_id),
                next_token=follows,
            )
            chunks.append((req, prompt, start + chunk, launched))
        n_prefill_tokens = 0 if ahead else self._commit_chunks(chunks)

        # speculative slots peel off the batch: each proposes drafts,
        # then EVERY spec slot verifies in one batched target step
        # (models.llama.paged_verify_step: B slots x k+1 positions
        # per jit call). Slots whose proposer came up empty (no
        # n-gram match, draft pool dry) ride the plain batched
        # decode unchanged — speculation is an opportunistic
        # throughput lever, never a dependency.
        spec_slots: List[tuple] = []
        plain: List[Request] = []
        drafting: List[Request] = []
        if self.spec is None:
            plain = plan.decodes
        elif self._mtp:
            # the model drafts for itself, in the step's own program, for
            # every slot at once: nothing is proposed here
            drafting = plan.decodes
        else:
            # proposing is deciding what this step runs: schedule's time
            with clock.phase("schedule"):
                for r in plan.decodes:
                    drafts = self._spec_propose(r) if r.spec_step_k > 0 else []
                    if drafts:
                        spec_slots.append((r, drafts))
                    else:
                        plain.append(r)
        batch = None
        if plain:
            batch = self._launch_decode(plain)
        elif drafting:
            batch = self._launch_windows(drafting)
        if batch is not None:
            # the last commits go out while the device runs this step's
            # programs; what the step commits from here on, before each of
            # its later waits
            wake()
        if ahead:
            # the decode launch the last step left unread: its tokens were in
            # flight while this step was planned and launched
            self._read_unread(batch)
            n_prefill_tokens = self._commit_chunks(chunks)

        # each batch: sample every slot, then emit every slot, so that
        # the two are two spans and not 2 x slots slivers; the tokens
        # and their order are those of sampling and emitting in turn
        if batch is not None:
            stays = self._stays_unread(plan, batch) if in_loop and not spec_slots else None
            if stays is not None:
                for row, req in enumerate(batch.reqs):
                    req.in_flight = row
                    req.in_flight_most = 1 + (batch.windows.riding(row) if batch.windows else 0)
                batch.chunk_due = stays == "chunk"
                self._unread = batch
            else:
                self._read_decode(batch)
        if spec_slots:
            with clock.phase("launch"), clock.part("rows"):
                windows = [[r.generated[-1]] + d for r, d in spec_slots]
                rows = [
                    self.blocks.table_row(
                        r.request_id, self.runner.max_blocks_per_seq
                    )
                    for r, _ in spec_slots
                ]
                ctxs = [r.context_len - 1 for r, _ in spec_slots]
            all_logits = self.runner.verify_batch(windows, rows, ctxs, clock, wake)
            with clock.phase("sample"):
                sampled = [
                    self._spec_sample(req, drafts, logits)
                    for (req, drafts), logits in zip(spec_slots, all_logits)
                ]
            with clock.phase("emit"), clock.part("commit"):
                for (req, drafts), tokens in zip(spec_slots, sampled):
                    self._spec_commit(req, drafts, tokens)
        with clock.phase("bookkeeping"):
            if n_prefill_tokens:
                self._prefill_token_times.append((time.monotonic(), n_prefill_tokens))
            self._update_gauges(len(plan.decodes))
            # one event a step: the whole of it, from the top of step(),
            # with where its time went (bookkeeping, still running, is
            # what the others leave of the event's duration)
            timeline.record_event(
                "engine_step",
                "inference",
                t0_us,
                timeline._now_us(),
                args={
                    "step": self.total_steps,
                    "prefill_tokens": n_prefill_tokens,
                    "decode_batch": len(plan.decodes),
                    "phases_us": {
                        name: round(seconds * 1e6)
                        for name, seconds in clock.lap.items()
                        if seconds and name != "bookkeeping"
                    },
                },
            )
        self.total_steps += 1
        return True

    # -- launch before read ------------------------------------------------
    def _launch_decode(self, plain: List[Request]) -> "_DecodeBatch":
        """Launch the plain decode batch. A request whose newest token is in
        flight (in the launch the last step left unread) is one position
        further than ``generated`` says, and its row's token is named, not
        given: ``-1 - j`` is row ``j`` of that launch's picks, which the
        program takes on the device."""
        clock = self._clock
        unread = self._unread
        with clock.phase("launch"), clock.part("rows"):
            toks = [r.generated[-1] if r.in_flight is None else -1 - r.in_flight for r in plain]
            poss = [r.context_len - 1 + r.ahead for r in plain]
            rows = [
                self.blocks.table_row(r.request_id, self.runner.max_blocks_per_seq)
                for r in plain
            ]
            cls = [r.context_len + r.ahead for r in plain]
            slots = [self.blocks.slot_of(r.request_id) for r in plain]
            self._account_held()
            # an all-greedy batch reads back the device's picks (one
            # int a slot), not the logits: the same program either way
            greedy = all(r.temperature <= 0.0 for r in plain)
        launched = self.runner.launch_decode(
            toks, poss, rows, cls, clock, slots=slots, greedy=greedy,
            after=unread.launched if unread is not None else None,
        )
        counts = self._decode_ahead
        counts["launches"] += 1
        counts["ahead"] += unread is not None
        self._ahead_chunk_due += unread is not None and unread.chunk_due
        return _DecodeBatch(plain, launched, greedy)

    def _account_held(self) -> None:
        """Add a decode launch to ``kv_held``: the block-layers out of the
        groups' pools now, and the block-layers ONE table a request would hold
        for the same sequences: the blocks of the group that keeps all, in
        every layer."""
        layers = [len(g.layers) for g in self.runner.cache_layout.groups]
        in_use = self.blocks.blocks_in_use()
        acc = self._kv_held
        acc["launches"] += 1
        acc["held_block_layers"] += sum(n * l for n, l in zip(in_use, layers, strict=True))
        acc["one_table_block_layers"] += in_use[0] * sum(layers)

    def _commit_chunks(self, chunks: List[tuple]) -> int:
        """Read the step's prefill chunks in the order of their launches and
        commit each: the first token of a prompt that is through, or its
        export. Returns the prompt tokens they ran."""
        clock = self._clock
        wake = self._wake_after_launch
        n_prefill_tokens = 0
        for req, prompt, end, launched in chunks:
            logits = self.runner.read(launched, clock, wake)
            n_prefill_tokens += end - req.prefill_pos
            req.prefill_pos = end
            if req.prefill_done and req.prefill_done_at is None:
                req.prefill_done_at = time.monotonic()
            if req.prefill_done:
                if not req.prefill_only:
                    with clock.phase("sample"):
                        token = self._sample(req, logits)
                with clock.phase("emit"), clock.part("commit"):
                    # the prompt's K/V is fully written: index its full
                    # blocks so later requests sharing the prefix skip them
                    self.blocks.register_prefix(req.request_id, prompt)
                    if self.engine_cfg.kv_tier_enabled and not req.prefill_only:
                        # tier write-back trigger 1: the prompt's full
                        # blocks become cluster-recoverable the moment they
                        # exist — a replica killed one token later already
                        # left its prefill in the tier
                        self._tier_writeback_full_blocks(req, prompt, "prefill")
                    if req.prefill_only:
                        # KV-migration export: gather the full blocks to
                        # host and hand the payload to the waiting exporter
                        # — no token is ever sampled on this engine
                        self._complete_prefill_export(req, prompt)
                    else:
                        req.state = DECODE
                        if self._mtp:
                            # prefilled (again): whatever draft it had is not
                            # the one after this context
                            self.spec.release(req.request_id)
                        self._emit_token(req, token)
        return n_prefill_tokens

    def _stays_unread(self, plan, batch: "_DecodeBatch") -> Optional[str]:
        """Whether the step loop may go on to plan the next step before it
        reads this decode launch: what lets it (:meth:`_next_step_taken_by`'s
        answer), or None: read it now. One predicate, read off the engine's
        own state: the device holds the batch's next tokens and the next launch
        can take them there (a plain, all-greedy batch in which nothing may
        speculate: the picks; or the all-greedy step of a model that drafts
        for itself, in its ONE-program form: its new tokens, accepted count
        and next draft are the next window, and every request that could
        decode next is greedy too, so that the next step is that program
        again; a batch a proposer on the HOST drafts for never is; no chunk
        of the step is an export), and AN ARRIVAL COULD NOT HAVE HAD THE NEXT
        STEP'S PLACE ANYWAY. Then looking ahead costs nobody a slot or a
        chunk it could have had; anywhere else it would cost an arrival up to
        one decode step before its chunk can start."""
        if batch.windows is None:
            on_device = all(r.spec_k == 0 for r in batch.reqs)
        else:
            on_device = all(r.temperature <= 0.0 for r in list(self.scheduler.running))
        if not (batch.greedy and on_device) or any(p[0].prefill_only for p in plan.prefills):
            return None
        return self._next_step_taken_by()

    def _next_step_taken_by(self) -> Optional[str]:
        """What keeps an arrival out of the next step, ``"slot"`` or
        ``"chunk"``, or None where it could have a place there; called once
        this step's chunks are committed. No SLOT: the running requests
        (those in prefill hold the slots they will decode in) fill the decode
        batch, or requests already wait. No CHUNK: as many running requests
        still have prompt left as a step has chunks, and the plan gives a
        step's chunks to the oldest of them, so the next step is ``[their
        chunks, the decode batch]`` whoever arrives and an arrival's first
        chunk lands in the same step either way.

        Not promised: an arrival of HIGHER priority than the request in
        prefill would have overtaken its chunk and now waits one chunk more,
        as it already may behind a full batch. And where a window pool
        refuses the due chunk the next step carries none: the arrival stands
        behind the refused chunk for that step with or without its decode
        launch."""
        sched = self.scheduler
        if len(sched.running) >= sched.max_decode_batch or sched.waiting:
            return "slot"
        in_prefill = sum(not r.prefill_done for r in list(sched.running))
        return "chunk" if in_prefill >= sched.max_prefills_per_step else None

    def _read_unread(self, later: Optional["_DecodeBatch"] = None) -> bool:
        """Read the decode launch the last step left unread, if it left one.
        ``later``: the launch made since, whose rows named this one's."""
        batch, self._unread = self._unread, None
        if batch is None:
            return False
        self._read_decode(batch, later)
        return True

    def _read_decode(self, batch: "_DecodeBatch", later: Optional["_DecodeBatch"] = None) -> None:
        """Wait for a decode launch, sample and commit its rows: a token a
        row of a plain batch, the accepted prefix of its window of a
        drafter's step (:meth:`_spec_commit`; the ONE program hands back
        three small integers a slot, the two-program form both rows' logits
        for the engine's own sampler, :meth:`_spec_sample`, and its second
        program runs the drafter while the commits go out). A row whose
        request is no longer decoding is DROPPED, never emitted, and its
        draft released: it finished on the tokens before (an EOS the host
        could only see in the token, a cap reached inside an accepted
        window), was cancelled, reaped, failed or preempted while this one
        was in flight. Its blocks and state slot went back to the pool with
        that launch still queued, and that is safe: the device runs its queue
        in the order of the launches, so the dropped row's write lands before
        any program launched after the blocks or the slot were handed on,
        and every reader reads only what its own sequence wrote after that
        (a chunk at context 0 starts its slot's state from zeros). ``later``:
        the launch made after this one and not read yet; a request with a
        window in it keeps its blocks as far as that window reaches."""
        clock = self._clock
        wake = self._wake_after_launch
        out = self.runner.read(batch.launched, clock, wake)
        w, reqs = batch.windows, batch.reqs
        nxt = second = None
        with clock.phase("sample"):
            if w is None:
                if batch.greedy:  # the device's picks, one a slot
                    sampled = [int(t) for t in out]
                else:
                    sampled = [self._sample(req, lg) for req, lg in zip(reqs, out)]
            elif batch.greedy:  # [new tokens.., accepted, the next draft] a slot
                sampled = [
                    [int(t) for t in (row[: 1 + row[-2]] if k == 1 else row[:1])]
                    for row, k in zip(out, w.known)
                ]
                nxt = [int(row[-1]) for row in out]
            else:
                sampled = [
                    self._spec_sample(r, d, lg[len(t) - 1 - len(d) : len(t)])
                    for r, d, t, lg in zip(reqs, w.drafts, w.tokens, out)
                ]
        if w is not None and not batch.greedy:
            # the token after each position of a window, as far as committed
            follows = [
                (t[1:] if k == 2 else []) + toks for t, k, toks in zip(w.tokens, w.known, sampled)
            ]
            second = self.runner.launch_mtp_draft(batch.launched, follows, w.rows, w.ctxs, clock)
        reach = {}
        if later is not None and later.windows is not None:
            reach = {id(r): later.windows.riding(i) for i, r in enumerate(later.reqs)}
        counts = self._mtp_counts
        with clock.phase("emit"), clock.part("commit"):
            for i, (req, tokens) in enumerate(zip(reqs, sampled)):
                req.in_flight = None
                if req.state != DECODE:
                    if w is None:
                        self._decode_ahead["dropped"] += 1
                    else:
                        counts["rows_dropped"] += 1
                        self.spec.release(req.request_id)
                    continue
                if w is None:
                    self._emit_token(req, tokens)
                    continue
                drafts = w.drafts[i]
                if drafts is None:  # it rode on the device: the step before handed it back
                    draft = self.spec.draft_of(req.request_id)
                    drafts = [] if draft is None else [draft]
                had = len(req.generated)
                self._spec_commit(req, drafts, tokens, reach.get(id(req), 0))
                counts["slot_steps"] += 1
                counts["committed_tokens"] += len(req.generated) - had
        if second is not None:
            nxt = [int(t) for t in self.runner.read(second, clock, wake)]
        if nxt is not None:
            for req, draft in zip(reqs, nxt):
                if req.state == DECODE:
                    self.spec.keep(req.request_id, draft)

    def _drop_unread(self) -> None:
        """Forget the unread launch: its requests have all been failed."""
        batch, self._unread = self._unread, None
        if batch is not None:
            for req in batch.reqs:
                req.in_flight = None
            if batch.windows is None:
                self._decode_ahead["dropped"] += len(batch.reqs)
            else:
                self._mtp_counts["rows_dropped"] += len(batch.reqs)

    # -- speculative decoding (PR 19) -------------------------------------
    def _spec_propose(self, req: Request) -> List[int]:
        """Ask the proposer for up to ``spec_step_k`` drafts for this
        slot. An empty proposal (nothing to look up, draft pool dry, a
        broken proposer) degrades the slot to plain decode this step and
        hands back the blocks the scheduler grew for the draft window."""
        ctx = req.prompt + req.generated
        try:
            drafts = self.spec.propose(
                ctx, req.spec_step_k, request_id=req.request_id
            )
        except Exception:  # noqa: BLE001 — proposer bugs must not kill steps
            logger.exception("speculative proposer failed; plain decode")
            drafts = []
        drafts = [int(t) for t in list(drafts)[: req.spec_step_k]]
        if not drafts:
            self.blocks.trim_to(req.request_id, req.context_len)
        return drafts

    def _spec_sample(
        self, req: Request, drafts: List[int], logits: np.ndarray
    ) -> List[int]:
        """The target's own tokens along one slot's verify window, up to
        and including the first that differs from its draft: what
        :meth:`_spec_commit` emits. Position ``i`` is sampled as if the
        ``i`` tokens before it had been emitted, which they will have
        been by the time it is (commit stops where the request ends)."""
        base = len(req.prompt) + len(req.generated)
        tokens: List[int] = []
        for i in range(len(drafts) + 1):
            tokens.append(self._sample(req, logits[i], base + i))
            if i < len(drafts) and tokens[-1] != drafts[i]:
                break
        return tokens

    def _spec_commit(
        self, req: Request, drafts: List[int], tokens: List[int], reach: int = 0
    ) -> None:
        """Commit the deterministically-accepted prefix of one slot's
        verify window ``[last_committed, d_1..d_k']`` from its
        all-position target logits (``logits[i]`` is the distribution
        AFTER window position i; the batched verify already ran, and
        :meth:`_spec_sample` realized ``tokens`` from them).

        Acceptance is exact-match: at each window position the target's
        token is realized with the engine's own (seed, absolute-position)
        sampler (:meth:`_sample` — ``pos`` advances naturally as tokens
        emit), drafts are accepted while they match it, and the first
        mismatch position emits the target's token INSTEAD (the
        bonus/correction token — every speculative step nets >= 1
        token). Emitted bytes are therefore identical to plain decode by
        construction, for greedy and seeded temperature>0 sampling
        alike, and the proposer can never affect content — only the
        acceptance rate.

        Rollback is pure host-side accounting: ``generated`` only ever
        received accepted tokens (the write cursor rewind is implicit),
        and :meth:`PagedBlockManager.trim_to` hands back the blocks
        grown past the committed context. The rejected tail's K/V stays
        stale on device, unreachable by construction — every masked
        read stops at the committed context length, and re-verification
        overwrites the slots in place. The prefix index and the KV tier
        only ever see positions below the verified cursor because both
        derive from ``generated``. ``reach``: the drafts that ride the
        request's NEXT window where that is launched already (a drafter's
        step read late): its blocks stay as far as that window writes."""
        m = self.metrics
        accepted = 0
        for i, tok in enumerate(tokens):
            if req.finished:
                break
            self._emit_token(req, tok)
            if i < len(drafts) and tok == drafts[i]:
                accepted += 1
        self._spec_proposed += len(drafts)
        self._spec_accepted += accepted
        m["spec_proposed"].inc(len(drafts))
        if accepted:
            m["spec_accepted"].inc(accepted)
        if accepted < len(drafts):
            self._spec_rollbacks += 1
            m["spec_rollbacks"].inc()
        self.blocks.trim_to(req.request_id, req.context_len + reach)

    def _launch_windows(self, reqs: List[Request]) -> "_DecodeBatch":
        """Launch the decode step of a model that drafts for itself. A slot's
        window is ``[x_n, d]``, its committed last token and the draft the
        step before handed back (``[x_n]`` alone where the plan left no room
        for a draft), or, for a slot without a draft yet, the window one
        position earlier with both tokens committed (``known`` 2: the
        drafter's row at the first waits for exactly this step). A request
        with a window in flight (in the step the loop left unread) is NAMED,
        not given: ``-1 - j`` is what row ``j`` of that step leaves on the
        device, its last committed token and its draft, at least one position
        on. An all-greedy batch is ONE launch: the device verifies, picks,
        compares, runs the drafter over what it committed and drafts again.
        Any other takes the two-program form, whose rows the host must know
        (:meth:`_stays_unread` leaves nothing unread before such a batch).
        :meth:`_read_decode` commits either."""
        clock = self._clock
        unread = self._unread
        with clock.phase("launch"), clock.part("rows"):
            tokens, known, ctxs, drafts = [], [], [], []
            for r in reqs:
                rides = r.spec_step_k > 0
                if r.in_flight is not None:
                    tokens.append([-1 - r.in_flight] + [0] * rides)
                    ctxs.append(r.context_len - 1 + r.ahead)
                    drafts.append(None if rides else [])
                    known.append(1)
                    continue
                last, draft = r.generated[-1], self.spec.draft_of(r.request_id)
                if draft is None:
                    before = r.generated[-2] if len(r.generated) > 1 else r.prompt[-1]
                    tokens.append([before, last])
                    ctxs.append(r.context_len - 2)
                    drafts.append([])
                else:
                    drafts.append([draft] if rides else [])
                    tokens.append([last] + drafts[-1])
                    ctxs.append(r.context_len - 1)
                known.append(2 if draft is None else 1)
            rows = [self.blocks.table_row(r.request_id, self.runner.max_blocks_per_seq) for r in reqs]
            greedy = all(r.temperature <= 0.0 for r in reqs)
        launched = self.runner.launch_mtp_step(
            tokens, known, rows, ctxs, clock, greedy=greedy,
            after=unread.launched if unread is not None else None,
        )
        counts = self._mtp_counts
        counts["launches_fused" if greedy else "launches_split"] += 1
        counts["step_launches"] += 1
        counts["launches_ahead"] += unread is not None
        return _DecodeBatch(reqs, launched, greedy, _Windows(tokens, known, drafts, rows, ctxs))

    # -- internals --------------------------------------------------------
    def _sample(
        self, req: Request, logits: np.ndarray, pos: Optional[int] = None
    ) -> int:
        """Deterministic continuation: the RNG is keyed on
        ``(request seed, absolute position)`` instead of a stateful
        per-request stream. ``len(prompt) + len(generated)`` equals the
        original sequence position regardless of how much of the
        sequence arrived AS prompt — so a request resubmitted as
        ``prompt + generated[:k]`` provably samples token k+1
        identically, which is what makes mid-stream failover replay
        byte-exact (serve router resume; pinned by
        tests/test_stream_resume.py)."""
        if req.temperature <= 0.0:
            return int(np.argmax(logits))
        if pos is None:
            pos = len(req.prompt) + len(req.generated)
        seed = req.seed if req.seed is not None else 0
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, pos])
        )
        z = (logits / req.temperature).astype(np.float64)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(len(p), p=p))

    def _consult_replica_chaos(self, plan) -> None:
        """Replica fault injection at the step boundary (ReplicaFaultPlan):
        consulted once per phase this step actually runs, BEFORE the
        phase's device work — a kill lands after the last emitted token
        and before the next one samples, the boundary the router's
        seq-numbered resume must cover."""
        for phase, present in (
            ("prefill", bool(plan.prefills)),
            ("decode", bool(plan.decodes)),
        ):
            if present:
                self._consult_phase_chaos(phase)

    def _consult_phase_chaos(self, phase: str) -> None:
        """One chaos consult for a named engine phase ("prefill" |
        "decode" | "export" | "import" — the latter two are the
        KV-migration consult points: a kill there lands exactly
        mid-handoff, which the disagg fallback ladder must absorb)."""
        chaos = self.testing_fault_plan or active_replica_fault_plan()
        if chaos is None:
            return
        fault = chaos.consult(phase)
        if fault is None:
            return
        mode, param = fault
        if mode == "stall":
            logger.warning(
                "replica chaos: stalling step loop %.2fs (seed=%d)",
                param, chaos.seed,
            )
            time.sleep(param)
        else:
            logger.warning(
                "replica chaos: %s — SIGKILL self (pid=%d seed=%d)",
                mode, os.getpid(), chaos.seed,
            )
            os.kill(os.getpid(), signal.SIGKILL)

    # -- KV-cache migration (disaggregated serving) -----------------------
    def prefill_kv(
        self,
        prompt: Sequence[int],
        *,
        priority: int = 0,
        request_id: Optional[str] = None,
        timeout_s: Optional[float] = None,
    ) -> Optional[Dict[str, Any]]:
        """Export mode: run ONLY the prompt's prefill, then gather its
        FULL KV blocks to host and return the payload ``{"tokens":
        covered_tokens, "kv": np[2, L, n, bs, n_kv, hd], "block_size"}``
        — the migration unit ``inference/kv_transfer.py`` serializes and
        ships. Returns None when the prompt spans no full block (nothing
        exportable — the caller falls back to plain generation). The
        prefill itself also populates THIS engine's radix index, so an
        exporting replica keeps the warm-prefix benefit locally."""
        self._refuse_with_state("prefill_kv (KV export)")
        rid = self.submit(
            prompt,
            max_new_tokens=1,
            priority=priority,
            request_id=request_id,
            timeout_s=timeout_s,
            prefill_only=True,
        )
        q = self._out.get(rid)
        try:
            while True:
                item = q.get(timeout=timeout_s)
                if item is _END:
                    return None
                if isinstance(item, Exception):
                    raise item
                if isinstance(item, tuple) and item and item[0] == "kv_export":
                    return item[1]
        except queue.Empty:
            self.cancel(rid)
            raise TimeoutError(
                f"kv export of {len(prompt)} prompt tokens not done within "
                f"{timeout_s}s"
            ) from None
        finally:
            with self._lock:
                self._out.pop(rid, None)
                self._finished_at.pop(rid, None)

    def _refuse_with_state(self, what: str) -> None:
        """Blocks moved without their sequence's state would decode from
        zeros: refuse on a model that has a state description."""
        if self.runner.state_layout is not None:
            raise RuntimeError(
                f"{what} is refused: this model keeps a per-sequence state in its recurrent "
                "layers, which the blocks do not carry"
            )

    def _complete_prefill_export(self, req: Request, prompt) -> None:
        """Step-thread half of :meth:`prefill_kv`: the gather MUST run
        here — the step loop swaps (and on TPU donates) the cache value
        every step, so a reader on another thread could hold an
        invalidated buffer."""
        self._consult_phase_chaos("export")
        bs = self.blocks.block_size
        n_full = len(prompt) // bs
        try:
            payload = None
            if n_full > 0:
                t0 = time.monotonic()
                blocks = self.blocks.owned(req.request_id)[:n_full]
                kv = self.runner.gather_blocks(blocks)
                # ledger stage: device→host gather time of the exported
                # blocks (the disagg handoff's engine-side cost)
                req.ledger_stages["kv_export"] = time.monotonic() - t0
                payload = {
                    "tokens": list(prompt[: n_full * bs]),
                    "kv": kv,
                    "block_size": bs,
                }
        except Exception as e:  # noqa: BLE001 — exporter must not hang
            if self.scheduler.finish(req, FAILED):
                req.state = FAILED
                self._finish_request(
                    req, FAILED,
                    error=RequestFailedError(f"kv export failed: {e!r}"),
                )
            return
        if payload is not None:
            with self._lock:
                q = self._out.get(req.request_id)
            if q is not None:
                self._hold(q, ("kv_export", payload), now=True)
        if self.scheduler.finish(req, FINISHED):
            self._finish_request(req, FINISHED, error=None)

    def import_kv_blocks(
        self, tokens: Sequence[int], kv, timeout_s: float = 30.0
    ) -> int:
        """Install migrated KV blocks into this engine's cache + radix
        index (the import half of KV migration). ``kv`` is the
        :meth:`prefill_kv` payload layout; block i must hold the K/V of
        ``tokens[i*bs:(i+1)*bs]``. Queued to the STEP THREAD (cache
        mutation must not race its swaps) and waited on here. Returns
        the number of prompt tokens now covered by the radix index —
        the immediately-following submit acquires them as a prefix hit.
        Raises on block-pool exhaustion or scatter failure (callers
        degrade to a plain prefill)."""
        self._refuse_with_state("import_kv_blocks (KV import)")
        bs = self.blocks.block_size
        n = min(len(tokens) // bs, int(kv.shape[2]))
        if n <= 0:
            return 0
        reply: "queue.Queue" = queue.Queue()
        self._kv_imports.put((list(tokens[: n * bs]), kv[:, :, :n], reply))
        self._work.set()
        try:
            ok, result = reply.get(timeout=timeout_s)
        except queue.Empty:
            raise TimeoutError(
                f"kv import of {n} blocks not executed within {timeout_s}s"
            ) from None
        if not ok:
            raise result
        return result

    def _drain_kv_imports(self) -> bool:
        """Step-thread executor for queued KV imports. Each job:
        reserve pinned blocks → device scatter → commit into the radix
        index (redundant blocks freed). All-or-nothing per job; failures
        surface to the waiting importer, never wedge the step loop."""
        did = False
        while True:
            try:
                tokens, kv, reply = self._kv_imports.get_nowait()
            except queue.Empty:
                return did
            did = True
            try:
                self._consult_phase_chaos("import")
                bs = self.blocks.block_size
                n = len(tokens) // bs
                blocks = self.blocks.reserve_import(n)
                if blocks is None:
                    reply.put((
                        False,
                        RequestFailedError(
                            f"kv import: pool cannot cover {n} blocks"
                        ),
                    ))
                    continue
                try:
                    # no ascontiguousarray: scatter_blocks's per-chunk
                    # packing copies handle non-contiguous views, and a
                    # whole-payload memcpy here would stall the standing
                    # decode batch — on the step thread, for the full
                    # payload size, on every import
                    self.runner.scatter_blocks(blocks, kv)
                except Exception as e:  # noqa: BLE001
                    self.blocks.abort_import(blocks)
                    reply.put((False, e))
                    continue
                self.blocks.commit_import(blocks, tokens)
                # covered tokens, not blocks indexed: duplicates of
                # already-indexed prefixes still serve acquire_prefix
                reply.put((True, n * bs))
            except Exception as e:  # noqa: BLE001
                reply.put((False, e))

    # -- cluster KV tier (PR 17) ------------------------------------------
    def _tier_spill(self, digest: bytes, blk: int, hits: int) -> bool:
        """Spill half of the block manager's ONE spill-vs-drop policy
        point — invoked under the manager lock at every indexed-block
        eviction, so it only GATHERS here (one block, device→host) and
        defers the heavy publish (shm write + daemon RPC) to
        :meth:`_drain_tier_spills` on the next step. Popular blocks
        (ever hit, or already tier-resident) spill; cold ones drop."""
        digest_hex = digest.hex()
        with self._tier_lock:
            if digest_hex in self._tier_adverts or digest_hex in self._tier_pending:
                return True  # already tier-resident/queued: content survives
        if hits <= 0:
            return False  # never reused since indexing: cold, drop
        try:
            kv = self.runner.gather_blocks([blk])
        except Exception:  # noqa: BLE001 — a failed gather is a drop
            return False
        self._tier_spill_pending.append((digest, kv))
        return True

    def _drain_tier_spills(self) -> None:
        if not self._tier_spill_pending:
            return
        pending, self._tier_spill_pending = self._tier_spill_pending, []
        for digest, kv in pending:
            self._tier_enqueue(digest, kv, "evict")

    def _tier_enqueue(self, digest: bytes, kv, trigger: str) -> None:
        """Hand one gathered block to the tier publisher thread. The
        step thread only ever pays a lock + queue put here; the shm
        write and daemon RPC happen off the token-emission path. A full
        queue DROPS the write-back — tier warmth is best-effort and
        must never backpressure decode."""
        digest_hex = digest.hex()
        with self._tier_lock:
            if digest_hex in self._tier_adverts:
                self._tier_adverts.move_to_end(digest_hex)
                return
            if digest_hex in self._tier_pending:
                return
            self._tier_pending.add(digest_hex)
        try:
            self._tier_pub_q.put_nowait((digest, kv, trigger))
        except queue.Full:
            with self._tier_lock:
                self._tier_pending.discard(digest_hex)

    def _tier_publish_loop(self) -> None:
        while not self._stop.is_set():
            try:
                digest, kv, trigger = self._tier_pub_q.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                self._tier_writeback(digest, kv, trigger)
            except Exception:  # noqa: BLE001 — publish is best-effort
                pass
            finally:
                with self._tier_lock:
                    self._tier_pending.discard(digest.hex())
                self._tier_pub_q.task_done()

    def _drain_tier_pub_queue_sync(self) -> None:
        """Publish everything still queued on the CALLER's thread —
        the migrate path needs residency guaranteed before it errors
        the streams, so it cannot leave work racing its own exit."""
        while True:
            try:
                digest, kv, trigger = self._tier_pub_q.get_nowait()
            except queue.Empty:
                return
            try:
                self._tier_writeback(digest, kv, trigger)
            except Exception:  # noqa: BLE001
                pass
            finally:
                with self._tier_lock:
                    self._tier_pending.discard(digest.hex())
                self._tier_pub_q.task_done()

    def flush_tier_writebacks(self, timeout_s: float = 10.0) -> bool:
        """Block until the deferred tier publisher is idle (queue empty
        and no publish in flight). Tests and the migrate path use this
        to turn the asynchronous write-back into a happens-before."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._tier_lock:
                idle = not self._tier_pending
            if idle and self._tier_pub_q.unfinished_tasks == 0:
                return True
            time.sleep(0.01)
        return False

    def _tier_writeback(self, digest: bytes, kv, trigger: str) -> None:
        """Publish one block payload into the tier + advert it. Dedup
        by advert: a digest this replica already adverts just refreshes
        recency (idempotent republish would rewrite identical bytes).
        Runs on the tier publisher thread (or the step thread for the
        synchronous migrate flush) — advert mutations take _tier_lock,
        the publish RPC deliberately does not."""
        digest_hex = digest.hex()
        with self._tier_lock:
            if digest_hex in self._tier_adverts:
                self._tier_adverts.move_to_end(digest_hex)
                return
        from ray_tpu.inference import kv_transfer
        from ray_tpu.observability import rpc_metrics

        desc = kv_transfer.tier_publish(
            digest, kv, self.blocks.block_size, ns=self._tier_ns
        )
        if desc is None:
            return
        with self._tier_lock:
            self._tier_adverts[digest_hex] = desc
            self._tier_adverts.move_to_end(digest_hex)
            # advert cap: dropping the LRU advert retracts it from the
            # gossip (routers purge on the next report's advert-set diff) —
            # the daemon registry may keep the bytes until ITS ttl/cap
            cap = max(1, GLOBAL_CONFIG.kv_tier_max_adverts)
            while len(self._tier_adverts) > cap:
                self._tier_adverts.popitem(last=False)
        rpc_metrics.KV_TIER_PUBLISHES.inc(labels={"trigger": trigger})

    def _tier_writeback_full_blocks(
        self, req: Request, written, trigger: str, sync: bool = False
    ) -> None:
        """Write back every full block of ``written`` (token positions
        whose K/V is in the cache) that is not yet tier-resident. The
        per-block chain digests are recomputed from tokens — the same
        capability-name derivation any future reader uses. Gathers run
        HERE (the step thread: device cache reads must not race the
        loop's own swaps); the publish defers to the tier publisher
        thread unless ``sync`` (migrate needs residency-before-error)."""
        bs = self.blocks.block_size
        owned = self.blocks.owned(req.request_id)
        n_full = min(len(written) // bs, len(owned))
        prev = b""
        for i in range(n_full):
            prev = _chain_digest(prev, written[i * bs : (i + 1) * bs])
            with self._tier_lock:
                resident = (
                    prev.hex() in self._tier_adverts
                    or prev.hex() in self._tier_pending
                )
            if resident:
                continue
            try:
                kv = self.runner.gather_blocks([owned[i]])
            except Exception:  # noqa: BLE001 — write-back is best-effort
                return
            if sync:
                self._tier_writeback(prev, kv, trigger)
            else:
                self._tier_enqueue(prev, kv, trigger)

    def _migrate_inflight(self) -> None:
        """Drain-with-migration (consumer (a) of the tier): flush every
        in-flight request's written KV — prompt AND generated — into
        the tier, then fail it with :class:`KvMigrationHandoff` so
        the router resumes it on a survivor that faults the KV back in.
        The generated-token half is what plain disagg export never
        covered; it is exactly the state a mid-stream failover used to
        re-prefill via replay. Publishes run synchronously here: the
        handoff error must not reach the router before the blocks are
        tier-resident, or the survivor's fault-in races our exit."""
        self._migrate_on_drain = False
        self._drain_tier_pub_queue_sync()
        self.flush_tier_writebacks(5.0)
        for req in self.scheduler.take_all():
            try:
                # Only positions whose K/V truly reached the device
                # cache: blocks are allocated for the WHOLE prompt at
                # admission but chunked prefill writes incrementally —
                # a mid-prefill request has written exactly
                # effective_prompt[:prefill_pos] (a prefix of
                # prompt+generated), and decode has written through
                # context_len-1 once prefill is done. Publishing past
                # that point would advert never-written device blocks
                # under the VALID chain digest of the real tokens (the
                # CRC gate covers transport, not content) and poison
                # every future fault-in of that prefix.
                end = (
                    (req.context_len - 1) if req.prefill_done else req.prefill_pos
                )
                if end > 0:
                    written = (req.prompt + req.generated)[:end]
                    self._tier_writeback_full_blocks(
                        req, written, "migrate", sync=True
                    )
            except Exception:  # noqa: BLE001 — flush failure → plain replay
                pass
            self.blocks.free(req.request_id)
            req.state = FAILED
            self._finish_request(
                req, FAILED, error=KvMigrationHandoff(KV_MIGRATION_MARKER)
            )

    def _emit_token(self, req: Request, token: int) -> None:
        if req.finished:
            # cancelled/failed after this step's plan was built but before
            # its token was sampled: emitting would stream a stray token
            # and the done-path below would overwrite CANCELLED with
            # FINISHED, double-counting requests_total
            return
        req.generated.append(token)
        if self.engine_cfg.kv_tier_enabled:
            # tier write-back trigger 2: each DECODE block boundary —
            # position n_written-1's K/V was written by the step that
            # sampled this token, so when n_written crosses a block
            # boundary a new immutable full block exists. Flushing it
            # now is what makes a mid-stream SIGKILL recoverable by
            # fault-in: the generated prefix is already tier-resident.
            n_written = len(req.prompt) + len(req.generated) - 1
            if n_written > 0 and n_written % self.blocks.block_size == 0:
                self._tier_writeback_full_blocks(
                    req, (req.prompt + req.generated)[:n_written], "decode"
                )
        now = time.monotonic()
        self._token_times.append(now)
        self.metrics["tokens_total"].inc()
        first_span: Optional[tuple] = None
        ttft: Optional[float] = None
        with self._lock:
            q = self._out.get(req.request_id)
            if req.request_id not in self._first_token_at:
                self._first_token_at[req.request_id] = now
                sub = self._submitted_at.get(req.request_id)
                if sub is not None:
                    ttft = now - sub
                    self._ttft_tape.observe(ttft)
                    self._recent_ttfts.append((now, ttft))
                    # the request account: the three parts sum to ttft
                    admitted = req.admitted_at if req.admitted_at is not None else sub
                    started = (
                        req.prefill_started_at
                        if req.prefill_started_at is not None
                        else admitted
                    )
                    stages = self._request_stages
                    stages["first_tokens"] += 1
                    stages["queue_s"] += admitted - sub
                    stages["prefill_wait_s"] += started - admitted
                    stages["prefill_run_s"] += now - started
                    wire = self._trace_ctx.get(req.request_id)
                    if wire is not None:
                        first_span = (wire, ttft)
        # SLO-ledger stamps: TTFT on the first token, the inter-token
        # gap on every later one (one histogram observe = bisect +
        # increment; the request object carries the per-token state)
        slo_labels = {
            "deployment": self.slo_deployment,
            "tenant_class": req.tenant_class,
        }
        if ttft is not None:
            if req.record_slo:
                self.metrics["ttft"].observe(ttft, labels=slo_labels)
        elif req.last_emit_at is not None:
            gap = now - req.last_emit_at
            self._recent_itls.append((now, gap))
            if gap > req.max_itl_s:
                req.max_itl_s = gap
            if req.record_slo:
                self.metrics["itl"].observe(gap, labels=slo_labels)
        req.last_emit_at = now
        if first_span is not None:
            # TTFT span under the caller's trace: engine admission +
            # queue + prefill chunks up to the first sampled token
            end_us = timeline._now_us()
            _tracing.record_span(
                first_span[0], "llm_first_token",
                end_us - first_span[1] * 1e6, end_us, category="inference",
                request_id=req.request_id,
                prompt_tokens=len(req.prompt),
                cached_prefix_tokens=req.cached_prefix_tokens,
            )
        if q is not None:
            self._hold(q, token, now=ttft is not None)
        done = (
            len(req.generated) >= req.max_new_tokens
            or (req.eos_token is not None and token == req.eos_token)
        )
        if done:
            # index the finished conversation's full blocks (multi-turn
            # reuse) BEFORE finish() releases them to the cache LRU.
            # Only positions whose K/V is actually written qualify: the
            # final sampled token's K/V never was (its decode step never
            # runs), so the registered prefix stops one token short.
            written = (req.prompt + req.generated)[: req.context_len - 1]
            self.blocks.register_prefix(req.request_id, written)
        if done and self.scheduler.finish(req, FINISHED):
            # finish() returns False when cancel() won the race after the
            # req.finished guard above — the cancel path already notified
            # the waiter and counted the outcome
            self._finish_request(req, FINISHED, error=None)

    def _finish_request(self, req: Request, state: str, error: Optional[Exception]) -> None:
        outcome = {FINISHED: "finished", CANCELLED: "cancelled"}.get(state, "failed")
        if self.spec is not None:
            try:
                self.spec.release(req.request_id)
            except Exception:  # noqa: BLE001 — draft cleanup is best-effort
                pass
        now = time.monotonic()
        with self._lock:
            q = self._out.get(req.request_id)
            submitted = self._submitted_at.pop(req.request_id, None)
            wire = self._trace_ctx.pop(req.request_id, None)
            first_token = self._first_token_at.pop(req.request_id, None)
            self._books[outcome] = self._books.get(outcome, 0) + 1
            if q is not None:
                # the queue stays for a late tokens() call; stamp it so an
                # abandoned stream is reaped instead of pinned forever
                self._finished_at[req.request_id] = now
        self._close_ledger(req, outcome, submitted, first_token, now, wire, error)
        if wire is not None and submitted is not None:
            # whole-request span under the caller's trace: admission
            # through the last decode step (covers every prefill chunk
            # and decode token the step loop ran for this request)
            end_us = timeline._now_us()
            _tracing.record_span(
                wire, "llm_request",
                end_us - (time.monotonic() - submitted) * 1e6, end_us,
                category="inference",
                request_id=req.request_id,
                outcome=outcome,
                generated_tokens=len(req.generated),
                preemptions=req.preemptions,
            )
        if q is not None:
            self._hold(q, error if error is not None else _END, now=True)
        self.metrics["requests_total"].inc(labels={"outcome": outcome})

    def _close_ledger(
        self,
        req: Request,
        outcome: str,
        submitted: Optional[float],
        first_token: Optional[float],
        now: float,
        wire,
        error: Optional[Exception],
    ) -> None:
        """Close a request's SLO ledger: observe e2e, split its token
        work into goodput vs fault cost, and file the flight-recorder
        entry (flagged when the request violated an SLO target, was
        preempted, or ended abnormally — those are exactly the outliers
        an operator asks the recorder about)."""
        from ray_tpu.observability.slo import flight_recorder

        labels = {
            "deployment": self.slo_deployment,
            "tenant_class": req.tenant_class,
        }
        e2e = (now - submitted) if submitted is not None else None
        ttft = (
            first_token - submitted
            if submitted is not None and first_token is not None
            else None
        )
        if e2e is not None and req.record_slo:
            self.metrics["e2e"].observe(e2e, labels=labels)
        n_gen = len(req.generated)
        if n_gen:
            if outcome == "finished":
                self.metrics["goodput"].inc(n_gen, labels=labels)
            else:
                # decode work that never reached a satisfied client is
                # fault cost, attributed by why it was thrown away
                self.metrics["fault"].inc(
                    n_gen,
                    labels={"deployment": self.slo_deployment, "reason": outcome},
                )
        flags: List[str] = []
        if outcome != "finished":
            flags.append(outcome)
        if req.preemptions:
            flags.append("preempted")
        if ttft is not None and ttft > GLOBAL_CONFIG.slo_ttft_slow_s:
            flags.append("slow_ttft")
        if req.max_itl_s > GLOBAL_CONFIG.slo_itl_slow_s:
            flags.append("slow_itl")
        stages = {k: round(float(v), 6) for k, v in req.ledger_stages.items()}
        if submitted is not None and req.admitted_at is not None:
            stages["queue"] = round(max(0.0, req.admitted_at - submitted), 6)
        if req.admitted_at is not None and req.prefill_done_at is not None:
            stages["prefill"] = round(
                max(0.0, req.prefill_done_at - req.admitted_at), 6
            )
        if req.prefill_started_at is not None:
            # the request account's split of what the first token waited for
            # after admission: behind other requests' chunks, then its own
            if req.admitted_at is not None:
                stages["prefill_wait"] = round(
                    max(0.0, req.prefill_started_at - req.admitted_at), 6
                )
            if first_token is not None:
                stages["prefill_run"] = round(
                    max(0.0, first_token - req.prefill_started_at), 6
                )
        if first_token is not None:
            stages["decode"] = round(max(0.0, now - first_token), 6)
        entry = {
            "tier": "engine",
            "request_id": req.request_id,
            "trace_id": wire[0] if wire else None,
            "deployment": self.slo_deployment,
            "tenant_class": req.tenant_class,
            "outcome": outcome,
            "error": repr(error) if error is not None else None,
            "ttft_s": round(ttft, 6) if ttft is not None else None,
            "e2e_s": round(e2e, 6) if e2e is not None else None,
            "max_itl_s": round(req.max_itl_s, 6),
            "prompt_tokens": len(req.prompt),
            "generated_tokens": n_gen,
            "cached_prefix_tokens": req.cached_prefix_tokens,
            "preemptions": req.preemptions,
            "stages": stages,
            "flags": flags,
        }
        # slowest-K keys on TOTAL latency (TTFT only when the request
        # never streamed): a fast-first-token request that then decoded
        # for minutes is exactly the outlier the heap must retain
        flight_recorder().add(
            entry,
            flagged=bool(flags),
            slow_key=e2e if e2e is not None else ttft,
        )

    def _fail_all(self, error: Exception) -> None:
        for req in self.scheduler.take_all():
            self.blocks.free(req.request_id)
            req.state = FAILED
            self._finish_request(req, FAILED, error=error)
        self._drop_unread()
        # no launch follows a failure: the errors, and whatever an earlier
        # step left held, go out now
        self._deliver_held("direct")

    # -- held wake-ups ----------------------------------------------------
    def _hold(self, q: "queue.Queue", item: Any, now: bool = False) -> None:
        """What ``q.put(item)`` was on the step thread: the item is
        committed, its consumer is woken by a later delivery; ``now``: by the
        next one, however many streams wait (a first token, an end)."""
        with self._held_lock:
            self._held.append((q, item, time.perf_counter(), now))

    def _deliver_held(self, where: str, most: Optional[int] = None) -> None:
        """Put every held item, in the order it was committed, and count
        it under ``where``. The puts run under the lock, so that two
        deliverers cannot interleave one stream's items. ``most``: wake
        that many streams and no more, those that wait longest first and
        those held ``now`` among them; a stream that is woken gets all it
        has, the others' items stay held, so that each wakes once for
        several tokens (``tokens_chunked`` hands them on as one item)."""
        with self._held_lock:
            held = self._held
            if not held:
                return
            self._held = []
            if most is not None:
                going = {id(q) for q, _, _, now in held if now}
                for q, _, _, _ in held:  # in commit order: the longest wait first
                    if len(going) >= most:
                        break
                    going.add(id(q))
                self._wakes_left = max(0, most - len(going))
                self._held = [entry for entry in held if id(entry[0]) not in going]
                held = [entry for entry in held if id(entry[0]) in going]
            committed = 0.0
            for q, item, at, _ in held:
                q.put(item)
                committed += at
            wakes = self._wakes
            wakes["items"] += len(held)
            wakes[where] += len(held)
            wakes["held_s"] += len(held) * time.perf_counter() - committed

    def _wake(self, where: str, most: Optional[int] = None) -> None:
        """:meth:`_deliver_held` on the step account, for a caller that is
        in no phase: the wake-ups are ``emit``'s second half, its part
        ``deliver`` (the first, ``commit``, runs while the device is idle;
        this one, after a launch, beside it)."""
        if self._held:
            with self._clock.phase("emit"), self._clock.part("deliver"):
                self._deliver_held(where, most)

    def _wake_after_launch(self) -> None:
        """The runner's ``launched`` hook: the program is on its way and
        this thread is about to wait for it, so the consumers of the LAST
        launch run while the device does: as many of them as a device
        step hides."""
        self._wake("after_launch", self._wakes_left)

    def _reap_abandoned_streams(self) -> None:
        ttl = self.engine_cfg.finished_stream_ttl_s
        if ttl <= 0:
            return
        now = time.monotonic()
        if now < self._next_stream_reap:
            return
        self._next_stream_reap = now + min(ttl, 10.0)
        with self._lock:
            dead = [r for r, t in self._finished_at.items() if now - t > ttl]
            for rid in dead:
                self._finished_at.pop(rid, None)
                self._out.pop(rid, None)

    def _tokens_per_s(self) -> float:
        # expired timestamps are dropped incrementally: the step loop calls
        # this via _update_gauges, and a full copy-and-filter of the 2048-cap
        # deque every step was measurable overhead at decode rates
        now = time.monotonic()
        tt = self._token_times
        while tt and now - tt[0] > 10.0:
            tt.popleft()
        if len(tt) < 2:
            return 0.0
        span = max(now - tt[0], 1e-6)
        return len(tt) / span

    @staticmethod
    def _recent_quantile(samples: deque, q: float, window_s: float = 30.0) -> float:
        """Quantile over the (ts, value) samples inside ``window_s`` —
        the sliding-window control signal the autopilot steers on. 0.0
        when the window is empty (callers treat that as "no signal").
        Reads a list() copy: the reporter thread computes this while the
        step thread appends."""
        now = time.monotonic()
        vals = sorted(v for ts, v in list(samples) if now - ts <= window_s)
        if not vals:
            return 0.0
        idx = min(len(vals) - 1, max(0, int(math.ceil(q * len(vals))) - 1))
        return vals[idx]

    def _prefill_tokens_per_s(self, window_s: float = 10.0) -> float:
        now = time.monotonic()
        entries = [(ts, n) for ts, n in list(self._prefill_token_times)
                   if now - ts <= window_s]
        if not entries:
            return 0.0
        span = max(now - entries[0][0], 1e-6)
        return sum(n for _ts, n in entries) / span

    def _ttft_quantiles(self) -> Dict[str, float]:
        """stats()/bench back-compat shape ({"p50", "p99"}), now derived
        from this engine's log-bucket TTFT tape instead of a sorted
        sample deque (the old deque fed quantile GAUGES, which cannot be
        aggregated across replicas — the histogram can)."""
        with self._lock:
            if self._ttft_tape.total == 0:
                return {}
            p50 = self._ttft_tape.quantile(0.50)
            p99 = self._ttft_tape.quantile(0.99)
        return {"p50": p50, "p99": p99}

    def _update_gauges(self, decode_batch: int) -> None:
        m = self.metrics
        m["decode_batch"].set(decode_batch)
        pre = self.scheduler.total_preempted - getattr(self, "_preempt_seen", 0)
        if pre > 0:
            m["preemptions_total"].inc(pre)
        self._preempt_seen = self.scheduler.total_preempted
        # fault-cost ledger: prefill tokens readmissions had to RE-RUN
        # (delta-tracked from the scheduler like the preemption counter)
        replay = self.scheduler.total_replay_prefill_tokens - self._replay_seen
        if replay > 0:
            m["fault"].inc(
                replay,
                labels={
                    "deployment": self.slo_deployment,
                    "reason": "preempt_replay",
                },
            )
            self._replay_seen = self.scheduler.total_replay_prefill_tokens
        # prefix-cache counters ride the same delta pattern (the manager
        # owns the source of truth; /metrics gets monotonic counters)
        for attr, name in (
            ("prefix_hits_total", "prefix_hits_total"),
            ("prefix_tokens_saved_total", "prefix_tokens_saved_total"),
            ("cow_copies_total", "cow_copies_total"),
        ):
            cur = getattr(self.blocks, attr)
            seen = self._prefix_seen.get(attr, 0)
            if cur > seen:
                m[name].inc(cur - seen)
                self._prefix_seen[attr] = cur
        # the remaining gauges cost lock round-trips — at hundreds of
        # steps/s that's pure step-loop overhead, so refresh them at 4 Hz
        # (first step always publishes, so metric names appear on
        # /metrics as soon as anything runs)
        now = time.monotonic()
        if now < self._next_gauge_refresh:
            return
        self._next_gauge_refresh = now + 0.25
        m["cache_util"].set(self.blocks.utilization())
        m["queue_depth"].set(self.scheduler.queue_depth())
        m["active"].set(len(self.scheduler.running))
        m["tps"].set(round(self._tokens_per_s(), 2))
        # adaptive speculative k rides the same 4 Hz refresh: steer the
        # live draft budget on the acceptance rate measured since the
        # last refresh window with enough proposals to mean something.
        # Shrinking/growing k never recompiles — the verify bucket stays
        # sized for speculative_k+1 and shorter windows pad via true_len.
        if self.spec is not None:
            prop, acc = self._spec_proposed, self._spec_accepted
            d_prop = prop - self._spec_window_seen[0]
            d_acc = acc - self._spec_window_seen[1]
            if d_prop >= 8:
                rate = d_acc / d_prop
                self._spec_acceptance = rate
                m["spec_acceptance"].set(round(rate, 4))
                self._spec_window_seen = (prop, acc)
                if self.engine_cfg.speculative_adaptive:
                    k = (
                        self.scheduler.spec_k_live
                        or self.engine_cfg.speculative_k
                    )
                    if rate < self.engine_cfg.speculative_accept_floor and k > 1:
                        self.scheduler.spec_k_live = k - 1
                    elif rate >= 0.75 and k < self.engine_cfg.speculative_k:
                        self.scheduler.spec_k_live = k + 1

    # -- introspection ----------------------------------------------------
    def set_deployment_name(self, name: str) -> None:
        """Stamp the serve deployment label onto this engine's SLO
        series (serve/replica.py calls this through the callable before
        any request arrives)."""
        self.slo_deployment = str(name or "")

    def ledger_books(self) -> Dict[str, Any]:
        """Intake conservation books (slo.books_balanced): submitted ==
        finished + failed + cancelled + queued + running, exactly, at
        quiesce — the gate that proves no fault path (chaos kill, drain
        cutoff, preemption churn, disconnect cancel) leaks a request."""
        with self._lock:
            books = dict(self._books)
        s = self.scheduler.stats()
        books.update(
            kind="engine",
            queued=s["queue_depth"],
            running=s["running"],
            total_admitted=s["total_admitted"],
            replay_prefill_tokens=self.scheduler.total_replay_prefill_tokens,
        )
        return books

    def slo_snapshot(self) -> Dict[str, Any]:
        """This process's SLO ledger state + this engine's books (the
        serve controller's ``slo_report`` fans this out per replica)."""
        from ray_tpu.observability import slo as _slo

        snap = _slo.snapshot()
        snap["books"] = self.ledger_books()
        snap["tier"] = "engine"
        snap["deployment"] = self.slo_deployment
        return snap

    def stats(self) -> Dict[str, Any]:
        # draft + verify buckets ride the same zero-recompile gate: a
        # speculative engine's compile books count the draft runner too
        spec_compiles = self.spec.compile_count() if self.spec is not None else 0
        spec_recompiles = (
            self.spec.recompiles_after_warmup() if self.spec is not None else 0
        )
        s = {
            "scheduler": self.scheduler.stats(),
            "blocks": self.blocks.stats(),
            "prefix_cache": self.blocks.prefix_stats(),
            "total_steps": self.total_steps,
            "draining": self._draining,
            "compile_count": self.runner.compile_count() + spec_compiles,
            "recompiles_after_warmup": (
                self.runner.recompiles_after_warmup() + spec_recompiles
            ),
            "tokens_per_s": round(self._tokens_per_s(), 2),
            "ttft": {k: round(v, 6) for k, v in self._ttft_quantiles().items()},
            "step_phases": self._step_phases(),
            # the phases' parts: accounts of their own, in no leaf
            "step_parts": {
                f"{name.replace('.', '_')}_s": seconds
                for name, seconds in self._clock.parts_total.items()
            },
            # what the step thread WAITED: its own, each phase's and part's
            "step_offcpu": self._step_offcpu(),
            # the laps that stood still: how many of how many, whose seconds
            "step_stalls": dict(self._clock.stalls),
            # the waits for the device, and those that found it done
            "device_reads": dict(self._clock.reads),
            # where the step thread's puts were delivered, and how long held
            "wakes": dict(self._wakes),
            # how often the loop launched a decode step before it had read the last
            "decode_ahead": dict(self._decode_ahead),
            # of those ahead, the ones a due chunk alone allowed: the slots
            # had room (a key beside the dict: its three are pinned)
            "decode_ahead_chunk_due": self._ahead_chunk_due,
            # how wide the decode and verify launches gathered (the target
            # runner's own; a draft model's runner keeps its own count)
            "decode_width": dict(self.runner.decode_width),
            # what the prefill launches' attention read of the table, likewise
            "prefill_width": dict(self.runner.prefill_width),
            # what a token leaves in the cache (the model's description)
            "kv_layout": self.runner.cache_layout.describe(),
            # a model whose attention selects: how much of the live context
            # the launches' queries chose (None: every query sees all of it)
            "sparse_attention": (
                dict(self.runner.sparse_attention) if self.runner.sparse_attention else None
            ),
            # a model with recurrent layers: what a SEQUENCE holds beside
            # its rows, and the pool of slots it is held in (None / zeros else)
            # (``stored_bytes_per_seq``: what the pool's layout really holds a
            # sequence on the device, ``bytes_per_seq`` where nothing is padded)
            "state_layout": (
                {**self.runner.state_layout.describe(),
                 "stored_bytes_per_seq": self.runner.state_layout.stored_bytes_per_seq}
                if self.runner.state_layout else None
            ),
            "state_pool": self.blocks.slot_stats(),
            # a pool a layer group: blocks, in use now and at the peak, taken,
            # given back by sliding
            "kv_pools": self.blocks.pool_stats(),
            "request_stages": dict(self._request_stages),
            "startup": {
                **self.startup,
                "warmup_programs": dict(self.startup["warmup_programs"]),
            },
        }
        s["kv_held"] = dict(self._kv_held)
        if self.runner.moe is not None:
            # what the experts saw (the target runner's; absent for a dense model)
            s["moe"] = {kind: dict(acc) for kind, acc in self.runner.moe.items()}
        if self.spec is not None:
            prop, acc = self._spec_proposed, self._spec_accepted
            s["speculative"] = {
                "k": self.engine_cfg.speculative_k,
                "k_live": self.scheduler.spec_k_live,
                "draft": self.engine_cfg.speculative_draft,
                "proposed_tokens": prop,
                "accepted_tokens": acc,
                "rollbacks": self._spec_rollbacks,
                **(self._mtp_counts if self._mtp else {}),
                "acceptance_rate": round(acc / prop, 4) if prop else 0.0,
            }
        return s

    def _step_phases(self) -> Dict[str, float]:
        """The step account: seconds of the step-loop thread's life per
        leaf phase (monotonic, engine lifetime: a reader differences two
        calls), their sum ``wall_s``, and ``host_serial_s``, the part in
        which the device waits for the host. Outside that sum: the longest
        step so far (``longest_wall_s``, without its ``loop_wait``) and the
        ``device_wait`` of that same step."""
        clock = self._clock
        total = dict(clock.total)
        out = {f"{name}_s": seconds for name, seconds in total.items()}
        out["wall_s"] = sum(total.values())
        out["host_serial_s"] = out["wall_s"] - total["device_wait"] - total["loop_wait"]
        out["longest_wall_s"] = clock.longest_wall_s
        out["longest_device_wait_s"] = clock.longest_device_wait_s
        return out

    def _step_offcpu(self) -> Dict[str, float]:
        """The step thread's off-CPU seconds (as ``step_phases``: engine
        lifetime, a reader differences two calls), each clamped to ``[0,`` the
        wall seconds of the same key``]``. ``wall_s``: the thread's own,
        ``wall - cpu`` over its settled laps, one CPU reading a lap.
        ``host_serial_s``: that less the two waiting leaves', as
        ``step_phases.host_serial_s`` is ``wall_s`` less their wall.
        ``<phase>_s`` a leaf and ``<phase>_<part>_s`` a part: its wall seconds
        less its CPU seconds, read every lap where the CPU clock is cheap and
        scaled up from a sample of laps where it is not
        (``timeline._SAMPLE_EVERY``);
        ``unblocked_s``: their sum over :data:`UNBLOCKED`."""
        clock = self._clock
        walls = {**clock.total, **clock.parts_total}
        blocks = {
            name: min(max(0.0, walls[name] - seconds), walls[name])
            for name, seconds in {**clock.cpu_total, **clock.parts_cpu_total}.items()
        }
        out = {f"{name.replace('.', '_')}_s": seconds for name, seconds in blocks.items()}
        wall = sum(clock.total.values())
        out["wall_s"] = min(max(0.0, clock.thread_offcpu), wall)
        host = wall - clock.total["device_wait"] - clock.total["loop_wait"]
        waits = blocks["device_wait"] + blocks["loop_wait"]
        out["host_serial_s"] = min(max(0.0, out["wall_s"] - waits), host)
        out["unblocked_s"] = sum(blocks[name] for name in UNBLOCKED)
        return out

    def routing_stats(self) -> Dict[str, Any]:
        """Compact replica load + cache-locality digest, gossiped to
        routers through the serve controller's long-poll channel
        (replica -> controller push -> router). Everything here must
        stay small and picklable — it travels on every routing-set
        update."""
        if self.engine_cfg.kv_tier_enabled:
            # snapshot under the tier lock: the step + publisher threads
            # move_to_end/popitem concurrently, and an unlocked dict()
            # copy can raise "OrderedDict mutated during iteration" and
            # fail the whole stats report
            with self._tier_lock:
                tier_adverts = dict(self._tier_adverts)
        else:
            tier_adverts = {}
        return {
            "queue_depth": self.scheduler.queue_depth(),
            "cache_util": round(self.blocks.utilization(), 4),
            "outstanding_tokens": self.scheduler.outstanding_tokens(),
            "block_size": self.blocks.block_size,
            "prefix_digest": self.blocks.prefix_digest(),
            # tier adverts ride the same gossip beat: digest hex ->
            # routable descriptor, bounded by kv_tier_max_adverts. A
            # digest absent from a holder's NEXT report is thereby
            # RETRACTED — routers diff per-actor advert sets and purge
            # in one hop instead of waiting out a TTL.
            "kv_tier": tier_adverts,
            "draining": self._draining,
            # queue-pressure export for the ingress tier: the admission
            # BOUND (so a proxy can judge fullness, not just depth) and
            # the monotonic intake count (so shed-vs-admitted reconciles
            # without a replica round-trip per request)
            "max_queue_depth": self.engine_cfg.max_queue_depth,
            "total_admitted": self.scheduler.total_admitted,
            # closed-loop control signals (serve/controller.py autopilot
            # + ingress ITL-derived shed threshold + disagg pool-ratio
            # adaptation): sliding-window latency quantiles and token
            # throughput, NOT the lifetime ledger tapes — the autopilot
            # must feel current burn, not the whole run's history
            "ttft_p99_s": round(self._recent_quantile(self._recent_ttfts, 0.99), 6),
            "itl_p99_s": round(self._recent_quantile(self._recent_itls, 0.99), 6),
            "decode_tokens_per_s": round(self._tokens_per_s(), 2),
            "prefill_tokens_per_s": round(self._prefill_tokens_per_s(), 2),
        }

    def healthy(self) -> bool:
        """Liveness the serve controller polls through ``replica.health()``:
        False once the step loop is dead, or wedged — work pending with
        no loop heartbeat inside ``step_stall_unhealthy_s``. A stalled
        step thread doesn't stop the actor's async loop from answering
        RPCs, so plain reachability checks can never catch it."""
        if self._stop.is_set():
            return False
        if self._thread is None or not self._thread.is_alive():
            return False
        stall = self.engine_cfg.step_stall_unhealthy_s
        if stall > 0 and self.scheduler.has_work():
            return time.monotonic() - self._last_beat <= stall
        return True

    def wait_idle(self, timeout: float = 30.0) -> bool:
        """Block until no queued/running work remains (drain helper)."""
        deadline = time.monotonic() + timeout
        while self.scheduler.has_work() or self._unread is not None:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.005)
        return True
