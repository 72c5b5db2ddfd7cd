"""Typed runtime configuration flags.

Equivalent of the reference's RayConfig flag system
(``src/ray/common/ray_config_def.h:18-22``): every flag has a type and a
default, is overridable per-process via ``RAY_TPU_<name>`` environment
variables, and cluster-wide via a ``system_config`` dict handed to
``ray_tpu.init``. Flags are plain attributes on the singleton ``GlobalConfig``
so hot paths read them without dict lookups.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any, Dict

_ENV_PREFIX = "RAY_TPU_"


@dataclass
class GlobalConfig:
    # --- object store ---
    object_store_memory_bytes: int = 2 * 1024**3
    # Objects at or below this size are stored inline in the owner's
    # in-process memory store and shipped inside RPC replies instead of
    # going through shared memory (reference: task output inlining).
    max_direct_call_object_size: int = 100 * 1024
    # Task RESULTS at or below this size ride back to the owner inside
    # the task-done reply and are served from the owner's in-process
    # inline cache — get() on a small result never touches the shm store
    # or makes an extra RPC (reference: direct-call inline return limit).
    # Distinct from max_direct_call_object_size (puts / arg inlining) so
    # the two paths can be tuned independently.
    inline_result_threshold_bytes: int = 100 * 1024
    # Chunk size for node-to-node object transfer (reference 5 MiB,
    # ``ray_config_def.h:341``).
    object_transfer_chunk_bytes: int = 5 * 1024**2
    # Spill to disk when the store is above this fraction of capacity.
    object_spilling_threshold: float = 0.8
    object_spilling_dir: str = ""
    # Per-process cap on the segment reuse pool (plasma-arena-style warm
    # page recycling in StoreClient; 0 disables recycling).
    object_store_recycle_bytes: int = 512 * 1024**2

    # --- pull manager (core/pull_manager.py: daemon↔daemon transfer) ---
    #: admission budget for concurrent inbound transfers: total bytes of
    #: objects in flight; further pulls queue FIFO (backpressure instead
    #: of OOMing the daemon). An object larger than the whole budget is
    #: still admitted when it is alone. <=0 disables admission control.
    pull_max_inflight_bytes: int = 256 * 1024**2
    #: per-chunk fetch timeout — a stalled source costs one chunk
    #: timeout, not the whole-transfer timeout
    pull_chunk_timeout_s: float = 15.0
    #: chunk fetch attempts per source before failing over to the next
    #: source (the transfer RESUMES from the last verified offset there)
    pull_chunk_retries: int = 3
    #: chunk requests kept in flight per transfer (reference: pipelined
    #: 5 MiB chunks) — serial request/response is latency-bound on
    #: virtualized hosts; verification and shm writes stay strictly
    #: sequential regardless. 1 disables pipelining.
    pull_pipeline_depth: int = 4
    #: daemon-side receive-segment reuse pool cap (bytes): segments of
    #: transfer-received objects deleted with ``recycle_receive`` (and
    #: aborted receives this store created) are renamed into a warm
    #: LRU pool instead of unlinked, and ``allocate_receive`` reuses a
    #: fitting one — repeated KV migrations skip segment create/zero
    #: (this 4.4-kernel sandbox can't MADV_POPULATE; warm inodes are
    #: the substitute). 0 disables the pool.
    receive_segment_pool_bytes: int = 128 * 1024**2

    # --- scheduling ---
    # Hybrid policy: prefer local node until it exceeds this utilization
    # fraction, then spread over the top-k best nodes (reference
    # ``hybrid_scheduling_policy.h:50``).
    scheduler_spread_threshold: float = 0.5
    scheduler_top_k_fraction: float = 0.2
    worker_lease_timeout_s: float = 30.0
    # An infeasible-NOW lease parks this long daemon-side before the
    # infeasible verdict is returned: parked demand is what the
    # autoscaler sees, and a joining node can make the shape feasible
    # (reference: infeasible tasks wait forever and feed the load
    # report).
    infeasible_lease_grace_s: float = 10.0
    # The CLIENT keeps retrying an infeasible verdict this long before
    # failing the task — covers node boot time on autoscaled clusters
    # (raise it when provisioning takes minutes) while keeping a crisp
    # terminal error for static ones.
    infeasible_fail_after_s: float = 30.0
    # Release a blocked worker's CPU share back to the node pool while it
    # parks in a sync get/arg-fetch, re-acquiring on wake (reference:
    # NotifyDirectCallTaskBlocked). Without it, a task graph whose
    # consumers saturate every CPU while blocked on producers that still
    # need a CPU deadlocks — the documented fault-recovery trap.
    blocked_worker_resource_release: bool = True
    # Max workers the pool will cold-start concurrently (startup tokens).
    worker_maximum_startup_concurrency: int = 4
    idle_worker_killing_time_s: float = 300.0
    num_initial_workers: int = 0

    # --- streaming generators ---
    #: producer pauses once (produced - consumed) reaches this many
    #: items; consumer progress resumes it (reference ObjectRefStream
    #: consumer-position protocol, ``task_manager.h:102``). 0 disables.
    streaming_generator_backpressure_items: int = 64
    #: inline stream items at or above this size ride a RAW push frame
    #: (core/rpc.py kind 5): the item bytes travel out-of-band instead of
    #: being pickled+msgpacked into the push payload on both ends. Small
    #: items stay on the plain path (a RAW frame costs an extra header).
    #: <0 disables RAW stream pushes entirely.
    rpc_raw_stream_min_bytes: int = 8 * 1024

    # --- fault tolerance ---
    task_max_retries: int = 3
    actor_max_restarts: int = 0
    health_check_period_s: float = 1.0
    health_check_failure_threshold: int = 5
    lineage_pinning_enabled: bool = True
    #: resubmission attempts per lost object (``task_manager.h:273``)
    max_lineage_reconstructions: int = 3
    #: concurrent worker leases per scheduling class (lease pipelining,
    #: ``normal_task_submitter.cc:351``)
    max_lease_pumps: int = 16
    #: how long an idle held lease waits for more same-class work before
    #: being returned
    lease_linger_s: float = 0.02
    #: specs per push RPC on a held lease (serial worker-side execution);
    #: the adaptive divisor in _drain_on_lease shrinks batches once pumps
    #: fan out, so this is the micro-task amortization ceiling
    lease_push_batch: int = 32
    #: a pump spawns a sibling when its push has been in flight this long
    #: with work still queued (demand-adaptive lease pipelining: micro
    #: tasks amortize on one lease; long/blocked tasks fan out to more
    #: workers). Must sit well above micro-task push round-trips even on
    #: a contended box, or noop floods cascade into eager fan-out.
    lease_pump_growth_s: float = 0.05

    # --- observability ---
    #: serve a Prometheus /metrics endpoint from daemons + controller
    metrics_export_enabled: bool = True
    #: fixed metrics port (0 = auto-assign per process)
    metrics_port: int = 0
    #: bind address for /metrics ("0.0.0.0" for off-host Prometheus)
    metrics_bind_host: str = "127.0.0.1"
    #: tail worker logs and forward them to connected drivers
    log_to_driver: bool = True
    #: push task lifecycle events to the controller (state API `list tasks`)
    task_events_enabled: bool = True
    #: distributed-tracing sample rate in [0, 1]: a fresh trace root is
    #: sampled at request entry points (driver submit, serve router
    #: dispatch) with this probability; children inherit the verdict
    #: causally. 0 (default) keeps the submit hot path span-free — one
    #: contextvar read + one float compare per submit, no allocation.
    trace_sample_rate: float = 0.0
    #: byte budget for worker-exported timeline event chunks retained on
    #: the controller (observability/timeline.py): past it the OLDEST
    #: exports are dropped; a dead node's chunks are reaped with it.
    timeline_kv_max_bytes: int = 16 * 1024**2
    #: grace window for daemons to re-register/sync after a controller
    #: restart before unadopted restored state is rescheduled
    controller_restore_grace_s: float = 10.0
    #: controller snapshot (WAL compaction) period; mutations acked
    #: between ticks are covered by the WAL, so raising this trades
    #: replay length for snapshot churn, never durability
    controller_persist_interval_s: float = 1.0
    #: controller WAL fsync policy: fsync every N appended records
    #: (1 = every record, the zero-loss default); 0 = flush to the OS
    #: only (process-crash safe, not host-crash safe). See core/wal.py.
    controller_wal_fsync: int = 1
    #: active controller lease heartbeat period (core/wal.py lease file;
    #: a hot standby polls the same file at this period)
    controller_lease_interval_s: float = 0.5
    #: lease staleness bound: a standby takes over when the lease stamp
    #: is older than this; the ACTIVE self-fences acks at ~75% of it
    #: (stops acking mutations strictly before a standby can assume the
    #: lease is dead — the classic lease safety margin)
    controller_lease_timeout_s: float = 2.0

    # --- SLO ledger (observability/slo.py) ---
    #: flight-recorder slowest-K slots per process (fixed-size heap of
    #: the slowest requests by e2e, TTFT when the request never
    #: streamed). 0 keeps only flagged entries.
    slo_flight_recorder_slots: int = 32
    #: flight-recorder ring capacity for FLAGGED requests (SLO-violating,
    #: resumed, preempted, shed, failed) — newest win
    slo_flight_flagged_slots: int = 128
    #: TTFT above this flags a request into the flight recorder (and the
    #: traffic simulator's default TTFT SLO target)
    slo_ttft_slow_s: float = 2.0
    #: max inter-token gap above this flags a request (ITL SLO target)
    slo_itl_slow_s: float = 1.0

    # --- memory monitor (``common/memory_monitor.h:52``) ---
    memory_monitor_enabled: bool = True
    #: kill the newest leased task worker when the node's available
    #: memory falls below this fraction (owners resubmit per max_retries)
    memory_monitor_min_available_fraction: float = 0.03
    memory_monitor_period_s: float = 1.0

    # --- hang defense (observability/event_stats.py, util/reaper.py) ---
    #: instrument owned asyncio loops with a heartbeat + stall watchdog
    event_loop_monitor_enabled: bool = True
    #: heartbeat period; also the watchdog's check interval
    event_loop_tick_s: float = 0.1
    #: heartbeat silence that counts as a stall (dump + stall counter).
    #: The loop-lag gauge is exported regardless; this only gates dumps.
    event_loop_stall_threshold_s: float = 5.0
    #: rate limit between stack dumps while a stall persists
    event_loop_stall_dump_interval_s: float = 30.0
    #: >0: a stall persisting this long HARD-EXITS the process (code 70).
    #: Off by default — production stalls should dump and recover; tests
    #: set it so a wedged process dies visibly instead of freezing pytest.
    watchdog_abort_after_s: float = 0.0
    #: escalating reap: SIGTERM grace before SIGKILL, then SIGKILL grace
    reap_term_grace_s: float = 2.0
    reap_kill_grace_s: float = 3.0

    # --- node drain / preemption (core/node_daemon.py, controller) ---
    #: how long a draining node lets running tasks finish (and library
    #: controllers migrate actors) before it flushes objects and exits
    drain_grace_s: float = 30.0
    #: treat SIGTERM to a worker-node daemon as a preemption warning:
    #: self-report drain, run the grace, exit cleanly — instead of
    #: stopping abruptly (spot/maintenance reclaims deliver SIGTERM)
    drain_on_sigterm: bool = True
    #: >0: poll the accelerator maintenance-event probe this often and
    #: self-drain when an event is imminent (0 disables; the probe is
    #: pluggable via accelerators.tpu.set_metadata_fetcher)
    preemption_probe_period_s: float = 0.0
    #: replicate primary shm object copies to a peer node during drain
    #: so consumers re-fetch instead of paying lineage reconstruction
    drain_flush_objects: bool = True

    # --- serve routing (serve/router.py, serve/replica.py) ---
    #: how often a replica hosting a gossip-capable callable (one that
    #: exposes ``routing_stats()``, e.g. an LLM engine) pushes its load +
    #: prefix digest to the serve controller (propagated to routers via
    #: the long-poll channel). <= 0 disables the reporter thread.
    serve_replica_stats_period_s: float = 0.25
    #: routing stats older than this fall back to pow-2 choice — a
    #: stale digest must not keep steering traffic at a replica whose
    #: cache (or queue) has moved on
    serve_routing_stats_ttl_s: float = 5.0
    #: cache-affinity blend weight: a replica's score is
    #: outstanding_tokens - weight * matched_prefix_tokens, lowest wins.
    #: 1.0 values a cached token exactly as much as a token of queue
    #: backlog (it removes one prefill token of work); raise it to pin
    #: conversations harder, 0 disables affinity (pure least-tokens).
    serve_affinity_weight: float = 1.0
    #: how often the serve controller polls replica.health() (the user
    #: callable's check_health — e.g. the LLM engine's wedged-step-loop
    #: detector) and restarts replicas that ANSWER but report unhealthy.
    #: Liveness reaping alone never catches a stalled engine whose actor
    #: loop still replies. <= 0 disables the poll.
    serve_replica_health_period_s: float = 1.0

    # --- disaggregated prefill/decode serving (inference/kv_transfer.py) ---
    #: budget for the whole prefill-pool handoff (dispatch prefill_export
    #: + KV publish) before the router degrades the request to plain
    #: single-replica generation — the failure ladder's first rung
    serve_disagg_handoff_timeout_s: float = 30.0
    #: prompts whose FULL blocks span fewer tokens than this skip the
    #: disagg handoff entirely (migrating a couple of blocks costs more
    #: than re-prefilling them); also the router's guard when gossip
    #: hasn't told it the engine block size yet
    serve_disagg_min_prompt_tokens: int = 16
    #: published KV exports nobody consumed are reaped after this long
    kv_export_ttl_s: float = 120.0
    #: descriptor-inline payload cap for daemon-less processes (local
    #: mode / unit tests) — bigger exports fail → plain generation
    kv_inline_max_bytes: int = 32 * 1024**2

    # --- cluster-wide KV prefix tier (inference/kv_transfer.py + node_daemon) ---
    #: cap on tier-resident prefix digests a replica advertises through
    #: the routing-stats gossip (MRU subset; the daemon registry can
    #: hold more — adverts are the routable window, not the inventory)
    kv_tier_max_adverts: int = 32
    #: daemon-side tier registry TTL: blocks nobody faulted in for this
    #: long are dropped (and their shm objects deleted). The tier is a
    #: cache, not a durable store.
    kv_tier_ttl_s: float = 600.0
    #: entry cap per daemon tier registry; oldest-first eviction with
    #: object deletion. Bounds shm spent on spilled KV.
    kv_tier_max_entries: int = 512
    #: how long a router keeps tier directory entries sourced from a
    #: DEAD replica before expiring them (the daemon still holds the
    #: bytes — a replacement replica re-adverts within one gossip beat,
    #: so this is the warm-restart bridge window). Explicit retraction
    #: by a LIVE holder purges immediately, not on this TTL.
    kv_tier_advert_ttl_s: float = 30.0
    #: explicit tier namespace override. The daemon tier registry is
    #: node-global and the chain digest names only the TOKENS, so tier
    #: keys are scoped by a model-identity namespace (config + weight
    #: fingerprint, derived per engine) — two deployments of the same
    #: architecture with different weights can never serve each other's
    #: KV. Set this to force a shared (or extra-isolated) namespace.
    kv_tier_namespace: str = ""

    # --- serve ingress (serve/ingress.py: the HTTP/SSE front door) ---
    #: per-request deadline when the client sends none (header
    #: x-request-timeout-s / body timeout_s override, clamped to this as
    #: a ceiling) — stamped into the ambient core/deadline budget so the
    #: engine stops decoding for callers that gave up
    serve_ingress_default_timeout_s: float = 60.0
    #: Retry-After hint (seconds) on pressure sheds; rate-limit sheds
    #: compute the exact bucket-refill wait instead
    serve_ingress_retry_after_s: float = 1.0
    #: default per-tenant token-bucket refill rate, in COST units/s
    #: (cost of one request = prompt tokens + max_new_tokens); tenants
    #: without an explicit TenantPolicy get this
    serve_ingress_default_rate: float = 4000.0
    #: default per-tenant bucket capacity (burst allowance), cost units
    serve_ingress_default_burst: float = 8000.0
    #: how often an ingress replica snapshots per-tenant bucket fill
    #: levels to the serve controller (restored by replacement replicas,
    #: so a restart doesn't refill every tenant's budget). <= 0 disables.
    serve_ingress_bucket_snapshot_period_s: float = 1.0

    # --- runtime_env ---
    #: TTL on the driver-side working_dir/py_modules change-signature
    #: cache: within this window a .remote() carrying a runtime_env
    #: reuses the cached tree signature instead of stat-walking the
    #: whole directory per submit. An edit re-ships at most this many
    #: seconds late. 0 disables the cache (walk every submit).
    tree_signature_ttl_s: float = 5.0

    # --- RPC ---
    #: frames per coalesced batch frame on a connection flush (RPC
    #: micro-batching): a flush packs up to this many queued frames into
    #: one wire frame, so the receiver dispatches them from a single
    #: read wakeup. 1 disables batching (every frame travels alone).
    rpc_batch_max_frames: int = 64
    #: byte ceiling for one batch frame — oversized frames travel alone
    #: so a huge payload can't add head-of-line latency to tiny ones
    rpc_batch_max_bytes: int = 256 * 1024
    #: asyncio StreamReader buffer limit per connection. The stock 64 KiB
    #: limit pauses/resumes the transport every 128 KiB — measured ~0.27
    #: GB/s loopback on the bench box vs ~0.85 GB/s at 2 MiB. Bulk RAW
    #: payloads (chunk transfer) ride the same connections, so this is a
    #: first-order data-plane throughput knob.
    rpc_stream_buffer_bytes: int = 2 * 1024**2
    #: kernel socket send/receive buffer request per RPC connection
    #: (best-effort; uses SO_SNDBUFFORCE/SO_RCVBUFFORCE when privileged
    #: so the wmem_max cap doesn't clamp it). Big socket buffers let the
    #: transport hand a whole chunk to the kernel in one send instead of
    #: memcpy'ing the unsent tail into the asyncio write buffer. 0
    #: leaves the system defaults.
    rpc_socket_buffer_bytes: int = 4 * 1024**2
    rpc_connect_timeout_s: float = 10.0
    rpc_retry_base_delay_s: float = 0.05
    rpc_retry_max_delay_s: float = 2.0
    rpc_max_retries: int = 5
    # --- exactly-once request dedup (core/rpc.py) ---
    #: stamp mutating RPCs with (client id, request id) and answer
    #: retried duplicates from a server-side reply cache instead of
    #: re-executing the handler (the lost-reply trap). Idempotent
    #: methods (rpc.IDEMPOTENT_METHODS) skip the cache entirely.
    rpc_dedup_enabled: bool = True
    #: reply-cache bounds per server process; oldest-first eviction. A
    #: retry arriving after its entry was evicted re-executes — size the
    #: window well past (retry budget × max backoff) worth of traffic.
    rpc_dedup_cache_entries: int = 4096
    rpc_dedup_cache_max_bytes: int = 32 * 1024**2

    # --- task events / observability ---
    task_events_buffer_size: int = 10000
    task_events_flush_period_s: float = 1.0
    metrics_report_period_s: float = 2.0

    # --- testing / chaos ---
    testing_rpc_failure: str = ""  # legacy "method:failure_prob" (pre-handler)
    #: seeded per-method fault plan: "method:mode:prob[:param],..." with
    #: mode in {request_drop, reply_drop, delay, disconnect} — see
    #: util/chaos.py::RpcFaultPlan for the grammar and determinism
    #: contract. Empty = no injection.
    testing_rpc_chaos: str = ""
    #: RNG seed for the fault plan; 0 = generate one (printed at
    #: activation so any failure reproduces from the log)
    testing_rpc_chaos_seed: int = 0
    #: seeded DATA-PLANE fault plan consulted by the pull manager once
    #: per chunk attempt: "mode:prob[:param],..." with mode in
    #: {chunk_drop, chunk_corrupt, chunk_stall, source_die_mid_transfer}
    #: — see util/chaos.py::DataFaultPlan (same determinism contract as
    #: RpcFaultPlan). Empty = no injection.
    testing_pull_chaos: str = ""
    #: RNG seed for the pull fault plan; 0 = generate one (logged at
    #: activation for replay)
    testing_pull_chaos_seed: int = 0
    #: seeded REPLICA fault plan consulted by the LLM engine's step loop
    #: once per executed step phase: "mode:prob[:param][:max],..." with
    #: mode in {kill_mid_decode, kill_mid_prefill, stall} — see
    #: util/chaos.py::ReplicaFaultPlan (same determinism contract as
    #: RpcFaultPlan). Empty = no injection.
    testing_replica_chaos: str = ""
    #: RNG seed for the replica fault plan; 0 = generate one (logged at
    #: activation for replay)
    testing_replica_chaos_seed: int = 0
    #: seeded KV-TIER fault plan consulted by the tier fault-in path
    #: once per phase execution: "mode:prob[:param][:max],..." with mode
    #: in {missing_block, corrupt_block, stale_advert,
    #: kill_mid_migration} — see util/chaos.py::KvTierFaultPlan (same
    #: determinism contract as ReplicaFaultPlan). Empty = no injection.
    testing_kv_tier_chaos: str = ""
    #: RNG seed for the KV-tier fault plan; 0 = generate one (logged at
    #: activation for replay)
    testing_kv_tier_chaos_seed: int = 0
    #: seeded CONTROLLER fault plan consulted by the control plane's
    #: WAL-append ("mutation"), snapshot ("snapshot") and lease-heartbeat
    #: ("lease") paths: "mode:prob[:param][:max],..." with mode in
    #: {kill_mid_mutation, kill_mid_snapshot, partition,
    #: zombie_resurrect} — see util/chaos.py::ControllerFaultPlan (same
    #: determinism contract as ReplicaFaultPlan). Empty = no injection.
    testing_controller_chaos: str = ""
    #: RNG seed for the controller fault plan; 0 = generate one (logged
    #: at activation for replay)
    testing_controller_chaos_seed: int = 0
    #: MASTER chaos seed: when non-zero, every fault plan whose own seed
    #: knob is 0 derives its seed deterministically from this one value
    #: (util/chaos.py::derive_plan_seed — keyed blake2b of the plan
    #: label), so a run arming all three plans reproduces from ONE
    #: logged number instead of three. Explicit per-plan seeds still win.
    testing_chaos_seed: int = 0

    def reset(self) -> None:
        for f in fields(self):
            setattr(self, f.name, f.default)
        self.apply_env()

    def apply_env(self) -> None:
        for f in fields(self):
            env = os.environ.get(_ENV_PREFIX + f.name)
            if env is None:
                continue
            setattr(self, f.name, _parse(env, f.type))

    def apply_system_config(self, overrides: Dict[str, Any]) -> None:
        valid = {f.name: f for f in fields(self)}
        for key, value in overrides.items():
            if key not in valid:
                raise ValueError(f"unknown system_config key: {key!r}")
            setattr(self, key, value)

    def to_dict(self) -> Dict[str, Any]:
        return {f.name: getattr(self, f.name) for f in fields(self)}


#: JAX's own variable for its persistent compilation cache. JAX reads it
#: itself at import; nothing in this package sets the jax config option.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def ensure_compile_cache_env(env: Dict[str, str]) -> str:
    """Point a child-process env at the persistent JAX compile cache and
    return the directory. A ``JAX_COMPILATION_CACHE_DIR`` that is already
    set is left alone (workers inherit it through the daemon's env);
    otherwise the cache lives at ONE fixed directory inside the checkout,
    next to the package. The directory is part of what makes an entry
    findable again, so it never derives from a pid, a time or tempfile."""
    path = env.get(COMPILE_CACHE_ENV)
    if not path:
        checkout = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        path = env[COMPILE_CACHE_ENV] = os.path.join(checkout, ".jax_compile_cache")
    return path


def _parse(raw: str, typ: Any) -> Any:
    typ = str(typ)
    if "bool" in typ:
        return raw.lower() in ("1", "true", "yes", "on")
    if "int" in typ:
        return int(raw)
    if "float" in typ:
        return float(raw)
    return raw


GLOBAL_CONFIG = GlobalConfig()
GLOBAL_CONFIG.apply_env()


def serialize_config() -> str:
    return json.dumps(GLOBAL_CONFIG.to_dict())


def load_config(serialized: str) -> None:
    GLOBAL_CONFIG.apply_system_config(json.loads(serialized))
