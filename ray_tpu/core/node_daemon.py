"""Per-node daemon (raylet equivalent).

Reference: ``src/ray/raylet/`` — the node-local authority owning the shm
object store thread (``object_manager/object_manager.cc:28-41``), the
worker pool with startup tokens (``worker_pool.h:83``), the lease protocol
(``NodeManager::HandleRequestWorkerLease``, ``node_manager.cc:1797``),
local + spillback scheduling, placement-group bundle reservation 2PC
(``placement_group_resource_manager.{h,cc}``), and node-to-node object
transfer (``object_manager/``: pull/push with 5 MiB chunks).

Design notes vs. the reference:
  * Leases are granted against fixed-point local resources; when the local
    node can't fit (or exceeds the hybrid threshold) the reply carries a
    *spillback* target chosen from the controller-synced cluster view —
    the submitter re-requests there, exactly like raylet spillback.
  * Object transfer is daemon↔daemon chunked RPC pull; POSIX shm unlink
    semantics stand in for plasma's pinning during reads.
  * Workers are spawned as ``python -m ray_tpu.core.worker_main`` with a
    spawn token; the pool correlates registration with purpose (idle pool
    vs. dedicated actor worker — reference dedicated workers).
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.core.config import GLOBAL_CONFIG
from ray_tpu.core.ids import ActorID, NodeID, ObjectID
from ray_tpu.core.object_store import ShmStore
from ray_tpu.core.resources import NodeResources, ResourceSet
from ray_tpu.core.rpc import RpcClient, RpcServer, ServerConnection
from ray_tpu.core.scheduling_policies import (
    feasible_anywhere,
    fits,
    pick_node_hybrid,
    utilization,
)
from ray_tpu.core.task_spec import DefaultScheduling, PlacementGroupScheduling, TaskSpec

logger = logging.getLogger(__name__)


@dataclass
class WorkerProc:
    pid: int
    proc: subprocess.Popen
    token: str
    host: str = ""
    port: int = 0
    registered: bool = False
    leased: bool = False
    claimed: bool = False  # a pending _pop_worker will take this worker
    actor_id: Optional[ActorID] = None
    # resources held by a dedicated actor worker, released on its death
    actor_resources: Optional[Dict[str, float]] = None
    actor_bundle_key: Optional[Tuple[bytes, int]] = None
    tpu_chips: Optional[List[int]] = None  # chip ids assigned to this worker
    conn: Optional[ServerConnection] = None
    client: Optional[RpcClient] = None
    idle_since: float = 0.0  # monotonic ts when last parked in the idle pool
    # CPU resources this worker holds that are currently RELEASED back to
    # the node pool because it blocks in a sync get/arg-fetch (reference:
    # NotifyDirectCallTaskBlocked). Stays set past an unblock that can't
    # re-acquire (bounded oversubscription); the lease/actor release
    # withholds exactly this amount so accounting always balances.
    blocked_released: Optional[Dict[str, float]] = None


@dataclass
class Lease:
    lease_id: int
    resources: Dict[str, float]
    worker: WorkerProc
    bundle_key: Optional[Tuple[bytes, int]] = None
    tpu_chips: Optional[List[int]] = None


@dataclass
class _ViewNode:
    node_id: bytes
    host: str
    port: int
    total: Dict[str, float]
    available: Dict[str, float]
    labels: Dict[str, str]


class NodeDaemon:
    def __init__(
        self,
        controller_host: str,
        controller_port: int,
        *,
        resources: Optional[Dict[str, float]] = None,
        host: str = "127.0.0.1",
        port: int = 0,
        session_dir: Optional[str] = None,
        labels: Optional[Dict[str, str]] = None,
    ):
        self.node_id = NodeID.from_random()
        self.host = host
        self.server = RpcServer(host, port)
        #: highest controller incarnation epoch this daemon has seen;
        #: the server-side fencing gate rejects writes stamped lower
        #: (a deposed controller double-writing after a takeover)
        self._controller_epoch_seen = 0
        self.server.epoch_gate = self._controller_epoch_gate
        # retry-by-default toward the control plane: every mutating call
        # is dedup-stamped (core/rpc.py), so surviving a controller
        # restart or a chaos'd reply is a transparent retry, not an error
        self.controller = RpcClient(
            controller_host, controller_port, name="controller",
            default_retries=GLOBAL_CONFIG.rpc_max_retries,
            role="controller",
        )
        self.controller_addr = (controller_host, controller_port)
        res = dict(resources or {})
        res.setdefault("CPU", float(os.cpu_count() or 1))
        merged_labels = dict(labels or {})
        # Accelerator autodetection (reference: raylet consults the
        # accelerator registry at startup). Explicit user resources win.
        if "TPU" not in res:
            from ray_tpu.accelerators import detect_node_accelerators

            auto_res, auto_labels = detect_node_accelerators()
            for k, v in auto_res.items():
                res.setdefault(k, v)
            for k, v in auto_labels.items():
                merged_labels.setdefault(k, v)
        self.resources = NodeResources(ResourceSet(res), labels=merged_labels or None)
        # Node-wide TPU chip-id pool: every worker holding TPU resources
        # gets concrete chip ids (TPU_VISIBLE_CHIPS isolation).
        self._tpu_chips_free: List[int] = list(range(int(res.get("TPU", 0))))
        self.store = ShmStore()
        from ray_tpu.core.pull_manager import PullManager

        self.pulls = PullManager(self.store, self._peer)
        self.session_dir = session_dir or f"/tmp/ray_tpu/session_{os.getpid()}"
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)
        self.workers: Dict[str, WorkerProc] = {}  # token -> proc
        self.idle: List[WorkerProc] = []
        self.leases: Dict[int, Lease] = {}
        self._lease_counter = 0
        self._pending_actor_specs: Dict[str, TaskSpec] = {}  # token -> spec
        self._bundle_pools: Dict[Tuple[bytes, int], NodeResources] = {}
        self._prepared_bundles: Dict[Tuple[bytes, int], Dict[str, float]] = {}
        self._view: List[_ViewNode] = []
        self._peer_clients: Dict[Tuple[str, int], RpcClient] = {}
        self._tasks: List[asyncio.Task] = []
        self._capacity_event = asyncio.Event()
        # lease requests currently parked on capacity (autoscaler demand)
        self._waiting_leases: Dict[int, Dict[str, float]] = {}
        self._waiting_seq = 0
        self._last_oom_check = 0.0
        self._stopping = False
        # relocation reports already delivered to the controller: its
        # directory is in-memory only, so a restarted controller needs
        # them REPLAYED after re-registration or owners mid-fetch would
        # fall back to lineage reconstruction (bounded ring)
        self._reported_moves: List[Dict[str, Any]] = []
        # cluster KV-tier registry: chain-digest hex -> {"desc", "expiry"}
        # (oldest-put first; refreshed to MRU on every get). The DAEMON
        # owns tier entries — they survive the replica process that
        # published them, which is the whole warm-restart story; TTL/cap
        # eviction (and the object delete that goes with it) runs in
        # _reap_loop.
        self._kv_tier: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._last_kv_tier_sweep = 0.0
        # drain protocol state (graceful preemption; see drain())
        self._draining = False
        self._drain_task: Optional[asyncio.Task] = None
        #: hook the hosting process installs (node_main) so a completed
        #: drain exits the process; None for in-process daemons (tests)
        self.on_drained = None
        for name in [m for m in dir(self) if m.startswith("d_")]:
            self.server.register(name[2:], getattr(self, name))

    # ---- lifecycle -----------------------------------------------------
    async def start(self) -> int:
        port = await self.server.start()
        self.port = port
        # hang defense: a blocked daemon loop freezes leases/object pulls
        # for every worker on this node — watchdog it
        from ray_tpu.observability.event_stats import install_loop_monitor

        install_loop_monitor(asyncio.get_event_loop(), "node_daemon")
        self._start_metrics()
        await self._register_with_controller(port)
        self._tasks.append(asyncio.ensure_future(self._sync_loop()))
        self._tasks.append(asyncio.ensure_future(self._reap_loop()))
        self._tasks.append(asyncio.ensure_future(self._log_tail_loop()))
        # Prestart (reference WorkerPool prestart): warm the pool so the
        # first wave of leases skips cold-start latency.
        for _ in range(GLOBAL_CONFIG.num_initial_workers):
            self._spawn_worker()
        if GLOBAL_CONFIG.preemption_probe_period_s > 0:
            self._tasks.append(asyncio.ensure_future(self._preemption_probe_loop()))
        return port

    # ---- drain protocol (graceful preemption) --------------------------
    async def _preemption_probe_loop(self) -> None:
        """Poll the pluggable maintenance-event probe (GCE metadata by
        default, injectable via accelerators.tpu.set_metadata_fetcher);
        an imminent event self-initiates drain — the SIGTERM-less half of
        preemption detection (host maintenance warns via metadata first)."""
        from ray_tpu.accelerators.tpu import maintenance_event_imminent

        loop = asyncio.get_event_loop()
        while not self._stopping and not self._draining:
            await asyncio.sleep(GLOBAL_CONFIG.preemption_probe_period_s)
            try:
                # the probe does blocking I/O (metadata HTTP) — keep it
                # off the daemon's event loop
                imminent = await loop.run_in_executor(None, maintenance_event_imminent)
            except Exception:
                continue
            if imminent:
                self.start_drain("maintenance event imminent")
                return

    def start_drain(self, reason: str) -> None:
        """Idempotently kick off the drain sequence (callable from signal
        handlers, the probe loop, and the ``drain`` RPC)."""
        if self._draining or self._stopping:
            return
        self._draining = True
        logger.warning("node %s draining: %s", self.node_id.hex()[:8], reason)
        # wake parked lease requests so they re-evaluate → spillback away
        self._notify_capacity()
        self._drain_task = asyncio.ensure_future(self._drain(reason))

    async def d_drain(self, payload, conn):
        """Drain RPC (reference GCS ``DrainNode`` delivered to the
        raylet): stop accepting work, finish what's running within the
        grace, replicate primary object copies off-node, exit cleanly."""
        self.start_drain(payload.get("reason", "drain RPC"))
        return {"ok": True, "draining": True}

    async def _drain(self, reason: str) -> None:
        from ray_tpu.core.deadline import Deadline

        deadline = Deadline.after(GLOBAL_CONFIG.drain_grace_s)
        # 1. self-report: the controller pulls us from the scheduling pool
        #    and pushes the DRAINING event to subscribed drivers/libraries
        try:
            await self.controller.call(
                "drain_node",
                {"node_id": self.node_id.binary(), "reason": reason},
                timeout=5,
            )
        except Exception:
            logger.warning("drain self-report failed", exc_info=True)
        # 2. let running work finish: leases (tasks) drain by completing;
        #    actors drain when their library controller migrates/kills
        #    them (Serve unroutes, Train checkpoints then fails over on
        #    node death). Poll — both counts only shrink now.
        while not deadline.expired and not self._stopping:
            busy_actors = sum(1 for w in self.workers.values() if w.actor_id is not None)
            if not self.leases and not busy_actors:
                break
            await asyncio.sleep(0.1)
        if self.leases:
            logger.warning(
                "drain grace expired with %d lease(s) still running — "
                "falling back to abrupt teardown", len(self.leases),
            )
        # 3. replicate primary shm copies to a peer so consumers re-fetch
        #    instead of paying lineage reconstruction (bounded by the
        #    remaining grace; best-effort)
        if GLOBAL_CONFIG.drain_flush_objects and not self._stopping:
            try:
                await self._flush_objects(deadline)
            except Exception:
                logger.warning("drain object flush failed", exc_info=True)
        # 3b. the grace is spent: any worker still hosting an actor or a
        #    running lease is in the documented abrupt-death fallback —
        #    reap it BEFORE deregistering. Deregistration makes the
        #    controller restart our actors (and resubmit our tasks)
        #    elsewhere immediately; a stale worker that outlives it can
        #    still answer pushes from clients with cached addresses, so
        #    one actor briefly has TWO live incarnations — the old one
        #    answering a call the new one should get (the test_drain
        #    pid2==pid1 flake: the budget-free restart happened, but the
        #    not-yet-reaped old worker answered first), and a task
        #    re-executed elsewhere can double its side effects.
        stale = [
            w.proc
            for w in self.workers.values()
            if w.actor_id is not None or w.leased
        ]
        if stale and not self._stopping:
            from ray_tpu.util.reaper import reap_all

            await asyncio.get_event_loop().run_in_executor(
                None, lambda: reap_all(stale)
            )
        # 4. deregister: the controller fails our remaining actors over
        #    budget-free NOW instead of waiting out the health checker
        try:
            await self.controller.call(
                "deregister_node",
                {"node_id": self.node_id.binary(), "reason": f"drained: {reason}"},
                timeout=5,
            )
        except Exception:
            logger.warning("drain deregister failed", exc_info=True)
        logger.info("drain complete (%s)", reason)
        if self.on_drained is not None:
            try:
                self.on_drained()
            except Exception:
                pass

    async def _flush_objects(self, deadline) -> None:
        """Ask a live peer daemon to pull every local primary copy, then
        record the relocations with the controller (the owner-side fetch
        fallback consults that directory when our copies vanish)."""
        peers = [
            n for n in self._view if n.node_id != self.node_id.binary()
        ]
        if not peers:
            return
        # primaries only: transfer-received replicas already live on
        # their source node — re-replicating them burns the bounded grace
        # and pollutes the relocation ring for no added durability.
        # Unsealed entries are mid-receive and not ours to replicate.
        entries = [
            e
            for e in self.store.list_entries()
            if e.get("primary", True) and e.get("sealed", True)
        ]
        if not entries:
            return
        moves: List[Dict[str, Any]] = []
        for i, entry in enumerate(entries):
            if deadline.expired or self._stopping:
                logger.warning(
                    "drain flush ran out of grace: %d/%d objects replicated",
                    len(moves), len(entries),
                )
                break
            peer = peers[i % len(peers)]
            object_id = bytes.fromhex(entry["object_id"])  # list_entries is hex
            try:
                meta = await self._peer(peer.host, peer.port).call(
                    "pull_object",
                    {
                        "object_id": object_id,
                        "sources": [(self.host, self.port)],
                    },
                    timeout=max(1.0, min(60.0, deadline.remaining())),
                )
            except Exception:
                logger.warning(
                    "drain flush of %s to %s:%s failed",
                    object_id.hex()[:12], peer.host, peer.port, exc_info=True,
                )
                continue
            if meta is not None and meta.get("segment"):
                moves.append(
                    {
                        "object_id": object_id,
                        "node_id": peer.node_id,
                        "host": peer.host,
                        "port": peer.port,
                    }
                )
                # the peer holds the replica now: stop claiming the
                # object so our shutdown doesn't unlink the (possibly
                # shared-inode) segment out from under it
                self.store.forget(ObjectID(object_id))
        if moves:
            await self.controller.call(
                "report_relocated", {"moves": moves}, timeout=10
            )
            # remember what we told the controller: a controller restart
            # mid-drain loses the directory, and the re-register path
            # replays these (bounded like the controller-side ring)
            self._reported_moves.extend(moves)
            del self._reported_moves[:-4096]
            logger.info("drain: replicated %d object(s) off-node", len(moves))

    # ---- memory monitor (OOM killer) -----------------------------------
    @staticmethod
    def _memory_available_fraction() -> float:
        """MemAvailable/MemTotal from /proc/meminfo (no psutil dep)."""
        try:
            info = {}
            with open("/proc/meminfo") as f:
                for line in f:
                    k, _, rest = line.partition(":")
                    info[k] = int(rest.strip().split()[0])
            return info["MemAvailable"] / max(1, info["MemTotal"])
        except Exception:
            return 1.0  # unknown platform: never trigger

    def _oom_check(self, available_fraction: Optional[float] = None) -> Optional[WorkerProc]:
        """Reference ``MemoryMonitor`` + ``WorkerKillingPolicy``: when the
        node runs out of memory, kill the NEWEST leased pooled worker
        (newest-first loses the least progress; reference FIFO policy).
        The owner resubmits the task if it has retries left — a task
        submitted with max_retries=0 fails as WorkerCrashedError, the
        same contract as any worker death. Returns the victim (already
        terminated) or None."""
        if not GLOBAL_CONFIG.memory_monitor_enabled:
            return None
        frac = (
            available_fraction
            if available_fraction is not None
            else self._memory_available_fraction()
        )
        if frac >= GLOBAL_CONFIG.memory_monitor_min_available_fraction:
            return None
        leased = [
            l.worker
            for l in sorted(self.leases.values(), key=lambda l: l.lease_id)
            if l.worker.actor_id is None
        ]
        if not leased:
            return None
        victim = leased[-1]  # newest lease = least progress lost
        logger.warning(
            "memory monitor: available fraction %.3f below %.3f — killing "
            "newest task worker pid=%d",
            frac, GLOBAL_CONFIG.memory_monitor_min_available_fraction, victim.pid,
        )
        try:
            victim.proc.kill()
        except Exception:
            pass
        return victim

    async def _register_with_controller(self, port: int) -> None:
        await self.controller.call(
            "register_node",
            {
                "node_id": self.node_id.binary(),
                "host": self.host,
                "port": port,
                "resources": self.resources.total.to_dict(),
                "labels": self.resources.labels,
                # held PG bundles: a restarted controller re-adopts these
                # instead of double-reserving the PG elsewhere
                "bundles": [
                    {
                        "pg_id": key[0],
                        "bundle_index": key[1],
                        "resources": pool.total.to_dict(),
                    }
                    for key, pool in self._bundle_pools.items()
                ],
            },
            retries=GLOBAL_CONFIG.rpc_max_retries,
        )

    def _start_metrics(self) -> None:
        """Prometheus /metrics endpoint (reference ``metrics_agent.py`` →
        ``prometheus_exporter.py``; system metrics per ``metric_defs.cc``)."""
        if not GLOBAL_CONFIG.metrics_export_enabled:
            self.metrics_port = 0
            return
        from ray_tpu.observability.metrics import Gauge, MetricsServer, on_collect

        nid = self.node_id.hex()[:12]
        g_store_used = Gauge("raytpu_object_store_used_bytes", "shm store bytes in use", ("node",))
        g_store_objs = Gauge("raytpu_object_store_num_objects", "objects in the shm store", ("node",))
        g_spilled = Gauge("raytpu_object_store_num_spilled", "objects spilled to disk", ("node",))
        g_workers = Gauge("raytpu_workers", "worker processes", ("node", "state"))
        g_leases = Gauge("raytpu_active_leases", "granted worker leases", ("node",))
        g_avail = Gauge("raytpu_resource_available", "available resource capacity", ("node", "resource"))

        def sample() -> None:
            st = self.store.stats()
            labels = {"node": nid}
            g_store_used.set(st["used_bytes"], labels)
            g_store_objs.set(st["num_objects"], labels)
            g_spilled.set(st["num_spilled"], labels)
            g_workers.set(len(self.workers), {"node": nid, "state": "total"})
            g_workers.set(len(self.idle), {"node": nid, "state": "idle"})
            g_leases.set(len(self.leases), labels)
            for res, val in self.resources.available.to_dict().items():
                g_avail.set(val, {"node": nid, "resource": res})

        self._metrics_cb = on_collect(sample)
        self._metrics_server = MetricsServer(host=GLOBAL_CONFIG.metrics_bind_host, port=GLOBAL_CONFIG.metrics_port)
        self.metrics_port = self._metrics_server.port
        logger.info("metrics at http://127.0.0.1:%d/metrics", self.metrics_port)

    async def stop(self) -> None:
        self._stopping = True
        from ray_tpu.observability.event_stats import remove_loop_monitor

        remove_loop_monitor(asyncio.get_event_loop())
        if getattr(self, "_metrics_server", None) is not None:
            from ray_tpu.observability.metrics import remove_collect

            remove_collect(self._metrics_cb)
            self._metrics_server.stop()
        for t in self._tasks:
            t.cancel()
        if self._drain_task is not None:
            self._drain_task.cancel()
        # Escalating reap of every child we spawned (hang defense): one
        # shared SIGTERM grace for the whole pool, SIGKILL the survivors —
        # a worker ignoring SIGTERM (stuck in native code, masked signal)
        # must not outlive its daemon and leak into the next session. Off
        # the event loop: wait() grace windows would block it.
        from ray_tpu.util.reaper import reap_all

        procs = [w.proc for w in self.workers.values()]
        if procs:
            survivors = await asyncio.get_event_loop().run_in_executor(
                None, lambda: reap_all(procs)
            )
            if survivors:
                logger.error("unreapable worker pids (D-state?): %s", survivors)
        await self.controller.close()
        for c in self._peer_clients.values():
            await c.close()
        self.store.shutdown()
        await self.server.stop()

    async def _log_tail_loop(self) -> None:
        """Tail this node's worker log files and forward new lines to the
        controller for driver display (reference ``LogMonitor``,
        ``_private/log_monitor.py:103``).

        Known limitation vs the reference: lines are not tagged with a
        job id, so in a multi-driver cluster every driver sees every
        worker's output (the reference filters per job)."""
        if not GLOBAL_CONFIG.log_to_driver:
            return
        import glob as _glob

        offsets: Dict[str, int] = {}
        logs_dir = os.path.join(self.session_dir, "logs")
        while not self._stopping:
            await asyncio.sleep(0.5)
            batch = []
            try:
                for path in _glob.glob(os.path.join(logs_dir, "worker-*.log")):
                    try:
                        size = os.path.getsize(path)
                        off = offsets.get(path, 0)
                        if size < off:
                            off = 0  # truncated/rotated: restart from top
                        if size == off:
                            offsets[path] = off
                            continue
                        with open(path, "rb") as f:
                            f.seek(off)
                            data = f.read(min(size - off, 1 << 16))
                        # advance only past COMPLETE lines — a partial
                        # tail line is re-read next tick, and nothing is
                        # ever skipped (the chunk bound paces big bursts
                        # across ticks instead of dropping them)
                        cut = data.rfind(b"\n")
                        if cut < 0:
                            offsets[path] = off
                            continue
                        offsets[path] = off + cut + 1
                        lines = data[: cut + 1].decode(errors="replace").splitlines()
                        if lines:
                            batch.append(
                                {
                                    "worker": os.path.basename(path),
                                    "lines": lines,
                                }
                            )
                    except OSError:
                        continue
                if batch:
                    await self.controller.call(
                        "worker_logs",
                        {"node_id": self.node_id.binary(), "batch": batch},
                        timeout=10,
                    )
            except Exception:
                pass  # forwarding is best-effort

    # ---- resource sync (ray_syncer) -----------------------------------
    async def _sync_loop(self) -> None:
        while not self._stopping:
            try:
                reply = await self.controller.call(
                    "sync_resources",
                    {
                        "node_id": self.node_id.binary(),
                        "available": self.resources.available.to_dict(),
                        "total": self.resources.total.to_dict(),
                        # store + worker counters: what cluster_status()
                        # reports per node without a fan-out RPC
                        "store": self.store.stats(),
                        "num_workers": len(self.workers),
                        "num_leases": len(self.leases),
                        # parked lease shapes: task demand for the
                        # autoscaler's bin-packing
                        "pending_leases": list(self._waiting_leases.values()),
                        # running actors: a restarted controller adopts
                        # these instead of re-scheduling them (GCS-restart
                        # reconciliation, reference raylet reconnect)
                        "actors": [
                            {
                                "actor_id": w.actor_id,
                                "host": w.host,
                                "port": w.port,
                                "pid": w.pid,
                            }
                            for w in self.workers.values()
                            if w.actor_id is not None and w.registered
                        ],
                    },
                    timeout=5,
                )
                # passive fencing-floor update: every sync reply carries
                # the current controller incarnation epoch
                self._note_controller_epoch(reply.get("controller_epoch", 0))
                if reply.get("unknown_node"):
                    # controller restarted and lost node membership:
                    # re-register (carrying held bundles for re-adoption)
                    # and replay unacked session state — the relocation
                    # reports live only in controller memory. Running
                    # actors replay themselves on the next sync's
                    # ``actors`` payload.
                    logger.info("controller does not know us — re-registering")
                    from ray_tpu.observability.rpc_metrics import (
                        CONTROLLER_RECONNECTS,
                    )

                    CONTROLLER_RECONNECTS.inc(labels={"role": "daemon"})
                    await self._register_with_controller(self.port)
                    if self._reported_moves:
                        await self.controller.call(
                            "report_relocated",
                            {"moves": list(self._reported_moves)},
                            timeout=10,
                        )
                    continue
                self._view = [
                    _ViewNode(
                        node_id=n["node_id"],
                        host=n["host"],
                        port=n["port"],
                        total=n["total"],
                        available=n["available"],
                        labels=n.get("labels", {}),
                    )
                    for n in reply["view"]
                ]
            except Exception:
                if not self._stopping:
                    logger.debug("resource sync failed", exc_info=True)
            await asyncio.sleep(0.2)

    # ---- TPU chip-id pool ----------------------------------------------
    def _allocate_tpu_chips(self, n: int) -> Optional[List[int]]:
        if n <= 0:
            return None
        if len(self._tpu_chips_free) < n:
            logger.warning(
                "TPU accounting says %d chips free but id pool has %d",
                n, len(self._tpu_chips_free),
            )
            return None
        chips = self._tpu_chips_free[:n]
        del self._tpu_chips_free[:n]
        return chips

    def _free_tpu_chips(self, chips: Optional[List[int]]) -> None:
        if chips:
            self._tpu_chips_free.extend(chips)
            self._tpu_chips_free.sort()

    # ---- worker pool ---------------------------------------------------
    def _spawn_worker(
        self,
        actor_spec: Optional[TaskSpec] = None,
        tpu_chips: Optional[List[int]] = None,
    ) -> WorkerProc:
        if self._stopping:
            # a lease racing shutdown must not spawn a worker the stop()
            # reap snapshot will never see (leak defense)
            raise RuntimeError("daemon is stopping")
        token = os.urandom(8).hex()
        log_path = os.path.join(self.session_dir, "logs", f"worker-{token}.log")
        log_f = open(log_path, "ab")
        env = dict(os.environ)
        env["RAY_TPU_SPAWN_TOKEN"] = token
        env["RAY_TPU_NODE_ID"] = self.node_id.hex()
        env["RAY_TPU_DAEMON_ADDR"] = f"{self.host}:{self.port}"
        # explicit parent pid: the worker's orphan watch must not trust
        # os.getppid() captured at ITS boot — the daemon can die during
        # that window and the worker would memorize the reparented value
        env["RAY_TPU_DAEMON_PID"] = str(os.getpid())
        env["RAY_TPU_CONTROLLER_ADDR"] = f"{self.controller_addr[0]}:{self.controller_addr[1]}"
        chips = tpu_chips
        if chips is None:
            # Chip-less workers are pinned to CPU (chip ownership): a
            # bare `import jax` in one would otherwise probe the TPU
            # runtime — minutes of instance-metadata retries on non-TPU
            # hosts (the round-5 "suite wedged" class), or grabbing every
            # chip on a real TPU host. A pooled worker later PROMOTED to
            # TPU undoes only THIS pin in w_set_accelerator_env
            # (restoring whatever the operator had set, "" = unset),
            # which refuses once jax has initialized in that process.
            env["RAY_TPU_PREPIN_JAX_PLATFORMS"] = env.get("JAX_PLATFORMS") or ""
            env["JAX_PLATFORMS"] = "cpu"
        else:
            # Dedicated actor workers get their chip isolation at spawn
            # time — before libtpu can initialize (TPU_VISIBLE_CHIPS +
            # topology bounds, reference accelerators/tpu.py:31). An
            # operator-set JAX_PLATFORMS passes through untouched (same
            # contract as the promotion path in w_set_accelerator_env —
            # the two chip-grant paths must not place the same env on
            # different devices); unset means jax picks the TPU it was
            # given.
            from ray_tpu.accelerators.tpu import TPUAcceleratorManager

            env.update(TPUAcceleratorManager.isolation_env([str(c) for c in chips]))
        # Workers share the daemon's process group so a hard node kill
        # (killpg, cluster_utils.remove_node) takes them down too.
        proc = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.worker_main"],
            env=env,
            stdout=log_f,
            stderr=subprocess.STDOUT,
        )
        w = WorkerProc(pid=proc.pid, proc=proc, token=token)
        w.tpu_chips = chips
        self.workers[token] = w
        if actor_spec is not None:
            w.actor_id = actor_spec.actor_id
            self._pending_actor_specs[token] = actor_spec
        return w

    async def d_register_worker(self, payload, conn: ServerConnection):
        token = payload["token"]
        w = self.workers.get(token)
        if w is None:
            raise ValueError(f"unknown spawn token {token}")
        w.host, w.port = payload["host"], payload["port"]
        w.registered = True
        w.conn = conn
        conn.peer_tags["worker_token"] = token
        w.client = RpcClient(
            w.host, w.port, name=f"worker-{token[:6]}", role="worker"
        )
        spec = self._pending_actor_specs.pop(token, None)
        if spec is not None:
            asyncio.ensure_future(self._run_actor_creation(w, spec))
        elif not w.claimed:
            # Workers spawned by a waiting _pop_worker are claimed by that
            # lease — adding them to the idle pool too would double-grant
            # one worker to two leases (deadlock on its execution lane).
            w.idle_since = time.monotonic()
            self.idle.append(w)
        # always notify: a _pop_worker parked on ITS claimed spawn wakes
        # on this registration instead of its poll timeout (the lease
        # grant sits on the submit hot path during pump growth)
        self._notify_capacity()
        return {"node_id": self.node_id.binary()}

    async def _run_actor_creation(self, w: WorkerProc, spec: TaskSpec) -> None:
        try:
            await w.client.call("run_actor_creation", {"spec": spec}, timeout=None)
        except Exception as e:
            logger.warning("actor creation dispatch failed: %r", e)
            try:
                await self.controller.call(
                    "report_actor_death",
                    {"actor_id": spec.actor_id, "reason": f"worker failed: {e!r}"},
                )
            except Exception:
                pass

    async def _reap_loop(self) -> None:
        """Detect worker process deaths (reference: raylet notices socket
        close; here we also poll the pid)."""
        while not self._stopping:
            for token, w in list(self.workers.items()):
                code = w.proc.poll()
                if code is None:
                    continue
                del self.workers[token]
                if w in self.idle:
                    self.idle.remove(w)
                for lease_id, lease in list(self.leases.items()):
                    if lease.worker is w:
                        self._release_lease(lease_id)
                self._release_actor_resources(w)
                self._sweep_recycle_pool(w.proc.pid)
                if w.actor_id is not None:
                    try:
                        await self.controller.call(
                            "report_actor_death",
                            {
                                "actor_id": w.actor_id,
                                "reason": f"worker exited with code {code}",
                                # deaths during OUR drain are preemption
                                # casualties (incl. the pre-deregister
                                # reap of grace overstayers): restarts
                                # must stay budget-free, same as the
                                # deregistration-path failover
                                "drained": self._draining,
                            },
                        )
                    except Exception:
                        pass
            self._kill_idle_workers()
            self._sweep_orphan_pools()
            self._kv_tier_sweep()
            now = time.monotonic()
            if now - self._last_oom_check >= GLOBAL_CONFIG.memory_monitor_period_s:
                self._last_oom_check = now
                self._oom_check()
            await asyncio.sleep(0.1)

    @staticmethod
    def _sweep_recycle_pool(pid: int) -> None:
        """Unlink a dead worker's segment-reuse pool files (named
        ``rt-pool-<pid>-<n>`` by StoreClient.recycle) so they don't leak
        tmpfs memory past the process's lifetime."""
        import glob

        for path in glob.glob(f"/dev/shm/rt-pool-{pid}-*"):
            try:
                os.unlink(path)
            except OSError:
                pass

    _pool_orphan_sweep_period_s = 10.0
    _last_pool_orphan_sweep = 0.0

    def _sweep_orphan_pools(self) -> None:
        """Reap pool files whose owning pid is dead — covers DRIVERS and
        externally-started processes the worker-reap path never sees
        (SIGKILL'd drivers would otherwise shrink usable store capacity
        forever, since pool files count as used in admission control)."""
        import glob

        now = time.monotonic()
        if now - self._last_pool_orphan_sweep < self._pool_orphan_sweep_period_s:
            return
        self._last_pool_orphan_sweep = now
        # rt-pool-<pid>-* (segment reuse pools), rt-chan-<pid>-* (compiled
        # graph channels) and their sem.rt-chan-<pid>-* wakeup semaphores
        # all embed the owning pid
        for path in glob.glob("/dev/shm/rt-pool-*") + glob.glob(
            "/dev/shm/rt-chan-*"
        ) + glob.glob("/dev/shm/sem.rt-chan-*"):
            base = os.path.basename(path)
            if base.startswith("sem."):
                base = base[4:]
            try:
                pid = int(base.split("-")[2])
            except (IndexError, ValueError):
                continue
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            except PermissionError:
                pass  # pid alive under another uid

    def _kill_idle_workers(self) -> None:
        """Reference ``idle_worker_killing``: pooled workers idle past the
        deadline are retired (the floor of ``num_initial_workers`` stays
        warm)."""
        deadline = GLOBAL_CONFIG.idle_worker_killing_time_s
        if deadline <= 0:
            return
        now = time.monotonic()
        keep_floor = GLOBAL_CONFIG.num_initial_workers
        for w in list(self.idle):
            if len(self.idle) <= keep_floor:
                break
            if w.claimed or now - w.idle_since < deadline:
                continue
            self.idle.remove(w)
            try:
                w.proc.terminate()  # reap loop finishes the bookkeeping
            except Exception:
                pass

    # ---- leases (task scheduling) -------------------------------------
    async def d_request_lease(self, payload, conn):
        """The lease hot path (``HandleRequestWorkerLease``).

        Requests that can't be served *right now* are queued daemon-side
        (waiting on capacity/worker changes) rather than bounced back —
        client retry-polling collapses throughput under backlog (reference:
        raylet queues lease requests in the local task manager)."""
        request: Dict[str, float] = payload["resources"]
        strategy = payload.get("strategy")
        deadline = time.monotonic() + 30.0
        # visible to the resource sync → the AUTOSCALER's task-demand
        # signal (reference: resource_demand_scheduler reads queued
        # lease shapes from the load report)
        self._waiting_seq += 1
        wid = self._waiting_seq
        first = True
        grace_deadline = (
            time.monotonic() + GLOBAL_CONFIG.infeasible_lease_grace_s
        )
        try:
            while True:
                reply = await self._try_lease(request, strategy)
                if reply is not None:
                    if reply.get("infeasible") and time.monotonic() < grace_deadline:
                        # infeasible NOW ≠ infeasible forever: park so the
                        # autoscaler sees the demand; a joining node flips
                        # this to a grant/spillback
                        reply = None
                    else:
                        return reply
                if first:
                    first = False
                    self._waiting_leases[wid] = dict(request)
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return {"retry_after": 0.05}
                try:
                    await asyncio.wait_for(
                        self._capacity_event.wait(), timeout=min(0.5, remaining)
                    )
                except (asyncio.TimeoutError, TimeoutError):
                    pass
        finally:
            self._waiting_leases.pop(wid, None)

    def _notify_capacity(self) -> None:
        """Wake queued lease requests (set() resolves current waiters even
        though we clear immediately — single-threaded loop)."""
        self._capacity_event.set()
        self._capacity_event.clear()

    async def _try_lease(self, request: Dict[str, float], strategy):
        """One grant attempt: dict reply, or None = queue and retry."""
        # Draining: no NEW leases land here — spill to a live peer (or
        # report infeasible so the client's retry window + autoscaler
        # replacement take over). PG-bundle leases are exempt: a committed
        # bundle exists only on this node, refusing would wedge the gang.
        if self._draining and not isinstance(strategy, PlacementGroupScheduling):
            reply = self._spillback_or_retry(request, strategy)
            return None if "retry_after" in reply else reply
        # Placement-group leases consume from the bundle pool.
        bundle_key = None
        if isinstance(strategy, PlacementGroupScheduling):
            bundle_key = self._find_bundle(strategy, request)
            if bundle_key is None:
                return None
            pool = self._bundle_pools[bundle_key]
            req = ResourceSet(request)
            pool.allocate(req)
        else:
            req = ResourceSet(request)
            if not self.resources.can_fit(req):
                reply = self._spillback_or_retry(request, strategy)
                return None if "retry_after" in reply else reply
            # hybrid: spill when local utilization is past the threshold
            if (
                self.resources.utilization() >= GLOBAL_CONFIG.scheduler_spread_threshold
                and len(self._view) > 1
            ):
                alt = self._pick_remote(request, strategy)
                if alt is not None and alt.node_id != self.node_id.binary():
                    return {"spillback": (alt.host, alt.port)}
            self.resources.allocate(req)

        worker = await self._pop_worker()
        if worker is None:
            if bundle_key is not None:
                self._bundle_pools[bundle_key].release(ResourceSet(request))
            else:
                self.resources.release(ResourceSet(request))
            return None
        worker.leased = True
        self._lease_counter += 1
        lease = Lease(self._lease_counter, request, worker, bundle_key)
        # TPU isolation for pooled workers: assign chip ids and tell the
        # worker before any task lands on it. A worker that holds chips is
        # chip-BOUND for its lifetime (libtpu can't rebind after init), so
        # it is retired — not pooled — when the lease ends; failure to
        # isolate fails the lease rather than granting an unisolated one,
        # and retires the worker (one that already imported jax under the
        # CPU pin refuses the chips; re-pooling it would offer it again).
        if request.get("TPU", 0) >= 1 and worker.tpu_chips is None:
            chips = self._allocate_tpu_chips(int(request["TPU"]))
            ok = retired = False
            if chips is not None and worker.client is not None:
                try:
                    await worker.client.call(
                        "set_accelerator_env",
                        {"resource": "TPU", "ids": chips},
                        timeout=5,
                    )
                    ok = True
                except Exception:
                    logger.warning(
                        "set_accelerator_env failed; retiring worker %d",
                        worker.pid, exc_info=True,
                    )
                    retired = True
                    try:
                        worker.proc.terminate()
                    except Exception:
                        pass
            if not ok:
                self._free_tpu_chips(chips)
                worker.leased = False
                if not retired and worker not in self.idle:
                    worker.idle_since = time.monotonic()
                    self.idle.append(worker)
                if bundle_key is not None:
                    self._bundle_pools[bundle_key].release(ResourceSet(request))
                else:
                    self.resources.release(ResourceSet(request))
                return None
            worker.tpu_chips = chips
            lease.tpu_chips = chips
        self.leases[lease.lease_id] = lease
        return {
            "grant": {
                "lease_id": lease.lease_id,
                "host": worker.host,
                "port": worker.port,
                "node_id": self.node_id.binary(),
            }
        }

    def _find_bundle(self, strategy: PlacementGroupScheduling, request) -> Optional[Tuple[bytes, int]]:
        if strategy.bundle_index >= 0:
            key = (strategy.pg_id, strategy.bundle_index)
            pool = self._bundle_pools.get(key)
            if pool is not None and pool.can_fit(ResourceSet(request)):
                return key
            return None
        for key, pool in self._bundle_pools.items():
            if key[0] == strategy.pg_id and pool.can_fit(ResourceSet(request)):
                return key
        return None

    def _spillback_or_retry(self, request, strategy):
        alt = self._pick_remote(request, strategy)
        if alt is not None and alt.node_id != self.node_id.binary():
            return {"spillback": (alt.host, alt.port)}
        if self._view and not feasible_anywhere(self._view, request):
            return {"infeasible": True}
        return {"retry_after": 0.05}

    def _pick_remote(self, request, strategy):
        return pick_node_hybrid(
            self._view,
            request,
            strategy if strategy is not None else DefaultScheduling(),
            local_node_id=self.node_id.binary(),
            spread_threshold=GLOBAL_CONFIG.scheduler_spread_threshold,
        )

    async def _pop_worker(self) -> Optional[WorkerProc]:
        while self.idle:
            w = self.idle.pop()
            if w.proc.poll() is None and w.registered:
                return w
        # cold start (startup token accounting: bounded concurrent spawns)
        starting = sum(
            1 for w in self.workers.values() if not w.registered and w.actor_id is None
        )
        if starting >= GLOBAL_CONFIG.worker_maximum_startup_concurrency:
            return None
        w = self._spawn_worker()
        w.claimed = True
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if w.registered:
                w.claimed = False
                return w
            if w.proc.poll() is not None:
                w.claimed = False
                return None
            # event-driven: d_register_worker notifies capacity, so the
            # grant fires the moment the worker registers — the timeout
            # only paces the liveness re-check of the spawned process
            try:
                await asyncio.wait_for(self._capacity_event.wait(), timeout=0.05)
            except (asyncio.TimeoutError, TimeoutError):
                pass
        # spawn timed out: release the claim; if it registered late, give
        # it to the idle pool so it isn't orphaned
        w.claimed = False
        if w.registered and not w.leased and w not in self.idle:
            w.idle_since = time.monotonic()
            self.idle.append(w)
        return None

    async def d_return_lease(self, payload, conn):
        self._release_lease(payload["lease_id"])
        return True

    # ---- blocked-worker resource release (reference raylet
    # NotifyDirectCallTaskBlocked/Unblocked) --------------------------------
    # A worker parked in a sync get/arg-fetch holds CPUs it cannot use —
    # the PR 10 scheduling deadlock: every CPU held by consume tasks
    # blocked on producers that NEED a CPU to (re)run. While blocked, the
    # CPU share of the worker's lease (or actor allocation) goes back to
    # the node pool; on wake it is re-acquired when it fits, otherwise
    # the task finishes briefly oversubscribed and the lease release
    # withholds the already-returned amount. TPU chips are never
    # released: a chip-bound process can't lend its chips.

    def _worker_held_node_resources(self, w: WorkerProc) -> Optional[Dict[str, float]]:
        """The resources ``w`` holds from the NODE pool (bundle-pool
        allocations are excluded — a PG bundle's capacity is not the
        node's to lend)."""
        if w.actor_id is not None:
            if w.actor_resources is not None and w.actor_bundle_key is None:
                return w.actor_resources
            return None
        for lease in self.leases.values():
            if lease.worker is w and lease.bundle_key is None:
                return lease.resources
        return None

    async def d_worker_blocked(self, payload, conn):
        """The worker entered a blocking sync get/arg-fetch: release the
        CPU share of what it holds so other work (e.g. the producer it
        waits on) can be scheduled here. Idempotent per block episode."""
        if not GLOBAL_CONFIG.blocked_worker_resource_release:
            return False
        w = self.workers.get(payload.get("token", ""))
        if w is None or w.blocked_released is not None:
            return False
        held = self._worker_held_node_resources(w)
        cpu = (held or {}).get("CPU", 0.0)
        if cpu <= 0:
            return False
        rel = {"CPU": cpu}
        self.resources.release(ResourceSet(rel))
        w.blocked_released = rel
        self._notify_capacity()
        return True

    async def d_worker_unblocked(self, payload, conn):
        """The worker woke up: re-acquire the released CPUs when they
        fit. When they don't (another task took them meanwhile), the
        task continues oversubscribed and the eventual lease/actor
        release withholds the debt — accounting self-heals even if this
        RPC is lost entirely."""
        w = self.workers.get(payload.get("token", ""))
        if w is None or w.blocked_released is None:
            return False
        rel = ResourceSet(w.blocked_released)
        if self.resources.can_fit(rel):
            self.resources.allocate(rel)
            w.blocked_released = None
            return True
        return False

    def _withhold_blocked_release(self, w: WorkerProc, req: ResourceSet) -> ResourceSet:
        """Subtract the CPUs already returned to the pool while ``w``
        blocked from what a lease/actor release would give back."""
        if w.blocked_released is None:
            return req
        rel, w.blocked_released = w.blocked_released, None
        return req.subtract(ResourceSet(rel), allow_negative=True)

    def _release_lease(self, lease_id: int) -> None:
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return
        req = ResourceSet(lease.resources)
        if lease.bundle_key is not None:
            pool = self._bundle_pools.get(lease.bundle_key)
            if pool is not None:
                pool.release(req)
        else:
            self.resources.release(
                self._withhold_blocked_release(lease.worker, req)
            )
        w = lease.worker
        w.leased = False
        self._notify_capacity()
        if w.tpu_chips is not None and w.actor_id is None:
            # Chip-bound pooled worker: libtpu is (possibly) initialized on
            # these chips, so the process can never serve a different chip
            # set. Retire it; the reap loop frees its chips.
            try:
                w.proc.terminate()
            except Exception:
                pass
            return
        if w.proc.poll() is None and w.registered and w.actor_id is None and w not in self.idle:
            w.idle_since = time.monotonic()
            self.idle.append(w)

    # ---- actors --------------------------------------------------------
    async def d_start_actor(self, payload, conn):
        if self._draining:
            # races the controller's DRAINING exclusion: reschedule
            raise RuntimeError("node is draining; cannot host new actors")
        spec: TaskSpec = payload["spec"]
        # Exactly-once guard for control-plane replays (a restarted
        # controller rescheduling an actor it only half-persisted, or a
        # dedup-window miss): if a live worker already hosts this actor
        # id, report it instead of spawning a duplicate incarnation.
        for w in self.workers.values():
            if w.actor_id == spec.actor_id and w.proc.poll() is None:
                return {"pid": w.pid}
        req = ResourceSet(spec.resources)
        bundle_key = None
        if isinstance(spec.scheduling_strategy, PlacementGroupScheduling):
            bundle_key = self._find_bundle(spec.scheduling_strategy, spec.resources)
            if bundle_key is None:
                raise RuntimeError("no bundle capacity for actor")
            self._bundle_pools[bundle_key].allocate(req)
        else:
            if not self.resources.can_fit(req):
                raise RuntimeError("insufficient resources for actor")
            self.resources.allocate(req)
        # Chip isolation is mandatory for TPU actors: failing the creation
        # (controller reschedules) beats spawning an unisolated process
        # that would grab every chip on the host.
        chips = None
        if spec.resources.get("TPU", 0) >= 1:
            chips = self._allocate_tpu_chips(int(spec.resources["TPU"]))
            if chips is None:
                if bundle_key is not None:
                    self._bundle_pools[bundle_key].release(req)
                else:
                    self.resources.release(req)
                raise RuntimeError("TPU chip ids unavailable (pool exhausted)")
        w = self._spawn_worker(actor_spec=spec, tpu_chips=chips)
        w.actor_resources = dict(spec.resources)
        w.actor_bundle_key = bundle_key
        return {"pid": w.pid}

    def _release_actor_resources(self, w: WorkerProc) -> None:
        self._free_tpu_chips(w.tpu_chips)
        w.tpu_chips = None
        if w.actor_resources is None:
            return
        req = ResourceSet(w.actor_resources)
        w.actor_resources = None
        if w.actor_bundle_key is not None:
            pool = self._bundle_pools.get(w.actor_bundle_key)
            if pool is not None:
                pool.release(req)
        else:
            self.resources.release(self._withhold_blocked_release(w, req))
        self._notify_capacity()

    async def d_kill_worker(self, payload, conn):
        actor_id = payload.get("actor_id")
        pid = payload.get("pid")
        for w in list(self.workers.values()):
            if (actor_id is not None and w.actor_id == actor_id) or (pid and w.pid == pid):
                try:
                    w.proc.kill()
                except Exception:
                    pass
                return True
        return False

    # ---- placement group bundles (2PC) --------------------------------
    async def d_prepare_bundle(self, payload, conn):
        if self._draining:
            raise RuntimeError("node is draining; cannot reserve bundles")
        key = (payload["pg_id"], payload["bundle_index"])
        req = ResourceSet(payload["resources"])
        if key in self._prepared_bundles or key in self._bundle_pools:
            return True
        if not self.resources.can_fit(req):
            raise RuntimeError("cannot reserve bundle: insufficient resources")
        self.resources.allocate(req)
        self._prepared_bundles[key] = payload["resources"]
        return True

    async def d_commit_bundle(self, payload, conn):
        key = (payload["pg_id"], payload["bundle_index"])
        resources = self._prepared_bundles.pop(key, None)
        if resources is None:
            if key in self._bundle_pools:
                return True
            raise RuntimeError("commit without prepare")
        self._bundle_pools[key] = NodeResources(ResourceSet(resources))
        self._notify_capacity()
        return True

    async def d_release_bundle(self, payload, conn):
        key = (payload["pg_id"], payload["bundle_index"])
        resources = self._prepared_bundles.pop(key, None)
        if resources is not None:
            self.resources.release(ResourceSet(resources))
        pool = self._bundle_pools.pop(key, None)
        if pool is not None:
            self.resources.release(pool.total)
        return True

    # ---- object store services ----------------------------------------
    async def d_adopt_object(self, payload, conn):
        self.store.adopt(ObjectID(payload["object_id"]), payload["size"])
        return True

    async def d_get_object_meta(self, payload, conn):
        meta = self.store.ensure_local(ObjectID(payload["object_id"]))
        if meta is None:
            return None
        return {"segment": meta[0], "size": meta[1]}

    async def d_pull_object(self, payload, conn):
        """Ensure the object is in the local store, pulling chunks from a
        source node. The heavy lifting — admission control, single-flight
        coalescing, resumable multi-source transfer, end-to-end integrity
        — lives in :class:`core.pull_manager.PullManager`. The caller may
        stamp ``deadline_s`` (its remaining budget) so the manager's
        retry/backoff loops are capped by the SAME deadline the caller
        enforces, instead of retrying into a dead wait."""
        from ray_tpu.core.deadline import deadline_scope

        object_id = ObjectID(payload["object_id"])
        with deadline_scope(payload.get("deadline_s")):
            return await self.pulls.pull(object_id, payload["sources"])

    #: above this size the first (uncached) digest computation would risk
    #: blowing the puller's fixed probe timeout — serve digest=None and
    #: warm the cache in the background instead (per-chunk crcs still
    #: protect the transfer; the whole-object gate kicks in once cached)
    _DIGEST_SYNC_MAX_BYTES = 1 << 30

    async def d_object_info(self, payload, conn):
        """Transfer head: size + whole-object crc32 digest (computed
        lazily off-loop, cached on the entry) — the end-to-end integrity
        token the puller verifies before sealing."""
        object_id = ObjectID(payload["object_id"])
        meta = self.store.ensure_local(object_id)
        if meta is None:
            return None
        loop = asyncio.get_event_loop()
        if meta[1] > self._DIGEST_SYNC_MAX_BYTES:
            digest = self.store.peek_digest(object_id)
            if digest is None:
                loop.run_in_executor(None, self.store.digest_of, object_id)
        else:
            digest = await loop.run_in_executor(
                None, self.store.digest_of, object_id
            )
        return {"size": meta[1], "digest": digest}

    async def d_fetch_chunk(self, payload, conn):
        """One transfer chunk. A receiver that stamps ``raw: True`` gets
        a RAW frame: the payload is written to the socket straight from
        this node's mapped segment (scatter-gather, no per-chunk bytes
        copy) with the crc riding the frame header; the receiver reads
        it directly into its destination segment and verifies there.
        Legacy receivers get the pickled ``(bytes, crc)`` tuple."""
        import zlib

        object_id = ObjectID(payload["object_id"])
        if payload.get("raw"):
            from ray_tpu.core.rpc import RawPayload

            win = self.store.read_window(
                object_id, payload["offset"], payload["length"]
            )
            if win is None:
                raise KeyError(f"object {object_id.hex()[:12]} not here")
            # crc over the mapped view — computed by the sender so a
            # corrupt wire byte (or segment) is caught receiver-side
            # before the chunk commits
            return RawPayload(win.view, meta=zlib.crc32(win.view), close=win.close)
        data = self.store.read_range(object_id, payload["offset"], payload["length"])
        if data is None:
            raise KeyError(f"object {object_id.hex()[:12]} not here")
        # per-chunk crc: the receiver verifies BEFORE the bytes commit to
        # its destination segment (a corrupt chunk is re-fetched, not
        # served)
        return (data, zlib.crc32(data))

    async def d_delete_object(self, payload, conn):
        """Delete an object. ``allow_recycle`` is sent by the deleting
        OWNER (segment creator): if no reader ever resolved the object
        here, the entry is dropped WITHOUT unlinking and True is returned
        — the caller renames the inode into its warm-page reuse pool."""
        return self.store.delete(
            ObjectID(payload["object_id"]),
            allow_recycle=bool(payload.get("allow_recycle")),
            # KV-migration importers send this after releasing their
            # mapping: the received segment's inode joins the store's
            # receive reuse pool instead of being unlinked
            recycle_receive=bool(payload.get("recycle_receive")),
        )

    # ---- cluster KV-tier registry (PR 17) ------------------------------
    def _kv_tier_drop_locked(self, digest: str) -> None:
        """Remove one tier entry and its store object (best-effort: the
        object may already be gone if a reader raced a delete)."""
        ent = self._kv_tier.pop(digest, None)
        if ent is None:
            return
        oid_hex = (ent.get("desc") or {}).get("object_id")
        if oid_hex:
            try:
                self.store.delete(ObjectID(bytes.fromhex(oid_hex)))
            except Exception:  # noqa: BLE001
                pass

    def _kv_tier_sweep(self) -> None:
        """TTL + cap eviction for tier entries (called from _reap_loop).
        The tier is a cache: entries nobody faulted in for kv_tier_ttl_s
        expire unconditionally; past kv_tier_max_entries the victim is
        chosen by POPULARITY — lowest hit count first, oldest recency
        among ties — not pure insertion age. A shared system-prompt
        prefix that every request faults in must outlive a parade of
        colder, newer one-off entries, or the cap turns the tier into a
        FIFO that evicts exactly its most valuable bytes."""
        now = time.monotonic()
        if now - self._last_kv_tier_sweep < 1.0:
            return
        self._last_kv_tier_sweep = now
        for digest in [
            d for d, ent in self._kv_tier.items() if now > ent["expiry"]
        ]:
            self._kv_tier_drop_locked(digest)
        cap = max(1, GLOBAL_CONFIG.kv_tier_max_entries)
        while len(self._kv_tier) > cap:
            victim, best = None, None
            # O(n) scan per eviction: the OrderedDict's order IS the
            # recency axis (get/put move_to_end), so position breaks
            # hit-count ties toward the longest-unused entry. Bounded
            # by the 1s sweep throttle + the entry cap.
            for i, (d, ent) in enumerate(self._kv_tier.items()):
                score = (ent.get("hits", 0), i)
                if best is None or score < best:
                    victim, best = d, score
            self._kv_tier_drop_locked(victim)

    async def d_kv_tier_put(self, payload, conn):
        """Register one tier entry (the object itself was already
        published + adopted through the normal store path — this call
        transfers LIFETIME ownership to the daemon's registry). A re-put
        of a live digest is a USE signal (some replica re-derived the
        same prefix): it bumps the hit count the sweep's popularity
        eviction keys on."""
        digest = str(payload["digest"])
        prev = self._kv_tier.get(digest)
        self._kv_tier[digest] = {
            "desc": payload["desc"],
            "expiry": time.monotonic() + GLOBAL_CONFIG.kv_tier_ttl_s,
            "hits": (prev["hits"] + 1) if prev else 0,
        }
        self._kv_tier.move_to_end(digest)
        self._kv_tier_sweep()
        return True

    async def d_kv_tier_get(self, payload, conn):
        """Lookup one entry; a hit refreshes TTL + recency and bumps the
        popularity count (a faulted-in prefix is by definition still
        hot — hit-weighted cap eviction keeps it past colder entries)."""
        ent = self._kv_tier.get(str(payload["digest"]))
        if ent is None:
            return None
        ent["expiry"] = time.monotonic() + GLOBAL_CONFIG.kv_tier_ttl_s
        ent["hits"] = ent.get("hits", 0) + 1
        self._kv_tier.move_to_end(str(payload["digest"]))
        return ent["desc"]

    async def d_kv_tier_del(self, payload, conn):
        self._kv_tier_drop_locked(str(payload["digest"]))
        return True

    async def d_kv_tier_list(self, payload, conn):
        """Full registry dump — the warm-restart recovery read: a
        replacement replica booting on this node re-adverts every
        surviving entry within one gossip beat."""
        return {
            "entries": {d: ent["desc"] for d, ent in self._kv_tier.items()}
        }

    def _peer(self, host: str, port: int) -> RpcClient:
        key = (host, port)
        client = self._peer_clients.get(key)
        if client is None:
            # peers of a daemon are other daemons (object transfer)
            client = self._peer_clients[key] = RpcClient(
                host, port, name=f"peer-{port}", role="noded"
            )
        return client

    # ---- controller fencing (epoch gate) -------------------------------
    def _note_controller_epoch(self, epoch: int) -> None:
        if epoch > self._controller_epoch_seen:
            if self._controller_epoch_seen:
                logger.info(
                    "controller epoch %d -> %d (restart/takeover)",
                    self._controller_epoch_seen, epoch,
                )
            self._controller_epoch_seen = epoch

    def _controller_epoch_gate(self, method: str, epoch: int):
        """RpcServer fencing gate (core/rpc.py meta slot 3): record the
        highest controller epoch seen; reject anything lower with a
        structured ``stale_controller`` error — the deposed controller
        takes it as the order to exit. Split-brain writes become a
        counted non-event instead of silent state corruption."""
        if epoch < self._controller_epoch_seen:
            from ray_tpu.observability.rpc_metrics import (
                CONTROLLER_FENCED_WRITES,
            )

            CONTROLLER_FENCED_WRITES.inc()
            logger.warning(
                "fenced stale controller write %s (epoch %d < %d)",
                method, epoch, self._controller_epoch_seen,
            )
            from ray_tpu.core.rpc import StaleControllerError

            return StaleControllerError(
                f"stale_controller: write {method!r} carries epoch {epoch} "
                f"but epoch {self._controller_epoch_seen} has taken over — "
                "the deposed controller must exit",
                seen_epoch=self._controller_epoch_seen,
            )
        self._note_controller_epoch(epoch)
        return None

    async def d_controller_hello(self, payload, conn):
        """A (new or resurrected) controller announces itself. A new
        incumbent's hello raises the fencing floor cluster-wide before
        it even binds the service port; a zombie's hello is exactly the
        write the epoch gate bounces (it never reaches this handler)."""
        return {"ok": True, "node_id": self.node_id.binary(),
                "epoch_seen": self._controller_epoch_seen}

    # ---- misc ----------------------------------------------------------
    async def d_ping(self, payload, conn):
        return "pong"

    async def d_hello(self, payload, conn):
        """Driver handshake: learn the local node id."""
        return {"node_id": self.node_id.binary()}

    async def d_list_objects(self, payload, conn):
        return self.store.list_entries()

    async def d_stats(self, payload, conn):
        return {
            "node_id": self.node_id.binary(),
            "store": self.store.stats(),
            "num_workers": len(self.workers),
            "num_idle": len(self.idle),
            "num_leases": len(self.leases),
            "resources": self.resources.to_dict(),
            "metrics_port": getattr(self, "metrics_port", 0),
        }

    async def d_event_stats(self, payload, conn):
        """Per-handler timing + loop liveness (reference event_stats.h
        debug dump) for this daemon process."""
        from ray_tpu.observability.event_stats import debug_snapshot

        return debug_snapshot()

    async def d_metrics_text(self, payload, conn):
        """This daemon's full Prometheus registry as exposition text —
        the controller's federation scrape (``c_cluster_telemetry``)
        aggregates every node's registry with ``node`` labels from here,
        so one scrape of the controller sees the whole cluster."""
        from ray_tpu.observability.metrics import render

        loop = asyncio.get_event_loop()
        # render() runs collect callbacks (store stats etc.) — keep the
        # lock-taking text assembly off the daemon's event loop
        return await loop.run_in_executor(None, render)
