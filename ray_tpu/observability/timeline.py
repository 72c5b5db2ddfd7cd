"""Task timeline: chrome://tracing dump of profile events.

Reference: ``ray.timeline`` (``python/ray/_private/profiling.py:124``,
``_private/state.py:948``) — emits chrome-tracing JSON of task lifecycle
events. Redesigned single-file equivalent: every process records
``ProfileEvent``s into a bounded in-memory ring buffer; the driver dumps
its own buffer plus any chunks workers exported to the controller's
BOUNDED export table (``export_events``/``collect_events`` RPCs —
byte-budgeted, reaped on node death; legacy/local backends fall back to
the raw KV prefix path) into one chrome-trace file loadable in
chrome://tracing or Perfetto. Events whose args carry trace ids
(``observability/tracing.py``) additionally yield flow events — the
cross-process causal arrows.
"""

from __future__ import annotations

import json
import logging
import math
import os
import sys
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

_MAX_EVENTS = 100_000
logger = logging.getLogger(__name__)
#: the calling thread's CPU seconds (``CLOCK_THREAD_CPUTIME_ID``). Bound
#: here, not looked up on ``time``: a test that scripts this module's
#: ``time`` scripts the wall clock alone
_cpu_now = time.thread_time


def _cpu_read_cost() -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        _cpu_now()
        best = min(best, time.perf_counter() - t0)
    return best


#: what one reading of the CPU clock costs here, measured once: 0.4 us on a
#: plain Linux host, 6.1 us in the sandbox that holds the chip, whose CPU
#: clock also moves in ticks of 10 ms (PERF.md section 6, PR 54). A block's
#: two readings lie inside its wall readings; about one reading's time lies
#: between them and is taken off again
_CPU_READ_S = _cpu_read_cost()
#: the blocks of one lap in ``_SAMPLE_EVERY`` read the CPU clock, so that
#: a lap's 25 blocks pay 25 us for it on average wherever a reading is
#: dear: every lap at 0.4 us, one in 13 at 6.1. Odd, so that it does not
#: fall in step with a load whose laps alternate. The lap's own reading at
#: ``settle`` is taken every lap
_SAMPLE_EVERY = max(1, math.ceil(50 * _CPU_READ_S / 25e-6)) | 1
#: a lap that begins this long after the last settle did not follow it: a
#: ``step()`` from outside the loop, with its caller's time between
_LAP_GAP_S = 1e-3
#: a settled lap is STALLED when its wall is this many times the mean of the
#: rated laps before it: the rule ``step_longest_ms.*`` gives its reader
#: ("ten times their sum is a stall, not a program that idles"). A prefill
#: chunk beside decode steps is 3-8 x the mean lap in every cell
#: (127 ms beside 15-40), a frozen machine 50-300 x (1.4-4.5 s)
_STALL_TIMES = 10.0
#: ... once the mean stands on this many laps: a replica's first laps are
#: its lead-in (one request, then a few) and, where nothing was warmed, its
#: compiles; 64 is two to four seconds of any cell's steps
_STALL_AFTER_LAPS = 64
#: this many stalled laps IN A ROW are the load's new shape (short chats,
#: then long documents), not a stall: the mean starts anew, or every later
#: lap would be rated against a load that is gone
_STALL_RUN = 8


@dataclass
class ProfileEvent:
    name: str
    category: str
    start_us: float
    end_us: float
    pid: int = field(default_factory=os.getpid)
    tid: int = 0
    args: Optional[Dict[str, Any]] = None


_events: "deque[ProfileEvent]" = deque(maxlen=_MAX_EVENTS)
_lock = threading.Lock()
_total_recorded = 0
_exporter_uid = uuid.uuid4().hex[:8]


def _now_us() -> float:
    # Wall clock, not perf_counter: events from many processes are merged
    # into one trace, so timestamps need a shared epoch.
    return time.time_ns() / 1e3


def record_event(
    name: str,
    category: str,
    start_us: float,
    end_us: float,
    args: Optional[Dict[str, Any]] = None,
) -> None:
    global _total_recorded
    ev = ProfileEvent(
        name=name,
        category=category,
        start_us=start_us,
        end_us=end_us,
        tid=threading.get_ident() % 1_000_000,
        args=args,
    )
    with _lock:
        _events.append(ev)
        _total_recorded += 1


class PhaseClock:
    """Where one thread's loop spends its time, phase by phase.

    ``with clock.phase("sample"):`` adds the block's ``perf_counter``
    seconds to ``clock.lap["sample"]`` and, in a process that has
    imported JAX, wraps it in ``jax.profiler.TraceAnnotation
    ("<prefix>.sample", **args)``: inert unless a profiler trace is
    running, and then a span on the trace's own clock, beside the device's
    operations. A process that has not imported JAX is not made to: there
    the phase only accumulates.

    Phases are leaves: they do not nest, so their seconds add up.
    ``settle(since, rest)`` closes the account over ``[since, now]``:
    what no phase claimed of that wall time goes to ``rest`` and the lap
    is added to ``total``, so ``sum(total.values())`` is the wall time of
    everything settled so far.

    A phase has PARTS: ``with clock.part("rows"):`` inside an open phase
    ``launch`` adds the block's seconds to ``clock.parts["launch.rows"]``
    (``settle`` moves them into ``parts_total``) under a span
    ``<prefix>.launch.rows`` nested in the phase's. A part's seconds are in
    an account of their own, never in ``lap`` or ``total``: the leaves read
    what they read without parts, and what a phase's parts leave of it is
    its self time. Parts do not nest, and there is none outside a phase.

    Beside the wall clock the thread's CPU clock (``time.thread_time``) is
    read, and ``wall - cpu`` is OFF-CPU time: what the thread spent waiting,
    for the GIL, in a blocking call into the runtime (a transfer, a lock),
    for the OS. A C call that releases the GIL and computes is ON the CPU.
    Two accounts, beside ``lap`` / ``total`` / ``parts`` and never in them.
    The THREAD's: ``settle`` reads the CPU clock once a lap, and
    ``thread_offcpu`` sums ``wall - cpu`` over the laps that followed one
    another on one thread: one reading a lap, and exact to the CPU clock's
    grain over any window, since the laps' readings telescope. The BLOCKS':
    in one lap of ``_SAMPLE_EVERY`` every phase and part reads the CPU clock
    at both ends and adds ``_SAMPLE_EVERY x cpu`` to ``cpu`` / ``parts_cpu``
    (settled into ``cpu_total`` / ``parts_cpu_total``): an estimate of the
    block's CPU seconds over ALL laps, every lap where a reading is cheap,
    and a block's off-CPU seconds are its wall seconds less that. No block
    is clamped: where the CPU clock moves in ticks a block reads 0 or a
    whole tick, and only the sums mean anything; a reader clamps a
    difference to ``[0, wall]`` (``InferenceEngine._step_offcpu``). What
    ``settle`` hands to ``rest`` takes, in a sampled lap, the CPU seconds
    that the lap's own reading has over its phases'.

    ``settle`` also keeps the longest lap so far without its ``loop_wait``
    (``longest_wall_s``) and that same lap's ``device_wait``
    (``longest_device_wait_s``): a window's means cannot tell one lap that
    stood still from a loop that idles. And it RATES every lap it is told
    did work (``rated``): after ``_STALL_AFTER_LAPS`` rated laps, one whose
    wall (without ``loop_wait``) is ``_STALL_TIMES`` the mean of the rated
    laps before it is STALLED, and does not enter the mean. ``stalls`` holds
    monotonic sums a reader differences: rated and stalled laps, and over
    the stalled ones their wall, the ``device_wait`` in it (the device's or
    the machine's) and the rest (the host's). A stalled lap also leaves a
    timeline event ``<prefix>_stall``, a zero-length annotation
    ``<prefix>.stall`` where it ended on the profiler's clock, outside every
    phase, and a warning in the log, at most one a second.

    ``reads``: what a reader of the device counts on the clock it is handed
    (``PagedModelRunner.read``): its waits, and those that found the result
    there."""

    def __init__(self, prefix: str, phases=(), parts=()):
        self.prefix = prefix
        self.lap: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self.total: Dict[str, float] = dict.fromkeys(phases, 0.0)
        #: ``"<phase>.<part>"`` -> seconds since the last settle / settled
        self.parts: Dict[str, float] = dict.fromkeys(parts, 0.0)
        self.parts_total: Dict[str, float] = dict.fromkeys(parts, 0.0)
        #: the same four for the CPU seconds of the same blocks (estimates from
        #: the sampled laps), and the thread's off-CPU seconds over its laps
        self.cpu: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self.cpu_total: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self.parts_cpu: Dict[str, float] = dict.fromkeys(parts, 0.0)
        self.parts_cpu_total: Dict[str, float] = dict.fromkeys(parts, 0.0)
        self.thread_offcpu = 0.0
        self.longest_wall_s = 0.0
        self.longest_device_wait_s = 0.0
        self.stalls: Dict[str, float] = {
            "laps": 0, "stalled": 0, "wall_s": 0.0, "device_wait_s": 0.0, "host_s": 0.0,
        }
        self.reads: Dict[str, int] = {"reads": 0, "ready": 0}
        #: perf_counter of the last settle: a loop resumes its account here
        self.settled_at = time.perf_counter()
        self._open: Optional[str] = None  # the phase this thread is in
        self._in_part = False
        # whether this lap's blocks read the CPU clock (the first does: a
        # clock nobody settles keeps one lap), and the laps settled
        self._sampled = True
        self._laps = 0
        # of a sampled lap: the readings its blocks took, its phases, their CPU seconds
        self._reads = 0
        self._phases = 0
        self._phases_cpu = 0.0
        # the CPU clock at the last settle, and the thread that read it
        self._cpu_at = 0.0
        self._cpu_thread: Optional[int] = None
        # the rated laps that were not stalled: how many, their wall
        self._usual_laps = 0
        self._usual_s = 0.0
        self._stall_run = 0
        self._warned_at = float("-inf")

    def phase(self, name: str, **args) -> "_Phase":
        return _Phase(self, name, args)

    def part(self, name: str) -> "_Part":
        if self._open is None or self._in_part:
            raise RuntimeError(
                f"part {name!r} of clock {self.prefix!r} "
                + ("inside another part" if self._in_part else "outside any phase")
            )
        return _Part(self, f"{self._open}.{name}")

    def settle(self, since: float, rest: str, rated: bool = True) -> None:
        now = time.perf_counter()
        cpu, thread = _cpu_now(), threading.get_ident()
        lap_cpu = None  # unknown: the first lap, another thread's, one after a gap
        if thread == self._cpu_thread and since - self.settled_at < _LAP_GAP_S:
            lap_cpu = cpu - self._cpu_at
            self.thread_offcpu += now - self.settled_at - lap_cpu
        self._cpu_at, self._cpu_thread = cpu, thread
        lap, total = self.lap, self.total
        claimed = sum(lap.values())
        unclaimed = max(0.0, now - since - claimed)
        lap[rest] = lap.get(rest, 0.0) + unclaimed
        accounts = [(lap, total), (self.parts, self.parts_total)]
        if self._sampled:
            if lap_cpu is not None:
                # what the lap's own reading has over its phases': the rest's,
                # with the half of each phase's two readings that lies outside it
                rest_cpu = lap_cpu - self._phases_cpu - self._phases * _CPU_READ_S
                self.cpu[rest] = self.cpu.get(rest, 0.0) + _SAMPLE_EVERY * rest_cpu
            self._reads, self._phases, self._phases_cpu = 0, 0, 0.0
            accounts += [(self.cpu, self.cpu_total), (self.parts_cpu, self.parts_cpu_total)]
        wall = claimed + unclaimed - lap.get("loop_wait", 0.0)
        if wall > self.longest_wall_s:
            self.longest_wall_s = wall
            self.longest_device_wait_s = lap.get("device_wait", 0.0)
        if rated:
            self._rate(wall, now, since, lap_cpu)
        for since_settle, settled in accounts:
            for name, seconds in since_settle.items():
                settled[name] = settled.get(name, 0.0) + seconds
                since_settle[name] = 0.0
        self.settled_at = now
        self._laps += 1
        self._sampled = self._laps % _SAMPLE_EVERY == 0

    def _rate(self, wall: float, now: float, since: float, lap_cpu: Optional[float]) -> None:
        stalls = self.stalls
        stalls["laps"] += 1
        if (
            self._usual_laps < _STALL_AFTER_LAPS
            or wall * self._usual_laps < _STALL_TIMES * self._usual_s
        ):
            self._usual_laps += 1
            self._usual_s += wall
            self._stall_run = 0
            return
        device_wait = min(wall, self.lap.get("device_wait", 0.0))
        usual_ms = 1e3 * self._usual_s / self._usual_laps
        stalls["stalled"] += 1
        stalls["wall_s"] += wall
        stalls["device_wait_s"] += device_wait
        stalls["host_s"] += wall - device_wait
        self._stall_run += 1
        if self._stall_run >= _STALL_RUN:
            self._usual_laps, self._usual_s, self._stall_run = 0, 0.0, 0
        args = {
            "wall_ms": round(1e3 * wall, 3),
            "device_wait_ms": round(1e3 * device_wait, 3),
            "usual_ms": round(usual_ms, 3),
        }
        if lap_cpu is not None:  # the lap's CPU seconds: a host that worked, or one that stood still
            args["cpu_ms"] = round(1e3 * lap_cpu, 3)
        end_us = _now_us()
        record_event(
            f"{self.prefix}_stall", "inference", end_us - 1e6 * (now - since), end_us,
            args={**args, "phases_ms": {n: round(1e3 * s, 3) for n, s in self.lap.items() if s}},
        )
        span = _annotation(f"{self.prefix}.stall", args)
        if span is not None:
            with span:
                pass
        if now - self._warned_at >= 1.0:
            self._warned_at = now
            logger.warning(
                "%s: a lap of %.0f ms, %.0f x the usual %.1f ms: %.0f ms waiting for the "
                "device, %.0f ms on the host (stalled lap %d of %d)",
                self.prefix, args["wall_ms"], args["wall_ms"] / usual_ms, usual_ms,
                args["device_wait_ms"], args["wall_ms"] - args["device_wait_ms"],
                stalls["stalled"], stalls["laps"],
            )


def _annotation(name: str, args: Dict[str, Any]):
    # sys.modules, not an import: the driver and the benchmark's own
    # process stay off JAX
    profiler = sys.modules.get("jax.profiler")
    return profiler.TraceAnnotation(name, **args) if profiler is not None else None


def _sampled_cpu(cpu: float, inner: int = 0) -> float:
    """What a sampled block adds to its CPU account, from the reading ``cpu``
    between its two readings of the clock with ``inner`` readings of its
    parts between them: its own CPU seconds (the reading less the clock's
    own time in it: about one reading of its two, and the parts' whole) for
    every lap it stands for, and all those readings once: they are in the
    block's wall seconds this lap, and were not waited."""
    return _SAMPLE_EVERY * (cpu - (1 + inner) * _CPU_READ_S) + (2 + inner) * _CPU_READ_S


class _Phase:
    __slots__ = ("_clock", "_name", "_span", "_t0", "_c0", "_n0", "seconds")

    def __init__(self, clock: PhaseClock, name: str, args: Dict[str, Any]):
        self._clock = clock
        self._name = name
        self._span = _annotation(f"{clock.prefix}.{name}", args)

    def __enter__(self) -> "_Phase":
        if self._span is not None:
            self._span.__enter__()
        clock = self._clock
        clock._open = self._name
        self._t0 = time.perf_counter()
        if clock._sampled:
            self._n0 = clock._reads
            self._c0 = _cpu_now()
        else:
            self._c0 = None
        return self

    def __exit__(self, *exc) -> None:
        clock, name = self._clock, self._name
        cpu = _cpu_now() - self._c0 if self._c0 is not None else None
        #: the block's wall seconds, for the caller that asked ``as``
        self.seconds = seconds = time.perf_counter() - self._t0
        if cpu is not None:
            clock.cpu[name] = clock.cpu.get(name, 0.0) + _sampled_cpu(cpu, clock._reads - self._n0)
            clock._reads += 2
            clock._phases += 1
            clock._phases_cpu += cpu
        clock.lap[name] = clock.lap.get(name, 0.0) + seconds
        clock._open = None
        if self._span is not None:
            self._span.__exit__(*exc)


class _Part:
    __slots__ = ("_clock", "_key", "_span", "_t0", "_c0")

    def __init__(self, clock: PhaseClock, key: str):
        self._clock = clock
        self._key = key
        self._span = _annotation(f"{clock.prefix}.{key}", {})

    def __enter__(self) -> None:
        if self._span is not None:
            self._span.__enter__()
        clock = self._clock
        clock._in_part = True
        self._t0 = time.perf_counter()
        self._c0 = _cpu_now() if clock._sampled else None

    def __exit__(self, *exc) -> None:
        clock, key = self._clock, self._key
        cpu = _cpu_now() - self._c0 if self._c0 is not None else None
        seconds = time.perf_counter() - self._t0
        if cpu is not None:
            clock.parts_cpu[key] = clock.parts_cpu.get(key, 0.0) + _sampled_cpu(cpu)
            clock._reads += 2
        clock.parts[key] = clock.parts.get(key, 0.0) + seconds
        clock._in_part = False
        if self._span is not None:
            self._span.__exit__(*exc)


def timeline_events() -> List[ProfileEvent]:
    with _lock:
        return list(_events)


def clear_events() -> None:
    with _lock:
        _events.clear()


_EVENTS_KV_PREFIX = b"ray_tpu:events:"
_export_count = 0
_export_chunk = 0


def _collect_remote_events() -> List[ProfileEvent]:
    """Pull worker-exported event chunks. Cluster backends serve them
    from the controller's BOUNDED export table (``collect_events`` RPC —
    oldest chunks past ``timeline_kv_max_bytes`` are dropped, a dead
    node's chunks are reaped with it); legacy/local backends fall back
    to the old KV prefix scan."""
    out: List[ProfileEvent] = []
    try:
        from ray_tpu.core import api

        worker = api.get_global_worker_or_none()
        if worker is None:
            return out
        backend = worker.backend
        collect = getattr(backend, "collect_timeline_chunks", None)
        if collect is not None:
            blobs = collect()
        else:
            blobs = [
                backend.kv_get(key) for key in backend.kv_keys(_EVENTS_KV_PREFIX)
            ]
        for blob in blobs:
            if blob:
                for d in json.loads(blob):
                    out.append(ProfileEvent(**d))
    except Exception:
        pass
    return out


def export_events_to_kv() -> None:
    """Worker-side: publish NEW events (since the last export) as one
    immutable chunk under a per-process key — writes are O(delta), and no
    cross-process read-modify-write exists anywhere. Retention is the
    CONTROLLER's job (bounded byte budget + node-death reap); legacy/
    local backends without the export RPC keep the raw KV path."""
    global _export_count, _export_chunk
    from ray_tpu.core import api

    worker = api.get_global_worker_or_none()
    if worker is None:
        return
    with _lock:
        fresh_n = min(_total_recorded - _export_count, len(_events))
        fresh = list(_events)[-fresh_n:] if fresh_n > 0 else []
        _export_count = _total_recorded
    if not fresh:
        return
    # Key on (startup-unique uuid, pid): bare pids collide across nodes in
    # a multi-node cluster and one worker's chunks would overwrite another's.
    key = f"{_exporter_uid}:{os.getpid()}:{_export_chunk:06d}"
    _export_chunk += 1
    blob = json.dumps([ev.__dict__ for ev in fresh]).encode()
    export = getattr(worker.backend, "export_timeline_chunk", None)
    if export is not None:
        export(key, blob)
    else:
        worker.backend.kv_put(_EVENTS_KV_PREFIX + key.encode(), blob)


def start_export_thread(period_s: float = 2.0) -> threading.Thread:
    """Background exporter for worker processes: ships new events to the
    controller KV so driver-side ``timeline()`` sees remote task spans
    without a worker round-trip. Idle workers cost nothing (delta export)."""

    def _loop():
        while True:
            time.sleep(period_s)
            try:
                export_events_to_kv()
            except Exception:
                pass

    t = threading.Thread(target=_loop, daemon=True, name="timeline-export")
    t.start()
    return t


def _flow_events(events: List[ProfileEvent]) -> List[Dict[str, Any]]:
    """Chrome-trace flow events for every resolvable trace edge: spans
    (events whose args carry ``span_id``) are indexed, and each child's
    ``parent_span_id`` found in the index yields an ``s``/``f`` pair —
    the arrows Perfetto draws from the parent's slice (any process) to
    the child's. Unresolvable parents (not exported yet) are skipped."""
    by_span: Dict[str, ProfileEvent] = {}
    for ev in events:
        sid = (ev.args or {}).get("span_id")
        if sid:
            by_span[sid] = ev
    out: List[Dict[str, Any]] = []
    for ev in events:
        args = ev.args or {}
        parent_id = args.get("parent_span_id")
        sid = args.get("span_id")
        if not parent_id or not sid:
            continue
        parent = by_span.get(parent_id)
        if parent is None:
            continue
        flow_id = int(sid[:12], 16)
        common = {"name": "trace", "cat": "trace", "id": flow_id}
        # start binds to the parent's slice, finish ("e" = enclosing
        # slice) to the child's — ts must fall inside each slice
        out.append(
            dict(common, ph="s", ts=parent.start_us, pid=parent.pid, tid=parent.tid)
        )
        out.append(
            dict(common, ph="f", bp="e", ts=ev.start_us, pid=ev.pid, tid=ev.tid)
        )
    return out


def dump_timeline(filename: Optional[str] = None) -> Any:
    """Dump chrome://tracing JSON (slices + trace flow arrows). Returns
    the trace list (and writes ``filename`` if given) — matches
    ``ray.timeline`` semantics; load in Perfetto / chrome://tracing."""
    events = timeline_events() + _collect_remote_events()
    trace = []
    for ev in events:
        trace.append(
            {
                "name": ev.name,
                "cat": ev.category,
                "ph": "X",
                "ts": ev.start_us,
                "dur": max(0.0, ev.end_us - ev.start_us),
                "pid": ev.pid,
                "tid": ev.tid,
                "args": ev.args or {},
            }
        )
    trace.extend(_flow_events(events))
    trace.sort(key=lambda e: e["ts"])
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace
