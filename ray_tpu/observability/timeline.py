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
import os
import sys
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

_MAX_EVENTS = 100_000


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

    ``settle`` also keeps the longest lap so far without its ``loop_wait``
    (``longest_wall_s``) and that same lap's ``device_wait``
    (``longest_device_wait_s``): a window's means cannot tell one lap that
    stood still from a loop that idles."""

    def __init__(self, prefix: str, phases=(), parts=()):
        self.prefix = prefix
        self.lap: Dict[str, float] = dict.fromkeys(phases, 0.0)
        self.total: Dict[str, float] = dict.fromkeys(phases, 0.0)
        #: ``"<phase>.<part>"`` -> seconds since the last settle / settled
        self.parts: Dict[str, float] = dict.fromkeys(parts, 0.0)
        self.parts_total: Dict[str, float] = dict.fromkeys(parts, 0.0)
        self.longest_wall_s = 0.0
        self.longest_device_wait_s = 0.0
        #: perf_counter of the last settle: a loop resumes its account here
        self.settled_at = time.perf_counter()
        self._open: Optional[str] = None  # the phase this thread is in
        self._in_part = False

    def phase(self, name: str, **args) -> "_Phase":
        return _Phase(self, name, args)

    def part(self, name: str) -> "_Part":
        if self._open is None or self._in_part:
            raise RuntimeError(
                f"part {name!r} of clock {self.prefix!r} "
                + ("inside another part" if self._in_part else "outside any phase")
            )
        return _Part(self, f"{self._open}.{name}")

    def settle(self, since: float, rest: str) -> None:
        now = time.perf_counter()
        lap, total = self.lap, self.total
        claimed = sum(lap.values())
        unclaimed = max(0.0, now - since - claimed)
        lap[rest] = lap.get(rest, 0.0) + unclaimed
        wall = claimed + unclaimed - lap.get("loop_wait", 0.0)
        if wall > self.longest_wall_s:
            self.longest_wall_s = wall
            self.longest_device_wait_s = lap.get("device_wait", 0.0)
        for name, seconds in lap.items():
            total[name] = total.get(name, 0.0) + seconds
            lap[name] = 0.0
        parts, parts_total = self.parts, self.parts_total
        for name, seconds in parts.items():
            parts_total[name] = parts_total.get(name, 0.0) + seconds
            parts[name] = 0.0
        self.settled_at = now


def _annotation(name: str, args: Dict[str, Any]):
    # sys.modules, not an import: the driver and the benchmark's own
    # process stay off JAX
    profiler = sys.modules.get("jax.profiler")
    return profiler.TraceAnnotation(name, **args) if profiler is not None else None


class _Phase:
    __slots__ = ("_clock", "_name", "_span", "_t0")

    def __init__(self, clock: PhaseClock, name: str, args: Dict[str, Any]):
        self._clock = clock
        self._name = name
        self._span = _annotation(f"{clock.prefix}.{name}", args)

    def __enter__(self) -> None:
        if self._span is not None:
            self._span.__enter__()
        self._clock._open = self._name
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        clock = self._clock
        clock.lap[self._name] = clock.lap.get(self._name, 0.0) + seconds
        clock._open = None
        if self._span is not None:
            self._span.__exit__(*exc)


class _Part:
    __slots__ = ("_clock", "_key", "_span", "_t0")

    def __init__(self, clock: PhaseClock, key: str):
        self._clock = clock
        self._key = key
        self._span = _annotation(f"{clock.prefix}.{key}", {})

    def __enter__(self) -> None:
        if self._span is not None:
            self._span.__enter__()
        self._clock._in_part = True
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        clock = self._clock
        clock.parts[self._key] = clock.parts.get(self._key, 0.0) + seconds
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
