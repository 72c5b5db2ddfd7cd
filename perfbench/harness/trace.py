"""From a profiler trace to numbers. The reduction works on a neutral form
(planes -> lines -> events as ``[name, start_ns, duration_ns]``) so that it
can be checked on a small recorded trace without JAX; ``load_xplane`` is
the thin adapter from the profiler's ``.xplane.pb``.

Busy time is the union of the intervals in which an operation ran on a
device (line ``XLA Ops`` of a ``/device:TPU:n`` plane), averaged over the
device planes; the idle share is 1 - busy / window. A long idle gap is
divided among the spans of the host thread that was busiest in it."""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import statistics
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|CPU-rehearsal):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
#: idle gaps shorter than this are summed under one name and not looked at
GAP_FLOOR_NS = 50_000.0
#: the device's clock and the host's disagree by tens of microseconds: an
#: execution may appear to begin this much before the span that launched it
CLOCK_SLACK_NS = 2_000_000.0


def load_xplane(trace_dir: str, host_as_device: bool = False) -> Dict[str, Any]:
    """The newest ``.xplane.pb`` under ``trace_dir`` in the neutral form.
    ``host_as_device`` is for the CPU rehearsal only: with no TPU plane in
    the trace, the CPU client's executor threads stand in for a device, so
    that the reductions have something to read. A run that has to be on a
    TPU never sets it."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    trace = {"planes": planes}
    if host_as_device and not device_planes(trace):
        ops = [
            e for p in planes if p["name"].startswith("/host:") for line in p["lines"]
            if line["name"].startswith("tf_XLAPjRtCpuClient") for e in line["events"] if e[2] > 0
        ]
        planes.append({"name": "/device:CPU-rehearsal:0", "lines": [{"name": OPS_LINE, "events": ops}]})
    return trace


def _merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def device_planes(trace: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: Dict[str, Any], name: str) -> List[List[Any]]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _window(trace: Dict[str, Any]) -> Interval:
    """The traced window: first start to last end of any device operation."""
    starts, ends = [], []
    for plane in device_planes(trace):
        ev = _line(plane, OPS_LINE)
        if ev:
            starts.append(min(e[1] for e in ev))
            ends.append(max(e[1] + e[2] for e in ev))
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy(trace: Dict[str, Any]) -> Dict[str, float]:
    """``busy_s`` (mean over device planes of the union of op intervals)
    and ``window_s``."""
    w0, w1 = _window(trace)
    per_plane = [
        _length(_merge((e[1], e[1] + e[2]) for e in _line(p, OPS_LINE)))
        for p in device_planes(trace)
    ]
    return {"busy_s": statistics.mean(per_plane) / 1e9, "window_s": (w1 - w0) / 1e9}


def idle_share_pct(trace: Dict[str, Any]) -> float:
    b = busy(trace)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])


DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")


def program_names(trace: Dict[str, Any], plane: Dict[str, Any]) -> List[Optional[str]]:
    """The jitted function behind each execution on ``XLA Modules``, in the
    line's order. The device names a program only by a fingerprint
    (``jit__unknown(123...)``), but the host thread that launched it wrote
    a ``PjitFunction(<name>)`` span, and one process launches its programs
    in order: each execution takes the oldest launch that began before it
    and has not been taken (or the newest, where the device stood idle at
    that launch). Executions launched before the trace began get ``None``."""
    spans = sorted(
        (e[1], e[1] + e[2], m.group(1))
        for p in trace["planes"] if p["name"].startswith("/host:")
        for line in p["lines"] for e in line["events"]
        for m in [DISPATCH.match(e[0])] if m
    )
    launches: List[Tuple[float, str]] = []
    end = float("-inf")
    for s0, s1, name in spans:
        if s0 < end:  # the same call's inner span (jit's fast path nests one in the other)
            continue
        launches.append((s0, name))
        end = s1
    names: List[Optional[str]] = []
    nxt = 0
    prev_end = float("-inf")
    for _name, start, dur in sorted(_line(plane, MODULES_LINE), key=lambda e: e[1]):
        last = nxt
        while last < len(launches) and launches[last][0] <= start + CLOCK_SLACK_NS:
            last += 1
        if last == nxt:
            names.append(None)  # launched before the trace began
        elif launches[last - 1][0] >= prev_end:
            # the device stood idle when the newest of them was launched, so
            # nothing was queued: this execution is that launch's, and older
            # launches still waiting lost their executions to the trace's edge
            names.append(launches[last - 1][1])
            nxt = last
        else:
            names.append(launches[nxt][1])  # launches queued behind a running program: in order
            nxt += 1
        prev_end = start + dur
    return names


def program_durations_ms(trace: Dict[str, Any], name_regex: str) -> Dict[str, List[float]]:
    """Durations of the executions (line ``XLA Modules``) of the programs
    whose jitted function's name matches, by compiled program (one per
    shape bucket), on the first device plane that has any. An execution
    counts for the name most of its fingerprint's executions were launched
    under, so one mismatch at the trace's edge does no harm."""
    pat = re.compile(name_regex)
    for plane in device_planes(trace):
        events = sorted(_line(plane, MODULES_LINE), key=lambda e: e[1])
        votes: Dict[str, Dict[str, int]] = {}
        for (fingerprint, _s, _d), name in zip(events, program_names(trace, plane)):
            if name is not None:
                tally = votes.setdefault(fingerprint, {})
                tally[name] = tally.get(name, 0) + 1
        wanted = {
            fp for fp, tally in votes.items() if pat.search(max(tally, key=tally.get))
        }
        got: Dict[str, List[float]] = {}
        for e in events:
            if e[0] in wanted:
                got.setdefault(e[0], []).append(e[2] / 1e6)
        if got:
            return got
    return {}


def slowest_program_median_ms(trace: Dict[str, Any], name_regex: str) -> Optional[float]:
    """Median duration of the executions of the slowest matching program:
    where a function is compiled for several buckets, its largest."""
    medians = [statistics.median(d) for d in program_durations_ms(trace, name_regex).values()]
    return max(medians) if medians else None


def ops_share_of_busy_pct(trace: Dict[str, Any], name_regex: str) -> Optional[float]:
    """Time in the device operations whose (short) name matches, over busy
    time. The short name is the HLO instruction's own (``fusion.97``): the
    full text also names operands, which would match by accident."""
    pat = re.compile(name_regex)
    shares = []
    for plane in device_planes(trace):
        ev = _line(plane, OPS_LINE)
        total = _length(_merge((e[1], e[1] + e[2]) for e in ev))
        if total <= 0:
            continue
        hit = _length(_merge((e[1], e[1] + e[2]) for e in ev if pat.search(short_name(e[0]))))
        shares.append(100.0 * hit / total)
    return statistics.mean(shares) if shares else None


def exposed_share_pct(trace: Dict[str, Any], name_regex: str) -> Optional[float]:
    """Time in which a matching operation (a collective, on ``XLA Ops`` or
    in flight on ``Async XLA Ops``) runs on a device and no other operation
    of ``XLA Ops`` does, over the traced window."""
    pat = re.compile(name_regex)
    w0, w1 = _window(trace)
    shares = []
    for plane in device_planes(trace):
        sync = _line(plane, OPS_LINE)
        if not sync:
            continue
        both = sync + _line(plane, ASYNC_LINE)
        hit = _merge((e[1], e[1] + e[2]) for e in both if pat.search(short_name(e[0])))
        other = _merge((e[1], e[1] + e[2]) for e in sync if not pat.search(short_name(e[0])))
        shares.append(100.0 * _length(_subtract(hit, other)) / (w1 - w0))
    return statistics.mean(shares) if shares else None


def short_name(name: str) -> str:
    """``%fusion.97 = bf16[...] fusion(...)`` -> ``fusion.97``."""
    return name.split(" = ", 1)[0].lstrip("%")


def safe_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", short_name(name))[:64]


def top_device_ops(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The device operations that took most time: [name, seconds], summed
    over executions, mean over device planes."""
    planes = device_planes(trace)
    total: Dict[str, float] = {}
    for plane in planes:
        for name, _start, dur in _line(plane, OPS_LINE):
            total[name] = total.get(name, 0.0) + dur
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[safe_name(k), v / 1e9 / max(1, len(planes))] for k, v in top]


def _innermost(events: Sequence[Tuple[float, float, str]]) -> List[Tuple[float, float, str]]:
    """One host thread's events ``(start, end, name)`` as segments that do
    not overlap, each under the INNERMOST event at that instant: of the
    events that cover it the one that began last (the shorter, where two
    began together). A span's time is then its own, without its children's."""
    bounds = sorted({t for s, e, _ in events for t in (s, e)})
    order = sorted(events, key=lambda ev: (ev[0], ev[0] - ev[1]))
    open_: List[Tuple[float, float, float, str]] = []  # a heap: the innermost on top
    out: List[Tuple[float, float, str]] = []
    nxt = 0
    for t, t_next in zip(bounds, bounds[1:]):
        while nxt < len(order) and order[nxt][0] <= t:
            s, e, name = order[nxt]
            heapq.heappush(open_, (-s, e - s, e, name))
            nxt += 1
        while open_ and open_[0][2] <= t:
            heapq.heappop(open_)
        if not open_:
            continue
        name = open_[0][3]
        if out and out[-1][2] == name and out[-1][1] == t:
            out[-1] = (out[-1][0], t_next, name)
        else:
            out.append((t, t_next, name))
    return out


def idle_gaps(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """The idle time of the first device, by what the host was doing: each
    gap between device operations longer than ``GAP_FLOOR_NS`` goes to the
    host THREAD (a line of a ``/host:`` plane) whose events cover most of
    it, and is DIVIDED among that thread's innermost events at each instant
    in proportion to overlap; what no event of that thread covers is
    ``unattributed``. A serving step's serial part is several spans in a
    row under one gap: each gets its own share, none the whole."""
    planes = device_planes(trace)
    if not planes:
        return []
    w0, w1 = _window(trace)
    busy_iv = _merge((e[1], e[1] + e[2]) for e in _line(planes[0], OPS_LINE))
    gaps = _subtract([(w0, w1)], busy_iv)
    threads = []  # per host thread: its innermost segments and their ends, for bisect
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for line in p["lines"]:
            segments = _innermost([(e[1], e[1] + e[2], e[0]) for e in line["events"] if e[2] > 0])
            if segments:
                threads.append((segments, [seg[1] for seg in segments]))
    total: Dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        total[name] = total.get(name, 0.0) + ns

    for s, e in gaps:
        if e - s < GAP_FLOOR_NS:
            add("shorter_gaps_not_looked_at", e - s)
            continue
        best: List[Tuple[str, float]] = []
        best_cover = 0.0
        for segments, ends in threads:
            shares = []
            i = bisect.bisect_right(ends, s)  # the first segment that ends inside or after the gap
            while i < len(segments) and segments[i][0] < e:
                seg_s, seg_e, name = segments[i]
                shares.append((name, min(e, seg_e) - max(s, seg_s)))
                i += 1
            cover = sum(ns for _, ns in shares)
            if cover > best_cover:
                best, best_cover = shares, cover
        for name, ns in best:
            add(name, ns)
        if e - s > best_cover:
            add("unattributed", e - s - best_cover)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[safe_name(k), v / 1e9] for k, v in top]


def breakdown(trace: Dict[str, Any]) -> Dict[str, Any]:
    return {"device_ops": top_device_ops(trace), "idle_gaps": idle_gaps(trace)}


def structure(trace: Dict[str, Any], per_line: int = 12) -> List[str]:
    """A few lines describing what the trace holds (for a human)."""
    out = []
    for p in trace["planes"]:
        out.append(f"plane {p['name']}")
        for line in p["lines"]:
            names: Dict[str, List[float]] = {}
            for name, _s, d in line["events"]:
                names.setdefault(name, []).append(d)
            top = sorted(names.items(), key=lambda kv: -sum(kv[1]))[:per_line]
            out.append(
                f"  line {line['name']!r}: {len(line['events'])} events; "
                + "; ".join(f"{short_name(k)[:60]} x{len(v)} {sum(v) / 1e6:.2f}ms" for k, v in top)
            )
    return out


def cut(trace: Dict[str, Any], seconds: float = 0.6) -> Dict[str, Any]:
    """A small piece of a trace, for a recorded fixture: the events of the
    device and host planes that start in the first ``seconds`` of the
    device's window, device operations under their short names."""
    w0, _ = _window(trace)
    w1 = w0 + seconds * 1e9
    planes = []
    for p in trace["planes"]:
        if not (DEVICE_PLANE.match(p["name"]) or p["name"].startswith("/host:")):
            continue
        lines = []
        for line in p["lines"]:
            keep = [
                [short_name(e[0]) if line["name"] in (OPS_LINE, ASYNC_LINE) else e[0], e[1] - w0, e[2]]
                for e in line["events"] if w0 <= e[1] + e[2] and e[1] < w1
            ]
            if keep:
                lines.append({"name": line["name"], "events": keep})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}


def gaps_view(trace: Dict[str, Any]) -> Dict[str, Any]:
    """All that ``idle_gaps`` reads of a trace, over the whole window: the
    first device's busy intervals as one operation each, and the host
    planes whole. ``idle_gaps`` of it is ``idle_gaps`` of the trace."""
    w0, _ = _window(trace)
    first = device_planes(trace)[0]
    busy_iv = _merge((e[1], e[1] + e[2]) for e in _line(first, OPS_LINE))
    planes = [{"name": first["name"], "lines": [
        {"name": OPS_LINE, "events": [["busy", s - w0, e - s] for s, e in busy_iv]}]}]
    for p in trace["planes"]:
        if p["name"].startswith("/host:"):
            planes.append({"name": p["name"], "lines": [
                {"name": line["name"], "events": [[e[0], e[1] - w0, e[2]] for e in line["events"]]}
                for line in p["lines"] if line["events"]]})
    return {"planes": planes}


def dump(trace: Dict[str, Any], directory: str) -> None:
    """For a human: what the trace holds, a small piece of it, and what the
    idle gaps are read from (so that a change to ``idle_gaps`` can be read
    against the rule before it on one trace)."""
    import gzip
    import json

    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, "trace_structure.txt"), "w") as f:
        f.write("\n".join(structure(trace)) + "\n")
    with open(os.path.join(directory, "trace_cut.json"), "w") as f:
        json.dump(cut(trace), f)
    with gzip.open(os.path.join(directory, "trace_gaps.json.gz"), "wt") as f:
        json.dump(gaps_view(trace), f)
