"""Per-layer metrics: each is a small data file (``layer_metrics/<name>.json``)
read by one of the general readers below, chosen by its ``kind``. A reader
that finds nothing to read returns ``None`` and the harness leaves that
metric out of the line.

What a run hands the readers (``Observed``):

* ``stats_start`` / ``stats_end``: ``engine_stats()`` of the replica at the
  two ends of the window, and ``stats_samples`` taken once a second;
* ``series``: named lists of per-request or per-step readings on a host
  clock (``late_ms``, ``client_ttft_ms``, ``replica_ttft_ms``, ``step_ms`` ...);
* ``scalars``: named single numbers (``output_tokens``, the end-to-end
  rates, ``flops_per_token``, ``peak_flops_per_s``, ``chips`` ...);
* ``trace``: the profiler trace of the traced window in the neutral form of
  ``trace.py`` (only in the process that took it)."""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import stats as st
from . import trace as tr


@dataclass
class Observed:
    stats_start: Optional[Dict[str, Any]] = None
    stats_end: Optional[Dict[str, Any]] = None
    stats_samples: List[Dict[str, Any]] = field(default_factory=list)
    series: Dict[str, List[float]] = field(default_factory=dict)
    scalars: Dict[str, float] = field(default_factory=dict)
    trace: Optional[Dict[str, Any]] = None


def _dig(tree: Optional[Dict[str, Any]], path: Sequence[str]) -> Optional[float]:
    cur: Any = tree
    for key in path:
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return None if cur is None else float(cur)


def _reduce(values: Sequence[float], how: str) -> Optional[float]:
    if not values:
        return None
    if how == "median":
        return statistics.median(values)
    if how == "mean":
        return statistics.mean(values)
    if how == "max":
        return max(values)
    if how.startswith("hd"):  # hd90 = Harrell-Davis 0.90 quantile
        return st.harrell_davis(values, int(how[2:]) / 100.0)
    if how.startswith("p"):  # p99 = plain order statistic
        return st.percentile(values, int(how[1:]) / 100.0)
    raise ValueError(f"unknown reduction {how!r}")


def _stats_delta(spec: Dict[str, Any], ob: Observed) -> Optional[float]:
    """Counters of ``engine_stats()`` as deltas over the window.
    ``reduce``: ``delta`` (end - start of ``key``), ``ratio`` (delta of
    ``key`` or the scalar ``numerator_scalar`` over delta of ``per``),
    ``max_sample`` (largest sampled ``key`` / ``per``), ``last``."""
    how = spec.get("reduce", "delta")
    scale = float(spec.get("scale", 1.0))
    if ob.stats_start is None or ob.stats_end is None:
        return None

    def delta(path):
        a, b = _dig(ob.stats_start, path), _dig(ob.stats_end, path)
        return None if a is None or b is None else b - a

    if how == "delta":
        d = delta(spec["key"])
        return None if d is None else scale * d
    if how == "last":
        v = _dig(ob.stats_end, spec["key"])
        return None if v is None else scale * v
    if how == "ratio":
        num = (
            ob.scalars.get(spec["numerator_scalar"])
            if "numerator_scalar" in spec else delta(spec["key"])
        )
        den = delta(spec["per"])
        if num is None or den is None:
            return None
        return scale * num / den if den else float(spec.get("if_no_denominator", 0.0))
    if how == "max_sample":
        got = []
        for s in ob.stats_samples or [ob.stats_end]:
            v, per = _dig(s, spec["key"]), _dig(s, spec["per"]) if "per" in spec else 1.0
            if v is not None and per:
                got.append(scale * v / per)
        return max(got) if got else None
    raise ValueError(f"unknown stats_delta reduction {how!r}")


def _clock(spec: Dict[str, Any], ob: Observed) -> Optional[float]:
    """Readings on a host clock. ``scalar``, or ``series`` + ``reduce``; optionally
    ``minus`` another (series, reduce) pair; or ``reduce: mfu``: required
    operations per token x the scalar ``rate`` over chips x peak."""
    scale = float(spec.get("scale", 1.0))
    if spec.get("reduce") == "mfu":
        need = ("flops_per_token", "peak_flops_per_s", "chips", spec["rate"])
        if any(k not in ob.scalars for k in need):
            return None
        s = ob.scalars
        return 100.0 * s["flops_per_token"] * s[spec["rate"]] / (s["chips"] * s["peak_flops_per_s"])
    if "scalar" in spec:
        v = ob.scalars.get(spec["scalar"])
    else:
        v = _reduce(ob.series.get(spec["series"], []), spec["reduce"])
    if v is None:
        return None
    if "minus" in spec:
        w = _reduce(ob.series.get(spec["minus"]["series"], []), spec["minus"]["reduce"])
        if w is None:
            return None
        v -= w
    return scale * v


def _device_trace(spec: Dict[str, Any], ob: Observed) -> Optional[float]:
    """The profiler's trace. ``reduce``: ``program_median_ms`` (median
    duration of the executions of the slowest compiled program, the largest
    bucket, of the jitted functions matching ``name_regex``),
    ``ops_share_of_busy`` (time in matching device operations over busy
    time, %), ``exposed_share`` (matching operations running alone over the
    window, %), ``idle_share`` (%)."""
    if ob.trace is None:
        return None
    how = spec["reduce"]
    if how == "idle_share":
        return tr.idle_share_pct(ob.trace)
    if how == "program_median_ms":
        return tr.slowest_program_median_ms(ob.trace, spec["name_regex"])
    if how == "ops_share_of_busy":
        return tr.ops_share_of_busy_pct(ob.trace, spec["name_regex"])
    if how == "exposed_share":
        return tr.exposed_share_pct(ob.trace, spec["name_regex"])
    raise ValueError(f"unknown device_trace reduction {how!r}")


READERS: Dict[str, Callable[[Dict[str, Any], Observed], Optional[float]]] = {
    "stats_delta": _stats_delta,
    "client_clock": _clock,
    "host_clock": _clock,
    "device_trace": _device_trace,
}


def read(spec: Dict[str, Any], ob: Observed) -> Optional[float]:
    try:
        reader = READERS[spec["kind"]]
    except KeyError:
        raise ValueError(f"unknown layer-metric kind {spec['kind']!r} (has {sorted(READERS)})") from None
    return reader(spec, ob)


def read_all(specs: Dict[str, Dict[str, Any]], ob: Observed) -> Dict[str, float]:
    """name -> value for every spec whose reader found something."""
    out = {}
    for name, spec in specs.items():
        v = read(spec, ob)
        if v is not None:
            out[name] = float(v)
    return out
