"""The serving cells: one replica of the benchmark's ``LLMServer`` subclass
on one chip, behind ``serve.run`` and a ``DeploymentHandle``, under the load
a traffic file describes (``paced_open`` or ``closed``). This process
stays off JAX; the replica holds the chip."""

from __future__ import annotations

import concurrent.futures
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import layer_metrics as lm
from . import schedule as sch
from .program import engine_config, llama_config

DEPLOYMENT = "perfbench-llm"


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


class _Record:
    __slots__ = ("req", "due", "sent", "first", "last", "tokens", "error", "done")

    def __init__(self, req: sch.Request, due: float):
        self.req, self.due = req, due
        self.sent = self.first = self.last = None
        self.tokens = 0
        self.error: Optional[str] = None
        self.done = False


def _replica_call(replica, method: str, *args, timeout: float = 600.0):
    import ray_tpu

    return ray_tpu.get(replica.handle_request.remote(method, list(args), {}, ""), timeout=timeout)


def deploy(model_cfg, engine_cfg, *, seed: int, max_concurrent_queries: int):
    """``serve.run`` of the benchmark's server class with the arguments
    ``llm_deployment`` passes for a plain (not disaggregated) deployment."""
    import ray_tpu
    from ray_tpu import serve

    from .server import BenchLLMServer

    dep = serve.deployment(
        name=DEPLOYMENT,
        num_replicas=1,
        max_concurrent_queries=max_concurrent_queries,
        ray_actor_options={"resources": {"TPU": 1}},
        route_prefix=None,
        autoscaling_config=None,
        version="perfbench",
    )(BenchLLMServer)
    handle = serve.run(dep.bind(model_cfg, engine_cfg, seed=seed % (2**31)))
    controller = serve.get_or_create_controller()
    (replica,) = ray_tpu.get(controller.get_replicas.remote(DEPLOYMENT), timeout=60)
    return handle, replica


def _stream(handle, rec: _Record, payload: Dict[str, Any], on_token: Optional[Callable] = None,
            stop: Optional[threading.Event] = None) -> None:
    rec.sent = time.monotonic()
    try:
        gen = handle.stream(payload, _method="generate", _timeout=300.0)
        for _tok in gen:
            now = time.monotonic()
            if rec.first is None:
                rec.first = now
            if stop is not None and stop.is_set():
                gen.close()  # past the window: the first token's instant was all that was wanted
                return
            rec.last = now
            rec.tokens += 1
            if on_token is not None:
                on_token(rec, now)
        rec.done = True
    except Exception as e:  # noqa: BLE001 - a failed request is a data point
        if stop is None or not stop.is_set():
            rec.error = repr(e)


def _payload(req: sch.Request, vocab: int, rid: str) -> Dict[str, Any]:
    return {
        "prompt": sch.prompt_tokens(req, vocab),
        "max_new_tokens": req.output_len,
        "temperature": 0.0,
        "request_id": rid,
    }


def _at(instant: float, fn: Callable[[], None]) -> threading.Thread:
    def wait_then():
        delay = instant - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        fn()

    t = threading.Thread(target=wait_then, daemon=True)
    t.start()
    return t


class _Window:
    """What is taken from the replica around the measured window: its
    ``engine_stats()`` at both ends, once a second in a traced run, and
    the profiler's start and stop."""

    def __init__(self, replica, t0: float, seconds: float, traffic: Dict[str, Any], trace: bool,
                 trace_dir: str):
        self.ob = lm.Observed()
        self.errors: List[str] = []
        self._replica = replica
        self._threads = [
            _at(t0, lambda: self._grab("stats_start")),
            _at(t0 + seconds, lambda: self._grab("stats_end")),
        ]
        if trace:
            span = min(float(traffic.get("trace_seconds", 5.0)), seconds / 2.0)
            begin = t0 + max(0.0, (seconds - span) / 2.0)
            self._threads += [
                _at(begin, lambda: self._call("bench_trace_start", trace_dir)),
                _at(begin + span, lambda: self._call("bench_trace_stop")),
            ]
            self._threads.append(_at(t0, lambda: self._sample(t0 + seconds)))

    def _call(self, method: str, *args):
        try:
            return _replica_call(self._replica, method, *args)
        except Exception as e:  # noqa: BLE001
            self.errors.append(f"{method}: {e!r}")

    def _grab(self, name: str) -> None:
        setattr(self.ob, name, self._call("engine_stats"))

    def _sample(self, until: float) -> None:
        while time.monotonic() < until:
            s = self._call("engine_stats")
            if s is not None:
                self.ob.stats_samples.append(s)
            time.sleep(1.0)

    def join(self) -> None:
        for t in self._threads:
            t.join(timeout=120)
        if self.errors:
            raise RuntimeError(f"calls to the replica failed: {self.errors}")


def _paced(handle, traffic, seed, seconds, vocab, window_factory) -> Dict[str, Any]:
    requests = sch.paced_schedule(traffic, seed, seconds)
    payloads = [
        _payload(r, vocab, f"{'w' if r.index >= 0 else 'l'}{abs(r.index)}-{seed}") for r in requests
    ]
    lead_s = -min(r.due_s for r in requests) if requests else 0.0
    t0 = time.monotonic() + lead_s + 0.25
    window = window_factory(t0)
    records = [_Record(r, t0 + r.due_s) for r in requests]
    say(f"paced_open: {sch.describe(requests)} at {traffic['rate_per_s']} requests/s, "
        f"lead-in {lead_s:.1f}s")
    with concurrent.futures.ThreadPoolExecutor(int(traffic.get("client_threads", 128))) as pool:
        futures = []
        for rec, payload in zip(records, payloads):
            delay = rec.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(_stream, handle, rec, payload))
        deadline = t0 + seconds + float(traffic["drain_grace_s"])
        concurrent.futures.wait(futures, timeout=max(0.0, deadline - time.monotonic()))
        measured = [r for r in records if r.req.index >= 0]
        # what has not finished when the grace ends has failed
        unfinished = [r for r in measured if not r.done and r.error is None]
        for r in unfinished:
            r.error = "not finished when the drain grace ended"
        pool.shutdown(wait=False, cancel_futures=True)
    window.join()
    ok = [r for r in measured if r.done]
    ob = window.ob
    ob.series.update(
        ttft_ms=[1e3 * (r.first - r.due) for r in ok],
        client_ttft_ms=[1e3 * (r.first - r.sent) for r in ok],
        tpot_ms=[1e3 * (r.last - r.first) / (r.tokens - 1) for r in ok if r.tokens > 1],
        late_ms=[1e3 * (r.sent - r.due) for r in measured if r.sent is not None],
    )
    ob.scalars.update(
        output_tokens=float(sum(r.tokens for r in measured)),
        prompt_tokens=float(sum(r.req.prompt_len for r in ok)),
    )
    short = [r for r in ok if r.tokens != r.req.output_len]

    def backlog(at: float) -> int:
        """Requests due by ``at`` and not finished by then."""
        return sum(1 for r in measured if r.due <= at and not (r.done and r.last <= at))

    say(f"backlog (due and unfinished): {backlog(t0 + seconds / 2)} at the middle of the window, "
        f"{backlog(t0 + seconds)} at its end; sender late by "
        f"{max(ob.series['late_ms'], default=0.0):.1f} ms at most")
    return {
        "backlog_mid": backlog(t0 + seconds / 2), "backlog_end": backlog(t0 + seconds),
        "t0": t0, "window": window, "attempted": len(measured),
        "failed": len(measured) - len(ok),
        "errors": [r.error for r in measured if r.error][:5],
        "wrong_length": len(short),
        "ids": {f"w{r.req.index}-{seed}" for r in ok},
        "samples": len(ok),
    }


def _closed(handle, traffic, seed, seconds, vocab, window_factory) -> Dict[str, Any]:
    stream = sch.closed_stream(traffic, seed)
    clients = int(traffic["clients"])
    t0 = time.monotonic() + float(traffic["lead_in_seconds"])
    t1 = t0 + seconds
    window = window_factory(t0)
    stop = threading.Event()
    lock = threading.Lock()
    counter = {"next": 0, "output": 0}
    records: List[_Record] = []
    say(f"closed: {clients} clients over a multiset of {len(stream)} requests, "
        f"lead-in {traffic['lead_in_seconds']}s")

    def on_token(rec: _Record, now: float) -> None:
        if t0 <= now < t1:
            with lock:
                counter["output"] += 1

    def client() -> None:
        while not stop.is_set():
            with lock:
                i = counter["next"]
                counter["next"] += 1
            req = stream[i % len(stream)]
            rec = _Record(req, time.monotonic())
            with lock:
                records.append(rec)
            _stream(handle, rec, _payload(req, vocab, f"c{i}-{seed}"), on_token, stop)

    threads = [threading.Thread(target=client, daemon=True) for _ in range(clients)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t1 - time.monotonic()))
    stop.set()
    # a client ends with the first token of the request it has in flight:
    # that instant says how much of the request's prefill fell in the window
    edge = t1 + float(traffic["edge_grace_s"])
    for t in threads:
        t.join(timeout=max(0.0, edge - time.monotonic()))
    window.join()
    with lock:
        seen = list(records)
    # a prompt's tokens count in proportion to the part of [sent, first
    # token] that lies in the window: a 4k prompt is seconds of work, and
    # counted whole at its first token it would move a run by 2%
    prompt = sum(
        r.req.prompt_len * max(0.0, min(r.first, t1) - max(r.sent, t0)) / max(r.first - r.sent, 1e-9)
        for r in seen if r.first is not None
    )
    finished = [r for r in seen if r.done and t0 <= r.last < t1]
    failed = [r for r in seen if r.error is not None]
    ob = window.ob
    ob.scalars.update(
        output_tokens=float(counter["output"]),
        prompt_tokens=prompt,
        serve_tokens_per_s=(counter["output"] + prompt) / seconds,
    )
    ob.series.update(
        client_ttft_ms=[1e3 * (r.first - r.sent) for r in finished],
        tpot_ms=[1e3 * (r.last - r.first) / (r.tokens - 1) for r in finished if r.tokens > 1],
    )
    return {
        "t0": t0, "window": window, "attempted": len(finished) + len(failed),
        "failed": len(failed), "errors": [r.error for r in failed][:5],
        "wrong_length": sum(1 for r in finished if r.tokens != r.req.output_len),
        "ids": set(), "samples": len(finished),
    }


GENERATORS = {"paced_open": _paced, "closed": _closed}


def _sweep(handle, replica, traffic, seed, seconds, vocab, rates) -> None:
    """The builder's sweep for the knee of a ``paced_open`` mix: the same
    replica, one window per rate, the engine drained in between. Prints one
    row per rate; the knee is the highest rate at which the backlog at the
    end of the window is no larger than at its middle and nothing failed."""
    from . import stats as st

    for rate in rates:
        mix = {**traffic, "rate_per_s": rate}
        r = _paced(handle, mix, seed, seconds, vocab,
                   lambda t0: _Window(replica, t0, seconds, mix, False, ""))
        ob = r["window"].ob
        steps = ob.stats_end["total_steps"] - ob.stats_start["total_steps"]
        say(f"SWEEP rate {rate} req/s: attempted {r['attempted']} failed {r['failed']} "
            f"backlog mid {r['backlog_mid']} end {r['backlog_end']} "
            f"ttft p50 {st.percentile(ob.series['ttft_ms'], 0.5):.1f} hd90 "
            f"{st.harrell_davis(ob.series['ttft_ms'], 0.9):.1f} ms, tpot p50 "
            f"{st.percentile(ob.series['tpot_ms'], 0.5):.1f} hd90 "
            f"{st.harrell_davis(ob.series['tpot_ms'], 0.9):.1f} ms, engine steps {steps}, "
            f"completed tokens/s {(ob.scalars['output_tokens'] + ob.scalars['prompt_tokens']) / seconds:.0f}")
        _replica_call(replica, "bench_replica_ttft")


def run(
    *, config: Dict[str, Any], traffic: Dict[str, Any], seed: int, seconds: float, trace: bool,
    t_start: float, layer_specs: Dict[str, Dict[str, Any]], work_dir: str, require_tpu: bool = True,
    dump_trace: bool = False, sweep: Optional[List[float]] = None,
) -> Optional[Dict[str, Any]]:
    """One run of a serving cell on a cluster that is up. Returns the
    observations (``Observed``), the device report and the counts; the
    caller turns them into the result line."""
    from ray_tpu import serve
    from ray_tpu.util.reaper import pid_alive

    model = config  # the published keys sit at the top level of the file
    serving = config["serving"]
    model_cfg = llama_config(model, max_seq_len=int(model["max_position_embeddings"]))
    handle, replica = deploy(
        model_cfg, engine_config(serving["engine"]), seed=seed,
        max_concurrent_queries=int(serving["max_concurrent_queries"]),
    )
    try:
        device = _replica_call(replica, "engine_stats")["device"]
        say(f"replica pid {device['pid']}: platform {device['platform']} kind "
            f"{device['device_kind']!r} devices {len(device['device_ids'])} after "
            f"{time.monotonic() - t_start:.1f}s")
        if require_tpu and device["platform"] != "tpu":
            raise SystemExit(f"the replica computes on {device['platform']!r}, not a TPU")

        check = dict(config["correctness"])
        got = _replica_call(
            replica, "bench_check", model, seed, check["prompt_lens"], check["decode_steps"]
        )
        worst = max(got["rel_err"])
        correct = bool(got["finite"] and worst <= check["logit_rel_tol"])
        say(f"correctness: paged prefill+decode vs float32 reference at {got['positions']}: "
            f"worst max|diff|/max|ref| {worst:.5f} (tolerance {check['logit_rel_tol']}) "
            f"after {time.monotonic() - t_start:.1f}s")

        trace_dir = os.path.join(work_dir, "trace")
        if sweep:
            _sweep(handle, replica, traffic, seed, seconds, model["vocab_size"], sweep)
            return None
        result = GENERATORS[traffic["kind"]](
            handle, traffic, seed, seconds, model["vocab_size"],
            lambda t0: _Window(replica, t0, seconds, traffic, trace, trace_dir),
        )
        ob: lm.Observed = result["window"].ob
        marks = _replica_call(replica, "bench_replica_ttft")
        ob.series["replica_ttft_ms"] = [1e3 * v for k, v in marks.items() if k in result["ids"]]
        ob.scalars["setup_s"] = result["t0"] - t_start
        traced = None
        if trace:
            specs = {k: v for k, v in layer_specs.items() if v["kind"] == "device_trace"}
            traced = _replica_call(
                replica, "bench_trace_reduce", specs,
                os.path.join(work_dir, "trace_dump") if dump_trace else None,
                not require_tpu,  # the CPU rehearsal has no device plane
            )
        end = _replica_call(replica, "engine_stats")
        if end["recompiles_after_warmup"]:
            say(f"WARNING: {end['recompiles_after_warmup']} compiles after warm-up")
        if result["wrong_length"]:
            correct = False
            say(f"{result['wrong_length']} requests returned another number of tokens than asked")
        say(f"window: {result['samples']} samples, attempted {result['attempted']}, failed "
            f"{result['failed']} {result['errors']}")
        return {
            "observed": ob, "traced": traced, "correct": correct,
            "attempted": result["attempted"], "failed": result["failed"],
            "device": {
                "platform": device["platform"], "kind": device["device_kind"],
                "count": len(device["device_ids"]),
                "memory_peak_bytes": int(end["device"]["peak_bytes_in_use"]),
            },
            "samples": result["samples"],
        }
    finally:
        pid = None
        try:
            pid = _replica_call(replica, "engine_stats", timeout=30)["device"]["pid"]
        except Exception:  # noqa: BLE001 - the replica may be gone already
            pass
        serve.shutdown()
        deadline = time.monotonic() + 60
        while pid is not None and pid_alive(pid) and time.monotonic() < deadline:
            time.sleep(0.2)
