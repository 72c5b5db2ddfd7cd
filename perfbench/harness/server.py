"""The deployment class of the serving cells: the program's ``LLMServer``
with what only the process that holds the chip can do for the benchmark.

It is user code: a subclass deployed with the arguments ``llm_deployment``
passes, no change to the program. Requests take the parent's path
(``generate`` delegates, and only notes two instants per request). The
extra methods run outside the measured window, except the trace's start
and stop in a ``--trace 1`` run."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from ray_tpu.inference.serve_llm import LLMServer


class BenchLLMServer(LLMServer):
    def __init__(self, model_cfg=None, engine_cfg=None, **kwargs):
        super().__init__(model_cfg, engine_cfg, **kwargs)
        self._bench_lock = threading.Lock()
        self._bench_marks: Dict[str, float] = {}
        self._bench_trace_dir: Optional[str] = None

    # -- the replica's own view of time to first token ---------------------
    def generate(self, request) -> Iterator[Any]:
        entered = time.monotonic()
        rid = request.get("request_id") if isinstance(request, dict) else None
        first = True
        for chunk in super().generate(request):
            if first and rid is not None:
                first = False
                with self._bench_lock:
                    self._bench_marks[rid] = time.monotonic() - entered
            yield chunk

    def bench_replica_ttft(self) -> Dict[str, float]:
        """request id -> seconds from entering ``generate`` on the replica
        to its first chunk, for every request since the last call."""
        with self._bench_lock:
            marks, self._bench_marks = self._bench_marks, {}
        return marks

    # -- correctness --------------------------------------------------------
    def bench_check(self, model: Dict[str, Any], seed: int, prompt_lens: List[int],
                    decode_steps: int) -> Dict[str, Any]:
        """Prefill then decode through the paged cache with the engine's
        own runner and warmed programs, against the plain float32
        reference's full forward pass over the same tokens, from the same
        weights. Teacher-forced (the "generated" tokens are drawn from the
        seed), so rounding cannot change what is compared. The engine must
        be idle: the check writes into blocks 1.. of the free pool, which
        later requests overwrite."""
        import jax.numpy as jnp

        from . import reference

        runner = self.engine.runner
        bs, width = runner.block_size, runner.max_blocks_per_seq
        rng = np.random.default_rng([int(seed), 99])
        totals = [n + decode_steps for n in prompt_lens]
        tokens = rng.integers(1, model["vocab_size"], size=(len(totals), max(totals))).astype(np.int32)
        rows, nxt = [], 1
        for n in totals:
            need = -(-n // bs)
            row = np.zeros(width, np.int32)
            row[:need] = np.arange(nxt, nxt + need)
            nxt += need
            rows.append(row)
        got: List[Any] = []  # (row, position, logits [V])
        largest = runner.prefill_buckets[-1]
        for i, n in enumerate(prompt_lens):
            start = 0
            while start < n:
                c = min(largest, n - start)
                logits = runner.prefill_chunk(tokens[i, start : start + c], rows[i], start)
                start += c
            got.append((i, n - 1, logits))
        for d in range(decode_steps):
            poss = [n + d for n in prompt_lens]
            logits = runner.decode(
                [int(tokens[i, p]) for i, p in enumerate(poss)], poss, rows, [p + 1 for p in poss]
            )
            got += [(i, p, logits[i]) for i, p in enumerate(poss)]
        hidden = reference.hidden_states(model, runner.params, jnp.asarray(tokens))
        picked = jnp.stack([hidden[i, p] for i, p, _ in got])
        want = np.asarray(
            reference.head(runner.params["final_norm"], runner.params["lm_head"], picked,
                           eps=float(model["rms_norm_eps"]))
        )
        errs = []
        for (i, p, have), ref in zip(got, want):
            errs.append(float(np.max(np.abs(have - ref)) / np.max(np.abs(ref))))
        return {
            "positions": [[i, p] for i, p, _ in got],
            "rel_err": errs,
            "finite": bool(all(np.all(np.isfinite(h)) for _, _, h in got)),
        }

    # -- the device trace ------------------------------------------------------
    def bench_trace_start(self, trace_dir: str) -> None:
        import jax

        self._bench_trace_dir = trace_dir
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # TraceMe spans suffice; keeps the trace small
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    def bench_trace_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def bench_trace_reduce(self, specs: Dict[str, Dict[str, Any]], dump_to: Optional[str] = None,
                           host_as_device: bool = False) -> Dict[str, Any]:
        """Reduce the trace here, where it is, after the window: the
        device's busy time and window, the values of the ``device_trace``
        metrics in ``specs``, and the breakdown."""
        from . import layer_metrics as lm
        from . import trace as tr

        trace = tr.load_xplane(self._bench_trace_dir, host_as_device=host_as_device)
        if dump_to:
            tr.dump(trace, dump_to)
        return {
            **tr.busy(trace),
            "metrics": lm.read_all(specs, lm.Observed(trace=trace)),
            "breakdown": tr.breakdown(trace),
        }
