"""serve.llm-style deployment: the InferenceEngine behind Serve.

``llm_deployment(...)`` returns a regular Serve :class:`Deployment`
whose replicas each host one :class:`LLMServer` (engine + model params).
Tokens stream to callers through the runtime's ``num_returns="streaming"``
generator path and the Serve router/proxy:

    from ray_tpu import serve
    from ray_tpu.inference import EngineConfig, llm_deployment

    dep = llm_deployment(LlamaConfig.tiny(), engine=EngineConfig(num_blocks=64))
    handle = serve.run(dep.bind())
    for tok in handle.stream({"prompt": [3, 7, 11], "max_new_tokens": 16},
                             _method="generate"):
        ...

Per-request deadlines: the caller's timeout propagates onto the task
spec (``core/deadline.py``) and the executing replica re-enters the
budget, so ``LLMServer.generate`` submits with the remaining budget and
the engine stops decoding for callers that already gave up. Node drain:
each replica engine subscribes to the node DRAINING push — a preemption
warning stops admission while Serve unroutes the replica and waits for
the in-flight streams, so clients see completed generations, not errors.

Retry semantics note (the three-tier contract, serve/router.py):
``handle.call``/``router.execute`` are at-least-once across replica
death; ``handle.stream(..., _method="generate")`` is EXACTLY-ONCE —
``generate`` is declared in :attr:`LLMServer.resumable_streams`, so the
router resumes an interrupted stream on a survivor with the prompt
extended by the already-delivered tokens, and deterministic continuation
(engine sampling keyed on ``(seed, position)``) makes the replayed
stream byte-exact. The replay is sound ONLY because generation is
side-effect-free and deterministic given (params seed, request seed,
prompt) — a callable with external side effects must not declare its
streams resumable.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Dict, Iterator, List, Optional, Sequence, Union

from ray_tpu.core.streaming import TokenChunk


class LLMServer:
    """One replica: model params + continuous-batching engine.

    Defined undecorated at module level so cloudpickle exports it by
    reference (see serve/replica.py for the rationale).
    """

    #: streaming methods that are safe to RESUME on another replica after
    #: a mid-stream death (serve router exactly-once token delivery).
    #: Sound here because generation is deterministic (same params seed +
    #: request seed + prompt → same tokens, engine sampling keyed on
    #: (seed, position)) and side-effect-free; anything that writes to
    #: the outside world per item must never appear in this tuple.
    resumable_streams = ("generate",)

    def __init__(
        self,
        model_cfg=None,
        engine_cfg=None,
        *,
        seed: int = 0,
        params=None,
        export_metrics: bool = True,
    ):
        t_enter = time.perf_counter()
        import jax

        from ray_tpu.accelerators.tpu import process_device_report
        from ray_tpu.core.config import GLOBAL_CONFIG
        from ray_tpu.inference.engine import EngineConfig, InferenceEngine
        from ray_tpu.models.interface import model_of

        # raises here, before any weight exists, when the replica was
        # granted chips and JAX landed elsewhere
        process_device_report()
        if model_cfg is None:
            from ray_tpu.models import LlamaConfig

            model_cfg = LlamaConfig.tiny()
        self.model_cfg = model_cfg
        # the start-up account (engine_stats()["startup"]): the weights, and
        # below the whole of this constructor (what is left of it is the
        # import of JAX and the backend's first answer); the engine adds
        # its cache and its warm-up
        t0 = time.perf_counter()
        if params is None:
            # one compiled program: the draw, scale and cast of each weight
            # fuse (no float32 copy of a bf16 model), and a restarted or
            # sibling replica finds the program in the compile cache
            params = jax.block_until_ready(
                jax.jit(partial(model_of(model_cfg).init_params, model_cfg))(
                    jax.random.PRNGKey(seed)
                )
            )
        param_init_s = time.perf_counter() - t0
        self.engine = InferenceEngine(
            model_cfg, params, engine_cfg or EngineConfig()
        ).start()
        self.engine.attach_node_drain_listener()
        self._metrics_server = None
        if export_metrics and GLOBAL_CONFIG.metrics_export_enabled:
            # replicas run in worker processes, which don't host the
            # daemon's /metrics endpoint — export the engine gauges from
            # an auto-port server of our own (address via metrics_address)
            from ray_tpu.observability.metrics import MetricsServer

            self._metrics_server = MetricsServer(
                host=GLOBAL_CONFIG.metrics_bind_host, port=0
            )
        self.engine.startup.update(
            param_init_s=param_init_s,
            replica_init_s=time.perf_counter() - t_enter,
        )

    # -- request plumbing -------------------------------------------------
    @staticmethod
    def _parse(request) -> Dict[str, Any]:
        if isinstance(request, dict):
            if "prompt" not in request:
                raise ValueError("request dict needs a 'prompt' (list of token ids)")
            return dict(request)
        if isinstance(request, (list, tuple)):
            return {"prompt": list(request)}
        raise TypeError(
            f"request must be a dict or token list, got {type(request).__name__}"
        )

    def generate(self, request) -> Iterator[int]:
        """Streaming entry (call with ``num_returns="streaming"`` /
        ``handle.stream(..., _method="generate")``): yields
        :class:`TokenChunk` bursts of token ids as they decode (one per
        engine wake-up — the serve router flattens them, so
        ``handle.stream`` consumers still see a per-token stream).
        Request fields: prompt (required), max_new_tokens,
        temperature, priority, eos_token, request_id, seed, resume_from,
        speculative (per-request off-switch for a speculative engine —
        output bytes are identical either way).

        ``resume_from`` (stamped by the serve router for resumable
        streams; absent for direct callers) switches to seq-numbered
        mode: the prompt carries ``resume_from`` already-delivered
        tokens of an interrupted stream, and chunk elements become
        ``(seq, token)`` pairs so the router can suppress replayed
        duplicates at the failover boundary. ``max_new_tokens`` stays
        the ORIGINAL request's cap — the replica subtracts what was
        already delivered, so the client-visible stream length never
        changes across failovers."""
        import time as _time

        r = self._parse(request)
        resume_from = r.get("resume_from")
        tenant_class = str(r.get("tenant_class") or "")
        # resumable streams are observed into the SLO latency histograms
        # by the ROUTER (slo_observer="router"): the router sees the
        # client-perceived timeline — failover stalls count as slow
        # gaps, samples survive replica SIGKILLs, and a resume attempt's
        # artificially fast warm replay (resume_attempt>=1) never lands
        # as its own sample. The engine observes only for requests no
        # router is watching (direct callers, non-resumable streams).
        record_slo = not (
            r.get("resume_attempt") or r.get("slo_observer") == "router"
        )
        ledger_stages = {}
        desc = r.pop("kv_import", None)
        if desc is not None and not resume_from:
            # not resume_from: attempt 0 of a resumable stream carries
            # resume_from=0 (the router stamps it on every attempt), and
            # 0 delivered tokens means the prompt is still the original
            # one the descriptor was exported for
            # disaggregated handoff: install the prefill pool's KV
            # blocks BEFORE submitting, so admission acquires them as a
            # prefix hit (prefill_pos=cached; the 1-token tail rides the
            # existing COW last-block rule). Any failure — transfer,
            # digest, pool pressure, shape mismatch — degrades to a
            # plain full prefill right here; the stream never fails
            # because of the migration.
            t0 = _time.monotonic()
            self._import_kv(desc, r["prompt"])
            # ledger stage: the KV fetch+scatter ran BEFORE submit, so
            # its cost is handed to the engine's ledger as a pre-stage
            ledger_stages["kv_import"] = _time.monotonic() - t0
        tier = r.pop("kv_tier", None)
        if tier is not None:
            # cluster KV-tier fault-in (PR 17): unlike kv_import this
            # runs on EVERY attempt — on a resume the prompt already
            # carries the delivered tokens, and the router re-attached
            # descriptors for the extended token chain, so a failover's
            # "replay" becomes tier hits instead of re-prefill. Every
            # failure rung degrades toward plain prefix replay; the
            # stream itself can never fail here.
            t0 = _time.monotonic()
            committed = self._import_tier(tier, r["prompt"])
            ledger_stages["kv_tier"] = _time.monotonic() - t0
            # the router books replayed=0 when the chain COVERED the
            # stream — but the fallback outcome is only known HERE, so
            # a covered-but-failed fault-in reconciles its real replay
            # cost into the resume counters from the replica side
            self._reconcile_tier_replay(tier, r["prompt"], resume_from, committed)
        if resume_from is None:
            # bursts ride ONE stream item each (TokenChunk; the router
            # flattens): a speculative engine commits up to k+1 tokens
            # per verify step, and per-item stream overhead must be paid
            # per step, not per token, for that win to reach clients
            for chunk in self.engine.generate_chunks(
                r["prompt"],
                max_new_tokens=r.get("max_new_tokens"),
                temperature=float(r.get("temperature", 0.0)),
                priority=int(r.get("priority", 0)),
                eos_token=r.get("eos_token"),
                request_id=r.get("request_id"),
                seed=r.get("seed"),
                tenant_class=tenant_class,
                ledger_stages=ledger_stages,
                record_slo=record_slo,
                speculative=r.get("speculative"),
            ):
                yield TokenChunk(chunk)
            return
        seq = int(resume_from)
        max_new = r.get("max_new_tokens")
        if max_new is None:
            max_new = self.engine.engine_cfg.max_new_tokens_default
        # the cap the ORIGINAL run actually obeyed: the engine clamps
        # max_new_tokens to the context room (max_seq_len - prompt), so
        # a room-clamped stream ends early — resume math must use the
        # clamped cap, or a death exactly after the last clamped token
        # would resubmit with remaining>0 and a full-context prompt,
        # raising "prompt >= max_seq_len" instead of closing cleanly
        orig_prompt_len = len(r["prompt"]) - seq
        effective_cap = min(
            int(max_new), max(0, self.engine.cfg.max_seq_len - orig_prompt_len)
        )
        remaining = effective_cap - seq
        if remaining <= 0:
            # the whole (clamped) budget was delivered before the
            # failover: the resume covers only the end-of-stream signal
            return
        eos = r.get("eos_token")
        if eos is not None and seq > 0 and r["prompt"][-1] == eos:
            # the stream already ENDED at this EOS — it was delivered,
            # then the replica died before the end-of-stream signal. The
            # engine's EOS check applies only to SAMPLED tokens, so
            # decoding past the replayed EOS would emit tokens an
            # undisturbed run never produced.
            return
        for chunk in self.engine.generate_chunks(
            r["prompt"],
            max_new_tokens=remaining,
            temperature=float(r.get("temperature", 0.0)),
            priority=int(r.get("priority", 0)),
            eos_token=r.get("eos_token"),
            request_id=r.get("request_id"),
            seed=r.get("seed"),
            tenant_class=tenant_class,
            ledger_stages=ledger_stages,
            record_slo=record_slo,
            speculative=r.get("speculative"),
        ):
            yield TokenChunk((seq + i, tok) for i, tok in enumerate(chunk))
            seq += len(chunk)

    def __call__(self, request) -> Dict[str, Any]:
        """Non-streaming: returns the full generation in one reply."""
        return {"tokens": [t for chunk in self.generate(request) for t in chunk]}

    # -- disaggregated prefill/decode (inference/kv_transfer.py) ----------
    def prefill_export(self, request) -> Optional[Dict[str, Any]]:
        """Prefill-pool entry of the disaggregated two-stage dispatch:
        run ONLY the prompt's prefill (no token sampled), publish the
        gathered KV blocks through the local daemon's store, and return
        the migration descriptor the router attaches to the decode
        dispatch. Returns None when the prompt spans no full block —
        nothing worth migrating. Idempotent in effect: a retried export
        publishes a fresh segment; unconsumed ones are TTL-reaped."""
        from ray_tpu.inference import kv_transfer

        r = self._parse(request)
        payload = self.engine.prefill_kv(
            r["prompt"],
            priority=int(r.get("priority", 0)),
            request_id=r.get("request_id"),
        )
        if payload is None:
            return None
        return kv_transfer.publish(payload)

    def _import_kv(self, desc: Dict[str, Any], prompt) -> bool:
        """Decode-pool half: fetch the descriptor's payload (zero-copy
        pull path, digest-before-attach) and scatter it into this
        engine's cache + radix index. Failure ladder: every exception is
        swallowed into a counted fallback — the caller proceeds with a
        plain prefill."""
        from ray_tpu.inference import kv_transfer

        eng = self.engine
        try:
            shape = tuple(desc.get("shape") or ())
            # the payload this engine's cache layout exports and imports
            # (any number of blocks): [arrays, L, P, bs, *row]
            layout = eng.runner.cache_layout
            expect = layout.payload_shape(None)
            if (
                len(shape) != len(expect)
                or int(desc.get("block_size") or 0) != eng.blocks.block_size
                or any(e is not None and s != e for s, e in zip(shape, expect))
                or str(desc.get("dtype")) != layout.dtype_name
            ):
                kv_transfer.count_failure("shape")
                kv_transfer.count_fallback("shape_mismatch")
                return False
            from ray_tpu.core.config import GLOBAL_CONFIG

            fetched = kv_transfer.fetch(
                desc, timeout_s=GLOBAL_CONFIG.serve_disagg_handoff_timeout_s
            )
            try:
                covered = eng.import_kv_blocks(
                    [int(t) for t in prompt[: int(desc["tokens"])]],
                    fetched.array,
                )
            finally:
                fetched.close()
            return covered > 0
        except kv_transfer.KvTransferError:
            kv_transfer.count_fallback("transfer")
            return False
        except Exception:  # noqa: BLE001 — migration must never fail a stream
            kv_transfer.count_failure("import")
            kv_transfer.count_fallback("import")
            return False

    def _import_tier(self, spec: Dict[str, Any], prompt) -> int:
        """Cluster KV-tier consumer: fault the router-attached prefix
        blocks in (zero-copy pull, digest-before-attach, keep_source —
        tier reads never consume the entry) and commit them into this
        engine's cache. ``spec`` is ``{"blocks": [[digest_hex, desc],
        ...]}`` — a consecutive root-anchored chain the router matched
        against the request's tokens, so a fetched block's KV provably
        belongs to exactly that token prefix (chain-digest keying).

        Counted fallback ladder, longest-valid-prefix semantics: the
        first block that fails STOPS the chain (later blocks would be
        unreachable in the radix index anyway) and everything already
        fetched still commits — partial warmth beats none. Returns the
        number of tokens committed; 0 means the caller proceeds on pure
        prefix replay / cold prefill, byte-exact either way."""
        import os
        import signal

        from ray_tpu.core.config import GLOBAL_CONFIG
        from ray_tpu.inference import kv_transfer
        from ray_tpu.observability import rpc_metrics

        eng = self.engine
        blocks = list(spec.get("blocks") or ())
        if not blocks:
            return 0
        bs = eng.blocks.block_size
        layout = eng.runner.cache_layout
        expect = layout.payload_shape(1)  # one block: [arrays, L, 1, bs, *row]
        fetched: List[Any] = []
        try:
            for _digest_hex, desc in blocks:
                if str(desc.get("tier_ns") or "") != getattr(eng, "_tier_ns", ""):
                    # model-identity gate: the chain digest names the
                    # TOKENS, not the weights that computed the KV — a
                    # descriptor published under another deployment's
                    # namespace passes every shape/dtype check (same
                    # architecture!) yet holds a different model's KV
                    rpc_metrics.KV_TIER_FALLBACKS.inc(
                        labels={"reason": "namespace"}
                    )
                    break
                shape = tuple(desc.get("shape") or ())
                if (
                    len(shape) != len(expect)
                    or int(desc.get("block_size") or 0) != bs
                    or any(s != e for s, e in zip(shape, expect))
                    or str(desc.get("dtype")) != layout.dtype_name
                ):
                    rpc_metrics.KV_TIER_FALLBACKS.inc(
                        labels={"reason": "shape"}
                    )
                    break
                try:
                    payload = kv_transfer.tier_fetch(
                        desc,
                        timeout_s=(
                            GLOBAL_CONFIG.serve_disagg_handoff_timeout_s
                        ),
                    )
                except kv_transfer.KvTransferError as e:
                    msg = str(e)
                    reason = (
                        "missing" if "missing" in msg
                        else "digest" if "digest" in msg
                        else "transfer"
                    )
                    rpc_metrics.KV_TIER_FALLBACKS.inc(
                        labels={"reason": reason}
                    )
                    break
                fetched.append(payload)
            if not fetched:
                return 0
            verdict = kv_transfer.consult_tier_chaos("migration")
            if verdict is not None and verdict[0] == "kill_mid_migration":
                # die exactly like a replica lost mid-migration: blocks
                # fetched, nothing committed, process gone without a
                # goodbye. The router's resume machinery is the fallback
                # rung (STREAM_RESUMES counts it); the tier entries
                # survive in their holder daemons for the next attempt.
                os.kill(os.getpid(), signal.SIGKILL)
            import numpy as _np

            kv = (
                fetched[0].array
                if len(fetched) == 1
                else _np.concatenate([f.array for f in fetched], axis=2)
            )
            covered = len(fetched) * bs
            n = eng.import_kv_blocks(
                [int(t) for t in prompt[:covered]], kv
            )
            if n > 0:
                rpc_metrics.KV_TIER_HITS.inc(n // bs)
                rpc_metrics.KV_TIER_BYTES.inc(
                    sum(int(f.array.nbytes) for f in fetched),
                    labels={"direction": "fault_in"},
                )
            return int(n)
        except Exception:  # noqa: BLE001 — fault-in must never fail a stream
            rpc_metrics.KV_TIER_FALLBACKS.inc(labels={"reason": "import"})
            return 0
        finally:
            for f in fetched:
                f.close()

    def _reconcile_tier_replay(
        self, spec: Optional[Dict[str, Any]], prompt, resume_from, committed: int
    ) -> None:
        """Replay accounting for RESUME attempts: the router books
        ``replayed=0`` when the attached chain COVERS the stream,
        trusting the fault-in — but only this side knows whether it
        actually landed. When a covered chain commits short (fallback
        ladder: missing holder, digest mismatch, import failure), the
        positions the router assumed warm get re-prefilled here, and the
        delivered-region share of that work is real replay — book the
        shortfall into the same sinks the router uses so covered-but-
        failed fault-ins stop undercounting replay."""
        try:
            seq = int(resume_from or 0)
            if seq <= 0 or not spec:
                return
            n_blocks = len(spec.get("blocks") or ())
            tokens = int(spec.get("tokens") or 0)
            if n_blocks <= 0 or tokens <= 0:
                return
            bs = tokens // n_blocks
            prompt_len = len(prompt)
            if tokens < prompt_len - bs:
                return  # not covered: the router counted the replay
            # positions assumed warm but re-prefilled, clipped to the
            # delivered region (re-prefilling the ORIGINAL prompt is
            # prompt work any attempt-0 request pays too, not replay)
            owed = max(0, tokens - max(int(committed), prompt_len - seq))
            if owed <= 0:
                return
            from ray_tpu.observability import rpc_metrics
            from ray_tpu.observability.slo import slo_metrics

            rpc_metrics.STREAM_RESUME_REPLAY_TOKENS.inc(owed)
            slo_metrics()["fault"].inc(
                owed,
                labels={
                    "deployment": self.engine.slo_deployment,
                    "reason": "resume_replay",
                },
            )
        except Exception:  # noqa: BLE001 — accounting never fails a stream
            pass

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued/running request by id; frees its KV blocks.
        The serve stream-close path usually beats callers to it (an
        abandoned stream cancels its producer task, which closes the
        generator and cancels the engine request) — this is the explicit
        escape hatch for callers that tracked only the request id."""
        return self.engine.cancel(str(request_id))

    # -- introspection ----------------------------------------------------
    def set_deployment_name(self, name: str) -> None:
        """serve/replica.py hook: stamps the deployment label onto the
        engine's SLO histograms/counters before any request arrives."""
        self.engine.set_deployment_name(name)

    def slo_snapshot(self) -> Dict[str, Any]:
        """SLO-ledger dump for ``serve.slo_report()``: this process's
        latency histograms + flight recorder, plus the engine's intake
        books (exact conservation: submitted == finished + failed +
        cancelled + in-flight)."""
        return self.engine.slo_snapshot()

    def engine_stats(self) -> Dict[str, Any]:
        from ray_tpu.accelerators.tpu import process_device_report

        # what this replica computes on, and its device memory high-water
        return {**self.engine.stats(), "device": process_device_report()}

    def routing_stats(self) -> Dict[str, Any]:
        """Load + prefix-digest gossip consumed by the serve router's
        cache-affinity scoring. The presence of this method is what opts
        a deployment's replicas into the gossip reporter
        (``serve/replica.py``) — plain deployments never pay for it."""
        return self.engine.routing_stats()

    def metrics_address(self) -> Optional[str]:
        if self._metrics_server is None:
            return None
        return f"{self._metrics_server.host}:{self._metrics_server.port}"

    def begin_drain(
        self, grace_s: Optional[float] = None, migrate: bool = False
    ) -> None:
        """Test/ops hook: drain without a node event. ``migrate=True``
        (tier-enabled engines only) additionally hands every in-flight
        decode's FULL KV — prompt plus generated — to the tier and fails
        the requests with the resumable migration marker, so the router
        moves each stream to a survivor that admits it as tier hits:
        live decode migration instead of drain-then-replay."""
        self.engine.begin_drain(grace_s, migrate=migrate)

    def check_health(self) -> bool:
        """Polled by the serve controller (replica.health): False once
        the engine's step loop is dead or wedged — the signal that gets
        a stalled replica proactively restarted (engine.healthy())."""
        return self.engine.healthy()

    def testing_arm_replica_chaos(self, spec: str, seed: int) -> int:
        """Test hook: install a ReplicaFaultPlan on THIS replica only
        (the env/config plan arms every replica including controller
        replacements — surgical tests target one). Returns the seed."""
        from ray_tpu.util.chaos import ReplicaFaultPlan

        self.engine.testing_fault_plan = ReplicaFaultPlan(spec, seed)
        return seed

    def testing_arm_kv_tier_chaos(self, spec: str, seed: int) -> int:
        """Test hook: install a KvTierFaultPlan in THIS replica's
        kv_transfer module only (surgical tier chaos — the env plan
        would arm every process including controller replacements).
        Returns the seed for the repro line."""
        from ray_tpu.inference import kv_transfer
        from ray_tpu.util.chaos import KvTierFaultPlan

        kv_transfer.testing_tier_plan = KvTierFaultPlan(spec, seed)
        return seed

    def __del__(self):
        try:
            self.engine.stop()
            if self._metrics_server is not None:
                self._metrics_server.stop()
        except Exception:
            pass


def llm_deployment(
    model_cfg=None,
    *,
    engine: Any = None,
    name: str = "llm",
    num_replicas: int = 1,
    max_concurrent_queries: int = 32,
    ray_actor_options: Optional[Dict[str, Any]] = None,
    route_prefix: Optional[str] = "/llm",
    seed: int = 0,
    autoscaling_config=None,
    version: Optional[str] = None,
    kv_tier: bool = False,
    disaggregated: bool = False,
    prefill_replicas: int = 1,
    decode_replicas: Optional[int] = None,
    prefill_autoscaling_config=None,
    prefill_actor_options: Optional[Dict[str, Any]] = None,
):
    """Build a Serve deployment serving ``model_cfg`` through a
    continuous-batching engine (the ``serve.llm`` entry point).

    ``serve.run(llm_deployment(cfg).bind())`` → DeploymentHandle whose
    ``stream(request, _method="generate")`` yields tokens and whose
    ``remote(request)`` returns the whole generation. ``num_replicas``
    scales out: each replica hosts its own engine (same ``seed`` → same
    params → identical generations), the router scores replicas by
    outstanding tokens blended with prefix-cache affinity, and
    ``autoscaling_config`` reacts to serve ongoing counts PLUS the
    engines' gossiped admission-queue depth. Pin ``version`` to make a
    num_replicas redeploy an in-place scale instead of a rolling
    replacement (model code rarely changes between scale events; a
    fresh replica warmup per scale step would).

    ``disaggregated=True`` splits prefill from decode onto two replica
    pools (README "Disaggregated serving"): a sibling
    ``{name}-prefill`` deployment (``prefill_replicas`` /
    ``prefill_autoscaling_config`` / ``prefill_actor_options``) computes
    prompt KV and exports it over the zero-copy data plane; the decode
    pool (``decode_replicas``, default ``num_replicas``) imports the
    blocks as prefix-cache hits and streams from a 1-token tail
    prefill. ``serve.run(dep.bind())`` deploys BOTH pools; the returned
    handle routes exactly as before (the two-stage dispatch lives in
    the router, keyed off the deployment's ``disagg_prefill`` meta).
    Both engines get ``kv_transfer_enabled`` forced on so migrations
    never recompile. Handoff failures at every rung degrade to plain
    single-replica generation — ``disaggregated`` changes the cost
    profile, never the token stream (deterministic continuation makes
    the handoff byte-exact by construction).

    ``kv_tier=True`` opts every replica into the cluster-wide KV prefix
    tier (README "KV prefix tier"): engines write popular full prefix
    blocks back into daemon-owned tier storage and advertise them
    through the routing gossip, replicas fault advertised prefixes in
    over the zero-copy pull path, and mid-stream failovers resume as
    tier hits instead of replayed prefill. Forces
    ``kv_transfer_enabled`` too (the tier rides the same data plane).
    Off by default: tier write-back warms gather/scatter programs and
    changes the warmup compile set."""
    from ray_tpu import serve

    if kv_tier:
        import dataclasses as _dc

        from ray_tpu.inference.engine import EngineConfig as _EC

        engine = _dc.replace(
            engine or _EC(), kv_transfer_enabled=True, kv_tier_enabled=True
        )

    if not disaggregated:
        dep = serve.deployment(
            name=name,
            num_replicas=num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            ray_actor_options=ray_actor_options,
            route_prefix=route_prefix,
            autoscaling_config=autoscaling_config,
            version=version,
        )(LLMServer)

        class _BoundDeployment:
            """Deployment with the model/engine config pre-bound."""

            def __init__(self, inner):
                self._inner = inner

            def bind(self, **overrides):
                kwargs = {"seed": seed, **overrides}
                return self._inner.bind(model_cfg, engine, **kwargs)

            def __getattr__(self, item):
                return getattr(self._inner, item)

        return _BoundDeployment(dep)

    import dataclasses

    from ray_tpu.inference.engine import EngineConfig
    from ray_tpu.serve import Deployment, DisaggApplication
    from ray_tpu.serve.config import DeploymentConfig

    ec = engine or EngineConfig()
    if not ec.kv_transfer_enabled:
        ec = dataclasses.replace(ec, kv_transfer_enabled=True)
    prefill_name = f"{name}-prefill"
    decode_dep = Deployment(
        LLMServer,
        name,
        DeploymentConfig(
            num_replicas=decode_replicas or num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            ray_actor_options=dict(ray_actor_options or {}),
            autoscaling=autoscaling_config,
            route_prefix=route_prefix,
            version=version,
            disagg_prefill=prefill_name,
        ),
    )
    prefill_dep = Deployment(
        LLMServer,
        prefill_name,
        DeploymentConfig(
            num_replicas=prefill_replicas,
            max_concurrent_queries=max_concurrent_queries,
            ray_actor_options=dict(
                prefill_actor_options or ray_actor_options or {}
            ),
            autoscaling=prefill_autoscaling_config,
            route_prefix=None,
            version=version,
        ),
    )

    class _BoundDisagg:
        """Two-pool deployment bundle with the configs pre-bound.
        ``bind()`` returns a :class:`serve.DisaggApplication` —
        ``serve.run`` deploys the prefill pool first, then the decode
        pool, and hands back the decode pool's handle."""

        def __init__(self, decode, prefill):
            self._decode = decode
            self._prefill = prefill

        def bind(self, **overrides):
            kwargs = {"seed": seed, **overrides}
            app = DisaggApplication(
                self._decode, (model_cfg, ec), dict(kwargs)
            )
            app.prefill_app = self._prefill.bind(model_cfg, ec, **kwargs)
            return app

        def __getattr__(self, item):
            return getattr(self._decode, item)

    return _BoundDisagg(decode_dep, prefill_dep)
