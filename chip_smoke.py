#!/usr/bin/env python3
"""chip_smoke.py: the serve path and the train path, once each, on the
chip, through the entry points a user calls.

    python3 chip_smoke.py          # on a machine with one or four TPU chips

The quickest proof that the system still starts on the accelerator. It is
not a benchmark: the times it prints are set-up and smoke times, never
speeds. What it establishes:

* *Serve.* ``ray_tpu.init()`` -> ``serve.run(llm_deployment(...))`` with one
  ``{"TPU": 1}`` replica per chip -> concurrent ``handle.stream`` requests
  (chunked prefill through a 1024 bucket, decode at batch > 1) ->
  ``serve.shutdown()``. The model is ``LlamaConfig.llama2_7b()`` at its full
  width with depth cut so that weights plus a KV pool the requests really
  use fill most of one chip's HBM.
* *Train.* ``JaxTrainer`` with one worker holding every chip -> flash
  attention forward and backward against the float32 reference for every
  backward block bucket, the long fallback and one GQA shape -> a few
  steps of the unified sharded train step (``attention_impl="pallas"``,
  selective remat, donation) at the same widths, sequence 2048.

A chip belongs to one process at a time, so THIS process never initializes
a JAX backend: every phase runs in worker processes the runtime starts, and
the second phase starts only after the first one's workers have exited.

Any failed check, any phase that raises, any worker that dies: non-zero
exit and no result line. On success the last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it in the process that held every chip.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import ray_tpu

_T0 = time.monotonic()

#: the driver allows 1200 s; give up (non-zero, workers reaped) before that
BUDGET_S = 1100.0

#: |flash - reference| <= ATTN_TOL * max|reference|, per tensor (o, dq, dk,
#: dv). The kernel's inputs and outputs are bf16 (eps 2^-8 = 0.0039) and it
#: rounds P and dS to bf16 before their second matmuls, so a few bf16 ulps
#: of the largest value is its floor: the chip measured <= 0.0065 (PR 21).
#: A wrong block, mask or GQA head map is off by O(1), and computing the
#: reference in bf16 instead of float32 would not pass either.
ATTN_TOL = 0.02


def say(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def _check_budget(what: str) -> None:
    if time.monotonic() - _T0 > BUDGET_S:
        raise TimeoutError(f"over the {BUDGET_S:.0f}s budget while {what}")


# ---------------------------------------------------------------------------
# device probe: a task that asks for every chip. It lands on a POOLED worker
# (spawned chip-less, pinned to the CPU), which the daemon promotes by
# handing it chip ids before JAX initializes there. Cheap, and it lets
# main() refuse a machine without a TPU before any model exists.


@ray_tpu.remote
def _device_probe() -> Dict[str, Any]:
    import jax

    from ray_tpu.accelerators.tpu import process_device_report

    report = process_device_report()
    devices = jax.devices()
    x = jax.numpy.ones((512, 512), jax.numpy.bfloat16)
    report["matmul"] = float((x @ x).block_until_ready()[0, 0])
    report["jax_devices"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    return report


# ---------------------------------------------------------------------------
# serve phase


def _replica_call(replica, method: str, *args, timeout: float = 600.0):
    """Call ``method`` on ONE replica's callable (the router would pick)."""
    return ray_tpu.get(
        replica.handle_request.remote(method, list(args), {}, ""), timeout=timeout
    )


def serve_phase(
    model_cfg,
    engine_cfg,
    *,
    chips: int,
    requests_per_replica: int,
    prompt_len_range: Tuple[int, int],
    check_prompt_len: int,
    max_new_tokens: int,
    seed: int = 0,
) -> Dict[str, Any]:
    """Deploy one ``{"TPU": 1}`` LLM replica per chip, stream concurrent
    requests through the router, check every answer, shut serve down and
    wait until the replica processes are gone. Returns the per-replica
    reports. Needs a running cluster (``ray_tpu.init``)."""
    from ray_tpu import serve
    from ray_tpu.inference import llm_deployment
    from ray_tpu.util.reaper import pid_alive

    name = "chip-smoke-llm"

    def deploy(n: int):
        return serve.run(
            llm_deployment(
                model_cfg,
                engine=engine_cfg,
                name=name,
                num_replicas=n,
                # one pinned version: growing from 1 to ``chips`` replicas
                # is an in-place scale, not a rolling replacement
                version="chip-smoke",
                max_concurrent_queries=4 * requests_per_replica,
                ray_actor_options={"resources": {"TPU": 1}},
                route_prefix=None,
                seed=seed,
            ).bind()
        )

    # The first replica compiles every bucket and fills the compile cache;
    # the others start after it and find the programs there instead of
    # compiling the same buckets side by side.
    t = time.monotonic()
    handle = deploy(1)
    say(f"serve set-up: first replica routable after {time.monotonic() - t:.1f}s")
    if chips > 1:
        t = time.monotonic()
        handle = deploy(chips)
        while True:
            st = serve.status()[name]
            if sum(st["restarts"].values()):
                raise RuntimeError(f"replica restarts while scaling up: {st}")
            if st["replicas"] == chips and not st["starting"]:
                break
            _check_budget("waiting for every replica")
            time.sleep(1.0)
        say(f"serve set-up: all {chips} replicas routable after another "
            f"{time.monotonic() - t:.1f}s")

    controller = serve.get_or_create_controller()
    replicas = ray_tpu.get(controller.get_replicas.remote(name), timeout=60)
    assert len(replicas) == chips, (len(replicas), chips)

    # -- what each replica computes on ---------------------------------
    devices = [_replica_call(r, "engine_stats")["device"] for r in replicas]
    for i, d in enumerate(devices):
        say(f"replica {i}: pid {d['pid']} platform {d['platform']} kind "
            f"{d['device_kind']!r} visible_chips {d['visible_chips']} "
            f"chip_files {d['chip_files']} device_ids {d['device_ids']}")
    assert len({d["pid"] for d in devices}) == chips, "replicas share a process"
    assert len({d["visible_chips"] for d in devices}) == chips, (
        f"replicas were granted the same chip: {[d['visible_chips'] for d in devices]}"
    )
    if all(d["platform"] == "tpu" for d in devices):
        # under isolation every replica calls its chip device 0; the device
        # node libtpu opened is what tells the chips apart
        held = [tuple(d["chip_files"]) for d in devices]
        assert all(len(h) == 1 for h in held) and len(set(held)) == chips, (
            f"replicas do not hold one distinct chip each: {held}"
        )
    assert len({(d["platform"], d["device_kind"]) for d in devices}) == 1, devices

    # -- concurrent streams through the router ---------------------------
    rng = np.random.default_rng(seed)
    vocab = model_cfg.vocab_size

    def prompt(n: int) -> List[int]:
        return [int(t) for t in rng.integers(1, vocab, size=n)]

    check_prompt = prompt(check_prompt_len)
    greedy = {"prompt": check_prompt, "max_new_tokens": max_new_tokens}
    sampled = {**greedy, "temperature": 0.8, "seed": 1234}
    n_requests = requests_per_replica * chips
    lo, hi = prompt_len_range
    requests = [greedy, sampled] + [
        {"prompt": prompt(int(rng.integers(lo, hi + 1))), "max_new_tokens": max_new_tokens}
        for _ in range(n_requests - 2)
    ]
    pool_tokens = (engine_cfg.num_blocks - 1) * engine_cfg.block_size
    asked = sum(len(r["prompt"]) + max_new_tokens for r in requests)
    say(f"serve smoke: {n_requests} concurrent streams, {asked} tokens of KV "
        f"asked for against {chips} pool(s) of {pool_tokens} "
        f"({asked / (chips * pool_tokens):.0%})")

    def stream(req) -> List[int]:
        return list(handle.stream(req, _method="generate", _timeout=600.0))

    peak_util = [0.0] * chips
    sampling = threading.Event()

    def sample_pool() -> None:
        while not sampling.wait(0.5):
            for i, r in enumerate(replicas):
                u = _replica_call(r, "engine_stats")["blocks"]["utilization"]
                peak_util[i] = max(peak_util[i], u)

    sampler = threading.Thread(target=sample_pool, daemon=True)
    sampler.start()
    t = time.monotonic()
    try:
        with concurrent.futures.ThreadPoolExecutor(n_requests) as pool:
            outputs = list(pool.map(stream, requests))
    finally:
        sampling.set()
        sampler.join(timeout=60)
    say(f"serve smoke: all streams done in {time.monotonic() - t:.1f}s; "
        f"KV pool utilization seen per replica {[round(u, 2) for u in peak_util]}")
    for out in outputs:
        assert len(out) == max_new_tokens, (
            f"asked for {max_new_tokens} tokens, got {len(out)}"
        )
        assert all(isinstance(t, int) and 0 <= t < vocab for t in out), out

    # -- same prompt and seed -> same tokens, again and on every replica --
    for label, req, first in (("greedy", greedy, outputs[0]), ("seeded", sampled, outputs[1])):
        again = stream(req)
        assert again == first, f"{label}: a repeat through the router differs"
        for i, r in enumerate(replicas):
            got = _replica_call(r, "__call__", req)["tokens"]
            assert got == first, f"{label}: replica {i} differs from the first answer"
    say("serve smoke: greedy and seeded answers repeat exactly, on every replica")

    # -- the engines' own books ------------------------------------------
    reports = []
    for i, r in enumerate(replicas):
        st = _replica_call(r, "engine_stats")
        say(f"replica {i}: compile_count {st['compile_count']} recompiles "
            f"{st['recompiles_after_warmup']} steps {st['total_steps']} "
            f"max_decode_batch {st['scheduler']['max_decode_batch_seen']} "
            f"admitted {st['scheduler']['total_admitted']} peak_bytes_in_use "
            f"{st['device']['peak_bytes_in_use']} of {st['device']['bytes_limit']}")
        assert st["recompiles_after_warmup"] == 0, st
        assert st["scheduler"]["max_decode_batch_seen"] > 1, st["scheduler"]
        assert st["scheduler"]["steps_with_prefill_and_decode"] > 0, st["scheduler"]
        reports.append(st)
    status = serve.status()[name]
    assert sum(status["restarts"].values()) == 0, f"replica restarts: {status}"

    # -- release the chips -------------------------------------------------
    t = time.monotonic()
    serve.shutdown()
    pids = [d["pid"] for d in devices]
    while any(pid_alive(p) for p in pids):
        _check_budget("waiting for the serve workers to exit")
        time.sleep(0.2)
    say(f"serve: replica processes {pids} gone {time.monotonic() - t:.1f}s "
        "after shutdown")
    return {"devices": devices, "replicas": reports, "kv_pool_peak_utilization": peak_util}


# ---------------------------------------------------------------------------
# train phase (``_train_loop`` and ``_check_attention`` run in the worker)


def attention_cases() -> List[Tuple[int, int, int, int]]:
    """(batch, heads, kv_heads, seq) to check on the chip: one per row of
    the backward block table, one above its largest bucket (the long
    fallback), one GQA shape. Head dim is 128 throughout."""
    from ray_tpu.ops.attention import BWD_BLOCK_BUCKETS

    bounds = [bound for bound, _ in BWD_BLOCK_BUCKETS]
    return (
        [(1, 4, 4, bound) for bound in bounds]
        + [(1, 2, 2, 2 * bounds[-1])]
        + [(1, 8, 2, 2048)]
    )


def _check_attention(cases: Sequence[Tuple[int, int, int, int]], d: int = 128):
    """Flash attention forward and backward against the float32 reference
    for every ``(batch, heads, kv_heads, seq)`` of ``cases``. All cases go
    through ONE flash program and ONE reference program: a single case
    compiles in about the compile cache's 1 s write threshold, so per-case
    programs were cached in one run and not in the next. Inputs are drawn
    and errors taken on the host for the same reason."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import (
        default_bwd_blocks,
        flash_attention,
        reference_attention,
    )

    def draw(rng, b, heads, s, dtype):
        return rng.standard_normal((b, heads, s, d), dtype=np.float32).astype(dtype)

    inputs = []
    for b, h, hk, s in cases:
        rng = np.random.default_rng(7 * s + h + hk)
        inputs.append(
            (
                draw(rng, b, h, s, jnp.bfloat16),  # q
                draw(rng, b, hk, s, jnp.bfloat16),  # k
                draw(rng, b, hk, s, jnp.bfloat16),  # v
                draw(rng, b, h, s, np.float32),  # cotangent of the output
            )
        )

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas")

    def reference(q, k, v):
        # float32, highest matmul precision (a TPU runs float32 matmuls in
        # bf16 passes otherwise), K/V repeated to the query heads
        rep = q.shape[1] // k.shape[1]
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
        if rep > 1:
            k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        with jax.default_matmul_precision("highest"):
            return reference_attention(q, k, v, causal=True)

    def fwd_bwd_all(fn):
        def one(q, k, v, g):
            def loss(q, k, v):
                return jnp.sum(fn(q, k, v).astype(jnp.float32) * g)

            return (fn(q, k, v), *jax.grad(loss, (0, 1, 2))(q, k, v))

        return jax.jit(lambda inputs: [one(*case) for case in inputs])

    got_all = jax.device_get(fwd_bwd_all(flash)(inputs))
    want_all = jax.device_get(fwd_bwd_all(reference)(inputs))
    results = []
    for (b, h, hk, s), got, want in zip(cases, got_all, want_all):
        out = {
            "shape": [b, h, hk, s, d],
            "bwd_blocks": list(default_bwd_blocks(s)),
            "interpret": jax.default_backend() != "tpu",
        }
        for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
            w = w.astype(np.float32)
            err = float(np.max(np.abs(g.astype(np.float32) - w)))
            scale = float(np.max(np.abs(w)))
            out[name] = round(err / scale, 5)
            if not err <= ATTN_TOL * scale:
                raise AssertionError(
                    f"flash attention {name} off the reference at {out['shape']}: "
                    f"max err {err} > {ATTN_TOL} * {scale}"
                )
        results.append(out)
    return results


def _train_loop(config: Dict[str, Any]) -> None:
    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.accelerators.tpu import process_device_report
    from ray_tpu.models.llama import batch_sharding, init_sharded, make_train_step

    cfg = config["model_cfg"]
    devices = jax.devices()

    t = time.monotonic()
    attention = _check_attention(config["attention_cases"])
    attention_s = time.monotonic() - t

    t = time.monotonic()
    mesh = train.get_mesh()
    rules = train.get_sharding_rules()
    opt = optax.adamw(config["lr"])
    params, opt_state = init_sharded(
        cfg, mesh, rules, jax.random.PRNGKey(config["seed"]), opt
    )
    step = make_train_step(
        cfg, opt, mesh=mesh, rules=rules, remat="selective", donate=True
    )
    batch, seq = config["batch"], config["seq"]
    tokens = np.random.default_rng(config["seed"] + 1).integers(
        0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32
    )
    sharding = batch_sharding(mesh, rules)
    fixed = {
        "tokens": jax.device_put(tokens[:, :-1], sharding),
        "targets": jax.device_put(tokens[:, 1:], sharding),
    }
    wq = params["layers"][0]["wq"]
    wq_shards = len({(s.device.id, repr(s.index)) for s in wq.addressable_shards})
    state = (params, opt_state)
    del params, opt_state, wq  # donated below
    # What the step needs on each device, as the compiler planned it:
    # ``peak_bytes_in_use`` counts live arrays, not a program's temporaries.
    # (The jit call below compiles the same program again and finds it in
    # the compile cache.)
    planned = step.lower(state, fixed).compile().memory_analysis()
    program_bytes = {
        "arguments": planned.argument_size_in_bytes,
        "outputs": planned.output_size_in_bytes,
        "aliased": planned.alias_size_in_bytes,
        "temporaries": planned.temp_size_in_bytes,
    }
    losses = []
    for _ in range(config["steps"]):
        # the loss a step returns is the loss BEFORE its update
        state, loss = step(state, fixed)
        losses.append(float(loss.block_until_ready()))
    steps_s = time.monotonic() - t

    train.report(
        {
            # taken last: it carries the device memory high-water mark
            "device": process_device_report(),
            "jax_devices": {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            },
            "attention": attention,
            "losses": losses,
            "wq_shards": wq_shards,
            "step_programs": step._cache_size(),
            "step_program_bytes": program_bytes,
            "attention_s": round(attention_s, 1),
            "init_compile_steps_s": round(steps_s, 1),
        }
    )


def train_phase(
    model_cfg,
    *,
    chips: int,
    batch: int,
    seq: int,
    steps: int,
    cases: Sequence[Tuple[int, ...]],
    lr: float = 3e-4,
    seed: int = 0,
) -> Dict[str, Any]:
    """One JaxTrainer worker holding ``chips`` chips: flash attention
    against the reference for ``cases``, then ``steps`` steps of the
    unified sharded train step on a fixed batch. Returns the worker's
    report. Needs a running cluster."""
    import math

    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import JaxBackendConfig, JaxTrainer, RunConfig, ScalingConfig

    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as storage:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "model_cfg": model_cfg, "batch": batch, "seq": seq,
                "steps": steps, "lr": lr, "seed": seed,
                "attention_cases": [tuple(c) for c in cases],
            },
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": chips}
            ),
            backend_config=JaxBackendConfig(
                mesh_spec=MeshSpec(fsdp=-1), sharding="fsdp"
            ),
            run_config=RunConfig(name="chip-smoke", storage_path=storage),
        ).fit()
    out = result.metrics
    d = out["device"]
    say(f"train worker: pid {d['pid']} platform {d['platform']} kind "
        f"{d['device_kind']!r} visible_chips {d['visible_chips']} chip_files "
        f"{d['chip_files']} devices {out['jax_devices']['count']}")
    for a in out["attention"]:
        say(f"flash vs reference {a['shape']} bwd_blocks {a['bwd_blocks']} "
            f"interpret={a['interpret']}: rel err o {a['o']} dq {a['dq']} "
            f"dk {a['dk']} dv {a['dv']} (tol {ATTN_TOL})")
    pb = out["step_program_bytes"]
    say(f"train smoke: losses {[round(x, 4) for x in out['losses']]}; wq shards "
        f"{out['wq_shards']} over {out['jax_devices']['count']} device(s); "
        f"peak_bytes_in_use {d['peak_bytes_in_use']} of {d['bytes_limit']}; the "
        f"step as compiled needs {pb} = "
        f"{pb['arguments'] + pb['outputs'] - pb['aliased'] + pb['temporaries']} "
        "bytes a device; "
        f"attention checks {out['attention_s']}s, init+compile+steps "
        f"{out['init_compile_steps_s']}s (set-up and smoke times, not speeds)")
    assert len(out["attention"]) == len(cases)
    assert all(math.isfinite(x) for x in out["losses"]), out["losses"]
    assert out["losses"][-1] < out["losses"][0], (
        f"loss did not go down on a fixed batch: {out['losses']}"
    )
    n = out["jax_devices"]["count"]
    assert out["wq_shards"] == n, (
        f"wq has {out['wq_shards']} distinct shards over {n} devices"
    )
    assert out["step_programs"] == 1, f"train step compiled {out['step_programs']}x"
    return out


# ---------------------------------------------------------------------------
# the run on the chip


def chip_sizes(chips: int) -> Dict[str, Any]:
    """The one supported model at its full width (dim 4096, 32 heads x 128,
    MLP 11008, vocab 32000, bf16), depth cut to one chip's 16 GB.

    Serve, per chip: 16 of 32 layers = 7.0 GB of weights; 1536 blocks x 16
    tokens x 16 layers x 16 KiB = 6.4 GB of KV pool; the prefill of a
    1024-token chunk over the 4096-wide block table adds ~1.5 GB of
    float32 scores. Eight concurrent requests of ~2.9k tokens each ask
    for ~95% of the pool.

    Train: weights and both AdamW moments in bf16 are 6 bytes a parameter,
    held for the whole run; gradients and the activations selective remat
    keeps are the step's temporaries (2.2 GB at 4 layers, batch 2 x 2048,
    as compiled on the chip, PR 21). One chip holds 6 layers (1.48 B
    parameters, 8.9 GB of state) with a batch of 2 x 2048; with the state
    sharded over four chips the depth is 16 (3.5 B parameters, 5.2 GB of
    state a chip) with one sequence per chip."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.inference import EngineConfig
    from ray_tpu.models.llama import LlamaConfig

    assert LlamaConfig.llama2_7b().dtype == jnp.bfloat16
    return {
        "serve": {
            "model_cfg": LlamaConfig.llama2_7b(n_layers=16),
            "engine_cfg": EngineConfig(
                num_blocks=1536,
                block_size=16,
                prefill_buckets=(256, 1024),
                decode_buckets=(8,),
                max_decode_batch=8,
            ),
            "requests_per_replica": 8,
            "prompt_len_range": (2800, 3000),
            # two full 1024 chunks and a tail through the 256 bucket
            "check_prompt_len": 2048 + 200,
            "max_new_tokens": 48,
        },
        "train": {
            "model_cfg": LlamaConfig.llama2_7b(
                n_layers={1: 6, 4: 16}[chips], max_seq_len=2048,
                attention_impl="pallas",
            ),
            "batch": max(2, chips),
            "seq": 2048,
            "steps": 4,
            "cases": attention_cases(),
        },
    }


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    if "jax" in sys.modules:
        raise SystemExit("`import ray_tpu` pulled in jax: the driver must stay off it")
    from ray_tpu.accelerators.tpu import jax_backend_initialized
    from ray_tpu.core.config import ensure_compile_cache_env
    from ray_tpu.util.reaper import find_runtime_pids, reap_all

    cache_dir = ensure_compile_cache_env(dict(os.environ))
    cache_before = _cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {cache_before} entries")
    ray_tpu.init()
    try:
        chips = int(sum(n["Resources"].get("TPU", 0) for n in ray_tpu.nodes()))
        if chips < 1:
            raise SystemExit("the node reports no TPU chip: nothing to smoke")
        say(f"node reports {chips} TPU chip(s)")
        if chips not in (1, 4):
            raise SystemExit(f"sized for one chip or a four-chip host, not {chips}")

        probe = ray_tpu.get(
            _device_probe.options(resources={"TPU": chips}).remote(), timeout=300
        )
        say(f"device probe (promoted pooled worker, pid {probe['pid']}): {probe}")
        if probe["platform"] != "tpu":
            raise SystemExit(
                f"the worker granted {chips} chip(s) computes on "
                f"{probe['platform']!r}, not a TPU"
            )
        assert probe["jax_devices"]["count"] == chips, probe
        while ray_tpu.available_resources().get("TPU", 0) < chips:
            _check_budget("waiting for the probe worker to release the chips")
            time.sleep(0.2)

        sizes = chip_sizes(chips)
        served = serve_phase(chips=chips, **sizes["serve"])
        cache_mid = _cache_entries(cache_dir)
        say(f"compile cache after serve: {cache_mid} entries (+{cache_mid - cache_before})")
        trained = train_phase(chips=chips, **sizes["train"])
    finally:
        ray_tpu.shutdown()
    cache_after = _cache_entries(cache_dir)
    say(f"compile cache after train: {cache_after} entries "
        f"(+{cache_after - cache_before} this run)")

    for d in served["devices"] + [trained["device"]]:
        assert d["platform"] == "tpu", d
        assert d["device_kind"] == probe["device_kind"], (d, probe)
    assert trained["jax_devices"] == probe["jax_devices"], (trained, probe)
    assert not jax_backend_initialized(), "the driver initialized a JAX backend"
    deadline = time.monotonic() + 30
    while (left := find_runtime_pids(spawner_pid=os.getpid())) and time.monotonic() < deadline:
        time.sleep(0.2)
    if left:
        reap_all(left)
        raise SystemExit(f"runtime processes outlived shutdown: {left}")
    say(f"done in {time.monotonic() - _T0:.0f}s")
    print(json.dumps({"ok": True, "device": trained["jax_devices"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
