"""The training cell: ``JaxTrainer`` with one worker that holds every chip
of the cell, the program's sharded train step under fsdp rules, a fresh
seeded batch every step. ``loop`` runs in the worker (which holds the
chips); ``run`` in the benchmark's process, which stays off JAX."""

from __future__ import annotations

import os
import statistics
import tempfile
import time
from typing import Any, Dict

from . import layer_metrics as lm
from .program import llama_config


def loop(config: Dict[str, Any]) -> None:
    """Set-up (weights on the devices from the seed, the reference's loss,
    one step that compiles), then steps for ``seconds``, then the report."""
    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import batch_sharding, init_sharded, make_train_step

    from . import reference
    from . import trace as tr

    job, model, seed = config["job"], config["model"], config["seed"]
    seconds = float(config["seconds"])
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if config["require_tpu"] and device["platform"] != "tpu":
        raise RuntimeError(f"the train worker computes on {device['platform']!r}, not a TPU")
    cfg = llama_config(model, max_seq_len=int(job["seq_len"]), **config["model_overrides"])
    mesh, rules = train.get_mesh(), train.get_sharding_rules()
    opt = optax.adamw(float(job["lr"]))
    params, opt_state = init_sharded(cfg, mesh, rules, jax.random.PRNGKey(seed % (2**31)), opt)
    step = make_train_step(cfg, opt, mesh=mesh, rules=rules, remat=job["remat"], donate=True)
    batch, seq = int(job["global_batch"]), int(job["seq_len"])
    sharding = batch_sharding(mesh, rules)
    rng = np.random.default_rng([seed, 5])

    def draw():
        tokens = rng.integers(0, cfg.vocab_size, size=(batch, seq + 1), dtype=np.int32)
        return {
            "tokens": jax.device_put(tokens[:, :-1], sharding),
            "targets": jax.device_put(tokens[:, 1:], sharding),
        }

    first = draw()
    # the reference's loss on the first batch, from the same weights, before
    # the step consumes them (the loss a step returns is the one BEFORE its update)
    want = reference.next_token_loss(model, params, first["tokens"], first["targets"])
    state = (params, opt_state)
    del params, opt_state
    state, loss = step(state, first)
    got = float(loss.block_until_ready())
    nxt = draw()
    for _ in range(int(job["warm_steps"])):
        state, loss = step(state, nxt)
        nxt = draw()
        loss.block_until_ready()

    trace_dir = os.path.join(config["work_dir"], "trace")
    trace_steps = int(job["trace_steps"]) if config["trace"] else 0
    trace_from = 3
    losses, step_ms = [], []
    t0 = time.monotonic()
    t0_wall = time.time()
    last = t0
    n = 0
    while last - t0 < seconds:
        if trace_steps and n == trace_from:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        state, loss = step(state, nxt)
        nxt = draw()  # drawn on the host while the device runs the step
        losses.append(float(loss.block_until_ready()))
        now = time.monotonic()
        step_ms.append(1e3 * (now - last))
        last = now
        n += 1
        if trace_steps and n == trace_from + trace_steps:
            jax.profiler.stop_trace()
            trace_steps = 0
    if trace_steps:  # the window ended inside the traced steps
        jax.profiler.stop_trace()
    elapsed = last - t0
    traced = None
    if config["trace"]:
        trace = tr.load_xplane(trace_dir, host_as_device=not config["require_tpu"])
        if config.get("dump_trace"):
            tr.dump(trace, os.path.join(config["work_dir"], "trace_dump"))
        specs = {k: v for k, v in config["layer_specs"].items() if v["kind"] == "device_trace"}
        traced = {
            **tr.busy(trace),
            "metrics": lm.read_all(specs, lm.Observed(trace=trace)),
            "breakdown": tr.breakdown(trace),
        }
    memory = [d.memory_stats() or {} for d in jax.local_devices()]
    train.report({
        "device": {**device, "memory_peak_bytes": int(max(m.get("peak_bytes_in_use", 0) for m in memory))},
        "t0_wall": t0_wall, "elapsed_s": elapsed, "steps": n, "tokens_per_step": batch * seq,
        "step_ms": step_ms, "losses": losses, "first_loss": got, "reference_loss": want,
        "step_programs": step._cache_size(), "traced": traced,
    })


def run(
    *, config: Dict[str, Any], traffic: Dict[str, Any], seed: int, seconds: float, trace: bool,
    t_start_wall: float, layer_specs: Dict[str, Dict[str, Any]], work_dir: str, chips: int,
    require_tpu: bool = True, dump_trace: bool = False,
) -> Dict[str, Any]:
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train import JaxBackendConfig, JaxTrainer, RunConfig, ScalingConfig

    from . import peaks

    model, job = config, traffic  # the published keys sit at the top level of the file
    with tempfile.TemporaryDirectory(prefix="train_", dir=work_dir) as storage:
        result = JaxTrainer(
            loop,
            train_loop_config={
                "model": model, "model_overrides": config["training"]["model_overrides"],
                "job": job, "seed": seed, "seconds": seconds, "trace": trace,
                "layer_specs": layer_specs, "work_dir": work_dir, "require_tpu": require_tpu,
                "dump_trace": dump_trace,
            },
            scaling_config=ScalingConfig(num_workers=1, resources_per_worker={"TPU": chips}),
            backend_config=JaxBackendConfig(
                mesh_spec=MeshSpec(**config["training"]["mesh"]),
                sharding=config["training"]["sharding"],
            ),
            run_config=RunConfig(name="perfbench-train", storage_path=storage),
        ).fit()
    out = result.metrics
    tol = config["correctness"]
    head = sum(out["losses"][: tol["loss_window"]]) / tol["loss_window"]
    tail = sum(out["losses"][-tol["loss_window"]:]) / tol["loss_window"]
    agrees = abs(out["first_loss"] - out["reference_loss"]) <= tol["loss_abs_tol"]
    print(f"[perfbench] correctness: first step's loss {out['first_loss']:.5f} vs float32 "
          f"reference {out['reference_loss']:.5f} (tolerance {tol['loss_abs_tol']}); mean loss of "
          f"the first {tol['loss_window']} steps {head:.4f}, of the last {tail:.4f}; "
          f"{out['steps']} steps in {out['elapsed_s']:.2f}s; step programs {out['step_programs']}",
          flush=True)
    ob = lm.Observed()
    rate = out["steps"] * out["tokens_per_step"] / out["elapsed_s"]
    ob.series["step_ms"] = out["step_ms"]
    ob.scalars.update(
        train_tokens_per_s=rate,
        train_tokens_per_s_steady=1e3 * out["tokens_per_step"] / statistics.median(out["step_ms"]),
        setup_s=out["t0_wall"] - t_start_wall,
        flops_per_token=peaks.train_flops_per_token(model, int(job["seq_len"])),
        chips=float(out["device"]["count"]),
    )
    ob.stats_start = ob.stats_end = {"device": {"peak_bytes_in_use": out["device"]["memory_peak_bytes"]}}
    if out["device"]["platform"] == "tpu":
        ob.scalars["peak_flops_per_s"] = peaks.peaks_for(out["device"]["kind"])["bf16_flops_per_s"]
    return {
        "observed": ob, "traced": out["traced"],
        "correct": bool(agrees and tail < head and out["step_programs"] == 1),
        "attempted": out["steps"], "failed": 0, "device": out["device"], "samples": out["steps"],
    }
