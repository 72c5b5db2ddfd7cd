#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. A new process each time; this process stays off JAX (the chips
belong to the replica or the train worker it starts). Earlier lines are
for people; the LAST line of standard output is the result object. No
TPU, fewer chips than the cell asks for, any failed step: a non-zero exit
and no result line."""

from __future__ import annotations

import time

_T_START = time.monotonic()
_T_START_WALL = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.harness import cells  # noqa: E402
from perfbench.harness import layer_metrics as lm  # noqa: E402


def result_line(bench, cell, out, trace: bool):
    """The contract's object: with ``--trace 0`` the cell's end-to-end
    metrics, with ``--trace 1`` its per-layer metrics."""
    ob: lm.Observed = out["observed"]
    group, folder = ("per_layer", "layer_metrics") if trace else ("end_to_end", "end_to_end")
    values = {}
    traced = out.get("traced") or {}
    for m in cells.metrics_of(bench, cell["name"], group):
        spec = cells.load_json(os.path.join(cells.HERE, folder, f"{m['name']}.json"))
        v = traced.get("metrics", {}).get(m["name"]) if spec["kind"] == "device_trace" else lm.read(spec, ob)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(out["device"])
    line = {
        "correct": bool(out["correct"]), "attempted": int(out["attempted"]),
        "failed": int(out["failed"]), "metrics": values, "device": device,
    }
    if trace:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        line["breakdown"] = traced["breakdown"]
    return line


def layer_specs_of(bench, cell_name):
    return {
        m["name"]: cells.layer_metric_spec(m["name"])
        for m in cells.metrics_of(bench, cell_name, "per_layer")
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--dump-trace", action="store_true",
                    help="also write the trace's structure and a small cut under chiprun_out/")
    ap.add_argument("--sweep", default="",
                    help="builder's use: comma-separated rates (requests/s) of a paced_open mix to "
                         "try on one replica; prints a table and no result line")
    args = ap.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "").lower()
    if platforms and "tpu" not in platforms.split(","):
        raise SystemExit(f"JAX_PLATFORMS={platforms!r} keeps JAX off the TPU: the benchmark measures "
                         "on a TPU and nowhere else")
    bench = cells.benchmark()
    cell = cells.cell(bench, args.workload)
    config = cells.config_of(bench, cell["config"])
    traffic = cells.traffic_of(cell["traffic"])
    specs = layer_specs_of(bench, cell["name"])

    # programs that compile in under a second are cached too: every run
    # after a cell's first finds all of them
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    import ray_tpu
    from ray_tpu.accelerators.tpu import jax_backend_initialized
    from ray_tpu.core.config import ensure_compile_cache_env
    from ray_tpu.util.reaper import find_runtime_pids, reap_all

    work_dir = os.path.join(ROOT, ".perfbench_work", f"{cell['name']}")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    cache_dir = ensure_compile_cache_env(dict(os.environ))
    print(f"[perfbench] cell {cell['name']}: config {cell['config']}, traffic {cell['traffic']} "
          f"({traffic['kind']}), {cell['chips']} chip(s), seed {args.seed}, {args.seconds}s, "
          f"trace {args.trace}; compile cache {cache_dir}", flush=True)
    ray_tpu.init()
    try:
        chips = int(sum(n["Resources"].get("TPU", 0) for n in ray_tpu.nodes()))
        if chips < cell["chips"]:
            raise SystemExit(f"the cell asks for {cell['chips']} TPU chip(s), the node reports {chips}")
        common = dict(
            config=config, traffic=traffic, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), layer_specs=specs, work_dir=work_dir,
            dump_trace=args.dump_trace,
        )
        if traffic["kind"] == "train_job":
            from perfbench.harness import train_cell

            out = train_cell.run(t_start_wall=_T_START_WALL, chips=cell["chips"], **common)
        else:
            from perfbench.harness import serve_cell

            sweep = [float(x) for x in args.sweep.split(",") if x]
            out = serve_cell.run(t_start=_T_START, sweep=sweep, **common)
    finally:
        ray_tpu.shutdown()
    deadline = time.monotonic() + 30
    while (left := find_runtime_pids(spawner_pid=os.getpid())) and time.monotonic() < deadline:
        time.sleep(0.2)
    if left:
        reap_all(left)
        raise SystemExit(f"runtime processes outlived shutdown: {left}")
    if jax_backend_initialized():
        raise SystemExit("the benchmark's own process initialized a JAX backend")
    if out is None:  # a sweep: a table on the earlier lines, no result
        return 0
    device = out["device"]
    print(f"[perfbench] device: platform {device['platform']} kind {device['kind']!r} "
          f"count {device['count']}; samples {out['samples']}", flush=True)
    if device["platform"] != "tpu" or device["count"] != cell["chips"]:
        raise SystemExit(f"the cell asks for {cell['chips']} TPU chip(s), the run used {device}")
    if args.dump_trace and os.path.isdir(os.path.join(work_dir, "trace_dump")):
        dst = os.path.join(ROOT, "chiprun_out", f"trace_{cell['name']}")
        shutil.rmtree(dst, ignore_errors=True)
        shutil.copytree(os.path.join(work_dir, "trace_dump"), dst)
    line = result_line(bench, cell, out, bool(args.trace))
    shutil.rmtree(work_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
