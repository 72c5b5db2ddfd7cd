"""Every cell of BENCHMARK.json end to end on the CPU with a fake chip, at
toy sizes (``rehearsal.py``): the drivers, the server subclass, the
correctness checks, the traced path and the result line, through the same
entry points the chip run takes. And the command itself refusing to
measure anywhere but on a TPU. No number printed here is a speed."""

import json
import math
import os
import subprocess
import sys
import time

import pytest

import ray_tpu

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import rehearsal  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells  # noqa: E402

BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _refusal(extra_env):
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_NUM_CHIPS"}
    env.update(extra_env)
    return subprocess.Popen(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


@pytest.fixture(scope="module")
def fake_chip_cluster():
    # the command's refusals need clusters of their own: start them now so
    # that they run beside the rehearsal instead of after it
    refusals = {
        "held_to_cpu": _refusal({"JAX_PLATFORMS": "cpu", "RAY_TPU_NUM_CHIPS": "1"}),
        "no_chip": _refusal({"JAX_PLATFORMS": "", "RAY_TPU_NUM_CHIPS": "0"}),
    }
    saved = os.environ.get("RAY_TPU_NUM_CHIPS")
    os.environ["RAY_TPU_NUM_CHIPS"] = "1"
    ray_tpu.init(num_cpus=4)
    try:
        yield refusals
    finally:
        ray_tpu.shutdown()
        if saved is None:
            os.environ.pop("RAY_TPU_NUM_CHIPS", None)
        else:
            os.environ["RAY_TPU_NUM_CHIPS"] = saved
        for proc in refusals.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait()


def _accepts(line, cell, group, traced):
    """What the contract asks of the last line."""
    line = json.loads(json.dumps(line))  # one JSON object, as printed
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    allowed = {m["name"]: m["unit"] for m in cells.metrics_of(BENCH, cell, group)}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert name in allowed and m["unit"] == allowed[name]
        assert isinstance(m["value"], float) and math.isfinite(m["value"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    if traced:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] >= line["device"]["busy_s"]
        for key in ("device_ops", "idle_gaps"):
            assert len(line["breakdown"][key]) <= 10
            assert all(isinstance(n, str) and isinstance(s, float) for n, s in line["breakdown"][key])
    else:
        assert set(line["metrics"]) == set(allowed), "an end-to-end metric is missing"
        assert line["metrics"]["setup_s"]["value"] > 0
        assert "breakdown" not in line


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_end_to_end_on_a_fake_chip(fake_chip_cluster, cell_name, tmp_path):
    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, cell_name)
    config = rehearsal.tiny_config(cell["config"])
    traffic = rehearsal.tiny_traffic(cell["traffic"])
    common = dict(
        config=config, traffic=traffic, seed=2**31 + 7, seconds=2.5, trace=True,
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False,
    )
    if traffic["kind"] == "train_job":
        from perfbench.harness import train_cell

        out = train_cell.run(t_start_wall=time.time(), chips=1, **common)
    else:
        from perfbench.harness import serve_cell

        out = serve_cell.run(t_start=time.monotonic(), **common)
    assert out["device"]["platform"] == "cpu"  # the operator's JAX_PLATFORMS=cpu passes through
    # one run, both lines: the traced run measures the end-to-end metrics too
    _accepts(bench_run.result_line(BENCH, cell, out, False), cell_name, "end_to_end", traced=False)
    traced = bench_run.result_line(BENCH, cell, out, True)
    _accepts(traced, cell_name, "per_layer", traced=True)
    # no CPU number under a device metric's name: what needs the table of peaks is left out
    assert "train_mfu" not in traced["metrics"]


@pytest.mark.parametrize("which, says", [
    ("held_to_cpu", "keeps JAX off the TPU"),
    ("no_chip", "the node reports 0"),
])
def test_the_command_measures_on_a_tpu_or_not_at_all(fake_chip_cluster, which, says):
    proc = fake_chip_cluster[which]
    out, err = proc.communicate(timeout=150)
    assert proc.returncode not in (0, None), (out, err)
    assert says in err, (out, err)
    assert '"metrics"' not in out, out


def test_no_topology_or_backend_at_import():
    code = (
        "import sys; sys.path.insert(0, %r); import perfbench.run, perfbench.harness.serve_cell, "
        "perfbench.harness.train_cell, perfbench.harness.trace, perfbench.harness.layer_metrics; "
        "assert 'jax' not in sys.modules, 'the benchmark imports jax at import time'" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
