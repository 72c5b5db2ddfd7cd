"""The per-layer metrics that read the engine's own accounts (ISSUE 24):
each is a data file beside the others and one entry of BENCHMARK.json, read
by the ``stats_delta`` reader that was there. The serving cells are
rehearsed on the CPU with a fake chip at toy sizes (``rehearsal.py``) and
must print every one of them. No number printed here is a speed."""

import json
import math
import os
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
from perfbench.harness import layer_metrics as lm  # noqa: E402

BENCH = cells.benchmark()
PACED, BATCH = ["chat-paced"], ["chat-offline", "longprompt-batch"]
STEP = ("host_serial", "schedule", "sample", "emit", "launch", "device_wait", "readback")
#: metric -> (layer, unit, moves, cells, path read in engine_stats())
NEW = {
    **{
        f"step_{phase}_ms.{suffix}": (
            "model runner" if phase in ("launch", "device_wait", "readback") else "engine scheduler",
            "ms", moves, where, ["step_phases", f"{phase}_s"],
        )
        for phase in STEP
        for suffix, moves, where in (
            ("paced", "tpot_p90_ms", PACED), ("batch", "serve_tokens_per_s", BATCH),
        )
    },
    **{
        f"ttft_{stage}_ms": ("engine scheduler", "ms", "ttft_p90_ms", PACED,
                             ["request_stages", f"{key}_s"])
        for stage, key in (("queue_wait", "queue"), ("prefill_wait", "prefill_wait"),
                           ("prefill_run", "prefill_run"))
    },
    **{
        name: ("model runner", "s", "setup_s", PACED + BATCH, ["startup", name])
        for name in ("replica_init_s", "param_init_s", "warmup_s")
    },
}


def _new_for(cell):
    return sorted(name for name, spec in NEW.items() if cell in spec[3])


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    layer, unit, moves, where, key = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter",
        "layer": layer, "moves": moves,
    }
    # the cells ISSUE 24 named, in that order, from the list's start. What follows them is not
    # pinned: a later cell whose program has the counter JOINS the entry (it brings no copy)
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == (layer, unit, moves)
    assert spec["kind"] == "stats_delta" and spec["key"] == key
    if unit == "s":
        assert spec["reduce"] == "last" and "per" not in spec
    else:  # seconds of the account over steps, or over first tokens: ms each
        assert spec["reduce"] == "ratio" and spec["scale"] == 1000.0
        assert spec["per"] == (["total_steps"] if name.startswith("step_")
                               else ["request_stages", "first_tokens"])


#: the per-layer metrics the benchmark had before these, in their order
BEFORE = (
    "loadgen_late_p99_ms", "host_path_ttft_p50_ms", "tokens_per_engine_step.paced",
    "preemptions.paced", "prefill_step_device_ms.paced", "decode_step_device_ms.paced",
    "device_idle_share.paced", "tokens_per_engine_step.batch", "preemptions.batch",
    "prefill_step_device_ms.batch", "decode_step_device_ms.batch", "device_idle_share.batch",
    "device_idle_share.train", "kv_pool_peak_share.batch", "prefix_hit_rate",
    "recompiles_in_window", "train_step_ms", "train_mfu", "flash_kernel_time_share",
    "collective_exposed_share", "peak_hbm_gb",
)


def test_the_twenty_are_appended_and_nothing_else_changed():
    """What was there stays first and in its order; each of the twenty is
    there once, after it. Where the list ends is not pinned: a later PR
    appends its own metrics without touching this file."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(NEW) == 20 and set(NEW) <= set(names)
    assert tuple(names[: len(BEFORE)]) == BEFORE
    assert all(names.count(name) == 1 for name in NEW)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_readers_on_a_worked_account():
    """The reader that was there, on a hand-made pair of ``engine_stats()``."""
    start = {"total_steps": 100, "step_phases": {"emit_s": 1.0, "host_serial_s": 2.0},
             "request_stages": {"first_tokens": 10, "queue_s": 0.5},
             "startup": {"warmup_s": 12.5}}
    end = {"total_steps": 300, "step_phases": {"emit_s": 1.5, "host_serial_s": 4.5},
           "request_stages": {"first_tokens": 30, "queue_s": 0.9},
           "startup": {"warmup_s": 12.5}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    read = lambda name: lm.read(cells.layer_metric_spec(name), ob)  # noqa: E731
    assert read("step_emit_ms.batch") == pytest.approx(2.5)
    assert read("step_host_serial_ms.paced") == pytest.approx(12.5)
    assert read("ttft_queue_wait_ms") == pytest.approx(20.0)
    assert read("warmup_s") == 12.5
    # a program without the account (the parent commit): nothing, not an error
    bare = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert all(lm.read(cells.layer_metric_spec(n), bare) is None for n in NEW)


@pytest.fixture(scope="module")
def fake_chip_cluster():
    saved = os.environ.get("RAY_TPU_NUM_CHIPS")
    os.environ["RAY_TPU_NUM_CHIPS"] = "1"
    ray_tpu.init(num_cpus=4)
    try:
        yield
    finally:
        ray_tpu.shutdown()
        if saved is None:
            os.environ.pop("RAY_TPU_NUM_CHIPS", None)
        else:
            os.environ["RAY_TPU_NUM_CHIPS"] = saved


@pytest.mark.parametrize("cell_name", PACED + BATCH)
def test_rehearsal_prints_every_new_metric_of_the_cell(fake_chip_cluster, cell_name, tmp_path):
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, cell_name)
    seconds = 2.5
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 7, seconds=seconds, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False,
    )
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(_new_for(cell_name)) <= set(got), sorted(set(_new_for(cell_name)) - set(got))
    assert all(math.isfinite(got[n]) and got[n] >= 0 for n in _new_for(cell_name))
    suffix = "paced" if cell_name in PACED else "batch"
    step = {phase: got[f"step_{phase}_ms.{suffix}"] for phase in STEP}
    assert step["device_wait"] > 0 and step["launch"] > 0 and step["emit"] > 0
    # host_serial is everything but device_wait and loop_wait: the leaves
    # that are metrics fit inside it (bookkeeping is the rest)
    leaves = sum(step[p] for p in ("schedule", "sample", "emit", "launch", "readback"))
    assert leaves <= step["host_serial"] * 1.001
    # the account covers the window: its wall time is the window's
    ob = out["observed"]
    wall = ob.stats_end["step_phases"]["wall_s"] - ob.stats_start["step_phases"]["wall_s"]
    assert wall == pytest.approx(seconds, rel=0.1)
    # the traced window names the engine's phases in what the host was doing
    assert any(name.startswith("engine.") for name, _s in line["breakdown"]["idle_gaps"])
    if cell_name in PACED:
        parts = got["ttft_queue_wait_ms"] + got["ttft_prefill_wait_ms"] + got["ttft_prefill_run_ms"]
        assert parts > 0
    startup = ob.stats_end["startup"]
    assert got["replica_init_s"] == startup["replica_init_s"] > got["param_init_s"] > 0
    assert got["warmup_s"] == 0.0  # the rehearsal's engine does not warm up
