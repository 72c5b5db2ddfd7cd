"""The per-layer metrics that read what the step thread WAITS for (ISSUE 54):
``engine_stats()["step_offcpu"]`` (each phase's and part's off-CPU seconds),
``["step_stalls"]`` (the stalled laps' seconds, differenced over the window)
and ``["device_reads"]`` (the waits that found the device done). Nine data
files beside the others and nine entries of BENCHMARK.json, read by the
``stats_delta`` reader that was there. No number printed here is a speed."""

import json
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
PACED = ["chat-paced"]
#: the closed-loop cells, as ``step_host_serial_ms.batch`` lists them
BATCH = ["chat-offline", "longprompt-batch", "moe-chat-offline", "mla-longdoc-batch", "kda-reason-offline",
         "mtp-reason-offline", "swa-mixed-offline", "conv-reason-offline", "ssm-reason-offline"]
SCHEDULER, RUNNER = "engine scheduler", "model runner"
ARROW = {"batch": "serve_tokens_per_s", "paced": "tpot_p90_ms"}
STEPS = {"per": ["total_steps"], "scale": 1000.0}
#: metric -> (layer, unit, cells, what its file holds beside the prose)
NEW = {
    "step_host_offcpu_ms.batch": (SCHEDULER, "ms", BATCH, {"reduce": "ratio", "key": ["step_offcpu", "host_serial_s"], **STEPS}),
    "step_host_offcpu_ms.paced": (SCHEDULER, "ms", PACED, {"reduce": "ratio", "key": ["step_offcpu", "host_serial_s"], **STEPS}),
    "step_gil_offcpu_ms.batch": (SCHEDULER, "ms", BATCH, {"reduce": "ratio", "key": ["step_offcpu", "unblocked_s"], **STEPS}),
    "step_gil_offcpu_ms.paced": (SCHEDULER, "ms", PACED, {"reduce": "ratio", "key": ["step_offcpu", "unblocked_s"], **STEPS}),
    "step_launch_call_offcpu_ms.batch": (RUNNER, "ms", BATCH, {"reduce": "ratio", "key": ["step_offcpu", "launch_call_s"], **STEPS}),
    "stalled_step_ms.batch": (SCHEDULER, "ms", BATCH, {"reduce": "delta", "key": ["step_stalls", "wall_s"], "scale": 1000.0}),
    "stalled_step_ms.paced": (SCHEDULER, "ms", PACED, {"reduce": "delta", "key": ["step_stalls", "wall_s"], "scale": 1000.0}),
    "stalled_step_device_wait_share.batch": (SCHEDULER, "%", BATCH, {
        "reduce": "ratio", "key": ["step_stalls", "device_wait_s"], "per": ["step_stalls", "wall_s"], "scale": 100.0,
        "if_no_denominator": 0.0}),
    "device_ready_on_arrival_share.batch": (RUNNER, "%", BATCH, {
        "reduce": "ratio", "key": ["device_reads", "ready"], "per": ["device_reads", "reads"], "scale": 100.0,
        "if_no_denominator": 0.0}),
}
PROSE = ("layer", "unit", "moves", "kind", "what")


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    layer, unit, where, held = NEW[name]
    moves = ARROW[name.rsplit(".", 1)[1]]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": "lower", "source": "program_counter", "layer": layer, "moves": moves,
    }
    # the cells ISSUE 54 named, from the list's start; a later cell joins after them
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"], spec["kind"]) == (layer, unit, moves, "stats_delta")
    assert {k: v for k, v in spec.items() if k not in PROSE} == held
    # what the reading is for, and what a parent's line does with it
    assert len(spec["what"]) > 200 and "parent of PR 54" in spec["what"] and "leaves the metric out" in spec["what"]


def test_the_nine_are_appended_after_what_was_there_and_beside_the_outside_readings():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(NEW) == 9 and all(names.count(name) == 1 for name in NEW)
    assert names.index("prefill_expand_live_share.mla") < min(names.index(n) for n in NEW)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    # every .batch entry lists the cells that step_host_serial_ms.batch lists, in that order
    serial = next(m for m in BENCH["per_layer"] if m["name"] == "step_host_serial_ms.batch")["workloads"]
    assert serial[: len(BATCH)] == BATCH
    for name, (_layer, _unit, where, _held) in NEW.items():
        if where is BATCH:
            assert next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"] == serial
    # the outside readings of the same layer stay: a benchmark PR retires what these replace
    for stays in ("step_host_serial_ms", "step_longest_ms", "device_idle_share", "step_launch_call_ms"):
        assert {f"{stays}.batch", f"{stays}.paced"} <= set(names)


#: ``engine_stats()`` at the two ends of a window of 200 steps in which ONE lap stood still for 1.5 s
START = {"total_steps": 100,
         "step_phases": {"host_serial_s": 1.0, "longest_wall_s": 3.3},  # a lap of the lead-in: not the window's
         "step_parts": {"launch_call_s": 0.30},
         "step_offcpu": {"host_serial_s": 0.20, "unblocked_s": 0.05, "launch_call_s": 0.10, "device_wait_s": 2.0},
         "step_stalls": {"laps": 100, "stalled": 1, "wall_s": 3.3, "device_wait_s": 0.1, "host_s": 3.2},
         "device_reads": {"reads": 120, "ready": 30}}
END = {"total_steps": 300,
       "step_phases": {"host_serial_s": 3.0, "longest_wall_s": 3.3},
       "step_parts": {"launch_call_s": 0.90},
       "step_offcpu": {"host_serial_s": 0.80, "unblocked_s": 0.15, "launch_call_s": 0.50, "device_wait_s": 9.0},
       "step_stalls": {"laps": 300, "stalled": 2, "wall_s": 4.8, "device_wait_s": 1.45, "host_s": 3.35},
       "device_reads": {"reads": 520, "ready": 50}}


@pytest.mark.parametrize("name, want", [
    ("step_host_offcpu_ms.batch", 3.0),            # of step_host_serial_ms's 10 ms a step, 3 were waited
    ("step_host_offcpu_ms.paced", 3.0),
    ("step_gil_offcpu_ms.batch", 0.5),
    ("step_gil_offcpu_ms.paced", 0.5),
    ("step_launch_call_offcpu_ms.batch", 2.0),     # beside step_launch_call_ms's 3.0: two thirds of the call a wait
    # the window's stall alone: the lead-in's 3.3 s, which step_longest_ms still shows, is differenced away
    ("stalled_step_ms.batch", 1500.0),
    ("stalled_step_ms.paced", 1500.0),
    ("stalled_step_device_wait_share.batch", 90.0),
    ("device_ready_on_arrival_share.batch", 5.0),
])
def test_readers_on_a_worked_account(name, want):
    ob = lm.Observed(stats_start=START, stats_end=END)
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)


def test_a_window_without_a_stall_reads_zero_and_a_parent_prints_none_of_the_nine():
    read = lambda n, ob: lm.read(cells.layer_metric_spec(n), ob)  # noqa: E731
    ob = lm.Observed(stats_start=START, stats_end=END)
    assert read("step_longest_ms.batch", ob) == 3300.0 > read("stalled_step_ms.batch", ob)  # since the first step
    assert read("step_host_offcpu_ms.batch", ob) <= read("step_host_serial_ms.batch", ob) == pytest.approx(10.0)
    assert read("step_launch_call_offcpu_ms.batch", ob) <= read("step_launch_call_ms.batch", ob) == pytest.approx(3.0)
    # no stalled lap and no read in the window: 0, not a division and not a gap in the line
    quiet = lm.Observed(stats_start=END, stats_end={**END, "total_steps": 400})
    assert read("stalled_step_ms.batch", quiet) == 0.0 and read("stalled_step_device_wait_share.batch", quiet) == 0.0
    assert read("device_ready_on_arrival_share.batch", quiet) == 0.0 and read("step_gil_offcpu_ms.paced", quiet) == 0.0
    # a program without the three accounts (the parent commit): nothing, not an error
    bare = lm.Observed(stats_start={"total_steps": 1, "step_phases": {"host_serial_s": 0.1}},
                       stats_end={"total_steps": 2, "step_phases": {"host_serial_s": 0.2}})
    assert all(read(n, bare) is None for n in NEW)
    assert lm.read_all({n: cells.layer_metric_spec(n) for n in NEW}, bare) == {}


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


def test_one_rehearsal_prints_all_nine(fake_chip_cluster, tmp_path):
    """``swa-mixed-offline`` at its toy sizes: a closed-loop cell's traced line
    holds the six ``.batch`` entries; the three ``.paced`` ones are the same
    readers over the same two snapshots, under ``chat-paced``'s arrow."""
    from perfbench.harness import serve_cell

    cell_name = "swa-mixed-offline"
    cell = cells.cell(BENCH, cell_name)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 54, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["correct"] is True
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine = sorted(name for name, (_l, _u, where, _h) in NEW.items() if cell_name in where)
    assert len(mine) == 6 and set(mine) <= set(got), sorted(set(mine) - set(got))
    ob = out["observed"]
    paced = {n: lm.read(cells.layer_metric_spec(n), ob) for n in NEW if n.endswith(".paced")}
    assert len(paced) == 3 and all(paced[n] == got[n.replace(".paced", ".batch")] for n in paced)
    print({**{n: got[n] for n in mine}, **paced})
    assert all(got[name] >= 0.0 for name in mine)
    # off-CPU is a part of the same blocks' wall, differenced over the same two snapshots
    assert got["step_gil_offcpu_ms.batch"] <= got["step_host_offcpu_ms.batch"] <= got["step_host_serial_ms.batch"]
    assert got["step_launch_call_offcpu_ms.batch"] <= got["step_launch_call_ms.batch"] * 1.02
    assert got["stalled_step_ms.batch"] <= got["step_longest_ms.batch"] * 1.001  # no stalled lap is longer than the longest
    assert 0.0 <= got["stalled_step_device_wait_share.batch"] <= 100.0
    assert 0.0 <= got["device_ready_on_arrival_share.batch"] <= 100.0
    end = ob.stats_end
    assert end["device_reads"]["reads"] > ob.stats_start["device_reads"]["reads"] > 0
    assert end["step_stalls"]["laps"] > ob.stats_start["step_stalls"]["laps"] > 0
    for key, seconds in end["step_offcpu"].items():  # key by key under the wall clock's account
        wall = end["step_phases"].get(key, end["step_parts"].get(key))
        assert key == "unblocked_s" or 0.0 <= seconds <= wall, key
