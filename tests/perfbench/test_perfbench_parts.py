"""The per-layer metrics that read the PARTS of the engine's step phases
(ISSUE 38): ``engine_stats()["step_parts"]`` a step, and the longest step
beside them (``["step_phases"]["longest_wall_s"]``). Fifteen data files beside
the others and fifteen entries of BENCHMARK.json, read by the ``stats_delta``
reader that was there. No number printed here is a speed."""

import gzip
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
BATCH = ["chat-offline", "longprompt-batch", "moe-chat-offline", "mla-longdoc-batch", "kda-reason-offline"]
MOE = ["moe-chat-offline", "mla-longdoc-batch", "kda-reason-offline"]
SCHEDULER, RUNNER = "engine scheduler", "model runner"
ARROW = {"batch": "serve_tokens_per_s", "moe": "serve_tokens_per_s", "paced": "tpot_p90_ms"}
#: metric -> (layer, cells, path read in engine_stats())
NEW = {
    **{
        f"step_{phase}_{part}_ms.{suffix}": (layer, where, ["step_parts", f"{phase}_{part}_s"])
        for phase, part, layer, suffix, where in (
            ("schedule", "drain", SCHEDULER, "batch", BATCH), ("schedule", "admit", SCHEDULER, "batch", BATCH),
            ("schedule", "plan", SCHEDULER, "batch", BATCH),
            ("launch", "rows", RUNNER, "batch", BATCH), ("launch", "inputs", RUNNER, "batch", BATCH),
            ("launch", "call", RUNNER, "batch", BATCH),
            ("launch", "rows", RUNNER, "paced", PACED), ("launch", "inputs", RUNNER, "paced", PACED),
            ("launch", "call", RUNNER, "paced", PACED),
            ("readback", "logits", RUNNER, "batch", BATCH), ("readback", "loads", RUNNER, "moe", MOE),
            ("emit", "commit", SCHEDULER, "batch", BATCH), ("emit", "deliver", SCHEDULER, "batch", BATCH),
        )
    },
    "step_longest_ms.batch": (SCHEDULER, BATCH, ["step_phases", "longest_wall_s"]),
    "step_longest_ms.paced": (SCHEDULER, PACED, ["step_phases", "longest_wall_s"]),
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    layer, where, key = NEW[name]
    moves = ARROW[name.rsplit(".", 1)[1]]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": layer, "moves": moves,
    }
    # the cells ISSUE 38 named, from the list's start; a later cell joins after them
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == (layer, "ms", moves)
    assert spec["kind"] == "stats_delta" and spec["key"] == key and spec["scale"] == 1000.0
    if name.startswith("step_longest_ms"):  # one step's reading, not a sum: never differenced
        assert spec["reduce"] == "last" and "per" not in spec
    else:
        assert spec["reduce"] == "ratio" and spec["per"] == ["total_steps"]


def test_the_fifteen_are_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(NEW) == 15 and all(names.count(name) == 1 for name in NEW)
    assert names.index("latent_rows_time_share") < min(names.index(n) for n in NEW)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    # the entries that time the same phases WHOLE stay: a part is read beside its phase
    for phase in ("schedule", "launch", "readback", "emit", "host_serial"):
        assert {f"step_{phase}_ms.batch", f"step_{phase}_ms.paced"} <= set(names)


#: ``engine_stats()`` at the two ends of a window of 200 steps on an expert model
START = {"total_steps": 100,
         "step_phases": {"launch_s": 1.0, "longest_wall_s": 0.080, "longest_device_wait_s": 0.070},
         "step_parts": {"schedule_plan_s": 0.50, "launch_rows_s": 0.30, "launch_inputs_s": 0.20,
                        "launch_call_s": 0.40, "readback_loads_s": 0.10, "emit_deliver_s": 0.25}}
END = {"total_steps": 300,
       "step_phases": {"launch_s": 2.0, "longest_wall_s": 2.115, "longest_device_wait_s": 2.050},
       "step_parts": {"schedule_plan_s": 1.30, "launch_rows_s": 0.70, "launch_inputs_s": 0.30,
                      "launch_call_s": 0.80, "readback_loads_s": 0.44, "emit_deliver_s": 0.39}}


@pytest.mark.parametrize("name, want", [
    ("step_schedule_plan_ms.batch", 4.0),
    ("step_launch_rows_ms.batch", 2.0),
    ("step_launch_call_ms.paced", 2.0),
    ("step_readback_loads_ms.moe", 1.7),
    ("step_emit_deliver_ms.batch", 0.7),
    # the longest step SINCE THE REPLICA'S FIRST, as the end of the window has it:
    # a stall of 2.1 s shows whole, where the window's means would spread it over 200 steps
    ("step_longest_ms.batch", 2115.0),
    ("step_longest_ms.paced", 2115.0),
])
def test_readers_on_a_worked_account(name, want):
    ob = lm.Observed(stats_start=START, stats_end=END)
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)


def test_a_phases_self_time_is_read_by_subtraction_and_a_parent_prints_none_of_the_fifteen():
    ob = lm.Observed(stats_start=START, stats_end=END)
    read = lambda n: lm.read(cells.layer_metric_spec(n), ob)  # noqa: E731
    parts = sum(read(f"step_launch_{p}_ms.batch") for p in ("rows", "inputs", "call"))
    assert parts == pytest.approx(4.5) and read("step_launch_ms.batch") - parts == pytest.approx(0.5)
    # a program without parts (the parent commit): nothing, not an error
    bare = lm.Observed(stats_start={"total_steps": 1, "step_phases": {"launch_s": 0.1}},
                       stats_end={"total_steps": 2, "step_phases": {"launch_s": 0.2}})
    assert all(lm.read(cells.layer_metric_spec(n), bare) is None for n in NEW)
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


def test_rehearsal_prints_the_parts_beside_their_phases_and_the_trace_nests_them(fake_chip_cluster, tmp_path):
    """``kda-reason-offline`` at its toy sizes: the one cell whose slots the
    scheduler's search is quadratic in, and an expert model (it reads loads back)."""
    from perfbench.harness import serve_cell

    cell_name = "kda-reason-offline"
    cell = cells.cell(BENCH, cell_name)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 38, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False, dump_trace=True,
    )
    assert out["failed"] == 0 and out["correct"] is True
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine = sorted(name for name, (_layer, where, _key) in NEW.items() if cell_name in where)
    assert len(mine) == 11 and set(mine) <= set(got), sorted(set(mine) - set(got))
    assert all(got[name] >= 0.0 for name in mine)
    part = lambda phase, p, suffix="batch": got[f"step_{phase}_{p}_ms.{suffix}"]  # noqa: E731
    of = {
        "schedule": part("schedule", "drain") + part("schedule", "admit") + part("schedule", "plan"),
        "launch": part("launch", "rows") + part("launch", "inputs") + part("launch", "call"),
        "readback": part("readback", "logits") + part("readback", "loads", "moe"),
        "emit": part("emit", "commit") + part("emit", "deliver"),
    }
    for phase, parts in of.items():  # both differenced over the same two snapshots
        assert 0.0 < parts <= got[f"step_{phase}_ms.batch"] * 1.02, phase  # a settle may fall between the two reads of one snapshot
    assert part("readback", "loads", "moe") > 0.0
    # one step since the replica's first, compiles of the toy (no warm-up) included
    phases = out["observed"].stats_end["step_phases"]
    assert got["step_longest_ms.batch"] == 1000.0 * phases["longest_wall_s"] > 0.0
    assert phases["longest_device_wait_s"] < phases["longest_wall_s"]
    # the profiler's trace holds the parts as spans of their own, under the phase's
    with gzip.open(os.path.join(str(tmp_path), "trace_dump", "trace_gaps.json.gz"), "rt") as f:
        view = json.load(f)
    names = {e[0] for p in view["planes"] for ln in p["lines"] for e in ln["events"]}
    assert {"engine.schedule.plan", "engine.launch.rows", "engine.launch.inputs", "engine.launch.call",
            "engine.readback.loads", "engine.emit.commit", "engine.emit.deliver"} <= names
    assert {"engine.schedule", "engine.launch", "engine.readback", "engine.emit"} <= names
