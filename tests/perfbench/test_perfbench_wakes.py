"""The per-layer metrics that read the engine's ``wakes`` counter (ISSUE
28): how many of the step thread's puts went out after a launch, and how
long an item was held. Four data files beside the others and four entries
of BENCHMARK.json, read by the ``stats_delta`` / ``ratio`` reader that was
there. No number printed here is a speed."""

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
#: the MoE cell reads the ``.batch`` entries, as every cell judged by
#: ``serve_tokens_per_s`` does since PR 37: a cell joins an entry, it brings no twin
PACED, BATCH = ["chat-paced"], ["chat-offline", "longprompt-batch", "moe-chat-offline"]
#: metric -> (unit, better, moves, cells, counter, scale)
NEW = {
    f"{name}.{suffix}": (unit, better, moves, where, key, scale)
    for name, unit, better, key, scale, moves_paced in (
        ("wakes_after_launch_share", "%", "higher", "after_launch", 100.0, "tpot_p90_ms"),
        ("wake_hold_ms", "ms", "lower", "held_s", 1000.0, "ttft_p90_ms"),
    )
    for suffix, moves, where in (
        ("paced", moves_paced, PACED), ("batch", "serve_tokens_per_s", BATCH),
    )
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    unit, better, moves, where, key, scale = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": "program_counter",
        "layer": "engine scheduler", "moves": moves,
    }
    # the cells ISSUE 28 named, from the list's start; a later cell joins after them
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("engine scheduler", unit, moves)
    assert (spec["kind"], spec["reduce"]) == ("stats_delta", "ratio")
    assert spec["key"] == ["wakes", key] and spec["per"] == ["wakes", "items"]
    assert spec["scale"] == scale


def test_the_four_are_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)
    assert names.index("moe_rows_per_expert_prefill.moe") < min(names.index(n) for n in NEW)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_ratio_reader_on_two_snapshots():
    """``engine_stats()`` at the two ends of a window of 100 full decode
    steps: 3200 items, 3100 woken after a launch, 12.8 item-seconds held."""
    start = {"wakes": {"items": 5000, "after_launch": 4000, "at_idle": 900, "direct": 100,
                       "held_s": 20.0}}
    end = {"wakes": {"items": 8200, "after_launch": 7100, "at_idle": 1000, "direct": 100,
                     "held_s": 32.8}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    read = lambda name: lm.read(cells.layer_metric_spec(name), ob)  # noqa: E731
    for suffix in ("paced", "batch"):
        assert read(f"wakes_after_launch_share.{suffix}") == pytest.approx(100.0 * 3100 / 3200)
        assert read(f"wake_hold_ms.{suffix}") == pytest.approx(4.0)
    # a program without the counter (the parent commit): nothing, not an error
    bare = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert all(lm.read(cells.layer_metric_spec(n), bare) is None for n in NEW)
    assert lm.read_all({n: cells.layer_metric_spec(n) for n in NEW}, bare) == {}
    # a window in which nothing was put: 0, not a division
    still = lm.Observed(stats_start=end, stats_end=end)
    assert all(lm.read(cells.layer_metric_spec(n), still) == 0.0 for n in NEW)


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


@pytest.mark.parametrize("cell_name", ["chat-paced", "chat-offline", "moe-chat-offline"])
def test_rehearsal_prints_the_wake_metrics_of_the_cell(fake_chip_cluster, cell_name, tmp_path):
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, cell_name)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 28, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False,
    )
    assert out["failed"] == 0 and out["correct"] is True
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    suffix = "paced" if cell_name in PACED else "batch"
    assert 0.0 < got[f"wakes_after_launch_share.{suffix}"] <= 100.0
    assert got[f"wake_hold_ms.{suffix}"] > 0.0
    ob = out["observed"]
    counted = {k: ob.stats_end["wakes"][k] - ob.stats_start["wakes"][k] for k in ob.stats_end["wakes"]}
    # the replica's loop is the only one that steps its engine: nothing direct
    assert counted["items"] == counted["after_launch"] + counted["at_idle"] > 0
    assert counted["direct"] == 0
    # every token of the window and every terminal item is one put
    assert counted["items"] >= ob.scalars["output_tokens"]
