"""The per-layer metrics that read the runner's table-width counter
(ISSUE 25): four data files beside the others and four entries of
BENCHMARK.json, read by the ``stats_delta`` / ``ratio`` reader that was
there. No number printed here is a speed."""

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
#: metric -> (unit, better, moves, cells, counter over counter, scale)
NEW = {
    f"{name}.{suffix}": (unit, better, moves, where, key, per, scale)
    for name, unit, better, key, per, scale in (
        ("decode_table_width_tokens", "tokens", "lower", "width_tokens", "launches", None),
        ("decode_gather_live_share", "%", "higher", "live_tokens", "gathered_tokens", 100.0),
    )
    for suffix, moves, where in (
        ("paced", "tpot_p90_ms", PACED), ("batch", "serve_tokens_per_s", BATCH),
    )
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_metric_file_agrees_with_its_entry(name):
    unit, better, moves, where, key, per, scale = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better, "source": "program_counter",
        "layer": "model runner", "moves": moves,
    }
    # the cells ISSUE 25 named, from the list's start; a later cell joins after them
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("model runner", unit, moves)
    assert (spec["kind"], spec["reduce"]) == ("stats_delta", "ratio")
    assert spec["key"] == ["decode_width", key] and spec["per"] == ["decode_width", per]
    assert spec.get("scale") == scale


def test_the_four_are_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)
    assert names.index("warmup_s") < min(names.index(name) for name in NEW)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_ratio_reader_on_two_snapshots():
    """``engine_stats()`` at the two ends of a window in which 10 launches
    of a 32-slot bucket ran: 8 at the 2048 rung and 2 at 4096."""
    start = {"decode_width": {"launches": 100, "width_tokens": 204800, "needed_tokens": 150000,
                              "live_tokens": 2000000, "gathered_tokens": 6553600}}
    end = {"decode_width": {"launches": 110, "width_tokens": 204800 + 8 * 2048 + 2 * 4096,
                            "needed_tokens": 150000 + 10 * 1100,
                            "live_tokens": 2000000 + 10 * 24000,
                            "gathered_tokens": 6553600 + 32 * (8 * 2048 + 2 * 4096)}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    read = lambda name: lm.read(cells.layer_metric_spec(name), ob)  # noqa: E731
    for suffix in ("paced", "batch"):
        assert read(f"decode_table_width_tokens.{suffix}") == pytest.approx(2457.6)
        assert read(f"decode_gather_live_share.{suffix}") == pytest.approx(
            100.0 * 240000 / (32 * 24576)
        )
    # a program without the counter (the parent commit): nothing, not an error
    bare = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert all(lm.read(cells.layer_metric_spec(n), bare) is None for n in NEW)
    # a window with no decode launch: 0, not a division
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


@pytest.mark.parametrize("cell_name", ["chat-paced", "chat-offline"])
def test_rehearsal_prints_the_width_metrics_of_the_cell(fake_chip_cluster, cell_name, tmp_path):
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, cell_name)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 11, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False,
    )
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    suffix = "paced" if cell_name in PACED else "batch"
    # the toy model has one rung, its whole ``max_seq_len`` of 128
    assert got[f"decode_table_width_tokens.{suffix}"] == 128.0
    share = got[f"decode_gather_live_share.{suffix}"]
    assert math.isfinite(share) and 0.0 < share <= 100.0
    ob = out["observed"]
    counted = {k: ob.stats_end["decode_width"][k] - ob.stats_start["decode_width"][k]
               for k in ob.stats_end["decode_width"]}
    assert counted["launches"] > 0
    assert counted["needed_tokens"] <= counted["width_tokens"]
    assert counted["live_tokens"] <= counted["gathered_tokens"] == 4 * counted["width_tokens"]
