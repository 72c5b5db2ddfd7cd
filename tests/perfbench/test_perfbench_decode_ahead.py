"""The two per-layer metrics that say whether the engine launched a decode
step before it had read the last (ISSUE 39): ``engine_stats()["decode_ahead"]``,
``ahead`` over ``launches``. Two data files beside the others and two entries
of BENCHMARK.json, read by the ``stats_delta`` reader that was there. No
number printed here is a speed."""

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
BATCH = ["chat-offline", "longprompt-batch", "moe-chat-offline", "mla-longdoc-batch", "kda-reason-offline"]
#: metric -> (the end-to-end metric it moves, its cells)
NEW = {
    "decode_ahead_share.batch": ("serve_tokens_per_s", BATCH),
    "decode_ahead_share.paced": ("tpot_p90_ms", ["chat-paced"]),
}
PAIRS = [(name, cell) for name, (_moves, where) in NEW.items() for cell in where]


@pytest.mark.parametrize("name, cell", PAIRS)
def test_entry_file_and_cell_agree(name, cell):
    moves, where = NEW[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "engine scheduler", "moves": moves,
    }
    # the cells ISSUE 39 named, from the list's start; a later cell joins after them
    assert entry["workloads"][: len(where)] == where
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("engine scheduler", "%", moves)
    assert (spec["kind"], spec["reduce"], spec["scale"]) == ("stats_delta", "ratio", 100.0)
    assert spec["key"] == ["decode_ahead", "ahead"] and spec["per"] == ["decode_ahead", "launches"]
    # the cell reports the end-to-end metric the entry moves, and reads the entry
    assert name in bench_run.layer_specs_of(BENCH, cell)
    (metric,) = [m for m in BENCH["end_to_end"] if m["name"] == moves]
    assert cell in metric["workloads"]


def test_the_two_are_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.count(name) == 1 for name in NEW)
    # after PR 38's last, in the issue's order; a later PR appends after them
    at = [names.index(n) for n in ("step_longest_ms.paced", *NEW)]
    assert at == [at[0], at[0] + 1, at[0] + 2]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("ahead, launches, want", [(190, 200, 95.0), (0, 200, 0.0), (7, 200, 3.5)])
def test_reader_on_a_worked_account(name, ahead, launches, want):
    start = {"decode_ahead": {"launches": 1000, "ahead": 400, "dropped": 3}}
    end = {"decode_ahead": {"launches": 1000 + launches, "ahead": 400 + ahead, "dropped": 5}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_without_the_counter_prints_nothing(name):
    """The parent commit, under this PR's benchmark files: no reading, no error."""
    bare = lm.Observed(stats_start={"total_steps": 1, "wakes": {"items": 1}},
                       stats_end={"total_steps": 2, "wakes": {"items": 2}})
    assert lm.read(cells.layer_metric_spec(name), bare) is None
    assert lm.read_all({name: cells.layer_metric_spec(name)}, bare) == {}


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


def test_rehearsal_of_a_saturated_cell_reads_launches_ahead(fake_chip_cluster, tmp_path):
    """``chat-offline`` at its toy sizes: 4 clients on 4 decode slots, closed
    loop. The replica's loop looks ahead while the slots are spoken for, and
    the cell stays correct: the check drives the same decode program."""
    from perfbench.harness import serve_cell

    cell_name = "chat-offline"
    cell = cells.cell(BENCH, cell_name)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 39, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, cell_name), work_dir=str(tmp_path),
        require_tpu=False,
    )
    assert out["failed"] == 0 and out["correct"] is True
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < got["decode_ahead_share.batch"] <= 100.0
    counts = out["observed"].stats_end["decode_ahead"]
    assert set(counts) == {"launches", "ahead", "dropped"}
    # length finishes only; a client the window's end stops may cancel with a token in flight
    assert 0 < counts["ahead"] <= counts["launches"] and 0 <= counts["dropped"] <= 4
    assert "decode_ahead_share.paced" not in got
