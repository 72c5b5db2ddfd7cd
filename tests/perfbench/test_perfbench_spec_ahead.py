"""The per-layer metric that says how often a drafter's step was launched
while the step before it was unread, its result handed over on the device
(ISSUE 45): ``engine_stats()["speculative"]``'s ``launches_ahead`` over
``step_launches``. One data file beside the others and one entry of
BENCHMARK.json, read by the ``stats_delta`` reader that was there. It is the
mechanism of ``decode_ahead_share.*`` under a counter of its own:
``engine_stats()["decode_ahead"]`` counts plain decode launches alone and keeps
reading 0 in the cell whose decode is the drafter's step. No number printed
here is a speed."""

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
NAME = "spec_ahead_share.mtp"
CELL = "mtp-reason-offline"
KEY, PER = ["speculative", "launches_ahead"], ["speculative", "step_launches"]


def test_the_entry_and_its_file_agree_and_it_is_appended_after_what_was_there():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "engine scheduler", "moves": "serve_tokens_per_s",
    }
    assert entry["workloads"][:1] == [CELL]  # a later cell with a drafter of its own joins after it
    spec = cells.layer_metric_spec(NAME)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("engine scheduler", "%", "serve_tokens_per_s")
    assert (spec["kind"], spec["reduce"], spec["scale"]) == ("stats_delta", "ratio", 100.0)
    assert (spec["key"], spec["per"]) == (KEY, PER)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.count(NAME) == 1
    assert names.index(NAME) > names.index("kv_held_over_one_table_share.swa")  # after PR 44's last
    # the layer is one BENCHMARK.json already names, letter for letter; the denominator is the one
    # spec_fused_launch_share.mtp reads
    assert "engine scheduler" in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}
    assert cells.layer_metric_spec("spec_fused_launch_share.mtp")["per"] == PER
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_the_cell_reads_the_entry_and_reports_its_arrow():
    assert NAME in bench_run.layer_specs_of(BENCH, CELL)
    (metric,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in metric["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"] if w["name"] != CELL])
def test_a_cell_whose_model_keeps_no_drafter_does_not_read_it(cell):
    assert NAME not in bench_run.layer_specs_of(BENCH, cell)


def test_the_toy_replica_has_the_counter_from_construction():
    """The (entry, cell) pair of the yardstick: a fresh toy replica of the cell's family, no request
    yet, has both keys at 0, and ``decode_ahead`` beside them as every engine has it."""
    stats = rehearsal.toy_engine_stats(cells.cell(BENCH, CELL)["config"])
    spec = stats["speculative"]
    assert spec["launches_ahead"] == 0 and spec["step_launches"] == 0 and spec["rows_dropped"] == 0
    assert lm._dig(stats, KEY) == 0.0 and lm._dig(stats, PER) == 0.0
    assert stats["decode_ahead"] == {"launches": 0, "ahead": 0, "dropped": 0}


@pytest.mark.parametrize("ahead, launches, want", [(190, 200, 95.0), (0, 200, 0.0), (7, 200, 3.5)])
def test_reader_on_a_worked_account(ahead, launches, want):
    start = {"speculative": {"step_launches": 1000, "launches_ahead": 400, "rows_dropped": 3}}
    end = {"speculative": {"step_launches": 1000 + launches, "launches_ahead": 400 + ahead, "rows_dropped": 5}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    assert lm.read(cells.layer_metric_spec(NAME), ob) == pytest.approx(want)


def test_a_program_without_the_counter_reports_nothing():
    """The driver lays this file over the PARENT's checkout too: its ``engine_stats()`` has
    ``speculative.step_launches`` and no ``launches_ahead``, and the reader returns nothing."""
    parent = {"speculative": {"step_launches": 30, "launches_fused": 30, "slot_steps": 90}}
    spec = cells.layer_metric_spec(NAME)
    assert lm.read(spec, lm.Observed(stats_start=parent, stats_end=parent)) is None
    assert lm.read(spec, lm.Observed()) is None
    assert NAME not in lm.read_all({NAME: spec}, lm.Observed(stats_start=parent, stats_end=parent))


def test_no_drafter_step_in_the_window_reads_zero():
    still = {"speculative": {"step_launches": 12, "launches_ahead": 9}}
    assert lm.read(cells.layer_metric_spec(NAME), lm.Observed(stats_start=still, stats_end=still)) == 0.0


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


def test_rehearsal_of_the_cell_reads_drafter_steps_launched_ahead(fake_chip_cluster, tmp_path):
    """``mtp-reason-offline`` at its toy sizes: 4 clients on 4 decode slots, closed loop, every
    request greedy. The replica's loop looks ahead while the slots are spoken for, the cell stays
    correct, and the plain decode launches' counter stays where it was: at nothing."""
    from perfbench.harness import serve_cell

    cell = cells.cell(BENCH, CELL)
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 45, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["correct"] is True
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < got[NAME] <= 100.0
    assert got["decode_ahead_share.batch"] == 0.0 and got["spec_fused_launch_share.mtp"] == 100.0
    assert 1.0 <= got["spec_committed_per_slot_step.mtp"] <= 1.0 + got["spec_accept_share.mtp"] / 100.0 + 1e-9
    assert got["recompiles_in_window.moe"] == 0.0
    spec = out["observed"].stats_end["speculative"]
    assert 0 < spec["launches_ahead"] <= spec["step_launches"] == spec["launches_fused"]
    # the books hold the rows that were read AND committed; length finishes are planned a step
    # ahead, so a row is dropped only where a draft was accepted as a request's last token or a
    # client the window's end stops cancels with a window in flight
    assert spec["committed_tokens"] <= spec["slot_steps"] + spec["accepted_tokens"]
    assert 0 <= spec["rows_dropped"] <= 4 + spec["accepted_tokens"]
    assert out["observed"].stats_end["decode_ahead"] == {"launches": 0, "ahead": 0, "dropped": 0}
