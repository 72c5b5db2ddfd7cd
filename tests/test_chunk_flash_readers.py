"""What the benchmark's accepted readers read of a PLAIN configuration's
prefill chunk since ISSUE 51 sent it through the flash kernel:
``latent_flash_time_share.longdoc`` (the kernel's calls in the device trace
over busy time) and ``prefill_read_live_share.longdoc``
(``engine_stats()["prefill_width"]``: live key positions over those the
chunk's attention read). Their lists are the cells that had the kernel before
PR 51. A PR that claims a gain edits nothing of the benchmark, an accepted
entry's list of cells included, and the benchmark's own tests
(``tests/perfbench/test_perfbench_yardstick.py``: one entry a reader;
``test_perfbench_mellum.py``: every ``.batch`` entry is that cell's too) refuse
a second entry of the same reader, so PR 51 adds NO entry: a ``benchmark`` PR
appends ``chat-offline``, ``longprompt-batch`` and ``moe-chat-offline`` to the
two lists (PERF.md section 7). This file, outside the benchmark's paths, holds
the readers to those cells' programs meanwhile. No number printed here is a
speed."""

import os
import sys
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))

import rehearsal  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells  # noqa: E402
from perfbench.harness import layer_metrics as lm  # noqa: E402

BENCH = cells.benchmark()
FLASH, LIVE = "latent_flash_time_share.longdoc", "prefill_read_live_share.longdoc"
CELLS = ["chat-offline", "longprompt-batch", "moe-chat-offline"]


@pytest.mark.parametrize("live, read, want", [(3 * 1500, 3 * 4096, 100 * 1500 / 4096), (1500, 2048, 100 * 1500 / 2048),
                                              (700, 1024, 100 * 700 / 1024)])
def test_the_live_share_on_a_worked_account(live, read, want):
    """Three chunks of a 1500-token context over the 4096-wide table (the
    parent of PR 51), the same in two key tiles of 1024, a chat prompt in one."""
    start = {"prefill_width": {"launches": 10, "width_tokens": 40960, "live_tokens": 9000, "read_tokens": 40960}}
    end = {"prefill_width": {"launches": 13, "width_tokens": 40960 + 3 * 4096, "live_tokens": 9000 + live,
                             "read_tokens": 40960 + read}}
    ob = lm.Observed(stats_start=start, stats_end=end)
    assert lm.read(cells.layer_metric_spec(LIVE), ob) == pytest.approx(want)


def test_a_program_without_the_kernel_or_the_account_leaves_the_reading_out():
    """A program without the ``prefill_width`` account, or a trace without a
    device plane, leaves the reading out of the line and does not raise."""
    spec = cells.layer_metric_spec(FLASH)
    assert lm.read(spec, lm.Observed()) is None
    assert lm.read(cells.layer_metric_spec(LIVE), lm.Observed()) is None
    no_account = {"decode_width": {"launches": 3}}
    assert lm.read(cells.layer_metric_spec(LIVE), lm.Observed(stats_start=no_account, stats_end=no_account)) is None
    assert FLASH not in lm.read_all({FLASH: spec}, lm.Observed())


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


@pytest.mark.parametrize("cell_name", CELLS)
def test_rehearsal_of_the_cell_reads_the_table_off_the_chip(fake_chip_cluster, cell_name, tmp_path):
    """Each of the three cells at its toy sizes: off the chip the chunk keeps
    the materialised softmax, so the account reads the table's width a launch
    (the share is live over table: above 0, far under 100) and no operation of
    the trace is the kernel's: its share reads 0, or nothing."""
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, cell_name)
    specs = {**bench_run.layer_specs_of(BENCH, cell_name), FLASH: cells.layer_metric_spec(FLASH),
             LIVE: cells.layer_metric_spec(LIVE)}  # as the cell would list them
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 51, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=specs, work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["correct"] is True
    start, end = (getattr(out["observed"], k)["prefill_width"] for k in ("stats_start", "stats_end"))
    assert end["launches"] > start["launches"] and end["read_tokens"] == end["width_tokens"]  # the table, every launch
    got = lm.read(specs[LIVE], out["observed"])
    assert 0.0 < got < 100.0
    assert got == pytest.approx(100.0 * (end["live_tokens"] - start["live_tokens"]) / (end["read_tokens"] - start["read_tokens"]))
    assert (out.get("traced") or {}).get("metrics", {}).get(FLASH, 0.0) == 0.0
