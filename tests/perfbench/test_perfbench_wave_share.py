"""``decode_wave_live_share.batch`` (ISSUE 57): one data file beside the other
per-layer metrics and one entry of BENCHMARK.json, read by the ``stats_delta``
/ ``ratio`` reader that was there from the runner's ``decode_width`` account
(``live_tokens`` over ``multiplied_tokens``). No number printed here is a
speed."""

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
NAME = "decode_wave_live_share.batch"


#: the seven cells of ``paged_attn_time_share.batch`` as ISSUE 57 found them; a later cell joins after them
KERNEL_CELLS = ["chat-offline", "longprompt-batch", "moe-chat-offline", "swa-mixed-offline", "conv-reason-offline",
                "ssm-reason-offline", "gated-swa-reason-offline"]


def test_the_file_agrees_with_its_entry_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.count(NAME) == 1 and names.index("decode_window_read_share.swa") < names.index(NAME)
    entry = BENCH["per_layer"][names.index(NAME)]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "model runner", "moves": "serve_tokens_per_s",
    }
    assert entry["workloads"][:7] == KERNEL_CELLS
    kernel_cells = [m for m in BENCH["per_layer"] if m["name"] == "paged_attn_time_share.batch"][0]["workloads"]
    assert kernel_cells[:7] == KERNEL_CELLS
    spec = cells.layer_metric_spec(NAME)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("model runner", "%", "serve_tokens_per_s")
    assert (spec["kind"], spec["reduce"], spec["scale"]) == ("stats_delta", "ratio", 100.0)
    assert spec["key"] == ["decode_width", "live_tokens"] and spec["per"] == ["decode_width", "multiplied_tokens"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def _snapshot(launches, live, gathered, multiplied=None):
    width = {"launches": launches, "width_tokens": 8192 * launches, "needed_tokens": 0,
             "live_tokens": live, "gathered_tokens": gathered}
    if multiplied is not None:
        width.update(multiplied_tokens=multiplied, waves=multiplied // 256, single_wait_waves=0)
    return {"decode_width": width}


def test_the_reader_on_worked_snapshots():
    """A window of 100 launches of 32 slots far past a window of 512 in blocks
    of 16: 33 live blocks a slot in waves of 16 are 48 blocks multiplied, in
    waves of 17 they are 34."""
    spec = cells.layer_metric_spec(NAME)
    live, read = 100 * 32 * 512, 100 * 32 * 33 * 16
    for wave, share in ((16, 100.0 * 512 / (48 * 16)), (17, 100.0 * 512 / (34 * 16))):
        multiplied = 100 * 32 * (-(-33 // wave) * wave) * 16
        ob = lm.Observed(stats_start=_snapshot(10, 7, 9, 11), stats_end=_snapshot(110, 7 + live, 9 + read, 11 + multiplied))
        assert lm.read(spec, ob) == pytest.approx(share)
    # an engine_stats() without the counter (a parent checkout, a latent cache): nothing is read, nothing raises
    older = _snapshot(1, 5, 7)
    assert lm.read(spec, lm.Observed(stats_start=older, stats_end=older)) is None
    assert lm.read(spec, lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})) is None
    # a window with no decode launch: 0, not a division
    still = _snapshot(3, 5, 7, 9)
    assert lm.read(spec, lm.Observed(stats_start=still, stats_end=still)) == 0.0


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


def test_rehearsal_of_a_kernel_cell_prints_the_share(fake_chip_cluster, tmp_path):
    """``chat-offline`` at toy sizes on the CPU: decode takes the gather, which
    multiplies what it reads, so the share is ``decode_gather_live_share``'s
    there; the chip's kernel counts whole waves (``tests/test_decode_width.py``)."""
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)
    cell = cells.cell(BENCH, "chat-offline")
    out = serve_cell.run(
        config=rehearsal.tiny_config(cell["config"]), traffic=rehearsal.tiny_traffic(cell["traffic"]),
        seed=2**31 + 57, seconds=2.5, trace=True, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, "chat-offline"), work_dir=str(tmp_path),
        require_tpu=False,
    )
    line = json.loads(json.dumps(bench_run.result_line(BENCH, cell, out, True)))
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < got[NAME] <= 100.0
    assert got[NAME] == pytest.approx(got["decode_gather_live_share.batch"])
    width = out["observed"].stats_end["decode_width"]
    assert width["multiplied_tokens"] == width["gathered_tokens"] > 0 and width["waves"] == 0
    # a cell the entry does not list (a latent cache) is not asked for it
    assert NAME not in bench_run.layer_specs_of(BENCH, "mla-longdoc-batch")
    assert NAME not in bench_run.layer_specs_of(BENCH, "chat-paced")
