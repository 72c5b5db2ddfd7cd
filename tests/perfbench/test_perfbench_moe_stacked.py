"""The per-layer metric that says whether a cell's expert layers read their
matrices in place in a scanned group's stack (ISSUE 43):
``engine_stats()["moe"][kind]``'s ``stacked_layers`` over ``expert_layers``.
One data file beside the others and one entry of BENCHMARK.json, read by the
``stats_delta`` reader that was there; the runner's ``moe`` account holds
both keys from construction for EVERY model with experts (no family's file
says anything of them), and the model says what its program was traced with
(``Model.experts_in_place``). No number printed here is a speed."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells  # noqa: E402
from perfbench.harness import layer_metrics as lm  # noqa: E402
from perfbench.harness.program import engine_config  # noqa: E402

BENCH = cells.benchmark()
NAME = "moe_stacked_layers_share.moe"
#: cell -> what its program reads: the latent models scan their expert layers
#: (the stack in place), OLMoE unrolls them and Kimi-Linear loops over them
CELLS = {"moe-chat-offline": 0.0, "mla-longdoc-batch": 100.0, "kda-reason-offline": 0.0, "mtp-reason-offline": 100.0}
KEY, PER = ["moe", "decode", "stacked_layers"], ["moe", "decode", "expert_layers"]


def test_the_entry_and_its_file_agree_and_it_is_appended_last_of_what_pr_43_found():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "serve_tokens_per_s",
    }
    assert entry["workloads"][: len(CELLS)] == list(CELLS)  # a later cell joins after them
    spec = cells.layer_metric_spec(NAME)
    assert (spec["layer"], spec["unit"], spec["moves"]) == ("kernels", "%", "serve_tokens_per_s")
    assert (spec["kind"], spec["reduce"], spec["scale"]) == ("stats_delta", "ratio", 100.0)
    assert (spec["key"], spec["per"]) == (KEY, PER)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) > names.index("moe_group_changed_share.dsv3")  # after PR 41's last
    # the layer is one BENCHMARK.json already names, letter for letter
    assert "kernels" in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}


@pytest.mark.parametrize("cell", CELLS)
def test_the_cell_reads_the_entry_and_reports_its_arrow(cell):
    assert NAME in bench_run.layer_specs_of(BENCH, cell)
    (metric,) = [m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert cell in metric["workloads"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"] if w["name"] not in CELLS])
def test_a_cell_without_experts_does_not_read_it(cell):
    assert NAME not in bench_run.layer_specs_of(BENCH, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_expert_family_has_the_counter_from_construction(cell):
    """The (entry, cell) pair of the yardstick, said here for the four: a
    fresh toy replica of the cell's family, no request yet, has both keys at
    0 in both halves of the account."""
    stats = rehearsal.toy_engine_stats(cells.cell(BENCH, cell)["config"])
    for kind in ("decode", "prefill"):
        assert stats["moe"][kind]["stacked_layers"] == 0 and stats["moe"][kind]["expert_layers"] == 0
    assert lm._dig(stats, KEY) == 0.0 and lm._dig(stats, PER) == 0.0


def test_a_program_without_the_counter_reports_nothing():
    """The driver lays this file over the PARENT's checkout too: its
    ``engine_stats()`` has no such key, and the reader returns nothing."""
    parent = {"moe": {"decode": {"launches": 3, "assignments": 24}}}
    spec = cells.layer_metric_spec(NAME)
    assert lm.read(spec, lm.Observed(stats_start=parent, stats_end=parent)) is None
    assert lm.read(spec, lm.Observed()) is None
    assert NAME not in lm.read_all({NAME: spec}, lm.Observed(stats_start=parent, stats_end=parent))


def test_no_decode_launch_in_the_window_reads_zero():
    still = {"moe": {"decode": {"stacked_layers": 12, "expert_layers": 12}}}
    assert lm.read(cells.layer_metric_spec(NAME), lm.Observed(stats_start=still, stats_end=still)) == 0.0


@pytest.mark.parametrize("cell, want", CELLS.items())
def test_a_toy_replica_of_the_family_reads_what_its_program_does(cell, want):
    """A toy replica of the cell's family serves two requests (prefill,
    then decode steps; DeepSeek-V3's through the one-program MTP step): the
    entry read over them is 100 where the expert layers are scanned and 0
    where each is an operand of its own, in both halves of the account."""
    model = rehearsal.tiny(cells.config_of(BENCH, cells.cell(BENCH, cell)["config"]))
    fam = families.of(model)
    cfg = fam.model_config(model, max_seq_len=int(model["max_position_embeddings"]),
                           **model["serving"].get("model_overrides", {}))
    server = fam.server_class()(cfg, engine_config(model["serving"]["engine"]), seed=7, export_metrics=False)
    try:
        start = server.engine_stats()
        for prompt in ([5, 9, 2, 77, 31, 8, 120], list(range(3, 25))):
            assert len(list(server.engine.generate(prompt, max_new_tokens=5))) == 5
        end = server.engine_stats()
    finally:
        server.engine.stop()
    ob = lm.Observed(stats_start=start, stats_end=end)
    assert lm.read(cells.layer_metric_spec(NAME), ob) == want
    for kind in ("decode", "prefill"):
        acc = end["moe"][kind]
        assert acc["launches"] > 0 and acc["expert_layers"] >= acc["launches"]
        assert acc["stacked_layers"] == (acc["expert_layers"] if want else 0)
    # the readers that were there read the same account as before
    assert lm.read(cells.layer_metric_spec("moe_experts_touched_share.moe"), ob) > 0.0
