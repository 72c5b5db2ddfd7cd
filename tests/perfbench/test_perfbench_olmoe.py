"""The ``olmoe`` family and its cell without a chip: the family's counts
against the program's at the configuration's sizes, every per-layer reading
of the cell against the ONE entry that reads it (``readings.py``), the
``ratio`` reader on two worked ``moe`` snapshots, the rehearsal of
``moe-chat-offline`` printing every one of those readings that needs no
device operation, twin families whose reference is
another model reading ``correct`` false, and the expert FFN's own reading
of the correctness check. No number printed here is a speed."""

import os
import sys
import time

import pytest

import ray_tpu

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import readings  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells, layer_metrics as lm  # noqa: E402

BENCH = cells.benchmark()
CELL = "moe-chat-offline"
CONFIG = "olmoe-1b-7b-0125-12l"
#: the 24 per-layer readings PR 27 gave this cell. Until PR 37 each was an entry of its own under ``.moe``;
#: now the cell is listed by the entry that already read the counter (``.batch``: a cell judged by
#: ``serve_tokens_per_s``), and ``.moe`` stays on the readings PR 27 was first to bring
READINGS = readings.MOE_CHAT_OFFLINE
#: read from the DEVICE's operations in the trace (a kernel's name, a jitted program's executions):
#: the CPU rehearsal's trace has host threads only, the reader finds nothing and the line leaves them out
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch"}


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_only_depth():
    model = cells.config_of(BENCH, CONFIG)
    row = {  # the catalog row's config (model-configs guide), every key under its own name
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    differs = sorted(k for k, v in row.items() if k not in model or model[k] != v)
    assert differs == ["num_hidden_layers"] == sorted(model["reduced"])
    assert model["published"] == {"num_hidden_layers": 16} and model["num_hidden_layers"] == 12
    assert model["family"] == "olmoe" and model["source"].endswith("OLMoE-1B-7B-0125-Instruct/blob/main/config.json")
    assert {"head_dim", "torch_dtype", "router_aux_loss_coef"} <= set(model["assumed"])
    assert model["deployment"] and model["serving"]["num_blocks_arithmetic"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == model["source"]


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models.llama import param_count

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.olmoe"
    cfg = fam.model_config(model, max_seq_len=4096)
    assert (cfg.qk_norm, cfg.moe_experts, cfg.moe_top_k, cfg.moe_renormalize) == (True, 64, 8, False)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden) == (2048, 16, 16, 128, 1024)
    assert fam.param_count(model) == param_count(cfg)
    assert fam.param_count(model) == pytest.approx(5.24e9, rel=0.005)
    assert fam.counts.layer_params(model) == pytest.approx(419.6e6, rel=0.001)
    assert fam.param_count({**model, "num_hidden_layers": 16}) == pytest.approx(6.92e9, rel=0.005)
    assert fam.kv_bytes_per_token(model) == 96 * 1024
    # a token multiplies against 8 of the 64 experts: 0.91 B of the 5.24 B held (1.3 B active of 6.92 B at 16 layers)
    assert fam.forward_flops_per_token(model, 0) == pytest.approx(2 * 0.910e9, rel=0.005)
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 12 * 4 * 1024 * 2048
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(
        3 * fam.forward_flops_per_token(model, 1024), rel=1e-9)
    # the grouped expert matmul of one layer: a full decode batch reads 63 experts' weights, a chunk computes
    assert fam.moe_ffn_flops(model, 256) == 2 * 256 * 3 * 2048 * 1024
    assert fam.moe_ffn_bytes(model, 256, 63) == pytest.approx(63 * 12.58e6 + 256 * 2 * 8192, rel=0.001)
    assert fam.moe_ffn_flops(model, 8192) / 197e12 > fam.moe_ffn_bytes(model, 8192, 64) / 819e9 * 0.4
    # an override of the file reaches the program's config; what the program does not run is refused
    assert fam.model_config(model, max_seq_len=64, moe_capacity_factor=2.0).moe_capacity_factor == 2.0
    with pytest.raises(ValueError, match="clip_qkv"):
        fam.model_config({**model, "clip_qkv": 8.0}, max_seq_len=64)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_reports_what_the_issue_names():
    names = [m["name"] for m in BENCH["per_layer"]]
    # each there once. The cell's readings are not a closed set and no place in a list is the last: a later
    # PR appends its own (``test_perfbench_append.py``)
    assert len(set(READINGS)) == 24 and all(names.count(name) == 1 for name in READINGS)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "chat-offline", 1)


@pytest.mark.parametrize("name", READINGS)
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name.endswith(".moe"):  # PR 27's own: this cell is the first the entry names
        assert entry["workloads"][0] == CELL


def _snapshot(launches, assignments, touched, max_load, mean_load, slots_per_launch=12 * 64, prefill=(0, 0)):
    return {"moe": {"decode": {
        "launches": launches, "assignments": assignments, "expert_slots": launches * slots_per_launch,
        "experts_touched": touched, "max_load": max_load, "mean_load": mean_load,
    }, "prefill": {"assignments": prefill[0], "experts_touched": prefill[1]}}}


@pytest.mark.parametrize("name, want", [
    # 100 decode launches of 32 full slots over 12 layers of 64 experts between the two snapshots
    ("moe_experts_touched_share.moe", 100.0 * 75_300 / 76_800),
    ("moe_load_imbalance.moe", 11_400 / 4_800.0),
    ("moe_rows_per_expert.moe", 307_200 / 75_300),
    # and 10 prefill chunks of 1024 real rows: 128 rows an expert where all 64 are touched
    ("moe_rows_per_expert_prefill.moe", 983_040 / 7_680),
])
def test_the_ratio_reader_on_two_worked_moe_snapshots(name, want):
    ob = lm.Observed(
        stats_start=_snapshot(40, 122_880, 30_120, 4_560, 1_920.0, prefill=(196_608, 1_536)),
        stats_end=_snapshot(140, 430_080, 105_420, 15_960, 6_720.0, prefill=(1_179_648, 9_216)),
    )
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # a dense model's engine_stats() has no "moe" key: the reader finds nothing and says nothing
    dense = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert lm.read(cells.layer_metric_spec(name), dense) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import olmoe_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import olmoe

TOY_SIZES = dict(olmoe.TOY_SIZES)
model_config, server_class, train_program = olmoe.model_config, olmoe.server_class, olmoe.train_program
param_count, kv_bytes_per_token = olmoe.param_count, olmoe.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = olmoe.forward_flops_per_token, olmoe.train_flops_per_token
reference_loss = olmoe.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_expert_ffn(model, layer_params, h):
    return controls.expert_ffn(layer_params, h, top_k=int(model["num_experts_per_tok"]), variant={ffn!r})
'''

#: twin family -> the control its whole-model reference and its expert FFN's reference compute
TWINS = {
    "olmoe_renormalised": ("renormalised", "renormalised"),
    # the whole model as the reference has it, the expert FFN alone wrong: only the second reading can tell
    "olmoe_ffn_experts_fp8": (None, "experts_fp8"),
    "olmoe_ffn_last_expert_out": (None, "last_expert_out"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    """The twin families of ``TWINS``, importable from beside ``perfbench/``
    here and, through ``PYTHONPATH``, in a cluster's workers."""
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, ffn) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, ffn=ffn))
    saved = os.environ.get("PYTHONPATH")
    saved_path = list(families.__path__)
    families.__path__.append(str(portion))
    os.environ["PYTHONPATH"] = os.pathsep.join(filter(None, [str(outside), HERE, saved]))
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("PYTHONPATH", None)
        else:
            os.environ["PYTHONPATH"] = saved
        families.__path__[:] = saved_path
        for name in TWINS:
            sys.modules.pop(f"perfbench.families.{name}", None)


@pytest.fixture(scope="module")
def cluster(twins):
    """A fake-chip cluster whose workers can also import the twin families."""
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


def _rehearse(family, tmp_path, trace):
    from perfbench.harness import serve_cell

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        time.sleep(0.1)  # the previous cell's worker is being retired
    cell = cells.cell(BENCH, CELL)
    config = rehearsal.tiny({**cells.config_of(BENCH, cell["config"]), "family": family})
    assert config["correctness"]["logit_rel_tol"] == 1e-3
    assert (config["num_experts"], config["num_experts_per_tok"], config["intermediate_size"]) == (4, 2, 32)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 27,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("olmoe", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(READINGS) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0.0 < value["moe_experts_touched_share.moe"] <= 100.0
    assert value["moe_load_imbalance.moe"] >= 1.0 and value["moe_rows_per_expert.moe"] >= 1.0
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end["moe"]
    assert end["decode"]["launches"] > 0 and end["prefill"]["launches"] > 0
    assert end["decode"]["assignments"] <= 4 * 2 * 2 * end["decode"]["launches"]  # slots x k x layers
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    """Gates renormalised everywhere; and, with the whole model as the
    reference has it, the expert FFN's own reference computed in float8 or
    without a token's last expert: the second reading alone refuses those."""
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False


# -- the expert FFN's own reading (perfbench/families/olmoe/server.py) ------------------------

@pytest.fixture(scope="module")
def toy_server():
    """The family's deployment class at the toy sizes, in this process."""
    from perfbench.harness.program import engine_config

    model = rehearsal.tiny(cells.config_of(BENCH, CONFIG))
    fam = families.of(model)
    cfg = fam.model_config(model, max_seq_len=model["max_position_embeddings"])
    server = fam.server_class()(cfg, engine_config(model["serving"]["engine"]), seed=7, export_metrics=False)
    try:
        yield model, server
    finally:
        server.engine.stop()


def test_the_expert_ffn_is_read_at_both_shapes_and_entered_against_its_own_limit(toy_server):
    model, server = toy_server
    limits = model["correctness"]
    got = server.bench_check(model, 2**31 + 5, limits["prompt_lens"], limits["decode_steps"])
    assert got["positions"][-2:] == [["expert_ffn", "32"], ["expert_ffn", "4"]]  # a prefill chunk, a decode batch
    ffn = got["expert_ffn"]
    assert ffn["finite"] and got["finite"] and set(ffn["worst"]) == {"32", "4"}
    assert all(len(v) == 2 for v in ffn["by_layer"].values())  # three layers over the depth; the toy has two
    assert max(ffn["worst"].values()) < 1e-5  # float32 against float32: the order of summation
    share = limits["logit_rel_tol"] / limits["expert_ffn_rel_tol"]
    assert got["rel_err"][-2:] == [pytest.approx(share * ffn["worst"][k]) for k in ("32", "4")]
    assert max(got["rel_err"]) <= limits["logit_rel_tol"]


@pytest.mark.parametrize("family", ["olmoe_ffn_experts_fp8", "olmoe_ffn_last_expert_out"])
def test_the_second_reading_alone_refuses_a_wrong_expert_ffn(toy_server, twins, family):
    """Against a twin that is wrong in the expert FFN's reference only, the
    logits' entries pass and the FFN's are over the limit the harness holds
    the worst entry to."""
    model, server = toy_server
    limits = model["correctness"]
    got = server.bench_check({**model, "family": family}, 2**31 + 5, limits["prompt_lens"],
                             limits["decode_steps"])
    logits, ffn = got["rel_err"][:-2], got["rel_err"][-2:]
    assert max(logits) <= limits["logit_rel_tol"] < min(ffn)
    assert min(got["expert_ffn"]["worst"].values()) > limits["expert_ffn_rel_tol"]
