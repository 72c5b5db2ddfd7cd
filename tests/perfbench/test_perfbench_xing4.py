"""The ``xing4`` family and its cell without a chip: the configuration file
against the catalog row and its ``BENCHMARK.json`` entry, the family's
counts against the program's at the configuration's sizes, every per-layer
reading of the cell against the ONE entry that reads it (``readings.py``),
the rehearsal of ``mla-longdoc-batch`` printing every one of those readings
that needs no device operation, and twin families whose reference is another model reading
``correct`` false. No number printed here is a speed."""

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
CELL = "mla-longdoc-batch"
CONFIG = "xing4.0-29b-a4b-ep8"
#: what was there before PR 31, in its order: the place of each is still held, what follows PR 31's is not
BEFORE_CONFIGS = ("mistral-7b-v0.3-16l", "codestral-22b-v0.1-8l-fsdp4", "olmoe-1b-7b-0125-12l")
BEFORE_CELLS = ("chat-paced", "chat-offline", "train-fsdp4-2k", "longprompt-batch", "moe-chat-offline")
#: the 24 readings ``moe-chat-offline`` has that PR 31 gave this cell too, a literal list (a reading that cell
#: gets later is no business of this one's). Until PR 37 each was a twin under ``.mla``; now the cell is
#: listed by the entry that already read the counter
SHARED = readings.MOE_CHAT_OFFLINE
#: PR 31's own counters -> what each one's file must hold. ``.mla`` stays on them: this cell is their first
NEW_COUNTERS = {
    "moe_held_assignment_share.mla": readings.WANT["moe_held_assignment_share.mla"],
    "moe_bias_changed_share.mla": {"kind": "stats_delta", "key": ["moe", "prefill", "bias_changed"],
                                   "per": ["moe", "prefill", "assignments"], "scale": 400.0},
    "kv_bytes_per_token.mla": readings.WANT["kv_bytes_per_token.mla"],
}
#: the engine's hold-and-wake path runs on every step of this cell too
WAKES = ["wakes_after_launch_share.batch", "wake_hold_ms.batch"]
READINGS = SHARED + list(NEW_COUNTERS) + WAKES
#: the kernels this cell's trace names: PR 32's flash kernel over a prefill chunk, PR 36's over latent rows
KERNELS = ["latent_flash_time_share.longdoc", "latent_rows_time_share"]
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch"}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu",
    "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
}
CUT = ["max_position_embeddings", "n_routed_experts", "num_nextn_predict_layers"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_three_keys():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["n_routed_experts"], model["max_position_embeddings"], model["num_nextn_predict_layers"]) == (8, 8192, 0)
    assert model["num_hidden_layers"] == 40 and model["vocab_size"] == 131072  # depth and vocabulary whole
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["n_routed_experts_total"], dep["held_experts"]) == (8, 64, [0, 8])
    assert model["family"] == "xing4" and model["source"].endswith("Xing4.0-29B-A4B/blob/main/config.json")
    assert {"torch_dtype", "rotary_pairing", "residual_state", "mhc_norm", "norm_weights"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    # appended after what was there, nothing moved. Where the lists end is not pinned: a later PR appends its own
    assert tuple(c["name"] for c in BENCH["configs"][: len(BEFORE_CONFIGS) + 1]) == BEFORE_CONFIGS + (CONFIG,)
    assert tuple(w["name"] for w in BENCH["workloads"][: len(BEFORE_CELLS) + 1]) == BEFORE_CELLS + (CELL,)


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import xing4

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.xing4"
    cfg = fam.model_config(model, max_seq_len=8192)
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mlp_hidden, cfg.moe_hidden) == (3584, 32, 768, 512, 128, 64, 128, 9216, 1024)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_experts, cfg.held_experts, cfg.moe_top_k,
            cfg.hc_mult, cfg.hc_sinkhorn_iters) == (40, 2, 64, (0, 8), 4, 4, 20)
    assert fam.param_count(model) == xing4.param_count(cfg)
    assert fam.param_count(model) == pytest.approx(6.076e9, rel=0.0005)
    assert fam.counts.layer_params(model, False) == pytest.approx(128.20e6, rel=0.0005)
    assert fam.counts.layer_params(model, True) == pytest.approx(128.43e6, rel=0.0005)
    assert fam.counts.attention_params(model) == pytest.approx(28.41e6, rel=0.0005)
    assert fam.counts.mhc_params(model) == pytest.approx(0.69e6, rel=0.005)
    # the whole model, all 64 experts held: the name's 29B
    whole = {**model, "n_routed_experts": 64}
    assert fam.param_count(whole) == pytest.approx(29.51e9, rel=0.001)
    layout = xing4.cache_layout(cfg, 16)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 46080
    assert layout.block_bytes == 737280 and layout.row_width == 576
    assert layout.describe() == {"kind": "latent", "row_width": 576, "bytes_per_token": 46080}
    # a token's context costs the expanded form's pairs; the absorbed form is 3.4 x that a pair
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 40 * 20480 * 1024
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    assert fam.attention_flops_per_pair(model, True) / fam.attention_flops_per_pair(model, False) == 3.4
    with pytest.raises(ValueError, match="scoring_func"):
        fam.model_config({**model, "scoring_func": "softmax"}, max_seq_len=64)
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "n_routed_experts": 16}, max_seq_len=64)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_reports_the_moe_cells_readings_the_wakes_and_three_new_counters():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(READINGS)) == 29 and all(names.count(name) == 1 for name in READINGS)  # each once; more may follow
    assert names.index("paged_attn_time_share.batch") < min(names.index(name) for name in NEW_COUNTERS)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longdoc-batch", 1)
    traffic = cells.traffic_of("longdoc-batch")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"]) == ("closed", 8, 8)
    assert traffic["lengths"]["prompt"]["clip"] == [2048, 6656] and traffic["lengths"]["output"]["clip"] == [16, 64]
    assert traffic["lengths"]["pairing_seed"] == 23 and traffic["lead_in_seconds"] == 8.0


@pytest.mark.parametrize("name", READINGS + KERNELS)
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, NEW_COUNTERS.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW_COUNTERS or name in KERNELS:  # this cell is the first the entry names
        assert entry["workloads"][0] == CELL
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("moe-chat-offline") < entry["workloads"].index(CELL)


def _snapshot(assignments, held, changed, prefill):
    return {"kv_layout": {"kind": "latent", "row_width": 576, "bytes_per_token": 46080},
            "moe": {"decode": {"assignments": assignments, "held_assignments": held},
                    "prefill": {"assignments": prefill, "bias_changed": changed}}}


@pytest.mark.parametrize("name, want", [
    ("moe_held_assignment_share.mla", 100.0 * 15_300 / 121_600),
    ("moe_bias_changed_share.mla", 100.0 * 9_000 / (1_556_480 / 4)),
    ("kv_bytes_per_token.mla", 46080.0),
])
def test_the_new_counters_readers_on_two_worked_snapshots(name, want):
    ob = lm.Observed(stats_start=_snapshot(48_640, 6_000, 1_000, 622_592),
                     stats_end=_snapshot(170_240, 21_300, 10_000, 2_179_072))
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # an engine_stats() without these counters (a parent checkout): nothing is read, nothing raises
    older = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert lm.read(cells.layer_metric_spec(name), older) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import xing4_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import xing4

TOY_SIZES = dict(xing4.TOY_SIZES)
model_config, server_class, train_program = xing4.model_config, xing4.server_class, xing4.train_program
param_count, kv_bytes_per_token = xing4.param_count, xing4.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = xing4.forward_flops_per_token, xing4.train_flops_per_token
reference_loss = xing4.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_expert_ffn(model, layer_params, h):
    return controls.expert_ffn(model, layer_params, h, variant={ffn!r})


def reference_residual(model, layer_params, sub, norm, X):
    return controls.residual(model, layer_params, sub, norm, X, variant={residual!r})
'''

#: twin family -> the control its whole-model reference, its expert FFN's and its residual's compute
TWINS = {
    "xing4_key_rope_unrotated": ("key_rope_unrotated", None, None),
    "xing4_sinkhorn_1_round": ("sinkhorn_1_round", None, None),
    # the whole model as the reference has it, the expert FFN alone wrong: only the second reading can tell
    "xing4_ffn_bias_in_the_gate": (None, "bias_in_the_gate", None),
    # ... the residual alone wrong: only the third reading can tell (on the chip six logits cannot: PERF.md section 6)
    "xing4_residual_sinkhorn_1_round": (None, None, "sinkhorn_1_round"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, ffn, residual) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, ffn=ffn, residual=residual))
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
    assert (config["n_routed_experts"], config["deployment"]["n_routed_experts_total"], config["hc_mult"]) == (4, 8, 2)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 31,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("xing4", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(READINGS) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == 4 * (16 + 8) * 4  # 4 layers, a row of 24 float32
    assert 20.0 < value["moe_held_assignment_share.mla"] < 80.0  # 4 of 8 held at the toy sizes
    assert 0.0 <= value["moe_bias_changed_share.mla"] <= 200.0  # the toy routes 2 a token, the scale says 4
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "latent"
    moe = end["moe"]
    assert moe["decode"]["launches"] > 0 and moe["prefill"]["launches"] > 0
    assert 0 < moe["decode"]["held_assignments"] < moe["decode"]["assignments"]
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
