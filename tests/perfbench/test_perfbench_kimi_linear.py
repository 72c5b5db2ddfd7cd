"""The ``kimi_linear`` family and its cell without a chip: the configuration
file against the catalog row and its ``BENCHMARK.json`` entry, the family's
counts against the program's at the configuration's sizes, every per-layer
reading of the cell against the ONE entry that reads it (``readings.py``),
the rehearsal of ``kda-reason-offline`` printing every one of those readings
that needs no device operation, and twin families whose reference is another model reading
``correct`` false. No number printed here is a speed.

What this PR added is held RELATIVE to what was there (after a named earlier
entry, by membership, once): never a last place, a whole list or a count."""

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
CELL = "kda-reason-offline"
CONFIG = "kimi-linear-48b-a3b-ep16"
#: the 19 readings of other cells PR 35 gave this one. Until PR 37 each was a twin under ``.kda``; now the
#: cell is listed by the entry that already read the counter, under that entry's name
SHARED = [f"{n}.batch" for n in (
    "decode_step_device_ms", "prefill_step_device_ms", "step_device_wait_ms", "step_host_serial_ms",
    "tokens_per_engine_step", "device_idle_share", "kv_pool_peak_share", "preemptions",
    "decode_gather_live_share", "step_readback_ms", "decode_table_width_tokens",
)] + ["warmup_s", "recompiles_in_window.moe", "moe_ffn_time_share.moe", "moe_rows_per_expert.moe",
      "moe_held_assignment_share.mla", "kv_bytes_per_token.mla",
      "prefill_read_live_share.longdoc", "latent_flash_time_share.longdoc"]
#: PR 35's own counters -> what each one's file must hold. ``.kda`` stays on them
NEW_COUNTERS = {
    "state_bytes_per_seq.kda": {"kind": "stats_delta", "key": ["state_layout", "bytes_per_seq"]},
    "state_pool_peak_share.kda": {"kind": "stats_delta", "key": ["state_pool", "in_use"],
                                  "per": ["state_pool", "slots"], "scale": 100.0},
    "state_admission_waits.kda": {"kind": "stats_delta", "key": ["state_pool", "admission_waits"]},
}
READINGS = SHARED + list(NEW_COUNTERS)
#: what PR 35 had no place for among the contract's 128 entries and the cell JOINED in PR 37: the rest of
#: the step's host account (``engine.schedule`` is most of this cell's idle gaps), the wakes, the start-up
#: stages, the expert account's other three readings; and the reader of PR 36's kernel over latent rows
JOINED = [f"{n}.batch" for n in ("step_schedule_ms", "step_launch_ms", "step_sample_ms", "step_emit_ms",
                                 "wakes_after_launch_share", "wake_hold_ms")] + [
    "replica_init_s", "param_init_s", "moe_experts_touched_share.moe", "moe_load_imbalance.moe",
    "moe_rows_per_expert_prefill.moe", "latent_rows_time_share"]
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch",
              "latent_flash_time_share.longdoc", "latent_rows_time_share"}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                           "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
                           "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1,
    "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840,
}
CUT = ["model_max_length", "num_experts"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_two_keys():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_experts"], model["model_max_length"], model["max_position_embeddings"]) == (16, 8192, 8192)
    assert "max_position_embeddings" in model["assumed"]  # the harness's name for the serving length
    assert model["num_hidden_layers"] == 27 and model["vocab_size"] == 163840  # depth and vocabulary whole
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["num_experts_total"], dep["held_experts"]) == (16, 256, [0, 16])
    assert model["family"] == "kimi_linear"
    assert model["source"].endswith("Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert {"torch_dtype", "kda_gate_rank", "kda_state_dtype", "seeded_gates", "norm_weights"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "state_rel_tol", "state_deep_rel_tol", "expert_ffn_rel_tol", "kda_rel_tol",
            "mla_rel_tol"} <= set(model["correctness"])
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["num_blocks"] % 8) == ([64], 64, 0)
    assert not {"state_slots", "greedy_on_device"} & set(engine)  # derived by the engine, not set
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])  # no width is cut
    # appended after what was there; where the lists end is not pinned: a later PR appends its own
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("xing4.0-29b-a4b-ep8") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("mla-longdoc-batch") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import kimi_linear as kl

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.kimi_linear"
    cfg = fam.model_config(model, max_seq_len=8192)
    assert (cfg.dim, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.kda_heads, cfg.kda_head_dim, cfg.mlp_hidden, cfg.moe_hidden) == (2304, 32, 512, 128, 64, 128, 32, 128, 9216, 1024)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_experts, cfg.held_experts, cfg.moe_top_k,
            cfg.conv_kernel, cfg.routed_scaling_factor) == (27, 1, 256, (0, 16), 8, 4, 2.446)
    assert cfg.kinds.count("kda") == 20 and [l + 1 for l, k in enumerate(cfg.kinds) if k == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    assert fam.param_count(model) == kl.param_count(cfg) == 4_956_660_608
    assert fam.counts.kda_params(model) == pytest.approx(39.51e6, rel=0.0005)
    assert fam.counts.mla_params(model) == pytest.approx(29.11e6, rel=0.0005)
    assert fam.counts.ffn_params(model, False) == pytest.approx(63.70e6, rel=0.0005)
    whole = {**model, "num_experts": 256}  # all 256 experts held: the name's 48B
    assert fam.param_count(whole) == pytest.approx(49.12e9, rel=0.001)
    layout, state = kl.cache_layout(cfg, 16), kl.state_layout(cfg)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 8064  # 7 of 27 layers write rows
    assert layout.n_layers == 7 and layout.block_bytes == 129024 and layout.row_width == 576
    assert layout.describe() == {"kind": "latent", "row_width": 576, "bytes_per_token": 8064}
    assert fam.state_bytes_per_seq(model) == state.bytes_per_seq == 43_417_600  # 43.4 MB whatever the length
    assert state.describe() == {"kind": "kda", "layers": 20, "bytes_per_seq": 43_417_600}
    # a token's context costs the 7 attending layers' expanded pairs alone; the recurrence costs the same at any length
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 7 * 20480 * 1024
    assert fam.counts.kda_update_bytes(model, 64) == 64 * 2 * 32 * 128 * 128 * 4
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    with pytest.raises(ValueError, match="mla_use_nope"):
        fam.model_config({**model, "mla_use_nope": False}, max_seq_len=64)
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "num_experts": 32}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


# -- the metric files -------------------------------------------------------------------

def test_the_cell_reports_nineteen_readings_of_other_cells_and_three_new_counters_each_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(set(READINGS)) == 22 and not set(READINGS) & set(JOINED)
    assert all(names.count(name) == 1 for name in READINGS + JOINED)  # each there once; more may follow
    assert names.index("prefill_read_live_share.longdoc") < min(names.index(name) for name in NEW_COUNTERS)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("moe-chat-offline") < e2e["workloads"].index(CELL)
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-offline", 1)
    traffic = cells.traffic_of("reason-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"], traffic["rounds"]) == ("closed", 64, 64, 8)
    assert traffic["lengths"]["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8, "clip": [256, 4096]}
    assert traffic["lengths"]["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "clip": [128, 1536]}
    assert traffic["lengths"]["pairing_seed"] == 23 and traffic["lead_in_seconds"] == 20.0
    from perfbench.harness import schedule as sch

    assert max(r.prompt_len + r.output_len for r in sch.closed_stream(traffic, 1)) <= 8192  # every request fits


@pytest.mark.parametrize("name", READINGS + JOINED)
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, NEW_COUNTERS.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW_COUNTERS:
        assert entry["workloads"][0] == CELL and entry["layer"] == "state pool"
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("mla-longdoc-batch") < entry["workloads"].index(CELL)


def _snapshot(in_use, waits):
    return {"state_layout": {"kind": "kda", "layers": 20, "bytes_per_seq": 43_417_600},
            "state_pool": {"slots": 64, "in_use": in_use, "peak_in_use": 64, "assigned": 100 + waits,
                           "released": 90, "admission_waits": waits}}


@pytest.mark.parametrize("name, want", [
    ("state_bytes_per_seq.kda", 43_417_600.0),
    ("state_pool_peak_share.kda", 100.0 * 61 / 64),
    ("state_admission_waits.kda", 7.0),
])
def test_the_new_counters_readers_on_worked_snapshots(name, want):
    ob = lm.Observed(stats_start=_snapshot(40, 5), stats_end=_snapshot(58, 12),
                     stats_samples=[_snapshot(40, 5), _snapshot(61, 9), _snapshot(58, 12)])
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # an engine_stats() without these counters (a parent checkout), or of a model whose layers
    # all attend (state_layout None): nothing is read, nothing raises
    older = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert lm.read(cells.layer_metric_spec(name), older) is None
    none = {"state_layout": None, "state_pool": {"slots": 0, "in_use": 0, "admission_waits": 0}}
    if name != "state_admission_waits.kda":
        assert lm.read(cells.layer_metric_spec(name), lm.Observed(stats_start=none, stats_end=none)) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import kimi_linear_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import kimi_linear as real

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss, reference_expert_ffn = real.reference_loss, real.reference_expert_ffn
reference_attention = real.reference_attention


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_logits_and_states(model, params, tokens, picks, lengths):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r}, lengths=lengths)


def reference_kda(model, layer_params, h):
    return controls.kda(model, layer_params, h, variant={kda!r})
'''

#: twin family -> the control its whole-model reference and its KDA layer's compute
TWINS = {
    "kimi_shared_key_rotated": ("shared_key_rotated", None),
    "kimi_state_dropped_at_chunk_edge": ("state_dropped_at_chunk_edge", None),
    # the whole model as the reference has it, the KDA layer alone wrong: only the third reading can tell
    "kimi_kda_state_dropped_at_chunk_edge": (None, "state_dropped_at_chunk_edge"),
    "kimi_kda_decay_left_out": (None, "decay_left_out"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, kda) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, kda=kda))
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
    assert (config["num_experts"], config["deployment"]["num_experts_total"]) == (4, 8)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 35,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("kimi_linear", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(READINGS + JOINED) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == 2 * (16 + 8) * 4  # 2 attending layers of 7, a row of 24 float32
    assert value["state_bytes_per_seq.kda"] == 5 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)  # 5 KDA layers
    assert value["state_pool_peak_share.kda"] == 100.0  # 4 clients on 4 slots
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "latent" and end["state_layout"]["kind"] == "kda"
    pool = end["state_pool"]
    assert pool["slots"] == 4 and pool["assigned"] >= pool["released"] > 0
    assert end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
