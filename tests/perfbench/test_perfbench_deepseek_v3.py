"""The ``deepseek_v3`` family and its cell without a chip: the configuration
file against the catalog row and its ``BENCHMARK.json`` entry, the family's
counts against the program's at the configuration's sizes, every per-layer
reading of the cell against the ONE entry that reads it (``readings.py``), the
new counters' readers on worked snapshots, the rehearsal of
``mtp-reason-offline`` printing every one of those readings that needs no
device operation, and twin families whose reference is another model reading
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
CELL = "mtp-reason-offline"
CONFIG = "gigachat3.1-702b-a36b-ep16"
#: the readings of other cells this one JOINED: each entry that already read a counter its program has
JOINED = [f"{n}.batch" for n in (
    "prefill_step_device_ms", "device_idle_share", "tokens_per_engine_step", "step_host_serial_ms",
    "step_launch_ms", "step_device_wait_ms", "step_readback_ms", "kv_pool_peak_share", "preemptions",
    "decode_table_width_tokens", "decode_gather_live_share", "step_schedule_ms", "step_sample_ms",
    "step_emit_ms", "wakes_after_launch_share", "wake_hold_ms",
)] + ["replica_init_s", "param_init_s", "warmup_s"] + [f"{n}.moe" for n in (
    "recompiles_in_window", "moe_experts_touched_share", "moe_load_imbalance", "moe_rows_per_expert",
    "moe_ffn_time_share", "moe_rows_per_expert_prefill",
)] + ["moe_held_assignment_share.mla", "kv_bytes_per_token.mla", "prefill_read_live_share.longdoc",
      "latent_flash_time_share.longdoc", "latent_rows_time_share"]
#: joined too, and held by membership alone (their files are other tests' to hold: PRs 38 and 39)
ALSO = ["decode_ahead_share.batch", "step_longest_ms.batch", "step_launch_call_ms.batch",
        "step_readback_loads_ms.moe", "step_emit_commit_ms.batch"]
#: this PR's own counters -> what each one's file must hold
NEW = {
    "spec_accept_share.mtp": {"kind": "stats_delta", "key": ["speculative", "accepted_tokens"],
                              "per": ["speculative", "proposed_tokens"], "scale": 100.0},
    "spec_committed_per_slot_step.mtp": {"kind": "stats_delta", "key": ["speculative", "committed_tokens"],
                                         "per": ["speculative", "slot_steps"]},
    "spec_fused_launch_share.mtp": {"kind": "stats_delta", "key": ["speculative", "launches_fused"],
                                    "per": ["speculative", "step_launches"], "scale": 100.0},
    "verify_step_device_ms.mtp": {"kind": "device_trace", "name_regex": "paged_mtp_step"},
    "moe_group_changed_share.dsv3": {"kind": "stats_delta", "key": ["moe", "decode", "group_changed"],
                                     "per": ["moe", "decode", "routed_rows"], "scale": 100.0},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "prefill_step_device_ms.batch", "latent_flash_time_share.longdoc",
              "latent_rows_time_share", "verify_step_device_ms.mtp"}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "vocab_size": 128256, "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64, "num_nextn_predict_layers": 1,
    "num_attention_heads": 64, "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
    "v_head_dim": 192, "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 8, "topk_group": 4,
    "num_experts_per_tok": 8, "moe_layer_freq": 1, "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False, "model_type": "deepseek_v3",
}
CUT = ["first_k_dense_replace", "max_position_embeddings", "n_routed_experts", "num_hidden_layers", "vocab_size"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_five_keys_and_no_width():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["n_routed_experts"],
            model["vocab_size"], model["max_position_embeddings"]) == (6, 1, 16, 16032, 8192)
    assert model["vocab_size"] * 8 == ROW["vocab_size"]  # an eighth: the floor
    assert model["num_nextn_predict_layers"] == 1  # the MTP module KEPT: what the cell is for
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["n_routed_experts_total"], dep["held_experts"],
            dep["vocabulary_shards"]) == (16, 256, [0, 16], 8)
    assert model["family"] == "deepseek_v3" and model["source"].endswith("GigaChat3.1-702B-A36B/blob/main/config.json")
    assert {"torch_dtype", "mtp_concatenation_order", "mtp_rope_position", "speculative_adaptive", "norm_weights",
            "gate"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "expert_ffn_rel_tol", "mtp_logit_rel_tol"} <= set(model["correctness"])
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["num_blocks"] % 8) == ([64], 64, 0)
    assert (engine["speculative_k"], engine["speculative_draft"], engine["speculative_adaptive"],
            engine["prefix_cache_enabled"]) == (1, "mtp", False, False)
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    # appended after what was there; where the lists end is not pinned: a later PR appends its own
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("kimi-linear-48b-a3b-ep16") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("kda-reason-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import deepseek_v3 as dsv3
    from ray_tpu.models import latent

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.deepseek_v3"
    cfg = fam.model_config(model, max_seq_len=8192)
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mlp_hidden, cfg.moe_hidden) == (7168, 64, 1536, 512, 128, 64, 192, 18432, 2048)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_experts, cfg.held_experts, cfg.moe_top_k, cfg.n_group,
            cfg.topk_group, cfg.routed_scaling_factor, cfg.n_mtp_layers, cfg.hc_mult) == (
        6, 1, 256, (0, 16), 8, 8, 4, 2.5, 1, 0)
    assert fam.param_count(model) == dsv3.param_count(cfg) == 6_160_266_752
    assert fam.counts.attention_params(model) == pytest.approx(132.58e6, rel=0.0005)
    assert fam.counts.layer_params(model, False) == pytest.approx(528.96e6, rel=0.0005)
    assert fam.counts.layer_params(model, True) == pytest.approx(883.11e6, rel=0.0005)
    assert fam.counts.mtp_params(model) == pytest.approx(985.90e6, rel=0.0005)
    whole = {**model, "n_routed_experts": 256, "num_hidden_layers": 64, "first_k_dense_replace": 3,
             "vocab_size": 128256}  # every expert, layer and token id: the name's 702B (with the MTP module)
    assert fam.param_count(whole) == pytest.approx(702e9, rel=0.03)
    layout = dsv3.cache_layout(cfg, 16)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 8064  # 6 layers + the module: 7 rows of 1152 B
    assert layout.n_layers == 7 and layout.block_bytes == 129024 and layout.row_width == 576
    assert layout.describe() == {"kind": "latent", "row_width": 576, "bytes_per_token": 8064}
    # a window of two absorbs, a chunk of 256 expands; the flash kernel takes the 192-wide value
    assert fam.counts.absorb_break_even_window(model) == pytest.approx(232.7, abs=0.1)
    assert latent.absorbs(cfg, 2) and not latent.absorbs(cfg, 256)
    flash = fam.latent_flash_cost(model, 1024, 2048)
    assert flash["flops"] == 2 * 64 * 1024 * 2048 * (128 + 64 + 192)
    assert flash["bytes"] == 2 * (64 * 1024 * (128 + 64 + 256) + 2048 * (64 * (128 + 256) + 64))
    rows2, rows1 = fam.latent_rows_cost(model, 2, 1000), fam.latent_rows_cost(model, 1, 1000)
    assert rows2["flops"] == 2 * rows1["flops"] == 2 * 2.0 * 64 * 1008 * (576 + 512)
    assert rows2["bytes"] - rows1["bytes"] == 2 * 64 * (576 + 512)  # the rows are read once for both queries
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "n_routed_experts": 32}, max_seq_len=64)
    with pytest.raises(ValueError, match="one MTP module or none"):
        fam.model_config({**model, "num_nextn_predict_layers": 2}, max_seq_len=64)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_five_each_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(JOINED) & set(NEW) and not set(ALSO) & set(JOINED)
    assert all(names.count(name) == 1 for name in JOINED + ALSO + list(NEW))  # each there once; more may follow
    assert names.index("decode_ahead_share.paced") < min(names.index(name) for name in NEW)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("kda-reason-offline") < e2e["workloads"].index(CELL)
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-offline", 1)
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert set(ALSO) <= listed
    # what its program has no counter or operation for: no plain decode program, no K/V kernel, no
    # state pool, and a bias reader scaled for 4 experts a token
    assert not listed & {"decode_step_device_ms.batch", "paged_attn_time_share.batch", "moe_bias_changed_share.mla",
                         "state_pool_peak_share.kda", "state_bytes_per_seq.kda", "state_admission_waits.kda"}


@pytest.mark.parametrize("name", JOINED + list(NEW))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, NEW.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW:
        assert entry["workloads"][0] == CELL
        assert entry["layer"] == {"spec": "engine scheduler", "veri": "paged steps", "moe_": "expert FFN"}[name[:4]]
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("kda-reason-offline") < entry["workloads"].index(CELL)


def _snapshot(steps, accepted, split=0):
    """An engine of 64 slots after ``steps`` drafter steps of which ``accepted`` drafts were accepted."""
    slots = 64 * steps
    return {
        "speculative": {"draft": "mtp", "proposed_tokens": slots, "accepted_tokens": accepted, "rollbacks": slots - accepted,
                        "slot_steps": slots, "committed_tokens": slots + accepted, "step_launches": steps,
                        "launches_fused": steps - split, "launches_split": split},
        "moe": {"decode": {"routed_rows": 6 * 2 * slots, "group_changed": 5 * slots}},
    }


@pytest.mark.parametrize("name, want", [
    ("spec_accept_share.mtp", 100.0 * 160 / 6400),
    ("spec_committed_per_slot_step.mtp", 1.0 + 160 / 6400),
    ("spec_fused_launch_share.mtp", 100.0 * 98 / 100),
    ("moe_group_changed_share.dsv3", 100.0 * 5 / 12),
])
def test_the_new_counters_readers_on_worked_snapshots(name, want):
    ob = lm.Observed(stats_start=_snapshot(50, 40), stats_end=_snapshot(150, 200, split=2))
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # an engine_stats() without these counters (a parent checkout, or an engine that does not
    # speculate): nothing is read, nothing raises
    older = lm.Observed(stats_start={"total_steps": 1, "moe": {"decode": {"launches": 1}}},
                        stats_end={"total_steps": 2, "moe": {"decode": {"launches": 2}}})
    assert lm.read(cells.layer_metric_spec(name), older) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import deepseek_v3_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import deepseek_v3 as real

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={whole!r})


def reference_both_logits(model, params, tokens, picks, mtp_picks):
    return controls.both_logits_at(model, params, tokens, picks, mtp_picks, variant={whole!r})


def reference_expert_ffn(model, stacked, layer, h):
    return controls.expert_ffn(model, stacked, layer, h, variant={ffn!r})
'''

#: twin family -> the control its whole-model reference (main and MTP logits) and its expert FFN's compute
TWINS = {
    "dsv3_scale_without_m2": ("scale_without_m2", None),
    # the main model as the reference has it, the MTP module alone wrong: only the third reading can tell
    "dsv3_mtp_halves_swapped": ("mtp_halves_swapped", None),
    "dsv3_mtp_embeds_the_same_token": ("mtp_embeds_the_same_token", None),
    # the whole model as the reference has it, the expert FFN alone without the group limit
    "dsv3_ffn_group_limit_left_out": (None, "group_limit_left_out"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (whole, ffn) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(whole=whole, ffn=ffn))
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
    assert (config["n_routed_experts"], config["deployment"]["n_routed_experts_total"]) == (4, 8)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 41,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("deepseek_v3", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + ALSO + list(NEW)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == (3 + 1) * (16 + 8) * 4  # 3 layers + the module, a row of 24 float32
    # an all-greedy cell: every step is the ONE program; seeded weights: about no draft is accepted
    assert value["spec_fused_launch_share.mtp"] == 100.0
    assert 1.0 <= value["spec_committed_per_slot_step.mtp"] <= 1.0 + value["spec_accept_share.mtp"] / 100.0 + 1e-9
    assert 0.0 < value["moe_group_changed_share.dsv3"] < 100.0
    assert value["decode_ahead_share.batch"] == 0.0  # looking ahead stays off for drafting slots
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    spec = end["speculative"]
    assert spec["draft"] == "mtp" and spec["launches_split"] == 0 and spec["slot_steps"] > 0
    assert spec["committed_tokens"] <= spec["slot_steps"] + spec["accepted_tokens"]
    assert end["kv_layout"]["kind"] == "latent" and end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
