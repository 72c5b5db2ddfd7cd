"""The ``glm_moe_dsa`` family and its cell without a chip: the configuration
file against the catalog row and its ``BENCHMARK.json`` entry, the traffic file,
the family's counts against the program's at the configuration's sizes, every
per-layer reading of the cell against the ONE entry that reads it
(``readings.py``), the new counters' readers on worked snapshots, the rehearsal
of ``dsa-longctx-batch`` printing every one of those readings that needs no
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
from perfbench.harness import cells, layer_metrics as lm, schedule  # noqa: E402

BENCH = cells.benchmark()
CELL = "dsa-longctx-batch"
CONFIG = "glm-5-744b-a40b-ep16"
#: the readings of other cells this one JOINED: each entry that already read a counter its program has
JOINED = [f"{n}.batch" for n in (
    "prefill_step_device_ms", "device_idle_share", "tokens_per_engine_step", "step_host_serial_ms",
    "step_launch_ms", "step_device_wait_ms", "step_readback_ms", "kv_pool_peak_share", "preemptions",
    "decode_table_width_tokens", "decode_gather_live_share", "step_schedule_ms", "step_sample_ms",
    "step_emit_ms", "wakes_after_launch_share", "wake_hold_ms",
)] + ["replica_init_s", "param_init_s", "warmup_s"] + [f"{n}.moe" for n in (
    "recompiles_in_window", "moe_experts_touched_share", "moe_load_imbalance", "moe_rows_per_expert",
    "moe_ffn_time_share", "moe_rows_per_expert_prefill",
)] + ["moe_held_assignment_share.mla", "kv_bytes_per_token.mla", "prefill_read_live_share.longdoc"]
#: joined too, and held by membership alone (their files are other tests' to hold)
ALSO = ["decode_ahead_share.batch", "step_longest_ms.batch", "step_launch_call_ms.batch",
        "step_readback_loads_ms.moe", "step_emit_commit_ms.batch", "spec_accept_share.mtp",
        "spec_committed_per_slot_step.mtp", "spec_fused_launch_share.mtp",
        "verify_step_device_ms.mtp", "prefill_expand_live_share.mla",
        "stalled_step_ms.batch", "device_ready_on_arrival_share.batch"]
#: this PR's own counters -> what each one's file must hold
NEW = {
    "dsa_selected_share.dsa": {"kind": "stats_delta", "key": ["sparse_attention", "chosen"],
                               "per": ["sparse_attention", "live"], "scale": 100.0},
    "dsa_queries_past_topk_share.dsa": {"kind": "stats_delta", "key": ["sparse_attention", "queries_past_topk"],
                                        "per": ["sparse_attention", "queries"], "scale": 100.0},
    "index_cache_bytes_per_token.dsa": {"kind": "stats_delta", "key": ["kv_layout", "arrays", "index", "bytes_per_token"]},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "prefill_step_device_ms.batch", "verify_step_device_ms.mtp"}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu", "head_dim": 64,
    "hidden_size": 6144, "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 202752, "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts_per_tok": 8, "num_hidden_layers": 78,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880,
}
CUT = ["first_k_dense_replace", "max_position_embeddings", "n_routed_experts", "num_hidden_layers", "vocab_size"]


# -- the configuration, the traffic and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_five_keys_and_no_width():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["n_routed_experts"],
            model["vocab_size"], model["max_position_embeddings"]) == (6, 1, 16, 19360, 32768)
    assert model["vocab_size"] * 8 == ROW["vocab_size"]  # an eighth: the floor
    assert model["num_nextn_predict_layers"] == 1 and model["index_topk"] == 2048
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["n_routed_experts_total"], dep["held_experts"],
            dep["vocabulary_shards"]) == (16, 256, [0, 16], 8)
    assert model["family"] == "glm_moe_dsa" and model["source"].endswith("zai-org/GLM-5/blob/main/config.json")
    assert {"torch_dtype", "indexer_hadamard", "index_key_precision", "indexer_rope", "index_key_norm",
            "mtp_indexer", "norm_weights", "gate"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "expert_ffn_rel_tol", "mtp_logit_rel_tol", "select_margin", "select_miss_tol",
            "attention_rel_tol"} <= set(model["correctness"])
    lens = model["correctness"]["prompt_lens"]  # one well past index_topk, one under it
    assert max(lens) > 3 * model["index_topk"] and min(lens) < model["index_topk"]
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["num_blocks"] % 8) == ([8], 8, 0)
    assert (engine["speculative_k"], engine["speculative_draft"], engine["speculative_adaptive"],
            engine["prefix_cache_enabled"]) == (1, "mtp", False, False)
    # the pool holds the traffic's fullest moment: 8 requests of up to 23,552 + 128 tokens
    assert engine["num_blocks"] * engine["block_size"] > 8 * 16384 + 8 * 128
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size" for k in entry["reduced"])
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("laguna-xs.2-33b-a3b-ep16") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("gated-swa-reason-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_the_traffic_is_what_the_cell_names():
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "longctx-batch", 1)
    traffic = cells.traffic_of("longctx-batch")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"]) == ("closed", 8, 8)
    pairs = schedule.length_multiset(traffic["lengths"], 8)
    prompts = sorted(p for p, _ in pairs)
    assert prompts == list(range(9216, 24576, 2048)) and all(p % 1024 == 0 for p in prompts)
    assert all(32 <= o <= 128 for _, o in pairs) and max(p + o for p, o in pairs) < 32768
    # by the lengths: 2048 of a mean prompt of 16384 lie under index_topk, and a query keeps about 22%
    assert sum(prompts) == 8 * 16384
    chosen = sum(min(t + 1, 2048) for p in prompts for t in range(p))
    live = sum(p * (p + 1) // 2 for p in prompts)
    assert 0.20 < chosen / live < 0.24
    stream = schedule.closed_stream(traffic, 5)
    assert sorted(r.prompt_len for r in stream[:8]) == prompts == sorted(r.prompt_len for r in stream[8:16])


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import glm_dsa, latent

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.glm_moe_dsa"
    cfg = fam.model_config(model, max_seq_len=32768)
    assert (cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.mlp_hidden, cfg.moe_hidden) == (6144, 64, 2048, 512, 192, 64, 256, 12288, 2048)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (32, 128, 2048)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_experts, cfg.held_experts, cfg.moe_top_k, cfg.n_group,
            cfg.routed_scaling_factor, cfg.n_mtp_layers, cfg.hc_mult, cfg.rope_factor, cfg.rope_theta) == (
        6, 1, 256, (0, 16), 8, 1, 2.5, 1, 0, 1.0, 1e6)
    assert cfg.attn_scale == 1 / 16  # (192 + 64)^-1/2, no YaRN factor
    assert fam.param_count(model) == glm_dsa.MODEL.param_count(cfg) == 5_620_564_736
    assert fam.counts.indexer_params(model) == 2048 * 32 * 128 + 6144 * 128 + 2 * 128 + 6144 * 32
    assert fam.counts.attention_params(model) == pytest.approx(174.39e6, rel=0.0005)
    assert fam.counts.layer_params(model, False) == pytest.approx(400.90e6, rel=0.0005)
    assert fam.counts.layer_params(model, True) == pytest.approx(817.71e6, rel=0.0005)
    assert fam.counts.mtp_params(model) == pytest.approx(893.22e6, rel=0.0005)
    whole = {**model, "n_routed_experts": 256, "num_hidden_layers": 78, "first_k_dense_replace": 3,
             "vocab_size": 154880}  # every expert, layer and token id: the name's 744B (with the MTP module)
    assert fam.param_count(whole) == pytest.approx(744e9, rel=0.03)
    layout = glm_dsa.MODEL.cache_layout(cfg, 16)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 9856  # 7 rows of (576 + 128) x 2 B
    assert fam.index_bytes_per_token(model) == 1792 == layout.describe()["arrays"]["index"]["bytes_per_token"]
    assert layout.n_layers == 7 and layout.block_bytes == 157696 and layout.row_width == 704
    # a window of two absorbs and gathers by token, a chunk attends under the mask; 8 rungs of 4096
    assert latent.absorbs(cfg, 2) and not latent.absorbs(cfg, 1024)
    assert latent.index_rungs(cfg, 32768, 16) == tuple(range(4096, 32769, 4096))
    assert glm_dsa.MODEL.attention_path(cfg, 2, None).name == "latent.sparse"
    assert glm_dsa.MODEL.attention_path(cfg, 1024, None).name == "latent.sparse_masked"
    # the indexer over ALL of the context, the attention over min(context, 2048)
    at = lambda n: fam.forward_flops_per_token(model, n)  # noqa: E731
    per_pair_index, per_pair_attend = 32 * (2 * 128 + 3), 64 * (2 * 256 + 2 * 256)
    assert at(2048) - at(1024) == pytest.approx(6 * 1024 * (per_pair_index + per_pair_attend))
    assert at(16384) - at(8192) == pytest.approx(6 * 8192 * per_pair_index)
    index = fam.index_scores_cost(model, 1024, 16384)
    assert index["flops"] == 1024 * 16384 * per_pair_index
    masked = fam.masked_attend_cost(model, 1024, 16384)
    assert masked["flops"] == 1024 * 16384 * 64 * (2 * 576 + 2 * 512)
    assert fam.gathered_attend_cost(model, 2)["flops"] == 2 * 2048 * 64 * (2 * 576 + 2 * 512)
    assert fam.topk_cost(model, 1024, 16384) == {"flops": 64.0 * 1024 * 16384, "bytes": 5.0 * 1024 * 16384}
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * at(1024))
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "n_routed_experts": 32}, max_seq_len=64)
    with pytest.raises(ValueError, match="n_group"):
        fam.model_config({**model, "n_group": 8}, max_seq_len=64)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_three_each_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(JOINED) & set(NEW) and not set(ALSO) & set(JOINED)
    assert all(names.count(name) == 1 for name in JOINED + ALSO + list(NEW))  # each there once; more may follow
    assert names.index("decode_wave_live_share.batch") < min(names.index(name) for name in NEW)
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("gated-swa-reason-offline") < e2e["workloads"].index(CELL)
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", ())}
    assert set(ALSO) <= listed
    # what its program has no counter or operation for: no plain decode program, no K/V kernel, neither
    # latent kernel (every window selects), no group stage, no state pool, a bias reader scaled for 4 a token;
    # and two entries whose counters it HAS but whose own tests (PRs 43, 45) let no later cell join them
    assert not listed & {"decode_step_device_ms.batch", "paged_attn_time_share.batch", "moe_bias_changed_share.mla",
                         "latent_flash_time_share.longdoc", "latent_rows_time_share", "moe_group_changed_share.dsv3",
                         "decode_wave_live_share.batch", "state_pool_peak_share.kda",
                         "spec_ahead_share.mtp", "moe_stacked_layers_share.moe"}


@pytest.mark.parametrize("name", JOINED + list(NEW))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, NEW.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW:
        assert entry["workloads"][0] == CELL
        assert entry["layer"] == {"dsa_": "paged steps", "inde": "KV cache manager"}[name[:4]]
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("mtp-reason-offline") < entry["workloads"].index(CELL)


def _snapshot(queries, past, chosen, live):
    return {"sparse_attention": {"queries": queries, "queries_past_topk": past, "chosen": chosen, "live": live},
            "kv_layout": {"kind": "latent", "bytes_per_token": 9856,
                          "arrays": {"latent": {"row_width": 576, "bytes_per_token": 8064},
                                     "index": {"row_width": 128, "bytes_per_token": 1792}}}}


@pytest.mark.parametrize("name, want", [
    ("dsa_selected_share.dsa", 100.0 * (9000 - 1000) / (40000 - 2000)),
    ("dsa_queries_past_topk_share.dsa", 100.0 * (900 - 50) / (1000 - 100)),
    ("index_cache_bytes_per_token.dsa", 1792.0),
])
def test_the_new_counters_readers_on_worked_snapshots(name, want):
    ob = lm.Observed(stats_start=_snapshot(100, 50, 1000, 2000), stats_end=_snapshot(1000, 900, 9000, 40000))
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # an engine_stats() without these counters (a parent checkout, or a model whose attention sees
    # everything: the key is there and None): nothing is read, nothing raises
    for sparse in ({}, {"sparse_attention": None}):
        older = lm.Observed(stats_start={"total_steps": 1, "kv_layout": {"kind": "latent", "bytes_per_token": 8064}, **sparse},
                            stats_end={"total_steps": 2, "kv_layout": {"kind": "latent", "bytes_per_token": 8064}, **sparse})
        assert lm.read(cells.layer_metric_spec(name), older) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import glm_dsa_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import glm_moe_dsa as real

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss, reference_expert_ffn = real.reference_loss, real.reference_expert_ffn


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={whole!r})


def reference_both_logits(model, params, tokens, picks, mtp_picks):
    return controls.both_logits_at(model, params, tokens, picks, mtp_picks, variant={whole!r})


def reference_attention(model, stacked, layer, h, queries):
    return controls.attention_alone(model, stacked, layer, h, queries, variant={alone!r})
'''

#: twin family -> the control its whole-model reference computes, and its one layer's attention alone
TWINS = {
    "glm_selection_left_out": ("selection_left_out", "selection_left_out"),
    "glm_indexer_without_relu": ("indexer_without_relu", "indexer_without_relu"),
    "glm_indexer_without_weights": ("indexer_without_weights", "indexer_without_weights"),
    # the whole model as the reference has it: the SELECTION's reading alone tells the late choice
    "glm_selection_a_block_late_alone": (None, "selection_a_block_late"),
    "glm_selection_a_block_late": ("selection_a_block_late", None),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (whole, alone) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(whole=whole, alone=alone))
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
    # float32 against float32: the two readings of one layer alone read rounding
    config["correctness"].update(select_margin=1e-5, select_miss_tol=1e-3, attention_rel_tol=1e-3,
                                 mtp_logit_rel_tol=1e-3, expert_ffn_rel_tol=1e-3)
    assert (config["n_routed_experts"], config["deployment"]["n_routed_experts_total"]) == (4, 8)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 41,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("glm_moe_dsa", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + ALSO + list(NEW)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    # 3 layers + the module, a latent row of 24 and an index key of 16 float32
    assert value["kv_bytes_per_token.mla"] == (3 + 1) * (24 + 16) * 4
    assert value["index_cache_bytes_per_token.dsa"] == (3 + 1) * 16 * 4
    # the toy prompts (8 to 60 tokens) stand on both sides of the toy index_topk of 24
    assert 0.0 < value["dsa_queries_past_topk_share.dsa"] < 100.0
    assert 24.0 / 72 * 100 < value["dsa_selected_share.dsa"] < 100.0
    assert value["spec_fused_launch_share.mtp"] == 100.0
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["speculative"]["draft"] == "mtp" and end["speculative"]["slot_steps"] > 0
    assert end["kv_layout"]["arrays"]["index"]["row_width"] == 16 and end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
