"""The ``lfm2`` family and its cell without a chip: the configuration file
against the catalog row and its ``BENCHMARK.json`` entry, the family's counts
against the program's at the configuration's sizes, every per-layer reading of
the cell against the ONE entry that reads it (``readings.py``), the rehearsal
of ``conv-reason-offline`` printing every one of those readings that needs no
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
CELL = "conv-reason-offline"
CONFIG = "lfm2-8b-a1b-ep2"
#: the readings of other cells this one JOINS: the entry that already reads the counter lists the cell
BATCH = [f"{n}.batch" for n in (
    "decode_step_device_ms", "prefill_step_device_ms", "device_idle_share", "tokens_per_engine_step",
    "step_host_serial_ms", "step_schedule_ms", "step_sample_ms", "step_emit_ms", "step_launch_ms",
    "step_device_wait_ms", "step_readback_ms", "kv_pool_peak_share", "preemptions",
    "decode_table_width_tokens", "decode_gather_live_share", "wakes_after_launch_share", "wake_hold_ms",
)]
MOE = [f"{n}.moe" for n in ("recompiles_in_window", "moe_experts_touched_share", "moe_load_imbalance",
                            "moe_rows_per_expert", "moe_ffn_time_share", "moe_rows_per_expert_prefill")]
JOINED = BATCH + MOE + ["replica_init_s", "param_init_s", "warmup_s", "moe_held_assignment_share.mla",
                        "kv_bytes_per_token.mla", "prefill_read_live_share.longdoc",
                        "latent_flash_time_share.longdoc"]
#: entries whose files ``readings.WANT`` does not hold: what each one's file must read
OTHERS = {
    "state_bytes_per_seq.kda": {"kind": "stats_delta", "key": ["state_layout", "bytes_per_seq"]},
    "state_pool_peak_share.kda": {"kind": "stats_delta", "key": ["state_pool", "in_use"],
                                  "per": ["state_pool", "slots"], "scale": 100.0},
    "state_admission_waits.kda": {"kind": "stats_delta", "key": ["state_pool", "admission_waits"]},
    "paged_attn_time_share.batch": {"kind": "device_trace", "name_regex": "^paged_attn"},
    # scale = 100 x the 4 experts a token (assignments / 4 = pairs): this model's top-k too
    "moe_bias_changed_share.mla": {"kind": "stats_delta", "key": ["moe", "prefill", "bias_changed"],
                                   "per": ["moe", "prefill", "assignments"], "scale": 400.0},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch",
              "latent_flash_time_share.longdoc", "paged_attn_time_share.batch"}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
CUT = ["max_position_embeddings", "num_experts"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_two_keys():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_experts"], model["max_position_embeddings"]) == (16, 8192)
    assert model["num_hidden_layers"] == 24 and model["vocab_size"] == 65536  # depth and vocabulary whole
    assert model["layer_types"].count("conv") == 18 and model["layer_types"].count("full_attention") == 6
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["num_experts_total"], dep["held_experts"]) == (2, 32, [0, 16])
    assert model["family"] == "lfm2"
    assert model["source"].endswith("LFM2-8B-A1B/blob/main/config.json")
    assert {"tie_word_embeddings", "torch_dtype", "expert_bias", "intermediate_size", "norm_weights",
            "head_norms", "conv"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "state_rel_tol", "state_deep_rel_tol", "expert_ffn_rel_tol", "conv_rel_tol",
            "attn_rel_tol"} <= set(model["correctness"])
    lens = model["correctness"]["prompt_lens"]
    largest = model["serving"]["engine"]["prefill_buckets"][-1]
    # three chunks with a padded tail; inside the small bucket; a chunk shorter than the taps; one short
    assert lens[0] > 2 * largest and lens[0] % largest and 0 < lens[2] % largest < model["conv_L_cache"]
    assert model["correctness"]["decode_steps"] >= 24
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["num_blocks"] % 8) == ([128], 128, 0)
    assert not {"state_slots", "greedy_on_device"} & set(engine)  # derived by the engine, not set
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])  # no width is cut
    # appended after what was there; where the lists end is not pinned: a later PR appends its own
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("mellum2-12b-a2.5b-ep4") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("swa-mixed-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import lfm2

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.lfm2"
    cfg = fam.model_config(model, max_seq_len=8192)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden, cfg.moe_hidden,
            cfg.conv_kernel) == (2048, 32, 8, 64, 7168, 1792, 3)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_experts, cfg.held_experts, cfg.moe_top_k,
            cfg.routed_scaling_factor, cfg.rope_theta, cfg.vocab_size) == (24, 2, 32, (0, 16), 4, 1.0, 1e6, 65536)
    assert cfg.attn_layers == (2, 6, 10, 14, 18, 21) and cfg.kinds.count("conv") == 18
    assert fam.param_count(model) == lfm2.param_count(cfg) == 4_464_393_664
    whole = fam.model_config({**model, "num_experts": 32, "deployment": {**model["deployment"], "held_experts": [0, 32]}},
                             max_seq_len=8192)
    assert fam.counts.param_count(model, experts=32) == lfm2.param_count(whole) == 8_339_930_560
    assert fam.counts.conv_params(model) == 16_783_360 and fam.counts.attention_params(model) == 10_485_888
    assert fam.counts.ffn_params(model, False) == 44_040_192
    assert fam.counts.ffn_params(model, True, experts=1) - fam.counts.ffn_params(model, True, experts=0) == 11_010_048
    layout, state = lfm2.cache_layout(cfg, 16), lfm2.state_layout(cfg)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 12_288  # 6 of 24 layers write rows
    assert layout.n_layers == 6 and layout.block_bytes == 196_608 and layout.row_width == 1024
    assert layout.block_shape((8, 64)) == (16, 512)  # a token's heads in one row: four whole lane tiles
    assert layout.describe() == {"kind": "kv", "row_width": 1024, "bytes_per_token": 12_288}
    assert fam.state_bytes_per_seq(model) == state.bytes_per_seq == 147_456
    assert state.describe() == {"kind": "short_conv", "layers": 18, "bytes_per_seq": 147_456}
    # a token's context costs the 6 attending layers' pairs alone; the convolution costs the same at any length
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 6 * 4 * 32 * 64 * 1024
    # 1.5 B weights multiply a token where all 32 experts are held (the published A1.5B), about 1.05 B here
    assert fam.counts.matmul_params_per_token({**model, "num_experts": 32}) == pytest.approx(1.56e9, rel=0.03)
    assert fam.counts.matmul_params_per_token(model) == pytest.approx(1.08e9, rel=0.03)
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    # the kernels' calls at heads of 64: K and V of the live rows once; 8 and 2 times the multiplies
    paged = fam.counts.paged_attn_cost(model, live_tokens=128 * 1650, slots=128)
    assert paged["bytes"] == (2 * 512 * 128 * 1650 + 2 * 128 * 2048) * 2 and paged["flops_run"] == 8 * paged["flops"]
    flash = fam.counts.flash_cost(model, ctx_len=1024, true_len=1024)
    assert flash["flops"] == 4 * 2048 * (1024 * 1024 + 1024 * 1025 // 2) and flash["flops_run"] == 2 * flash["flops"]
    with pytest.raises(ValueError, match="conv_bias"):
        fam.model_config({**model, "conv_bias": True}, max_seq_len=64)
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "num_experts": 32}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_each_once():
    names = [m["name"] for m in BENCH["per_layer"]]
    readings_ = JOINED + list(OTHERS)
    assert len(set(readings_)) == len(readings_)
    assert all(names.count(name) == 1 for name in readings_)  # each there once; more may follow
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("swa-mixed-offline") < e2e["workloads"].index(CELL)
    # an entry an earlier PR's test holds to the cells it named is not joined, and no copy is brought
    stacked = next(m for m in BENCH["per_layer"] if m["name"] == "moe_stacked_layers_share.moe")
    assert CELL not in stacked["workloads"]
    assert not [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]  # the cell brings no entry of its own
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-offline-128", 1)
    traffic, parent = cells.traffic_of("reason-offline-128"), cells.traffic_of("reason-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"]) == ("closed", 128, 128)
    assert traffic["rounds"] >= 8 and traffic["lengths"] == parent["lengths"]  # the lengths unchanged
    assert {k: traffic[k] for k in ("lead_in_seconds", "trace_seconds", "edge_grace_s")} == {
        "lead_in_seconds": 20.0, "trace_seconds": 5.0, "edge_grace_s": 15.0}
    model = cells.config_of(BENCH, CONFIG)
    assert traffic["clients"] == model["serving"]["engine"]["max_decode_batch"]  # = decode slots = state slots
    from perfbench.harness import schedule as sch

    longest = max(r.prompt_len + r.output_len for r in sch.closed_stream(traffic, 1))
    assert longest <= 5632 <= model["max_position_embeddings"]  # every request fits


@pytest.mark.parametrize("name", JOINED + list(OTHERS))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, OTHERS.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    # a joined entry: the cells that were there come first
    assert len(entry["workloads"]) > 1 and entry["workloads"].index(CELL) > 0
    assert entry["workloads"][0] != CELL


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import lfm2_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import lfm2 as real
from perfbench.families.lfm2 import server

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_logits_and_tails(model, params, tokens, picks, ats):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r}, ats=ats)


def reference_conv(model, layer_params, u):
    C = model["serving"]["engine"]["prefill_buckets"][-1]
    n2 = max(1, int(C * server.TAIL_SHARE))
    return controls.conv(model, layer_params, u, variant={conv!r}, edges=(C + n2, C + n2 + 1))


def reference_attention(model, layer_params, u):
    return controls.attention(model, layer_params, u, variant={attn!r})


def reference_expert_ffn(model, layer_params, f):
    return controls.expert_ffn(model, layer_params, f, variant={ffn!r})
'''

#: twin family -> the control its whole-model reference, its convolution, its attention and its expert FFN compute
TWINS = {
    "lfm2_oldest_tap_dropped": ("oldest_tap_dropped", None, None, None),
    "lfm2_tail_cut_at_padded_end": ("tail_cut_at_padded_end", None, None, None),
    "lfm2_qk_norm_whole_projection": ("qk_norm_whole_projection", None, None, None),
    "lfm2_weights_fp8": ("weights_fp8", None, None, None),
    # the whole model as the reference has it, ONE mixer alone wrong: only that mixer's reading can tell (a gate
    # that keeps its bias is held by the expert FFN's reading alone: the logits hardly hear of it)
    "lfm2_conv_oldest_tap_dropped": (None, "oldest_tap_dropped", None, None),
    "lfm2_conv_tail_cut_at_padded_end": (None, "tail_cut_at_padded_end", None, None),
    "lfm2_attn_qk_norm_whole_projection": (None, None, "qk_norm_whole_projection", None),
    "lfm2_ffn_gate_keeps_bias": (None, None, None, "gate_keeps_bias"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, conv, attn, ffn) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, conv=conv, attn=attn, ffn=ffn))
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
    # the toy's drive: two chunks with a padded tail, and 32 + 1: a chunk shorter than the taps
    config["correctness"].update(prompt_lens=[40, 33, 12], decode_steps=3)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 49,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("lfm2", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + list(OTHERS)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == 2 * 2 * (2 * 64) * 4  # 2 attending layers of 7, K and V of 2 x 64 float32
    assert value["state_bytes_per_seq.kda"] == 5 * 2 * 256 * 4  # 5 convolution layers, two inputs of 256 float32
    assert value["state_pool_peak_share.kda"] == 100.0  # 4 clients on 4 slots
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    assert value["moe_held_assignment_share.mla"] < 100.0  # 4 of 8 experts held
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "kv" and end["state_layout"]["kind"] == "short_conv"
    pool = end["state_pool"]
    assert pool["slots"] == 4 and pool["assigned"] >= pool["released"] > 0
    assert end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
