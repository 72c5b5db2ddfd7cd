"""The ``olmo_hybrid`` family and its cell without a chip: the configuration
file against the catalog row and its ``BENCHMARK.json`` entry, the family's
counts against the program's at the configuration's sizes and against a hand
count, every per-layer reading of the cell against the ONE entry that reads it
(``readings.py``), the rehearsal of ``gdn-rollout-offline`` printing every one
of those readings that needs no device operation, and twin families whose
reference is another model reading ``correct`` false. No number printed here is
a speed.

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

import olmo_hybrid_controls as controls  # noqa: E402
import readings  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells  # noqa: E402

BENCH = cells.benchmark()
CELL = "gdn-rollout-offline"
CONFIG = "olmo-hybrid-7b-16l"
#: the readings of other cells this one JOINS: the entry that already reads the counter lists the cell
BATCH = [f"{n}.batch" for n in (
    "decode_step_device_ms", "prefill_step_device_ms", "device_idle_share", "tokens_per_engine_step",
    "step_host_serial_ms", "step_schedule_ms", "step_sample_ms", "step_emit_ms", "step_launch_ms",
    "step_device_wait_ms", "step_readback_ms", "kv_pool_peak_share", "preemptions",
    "decode_table_width_tokens", "decode_gather_live_share", "wakes_after_launch_share", "wake_hold_ms",
)]
JOINED = BATCH + ["replica_init_s", "param_init_s", "warmup_s", "recompiles_in_window.moe",
                  "kv_bytes_per_token.mla", "prefill_read_live_share.longdoc", "latent_flash_time_share.longdoc"]
#: entries whose files ``readings.WANT`` does not hold: what each one's file must read
OTHERS = {
    "state_bytes_per_seq.kda": {"kind": "stats_delta", "key": ["state_layout", "bytes_per_seq"]},
    "state_pool_peak_share.kda": {"kind": "stats_delta", "key": ["state_pool", "in_use"],
                                  "per": ["state_pool", "slots"], "scale": 100.0},
    "state_admission_waits.kda": {"kind": "stats_delta", "key": ["state_pool", "admission_waits"]},
    "paged_attn_time_share.batch": {"kind": "device_trace", "name_regex": "^paged_attn"},
    # the ONE decode-update kernel serves both states and keeps its device operation's name
    "kda_update_time_share.kda": {"kind": "device_trace", "name_regex": "^kda_update"},
}
#: the ONE entry this PR BRINGS: what the pool's layout really holds a sequence
BROUGHT = {
    "state_stored_bytes_per_seq.gdn": {"kind": "stats_delta", "key": ["state_layout", "stored_bytes_per_seq"]},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"decode_step_device_ms.batch", "prefill_step_device_ms.batch", "latent_flash_time_share.longdoc",
              "paged_attn_time_share.batch", "kda_update_time_share.kda"}

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
CUT = ["layer_types", "max_position_embeddings", "num_hidden_layers"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_depth_and_the_table():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    # four WHOLE periods of the published eight, and every published width
    assert model["layer_types"] == PERIOD * 4 == ROW["layer_types"][:16] and model["num_hidden_layers"] == 16
    assert model["max_position_embeddings"] == 4096
    assert model["published"]["num_hidden_layers"] == 32 and model["published"]["max_position_embeddings"] == 65536
    assert (model["hidden_size"], model["intermediate_size"], model["vocab_size"]) == (3840, 11008, 100352)
    assert model["deployment"]["chips"] == 1 and model["deployment"]["pipeline_stages_of_the_whole_model"] == 2
    assert model["family"] == "olmo_hybrid" and model["source"].endswith("Olmo-Hybrid-7B/blob/main/config.json")
    assert {"norm_placement", "qk_norm", "rope_theta", "head_dim", "state_dtype", "state_layout", "seeded_gates",
            "weight_scales", "torch_dtype"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"] and model["sizes"]
    assert {"logit_rel_tol", "state_rel_tol", "state_deep_rel_tol", "tail_rel_tol", "tail_deep_rel_tol",
            "gdn_rel_tol", "attn_rel_tol"} <= set(model["correctness"])
    lens = model["correctness"]["prompt_lens"]
    largest = model["serving"]["engine"]["prefill_buckets"][-1]
    # three chunks with a padded tail; inside the small bucket; a chunk of ONE row, shorter than the taps; one short
    assert lens[0] > 2 * largest and lens[0] % largest and lens[2] % largest == 1 < model["linear_conv_kernel_dim"]
    assert model["correctness"]["decode_steps"] >= 24
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["block_size"]) == ([64], 64, 16)
    assert engine["prefix_cache_enabled"] is False
    assert not {"state_slots", "greedy_on_device"} & set(engine)  # derived by the engine, not set
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not [k for k in entry["reduced"] if k.endswith(("_dim", "_rank", "_size"))]  # no width is named
    # appended after what was there; where the lists end is not pinned: a later PR appends its own
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("glm-5-744b-a40b-ep16") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("dsa-longctx-batch") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_and_with_a_hand_count():
    from ray_tpu.models import olmo_hybrid as oh

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.olmo_hybrid"
    cfg = fam.model_config(model, max_seq_len=4096)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden) == (3840, 30, 30, 128, 11008)
    assert (cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.conv_kernel, cfg.allow_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.n_layers, cfg.vocab_size, cfg.norm_eps, cfg.conv_width) == (16, 100352, 1e-6, 11520)
    assert [l for l, k in enumerate(cfg.layer_types) if k == "full_attention"] == [3, 7, 11, 15]
    assert fam.param_count(model) == oh.param_count(cfg) == 4_100_788_944
    assert fam.counts.gdn_params(model) == 88_750_332 and fam.counts.attn_params(model) == 58_990_080
    layout, state = oh.cache_layout(cfg, 16), oh.state_layout(cfg)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 61_440  # 4 of 16 layers write rows
    assert fam.state_bytes_per_seq(model) == state.bytes_per_seq == 27_371_520 == state.stored_bytes_per_seq
    # the form not chosen, [30, 96, 192]: 192 lanes stored as 256
    from ray_tpu.models.interface import StateLayout
    import jax.numpy as jnp

    padded = StateLayout("gdn", 12, (("gdn_state", (30, 96, 192), jnp.float32), state.arrays[1]))
    assert padded.bytes_per_seq == 27_371_520 and padded.stored_bytes_per_seq == 36_218_880
    # a token's context costs the 4 attending layers' pairs alone; a Gated DeltaNet layer costs the same at any length
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 4 * 4 * 30 * 128 * 1024
    assert fam.counts.matmul_params_per_token(model) == pytest.approx(3.715e9, rel=0.01)
    assert fam.counts.gdn_state_flops_per_token(model) == 30 * 7 * 96 * 192
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    # the kernels' calls, by hand: a step of 65 slots of one layer; 55,000 live tokens of one layer
    assert fam.counts.gdn_update_bytes(model, 65) == 65 * 4_423_680 == 287_539_200
    assert fam.counts.paged_attn_bytes(model, 55_000) == 55_000 * 15_360
    with pytest.raises(ValueError, match="rope_parameters"):
        fam.model_config({**model, "rope_parameters": {"rope_theta": 500000.0}}, max_seq_len=64)
    with pytest.raises(ValueError, match="key heads"):
        fam.model_config({**model, "linear_num_key_heads": 15}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


def test_a_checkout_without_the_model_module_ends_the_run_as_the_family_is_imported(monkeypatch, tmp_path):
    """A parent under this PR's benchmark files: ``families.of`` in ``run.py``
    imports the family before any cluster starts, and the family asks for the
    model module's PATH (it imports nothing of the program)."""
    import importlib.machinery
    import importlib.util

    fam = families.of(cells.config_of(BENCH, CONFIG))
    fam._refuse_a_checkout_without_the_model()  # this checkout has it
    (tmp_path / "models").mkdir()
    bare = importlib.machinery.ModuleSpec("ray_tpu", None, is_package=True)
    bare.submodule_search_locations = [str(tmp_path)]
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: bare)
    with pytest.raises(SystemExit, match="no ray_tpu.models.olmo_hybrid"):
        fam._refuse_a_checkout_without_the_model()


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_one():
    names = [m["name"] for m in BENCH["per_layer"]]
    readings_ = JOINED + list(OTHERS) + list(BROUGHT)
    assert len(set(readings_)) == len(readings_)
    assert all(names.count(name) == 1 for name in readings_)  # each there once; more may follow
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("dsa-longctx-batch") < e2e["workloads"].index(CELL)
    # no routed experts, no selective scan: none of those entries is joined
    for m in BENCH["per_layer"]:
        if m["name"].startswith(("moe_", "ssm_")) or m["name"] == "step_readback_loads_ms.moe":
            assert CELL not in m.get("workloads", ())
    # the one it brings comes after what was there and reads this cell
    for name in BROUGHT:
        assert names.index("index_cache_bytes_per_token.dsa") < names.index(name)
        assert CELL in next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"]
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "rollout-offline", 1)
    traffic = cells.traffic_of("rollout-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"]) == ("closed", 64, 64)
    assert traffic["rounds"] >= 8 and traffic["lead_in_seconds"] >= 20.0
    lengths = traffic["lengths"]
    assert (lengths["pairing_seed"], lengths["prompt"]["median"], lengths["prompt"]["clip"]) == (23, 256, [64, 1024])
    assert (lengths["output"]["median"], lengths["output"]["sigma"], lengths["output"]["clip"]) == (768, 0.6, [256, 2048])
    assert {k: traffic[k] for k in ("trace_seconds", "edge_grace_s")} == {"trace_seconds": 5.0, "edge_grace_s": 15.0}
    model = cells.config_of(BENCH, CONFIG)
    assert traffic["clients"] == model["serving"]["engine"]["max_decode_batch"]  # = decode slots = state slots
    from perfbench.harness import schedule as sch

    stream = sch.closed_stream(traffic, 1)
    longest = max(r.prompt_len + r.output_len for r in stream)
    assert longest <= 3072 <= model["max_position_embeddings"]  # every request fits
    # the pool holds more than the traffic can ask: the multiset whole with every request at its END (the peak of
    # 64 clients somewhere inside their requests is about 4,200 blocks)
    blocks = sum(-(-(r.prompt_len + r.output_len) // 16) for r in stream[:64])
    assert blocks < model["serving"]["engine"]["num_blocks"] - 1


@pytest.mark.parametrize("name", JOINED + list(OTHERS) + list(BROUGHT))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, BROUGHT.get(name) or OTHERS.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in BROUGHT:
        assert entry["layer"] == "state pool" and entry["workloads"].index(CELL) == 0
    else:  # a joined entry: the cells that were there come first
        assert len(entry["workloads"]) > 1 and entry["workloads"].index(CELL) > 0


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import olmo_hybrid_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import olmo_hybrid as real
from perfbench.families.olmo_hybrid import server

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_logits_and_state(model, params, tokens, picks, ats):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r}, ats=ats)


def reference_gdn(model, layer_params, x):
    buckets = model["serving"]["engine"]["prefill_buckets"]
    C, n2 = buckets[-1], max(1, int(buckets[-1] * server.TAIL_SHARE))
    return controls.gdn(model, layer_params, x, variant={gdn!r}, starts=(C, C + n2, C + n2 + 1))


def reference_attention(model, layer_params, x):
    return controls.attention(model, layer_params, x, variant={attn!r})
'''

#: twin family -> the control its whole-model reference, its Gated DeltaNet mixer and its attention compute
TWINS = {
    **{f"olmo_hybrid_{v}": (v, None, None) for v in controls.VARIANTS},
    # the whole model as the reference has it, ONE mixer alone wrong: only that mixer's reading can tell
    **{f"olmo_hybrid_gdn_{v}": (None, v, None) for v in ("beta_without_its_factor", "gate_mean_over_heads",
                                                         "carry_dropped")},
    "olmo_hybrid_attn_k_norm_left_out": (None, None, "k_norm_left_out"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, gdn, attn) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, gdn=gdn, attn=attn))
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
    # the toy's drive: two chunks with a padded tail, and 32 + 1: a chunk of ONE row; float32 against float32
    config["correctness"].update(prompt_lens=[40, 33, 12], decode_steps=3, state_rel_tol=1e-4,
                                 state_deep_rel_tol=1e-4, tail_rel_tol=1e-4, tail_deep_rel_tol=1e-4,
                                 gdn_rel_tol=1e-4, attn_rel_tol=1e-4)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 64,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("olmo_hybrid", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + list(OTHERS) + list(BROUGHT)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == 2 * 2 * 3 * 16 * 4  # 2 attending layers of 7, K and V of 3 x 16 float32
    needs = 5 * (3 * 8 * 16 + 3 * 96) * 4  # 5 recurrent layers: S of 3 heads of 8 x 16 and 3 inputs of 96
    assert value["state_bytes_per_seq.kda"] == needs
    assert value["state_stored_bytes_per_seq.gdn"] == 5 * (8 * 128 + 384) * 4 > needs  # the toy's lanes pad
    # 4 clients on 4 slots, sampled once a second over a short window: a loaded machine may miss some
    assert value["state_pool_peak_share.kda"] >= 50.0
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    assert value["state_admission_waits.kda"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "kv" and end["state_layout"]["kind"] == "gdn"
    pool = end["state_pool"]
    assert pool["slots"] == 4 and pool["assigned"] >= pool["released"] > 0
    assert end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
