"""The ``jamba`` family and its cell without a chip: the configuration file
against the catalog row and its ``BENCHMARK.json`` entry, the family's counts
against the program's at the configuration's sizes and against a hand count,
every per-layer reading of the cell against the ONE entry that reads it
(``readings.py``), the rehearsal of ``ssm-reason-offline`` printing every one of
those readings that needs no device operation, and twin families whose
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

import jamba_controls as controls  # noqa: E402
import readings  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench import run as bench_run  # noqa: E402
from perfbench.harness import cells  # noqa: E402

BENCH = cells.benchmark()
CELL = "ssm-reason-offline"
CONFIG = "ai21-jamba2-3b"
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
}
#: the two entries this PR BRINGS: the kernels of ops/selective_scan.py by their device operations' names
BROUGHT = {
    "ssm_scan_time_share.ssm": {"kind": "device_trace", "name_regex": "^ssm_scan"},
    "ssm_update_time_share.ssm": {"kind": "device_trace", "name_regex": "^ssm_update"},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"decode_step_device_ms.batch", "prefill_step_device_ms.batch", "latent_flash_time_share.longdoc",
              "paged_attn_time_share.batch", *BROUGHT}

ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
CUT = ["max_position_embeddings"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_one_key():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT} and model["max_position_embeddings"] == 8192
    assert "deployment" not in model  # the chip holds the whole model
    assert model["family"] == "jamba" and model["source"].endswith("AI21-Jamba2-3B/blob/main/config.json")
    assert {"layer_order", "head_dim", "positions", "inner_norms", "torch_dtype", "state_dtype",
            "norm_weights"} <= set(model["assumed"])
    assert model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"] and model["sizes"]
    assert {"logit_rel_tol", "state_rel_tol", "state_deep_rel_tol", "tail_rel_tol", "tail_deep_rel_tol",
            "mamba_rel_tol", "attn_rel_tol"} <= set(model["correctness"])
    lens = model["correctness"]["prompt_lens"]
    largest = model["serving"]["engine"]["prefill_buckets"][-1]
    # three chunks with a padded tail; inside the small bucket; a chunk of ONE row, shorter than the taps; one short
    assert lens[0] > 2 * largest and lens[0] % largest and lens[2] % largest == 1 < model["mamba_d_conv"]
    assert model["correctness"]["decode_steps"] >= 24
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["block_size"]) == ([256], 256, 16)
    # every slot can reach the table's width, and no block beyond that
    assert engine["num_blocks"] == 256 * (model["max_position_embeddings"] // 16) + 1
    assert not {"state_slots", "greedy_on_device"} & set(engine)  # derived by the engine, not set
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    # appended after what was there; where the lists end is not pinned: a later PR appends its own
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("lfm2-8b-a1b-ep2") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("conv-reason-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_and_with_a_hand_count():
    from ray_tpu.models import jamba

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.jamba"
    cfg = fam.model_config(model, max_seq_len=8192)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden) == (2560, 20, 1, 128, 8192)
    assert (cfg.d_state, cfg.d_conv, cfg.dt_rank, cfg.expand, cfg.d_inner) == (16, 4, 160, 2, 5120)
    assert (cfg.n_layers, cfg.vocab_size, cfg.norm_eps) == (28, 65536, 1e-6)
    assert [l for l, k in enumerate(cfg.kinds) if k == "attn"] == [7, 21] and cfg.kinds.count("mamba") == 26
    assert fam.counts.layer_kinds(model).count("mamba") == 26
    assert fam.param_count(model) == jamba.param_count(cfg) == 3_029_337_472
    assert fam.counts.mamba_params(model) == 41_241_792 and fam.counts.attention_params(model) == 13_762_560
    assert fam.counts.mlp_params(model) == 62_914_560
    layout, state = jamba.cache_layout(cfg, 16), jamba.state_layout(cfg)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 1024  # 2 of 28 layers write rows
    assert fam.state_bytes_per_seq(model) == state.bytes_per_seq == 9_318_400
    assert state.describe() == {"kind": "mamba1", "layers": 26, "bytes_per_seq": 9_318_400}
    # a token's context costs the 2 attending layers' pairs alone; a Mamba layer costs the same at any length
    assert fam.forward_flops_per_token(model, 1024) - fam.forward_flops_per_token(model, 0) == 2 * 4 * 20 * 128 * 1024
    assert fam.counts.matmul_params_per_token(model) == pytest.approx(3.03e9, rel=0.01)
    assert fam.counts.recurrence_flops_per_token(model) == 6 * 16 * 5120 + 8 * 5120
    assert fam.train_flops_per_token(model, 2048) == pytest.approx(3 * fam.forward_flops_per_token(model, 1024))
    # the kernels' calls, by hand: a chunk of 1024 of one layer; a step of 256 slots of one layer
    scan = fam.counts.ssm_scan_cost(model, 1024)
    assert scan["exps"] == 1024 * 16 * 5120 == 83_886_080
    assert scan["vector_ops"] == 1024 * (6 * 81_920 + 5120) == 508_559_360
    assert scan["bytes"] == 1024 * (3 * 5120 + 32) * 4 + 3 * 327_680 == 64_028_672
    assert fam.counts.ssm_update_bytes(model, 256) == 256 * (2 * 327_680 + 3 * 20_480 + 128) + 327_680 == 183_861_248
    with pytest.raises(ValueError, match="mamba_conv_bias"):
        fam.model_config({**model, "mamba_conv_bias": False}, max_seq_len=64)
    with pytest.raises(ValueError, match="num_experts"):
        fam.model_config({**model, "num_experts": 8}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


def test_a_checkout_without_the_model_module_ends_the_run_as_the_family_is_imported(monkeypatch, tmp_path):
    """The parent of PR 52 under this PR's benchmark files: ``families.of`` in
    ``run.py`` imports the family before any cluster starts, and the family asks
    for the model module's PATH (it imports nothing of the program)."""
    import importlib.machinery
    import importlib.util

    fam = families.of(cells.config_of(BENCH, CONFIG))
    fam._refuse_a_checkout_without_the_model()  # this checkout has it
    (tmp_path / "models").mkdir()
    bare = importlib.machinery.ModuleSpec("ray_tpu", None, is_package=True)
    bare.submodule_search_locations = [str(tmp_path)]
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: bare)
    with pytest.raises(SystemExit, match="no ray_tpu.models.jamba"):
        fam._refuse_a_checkout_without_the_model()


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_two():
    names = [m["name"] for m in BENCH["per_layer"]]
    readings_ = JOINED + list(OTHERS) + list(BROUGHT)
    assert len(set(readings_)) == len(readings_)
    assert all(names.count(name) == 1 for name in readings_)  # each there once; more may follow
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1
    assert e2e["workloads"].index("conv-reason-offline") < e2e["workloads"].index(CELL)
    # no routed experts: none of the expert entries is joined
    for m in BENCH["per_layer"]:
        if m["name"].startswith("moe_") or m["name"] == "step_readback_loads_ms.moe":
            assert CELL not in m.get("workloads", ())
    # the two it brings come after what was there and read this cell
    for name in BROUGHT:
        assert names.index("kda_update_time_share.kda") < names.index(name)
        assert CELL in next(m for m in BENCH["per_layer"] if m["name"] == name)["workloads"]
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-offline-256", 1)
    traffic, parent = cells.traffic_of("reason-offline-256"), cells.traffic_of("reason-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"]) == ("closed", 256, 256)
    assert traffic["rounds"] >= 8 and traffic["lengths"] == parent["lengths"]  # the lengths unchanged
    assert traffic["lead_in_seconds"] >= 20.0
    assert {k: traffic[k] for k in ("trace_seconds", "edge_grace_s")} == {"trace_seconds": 5.0, "edge_grace_s": 15.0}
    model = cells.config_of(BENCH, CONFIG)
    assert traffic["clients"] == model["serving"]["engine"]["max_decode_batch"]  # = decode slots = state slots
    from perfbench.harness import schedule as sch

    longest = max(r.prompt_len + r.output_len for r in sch.closed_stream(traffic, 1))
    assert longest <= 5632 <= model["max_position_embeddings"]  # every request fits


@pytest.mark.parametrize("name", JOINED + list(OTHERS) + list(BROUGHT))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    entry = readings.check(BENCH, CELL, name, BROUGHT.get(name) or OTHERS.get(name) or readings.WANT[name])
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in BROUGHT:
        assert entry["layer"] == "kernels" and entry["workloads"].index(CELL) == 0
    else:  # a joined entry: the cells that were there come first
        assert len(entry["workloads"]) > 1 and entry["workloads"].index(CELL) > 0


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import jamba_controls as controls  # the tests' twin of the reference, with the wrong models
from perfbench.families import jamba as real
from perfbench.families.jamba import server

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def reference_logits(model, params, tokens, picks):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r})


def reference_logits_and_state(model, params, tokens, picks, ats):
    return controls.logits_at(model, params, tokens, picks, variant={logits!r}, ats=ats)


def reference_mamba(model, layer_params, u):
    buckets = model["serving"]["engine"]["prefill_buckets"]
    C, n2 = buckets[-1], max(1, int(buckets[-1] * server.TAIL_SHARE))
    return controls.mamba(model, layer_params, u, variant={mamba!r}, starts=(C, C + n2, C + n2 + 1),
                          padded={{C + n2: C - n2, C + n2 + 1: buckets[0] - 1}})


def reference_attention(model, layer_params, u):
    return controls.attention(model, layer_params, u, variant={attn!r})
'''

#: twin family -> the control its whole-model reference, its Mamba mixer and its attention compute
TWINS = {
    **{f"jamba_{v}": (v, None, None) for v in controls.VARIANTS},
    # the whole model as the reference has it, ONE mixer alone wrong: only that mixer's reading can tell
    **{f"jamba_mamba_{v}": (None, v, None) for v in ("inner_norm_left_out", "conv_bias_left_out", "carry_dropped",
                                                     "padded_row_advances")},
    "jamba_attn_rotary_added": (None, None, "rotary_added"),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, mamba, attn) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, mamba=mamba, attn=attn))
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
    config["correctness"].update(prompt_lens=[40, 33, 12], decode_steps=3, state_rel_tol=1e-4, state_deep_rel_tol=1e-4, tail_rel_tol=1e-4,
                                 tail_deep_rel_tol=1e-4,
                                 mamba_rel_tol=1e-4, attn_rel_tol=1e-4)
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 52,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("jamba", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + list(OTHERS)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert value["kv_bytes_per_token.mla"] == 1 * 2 * 16 * 4  # 1 attending layer of 5, K and V of 1 x 16 float32
    assert value["state_bytes_per_seq.kda"] == 4 * (4 * 128 + 3 * 128) * 4  # 4 Mamba layers: h [4, 128] and 3 inputs of 128
    # 4 clients on 4 slots; sampled once a second over a 2.5 s window: a loaded machine may catch 3 of the 4
    assert value["state_pool_peak_share.kda"] >= 75.0
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "kv" and end["state_layout"]["kind"] == "mamba1"
    pool = end["state_pool"]
    assert pool["slots"] == 4 and pool["assigned"] >= pool["released"] > 0
    assert end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
