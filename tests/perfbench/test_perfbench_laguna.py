"""The ``laguna`` family and its cell without a chip: the configuration file
against the catalog row and its ``BENCHMARK.json`` entry, the family's counts
against the program's at the configuration's sizes, every per-layer reading of
the cell against the ONE entry that reads it (``readings.py``), the two new
readers on worked snapshots, the rehearsal of ``gated-swa-reason-offline``
printing every one of those readings that needs no device operation, and twin
families whose reference is another model reading ``correct`` false. No number
printed here is a speed.

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
from perfbench.harness import schedule as sch  # noqa: E402

BENCH = cells.benchmark()
CELL = "gated-swa-reason-offline"
CONFIG = "laguna-xs.2-33b-a3b-ep16"
#: the readings of other cells this one JOINS, by name (a literal: what a later PR names is its own test's)
JOINED = [f"{n}.batch" for n in (
    "tokens_per_engine_step", "preemptions", "prefill_step_device_ms", "decode_step_device_ms", "device_idle_share",
    "kv_pool_peak_share", "step_host_serial_ms", "step_schedule_ms", "step_sample_ms", "step_emit_ms", "step_launch_ms",
    "step_device_wait_ms", "step_readback_ms", "decode_table_width_tokens", "decode_gather_live_share",
    "wakes_after_launch_share", "wake_hold_ms", "paged_attn_time_share", "step_schedule_drain_ms",
    "step_schedule_admit_ms", "step_schedule_plan_ms", "step_launch_rows_ms", "step_launch_inputs_ms",
    "step_launch_call_ms", "step_readback_logits_ms", "step_emit_commit_ms", "step_emit_deliver_ms", "step_longest_ms",
    "decode_ahead_share", "step_host_offcpu_ms", "step_gil_offcpu_ms", "step_launch_call_offcpu_ms", "stalled_step_ms",
    "stalled_step_device_wait_share", "device_ready_on_arrival_share",
)] + [f"{n}.moe" for n in (
    "recompiles_in_window", "moe_experts_touched_share", "moe_load_imbalance", "moe_rows_per_expert",
    "moe_ffn_time_share", "moe_rows_per_expert_prefill", "step_readback_loads_ms",
)] + ["replica_init_s", "param_init_s", "warmup_s", "moe_held_assignment_share.mla", "kv_bytes_per_token.mla",
      "latent_flash_time_share.longdoc", "prefill_read_live_share.longdoc"]
#: this PR's own readers -> what each one's file must hold. ``.swa`` stays on them
NEW = {
    "attn_window_time_share.swa": {"kind": "device_trace", "name_regex": "^(paged_attn|latent_flash)_window"},
    "decode_window_read_share.swa": {"kind": "stats_delta", "key": ["decode_width", "window_read_tokens"],
                                     "per": ["decode_width", "gathered_tokens"], "scale": 100.0},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch",
              "latent_flash_time_share.longdoc", "paged_attn_time_share.batch", "attn_window_time_share.swa"}

_KINDS = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 10
ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
    "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                           "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": _KINDS, "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
}
CUT = ["max_position_embeddings", "num_experts"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_two_keys():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_experts"], model["max_position_embeddings"]) == (16, 8192)
    # depth, vocabulary, the window and every width whole
    assert (model["num_hidden_layers"], model["vocab_size"], model["sliding_window"]) == (40, 100352, 512)
    assert (model["hidden_size"], model["head_dim"], model["intermediate_size"], model["moe_intermediate_size"]) == (
        2048, 128, 8192, 512)
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["num_experts_total"], dep["held_experts"]) == (16, 256, [0, 16])
    assert dep["rows_per_held_expert_per_decode_step"] == {"here": 1, "deployed": 16}
    assert model["family"] == "laguna" and model["source"].endswith("poolside/Laguna-XS.2/blob/main/config.json")
    # what the published config leaves to the family's convention is said, with the evidence, and is a key the
    # reference reads (so that a control can change it)
    assert {"gating", "router", "shared_expert", "qk_norm", "rotary_pairing", "window_edge", "hidden_act"} <= set(model["assumed"])
    assert "33.44 B" in model["assumed"]["gating"] and "34.07 B" in model["assumed"]["gating"]
    assert (model["scoring_func"], model["norm_topk_prob"]) == ("sigmoid", True)
    assert model["sizes"] and model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "expert_ffn_rel_tol", "window_attn_rel_tol", "full_attn_rel_tol"} <= set(model["correctness"])
    # the long prompt's window table slides inside its prefill: chunks twice the window
    engine = model["serving"]["engine"]
    assert max(model["correctness"]["prompt_lens"]) > 4 * max(engine["prefill_buckets"]) > 8 * model["sliding_window"] - 1
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["block_size"]) == ([32], 32, 16)
    assert engine["prefill_buckets"] == [256, 1024] and engine["prefix_cache_enabled"] is False
    assert set(engine) == {"num_blocks", "block_size", "prefill_buckets", "decode_buckets",
                           "max_decode_batch", "prefix_cache_enabled"}
    assert engine["num_blocks"] - 1 >= model["max_position_embeddings"] // 16
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])  # no width is cut
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("ai21-jamba2-3b") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("ssm-reason-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))
    assert "1 row a held expert" in cells.cell(BENCH, CELL)["why"]  # the expert load, said


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import llama

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.laguna"
    cfg = fam.model_config(model, max_seq_len=8192)
    full, window = cfg.kind_of(0), cfg.kind_of(1)
    assert cfg.kinds == (full, window) and [cfg.kind_of(l) is not None for l in (0, 39)]
    assert (full.window, full.n_heads, full.rope_theta, full.rotary_dim) == (0, 48, 500000.0, 64)
    assert (full.rope_scaling.factor, full.rope_scaling.original_max, full.rope_scaling.beta_fast,
            full.rope_scaling.attention_factor) == (64.0, 4096, 64.0, 1.4158883083359672)
    assert (window.window, window.n_heads, window.rope_theta, window.rotary_dim, window.rope_scaling) == (
        512, 64, 10000.0, 0, None)
    assert cfg.layer_windows == (0, 512, 512, 512) * 10
    assert (cfg.dim, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden, cfg.dense_mlp_hidden, cfg.moe_shared_hidden) == (
        2048, 8, 128, 512, 8192, 512)
    assert (cfg.n_layers, cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.dense_layers, cfg.attn_gate) == (
        40, 256, (0, 16), 8, (0,), True)
    assert (cfg.moe_scoring, cfg.moe_renormalize, cfg.moe_scale) == ("sigmoid", True, 2.5)
    assert fam.param_count(model) == llama.param_count(cfg) == 3_998_582_784
    whole = {**model, "num_experts": 256}  # every expert held: the published 33.4B
    assert fam.param_count(whole) == 33_442_596_864
    # a gate a channel instead of a head would be 34.07 B: the count is the evidence for the head
    a_channel = sum(2048 * h * 127 for h in model["num_attention_heads_per_layer"])
    assert round((fam.param_count(whole) + a_channel) / 1e9, 2) == 34.07
    c = fam.counts
    assert c.attention_params(model, 0) == 29_458_432 and c.attention_params(model, 1) == 37_879_808
    assert c.ffn_params(model, 0) == 50_331_648 and c.ffn_params(model, 1) == 16 * 3_145_728 + 3_145_728 + 524_288
    layout = llama.cache_layout(cfg, 16)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 163_840
    groups = layout.describe()["groups"]
    assert groups == {"full": {"layers": 10, "keeps": "all", "bytes_per_token": 40_960},
                      "window": {"layers": 30, "keeps": 512, "bytes_per_token": 122_880}}
    assert c.group_layers(model) == {"full": (10, 0), "window": (30, 512)}
    assert (c.group_heads(model, "full"), c.group_heads(model, "window")) == (48, 64)
    assert not layout.flat_blocks and layout.block_shape((8, 128)) == (16, 8, 128)  # 8 KV heads: whole tiles as they are
    # a sequence far past the window holds all of its full rows and 33 blocks of window rows (64.9 MB)
    held = fam.kv_bytes_held(model, 5005)
    assert held == {"full": 313 * 16 * 40_960, "window": 33 * 16 * 122_880}
    assert sum(fam.kv_bytes_held(model, 400).values()) == 25 * 16 * 163_840  # inside the window: every layer keeps all
    # a token's context costs the 10 full layers' pairs past the window, all 40 layers' inside it
    pair48, pair64 = 2 * 2 * 48 * 128, 2 * 2 * 64 * 128
    assert fam.forward_flops_per_token(model, 5000) - fam.forward_flops_per_token(model, 4000) == 10 * pair48 * 1000
    assert fam.forward_flops_per_token(model, 500) - fam.forward_flops_per_token(model, 0) == (10 * pair48 + 30 * pair64) * 500
    # the kernels' costs a layer: decode reads the live blocks, a window layer's from the first live one
    slots = [5000, 400, 512, 513]
    full_cost, window_cost = fam.paged_attn_cost(model, "full", slots), fam.paged_attn_cost(model, "window", slots)
    assert window_cost["flops"] == pair64 * (512 + 400 + 512 + 512) and full_cost["flops"] == pair48 * sum(slots)
    assert full_cost["bytes"] - window_cost["bytes"] == (313 - 33) * 16 * 4096 - 2 * 4 * 16 * 128 * 2
    chunk_full, chunk_window = fam.chunk_attn_cost(model, "full", 2048, 1024), fam.chunk_attn_cost(model, "window", 2048, 1024)
    assert chunk_full["flops"] == pair48 * sum(range(2049, 3073)) and chunk_window["flops"] == pair64 * 1024 * 512
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "num_experts": 32}, max_seq_len=64)
    with pytest.raises(ValueError, match="kinds"):
        fam.model_config({**model, "layer_types": ["chunked_attention"] * 40}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


def test_the_traffic_is_the_issues_and_every_request_fits_the_table():
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "reason-offline-32", 1)
    traffic, parent = cells.traffic_of("reason-offline-32"), cells.traffic_of("reason-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"], traffic["rounds"]) == ("closed", 32, 32, 8)
    # reason-offline.json's lengths and edges unchanged: the fifth model under them
    for key in ("lengths", "lead_in_seconds", "trace_seconds", "edge_grace_s", "kind", "rounds"):
        assert traffic[key] == parent[key], key
    assert traffic["lengths"]["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8, "clip": [256, 4096]}
    assert traffic["lengths"]["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "clip": [128, 1536]}
    pairs = sch.length_multiset(traffic["lengths"], 32)
    prompts = sorted(p for p, _ in pairs)
    assert (prompts[0], prompts[-1]) == (256, 4096) and sum(p > 1024 for p in prompts) == 16  # every second one crosses a chunk edge
    assert max(r.prompt_len + r.output_len for r in sch.closed_stream(traffic, 1)) <= 8192  # every request fits
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1 and e2e["workloads"].index("ssm-reason-offline") < e2e["workloads"].index(CELL)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_two():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(JOINED) & set(NEW) and len(set(JOINED)) == len(JOINED)
    assert all(names.count(name) == 1 for name in JOINED + list(NEW))
    assert names.index("device_ready_on_arrival_share.batch") < min(names.index(name) for name in NEW)
    listed = {m["name"] for m in cells.metrics_of(BENCH, CELL, "per_layer")}
    assert set(JOINED) | set(NEW) | {"peak_hbm_gb"} <= listed  # by membership: a later PR may read more of this cell
    # Mellum2's four pool readers hold their lists to that one cell (test_perfbench_mellum.py, not this
    # PR's to edit): this cell's pools are read from the same counters by hand until a benchmark PR opens them
    assert not {n for n in listed if n.startswith("kv_") and n.endswith(".swa")}


@pytest.mark.parametrize("name", JOINED + list(NEW))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    want = NEW.get(name) or readings.WANT.get(name)
    if want is None:  # an entry no earlier family's test pinned: its own file is what it must hold
        spec = cells.layer_metric_spec(name)
        want = {k: spec[k] for k in readings.HELD if k in spec}
    entry = readings.check(BENCH, CELL, name, want)
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW:
        assert entry["workloads"][0] == CELL and entry["layer"] in ("kernels", "model runner")
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("swa-mixed-offline") < entry["workloads"].index(CELL)


def _snapshot(launches, gathered, window_read):
    return {"decode_width": {"launches": launches, "width_tokens": 8192 * launches, "needed_tokens": 0,
                             "live_tokens": gathered - 100, "gathered_tokens": gathered,
                             "window_read_tokens": window_read}}


def test_the_new_counters_reader_on_worked_snapshots():
    spec = cells.layer_metric_spec("decode_window_read_share.swa")
    ob = lm.Observed(stats_start=_snapshot(10, 500_000, 240_000), stats_end=_snapshot(110, 5_500_000, 2_490_000))
    assert lm.read(spec, ob) == pytest.approx(100.0 * (2_490_000 - 240_000) / 5_000_000)
    # an engine_stats() without the counter (a parent checkout, or a cache of one group): nothing is read, nothing raises
    older = {"decode_width": {"launches": 1, "gathered_tokens": 7, "live_tokens": 5}}
    assert lm.read(spec, lm.Observed(stats_start=older, stats_end=older)) is None
    assert lm.read(spec, lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})) is None
    trace = cells.layer_metric_spec("attn_window_time_share.swa")
    assert (trace["reduce"], trace["layer"]) == ("ops_share_of_busy", "kernels")
    import re
    rx = re.compile(trace["name_regex"])
    assert rx.search("paged_attn_window.3") and rx.search("latent_flash_window.17")
    assert not rx.search("paged_attn.3") and not rx.search("latent_flash.17")
    both = re.compile(cells.layer_metric_spec("paged_attn_time_share.batch")["name_regex"])
    assert both.search("paged_attn_window.3") and both.search("paged_attn.3")  # the joined entries still read both kinds


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import laguna_controls as controls  # the wrong models, each a change of the reference's DATA
from perfbench.families import laguna as real

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def _as(model, variant):
    return controls.wrong_model(model, variant, 8) if variant else model


def reference_logits(model, params, tokens, picks):
    variant = {logits!r}
    return real.reference_logits(_as(model, variant), controls.wrong_params(model, params, variant), tokens, picks)


def reference_expert_ffn(model, layer_params, h):
    return real.reference_expert_ffn(_as(model, {ffn!r}), layer_params, h)


def reference_attention(model, layer_params, h, kind):
    variant = {attention!r}
    return real.reference_attention(_as(model, variant), controls.wrong_layer_params(model, layer_params, variant), h, kind)
'''

#: twin family -> the control of its whole-model reference, of its expert FFN's and of its one layer's
TWINS = {
    "laguna_gate_left_out": ("no_gate", None, None),
    "laguna_layer0_routed": ("layer0_routed", None, None),
    # the whole model as the reference has it, ONE reading alone wrong: only that reading can tell
    "laguna_gate_a_channel_in_one_layer": (None, None, "gate_a_channel"),
    "laguna_one_head_count_in_one_layer": (None, None, "one_head_count"),
    "laguna_all_rotated_in_one_layer": (None, None, "all_rotated"),
    "laguna_ffn_softmax_router": (None, "softmax_router", None),
    "laguna_ffn_shared_expert_left_out": (None, "no_shared_expert", None),
}


@pytest.fixture(scope="module")
def twins(tmp_path_factory):
    outside = tmp_path_factory.mktemp("outside")
    portion = outside / "perfbench" / "families"
    portion.mkdir(parents=True)
    for name, (logits, ffn, attention) in TWINS.items():
        (portion / f"{name}.py").write_text(TWIN.format(logits=logits, ffn=ffn, attention=attention))
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
    assert (config["num_experts"], config["deployment"]["num_experts_total"], config["sliding_window"]) == (4, 8, 16)
    assert config["sliding_window"] * 2 == max(config["serving"]["engine"]["prefill_buckets"])  # half a chunk, as at full size
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 56,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("laguna", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + list(NEW)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    # the CPU's gather reads every layer's table whole: the window groups' part is their share of the layers (3 of 5)
    assert value["decode_window_read_share.swa"] == pytest.approx(60.0)
    assert value["moe_held_assignment_share.mla"] == pytest.approx(50.0, abs=15)  # 4 of 8 held
    assert value["kv_bytes_per_token.mla"] == 5 * 2 * 2 * 16 * 4  # five layers' K and V rows of two heads of 16, float32
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "kv" and set(end["kv_layout"]["groups"]) == {"full", "window"}
    # prompts of 8-60 under a window of 16 and chunks of 32 in blocks of 8: windows slide, blocks come back
    window = end["kv_pools"]["window"]
    assert window["keeps"] == 16 and 0 < window["released_behind"] <= window["taken"]
    assert end["prefix_cache"]["enabled"] is False and end["moe"]["decode"]["expert_layers"] > 0
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
