"""The ``mellum`` family and its cell without a chip: the configuration file
against the catalog row and its ``BENCHMARK.json`` entry, the family's counts
against the program's at the configuration's sizes, every per-layer reading of
the cell against the ONE entry that reads it (``readings.py``), the new
counters' readers on worked snapshots, the rehearsal of ``swa-mixed-offline``
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
CELL = "swa-mixed-offline"
CONFIG = "mellum2-12b-a2.5b-ep4"
#: the readings of other cells this one JOINS: every ``.batch`` and ``.moe`` entry, the three start-up
#: stages, the held share of the assignments, and what a chunk and a decode launch read of the cache.
#: NOT ``moe_stacked_layers_share.moe``: its own test (PR 43) holds it to the four cells it named, and
#: on this model (layers unrolled, no scanned stack) it would read 0 as on OLMoE
JOINED = sorted(
    [m["name"] for m in BENCH["per_layer"]
     if m["name"].endswith((".batch", ".moe")) and m["name"] != "moe_stacked_layers_share.moe"]
    + ["replica_init_s", "param_init_s", "warmup_s", "moe_held_assignment_share.mla",
       "prefill_read_live_share.longdoc", "latent_flash_time_share.longdoc", "kv_bytes_per_token.mla"]
)
_POOL = {"kind": "stats_delta", "scale": 100.0}
#: this PR's own counters -> what each one's file must hold. ``.swa`` stays on them
NEW_COUNTERS = {
    "kv_window_pool_peak_share.swa": {**_POOL, "key": ["kv_pools", "window", "in_use"], "per": ["kv_pools", "window", "blocks"]},
    "kv_full_pool_peak_share.swa": {**_POOL, "key": ["kv_pools", "full", "in_use"], "per": ["kv_pools", "full", "blocks"]},
    "kv_window_released_share.swa": {**_POOL, "key": ["kv_pools", "window", "released_behind"],
                                     "per": ["kv_pools", "window", "taken"]},
    "kv_held_over_one_table_share.swa": {**_POOL, "key": ["kv_held", "held_block_layers"],
                                         "per": ["kv_held", "one_table_block_layers"]},
}
#: read from the DEVICE's operations in the trace: the CPU rehearsal's trace has host threads only
DEVICE_OPS = {"moe_ffn_time_share.moe", "decode_step_device_ms.batch", "prefill_step_device_ms.batch",
              "latent_flash_time_share.longdoc", "paged_attn_time_share.batch"}

_KINDS = ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 7
ROW = {  # the catalog row's config (model-configs guide), every key under its own name
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": _KINDS, "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum", "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False, "vocab_size": 98304, "use_sliding_window": True,
}
CUT = ["max_position_embeddings", "num_experts"]


# -- the configuration and the counts ------------------------------------------------

def test_the_configuration_holds_the_catalog_row_and_cuts_two_keys():
    model = cells.config_of(BENCH, CONFIG)
    differs = sorted(k for k, v in ROW.items() if k not in model or model[k] != v)
    assert differs == CUT == sorted(model["reduced"])
    assert model["published"] == {k: ROW[k] for k in CUT}
    assert (model["num_experts"], model["max_position_embeddings"]) == (16, 16384)
    # depth, vocabulary, the window and every width whole
    assert (model["num_hidden_layers"], model["vocab_size"], model["sliding_window"]) == (28, 98304, 1024)
    dep = model["deployment"]
    assert (dep["chips_sharing_each_layer"], dep["num_experts_total"], dep["held_experts"]) == (4, 64, [0, 16])
    assert model["family"] == "mellum"
    assert model["source"].endswith("Mellum2-12B-A2.5B-Instruct/blob/main/config.json")
    assert {"qk_norm", "window_edge", "mtp", "rotary_pairing", "norm_weights", "intermediate_size"} <= set(model["assumed"])
    assert model["sizes"] and model["serving"]["num_blocks_arithmetic"] and model["correctness"]["reason"]
    assert {"logit_rel_tol", "expert_ffn_rel_tol", "window_attn_rel_tol", "full_attn_rel_tol"} <= set(model["correctness"])
    assert max(model["correctness"]["prompt_lens"]) > 4 * model["sliding_window"]  # the long one has slid for chunks
    engine = model["serving"]["engine"]
    assert (engine["decode_buckets"], engine["max_decode_batch"], engine["block_size"]) == ([64], 64, 16)
    assert engine["prefill_buckets"] == [256, 1024] and engine["prefix_cache_enabled"] is False
    # the window pool is the engine's to size (every slot's window of 65 blocks and two chunks
    # beside them): the file names the pool that keeps a sequence whole and no other
    assert set(engine) == {"num_blocks", "block_size", "prefill_buckets", "decode_buckets",
                           "max_decode_batch", "prefix_cache_enabled"}
    assert engine["num_blocks"] - 1 >= model["max_position_embeddings"] // 16
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == CUT and entry["source"] == model["source"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])  # no width is cut
    configs = [c["name"] for c in BENCH["configs"]]
    cells_ = [w["name"] for w in BENCH["workloads"]]
    assert configs.count(CONFIG) == 1 and configs.index("gigachat3.1-702b-a36b-ep16") < configs.index(CONFIG)
    assert cells_.count(CELL) == 1 and cells_.index("mtp-reason-offline") < cells_.index(CELL)
    assert all(len(x["why"]) <= 200 for x in (entry, cells.cell(BENCH, CELL)))


def test_counts_agree_with_the_program_at_the_configurations_sizes():
    from ray_tpu.models import llama

    model = cells.config_of(BENCH, CONFIG)
    fam = families.of(model)
    assert fam.__name__ == "perfbench.families.mellum"
    cfg = fam.model_config(model, max_seq_len=16384)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.mlp_hidden) == (2304, 32, 4, 128, 896)
    assert (cfg.n_layers, cfg.moe_experts, cfg.moe_held, cfg.moe_top_k, cfg.moe_renormalize) == (28, 64, (0, 16), 8, True)
    assert cfg.layer_windows == (1024, 1024, 1024, 0) * 7 and cfg.rope_theta == 500000.0
    assert (cfg.rope_scaling.factor, cfg.rope_scaling.original_max, cfg.rope_scaling.attention_factor) == (
        16.0, 8192, 1.2772588722239782)
    assert fam.param_count(model) == llama.param_count(cfg) == 3_826_319_616
    whole = {**model, "num_experts": 64}  # every expert held: the name's 12B
    assert fam.param_count(whole) == pytest.approx(12.15e9, rel=0.001)
    layout = llama.cache_layout(cfg, 16)
    assert fam.kv_bytes_per_token(model) == layout.bytes_per_token == 57_344
    groups = layout.describe()["groups"]
    assert groups == {"full": {"layers": 7, "keeps": "all", "bytes_per_token": 14_336},
                      "window": {"layers": 21, "keeps": 1024, "bytes_per_token": 43_008}}
    assert {g: (n, keeps) for g, (n, keeps) in fam.counts.group_layers(model).items()} == {"full": (7, 0), "window": (21, 1024)}
    assert layout.flat_blocks and layout.block_shape((4, 128)) == (64, 128)  # a block 64 rows of 128: whole tiles
    # a sequence far past the window holds all of its full rows and 65 blocks of window rows
    held = fam.kv_bytes_held(model, 16005)
    assert held == {"full": 1001 * 16 * 14_336, "window": 65 * 16 * 43_008}
    assert sum(fam.kv_bytes_held(model, 900).values()) == 57 * 16 * 57_344  # inside the window: every layer keeps all
    # a token's context costs the 7 full layers' pairs past the window, all 28 layers' inside it
    per_pair = 2 * 2 * 32 * 128
    assert fam.forward_flops_per_token(model, 5000) - fam.forward_flops_per_token(model, 4000) == 7 * per_pair * 1000
    assert fam.forward_flops_per_token(model, 1000) - fam.forward_flops_per_token(model, 0) == 28 * per_pair * 1000
    # the kernels' costs a layer: decode reads the live blocks, a window layer's from the first live one
    slots = [16000, 900, 1024, 1025]
    full, window = fam.paged_attn_cost(model, "full", slots), fam.paged_attn_cost(model, "window", slots)
    assert full["bytes"] - window["bytes"] == (1000 - 64) * 16 * 2048  # the long slot alone differs
    assert window["flops"] == per_pair * (1024 + 900 + 1024 + 1024)
    chunk_full, chunk_window = fam.chunk_attn_cost(model, "full", 8192, 1024), fam.chunk_attn_cost(model, "window", 8192, 1024)
    assert chunk_full["flops"] == per_pair * sum(range(8193, 9217)) and chunk_window["flops"] == per_pair * 1024 * 1024
    with pytest.raises(ValueError, match="held"):
        fam.model_config({**model, "num_experts": 32}, max_seq_len=64)
    with pytest.raises(ValueError, match="kinds"):
        fam.model_config({**model, "layer_types": ["chunked_attention"] * 28}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


def test_the_traffic_is_the_issues_and_every_request_fits_the_table():
    cell = cells.cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "mixed-offline", 1)
    traffic = cells.traffic_of("mixed-offline")
    assert (traffic["kind"], traffic["clients"], traffic["multiset_size"], traffic["rounds"]) == ("closed", 64, 32, 24)
    assert traffic["lengths"]["prompt"] == {"dist": "lognormal", "median": 1536, "sigma": 1.2, "clip": [128, 15360]}
    assert traffic["lengths"]["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6, "clip": [64, 768]}
    assert traffic["lengths"]["pairing_seed"] == 23 and traffic["lead_in_seconds"] == 20.0
    pairs = sch.length_multiset(traffic["lengths"], 32)
    prompts = sorted(p for p, _ in pairs)
    assert (prompts[0], prompts[-1], round(sum(prompts) / 32)) == (128, 15360, 2826)
    assert (sum(p < 1024 for p in prompts), sum(p > 4096 for p in prompts), sum(p > 8192 for p in prompts)) == (12, 7, 3)
    assert max(r.prompt_len + r.output_len for r in sch.closed_stream(traffic, 1)) <= 16384  # every request fits
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"].count(CELL) == 1 and e2e["workloads"].index("mtp-reason-offline") < e2e["workloads"].index(CELL)


# -- the metric files -------------------------------------------------------------------

def test_the_cell_joins_the_entries_that_read_its_counters_and_brings_four():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert not set(JOINED) & set(NEW_COUNTERS) and len(set(JOINED)) == len(JOINED)
    assert all(names.count(name) == 1 for name in JOINED + list(NEW_COUNTERS))
    assert names.index("moe_stacked_layers_share.moe") < min(names.index(name) for name in NEW_COUNTERS)
    listed = {m["name"] for m in cells.metrics_of(BENCH, CELL, "per_layer")}
    assert listed == set(JOINED) | set(NEW_COUNTERS) | {"peak_hbm_gb"}  # the one entry without a list


@pytest.mark.parametrize("name", JOINED + list(NEW_COUNTERS))
def test_each_reading_of_the_cell_has_one_entry_whose_file_reads_what_is_expected(name):
    want = NEW_COUNTERS.get(name) or readings.WANT.get(name)
    if want is None:  # an entry no earlier family's test pinned: its own file is what it must hold
        spec = cells.layer_metric_spec(name)
        want = {k: spec[k] for k in readings.HELD if k in spec}
    entry = readings.check(BENCH, CELL, name, want)
    start_up = name in ("replica_init_s", "param_init_s", "warmup_s")
    assert entry["moves"] == ("setup_s" if start_up else "serve_tokens_per_s")
    if name in NEW_COUNTERS:
        assert entry["workloads"] == [CELL] and entry["layer"] == "KV cache manager"
    else:  # a joined entry: the cells that were there come first
        assert entry["workloads"].index("moe-chat-offline" if "moe-chat-offline" in entry["workloads"]
                                        else "mla-longdoc-batch") < entry["workloads"].index(CELL)


def _snapshot(window_in_use, full_in_use, taken, released, held, one_table):
    return {"kv_pools": {"full": {"blocks": 1000, "keeps": "all", "in_use": full_in_use, "peak_in_use": 900,
                                  "taken": 5000, "released_behind": 0},
                         "window": {"blocks": 400, "keeps": 1024, "in_use": window_in_use, "peak_in_use": 390,
                                    "taken": taken, "released_behind": released}},
            "kv_held": {"launches": 10, "held_block_layers": held, "one_table_block_layers": one_table}}


@pytest.mark.parametrize("name, want", [
    ("kv_window_pool_peak_share.swa", 100.0 * 380 / 400),
    ("kv_full_pool_peak_share.swa", 100.0 * 870 / 1000),
    ("kv_window_released_share.swa", 100.0 * (700 - 100) / (1200 - 200)),
    ("kv_held_over_one_table_share.swa", 100.0 * (50_000 - 10_000) / (130_000 - 30_000)),
])
def test_the_new_counters_readers_on_worked_snapshots(name, want):
    ob = lm.Observed(stats_start=_snapshot(300, 700, 200, 100, 10_000, 30_000),
                     stats_end=_snapshot(350, 800, 1200, 700, 50_000, 130_000),
                     stats_samples=[_snapshot(300, 700, 200, 100, 0, 0), _snapshot(380, 870, 600, 300, 0, 0),
                                    _snapshot(350, 800, 1200, 700, 0, 0)])
    assert lm.read(cells.layer_metric_spec(name), ob) == pytest.approx(want)
    # an engine_stats() without these counters (a parent checkout), or of a cache with one group
    # (every other configuration: one pool "all", no kv_held): nothing is read, nothing raises
    older = lm.Observed(stats_start={"total_steps": 1}, stats_end={"total_steps": 2})
    assert lm.read(cells.layer_metric_spec(name), older) is None
    one = {"kv_pools": {"all": {"blocks": 64, "in_use": 3, "taken": 9, "released_behind": 0}}}
    assert lm.read(cells.layer_metric_spec(name), lm.Observed(stats_start=one, stats_end=one, stats_samples=[one])) is None


# -- the rehearsal of the cell, and of a wrong reference -----------------------------------

TWIN = '''
import mellum_controls as controls  # the wrong models, each a change of the reference's DATA
from perfbench.families import mellum as real

TOY_SIZES = dict(real.TOY_SIZES)
model_config, server_class, train_program = real.model_config, real.server_class, real.train_program
param_count, kv_bytes_per_token = real.param_count, real.kv_bytes_per_token
forward_flops_per_token, train_flops_per_token = real.forward_flops_per_token, real.train_flops_per_token
reference_loss = real.reference_loss


def _as(model, variant):
    return controls.wrong_model(model, variant, 8) if variant else model


def reference_logits(model, params, tokens, picks):
    return real.reference_logits(_as(model, {logits!r}), params, tokens, picks)


def reference_expert_ffn(model, layer_params, h):
    return real.reference_expert_ffn(_as(model, {ffn!r}), layer_params, h)


def reference_attention(model, layer_params, h, kind):
    return real.reference_attention(_as(model, {attention!r}), layer_params, h, kind)
'''

#: twin family -> the control of its whole-model reference, of its expert FFN's and of its one layer's
TWINS = {
    "mellum_no_window": ("no_window", None, None),
    "mellum_yarn_left_out": ("no_yarn", None, None),
    "mellum_gates_not_renormalised": ("not_renormalised", None, None),
    # the whole model as the reference has it, ONE reading alone wrong: only that reading can tell
    "mellum_window_a_block_late_in_one_layer": (None, None, "window_plus_block"),
    "mellum_one_rope_in_one_layer": (None, None, "yarn_everywhere"),
    "mellum_ffn_not_renormalised": (None, "not_renormalised", None),
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
    out = serve_cell.run(
        config=config, traffic=rehearsal.tiny_traffic(cell["traffic"]), seed=2**31 + 44,
        seconds=2.5, trace=trace, t_start=time.monotonic(),
        layer_specs=bench_run.layer_specs_of(BENCH, CELL), work_dir=str(tmp_path), require_tpu=False,
    )
    assert out["failed"] == 0 and out["attempted"] > 0
    return cell, out


def test_the_rehearsal_of_the_cell_prints_every_reading(cluster, tmp_path):
    cell, out = _rehearse("mellum", tmp_path, trace=True)
    assert out["correct"] is True
    line = bench_run.result_line(BENCH, cell, out, True)
    printed = set(line["metrics"])
    assert set(JOINED + list(NEW_COUNTERS)) - DEVICE_OPS <= printed
    assert "peak_hbm_gb" in printed  # no workloads key: every cell reports it
    value = {k: v["value"] for k, v in line["metrics"].items()}
    # prompts of 8-60 under a window of 16 in blocks of 8: windows slide, blocks come back
    assert 0 < value["kv_window_released_share.swa"] <= 100 and 25 < value["kv_held_over_one_table_share.swa"] < 100
    assert 0 < value["kv_window_pool_peak_share.swa"] <= 100 and 0 < value["kv_full_pool_peak_share.swa"] <= 100
    assert value["moe_held_assignment_share.mla"] == pytest.approx(50.0, abs=15)  # 4 of 8 held
    assert value["recompiles_in_window.moe"] == 0.0 and value["preemptions.batch"] == 0.0
    end = out["observed"].stats_end
    assert end["kv_layout"]["kind"] == "kv" and set(end["kv_layout"]["groups"]) == {"full", "window"}
    assert end["kv_pools"]["window"]["keeps"] == 16 and end["prefix_cache"]["enabled"] is False
    e2e = bench_run.result_line(BENCH, cell, out, False)
    assert set(e2e["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("family", sorted(TWINS))
def test_a_twin_whose_reference_is_another_model_reads_not_correct(cluster, tmp_path, family):
    _, out = _rehearse(family, tmp_path, trace=False)
    assert out["correct"] is False
