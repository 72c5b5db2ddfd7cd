"""The benchmark's yardstick without a chip: the schedule, the estimators,
the peaks, each family's counts, the layer-metric readers, and
BENCHMARK.json against the files it names. Seconds, no cluster; JAX only
where a family's toy replica is built on the CPU for its ``engine_stats()``
(``toy_engine_stats`` of ``rehearsal.py``: the (entry, cell) pairs)."""

import json
import math
import os
import re
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

from rehearsal import toy_engine_stats  # noqa: E402

from perfbench import families  # noqa: E402
from perfbench.harness import cells, layer_metrics as lm, peaks, schedule as sch, stats  # noqa: E402

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# -- the schedule ---------------------------------------------------------------

@pytest.fixture(scope="module")
def paced():
    return cells.traffic_of("chat-paced")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_019])
def test_schedule_is_a_pure_function_of_file_and_seed(paced, seed):
    a = sch.paced_schedule(paced, seed, 51.0)
    b = sch.paced_schedule(paced, seed, 51.0)
    assert a == b
    assert [sch.prompt_tokens(r, 32768) for r in a[:3]] == [sch.prompt_tokens(r, 32768) for r in b[:3]]


def test_every_seed_offers_the_same_multiset_in_another_order(paced):
    runs = [sch.paced_schedule(paced, s, 51.0) for s in (1, 2, 2**31 + 5)]
    multisets = [sorted((r.prompt_len, r.output_len) for r in run if r.index >= 0) for run in runs]
    assert multisets[0] == multisets[1] == multisets[2]
    orders = [[(r.prompt_len, r.output_len) for r in run if r.index >= 0] for run in runs]
    assert orders[0] != orders[1] and orders[1] != orders[2]
    lead = [sorted((r.prompt_len, r.output_len) for r in run if r.index < 0) for run in runs]
    assert lead[0] == lead[1] == lead[2] and len(lead[0]) == paced["lead_in_requests"]


def test_one_arrival_in_every_slot(paced):
    slot = 1.0 / paced["rate_per_s"]
    run = sch.paced_schedule(paced, 5, 51.0)
    measured = [r for r in run if r.index >= 0]
    assert len(measured) == math.floor(51.0 * paced["rate_per_s"])
    for r in run:
        assert r.index * slot <= r.due_s < (r.index + 1) * slot
    assert all(0.0 <= r.due_s < 51.0 for r in measured)


def test_lengths_follow_the_file(paced):
    pairs = sch.length_multiset(paced["lengths"], 400)
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert prompts[0] >= 32 and prompts[-1] == 1024 and outputs[0] >= 16 and outputs[-1] == 256
    assert abs(statistics.median(prompts) - 256) <= 4 and abs(statistics.median(outputs) - 128) <= 2
    # the pairing does not depend on the run's seed, only on the file
    assert pairs == sch.length_multiset(paced["lengths"], 400)


@pytest.mark.parametrize("mix", ["chat-offline", "longprompt-batch"])
def test_closed_stream_is_rounds_of_one_fixed_multiset(mix):
    t = cells.traffic_of(mix)
    n = t["multiset_size"]
    a, b = sch.closed_stream(t, 3), sch.closed_stream(t, 2**31 + 9)
    assert len(a) == n * t["rounds"] == len(b)
    multiset = sorted(sch.length_multiset(t["lengths"], n))
    for run in (a, b):
        for r in range(t["rounds"]):  # every round offers the same work, in another order
            chunk = run[r * n : (r + 1) * n]
            assert sorted((q.prompt_len, q.output_len) for q in chunk) == multiset
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert [r.prompt_len for r in a[:n]] != [r.prompt_len for r in a[n : 2 * n]]
    assert all(r.prompt_len + r.output_len <= 4096 for r in a)
    assert len({r.token_seed for r in a}) == len(a)  # distinct prompts: no shared prefix


# -- estimators ---------------------------------------------------------------------

def test_harrell_davis_against_a_hand_worked_case():
    # n = 3, q = 0.5: Beta(2, 2), I_x(2, 2) = 3x^2 - 2x^3 -> weights 7/27, 13/27, 7/27
    assert stats.harrell_davis([1, 2, 6], 0.5) == pytest.approx((7 * 1 + 13 * 2 + 7 * 6) / 27)
    # n = 2, q = 0.5: Beta(1.5, 1.5) is symmetric -> the mean
    assert stats.harrell_davis([10, 20], 0.5) == pytest.approx(15.0)
    assert stats._betainc(2, 3, 0.4) == pytest.approx(0.5248)  # x^2 (6 - 8x + 3x^2)


def test_harrell_davis_is_a_p90_that_does_not_jump():
    base = [100.0] * 80 + [250.0] * 20
    moved = [100.0] * 81 + [250.0] * 19
    assert stats.percentile(base, 0.9) == 250.0
    hd = stats.harrell_davis(base, 0.9)
    assert 100.0 < hd <= 250.0
    assert abs(stats.harrell_davis(moved, 0.9) - hd) < 0.1 * 150.0  # a fraction of the step


def test_quartile_spread_uses_the_statistics_module_quartiles():
    values = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == pytest.approx((q3 - q1) / 12.5)


# -- peaks and operation counts ----------------------------------------------------

def test_peaks_table_and_unknown_kind():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert v5e["source"]
    with pytest.raises(KeyError, match="not in the benchmark's table"):
        peaks.peaks_for("cpu")


def test_parameter_and_flop_counts_match_the_issue():
    mistral = cells.config_of(BENCH, "mistral-7b-v0.3-16l")
    codestral = cells.config_of(BENCH, "codestral-22b-v0.1-8l-fsdp4")
    fam = families.of(mistral)
    assert fam is families.of(codestral) and fam.__name__ == "perfbench.families.dense_gqa"
    assert fam.counts.layer_params(mistral) == pytest.approx(218e6, rel=0.005)
    assert fam.param_count(mistral) == pytest.approx(3.76e9, rel=0.005)
    assert fam.kv_bytes_per_token(mistral) == 64 * 1024
    assert fam.counts.layer_params(codestral) == pytest.approx(390e6, rel=0.005)
    assert fam.param_count(codestral) == pytest.approx(3.52e9, rel=0.005)
    assert fam.counts.matmul_params(codestral) == pytest.approx(3.32e9, rel=0.005)
    # 6 x 3.32 B + attention = about 21 GFLOP a token at 2048
    assert fam.train_flops_per_token(codestral, 2048) == pytest.approx(20.5e9, rel=0.01)
    # serving: 2 per matmul parameter (3.62 B without the embedding table) + attention over the context
    assert fam.forward_flops_per_token(mistral, 0) == pytest.approx(2 * 3.624e9, rel=0.005)
    assert fam.forward_flops_per_token(mistral, 1024) - fam.forward_flops_per_token(mistral, 0) == 16 * 4 * 1024 * 4096
    # the table of peaks keeps no count of its own
    assert not [n for n in dir(peaks) if "param" in n or "flops_per_token" in n or "bytes_per_token" in n]


@pytest.mark.parametrize("name", ["mistral-7b-v0.3-16l", "codestral-22b-v0.1-8l-fsdp4"])
def test_param_count_agrees_with_the_program(name):
    from ray_tpu.models.llama import param_count

    model = cells.config_of(BENCH, name)
    fam = families.of(model)
    assert param_count(fam.model_config(model, max_seq_len=2048)) == fam.param_count(model)
    # a file's own overrides reach the program's config object; an unknown one is refused
    assert fam.model_config(model, max_seq_len=64, attention_impl="pallas").attention_impl == "pallas"
    with pytest.raises(TypeError):
        fam.model_config(model, max_seq_len=64, no_such_field=1)


# -- families ----------------------------------------------------------------------------

def test_every_family_present_exposes_the_whole_interface():
    assert "dense_gqa" in families.present()
    for name in families.present():
        fam = families.of({"family": name})
        assert all(hasattr(fam, member) for member in families.INTERFACE)
        assert isinstance(fam.TOY_SIZES, dict) and fam.TOY_SIZES


@pytest.mark.parametrize("config, names", [
    ({"hidden_size": 64}, "None"),
    ({"family": "no_such_family"}, "'no_such_family'"),
    ({"family": 7}, "7"),
])
def test_a_missing_or_unknown_family_is_an_exit_that_lists_those_present(config, names):
    with pytest.raises(SystemExit) as e:
        families.of(config)
    assert names in str(e.value) and "dense_gqa" in str(e.value)


@pytest.mark.parametrize("config", [{"hidden_size": 64}, {"family": "no_such_family"}])
def test_the_command_refuses_such_a_configuration_before_any_cluster(config, monkeypatch):
    import ray_tpu
    from perfbench import run as bench_run

    def no_cluster(*a, **k):
        raise AssertionError("a cluster was started for a configuration without a family")

    monkeypatch.setenv("JAX_PLATFORMS", "")
    monkeypatch.setattr(ray_tpu, "init", no_cluster)
    monkeypatch.setattr(cells, "config_of", lambda bench, name: dict(config))
    with pytest.raises(SystemExit, match="dense_gqa"):
        bench_run.main(["--workload", BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                        "--trace", "0"])


def test_a_family_that_lacks_a_member_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half_a_family.py").write_text("TOY_SIZES = {}\n")
    monkeypatch.setattr(families, "__path__", [*families.__path__, str(tmp_path)])
    with pytest.raises(SystemExit, match="lacks .*model_config"):
        families.of({"family": "half_a_family"})
    sys.modules.pop("perfbench.families.half_a_family", None)


# -- layer-metric readers --------------------------------------------------------------

def _observed():
    return lm.Observed(
        stats_start={"total_steps": 100, "scheduler": {"total_preempted": 1},
                     "prefix_cache": {"hits_total": 0, "queries_total": 10}},
        stats_end={"total_steps": 300, "scheduler": {"total_preempted": 4},
                   "prefix_cache": {"hits_total": 5, "queries_total": 30},
                   "device": {"peak_bytes_in_use": 14.5e9}},
        stats_samples=[{"blocks": {"used_blocks": u, "num_blocks": 100}} for u in (10, 40, 25)],
        series={"late_ms": [0.1 * i for i in range(101)], "client_ttft_ms": [50, 60, 70],
                "replica_ttft_ms": [40, 45, 50], "step_ms": [700, 702, 698]},
        scalars={"output_tokens": 4000.0, "train_tokens_per_s_steady": 24000.0, "flops_per_token": 20.5e9,
                 "peak_flops_per_s": 197e12, "chips": 4.0},
    )


@pytest.mark.parametrize("name, want", [
    ("tokens_per_engine_step.paced", 20.0),
    ("preemptions.batch", 3.0),
    ("prefix_hit_rate", 25.0),
    ("kv_pool_peak_share.batch", 40.0),
    ("peak_hbm_gb", 14.5),
    ("loadgen_late_p99_ms", 9.9),
    ("host_path_ttft_p50_ms", 15.0),
    ("train_step_ms", 700.0),
    ("train_mfu", 100.0 * 20.5e9 * 24000.0 / (4 * 197e12)),
])
def test_readers_on_worked_observations(name, want):
    assert lm.read(cells.layer_metric_spec(name), _observed()) == pytest.approx(want)


def test_a_reader_that_finds_nothing_returns_nothing():
    empty = lm.Observed()
    for m in BENCH["per_layer"]:
        assert lm.read(cells.layer_metric_spec(m["name"]), empty) is None
    assert lm.read_all({"x": cells.layer_metric_spec("train_mfu")}, empty) == {}
    with pytest.raises(ValueError, match="unknown layer-metric kind"):
        lm.read({"kind": "guess"}, empty)


# -- BENCHMARK.json against the contract and the files it names ---------------------------

def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    names += [w["name"] for w in BENCH["workloads"]] + [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metric_names = [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    assert len(set(metric_names)) == len(metric_names)
    for g in ("end_to_end", "per_layer"):
        for m in BENCH[g]:
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
            assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    assert all(w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def _may_be_cut(key, held, published):
    """Whether a configuration may list ``key`` under ``reduced`` at the
    value ``held``. Never a width. What one chip of a stated deployment holds
    of a layer may be its share (model-configs guide, section 4), down to the
    guide's floors: an eighth of the vocabulary, 8 routed experts a layer."""
    if key == "vocab_size":
        return 8 * held >= published
    if re.search(r"(_size|_dim|_rank|experts_per_tok)$", key):
        return False
    if re.search(r"(n_routed_experts|num_experts|num_local_experts)$", key):
        return held >= 8
    return True


@pytest.mark.parametrize("key, held, published, may", [
    ("num_hidden_layers", 12, 16, True),
    ("max_position_embeddings", 8192, 262144, True),
    ("vocab_size", 16384, 131072, True),          # an eighth: the floor itself
    ("vocab_size", 16383, 131072, False),
    ("n_routed_experts", 8, 64, True),            # Xing4's share of eight chips
    ("n_routed_experts", 4, 64, False),
    ("num_experts", 7, 64, False),
    ("num_local_experts", 8, 128, True),
    ("num_experts_per_tok", 8, 8, False),         # the widths, whatever the value
    ("hidden_size", 2048, 4096, False),
    ("moe_intermediate_size", 512, 1024, False),
    ("kv_lora_rank", 256, 512, False),
    ("qk_rope_head_dim", 32, 64, False),
])
def test_which_keys_a_configuration_may_cut(key, held, published, may):
    assert _may_be_cut(key, held, published) is may


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files_and_its_arrows(cell):
    w = cells.cell(BENCH, cell)
    config = cells.config_of(BENCH, w["config"])
    fam = families.of(config)  # the file's family resolves and is whole
    assert all(hasattr(fam, member) for member in families.INTERFACE)
    assert set(fam.TOY_SIZES) <= set(config), "toy sizes overlay keys the file has"
    traffic = cells.traffic_of(w["traffic"])
    assert traffic["kind"] in ("paced_open", "closed", "train_job")
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key in entry["reduced"]:
        assert config[key] != config["published"][key]
        assert _may_be_cut(key, config[key], config["published"][key]), "a width may never be cut, nor a share fall under its floor"
    e2e = {m["name"] for m in cells.metrics_of(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    for name in e2e:
        assert os.path.exists(os.path.join(cells.HERE, "end_to_end", f"{name}.json"))
    layer = cells.metrics_of(BENCH, cell, "per_layer")
    assert layer
    for m in layer:
        spec = cells.layer_metric_spec(m["name"])
        assert spec["kind"] in lm.READERS
        assert (spec["layer"], spec["unit"], spec["moves"]) == (m["layer"], m["unit"], m["moves"])
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which {cell} does not report"


# -- one entry a reader: a cell JOINS the entry that reads its counter, it brings no copy (PR 37) -----

PROSE = ("what", "why")


def _reader(entry):
    """What an entry reads and which way it points: its file without the
    prose, and the entry without its name and its cells. Two entries that
    agree in this are the SAME reader, whatever their names."""
    spec = cells.layer_metric_spec(entry["name"])
    return json.dumps([{k: v for k, v in spec.items() if k not in PROSE},
                       [entry[k] for k in ("unit", "better", "moves", "layer", "source")]], sort_keys=True)


def test_no_two_entries_are_the_same_reader_and_every_file_has_its_entry():
    """Until PR 37 a family brought a copy of every reader under a suffix of
    its own (63 of 128 entries were copies, and the contract's 128 were
    full). A later cell appends its NAME to the ``workloads`` of the entry
    that is there; an entry is new only where its reader is."""
    by_reader = {}
    for entry in BENCH["per_layer"]:
        by_reader.setdefault(_reader(entry), []).append(entry["name"])
    copies = [names for names in by_reader.values() if len(names) > 1]
    assert not copies, f"the same reader under several names (join the first entry's workloads instead): {copies}"
    files = sorted(f[: -len(".json")] for f in os.listdir(os.path.join(cells.HERE, "layer_metrics")))
    assert files == sorted(m["name"] for m in BENCH["per_layer"])  # every file one entry, every entry its file
    assert len(BENCH["per_layer"]) < 128  # the contract's limit: room is left for the next reader
    for entry in BENCH["per_layer"]:
        listed = entry.get("workloads", [])
        assert len(set(listed)) == len(listed) and set(listed) <= {w["name"] for w in BENCH["workloads"]}


PAIRS = [(m["name"], w["name"]) for w in BENCH["workloads"] for m in cells.metrics_of(BENCH, w["name"], "per_layer")]


@pytest.mark.parametrize("name, cell", PAIRS, ids=[f"{n}@{c}" for n, c in PAIRS])
def test_each_cell_an_entry_lists_reports_its_arrow_and_has_its_counter(name, cell):
    """A cell that joins an entry must be judged by the end-to-end metric the
    entry moves, and its program must keep the counter the entry digs for:
    ``engine_stats()`` of the family's replica at the toy sizes, or, in a
    training cell, what ``train_cell.run`` hands the readers."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    reported = {m["name"] for m in cells.metrics_of(BENCH, cell, "end_to_end")}
    assert entry["moves"] in reported, f"{name} moves {entry['moves']}, which {cell} does not report"
    spec = cells.layer_metric_spec(name)
    if spec["kind"] != "stats_delta":
        return
    w = cells.cell(BENCH, cell)
    if cells.traffic_of(w["traffic"])["kind"] == "train_job":
        stats = {"device": {"peak_bytes_in_use": 0}}  # perfbench/harness/train_cell.py::run
    else:
        stats = toy_engine_stats(w["config"])
    for path in (spec.get("key"), spec.get("per")):
        assert path is None or lm._dig(stats, path) is not None, f"{cell}'s engine_stats() has no {path}"


def test_every_file_under_paths_has_a_permitted_name():
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for path in BENCH["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), REPO)
                assert ok.match(rel) and len(rel) <= 200, rel
    assert all(not c.startswith("/") and ".." not in c for c in BENCH["command"])
    json.dumps(BENCH)
