"""The seam a ``model_config`` PR goes through, held open: the benchmark as
such a PR would leave it (one configuration, one cell, the cell's name in
``serve_tokens_per_s``'s ``workloads`` and in those of three per-layer entries
whose counters its program has, and two per-layer metrics of its own that NO
entry reads yet, each APPENDED at the end of its list, as the driver demands)
must pass every test of this folder that reads the lists of ``BENCHMARK.json``.
A cell JOINS the entry that reads its counter (PR 37): a copy of an entry that
is there under a suffix of the cell's own fails the guard of
``test_perfbench_yardstick.py``.

PR 33 was refused because two tests held PR 31's entries to the LAST place of
``configs`` and ``workloads``: appended to, the tests failed; with the new
entries put before Xing4's the tests passed and the driver read a moved
workload. A test here holds what its PR added RELATIVE to what was there
(after a named earlier entry; membership; once), never a last place, a whole
list or a count (``perfbench/README.md``, "Adding a family", step 6).

Every ``test_perfbench_*.py`` beside this file is found by its name, so a file
that a later PR adds is on trial without an edit here. Each is loaded under
another name with ``cells.load_json`` answering for the appended benchmark
and its new files, and every test function of it that reads the lists, starts
no rehearsal and asks for no fixture is called (``pytest.mark.parametrize`` is
unrolled). JSON and file reads only."""

import copy
import glob
import importlib.util
import inspect
import itertools
import os
import sys
import traceback

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from perfbench import families  # noqa: E402
from perfbench.harness import cells  # noqa: E402

FILES = sorted(f for f in glob.glob(os.path.join(HERE, "test_perfbench_*.py"))
               if os.path.abspath(f) != os.path.abspath(__file__))
CONFIG, CELL, FAMILY, SUFFIX = "appended-olmoe-12l-v8", "appended-longprompt-batch", "appended_twin", "appended"
#: a family outside ``perfbench/`` (as ``test_perfbench_families.py::twins`` makes them): OLMoE's members
TWIN = "from perfbench.families.olmoe import *  # noqa: F401,F403\n"
#: the entries the throwaway cell JOINS (its name at the end of each one's ``workloads``): OLMoE's program has their counters
JOINS = ("prefill_step_device_ms.batch", "tokens_per_engine_step.batch", "moe_rows_per_expert.moe")
#: the per-layer metrics it brings under its own suffix, readings no entry has -> (``better``, the file)
NEW_METRICS = {
    f"steps_with_prefill_share.{SUFFIX}": ("higher", {
        "layer": "engine scheduler", "unit": "%", "moves": "serve_tokens_per_s", "kind": "stats_delta", "reduce": "ratio",
        "key": ["scheduler", "steps_with_prefill_and_decode"], "per": ["total_steps"], "scale": 100.0}),
    f"prefill_launches.{SUFFIX}": ("lower", {
        "layer": "model runner", "unit": "launches", "moves": "serve_tokens_per_s", "kind": "stats_delta", "reduce": "delta",
        "key": ["prefill_width", "launches"]}),
}
#: what a family brought before PR 37: the same reader as an entry that is there, under a suffix of its own
TWIN_OF = "decode_step_device_ms.batch"
#: the last name of each list when this file was written (PR 34): where PR 33 put its entries BEFORE
LAST_THEN = {"configs": "xing4.0-29b-a4b-ep8", "workloads": "mla-longdoc-batch",
             "serve_tokens_per_s": "moe-chat-offline", "per_layer": "prefill_read_live_share.longdoc"}
#: what a test that starts a rehearsal, a cluster or a process names
STARTS_SOMETHING = ("rehearsal.", "bench_run.", "subprocess")


def _appended(bench, at_the_end=True, twin=False):
    """``bench`` as a ``model_config`` PR would leave it, and the files that
    PR would add (path -> content). ``at_the_end`` false: each new entry put
    BEFORE the entry ``LAST_THEN`` names, which the driver refuses as a moved
    entry (wherever later PRs have appended theirs since). ``twin``: it also
    brings a copy of ``TWIN_OF`` under its own suffix instead of joining it."""
    bench = copy.deepcopy(bench)

    def put(which, items, new):
        names = [item if isinstance(item, str) else item["name"] for item in items]
        items.insert(len(items) if at_the_end else names.index(LAST_THEN[which]), new)

    old = next(c for c in bench["configs"] if c["name"] == "olmoe-1b-7b-0125-12l")
    model = cells.config_of(bench, old["name"])
    # the guide's third cut beside depth: an eighth of the vocabulary held here
    model.update(family=FAMILY, vocab_size=model["vocab_size"] // 8)
    model["published"]["vocab_size"] = 8 * model["vocab_size"]
    model["reduced"]["vocab_size"] = "an eighth of the vocabulary: this chip's slice"
    file = f"perfbench/configs/{CONFIG}.json"
    files = {os.path.join(cells.ROOT, file): model}
    put("configs", bench["configs"], {**old, "name": CONFIG, "file": file, "reduced": sorted(model["reduced"])})
    put("workloads", bench["workloads"], {"name": CELL, "config": CONFIG, "traffic": "longprompt-batch", "chips": 1,
                                          "why": "a throwaway cell: what a model_config PR appends"})
    put("serve_tokens_per_s", next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"], CELL)
    for name in JOINS:
        next(m for m in bench["per_layer"] if m["name"] == name)["workloads"].append(CELL)
    new = {name: ({"unit": spec["unit"], "better": better, "source": "program_counter", "layer": spec["layer"],
                   "moves": spec["moves"]}, spec) for name, (better, spec) in NEW_METRICS.items()}
    if twin:
        entry = next(m for m in bench["per_layer"] if m["name"] == TWIN_OF)
        new[TWIN_OF.replace(".batch", f".{SUFFIX}")] = (
            {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}, cells.layer_metric_spec(TWIN_OF))
    for name, (entry, spec) in new.items():
        put("per_layer", bench["per_layer"], {"name": name, **entry, "workloads": [CELL]})
        files[os.path.join(cells.HERE, "layer_metrics", f"{name}.json")] = spec
    files[os.path.join(cells.ROOT, "BENCHMARK.json")] = bench
    return files


@pytest.fixture
def appended(tmp_path, monkeypatch):
    """``install(at_the_end)``: from then on ``cells`` reads the appended
    benchmark and its new files, and the throwaway family is importable."""
    portion = tmp_path / "perfbench" / "families"
    portion.mkdir(parents=True)
    (portion / f"{FAMILY}.py").write_text(TWIN)
    monkeypatch.setattr(families, "__path__", [*families.__path__, str(portion)])
    monkeypatch.setattr(sys, "path", list(sys.path))  # the files under trial prepend to it as they load
    read = cells.load_json

    listdir = os.listdir

    def install(at_the_end, twin=False):
        monkeypatch.setattr(cells, "load_json", read)  # a second call starts from the tree's own files again
        files = _appended(cells.benchmark(), at_the_end, twin)
        monkeypatch.setattr(cells, "load_json", lambda path: copy.deepcopy(files[path]) if path in files else read(path))
        # the guard also lists the folder of readers: the new files are "in" it
        folder = os.path.join(cells.HERE, "layer_metrics")
        added = [os.path.basename(path) for path in files if os.path.dirname(path) == folder]
        monkeypatch.setattr(os, "listdir", lambda path=".": listdir(path) + (added if path == folder else []))

    yield install
    sys.modules.pop(f"perfbench.families.{FAMILY}", None)


def _cases(fn):
    """The keyword arguments of each call ``pytest`` would make of ``fn``,
    or nothing where it asks for a fixture."""
    axes = []
    for mark in getattr(fn, "pytestmark", []):
        if mark.name != "parametrize":
            continue
        names, values = mark.args[:2]
        names = [n.strip() for n in names.split(",")] if isinstance(names, str) else list(names)
        values = [v.values if isinstance(v, type(pytest.param())) else v for v in values]
        axes.append([dict(zip(names, v if len(names) > 1 else [v])) for v in values])
    calls = [{k: v for part in combo for k, v in part.items()} for combo in itertools.product(*axes)]
    wanted = set(inspect.signature(fn).parameters)
    return [kwargs for kwargs in calls if set(kwargs) == wanted]


def _failures(path):
    """Load the test file under another name and call what reads the lists;
    ``(how many calls were made, [the failures, one line each])``."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"appended_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    made, failed = 0, []
    for name, fn in vars(module).items():
        if not (name.startswith("test_") and inspect.isfunction(fn) and fn.__module__ == module.__name__):
            continue
        source = inspect.getsource(fn)
        if not ("BENCH" in source or "cells." in source) or any(word in source for word in STARTS_SOMETHING):
            continue
        for i, kwargs in enumerate(_cases(fn)):
            made += 1
            try:
                fn(**kwargs)
            except Exception as e:  # noqa: BLE001 - whatever a test raises is that test's failure
                line = traceback.extract_tb(e.__traceback__)[-1].lineno
                failed.append(f"{stem}.py::{name}[{i}] line {line}: {type(e).__name__} {e}".strip())
    return made, failed


@pytest.mark.parametrize("path", FILES, ids=lambda p: os.path.basename(p)[len("test_perfbench_"):-len(".py")])
def test_a_configuration_and_a_cell_appended_at_the_ends_pass(appended, path):
    appended(at_the_end=True)
    bench = cells.benchmark()
    assert (bench["configs"][-1]["name"], bench["workloads"][-1]["name"]) == (CONFIG, CELL)
    assert [m["name"] for m in bench["per_layer"][-len(NEW_METRICS):]] == list(NEW_METRICS)
    joined = [m for m in bench["per_layer"] if m["name"] in JOINS]
    assert len(joined) == 3 and all(m["workloads"][-1] == CELL and len(m["workloads"]) > 1 for m in joined)
    assert {m["name"] for m in cells.metrics_of(bench, CELL, "per_layer")} == {*JOINS, *NEW_METRICS, "peak_hbm_gb"}
    _, failed = _failures(path)
    assert not failed, "\n".join(failed)


def test_the_trial_calls_tests_and_an_entry_put_before_one_that_was_there_fails(appended):
    """The repair has not simply let go: the place of what was there is still
    held, and the trial above does call tests (it is no empty loop)."""
    appended(at_the_end=True)
    assert sum(_failures(path)[0] for path in FILES) >= 100
    appended(at_the_end=False)
    for which, new in (("configs", CONFIG), ("workloads", CELL)):
        names = [item["name"] for item in cells.benchmark()[which]]
        assert names.index(new) + 1 == names.index(LAST_THEN[which])
    failed = [line for path in FILES for line in _failures(path)[1]]
    assert failed, "entries put before ones that were there were accepted by every test"


def test_a_copy_of_an_entry_that_is_there_fails_the_guard(appended):
    """What filled the contract's 128 entries by PR 35: the same reader
    under a suffix of the cell's own. The guard names it; joined, it passes."""
    yardstick = os.path.join(HERE, "test_perfbench_yardstick.py")
    appended(at_the_end=True, twin=True)
    failed = _failures(yardstick)[1]
    assert len(failed) == 1 and "test_no_two_entries_are_the_same_reader" in failed[0], failed
    assert TWIN_OF in failed[0] and TWIN_OF.replace(".batch", f".{SUFFIX}") in failed[0]
    appended(at_the_end=True)
    assert not _failures(yardstick)[1]
