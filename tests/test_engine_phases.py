"""The serving engine's own accounts (ISSUE 24): where a step's time goes
(``stats()["step_phases"]``), what a first token waited for
(``["request_stages"]``), what start-up cost (``["startup"]``), the same
phases as ``engine.<phase>`` spans in a ``jax.profiler`` trace, and the
``recompile`` event. CPU, tiny config: what is checked is the
book-keeping, never a speed.

``python tests/test_engine_phases.py`` prints the offset between the
``engine_step`` timeline events and the ``engine.schedule`` annotations of
the same steps in a profiler trace taken on whatever device JAX finds
(through ``chiprun``: the chip; the number in PERF.md section 7)."""

import glob
import logging
import os
import statistics
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, REPO)

jax = pytest.importorskip("jax")

from ray_tpu.inference.engine import STEP_PHASES, EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.serve_llm import LLMServer  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.observability import timeline  # noqa: E402

ENGINE = dict(
    num_blocks=64, block_size=8, prefill_buckets=(16, 32), decode_buckets=(4,),
    max_decode_batch=4,
)


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture
def engine(cfg, params):
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE)).start()
    try:
        yield eng
    finally:
        eng.stop()


def _traffic(eng, n=10, new_tokens=12):
    """Requests arriving while others decode: steps that carry a prefill
    chunk (one or two chunks a prompt), a decode batch, or both."""
    rids = []
    for i in range(n):
        rids.append(eng.submit(list(range(1, 14 + 5 * i)), max_new_tokens=new_tokens))
        time.sleep(0.002)
    return [list(eng.tokens(r, timeout=60)) for r in rids]


def _leaves(phases):
    return sum(phases[f"{name}_s"] for name in STEP_PHASES)


def test_step_account_closes_and_is_monotonic(engine):
    seen = [engine.stats()]
    while engine.stats()["total_steps"] < 50:
        _traffic(engine, n=6)
        seen.append(engine.stats())
    engine.wait_idle()
    seen.append(engine.stats())
    last = seen[-1]["step_phases"]
    assert set(last) == {f"{n}_s" for n in STEP_PHASES} | {
        "wall_s", "host_serial_s", "longest_wall_s", "longest_device_wait_s",  # the last two: ISSUE 38
    }
    assert last["wall_s"] > 0 and all(last[f"{n}_s"] > 0 for n in STEP_PHASES)
    # the account closes: the leaves sum to the loop's wall time
    assert _leaves(last) == pytest.approx(last["wall_s"], rel=0.02)
    assert last["host_serial_s"] == pytest.approx(
        last["wall_s"] - last["device_wait_s"] - last["loop_wait_s"], abs=1e-9
    )
    # emit's second half, the wake-ups held for the next launch (ISSUE 28),
    # is inside the account that closed above: every item went out somewhere
    wakes = seen[-1]["wakes"]
    assert wakes["items"] == wakes["after_launch"] + wakes["at_idle"] + wakes["direct"] > 0
    assert wakes["after_launch"] > 0 and wakes["direct"] == 0 and wakes["held_s"] > 0
    # the loop's wall time is the thread's life, not only its steps
    for a, b in zip(seen, seen[1:]):
        for group in ("step_phases", "request_stages", "wakes"):
            for key, value in a[group].items():
                # one step's reading, not a sum: a longer step may have waited less
                assert b[group][key] >= value or key == "longest_device_wait_s", (group, key)
        assert b["total_steps"] >= a["total_steps"]


def test_wall_time_follows_the_clock(engine):
    """An idle loop waits: its wall time grows with the clock, under
    ``loop_wait``, and ``host_serial_s`` does not."""
    _traffic(engine, n=2)
    engine.wait_idle()
    time.sleep(0.05)  # the last step settles after the scheduler ran dry
    a, t0 = engine.stats()["step_phases"], time.perf_counter()
    time.sleep(0.5)
    b, elapsed = engine.stats()["step_phases"], time.perf_counter() - t0
    assert b["wall_s"] - a["wall_s"] == pytest.approx(elapsed, abs=0.1)
    assert b["loop_wait_s"] - a["loop_wait_s"] > 0.8 * (b["wall_s"] - a["wall_s"])
    assert b["device_wait_s"] == a["device_wait_s"]
    assert b["host_serial_s"] - a["host_serial_s"] < 0.2 * (b["wall_s"] - a["wall_s"])


def test_direct_steps_close_the_account_too(cfg, params):
    """``step()`` without the loop (how tests and tools drive an engine)
    settles its own wall time."""
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE))
    rid = eng.submit(list(range(1, 30)), max_new_tokens=4)
    while eng.scheduler.has_work():
        assert eng.step()
    assert len(list(eng.tokens(rid, timeout=5))) == 4
    assert not eng.step()  # nothing to do: its time is schedule's
    p = eng.stats()["step_phases"]
    assert _leaves(p) == pytest.approx(p["wall_s"], rel=1e-6)
    assert p["loop_wait_s"] == 0.0 and p["device_wait_s"] > 0 and p["schedule_s"] > 0
    # and delivers what it committed before it returns, inside the account
    wakes = eng.stats()["wakes"]
    assert wakes["items"] == wakes["direct"] + wakes["after_launch"] == 5 and not eng._held


def test_runner_calls_off_the_loop_stay_out_of_the_step_account(engine):
    """The account is the step-loop thread's. Whoever else calls the
    runner (a check on the actor's thread, a tool) lands on the runner's
    own clock, so the loop's leaves still sum to its wall time."""
    _traffic(engine, n=2)
    engine.wait_idle()
    time.sleep(0.05)
    before = engine.stats()["step_phases"]
    runner = engine.runner
    row = [0] * runner.max_blocks_per_seq
    for _ in range(5):
        runner.prefill_chunk([1, 2, 3], row, 0)  # writes the null block only
        runner.decode([1], [0], [row], [1])
    after = engine.stats()["step_phases"]
    for name in ("launch", "device_wait", "readback"):
        assert after[f"{name}_s"] == before[f"{name}_s"]
        assert runner.clock.lap[name] > 0
    assert _leaves(after) == pytest.approx(after["wall_s"], rel=1e-6)


def test_request_account_sums_to_the_engines_ttft(engine):
    _traffic(engine, n=8)
    stages = engine.stats()["request_stages"]
    ttfts = [ttft for _at, ttft in engine._recent_ttfts]
    assert stages["first_tokens"] == len(ttfts) == 8
    parts = stages["queue_s"] + stages["prefill_wait_s"] + stages["prefill_run_s"]
    assert parts == pytest.approx(sum(ttfts), rel=1e-9)
    assert all(stages[k] >= 0 for k in stages)
    # one prefill chunk a step: requests that arrive together wait behind
    # each other's chunks, and every one of them runs its own
    assert stages["prefill_run_s"] > 0 and stages["prefill_wait_s"] > 0


def test_flight_recorder_gets_the_split(engine, monkeypatch):
    from ray_tpu.observability import slo

    monkeypatch.setattr(slo, "_RECORDER", slo.FlightRecorder(slow_slots=4))
    rid = engine.submit(list(range(1, 40)), max_new_tokens=3)
    list(engine.tokens(rid, timeout=60))
    engine.wait_idle()
    (entry,) = [e for e in slo.flight_recorder().snapshot() if e["request_id"] == rid]
    stages = entry["stages"]
    assert {"queue", "prefill", "prefill_wait", "prefill_run", "decode"} <= set(stages)
    assert stages["queue"] + stages["prefill_wait"] + stages["prefill_run"] == pytest.approx(
        entry["ttft_s"], abs=1e-4
    )


def test_startup_is_written_once(cfg):
    server = LLMServer(cfg, EngineConfig(**ENGINE), export_metrics=False)
    try:
        first = server.engine_stats()["startup"]
        assert set(first) == {
            "param_init_s", "cache_alloc_s", "warmup_s", "warmup_programs", "replica_init_s",
        }
        assert set(first["warmup_programs"]) == {
            "paged_prefill_step[16]", "paged_prefill_step[32]", "paged_decode_step[4x64]",
            "copy_paged_blocks",
        }
        assert all(v > 0 for v in first["warmup_programs"].values())
        assert sum(first["warmup_programs"].values()) <= first["warmup_s"]
        parts = sum(first[k] for k in ("param_init_s", "cache_alloc_s", "warmup_s"))
        assert 0 < parts <= first["replica_init_s"]
        assert len([t for chunk in server.generate({"prompt": [1, 2, 3], "max_new_tokens": 4})
                    for t in chunk]) == 4
        assert server.engine_stats()["startup"] == first
    finally:
        server.engine.stop()


def test_a_bare_engine_without_warmup_still_reports_startup(cfg, params):
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE, warmup=False))
    startup = eng.stats()["startup"]
    assert startup["warmup_s"] == 0.0 and startup["warmup_programs"] == {}
    assert startup["cache_alloc_s"] > 0


def test_phase_helper_stays_off_jax():
    code = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "from ray_tpu.observability import timeline\n"
        "clock = timeline.PhaseClock('engine', ('a', 'b'))\n"
        "since = time.perf_counter()\n"
        "with clock.phase('a', program='p', bucket=3):\n"
        "    time.sleep(0.01)\n"
        "clock.settle(since, 'b')\n"
        "elapsed = time.perf_counter() - since\n"
        "assert clock.total['a'] >= 0.01 and clock.total['b'] >= 0, clock.total\n"
        "assert 0 <= elapsed - sum(clock.total.values()) < 1e-3\n"
        "assert clock.lap == {'a': 0.0, 'b': 0.0}\n"
        "assert not hasattr(timeline, 'profile')\n"
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def _host_events(trace_dir):
    """name -> [(start_ns, duration_ns, stats)] of the trace's host planes."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine"):
                    out.setdefault(e.name, []).append((e.start_ns, e.duration_ns, dict(e.stats)))
    return out


def _traced_traffic(eng, trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's server subclass traces
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        _traffic(eng, n=6)
        eng.wait_idle()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def _offsets_us(events):
    """Trace clock minus timeline clock, per step both saw: the start of
    ``engine.schedule`` (its ``step`` stat) against the ``engine_step``
    event of that step, which starts a few clock reads before it."""
    steps = {
        ev.args["step"]: ev.start_us for ev in timeline.timeline_events()
        if ev.name == "engine_step" and ev.pid == os.getpid()
    }
    return [
        start_ns / 1e3 - steps[int(stats["step"])]
        for start_ns, _dur, stats in events.get("engine.schedule", [])
        if "step" in stats and int(stats["step"]) in steps
    ]


def test_profiler_trace_holds_the_phases_and_no_enclosing_step(engine, tmp_path):
    timeline.clear_events()
    events = _traced_traffic(engine, str(tmp_path))
    for name in ("schedule", "launch", "device_wait", "readback", "sample", "emit", "bookkeeping"):
        assert events.get(f"engine.{name}"), f"no engine.{name} span in the trace"
    # an outer span would take every idle gap from the leaves (idle_gaps
    # ranks by overlap share): the step as a whole lives in the timeline
    assert "engine.step" not in events and "engine_step" not in events
    launches = [stats for _s, _d, stats in events["engine.launch"] if stats]
    assert {s["program"] for s in launches} == {"paged_prefill_step", "paged_decode_step"}
    # prefill: the chunk bucket; decode: batch bucket x table width in tokens
    assert {str(s["bucket"]) for s in launches} <= {"16", "32", "4x64"}
    # leaves: on the engine's thread no phase begins inside another
    # (their parts, ``engine.<phase>.<part>``, nest in them: tests/test_phase_parts.py)
    spans = sorted((s, s + d) for name, evs in events.items() if name.count(".") == 1 for s, d, _ in evs)
    assert all(b[0] >= a[1] - 1 for a, b in zip(spans, spans[1:]))
    # the two clocks differ by a constant: the offset that lays the
    # timeline's engine_step events beside the trace
    offsets = _offsets_us(events)
    assert len(offsets) >= 10
    assert max(offsets) - min(offsets) < 5_000


def test_engine_step_event_covers_the_whole_step(engine):
    timeline.clear_events()
    _traffic(engine, n=4)
    engine.wait_idle()
    steps = [ev for ev in timeline.timeline_events() if ev.name == "engine_step"]
    assert [ev.args["step"] for ev in steps] == list(range(steps[0].args["step"],
                                                           steps[0].args["step"] + len(steps)))
    assert not [ev for ev in timeline.timeline_events() if ev.name.startswith("engine.")]
    for ev in steps:
        phases = ev.args["phases_us"]
        assert {"schedule", "launch"} <= set(phases) <= set(STEP_PHASES)
        # one event a step, from the top of step(): schedule() is inside it
        assert 0 < sum(phases.values()) <= ev.end_us - ev.start_us + 50
    # a step waits for the device, but for the one that leaves its decode launch
    # unread and found none to read: the first of a saturated stretch (ISSUE 39)
    assert sum("device_wait" in ev.args["phases_us"] for ev in steps) > len(steps) // 2
    assert any(ev.args["prefill_tokens"] and ev.args["decode_batch"] for ev in steps)


def test_recompile_after_warmup_names_the_program_and_shapes(cfg, params, caplog):
    eng = InferenceEngine(cfg, params, EngineConfig(**ENGINE))
    assert eng.stats()["recompiles_after_warmup"] == 0
    timeline.clear_events()
    runner = eng.runner
    runner.prefill_buckets = (16, 32, 48)  # a shape warm-up never saw
    row = [0] * runner.max_blocks_per_seq  # the null block: trash, by design
    with caplog.at_level(logging.WARNING, logger="ray_tpu.inference.model_runner"):
        runner.prefill_chunk(list(range(1, 41)), row, 0)
        runner.prefill_chunk(list(range(2, 44)), row, 0)  # the same shape again
    assert eng.stats()["recompiles_after_warmup"] == 1  # the counter is untouched
    events = [ev for ev in timeline.timeline_events() if ev.name == "recompile"]
    assert len(events) == 1
    assert events[0].args["program"] == "paged_prefill_step"
    assert [48] in events[0].args["arg_shapes"]
    said = [r for r in caplog.records if "compiled after warm-up" in r.getMessage()]
    assert len(said) == 1 and "paged_prefill_step" in said[0].getMessage()


def test_speculative_split_emits_what_sampling_in_turn_did(cfg, params):
    """Sampling a verify window first and emitting it after (two spans)
    gives the tokens of plain decode, greedy and seeded."""
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7]
    out = {}
    for k in (0, 3):
        eng = InferenceEngine(
            cfg, params, EngineConfig(**ENGINE, speculative_k=k, speculative_draft="ngram")
        ).start()
        try:
            out[k] = [
                list(eng.generate(prompt, max_new_tokens=12, temperature=t, seed=11))
                for t in (0.0, 0.9)
            ]
            p = eng.stats()["step_phases"]
            assert _leaves(p) == pytest.approx(p["wall_s"], rel=0.02)
        finally:
            eng.stop()
    assert out[0] == out[3]


if __name__ == "__main__":
    import tempfile

    device = jax.devices()[0]
    model = LlamaConfig.tiny()
    eng = InferenceEngine(
        model, init_params(model, jax.random.PRNGKey(0)), EngineConfig(**ENGINE)
    ).start()
    got = _offsets_us(_traced_traffic(eng, tempfile.mkdtemp()))
    eng.stop()
    q = statistics.quantiles(got, n=4)
    print(
        f"platform {device.platform} kind {device.device_kind!r}: engine.schedule (trace clock) "
        f"minus engine_step (time.time_ns) over {len(got)} steps, us: min {min(got):.1f} "
        f"median {q[1]:.1f} quartiles {q[0]:.1f}..{q[2]:.1f} max {max(got):.1f}; "
        f"spread max-min {max(got) - min(got):.1f}"
    )
