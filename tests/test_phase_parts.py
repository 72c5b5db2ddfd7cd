"""The PARTS of the step account's phases (ISSUE 38): ``with clock.part(name)``
inside an open phase of a ``PhaseClock`` keeps the block's seconds in an
account of its own (``engine_stats()["step_parts"]``), never in a leaf, under a
span ``engine.<phase>.<part>`` nested in the phase's; ``settle`` keeps the
longest lap beside them. CPU, tiny configs: what is checked is the
book-keeping, never a speed."""

import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")

from ray_tpu.inference.engine import (  # noqa: E402
    STEP_PARTS, STEP_PHASES, EngineConfig, InferenceEngine,
)
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.kimi_linear import KimiLinearConfig  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.models.xing4 import Xing4Config  # noqa: E402
from ray_tpu.observability import timeline  # noqa: E402

ENGINE = dict(
    num_blocks=64, block_size=8, prefill_buckets=(16, 32), decode_buckets=(4,),
    max_decode_batch=4, warmup=False,
)
KEYS = {f"{name.replace('.', '_')}_s" for name in STEP_PARTS}
#: a toy model a family: the engine is the same, what the runner reads back is not
FAMILIES = {
    "dense": lambda: LlamaConfig.tiny(),
    "olmoe": lambda: LlamaConfig.tiny(
        mlp_hidden=32, max_seq_len=128, qk_norm=True, moe_experts=4, moe_top_k=2,
        moe_renormalize=False,
    ),
    "xing4": lambda: Xing4Config.tiny(),
    "kimi_linear": lambda: KimiLinearConfig.tiny(),
}


# -- the clock alone, on a scripted time -------------------------------------------------

class _Scripted:
    """``time`` for ``timeline``: ``perf_counter`` moves only when the script sleeps."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


@pytest.fixture
def scripted(monkeypatch):
    fake = _Scripted()
    monkeypatch.setattr(timeline, "time", fake)
    return fake


def _lap(clock, t, parts, device_wait, loop_wait=0.0):
    """One lap of a step loop: every phase sleeps a scripted time, half of it
    inside a part where ``parts``."""
    since = t.perf_counter()
    for phase, part, seconds in (
        ("schedule", "plan", 0.003), ("launch", "rows", 0.002), ("launch", "call", 0.004),
        ("device_wait", None, device_wait), ("readback", "logits", 0.001), ("emit", "commit", 0.002),
    ):
        with clock.phase(phase):
            t.sleep(seconds / 2)
            if parts and part:
                with clock.part(part):
                    t.sleep(seconds / 2)
            else:
                t.sleep(seconds / 2)
    t.sleep(0.0005)  # what no phase claims: the rest's
    if loop_wait:
        with clock.phase("loop_wait"):
            t.sleep(loop_wait)
    return since


def _account(clock):
    # the engine's own reading of a clock: leaves, wall_s, host_serial_s, longest_*
    return InferenceEngine._step_phases(types.SimpleNamespace(_clock=clock))


def test_parts_leave_every_leaf_and_sum_what_they_read_without_them(scripted):
    with_parts, without = (timeline.PhaseClock("engine", STEP_PHASES) for _ in range(2))
    unsettled = {}
    for clock, parts in ((with_parts, True), (without, False)):
        scripted.now = 100.0
        for device_wait in (0.010, 0.030, 0.020):
            since = _lap(clock, scripted, parts, device_wait)
            unsettled[parts, device_wait] = dict(clock.lap)
            clock.settle(since, "bookkeeping")
    for device_wait in (0.010, 0.030, 0.020):  # lap, before a settle
        assert unsettled[True, device_wait] == unsettled[False, device_wait]
    assert with_parts.total == without.total and with_parts.lap == without.lap
    assert _account(with_parts) == _account(without)  # wall_s, host_serial_s, longest_*
    assert not without.parts_total and not without.parts
    # half of each phase that has a part, three laps; launch has two parts
    assert with_parts.parts_total == pytest.approx({
        "schedule.plan": 0.0045, "launch.rows": 0.003, "launch.call": 0.006,
        "readback.logits": 0.0015, "emit.commit": 0.003,
    })
    assert set(with_parts.parts.values()) == {0.0}  # settled with the leaves
    for key, seconds in with_parts.parts_total.items():
        phase = key.split(".")[0]
        of_phase = sum(s for k, s in with_parts.parts_total.items() if k.startswith(phase + "."))
        assert 0 < seconds <= of_phase <= with_parts.total[phase]


@pytest.mark.parametrize("where", ["outside any phase", "inside another part", "after the phase closed"])
def test_a_part_needs_an_open_phase_and_does_not_nest(where):
    clock = timeline.PhaseClock("engine", ("launch",), ("launch.rows",))
    with pytest.raises(RuntimeError, match="inside another part" if "inside" in where else "outside any phase"):
        if where == "outside any phase":
            clock.part("rows")
        elif where == "inside another part":
            with clock.phase("launch"), clock.part("rows"):
                clock.part("call")
        else:
            with clock.phase("launch"):
                pass
            clock.part("rows")
    # the refusal left the clock usable, and no account of the refused part
    with clock.phase("launch"), clock.part("rows"):
        pass
    assert set(clock.parts) == {"launch.rows"} and clock.parts["launch.rows"] >= 0.0


def test_longest_lap_is_the_largest_settled_and_leaves_loop_wait_out(scripted):
    clock = timeline.PhaseClock("engine", STEP_PHASES, STEP_PARTS)
    walls = []
    for device_wait, loop_wait in ((0.010, 0.0), (0.050, 0.0), (0.020, 9.0), (0.030, 0.0)):
        since = _lap(clock, scripted, True, device_wait, loop_wait)
        walls.append(scripted.now - since - loop_wait)
        clock.settle(since, "bookkeeping")
        assert clock.longest_wall_s == pytest.approx(max(walls))
    # the lap that waited 9 s for work is not the longest: the second is, and
    # the device_wait kept is that lap's own, not the largest or the last
    assert clock.longest_wall_s == pytest.approx(walls[1]) and walls[1] < 0.1
    assert clock.longest_device_wait_s == pytest.approx(0.050)
    account = _account(clock)
    assert account["longest_wall_s"] == clock.longest_wall_s
    assert account["wall_s"] == pytest.approx(scripted.now - 100.0)  # outside the sum


def test_part_stays_off_jax():
    code = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "from ray_tpu.observability import timeline\n"
        "clock = timeline.PhaseClock('engine', ('a', 'b'), ('a.x',))\n"
        "since = time.perf_counter()\n"
        "with clock.phase('a'):\n"
        "    with clock.part('x'):\n"
        "        time.sleep(0.01)\n"
        "    with clock.part('y'):\n"
        "        pass\n"
        "assert clock.parts['a.x'] >= 0.01 and clock.lap['a'] >= clock.parts['a.x'] + clock.parts['a.y']\n"
        "clock.settle(since, 'b')\n"
        "assert clock.parts == {'a.x': 0.0, 'a.y': 0.0} and clock.parts_total['a.x'] >= 0.01\n"
        "assert clock.longest_wall_s == sum(clock.total.values()) >= 0.01\n"
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- the engine's parts -------------------------------------------------------------------

def _engine(family):
    cfg = FAMILIES[family]()
    params = model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, EngineConfig(**ENGINE))


def _traffic(eng, n=8, new_tokens=10):
    rids = []
    for i in range(n):
        rids.append(eng.submit(list(range(1, 14 + 5 * i)), max_new_tokens=new_tokens))
        time.sleep(0.002)
    return [list(eng.tokens(r, timeout=120)) for r in rids]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_model_keeps_the_ten_parts_and_they_fit_their_phases(family):
    eng = _engine(family)
    assert set(eng.stats()["step_parts"]) == KEYS  # from construction
    assert set(eng.stats()["step_parts"].values()) == {0.0}
    eng.start()
    try:
        assert all(len(tokens) == 10 for tokens in _traffic(eng))
        eng.wait_idle()
        time.sleep(0.05)  # the last step settles after the scheduler ran dry
        stats = eng.stats()
    finally:
        eng.stop()
    parts, phases = stats["step_parts"], stats["step_phases"]
    assert set(parts) == KEYS and all(seconds >= 0.0 for seconds in parts.values())
    # what the experts saw comes over in a part of its own: none on a dense model
    assert (parts["readback_loads_s"] > 0) == (family != "dense")
    assert all(parts[k] > 0 for k in KEYS - {"readback_loads_s"})
    of = {
        phase: sum(s for k, s in parts.items() if k.startswith(phase + "_"))
        for phase in ("schedule", "launch", "readback", "emit")
    }
    for phase, covered in of.items():  # a part's seconds are the phase's too
        assert covered <= phases[f"{phase}_s"], phase
    # launch, readback and emit are nothing but their parts: what is left is the
    # part spans' own cost, 2-3 us an entry. On the chip, where a phase is 0.3-5 ms
    # a step, the parts read 98-99.5% of it (PERF.md section 5); a toy's readback is
    # 9 us of work an entry, so the floors here are a share that a load on the
    # machine does not move. schedule keeps a self time (reaped requests, chaos)
    for phase, floor in (("launch", 0.9), ("emit", 0.8), ("readback", 0.5)):
        assert of[phase] >= floor * phases[f"{phase}_s"], (phase, of[phase], phases[f"{phase}_s"])
    # the leaves close on the wall time as they did without parts
    leaves = sum(phases[f"{name}_s"] for name in STEP_PHASES)
    assert leaves == pytest.approx(phases["wall_s"], rel=0.02)
    assert 0 < phases["longest_device_wait_s"] < phases["longest_wall_s"] <= phases["wall_s"]


def test_direct_steps_and_the_runners_own_clock_keep_parts_too():
    eng = _engine("dense")
    rid = eng.submit(list(range(1, 30)), max_new_tokens=4)
    while eng.scheduler.has_work():
        assert eng.step()
    assert len(list(eng.tokens(rid, timeout=5))) == 4
    parts = eng.stats()["step_parts"]
    assert parts["emit_deliver_s"] > 0 and parts["schedule_plan_s"] > 0
    # a caller that is not the engine's step lands on the runner's own clock
    runner = eng.runner
    row = [0] * runner.max_blocks_per_seq
    runner.decode([1], [0], [row], [1])
    assert eng.stats()["step_parts"] == parts
    assert {"launch.inputs", "launch.call", "readback.logits"} <= set(runner.clock.parts)
    # the scheduler alone, with no clock, keeps none
    assert eng.scheduler.schedule().empty


def test_profiler_trace_nests_each_part_in_its_phase(tmp_path):
    """``idle_gaps`` hands a gap to the INNERMOST span under it: a part must
    lie inside a span of its phase on the step thread's line."""
    import glob

    from jax.profiler import ProfileData

    eng = _engine("olmoe").start()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # as the benchmark's server subclass traces
    try:
        _traffic(eng, n=2)  # compiles outside the trace
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _traffic(eng, n=6)
            eng.wait_idle()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = [
        [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    (step_thread,) = [events for events in lines if any(name == "engine.launch" for name, _s, _e in events)]
    spans = [ev for ev in step_thread if ev[0].startswith("engine.")]
    seen = {name for name, _s, _e in spans}
    assert {f"engine.{part}" for part in STEP_PARTS} <= seen, sorted(seen)
    for name, start, end in spans:
        if name.count(".") == 2:
            phase = name.rsplit(".", 1)[0]
            assert any(n == phase and s <= start and end <= e for n, s, e in spans), name
