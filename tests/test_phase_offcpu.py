"""What the step thread WAITS for (ISSUE 54): a ``PhaseClock`` reads the
calling thread's CPU clock beside the wall clock, once a lap for the thread
and, in the sampled laps, at both ends of every phase and part; wall less CPU
seconds is OFF-CPU time, in accounts of its own
(``engine_stats()["step_offcpu"]``); ``settle`` rates the laps that did work
and keeps the stalled ones' seconds in sums a window can difference
(``["step_stalls"]``); ``PagedModelRunner.read`` counts its waits and those
that found the device done (``["device_reads"]``). CPU, tiny configs: what is
checked is the book-keeping, never a speed."""

import logging
import os
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

jax = pytest.importorskip("jax")

from ray_tpu.inference import model_runner  # noqa: E402
from ray_tpu.inference.engine import (  # noqa: E402
    STEP_PARTS, STEP_PHASES, UNBLOCKED, EngineConfig, InferenceEngine,
)
from ray_tpu.models.deepseek_v3 import DeepseekV3Config  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.jamba import JambaConfig  # noqa: E402
from ray_tpu.models.kimi_linear import KimiLinearConfig  # noqa: E402
from ray_tpu.models.lfm2 import Lfm2Config  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.models.xing4 import Xing4Config  # noqa: E402
from ray_tpu.observability import timeline  # noqa: E402

ENGINE = dict(
    num_blocks=64, block_size=8, prefill_buckets=(16, 32), decode_buckets=(4,),
    max_decode_batch=4, warmup=False,
)
PHASE_KEYS = {f"{name}_s" for name in STEP_PHASES}
PART_KEYS = {f"{name.replace('.', '_')}_s" for name in STEP_PARTS}
OFFCPU_KEYS = PHASE_KEYS | PART_KEYS | {"wall_s", "host_serial_s", "unblocked_s"}
STALL_KEYS = {"laps", "stalled", "wall_s", "device_wait_s", "host_s"}
#: a toy model a family: the clock is the engine's, whatever the runner runs
FAMILIES = {
    "dense": lambda: LlamaConfig.tiny(),
    "olmoe": lambda: LlamaConfig.tiny(
        mlp_hidden=32, max_seq_len=128, qk_norm=True, moe_experts=4, moe_top_k=2,
        moe_renormalize=False,
    ),
    "xing4": lambda: Xing4Config.tiny(),
    "kimi_linear": lambda: KimiLinearConfig.tiny(),
    "deepseek_v3": lambda: DeepseekV3Config.tiny(),
    "lfm2": lambda: Lfm2Config.tiny(),
    "jamba": lambda: JambaConfig.tiny(),
}


# -- the clock alone, on scripted time ----------------------------------------------------

class _Scripted:
    """``time`` AND the CPU clock for ``timeline``: ``sleep`` moves the wall
    clock alone (the thread waits), ``spin`` moves both (the thread works).
    ``tick``: the CPU clock is read in whole ticks, as the chip's machine has it."""

    def __init__(self, tick=0.0):
        self.now = 100.0
        self.cpu = 7.0
        self.tick = tick
        self.cpu_reads = 0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        self.cpu_reads += 1
        return self.cpu // self.tick * self.tick if self.tick else self.cpu

    def time_ns(self):
        return int(self.now * 1e9)

    def sleep(self, seconds):
        self.now += seconds

    def spin(self, seconds):
        self.now += seconds
        self.cpu += seconds


@pytest.fixture
def scripted(monkeypatch):
    """Every lap sampled, a CPU reading that costs nothing: what is read is what was scripted."""
    fake = _Scripted()
    monkeypatch.setattr(timeline, "time", fake)
    monkeypatch.setattr(timeline, "_cpu_now", fake.thread_time)
    monkeypatch.setattr(timeline, "_SAMPLE_EVERY", 1)
    monkeypatch.setattr(timeline, "_CPU_READ_S", 0.0)
    return fake


def _clock(*names):
    return timeline.PhaseClock("engine", [n for n in names if "." not in n], [n for n in names if "." in n])


def _offcpu(clock):
    walls, cpu = {**clock.total, **clock.parts_total}, {**clock.cpu_total, **clock.parts_cpu_total}
    return {name: walls[name] - cpu[name] for name in walls}


def _three_blocks(clock, t):
    since = t.perf_counter()
    with clock.phase("wait"):
        t.sleep(0.030)
    with clock.phase("work"):
        t.spin(0.020)
    with clock.phase("both"):
        t.spin(0.004)
        with clock.part("x"):
            t.spin(0.001)
            t.sleep(0.005)
        t.sleep(0.010)
    return since


def test_a_block_that_waits_reads_off_cpu_and_one_that_works_reads_none(scripted):
    clock = _clock("wait", "work", "both", "both.x")
    since = _three_blocks(clock, scripted)
    # before a settle: the lap's accounts, beside the wall clock's and never in them
    assert clock.lap == pytest.approx({"wait": 0.030, "work": 0.020, "both": 0.020})
    assert clock.cpu == pytest.approx({"wait": 0.0, "work": 0.020, "both": 0.005})
    assert clock.parts == pytest.approx({"both.x": 0.006}) and clock.parts_cpu == pytest.approx({"both.x": 0.001})
    assert set(clock.cpu_total.values()) == set(clock.parts_cpu_total.values()) == {0.0}
    scripted.sleep(0.002)  # what no phase claims has no reading of its own: the thread's account alone
    clock.settle(since, "work")
    assert clock.total == pytest.approx({"wait": 0.030, "work": 0.022, "both": 0.020})
    assert _offcpu(clock) == pytest.approx({"wait": 0.030, "work": 0.002, "both": 0.015, "both.x": 0.005})
    assert set(clock.cpu.values()) == set(clock.parts_cpu.values()) == {0.0}  # settled with the leaves
    assert clock.thread_offcpu == 0.0  # the first lap has no CPU reading behind it
    since = _three_blocks(clock, scripted)
    clock.settle(since, "work")
    assert clock.thread_offcpu == pytest.approx(0.045)  # 30 + 5 + 10 ms waited of the lap's 70


def test_a_lap_in_so_many_reads_the_cpu_clock_and_stands_for_the_others(scripted, monkeypatch):
    monkeypatch.setattr(timeline, "_SAMPLE_EVERY", 3)
    clock = timeline.PhaseClock("engine", ("wait", "work", "both"), ("both.x",))
    for lap in range(1, 10):
        before = scripted.cpu_reads
        clock.settle(_three_blocks(clock, scripted), "work")
        # the blocks' eight readings in the first lap and every third after it; one at every settle
        assert scripted.cpu_reads - before == (9 if lap % 3 == 1 else 1), lap
    assert clock.total == pytest.approx({"wait": 0.270, "work": 0.180, "both": 0.180})  # every lap's wall
    assert _offcpu(clock) == pytest.approx({"wait": 0.270, "work": 0.0, "both": 0.135, "both.x": 0.045})
    assert clock.thread_offcpu == pytest.approx(8 * 0.045)  # every lap but the first


def test_a_reading_costs_the_block_its_wall_seconds_and_is_not_read_as_a_wait(scripted, monkeypatch):
    """On a sampled lap a block's wall holds its two CPU readings, and about one
    reading's time lies between them: taken off, for each lap the block stands for."""
    monkeypatch.setattr(timeline, "_SAMPLE_EVERY", 5)
    monkeypatch.setattr(timeline, "_CPU_READ_S", 6e-6)
    read = scripted.thread_time

    def costly():
        scripted.spin(3e-6)
        value = read()
        scripted.spin(3e-6)
        return value

    monkeypatch.setattr(timeline, "_cpu_now", costly)
    clock = timeline.PhaseClock("engine", ("work",), ("work.x",))
    for _ in range(20):
        since = clock.settled_at  # as the loop's tail: the lap holds the reading of the settle before it
        with clock.phase("work"):
            scripted.spin(0.0004)
            with clock.part("x"):
                scripted.spin(0.0006)
        clock.settle(since, "work")
    # laps 1, 6, 11, 16 paid two readings in the part and four in the phase; every settle's own is the next lap's rest
    assert clock.parts_total["work.x"] == pytest.approx(20 * 0.0006 + 4 * 12e-6)
    assert clock.total["work"] == pytest.approx(20 * 0.001 + 4 * 24e-6 + 19 * 6e-6)
    # ... and none of it reads as a wait, but in the laps that stand on the first, which has no reading behind it
    off = _offcpu(clock)
    assert off["work.x"] == pytest.approx(0.0, abs=1e-9) and abs(off["work"]) < 5 * 6e-6


def test_a_cpu_clock_that_moves_in_ticks_means_something_in_the_sums_alone(scripted):
    import random

    scripted.tick = 0.010
    rng = random.Random(54)
    clock = _clock("work", "wait")
    clamped = worked = 0.0
    for _ in range(2000):
        since = scripted.perf_counter()
        seconds = rng.uniform(0.0005, 0.0021)
        worked += seconds
        with clock.phase("work"):
            scripted.spin(seconds)
        clamped += max(0.0, clock.lap["work"] - clock.cpu["work"])
        with clock.phase("wait"):
            scripted.sleep(0.0031)
        scripted.spin(0.0002)
        clock.settle(since, "work")
    off = _offcpu(clock)
    assert off["wait"] == pytest.approx(6.2) and clock.total["work"] == pytest.approx(worked + 0.4)
    assert abs(off["work"]) < 0.15  # the phase worked all of its 2.6 s and the rest, its too, all of its 0.4: to a dozen ticks of 300
    assert clamped > 0.8 * worked  # a block at a time, the same readings say the thread waited most of what it worked
    assert clock.thread_offcpu == pytest.approx(1999 * 0.0031, abs=0.011)  # the laps telescope: one tick, any window


def test_the_threads_account_skips_a_lap_it_cannot_vouch_for(scripted):
    clock = _clock("work")

    def lap(gap=0.0):
        scripted.sleep(gap)
        scripted.spin(gap)  # the caller's own time between two direct steps
        since = scripted.perf_counter()
        with clock.phase("work"):
            scripted.spin(0.002)
            scripted.sleep(0.001)
        clock.settle(since, "work")

    lap()
    lap()
    lap(gap=0.0002)  # as the loop's own gap between its tail's settle and the next step: the gap is the lap's
    assert clock.thread_offcpu == pytest.approx(0.0022)
    lap(gap=0.5)  # a step() from outside the loop: whose half second that was, nobody knows
    assert clock.thread_offcpu == pytest.approx(0.0022)
    lap()
    assert clock.thread_offcpu == pytest.approx(0.0032)
    other = threading.Thread(target=lap)  # another thread's CPU clock is another clock
    other.start()
    other.join()
    assert clock.thread_offcpu == pytest.approx(0.0032)
    lap()  # ... and so is this one's again, after it
    lap()
    assert clock.thread_offcpu == pytest.approx(0.0042)


def _spin(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_the_real_clocks_tell_a_sleep_from_a_spin_and_see_the_gil(monkeypatch):
    monkeypatch.setattr(timeline, "_SAMPLE_EVERY", 1)
    clock = _clock("sleeps", "spins", "contended")
    with clock.phase("sleeps"):
        time.sleep(0.05)
    with clock.phase("spins"):
        _spin(0.05)
    stop = threading.Event()

    def other():
        while not stop.is_set():
            pass

    thread = threading.Thread(target=other, daemon=True)
    thread.start()
    try:
        with clock.phase("contended"):
            _spin(0.2)
    finally:
        stop.set()
        thread.join()
    lap = clock.lap
    off = {name: lap[name] - clock.cpu[name] for name in lap}
    assert off["sleeps"] > 0.9 * lap["sleeps"]
    # alone, the thread runs but for what the machine's other work takes of its core
    assert off["spins"] < 0.5 * lap["spins"]
    # beside a thread that spins it holds the GIL about every other switch interval
    assert off["contended"] > 0.1 * lap["contended"] and off["contended"] > 2 * off["spins"]


def test_the_clock_stays_off_jax():
    code = (
        "import sys, time; sys.path.insert(0, %r)\n"
        "from ray_tpu.observability import timeline\n"
        "timeline._STALL_AFTER_LAPS, timeline._SAMPLE_EVERY = 2, 1\n"
        "assert 0 < timeline._CPU_READ_S < 1e-3\n"
        "clock = timeline.PhaseClock('engine', ('a', 'device_wait'), ('a.x',))\n"
        "for seconds in (0.001, 0.001, 0.1):\n"
        "    since = time.perf_counter()\n"
        "    with clock.phase('a'):\n"
        "        with clock.part('x'):\n"
        "            time.sleep(seconds)\n"
        "    clock.settle(since, 'a')\n"
        "assert 0.09 < clock.parts_total['a.x'] - clock.parts_cpu_total['a.x'] <= clock.parts_total['a.x']\n"
        "assert 0.09 < clock.total['a'] - clock.cpu_total['a'] <= clock.total['a']\n"
        "assert 0.09 < clock.thread_offcpu <= clock.total['a']\n"
        "assert clock.stalls['laps'] == 3 and clock.stalls['stalled'] == 1, clock.stalls\n"
        "assert clock.stalls['host_s'] == clock.stalls['wall_s'] >= 0.1\n"
        "(ev,) = [e for e in timeline.timeline_events() if e.name == 'engine_stall']\n"
        "assert ev.args['phases_ms']['a'] >= 100 and ev.args['cpu_ms'] < 10\n"
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n" % REPO
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# -- the stall rule, on scripted laps -------------------------------------------------------

def _lap(clock, t, host, device_wait, loop_wait=0.0, rated=True):
    since = t.perf_counter()
    with clock.phase("launch"):
        t.spin(host)
    with clock.phase("device_wait"):
        t.sleep(device_wait)
    if loop_wait:
        with clock.phase("loop_wait"):
            t.sleep(loop_wait)
    clock.settle(since, "bookkeeping", rated=rated)


def _usual_ms(clock):
    return 1e3 * clock._usual_s / clock._usual_laps


def test_sixty_four_laps_of_ten_ms_then_one_of_two_hundred_is_one_stall(scripted, caplog):
    timeline.clear_events()
    clock = timeline.PhaseClock("engine", STEP_PHASES, STEP_PARTS)
    assert set(clock.stalls) == STALL_KEYS and not any(clock.stalls.values())
    _lap(clock, scripted, 0.050, 0.150)  # among the first 64 no lap is rated against so few
    for _ in range(62):
        _lap(clock, scripted, 0.004, 0.006)
    _lap(clock, scripted, 0.004, 0.006, loop_wait=5.0)  # its wait for work is not its wall
    _lap(clock, scripted, 9.0, 0.0, rated=False)        # a lap that did no work is not rated at all
    assert clock.stalls == {"laps": 64, "stalled": 0, "wall_s": 0.0, "device_wait_s": 0.0, "host_s": 0.0}
    usual = _usual_ms(clock)
    assert usual == pytest.approx((200.0 + 63 * 10.0) / 64)  # 12.97 ms
    with caplog.at_level(logging.WARNING, logger="ray_tpu.observability.timeline"):
        _lap(clock, scripted, 0.012, 0.100)  # 112 ms: under ten times the mean
        assert clock.stalls["laps"] == 65 and clock.stalls["stalled"] == 0 and not caplog.records
        usual = _usual_ms(clock)
        _lap(clock, scripted, 0.020, 0.180)  # 200 ms: a stall, 90% of it the device's or the machine's
    stalls = clock.stalls
    assert (stalls["laps"], stalls["stalled"]) == (66, 1)
    assert stalls["wall_s"] == pytest.approx(0.200) and stalls["device_wait_s"] == pytest.approx(0.180)
    assert stalls["wall_s"] == pytest.approx(stalls["device_wait_s"] + stalls["host_s"], abs=1e-12)
    assert _usual_ms(clock) == usual  # a stalled lap does not move the mean it was rated against
    # ... and is in every other account as any lap is
    assert clock.longest_wall_s == pytest.approx(9.0) and clock.total["device_wait"] > 0.180
    (ev,) = [e for e in timeline.timeline_events() if e.name == "engine_stall"]
    assert ev.category == "inference" and ev.end_us - ev.start_us == pytest.approx(200e3, rel=1e-6)
    assert ev.args["wall_ms"] == 200.0 and ev.args["device_wait_ms"] == 180.0
    assert ev.args["usual_ms"] == pytest.approx(usual, abs=1e-3)
    assert ev.args["phases_ms"] == {"launch": 20.0, "device_wait": 180.0}
    assert ev.args["cpu_ms"] == 20.0  # the host's 20 ms were work: the lap's own CPU reading
    (record,) = caplog.records
    assert "200 ms" in record.getMessage() and "180 ms waiting for the device" in record.getMessage()


def test_stalls_warn_once_a_second_and_a_run_of_them_is_the_new_load(scripted, caplog):
    timeline.clear_events()
    clock = timeline.PhaseClock("engine", STEP_PHASES, STEP_PARTS)
    for _ in range(64):
        _lap(clock, scripted, 0.004, 0.006)
    with caplog.at_level(logging.WARNING, logger="ray_tpu.observability.timeline"):
        for _ in range(3):  # 0.9 s of stalls: one line in the log, three events
            _lap(clock, scripted, 0.3, 0.0)
        assert clock.stalls["stalled"] == 3 and len(caplog.records) == 1
        _lap(clock, scripted, 0.004, 0.006)  # a usual lap between: the run ends
        for _ in range(timeline._STALL_RUN):
            _lap(clock, scripted, 0.3, 0.0)
    assert clock.stalls["stalled"] == 3 + timeline._STALL_RUN and 2 <= len(caplog.records) <= 4
    assert len([e for e in timeline.timeline_events() if e.name == "engine_stall"]) == clock.stalls["stalled"]
    # eight in a row: the mean starts anew, and laps of 300 ms are the usual ones 64 laps on
    assert clock._usual_laps == 0
    for _ in range(80):
        _lap(clock, scripted, 0.3, 0.0)
    assert clock.stalls["stalled"] == 3 + timeline._STALL_RUN and _usual_ms(clock) == pytest.approx(300.0)
    _lap(clock, scripted, 0.5, 3.0)
    assert clock.stalls["stalled"] == 4 + timeline._STALL_RUN
    assert clock.stalls["wall_s"] == pytest.approx(clock.stalls["device_wait_s"] + clock.stalls["host_s"])
    assert clock.stalls["device_wait_s"] == pytest.approx(3.0)


# -- the engine's accounts ------------------------------------------------------------------

def _engine(family, **kw):
    cfg = FAMILIES[family]()
    params = model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, EngineConfig(**{**ENGINE, **kw}))


def _traffic(eng, n=8, new_tokens=10):
    rids = []
    for i in range(n):
        rids.append(eng.submit(list(range(1, 14 + 5 * i)), max_new_tokens=new_tokens))
        time.sleep(0.002)
    return [list(eng.tokens(r, timeout=120)) for r in rids]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_family_has_the_three_accounts_from_construction(family):
    stats = _engine(family).stats()
    assert set(stats["step_offcpu"]) == OFFCPU_KEYS and set(stats["step_offcpu"].values()) == {0.0}
    assert set(stats["step_stalls"]) == STALL_KEYS and not any(stats["step_stalls"].values())
    assert stats["device_reads"] == {"reads": 0, "ready": 0}
    # beside what was there, which reads what it read
    assert set(stats["step_parts"]) == PART_KEYS
    assert set(stats["step_phases"]) == PHASE_KEYS | {
        "wall_s", "host_serial_s", "longest_wall_s", "longest_device_wait_s"}


@pytest.mark.parametrize("family, every", [("dense", 1), ("olmoe", 1), ("dense", 5)])
def test_off_cpu_fits_the_wall_clock_key_by_key_and_the_sums_are_what_they_are_said_to_be(family, every, monkeypatch):
    """``every`` 5: the blocks of one lap in five read the CPU clock, as on the
    chip's machine, where a reading is dear; the thread's own is read every lap."""
    monkeypatch.setattr(timeline, "_SAMPLE_EVERY", every)
    eng = _engine(family).start()
    try:
        assert all(len(tokens) == 10 for tokens in _traffic(eng))
        eng.wait_idle()
        time.sleep(0.05)  # the last step settles after the scheduler ran dry
        stats = eng.stats()
    finally:
        eng.stop()
    off, phases, parts = stats["step_offcpu"], stats["step_phases"], stats["step_parts"]
    for key in PHASE_KEYS | {"wall_s", "host_serial_s"}:
        assert 0.0 <= off[key] <= phases[key], key
    for key in PART_KEYS:
        assert 0.0 <= off[key] <= parts[key], key
    # the thread's own account less the two leaves that wait, as the wall clock's host_serial_s is
    assert off["host_serial_s"] == pytest.approx(
        min(max(0.0, off["wall_s"] - off["device_wait_s"] - off["loop_wait_s"]), phases["host_serial_s"]), abs=1e-12)
    assert set(UNBLOCKED) == {"schedule.admit", "schedule.plan", "launch.rows", "launch.inputs", "sample"}
    unblocked = [f"{name.replace('.', '_')}_s" for name in UNBLOCKED]
    assert off["unblocked_s"] == pytest.approx(sum(off[k] for k in unblocked), abs=1e-12)
    # a thread that waits is off the CPU: the loop's wait for work, and the device's steps
    assert off["wall_s"] > 0.5 * phases["loop_wait_s"] > 0.0
    if every == 1:
        assert off["loop_wait_s"] > 0.5 * phases["loop_wait_s"] and off["device_wait_s"] > 0.0
        host = PHASE_KEYS - {"device_wait_s", "loop_wait_s"}
        # the leaves' own readings and the thread's agree, but for what no phase claimed and the readings' cost
        assert sum(off[k] for k in host) <= off["host_serial_s"] + 0.1 * phases["host_serial_s"] + 1e-3
    stalls, reads = stats["step_stalls"], stats["device_reads"]
    assert 0 < stalls["laps"] <= stats["total_steps"] and stalls["stalled"] <= stalls["laps"]
    assert stalls["wall_s"] == pytest.approx(stalls["device_wait_s"] + stalls["host_s"])
    assert 0 <= reads["ready"] <= reads["reads"] and reads["reads"] >= stats["total_steps"]


def test_reads_count_an_array_that_was_finished_and_one_that_was_not():
    import jax.numpy as jnp

    eng = _engine("dense")
    runner, clock = eng.runner, eng._clock
    x = jnp.ones((600, 600), jnp.float32)

    @jax.jit
    def work(x):
        for _ in range(40):
            x = jnp.tanh(x @ x) / 600.0
        return x

    work(x).block_until_ready()  # compiled
    done = work(x)
    done.block_until_ready()
    runner.read(model_runner.Launched("decode", done), clock)
    assert clock.reads == {"reads": 1, "ready": 1}
    t0 = time.perf_counter()
    running = work(x)
    launched_in = time.perf_counter() - t0
    runner.read(model_runner.Launched("decode", running), clock)
    took = time.perf_counter() - t0
    if took > 20 * model_runner._READY_S and launched_in < took / 2:  # the launch did not wait for the program
        assert clock.reads == {"reads": 2, "ready": 1}
    assert clock.reads["reads"] == 2
    # what a caller that is not the step loop reads lands on the runner's own clock
    runner.read(model_runner.Launched("decode", done))
    assert clock.reads["reads"] == 2 and runner.clock.reads == {"reads": 1, "ready": 1}
    clock.settle(clock.settled_at, "bookkeeping", rated=False)
    assert eng.stats()["device_reads"] == clock.reads
    assert 0 < model_runner._READY_S < 1e-3


def test_a_stalled_step_leaves_an_event_a_warning_and_a_span_outside_every_phase(tmp_path, monkeypatch, caplog):
    """Direct steps on this thread, the compiles behind them: the mean stands on
    warm steps, then ONE read stands still for 0.6 s."""
    import glob

    from jax.profiler import ProfileData

    monkeypatch.setattr(timeline, "_STALL_AFTER_LAPS", 6)
    eng = _engine("dense")

    def serve(n):
        rid = eng.submit(list(range(1, 30)), max_new_tokens=n)
        while eng.scheduler.has_work():
            assert eng.step()
        return list(eng.tokens(rid, timeout=5))

    assert len(serve(4)) == 4 and len(serve(4)) == 4  # the second finds its prefix cached: the other chunk bucket
    eng._clock = timeline.PhaseClock("engine", STEP_PHASES, STEP_PARTS)  # the account from here on
    steps_before = eng.total_steps
    timeline.clear_events()
    read, reads = eng.runner.read, []

    def slow_once(step, clock=None, before_wait=None):
        reads.append(step)
        if len(reads) == 12:
            with clock.phase("device_wait"):
                time.sleep(0.6)
        return read(step, clock, before_wait)

    monkeypatch.setattr(eng.runner, "read", slow_once)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with caplog.at_level(logging.WARNING, logger="ray_tpu.observability.timeline"):
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            assert len(serve(16)) == 16
        finally:
            jax.profiler.stop_trace()
    stalls = eng.stats()["step_stalls"]
    assert stalls["stalled"] == 1 and stalls["laps"] == eng.total_steps - steps_before > 12
    assert 0.6 <= stalls["device_wait_s"] <= stalls["wall_s"] < 0.7
    assert eng.stats()["step_offcpu"]["wall_s"] >= 0.59  # the thread slept: off the CPU
    (ev,) = [e for e in timeline.timeline_events() if e.name == "engine_stall"]
    assert ev.args["phases_ms"]["device_wait"] >= 600 and ev.args["cpu_ms"] < 100 and ev.args["wall_ms"] == pytest.approx(1e3 * stalls["wall_s"], abs=1e-2)
    steps = [e for e in timeline.timeline_events() if e.name == "engine_step"]
    assert any(s.start_us - 1e3 <= ev.start_us and ev.end_us <= s.end_us + 1e3 for s in steps)  # beside its engine_step
    (record,) = [r for r in caplog.records if r.name == "ray_tpu.observability.timeline"]
    assert "engine: a lap of 6" in record.getMessage()
    # the trace: ``engine.stall`` where the lap ended, inside no phase and no part
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith("engine.")
    ]
    (stall,) = [s for s in spans if s[0] == "engine.stall"]
    assert float(stall[3]["wall_ms"]) == ev.args["wall_ms"] and float(stall[3]["device_wait_ms"]) >= 600
    others = [s for s in spans if s[0] != "engine.stall"]
    assert others and not [s for s in others if s[1] < stall[1] < s[2]]
    # it follows the lap's last span and precedes the next step's first
    before = max(s[2] for s in others if s[2] <= stall[1])
    slept = [s for s in others if s[0] == "engine.device_wait" and s[2] - s[1] >= 0.6e9]
    assert len(slept) == 1 and slept[0][2] <= before <= stall[1]
