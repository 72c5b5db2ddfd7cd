"""The reduction from a profiler trace to numbers: on a hand-made trace
whose answers can be worked out by eye, and on a small trace recorded on
the chip (``data/trace_chat-paced.json``: 0.6 s of ``chat-paced`` on a TPU
v5e, PR 23; device operations under 200 us left out to keep it small)."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench.harness import layer_metrics as lm, trace as tr  # noqa: E402

MS = 1e6  # ns


def _hand_made():
    ops = [  # [name, start, duration] on one device: busy 0-10, 12-20, 30-40 ms
        ["%fusion.1 = bf16[8] fusion(...)", 0 * MS, 10 * MS],
        ["%all-gather.3 = bf16[8] all-gather(...)", 12 * MS, 8 * MS],
        ["%custom-call.7 = bf16[8] custom-call(...)", 30 * MS, 10 * MS],
    ]
    async_ops = [["%all-gather-start.1 = (...)", 8 * MS, 4 * MS]]  # 8-12: 2 ms alone, 10-12
    modules = [["jit__unknown(11)", 0 * MS, 20 * MS], ["jit__unknown(22)", 30 * MS, 10 * MS]]
    host = [
        ["PjitFunction(step_a)", -1 * MS, 0.5 * MS], ["PjitFunction(step_a)", -0.9 * MS, 0.2 * MS],
        ["np.asarray(jax.Array)", 19 * MS, 10.5 * MS], ["outer", 15 * MS, 20 * MS],
        ["PjitFunction(step_b)", 29.6 * MS, 0.3 * MS],
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules}, {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": async_ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
        {"name": "/host:metadata", "lines": []},
    ]}


def test_busy_window_and_idle_share_by_hand():
    t = _hand_made()
    assert tr.busy(t) == {"busy_s": pytest.approx(0.028), "window_s": pytest.approx(0.040)}
    assert tr.idle_share_pct(t) == pytest.approx(30.0)


def test_programs_are_named_by_the_launch_that_precedes_them():
    t = _hand_made()
    plane = tr.device_planes(t)[0]
    assert tr.program_names(t, plane) == ["step_a", "step_b"]  # the nested inner span counts once
    assert tr.slowest_program_median_ms(t, "step_a") == pytest.approx(20.0)
    assert tr.slowest_program_median_ms(t, "step_b") == pytest.approx(10.0)
    assert tr.slowest_program_median_ms(t, "no_such_step") is None


def test_shares_by_operation_name():
    t = _hand_made()
    assert tr.ops_share_of_busy_pct(t, "custom-call") == pytest.approx(100 * 10 / 28)
    # all-gather runs alone 10-12 (async, nothing else) and 12-20 (on the op line): 10 of 40 ms
    assert tr.exposed_share_pct(t, "all-gather") == pytest.approx(25.0)
    assert tr.exposed_share_pct(t, "reduce-scatter") == pytest.approx(0.0)


def test_an_idle_gap_is_divided_among_the_innermost_host_spans():
    t = _hand_made()
    gaps = dict(tr.idle_gaps(t))
    # 10-12 ms: nothing on the host. 20-30 ms, one thread: np.asarray is innermost until 29.5,
    # step_b 29.6-29.9, and "outer", which covers all of it, keeps only what no child has
    assert gaps == {
        "unattributed": pytest.approx(0.002), "np.asarray_jax.Array_": pytest.approx(0.0095),
        "PjitFunction_step_b_": pytest.approx(0.0003), "outer": pytest.approx(0.0002),
    }
    top = tr.top_device_ops(t, 2)
    assert [n for n, _ in top] == ["fusion.1", "custom-call.7"]


def _one_gap(*threads, gap=(10.0, 15.5)):
    """A device busy 0-10 ms and again from the gap's end, and host threads
    of ``[name, start_ms, duration_ms]`` events."""
    ops = [["%fusion.1 = f32[] fusion()", 0.0, gap[0] * MS], ["%fusion.2 = f32[] fusion()", gap[1] * MS, 10 * MS]]
    lines = [{"name": f"thread-{i}", "events": [[n, s * MS, d * MS] for n, s, d in events]}
             for i, events in enumerate(threads)]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": lines}]}


def test_consecutive_spans_under_one_gap_each_get_their_own_share():
    """A serving step's serial part: three spans in a row under one idle gap
    of 5.5 ms. The old rule gave all 5.5 to the second, which overlaps most."""
    step = [["engine.step", 9.0, 8.0],  # the parent of the three: nothing of the gap is its own
            ["engine.schedule", 10.0, 1.0], ["engine.launch", 11.0, 2.3], ["engine.readback", 13.3, 2.2]]
    gaps = dict(tr.idle_gaps(_one_gap(step)))
    assert gaps == {"engine.schedule": pytest.approx(0.001), "engine.launch": pytest.approx(0.0023),
                    "engine.readback": pytest.approx(0.0022)}
    assert sum(gaps.values()) == pytest.approx(0.0055)


def test_a_gap_goes_to_the_busier_thread_alone_and_the_rest_is_unattributed():
    step = [["engine.schedule", 10.0, 1.0], ["engine.launch", 11.5, 3.0]]  # covers 4 of 5.5 ms
    other = [["asyncio.select", 9.0, 3.0], ["asyncio.send", 14.9, 2.0]]    # covers 2.6
    for threads in ((step, other), (other, step)):  # whichever the trace lists first
        gaps = dict(tr.idle_gaps(_one_gap(*threads)))
        assert gaps == {"engine.schedule": pytest.approx(0.001), "engine.launch": pytest.approx(0.003),
                        "unattributed": pytest.approx(0.0015)}
    # under the floor a gap is not looked at, whoever was busy in it
    short = dict(tr.idle_gaps(_one_gap(step, gap=(10.0, 10.04))))
    assert short == {"shorter_gaps_not_looked_at": pytest.approx(0.00004)}


def test_spans_that_begin_together_or_overlap_without_nesting_are_still_divided_once():
    # two spans begin together: the shorter is the inner one; "b" begins inside "a" and outlives it
    thread = [["a", 10.0, 3.0], ["a.inner", 10.0, 1.0], ["b", 12.0, 3.5]]
    gaps = dict(tr.idle_gaps(_one_gap(thread)))
    assert gaps == {"a.inner": pytest.approx(0.001), "a": pytest.approx(0.001), "b": pytest.approx(0.0035)}


def test_a_trace_without_device_operations_is_refused():
    t = _hand_made()
    t["planes"][0]["lines"][1]["events"] = []
    with pytest.raises(ValueError, match="no device operation"):
        tr.busy(t)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_chat-paced.json")) as f:
        return json.load(f)


def test_reduction_on_the_recorded_trace(recorded):
    with open(os.path.join(HERE, "data", "trace_chat-paced.expected.json")) as f:
        want = json.load(f)
    b = tr.busy(recorded)
    assert b["busy_s"] == pytest.approx(want["busy_s"]) and b["window_s"] == pytest.approx(want["window_s"])
    assert 0.0 < b["busy_s"] < b["window_s"]
    specs = {
        "prefill": {"kind": "device_trace", "reduce": "program_median_ms", "name_regex": "paged_prefill_step"},
        "decode": {"kind": "device_trace", "reduce": "program_median_ms", "name_regex": "paged_decode_step"},
        "idle": {"kind": "device_trace", "reduce": "idle_share"},
    }
    got = lm.read_all(specs, lm.Observed(trace=recorded))
    assert got == pytest.approx(want["metrics"])
    # what the chip showed in every run of PR 23: one decode step is 42 ms on the device
    assert got["decode"] == pytest.approx(42.0, abs=0.5)
    assert got["prefill"] == pytest.approx(30.2, abs=0.5)  # the one chunk in this piece: the 256 bucket
    gaps = tr.idle_gaps(recorded, n=1000)
    assert gaps[0][0] == "np.asarray_jax.Array_"  # the host reading logits and sampling
    # divided, not awarded: the rows still sum to the first device's idle time, as under the rule before
    assert sum(v for _, v in gaps) == pytest.approx(want["window_s"] - want["busy_s"])  # 0.2091 s: one device
