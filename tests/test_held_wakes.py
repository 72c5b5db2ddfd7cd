"""Emit commits, the wake-ups wait for the launch (ISSUE 28): what the step
thread has for a request's out-queue is held until the next launch is on
its way, and nothing stays held when there is no launch to wait for. CPU,
tiny config, no cluster: what is checked is order and delivery, never a
speed."""

import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from ray_tpu.inference import engine as engine_module  # noqa: E402
from ray_tpu.inference.engine import (  # noqa: E402
    _END,
    EngineConfig,
    InferenceEngine,
    RequestFailedError,
)
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402

ENGINE = dict(
    num_blocks=64, block_size=8, prefill_buckets=(16, 32), decode_buckets=(4,),
    max_decode_batch=4, warmup=False,
)
#: repeats, so that the n-gram proposer has something to propose
PROMPTS = [[5, 6, 7, 8] * 3 + [5, 6, 7][: i % 4] + [9 + i] * (i % 3) for i in range(6)]


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, **kw):
    return InferenceEngine(cfg, params, EngineConfig(**{**ENGINE, **kw}))


def _submit_all(eng, temperature=0.0, new_tokens=10):
    return [
        eng.submit(p, max_new_tokens=new_tokens, temperature=temperature, seed=40 + i)
        for i, p in enumerate(PROMPTS)
    ]


def _drain(eng, rid, timeout=30.0):
    """The out-queue as the step thread filled it: every item up to and
    including the terminal one (``_END`` or an exception)."""
    q, items = eng._out[rid], []
    while not items or not (items[-1] is _END or isinstance(items[-1], Exception)):
        items.append(q.get(timeout=timeout))
    return items


def _wakes_add_up(eng):
    w = eng.stats()["wakes"]
    assert w["items"] == w["after_launch"] + w["at_idle"] + w["direct"]
    assert w["held_s"] >= 0.0
    return w


@pytest.mark.parametrize(
    "kw, temperature",
    [({}, 0.0), ({}, 0.9), (dict(speculative_k=3, speculative_draft="ngram"), 0.0)],
    ids=["greedy", "seeded", "speculative"],
)
def test_the_loop_streams_what_direct_steps_stream(cfg, params, kw, temperature):
    direct = _engine(cfg, params, **kw)
    rids = _submit_all(direct, temperature)
    while direct.scheduler.has_work():
        assert direct.step()
    want = [_drain(direct, r, timeout=1) for r in rids]
    assert all(len(items) == 11 and items[-1] is _END for items in want)

    looped = _engine(cfg, params, **kw)
    rids = _submit_all(looped, temperature)  # before start(): the same steps
    looped.start()
    try:
        assert [_drain(looped, r) for r in rids] == want
        assert looped.wait_idle()
        w = _wakes_add_up(looped)
        # the loop holds across the step boundary, a step() from outside never
        assert w["items"] == 6 * 11 and w["direct"] == 0 and w["after_launch"] > w["at_idle"] > 0
        d = _wakes_add_up(direct)
        assert d["items"] == 6 * 11 and d["at_idle"] == 0 and d["direct"] > 0
    finally:
        looped.stop()


def _record(eng, rids, log):
    """Log, in the step thread's order: each commit (``hold``), each launch
    proper (the runner's, which names its program), each phase opened, and
    each put into a request's queue."""
    hold, phase = eng._hold, eng._clock.phase

    def logged_hold(q, item, now=False):
        log.append(("hold", id(q), item))
        hold(q, item, now)

    def logged_phase(name, **args):
        log.append(("launch",) if "program" in args else ("phase", name))
        return phase(name, **args)

    eng._hold, eng._clock.phase = logged_hold, logged_phase
    for rid in rids:
        q = eng._out[rid]

        def logged_put(item, q=q, put=q.put):
            log.append(("put", id(q), item))
            put(item)

        q.put = logged_put


def test_items_of_one_launch_are_woken_after_the_next_launch_returns(cfg, params):
    eng = _engine(cfg, params)
    rids = _submit_all(eng, new_tokens=6)
    log = []
    _record(eng, rids, log)
    eng.start()
    try:
        for r in rids:
            _drain(eng, r)
        assert eng.wait_idle()
    finally:
        eng.stop()
    w = _wakes_add_up(eng)
    queues = {entry[1] for entry in log if entry[0] == "hold"}
    assert len(queues) == 6
    after_launch = pairs = 0
    for q in queues:
        holds = [i for i, e in enumerate(log) if e[:2] == ("hold", q)]
        puts = [i for i, e in enumerate(log) if e[:2] == ("put", q)]
        # a stream's items arrive in the order they were committed
        assert [log[i][2] for i in holds] == [log[i][2] for i in puts]
        for held_at, put_at in zip(holds, puts):
            pairs += 1
            opened = [e for e in log[held_at:put_at] if e[0] in ("phase", "launch")]
            following = next(e for e in log[put_at:] if e[0] in ("phase", "launch"))
            if following == ("phase", "device_wait") or ("launch",) in opened:
                # a launch has returned and its wait has not begun (ISSUE 39:
                # beside an unread decode launch a step launches its chunk AND
                # its decode batch before it waits, and what it commits between
                # two of its waits goes out before the second: no launch need
                # lie between hold and put; and a launch that stays unread is
                # followed by no wait in its own step)
                after_launch += 1
                before = log[:put_at]
                assert before.count(("launch",)) > before.count(("phase", "device_wait"))
                assert opened[-1] == ("phase", "emit")
            else:
                # nothing to launch: delivered where the step found no work
                assert ("launch",) not in opened
                assert opened[-2:] == [("phase", "schedule"), ("phase", "emit")]
    assert after_launch == w["after_launch"] > 0
    assert pairs == w["items"] == 6 * 7 and w["direct"] == 0


def test_a_step_wakes_so_many_streams_and_each_gets_all_it_has(cfg, params, monkeypatch):
    """More streams than a step may wake: the longest-held go first and take
    every token they have (one wake-up for several), a first token and an end
    go out with the next launch whatever the count, no stream is passed over
    twice in a row, and every stream reads what it read uncapped."""
    direct = _engine(cfg, params)
    rids = _submit_all(direct, new_tokens=12)
    while direct.scheduler.has_work():
        direct.step()
    want = [_drain(direct, r, timeout=1) for r in rids]

    monkeypatch.setattr(engine_module, "_WAKES_PER_STEP", 2)
    eng = _engine(cfg, params)
    rids = _submit_all(eng, new_tokens=12)
    log = []
    _record(eng, rids, log)
    steps, bounds = [], [0]  # a step: the items put to each queue in it, in order; and where it ends in the log
    while eng.scheduler.has_work():
        assert eng.step(hold_wakes=True)
        puts = {}
        for entry in log[bounds[-1]:]:
            if entry[0] == "put":
                puts.setdefault(entry[1], []).append(entry[2])
        steps.append(puts)
        bounds.append(len(log))
    assert eng._held and not eng.step(hold_wakes=True) and not eng._held  # the idle step: all that is left
    assert [_drain(eng, r, timeout=1) for r in rids] == want
    w = _wakes_add_up(eng)
    assert w["items"] == 6 * 13 and w["direct"] == 0 and w["after_launch"] > 0
    started, bursts = set(), 0
    for puts in steps:
        urgent = {q for q, items in puts.items() if q not in started or items[-1] is _END}
        assert len(puts) <= 2 + len(urgent)  # an end committed after the step's two went out goes out too
        bursts += sum(len(items) > 1 for q, items in puts.items() if q not in urgent)
        started |= set(puts)
    assert bursts > 0
    step_of = lambda i: next(n for n, end in enumerate(bounds[1:] + [len(log)]) if i < end)  # noqa: E731
    for q in started:
        holds = [i for i, e in enumerate(log) if e[:2] == ("hold", q)]
        puts = [i for i, e in enumerate(log) if e[:2] == ("put", q)]
        assert [log[i][2] for i in holds] == [log[i][2] for i in puts]
        # 4 decode at once, 2 a step: the step after the next one, and one more where first tokens or ends went first
        assert max(step_of(p) - step_of(h) for h, p in zip(holds, puts)) <= 3


def _until(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


class _Boom(RuntimeError):
    pass


def _end_finish(eng, rid):
    return _END


def _end_cancel(eng, rid):
    assert eng.cancel(rid)
    return _END


def _end_fail_all(eng, rid):
    eng._fail_all(_Boom("failed"))
    return _Boom


def _end_stop(eng, rid):
    eng.stop()
    return RequestFailedError


def _end_drain_expiry(eng, rid):
    eng.begin_drain(grace_s=0.0)
    return RequestFailedError


def _end_step_raises(eng, rid):
    def decode(*a, **kw):
        del eng.runner.launch_decode  # once: the loop keeps serving
        raise _Boom("step")

    eng.runner.launch_decode = decode  # the engine's decode is two calls since ISSUE 39
    return _Boom


@pytest.mark.parametrize(
    "end, others",
    [
        (_end_finish, 1), (_end_finish, 0), (_end_cancel, 1), (_end_fail_all, 1),
        (_end_stop, 1), (_end_drain_expiry, 1), (_end_step_raises, 1),
    ],
    ids=["finish", "finish_then_idle", "cancel", "fail_all", "stop", "drain_expiry",
         "step_raises"],
)
def test_no_terminal_item_stays_held(cfg, params, end, others):
    """However a request ends, its consumer has the terminal item without
    waiting for a launch that may never come: within the idle loop's 5 ms
    wait, here bounded by what a loaded CPU allows."""
    eng = _engine(cfg, params).start()
    try:
        finishing = end is _end_finish
        rid = eng.submit(PROMPTS[0], max_new_tokens=4 if finishing else 400)
        for p in PROMPTS[1 : 1 + others]:
            eng.submit(p, max_new_tokens=400)
        q = eng._out[rid]
        first = q.get(timeout=30)
        assert isinstance(first, int)
        terminal = end(eng, rid)
        t0 = time.monotonic()
        items = [first] + _drain(eng, rid, timeout=10)
        assert time.monotonic() - t0 < 5.0
        assert all(isinstance(t, int) for t in items[:-1])
        if terminal is _END:
            assert items[-1] is _END
            assert not finishing or len(items) == 5
        else:
            assert isinstance(items[-1], terminal)
        if end is not _end_stop:
            # nothing is left held once the loop has nothing to launch
            for other in list(eng._out):
                eng.cancel(other)
            assert eng.wait_idle()
            _until(lambda: not eng._held)
        assert not eng._held
        w = _wakes_add_up(eng)
        if end is _end_finish and not others:
            assert w["at_idle"] >= 1  # the last token and _END, at the least
    finally:
        eng.stop()
    assert not eng._held


def test_a_step_from_outside_the_loop_delivers_before_it_returns(cfg, params):
    eng = _engine(cfg, params)
    rid = eng.submit(PROMPTS[0], max_new_tokens=3)
    q, got = eng._out[rid], []
    while eng.scheduler.has_work():
        assert eng.step()
        assert not eng._held  # step, then read the queue
        while not q.empty():  # this thread is the queue's one consumer
            got.append(q.get_nowait())
        assert got, "the first step prefills and emits"
    assert len(got) == 4 and got[-1] is _END
    w = _wakes_add_up(eng)
    assert w["items"] == 4 and w["at_idle"] == 0
    assert w["direct"] == 4  # one launch a step here: nothing to go out after
    # a loop's own step keeps them: that is the one difference
    rid = eng.submit(PROMPTS[1], max_new_tokens=3)
    assert eng.step(hold_wakes=True)
    assert eng._held  # this step's tokens wait for the next step's launch
    while eng.scheduler.has_work():
        assert eng.step(hold_wakes=True)
    assert eng._held  # the last token and _END: for a step with no launch
    assert not eng.step(hold_wakes=True)
    assert not eng._held and _drain(eng, rid, timeout=1)[-1] is _END
    assert _wakes_add_up(eng)["at_idle"] >= 1


def test_cancels_from_many_threads_keep_each_stream_in_order(cfg, params):
    """The held list is shared by the step thread and whoever cancels: each
    stream is a prefix of its tokens and then exactly one terminal item."""
    direct = _engine(cfg, params)
    rids = _submit_all(direct, new_tokens=24)
    while direct.scheduler.has_work():
        direct.step()
    want = [_drain(direct, r, timeout=1)[:-1] for r in rids]

    eng = _engine(cfg, params).start()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    seen, errors = {}, []

    def consume(i, rid):
        try:
            items = []
            for token in eng.tokens(rid, timeout=30):
                items.append(token)
                if i % 2 and len(items) == 3 + i:
                    eng.cancel(rid)
            seen[i] = items
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(e)

    try:
        for _round in range(3):
            rids = _submit_all(eng, new_tokens=24)
            threads = [
                threading.Thread(target=consume, args=(i, r)) for i, r in enumerate(rids)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not [t for t in threads if t.is_alive()] and not errors
            for i, items in seen.items():
                assert items == want[i][: len(items)]
                assert len(items) == 24 if i % 2 == 0 else len(items) >= 3 + i
            assert eng.wait_idle()
            _until(lambda: not eng._held)
            _wakes_add_up(eng)
    finally:
        sys.setswitchinterval(interval)
        eng.stop()
