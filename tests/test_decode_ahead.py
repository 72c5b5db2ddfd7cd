"""Launch before read (ISSUE 39): where an arrival could not have had the next
step's slot or chunk anyway (ISSUE 55) the loop launches decode step n + 1
before it reads step n, the rows of n + 1 take their tokens from n's picks on
the device, and every stream is token for token what the synchronous order
gives (an engine driven by ``step()`` from outside the loop
never looks ahead: it is the reference here). CPU, tiny configs, through the
started loop: what is checked is tokens, order and counters, never a speed."""

import os
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "perfbench"))

jax = pytest.importorskip("jax")

import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from ray_tpu.inference.engine import (  # noqa: E402
    _END,
    EngineConfig,
    InferenceEngine,
    RequestFailedError,
)
from ray_tpu.inference.kv_cache import PagedBlockManager  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.inference.scheduler import (  # noqa: E402
    DECODE,
    QUEUED,
    ContinuousBatchingScheduler,
    Request,
)
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.models.xing4 import Xing4Config  # noqa: E402

#: 2 decode slots and 6 requests: the running requests fill the batch (on the
#: model with a state pool 2 run and 4 wait), so the engine is saturated
ENGINE = dict(
    num_blocks=64, block_size=8, prefill_buckets=(16, 32), decode_buckets=(2,),
    max_decode_batch=2, max_queue_depth=16, warmup=False,
)
PROMPTS = [[5, 6, 7, 8] * 3 + [5, 6, 7][: i % 4] + [9 + i] * (i % 3) for i in range(6)]
MODELS = ["llama", "xing4", "kimi_linear"]
KEYS = {"launches", "ahead", "dropped"}

_built = {}


def _model(name):
    """(config, parameters) of a toy model: K and V rows, a latent cache, a
    latent cache beside a state pool."""
    if name not in _built:
        if name == "kimi_linear":
            toy = rehearsal.tiny_config("kimi-linear-48b-a3b-ep16")
            cfg = families.of(toy).model_config(toy, max_seq_len=toy["max_position_embeddings"])
        else:
            cfg = LlamaConfig.tiny() if name == "llama" else Xing4Config.tiny()
        _built[name] = cfg, model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    return _built[name]


def _engine(name, **kw):
    cfg, params = _model(name)
    return InferenceEngine(cfg, params, EngineConfig(**{**ENGINE, **kw}))


def _drain(eng, rid, timeout=60.0):
    q, items = eng._out[rid], []
    while not items or not (items[-1] is _END or isinstance(items[-1], Exception)):
        items.append(q.get(timeout=timeout))
    return items


def _synchronous(name, submit, **kw):
    """The streams of an engine stepped from outside the loop: every launch
    read in the step that made it."""
    eng = _engine(name, **kw)
    rids = submit(eng)
    while eng.scheduler.has_work():
        assert eng.step()
        assert eng._unread is None
    assert eng.stats()["decode_ahead"]["ahead"] == 0
    return [_drain(eng, r, timeout=1) for r in rids]


def _looped(name, submit, **kw):
    eng = _engine(name, **kw)
    rids = submit(eng)  # before start(): the loop plans what the direct steps planned
    eng.start()
    try:
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
        return eng, got
    finally:
        eng.stop()


def _until(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


def _all_returned(eng):
    st = eng.stats()
    assert st["blocks"]["used_blocks"] == 0
    pool = st["state_pool"]
    assert pool["in_use"] == 0 and pool["assigned"] == pool["released"]
    w = st["wakes"]
    assert w["items"] == w["after_launch"] + w["at_idle"] + w["direct"]
    return st["decode_ahead"]


# -- the streams -------------------------------------------------------------------------------------

@pytest.mark.parametrize("model", MODELS)
def test_length_finishes_are_planned_ahead_and_no_row_is_wasted(model):
    submit = lambda eng: [eng.submit(p, max_new_tokens=5 + i) for i, p in enumerate(PROMPTS)]  # noqa: E731
    want = _synchronous(model, submit)
    assert [len(items) for items in want] == [6 + i for i in range(6)]
    eng, got = _looped(model, submit)
    assert got == want
    ahead = _all_returned(eng)
    # a request whose last token is in flight is not launched again: every row
    # of every launch reached its stream. The first token of each is prefill's
    assert ahead["dropped"] == 0
    assert ahead["launches"] > 0 and ahead["ahead"] >= ahead["launches"] // 2
    rows = eng.stats()["decode_width"]["launches"]
    assert rows == ahead["launches"]


@pytest.mark.parametrize("model", MODELS)
def test_an_eos_met_one_step_late_drops_one_result_and_streams_no_stray_token(model):
    plain = lambda eng: [eng.submit(p, max_new_tokens=12) for p in PROMPTS]  # noqa: E731
    free = _synchronous(model, plain)
    # one request stops at a token of its stream that did not occur before (after
    # its second): the host sees it only when it reads the launch, one step late
    which, tokens, at = next(
        (n, items[:-1], i) for n, items in enumerate(free)
        for i in range(2, 11) if items[i] not in items[:i]
    )
    eos = tokens[at]

    def submit(eng):
        return [eng.submit(p, max_new_tokens=12, eos_token=eos if i == which else None)
                for i, p in enumerate(PROMPTS)]

    want = _synchronous(model, submit)
    assert want[which] == tokens[: at + 1] + [_END]
    assert all(want[i] == free[i] for i in range(6) if i != which)
    eng, got = _looped(model, submit)
    assert got == want
    assert _all_returned(eng)["dropped"] == 1


def _at_launch(eng, n, act):
    """Run ``act`` on the step thread inside its ``n``-th decode launch made
    while another is unread: a token of every row is in flight then."""
    launch, seen = eng.runner.launch_decode, []

    def hooked(*a, after=None, **kw):
        if after is not None:
            seen.append(1)
            if len(seen) == n:
                act()
        return launch(*a, after=after, **kw)

    eng.runner.launch_decode = hooked


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_with_a_token_in_flight_streams_a_prefix_and_its_terminal(how):
    submit = lambda eng: [eng.submit(p, max_new_tokens=12) for p in PROMPTS]  # noqa: E731
    want = _synchronous("llama", submit)
    eng = _engine("llama")
    rids = submit(eng)

    def end():
        req = eng._unread.reqs[0]
        assert req.in_flight == 0 and req.state == DECODE
        if how == "cancel":
            assert eng.cancel(req.request_id)
        else:
            req.deadline = SimpleNamespace(expired=True)  # reaped by the next plan
        ended.append(req.request_id)

    ended = []
    _at_launch(eng, 2, end)
    eng.start()
    try:
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
    finally:
        eng.stop()
    for rid, have, full in zip(rids, got, want):
        if rid in ended:
            assert 1 <= len(have) - 1 < len(full) - 1 and have[:-1] == full[: len(have) - 1]
            assert have[-1] is _END if how == "cancel" else isinstance(have[-1], RequestFailedError)
        else:
            assert have == full
    # a cancel ends it at once: the unread launch and the one being made both
    # carried a row of it. A deadline is met by the next plan: the second alone
    assert len(ended) == 1 and _all_returned(eng)["dropped"] == (2 if how == "cancel" else 1)


@pytest.mark.parametrize("model, num_blocks", [("llama", 13), ("kimi_linear", 17)])
def test_a_preempted_request_loses_its_token_in_flight_and_samples_it_again(model, num_blocks):
    rs = np.random.RandomState(4)
    prompts = [[int(t) for t in rs.randint(1, 200, size=n)] for n in (33, 27)]
    submit = lambda eng: [eng.submit(p, max_new_tokens=40) for p in prompts]  # noqa: E731
    # the two sequences grow to 64 + 64 tokens (16 blocks of 8, 12 usable) and to
    # 73 + 67 (19 blocks, 16 usable): the pool runs dry while both decode
    tight = dict(num_blocks=num_blocks)
    want = _synchronous(model, submit, **tight)
    eng, got = _looped(model, submit, **tight)
    assert got == want and all(len(items) > 20 and items[-1] is _END for items in got)
    assert eng.stats()["scheduler"]["total_preempted"] >= 1
    ahead = _all_returned(eng)
    assert ahead["ahead"] > 0 and ahead["dropped"] >= 1


@pytest.mark.parametrize("turn", ["sampled", "speculative"])
def test_a_batch_that_turns_sampled_or_speculative_is_read_at_once(turn):
    kw = dict(speculative_k=3, speculative_draft="ngram") if turn == "speculative" else {}
    late = dict(temperature=0.9, seed=7) if turn == "sampled" else dict(speculative=True)

    def submit(eng, then=lambda: None):
        rids = [eng.submit(p, max_new_tokens=24, speculative=False) for p in PROMPTS[:3]]
        then()
        return rids + [eng.submit(PROMPTS[3], max_new_tokens=10, **late)]

    want = _synchronous("llama", submit, **kw)
    eng = _engine("llama", **kw)
    decided = []
    stays = eng._stays_unread

    def logged(plan, batch):
        plain = batch.greedy and all(r.spec_k == 0 for r in batch.reqs)
        decided.append((plain, stays(plan, batch), eng._unread is None))
        return decided[-1][1]

    eng._stays_unread = logged

    def started():
        eng.start()
        _until(lambda: eng.stats()["decode_ahead"]["ahead"] >= 2)

    try:
        rids = submit(eng, started)
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
    finally:
        eng.stop()
    assert got == want
    # the engine looked ahead before the late request decoded, not while its
    # batch was sampled or might speculate, and nothing was unread at the switch
    assert any(plain and stayed for plain, stayed, _ in decided)
    assert any(not plain for plain, _, _ in decided)
    assert all(not stayed and none_unread for plain, stayed, none_unread in decided if not plain)
    _all_returned(eng)


# -- when it engages ---------------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 3])
def test_an_engine_with_room_reads_every_launch_in_its_own_step_unless_a_chunk_is_due(chunks):
    """2 of 4 slots and nobody waits. Prompts of one chunk: nothing is ever
    left unread. Prompts of three (three times the largest prefill bucket): a
    decode launch stays unread exactly where a request still has prompt left
    once the step's chunk is committed, and the streams are the synchronous
    engine's."""
    kw = dict(max_decode_batch=4, decode_buckets=(4,), prefill_buckets=(8, 16))
    prompts = [(p * chunks)[: 16 * chunks] for p in ([5, 6, 7, 8] * 4, [9, 8, 7, 6, 5] * 4)]
    submit = lambda eng: [eng.submit(p, max_new_tokens=8) for p in prompts]  # noqa: E731
    want = _synchronous("llama", submit, **kw)
    assert [len(items) for items in want] == [9, 9]
    eng = _engine("llama", **kw)
    assert eng.scheduler.max_prefill_chunk == 16
    decided, stays = [], eng._stays_unread

    def logged(plan, batch):
        due = any(not r.prefill_done for r in eng.scheduler.running)
        decided.append((due, stays(plan, batch)))
        return decided[-1][1]

    eng._stays_unread = logged
    rids = submit(eng)
    eng.start()
    try:
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
    finally:
        eng.stop()
    assert got == want
    ahead = _all_returned(eng)
    assert ahead["launches"] == len(decided) >= 7 and ahead["dropped"] == 0
    # the first prompt decodes beside the second's chunks: behind each but the
    # last another is due. Every launch after the last chunk is read at once
    assert [stayed for _due, stayed in decided] == ["chunk" if due else None for due, _ in decided]
    assert [due for due, _ in decided] == [True] * (chunks - 1) + [False] * (len(decided) - chunks + 1)
    assert ahead["ahead"] == eng.stats()["decode_ahead_chunk_due"] == chunks - 1


@pytest.mark.parametrize("model", MODELS)
def test_the_counters_exist_from_construction(model):
    eng = _engine(model)
    assert eng.stats()["decode_ahead"] == dict.fromkeys(KEYS, 0)
    assert eng.stats()["decode_ahead_chunk_due"] == 0
    assert eng._unread is None


@pytest.mark.parametrize("leave", ["stop", "wait_idle", "outside_step", "fail_all"])
def test_nothing_stays_unread(leave):
    eng = _engine("llama")
    rids = [eng.submit(p, max_new_tokens=6 if leave == "wait_idle" else 300) for p in PROMPTS]
    if leave == "outside_step":
        # the loop's own step leaves its launch unread, a step from outside reads both
        assert eng.step(hold_wakes=True) and eng._unread is None  # a chunk alone
        while eng._unread is None:
            assert eng.step(hold_wakes=True)
        assert all(r.in_flight is not None for r in eng._unread.reqs)
        assert eng.step() and eng._unread is None
        assert all(r.in_flight is None for r in eng.scheduler.running)
        assert eng.stats()["decode_ahead"]["ahead"] == 1
        return
    eng.start()
    try:
        _until(lambda: eng.stats()["decode_ahead"]["ahead"] >= 3)
        if leave == "wait_idle":
            assert eng.wait_idle(60)
            assert all(_drain(eng, r)[-1] is _END for r in rids)
        elif leave == "fail_all":
            # from another thread, as a failing caller would: the loop goes on
            eng._fail_all(RequestFailedError("failed"))
            assert all(isinstance(_drain(eng, r)[-1], RequestFailedError) for r in rids)
            assert eng.wait_idle(60)
        else:
            eng.stop()
        assert eng._unread is None
    finally:
        eng.stop()
    assert eng._unread is None and not eng._held
    assert eng.blocks.used_blocks == 0


def test_streams_survive_consumers_and_cancels_on_other_threads():
    """Consumers on threads of their own, half of which cancel mid-stream,
    while the loop looks ahead: each stream is a prefix of the synchronous
    one's tokens."""
    submit = lambda eng: [eng.submit(p, max_new_tokens=24) for p in PROMPTS]  # noqa: E731
    want = [items[:-1] for items in _synchronous("llama", submit)]
    eng = _engine("llama")
    rids = submit(eng)
    seen = {}

    def consume(i, rid):
        items = []
        for token in eng.tokens(rid, timeout=60):
            items.append(token)
            if i % 2 and len(items) == 3 + i:
                eng.cancel(rid)
        seen[i] = items

    threads = [threading.Thread(target=consume, args=(i, r)) for i, r in enumerate(rids)]
    eng.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert eng.wait_idle()
    finally:
        eng.stop()
    for i in range(6):
        # a cancelled stream may hold what was delivered before the cancel landed
        assert seen[i] == want[i][: len(seen[i])] and len(seen[i]) >= (3 + i if i % 2 else 24), i
    assert _all_returned(eng)["ahead"] > 0


# -- the runner's two halves ---------------------------------------------------------------------------

def _runner(name, decode_buckets=(2, 4)):
    cfg, params = _model(name)
    slots = 4 if name == "kimi_linear" else 0
    return PagedModelRunner(cfg, params, num_blocks=32, block_size=8, prefill_buckets=(16,),
                            decode_buckets=decode_buckets, state_slots=slots)


@pytest.mark.parametrize("model", MODELS)
def test_a_row_named_in_the_earlier_picks_decodes_as_the_token_itself(model):
    stateful = model == "kimi_linear"
    logits = []
    for named in (False, True):
        runner = _runner(model)
        runner.warmup()
        rows = [[1 + 2 * i, 2 + 2 * i] + [0] * (runner.max_blocks_per_seq - 2) for i in range(3)]
        slots = [1, 2, 3] if stateful else None
        for i in range(3):
            runner.prefill_chunk(PROMPTS[i][:10], rows[i], 0, slot=i + 1 if stateful else 0)
        first = runner.launch_decode([3, 4, 5], [10] * 3, rows, [11] * 3, slots=slots, greedy=True)
        if named:
            # two rows (out of order) take their tokens from the unread launch,
            # a batch bucket of 2 after one of 4: the same picks, another program
            second = runner.launch_decode([-1 - 2, -1 - 0], [11] * 2, [rows[2], rows[0]], [12] * 2,
                                          slots=[3, 1] if stateful else None, after=first)
            picks = runner.read(first)
        else:
            picks = runner.read(first)
            second = runner.launch_decode([int(picks[2]), int(picks[0])], [11] * 2, [rows[2], rows[0]],
                                          [12] * 2, slots=[3, 1] if stateful else None)
        assert picks.shape == (3,) and picks.dtype == np.int32
        logits.append(runner.read(second))
        assert logits[-1].shape == (2, runner.cfg.vocab_size)
        # one program a batch bucket, whatever bucket made the picks it is handed
        assert runner.recompiles_after_warmup() == 0
        assert runner.compile_count() == 1 + 2 + 1  # the chunk's, a decode program a bucket, the COW copy
    np.testing.assert_array_equal(logits[0], logits[1])


def test_decode_is_launch_and_read_at_once_and_a_name_needs_the_launch_it_names():
    runner = _runner("llama", decode_buckets=(2,))
    row = [1] + [0] * (runner.max_blocks_per_seq - 1)
    runner.prefill_chunk(PROMPTS[0][:8], row, 0)
    # positional, as the benchmark's check calls it
    picks = runner.decode([3], [8], [row], [9], None, None, None, True)
    assert picks.shape == (1,)
    logits = runner.decode([3], [8], [row], [9])
    assert logits.shape == (1, runner.cfg.vocab_size) and int(np.argmax(logits[0])) == int(picks[0])
    with pytest.raises(ValueError, match="names a row"):
        runner.launch_decode([-1], [9], [row], [10])


# -- the plan ------------------------------------------------------------------------------------------

def _planner(num_blocks=16, max_decode_batch=2, **kw):
    blocks = PagedBlockManager(num_blocks, 4)
    return blocks, ContinuousBatchingScheduler(
        blocks, max_decode_batch=max_decode_batch, max_prefill_chunk=16, **kw
    )


def _decoding(sched, rid, prompt_len, generated, max_new):
    req = Request(request_id=rid, prompt=list(range(1, prompt_len + 1)), max_new_tokens=max_new)
    sched.add(req)
    sched.schedule()
    req.prefill_pos, req.state, req.generated = prompt_len, DECODE, list(generated)
    return req


def test_a_token_in_flight_counts_one_position_further():
    blocks, sched = _planner()
    req = _decoding(sched, "a", 7, [1], 8)  # context 8: two blocks hold it
    assert sched.schedule().decodes == [req] and len(blocks.owned("a")) == 2
    req.in_flight = 0
    assert req.ahead == 1
    # the step writes the K/V of the token in flight at position 8: a third block
    assert sched.schedule().decodes == [req] and len(blocks.owned("a")) == 3


@pytest.mark.parametrize("generated, in_flight, planned", [
    (2, None, True), (2, 0, False), (1, 0, True), (1, None, True),
])
def test_a_request_whose_last_token_is_in_flight_is_not_planned_again(generated, in_flight, planned):
    _blocks, sched = _planner()
    last = _decoding(sched, "a", 4, [1] * generated, 3)
    other = _decoding(sched, "b", 4, [1], 9)
    third = _decoding(sched, "c", 4, [1], 9)
    last.in_flight = in_flight
    # its place in the batch of 2 goes to the next request: no row is wasted
    assert sched.schedule().decodes == ([last, other] if planned else [other, third])


def test_a_preempted_request_restarts_without_its_token_in_flight():
    blocks, sched = _planner(num_blocks=5)  # 4 usable blocks of 4
    a = _decoding(sched, "a", 7, [1], 20)
    b = _decoding(sched, "b", 7, [1], 20)
    a.in_flight, b.in_flight = 0, 1
    plan = sched.schedule()  # both need a third block, one is free: b is evicted
    assert plan.decodes == [a] and b.state == QUEUED and sched.total_preempted == 1
    assert b.restart_prompt == b.prompt + [1] and blocks.owned("b") == []
    # the engine clears the mark when it reads (and drops) the launch
    assert b.in_flight == 1 and b not in sched.running


# -- the plan behind a chunk that is due (ISSUE 55) ----------------------------------------------------
# A loop that looks ahead plans step n + 1 while step n runs: an arrival that lands meanwhile meets
# plan n + 2 first. One that does not plans n + 1 once n is read: the same arrival meets plan n + 1.

def _first_chunk_step(left, looks_ahead, priority=0):
    """The number of the step that carries an arrival's first chunk, on an
    engine with room (2 of 4 slots): one request decodes, another has ``left``
    chunks of prompt left once step 0's chunk is committed, and the arrival
    lands while step 0 runs. Also what the engine's predicate says after step 0."""
    _blocks, sched = _planner(num_blocks=64, max_decode_batch=4)
    _decoding(sched, "d", 4, [1], 99)
    sched.add(Request(request_id="r", prompt=[1] * 16 * (left + 1), max_new_tokens=9))
    arrival = Request(request_id="a", prompt=[2] * 16, max_new_tokens=9, priority=priority)
    taken_by = None
    for step in range(left + 3):
        if step == 1 and not looks_ahead:
            sched.add(arrival)  # step 0 was read before step 1 was planned
        plan = sched.schedule()
        if step == 1 and looks_ahead:
            sched.add(arrival)  # step 1 was planned while step 0 ran
        assert len(plan.prefills) <= 1 and len(plan.decodes) >= 1
        for req, start, chunk in plan.prefills:  # committed inside their own step
            if req is arrival:
                return step, taken_by
            req.prefill_pos = start + chunk
            if req.prefill_done:
                req.state, req.generated = DECODE, [1]
        if step == 0:
            taken_by = InferenceEngine._next_step_taken_by(SimpleNamespace(scheduler=sched))
    raise AssertionError("the arrival's chunk was never planned")


@pytest.mark.parametrize("left", [1, 2, 3])
def test_an_arrival_behind_a_due_chunk_gets_its_chunk_in_the_same_step_either_way(left):
    step, taken_by = _first_chunk_step(left, looks_ahead=True)
    assert taken_by == "chunk"  # so the engine does look ahead
    assert (step, taken_by) == _first_chunk_step(left, looks_ahead=False)
    assert step == left + 1  # right behind the older request's last chunk


def test_with_no_chunk_due_looking_ahead_would_cost_an_arrival_a_step_and_the_engine_does_not():
    step, taken_by = _first_chunk_step(0, looks_ahead=False)
    assert (step, taken_by) == (1, None)
    assert _first_chunk_step(0, looks_ahead=True)[0] == 2


def test_an_arrival_of_higher_priority_waits_one_chunk_more_behind_a_due_chunk():
    """What the predicate does not promise: the plan would have given step 1's
    chunk to the arrival of higher priority, and a loop that had planned step 1
    already gives it step 2's, as a full batch's loop already may."""
    assert _first_chunk_step(2, looks_ahead=False, priority=1) == (1, "chunk")
    assert _first_chunk_step(2, looks_ahead=True, priority=1) == (2, "chunk")
    assert _first_chunk_step(2, looks_ahead=True)[0] == 3  # and one of the same priority


def test_every_chunk_of_a_step_must_be_taken_for_the_next_step_to_be():
    _blocks, sched = _planner(num_blocks=64, max_decode_batch=4, max_prefills_per_step=2)
    engine = SimpleNamespace(scheduler=sched)
    _decoding(sched, "d", 4, [1], 99)
    sched.add(Request(request_id="r", prompt=[1] * 48, max_new_tokens=9))
    assert [p[0].request_id for p in sched.schedule().prefills] == ["r"]
    # one request in prefill of two chunks a step: an arrival could have had the other
    assert InferenceEngine._next_step_taken_by(engine) is None
    sched.add(Request(request_id="s", prompt=[1] * 48, max_new_tokens=9))
    assert [p[0].request_id for p in sched.schedule().prefills] == ["r", "s"]
    assert InferenceEngine._next_step_taken_by(engine) == "chunk"
    sched.add(Request(request_id="t", prompt=[1] * 8, max_new_tokens=9))
    sched.schedule()  # four run of four slots
    assert InferenceEngine._next_step_taken_by(engine) == "slot"


# -- the plan over a drafter's window in flight (ISSUE 45) ---------------------------------------------
# A drafter's step commits 1 to 1 + k tokens a row: the plan counts such a request by that RANGE.

def test_a_window_in_flight_counts_from_its_least_to_its_most():
    req = Request(request_id="a", prompt=[1, 2, 3], max_new_tokens=8)
    assert (req.ahead, req.ahead_most) == (0, 0)
    req.in_flight = 1  # a plain decode launch: exactly one token
    assert (req.ahead, req.ahead_most) == (1, 1)
    req.in_flight_most = 2  # a window with one draft riding: one token, or two
    assert (req.ahead, req.ahead_most) == (1, 2)
    req.in_flight = None  # read: what the launch might have committed no longer counts
    assert (req.ahead, req.ahead_most) == (0, 0)


@pytest.mark.parametrize("left, most, planned, drafts", [
    (1, 2, False, 0),  # its last token is CERTAINLY in flight: not planned, no row wasted
    (1, 1, False, 0),
    (2, 2, True, 0),   # it MAY finish in flight (an accepted draft): planned without a draft, dropped if it did
    (2, 1, True, 0),   # one token left after the one in flight: nothing to draft for
    (3, 2, True, 0),   # the most leaves one: no draft; the least would leave two
    (3, 1, True, 1),
    (4, 2, True, 1),   # two left even after the most: a draft rides
])
def test_a_request_near_its_cap_is_planned_by_the_range_in_flight(left, most, planned, drafts):
    _blocks, sched = _planner(num_blocks=32)
    sched.spec_drafts_on_device, sched.spec_max_context = True, 64
    last = _decoding(sched, "a", 4, [1] * (9 - left), 9)
    other = _decoding(sched, "b", 4, [1], 9)
    third = _decoding(sched, "c", 4, [1], 9)
    for r in (last, other, third):
        r.spec_k = 1
    last.in_flight, last.in_flight_most = 0, most
    plan = sched.schedule()
    # its place in the batch of 2 goes to the next request where it is not planned
    assert plan.decodes == ([last, other] if planned else [other, third])
    assert last.spec_step_k == drafts and other.spec_step_k == 1


def test_blocks_grow_to_the_most_a_window_in_flight_may_commit_and_this_steps_window():
    blocks, sched = _planner()
    sched.spec_drafts_on_device, sched.spec_max_context = True, 64
    req = _decoding(sched, "a", 6, [1], 20)  # context 7: two blocks of 4
    req.spec_k = 1
    assert sched.schedule().decodes == [req] and req.spec_step_k == 1
    assert len(blocks.owned("a")) == 2  # positions 6 and 7: the window [x, d]
    req.in_flight, req.in_flight_most = 0, 2
    # the unread step may leave the context at 9, and this step's window writes positions 8 and 9:
    # 10 positions, a third block. With a plain token in flight (most 1): 9, and the same blocks
    assert sched.schedule().decodes == [req] and req.spec_step_k == 1 and len(blocks.owned("a")) == 3
    blocks.trim_to("a", 7)
    req.in_flight_most = 1
    assert sched.schedule().decodes == [req] and len(blocks.owned("a")) == 3  # 7 + 1 + 1 = 9 positions
    blocks.trim_to("a", 7)
    req.in_flight = None
    assert sched.schedule().decodes == [req] and len(blocks.owned("a")) == 2


def test_a_proposer_on_the_host_drafts_nothing_after_a_token_it_has_not_seen():
    _blocks, sched = _planner()
    sched.spec_max_context = 64
    assert sched.spec_drafts_on_device is False
    req = _decoding(sched, "a", 6, [1], 20)
    req.spec_k = 3
    assert sched.schedule().decodes == [req] and req.spec_step_k == 3
    req.in_flight = 0
    assert sched.schedule().decodes == [req] and req.spec_step_k == 0
    sched.spec_drafts_on_device = True  # the model's own drafter: the draft is where the token is
    assert sched.schedule().decodes == [req] and req.spec_step_k == 3


def test_a_preempted_request_restarts_without_its_window_in_flight():
    blocks, sched = _planner(num_blocks=5)  # 4 usable blocks of 4
    sched.spec_drafts_on_device, sched.spec_max_context = True, 64
    a = _decoding(sched, "a", 7, [1], 20)
    b = _decoding(sched, "b", 7, [1], 20)
    for r, row in ((a, 0), (b, 1)):
        r.spec_k, r.in_flight, r.in_flight_most = 1, row, 2
    plan = sched.schedule()  # both need a third block, one is free: b is evicted
    assert plan.decodes == [a] and b.state == QUEUED and sched.total_preempted == 1
    # what the window might have committed is lost with it: the restart is from what the host has
    assert b.restart_prompt == b.prompt + [1] and blocks.owned("b") == []
    assert b.in_flight == 1 and b not in sched.running  # the engine clears the mark when it drops the row
