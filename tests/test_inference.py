"""Inference engine tests: paged KV cache, continuous batching, engine
edge cases (ISSUE 4). Everything here is CPU-runnable and cluster-free —
the engine is plain in-process machinery; serve integration is covered
in test_serve_llm.py."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.inference.engine import (  # noqa: E402
    EngineConfig,
    EngineDrainingError,
    InferenceEngine,
    RequestFailedError,
)
from ray_tpu.inference.kv_cache import PagedBlockManager  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.inference.scheduler import (  # noqa: E402
    FAILED,
    QUEUED,
    ContinuousBatchingScheduler,
    Request,
)
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.models.xing4 import Xing4Config  # noqa: E402


# every test that takes ``cfg`` runs on both cache layouts: K and V rows a
# head (``models/llama.py``) and one latent row (``models/xing4.py``)
@pytest.fixture(scope="module", params=["kv", "latent"])
def cfg(request):
    return LlamaConfig.tiny() if request.param == "kv" else Xing4Config.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))


_dense_fwd = {}


def _dense_greedy(cfg, params, prompt, n):
    # fixed-shape jitted reference: pad to max_seq_len so every step hits
    # ONE compiled program (an unjitted growing-length loop dominates the
    # module's wall time on CPU); causal masking makes the padding inert
    fwd = _dense_fwd.get(cfg)
    if fwd is None:
        fwd = _dense_fwd[cfg] = jax.jit(
            lambda p, t: model_of(cfg).forward(cfg, p, t)
        )
    toks = list(prompt)
    out = []
    for _ in range(n):
        padded = np.zeros((1, cfg.max_seq_len), np.int32)
        padded[0, : len(toks)] = toks
        logits = fwd(params, padded)
        nxt = int(jnp.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
    return out


# ---------------------------------------------------------------------------
# host-side accounting (no jax compute)


def test_block_manager_alloc_free_evict():
    mgr = PagedBlockManager(num_blocks=8, block_size=4)
    assert mgr.usable_blocks == 7  # block 0 reserved
    assert mgr.grow_to("a", 9)  # 3 blocks
    assert mgr.used_blocks == 3
    assert 0 not in mgr.owned("a")  # null block never handed out
    # all-or-nothing: 5 more blocks don't fit 4 free
    assert not mgr.grow_to("b", 20)
    assert mgr.owned("b") == []
    assert mgr.grow_to("b", 16)  # 4 blocks: exactly fits
    assert mgr.free_blocks == 0
    row = mgr.table_row("a", 6)
    assert len(row) == 6 and row[3:] == [0, 0, 0]
    assert mgr.evict("a") == 3
    assert mgr.total_evictions == 1
    assert mgr.free_blocks == 3
    assert mgr.free("b") == 4
    assert mgr.stats()["utilization"] == 0.0


def test_scheduler_admission_queues_then_admits():
    mgr = PagedBlockManager(num_blocks=5, block_size=4)  # 4 usable
    sched = ContinuousBatchingScheduler(mgr, max_decode_batch=4)
    a = Request("a", prompt=list(range(1, 12)))  # needs 3 blocks (12 tokens)
    b = Request("b", prompt=list(range(1, 8)))  # needs 2 blocks
    sched.add(a)
    sched.add(b)
    plan = sched.schedule()
    # a admitted; b queued behind the exhausted pool (1 block free < 2)
    assert [r.request_id for r in sched.running] == ["a"]
    assert sched.queue_depth() == 1
    assert plan.prefills and plan.prefills[0][0] is a
    sched.finish(a)  # a's blocks return to the pool
    sched.schedule()
    assert [r.request_id for r in sched.running] == ["b"]
    assert sched.queue_depth() == 0
    assert sched.total_admitted == 2


def test_scheduler_preempts_lowest_priority_for_block_growth():
    mgr = PagedBlockManager(num_blocks=6, block_size=4)  # 5 usable
    sched = ContinuousBatchingScheduler(mgr, max_decode_batch=4)
    lo = Request("lo", prompt=list(range(1, 8)), priority=0)  # 2 blocks
    hi = Request("hi", prompt=list(range(1, 8)), priority=1)  # 2 blocks
    sched.add(lo)
    sched.add(hi)
    sched.schedule()
    assert len(sched.running) == 2 and mgr.free_blocks == 1
    # both decode-ready with 8 cached tokens; growing past 2 blocks
    for r in (lo, hi):
        r.prefill_pos = len(r.prompt)
        r.generated.extend([5] * 4)  # context 11 -> needs 3 blocks
    plan = sched.schedule()
    # hi grew into the free block; lo's growth preempted... nobody —
    # lo is the only candidate lower than itself, so ordering matters:
    # hi (priority 1) schedules first, takes the free block; lo then
    # needs one more and evicts... only hi is left, which outranks it —
    # lo stalls instead of preempting higher-priority work.
    assert hi in plan.decodes
    assert lo not in plan.decodes
    assert lo in sched.running  # stalled, not evicted
    # now the roles reverse: drop hi's priority below lo's and grow again
    hi.priority = -1
    lo.generated.extend([5] * 1)
    plan = sched.schedule()
    assert lo in plan.decodes
    assert hi.state == QUEUED and hi.preemptions == 1
    assert sched.waiting[0] is hi  # readmission from the queue FRONT
    assert mgr.total_evictions == 1


# ---------------------------------------------------------------------------
# paged forward correctness


def test_paged_prefill_decode_matches_dense(cfg, params):
    runner = PagedModelRunner(
        cfg, params, num_blocks=32, block_size=8,
        prefill_buckets=(4, 8), decode_buckets=(1, 4),
    )
    mgr = PagedBlockManager(32, 8)
    rs = np.random.RandomState(7)
    state = {}
    for rid, n in (("r0", 11), ("r1", 5), ("r2", 9)):
        prompt = [int(x) for x in rs.randint(1, cfg.vocab_size, size=n)]
        mgr.grow_to(rid, n + 1)
        row = mgr.table_row(rid, runner.max_blocks_per_seq)
        pos = 0
        while pos < n:  # chunked prefill, chunks of <= 4
            chunk = prompt[pos : pos + 4]
            logits = runner.prefill_chunk(chunk, row, pos)
            pos += len(chunk)
        state[rid] = {"prompt": prompt, "gen": [int(logits.argmax())]}
    for _ in range(5):  # batched decode across all three requests
        rids = list(state)
        toks, poss, rows, cls = [], [], [], []
        for rid in rids:
            st = state[rid]
            p = len(st["prompt"]) + len(st["gen"]) - 1
            mgr.grow_to(rid, p + 2)
            toks.append(st["gen"][-1])
            poss.append(p)
            rows.append(mgr.table_row(rid, runner.max_blocks_per_seq))
            cls.append(p + 1)
        logits = runner.decode(toks, poss, rows, cls)
        for rid, lg in zip(rids, logits):
            state[rid]["gen"].append(int(lg.argmax()))
    for st in state.values():
        assert st["gen"] == _dense_greedy(cfg, params, st["prompt"], 6)


# ---------------------------------------------------------------------------
# engine edge cases


@pytest.fixture(scope="module")
def engine(cfg, params):
    ec = EngineConfig(
        num_blocks=64, block_size=8, prefill_buckets=(8, 16),
        decode_buckets=(1, 2, 4, 8), max_decode_batch=8,
        max_new_tokens_default=8,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    yield eng
    eng.stop()


def test_engine_concurrent_streams_match_dense_zero_recompiles(cfg, params, engine):
    rs = np.random.RandomState(3)
    prompts = [
        [int(x) for x in rs.randint(1, cfg.vocab_size, size=n)]
        for n in (5, 9, 12, 4, 7, 6)
    ]
    results = {}

    def consume(i):
        results[i] = list(engine.generate(prompts[i], max_new_tokens=6))

    threads = [threading.Thread(target=consume, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    for i, p in enumerate(prompts):
        assert results[i] == _dense_greedy(cfg, params, p, 6), f"prompt {i}"
    # fixed-shape buckets: warmup compiled one program per bucket and
    # serving added NOTHING
    assert engine.runner.recompiles_after_warmup() == 0
    # prefill + decode buckets + the COW block-copy program
    assert engine.runner.compile_count() == 2 + 4 + 1
    # all blocks returned
    assert engine.blocks.used_blocks == 0


def test_engine_temperature_sampling_reproducible(cfg, engine):
    prompt = [3, 1, 4, 1, 5]
    a = list(engine.generate(prompt, max_new_tokens=6, temperature=0.8, seed=42))
    b = list(engine.generate(prompt, max_new_tokens=6, temperature=0.8, seed=42))
    assert a == b
    assert len(a) == 6


def test_engine_block_exhaustion_queues_then_admits(cfg, params):
    # pool fits ONE max-length sequence (plus null): the second request
    # must wait in the admission queue until the first finishes
    ec = EngineConfig(
        num_blocks=9, block_size=8, prefill_buckets=(16,),
        decode_buckets=(1, 2), max_decode_batch=2, max_new_tokens_default=8,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        p1 = [1, 2, 3] * 5  # 15 tokens -> 2 blocks, grows while decoding
        p2 = [4, 5, 6] * 5
        r1 = eng.submit(p1, max_new_tokens=30)  # ends holding 6 blocks
        # give r1's prefill a head start so it holds the pool
        deadline = time.monotonic() + 10
        while eng.blocks.used_blocks == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        r2 = eng.submit(p2, max_new_tokens=30)
        saw_queued = False
        for _ in range(1000):
            if eng.scheduler.queue_depth() > 0:
                saw_queued = True
                break
            time.sleep(0.001)
        out1 = list(eng.tokens(r1, timeout=30))
        out2 = list(eng.tokens(r2, timeout=30))
        assert saw_queued, "second request never waited for blocks"
        assert out1 == _dense_greedy(cfg, params, p1, 30)
        assert out2 == _dense_greedy(cfg, params, p2, 30)
        assert eng.scheduler.total_admitted == 2
        assert eng.blocks.used_blocks == 0
    finally:
        eng.stop()


def test_engine_mid_decode_cancellation_frees_blocks(cfg, params):
    ec = EngineConfig(
        num_blocks=32, block_size=8, prefill_buckets=(16,),
        decode_buckets=(1,), max_decode_batch=1,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=500)
        it = eng.tokens(rid, timeout=30)
        first = [next(it), next(it)]  # stream is live mid-decode
        assert len(first) == 2
        assert eng.blocks.used_blocks > 0
        assert eng.cancel(rid)
        # stream terminates (cancel surfaces as clean end-of-stream)
        rest = list(it)
        assert len(rest) < 500
        deadline = time.monotonic() + 10
        while eng.blocks.used_blocks and time.monotonic() < deadline:
            time.sleep(0.002)
        assert eng.blocks.used_blocks == 0
        assert not eng.scheduler.has_work()
    finally:
        eng.stop()


def test_engine_preemption_readmission_matches_dense(cfg, params):
    # pool too small for two grown sequences: the lower-priority request
    # gets evicted mid-decode and must re-prefill prompt+generated on
    # readmission — its final stream must still match dense greedy.
    ec = EngineConfig(
        num_blocks=11, block_size=8, prefill_buckets=(16, 32),
        decode_buckets=(1, 2), max_decode_batch=2, max_new_tokens_default=40,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        lo_p = [1, 2, 3, 4, 5, 6, 7] * 2  # 14 tokens
        hi_p = [8, 9, 10, 11, 12, 13] * 2  # 12 tokens
        lo = eng.submit(lo_p, max_new_tokens=40, priority=0)
        hi = eng.submit(hi_p, max_new_tokens=40, priority=1)
        out_lo = list(eng.tokens(lo, timeout=60))
        out_hi = list(eng.tokens(hi, timeout=60))
        assert out_hi == _dense_greedy(cfg, params, hi_p, 40)
        assert out_lo == _dense_greedy(cfg, params, lo_p, 40)
        assert eng.blocks.used_blocks == 0
    finally:
        eng.stop()


def test_engine_drain_finishes_in_flight_rejects_new(cfg, params):
    ec = EngineConfig(
        num_blocks=64, block_size=8, prefill_buckets=(16,),
        decode_buckets=(1, 2, 4), max_decode_batch=4,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        rids = [eng.submit([1 + i, 2, 3], max_new_tokens=30) for i in range(3)]
        eng.begin_drain(grace_s=30)
        with pytest.raises(EngineDrainingError):
            eng.submit([9, 9, 9])
        # every in-flight stream completes cleanly inside the grace
        for i, rid in enumerate(rids):
            out = list(eng.tokens(rid, timeout=30))
            assert out == _dense_greedy(cfg, params, [1 + i, 2, 3], 30)
        assert eng.wait_idle(timeout=10)
    finally:
        eng.stop()


def test_engine_drain_grace_expiry_fails_stragglers(cfg, params):
    ec = EngineConfig(
        num_blocks=64, block_size=8, prefill_buckets=(16,),
        decode_buckets=(1,), max_decode_batch=1,
    )
    eng = InferenceEngine(cfg, params, ec)  # NOT started: nothing decodes
    try:
        rid = eng.submit([1, 2, 3], max_new_tokens=5)
        eng.begin_drain(grace_s=0.0)  # grace already over
        eng.start()
        with pytest.raises(RequestFailedError):
            list(eng.tokens(rid, timeout=30))
    finally:
        eng.stop()


def test_engine_expired_deadline_fails_request(cfg, params):
    ec = EngineConfig(
        num_blocks=32, block_size=8, prefill_buckets=(16,), decode_buckets=(1,),
        max_decode_batch=1,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        rid = eng.submit([1, 2, 3], max_new_tokens=5, timeout_s=0.0)
        with pytest.raises(RequestFailedError):
            list(eng.tokens(rid, timeout=30))
        assert eng.blocks.used_blocks == 0
    finally:
        eng.stop()


def test_engine_stops_when_a_step_loses_the_donated_cache(cfg, params):
    """Every step donates the KV cache (on the CPU backend too). A step
    that raises after its jit call consumed the buffer leaves nothing to
    serve from: the request fails, the loop stops and healthy() turns
    False (the serve controller's cue to replace the replica) — the
    engine must not keep stepping on a deleted buffer."""
    ec = EngineConfig(
        num_blocks=32, block_size=8, prefill_buckets=(16,), decode_buckets=(1,),
        max_decode_batch=1, warmup=False,
    )
    eng = InferenceEngine(cfg, params, ec)
    real = eng.runner._prefill_jit

    def consume_then_fail(p, cache, *rest):
        real(p, cache, *rest)  # donates ``cache``; its new value is dropped
        raise RuntimeError("device step failed after donation")

    eng.runner._prefill_jit = consume_then_fail
    eng.start()
    try:
        rid = eng.submit([1, 2, 3], max_new_tokens=2)
        with pytest.raises(RuntimeError, match="after donation"):
            list(eng.tokens(rid, timeout=30))
        eng._thread.join(timeout=10)
        assert not eng._thread.is_alive()
        assert not eng.healthy()
    finally:
        eng.stop()


def test_engine_rejects_batch_beyond_buckets(cfg, params):
    """A decode batch cap the compiled bucket set can't cover must fail
    at init, not as a repeated runtime fail-all inside step()."""
    with pytest.raises(ValueError, match="decode bucket"):
        InferenceEngine(
            cfg,
            params,
            EngineConfig(
                num_blocks=32, block_size=8, prefill_buckets=(16,),
                decode_buckets=(1, 2), max_decode_batch=4,
            ),
        )


def test_tokens_timeout_keeps_stream_resumable(cfg, params):
    """An inter-token timeout raises TimeoutError but must NOT tear down
    the stream: the request keeps running and a retry resumes (a popped
    queue would silently drop every later token and KeyError the retry)."""
    ec = EngineConfig(
        num_blocks=32, block_size=8, prefill_buckets=(16,), decode_buckets=(1,),
        max_decode_batch=1,
    )
    eng = InferenceEngine(cfg, params, ec)  # NOT started: no tokens flow yet
    try:
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(TimeoutError):
            next(eng.tokens(rid, timeout=0.05))
        eng.start()
        assert len(list(eng.tokens(rid, timeout=30))) == 4
        assert eng.blocks.used_blocks == 0
    finally:
        eng.stop()


def test_expired_request_behind_stuck_head_is_reaped():
    """Deadline expiry must sweep the WHOLE admission queue, not just the
    head: an expired request parked behind a non-admittable head fails
    promptly instead of hanging its caller until the head admits."""

    class _Expired:
        expired = True

    mgr = PagedBlockManager(4, 4)  # 3 usable blocks
    sched = ContinuousBatchingScheduler(mgr)
    head = Request(request_id="head", prompt=list(range(40)))  # needs 11 blocks: stuck
    behind = Request(request_id="behind", prompt=[1, 2], deadline=_Expired())
    sched.add(head)
    sched.add(behind)
    plan = sched.schedule()
    assert behind in plan.reaped and behind.state == FAILED
    assert head.state == QUEUED and sched.queue_depth() == 1


def test_abandoned_finished_stream_is_reaped(cfg, params):
    """A caller that submits and never drains (gave up without cancel())
    must not pin its token queue in the replica forever — the engine reaps
    finished-but-undrained streams after finished_stream_ttl_s."""
    ec = EngineConfig(
        num_blocks=32, block_size=8, prefill_buckets=(16,), decode_buckets=(1,),
        max_decode_batch=1, finished_stream_ttl_s=0.2,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        rid = eng.submit([1, 2, 3], max_new_tokens=3)
        deadline = time.monotonic() + 10
        while rid in eng._out and time.monotonic() < deadline:
            time.sleep(0.05)
        assert rid not in eng._out and rid not in eng._finished_at
        with pytest.raises(KeyError):
            next(eng.tokens(rid))
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# prefix caching (ISSUE 7): radix reuse, COW, refcount accounting


def test_prefix_cache_manager_hit_lru_and_refcounts():
    """Host-side radix-index mechanics: full blocks registered, hit,
    shared refcounted, revived off the LRU, and reclaimed under pool
    pressure — no jax involved."""
    mgr = PagedBlockManager(8, 4, prefix_cache_enabled=True)  # 7 usable
    toks = list(range(10, 22))  # 12 tokens = 3 full blocks
    assert mgr.grow_to("a", 12)
    assert mgr.register_prefix("a", toks) == 3
    assert mgr.free("a") == 3
    # unreferenced cached blocks count as FREE capacity (reclaimable),
    # but stay indexed until pressure needs them
    assert mgr.used_blocks == 0 and mgr.cached_blocks == 3
    # partial-prefix hit: 2 of 3 blocks match, third diverges
    cached, cow = mgr.acquire_prefix("b", toks[:8] + [99, 98, 97, 96])
    assert cached == 8 and cow == []
    shared = mgr.owned("b")
    assert len(shared) == 2 and all(mgr.refcount(x) == 1 for x in shared)
    assert mgr.grow_to("b", 13)  # tail blocks from free/LRU
    # pool pressure reclaims the remaining unreferenced cached block
    # (b holds 4: 2 shared + 2 private; c's 3 drain free list + LRU)
    assert mgr.grow_to("c", 4 * (7 - 3 - 1))
    assert mgr.free_blocks == 0
    stats = mgr.prefix_stats()
    assert stats["indexed_blocks"] < 3  # LRU eviction dropped index entries
    mgr.free("b")
    mgr.free("c")
    assert mgr.used_blocks == 0


def test_prefix_cache_cow_under_preemption_accounting():
    """COW + sharer eviction accounting: evicting one sharer leaves the
    other's blocks intact (refcount decrement, not a free), readmission
    re-acquires from the cache, and after everything finishes the free /
    cached / refcount books balance exactly."""
    mgr = PagedBlockManager(8, 4, prefix_cache_enabled=True)  # 7 usable
    p = list(range(30, 38))  # 8 tokens = 2 full blocks
    # A: admit, prefill, register its prompt blocks
    assert mgr.grow_to("A", 9)  # 3 blocks
    assert mgr.register_prefix("A", p) == 2
    a_blocks = mgr.owned("A")
    # B shares A's prompt blocks (prefix hit) + 1 private tail block
    cached, cow = mgr.acquire_prefix("B", p + [50, 51])
    assert cached == 8 and cow == []
    assert mgr.owned("B")[:2] == a_blocks[:2]
    assert mgr.grow_to("B", 11)
    assert [mgr.refcount(x) for x in a_blocks[:2]] == [2, 2]
    used_with_sharing = mgr.used_blocks
    assert used_with_sharing == 4  # 3 (A) + 1 private tail (B)
    # evict the sharer (preemption): shared blocks survive for A,
    # B's private tail returns to the pool
    assert mgr.evict("B") == 3
    assert mgr.total_evictions == 1
    assert [mgr.refcount(x) for x in a_blocks[:2]] == [1, 1]
    assert mgr.used_blocks == 3 and mgr.owned("A") == a_blocks
    # readmission hits the cache again — near-free re-prefill
    cached, _ = mgr.acquire_prefix("B", p + [50, 51])
    assert cached == 8
    assert mgr.grow_to("B", 11)
    # finish both: refcounts drain to zero, registered blocks park on
    # the LRU (still free capacity), private blocks go straight back
    mgr.free("B")
    mgr.free("A")
    assert mgr.used_blocks == 0
    assert mgr.free_blocks == 7
    assert mgr.cached_blocks == 2
    assert all(mgr.refcount(x) == 0 for x in range(1, 8))
    # full-prompt hit takes the COW path: last shared block duplicated
    cached, cow = mgr.acquire_prefix("C", p)
    assert cached == len(p) - 1  # one token recomputes into the copy
    assert len(cow) == 1
    src, dst = cow[0]
    assert mgr.owned("C")[-1] == dst and mgr.refcount(src) == 1  # pinned
    mgr.cow_copied("C")
    assert mgr.refcount(src) == 0  # pin released, back to the cache
    assert mgr.cow_copies_total == 1
    mgr.free("C")
    assert mgr.used_blocks == 0 and mgr.free_blocks == 7


def test_engine_shared_prefix_matches_dense_with_zero_recompiles(cfg, params, engine):
    """Two requests sharing a system prompt: the second's prefill skips
    the cached blocks yet streams IDENTICAL tokens to the uncached dense
    reference, with zero post-warmup recompiles; an exact full-prompt
    repeat exercises the COW path and also matches."""
    ps0 = engine.blocks.prefix_stats()
    sys_prompt = [91, 17, 53, 28, 64, 39, 75, 46] * 2  # 16 tokens = 2 blocks
    tails = ([101, 7], [55, 9])
    outs = [
        list(engine.generate(sys_prompt + t, max_new_tokens=6)) for t in tails
    ]
    for t, out in zip(tails, outs):
        assert out == _dense_greedy(cfg, params, sys_prompt + t, 6)
    # exact repeat of a FULL prompt: every block hits -> COW + 1-token tail
    rep1 = list(engine.generate(sys_prompt, max_new_tokens=6))
    rep2 = list(engine.generate(sys_prompt, max_new_tokens=6))
    assert rep1 == rep2 == _dense_greedy(cfg, params, sys_prompt, 6)
    ps1 = engine.blocks.prefix_stats()
    assert ps1["hits_total"] - ps0["hits_total"] >= 2  # warm tail + repeat
    assert ps1["tokens_saved_total"] - ps0["tokens_saved_total"] >= 16 + 15
    assert ps1["cow_copies_total"] - ps0["cow_copies_total"] >= 1
    assert engine.runner.recompiles_after_warmup() == 0
    assert engine.blocks.used_blocks == 0  # every request's refs released
