"""A window group whose window is NARROWER than the largest prefill chunk
(Laguna-XS.2: 512 under chunks of 1024, blocks of 16, 30 window layers beside
10 full ones), on the host alone: ``_WindowPool``, ``PagedBlockManager.grow_to``,
``ContinuousBatchingScheduler._slide_for_chunk`` and the pool
``InferenceEngine._window_pools`` sizes, at the benchmark's own numbers. Mellum2's
window is exactly one chunk, so until this configuration no test had a chunk
that holds more than a window while it runs and gives most of it back before
the next. No array, no device."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perfbench import families  # noqa: E402
from perfbench.harness import schedule as sch  # noqa: E402
from perfbench.harness.program import engine_config  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.inference.kv_cache import PagedBlockManager, _WindowPool  # noqa: E402
from ray_tpu.inference.scheduler import ContinuousBatchingScheduler, Request  # noqa: E402

W, BS, CHUNK, SLOTS = 512, 16, 1024, 32


def _file(*parts):
    with open(os.path.join(REPO, "perfbench", *parts)) as f:
        return json.load(f)


MODEL = _file("configs", "laguna-xs.2-33b-a3b-ep16.json")
ENGINE = engine_config(MODEL["serving"]["engine"])


def _pools():
    cfg = families.of(MODEL).model_config(MODEL, max_seq_len=MODEL["max_position_embeddings"])
    return InferenceEngine._window_pools(cfg, ENGINE)


def test_the_engine_sizes_the_window_pool_for_a_full_batch_and_two_chunks():
    # the null block, a window and a block's slack a slot, a largest chunk for the request being
    # prefilled and one for the request whose last chunk ran and whose first decode step has not slid it
    assert _pools() == (("window", 1 + SLOTS * (W // BS + 1) + 2 * (CHUNK // BS), W),)
    assert _pools()[0][1] == 1185


@pytest.mark.parametrize("prompt", [1024, 1025, 2047, 2048, 2049, 4096, 5000])
def test_a_chunk_holds_the_window_and_itself_and_gives_the_chunk_back(prompt):
    """Chunk by chunk through one pool: while a chunk of 1024 runs the request
    holds at most 32 + 64 + 1 blocks; before the next it gives back all but the
    window's; once it decodes it holds at most 33; no entry behind ``first
    query - 511`` is ever held."""
    pool = _WindowPool("window", 200, BS, W)
    total = prompt + 1
    peak = 0
    for start in range(0, prompt, CHUNK):
        end = min(start + CHUNK, prompt)
        lo, hi, take = pool.plan("r", start, total if end + 1 == total else end)
        assert take <= len(pool.free)
        pool.slide("r", lo, hi)
        table = pool.tables["r"]
        assert lo == max(0, start - W + 1) // BS and not any(table[:lo]) and all(table[lo:hi])
        peak = max(peak, pool.in_use)
        assert pool.in_use == hi - lo <= W // BS + CHUNK // BS + 1
    assert peak <= 97 and (prompt < 2 * CHUNK or peak >= 96)
    for tokens in range(total, total + 40):  # decode: the query stands at tokens - 1
        lo, hi, _ = pool.plan("r", tokens - 1, tokens)
        pool.slide("r", lo, hi)
        assert pool.in_use <= W // BS + 1 and not any(pool.tables["r"][: max(0, tokens - W) // BS])
    assert pool.released_behind == pool.taken - pool.in_use
    held = pool.in_use
    assert pool.release("r") == held and pool.in_use == 0


def test_an_admitted_request_that_waits_its_turn_holds_a_window_not_a_chunk():
    """Admission asks the window pool for no more than a window of the first
    chunk: four long prompts admitted in one step, one chunk a step, and the
    three that wait hold 32 blocks each (not the chunk's 64: the engine counted
    a slot for 33); the one whose chunk is planned holds the chunk."""
    manager = PagedBlockManager(ENGINE.num_blocks, BS, group="full", windows=list(_pools()))
    scheduler = ContinuousBatchingScheduler(manager, max_decode_batch=SLOTS, max_prefill_chunk=CHUNK)
    reqs = [Request(f"r{i}", [1] * 3000, max_new_tokens=4) for i in range(4)]
    for req in reqs:
        scheduler.add(req)
    plan = scheduler.schedule()
    assert [r.request_id for r, _, _ in plan.prefills] == ["r0"] and len(scheduler.running) == 4
    held = {r.request_id: sum(1 for b in manager.windows[0].tables[r.request_id] if b) for r in reqs}
    assert held == {"r0": CHUNK // BS, "r1": W // BS, "r2": W // BS, "r3": W // BS}


def _drive(scheduler, reqs, new_tokens):
    """The engine's loop without a device: every plan the scheduler makes is
    'run' by moving the requests on, a finished one is handed back."""
    peak_prefilling = peak_decoding = steps = 0
    pool = scheduler.blocks.windows[0]
    while any(not r.finished for r in reqs):
        plan = scheduler.schedule()
        assert plan.prefills or plan.decodes, scheduler.blocks.pool_stats()
        steps += 1
        for req, start, chunk in plan.prefills:
            held = sum(1 for b in pool.tables[req.request_id] if b)
            peak_prefilling = max(peak_prefilling, held)
            assert not any(pool.tables[req.request_id][: max(0, start - W + 1) // BS])
            req.prefill_pos = start + chunk
            if req.prefill_done:
                req.generated.append(1)
        for req in plan.decodes:
            held = sum(1 for b in pool.tables[req.request_id] if b)
            peak_decoding = max(peak_decoding, held)
            assert not any(pool.tables[req.request_id][: max(0, req.context_len - W) // BS])
            req.generated.append(1)
        for req in [r for r in scheduler.running if len(r.generated) >= new_tokens[r.request_id]]:
            scheduler.finish(req)
    return peak_prefilling, peak_decoding, steps


def test_a_full_batch_of_the_cells_requests_never_waits_on_the_sized_pool():
    """32 requests of the cell's own lengths (its multiset, twice over: a
    second round arrives as the first finishes) through a scheduler of the
    engine's settings over the pools the engine sizes: no preemption, no step
    without work, every block back at the end."""
    traffic = _file("traffic", "reason-offline-32.json")
    pairs = sch.length_multiset(traffic["lengths"], traffic["multiset_size"])
    assert len(pairs) == SLOTS and max(p + o for p, o in pairs) <= MODEL["max_position_embeddings"]
    manager = PagedBlockManager(ENGINE.num_blocks, BS, group="full", windows=list(_pools()))
    scheduler = ContinuousBatchingScheduler(
        manager, max_decode_batch=ENGINE.max_decode_batch, max_prefill_chunk=CHUNK,
        max_prefills_per_step=ENGINE.max_prefills_per_step, max_queue_depth=4 * SLOTS,
    )
    reqs, new_tokens = [], {}
    for round_ in range(2):
        for i, (prompt, output) in enumerate(pairs):
            req = Request(f"r{round_}-{i}", [1] * prompt, max_new_tokens=output)
            reqs.append(req)
            new_tokens[req.request_id] = output
    # closed loop: 32 in flight, the next one is added when one finishes
    waiting = list(reqs[SLOTS:])
    for req in reqs[:SLOTS]:
        scheduler.add(req)
    finish = scheduler.finish

    def finish_and_refill(req, *a, **kw):
        done = finish(req, *a, **kw)
        if done and waiting:
            scheduler.add(waiting.pop(0))
        return done

    scheduler.finish = finish_and_refill
    peak_prefilling, peak_decoding, steps = _drive(scheduler, reqs, new_tokens)
    assert scheduler.stats()["total_preempted"] == 0
    assert 64 <= peak_prefilling <= 97 and peak_decoding <= W // BS + 1
    pools = manager.pool_stats()
    assert pools["window"]["in_use"] == pools["full"]["in_use"] == 0
    assert pools["window"]["peak_in_use"] <= 1184 and pools["window"]["released_behind"] > 0
    # blocks come back by sliding INSIDE a prompt's prefill: more than the decode steps alone give
    # (a decode step gives back at most one block in sixteen steps)
    decode_steps = sum(new_tokens.values())
    assert pools["window"]["released_behind"] > decode_steps // BS
    assert pools["full"]["peak_in_use"] < ENGINE.num_blocks - 1
