"""ISSUE 17: cluster-wide KV prefix tier — integrity-checked fault-in,
live decode migration, warm replica restart.

Layers under test, cheapest first:

* ``KvTierFaultPlan`` — seeded grammar/phase/skip-window/cap semantics
  and the master-seed (``testing_chaos_seed``) derivation fold;
* spill-vs-drop books balance — ``PagedBlockManager`` eviction and the
  tier write-back are ONE policy decision point: every evicted indexed
  block is exactly one of spilled / dropped, referenced blocks are
  never offered, and a broken policy hook degrades to drop;
* daemon-less tier registry — publish/fetch/delete/list roundtrip via
  the inline-descriptor fallback, with the chaos modes driving the
  integrity gate (corrupt payload refused, missing/stale fall through
  fast);
* router tier directory — live-holder one-hop retraction vs dead-holder
  TTL retention, and the chain-digest prefix matcher that builds the
  ``kv_tier`` request spec;
* cluster-free engine/server roundtrips — prefill write-back on one
  engine faulted in by another (byte-exact, prefix-warm), the counted
  fallback ladder under armed chaos, and drain-with-migration flushing
  prompt+generated KV for a survivor to resume from.

The one-cluster E2E chaos gate (hot replica SIGKILLed mid-decode: plan
OFF resumes via tier fault-in with ZERO replay tokens; plan armed
falls back byte-exact) lives in tests/test_stream_resume_tier.py with
the other stream-resume E2E suites.
"""

import pytest

from ray_tpu.util.chaos import KvTierFaultPlan, derive_plan_seed

pytest.importorskip("jax")

import jax  # noqa: E402

from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.kv_cache import PagedBlockManager, _chain_digest  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.models.xing4 import Xing4Config  # noqa: E402

#: 24 tokens = 3 full blocks at block_size 8
SHARED = [12, 7, 3, 9, 1, 5, 2, 8] * 3


# every test that takes ``cfg`` runs on both cache layouts: K and V rows a
# head (``models/llama.py``) and one latent row (``models/xing4.py``)
@pytest.fixture(scope="module", params=["kv", "latent"])
def cfg(request):
    return LlamaConfig.tiny() if request.param == "kv" else Xing4Config.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))


def _ec(**overrides):
    kw = dict(
        num_blocks=64, block_size=8, prefill_buckets=(8, 32),
        decode_buckets=(1, 4), max_decode_batch=4, max_new_tokens_default=8,
        warmup=False, kv_transfer_enabled=True, kv_tier_enabled=True,
    )
    kw.update(overrides)
    return EngineConfig(**kw)


@pytest.fixture(autouse=True)
def _clean_tier():
    """Every test starts and ends with an empty local tier and no
    surgically-armed plan — _LOCAL_TIER is process-global state."""
    from ray_tpu.inference import kv_transfer

    yield
    with kv_transfer._LOCAL_TIER_LOCK:
        kv_transfer._LOCAL_TIER.clear()
    kv_transfer.testing_tier_plan = None


def _digests(tokens, bs=8):
    """Full-block chain digests of ``tokens`` (the tier's key space)."""
    out, prev = [], b""
    for end in range(bs, len(tokens) + 1, bs):
        prev = _chain_digest(prev, tokens[end - bs : end])
        out.append(prev)
    return out


def _collect(gen):
    """Flatten LLMServer.generate's TokenChunk bursts — the serve
    router does the same before clients see individual items."""
    return [t for chunk in gen for t in chunk]


# ---------------------------------------------------------------------------
# unit: KvTierFaultPlan


def test_kv_tier_fault_plan_grammar_and_determinism():
    with pytest.raises(ValueError):
        KvTierFaultPlan("missing_block", 1)  # no prob
    with pytest.raises(ValueError):
        KvTierFaultPlan("explode:1.0", 1)  # unknown mode

    # same seed -> identical schedule over an identical consult sequence
    phases = ["fault_in"] * 6 + ["migration"] * 4 + ["fault_in"] * 6
    p1 = KvTierFaultPlan("corrupt_block:0.5:0:99", 77)
    p2 = KvTierFaultPlan("corrupt_block:0.5:0:99", 77)
    s1 = [p1.consult(ph) for ph in phases]
    assert s1 == [p2.consult(ph) for ph in phases]
    assert p1.consults == len(phases)
    # block-fault modes never fire on the migration phase
    assert all(
        v is None for v, ph in zip(s1, phases) if ph == "migration"
    )

    # skip window: param=2 skips the first two matching consults
    p = KvTierFaultPlan("missing_block:1.0:2:99", 3)
    got = [p.consult("fault_in") for _ in range(4)]
    assert got[:2] == [None, None]
    assert got[2] == ("missing_block", 2.0)

    # default cap: one injection per process, then the plan goes quiet
    p = KvTierFaultPlan("missing_block:1.0", 3)
    fired = [p.consult("fault_in") for _ in range(5)]
    assert fired.count(("missing_block", 0.0)) == 1 and p.injections == 1

    # kill_mid_migration matches ONLY the migration phase
    p = KvTierFaultPlan("kill_mid_migration:1.0", 9)
    assert p.consult("fault_in") is None
    assert p.consult("migration") == ("kill_mid_migration", 0.0)


def test_kv_tier_plan_derives_from_master_chaos_seed():
    """The composite-chaos fold: one logged master seed reproduces the
    tier plan's full schedule (conftest prints the one-line repro)."""
    master = 20260806
    seed = derive_plan_seed(master, "kv_tier")
    assert seed == derive_plan_seed(master, "kv_tier")  # stable
    assert seed != derive_plan_seed(master, "replica")  # per-label
    a = KvTierFaultPlan("missing_block:0.3:0:99", seed)
    b = KvTierFaultPlan("missing_block:0.3:0:99", seed)
    phases = ["fault_in"] * 32
    assert [a.consult(p) for p in phases] == [b.consult(p) for p in phases]


# ---------------------------------------------------------------------------
# unit: spill-vs-drop books balance (the unlocking refactor)


def _balanced(mgr):
    assert (
        mgr.prefix_evictions_total
        == mgr.prefix_spilled_total + mgr.prefix_dropped_total
    ), (mgr.prefix_evictions_total, mgr.prefix_spilled_total,
        mgr.prefix_dropped_total)


def test_spill_vs_drop_books_balance():
    """Every evicted indexed block is EXACTLY one of spilled or dropped
    (evictions == spilled + dropped at every step), the policy hook only
    ever sees unreferenced blocks, popularity decides the verdict, and
    both ``_evict_indexed_locked`` call sites — allocation-pressure LRU
    reclaim and the register cap-eviction — run the same policy."""
    T = [31, 4, 44, 18] * 2  # 8 tokens = 2 full blocks at bs 4
    offered = []

    mgr = PagedBlockManager(8, 4, prefix_cache_enabled=True)

    def hook(digest, blk, hits):
        offered.append((digest, blk, hits, mgr._ref.get(blk, 0)))
        return hits > 0  # spill popular, drop cold

    mgr.set_spill_hook(hook)

    # index two blocks, release them to the LRU
    assert mgr.grow_to("a", 8)
    assert mgr.register_prefix("a", T) == 2
    mgr.free("a")
    # one popularity hit on both blocks (9-token prompt: no COW path)
    cached, cow = mgr.acquire_prefix("b", T + [99])
    assert cached == 8 and not cow
    mgr.free("b")

    # allocation pressure: 7 blocks needed, 5 free -> reclaims both LRU
    # blocks through the ONE policy point; hits==1 -> spilled
    assert mgr.grow_to("c", 28)
    _balanced(mgr)
    assert mgr.prefix_evictions_total == 2
    assert mgr.prefix_spilled_total == 2 and mgr.prefix_dropped_total == 0
    assert [h for _, _, h, _ in offered] == [1, 1]

    # index c's blocks cold (never acquired), free, then evict under
    # pressure again: hits==0 -> dropped
    U = list(range(100, 128))  # 28 tokens, distinct from T
    assert mgr.register_prefix("c", U) == 7
    mgr.free("c")
    assert mgr.grow_to("d", 28)
    _balanced(mgr)
    assert mgr.prefix_evictions_total == 9
    assert mgr.prefix_dropped_total == 7
    mgr.free("d")

    # the hook NEVER saw a referenced block
    assert all(ref == 0 for _, _, _, ref in offered), offered

    # stats surface the split for the metrics endpoint
    st = mgr.prefix_stats()
    assert st["spilled_total"] == 2 and st["dropped_total"] == 7


def test_spill_hook_cap_eviction_and_broken_hook():
    # cap-eviction call site: prefix_cache_max_blocks forces the
    # register path itself through the policy point
    seen = []
    mgr = PagedBlockManager(8, 4, prefix_cache_enabled=True,
                            prefix_cache_max_blocks=1)
    mgr.set_spill_hook(lambda d, b, h: seen.append(b) or True)
    assert mgr.grow_to("a", 8)
    assert mgr.register_prefix("a", [1, 2, 3, 4]) == 1
    mgr.free("a")
    assert mgr.grow_to("b", 4)
    assert mgr.register_prefix("b", [9, 9, 9, 9]) == 1
    _balanced(mgr)
    assert mgr.prefix_evictions_total == 1 and mgr.prefix_spilled_total == 1
    assert len(seen) == 1
    mgr.free("b")

    # a hook that raises degrades to drop — never to a stuck pool
    mgr2 = PagedBlockManager(4, 4, prefix_cache_enabled=True)

    def broken(d, b, h):
        raise RuntimeError("policy crashed")

    mgr2.set_spill_hook(broken)
    assert mgr2.grow_to("a", 4)
    assert mgr2.register_prefix("a", [5, 6, 7, 8]) == 1
    mgr2.free("a")
    assert mgr2.grow_to("b", 12)  # needs all 3 usable -> evicts the block
    _balanced(mgr2)
    assert mgr2.prefix_dropped_total == 1 and mgr2.prefix_spilled_total == 0


# ---------------------------------------------------------------------------
# unit: daemon-less tier registry + integrity gate


def test_local_tier_roundtrip_delete_and_cap():
    import numpy as np

    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.inference import kv_transfer

    kv = np.arange(2 * 2 * 1 * 8 * 2 * 16, dtype=np.float32).reshape(
        2, 2, 1, 8, 2, 16
    )
    d1, d2, d3 = _digests([1] * 8 + [2] * 8 + [3] * 8)
    desc = kv_transfer.tier_publish(d1, kv, 8)
    assert desc is not None and desc["tier_digest"] == d1.hex()
    assert d1.hex() in kv_transfer.tier_list()

    f = kv_transfer.tier_fetch(desc)
    try:
        assert np.array_equal(f.array, kv)
    finally:
        f.close()
    # tier reads keep the source: a second fault-in still succeeds
    f2 = kv_transfer.tier_fetch(desc)
    f2.close()

    kv_transfer.tier_delete(d1.hex(), desc=desc)
    assert d1.hex() not in kv_transfer.tier_list()

    # bounded registry: oldest entry evicted at kv_tier_max_entries
    old_cap = GLOBAL_CONFIG.kv_tier_max_entries
    GLOBAL_CONFIG.kv_tier_max_entries = 2
    try:
        for d in (d1, d2, d3):
            assert kv_transfer.tier_publish(d, kv, 8) is not None
        entries = kv_transfer.tier_list()
        assert d1.hex() not in entries
        assert d2.hex() in entries and d3.hex() in entries
    finally:
        GLOBAL_CONFIG.kv_tier_max_entries = old_cap


def test_daemon_tier_popularity_eviction_hot_prefix_outlives_cold():
    """PR 19 satellite: the daemon registry's cap eviction is keyed on
    (hit count, recency), not insertion age — a hot shared prefix that
    readers keep faulting in outlives colder NEWER entries. Drives the
    real NodeDaemon registry methods on a stub (no cluster, no sockets:
    the registry touches only its own dicts + store.delete)."""
    import asyncio
    from collections import OrderedDict

    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.node_daemon import NodeDaemon

    class _Stub:
        class store:  # noqa: N801 — _kv_tier_drop_locked calls .delete
            @staticmethod
            def delete(oid):
                pass

    stub = _Stub()
    stub._kv_tier = OrderedDict()
    stub._last_kv_tier_sweep = 0.0
    stub._kv_tier_sweep = NodeDaemon._kv_tier_sweep.__get__(stub)
    stub._kv_tier_drop_locked = NodeDaemon._kv_tier_drop_locked.__get__(stub)

    def put(d):
        stub._last_kv_tier_sweep = -1e9  # defeat the 1s sweep throttle
        assert asyncio.run(
            NodeDaemon.d_kv_tier_put(stub, {"digest": d, "desc": {"d": d}}, None)
        )

    def get(d):
        return asyncio.run(NodeDaemon.d_kv_tier_get(stub, {"digest": d}, None))

    old_cap = GLOBAL_CONFIG.kv_tier_max_entries
    GLOBAL_CONFIG.kv_tier_max_entries = 3
    try:
        for d in ("hot", "cold1", "cold2"):
            put(d)
        for _ in range(4):  # the shared prefix keeps getting faulted in
            assert get("hot") == {"d": "hot"}
        # two colder NEWER entries arrive over cap: the zero-hit ones go
        # (oldest-recency first), the hot OLDEST entry survives both
        put("new1")
        assert set(stub._kv_tier) == {"hot", "cold2", "new1"}
        put("new2")
        assert set(stub._kv_tier) == {"hot", "new1", "new2"}
        # a re-put of a live digest counts as a use too
        put("new1")
        assert stub._kv_tier["new1"]["hits"] == 1
        # TTL still dominates popularity: an expired hot entry drops
        stub._kv_tier["hot"]["expiry"] = -1.0
        stub._last_kv_tier_sweep = -1e9
        NodeDaemon._kv_tier_sweep(stub)
        assert "hot" not in stub._kv_tier
        assert get("hot") is None
    finally:
        GLOBAL_CONFIG.kv_tier_max_entries = old_cap


def test_tier_fetch_chaos_modes_hit_the_integrity_gate():
    import numpy as np

    from ray_tpu.inference import kv_transfer

    kv = np.ones((2, 2, 1, 8, 2, 16), dtype=np.float32)
    (d1,) = _digests([4] * 8)
    desc = kv_transfer.tier_publish(d1, kv, 8)
    assert desc is not None

    # corrupt_block: the digest-before-attach gate MUST refuse it
    kv_transfer.testing_tier_plan = KvTierFaultPlan("corrupt_block:1.0", 5)
    with pytest.raises(kv_transfer.KvTransferError, match="digest"):
        kv_transfer.tier_fetch(desc)

    # missing_block: fails fast, entry untouched
    kv_transfer.testing_tier_plan = KvTierFaultPlan("missing_block:1.0", 5)
    with pytest.raises(kv_transfer.KvTransferError, match="missing"):
        kv_transfer.tier_fetch(desc)
    assert d1.hex() in kv_transfer.tier_list()

    # stale_advert: the entry is deleted under the reader, the pull
    # falls through immediately (no source, no timeout)
    kv_transfer.testing_tier_plan = KvTierFaultPlan("stale_advert:1.0", 5)
    with pytest.raises(kv_transfer.KvTransferError):
        kv_transfer.tier_fetch(desc)
    assert d1.hex() not in kv_transfer.tier_list()

    # plan exhausted (cap 1 per rule): the same descriptor now fetches
    # clean — chaos injects faults, it doesn't poison state
    desc2 = kv_transfer.tier_publish(d1, kv, 8)
    f = kv_transfer.tier_fetch(desc2)
    f.close()


# ---------------------------------------------------------------------------
# unit: router tier directory — retraction, TTL, chain matching


class _FakeHandle:
    def __init__(self, aid):
        self.actor_id = aid


def _routing_set(entries, stamp):
    """[(handle, adverts-dict)] -> controller routing_set triples."""
    return [
        (h, (), {"stats": {"prefix_digest": [], "kv_tier": adv},
                 "age_s": 0.0, "stamp": stamp})
        for h, adv in entries
    ]


def test_router_tier_retraction_and_dead_holder_ttl():
    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.observability.rpc_metrics import KV_TIER_RETRACTIONS
    from ray_tpu.serve.router import Router

    r = Router(None, "t")
    a = _FakeHandle("actor-a")
    d1, d2 = _digests([7] * 8 + [8] * 8)
    desc = {"block_size": 8}
    before = KV_TIER_RETRACTIONS._values.get((), 0.0)

    r._apply(_routing_set([(a, {d1.hex(): desc, d2.hex(): desc})], 1))
    assert set(r._tier_dir) == {d1.hex(), d2.hex()}

    # live holder drops d2 from its advert set -> ONE-HOP retraction
    r._apply(_routing_set([(a, {d1.hex(): desc})], 2))
    assert set(r._tier_dir) == {d1.hex()}
    assert KV_TIER_RETRACTIONS._values.get((), 0.0) - before == 1

    # the holder DIES (gone from the routing set): death is NOT
    # retraction — the daemon still owns the bytes, the entry stays
    # for the warm replacement...
    r._apply([])
    assert set(r._tier_dir) == {d1.hex()}
    assert KV_TIER_RETRACTIONS._values.get((), 0.0) - before == 1

    # ...but not forever: the dead-holder TTL bounds it
    old_ttl = GLOBAL_CONFIG.kv_tier_advert_ttl_s
    GLOBAL_CONFIG.kv_tier_advert_ttl_s = 0.0
    try:
        r._apply([])
        assert not r._tier_dir
    finally:
        GLOBAL_CONFIG.kv_tier_advert_ttl_s = old_ttl


def test_router_tier_attach_matches_consecutive_chain():
    from ray_tpu.serve.router import Router

    r = Router(None, "t")
    a = _FakeHandle("actor-a")
    prompt = SHARED + [77]  # 25 tokens: 3 full blocks + tail
    d1, d2, d3 = _digests(prompt)
    desc = {"block_size": 8}

    # nothing advertised -> no spec (and short prompts never match)
    assert r._tier_attach(prompt) is None

    r._apply(_routing_set([(a, {d1.hex(): desc, d3.hex(): desc})], 1))
    # d2 missing: the chain stops at the first gap — d3 is unreachable
    spec = r._tier_attach(prompt)
    assert spec["tokens"] == 8 and [b[0] for b in spec["blocks"]] == [d1.hex()]

    r._apply(_routing_set([(a, {d.hex(): desc for d in (d1, d2, d3)})], 2))
    spec = r._tier_attach(prompt)
    assert spec["tokens"] == 24
    assert [b[0] for b in spec["blocks"]] == [d1.hex(), d2.hex(), d3.hex()]
    # a prompt inside one block has no full-block prefix to attach
    assert r._tier_attach(prompt[:8]) is None


# ---------------------------------------------------------------------------
# cluster-free: engine write-back -> cross-server fault-in


def test_tier_fault_in_across_servers_byte_exact(cfg, params):
    """Engine A's prefill write-back lands in the (local) tier; server B
    faults it in from a router-built spec and produces the byte-exact
    sequence with the prefix provably warm (KV_TIER_HITS + radix hits).
    Then the armed fallback ladder: every fetch fails, the stream is
    STILL byte-exact, and the fallback is counted. Finally corrupt_block
    chaos: the digest-before-attach gate refuses the tampered payload —
    a corrupt tier can cost warmth, never correctness."""
    from ray_tpu.inference import kv_transfer
    from ray_tpu.inference.serve_llm import LLMServer
    from ray_tpu.observability.rpc_metrics import (
        KV_TIER_FALLBACKS, KV_TIER_HITS, KV_TIER_PUBLISHES,
    )

    prompt = SHARED + [77]
    ref = InferenceEngine(cfg, params, _ec(kv_tier_enabled=False)).start()
    try:
        expected = list(
            ref.generate(prompt, max_new_tokens=6, temperature=0.7, seed=3)
        )
    finally:
        ref.stop()

    pubs_before = KV_TIER_PUBLISHES._values.get(("prefill",), 0.0)
    a = InferenceEngine(cfg, params, _ec()).start()
    try:
        out_a = list(
            a.generate(prompt, max_new_tokens=6, temperature=0.7, seed=3)
        )
        assert out_a == expected
        # write-backs publish on a background thread now (REVIEW: the
        # daemon RPC must never stall the step thread) — flush turns
        # the deferral into a happens-before for the advert asserts
        assert a.flush_tier_writebacks()
        adverts = a.routing_stats()["kv_tier"]
        chain = _digests(prompt)
        assert all(d.hex() in adverts for d in chain), list(adverts)
        assert KV_TIER_PUBLISHES._values.get(("prefill",), 0.0) > pubs_before
        spec = {
            "blocks": [[d.hex(), adverts[d.hex()]] for d in chain],
            "tokens": 24,
        }
    finally:
        a.stop()

    hits_before = KV_TIER_HITS._values.get((), 0.0)
    b = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        out_b = _collect(b.generate({
            "prompt": prompt, "max_new_tokens": 6,
            "temperature": 0.7, "seed": 3, "kv_tier": dict(spec),
        }))
        assert out_b == expected
        assert KV_TIER_HITS._values.get((), 0.0) - hits_before >= 3
        assert b.engine.blocks.prefix_tokens_saved_total >= 24
    finally:
        b.engine.stop()

    # armed ladder: missing_block on EVERY fetch -> counted fallback,
    # plain prefill, same bytes
    fb_before = sum(KV_TIER_FALLBACKS._values.values())
    c = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        c.testing_arm_kv_tier_chaos("missing_block:1.0:0:99", 13)
        out_c = _collect(c.generate({
            "prompt": prompt, "max_new_tokens": 6,
            "temperature": 0.7, "seed": 3, "kv_tier": dict(spec),
        }))
        assert out_c == expected
        assert sum(KV_TIER_FALLBACKS._values.values()) > fb_before
        assert c.engine.blocks.prefix_tokens_saved_total == 0
    finally:
        kv_transfer.testing_tier_plan = None
        c.engine.stop()

    # corrupt_block chaos between publish and fault-in: the
    # digest-before-attach gate refuses the tampered payload, the
    # fallback is counted, and the stream is byte-exact via plain
    # prefill (same spec, same expected bytes)
    fb_before = sum(KV_TIER_FALLBACKS._values.values())
    d = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        d.testing_arm_kv_tier_chaos("corrupt_block:1.0:0:99", 17)
        out_d = _collect(d.generate({
            "prompt": prompt, "max_new_tokens": 6,
            "temperature": 0.7, "seed": 3, "kv_tier": dict(spec),
        }))
        assert out_d == expected
        assert sum(KV_TIER_FALLBACKS._values.values()) > fb_before
        assert d.engine.blocks.prefix_tokens_saved_total == 0
    finally:
        kv_transfer.testing_tier_plan = None
        d.engine.stop()


# ---------------------------------------------------------------------------
# cluster-free: live decode migration (drain flushes prompt+generated)


def test_drain_migration_flushes_full_kv_and_survivor_resumes(cfg, params):
    """begin_drain(migrate=True) mid-decode: the in-flight request fails
    with the resumable migration marker, its FULL written KV — prompt
    AND generated — is tier-resident, and a survivor resumes the stream
    byte-exact from tier fault-in with the generated prefix warm (the
    state a failover used to re-prefill via replay)."""
    from ray_tpu.inference.kv_transfer import KV_MIGRATION_MARKER
    from ray_tpu.inference.serve_llm import LLMServer
    from ray_tpu.observability.rpc_metrics import KV_TIER_PUBLISHES
    from ray_tpu.util.chaos import ReplicaFaultPlan

    max_new = 20
    ref = InferenceEngine(cfg, params, _ec(kv_tier_enabled=False)).start()
    try:
        expected = list(ref.generate(
            SHARED, max_new_tokens=max_new, temperature=0.7, seed=11
        ))
    finally:
        ref.stop()

    dec_before = KV_TIER_PUBLISHES._values.get(("decode",), 0.0)
    a = InferenceEngine(cfg, params, _ec()).start()
    delivered = []
    try:
        rid = a.submit(
            SHARED, max_new_tokens=max_new, temperature=0.7, seed=11
        )
        it = a.tokens(rid, timeout=120)
        # throttle decode (one surgical stall per step) so the drain
        # deterministically lands mid-stream with >= 9 generated tokens
        # — past the 32-token boundary, so a GENERATED block is among
        # the migrated flush, not just the prompt's
        delivered.append(next(it))
        a.testing_fault_plan = ReplicaFaultPlan("stall:1.0:0.25:9999", 1)
        try:
            for t in it:
                delivered.append(t)
                if len(delivered) == 9:
                    a.begin_drain(migrate=True)
        except Exception as e:  # noqa: BLE001
            assert KV_MIGRATION_MARKER in str(e), e
        else:
            pytest.fail("drain-migration never interrupted the stream")
        d = len(delivered)
        assert 9 <= d < max_new
        assert delivered == expected[:d]
        # prompt+generated full blocks are all tier-resident
        extended = SHARED + delivered
        assert a.flush_tier_writebacks()
        adverts = a.routing_stats()["kv_tier"]
        chain = _digests(extended[: len(extended) - 1])
        assert len(chain) >= 4  # at least one generated-token block
        assert all(dg.hex() in adverts for dg in chain)
        # the generated block was flushed at its decode boundary —
        # already tier-resident BEFORE the drain even ran (a SIGKILL at
        # any point would have been just as recoverable)
        assert KV_TIER_PUBLISHES._values.get(("decode",), 0.0) > dec_before
    finally:
        a.testing_fault_plan = None
        a.stop()

    # survivor: resume exactly as the router would — extended prompt,
    # resume_from=d, tier spec for the extended chain
    b = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        spec = {
            "blocks": [[dg.hex(), adverts[dg.hex()]] for dg in chain],
            "tokens": len(chain) * 8,
        }
        out = _collect(b.generate({
            "prompt": extended, "max_new_tokens": max_new,
            "temperature": 0.7, "seed": 11, "resume_from": d,
            "kv_tier": spec, "request_id": "mig-resume",
        }))
        assert [tok for _, tok in out] == expected[d:]
        assert [seq for seq, _ in out] == list(range(d, max_new))
        assert b.engine.blocks.prefix_tokens_saved_total >= len(chain) * 8 - 8
    finally:
        b.engine.stop()


def test_migrate_mid_prefill_publishes_only_written_blocks(cfg, params):
    """REVIEW (high): blocks are allocated for the WHOLE prompt at
    admission but chunked prefill writes KV incrementally — a drain
    migration landing mid-prefill must flush only positions that were
    actually prefilled, or it adverts never-written device blocks under
    the VALID chain digest of the real tokens and poisons every future
    fault-in of that prefix (the CRC gate covers transport, not
    content)."""
    from ray_tpu.inference.kv_transfer import KV_MIGRATION_MARKER

    prompt = SHARED + [41, 42, 43, 44, 45, 46, 47, 48]  # 32 = 4 blocks
    eng = InferenceEngine(cfg, params, _ec(prefill_buckets=(8,)))
    try:
        rid = eng.submit(prompt, max_new_tokens=4, temperature=0.0)
        # drive ONE step by hand (no step loop running): exactly one
        # 8-token prefill chunk lands -> prefill_pos=8, prefill NOT done
        assert eng.step()
        eng._migrate_on_drain = True
        # the next step migrates first, finds nothing left to launch and
        # delivers the handoff it held
        assert not eng.step()
        adverts = eng.routing_stats()["kv_tier"]
        chain = _digests(prompt)
        # only the chunk that was truly written is tier-resident; the
        # allocated-but-unwritten blocks 2..4 must NOT be published
        assert chain[0].hex() in adverts, list(adverts)
        assert all(dg.hex() not in adverts for dg in chain[1:]), list(adverts)
        with pytest.raises(Exception, match=KV_MIGRATION_MARKER):
            list(eng.tokens(rid, timeout=10))
    finally:
        eng.stop()


def test_tier_namespace_scopes_models(cfg, params):
    """REVIEW (medium): the chain digest names TOKENS and the daemon
    registry is node-global — without model-identity scoping, one model
    can serve another's KV (same architecture, different weights passes
    every shape/dtype gate). Namespaces must be deterministic across
    replicas of one deployment, disjoint across weights, enforced at
    recovery adoption AND at the fault-in consumer."""
    import numpy as np

    from ray_tpu.inference import kv_transfer
    from ray_tpu.inference.serve_llm import LLMServer
    from ray_tpu.observability.rpc_metrics import KV_TIER_FALLBACKS

    params2 = model_of(cfg).init_params(cfg, jax.random.PRNGKey(1))
    a = InferenceEngine(cfg, params, _ec())
    b = InferenceEngine(cfg, params2, _ec())
    same = InferenceEngine(cfg, params, _ec())
    assert a._tier_ns and a._tier_ns == same._tier_ns
    assert a._tier_ns != b._tier_ns

    # node-global registry holds both models' entries for the SAME
    # token chain under disjoint keys; filtered views are disjoint
    kv = np.ones((2, 2, 1, 8, 2, 16), dtype=np.float32)
    (d1,) = _digests([4] * 8)
    da = kv_transfer.tier_publish(d1, kv, 8, ns=a._tier_ns)
    db = kv_transfer.tier_publish(d1, kv, 8, ns=b._tier_ns)
    assert da["tier_ns"] == a._tier_ns and db["tier_ns"] == b._tier_ns
    raw = kv_transfer.tier_list()
    assert f"{a._tier_ns}:{d1.hex()}" in raw
    assert f"{b._tier_ns}:{d1.hex()}" in raw
    assert d1.hex() in kv_transfer.tier_list(ns=a._tier_ns)
    assert d1.hex() in kv_transfer.tier_list(ns=b._tier_ns)
    assert not kv_transfer.tier_list(ns="")

    # warm-restart recovery adopts ONLY its own namespace's entries
    a._tier_recover()
    assert a._tier_adverts[d1.hex()]["tier_ns"] == a._tier_ns
    assert all(v["tier_ns"] == a._tier_ns for v in a._tier_adverts.values())

    # fault-in consumer refuses a foreign-namespace descriptor outright
    # (counted "namespace" rung) and stays byte-exact on plain prefill
    ref = InferenceEngine(cfg, params, _ec(kv_tier_enabled=False)).start()
    try:
        expected = list(ref.generate(
            SHARED + [77], max_new_tokens=4, temperature=0.7, seed=3
        ))
    finally:
        ref.stop()
    srv = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        fb_before = KV_TIER_FALLBACKS._values.get(("namespace",), 0.0)
        out = _collect(srv.generate({
            "prompt": SHARED + [77], "max_new_tokens": 4,
            "temperature": 0.7, "seed": 3,
            "kv_tier": {"blocks": [[d1.hex(), db]], "tokens": 8},
        }))
        assert out == expected
        assert (
            KV_TIER_FALLBACKS._values.get(("namespace",), 0.0) - fb_before
            == 1
        )
        assert srv.engine.blocks.prefix_tokens_saved_total == 0
    finally:
        srv.engine.stop()


def test_covered_but_failed_fault_in_books_replay_shortfall(cfg, params):
    """REVIEW: the router books replayed=0 whenever the attached chain
    COVERS the resume — but the fallback outcome is only known at the
    replica. A covered chain whose fault-in fails must book the
    delivered-region shortfall into the replay counter from the replica
    side, or resume accounting undercounts real replay work."""
    from ray_tpu.inference import kv_transfer
    from ray_tpu.inference.serve_llm import LLMServer
    from ray_tpu.observability.rpc_metrics import (
        STREAM_RESUME_REPLAY_TOKENS,
    )

    max_new, seq = 20, 9
    ref = InferenceEngine(cfg, params, _ec(kv_tier_enabled=False)).start()
    try:
        expected = list(ref.generate(
            SHARED, max_new_tokens=max_new, temperature=0.7, seed=11
        ))
    finally:
        ref.stop()
    extended = SHARED + expected[:seq]  # 33 tokens: the resume prompt

    # a real holder publishes the full chain (prefill write-back)
    a = InferenceEngine(cfg, params, _ec()).start()
    try:
        list(a.generate(extended, max_new_tokens=1, temperature=0.7, seed=2))
        assert a.flush_tier_writebacks()
        adverts = a.routing_stats()["kv_tier"]
        chain = _digests(extended)
        assert all(dg.hex() in adverts for dg in chain)
        spec = {
            "blocks": [[dg.hex(), adverts[dg.hex()]] for dg in chain],
            "tokens": len(chain) * 8,
        }
    finally:
        a.stop()
    # the spec COVERS the stream (router would book replayed=0):
    # 32 >= 33 - 8
    assert spec["tokens"] >= len(extended) - 8

    b = LLMServer(cfg, _ec(), params=params, export_metrics=False)
    try:
        b.testing_arm_kv_tier_chaos("missing_block:1.0:0:99", 13)
        before = STREAM_RESUME_REPLAY_TOKENS._values.get((), 0.0)
        out = _collect(b.generate({
            "prompt": extended, "max_new_tokens": max_new,
            "temperature": 0.7, "seed": 11, "resume_from": seq,
            "kv_tier": dict(spec), "request_id": "rs-shortfall",
        }))
        # byte-exact on the plain-replay rung regardless
        assert [tok for _, tok in out] == expected[seq:]
        # committed=0, so the shortfall is the delivered-region share
        # the router assumed warm: tokens - (P - seq) = 32 - 24 = 8
        assert (
            STREAM_RESUME_REPLAY_TOKENS._values.get((), 0.0) - before == 8
        )
    finally:
        kv_transfer.testing_tier_plan = None
        b.engine.stop()

