"""Submit/complete hot-path perf smoke test (tier-1 safe, CPU-only).

Floors are DELIBERATELY generous (~0.1-0.3× of what this box does warm
and idle): the point is to fail loudly when a future change regresses
the submit path by an order of magnitude — cached task-spec templates
dropped, RPC micro-batching disabled, inline returns detouring through
the shm store — not to flake on a noisy CI box.
"""

import os
import time

import pytest

import ray_tpu


def _rate(fn, min_time=0.5):
    fn()  # warmup
    total = 0
    start = time.perf_counter()
    while time.perf_counter() - start < min_time:
        total += fn()
    return total / (time.perf_counter() - start)


def _floored_rate(fn, floor, min_time=0.5):
    """Rate measurement that is LOAD-AWARE on failure: a single sample
    below the floor re-measures twice more and judges the median-of-3 —
    a transient box-load spike (the PR 4 full-suite flake) loses to the
    two clean samples, while a real order-of-magnitude regression fails
    all three. The healthy path stays one sample (no extra suite time)."""
    first = _rate(fn, min_time)
    if first >= floor:
        return first
    samples = sorted([first, _rate(fn, min_time), _rate(fn, min_time)])
    return samples[1]


def test_submit_hot_path_smoke():
    ray_tpu.init(num_cpus=max(4, (os.cpu_count() or 4)))
    try:

        @ray_tpu.remote
        def noop():
            return None

        # warm the pool + template/KV caches
        ray_tpu.get([noop.remote() for _ in range(20)], timeout=120)

        def tasks_async():
            ray_tpu.get([noop.remote() for _ in range(200)], timeout=120)
            return 200

        def tasks_sync():
            ray_tpu.get(noop.remote(), timeout=60)
            return 1

        async_rate = _floored_rate(tasks_async, 250)
        sync_rate = _floored_rate(tasks_sync, 25)

        # inline results: a small result is served from the in-process
        # cache — second get must not pay any RPC (sub-ms even cold-ish)
        ref = noop.remote()
        ray_tpu.get(ref, timeout=60)
        t0 = time.perf_counter()
        for _ in range(50):
            ray_tpu.get(ref, timeout=60)
        cached_get_ms = (time.perf_counter() - t0) * 1000 / 50

        # ~0.1-0.3× of warm-box numbers (tasks_async ≈ 2000-4000/s,
        # tasks_sync ≈ 200-300/s, cached get ≈ 0.01 ms on this class of
        # box): an order-of-magnitude submit-path regression trips these
        # while ambient CI load does not.
        assert async_rate >= 250, f"tasks_async collapsed: {async_rate:.0f}/s"
        assert sync_rate >= 25, f"tasks_sync collapsed: {sync_rate:.0f}/s"
        assert cached_get_ms < 5.0, (
            f"cached inline get took {cached_get_ms:.2f} ms — the owner-side "
            "inline cache is being bypassed"
        )
    finally:
        ray_tpu.shutdown()


def test_decode_step_throughput_smoke():
    """Inference-engine decode floor (cluster-free, toy config): 4
    concurrent requests decode through the batched jitted step at
    ~1500 tokens/s warm on this box — 100/s trips only an
    order-of-magnitude regression (per-token recompiles, the decode
    batch falling apart into singletons, a python hot loop in the
    step path). The SLO ledger (ISSUE 15) is ALWAYS-ON in this path —
    per-token histogram observes, lifecycle stamps, flight-recorder
    inserts — so this floor doubles as the ledger-overhead guard:
    observability can never become the regression."""
    jax = pytest.importorskip("jax")
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.observability import slo

    cfg = LlamaConfig.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    ec = EngineConfig(
        num_blocks=64, block_size=8, prefill_buckets=(8,),
        decode_buckets=(4,), max_decode_batch=4,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        # warm pass (first steps pay dispatch caches, not compiles —
        # warmup=True compiled the buckets at init)
        for r in [eng.submit([1 + i, 2, 3], max_new_tokens=8) for i in range(4)]:
            list(eng.tokens(r, timeout=120))
        t0 = time.perf_counter()
        rids = [eng.submit([1 + i, 2, 3], max_new_tokens=32) for i in range(4)]
        total = sum(len(list(eng.tokens(r, timeout=120))) for r in rids)
        rate = total / (time.perf_counter() - t0)
        assert total == 4 * 32
        assert eng.runner.recompiles_after_warmup() == 0
        assert rate >= 100, f"decode throughput collapsed: {rate:.0f} tokens/s"
        # the ledger provably ran during the measured window (this floor
        # is its overhead gate, so it must not be silently off) and its
        # books balance exactly at quiesce
        deadline = time.monotonic() + 10
        books = eng.ledger_books()
        while time.monotonic() < deadline and not slo.books_balanced(books):
            time.sleep(0.05)
            books = eng.ledger_books()
        assert slo.books_balanced(books), books
        assert books["submitted"] == 8 and books["finished"] == 8, books
        snap = eng.slo_snapshot()
        itl = snap["histograms"]["raytpu_llm_itl_seconds"]["values"]
        assert sum(v[-1] for v in itl.values()) >= 4 * 31, "ITL ledger idle"
    finally:
        eng.stop()


def test_warm_prefix_ttft_and_hit_rate_smoke():
    """Prefix-cache perf gate (cluster-free): a prompt whose blocks are
    already cached must reach its first token FASTER than the cold
    prefill of the same prompt, and the engine must report a nonzero
    prefix hit rate. Judged on the median-of-3 re-measure pattern
    (_floored_rate's shape): the healthy path is one cold/warm pair; a
    suspicious first pair re-measures twice more and the medians decide,
    so a box-load spike loses to the two clean samples while a real
    regression (hits not taken, COW recompiling, prefill not skipped)
    fails all three."""
    jax = pytest.importorskip("jax")
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(max_seq_len=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    ec = EngineConfig(
        num_blocks=72, block_size=16, prefill_buckets=(16, 512),
        decode_buckets=(1,), max_decode_batch=1, max_new_tokens_default=2,
    )
    eng = InferenceEngine(cfg, params, ec).start()
    try:
        import numpy as np

        rs = np.random.RandomState(11)
        prompts = [
            [int(x) for x in rs.randint(1, cfg.vocab_size, size=448)]
            for _ in range(3)
        ]

        def ttft(prompt):
            t0 = time.perf_counter()
            rid = eng.submit(prompt, max_new_tokens=2)
            next(eng.tokens(rid, timeout=120))
            dt = time.perf_counter() - t0
            eng.cancel(rid)
            return dt

        def pair(prompt):
            return ttft(prompt), ttft(prompt)  # cold (populates), warm (hits)

        cold, warm = pair(prompts[0])
        if warm >= cold:  # suspicious: re-measure, judge the medians
            colds, warms = [cold], [warm]
            for p in prompts[1:]:
                c, w = pair(p)
                colds.append(c)
                warms.append(w)
            cold, warm = sorted(colds)[1], sorted(warms)[1]
        assert warm < cold, (
            f"warm-prefix TTFT {warm*1e3:.1f} ms not below cold "
            f"{cold*1e3:.1f} ms — the prefix cache is not skipping prefill"
        )
        ps = eng.blocks.prefix_stats()
        assert ps["hit_rate"] > 0, ps
        assert ps["tokens_saved_total"] >= 447, ps  # full-hit minus 1 token
        assert eng.runner.recompiles_after_warmup() == 0
    finally:
        eng.stop()


def test_ingress_http_path_smoke():
    """HTTP ingress floor (TTFT through the real door and delivered
    tokens/s): 4 concurrent SSE streams through
    the full stack — urllib → aiohttp ingress (bucket + shed policy) →
    router → streaming replica → engine. Warm numbers on this box are
    ~40-150 ms TTFT p50 and hundreds of delivered tokens/s; the floors
    trip only an order-of-magnitude regression (a blocking call parked
    on the ingress event loop, the shed path running per-token, the
    stream detouring through a non-streaming path)."""
    pytest.importorskip("jax")
    import threading

    from ray_tpu import serve
    from ray_tpu.inference.engine import EngineConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.ingress import IngressConfig, http_stream

    ray_tpu.init(num_cpus=max(4, (os.cpu_count() or 4)))
    try:
        ec = EngineConfig(
            num_blocks=64, block_size=8, prefill_buckets=(8, 32),
            decode_buckets=(1, 4), max_decode_batch=4,
        )
        serve.run(serve.llm_deployment(LlamaConfig.tiny(), engine=ec).bind())
        serve.run(
            serve.ingress_deployment(
                "llm", IngressConfig(target="llm"), name="ingress"
            ).bind(),
            name="ingress",
        )
        addr = serve.ingress_addresses("ingress")[0]
        list(http_stream(addr, {"prompt": [1, 2, 3], "max_new_tokens": 4}))

        def one_round():
            n, new_tokens = 4, 16
            ttfts, counts = [], []
            lock = threading.Lock()

            def consume(i):
                t0 = time.perf_counter()
                first, c = None, 0
                for _ in http_stream(
                    addr,
                    {"prompt": [1 + i, 2, 3], "max_new_tokens": new_tokens},
                    tenant=f"t{i}", connect_timeout=120.0,
                ):
                    if first is None:
                        first = time.perf_counter() - t0
                    c += 1
                with lock:
                    ttfts.append(first)
                    counts.append(c)

            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=consume, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            wall = time.perf_counter() - t0
            assert sum(counts) == n * new_tokens, counts
            return sorted(ttfts)[len(ttfts) // 2], sum(counts) / wall

        ttft_p50, goodput = one_round()
        if ttft_p50 > 2.0 or goodput < 20.0:
            # load-aware re-judge (the _floored_rate shape): median-of-3
            rounds = sorted([(ttft_p50, goodput), one_round(), one_round()])
            ttft_p50, goodput = rounds[1]
        assert ttft_p50 < 2.0, f"ingress TTFT p50 collapsed: {ttft_p50:.2f}s"
        assert goodput >= 20.0, f"ingress goodput collapsed: {goodput:.0f} tok/s"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def test_chunk_receive_path_zero_copy_guard():
    """Copy-count guard for the zero-copy data plane (cluster-free): pull
    a multi-chunk object through the RAW path and assert (a) EVERY chunk
    rode the zero-copy receive — raytpu_pull_raw_chunks_total advances by
    exactly the chunk count, so a silent fallback to the pickled copy
    path fails loudly — and (b) the tracemalloc'd python-allocator peak
    during the transfer stays a small fraction of the payload: the
    destination is an mmap-backed shm window (invisible to the traced
    allocator) and the source serves memoryview windows, so any
    full-payload bytes materialization creeping back into either end
    (pickle of bulk, msgpack re-copy, whole-object heap buffer) trips
    the bound."""
    import tracemalloc
    import zlib

    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.ids import JobID, ObjectID, TaskID
    from ray_tpu.core.object_store import ShmStore
    from ray_tpu.core.pull_manager import PullManager
    from ray_tpu.core.rpc import IoThread, RawPayload, RpcClient, RpcServer
    from ray_tpu.observability.rpc_metrics import PULL_CHUNKS, PULL_RAW_CHUNKS

    payload_mb = 16
    chunk_bytes = 1024 * 1024
    payload = bytes(bytearray(range(256)) * (payload_mb * 4096))
    n_chunks = payload_mb  # 16 × 1 MiB
    oid = ObjectID.for_put(TaskID.for_driver(JobID.from_index(9)), 777)

    io = IoThread("copyguard-io")
    old_chunk = GLOBAL_CONFIG.object_transfer_chunk_bytes
    GLOBAL_CONFIG.object_transfer_chunk_bytes = chunk_bytes
    store = ShmStore(capacity_bytes=4 * payload_mb * 1024 * 1024)
    clients = {}

    def peer(host, port):
        key = (host, port)
        if key not in clients:
            clients[key] = RpcClient(host, port, name="copyguard", role="noded")
        return clients[key]

    async def setup():
        server = RpcServer()

        async def object_info(p, conn):
            return {"size": len(payload), "digest": zlib.crc32(payload)}

        async def fetch_chunk(p, conn):
            view = memoryview(payload)[p["offset"] : p["offset"] + p["length"]]
            assert p.get("raw"), "receiver stopped requesting RAW framing"
            return RawPayload(view, meta=zlib.crc32(view))

        server.register("object_info", object_info)
        server.register("fetch_chunk", fetch_chunk)
        port = await server.start()
        return server, port

    server, port = io.run(setup())
    pm = PullManager(store, peer)
    try:
        raw_before = sum(PULL_RAW_CHUNKS._values.values())  # noqa: SLF001
        total_before = sum(PULL_CHUNKS._values.values())  # noqa: SLF001
        tracemalloc.start()
        try:
            reply = io.run(pm.pull(oid, [("127.0.0.1", port)]), timeout=120)
            _current, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert reply.get("segment"), reply
        assert store.read_bytes(oid) == payload  # byte-exact, digest-sealed
        raw_chunks = sum(PULL_RAW_CHUNKS._values.values()) - raw_before  # noqa: SLF001
        chunks = sum(PULL_CHUNKS._values.values()) - total_before  # noqa: SLF001
        assert chunks == n_chunks, (chunks, n_chunks)
        assert raw_chunks == n_chunks, (
            f"only {raw_chunks}/{n_chunks} chunks rode the zero-copy path"
        )
        # generous ceiling (×4 headroom over the observed ~1-2 MiB of
        # transient reader/transport buffers) yet far below the 16 MiB
        # payload: ONE full-payload bytes object would trip it
        assert peak < payload_mb * 1024 * 1024 // 2, (
            f"traced peak {peak / 1e6:.1f} MB — a full-payload copy is back "
            "in the chunk receive path"
        )
    finally:
        GLOBAL_CONFIG.object_transfer_chunk_bytes = old_chunk

        async def teardown():
            for c in clients.values():
                await c.close()
            await server.stop()

        io.run(teardown())
        store.shutdown()
        io.stop()


def test_kv_migration_raw_path_floor_and_receive_pool_reuse():
    """KV-migration tripwires (ISSUE 13), cluster-free over the REAL
    pull path: a migration-shaped payload pulled through PullManager
    must (a) ride the RAW zero-copy receive for EVERY chunk (the
    copy-count tripwire extended to the migration path), (b) clear a
    deliberately generous throughput floor — kv_migration_gbps ~0.1+
    GB/s warm on this box over loopback, floored at 0.02 so only an
    order-of-magnitude regression (per-chunk bytes copies, RAW fallback,
    digest recompute per chunk) trips it — and (c) REUSE the receive
    segment across back-to-back migrations via the daemon-side pool
    (delete with recycle_receive → allocate_receive pool hit), the
    4.4-kernel substitute for MADV_POPULATE."""
    import zlib

    from ray_tpu.core.config import GLOBAL_CONFIG
    from ray_tpu.core.ids import JobID, ObjectID, TaskID
    from ray_tpu.core.object_store import ShmStore
    from ray_tpu.core.pull_manager import PullManager
    from ray_tpu.core.rpc import IoThread, RawPayload, RpcClient, RpcServer
    from ray_tpu.observability.rpc_metrics import PULL_CHUNKS, PULL_RAW_CHUNKS

    payload_mb = 8
    chunk_bytes = 1024 * 1024
    payloads = {
        i: bytes(bytearray((i + j) & 0xFF for j in range(256)) * (payload_mb * 4096))
        for i in (1, 2)
    }
    oids = {
        i: ObjectID.for_put(TaskID.for_driver(JobID.from_index(13)), i)
        for i in (1, 2)
    }
    by_oid = {oids[i].binary(): payloads[i] for i in (1, 2)}

    io = IoThread("kvmig-io")
    old = (
        GLOBAL_CONFIG.object_transfer_chunk_bytes,
        GLOBAL_CONFIG.receive_segment_pool_bytes,
    )
    GLOBAL_CONFIG.object_transfer_chunk_bytes = chunk_bytes
    GLOBAL_CONFIG.receive_segment_pool_bytes = 64 * 1024 * 1024
    store = ShmStore(capacity_bytes=8 * payload_mb * 1024 * 1024)
    clients = {}

    def peer(host, port):
        key = (host, port)
        if key not in clients:
            clients[key] = RpcClient(host, port, name="kvmig", role="noded")
        return clients[key]

    async def setup():
        server = RpcServer()

        async def object_info(p, conn):
            data = by_oid[p["object_id"]]
            return {"size": len(data), "digest": zlib.crc32(data)}

        async def fetch_chunk(p, conn):
            data = by_oid[p["object_id"]]
            view = memoryview(data)[p["offset"] : p["offset"] + p["length"]]
            assert p.get("raw"), "migration receiver stopped requesting RAW"
            return RawPayload(view, meta=zlib.crc32(view))

        server.register("object_info", object_info)
        server.register("fetch_chunk", fetch_chunk)
        port = await server.start()
        return server, port

    server, port = io.run(setup())
    pm = PullManager(store, peer)
    try:
        raw_before = sum(PULL_RAW_CHUNKS._values.values())  # noqa: SLF001
        total_before = sum(PULL_CHUNKS._values.values())  # noqa: SLF001

        t0 = time.perf_counter()
        reply = io.run(pm.pull(oids[1], [("127.0.0.1", port)]), timeout=120)
        dt1 = time.perf_counter() - t0
        assert reply.get("segment"), reply
        assert store.read_bytes(oids[1]) == payloads[1]

        # every migrated chunk rode the zero-copy receive
        n_chunks = payload_mb
        raw = sum(PULL_RAW_CHUNKS._values.values()) - raw_before  # noqa: SLF001
        total = sum(PULL_CHUNKS._values.values()) - total_before  # noqa: SLF001
        assert total == n_chunks and raw == n_chunks, (raw, total, n_chunks)

        # the importer's delete recycles the segment into the pool …
        assert store.delete(oids[1], recycle_receive=True) is True
        assert store.stats()["recv_pool_segments"] == 1, store.stats()

        # … and the NEXT migration reuses it instead of create+zero
        t0 = time.perf_counter()
        reply = io.run(pm.pull(oids[2], [("127.0.0.1", port)]), timeout=120)
        dt2 = time.perf_counter() - t0
        assert reply.get("segment"), reply
        assert store.read_bytes(oids[2]) == payloads[2]
        assert store.stats()["recv_pool_hits"] == 1, store.stats()

        gbps = (2 * payload_mb / 1024) / (dt1 + dt2)
        if gbps < 0.02:  # load-aware re-judge (the _floored_rate shape)
            samples = [gbps]
            for _ in range(2):
                store.delete(oids[2], recycle_receive=True)
                t0 = time.perf_counter()
                io.run(pm.pull(oids[2], [("127.0.0.1", port)]), timeout=120)
                samples.append(
                    (payload_mb / 1024) / (time.perf_counter() - t0)
                )
            gbps = sorted(samples)[1]
        assert gbps >= 0.02, f"kv_migration_gbps collapsed: {gbps:.3f} GB/s"
    finally:
        (
            GLOBAL_CONFIG.object_transfer_chunk_bytes,
            GLOBAL_CONFIG.receive_segment_pool_bytes,
        ) = old

        async def teardown():
            for c in clients.values():
                await c.close()
            await server.stop()

        io.run(teardown())
        store.shutdown()
        io.stop()
