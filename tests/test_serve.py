"""ray_tpu.serve tests: deploy/route/scale/HTTP (reference test model:
``serve/tests/`` + ``_private/local_testing_mode.py``)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_deploy_and_call(cluster):
    @serve.deployment(num_replicas=2, ray_actor_options={"num_cpus": 0.25})
    class Adder:
        def __init__(self, bias):
            self.bias = bias

        def __call__(self, x):
            return x + self.bias

        def bias_value(self):
            return self.bias

    handle = serve.run(Adder.bind(10))
    assert ray_tpu.get(handle.remote(5), timeout=60) == 15
    assert ray_tpu.get(handle.method("bias_value")(), timeout=30) == 10
    assert serve.status()["Adder"]["replicas"] == 2
    serve.delete("Adder")


def test_function_deployment(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0.25})
    def double(x):
        return 2 * x

    handle = serve.run(double.bind())
    assert ray_tpu.get(handle.remote(8), timeout=60) == 16
    serve.delete("double")


def test_run_raises_when_replicas_keep_dying_at_start(cluster):
    """serve.run has no clock bound (a replica compiling for minutes is
    healthy); what ends the wait is the controller seeing starters die.
    A constructor that raises must surface, with its reason, in seconds."""

    @serve.deployment(ray_actor_options={"num_cpus": 0.25})
    class Broken:
        def __init__(self):
            raise ValueError("constructor says no")

        def __call__(self, x):
            return x

    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="constructor says no"):
        serve.run(Broken.bind())
    assert time.monotonic() - t0 < 25
    assert serve.status()["Broken"]["restarts"]["start_failed"] >= 3
    serve.delete("Broken")


@pytest.mark.slow
def test_run_waits_for_a_slow_starter(cluster):
    """A replica whose construction outlasts the old fixed 30 s wait is
    still awaited: it is alive, and nothing died."""

    @serve.deployment(ray_actor_options={"num_cpus": 0.25})
    class Slow:
        def __init__(self):
            time.sleep(33)

        def __call__(self, x):
            return x + 1

    handle = serve.run(Slow.bind())
    assert ray_tpu.get(handle.remote(1), timeout=60) == 2
    assert serve.status()["Slow"]["restarts"]["start_failed"] == 0
    serve.delete("Slow")


def test_requests_spread_across_replicas(cluster):
    @serve.deployment(num_replicas=2, ray_actor_options={"num_cpus": 0.25})
    class WhoAmI:
        def __call__(self, _):
            import os

            return os.getpid()

    handle = serve.run(WhoAmI.bind())
    pids = set(
        ray_tpu.get([handle.remote(None) for _ in range(20)], timeout=120)
    )
    assert len(pids) == 2  # pow-2 routing reaches both replicas
    serve.delete("WhoAmI")


def test_replica_failure_recovery(cluster):
    @serve.deployment(num_replicas=2, ray_actor_options={"num_cpus": 0.25})
    class Flaky:
        def __call__(self, x):
            return x

    handle = serve.run(Flaky.bind())
    replicas = ray_tpu.get(
        handle._controller.get_replicas.remote("Flaky"), timeout=30
    )
    ray_tpu.kill(replicas[0])  # kill one replica
    # condition-based wait (controller-side long-poll on its change
    # condition) instead of client sleep-polling: returns the moment the
    # replacement replica is routed. 120s budget: replica respawn
    # includes a fresh worker cold-start, which can take well over 60s
    # on a box saturated by the full suite.
    st = ray_tpu.get(
        handle._controller.wait_status.remote(
            "Flaky", min_replicas=2, quiescent=True, timeout_s=120
        ),
        timeout=150,
    )
    assert st and st["replicas"] == 2, st
    # reconcile loop replaced the dead replica; traffic still flows.
    # Routing is at-most-once: a dispatch racing the replica death can
    # land on the dead actor, so allow a couple of retries.
    result = None
    for _ in range(3):
        try:
            result = ray_tpu.get(handle.remote(7), timeout=60)
            break
        except ray_tpu.RayTpuError:
            time.sleep(1.0)
    assert result == 7
    serve.delete("Flaky")


def test_autoscaling_up_and_down(cluster):
    @serve.deployment(
        ray_actor_options={"num_cpus": 0.1},
        max_concurrent_queries=4,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=1,
            max_replicas=3,
            target_ongoing_requests=1.0,
            upscale_delay_s=0.1,
            downscale_delay_s=0.5,
        ),
    )
    class Slow:
        async def __call__(self, x):
            import asyncio

            await asyncio.sleep(1.0)
            return x

    handle = serve.run(Slow.bind())
    assert serve.status()["Slow"]["replicas"] == 1
    # sustained load so the autoscaler sees ongoing requests, then a
    # condition-based wait for the scale-up (controller-side long-poll
    # instead of client sleep-polling; the load thread keeps requests in
    # flight the whole time). 120s budget: scale-up = actor creation =
    # worker cold boot, which takes >60s when the suite saturates the box.
    import threading

    refs = []
    stop_load = threading.Event()

    def pump():
        while not stop_load.is_set():
            refs.extend(handle.remote(i) for i in range(4))
            stop_load.wait(0.4)

    loader = threading.Thread(target=pump, daemon=True)
    loader.start()
    try:
        st = ray_tpu.get(
            handle._controller.wait_status.remote(
                "Slow", min_replicas=2, timeout_s=120
            ),
            timeout=150,
        )
    finally:
        stop_load.set()
        loader.join(timeout=10)
    assert st and st["replicas"] >= 2, f"should scale up under load: {st}"
    ray_tpu.get(refs, timeout=120)
    # idle: scales back toward min (quiescent: the drain of the surplus
    # replica must have completed too)
    st = ray_tpu.get(
        handle._controller.wait_status.remote(
            "Slow", max_replicas=1, quiescent=True, timeout_s=90
        ),
        timeout=120,
    )
    assert st and st["replicas"] == 1, f"should scale down when idle: {st}"
    serve.delete("Slow")


def test_http_proxy(cluster):
    @serve.deployment(ray_actor_options={"num_cpus": 0.25}, route_prefix="/sq")
    class Square:
        def __call__(self, x):
            return x * x

    serve.run(Square.bind())
    from ray_tpu.serve.controller import get_or_create_controller

    serve.start_http(get_or_create_controller(), port=18114)
    req = urllib.request.Request(
        "http://127.0.0.1:18114/sq",
        data=json.dumps(7).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = json.loads(urllib.request.urlopen(req, timeout=60).read())
    assert resp["result"] == 49
    # unknown route -> 404
    try:
        urllib.request.urlopen("http://127.0.0.1:18114/nope", timeout=30)
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    serve.delete("Square")


def test_dynamic_batching(cluster):
    """@serve.batch: N concurrent requests coalesce into one replica
    call with a list argument (reference serve/batching.py)."""

    @serve.deployment(max_concurrent_queries=16)
    class Batcher:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        async def handle(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        async def __call__(self, x):
            return await self.handle(x)

        async def sizes(self):
            return self.batch_sizes

    handle = serve.run(Batcher.bind(), name="batcher")
    refs = [handle.remote(i) for i in range(8)]
    assert sorted(ray_tpu.get(refs, timeout=60)) == [i * 10 for i in range(8)]
    sizes = ray_tpu.get(handle.method("sizes")(), timeout=30)
    # all 8 concurrent requests should land in few (ideally 1) batches
    assert max(sizes) >= 4, sizes
    assert sum(sizes) == 8, sizes
    serve.delete("Batcher")


def test_batching_error_propagates(cluster):
    @serve.deployment
    class Bad:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        async def handle(self, items):
            raise RuntimeError("batch boom")

        async def __call__(self, x):
            return await self.handle(x)

    handle = serve.run(Bad.bind(), name="bad")
    with pytest.raises(Exception, match="batch boom"):
        ray_tpu.get(handle.remote(1), timeout=60)
    serve.delete("Bad")


def test_rolling_update_zero_downtime(cluster):
    """Redeploying a new version rolls replicas start-before-kill: a
    request stream across the roll never fails, and answers flip to the
    new version (reference deployment_state.py:2331)."""

    @serve.deployment(num_replicas=2, version="v1")
    class Versioned:
        def __init__(self, tag):
            self.tag = tag

        def __call__(self, _x):
            return self.tag

    handle = serve.run(Versioned.bind("v1"), name="versioned")
    assert ray_tpu.get(handle.remote(0), timeout=60) == "v1"

    import threading

    results, errors = [], []
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                # retry-until-executed: the router re-chooses on a
                # death-raced dispatch, so the roll drops ZERO requests
                results.append(handle.call(0, _timeout=30))
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            time.sleep(0.02)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        serve.run(
            Versioned.options(version="v2").bind("v2"), name="versioned"
        )
        # wait for the ROLL to finish (every routed replica on v2, none
        # starting/draining) via the controller's condition-based
        # long-poll — breaking on the first 'v2' response races a
        # legitimately-mixed routing set mid-roll (advisor finding r4)
        ray_tpu.get(
            handle._controller.wait_status.remote(
                "Versioned",
                min_replicas=2,
                quiescent=True,
                version="v2",
                timeout_s=60,
            ),
            timeout=90,
        )
        # a few post-roll requests must all answer v2
        post_roll = [handle.call(0, _timeout=30) for _ in range(3)]
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors[:3]
    assert post_roll == ["v2"] * 3, post_roll
    assert "v1" in results  # the stream spanned the roll
    serve.delete("Versioned")


def test_same_version_redeploy_keeps_replicas(cluster):
    """Deploying the SAME version is an in-place config update — the
    running replicas survive (no churn)."""

    @serve.deployment(num_replicas=1, version="stable")
    class Stable:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self, _x):
            return self.pid

    handle = serve.run(Stable.bind(), name="stable")
    pid1 = ray_tpu.get(handle.remote(0), timeout=60)
    serve.run(Stable.bind(), name="stable")  # same version again
    time.sleep(1.0)
    pid2 = ray_tpu.get(handle.remote(0), timeout=60)
    assert pid1 == pid2
    serve.delete("Stable")


def test_streaming_deployment_handle(cluster):
    """Generator deployments stream values through handle.stream()
    (reference streaming replica responses)."""

    @serve.deployment(num_replicas=1, ray_actor_options={"num_cpus": 0.25})
    class Tokens:
        def __call__(self, prompt):
            for i, word in enumerate(str(prompt).split()):
                yield {"index": i, "token": word}

    handle = serve.run(Tokens.bind(), name="tokens")
    out = list(handle.stream("the quick brown fox"))
    assert [o["token"] for o in out] == ["the", "quick", "brown", "fox"]
    assert [o["index"] for o in out] == [0, 1, 2, 3]
    serve.delete("Tokens")


def test_streaming_async_deployment(cluster):
    @serve.deployment(num_replicas=1, ray_actor_options={"num_cpus": 0.25})
    class AsyncTokens:
        async def __call__(self, n):
            import asyncio as aio

            for i in range(n):
                await aio.sleep(0.01)
                yield f"t{i}"

    handle = serve.run(AsyncTokens.bind(), name="atokens")
    assert list(handle.stream(3)) == ["t0", "t1", "t2"]
    serve.delete("AsyncTokens")


def test_streaming_http_sse(cluster):
    """SSE through the HTTP proxy: Accept: text/event-stream gets one
    data: event per yielded item (reference proxy streaming)."""

    @serve.deployment(num_replicas=1, route_prefix="/sse", ray_actor_options={"num_cpus": 0.25})
    class SSE:
        def __call__(self, body):
            for i in range(3):
                yield {"n": i}

    serve.run(SSE.bind(), name="sse")
    from ray_tpu.serve.controller import get_or_create_controller

    # start_http is a per-process singleton: reuse whatever port it holds
    proxy = serve.start_http(get_or_create_controller(), port=18457)
    req = urllib.request.Request(
        f"http://127.0.0.1:{proxy.port}/sse",
        data=b"{}",
        headers={"Accept": "text/event-stream", "Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        raw = resp.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ")
    ]
    assert events == [{"n": 0}, {"n": 1}, {"n": 2}], raw
    serve.delete("SSE")


def test_multiplexed_models_lru_eviction(cluster):
    """@serve.multiplexed: per-replica LRU of loaded models with
    eviction beyond max_num_models_per_replica (reference
    multiplex.py:22), model id carried by handle.options()."""

    @serve.deployment(num_replicas=1, ray_actor_options={"num_cpus": 0.25})
    class Multi:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "scale": len(self.loads)}

        async def __call__(self, x):
            model = await self.get_model(serve.get_multiplexed_model_id())
            return {"model": model["id"], "x": x}

        def load_history(self):
            return self.loads

    handle = serve.run(Multi.bind(), name="multi")
    # three models through a capacity-2 cache
    for mid in ["m1", "m2", "m1", "m3", "m1"]:
        out = ray_tpu.get(
            handle.options(multiplexed_model_id=mid).remote(1), timeout=60
        )
        assert out["model"] == mid
    history = ray_tpu.get(handle.method("load_history")(), timeout=30)
    # m1: loaded once then cache-hit (still resident when m3 evicted m2)
    assert history == ["m1", "m2", "m3"], history
    # m2 was evicted; calling it again re-loads
    ray_tpu.get(handle.options(multiplexed_model_id="m2").remote(1), timeout=60)
    history = ray_tpu.get(handle.method("load_history")(), timeout=30)
    assert history == ["m1", "m2", "m3", "m2"], history
    serve.delete("Multi")


def test_multiplexed_model_aware_routing(cluster):
    """With multiple replicas, requests for a model prefer the replica
    that already loaded it (model-locality routing)."""
    import os

    @serve.deployment(num_replicas=2, ray_actor_options={"num_cpus": 0.25})
    class Which:
        @serve.multiplexed(max_num_models_per_replica=4)
        async def get_model(self, model_id: str):
            return model_id

        async def __call__(self, x):
            await self.get_model(serve.get_multiplexed_model_id())
            return os.getpid()

    handle = serve.run(Which.bind(), name="which")
    h = handle.options(multiplexed_model_id="modelA")
    first = h.call(0, _timeout=60)
    # subsequent calls for the same model land on the same replica
    # (stats TTL is 250ms — wait for a fresh stats fetch to pick up the
    # loaded-models set)
    time.sleep(0.4)
    pids = {h.call(0, _timeout=60) for _ in range(8)}
    assert pids == {first}, (first, pids)
    serve.delete("Which")


def test_dispatch_retry_on_replica_death(cluster):
    """handle.call() re-chooses when its dispatch races a replica kill
    (retry-until-executed; reference router)."""

    @serve.deployment(num_replicas=2, ray_actor_options={"num_cpus": 0.25})
    class Sturdy:
        def __call__(self, x):
            return x * 2

    handle = serve.run(Sturdy.bind(), name="sturdy")
    assert handle.call(4, _timeout=60) == 8
    # kill one replica out from under the router's cached set
    replicas = ray_tpu.get(
        handle._controller.get_replicas.remote("Sturdy"), timeout=30
    )
    ray_tpu.kill(replicas[0])
    # every call still succeeds (some will race the corpse and retry)
    for i in range(10):
        assert handle.call(i, _timeout=60) == i * 2
    serve.delete("Sturdy")
