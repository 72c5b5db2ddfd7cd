"""Test fixtures.

Mirrors the reference's load-bearing fixtures
(``python/ray/tests/conftest.py``): ``ray_start_local`` (eager in-process),
``ray_start_regular`` (real single-node runtime), and the simulated
multi-node ``cluster`` fixture (``python/ray/cluster_utils.py:135``).

JAX-dependent tests run on a virtual 8-device CPU mesh: the env vars below
must be set before jax initializes, which this conftest guarantees because
pytest imports it before any test module.

Hang defense (see ``ray_tpu/observability/event_stats.py`` and
``ray_tpu/util/reaper.py``):

* every test runs under a HARD timeout enforced by stdlib
  ``faulthandler.dump_traceback_later(..., exit=True)`` — a wedged test
  dumps every thread's stack and aborts the run instead of freezing the
  suite (and the box) indefinitely;
* spawned runtime processes run with ``watchdog_abort_after_s`` set, so a
  daemon/worker whose event loop stalls hard-exits (code 70) after dumping
  its stacks rather than holding ports/shm forever;
* an autouse leak guard snapshots runtime pids around each test and FAILS
  the test that leaked ``worker_main``/``node_main``/``head_main``
  processes — "suite wedged 25 minutes" becomes a named failure.
"""

import os

# JAX reads JAX_PLATFORMS when it is imported (below), and runtime
# subprocesses inherit it as real process env. setdefault keeps an
# operator's value for those subprocesses; THIS process is forced onto the
# CPU regardless by the jax.config.update after the import, so the test
# session never holds a chip (a machine with a TPU may export
# JAX_PLATFORMS=tpu,cpu, which setdefault leaves alone).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Test-mode hang defense: runtime processes spawned by tests inherit this
# env, so a process whose event loop stalls past the threshold dumps
# stacks and hard-exits instead of silently wedging the suite. Set before
# importing ray_tpu (GLOBAL_CONFIG reads env at import).
os.environ.setdefault("RAY_TPU_watchdog_abort_after_s", "120")

# One chaos seed per SESSION, chosen here (before ray_tpu imports config)
# and printed in the report header: every chaos-enabled test in this run
# draws its fault plan from this seed, and spawned runtime processes
# inherit it through env + system-config — so a chaos-test failure in a
# tier-1 log is reproducible from the log alone by re-exporting the
# printed RAY_TPU_testing_rpc_chaos_seed value.
if not os.environ.get("RAY_TPU_testing_rpc_chaos_seed"):
    os.environ["RAY_TPU_testing_rpc_chaos_seed"] = str(
        int.from_bytes(os.urandom(3), "little") | 1
    )

# One MASTER chaos seed per session too (util/chaos.py::derive_plan_seed):
# any fault plan armed without its own seed knob derives deterministically
# from this value, so a multi-plan chaos failure replays from ONE number
# instead of three. Explicit per-plan seeds (like the rpc one above) win.
if not os.environ.get("RAY_TPU_testing_chaos_seed"):
    os.environ["RAY_TPU_testing_chaos_seed"] = str(
        int.from_bytes(os.urandom(3), "little") | 1
    )

# One persistent compile cache for the session, shared by this process and
# every runtime subprocess (which would get the same directory from
# cluster_backend._subprocess_env anyway): the suite compiles the same tiny
# programs in dozens of processes. JAX reads both variables itself, at
# import. Its default threshold (1 s) would skip nearly every toy-sized
# program; 0.2 s keeps the trivial ones out and the warm-up buckets in.
from ray_tpu.core.config import ensure_compile_cache_env  # noqa: E402

ensure_compile_cache_env(os.environ)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")

import faulthandler  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402
from ray_tpu.observability import event_stats as _event_stats  # noqa: E402
from ray_tpu.util.reaper import find_runtime_pids, pid_alive, reap_all  # noqa: E402

# The pytest process itself must never watchdog-ABORT (that kills the
# whole suite; its wedges are bounded by the per-test faulthandler timer
# below) — it still detects and DUMPS loop stalls. Spawned runtime
# processes don't import this conftest and keep the 120s hard abort.
_event_stats.ABORT_DISABLED_IN_PROCESS = True

# faulthandler output must survive pytest's fd-level capture. A dup of
# fd 2 here does NOT work: tests/conftest.py imports during collection,
# AFTER the capture plugin has already swapped fd 2 for its tempfile, so
# the dup points into the capture buffer and _exit(1) discards it — the
# hard-timeout abort then looks like a silent exit-code-1 with zero
# output (exactly the unattributable wedge this timer exists to avoid).
# Dump to a well-known file instead; truncated each session, announced in
# pytest's report header (the one place guaranteed visible in the log even
# when the abort itself prints nothing), overridable for parallel runs.
_DUMP_PATH = os.environ.get(
    "RAY_TPU_TEST_DUMP_FILE", "/tmp/raytpu_test_timeout_dump.log"
)
try:
    _DUMP_FILE = open(_DUMP_PATH, "w")
    _DUMP_FILE.write(
        "armed: a per-test hard-timeout stack dump will land here "
        "(tests/conftest.py raytpu_test_timeout); an empty-but-armed file "
        "means no test overran its timer\n"
    )
    _DUMP_FILE.flush()
except OSError:
    _DUMP_FILE = None


def pytest_report_header(config):
    # a hard-timeout abort is exit-code-1 with ZERO terminal output (fd 2
    # is pytest's capture tempfile by dump time) — this header line is how
    # an operator staring at a silent crash finds the stacks
    if _DUMP_FILE is None:
        lines = ["hard-timeout stack dumps: DISABLED (could not open dump file)"]
    else:
        lines = [
            f"hard-timeout stack dumps land in {_DUMP_PATH} "
            "(silent exit-1 run? look there; last '[armed]' line names the test)"
        ]
    # chaos reproducibility: any chaos-test failure in this log replays
    # with these two env vars (tests that pin their own seed say so)
    from ray_tpu.core.config import GLOBAL_CONFIG as _CFG

    plan = _CFG.testing_rpc_chaos or "(none; chaos tests set per-test specs)"
    lines.append(
        f"rpc chaos: seed={_CFG.testing_rpc_chaos_seed} plan={plan} — "
        "reproduce a chaos failure with "
        f"RAY_TPU_testing_rpc_chaos_seed={_CFG.testing_rpc_chaos_seed}"
    )
    lines.append(
        f"master chaos seed: RAY_TPU_testing_chaos_seed="
        f"{_CFG.testing_chaos_seed} (derives every plan seed not pinned "
        "explicitly — one number replays the whole composite schedule)"
    )
    return lines


# ---------------------------------------------------------------------------
# chaos repro helper: a failure under ANY seeded fault plan prints ONE
# copy-pasteable env line reproducing that session's full chaos schedule.
# The seeds already print (report header + activation logs), but the
# operator had to assemble the env by hand from three knob pairs.

def _activated_plans():
    """(spec_key, spec, seed_key, seed) for every fault plan that was
    ACTIVATED in this (driver) process — read from the SeededPlanCache
    singletons, not GLOBAL_CONFIG: chaos tests restore their config in
    their own ``finally`` BEFORE the report hook runs, which made the
    config-only version print nothing for exactly the failures it was
    built for. The cache keeps the last-activated plan's spec+seed."""
    out = []
    probes = (
        ("ray_tpu.core.rpc", "testing_rpc_chaos"),
        ("ray_tpu.core.pull_manager", "testing_pull_chaos"),
        ("ray_tpu.inference.engine", "testing_replica_chaos"),
        ("ray_tpu.inference.kv_transfer", "testing_kv_tier_chaos"),
        ("ray_tpu.core.controller", "testing_controller_chaos"),
    )
    import importlib
    import sys as _sys

    for mod_name, spec_key in probes:
        mod = _sys.modules.get(mod_name)  # never IMPORT here (engine pulls jax)
        if mod is None:
            continue
        cache = getattr(mod, "_PLAN_CACHE", None) or getattr(mod, "_RPLAN_CACHE", None)
        plan = getattr(cache, "_plan", None)
        if plan is not None:
            out.append((spec_key, plan.spec, spec_key + "_seed", plan.seed))
    return out


def _chaos_repro_line(nodeid: str):
    from ray_tpu.core.config import GLOBAL_CONFIG as cfg

    entries = {k: (spec, sk, seed) for k, spec, sk, seed in _activated_plans()}
    # config still carries a plan the driver never consulted (e.g. env
    # chaos that only child processes run): include it too
    for spec_key, seed_key in (
        ("testing_rpc_chaos", "testing_rpc_chaos_seed"),
        ("testing_pull_chaos", "testing_pull_chaos_seed"),
        ("testing_replica_chaos", "testing_replica_chaos_seed"),
        ("testing_kv_tier_chaos", "testing_kv_tier_chaos_seed"),
        ("testing_controller_chaos", "testing_controller_chaos_seed"),
    ):
        spec = getattr(cfg, spec_key)
        if spec and spec_key not in entries:
            entries[spec_key] = (spec, seed_key, getattr(cfg, seed_key))
        # env-armed plans (the ingress/stream-resume E2E pattern: the
        # test exports RAY_TPU_testing_* so CHILD processes inherit the
        # plan while the driver's GLOBAL_CONFIG stays clean — env is
        # only read at import). Without this probe exactly those
        # failures printed no repro line.
        env_spec = os.environ.get("RAY_TPU_" + spec_key)
        if env_spec and spec_key not in entries:
            entries[spec_key] = (
                env_spec,
                seed_key,
                os.environ.get("RAY_TPU_" + seed_key) or 0,
            )
    if not entries:
        return None
    # composite-chaos compression: per-plan seeds that are (or will be)
    # DERIVED from the session's master seed collapse into the one
    # master knob — a three-plan schedule replays from a single number
    from ray_tpu.util.chaos import derive_plan_seed as _derive

    _labels = {
        "testing_rpc_chaos": "rpc",
        "testing_pull_chaos": "pull",
        "testing_replica_chaos": "replica",
        "testing_kv_tier_chaos": "kv_tier",
        "testing_controller_chaos": "controller",
    }
    try:
        master = int(
            os.environ.get("RAY_TPU_testing_chaos_seed")
            or getattr(cfg, "testing_chaos_seed", 0)
            or 0
        )
    except ValueError:
        master = 0
    parts = []
    master_covers = False
    for spec_key, (spec, seed_key, seed) in entries.items():
        parts.append(f"RAY_TPU_{spec_key}={spec!r}")
        try:
            seed_i = int(seed)
        except (TypeError, ValueError):
            seed_i = 0
        if master and (
            not seed_i or seed_i == _derive(master, _labels[spec_key])
        ):
            master_covers = True
        elif seed_i:
            parts.append(f"RAY_TPU_{seed_key}={seed_i}")
    if master_covers:
        parts.append(f"RAY_TPU_testing_chaos_seed={master}")
    return (
        " ".join(parts)
        + f" python -m pytest '{nodeid}'"
        + "  # replays this session's seeded fault schedule"
        + " (a child process that GENERATED its own seed logs it at"
        + " plan activation — substitute that value)"
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if report.when == "call" and report.failed:
        try:
            line = _chaos_repro_line(item.nodeid)
        except Exception:
            line = None
        if line:
            report.sections.append(("chaos repro", line))


@pytest.fixture
def ray_start_local():
    ray_tpu.init(local_mode=True)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_regular():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    yield
    ray_tpu.shutdown()


#: shared capability gate (import as ``from conftest import ...``):
#: jaxlib < 0.5 CPU backend has no cross-process collectives — a 2-proc
#: allgather/psum dies with "Multiprocess computations aren't implemented
#: on the CPU backend". The rendezvous itself (process_count) still works.
multiprocess_cpu_collectives = pytest.mark.skipif(
    tuple(int(x) for x in jax.__version__.split(".")[:2]) < (0, 5),
    reason="jaxlib <0.5 CPU backend lacks multiprocess collectives",
)


def wait_for_node_resource(name, *, exclude=(), timeout=20.0):
    """Block until an ALIVE node carrying resource ``name`` (and not in
    ``exclude`` node-ids) is registered — the condition-based replacement
    for the blind ``sleep(1.0)`` after ``cluster.add_node`` (suite-time
    CAUTION: fixed sleeps were ~10s of pure waiting across the cluster
    modules). Returns the node_id."""
    import time as _time

    import ray_tpu as _rt

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        for n in _rt.nodes():
            if (
                n.get("Alive")
                and name in (n.get("Resources") or {})
                and n.get("node_id") not in exclude
            ):
                return n["node_id"]
        _time.sleep(0.05)
    raise TimeoutError(f"no alive node with resource {name!r} within {timeout}s")


# ---------------------------------------------------------------------------
# per-test hard timeout (stdlib faulthandler, no plugin dependency)

def pytest_addoption(parser):
    parser.addini(
        "raytpu_test_timeout",
        "per-test hard timeout in seconds; on expiry every thread's stack is "
        "dumped and the run aborts (faulthandler.dump_traceback_later). "
        "0 disables. Env override: RAY_TPU_TEST_TIMEOUT_S.",
        default="180",
    )


def _test_timeout(config) -> float:
    try:
        return float(
            os.environ.get(
                "RAY_TPU_TEST_TIMEOUT_S", config.getini("raytpu_test_timeout")
            )
        )
    except (TypeError, ValueError):
        return 180.0


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    timeout = _test_timeout(item.config)
    armed = timeout > 0 and hasattr(faulthandler, "dump_traceback_later")
    if armed:
        # exit=True: a test that outlives the timer is unrecoverably wedged
        # (futex/GIL/asyncio) — dump all stacks and kill the process so the
        # outer harness sees a crash named by these stacks, not a freeze.
        # The dump goes to _DUMP_FILE (see above); record WHICH test armed
        # the timer so the abort is attributable even mid-dump.
        if _DUMP_FILE is not None:
            _DUMP_FILE.write(f"[armed] {item.nodeid}\n")
            _DUMP_FILE.flush()
        kwargs = {"file": _DUMP_FILE} if _DUMP_FILE is not None else {}
        faulthandler.dump_traceback_later(timeout, exit=True, **kwargs)
    try:
        yield
    finally:
        if armed:
            faulthandler.cancel_dump_traceback_later()


# ---------------------------------------------------------------------------
# leaked-process guard: the test that orphans runtime processes FAILS

#: grace for asynchronous child teardown after a test's fixtures finish
_LEAK_GRACE_S = 5.0


def _wait_for_drain(candidates, grace_s):
    import time as _time

    deadline = _time.monotonic() + grace_s
    live = [p for p in candidates if pid_alive(p)]
    while live and _time.monotonic() < deadline:
        _time.sleep(0.2)
        live = [p for p in live if pid_alive(p)]
    return live


def _our_runtime_pids():
    """Runtime processes belonging to clusters THIS pytest process
    spawned (RAY_TPU_SPAWNER_PID stamp): a sibling session's (or a dev's
    detached) cluster must never be flagged or reaped by these guards."""
    return find_runtime_pids(spawner_pid=os.getpid())


def _daemon_reachable(host: str, port: int) -> bool:
    import socket as _socket

    try:
        with _socket.create_connection((host, port), timeout=1.0):
            return True
    except OSError:
        return False


def _assert_no_ghost_draining_nodes():
    """PR 2 drain invariant: a drain-exited daemon must have DEREGISTERED
    from the controller — a node row stuck in DRAINING whose daemon
    process is GONE is a protocol leak (the controller would neither
    schedule on it nor fail its actors over). Checked while a shared
    cluster is still up; a node mid-drain (daemon still reachable) is
    legitimate and not flagged."""
    try:
        rows = ray_tpu.nodes()
    except Exception:
        return  # cluster mid-teardown: nothing to assert against
    ghosts = []
    for row in rows:
        if row.get("State") != "DRAINING":
            continue
        import time as _time

        # give an in-flight deregistration a moment to land
        deadline = _time.monotonic() + _LEAK_GRACE_S
        while _time.monotonic() < deadline:
            try:
                fresh = {n["NodeID"]: n for n in ray_tpu.nodes()}
            except Exception:
                return
            cur = fresh.get(row["NodeID"])
            if cur is None or cur.get("State") != "DRAINING":
                break
            if _daemon_reachable(cur["host"], cur["port"]):
                break  # daemon alive: legitimately mid-drain, not a ghost
            _time.sleep(0.2)
        else:
            ghosts.append(f"{row['NodeID'][:12]} ({row.get('DrainReason', '')})")
    if ghosts:
        pytest.fail(
            "test left ghost DRAINING node entries (drain-exited daemons "
            "must deregister):\n  " + "\n  ".join(ghosts),
            pytrace=False,
        )


@pytest.fixture(autouse=True)
def _runtime_leak_guard(request):
    before = set(_our_runtime_pids())
    yield
    if ray_tpu.is_initialized():
        # a module/session-scoped cluster is legitimately still up; its
        # processes are accounted for when that fixture finalizes — but
        # drain protocol state must still be clean between tests
        _assert_no_ghost_draining_nodes()
        return
    leaked = _wait_for_drain(set(_our_runtime_pids()) - before, _LEAK_GRACE_S)
    if leaked:
        details = []
        for pid in leaked:
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    cmd = f.read().replace(b"\x00", b" ").decode(errors="replace").strip()
            except OSError:
                cmd = "?"
            details.append(f"pid {pid}: {cmd}")
        reap_all(leaked)  # don't poison the rest of the suite
        pytest.fail(
            "test leaked runtime processes (reaped):\n  " + "\n  ".join(details),
            pytrace=False,
        )


@pytest.fixture(autouse=True, scope="session")
def _session_process_sweep():
    """Backstop for leaks that escape per-test attribution (module-scoped
    fixture teardown after the last test of a module): reap anything left
    at session end so consecutive suite runs start clean. Scoped to OUR
    spawner stamp — a concurrently running sibling pytest session's
    clusters must never be reaped from here."""
    yield
    leftovers = _wait_for_drain(_our_runtime_pids(), _LEAK_GRACE_S)
    if leftovers:
        import warnings

        reap_all(leftovers)
        warnings.warn(
            f"session ended with leaked runtime processes (reaped): {sorted(leftovers)}",
            stacklevel=1,
        )
