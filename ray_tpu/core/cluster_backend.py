"""Driver-side cluster backend: CoreWorker + cluster lifecycle.

``ray_tpu.init()`` with no address spawns a head process (controller +
head-node daemon, see ``head_main.py``) and connects to it;
``ray_tpu.init(address=...)`` connects to an existing cluster started by
the ``Cluster`` test fixture or the CLI. Address format:
``host:controller_port:daemon_port``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

from ray_tpu.core.core_worker import CoreWorker


def _subprocess_env() -> dict:
    """Env for child processes: make the ray_tpu package importable even
    when the driver found it via sys.path manipulation, and give every
    descendant the same persistent JAX compile cache."""
    import ray_tpu
    from ray_tpu.core.config import ensure_compile_cache_env

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    env = dict(os.environ)
    ensure_compile_cache_env(env)
    existing = env.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = pkg_root + (os.pathsep + existing if existing else "")
    # every process spawned through here belongs to THIS driver: it must
    # exit (gracefully) if the driver dies without running shutdown —
    # the orphaned-head_main leak class (util/reaper.start_orphan_watch)
    from ray_tpu.util.reaper import EXIT_ON_DRIVER_EXIT_ENV, SPAWNER_PID_ENV

    env[EXIT_ON_DRIVER_EXIT_ENV] = "1"
    env[SPAWNER_PID_ENV] = str(os.getpid())
    # cluster-wide trace epoch: every runtime process mints trace ids
    # under the driver's epoch prefix, so ids from one cluster
    # incarnation never collide with a restarted one's (tracing.py)
    from ray_tpu.observability.tracing import TRACE_EPOCH_ENV, trace_epoch

    env.setdefault(TRACE_EPOCH_ENV, trace_epoch())
    return env


def _spawn_and_handshake(cmd, log_path: str, what: str) -> tuple:
    """Spawn one runtime process (head / node daemon / standalone
    controller) and complete the stdout handshake: every spawner shares
    the same contract — detached session + driver-scoped env
    (``_subprocess_env``: orphan watch, compile-cache location, the
    cluster trace epoch), stderr appended to ``log_path``, and ONE
    stdout line of JSON announcing the ports. Returns ``(proc, info)``.
    (Third and last of the PR 5 deferred refactor trio: spawn_node and
    spawn_controller used to duplicate all of this.)"""
    os.makedirs(os.path.dirname(log_path) or ".", exist_ok=True)
    err_f = open(log_path, "ab")
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=err_f, start_new_session=True,
        env=_subprocess_env(),
    )
    line = proc.stdout.readline().decode()
    if not line:
        raise RuntimeError(f"{what} failed to start (see {log_path})")
    return proc, json.loads(line)


class ClusterBackend(CoreWorker):
    _head_proc: Optional[subprocess.Popen] = None

    @classmethod
    def start_cluster(
        cls,
        num_cpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        num_nodes: int = 1,
    ) -> "ClusterBackend":
        session_dir = f"/tmp/ray_tpu/session_{os.getpid()}_{int(time.time())}"
        cmd = [sys.executable, "-m", "ray_tpu.core.head_main", "--session-dir", session_dir]
        if num_cpus is not None:
            cmd += ["--num-cpus", str(num_cpus)]
        if resources:
            cmd += ["--resources", json.dumps(resources)]
        from ray_tpu.core.config import GLOBAL_CONFIG, serialize_config

        cmd += ["--system-config", serialize_config()]
        os.makedirs(session_dir, exist_ok=True)
        proc, ports = _spawn_and_handshake(
            cmd, os.path.join(session_dir, "head.log"), "head process"
        )
        backend = cls(
            "127.0.0.1", ports["controller_port"], "127.0.0.1", ports["daemon_port"]
        )
        backend._head_proc = proc
        backend._finish_handshake()
        # extra simulated nodes (tests / local multi-node)
        backend._extra_nodes = []
        for _ in range(max(0, num_nodes - 1)):
            backend._extra_nodes.append(
                spawn_node(
                    f"127.0.0.1:{ports['controller_port']}", num_cpus=num_cpus, resources=resources
                )
            )
        return backend

    @classmethod
    def connect(cls, address: str) -> "ClusterBackend":
        host, cport, dport = address.rsplit(":", 2)
        backend = cls(host, int(cport), host, int(dport))
        backend._head_proc = None
        backend._extra_nodes = []
        backend._finish_handshake()
        return backend

    def _finish_handshake(self) -> None:
        reply = self.io.run(self.daemon.call("hello", retries=5))
        self.finish_init(reply["node_id"])

    def bind_worker(self, worker) -> None:
        worker.address = self.address
        self.io.run(
            self.controller.call(
                "register_job", {"job_id": worker.job_id.binary(), "driver_pid": os.getpid()}
            )
        )

    def shutdown(self) -> None:
        super().shutdown()
        for proc in getattr(self, "_extra_nodes", []):
            _stop(proc)
        if self._head_proc is not None:
            _stop(self._head_proc)


def spawn_node(
    controller_addr: str,
    num_cpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    labels: Optional[Dict[str, str]] = None,
) -> subprocess.Popen:
    cmd = [sys.executable, "-m", "ray_tpu.core.node_main", "--controller", controller_addr]
    if num_cpus is not None:
        cmd += ["--num-cpus", str(num_cpus)]
    if resources:
        cmd += ["--resources", json.dumps(resources)]
    if labels:
        cmd += ["--labels", json.dumps(labels)]
    from ray_tpu.core.config import serialize_config

    cmd += ["--system-config", serialize_config()]
    proc, info = _spawn_and_handshake(
        cmd,
        f"/tmp/ray_tpu/node-{os.getpid()}-{time.time_ns()}.log",
        "node daemon",
    )
    proc.node_port = info["daemon_port"]  # type: ignore[attr-defined]
    proc.node_id_hex = info["node_id"]  # type: ignore[attr-defined]
    return proc


def spawn_controller(
    session_dir: str, port: int = 0, standby: bool = False
) -> subprocess.Popen:
    """Spawn a STANDALONE controller process (``controller_main.py``) —
    the failover topology where the control plane can be killed and
    restarted from its snapshot + WAL independently of every node
    daemon. Restarting with the same ``session_dir`` restores state AND
    the old listening port, so clients reconnect with no rediscovery.
    The returned proc carries ``controller_port``.

    ``standby=True`` starts a HOT STANDBY follower instead: it tails the
    session WAL and the active's lease file, and promotes itself (WAL
    replay to the tip, epoch bump, same-port rebind) the moment the
    lease goes stale or is released. Its ``controller_port`` is the
    port the ACTIVE held at spawn time — the address the promoted
    standby will rebind."""
    from ray_tpu.core.config import serialize_config

    os.makedirs(session_dir, exist_ok=True)
    cmd = [
        sys.executable, "-m", "ray_tpu.core.controller_main",
        "--session-dir", session_dir, "--port", str(port),
        "--system-config", serialize_config(),
    ]
    log_name = "controller-standby.log" if standby else "controller.log"
    if standby:
        cmd.append("--standby")
    proc, info = _spawn_and_handshake(
        cmd, os.path.join(session_dir, log_name), "controller"
    )
    proc.controller_port = info["controller_port"]  # type: ignore[attr-defined]
    proc.standby = bool(info.get("standby", False))  # type: ignore[attr-defined]
    return proc


def _stop(proc: subprocess.Popen) -> None:
    """Escalating stop of a spawned runtime process AND its process group
    (head/node daemons run with start_new_session=True and own their
    workers' group): SIGTERM → grace → SIGKILL, always bounded. The group
    kill is what prevents the round-5 "orphaned head_main" leak class —
    terminating only the leader leaves its children reparented to init.

    SIGINT precedes the reap: driver-initiated teardown is "cluster
    over", not a preemption warning — daemons must exit now, not run the
    SIGTERM drain protocol."""
    import signal

    from ray_tpu.util.reaper import reap_process

    try:
        os.kill(proc.pid, signal.SIGINT)
    except OSError:
        pass
    reap_process(proc, group=True)
