"""Train configuration dataclasses.

Reference: ``python/ray/air/config.py`` (`ScalingConfig`, `RunConfig`,
`FailureConfig`, `CheckpointConfig`). TPU-first deltas: ``use_tpu`` +
``topology`` (a pod-slice type like ``"v4-32"``) replace ``use_gpu``; a
topology implies one worker per slice host, gang-reserved via a
STRICT_SPREAD placement group (partial slices are useless).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class ScalingConfig:
    """How many training workers, and what each one holds.

    Reference: ``air/config.py`` ScalingConfig (num_workers,
    use_gpu→use_tpu, resources_per_worker, placement_strategy).
    """

    num_workers: int = 1
    use_tpu: bool = False
    #: Pod-slice type (e.g. ``"v4-32"``). Overrides num_workers to the
    #: slice's host count and gangs one worker per host.
    topology: Optional[str] = None
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"

    def worker_resources(self) -> Dict[str, float]:
        if self.resources_per_worker is not None:
            # a worker is a process: it holds a CPU unless told otherwise
            # (the worker group asks for one, so a bundle without it could
            # never host its worker)
            res = {"CPU": 1.0, **self.resources_per_worker}
            # An explicit TPU count wins; otherwise topology decides.
            if self.topology and "TPU" not in res:
                res["TPU"] = self._chips_per_host()
        elif self.use_tpu or self.topology:
            # Topology is authoritative; a bare use_tpu asks the cluster
            # what a host holds (see _chips_per_host).
            res = {"CPU": 1.0, "TPU": self._chips_per_host()}
        else:
            res = {"CPU": 1.0}
        return res

    def _chips_per_host(self) -> float:
        if self.topology:
            from ray_tpu.accelerators import (
                pod_type_chips_per_host,
                pod_type_num_chips,
            )

            # Sub-host slices (v5e-4 = 4 chips on an 8-chip host machine)
            # expose only their own chips — never request more than the
            # slice has in total.
            return float(
                min(
                    pod_type_chips_per_host(self.topology),
                    pod_type_num_chips(self.topology),
                )
            )
        # Bare use_tpu: every chip of a host, as the running cluster's
        # nodes detected them. A fixed guess is unplaceable wherever it is
        # wrong (4 on a one-chip node), so there is none.
        import ray_tpu

        counts = {
            int(n["Resources"].get("TPU", 0)) for n in ray_tpu.nodes() if n["Alive"]
        } - {0}
        if not counts:
            raise ValueError(
                "ScalingConfig(use_tpu=True) asks for every chip of a host, "
                "but no live node of this cluster reports TPU chips; state "
                "resources_per_worker={'TPU': n} or a topology"
            )
        return float(min(counts))

    def resolved_num_workers(self) -> int:
        if self.topology:
            from ray_tpu.accelerators import pod_type_num_hosts

            return pod_type_num_hosts(self.topology)
        return self.num_workers

    def bundles(self) -> List[Dict[str, float]]:
        per_worker = self.worker_resources()
        n = self.resolved_num_workers()
        bundles = [dict(per_worker) for _ in range(n)]
        if self.topology:
            from ray_tpu.accelerators import slice_head_resource_name

            bundles[0][slice_head_resource_name(self.topology)] = 1.0
        return bundles

    def pg_strategy(self) -> str:
        # One worker per host for real slices; tests pack on one machine.
        if self.topology:
            return "STRICT_SPREAD"
        return self.placement_strategy


@dataclass
class FailureConfig:
    """Reference: ``air/config.py:394-408`` — how many times fit() may
    restart the worker group from the latest checkpoint."""

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    """Reference: ``air/config.py`` CheckpointConfig (num_to_keep etc.)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)
    #: LoggerCallback instances (reference ``RunConfig.callbacks`` —
    #: CSV/JSON/TensorBoard in ``ray_tpu.tune.loggers``)
    callbacks: list = field(default_factory=list)
