"""Accelerator autodetection registry.

Reference: ``python/ray/_private/accelerators/__init__.py:13-59`` — a
registry of per-family managers consulted by the node daemon at startup
(resource autodetection) and by the worker-launch path (device isolation).
TPU is first-class here; the registry shape still allows other families.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Type

from ray_tpu.accelerators.base import AcceleratorManager
from ray_tpu.accelerators.tpu import (
    TPUAcceleratorManager,
    pod_type_chips_per_host,
    pod_type_num_chips,
    pod_type_num_hosts,
    set_metadata_fetcher,
    slice_head_resource_name,
)

_MANAGERS: Dict[str, Type[AcceleratorManager]] = {
    "TPU": TPUAcceleratorManager,
}


def get_all_accelerator_managers() -> List[Type[AcceleratorManager]]:
    return list(_MANAGERS.values())


def get_accelerator_manager(resource_name: str) -> Optional[Type[AcceleratorManager]]:
    return _MANAGERS.get(resource_name)


def detect_node_accelerators() -> tuple:
    """(resources, labels) this host contributes, across all families.

    Called by the node daemon on startup; explicit user resources win.
    A detection error propagates: a node that silently registers zero
    chips runs everything on the CPU and makes ``{"TPU": 1}`` unplaceable.
    """
    resources: Dict[str, float] = {}
    labels: Dict[str, str] = {}
    for mgr in _MANAGERS.values():
        n = mgr.get_current_node_num_accelerators()
        if n <= 0:
            continue
        resources[mgr.get_resource_name()] = float(n)
        resources.update(mgr.get_additional_node_resources())
        labels.update(mgr.get_additional_node_labels())
    return resources, labels


__all__ = [
    "AcceleratorManager",
    "TPUAcceleratorManager",
    "detect_node_accelerators",
    "get_accelerator_manager",
    "get_all_accelerator_managers",
    "pod_type_chips_per_host",
    "pod_type_num_chips",
    "pod_type_num_hosts",
    "set_metadata_fetcher",
    "slice_head_resource_name",
]
