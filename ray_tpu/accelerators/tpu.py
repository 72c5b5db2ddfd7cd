"""TPU accelerator manager: autodetection, isolation, slice gang resources.

Reference: ``python/ray/_private/accelerators/tpu.py`` — chip detection via
``/dev/accel*`` / ``/dev/vfio`` (``:31`` area), GCE/GKE metadata probing
(``:19-45``), ``TPU_VISIBLE_CHIPS`` per-process isolation, the
``TPU-{pod_type}-head`` slice-head resource granted on worker 0 of a pod,
and the {1,2,4} valid chips-per-process rule. Re-designed, not ported: the
metadata fetcher is injectable so every path is testable offline, and the
pod math understands v2–v6e naming (cores-suffixed for v2–v5p,
chips-suffixed for v5e/v6e).
"""

from __future__ import annotations

import glob
import logging
import os
import sys
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.accelerators.base import AcceleratorManager

logger = logging.getLogger(__name__)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# libtpu reads these to carve a host's chips into multiple processes.
TPU_CHIPS_PER_PROCESS_BOUNDS_ENV = "TPU_CHIPS_PER_PROCESS_BOUNDS"
TPU_PROCESS_BOUNDS_ENV = "TPU_PROCESS_BOUNDS"

# Explicit overrides (tests / operators without metadata servers).
NUM_CHIPS_OVERRIDE_ENV = "RAY_TPU_NUM_CHIPS"
ACCELERATOR_TYPE_OVERRIDE_ENV = "TPU_ACCELERATOR_TYPE"
WORKER_ID_OVERRIDE_ENV = "TPU_WORKER_ID"
WORKER_HOSTNAMES_ENV = "TPU_WORKER_HOSTNAMES"
TPU_NAME_ENV = "TPU_NAME"

# A process may attach to 1, 2, or 4 chips of a host (libtpu constraint;
# reference TPU_VALID_CHIP_OPTIONS).
VALID_CHIPS_PER_PROCESS = (1, 2, 4)

_GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1/instance/"

# chips per host by TPU generation
_CHIPS_PER_HOST = {
    "v2": 4,
    "v3": 4,
    "v4": 4,
    "v5p": 4,
    "v5litepod": 8,
    "v5e": 8,
    "v6e": 8,
}
# generations whose pod-type suffix counts TensorCores (2/chip), not chips
_CORES_SUFFIXED = {"v2", "v3", "v4", "v5p"}

# Public per-chip bf16 peak (dense) in TFLOP/s, keyed by substrings of
# ``jax.Device.device_kind`` — the denominator for MFU reporting. Longest
# match wins ("v5 lite" before "v5").
_PEAK_BF16_TFLOPS = {
    "v2": 46.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def peak_bf16_tflops(device_kind: str) -> float:
    """Per-chip dense-bf16 peak for a jax ``device_kind`` string (e.g.
    ``"TPU v5 lite"``). An unknown kind is an error, not a default: a
    utilization over a guessed peak is not a measurement."""
    kind = device_kind.lower()
    matches = [key for key in _PEAK_BF16_TFLOPS if key in kind]
    if not matches:
        raise ValueError(
            f"no bf16 peak on record for device_kind {device_kind!r}; "
            f"known: {sorted(_PEAK_BF16_TFLOPS)}"
        )
    return _PEAK_BF16_TFLOPS[max(matches, key=len)]


# ---------------------------------------------------------------------------
# What a process computes on. A chip belongs to one process at a time, so
# launchers (drivers, daemons) stay off JAX and the process that was
# granted chips says what it landed on.


def _chip_device_files() -> List[str]:
    """This host's chip device nodes: ``/dev/accel*`` (TPU VM images) or
    ``/dev/vfio/<n>`` (the group nodes; ``/dev/vfio/vfio`` is the
    container device, not a chip)."""
    accel = glob.glob("/dev/accel*")
    if accel:
        return sorted(accel)
    try:
        return sorted(f"/dev/vfio/{f}" for f in os.listdir("/dev/vfio") if f != "vfio")
    except OSError:
        return []


def jax_backend_initialized() -> bool:
    """True once THIS process has initialized a JAX backend (and so holds
    whatever chips it could see at that moment). Importing jax alone does
    not count. Never imports jax itself."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def process_device_report() -> Dict[str, Any]:
    """Platform, ``device_kind``, devices and device memory high-water
    mark of THIS process as JAX reports them, plus the chip device files
    it holds open. Initializes the JAX backend.

    Under ``isolation_env`` every one-chip process sees its chip as
    device 0, so ``device_ids`` cannot tell two replicas apart —
    ``chip_files`` (the ``/dev/vfio/<n>`` or ``/dev/accel<n>`` node
    libtpu opened) can.

    Raises when the daemon granted this process chips
    (``TPU_VISIBLE_CHIPS``) and JAX landed anywhere else — on another
    platform (unless the operator's ``JAX_PLATFORMS`` excludes the TPU:
    an explicit CPU run says ``cpu`` in the report instead), or on a
    different number of chips than it was granted."""
    import jax

    devices = jax.local_devices()
    chip_files = set(_chip_device_files())
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # closed between listdir and readlink
        if target in chip_files:
            held.add(target)
    memory = [d.memory_stats() or {} for d in devices]  # {} on the CPU
    report = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_ids": [d.id for d in devices],
        "visible_chips": os.environ.get(TPU_VISIBLE_CHIPS_ENV),
        "chip_files": sorted(held),
        "pid": os.getpid(),
        # the fullest device's high-water mark, against one device's limit
        "peak_bytes_in_use": max(m.get("peak_bytes_in_use", 0) for m in memory),
        "bytes_limit": memory[0].get("bytes_limit"),
    }
    granted = TPUAcceleratorManager.get_current_process_visible_accelerator_ids()
    if granted:
        platforms = os.environ.get("JAX_PLATFORMS", "").lower()
        tpu_excluded = bool(platforms) and "tpu" not in platforms.split(",")
        on_tpu = report["platform"] == "tpu"
        if not on_tpu and not tpu_excluded:
            raise RuntimeError(
                f"this process was granted TPU chips {granted} but JAX "
                f"initialized on {report['platform']!r} "
                f"(JAX_PLATFORMS={platforms!r}): refusing to compute on "
                "another device than the one scheduled"
            )
        if on_tpu and len(devices) != len(granted):
            raise RuntimeError(
                f"this process was granted TPU chips {granted} but JAX sees "
                f"{len(devices)} local device(s): chip isolation did not hold"
            )
    return report


# ---------------------------------------------------------------------------
# Metadata access — injectable for tests (reference probes GCE/GKE metadata)

_metadata_fetcher: Optional[Callable[[str], Optional[str]]] = None


def set_metadata_fetcher(fetcher: Optional[Callable[[str], Optional[str]]]) -> None:
    """Inject a metadata source (tests / non-GCE deployments)."""
    global _metadata_fetcher
    _metadata_fetcher = fetcher


def _fetch_metadata(path: str) -> Optional[str]:
    if _metadata_fetcher is not None:
        return _metadata_fetcher(path)
    try:
        from urllib.request import Request, urlopen

        req = Request(
            _GCE_METADATA_URL + path, headers={"Metadata-Flavor": "Google"}
        )
        with urlopen(req, timeout=1) as resp:  # noqa: S310
            return resp.read().decode()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Preemption / maintenance-event probe (pluggable via set_metadata_fetcher)

#: metadata path GCE flips from NONE before host maintenance / preemption
MAINTENANCE_EVENT_PATH = "maintenance-event"


def get_current_node_maintenance_event() -> Optional[str]:
    """The pending maintenance event for this host (e.g. ``"TERMINATE_ON_
    HOST_MAINTENANCE"``), ``"NONE"``/None when nothing is scheduled. Uses
    the same injectable metadata fetcher as the rest of TPU detection, so
    tests and non-GCE deployments plug in their own preemption signal."""
    event = _fetch_metadata(MAINTENANCE_EVENT_PATH)
    return event.strip() if event else None


def maintenance_event_imminent() -> bool:
    """True when the platform has announced this host will be reclaimed —
    the node daemon's preemption-probe loop turns this into a drain."""
    event = get_current_node_maintenance_event()
    return bool(event) and event.upper() != "NONE"


# ---------------------------------------------------------------------------
# Pod-type math


def pod_type_num_chips(pod_type: str) -> int:
    """Total chips in a pod slice, from its type string (e.g. v4-32 → 16)."""
    gen, _, suffix = pod_type.partition("-")
    n = int(suffix)
    return n // 2 if gen in _CORES_SUFFIXED else n


def pod_type_chips_per_host(pod_type: str) -> int:
    gen = pod_type.partition("-")[0]
    return _CHIPS_PER_HOST.get(gen, 4)


def pod_type_num_hosts(pod_type: str) -> int:
    chips = pod_type_num_chips(pod_type)
    per_host = pod_type_chips_per_host(pod_type)
    return max(1, chips // per_host)


def slice_head_resource_name(pod_type: str) -> str:
    """Gang resource present only on host 0 of a slice: lets one actor/PG
    claim the whole slice by requesting ``{"TPU-v4-32-head": 1}``."""
    from ray_tpu.core.resources import tpu_slice_head_resource

    return tpu_slice_head_resource(pod_type)


# ---------------------------------------------------------------------------


class TPUAcceleratorManager(AcceleratorManager):
    @staticmethod
    def get_resource_name() -> str:
        return "TPU"

    @staticmethod
    def get_visible_accelerator_ids_env_var() -> str:
        return TPU_VISIBLE_CHIPS_ENV

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Chips on this host: env override → device files → metadata."""
        override = os.environ.get(NUM_CHIPS_OVERRIDE_ENV)
        if override:
            return int(override)
        chips = _chip_device_files()
        if chips:
            return len(chips)
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type:
            return min(
                pod_type_num_chips(pod_type), pod_type_chips_per_host(pod_type)
            )
        return 0

    @staticmethod
    def get_current_node_tpu_pod_type() -> Optional[str]:
        """Pod/slice type (e.g. ``"v4-32"``): env → GCE/GKE metadata."""
        t = os.environ.get(ACCELERATOR_TYPE_OVERRIDE_ENV)
        if t:
            return t
        t = _fetch_metadata("attributes/accelerator-type")
        if t:
            return t.strip()
        return None

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """Family type string, e.g. ``"TPU-V4"`` (used as a node label)."""
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if not pod_type:
            return None
        gen = pod_type.partition("-")[0]
        return f"TPU-{gen.upper()}"

    @staticmethod
    def get_current_node_tpu_worker_id() -> Optional[int]:
        """This host's index within its slice: env → metadata."""
        wid = os.environ.get(WORKER_ID_OVERRIDE_ENV)
        if wid is not None and wid != "":
            return int(wid)
        wid = _fetch_metadata("attributes/agent-worker-number")
        if wid:
            return int(wid.strip())
        return None

    @staticmethod
    def get_current_node_tpu_name() -> Optional[str]:
        name = os.environ.get(TPU_NAME_ENV)
        if name:
            return name
        name = _fetch_metadata("attributes/instance-id")
        return name.strip() if name else None

    @staticmethod
    def get_num_workers_in_current_tpu_pod() -> Optional[int]:
        """Host count of this slice: hostnames env → pod-type arithmetic."""
        hostnames = os.environ.get(WORKER_HOSTNAMES_ENV)
        if hostnames:
            return len(hostnames.split(","))
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type:
            return pod_type_num_hosts(pod_type)
        return None

    @staticmethod
    def validate_resource_request_quantity(quantity: float):
        if quantity != int(quantity):
            return False, f"TPU request must be a whole number, got {quantity}"
        q = int(quantity)
        # A multi-host request is expressed via slice resources/PGs, not a
        # single worker asking for more chips than one process may hold.
        if q not in VALID_CHIPS_PER_PROCESS and q % 4 != 0:
            return (
                False,
                f"a process can use {VALID_CHIPS_PER_PROCESS} chips (or all "
                f"chips of whole hosts, multiples of 4); got {q}",
            )
        return True, None

    @staticmethod
    def isolation_env(ids: List[str]) -> Dict[str, str]:
        """The complete env-var set for restricting a process to ``ids`` —
        one source of truth for both the spawn path (daemon) and the
        in-process path (set_current_process_visible_accelerator_ids).
        Includes the topology hints for libtpu: without these a process
        holding 1 or 2 chips of a host fails to initialize."""
        env = {TPU_VISIBLE_CHIPS_ENV: ",".join(str(i) for i in ids)}
        n = len(ids)
        if n == 1:
            env[TPU_CHIPS_PER_PROCESS_BOUNDS_ENV] = "1,1,1"
            env[TPU_PROCESS_BOUNDS_ENV] = "1,1,1"
        elif n == 2:
            env[TPU_CHIPS_PER_PROCESS_BOUNDS_ENV] = "1,2,1"
            env[TPU_PROCESS_BOUNDS_ENV] = "1,1,1"
        return env

    @staticmethod
    def set_current_process_visible_accelerator_ids(ids: List[str]) -> None:
        env = TPUAcceleratorManager.isolation_env(ids)
        os.environ.update(env)
        for var in (TPU_CHIPS_PER_PROCESS_BOUNDS_ENV, TPU_PROCESS_BOUNDS_ENV):
            if var not in env:
                os.environ.pop(var, None)

    @staticmethod
    def get_current_process_visible_accelerator_ids() -> Optional[List[str]]:
        raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
        if raw is None:
            return None
        return [s for s in raw.split(",") if s != ""]

    # -- node registration extras ---------------------------------------
    @staticmethod
    def get_additional_node_resources() -> Dict[str, float]:
        """Slice-head gang resource on host 0 of a multi-host slice, plus a
        per-pod-type count resource (reference ``tpu.py`` pod head)."""
        out: Dict[str, float] = {}
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if not pod_type:
            return out
        worker_id = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        # Unknown worker id only implies "head" for single-host slices;
        # on a multi-host slice every host would otherwise advertise the
        # head marker and break the one-gang-per-slice invariant.
        if worker_id == 0 or (worker_id is None and pod_type_num_hosts(pod_type) == 1):
            out[slice_head_resource_name(pod_type)] = 1.0
        return out

    @staticmethod
    def get_additional_node_labels() -> Dict[str, str]:
        out: Dict[str, str] = {}
        accel_type = TPUAcceleratorManager.get_current_node_accelerator_type()
        if accel_type:
            out["ray.io/accelerator-type"] = accel_type
        pod_type = TPUAcceleratorManager.get_current_node_tpu_pod_type()
        if pod_type:
            out["ray.io/tpu-pod-type"] = pod_type
        name = TPUAcceleratorManager.get_current_node_tpu_name()
        if name:
            out["ray.io/tpu-pod-name"] = name
        wid = TPUAcceleratorManager.get_current_node_tpu_worker_id()
        if wid is not None:
            out["ray.io/tpu-worker-id"] = str(wid)
        return out
