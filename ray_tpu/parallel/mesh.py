"""Device meshes with canonical parallelism axes.

The framework's standard mesh axes (every library component speaks these
names):

  * ``data``  — pure data parallelism (gradient psum over DCN or ICI)
  * ``fsdp``  — data parallelism with parameter sharding (ZeRO-3
                equivalent; GSPMD shards params over this axis)
  * ``tensor``— tensor/model parallelism (matmul-sharded, all-reduce on
                activations; keep within a pod slice so it rides ICI)
  * ``seq``   — sequence/context parallelism (ring attention, Ulysses)
  * ``expert``— MoE expert parallelism (all-to-all dispatch)
  * ``stage`` — pipeline stages

Replaces the reference's process-group bootstrap
(``train/torch/config.py:66-116``): instead of NCCL rendezvous, build a
``jax.sharding.Mesh`` and let pjit/XLA insert collectives. Axis order puts
the fastest-varying (most-communicating) axes last so they map to
adjacent ICI neighbors (cf. the scaling-book recipe).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DATA = "data"
FSDP = "fsdp"
TENSOR = "tensor"
SEQUENCE = "seq"
EXPERT = "expert"
STAGE = "stage"

# canonical order: slower-varying first; tensor last → nearest neighbors
AXIS_ORDER = (STAGE, DATA, FSDP, EXPERT, SEQUENCE, TENSOR)


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape: axis name -> size (missing axes = 1).

    ``MeshSpec(fsdp=8, tensor=4)`` on 32 devices; ``auto`` axes (-1) are
    inferred from the device count.
    """

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    stage: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {
            STAGE: self.stage,
            DATA: self.data,
            FSDP: self.fsdp,
            EXPERT: self.expert,
            SEQUENCE: self.seq,
            TENSOR: self.tensor,
        }

    @property
    def num_devices(self) -> int:
        n = 1
        for v in self.axis_sizes().values():
            n *= abs(v)
        return n

    def resolve(self, device_count: int) -> "MeshSpec":
        """Infer a single -1 axis from the device count."""
        sizes = self.axis_sizes()
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if device_count % known:
                raise ValueError(
                    f"cannot infer {unknown[0]}: {device_count} devices not "
                    f"divisible by {known}"
                )
            sizes[unknown[0]] = device_count // known
        total = math.prod(sizes.values())
        if total != device_count:
            raise ValueError(
                f"mesh {sizes} needs {total} devices, have {device_count}"
            )
        return MeshSpec(
            data=sizes[DATA],
            fsdp=sizes[FSDP],
            tensor=sizes[TENSOR],
            seq=sizes[SEQUENCE],
            expert=sizes[EXPERT],
            stage=sizes[STAGE],
        )

    def active_axes(self) -> Tuple[str, ...]:
        return tuple(k for k in AXIS_ORDER if self.axis_sizes()[k] > 1)


def make_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a ``jax.sharding.Mesh`` with ALL canonical axes (size-1 axes
    included, so sharding rules can always name them)."""
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    spec = spec.resolve(len(devices))
    sizes = spec.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    arr = np.asarray(devices).reshape(shape)
    return jax.sharding.Mesh(arr, AXIS_ORDER)


def cpu_mesh_devices(n: int = 8):
    """CPU devices for the fake-ICI test path. Requires
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and
    ``JAX_PLATFORMS=cpu`` set before jax initializes (tests/conftest.py
    does this; mirrors the reference's mocked-NCCL conftest pattern,
    ``experimental/channel/conftest.py``)."""
    import jax

    devices = jax.devices("cpu")
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} virtual CPU devices, have {len(devices)}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before jax init"
        )
    return devices[:n]


def slice_topology_mesh(num_slices: int, per_slice_spec: MeshSpec, devices=None):
    """Multi-slice mesh: ``data`` axis spans slices over DCN, everything
    else stays inside a slice on ICI (reference's cross-NCCL-group
    training has no equivalent; this is the jax multi-slice recipe)."""
    import jax

    if devices is None:
        devices = jax.devices()
    per = len(devices) // num_slices
    spec = per_slice_spec.resolve(per)
    merged = MeshSpec(
        data=spec.data * num_slices,
        fsdp=spec.fsdp,
        tensor=spec.tensor,
        seq=spec.seq,
        expert=spec.expert,
        stage=spec.stage,
    )
    return make_mesh(merged, devices)
