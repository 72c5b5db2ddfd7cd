"""TPU accelerator layer tests (mocked metadata — no TPU needed).

Reference test model: ``python/ray/tests/accelerators/test_tpu.py``."""

import os

import pytest

from ray_tpu.accelerators import (
    TPUAcceleratorManager,
    detect_node_accelerators,
    pod_type_chips_per_host,
    pod_type_num_chips,
    pod_type_num_hosts,
    set_metadata_fetcher,
    slice_head_resource_name,
)
from ray_tpu.accelerators.tpu import (
    ACCELERATOR_TYPE_OVERRIDE_ENV,
    NUM_CHIPS_OVERRIDE_ENV,
    TPU_VISIBLE_CHIPS_ENV,
    WORKER_ID_OVERRIDE_ENV,
)


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    for var in (
        NUM_CHIPS_OVERRIDE_ENV,
        ACCELERATOR_TYPE_OVERRIDE_ENV,
        WORKER_ID_OVERRIDE_ENV,
        TPU_VISIBLE_CHIPS_ENV,
        "TPU_WORKER_HOSTNAMES",
        "TPU_NAME",
    ):
        monkeypatch.delenv(var, raising=False)
    set_metadata_fetcher(lambda path: None)  # no metadata server in CI
    yield
    set_metadata_fetcher(None)


def test_pod_type_math():
    # v2-v5p suffixes count TensorCores (2/chip); v5e/v6e count chips.
    assert pod_type_num_chips("v4-8") == 4
    assert pod_type_num_chips("v4-32") == 16
    assert pod_type_num_chips("v5litepod-16") == 16
    assert pod_type_chips_per_host("v4-32") == 4
    assert pod_type_chips_per_host("v5litepod-16") == 8
    assert pod_type_num_hosts("v4-8") == 1
    assert pod_type_num_hosts("v4-32") == 4
    assert pod_type_num_hosts("v5litepod-16") == 2
    assert slice_head_resource_name("v4-32") == "TPU-v4-32-head"


def test_detect_via_env_override(monkeypatch):
    monkeypatch.setenv(NUM_CHIPS_OVERRIDE_ENV, "4")
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4
    resources, labels = detect_node_accelerators()
    assert resources["TPU"] == 4.0


def test_detect_via_metadata(monkeypatch):
    meta = {
        "attributes/accelerator-type": "v4-16",
        "attributes/agent-worker-number": "0",
        "attributes/instance-id": "my-tpu-pod",
    }
    set_metadata_fetcher(meta.get)
    assert TPUAcceleratorManager.get_current_node_tpu_pod_type() == "v4-16"
    assert TPUAcceleratorManager.get_current_node_accelerator_type() == "TPU-V4"
    assert TPUAcceleratorManager.get_current_node_tpu_worker_id() == 0
    # no /dev/accel* in CI → falls back to pod-type arithmetic (4/host)
    assert TPUAcceleratorManager.get_current_node_num_accelerators() == 4
    resources, labels = detect_node_accelerators()
    assert resources["TPU"] == 4.0
    assert resources[slice_head_resource_name("v4-16")] == 1.0
    assert labels["ray.io/accelerator-type"] == "TPU-V4"
    assert labels["ray.io/tpu-pod-name"] == "my-tpu-pod"


def test_head_resource_only_on_worker_zero(monkeypatch):
    meta = {"attributes/accelerator-type": "v4-32"}
    set_metadata_fetcher(meta.get)
    monkeypatch.setenv(WORKER_ID_OVERRIDE_ENV, "1")
    extras = TPUAcceleratorManager.get_additional_node_resources()
    assert slice_head_resource_name("v4-32") not in extras
    monkeypatch.setenv(WORKER_ID_OVERRIDE_ENV, "0")
    extras = TPUAcceleratorManager.get_additional_node_resources()
    assert extras[slice_head_resource_name("v4-32")] == 1.0


def test_visible_chips_isolation(monkeypatch):
    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(["0", "1"])
    assert os.environ[TPU_VISIBLE_CHIPS_ENV] == "0,1"
    assert TPUAcceleratorManager.get_current_process_visible_accelerator_ids() == ["0", "1"]
    # 2 chips → libtpu bounds hints set
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    TPUAcceleratorManager.set_current_process_visible_accelerator_ids(["0", "1", "2", "3"])
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in os.environ


def test_validate_request():
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(2)
    assert ok
    ok, msg = TPUAcceleratorManager.validate_resource_request_quantity(3)
    assert not ok and "chips" in msg
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(8)
    assert ok  # whole hosts
    ok, msg = TPUAcceleratorManager.validate_resource_request_quantity(0.5)
    assert not ok


def test_worker_count(monkeypatch):
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "h0,h1,h2,h3")
    assert TPUAcceleratorManager.get_num_workers_in_current_tpu_pod() == 4
    monkeypatch.delenv("TPU_WORKER_HOSTNAMES")
    monkeypatch.setenv(ACCELERATOR_TYPE_OVERRIDE_ENV, "v4-32")
    assert TPUAcceleratorManager.get_num_workers_in_current_tpu_pod() == 4


def test_daemon_chip_pool_allocation(tmp_path):
    """Daemon assigns disjoint chip ids to dedicated TPU actor workers."""
    from ray_tpu.core.node_daemon import NodeDaemon

    daemon = NodeDaemon.__new__(NodeDaemon)
    daemon._tpu_chips_free = [0, 1, 2, 3]
    a = daemon._allocate_tpu_chips(2)
    b = daemon._allocate_tpu_chips(2)
    assert a == [0, 1] and b == [2, 3]
    assert daemon._allocate_tpu_chips(1) is None  # exhausted
    daemon._free_tpu_chips(a)
    assert daemon._allocate_tpu_chips(2) == [0, 1]


def test_detection_errors_propagate(monkeypatch):
    """A node that cannot tell how many chips it has must not register
    zero and carry on: the error reaches whoever starts the daemon."""
    monkeypatch.setenv(NUM_CHIPS_OVERRIDE_ENV, "four")
    with pytest.raises(ValueError):
        detect_node_accelerators()


def test_peak_table_rejects_unknown_device_kind():
    from ray_tpu.accelerators.tpu import peak_bf16_tflops

    assert peak_bf16_tflops("TPU v5 lite") == 197.0
    assert peak_bf16_tflops("TPU v5p") == 459.0  # longest key wins
    with pytest.raises(ValueError, match="no bf16 peak"):
        peak_bf16_tflops("cpu")


def test_granted_process_must_land_on_its_chips(monkeypatch):
    """A process the daemon granted chips raises when JAX landed
    elsewhere — unless the operator's JAX_PLATFORMS excludes the TPU, in
    which case the report simply says ``cpu``."""
    from ray_tpu.accelerators.tpu import process_device_report

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert process_device_report()["visible_chips"] is None  # nothing granted
    monkeypatch.setenv(TPU_VISIBLE_CHIPS_ENV, "0")
    report = process_device_report()
    assert report["platform"] == "cpu" and report["visible_chips"] == "0"
    for platforms in ("", "tpu,cpu"):  # the TPU was wanted, the CPU answered
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(RuntimeError, match="granted TPU chips"):
            process_device_report()
