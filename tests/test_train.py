"""JaxTrainer end-to-end tests (CPU, multi-process worker gang).

Reference test model: ``train/tests/test_data_parallel_trainer.py``.
XLA cross-process collectives don't run on CPU in CI, so the 2-worker
data-parallel test syncs gradients through the object-store collective
group — the orchestration path (gang PG, session, report, checkpoints,
failure restart) is identical to the TPU case, where sync happens inside
the compiled program over ICI instead.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    Checkpoint,
    CheckpointConfig,
    CheckpointManager,
    FailureConfig,
    JaxBackendConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    TrainingFailedError,
)
from ray_tpu import train


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def _dp_train_fn(config):
    """Linear regression, data-parallel over the object store."""
    from ray_tpu.parallel.collectives import CollectiveGroup

    ctx = train.get_context()
    rank, world = ctx.get_world_rank(), ctx.get_world_size()
    group = (
        CollectiveGroup(f"train-{ctx.get_experiment_name()}", world, rank)
        if world > 1
        else None
    )
    rng = np.random.RandomState(100 + rank)
    w_true = np.array([2.0, -3.0])
    w = np.zeros(2)
    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        state = ckpt.to_dict()
        w, start = state["w"], state["step"]
    for step in range(start, config["steps"]):
        X = rng.randn(32, 2)
        y = X @ w_true
        grad = -2 * X.T @ (y - X @ w) / 32
        if group is not None:
            grad = group.allreduce(grad, op="mean")
        w = w - 0.2 * grad
        loss = float(((y - X @ w) ** 2).mean())
        out_ckpt = None
        if rank == 0 and (step + 1) % 5 == 0:
            out_ckpt = Checkpoint.from_dict({"w": w, "step": step + 1})
        if config.get("crash_at") is not None and step == config["crash_at"] and ckpt is None:
            raise RuntimeError("injected worker failure")
        train.report({"loss": loss, "step": step}, checkpoint=out_ckpt)


@pytest.mark.slow
def test_two_worker_dp_loss_goes_down(cluster, tmp_path):
    trainer = JaxTrainer(
        _dp_train_fn,
        train_loop_config={"steps": 12},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="dp2", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    losses = [m["loss"] for m in result.metrics_history]
    assert len(losses) == 12
    assert losses[-1] < losses[0] * 0.5, losses
    assert result.metrics["step"] == 11
    assert result.checkpoint is not None
    state = result.checkpoint.to_dict()
    np.testing.assert_allclose(state["w"], [2.0, -3.0], atol=0.5)


def test_failure_restart_resumes_from_checkpoint(cluster, tmp_path):
    trainer = JaxTrainer(
        _dp_train_fn,
        train_loop_config={"steps": 10, "crash_at": 7},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="ft",
            storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    result = trainer.fit()
    # crashed at step 7 on attempt 1 (checkpoint was at step 5), resumed
    # from step 5 and ran to completion
    assert result.metrics["step"] == 9
    assert result.checkpoint.to_dict()["step"] == 10


def test_failure_exhausts_max_failures(cluster, tmp_path):
    def always_crash(config):
        raise ValueError("boom")

    trainer = JaxTrainer(
        always_crash,
        train_loop_config={},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="crash", storage_path=str(tmp_path)),
    )
    with pytest.raises(TrainingFailedError, match="boom"):
        trainer.fit()


def test_checkpoint_manager_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "run"), num_to_keep=2,
                            score_attribute="acc", score_order="max")
    paths = []
    for i, acc in enumerate([0.1, 0.9, 0.3, 0.2]):
        c = mgr.register(Checkpoint.from_dict({"i": i}), {"acc": acc})
        paths.append(c.path)
    assert len(mgr.registered) == 2
    # best (acc=0.9) survives retention; latest is the last registered
    assert mgr.best().to_dict()["i"] == 1
    assert mgr.latest().to_dict()["i"] == 3


def test_checkpoint_manager_restore(tmp_path):
    run = str(tmp_path / "run")
    mgr = CheckpointManager(run)
    mgr.register(Checkpoint.from_dict({"step": 1}), {"loss": 1.0})
    mgr2 = CheckpointManager.restore(run)
    assert mgr2.latest().to_dict()["step"] == 1
    mgr2.register(Checkpoint.from_dict({"step": 2}), {"loss": 0.5})
    assert mgr2.latest().to_dict()["step"] == 2


def test_mesh_and_sharding_rules_session_plumbing():
    """JaxBackendConfig.mesh_spec/sharding → context metadata →
    train.get_mesh()/get_sharding_rules() (ISSUE 14 unified-plan
    delivery). Session-level, no cluster: the trainer serializes the
    spec as plain dataclass fields, the session rebuilds the mesh over
    the worker's global devices."""
    from dataclasses import asdict

    import jax

    from ray_tpu.parallel.mesh import FSDP, MeshSpec
    from ray_tpu.train.session import TrainContext, _end_session, _start_session

    ctx = TrainContext(
        metadata={
            "mesh_spec": asdict(MeshSpec(fsdp=-1)),
            "sharding_rules": "fsdp",
        }
    )
    _start_session(ctx)
    try:
        mesh = train.get_mesh()
        assert mesh is not None
        assert mesh.shape[FSDP] == len(jax.devices())  # -1 resolved globally
        rules = train.get_sharding_rules()
        assert rules["embed"] == FSDP and rules["batch"] is not None
        # unconfigured keys degrade to None, unknown table names raise
        ctx.metadata.pop("mesh_spec")
        assert train.get_mesh() is None
        ctx.metadata["sharding_rules"] = "zigzag"
        with pytest.raises(ValueError, match="zigzag"):
            train.get_sharding_rules()
    finally:
        _end_session()


def test_trainer_threads_mesh_spec_into_contexts(cluster, tmp_path):
    """The trainer delivers the SAME plan to every rank (metadata is
    per-rank copied, not shared)."""
    from ray_tpu.parallel.mesh import MeshSpec

    def loop(config=None):
        ctx = train.get_context()
        spec = ctx.metadata.get("mesh_spec")
        train.report(
            {
                "rank": ctx.get_world_rank(),
                "spec_fsdp": spec["fsdp"] if spec else None,
                "rules": ctx.metadata.get("sharding_rules"),
            }
        )

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        backend_config=JaxBackendConfig(
            distributed=False, platform="cpu",
            mesh_spec=MeshSpec(fsdp=-1), sharding="fsdp",
        ),
        run_config=RunConfig(name="mesh-plumb", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.metrics["spec_fsdp"] == -1
    assert result.metrics["rules"] == "fsdp"


def test_scaling_config_topology_bundles():
    sc = ScalingConfig(topology="v4-32", use_tpu=True)
    assert sc.resolved_num_workers() == 4
    bundles = sc.bundles()
    assert len(bundles) == 4
    assert all(b["TPU"] == 4.0 for b in bundles)
    assert bundles[0]["TPU-v4-32-head"] == 1.0
    assert sc.pg_strategy() == "STRICT_SPREAD"


def test_scaling_config_bare_use_tpu_asks_the_cluster(monkeypatch):
    """A bare use_tpu takes a host's chip count from what the cluster's
    nodes detected (1 on a one-chip node, not a fixed 4), and says so
    when no node reports a chip instead of asking for something
    unplaceable."""
    import ray_tpu

    monkeypatch.setattr(
        ray_tpu, "nodes",
        lambda: [
            {"Alive": True, "Resources": {"CPU": 8.0, "TPU": 1.0}},
            {"Alive": False, "Resources": {"CPU": 8.0, "TPU": 4.0}},
        ],
    )
    assert ScalingConfig(use_tpu=True).worker_resources() == {"CPU": 1.0, "TPU": 1.0}
    # an explicit chip count keeps the worker's CPU (the bundle must fit
    # what the worker group asks for)
    explicit = ScalingConfig(resources_per_worker={"TPU": 4})
    assert explicit.worker_resources() == {"CPU": 1.0, "TPU": 4}
    monkeypatch.setattr(
        ray_tpu, "nodes", lambda: [{"Alive": True, "Resources": {"CPU": 8.0}}]
    )
    with pytest.raises(ValueError, match="no live node"):
        ScalingConfig(use_tpu=True).worker_resources()
