"""Rehearsal of ``chip_smoke.py`` on the CPU with a fake chip.

The chip run itself needs a TPU (``python chip_smoke.py`` through the chip
tool). Here the same two phase functions run at ``LlamaConfig.tiny()``
size against a node that claims one chip (``RAY_TPU_NUM_CHIPS=1``): the
daemon grants it, sets the isolation env, and the operator-set
``JAX_PLATFORMS=cpu`` passes through to the granted workers, so every
check but the platform runs. ``main()`` itself must refuse that platform.
"""

import os
import subprocess
import sys

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _fake_chip_env():
    return {**os.environ, "RAY_TPU_NUM_CHIPS": "1", "JAX_PLATFORMS": "cpu"}


@pytest.fixture(scope="module")
def fake_chip_cluster():
    # main()'s refusal needs a cluster of its own: start it now so that it
    # runs beside the rehearsal instead of after it
    main_proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_fake_chip_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    saved = os.environ.get("RAY_TPU_NUM_CHIPS")
    os.environ["RAY_TPU_NUM_CHIPS"] = "1"
    ray_tpu.init(num_cpus=4)
    try:
        yield main_proc
    finally:
        ray_tpu.shutdown()
        if saved is None:
            os.environ.pop("RAY_TPU_NUM_CHIPS", None)
        else:
            os.environ["RAY_TPU_NUM_CHIPS"] = saved
        if main_proc.poll() is None:
            main_proc.kill()
            main_proc.wait()


@ray_tpu.remote
def _init_jax_on_cpu() -> int:
    import jax.numpy as jnp

    jnp.zeros(1).block_until_ready()
    return os.getpid()


@ray_tpu.remote
def _where_am_i():
    from ray_tpu.accelerators.tpu import process_device_report

    return process_device_report()


def test_pooled_worker_with_jax_up_is_not_promoted(fake_chip_cluster):
    """A pooled worker that already initialized JAX (pinned to the CPU)
    cannot be rebound to a chip: offered one, it refuses, the daemon
    retires it, and the TPU task runs in a fresh process that got its chip
    ids before JAX came up."""
    cpu_pid = ray_tpu.get(_init_jax_on_cpu.remote(), timeout=60)
    report = ray_tpu.get(
        _where_am_i.options(resources={"TPU": 1}).remote(), timeout=60
    )
    assert report["pid"] != cpu_pid
    assert report["visible_chips"] == "0"
    assert report["platform"] == "cpu"  # operator-set JAX_PLATFORMS=cpu


def test_phases_rehearsal(fake_chip_cluster):
    from ray_tpu.inference import EngineConfig
    from ray_tpu.models.llama import LlamaConfig

    while ray_tpu.available_resources().get("TPU", 0) < 1:
        pass  # the previous test's chip-bound worker is being retired
    served = chip_smoke.serve_phase(
        LlamaConfig.tiny(),
        EngineConfig(
            num_blocks=24, block_size=8, prefill_buckets=(16,),
            decode_buckets=(4,), max_decode_batch=4,
        ),
        chips=1,
        requests_per_replica=4,
        prompt_len_range=(30, 40),  # chunked prefill: three 16-token chunks
        check_prompt_len=36,
        max_new_tokens=6,
    )
    (device,) = served["devices"]
    assert device["platform"] == "cpu" and device["visible_chips"] == "0"
    assert served["replicas"][0]["compile_count"] == 3  # prefill, decode, COW

    trained = chip_smoke.train_phase(
        LlamaConfig.tiny(attention_impl="pallas"),
        chips=1,
        batch=8,  # the worker inherits the 8 virtual CPU devices: fsdp=8
        seq=16,
        steps=3,
        cases=[(1, 2, 2, 128), (1, 4, 2, 128)],
    )
    assert trained["device"]["visible_chips"] == "0"
    assert trained["wq_shards"] == trained["jax_devices"]["count"] == 8
    assert all(a["interpret"] for a in trained["attention"])


def test_main_refuses_a_non_tpu_platform(fake_chip_cluster):
    out, err = fake_chip_cluster.communicate(timeout=120)
    assert fake_chip_cluster.returncode not in (0, None), (out, err)
    assert "computes on 'cpu', not a TPU" in err, (out, err)
    assert '"ok"' not in out, out


def test_compile_cache_location(tmp_path):
    from ray_tpu.core.config import COMPILE_CACHE_ENV, ensure_compile_cache_env

    env = {COMPILE_CACHE_ENV: "/somewhere/else"}
    assert ensure_compile_cache_env(env) == "/somewhere/else"
    assert env == {COMPILE_CACHE_ENV: "/somewhere/else"}

    code = (
        "from ray_tpu.core.config import ensure_compile_cache_env as f;"
        "e = {}; print(f(e)); assert e['JAX_COMPILATION_CACHE_DIR'] == f({})"
    )
    env = {k: v for k, v in os.environ.items() if k != COMPILE_CACHE_ENV}
    env["PYTHONPATH"] = REPO
    paths = {
        subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=cwd, text=True,
            capture_output=True, check=True,
        ).stdout.strip()
        for cwd in (REPO, str(tmp_path))
    }
    assert paths == {os.path.join(REPO, ".jax_compile_cache")}
