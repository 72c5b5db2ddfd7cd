"""The lowered text of every program a deployment warms, hashed: what a change
that should move no number is held to (ROADMAP D10; the builders' method since
PR 35, until PR 48 a script under the gitignored ``.chip_scripts/``).

    python tests/tools/lowered_text.py                       # the seven files of perfbench/configs, tpu and cpu
    python tests/tools/lowered_text.py --platform tpu --ref HEAD
    python tests/tools/lowered_text.py --toy --config xing4.0-29b-a4b-ep8

For a configuration file of ``perfbench/configs/`` (at its own sizes, or with
``--toy`` at its family's toy sizes) it builds the runner the engine would build
(``InferenceEngine``'s own three questions: state slots, the drafter, the window
pools) over ``ShapeDtypeStruct``s (no weight and no cache is ever in memory),
runs ``PagedModelRunner.warmup()`` with every call replaced by
``jax.jit(step).trace(*args).lower(lowering_platforms=(platform,)).as_text()``
and prints ``sha256`` of each text beside the label the warm-up gives the
program (``paged_decode_step[32x4096]``): the same labels, the same count, as
``engine_stats()["startup"]["warmup_programs"]`` on the chip. A configuration
with a ``training`` section gives its train step, over a mesh of the host's
(virtual) devices. Nothing is compiled and nothing runs: a hash says the
PROGRAM is the same, never that it is as fast.

The program picks its kernels from ``jax.default_backend()`` at trace time
(``ops/*::kernel_serves``, ``models/latent.py::paged_serves``): while a text is
lowered for a platform that function answers with that platform, so ``tpu``
rows are the programs a chip runs though this process has none. Source
locations are left out of the texts (:func:`described` says why), so two
checkouts in two directories, or two call stacks, give one hash for one program.

``--ref <rev>``: the same on ``git archive <rev>`` in a temporary directory (a
child process whose ``ray_tpu`` and ``perfbench`` are that tree's), and the rows
that differ; exit 1 if any does."""

from __future__ import annotations

import argparse
import contextlib
import copy
import glob
import hashlib
import json
import os
import subprocess
import sys
import tempfile
from functools import partial
from typing import Any, Dict, Iterator, Optional, Sequence, Tuple

HERE = os.path.abspath(__file__)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


@contextlib.contextmanager
def described(platform: str) -> Iterator[None]:
    """``jax.default_backend()`` answers ``platform`` inside: the one thing
    the program asks of the backend when it chooses a path at trace time. And
    operations carry no source location inside: a kernel's serialised module
    holds the file paths and lines of its WHOLE call stack, so that without
    this a text would say where its callers' lines stand and in which
    directory, beside what the program computes."""
    import jax

    real, frames = jax.default_backend, jax.config.jax_traceback_in_locations_limit
    jax.default_backend = lambda: platform
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        yield
    finally:
        jax.default_backend = real
        jax.config.update("jax_traceback_in_locations_limit", frames)


@contextlib.contextmanager
def _abstract_pools() -> Iterator[None]:
    """The cache and the state pool as shapes: ``CacheLayout.init`` /
    ``StateLayout.init`` traced, not run (a deployment's pool is gigabytes)."""
    import jax

    from ray_tpu.models import interface

    real = {cls: cls.init for cls in (interface.CacheLayout, interface.StateLayout)}
    for cls, init in real.items():
        cls.init = lambda self, n, init=init: jax.eval_shape(partial(init, self, n))
    try:
        yield
    finally:
        for cls, init in real.items():
            cls.init = init


def warmed_texts(cfg, platform: str, **runner_kwargs) -> Tuple[Dict[str, str], Any]:
    """``({label: lowered text}, runner)`` of every program
    ``PagedModelRunner(cfg, <abstract params>, **runner_kwargs).warmup()``
    would compile, lowered for ``platform``; the labels are the warm-up's own."""
    import jax

    from ray_tpu.inference.model_runner import PagedModelRunner
    from ray_tpu.models.interface import model_of

    params = jax.eval_shape(partial(model_of(cfg).init_params, cfg), jax.random.PRNGKey(0))
    texts: Dict[str, str] = {}
    with described(platform), _abstract_pools():
        runner = PagedModelRunner(cfg, params, **runner_kwargs)

        def lower(program: str, fn, *args, bucket=None):
            label = program if bucket is None else f"{program}[{bucket}]"
            if label in texts:
                raise AssertionError(f"warm-up names two programs {label}")
            texts[label] = fn.trace(*args).lower(lowering_platforms=(platform,)).as_text()
            return jax.eval_shape(fn, *args)

        runner._warm = lower
        runner.warmup()
    return texts, runner


def engine_runner_kwargs(cfg, engine_cfg) -> Dict[str, Any]:
    """What ``InferenceEngine.__init__`` hands its runner for this model and
    engine configuration (its own static questions, asked the same way)."""
    from ray_tpu.inference.engine import InferenceEngine

    windows = InferenceEngine._window_pools(cfg, engine_cfg)
    return dict(
        num_blocks=(engine_cfg.num_blocks, *(n for _, n, _ in windows)),
        block_size=engine_cfg.block_size,
        prefill_buckets=engine_cfg.resolved_prefill_buckets(cfg.max_seq_len),
        decode_buckets=engine_cfg.resolved_decode_buckets(),
        verify_buckets=engine_cfg.resolved_verify_buckets(),
        cache_dtype=engine_cfg.cache_dtype,
        state_slots=InferenceEngine._state_slots(cfg, engine_cfg),
        drafter=InferenceEngine._drafts_for_itself(cfg, engine_cfg),
    )


def serving_texts(config: Dict[str, Any], platform: str) -> Dict[str, str]:
    """A configuration file's serving programs, as ``perfbench/harness/
    serve_cell.py`` builds the model and the engine from it."""
    from perfbench import families
    from perfbench.harness.program import engine_config

    serving = config["serving"]
    cfg = families.of(config).model_config(
        config, max_seq_len=int(config["max_position_embeddings"]), **serving.get("model_overrides", {})
    )
    return warmed_texts(cfg, platform, **engine_runner_kwargs(cfg, engine_config(serving["engine"])))[0]


def train_text(config: Dict[str, Any], job: Dict[str, Any], platform: str) -> Dict[str, str]:
    """A configuration file's train step under its mesh and rules, as
    ``perfbench/harness/train_cell.py::loop`` builds it, over the devices this
    process has (virtual CPU devices stand for the host's chips)."""
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import families
    from ray_tpu.parallel import sharding
    from ray_tpu.parallel.mesh import MeshSpec, make_mesh

    family = families.of(config)
    init_sharded, make_train_step, batch_sharding = family.train_program()
    training = config["training"]
    cfg = family.model_config(config, max_seq_len=int(job["seq_len"]), **training.get("model_overrides", {}))
    mesh = make_mesh(MeshSpec(**training["mesh"]))
    rules = {"ddp": sharding.ddp_rules, "fsdp": sharding.fsdp_rules, "tp": sharding.tp_rules}[training["sharding"]]()
    opt = optax.adamw(float(job["lr"]))
    with described(platform):
        state = jax.eval_shape(lambda key: init_sharded(cfg, mesh, rules, key, opt), jax.random.PRNGKey(0))
        step = make_train_step(cfg, opt, mesh=mesh, rules=rules, remat=job["remat"], donate=True)
        rows = jax.ShapeDtypeStruct(
            (int(job["global_batch"]), int(job["seq_len"])), jnp.int32, sharding=batch_sharding(mesh, rules)
        )
        text = step.trace(state, {"tokens": rows, "targets": rows}).lower(lowering_platforms=(platform,)).as_text()
    return {f"train_step[{rows.shape[0]}x{rows.shape[1]}@{mesh.devices.size}]": text}


def _json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def config_hashes(root: str, name: str, platforms: Sequence[str], toy: bool) -> Dict[str, str]:
    """``{"<config> <platform> <label>": sha256}`` of one file of ``perfbench/configs``."""
    config = _json(root, "perfbench", "configs", f"{name}.json")
    job = None
    if "training" in config:
        cells = _json(root, "BENCHMARK.json")["workloads"]
        job = _json(root, "perfbench", "traffic", next(c["traffic"] for c in cells if c["config"] == name) + ".json")
    if toy:
        helpers = os.path.join(root, "tests", "perfbench")
        if helpers not in sys.path:
            sys.path.insert(0, helpers)
        import rehearsal

        config = rehearsal.tiny(config)
        if job is not None:
            job = copy.deepcopy(job)
            job.update(seq_len=16, global_batch=8)
    rows = {}
    for platform in platforms:
        texts = serving_texts(config, platform) if job is None else train_text(config, job, platform)
        for label, text in texts.items():
            rows[f"{name} {platform} {label}"] = hashlib.sha256(text.encode()).hexdigest()
    return rows


def _args(argv: Optional[Sequence[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", help="a name under perfbench/configs (default: every file there)")
    ap.add_argument("--platform", action="append", choices=("tpu", "cpu"), help="default: tpu and cpu")
    ap.add_argument("--toy", action="store_true", help="the family's toy sizes and the rehearsal's toy engine")
    ap.add_argument("--ref", help="a revision to compare with (git archive into a temporary directory)")
    ap.add_argument("--root", default=ROOT, help="the checkout whose ray_tpu and perfbench are lowered")
    ap.add_argument("--json", action="store_true", help="print one JSON object instead of rows")
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = _args(argv)
    root = os.path.abspath(args.root)
    # before jax is imported: the CPU alone, and four of it for a training mesh
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, root)
    names = args.config or sorted(
        os.path.basename(p)[: -len(".json")] for p in glob.glob(os.path.join(root, "perfbench", "configs", "*.json"))
    )
    platforms = args.platform or ["tpu", "cpu"]
    rows: Dict[str, str] = {}
    for name in names:
        rows.update(config_hashes(root, name, platforms, args.toy))
        print(f"lowered {name}", file=sys.stderr, flush=True)
    if args.ref is None:
        if args.json:
            print(json.dumps(rows))
        else:
            for key, digest in rows.items():
                print(f"{digest}  {key}")
        return 0
    with tempfile.TemporaryDirectory(prefix="lowered_text_ref_") as ref:
        archive = subprocess.run(["git", "-C", root, "archive", args.ref], check=True, capture_output=True).stdout
        subprocess.run(["tar", "-x", "-C", ref], input=archive, check=True)
        child = [sys.executable, HERE, "--root", ref, "--json", *(("--toy",) if args.toy else ())]
        for name in names:
            child += ["--config", name]
        for platform in platforms:
            child += ["--platform", platform]
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        theirs = json.loads(subprocess.run(child, cwd=ref, env=env, check=True, capture_output=True, text=True).stdout)
    differ = 0
    for key in (*rows, *(k for k in theirs if k not in rows)):
        mine, other = rows.get(key), theirs.get(key)
        same = mine == other
        differ += not same
        print(f"{'same  ' if same else 'DIFFER'}  {(mine or '-' * 64)[:16]}  {(other or '-' * 64)[:16]}  {key}")
    print(f"{len(rows)} programs here, {len(theirs)} at {args.ref}: {differ} rows differ")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
