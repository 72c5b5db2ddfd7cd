"""Pipeline parallelism: GPipe scheduling over the ``stage`` mesh axis.

Reference: no native impl — the reference simulates PP with compiled
actor DAGs (``dag/tests/experimental/test_accelerated_dag.py:1962``).
TPU-native build-new (SURVEY §2.4): a single SPMD program where stages
live on different devices of the ``stage`` axis, microbatch activations
hop stage→stage with ``lax.ppermute`` over ICI, and the whole schedule
is one ``lax.scan`` — XLA overlaps each step's compute with the
neighbor transfer (scaling-book "pipelining via collective permute").

Schedule: microbatch m is computed by stage s at step t = m + s; the
pipeline runs M + S - 1 steps (fill + drain). Stage 0 injects from the
input queue; the last stage's results are collected per step and
broadcast at the end (psum of a one-stage mask)."""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import STAGE


def stack_stage_params(per_stage_params: list):
    """[params_stage0, params_stage1, ...] → one pytree with a leading
    ``num_stages`` dim (the shard_map input over the stage axis)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params
    )


def pipeline_apply(
    mesh,
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    microbatches: jnp.ndarray,
    *,
    stage_axis: str = STAGE,
):
    """Run ``microbatches [M, ...]`` through ``num_stages`` pipeline
    stages. ``stage_fn(stage_params, x) -> x`` is one stage's compute;
    ``stacked_params`` carries a leading ``num_stages`` dim (see
    ``stack_stage_params``). Returns outputs ``[M, ...]``.

    Differentiable: the scan + ppermute transpose cleanly, so this
    drops into a jitted train step."""
    num_stages = mesh.shape[stage_axis]
    M = microbatches.shape[0]

    def inner(params_local, xs):
        # params_local: [1, ...] (this stage's slice); xs: [M, ...] (replicated)
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(stage_axis)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)

        def step(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped index; masked later)
            inject = xs[jnp.minimum(t, M - 1)]
            state = jnp.where(s == 0, inject, state)
            state = stage_fn(p, state)
            # last stage emits microbatch t-(S-1) after its compute
            out_idx = t - (num_stages - 1)
            is_emit = (s == num_stages - 1) & (out_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, state, jnp.maximum(out_idx, 0), axis=0
            )
            outputs = jnp.where(is_emit, updated, outputs)
            state = jax.lax.ppermute(state, stage_axis, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(
            step, (state0, out0), jnp.arange(M + num_stages - 1)
        )
        # results live on the last stage only — broadcast to every stage
        mask = (s == num_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, stage_axis)

    fn = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, microbatches)
