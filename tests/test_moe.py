"""MoE / expert parallelism (SURVEY §2.4 build-new: EP over the
``expert`` mesh axis with GSPMD-inserted all-to-alls): ``ops.moe.moe_ffn``,
the capacity-limited GShard path that training over an ``expert`` axis
larger than 1 takes. The dropless path of serving and of ``forward`` on one
device is in ``tests/test_olmoe.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.moe import init_moe_params, moe_ffn
from ray_tpu.parallel.mesh import EXPERT, MeshSpec, cpu_mesh_devices, make_mesh


def _reference_moe(params, x, top_k):
    """Per-token reference: every token processed by its top-k experts,
    unlimited capacity."""
    B, S, d = x.shape
    xt = np.asarray(x, np.float64).reshape(-1, d)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xt) @ params["router"], axis=-1))
    out = np.zeros_like(xt)
    for t in range(len(xt)):
        idx = np.argsort(-probs[t])[:top_k]
        gates = probs[t][idx] / probs[t][idx].sum()
        for g, e in zip(gates, idx):
            wg = np.asarray(params["w_gate"][e], np.float64)
            wu = np.asarray(params["w_up"][e], np.float64)
            wd = np.asarray(params["w_down"][e], np.float64)
            h = xt[t] @ wg
            silu = h / (1 + np.exp(-h))
            out[t] += g * ((silu * (xt[t] @ wu)) @ wd)
    return out.reshape(B, S, d)


def test_moe_matches_reference_when_uncapped():
    rng = jax.random.PRNGKey(0)
    params = init_moe_params(rng, dim=16, hidden=32, num_experts=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16), jnp.float32)
    out, aux = moe_ffn(params, x, top_k=2, capacity_factor=8.0)  # uncapped
    ref = _reference_moe(params, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)
    assert float(aux["dropped_fraction"]) == 0.0
    assert float(aux["aux_loss"]) > 0.0


def test_moe_capacity_drops_overflow():
    """Capacity exists on the expert-parallel path alone."""
    rng = jax.random.PRNGKey(0)
    params = init_moe_params(rng, dim=8, hidden=16, num_experts=2)
    # force every token to expert 0: positive inputs x biased router
    params["router"] = jnp.zeros((8, 2)).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))) + 0.1
    out, aux = moe_ffn(params, x, top_k=1, capacity_factor=0.5)
    # capacity = ceil(16/2*0.5) = 4 of 16 tokens kept -> 75% dropped
    assert abs(float(aux["dropped_fraction"]) - 0.75) < 1e-6
    # dropped tokens contribute zero (residual-only pass-through):
    # the LAST tokens overflowed (slots assigned in arrival order)
    np.testing.assert_allclose(np.asarray(out[0, -1]), np.zeros(8), atol=1e-6)


def test_moe_sharded_over_expert_axis():
    """Expert-sharded params on an 8-device mesh: same numerics as
    unsharded (XLA inserts the dispatch all-to-alls)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(MeshSpec(expert=4), cpu_mesh_devices(8)[:4])
    rng = jax.random.PRNGKey(0)
    params = init_moe_params(rng, dim=16, hidden=32, num_experts=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16), jnp.float32)
    dense_out, _ = moe_ffn(params, x, top_k=2, capacity_factor=4.0)

    shard = {
        "router": NamedSharding(mesh, P(None, None)),
        "w_gate": NamedSharding(mesh, P(EXPERT, None, None)),
        "w_up": NamedSharding(mesh, P(EXPERT, None, None)),
        "w_down": NamedSharding(mesh, P(EXPERT, None, None)),
    }
    sharded_params = {k: jax.device_put(v, shard[k]) for k, v in params.items()}
    fn = jax.jit(lambda p, x: moe_ffn(p, x, top_k=2, capacity_factor=4.0)[0])
    sharded_out = fn(sharded_params, x)
    np.testing.assert_allclose(
        np.asarray(sharded_out), np.asarray(dense_out), atol=2e-5, rtol=2e-5
    )


def test_llama_moe_train_step():
    """MoE Llama end to end on a dp×ep mesh: finite loss, expert params
    sharded, params update."""
    import optax

    from ray_tpu.models.llama import (
        LlamaConfig,
        batch_sharding,
        init_sharded,
        make_train_step,
    )
    from ray_tpu.parallel.sharding import tp_rules

    mesh = make_mesh(MeshSpec(data=2, expert=4), cpu_mesh_devices(8))
    cfg = LlamaConfig.tiny(moe_experts=4)
    rules = tp_rules()
    optimizer = optax.adamw(1e-3)
    params, opt_state = init_sharded(cfg, mesh, rules, jax.random.PRNGKey(0), optimizer)
    # expert FFN params really are sharded over the expert axis
    spec = params["layers"][0]["w_gate"].sharding.spec
    assert spec[0] == EXPERT, spec
    step = make_train_step(cfg, optimizer, mesh=mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size, jnp.int32)
    bs = batch_sharding(mesh, rules)
    batch = {"tokens": jax.device_put(tokens, bs), "targets": jax.device_put(tokens, bs)}
    before = np.asarray(params["layers"][0]["w_gate"], np.float32).copy()
    (params2, _), loss = step((params, opt_state), batch)
    assert jnp.isfinite(loss)
    after = np.asarray(params2["layers"][0]["w_gate"], np.float32)
    assert np.abs(after - before).max() > 0
