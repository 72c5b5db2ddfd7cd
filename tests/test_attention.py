"""Flash attention kernel vs. reference (interpret mode on CPU)."""

import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention, reference_attention


def make_qkv(b=1, h=2, s=256, d=64, seed=0, dtype="float32"):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.3
    k = jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.3
    v = jnp.asarray(rng.randn(b, h, s, d), dtype) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = make_qkv(s=256)
    out = flash_attention(q, k, v, causal=causal, impl="pallas", block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_backward_matches_reference():
    import jax
    import jax.numpy as jnp

    q, k, v = make_qkv(s=256)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, impl="pallas") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_in_kernel_head_mapping(causal):
    """GQA: k/v at n_kv_heads < n_heads must match the repeated-KV
    reference — the kernel maps q-head -> kv-head in its index map."""
    import jax
    import jax.numpy as jnp

    q, _, _ = make_qkv(b=2, h=4, s=256, d=64, seed=1)
    _, k, v = make_qkv(b=2, h=2, s=256, d=64, seed=2)
    out = flash_attention(q, k, v, causal=causal, impl="pallas", block_q=128, block_k=128)
    kr = jnp.repeat(k, 2, axis=1)
    vr = jnp.repeat(v, 2, axis=1)
    ref = reference_attention(q, kr, vr, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, impl="pallas", block_q=128, block_k=128) ** 2)

    def loss_ref(q, k, v):
        kr = jnp.repeat(k, 2, axis=1)
        vr = jnp.repeat(v, 2, axis=1)
        return jnp.sum(reference_attention(q, kr, vr, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


def test_flash_sharded_matches_local():
    """The shard_map wrapper the sharded train step calls the kernel
    through (GSPMD cannot partition a Mosaic kernel): batch over fsdp,
    q and kv heads over tensor, GQA group mapping intact per shard,
    forward and backward."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import flash_attention_sharded
    from ray_tpu.parallel.mesh import MeshSpec, cpu_mesh_devices, make_mesh

    mesh = make_mesh(MeshSpec(fsdp=4, tensor=2), cpu_mesh_devices(8))
    q, _, _ = make_qkv(b=4, h=4, s=128, d=32, seed=3)
    _, k, v = make_qkv(b=4, h=2, s=128, d=32, seed=4)
    spec = P(("data", "fsdp"), "tensor")

    def sharded(q, k, v):
        return flash_attention_sharded(
            q, k, v, mesh, q_spec=spec, kv_spec=spec, impl="pallas"
        )

    def local(q, k, v):
        return reference_attention(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), causal=True
        )

    def grads(fn):
        return jax.jit(
            jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2))
        )(q, k, v)

    np.testing.assert_allclose(
        np.asarray(jax.jit(sharded)(q, k, v)), np.asarray(local(q, k, v)),
        rtol=2e-4, atol=2e-4,
    )
    for a, b in zip(grads(sharded), grads(local)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


def test_flash_branched_mask_path():
    """>=8 K tiles triggers the lax.cond diagonal-branch mask path in
    all three kernels (fwd, bwd_dq, bwd_dkv) — CI must not leave it to
    be discovered on TPU at s>=1024."""
    import jax
    import jax.numpy as jnp

    q, k, v = make_qkv(h=1, s=1024, d=64, seed=4)
    out = flash_attention(q, k, v, causal=True, impl="pallas", block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, impl="pallas", block_q=128, block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3, atol=3e-3)


def test_uneven_seq_block_fallback():
    """Sequences not divisible by the requested block fall back to a
    divisor block (or the sequence itself) instead of erroring."""
    import numpy as np

    q, k, v = make_qkv(s=200)
    out = flash_attention(q, k, v, impl="pallas", block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_layers():
    import jax.numpy as jnp

    from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies

    x = jnp.ones((2, 8), jnp.float32) * 3
    w = jnp.ones((8,))
    out = rms_norm(x, w)
    np.testing.assert_allclose(np.asarray(out), np.ones((2, 8)), rtol=1e-5)
    # the one body every model runs: float32 inside, cast back, THEN times the weight in its own dtype
    xb, wb = jnp.linspace(-3, 3, 16, dtype=jnp.bfloat16).reshape(2, 8), jnp.full((8,), 1.5, jnp.bfloat16)
    x32 = xb.astype(jnp.float32)
    normed = (x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-5)).astype(jnp.bfloat16)
    have = rms_norm(xb, wb, 1e-5)
    assert have.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(have, np.float32), np.asarray(normed * wb, np.float32), rtol=1e-2)

    cos, sin = rope_frequencies(64, 128)
    assert cos.shape == (128, 32)
    xq = jnp.ones((1, 2, 16, 64))
    rotated = apply_rope(xq, cos, sin)
    assert rotated.shape == xq.shape
    # norm preserved by rotation
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rotated), axis=-1),
        np.linalg.norm(np.asarray(xq), axis=-1),
        rtol=1e-5,
    )
