"""Model-layer ops: RMSNorm, rotary embeddings.

Plain-XLA implementations — these fuse into neighboring ops on TPU (XLA
handles elementwise fusion; Pallas is reserved for the ops XLA can't fuse
well, i.e. attention)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps: float = 1e-6):
    """RMSNorm: the mean square and its ``rsqrt`` in float32, the normalised
    activations cast back and THEN times the weight (in the weight's dtype):
    the one every model module runs."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * weight


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0):
    """Precomputed cos/sin tables: ``[max_seq, head_dim//2]``."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, positions=None):
    """Rotary position embedding. x: ``[batch, heads, seq, head_dim]``;
    cos/sin: ``[max_seq, head_dim//2]``; positions: ``[batch, seq]`` or
    None (implicit arange — supports sequence-parallel offsets)."""
    seq = x.shape[2]
    if positions is None:
        c = cos[:seq][None, None, :, :]
        s = sin[:seq][None, None, :, :]
    else:
        c = cos[positions][:, None, :, :]
        s = sin[positions][:, None, :, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(x.dtype)
