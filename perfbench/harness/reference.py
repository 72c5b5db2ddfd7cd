"""The plain reference of the benchmark's configurations: a Mistral-style
decoder (pre-norm, grouped-query attention with rotary embeddings, gated
SiLU MLP, untied head) in straightforward ``jax.numpy`` and float32, with
``highest`` matmul precision. No cache, no kernels, no batching tricks,
one layer at a time from the SAME (bf16) weights the system serves.

It reads the system's parameter layout as data (``embed``, ``layers[i]``
with ``wq [D,H,hd]``, ``wk``/``wv [D,KV,hd]``, ``wo [H,hd,D]``, ``w_gate``,
``w_up``, ``w_down``, the two norms; ``final_norm``; ``lm_head [D,V]``) and
imports nothing of the program.

Departure from the published model, shared with the system and noted in
the configuration files: rotary pairs are (even, odd) neighbours, where
the Hugging Face implementation pairs element i with i + hd/2. With
random weights the two are the same model up to a fixed permutation of
each head's columns."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(F32)


def _rope(x, theta):
    """x: [B, T, H, hd]; rotate (even, odd) pairs by position * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("theta", "eps"))
def layer(p, x, *, theta: float, eps: float):
    """One decoder layer on x [B, T, D] float32, causal over T."""
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in p.items()}
        h = _rms_norm(x, w["attn_norm"], eps)
        q = _rope(jnp.einsum("btd,dhk->bthk", h, w["wq"]), theta)
        k = _rope(jnp.einsum("btd,dhk->bthk", h, w["wk"]), theta)
        v = jnp.einsum("btd,dhk->bthk", h, w["wv"])
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bthk,bshk->bhts", q, k) / jnp.sqrt(F32(q.shape[-1]))
        t = x.shape[1]
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("bthk,hkd->btd", o, w["wo"])
        h = _rms_norm(x, w["mlp_norm"], eps)
        gated = jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
        return x + gated @ w["w_down"]


@partial(jax.jit, static_argnames=("eps",))
def head(final_norm, lm_head, x, *, eps: float):
    """Logits [..., V] float32 of hidden states x [..., D]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head.astype(F32)


def hidden_states(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> last layer's output [B, T, D] float32."""
    x = params["embed"][tokens].astype(F32)
    theta, eps = float(model["rope_theta"]), float(model["rms_norm_eps"])
    for p in params["layers"]:
        x = layer(p, x, theta=theta, eps=eps)
    return x


def next_token_loss(model: Dict[str, Any], params, tokens, targets, rows_per_call: int = 1):
    """Mean negative log-likelihood of ``targets`` [B, T] over all
    positions, a few rows at a time so that the [rows, heads, T, T] scores
    and the [rows, T, V] logits, all float32, fit beside a training state."""
    eps = float(model["rms_norm_eps"])
    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_call):
        x = hidden_states(model, params, tokens[i : i + rows_per_call])
        logits = head(params["final_norm"], params["lm_head"], x, eps=eps)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[i : i + rows_per_call, :, None], axis=-1)
        total += float(-jnp.sum(picked))
    return total / targets.size
