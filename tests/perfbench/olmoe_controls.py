"""The CONTROLS of the ``olmoe`` family's correctness limits: a twin of the
plain reference (``perfbench/families/olmoe/reference.py``, the same
functions with the same equations) in which a ``variant`` argument makes
the model WRONG or computes it a precision lower. The tests and a builder
setting a limit on the chip use it; the benchmark never does.

``variant`` None is the reference itself (``tests/test_olmoe.py`` holds the
twin to it, exactly). Wrong models: the kept gates ``renormalised``; the
expert with a token's largest (``first_expert_out``) or smallest
(``last_expert_out``) gate left out. A precision lower, what a matmul
multiplies rounded to float8 e4m3 (products and sums stay float32): the
experts alone (``experts_fp8``), router and experts (``moe_fp8``), every
matmul against a weight (``all_fp8``)."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

VARIANTS = (None, "renormalised", "first_expert_out", "last_expert_out",
            "experts_fp8", "moe_fp8", "all_fp8")


def _low(variant, *which):
    """Rounding to float8 and back where ``variant`` is one of ``which``."""
    if variant in which:
        return lambda a: a.astype(jnp.float8_e4m3fn).astype(F32)
    return lambda a: a


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


@partial(jax.jit, static_argnames=("top_k", "variant"))
def routing(router, h, *, top_k: int, variant: Optional[str] = None):
    """As the reference's: ``(gates [B, T, E], margin [B, T])``."""
    with jax.default_matmul_precision("highest"):
        low = _low(variant, "moe_fp8", "all_fp8")
        r = jax.nn.softmax(low(h) @ low(router.astype(F32)), axis=-1)
        n_experts = router.shape[1]
        best, chosen = jax.lax.top_k(r, min(top_k + 1, n_experts))  # largest first
        gates, chosen = best[..., :top_k], chosen[..., :top_k]
        if top_k < n_experts:
            margin = (best[..., top_k - 1] - best[..., top_k]) / best[..., top_k - 1]
        else:
            margin = jnp.ones(r.shape[:-1], F32)
        if variant == "renormalised":
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        if variant == "first_expert_out":
            gates = gates.at[..., 0].set(0.0)
        if variant == "last_expert_out":
            gates = gates.at[..., -1].set(0.0)
        dense = jnp.sum(
            jnp.where(chosen[..., None] == jnp.arange(n_experts), gates[..., None], 0.0), axis=-2
        )
        return dense, margin


@partial(jax.jit, static_argnames=("variant",))
def expert(w_gate, w_up, w_down, h, gate, *, variant: Optional[str] = None):
    """One expert on ALL tokens h [B, T, D], weighted by its gate [B, T]
    (0 where the token did not choose it: the mask)."""
    with jax.default_matmul_precision("highest"):
        wg, wu, wd = w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32)
        low = _low(variant, "experts_fp8", "moe_fp8", "all_fp8")
        h = low(h)
        hidden = jax.nn.silu(h @ low(wg)) * (h @ low(wu))
        return gate[..., None] * (low(hidden) @ low(wd))


@partial(jax.jit, static_argnames=("theta", "eps", "variant"))
def attention(p, x, *, theta: float, eps: float, variant: Optional[str] = None):
    """The attention half of a layer on x [B, T, D] float32, causal over
    T: returns ``(x + attention, rms_norm(that, mlp_norm))``."""
    with jax.default_matmul_precision("highest"):
        w = {k: p[k].astype(F32) for k in
             ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "mlp_norm")}
        b, t, _ = x.shape
        low = _low(variant, "all_fp8")
        h = low(_rms_norm(x, w["attn_norm"], eps))
        q = jnp.einsum("btd,dhk->bthk", h, low(w["wq"]))
        k = jnp.einsum("btd,dhk->bthk", h, low(w["wk"]))
        v = jnp.einsum("btd,dhk->bthk", h, low(w["wv"]))
        # the norm runs over all heads of the projection together
        q = _rms_norm(q.reshape(b, t, -1), w["q_norm"], eps).reshape(q.shape)
        k = _rms_norm(k.reshape(b, t, -1), w["k_norm"], eps).reshape(k.shape)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bthk,bshk->bhts", q, k) / jnp.sqrt(F32(q.shape[-1]))
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
        x = x + jnp.einsum("bthk,hkd->btd", low(o), low(w["wo"]))
        return x, _rms_norm(x, w["mlp_norm"], eps)


def expert_ffn(p, h, *, top_k: int, variant: Optional[str] = None):
    gates, margin = routing(p["router"], h, top_k=top_k, variant=variant)
    out = jnp.zeros_like(h)
    for e in range(p["router"].shape[1]):
        out = out + expert(p["w_gate"][e], p["w_up"][e], p["w_down"][e], h, gates[..., e],
                           variant=variant)
    return out, margin


def layer(p, x, *, theta: float, eps: float, top_k: int, variant: Optional[str] = None):
    x, h2 = attention(p, x, theta=theta, eps=eps, variant=variant)
    return x + expert_ffn(p, h2, top_k=top_k, variant=variant)[0]


@partial(jax.jit, static_argnames=("eps", "variant"))
def head(final_norm, lm_head, x, *, eps: float, variant: Optional[str] = None):
    """Logits [..., V] float32 of hidden states x [..., D]."""
    with jax.default_matmul_precision("highest"):
        low = _low(variant, "all_fp8")
        return low(_rms_norm(x, final_norm, eps)) @ low(lm_head.astype(F32))


def hidden_states(model: Dict[str, Any], params, tokens, variant: Optional[str] = None):
    """tokens [B, T] int32 -> last layer's output [B, T, D] float32."""
    x = params["embed"][tokens].astype(F32)
    theta, eps = float(model["rope_theta"]), float(model["rms_norm_eps"])
    for p in params["layers"]:
        x = layer(p, x, theta=theta, eps=eps, top_k=int(model["num_experts_per_tok"]),
                  variant=variant)
    return x


def logits_at(model: Dict[str, Any], params, tokens, picks, variant: Optional[str] = None):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]."""
    hidden = hidden_states(model, params, jnp.asarray(tokens), variant)
    picked = jnp.stack([hidden[i, p] for i, p in picks])
    return np.asarray(
        head(params["final_norm"], params["lm_head"], picked, eps=float(model["rms_norm_eps"]),
             variant=variant)
    )
