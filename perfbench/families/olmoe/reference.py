"""The plain reference of the ``olmoe`` family: the OLMoE block (pre-norm,
multi-head attention with an RMS norm of the whole q and of the whole k
projection before the rotary embedding, a softmax router over all experts,
top-k WITHOUT renormalisation, every token through all of its k experts,
untied head) in straightforward ``jax.numpy`` and float32, with ``highest``
matmul precision. A Python loop over the experts with one mask per expert:
no sort, no grouped matmul, no capacity, no cache, one layer at a time from
the SAME (bf16) weights the system serves.

It reads the system's parameter layout as data (``embed``, ``layers[i]``
with ``wq [D,H,hd]``, ``wk``/``wv [D,KV,hd]``, ``wo [H,hd,D]``, ``q_norm
[H*hd]``, ``k_norm [KV*hd]``, ``router [D,E]``, ``w_gate``/``w_up
[E,D,F]``, ``w_down [E,F,D]``, the two block norms; ``final_norm``;
``lm_head [D,V]``) and imports nothing of the program.

The block, as ``modeling_olmoe`` computes it:

    h  = rms_norm(x, attn_norm)
    q  = rms_norm(h Wq, q_norm)     # over the WHOLE projection, before the heads
    k  = rms_norm(h Wk, k_norm)
    v  = h Wv
    x  = x + causal_softmax(rope(q) rope(k)^T / sqrt(hd)) v Wo
    h2 = rms_norm(x, mlp_norm)
    r  = softmax_float32(h2 W_router)
    g, e = top_k(r)                 # no renormalisation (norm_topk_prob false)
    x  = x + sum_j g_j * W_down[e_j](silu(W_gate[e_j] h2) * (W_up[e_j] h2))

Departures from the published model:

* rotary pairs are (even, odd) neighbours, as in the system and in the
  ``dense_gqa`` reference, where the Hugging Face implementation pairs
  element i with i + hd/2. With random weights the two are the same model
  up to a fixed permutation of each head's columns, and an RMS norm over
  the whole projection does not change under a permutation of its columns
  (the norm's weight vector is permuted with them);
* ``next_token_loss`` has no load-balancing and no z-loss term: they are
  training regularisers (``router_aux_loss_coef`` is not in the catalog
  row: ``assumed`` 0 in the configuration file), and the family's train
  program is run with the coefficient 0;
* ``modeling_olmoe`` casts the kept gates to the activations' dtype
  (bfloat16 there); here everything is float32, so the gates stay float32.

These are the bare equations and nothing else. The CONTROLS of the
correctness limits (wrong models, and the model computed in float8 where
the configuration states bfloat16) are a twin of this file that the tests
keep: ``tests/perfbench/olmoe_controls.py``."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

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


@partial(jax.jit, static_argnames=("top_k",))
def routing(router, h, *, top_k: int):
    """h [B, T, D] float32 -> ``(gates [B, T, E], margin [B, T])``, both
    float32. ``gates``: the softmax's value for a token's ``top_k`` experts
    (not renormalised), 0 for the others. ``margin``: by how much the last
    expert chosen beat the first one left out, as a share of its own
    probability (1 where no expert is left out): where it is next to 0
    either choice is right, and a comparison has to know."""
    with jax.default_matmul_precision("highest"):
        r = jax.nn.softmax(h @ router.astype(F32), axis=-1)
        n_experts = router.shape[1]
        best, chosen = jax.lax.top_k(r, min(top_k + 1, n_experts))  # largest first
        gates, chosen = best[..., :top_k], chosen[..., :top_k]
        if top_k < n_experts:
            margin = (best[..., top_k - 1] - best[..., top_k]) / best[..., top_k - 1]
        else:
            margin = jnp.ones(r.shape[:-1], F32)
        dense = jnp.sum(
            jnp.where(chosen[..., None] == jnp.arange(n_experts), gates[..., None], 0.0), axis=-2
        )
        return dense, margin


@jax.jit
def expert(w_gate, w_up, w_down, h, gate):
    """One expert on ALL tokens h [B, T, D], weighted by its gate [B, T]
    (0 where the token did not choose it: the mask)."""
    with jax.default_matmul_precision("highest"):
        wg, wu, wd = w_gate.astype(F32), w_up.astype(F32), w_down.astype(F32)
        return gate[..., None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd)


def expert_ffn(p, h, *, top_k: int):
    """The FFN half of a layer on normed activations h [B, T, D] float32:
    ``(sum_j g_j * expert_j(h) [B, T, D], margin [B, T])``. The experts are
    a Python loop, one small program per expert, so that only one expert's
    weights are ever held in float32 (a layer's 64 would be 1.6 GB beside a
    serving replica's cache)."""
    gates, margin = routing(p["router"], h, top_k=top_k)
    out = jnp.zeros_like(h)
    for e in range(p["router"].shape[1]):
        out = out + expert(p["w_gate"][e], p["w_up"][e], p["w_down"][e], h, gates[..., e])
    return out, margin


@partial(jax.jit, static_argnames=("theta", "eps"))
def attention(p, x, *, theta: float, eps: float):
    """The attention half of a layer on x [B, T, D] float32, causal over
    T: returns ``(x + attention, rms_norm(that, mlp_norm))``."""
    with jax.default_matmul_precision("highest"):
        w = {k: p[k].astype(F32) for k in
             ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "mlp_norm")}
        b, t, _ = x.shape
        h = _rms_norm(x, w["attn_norm"], eps)
        q = jnp.einsum("btd,dhk->bthk", h, w["wq"])
        k = jnp.einsum("btd,dhk->bthk", h, w["wk"])
        v = jnp.einsum("btd,dhk->bthk", h, w["wv"])
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
        x = x + jnp.einsum("bthk,hkd->btd", o, w["wo"])
        return x, _rms_norm(x, w["mlp_norm"], eps)


def layer(p, x, *, theta: float, eps: float, top_k: int):
    """One decoder layer on x [B, T, D] float32."""
    x, h2 = attention(p, x, theta=theta, eps=eps)
    return x + expert_ffn(p, h2, top_k=top_k)[0]


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
        x = layer(p, x, theta=theta, eps=eps, top_k=int(model["num_experts_per_tok"]))
    return x


def logits_at(model: Dict[str, Any], params, tokens, picks):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]."""
    hidden = hidden_states(model, params, jnp.asarray(tokens))
    picked = jnp.stack([hidden[i, p] for i, p in picks])
    return np.asarray(
        head(params["final_norm"], params["lm_head"], picked, eps=float(model["rms_norm_eps"]))
    )


def next_token_loss(model: Dict[str, Any], params, tokens, targets, rows_per_call: int = 1):
    """Mean negative log-likelihood of ``targets`` [B, T] over all
    positions (no auxiliary term), a few rows at a time so that the
    [rows, heads, T, T] scores and the [rows, T, V] logits, all float32,
    fit beside a training state."""
    eps = float(model["rms_norm_eps"])
    total = 0.0
    for i in range(0, tokens.shape[0], rows_per_call):
        x = hidden_states(model, params, tokens[i : i + rows_per_call])
        logits = head(params["final_norm"], params["lm_head"], x, eps=eps)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[i : i + rows_per_call, :, None], axis=-1)
        total += float(-jnp.sum(picked))
    return total / targets.size
