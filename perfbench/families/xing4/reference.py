"""The plain reference of the ``xing4`` family: latent attention (MLA) with
YaRN, the hyper-connected residual of ``hc_mult`` streams (mHC, arXiv
2512.24880), leading dense layers, then sigmoid-routed experts beside a
shared expert, in straightforward ``jax.numpy`` and float32 with ``highest``
matmul precision. No cache, no kernel, no scan, no sort, no grouped matmul:
the full forward pass over the whole sequence, attention EXPANDED only (K
and V of every position computed from its latent), one mask an expert, one
row of the batch and one layer's weights at a time from the SAME (bf16)
weights the system serves, queries a few hundred at a time and the head a
slice of the vocabulary at a time so that it fits beside a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]``, and two
groups of layers stacked on a leading axis, ``dense`` (the first
``first_k_dense_replace`` layers) and ``moe``: ``attn_norm``, ``w_qa [D,
qr]``, ``q_norm``, ``w_qb [qr, H, dn + dr]``, ``w_kva [D, kr + dr]``,
``kv_norm``, ``w_kvb [kr, H, dn + dv]``, ``wo [H, dv, D]``, ``mlp_norm``,
``hc_attn_phi`` / ``hc_mlp_phi [n D, 2 n + n n]`` (columns: pre, post, res
row-major), ``hc_*_b``, ``hc_*_alpha [3]`` (pre, post, res); dense:
``w_gate`` / ``w_up [D, F]``, ``w_down [F, D]``; moe: ``router [D, E]``,
``router_bias [E]``, ``w_gate`` / ``w_up [held, D, Fm]``, ``w_down [held,
Fm, D]`` (the HELD experts alone, in order), ``shared_gate`` / ``shared_up``
/ ``shared_down``.

The layers (``h`` is a sublayer's input after its RMS norm, eps
``rms_norm_eps``):

    attention   c_q = rms(h W_qa); [q_nope_i | q_rope_i] = c_q W_qb
                [c | k_rope] = h W_kva; c = rms(c); [k_nope_i | v_i] = c W_kvb
                q_rope_i and the ONE k_rope rotated at the token's position (YaRN)
                score_ij = (q_nope_i k_nope_j + q_rope_i k_rope_j) (dn + dr)^-1/2 m^2,
                m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax; o = concat_i(p v_i) W_o
    FFN         dense layers: W_down(silu(W_gate h) * W_up h)
                expert layers: s = sigmoid(h W_r); keep the top-k of s + b; g_e = scaling * s_e / sum_kept s;
                y = Shared(h) + sum over e kept AND held of g_e Expert_e(h)
    residual    X [n, D] a token; for each sublayer F with its own phi, b, alpha:
                xbar = rms(vec X) (no weight); H_pre = sigmoid(a_pre xbar phi_pre + b_pre);
                H_post = 2 sigmoid(a_post xbar phi_post + b_post);
                H_res = Sinkhorn-Knopp(exp(clip(a_res mat(xbar phi_res) + b_res, -30, 30))):
                ``hc_sinkhorn_iters`` rounds of rows then columns, each divided by its sum + ``hc_eps``;
                X <- H_res X + H_post^T (x) F(norm(H_pre X))

Departures from the published model and what the config does not say (the
configuration file lists them under ``assumed``):

* the state starts as the embedding repeated n times, and the n streams are
  summed before the final norm; ``xbar``'s norm has no weight and uses
  ``rms_norm_eps``; Sinkhorn normalises rows first;
* rotary pairs are (even, odd) neighbours, as in the system, where the
  Hugging Face MLA implementations pair element i with i + dr/2 after a
  fixed permutation: the same model up to a permutation of ``W_qb``'s and
  ``W_kva``'s rope columns with random weights;
* of the ``n_routed_experts`` the router chooses among, only the held range
  (``deployment.held_experts``) is computed: what the absent experts would
  add is left out here as in the system (one chip of the deployment);
* no multi-token-prediction module (``num_nextn_predict_layers`` 0 as run);
* ``next_token_loss`` has no auxiliary term (``noaux_tc``: the balance is
  the bias's, a weight here).

The CONTROLS of the correctness limits (wrong models, float8 weights) are
kept by the tests: ``tests/perfbench/xing4_controls.py``."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries attended at a time, and columns of the head multiplied at a time
QUERY_CHUNK = 512
VOCAB_CHUNK = 16384


class _Sizes(dict):
    """A dict that hashes by its items, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    rs = model.get("rope_scaling") or {}
    lo, hi = model["deployment"]["held_experts"]
    return _Sizes(
        n=int(model["hc_mult"]), H=int(model["num_attention_heads"]),
        dn=int(model["qk_nope_head_dim"]), dr=int(model["qk_rope_head_dim"]),
        dv=int(model["v_head_dim"]), kr=int(model["kv_lora_rank"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        factor=float(rs.get("factor", 1.0)),
        original=int(rs.get("original_max_position_embeddings", model["max_position_embeddings"])),
        beta_fast=float(rs.get("beta_fast", 32)), beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)), mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        top_k=int(model["num_experts_per_tok"]), scaling=float(model["routed_scaling_factor"]),
        normalise=bool(model["norm_topk_prob"]), lo=int(lo), hi=int(hi),
        iters=int(model["hc_sinkhorn_iters"]), hc_eps=float(model["hc_eps"]),
        clamp=(float(model["mhc_h_res_clamp_min"]), float(model["mhc_h_res_clamp_max"])),
    )


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(z: Dict[str, Any]) -> np.ndarray:
    """YaRN's frequencies ``[dr / 2]`` (float64 numpy): ``theta^(-2i/dr)``
    for the pairs that turn more than ``beta_fast`` times over the original
    context, divided by ``factor`` for those that turn fewer than
    ``beta_slow`` times, a linear ramp over the pair index between."""
    dr = z["dr"]
    extra = z["theta"] ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
    if z["factor"] == 1.0:
        return extra

    def correction_dim(rotations):
        return dr * math.log(z["original"] / (rotations * 2 * math.pi)) / (2 * math.log(z["theta"]))

    low = max(math.floor(correction_dim(z["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(z["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extra / z["factor"] * ramp + extra * (1.0 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(z: Dict[str, Any]) -> float:
    m = _mscale(z["factor"], z["mscale_all_dim"]) if z["mscale_all_dim"] else 1.0
    return (z["dn"] + z["dr"]) ** -0.5 * m * m


def rope(x, inv_freq, attention_factor: float = 1.0):
    """x [T, ..., dr] rotated at positions 0..T-1: (even, odd) neighbours are a pair."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang) * attention_factor, jnp.sin(ang) * attention_factor
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnums=0)
def mhc_maps(z: Dict[str, Any], phi, b, alpha, X):
    """X [T, n, D] float32 -> ``(H_pre [T, n], H_post [T, n], H_res [T, n, n])``."""
    n = z["n"]
    with jax.default_matmul_precision("highest"):
        xbar = _rms(X.reshape(X.shape[0], -1), z["eps"])
        y = xbar @ phi.astype(F32)
    pre = jax.nn.sigmoid(alpha[0] * y[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * y[:, n : 2 * n] + b[n : 2 * n])
    res = (alpha[2] * y[:, 2 * n :] + b[2 * n :]).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(res, *z["clamp"]))
    for _ in range(z["iters"]):
        m = m / (m.sum(axis=2, keepdims=True) + z["hc_eps"])  # each row by its sum
        m = m / (m.sum(axis=1, keepdims=True) + z["hc_eps"])  # then each column
    return pre, post, m


def hyper(z: Dict[str, Any], p, sub: str, norm: str, X, F):
    """One sublayer through the residual: ``X <- H_res X + H_post^T (x) F(norm(H_pre X))``."""
    pre, post, res = mhc_maps(z, p[f"{sub}_phi"], p[f"{sub}_b"], p[f"{sub}_alpha"], X)
    h = _rms(jnp.einsum("tn,tnd->td", pre, X), z["eps"]) * p[norm].astype(F32)
    y = F(h)
    return jnp.einsum("tij,tjd->tid", res, X) + post[:, :, None] * y[:, None, :]


@partial(jax.jit, static_argnums=0)
def _project(z: Dict[str, Any], p, h):
    """h [T, D] -> ``(q_nope [T, H, dn], q_rope [T, H, dr], k_nope [T, H, dn],
    k_rope [T, dr], v [T, H, dv])``, the rope parts rotated."""
    with jax.default_matmul_precision("highest"):
        w = {k: p[k].astype(F32) for k in ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb")}
        c_q = _rms(h @ w["w_qa"], z["eps"]) * w["q_norm"]
        q = jnp.einsum("tr,rhk->thk", c_q, w["w_qb"])
        ckv = h @ w["w_kva"]
        c = _rms(ckv[:, : z["kr"]], z["eps"]) * w["kv_norm"]
        kv = jnp.einsum("tr,rhk->thk", c, w["w_kvb"])
    inv_freq = yarn_inv_freq(z)
    att = _mscale(z["factor"], z["mscale"]) / _mscale(z["factor"], z["mscale_all_dim"])
    q_rope = rope(q[..., z["dn"] :], inv_freq, att)
    k_rope = rope(ckv[:, z["kr"] :], inv_freq, att)
    return q[..., : z["dn"]], q_rope, kv[..., : z["dn"]], k_rope, kv[..., z["dn"] :]


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_rope, k_nope, k_rope, v, first, *, scale: float):
    """Queries ``first .. first + len(q)`` of a sequence against all of its
    keys, causal: ``[t, H, dv]``."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thk,shk->hts", q_nope, k_nope) + jnp.einsum("thk,sk->hts", q_rope, k_rope)
        q_pos = first + jnp.arange(q_nope.shape[0])
        seen = jnp.arange(k_nope.shape[0])[None, :] <= q_pos[:, None]
        s = jnp.where(seen[None], s * scale, -jnp.inf)
        return jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)


def attention(z: Dict[str, Any], p, h):
    """The attention sublayer's F on normed h [T, D] float32, causal over T."""
    q_nope, q_rope, k_nope, k_rope, v = _project(
        z, {k: p[k] for k in ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb")}, h
    )
    out = []
    for first in range(0, h.shape[0], QUERY_CHUNK):
        cut = slice(first, first + QUERY_CHUNK)
        out.append(_attend(q_nope[cut], q_rope[cut], k_nope, k_rope, v, first,
                           scale=softmax_scale(z)))
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


@partial(jax.jit, static_argnums=0)
def gates(z: Dict[str, Any], router, bias, h):
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: a token's gate
    for each of the ``top_k`` experts with the largest ``sigmoid(h W_r) +
    b`` (``scaling * s_e / sum_kept s``, no bias in the gate), 0 for the
    others; ``margin``: by how much the last chosen beat the first left out
    in ``s + b`` (where it is next to 0 either choice is right)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ router.astype(F32))
    E, k = s.shape[-1], z["top_k"]
    best, chosen = jax.lax.top_k(s + bias.astype(F32), min(k + 1, E))
    margin = best[:, k - 1] - best[:, k] if k < E else jnp.ones(s.shape[0], F32)
    kept = jnp.any(chosen[:, :k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, s, 0.0)
    if z["normalise"]:
        g = g / g.sum(axis=-1, keepdims=True)
    return z["scaling"] * g, margin


@jax.jit
def mlp(w_gate, w_up, w_down, h):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_ffn(z: Dict[str, Any], p, h):
    """The FFN sublayer's F of an EXPERT layer on normed h [T, D]:
    ``(Shared(h) + sum over e kept and held of g_e Expert_e(h), margin [T])``.
    A Python loop over the held experts, one mask each."""
    g, margin = gates(z, p["router"], p["router_bias"], h)
    out = mlp(p["shared_gate"], p["shared_up"], p["shared_down"], h)
    for e in range(z["lo"], z["hi"]):
        i = e - z["lo"]
        out = out + g[:, e, None] * mlp(p["w_gate"][i], p["w_up"][i], p["w_down"][i], h)
    return out, margin


def layer(z: Dict[str, Any], p, X, moe: bool):
    """One layer on the residual state X [T, n, D] float32."""
    X = hyper(z, p, "hc_attn", "attn_norm", X, lambda h: attention(z, p, h))
    if moe:
        return hyper(z, p, "hc_mlp", "mlp_norm", X, lambda h: expert_ffn(z, p, h)[0])
    return hyper(z, p, "hc_mlp", "mlp_norm", X, lambda h: mlp(p["w_gate"], p["w_up"], p["w_down"], h))


def layers_of(model: Dict[str, Any], params):
    """``(one layer's weights, is it an expert layer)`` in the model's order,
    cut out of the stacked groups one layer at a time."""
    for group, moe in (("dense", False), ("moe", True)):
        stacked = params.get(group)
        if stacked:
            for i in range(next(iter(stacked.values())).shape[0]):
                yield {k: v[i] for k, v in stacked.items()}, moe


def hidden_states(model: Dict[str, Any], params, tokens) -> List[Any]:
    """tokens [B, T] int32 -> per row the summed streams after the last
    layer, ``[T, D]`` float32 (what the final norm takes)."""
    z = sizes(model)
    out = []
    for row in np.asarray(tokens):
        x = params["embed"][jnp.asarray(row)].astype(F32)
        X = jnp.broadcast_to(x[:, None, :], (x.shape[0], z["n"], x.shape[1]))
        for p, moe in layers_of(model, params):
            X = layer(z, p, X, moe)
        out.append(X.sum(axis=1))
    return out


def head(model: Dict[str, Any], params, x):
    """Logits [..., V] float32 (numpy) of summed streams x [..., D], a slice
    of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, float(model["rms_norm_eps"])) * params["final_norm"].astype(F32)
        V = params["lm_head"].shape[1]
        return np.concatenate([
            np.asarray(h @ params["lm_head"][:, v : v + VOCAB_CHUNK].astype(F32))
            for v in range(0, V, VOCAB_CHUNK)
        ], axis=-1)


def logits_at(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]."""
    hidden = hidden_states(model, params, tokens)
    return head(model, params, jnp.stack([hidden[i][t] for i, t in picks]))


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
