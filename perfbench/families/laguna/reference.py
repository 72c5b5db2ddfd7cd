"""The plain reference of the ``laguna`` family: the Laguna-XS.2 block
(pre-norm, grouped-query attention in layers of two KINDS that differ in more
than their window: the number of query heads, the rope's base, how much of a
head it rotates and whether YaRN stretches it; a learned sigmoid gate a HEAD
on the attention output; layer 0 a dense gated SiLU MLP, every other layer a
sigmoid router over all experts, the kept scores normalised and scaled, over
gated SiLU experts of which this process may hold a range, beside a shared
expert every token goes through; untied head) in straightforward
``jax.numpy`` and float32, with ``highest`` matmul precision. No cache, no
kernel, no sort, no grouped matmul: one sequence at a time, the queries a few
hundred at a time against ALL the keys of the sequence under a mask, a Python
loop over the held experts with one mask each, from the SAME (bf16) weights
the system serves.

It reads the system's parameter layout as data (``embed``, ``layers[l]`` with
``wq [D,H_l,hd]``, ``wk``/``wv [D,KV,hd]``, ``wo [H_l,hd,D]``, ``wg [D,H_l]``,
the two block norms and either a dense ``w_gate``/``w_up [D,F]``, ``w_down
[F,D]`` or ``router [D,E]`` over ALL ``E`` experts, ``w_gate``/``w_up
[held,D,F]``, ``w_down [held,F,D]`` for the held range alone and
``shared_gate``/``shared_up [D,S]``, ``shared_down [S,D]``; ``final_norm``;
``lm_head [D,V]``) and imports nothing of the program.

Layer ``l`` of a sequence ``x [T, D]``, ``kind = layer_types[l]``, ``H_l =
num_attention_heads_per_layer[l]``:

    h  = rms_norm(x, attn_norm)
    q, k, v = h Wq [T,H_l,hd], h Wk [T,KV,hd], h Wv [T,KV,hd]      (no bias, no q/k norm)
    q, k = rope_kind(q), rope_kind(k): the FIRST r = partial_rotary_factor x hd
        numbers of each head are rotated by a table computed for a width of r,
        the other hd - r pass as they are
        sliding_attention: r = hd, inv_freq_d = theta^(-2d/r), theta 1e4
        full_attention:    r = hd / 2, theta 5e5, YaRN: inv_freq_d where the pair
                           turns more than beta_fast times over original_max
                           positions, that / factor where fewer than beta_slow
                           times, a linear ramp over the pair index between; cos
                           and sin BOTH times attention_factor
    s_ij = q_i . k_j / sqrt(hd), query head a reads KV head a // (H_l / KV)
    seen(i, j) = j <= i                     (full_attention)
               = i - W < j <= i             (sliding_attention, W = sliding_window)
    o  = softmax_seen(s) v                                          [T,H_l,hd]
    g  = sigmoid(h Wg)                                              [T,H_l]   (gating: one number a head)
    x  = x + (g * o) Wo
    f  = rms_norm(x, mlp_norm)
    mlp_layer_types[l] dense:  x = x + W_down(silu(W_gate f) * (W_up f))
    sparse: s = sigmoid_float32(f W_router) over all E;  e = top_k(s)
            w_j = moe_routed_scaling_factor * s_{e_j} / sum_j' s_{e_j'}     (norm_topk_prob)
            x = x + Shared(f) + sum over j with lo <= e_j < hi of w_j Expert_{e_j}(f)

then the final RMS norm and the untied head. With ``[lo, hi)`` a part of the
experts the layer's FFN is THIS process's part of the sum (the shared expert
whole, as every chip computes it): the other chips' parts and the exchange
that would add them are not stood in for.

Departures from the published model, and what the published config does not
say (``assumed`` in the configuration file, with the evidence):

* ``gating: true`` is read as ONE sigmoid a head, computed from the block's
  normed input and applied to the attention output before ``Wo`` (the sibling
  Laguna-S-2.1 says ``"gating": "per-head"``; a gate a channel would make the
  model 34.07 B parameters, one a head 33.44 B = the published 33.4B);
  ``"per-channel"`` (``wg [D,H_l,hd]``) and ``false`` are what the controls run;
* the router scores by a sigmoid each, the kept scores divided by their sum
  and times ``moe_routed_scaling_factor`` (``scoring_func`` / ``norm_topk_prob``
  in the file; the published config has neither key: 2.5 is DeepSeek-V3's
  constant for that form, the sibling says ``norm_topk_prob: true``), no
  bias and no groups, the weight on the expert's OUTPUT
  (``moe_apply_router_weight_on_input`` false); the shared expert ungated;
* rotary pairs are (even, odd) neighbours inside the rotated width, as in the
  system and in the other references, where the Hugging Face implementation
  pairs element i with i + r/2: with random weights the same model up to a
  fixed permutation of each head's columns;
* no norm over q and k: the published config has no key that declares one;
* the window's edge is the convention ``i - W < j <= i`` (``W`` keys with the
  query's own), which the config has no key for;
* ``next_token_loss`` has no load-balancing term (a training regulariser).

A layer reads ``H_l`` from the configuration and takes the first ``H_l`` heads
of its ``wq`` / ``wo`` / ``wg``: all of them in the model as published.

These are the bare equations and nothing else. The CONTROLS of the
correctness limits (wrong models, and the model computed in float8 where the
configuration states bfloat16) are changes of this file's DATA, another
configuration or other weights, which the tests keep:
``tests/perfbench/laguna_controls.py``."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: queries a call of the score matrix: [heads, QUERY_CHUNK, T] float32 is
#: 164 MB at 64 heads and 5000 keys, beside a replica that fills its chip
#: (the check's peak was 16.2 GB of 16.9 at 256: my chip run, PR 56)
QUERY_CHUNK = 128
#: columns of the head a call
VOCAB_CHUNK = 16384


class _Sizes(dict):
    """The numbers the equations read, hashable so that a jitted function
    can take them as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file's keys under the equations' names."""
    dep = model.get("deployment") or {}
    total = int(dep.get("num_experts_total", model["num_experts"]))
    lo, hi = dep.get("held_experts", (0, total))
    ropes = model["rope_parameters"]
    n = int(model["num_hidden_layers"])
    gating = model["gating"]
    return _Sizes(
        KV=int(model["num_key_value_heads"]), hd=int(model["head_dim"]), eps=float(model["rms_norm_eps"]),
        W=int(model["sliding_window"]), E=total, lo=int(lo), hi=int(hi),
        top_k=int(model["num_experts_per_tok"]), normalise=bool(model["norm_topk_prob"]),
        scoring=str(model["scoring_func"]), scale=float(model["moe_routed_scaling_factor"]),
        shared=int(model["shared_expert_intermediate_size"]),
        gating="per-head" if gating is True else gating,
        kinds=tuple(model["layer_types"][:n]), heads=tuple(int(h) for h in model["num_attention_heads_per_layer"][:n]),
        ffn=tuple(model["mlp_layer_types"][:n]),
        full=_Sizes(ropes["full_attention"]), sliding=_Sizes(ropes["sliding_attention"]),
    )


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(F32)


def inv_freq(hd: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """The rotary frequencies ``[r / 2]`` (float64 numpy) of a layer kind, ``r
    = partial_rotary_factor x hd`` the width it rotates, and what multiplies
    its cos and sin: ``rope_type`` ``default`` or ``yarn``."""
    theta = float(rope["rope_theta"])
    r = int(hd * float(rope.get("partial_rotary_factor", 1)))
    plain = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")

    def correction_dim(rotations):
        return r * math.log(rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = float(rope["factor"])
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return plain / factor * ramp + plain * (1.0 - ramp), float(attention_factor)


def rope(x, freqs, attention_factor: float):
    """x [T, heads, hd] at positions 0..T-1: the first ``2 len(freqs)`` numbers
    of each head rotated, (even, odd) neighbours a pair; the others as they are."""
    r = 2 * len(freqs)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    cos = (jnp.cos(ang) * attention_factor)[:, None, :]
    sin = (jnp.sin(ang) * attention_factor)[:, None, :]
    x1, x2 = x[..., :r:2], x[..., 1:r:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(*x.shape[:-1], r)
    return jnp.concatenate([turned, x[..., r:]], axis=-1)


@partial(jax.jit, static_argnames=("window",))
def _attend(q, k, v, first, *, window: int):
    """Queries ``first .. first + len(q)`` of a sequence (``[t, H, hd]``)
    against ALL of its keys (``[T, KV, hd]``), causal and, where ``window``,
    no further back than ``window`` keys with the query's own: ``[t, H, hd]``."""
    with jax.default_matmul_precision("highest"):
        t, H, hd = q.shape
        KV = k.shape[1]
        qg = q.reshape(t, KV, H // KV, hd)
        s = jnp.einsum("tgrk,sgk->grts", qg, k) / math.sqrt(hd)
        i = first + jnp.arange(t)[:, None]
        j = jnp.arange(k.shape[0])[None, :]
        seen = j <= i
        if window:
            seen &= j > i - window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("grts,sgk->tgrk", jax.nn.softmax(s, axis=-1), v).reshape(t, H, hd)


@jax.jit
def _project(p, h):
    with jax.default_matmul_precision("highest"):
        return tuple(jnp.einsum("td,dhk->thk", h, p[w].astype(F32)) for w in ("wq", "wk", "wv"))


@jax.jit
def _gate(wg, h):
    """sigmoid(h Wg): ``[T, H]`` for a gate a head (``wg [D, H]``), ``[T, H,
    hd]`` for one a channel (``wg [D, H, hd]``)."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(jnp.tensordot(h, wg.astype(F32), axes=1))


def attention(z: Dict[str, Any], p, h, kind: str, heads: int):
    """The attention sublayer on normed h [T, D] float32 of a layer of that
    kind with ``heads`` query heads: ``(gate * softmax_seen(rope(q) rope(k)^T /
    sqrt(hd)) v) Wo`` [T, D]."""
    q, k, v = _project({"wq": p["wq"][:, :heads], "wk": p["wk"], "wv": p["wv"]}, h)
    freqs, att = inv_freq(z["hd"], z["full"] if kind == "full_attention" else z["sliding"])
    q, k = rope(q, freqs, att), rope(k, freqs, att)
    window = 0 if kind == "full_attention" else z["W"]
    o = jnp.concatenate([
        _attend(q[first : first + QUERY_CHUNK], k, v, first, window=window)
        for first in range(0, h.shape[0], QUERY_CHUNK)
    ])
    if z["gating"] == "per-head":
        o = o * _gate(p["wg"][:, :heads], h)[..., None]
    elif z["gating"] == "per-channel":
        o = o * _gate(p["wg"][:, :heads], h)
    elif z["gating"]:
        raise ValueError(f"unknown gating {z['gating']!r}")
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", o, p["wo"][:heads].astype(F32))


@partial(jax.jit, static_argnames=("top_k", "normalise", "scoring", "scale"))
def gates(router, h, *, top_k: int, normalise: bool, scoring: str, scale: float):
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: the score of a
    token's ``top_k`` experts (``scoring``: a sigmoid each, or a softmax over
    all), divided by their sum where ``normalise``, times ``scale``, 0 for the
    others; ``margin``: by how much the last chosen beat the first left out,
    as a share of its own score (where it is next to 0 either choice is right,
    and a comparison has to know)."""
    with jax.default_matmul_precision("highest"):
        logits = h @ router.astype(F32)
    r = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    E = r.shape[-1]
    best, chosen = jax.lax.top_k(r, min(top_k + 1, E))
    margin = (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1] if top_k < E else jnp.ones(r.shape[0], F32)
    kept = jnp.any(chosen[:, :top_k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, r, 0.0)
    if normalise:
        g = g / g.sum(axis=-1, keepdims=True)
    return g * scale, margin


@jax.jit
def mlp(w_gate, w_up, w_down, h):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_ffn(z: Dict[str, Any], p, h):
    """A sparse layer's FFN on normed h [T, D] float32: ``(Shared(h) + sum
    over e kept and HELD of w_e Expert_e(h) [T, D], margin [T])``. A Python
    loop over the held experts, one mask each, one expert's weights in float32
    at a time."""
    g, margin = gates(p["router"], h, top_k=z["top_k"], normalise=z["normalise"],
                      scoring=z["scoring"], scale=z["scale"])
    out = jnp.zeros_like(h)
    if z["shared"]:
        out = out + mlp(p["shared_gate"], p["shared_up"], p["shared_down"], h)
    for e in range(z["lo"], z["hi"]):
        i = e - z["lo"]
        out = out + g[:, e, None] * mlp(p["w_gate"][i], p["w_up"][i], p["w_down"][i], h)
    return out, margin


def ffn(z: Dict[str, Any], p, h, kind: str):
    """Layer's FFN of that ``mlp_layer_types`` kind on normed h [T, D]."""
    if kind == "dense":
        return mlp(p["w_gate"], p["w_up"], p["w_down"], h)
    if kind != "sparse":
        raise ValueError(f"unknown mlp layer type {kind!r}")
    return expert_ffn(z, p, h)[0]


def layer(z: Dict[str, Any], p, x, index: int):
    """Decoder layer ``index`` on x [T, D] float32."""
    x = x + attention(z, p, _rms_norm(x, p["attn_norm"], z["eps"]), z["kinds"][index], z["heads"][index])
    return x + ffn(z, p, _rms_norm(x, p["mlp_norm"], z["eps"]), z["ffn"][index])


def hidden_states(model: Dict[str, Any], params, tokens, lengths=None) -> List[Any]:
    """tokens [B, T] int32 -> per row the last layer's output [T, D] float32;
    ``lengths``: tokens of each row that anybody reads (the pass is causal, so
    a row is run no further than its last read position)."""
    z = sizes(model)
    if len(params["layers"]) != len(z["kinds"]):
        raise ValueError(f"{len(params['layers'])} layers of weights for {len(z['kinds'])} layer_types")
    out = []
    for b, row in enumerate(np.asarray(tokens)):
        if lengths is not None:
            row = row[: lengths[b]]
        x = params["embed"][jnp.asarray(row)].astype(F32)
        for index, p in enumerate(params["layers"]):
            x = layer(z, p, x, index)
        out.append(x)
    return out


def head(model: Dict[str, Any], params, x):
    """Logits [..., V] float32 (numpy) of hidden states x [..., D], a slice of
    the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, params["final_norm"], float(model["rms_norm_eps"]))
        V = params["lm_head"].shape[1]
        return np.concatenate([
            np.asarray(h @ params["lm_head"][:, v : v + VOCAB_CHUNK].astype(F32))
            for v in range(0, V, VOCAB_CHUNK)
        ], axis=-1)


def logits_at(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)`` pairs
    ``picks`` of the full forward pass over tokens [B, T]."""
    lengths = [max([p for i, p in picks if i == b], default=0) + 1 for b in range(len(tokens))]
    hidden = hidden_states(model, params, tokens, lengths)
    return head(model, params, jnp.stack([hidden[i][p] for i, p in picks]))


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions
    (no auxiliary term), a row at a time."""
    total = 0.0
    targets = np.asarray(targets)
    for x, want in zip(hidden_states(model, params, tokens), targets):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(want)[:, None], axis=-1)))
    return total / targets.size
