"""The plain reference of the ``mellum`` family: the Mellum2 block (pre-norm,
grouped-query attention whose head width is a key of its own, layers of two
KINDS, a window layer that sees the last ``sliding_window`` positions under
the plain rotary table and a full layer that sees them all under YaRN's, a
softmax router over all experts with the kept gates renormalised, gated SiLU
experts of which this process may hold a range, no shared expert, untied
head) in straightforward ``jax.numpy`` and float32, with ``highest`` matmul
precision. No cache, no kernel, no sort, no grouped matmul: one sequence at a
time, the queries a few hundred at a time against ALL the keys of the
sequence under a mask, a Python loop over the held experts with one mask
each, from the SAME (bf16) weights the system serves.

It reads the system's parameter layout as data (``embed``, ``layers[i]`` with
``wq [D,H,hd]``, ``wk``/``wv [D,KV,hd]``, ``wo [H,hd,D]``, ``router [D,E]``
over ALL ``E`` experts, ``w_gate``/``w_up [held,D,F]``, ``w_down [held,F,D]``
for the held range alone, the two block norms; ``final_norm``; ``lm_head
[D,V]``) and imports nothing of the program.

Layer ``l`` (1-based) of a sequence ``x [T, D]``, ``kind = layer_types[l-1]``:

    h  = rms_norm(x, attn_norm)
    q, k, v = h Wq [T,H,hd], h Wk [T,KV,hd], h Wv [T,KV,hd]      (no bias, no q/k norm)
    q, k = rope_kind(q), rope_kind(k)
        sliding_attention: inv_freq_d = theta^(-2d/hd), cos and sin as they are
        full_attention:    YaRN: inv_freq_d = theta^(-2d/hd) where the pair turns
                           more than beta_fast times over original_max positions,
                           that / factor where fewer than beta_slow times, a
                           linear ramp over the pair index between; cos and sin
                           BOTH times attention_factor (the scores by its square)
    s_ij = q_i . k_j / sqrt(hd), query head a reads KV head a // (H / KV)
    seen(i, j) = j <= i                     (full_attention)
               = i - W < j <= i             (sliding_attention, W = sliding_window)
    x  = x + softmax_seen(s) v Wo
    h2 = rms_norm(x, mlp_norm)
    r  = softmax_float32(h2 W_router)                 over all E
    g, e = top_k(r);  g = g / sum(g)                  (norm_topk_prob)
    x  = x + sum over j with lo <= e_j < hi of g_j W_down[e_j](silu(W_gate[e_j] h2) * (W_up[e_j] h2))

then the final RMS norm and the untied head. With ``[lo, hi)`` a part of the
experts the layer's FFN is THIS process's part of the sum: the other chips'
parts and the exchange that would add them are not stood in for.

Departures from the published model:

* rotary pairs are (even, odd) neighbours, as in the system and in the other
  references, where the Hugging Face implementation pairs element i with i +
  hd/2: with random weights the same model up to a fixed permutation of each
  head's columns;
* no norm over q and k: the published config has no key that declares one
  (``assumed`` in the configuration file);
* the window's edge is the convention ``i - W < j <= i`` (``W`` keys with the
  query's own), which the config has no key for (``assumed``);
* no multi-token-prediction module: the config has no key for one
  (``described_as`` names an "MTP head"; the guide says to trust ``config``);
* ``intermediate_size`` (7168) is read by no layer: every entry of
  ``mlp_layer_types`` is ``sparse``;
* ``next_token_loss`` has no load-balancing term (a training regulariser).

These are the bare equations and nothing else. The CONTROLS of the
correctness limits (wrong models, and the model computed in float8 where the
configuration states bfloat16) are changes of this file's DATA, another
configuration or other weights, which the tests keep:
``tests/perfbench/mellum_controls.py``."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: queries a call of the score matrix: [heads, QUERY_CHUNK, T] float32 is
#: 170 MB at 32 heads and 5200 keys, beside a replica that fills its chip
QUERY_CHUNK = 256
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
    return _Sizes(
        H=int(model["num_attention_heads"]), KV=int(model["num_key_value_heads"]),
        hd=int(model["head_dim"]), eps=float(model["rms_norm_eps"]),
        W=int(model["sliding_window"]), E=total, lo=int(lo), hi=int(hi),
        top_k=int(model["num_experts_per_tok"]), normalise=bool(model["norm_topk_prob"]),
        kinds=tuple(model["layer_types"][: int(model["num_hidden_layers"])]),
        full=_Sizes(ropes["full_attention"]), sliding=_Sizes(ropes["sliding_attention"]),
    )


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(F32)


def inv_freq(hd: int, rope: Dict[str, Any]) -> Tuple[np.ndarray, float]:
    """The rotary frequencies ``[hd / 2]`` (float64 numpy) of a layer kind and
    what multiplies its cos and sin: ``rope_type`` ``default`` or ``yarn``."""
    theta = float(rope["rope_theta"])
    plain = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rope["rope_type"] == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")

    def correction_dim(rotations):
        return hd * math.log(rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    factor = float(rope["factor"])
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return plain / factor * ramp + plain * (1.0 - ramp), float(attention_factor)


def rope(x, freqs, attention_factor: float):
    """x [T, heads, hd] rotated at positions 0..T-1: (even, odd) neighbours a pair."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    cos = (jnp.cos(ang) * attention_factor)[:, None, :]
    sin = (jnp.sin(ang) * attention_factor)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


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


def attention(z: Dict[str, Any], p, h, kind: str):
    """The attention sublayer on normed h [T, D] float32 of a layer of that
    kind: ``softmax_seen(rope(q) rope(k)^T / sqrt(hd)) v Wo`` [T, D]."""
    q, k, v = _project({w: p[w] for w in ("wq", "wk", "wv")}, h)
    freqs, att = inv_freq(z["hd"], z["full"] if kind == "full_attention" else z["sliding"])
    q, k = rope(q, freqs, att), rope(k, freqs, att)
    window = 0 if kind == "full_attention" else z["W"]
    out = [
        _attend(q[first : first + QUERY_CHUNK], k, v, first, window=window)
        for first in range(0, h.shape[0], QUERY_CHUNK)
    ]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


@partial(jax.jit, static_argnames=("top_k", "normalise"))
def gates(router, h, *, top_k: int, normalise: bool):
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: the softmax's value
    for a token's ``top_k`` experts, divided by their sum where ``normalise``,
    0 for the others; ``margin``: by how much the last chosen beat the first
    left out, as a share of its own probability (where it is next to 0 either
    choice is right, and a comparison has to know)."""
    with jax.default_matmul_precision("highest"):
        r = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    E = r.shape[-1]
    best, chosen = jax.lax.top_k(r, min(top_k + 1, E))
    margin = (best[:, top_k - 1] - best[:, top_k]) / best[:, top_k - 1] if top_k < E else jnp.ones(r.shape[0], F32)
    kept = jnp.any(chosen[:, :top_k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, r, 0.0)
    if normalise:
        g = g / g.sum(axis=-1, keepdims=True)
    return g, margin


@jax.jit
def mlp(w_gate, w_up, w_down, h):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_ffn(z: Dict[str, Any], p, h):
    """The FFN sublayer on normed h [T, D] float32: ``(sum over e kept and
    HELD of g_e Expert_e(h) [T, D], margin [T])``. A Python loop over the held
    experts, one mask each, one expert's weights in float32 at a time."""
    g, margin = gates(p["router"], h, top_k=z["top_k"], normalise=z["normalise"])
    out = jnp.zeros_like(h)
    for e in range(z["lo"], z["hi"]):
        i = e - z["lo"]
        out = out + g[:, e, None] * mlp(p["w_gate"][i], p["w_up"][i], p["w_down"][i], h)
    return out, margin


def layer(z: Dict[str, Any], p, x, kind: str):
    """One decoder layer of that kind on x [T, D] float32."""
    x = x + attention(z, p, _rms_norm(x, p["attn_norm"], z["eps"]), kind)
    return x + expert_ffn(z, p, _rms_norm(x, p["mlp_norm"], z["eps"]))[0]


def hidden_states(model: Dict[str, Any], params, tokens, lengths=None) -> List[Any]:
    """tokens [B, T] int32 -> per row the last layer's output [T, D] float32;
    ``lengths``: tokens of each row that anybody reads (the pass is causal, so
    a row is run no further than its last read position)."""
    z = sizes(model)
    out = []
    for b, row in enumerate(np.asarray(tokens)):
        if lengths is not None:
            row = row[: lengths[b]]
        x = params["embed"][jnp.asarray(row)].astype(F32)
        for p, kind in zip(params["layers"], z["kinds"], strict=True):
            x = layer(z, p, x, kind)
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
