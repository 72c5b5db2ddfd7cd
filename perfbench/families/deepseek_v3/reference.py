"""The plain reference of the ``deepseek_v3`` family (GigaChat3.1-702B-A36B):
latent attention (MLA) with YaRN and a value head of its own width, leading
dense layers, then sigmoid-routed experts beside a shared expert with the
choice LIMITED TO GROUPS of experts, and the multi-token-prediction (MTP)
module, in straightforward ``jax.numpy`` and float32 with ``highest`` matmul
precision. No cache, no kernel, no scan, no sort, no grouped matmul: the full
forward pass over the whole sequence, K and V of every position expanded from
its latent, one mask an expert, one row of the batch and one layer's weights at
a time from the SAME (bf16) weights the system serves, queries a few hundred at
a time and the head a slice of the vocabulary at a time so that it fits beside
a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]``; two groups
of layers stacked on a leading axis, ``dense`` (the first
``first_k_dense_replace`` layers) and ``moe``: ``attn_norm``, ``w_qa [D, qr]``,
``q_norm``, ``w_qb [qr, H, dn + dr]``, ``w_kva [D, kr + dr]``, ``kv_norm``,
``w_kvb [kr, H, dn + dv]``, ``wo [H, dv, D]``, ``mlp_norm``; dense: ``w_gate``
/ ``w_up [D, F]``, ``w_down [F, D]``; moe: ``router [D, E]``, ``router_bias
[E]``, ``w_gate`` / ``w_up [held, D, Fm]``, ``w_down [held, Fm, D]`` (the HELD
experts alone, in order), ``shared_gate`` / ``shared_up`` / ``shared_down``;
and ``mtp``: ``enorm [D]``, ``hnorm [D]``, ``eh_proj [2 D, D]``, ``final_norm
[D]``, ``moe`` (ONE stacked expert layer).

The layers (``h`` is a sublayer's input after its RMS norm, eps ``rms_norm_eps``;
the residual is the plain ``x + F(norm(x))``):

    attention   c_q = rms(h W_qa); [q_nope_i | q_rope_i] = c_q W_qb
                [c | k_rope] = h W_kva; c = rms(c); [k_nope_i | v_i] = c W_kvb  (v_i: dv wide, 192 published)
                q_rope_i and the ONE k_rope rotated at the token's position (YaRN)
                score_ij = (q_nope_i k_nope_j + q_rope_i k_rope_j) (dn + dr)^-1/2 m^2,
                m = 0.1 mscale_all_dim ln(factor) + 1; causal softmax; o = concat_i(p v_i) W_o
    FFN         dense layers: W_down(silu(W_gate h) * W_up h)
                expert layers: s = sigmoid(h W_r); c = s + b; the E experts are n_group runs of
                neighbours; a group's score is the sum of its TWO largest c; only the experts of the
                topk_group best groups may be chosen; keep the top-k of c among them;
                g_e = scaling * s_e / sum_kept s;  y = Shared(h) + sum over e kept AND held of g_e Expert_e(h)
    MTP         for position i, h_i the main model's residual after its last layer (BEFORE the final norm):
                h' = [rms(Emb(t_{i+1})) w_e ; rms(h_i) w_h] W_eh; h'' = one expert layer on h' (causal over
                the positions, rope position i); logits_i = Head(rms(h'') w_s): the distribution of t_{i+2}

Departures from the published model and what the config does not say (the
configuration file lists them under ``assumed``):

* rotary pairs are (even, odd) neighbours, as in the system, where the Hugging
  Face MLA implementations pair element i with i + dr/2 after a fixed
  permutation: the same model up to a permutation of ``W_qb``'s and
  ``W_kva``'s rope columns with random weights;
* of the ``n_routed_experts`` the router chooses among, only the held range
  (``deployment.held_experts``) is computed: what the absent experts would add
  is left out here as in the system (one chip of the deployment); the group
  limit is over ALL the groups;
* an expert outside the kept groups reads ``-inf`` for the choice (DeepSeek's
  own inference code; the Hugging Face port fills 0.0, the same choice while
  ``s + b`` is positive);
* the MTP module concatenates the embedding half FIRST and is rotated at the
  position ``i`` of the hidden state it takes (DeepSeek-V3's released
  ``modeling`` for inference leaves the module out; the paper's equation 21
  gives the order ``[rms(h_i) ; rms(Emb(t_{i+1}))]``, vLLM's and SGLang's
  ``DeepSeekMTP`` load ``eh_proj`` for ``[enorm(embed) ; hnorm(hidden)]``: the
  latter is what a served checkpoint's weights mean);
* ``next_token_loss`` is the main model's alone, with no auxiliary term.

The CONTROLS of the correctness limits (wrong models, float8 weights) are kept
by the tests: ``tests/perfbench/deepseek_v3_controls.py``."""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries attended at a time, columns of the head multiplied at a time, and
#: hidden units of an MLP at a time (a dense layer's three matrices are 1.6 GB
#: in float32 at the published widths, beside a replica that fills its chip)
QUERY_CHUNK = 256
VOCAB_CHUNK = 4096
MLP_CHUNK = 4096


class _Sizes(dict):
    """A dict that hashes by its items, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    rs = model.get("rope_scaling") or {}
    lo, hi = model["deployment"]["held_experts"]
    return _Sizes(
        H=int(model["num_attention_heads"]), dn=int(model["qk_nope_head_dim"]),
        dr=int(model["qk_rope_head_dim"]), dv=int(model["v_head_dim"]), kr=int(model["kv_lora_rank"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_theta"]),
        factor=float(rs.get("factor", 1.0)),
        original=int(rs.get("original_max_position_embeddings", model["max_position_embeddings"])),
        beta_fast=float(rs.get("beta_fast", 32)), beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1.0)), mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        top_k=int(model["num_experts_per_tok"]), scaling=float(model["routed_scaling_factor"]),
        normalise=bool(model["norm_topk_prob"]), lo=int(lo), hi=int(hi),
        n_group=int(model["n_group"]), topk_group=int(model["topk_group"]),
    )


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(z: Dict[str, Any]) -> np.ndarray:
    """YaRN's frequencies ``[dr / 2]`` (float64 numpy): ``theta^(-2i/dr)`` for
    the pairs that turn more than ``beta_fast`` times over the original
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
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: a token's gate for
    each of the ``top_k`` experts with the largest ``c = sigmoid(h W_r) + b``
    AMONG the experts of the ``topk_group`` groups whose two largest ``c`` sum
    highest (``scaling * s_e / sum_kept s``, no bias in the gate), 0 for the
    others; ``margin``: the smaller of by how much the last chosen expert beat
    the first left out, in ``c`` among the kept groups, and by how much the
    last kept GROUP beat the first left out, in group score (where it is next
    to 0 either choice is right)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ router.astype(F32))
    c = s + bias.astype(F32)
    T, E = s.shape
    G, k = z["n_group"], z["top_k"]
    margin = jnp.full((T,), jnp.inf, F32)
    if G > 1:
        per_group = jnp.sort(c.reshape(T, G, E // G), axis=-1)
        group_score = per_group[..., -1] + per_group[..., -2]
        ranked = jnp.sort(group_score, axis=-1)[:, ::-1]
        threshold = ranked[:, z["topk_group"] - 1]
        if z["topk_group"] < G:
            margin = threshold - ranked[:, z["topk_group"]]
        in_kept_group = jnp.repeat(group_score >= threshold[:, None], E // G, axis=1)
        c = jnp.where(in_kept_group, c, -jnp.inf)
    best, chosen = jax.lax.top_k(c, min(k + 1, E))
    if k < E:
        margin = jnp.minimum(margin, best[:, k - 1] - best[:, k])
    kept = jnp.any(chosen[:, :k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, s, 0.0)
    if z["normalise"]:
        g = g / g.sum(axis=-1, keepdims=True)
    return z["scaling"] * g, margin


@jax.jit
def _mlp(w_gate, w_up, w_down, h):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def mlp(w_gate, w_up, w_down, h):
    """The gated SiLU MLP, ``MLP_CHUNK`` hidden units at a time (a sum over
    the hidden units, so the chunks add up)."""
    out = 0.0
    for f in range(0, w_gate.shape[1], MLP_CHUNK):
        cut = slice(f, f + MLP_CHUNK)
        out = out + _mlp(w_gate[:, cut], w_up[:, cut], w_down[cut], h)
    return out


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


def layer(z: Dict[str, Any], p, x, moe: bool):
    """One layer on the residual x [T, D] float32: ``x + F(norm(x))`` twice."""
    x = x + attention(z, p, _rms(x, z["eps"]) * p["attn_norm"].astype(F32))
    h = _rms(x, z["eps"]) * p["mlp_norm"].astype(F32)
    if moe:
        return x + expert_ffn(z, p, h)[0]
    return x + mlp(p["w_gate"], p["w_up"], p["w_down"], h)


class _Cut:
    """One layer of a stacked weight, cut out only as far as it is indexed:
    a layer's 16 held experts are 1.4 GB and its dense MLP 0.8 GB at the
    published widths, beside a replica that fills its chip; one expert, or
    ``MLP_CHUNK`` hidden units, is what is on the device at a time."""

    def __init__(self, stacked, layer: int):
        self.stacked, self.layer = stacked, layer

    @property
    def shape(self):
        return self.stacked.shape[1:]

    def __getitem__(self, idx):
        return self.stacked[(self.layer, *(idx if isinstance(idx, tuple) else (idx,)))]


def cut_layer(stacked: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i`` of a stacked group: the MLP's and the experts' matrices as
    :class:`_Cut`, everything else cut out whole."""
    return {k: _Cut(v, i) if k in ("w_gate", "w_up", "w_down") else v[i] for k, v in stacked.items()}


def layers_of(params):
    """``(one layer's weights, is it an expert layer)`` in the model's order,
    cut out of the stacked groups one layer at a time."""
    for group, moe in (("dense", False), ("moe", True)):
        stacked = params.get(group)
        if stacked:
            for i in range(next(iter(stacked.values())).shape[0]):
                yield cut_layer(stacked, i), moe


def hidden_states(model: Dict[str, Any], params, tokens) -> List[Any]:
    """tokens [B, T] int32 -> per row the residual after the last layer,
    ``[T, D]`` float32 (what the final norm takes, and the MTP module)."""
    z = sizes(model)
    out = []
    for row in np.asarray(tokens):
        x = params["embed"][jnp.asarray(row)].astype(F32)
        for p, moe in layers_of(params):
            x = layer(z, p, x, moe)
        out.append(x)
    return out


def mtp_follows(row):
    """The token the MTP module embeds at each position ``i`` of a row of
    ``T`` tokens: the NEXT one, ``t_{i+1}`` (``T - 1`` positions)."""
    return row[1:]


def mtp_hidden_states(model: Dict[str, Any], params, tokens, hidden) -> List[Any]:
    """The MTP module over whole sequences: ``hidden`` as :func:`hidden_states`
    gives it for ``tokens [B, T]``. Position ``i`` takes ``h_i`` and token ``i +
    1``, so a row of ``T`` tokens gives ``T - 1`` positions: per row ``[T - 1,
    D]``, what the module's final norm takes."""
    z = sizes(model)
    mtp = params["mtp"]
    out = []
    for row, h in zip(np.asarray(tokens), hidden):
        e = params["embed"][jnp.asarray(mtp_follows(row))].astype(F32)
        both = jnp.concatenate([
            _rms(e, z["eps"]) * mtp["enorm"].astype(F32),
            _rms(h[:-1], z["eps"]) * mtp["hnorm"].astype(F32),
        ], axis=-1)
        with jax.default_matmul_precision("highest"):
            x = both @ mtp["eh_proj"].astype(F32)
        for p, moe in layers_of({"moe": mtp["moe"]}):
            x = layer(z, p, x, moe)
        out.append(x)
    return out


def head(model: Dict[str, Any], params, x, norm=None):
    """Logits [..., V] float32 (numpy) of residuals x [..., D], a slice of the
    vocabulary at a time; ``norm``: the norm vector (the main model's final
    norm unless told: the MTP module has its own, and the main model's head)."""
    with jax.default_matmul_precision("highest"):
        w = params["final_norm"] if norm is None else norm
        h = _rms(x, float(model["rms_norm_eps"])) * w.astype(F32)
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


def both_logits_at(model: Dict[str, Any], params, tokens, picks, mtp_picks):
    """The main model's logits at ``picks`` and the MTP module's at
    ``mtp_picks`` (position ``i``: the distribution of token ``i + 2``, from
    ``h_i`` and token ``i + 1``, which must be in ``tokens``), one pass over
    ``tokens [B, T]``: ``([len(picks), V], [len(mtp_picks), V])``."""
    hidden = hidden_states(model, params, tokens)
    main = head(model, params, jnp.stack([hidden[i][t] for i, t in picks]))
    after = mtp_hidden_states(model, params, tokens, hidden)
    drafts = head(model, params, jnp.stack([after[i][t] for i, t in mtp_picks]), params["mtp"]["final_norm"])
    return main, drafts


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions
    (the main model's head alone)."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
