"""The plain reference of the ``glm_moe_dsa`` family (GLM-5): latent attention
(MLA) whose softmax runs over a LEARNED SPARSE SELECTION of the earlier
positions (DeepSeek Sparse Attention: an indexer scores them, the best
``index_topk`` stay), leading dense layers, then sigmoid-routed experts beside a
shared expert, and the multi-token-prediction (MTP) module, in straightforward
``jax.numpy`` and float32 with ``highest`` matmul precision. No cache, no
kernel, no scan, no grouped matmul, no radix select: the full forward pass over
the whole sequence, K and V of every position expanded from its latent, the
indexer's scores of every (query, earlier position) pair, a stable sort a query
row for the choice, one mask an expert, one row of the batch and one layer's
weights at a time from the SAME (bf16) weights the system serves, queries a few
hundred at a time and the head a slice of the vocabulary at a time so that it
fits beside a serving replica. It imports nothing of the program and of no
other family.

The system's parameter layout is read as data: ``embed [V, D]``, ``final_norm
[D]``, ``lm_head [D, V]``; two groups of layers stacked on a leading axis,
``dense`` (the first ``first_k_dense_replace`` layers) and ``moe``:
``attn_norm``, ``w_qa [D, qr]``, ``q_norm``, ``w_qb [qr, H, dn + dr]``, ``w_kva
[D, kr + dr]``, ``kv_norm``, ``w_kvb [kr, H, dn + dv]``, ``wo [H, dv, D]``,
``mlp_norm``; the indexer's ``idx_wq [qr, Hi, di]``, ``idx_wk [D, di]``,
``idx_k_norm [di]``, ``idx_k_bias [di]``, ``idx_ww [D, Hi]``; dense: ``w_gate``
/ ``w_up [D, F]``, ``w_down [F, D]``; moe: ``router [D, E]``, ``router_bias
[E]``, ``w_gate`` / ``w_up [held, D, Fm]``, ``w_down [held, Fm, D]`` (the HELD
experts alone, in order), ``shared_gate`` / ``shared_up`` / ``shared_down``; and
``mtp``: ``enorm [D]``, ``hnorm [D]``, ``eh_proj [2 D, D]``, ``final_norm
[D]``, ``moe`` (ONE stacked expert layer, indexer included).

One layer (``h`` a sublayer's input after its RMS norm, eps ``rms_norm_eps``;
the residual is the plain ``x + F(norm(x))``), position ``t``:

    c_Q = rms(h W_qa) w_q;  [q_nope_i | q_rope_i] = c_Q W_qb     (64 heads i of 192 + 64)
    [c | k_rope] = h W_kva; c = rms(c) w_kv; [k_nope_i | v_i] = c W_kvb   (v_i: 256 wide)
    q_rope_i and the ONE k_rope rotated at t (the plain table at rope_theta: no YaRN)
    indexer   q_I_j = c_Q W_Iq  (Hi heads j of di), k_I = layer_norm(h W_Ik) w_k + b_k  [di],
              the first dr numbers of each rotated at t;  w_j = (h W_Iw)_j Hi^-1/2 di^-1/2
              I[t, s] = sum_j w_j[t] relu(q_I_j[t] . k_I[s])            s <= t
              S_t = the index_topk positions s <= t of largest I[t, s] (all while t < index_topk;
                    of equal scores the lower position: a stable sort, descending)
    score_i[t, s] = (q_nope_i k_nope_i[s] + q_rope_i k_rope[s]) (dn + dr)^-1/2, softmax over s in S_t ONLY;
    o = concat_i(sum_s p_i[s] v_i[s]) W_o
    FFN  dense layers: W_down(silu(W_gate h) * W_up h)
         expert layers: s = sigmoid(h W_r); keep the top-k of s + b (n_group 1: no group stage);
         g_e = scaling * s_e / sum_kept s;  y = Shared(h) + sum over e kept AND held of g_e Expert_e(h)
    MTP  for position i, h_i the main model's residual after its last layer (BEFORE the final norm):
         h' = [rms(Emb(t_{i+1})) w_e ; rms(h_i) w_h] W_eh; h'' = one expert layer on h' (its own indexer,
         causal over the positions, rope position i); logits_i = Head(rms(h'') w_s): the distribution of t_{i+2}

Departures from the published model and what the config does not say (the
configuration file lists them under ``assumed``):

* rotary pairs are (even, odd) neighbours, in the attention and in the indexer
  (``rope_interleave`` and ``indexer_rope_interleave`` true: neighbours, as the
  system pairs); of an indexer head and of the index key the FIRST
  ``qk_rope_head_dim`` numbers rotate (DeepSeek-V3.2's inference code splits
  ``[rope | nope]`` there, the other way round from the attention's heads);
* the Hadamard rotation that the published inference code applies to ``q_I``
  and ``k_I`` is left out: it is orthogonal and the same on both sides, so every
  product ``q_I . k_I`` is unchanged; so is the float8 form of the cached index
  key and of ``q_I`` (the configuration is served in bfloat16);
* the index key's layer norm has a weight and a bias (1 and 0 as seeded), eps
  ``rms_norm_eps``;
* of the ``n_routed_experts`` the router chooses among, only the held range
  (``deployment.held_experts``) is computed: what the absent experts would add
  is left out here as in the system (one chip of the deployment);
* the MTP module concatenates the embedding half FIRST, is rotated at the
  position ``i`` of the hidden state it takes, and its layer has an indexer of
  its own over ITS inputs (the module is a whole decoder layer of the model);
* ``next_token_loss`` is the main model's alone, with no auxiliary term.

The CONTROLS of the correctness limits (wrong models, float8 weights) are kept
by the tests: ``tests/perfbench/glm_dsa_controls.py``."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries attended at a time, queries scored by the indexer at a time (the
#: per-head products of 256 queries over 6,500 positions are 0.2 GB, three
#: times over) and heads expanded at a time (all 64 heads of 6,500 positions in
#: float32 are 2.6 GB, beside a replica that fills its chip), columns of the head multiplied at a time, and
#: hidden units of an MLP at a time
QUERY_CHUNK = 256
INDEX_CHUNK = 64
HEAD_GROUP = 8
VOCAB_CHUNK = 4096
MLP_CHUNK = 4096

_ATTENTION = ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb",
              "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww")


class _Sizes(dict):
    """A dict that hashes by its items, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    lo, hi = model["deployment"]["held_experts"]
    return _Sizes(
        H=int(model["num_attention_heads"]), dn=int(model["qk_nope_head_dim"]),
        dr=int(model["qk_rope_head_dim"]), dv=int(model["v_head_dim"]), kr=int(model["kv_lora_rank"]),
        eps=float(model["rms_norm_eps"]), theta=float(model["rope_parameters"]["rope_theta"]),
        Hi=int(model["index_n_heads"]), di=int(model["index_head_dim"]), topk=int(model["index_topk"]),
        top_k=int(model["num_experts_per_tok"]), scaling=float(model["routed_scaling_factor"]),
        normalise=bool(model["norm_topk_prob"]), lo=int(lo), hi=int(hi),
    )


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def inv_freq(z: Dict[str, Any]) -> np.ndarray:
    """The plain rotary table ``theta^(-2i/dr)`` ``[dr / 2]`` (float64 numpy)."""
    dr = z["dr"]
    return z["theta"] ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)


def rope(x, freq):
    """x [T, ..., dr] rotated at positions 0..T-1: (even, odd) neighbours are a pair."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freq, F32)[None, :]
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnums=0)
def _project(z: Dict[str, Any], p, h):
    """h [T, D] -> what all heads share: ``(c_q [T, qr]`` the query's normed
    latent, ``c [T, kr]`` the normed latent, ``k_rope [T, dr]`` rotated) and the
    indexer's ``(q_i [T, Hi, di], k_i [T, di], w [T, Hi])``, rope parts rotated."""
    dr, kr = z["dr"], z["kr"]
    names = ("w_qa", "q_norm", "w_kva", "kv_norm", "idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww")
    with jax.default_matmul_precision("highest"):
        w = {k: p[k].astype(F32) for k in names}
        c_q = _rms(h @ w["w_qa"], z["eps"]) * w["q_norm"]
        ckv = h @ w["w_kva"]
        c = _rms(ckv[:, :kr], z["eps"]) * w["kv_norm"]
        q_i = jnp.einsum("tr,rhk->thk", c_q, w["idx_wq"])
        k_i = h @ w["idx_wk"]
        k_i = (k_i - k_i.mean(-1, keepdims=True)) * jax.lax.rsqrt(k_i.var(-1, keepdims=True) + z["eps"])
        k_i = k_i * w["idx_k_norm"] + w["idx_k_bias"]
        w_i = (h @ w["idx_ww"]) * (z["Hi"] * z["di"]) ** -0.5
    freq = inv_freq(z)
    q_i = jnp.concatenate([rope(q_i[..., :dr], freq), q_i[..., dr:]], axis=-1)
    k_i = jnp.concatenate([rope(k_i[..., :dr], freq), k_i[..., dr:]], axis=-1)
    return (c_q, c, rope(ckv[:, kr:], freq)), (q_i, k_i, w_i)


@partial(jax.jit, static_argnums=0)
def _heads(z: Dict[str, Any], w_qb, w_kvb, c_q, c):
    """A few heads expanded from the two latents: ``(q_nope [T, g, dn], q_rope
    [T, g, dr]`` rotated, ``k_nope [T, g, dn], v [T, g, dv])``."""
    dn = z["dn"]
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("tr,rhk->thk", c_q, w_qb.astype(F32))
        kv = jnp.einsum("tr,rhk->thk", c, w_kvb.astype(F32))
    return q[..., :dn], rope(q[..., dn:], inv_freq(z)), kv[..., :dn], kv[..., dn:]


@jax.jit
def index_scores(q_i, k_i, w_i, first):
    """``I [t, S]`` float32 of queries ``first .. first + t`` against every
    position of the sequence; a position after the query reads ``-inf``."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thk,sk->ths", q_i, k_i)
        scores = jnp.sum(jax.nn.relu(s) * w_i[:, :, None], axis=1)
    q_pos = first + jnp.arange(q_i.shape[0])
    seen = jnp.arange(k_i.shape[0])[None, :] <= q_pos[:, None]
    return jnp.where(seen, scores, -jnp.inf)


@partial(jax.jit, static_argnames=("topk",))
def select(scores, *, topk: int):
    """``scores [t, S]`` (``-inf``: not seen) -> ``[t, S]`` bool: the ``topk``
    positions of largest score a row among those seen (all of them where fewer
    are), of equal scores the lower position: a stable sort, descending."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)  # a position's place in the order
    return (rank < topk) & (scores > -jnp.inf)


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_rope, k_nope, k_rope, v, chosen, wo, *, scale: float):
    """A few heads' queries against all keys of their sequence, the softmax
    over ``chosen [t, S]`` only, through their rows of ``W_o``: ``[t, D]``."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thk,shk->hts", q_nope, k_nope) + jnp.einsum("thk,sk->hts", q_rope, k_rope)
        s = jnp.where(chosen[None], s * scale, -jnp.inf)
        o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)
        return jnp.einsum("thk,hkd->td", o, wo.astype(F32))


def selection(z: Dict[str, Any], index, first: int, count: int):
    """``(chosen [count, S] bool, scores [count, S])`` of queries ``first ..
    first + count``: what :func:`attention` attends under. The seam the
    controls change (``tests/perfbench/glm_dsa_controls.py``)."""
    q_i, k_i, w_i = index
    scores = jnp.concatenate([
        index_scores(q_i[at : at + INDEX_CHUNK], k_i, w_i[at : at + INDEX_CHUNK], at)
        for at in range(first, first + count, INDEX_CHUNK)
    ])[:count]
    return select(scores, topk=z["topk"]), scores


def attention(z: Dict[str, Any], p, h, chosen_rows: Optional[Dict[int, Any]] = None):
    """The attention sublayer's F on normed h [T, D] float32, causal over T
    and selected: the choice of every query first (a chunk of queries at a
    time), then ``HEAD_GROUP`` heads at a time under it, each group through its
    rows of ``W_o`` (a sum over the heads, so the groups add up).
    ``chosen_rows``: filled with ``{query: (chosen [S] bool, scores [S])}`` for
    the queries it names (what the check's second and third readings take)."""
    (c_q, c, k_rope), index = _project(z, {k: p[k] for k in _ATTENTION if k not in ("w_qb", "w_kvb")}, h)
    T = h.shape[0]
    chosen = []
    for first in range(0, T, QUERY_CHUNK):
        mask, scores = selection(z, index, first, min(QUERY_CHUNK, T - first))
        for t in (chosen_rows or {}):
            if first <= t < first + QUERY_CHUNK:
                chosen_rows[t] = (np.asarray(mask[t - first]), np.asarray(scores[t - first]))
        chosen.append(mask)
    out = 0.0
    for g in range(0, z["H"], HEAD_GROUP):
        heads = slice(g, g + HEAD_GROUP)
        q_nope, q_rope, k_nope, v = _heads(z, p["w_qb"][:, heads], p["w_kvb"][:, heads], c_q, c)
        out = out + jnp.concatenate([
            _attend(q_nope[cut], q_rope[cut], k_nope, k_rope, v, mask, p["wo"][heads],
                    scale=(z["dn"] + z["dr"]) ** -0.5)
            for mask, cut in zip(chosen, (slice(f, f + QUERY_CHUNK) for f in range(0, T, QUERY_CHUNK)))
        ])
    return out


@partial(jax.jit, static_argnums=0)
def gates(z: Dict[str, Any], router, bias, h):
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: a token's gate for
    each of the ``top_k`` experts with the largest ``c = sigmoid(h W_r) + b``
    (``scaling * s_e / sum_kept s``, no bias in the gate), 0 for the others;
    ``margin``: by how much the last chosen expert beat the first left out in
    ``c`` (where it is next to 0 either choice is right)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ router.astype(F32))
    c = s + bias.astype(F32)
    T, E = s.shape
    k = z["top_k"]
    best, chosen = jax.lax.top_k(c, min(k + 1, E))
    margin = best[:, k - 1] - best[:, k] if k < E else jnp.full((T,), jnp.inf, F32)
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


def layer(z: Dict[str, Any], p, x, moe: bool, chosen_rows=None):
    """One layer on the residual x [T, D] float32: ``x + F(norm(x))`` twice."""
    x = x + attention(z, p, _rms(x, z["eps"]) * p["attn_norm"].astype(F32), chosen_rows)
    h = _rms(x, z["eps"]) * p["mlp_norm"].astype(F32)
    if moe:
        return x + expert_ffn(z, p, h)[0]
    return x + mlp(p["w_gate"], p["w_up"], p["w_down"], h)


class _Cut:
    """One layer of a stacked weight, cut out only as far as it is indexed: one
    expert, or ``MLP_CHUNK`` hidden units, is what is on the device at a time
    beside a replica that fills its chip."""

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


def attention_alone(model: Dict[str, Any], p, h, queries: List[int]):
    """For the check's second and third readings: the attention sublayer's F
    of ONE layer (``p``: its weights, cut out) on normed activations ``h [T,
    D]`` float32 -> ``(out [T, D], {query: (chosen [T] bool, scores [T])})``
    for the ``queries`` named."""
    rows = dict.fromkeys(int(t) for t in queries)
    out = attention(sizes(model), p, h, rows)
    return out, rows


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions
    (the main model's head alone)."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
