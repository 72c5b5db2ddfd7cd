"""The plain reference of the ``lfm2`` family: gated short-convolution layers
and grouped-query attention layers of narrow heads in one pre-norm residual
stream, leading dense MLPs, then sigmoid-routed experts with a choice bias
and no shared expert, the embedding read again as the head, in
straightforward ``jax.numpy`` and float32 with ``highest`` matmul precision.
No cache, no state pool, no kernel, no chunk, no sort, no grouped matmul: the
full forward pass over the whole sequence, the convolution as ``K`` shifted
copies of its input from zeros before position 0, attention over the
sequence itself, one mask an expert, one row of the batch and one layer's
weights at a time from the SAME (bf16) weights the system serves, queries a
few hundred at a time and the head a slice of the vocabulary at a time so
that it fits beside a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]`` (also the head), ``final_norm [D]`` and ``layers``,
one dict a layer in the model's order. Every layer: ``operator_norm``,
``ffn_norm`` and its FFN (dense: ``w_gate`` / ``w_up [D, F]``, ``w_down [F,
D]``; experts: ``router [D, E]``, ``router_bias [E]``, ``w_gate`` / ``w_up
[held, D, Fm]``, ``w_down [held, Fm, D]``: the HELD experts alone, in order).
A convolution layer (it has ``conv_in``): ``conv_in [D, 3 D]`` (B, C, X side
by side), ``conv_taps [K, D]`` (tap ``K - 1`` multiplies the current
position), ``conv_out [D, D]``. An attending layer: ``wq [D, H, hd]``, ``wk``
/ ``wv [D, KV, hd]``, ``q_norm`` / ``k_norm [hd]``, ``wo [H, hd, D]``.

The layers (24 at the published sizes; ``u`` is a sublayer's input after its
RMS norm, ``rms(x, w) = x rsqrt(mean(x^2) + norm_eps) w``, ``norm_eps`` 1e-5;
``h <- h + mix(rms(h))``, ``h <- h + ffn(rms(h))``; final norm, tied head):

    conv        18 layers (``layer_types`` "conv")
                [B | C | X] = u W_in                       (2048 -> 3 x 2048, no bias)
                z_t = B_t * X_t
                c_t = sum_{j=0..2} w[j] * z_{t-2+j}        depthwise, causal, zeros before position 0
                m_t = (C_t * c_t) W_out
    attention   6 layers (``layer_types`` "full_attention": 2, 6, 10, 14, 18, 21)
                q, k, v = u Wq, u Wk, u Wv                 (32 x 64 | 8 x 64 | 8 x 64, no bias)
                q, k = rms over EACH HEAD's 64 (q_norm, k_norm), then rotary (lane i pairs with
                       lane i + 32: the halves convention; theta 1e6)
                scores x 64^-1/2, causal softmax, 4 query heads a KV head;  m = o Wo
    FFN         layers 0-1 (``num_dense_layers`` 2): W2(silu(W1 f) * W3 f), width 7168
                layers 2-23: s = sigmoid(f W_g) [32]; chosen = top-4 of s + expert_bias;
                g = s[chosen] / (sum + 1e-6) x routed_scaling_factor (1)
                y = sum over e chosen AND held of g_e Expert_e(f)               (1792)

What a sequence leaves in a convolution layer is ``z`` at its last two
positions (``conv_L_cache - 1``), zeros where the context starts
(:func:`conv_tail`).

Departures from the published model and what its ``config.json`` does not
say (the configuration file lists them under ``assumed``):

* the head is the embedding (tied): the key is not in the catalog's
  ``config``; one table gives the published 8.3 B total;
* of the ``num_experts`` the router chooses among, only the held range
  (``deployment.held_experts``) is computed: what the absent experts would
  add is left out here as in the system (one chip of the deployment);
* the expert bias is seeded (no checkpoint here), float32, in the choice
  alone; the gates are the scores without it;
* the PROGRAM divides the kept gates by ``max(sum, 1e-9)`` (``ops/moe.py::
  route``, the one routing function) where this file has the published ``sum
  + 1e-6``: 5e-7 of a gate, under float32's own step in the sums around it;
* the trust is in ``config``'s numbers over the prose ``described_as``.

The CONTROLS of the correctness limits (wrong models, float8 weights) are
kept by the tests: ``tests/perfbench/lfm2_controls.py``."""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: queries attended at a time, and rows of the tied head multiplied at a time
QUERY_CHUNK = 512
VOCAB_CHUNK = 16384


class _Sizes(dict):
    """A dict that hashes by its items, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    lo, hi = model["deployment"]["held_experts"]
    return _Sizes(
        H=int(model["num_attention_heads"]), KV=int(model["num_key_value_heads"]),
        taps=int(model["conv_L_cache"]), theta=float(model["rope_theta"]),
        eps=float(model["norm_eps"]), top_k=int(model["num_experts_per_tok"]),
        scaling=float(model["routed_scaling_factor"]), normalise=bool(model["norm_topk_prob"]),
        lo=int(lo), hi=int(hi),
    )


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# the gated short convolution


@jax.jit
def conv_inputs(p, u):
    """u [T, D] float32 -> ``(z, gate) [T, D]``: the convolution's input ``B * X`` and ``C``."""
    with jax.default_matmul_precision("highest"):
        b, c, x = jnp.split(u @ p["conv_in"].astype(F32), 3, axis=-1)
    return b * x, c


@jax.jit
def conv_mix(p, z, gate):
    """``(C_t * sum_j w[j] z_{t - (K - 1) + j}) W_out`` over the whole sequence, zeros before position 0."""
    w = p["conv_taps"].astype(F32)
    K, T = w.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), F32), z])
    c = sum(padded[j : j + T] * w[j] for j in range(K))
    with jax.default_matmul_precision("highest"):
        return (gate * c) @ p["conv_out"].astype(F32)


def conv_tail(z, at: int, keep: int):
    """What the convolution keeps of the first ``at`` positions: its last
    ``keep`` inputs, rows ``at - keep .. at - 1`` of ``z``, zeros before position 0."""
    z = np.asarray(z)
    return np.concatenate([np.zeros((keep, z.shape[1]), np.float32), z])[at : at + keep]


def conv(z: Dict[str, Any], p, u):
    """The convolution mixer on normed u [T, D] float32 over the whole sequence."""
    del z
    return conv_mix(p, *conv_inputs(p, u))


# ---------------------------------------------------------------------------
# grouped-query attention, a norm a head, rotary by halves


def _head_norm(z: Dict[str, Any], x, w):
    """``x [T, heads, hd]`` RMS-normalised over EACH head's ``hd`` numbers, one weight ``[hd]`` for all heads."""
    return _rms(x, z["eps"]) * w.astype(F32)


def _rotate(z: Dict[str, Any], x):
    """``x [T, heads, hd]`` rotated at positions 0..T-1: lane ``i`` pairs with lane ``i + hd / 2``."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * (z["theta"] ** (-jnp.arange(half, dtype=F32) / half))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _project(z: Dict[str, Any], p, u):
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("td,dhk->thk", u, p["wq"].astype(F32))
        k = jnp.einsum("td,dhk->thk", u, p["wk"].astype(F32))
        v = jnp.einsum("td,dhk->thk", u, p["wv"].astype(F32))
    return _rotate(z, _head_norm(z, q, p["q_norm"])), _rotate(z, _head_norm(z, k, p["k_norm"])), v


@jax.jit
def _attend(q, k, v, first):
    """q [t, H, hd] at positions ``first ..`` over k, v [S, KV, hd]."""
    t, H, hd = q.shape
    KV = k.shape[1]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("tgrk,sgk->grts", q.reshape(t, KV, H // KV, hd), k) * hd ** -0.5
        seen = jnp.arange(k.shape[0])[None, :] <= (first + jnp.arange(t))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("grts,sgk->tgrk", jax.nn.softmax(s, axis=-1), v).reshape(t, H, hd)


def attention(z: Dict[str, Any], p, u):
    """The attention mixer on normed u [T, D] float32, causal over T."""
    q, k, v = _project(z, {n: p[n] for n in ("wq", "wk", "wv", "q_norm", "k_norm")}, u)
    out = [_attend(q[first : first + QUERY_CHUNK], k, v, first) for first in range(0, u.shape[0], QUERY_CHUNK)]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


# ---------------------------------------------------------------------------
# the FFNs


@partial(jax.jit, static_argnums=0)
def gates(z: Dict[str, Any], router, bias, f):
    """f [T, D] float32 -> ``(gates [T, E], margin [T])``: a token's gate for
    each of the ``top_k`` experts with the largest ``sigmoid(f W_g) + b``
    (``scaling * s_e / (sum_kept s + 1e-6)``, no bias in the gate), 0 for the
    others; ``margin``: by how much the last chosen beat the first left out."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(f @ router.astype(F32))
    E, k = s.shape[-1], z["top_k"]
    best, chosen = jax.lax.top_k(s + bias.astype(F32), min(k + 1, E))
    margin = best[:, k - 1] - best[:, k] if k < E else jnp.ones(s.shape[0], F32)
    kept = jnp.any(chosen[:, :k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, s, 0.0)
    if z["normalise"]:
        g = g / (g.sum(axis=-1, keepdims=True) + 1e-6)
    return z["scaling"] * g, margin


@jax.jit
def mlp(w_gate, w_up, w_down, f):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(f @ w_gate.astype(F32)) * (f @ w_up.astype(F32))) @ w_down.astype(F32)


def expert_ffn(z: Dict[str, Any], p, f):
    """The FFN of an EXPERT layer on normed f [T, D]: ``(sum over e kept and
    held of g_e Expert_e(f), margin [T])``. A Python loop over the held
    experts, one mask each."""
    g, margin = gates(z, p["router"], p["router_bias"], f)
    out = jnp.zeros_like(f)
    for e in range(z["lo"], z["hi"]):
        i = e - z["lo"]
        out = out + g[:, e, None] * mlp(p["w_gate"][i], p["w_up"][i], p["w_down"][i], f)
    return out, margin


# ---------------------------------------------------------------------------
# the model


def layer(z: Dict[str, Any], p, x):
    """One layer on x [T, D] float32: which mixer and which FFN its weights
    say. Returns ``(x, conv input)``: the convolution's input ``z [T, D]``
    of a convolution layer (its tails are cut from it), None for an
    attending layer."""
    u = _rms(x, z["eps"]) * p["operator_norm"].astype(F32)
    conv_in = None
    if "conv_in" in p:
        conv_in, gate = conv_inputs(p, u)
        x = x + conv_mix(p, conv_in, gate)
    else:
        x = x + attention(z, p, u)
    f = _rms(x, z["eps"]) * p["ffn_norm"].astype(F32)
    if "router" in p:
        return x + expert_ffn(z, p, f)[0], conv_in
    return x + mlp(p["w_gate"], p["w_up"], p["w_down"], f), conv_in


def layers_of(params):
    """One layer's weights at a time, in the model's order."""
    yield from params["layers"]


def hidden_states(model: Dict[str, Any], params, tokens, ats: Sequence[Sequence[int]] = None) -> List[Any]:
    """tokens [B, T] int32 -> per row the residual stream after the last
    layer, ``[T, D]`` float32 (what the final norm takes). With ``ats`` (a
    few lengths a row): ``(that, tails)``, per row and convolution layer what
    a sequence of the row's first ``at`` tokens leaves there, ``[len(ats[row]),
    K - 1, D]`` (numpy)."""
    z = sizes(model)
    out, tails = [], []
    for i, row in enumerate(np.asarray(tokens)):
        x = params["embed"][jnp.asarray(row)].astype(F32)
        kept = []
        for p in layers_of(params):
            x, conv_in = layer(z, p, x)
            if conv_in is not None and ats is not None:
                kept.append(np.stack([conv_tail(conv_in, int(at), z["taps"] - 1) for at in ats[i]]))
        out.append(x)
        tails.append(kept)
    return out if ats is None else (out, tails)


def head(model: Dict[str, Any], params, x):
    """Logits [..., V] float32 (numpy) of x [..., D] through the TIED head, a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, float(model["norm_eps"])) * params["final_norm"].astype(F32)
        V = params["embed"].shape[0]
        return np.concatenate([
            np.asarray(h @ params["embed"][v : v + VOCAB_CHUNK].astype(F32).T)
            for v in range(0, V, VOCAB_CHUNK)
        ], axis=-1)


def logits_at(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]], ats=None):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]. With
    ``ats``: ``(logits, tails)`` (:func:`hidden_states`), from the same pass."""
    hidden = hidden_states(model, params, tokens, ats)
    hidden, tails = hidden if ats is not None else (hidden, None)
    logits = head(model, params, jnp.stack([hidden[i][t] for i, t in picks]))
    return logits if ats is None else (logits, tails)


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
