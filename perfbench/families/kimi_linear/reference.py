"""The plain reference of the ``kimi_linear`` family: gated delta-rule
layers (KDA) and latent attention layers (MLA, NoPE) alternating 3 : 1 in
one pre-norm residual stream, a leading dense MLP, then sigmoid-routed
experts beside a shared expert, in straightforward ``jax.numpy`` and float32
with ``highest`` matmul precision. No cache, no state pool, no kernel, no
chunked form, no sort, no grouped matmul: the full forward pass over the
whole sequence, **KDA as the token-by-token recurrence** (one ``lax.scan``
over the positions from a zero state), attention EXPANDED (K and V of every
position from its latent), one mask an expert, one row of the batch and one
layer's weights at a time from the SAME (bf16) weights the system serves,
queries a few hundred at a time and the head a slice of the vocabulary at a
time so that it fits beside a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]``, ``final_norm [D]``, ``lm_head [D, V]`` and
``layers``, one dict a layer in the model's order. Every layer:
``attn_norm``, ``mlp_norm`` and its FFN (dense: ``w_gate`` / ``w_up [D, F]``,
``w_down [F, D]``; experts: ``router [D, E]``, ``router_bias [E]``, ``w_gate``
/ ``w_up [held, D, Fm]``, ``w_down [held, Fm, D]`` (the HELD experts alone,
in order), ``shared_gate`` / ``shared_up`` / ``shared_down``). A KDA layer
(it has ``kda_wqkv``): ``kda_wqkv [D, 3 W]`` (q, k, v side by side, ``W = H
dk``), ``kda_conv [K, 3 W]`` (tap ``K - 1`` multiplies the current token),
``kda_f_down [D, r]``, ``kda_f_up [r, W]``, ``kda_dt_bias [W]``, ``kda_a_log
[H]``, ``kda_wbeta [D, H]``, ``kda_g_down [D, r]``, ``kda_g_up [r, W]``,
``kda_o_norm [dv]``, ``kda_wo [W, D]``. An attending layer: ``w_q [D, H, dn +
dr]``, ``w_kva [D, kr + dr]``, ``kv_norm [kr]``, ``w_kvb [kr, H, dn + dv]``,
``wo [H, dv, D]``.

The layers (27 at the published sizes; ``h`` is a sublayer's input after
its RMS norm, eps ``rms_norm_eps`` 1e-5; ``x <- x + mix(norm(x))``, ``x <- x +
ffn(norm(x))``; final norm, untied head):

    KDA         layers 1-3, 5-7, ..., 25-26 (``linear_attn_config.kda_layers``)
                [q | k | v] = SiLU(conv(h W_qkv)): depthwise, causal, 4 taps, zeros before position 0
                q, k L2-normalised a head (eps 1e-6 inside the root), q x dk^-1/2
                g_t = -exp(A_log_h) softplus(W_f_up W_f_down h_t + dt_bias)   a CHANNEL of a head
                beta_t = sigmoid(h_t W_beta)                                  a head
                S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,  S_0 = 0, float32
                o_t = S_t^T q_t
                out = W_o [ RMSNorm_head(o_t; kda_o_norm) * sigmoid(W_g_up W_g_down h_t) ]
    MLA, NoPE   layers 4, 8, ..., 24, 27 (``full_attn_layers``; the last period is short)
                [q_nope_i | q_shared_i] = h W_q  (``q_lora_rank`` null: no projection down)
                [c | k_shared] = h W_kva; c = rms(c) kv_norm; [k_nope_i | v_i] = c W_kvb
                NOTHING is rotated (``mla_use_nope``)
                score_ij = (q_nope_i k_nope_j + q_shared_i k_shared_j) (dn + dr)^-1/2; causal softmax
                o = concat_i(p v_i) W_o
    FFN         layer 1 (``first_k_dense_replace`` 1): W_down(silu(W_gate h) * W_up h), width 9216
                layers 2-27: s = sigmoid(h W_r); keep the top-8 of s + b (one group: plain top-k);
                g_e = 2.446 s_e / sum_kept s; y = Shared(h) + sum over e kept AND held of g_e Expert_e(h)

Departures from the published model and what its ``config.json`` does not
say (the configuration file lists them under ``assumed``):

* the low-rank gates' second projections carry no bias (the decay's has
  ``dt_bias`` added before the softplus, as published);
* the state is float32 (as the published kernels keep it);
* of the ``num_experts`` the router chooses among, only the held range
  (``deployment.held_experts``) is computed: what the absent experts would
  add is left out here as in the system (one chip of the deployment);
* the trust is in ``config``'s numbers over the prose ``described_as``.

The CONTROLS of the correctness limits (wrong models, float8 weights) are
kept by the tests: ``tests/perfbench/kimi_linear_controls.py``."""

from __future__ import annotations

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
    lin = model["linear_attn_config"]
    lo, hi = model["deployment"]["held_experts"]
    return _Sizes(
        H=int(model["num_attention_heads"]), dn=int(model["qk_nope_head_dim"]),
        dr=int(model["qk_rope_head_dim"]), dv=int(model["v_head_dim"]), kr=int(model["kv_lora_rank"]),
        Hk=int(lin["num_heads"]), dk=int(lin["head_dim"]), taps=int(lin["short_conv_kernel_size"]),
        eps=float(model["rms_norm_eps"]), top_k=int(model["num_experts_per_token"]),
        scaling=float(model["routed_scaling_factor"]), normalise=bool(model["moe_renormalize"]),
        lo=int(lo), hi=int(hi),
    )


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# KDA: the recurrence, token by token


@partial(jax.jit, static_argnums=0)
def kda_inputs(z: Dict[str, Any], p, h):
    """h [T, D] float32 -> ``(q, k [T, Hk, dk], v [T, Hk, dk], g [T, Hk, dk],
    beta [T, Hk])``: the convolution over the sequence from zeros before
    position 0, the norms, the decay a channel, the step a head."""
    T, Hk, taps = h.shape[0], z["Hk"], z["taps"]
    with jax.default_matmul_precision("highest"):
        proj = h @ p["kda_wqkv"].astype(F32)
        f = (h @ p["kda_f_down"].astype(F32)) @ p["kda_f_up"].astype(F32)
        beta = jax.nn.sigmoid(h @ p["kda_wbeta"].astype(F32))
    padded = jnp.concatenate([jnp.zeros((taps - 1, proj.shape[1]), F32), proj])
    w = p["kda_conv"].astype(F32)
    mixed = jax.nn.silu(sum(padded[j : j + T] * w[j] for j in range(taps)))
    q, k, v = (a.reshape(T, Hk, -1) for a in jnp.split(mixed, 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q, k = unit(q) * z["dk"] ** -0.5, unit(k)
    g = -jnp.exp(p["kda_a_log"].astype(F32))[:, None] * jax.nn.softplus(
        f + p["kda_dt_bias"].astype(F32)
    ).reshape(T, Hk, -1)
    return q, k, v, g, beta


@jax.jit
def kda_recurrence(q, k, v, g, beta, at):
    """``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``
    from ``S_0 = 0``, ``o_t = S_t^T q_t``, one position at a time: ``(o [T,
    Hk, dv], S_at [Hk, dk, dv])``, the state after the first ``at`` positions
    (zeros for ``at`` 0)."""
    Hk, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(carry, x):
        S, kept = carry
        t, q, k, v, g, beta = x
        S = jnp.exp(g)[:, :, None] * S
        S = S + (beta[:, None] * k)[:, :, None] * (v - jnp.sum(S * k[:, :, None], axis=1))[:, None, :]
        return (S, jnp.where(t + 1 == at, S, kept)), jnp.sum(S * q[:, :, None], axis=1)

    zeros = jnp.zeros((Hk, dk, dv), F32)
    (_, kept), o = jax.lax.scan(step, (zeros, zeros), (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o, kept


@partial(jax.jit, static_argnums=0)
def kda_output(z: Dict[str, Any], p, h, o):
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.sigmoid((h @ p["kda_g_down"].astype(F32)) @ p["kda_g_up"].astype(F32))
        y = _rms(o, z["eps"]) * p["kda_o_norm"].astype(F32) * gate.reshape(o.shape)
        return y.reshape(h.shape[0], -1) @ p["kda_wo"].astype(F32)


@partial(jax.jit, static_argnums=0)
def kda_tail(z: Dict[str, Any], p, h, at):
    """What the convolution keeps of the first ``at`` positions: its last
    ``taps - 1`` inputs, rows ``at - taps + 1 .. at - 1`` of ``h W_qkv`` ``[taps
    - 1, 3 W]``, zeros before position 0."""
    keep = z["taps"] - 1
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([jnp.zeros((keep, h.shape[1]), F32), h]), at, keep
    )
    with jax.default_matmul_precision("highest"):
        return rows @ p["kda_wqkv"].astype(F32)


def kda_with_state(z: Dict[str, Any], p, h, at):
    """The KDA mixer on normed h [T, D] float32 over the whole sequence, and
    what a sequence that ended after ``at`` positions would leave in the
    layer: ``(out [T, D], S [Hk, dk, dv], tail [taps - 1, 3 W])``."""
    q, k, v, g, beta = kda_inputs(z, p, h)
    o, S = kda_recurrence(q, k, v, g, beta, at)
    return kda_output(z, p, h, o), S, kda_tail(z, p, h, at)


def kda(z: Dict[str, Any], p, h):
    """The KDA mixer on normed h [T, D] float32 over the whole sequence."""
    return kda_with_state(z, p, h, h.shape[0])[0]


# ---------------------------------------------------------------------------
# MLA, NoPE


@partial(jax.jit, static_argnums=0)
def _project(z: Dict[str, Any], p, h):
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("td,dhk->thk", h, p["w_q"].astype(F32))
        ckv = h @ p["w_kva"].astype(F32)
        c = _rms(ckv[:, : z["kr"]], z["eps"]) * p["kv_norm"].astype(F32)
        kv = jnp.einsum("tr,rhk->thk", c, p["w_kvb"].astype(F32))
    dn = z["dn"]
    return q[..., :dn], q[..., dn:], kv[..., :dn], ckv[:, z["kr"] :], kv[..., dn:]


@partial(jax.jit, static_argnames=("scale",))
def _attend(q_nope, q_shared, k_nope, k_shared, v, first, *, scale: float):
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("thk,shk->hts", q_nope, k_nope) + jnp.einsum("thk,sk->hts", q_shared, k_shared)
        q_pos = first + jnp.arange(q_nope.shape[0])
        seen = jnp.arange(k_nope.shape[0])[None, :] <= q_pos[:, None]
        s = jnp.where(seen[None], s * scale, -jnp.inf)
        return jnp.einsum("hts,shk->thk", jax.nn.softmax(s, axis=-1), v)


def attention(z: Dict[str, Any], p, h):
    """The attention mixer on normed h [T, D] float32, causal over T."""
    q_nope, q_shared, k_nope, k_shared, v = _project(
        z, {k: p[k] for k in ("w_q", "w_kva", "kv_norm", "w_kvb")}, h
    )
    out = []
    for first in range(0, h.shape[0], QUERY_CHUNK):
        cut = slice(first, first + QUERY_CHUNK)
        out.append(_attend(q_nope[cut], q_shared[cut], k_nope, k_shared, v, first,
                           scale=(z["dn"] + z["dr"]) ** -0.5))
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


# ---------------------------------------------------------------------------
# the FFNs


@partial(jax.jit, static_argnums=0)
def gates(z: Dict[str, Any], router, bias, h):
    """h [T, D] float32 -> ``(gates [T, E], margin [T])``: a token's gate for
    each of the ``top_k`` experts with the largest ``sigmoid(h W_r) + b``
    (``scaling * s_e / sum_kept s``, no bias in the gate), 0 for the others;
    ``margin``: by how much the last chosen beat the first left out."""
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
    """The FFN of an EXPERT layer on normed h [T, D]: ``(Shared(h) + sum over
    e kept and held of g_e Expert_e(h), margin [T])``. A Python loop over the
    held experts, one mask each."""
    g, margin = gates(z, p["router"], p["router_bias"], h)
    out = mlp(p["shared_gate"], p["shared_up"], p["shared_down"], h)
    for e in range(z["lo"], z["hi"]):
        i = e - z["lo"]
        out = out + g[:, e, None] * mlp(p["w_gate"][i], p["w_up"][i], p["w_down"][i], h)
    return out, margin


# ---------------------------------------------------------------------------
# the model


def layer(z: Dict[str, Any], p, x, at=None):
    """One layer on x [T, D] float32: which mixer and which FFN its weights
    say. Returns ``(x, state)``: ``state`` is what a sequence of ``at``
    positions leaves in a KDA layer (:func:`kda_with_state`: ``(S, tail)``),
    None for an attending layer."""
    h = _rms(x, z["eps"]) * p["attn_norm"].astype(F32)
    state = None
    if "kda_wqkv" in p:
        mix, *state = kda_with_state(z, p, h, x.shape[0] if at is None else at)
    else:
        mix = attention(z, p, h)
    x = x + mix
    h = _rms(x, z["eps"]) * p["mlp_norm"].astype(F32)
    if "router" in p:
        return x + expert_ffn(z, p, h)[0], state
    return x + mlp(p["w_gate"], p["w_up"], p["w_down"], h), state


def layers_of(params):
    """One layer's weights at a time, in the model's order."""
    yield from params["layers"]


def hidden_states(model: Dict[str, Any], params, tokens, lengths=None) -> List[Any]:
    """tokens [B, T] int32 -> per row the residual stream after the last
    layer, ``[T, D]`` float32 (what the final norm takes). With ``lengths``
    (one a row): ``(that, states)``, per row and KDA layer what a sequence of
    the row's first ``lengths[row]`` tokens leaves there, ``(S, tail)``
    (numpy)."""
    z = sizes(model)
    out, states = [], []
    for i, row in enumerate(np.asarray(tokens)):
        x = params["embed"][jnp.asarray(row)].astype(F32)
        kept = []
        for p in layers_of(params):
            x, state = layer(z, p, x, None if lengths is None else int(lengths[i]))
            if state is not None and lengths is not None:
                kept.append(tuple(np.asarray(a) for a in state))
        out.append(x)
        states.append(kept)
    return out if lengths is None else (out, states)


def head(model: Dict[str, Any], params, x):
    """Logits [..., V] float32 (numpy) of x [..., D], a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, float(model["rms_norm_eps"])) * params["final_norm"].astype(F32)
        V = params["lm_head"].shape[1]
        return np.concatenate([
            np.asarray(h @ params["lm_head"][:, v : v + VOCAB_CHUNK].astype(F32))
            for v in range(0, V, VOCAB_CHUNK)
        ], axis=-1)


def logits_at(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]], lengths=None):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]. With
    ``lengths``: ``(logits, states)`` (:func:`hidden_states`), from the same pass."""
    hidden = hidden_states(model, params, tokens, lengths)
    hidden, states = hidden if lengths is not None else (hidden, None)
    logits = head(model, params, jnp.stack([hidden[i][t] for i, t in picks]))
    return logits if lengths is None else (logits, states)


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
