"""The plain reference of the ``olmo_hybrid`` family: Gated DeltaNet layers and
multi-head attention layers without any position term, 3 : 1 in one residual
stream with the norm on each sublayer's OUTPUT, a dense gated MLP in every
layer, an untied head, in straightforward ``jax.numpy`` and float32 with
``highest`` matmul precision. No cache, no state pool, no kernel, no chunked
form, no chunk edge: the full forward pass over the whole sequence, the
convolution as ``K`` shifted copies of its input from zeros before position 0,
the recurrence ONE ``lax.scan`` step a position from a zero state, attention
over the sequence itself, one row of the batch and one layer's weights at a
time from the SAME (bf16) weights the system serves, positions a block at a
time (the recurrence's inputs and the scores of ``[heads, block, T]``) and the
head a slice of the vocabulary at a time, so that 16 layers at the published
widths fit beside a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]``, ``lm_head [D, V]``, ``final_norm [D]`` and
``layers``, one dict a layer in the model's order. Every layer: ``mixer_norm``,
``mlp_norm [D]`` (each on its sublayer's OUTPUT), ``w_gate`` / ``w_up [D, F]``,
``w_down [F, D]``. A Gated DeltaNet layer (it has ``gdn_wqkv``): ``gdn_wqkv [D,
2 H dk + H dv]`` (``W_q``, ``W_k``, ``W_v`` side by side), ``gdn_conv [K, 2 H dk
+ H dv]`` (tap ``K - 1`` multiplies the current position), ``gdn_wab [D, 2 H]``
(``W_a``, ``W_b`` side by side), ``gdn_a_log``, ``gdn_dt_bias [H]``, ``gdn_wg
[D, H dv]``, ``gdn_o_norm [dv]``, ``gdn_wo [H dv, D]``. An attending layer:
``wq [D, H, hd]``, ``wk`` / ``wv [D, KV, hd]``, ``q_norm [H hd]``, ``k_norm [KV
hd]``, ``wo [H, hd, D]``.

The layers (32 at the published sizes: ``layer_types`` = (3 x
``linear_attention``, 1 x ``full_attention``) x 8; ``x [T, 3840]`` a layer's
input; ``rms(y, w) = y rsqrt(mean(y^2) + rms_norm_eps) w``, ``rms_norm_eps``
1e-6; ``h = x + rms(Mixer(x))``, ``out = h + rms(MLP(h))``; a final norm before
the untied head)::

    gated deltanet   H = 30 heads, dk = 96, dv = 192, 4 taps
        q~, k~, v~ = SiLU(conv4(x W_q)), SiLU(conv4(x W_k)), SiLU(conv4(x W_v))
        a head:  q = l2norm(q~) 96^-1/2,  k = l2norm(k~),  v = v~
        beta = 2 sigmoid(x W_b)        (the 2 is linear_allow_neg_eigval: the transition
                                        I - beta k k^T may have an eigenvalue in (-1, 1))
        g = -exp(A_log) softplus(x W_a + dt_bias)          ONE number a head a position
        S_t = e^{g_t} S_{t-1};  S_t <- S_t + beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t
                                        S [96, 192] float32, S_{-1} = 0
        y = W_o [ RMSNorm_192(o_t) * SiLU(x W_g) ]
    attention        30 query and 30 KV heads of 128, NO rotary embedding
        q, k = rms(x W_q, w_q), rms(x W_k, w_k)   over the WHOLE projection of 3840 (QK-norm)
        causal softmax(q k^T 128^-1/2) v;  y = o W_o
    mlp              W_down(SiLU(W_gate h) * W_up h)       (3840 -> 11008 -> 3840)

``l2norm(y) = y rsqrt(sum(y^2) + 1e-6)``.

Departures from the published model, every one the configuration file's
``assumed``:

* the weights are SEEDED (no checkpoint is in the repository), read from the
  system as it holds them (bf16) and used in float32; ``A_log`` / ``dt_bias``
  come from the published family's law, not from training;
* the catalog's ``config`` has no key for the norm's place or for QK-norm: both
  are the OLMo 2 / OLMo 3 family's published convention;
* ``rope_parameters.rope_theta`` null is read as "the attending layers carry no
  positions";
* the state ``S`` is float32; every sum and product here is float32;
* ``W_q``, ``W_k``, ``W_v`` (and ``W_a``, ``W_b``) are stored side by side in
  one matrix: the same numbers.

The CONTROLS of the correctness limits (wrong models) are kept by the tests:
``tests/perfbench/olmo_hybrid_controls.py``."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: positions the recurrence and the attention take at a time, and columns of the head
POSITION_BLOCK = 512
VOCAB_CHUNK = 16384


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    return dict(
        H=int(model["linear_num_value_heads"]), dk=int(model["linear_key_head_dim"]),
        dv=int(model["linear_value_head_dim"]), K=int(model["linear_conv_kernel_dim"]),
        beta_max=2.0 if model["linear_allow_neg_eigval"] else 1.0, eps=float(model["rms_norm_eps"]),
    )


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


# ---------------------------------------------------------------------------
# the Gated DeltaNet mixer


@jax.jit
def project(p, x):
    """x [T, D] float32 -> the convolution's inputs ``[T, 2 H dk + H dv]``."""
    with jax.default_matmul_precision("highest"):
        return x @ p["gdn_wqkv"].astype(F32)


@jax.jit
def convolve(p, z):
    """``SiLU(sum_j taps[j] z_{t - (K - 1) + j})`` over the whole sequence, zeros before position 0."""
    w = p["gdn_conv"].astype(F32)
    K, T = w.shape[0], z.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), F32), z])
    return jax.nn.silu(sum(padded[j : j + T] * w[j] for j in range(K)))


def recurrence_inputs(z: Dict[str, Any], p, x, mixed):
    """``(q, k [T, H, dk], v [T, H, dv], g, beta [T, H])`` of a block of
    positions: x [T, D] the layer's input, ``mixed`` the convolved projections."""
    H, dk = z["H"], z["dk"]
    T = x.shape[0]
    q = _l2(mixed[:, : H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = _l2(mixed[:, H * dk : 2 * H * dk].reshape(T, H, dk))
    v = mixed[:, 2 * H * dk :].reshape(T, H, -1)
    with jax.default_matmul_precision("highest"):
        ab = x @ p["gdn_wab"].astype(F32)
    g = -jnp.exp(p["gdn_a_log"].astype(F32)) * jax.nn.softplus(ab[:, :H] + p["gdn_dt_bias"].astype(F32))
    return q, k, v, g, z["beta_max"] * jax.nn.sigmoid(ab[:, H:])


@jax.jit
def recur(S, q, k, v, g, beta):
    """``T`` positions of the recurrence from ``S [H, dk, dv]``: ``(S, o [T, H, dv])``."""

    def position(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, None, None] * S
        u = jnp.sum(S * k_t[:, :, None], axis=1)                      # S^T k  [H, dv]
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None, :]
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    return jax.lax.scan(position, S, (q, k, v, g, beta))


def recurrence(z: Dict[str, Any], p, x, mixed, cuts: Sequence[int]):
    """The recurrence over the whole sequence from a zero state, a block of
    positions at a time: ``(o [T, H, dv], {cut: S [H, dk, dv] after position
    cut - 1})`` for each of ``cuts`` (a block ends at each)."""
    T = x.shape[0]
    S = jnp.zeros((z["H"], z["dk"], z["dv"]), F32)
    edges = sorted({0, T, *(int(c) for c in cuts), *range(0, T, POSITION_BLOCK)})
    os, states = [], {}
    for lo, hi in zip(edges[:-1], edges[1:]):
        S, o = recur(S, *recurrence_inputs(z, p, x[lo:hi], mixed[lo:hi]))
        os.append(o)
        states[hi] = np.asarray(S)
    return jnp.concatenate(os), states


def gated_deltanet(z: Dict[str, Any], p, x, ats: Sequence[int] = ()):
    """The Gated DeltaNet mixer on a layer's input x [T, D] float32 over the
    whole sequence from a zero state. Returns ``(out [T, D], kept)``: for each
    ``at`` of ``ats`` what a sequence of the first ``at`` positions leaves in
    the layer, ``(S [H, dk, dv], tail [K - 1, 2 H dk + H dv])`` (numpy): the
    state after position ``at - 1`` and the convolution's last ``K - 1`` INPUTS,
    zeros before position 0."""
    T, keep = x.shape[0], z["K"] - 1
    proj = project(p, x)
    o, states = recurrence(z, p, x, convolve(p, proj), ats)
    with jax.default_matmul_precision("highest"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z["eps"]) * p["gdn_o_norm"].astype(F32)
        gate = jax.nn.silu(x @ p["gdn_wg"].astype(F32))
        out = (o.reshape(T, -1) * gate) @ p["gdn_wo"].astype(F32)
    padded = np.concatenate([np.zeros((keep, proj.shape[1]), np.float32), np.asarray(proj)])
    return out, [(states[int(a)], padded[int(a) : int(a) + keep]) for a in ats]


# ---------------------------------------------------------------------------
# multi-head attention without positions


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


def qkv(z: Dict[str, Any], p, x):
    """``(q [T, H, hd], k, v [T, KV, hd])`` of x [T, D]: three products, and the
    norm over the whole of q and of k."""
    with jax.default_matmul_precision("highest"):
        q, k, v = (jnp.einsum("td,dhk->thk", x, p[w].astype(F32)) for w in ("wq", "wk", "wv"))
    T = x.shape[0]
    q = _rms(q.reshape(T, -1), p["q_norm"], z["eps"]).reshape(q.shape)
    k = _rms(k.reshape(T, -1), p["k_norm"], z["eps"]).reshape(k.shape)
    return q, k, v


def attention(z: Dict[str, Any], p, x):
    """The attention mixer on a layer's input x [T, D] float32, causal over T; no position enters."""
    q, k, v = qkv(z, p, x)
    out = [_attend(q[first : first + POSITION_BLOCK], k, v, first)
           for first in range(0, x.shape[0], POSITION_BLOCK)]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


# ---------------------------------------------------------------------------
# the model


@jax.jit
def mlp(w_gate, w_up, w_down, h):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32)


def layer(z: Dict[str, Any], p, x, ats: Sequence[int] = ()):
    """One layer on x [T, D] float32: which mixer its weights say. Returns
    ``(x, kept)``: :func:`gated_deltanet`'s ``kept`` for a recurrent layer, None
    for an attending one."""
    kept = None
    if "gdn_wqkv" in p:
        mix, kept = gated_deltanet(z, p, x, ats)
    else:
        mix = attention(z, p, x)
    h = x + _rms(mix, p["mixer_norm"], z["eps"])
    return h + _rms(mlp(p["w_gate"], p["w_up"], p["w_down"], h), p["mlp_norm"], z["eps"]), kept


def layers_of(params):
    """One layer's weights at a time, in the model's order."""
    yield from params["layers"]


def hidden_states(model: Dict[str, Any], params, tokens, ats: Sequence[Sequence[int]] = None) -> List[Any]:
    """tokens [B, T] int32 -> per row the residual stream after the last
    layer, ``[T, D]`` float32 (what the final norm takes). With ``ats`` (a few
    lengths a row): ``(that, kept)``, per row and recurrent layer what a
    sequence of the row's first ``at`` tokens leaves there (:func:`gated_deltanet`)."""
    z = sizes(model)
    out, kept = [], []
    for i, row in enumerate(np.asarray(tokens)):
        x = params["embed"][jnp.asarray(row)].astype(F32)
        of_row = []
        for p in layers_of(params):
            x, left = layer(z, p, x, () if ats is None else ats[i])
            if left is not None:
                of_row.append(left)
        out.append(x)
        kept.append(of_row)
    return out if ats is None else (out, kept)


def head(model: Dict[str, Any], params, x):
    """Logits [..., V] float32 (numpy) of x [..., D] through the final norm and
    the untied head, a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"], float(model["rms_norm_eps"]))
        V = params["lm_head"].shape[1]
        return np.concatenate([
            np.asarray(h @ params["lm_head"][:, v : v + VOCAB_CHUNK].astype(F32))
            for v in range(0, V, VOCAB_CHUNK)
        ], axis=-1)


def logits_at(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]], ats=None):
    """Logits [len(picks), V] float32 (numpy) at the ``(row, position)``
    pairs ``picks`` of the full forward pass over tokens [B, T]. With
    ``ats``: ``(logits, kept)`` (:func:`hidden_states`), from the same pass."""
    hidden = hidden_states(model, params, tokens, ats)
    hidden, kept = hidden if ats is not None else (hidden, None)
    logits = head(model, params, jnp.stack([hidden[i][t] for i, t in picks]))
    return logits if ats is None else (logits, kept)


def next_token_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    """Mean negative log-likelihood of ``targets`` [B, T] over all positions."""
    total = 0.0
    targets = np.asarray(targets)
    for i, x in enumerate(hidden_states(model, params, tokens)):
        logp = jax.nn.log_softmax(jnp.asarray(head(model, params, x)), axis=-1)
        total += float(-jnp.sum(jnp.take_along_axis(logp, jnp.asarray(targets[i])[:, None], axis=-1)))
    return total / targets.size
