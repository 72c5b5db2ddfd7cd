"""The plain reference of the ``jamba`` family: Mamba-1 selective state-space
layers and a few multi-query attention layers without any position term in
one pre-norm residual stream, a dense gated MLP in every layer, the embedding
read again as the head, in straightforward ``jax.numpy`` and float32 with
``highest`` matmul precision. No cache, no state pool, no kernel, no chunk
edge: the full forward pass over the whole sequence, the convolution as ``K``
shifted copies of its input from zeros before position 0, the recurrence one
``lax.scan`` step a position from a zero state, attention over the sequence
itself, one row of the batch and one layer's weights at a time from the SAME
(bf16) weights the system serves, positions a block at a time (the recurrence's
inputs of ``[block, Di]`` and the scores of ``[heads, block, T]``) and the head
a slice of the vocabulary at a time so that it fits beside a serving replica.

It reads the system's parameter layout as data and imports nothing of the
program: ``embed [V, D]`` (also the head), ``final_norm [D]`` and ``layers``,
one dict a layer in the model's order. Every layer: ``mixer_norm``,
``ffn_norm``, ``w_gate`` / ``w_up [D, F]``, ``w_down [F, D]``. A Mamba layer
(it has ``in_proj``): ``in_proj [D, 2 Di]`` (x, z side by side), ``conv_taps
[K, Di]`` (tap ``K - 1`` multiplies the current position), ``conv_bias [Di]``,
``x_proj [Di, R + 2 N]`` (dt, B, C side by side), ``dt_norm [R]``, ``b_norm``
/ ``c_norm [N]``, ``dt_proj [R, Di]``, ``dt_bias [Di]``, ``A_log [N, Di]``
(the state index FIRST: the published layout is ``[Di, N]``), ``D [Di]``,
``out_proj [Di, D]``. An attending layer: ``wq [D, H, hd]``, ``wk`` / ``wv
[D, KV, hd]``, ``wo [H, hd, D]``.

The layers (28 at the published sizes, layer ``l`` attends iff ``l % 14 ==
7``; ``u`` is a sublayer's input after its RMS norm, ``rms(x, w) = x
rsqrt(mean(x^2) + rms_norm_eps) w``, ``rms_norm_eps`` 1e-6; ``h <- h +
mix(rms(h))``, ``h <- h + mlp(rms(h))``; final norm, tied head)::

    mamba       [x | z] = u W_in                                  (2560 -> 2 x 5120)
                x_t = silu(sum_{j<4} taps[j] x_{t-3+j} + b_conv)
                [dt | B | C] = x_t W_x                            (5120 -> 160 + 16 + 16)
                dt, B, C = rms(dt) w_dt, rms(B) w_b, rms(C) w_c
                D_t = softplus(dt W_dt + b_dt)                    (160 -> 5120)
                h_t = exp(D_t A) h_{t-1} + (D_t x_t) B_t^T        A = -exp(A_log), h_{-1} = 0
                y_t = h_t^T C_t + Dskip x_t;  out = (y_t silu(z_t)) W_out
    attention   q, k, v = u Wq, u Wk, u Wv   (20 x 128 | 1 x 128 | 1 x 128), NO position term
                causal softmax(q k^T / sqrt(128)) v;  out = o Wo
    mlp         (silu(u W_gate) * (u W_up)) W_down                (2560 -> 8192 -> 2560)

Departures from the published model:

* the weights are SEEDED (no checkpoint is in the repository), read from the
  system as it holds them (bf16) and used in float32;
* the state ``h`` is float32 (the published code keeps it in the model's
  dtype); every sum and product here is float32;
* nothing else: the published layer order (``attn_layer_period`` /
  ``attn_layer_offset``), widths, the three inner norms, the convolution's
  bias, the absence of any position term and the tied head are as ``config``
  and the public ``jamba`` modelling code give them.

The CONTROLS of the correctness limits (wrong models) are kept by the tests:
``tests/perfbench/jamba_controls.py``."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: positions the recurrence and the attention take at a time, and rows of the tied head
POSITION_BLOCK = 512
VOCAB_CHUNK = 16384


def sizes(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the equations need of the configuration file, under short names."""
    return dict(
        N=int(model["mamba_d_state"]), K=int(model["mamba_d_conv"]), R=int(model["mamba_dt_rank"]),
        eps=float(model["rms_norm_eps"]),
    )


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


# ---------------------------------------------------------------------------
# the selective state-space mixer


@jax.jit
def ssm_inputs(p, u):
    """u [T, D] float32 -> ``(x, z) [T, Di]``: the convolution's input and the gate."""
    with jax.default_matmul_precision("highest"):
        x, z = jnp.split(u @ p["in_proj"].astype(F32), 2, axis=-1)
    return x, z


@jax.jit
def convolve(p, x):
    """``silu(sum_j taps[j] x_{t - (K - 1) + j} + b)`` over the whole sequence, zeros before position 0."""
    w = p["conv_taps"].astype(F32)
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(padded[j : j + T] * w[j] for j in range(K)) + p["conv_bias"].astype(F32))


def recurrence_inputs(z: Dict[str, Any], p, x):
    """The convolved x [T, Di] -> ``(dt [T, Di], B [T, N], C [T, N])``."""
    R, N, eps = z["R"], z["N"], z["eps"]
    with jax.default_matmul_precision("highest"):
        low = x @ p["x_proj"].astype(F32)
        dt = _rms(low[:, :R], p["dt_norm"], eps)
        Bm = _rms(low[:, R : R + N], p["b_norm"], eps)
        Cm = _rms(low[:, R + N :], p["c_norm"], eps)
        dt = jax.nn.softplus(dt @ p["dt_proj"].astype(F32) + p["dt_bias"].astype(F32))
    return dt, Bm, Cm


@jax.jit
def recur(h, A, dt, Bm, Cm, x):
    """``T`` positions of the recurrence from ``h [N, Di]``: ``(h, y [T, Di])``."""

    def position(h, at):
        dt_t, x_t, b_t, c_t = at
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    return jax.lax.scan(position, h, (dt, x, Bm, Cm))


def recurrence(z: Dict[str, Any], p, x, cuts: Sequence[int]):
    """The recurrence over the convolved x [T, Di] from a zero state, a block
    of positions at a time: ``(y [T, Di], {cut: h [N, Di] after position cut
    - 1})`` for each of ``cuts`` (a block ends at each)."""
    T = x.shape[0]
    A = -jnp.exp(p["A_log"].astype(F32))
    h = jnp.zeros(A.shape, F32)
    edges = sorted({0, T, *(int(c) for c in cuts), *range(0, T, POSITION_BLOCK)})
    ys, states = [], {}
    for lo, hi in zip(edges[:-1], edges[1:]):
        h, y = recur(h, A, *recurrence_inputs(z, p, x[lo:hi]), x[lo:hi])
        ys.append(y)
        states[hi] = np.asarray(h)
    return jnp.concatenate(ys), states


def mamba(z: Dict[str, Any], p, u, ats: Sequence[int] = ()):
    """The Mamba mixer on normed u [T, D] float32 over the whole sequence from
    a zero state. Returns ``(out [T, D], kept)``: for each ``at`` of ``ats``
    what a sequence of the first ``at`` positions leaves in the layer,
    ``(h [N, Di], tail [K - 1, Di])`` (numpy): the state after position ``at -
    1`` and the convolution's last ``K - 1`` INPUTS, zeros before position 0."""
    keep = z["K"] - 1
    x_in, gate = ssm_inputs(p, u)
    x = convolve(p, x_in)
    y, states = recurrence(z, p, x, ats)
    with jax.default_matmul_precision("highest"):
        out = ((y + p["D"].astype(F32) * x) * jax.nn.silu(gate)) @ p["out_proj"].astype(F32)
    padded = np.concatenate([np.zeros((keep, x_in.shape[1]), np.float32), np.asarray(x_in)])
    return out, [(states[int(a)], padded[int(a) : int(a) + keep]) for a in ats]


# ---------------------------------------------------------------------------
# multi-query attention without positions


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


def project(p, u):
    """``(q [T, H, hd], k, v [T, KV, hd])`` of normed u [T, D]: three products and nothing else."""
    with jax.default_matmul_precision("highest"):
        return tuple(jnp.einsum("td,dhk->thk", u, p[w].astype(F32)) for w in ("wq", "wk", "wv"))


def attention(z: Dict[str, Any], p, u):
    """The attention mixer on normed u [T, D] float32, causal over T; no position enters."""
    del z
    q, k, v = project(p, u)
    out = [_attend(q[first : first + POSITION_BLOCK], k, v, first)
           for first in range(0, u.shape[0], POSITION_BLOCK)]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("thk,hkd->td", jnp.concatenate(out), p["wo"].astype(F32))


# ---------------------------------------------------------------------------
# the model


@jax.jit
def mlp(w_gate, w_up, w_down, f):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(f @ w_gate.astype(F32)) * (f @ w_up.astype(F32))) @ w_down.astype(F32)


def layer(z: Dict[str, Any], p, x, ats: Sequence[int] = ()):
    """One layer on x [T, D] float32: which mixer its weights say. Returns
    ``(x, kept)``: :func:`mamba`'s ``kept`` for a Mamba layer, None for an
    attending layer."""
    u = _rms(x, p["mixer_norm"], z["eps"])
    kept = None
    if "in_proj" in p:
        mix, kept = mamba(z, p, u, ats)
    else:
        mix = attention(z, p, u)
    x = x + mix
    return x + mlp(p["w_gate"], p["w_up"], p["w_down"], _rms(x, p["ffn_norm"], z["eps"])), kept


def layers_of(params):
    """One layer's weights at a time, in the model's order."""
    yield from params["layers"]


def hidden_states(model: Dict[str, Any], params, tokens, ats: Sequence[Sequence[int]] = None) -> List[Any]:
    """tokens [B, T] int32 -> per row the residual stream after the last
    layer, ``[T, D]`` float32 (what the final norm takes). With ``ats`` (a few
    lengths a row): ``(that, kept)``, per row and Mamba layer what a sequence of
    the row's first ``at`` tokens leaves there (:func:`mamba`)."""
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
    """Logits [..., V] float32 (numpy) of x [..., D] through the TIED head, a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        h = _rms(x, params["final_norm"], float(model["rms_norm_eps"]))
        V = params["embed"].shape[0]
        return np.concatenate([
            np.asarray(h @ params["embed"][v : v + VOCAB_CHUNK].astype(F32).T)
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
