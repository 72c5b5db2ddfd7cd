"""The CONTROLS of the ``olmo_hybrid`` family's correctness limits: wrong models
that a comparison with the reference has to tell from the right one, and the
right one computed in float8 where the configuration states bfloat16. Each is
``perfbench/families/olmo_hybrid/reference.py`` with ONE thing wrong: a changed
weight (as a layer is handed over) or one function of the reference replaced
for the call. The tests keep this file; nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.families.olmo_hybrid import reference

F32 = jnp.float32

VARIANTS = (
    "weights_fp8", "state_bf16", "beta_without_its_factor", "gate_mean_over_heads", "carry_dropped",
    "k_norm_left_out",
)


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits of mantissa, kept float32. NOT ``astype``
    there and back: inside a jitted fusion XLA:TPU takes such a round trip out
    (``jamba_controls.py``)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


def _layers_fp8(params):
    """A layer's matrices through float8 e4m3 as it is handed over: one layer
    at a time (the embedding and the head stay as they are)."""
    for p in params["layers"]:
        yield {k: _fp8(v) for k, v in p.items()}


_REAL_RECURRENCE = reference.recurrence
_REAL_INPUTS = reference.recurrence_inputs
_REAL_QKV = reference.qkv


@jax.jit
def _recur_with_the_state_in_bf16(S, q, k, v, g, beta):
    def position(S, at):
        q_t, k_t, v_t, g_t, b_t = at
        S = jnp.exp(g_t)[:, None, None] * S
        u = jnp.sum(S * k_t[:, :, None], axis=1)
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None, :]
        S = _bf16(S)  # WRONG: the state kept in the model's dtype
        return S, jnp.sum(S * q_t[:, :, None], axis=1)

    return jax.lax.scan(position, S, (q, k, v, g, beta))


def _inputs_with_beta_in_0_1(z, p, x, mixed):
    q, k, v, g, beta = _REAL_INPUTS(z, p, x, mixed)
    return q, k, v, g, beta / z["beta_max"]  # WRONG: linear_allow_neg_eigval ignored


def _inputs_with_one_gate_for_all_heads(z, p, x, mixed):
    q, k, v, g, beta = _REAL_INPUTS(z, p, x, mixed)
    return q, k, v, jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape), beta  # WRONG: the mean over heads


def _recurrence_that_drops_the_carry_at(edges: Sequence[int]):
    """The recurrence as a program would compute it that starts every chunk
    from a zero state: behind each edge of ``edges`` (the chunks' starts) ``S``
    holds what the chunk alone left."""
    def recurrence(z, p, x, mixed, cuts):
        T = x.shape[0]
        bounds = sorted({0, T, *(e for e in edges if 0 < e < T)})
        os, states = [], {}
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            inside = [c - lo for c in cuts if lo < c <= hi]
            o, kept = _REAL_RECURRENCE(z, p, x[lo:hi], mixed[lo:hi], inside)  # WRONG: from zeros at every edge
            os.append(o)
            states.update({c + lo: S for c, S in kept.items()})
        return jnp.concatenate(os), states
    return recurrence


def _qkv_without_the_norm_of_k(z, p, x):
    q, _, v = _REAL_QKV(z, p, x)
    with jax.default_matmul_precision("highest"):
        k = jnp.einsum("td,dhk->thk", x, p["wk"].astype(F32))  # WRONG: QK-norm's k left as projected
    return q, k, v


def chunk_starts(model: Dict[str, Any], length: int):
    """Where a prompt of ``length`` tokens, prefilled in chunks of the largest
    bucket, STARTS a chunk after its first."""
    largest = max(model["serving"]["engine"]["prefill_buckets"])
    return tuple(range(largest, length, largest))


@contextlib.contextmanager
def wrong(model: Dict[str, Any], variant, starts=()):
    """The reference computing ``variant`` for the length of the block (None:
    the reference as it is). The replaced names are looked up by the
    reference's unjitted callers at every call. ``starts``: for
    ``carry_dropped``, the sequence's chunk edges (:func:`chunk_starts`)."""
    del model
    patched: Dict[str, Any] = {}
    if variant is None:
        pass
    elif variant == "weights_fp8":  # the precision below bfloat16
        patched["layers_of"] = _layers_fp8
    elif variant == "state_bf16":
        patched["recur"] = _recur_with_the_state_in_bf16
    elif variant == "beta_without_its_factor":
        patched["recurrence_inputs"] = _inputs_with_beta_in_0_1
    elif variant == "gate_mean_over_heads":
        patched["recurrence_inputs"] = _inputs_with_one_gate_for_all_heads
    elif variant == "carry_dropped":
        patched["recurrence"] = _recurrence_that_drops_the_carry_at(tuple(starts))
    elif variant == "k_norm_left_out":
        patched["qkv"] = _qkv_without_the_norm_of_k
    else:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
    saved = {name: getattr(reference, name) for name in patched}
    for name, fn in patched.items():
        setattr(reference, name, fn)
    try:
        yield
    finally:
        for name, fn in saved.items():
            setattr(reference, name, fn)


def logits_at(model, params, tokens, picks, variant=None, ats=None):
    """``reference.logits_at`` under a control; a row at a time, because the
    edges of ``carry_dropped`` are a row's own (its prompt's length:
    ``ats[row][0]``, or the configuration's ``prompt_lens``)."""
    tokens = np.asarray(tokens)
    prompts = [a[0] for a in ats] if ats is not None else model["correctness"]["prompt_lens"]
    logits, kept = [None] * len(picks), []
    for i in range(tokens.shape[0]):
        mine = [n for n, (row, _) in enumerate(picks) if row == i]
        starts = chunk_starts(model, int(prompts[i])) if i < len(prompts) else ()
        with wrong(model, variant, starts):
            got = reference.logits_at(
                model, params, tokens[i : i + 1], [(0, picks[n][1]) for n in mine],
                None if ats is None else [ats[i]],
            )
        got, left = got if ats is not None else (got, [None])
        for n, row in zip(mine, got):
            logits[n] = row
        kept.append(left[0])
    logits = np.stack(logits)
    return logits if ats is None else (logits, kept)


def gdn(model, layer_params, x, variant=None, starts=()):
    """``reference.gated_deltanet`` of one layer's weights under a control -> ``[T, D]``."""
    with wrong(model, variant, starts):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.gated_deltanet(reference.sizes(model), p, x)[0]


def attention(model, layer_params, x, variant=None):
    """``reference.attention`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.attention(reference.sizes(model), p, x)
