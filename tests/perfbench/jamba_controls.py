"""The CONTROLS of the ``jamba`` family's correctness limits: wrong models
that a comparison with the reference has to tell from the right one, and the
right one computed in float8 where the configuration states bfloat16. Each is
``perfbench/families/jamba/reference.py`` with ONE thing wrong: a changed
weight (as a layer is handed over) or one function of the reference replaced
for the call. The tests keep this file; nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.families.jamba import reference

F32 = jnp.float32

VARIANTS = (
    "weights_fp8", "state_bf16", "decay_bf16", "inner_norm_left_out", "conv_bias_left_out",
    "carry_dropped", "padded_row_advances", "rotary_added",
)


def _bf16(x):
    """``x`` rounded to bfloat16's 8 bits of mantissa, kept float32. NOT ``astype``
    there and back: inside a jitted fusion XLA:TPU takes such a round trip out
    (PR 52's first controls on the chip read the model's own numbers to the digit)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


def _layers_fp8(params):
    """A layer's matrices through float8 e4m3 as it is handed over: one layer
    at a time (the embedding, which is also the head, stays as it is)."""
    for p in params["layers"]:
        yield {k: _fp8(v) for k, v in p.items()}


_REAL_RECURRENCE = reference.recurrence
_REAL_PROJECT = reference.project


@jax.jit
def _recur_with_the_state_in_bf16(h, A, dt, Bm, Cm, x):
    def position(h, at):
        dt_t, x_t, b_t, c_t = at
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * x_t)[None, :] * b_t[:, None]
        h = _bf16(h)  # WRONG: the state kept in the model's dtype
        return h, jnp.sum(h * c_t[:, None], axis=0)

    return jax.lax.scan(position, h, (dt, x, Bm, Cm))


@jax.jit
def _recur_with_the_decay_in_bf16(h, A, dt, Bm, Cm, x):
    def position(h, at):
        dt_t, x_t, b_t, c_t = at
        decay = _bf16(jnp.exp(_bf16(dt_t[None, :] * A)))  # WRONG: exp(D A) in bfloat16
        h = decay * h + (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    return jax.lax.scan(position, h, (dt, x, Bm, Cm))


def _inputs_without_the_norm_of_b(z, p, x):
    R, N, eps = z["R"], z["N"], z["eps"]
    with jax.default_matmul_precision("highest"):
        low = x @ p["x_proj"].astype(F32)
        dt = reference._rms(low[:, :R], p["dt_norm"], eps)
        Bm = low[:, R : R + N]  # WRONG: one of the three inner norms left out
        Cm = reference._rms(low[:, R + N :], p["c_norm"], eps)
        dt = jax.nn.softplus(dt @ p["dt_proj"].astype(F32) + p["dt_bias"].astype(F32))
    return dt, Bm, Cm


@jax.jit
def _convolve_without_the_bias(p, x):
    w = p["conv_taps"].astype(F32)
    K, T = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(padded[j : j + T] * w[j] for j in range(K)))  # WRONG: no bias


def _recurrence_that_drops_the_carry_at(edges: Sequence[int]):
    """The recurrence as a program would compute it that starts every chunk
    from a zero state: behind each edge of ``edges`` (the chunks' starts) ``h``
    holds what the chunk alone left."""
    def recurrence(z, p, x, cuts):
        T = x.shape[0]
        bounds = sorted({0, T, *(e for e in edges if 0 < e < T)})
        ys, states = [], {}
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            inside = [c - lo for c in cuts if lo < c <= hi]
            y, kept = _REAL_RECURRENCE(z, p, x[lo:hi], inside)  # WRONG: from zeros at every edge
            ys.append(y)
            states.update({c + lo: h for c, h in kept.items()})
        return jnp.concatenate(ys), states
    return recurrence


def _recurrence_whose_padding_advances_at(edges: Dict[int, int]):
    """The recurrence as a program would compute it whose PADDED rows advance
    the state: at each edge of ``edges`` (the end of a padded chunk -> the rows
    of padding behind it) the state goes on through that many positions of the
    chunk's last real input before the next position (or the pool) sees it."""
    def recurrence(z, p, x, cuts):
        T = x.shape[0]
        A = -jnp.exp(p["A_log"].astype(F32))
        h = jnp.zeros(A.shape, F32)
        bounds = sorted({0, T, *(int(c) for c in cuts), *(e for e in edges if 0 < e <= T)})
        ys, states = [], {}
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            for a in range(lo, hi, reference.POSITION_BLOCK):
                b = min(hi, a + reference.POSITION_BLOCK)
                h, y = reference.recur(h, A, *reference.recurrence_inputs(z, p, x[a:b]), x[a:b])
                ys.append(y)
            if hi in edges:
                pad = jnp.broadcast_to(x[hi - 1], (min(edges[hi], reference.POSITION_BLOCK), x.shape[1]))
                h, _ = reference.recur(h, A, *reference.recurrence_inputs(z, p, pad), pad)  # WRONG
            states[hi] = np.asarray(h)
        return jnp.concatenate(ys), states
    return recurrence


def _project_with_a_rotary_term(p, u):
    q, k, v = _REAL_PROJECT(p, u)

    def rotate(x):  # WRONG: the model has no position term
        half = x.shape[-1] // 2
        ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * (10000.0 ** (-jnp.arange(half, dtype=F32) / half))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    return rotate(q), rotate(k), v


def chunk_edges(model: Dict[str, Any], length: int):
    """Where a prompt of ``length`` tokens, prefilled in chunks of the largest
    bucket, STARTS a chunk after its first: ``(starts, padded)``, ``padded``
    the end of its last chunk -> the rows of padding behind it in its bucket
    (none where the chunk fills one)."""
    buckets = sorted(model["serving"]["engine"]["prefill_buckets"])
    starts = tuple(range(buckets[-1], length, buckets[-1]))
    last = length - (starts[-1] if starts else 0)
    bucket = next(b for b in buckets if b >= last)
    return starts, ({length: bucket - last} if bucket > last else {})


@contextlib.contextmanager
def wrong(model: Dict[str, Any], variant, starts=(), padded=None):
    """The reference computing ``variant`` for the length of the block (None:
    the reference as it is). The replaced names are looked up by the
    reference's unjitted callers at every call. ``starts`` / ``padded``: for
    ``carry_dropped`` / ``padded_row_advances``, the sequence's chunk edges
    (:func:`chunk_edges`)."""
    del model
    patched: Dict[str, Any] = {}
    if variant is None:
        pass
    elif variant == "weights_fp8":  # the precision below bfloat16
        patched["layers_of"] = _layers_fp8
    elif variant == "state_bf16":
        patched["recur"] = _recur_with_the_state_in_bf16
    elif variant == "decay_bf16":
        patched["recur"] = _recur_with_the_decay_in_bf16
    elif variant == "inner_norm_left_out":
        patched["recurrence_inputs"] = _inputs_without_the_norm_of_b
    elif variant == "conv_bias_left_out":
        patched["convolve"] = _convolve_without_the_bias
    elif variant == "carry_dropped":
        patched["recurrence"] = _recurrence_that_drops_the_carry_at(tuple(starts))
    elif variant == "padded_row_advances":
        patched["recurrence"] = _recurrence_whose_padding_advances_at(dict(padded or {}))
    elif variant == "rotary_added":
        patched["project"] = _project_with_a_rotary_term
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
    edges of ``carry_dropped`` and ``padded_row_advances`` are a row's own (its
    prompt's length: ``ats[row][0]``, or the configuration's ``prompt_lens``)."""
    tokens = np.asarray(tokens)
    prompts = [a[0] for a in ats] if ats is not None else model["correctness"]["prompt_lens"]
    logits, kept = [None] * len(picks), []
    for i in range(tokens.shape[0]):
        mine = [n for n, (row, _) in enumerate(picks) if row == i]
        starts, padded = chunk_edges(model, int(prompts[i])) if i < len(prompts) else ((), {})
        with wrong(model, variant, starts, padded):
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


def mamba(model, layer_params, u, variant=None, starts=(), padded=None):
    """``reference.mamba`` of one layer's weights under a control -> ``[T, D]``."""
    with wrong(model, variant, starts, padded):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.mamba(reference.sizes(model), p, u)[0]


def attention(model, layer_params, u, variant=None):
    """``reference.attention`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.attention(reference.sizes(model), p, u)
