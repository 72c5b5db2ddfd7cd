"""The CONTROLS of the ``lfm2`` family's correctness limits: wrong models
that a comparison with the reference has to tell from the right one, and the
right one computed in float8 where the configuration states bfloat16. Each is
``perfbench/families/lfm2/reference.py`` with ONE thing wrong: a changed
weight (as a layer is handed over) or one function of the reference replaced
for the call. The tests keep this file; nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.families.lfm2 import reference

F32 = jnp.float32

VARIANTS = (
    "weights_fp8", "oldest_tap_dropped", "tail_cut_at_padded_end", "qk_norm_whole_projection",
    "gate_keeps_bias",
)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


def _layers_fp8(params):
    """A layer's matrices through float8 e4m3 as it is handed over: one layer
    at a time, because a changed twin of all the weights does not fit beside
    a serving replica (the embedding, which is also the head, stays as it is)."""
    for p in params["layers"]:
        yield {k: _fp8(v) for k, v in p.items()}


_REAL_MIX = reference.conv_mix
_REAL_TAIL = reference.conv_tail


def _mix_without_the_oldest_tap(p, z, gate):
    taps = p["conv_taps"].astype(F32)
    return _REAL_MIX({**p, "conv_taps": taps.at[0].set(0.0)}, z, gate)  # WRONG: two taps of three


def _mix_with_the_tail_lost_at(edges):
    """The convolution as a program would compute it that cuts a chunk's
    tail from the END of its padded bucket instead of behind its last real
    input: behind each edge of ``edges`` (the ends of the padded chunks) the
    next positions see what the padding left (zeros stand for it) where the
    sequence's own last inputs should be."""
    def mix(p, z, gate):
        w = p["conv_taps"].astype(F32)
        K, T = w.shape[0], z.shape[0]
        padded = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), F32), z])
        t = jnp.arange(T)[:, None]
        c = 0.0
        for j in range(K):
            src = t - (K - 1) + j  # the input position tap j reads for output t
            lost = jnp.zeros_like(src, bool)
            for edge in edges:
                lost |= (src < edge) & (t >= edge)  # WRONG: across the edge nothing is carried
            c = c + jnp.where(lost, 0.0, padded[j : j + T]) * w[j]
        with jax.default_matmul_precision("highest"):
            return (gate * c) @ p["conv_out"].astype(F32)
    return mix


def _tail_lost_at(edges):
    def tail(z, at, keep):
        return np.zeros((keep, np.asarray(z).shape[1]), np.float32) if at in edges else _REAL_TAIL(z, at, keep)
    return tail


def _norm_over_the_projection(z, x, w):
    """q or k normalised over its WHOLE projection (all heads together, as
    ``models/llama.py``'s ``qk_norm``) instead of a head."""
    T, heads, _ = x.shape
    return (reference._rms(x.reshape(T, -1), z["eps"]) * jnp.tile(w.astype(F32), heads)).reshape(x.shape)  # WRONG


def _gates_that_keep_the_bias(z, router, bias, f):
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(f @ router.astype(F32)) + bias.astype(F32)  # WRONG: the bias stays in the gate
    E, k = s.shape[-1], z["top_k"]
    best, chosen = jax.lax.top_k(s, min(k + 1, E))
    margin = best[:, k - 1] - best[:, k] if k < E else jnp.ones(s.shape[0], F32)
    kept = jnp.any(chosen[:, :k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, s, 0.0)
    return z["scaling"] * g / (g.sum(axis=-1, keepdims=True) + 1e-6), margin


def padded_edges(model: Dict[str, Any], length: int):
    """Where a prompt of ``length`` tokens, prefilled in chunks of the largest
    bucket, ends a chunk that is PADDED to its bucket: its own end, unless the
    last chunk fills a bucket exactly."""
    buckets = sorted(model["serving"]["engine"]["prefill_buckets"])
    last = length % buckets[-1] or buckets[-1]
    return () if last in buckets else (length,)


@contextlib.contextmanager
def wrong(model: Dict[str, Any], variant, edges=()):
    """The reference computing ``variant`` for the length of the block (None:
    the reference as it is). The replaced names are looked up by the
    reference's unjitted callers at every call. ``edges``: for
    ``tail_cut_at_padded_end``, the ends of the sequence's padded chunks."""
    patched: Dict[str, Any] = {}
    if variant is None:
        pass
    elif variant == "weights_fp8":  # the precision below bfloat16
        patched["layers_of"] = _layers_fp8
    elif variant == "oldest_tap_dropped":
        patched["conv_mix"] = _mix_without_the_oldest_tap
    elif variant == "tail_cut_at_padded_end":
        patched["conv_mix"] = _mix_with_the_tail_lost_at(tuple(edges))
        patched["conv_tail"] = _tail_lost_at(tuple(edges))
    elif variant == "qk_norm_whole_projection":
        patched["_head_norm"] = _norm_over_the_projection
    elif variant == "gate_keeps_bias":
        patched["gates"] = _gates_that_keep_the_bias
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
    edges of ``tail_cut_at_padded_end`` are a row's own (its prompt's length:
    ``ats[row][0]``, or the configuration's ``prompt_lens``)."""
    tokens = np.asarray(tokens)
    prompts = [a[0] for a in ats] if ats is not None else model["correctness"]["prompt_lens"]
    logits, tails = [None] * len(picks), []
    for i in range(tokens.shape[0]):
        mine = [n for n, (row, _) in enumerate(picks) if row == i]
        with wrong(model, variant, padded_edges(model, int(prompts[i])) if i < len(prompts) else ()):
            got = reference.logits_at(
                model, params, tokens[i : i + 1], [(0, picks[n][1]) for n in mine],
                None if ats is None else [ats[i]],
            )
        got, tail = got if ats is not None else (got, [None])
        for n, row in zip(mine, got):
            logits[n] = row
        tails.append(tail[0])
    logits = np.stack(logits)
    return logits if ats is None else (logits, tails)


def conv(model, layer_params, u, variant=None, edges=()):
    """``reference.conv`` of one layer's weights under a control."""
    with wrong(model, variant, edges):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.conv_mix(p, *reference.conv_inputs(p, u))


def attention(model, layer_params, u, variant=None):
    """``reference.attention`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.attention(reference.sizes(model), p, u)


def expert_ffn(model, layer_params, f, variant=None):
    """``reference.expert_ffn`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.expert_ffn(reference.sizes(model), p, f)
