"""The CONTROLS of the ``kimi_linear`` family's correctness limits: wrong
models that a comparison with the reference has to tell from the right one,
and the right one computed in float8 where the configuration states
bfloat16. Each is ``perfbench/families/kimi_linear/reference.py`` with ONE
thing wrong: a changed weight (as a layer is handed over) or one function of
the reference replaced for the call. The tests keep this file; nothing under
``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.families.kimi_linear import reference

F32 = jnp.float32

VARIANTS = (
    "state_bf16", "decay_left_out", "state_dropped_at_chunk_edge", "shared_key_rotated", "weights_fp8",
)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


def _layers_fp8(params):
    """A layer's matrices through float8 e4m3 as it is handed over: one layer
    at a time, because a changed twin of all the weights does not fit beside
    a serving replica (the embedding and the head stay as they are)."""
    for p in params["layers"]:
        yield {k: _fp8(v) for k, v in p.items()}


def _recurrence(keep=lambda S: S, reset_at=None):
    """``reference.kda_recurrence`` with the state passed through ``keep``
    after every position, and forgotten before position ``reset_at``."""
    def run(q, k, v, g, beta, at):
        Hk, dk, dv = q.shape[1], q.shape[2], v.shape[2]

        def step(carry, x):
            S, kept = carry
            t, q, k, v, g, beta = x
            if reset_at is not None:
                S = jnp.where(t == reset_at, 0.0, S)
            S = jnp.exp(g)[:, :, None] * S
            S = S + (beta[:, None] * k)[:, :, None] * (v - jnp.sum(S * k[:, :, None], axis=1))[:, None, :]
            o, S = jnp.sum(S * q[:, :, None], axis=1), keep(S)
            return (S, jnp.where(t + 1 == at, S, kept)), o

        zeros = jnp.zeros((Hk, dk, dv), F32)
        (_, kept), o = jax.lax.scan(step, (zeros, zeros), (jnp.arange(q.shape[0]), q, k, v, g, beta))
        return o, kept

    return jax.jit(run)


_REAL_INPUTS = reference.kda_inputs
_REAL_PROJECT = reference._project


def _inputs_without_the_decay(z, p, h):
    q, k, v, g, beta = _REAL_INPUTS(z, p, h)
    return q, k, v, jnp.zeros_like(g), beta  # WRONG: alpha = 1, the state never forgets


def _rope(x, theta: float):
    """x [T, ..., dr] rotated at positions 0..T-1, (even, odd) neighbours a pair."""
    dr = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)
    ang = (jnp.arange(x.shape[0], dtype=F32)[:, None] * inv).reshape(x.shape[0], *([1] * (x.ndim - 2)), -1)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                     axis=-1).reshape(x.shape)


def _project_rotated(theta: float):
    def project(z, p, h):
        q_nope, q_shared, k_nope, k_shared, v = _REAL_PROJECT(z, p, h)
        return q_nope, _rope(q_shared, theta), k_nope, _rope(k_shared, theta), v  # WRONG: the model is NoPE
    return project


@contextlib.contextmanager
def wrong(model: Dict[str, Any], variant):
    """The reference computing ``variant`` for the length of the block (None:
    the reference as it is). The replaced names are looked up by the
    reference's unjitted callers at every call."""
    patched: Dict[str, Any] = {}
    if variant is None:
        pass
    elif variant == "state_bf16":
        # ``reduce_precision``, not a convert there and back: the TPU compiler
        # elides the pair (excess precision allowed) and the control read as the model
        patched["kda_recurrence"] = _recurrence(keep=lambda S: jax.lax.reduce_precision(S, 8, 7))
    elif variant == "state_dropped_at_chunk_edge":
        patched["kda_recurrence"] = _recurrence(reset_at=int(model["serving"]["engine"]["prefill_buckets"][-1]))
    elif variant == "decay_left_out":
        patched["kda_inputs"] = _inputs_without_the_decay
    elif variant == "shared_key_rotated":
        patched["_project"] = _project_rotated(float(model["rope_theta"]))
    elif variant == "weights_fp8":  # the precision below bfloat16
        patched["layers_of"] = _layers_fp8
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


def logits_at(model, params, tokens, picks, variant=None, lengths=None):
    with wrong(model, variant):
        return reference.logits_at(model, params, tokens, picks, lengths)


def kda(model, layer_params, h, variant=None):
    """``reference.kda`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.kda(reference.sizes(model), p, h)


def attention(model, layer_params, h, variant=None):
    """``reference.attention`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.attention(reference.sizes(model), p, h)


def expert_ffn(model, layer_params, h, variant=None):
    """``reference.expert_ffn`` of one layer's weights under a control."""
    with wrong(model, variant):
        (p,) = reference.layers_of({"layers": [layer_params]})
        return reference.expert_ffn(reference.sizes(model), p, h)
