"""The CONTROLS of the ``xing4`` family's correctness limits: wrong models
that a comparison with the reference has to tell from the right one, and the
right one computed in float8 where the configuration states bfloat16. Each is
``perfbench/families/xing4/reference.py`` with ONE thing wrong, made by the
smallest of three means: a changed key of the configuration, a changed
weight (as a layer is cut out of the stacks), or (two of them) one function of the reference replaced for the
call by a plain (unjitted) one. The tests keep this file; nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
import copy
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.families.xing4 import reference

F32 = jnp.float32

VARIANTS = (
    "sinkhorn_1_round", "post_without_its_2", "bias_out_of_the_choice", "bias_in_the_gate",
    "gates_not_normalised", "scale_without_m2", "key_rope_unrotated", "shared_expert_0_times",
    "shared_expert_2_times", "weights_fp8",
)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype) if a.ndim >= 2 else a


_REAL_LAYERS_OF = reference.layers_of


def _layers_changed(change):
    """``reference.layers_of`` with ``change(name, weight)`` applied to a
    layer's weights as the layer is cut out of the stacks: one layer at a
    time, because a changed twin of all the weights does not fit beside a
    serving replica (the embedding and the head stay as they are)."""
    def layers_of(model, params):
        for p, moe in _REAL_LAYERS_OF(model, params):
            yield {k: change(k, v) for k, v in p.items()}, moe
    return layers_of


def _scaled(names, factor):
    return _layers_changed(lambda k, v: (v * factor).astype(v.dtype) if k in names else v)


def _gates_with_bias_in_the_gate(z, router, bias, h):
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(h @ router.astype(F32)) + bias.astype(F32)  # WRONG: the gate keeps the bias
    E, k = s.shape[-1], z["top_k"]
    best, chosen = jax.lax.top_k(s, min(k + 1, E))
    margin = best[:, k - 1] - best[:, k] if k < E else jnp.ones(s.shape[0], F32)
    kept = jnp.any(chosen[:, :k, None] == jnp.arange(E), axis=1)
    g = jnp.where(kept, s, 0.0)
    return z["scaling"] * g / g.sum(axis=-1, keepdims=True), margin


def _rope_that_leaves_the_key(x, inv_freq, attention_factor=1.0):
    # the ONE key's rope part is [T, dr]; a query's is [T, H, dr]
    return x if x.ndim == 2 else _REAL_ROPE(x, inv_freq, attention_factor)


_REAL_ROPE = reference.rope
_REAL_PROJECT = reference._project


def _project_with_the_key_unrotated(z, p, h):
    """``reference._project`` traced anew (its plain function, eagerly) with
    a ``rope`` that leaves the key: no compiled program of the process is touched."""
    reference.rope = _rope_that_leaves_the_key
    try:
        return _REAL_PROJECT.__wrapped__(z, p, h)
    finally:
        reference.rope = _REAL_ROPE


@contextlib.contextmanager
def wrong(model: Dict[str, Any], params, variant):
    """``(model, params)`` under which the reference computes ``variant``
    (None: the reference as it is)."""
    model = copy.deepcopy(model)
    patched = {}
    if variant is None:
        pass
    elif variant == "sinkhorn_1_round":
        model["hc_sinkhorn_iters"] = 1
    elif variant == "gates_not_normalised":
        model["norm_topk_prob"] = False
    elif variant == "scale_without_m2":
        model["rope_scaling"] = {**model["rope_scaling"], "mscale_all_dim": 0}
    elif variant == "post_without_its_2":  # H_post = sigmoid: every F's output halved
        patched["layers_of"] = _scaled(("wo", "w_down", "shared_down"), 0.5)
    elif variant == "bias_out_of_the_choice":
        patched["layers_of"] = _scaled(("router_bias",), 0.0)
    elif variant == "shared_expert_0_times":
        patched["layers_of"] = _scaled(("shared_down",), 0.0)
    elif variant == "shared_expert_2_times":
        patched["layers_of"] = _scaled(("shared_down",), 2.0)
    elif variant == "weights_fp8":  # the precision below bfloat16
        patched["layers_of"] = _layers_changed(lambda k, v: _fp8(v))
    elif variant == "bias_in_the_gate":
        patched["gates"] = _gates_with_bias_in_the_gate
    elif variant == "key_rope_unrotated":
        patched["_project"] = _project_with_the_key_unrotated
    else:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
    # both are looked up by the reference's unjitted callers at every call
    saved = {name: getattr(reference, name) for name in patched}
    for name, fn in patched.items():
        setattr(reference, name, fn)
    try:
        yield model, params
    finally:
        for name, fn in saved.items():
            setattr(reference, name, fn)


def logits_at(model, params, tokens, picks, variant=None):
    with wrong(model, params, variant) as (m, p):
        return reference.logits_at(m, p, tokens, picks)


def residual(model, layer_params, sub, norm, X, variant=None):
    """``reference.hyper`` of one sublayer around the identity under a control."""
    stacked = {"moe": {k: v[None] for k, v in layer_params.items()}}
    with wrong(model, stacked, variant) as (m, p):
        (layer, _), = reference.layers_of(m, p)
        return reference.hyper(reference.sizes(m), layer, sub, norm, X, lambda h: h)


def expert_ffn(model, layer_params, h, variant=None):
    """``reference.expert_ffn`` of one layer's weights under a control."""
    stacked = {"moe": {k: v[None] for k, v in layer_params.items()}}
    with wrong(model, stacked, variant) as (m, p):
        (layer, _), = reference.layers_of(m, p)  # through the control's view of a layer's weights
        return reference.expert_ffn(reference.sizes(m), layer, h)
