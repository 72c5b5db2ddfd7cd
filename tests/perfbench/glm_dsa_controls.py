"""The CONTROLS of the ``glm_moe_dsa`` family's correctness limits: wrong models
that a comparison with the reference has to tell from the right one, and the
right one computed in float8 where the configuration states bfloat16. Each is
``perfbench/families/glm_moe_dsa/reference.py`` with ONE thing wrong, made by a
changed function of the selection's seam (``index_scores``, ``select``,
``selection``) or a changed weight (as a layer is cut out of the stacks). The
tests keep this file; nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from perfbench.families.glm_moe_dsa import reference

VARIANTS = (
    "weights_fp8", "selection_left_out", "indexer_without_relu", "indexer_without_weights",
    "selection_a_block_late",
)
#: positions the late selection lags by: one block of the benchmark's cache
LATE = 16


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


class _Changed(reference._Cut):
    """A cut of a stacked weight whose every piece is changed as it is cut."""

    def __init__(self, stacked, layer, change):
        super().__init__(stacked, layer)
        self.change = change

    def __getitem__(self, idx):
        return self.change(super().__getitem__(idx))


_REAL = {name: getattr(reference, name) for name in ("cut_layer", "index_scores", "select", "selection")}


def _layers_changed(change):
    """``reference.cut_layer`` with ``change(name, weight)`` applied to a
    layer's weights as they are cut out of the stacks, an expert at a time."""
    def cut_layer(stacked, i):
        out = {}
        for k, v in _REAL["cut_layer"](stacked, i).items():
            if isinstance(v, reference._Cut):
                out[k] = _Changed(v.stacked, v.layer, lambda w, k=k: change(k, w))
            else:
                out[k] = change(k, v)
        return out
    return cut_layer


def _scores(relu: bool, weights: bool):
    @jax.jit
    def index_scores(q_i, k_i, w_i, first):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("thk,sk->ths", q_i, k_i)
            s = jax.nn.relu(s) if relu else s
            scores = jnp.sum(s * (w_i[:, :, None] if weights else 1.0), axis=1)
        q_pos = first + jnp.arange(q_i.shape[0])
        return jnp.where(jnp.arange(k_i.shape[0])[None, :] <= q_pos[:, None], scores, -jnp.inf)
    return index_scores


def _late(z, index, first, count):
    """The top ``index_topk`` taken a block late: among the positions ``s <=
    max(t - LATE, 0)`` (position 0 stays: a softmax over nothing is no model)."""
    chosen, scores = _REAL["selection"](z, index, first, count)
    q_pos = first + jnp.arange(count)
    early = jnp.arange(scores.shape[1])[None, :] <= jnp.maximum(q_pos - LATE, 0)[:, None]
    scores = jnp.where(early, scores, -jnp.inf)
    return reference.select(scores, topk=z["topk"]), scores


@contextlib.contextmanager
def wrong(model: Dict[str, Any], params, variant):
    """``(model, params)`` under which the reference computes ``variant``
    (None: the reference as it is)."""
    patched = {}
    if variant is None:
        pass
    elif variant == "weights_fp8":  # the precision below bfloat16, every matrix of every layer and of the module
        patched["cut_layer"] = _layers_changed(lambda k, v: _fp8(v) if v.ndim >= 2 else v)
        if "mtp" in params:
            params = {**params, "mtp": {**params["mtp"], "eh_proj": _fp8(params["mtp"]["eh_proj"])}}
    elif variant == "selection_left_out":  # attention over ALL earlier positions
        patched["select"] = lambda scores, *, topk: scores > -jnp.inf
    elif variant == "indexer_without_relu":
        patched["index_scores"] = _scores(relu=False, weights=True)
    elif variant == "indexer_without_weights":
        patched["index_scores"] = _scores(relu=True, weights=False)
    elif variant == "selection_a_block_late":
        patched["selection"] = _late
    else:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
    for name, fn in patched.items():
        setattr(reference, name, fn)
    try:
        yield model, params
    finally:
        for name in patched:
            setattr(reference, name, _REAL[name])


def logits_at(model, params, tokens, picks, variant=None):
    with wrong(model, params, variant) as (m, p):
        return reference.logits_at(m, p, tokens, picks)


def both_logits_at(model, params, tokens, picks, mtp_picks, variant=None):
    with wrong(model, params, variant) as (m, p):
        return reference.both_logits_at(m, p, tokens, picks, mtp_picks)


def attention_alone(model, stacked, layer, h, queries, variant=None):
    """``reference.attention_alone`` of one layer of a stacked group under a control."""
    with wrong(model, {}, variant) as (m, _):
        return reference.attention_alone(m, reference.cut_layer(stacked, layer), h, queries)


def expert_ffn(model, stacked, layer, h, variant=None):
    """``reference.expert_ffn`` of one layer of a stacked group under a control."""
    with wrong(model, {}, variant) as (m, _):
        return reference.expert_ffn(reference.sizes(m), reference.cut_layer(stacked, layer), h)
