"""The CONTROLS of the ``deepseek_v3`` family's correctness limits: wrong
models that a comparison with the reference has to tell from the right one,
and the right one computed in float8 where the configuration states bfloat16.
Each is ``perfbench/families/deepseek_v3/reference.py`` with ONE thing wrong,
made by a changed key of the configuration, a changed weight (as a layer is cut
out of the stacks) or a changed ``mtp`` group. The tests keep this file;
nothing under ``perfbench/`` imports it."""

from __future__ import annotations

import contextlib
import copy
from typing import Any, Dict

import jax.numpy as jnp

from perfbench.families.deepseek_v3 import reference

VARIANTS = (
    "group_limit_left_out", "one_group_kept_too_many", "gates_not_normalised", "scale_without_m2",
    "bias_out_of_the_choice", "shared_expert_2_times", "weights_fp8",
    "mtp_halves_swapped", "mtp_embeds_the_same_token",
)


def _fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


class _Changed(reference._Cut):
    """A cut of a stacked weight whose every piece is changed as it is cut."""

    def __init__(self, stacked, layer, change):
        super().__init__(stacked, layer)
        self.change = change

    def __getitem__(self, idx):
        return self.change(super().__getitem__(idx))


_REAL_CUT = reference.cut_layer


def _layers_changed(change):
    """``reference.cut_layer`` with ``change(name, weight)`` applied to a
    layer's weights as they are cut out of the stacks, an expert at a time."""
    def cut_layer(stacked, i):
        out = {}
        for k, v in _REAL_CUT(stacked, i).items():
            if isinstance(v, reference._Cut):
                out[k] = _Changed(v.stacked, v.layer, lambda w, k=k: change(k, w))
            else:
                out[k] = change(k, v)
        return out
    return cut_layer


def _scaled(names, factor):
    return _layers_changed(lambda k, v: (v * factor).astype(v.dtype) if k in names else v)


@contextlib.contextmanager
def wrong(model: Dict[str, Any], params, variant):
    """``(model, params)`` under which the reference computes ``variant``
    (None: the reference as it is)."""
    model = copy.deepcopy(model)
    patched = {}
    if variant is None:
        pass
    elif variant == "group_limit_left_out":  # the plain top-8 of score + bias
        model.update(n_group=1, topk_group=1)
    elif variant == "one_group_kept_too_many":
        model["topk_group"] = model["topk_group"] + 1
    elif variant == "gates_not_normalised":
        model["norm_topk_prob"] = False
    elif variant == "scale_without_m2":
        model["rope_scaling"] = {**model["rope_scaling"], "mscale_all_dim": 0}
    elif variant == "bias_out_of_the_choice":
        patched["cut_layer"] = _scaled(("router_bias",), 0.0)
    elif variant == "shared_expert_2_times":
        patched["cut_layer"] = _scaled(("shared_down",), 2.0)
    elif variant == "weights_fp8":  # the precision below bfloat16, every matrix of every layer and of the module
        patched["cut_layer"] = _layers_changed(lambda k, v: _fp8(v) if v.ndim >= 2 else v)
        params = {**params, "mtp": {**params["mtp"], "eh_proj": _fp8(params["mtp"]["eh_proj"])}}
    elif variant == "mtp_halves_swapped":  # [rms(h) ; rms(Emb)]: the paper's order against the served weights'
        eh = params["mtp"]["eh_proj"]
        half = eh.shape[0] // 2
        swapped = jnp.concatenate([eh[half:], eh[:half]])
        params = {**params, "mtp": {**params["mtp"], "eh_proj": swapped}}
    elif variant == "mtp_embeds_the_same_token":  # Emb(t_i) where the module takes Emb(t_{i+1})
        patched["mtp_follows"] = lambda row: row[:-1]
    else:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
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


def both_logits_at(model, params, tokens, picks, mtp_picks, variant=None):
    with wrong(model, params, variant) as (m, p):
        return reference.both_logits_at(m, p, tokens, picks, mtp_picks)


def expert_ffn(model, stacked, layer, h, variant=None):
    """``reference.expert_ffn`` of one layer of a stacked group under a control."""
    with wrong(model, {"mtp": {"eh_proj": jnp.zeros((2, 2))}, "moe": stacked}, variant) as (m, _):
        return reference.expert_ffn(reference.sizes(m), reference.cut_layer(stacked, layer), h)
