"""The CONTROLS of the ``laguna`` family's correctness limits: WRONG models
and the model a precision lower, for the tests and for a builder setting a
limit on the chip; the benchmark never uses them.

Every control is a change of DATA, not of equations: the plain reference
(``perfbench/families/laguna/reference.py``) is run as it is on another
configuration (:func:`wrong_model`) and, where the other model has other
weights, on weights made from the system's (:func:`wrong_params`,
:func:`low_params`), so there is no twin of the equations to keep in step.

Wrong models, one for each thing the published config leaves to the family's
convention or that this family adds to the program: ``no_gate`` (the attention
output goes to ``wo`` as it is), ``gate_a_channel`` (a sigmoid a CHANNEL:
``wg [D, H, hd]``, channel 0 of a head the head's own column, channel c that
column rolled by c), ``all_rotated`` (a full layer rotates the whole head, not
its first half), ``one_head_count`` (every layer the full layers' 48 heads: a
window layer's first 48 of 64, six a KV head), ``one_rope_base`` (the window
layers under the full layers' base), ``softmax_router``, ``not_normalised``
(the kept scores as the sigmoid left them), ``no_scale`` (x 2.5 left out),
``no_shared_expert``, ``layer0_routed`` (layer 0 sparse like the others, over
layer 1's router and experts), ``window_minus_one`` (the edge off by one key),
``window_plus_block`` (what a table that slides a block late would attend over).
A precision lower: ``weights_fp8`` (every matrix a matmul multiplies against
rounded to float8 e4m3, the precision below the stated bfloat16),
``experts_fp8`` (the routed and shared expert matrices alone),
``attention_fp8`` (the four projections and the gate alone)."""

from __future__ import annotations

import copy
from typing import Any, Dict

WRONG_MODELS = ("no_gate", "gate_a_channel", "all_rotated", "one_head_count", "one_rope_base", "softmax_router",
                "not_normalised", "no_scale", "no_shared_expert", "layer0_routed", "window_minus_one",
                "window_plus_block")
LOW_PARAMS = ("weights_fp8", "experts_fp8", "attention_fp8")
VARIANTS = WRONG_MODELS + LOW_PARAMS
#: the wrong models a sparse layer's FFN alone can tell, a layer of either kind alone, a full layer alone
OF_THE_FFN = ("softmax_router", "not_normalised", "no_scale", "no_shared_expert")
OF_A_LAYER = ("no_gate", "gate_a_channel")
OF_A_WINDOW_LAYER = ("one_head_count", "one_rope_base", "window_minus_one", "window_plus_block")
OF_A_FULL_LAYER = ("all_rotated",)

_EXPERTS = ("w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down")
_ATTENTION = ("wq", "wk", "wv", "wo", "wg")
_FFN = ("router",) + _EXPERTS


def wrong_model(model: Dict[str, Any], variant: str, block_size: int = 16) -> Dict[str, Any]:
    """``model`` (a configuration file's dict) as the wrong model ``variant``;
    a ``LOW_PARAMS`` variant leaves it as it is."""
    m = copy.deepcopy(model)
    ropes = m["rope_parameters"]
    if variant == "no_gate":
        m["gating"] = False
    elif variant == "gate_a_channel":
        m["gating"] = "per-channel"
    elif variant == "all_rotated":
        ropes["full_attention"]["partial_rotary_factor"] = 1
    elif variant == "one_head_count":
        kinds, heads = m["layer_types"], m["num_attention_heads_per_layer"]
        full = next(h for h, kind in zip(heads, kinds) if kind == "full_attention")
        m["num_attention_heads_per_layer"] = [full] * len(heads)
    elif variant == "one_rope_base":
        ropes["sliding_attention"]["rope_theta"] = ropes["full_attention"]["rope_theta"]
    elif variant == "softmax_router":
        m["scoring_func"] = "softmax"
    elif variant == "not_normalised":
        m["norm_topk_prob"] = False
    elif variant == "no_scale":
        m["moe_routed_scaling_factor"] = 1.0
    elif variant == "no_shared_expert":
        m["shared_expert_intermediate_size"] = 0
    elif variant == "layer0_routed":
        m["mlp_layer_types"] = ["sparse"] + list(m["mlp_layer_types"][1:])
    elif variant == "window_minus_one":
        m["sliding_window"] -= 1
    elif variant == "window_plus_block":
        m["sliding_window"] += block_size
    elif variant not in LOW_PARAMS:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
    return m


class _Read(dict):
    """A dict of weights whose entries named in ``how`` read as ``how[name]``
    makes them of the stored value, ONE at a time as the reference asks for
    them: a changed copy of all of them would not fit beside a replica that
    fills its chip."""

    def __init__(self, weights, how):
        super().__init__(weights)
        self._how = how

    def __getitem__(self, key):
        value = super().__getitem__(key)
        return self._how[key](value) if key in self._how else value


def _fp8(value):
    import jax.numpy as jnp

    return value.astype(jnp.float8_e4m3fn).astype(value.dtype)


def _a_channel(hd: int):
    def widen(wg):  # [D, H] -> [D, H, hd]: channel c the head's column rolled by c along D
        import jax.numpy as jnp

        return jnp.stack([jnp.roll(wg, c, axis=0) for c in range(hd)], axis=-1)

    return widen


def wrong_layer_params(model: Dict[str, Any], p, variant: str):
    """ONE layer's weights as the wrong model ``variant`` reads them: a gate a
    channel made of the layer's gate a head; every other variant's as they are."""
    if variant == "gate_a_channel":
        return _Read(p, {"wg": _a_channel(int(model["head_dim"]))})
    return p


def wrong_params(model: Dict[str, Any], params, variant: str):
    """The weights the wrong model ``variant`` of ``model`` reads, where it
    has others than the system's: a gate a channel, a routed layer 0."""
    layers = [wrong_layer_params(model, p, variant) for p in params["layers"]]
    if variant == "layer0_routed":
        layers[0] = {**layers[0], **{k: layers[1][k] for k in _FFN}}
    return {**params, "layers": layers}


def low_params(params, variant: str):
    """``params`` with the matrices ``variant`` names read as rounded to
    float8 e4m3 and back (the router stays float32, as the system keeps it);
    a ``WRONG_MODELS`` variant leaves them as they are."""
    names = {"weights_fp8": _EXPERTS + _ATTENTION, "experts_fp8": _EXPERTS,
             "attention_fp8": _ATTENTION}.get(variant, ())
    if not names:
        return params
    how = dict.fromkeys(names, _fp8)
    top = {"lm_head": _fp8} if variant == "weights_fp8" else {}
    return _Read({**params, "layers": [_Read(p, how) for p in params["layers"]]}, top)


def low_layer_params(p, variant: str):
    """ONE layer's weights as :func:`low_params` would hand them."""
    return low_params({"layers": [p]}, variant)["layers"][0]
