"""The CONTROLS of the ``mellum`` family's correctness limits: WRONG models
and the model a precision lower, for the tests and for a builder setting a
limit on the chip; the benchmark never uses them.

Every control is a change of DATA, not of equations: the plain reference
(``perfbench/families/mellum/reference.py``) is run as it is on another
configuration (:func:`wrong_model`) or on other weights (:func:`low_params`),
so there is no twin of the equations to keep in step.

Wrong models: ``no_window`` (a window layer sees every key), ``window_plus_block``
(the window a block of 16 too wide: what a table that slides a block late
would attend over), ``window_minus_one`` (the edge off by one key), ``no_yarn``
(the plain table in full layers too, attention factor 1), ``yarn_everywhere``
(one rope for both kinds: YaRN's in window layers too),
``no_attention_factor`` (YaRN's frequencies, cos and sin not scaled),
``not_renormalised`` (the kept gates as the softmax left them). A precision
lower: ``weights_fp8`` (every matrix a matmul multiplies against rounded to
float8 e4m3, the precision below the stated bfloat16), ``experts_fp8`` (the
expert matrices alone), ``attention_fp8`` (the four projections alone)."""

from __future__ import annotations

import copy
from typing import Any, Dict

WRONG_MODELS = ("no_window", "window_plus_block", "window_minus_one", "no_yarn", "yarn_everywhere",
                "no_attention_factor", "not_renormalised")
LOW_PARAMS = ("weights_fp8", "experts_fp8", "attention_fp8")
VARIANTS = WRONG_MODELS + LOW_PARAMS

_EXPERTS = ("w_gate", "w_up", "w_down")
_ATTENTION = ("wq", "wk", "wv", "wo")


def wrong_model(model: Dict[str, Any], variant: str, block_size: int = 16) -> Dict[str, Any]:
    """``model`` (a configuration file's dict) as the wrong model ``variant``;
    a ``LOW_PARAMS`` variant leaves it as it is."""
    m = copy.deepcopy(model)
    ropes = m["rope_parameters"]
    if variant == "no_window":
        m["sliding_window"] = 2**30
    elif variant == "window_plus_block":
        m["sliding_window"] += block_size
    elif variant == "window_minus_one":
        m["sliding_window"] -= 1
    elif variant == "no_yarn":
        ropes["full_attention"] = dict(ropes["sliding_attention"])
    elif variant == "yarn_everywhere":
        ropes["sliding_attention"] = dict(ropes["full_attention"])
    elif variant == "no_attention_factor":
        ropes["full_attention"]["attention_factor"] = 1.0
    elif variant == "not_renormalised":
        m["norm_topk_prob"] = False
    elif variant not in LOW_PARAMS:
        raise ValueError(f"unknown control {variant!r} (has {VARIANTS})")
    return m


class _Low(dict):
    """A dict of weights whose matrices named in ``names`` read as rounded
    to float8 e4m3 and back, ONE at a time as the reference asks for them: a
    rounded copy of all of them would not fit beside a replica that fills
    its chip."""

    def __init__(self, weights, names):
        super().__init__(weights)
        self._names = names

    def __getitem__(self, key):
        import jax.numpy as jnp

        value = super().__getitem__(key)
        return value.astype(jnp.float8_e4m3fn).astype(value.dtype) if key in self._names else value


def low_params(params, variant: str):
    """``params`` with the matrices ``variant`` names read as rounded to
    float8 e4m3 and back (the router stays float32, as the system keeps it);
    a ``WRONG_MODELS`` variant leaves them as they are."""
    names = {"weights_fp8": _EXPERTS + _ATTENTION, "experts_fp8": _EXPERTS,
             "attention_fp8": _ATTENTION}.get(variant, ())
    if not names:
        return params
    top = ("lm_head",) if variant == "weights_fp8" else ()
    return _Low({**params, "layers": [_Low(p, names) for p in params["layers"]]}, top)
