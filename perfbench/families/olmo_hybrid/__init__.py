"""The family of Olmo-Hybrid-style decoders: Gated DeltaNet layers (a
delta-rule recurrence with ONE gate a head over a state of ``dk x dv`` a head)
and multi-head attention layers without any position term, 3 : 1 in one
residual stream with the norm on each sublayer's output (the OLMo 2 / OLMo 3
block), a dense gated MLP in every layer, an untied head. The program runs it
through ``ray_tpu.models.olmo_hybrid``.

The members are ``perfbench.families.INTERFACE`` plus
``state_bytes_per_seq(model)``; the reference's equations are in
``reference.py`` and the counts' in ``counts.py``, once each. JAX is imported
inside the functions that need it: the benchmark's own process imports this
module and stays off the chip.

Worked notes (``families/kimi_linear/__init__.py`` has the first family that
keeps something a SEQUENCE beside the rows a token, ``families/jamba`` the one
nearest to this: a ``"kv"`` cache beside the state; what differs here):

* The model's two pools are BOTH large: a ``"kv"`` ``CacheLayout`` of 4 of 16
  layers (30 KV heads of 128: 61,440 B a token) and a ``StateLayout`` ``gdn``
  of 12 with two arrays (``gdn_state`` ``[96, 30 x 192]`` float32, a slot's
  heads joined along the lanes; ``gdn_conv`` ``[3 x 11520]``): 27,371,520 B a
  sequence. ``server.py`` overrides ``bench_check`` and drives the runner with
  a state slot a sequence, as Jamba's does.
* Four readings decide ``correct`` (``server.py``): the logits after the
  prefill and after EVERY decode step; THE STATE POOL as the serving programs
  left it (``S`` and the tail), read twice; a Gated DeltaNet mixer and an
  attention mixer ALONE (the configuration file's ``correctness.reason``).
* A checkout without ``ray_tpu/models/olmo_hybrid.py`` ends the run as this
  module is imported (``families.of`` in the benchmark's own process, before any
  cluster starts): at once, non-zero, with no worker behind it. (The parent of
  the PR that brought the family has no ``families/olmo_hybrid`` either, and
  ends earlier still: "the families present under perfbench/families are".)
* ``train_program`` refuses: the program has no backward of the chunked form,
  and no training cell runs this family.
* The cell joins the per-layer entries that already read its counters
  (``.batch``, ``.kda``, ``.mla``, ``.longdoc``) and brings ONE of its own:
  ``state_stored_bytes_per_seq.gdn``, what the pool's layout really holds a
  sequence (``engine_stats()["state_layout"]["stored_bytes_per_seq"]``)."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface
    forward_flops_per_token,
    kv_bytes_per_token,
    param_count,
    state_bytes_per_seq,
    train_flops_per_token,
)


def _refuse_a_checkout_without_the_model() -> None:
    """A checkout from before the program could run this family ends the run
    HERE, as the family is imported (``families.of`` in ``run.py``): before any
    cluster starts, and without importing the program (a path is asked for)."""
    import importlib.util
    import os

    spec = importlib.util.find_spec("ray_tpu")
    roots = list(spec.submodule_search_locations or ()) if spec else []
    if not any(os.path.exists(os.path.join(root, "models", "olmo_hybrid.py")) for root in roots):
        raise SystemExit(
            "this checkout has no ray_tpu.models.olmo_hybrid: the program here cannot run the olmo_hybrid "
            "family (Gated DeltaNet layers with a per-sequence state pool beside a K/V cache of 30 heads)"
        )


_refuse_a_checkout_without_the_model()

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): two
#: periods less a layer (G G G A | G G A), 3 heads of 16 over 3 KV heads, a
#: state of 8 x 16 a head (``dv = 2 dk``: no whole lane, as published).
TOY_SIZES = {
    "hidden_size": 48, "num_attention_heads": 3, "num_key_value_heads": 3, "intermediate_size": 96,
    "vocab_size": 256, "num_hidden_layers": 7,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"] + ["linear_attention"] * 2 + ["full_attention"],
    "linear_num_key_heads": 3, "linear_num_value_heads": 3, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "max_position_embeddings": 128, "torch_dtype": "float32",
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``OlmoHybridConfig`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.olmo_hybrid import OlmoHybridConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("rope_parameters", {"rope_theta": None})):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if model["linear_num_key_heads"] != model["linear_num_value_heads"]:
        raise ValueError("the program runs as many key heads as value heads in a Gated DeltaNet layer only")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        layer_types=tuple(model["layer_types"]), n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"],
        gdn_heads=model["linear_num_value_heads"], gdn_key_dim=model["linear_key_head_dim"],
        gdn_value_dim=model["linear_value_head_dim"], conv_kernel=model["linear_conv_kernel_dim"],
        allow_neg_eigval=bool(model["linear_allow_neg_eigval"]), mlp_hidden=model["intermediate_size"],
        max_seq_len=max_seq_len, norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return OlmoHybridConfig(**fields)


def server_class():
    from .server import BenchOlmoHybridServer

    return BenchOlmoHybridServer


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the olmo_hybrid family is served only: the program has no backward of the chunked form of the "
        "gated delta rule and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_logits_and_state(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]],
                               ats: Sequence[Sequence[int]]):
    """Beyond the interface, for this family's drive (``server.py``): the
    logits as :func:`reference_logits` and, from the same pass, per row and
    Gated DeltaNet layer what a sequence of the row's first ``at`` tokens leaves
    in the layer for each ``at`` of ``ats[row]``: ``(S [H, dk, dv], tail [K - 1,
    2 H dk + H dv])``."""
    from . import reference

    return reference.logits_at(model, params, tokens, picks, ats)


def reference_gdn(model: Dict[str, Any], layer_params, x):
    """The reference's Gated DeltaNet mixer of ONE layer over x [T, D] float32
    from a zero state and zeros before position 0 -> ``[T, D]``."""
    from . import reference

    return reference.gated_deltanet(reference.sizes(model), layer_params, x)[0]


def reference_attention(model: Dict[str, Any], layer_params, x):
    """The reference's attention of ONE layer, causal over x [T, D] float32
    from an empty context -> ``[T, D]``."""
    from . import reference

    return reference.attention(reference.sizes(model), layer_params, x)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
