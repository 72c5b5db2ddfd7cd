"""The family of LFM2-style decoders: gated short-convolution layers and
grouped-query attention layers of narrow heads (64) in an irregular pattern
(``layer_types``), leading dense MLPs and then sigmoid-routed experts with a
choice bias and no shared expert, of which a configuration may hold a range
(one chip's share of an expert-parallel deployment: ``num_experts`` in the
file is the number held, ``deployment.num_experts_total`` the router's width
and ``deployment.held_experts`` the range), the embedding read again as the
head. The program runs it through ``ray_tpu.models.lfm2``.

The members are ``perfbench.families.INTERFACE`` plus
``state_bytes_per_seq(model)``; the reference's equations are in
``reference.py`` and the counts' in ``counts.py``, once each. JAX is imported
inside the functions that need it: the benchmark's own process imports this
module and stays off the chip.

Worked notes (``families/kimi_linear/__init__.py`` has the first family that
keeps something a SEQUENCE beside the rows a token; what differs here):

* The model's two pools are a ``"kv"`` ``CacheLayout`` (K and V rows of 8 x
  64 in the 6 attending layers) and a ``StateLayout`` ``short_conv`` (the last
  two inputs of the convolution in the 18 others, 8 KB a layer a sequence).
  ``server.py`` overrides ``bench_check`` and drives the runner with a state
  slot a sequence, as Kimi-Linear's does.
* Five readings decide ``correct`` (``server.py``): the logits; THE STATE
  POOL as the serving programs left it, read TWICE (after the chunked prefill
  and after the last decode step) against the reference's ``z`` at the
  sequence's last two positions; a convolution mixer, an attention mixer and
  an expert FFN ALONE (the configuration file's ``correctness.reason``).
* ``train_program`` refuses: the program has no sharded training step for
  this family, and no training cell runs it.
* The cell joins the per-layer entries that already read its counters
  (``.batch``, ``.moe``, ``.mla``, ``.longdoc``, ``.kda``) and brings none of
  its own: both kernels run under the names they had."""

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

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): C C A
#: C A C C (the last attention after ONE convolution, then two more), the first
#: layer dense, 8 experts of which 4 are held, 2 a token; heads of 64, two KV
#: heads: a row of the cache is whole lanes, the form the chip's is stored in.
TOY_SIZES = {
    "hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_tok": 2, "vocab_size": 256, "num_hidden_layers": 7, "num_dense_layers": 1,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128, "torch_dtype": "float32",
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8",
                   "chips_sharing_each_layer": 2, "num_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``Lfm2Config`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    try:
        from ray_tpu.models.lfm2 import Lfm2Config
    except ImportError as e:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout has no ray_tpu.models.lfm2 ({e}): the program here cannot run the lfm2 "
            "family (gated short-convolution layers with a per-sequence state pool beside a K/V cache)"
        ) from None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("conv_bias", False), ("use_expert_bias", True), ("norm_topk_prob", True),
                      ("tie_word_embeddings", True)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    kinds = model["layer_types"]
    if len(kinds) != model["num_hidden_layers"] or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("layer_types names 'conv' or 'full_attention' for each of num_hidden_layers layers")
    lo, hi = model["deployment"]["held_experts"]
    if hi - lo != model["num_experts"]:
        raise ValueError("num_experts is the number of experts held: the width of held_experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        attn_layers=tuple(l for l, kind in enumerate(kinds) if kind == "full_attention"),
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"],
        conv_kernel=model["conv_L_cache"], n_dense_layers=model["num_dense_layers"],
        mlp_hidden=model["intermediate_size"], moe_hidden=model["moe_intermediate_size"],
        n_routed_experts=model["deployment"]["num_experts_total"], held_experts=(int(lo), int(hi)),
        moe_top_k=model["num_experts_per_tok"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        rope_theta=float(model["rope_theta"]), max_seq_len=max_seq_len,
        norm_eps=float(model["norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return Lfm2Config(**fields)


def server_class():
    from .server import BenchLfm2Server

    return BenchLfm2Server


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the lfm2 family is served only: the program has no sharded training step for it "
        "(the convolution's backward over a state description) and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_logits_and_tails(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]],
                               ats: Sequence[Sequence[int]]):
    """Beyond the interface, for this family's drive (``server.py``): the
    logits as :func:`reference_logits` and, from the same pass, per row and
    convolution layer what a sequence of the row's first ``at`` tokens leaves
    in the layer for each ``at`` of ``ats[row]``: ``[len(ats[row]), K - 1, D]``."""
    from . import reference

    return reference.logits_at(model, params, tokens, picks, ats)


def reference_expert_ffn(model: Dict[str, Any], layer_params, f):
    """The reference's FFN of ONE expert layer on normed activations f [T, D]
    float32, ``(out [T, D], margin [T])``."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), layer_params, f)


def reference_conv(model: Dict[str, Any], layer_params, u):
    """The reference's convolution mixer of ONE layer over u [T, D] float32
    from zeros before position 0 -> ``[T, D]``."""
    from . import reference

    return reference.conv(reference.sizes(model), layer_params, u)


def reference_attention(model: Dict[str, Any], layer_params, u):
    """The reference's attention of ONE layer, causal over u [T, D] float32
    from an empty context -> ``[T, D]``."""
    from . import reference

    return reference.attention(reference.sizes(model), layer_params, u)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
