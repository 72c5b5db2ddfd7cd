"""The family of Laguna-style decoders (``model_type`` ``laguna``;
Laguna-XS.2): grouped-query attention in layers of two KINDS by
``layer_types`` that differ in more than their window: the query heads
(``num_attention_heads_per_layer``: 48 in a ``full_attention`` layer, 64 in a
``sliding_attention`` one, over 8 KV heads of 128), the rope (``rope_parameters``
a kind: its base, ``partial_rotary_factor`` of a head rotated, YaRN or the
plain table); a learned sigmoid gate a head on the attention output
(``gating``); FFNs by ``mlp_layer_types``: ``dense`` of ``intermediate_size``,
or ``sparse``: a sigmoid router whose kept scores are normalised and scaled by
``moe_routed_scaling_factor`` over dropless gated SiLU experts of which a
configuration may hold a range (one chip's share of an expert-parallel
deployment: ``num_experts`` in the file is the number held,
``deployment.num_experts_total`` the router's width and
``deployment.held_experts`` the range), beside a shared expert; untied head.
The program runs it through ``ray_tpu.models.llama``: a ``LayerKind`` a layer
(``layer_kinds``), ``attn_gate``, ``dense_layers``, ``moe_scoring`` /
``moe_scale`` / ``moe_shared_hidden`` / ``moe_held``. The paged cache is one
pool of blocks a window width (``models/interface.py::LayerGroup``), as
Mellum2's: ``perfbench/families/mellum/__init__.py`` has the notes on pools,
rows and what is refused; what is new here is that the window (512) is
narrower than the largest prefill chunk (1024), so a window table slides
INSIDE a prompt's prefill.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface, and the kernels' costs
    chunk_attn_cost,
    forward_flops_per_token,
    kv_bytes_held,
    kv_bytes_per_token,
    paged_attn_cost,
    param_count,
    train_flops_per_token,
)

KINDS = ("sliding_attention", "full_attention")

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): the
#: dense layer 0 and a period after it (F W W W F), 6 / 8 query heads over 2 KV
#: heads of 16, a window of two of the toy engine's blocks of 8 (half of its
#: largest chunk, as at full size), 8 experts of which 4 are held, 2 a token,
#: half-rotary YaRN over an original context of 32.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 4, "num_experts_per_tok": 2,
    "vocab_size": 256, "num_hidden_layers": 5, "max_position_embeddings": 128, "sliding_window": 16,
    "torch_dtype": "float32",
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
                           "original_max_position_embeddings": 32, "beta_fast": 8, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
        "original_max_position_embeddings": 32,
    },
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8",
                   "chips_sharing_each_layer": 2, "num_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``LlamaConfig`` for a configuration file's published keys (Hugging Face
    names), unchanged widths. Refuses what the program does not run."""
    import dataclasses

    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models import llama

    lacks = {"layer_kinds", "attn_gate", "dense_layers", "moe_scoring", "moe_shared_hidden", "moe_held",
             "init_depth_scaled"} - {
        f.name for f in dataclasses.fields(llama.LlamaConfig)
    }
    if lacks:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout's ray_tpu.models.llama.LlamaConfig has no {sorted(lacks)}: the program here "
            "cannot run the laguna family (layer kinds with their own query heads and rope, a gate a head, "
            "a dense layer beside sigmoid-routed ones with a shared expert)"
        )
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("gating", True), ("moe_apply_router_weight_on_input", False)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if model["scoring_func"] not in ("sigmoid", "softmax"):
        raise ValueError(f"the program's router scores by sigmoid or softmax, the file says {model['scoring_func']!r}")
    n = model["num_hidden_layers"]
    kinds, heads, ffns = (model[key][:n] for key in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types"))
    if min(len(kinds), len(heads), len(ffns)) != n or set(kinds) - set(KINDS) or set(ffns) - {"dense", "sparse"}:
        raise ValueError(f"the program runs {n} layers of the kinds {KINDS}, each with a dense or a sparse MLP")
    hd = model["head_dim"]

    def kind_of(name: str, n_heads: int):
        rope = model["rope_parameters"][name]
        scaling = None
        if rope["rope_type"] == "yarn":
            scaling = llama.RopeScaling(
                factor=float(rope["factor"]), original_max=int(rope["original_max_position_embeddings"]),
                beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
                attention_factor=float(rope["attention_factor"]),
            )
        elif rope["rope_type"] != "default":
            raise ValueError(f"the program runs the plain table or YaRN, the file says {rope['rope_type']!r}")
        rotated = int(hd * float(rope.get("partial_rotary_factor", 1)))
        return llama.LayerKind(
            window=model["sliding_window"] if name == "sliding_attention" else 0, n_heads=int(n_heads),
            rope_theta=float(rope["rope_theta"]), rotary_dim=0 if rotated == hd else rotated, rope_scaling=scaling,
        )

    lo, hi = model["deployment"]["held_experts"]
    total = model["deployment"]["num_experts_total"]
    if hi - lo != model["num_experts"]:
        raise ValueError("num_experts is the number of experts held: the width of held_experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=n,
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], attn_head_dim=hd,
        max_seq_len=max_seq_len, norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
        layer_kinds=tuple(kind_of(name, h) for name, h in zip(kinds, heads)), attn_gate=True,
        mlp_hidden=model["moe_intermediate_size"],  # the width of ONE routed expert
        dense_layers=tuple(l for l, ffn in enumerate(ffns) if ffn == "dense"),
        dense_mlp_hidden=model["intermediate_size"], moe_shared_hidden=model["shared_expert_intermediate_size"],
        moe_experts=total, moe_held=(int(lo), int(hi)), moe_top_k=model["num_experts_per_tok"],
        moe_scoring=model["scoring_func"], moe_renormalize=bool(model["norm_topk_prob"]),
        moe_scale=float(model["moe_routed_scaling_factor"]), moe_aux_loss_coeff=0.0,
        init_depth_scaled=True,  # the seeded weights of assumed.norm_weights
    )
    fields.update(overrides)
    return llama.LlamaConfig(**fields)


def server_class():
    from .server import BenchLagunaServer

    return BenchLagunaServer


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the laguna family is served only: the program's sharded training step has no window mask "
        "(ops/attention.py; ROADMAP R4) and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_expert_ffn(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's second reading (``server.py``):
    the reference's FFN of ONE sparse layer (its held part and the shared
    expert) on normed activations h [T, D] float32, ``(out [T, D], margin [T])``."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), layer_params, h)


def reference_attention(model: Dict[str, Any], layer_params, h, kind: str):
    """Beyond the interface, for the third and fourth readings: the
    reference's attention of ONE layer of that kind (its heads, its rope, the
    gate in), causal over h [T, D] float32 from an empty context -> ``[T, D]``."""
    from . import reference

    z = reference.sizes(model)
    (n_heads,) = {n for n, k in zip(z["heads"], z["kinds"]) if k == kind}  # one number a kind
    return reference.attention(z, layer_params, h, kind, n_heads)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
