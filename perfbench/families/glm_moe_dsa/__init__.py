"""The family of GLM-5-style decoders (``model_type`` ``glm_moe_dsa``):
DeepSeek-V3's block (latent attention, leading dense layers, sigmoid-routed
experts beside a shared expert, of which a configuration may hold a range: one
chip's share of an expert-parallel deployment, ``n_routed_experts`` in the file
the number held, ``deployment.n_routed_experts_total`` the router's width and
``deployment.held_experts`` the range; the multi-token-prediction module kept
as the drafter of a serving step) with a LEARNED SPARSE SELECTION in every
attention: an indexer of ``index_n_heads`` heads of ``index_head_dim`` scores
every earlier position from its own cached key a token, and the softmax runs
over the ``index_topk`` best positions a query. No YaRN, no group stage in the
router. The program runs it through ``ray_tpu.models.glm_dsa``.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip.

A checkout whose program has no ``ray_tpu/models/glm_dsa.py`` cannot run this
family; importing the family there ends the run with that sentence, which is
before any cluster starts (``run.py`` asks for the family first)."""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import ray_tpu  # jax-free

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface, and the selection's costs
    forward_flops_per_token,
    gathered_attend_cost,
    index_bytes_per_token,
    index_scores_cost,
    kv_bytes_per_token,
    masked_attend_cost,
    param_count,
    topk_cost,
    train_flops_per_token,
)

if not os.path.exists(os.path.join(os.path.dirname(ray_tpu.__file__), "models", "glm_dsa.py")):
    raise SystemExit(
        "this checkout has no ray_tpu/models/glm_dsa.py: the program here cannot run the "
        "glm_moe_dsa family (a learned sparse selection inside latent attention, a second cached row a token)"
    )

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): 1
#: dense + 2 expert layers and the MTP module, 8 experts of which 4 are held, 2
#: a token, an indexer of 3 heads of 16 that keeps 24 positions a query (the
#: rehearsal's prompts run to a few dozen tokens: both sides of it are met).
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "head_dim": 8, "v_head_dim": 24, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256,
    "index_n_heads": 3, "index_head_dim": 16, "index_topk": 24,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "max_position_embeddings": 128,
    "torch_dtype": "float32",
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8",
                   "chips_sharing_each_layer": 2, "n_routed_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``GlmDsaConfig`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.glm_dsa import GlmDsaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1), ("n_group", 1), ("topk_group", 1), ("rope_interleave", True),
                      ("indexer_rope_interleave", True)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if model["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("the program runs this family with the plain rotary table only")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the program runs one latent for all heads only")
    if model["qk_head_dim"] != model["qk_nope_head_dim"] + model["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is the nope and the rope part of a head")
    if model["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("the program keeps one MTP module or none")
    if model["index_head_dim"] < model["qk_rope_head_dim"]:
        raise ValueError("an indexer head holds the rope part and more")
    lo, hi = model["deployment"]["held_experts"]
    if hi - lo != model["n_routed_experts"]:
        raise ValueError("n_routed_experts is the number of experts held: the width of held_experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"], q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"], qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        mlp_hidden=model["intermediate_size"], moe_hidden=model["moe_intermediate_size"],
        n_routed_experts=model["deployment"]["n_routed_experts_total"], held_experts=(int(lo), int(hi)),
        n_shared_experts=model["n_shared_experts"], moe_top_k=model["num_experts_per_tok"],
        routed_scaling_factor=float(model["routed_scaling_factor"]), n_group=1, topk_group=1,
        n_mtp_layers=model["num_nextn_predict_layers"], max_seq_len=max_seq_len,
        rope_theta=float(model["rope_parameters"]["rope_theta"]), rope_factor=1.0,
        index_n_heads=model["index_n_heads"], index_head_dim=model["index_head_dim"],
        index_topk=model["index_topk"], norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return GlmDsaConfig(**fields)


def server_class():
    from .server import BenchGlmDsaServer

    return BenchGlmDsaServer


def train_program() -> Tuple[Any, Any, Any]:
    """``init_sharded``, ``make_train_step`` and ``batch_sharding`` of the
    program: the MAIN model's (``ray_tpu.models.xing4``'s, which this family's
    body is; the selection is a mask in its ``forward``, through which nothing
    is differentiated, so the indexer learns nothing there: the published model
    trains it with a loss of its own). Rehearsed at ``TOY_SIZES`` only: no
    training cell runs this family."""
    from ray_tpu.models.xing4 import batch_sharding, init_sharded, make_train_step

    return init_sharded, make_train_step, batch_sharding


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_both_logits(model: Dict[str, Any], params, tokens, picks, mtp_picks):
    """Beyond the interface, for this family's check (``server.py``): the main
    model's logits at ``picks`` and the MTP module's at ``mtp_picks``, one
    pass over ``tokens``."""
    from . import reference

    return reference.both_logits_at(model, params, tokens, picks, mtp_picks)


def reference_attention(model: Dict[str, Any], stacked, layer: int, h, queries: List[int]):
    """Beyond the interface, for the check's second and third readings: the
    reference's attention sublayer of layer ``layer`` of a stacked group on
    normed activations h [T, D] float32: ``(out [T, D], {query: (chosen [T]
    bool, scores [T] float32)})`` for the ``queries`` named."""
    from . import reference

    return reference.attention_alone(model, reference.cut_layer(stacked, layer), h, queries)


def reference_expert_ffn(model: Dict[str, Any], stacked, layer: int, h):
    """Beyond the interface, for the check's fourth reading: the reference's
    FFN of expert layer ``layer`` of a stacked group on normed activations h
    [T, D] float32, ``(out [T, D], margin [T])``. An expert at a time is cut
    out of the stack."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), reference.cut_layer(stacked, layer), h)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
