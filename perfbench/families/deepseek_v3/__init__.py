"""The family of DeepSeek-V3-style decoders (``model_type`` ``deepseek_v3``;
GigaChat3.1-702B-A36B): latent attention (MLA) with YaRN and a value head of
its own width, leading dense layers, then sigmoid-routed experts beside a
shared expert with the choice limited to groups of experts, of which a
configuration may hold a range (one chip's share of an expert-parallel
deployment: ``n_routed_experts`` in the file is the number held,
``deployment.n_routed_experts_total`` the router's width and
``deployment.held_experts`` the range), and the multi-token-prediction module
kept as the drafter of a serving step (``num_nextn_predict_layers`` 1). The
program runs it through ``ray_tpu.models.deepseek_v3``.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip.

A checkout whose program has no ``ray_tpu/models/deepseek_v3.py`` cannot run
this family; importing the family there ends the run with that sentence, which
is before any cluster starts (``run.py`` asks for the family first)."""

from __future__ import annotations

import os
from typing import Any, Dict, List, Tuple

import ray_tpu  # jax-free

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface, and the kernels' costs
    forward_flops_per_token,
    kv_bytes_per_token,
    latent_flash_cost,
    latent_rows_cost,
    param_count,
    train_flops_per_token,
)

if not os.path.exists(os.path.join(os.path.dirname(ray_tpu.__file__), "models", "deepseek_v3.py")):
    raise SystemExit(
        "this checkout has no ray_tpu/models/deepseek_v3.py: the program here cannot run the "
        "deepseek_v3 family (group-limited routing, a 192-wide value head, the MTP module as drafter)"
    )

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): 1
#: dense + 2 expert layers and the MTP module, 8 experts in 4 groups of which 2
#: stay, 4 held, 2 a token, a value head one and a half times the nope part.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2, "vocab_size": 256,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "max_position_embeddings": 128,
    "torch_dtype": "float32",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "rope_type": "yarn"},
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8 (groups 0 and 1 of 4)",
                   "chips_sharing_each_layer": 2, "n_routed_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``DeepseekV3Config`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.deepseek_v3 import DeepseekV3Config

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    rs = model["rope_scaling"]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("moe_layer_freq", 1)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if rs.get("rope_type", rs.get("type")) != "yarn" or model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the program runs YaRN and one latent for all heads only")
    if model["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("the program keeps one MTP module or none")
    lo, hi = model["deployment"]["held_experts"]
    total = model["deployment"]["n_routed_experts_total"]
    if hi - lo != model["n_routed_experts"]:
        raise ValueError("n_routed_experts is the number of experts held: the width of held_experts")
    if total % model["n_group"]:
        raise ValueError("the router's width is n_group groups of equally many experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"], n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"], q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"], qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        mlp_hidden=model["intermediate_size"], moe_hidden=model["moe_intermediate_size"],
        n_routed_experts=total, held_experts=(int(lo), int(hi)),
        n_shared_experts=model["n_shared_experts"], moe_top_k=model["num_experts_per_tok"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        n_group=model["n_group"], topk_group=model["topk_group"],
        n_mtp_layers=model["num_nextn_predict_layers"], max_seq_len=max_seq_len,
        rope_theta=float(model["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return DeepseekV3Config(**fields)


def server_class():
    from .server import BenchDeepseekV3Server

    return BenchDeepseekV3Server


def train_program() -> Tuple[Any, Any, Any]:
    """``init_sharded``, ``make_train_step`` and ``batch_sharding`` of the
    program: the MAIN model's (``ray_tpu.models.xing4``'s, which this family's
    body is; the MTP module is serving's). Rehearsed at ``TOY_SIZES`` only: no
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


def reference_expert_ffn(model: Dict[str, Any], stacked, layer: int, h):
    """Beyond the interface, for the check's second reading: the reference's
    FFN of expert layer ``layer`` of a stacked group on normed activations h
    [T, D] float32, ``(out [T, D], margin [T])`` (the margin of the group
    choice and of the experts'). An expert at a time is cut out of the stack."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), reference.cut_layer(stacked, layer), h)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
