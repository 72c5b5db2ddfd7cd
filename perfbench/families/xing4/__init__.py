"""The family of Xing4-style decoders: latent attention (MLA) with YaRN, a
hyper-connected residual of ``hc_mult`` streams (mHC), leading dense layers
and then sigmoid-routed experts beside a shared expert, of which a
configuration may hold a range (one chip's share of an expert-parallel
deployment: ``n_routed_experts`` in the file is the number held,
``deployment.n_routed_experts_total`` the router's width and
``deployment.held_experts`` the range). The program runs it through
``ray_tpu.models.xing4``.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface, and the latent paths' costs
    absorb_break_even_window,
    attention_flops_per_pair,
    expansion_flops_per_position,
    forward_flops_per_token,
    kv_bytes_per_token,
    param_count,
    train_flops_per_token,
)

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): 2
#: dense + 2 expert layers, 8 experts of which 4 are held, 2 a token, 2 streams.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "hc_mult": 2, "vocab_size": 256,
    "num_hidden_layers": 4, "first_k_dense_replace": 2, "max_position_embeddings": 128,
    "torch_dtype": "float32",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8",
                   "chips_sharing_each_layer": 2, "n_routed_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``Xing4Config`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    try:
        from ray_tpu.models.xing4 import Xing4Config
    except ImportError as e:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout has no ray_tpu.models.xing4 ({e}): the program here cannot run the "
            "xing4 family (latent attention, the hyper-connected residual, held experts)"
        ) from None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    rs = model["rope_scaling"]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if rs["type"] != "yarn" or model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the program runs YaRN and one latent for all heads only")
    if -model["mhc_h_res_clamp_min"] != model["mhc_h_res_clamp_max"]:
        raise ValueError("the program clamps H_res symmetrically")
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
        routed_scaling_factor=float(model["routed_scaling_factor"]), hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"], hc_eps=float(model["hc_eps"]),
        hc_res_clamp=float(model["mhc_h_res_clamp_max"]), max_seq_len=max_seq_len,
        rope_theta=float(model["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]), rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]), rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return Xing4Config(**fields)


def server_class():
    from .server import BenchXing4Server

    return BenchXing4Server


def train_program() -> Tuple[Any, Any, Any]:
    """``init_sharded``, ``make_train_step`` and ``batch_sharding`` of the
    program (rehearsed at ``TOY_SIZES`` only: no training cell runs this family)."""
    from ray_tpu.models.xing4 import batch_sharding, init_sharded, make_train_step

    return init_sharded, make_train_step, batch_sharding


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_expert_ffn(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's second reading of the
    correctness check (``server.py``): the reference's FFN of ONE expert
    layer on normed activations h [T, D] float32, ``(out [T, D], margin [T])``."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), layer_params, h)


def reference_residual(model: Dict[str, Any], layer_params, sub: str, norm: str, X):
    """Beyond the interface, for this family's third reading (``server.py``):
    the reference's hyper-connected residual of ONE sublayer (``sub``
    ``"hc_attn"`` | ``"hc_mlp"``, its norm ``norm``) around ``F`` = the
    identity, on a state X [T, n, D] float32: the maps, Sinkhorn, both mixes."""
    from . import reference

    return reference.hyper(reference.sizes(model), layer_params, sub, norm, X, lambda h: h)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
