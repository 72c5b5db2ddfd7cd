"""The family of OLMoE-style sparse decoders (pre-norm, multi-head
attention with rotary embeddings and an RMS norm over the whole q and the
whole k projection, a softmax router with top-k gates that are NOT
renormalised, dropless gated SiLU experts, no shared expert, untied head),
which the program runs through ``ray_tpu.models.llama`` with ``qk_norm``,
``moe_experts``, ``moe_top_k`` and ``moe_renormalize`` set.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from .counts import (  # noqa: F401 - members of the interface, and the new kernel's roofline
    forward_flops_per_token,
    kv_bytes_per_token,
    moe_ffn_bytes,
    moe_ffn_flops,
    param_count,
    train_flops_per_token,
)

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``):
#: ``dense_gqa``'s, with a handful of narrow experts, two a token.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "vocab_size": 256, "num_hidden_layers": 2,
    "max_position_embeddings": 128, "torch_dtype": "float32",
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``LlamaConfig`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import dataclasses

    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.llama import LlamaConfig

    lacks = {"qk_norm", "moe_renormalize"} - {f.name for f in dataclasses.fields(LlamaConfig)}
    if lacks:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout's ray_tpu.models.llama.LlamaConfig has no {sorted(lacks)}: the program "
            "here cannot run the olmoe family (QK-norm, dropless experts in the paged steps)"
        )
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    if model["hidden_size"] != model["num_attention_heads"] * model["head_dim"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / heads")
    for key, want in (("clip_qkv", None), ("attention_bias", False), ("rope_scaling", None),
                      ("tie_word_embeddings", False), ("hidden_act", "silu")):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    fields = dict(
        vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        mlp_hidden=model["intermediate_size"],  # the width of ONE expert
        max_seq_len=max_seq_len,
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=dtype,
        qk_norm=True,
        moe_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        moe_renormalize=bool(model["norm_topk_prob"]),
        # not in the catalog row; the reference's loss has no such term
        moe_aux_loss_coeff=float(model.get("router_aux_loss_coef", 0.0)),
    )
    fields.update(overrides)
    return LlamaConfig(**fields)


def server_class():
    from .server import BenchOlmoeServer

    return BenchOlmoeServer


def train_program() -> Tuple[Any, Any, Any]:
    """``init_sharded(cfg, mesh, rules, key, opt)``, ``make_train_step(cfg,
    opt, *, mesh, rules, remat, donate)`` and ``batch_sharding(mesh, rules)``
    of the program."""
    from ray_tpu.models.llama import batch_sharding, init_sharded, make_train_step

    return init_sharded, make_train_step, batch_sharding


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_expert_ffn(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's second reading of the
    correctness check (``server.py``): the reference's FFN of ONE block on
    normed activations h [B, T, D] float32, ``(out [B, T, D], margin [B, T])``."""
    from . import reference

    return reference.expert_ffn(layer_params, h, top_k=int(model["num_experts_per_tok"]))


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
