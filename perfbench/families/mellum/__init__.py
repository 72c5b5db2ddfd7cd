"""The family of Mellum2-style decoders (``model_type`` ``mellum``;
Mellum2-12B-A2.5B-Instruct): grouped-query attention whose head width is a
key of its own (32 heads of 128 over a hidden size of 2304), layers of two
KINDS by ``layer_types`` (``sliding_attention``: the last ``sliding_window``
positions under the plain rotary table; ``full_attention``: every position
under YaRN's, ``rope_parameters`` a kind), a softmax router with the kept
gates renormalised over dropless gated SiLU experts of which a configuration
may hold a range (one chip's share of an expert-parallel deployment:
``num_experts`` in the file is the number held,
``deployment.num_experts_total`` the router's width and
``deployment.held_experts`` the range), no shared expert, untied head. The
program runs it through ``ray_tpu.models.llama`` with ``attn_head_dim``,
``layer_windows``, ``rope_scaling`` and ``moe_held`` set: the paged cache is
then one pool of blocks a KIND of layer (``models/interface.py::LayerGroup``),
and a window layer's blocks behind its window are given back while the
sequence runs.

The members are ``perfbench.families.INTERFACE``; the reference's equations
are in ``reference.py`` and the counts' in ``counts.py``, once each. JAX is
imported inside the functions that need it: the benchmark's own process
imports this module and stays off the chip.

Notes for the next family whose layers keep different parts of a sequence:

* the engine takes the first group's pool as ``num_blocks`` and sizes a window
  group's pool itself, from the decode batch, the window, the largest chunk
  and the block size (``InferenceEngine._window_pools``);
* the runner's ``prefill_chunk`` and ``decode`` take ONE row a group for a
  sequence (``[groups][max_blocks]``), and a window group's row must be slid
  chunk by chunk as the scheduler does: the harness's ``BenchServer.bench_check``
  hands one row, so ``server.py`` overrides it and hands the check's sequences
  to a scheduler of the engine's class over a block manager of its own;
* prefix reuse is switched off for such a model, export / import, the tier and
  a verify window are refused where the engine is made;
* ``train_program`` refuses: ``forward`` masks by kind on one device through
  XLA; the sharded training step wants the window in ``ops/attention.py``'s
  kernel (ROADMAP R4) and no training cell runs the family."""

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

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): one
#: period (W W W F), a window of two of the toy engine's blocks of 8, 8 experts
#: of which 4 are held, 2 a token, YaRN over an original context of 32.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 24,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4, "num_experts_per_tok": 2,
    "vocab_size": 256, "num_hidden_layers": 4, "max_position_embeddings": 128, "sliding_window": 16,
    "torch_dtype": "float32",
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse"] * 4,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                           "original_max_position_embeddings": 32, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
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

    lacks = {"attn_head_dim", "layer_windows", "rope_scaling", "moe_held"} - {
        f.name for f in dataclasses.fields(llama.LlamaConfig)
    }
    if lacks:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout's ray_tpu.models.llama.LlamaConfig has no {sorted(lacks)}: the program here "
            "cannot run the mellum family (window layers beside full ones, a pool of cache blocks a kind)"
        )
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("attention_bias", False), ("tie_word_embeddings", False), ("hidden_act", "silu"),
                      ("use_sliding_window", True), ("max_window_layers", 0)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    n = model["num_hidden_layers"]
    kinds = model["layer_types"][:n]
    if len(kinds) != n or set(kinds) - set(KINDS) or set(model["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError(f"the program runs layers of the kinds {KINDS}, each with a sparse MLP")
    ropes = model["rope_parameters"]
    sliding, full = ropes["sliding_attention"], ropes["full_attention"]
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn" or sliding["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program runs the plain table in window layers and YaRN over the same theta in full ones")
    lo, hi = model["deployment"]["held_experts"]
    total = model["deployment"]["num_experts_total"]
    if hi - lo != model["num_experts"]:
        raise ValueError("num_experts is the number of experts held: the width of held_experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=n,
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"],
        attn_head_dim=model["head_dim"],
        mlp_hidden=model["moe_intermediate_size"],  # the width of ONE expert; intermediate_size is read by no layer
        max_seq_len=max_seq_len, rope_theta=float(full["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
        layer_windows=tuple(model["sliding_window"] if kind == "sliding_attention" else 0 for kind in kinds),
        rope_scaling=llama.RopeScaling(
            factor=float(full["factor"]), original_max=int(full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"]),
        ),
        moe_experts=total, moe_held=(int(lo), int(hi)), moe_top_k=model["num_experts_per_tok"],
        moe_renormalize=bool(model["norm_topk_prob"]), moe_aux_loss_coeff=0.0,
    )
    fields.update(overrides)
    return llama.LlamaConfig(**fields)


def server_class():
    from .server import BenchMellumServer

    return BenchMellumServer


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the mellum family is served only: the program's sharded training step has no window mask "
        "(ops/attention.py; ROADMAP R4) and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_expert_ffn(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's second reading (``server.py``):
    the reference's FFN of ONE layer (its held part) on normed activations h
    [T, D] float32, ``(out [T, D], margin [T])``."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), layer_params, h)


def reference_attention(model: Dict[str, Any], layer_params, h, kind: str):
    """Beyond the interface, for the third and fourth readings: the
    reference's attention of ONE layer of that kind, causal over h [T, D]
    float32 from an empty context -> ``[T, D]``."""
    from . import reference

    return reference.attention(reference.sizes(model), layer_params, h, kind)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
