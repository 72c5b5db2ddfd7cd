"""The family of Kimi-Linear-style decoders: gated delta-rule layers (KDA)
and latent attention layers (MLA, NoPE) alternating 3 : 1, a leading dense
MLP and then sigmoid-routed experts beside a shared expert, of which a
configuration may hold a range (one chip's share of an expert-parallel
deployment: ``num_experts`` in the file is the number held,
``deployment.num_experts_total`` the router's width and
``deployment.held_experts`` the range). The program runs it through
``ray_tpu.models.kimi_linear``.

The members are ``perfbench.families.INTERFACE`` plus
``state_bytes_per_seq(model)``; the reference's equations are in
``reference.py`` and the counts' in ``counts.py``, once each. JAX is imported
inside the functions that need it: the benchmark's own process imports this
module and stays off the chip.

Worked notes, for the next family that keeps something a SEQUENCE beside
the rows a token (``perfbench/README.md`` is the benchmark's and says the
rest):

* The program's model describes TWO pools (``ray_tpu/models/interface.py``:
  ``CacheLayout`` for the layers that attend, ``StateLayout`` for the layers
  that recur). The runner's ``prefill_chunk`` and ``decode`` then need a
  state slot a sequence (``slot=`` / ``slots=``), which the harness's
  ``BenchServer.bench_check`` does not hand over: ``server.py`` overrides
  ``bench_check`` and drives the runner itself, its sequences on slots
  scattered over the pool (the engine is idle during the check, and a chunk
  at ``ctx_len`` 0 starts its slot from zeros inside the program, so nothing
  is cleaned up).
* Five readings decide ``correct`` (``server.py``): the logits; THE STATE
  POOL as the serving programs left it (``runner.state`` of the driven slots
  after chunked prefill and two dozen decode steps, against what the
  reference's recurrence leaves after the same tokens: the only reading that
  goes through the pool, its dtype, the slots and the chunk edges of the
  timed path); the expert FFN alone; a latent attention layer alone (what
  tells a rotated shared key: the logits' routing noise hides it); and a KDA
  layer ALONE: the program's mixer over two chunks with a padded tail and
  then decode steps, against the reference's token-by-token recurrence on the
  same normed activations (the mixer's OUTPUTS). The state kept in bfloat16,
  a chunk's state not carried into the next and the decay gate left out all
  read inside the logits' own noise; the pool's and the layer's readings
  tell each (the configuration file's ``correctness.reason``).
* The serving length comes from ``max_position_embeddings``
  (``harness/serve_cell.py``), a key this model's ``config.json`` does not
  have: the file carries it beside ``model_max_length`` and says so under
  ``assumed``.
* ``train_program`` refuses: the program has no sharded training step for
  this family, and no training cell runs it.
* The cell is listed by the per-layer entries that already read its
  counters (``.batch``, ``.moe``, ``.mla``, ``.longdoc``: one entry a reader
  since PR 37) and brings three of its own under ``.kda``, over
  ``engine_stats()["state_layout"]`` / ``["state_pool"]``. On a checkout
  without those keys the readers find nothing and say nothing."""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from . import counts  # noqa: F401
from .counts import (  # noqa: F401 - members of the interface
    forward_flops_per_token,
    kv_bytes_per_token,
    param_count,
    state_bytes_per_seq,
    train_flops_per_token,
)

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): two
#: periods (K K K A | K K A), the first layer dense, 8 experts of which 4 are
#: held, 2 a token.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_per_token": 2, "vocab_size": 256, "num_hidden_layers": 7,
    "first_k_dense_replace": 1, "max_position_embeddings": 128, "model_max_length": 128,
    "torch_dtype": "float32",
    "linear_attn_config": {"full_attn_layers": [4, 7], "head_dim": 16, "kda_layers": [1, 2, 3, 5, 6],
                           "num_heads": 4, "short_conv_kernel_size": 4},
    "deployment": {"what": "the toy: two chips share each layer, this one holds experts [0, 4) of 8",
                   "chips_sharing_each_layer": 2, "num_experts_total": 8, "held_experts": [0, 4]},
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``KimiLinearConfig`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    try:
        from ray_tpu.models.kimi_linear import KimiLinearConfig
    except ImportError as e:  # a checkout from before the program could run this family
        raise SystemExit(
            f"this checkout has no ray_tpu.models.kimi_linear ({e}): the program here cannot run "
            "the kimi_linear family (KDA layers with a per-sequence state pool beside a latent cache)"
        ) from None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("tie_word_embeddings", False), ("hidden_act", "silu"), ("mla_use_nope", True),
                      ("moe_router_activation_func", "sigmoid"), ("num_expert_group", 1),
                      ("topk_group", 1), ("moe_renormalize", True), ("moe_layer_freq", 1),
                      ("num_nextn_predict_layers", 0), ("q_lora_rank", None), ("rope_scaling", None)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    if model["num_key_value_heads"] != model["num_attention_heads"]:
        raise ValueError("the program runs one latent for all heads only")
    lin = model["linear_attn_config"]
    layers = sorted(lin["kda_layers"] + lin["full_attn_layers"])
    if layers != list(range(1, model["num_hidden_layers"] + 1)):
        raise ValueError("kda_layers and full_attn_layers must part the layers 1..num_hidden_layers")
    lo, hi = model["deployment"]["held_experts"]
    if hi - lo != model["num_experts"]:
        raise ValueError("num_experts is the number of experts held: the width of held_experts")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"], mla_layers=tuple(lin["full_attn_layers"]),
        n_heads=model["num_attention_heads"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], kda_gate_rank=lin["head_dim"],
        mlp_hidden=model["intermediate_size"], moe_hidden=model["moe_intermediate_size"],
        n_routed_experts=model["deployment"]["num_experts_total"], held_experts=(int(lo), int(hi)),
        n_shared_experts=model["num_shared_experts"], moe_top_k=model["num_experts_per_token"],
        routed_scaling_factor=float(model["routed_scaling_factor"]), max_seq_len=max_seq_len,
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return KimiLinearConfig(**fields)


def server_class():
    from .server import BenchKimiLinearServer

    return BenchKimiLinearServer


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the kimi_linear family is served only: the program has no sharded training step for it "
        "and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_logits_and_states(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]],
                                lengths: List[int]):
    """Beyond the interface, for this family's drive (``server.py``): the
    logits as :func:`reference_logits` and, from the same pass, per row and
    KDA layer what a sequence of the row's first ``lengths[row]`` tokens
    leaves in the layer: ``(S [H, dk, dv], tail [K - 1, 3 W])``."""
    from . import reference

    return reference.logits_at(model, params, tokens, picks, lengths)


def reference_expert_ffn(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's second reading (``server.py``):
    the reference's FFN of ONE expert layer on normed activations h [T, D]
    float32, ``(out [T, D], margin [T])``."""
    from . import reference

    return reference.expert_ffn(reference.sizes(model), layer_params, h)


def reference_kda(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's third reading (``server.py``):
    the reference's KDA mixer of ONE layer, the token-by-token recurrence from
    a zero state, on normed activations h [T, D] float32 -> ``[T, D]``."""
    from . import reference

    return reference.kda(reference.sizes(model), layer_params, h)


def reference_attention(model: Dict[str, Any], layer_params, h):
    """Beyond the interface, for this family's fourth reading (``server.py``):
    the reference's latent attention of ONE layer, causal over h [T, D]
    float32 from an empty context -> ``[T, D]``."""
    from . import reference

    return reference.attention(reference.sizes(model), layer_params, h)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
