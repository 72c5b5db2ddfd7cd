"""The family of Jamba-style decoders: Mamba-1 selective state-space layers
and a few multi-query attention layers without any position term (layer ``l``
attends iff ``l % attn_layer_period == attn_layer_offset``), a dense gated MLP
in every layer (``num_experts`` 1: nothing is routed), the embedding read again
as the head. The program runs it through ``ray_tpu.models.jamba``.

The members are ``perfbench.families.INTERFACE`` plus
``state_bytes_per_seq(model)``; the reference's equations are in
``reference.py`` and the counts' in ``counts.py``, once each. JAX is imported
inside the functions that need it: the benchmark's own process imports this
module and stays off the chip.

Worked notes (``families/kimi_linear/__init__.py`` has the first family that
keeps something a SEQUENCE beside the rows a token, ``families/lfm2`` the first
with a ``"kv"`` cache beside it; what differs here):

* The model's two pools are a ``"kv"`` ``CacheLayout`` of TWO layers (a K and
  a V row of ONE head of 128: 1,024 B a token) and a ``StateLayout``
  ``mamba1`` of 26 with two arrays (``ssm`` ``[16, 40, 128]`` float32,
  ``conv_tail`` ``[3 x 5120]``): 9,318,400 B a sequence. ``server.py``
  overrides ``bench_check`` and drives the runner with a state slot a
  sequence, as LFM2's does.
* Four readings decide ``correct`` (``server.py``): the logits after the
  prefill and after EVERY decode step; THE STATE POOL as the serving programs
  left it (``h`` and the tail), read twice; a Mamba mixer and an attention
  mixer ALONE (the configuration file's ``correctness.reason``).
* A checkout without ``ray_tpu/models/jamba.py`` ends the run as this module is
  imported (``families.of`` in the benchmark's own process, before any cluster
  starts): at once, non-zero, with no worker behind it.
* ``train_program`` refuses: the program has no backward of the scan, and no
  training cell runs this family.
* The cell joins the per-layer entries that already read its counters
  (``.batch``, ``.kda``, ``.mla``, ``.longdoc``) and brings two of its own: the
  two kernels of ``ops/selective_scan.py`` by their device operations' names."""

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
    if not any(os.path.exists(os.path.join(root, "models", "jamba.py")) for root in roots):
        raise SystemExit(
            "this checkout has no ray_tpu.models.jamba: the program here cannot run the jamba family "
            "(selective state-space layers with a per-sequence state pool beside a K/V cache)"
        )


_refuse_a_checkout_without_the_model()

#: The toy sizes of the CPU rehearsal (``tests/perfbench/rehearsal.py``): M M A
#: M M (one attention layer among four Mamba), 4 heads of 16 over ONE KV head,
#: 4 states a channel, a step size of rank 8.
TOY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1, "intermediate_size": 96,
    "vocab_size": 256, "num_hidden_layers": 5, "attn_layer_period": 5, "attn_layer_offset": 2,
    "mamba_d_state": 4, "mamba_dt_rank": 8, "max_position_embeddings": 128, "torch_dtype": "float32",
}


def model_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``JambaConfig`` for a configuration file's published keys (Hugging
    Face names), unchanged widths. Refuses what the program does not run."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.jamba import JambaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    for key, want in (("mamba_conv_bias", True), ("mamba_proj_bias", False), ("num_experts", 1),
                      ("tie_word_embeddings", True), ("hidden_act", "silu"), ("sliding_window", None)):
        if model.get(key, want) != want:
            raise ValueError(f"the program runs {key}={want!r} only, the file says {model[key]!r}")
    fields = dict(
        vocab_size=model["vocab_size"], dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        attn_period=model["attn_layer_period"], attn_offset=model["attn_layer_offset"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"],
        mlp_hidden=model["intermediate_size"], d_state=model["mamba_d_state"], d_conv=model["mamba_d_conv"],
        dt_rank=model["mamba_dt_rank"], expand=model["mamba_expand"], max_seq_len=max_seq_len,
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
    )
    fields.update(overrides)
    return JambaConfig(**fields)


def server_class():
    from .server import BenchJambaServer

    return BenchJambaServer


def train_program() -> Tuple[Any, Any, Any]:
    raise SystemExit(
        "the jamba family is served only: the program has no backward of the selective scan (and "
        "16 bytes a parameter of even one period of 14 layers fits no chip) and no training cell runs it"
    )


def reference_logits(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]]):
    from . import reference

    return reference.logits_at(model, params, tokens, picks)


def reference_logits_and_state(model: Dict[str, Any], params, tokens, picks: List[Tuple[int, int]],
                               ats: Sequence[Sequence[int]]):
    """Beyond the interface, for this family's drive (``server.py``): the
    logits as :func:`reference_logits` and, from the same pass, per row and
    Mamba layer what a sequence of the row's first ``at`` tokens leaves in the
    layer for each ``at`` of ``ats[row]``: ``(h [N, Di], tail [K - 1, Di])``."""
    from . import reference

    return reference.logits_at(model, params, tokens, picks, ats)


def reference_mamba(model: Dict[str, Any], layer_params, u):
    """The reference's Mamba mixer of ONE layer over u [T, D] float32 from a
    zero state and zeros before position 0 -> ``[T, D]``."""
    from . import reference

    return reference.mamba(reference.sizes(model), layer_params, u)[0]


def reference_attention(model: Dict[str, Any], layer_params, u):
    """The reference's attention of ONE layer, causal over u [T, D] float32
    from an empty context -> ``[T, D]``."""
    from . import reference

    return reference.attention(reference.sizes(model), layer_params, u)


def reference_loss(model: Dict[str, Any], params, tokens, targets) -> float:
    from . import reference

    return reference.next_token_loss(model, params, tokens, targets)
