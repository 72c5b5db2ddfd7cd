"""The adapter to the system under test: how a configuration file becomes
the program's own config objects. The only benchmark module, besides the
cell drivers and the server subclass, that imports the program."""

from __future__ import annotations

from typing import Any, Dict


def llama_config(model: Dict[str, Any], *, max_seq_len: int, **overrides):
    """``LlamaConfig`` for a configuration file's ``model`` group (Hugging
    Face key names), unchanged widths."""
    import jax.numpy as jnp  # dtype names only: no array, no backend

    from ray_tpu.models.llama import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["torch_dtype"]]
    if model["hidden_size"] != model["num_attention_heads"] * model["head_dim"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / heads")
    if model.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    return LlamaConfig(
        vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        mlp_hidden=model["intermediate_size"],
        max_seq_len=max_seq_len,
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]),
        dtype=dtype,
        **overrides,
    )


def engine_config(engine: Dict[str, Any]):
    """``EngineConfig`` with the file's fields set and every other field at
    the program's default."""
    from ray_tpu.inference import EngineConfig

    fields = dict(engine)
    for key in ("prefill_buckets", "decode_buckets"):
        if key in fields:
            fields[key] = tuple(fields[key])
    return EngineConfig(**fields)
